"""Shared fixtures: a throwaway benchmark tree with tiny cells.

Run with ``pytest bench/tests`` from the checkout root, on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# tiny cells at float32: the program and the reference agree to ~1e-6
TINY_LIMITS = {"served_err": {"limit": 1e-3}, "step_err": {"limit": 1e-3}}


def make_tree(tmp: Path) -> Path:
    """A benchmark tree (BENCHMARK.json, configs, models, workloads,
    metrics, limits) holding the tiny cells ``tiny-lp2`` (one device) and
    ``tiny-lp4`` (four)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [
        {"name": "tiny-lp2", "config": "tiny", "traffic": "lp2-3step-closed",
         "chips": 1, "why": "test"},
        {"name": "tiny-lp4", "config": "tiny4",
         "traffic": "lp4-halo-3step-closed", "chips": 4, "why": "test"},
    ]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-lp2", "tiny-lp4"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(DATA / "configs", tmp / "configs")
    shutil.copytree(BENCH / "models", tmp / "models",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(BENCH / "workloads", tmp / "workloads")
    shutil.copytree(BENCH / "metrics", tmp / "metrics")
    (tmp / "limits").mkdir()
    for cell in ("tiny-lp2", "tiny-lp4"):
        (tmp / "limits" / f"{cell}.json").write_text(json.dumps(TINY_LIMITS))
    return tmp


@pytest.fixture
def tree(tmp_path):
    return make_tree(tmp_path)
