"""Finds a cell's pieces by name.

``BENCHMARK.json`` (checkout root) names each cell's configuration and
traffic mix; the files live under ``bench/``:

* ``configs/<config>.json``: the model configuration as it is run; its
  ``model`` names the architecture;
* ``models/<model>.py``: everything that belongs to one architecture:
  ``program(conf) -> (forward, cfg)``, ``make_params(key, arch,
  sharding)``, ``context(arch, key)``, ``step_extras(engine, req)``, the
  reference's ``window_fn(arch, quant, mesh, axis)`` and ``guide(pred,
  guidance)``, and ``step_flops(arch, latent)``.  The harness, the
  reference's windows, stitch and Euler step, and the metric readers
  hold nothing of any one architecture;
* ``workloads/<traffic>.json``: the traffic mix (LP degree, overlap,
  steps per request, batch, guidance, loop);
* ``limits/<cell>.json``: the limits of the numbers that decide
  ``correct``, with the readings they were set from;
* ``metrics/<metric>.py``: one reader per metric, ``read(rec)`` returning
  a number or ``None`` when the run holds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, base: Path = BENCH) -> dict:
    return _json(base / "configs" / f"{name}.json")


def traffic(name: str, base: Path = BENCH) -> dict:
    return _json(base / "workloads" / f"{name}.json")


def limits(cell_name: str, base: Path = BENCH) -> Optional[dict]:
    path = base / "limits" / f"{cell_name}.json"
    return _json(path) if path.exists() else None


def metrics_for(bench: dict, cell_name: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer ones."""
    out = []
    for m in bench["per_layer" if trace else "end_to_end"]:
        cells = m.get("workloads")
        if cells is None or cell_name in cells:
            out.append(m)
    return out


def freeze(arch: dict) -> tuple:
    """A configuration's ``arch`` block as a hashable key (lists become
    tuples), for caches of compiled programs."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def _load(kind: str, name: str, base: Path):
    path = base / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, base: Path = BENCH):
    return _load("metrics", metric, base).read


def model(name: str, base: Path = BENCH):
    """The architecture module ``models/<name>.py``."""
    return _load("models", name, base)
