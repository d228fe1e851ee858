"""A run with the timed path broken underneath reads ``correct`` false,
once for each fault the cells can have."""
import json
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from harness_run import run_cell

HERE = Path(__file__).resolve().parent


def _state_unchanged(monkeypatch):
    from repro.diffusion.sampler import FlowMatchEuler

    monkeypatch.setattr(FlowMatchEuler, "update",
                        lambda self, z, v, dt: z)


def _half_batch(monkeypatch):
    """The DiT runs the first half of its batch (the conditional rows)
    and hands it back for both halves."""
    from repro.models import dit

    full = dit.forward

    def half(params, z, t, ctx, cfg, **kw):
        b = z.shape[0] // 2
        out = full(params, z[:b], t[:b], ctx[:b], cfg, **kw)
        return jnp.concatenate([out, out], axis=0)

    monkeypatch.setattr(dit, "forward", half)


def _answer_altered(monkeypatch):
    """The served latent's first frame shifted where the engine makes it."""
    from repro.serving.engine import LPServingEngine

    made = LPServingEngine._denoise_batch

    def altered(self, reqs, snapshot=None):
        out = made(self, reqs, snapshot)
        for r in out:
            r.latent = r.latent.at[:, 0].add(1.0)
        return out

    monkeypatch.setattr(LPServingEngine, "_denoise_batch", altered)


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "answer_altered"])
def test_one_device_faults(tree, monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    result = run_cell(tree, "tiny-lp2")
    assert result["correct"] is (fault is None), result["check"]


@pytest.mark.parametrize("fault", [None, "no_exchange"],
                         ids=["sound", "no_exchange"])
def test_four_device_exchange_left_out(tree, fault):
    args = [sys.executable, str(HERE / "harness_run.py"), str(tree),
            "tiny-lp4"] + ([fault] if fault else [])
    p = subprocess.run(args, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is (fault is None), result["check"]
