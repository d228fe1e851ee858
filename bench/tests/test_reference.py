"""The plain reference against the engine at a small size, its LP
geometry against the program's plan, and the control judged not
correct."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness, reference, spec

from conftest import DATA
from harness_run import run_cell

TINY = json.loads((DATA / "configs" / "tiny.json").read_text())["arch"]
WAN = spec.model("wan21-dit-1.3b")


@pytest.mark.parametrize("latent,k", [((5, 60, 104), 2), ((21, 60, 104), 4),
                                      ((4, 8, 12), 2), ((4, 8, 16), 4)])
def test_windows_match_the_program_plan(latent, k):
    from repro.core.uniform import plan_uniform

    for dim in range(3):
        patch = (1, 2, 2)[dim]
        if latent[dim] // patch < k:
            continue
        plan = plan_uniform(latent[dim], patch, k, 0.5, dim)
        starts, size, w, norm = reference.windows(latent[dim], patch, k, 0.5)
        assert tuple(starts) == plan.starts and size == plan.window
        for j in range(k):
            np.testing.assert_allclose(w[j], plan.weight_1d(j), rtol=1e-6)
        np.testing.assert_allclose(norm, plan.normalizer(), rtol=1e-6)


def test_rotation_and_schedule():
    assert reference.rotation((5, 60, 104), (1, 2, 2), 2, 3) == [0, 1, 2]
    assert reference.rotation((2, 60, 104), (1, 2, 2), 4, 4) == [1, 2, 1, 2]
    np.testing.assert_allclose(reference.sigmas(3), [1, 6 / 7, 0.6, 0],
                               rtol=1e-12)


def test_reference_matches_the_engine_at_small_size(tree):
    cell = harness.load_cell("tiny-lp2", 5, root=tree, base=tree)
    served = harness.Served(cell, jax.devices())
    req = served.request(0)
    latent = np.asarray(served.serve(req), np.float64)[0]
    ref = reference.Reference(cell.model, cell.arch, served.params)
    got, traj = harness.check_request(served, req, latent, ref)
    # float32 program, float32 reference: summation order only
    assert got["served_err"] < 1e-4 and got["step_err"] < 1e-4
    assert served.replay_compiles == 0
    assert np.linalg.norm(traj[-1] - traj[0]) > 0.5 * np.linalg.norm(traj[0])


def test_the_control_is_judged_not_correct(tree, monkeypatch):
    """The reference with float8 linear layers, put in the DiT's place,
    fails the check that the float32 program passes."""
    from repro.models import dit

    def fp8_forward(params, z, t, ctx, cfg, **kw):
        rows = [WAN.velocity(params, z[b], t[b], ctx[b], TINY, "fp8")
                for b in range(z.shape[0])]
        return jnp.stack(rows).astype(z.dtype)

    sound = run_cell(tree, "tiny-lp2")
    assert sound["correct"] is True
    monkeypatch.setattr(dit, "forward", fp8_forward)
    control = run_cell(tree, "tiny-lp2")
    assert control["correct"] is False
    assert control["check"]["step_err"]["value"] > \
        3 * control["check"]["step_err"]["limit"]
