"""SPMD LP engine equivalence tests (multi-device via 8 fake CPU devices).

These run in a subprocess so the 8-device XLA flag never leaks into other
tests (smoke tests must see 1 device).
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_uniform
from repro.core.lp_step import lp_forward_uniform
from repro.core.spmd import blend_windows, lp_forward_stacked, stack_windows


def test_stacked_matches_uniform_reference():
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(26, 6, 4)).astype(np.float32))
    plan = plan_uniform(26, 2, 4, 1.0)

    def denoise(x):
        return jnp.tanh(x) * 0.5 + x

    ref = lp_forward_uniform(denoise, z, plan, axis=0)
    out = lp_forward_stacked(denoise, z, plan, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blend_windows_identity():
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.normal(size=(24, 3)).astype(np.float32))
    plan = plan_uniform(24, 1, 3, 0.5)
    windows = stack_windows(z, plan, axis=0)
    out = blend_windows(windows, plan, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(z), atol=1e-5)


MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import plan_uniform
    from repro.core.lp_step import lp_forward_uniform
    from repro.core.spmd import lp_forward_shard_map, lp_forward_gspmd

    mesh = compat.make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(26, 6, 4)).astype(np.float32))
    plan = plan_uniform(26, 2, 4, 1.0)
    def denoise(x):
        return jnp.tanh(x) * 0.5 + x
    ref = lp_forward_uniform(denoise, z, plan, axis=0)
    with jax.set_mesh(mesh):
        # compile once, reuse the AOT executable for both the value check
        # and the collective check (compiles are slow on tiny CPU quotas)
        compiled_sm = jax.jit(
            lambda zz: lp_forward_shard_map(denoise, zz, plan, 0, mesh)
        ).lower(z).compile()
        out_sm = compiled_sm(z)
    # GSPMD engine on the same two-axis mesh (Auto axis types)
    out_gs = jax.jit(
        lambda zz: lp_forward_gspmd(denoise, zz, plan, 0, mesh)
    )(z)
    np.testing.assert_allclose(np.asarray(out_sm), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_gs), np.asarray(ref), atol=1e-5)

    # collective check: shard_map path must contain exactly one all-reduce
    hlo = compiled_sm.as_text()
    n_ar = hlo.count("all-reduce(")
    assert n_ar >= 1, "expected a psum in the LP reconstruction"
    print("OK", n_ar)
    """
)


@pytest.mark.slow
def test_shard_map_and_gspmd_match_reference_multidevice():
    res = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd="/root/repo",
        timeout=580,  # 8-fake-device XLA compiles crawl on tiny CPU quotas
    )
    assert res.returncode == 0, f"stdout={res.stdout}\nstderr={res.stderr}"
    assert "OK" in res.stdout
