"""Hybrid LP×TP halo engine: byte-model contract, compile-count
guarantee, mesh helpers, and the TP CFG-pair Phi_m building block."""
import subprocess
import sys
import textwrap

import pytest

from repro.core import comm_model as cm
from repro.core.spmd import LP_IMPLS, select_lp_impl
from repro.launch.mesh import parse_mesh


# ----------------------------------------------------------- pure helpers
def test_parse_mesh():
    assert parse_mesh("4x2") == (4, 2)
    assert parse_mesh("16X16") == (16, 16)
    assert parse_mesh("4") == (4, 1)
    for bad in ("1x2", "4x0", "4x2x2", "ax2", ""):
        with pytest.raises(ValueError):
            parse_mesh(bad)


def test_select_lp_impl_tp_aware():
    assert "halo_hybrid" in LP_IMPLS
    assert select_lp_impl(2) == "shard_map"
    assert select_lp_impl(2, tp=4) == "shard_map"   # break-even unchanged
    assert select_lp_impl(4) == "halo"
    assert select_lp_impl(4, tp=2) == "halo_hybrid"
    assert select_lp_impl(16, tp=16) == "halo_hybrid"


def test_comm_lp_halo_hybrid_model():
    cfg = cm.wan21_comm_config(49)
    # T parallel lp rings: group bytes scale linearly in T, per-device
    # payloads (the HLO contract) are T-independent
    one = cm.comm_lp_halo_hybrid(cfg, 4, 1, 0.5)
    assert one == cm.comm_lp_halo_codec(cfg, 4, 0.5, "fp32")
    assert cm.comm_lp_halo_hybrid(cfg, 4, 4, 0.5) == 4 * one
    step1 = cm.lp_halo_hybrid_step_collectives(cfg, 4, 1, 0.5, dim=1)
    step8 = cm.lp_halo_hybrid_step_collectives(cfg, 4, 8, 0.5, dim=1)
    assert step1 == step8
    assert step1 == cm.lp_halo_codec_step_collectives(cfg, 4, 0.5, dim=1,
                                                      codec="fp32")
    with pytest.raises(ValueError):
        cm.comm_lp_halo_hybrid(cfg, 4, 0, 0.5)
    # codec'd gspmd saves zero bytes by construction
    assert cm.comm_lp_gspmd_codec(cfg, 4, 0.5, "int8") == \
        cm.comm_lp_spmd(cfg, 4, 0.5)


# --------------------------------------------------- multi-device (slow)
HYBRID_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.analysis.hlo_analyzer import analyze
    from repro.comm import get_codec
    from repro.core import LPStepCompiler, comm_model as cm, lp_denoise
    from repro.core import plan_uniform
    from repro.core.hybrid import (
        lp_forward_halo_hybrid, tp_cfg_branch, tp_cfg_combine)
    from repro.core.lp_step import lp_forward_uniform
    from repro.diffusion.sampler import FlowMatchEuler
    from repro.launch.mesh import make_hybrid_mesh

    M, T = 4, 2
    mesh = make_hybrid_mesh(M, T)
    rng = np.random.default_rng(0)

    # ---- byte-model contract: modeled == measured EXACTLY per codec
    z = jnp.asarray(rng.normal(size=(26, 6, 4)).astype(np.float32))
    plan = plan_uniform(26, 2, M, 0.5)
    d = 4
    w1 = jnp.eye(d) * 0.1 + 0.05

    def tp_den(x):
        tp = jax.lax.axis_index("model")
        half = d // 2
        ws = jax.lax.dynamic_slice_in_dim(w1, tp * half, half, 0)
        xs = jax.lax.dynamic_slice_in_dim(x, tp * half, half, x.ndim - 1)
        part = jnp.einsum("...c,cd->...d", xs, ws)
        return jnp.tanh(x) * 0.5 + jax.lax.psum(part, "model")

    ccfg = cm.VDMCommConfig(
        latent_dims=(26, 6, 4), latent_channels=1, patch_sizes=(2, 1, 1),
        d_model=1, num_blocks=1, num_steps=1,
    )
    for name in ("fp32", "bf16", "int8"):
        c = None if name == "fp32" else name
        fn = jax.jit(lambda zz: lp_forward_halo_hybrid(
            tp_den, zz, plan, 0, mesh, codec=c))
        a = analyze(fn.lower(z).compile().as_text())
        want = cm.lp_halo_hybrid_step_collectives(
            ccfg, M, T, 0.5, dim=0, codec=name)
        for kind in ("all-gather", "collective-permute"):
            got = a.collective_bytes.get(kind, 0)
            assert got == want[kind], (name, kind, got, want)
        # the ONLY all-reduce is the intra-group Phi_m psum (never LP)
        n_ar = a.collective_counts.get("all-reduce", 0)
        assert n_ar <= 1, (name, a.collective_counts)
    print("BYTES-OK")

    # ---- compile-count guarantee: T-step denoise on the (M, T) mesh
    # with a residual codec still compiles <= 3 times (state in the
    # scan carry, hybrid collectives inside the compiled step)
    codec = get_codec("int8-residual")
    z5 = jnp.asarray(rng.normal(size=(1, 8, 12, 10, 4)).astype(np.float32))
    sampler = FlowMatchEuler(12)
    traces = {"n": 0}

    def den_step(w, t):
        traces["n"] += 1  # fires only while tracing
        g = tp_cfg_branch("model").astype(jnp.float32)  # exercise tp axis
        pred = jnp.tanh(w) * (0.1 + 0.01 * g) + w * 1e-4 * t
        return tp_cfg_combine(pred, "model", 1.0)

    fwd = lambda fn, zz, plan, axis, st: lp_forward_halo_hybrid(
        fn, zz, plan, axis, mesh, "data", "model",
        codec=codec, codec_state=st)
    comp = LPStepCompiler(
        den_step, sampler.update, M, 0.5, (1, 2, 2), (1, 2, 3),
        uniform=True, forward=fwd, codec=codec, mesh_shape=(M, T),
    )
    out = lp_denoise(None, z5, sampler, 12, M, 0.5, (1, 2, 2), (1, 2, 3),
                     uniform=True, compiler=comp)
    assert np.isfinite(np.asarray(out)).all()
    # counted on compiled steps, not Python traces: the first step's
    # latent is off the mesh and later ones come back mesh-replicated,
    # and jit keys its trace on that, so the first rotation dim is
    # traced once more by jax itself inside the same compiled step
    assert comp.compiles <= 3 and comp.hits >= 9, (comp.compiles, comp.hits)
    before, traced = comp.compiles, traces["n"]
    lp_denoise(None, z5, sampler, 12, M, 0.5, (1, 2, 2), (1, 2, 3),
               uniform=True, compiler=comp)
    assert comp.compiles == before  # second run fully cache-served
    assert traces["n"] == traced, traces  # ... and never retraced
    print("COMPILES-OK", comp.compiles, comp.hits)
    """
)


@pytest.mark.slow
def test_hybrid_bytes_contract_and_compile_count():
    res = subprocess.run(
        [sys.executable, "-c", HYBRID_SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},  # skip the TPU-runtime probe
        cwd="/root/repo",
        timeout=580,
    )
    assert res.returncode == 0, f"stdout={res.stdout}\nstderr={res.stderr}"
    assert "BYTES-OK" in res.stdout and "COMPILES-OK" in res.stdout
