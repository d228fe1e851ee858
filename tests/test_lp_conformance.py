"""LP engine conformance matrix — the single source of truth.

One parametrized suite asserting numerical equivalence across every LP
SPMD engine x K x rotation dim x wire codec, against the fp32 psum-math
reference (``lp_forward_uniform``).  Cells with an exact wire (fp32
codec, or no codec) must match to 1e-5; lossy codecs are gated at the
documented PSNR floors below (vs the fp32 reference — int8-family cells
sit >= 40 dB, int4 trades quality for an 8x wire and gets its own
documented floor; see docs/hybrid_lp_tp.md).

Engines (``ENGINE_CODECS`` is the support matrix — a future engine joins
the suite by adding a row here and a branch in the subprocess runner):

  * ``psum``        — ``core/spmd.lp_forward_shard_map`` (fp32 wire only)
  * ``gspmd``       — ``core/spmd.lp_forward_gspmd`` (stateless codecs,
                      value-faithful blend)
  * ``halo``        — ``core/spmd.lp_forward_halo`` (all codecs)
  * ``halo_hybrid`` — ``core/hybrid.lp_forward_halo_hybrid`` on a
                      ``(K, 2)`` mesh with a Megatron-style TP Phi_m
                      (all codecs)
  * ``halo_hybrid_ws`` — the hybrid engine with ``wire_shard=True``
                      (tp-sharded wire, same ``(K, 2)`` mesh, all
                      codecs incl. the residual scan-carry state).
                      These cells additionally assert BIT-equality
                      with the unsharded hybrid engine — sharding is
                      transport-only
  * ``halo_hybrid_ws4`` — wire-shard at T=4 (``(2, 4)`` mesh; K=2
                      only — 8 fake devices), int8 + int8-residual
  * ``simulate``    — ``comm.wire.simulate_halo_forward``, the
                      single-process mirror (all codecs; runs in-process
                      in the fast tier too)

The SPMD cells run on 8 fake CPU devices in one subprocess per K (the
device-count XLA flag must not leak into this process).
"""
import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import get_codec, init_halo_wire_state, simulate_halo_forward
from repro.core import plan_uniform
from repro.core.lp_step import lp_forward_uniform
from repro.distributed.collectives import halo_spec

# ------------------------------------------------------------ the matrix
KS = (2, 3, 4)
# z is (T, H, W, C); dim d partitions axis d with patch PATCHES[d]
Z_SHAPE = (8, 12, 10, 4)
PATCHES = (1, 2, 2)
R = 0.5

ALL_CODECS = ("fp32", "bf16", "int8", "int4", "int8-residual")
STATELESS = ("fp32", "bf16", "int8", "int4")
# displaced (stale-slab) halo cells: the dim-rotation flush makes the
# FIRST step of every run synchronous, so a single-pass cell must land
# exactly where its residual base does — well above the displaced
# envelope floor, which prices multi-step staleness (the multi-step
# staleness bound itself is property-tested in test_wire_codec.py).
DISPLACED = ("displaced:int8-residual", "displaced:int4-residual")
ENGINE_CODECS = {
    "psum": ("fp32",),            # the psum engine has no codec layer
    "gspmd": STATELESS,           # residual state needs the halo schedule
    "halo": ALL_CODECS + DISPLACED[:1],
    "halo_hybrid": ALL_CODECS + DISPLACED[:1],
    # tp-sharded wire: every codec incl. BOTH residual scan-carry
    # variants and BOTH displaced variants (whose state adds the
    # staleness flag) — the cells assert bit-equality with the
    # unsharded hybrid engine (output AND codec state)
    "halo_hybrid_ws": ALL_CODECS + ("int4-residual",) + DISPLACED,
    "simulate": ALL_CODECS,
}
# wire-shard at T=4: K=2 fits the (2, 4) mesh on 8 fake devices
WS4_CODECS = ("int8", "int8-residual")
# documented PSNR floors (dB) for lossy wires vs the fp32 psum reference,
# single forward pass on N(0,1) latents; exact cells use allclose 1e-5.
# The floors live in policy/envelope.py — they double as the quality
# envelope the step-policy autotuner plans against, and importing them
# here means the CI gate and the planner can never disagree.
from repro.policy.envelope import PSNR_ENVELOPE_DB

PSNR_FLOOR_DB = {k: v for k, v in PSNR_ENVELOPE_DB.items() if k != "fp32"}


def _psnr(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    mse = float(np.mean((a - b) ** 2))
    return float(10 * np.log10(float(np.abs(b).max()) ** 2 / max(mse, 1e-30)))


def _check_cell(out, ref, codec_name: str, tag: str) -> None:
    if codec_name == "fp32":
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, err_msg=tag
        )
    else:
        db = _psnr(out, ref)
        floor = PSNR_FLOOR_DB[codec_name]
        assert db >= floor, f"{tag}: {db:.1f} dB < {floor} dB floor"


def _cells_for(engine: str, K: int):
    for dim in range(3):
        for codec in ENGINE_CODECS[engine]:
            yield dim, codec


# --------------------------------------------- fast tier: simulate engine
def _den(x):
    return jnp.tanh(x) * 0.5 + x


@pytest.mark.parametrize("K", KS)
@pytest.mark.parametrize("dim", [0, 1, 2])
@pytest.mark.parametrize("codec_name",
                         ALL_CODECS + ("int4-residual",) + DISPLACED)
def test_simulate_engine_conformance(K, dim, codec_name):
    """The single-process mirror passes every cell of the matrix without
    needing fake devices — this is the tier-1 face of the suite."""
    rng = np.random.default_rng(7)
    z = jnp.asarray(rng.normal(size=Z_SHAPE).astype(np.float32))
    plan = plan_uniform(Z_SHAPE[dim], PATCHES[dim], K, R, dim)
    ref = lp_forward_uniform(_den, z, plan, axis=dim)
    codec = get_codec(codec_name)
    if codec.stateful:
        rest = tuple(s for i, s in enumerate(Z_SHAPE) if i != dim)
        st = init_halo_wire_state(codec, halo_spec(plan), rest)
        out, _ = simulate_halo_forward(_den, z, plan, dim, codec, st)
    else:
        out = simulate_halo_forward(_den, z, plan, dim, codec_name)
    _check_cell(out, ref, codec_name, f"simulate/K{K}/dim{dim}/{codec_name}")


# ------------------------------------------- slow tier: SPMD engine matrix
SPMD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.comm import get_codec, init_halo_wire_state
    from repro.core import plan_uniform
    from repro.core.hybrid import lp_forward_halo_hybrid
    from repro.core.lp_step import lp_forward_uniform
    from repro.core.spmd import (
        lp_forward_gspmd, lp_forward_halo, lp_forward_shard_map)
    from repro.distributed.collectives import halo_spec
    from repro.launch.mesh import make_hybrid_mesh

    K = %(K)d
    Z_SHAPE, PATCHES, R = %(Z_SHAPE)r, %(PATCHES)r, %(R)r
    mesh1 = Mesh(np.asarray(jax.devices()[:K]), ("data",))
    mesh2 = make_hybrid_mesh(K, 2)
    mesh4 = make_hybrid_mesh(K, 4) if K * 4 <= len(jax.devices()) else None

    rng = np.random.default_rng(7)
    z = jnp.asarray(rng.normal(size=Z_SHAPE).astype(np.float32))
    C = Z_SHAPE[-1]
    w1 = jnp.asarray(rng.normal(size=(C, C)).astype(np.float32)) * 0.1

    def den(x):  # same math every engine computes
        return jnp.tanh(x) * 0.5 + jnp.einsum("...c,cd->...d", x, w1)

    def make_tp_den(T):  # Megatron Phi_m: 1/T of the contraction per rank
        def tp_den(x):
            tp = jax.lax.axis_index("model")
            part = C // T
            ws = jax.lax.dynamic_slice_in_dim(w1, tp * part, part, 0)
            xs = jax.lax.dynamic_slice_in_dim(x, tp * part, part, x.ndim - 1)
            p = jnp.einsum("...c,cd->...d", xs, ws)
            return jnp.tanh(x) * 0.5 + jax.lax.psum(p, "model")
        return tp_den

    tp_den = make_tp_den(2)

    def run_hybrid(dim, name, plan, rest, mesh, tden, wire_shard):
        codec = get_codec(name)
        if codec.stateful:
            st = init_halo_wire_state(codec, halo_spec(plan), rest)
            return jax.jit(lambda zz, s: lp_forward_halo_hybrid(
                tden, zz, plan, dim, mesh, codec=codec, codec_state=s,
                wire_shard=wire_shard))(z, st)
        c = None if name == "fp32" else codec
        return jax.jit(lambda zz: lp_forward_halo_hybrid(
            tden, zz, plan, dim, mesh, codec=c,
            wire_shard=wire_shard))(z), None

    def run_cell(engine, dim, name, plan, rest):
        codec = get_codec(name)
        st = (init_halo_wire_state(codec, halo_spec(plan), rest)
              if codec.stateful else None)
        c = None if name == "fp32" else codec
        if engine == "psum":
            return jax.jit(lambda zz: lp_forward_shard_map(
                den, zz, plan, dim, mesh1, "data"))(z)
        if engine == "gspmd":
            return jax.jit(lambda zz: lp_forward_gspmd(
                den, zz, plan, dim, mesh1, "data", codec=c))(z)
        if engine == "halo":
            if st is not None:
                return jax.jit(lambda zz, s: lp_forward_halo(
                    den, zz, plan, dim, mesh1, "data", codec=codec,
                    codec_state=s))(z, st)[0]
            return jax.jit(lambda zz: lp_forward_halo(
                den, zz, plan, dim, mesh1, "data", codec=c))(z)
        if engine == "halo_hybrid":
            return run_hybrid(dim, name, plan, rest, mesh2, tp_den,
                              False)[0]
        raise ValueError(engine)

    cells = %(CELLS)r
    for engine, dim, name in cells:
        plan = plan_uniform(Z_SHAPE[dim], PATCHES[dim], K, R, dim)
        rest = tuple(s for i, s in enumerate(Z_SHAPE) if i != dim)
        ref = lp_forward_uniform(den, z, plan, axis=dim)
        extra = ""
        if engine in ("halo_hybrid_ws", "halo_hybrid_ws4"):
            # the wire-sharded engine must be BIT-identical to the
            # unsharded one (output and residual scan-carry state):
            # sharding only rearranges the transport
            T = 4 if engine == "halo_hybrid_ws4" else 2
            mesh = mesh4 if T == 4 else mesh2
            tden = make_tp_den(T)
            out, st_ws = run_hybrid(dim, name, plan, rest, mesh, tden, True)
            ref_out, st_un = run_hybrid(dim, name, plan, rest, mesh, tden,
                                        False)
            bit = bool(jnp.all(out == ref_out))
            if st_ws is not None:
                bit = bit and all(
                    bool(jnp.all(x == y)) for x, y in
                    zip(jax.tree.leaves(st_ws), jax.tree.leaves(st_un)))
            extra = f" bit={int(bit)}"
        else:
            out = run_cell(engine, dim, name, plan, rest)
        a = np.asarray(out, np.float64)
        b = np.asarray(ref, np.float64)
        mse = float(np.mean((a - b) ** 2))
        db = float(10 * np.log10(float(np.abs(b).max()) ** 2
                                 / max(mse, 1e-30)))
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        print(f"CELL {engine} dim={dim} codec={name} "
              f"psnr={db:.1f} rel={rel:.2e}{extra}")
    print(f"DONE {len(cells)}")
    """
)


def _run_matrix(K: int):
    cells = [
        (engine, dim, codec)
        for engine in ("psum", "gspmd", "halo", "halo_hybrid",
                       "halo_hybrid_ws")
        for dim, codec in _cells_for(engine, K)
    ]
    if K * 4 <= 8:  # the (K, 4) wire-shard mesh fits the fake devices
        cells += [
            ("halo_hybrid_ws4", dim, codec)
            for dim in range(3) for codec in WS4_CODECS
        ]
    res = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT % {
            "K": K, "Z_SHAPE": Z_SHAPE, "PATCHES": PATCHES, "R": R,
            "CELLS": cells,
        }],
        capture_output=True, text=True,
        env={"PYTHONPATH": os.path.join(REPO_ROOT, "src"),
             "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},  # skip the TPU-runtime probe
        cwd=REPO_ROOT,
        timeout=580,
    )
    assert res.returncode == 0, f"stdout={res.stdout}\nstderr={res.stderr}"
    lines = [l for l in res.stdout.splitlines() if l.startswith("CELL ")]
    assert f"DONE {len(cells)}" in res.stdout, res.stdout
    assert len(lines) == len(cells)
    return cells, lines


# --------------------------------------- scheduled codecs (step policy)
# A mid-denoise codec switch must be invisible: running a schedule
# [codec A on steps 1..k, codec B on steps k+1..T] must equal the
# composition of two fixed-codec runs over the same step ranges — exact
# for stateless codecs, and exact for residual codecs too because the
# error-feedback state resets at the segment boundary in BOTH paths.

class _OffsetSampler:
    """View of a sampler shifted by ``offset`` forward passes, so the
    composition's second run continues the SAME trajectory."""

    def __init__(self, base, offset):
        self._base = base
        self._offset = offset

    def timestep(self, i):
        return self._base.timestep(i + self._offset)

    def step_scalars(self, i):
        return self._base.step_scalars(i + self._offset)

    @property
    def update(self):
        return self._base.update


def _single_dim_z(seed=0):
    # spatial (8, 2, 2) with patches (1, 2, 2): only the temporal dim
    # rotates, so the schedule's segment boundary is the ONLY structural
    # break between the two runs being compared
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(1, 8, 2, 2, 3)).astype(np.float32))


@pytest.mark.parametrize("codec_a,codec_b", [
    ("fp32", "bf16"),
    ("bf16", "int8"),
    ("int8", "int4"),
    ("int8-residual", "int8"),
    ("int8-residual", "int4-residual"),
])
def test_scheduled_codec_equals_fixed_composition(codec_a, codec_b):
    from repro.core import LPStepCompiler, lp_denoise
    from repro.diffusion.sampler import FlowMatchEuler
    from repro.policy.schedule import segment_steps, trajectory_sigmas
    from repro.policy import parse_schedule

    steps, boundary = 6, 4  # codec A on 1..4, codec B on 5..6
    sampler = FlowMatchEuler(steps)
    sigmas = trajectory_sigmas(sampler, steps)
    thr = (sigmas[boundary - 1] + sigmas[boundary]) / 2
    spec = f"{codec_a}@{thr:.6f},{codec_b}"
    schedule = parse_schedule(spec)
    runs = segment_steps(schedule, sigmas)
    assert [(r.start, r.stop) for r in runs] == [
        (1, boundary), (boundary + 1, steps)]

    z = _single_dim_z(11)
    args = (2, 0.5, (1, 2, 2), (1, 2, 3))

    comp = LPStepCompiler(lambda w, t: _den(w) * (1 + 1e-4 * t),
                          sampler.update, *args[:2], args[2], args[3],
                          uniform=True, schedule=spec)
    scheduled = lp_denoise(None, z, sampler, steps, *args, uniform=True,
                           compiler=comp)

    def fixed(codec, z0, smp, n):
        c = LPStepCompiler(lambda w, t: _den(w) * (1 + 1e-4 * t),
                           smp.update, *args[:2], args[2], args[3],
                           uniform=True, codec=codec)
        return lp_denoise(None, z0, smp, n, *args, uniform=True,
                          compiler=c)

    z_mid = fixed(codec_a, z, sampler, boundary)
    composed = fixed(codec_b, z_mid, _OffsetSampler(sampler, boundary),
                     steps - boundary)
    np.testing.assert_allclose(
        np.asarray(scheduled), np.asarray(composed), atol=1e-5,
        err_msg=f"schedule {spec} != composition {codec_a}->{codec_b}",
    )
    # compile-count contract: <= 3 x num_segments (single rotation dim
    # here, so exactly one compile per segment)
    assert comp.compiles <= 3 * len(runs), (comp.compiles, len(runs))


def test_scheduled_cell_meets_min_segment_floor():
    """A scheduled run sits above the WORST segment codec's envelope
    floor vs the fp32 reference — the conservative bound the planner
    assumes (sigma credit only helps)."""
    from repro.core import LPStepCompiler, lp_denoise
    from repro.diffusion.sampler import FlowMatchEuler

    steps = 6
    sampler = FlowMatchEuler(steps)
    z = _single_dim_z(5)
    args = (2, 0.5, (1, 2, 2), (1, 2, 3))

    def run(**kw):
        c = LPStepCompiler(lambda w, t: _den(w) * (1 + 1e-4 * t),
                           sampler.update, *args[:2], args[2], args[3],
                           uniform=True, **kw)
        return lp_denoise(None, z, sampler, steps, *args, uniform=True,
                          compiler=c)

    ref = run(codec="fp32")
    out = run(schedule="int8-residual@0.7,bf16")
    db = _psnr(out, ref)
    assert db >= PSNR_FLOOR_DB["int8-residual"], db


@pytest.mark.slow
@pytest.mark.parametrize("K", KS)
def test_spmd_engine_conformance_matrix(K):
    """Every SPMD engine x dim x supported codec, on 8 fake CPU devices.

    Exact cells (fp32) must sit at numerical-noise PSNR; lossy cells at
    their documented floors.  ONE subprocess per K amortizes the ~50
    tiny XLA compiles."""
    cells, lines = _run_matrix(K)
    for (engine, dim, codec), line in zip(cells, lines):
        db = float(line.split("psnr=")[1].split()[0])
        rel = float(line.split("rel=")[1].split()[0])
        tag = f"{engine}/K{K}/dim{dim}/{codec}: {line}"
        if engine in ("halo_hybrid_ws", "halo_hybrid_ws4"):
            # transport-only rearrangement: sharded == unsharded, bitwise
            # (output AND residual scan-carry state)
            assert "bit=1" in line, f"{tag} not bit-equal to unsharded"
        if codec == "fp32":
            assert rel < 1e-5, tag
        else:
            assert db >= PSNR_FLOOR_DB[codec], (
                f"{tag} < {PSNR_FLOOR_DB[codec]} dB floor"
            )
