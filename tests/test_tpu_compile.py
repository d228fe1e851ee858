"""Compiles of the chip path for a described TPU v5e — no chip needed.

jaxlib ships the TPU compiler, which compiles for a topology that is
described rather than attached.  That catches what interpret mode cannot:
block shapes the Mosaic lowering refuses, kernels that overflow VMEM, and
programs that do not fit the chip's HBM.  Nothing runs, so nothing here
says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library at a time, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import plan_uniform
from repro.kernels.latent_blend import latent_blend
from repro.kernels.wire_codec import dequant_blend, int8_quantize

# HBM the v5e compiler reports as usable per chip (of its 16 GB); it
# checks only a program's temporaries, so arguments are added by hand
V5E_USABLE_HBM = 15.75e9
# temporaries of the one-chip 17-frame step: 6.92 / 6.70 / 6.92 GB (T/H/W)
# with attention as the chunked f32 einsum scan, 0.77 / 0.75 / 0.77 GB
# with the DiT flash kernel, which keeps no score tile in HBM
SMOKE_STEP_TEMP = 1.0e9

# (latent T x H x W, K): the one-chip smoke's 17 frames at 480p and one
# chip's share of the four-chip 81-frame plan
SMOKE = ((5, 60, 104), 2)
FOUR_CHIP = ((21, 60, 104), 4)
CHANNELS = 16
PATCHES = (1, 2, 2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compile could be written to a persistent cache
    # but never read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _window(latent_k, dim, batch=1):
    """(plan, F) of one rotation window: F flattens the other latent dims,
    the channels and the batch."""
    latent, k = latent_k
    plan = plan_uniform(latent[dim], PATCHES[dim], k, 0.5, dim)
    rest = int(np.prod([s for i, s in enumerate(latent) if i != dim]))
    return plan, rest * CHANNELS * batch


# an HLO instruction line: (name, result shape, opcode)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(", re.M)
_F32_HEADS = re.compile(r"f32\[[\d,]*,12,128\]")


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dim", [0, 1, 2])
@pytest.mark.parametrize("latent_k", [SMOKE, FOUR_CHIP],
                         ids=["k2_17f", "k4_81f"])
def test_latent_blend_compiles_for_v5e(one_chip, latent_k, dim, dtype):
    plan, F = _window(latent_k, dim)
    K = plan.num_partitions
    fn = jax.jit(lambda p, w, z: latent_blend(
        p, w, z, plan.starts, plan.window, plan.extent, interpret=False))
    _assert_kernel(fn.lower(
        _sds((K, plan.window, F), dtype, one_chip),
        _sds((K, plan.window), jnp.float32, one_chip),
        _sds((plan.extent,), jnp.float32, one_chip)).compile())


@pytest.mark.parametrize("dim", [0, 1, 2])
def test_dequant_blend_compiles_for_v5e(one_chip, dim):
    plan, F = _window(SMOKE, dim)
    K = plan.num_partitions
    fn = jax.jit(lambda q, s, w, z: dequant_blend(
        q, s, w, z, plan.starts, plan.window, plan.extent, interpret=False))
    _assert_kernel(fn.lower(
        _sds((K, plan.window, F), jnp.int8, one_chip),
        _sds((K,), jnp.float32, one_chip),
        _sds((K, plan.window), jnp.float32, one_chip),
        _sds((plan.extent,), jnp.float32, one_chip)).compile())


@pytest.mark.parametrize("latent_k,dim,batch", [
    (SMOKE, 0, 1), (SMOKE, 1, 1), (SMOKE, 2, 1),
    (FOUR_CHIP, 0, 4),   # 12 x 399,360: VMEM must not grow with the slab
], ids=["k2_17f_T", "k2_17f_H", "k2_17f_W", "k4_81f_T_b4"])
def test_int8_quantize_compiles_for_v5e(one_chip, latent_k, dim, batch):
    plan, F = _window(latent_k, dim, batch)
    fn = jax.jit(lambda x: int8_quantize(x, interpret=False))
    _assert_kernel(fn.lower(
        _sds((plan.window, F), jnp.float32, one_chip)).compile())


@pytest.mark.parametrize("rows,sq,skv,rope", [
    (2, 7800, 7800, False), (2, 7540, 7540, False),    # one chip: K=2
    (2, 7800, 512, False),
    (1, 18720, 18720, False), (1, 17472, 17472, False),  # a K=4 window
    (1, 15750, 15750, False),
    (2, 7540, 7540, True), (1, 18720, 18720, True), (1, 15750, 15750, True),
], ids=["17f_TW", "17f_H", "17f_cross", "81f_T", "81f_H", "81f_W",
        "17f_H_rope", "81f_T_rope", "81f_W_rope"])
def test_dit_attention_compiles_for_v5e(one_chip, rows, sq, skv, rope):
    """The DiT flash kernel at WAN2.1-1.3B's 12 heads of 128 on the
    cells' window lengths (the CFG pair is the batch, LP windows a
    vmapped axis), with the block sizes ``ops`` picks; with ``rope``,
    the ``dit_rope`` passes in front of it on one table pair for all
    windows, as the engine's step has it."""
    from repro.kernels import ops

    q = _sds((rows, 2, sq, 12, 128), jnp.bfloat16, one_chip)
    kv = _sds((rows, 2, skv, 12, 128), jnp.bfloat16, one_chip)
    args = (q, kv, kv)
    if rope:
        args += (_sds((sq, 128), jnp.float32, one_chip),) * 2
    fn = jax.jit(lambda q, k, v, *tables: jax.vmap(
        lambda q, k, v: ops.dit_attention(
            q, k, v, rope=tables or None, interpret=False))(q, k, v))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("dit_rope" in text) == rope


@pytest.mark.parametrize("dim", [0, 1, 2])
def test_smoke_guided_step_fits_v5e(one_chip, dim, monkeypatch):
    """The one-chip engine's compiled LP step at WAN2.1-1.3B's published
    width: K=2 windows x CFG pair on the 17-frame latent, batch 1, with
    the stitch kernel compiled in and both attention sub-blocks in the
    DiT flash kernel.  Temporaries plus arguments (the parameters) must
    fit the chip, and the temporaries stay under the kernel's bound."""
    from repro import models
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import dit
    from repro.obs.scopes import scope_map
    from repro.serving.engine import LPServingEngine

    # this process's backend is the CPU; steer the step onto the chip's
    # kernel path the way the TPU backend selects it
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = get_config("wan21-dit-1.3b")
    latent, k = SMOKE
    engine = LPServingEngine(dit.forward, None, cfg, num_partitions=k,
                             num_steps=3, max_batch=1)
    comp = engine._compiler
    comp.use_kernel = True
    params = jax.eval_shape(models.build(cfg).init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), params)
    ctx = _sds((1, cfg.context_len, cfg.context_dim), jnp.float32, one_chip)
    scalar = _sds((), jnp.float32, one_chip)
    extras = (params, ctx, ctx, scalar)
    z = _sds((1, *latent, cfg.latent_channels), jnp.float32, one_chip)
    step = comp.step_fn(dim, z, 1, np.float32(0.0), extras)
    compiled = step.lower(z, scalar, scalar, extras).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    scopes = scope_map(text)
    assert {scope for name, scope in scopes.items()
            if name.startswith("dit_flash_attention")} == {
        "dit.self_attn", "dit.cross_attn"}
    assert {scope for name, scope in scopes.items()
            if name.startswith("dit_rope")} == {"dit.self_attn"}
    # the kernels take the projections as they are: no padding, slicing
    # or f32 copy of q/k/v (B, S, 12 heads, 128) around either attention
    glue = [(name, shape, op) for name, shape, op in _INSTRUCTION.findall(text)
            if scopes.get(name) in ("dit.self_attn", "dit.cross_attn")
            and (op in ("pad", "slice") or _F32_HEADS.search(shape))]
    assert not glue, glue[:8]
    mem = compiled.memory_analysis()
    total = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert mem.argument_size_in_bytes > 4.4e9   # the parameters are args
    assert total < V5E_USABLE_HBM, (mem.temp_size_in_bytes,
                                    mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < SMOKE_STEP_TEMP, mem.temp_size_in_bytes


# temporaries of HunyuanVideo's quarter-depth K=2 step at 9x68x120
# (33 frames at 544x960): 3.67 / 4.01 / 3.67 GB (T/H/W) on top of
# 6.96 GB of parameters, 10.97 GB at most of the chip's 15.75
HYV_STEP_TEMP = 4.5e9


def test_hunyuan_video_step_fits_v5e(one_chip, monkeypatch):
    """The one-chip engine's compiled LP step for HunyuanVideo's MMDiT at
    published widths and a quarter of its depth (5 double + 10 single
    blocks, the benchmark's configuration): K=2 windows of the 33-frame
    544x960 latent on the H rotation (the largest temporaries), one
    forward per window with the guidance embedded, joint attention over
    18,076 tokens in the DiT flash kernel."""
    import sys
    from pathlib import Path

    from repro.kernels import ops
    from repro.models import hunyuan_video
    from repro.obs.scopes import scope_map
    from repro.serving.engine import LPServingEngine

    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from benchlib import spec

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    conf = spec.config("hunyuanvideo-544p-33f", bench)
    forward, cfg = spec.model(conf["model"], bench).program(conf)
    engine = LPServingEngine(forward, None, cfg, num_partitions=2,
                             num_steps=3, max_batch=1)
    comp = engine._compiler
    comp.use_kernel = True
    params = jax.eval_shape(lambda k: hunyuan_video.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), params)
    cond = {"text": _sds((1, 256, 4096), jnp.float32, one_chip),
            "pooled": _sds((1, 768), jnp.float32, one_chip)}
    scalar = _sds((), jnp.float32, one_chip)
    extras = (params, cond, scalar)
    z = _sds((1, *conf["latent"], cfg.latent_channels), jnp.float32,
             one_chip)
    step = comp.step_fn(1, z, 1, np.float32(0.0), extras)
    compiled = step.lower(z, scalar, scalar, extras).compile()
    _assert_kernel(compiled)
    scopes = scope_map(compiled.as_text())
    assert {scope for name, scope in scopes.items()
            if name.startswith("dit_flash_attention")} == {
        "mmdit.refiner", "mmdit.double_attn", "mmdit.single"}
    mem = compiled.memory_analysis()
    total = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert mem.argument_size_in_bytes > 6.9e9   # the parameters are args
    assert total < V5E_USABLE_HBM, (mem.temp_size_in_bytes,
                                    mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < HYV_STEP_TEMP, mem.temp_size_in_bytes
