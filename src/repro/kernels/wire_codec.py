"""Pallas TPU kernels for the wire-codec hot paths.

Two fused kernels extend ``latent_blend`` (the LP stitch kernel) to the
quantized wire:

* :func:`int8_quantize` — per-slab max-abs scale + symmetric int8
  quantization in one ``pallas_call``: a two-phase grid first reduces
  ``max|x|`` into SMEM scratch (phase 0 sweeps the (row, column)
  blocks), then quantizes every block with the final scale (phase 1).  The jnp encode
  path reads the slab twice from HBM (amax reduce, then quantize); here
  each block is only re-streamed once with no intermediate f32 buffer.

* :func:`dequant_blend` — position-aware latent reconstruction
  (``latent_blend``'s Eqs. 15-17 math) fused with the int8 dequantize:
  quantized window predictions (K, W, F) + per-window scales go straight
  to the blended output without ever materializing the dequantized f32
  windows in HBM (K latent-sized round trips saved on top of
  latent_blend's fusion).

Grid layouts mirror ``latent_blend``: F is blocked, K (or the phase) is
the innermost grid dim so VMEM scratch accumulates across it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------------- quantize
def _quant_kernel(x_ref, wire_ref, scale_ref, amax_ref, *, qmax: int):
    phase = pl.program_id(0)
    ib = pl.program_id(1)
    jf = pl.program_id(2)
    last = (ib == pl.num_programs(1) - 1) & (jf == pl.num_programs(2) - 1)

    @pl.when((phase == 0) & (ib == 0) & (jf == 0))
    def _init():
        amax_ref[0] = 0.0

    @pl.when(phase == 0)
    def _scan():
        amax_ref[0] = jnp.maximum(
            amax_ref[0], jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)))
        )

    @pl.when(phase == 1)
    def _quantize():
        scale = jnp.maximum(amax_ref[0], 1e-20) / qmax
        q = jnp.clip(
            jnp.round(x_ref[...].astype(jnp.float32) / scale), -qmax, qmax
        )
        wire_ref[...] = q.astype(jnp.int8)

        @pl.when(last)
        def _emit_scale():
            scale_ref[0, 0] = scale


@functools.partial(jax.jit,
                   static_argnames=("qmax", "blk_r", "blk_f", "interpret"))
def int8_quantize(
    x: jnp.ndarray,            # (R, F) rows to quantize as ONE slab
    qmax: int = 127,
    blk_r: int = 256,
    blk_f: int = 2048,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused per-slab max-abs + int8 quantize: (wire (R, F) int8,
    scale (1, 1) f32).  Matches ``comm.codecs.IntCodec(bits=8).encode``
    bit-for-bit (same scale floor, same rounding).

    Tiled over rows AND columns, so VMEM per grid step is one
    ``(blk_r, blk_f)`` block whatever the slab's size.  During the max
    sweep the wire block index stays pinned at (0, 0), so no block is
    written back before the quantize phase fills it."""
    R, F = x.shape
    blk_r = min(blk_r, R)
    blk_f = min(blk_f, F)
    pr, pf = -R % blk_r, -F % blk_f
    if pr or pf:
        # zeros never win the max-abs and quantize to 0: safe padding
        x = jnp.pad(x, ((0, pr), (0, pf)))
    nb, nf = (R + pr) // blk_r, (F + pf) // blk_f
    kernel = functools.partial(_quant_kernel, qmax=qmax)
    wire, scale = pl.pallas_call(
        kernel,
        grid=(2, nb, nf),
        in_specs=[pl.BlockSpec((blk_r, blk_f),
                               lambda ph, ib, jf: (ib, jf))],
        out_specs=[
            pl.BlockSpec((blk_r, blk_f),
                         lambda ph, ib, jf: (ib * ph, jf * ph)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R + pr, F + pf), jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
    )(x)
    return wire[:R, :F], scale


# --------------------------------------------------------- dequant+blend
def _dequant_blend_kernel(wire_ref, scale_ref, w_ref, norm_ref, o_ref,
                          acc_ref, *, starts: Tuple[int, ...], window: int,
                          num_k: int):
    ikk = pl.program_id(1)

    @pl.when(ikk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    scale = scale_ref[ikk]
    pred = wire_ref[0].astype(jnp.float32) * scale     # fused dequantize
    w = w_ref[ikk, :]                                  # (W,)
    contrib = pred * w[:, None]

    def add_at(s):
        acc_ref[pl.ds(s, window), :] += contrib

    branches = [functools.partial(add_at, s) for s in starts]
    jax.lax.switch(ikk, branches)

    @pl.when(ikk == num_k - 1)
    def _finish():
        z = norm_ref[0, :]                             # (E,)
        o_ref[...] = (acc_ref[...] / z[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("starts", "window", "extent", "blk_f",
                              "interpret", "out_dtype"),
)
def dequant_blend(
    wire: jnp.ndarray,         # (K, W, F) int8 quantized window preds
    scales: jnp.ndarray,       # (K,) f32 per-window dequant scales
    weights: jnp.ndarray,      # (K, W) trapezoid masks
    normalizer: jnp.ndarray,   # (E,)
    starts: Tuple[int, ...],   # static per-partition offsets
    window: int,
    extent: int,
    blk_f: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """out[x, f] = (sum_k W_k[x-s_k] * scale_k * wire[k, x-s_k, f]) / Z[x]
    in one pass — the quantized-wire twin of ``latent_blend``.  The
    scales (whole, in SMEM) and the weights (whole, in VMEM) are indexed
    by the partition grid index inside the kernel."""
    K, W, F = wire.shape
    assert W == window and len(starts) == K
    blk_f = min(blk_f, F)
    pf = -F % blk_f
    if pf:
        wire = jnp.pad(wire, ((0, 0), (0, 0), (0, pf)))
    nf = (F + pf) // blk_f
    kernel = functools.partial(
        _dequant_blend_kernel, starts=tuple(starts), window=window, num_k=K,
    )
    out = pl.pallas_call(
        kernel,
        grid=(nf, K),
        in_specs=[
            pl.BlockSpec((1, window, blk_f), lambda jf, kk: (kk, 0, jf)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((K, window), lambda jf, kk: (0, 0)),
            pl.BlockSpec((1, extent), lambda jf, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((extent, blk_f), lambda jf, kk: (0, jf)),
        out_shape=jax.ShapeDtypeStruct((extent, F + pf), out_dtype),
        scratch_shapes=[pltpu.VMEM((extent, blk_f), jnp.float32)],
        interpret=interpret,
    )(wire, scales, weights, normalizer[None, :])
    return out[:, :F]
