"""Pallas TPU kernel: fused position-aware latent reconstruction
(paper Eqs. 15-17 — the LP stitching hot path).

Computes, for the uniform-window plan,

    out[x, f] = ( sum_k W_k[x - s_k] * preds[k, x - s_k, f] ) / Z[x]

in ONE pass over the output: the jnp reference materializes K weighted
scatter buffers + an fp32 accumulator (K+2 latent-sized HBM round trips);
the kernel keeps the accumulator tile in VMEM and writes each output tile
once.

Layout: preds (K, W, F) where the partition dim is dim 1 and F flattens
every other latent dim.  Grid (F_blocks, K) — K innermost so the output
tile accumulates across partitions in VMEM scratch:

    preds block (1, W, bf)      weights (K, W), whole, row k read in-kernel
    out block   (E, bf)         acc scratch (E, bf) f32

The weights go in whole because a (1, W) block over the (K, W) array has
a second-to-last block dim of 1, which the TPU lowering refuses.  Starts
are static (partition geometry is compile-time), so the scatter offset
per k is a constant-indexed slice.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(preds_ref, w_ref, norm_ref, o_ref, acc_ref, *,
            starts: Tuple[int, ...], window: int, num_k: int):
    ikk = pl.program_id(1)

    @pl.when(ikk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pred = preds_ref[0].astype(jnp.float32)          # (W, bf)
    w = w_ref[ikk, :]                                 # (W,)
    contrib = pred * w[:, None]

    # static scatter offset per partition index
    def add_at(s):
        acc_ref[pl.ds(s, window), :] += contrib

    branches = [functools.partial(add_at, s) for s in starts]
    jax.lax.switch(ikk, branches)

    @pl.when(ikk == num_k - 1)
    def _finish():
        z = norm_ref[0, :]                            # (E,)
        o_ref[...] = (acc_ref[...] / z[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("starts", "window", "extent", "blk_f",
                              "interpret"),
)
def latent_blend(
    preds: jnp.ndarray,        # (K, W, F)
    weights: jnp.ndarray,      # (K, W) trapezoid masks
    normalizer: jnp.ndarray,   # (E,)
    starts: Tuple[int, ...],   # static per-partition offsets
    window: int,
    extent: int,
    blk_f: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    K, W, F = preds.shape
    assert W == window and len(starts) == K
    blk_f = min(blk_f, F)
    pf = -F % blk_f
    if pf:
        preds = jnp.pad(preds, ((0, 0), (0, 0), (0, pf)))
    nf = (F + pf) // blk_f
    kernel = functools.partial(
        _kernel, starts=tuple(starts), window=window, num_k=K,
    )
    out = pl.pallas_call(
        kernel,
        grid=(nf, K),
        in_specs=[
            pl.BlockSpec((1, window, blk_f), lambda jf, kk: (kk, 0, jf)),
            pl.BlockSpec((K, window), lambda jf, kk: (0, 0)),
            pl.BlockSpec((1, extent), lambda jf, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((extent, blk_f), lambda jf, kk: (0, jf)),
        out_shape=jax.ShapeDtypeStruct((extent, F + pf), preds.dtype),
        scratch_shapes=[pltpu.VMEM((extent, blk_f), jnp.float32)],
        interpret=interpret,
    )(preds, weights, normalizer[None, :])
    return out[:, :F]
