"""Pallas TPU kernels: bidirectional (DiT) flash attention with bf16 MXU
operands, and the RoPE pass that feeds it.

The DiT's self-attention (every video token sees every other) and its
cross-attention (video tokens over the text context) are one operation:
softmax(q k^T / sqrt(D)) v with no mask.  ``dit_attention`` takes q, k
and v as the projections produce them, plus optional RoPE tables, and
runs it as one online-softmax sweep per (row, head, q block):

    q block   (bq, D)     bf16, scaled by 1/sqrt(D) before the sweep
    k/v block (bkv, D)    bf16, swept in bkv_compute-wide chunks
    m, l      (bq, 128)   f32 scratch, lane-replicated running max / sum
    acc       (bq, D)     f32 scratch

Both products take bf16 operands and accumulate in f32 on the MXU; the
running max, sum and the output accumulator stay f32, and the
probabilities are cast to bf16 only as the PV operand.

Layout: q/k/v stay (B, S, H*D) as the projections produce them, and a
block (bq, D) at column block h is head h, so no transpose surrounds the
kernel.  A head size that is not a multiple of the 128 lanes is
zero-padded to one (zero columns change no score, and the padded output
columns are dropped).

Ragged tails are read in place: lengths need not be block multiples
(7,800 tokens at 17 frames), and nothing pads the sequences.  The grid
runs over ``cdiv`` of each length; the last q block is partial and its
writes past Sq are discarded.  Only the last kv block masks the keys
past Skv, by a static compare, and zeroes the values there (a partial
block's rows past the array hold unspecified bits, and 0 * NaN is NaN);
its chunks that hold no key are skipped outright.  Every other block
runs unmasked.

RoPE (``rope = (cos, sin)``, each (S, D) f32 in rotate-half form: cos
over both halves, sin as [-sin, +sin]): ``dit_rope`` rotates q and k in
one pass each, ``x * cos + swap_halves(x) * sin`` with the halves
swapped by a lane roll, bf16 in, f32 math, bf16 out.  q's pass folds
the 1/sqrt(D) scale into its tables, so it replaces the scale pass.  The
flash kernel does not rotate k itself: it would fetch k's tables again
for every q block.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30     # finite: exp(NEG_INF - m) is 0 and never inf - inf
LANES = 128
NT = (((1,), (1,)), ((), ()))   # contract the last dims: q @ k^T
NN = (((1,), (0,)), ((), ()))


def _lanes(x, width):
    """(bq, 128) lane-replicated -> (bq, width) by whole-vreg copies."""
    return jnp.tile(x, (1, pl.cdiv(width, LANES)))[:, :width]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bkv: int, bkv_compute: int, num_kv_blocks: int, tail: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(c, valid):
        """Fold kv rows [c*bkv_compute, +bkv_compute) of the block into
        the carry; ``valid`` < bkv_compute masks the rows past it."""
        sl = pl.ds(c * bkv_compute, bkv_compute)
        s = jax.lax.dot_general(q_ref[...], k_ref[sl, :], NT,
                                preferred_element_type=jnp.float32)
        v = v_ref[sl, :]
        if valid < bkv_compute:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < valid, s, NEG_INF)
            # the rows past the array hold unspecified bits: 0 * NaN is NaN
            row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < valid, v, jnp.zeros_like(v))
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bkv_compute))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_next
        pv = jax.lax.dot_general(p.astype(v.dtype), v, NN,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = _lanes(alpha, acc_ref.shape[-1]) * acc_ref[...] + pv

    def sweep(valid_rows):
        for c in range(bkv // bkv_compute):
            valid = valid_rows - c * bkv_compute
            if valid > 0:                 # a chunk past the keys: skip
                chunk(c, min(valid, bkv_compute))

    if tail == bkv:
        sweep(bkv)
    else:
        pl.when(j < num_kv_blocks - 1)(lambda: sweep(bkv))
        pl.when(j == num_kv_blocks - 1)(lambda: sweep(tail))

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        inv = 1.0 / l_ref[...]
        o_ref[...] = (acc_ref[...] * _lanes(inv, acc_ref.shape[-1])
                      ).astype(o_ref.dtype)


def _pad_to(x, axis, multiple):
    pad = -x.shape[axis] % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_halves(x, width):
    """Zero-pad each half of the last axis to ``width // 2``, so that a
    rotate-half pair stays ``width // 2`` lanes apart."""
    half = x.shape[-1] // 2
    if 2 * half == width:
        return x
    return jnp.concatenate([_pad_to(h, x.ndim - 1, width // 2)
                            for h in jnp.split(x, 2, axis=-1)], axis=-1)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, scale: float):
    x = x_ref[...].astype(jnp.float32)
    cos, sin = cos_ref[...], sin_ref[...]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    swapped = pltpu.roll(x, x.shape[-1] // 2, 1)      # [x2, x1]
    o_ref[...] = (x * cos + swapped * sin).astype(o_ref.dtype)


def dit_rope(x, cos, sin, *, scale: float = 1.0, block_s: int = 1024,
             interpret: bool = False):
    """``scale * (x * cos + swap_halves(x) * sin)`` for each head of
    ``x`` (B, S, H*Dp), with (S, Dp) f32 rotate-half tables: one pass,
    the math in f32, the result in x's dtype."""
    B, S, W = x.shape
    Dp = cos.shape[-1]
    bs = min(block_s, S + (-S % 16))
    table = pl.BlockSpec((bs, Dp), lambda i, b, h: (i, 0))
    head = pl.BlockSpec((None, bs, Dp), lambda i, b, h: (b, i, h))
    return pl.pallas_call(
        functools.partial(_rope_kernel, scale=scale),
        # heads innermost: a table block is fetched once per row block
        grid=(pl.cdiv(S, bs), B, W // Dp),
        in_specs=[head, table, table],
        out_specs=head,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="dit_rope",
    )(x, cos, sin)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_kv", "block_kv_compute", "interpret"),
)
def dit_attention(
    q: jnp.ndarray,            # (B, Sq, H, D)
    k: jnp.ndarray,            # (B, Skv, H, D)
    v: jnp.ndarray,            # (B, Skv, H, D)
    rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (S, D) f32 x2
    block_q: int = 1024,
    block_kv: int = 2048,
    block_kv_compute: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Unmasked softmax(q k^T / sqrt(D)) v, (B, Sq, H, D), for q, k and v
    of one dtype (bf16 on the DiT's path); with ``rope``, q and k are
    first rotated by its rotate-half tables (self-attention, Sq = Skv)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    dt = q.dtype
    scale = 1.0 / math.sqrt(D)
    Dp = D + (-D % LANES)
    # blocks no longer than the (lane-rounded) sequences they tile
    bq = min(block_q, Sq + (-Sq % LANES))
    bkv = min(block_kv, Skv + (-Skv % LANES))
    bkc = math.gcd(min(block_kv_compute, bkv), bkv)
    if rope is None:
        q = (q.astype(jnp.float32) * scale).astype(dt)
        q, k = (_pad_to(x, 3, LANES) for x in (q, k))
    else:
        q, k = (_pad_halves(x, Dp) for x in (q, k))
        cos, sin = (_pad_halves(t.astype(jnp.float32), Dp) for t in rope)
    q, k, v = (x.reshape(B, x.shape[1], H * Dp)
               for x in (q, k, _pad_to(v, 3, LANES)))
    if rope is not None:
        q = dit_rope(q, cos, sin, scale=scale, interpret=interpret)
        k = dit_rope(k, cos, sin, interpret=interpret)
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Skv, bkv)
    kernel = functools.partial(
        _kernel, bkv=bkv, bkv_compute=bkc, num_kv_blocks=nk,
        tail=Skv - (nk - 1) * bkv)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, Dp), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((None, bkv, Dp), lambda b, h, i, j: (b, j, h)),
            pl.BlockSpec((None, bkv, Dp), lambda b, h, i, j: (b, j, h)),
        ],
        out_specs=pl.BlockSpec((None, bq, Dp), lambda b, h, i, j: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, dt),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),    # m
            pltpu.VMEM((bq, LANES), jnp.float32),    # l
            pltpu.VMEM((bq, Dp), jnp.float32),       # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="dit_flash_attention",
    )(q, k, v)
    out = out.reshape(B, Sq, H, Dp)
    return out if Dp == D else out[..., :D]
