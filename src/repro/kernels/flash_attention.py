"""Pallas TPU flash attention (tiled online softmax, GQA, causal/SWA).

TPU-native tiling: the grid is (batch, q_head, q_blocks, kv_blocks) with
the kv dimension innermost — TPU executes the grid sequentially per core,
so the (m, l, acc) online-softmax carry lives in VMEM scratch across the
kv sweep.  Block shapes keep the MXU fed ((bq x D) @ (D x bk) with D, bq,
bk multiples of the 128-lane registers) and the working set in VMEM:

    q block   (bq, D)    bf16/f32
    k/v block (bk, D)
    acc       (bq, D)    f32 scratch
    m, l      (bq, 128)  f32 scratch (lane-padded)

GQA is handled in the BlockSpec index_map (q head h reads kv head h//G) —
no KV replication in HBM.  Causal masking uses position tensors (LP
sub-latents and decode steps have non-trivial global positions); when
``causal`` and positions are block-contiguous, fully-masked kv blocks are
skipped via ``pl.when`` on the grid indices (upper-triangle skip: ~2x
fewer matmuls at long S).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
LANES = 128


def _kernel(
    q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref,   # inputs
    o_ref,                                        # output
    acc_ref, m_ref, l_ref,                        # VMEM scratch
    *, causal: bool, window: int, blk_q: int, blk_k: int,
    num_kv_blocks: int, skip_upper: bool,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_pos_ref[0, :]                       # (bq,)
    kv_pos = kv_pos_ref[0, :]                     # (bk,)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / math.sqrt(d)                           # (bq, bk)
        ok = (kv_pos[None, :] < jnp.iinfo(jnp.int32).max)
        if causal:
            ok = ok & (kv_pos[None, :] <= q_pos[:, None])
        if window > 0:
            ok = ok & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if skip_upper and causal:
        # contiguous positions: kv block strictly after q block -> all masked
        iq = pl.program_id(2)
        q_end = (iq + 1) * blk_q - 1
        k_start = ik * blk_k
        pl.when(k_start <= q_end)(compute)
    else:
        compute()

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "interpret",
                     "skip_upper"),
)
def flash_attention(
    q: jnp.ndarray,            # (B, Sq, H, D)
    k: jnp.ndarray,            # (B, Skv, KV, D)
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # (B, Sq) int32
    kv_positions: jnp.ndarray, # (B, Skv) int32
    causal: bool = True,
    window: int = 0,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = False,
    skip_upper: bool = False,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)

    # pad sequences to block multiples; padded kv slots get int32-max
    # positions (always masked), padded q rows are dropped at the end
    pq = -Sq % blk_q
    pk = -Skv % blk_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pq)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pk)),
                               constant_values=jnp.iinfo(jnp.int32).max)
    Sq_p, Skv_p = Sq + pq, Skv + pk
    nq, nk = Sq_p // blk_q, Skv_p // blk_k

    qt = q.transpose(0, 2, 1, 3)       # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)       # (B, KV, Skv, D)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, blk_q=blk_q, blk_k=blk_k,
        num_kv_blocks=nk, skip_upper=skip_upper,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q), lambda b, h, iq, ik: (b, iq)),
            pl.BlockSpec((1, blk_k), lambda b, h, iq, ik: (b, ik)),
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),      # acc
            pltpu.VMEM((blk_q, LANES), jnp.float32),  # m (lane-padded)
            pltpu.VMEM((blk_q, LANES), jnp.float32),  # l
        ],
        interpret=interpret,
    )(q_positions, kv_positions, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
