"""Grouped-query attention with memory-safe chunked online softmax.

The default path scans over KV chunks with a running (max, sum, acc) —
the flash-attention recurrence in pure jnp — so 32k prefill and 500k decode
never materialize an S x S score matrix.  ``kernels/flash_attention``
provides the Pallas TPU kernel with the same semantics (swapped in via
``use_pallas``); ``attention_dense`` is the O(S^2)-memory oracle used by
tests and small models.

Supports: causal, sliding-window (h2o-danube), bidirectional (encoders,
DiT), GQA head grouping, and single-token decode against a KV cache.

The DiT (``models/dit._attn``) takes another path where it can: on a
backend that compiles Pallas (``kernels.ops.default_interpret()`` false)
and outside a GSPMD activation context (``actctx.active()`` false, which
only the sharded dry-run enters), its self- and cross-attention run in
``kernels/dit_attention``, a bf16 flash kernel without masks.  Elsewhere
(CPU, the dry-run) it falls back to ``attention_chunked`` here.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import actctx
from .layers import dense, dense_init
from .scan_util import pscan

NEG_INF = -1.0e30


def gqa_init(key, d_model: int, num_heads: int, num_kv_heads: int, head_dim: int,
             dtype=jnp.bfloat16, out_dim: Optional[int] = None):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "q": dense_init(kq, d_model, num_heads * head_dim, dtype),
        "k": dense_init(kk, d_model, num_kv_heads * head_dim, dtype),
        "v": dense_init(kv, d_model, num_kv_heads * head_dim, dtype),
        "o": dense_init(ko, num_heads * head_dim, out_dim or d_model, dtype),
    }


def _mask_bias(q_pos, kv_pos, causal: bool, window: int, kv_len=None):
    """(..., Sq, Skv) additive bias: 0 where attendable, NEG_INF elsewhere."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    # int32-max marks padded KV slots (see attention_chunked) — always masked
    ok = kp < jnp.iinfo(jnp.int32).max
    ok = jnp.broadcast_to(
        ok, q_pos.shape[:-1] + (q_pos.shape[-1], kv_pos.shape[-1])
    )
    if causal:
        ok &= kp <= qp
    if window > 0:
        ok &= kp > qp - window
    if kv_len is not None:
        ok &= kp < kv_len[..., None, None]
    return jnp.where(ok, 0.0, NEG_INF)


def attention_dense(
    q: jnp.ndarray,           # (B, Sq, H, D)
    k: jnp.ndarray,           # (B, Skv, KV, D)
    v: jnp.ndarray,           # (B, Skv, KV, D)
    q_positions: jnp.ndarray,     # (B, Sq)
    kv_positions: jnp.ndarray,    # (B, Skv)
    causal: bool = True,
    window: int = 0,
    kv_len: Optional[jnp.ndarray] = None,   # (B,) valid cache length
) -> jnp.ndarray:
    """Reference attention, O(Sq*Skv) memory."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(float(D))
    bias = _mask_bias(q_positions, kv_positions, causal, window, kv_len)
    scores = scores + bias[:, None, None, :, :]
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attention_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    kv_len: Optional[jnp.ndarray] = None,
    kv_chunk: int = 2048,
) -> jnp.ndarray:
    """Online-softmax attention scanning over KV chunks (flash recurrence)."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    if Skv <= kv_chunk:
        return attention_dense(
            q, k, v, q_positions, kv_positions, causal, window, kv_len
        )
    n_chunks = -(-Skv // kv_chunk)
    pad = n_chunks * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded positions get an out-of-range marker so masking kills them
        kv_positions = jnp.pad(
            kv_positions, ((0, 0), (0, pad)), constant_values=jnp.iinfo(jnp.int32).max
        )
    kc = k.reshape(B, n_chunks, kv_chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, KV, D).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(B, n_chunks, kv_chunk).transpose(1, 0, 2)

    qg = q.reshape(B, Sq, KV, G, D).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(float(D))

    def step(carry, chunk):
        m, l, acc = carry
        k_i, v_i, pos_i = chunk
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_i.astype(jnp.float32)) * scale
        bias = _mask_bias(q_positions, pos_i, causal, window, kv_len)
        s = s + bias[:, None, None, :, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v_i.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    (m, l, acc), _ = pscan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def attention(
    q, k, v, q_positions, kv_positions,
    causal: bool = True,
    window: int = 0,
    kv_len=None,
    kv_chunk: int = 2048,
    use_pallas: bool = False,
    pallas_interpret: Optional[bool] = None,
):
    """Dispatch: Pallas flash kernel (TPU target) or chunked jnp."""
    if use_pallas:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.flash_attention(
            q, k, v, q_positions, kv_positions,
            causal=causal, window=window, kv_len=kv_len,
            interpret=pallas_interpret,
        )
    return attention_chunked(
        q, k, v, q_positions, kv_positions, causal, window, kv_len, kv_chunk
    )


def gqa_apply(
    params,
    x: jnp.ndarray,                 # (B, S, d)
    positions: jnp.ndarray,         # (B, S)
    rope_theta: float,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    causal: bool = True,
    window: int = 0,
    kv_source: Optional[jnp.ndarray] = None,      # cross-attention context
    kv_positions: Optional[jnp.ndarray] = None,
    use_rope: bool = True,
    kv_chunk: int = 2048,
):
    """Self- or cross-attention block (projections + attention + out proj)."""
    from .layers import apply_rope

    B, S, _ = x.shape
    src = x if kv_source is None else kv_source
    Skv = src.shape[1]
    q = dense(params["q"], x).reshape(B, S, num_heads, head_dim)
    k = dense(params["k"], src).reshape(B, Skv, num_kv_heads, head_dim)
    v = dense(params["v"], src).reshape(B, Skv, num_kv_heads, head_dim)
    if kv_positions is None:
        kv_positions = positions if kv_source is None else (
            jnp.broadcast_to(jnp.arange(Skv)[None, :], (B, Skv))
        )
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, kv_positions, rope_theta)
    q = actctx.shard_attn_q(q)
    k = actctx.shard_attn_kv(k)
    v = actctx.shard_attn_kv(v)
    out = attention(
        q, k, v, positions, kv_positions,
        causal=causal, window=window, kv_chunk=kv_chunk,
    )
    out = actctx.shard_attn_out(out.reshape(B, S, num_heads * head_dim))
    return dense(params["o"], out)


def decode_attention(
    params,
    x_t: jnp.ndarray,               # (B, 1, d)
    cache_k: jnp.ndarray,           # (B, S_max, KV, D)
    cache_v: jnp.ndarray,
    position: jnp.ndarray,          # (B,) current index
    rope_theta: float,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    window: int = 0,
    use_rope: bool = True,
    kv_chunk: int = 8192,
):
    """One-token decode: project, update cache at ``position``, attend.

    Returns (out (B,1,d), new_cache_k, new_cache_v).
    """
    from .layers import apply_rope

    B = x_t.shape[0]
    q = dense(params["q"], x_t).reshape(B, 1, num_heads, head_dim)
    k = dense(params["k"], x_t).reshape(B, 1, num_kv_heads, head_dim)
    v = dense(params["v"], x_t).reshape(B, 1, num_kv_heads, head_dim)
    pos2d = position[:, None]
    if use_rope:
        q = apply_rope(q, pos2d, rope_theta)
        k = apply_rope(k, pos2d, rope_theta)

    def upd(c, new):
        return jax.vmap(
            lambda cb, nb, p: jax.lax.dynamic_update_slice_in_dim(cb, nb, p, 0)
        )(c, new, position)

    cache_k = upd(cache_k, k)
    cache_v = upd(cache_v, v)
    S_max = cache_k.shape[1]
    if 0 < window < S_max:
        # sliding-window decode only ever attends to the last `window`
        # positions: slice them out of the cache so attention reads
        # O(window) instead of O(S_max) — a 128x traffic cut for
        # h2o-danube's 4096-window at the 500k-token cell (§Perf).
        start = jnp.clip(position + 1 - window, 0, S_max - window)
        win_k = jax.vmap(
            lambda cb, s: jax.lax.dynamic_slice_in_dim(cb, s, window, 0)
        )(cache_k, start)
        win_v = jax.vmap(
            lambda cb, s: jax.lax.dynamic_slice_in_dim(cb, s, window, 0)
        )(cache_v, start)
        kv_pos = start[:, None] + jnp.arange(window)[None, :]
        out = attention_chunked(
            q, win_k, win_v, pos2d, kv_pos,
            causal=False, window=window, kv_len=position + 1,
            kv_chunk=kv_chunk,
        )
    else:
        kv_pos = jnp.broadcast_to(jnp.arange(S_max)[None, :], (B, S_max))
        out = attention_chunked(
            q, cache_k, cache_v, pos2d, kv_pos,
            causal=False, window=window, kv_len=position + 1,
            kv_chunk=kv_chunk,
        )
    y = dense(params["o"], out.reshape(B, 1, num_heads * head_dim))
    return y, cache_k, cache_v
