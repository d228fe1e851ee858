"""Pallas TPU kernel: chunked Mamba2/SSD scan with VMEM-resident state.

The XLA chunk-scan (ssm.gated_linear_scan) must round-trip the recurrent
state S (heads x n x p — 1.3 GB for zamba2 at batch 16) through HBM on
every 64-token chunk: §Perf A4 measured ~19 TB/step of pure state traffic.
This kernel keeps S in VMEM scratch across the chunk sweep:

    grid = (batch, head_blocks, num_chunks)   # chunks innermost
    scratch: S (hb, n, p) f32 — persists across the chunk dimension,
             reset at chunk 0

Per chunk (all in VMEM): cumulative decays, the factorized intra-chunk
form (same math as gated_linear_scan(factorized=True): group-level C·B^T
Gram + rank-1 exp scalings, exponents clipped at ±60 with per-chunk
centering), inter-chunk readout against S, then the state update.

HBM traffic per chunk = read x/decay/scale/B/C once + write y once —
state never leaves VMEM.  Assumes ssm_groups == 1 (zamba2's config);
B/C are shared across every head block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, *,
            num_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0, 0].astype(jnp.float32)        # (Q, hb, p)
    a = a_ref[0, 0].astype(jnp.float32)        # (Q, hb)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (Q, hb)
    Bc = b_ref[0, 0].astype(jnp.float32)       # (Q, n)
    Cc = c_ref[0, 0].astype(jnp.float32)       # (Q, n)
    Q = x.shape[0]

    cum = jnp.cumsum(a, axis=0)                # (Q, hb)
    total = cum[-1, :]                         # (hb,)

    # inter-chunk: y += exp(cum) * (C . S_in)
    y_inter = jnp.einsum("qn,hnp->qhp", Cc, s_ref[...],
                         preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)[:, :, None]

    # intra-chunk (factorized, ±60-clipped centered exponents)
    center = 0.5 * (cum.max(axis=0) + cum.min(axis=0))      # (hb,)
    a_i = jnp.exp(jnp.clip(cum - center[None, :], -60.0, 60.0))
    b_j = jnp.exp(jnp.clip(center[None, :] - cum, -60.0, 60.0))
    cb = jnp.einsum("in,jn->ij", Cc, Bc,
                    preferred_element_type=jnp.float32)      # (Q, Q)
    mask = jnp.tril(jnp.ones((Q, Q), jnp.float32))
    cb = cb * mask
    v = x * (dt * b_j)[:, :, None]                           # (Q, hb, p)
    y_intra = jnp.einsum("ij,jhp->ihp", cb, v,
                         preferred_element_type=jnp.float32)
    y_intra = y_intra * a_i[:, :, None]

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(total) S + sum_j exp(total-cum_j) dt_j B_j x_j
    w = jnp.exp(total[None, :] - cum) * dt                   # (Q, hb)
    s_new = jnp.einsum("qn,qhp->hnp", Bc, w[:, :, None] * x,
                       preferred_element_type=jnp.float32)
    s_ref[...] = jnp.exp(total)[:, None, None] * s_ref[...] + s_new


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "head_block", "interpret"),
)
def mamba_ssd(
    x: jnp.ndarray,           # (b, s, h, p)
    log_decay: jnp.ndarray,   # (b, s, h)
    scale: jnp.ndarray,       # (b, s, h)
    B: jnp.ndarray,           # (b, s, n)   (groups == 1)
    C: jnp.ndarray,           # (b, s, n)
    chunk: int = 64,
    head_block: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, p = x.shape
    n = B.shape[-1]
    hb = min(head_block, h)
    assert h % hb == 0, (h, hb)
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded positions: zero input, zero decay (exp(0)=1 keeps state)
        log_decay = jnp.pad(log_decay, ((0, 0), (0, pad), (0, 0)))
        scale = jnp.pad(scale, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    xq = x.reshape(b, nc, chunk, h, p)
    aq = log_decay.reshape(b, nc, chunk, h)
    dq = scale.reshape(b, nc, chunk, h)
    Bq = B.reshape(b, nc, chunk, n)
    Cq = C.reshape(b, nc, chunk, n)

    grid = (b, h // hb, nc)
    out = pl.pallas_call(
        functools.partial(_kernel, num_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hb, p),
                         lambda ib, ih, ic: (ib, ic, 0, ih, 0)),
            pl.BlockSpec((1, 1, chunk, hb),
                         lambda ib, ih, ic: (ib, ic, 0, ih)),
            pl.BlockSpec((1, 1, chunk, hb),
                         lambda ib, ih, ic: (ib, ic, 0, ih)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic: (ib, ic, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hb, p),
                               lambda ib, ih, ic: (ib, ic, 0, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nc, chunk, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((hb, n, p), jnp.float32)],
        interpret=interpret,
    )(xq, aq, dq, Bq, Cq)
    return out.reshape(b, sp, h, p)[:, :s]
