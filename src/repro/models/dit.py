"""WAN2.1-style video DiT — the paper's denoising network f(.).

Latent z: (B, T_lat, H_lat, W_lat, C).  3D-patchified with (p_T, p_H, p_W)
into tokens, processed by DiT blocks (self-attention over all patch tokens,
cross-attention to the encoded text prompt, SwiGLU FFN) with adaLN timestep
modulation, then unpatchified back to a noise prediction of z's shape.

This is the f(.) that LP calls on *sub-latents*: the model is fully shape-
polymorphic over (T_lat, H_lat, W_lat) as long as they are patch-aligned,
which is exactly what the patch-aligned partitioning (paper §3.3)
guarantees.  RoPE uses 3D axial frequencies computed from *global* patch
coordinates, so a sub-latent sees the same positional code it would see
inside the full latent (pass ``origin`` = its offset).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from .scan_util import pscan
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed import actctx
from repro.kernels import ops as kernel_ops
from .attention import attention_chunked
from .layers import (
    dense,
    dense_init,
    layernorm,
    layernorm_init,
    mlp,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    sinusoidal_embedding,
)
from .transformer import stack_init


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def dit_block_init(key, cfg: ArchConfig):
    ka, kc, km, km2 = jax.random.split(key, 4)
    d = cfg.d_model
    dt = _dt(cfg)
    def qkvo(k):
        kq, kk, kv, ko = jax.random.split(k, 4)
        return {
            "q": dense_init(kq, d, cfg.num_heads * cfg.head_dim, dt),
            "k": dense_init(kk, d, cfg.num_heads * cfg.head_dim, dt),
            "v": dense_init(kv, d, cfg.num_heads * cfg.head_dim, dt),
            "o": dense_init(ko, cfg.num_heads * cfg.head_dim, d, dt),
        }
    return {
        "self_attn": qkvo(ka),
        "cross_attn": qkvo(kc),
        "cross_norm": layernorm_init(d),
        "mlp": mlp_init(km, d, cfg.d_ff, dt),
        # adaLN: 6 modulation vectors from the time embedding.  Gate rows
        # (g1, g2) start at 1 so a random-init model already has active
        # self-attention mixing — a trained DiT's operating point, and
        # what makes the LP-vs-centralized quality proxy meaningful.
        "ada": {"w": jnp.zeros((cfg.time_embed_dim, 6 * d), dt)},
        "ada_b": jnp.zeros((6, d), jnp.float32).at[2].set(1.0).at[5].set(1.0),
    }


def init_params(key, cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    dt = _dt(cfg)
    pt, ph, pw = cfg.patch_sizes
    patch_elems = pt * ph * pw * cfg.latent_channels
    ks = jax.random.split(key, 6)
    return {
        "patch_embed": dense_init(ks[0], patch_elems, d, dt),
        "text_proj": dense_init(ks[1], cfg.context_dim, d, dt),
        "time_mlp": {
            "w1": dense_init(ks[2], 256, cfg.time_embed_dim, jnp.float32),
            "w2": dense_init(ks[3], cfg.time_embed_dim, cfg.time_embed_dim, jnp.float32),
        },
        "blocks": stack_init(ks[4], cfg.num_layers, lambda k: dit_block_init(k, cfg)),
        "final_norm": layernorm_init(d),
        "final_ada": {"w": jnp.zeros((cfg.time_embed_dim, 2 * d), dt)},
        "head": dense_init(ks[5], d, patch_elems, dt),
    }


def _patchify(z: jnp.ndarray, cfg: ArchConfig) -> Tuple[jnp.ndarray, Tuple[int, int, int]]:
    """(B,T,H,W,C) -> (B, N_tokens, patch_elems) + patch-grid dims."""
    B, T, H, W, C = z.shape
    pt, ph, pw = cfg.patch_sizes
    nt, nh, nw = T // pt, H // ph, W // pw
    z = z.reshape(B, nt, pt, nh, ph, nw, pw, C)
    z = z.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    return z.reshape(B, nt * nh * nw, pt * ph * pw * C), (nt, nh, nw)


def _unpatchify(tok: jnp.ndarray, grid, cfg: ArchConfig, out_shape):
    B = tok.shape[0]
    nt, nh, nw = grid
    pt, ph, pw = cfg.patch_sizes
    C = cfg.latent_channels
    z = tok.reshape(B, nt, nh, nw, pt, ph, pw, C)
    z = z.transpose(0, 1, 4, 2, 5, 3, 6, 7)
    return z.reshape(out_shape)


def rope_tables(grid, origin, head_dim, theta=10_000.0):
    """3D axial RoPE over (t, h, w) patch coordinates (global coords), as
    rotate-half tables ``(cos, sin)``, each (N_tokens, head_dim) f32: cos
    over both halves, sin as [-sin, +sin] (:func:`apply_rope`)."""
    from .layers import rope_frequencies

    nt, nh, nw = grid
    ot, oh, ow = origin
    # split head_dim into 3 axial parts (multiples of 2)
    d_t = (head_dim // 3) & ~1
    d_h = (head_dim // 3) & ~1
    d_w = head_dim - d_t - d_h
    coords = [
        (jnp.arange(nt) + ot, d_t),
        (jnp.arange(nh) + oh, d_h),
        (jnp.arange(nw) + ow, d_w),
    ]
    angles = []
    for ax, (pos, dd) in enumerate(coords):
        freqs = jnp.asarray(rope_frequencies(dd, theta), jnp.float32)
        a = pos[:, None].astype(jnp.float32) * freqs  # (n, dd/2)
        shape = [1, 1, 1, dd // 2]
        shape[ax] = a.shape[0]
        a = a.reshape(shape)
        a = jnp.broadcast_to(a, (nt, nh, nw, dd // 2))
        angles.append(a)
    ang = jnp.concatenate(angles, axis=-1).reshape(nt * nh * nw, head_dim // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def apply_rope(x, rope):
    """``x * cos + swap_halves(x) * sin`` for x (B, S, H, D) in f32, back
    in x's dtype: what the kernel's ``dit_rope`` pass computes."""
    cos, sin = (t[:, None, :] for t in rope)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return (xf * cos + jnp.concatenate([x2, x1], axis=-1) * sin
            ).astype(x.dtype)


def _attn(params, x, cfg, rope=None, context=None, kv_chunk: int = 4096):
    """Bidirectional (DiT) self- or cross-attention, through
    :func:`attend`; ``rope`` (:func:`rope_tables`) rotates self-attention's
    q and k."""
    B, S, _ = x.shape
    H, D = cfg.num_heads, cfg.head_dim
    src = x if context is None else context
    Skv = src.shape[1]
    q = dense(params["q"], x).reshape(B, S, H, D)
    k = dense(params["k"], src).reshape(B, Skv, H, D)
    v = dense(params["v"], src).reshape(B, Skv, H, D)
    # sequence-parallel attention inside LP windows: 12 heads don't divide
    # a 16-way TP axis, so shard query tokens instead (§Perf C)
    q = actctx.shard_attn_q(q)
    k = actctx.shard_attn_kv(k)
    v = actctx.shard_attn_kv(v)
    out = attend(q, k, v, kv_chunk, rope=rope)
    out = actctx.shard_attn_out(out.reshape(B, S, H * D))
    return dense(params["o"], out)


def attend(q, k, v, kv_chunk: int = 4096, rope=None):
    """Unmasked attention, (B, S, H, D) x (B, Skv, H, D), q and k first
    rotated by ``rope``'s tables where given: the fused kernel
    ``kernels.ops.dit_attention`` where the backend compiles Pallas and
    no GSPMD activation context is active (a ``pallas_call`` cannot be
    auto-partitioned), else :func:`apply_rope` and the chunked
    online-softmax scan."""
    if kernel_ops.default_interpret() or actctx.active():
        if rope is not None:
            q, k = apply_rope(q, rope), apply_rope(k, rope)
        B, S = q.shape[:2]
        Skv = k.shape[1]
        qp = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        kp = jnp.broadcast_to(jnp.arange(Skv)[None], (B, Skv))
        return attention_chunked(q, k, v, qp, kp, causal=False,
                                 kv_chunk=kv_chunk)
    return kernel_ops.dit_attention(q, k, v, rope=rope)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def forward(
    params,
    z: jnp.ndarray,                    # (B, T, H, W, C) noisy latent
    t: jnp.ndarray,                    # (B,) diffusion timestep
    context: jnp.ndarray,              # (B, L_ctx, context_dim) text embeds
    cfg: ArchConfig,
    origin: Tuple[int, int, int] = (0, 0, 0),   # global patch offset (LP!)
    kv_chunk: int = 4096,
    remat: bool = False,
) -> jnp.ndarray:
    """Noise prediction f(z_t, t, c) with the same shape as ``z``.

    Each sub-block runs under a ``jax.named_scope``
    (``repro.obs.scopes.SCOPES``), which names its ops in the compiled
    HLO's ``op_name`` metadata, so a profile's device ops map back to
    the sub-block that owns them (``repro.obs.scopes.scope_map``).
    """
    B = z.shape[0]
    with jax.named_scope("dit.embed"):
        tok, grid = _patchify(z, cfg)
        x = dense(params["patch_embed"], tok.astype(_dt(cfg)))
        ctx = dense(params["text_proj"], context.astype(_dt(cfg)))

        temb = sinusoidal_embedding(t.astype(jnp.float32), 256)
        temb = dense(params["time_mlp"]["w2"],
                     jax.nn.silu(dense(params["time_mlp"]["w1"], temb)))
        temb = jax.nn.silu(temb)                               # (B, time_dim)
        rope = rope_tables(grid, origin, cfg.head_dim)

    def body(h, blk):
        with jax.named_scope("dit.adaln"):
            mods = (dense(blk["ada"], temb).reshape(B, 6, cfg.d_model)
                    + blk["ada_b"][None])
            s1, b1, g1, s2, b2, g2 = [mods[:, i].astype(h.dtype)
                                      for i in range(6)]
            hn = _modulate(rmsnorm({"scale": jnp.ones(cfg.d_model)}, h),
                           b1, s1)
        with jax.named_scope("dit.self_attn"):
            h = h + g1[:, None, :] * _attn(
                blk["self_attn"], hn, cfg, rope, kv_chunk=kv_chunk
            )
        with jax.named_scope("dit.cross_attn"):
            h = h + _attn(
                blk["cross_attn"],
                layernorm(blk["cross_norm"], h), cfg, context=ctx,
                kv_chunk=kv_chunk,
            )
        with jax.named_scope("dit.adaln"):
            hn = _modulate(rmsnorm({"scale": jnp.ones(cfg.d_model)}, h),
                           b2, s2)
        with jax.named_scope("dit.ffn"):
            h = h + g2[:, None, :] * mlp(blk["mlp"], hn)
            return actctx.shard_batch(h), None

    body_fn = jax.checkpoint(body) if remat else body
    with jax.named_scope("dit.blocks"):     # the loop over the blocks
        x, _ = pscan(body_fn, x, params["blocks"])

    with jax.named_scope("dit.head"):
        fmods = dense(params["final_ada"], temb).reshape(B, 2, cfg.d_model)
        shift, scale = fmods[:, 0].astype(x.dtype), fmods[:, 1].astype(x.dtype)
        x = _modulate(layernorm(params["final_norm"], x), shift, scale)
        out = dense(params["head"], x)
        return _unpatchify(out, grid, cfg, z.shape).astype(z.dtype)
