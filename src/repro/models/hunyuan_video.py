"""HunyuanVideo's MMDiT (arXiv:2412.03603; release ``tencent/HunyuanVideo``)
as an LP denoiser.

Latent z: (B, T_lat, H_lat, W_lat, C), patchified (1, 2, 2) into video
tokens as ``models/dit`` does.  The conditioning is a pytree,
``{"text": (B, L, context_dim), "pooled": (B, pooled_dim)}``: LLM hidden
states and a pooled CLIP vector.  Guidance is embedded: the scale is an
input of the network, so a step is one forward and there is no CFG pair.

* ``vec = time_in(t) + vector_in(pooled) + guidance_in(1000 g)``, each an
  MLP (``time_in`` and ``guidance_in`` over a 256-wide sinusoidal code).
* Token refiner over the text: ``c = t_emb(t) + c_emb(mean(text))``,
  ``x = Linear(text)``, then per block ``x += g_msa Attn(LN(x))`` and
  ``x += g_mlp MLP_silu(LN(x))`` with ``(g_msa, g_mlp) = Linear(SiLU(c))``
  and affine LayerNorms.
* Double-stream blocks: video and text each modulate their own
  non-affine LayerNorm from ``Linear(SiLU(vec))``, project q, k, v, take
  a learned RMSNorm over each head of q and k, and RoPE the video q and
  k; one joint attention over [video; text] is split back into the
  streams, each with its gated projection and gated GELU-tanh MLP.
* Single-stream blocks over [video; text]: one fused ``linear1`` gives
  q, k, v and the MLP input; ``x += gate linear2([attn; GELU(mlp)])``.
* Final adaLN (shift, scale), LayerNorm, a linear head, unpatchify.

3D RoPE splits each head into (t, h, w) parts of ``cfg.rope_axes`` and
rotates interleaved pairs, as the release does.  Like ``dit.forward``
this is shape-polymorphic over patch-aligned sub-latents; positions
start at 0 in every LP window.  Linears carry biases (float32); their
matrices are ``cfg.dtype``.  Attention runs through ``dit.attend``: the
flash kernel on a TPU, the chunked scan elsewhere.  Each sub-block runs
under a ``jax.named_scope`` of ``repro.obs.scopes.SCOPES``
(``mmdit.*``).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .dit import _patchify, _unpatchify, attend
from .layers import sinusoidal_embedding
from .scan_util import pscan

FREQ_DIM = 256          # sinusoidal code of the timestep and the guidance
EPS = 1e-6              # of every LayerNorm and q/k RMSNorm, as released
F32 = jnp.float32


# ------------------------------------------------------------ weights
def _linear_init(key, fan_in, fan_out, dtype):
    """A fan-in scaled truncated-normal matrix and a small bias."""
    kw, kb = jax.random.split(key)
    std = math.sqrt(1.0 / fan_in) / 0.87962566
    w = jax.random.truncated_normal(kw, -2.0, 2.0, (fan_in, fan_out)) * std
    return {"w": w.astype(dtype),
            "b": 0.02 * jax.random.normal(kb, (fan_out,), F32)}


def _scale_init(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,), F32)


def _mlp_init(key, fan_in, d, dtype):
    k1, k2 = jax.random.split(key)
    return {"l1": _linear_init(k1, fan_in, d, dtype),
            "l2": _linear_init(k2, d, d, dtype)}


def _stream_init(key, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 6)
    return {"mod": _linear_init(ks[0], d, 6 * d, dtype),
            "qkv": _linear_init(ks[1], d, 3 * d, dtype),
            "q_norm": _scale_init(ks[2], cfg.head_dim),
            "k_norm": _scale_init(ks[3], cfg.head_dim),
            "proj": _linear_init(ks[4], d, d, dtype),
            "fc1": _linear_init(ks[5], d, ff, dtype),
            "fc2": _linear_init(jax.random.fold_in(ks[5], 1), ff, d, dtype)}


def _single_init(key, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    return {"mod": _linear_init(ks[0], d, 3 * d, dtype),
            "linear1": _linear_init(ks[1], d, 3 * d + ff, dtype),
            "q_norm": _scale_init(ks[2], cfg.head_dim),
            "k_norm": _scale_init(ks[3], cfg.head_dim),
            "linear2": _linear_init(ks[4], d + ff, d, dtype)}


def _refiner_block_init(key, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 9)
    return {"norm1": {"scale": _scale_init(ks[0], d),
                      "bias": 0.02 * jax.random.normal(ks[1], (d,), F32)},
            "qkv": _linear_init(ks[2], d, 3 * d, dtype),
            "proj": _linear_init(ks[3], d, d, dtype),
            "norm2": {"scale": _scale_init(ks[4], d),
                      "bias": 0.02 * jax.random.normal(ks[5], (d,), F32)},
            "fc1": _linear_init(ks[6], d, ff, dtype),
            "fc2": _linear_init(ks[7], ff, d, dtype),
            "ada": _linear_init(ks[8], d, 2 * d, dtype)}


def _stack(key, n, init):
    return jax.vmap(init)(jax.random.split(key, n))


def init_params(key, cfg: ArchConfig) -> Dict[str, Any]:
    """Seeded weights at ``cfg``'s widths, every matrix and bias drawn
    (the release zero-initializes its modulations and head, which would
    leave the conditioning dead weight in a check on random weights)."""
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    pt, ph, pw = cfg.patch_sizes
    patch = pt * ph * pw * cfg.latent_channels
    ks = jax.random.split(key, 12)
    params = {
        "img_in": _linear_init(ks[0], patch, d, dt),
        "time_in": _mlp_init(ks[1], FREQ_DIM, d, dt),
        "vector_in": _mlp_init(ks[2], cfg.pooled_dim, d, dt),
        "txt_in": {
            "input": _linear_init(ks[3], cfg.context_dim, d, dt),
            "t_emb": _mlp_init(ks[4], FREQ_DIM, d, dt),
            "c_emb": _mlp_init(ks[5], cfg.context_dim, d, dt),
            "blocks": _stack(ks[6], cfg.refiner_layers,
                             lambda k: _refiner_block_init(k, cfg, dt)),
        },
        "double": _stack(ks[7], cfg.double_layers, lambda k: {
            "img": _stream_init(jax.random.fold_in(k, 0), cfg, dt),
            "txt": _stream_init(jax.random.fold_in(k, 1), cfg, dt)}),
        "single": _stack(ks[8], cfg.single_layers,
                         lambda k: _single_init(k, cfg, dt)),
        "final": {"ada": _linear_init(ks[9], d, 2 * d, dt),
                  "linear": _linear_init(ks[10], d, patch, dt)},
    }
    if cfg.guidance_embed:
        params["guidance_in"] = _mlp_init(ks[11], FREQ_DIM, d, dt)
    return params


# ------------------------------------------------------------ layers
def _linear(p, x):
    y = jnp.einsum("...i,io->...o", x, p["w"],
                   preferred_element_type=F32) + p["b"]
    return y.astype(x.dtype)


def _embed(p, x):
    """Linear -> SiLU -> Linear, float32 in and out."""
    return _linear(p["l2"], jax.nn.silu(_linear(p["l1"], x.astype(F32))))


def _layernorm(x, p=None):
    """LayerNorm over the last dim in float32, affine when ``p`` is given."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(jnp.var(xf, -1, keepdims=True) + EPS)
    return y if p is None else y * p["scale"] + p["bias"]


def _modulate(x, shift, scale, dtype):
    """``LN(x) (1 + scale) + shift`` with (B, d) shift and scale."""
    y = _layernorm(x) * (1.0 + scale[:, None]) + shift[:, None]
    return y.astype(dtype)


def _gated(x, gate, y):
    """``x + gate y`` with a (B, d) gate, in float32, stored as ``x``."""
    return (x.astype(F32) + gate[:, None] * y.astype(F32)).astype(x.dtype)


def _qk_norm(x, scale):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS)
    return y * scale


def _rope_tables(grid, cfg: ArchConfig):
    """cos, sin of the 3D RoPE angles, (tokens, head_dim / 2): head part
    ``a`` of width ``cfg.rope_axes[a]`` rotates by axis ``a``'s patch
    position, positions from 0 within the (sub-)latent."""
    parts = []
    for ax, (n, dd) in enumerate(zip(grid, cfg.rope_axes)):
        freqs = 1.0 / cfg.rope_theta ** (jnp.arange(0, dd, 2, dtype=F32) / dd)
        ang = jnp.arange(n, dtype=F32)[:, None] * freqs
        shape = [1, 1, 1, dd // 2]
        shape[ax] = n
        parts.append(jnp.broadcast_to(ang.reshape(shape), (*grid, dd // 2)))
    ang = jnp.concatenate(parts, -1).reshape(-1, cfg.head_dim // 2)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """Rotate interleaved pairs ``(x[2i], x[2i+1])`` of (B, S, H, D)."""
    xr = x.reshape(*x.shape[:-1], -1, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x0 * c - x1 * s, x1 * c + x0 * s], -1).reshape(x.shape)


def _heads(qkv, cfg):
    """(B, S, 3 d) -> q, k, v of (B, S, H, D)."""
    B, S = qkv.shape[:2]
    qkv = qkv.reshape(B, S, 3, cfg.num_heads, cfg.head_dim)
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _normed(q, k, p, dtype, rope=None):
    """q/k RMSNorm with learned scales, then RoPE on the video tokens
    (the first ``rope[0].shape[0]`` of the sequence)."""
    q, k = _qk_norm(q, p["q_norm"]), _qk_norm(k, p["k_norm"])
    if rope is not None:
        n = rope[0].shape[0]
        q = jnp.concatenate([_rope(q[:, :n], *rope), q[:, n:]], 1)
        k = jnp.concatenate([_rope(k[:, :n], *rope), k[:, n:]], 1)
    return q.astype(dtype), k.astype(dtype)


def _refine(p, text, t, cfg, kv_chunk):
    """The token refiner: the text tokens as the blocks take them."""
    B, L = text.shape[:2]
    dt = jnp.dtype(cfg.dtype)
    c = (_embed(p["t_emb"], sinusoidal_embedding(t.astype(F32), FREQ_DIM))
         + _embed(p["c_emb"], jnp.mean(text.astype(F32), axis=1)))
    sc = jax.nn.silu(c)
    x = _linear(p["input"], text.astype(dt))

    def block(x, blk):
        g_msa, g_mlp = jnp.split(_linear(blk["ada"], sc), 2, axis=-1)
        q, k, v = _heads(_linear(blk["qkv"],
                                 _layernorm(x, blk["norm1"]).astype(dt)), cfg)
        attn = attend(q, k, v, kv_chunk).reshape(B, L, -1)
        x = _gated(x, g_msa, _linear(blk["proj"], attn))
        h = _layernorm(x, blk["norm2"]).astype(dt)
        return _gated(x, g_mlp, _linear(blk["fc2"], jax.nn.silu(
            _linear(blk["fc1"], h)))), None

    return pscan(block, x, p["blocks"])[0]


# ------------------------------------------------------------ forward
def forward(
    params,
    z: jnp.ndarray,                    # (B, T, H, W, C) noisy latent
    t: jnp.ndarray,                    # (B,) diffusion timestep
    cond: Dict[str, jnp.ndarray],      # {"text": (B, L, C), "pooled": (B, P)}
    cfg: ArchConfig,
    guidance: jnp.ndarray,             # (B,) guidance scale
    kv_chunk: int = 4096,
) -> jnp.ndarray:
    """Velocity prediction with the same shape as ``z``."""
    B = z.shape[0]
    dt = jnp.dtype(cfg.dtype)
    gelu = lambda x: jax.nn.gelu(x, approximate=True)   # noqa: E731
    with jax.named_scope("mmdit.embed"):
        tok, grid = _patchify(z, cfg)
        img = _linear(params["img_in"], tok.astype(dt))
        vec = (_embed(params["time_in"],
                      sinusoidal_embedding(t.astype(F32), FREQ_DIM))
               + _embed(params["vector_in"], cond["pooled"]))
        if cfg.guidance_embed:
            vec = vec + _embed(params["guidance_in"], sinusoidal_embedding(
                1000.0 * guidance.astype(F32), FREQ_DIM))
        svec = jax.nn.silu(vec)
        rope = _rope_tables(grid, cfg)
    n_img = img.shape[1]
    with jax.named_scope("mmdit.refiner"):
        txt = _refine(params["txt_in"], cond["text"], t, cfg, kv_chunk)

    def double(carry, blk):
        img, txt = carry
        pi, pt = blk["img"], blk["txt"]
        with jax.named_scope("mmdit.double_attn"):
            mi = jnp.split(_linear(pi["mod"], svec), 6, axis=-1)
            mt = jnp.split(_linear(pt["mod"], svec), 6, axis=-1)
            qi, ki, vi = _heads(_linear(pi["qkv"],
                                        _modulate(img, mi[0], mi[1], dt)), cfg)
            qt, kt, vt = _heads(_linear(pt["qkv"],
                                        _modulate(txt, mt[0], mt[1], dt)), cfg)
            qi, ki = _normed(qi, ki, pi, dt, rope)
            qt, kt = _normed(qt, kt, pt, dt)
            attn = attend(jnp.concatenate([qi, qt], 1),
                          jnp.concatenate([ki, kt], 1),
                          jnp.concatenate([vi, vt], 1), kv_chunk)
            attn = attn.reshape(B, attn.shape[1], -1)
            img = _gated(img, mi[2], _linear(pi["proj"], attn[:, :n_img]))
            txt = _gated(txt, mt[2], _linear(pt["proj"], attn[:, n_img:]))
        with jax.named_scope("mmdit.double_mlp"):
            img = _gated(img, mi[5], _linear(pi["fc2"], gelu(_linear(
                pi["fc1"], _modulate(img, mi[3], mi[4], dt)))))
            txt = _gated(txt, mt[5], _linear(pt["fc2"], gelu(_linear(
                pt["fc1"], _modulate(txt, mt[3], mt[4], dt)))))
        return (img, txt), None

    d = cfg.d_model

    def single(x, blk):
        with jax.named_scope("mmdit.single"):
            shift, scale, gate = jnp.split(_linear(blk["mod"], svec), 3, -1)
            h = _linear(blk["linear1"], _modulate(x, shift, scale, dt))
            q, k, v = _heads(h[..., :3 * d], cfg)
            q, k = _normed(q, k, blk, dt, rope)
            attn = attend(q, k, v, kv_chunk).reshape(B, x.shape[1], -1)
            out = _linear(blk["linear2"],
                          jnp.concatenate([attn, gelu(h[..., 3 * d:])], -1))
            return _gated(x, gate, out), None

    with jax.named_scope("mmdit.blocks"):   # the loops over the blocks
        (img, txt), _ = pscan(double, (img, txt), params["double"])
        x, _ = pscan(single, jnp.concatenate([img, txt], 1),
                     params["single"])

    with jax.named_scope("mmdit.head"):
        shift, scale = jnp.split(_linear(params["final"]["ada"], svec), 2, -1)
        out = _linear(params["final"]["linear"],
                      _modulate(x[:, :n_img], shift, scale, dt))
        return _unpatchify(out, grid, cfg, z.shape).astype(z.dtype)
