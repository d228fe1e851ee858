"""WAN2.1-style DiT (arXiv:2503.20314) with classifier-free guidance: the
program's forward, the seeded weights, the text stand-in, the replayed
step's extras, the plain reference's per-window prediction and guidance
combine, and the FLOP count of a step.

A configuration whose ``model`` is ``wan21-dit-1.3b`` runs through this
file (``benchlib.spec.model``); the harness, the reference's LP windows,
stitch and Euler step, and the metric readers hold nothing of it.

The block is the one the served model computes, which departs from the
published WAN2.1 block in two ways (listed under ``assumed`` in the
configuration files): a SwiGLU FFN (gate, up, down) where WAN2.1 has a
GELU MLP, and one adaLN projection per block where WAN2.1 shares one.

* DiT: 3D patchify (1, 2, 2); per block
  ``h += g1 * SelfAttn(rms(h) * (1 + s1) + b1)`` with 3D axial RoPE on
  q and k, ``h += CrossAttn(LN(h), text)``, ``h += g2 * FFN(rms(h) *
  (1 + s2) + b2)``, with ``(s1, b1, g1, s2, b2, g2) = ada(temb) +
  ada_b``; final ``LN(h) * (1 + scale) + shift`` and a linear head.
  Each LP window is denoised as a latent of its own: RoPE positions
  start at 0 in every window.
* Guidance: ``v = v_uncond + g (v_cond - v_uncond)``, the unconditional
  pass on an all-zero text context; the compiled step takes the pair as
  its extras, the reference computes both and combines them in float64.

The reference's forward is float32 ``jax.numpy`` with every matrix
product at ``Precision.HIGHEST``; ``quant="fp8"`` is the control: every
linear layer's operands are rounded to float8 e4m3 (one absmax scale per
tensor) before the product.

Weights: the tree the program's DiT forward reads (``patch_embed``,
``text_proj``, ``time_mlp``, stacked ``blocks``, ``final_norm``,
``final_ada``, ``head``), every matrix a fan-in scaled truncated normal
as the program's own initializer draws them, made on the device in one
jitted call.  One departure from that initializer: its adaLN projections
(``ada``, ``final_ada``) start at zero, which would make the timestep
embedding dead weight; here they are drawn like every other matrix, so
the check covers adaLN and the time MLP.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import flops
from benchlib.reference import layernorm, linear, rms, softmax_attention
from benchlib.spec import freeze

FREQ_DIM = 256                  # sinusoidal timestep embedding width
# std correction of a normal truncated to [-2, 2]
_TRUNC_STD = 0.87962566


# ------------------------------------------------------------ program
def program(conf: dict):
    """``(forward, cfg)``: the program's DiT forward and its ArchConfig
    for the configuration, checked against the file: every width the
    file states is the one that runs."""
    from repro.configs import get_config
    from repro.models import dit

    a = conf["arch"]
    cfg = dataclasses.replace(
        get_config(conf["model"]),
        num_layers=a["num_layers"], d_model=a["d_model"],
        num_heads=a["num_heads"], num_kv_heads=a["num_heads"],
        head_dim=a["head_dim"], d_ff=a["d_ff"],
        patch_sizes=tuple(a["patch_sizes"]),
        latent_channels=a["latent_channels"],
        context_len=a["context_len"], context_dim=a["context_dim"],
        time_embed_dim=a["time_embed_dim"], dtype=a["dtype"])
    return dit.forward, cfg


@functools.partial(jax.jit, static_argnums=0)
def _context(shape, key):
    # the text encoder's output stand-in: unit-scale noise times 0.02
    return jax.random.normal(key, shape, jnp.float32) * 0.02


def context(arch: dict, key):
    """A request's text context stand-in, (1, context_len, context_dim)."""
    return _context((1, arch["context_len"], arch["context_dim"]), key)


def step_extras(engine, req) -> tuple:
    """What the engine's compiled step takes besides the latent and the
    step scalars: the weights, the text context and the all-zero
    unconditional one, and the guidance scale."""
    ctx = req.context
    return (engine._step_params(), ctx, jnp.zeros_like(ctx),
            jnp.float32(req.guidance))


# ------------------------------------------------------------ weights
def _dense(key, fan_in, fan_out, dtype):
    std = math.sqrt(1.0 / fan_in) / _TRUNC_STD
    w = jax.random.truncated_normal(key, -2.0, 2.0, (fan_in, fan_out)) * std
    return {"w": w.astype(dtype)}


def _block(key, a, dtype):
    d, inner = a["d_model"], a["num_heads"] * a["head_dim"]
    ks = jax.random.split(key, 12)

    def attn(k4):
        return {"q": _dense(k4[0], d, inner, dtype),
                "k": _dense(k4[1], d, inner, dtype),
                "v": _dense(k4[2], d, inner, dtype),
                "o": _dense(k4[3], inner, d, dtype)}

    return {
        "self_attn": attn(ks[0:4]),
        "cross_attn": attn(ks[4:8]),
        "cross_norm": {"scale": jnp.ones((d,), jnp.float32),
                       "bias": jnp.zeros((d,), jnp.float32)},
        "mlp": {"wi": _dense(ks[8], d, a["d_ff"], dtype),
                "wg": _dense(ks[9], d, a["d_ff"], dtype),
                "wo": _dense(ks[10], a["d_ff"], d, dtype)},
        "ada": _dense(ks[11], a["time_embed_dim"], 6 * d, dtype),
        # gates (rows 2 and 5) start at 1, shifts and scales at 0
        "ada_b": jnp.zeros((6, d), jnp.float32).at[2].set(1.0).at[5].set(1.0),
    }


def _init(key, a):
    dtype = jnp.dtype(a["dtype"])
    d = a["d_model"]
    pt, ph, pw = a["patch_sizes"]
    patch = pt * ph * pw * a["latent_channels"]
    ks = jax.random.split(key, 7)
    return {
        "patch_embed": _dense(ks[0], patch, d, dtype),
        "text_proj": _dense(ks[1], a["context_dim"], d, dtype),
        "time_mlp": {
            "w1": _dense(ks[2], FREQ_DIM, a["time_embed_dim"], jnp.float32),
            "w2": _dense(ks[3], a["time_embed_dim"], a["time_embed_dim"],
                         jnp.float32),
        },
        "blocks": jax.vmap(lambda k: _block(k, a, dtype))(
            jax.random.split(ks[4], a["num_layers"])),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32),
                       "bias": jnp.zeros((d,), jnp.float32)},
        "final_ada": _dense(ks[5], a["time_embed_dim"], 2 * d, dtype),
        "head": _dense(ks[6], d, patch, dtype),
    }


def make_params(key, arch: dict, sharding=None):
    """The weights for ``arch`` (a configuration's ``arch`` block) from
    ``key``, in the dtype they are served in, made in one compiled call
    straight onto ``sharding`` (replicated over a mesh, or one device)."""
    fn = jax.jit(functools.partial(_init_frozen, frozen=freeze(arch)),
                 out_shardings=sharding)
    return fn(key)


def _init_frozen(key, frozen):
    return _init(key, dict(frozen))


# ------------------------------------------------------------ reference
def _rope_tables(grid, head_dim):
    """cos, sin of the 3D axial angles, (tokens, head_dim / 2)."""
    dt = (head_dim // 3) & ~1
    dw = head_dim - 2 * dt
    parts = []
    for ax, (n, dd) in enumerate(zip(grid, (dt, dt, dw))):
        freqs = 1.0 / 10000.0 ** (np.arange(0, dd, 2) / dd)
        ang = np.arange(n)[:, None] * freqs            # (n, dd / 2)
        shape = [1, 1, 1, dd // 2]
        shape[ax] = n
        parts.append(np.broadcast_to(ang.reshape(shape),
                                     (*grid, dd // 2)))
    ang = np.concatenate(parts, -1).reshape(-1, head_dim // 2)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(p, x, src, a, quant, rope=None):
    h, d = a["num_heads"], a["head_dim"]
    q = linear(x, p["q"]["w"], quant).reshape(x.shape[0], h, d)
    k = linear(src, p["k"]["w"], quant).reshape(src.shape[0], h, d)
    v = linear(src, p["v"]["w"], quant).reshape(src.shape[0], h, d)
    if rope is not None:
        q, k = _rope(q, *rope), _rope(k, *rope)
    out = softmax_attention(q, k, v).reshape(x.shape[0], h * d)
    return linear(out, p["o"]["w"], quant)


def _timestep_embedding(t):
    half = FREQ_DIM // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    arg = t * freqs
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)])


def velocity(params, z, t, ctx, a: dict, quant: Optional[str] = None):
    """The DiT's prediction for one latent ``z`` (T, H, W, C) at timestep
    ``t`` under text context ``ctx`` (L, context_dim); float32."""
    f32 = jnp.float32
    lin = functools.partial(linear, quant=quant)
    t_len, h_len, w_len, ch = z.shape
    pt, ph, pw = a["patch_sizes"]
    grid = (t_len // pt, h_len // ph, w_len // pw)
    d = a["d_model"]
    tok = z.reshape(grid[0], pt, grid[1], ph, grid[2], pw, ch)
    tok = tok.transpose(0, 2, 4, 1, 3, 5, 6).reshape(-1, pt * ph * pw * ch)
    x = lin(tok, params["patch_embed"]["w"])
    text = lin(ctx, params["text_proj"]["w"])
    temb = _timestep_embedding(t)
    temb = lin(jax.nn.silu(lin(temb, params["time_mlp"]["w1"]["w"])),
               params["time_mlp"]["w2"]["w"])
    temb = jax.nn.silu(temb)
    rope = _rope_tables(grid, a["head_dim"])

    def block(x, p):
        mods = lin(temb, p["ada"]["w"]).reshape(6, d) + p["ada_b"].astype(f32)
        s1, b1, g1, s2, b2, g2 = mods
        hn = rms(x) * (1 + s1) + b1
        x = x + g1 * _attention(p["self_attn"], hn, hn, a, quant, rope)
        x = x + _attention(p["cross_attn"], layernorm(x, p["cross_norm"]),
                           text, a, quant)
        hn = rms(x) * (1 + s2) + b2
        ffn = jax.nn.silu(lin(hn, p["mlp"]["wg"]["w"])) * \
            lin(hn, p["mlp"]["wi"]["w"])
        return x + g2 * lin(ffn, p["mlp"]["wo"]["w"]), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    shift, scale = lin(temb, params["final_ada"]["w"]).reshape(2, d)
    x = layernorm(x, params["final_norm"]) * (1 + scale) + shift
    out = lin(x, params["head"]["w"])
    out = out.reshape(*grid, pt, ph, pw, ch).transpose(0, 3, 1, 4, 2, 5, 6)
    return out.reshape(z.shape)


@functools.lru_cache(maxsize=None)
def window_fn(arch: Tuple, quant: Optional[str], mesh, axis: Optional[str]):
    """Jitted (cond, uncond) predictions of stacked windows, called as
    ``fn(params, windows, t, ctx, guidance)``; the guidance scale enters
    only the combine (``guide``).  On a mesh each device takes one window
    of ``windows`` (K, T, H, W, C); otherwise ``windows`` is one window."""
    a = dict(arch)

    def pair(params, win, t, ctx):
        both = jnp.stack([ctx, jnp.zeros_like(ctx)])
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda c: velocity(params, win, t, c, a, quant), both)

    if mesh is None:
        fn = jax.jit(pair)
    else:
        from jax.sharding import PartitionSpec as P

        def per_device(params, wins, t, ctx):
            return pair(params, wins[0], t, ctx)[None]

        fn = jax.jit(jax.shard_map(
            per_device, mesh=mesh, in_specs=(P(), P(axis), P(), P()),
            out_specs=P(axis), check_vma=False))
    return lambda params, wins, t, ctx, guidance: fn(params, wins, t, ctx)


def guide(pred: np.ndarray, guidance: float) -> np.ndarray:
    """Classifier-free guidance of the windows' (cond, uncond) pairs
    ``pred`` (K, 2, ...), float64: ``v_uncond + g (v_cond - v_uncond)``."""
    return pred[:, 1] + guidance * (pred[:, 0] - pred[:, 1])


# ------------------------------------------------------------ FLOPs
def forward_flops(a: dict, latent: Sequence[int]) -> int:
    """FLOPs of one DiT forward (one row, one timestep) over ``latent``
    (T, H, W) at the widths of ``a`` (a configuration's ``arch``): the
    multiply-adds (2 FLOP each) of its matrix products as the block
    computes them; elementwise work (norms, RoPE, softmax, gating) is not
    counted, as is usual for a model FLOP count."""
    s = flops.tokens(latent, a["patch_sizes"])
    d, ff = a["d_model"], a["d_ff"]
    inner = a["num_heads"] * a["head_dim"]
    ctx_len, ctx_dim, temb = a["context_len"], a["context_dim"], \
        a["time_embed_dim"]
    pt, ph, pw = a["patch_sizes"]
    patch = pt * ph * pw * a["latent_channels"]
    per_block = (
        2 * temb * 6 * d                        # adaLN projection
        + 2 * s * d * inner * 3                 # self q, k, v
        + 2 * s * inner * s * 2                 # self scores and values
        + 2 * s * inner * d                     # self out
        + 2 * s * d * inner                     # cross q
        + 2 * ctx_len * d * inner * 2           # cross k, v
        + 2 * s * inner * ctx_len * 2           # cross scores and values
        + 2 * s * inner * d                     # cross out
        + 2 * s * d * ff * 3                    # SwiGLU gate, up, down
    )
    outside = (
        2 * s * patch * d                       # patch embedding
        + 2 * ctx_len * ctx_dim * d             # text projection
        + 2 * FREQ_DIM * temb + 2 * temb * temb  # time MLP
        + 2 * temb * 2 * d                      # final adaLN
        + 2 * s * d * patch                     # head
    )
    return a["num_layers"] * per_block + outside


def step_flops(a: dict, latent: Sequence[int]) -> int:
    """One full-latent denoise step: the conditional and unconditional
    forwards of classifier-free guidance.  Independent of K and r, so LP's
    overlap counts as overhead."""
    return 2 * forward_flops(a, latent)
