"""Plain reference of one served request: WAN2.1-style DiT, classifier-free
guidance, LP windows and their stitch, and the flow-matching Euler step.

Written from the published descriptions and the layer equations the
served model states, in float32 ``jax.numpy`` with every matrix product
at ``Precision.HIGHEST``; the guidance combine, the stitch and the Euler
step run in float64 NumPy.  It imports nothing of the program under test.

The block is the one the served model computes, which departs from the
published WAN2.1 block in two ways (listed under ``assumed`` in the
configuration files): a SwiGLU FFN (gate, up, down) where WAN2.1 has a
GELU MLP, and one adaLN projection per block where WAN2.1 shares one.

* DiT (WAN2.1, arXiv:2503.20314): 3D patchify (1, 2, 2); per block
  ``h += g1 * SelfAttn(rms(h) * (1 + s1) + b1)`` with 3D axial RoPE on
  q and k, ``h += CrossAttn(LN(h), text)``, ``h += g2 * FFN(rms(h) *
  (1 + s2) + b2)``, with ``(s1, b1, g1, s2, b2, g2) = ada(temb) +
  ada_b``; final ``LN(h) * (1 + scale) + shift`` and a linear head.
  Each LP window is denoised as a latent of its own: RoPE positions
  start at 0 in every window.
* Guidance: ``v = v_uncond + g (v_cond - v_uncond)``, the unconditional
  pass on an all-zero text context.
* LP (uniform windows): along the step's rotation dim, K windows of one
  size, cores balanced over the patches, ``O = floor(ceil(N/K) r)``
  overlap patches, starts clamped into range, trapezoid weights that
  ramp from the window edge to the core edge; the stitch is the
  weighted sum over windows divided by the summed weights.  Rotation
  runs over the dims with at least K patches, T, H, W in turn.
* Flow-matching Euler (WAN's shifted schedule, shift 3):
  ``sigma = 3 s / (1 + 2 s)`` for ``s`` linear from 1 to 0; step ``i``
  conditions on ``t = 1000 sigma_{i-1}`` and moves
  ``z += (sigma_i - sigma_{i-1}) v``.

``quant="fp8"`` is the control: every linear layer's operands are
rounded to float8 e4m3 (one absmax scale per tensor) before the product.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .spec import freeze

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5
SHIFT = 3.0
FREQ_DIM = 256
Q_CHUNK = 1024          # queries per attention block (exact softmax)
FP8_MAX = 448.0         # largest finite float8 e4m3fn


# ------------------------------------------------------------ schedule
def sigmas(steps: int) -> np.ndarray:
    s = np.linspace(1.0, 0.0, steps + 1)
    return SHIFT * s / (1.0 + (SHIFT - 1.0) * s)


def rotation(latent: Sequence[int], patch: Sequence[int], k: int,
             steps: int) -> List[int]:
    """Partition dim of each step: T, H, W in turn over the dims that
    hold at least ``k`` patches."""
    dims = [d for d in range(3) if latent[d] // patch[d] >= k]
    return [dims[(i - 1) % len(dims)] for i in range(1, steps + 1)]


def windows(extent: int, patch: int, k: int, r: float):
    """``(starts, window, weights (k, window), normalizer (extent,))`` of
    the uniform-window plan, in latent units."""
    n = extent // patch
    base, extra = divmod(n, k)
    core = math.ceil(n / k)
    over = math.floor(core * r)
    wp = min(n, core + 2 * over)
    starts, weights = [], []
    pos = 0
    for j in range(k):
        a = pos
        pos += base + (1 if j < extra else 0)
        b = pos
        s = min(max(0, a - over), n - wp)
        ramp_in, ramp_out = (a - s) * patch, (s + wp - b) * patch
        length = wp * patch
        w = np.ones(length)
        x = np.arange(length, dtype=np.float64)
        if ramp_in:
            w[:ramp_in] = x[:ramp_in] / ramp_in
        if ramp_out:
            w[length - ramp_out:] = (length - x[length - ramp_out:]) / ramp_out
        starts.append(s * patch)
        weights.append(w)
    norm = np.zeros(extent)
    for s, w in zip(starts, weights):
        norm[s:s + len(w)] += w
    assert (norm > 0).all()
    return starts, wp * patch, np.stack(weights), norm


# ------------------------------------------------------------ the DiT
def _fp8(a):
    scale = jnp.max(jnp.abs(a)) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def _layernorm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


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


def _softmax_attention(q, k, v):
    """Exact softmax attention, (S, H, D) x (Skv, H, D), in query blocks."""
    s_len, heads, dim = q.shape
    n = -(-s_len // Q_CHUNK)
    qp = jnp.pad(q, ((0, n * Q_CHUNK - s_len), (0, 0), (0, 0)))

    def block(qc):
        sc = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST)
        p = jax.nn.softmax(sc / math.sqrt(dim), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, qp.reshape(n, Q_CHUNK, heads, dim))
    return out.reshape(n * Q_CHUNK, heads, dim)[:s_len]


def _attention(p, x, src, a, quant, rope=None):
    h, d = a["num_heads"], a["head_dim"]
    q = _linear(x, p["q"]["w"], quant).reshape(x.shape[0], h, d)
    k = _linear(src, p["k"]["w"], quant).reshape(src.shape[0], h, d)
    v = _linear(src, p["v"]["w"], quant).reshape(src.shape[0], h, d)
    if rope is not None:
        q, k = _rope(q, *rope), _rope(k, *rope)
    out = _softmax_attention(q, k, v).reshape(x.shape[0], h * d)
    return _linear(out, p["o"]["w"], quant)


def _timestep_embedding(t):
    half = FREQ_DIM // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    arg = t * freqs
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)])


def velocity(params, z, t, ctx, a: dict, quant: Optional[str] = None):
    """The DiT's prediction for one latent ``z`` (T, H, W, C) at timestep
    ``t`` under text context ``ctx`` (L, context_dim); float32."""
    f32 = jnp.float32
    lin = functools.partial(_linear, quant=quant)
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
        hn = _rms(x) * (1 + s1) + b1
        x = x + g1 * _attention(p["self_attn"], hn, hn, a, quant, rope)
        x = x + _attention(p["cross_attn"], _layernorm(x, p["cross_norm"]),
                           text, a, quant)
        hn = _rms(x) * (1 + s2) + b2
        ffn = jax.nn.silu(lin(hn, p["mlp"]["wg"]["w"])) * \
            lin(hn, p["mlp"]["wi"]["w"])
        return x + g2 * lin(ffn, p["mlp"]["wo"]["w"]), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    shift, scale = lin(temb, params["final_ada"]["w"]).reshape(2, d)
    x = _layernorm(x, params["final_norm"]) * (1 + scale) + shift
    out = lin(x, params["head"]["w"])
    out = out.reshape(*grid, pt, ph, pw, ch).transpose(0, 3, 1, 4, 2, 5, 6)
    return out.reshape(z.shape)


@functools.lru_cache(maxsize=None)
def _window_fn(arch: Tuple, quant: Optional[str], mesh, axis: Optional[str]):
    """Jitted (cond, uncond) predictions of stacked windows.  On a mesh
    each device takes one window; otherwise one window per call."""
    a = dict(arch)

    def pair(params, win, t, ctx):
        both = jnp.stack([ctx, jnp.zeros_like(ctx)])
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(
                lambda c: velocity(params, win, t, c, a, quant), both)

    if mesh is None:
        return jax.jit(pair)
    from jax.sharding import PartitionSpec as P

    def per_device(params, wins, t, ctx):
        return pair(params, wins[0], t, ctx)[None]

    return jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(), P(axis), P(), P()),
        out_specs=P(axis), check_vma=False))


class Reference:
    """The reference trajectory of a request; ``quant="fp8"`` gives the
    control.  ``mesh`` / ``axis`` spread the K windows of a step over the
    devices of that mesh axis (one window each); without them the
    windows run one after another on the default device."""

    def __init__(self, arch: dict, params, mesh=None, axis=None,
                 quant: Optional[str] = None):
        self.arch = arch
        self.params = params
        self.mesh, self.axis = mesh, axis
        self._fn = _window_fn(freeze(arch), quant, mesh, axis)

    def _predict(self, wins: np.ndarray, t: float, ctx) -> np.ndarray:
        """(cond, uncond) per window, float64, (K, 2, ...)."""
        t = np.float32(t)
        if self.mesh is not None:
            out = self._fn(self.params, jnp.asarray(wins, jnp.float32), t,
                           ctx)
            return np.asarray(out, np.float64)
        # identical windows (a window that spans the whole extent) give
        # identical predictions: compute each distinct one once
        out, seen = [], {}
        for w in wins:
            key = w.tobytes()
            if key not in seen:
                seen[key] = np.asarray(
                    self._fn(self.params, jnp.asarray(w, jnp.float32), t,
                             ctx), np.float64)
            out.append(seen[key])
        return np.stack(out)

    def step(self, z: np.ndarray, i: int, steps: int, k: int, r: float,
             ctx, guidance: float) -> np.ndarray:
        """Step ``i`` (1-indexed) of ``steps`` from latent ``z`` (T, H, W,
        C), float64 in and out."""
        a = self.arch
        sig = sigmas(steps)
        dim = rotation(z.shape[:3], a["patch_sizes"], k, steps)[i - 1]
        starts, size, weights, norm = windows(
            z.shape[dim], a["patch_sizes"][dim], k, r)
        wins = np.stack([np.take(z, np.arange(s, s + size), axis=dim)
                         for s in starts])
        pred = self._predict(wins, 1000.0 * sig[i - 1], ctx)
        guided = pred[:, 1] + guidance * (pred[:, 0] - pred[:, 1])
        acc = np.zeros_like(z)
        for s, w, g in zip(starts, weights, guided):
            shape = [1] * 4
            shape[dim] = size
            idx = [slice(None)] * 4
            idx[dim] = slice(s, s + size)
            acc[tuple(idx)] += w.reshape(shape) * g
        nshape = [1] * 4
        nshape[dim] = z.shape[dim]
        v = acc / norm.reshape(nshape)
        return z + (sig[i] - sig[i - 1]) * v

    def trajectory(self, z_T: np.ndarray, steps: int, k: int, r: float,
                   ctx, guidance: float) -> List[np.ndarray]:
        """``[z_T, z_1, ..., z_steps]``, float64."""
        out = [np.asarray(z_T, np.float64)]
        for i in range(1, steps + 1):
            out.append(self.step(out[-1], i, steps, k, r, ctx, guidance))
        return out
