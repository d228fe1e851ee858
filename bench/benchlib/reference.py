"""Plain reference of one served request: LP windows and their stitch, and
the flow-matching Euler step, around the prediction of the configuration's
architecture.

What depends on the architecture comes from its module
(``bench/models/<model>.py``, found by the configuration's ``model``):
``window_fn`` predicts one window (or, on a mesh, one per device), and
``guide`` turns the K windows' predictions into the velocity to stitch
(for a CFG model, the guidance combine).  This file holds what every
architecture shares, and the plain layers a module's forward is written
with (float32 ``jax.numpy``, every matrix product at
``Precision.HIGHEST``; ``quant="fp8"`` rounds a linear layer's operands
to float8 e4m3, one absmax scale per tensor, for the control).  The
guidance combine, the stitch and the Euler step run in float64 NumPy.
It imports nothing of the program under test.

* LP (uniform windows): along the step's rotation dim, K windows of one
  size, cores balanced over the patches, ``O = floor(ceil(N/K) r)``
  overlap patches, starts clamped into range, trapezoid weights that
  ramp from the window edge to the core edge; the stitch is the
  weighted sum over windows divided by the summed weights.  Rotation
  runs over the dims with at least K patches, T, H, W in turn.  Each
  window is predicted as a latent of its own.
* Flow-matching Euler (WAN's shifted schedule, shift 3):
  ``sigma = 3 s / (1 + 2 s)`` for ``s`` linear from 1 to 0; step ``i``
  conditions on ``t = 1000 sigma_{i-1}`` and moves
  ``z += (sigma_i - sigma_{i-1}) v``.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .spec import freeze

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5
SHIFT = 3.0
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


# ------------------------------------------------------------ plain layers
def _fp8(a):
    scale = jnp.max(jnp.abs(a)) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def layernorm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def softmax_attention(q, k, v):
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


# ------------------------------------------------------------ the request
class Reference:
    """The reference trajectory of a request under the architecture
    module ``model``; ``quant="fp8"`` gives the control.  ``mesh`` /
    ``axis`` spread the K windows of a step over the devices of that mesh
    axis (one window each); without them the windows run one after
    another on the default device."""

    def __init__(self, model, arch: dict, params, mesh=None, axis=None,
                 quant: Optional[str] = None):
        self.model = model
        self.arch = arch
        self.params = params
        self.mesh, self.axis = mesh, axis
        self._fn = model.window_fn(freeze(arch), quant, mesh, axis)

    def _predict(self, wins: np.ndarray, t: float, ctx,
                 guidance: float) -> np.ndarray:
        """The velocity of each window, float64, (K, ...)."""
        t, g = np.float32(t), np.float32(guidance)
        if self.mesh is not None:
            out = self._fn(self.params, jnp.asarray(wins, jnp.float32), t,
                           ctx, g)
            return self.model.guide(np.asarray(out, np.float64), guidance)
        # identical windows (a window that spans the whole extent) give
        # identical predictions: compute each distinct one once
        out, seen = [], {}
        for w in wins:
            key = w.tobytes()
            if key not in seen:
                seen[key] = np.asarray(
                    self._fn(self.params, jnp.asarray(w, jnp.float32), t,
                             ctx, g), np.float64)
            out.append(seen[key])
        return self.model.guide(np.stack(out), guidance)

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
        guided = self._predict(wins, 1000.0 * sig[i - 1], ctx, guidance)
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
