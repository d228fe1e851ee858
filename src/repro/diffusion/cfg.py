"""Classifier-free guidance (paper Eq. 2/4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cfg_combine(cond: jnp.ndarray, uncond: jnp.ndarray, w: float) -> jnp.ndarray:
    """f~ = f_uncond + w (f_cond - f_uncond)."""
    with jax.named_scope("dit.cfg"):
        return (uncond.astype(jnp.float32)
                + w * (cond.astype(jnp.float32) - uncond.astype(jnp.float32))
                ).astype(cond.dtype)


def cfg_batched(denoise_fn, w: float):
    """Wrap a denoiser so one call computes both CFG passes as a stacked
    leading dim of 2 — the paper's on-device CFG batching (Table 1
    accounting), and the form that maps onto a mesh axis of size 2."""

    def wrapped(z, t, ctx_pair):
        import jax.numpy as jnp

        z2 = jnp.stack([z, z])
        pred = denoise_fn(z2, jnp.stack([t, t]), ctx_pair)  # (2, ...)
        return cfg_combine(pred[0], pred[1], w)

    return wrapped
