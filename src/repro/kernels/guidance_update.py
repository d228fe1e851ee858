"""Pallas TPU kernel: fused CFG combine + flow-matching scheduler update.

The per-step epilogue of the diffusion loop is pure elementwise traffic:

    pred   = uncond + w * (cond - uncond)        (CFG, Eq. 2)
    z_next = z + dt * pred                       (Euler step, Eq. 6)

Composed naively that is 4 latent-sized HBM reads + 2 writes; fused it is
3 reads + 1 write (~1.7x less traffic on a memory-bound step).  Tiled
over flattened latent blocks, everything in one VMEM pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(z_ref, cond_ref, uncond_ref, o_ref, *, w: float, dt: float):
    z = z_ref[...].astype(jnp.float32)
    c = cond_ref[...].astype(jnp.float32)
    u = uncond_ref[...].astype(jnp.float32)
    pred = u + w * (c - u)
    o_ref[...] = (z + dt * pred).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("w", "dt", "blk", "interpret"))
def guidance_update(
    z: jnp.ndarray,
    cond: jnp.ndarray,
    uncond: jnp.ndarray,
    w: float,
    dt: float,
    blk: int = 65536,
    interpret: bool = False,
) -> jnp.ndarray:
    shape = z.shape
    flat = z.size
    blk = min(blk, flat)
    pad = -flat % blk
    def prep(a):
        a = a.reshape(-1)
        return jnp.pad(a, (0, pad)) if pad else a
    zf, cf, uf = prep(z), prep(cond), prep(uncond)
    n = zf.size // blk
    out = pl.pallas_call(
        functools.partial(_kernel, w=w, dt=dt),
        grid=(n,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))] * 3,
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(zf.shape, z.dtype),
        interpret=interpret,
    )(zf, cf, uf)
    return out[:flat].reshape(shape)
