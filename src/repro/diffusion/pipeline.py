"""End-to-end denoising pipelines: centralized and Latent-Parallel.

``generate_centralized`` is the single-device reference (paper's
"Centralized" row); ``generate_lp`` runs the paper's full workflow
(rotating partition -> parallel denoise -> position-aware reconstruction).
By default it rides the compiled fast path (``core/lp_step.lp_denoise``):
timestep and scheduler coefficients are traced arguments, so a T-step run
compiles at most once per rotation dim; ``compiled=False`` falls back to
the eager reference loop.  Quality benchmarks diff the two against the
centralized output.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import LPStepCompiler, lp_denoise, lp_denoise_reference
from repro.diffusion.cfg import cfg_combine
from repro.diffusion.sampler import FlowMatchEuler


def make_guided_denoiser(dit_forward, params, cfg_model, context, null_context,
                         guidance: float = 5.0):
    """Returns f~(z, t) with CFG batched on-device (cond+uncond stacked)."""

    def guided(z, t):
        b = z.shape[0]
        z2 = jnp.concatenate([z, z], axis=0)
        t2 = jnp.concatenate([t, t], axis=0)
        ctx = jnp.concatenate([context, null_context], axis=0)
        pred = dit_forward(params, z2, t2, ctx, cfg_model)
        return cfg_combine(pred[:b], pred[b:], guidance)

    return guided


def make_guided_step_denoiser(dit_forward, cfg_model,
                              guidance_default: float = 5.0):
    """Fully-traced guided denoiser for the compiled LP step cache.

    Unlike :func:`make_guided_denoiser`, neither the parameters nor the
    conditioning are closed over: they are traced arguments, so one
    compiled step serves every batch of the same geometry — the serving
    engine builds this once per engine, not once per batch.  Parameters
    that a jitted closure captured would be embedded in the program as
    constants (4.49 GB at WAN2.1-1.3B's published width).  ``t`` is a
    traced f32 scalar (the LP step protocol).

    The architecture decides the form.  With ``cfg_model.guidance_embed``
    the guidance scale is an input of the network: ``guided(window, t,
    params, cond, guidance)`` is one forward over the window's B rows,
    ``cond`` any pytree the forward reads.  Otherwise classifier-free
    guidance: ``guided(window, t, params, context, null_context,
    guidance)`` runs the conditional and unconditional pair as one batch
    of 2B rows and combines them.
    """
    if cfg_model.guidance_embed:
        def embedded(window, t, params, cond, guidance=None):
            g = guidance_default if guidance is None else guidance
            b = window.shape[0]
            return dit_forward(params, window, jnp.full((b,), t, jnp.float32),
                               cond, cfg_model,
                               jnp.full((b,), g, jnp.float32))

        return embedded

    def guided(window, t, params, context, null_context, guidance=None):
        g = guidance_default if guidance is None else guidance
        b = window.shape[0]
        with jax.named_scope("dit.cfg"):       # the CFG pair's inputs
            z2 = jnp.concatenate([window, window], axis=0)
            t2 = jnp.full((2 * b,), t, jnp.float32)
            ctx = jnp.concatenate([context, null_context], axis=0)
        pred = dit_forward(params, z2, t2, ctx, cfg_model)
        with jax.named_scope("dit.cfg"):
            return cfg_combine(pred[:b], pred[b:], g)

    return guided


def generate_centralized(
    guided_denoiser: Callable,
    z_T: jnp.ndarray,
    num_steps: int,
    sampler: Optional[FlowMatchEuler] = None,
) -> jnp.ndarray:
    sampler = sampler or FlowMatchEuler(num_steps)
    z = z_T
    for i in range(1, num_steps + 1):
        t = jnp.full((z.shape[0],), sampler.timestep(i), jnp.float32)
        pred = guided_denoiser(z, t)
        z = sampler.step(z, pred, i)
    return z


def generate_lp(
    guided_denoiser: Callable,
    z_T: jnp.ndarray,
    num_steps: int,
    num_partitions: int,
    overlap_ratio: float,
    patch_sizes: Sequence[int],
    sampler: Optional[FlowMatchEuler] = None,
    spatial_axes: Sequence[int] = (1, 2, 3),   # (B, T, H, W, C) layout
    uniform: bool = False,
    compiled: bool = True,
    compiler: Optional[LPStepCompiler] = None,
) -> jnp.ndarray:
    """Latent-Parallel generation (paper Fig. 3 full loop).

    ``guided_denoiser(z, t)`` takes a per-sample timestep vector; the
    compiled path adapts it to the traced-scalar step protocol.  Pass
    ``compiler`` to share the compiled-step cache across calls.
    """
    sampler = sampler or FlowMatchEuler(num_steps)

    if not compiled:
        def denoise_for_step(i, dim):
            t_val = sampler.timestep(i)

            def fn(sub):
                t = jnp.full((sub.shape[0],), t_val, jnp.float32)
                return guided_denoiser(sub, t)

            return fn

        return lp_denoise_reference(
            denoise_for_step, z_T, lambda z, pred, i: sampler.step(z, pred, i),
            num_steps, num_partitions, overlap_ratio, patch_sizes,
            spatial_axes, uniform=uniform,
        )

    def den(window, t):
        tv = jnp.full((window.shape[0],), t, jnp.float32)
        return guided_denoiser(window, tv)

    return lp_denoise(
        den, z_T, sampler, num_steps, num_partitions, overlap_ratio,
        patch_sizes, spatial_axes, uniform=uniform, compiler=compiler,
    )
