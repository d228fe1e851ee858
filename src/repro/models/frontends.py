"""Stub modality frontends (per assignment: [audio]/[vlm] entries specify
the transformer BACKBONE only; the frontend provides precomputed frame /
patch embeddings).

These generate deterministic synthetic embeddings for smoke tests and the
matching ShapeDtypeStructs for the dry-run (``launch/dryrun.input_specs``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


def audio_frames(key, batch: int, cfg: ArchConfig) -> jnp.ndarray:
    """Whisper conv-frontend output: (B, encoder_seq, d_model)."""
    return (
        jax.random.normal(key, (batch, cfg.encoder_seq, cfg.d_model)) * 0.02
    ).astype(jnp.dtype(cfg.dtype))


def vision_patches(key, batch: int, cfg: ArchConfig) -> jnp.ndarray:
    """InternViT patch embeddings projected to d_model: (B, N_vis, d)."""
    return (
        jax.random.normal(key, (batch, cfg.num_vision_tokens, cfg.d_model)) * 0.02
    ).astype(jnp.dtype(cfg.dtype))


def text_context(key, batch: int, cfg: ArchConfig) -> jnp.ndarray:
    """Encoded text prompt for the VDM (umT5 stub): (B, L_ctx, ctx_dim)."""
    return (
        jax.random.normal(key, (batch, cfg.context_len, cfg.context_dim)) * 0.02
    ).astype(jnp.float32)


def conditioning(key, batch: int, cfg: ArchConfig):
    """A request's conditioning stand-in for ``cfg``'s denoiser: WAN's
    text context, or for a model with a pooled text vector (HunyuanVideo)
    ``{"text": (B, L_ctx, ctx_dim), "pooled": (B, pooled_dim)}`` of
    unit-scale noise, the scale of a normalized encoder output."""
    if not cfg.pooled_dim:
        return text_context(key, batch, cfg)
    kt, kp = jax.random.split(key)
    return {
        "text": jax.random.normal(
            kt, (batch, cfg.context_len, cfg.context_dim), jnp.float32),
        "pooled": jax.random.normal(kp, (batch, cfg.pooled_dim), jnp.float32),
    }
