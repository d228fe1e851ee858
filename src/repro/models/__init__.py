"""Model zoo: unified init/forward/decode API across all families.

``build(cfg)`` returns a ``Model`` with:
  init(key)                          -> params
  forward(params, batch, **kw)       -> (hidden or noise-pred, aux)
  loss(params, batch)                -> scalar NLL (LM families)
  init_cache(batch, max_len)         -> decode cache (LM families)
  decode(params, token, cache, pos)  -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import dit, encdec, frontends, hunyuan_video, transformer
from .transformer import cross_entropy_chunked

# The video denoisers, by configuration name (``repro.configs``; a
# ``reduced()`` stand-in runs its source's): modules with
# ``init_params(key, cfg)`` and ``forward(params, z, t, cond, cfg, ...)``.
DENOISERS = {"wan21-dit-1.3b": dit, "hunyuanvideo-dit": hunyuan_video}
FORWARDS = {name: mod.forward for name, mod in DENOISERS.items()}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable
    forward: Callable
    loss: Optional[Callable] = None
    init_cache: Optional[Callable] = None
    decode: Optional[Callable] = None


def build(cfg: ArchConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "hybrid", "ssm"):
        def loss_fn(params, batch, remat=False, kv_chunk=2048):
            hidden, aux = transformer.forward(
                params, batch["tokens"], cfg,
                vision_embeds=batch.get("vision_embeds"),
                kv_chunk=kv_chunk, remat=remat,
            )
            nll = cross_entropy_chunked(params, hidden, batch["labels"], cfg)
            return nll + 0.01 * aux

        return Model(
            cfg=cfg,
            init=lambda key: transformer.init_params(key, cfg),
            forward=lambda p, batch, **kw: transformer.forward(
                p, batch["tokens"], cfg,
                vision_embeds=batch.get("vision_embeds"), **kw
            ),
            loss=loss_fn,
            init_cache=lambda b, m: transformer.init_cache(cfg, b, m),
            decode=lambda p, tok, cache, pos: transformer.decode_step(
                p, tok, cache, pos, cfg
            ),
        )
    if fam == "audio":
        def fwd(params, batch, **kw):
            enc = encdec.encode(params, batch["frames"], cfg, **kw)
            hid = encdec.decode_forward(params, batch["tokens"], enc, cfg, **kw)
            return hid, jnp.float32(0.0)

        def loss_fn(params, batch, remat=False, kv_chunk=2048):
            hid, _ = fwd(params, batch, kv_chunk=kv_chunk)
            return cross_entropy_chunked(
                {"embed": params["embed"]},
                hid,
                batch["labels"],
                dataclasses.replace(cfg, tie_embeddings=True),
            )

        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_params(key, cfg),
            forward=fwd,
            loss=loss_fn,
            init_cache=lambda b, m: encdec.init_cache(cfg, b, m),
            decode=lambda p, tok, cache, pos, enc: encdec.decode_step(
                p, tok, cache, pos, enc, cfg
            ),
        )
    if fam == "vdm":
        mod = DENOISERS[cfg.name.removesuffix("-reduced")]
        extra = ((lambda batch: (batch["guidance"],)) if cfg.guidance_embed
                 else (lambda batch: ()))
        return Model(
            cfg=cfg,
            init=lambda key: mod.init_params(key, cfg),
            forward=lambda p, batch, **kw: (
                mod.forward(p, batch["latent"], batch["t"],
                            batch["context"], cfg, *extra(batch), **kw),
                jnp.float32(0.0),
            ),
        )
    raise ValueError(f"unknown family {fam!r}")
