"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) is decided here, in one place, by
:func:`default_interpret`: compiled on a TPU, the Python interpreter on
any other backend (CPU tests).  Pass a bool only to override it, e.g. to
compile a kernel for a described TPU from a CPU process.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .dit_attention import dit_attention as _dit_attention
from .flash_attention import flash_attention as _flash
from .mamba_ssd import mamba_ssd as _ssd
from .guidance_update import guidance_update as _guidance
from .latent_blend import latent_blend as _blend
from .wire_codec import dequant_blend as _dequant_blend
from .wire_codec import int8_quantize as _int8_quantize


def default_interpret() -> bool:
    """Pallas kernels compile on a TPU and are interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def flash_attention(q, k, v, q_positions, kv_positions, *, causal=True,
                    window=0, kv_len=None, blk_q=128, blk_k=128,
                    interpret=None, skip_upper=False):
    if kv_len is not None:
        # fold the valid-length mask into kv positions (int32-max = masked)
        kv_positions = jnp.where(
            kv_positions < kv_len[:, None], kv_positions,
            jnp.iinfo(jnp.int32).max,
        )
    return _flash(q, k, v, q_positions.astype(jnp.int32),
                  kv_positions.astype(jnp.int32), causal=causal,
                  window=window, blk_q=blk_q, blk_k=blk_k,
                  interpret=_interpret(interpret), skip_upper=skip_upper)


def dit_attention(q, k, v, *, rope=None, interpret=None):
    """Unmasked (DiT) attention, (B, Sq, H, D) -> (B, Sq, H, D): the
    fused bf16 flash kernel, with q and k first rotated by ``rope``'s
    rotate-half (cos, sin) tables where it is given.  Blocks from a v5e
    sweep at 7.8k-18.7k tokens: kv blocks of 2048 in 512-row chunks, q
    blocks of 1024 rows, or 512 where that computes fewer rows past the
    end (7,540 tokens: 7,680 rows in 15 blocks, not 8,192 in 8)."""
    S = q.shape[1]
    block_q = 512 if -S % 512 < -S % 1024 else 1024
    return _dit_attention(q, k, v, rope, block_q=block_q,
                          interpret=_interpret(interpret))


def latent_blend(preds, weights, normalizer, starts: Tuple[int, ...],
                 window: int, extent: int, *, blk_f=512, interpret=None):
    return _blend(preds, weights, normalizer, tuple(int(s) for s in starts),
                  window, extent, blk_f=blk_f,
                  interpret=_interpret(interpret))


def int8_quantize(x, *, qmax=127, blk_r=256, blk_f=2048, interpret=None):
    """(wire int8, scale (1,1)) — fused per-slab max-abs + quantize."""
    return _int8_quantize(x, qmax=qmax, blk_r=blk_r, blk_f=blk_f,
                          interpret=_interpret(interpret))


def dequant_blend(wire, scales, weights, normalizer, starts: Tuple[int, ...],
                  window: int, extent: int, *, blk_f=512, interpret=None,
                  out_dtype=None):
    """Fused int8 dequantize + position-aware blend (latent_blend twin)."""
    return _dequant_blend(
        wire, scales.reshape(-1), weights, normalizer,
        tuple(int(s) for s in starts), window, extent, blk_f=blk_f,
        interpret=_interpret(interpret),
        out_dtype=out_dtype if out_dtype is not None else jnp.float32,
    )


def guidance_update(z, cond, uncond, w: float, dt: float, *,
                    blk=65536, interpret=None):
    return _guidance(z, cond, uncond, float(w), float(dt), blk=blk,
                     interpret=_interpret(interpret))


def mamba_ssd(x, log_decay, scale, B, C, *, chunk=64, head_block=8,
              interpret=None):
    return _ssd(x, log_decay, scale, B, C, chunk=chunk,
                head_block=head_block, interpret=_interpret(interpret))
