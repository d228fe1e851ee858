"""Operations and bytes, counted from shapes.

``dit_forward_flops`` counts the multiply-adds (2 FLOP each) of the
matrix products of one DiT forward over one latent, as the configured
block computes them; elementwise work (norms, RoPE, softmax, gating) is
not counted, as is usual for a model FLOP count.
"""
from __future__ import annotations

from typing import Sequence


def tokens(latent: Sequence[int], patch: Sequence[int]) -> int:
    t, h, w = latent
    pt, ph, pw = patch
    return (t // pt) * (h // ph) * (w // pw)


def dit_forward_flops(a: dict, latent: Sequence[int]) -> int:
    """FLOPs of one DiT forward (one row, one timestep) over ``latent``
    (T, H, W) at the widths of ``a`` (a configuration's ``arch``)."""
    s = tokens(latent, a["patch_sizes"])
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
        + 2 * 256 * temb + 2 * temb * temb      # time MLP
        + 2 * temb * 2 * d                      # final adaLN
        + 2 * s * d * patch                     # head
    )
    return a["num_layers"] * per_block + outside


def guided_step_flops(a: dict, latent: Sequence[int]) -> int:
    """One full-latent denoise step: the conditional and unconditional
    forwards of classifier-free guidance.  Independent of K and r, so LP's
    overlap counts as overhead."""
    return 2 * dit_forward_flops(a, latent)


def latent_blend_bytes(k: int, window: int, extent: int, rest: int,
                       itemsize: int = 4) -> int:
    """Least HBM traffic of one stitch: read the K window predictions
    (K, window, rest), their weights (K, window) and the normalizer
    (extent,), write the latent (extent, rest)."""
    return itemsize * (k * window * rest + extent * rest
                       + k * window + extent)
