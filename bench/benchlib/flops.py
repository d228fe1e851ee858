"""Operations and bytes, counted from shapes, of what every architecture
shares: the tokens of a latent and the LP stitch.  A step's FLOPs are
the architecture module's ``step_flops`` (``bench/models/``)."""
from __future__ import annotations

from typing import Sequence


def tokens(latent: Sequence[int], patch: Sequence[int]) -> int:
    t, h, w = latent
    pt, ph, pw = patch
    return (t // pt) * (h // ph) * (w // pw)


def latent_blend_bytes(k: int, window: int, extent: int, rest: int,
                       itemsize: int = 4) -> int:
    """Least HBM traffic of one stitch: read the K window predictions
    (K, window, rest), their weights (K, window) and the normalizer
    (extent,), write the latent (extent, rest)."""
    return itemsize * (k * window * rest + extent * rest
                       + k * window + extent)
