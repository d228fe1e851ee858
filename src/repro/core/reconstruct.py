"""Position-aware latent reconstruction (paper §3.4, Eqs. 13-17).

Given the K local noise predictions and the partition plan, compute

    A(x) = sum_k I_k(x) * W^(k)_{pi_k(x)} * pred_k[pi_k(x)]     (Eq. 15)
    Z(x) = sum_k I_k(x) * W^(k)_{pi_k(x)}                       (Eq. 16)
    F(x) = A(x) / Z(x)                                          (Eq. 17)

This module is the single-host reference: a Python loop over partitions with
scatter-adds.  The SPMD engine (``core/spmd.py``) computes the same math with
one ``psum`` over the mesh axis; the Pallas kernel (``kernels/latent_blend``)
fuses weighting + accumulation for the TPU hot path.  All three are tested
against each other.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .partition import PartitionPlan
from .weights import global_normalizer, partition_weights


def _shape_weight(w: np.ndarray, ndim: int, axis: int) -> jnp.ndarray:
    """Broadcast a 1-D weight along ``axis`` of an ``ndim``-rank tensor."""
    shape = [1] * ndim
    shape[axis] = w.shape[0]
    return jnp.asarray(w).reshape(shape)


@jax.named_scope("lp.stitch")
def reconstruct(
    preds: Sequence[jnp.ndarray],
    plan: PartitionPlan,
    axis: int,
    accumulate_dtype=jnp.float32,
) -> jnp.ndarray:
    """Stitch K local predictions into the global prediction (Eq. 17).

    ``preds[k]`` has the shape of partition ``k``'s sub-latent; all other
    axes must agree.  Accumulation runs in ``accumulate_dtype`` (fp32 by
    default — bf16 overlap sums lose ~2 bits of mantissa at seams).
    """
    if len(preds) != plan.num_partitions:
        raise ValueError(
            f"got {len(preds)} predictions for K={plan.num_partitions}"
        )
    ref = preds[0]
    out_shape = list(ref.shape)
    out_shape[axis] = plan.extent
    acc = jnp.zeros(out_shape, dtype=accumulate_dtype)
    weights = partition_weights(plan)
    for k, pred in enumerate(preds):
        s, e = plan.lat_start[k], plan.lat_end[k]
        if pred.shape[axis] != e - s:
            raise ValueError(
                f"partition {k}: prediction extent {pred.shape[axis]} != "
                f"plan extent {e - s} along axis {axis}"
            )
        w = _shape_weight(weights[k], pred.ndim, axis)
        idx = [slice(None)] * pred.ndim
        idx[axis] = slice(s, e)
        acc = acc.at[tuple(idx)].add(pred.astype(accumulate_dtype) * w)
    z = _shape_weight(global_normalizer(plan), acc.ndim, axis)
    return (acc / z).astype(ref.dtype)
