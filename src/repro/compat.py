"""Thin helpers over the JAX surface (jax 0.9).

Every mesh is built with Auto axis types (``jax.make_mesh`` would
otherwise make them Explicit, which the LP engines do not annotate for),
and ``cost_analysis`` never returns None.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def cost_analysis(compiled) -> Dict[str, Any]:
    """``Compiled.cost_analysis()`` as a dict ({} when the backend has
    no analysis)."""
    return compiled.cost_analysis() or {}
