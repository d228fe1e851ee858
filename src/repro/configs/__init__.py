"""Architecture + shape registry.

``get_config(arch_id)`` returns the exact assigned config;
``cells()`` enumerates every (arch x shape) dry-run cell with its skip
status (DESIGN.md §Arch-applicability skip matrix).
"""
from __future__ import annotations

import importlib
from typing import Dict, Iterator, Optional, Tuple

from .base import LM_SHAPES, VDM_SHAPES, ArchConfig, ParallelConfig, ShapeConfig

_MODULES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "xlstm-1.3b": "xlstm_1p3b",
    "granite-3-2b": "granite_3_2b",
    "llama3-405b": "llama3_405b",
    "h2o-danube-1.8b": "h2o_danube_1p8b",
    "minitron-4b": "minitron_4b",
    "internvl2-26b": "internvl2_26b",
    "whisper-small": "whisper_small",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "wan21-dit-1.3b": "wan21_dit_1p3b",
    "hunyuanvideo-dit": "hunyuan_video",
}

VDM_ARCHS = ("wan21-dit-1.3b", "hunyuanvideo-dit")
ASSIGNED_ARCHS = tuple(k for k in _MODULES if k not in VDM_ARCHS)
ALL_ARCHS = tuple(_MODULES)

# archs with sub-quadratic attention paths — the only ones long_500k runs on
SUBQUADRATIC = ("zamba2-2.7b", "xlstm-1.3b", "h2o-danube-1.8b")


def get_config(arch: str) -> ArchConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def get_shape(name: str) -> ShapeConfig:
    if name in LM_SHAPES:
        return LM_SHAPES[name]
    if name in VDM_SHAPES:
        return VDM_SHAPES[name]
    raise KeyError(f"unknown shape {name!r}")


def skip_reason(arch: str, shape: str) -> Optional[str]:
    """None if the (arch, shape) cell runs; else why it is skipped."""
    cfg = get_config(arch)
    if cfg.family == "vdm":
        return None if shape in VDM_SHAPES else "vdm arch uses vdm shapes"
    if shape in VDM_SHAPES:
        return "LM arch does not take vdm shapes"
    if shape == "long_500k" and arch not in SUBQUADRATIC:
        return "full attention is quadratic at 500k (assignment skip rule)"
    return None


def cells(include_vdm: bool = True) -> Iterator[Tuple[str, str, Optional[str]]]:
    """All (arch, shape, skip_reason) dry-run cells."""
    for arch in ASSIGNED_ARCHS:
        for shape in LM_SHAPES:
            yield arch, shape, skip_reason(arch, shape)
    if include_vdm:
        for shape in VDM_SHAPES:
            yield "wan21-dit-1.3b", shape, None
