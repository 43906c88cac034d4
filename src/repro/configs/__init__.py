"""Config registry: the architectures + the four shape cells.

Selection surface for every launcher/benchmark: ``--arch <id>`` resolves
through :func:`get_config`; :func:`applicable_shapes` encodes the
skip rules from the assignment (long_500k needs a sub-quadratic arch).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from .base import (
    LayerSpec,
    MLASpec,
    MambaSpec,
    ModelConfig,
    MoESpec,
    RWKVSpec,
    ShapeConfig,
    SHAPES,
    smoke_variant,
)
from .codeqwen1_5_7b import CONFIG as _codeqwen
from .gemma2_2b import CONFIG as _gemma2
from .internvl2_2b import CONFIG as _internvl2
from .jamba_v0_1_52b import CONFIG as _jamba
from .llama4_maverick_400b import CONFIG as _llama4
from .moonlight_16b_a3b import CONFIG as _moonlight
from .nemotron_4_15b import CONFIG as _nemotron
from .olmoe_1b_7b import CONFIG as _olmoe
from .rwkv6_7b import CONFIG as _rwkv6
from .seamless_m4t_medium import CONFIG as _seamless
from .stablelm_3b import CONFIG as _stablelm

__all__ = [
    "ARCHS",
    "SHAPES",
    "get_config",
    "applicable_shapes",
    "all_cells",
    "smoke_variant",
    "ModelConfig",
    "ShapeConfig",
    "LayerSpec",
    "MoESpec",
    "MLASpec",
    "MambaSpec",
    "RWKVSpec",
]

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _jamba,
        _codeqwen,
        _gemma2,
        _nemotron,
        _stablelm,
        _rwkv6,
        _seamless,
        _llama4,
        _olmoe,
        _internvl2,
        _moonlight,
    )
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def applicable_shapes(cfg: ModelConfig) -> List[ShapeConfig]:
    """The assigned shape cells this architecture actually runs.

    ``long_500k`` requires sub-quadratic attention (SSM/hybrid/windowed);
    pure full-attention archs record a SKIP for it.
    Every assigned arch has a decoder, so decode shapes always apply.
    """
    out = []
    for shape in SHAPES.values():
        if shape.name == "long_500k" and not cfg.sub_quadratic:
            continue
        out.append(shape)
    return out


def all_cells() -> List[Tuple[ModelConfig, ShapeConfig]]:
    """Every runnable (arch × shape) cell, in registry order."""
    return [(cfg, shape) for cfg in ARCHS.values() for shape in applicable_shapes(cfg)]
