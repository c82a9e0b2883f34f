"""Architecture registry: ``get_config("<arch-id>")`` for every assigned arch."""
from typing import Dict, List

from .base import InputShape, ModelConfig
from .shapes import SHAPES, get_shape

from .tinyllama_1_1b import CONFIG as _tinyllama
from .internvl2_76b import CONFIG as _internvl2
from .zamba2_7b import CONFIG as _zamba2
from .olmoe_1b_7b import CONFIG as _olmoe
from .xlstm_125m import CONFIG as _xlstm
from .granite_3_2b import CONFIG as _granite
from .whisper_small import CONFIG as _whisper
from .starcoder2_3b import CONFIG as _sc2_3b
from .starcoder2_7b import CONFIG as _sc2_7b
from .llama4_scout_17b_a16e import CONFIG as _llama4
from .kimi_vl_a3b import CONFIG as _kimi_vl

CONFIGS: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _tinyllama,
        _internvl2,
        _zamba2,
        _olmoe,
        _xlstm,
        _granite,
        _whisper,
        _sc2_3b,
        _sc2_7b,
        _llama4,
        _kimi_vl,
    )
}

ARCH_IDS: List[str] = sorted(CONFIGS)


def get_config(name: str) -> ModelConfig:
    try:
        return CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; have {ARCH_IDS}")
