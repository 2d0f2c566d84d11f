"""Registry of assigned architectures (``--arch <id>``)."""
from repro.configs.base import ArchConfig
from repro.configs.shapes import (
    SHAPES,
    ShapeSuite,
    ALL_SHAPE_NAMES,
    batch_specs,
    cache_seq_len,
    cell_supported,
    decode_batch_specs,
    token_split,
)

from repro.configs.granite_3_2b import CONFIG as _granite_3_2b
from repro.configs.command_r_plus_104b import CONFIG as _command_r_plus_104b
from repro.configs.internlm2_20b import CONFIG as _internlm2_20b
from repro.configs.yi_6b import CONFIG as _yi_6b
from repro.configs.granite_moe_1b_a400m import CONFIG as _granite_moe_1b_a400m
from repro.configs.qwen3_moe_30b_a3b import CONFIG as _qwen3_moe_30b_a3b
from repro.configs.mamba2_130m import CONFIG as _mamba2_130m
from repro.configs.hymba_1_5b import CONFIG as _hymba_1_5b
from repro.configs.whisper_medium import CONFIG as _whisper_medium
from repro.configs.paligemma_3b import CONFIG as _paligemma_3b
from repro.configs.trinity_mini import CONFIG as _trinity_mini

REGISTRY = {
    c.name: c
    for c in (
        _granite_3_2b,
        _command_r_plus_104b,
        _internlm2_20b,
        _yi_6b,
        _granite_moe_1b_a400m,
        _qwen3_moe_30b_a3b,
        _mamba2_130m,
        _hymba_1_5b,
        _whisper_medium,
        _paligemma_3b,
        _trinity_mini,
    )
}

ALL_ARCH_NAMES = tuple(REGISTRY)


def get_config(name: str) -> ArchConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]


__all__ = [
    "ArchConfig",
    "ShapeSuite",
    "SHAPES",
    "REGISTRY",
    "ALL_ARCH_NAMES",
    "ALL_SHAPE_NAMES",
    "get_config",
    "batch_specs",
    "decode_batch_specs",
    "cache_seq_len",
    "cell_supported",
    "token_split",
]
