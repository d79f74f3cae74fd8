"""Architecture + shape configs (assignment table)."""
from repro.configs import archs as _archs
from repro.configs.base import (ArchConfig, EncoderSpec, MoESpec, get_config,
                                list_configs, scaled_down)
from repro.configs.shapes import SHAPES, ShapeSpec

ALL_ARCHS = _archs.ALL

__all__ = ["ArchConfig", "EncoderSpec", "MoESpec", "get_config",
           "list_configs", "scaled_down", "SHAPES", "ShapeSpec", "ALL_ARCHS"]
