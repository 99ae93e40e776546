"""Architecture registry: --arch <id> resolves here."""

from typing import Dict, Optional

from .base import (SHAPES, LONG_CONTEXT_ARCHS, HybridConfig, MLAConfig,
                   ModelConfig, MoEConfig, ShapeConfig, SSMConfig,
                   reduce_for_smoke)
from .starcoder2_3b import CONFIG as STARCODER2_3B
from .qwen2_72b import CONFIG as QWEN2_72B
from .gemma_2b import CONFIG as GEMMA_2B
from .gemma3_27b import CONFIG as GEMMA3_27B
from .musicgen_medium import CONFIG as MUSICGEN_MEDIUM
from .phi3_vision_4b import CONFIG as PHI3_VISION
from .deepseek_v3_671b import CONFIG as DEEPSEEK_V3
from .granite_moe_1b import CONFIG as GRANITE_MOE
from .mamba2_1b import CONFIG as MAMBA2_1B
from .zamba2_2b import CONFIG as ZAMBA2_2B

ARCHS: Dict[str, ModelConfig] = {c.name: c for c in [
    STARCODER2_3B, QWEN2_72B, GEMMA_2B, GEMMA3_27B, MUSICGEN_MEDIUM,
    PHI3_VISION, DEEPSEEK_V3, GRANITE_MOE, MAMBA2_1B, ZAMBA2_2B,
]}


def get_arch(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def job_config(name: str, *, smoke: bool,
               num_layers: Optional[int] = None) -> ModelConfig:
    """The config a train/serve job runs: the reduced CPU config under
    ``smoke``, else the published one, optionally cut in depth only."""
    cfg = get_arch(name)
    if smoke:
        if num_layers is not None:
            raise ValueError("num_layers cuts a published config; the smoke "
                             "config sets its own depth")
        return reduce_for_smoke(cfg)
    if num_layers is not None:
        if num_layers < 1:
            raise ValueError(f"num_layers must be positive, got {num_layers}")
        cfg = cfg.with_(num_layers=num_layers)
    return cfg


def cell_is_runnable(arch: str, shape: str) -> bool:
    """long_500k only for sub-quadratic archs (DESIGN.md §4)."""
    if shape == "long_500k":
        return arch in LONG_CONTEXT_ARCHS
    return True


__all__ = ["ARCHS", "SHAPES", "LONG_CONTEXT_ARCHS", "ModelConfig",
           "MoEConfig", "MLAConfig", "SSMConfig", "HybridConfig",
           "ShapeConfig", "get_arch", "job_config", "cell_is_runnable",
           "reduce_for_smoke"]
