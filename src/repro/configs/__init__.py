from repro.configs.base import (
    ModelConfig,
    MoEConfig,
    SSMConfig,
    ShapeConfig,
    OptimizerConfig,
    ShardingConfig,
    TrainConfig,
    SHAPES,
    SHAPES_BY_NAME,
    shape_applicable,
    replace,
)
from repro.configs.registry import ARCH_IDS, get_config, get_reduced, all_cells

__all__ = [
    "ModelConfig", "MoEConfig", "SSMConfig", "ShapeConfig", "OptimizerConfig",
    "ShardingConfig", "TrainConfig", "SHAPES", "SHAPES_BY_NAME",
    "shape_applicable", "replace", "ARCH_IDS", "get_config", "get_reduced", "all_cells",
]
