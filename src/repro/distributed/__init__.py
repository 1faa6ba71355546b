from .sharding import (
    AxisRules,
    INFER_RULES,
    LONG_DECODE_RULES,
    TRAIN_RULES,
    axis_rules,
    constrain,
    current_mesh,
    current_rules,
    logical_sharding,
    logical_spec,
    tree_logical_sharding,
    tree_shardings,
)

__all__ = [
    "AxisRules", "INFER_RULES", "LONG_DECODE_RULES", "TRAIN_RULES",
    "axis_rules", "constrain", "current_mesh", "current_rules",
    "logical_sharding", "logical_spec", "tree_logical_sharding",
    "tree_shardings",
]
