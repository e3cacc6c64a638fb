from . import rules
from .rules import (
    mixed_operand_pspec,
    qtensor_pspec_from_dense,
    quantized_param_specs,
    shard_map_unchecked,
)

__all__ = [
    "rules",
    "mixed_operand_pspec",
    "qtensor_pspec_from_dense",
    "quantized_param_specs",
    "shard_map_unchecked",
]
