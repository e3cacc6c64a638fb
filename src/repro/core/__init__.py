"""MoR core: GAM scaling (Alg. 1) + Mixture-of-Representations (Alg. 2)."""
from .formats import (
    BF16,
    E4M3,
    E5M2,
    FORMATS,
    NVFP4,
    NVFP4_MICRO,
    FormatSpec,
    cast_to_format,
    cast_to_nvfp4,
)
from .gam import GamScales, compute_scales, split_mantissa_exponent
from .linear import N_BWD_EVENTS, N_FWD_EVENTS, mor_dot, new_token
from .metrics import (
    block_dynamic_range_ok,
    block_relative_error_sums,
    relative_error,
)
from .mor import (
    EVENT_GEMM,
    EVENT_GRAD,
    EVENT_MOMENT_M,
    EVENT_MOMENT_V,
    STAT_AMAX,
    STAT_DECISION,
    STAT_EVENT_KIND,
    STAT_FRAC_BF16,
    STAT_FRAC_E4M3,
    STAT_FRAC_E5M2,
    STAT_FRAC_NVFP4,
    STAT_GROUP_MANTISSA,
    STAT_MICRO_SCALE_BPE,
    STAT_NONZERO_FRAC,
    STAT_PAYLOAD_BPE,
    STAT_REL_ERR,
    STATS_WIDTH,
    mor_quantize,
    partition_of,
    quant_dequant,
    quantize_for_gemm,
)
from .partition import (
    PER_BLOCK_64,
    PER_BLOCK_128,
    PER_CHANNEL,
    PER_TENSOR,
    SUB_CHANNEL_128,
    Partition,
    block_amax,
)
from .collectives import pmax_over, psum_over, shard_map_unchecked
from .policy import (
    BF16_BASELINE,
    SUBTENSOR2_MOR,
    SUBTENSOR3_MOR,
    SUBTENSOR4_MOR,
    TENSOR_MOR,
    MoRDotPolicy,
    MoRPolicy,
    paper_default,
    with_mesh_axes,
)
from .stats import MoRStatsTracker, RelErrHistogram

__all__ = [
    "BF16", "E4M3", "E5M2", "FORMATS", "NVFP4", "NVFP4_MICRO",
    "FormatSpec", "cast_to_format", "cast_to_nvfp4",
    "GamScales", "compute_scales", "split_mantissa_exponent",
    "N_BWD_EVENTS", "N_FWD_EVENTS", "mor_dot", "new_token",
    "block_dynamic_range_ok", "block_relative_error_sums", "relative_error",
    "STATS_WIDTH", "mor_quantize", "partition_of", "quant_dequant",
    "quantize_for_gemm",
    "STAT_DECISION", "STAT_REL_ERR", "STAT_AMAX", "STAT_FRAC_E4M3",
    "STAT_FRAC_E5M2", "STAT_FRAC_BF16", "STAT_NONZERO_FRAC",
    "STAT_GROUP_MANTISSA", "STAT_FRAC_NVFP4", "STAT_MICRO_SCALE_BPE",
    "STAT_EVENT_KIND", "STAT_PAYLOAD_BPE",
    "EVENT_GEMM", "EVENT_GRAD", "EVENT_MOMENT_M", "EVENT_MOMENT_V",
    "PER_BLOCK_64", "PER_BLOCK_128", "PER_CHANNEL", "PER_TENSOR",
    "SUB_CHANNEL_128", "Partition", "block_amax",
    "BF16_BASELINE", "SUBTENSOR2_MOR", "SUBTENSOR3_MOR", "SUBTENSOR4_MOR",
    "TENSOR_MOR", "MoRDotPolicy", "MoRPolicy", "paper_default",
    "with_mesh_axes",
    "pmax_over", "psum_over", "shard_map_unchecked",
    "MoRStatsTracker", "RelErrHistogram",
]
