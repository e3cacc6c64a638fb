"""Architecture configs, input shapes, and ShapeDtypeStruct input specs.

Every assigned architecture is a frozen :class:`ArchConfig`; the registry
maps ``--arch <id>`` names to configs. ``input_specs`` builds the
allocation-free ShapeDtypeStruct stand-ins the multi-pod dry-run lowers
against. ``reduced()`` produces the CPU-smoke-test downscale of the same
family.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "ArchConfig", "ShapeConfig", "SHAPES", "register", "get_config",
    "list_archs", "reduced", "input_specs", "cell_is_runnable",
]


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'audio' | 'ssm' | 'vlm' | 'hybrid'
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    # Block composition: the repeating unit of layer types; the full stack is
    # unit * (n_layers // len(unit)). Types: 'dense' (attn+mlp), 'moe'
    # (attn+moe), 'mlstm', 'slstm', 'hymba' (parallel attn+ssm, +mlp).
    unit: Tuple[str, ...] = ("dense",)
    act: str = "swiglu"  # 'swiglu' | 'geglu' | 'gelu' | 'relu2'
    norm: str = "rms"  # 'rms' | 'ln'
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6  # RMS norm epsilon
    # Multi-head latent attention (DeepSeek-V2/V3), on when kv_lora_rank
    # > 0: queries x @ wq give n_heads x (qk_nope_dim + qk_rope_dim); the
    # latent x @ wkva gives kv_lora_rank RMS-normed columns, up-projected
    # to n_heads x (qk_nope_dim + head_dim) keys and values, and
    # qk_rope_dim rotary key columns shared by every head. head_dim is
    # the value width.
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    # Leading dense layers (attn + an MLP of d_ff) before the repeating
    # unit; they count in n_layers.
    n_lead: int = 0
    # MoE. n_experts is the router's width (every expert of the layer);
    # this program holds held_experts of them (0: all), from first_expert
    # on, and computes only their part of the layer's output.
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0  # expert width (0: d_ff)
    n_shared_experts: int = 0  # always-on experts, one MLP of n * moe_d_ff
    score_func: str = "softmax"  # 'softmax' | 'sigmoid'
    routed_scale: float = 1.0  # multiplies the routed experts' gates
    held_experts: int = 0
    first_expert: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    d_inner: int = 0  # mamba inner dim (0 => 2*d_model)
    conv_width: int = 4
    # Enc-dec (whisper): encoder layers + stub-frontend frame count.
    enc_layers: int = 0
    enc_seq: int = 0
    # VLM (paligemma): stub-frontend patch-token count (bidirectional prefix).
    img_tokens: int = 0
    # Hymba sliding-window size used by attention for the long_500k shape.
    window: int = 0
    tie_embed: bool = False
    # True when sequence mixing is sub-quadratic (may run long_500k).
    subquadratic: bool = False
    dtype: str = "bfloat16"

    @property
    def n_units(self) -> int:
        return (self.n_layers - self.n_lead) // len(self.unit)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """Width of a query/key head: nope + rope columns under MLA."""
        return self.qk_nope_dim + self.qk_rope_dim if self.mla \
            else self.head_dim

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_held(self) -> int:
        return self.held_experts or self.n_experts

    @property
    def mamba_d_inner(self) -> int:
        return self.d_inner or 2 * self.d_model

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        hq, hkv, hd = self.n_heads, self.n_kv, self.head_dim
        per_type = {}
        if self.mla:
            r, dq = self.kv_lora_rank, self.qk_head_dim
            attn = (d * hq * dq + d * (r + self.qk_rope_dim) + r
                    + r * hq * (self.qk_nope_dim + hd) + hq * hd * d)
        else:
            attn = d * (hq + 2 * hkv) * hd + hq * hd * d
        gated = self.act in ("swiglu", "geglu")
        mlp = d * f * (3 if gated else 2)
        per_type["dense"] = attn + mlp
        fe = self.expert_d_ff * (3 if gated else 2) * d
        per_type["moe"] = (attn + self.n_held * fe + d * self.n_experts
                           + self.n_shared_experts * fe)
        di = self.mamba_d_inner
        per_type["hymba"] = (
            attn + mlp + 2 * d * di + di * d
            + di * (2 * self.ssm_state + 2) + di * self.conv_width
        )
        per_type["mlstm"] = 2 * d * (2 * d) + (2 * d) * d + 3 * d
        per_type["slstm"] = 8 * d * d // max(self.n_heads, 1) * self.n_heads
        total = 0
        for t in self.unit:
            total += per_type.get(t, per_type["dense"]) * self.n_units
        total += self.n_lead * per_type["dense"]
        total += v * d * (1 if self.tie_embed else 2)
        total += self.enc_layers * (attn + mlp)
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts)."""
        if self.n_experts == 0:
            return self.param_count()
        d, f = self.d_model, self.expert_d_ff
        gated = self.act in ("swiglu", "geglu")
        dense_experts = self.n_held * d * f * (3 if gated else 2)
        active_experts = self.top_k * d * f * (3 if gated else 2)
        return self.param_count() - (
            dense_experts - active_experts
        ) * self.n_units


_REGISTRY: Dict[str, ArchConfig] = {}

_ARCH_MODULES = [
    "moonlight_16b_a3b", "granite_moe_1b_a400m", "gemma_2b",
    "deepseek_coder_33b", "llama3_8b", "minitron_4b", "whisper_tiny",
    "xlstm_350m", "paligemma_3b", "hymba_1_5b", "nemotron3_8b",
]


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def _ensure_loaded() -> None:
    if len(_REGISTRY) >= len(_ARCH_MODULES):
        return
    for mod in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{mod}")


def get_config(name: str) -> ArchConfig:
    _ensure_loaded()
    return _REGISTRY[name]


def list_archs():
    _ensure_loaded()
    return sorted(_REGISTRY)


def cell_is_runnable(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch x shape) is a defined dry-run cell (see
    repro.launch.dryrun)."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "long_500k needs sub-quadratic attention (skip noted)"
    return True, ""


def reduced(cfg: ArchConfig) -> ArchConfig:
    """CPU-smoke-scale downscale preserving the family's structure."""
    # MLA keeps one key/value head per query head.
    kv = 4 if cfg.mla else 1 if cfg.n_kv == 1 else 2
    mla = {}
    if cfg.mla:
        mla = dict(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8)
    n_lead = min(cfg.n_lead, 1)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-reduced",
        n_layers=n_lead + (2 * len(cfg.unit) if len(cfg.unit) > 1 else 2),
        d_model=64,
        n_heads=4,
        n_kv=kv,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 96,
        vocab=512,
        n_experts=min(cfg.n_experts, 4),
        top_k=min(cfg.top_k, 2),
        moe_d_ff=min(cfg.moe_d_ff, 32),
        held_experts=min(cfg.held_experts, 4),
        first_expert=0,
        n_lead=n_lead,
        **mla,
        ssm_state=min(cfg.ssm_state, 8) if cfg.ssm_state else 0,
        d_inner=128 if cfg.family in ("hybrid",) else 0,
        enc_layers=min(cfg.enc_layers, 2),
        enc_seq=min(cfg.enc_seq, 16) if cfg.enc_seq else 0,
        img_tokens=min(cfg.img_tokens, 8) if cfg.img_tokens else 0,
        window=min(cfg.window, 8) if cfg.window else 0,
    )


def _frontend_specs(cfg: ArchConfig, batch: int):
    """Stub modality-frontend inputs (precomputed embeddings)."""
    dt = jnp.bfloat16
    extras = {}
    if cfg.family == "audio":
        extras["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.enc_seq, cfg.d_model), dt
        )
    if cfg.family == "vlm":
        extras["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.img_tokens, cfg.d_model), dt
        )
    return extras


def input_specs(cfg: ArchConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train  -> {'tokens', 'labels', frontends...}
    prefill-> {'tokens', frontends...}
    decode -> {'token', 'cur_index'}; the KV/state cache specs come from
              repro.models.api.cache_specs (they depend on layer structure).
    """
    b, s = shape.global_batch, shape.seq_len
    specs = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs.update(_frontend_specs(cfg, b))
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        specs.update(_frontend_specs(cfg, b))
    elif shape.kind == "decode":
        specs["token"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        # One position per slot: mixed-length continuous batching reads
        # and writes each row at its own position (docs/serving.md).
        specs["cur_index"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    else:
        raise ValueError(shape.kind)
    return specs
