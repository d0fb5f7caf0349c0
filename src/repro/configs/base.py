"""Configuration system for repro.

Every assigned architecture is a frozen :class:`ModelConfig`; every
(architecture x input-shape) dry-run cell is a :class:`ShapeConfig`.
Configs are plain dataclasses (no framework dependency) so they can be
hashed, serialized into checkpoints manifests, and diffed.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # number of token groups used by the capacity-based dispatch.  0 means
    # "one group per data shard", resolved against the mesh at lowering time.
    n_groups: int = 0
    # target tokens per dispatch group (smaller -> smaller dispatch/combine
    # tensors and fewer dispatch FLOPs, at more capacity-drop variance)
    group_tokens: int = 2048
    aux_loss_weight: float = 0.01
    router_z_loss_weight: float = 1e-3
    # "tp"  : experts' ffn dim sharded over the model axis (tensor parallel)
    # "ep"  : expert dim sharded over the model axis (expert parallel)
    expert_sharding: str = "tp"


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD parameters."""
    state_dim: int = 0          # N (ssm_state)
    head_dim: int = 64          # P
    expand: int = 2             # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256       # SSD chunk length
    dt_min: float = 1e-3
    dt_max: float = 0.1
    # dtype of the full-size intra-chunk tensors (states stay f32);
    # "bfloat16" halves the SSD HBM traffic at bf16 storage precision
    intra_dtype: str = "float32"


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    # --- attention details -------------------------------------------------
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_global: float = 0.0      # gemma3 global layers; 0 -> rope_theta
    sliding_window: int = 0             # 0 -> full attention
    local_global_ratio: int = 0         # gemma3: N local layers per 1 global
    attn_logit_softcap: float = 0.0     # grok/gemma-style soft capping
    # --- mixture of experts -------------------------------------------------
    moe: MoEConfig = field(default_factory=MoEConfig)
    # --- state space --------------------------------------------------------
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # --- hybrid (zamba2): a shared attention block every k ssm blocks -------
    shared_attn_every: int = 0
    adapter_rank: int = 0               # per-site LoRA rank of the shared MLP
    # --- encoder/decoder (whisper) ------------------------------------------
    n_encoder_layers: int = 0
    encoder_seq: int = 0                # precomputed frame count (stub frontend)
    # --- vision (llama-3.2): gated cross-attn every k layers -----------------
    cross_attn_every: int = 0
    vision_tokens: int = 0              # precomputed patch-embedding count
    # --- misc ----------------------------------------------------------------
    act: str = "silu"                   # silu | gelu (tanh) | gelu_erf (exact)
    mlp_gated: bool = True              # SwiGLU/GeGLU vs plain 2-matrix MLP
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    max_position_embeddings: int = 1_048_576
    # --- numerics ------------------------------------------------------------
    param_dtype: str = "float32"        # master parameter dtype
    compute_dtype: str = "bfloat16"
    # vocabulary-chunked cross entropy (0 = disabled); bounds logits memory.
    vocab_chunk: int = 0
    # optimizer selection for this scale ("adamw" | "adafactor")
    optimizer: str = "adamw"
    # remat policy for the scanned layer ("full" | "dots" | "none")
    remat: str = "full"
    # nested remat: split the layer scan into this many checkpointed
    # groups (0 = flat scan). sqrt(n_layers)-ish gives minimal memory.
    remat_group: int = 0
    # attention implementation: "blocked" (scan online-softmax, XLA-lowered,
    # used for dry-runs), "naive" (reference), "pallas" (TPU runtime only).
    attention_impl: str = "blocked"
    attention_block_k: int = 512

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic archs that run the long_500k shape."""
        if self.family in ("ssm", "hybrid"):
            return True
        # sliding-window-dominant (gemma3 5:1 local:global)
        return self.local_global_ratio > 0

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs are decoders or enc-dec

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One dry-run cell input shape."""
    name: str               # train_4k | prefill_32k | decode_32k | long_500k
    kind: str               # train | prefill | decode
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeConfig("train_4k", "train", 4_096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524_288, 1)

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


def shapes_for(cfg: ModelConfig) -> tuple[ShapeConfig, ...]:
    """The shape set applicable to an architecture (see DESIGN.md §5)."""
    out = [TRAIN_4K, PREFILL_32K, DECODE_32K]
    if cfg.supports_long_context:
        out.append(LONG_500K)
    return tuple(out)


# ---------------------------------------------------------------------------
# Registry

_REGISTRY: dict[str, "ArchEntry"] = {}


@dataclass(frozen=True)
class ArchEntry:
    config: ModelConfig
    reduced: ModelConfig    # CPU-smoke-test variant of the same family


def register(config: ModelConfig, reduced: ModelConfig) -> ModelConfig:
    if config.name in _REGISTRY:
        raise ValueError(f"duplicate arch {config.name!r}")
    _REGISTRY[config.name] = ArchEntry(config=config, reduced=reduced)
    return config


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    entry = _REGISTRY[name]
    return entry.reduced if reduced else entry.config


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    """Import every config module exactly once (they self-register)."""
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    from repro.configs import (  # noqa: F401
        llama_3_2_vision_90b,
        zamba2_1_2b,
        qwen1_5_4b,
        qwen2_7b,
        gemma3_12b,
        gemma3_4b,
        dbrx_132b,
        grok_1_314b,
        mamba2_370m,
        whisper_tiny,
    )
