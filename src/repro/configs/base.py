"""Config system: architecture + shape + parallelism configs.

Every assigned architecture is a ``ModelConfig`` in its own module
(``repro.configs.<id>``); shapes are the four assigned input-shape sets.
``--arch <id>`` in the launchers resolves via ``repro.configs.get(<id>)``.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | hybrid_moe | encdec | vlm | xlstm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    # MoE
    n_experts: int = 0
    top_k: int = 0
    # SSM / hybrid
    ssm_state: int = 0
    shared_attn_period: int = 0       # zamba2: shared attn block every N layers
    ssm_heads: int = 0                # Mamba2 heads (0: d_inner // ssm_head_dim)
    ssm_head_dim: int = 64
    ssm_groups: int = 1               # B/C groups (only 1 is implemented)
    ssm_expand: int = 2               # d_inner = ssm_expand * d_model
    ssm_chunk: int = 128              # SSD chunk length
    # hybrid_moe: the published per-layer mixer list ("mamba" | "attention");
    # the first n_layers entries are built, each followed by an MoE FFN
    layer_types: tuple = ()
    shared_expert_ff: int = 0         # width of the shared SwiGLU expert
    n_experts_held: int = 0           # experts held here (0: all n_experts)
    expert_offset: int = 0            # id of the first held expert
    # muP scalars (granite): embeddings x mult, residual branches x mult,
    # attention scores x mult (None: 1/sqrt(head_dim)), logits / scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    norm_eps: float = 1e-6
    # xLSTM
    slstm_layers: tuple = ()
    # attention details
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    position_embedding: str = "rope"  # "rope" | "nope"
    qk_norm: bool = False
    tied_embeddings: bool = False
    window: Optional[int] = None      # sliding window used for long-context attn
    # encoder-decoder
    enc_layers: int = 0
    dec_layers: int = 0
    # shape applicability
    supports_long_context: bool = False
    has_decoder: bool = True
    # parallelism tier
    fsdp: bool = False                # FSDP weight sharding over 'data'
    expert_fsdp: bool = False         # additionally shard expert ff over 'data'
    optimizer_dtype: str = "float32"  # "bfloat16" for the 1T tier
    remat: bool = True                # activation checkpointing on the layer scan
    # -- beyond-baseline perf knobs (EXPERIMENTS.md §Perf; the dry-run's
    #    --profile=baseline ignores these, --profile=optimized applies them) --
    sharding_profile: str = "tp"      # "tp" (uniform TP rules) | "pure_dp"
    microbatches: int = 1             # grad-accumulation splits of the global batch
    remat_policy: str = "full"        # "full"=nothing_saveable | "dots" | "none"
    capacity_factor: float = 1.25     # MoE EP capacity factor
    zero1: bool = True                # ZeRO-1 optimizer-state sharding over data
    grad_dtype: str = "float32"       # gradient all-reduce dtype ("bfloat16" halves it)
    mlstm_chunk: int = 64             # mLSTM chunk length (HC1 iter4: 256)
    quad_dtype: str = "float32"       # intra-chunk quadratic operand dtype (HC1: bf16)
    moe_impl: str = "gather_weights"  # FSDP-MoE: "gather_weights" | "gather_tokens"
                                      # (HC2: weight-stationary, move tokens instead)
    mamba_split_proj: bool = False    # HC4: shard-aligned split mamba projections
    # modality frontend stub
    frontend: Optional[str] = None    # None | "audio_frames" | "vq_tokens"

    @property
    def is_encdec(self) -> bool:
        return self.family == "encdec"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                         # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = (
    "zamba2_2p7b",
    "seamless_m4t_medium",
    "stablelm_3b",
    "llama3p2_1b",
    "stablelm_1p6b",
    "granite_3_2b",
    "xlstm_125m",
    "chameleon_34b",
    "llama4_scout_17b_a16e",
    "kimi_k2_1t_a32b",
    "granite_4h_small",
)

# Mapping used by launchers: --arch accepts either the module id or the
# human-readable paper id.
ALIASES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "stablelm-3b": "stablelm_3b",
    "llama3.2-1b": "llama3p2_1b",
    "stablelm-1.6b": "stablelm_1p6b",
    "granite-3-2b": "granite_3_2b",
    "xlstm-125m": "xlstm_125m",
    "chameleon-34b": "chameleon_34b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "granite-4.0-h-small": "granite_4h_small",
    "esam-mnist": "esam_mnist",
}


def get(arch: str):
    """Return (module, ModelConfig) for an architecture id."""
    arch_id = ALIASES.get(arch, arch)
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.CONFIG


def smoke(arch: str):
    """Reduced same-family config for CPU smoke tests."""
    arch_id = ALIASES.get(arch, arch)
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.SMOKE


def applicable_shapes(cfg: ModelConfig) -> list[str]:
    """The assigned shape cells this architecture runs (skip rules per
    DESIGN.md §4: long_500k needs sub-quadratic; decode needs a decoder)."""
    shapes = ["train_4k", "prefill_32k"]
    if cfg.has_decoder:
        shapes.append("decode_32k")
        if cfg.supports_long_context:
            shapes.append("long_500k")
    return shapes
