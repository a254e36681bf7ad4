"""Configuration system for the repro framework.

Every architecture is described by a frozen ``ModelConfig`` dataclass; input
shapes by ``ShapeConfig``.  Configs are registered into a global registry so
launchers can select them with ``--arch <id> --shape <name>``.

The reduced ("smoke") variant of every architecture keeps the *family
structure* (block pattern, attention kind, MoE/SSM wiring) while shrinking
width/depth/vocab so a single CPU device can run a forward/train step.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttnConfig:
    """Attention flavour for a block.

    kind: "full" (causal), "local" (sliding window causal), "mla"
    (DeepSeek-style multi-head latent attention with compressed KV).
    """

    kind: str = "full"
    window: int = 1024            # sliding window (kind == "local")
    rope_base: float = 10_000.0
    rope_base_local: float = 10_000.0   # gemma3 uses a different base for local layers
    kv_lora_rank: int = 512       # MLA: compressed KV dim
    qk_rope_dim: int = 64         # MLA: rope sub-dim carried uncompressed
    qk_nope_dim: int = 128        # MLA: non-rope head dim
    v_head_dim: int = 128         # MLA: value head dim
    q_lora_rank: int = 0          # MLA: 0 = full-rank Q projection
    softmax_scale: Optional[float] = None


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 16
    num_shared_experts: int = 0
    top_k: int = 1
    d_ff_expert: int = 8192
    d_ff_shared: int = 0          # per shared expert; 0 → same as d_ff_expert
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01


@dataclass(frozen=True)
class SSMConfig:
    """Parameters shared by mamba-style SSM and xLSTM blocks."""

    state_dim: int = 16           # N: per-channel SSM state (mamba) / ignored by xlstm
    conv_width: int = 4           # depthwise conv width (mamba)
    expand: int = 2               # inner dim = expand * d_model (mamba, mLSTM)
    num_heads: int = 4            # recurrence heads (xlstm / hymba ssm heads)
    dt_rank: int = 0              # 0 → ceil(d_model / 16)
    chunk_size: int = 128         # chunked-parallel scan block (mLSTM / mamba train)
    slstm_proj_factor: float = 4.0 / 3.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 → d_model // num_heads
    # Block pattern, tiled to num_layers.  Entries:
    #   "attn"   full attention + MLP
    #   "local"  sliding-window attention + MLP
    #   "mla"    MLA attention + MLP (dense or moe FFN per moe_layer_pattern)
    #   "moe"    attention + MoE FFN
    #   "hybrid" parallel attention + mamba heads, then MLP
    #   "mlstm" / "slstm"  xLSTM blocks
    block_pattern: Tuple[str, ...] = ("attn",)
    attn: AttnConfig = field(default_factory=AttnConfig)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    frontend: Optional[str] = None    # None | "audio" | "vlm"
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # training-time knobs
    remat: str = "dots"           # none | dots | full
    optimizer_state_dtype: str = "float32"
    grad_accum: int = 1           # microbatch accumulation (activation memory / N)
    attn_chunk: int = 512         # flash attention q/kv chunk (loop trip count)
    scan_group: int = 0           # 0 → len(block_pattern); layers scanned in groups
    # long-context capability: archs whose decode memory/compute stays bounded
    # (SSM/hybrid/local-attention).  Pure full-attention archs skip long_500k.
    subquadratic: bool = False
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def layer_pattern(self) -> Tuple[str, ...]:
        """block_pattern tiled to num_layers."""
        p = self.block_pattern
        reps = -(-self.num_layers // len(p))
        return (p * reps)[: self.num_layers]

    @property
    def group_size(self) -> int:
        g = self.scan_group or len(self.block_pattern)
        assert self.num_layers % g == 0, (self.name, self.num_layers, g)
        return g

    def param_count(self) -> int:
        """Analytic parameter count (exact for our parameterization)."""
        from repro.models.model import count_params  # local import, avoids cycle
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params
        return count_params(self, active_only=True)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ModelConfig] = {}
_REDUCERS: Dict[str, Callable[[ModelConfig], ModelConfig]] = {}


def register(cfg: ModelConfig, reducer: Optional[Callable[[ModelConfig], ModelConfig]] = None) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch config {cfg.name!r}")
    _REGISTRY[cfg.name] = cfg
    if reducer is not None:
        _REDUCERS[cfg.name] = reducer
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def one_chip_config() -> ModelConfig:
    """yi-9b at its published widths with its depth cut to 24 of 48 layers.

    Every width stays as published (d_model 4096, 32 query heads over 4 KV
    heads, head_dim 128, d_ff 11008, vocab 64000, bf16).  The whole model
    is ~17.7 GB of bf16 weights; 24 layers are ~9.4 GB, which leaves room
    for an 8-slot, 2048-token paged KV pool on one 16 GB v5e chip.
    """
    return replace(get_config("yi-9b"), num_layers=24)


def list_configs() -> Tuple[str, ...]:
    _ensure_loaded()
    return tuple(sorted(_REGISTRY))


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch, shape) is a live cell; see DESIGN.md §5."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "skip(full-attn): long_500k requires sub-quadratic attention"
    return True, ""


def reduced_config(name_or_cfg) -> ModelConfig:
    """Smoke-test variant: same family wiring, tiny dims."""
    cfg = name_or_cfg if isinstance(name_or_cfg, ModelConfig) else get_config(name_or_cfg)
    if cfg.name in _REDUCERS:
        return _REDUCERS[cfg.name](cfg)
    return default_reducer(cfg)


def default_reducer(cfg: ModelConfig) -> ModelConfig:
    n_heads = min(cfg.num_heads, 4)
    n_kv = max(1, min(cfg.num_kv_heads, n_heads))
    head_dim = 16
    d_model = n_heads * head_dim
    moe = cfg.moe
    if moe is not None:
        moe = replace(
            moe,
            num_experts=min(moe.num_experts, 8),
            top_k=min(moe.top_k, 2),
            d_ff_expert=32,
            d_ff_shared=32 if moe.num_shared_experts else 0,
        )
    ssm = cfg.ssm
    if ssm is not None:
        ssm = replace(ssm, state_dim=min(ssm.state_dim, 8), num_heads=min(ssm.num_heads, 2),
                      chunk_size=16)
    pat = cfg.block_pattern
    num_layers = len(pat) if len(pat) > 1 else 2
    return replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=num_layers,
        d_model=d_model,
        num_heads=n_heads,
        num_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=4 * d_model if cfg.d_ff else 0,
        vocab_size=256,
        moe=moe,
        ssm=ssm,
        attn=replace(cfg.attn, window=32, kv_lora_rank=16, qk_rope_dim=8,
                     qk_nope_dim=head_dim, v_head_dim=head_dim),
        scan_group=0,
        remat="none",
        grad_accum=1,          # perf knobs don't survive reduction
        attn_chunk=32,
    )


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if not _LOADED:
        _LOADED = True
        from repro import configs  # noqa: F401  (registers everything)


# convenience for dataclass printing
def as_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)
