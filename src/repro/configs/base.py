"""Configuration dataclasses for the repro framework.

Plain dataclasses (no external deps) so configs are hashable-ish, printable and
trivially serializable. One ``ModelConfig`` per assigned architecture lives in
``repro.configs.<arch>``; the registry maps ``--arch`` ids to them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm", "audio")


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block hyper-parameters."""

    d_state: int = 128          # N — state dimension per head
    head_dim: int = 64          # P — channels per SSM head
    expand: int = 2             # d_inner = expand * d_model
    chunk_size: int = 128       # SSD chunk length (MXU-aligned)
    n_groups: int = 1           # B/C groups (GVA-style)
    conv_width: int = 4         # depthwise causal conv width
    dt_min: float = 1e-3
    dt_max: float = 1e-1


@dataclass(frozen=True)
class MoEConfig:
    """Top-k routed mixture-of-experts FFN.

    ``num_experts`` is the router's width, the published count. Each token
    takes its ``top_k`` largest router logits and a softmax over those.

    ``group_size`` (the capacity layer of the ``moe`` family): tokens are
    routed in independent groups of this size (GShard "groups"). None =
    one global group, whose dispatch einsums are quadratic in tokens
    (ROADMAP 1.7).

    ``held_experts`` and ``first_expert`` (the dropless layer of the
    pattern hybrid): this chip holds experts ``[first_expert, first_expert
    + held_experts)`` and computes their part of the result; None holds
    all of them. ``shared_d_ff`` > 0 adds a shared SwiGLU expert of that
    width, which every token passes through."""

    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2
    group_size: Optional[int] = None
    held_experts: Optional[int] = None
    first_expert: int = 0
    shared_d_ff: int = 0

    @property
    def held(self) -> int:
        return self.num_experts if self.held_experts is None \
            else self.held_experts


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # one of FAMILIES
    num_layers: int
    d_model: int
    num_heads: int                    # query heads (0 for attention-free)
    num_kv_heads: int
    d_ff: int                         # FFN hidden (per expert when MoE)
    vocab_size: int
    head_dim: int = 0                 # 0 → d_model // num_heads
    # attention variants
    qk_norm: bool = False
    swa_window: Optional[int] = None  # sliding-window attention width
    rope_theta: float = 10_000.0
    # norms / activations
    norm_type: str = "rmsnorm"        # rmsnorm | np_layernorm | layernorm
    norm_eps: float = 1e-5
    act: str = "silu"
    mlp_type: str = "glu"             # glu (gate/up/down) | mlp (up/down)
    tie_embeddings: bool = False
    # family extensions
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0               # hybrid: one (shared) attn block every N ssm blocks
    shared_attn: bool = False         # hybrid: attention weights shared across insertions
    # pattern hybrid (granite-4.0-h): each layer's mixer, "mamba" or
    # "attention", then the layer's feed-forward; replaces attn_every
    layer_types: Optional[Tuple[str, ...]] = None
    # attention without positional encoding (NoPE), and its softmax scale
    # (None: 1/sqrt(head_dim))
    use_rope: bool = True
    attention_multiplier: Optional[float] = None
    # granite scalars: x0 = embed * embedding_multiplier; each layer's
    # mixer and feed-forward outputs are scaled by residual_multiplier;
    # logits are divided by logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # encoder-decoder (audio family)
    enc_dec: bool = False
    enc_layers: int = 0
    enc_ctx: int = 0                  # encoder context length (e.g. whisper 1500 frames)
    # modality frontend stubs: precomputed embeddings prepended to the token sequence
    vision_tokens: int = 0            # vlm: number of patch-embedding tokens
    vision_dim: int = 0               # vlm: patch-embedding feature dim (projected to d_model)
    frontend_note: str = ""
    # head padding (beyond-paper perf knob): grow q/kv head counts with
    # ZERO-weight heads so they tile the TP axis. Function-preserving: pad q
    # rows of wq and pad output rows of wo are zero, so pad heads contribute
    # exactly 0. None = the paper-faithful baseline (non-divisible heads are
    # replicated over the model axis instead — see sharding/specs.py).
    pad_q_heads: Optional[int] = None
    pad_kv_heads: Optional[int] = None

    def __post_init__(self):
        assert self.family in FAMILIES, self.family
        if self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        # a configuration read from JSON brings its groups as dicts and lists
        if isinstance(self.moe, dict):
            object.__setattr__(self, "moe", MoEConfig(**self.moe))
        if isinstance(self.ssm, dict):
            object.__setattr__(self, "ssm", SSMConfig(**self.ssm))
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            object.__setattr__(self, "layer_types", kinds)
            if len(kinds) != self.num_layers or \
                    not set(kinds) <= {"mamba", "attention"}:
                raise ValueError(f"layer_types must name mamba or attention "
                                 f"for each of {self.num_layers} layers: "
                                 f"{kinds}")

    # -- derived ------------------------------------------------------------
    @property
    def q_heads_eff(self) -> int:
        return self.pad_q_heads or self.num_heads

    @property
    def kv_heads_eff(self) -> int:
        return self.pad_kv_heads or self.num_kv_heads

    @property
    def mixers(self) -> Optional[Tuple[str, ...]]:
        """Each layer's mixer, "mamba" or "attention", for a model served
        by the layer stack; None for zamba2's shared-block hybrid and the
        encoder-decoder, whose layers have no single kind."""
        if self.layer_types:
            return self.layer_types
        if self.family == "ssm":
            return ("mamba",) * self.num_layers
        if self.family in ("dense", "moe", "vlm"):
            return ("attention",) * self.num_layers
        return None

    @property
    def ffn_kind(self) -> Optional[str]:
        """The feed-forward after each mixer: "held" (the dropless layer
        over this chip's share of the experts), "moe" (the capacity
        layer), "mlp", or None."""
        if self.moe:
            return "held" if self.layer_types else "moe"
        return "mlp" if self.d_ff else None

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """True when the arch can serve 500k-token contexts (SSM state or SWA ring)."""
        return self.family in ("ssm", "hybrid") or self.swa_window is not None

    @property
    def d_inner(self) -> int:
        return (self.ssm.expand * self.d_model) if self.ssm else 0

    @property
    def ssm_heads(self) -> int:
        return (self.d_inner // self.ssm.head_dim) if self.ssm else 0

    def param_count(self) -> int:
        """Parameter count, exact for what ``init_params`` instantiates
        for the layer stack (it counts the experts held here)."""
        c, D = self, self.d_model
        n = c.vocab_size * D                      # embed
        if not c.tie_embeddings:
            n += c.vocab_size * D                 # lm head
        per_attn = (
            c.num_heads * c.head_dim * D          # q
            + 2 * c.num_kv_heads * c.head_dim * D  # k, v
            + c.num_heads * c.head_dim * D        # o
            + (2 * c.head_dim if c.qk_norm else 0)  # q, k norms
        )
        per_ffn = (3 if c.mlp_type == "glu" else 2) * D * c.d_ff  # (gate,) up, down
        if c.moe:
            per_ffn = (c.moe.held * per_ffn + D * c.moe.num_experts
                       + 3 * D * c.moe.shared_d_ff)
        per_ssm = 0
        if c.ssm:
            di, s = c.d_inner, c.ssm
            conv_ch = di + 2 * s.n_groups * s.d_state
            per_ssm = (
                D * (2 * di + 2 * s.n_groups * s.d_state + self.ssm_heads)  # in_proj(zx) + BC + dt
                + (s.conv_width + 1) * conv_ch                                # conv + bias
                + self.ssm_heads * 3                                          # A_log, D, dt_bias
                + di * D                                                      # out_proj
                + di                                                          # gate norm
            )
        norm_p = 0 if c.norm_type == "np_layernorm" else D
        if c.mixers:
            n_attn = c.mixers.count("attention")
            per_layer = per_ffn + 2 * norm_p if c.ffn_kind else norm_p
            n += ((c.num_layers - n_attn) * per_ssm + n_attn * per_attn
                  + c.num_layers * per_layer + norm_p)
        elif c.enc_dec:
            n += c.enc_layers * (per_attn + per_ffn + 3 * norm_p)             # enc self+ffn
            n += c.num_layers * (2 * per_attn + per_ffn + 4 * norm_p)         # dec self+cross+ffn
        else:
            n_attn = 1 if c.shared_attn else max(1, c.num_layers // max(1, c.attn_every))
            n += c.num_layers * (per_ssm + 2 * norm_p) + n_attn * (per_attn + norm_p)
        if c.vision_tokens:
            n += c.vision_dim * D + D
        return int(n)

    def active_param_count(self) -> int:
        """Activated params per token: of the experts held, a token is
        expected to reach top_k / num_experts of them."""
        if not self.moe:
            return self.param_count()
        c, m = self, self.moe
        dense_ffn = 3 * c.d_model * c.d_ff
        unused = (m.held - m.top_k * m.held / m.num_experts) * dense_ffn \
            * c.num_layers
        return int(self.param_count() - unused)


# ---------------------------------------------------------------------------
# Input shapes (the assigned grid)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)
SHAPES_BY_NAME = {s.name: s for s in SHAPES}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """(runs?, reason) for an (arch, shape) cell. Skips are recorded, never silent."""
    if shape.name == "long_500k" and not model.sub_quadratic:
        return False, "long_500k needs sub-quadratic attention; %s is pure full-attention" % model.name
    return True, ""


# ---------------------------------------------------------------------------
# Training / sharding knobs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


@dataclass(frozen=True)
class ShardingConfig:
    policy: str = "tp"            # tp | fsdp_tp
    remat: str = "block"          # none | block | full
    scan_layers: bool = True


@dataclass(frozen=True)
class TrainConfig:
    microbatch_size: int = 8      # per-step microbatch (grad accumulation over global/micro)
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    sharding: ShardingConfig = field(default_factory=ShardingConfig)
    seed: int = 0
    log_every: int = 10
    checkpoint_every: int = 100
    keep_checkpoints: int = 3


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
