"""Configuration system: frozen dataclasses for model / shape / mesh / LMS /
DDL / training, plus the architecture registry and shape-applicability rules.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm", "audio")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int            # query heads (0 for attention-free)
    num_kv_heads: int
    head_dim: int
    d_ff: int                 # MLP hidden (per-expert hidden for MoE)
    vocab_size: int

    # dense-transformer knobs
    qkv_bias: bool = False
    use_bias: bool = False            # bias on all linear layers (starcoder2)
    norm_type: str = "rmsnorm"        # rmsnorm | layernorm | layernorm_nonparam
    norm_eps: float = 1e-6
    mlp_act: str = "swiglu"           # swiglu | gelu | geglu
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False

    # MoE: a held-experts layer (models/moe.py) routes over all
    # `num_experts` and computes the `experts_held` of them from
    # `experts_offset` on (0 held: all), dropping no token
    num_experts: int = 0
    experts_per_token: int = 0
    experts_held: int = 0
    experts_offset: int = 0
    shared_experts: int = 0           # always-on experts, d_ff wide each
    router_score: str = "softmax"     # softmax | sigmoid
    router_scale: float = 1.0         # on the top-k weights, renormalised
    router_bias_rate: float = 0.0     # selection-bias step per train step
    router_aux_weight: float = 0.01   # balance-loss weight
    first_dense_layers: int = 0       # leading dense layers, outside the stack
    dense_d_ff: int = 0               # their MLP width

    # latent attention (MLA): kv_lora_rank > 0; head_dim is the q/k width
    # (nope + rope), v_head_dim the value width
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_ngroups: int = 1

    # hybrid (RecurrentGemma)
    block_pattern: Tuple[str, ...] = ()   # e.g. ("rglru","rglru","attn")
    window: int = 0                       # local-attention window
    lru_width: int = 0

    # multimodal stubs
    frontend: Optional[str] = None        # "vision" | "audio"
    mrope_sections: Tuple[int, ...] = ()  # M-RoPE split of head_dim/2 freqs
    encoder_layers: int = 0               # >0 => encoder-decoder (whisper)
    encoder_seq: int = 1500               # audio frames after conv frontend

    dtype: str = "bfloat16"

    def __post_init__(self):
        assert self.family in FAMILIES, self.family
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score {self.router_score!r}: softmax "
                             "or sigmoid")

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held(self) -> int:
        """Routed experts this chip computes."""
        return self.experts_held or self.num_experts

    @property
    def stack_layers(self) -> int:
        """Layers of the scanned decoder stack (the leading dense layers
        are not in it)."""
        return self.num_layers - self.first_dense_layers

    # ---- derived properties -------------------------------------------------
    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """True if a 500k-token KV history is bounded (SSM state / local window)."""
        if self.family == "ssm":
            return True
        if self.family == "hybrid" and self.window > 0:
            return True
        return False

    @property
    def d_inner(self) -> int:  # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim if self.ssm_state else 0

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind for the decoder stack."""
        if self.family == "ssm":
            return ("ssd",) * self.num_layers
        if self.family == "hybrid" and self.block_pattern:
            pat = self.block_pattern
            return tuple(pat[i % len(pat)] for i in range(self.num_layers))
        return ("mla" if self.is_mla else "attn",) * self.stack_layers

    # ---- parameter counting (used by planner + roofline MODEL_FLOPS) -------
    def param_count(self) -> int:
        return sum(n for _, n in self.param_breakdown())

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        total = 0
        for name, n in self.param_breakdown():
            if name == "moe_experts":
                total += n * self.experts_per_token // max(self.num_experts, 1)
            else:
                total += n
        return total

    def param_breakdown(self):
        """[(component, param_count)] for the full model."""
        out = []
        d = self.d_model
        out.append(("embed", self.vocab_size * d))
        if not self.tie_embeddings:
            out.append(("lm_head", self.vocab_size * d))
        kinds = self.layer_kinds()
        n_attn = sum(1 for k in kinds if k in ("attn", "local_attn"))
        n_ssd = sum(1 for k in kinds if k == "ssd")
        n_rglru = sum(1 for k in kinds if k == "rglru")

        if self.is_mla:
            h, r, dr = self.num_heads, self.kv_lora_rank, self.qk_rope_dim
            dn, dv = self.head_dim - dr, self.v_head_dim
            per = (d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv)
                   + h * dv * d)
            out.append(("attn", self.num_layers * per))
        elif n_attn:
            q = d * self.num_heads * self.head_dim + (self.num_heads * self.head_dim if self.qkv_bias or self.use_bias else 0)
            kv = 2 * (d * self.num_kv_heads * self.head_dim + (self.num_kv_heads * self.head_dim if self.qkv_bias or self.use_bias else 0))
            o = self.num_heads * self.head_dim * d + (d if self.use_bias else 0)
            out.append(("attn", n_attn * (q + kv + o)))
        if n_ssd:
            di, ns, ng, nh = self.d_inner, self.ssm_state, self.ssm_ngroups, self.ssm_nheads
            in_proj = d * (2 * di + 2 * ng * ns + nh)
            conv = self.ssm_conv * (di + 2 * ng * ns)
            extra = nh * 3  # A_log, D, dt_bias
            norm = di
            out_proj = di * d
            out.append(("ssd", n_ssd * (in_proj + conv + extra + norm + out_proj)))
        if n_rglru:
            w = self.lru_width or d
            proj = 2 * d * w + w * d          # x-branch, gate-branch, out
            conv = 4 * w                       # temporal conv width 4
            lru = 3 * w                        # Lambda, input gate, rec gate (diag approx)
            gates = 2 * w * w                  # RG-LRU input/recurrent gate mats (block-diag full here)
            out.append(("rglru", n_rglru * (proj + conv + lru + gates)))

        # MLP / MoE per decoder layer
        n_mlp_layers = self.num_layers if self.family != "ssm" else 0
        if self.first_dense_layers:
            out.append(("lead_mlp",
                        self.first_dense_layers * 3 * d * self.dense_d_ff))
            n_mlp_layers -= self.first_dense_layers
        if self.num_experts:
            per_expert = 3 * d * self.d_ff  # gated
            out.append(("moe_experts", n_mlp_layers * self.held * per_expert))
            out.append(("router", n_mlp_layers * d * self.num_experts))
            if self.shared_experts:
                out.append(("moe_shared", n_mlp_layers * self.shared_experts
                            * per_expert))
        elif n_mlp_layers:
            if self.mlp_act in ("swiglu", "geglu"):
                per = 3 * d * self.d_ff + (2 * self.d_ff + d if self.use_bias else 0)
            else:
                per = 2 * d * self.d_ff + (self.d_ff + d if self.use_bias else 0)
            out.append(("mlp", n_mlp_layers * per))

        # norms
        if self.norm_type != "layernorm_nonparam":
            scale = 2 if self.norm_type == "layernorm" else 1
            out.append(("norms", scale * (2 * self.num_layers + 1) * d))

        # encoder stack (whisper): same attn+mlp shape, full attention
        if self.is_encdec:
            enc_attn = self.encoder_layers * (4 * d * self.num_heads * self.head_dim)
            enc_mlp = self.encoder_layers * 2 * d * self.d_ff
            cross = self.num_layers * 4 * d * self.num_heads * self.head_dim
            out.append(("encoder", enc_attn + enc_mlp + cross))
        return out


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str          # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k":  ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k":   ShapeConfig("long_500k", "decode", 524_288, 1),
}


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """(applicable, reason-if-not). long_500k needs sub-quadratic attention."""
    if shape.name == "long_500k" and not model.subquadratic:
        return False, "full-attention arch: 500k KV is quadratic/unbounded; skipped per spec"
    return True, ""


# ---------------------------------------------------------------------------
# LMS / DDL / mesh / train configs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LMSConfig:
    enabled: bool = True
    hbm_budget: int = 0               # 0 => hardware HBM size
    offload_params: str = "auto"      # "auto" | "always" | "never"
    offload_optimizer: str = "auto"
    offload_activations: str = "auto"
    remat: bool = True                # allow remat as alternative to swap
    # planner safety margin for XLA workspace / fragmentation
    workspace_frac: float = 0.10


@dataclass(frozen=True)
class DDLConfig:
    mode: str = "allreduce"           # "allreduce" (paper) | "zero1" (beyond) | "none"
    compress_dcn: bool = False        # int8 + error feedback on pod hop
    # gradient bucketing for overlap. None = auto: the executor's default
    # 64 MiB (core/ddl/overlap.DEFAULT_BUCKET_MB).
    bucket_mb: Optional[int] = None
    topology_aware: bool = True       # False => flat NCCL-style single all-reduce
    # per-layer reduction inside the backward scan (core/ddl/overlap.py)
    # vs a post-hoc tree pass. None = auto: follow the LMS planner's priced
    # recommendation when a plan is present, else overlap. Explicit
    # True/False overrides the planner.
    overlap_grads: Optional[bool] = None


@dataclass(frozen=True)
class MeshSpec:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshSpec((16, 16), ("data", "model"))
MULTI_POD = MeshSpec((2, 16, 16), ("pod", "data", "model"))


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshSpec = SINGLE_POD
    lms: LMSConfig = field(default_factory=LMSConfig)
    ddl: DDLConfig = field(default_factory=DDLConfig)
    # optimizer
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    # execution
    microbatches: int = 1             # grad accumulation
    remat_policy: str = "auto"        # "auto" (planner) | "none" | "full" | "offload"
    seed: int = 0
    # checkpointing
    checkpoint_dir: str = "/tmp/repro_ckpt"
    checkpoint_every: int = 100       # 0: no checkpoints, no resume
    async_checkpoint: bool = True
    # observability: metrics flush cadence — device metrics cross to host
    # (the per-step float() sync) only every log_every steps
    log_every: int = 1


def smoke_shape(kind: str = "train") -> ShapeConfig:
    return ShapeConfig(f"smoke_{kind}", kind, 32, 4)


def override(cfg, **kw):
    return replace(cfg, **kw)
