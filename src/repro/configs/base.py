"""Architecture + shape configuration for the model zoo."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | vlm | ssm | hybrid | audio
    n_layers: int
    d_model: int
    n_heads: int                 # 0 for attention-free archs
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 -> d_model // n_heads

    # attention flavor
    qk_norm: bool = False
    qkv_bias: bool = False
    sliding_window: Optional[int] = None      # SWA window (tokens)
    rope_theta: float = 1e4

    # latent attention (MLA); kv_lora_rank 0 = GQA with n_kv_heads
    kv_lora_rank: int = 0                     # width of the cached latent
    qk_nope_head_dim: int = 0                 # per head: q/k part without RoPE
    qk_rope_head_dim: int = 0                 # shared roped key, per head q
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0                        # routed experts the router scores
    experts_per_token: int = 0
    experts_held: int = 0                     # held here (0: all); experts 0..held-1
    n_shared_experts: int = 0                 # one SwiGLU of n_shared * d_ff
    router_scoring: str = "softmax"           # softmax | sigmoid (+ bias to select)
    routed_scale: float = 1.0                 # on the normalised top-k weights
    first_k_dense: int = 0                    # leading dense layers
    dense_d_ff: int = 0                       # their SwiGLU width
    moe_dense_residual: bool = False          # arctic: parallel dense FFN
    capacity_factor: float = 1.25             # group-local dispatch only

    # SSM / hybrid
    attn_free: bool = False                   # rwkv6
    hybrid_ssm: bool = False                  # hymba: parallel attn+SSM heads
    ssm_state: int = 0
    rwkv_head_dim: int = 64

    # modality frontend stub (vlm / audio): inputs are precomputed embeddings
    embedding_stub: bool = False

    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # training knobs (perf-tunable; defaults overridden per arch/shape)
    grad_accum: int = 1
    remat: bool = True
    optimizer: str = "adamw"                  # adamw | adafactor
    param_dtype: str = "bfloat16"
    source: str = ""

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(1, self.n_heads)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    def reduced(self) -> "ArchConfig":
        """A tiny same-family config for CPU smoke tests. Latent attention,
        shared experts and leading dense layers are kept, and an expert
        layer holds an eighth of 16 experts, as a chip of an 8-way
        expert-parallel deployment does."""
        mla = self.is_mla
        n_experts = min(self.n_experts, 16)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=3 if self.first_k_dense else 2,
            d_model=64,
            n_heads=0 if self.attn_free else 4,
            n_kv_heads=0 if self.attn_free else max(1, min(self.n_kv_heads, 2)),
            head_dim=0 if self.attn_free else 16,
            d_ff=128,
            vocab_size=256,
            n_experts=n_experts,
            experts_per_token=min(self.experts_per_token, 4),
            experts_held=n_experts // 8,
            first_k_dense=min(self.first_k_dense, 1),
            dense_d_ff=256 if self.dense_d_ff else 0,
            kv_lora_rank=32 if mla else 0,
            qk_nope_head_dim=16 if mla else 0,
            qk_rope_head_dim=8 if mla else 0,
            v_head_dim=16 if mla else 0,
            # drop-free capacity so prefill/decode agree exactly in tests
            capacity_factor=float(max(1, self.n_experts)),
            sliding_window=16 if self.sliding_window else None,
            ssm_state=min(self.ssm_state, 8) if self.ssm_state else 0,
            rwkv_head_dim=16 if self.attn_free else self.rwkv_head_dim,
            grad_accum=1,
        )

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS = 6·N·D)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        per_layer = 0
        if self.is_mla:
            h, r, rope = self.n_heads, self.kv_lora_rank, self.qk_rope_head_dim
            per_layer += (d * h * (self.qk_nope_head_dim + rope)
                          + d * (r + rope) + r
                          + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                          + h * self.v_head_dim * d)
        elif not self.attn_free:
            q = d * self.n_heads * self.hd
            kv = 2 * d * self.n_kv_heads * self.hd
            o = self.n_heads * self.hd * d
            per_layer += q + kv + o
        if self.attn_free:
            # rwkv6 time-mix: r,k,v,g,o (5 d*d) + decay/shift loras (small)
            per_layer += 5 * d * d + 2 * d * 64
            per_layer += 2 * d * f // 2 + d * f  # channel-mix approx
        elif self.hybrid_ssm:
            di = self.n_heads * self.hd
            per_layer += 2 * d * di + di * (2 * self.ssm_state + 2) + di * d
        per_layer += 2 * d                      # norms
        n_moe = self.n_layers - self.first_k_dense if self.is_moe else 0
        ffn = (self.n_layers - n_moe) * 3 * d * (self.dense_d_ff or f)
        if n_moe:
            experts = self.held * 3 * d * f
            router = d * self.n_experts
            if self.router_scoring == "sigmoid":
                router += self.n_experts          # the selection bias
            ffn += n_moe * (experts + router
                            + self.n_shared_experts * 3 * d * f)
            if self.moe_dense_residual:
                ffn += n_moe * 3 * d * f
        elif self.attn_free:
            ffn = 0                              # counted with the block
        total = self.n_layers * per_layer + ffn + v * d + 2 * d
        if not self.tie_embeddings:
            total += v * d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        inactive = (self.n_layers - self.first_k_dense) \
            * (self.held - self.experts_per_token) * 3 * d * f
        return self.param_count() - inactive


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


TRAIN_4K = ShapeConfig("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524288, 1)

ALL_SHAPES = [TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K]


def shape_applicable(arch: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """long_500k needs sub-quadratic attention / bounded state (DESIGN.md
    §Arch-applicability)."""
    if shape.name == "long_500k":
        sub_quadratic = arch.attn_free or arch.hybrid_ssm or \
            (arch.sliding_window is not None)
        if not sub_quadratic:
            return False, ("pure full-attention arch: 500k-context decode "
                           "requires sub-quadratic attention (skip noted in "
                           "DESIGN.md)")
    return True, ""
