"""Architecture config dataclass + input-shape registry.

Every assigned architecture gets one ``src/repro/configs/<id>.py`` exporting
``CONFIG`` (full-scale, exercised via the dry-run only) and ``SMOKE_CONFIG``
(reduced: ≤2 layers, d_model ≤ 512, ≤4 experts — runs on CPU).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional

import jax.numpy as jnp


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str       # dense | moe | mla_moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    source: str = ""                  # citation (paper / model card)

    # attention details
    causal: bool = True               # False for BERT/ViT-style encoders
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    window: Optional[int] = None      # native sliding window (Mixtral)
    long_context_window: int = 8192   # SWA fallback used only for long_500k
    attn_impl: str = "xla"            # xla | pallas | pallas_interpret

    # MoE
    n_experts: int = 0                # routed experts (the router's width)
    top_k: int = 0
    capacity_factor: float = 1.25

    # fine-grained MoE with latent attention (mla_moe: DeepSeek-V3 class)
    kv_lora_rank: int = 0             # latent width of keys and values
    qk_nope_head_dim: int = 0         # per-head q/k part without rope
    qk_rope_head_dim: int = 0         # q/k rope part; k's is shared by heads
    v_head_dim: int = 0
    moe_d_ff: int = 0                 # routed expert width (d_ff: dense MLP)
    n_shared_experts: int = 0         # one SwiGLU of n_shared * moe_d_ff
    first_k_dense: int = 0            # leading dense layers
    routed_scaling_factor: float = 1.0
    n_experts_held: int = 0           # routed experts computed here (0: all)
    expert_offset: int = 0            # first held expert
    norm_eps: float = 1e-6

    # SSM / recurrent
    ssm_state: int = 0                # Mamba2 state dim N
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_kernel: int = 4
    slstm_ratio: int = 0              # xLSTM: 1 sLSTM per this many blocks (0=off)

    # hybrid (zamba2-style)
    attn_every: int = 0               # shared attention block every k core layers

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_len: int = 1500           # 30 s of audio at 50 Hz after conv stub

    # modality frontends (stubs per spec)
    takes_embeddings: bool = False    # VLM: input_specs feeds patch+text embeds

    # norms / mlp family / misc
    norm: str = "rms"                 # rms | layer
    mlp: str = "swiglu"               # swiglu | gelu
    mlp_bias: bool = False
    tie_embeddings: bool = True
    max_seq_len: int = 524_288

    # precision
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    kv_cache_dtype: str = "bfloat16"   # bfloat16 | float8_e4m3fn (serving)

    # training
    remat: bool = True                # activation checkpoint each layer

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # ---- derived quantities used by the partitioner / roofline ----------
    @property
    def attn_params(self) -> int:
        d, nh, nkv, hd = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim
        return d * nh * hd + 2 * d * nkv * hd + nh * hd * d

    @property
    def mlp_params(self) -> int:
        mult = 3 if self.mlp == "swiglu" else 2
        return mult * self.d_model * self.d_ff

    @property
    def mla_params(self) -> int:
        d, nh, r = self.d_model, self.n_heads, self.kv_lora_rank
        nope, rope, v = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim)
        return (d * nh * (nope + rope) + d * (r + rope) + r
                + r * nh * (nope + v) + nh * v * d)

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    def mla_layer_params(self, dense: bool, active: bool = False) -> int:
        """One mla_moe block, norms included; ``active`` counts the routed
        experts a token uses on average here: top_k x held / n_experts."""
        d, f = self.d_model, self.moe_d_ff
        base = self.mla_params + 2 * d
        if dense:
            return base + 3 * d * self.d_ff
        routed = 3 * d * f * (self.top_k * self.experts_held / self.n_experts
                              if active else self.experts_held)
        return int(base + routed + 3 * d * f * self.n_shared_experts
                   + (d + 1) * self.n_experts)

    @property
    def layer_params(self) -> int:
        if self.family == "mla_moe":
            return self.mla_layer_params(dense=False)
        if self.family == "moe":
            return self.attn_params + self.n_experts * self.mlp_params + \
                self.d_model * self.n_experts  # router
        if self.family == "ssm":
            d_in = self.d_model * self.ssm_expand
            return 2 * self.d_model * d_in + d_in * (2 * self.ssm_state + 2)
        return self.attn_params + self.mlp_params

    def _mla_moe_params(self, active: bool) -> int:
        k = self.first_k_dense
        return (k * self.mla_layer_params(dense=True)
                + (self.n_layers - k) * self.mla_layer_params(False, active)
                + 2 * self.vocab_size * self.d_model + self.d_model)

    @property
    def n_params(self) -> int:
        if self.family == "mla_moe":
            return self._mla_moe_params(active=False)
        emb = self.vocab_size * self.d_model
        body = self.n_layers * self.layer_params
        if self.is_encoder_decoder:
            body += self.n_encoder_layers * self.layer_params
        return emb * (1 if self.tie_embeddings else 2) + body

    @property
    def n_active_params(self) -> int:
        """Per-token active params (MoE counts top_k experts only)."""
        if self.family == "mla_moe":
            return self._mla_moe_params(active=True)
        if self.family != "moe":
            return self.n_params
        dense_layer = self.attn_params + self.top_k * self.mlp_params
        return self.vocab_size * self.d_model + self.n_layers * dense_layer


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# registry filled in by repro.configs.__init__
ARCH_REGISTRY: dict[str, "ArchConfig"] = {}
SMOKE_REGISTRY: dict[str, "ArchConfig"] = {}


def register(cfg: ArchConfig, smoke: ArchConfig) -> None:
    ARCH_REGISTRY[cfg.name] = cfg
    SMOKE_REGISTRY[cfg.name] = smoke


def get_config(name: str, smoke: bool = False) -> ArchConfig:
    import repro.configs  # noqa: F401  (triggers registration)
    reg = SMOKE_REGISTRY if smoke else ARCH_REGISTRY
    if name not in reg:
        raise KeyError(f"unknown arch {name!r}; have {sorted(reg)}")
    return reg[name]
