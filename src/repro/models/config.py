"""Model configuration for the assigned architectures.

One :class:`ModelConfig` per architecture; exact dimensions from the public
sources cited in the assignment.  ``reduced()`` produces the CPU-smoke-test
configuration of the same family (same block wiring, tiny dims).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple

__all__ = ["ModelConfig", "ShapeSpec", "SHAPES", "ARCHS", "get_config", "get_shape"]


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | vlm | audio | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None       # default d_model // n_heads
    # -- MoE ------------------------------------------------------------------
    moe_experts: int = 0
    moe_top_k: int = 0
    capacity_factor: float = 1.25
    # -- attention flavor -------------------------------------------------------
    qkv_bias: bool = False
    rope: str = "rope"                   # rope | mrope | none
    rope_theta: float = 10_000.0
    attn_scale: Optional[float] = None   # softmax scale; None: 1/sqrt(hd)
    # -- stream multipliers (Granite): x = embed * m_e; x += m_r * branch;
    #    logits = unembed / logits_scaling.  1.0 leaves the program as is --
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # -- SSM / linear-attention ---------------------------------------------------
    ssm_state: int = 0                   # mamba2 state size (hybrid)
    ssm_head_dim: int = 64
    rwkv_head_dim: int = 64
    expand: int = 2                      # mamba2 inner expansion
    # -- hybrid (zamba2): one shared attention block applied every k layers ------
    shared_attn_every: int = 0
    # -- hybrid by layer list (Granite 4.0-H): the mixer of each layer,
    #    "mamba" or "attention"; every layer has its own SwiGLU MLP ----------
    layer_types: Tuple[str, ...] = ()
    # -- encoder-decoder (whisper) -----------------------------------------------
    encoder_layers: int = 0
    # -- numerics -----------------------------------------------------------------
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # -- lowering knobs (dry-run probes unroll the layer scan so XLA's
    #    trip-count-blind cost analysis sees every layer) ------------------------
    scan_unroll: bool = False
    # -- bookkeeping ----------------------------------------------------------------
    source: str = ""
    notes: str = ""

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(f"{self.name}: {len(self.layer_types)} layer "
                             f"types for {self.n_layers} layers")
        if set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"{self.name}: unknown layer types "
                             f"{sorted(set(self.layer_types))}")

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def sub_quadratic(self) -> bool:
        """Can this architecture hold 500k context state without a quadratic
        full-attention prefill / full-layer KV cache?"""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        emb = v * d * 2  # embed + unembed (untied)
        if self.family == "ssm":
            # rwkv6: r,k,v,g,o projections + decay/lora + ffn(k,r,v)
            att = 5 * d * d + 3 * d * self.rwkv_head_dim
            ffn = 2 * d * f + d * d
            return emb + L * (att + ffn)
        hd, H, KV = self.hd, self.n_heads, self.n_kv_heads
        attn = d * H * hd + 2 * d * KV * hd + H * hd * d
        if self.family == "hybrid":
            d_in = self.expand * d
            nh = d_in // self.ssm_head_dim
            mamba = d * (2 * d_in + 2 * nh * self.ssm_state + nh) + d_in * d
            per_layer = mamba + 2 * (d * f) + f * d  # swiglu sized f
            shared = attn * (L // max(self.shared_attn_every, 1) and 1)
            return emb + L * per_layer + attn  # one shared attention block
        if self.moe_experts:
            ffn = self.moe_experts * 3 * d * f + d * self.moe_experts
        else:
            ffn = 3 * d * f
        total_layers = L + self.encoder_layers
        cross = attn if self.is_encdec else 0
        return emb + total_layers * (attn + ffn + cross)

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: top-k experts only)."""
        if not self.moe_experts:
            return self.param_count()
        d, f, L = self.d_model, self.d_ff, self.n_layers
        dense = self.param_count() - L * self.moe_experts * 3 * d * f
        return dense + L * self.moe_top_k * 3 * d * f

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        return replace(
            self,
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=2 if self.n_kv_heads < self.n_heads else 4,
            head_dim=16,
            d_ff=128,
            vocab=256,
            moe_experts=4 if self.moe_experts else 0,
            moe_top_k=min(self.moe_top_k, 2) if self.moe_experts else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=16,
            rwkv_head_dim=16,
            shared_attn_every=2 if self.shared_attn_every else 0,
            # one layer of each kind, in the order the list first has them
            layer_types=tuple(dict.fromkeys(self.layer_types)),
            encoder_layers=2 if self.encoder_layers else 0,
            dtype="float32",
        )


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


ARCHS: Dict[str, ModelConfig] = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    ARCHS[cfg.name] = cfg
    return cfg


_register(ModelConfig(
    name="llama4-maverick-400b-a17b", family="moe",
    n_layers=48, d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
    d_ff=8192, vocab=202_048, moe_experts=128, moe_top_k=1,
    source="hf:meta-llama/Llama-4-Scout-17B-16E (unverified)",
    notes="MoE 128e top-1; early-fusion frontend out of scope (text backbone)",
))
_register(ModelConfig(
    name="grok-1-314b", family="moe",
    n_layers=64, d_model=6144, n_heads=48, n_kv_heads=8, head_dim=128,
    d_ff=32768, vocab=131_072, moe_experts=8, moe_top_k=2,
    source="hf:xai-org/grok-1 (unverified)",
))
_register(ModelConfig(
    name="rwkv6-3b", family="ssm",
    n_layers=32, d_model=2560, n_heads=40, n_kv_heads=40,  # 2560/64 wkv heads
    d_ff=8960, vocab=65_536, rope="none", rwkv_head_dim=64,
    source="arXiv:2404.05892; hf",
    notes="Finch: attention-free, data-dependent decay",
))
_register(ModelConfig(
    name="qwen2-vl-7b", family="vlm",
    n_layers=28, d_model=3584, n_heads=28, n_kv_heads=4, head_dim=128,
    d_ff=18944, vocab=152_064, qkv_bias=True, rope="mrope",
    source="arXiv:2409.12191; hf",
    notes="M-RoPE backbone; vision frontend stubbed (patch embeddings input)",
))
_register(ModelConfig(
    name="stablelm-12b", family="dense",
    n_layers=40, d_model=5120, n_heads=32, n_kv_heads=8, head_dim=160,
    d_ff=13824, vocab=100_352,
    source="hf:stabilityai/stablelm-2-1_6b family",
))
_register(ModelConfig(
    name="smollm-360m", family="dense",
    n_layers=32, d_model=960, n_heads=15, n_kv_heads=5, head_dim=64,
    d_ff=2560, vocab=49_152,
    source="hf:HuggingFaceTB/SmolLM-135M family",
))
_register(ModelConfig(
    name="qwen2.5-14b", family="dense",
    n_layers=48, d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
    d_ff=13824, vocab=152_064, qkv_bias=True,
    source="hf:Qwen/Qwen2.5 family",
))
_register(ModelConfig(
    name="qwen2-1.5b", family="dense",
    n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2, head_dim=128,
    d_ff=8960, vocab=151_936, qkv_bias=True,
    source="arXiv:2407.10671; hf",
))
_register(ModelConfig(
    name="whisper-large-v3", family="audio",
    n_layers=32, d_model=1280, n_heads=20, n_kv_heads=20, head_dim=64,
    d_ff=5120, vocab=51_866, rope="none", encoder_layers=32,
    source="arXiv:2212.04356 (unverified)",
    notes="enc-dec; conv frontend stubbed (precomputed frame embeddings)",
))
_register(ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab=100_352, rope="none", attn_scale=1 / 64,
    ssm_state=128, ssm_head_dim=64, expand=2,
    layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json",
    notes="GraniteMoeHybrid without experts: 36 Mamba-2 and 4 NoPE GQA "
          "mixers, each layer with its own SwiGLU; tied head",
))
_register(ModelConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=112,
    d_ff=14336, vocab=32_000, ssm_state=64, ssm_head_dim=64,
    shared_attn_every=6,
    source="arXiv:2411.15242 (unverified)",
    notes="Mamba2 backbone + one shared attention block applied every 6 layers",
))


def get_config(name: str) -> ModelConfig:
    try:
        return ARCHS[name]
    except KeyError:
        raise ValueError(f"unknown arch {name!r}; have {sorted(ARCHS)}") from None


def get_shape(name: str) -> ShapeSpec:
    try:
        return SHAPES[name]
    except KeyError:
        raise ValueError(f"unknown shape {name!r}; have {sorted(SHAPES)}") from None
