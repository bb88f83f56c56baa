"""Model + shape configuration schema, and the architecture registry."""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    # attention features
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    mlp_variant: str = "swiglu"    # swiglu | gelu (classic 4x MLP)
    tie_embeddings: bool = True    # False -> separate unembedding matrix
    attn_impl: str = "blockwise"   # blockwise | full
    attn_chunk: int = 1024         # kv/q chunk for blockwise attention
    # paged decode attention path (kernels/paged_attention.py):
    # unfused (reference gather + chunk_decode_attention) | fused (one
    # Pallas kernel, same math) | fused_sc (fused, SC-sampled QK^T —
    # needs per-token rng keys, see models/attention.py)
    paged_attn: str = "unfused"
    # latent attention (MLA, DeepSeek-V2/V3): kv_lora_rank > 0 selects it.
    # Queries are n_heads x (qk_nope_head_dim + qk_rope_head_dim); keys and
    # values are decompressed from one kv_lora_rank-wide latent plus one
    # rope part shared by every head, which is all the cache holds.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading dense SwiGLU layers (width d_ff) before the MoE stack
    first_k_dense: int = 0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0              # expert width; 0 -> d_ff
    n_shared_experts: int = 0      # one SwiGLU of width n x expert width
    # softmax | noaux_tc (DeepSeek-V3: sigmoid scores, a selection-only
    # bias); the top-k gates are renormalized, then scaled
    router: str = "softmax"
    routed_scaling_factor: float = 1.0
    # dropless: every (token, expert) pair is computed, sorted by expert
    # through one grouped matmul (exact) or capacity = tokens (SC); else
    # tokens past capacity_factor are dropped
    moe_dropless: bool = False
    capacity_factor: float = 1.25
    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    # hybrid (zamba2): one SHARED attn+MLP block every `attn_every` ssm layers
    attn_every: int = 0
    # SC multiplication substrate (the paper's engine as a framework feature):
    # any backend registered in repro.sc — exact | moment | bitexact |
    # pallas_moment | pallas_bitexact. ``sc_mode`` is the deprecated alias;
    # the two fields are kept in sync (see __post_init__ / replace).
    sc_backend: str = ""
    sc_mode: str = ""              # DEPRECATED: use sc_backend
    sc_nbit: int = 1024
    # dtypes
    param_dtype: Any = jnp.bfloat16
    act_dtype: Any = jnp.bfloat16
    # input frontend: "tokens" (ids) or "embeddings" (stubbed modality frontend)
    frontend: str = "tokens"
    # remat policy inside the layer scan: none | full
    remat: str = "full"

    def __post_init__(self):
        # sc_mode -> sc_backend migration: either spelling may be passed at
        # construction; afterwards both fields hold the resolved backend so
        # legacy readers of cfg.sc_mode keep working. Two different non-empty
        # values is a conflict (e.g. raw dataclasses.replace updating only
        # sc_mode against a mirrored sc_backend) — refuse rather than let
        # one spelling silently win.
        if self.sc_backend and self.sc_mode and self.sc_mode != self.sc_backend:
            raise ValueError(
                f"conflicting sc_backend={self.sc_backend!r} / "
                f"sc_mode={self.sc_mode!r}; set one (or use "
                "ModelConfig.replace, which keeps the alias pair in sync)")
        if not self.sc_backend:
            object.__setattr__(self, "sc_backend", self.sc_mode or "exact")
        if self.sc_mode != self.sc_backend:
            object.__setattr__(self, "sc_mode", self.sc_backend)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // max(self.n_heads, 1)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def d_inner(self) -> int:           # SSM inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def replace(self, **kw) -> "ModelConfig":
        # keep the sc_backend/sc_mode alias pair in sync: whichever spelling
        # the caller passes wins over the mirrored stale value of the other
        if "sc_backend" in kw and "sc_mode" not in kw:
            kw["sc_mode"] = kw["sc_backend"]
        elif "sc_mode" in kw and "sc_backend" not in kw:
            kw["sc_backend"] = kw["sc_mode"]
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = [
    "musicgen-large", "moonlight-16b-a3b", "llama4-maverick-400b-a17b",
    "chameleon-34b", "starcoder2-15b", "qwen2-0.5b", "qwen3-14b", "yi-6b",
    "zamba2-7b", "mamba2-370m", "paper-sc",
]


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))
    return mod.CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))
    return mod.SMOKE


def supported_shapes(cfg: ModelConfig) -> list[str]:
    """Which of the four assigned shapes an architecture runs (§Arch-applicability).

    ``long_500k`` needs sub-quadratic attention: only the SSM/hybrid archs
    run it; pure full-attention archs skip (documented in DESIGN.md).
    """
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.family in ("ssm", "hybrid"):
        out.append("long_500k")
    return out
