"""Architecture config schema + registry.

One ``ArchConfig`` covers every family in the assigned pool:
dense / MoE / SSM (xLSTM) / hybrid (Mamba2+attn) / enc-dec (whisper) /
VLM (cross-attention).  Family-specific knobs default to "off".
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

_REGISTRY: dict[str, "ArchConfig"] = {}


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    d_expert: int = 0            # per-expert FFN hidden (0 -> use d_ff)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # renormalize the top-k routing weights to sum to 1 (DeepSeekMoE's
    # published ``norm_topk_prob: false`` keeps the softmax's own)
    norm_topk_prob: bool = True
    # "tp": experts sharded on the FFN hidden dim (no all-to-all);
    # "ep": experts sharded on the expert dim (GSPMD inserts all-to-all).
    parallelism: str = "tp"


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 64          # Mamba2 d_state / mLSTM key dim scale
    conv_kernel: int = 4         # Mamba2 local conv width
    expand: int = 2              # Mamba2 inner expansion
    chunk: int = 256             # SSD / chunked-scan chunk length
    slstm_every: int = 0         # xLSTM: 1 sLSTM block per this many layers
    attn_every: int = 0          # zamba: shared attention every N layers


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    # attention variants
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    attn_softcap: float = 0.0    # gemma2: 50.0 on attn logits
    final_softcap: float = 0.0   # gemma2: 30.0 on output logits
    sliding_window: int = 0      # local attention window (0 = full)
    local_global_alternate: bool = False  # gemma2: even layers local
    sandwich_norm: bool = False  # gemma2: post-norms after attn/mlp
    scale_embed: bool = False    # gemma2: embeddings scaled by sqrt(d)
    gated_mlp: bool = True       # False -> plain GELU MLP (starcoder2, whisper)
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # family extras
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    cross_attn_every: int = 0    # vlm: cross-attn layer every N self layers
    num_image_tokens: int = 0    # vlm stub frontend output length
    encoder_layers: int = 0      # audio enc-dec
    encoder_frames: int = 0      # audio stub frontend output length
    # serving
    max_draft_len: int = 16

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def attention_free(self) -> bool:
        """True when no layer does full softmax attention over the prefix."""
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (recurrent state decode)."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_decoder(self) -> bool:
        return True  # every pool member has an autoregressive decoder

    def param_count(self) -> int:
        """Approximate parameter count (used for roofline MODEL_FLOPS)."""
        from repro.roofline.model_flops import param_count

        return param_count(self)

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                top_k=min(self.moe.top_k, 2),
                num_shared_experts=min(self.moe.num_shared_experts, 1),
                d_expert=64 if self.moe.d_expert else 0,
            )
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(
                self.ssm,
                state_dim=16,
                chunk=16,
                slstm_every=min(self.ssm.slstm_every, 2),
                attn_every=min(self.ssm.attn_every, 2),
            )
        n_layers = 4 if (self.ssm or self.cross_attn_every) else 2
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=n_layers,
            d_model=64,
            n_heads=4,
            n_kv_heads=2 if self.n_kv_heads < self.n_heads else 4,
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab=512,
            moe=moe,
            ssm=ssm,
            cross_attn_every=2 if self.cross_attn_every else 0,
            num_image_tokens=8 if self.num_image_tokens else 0,
            encoder_layers=2 if self.encoder_layers else 0,
            encoder_frames=16 if self.encoder_frames else 0,
            sliding_window=32 if self.sliding_window else 0,
        )


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        import repro.configs  # noqa: F401  (populate registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    import repro.configs  # noqa: F401

    return sorted(_REGISTRY)
