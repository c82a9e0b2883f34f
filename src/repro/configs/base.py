"""ModelConfig: one dataclass describing every supported architecture family.

Families (``arch_type``): dense | moe | ssm | hybrid | vlm | audio.
Each assigned architecture gets a module in this package with the exact
published numbers; ``reduced()`` derives the smoke-test variant (≤2 layers,
d_model ≤ 512, ≤4 experts) required by the assignment.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    rope_theta: Optional[float] = 10000.0  # None → no RoPE (whisper)
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    mlp: str = "swiglu"  # swiglu | gelu
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    moe_top_k: int = 0
    moe_capacity_factor: float = 1.25
    moe_shared_expert: bool = False
    moe_dispatch: str = "scatter"  # scatter (baseline) | gather (§Perf B)

    # SSM / hybrid (mamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    attn_every: int = 0  # hybrid: shared attention block after every k ssm layers

    # xLSTM
    slstm_layers: Tuple[int, ...] = ()  # layer indices using sLSTM (rest mLSTM)

    # VLM / audio frontends (stubs per assignment)
    d_frontend: int = 0  # vision/audio embedding dim fed to the projector
    frontend_tokens: int = 0  # tokens per frame / encoder positions
    encoder_layers: int = 0  # audio: encoder depth (enc-dec)

    # long-context handling
    sliding_window: Optional[int] = None  # used by long_500k decode for attn archs

    # KV-cache head replication (beyond-paper perf knob, EXPERIMENTS.md §Perf):
    # replicate each kv head r× in the DECODE/PREFILL cache so kv_heads·r
    # divides the model-parallel degree — cache updates and attention stay
    # local to each shard instead of all-gathering the cache every layer.
    kv_replicate: int = 1

    # latent attention (MLA): ``kv_lora_rank`` > 0 gives every layer one
    # cache entry per token, the normed latent beside the rotated key shared
    # by all heads (``qk_rope_head_dim`` wide); queries and keys split into
    # a position-free part and a rotated part; values are ``v_head_dim``
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    norm_eps: float = 1e-6  # RMSNorm epsilon of the latent-attention family

    # expert stacks with leading dense layers (DeepSeek-V2/V3 layout, the
    # latent family): the first ``first_k_dense`` layers run a SwiGLU of
    # ``dense_d_ff``; the rest route by noaux_tc (top-k of sigmoid scores
    # plus a correction bias, weights the unbiased scores normalised over
    # the chosen and times ``moe_routed_scale``). ``moe_router_experts`` is
    # the router's width when a shard holds fewer experts (0: every expert
    # is held); ``moe_n_shared`` shared experts of ``d_ff`` each run as one
    # SwiGLU of their summed width.
    first_k_dense: int = 0
    dense_d_ff: int = 0
    moe_router_experts: int = 0
    moe_routed_scale: float = 1.0
    moe_n_shared: int = 0

    # distribution
    fsdp: bool = False  # additionally shard weights over the data axis
    remat: bool = True

    citation: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_cache_kv_heads(self) -> int:
        """KV heads as stored in the decode cache (incl. replication)."""
        return self.n_kv_heads * self.kv_replicate

    def with_kv_replication(self, tp: int) -> "ModelConfig":
        """Smallest replication making cache kv-heads shardable over tp while
        still dividing n_heads (attention grouping must stay integral)."""
        if self.arch_type == "ssm":
            return self
        for r in range(1, tp + 1):
            kv_eff = self.n_kv_heads * r
            if kv_eff % tp == 0 and self.n_heads % kv_eff == 0:
                return dataclasses.replace(self, kv_replicate=r)
        return self  # impossible (e.g. 24 heads vs tp=16) — keep fallback

    def optimized_for(self, tp: int) -> "ModelConfig":
        """All beyond-paper §Perf config changes for a model-parallel degree:
        shardable KV cache (iteration A) + gather-based MoE dispatch
        (iteration B). shard_map attention (iteration C) is a MeshRules
        toggle, not a config field."""
        cfg = self.with_kv_replication(tp)
        if cfg.has_moe:
            cfg = dataclasses.replace(cfg, moe_dispatch="ep_shard_map")
        return cfg

    @property
    def is_latent(self) -> bool:
        """Latent attention with leading dense layers and routed experts."""
        return self.kv_lora_rank > 0

    @property
    def router_width(self) -> int:
        return self.moe_router_experts or self.n_experts

    @property
    def is_encdec(self) -> bool:
        return self.arch_type == "audio"

    @property
    def has_moe(self) -> bool:
        return self.n_experts > 0

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant of the same family (assignment: 2 layers,
        d_model ≤ 512, ≤ 4 experts)."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        while n_heads % n_kv:
            n_kv -= 1
        scale = d_model / self.d_model
        if self.is_latent:
            latent = dict(kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                          v_head_dim=32, first_k_dense=min(self.first_k_dense, 1),
                          dense_d_ff=max(64, int(self.dense_d_ff * scale)),
                          moe_router_experts=min(self.router_width, 8))
        else:
            latent = {}
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=d_model // n_heads,
            d_ff=max(64, int(self.d_ff * scale)) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            moe_top_k=min(self.moe_top_k, 2) if self.moe_top_k else 0,
            moe_capacity_factor=8.0 if self.n_experts else self.moe_capacity_factor,
            ssm_head_dim=min(self.ssm_head_dim, 32) if self.ssm_state else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            attn_every=min(self.attn_every, 1) if self.attn_every else 0,
            slstm_layers=tuple(i for i in self.slstm_layers if i < 2) or ((1,) if self.slstm_layers else ()),
            d_frontend=min(self.d_frontend, 64) if self.d_frontend else 0,
            frontend_tokens=min(self.frontend_tokens, 16) if self.frontend_tokens else 0,
            encoder_layers=min(self.encoder_layers, 2) if self.encoder_layers else 0,
            fsdp=False,
            **latent,
        )


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One assigned workload geometry."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"
