"""Unified model builder: ``build_model(config)`` → a Model for any of the
six assigned families (dense / moe / vlm / audio / ssm / hybrid).

Interface (all pure functions over param pytrees, pjit-ready):

    model.init(key)                         -> params
    model.param_axes()                      -> logical-axes pytree (matches params)
    model.forward(params, batch)            -> (hidden, moe_aux)       # full seq
    model.logits(params, hidden)            -> (b, s, vocab)
    model.prefill(params, batch, max_seq)   -> (last_logits, cache)
    model.decode_step(params, token, cache, sparse_ctx=None)
                                            -> (logits, cache, io_latency)

Batch dict: {"tokens": (b, s_tok) int32, "frontend": (b, n, d_frontend)?}.
VLM/early-fusion archs prepend projected frontend embeddings to the token
embeddings; whisper routes "frontend" through its encoder. ``text_offset``
tells the trainer where token-aligned hidden states start.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import shard_act
from .attention import (
    CacheSpec,
    init_kv_cache,
    init_paged_kv_cache,
    multi_head_attention,
)
from .common import ParamDef, init_params, sinusoidal_positions, stack_layer_defs
from .mlp import gelu_mlp
from .ssm import (
    Mamba2Config,
    mamba2_decode_step,
    mamba2_forward,
    mamba2_param_defs,
    mamba2_state_init,
)
from .transformer import (
    EXPERT_LEAVES,
    apply_norm,
    block_decode,
    block_forward,
    block_param_defs,
    latent_block_append,
    latent_block_decode,
    latent_block_forward,
    latent_block_param_defs,
    stack_decode,
    stack_forward,
    stack_prefill,
)
from .xlstm import (
    XLSTMConfig,
    mlstm_forward,
    mlstm_param_defs,
    mlstm_state_init,
    slstm_forward,
    slstm_param_defs,
    slstm_state_init,
)

COMPUTE_DTYPE = jnp.bfloat16

# Sliding windows engage only for ultra-long decode (long_500k); 32k shapes
# exercise the full cache (DESIGN.md §4).
WINDOW_ENGAGE_THRESHOLD = 65_536


def effective_window(cfg: ModelConfig, seq_len: int) -> Optional[int]:
    if cfg.sliding_window and seq_len > WINDOW_ENGAGE_THRESHOLD:
        return cfg.sliding_window
    return None


def _embed_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    defs = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "final_norm_w": ParamDef((cfg.d_model,), ("embed",), init="ones"),
    }
    if cfg.norm == "layernorm":
        defs["final_norm_b"] = ParamDef((cfg.d_model,), ("embed",), init="zeros")
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"), scale=0.02)
    if cfg.d_frontend and not cfg.is_encdec:
        defs["projector"] = ParamDef((cfg.d_frontend, cfg.d_model), (None, "embed"))
    return defs


def _final_norm(x, params, cfg):
    from .common import layer_norm, rms_norm

    if cfg.norm == "layernorm":
        return layer_norm(x, params["final_norm_w"], params["final_norm_b"])
    return rms_norm(x, params["final_norm_w"])


class Model:
    """Family-dispatching functional model wrapper."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.family = cfg.arch_type
        if cfg.is_latent:
            self._impl = _LatentLM(cfg)
        elif self.family in ("dense", "moe", "vlm"):
            self._impl = _DecoderLM(cfg)
        elif self.family == "hybrid":
            self._impl = _Zamba(cfg)
        elif self.family == "ssm":
            self._impl = _XLSTM(cfg)
        elif self.family == "audio":
            self._impl = _Whisper(cfg)
        else:
            raise ValueError(f"unknown arch_type {cfg.arch_type}")

    # delegate
    def init(self, key):
        return self._impl.init(key)

    def param_axes(self):
        return self._impl.param_axes()

    def forward(self, params, batch, remat: Optional[bool] = None):
        return self._impl.forward(params, batch, remat=self.cfg.remat if remat is None else remat)

    def logits(self, params, hidden):
        head = params["embed"].T if self.cfg.tie_embeddings else params["head"]
        out = hidden @ head.astype(hidden.dtype)
        return shard_act(out, ("batch", None, "vocab"))

    @property
    def text_offset(self) -> int:
        return self._impl.text_offset

    def prefill(self, params, batch, max_seq: int):
        return self._impl.prefill(params, batch, max_seq)

    def init_cache(self, batch_size: int, max_seq: int):
        return self._impl.init_cache(batch_size, max_seq)

    @property
    def supports_paged_kv(self) -> bool:
        return hasattr(self._impl, "init_paged_cache")

    def init_paged_cache(self, batch_size: int, max_seq: int, page_tokens: int, n_pages: int):
        """Paged twin of ``init_cache`` (decoder families only): per-layer
        page pools + per-slot page table (see attention.init_paged_kv_cache)."""
        if not self.supports_paged_kv:
            raise NotImplementedError(f"paged KV not supported for {self.family}")
        return self._impl.init_paged_cache(batch_size, max_seq, page_tokens, n_pages)

    def paged_cache_axes(self):
        if not self.supports_paged_kv:
            raise NotImplementedError(f"paged KV not supported for {self.family}")
        return self._impl.paged_cache_axes()

    def decode_step(self, params, token, cache, sparse_ctx=None):
        return self._impl.decode_step(params, token, cache, sparse_ctx)

    def decode_step_planned(
        self, params, token, cache, sparse_ctx=None, plan=None, refresh=None
    ):
        """decode_step threading chunk-plan reuse state through the layer
        stack (dense/moe/vlm). Returns (logits, cache, io, plan) with ``io``
        a PER-LAYER (n_layers,) I/O-estimate vector — the serve engine feeds
        it to the overlapped prefetch timeline. Families without
        sparsification sites run a plain decode_step, spread its scalar io
        uniformly over layers, and pass ``plan`` through unchanged."""
        if hasattr(self._impl, "decode_step_planned"):
            return self._impl.decode_step_planned(
                params, token, cache, sparse_ctx, plan, refresh
            )
        logits, cache, io = self._impl.decode_step(params, token, cache, sparse_ctx)
        n_layers = self.cfg.n_layers
        io_vec = jnp.broadcast_to(io / n_layers, (n_layers,)).astype(jnp.float32)
        return logits, cache, io_vec, plan

    def append_frame(self, params, frame_embeds, cache, sparse_ctx=None):
        """VLM frame-append stage (paper §2.1): project one frame's patch
        embeddings and extend every layer's KV cache. dense/moe/vlm only."""
        if not hasattr(self._impl, "append_embeds"):
            raise NotImplementedError(f"append_frame not supported for {self.family}")
        return self._impl.append_embeds(params, frame_embeds, cache, sparse_ctx)

    def cache_axes(self):
        """Logical-axes pytree matching ``init_cache`` output structure."""
        return self._impl.cache_axes()


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


# ---------------------------------------------------------------------------
# dense / moe / vlm decoder LM
# ---------------------------------------------------------------------------


class _DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.block_defs = block_param_defs(cfg)
        self.has_frontend = bool(cfg.d_frontend)
        self.text_offset = cfg.frontend_tokens if self.has_frontend else 0

    def _defs(self):
        return {
            **_embed_defs(self.cfg),
            "layers": stack_layer_defs(self.block_defs, self.cfg.n_layers),
        }

    def init(self, key):
        defs = self._defs()
        top = {k: v for k, v in defs.items() if k != "layers"}
        k1, k2 = jax.random.split(key)
        params, _ = init_params(top, k1, COMPUTE_DTYPE)
        layers, _ = init_params(defs["layers"], k2, COMPUTE_DTYPE)
        params["layers"] = layers
        return params

    def param_axes(self):
        defs = self._defs()
        axes = {k: v.axes for k, v in defs.items() if k != "layers"}
        axes["layers"] = {k: v.axes for k, v in defs["layers"].items()}
        return axes

    def _embed_input(self, params, batch):
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(COMPUTE_DTYPE)
        if self.has_frontend:
            front = batch["frontend"].astype(COMPUTE_DTYPE)
            vis = front @ params["projector"].astype(COMPUTE_DTYPE)
            x = jnp.concatenate([vis, x], axis=1)  # early fusion: [vision|text]
        return shard_act(x, ("batch", "act_seq", "act_embed"))

    def forward(self, params, batch, remat: bool = True):
        cfg = self.cfg
        x = self._embed_input(params, batch)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        window = effective_window(cfg, s)
        x, aux = stack_forward(params["layers"], x, cfg, positions, window, remat)
        return _final_norm(x, params, cfg), aux

    def init_cache(self, batch_size: int, max_seq: int):
        cfg = self.cfg
        spec = CacheSpec(
            batch=batch_size,
            max_seq=max_seq,
            n_kv_heads=cfg.n_cache_kv_heads,
            head_dim=cfg.resolved_head_dim,
            window=effective_window(cfg, max_seq),
        )
        return init_kv_cache(spec, cfg.n_layers, COMPUTE_DTYPE)

    def cache_axes(self):
        kv = ("layer", "batch", "cache_seq", "cache_kv_heads", "head_dim")
        return {"k": kv, "v": kv, "length": ()}

    def init_paged_cache(self, batch_size: int, max_seq: int, page_tokens: int, n_pages: int):
        cfg = self.cfg
        if max_seq % page_tokens != 0:
            raise ValueError(
                f"max_seq ({max_seq}) must be divisible by page_tokens ({page_tokens})"
            )
        if effective_window(cfg, max_seq):
            raise ValueError("paged KV does not compose with sliding windows")
        return init_paged_kv_cache(
            n_pages,
            page_tokens,
            batch_size,
            max_seq // page_tokens,
            cfg.n_cache_kv_heads,
            cfg.resolved_head_dim,
            cfg.n_layers,
            COMPUTE_DTYPE,
        )

    def paged_cache_axes(self):
        # pools shard over their page axis the way dense caches shard over
        # batch (sharding/serve.py treats kv_page like batch → "data")
        kv = ("layer", "kv_page", "page_tokens", "cache_kv_heads", "head_dim")
        return {"k": kv, "v": kv, "page_table": ("batch", None), "length": ("batch",)}

    def prefill(self, params, batch, max_seq: int):
        cfg = self.cfg
        x = self._embed_input(params, batch)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        window = effective_window(cfg, max_seq)
        phys = min(max_seq, window) if window else max_seq
        x, _aux, cache = stack_prefill(
            params["layers"], x, cfg, positions, window, phys
        )
        x = _final_norm(x, params, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        last = x[:, -1] @ head.astype(x.dtype)
        return last, cache

    def append_embeds(self, params, frame_embeds, cache, sparse_ctx=None):
        """frame_embeds: (b, n, d_frontend) → projector → n-token cache append.
        Returns (hidden_last, cache, io_latency). Linear caches only."""
        from .transformer import stack_append

        cfg = self.cfg
        if "projector" in params:
            x = frame_embeds.astype(COMPUTE_DTYPE) @ params["projector"].astype(COMPUTE_DTYPE)
        else:
            x = frame_embeds.astype(COMPUTE_DTYPE)
        x, cache, io = stack_append(params["layers"], x, cache, cfg, sparse_ctx)
        return _final_norm(x, params, cfg), cache, io

    def decode_step(self, params, token, cache, sparse_ctx=None):
        logits, cache, io, _ = self.decode_step_planned(params, token, cache, sparse_ctx)
        return logits, cache, jnp.sum(io)

    def decode_step_planned(
        self, params, token, cache, sparse_ctx=None, plan=None, refresh=None
    ):
        """decode_step + chunk-plan state: ``plan`` is the per-(layer, site)
        decode-plan carry (see SparseExecution.init_plan), ``refresh`` a
        scalar bool selecting recompute-vs-reuse. Returns (logits, cache,
        io (n_layers,) per-layer estimate vector, plan)."""
        cfg = self.cfg
        x = jnp.take(params["embed"], token, axis=0).astype(COMPUTE_DTYPE)  # (b,1,d)
        if "page_table" in cache:
            # paged layout: cache["k"].shape[2] is page_tokens, not the
            # physical length — the shape-based window sniff below would
            # misfire. Paged KV never composes with sliding windows.
            window = None
        else:
            # window semantics are baked into the cache's physical length
            phys = cache["k"].shape[2]
            window = cfg.sliding_window if (cfg.sliding_window and phys == cfg.sliding_window) else None
        x, cache, io, plan = stack_decode(
            params["layers"], x, cache, cfg, window, sparse_ctx,
            plan=plan, refresh=refresh,
        )
        x = _final_norm(x, params, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = (x[:, 0] @ head.astype(x.dtype)).astype(jnp.float32)
        logits = shard_act(logits, ("batch", "vocab"))
        return logits, cache, io, plan


# ---------------------------------------------------------------------------
# latent attention, leading dense layers, held routed experts (kimi-vl-a3b)
# ---------------------------------------------------------------------------


class _LatentLM:
    """Two stacks, "dense" (the first ``first_k_dense`` layers) then "moe",
    each scanned; the cache holds one latent entry per token per layer,
    ``ckv`` (layers, b, P, kv_lora_rank + qk_rope_head_dim). The planned
    decode plan's sites span their own layers (``core.offload.site_layers``):
    each stack's scan gets its slice of every site it has."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.text_offset = 0
        n_dense = cfg.first_k_dense
        # (stack, first layer, depth, routed experts?)
        self.stacks = tuple(
            (name, first, depth, moe)
            for name, first, depth, moe in (
                ("dense", 0, n_dense, False),
                ("moe", n_dense, cfg.n_layers - n_dense, True))
            if depth)
        self.block_defs = {name: latent_block_param_defs(cfg, moe)
                           for name, _, _, moe in self.stacks}

    def _defs(self):
        defs = dict(_embed_defs(self.cfg))
        for name, _, depth, _ in self.stacks:
            defs[name] = stack_layer_defs(self.block_defs[name], depth)
        return defs

    def init(self, key):
        defs = self._defs()
        keys = jax.random.split(key, len(self.stacks) + 1)
        top = {k: v for k, v in defs.items() if not isinstance(v, dict)}
        params, _ = init_params(top, keys[0], COMPUTE_DTYPE)
        for (name, *_), k in zip(self.stacks, keys[1:]):
            params[name], _ = init_params(defs[name], k, COMPUTE_DTYPE)
        return params

    def param_axes(self):
        return {name: ({k: v.axes for k, v in d.items()} if isinstance(d, dict)
                       else d.axes)
                for name, d in self._defs().items()}

    def _embed(self, params, tokens):
        return jnp.take(params["embed"], tokens, axis=0).astype(COMPUTE_DTYPE)

    def _final(self, params, x):
        from .common import rms_norm

        return rms_norm(x, params["final_norm_w"], self.cfg.norm_eps)

    def _layers(self, params, x, positions, remat: bool):
        """Every block over the whole sequence; returns (x, per-layer cache
        entries (layers, b, s, R + rope))."""
        cfg, entries = self.cfg, []
        for name, _, _, moe in self.stacks:
            def body(h, lp, moe=moe):
                return latent_block_forward(lp, h, cfg, positions, moe)

            x, ent = jax.lax.scan(jax.checkpoint(body) if remat else body, x,
                                  params[name])
            entries.append(ent)
        return x, jnp.concatenate(entries, axis=0)

    def forward(self, params, batch, remat: bool = True):
        x = self._embed(params, batch["tokens"])
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x, _ = self._layers(params, x, positions, remat)
        return self._final(params, x), jnp.float32(0.0)

    def init_cache(self, batch_size: int, max_seq: int):
        cfg = self.cfg
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return {"ckv": jnp.zeros((cfg.n_layers, batch_size, max_seq, width), COMPUTE_DTYPE),
                "length": jnp.zeros((), jnp.int32)}

    def cache_axes(self):
        return {"ckv": ("layer", "batch", "cache_seq", None), "length": ()}

    def prefill(self, params, batch, max_seq: int):
        x = self._embed(params, batch["tokens"])
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x, entries = self._layers(params, x, positions, remat=False)
        ckv = jnp.pad(entries, ((0, 0), (0, 0), (0, max_seq - s), (0, 0)))
        x = self._final(params, x)
        last = x[:, -1] @ params["head"].astype(x.dtype)
        return last, {"ckv": ckv, "length": jnp.int32(s)}

    def append_embeds(self, params, frame_embeds, cache, sparse_ctx=None):
        """``frame_embeds`` (b, n, d) appended to the cache (no projector:
        the frames arrive embedded). Returns (hidden, cache, io)."""
        cfg, length = self.cfg, cache["length"]
        x = frame_embeds.astype(COMPUTE_DTYPE)
        out = []
        for name, first, depth, moe in self.stacks:
            def body(h, xs, moe=moe):
                lp, lc = xs
                return latent_block_append(lp, h, lc, length, cfg, moe)

            x, lc = jax.lax.scan(body, x, (params[name],
                                           cache["ckv"][first:first + depth]))
            out.append(lc)
        cache = {"ckv": jnp.concatenate(out, axis=0),
                 "length": length + frame_embeds.shape[1]}
        return self._final(params, x), cache, jnp.float32(0.0)

    def decode_step(self, params, token, cache, sparse_ctx=None):
        logits, cache, io, _ = self.decode_step_planned(params, token, cache, sparse_ctx)
        return logits, cache, jnp.sum(io)

    def _stack_plan(self, plan, first: int, depth: int):
        """The sites of ``plan`` that span layers first .. first+depth-1,
        each sliced to them."""
        from ..core.offload import site_layers

        out = {}
        for kind, entry in (plan or {}).items():
            span = site_layers(self.cfg, kind)
            if first in span:
                lo = first - span.start
                out[kind] = jax.tree.map(lambda a, lo=lo: a[lo:lo + depth], entry)
        return out

    def decode_step_planned(self, params, token, cache, sparse_ctx=None, plan=None,
                            refresh=None):
        """One token per row through both stacks. Returns (logits, cache,
        io (n_layers,), plan), the plan's sites reassembled over their
        layers."""
        cfg, length = self.cfg, cache["length"]
        x = self._embed(params, token)
        caches, ios, pieces = [], [], {}
        for name, first, depth, moe in self.stacks:
            sub = self._stack_plan(plan, first, depth)
            planned = bool(sub)
            in_place = moe and "expert_ffn" in sub
            layer_params = params[name]
            stacks = None
            if in_place:
                stacks = {k: layer_params[k] for k in EXPERT_LEAVES}
                layer_params = {k: v for k, v in layer_params.items()
                                if k not in EXPERT_LEAVES}

            def body(h, xs, moe=moe, planned=planned, stacks=stacks):
                lp, lc, lplan, i = xs
                h2, lc2, io2, lplan2 = latent_block_decode(
                    lp, h, lc, length, cfg, moe, sparse_ctx,
                    plan=lplan if planned else None, refresh=refresh,
                    experts=None if stacks is None else (stacks, i))
                return h2, (lc2, io2, lplan2 if planned else lplan)

            x, (lc, io, sub2) = jax.lax.scan(
                body, x, (layer_params, cache["ckv"][first:first + depth], sub,
                          jnp.arange(depth, dtype=jnp.int32)))
            caches.append(lc)
            ios.append(io)
            for kind, entry in sub2.items():
                pieces.setdefault(kind, []).append(entry)
        new_plan = plan
        if plan:
            new_plan = {kind: jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)
                        for kind, parts in pieces.items()}
        x = self._final(params, x)
        logits = (x[:, 0] @ params["head"].astype(x.dtype)).astype(jnp.float32)
        cache = {"ckv": jnp.concatenate(caches, axis=0), "length": length + 1}
        return logits, cache, jnp.concatenate(ios), new_plan


# ---------------------------------------------------------------------------
# zamba2 hybrid: scanned mamba2 groups + one shared attention/MLP block
# ---------------------------------------------------------------------------


class _Zamba:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.mcfg = Mamba2Config(
            d_model=cfg.d_model,
            d_state=cfg.ssm_state,
            d_conv=cfg.ssm_conv,
            expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim,
        )
        k = cfg.attn_every
        self.group_size = k
        self.n_groups = cfg.n_layers // k  # groups followed by shared attn
        self.n_tail = cfg.n_layers - self.n_groups * k
        self.text_offset = 0
        # shared transformer block operates on d_model with MHA + SwiGLU
        self.shared_defs = block_param_defs(
            dataclasses.replace(cfg, n_experts=0, arch_type="dense")
        )
        self.mamba_defs = mamba2_param_defs(self.mcfg)
        self.mamba_norm = {"mnorm_w": ParamDef((cfg.d_model,), ("embed",), init="ones")}

    def _defs(self):
        layer_defs = {**self.mamba_defs, **self.mamba_norm}
        grouped = stack_layer_defs(stack_layer_defs(layer_defs, self.group_size), self.n_groups)
        defs = {
            **_embed_defs(self.cfg),
            "mamba_groups": grouped,
            "shared": self.shared_defs,
        }
        if self.n_tail:
            defs["mamba_tail"] = stack_layer_defs(layer_defs, self.n_tail)
        return defs

    def init(self, key):
        defs = self._defs()
        keys = jax.random.split(key, len(defs))
        params = {}
        for (name, d), k in zip(sorted(defs.items()), keys):
            if isinstance(d, dict):
                params[name], _ = init_params(d, k, COMPUTE_DTYPE)
            else:
                params[name] = d.make(k, COMPUTE_DTYPE)
        return params

    def param_axes(self):
        defs = self._defs()
        return {
            name: ({k: v.axes for k, v in d.items()} if isinstance(d, dict) else d.axes)
            for name, d in defs.items()
        }

    def _mamba_layer(self, layer_params, x):
        from .common import rms_norm

        h = rms_norm(x, layer_params["mnorm_w"])
        return x + mamba2_forward(h, layer_params, self.mcfg)

    def _shared_attn(self, params, x, positions, window):
        out, _, _ = block_forward(params["shared"], x, self.cfg, positions, window)
        return out

    def forward(self, params, batch, remat: bool = True):
        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(COMPUTE_DTYPE)
        x = shard_act(x, ("batch", "act_seq", "act_embed"))
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        window = effective_window(cfg, s)

        def group_body(h, group_params):
            def inner(h2, lp):
                return (
                    jax.checkpoint(self._mamba_layer)(lp, h2) if remat else self._mamba_layer(lp, h2)
                ), None

            h, _ = jax.lax.scan(inner, h, group_params)
            h = self._shared_attn(params, h, positions, window)
            return h, None

        x, _ = jax.lax.scan(group_body, x, params["mamba_groups"])
        if self.n_tail:
            def inner(h2, lp):
                return self._mamba_layer(lp, h2), None

            x, _ = jax.lax.scan(inner, x, params["mamba_tail"])
        return _final_norm(x, params, cfg), jnp.float32(0.0)

    def init_cache(self, batch_size: int, max_seq: int):
        cfg, m = self.cfg, self.mcfg
        window = effective_window(cfg, max_seq)
        phys = min(max_seq, window) if window else max_seq

        def stacked_state(n):
            st = mamba2_state_init(m, batch_size, COMPUTE_DTYPE)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), st
            )

        cache = {
            "mamba_groups": stacked_state(self.n_groups * self.group_size),
            "attn_k": jnp.zeros(
                (self.n_groups, batch_size, phys, cfg.n_kv_heads, cfg.resolved_head_dim),
                COMPUTE_DTYPE,
            ),
            "attn_v": jnp.zeros(
                (self.n_groups, batch_size, phys, cfg.n_kv_heads, cfg.resolved_head_dim),
                COMPUTE_DTYPE,
            ),
            "length": jnp.zeros((), jnp.int32),
        }
        if self.n_tail:
            cache["mamba_tail"] = stacked_state(self.n_tail)
        return cache

    def cache_axes(self):
        mstate = {
            "conv": ("layer", "batch", None, "conv_dim"),
            "ssm": ("layer", "batch", "ssm_heads", None, None),
        }
        kv = ("layer", "batch", "cache_seq", "cache_kv_heads", "head_dim")
        axes = {
            "mamba_groups": mstate,
            "attn_k": kv,
            "attn_v": kv,
            "length": (),
        }
        if self.n_tail:
            axes["mamba_tail"] = dict(mstate)
        return axes

    def prefill(self, params, batch, max_seq: int):
        """Chunked-SSD prefill: runs the full sequence through every Mamba2
        layer collecting final states, and fills each shared-attn
        application's KV cache."""
        from .transformer import block_prefill

        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(COMPUTE_DTYPE)
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        window = effective_window(cfg, max_seq)
        phys = min(max_seq, window) if window else max_seq

        def mamba_with_state(lp, h):
            from .common import rms_norm

            hn = rms_norm(h, lp["mnorm_w"])
            out, st = mamba2_forward(hn, lp, self.mcfg, return_state=True)
            return h + out, st

        def group_body(h, gp):
            def inner(h2, lp):
                h3, st = mamba_with_state(lp, h2)
                return h3, st

            h, states = jax.lax.scan(inner, h, gp)
            h2, _aux, k, v = block_prefill(
                params["shared"], h, cfg, positions, window, phys
            )
            return h2, (states, k, v)

        x, (gstates, ks, vs) = jax.lax.scan(group_body, x, params["mamba_groups"])
        cache = {
            "mamba_groups": jax.tree.map(
                lambda a: a.reshape((self.n_groups * self.group_size,) + a.shape[2:]),
                gstates,
            ),
            "attn_k": ks,
            "attn_v": vs,
            "length": jnp.int32(s),
        }
        if self.n_tail:
            def inner(h2, lp):
                h3, st = mamba_with_state(lp, h2)
                return h3, st

            x, tail_states = jax.lax.scan(inner, x, params["mamba_tail"])
            cache["mamba_tail"] = tail_states
        x = _final_norm(x, params, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        return x[:, -1] @ head.astype(x.dtype), cache

    def decode_step(self, params, token, cache, sparse_ctx=None):
        cfg = self.cfg
        x = jnp.take(params["embed"], token, axis=0).astype(COMPUTE_DTYPE)
        length = cache["length"]
        window = cfg.sliding_window if cache["attn_k"].shape[2] == cfg.sliding_window else None
        gs, ng = self.group_size, self.n_groups

        group_states = cache["mamba_groups"]
        # reshape stacked (ng*gs, ...) -> (ng, gs, ...)
        group_states = jax.tree.map(
            lambda s: s.reshape((ng, gs) + s.shape[1:]), group_states
        )

        def group_body(carry, layer):
            h = carry
            gp, gstate, lk, lv = layer

            def inner(h2, sl):
                lp, st = sl
                from .common import rms_norm

                hn = rms_norm(h2, lp["mnorm_w"])
                out, st2 = mamba2_decode_step(hn, st, lp, self.mcfg)
                return h2 + out, st2

            h, gstate2 = jax.lax.scan(inner, h, (gp, gstate))
            h2, lk2, lv2, _, _ = block_decode(
                params["shared"], h, lk, lv, length, cfg, window
            )
            return h2, (gstate2, lk2, lv2)

        x, (gstates, ks, vs) = jax.lax.scan(
            group_body,
            x,
            (params["mamba_groups"], group_states, cache["attn_k"], cache["attn_v"]),
        )
        new_cache = dict(cache)
        new_cache["mamba_groups"] = jax.tree.map(
            lambda s: s.reshape((ng * gs,) + s.shape[2:]), gstates
        )
        new_cache["attn_k"], new_cache["attn_v"] = ks, vs
        if self.n_tail:
            def inner(h2, sl):
                lp, st = sl
                from .common import rms_norm

                hn = rms_norm(h2, lp["mnorm_w"])
                out, st2 = mamba2_decode_step(hn, st, lp, self.mcfg)
                return h2 + out, st2

            x, tail_states = jax.lax.scan(
                inner, x, (params["mamba_tail"], cache["mamba_tail"])
            )
            new_cache["mamba_tail"] = tail_states
        new_cache["length"] = length + 1
        x = _final_norm(x, params, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = (x[:, 0] @ head.astype(x.dtype)).astype(jnp.float32)
        return logits, new_cache, jnp.float32(0.0)


# ---------------------------------------------------------------------------
# xLSTM (python loop over 12 heterogeneous blocks)
# ---------------------------------------------------------------------------


class _XLSTM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.xcfg = XLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_heads)
        self.text_offset = 0

    def _block_kind(self, i: int) -> str:
        return "slstm" if i in self.cfg.slstm_layers else "mlstm"

    def _defs(self):
        defs = {**_embed_defs(self.cfg)}
        for i in range(self.cfg.n_layers):
            kind = self._block_kind(i)
            bdefs = (
                slstm_param_defs(self.xcfg) if kind == "slstm" else mlstm_param_defs(self.xcfg)
            )
            bdefs = {**bdefs, "bnorm_w": ParamDef((self.cfg.d_model,), ("embed",), init="ones")}
            defs[f"block_{i}"] = bdefs
        return defs

    def init(self, key):
        defs = self._defs()
        keys = jax.random.split(key, len(defs))
        params = {}
        for (name, d), k in zip(sorted(defs.items()), keys):
            if isinstance(d, dict):
                params[name], _ = init_params(d, k, COMPUTE_DTYPE)
            else:
                params[name] = d.make(k, COMPUTE_DTYPE)
        return params

    def param_axes(self):
        defs = self._defs()
        return {
            name: ({k: v.axes for k, v in d.items()} if isinstance(d, dict) else d.axes)
            for name, d in defs.items()
        }

    def _run(self, params, x, states=None):
        from .common import rms_norm

        new_states = {}
        for i in range(self.cfg.n_layers):
            bp = params[f"block_{i}"]
            kind = self._block_kind(i)
            h = rms_norm(x, bp["bnorm_w"])
            st = states[f"block_{i}"] if states is not None else None
            if kind == "slstm":
                out, st2 = slstm_forward(h, bp, self.xcfg, state=st)
            else:
                out, st2 = mlstm_forward(h, bp, self.xcfg, state=st)
            x = x + out
            new_states[f"block_{i}"] = st2
        return x, new_states

    def forward(self, params, batch, remat: bool = True):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(COMPUTE_DTYPE)
        x = shard_act(x, ("batch", "act_seq", "act_embed"))
        x, _ = self._run(params, x)
        return _final_norm(x, params, self.cfg), jnp.float32(0.0)

    def init_cache(self, batch_size: int, max_seq: int):
        states = {}
        for i in range(self.cfg.n_layers):
            if self._block_kind(i) == "slstm":
                states[f"block_{i}"] = slstm_state_init(self.xcfg, batch_size)
            else:
                states[f"block_{i}"] = mlstm_state_init(self.xcfg, batch_size)
        states["length"] = jnp.zeros((), jnp.int32)
        return states

    def cache_axes(self):
        axes = {}
        for i in range(self.cfg.n_layers):
            if self._block_kind(i) == "slstm":
                axes[f"block_{i}"] = (
                    ("batch", None),
                    ("batch", None),
                    ("batch", None),
                    ("batch", None),
                )
            else:
                axes[f"block_{i}"] = (
                    ("batch", "heads", None, None),
                    ("batch", "heads", None),
                    ("batch", "heads"),
                )
        axes["length"] = ()
        return axes

    def prefill(self, params, batch, max_seq: int):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(COMPUTE_DTYPE)
        x, states = self._run(params, x, states=self.init_cache(x.shape[0], max_seq))
        states["length"] = jnp.int32(x.shape[1])
        x = _final_norm(x, params, self.cfg)
        head = params["embed"].T if self.cfg.tie_embeddings else params["head"]
        return x[:, -1] @ head.astype(x.dtype), states

    def decode_step(self, params, token, cache, sparse_ctx=None):
        x = jnp.take(params["embed"], token, axis=0).astype(COMPUTE_DTYPE)
        states = {k: v for k, v in cache.items() if k != "length"}
        x, new_states = self._run(params, x, states=states)
        new_states["length"] = cache["length"] + 1
        x = _final_norm(x, params, self.cfg)
        head = params["embed"].T if self.cfg.tie_embeddings else params["head"]
        logits = (x[:, 0] @ head.astype(x.dtype)).astype(jnp.float32)
        return logits, new_states, jnp.float32(0.0)


# ---------------------------------------------------------------------------
# whisper-style encoder-decoder (audio)
# ---------------------------------------------------------------------------


class _Whisper:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.text_offset = 0
        dense_cfg = dataclasses.replace(cfg, arch_type="dense")
        self.dec_defs = {
            **block_param_defs(dense_cfg),
            # cross-attention sublayer (x_wk/x_wv consumed building enc_kv)
            **{
                f"x_{k}": v
                for k, v in block_param_defs(dense_cfg).items()
                if k in ("wq", "wk", "wv", "wo")
            },
            "ln_x_w": ParamDef((cfg.d_model,), ("embed",), init="ones"),
            "ln_x_b": ParamDef((cfg.d_model,), ("embed",), init="zeros"),
        }
        self.enc_defs = block_param_defs(dense_cfg)

    def _defs(self):
        cfg = self.cfg
        return {
            **_embed_defs(cfg),
            "pos_embed_dec": ParamDef((4096, cfg.d_model), (None, "embed"), scale=0.01),
            "frontend_proj": ParamDef((cfg.d_frontend, cfg.d_model), (None, "embed")),
            "enc_final_w": ParamDef((cfg.d_model,), ("embed",), init="ones"),
            "enc_final_b": ParamDef((cfg.d_model,), ("embed",), init="zeros"),
            "encoder": stack_layer_defs(self.enc_defs, cfg.encoder_layers),
            "decoder": stack_layer_defs(self.dec_defs, cfg.n_layers),
        }

    def init(self, key):
        defs = self._defs()
        keys = jax.random.split(key, len(defs))
        params = {}
        for (name, d), k in zip(sorted(defs.items()), keys):
            if isinstance(d, dict):
                params[name], _ = init_params(d, k, COMPUTE_DTYPE)
            else:
                params[name] = d.make(k, COMPUTE_DTYPE)
        return params

    def param_axes(self):
        defs = self._defs()
        return {
            name: ({k: v.axes for k, v in d.items()} if isinstance(d, dict) else d.axes)
            for name, d in defs.items()
        }

    def _dec_pos(self, params, s: int):
        """Decoder absolute positions; indexed modulo the table size — the
        assigned 32k/500k decoder contexts exceed Whisper's trained 448
        positions, so the geometry is exercised with wrapped embeddings
        (documented in DESIGN.md §4)."""
        table = params["pos_embed_dec"]
        idx = jnp.arange(s) % table.shape[0]
        return jnp.take(table, idx, axis=0)[None].astype(COMPUTE_DTYPE)

    def _encode(self, params, frontend):
        cfg = self.cfg
        x = frontend.astype(COMPUTE_DTYPE) @ params["frontend_proj"].astype(COMPUTE_DTYPE)
        pos = sinusoidal_positions(x.shape[1], cfg.d_model).astype(COMPUTE_DTYPE)
        x = x + pos[None]
        x = shard_act(x, ("batch", "act_seq", "act_embed"))

        def body(h, lp):
            h2 = apply_norm(h, lp, cfg, "ln1")
            attn = multi_head_attention(
                h2, lp, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                rope_theta=None, causal=False,
            )
            h = h + attn
            h2 = apply_norm(h, lp, cfg, "ln2")
            h = h + gelu_mlp(h2, lp)
            return h, None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        from .common import layer_norm

        return layer_norm(x, params["enc_final_w"], params["enc_final_b"])

    def _decoder_block(self, lp, x, enc_kv, positions, window, cache=None, length=None):
        """One decoder block: self-attn (+cache), cross-attn, MLP."""
        cfg = self.cfg
        if cache is None:
            h = apply_norm(x, lp, cfg, "ln1")
            attn = multi_head_attention(
                h, lp, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                positions=positions, rope_theta=None, causal=True, window=window,
            )
            x = x + attn
            new_cache = None
        else:
            lk, lv, = cache
            h = apply_norm(x, lp, cfg, "ln1")
            from .attention import cache_layer_update, decode_attention, project_kv_for_decode

            nk, nv = project_kv_for_decode(
                h, lp, cfg.n_kv_heads, cfg.resolved_head_dim, length, None
            )
            lk, lv = cache_layer_update(lk, lv, nk, nv, length, window)
            attn = decode_attention(
                h, lp, lk, lv, length + 1, cfg.n_heads, cfg.n_kv_heads,
                cfg.resolved_head_dim, None, window,
            )
            x = x + attn
            new_cache = (lk, lv)

        from .common import layer_norm

        h = layer_norm(x, lp["ln_x_w"], lp["ln_x_b"])
        ek, ev = enc_kv
        cross = multi_head_attention(
            h, lp, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            rope_theta=None, causal=False, kv_override=(ek, ev), prefix="x_",
        )
        # note: x_wk/x_wv are consumed when building enc_kv, not here
        x = x + cross
        h = apply_norm(x, lp, cfg, "ln2")
        x = x + gelu_mlp(h, lp)
        return x, new_cache

    def _enc_kv(self, lp, enc):
        cfg = self.cfg
        b, sk, _ = enc.shape
        ek = (enc @ lp["x_wk"]).reshape(b, sk, cfg.n_kv_heads, cfg.resolved_head_dim)
        ev = (enc @ lp["x_wv"]).reshape(b, sk, cfg.n_kv_heads, cfg.resolved_head_dim)
        return ek, ev

    def forward(self, params, batch, remat: bool = True):
        cfg = self.cfg
        enc = self._encode(params, batch["frontend"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(COMPUTE_DTYPE)
        x = x + self._dec_pos(params, s)
        x = shard_act(x, ("batch", "act_seq", "act_embed"))
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        window = effective_window(cfg, s)

        def body(h, lp):
            enc_kv = self._enc_kv(lp, enc)
            h2, _ = self._decoder_block(lp, h, enc_kv, positions, window)
            return h2, None

        body_fn = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(body_fn, x, params["decoder"])
        return _final_norm(x, params, cfg), jnp.float32(0.0)

    def init_cache(self, batch_size: int, max_seq: int):
        cfg = self.cfg
        window = effective_window(cfg, max_seq)
        phys = min(max_seq, window) if window else max_seq
        shape = (cfg.n_layers, batch_size, phys, cfg.n_kv_heads, cfg.resolved_head_dim)
        enc_shape = (
            cfg.n_layers,
            batch_size,
            cfg.frontend_tokens,
            cfg.n_kv_heads,
            cfg.resolved_head_dim,
        )
        return {
            "k": jnp.zeros(shape, COMPUTE_DTYPE),
            "v": jnp.zeros(shape, COMPUTE_DTYPE),
            "enc_k": jnp.zeros(enc_shape, COMPUTE_DTYPE),
            "enc_v": jnp.zeros(enc_shape, COMPUTE_DTYPE),
            "length": jnp.zeros((), jnp.int32),
        }

    def cache_axes(self):
        kv = ("layer", "batch", "cache_seq", "cache_kv_heads", "head_dim")
        return {"k": kv, "v": kv, "enc_k": kv, "enc_v": kv, "length": ()}

    def prefill(self, params, batch, max_seq: int):
        """Encode audio + prefill decoder with prompt tokens."""
        cfg = self.cfg
        enc = self._encode(params, batch["frontend"])
        cache = self.init_cache(batch["tokens"].shape[0], max_seq)

        def kv_body(_, lp):
            return None, self._enc_kv(lp, enc)

        _, (enc_k, enc_v) = jax.lax.scan(kv_body, None, params["decoder"])
        cache["enc_k"], cache["enc_v"] = enc_k, enc_v

        tokens = batch["tokens"]
        b, s = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(COMPUTE_DTYPE)
        x = x + self._dec_pos(params, s)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        window = effective_window(cfg, max_seq)
        phys = cache["k"].shape[2]

        def body(carry, layer):
            h = carry
            lp, ek, ev = layer
            # self-attn prefill (reuse decoder block without cache) + fill cache
            hb = apply_norm(h, lp, cfg, "ln1")
            k = (hb @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.resolved_head_dim)
            v = (hb @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.resolved_head_dim)
            if phys < s:
                k, v = k[:, -phys:], v[:, -phys:]
                pad = 0
            else:
                pad = phys - s
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            h2, _ = self._decoder_block(lp, h, (ek, ev), positions, window)
            return h2, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, (params["decoder"], enc_k, enc_v))
        cache["k"], cache["v"] = ks, vs
        cache["length"] = jnp.int32(s)
        x = _final_norm(x, params, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        return x[:, -1] @ head.astype(x.dtype), cache

    def decode_step(self, params, token, cache, sparse_ctx=None):
        cfg = self.cfg
        length = cache["length"]
        x = jnp.take(params["embed"], token, axis=0).astype(COMPUTE_DTYPE)
        pos_emb = jax.lax.dynamic_slice(
            params["pos_embed_dec"], (length % params["pos_embed_dec"].shape[0], 0), (1, cfg.d_model)
        )
        x = x + pos_emb[None].astype(COMPUTE_DTYPE)
        phys = cache["k"].shape[2]
        window = cfg.sliding_window if (cfg.sliding_window and phys == cfg.sliding_window) else None

        def body(carry, layer):
            h, _io = carry
            lp, lk, lv, ek, ev = layer
            h2, (lk2, lv2) = self._decoder_block(
                lp, h, (ek, ev), None, window, cache=(lk, lv), length=length
            )
            return (h2, _io), (lk2, lv2)

        (x, _), (ks, vs) = jax.lax.scan(
            body,
            (x, jnp.float32(0.0)),
            (params["decoder"], cache["k"], cache["v"], cache["enc_k"], cache["enc_v"]),
        )
        new_cache = dict(cache)
        new_cache["k"], new_cache["v"] = ks, vs
        new_cache["length"] = length + 1
        x = _final_norm(x, params, cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = (x[:, 0] @ head.astype(x.dtype)).astype(jnp.float32)
        return logits, new_cache, jnp.float32(0.0)
