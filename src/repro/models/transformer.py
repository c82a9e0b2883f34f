"""Decoder block + scan-over-layers stack for dense / moe / vlm families.

The stack is a single ``jax.lax.scan`` over stacked per-layer params (HLO size
independent of depth — required for 80-layer dry-runs), with optional
``jax.checkpoint`` per layer for training.

Neuron-chunking integration (first-class, paper §3): every block accepts an
optional ``sparse_ctx`` (serving/sparse_exec.SparseExecution). When present,
the block computes input importances for the q/o/gate/down projections
(k/v/up share masks per paper App. A), runs utility-guided chunk selection
*inside the jit*, applies the masks, and accumulates the additive-model I/O
latency estimate. Dense training never pays for this path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.quantize import (
    DECODE_COPY_SUFFIX,
    QUANT_SUFFIX_CHECKSUM,
    QUANT_SUFFIX_PAYLOAD,
    QUANT_SUFFIX_SCALE,
)
from ..sharding import shard_act
from .attention import (
    attention_param_defs,
    cache_layer_update,
    decode_attention,
    gather_paged_kv,
    latent_attention_absorbed,
    latent_attention_expanded,
    latent_attention_param_defs,
    latent_cache_write,
    latent_entry,
    latent_query,
    multi_head_attention,
    project_kv_for_decode,
    scatter_paged_kv,
)
from .common import ParamDef, layer_norm, rms_norm
from .mlp import (
    gelu_mlp,
    gelu_mlp_param_defs,
    gelu_mlp_planned,
    mlp_param_defs,
    swiglu_mlp,
    swiglu_mlp_planned,
)
from .moe import (
    MoEConfig,
    held_expert_ffn,
    held_expert_param_defs,
    moe_ffn,
    moe_param_defs,
    router_gates,
    shared_experts,
)


def _norm_defs(cfg: ModelConfig, name: str) -> Dict[str, ParamDef]:
    defs = {f"{name}_w": ParamDef((cfg.d_model,), ("embed",), init="ones")}
    if cfg.norm == "layernorm":
        defs[f"{name}_b"] = ParamDef((cfg.d_model,), ("embed",), init="zeros")
    return defs


def apply_norm(x, params, cfg: ModelConfig, name: str):
    if cfg.norm == "layernorm":
        return layer_norm(x, params[f"{name}_w"], params[f"{name}_b"])
    return rms_norm(x, params[f"{name}_w"])


def moe_cfg_of(cfg: ModelConfig) -> MoEConfig:
    return MoEConfig(
        n_experts=cfg.n_experts,
        top_k=cfg.moe_top_k,
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        capacity_factor=cfg.moe_capacity_factor,
        shared_expert=cfg.moe_shared_expert,
        dispatch=cfg.moe_dispatch,
    )


def block_param_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    """One decoder block's params (unstacked)."""
    defs: Dict[str, ParamDef] = {}
    defs.update(_norm_defs(cfg, "ln1"))
    defs.update(_norm_defs(cfg, "ln2"))
    defs.update(
        attention_param_defs(
            cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        )
    )
    if cfg.has_moe:
        defs.update(moe_param_defs(moe_cfg_of(cfg)))
    elif cfg.mlp == "gelu":
        defs.update(gelu_mlp_param_defs(cfg.d_model, cfg.d_ff))
    else:
        defs.update(mlp_param_defs(cfg.d_model, cfg.d_ff))
    return defs


def _apply_mask(x, mask):
    return x if mask is None else x * mask.astype(x.dtype)


# the offloaded per-layer matrices governed by sparsification — the set the
# engine quantizes (kernels/quantize.py) when serving at wbits=8; names
# absent from an arch family (gelu vs swiglu MLPs) are skipped
SPARSE_WEIGHT_NAMES = (
    "wq", "wk", "wv", "wo",
    "w_gate", "w_up", "w_down",  # swiglu family
    "w_fc", "w_proj",  # non-gated gelu family
)


def site_matrix_names(cfg: ModelConfig) -> Dict[str, Tuple[str, ...]]:
    """Which stored matrices stream through each sparsification site, in
    site matrix order — the integrity subsystem's twin of
    ``core.offload.decode_site_shapes`` (must agree with
    ``SparseExecution.site_matrix_count``)."""
    names: Dict[str, Tuple[str, ...]] = {
        "hidden_attn": ("wq", "wk", "wv"),
        "attn_out": ("wo",),
    }
    if cfg.d_ff and not cfg.has_moe:
        if cfg.mlp == "gelu":
            names["hidden_mlp"] = ("w_fc",)
            names["ffn"] = ("w_proj",)
        else:
            names["hidden_mlp"] = ("w_gate", "w_up")
            names["ffn"] = ("w_down",)
    return names


def _integrity_weights(params, sparse_ctx, cfg: ModelConfig, plan):
    """The per-site ((payload, checksums), ...) matrices ``refresh_layer``
    verifies fetched blocks against (corruption injection only): the same
    stored payload leaf the execution backend streams, paired with its
    pack-time ``_ck`` lane. None when integrity is off — the refresh is
    then bit-identical to a build without the subsystem."""
    if not getattr(sparse_ctx, "integrity_enabled", False):
        return None
    names = site_matrix_names(cfg)
    return {
        kind: tuple(
            (_site_weight(params, sparse_ctx, nm)[0],
             params[nm + QUANT_SUFFIX_CHECKSUM])
            for nm in names[kind]
        )
        for kind in plan
    }


def _site_weight(params, sparse_ctx, name):
    """One offloaded matrix in the form the planned decode path streams it:
    the (int8 payload, per-block scales) pair at wbits=8 — the quantized
    leaves the engine stores next to the fp originals — or (fp weight,
    None) at 16 bits. The execution backend dequantizes inside the gather
    (kernel) / before the identical contraction (reference twin)."""
    if (
        sparse_ctx is not None
        and getattr(sparse_ctx, "wbits", 16) == 8
        and name + QUANT_SUFFIX_PAYLOAD in params
    ):
        return params[name + QUANT_SUFFIX_PAYLOAD], params[name + QUANT_SUFFIX_SCALE]
    if sparse_ctx is not None and name + DECODE_COPY_SUFFIX in params:
        # sharded serving at wbits=16: stream the model-axis-sharded decode
        # copy; the replicated fp original stays for prefill/frame append
        return params[name + DECODE_COPY_SUFFIX], None
    return params[name], None


def block_forward(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # (b, s, d)
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray],
    window: Optional[int],
    sparse_ctx: Any = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (x_out, moe_aux, io_latency_s)."""
    io = jnp.float32(0.0)
    h = apply_norm(x, params, cfg, "ln1")
    h = shard_act(h, ("batch", None, "act_embed"))

    mask_q = None
    if sparse_ctx is not None:
        mask_q, lat = sparse_ctx.mask("hidden_attn", h)
        io += lat
    attn_in = _apply_mask(h, mask_q)
    attn_raw = multi_head_attention(
        attn_in,
        params,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.resolved_head_dim,
        positions=positions,
        rope_theta=cfg.rope_theta,
        causal=True,
        window=window,
        project_out=sparse_ctx is None,
    )
    if sparse_ctx is not None:
        mask_o, lat = sparse_ctx.mask("attn_out", attn_raw)
        io += lat
        attn_raw = _apply_mask(attn_raw, mask_o) @ params["wo"]
    x = x + attn_raw

    h = apply_norm(x, params, cfg, "ln2")
    h = shard_act(h, ("batch", None, "act_embed"))
    aux = jnp.float32(0.0)
    if cfg.has_moe:
        y, aux = moe_ffn(h, params, moe_cfg_of(cfg))
    else:
        y, lat, _ = _mlp_maybe_sparse(h, params, cfg, sparse_ctx)
        io += lat
    x = x + y
    x = shard_act(x, ("batch", "act_seq", "act_embed"))
    return x, aux, io


def _planned_mlp(h, params, cfg: ModelConfig, sparse_ctx, plan):
    """Planned-decode sparse MLP: masks were refreshed at the top of the
    block (one batched dispatch), so here we only read them, run the MLP
    through the decode execution backend off the plan's kernel chunk-table
    lanes, and record this step's importances for the NEXT refresh. The
    backend (``reference`` schedule twin vs ``kernel`` DMA gather) only
    changes how the arithmetic is realized — outputs are bitwise identical.

    Returns (y, io_latency (always 0 — the refresh charged it), new_plan).
    """
    backend = sparse_ctx.backend
    mask_g = plan["hidden_mlp"]["mask"]
    mask_f = plan["ffn"]["mask"]
    plan = sparse_ctx.record_importance("hidden_mlp", h, plan)
    qname = "w_fc" if cfg.mlp == "gelu" else "w_gate"
    quantized = (
        getattr(sparse_ctx, "wbits", 16) == 8
        and qname + QUANT_SUFFIX_PAYLOAD in params
    )
    if getattr(sparse_ctx, "integrity_corrupting", False):
        # recovery-OFF corruption: damage the MLP payload leaves the
        # planned functions stream, in a shallow params copy (both
        # backends consume the identical damaged operands)
        names = site_matrix_names(cfg)
        params = dict(params)
        for kind in ("hidden_mlp", "ffn"):
            for mi, nm in enumerate(names[kind]):
                leaf = nm + QUANT_SUFFIX_PAYLOAD if quantized else nm
                params[leaf] = sparse_ctx.apply_corruption(
                    plan, kind, mi, params[leaf]
                )
    if cfg.mlp == "gelu":
        y, mid = gelu_mlp_planned(
            h, params, backend, mask_g, mask_f,
            sparse_ctx.kernel_tables(plan, "hidden_mlp"),
            sparse_ctx.kernel_tables(plan, "ffn"),
            quantized=quantized,
        )
    else:
        starts, sizes = sparse_ctx.mlp_kernel_plan(plan)
        y, mid = swiglu_mlp_planned(
            h, params, backend, mask_g, mask_f, starts, sizes,
            quantized=quantized,
        )
    plan = sparse_ctx.record_importance("ffn", mid, plan)
    return y, jnp.float32(0.0), plan


def _mlp_maybe_sparse(h, params, cfg: ModelConfig, sparse_ctx, plan=None):
    """Gated/plain MLP with the paper's gate(+up-shared) and down masks.

    Returns (y, io_latency, new_plan); plan is passed through untouched on
    the unplanned paths (forward / append / per-token decode). When a
    decode plan carries the MLP sites, the compute routes through
    ``_planned_mlp`` (the execution-backend path) instead of the masked
    dense matmuls below."""
    if sparse_ctx is None:
        y = gelu_mlp(h, params) if cfg.mlp == "gelu" else swiglu_mlp(h, params)
        return y, jnp.float32(0.0), plan
    if plan is not None and "hidden_mlp" in plan and "ffn" in plan:
        return _planned_mlp(h, params, cfg, sparse_ctx, plan)
    mask_g, io1, plan = _site_mask(sparse_ctx, "hidden_mlp", h, plan)
    hm = _apply_mask(h, mask_g)
    if cfg.mlp == "gelu":
        mid = jax.nn.gelu(hm @ params["w_fc"] + params["b_fc"])
        mask_f, io2, plan = _site_mask(sparse_ctx, "ffn", mid, plan)
        y = _apply_mask(mid, mask_f) @ params["w_proj"] + params["b_proj"]
    else:
        from .common import swish

        mid = swish(hm @ params["w_gate"]) * (hm @ params["w_up"])
        mask_f, io2, plan = _site_mask(sparse_ctx, "ffn", mid, plan)
        y = _apply_mask(mid, mask_f) @ params["w_down"]
    return y, io1 + io2, plan


def stack_forward(
    stacked: Dict[str, jnp.ndarray],  # each leaf (L, ...)
    x: jnp.ndarray,
    cfg: ModelConfig,
    positions: Optional[jnp.ndarray],
    window: Optional[int],
    remat: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan the block over L layers. Returns (hidden, total_moe_aux)."""

    def body(carry, layer_params):
        h, aux = carry
        h2, aux2, _ = block_forward(layer_params, h, cfg, positions, window)
        return (h2, aux + aux2), None

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), stacked)
    return x, aux


# ---------------------------------------------------------------------------
# decode (one token, stacked KV cache)
# ---------------------------------------------------------------------------


def _site_mask(sparse_ctx, kind: str, acts, plan):
    """One sparsification site, optionally through a reusable chunk plan.

    Without a plan (``plan is None`` or the site has none) this is exactly
    ``sparse_ctx.mask`` — in-step per-site selection. With a plan, the
    layer's masks were already refreshed in ONE batched dispatch at the top
    of the block (``sparse_ctx.refresh_layer`` in ``block_decode``, which
    also charged the I/O); here we only read the current mask and record
    this step's importance as the input to the NEXT refresh (the
    prefetch-compatible deferred-selection contract, see docs/serving.md).

    Returns (mask, io_latency, new_plan).
    """
    if sparse_ctx is None:
        return None, jnp.float32(0.0), plan
    if plan is None or kind not in plan:
        m, lat = sparse_ctx.mask(kind, acts)
        return m, lat, plan
    new_plan = sparse_ctx.record_importance(kind, acts, plan)
    return new_plan[kind]["mask"], jnp.float32(0.0), new_plan


def block_decode(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # (b, 1, d)
    layer_k: jnp.ndarray,
    layer_v: jnp.ndarray,
    length: jnp.ndarray,  # tokens in cache BEFORE this one; () or (b,)
    cfg: ModelConfig,
    window: Optional[int],
    sparse_ctx: Any = None,
    plan: Optional[Dict[str, jnp.ndarray]] = None,  # per-layer site masks
    refresh: Optional[jnp.ndarray] = None,  # scalar bool: recompute selection
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, Any]:
    """Returns (x_out, new_k, new_v, io_latency, new_plan)."""
    io = jnp.float32(0.0)
    if sparse_ctx is not None and plan:
        # planned path: ONE batched selection dispatch refreshes every
        # site's mask for this layer (or reuses them at zero I/O); with
        # corruption injection on, the refresh also draws/verifies corrupt
        # blocks against the stored payloads' checksum lanes
        plan, sel_lat = sparse_ctx.refresh_layer(
            plan, refresh,
            weights=_integrity_weights(params, sparse_ctx, cfg, plan),
        )
        io += sel_lat
    h = apply_norm(x, params, cfg, "ln1")

    mask_q, lat, plan = _site_mask(sparse_ctx, "hidden_attn", h, plan)
    io += lat
    attn_in = _apply_mask(h, mask_q)
    q_pre = kv_pre = None
    if plan is not None and "hidden_attn" in plan:
        # planned path: the q/k/v projections run through the execution
        # backend off the hidden_attn chunk table — the same reference-twin /
        # chunk_gather_matmul_dma dispatch as every other site (closing the
        # last masked-dense residue of the decode hot path)
        b, s, _ = h.shape
        hs, hz = sparse_ctx.kernel_tables(plan, "hidden_attn")
        hflat = h.reshape(b * s, -1)
        outs = []
        for mi, name in enumerate(("wq", "wk", "wv")):
            w, sc = _site_weight(params, sparse_ctx, name)
            # recovery-OFF corruption: the damaged payload flows into the
            # gather on BOTH backends (no-op unless integrity_corrupting)
            w = sparse_ctx.apply_corruption(plan, "hidden_attn", mi, w)
            y = sparse_ctx.backend.project(
                w, hflat, mask_q, hs, hz, sc,
                params.get(name + QUANT_SUFFIX_CHECKSUM),
            )
            outs.append(y.astype(h.dtype).reshape(b, s, -1))
        q_pre, k_pre, v_pre = outs
        kv_pre = (k_pre, v_pre)
    new_k, new_v = project_kv_for_decode(
        attn_in, params, cfg.n_kv_heads, cfg.resolved_head_dim, length,
        cfg.rope_theta, kv=kv_pre,
    )
    if cfg.kv_replicate > 1:  # shardable-cache replication (§Perf iteration A)
        from .attention import repeat_kv

        new_k = repeat_kv(new_k, cfg.kv_replicate)
        new_v = repeat_kv(new_v, cfg.kv_replicate)
    layer_k, layer_v = cache_layer_update(
        layer_k, layer_v, new_k, new_v, length, window
    )
    attn_raw = decode_attention(
        attn_in,
        params,
        layer_k,
        layer_v,
        length + 1,
        cfg.n_heads,
        cfg.n_cache_kv_heads,
        cfg.resolved_head_dim,
        cfg.rope_theta,
        window,
        project_out=sparse_ctx is None,
        q=q_pre,
    )
    if sparse_ctx is not None:
        mask_o, lat, plan = _site_mask(sparse_ctx, "attn_out", attn_raw, plan)
        io += lat
        if plan is not None and "attn_out" in plan:
            # planned path: the single-site o-projection runs through the
            # execution backend off the plan's chunk table (reference twin
            # or chunk_gather_matmul_dma — bitwise identical)
            b, s, _ = attn_raw.shape
            w_o, sc_o = _site_weight(params, sparse_ctx, "wo")
            w_o = sparse_ctx.apply_corruption(plan, "attn_out", 0, w_o)
            y_o = sparse_ctx.backend.project(
                w_o, attn_raw.reshape(b * s, -1), mask_o,
                *sparse_ctx.kernel_tables(plan, "attn_out"), sc_o,
                params.get("wo" + QUANT_SUFFIX_CHECKSUM),
            )
            attn_raw = y_o.astype(attn_raw.dtype).reshape(b, s, -1)
        else:
            attn_raw = _apply_mask(attn_raw, mask_o) @ params["wo"]
    x = x + attn_raw

    h = apply_norm(x, params, cfg, "ln2")
    if cfg.has_moe:
        y, _ = moe_ffn(h, params, moe_cfg_of(cfg))
    else:
        y, lat, plan = _mlp_maybe_sparse(h, params, cfg, sparse_ctx, plan)
        io += lat
    x = x + y
    return x, layer_k, layer_v, io, plan


def stack_decode(
    stacked: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    cache: Dict[str, jnp.ndarray],  # k/v: (L, b, P, kv, hd), length: () or (b,)
    cfg: ModelConfig,
    window: Optional[int],
    sparse_ctx: Any = None,
    plan: Optional[Dict[str, jnp.ndarray]] = None,  # {site: (L, N)} masks
    refresh: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray, Any]:
    """Scan the decode block over layers. ``plan`` (when not None) carries
    each layer's cached chunk masks as scan inputs and the refreshed masks
    come back as scan outputs — so a fused multi-token decode loop can reuse
    selection across steps. Returns (x, new_cache, io, new_plan) where
    ``io`` is the PER-LAYER I/O-estimate vector (n_layers,) — the input the
    engine's overlapped prefetch timeline (core/pipeline.py) needs; sum it
    for the legacy scalar total."""
    length = cache["length"]
    planned = plan is not None and len(plan) > 0
    paged = "page_table" in cache
    if paged:
        # paged layout: cache k/v are per-layer page POOLS
        # (L, n_pages, page_tokens, kv, hd) and the table (b, max_pages)
        # rides the scan carry as a traced int32 leaf. Each layer gathers a
        # dense view (bit-equal shape to the dense cache), runs the
        # unchanged block, and scatters the one new entry back to its page.
        assert window is None, "paged KV does not compose with sliding windows"
        table = cache["page_table"]

    def body(h, layer):
        if planned:
            layer_params, lk, lv, layer_plan = layer
        else:
            layer_params, lk, lv = layer
            layer_plan = None
        if paged:
            pool_k, pool_v = lk, lv
            lk = gather_paged_kv(pool_k, table)
            lv = gather_paged_kv(pool_v, table)
        h2, lk2, lv2, io2, plan2 = block_decode(
            layer_params, h, lk, lv, length, cfg, window, sparse_ctx,
            plan=layer_plan, refresh=refresh,
        )
        if paged:
            lk2 = scatter_paged_kv(pool_k, lk2, table, length)
            lv2 = scatter_paged_kv(pool_v, lv2, table, length)
        ys = (lk2, lv2, io2, plan2) if planned else (lk2, lv2, io2)
        return h2, ys

    xs = (
        (stacked, cache["k"], cache["v"], plan)
        if planned
        else (stacked, cache["k"], cache["v"])
    )
    x, ys = jax.lax.scan(body, x, xs)
    if planned:
        ks, vs, io, new_plan = ys
    else:
        (ks, vs, io), new_plan = ys, plan
    new_cache = {"k": ks, "v": vs, "length": length + 1}
    if paged:
        new_cache["page_table"] = table
    return x, new_cache, io, new_plan


# ---------------------------------------------------------------------------
# frame append (multi-token cache extension — the paper's VLM stage)
# ---------------------------------------------------------------------------


def block_append(
    params,
    x: jnp.ndarray,  # (b, n, d) new (visual) tokens
    layer_k,
    layer_v,
    length,
    cfg: ModelConfig,
    sparse_ctx: Any = None,
):
    from .attention import append_attention

    io = jnp.float32(0.0)
    h = apply_norm(x, params, cfg, "ln1")
    mask_q = None
    if sparse_ctx is not None:
        mask_q, lat = sparse_ctx.mask("hidden_attn", h)
        io += lat
    attn_in = _apply_mask(h, mask_q)
    attn_raw, layer_k, layer_v = append_attention(
        attn_in,
        params,
        layer_k,
        layer_v,
        length,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.resolved_head_dim,
        cfg.rope_theta,
        kv_replicate=cfg.kv_replicate,
        project_out=sparse_ctx is None,
    )
    if sparse_ctx is not None:
        mask_o, lat = sparse_ctx.mask("attn_out", attn_raw)
        io += lat
        attn_raw = _apply_mask(attn_raw, mask_o) @ params["wo"]
    x = x + attn_raw

    h = apply_norm(x, params, cfg, "ln2")
    if cfg.has_moe:
        y, _ = moe_ffn(h, params, moe_cfg_of(cfg))
    else:
        y, lat, _ = _mlp_maybe_sparse(h, params, cfg, sparse_ctx)
        io += lat
    return x + y, layer_k, layer_v, io


def stack_append(
    stacked,
    x: jnp.ndarray,
    cache: Dict[str, jnp.ndarray],
    cfg: ModelConfig,
    sparse_ctx: Any = None,
):
    """Append n tokens to every layer's cache (paper §2.1 frame appending)."""
    length = cache["length"]
    n = x.shape[1]

    def body(carry, layer):
        h, io = carry
        layer_params, lk, lv = layer
        h2, lk2, lv2, io2 = block_append(
            layer_params, h, lk, lv, length, cfg, sparse_ctx
        )
        return (h2, io + io2), (lk2, lv2)

    (x, io), (ks, vs) = jax.lax.scan(
        body, (x, jnp.float32(0.0)), (stacked, cache["k"], cache["v"])
    )
    return x, {"k": ks, "v": vs, "length": length + n}, io


# ---------------------------------------------------------------------------
# prefill (full sequence, also fills the cache)
# ---------------------------------------------------------------------------


def block_prefill(
    params, x, cfg: ModelConfig, positions, window, phys_len: int
):
    """Like block_forward but also returns this layer's (k, v) cache fill."""
    from .attention import repeat_kv

    b, s, _ = x.shape
    h = apply_norm(x, params, cfg, "ln1")
    k = (h @ params["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.resolved_head_dim)
    v = (h @ params["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.resolved_head_dim)
    if cfg.rope_theta is not None:
        from .common import apply_rope

        k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.kv_replicate > 1:
        k, v = repeat_kv(k, cfg.kv_replicate), repeat_kv(v, cfg.kv_replicate)
    # keep the LAST phys_len positions (rotating-window layout: slot = pos % P)
    if phys_len < s:
        keep_k, keep_v = k[:, -phys_len:], v[:, -phys_len:]
        roll = (s % phys_len)
        # place so that slot (pos % P) matches decode's rotating writes
        keep_k = jnp.roll(keep_k, shift=roll, axis=1)
        keep_v = jnp.roll(keep_v, shift=roll, axis=1)
    else:
        pad = phys_len - s
        keep_k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        keep_v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    x_out, aux, _ = block_forward(params, x, cfg, positions, window)
    return x_out, aux, keep_k, keep_v


def stack_prefill(
    stacked,
    x,
    cfg: ModelConfig,
    positions,
    window: Optional[int],
    phys_len: int,
    remat: bool = False,
):
    def body(carry, layer_params):
        h, aux = carry
        h2, aux2, k, v = block_prefill(layer_params, h, cfg, positions, window, phys_len)
        return (h2, aux + aux2), (k, v)

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), (ks, vs) = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), stacked)
    cache = {"k": ks, "v": vs, "length": jnp.int32(x.shape[1])}
    return x, aux, cache


# ---------------------------------------------------------------------------
# the latent-attention family (kimi-vl-a3b): leading dense layers, then
# routed-expert layers, each with latent attention and one cache entry
# ---------------------------------------------------------------------------

# the stacked expert leaves the planned decode reads in place (never a
# scanned layer slice)
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def latent_block_param_defs(cfg: ModelConfig, moe: bool) -> Dict[str, ParamDef]:
    d = cfg.d_model
    defs = {"ln1_w": ParamDef((d,), ("embed",), init="ones"),
            "ln2_w": ParamDef((d,), ("embed",), init="ones")}
    defs.update(latent_attention_param_defs(
        d, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
        cfg.qk_rope_head_dim, cfg.v_head_dim))
    if moe:
        defs.update(held_expert_param_defs(d, cfg.d_ff, cfg.n_experts,
                                           cfg.router_width, cfg.moe_n_shared))
    else:
        defs.update(mlp_param_defs(d, cfg.dense_d_ff))
    return defs


def dense_mlp_cfg(cfg: ModelConfig) -> ModelConfig:
    """The leading dense layers' MLP as the dense family sees it."""
    return dataclasses.replace(cfg, d_ff=cfg.dense_d_ff, n_experts=0, mlp="swiglu",
                               kv_lora_rank=0)


def _latent_ffn(h, params, cfg: ModelConfig, moe: bool):
    if moe:
        return held_expert_ffn(h, params, cfg.moe_top_k, cfg.moe_routed_scale)
    return swiglu_mlp(h, params)


def _latent_qkv(h, params, cfg: ModelConfig, positions, q_lin=None, a=None):
    """The latent block's query (b, s, H, nope+rope) and cache entries
    (b, s, R+rope); ``q_lin``/``a``: the projections, when the planned
    decode has computed them through the execution backend."""
    q_lin = h @ params["wq"] if q_lin is None else q_lin
    a = h @ params["wkv_a"] if a is None else a
    q = latent_query(q_lin, positions, cfg.n_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.rope_theta)
    entries = latent_entry(a, positions, params["kv_norm_w"], cfg.kv_lora_rank,
                           cfg.rope_theta, cfg.norm_eps)
    return q, entries


def latent_block_forward(params, x, cfg: ModelConfig, positions, moe: bool):
    """A whole sequence through one block (forward, prefill), attention in
    the expanded form. Returns (x_out, the layer's cache entries)."""
    h = rms_norm(x, params["ln1_w"], cfg.norm_eps)
    q, entries = _latent_qkv(h, params, cfg, positions)
    attn = latent_attention_expanded(q, entries, params["wkv_b"], cfg.n_heads,
                                     cfg.qk_nope_head_dim, cfg.v_head_dim)
    x = x + attn @ params["wo"]
    h = rms_norm(x, params["ln2_w"], cfg.norm_eps)
    return x + _latent_ffn(h, params, cfg, moe), entries


def latent_block_append(params, x, layer_cache, length, cfg: ModelConfig, moe: bool):
    """``x`` (b, n, d) appended after ``length`` cached tokens (frame
    append), attending over the latent cache. Returns (x_out, cache)."""
    b, n, _ = x.shape
    pos = jnp.broadcast_to(length + jnp.arange(n)[None, :], (b, n))
    h = rms_norm(x, params["ln1_w"], cfg.norm_eps)
    q, entries = _latent_qkv(h, params, cfg, pos)
    layer_cache = latent_cache_write(layer_cache, entries, length)
    valid = jnp.arange(layer_cache.shape[1])[None, None, :] <= pos[:, :, None]
    attn = latent_attention_absorbed(q, layer_cache, valid, params["wkv_b"],
                                     cfg.qk_nope_head_dim, cfg.v_head_dim)
    x = x + attn @ params["wo"]
    h = rms_norm(x, params["ln2_w"], cfg.norm_eps)
    return x + _latent_ffn(h, params, cfg, moe), layer_cache


def _lane_project(backend, w, x, mask, starts, sizes):
    """``backend.project`` of a matrix whose width is off the 128-lane grid
    (``wkv_a``: 512 + 64): its columns are padded with zeros to the grid,
    inside the copy of the layer's slice the kernel takes anyway, and the
    padding is cut from the output."""
    pad = -w.shape[1] % 128
    if not pad:
        return backend.project(w, x, mask, starts, sizes)
    y = backend.project(jnp.pad(w, ((0, 0), (0, pad))), x, mask, starts, sizes)
    return y[:, : w.shape[1]]


def latent_block_decode(params, x, layer_cache, length, cfg: ModelConfig, moe: bool,
                        sparse_ctx: Any = None, plan=None, refresh=None,
                        experts=None):
    """One decode token per row through one block, attention in the
    absorbed form over the latent cache. With a plan, the hidden_attn
    (``wq``, ``wkv_a``) and attn_out (``wo``) projections, the dense
    layer's MLP and the expert layer's experts run through the execution
    backend off the plan; ``experts`` = (the whole stacked expert leaves,
    this layer's index in them) for the expert gather. Returns (x_out,
    cache, io_latency, plan)."""
    io = jnp.float32(0.0)
    if sparse_ctx is not None and plan:
        plan, sel_lat = sparse_ctx.refresh_layer(plan, refresh)
        io += sel_lat
    b = x.shape[0]
    backend = sparse_ctx.backend if sparse_ctx is not None else None
    h = rms_norm(x, params["ln1_w"], cfg.norm_eps)
    mask_q, lat, plan = _site_mask(sparse_ctx, "hidden_attn", h, plan)
    io += lat
    q_lin = a = None
    if plan is not None and "hidden_attn" in plan:
        hs, hz = sparse_ctx.kernel_tables(plan, "hidden_attn")
        q_lin, a = (_lane_project(backend, params[name], h.reshape(b, -1), mask_q, hs, hz)
                    .astype(h.dtype).reshape(b, 1, -1) for name in ("wq", "wkv_a"))
    pos = jnp.broadcast_to(jnp.reshape(length, (-1, 1)), (b, 1))
    q, entry = _latent_qkv(_apply_mask(h, mask_q), params, cfg, pos, q_lin, a)
    layer_cache = latent_cache_write(layer_cache, entry, length)
    valid = (jnp.arange(layer_cache.shape[1])[None, :]
             < jnp.reshape(length + 1, (-1, 1)))[:, None, :]
    attn = latent_attention_absorbed(q, layer_cache, jnp.broadcast_to(
        valid, (b,) + valid.shape[1:]), params["wkv_b"], cfg.qk_nope_head_dim,
        cfg.v_head_dim)
    mask_o, lat, plan = _site_mask(sparse_ctx, "attn_out", attn, plan)
    io += lat
    if plan is not None and "attn_out" in plan:
        y_o = backend.project(params["wo"], attn.reshape(b, -1), mask_o,
                              *sparse_ctx.kernel_tables(plan, "attn_out"))
        attn = y_o.astype(attn.dtype).reshape(b, 1, -1)
    else:
        attn = _apply_mask(attn, mask_o) @ params["wo"]
    x = x + attn
    h = rms_norm(x, params["ln2_w"], cfg.norm_eps)
    if not moe:
        y, lat, plan = _mlp_maybe_sparse(h, params, dense_mlp_cfg(cfg), sparse_ctx, plan)
        io += lat
    elif plan is not None and "expert_ffn" in plan:
        ht = h.reshape(b, -1)
        with jax.named_scope("decode.route"):
            gates = router_gates(ht, params, cfg.moe_top_k, cfg.moe_routed_scale,
                                 cfg.n_experts)
        with jax.named_scope("decode.experts"):
            stacks, layer = experts
            y, plan, lat = sparse_ctx.run_experts(plan, ht, gates, stacks, layer)
            y = y.astype(h.dtype) + shared_experts(ht, params)
        y = y.reshape(b, 1, -1)
        io += lat
    else:
        y = _latent_ffn(h, params, cfg, moe)
    return x + y, layer_cache, io, plan
