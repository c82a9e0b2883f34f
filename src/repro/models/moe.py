"""Mixture-of-Experts layer: top-k router + sort-based capacity dispatch.

Design (DESIGN.md §5): static shapes throughout so the layer pjit-shards —
expert dim over 'model' (expert parallelism), token buffers over the data
axes. GShard-style one-hot dispatch einsums would need a (tokens, E, C)
tensor (≈10^12 elements at train_4k scale); the sort-based dispatch below
replaces it with an argsort + two gathers, which GSPMD lowers to
all-to-all/all-gather collectives over the same axes.

Serves three MoE architectures:
  * olmoe-1b-7b:         64 experts, top-8, SwiGLU experts
  * llama4-scout-17b-a16e: 16 experts, top-1 + always-on shared expert
  * kimi-vl-a3b:         64 experts behind a noaux_tc router, top-6, plus
    2 shared experts, held as one expert-parallel shard of 8
    (``held_expert_ffn`` below: no capacity, so no token is dropped)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..sharding import shard_act
from .common import ParamDef, swish


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int  # per-expert hidden
    capacity_factor: float = 1.25
    shared_expert: bool = False  # llama4-style always-on shared expert
    router_aux_weight: float = 0.01
    # "scatter": paper-faithful-baseline dispatch (big scatter into the
    #   expert buffer — GSPMD reshards it expensively; §Perf iteration B).
    # "gather": beyond-paper optimized dispatch — pure gathers with padded
    #   drop rows; the buffer is born with its target sharding.
    dispatch: str = "scatter"

    def capacity(self, n_tokens: int) -> int:
        """Per-expert token capacity, padded to a multiple of 128 when large
        (keeps the capacity dim shardable over up to 32 data-parallel ways)."""
        import math

        c = math.ceil(n_tokens * self.top_k * self.capacity_factor / self.n_experts)
        if c >= 256:
            c = -(-c // 128) * 128
        return max(c, self.top_k)


def moe_param_defs(cfg: MoEConfig, prefix: str = "") -> Dict[str, ParamDef]:
    p = prefix
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    defs = {
        f"{p}router": ParamDef((d, e), ("embed", None), scale=0.02),
        f"{p}we_gate": ParamDef((e, d, f), ("expert", "embed", "ffn")),
        f"{p}we_up": ParamDef((e, d, f), ("expert", "embed", "ffn")),
        f"{p}we_down": ParamDef((e, f, d), ("expert", "ffn", "embed")),
    }
    if cfg.shared_expert:
        defs.update(
            {
                f"{p}ws_gate": ParamDef((d, f), ("embed", "ffn")),
                f"{p}ws_up": ParamDef((d, f), ("embed", "ffn")),
                f"{p}ws_down": ParamDef((f, d), ("ffn", "embed")),
            }
        )
    return defs


def moe_ffn(
    x: jnp.ndarray,  # (b, s, d)
    params: Dict[str, jnp.ndarray],
    cfg: MoEConfig,
    prefix: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (b,s,d), router aux loss scalar)."""
    if cfg.dispatch == "ep_shard_map":
        from ..sharding import current_rules

        if current_rules() is not None:
            return moe_ffn_ep(x, params, cfg, prefix)
        # no mesh (CPU unit tests): EP degenerates to the gather path
        cfg = dataclasses.replace(cfg, dispatch="gather")
    p = prefix
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt @ params[f"{p}router"]).astype(jnp.float32)  # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)  # (t, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)  # mean router prob per expert
    assign = jnp.zeros((t, cfg.n_experts), jnp.float32).at[
        jnp.arange(t)[:, None], top_e
    ].add(1.0)
    ce = assign.mean(axis=0) / cfg.top_k  # fraction of tokens per expert
    aux = cfg.n_experts * jnp.sum(me * ce) * cfg.router_aux_weight

    cap = cfg.capacity(t)
    flat_e = top_e.reshape(t * cfg.top_k)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), cfg.top_k)
    flat_w = top_w.reshape(t * cfg.top_k)

    order = jnp.argsort(flat_e, stable=True)  # group assignments by expert
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, jnp.arange(cfg.n_experts), side="left")
    rank = jnp.arange(t * cfg.top_k, dtype=jnp.int32) - first[se].astype(jnp.int32)
    keep = rank < cap
    buf_pos = jnp.where(keep, se * cap + rank, cfg.n_experts * cap)  # drop→OOB

    if cfg.dispatch == "gather":
        # token id occupying each expert slot (t = empty → zero pad row)
        slot_tok = (
            jnp.full((cfg.n_experts * cap + 1,), t, jnp.int32)
            .at[buf_pos]
            .set(st, mode="drop")[:-1]
        )
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), x.dtype)])
        x_buf = jnp.take(xt_pad, slot_tok, axis=0).reshape(cfg.n_experts, cap, d)
    else:
        # Dispatch: (E*C, d) buffer, dropped tokens fall off the end.
        x_buf = (
            jnp.zeros((cfg.n_experts * cap, d), x.dtype)
            .at[buf_pos]
            .set(xt[st], mode="drop")
            .reshape(cfg.n_experts, cap, d)
        )
    x_buf = shard_act(x_buf, ("expert", "expert_capacity", "act_embed"))

    gate = jnp.einsum("ecd,edf->ecf", x_buf, params[f"{p}we_gate"])
    up = jnp.einsum("ecd,edf->ecf", x_buf, params[f"{p}we_up"])
    h = swish(gate) * up
    h = shard_act(h, ("expert", "expert_capacity", "ffn"))
    y_buf = jnp.einsum("ecf,efd->ecd", h, params[f"{p}we_down"])
    y_buf = shard_act(y_buf, ("expert", "expert_capacity", "act_embed"))
    y_flat = y_buf.reshape(cfg.n_experts * cap, d)

    # Combine: gather each assignment's output, weight, scatter-add.
    # (Per-token K-gather combine was tried in §Perf iteration B2 and
    # REFUTED: each gather's backward emits a full (T, d) f32 all-reduce —
    # 1.1 TB/device/step at olmoe train_4k scale.)
    contrib = jnp.take(
        y_flat, jnp.minimum(buf_pos, cfg.n_experts * cap - 1), axis=0
    )
    contrib = contrib * (sw * keep.astype(jnp.float32))[:, None].astype(
        contrib.dtype
    )
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib.astype(x.dtype))

    if cfg.shared_expert:
        sh = swish(xt @ params[f"{p}ws_gate"]) * (xt @ params[f"{p}ws_up"])
        y = y + sh @ params[f"{p}ws_down"]

    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# §Perf iteration B3: explicit expert-parallel MoE via shard_map + all_to_all
# ---------------------------------------------------------------------------


def moe_ffn_ep(
    x: jnp.ndarray,  # (b, s, d) — batch over data axes, seq over model (SP)
    params: Dict[str, jnp.ndarray],
    cfg: MoEConfig,
    prefix: str = "",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism with hand-written dispatch/combine all_to_alls.

    GSPMD's resharding of the capacity buffer costs TBs of all-gather /
    all-reduce per step at olmoe train_4k scale (§Perf B1/B2). Here every
    token moves EXACTLY twice over the model axis (to its experts' shard and
    back): per-device volume = T·K·d·2B/n_devices per direction — the
    intrinsic routing cost. All shapes static; drops happen at send-side
    (per-destination capacity) and recv-side (per-expert capacity), matching
    the capacity-dropping semantics of the baseline.
    """
    import math

    from jax.sharding import PartitionSpec as P

    from ..sharding import current_rules

    p = prefix
    rules = current_rules()
    mesh = rules.mesh
    ep = rules.axis_size("model")
    dp_axis = rules.rules.get("batch")
    dp = rules.axis_size(dp_axis)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    if e % ep or (b % dp) or (s % ep):
        # fall back when the geometry doesn't divide (tiny smoke shapes)
        return moe_ffn(x, params, dataclasses.replace(cfg, dispatch="gather"), prefix)
    e_loc = e // ep
    t_dev = t // (dp * ep)
    c_send = max(k, math.ceil(t_dev * k * cfg.capacity_factor / ep))
    c_recv = max(k, math.ceil(ep * c_send * cfg.capacity_factor / e_loc))

    dp_tuple = dp_axis if isinstance(dp_axis, tuple) else ((dp_axis,) if dp_axis else ())
    tok_spec = P(dp_tuple + ("model",), None)
    rep_spec = P(None, None)
    ew_spec = P("model", None, None)

    def local(xt, router_w, we_gate, we_up, we_down, *shared):
        tl = xt.shape[0]  # t_dev
        logits = (xt @ router_w).astype(jnp.float32)  # (tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        # aux loss from global statistics (psum over all shards)
        me = jax.lax.pmean(probs.mean(axis=0), axis_name="model")
        me = jax.lax.pmean(me, axis_name=dp_tuple) if dp_tuple else me
        assign = jnp.zeros((tl, e), jnp.float32).at[
            jnp.arange(tl)[:, None], top_e
        ].add(1.0)
        ce = assign.mean(axis=0) / k
        ce = jax.lax.pmean(ce, axis_name="model")
        ce = jax.lax.pmean(ce, axis_name=dp_tuple) if dp_tuple else ce
        aux = e * jnp.sum(me * ce) * cfg.router_aux_weight

        # ---- send side: group assignments by destination expert-shard ----
        flat_e = top_e.reshape(tl * k)
        flat_tok = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), k)
        dest = flat_e // e_loc  # (tl·k,) destination shard
        order = jnp.argsort(dest, stable=True)
        sd, stok, sexp = dest[order], flat_tok[order], flat_e[order]
        first = jnp.searchsorted(sd, jnp.arange(ep), side="left")
        rank = jnp.arange(tl * k, dtype=jnp.int32) - first[sd].astype(jnp.int32)
        keep = rank < c_send
        slot = jnp.where(keep, sd * c_send + rank, ep * c_send)  # OOB → drop

        # token rows + expert-local ids packed per destination slot
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
        slot_tok = (
            jnp.full((ep * c_send + 1,), tl, jnp.int32).at[slot].set(stok, mode="drop")[:-1]
        )
        slot_eid = (
            jnp.full((ep * c_send + 1,), -1, jnp.int32)
            .at[slot]
            .set((sexp % e_loc).astype(jnp.int32), mode="drop")[:-1]
        )
        send_x = jnp.take(xt_pad, slot_tok, axis=0).reshape(ep, c_send, d)
        send_eid = slot_eid.reshape(ep, c_send)

        # assignment → (dest shard, slot) lookup for the combine gather
        a_slot = (
            jnp.full((tl * k,), ep * c_send, jnp.int32)
            .at[order]
            .set(jnp.where(keep, slot, ep * c_send))
            .reshape(tl, k)
        )

        # ---- all_to_all over the model axis ----
        recv_x = jax.lax.all_to_all(send_x, "model", split_axis=0, concat_axis=0,
                                    tiled=True)
        recv_eid = jax.lax.all_to_all(send_eid, "model", split_axis=0,
                                      concat_axis=0, tiled=True)
        recv_x = recv_x.reshape(ep * c_send, d)
        recv_eid = recv_eid.reshape(ep * c_send)

        # ---- recv side: group by local expert, capacity-pad, compute ----
        eid_sortable = jnp.where(recv_eid < 0, e_loc, recv_eid)  # pads last
        r_order = jnp.argsort(eid_sortable, stable=True)
        r_eid = eid_sortable[r_order]
        r_first = jnp.searchsorted(r_eid, jnp.arange(e_loc), side="left")
        r_rank = jnp.arange(ep * c_send, dtype=jnp.int32) - r_first[
            jnp.minimum(r_eid, e_loc - 1)
        ].astype(jnp.int32)
        r_keep = (r_eid < e_loc) & (r_rank < c_recv)
        r_slot = jnp.where(r_keep, r_eid * c_recv + r_rank, e_loc * c_recv)

        buf_src = (
            jnp.full((e_loc * c_recv + 1,), ep * c_send, jnp.int32)
            .at[r_slot]
            .set(r_order.astype(jnp.int32), mode="drop")[:-1]
        )
        recv_pad = jnp.concatenate([recv_x, jnp.zeros((1, d), recv_x.dtype)])
        x_buf = jnp.take(recv_pad, buf_src, axis=0).reshape(e_loc, c_recv, d)

        gate = jnp.einsum("ecd,edf->ecf", x_buf, we_gate)
        up = jnp.einsum("ecd,edf->ecf", x_buf, we_up)
        y_buf = jnp.einsum("ecf,efd->ecd", swish(gate) * up, we_down)
        y_buf = y_buf.reshape(e_loc * c_recv, d)

        # ---- un-sort back to received layout, all_to_all home ----
        # received row i → its expert slot (or drop): invert buf_src mapping
        row_slot = (
            jnp.full((ep * c_send + 1,), e_loc * c_recv, jnp.int32)
            .at[buf_src]
            .set(jnp.arange(e_loc * c_recv, dtype=jnp.int32), mode="drop")[:-1]
        )
        y_pad = jnp.concatenate([y_buf, jnp.zeros((1, d), y_buf.dtype)])
        ret = jnp.take(y_pad, row_slot, axis=0).reshape(ep, c_send, d)
        back = jax.lax.all_to_all(ret, "model", split_axis=0, concat_axis=0,
                                  tiled=True).reshape(ep * c_send, d)

        # ---- combine: per-assignment gather + weighted sum over K ----
        back_pad = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)])
        y = jnp.zeros((tl, d), xt.dtype)
        for kk in range(k):
            yk = jnp.take(back_pad, a_slot[:, kk], axis=0)
            y = y + (yk * top_w[:, kk : kk + 1].astype(yk.dtype)).astype(xt.dtype)
        return y, aux

    xt = x.reshape(t, d)
    y, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(tok_spec, rep_spec, ew_spec, ew_spec, ew_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(xt, params[f"{p}router"], params[f"{p}we_gate"], params[f"{p}we_up"],
      params[f"{p}we_down"])

    y = y.reshape(b, s, d)
    if cfg.shared_expert:
        sh = swish(xt @ params[f"{p}ws_gate"]) * (xt @ params[f"{p}ws_up"])
        y = y + (sh @ params[f"{p}ws_down"]).reshape(b, s, d)
    return y, aux


# ---------------------------------------------------------------------------
# an expert-parallel shard behind a sigmoid router (noaux_tc): the held part
# ---------------------------------------------------------------------------


def held_expert_param_defs(d_model: int, d_ff: int, n_held: int, router: int,
                           n_shared: int) -> Dict[str, ParamDef]:
    """The router over every expert of the layer (``router`` wide) with its
    correction bias, the ``n_held`` experts this shard holds, and the shared
    experts as one SwiGLU of ``n_shared * d_ff``."""
    fs = n_shared * d_ff
    defs = {
        "router": ParamDef((d_model, router), ("embed", None), scale=0.02),
        "router_bias": ParamDef((router,), (None,), init="zeros"),
        "we_gate": ParamDef((n_held, d_model, d_ff), ("expert", "embed", "ffn")),
        "we_up": ParamDef((n_held, d_model, d_ff), ("expert", "embed", "ffn")),
        "we_down": ParamDef((n_held, d_ff, d_model), ("expert", "ffn", "embed")),
    }
    if fs:
        defs.update(ws_gate=ParamDef((d_model, fs), ("embed", "ffn")),
                    ws_up=ParamDef((d_model, fs), ("embed", "ffn")),
                    ws_down=ParamDef((fs, d_model), ("ffn", "embed")))
    return defs


def route_sigmoid_bias(logits: jnp.ndarray, bias: jnp.ndarray, top_k: int,
                       scale: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """noaux_tc routing over the whole router: scores s = sigmoid(logits)
    in f32; the top ``top_k`` of s + ``bias`` are chosen; each weight is s
    over the sum of the chosen s, times ``scale``. The bias changes which
    experts are chosen, never their weights. (t, E) → (ids, weights) (t, k)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * scale


def held_gates(idx: jnp.ndarray, w: jnp.ndarray, first: int, n_held: int) -> jnp.ndarray:
    """(t, n_held) f32: each token's weight on held experts ``first`` ..
    ``first + n_held - 1``, 0 where the router did not choose the expert."""
    held = first + jnp.arange(n_held, dtype=idx.dtype)
    hit = idx[..., None] == held  # (t, k, n_held)
    return jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=-2)


def router_gates(h: jnp.ndarray, params: Dict[str, jnp.ndarray], top_k: int,
                 scale: float, n_held: int, first: int = 0) -> jnp.ndarray:
    """(t, d) → (t, n_held) held-expert weights of ``route_sigmoid_bias``."""
    logits = jnp.dot(h, params["router"], preferred_element_type=jnp.float32)
    idx, w = route_sigmoid_bias(logits, params["router_bias"], top_k, scale)
    return held_gates(idx, w, first, n_held)


def shared_experts(h: jnp.ndarray, params: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """The shared experts' SwiGLU (dense; (t, d) → (t, d) in h's dtype)."""
    g = jnp.dot(h, params["ws_gate"], preferred_element_type=jnp.float32)
    u = jnp.dot(h, params["ws_up"], preferred_element_type=jnp.float32)
    mid = (swish(g) * u).astype(h.dtype)
    return jnp.dot(mid, params["ws_down"], preferred_element_type=jnp.float32).astype(h.dtype)


def held_expert_ffn(x: jnp.ndarray, params: Dict[str, jnp.ndarray], top_k: int,
                    scale: float, first: int = 0, shared: bool = True) -> jnp.ndarray:
    """The held shard's part of a routed layer, (b, s, d) → (b, s, d): the
    router chooses over all of its experts; every held expert runs on every
    token, weighted by that token's gate (0 if not chosen), so no token is
    ever dropped; the shared experts are added when ``shared`` (one shard of
    a deployment adds them, the others not). Prefill, frame append and the
    unplanned decode path; the planned decode runs ``expert_ffn_planned``."""
    b, s, d = x.shape
    h = x.reshape(b * s, d)
    gates = router_gates(h, params, top_k, scale, params["we_gate"].shape[0], first)
    f32 = jnp.float32
    g = jnp.einsum("td,edf->tef", h, params["we_gate"], preferred_element_type=f32)
    u = jnp.einsum("td,edf->tef", h, params["we_up"], preferred_element_type=f32)
    mid = (swish(g) * u * gates[..., None]).astype(x.dtype)
    y = jnp.einsum("tef,efd->td", mid, params["we_down"], preferred_element_type=f32)
    y = y.astype(x.dtype)
    if shared and "ws_gate" in params:
        y = y + shared_experts(h, params)
    return y.reshape(b, s, d)
