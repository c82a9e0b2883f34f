"""A reference module for tests: a toy layout that is not a dense decoder.

Latent attention (one cache entry per layer, the normed latent beside the
rotated shared key), a first block with a dense SwiGLU MLP, then blocks of
routed experts behind a sigmoid router with a correction bias, plus shared
experts. The router is as wide as the published expert count; the layer
holds the cut count (the first experts of the router, as one
expert-parallel shard would). It gives everything ``bench/spec.py`` asks
of a reference module, so the harness's weights, key check, cache and
mask comparison, rooflines and FLOPs can be driven through it with no
edit to a harness file.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import EMBED, MATRIX, NORM_W, SMALL, layer_params, top_params

F32 = jnp.float32

key_map = {
    "hidden_size": "d_model", "moe_intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "vocab_size": "vocab_size", "n_routed_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int  # the dense block's MLP
    fe: int  # one expert's MLP
    layers: int
    dense_layers: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    experts: int  # held
    router: int  # published
    top_k: int
    shared: int
    scale: float
    vocab: int
    rope_theta: float
    eps: float


def dims(cfg: dict) -> Dims:
    published = cfg.get("published", {})
    return Dims(
        d=int(cfg["hidden_size"]), f=int(cfg["intermediate_size"]),
        fe=int(cfg["moe_intermediate_size"]), layers=int(cfg["num_hidden_layers"]),
        dense_layers=int(cfg["first_k_dense_replace"]),
        heads=int(cfg["num_attention_heads"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v_dim=int(cfg["v_head_dim"]), experts=int(cfg["n_routed_experts"]),
        router=int(published.get("n_routed_experts", cfg["n_routed_experts"])),
        top_k=int(cfg["num_experts_per_tok"]), shared=int(cfg["n_shared_experts"]),
        scale=float(cfg["routed_scaling_factor"]), vocab=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def program_facts(dm: Dims) -> dict:
    return {"arch_type": "moe", "norm": "rmsnorm", "mlp": "swiglu"}


def _stacks(dm: Dims):
    return (("dense", 0, dm.dense_layers),
            ("moe", dm.dense_layers, dm.layers - dm.dense_layers))


def leaves(dm: Dims) -> dict:
    qw, kvw = dm.heads * (dm.nope + dm.rope), dm.heads * (dm.nope + dm.v_dim)
    attn = {"ln1_w": ((dm.d,), NORM_W), "ln2_w": ((dm.d,), NORM_W),
            "wq": ((dm.d, qw), MATRIX), "wkv_a": ((dm.d, dm.kv_rank + dm.rope), MATRIX),
            "kv_norm_w": ((dm.kv_rank,), NORM_W), "wkv_b": ((dm.kv_rank, kvw), MATRIX),
            "wo": ((dm.heads * dm.v_dim, dm.d), MATRIX)}
    dense = dict(attn, w_gate=((dm.d, dm.f), MATRIX), w_up=((dm.d, dm.f), MATRIX),
                 w_down=((dm.f, dm.d), MATRIX))
    fs = dm.shared * dm.fe
    moe = dict(attn, router=((dm.d, dm.router), MATRIX), router_bias=((dm.router,), SMALL),
               we_gate=((dm.experts, dm.d, dm.fe), MATRIX),
               we_up=((dm.experts, dm.d, dm.fe), MATRIX),
               we_down=((dm.experts, dm.fe, dm.d), MATRIX),
               ws_gate=((dm.d, fs), MATRIX), ws_up=((dm.d, fs), MATRIX),
               ws_down=((fs, dm.d), MATRIX))
    return {"top": {"embed": ((dm.vocab, dm.d), EMBED), "final_norm_w": ((dm.d,), NORM_W),
                    "head": ((dm.d, dm.vocab), EMBED)},
            "stacks": {"dense": (dm.dense_layers, dense),
                       "moe": (dm.layers - dm.dense_layers, moe)}}


def cache_entries(dm: Dims) -> Tuple[str, ...]:
    """One latent per token: (layers, rows, positions, kv_rank + rope)."""
    return ("ckv",)


def sites(dm: Dims) -> Dict[str, Tuple[int, Tuple[int, ...]]]:
    every = tuple(range(dm.layers))
    dense = tuple(range(dm.dense_layers))
    return {"hidden_attn": (dm.d, every), "attn_out": (dm.heads * dm.v_dim, every),
            "hidden_mlp": (dm.d, dense), "ffn": (dm.f, dense),
            "expert_ffn": (dm.experts * dm.fe, tuple(range(dm.dense_layers, dm.layers)))}


def expected_mask(dm: Dims, site: str, importance: np.ndarray, budget: int) -> np.ndarray:
    """Top-k rows, except that expert rows are chosen in two levels: the
    experts of the largest summed importance, as many as the budget
    needs, then the budget's rows among theirs."""
    v = importance.astype(np.float32)
    if site == "expert_ffn":
        per = v.reshape(dm.experts, dm.fe)
        keep = np.argsort(-per.sum(-1), kind="stable")[: math.ceil(budget / dm.fe)]
        held = np.full(per.shape, -np.inf, np.float32)
        held[keep] = per[keep]
        v = held.reshape(-1)
    order = np.argsort(-v, kind="stable")
    m = np.zeros(v.shape[0], bool)
    m[order[:budget]] = True
    return m


def kernel_calls(dm: Dims) -> Dict[str, list]:
    """One decode step's calls: q, the latent and o in every layer; the
    dense block's fused MLP; each routed expert's down projection."""
    qw = dm.heads * (dm.nope + dm.rope)
    proj = [[("hidden_attn", qw)], [("hidden_attn", dm.kv_rank + dm.rope)],
            [("attn_out", dm.d)]] * dm.layers
    mlp = [[("hidden_mlp", dm.f), ("hidden_mlp", dm.f), ("ffn", dm.d)]] * dm.dense_layers
    experts = [[("expert_ffn", dm.d)]] * (dm.top_k * (dm.layers - dm.dense_layers))
    return {"matmul_dma": proj, "mlp_dma": mlp, "expert_dma": experts}


def flops_per_token(dm: Dims, context: float) -> float:
    qk, h = dm.nope + dm.rope, dm.heads
    attn = (dm.d * h * qk + dm.d * (dm.kv_rank + dm.rope)
            + dm.kv_rank * h * (dm.nope + dm.v_dim) + h * dm.v_dim * dm.d)
    moe = dm.d * dm.router + 3 * dm.d * dm.fe * (dm.top_k + dm.shared)
    mats = (dm.layers * attn + dm.dense_layers * 3 * dm.d * dm.f
            + (dm.layers - dm.dense_layers) * moe + dm.d * dm.vocab)
    return 2.0 * mats + 2.0 * dm.layers * h * (qk + dm.v_dim) * context


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: (b, s, heads, hd), positions 0..s-1, rotate-half."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, lp, masks, dm: Dims, moe: bool, imp_pos: Optional[tuple]):
    b, s, _ = x.shape
    h_, qk = dm.heads, dm.nope + dm.rope

    def masked(site, a):
        m = masks.get(site)
        return a if m is None else a * m[None]

    imps = {}
    h = _rms(x, lp["ln1_w"], dm.eps)
    imps["hidden_attn"] = h
    hq = masked("hidden_attn", h)
    q = (hq @ lp["wq"]).reshape(b, s, h_, qk)
    q = jnp.concatenate([q[..., : dm.nope], _rope(q[..., dm.nope:], dm.rope_theta)], -1)
    a = hq @ lp["wkv_a"]
    c = _rms(a[..., : dm.kv_rank], lp["kv_norm_w"], dm.eps)
    k_rope = _rope(a[..., dm.kv_rank:][:, :, None], dm.rope_theta)
    latent = jnp.concatenate([c, k_rope[:, :, 0]], -1)
    kv = (c @ lp["wkv_b"]).reshape(b, s, h_, dm.nope + dm.v_dim)
    k = jnp.concatenate([kv[..., : dm.nope], jnp.broadcast_to(k_rope, (b, s, h_, dm.rope))],
                        -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(qk)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), kv[..., dm.nope:])
    attn = attn.reshape(b, s, h_ * dm.v_dim)
    imps["attn_out"] = attn
    x = x + masked("attn_out", attn) @ lp["wo"]
    h2 = _rms(x, lp["ln2_w"], dm.eps)
    if not moe:
        imps["hidden_mlp"] = h2
        hm = masked("hidden_mlp", h2)
        mid = jax.nn.silu(hm @ lp["w_gate"]) * (hm @ lp["w_up"])
        imps["ffn"] = mid
        y = masked("ffn", mid) @ lp["w_down"]
    else:
        score = jax.nn.sigmoid(h2 @ lp["router"])[..., : dm.experts]
        _, idx = jax.lax.top_k(score + lp["router_bias"][: dm.experts], dm.top_k)
        w = jnp.take_along_axis(score, idx, -1)
        w = w / jnp.sum(w, -1, keepdims=True) * dm.scale
        gates = jnp.sum(jax.nn.one_hot(idx, dm.experts, dtype=F32) * w[..., None], -2)
        mid = (jax.nn.silu(jnp.einsum("bsd,edf->bsef", h2, lp["we_gate"]))
               * jnp.einsum("bsd,edf->bsef", h2, lp["we_up"])) * gates[..., None]
        flat = mid.reshape(b, s, dm.experts * dm.fe)
        imps["expert_ffn"] = flat
        routed = masked("expert_ffn", flat).reshape(b, s, dm.experts, dm.fe)
        y = jnp.einsum("bsef,efd->bsd", routed, lp["we_down"])
        y = y + (jax.nn.silu(h2 @ lp["ws_gate"]) * (h2 @ lp["ws_up"])) @ lp["ws_down"]
    if imp_pos is None:
        return x + y, latent, None
    pos = jnp.asarray(imp_pos)
    return x + y, latent, {k: jnp.mean(jnp.abs(v[:, pos]), axis=0) for k, v in imps.items()}


def _fp8(w):
    return w.astype(jnp.float8_e4m3fn).astype(F32)


def forward(dm: Dims, seed: int, tokens: np.ndarray, logit_pos: Sequence[int],
            masks_at: Optional[Callable[[int], Dict[str, np.ndarray]]] = None,
            imp_pos: Optional[Sequence[int]] = None, fp8: bool = False,
            on_cache: Optional[Callable[[int, Dict[str, jax.Array]], None]] = None):
    imp_key = None if imp_pos is None else tuple(int(p) for p in imp_pos)
    imps = {k: [] for k in sites(dm)} if imp_key else None
    with jax.default_matmul_precision("highest"):
        top = top_params(leaves, dm, seed, F32)
        if fp8:
            top = {k: (_fp8(v) if v.ndim >= 2 else v) for k, v in top.items()}
        x = jnp.take(top["embed"], jnp.asarray(tokens), axis=0)
        for stack, first, depth in _stacks(dm):
            for i in range(depth):
                lp = layer_params(leaves, dm, seed, stack, i, F32)
                if fp8:
                    lp = {k: (_fp8(v) if v.ndim >= 2 else v) for k, v in lp.items()}
                m = masks_at(first + i) if masks_at else {}
                m = {k: jnp.asarray(v, F32) for k, v in m.items()}
                x, latent, li = _layer(x, lp, m, dm, stack == "moe", imp_key)
                if on_cache is not None:
                    on_cache(first + i, {"ckv": latent})
                if imps is not None:
                    for k, v in li.items():
                        imps[k].append(np.asarray(v))
        h = _rms(x[:, jnp.asarray(np.asarray(logit_pos, np.int32))], top["final_norm_w"],
                 dm.eps)
        logits = h @ top["head"]
    if imps is not None:
        imps = {k: np.stack(v) for k, v in imps.items()}
    return logits, imps
