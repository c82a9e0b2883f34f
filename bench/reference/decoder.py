"""Plain float32 reference of the served decoder family, and the layout
of its weights, cache, sparsified sites and kernel calls.

The equations of the published models, in straightforward ``jax.numpy`` at
float32 with matmul precision "highest" (on a TPU an f32 matmul otherwise
runs as one bfloat16 pass): pre-norm blocks (RMSNorm or LayerNorm),
rotate-half RoPE, grouped-query causal attention, a SwiGLU or tanh-GELU
MLP, tied or untied head. It imports nothing of the program: weights come
from ``bench/weights.py`` and the sequence from the served tokens.

Sparse decoding is expressed as the paper defines it: a 0/1 mask over the
input neurons of each sparsified projection (q/k/v share the hidden_attn
mask, gate/up or fc the hidden_mlp mask; o reads attn_out, down or proj
reads ffn), given per position. Positions without a mask run dense.

The model is walked one layer at a time, so only one layer's float32
weights sit in device memory. ``bench/spec.py`` lists what a reference
module provides; every layer-shape fact the harness uses is here.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import EMBED, MATRIX, NORM_W, SMALL, layer_params, top_params

F32 = jnp.float32
STACK = "layers"

# configuration-file key -> the program's ModelConfig field
key_map = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    norm: str  # rms | layer
    norm_eps: float
    mlp: str  # swiglu | gelu
    tied: bool


def dims(cfg: dict) -> Dims:
    """Shape facts of a configuration file (Hugging Face key names)."""
    act = cfg["hidden_act"]
    if act == "silu":
        mlp = "swiglu"
    elif act.startswith("gelu"):
        mlp = "gelu"
    else:
        raise ValueError(f"unsupported hidden_act {act!r}")
    if "rms_norm_eps" in cfg:
        norm, eps = "rms", float(cfg["rms_norm_eps"])
    else:
        norm, eps = "layer", float(cfg["norm_epsilon"])
    # a served model that departs from the published epsilon says so
    eps = float(cfg.get("served_norm_eps", eps))
    heads = int(cfg["num_attention_heads"])
    return Dims(
        d=int(cfg["hidden_size"]), f=int(cfg["intermediate_size"]),
        layers=int(cfg["num_hidden_layers"]), heads=heads,
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // heads),
        vocab=int(cfg["vocab_size"]), rope_theta=float(cfg["rope_theta"]),
        norm=norm, norm_eps=eps, mlp=mlp, tied=bool(cfg["tie_word_embeddings"]),
    )


def program_facts(dm: Dims) -> dict:
    """What the program's ModelConfig has to say besides ``key_map``'s keys."""
    return {"resolved_head_dim": dm.head_dim,
            "norm": {"rms": "rmsnorm", "layer": "layernorm"}[dm.norm], "mlp": dm.mlp}


def leaves(dm: Dims) -> dict:
    top = {"embed": ((dm.vocab, dm.d), EMBED), "final_norm_w": ((dm.d,), NORM_W)}
    if dm.norm == "layer":
        top["final_norm_b"] = ((dm.d,), SMALL)
    if not dm.tied:
        top["head"] = ((dm.d, dm.vocab), EMBED)
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    layer = {"ln1_w": ((dm.d,), NORM_W), "ln2_w": ((dm.d,), NORM_W),
             "wq": ((dm.d, q), MATRIX), "wk": ((dm.d, kv), MATRIX),
             "wv": ((dm.d, kv), MATRIX), "wo": ((q, dm.d), MATRIX)}
    if dm.norm == "layer":
        layer.update(ln1_b=((dm.d,), SMALL), ln2_b=((dm.d,), SMALL))
    if dm.mlp == "swiglu":
        layer.update(w_gate=((dm.d, dm.f), MATRIX), w_up=((dm.d, dm.f), MATRIX),
                     w_down=((dm.f, dm.d), MATRIX))
    else:
        layer.update(w_fc=((dm.d, dm.f), MATRIX), b_fc=((dm.f,), SMALL),
                     w_proj=((dm.f, dm.d), MATRIX), b_proj=((dm.d,), SMALL))
    return {"top": top, "stacks": {STACK: (dm.layers, layer)}}


def cache_entries(dm: Dims) -> Tuple[str, ...]:
    """The program's cache leaves, each (layers, rows, positions, kv, hd)."""
    return ("k", "v")


def sites(dm: Dims) -> Dict[str, Tuple[int, Tuple[int, ...]]]:
    """Each sparsified site's input width and the layers it spans."""
    every = tuple(range(dm.layers))
    return {"hidden_attn": (dm.d, every), "attn_out": (dm.heads * dm.head_dim, every),
            "hidden_mlp": (dm.d, every), "ffn": (dm.f, every)}


def kernel_calls(dm: Dims) -> Dict[str, List[List[Tuple[str, int]]]]:
    """Each kernel's calls in one layer (all layers alike); a call is a
    list of (site, output width) for the matrices it streams."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    proj = [[("hidden_attn", q)], [("hidden_attn", kv)], [("hidden_attn", kv)],
            [("attn_out", dm.d)]]
    if dm.mlp == "swiglu":
        return {"matmul_dma": proj,
                "mlp_dma": [[("hidden_mlp", dm.f), ("hidden_mlp", dm.f), ("ffn", dm.d)]]}
    return {"matmul_dma": proj + [[("hidden_mlp", dm.f)], [("ffn", dm.d)]]}


def flops_per_token(dm: Dims, context: float) -> float:
    """Operations of one dense decode token: every matmul of every layer
    and the head, plus attention's QK and PV over ``context`` positions."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    mlp = (3 if dm.mlp == "swiglu" else 2) * dm.d * dm.f
    per_layer = dm.d * q + 2 * dm.d * kv + q * dm.d + mlp
    return 2.0 * (dm.layers * per_layer + dm.d * dm.vocab) + 4.0 * dm.layers * q * context


def _norm(x, w, b, dm: Dims):
    if dm.norm == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + dm.norm_eps) * w
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + dm.norm_eps) * w + b


def _rope(x, theta: float):
    """x: (b, s, heads, hd), positions 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(w):
    return w.astype(jnp.float8_e4m3fn).astype(F32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, lp, masks, dm: Dims, imp_pos: Optional[tuple]):
    """One block. ``masks``: site -> (s, N) 0/1 or None. Returns the new
    residual, this layer's keys (after RoPE) and values (b, s, kv, hd), and,
    at ``imp_pos``, each site's importance: the mean over the batch of
    |input| before its mask (what selection ranks)."""
    b, s, _ = x.shape
    hd, nh, nkv = dm.head_dim, dm.heads, dm.kv_heads

    def masked(site, a):
        m = masks.get(site)
        return a if m is None else a * m[None]

    imps = {}
    h = _norm(x, lp["ln1_w"], lp.get("ln1_b"), dm)
    imps["hidden_attn"] = h
    hq = masked("hidden_attn", h)
    q = _rope((hq @ lp["wq"]).reshape(b, s, nh, hd), dm.rope_theta)
    k_cache = _rope((hq @ lp["wk"]).reshape(b, s, nkv, hd), dm.rope_theta)
    v_cache = (hq @ lp["wv"]).reshape(b, s, nkv, hd)
    k = jnp.repeat(k_cache, nh // nkv, axis=2)
    v = jnp.repeat(v_cache, nh // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    attn = attn.reshape(b, s, nh * hd)
    imps["attn_out"] = attn
    x = x + masked("attn_out", attn) @ lp["wo"]
    h2 = _norm(x, lp["ln2_w"], lp.get("ln2_b"), dm)
    imps["hidden_mlp"] = h2
    hm = masked("hidden_mlp", h2)
    if dm.mlp == "swiglu":
        mid = jax.nn.silu(hm @ lp["w_gate"]) * (hm @ lp["w_up"])
        y = masked("ffn", mid) @ lp["w_down"]
    else:
        mid = jax.nn.gelu(hm @ lp["w_fc"] + lp["b_fc"], approximate=True)
        y = masked("ffn", mid) @ lp["w_proj"] + lp["b_proj"]
    imps["ffn"] = mid
    if imp_pos is None:
        return x + y, k_cache, v_cache, None
    pos = jnp.asarray(imp_pos)
    return x + y, k_cache, v_cache, {k: jnp.mean(jnp.abs(a[:, pos]), axis=0)
                                     for k, a in imps.items()}


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, top, pos, dm: Dims):
    h = _norm(x[:, pos], top["final_norm_w"], top.get("final_norm_b"), dm)
    head = top["embed"].T if dm.tied else top["head"]
    return h @ head


def forward(dm: Dims, seed: int, tokens: np.ndarray, logit_pos: Sequence[int],
            masks_at: Optional[Callable[[int], Dict[str, np.ndarray]]] = None,
            imp_pos: Optional[Sequence[int]] = None, fp8: bool = False,
            on_cache: Optional[Callable[[int, Dict[str, jax.Array]], None]] = None):
    """Float32 logits (b, len(logit_pos), vocab) at ``logit_pos`` of
    ``tokens`` (b, s), and, when ``imp_pos`` is given, each site's
    importance at those positions as site -> (layers, len(imp_pos), N).

    ``masks_at(layer)`` gives that layer's site -> (s, N) masks;
    ``on_cache(layer, entries)`` receives each layer's cache entries, keys
    and values (b, s, kv, hd). ``fp8`` rounds every weight matrix to float8
    e4m3 first: the precision a step below the served bfloat16, used as the
    correctness check's control."""
    imp_key = None if imp_pos is None else tuple(int(p) for p in imp_pos)
    imps = {k: [] for k in sites(dm)} if imp_key else None
    with jax.default_matmul_precision("highest"):
        top = top_params(leaves, dm, seed, F32)
        if fp8:
            top = {k: (_fp8(v) if v.ndim == 2 else v) for k, v in top.items()}
        x = jnp.take(top["embed"], jnp.asarray(tokens), axis=0)
        for layer in range(dm.layers):
            lp = layer_params(leaves, dm, seed, STACK, layer, F32)
            if fp8:
                lp = {k: (_fp8(v) if v.ndim == 2 else v) for k, v in lp.items()}
            m = masks_at(layer) if masks_at else {}
            m = {k: jnp.asarray(v, F32) for k, v in m.items() if v is not None}
            x, k, v, li = _layer(x, lp, m, dm, imp_key)
            if on_cache is not None:
                on_cache(layer, {"k": k, "v": v})
            if imps is not None:
                for k in imps:
                    imps[k].append(np.asarray(li[k]))
            del lp
        logits = _head(x, top, jnp.asarray(np.asarray(logit_pos, np.int32)), dm)
    if imps is not None:
        imps = {k: np.stack(v) for k, v in imps.items()}
    return logits, imps
