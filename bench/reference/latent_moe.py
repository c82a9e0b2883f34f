"""Plain float32 reference of the latent-attention decoder with routed
experts (DeepSeek-V2 layout, Kimi-VL-A3B's language model), and the layout
of its weights, cache, sparsified sites and kernel calls.

The published equations in straightforward ``jax.numpy`` at float32 with
matmul precision "highest". Latent attention (no q-LoRA): q = h·``wq``
split into a position-free part and a rotated part; ``wkv_a`` gives the
latent, RMS-normed by ``kv_norm_w``, beside the key shared by all heads,
rotated; ``wkv_b`` maps the latent to each head's position-free key and
its value; scale 1/sqrt(nope + rope). Rotate-half RoPE on the rotated
dimensions. The first ``first_k_dense_replace`` blocks run a SwiGLU MLP;
the rest route (``noaux_tc``): s = sigmoid(h·``router``) over the whole
router (the published expert count, ``published.n_routed_experts``), the
top ``num_experts_per_tok`` of s + ``router_bias`` chosen, each weight s
over the chosen s's sum times ``routed_scaling_factor``. The layer holds
the first ``n_routed_experts`` of them (shard 0 of an expert-parallel
deployment) and adds the shared experts, one SwiGLU of
``n_shared_experts`` × ``moe_intermediate_size``. Untied head.

Sparse decoding is the paper's 0/1 mask over the input neurons of each
sparsified projection, given per position: ``wq`` and ``wkv_a`` share the
hidden_attn mask, ``wo`` reads attn_out, the dense MLP's gate/up the
hidden_mlp mask and down the ffn mask; ``expert_ffn`` masks the routed
experts' intermediate (the held experts side by side, the routing weight
applied), which is the input of their down projection; masking it is
skipping those rows of gate, up and down. ``wkv_b``, the router and the
shared experts stay dense.

The expert site selects inside its own step: the router chooses the step's
experts, they run, and the importance of their rows ranks them (the
``expected_mask`` below). So the importance ``forward`` reports for that
site at a position is the next position's: ``bench/check.py`` pairs a
step's mask with the importance one position before it, which for the
other sites is the step whose importance the selection ranked.

The model is walked one layer at a time. It imports nothing of the
program; weights come from ``bench/weights.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import EMBED, MATRIX, NORM_W, SMALL, layer_params, top_params

F32 = jnp.float32

# configuration-file key -> the program's ModelConfig field
key_map = {
    "hidden_size": "d_model", "moe_intermediate_size": "d_ff",
    "intermediate_size": "dense_d_ff", "first_k_dense_replace": "first_k_dense",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "vocab_size": "vocab_size", "n_routed_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "routed_scaling_factor": "moe_routed_scale",
    "n_shared_experts": "moe_n_shared", "rms_norm_eps": "norm_eps",
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
    """Shape facts of a configuration file (Hugging Face key names); the
    router is as wide as the published expert count."""
    if cfg.get("q_lora_rank") or cfg.get("n_group", 1) != 1 or \
            cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference covers no q-LoRA, one expert group and "
                         "sigmoid scoring")
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
    """What the program's ModelConfig has to say besides ``key_map``'s keys."""
    return {"is_latent": True, "router_width": dm.router, "mlp": "swiglu"}


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
    """Top-k rows (ties to the lower row), except that expert rows are
    chosen in two levels: every held expert the step routed to runs (the
    rows of an unrouted expert have importance exactly 0); then ``budget`` ·
    routed // experts of their rows, the budget's share of the routed
    experts' width, by importance. An unrouted expert's rows are marked
    kept: they cost nothing (their input is 0), and a replay whose router
    chooses the expert there computes it whole rather than dropping it."""
    v = importance.astype(np.float32)
    unrouted = np.zeros(v.shape[0], bool)
    if site == "expert_ffn":
        routed = v.reshape(dm.experts, dm.fe).sum(-1) > 0
        budget = budget * int(routed.sum()) // dm.experts
        unrouted = ~np.repeat(routed, dm.fe)
        v = np.where(unrouted, -np.inf, v).astype(np.float32)
    order = np.argsort(-v, kind="stable")
    m = np.zeros(v.shape[0], bool)
    m[order[:budget]] = True
    return m | unrouted


def kernel_calls(dm: Dims) -> Dict[str, list]:
    """One decode step's calls: q, the latent and o in every layer; the
    dense blocks' fused MLP; each expert layer's down gather over the
    selected rows. (The expert gather's up phase reads the routed experts'
    gate and up whole, which no (site, width) list expresses:
    ``metrics/chunk_gather_expert_dma_roofline.py`` counts it from the
    program's expert counter.)"""
    qw = dm.heads * (dm.nope + dm.rope)
    proj = [[("hidden_attn", qw)], [("hidden_attn", dm.kv_rank + dm.rope)],
            [("attn_out", dm.d)]] * dm.layers
    mlp = [[("hidden_mlp", dm.f), ("hidden_mlp", dm.f), ("ffn", dm.d)]] * dm.dense_layers
    experts = [[("expert_ffn", dm.d)]] * (dm.layers - dm.dense_layers)
    return {"matmul_dma": proj, "mlp_dma": mlp, "expert_dma": experts}


def flops_per_token(dm: Dims, context: float) -> float:
    """Operations of one dense decode token on this shard: every matmul of
    every layer and the head, the held experts' expected share of the
    token's picks (top_k · held / router), and the latent attention over
    ``context`` positions in the absorbed form."""
    qk, h = dm.nope + dm.rope, dm.heads
    attn = (dm.d * h * qk + dm.d * (dm.kv_rank + dm.rope)
            + dm.kv_rank * h * (dm.nope + dm.v_dim) + h * dm.v_dim * dm.d)
    picks = dm.top_k * dm.experts / dm.router
    moe = dm.d * dm.router + 3 * dm.d * dm.fe * (picks + dm.shared)
    mats = (dm.layers * attn + dm.dense_layers * 3 * dm.d * dm.f
            + (dm.layers - dm.dense_layers) * moe + dm.d * dm.vocab)
    return 2.0 * mats + 2.0 * dm.layers * h * (dm.kv_rank + dm.rope + dm.kv_rank) * context


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
        # noaux_tc over the whole router; the held experts are its first
        score = jax.nn.sigmoid(h2 @ lp["router"])
        _, idx = jax.lax.top_k(score + lp["router_bias"], dm.top_k)
        w = jnp.take_along_axis(score, idx, -1)
        w = w / jnp.sum(w, -1, keepdims=True) * dm.scale
        gates = jnp.sum(jax.nn.one_hot(idx, dm.router, dtype=F32) * w[..., None],
                        -2)[..., : dm.experts]
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
    # the expert site's selection ranks its own step (module docstring)
    shifted = {"expert_ffn": jnp.minimum(pos + 1, s - 1)}
    return x + y, latent, {k: jnp.mean(jnp.abs(v[:, shifted.get(k, pos)]), axis=0)
                           for k, v in imps.items()}


def _fp8(w):
    return w.astype(jnp.float8_e4m3fn).astype(F32)


def forward(dm: Dims, seed: int, tokens: np.ndarray, logit_pos: Sequence[int],
            masks_at: Optional[Callable[[int], Dict[str, np.ndarray]]] = None,
            imp_pos: Optional[Sequence[int]] = None, fp8: bool = False,
            on_cache: Optional[Callable[[int, Dict[str, jax.Array]], None]] = None):
    """Float32 logits (b, len(logit_pos), vocab) at ``logit_pos`` of
    ``tokens`` (b, s), and, with ``imp_pos``, each site's importance there
    as site -> (its layers, len(imp_pos), N) (the expert site's one
    position later). ``masks_at(layer)``: that layer's site -> (s, N)
    masks; ``on_cache(layer, {"ckv": (b, s, R + rope)})``; ``fp8`` rounds
    every weight matrix to float8 e4m3 first (the correctness control)."""
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
