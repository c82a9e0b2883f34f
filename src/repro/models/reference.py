"""Plain float32 forward pass of the decoder-LM family, for checking logits.

The model code computes in bfloat16 through the sharding, cache and sparse
machinery. This module is its independent yardstick: the same equations in
straightforward ``jax.numpy`` at float32, with matmul precision "highest"
(on TPU an f32 matmul otherwise runs as one bfloat16 pass), and no kernels,
cache, sparsity or batching tricks. It covers the families the serve path
runs at full width: RMSNorm, rotate-half RoPE, grouped-query causal
attention, SwiGLU MLP, untied output head, and the early-fusion VLM prefix
(projected frontend embeddings ahead of the text tokens). Weights are read
one layer at a time and the head in vocabulary blocks, so the float32
copies of a full-width model never sit in device memory at once.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig

# Relative L2 error ‖logits − ref‖ / ‖ref‖ allowed for the bf16 model. Each
# bf16 rounding of an activation or weight costs up to 2^-9 relative; a
# forward pass rounds a few dozen times in series, and independent
# roundings add in quadrature, which puts the expected error near 1e-2.
# fp8 (2^-4 per rounding) or a missing term in the equations lands well
# above this. At the reduced 2-layer TinyLlama and InternVL2 configs on
# the CPU (tests/test_reference.py) the bf16 model reads 1.1e-2, fp8
# weights 9.2e-2 to 1.1e-1, and one layer dropped 0.85. The check covers
# the dense prefill path; the sparse decode kernels are held to their
# schedule twin instead (kernels/backend.py).
LOGIT_REL_TOL = 3e-2

_F32 = jnp.float32


def _rms_norm(x, w, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta: float):
    """x: (b, s, heads, hd); rotate-half over the first/second head_dim halves."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd)
    ang = jnp.arange(s, dtype=_F32)[:, None] * freqs  # (s, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(x, lp: Dict[str, jnp.ndarray], cfg: ModelConfig):
    """One decoder block at f32: x + attn(norm(x)), then + mlp(norm(·))."""
    lp = {k: v.astype(_F32) for k, v in lp.items()}
    b, s, _ = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    h = _rms_norm(x, lp["ln1_w"])
    q = _rope((h @ lp["wq"]).reshape(b, s, nh, hd), cfg.rope_theta)
    k = _rope((h @ lp["wk"]).reshape(b, s, nkv, hd), cfg.rope_theta)
    v = (h @ lp["wv"]).reshape(b, s, nkv, hd)
    # grouped-query attention: query head i reads kv head i // (nh // nkv)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(hd, _F32))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(b, s, nh * hd) @ lp["wo"]
    h = _rms_norm(x, lp["ln2_w"])
    gate = h @ lp["w_gate"]
    return x + (jax.nn.silu(gate) * (h @ lp["w_up"])) @ lp["w_down"]


def _check_supported(cfg: ModelConfig) -> None:
    if (cfg.arch_type not in ("dense", "vlm") or cfg.norm != "rmsnorm"
            or cfg.mlp != "swiglu" or cfg.tie_embeddings
            or cfg.rope_theta is None):
        raise NotImplementedError(
            f"the float32 reference covers RMSNorm/SwiGLU/RoPE decoder LMs "
            f"with an untied head; {cfg.name!r} is not one"
        )


def reference_last_logits(cfg: ModelConfig, params, batch,
                          vocab_block: int = 16384) -> jnp.ndarray:
    """Float32 logits (b, vocab) of the last prompt position — what
    ``Model.prefill`` returns as its first ``last`` output. ``batch`` is the
    model's prompt dict (``tokens`` and, for a VLM, ``frontend``), whose
    sequence must fit one window (no sliding-window masking here)."""
    _check_supported(cfg)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(_F32)
        if cfg.d_frontend:
            vis = batch["frontend"].astype(_F32) @ params["projector"].astype(_F32)
            x = jnp.concatenate([vis, x], axis=1)  # [vision | text]
        layer = jax.jit(functools.partial(_layer, cfg=cfg))
        for i in range(cfg.n_layers):
            x = layer(x, {k: v[i] for k, v in params["layers"].items()})
        h = _rms_norm(x[:, -1], params["final_norm_w"].astype(_F32))
        head = params["head"]
        return jnp.concatenate([
            h @ head[:, j:j + vocab_block].astype(_F32)
            for j in range(0, head.shape[1], vocab_block)
        ], axis=1)


def logit_rel_error(logits: jnp.ndarray, ref: jnp.ndarray) -> float:
    """‖logits − ref‖₂ / ‖ref‖₂ over the whole (b, vocab) block, in f32."""
    d = logits.astype(_F32) - ref.astype(_F32)
    return float(jnp.linalg.norm(d) / jnp.linalg.norm(ref.astype(_F32)))
