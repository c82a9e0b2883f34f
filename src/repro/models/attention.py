"""GQA attention with RoPE, sliding windows, blockwise (flash-style) softmax,
and KV caches (linear + rotating-window).

Used by every attention-bearing architecture (dense, vlm, moe, zamba2 shared
block, whisper). The blockwise path keeps peak activation memory bounded for
32k-token prefill on the production mesh (online softmax over kv blocks,
scanned q blocks) — functionally identical to naive attention (tested).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding import shard_act
from .common import ParamDef, apply_rope

NEG_INF = -1e30


def attention_param_defs(
    d_model: int, n_heads: int, n_kv_heads: int, head_dim: int, prefix: str = ""
) -> Dict[str, ParamDef]:
    p = prefix
    return {
        f"{p}wq": ParamDef((d_model, n_heads * head_dim), ("embed", "heads")),
        f"{p}wk": ParamDef((d_model, n_kv_heads * head_dim), ("embed", "kv_heads")),
        f"{p}wv": ParamDef((d_model, n_kv_heads * head_dim), ("embed", "kv_heads")),
        f"{p}wo": ParamDef((n_heads * head_dim, d_model), ("heads", "embed")),
    }


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(b, s, kv, hd) -> (b, s, kv * n_rep, hd) by head repetition."""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd))
    return x.reshape(b, s, kv * n_rep, hd)


def _direct_attention(
    q: jnp.ndarray,  # (b, sq, h, hd)
    k: jnp.ndarray,  # (b, sk, h, hd)
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],  # (sq, sk) or (b, sq, sk) bool
) -> jnp.ndarray:
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        scores = jnp.where(m[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_offset,
    causal: bool,
    window: Optional[int],
    block_q: int = 512,
    block_kv: int = 1024,
) -> jnp.ndarray:
    """Flash-style online-softmax attention, O(block_q*block_kv) score memory.

    q: (b, sq, h, hd); k/v: (b, sk, h, hd). Causal offset: query i has
    absolute position i + q_offset; key j has absolute position j.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd**-0.5
    # pad to block multiples
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_kv
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_kv

    q_blocks = qp.reshape(b, nq, block_q, h, hd).transpose(1, 0, 3, 2, 4)  # (nq,b,h,bq,hd)
    k_blocks = kp.reshape(b, nk, block_kv, h, hd).transpose(1, 0, 3, 2, 4)
    v_blocks = vp.reshape(b, nk, block_kv, h, hd).transpose(1, 0, 3, 2, 4)

    def q_block_body(qi, qb):
        qb32 = qb.astype(jnp.float32) * scale  # (b,h,bq,hd)
        q_pos = qi * block_q + jnp.arange(block_q) + q_offset  # (bq,)

        def kv_body(carry, inputs):
            m_prev, l_prev, acc = carry
            ki, kb, vb = inputs
            k_pos = ki * block_kv + jnp.arange(block_kv)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb32, kb.astype(jnp.float32))
            mask = jnp.ones((block_q, block_kv), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            mask &= (k_pos < sk)[None, :]  # kv padding
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_prev * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32)
            )
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, h, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        acc0 = jnp.zeros((b, h, block_q, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, acc0), (jnp.arange(nk), k_blocks, v_blocks)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (b,h,bq,hd)

    outs = jax.lax.map(lambda args: q_block_body(*args), (jnp.arange(nq), q_blocks))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * block_q, h, hd)
    return out[:, :sq].astype(q.dtype)


def _want_seq_shard_map(n_heads: int, q_shape) -> bool:
    from ..sharding import current_rules

    rules = current_rules()
    if rules is None or not getattr(rules, "seq_shard_attention", False):
        return False
    tp = rules.axis_size("model")
    dp = rules.axis_size(rules.rules.get("batch"))
    b, s = q_shape[0], q_shape[1]
    return (
        tp > 1
        and n_heads % tp != 0
        and s > 2048
        and s % tp == 0
        and b % dp == 0
    )


def _seq_sharded_attention(q, k, v, window):
    """Causal attention with the q-sequence explicitly sharded over 'model'
    (shard_map); kv replicated across the model axis (one all-gather)."""
    from jax.sharding import PartitionSpec as P

    from ..sharding import current_rules

    rules = current_rules()
    mesh = rules.mesh
    dp_axis = rules.rules.get("batch")
    tp = rules.axis_size("model")
    s = q.shape[1]
    s_local = s // tp

    q_spec = P(dp_axis, "model", None, None)
    kv_spec = P(dp_axis, None, None, None)

    def local(qb, kb, vb):
        import jax as _jax

        shard = _jax.lax.axis_index("model")
        q_offset = shard * s_local  # absolute position of this shard's row 0
        return _blockwise_attention(qb, kb, vb, q_offset, True, window)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k, v)


def multi_head_attention(
    x: jnp.ndarray,  # (b, s, d)
    params: Dict[str, jnp.ndarray],
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    positions: Optional[jnp.ndarray] = None,  # (b, s) absolute positions
    rope_theta: Optional[float] = 10000.0,
    causal: bool = True,
    window: Optional[int] = None,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # cross-attn
    blockwise_threshold: int = 2048,
    prefix: str = "",
    project_out: bool = True,
) -> jnp.ndarray:
    """Full attention sublayer (projections + SDPA). Returns (b, s, d), or the
    pre-o-projection (b, s, h*hd) when project_out=False (sparse exec masks
    the o-projection's input rows per paper App. A)."""
    b, s, d = x.shape
    p = prefix
    q = (x @ params[f"{p}wq"]).reshape(b, s, n_heads, head_dim)
    if kv_override is None:
        k = (x @ params[f"{p}wk"]).reshape(b, s, n_kv_heads, head_dim)
        v = (x @ params[f"{p}wv"]).reshape(b, s, n_kv_heads, head_dim)
        if rope_theta is not None:
            if positions is None:
                positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
    else:
        k, v = kv_override  # pre-projected encoder states (b, sk, kv, hd)
    # seq ("act_seq") is a FALLBACK target: it picks up the model axis only
    # when the head count doesn't divide it (e.g. starcoder2's 24/36 heads on
    # a 16-way mesh) — otherwise heads claim it first (§Perf iteration C).
    q = shard_act(q, ("batch", "act_seq", "heads", "head_dim"))
    k = shard_act(k, ("batch", None, "kv_heads", "head_dim"))
    v = shard_act(v, ("batch", None, "kv_heads", "head_dim"))

    n_rep = n_heads // k.shape[2]
    k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)

    sk = k.shape[1]
    if causal and kv_override is None and _want_seq_shard_map(n_heads, q.shape):
        # §Perf iteration C: head count doesn't divide the model axis —
        # instead of letting GSPMD replicate the whole attention per device,
        # explicitly shard the q-sequence over 'model' with shard_map; each
        # shard runs blockwise attention for its s/tp rows against full kv.
        out = _seq_sharded_attention(q, k, v, window)
    elif max(s, sk) > blockwise_threshold:
        q_offset = jnp.int32(sk - s) if causal else jnp.int32(0)
        out = _blockwise_attention(q, k, v, q_offset, causal, window)
    else:
        mask = None
        if causal:
            qi = jnp.arange(s)[:, None] + (sk - s)
            kj = jnp.arange(sk)[None, :]
            mask = kj <= qi
            if window is not None:
                mask &= kj > qi - window
        out = _direct_attention(q, k, v, mask)
    out = out.reshape(b, s, n_heads * head_dim)
    if not project_out:
        return out
    return out @ params[f"{p}wo"]


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Static geometry of one layer's KV cache.

    ``window`` caps physical length (rotating writes) — this is what makes
    dense architectures runnable at the 524k-token shape (DESIGN.md §4).
    """

    batch: int
    max_seq: int
    n_kv_heads: int
    head_dim: int
    window: Optional[int] = None

    @property
    def physical_len(self) -> int:
        return min(self.max_seq, self.window) if self.window else self.max_seq


def init_kv_cache(spec: CacheSpec, n_layers: int, dtype) -> Dict[str, jnp.ndarray]:
    shape = (n_layers, spec.batch, spec.physical_len, spec.n_kv_heads, spec.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        # number of tokens ever written (logical length)
        "length": jnp.zeros((), jnp.int32),
    }


def init_paged_kv_cache(
    n_pages: int,
    page_tokens: int,
    batch: int,
    max_pages: int,
    n_kv_heads: int,
    head_dim: int,
    n_layers: int,
    dtype,
) -> Dict[str, jnp.ndarray]:
    """Paged twin of ``init_kv_cache``: per-layer page POOLS plus a shared
    per-slot page table. ``max_pages * page_tokens`` equals the logical
    ``max_seq`` — the gathered view has exactly the dense cache's physical
    shape, which is what keeps paged decode byte-identical to dense.

    Pools are zero-initialised so unreferenced pages hold finite values:
    masked attention positions then contribute exact 0.0 probability times
    finite garbage — bitwise zero, same as the dense path's zero slots.
    Table rows start at ``GARBAGE_PAGE`` (page 0, core/paged_kv.py): free
    slots scatter there harmlessly and no live table ever reads it."""
    pool = (n_layers, n_pages, page_tokens, n_kv_heads, head_dim)
    return {
        "k": jnp.zeros(pool, dtype),
        "v": jnp.zeros(pool, dtype),
        "page_table": jnp.zeros((batch, max_pages), jnp.int32),
        # per-slot logical lengths (continuous batching: independent rows)
        "length": jnp.zeros((batch,), jnp.int32),
    }


def gather_paged_kv(pool_layer: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Materialise one layer's dense-view cache through the page table.

    ``pool_layer`` (n_pages, page_tokens, kv, hd) gathered by ``table``
    (b, max_pages) → (b, max_pages·page_tokens, kv, hd): positionally
    identical to the dense (b, P, kv, hd) layer cache, so the downstream
    ``decode_attention`` reduction tree — and therefore every bit of its
    output — is unchanged. Positions past a slot's logical length read
    whatever page the table maps (garbage page for unmapped tail entries);
    the validity mask zeroes their probabilities exactly."""
    b, max_pages = table.shape
    _, page_tokens, n_kv, head_dim = pool_layer.shape
    gathered = jnp.take(pool_layer, table, axis=0)  # (b, mp, pt, kv, hd)
    return gathered.reshape(b, max_pages * page_tokens, n_kv, head_dim)


def scatter_paged_kv(
    pool_layer: jnp.ndarray,  # (n_pages, page_tokens, kv, hd)
    dense_layer: jnp.ndarray,  # (b, P, kv, hd) gathered view AFTER update
    table: jnp.ndarray,  # (b, max_pages) int32
    length: jnp.ndarray,  # (b,) per-slot position the new entry was written at
) -> jnp.ndarray:
    """Write each slot's newly-decoded cache entry back into its page.

    The decode write position is ``min(length, P-1)`` — the same clamp as
    ``cache_layer_update`` — and always lands in a slot-private page (the
    partial prompt tail or a decode-grown page; full shared prefix pages
    are immutable by the pool's sharing discipline, which for exactly this
    reason keeps the final page of a ``max_seq``-length prompt private and
    unregistered: the clamp targets position ``max_seq - 1`` inside it),
    so cross-slot scatter collisions only occur on the garbage page, which
    nothing reads."""
    b, phys = dense_layer.shape[:2]
    page_tokens = pool_layer.shape[1]
    rows = jnp.arange(b)
    pos = jnp.minimum(length, phys - 1)
    page = table[rows, pos // page_tokens]
    return pool_layer.at[page, pos % page_tokens].set(dense_layer[rows, pos])


def cache_layer_update(
    layer_k: jnp.ndarray,  # (b, P, kv, hd) one layer's cache
    layer_v: jnp.ndarray,
    new_k: jnp.ndarray,  # (b, 1, kv, hd) decode step
    new_v: jnp.ndarray,
    length: jnp.ndarray,  # tokens already in cache: scalar, or (b,) per-slot
    window: Optional[int],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    phys = layer_k.shape[1]
    slot = length % phys if window else jnp.minimum(length, phys - 1)
    if length.ndim == 0:
        k = jax.lax.dynamic_update_slice(layer_k, new_k, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(layer_v, new_v, (0, slot, 0, 0))
    else:
        # continuous batching: each batch row is an independent request with
        # its own write position
        rows = jnp.arange(layer_k.shape[0])
        k = layer_k.at[rows, slot].set(new_k[:, 0])
        v = layer_v.at[rows, slot].set(new_v[:, 0])
    return k, v


@jax.named_scope("decode.attention")
def decode_attention(
    x: jnp.ndarray,  # (b, 1, d)
    params: Dict[str, jnp.ndarray],
    layer_k: jnp.ndarray,  # (b, P, kv, hd) cache AFTER update
    layer_v: jnp.ndarray,
    length: jnp.ndarray,  # logical length INCLUDING current token; () or (b,)
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float],
    window: Optional[int],
    prefix: str = "",
    project_out: bool = True,
    q: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token attention against a (possibly rotating) cache.

    ``length`` may be a scalar (whole batch at the same position) or a (b,)
    vector (continuous batching: one independent request per batch row).

    ``q``: optional precomputed q projection (b, 1, n_heads*head_dim) —
    the planned decode path computes it through the execution backend
    (kernel chunk gather / reference twin) instead of the dense matmul
    here; RoPE still applies below either way."""
    b, one, d = x.shape
    p = prefix
    phys = layer_k.shape[1]
    if q is None:
        q = x @ params[f"{p}wq"]
    q = q.reshape(b, 1, n_heads, head_dim)
    if rope_theta is not None:
        pos = jnp.broadcast_to(jnp.reshape(length - 1, (-1, 1)), (b, 1))
        q = apply_rope(q, pos, rope_theta)
    # grouped-query form: each group of n_rep query heads contracts against
    # its own KV head as the cache stores it, so K and V are read once, in
    # their own dtype; the products and sums stay float32
    n_rep = n_heads // n_kv_heads
    q = q.reshape(b, n_kv_heads, n_rep, head_dim).astype(jnp.float32)
    q = shard_act(q, ("batch", "cache_kv_heads", None, "head_dim"))
    f32_dot = dict(preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    scale = head_dim**-0.5
    scores = jnp.einsum("bgrd,bkgd->bgrk", q, layer_k, **f32_dot) * scale
    # valid slots: < length (linear) — rotation makes all slots valid once full
    slot_idx = jnp.arange(phys)[None, :]  # (1, P)
    len_col = jnp.reshape(length, (-1, 1))  # (1, 1) or (b, 1)
    valid = slot_idx < len_col
    if window:
        # rotating cache: slots hold the last min(length, phys) tokens
        valid = slot_idx < jnp.minimum(len_col, phys)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", probs, layer_v, **f32_dot).astype(x.dtype)
    out = out.reshape(b, 1, n_heads * head_dim)
    if not project_out:
        return out
    return out @ params[f"{p}wo"]


def append_attention(
    x: jnp.ndarray,  # (b, n, d) new tokens (VLM frame append: n = tokens/frame)
    params: Dict[str, jnp.ndarray],
    layer_k: jnp.ndarray,  # (b, P, kv, hd) LINEAR cache (no window rotation)
    layer_v: jnp.ndarray,
    length: jnp.ndarray,  # tokens in cache BEFORE this call
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Optional[float],
    kv_replicate: int = 1,
    prefix: str = "",
    project_out: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-token cache-extending attention (the paper's frame-append stage).

    Returns (out, new_k_cache, new_v_cache). Linear caches only.
    """
    b, n, d = x.shape
    p = prefix
    phys = layer_k.shape[1]
    positions = length[None, None] + jnp.arange(n)[None, :]  # (1, n) bcast
    positions = jnp.broadcast_to(positions.reshape(1, n), (b, n))
    q = (x @ params[f"{p}wq"]).reshape(b, n, n_heads, head_dim)
    k = (x @ params[f"{p}wk"]).reshape(b, n, n_kv_heads, head_dim)
    v = (x @ params[f"{p}wv"]).reshape(b, n, n_kv_heads, head_dim)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if kv_replicate > 1:
        k, v = repeat_kv(k, kv_replicate), repeat_kv(v, kv_replicate)
    slots = length + jnp.arange(n)
    layer_k = layer_k.at[:, slots].set(k)
    layer_v = layer_v.at[:, slots].set(v)

    n_rep = n_heads // (n_kv_heads * kv_replicate)
    kk = repeat_kv(layer_k, n_rep)
    vv = repeat_kv(layer_v, n_rep)
    scale = head_dim**-0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) * scale
    slot_idx = jnp.arange(phys)[None, :]  # key position = slot (linear cache)
    q_pos = (length + jnp.arange(n))[:, None]
    valid = slot_idx <= q_pos  # causal within the append + all history
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(b, n, n_heads * head_dim)
    if project_out:
        out = out @ params[f"{p}wo"]
    return out, layer_k, layer_v


def project_kv_for_decode(
    x: jnp.ndarray,
    params: Dict[str, jnp.ndarray],
    n_kv_heads: int,
    head_dim: int,
    length: jnp.ndarray,
    rope_theta: Optional[float],
    prefix: str = "",
    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``kv``: optional precomputed (k, v) projections (each (b, 1,
    n_kv_heads*head_dim)) from the planned decode path's execution-backend
    dispatch; RoPE on k still applies below either way."""
    b = x.shape[0]
    p = prefix
    if kv is None:
        k = x @ params[f"{p}wk"]
        v = x @ params[f"{p}wv"]
    else:
        k, v = kv
    k = k.reshape(b, 1, n_kv_heads, head_dim)
    v = v.reshape(b, 1, n_kv_heads, head_dim)
    if rope_theta is not None:
        pos = jnp.broadcast_to(jnp.reshape(length, (-1, 1)), (b, 1))
        k = apply_rope(k, pos, rope_theta)
    return k, v


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2): one cache entry per token
# ---------------------------------------------------------------------------


def latent_attention_param_defs(
    d_model: int, n_heads: int, kv_rank: int, nope: int, rope: int, v_dim: int
) -> Dict[str, ParamDef]:
    """q straight from the hidden state (no q-LoRA); ``wkv_a`` gives the
    latent and the shared rotated key, ``wkv_b`` maps the normed latent to
    each head's position-free key and value."""
    return {
        "wq": ParamDef((d_model, n_heads * (nope + rope)), ("embed", "heads")),
        "wkv_a": ParamDef((d_model, kv_rank + rope), ("embed", None)),
        "kv_norm_w": ParamDef((kv_rank,), (None,), init="ones"),
        "wkv_b": ParamDef((kv_rank, n_heads * (nope + v_dim)), (None, "heads")),
        "wo": ParamDef((n_heads * v_dim, d_model), ("heads", "embed")),
    }


def latent_query(q: jnp.ndarray, positions: jnp.ndarray, n_heads: int, nope: int,
                 rope: int, rope_theta: float) -> jnp.ndarray:
    """(b, s, H·(nope+rope)) q projection → (b, s, H, nope+rope) with the
    last ``rope`` dimensions of each head rotated."""
    b, s, _ = q.shape
    q = q.reshape(b, s, n_heads, nope + rope)
    q_rot = apply_rope(q[..., nope:], positions, rope_theta)
    return jnp.concatenate([q[..., :nope], q_rot], axis=-1)


def latent_entry(a: jnp.ndarray, positions: jnp.ndarray, norm_w: jnp.ndarray,
                 kv_rank: int, rope_theta: float, eps: float) -> jnp.ndarray:
    """The cache entry from the ``wkv_a`` projection ``a`` (b, s, R + rope):
    the RMS-normed latent beside the rotated shared key, (b, s, R + rope)."""
    from .common import rms_norm

    c = rms_norm(a[..., :kv_rank], norm_w, eps)
    k_rot = apply_rope(a[..., None, kv_rank:], positions, rope_theta)[..., 0, :]
    return jnp.concatenate([c, k_rot.astype(c.dtype)], axis=-1)


def latent_attention_expanded(q: jnp.ndarray, entries: jnp.ndarray,
                              wkv_b: jnp.ndarray, n_heads: int, nope: int,
                              v_dim: int) -> jnp.ndarray:
    """Causal self-attention in the expanded form (prefill): each head's
    key is its ``wkv_b`` key of the latent beside the shared rotated key,
    its value the ``wkv_b`` value. q (b, s, H, nope+rope), entries
    (b, s, R+rope) → (b, s, H·v_dim) in q's dtype."""
    b, s, h, qk = q.shape
    kv_rank = entries.shape[-1] - (qk - nope)
    kv = (entries[..., :kv_rank] @ wkv_b).reshape(b, s, h, nope + v_dim)
    k_rot = jnp.broadcast_to(entries[:, :, None, kv_rank:], (b, s, h, qk - nope))
    k = jnp.concatenate([kv[..., :nope], k_rot.astype(kv.dtype)], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * qk**-0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(b, s, h * v_dim)


@jax.named_scope("decode.latent_attention")
def latent_attention_absorbed(q: jnp.ndarray, cache: jnp.ndarray, valid: jnp.ndarray,
                              wkv_b: jnp.ndarray, nope: int,
                              v_dim: int) -> jnp.ndarray:
    """Attention over the latent cache alone (decode and append): the
    position-free query is taken through ``wkv_b``'s key half into latent
    space, scores are latent·latent plus rotated·rotated, and the weighted
    latent is taken out through the value half. q (b, n, H, nope+rope),
    cache (b, P, R+rope) holding this call's entries, valid (b, n, P) →
    (b, n, H·v_dim) in q's dtype. Operands bf16, accumulation f32."""
    b, n, h, qk = q.shape
    kv_rank = cache.shape[-1] - (qk - nope)
    w = wkv_b.reshape(kv_rank, h, nope + v_dim)

    def mm(spec, x, y):
        # bf16 values in f32 operands: on a TPU the default f32 matmul is the
        # same single bf16 pass; XLA:CPU has no bf16 x bf16 = f32 kernel for
        # some of these shapes
        return jnp.einsum(spec, x.astype(jnp.float32), y.astype(jnp.float32))

    q_lat = mm("bnhk,rhk->bnhr", q[..., :nope], w[..., :nope]).astype(q.dtype)
    scores = (mm("bnhr,bpr->bhnp", q_lat, cache[..., :kv_rank])
              + mm("bnhe,bpe->bhnp", q[..., nope:], cache[..., kv_rank:])) * qk**-0.5
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o_lat = mm("bhnp,bpr->bnhr", probs, cache[..., :kv_rank]).astype(q.dtype)
    out = mm("bnhr,rhv->bnhv", o_lat, w[..., nope:])
    return out.astype(q.dtype).reshape(b, n, h * v_dim)


def latent_cache_write(layer: jnp.ndarray, entries: jnp.ndarray,
                       length: jnp.ndarray) -> jnp.ndarray:
    """Write ``entries`` (b, n, R+rope) at positions ``length`` .. ``length +
    n - 1`` of one layer's latent cache (b, P, R+rope); ``length`` a scalar
    or, one token per row, (b,) (continuous batching)."""
    if length.ndim == 0:
        return jax.lax.dynamic_update_slice(layer, entries.astype(layer.dtype),
                                            (0, length, 0))
    rows = jnp.arange(layer.shape[0])
    pos = jnp.minimum(length, layer.shape[1] - 1)
    return layer.at[rows, pos].set(entries[:, 0].astype(layer.dtype))
