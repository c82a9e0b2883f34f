"""Attention mechanics: blockwise==direct, GQA, sliding window, append, RoPE,
and grouped-query decode against the repeat-then-float32 form."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    NEG_INF,
    _blockwise_attention,
    _direct_attention,
    append_attention,
    decode_attention,
    multi_head_attention,
    repeat_kv,
)
from repro.models.common import apply_rope, causal_mask


def _qkv(rng, b=2, s=96, h=4, hd=16, skv=None):
    skv = skv or s
    q = jnp.asarray(rng.normal(0, 1, (b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (b, skv, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, skv, h, hd)), jnp.float32)
    return q, k, v


def test_blockwise_equals_direct_causal(rng):
    q, k, v = _qkv(rng)
    s = q.shape[1]
    mask = causal_mask(s, s, 0)
    direct = _direct_attention(q, k, v, mask)
    block = _blockwise_attention(q, k, v, jnp.int32(0), True, None,
                                 block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(block), np.asarray(direct), atol=2e-5)


def test_blockwise_equals_direct_window(rng):
    q, k, v = _qkv(rng)
    s, w = q.shape[1], 24
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(s)[None, :]
    mask = (kj <= qi) & (kj > qi - w)
    direct = _direct_attention(q, k, v, mask)
    block = _blockwise_attention(q, k, v, jnp.int32(0), True, w,
                                 block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(block), np.asarray(direct), atol=2e-5)


def test_blockwise_ragged_lengths(rng):
    """Non-multiple-of-block seq lengths must pad correctly."""
    q, k, v = _qkv(rng, s=70)
    mask = causal_mask(70, 70, 0)
    direct = _direct_attention(q, k, v, mask)
    block = _blockwise_attention(q, k, v, jnp.int32(0), True, None,
                                 block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(block), np.asarray(direct), atol=2e-5)


def test_repeat_kv(rng):
    x = jnp.asarray(rng.normal(0, 1, (2, 5, 2, 4)), jnp.float32)
    r = repeat_kv(x, 3)
    assert r.shape == (2, 5, 6, 4)
    np.testing.assert_array_equal(np.asarray(r[:, :, 0]), np.asarray(r[:, :, 1]))
    np.testing.assert_array_equal(np.asarray(r[:, :, 0]), np.asarray(x[:, :, 0]))


def test_rope_relative_property(rng):
    """RoPE: q·k depends only on relative position."""
    hd = 32
    q = jnp.asarray(rng.normal(0, 1, (1, 1, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 1, 1, hd)), jnp.float32)

    def dot_at(pq, pk):
        qr = apply_rope(q, jnp.array([[pq]]), 10000.0)
        kr = apply_rope(k, jnp.array([[pk]]), 10000.0)
        return float(jnp.sum(qr * kr))

    assert dot_at(3, 1) == pytest.approx(dot_at(10, 8), abs=1e-4)
    assert dot_at(5, 5) == pytest.approx(dot_at(0, 0), abs=1e-4)


def test_append_attention_matches_fused_prefill(rng):
    """Appending n tokens against a prefilled cache == full causal attention
    over the concatenated sequence (positions included)."""
    b, s0, n, h, hd, d = 2, 10, 6, 2, 8, 16
    params = {
        "wq": jnp.asarray(rng.normal(0, 0.1, (d, h * hd)), jnp.float32),
        "wk": jnp.asarray(rng.normal(0, 0.1, (d, h * hd)), jnp.float32),
        "wv": jnp.asarray(rng.normal(0, 0.1, (d, h * hd)), jnp.float32),
        "wo": jnp.asarray(rng.normal(0, 0.1, (h * hd, d)), jnp.float32),
    }
    x_full = jnp.asarray(rng.normal(0, 1, (b, s0 + n, d)), jnp.float32)
    full = multi_head_attention(x_full, params, h, h, hd, rope_theta=10000.0)

    # prefill cache with first s0 tokens manually
    from repro.models.common import apply_rope as rope

    pos0 = jnp.broadcast_to(jnp.arange(s0)[None], (b, s0))
    k0 = rope((x_full[:, :s0] @ params["wk"]).reshape(b, s0, h, hd), pos0, 10000.0)
    v0 = (x_full[:, :s0] @ params["wv"]).reshape(b, s0, h, hd)
    phys = s0 + n
    ck = jnp.zeros((b, phys, h, hd)).at[:, :s0].set(k0)
    cv = jnp.zeros((b, phys, h, hd)).at[:, :s0].set(v0)
    out, ck, cv = append_attention(
        x_full[:, s0:], params, ck, cv, jnp.int32(s0), h, h, hd, 10000.0
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full[:, s0:]), atol=1e-4, rtol=1e-4
    )


def _repeat_then_f32_decode(x, params, layer_k, layer_v, length, n_heads,
                            n_kv_heads, head_dim, rope_theta, window,
                            project_out):
    """Decode attention as it was written before the grouped form: every KV
    head repeated to the query heads and the whole cache upcast to float32."""
    b = x.shape[0]
    phys = layer_k.shape[1]
    q = (x @ params["wq"]).reshape(b, 1, n_heads, head_dim)
    pos = jnp.broadcast_to(jnp.reshape(length - 1, (-1, 1)), (b, 1))
    q = apply_rope(q, pos, rope_theta)
    n_rep = n_heads // n_kv_heads
    k = repeat_kv(layer_k, n_rep)
    v = repeat_kv(layer_v, n_rep)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * head_dim**-0.5
    slot_idx = jnp.arange(phys)[None, :]
    len_col = jnp.reshape(length, (-1, 1))
    valid = slot_idx < (jnp.minimum(len_col, phys) if window else len_col)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(b, 1, n_heads * head_dim)
    return out @ params["wo"] if project_out else out


# (n_rep, kv_replicate): query heads per stored KV head, and the cache's
# replication of the model's KV heads (the sharded cache's layout)
_GROUPINGS = [(1, 1), (4, 1), (9, 1), (4, 2)]


@pytest.mark.parametrize("project_out", [True, False], ids=["wo", "raw"])
@pytest.mark.parametrize("lengths", ["scalar", "per_slot", "window"])
@pytest.mark.parametrize("n_rep,kv_replicate", _GROUPINGS,
                         ids=[f"rep{r}-kvx{k}" for r, k in _GROUPINGS])
def test_grouped_decode_attention_equals_repeat_then_f32(
        rng, n_rep, kv_replicate, lengths, project_out):
    """Each query-head group contracted against its own stored KV head gives
    the repeated float32 form: to float32 roundoff before the output cast,
    and the same bf16 outputs in almost every element after it."""
    b, phys, n_kv, hd = 3, 40, 2, 16
    n_heads = n_kv * n_rep
    d = n_heads * hd
    cache_kv = n_kv * kv_replicate
    params = {
        "wq": jnp.asarray(rng.normal(0, d**-0.5, (d, d)), jnp.float32),
        "wo": jnp.asarray(rng.normal(0, d**-0.5, (d, d)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(0, 1, (b, 1, d)), jnp.float32)
    k, v = (repeat_kv(jnp.asarray(rng.normal(0, 1, (b, phys, n_kv, hd)),
                                  jnp.bfloat16), kv_replicate)
            for _ in range(2))
    window = phys if lengths == "window" else None
    length = {"scalar": jnp.int32(29),
              "per_slot": jnp.asarray([1, 17, phys], jnp.int32),
              "window": jnp.asarray([5, phys + 7, 3 * phys], jnp.int32)}[lengths]
    args = (length, n_heads, cache_kv, hd, 10000.0, window)

    def both(x, params):
        new = decode_attention(x, params, k, v, *args, project_out=project_out)
        old = _repeat_then_f32_decode(x, params, k, v, *args, project_out)
        return np.asarray(new.astype(jnp.float32)), np.asarray(old.astype(jnp.float32))

    new, old = both(x, params)  # float32 activations: no output rounding
    np.testing.assert_allclose(new, old, rtol=2e-6, atol=2e-6)

    as_bf16 = {n: w.astype(jnp.bfloat16) for n, w in params.items()}
    new, old = both(x.astype(jnp.bfloat16), as_bf16)
    equal = np.mean(new == old)
    assert equal >= 0.99, f"only {equal:.4f} of the bf16 outputs agree"
    np.testing.assert_allclose(new, old, rtol=2**-7, atol=1e-6)
