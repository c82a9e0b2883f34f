"""The decode execution backend: kernel-vs-reference bitwise parity.

The PR-5 tentpole invariant — `backend="kernel"` routes the planned decode
path through the Pallas DMA gather kernels, `backend="reference"` through
their pure-jnp schedule twin, and the two must be BITWISE identical (same
multiply/add sequence), making byte-identical greedy tokens the system's
strongest correctness check. Array-level parity is fast-tier; the
engine-level token identity compiles two decode scans and is fast-tier too
(the acceptance criterion must gate every push).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.kernels import (
    ExecutionBackend,
    blocked_masked_matmul,
    dequantize_rows,
    masks_to_block_tables,
    pick_tile,
    quantize_rows,
    validate_backend,
)
from repro import trace
from repro.models import build_model
from repro.models.inputs import make_dummy_batch
from repro.serving import ServeEngine, SparseExecution
from repro.serving.engine import KERNEL_TRACE_COUNTERS
from repro.serving.sparse_exec import plan_kernel_counters, reset_plan_counters


def _backends():
    return (ExecutionBackend.create("reference"),
            ExecutionBackend.create("kernel", interpret=True))


# ---------------------------------------------------------------------------
# array-level parity: project / swiglu_mlp are bitwise twins
# ---------------------------------------------------------------------------


def test_project_bitwise_parity():
    rng = np.random.default_rng(0)
    n, d, b = 64, 48, 2  # d=48 -> pick_tile falls back to 16
    w = jnp.asarray(rng.normal(0, 0.1, (n, d)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1.0, (b, n)), jnp.bfloat16)
    mask = jnp.asarray(rng.random(n) < 0.4)
    starts, sizes = masks_to_block_tables(mask[None, :])
    ref, ker = _backends()
    y_ref = ref.project(w, x, mask, starts[0], sizes[0])
    y_ker = ker.project(w, x, mask, starts[0], sizes[0])
    assert y_ref.dtype == y_ker.dtype == jnp.float32
    assert bool(jnp.all(y_ref == y_ker)), "backends must agree bitwise"
    # and both equal the exact masked matmul up to f32 accumulation noise
    dense = (x * mask.astype(x.dtype)).astype(jnp.float32) @ w.astype(jnp.float32)
    assert float(jnp.max(jnp.abs(y_ref - dense))) < 1e-5


def test_swiglu_mlp_bitwise_parity_and_h():
    rng = np.random.default_rng(1)
    n, f, d, b = 64, 96, 64, 2
    wg = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.bfloat16)
    wu = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.bfloat16)
    wd = jnp.asarray(rng.normal(0, 0.1, (f, d)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1.0, (b, n)), jnp.bfloat16)
    hidden = jnp.asarray(rng.random(n) < 0.5)
    ffn = jnp.asarray(rng.random(f) < 0.3)
    # pad the two lanes into one (2, K) table like the batched refresh does
    n_max = max(n, f)
    masks = np.zeros((2, n_max), bool)
    masks[0, :n] = np.asarray(hidden)
    masks[1, :f] = np.asarray(ffn)
    starts, sizes = masks_to_block_tables(jnp.asarray(masks))
    ref, ker = _backends()
    y_ref, h_ref = ref.swiglu_mlp(wg, wu, wd, x, hidden, ffn, starts, sizes)
    y_ker, h_ker = ker.swiglu_mlp(wg, wu, wd, x, hidden, ffn, starts, sizes)
    assert bool(jnp.all(y_ref == y_ker))
    assert bool(jnp.all(h_ref == h_ker))
    # h is the UNMASKED intermediate: rows outside the ffn mask are nonzero
    # (importance recording must see them), while y charges only masked rows
    off = ~np.asarray(ffn)
    assert float(jnp.max(jnp.abs(np.asarray(h_ref)[:, off]))) > 0.0


def _superblock_mask(n, whole, partial):
    """Whole 128-row super-blocks, and one row of each listed 8-row block
    of the partial ones."""
    mask = np.zeros(n, bool)
    for k in whole:
        mask[k * 128:(k + 1) * 128] = True
    for k, blocks in partial.items():
        for j in blocks:
            mask[k * 128 + j * 8 + 5] = True
    return mask


@pytest.mark.parametrize("depth,wbits", [(0, 16), (1, 16), (2, 16), (1, 8)])
def test_swiglu_mlp_superblock_steps_bitwise(depth, wbits):
    """The fused MLP kernel's super-block steps — whole super-blocks as one
    DMA, then a partial one fetched block by block into a slot holding an
    earlier super-block's rows — against the twin's super-block
    accumulations, through both backends: y and h bitwise."""
    rng = np.random.default_rng(7)
    n, f, d, b = 512, 384, 256, 3
    hidden = _superblock_mask(n, [0, 1, 2], {3: [1, 6, 15]})
    ffn = _superblock_mask(f, [1], {0: [4], 2: list(range(1, 16))})
    ws = [jnp.asarray(rng.normal(0, 0.1, shape), jnp.bfloat16)
          for shape in ((n, f), (n, f), (f, d))]
    scales = None
    if wbits == 8:
        ws, scales = zip(*(quantize_rows(w) for w in ws))
    x = jnp.asarray(rng.normal(0, 1.0, (b, n)), jnp.bfloat16)
    masks = np.zeros((2, n), bool)
    masks[0], masks[1, :f] = hidden, ffn
    starts, sizes = masks_to_block_tables(jnp.asarray(masks))
    ref = ExecutionBackend.create("reference")
    ker = ExecutionBackend.create("kernel", prefetch_depth=depth, interpret=True)
    args = (*ws, x, jnp.asarray(hidden), jnp.asarray(ffn), starts, sizes, scales)
    y_ref, h_ref = ref.swiglu_mlp(*args)
    y_ker, h_ker = ker.swiglu_mlp(*args)
    assert bool(jnp.all(h_ref == h_ker))
    assert bool(jnp.all(y_ref == y_ker))
    assert float(jnp.max(jnp.abs(y_ref))) > 0.0


@pytest.mark.parametrize("name", ["reference", "kernel"])
def test_backend_ops_are_fusion_boundaries(name):
    """Both paths fence their activations and results with an optimization
    barrier, so XLA compiles (and rounds) the code around the call the same
    way on either backend — a Pallas call is opaque, the twin's jnp ops
    would not be without it."""
    be = ExecutionBackend.create(name, interpret=True if name == "kernel" else None)
    n, f, b = 64, 96, 2
    w = jnp.zeros((n, f), jnp.bfloat16)
    wd = jnp.zeros((f, n), jnp.bfloat16)
    x = jnp.zeros((b, n), jnp.bfloat16)
    hm, fm = jnp.ones((n,), bool), jnp.ones((f,), bool)
    st, sz = masks_to_block_tables(jnp.ones((2, f), bool))
    s0, z0 = st[0], sz[0]
    proj = jax.make_jaxpr(lambda x: be.project(w, x, hm, s0, z0))(x)
    mlp = jax.make_jaxpr(lambda x: be.swiglu_mlp(w, w, wd, x, hm, fm, st, sz))(x)
    for jaxpr in (proj, mlp):
        eqns = jaxpr.jaxpr.eqns
        assert eqns[0].primitive.name == "optimization_barrier"
        assert eqns[-1].primitive.name == "optimization_barrier"


@pytest.mark.parametrize("wdtype", ["bf16", "int8"])
def test_blocked_matmul_keeps_f32_accuracy_on_bf16_storage(wdtype):
    """An f32 input against bf16/int8 storage (the down projection's h)
    keeps f32 accuracy, far inside one bf16 rounding of the input (~4e-3
    relative), against an f64 product: the twin contracts at full f32
    precision, not in one bf16 pass."""
    rng = np.random.default_rng(6)
    n, d = 256, 128
    wf = rng.normal(0, 0.05, (n, d)).astype(np.float32)
    if wdtype == "int8":
        w, s = quantize_rows(jnp.asarray(wf), 8)
        exact = np.asarray(dequantize_rows(w, s), np.float64)
    else:
        w, s = jnp.asarray(wf, jnp.bfloat16), None
        exact = np.asarray(w.astype(jnp.float32), np.float64)
    x = rng.normal(0, 1, (2, n)).astype(np.float32)
    y = np.asarray(blocked_masked_matmul(jnp.asarray(x), w, 8, s), np.float64)
    ref = x.astype(np.float64) @ exact
    assert np.max(np.abs(y - ref)) / np.max(np.abs(ref)) < 1e-5


def test_blocked_matmul_is_exact_masked_semantics():
    rng = np.random.default_rng(2)
    n, d = 32, 16
    w = jnp.asarray(rng.normal(0, 0.1, (n, d)), jnp.float32)
    xm = jnp.asarray(rng.normal(0, 1.0, (2, n)), jnp.float32)
    y = blocked_masked_matmul(xm, w)
    assert np.allclose(np.asarray(y), np.asarray(xm) @ np.asarray(w), atol=1e-5)


# ---------------------------------------------------------------------------
# quantized chunk storage (PR 6): the same bitwise-twin property at 8 bits
# ---------------------------------------------------------------------------


def test_project_quantized_bitwise_parity():
    """int8 payload + per-block scale lane through both backends: still
    bitwise twins (the kernel's in-VMEM dequant multiply is elementwise the
    reference twin's per-block multiply), and within half a quantization
    step of the dequantized dense matmul."""
    rng = np.random.default_rng(5)
    n, d, b = 64, 48, 2
    w = jnp.asarray(rng.normal(0, 0.1, (n, d)), jnp.bfloat16)
    q, s = quantize_rows(w)
    x = jnp.asarray(rng.normal(0, 1.0, (b, n)), jnp.bfloat16)
    mask = jnp.asarray(rng.random(n) < 0.4)
    starts, sizes = masks_to_block_tables(mask[None, :])
    ref, ker = _backends()
    y_ref = ref.project(q, x, mask, starts[0], sizes[0], s)
    y_ker = ker.project(q, x, mask, starts[0], sizes[0], s)
    assert y_ref.dtype == y_ker.dtype == jnp.float32
    assert bool(jnp.all(y_ref == y_ker)), "quantized backends must agree bitwise"
    dense = (x * mask.astype(x.dtype)).astype(jnp.float32) @ dequantize_rows(q, s)
    assert float(jnp.max(jnp.abs(y_ref - dense))) < 1e-4


def test_swiglu_mlp_quantized_bitwise_parity():
    rng = np.random.default_rng(6)
    n, f, d, b = 64, 96, 64, 2
    wg = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.bfloat16)
    wu = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.bfloat16)
    wd = jnp.asarray(rng.normal(0, 0.1, (f, d)), jnp.bfloat16)
    qg, sg = quantize_rows(wg)
    qu, su = quantize_rows(wu)
    qd, sd = quantize_rows(wd)
    x = jnp.asarray(rng.normal(0, 1.0, (b, n)), jnp.bfloat16)
    hidden = jnp.asarray(rng.random(n) < 0.5)
    ffn = jnp.asarray(rng.random(f) < 0.3)
    n_max = max(n, f)
    masks = np.zeros((2, n_max), bool)
    masks[0, :n] = np.asarray(hidden)
    masks[1, :f] = np.asarray(ffn)
    starts, sizes = masks_to_block_tables(jnp.asarray(masks))
    ref, ker = _backends()
    y_ref, h_ref = ref.swiglu_mlp(qg, qu, qd, x, hidden, ffn, starts, sizes,
                                  scales=(sg, su, sd))
    y_ker, h_ker = ker.swiglu_mlp(qg, qu, qd, x, hidden, ffn, starts, sizes,
                                  scales=(sg, su, sd))
    assert bool(jnp.all(y_ref == y_ker))
    assert bool(jnp.all(h_ref == h_ker))
    # h (the importance-recording intermediate) stays unmasked at 8 bits too
    off = ~np.asarray(ffn)
    assert float(jnp.max(jnp.abs(np.asarray(h_ref)[:, off]))) > 0.0


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def test_backend_validation():
    with pytest.raises(ValueError, match="unknown execution backend"):
        validate_backend("cuda")
    with pytest.raises(ValueError, match="unknown execution backend"):
        ExecutionBackend.create("triton")
    with pytest.raises(ValueError, match="prefetch_depth"):
        ExecutionBackend.create("kernel", prefetch_depth=-1)
    assert pick_tile(704) == 64 and pick_tile(896) == 128 and pick_tile(48) == 16
    with pytest.raises(ValueError, match="tile divisor"):
        pick_tile(12)


def test_kernel_backend_rejects_reorderings():
    from repro.core import hot_cold_reordering

    cfg = get_config("internvl2-76b").reduced()
    cal = np.random.default_rng(0).random((8, cfg.d_model)).astype(np.float32)
    reo = {"hidden_attn": hot_cold_reordering(cal)}
    SparseExecution(cfg, reorderings=reo)  # reference backend: fine
    with pytest.raises(ValueError, match="reorderings"):
        SparseExecution(cfg, reorderings=reo, backend="kernel")


def test_engine_validates_backend():
    cfg = get_config("internvl2-76b").reduced()
    model = build_model(cfg)
    with pytest.raises(ValueError, match="unknown execution backend"):
        ServeEngine(model, None, max_seq=32, batch_size=1, backend="nope")


def test_wbits_validation():
    cfg = get_config("internvl2-76b").reduced()
    with pytest.raises(ValueError, match="wbits"):
        SparseExecution(cfg, wbits=4)
    model = build_model(cfg)
    with pytest.raises(ValueError, match="wbits"):
        ServeEngine(model, None, max_seq=32, batch_size=1, wbits=4)


# ---------------------------------------------------------------------------
# engine-level: byte-identical greedy tokens (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vlm():
    cfg = get_config("internvl2-76b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_dummy_batch(cfg, InputShape("bk", 16, 2, "train"))
    return cfg, model, params, batch


def _decode(model, params, batch, backend, n=6, **kw):
    eng = ServeEngine(model, params, max_seq=64, batch_size=2, device="nano",
                      sparsity=0.4, method="chunk", seed=3, backend=backend,
                      **kw)
    eng.simulator.noise = 0.0
    tok0 = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
    out = eng.decode(tok0, n)
    return eng, out


@pytest.fixture(scope="module")
def decoded(vlm):
    """Each backend's engine and tokens after one decode, and what its
    decode added to the trace's kernel DMA counters."""
    cfg, model, params, batch = vlm
    runs = {}
    for backend in ("reference", "kernel"):
        before = trace.counters()
        eng, out = _decode(model, params, batch, backend)
        after = trace.counters()
        runs[backend] = (eng, out, [after.get(k, 0) - before.get(k, 0)
                                    for k in KERNEL_TRACE_COUNTERS])
    return runs


def test_decode_tokens_byte_identical_across_backends(decoded):
    eng_r, out_r, _ = decoded["reference"]
    eng_k, out_k, _ = decoded["kernel"]
    assert bool(jnp.all(out_r == out_k)), (
        "kernel-backend decode diverged from the reference backend"
    )
    # the backend changes HOW the arithmetic runs, never the selection —
    # so the I/O accounting must agree exactly too
    sr, sk = eng_r.io_summary(), eng_k.io_summary()
    assert sr["io_est_s"] == pytest.approx(sk["io_est_s"], rel=0, abs=0)
    assert sr["miss_rows"] == sk["miss_rows"]


def test_kernel_dma_counters_reach_the_trace(decoded):
    """The kernel backend's plan counts what the gather kernels' DMA plans
    move — super-blocks whole, super-blocks by blocks, block DMAs — and the
    decode call's one read-back adds exactly the plan's totals to the
    trace; the reference backend's plan carries no such lanes."""
    eng_k, _, counted = decoded["kernel"]
    eng_r, _, counted_ref = decoded["reference"]
    assert counted_ref == [0, 0, 0]
    assert float(jnp.sum(plan_kernel_counters(eng_r._plan))) == 0.0
    whole, partial, dmas = counted
    assert whole + partial > 0
    assert partial <= dmas  # each partial super-block takes 1+ block DMAs
    np.testing.assert_array_equal(np.asarray(plan_kernel_counters(eng_k._plan)),
                                  np.asarray(counted, np.float32))
    zeroed = reset_plan_counters(eng_k._plan)
    assert float(jnp.sum(plan_kernel_counters(zeroed))) == 0.0


def test_decode_tokens_byte_identical_at_wbits8(vlm):
    """The PR-6 acceptance criterion: greedy decode at --wbits 8 (int8
    chunk payloads dequantized in-kernel) stays byte-identical between the
    kernel backend and the reference twin, and the quantized run's total
    modeled I/O bytes land strictly below the fp16 run's."""
    cfg, model, params, batch = vlm
    eng_r, out_r = _decode(model, params, batch, "reference", wbits=8)
    eng_k, out_k = _decode(model, params, batch, "kernel", wbits=8)
    assert bool(jnp.all(out_r == out_k)), (
        "wbits=8 kernel-backend decode diverged from the reference backend"
    )
    sr, sk = eng_r.io_summary(), eng_k.io_summary()
    assert sr["io_bytes"] == sk["io_bytes"]  # selection unchanged by backend
    eng16, _ = _decode(model, params, batch, "reference")
    assert sr["io_bytes"] < eng16.io_summary()["io_bytes"], (
        "int8 chunk storage must move strictly fewer modeled bytes than fp16"
    )


@pytest.mark.slow
def test_decode_backend_parity_with_cache_and_reuse(vlm):
    """Residency cache + plan reuse ride the same plan carry the kernels
    consume — parity must survive both."""
    cfg, model, params, batch = vlm
    kw = dict(cache_mb=4.0, plan_refresh_interval=2)
    _, out_r = _decode(model, params, batch, "reference", **kw)
    _, out_k = _decode(model, params, batch, "kernel", **kw)
    assert bool(jnp.all(out_r == out_k))


@pytest.mark.slow
def test_decode_backend_parity_gelu_mlp():
    """The non-gated (c_fc/c_proj) MLP routes through two single-site
    backend projections — parity on a gelu-family arch."""
    cfg = get_config("starcoder2-3b").reduced()
    assert cfg.mlp == "gelu"
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    batch = make_dummy_batch(cfg, InputShape("bg", 16, 2, "train"))
    _, out_r = _decode(model, params, batch, "reference", n=4)
    _, out_k = _decode(model, params, batch, "kernel", n=4)
    assert bool(jnp.all(out_r == out_k))


@pytest.mark.slow
def test_backend_is_depth_invariant(vlm):
    """prefetch_depth only re-times fetches; kernel-backend tokens are
    byte-identical across depths 0 and 2."""
    cfg, model, params, batch = vlm
    outs = [
        _decode(model, params, batch, "kernel", prefetch_depth=depth)[1]
        for depth in (0, 2)
    ]
    assert bool(jnp.all(outs[0] == outs[1]))
