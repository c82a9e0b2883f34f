"""Kimi-VL-A3B's language model (``configs/kimi_vl_a3b.py``) at a toy size
on the CPU: latent attention, two leading dense layers, then layers of 8
held experts behind a 64-wide noaux_tc router with shared experts, checked
against the plain float32 reference ``bench/reference/latent_moe.py`` on
seeded weights from ``bench/weights.py``. The serving path (prefill, the
latent cache, the planned sparse decode with the expert site) and the
expert gather kernel run as they do on the chip, the kernel interpreted."""
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import faults, harness, spec
from bench.weights import served_params
from repro.kernels.backend import ExecutionBackend
from repro.kernels.chunk_gather_dma import masks_to_block_tables
from repro.models import build_model
from repro.models.attention import latent_attention_absorbed, latent_attention_expanded
from repro.models.moe import held_expert_ffn, route_sigmoid_bias

REF = spec.load_module(spec.BENCH_DIR / "reference" / "latent_moe.py")
# Hugging Face keys of a toy configuration; the cut keys under ``reduced``
TOY = {"arch": "kimi-vl-a3b", "reference": "latent_moe", "hidden_size": 128,
       "intermediate_size": 256, "moe_intermediate_size": 128, "num_hidden_layers": 4,
       "first_k_dense_replace": 2, "num_attention_heads": 4, "kv_lora_rank": 64,
       "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
       "n_routed_experts": 8, "num_experts_per_tok": 6, "n_shared_experts": 2,
       "routed_scaling_factor": 2.446, "vocab_size": 512, "rope_theta": 10000.0,
       "rms_norm_eps": 1e-05, "tie_word_embeddings": False, "q_lora_rank": None,
       "n_group": 1, "scoring_func": "sigmoid",
       "reduced": ["hidden_size", "intermediate_size", "moe_intermediate_size",
                   "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
                   "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                   "n_routed_experts", "vocab_size", "rope_theta"],
       "published": {"n_routed_experts": 64}}
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def toy():
    from bench.serve import program_config

    dm = REF.dims(TOY)
    cfg = program_config(TOY, REF)
    return dm, cfg, build_model(cfg), served_params(REF.leaves, dm, SEED)


def test_the_full_configuration_matches_the_reference_layout():
    """The benchmark's configuration file, its key map and the program's
    parameter tree agree at the published widths (shapes only)."""
    from bench.serve import program_config

    cfg = json.loads((spec.BENCH_DIR / "configs" / "kimi-vl-a3b-e8.json").read_text())
    pcfg = program_config(cfg, REF)
    assert (pcfg.n_experts, pcfg.router_width, pcfg.moe_top_k) == (8, 64, 6)
    dm = REF.dims(cfg)
    want = jax.eval_shape(build_model(pcfg).init, jax.random.key(0))
    have = jax.eval_shape(lambda: served_params(REF.leaves, dm, 0))
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(want))
    assert 3.36e9 < n < 3.37e9


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(toy):
    dm, cfg, model, params = toy
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, dm.vocab, (1, 12)).astype(np.int32)
    p = 8
    last, cache = model.prefill(params, {"tokens": jnp.asarray(tokens[:, :p])}, 16)
    got = [np.asarray(last, np.float32)]
    for t in range(p, 12):
        logits, cache, _ = model.decode_step(params, jnp.asarray(tokens[:, t:t + 1]), cache)
        got.append(np.asarray(logits, np.float32))
    want, _ = REF.forward(dm, SEED, tokens, list(range(p - 1, 12)))
    want = np.asarray(want)[0]
    got = np.concatenate(got)
    hidden, _ = model.forward(params, {"tokens": jnp.asarray(tokens)}, remat=False)
    full = np.asarray(model.logits(params, hidden), np.float32)[0, p - 1:]
    # the logits spread about 0.23. Decode through the latent cache (absorbed)
    # and the prefill's expanded form round differently in bfloat16: 0.010 at
    # most over seeds 11-14 on the CPU
    assert np.abs(got - full).max() < 0.02
    # against float32, bfloat16 weights and activations read 0.006-0.013 a
    # position over seeds 11-14; a router choice flipped by rounding (one
    # token of seed 11) moves a position by 0.1, so one may exceed 0.02. A
    # wrong rotation, scale, routing weight or cache row moves every position
    # by tenths
    per_pos = np.abs(got - want).max(axis=1)
    assert np.sum(per_pos > 0.02) <= 1 and per_pos.max() < 0.15, per_pos


def test_frame_append_through_the_latent_cache_matches_the_whole_prefill(toy):
    """Appending embedded tokens to a prefilled cache (the frame-append
    stage) gives the hidden state a prefill of the whole sequence gives."""
    dm, cfg, model, params = toy
    tokens = jnp.asarray(np.random.default_rng(8).integers(0, dm.vocab, (1, 12)), jnp.int32)
    _, cache = model.prefill(params, {"tokens": tokens[:, :8]}, 16)
    frame = jnp.take(params["embed"], tokens[:, 8:], axis=0)
    hidden, cache, _ = model.append_frame(params, frame, cache)
    full, _ = model.forward(params, {"tokens": tokens}, remat=False)
    assert int(cache["length"]) == 12
    # the absorbed and expanded forms round differently in bfloat16: the
    # final hidden state (spread 1.0) reads 0.031 apart over seeds 11, 12 and
    # SEED, one bfloat16 step at its largest entries
    np.testing.assert_allclose(np.asarray(hidden[:, -1], np.float32),
                               np.asarray(full[:, -1], np.float32), atol=0.06)


def test_absorbed_decode_attention_equals_the_expanded_form():
    rng = np.random.default_rng(2)
    b, s, h, nope, rope, v, r = 2, 10, 4, 32, 16, 32, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, nope + rope)), jnp.float32)
    ent = jnp.asarray(rng.normal(size=(b, s, r + rope)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(r, h * (nope + v))) / 8, jnp.float32)
    with jax.default_matmul_precision("highest"):
        full = latent_attention_expanded(q, ent, w, h, nope, v)
        valid = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool))[None], (b, s, s))
        absorbed = latent_attention_absorbed(q, ent, valid, w, nope, v)
    # the same sums regrouped (q·W_k·c in place of q·(c·W_k)) in float32
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(full), rtol=1e-4,
                               atol=1e-4)


def test_noaux_tc_routing_by_hand():
    # experts 0-3 of a 4-wide router, top 2; sigmoid(logits) = .8 .6 .5 .2
    s = np.array([0.8, 0.6, 0.5, 0.2])
    logits = jnp.asarray(np.log(s / (1 - s)), jnp.float32)[None]
    idx, w = route_sigmoid_bias(logits, jnp.zeros(4), 2, 2.0)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(np.asarray(w), [[0.8 / 1.4 * 2, 0.6 / 1.4 * 2]], rtol=1e-6)
    # a bias of .4 on expert 3 chooses it over expert 1 (.2 + .4 > .6 is
    # false, .6 > .6 is not: take .5) -- its weight is its unbiased score
    idx, w = route_sigmoid_bias(logits, jnp.asarray([0.0, 0.0, 0.0, 0.5]), 2, 2.0)
    assert sorted(idx[0].tolist()) == [0, 3]
    got = dict(zip(idx[0].tolist(), np.asarray(w)[0].tolist()))
    np.testing.assert_allclose([got[0], got[3]], [0.8 / 1.0 * 2, 0.2 / 1.0 * 2], rtol=1e-6)


def test_eight_shards_add_up_to_the_uncut_layer():
    """A 64-expert layer cut into 8 shards of 8: each routes over all 64 and
    computes its own experts' part; with the shared experts added by one
    shard, the parts sum to the uncut layer."""
    rng = np.random.default_rng(3)
    d, f, e = 32, 16, 64

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)

    full = {"router": w(d, e), "router_bias": jnp.asarray(rng.normal(size=e) * 0.1,
                                                          jnp.float32),
            "we_gate": w(e, d, f), "we_up": w(e, d, f), "we_down": w(e, f, d),
            "ws_gate": w(d, 2 * f), "ws_up": w(d, 2 * f), "ws_down": w(2 * f, d)}
    x = jnp.asarray(rng.normal(size=(2, 5, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = held_expert_ffn(x, full, 6, 2.446)
        parts = []
        for k in range(8):
            shard = dict(full, **{n: full[n][8 * k: 8 * k + 8]
                                  for n in ("we_gate", "we_up", "we_down")})
            parts.append(held_expert_ffn(x, shard, 6, 2.446, first=8 * k, shared=k == 0))
    # the same float32 terms summed in another order
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("layer,ids,count", [(1, [5, 2, 0, 1, 3, 4, 6, 7], 3),
                                             (0, [7, 0, 1, 2, 3, 4, 5, 6], 1)])
def test_expert_kernel_addresses_the_stack_in_place(layer, ids, count):
    """The interpreted kernel equals its twin bit for bit, and both equal
    the routed experts of the given layer computed plainly."""
    rng = np.random.default_rng(4)
    L, e, d, f, b = 3, 8, 128, 128, 4

    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.bfloat16)

    wg, wu, wd = w(L, e, d, f), w(L, e, d, f), w(L, e, f, d)
    x = jnp.asarray(rng.normal(size=(b, d)), jnp.bfloat16)
    lid, ids_a, n = jnp.int32(layer), jnp.asarray(ids, jnp.int32), jnp.int32(count)
    kern, twin = ExecutionBackend.create("kernel"), ExecutionBackend.create("reference")
    act = kern.expert_up(wg, wu, lid, ids_a, n, x)
    np.testing.assert_array_equal(np.asarray(act), np.asarray(twin.expert_up(
        wg, wu, lid, ids_a, n, x)))
    xf = x.astype(jnp.float32)
    for k in range(e):
        cols = np.asarray(act)[:, k * f:(k + 1) * f]
        if k in ids[:count]:
            g = xf @ wg[layer, k].astype(jnp.float32)
            u = xf @ wu[layer, k].astype(jnp.float32)
            # the bf16 products are exact in f32; only the sums' order differs
            np.testing.assert_allclose(cols, np.asarray(jax.nn.silu(g) * u), rtol=1e-4,
                                       atol=1e-4)
        else:
            assert not cols.any()
    mask = jnp.asarray(rng.random(e * f) < 0.5) & jnp.repeat(
        jnp.isin(jnp.arange(e), jnp.asarray(ids[:count])), f)
    starts, sizes = masks_to_block_tables(mask[None], 8, 512)
    y = kern.expert_down(wd, lid, act, mask, starts[0], sizes[0])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(twin.expert_down(
        wd, lid, act, mask, starts[0], sizes[0])))
    plain = ((act * mask).astype(jnp.bfloat16).astype(jnp.float32)
             @ wd[layer].reshape(e * f, d).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(plain), rtol=1e-4, atol=1e-4)


def test_expected_mask_keeps_every_routed_expert():
    dm = REF.dims(TOY)
    rng = np.random.default_rng(5)
    imp = rng.random(dm.experts * dm.fe).astype(np.float32)
    routed = np.array([1, 0, 1, 1, 0, 0, 1, 0], bool)
    imp[~np.repeat(routed, dm.fe)] = 0.0
    budget = round(0.6 * dm.experts * dm.fe)
    m = REF.expected_mask(dm, "expert_ffn", imp, budget).reshape(dm.experts, dm.fe)
    # every routed expert runs on some of its rows, none of them whole; an
    # unrouted expert's rows are marked kept (nothing reads them)
    assert ((m.sum(1) > 0) & (m.sum(1) < dm.fe)).tolist() == routed.tolist()
    assert m[~routed].all()
    assert m[routed].sum() == budget * routed.sum() // dm.experts
    # the kept rows of the routed experts are their most important
    kept = imp.reshape(m.shape)[m & routed[:, None]]
    dropped = imp.reshape(m.shape)[~m & routed[:, None]]
    assert kept.min() >= dropped.max()


def test_the_programs_expert_mask_is_the_references_choice(toy):
    """``SparseExecution.run_experts`` selects, from the importance it
    records, exactly the rows the reference's ``expected_mask`` expects."""
    from repro.serving.sparse_exec import SparseExecution

    dm, cfg, model, params = toy
    sp = SparseExecution(cfg, sparsity=0.4, method="topk", backend="reference")
    plan = sp.init_plan(cfg.n_layers)
    moe = jax.tree.map(lambda a: a[0], plan["expert_ffn"])
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(4, dm.d)), jnp.bfloat16)
    gates = np.zeros((4, dm.experts), np.float32)
    gates[0, 1], gates[1, 1], gates[2, 4], gates[3, 6] = 0.5, 1.0, 0.7, 0.2
    stacks = {k: params["moe"][k] for k in ("we_gate", "we_up", "we_down")}
    y, new, _ = sp.run_experts({"expert_ffn": moe}, h, jnp.asarray(gates), stacks,
                               jnp.int32(1))
    act = ExecutionBackend.create("reference").expert_up(
        stacks["we_gate"], stacks["we_up"], jnp.int32(1), jnp.arange(8), jnp.int32(8), h)
    mid = np.asarray(act).reshape(4, dm.experts, dm.fe) * gates[:, :, None]
    imp = np.abs(mid.reshape(4, -1)).mean(0)
    want = REF.expected_mask(dm, "expert_ffn", imp, round(0.6 * dm.experts * dm.fe))
    got = np.asarray(new["expert_ffn"]["mask"]) > 0
    assert got.tolist() == want.tolist()
    counts = [float(new["expert_ffn"][k]) for k in ("experts", "pairs", "steps")]
    assert counts == [3.0, 4.0, 1.0]


def test_kernel_and_reference_backends_serve_the_same_tokens(toy):
    from repro.serving import Request, Scheduler, ServeEngine

    dm, cfg, model, params = toy
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, dm.vocab, 12) for _ in range(3)]
    out = {}
    for backend in ("reference", "kernel"):
        eng = ServeEngine(model, params, max_seq=20, batch_size=3, sparsity=0.4,
                          method="topk", backend=backend, cache_mb=0)
        sched = Scheduler(eng, round_tokens=1)
        reqs = [Request(rid=i, prompt={"tokens": jnp.asarray(p[None])}, max_new_tokens=4,
                        arrival_s=0.0) for i, p in enumerate(prompts)]
        for r in reqs:
            sched.submit(r)
        while not all(r.state.value == "finished" for r in reqs):
            sched.step()
        out[backend] = [list(r.tokens_out) for r in reqs]
        assert {k: v["mask"].shape for k, v in eng._plan.items()} == {
            "hidden_attn": (4, 128), "attn_out": (4, 128), "hidden_mlp": (2, 128),
            "ffn": (2, 256), "expert_ffn": (2, 1024)}
    assert out["reference"] == out["kernel"]


# the correctness check of the benchmark, through a whole run at toy size.
# Toy limits from toy readings on the CPU (seeds 3, 2**33 + 5, 2**34 + 9):
# sound runs read logit_gap <= 0.0005, kv_rel_err 0.011-0.021, kv0_rel_err
# <= 0.0029, mask_mismatch 0.003-0.007; the float8 reference reads logit_gap
# >= 0.059, kv_rel_err >= 0.12, kv0_rel_err >= 0.041; a stale latent cache
# kv0_rel_err 1.0; half the batch left out of the importance mask_mismatch
# >= 0.127
BATCH = {"kind": "closed", "engine": {"method": "topk", "sparsity": 0.4,
         "backend": "reference", "wbits": 16, "cache_mb": 0, "round_tokens": 1,
         "slots": 4, "max_seq": 24, "plan_refresh_interval": 1},
         "clients": 4, "prompt": {"frame_tokens": 8, "text_tokens": 8, "frames": 1},
         "output_tokens": 8}
LIMITS = {"logit_gap": 0.03, "kv_rel_err": 0.06, "kv0_rel_err": 0.015,
          "mask_mismatch": 0.04}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("kimi_check")
    bench = root / "bench"
    for sub in ("reference", "metrics"):
        shutil.copytree(spec.BENCH_DIR / sub, bench / sub)
    (bench / "traffic").mkdir()
    shutil.copy(spec.BENCH_DIR / "traffic" / "closed.py", bench / "traffic")
    for sub in ("configs", "limits"):
        (bench / sub).mkdir()
    (bench / "configs" / "toy.json").write_text(json.dumps(TOY))
    (bench / "traffic" / "batch.json").write_text(json.dumps(BATCH))
    (bench / "limits" / "toy.batch.json").write_text(json.dumps(LIMITS))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.batch", "config": "toy", "traffic": "batch",
                       "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return root


def _run(root, **kw):
    cell = spec.load_cell("toy.batch", repo_root=root, bench_dir=root / "bench")
    return harness.run_cell(cell, SEED, 1.5, False, time.perf_counter(), **kw)


@pytest.mark.parametrize("fault", [None, "stale_state", "fp8", "half_batch"])
def test_the_benchmark_check_passes_a_sound_run_and_catches_faults(root, fault):
    if fault == "fp8":
        r = _run(root, control="fp8")
    elif fault == "stale_state":
        r = _run(root, hooks=faults.stale_state())
    elif fault == "half_batch":
        with faults.half_batch():
            r = _run(root)
    else:
        r = _run(root)
    readings = {k: c["value"] for k, c in r["checks"].items()}
    assert r["correct"] == (fault is None), readings
