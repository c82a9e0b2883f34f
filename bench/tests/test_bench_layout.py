"""A layer layout that is not a dense decoder is added as new files alone:
a configuration and a reference module (``latent_moe_ref.py``: latent
attention, a dense first block, then routed experts behind a router wider
than the experts held). The weights, the key check, the cache and mask
comparisons, the rooflines and the FLOPs all follow the module, with no
edit to a harness file. CPU only; no program is served."""
import json
import math
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check, roofline, spec

MODULE = Path(__file__).resolve().parent / "latent_moe_ref.py"
CFG = {"arch": "olmoe-1b-7b", "reference": "latent_moe", "hidden_size": 32,
       "intermediate_size": 64, "moe_intermediate_size": 16, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "num_attention_heads": 2, "kv_lora_rank": 16,
       "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
       "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
       "routed_scaling_factor": 2.446, "vocab_size": 64, "rope_theta": 10000.0,
       "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
       "reduced": ["hidden_size", "moe_intermediate_size", "num_hidden_layers",
                   "num_attention_heads", "vocab_size", "n_routed_experts",
                   "num_experts_per_tok"],
       "published": {"n_routed_experts": 64}}
SEED = 2**35 + 7
SPARSITY = 0.4


@pytest.fixture(scope="module")
def ref(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_layout")
    bench = root / "bench"
    for sub in ("reference", "configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(MODULE, bench / "reference" / "latent_moe.py")
    (bench / "configs" / "toy_moe.json").write_text(json.dumps(CFG))
    (bench / "traffic" / "batch.json").write_text(json.dumps(
        {"kind": "closed", "engine": {"method": "topk"}}))
    (bench / "limits" / "toy_moe.batch.json").write_text(json.dumps({"logit_gap": 0.5}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy_moe", "file": "bench/configs/toy_moe.json"}],
        "workloads": [{"name": "toy_moe.batch", "config": "toy_moe", "traffic": "batch",
                       "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    cell = spec.load_cell("toy_moe.batch", repo_root=root, bench_dir=bench)
    return spec.reference_module(cell)


def test_a_reference_that_lacks_part_of_the_layout_is_refused(tmp_path):
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "bare.py").write_text("def forward(*a):\n    pass\n")
    cell = SimpleNamespace(bench_dir=tmp_path, cfg={"reference": "bare"})
    with pytest.raises(AttributeError, match="leaves"):
        spec.reference_module(cell)


def test_the_weights_follow_the_declared_stacks(ref):
    import jax.numpy as jnp

    from bench.weights import layer_params, served_params

    dm = ref.dims(CFG)
    tree = served_params(ref.leaves, dm, SEED)
    assert set(tree) == {"embed", "final_norm_w", "head", "dense", "moe"}
    assert tree["dense"]["w_gate"].shape == (1, 32, 64)
    assert tree["moe"]["router"].shape == (2, 32, 64)  # the published count
    assert tree["moe"]["we_gate"].shape == (2, 8, 32, 16)  # the held count
    assert tree["moe"]["wq"].dtype == jnp.bfloat16
    for stack, (depth, leaves) in ref.leaves(dm)["stacks"].items():
        for i in range(depth):
            one = layer_params(ref.leaves, dm, SEED, stack, i, jnp.bfloat16)
            assert set(one) == set(leaves)
            for name, leaf in one.items():
                np.testing.assert_array_equal(np.asarray(leaf),
                                              np.asarray(tree[stack][name][i]))
    # every layer of the model has its own key: the first layer of each
    # stack draws a leaf of the same name differently
    assert not np.array_equal(np.asarray(tree["dense"]["wq"][0]),
                              np.asarray(tree["moe"]["wq"][0]))
    with pytest.raises(IndexError):
        layer_params(ref.leaves, dm, SEED, "dense", 1)


def test_the_key_check_reads_the_modules_map_both_ways(ref):
    from bench.serve import program_config

    pcfg = program_config(CFG, ref)
    assert (pcfg.n_experts, pcfg.moe_top_k, pcfg.d_model, pcfg.d_ff, pcfg.n_layers) == \
        (8, 2, 32, 16, 3)
    unlisted = dict(CFG, reduced=[k for k in CFG["reduced"] if k != "n_routed_experts"])
    with pytest.raises(ValueError, match="n_routed_experts=8"):
        program_config(unlisted, ref)
    stray = dict(CFG, reduced=CFG["reduced"] + ["kv_lora_rank"])
    with pytest.raises(ValueError, match="kv_lora_rank"):
        program_config(stray, ref)
    dense_arch = dict(CFG, arch="granite-3-2b",
                      reduced=CFG["reduced"] + ["rope_theta", "tie_word_embeddings"])
    with pytest.raises(ValueError, match="arch_type=dense"):
        program_config(dense_arch, ref)


def reference_cache(ref, dm, tokens, masks_at=None):
    got = []
    ref.forward(dm, SEED, tokens, [tokens.shape[1] - 1], masks_at=masks_at,
                on_cache=lambda layer, c: got.append(np.asarray(c["ckv"])))
    return {"ckv": np.stack(got)}


def test_compare_rows_reads_the_latent_cache(ref):
    dm = ref.dims(CFG)
    tokens = np.random.default_rng(1).integers(0, dm.vocab, (2, 10)).astype(np.int32)
    p, s_len = 6, 10
    cache = reference_cache(ref, dm, tokens)
    assert cache["ckv"].shape == (3, 2, 10, 16 + 4)
    served = np.zeros((2, 4), np.int32)
    out, _ = check.compare_rows(ref, dm, SEED, tokens, served, p, s_len, cache, False)
    assert out["kv_rel_err"] < 1e-6 and out["kv0_rel_err"] < 1e-6
    # a deep layer off by 1%: the whole-cache number sees it, the first layer's not
    off = {"ckv": cache["ckv"].copy()}
    off["ckv"][2] *= 1.01
    out, _ = check.compare_rows(ref, dm, SEED, tokens, served, p, s_len, off, False)
    assert out["kv_rel_err"] == pytest.approx(0.01, rel=1e-3) and out["kv0_rel_err"] < 1e-6
    # the first layer's decoded positions off: both see it
    off = {"ckv": cache["ckv"].copy()}
    off["ckv"][0, :, p:] *= 1.02
    out, _ = check.compare_rows(ref, dm, SEED, tokens, served, p, s_len, off, False)
    assert out["kv0_rel_err"] == pytest.approx(0.02, rel=1e-3)
    # float8 weights in the program's place: their own latent is compared
    out, _ = check.compare_rows(ref, dm, SEED, tokens, served, p, s_len, cache, True)
    assert out["kv_rel_err"] > 1e-3 and out["kv0_rel_err"] > 1e-4


def consistent_masks(ref, dm, tokens, p, n):
    """The masks a selection faithful to ``expected_mask`` makes at each of
    ``n`` decode calls: call j's from the importance at position p+j-1
    under the calls before (call 0 keeps every row)."""
    spans = {site: layers for site, (_, layers) in ref.sites(dm).items()}
    masks = {c: {site: np.ones((len(layers), width), bool)
                 for site, (width, layers) in ref.sites(dm).items()} for c in range(n)}

    def masks_at(layer):
        out = {}
        for site, layers in spans.items():
            if layer in layers:
                m = np.ones((tokens.shape[1], masks[0][site].shape[1]), np.float32)
                for j in range(n):
                    m[p + j] = masks[j][site][layers.index(layer)]
                out[site] = m
        return out

    for _ in range(n - 1):
        _, imps = ref.forward(dm, SEED, tokens, [p], masks_at=masks_at,
                              imp_pos=list(range(p, p + n - 1)))
        for j in range(1, n):
            for site, imp in imps.items():
                b = check.budget(imp.shape[-1], SPARSITY)
                masks[j][site] = np.stack([ref.expected_mask(dm, site, imp[r, j - 1], b)
                                           for r in range(imp.shape[0])])
    return masks, masks_at


def test_the_mask_check_follows_each_sites_layers(ref):
    dm = ref.dims(CFG)
    rng = np.random.default_rng(2)
    p, n, rows = 5, 4, 2
    prompts = rng.integers(0, dm.vocab, (rows, p)).astype(np.int32)
    decoded = rng.integers(0, dm.vocab, (rows, n)).astype(np.int32)
    cohort = []
    for r in range(rows):
        logits = np.zeros((1, dm.vocab), np.float32)
        logits[0, decoded[r, 0]] = 1.0
        cohort.append(SimpleNamespace(item=SimpleNamespace(tokens=prompts[r], rid=r),
                                      prefill_logits=logits, tokens=list(decoded[r, 1:]) + [0],
                                      decode_calls=list(range(n))))
    tokens = np.concatenate([prompts, decoded], 1)
    masks, masks_at = consistent_masks(ref, dm, tokens, p, n)
    assert {s: m.shape[0] for s, m in masks[1].items()} == \
        {"hidden_attn": 3, "attn_out": 3, "hidden_mlp": 1, "ffn": 1, "expert_ffn": 2}
    # two levels: the expert rows chosen lie in as few experts as the budget needs
    b = check.budget(dm.experts * dm.fe, SPARSITY)
    chosen = masks[1]["expert_ffn"][1].reshape(dm.experts, dm.fe)
    assert chosen.sum() == b and chosen.any(-1).sum() == math.ceil(b / dm.fe)
    cache = reference_cache(ref, dm, tokens, masks_at)
    out = check.check_selecting(ref, dm, SEED, cohort, masks, SPARSITY, cache)
    assert out["mask_mismatch"] == 0.0 and out["kv_rel_err"] < 1e-6
    # one chosen expert row of the second routed layer swapped for one not chosen
    m = masks[2]["expert_ffn"][1]
    on, off = np.flatnonzero(m)[0], np.flatnonzero(~m)[0]
    m[on], m[off] = False, True
    out = check.check_selecting(ref, dm, SEED, cohort, masks, SPARSITY, cache)
    total = sum(2 * check.budget(w, SPARSITY) * len(layers) * (n - 1)
                for w, layers in ref.sites(dm).values())
    assert out["mask_mismatch"] == pytest.approx(2 / total)


def test_rooflines_and_flops_come_from_the_module(ref):
    dm = ref.dims(CFG)
    peaks = {"bf16_flops": 1e6, "int8_ops": 2e6, "hbm_bytes_per_s": 1e3}
    rows = {"hidden_attn": 20, "attn_out": 10, "hidden_mlp": 20, "ffn": 40,
            "expert_ffn": 77}
    t = roofline.least_time_per_call(ref, dm, "expert_dma", rows, 2, peaks)
    # one expert's down projection: 77 of its rows x 32 columns; it reads
    # the 128-wide expert input and writes 32
    flops, nbytes = 2 * 2 * 77 * 32, 77 * 32 * 2 + 2 * 128 * 4 + 2 * 32 * 4
    assert t == pytest.approx(max(flops / 1e6, nbytes / 1e3))
    mixed = roofline.least_time_per_call(ref, dm, "matmul_dma", rows, 2, peaks)
    calls = [(20, 2 * 12, 32), (20, 20, 32), (10, 32, 16)]  # q, latent, o: rows, cols, in
    want = sum(max(2 * 2 * r * c / 1e6, (r * c * 2 + 2 * i * 4 + 2 * c * 4) / 1e3)
               for r, c, i in calls) / 3
    assert mixed == pytest.approx(want)
    attn = 32 * 24 + 32 * 20 + 16 * 32 + 16 * 32
    moe = 32 * 64 + 3 * 32 * 16 * 3
    f = ref.flops_per_token(dm, 10.0)
    assert f == 2 * (3 * attn + 3 * 32 * 64 + 2 * moe + 32 * 64) + 2 * 3 * 2 * 20 * 10
    reader = spec.load_module(spec.BENCH_DIR / "metrics" / "mfu.decode.py")
    served = [SimpleNamespace(item=SimpleNamespace(tokens=[0] * 9), token_times=[0.0])]
    ctx = SimpleNamespace(ref=ref, dims=dm, peaks=peaks, tokens_per_s=2.0,
                          window=SimpleNamespace(requests=served))
    assert reader.read(ctx) == pytest.approx(100 * f * 2.0 / 1e6)
