"""A configuration, a traffic mix (with a generator kind of its own) and a
per-layer metric are added by new files alone: the harness finds them by
the names in BENCHMARK.json, with no edit to any file it already has."""
import json
import shutil
from types import SimpleNamespace

from bench import spec

KIND = '''
from bench.traffic.common import Item, prompt_tokens


class Burst:
    def __init__(self, spec, seed, vocab, seconds):
        self.n, self.seed, self.vocab = int(spec["burst"]), seed, vocab

    def poll(self, t_rel):
        return [Item(i, prompt_tokens(self.seed, i, 4, self.vocab), 2) for i in range(self.n)]


def make_source(spec, seed, vocab, seconds):
    return Burst(spec, seed, vocab, seconds)
'''

METRIC = '''
def read(ctx):
    return 2.0 * ctx.tokens_per_s
'''


def test_new_files_alone_make_a_new_cell(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR / "reference", bench / "reference")
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "metrics").mkdir()
    (bench / "limits").mkdir()
    (bench / "configs" / "toy.json").write_text(json.dumps(
        {"arch": "granite-3-2b", "reference": "decoder", "hidden_size": 64}))
    (bench / "traffic" / "burst.py").write_text(KIND)
    (bench / "traffic" / "b4.json").write_text(json.dumps(
        {"kind": "burst", "burst": 4, "engine": {"method": "topk"}}))
    (bench / "metrics" / "toy.double_rate.py").write_text(METRIC)
    (bench / "limits" / "toy.b4.json").write_text(json.dumps({"logit_gap": 0.5}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.b4", "config": "toy", "traffic": "b4", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["other"]}],
        "per_layer": [{"name": "toy.double_rate", "unit": "tokens/s",
                       "workloads": ["toy.b4"]}]}))

    cell = spec.load_cell("toy.b4", repo_root=tmp_path, bench_dir=bench)
    assert cell.cfg["hidden_size"] == 64 and cell.limits == {"logit_gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s"]
    items = spec.traffic_source(cell, 7, 50, 10.0).poll(0.0)
    assert [i.rid for i in items] == [0, 1, 2, 3]
    readers = spec.metric_readers(cell)
    assert readers["toy.double_rate"].read(SimpleNamespace(tokens_per_s=3.0)) == 6.0
    assert hasattr(spec.reference_module(cell), "forward")


def test_every_cell_of_the_benchmark_finds_its_files():
    bm = json.loads((spec.REPO_ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert set(spec.metric_readers(cell)) == {m["name"] for m in cell.per_layer}
        assert cell.per_layer and any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert set(cell.limits) >= {"logit_gap"}
