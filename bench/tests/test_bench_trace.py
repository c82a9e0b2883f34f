"""The trace reduction, the roofline and MFU arithmetic, and the table of
peaks, on a synthetic trace: nothing here loads libtpu."""
import dataclasses
from types import SimpleNamespace

import pytest

from bench import roofline, spec, trace_reduce
from bench.reference import decoder


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def synthetic():
    # window 1000..2000 ns; device ops overlap and spill past both ends
    ops = [Ev("%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p)", 900, 200),
           Ev("%chunk_gather_mlp_dma.3 = f32[16,8]{1,0} custom-call()", 1150, 100),
           Ev("%chunk_gather_matmul_dma.7 = f32[16,8]{1,0} custom-call()", 1200, 100),
           Ev("%chunk_gather_matmul_dma.8 = f32[16,2]{1,0} custom-call()", 1500, 100),
           Ev("%while.2 = (s32[]) while(%t)", 1400, 300),
           Ev("%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p)", 1900, 300)]
    mods = [Ev("jit__lambda", 1100, 300), Ev("jit__decode_scan_impl", 1450, 200)]
    host = [Ev("bench.window", 1000, 1000), Ev("bench.step", 1000, 500),
            Ev("bench.admit", 1300, 150), Ev("other", 1000, 1000)]
    return [Plane("/host:CPU", [Line("python", host)]),
            Plane("/device:TPU:0", [Line("XLA Ops", ops), Line("XLA Modules", mods),
                                   Line("Steps", [Ev("0", 0, 5000)])])]


def test_busy_is_the_union_of_device_ops_inside_the_window():
    s = trace_reduce.summarize_planes(synthetic())
    assert s.window == (1000, 2000)
    # [1000,1100) + [1150,1300) + [1400,1700) + [1900,2000)
    assert s.busy_s() == pytest.approx(650e-9)
    assert s.window_s == pytest.approx(1000e-9)


def test_kernel_time_by_name_and_module_times():
    s = trace_reduce.summarize_planes(synthetic())
    assert s.op_time_s(r"^chunk_gather_matmul_dma\.") == (pytest.approx(200e-9), 2)
    assert s.op_time_s(r"^chunk_gather_mlp_dma\.") == (pytest.approx(100e-9), 1)
    assert s.module_times_s(r"^jit__lambda") == [pytest.approx(300e-9)]
    # the loop spans its body: never one of the costliest operations itself
    assert s.top_ops(1) == [("fusion.1", pytest.approx(200e-9))]
    assert "while.2" not in dict(s.top_ops(10))


def test_idle_gaps_are_named_by_the_innermost_covering_span():
    s = trace_reduce.summarize_planes(synthetic())
    gaps = s.idle_gaps(10)
    # gaps: [1100,1150) in step, [1300,1400) mid 1350 in admit, [1700,1900) none
    assert gaps[0] == ("none", pytest.approx(200e-9))
    assert gaps[1] == ("bench.admit", pytest.approx(100e-9))
    assert gaps[2] == ("bench.step", pytest.approx(50e-9))


def test_a_trace_without_the_window_span_is_refused():
    planes = synthetic()
    planes[0].lines[0].events = [e for e in planes[0].lines[0].events
                                 if e.name != "bench.window"]
    with pytest.raises(RuntimeError, match="bench.window"):
        trace_reduce.summarize_planes(planes)


def test_peaks_known_and_unknown_device():
    p = roofline.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks_for("TPU v99")


DM = decoder.Dims(d=16, f=64, layers=2, heads=2, kv_heads=1, head_dim=8, vocab=100,
          rope_theta=1e4, norm="rms", norm_eps=1e-5, mlp="swiglu", tied=True)
PEAKS = {"bf16_flops": 1e6, "int8_ops": 2e6, "hbm_bytes_per_s": 1e3}


def test_call_work_counts_selected_rows_and_activations():
    rows = {"hidden_attn": 10, "attn_out": 16, "hidden_mlp": 8, "ffn": 32}
    # q projection: 10 rows x 16 columns of bf16, batch 2
    inputs = {site: width for site, (width, _) in decoder.sites(DM).items()}
    flops, nbytes = roofline.call_work([("hidden_attn", 16)], rows, inputs, 2, 2.0)
    assert flops == 2 * 2 * 10 * 16
    assert nbytes == 10 * 16 * 2 + 2 * 16 * 4 + 2 * 16 * 4
    # fused MLP: gate+up over 8 rows of 64, down over 32 rows of 16
    flops, nbytes = roofline.call_work(
        [("hidden_mlp", 64), ("hidden_mlp", 64), ("ffn", 16)], rows, inputs, 2, 2.0)
    assert flops == 2 * 2 * (8 * 64 * 2 + 32 * 16)
    assert nbytes == (8 * 64 * 2 + 32 * 16) * 2 + 2 * 16 * 4 + 2 * (16 + 64) * 4


def test_least_time_is_the_larger_bound_averaged_over_a_layers_calls():
    rows = {"hidden_attn": 10, "attn_out": 16, "hidden_mlp": 8, "ffn": 32}
    t = roofline.least_time_per_call(decoder, DM, "matmul_dma", rows, 2, PEAKS)
    calls = decoder.kernel_calls(DM)["matmul_dma"]
    inputs = {"hidden_attn": 16, "attn_out": 16, "hidden_mlp": 16, "ffn": 64}
    want = sum(max(f / 1e6, b / 1e3) for f, b in
               (roofline.call_work(c, rows, inputs, 2, 2.0) for c in calls)) / len(calls)
    assert t == pytest.approx(want)
    # at 8 bits: half the weight bytes, the int8 peak
    t8 = roofline.least_time_per_call(decoder, DM, "matmul_dma", rows, 2, PEAKS, wbits=8)
    assert t8 < t


def test_dense_flops_per_token():
    f = decoder.flops_per_token(DM, context=10)
    per_layer = 16 * 16 + 2 * 16 * 8 + 16 * 16 + 3 * 16 * 64
    assert f == 2 * (2 * per_layer + 16 * 100) + 4 * 2 * 16 * 10


def _reader(name):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py")


def test_roofline_and_mfu_readers():
    s = trace_reduce.summarize_planes(synthetic())
    rows = {"hidden_attn": 10, "attn_out": 16, "hidden_mlp": 8, "ffn": 32}
    cell = SimpleNamespace(engine={"slots": 2})
    served = [SimpleNamespace(item=SimpleNamespace(tokens=[0] * 4), token_times=[0, 1])]
    ctx = SimpleNamespace(trace=s, selected_rows=rows, peaks=PEAKS, ref=decoder, dims=DM,
                          cell=cell, wbits=16, tokens_per_s=5.0,
                          window=SimpleNamespace(requests=served))
    got = _reader("chunk_gather_matmul_dma_roofline").read(ctx)
    per_call = roofline.least_time_per_call(decoder, DM, "matmul_dma", rows, 2, PEAKS)
    assert got == pytest.approx(100 * 2 * per_call / 200e-9)
    mfu = _reader("mfu.decode").read(ctx)
    assert mfu == pytest.approx(100 * decoder.flops_per_token(DM, 5.5) * 5 / 1e6)
    # nothing to read: the reader says nothing, never 0
    ctx.trace = trace_reduce.summarize_planes(
        [p if p.name != "/device:TPU:0" else Plane(p.name, [Line("XLA Ops", [])])
         for p in synthetic()])
    assert _reader("chunk_gather_matmul_dma_roofline").read(ctx) is None
    assert _reader("device.idle_pct.batch").read(ctx) == pytest.approx(100.0)
