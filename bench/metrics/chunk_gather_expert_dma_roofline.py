"""Gather kernels: ``chunk_gather_expert_dma``'s share of its roofline —
the least time of the expert gather's work over its device time in the
trace. The gather runs as two calls per expert layer step: the up phase
reads the routed held experts' gate and up whole (how many a layer step
streams: the program's ``moe.experts_streamed`` over ``moe.layer_steps``),
the down phase the selected rows of their down projection (the step's
masks, ``ctx.selected_rows``, less the rows of the unrouted experts, which
the mask marks kept and nothing reads). Unrouted experts are not counted,
nor rows a block table rounds in. A program without the expert counters
reads nothing."""

from bench.roofline import ACT_BYTES

OP = r"^chunk_gather_expert_dma(\.|$)"
CALLS_PER_LAYER_STEP = 2


def read(ctx):
    if ctx.trace is None or ctx.selected_rows is None or ctx.peaks is None:
        return None
    rows = ctx.selected_rows.get("expert_ffn")
    try:
        from repro import trace
    except ImportError:
        return None
    counters = trace.counters()
    steps = counters.get("moe.layer_steps", 0)
    if rows is None or not steps:
        return None
    t, n = ctx.trace.op_time_s(OP)
    if n == 0 or t <= 0:
        return None
    dm, b = ctx.dims, int(ctx.cell.engine["slots"])
    experts = counters["moe.experts_streamed"] / steps
    rows -= (dm.experts - experts) * dm.fe  # the routed experts' selected rows
    weight_rows = 2 * experts * dm.d * dm.fe + rows * dm.d  # elements
    wb = 1.0 if ctx.wbits == 8 else 2.0
    flops = 2.0 * b * weight_rows
    # up: x in (bf16), the (B, E·F) act out; down: act in, y out
    acts = b * (2 * dm.d + ACT_BYTES * (2 * dm.experts * dm.fe + dm.d))
    peak = ctx.peaks["int8_ops"] if ctx.wbits == 8 else ctx.peaks["bf16_flops"]
    least = max(flops / peak, (weight_rows * wb + acts) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * (n / CALLS_PER_LAYER_STEP) * least / t
