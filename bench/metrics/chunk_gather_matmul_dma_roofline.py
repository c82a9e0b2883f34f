"""Gather kernels: ``chunk_gather_matmul_dma``'s share of its roofline — the
least time its calls could take (bench/roofline.py, from the rows the
step's masks selected and the calls the reference module lists) over
their device time in the trace."""

from bench.roofline import least_time_per_call

KERNEL, OP = "matmul_dma", r"^chunk_gather_matmul_dma(\.|$)"


def read(ctx):
    if ctx.trace is None or ctx.selected_rows is None or ctx.peaks is None:
        return None
    t, n = ctx.trace.op_time_s(OP)
    if n == 0 or t <= 0:
        return None
    per_call = least_time_per_call(ctx.ref, ctx.dims, KERNEL, ctx.selected_rows,
                                   int(ctx.cell.engine["slots"]), ctx.peaks, ctx.wbits)
    return 100.0 * n * per_call / t
