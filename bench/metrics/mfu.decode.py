"""Model step: the whole decode's share of the chip's bf16 peak — the
dense model's operations per output token (bench/roofline.py, at the mean
context of the window's tokens) times tokens per second, over peak. The
same count for every method, so a sparse method's saving shows as a rate."""

from bench.roofline import dense_flops_per_token


def read(ctx):
    if ctx.peaks is None or ctx.tokens_per_s <= 0:
        return None
    ctxs = [len(sv.item.tokens) + j + 1 for sv in ctx.window.requests
            for j in range(len(sv.token_times))]
    if not ctxs:
        return None
    flops = dense_flops_per_token(ctx.dims, sum(ctxs) / len(ctxs))
    return 100.0 * flops * ctx.tokens_per_s / ctx.peaks["bf16_flops"]
