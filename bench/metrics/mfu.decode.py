"""Model step: the whole decode's share of the chip's bf16 peak — the
operations of one output token with no selection (the reference module's
``flops_per_token``, at the mean context of the window's tokens) times
tokens per second, over peak. The same count for every method, so a
sparse method's saving shows as a rate."""


def read(ctx):
    if ctx.peaks is None or ctx.tokens_per_s <= 0:
        return None
    ctxs = [len(sv.item.tokens) + j + 1 for sv in ctx.window.requests
            for j in range(len(sv.token_times))]
    if not ctxs:
        return None
    flops = ctx.ref.flops_per_token(ctx.dims, sum(ctxs) / len(ctxs))
    return 100.0 * flops * ctx.tokens_per_s / ctx.peaks["bf16_flops"]
