"""Engine: the engine's own time per decode step (``StepStats.wall_s``, a
host span around the fused decode call that ends after the tokens and the
cache are ready), summed over the window and divided by its steps."""


def read(ctx):
    w = ctx.decode_wall_s
    return 1e3 * sum(w) / len(w) if w else None
