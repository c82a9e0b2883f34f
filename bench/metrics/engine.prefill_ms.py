"""Engine: mean device time of one prefill program (``ServeEngine``'s
``_prefill_one``, a jitted lambda: XLA module ``jit__lambda``) in the
traced window."""

PREFILL_MODULE = r"^jit__lambda"


def read(ctx):
    if ctx.trace is None:
        return None
    times = ctx.trace.module_times_s(PREFILL_MODULE)
    return 1e3 * sum(times) / len(times) if times else None
