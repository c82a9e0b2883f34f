"""Routed experts: held experts whose rows the expert gather streamed, per
expert layer step — the program's ``moe.experts_streamed`` over its
``moe.layer_steps`` counters (the whole run). 8 means every held expert
runs every step; under uniform routing of 16 tokens × 6 picks over 64
experts about 6.3 of 8 do. A program without the counters reads nothing."""


def read(ctx):
    try:
        from repro import trace
    except ImportError:
        return None
    counters = trace.counters()
    steps = counters.get("moe.layer_steps", 0)
    return counters["moe.experts_streamed"] / steps if steps else None
