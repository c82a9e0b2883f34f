"""Engine: host time per decode call — the program's ``engine.decode``
span less its ``engine.decode.wait`` (the host blocked on the device) —
averaged over the window's calls (bench/program_spans.py)."""

from bench.program_spans import engine_decode_host_ms


def read(ctx):
    return engine_decode_host_ms(ctx)
