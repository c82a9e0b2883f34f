"""Device: share of the traced window in which no operation ran on the
first device while some program span was open — idle the program's own
host work explains (bench/program_spans.py)."""

from bench.program_spans import idle_in_program_pct


def read(ctx):
    return idle_in_program_pct(ctx)
