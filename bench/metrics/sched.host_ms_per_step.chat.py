"""Scheduler: host time per scheduler step — the program's ``sched.step``
span less the time the ``engine.*`` spans inside it cover — averaged over
the window's steps (bench/program_spans.py)."""

from bench.program_spans import sched_host_ms_per_step


def read(ctx):
    return sched_host_ms_per_step(ctx)
