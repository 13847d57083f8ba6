"""Seconds the program spent building segment plans over the run, set-up
included: the summed duration of its plan.build spans."""
from benchmarks.chip.program_obs import span_seconds


def read(ctx):
    return span_seconds("plan.build")
