"""Optimizer wrapper + eager collectives: wall milliseconds a step of
``hvd:plan.run`` on any thread: the host dispatch of the plans' fuse,
wire and split programs (the executor thread at defaults). Source: the
program's span in the traced run (``program_spans.py``). Moves
``step_ms``."""

from benchmark import program_spans


def read(run):
    return program_spans.wall_ms_per_step(run, "hvd:plan.run")
