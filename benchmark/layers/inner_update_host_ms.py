"""Optimizer wrapper + eager collectives: wall milliseconds a step of
``hvd:optimizer.inner_update`` on the calling thread: the wrapped
optimizer's ``update`` run eagerly, operation by operation. Source: the
program's span in the traced run (``program_spans.py``). Moves
``step_ms``."""

from benchmark import program_spans


def read(run):
    return program_spans.wall_ms_per_step(
        run, "hvd:optimizer.inner_update", program_spans.CALLER)
