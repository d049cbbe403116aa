"""GSPMD cached step: wall milliseconds a call of
``hvd:cached_step.lookup``: the signature of the arguments and the plan
lookup that ``CachedStep.__call__`` makes before it enqueues. Source: the
program's span in the traced run (``program_spans.py``). Moves
``step_ms``."""

from benchmark import program_spans


def read(run):
    return program_spans.wall_ms_per_call(
        run, "hvd:cached_step.lookup", program_spans.CALLER)
