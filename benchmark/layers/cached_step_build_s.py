"""GSPMD cached step: total seconds of ``hvd:cached_step.build`` since
process start: trace, lower, compile or cache fetch, and load of each new
step signature. It runs before any profiler session, so the source is the
program's registry (``hvd_span_seconds``). Moves ``setup_s``."""

from benchmark import program_spans


def read(run):
    return program_spans.setup_seconds("cached_step.build")
