"""Entry / launch: total seconds of ``hvd:init`` (the whole of
``hvd.init()``) since process start. It runs before any profiler session,
so the source is the program's registry (``hvd_span_seconds``). Moves
``setup_s``."""

from benchmark import program_spans


def read(run):
    return program_spans.setup_seconds("init")
