"""Entry / launch: total seconds of ``hvd:broadcast_parameters`` since
process start: from the submit of the parameter tree until
``synchronize()`` returned. It runs before any profiler session, so the
source is the program's registry (``hvd_span_seconds``). Moves
``setup_s``."""

from benchmark import program_spans


def read(run):
    return program_spans.setup_seconds("broadcast_parameters")
