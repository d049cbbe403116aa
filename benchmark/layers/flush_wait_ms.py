"""Optimizer wrapper + eager collectives: milliseconds a step the calling
thread was blocked in ``hvd:cycle.wait_result`` (``handle.result()`` /
``synchronize()``): work waiting for the flush executor. Source: the
program's span in the traced run (``program_spans.py``). Moves
``step_ms``."""

from benchmark import program_spans


def read(run):
    return program_spans.wall_ms_per_step(
        run, "hvd:cycle.wait_result", program_spans.CALLER)
