"""Optimizer wrapper + eager collectives: wall milliseconds a step of
``hvd:optimizer.sync`` on the calling thread: the first stage of an eager
``DistributedOptimizer.update`` (bucket layout, ``grouped_allreduce_async``,
flush, waiting for the executor). Source: the program's span in the traced
run (``program_spans.py``). Moves ``step_ms``."""

from benchmark import program_spans


def read(run):
    return program_spans.wall_ms_per_step(
        run, "hvd:optimizer.sync", program_spans.CALLER)
