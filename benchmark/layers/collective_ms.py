"""Exchange: milliseconds per step and chip with a collective operation
in flight on the device (all-reduce, reduce-scatter, all-gather,
all-to-all, collective-permute; ``trace_reduce.collective_seconds``).
Moves ``step_ms``."""

from benchmark import trace_reduce


def read(run):
    if run.trace is None:
        return None
    total, _ = trace_reduce.collectives(run.trace)
    return total * 1e3 / run.traced_steps
