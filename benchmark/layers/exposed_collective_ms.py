"""Exchange: the part of ``collective_ms`` during which no other
operation runs on that chip: the exchange the backward pass did not
hide. Moves ``step_ms``."""

from benchmark import trace_reduce


def read(run):
    if run.trace is None:
        return None
    _, exposed = trace_reduce.collectives(run.trace)
    return exposed * 1e3 / run.traced_steps
