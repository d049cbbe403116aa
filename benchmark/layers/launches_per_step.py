"""Device: programs executed on the device per step (events on the
trace's ``XLA Modules`` line), mean over the chips. Moves ``step_ms``."""

from benchmark import trace_reduce


def read(run):
    if run.trace is None:
        return None
    return trace_reduce.launches(run.trace) / run.traced_steps
