"""Device: share (%) of the traced window in which no operation runs on
the chip, mean over the chips (``trace_reduce.idle_share``). A chip
waiting inside a collective counts as busy. Moves ``step_ms``."""

from benchmark import trace_reduce


def read(run):
    if run.trace is None:
        return None
    share = trace_reduce.idle_share(run.trace)
    return None if share is None else 100.0 * share
