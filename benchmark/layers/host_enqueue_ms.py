"""Host dispatch: median host milliseconds for one step call to return
(no device sync), over the windows this run made with the profiler off.
Where it approaches ``step_ms`` the host sets the pace. Moves
``step_ms``."""

import statistics


def read(run):
    if not run.enqueue_seconds:
        return None
    return statistics.median(run.enqueue_seconds) * 1e3
