"""GSPMD cached step: traces of the step function plus programs the
GSPMD cache built after warm-up (``CachedStep.traces``,
``hvd.gspmd_cache_stats()["builds"]``). 0 when every step replays. Moves
``step_ms``."""


def read(run):
    if run.retraces is None:
        return None
    builds = run.after["gspmd"]["builds"] - run.before["gspmd"]["builds"]
    return run.retraces + builds
