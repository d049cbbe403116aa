"""Eager collectives: of the dispatch-plan lookups made over the run's
windows, the share (%) that hit (``hvd.dispatch_cache_stats()``). Moves
``step_ms``."""


def read(run):
    hits = run.after["dispatch"]["hits"] - run.before["dispatch"]["hits"]
    misses = (run.after["dispatch"]["misses"]
              - run.before["dispatch"]["misses"])
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
