"""Optimizer wrapper: gradient buckets the eager
``DistributedOptimizer.update`` flushed per step
(``hvd.fusion_stats()["flushes"]["bucket"]`` over the run's windows).
Moves ``step_ms``."""


def read(run):
    def flushed(snapshot):
        return snapshot["fusion"]["flushes"].get("bucket", 0)

    if not run.steps:
        return None
    return (flushed(run.after) - flushed(run.before)) / run.steps
