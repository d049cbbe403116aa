"""Optimizer wrapper: milliseconds per step and chip of the device's own
time under ``hvd:optimizer.update`` (the wrapped optimizer's ``update``,
with the job's ``apply_updates`` where the compiler fused it in), in a
traced step and in the eager path's compiled update alike. Source: the
program's ``hvd:`` scopes joined to the device trace
(``device_scopes.py``). Moves ``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.phase_ms(run, "optimizer")
