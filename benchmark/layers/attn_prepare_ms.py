"""Kernels: milliseconds per step of the device's own time between
attention's projections and its kernels, ``hvd:attention.prepare``:
per-head norm, rotary positions, the key/value heads' repeat, the
pre-scale, transposes to the kernels' layout and back. Source:
``device_scopes.py``. Moves ``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.ms_per_step(run, scope="hvd:attention.prepare")
