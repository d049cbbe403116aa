"""Kernels: milliseconds per step of the device's own time in the window
layers' attention, ``hvd:attention.window``: the blocked kernels' calls
under the sliding window (``ops/flash.py``, forward and backward) with
their lse / D passes; the full layers' calls are ``hvd:attention.kernel``.
Source: ``device_scopes.py``. Moves ``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.ms_per_step(run, scope="hvd:attention.window")
