"""Kernels: milliseconds per step of the device's own time in the
block-diffusion mask's part outside the kernels,
``hvd:attention.own_block``: a noised row's own-block scores, the
log-sum-exp join with the kernels' part, the split and the copies of the
two halves, forward and backward. Source: ``device_scopes.py``. Moves
``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.ms_per_step(run, scope="hvd:attention.own_block")
