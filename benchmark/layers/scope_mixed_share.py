"""Device: percent of the device's own time in fusions whose fused
instructions name two ``hvd:`` scopes (counted under the scope of their
``dot`` / ``convolution``, else of most of their instructions). Lower is
better: inside such a fusion the table cannot say which scope's work took
the time. Source: ``device_scopes.py``. Moves ``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.share(run, mixed=True)
