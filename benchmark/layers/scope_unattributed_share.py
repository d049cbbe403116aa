"""Device: percent of the device's own time in operations no ``hvd:``
scope names: the job's own lines (its loss, ``apply_updates`` where it is
not fused into the optimizer's), and operations the compiler made without
an ``op_name``. Lower is better: what is left is what the scope table
cannot explain. Source: ``device_scopes.py``. Moves ``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.share(run, scope=device_scopes.UNATTRIBUTED)
