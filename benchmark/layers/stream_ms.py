"""Model: milliseconds per step of the device's own time in the residual
stream's passes, ``hvd:model.stream``: the norms before each operator and
ffn, the residual additions, the final norm, forward and backward (with
whatever dense product the compiler fused into them and that has no
``dot`` of another scope). Source: ``device_scopes.py``. Moves
``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.ms_per_step(run, scope="hvd:model.stream")
