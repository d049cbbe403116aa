"""Model: milliseconds per step and chip of the device's own time in the
forward pass: operations whose ``op_name`` holds ``jvp(`` and no
``transpose(`` (a fusion takes the phase its fused instructions carry),
outside the optimizer's update and the exchange. Source: the program's
``hvd:`` scopes joined to the device trace through the HLO the trace file
carries (``device_scopes.py``). Moves ``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.phase_ms(run, device_scopes.FORWARD)
