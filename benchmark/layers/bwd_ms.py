"""Model: milliseconds per step and chip of the device's own time in the
backward pass: operations whose ``op_name`` holds ``transpose(``
(hand-written ``custom_vjp`` rules included; a weight gradient with
Adam fused in is the product's time and counts here), outside the
optimizer's update and the exchange. Source: the program's ``hvd:``
scopes joined to the device trace (``device_scopes.py``). Moves
``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.phase_ms(run, device_scopes.BACKWARD)
