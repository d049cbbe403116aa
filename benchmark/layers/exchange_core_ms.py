"""Exchange: milliseconds per step and chip of the core's own time in the
permute rounds outside the collectives themselves: the additions, copies,
slices and updates under ``hvd:exchange.rounds`` that are not
``collective-permute`` operations, which ``exposed_collective_ms`` counts
as compute that covers the exchange. Source: ``device_scopes.py``. Moves
``step_ms``."""

from benchmark import device_scopes


def read(run):
    return device_scopes.ms_per_step(run, scope=device_scopes.ROUNDS,
                                     collective=False)
