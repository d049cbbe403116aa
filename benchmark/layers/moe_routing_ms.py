"""Expert layer: milliseconds per step of the device's own time around the
grouped products: ``hvd:moe.route`` (router product, scores, top-k),
``hvd:moe.dispatch`` (the sorts, group sizes, the gather of token rows to
sorted rows and its backward) and ``hvd:moe.combine`` (gates, the gather
back to token order and its backward). ``moe_gmm_ms`` is inside
``hvd:moe.experts``, not here. Source: ``device_scopes.py``. Moves
``step_ms``."""

from benchmark import device_scopes

SCOPES = ("hvd:moe.route", "hvd:moe.dispatch", "hvd:moe.combine")


def read(run):
    return device_scopes.ms_per_step(run, scope=SCOPES)
