"""Expert layer: milliseconds per step of the grouped matrix products'
own time on the device, forward and backward: the ``ragged-dot`` custom
calls ``jax.lax.ragged_dot`` becomes on a TPU (rows by weights, the row
gradient, the weight gradient), or the Pallas ``gmm`` / ``tgmm`` calls
should ``parallel/moe.py`` ``grouped_matmul`` take that kernel again.
Routing, sort, gathers and the combine are not in it. Moves ``step_ms``."""

from benchmark.layers import _kernels

KERNELS = ("ragged-dot", "gmm", "tgmm")


def read(run):
    if run.trace is None:
        return None
    seconds = _kernels.kernel_seconds(run.trace, KERNELS)
    return None if seconds is None else seconds * 1e3 / run.traced_steps
