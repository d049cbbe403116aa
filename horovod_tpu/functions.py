"""State synchronization helpers.

Rebuild of ``/root/reference/horovod/torch/functions.py`` (269 LoC:
``broadcast_parameters`` / ``broadcast_optimizer_state`` / ``broadcast_object``)
and ``/root/reference/horovod/tensorflow/functions.py`` (``broadcast_variables``).
Reference examples call these at step 0 so every rank starts from rank 0's
weights (``examples/pytorch/pytorch_mnist.py:220-221``).

On TPU under single-controller SPMD, jax arrays are already globally
consistent, so these matter for (a) process-set subsets, (b) multi-process
host state divergence (RNG, python objects), and (c) elastic restarts —
they broadcast through the same collective layer for full parity.
"""

from __future__ import annotations

import jax

from . import timeline as _timeline
from .ops import collectives
from .process_sets import ProcessSet

# program span (docs/timeline.md): submit to synchronize() returned
_BROADCAST_PARAMETERS = _timeline.span("broadcast_parameters")


def broadcast_parameters(params, root_rank: int = 0,
                         process_set: ProcessSet | None = None):
    """Broadcast a pytree of arrays from ``root_rank`` to all ranks
    (reference ``broadcast_parameters``, ``torch/functions.py``).
    Returns the synchronized pytree. Leaves ride the fusion-cycle
    broadcast queue and are fused per dtype into single wire buffers at
    the flush (see ``grouped_broadcast``) — a model broadcast coalesces
    with any other pending broadcasts of the same root before the
    synchronize drains the queue."""
    leaves, treedef = jax.tree.flatten(params)
    with _BROADCAST_PARAMETERS(leaves=len(leaves)):
        handle = collectives.grouped_broadcast_async(
            leaves, root_rank, process_set=process_set)
        synced = handle.synchronize()
    return jax.tree.unflatten(treedef, synced)


# TF-parity alias (reference ``broadcast_variables``, tensorflow/functions.py)
broadcast_variables = broadcast_parameters


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              process_set: ProcessSet | None = None):
    """Broadcast optimizer state (reference ``broadcast_optimizer_state``).
    optax states are array pytrees, so this is the same fused tree
    broadcast — non-array leaves (step counts as python ints, None) pass
    through. Array leaves ride the fusion-cycle broadcast queue like
    :func:`broadcast_parameters`, so a params + optimizer-state restore
    coalesces into one pipelined flush instead of two dispatch storms."""
    leaves, treedef = jax.tree.flatten(opt_state)
    is_array = [hasattr(x, "dtype") and hasattr(x, "shape") for x in leaves]
    handle = collectives.grouped_broadcast_async(
        [x for x, a in zip(leaves, is_array) if a], root_rank,
        process_set=process_set)
    it = iter(handle.synchronize())
    out = [next(it) if a else x for x, a in zip(leaves, is_array)]
    return jax.tree.unflatten(treedef, out)


broadcast_object = collectives.broadcast_object
allgather_object = collectives.allgather_object
