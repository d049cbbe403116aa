"""Standalone synchronized batch normalization.

TPU-native rebuild of the reference's ``SyncBatchNorm``
(``/root/reference/horovod/torch/sync_batch_norm.py:1-218``, which
allgathers per-rank mean/var and hand-computes the backward pass). On TPU
the cross-replica moment reduction is one ``lax.pmean`` over the mesh axis
inside the SPMD program — flax's ``BatchNorm`` already supports exactly
that via ``axis_name``, and XLA differentiates through the psum, so the
reference's 150 lines of manual backward collapse into configuration. This
module pins the defaults so users get the reference's drop-in behavior:

    norm = hvd.SyncBatchNorm()        # stats synced over hvd.mesh()
    y = norm(x, use_running_average=not train)

Must run inside traced code with the mesh axis bound (``jax.shard_map``
over ``hvd.mesh()``); outside, it falls back to local batch stats exactly
like single-process torch SyncBatchNorm.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from .. import runtime
from . import scopes as _scopes


class SyncBatchNorm(nn.BatchNorm):
    """``flax.linen.BatchNorm`` with cross-replica statistics over the
    framework's mesh axis by default (reference
    ``hvd.SyncBatchNorm``). All ``nn.BatchNorm`` fields apply; set
    ``axis_name`` explicitly to sync over a different axis (e.g. both axes
    of a 2-D hierarchical mesh: ``axis_name=("hvd_dcn", "hvd_ici")``)."""

    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None
    axis_name: Any = None

    @nn.compact
    def __call__(self, x, use_running_average: bool | None = None):
        from ..ops.collectives import _axis_is_bound

        axis = self.axis_name
        if axis is None:
            try:
                axis = runtime.axis_name()
            except Exception:
                axis = None
        # Outside shard_map the axis isn't bound: fall back to local stats
        # (under plain-jit GSPMD the partitioner reduces the batch mean
        # globally anyway; flax also skips the pmean during init).
        if axis is not None and not self.is_initializing():
            axes = axis if isinstance(axis, (tuple, list)) else (axis,)
            if not all(_axis_is_bound(a) for a in axes):
                axis = None
        if use_running_average is None:
            use_running_average = self.use_running_average
        # forward every nn.BatchNorm field (robust to fields flax adds),
        # overriding only the axis_name resolution above
        fields = {f for f in nn.BatchNorm.__dataclass_fields__
                  if f not in ("parent", "name")}
        kwargs = {f: getattr(self, f) for f in fields}
        kwargs.update(use_running_average=use_running_average,
                      axis_name=axis)
        with _scopes.BATCH_NORM():
            return nn.BatchNorm(name="sync_bn", **kwargs)(x)
