"""A plain-``jit`` step through ``hvd.cached_step``: no ``shard_map``,
the batch sharded over the mesh and the partitioner averaging the
gradients (``DistributedOptimizer``'s GSPMD passthrough), the program
kept in the plan cache, donation derived by the wrapper."""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import common


def build(env):
    hvd, mm = env.hvd, env.model
    model = mm.make_model(env.config)
    tx = hvd.DistributedOptimizer(mm.optimizer(env.config))
    state = jax.device_put(common.init_state(env, model, tx),
                           NamedSharding(hvd.mesh(), P()))
    train_step = common.step_body(mm, model, tx)
    step = hvd.cached_step(train_step)

    def run(state, batch):
        *state, loss = step(*state, *batch)
        return tuple(state), loss

    # the first call traces once (twice where donation is derived by a
    # shape probe); anything after warm-up is a retrace
    return common.Job(state=state, step=run, retraces=lambda: step.traces)
