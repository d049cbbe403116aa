"""The five-line step: ``jit(shard_map)`` over ``hvd.mesh()``, gradients
averaged by ``DistributedOptimizer``'s traced ``psum``, state donated.
The compiler owns the whole step."""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import common


def build(env):
    hvd, mm = env.hvd, env.model
    mesh, axis = hvd.mesh(), hvd.axis_name()
    # the axis name makes the model's batch norm reduce over the mesh
    model = mm.make_model(env.config, axis_name=axis)
    tx = hvd.DistributedOptimizer(mm.optimizer(env.config))
    state = jax.device_put(common.init_state(env, model, tx),
                           NamedSharding(mesh, P()))

    train_step = common.step_body(
        mm, model, tx, reduce_loss=lambda loss: jax.lax.pmean(loss, axis))
    n_inputs = len(env.batch_shapes)
    step = jax.jit(
        jax.shard_map(train_step, mesh=mesh,
                      in_specs=(P(), P(), P()) + (P(axis),) * n_inputs,
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
    # lowered and compiled once, ahead of time, as chip_smoke.py and
    # bench.py run it
    with env.spans("lower_compile"):
        compiled = step.lower(*state, *env.batch_shapes).compile()

    def run(state, batch):
        *state, loss = compiled(*state, *batch)
        return tuple(state), loss

    return common.Job(state=state, step=run)
