"""Horovod's classic loop: gradients from one jitted program, then the
**eager** ``DistributedOptimizer.update`` over the per-rank gradient tree
(bucketed ``grouped_allreduce_async`` -> fusion cycle -> plan cache ->
wire programs, then the wrapped optimizer's update, operation by
operation), then a jitted apply. Program defaults throughout."""

from __future__ import annotations

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import common


def build(env):
    hvd, mm = env.hvd, env.model
    mesh, axis = hvd.mesh(), hvd.axis_name()
    model = mm.make_model(env.config, axis_name=axis)
    tx = hvd.DistributedOptimizer(mm.optimizer(env.config))
    state = jax.device_put(common.init_state(env, model, tx),
                           NamedSharding(mesh, P()))

    def local_grads(params, aux, *batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: mm.loss(model, p, aux, batch), has_aux=True)(params)
        # one row per rank: what each Horovod rank would hand to allreduce
        return (jax.lax.pmean(loss, axis), aux,
                jax.tree.map(lambda g: g[None], grads))

    n_inputs = len(env.batch_shapes)
    grad_step = jax.jit(jax.shard_map(
        local_grads, mesh=mesh,
        in_specs=(P(), P()) + (P(axis),) * n_inputs,
        out_specs=(P(), P(), P(axis)), check_vma=False))
    apply = jax.jit(optax.apply_updates, donate_argnums=0)

    def run(state, batch):
        params, aux, opt_state = state
        loss, aux, grads = grad_step(params, aux, *batch)
        grads = jax.tree.map(hvd.per_rank, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (apply(params, updates), aux, opt_state), loss

    return common.Job(state=state, step=run)
