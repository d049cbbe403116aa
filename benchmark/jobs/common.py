"""What the three jobs share: the state a training loop carries and the
body of one optimizer step. Everything here goes through the public
``hvd`` API and optax, as a user's script does."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import optax


@dataclasses.dataclass
class Job:
    """One way of running a training step.

    ``step(state, batch) -> (state, loss)`` is the call the harness
    times; ``state`` is ``(params, aux, opt_state)`` and belongs to the
    job. ``retraces()`` counts traces of the step function, where the
    job's wrapper counts them."""

    state: Any
    step: Callable
    retraces: Callable = lambda: None


def init_state(env, model, tx):
    """Weights from the seed in one jitted call on the device, then the
    five-line contract's broadcast from rank 0, then the optimizer's
    state (jitted too: an eager ``tx.init`` is one dispatch a leaf)."""
    hvd, mm = env.hvd, env.model
    params, aux = jax.jit(lambda key: mm.init(model, env.config, key))(
        env.init_key)
    with env.spans("broadcast_parameters"):
        params = hvd.broadcast_parameters(params, root_rank=0)
    return params, aux, jax.jit(tx.init)(params)


def step_body(mm, model, tx, reduce_loss=lambda loss: loss):
    """``train_step(params, aux, opt_state, *batch)`` ->
    ``(params, aux, opt_state, loss)``: loss and gradients of the repo's
    model, the distributed optimizer's update, the apply."""

    def train_step(params, aux, opt_state, *batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: mm.loss(model, p, aux, batch), has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), aux, opt_state,
                reduce_loss(loss))

    return train_step
