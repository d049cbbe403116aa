"""The README's five-line data-parallel step for this package's image
classifiers on synthetic ImageNet-shaped batches.

One builder, shared by ``chip_smoke.py`` and
``examples/synthetic_benchmark.py``, so the step they run cannot drift:
``hvd.broadcast_parameters`` → the caller's ``hvd.DistributedOptimizer`` →
``jax.jit(jax.shard_map(step, mesh=hvd.mesh(), ...), donate_argnums=...)``
over every chip of the mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import functions, runtime


def classifier_trainer(model, tx, *, image_size: int, batch_per_chip: int,
                       remat: bool = False):
    """Build the traced data-parallel train step for a flax classifier
    with batch statistics (ResNet, Inception). ``hvd.init()`` must have
    run; ``tx`` is the caller's ``hvd.DistributedOptimizer``.

    Returns ``(step, state, batch)``:

    * ``step(params, batch_stats, opt_state, images, labels)`` →
      ``(params, batch_stats, opt_state, loss)``: ``jit(shard_map)`` over
      ``hvd.mesh()``, state replicated, batch sharded over the hvd axis,
      gradients averaged by ``tx`` (a traced ``psum``). The three state
      buffers are donated, so the update writes in place.
    * ``state = (params, batch_stats, opt_state)``: initialised from a
      fixed seed, broadcast from rank 0, replicated on the mesh.
    * ``batch = (images, labels)``: seeded standard-normal float32 images
      and uniform labels, ``batch_per_chip`` per chip.

    ``remat`` rematerializes the forward in the backward
    (``jax.checkpoint``)."""
    mesh, axis = runtime.mesh(), runtime.axis_name()
    n = runtime.size()
    classes = model.num_classes
    shape = (image_size, image_size, 3)

    # jitted: an eager init dispatches hundreds of one-op programs
    variables = jax.jit(lambda key: model.init(
        key, jnp.zeros((1,) + shape, jnp.float32), train=True))(
            jax.random.PRNGKey(0))
    params = functions.broadcast_parameters(variables["params"], root_rank=0)
    batch_stats = variables["batch_stats"]
    opt_state = tx.init(params)

    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            def apply(p, x):
                return model.apply({"params": p, "batch_stats": batch_stats},
                                   x, train=True, mutable=["batch_stats"])
            logits, mutated = (jax.checkpoint(apply) if remat
                               else apply)(p, images)
            one_hot = jax.nn.one_hot(labels, classes)
            loss = -jnp.mean(jnp.sum(one_hot * jax.nn.log_softmax(logits), -1))
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, new_opt, loss

    step = jax.jit(
        jax.shard_map(train_step, mesh=mesh,
                      in_specs=(P(), P(), P(), P(axis), P(axis)),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))

    replicated = NamedSharding(mesh, P())
    state = jax.device_put((params, batch_stats, opt_state), replicated)
    sharded = NamedSharding(mesh, P(axis))
    images = np.random.default_rng(0).standard_normal(
        (n * batch_per_chip,) + shape, dtype=np.float32)
    labels = np.random.default_rng(1).integers(
        0, classes, size=(n * batch_per_chip,))
    batch = (jax.device_put(images, sharded), jax.device_put(labels, sharded))
    return step, state, batch
