#!/usr/bin/env python
"""Training on a Ray cluster — static and elastic executors.

The Ray analog of ``examples/mnist.py`` (reference ``horovod.ray`` usage,
``/root/reference/docs/ray.rst``): actors replace ssh placement, the
worker fn is ordinary framework code starting with ``hvd.init()``.

Run on a machine with Ray installed:
    python examples/ray_train.py                # static, 2 workers
    python examples/ray_train.py --elastic      # elastic, min 2 workers

Without Ray (CI smoke): prints SKIP and exits 0.
"""

import argparse
import sys


def train_fn(steps: int = 10):
    """One rank: the usual five-line pattern."""
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    # The demo places several ranks on one machine and only one process
    # per host can hold its TPU chips, so ranks default to the CPU.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    rng = np.random.default_rng(hvd.rank())
    w_true = jnp.asarray([[2.0], [-3.0]])
    params = {"w": jnp.zeros((2, 1))}
    params = hvd.broadcast_parameters(params, root_rank=0)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    opt = tx.init(params)

    mesh, axis = hvd.mesh(), hvd.axis_name()

    def step(p, o, x, y):
        def loss_fn(p):
            return jnp.mean((x @ p["w"] - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    sharded = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P(), P()), check_vma=False))
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, P(axis))
    n = hvd.size()
    x = jax.device_put(rng.standard_normal((4 * n, 2)).astype("float32"), sh)
    y = jax.device_put(np.asarray(x) @ np.asarray(w_true), sh)
    loss = None
    for _ in range(steps):
        params, opt, loss = sharded(params, opt, x, y)
        jax.block_until_ready(loss)
    return float(loss)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--elastic", action="store_true")
    parser.add_argument("--workers", type=int, default=2)
    parser.add_argument("--smoke", action="store_true",
                        help="fewer training steps")
    args = parser.parse_args()
    steps = 3 if args.smoke else 10

    try:
        import ray  # noqa: F401
    except ImportError:
        print("SKIP: ray not installed (install Ray to run this example)")
        return 0

    if args.elastic:
        from horovod_tpu.ray import ElasticRayExecutor
        ex = ElasticRayExecutor(min_workers=args.workers)
        ex.start()
        try:
            # elastic worker fns wrap their loop in hvd.elastic.run; this
            # demo uses the static-shaped fn for brevity
            results = ex.run(train_fn, args=(steps,))
        finally:
            ex.shutdown()
    else:
        from horovod_tpu.ray import RayExecutor
        ex = RayExecutor(num_workers=args.workers)
        ex.start()
        try:
            results = ex.run(train_fn, args=(steps,))
        finally:
            ex.shutdown()
    print(f"final losses per rank: {results}")
    assert all(l < 1.0 for l in results)
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
