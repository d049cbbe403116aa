#!/usr/bin/env python
"""Training on Spark executors via ``horovod_tpu.spark.run``.

The Spark analog of ``examples/mnist.py`` (reference ``horovod.spark.run``
usage, ``/root/reference/docs/spark.rst``): one barrier-mode task per
rank, results returned rank-ordered.

Run on a machine with pyspark installed:
    python examples/spark_train.py

Without pyspark (CI smoke): prints SKIP and exits 0.
"""

import argparse
import sys


def train_fn(steps: int = 10):
    """One rank: the usual five-line pattern. Defined HERE (the __main__
    module) and fully self-contained, so pyspark's cloudpickle serializes
    it by value — importing it from a sibling example module would make
    executors try `import ray_train`, which is only on the driver's
    sys.path."""
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
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    rng = np.random.default_rng(hvd.rank())
    w_true = jnp.asarray([[2.0], [-3.0]])
    params = hvd.broadcast_parameters({"w": jnp.zeros((2, 1))}, root_rank=0)
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
    parser.add_argument("--num-proc", type=int, default=2)
    parser.add_argument("--smoke", action="store_true",
                        help="fewer training steps")
    args = parser.parse_args()

    try:
        from pyspark.sql import SparkSession
    except ImportError:
        print("SKIP: pyspark not installed (install pyspark to run this "
              "example)")
        return 0

    import horovod_tpu.spark as hvd_spark

    spark = (SparkSession.builder.master(f"local[{args.num_proc}]")
             .appName("horovod_tpu-spark-example").getOrCreate())
    try:
        results = hvd_spark.run(train_fn, args=(3 if args.smoke else 10,),
                                num_proc=args.num_proc)
    finally:
        spark.stop()
    print(f"final losses per rank: {results}")
    assert all(l < 1.0 for l in results)
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
