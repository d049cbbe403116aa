#!/usr/bin/env python
"""Synthetic throughput benchmark — the TPU-native mirror of the
reference's ``examples/tensorflow2/tensorflow2_synthetic_benchmark.py``
(ResNet-50 on synthetic ImageNet batches, DistributedGradientTape,
``--fp16-allreduce``). This example shows the user-facing recipe and
prints rates for whatever backend it runs on; the repo's measurements
come from ``benchmark/run.py`` on the chip (docs/benchmarks.md).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/synthetic_benchmark.py --model ResNet18 \
        --image-size 32 --batch-size 16 --num-iters 3
"""

import argparse
import time

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu import models as hvd_models
from horovod_tpu.models.train import classifier_trainer


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="ResNet50",
                        choices=["ResNet18", "ResNet34", "ResNet50",
                                 "ResNet101", "ResNet152"])
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-chip batch size (reference default)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--num-warmup", type=int, default=2)
    parser.add_argument("--fp16-allreduce", action="store_true",
                        help="compress gradients on the wire (reference "
                             "--fp16-allreduce)")
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    if args.smoke:
        args.model, args.image_size = "ResNet18", 32
        args.batch_size, args.num_iters, args.num_warmup = 4, 2, 1

    hvd.init()
    n = hvd.size()

    model = getattr(hvd_models, args.model)(
        num_classes=1000, dtype=jnp.bfloat16, axis_name=None)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  compression=compression)
    # broadcast_parameters + jit(shard_map(step, mesh=hvd.mesh(), ...)):
    # the same builder chip_smoke.py runs
    step, (params, batch_stats, opt_state), (x, y) = classifier_trainer(
        model, tx, image_size=args.image_size,
        batch_per_chip=args.batch_size)

    for _ in range(args.num_warmup):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, x, y)
    jax.block_until_ready((params, loss))
    elapsed = time.perf_counter() - t0

    img_sec = args.num_iters * args.batch_size * n / elapsed
    if hvd.rank() == 0:
        print(f"Model: {args.model}, batch {args.batch_size}/chip, "
              f"{n} x {jax.devices()[0].device_kind}")
        print(f"Total img/sec on {n} chip(s): {img_sec:.1f} "
              f"({img_sec / n:.1f} per chip)")
        print("OK")


if __name__ == "__main__":
    main()
