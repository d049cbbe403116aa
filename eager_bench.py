#!/usr/bin/env python
"""Eager multi-process negotiation overhead microbenchmark (np=2).

Measures what one eager collective costs when every op must be negotiated
through the dynamic engine over the HTTP KV (two real worker processes,
CPU backend — the negotiation is host-side, so the accelerator is
irrelevant). The reference's equivalent cost is one in-process
``RunLoopOnce`` cycle (1 ms default ``CycleTimeMs``,
``/root/reference/horovod/common/operations.cc:499-506``); over a KV
transport each cycle is an HTTP gather round, so the floor is the KV RTT.

Prints ONE JSON line:
  {"metric": "eager_negotiated_allreduce_ops_per_sec", "value": ...,
   "adaptive_cycle": {...}, "fixed_cycle": {...}}

comparing the event-driven adaptive tick (default; fresh enqueues wake
the cycle loop, in-flight work lowers the pace floor to
``HVD_PENDING_CYCLE_TIME``) against the fixed 20 ms cadence
(``HVD_ADAPTIVE_CYCLE=0``). Where the eager path stops being appropriate
is documented in docs/benchmarks.md — these numbers are the basis.
"""

import json
import os
import sys


def _worker(iters: int, warmup: int):
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import time

    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    x = jnp.ones((1024,), jnp.float32)
    for i in range(warmup):
        jax.block_until_ready(hvd.allreduce(x, name=f"warmup_{i}"))
    t0 = time.perf_counter()
    for i in range(iters):
        jax.block_until_ready(hvd.allreduce(x, name=f"bench_{i}"))
    dt = time.perf_counter() - t0

    # negotiation alone (no collective execution): the engine-service cost
    # an eager op pays on top of the XLA program
    from horovod_tpu import engine_service
    from horovod_tpu.dynamic import REQ_ALLREDUCE
    svc = engine_service.get_service()
    t0 = time.perf_counter()
    for i in range(iters):
        svc.negotiate(f"neg_{i}", REQ_ALLREDUCE, shape=(1024,))
    dneg = time.perf_counter() - t0
    return {"ops_per_sec": iters / dt, "ms_per_op": dt / iters * 1e3,
            "negotiations_per_sec": iters / dneg,
            "ms_per_negotiation": dneg / iters * 1e3}


def _measure(adaptive: bool, iters: int, warmup: int) -> dict:
    """Two worker processes on this host, both CPU-pinned
    (``JAX_PLATFORMS=cpu``): one host's chips belong to one process, and
    the negotiation measured here is host-side."""
    from horovod_tpu.runner import run as hvd_run

    env = {
        "JAX_PLATFORMS": "cpu",
        "HVD_ADAPTIVE_CYCLE": "1" if adaptive else "0",
    }
    results = hvd_run(_worker, args=(iters, warmup), np=2, env=env,
                      start_timeout=300.0)
    # both ranks time the same negotiated sequence; report rank 0
    return {k: round(v, 3) for k, v in results[0].items()}


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--iters", type=int, default=200)
    parser.add_argument("--warmup", type=int, default=20)
    args = parser.parse_args()

    adaptive = _measure(True, args.iters, args.warmup)
    fixed = _measure(False, args.iters, args.warmup)
    print(json.dumps({
        "metric": "eager_negotiated_allreduce_ops_per_sec",
        "value": adaptive["ops_per_sec"],
        "unit": "ops/sec",
        "np": 2,
        "payload_bytes": 4096,
        "adaptive_cycle": adaptive,
        "fixed_cycle": fixed,
        "speedup_vs_fixed": round(
            adaptive["ops_per_sec"] / fixed["ops_per_sec"], 2),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
