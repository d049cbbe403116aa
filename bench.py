#!/usr/bin/env python
"""Synthetic ResNet-50 training benchmark, the TPU-native mirror of the
reference's headline harness
(``/root/reference/examples/tensorflow2/tensorflow2_synthetic_benchmark.py``:
ResNet-50, synthetic ImageNet batches, SGD, DistributedGradientTape).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N, ...}

Every line names the device it ran on (``platform``, ``device_kind``,
``n_chips``). The lane needs a TPU: without one it fails before building
the model, unless the caller pins the CPU explicitly with
``JAX_PLATFORMS=cpu`` (a correctness run; ``mfu`` is then ``null`` and the
line says ``"platform": "cpu"``).

Fields:
  * ``mfu`` — model FLOPs utilization: per-chip training FLOPs per step
    (XLA's own ``cost_analysis()`` of the compiled program) divided by
    step time and the chip's peak bf16 FLOP/s from ``PEAK_BF16_FLOPS``.
  * ``step_time_ms`` — {mean, p50, min, max} over timed WINDOWS of chained
    steps (each window: several steps dispatched back-to-back with a data
    dependency — step i+1 consumes step i's outputs — then one device
    sync). The chained window is how steady-state training actually runs
    and cannot hide a slow step (the chain serializes them) or invent a
    fast one (min is a window mean); a host sync per step would add the
    host round trip to every step.
  * ``loss_first``/``loss_last``/``loss_decreased`` — the optimizer must
    actually be training; a harness that times a broken step is timing
    nothing.
  * ``baseline`` — what ``vs_baseline`` compares against, spelled out: the
    reference's only published absolute throughput is tf_cnn_benchmarks
    ResNet-101 on 2017-era Pascal GPUs, 1656.82 images/sec over 16 GPUs =
    103.55 images/sec/GPU (``/root/reference/docs/benchmarks.rst:30-43``).
    A modern TPU chip beating a 2017 GPU by a large factor is expected, not
    impressive — the honest headline metric is ``mfu`` and the scaling
    efficiency harness (``scaling_bench.py``).

The train step is ``horovod_tpu.models.train.classifier_trainer`` (shared
with ``chip_smoke.py`` and ``examples/synthetic_benchmark.py``):
params/batch-stats/opt-state buffers are donated, so the update writes in
place instead of copying ~300 MB of state per step.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50
from horovod_tpu.models.train import classifier_trainer
from horovod_tpu.utils.compile_cache import place_compile_cache

BASELINE_IMG_PER_SEC_PER_CHIP = 1656.82 / 16  # docs/benchmarks.rst:30-43
BASELINE_DESC = ("reference tf_cnn_benchmarks ResNet-101, 16x Pascal GPU "
                 "(2017), 103.55 images/sec/GPU; docs/benchmarks.rst:30-43")

# ResNet-50 @ 224x224: ~4.1 GMACs forward = 8.2 GFLOPs; backward ~2x forward
# => ~24.6 GFLOPs per image per training step. The MODEL-flops count MFU is
# scored from under --remat (the compiled program's count then includes the
# recomputed forward).
ANALYTIC_RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 8.2e9

# Peak dense bf16 FLOP/s per chip, keyed by the exact jax device_kind
# (Google Cloud TPU documentation, per-chip figures).
PEAK_BF16_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def chip_peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``. A kind that is not in the table is
    an error, not a guess: a utilization scored against the wrong peak
    reads as a measurement."""
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak bf16 FLOP/s on record for device_kind "
            f"{device.device_kind!r}; add it to bench.PEAK_BF16_FLOPS with "
            "its source") from None


def require_tpu(what: str):
    """The first device, after checking that ``what`` is running where its
    numbers mean something: on a TPU, or on a CPU the caller pinned
    explicitly with ``JAX_PLATFORMS=cpu``. Anything else — no accelerator
    found, jax quietly on the CPU — fails here, in seconds, before any
    model is built."""
    device = jax.devices()[0]
    if device.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"{what} needs a TPU but jax found platform "
            f"{device.platform!r} ({device.device_kind}). Set "
            "JAX_PLATFORMS=cpu to run it on the CPU on purpose.")
    return device


def _microbench_mesh():
    """Shared setup for the host-side microbenches (--dispatch-bench /
    --cycle-bench / --pipeline-bench / ...): ``hvd`` initialized on a
    virtual 8-device CPU mesh. They time host-side dispatch, so they run
    only where the caller pinned the CPU."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "the host-side microbench lanes run on a virtual CPU mesh: "
            "set JAX_PLATFORMS=cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import horovod_tpu as hvd
    hvd.init()
    return hvd, hvd.size()


def _median_ms(one_round, iters: int, divisor: int = 1) -> float:
    """Median wall time (ms) per unit over 5 chunks of back-to-back
    rounds (each chunk, like a training loop's steady state, is timed
    around a burst of rounds); two untimed rounds warm compile/plan
    caches first. ``divisor`` converts a round into per-call/per-tensor
    units."""
    jax.block_until_ready(one_round())
    jax.block_until_ready(one_round())
    chunks = 5
    per = max(1, iters // chunks)
    times = []
    for _ in range(chunks):
        t0 = time.perf_counter()
        for _ in range(per):
            outs = one_round()
        jax.block_until_ready(outs)
        times.append((time.perf_counter() - t0) / (per * divisor))
    return float(np.median(times) * 1e3)


def _pipeline_summary():
    """Overlap figures persisted into EVERY bench payload (ISSUE 6
    satellite): the perf trajectory must track whether communication is
    actually hidden, not just wall time."""
    import horovod_tpu as hvd
    p = hvd.fusion_stats()["pipeline"]
    return {
        "overlap_ratio": round(p["overlap_ratio"], 3),
        "inflight_peak": int(p["inflight_peak"]),
        "slot_occupancy": round(p["slot_occupancy"], 3),
        "device_wait_ms": round(p["device_wait_ms"], 3),
    }


def run_dispatch_bench(args) -> None:
    """Per-call eager dispatch overhead microbench (CPU backend, virtual
    8-chip mesh): repeated same-signature ``grouped_allreduce`` with the
    dispatch plan cache off vs on. The payload is deliberately tiny so the
    Python dispatch between XLA launches — mode probing, bundle
    canonicalization, mesh hashing, fusion bucketing, negotiation/autotune
    bookkeeping — dominates the wall time; this is exactly the steady-state
    latency the plan cache (ops/dispatch_cache.py, the ResponseCache HIT
    twin) removes. Prints ONE JSON line; ``value`` is the percent reduction
    in per-call wall time."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu.ops import dispatch_cache

    hvd, n = _microbench_mesh()
    size = args.dispatch_size
    tensors = [
        hvd.per_rank([jnp.full((size,), float((r + 1) * (i + 1)), jnp.float32)
                      for r in range(n)])
        for i in range(args.dispatch_tensors)
    ]

    def one_call():
        return hvd.grouped_allreduce(tensors, op=hvd.Sum)

    prev = os.environ.get("HVD_CACHE_CAPACITY")
    try:
        os.environ["HVD_CACHE_CAPACITY"] = "0"
        ref_out = [np.asarray(o) for o in one_call()]
        off_ms = _median_ms(one_call, args.dispatch_iters)
        os.environ["HVD_CACHE_CAPACITY"] = "1024"
        dispatch_cache.reset()
        on_out = [np.asarray(o) for o in one_call()]
        on_ms = _median_ms(one_call, args.dispatch_iters)
        stats = dispatch_cache.stats()
    finally:
        if prev is None:
            os.environ.pop("HVD_CACHE_CAPACITY", None)
        else:
            os.environ["HVD_CACHE_CAPACITY"] = prev

    numerics_match = all(np.allclose(a, b) for a, b in zip(ref_out, on_out))
    reduction = (off_ms - on_ms) / off_ms * 100.0 if off_ms else 0.0
    print(json.dumps({
        "metric": "eager_dispatch_plan_cache_reduction",
        "value": round(reduction, 1),
        "unit": "% reduction in per-call eager dispatch wall time",
        "cache_off": {"ms_per_call": round(off_ms, 4)},
        "cache_on": {"ms_per_call": round(on_ms, 4),
                     "stats": stats},
        "numerics_match": bool(numerics_match),
        "pipeline_overlap": _pipeline_summary(),
        "baseline": "same-signature grouped_allreduce, plan cache disabled "
                    "via HVD_CACHE_CAPACITY=0 (the pre-cache dispatch path)",
        "config": {"op": "grouped_allreduce", "tensors": args.dispatch_tensors,
                   "elems_per_tensor": size, "dtype": "float32",
                   "iters": args.dispatch_iters, "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_cycle_bench(args) -> None:
    """Cross-call fusion scheduler microbench (CPU backend, virtual 8-chip
    mesh): N small per-tensor ``allreduce_async`` + synchronize, scheduler
    ON (queued submissions coalesce into one grouped flush through the
    plan cache) vs OFF (``HVD_CYCLE_TIME=0``: every async call dispatches
    its own collective immediately — the pre-scheduler behavior). This is
    the reference's headline mechanism (the background cycle fusing
    independently-submitted small tensors, operations.cc:385-806) applied
    to the eager per-parameter gradient loop. Prints ONE JSON line;
    ``value`` is the percent reduction in per-tensor wall time."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu.ops import dispatch_cache, fusion_cycle

    hvd, n = _microbench_mesh()
    count = args.cycle_tensors
    elems = args.cycle_size // 4  # float32 -> 4 bytes/elem
    tensors = [
        hvd.per_rank([jnp.full((elems,), float((r + 1) * (i + 1)),
                               jnp.float32) for r in range(n)])
        for i in range(count)
    ]

    def one_round():
        handles = [hvd.allreduce_async(t, op=hvd.Sum) for t in tensors]
        return [h.synchronize() for h in handles]

    def set_mode(on: bool) -> None:
        # ON: both cycle knobs pinned long so every flush comes from the
        # synchronize (deterministic full-coalesce measurement) — a
        # mid-chunk timer fire on a share-throttled CI box would
        # otherwise split batches and add preemption noise; the timer
        # path itself is covered by tests/test_fusion_cycle.py.
        os.environ["HVD_CYCLE_TIME"] = "500" if on else "0"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"

    def timed_chunk(per):
        t0 = time.perf_counter()
        for _ in range(per):
            outs = one_round()
        jax.block_until_ready(outs)
        return (time.perf_counter() - t0) / (per * count)

    prev = {k: os.environ.get(k)
            for k in ("HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME")}
    try:
        # ABBA-interleaved on/off chunks (the --metrics-bench method,
        # adopted after the sequential version read 10-16% against its
        # 40% floor on slower boxes even at baseline — box drift between
        # the two long mode blocks swamped the scheduler's own delta):
        # both modes see the same load drift pair by pair, alternating
        # which side of the pair runs first, so the comparison measures
        # the scheduler, not the box. Plans for both modes coexist in
        # the dispatch cache after one warm round each.
        dispatch_cache.reset()
        fusion_cycle.reset()
        set_mode(False)  # immediate per-call dispatch (still plan-cached
        # — this measures the scheduler's win on top of PR 1's cache)
        ref_out = [np.asarray(o) for o in one_round()]
        set_mode(True)
        on_out = [np.asarray(o) for o in one_round()]
        chunks = max(args.cycle_iters // 5, 4)
        per = 5
        on_times, off_times = [], []
        for i in range(chunks):
            order = ((False, True) if i % 2 == 0 else (True, False))
            for on in order:
                set_mode(on)
                (on_times if on else off_times).append(timed_chunk(per))
        stats = hvd.fusion_stats()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    off_ms = float(np.median(off_times) * 1e3)
    on_ms = float(np.median(on_times) * 1e3)
    numerics_match = all(np.allclose(a, b) for a, b in zip(ref_out, on_out))
    reduction = (off_ms - on_ms) / off_ms * 100.0 if off_ms else 0.0
    print(json.dumps({
        "metric": "eager_cycle_fusion_reduction",
        "value": round(reduction, 1),
        "unit": "% reduction in per-tensor async allreduce wall time",
        "scheduler_off": {"ms_per_tensor": round(off_ms, 4)},
        "scheduler_on": {"ms_per_tensor": round(on_ms, 4),
                         "fusion_stats": {
                             k: stats[k] for k in (
                                 "flushes", "flushed_tensors", "dispatches",
                                 "tensors_per_flush", "coalesce_ratio")}},
        "numerics_match": bool(numerics_match),
        "pipeline_overlap": _pipeline_summary(),
        "coalesce_ratio": round(stats["coalesce_ratio"], 2),
        "baseline": "same per-tensor allreduce_async loop with "
                    "HVD_CYCLE_TIME=0 (immediate dispatch, scheduler off; "
                    "dispatch plan cache ON in both modes), strictly "
                    "ABBA-interleaved chunks so box drift cancels",
        "config": {"op": "allreduce_async", "tensors": count,
                   "bytes_per_tensor": args.cycle_size, "dtype": "float32",
                   "iters": args.cycle_iters, "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_metrics_bench(args) -> None:
    """Metrics-overhead microbench (docs/metrics.md overhead contract):
    the SAME per-tensor ``allreduce_async`` + synchronize stream as
    --cycle-bench — the path carrying the registry's hot instruments
    (fusion flush/enqueue counters, pending gauge, dispatch-cache hits,
    KV ops when a service runs) — timed with the registry force-ENABLED
    vs force-DISABLED in strictly interleaved A/B chunks, so box drift
    cancels. Prints ONE JSON line; ``value`` is the percent overhead of
    metrics ON over OFF (ci.sh gates <= 3%)."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu import metrics as _metrics

    hvd, n = _microbench_mesh()
    count = args.metrics_tensors
    elems = args.metrics_size // 4  # float32 -> 4 bytes/elem
    tensors = [
        hvd.per_rank([jnp.full((elems,), float((r + 1) * (i + 1)),
                               jnp.float32) for r in range(n)])
        for i in range(count)
    ]

    def one_round():
        handles = [hvd.allreduce_async(t, op=hvd.Sum) for t in tensors]
        return [h.synchronize() for h in handles]

    def timed_chunk(per):
        t0 = time.perf_counter()
        for _ in range(per):
            outs = one_round()
        jax.block_until_ready(outs)
        return (time.perf_counter() - t0) / (per * count)

    prev = {k: os.environ.get(k)
            for k in ("HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME")}
    try:
        # Cycle knobs pinned long (the --cycle-bench rationale): every
        # flush comes from the synchronize trigger, so a mid-chunk
        # timer fire on a share-throttled CI box cannot split batches
        # and swamp the nanoseconds under measurement.
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        # warm compile/plan caches in both modes
        _metrics.set_enabled(True)
        on_ref = [np.asarray(o) for o in one_round()]
        _metrics.set_enabled(False)
        off_ref = [np.asarray(o) for o in one_round()]
        chunks = max(args.metrics_iters // 5, 5)
        per = 5
        on_times, off_times = [], []
        for i in range(chunks):
            # ABBA interleave: alternate which mode runs first in each
            # pair, so warm-up/throttling drift within a pair cancels
            # instead of systematically flattering the second side
            order = ((False, True) if i % 2 == 0 else (True, False))
            for enabled in order:
                _metrics.set_enabled(enabled)
                (on_times if enabled else off_times).append(
                    timed_chunk(per))
    finally:
        _metrics.set_enabled(None)
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    off_ms = float(np.median(off_times) * 1e3)
    on_ms = float(np.median(on_times) * 1e3)
    overhead = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    numerics_match = all(np.allclose(a, b)
                         for a, b in zip(on_ref, off_ref))
    print(json.dumps({
        "metric": "metrics_registry_overhead",
        "value": round(overhead, 2),
        "unit": "% per-tensor wall-time overhead of HVD_METRICS=1 vs 0",
        "metrics_off": {"ms_per_tensor": round(off_ms, 4)},
        "metrics_on": {"ms_per_tensor": round(on_ms, 4)},
        "numerics_match": bool(numerics_match),
        "baseline": "identical allreduce_async stream, registry "
                    "force-disabled (hot instruments no-op), strictly "
                    "interleaved A/B chunks",
        "config": {"op": "allreduce_async", "tensors": count,
                   "bytes_per_tensor": args.metrics_size,
                   "chunks": chunks, "rounds_per_chunk": per,
                   "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_conformance_bench(args) -> None:
    """Conformance-recorder overhead microbench (docs/conformance.md cost
    contract): the SAME per-tensor ``allreduce_async`` + synchronize
    stream as --metrics-bench — every synchronize-triggered flush feeds
    the recorder a ``flush`` event, and every cold dispatch a
    ``plan_store`` — timed with the recorder force-ENABLED vs
    force-DISABLED in strictly ABBA-interleaved chunks, so box drift
    cancels. Prints ONE JSON line; ``value`` is the percent overhead of
    HVD_CONFORMANCE=1 over 0 (ci.sh gates <= 3%)."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu import conformance as _conformance

    hvd, n = _microbench_mesh()
    count = args.conformance_tensors
    elems = args.conformance_size // 4  # float32 -> 4 bytes/elem
    tensors = [
        hvd.per_rank([jnp.full((elems,), float((r + 1) * (i + 1)),
                               jnp.float32) for r in range(n)])
        for i in range(count)
    ]

    def one_round():
        handles = [hvd.allreduce_async(t, op=hvd.Sum) for t in tensors]
        return [h.synchronize() for h in handles]

    def timed_chunk(per):
        t0 = time.perf_counter()
        for _ in range(per):
            outs = one_round()
        jax.block_until_ready(outs)
        return (time.perf_counter() - t0) / (per * count)

    prev = {k: os.environ.get(k)
            for k in ("HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME")}
    try:
        # Cycle knobs pinned long (the --cycle-bench rationale): every
        # flush comes from the synchronize trigger, so a mid-chunk timer
        # fire on a share-throttled CI box cannot split batches and
        # swamp the nanoseconds under measurement. Pinned knobs are also
        # the recorder's own comparability precondition
        # (docs/conformance.md "What the flush hash covers").
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        # warm compile/plan caches in both modes
        _conformance.set_enabled(True)
        on_ref = [np.asarray(o) for o in one_round()]
        _conformance.set_enabled(False)
        off_ref = [np.asarray(o) for o in one_round()]
        chunks = max(args.conformance_iters // 5, 5)
        per = 5
        on_times, off_times = [], []
        for i in range(chunks):
            # ABBA interleave: alternate which mode runs first in each
            # pair, so warm-up/throttling drift within a pair cancels
            # instead of systematically flattering the second side
            order = ((False, True) if i % 2 == 0 else (True, False))
            for enabled in order:
                _conformance.set_enabled(enabled)
                (on_times if enabled else off_times).append(
                    timed_chunk(per))
        stats = _conformance.conformance_stats()
    finally:
        _conformance.set_enabled(None)
        _conformance.reset()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    off_ms = float(np.median(off_times) * 1e3)
    on_ms = float(np.median(on_times) * 1e3)
    overhead = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    numerics_match = all(np.allclose(a, b)
                         for a, b in zip(on_ref, off_ref))
    print(json.dumps({
        "metric": "conformance_recorder_overhead",
        "value": round(overhead, 2),
        "unit": "% per-tensor wall-time overhead of HVD_CONFORMANCE=1 vs 0",
        "conformance_off": {"ms_per_tensor": round(off_ms, 4)},
        "conformance_on": {"ms_per_tensor": round(on_ms, 4),
                           "events": stats["events"],
                           "by_stream": stats["by_stream"]},
        "numerics_match": bool(numerics_match),
        "baseline": "identical allreduce_async stream, recorder "
                    "force-disabled (every hook one cached-bool read + "
                    "early return), strictly ABBA-interleaved chunks",
        "config": {"op": "allreduce_async", "tensors": count,
                   "bytes_per_tensor": args.conformance_size,
                   "chunks": chunks, "rounds_per_chunk": per,
                   "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_pipeline_bench(args) -> None:
    """Pipelined flush executor + chunk pipeline microbench (CPU backend,
    virtual 8-chip mesh): a stream of LARGE (default 4 MiB) per-tensor
    ``allreduce_async`` submissions that the scheduler coalesces into one
    flush per round — the cycle scheduler's steady state for a training
    step's gradients. OFF = ``HVD_MAX_INFLIGHT_FLUSHES=1`` (the
    synchronous executor: the flush runs inline on the triggering thread
    and its whole multi-MiB fused buffer is ONE monolithic wire program —
    the PR-2 behavior). ON = 2 in-flight slots +
    ``HVD_PIPELINE_THRESHOLD``/``HVD_PIPELINE_CHUNKS`` chunking: the
    fused buffer dispatches as back-to-back chunk programs whose
    collectives pipeline across the per-device execution queues while the
    executor overlaps the next flush's fuse with in-flight collectives.
    Fuse and split stages are identical work in both modes; the measured
    delta is the wire-stage granularity (plus executor overhead, charged
    against the pipelined side). Prints ONE JSON line; ``value`` is the
    percent reduction in per-round wall time."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu.ops import dispatch_cache, fusion_cycle

    hvd, n = _microbench_mesh()
    count = args.pipeline_tensors
    elems = args.pipeline_size // 4  # float32 -> 4 bytes/elem
    tensors = [
        hvd.per_rank([jnp.full((elems,), float(r + 1) * 0.5 ** i,
                               jnp.float32) for r in range(n)])
        for i in range(count)
    ]

    def one_round():
        handles = [hvd.allreduce_async(t, op=hvd.Sum) for t in tensors]
        return [h.synchronize() for h in handles]

    knobs = ("HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME",
             "HVD_FUSION_THRESHOLD", "HVD_MAX_INFLIGHT_FLUSHES",
             "HVD_PIPELINE_THRESHOLD", "HVD_PIPELINE_CHUNKS")
    prev = {k: os.environ.get(k) for k in knobs}
    try:
        # both modes: timer quiet and fusion threshold unreachable, so
        # every round's submissions coalesce into ONE synchronize-
        # triggered flush with an identical composition — only the
        # executor and the wire-program granularity differ.
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        os.environ["HVD_FUSION_THRESHOLD"] = str(1 << 30)
        os.environ["HVD_MAX_INFLIGHT_FLUSHES"] = "1"
        dispatch_cache.reset()
        fusion_cycle.reset()
        ref_out = [np.asarray(o) for o in one_round()]
        off_ms = _median_ms(one_round, args.pipeline_iters)
        os.environ["HVD_MAX_INFLIGHT_FLUSHES"] = "2"
        os.environ["HVD_PIPELINE_THRESHOLD"] = str(args.pipeline_size)
        os.environ["HVD_PIPELINE_CHUNKS"] = str(args.pipeline_chunks)
        dispatch_cache.reset()
        fusion_cycle.reset()
        on_out = [np.asarray(o) for o in one_round()]
        on_ms = _median_ms(one_round, args.pipeline_iters)
        stats = hvd.fusion_stats()
        cache_stats = dispatch_cache.stats()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    numerics_match = all(np.allclose(a, b) for a, b in zip(ref_out, on_out))
    reduction = (off_ms - on_ms) / off_ms * 100.0 if off_ms else 0.0
    print(json.dumps({
        "metric": "eager_pipeline_flush_reduction",
        "value": round(reduction, 1),
        "unit": "% reduction in wall time per stream of large async "
                "allreduces",
        "synchronous": {"ms_per_round": round(off_ms, 4)},
        "pipelined": {"ms_per_round": round(on_ms, 4),
                      "pipeline": stats["pipeline"],
                      "chunked_plan_builds": cache_stats["chunked_builds"]},
        "numerics_match": bool(numerics_match),
        "pipeline_overlap": _pipeline_summary(),
        "overlap_ratio": round(stats["pipeline"]["overlap_ratio"], 3),
        "slot_occupancy": round(stats["pipeline"]["slot_occupancy"], 3),
        "baseline": "same large-tensor allreduce_async stream with "
                    "HVD_MAX_INFLIGHT_FLUSHES=1 (synchronous flush "
                    "executor, monolithic wire programs — the "
                    "pre-pipeline behavior)",
        "config": {"op": "allreduce_async", "tensors": count,
                   "bytes_per_tensor": args.pipeline_size,
                   "chunks": args.pipeline_chunks, "dtype": "float32",
                   "iters": args.pipeline_iters, "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_overlap_bench(args) -> None:
    """Flush-level overlap microbench (CPU backend, virtual 8-chip mesh):
    a stream of medium async allreduces where EVERY submission is its own
    threshold-triggered flush — the multi-flush stream the pipelined
    executor exists for. The ``--pipeline-bench`` stream coalesces each
    round into ONE synchronize-triggered flush, which by construction can
    never hold two flushes in flight (BENCH_r08/r09's ``overlap_ratio:
    0.0`` was the metric honestly reporting that workload, compounded by
    post-retirement depth sampling — ISSUE 6). Chunking is disabled so
    the measured effect is purely flush k+1 dispatching while flush k's
    collective is in flight. Prints ONE JSON line; ci.sh gates
    ``overlap_ratio > 0`` with >= 2 slots."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu.ops import dispatch_cache, fusion_cycle

    hvd, n = _microbench_mesh()
    count = args.overlap_tensors
    elems = args.overlap_size // 4  # float32 -> 4 bytes/elem
    tensors = [
        hvd.per_rank([jnp.full((elems,), float(r + 1) * 0.25 ** i,
                               jnp.float32) for r in range(n)])
        for i in range(count)
    ]

    def one_round():
        handles = [hvd.allreduce_async(t, op=hvd.Sum) for t in tensors]
        return [h.synchronize() for h in handles]

    knobs = ("HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME",
             "HVD_FUSION_THRESHOLD", "HVD_MAX_INFLIGHT_FLUSHES",
             "HVD_PIPELINE_THRESHOLD")
    prev = {k: os.environ.get(k) for k in knobs}
    try:
        # timer quiet; threshold of 1 byte = every submission drains its
        # own flush at enqueue; chunking off (threshold unreachable) so
        # only flush-level overlap differs between the modes.
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        os.environ["HVD_FUSION_THRESHOLD"] = "1"
        os.environ["HVD_PIPELINE_THRESHOLD"] = str(1 << 30)
        os.environ["HVD_MAX_INFLIGHT_FLUSHES"] = "1"
        dispatch_cache.reset()
        fusion_cycle.reset()
        ref_out = [np.asarray(o) for o in one_round()]
        off_ms = _median_ms(one_round, args.overlap_iters)
        os.environ["HVD_MAX_INFLIGHT_FLUSHES"] = str(args.overlap_slots)
        dispatch_cache.reset()
        fusion_cycle.reset()
        on_out = [np.asarray(o) for o in one_round()]
        on_ms = _median_ms(one_round, args.overlap_iters)
        stats = hvd.fusion_stats()
        summary = _pipeline_summary()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    numerics_match = all(np.allclose(a, b) for a, b in zip(ref_out, on_out))
    reduction = (off_ms - on_ms) / off_ms * 100.0 if off_ms else 0.0
    print(json.dumps({
        "metric": "eager_flush_overlap_ratio",
        "value": summary["overlap_ratio"],
        "unit": "fraction of flushes dispatched while >=1 earlier flush "
                "was still in flight on device",
        "wall_time_reduction_pct": round(reduction, 1),
        "synchronous": {"ms_per_round": round(off_ms, 4)},
        "pipelined": {"ms_per_round": round(on_ms, 4),
                      "pipeline": stats["pipeline"]},
        "numerics_match": bool(numerics_match),
        "pipeline_overlap": summary,
        "baseline": "same per-flush allreduce_async stream with "
                    "HVD_MAX_INFLIGHT_FLUSHES=1 (synchronous flush "
                    "executor; chunking disabled in both modes)",
        "config": {"op": "allreduce_async", "tensors": count,
                   "bytes_per_tensor": args.overlap_size,
                   "slots": args.overlap_slots, "dtype": "float32",
                   "iters": args.overlap_iters, "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def _step_bench_case(kind, hvd, n, args):
    """One eager data-parallel training setup: returns (label, local_fn,
    state0 host trees, sharded inputs, grad_bytes). ``local_fn`` is the
    jitted shard_map'd LOCAL backward (no collectives inside): per-rank
    gradients come back stacked on the leading rank axis, exactly a
    PerRank layout — gradient sync then happens EAGERLY through
    DistributedOptimizer, which is the path under test."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = hvd.mesh()
    axis = hvd.axis_name()
    batch = args.step_batch

    if kind == "resnet50":
        from horovod_tpu.models import ResNet50
        num_classes = 100
        img = args.step_image_size
        # ResNet-50 (the repo's benchmark workhorse): ~95 MB of f32
        # gradients — squarely in the bucketing regime (several 64 MiB
        # production buckets; several 16 MiB bench buckets). Tiny input
        # resolution keeps the conv compute CI-sized without shrinking
        # the gradient payload, which is what this bench stresses.
        model = ResNet50(num_classes=num_classes, dtype=jnp.float32,
                         axis_name=None)  # BN stats stay rank-local
        x_host = np.random.default_rng(0).standard_normal(
            (n * batch, img, img, 3)).astype(np.float32)
        y_host = np.random.default_rng(1).integers(
            0, num_classes, size=(n * batch,))
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, img, img, 3), jnp.float32),
                               train=True)
        params0 = variables["params"]
        stats0 = variables["batch_stats"]

        def local(p, stats_i, x_i, y_i):
            def loss_fn(p):
                logits, mut = model.apply(
                    {"params": p, "batch_stats": stats_i}, x_i,
                    train=True, mutable=["batch_stats"])
                one_hot = jax.nn.one_hot(y_i, num_classes)
                loss = -jnp.mean(jnp.sum(
                    one_hot * jax.nn.log_softmax(logits), -1))
                return loss, mut["batch_stats"]
            (loss, new_stats), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p)
            return g, new_stats, loss
    else:
        from horovod_tpu.models import TransformerConfig, TransformerLM
        seq = args.step_seq_len
        # vocab-heavy LM: the 32k-vocab embedding + lm_head gradients
        # (~33 MB each) put the ~75 MB grad tree in the bucketing
        # regime while the 2-layer trunk keeps CI compute small
        cfg = TransformerConfig(vocab_size=32768, num_layers=2,
                                num_heads=8, d_model=256, d_ff=1024,
                                max_seq_len=seq, dtype=jnp.float32)
        model = TransformerLM(cfg)
        x_host = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(n * batch, seq))
        y_host = x_host  # next-token objective shifts inside the loss
        params0 = model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, seq), jnp.int32))["params"]
        stats0 = {}

        def local(p, stats_i, x_i, y_i):
            del stats_i

            def loss_fn(p):
                logits = model.apply({"params": p}, x_i)
                tgt = jax.nn.one_hot(y_i[:, 1:], cfg.vocab_size)
                return -jnp.mean(jnp.sum(
                    tgt * jax.nn.log_softmax(logits[:, :-1]), -1))
            loss, g = jax.value_and_grad(loss_fn)(p)
            return g, {}, loss

    def shard_fn(p, stats, x_i, y_i):
        stats_i = jax.tree.map(lambda a: a[0], stats)
        g, new_stats, loss = local(p, stats_i, x_i, y_i)
        return (jax.tree.map(lambda a: a[None], g),
                jax.tree.map(lambda a: a[None], new_stats),
                loss[None])

    local_fn = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False))
    x = jax.device_put(x_host, NamedSharding(mesh, P(axis)))
    y = jax.device_put(y_host, NamedSharding(mesh, P(axis)))
    grad_bytes = sum(int(np.prod(l.shape)) * 4
                     for l in jax.tree.leaves(params0))
    return local_fn, params0, stats0, x, y, grad_bytes


def _run_step_mode(hvd, local_fn, params0, stats0, x, y, bucket_bytes,
                   iters):
    """One timing pass of the eager DP step (HVD_BUCKET_BYTES pinned):
    per-step wall times with every step materialized to completion (all
    updated param leaves ready — the reference's eager
    ``optimizer.step()`` semantics, where per-bucket completion
    pipelining lands), plus the params after the warmup step from the
    fixed init (numerics probe) and the overlap summary."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import dispatch_cache, fusion_cycle

    mesh = hvd.mesh()
    axis = hvd.axis_name()
    os.environ["HVD_BUCKET_BYTES"] = str(bucket_bytes)
    dispatch_cache.reset()
    fusion_cycle.reset()
    n = hvd.size()

    params = jax.device_put(params0, NamedSharding(mesh, P()))
    stats = jax.device_put(
        jax.tree.map(lambda a: np.broadcast_to(a[None], (n,) + a.shape),
                     stats0),
        NamedSharding(mesh, P(axis)))
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    opt = jax.device_put(tx.init(params0), NamedSharding(mesh, P()))
    state = {"params": params, "stats": stats, "opt": opt}

    def one_step():
        g, state["stats"], loss = local_fn(
            state["params"], state["stats"], x, y)
        gt = jax.tree.map(lambda a: hvd.PerRank(a), g)
        updates, state["opt"] = tx.update(gt, state["opt"],
                                          state["params"])
        state["params"] = optax.apply_updates(state["params"], updates)
        return loss

    # warmup (compiles this mode's fuse/wire plans); materializing it
    # doubles as the numerics probe — params after ONE step from init
    one_step()
    step1 = [np.asarray(l) for l in jax.tree.leaves(state["params"])]
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        one_step()
        jax.block_until_ready(jax.tree.leaves(state["params"]))
        times.append((time.perf_counter() - t0) * 1e3)
    return times, step1, _pipeline_summary()


def _grad_sync_ms(hvd, grads_pr, bucket_bytes, iters=7):
    """Median latency (ms) of syncing the model's ACTUAL gradient tree to
    device completion — the mechanism's direct measurement: bucketed
    dispatch pipelines fuse/wire/split across buckets, so time-to-ready
    drops even where the 2-core CI box can't run comm and compute
    concurrently. Robust where chained-step wall time is noise-bound."""
    from horovod_tpu.ops import dispatch_cache, fusion_cycle
    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.ops.reduce_ops import ReduceOp
    from horovod_tpu.optim import _allreduce_tree

    os.environ["HVD_BUCKET_BYTES"] = str(bucket_bytes)
    dispatch_cache.reset()
    fusion_cycle.reset()

    def sync():
        out = _allreduce_tree(
            grads_pr, op=ReduceOp.AVERAGE, process_set=None,
            compression=Compression.none, prescale_factor=1.0,
            postscale_factor=1.0, axis_name=None)
        jax.block_until_ready(jax.tree.leaves(out))

    sync()
    sync()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync()
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def run_step_bench(args) -> None:
    """End-to-end eager data-parallel step-time benchmark (CPU backend,
    virtual 8-chip mesh) for the bucketed backward-pass overlap (ISSUE 6
    tentpole b): per step, a jitted shard_map program computes LOCAL
    per-rank gradients (no collectives in the program), then
    ``DistributedOptimizer`` syncs them eagerly — whole-tree
    (``HVD_BUCKET_BYTES=0``, one grouped allreduce: the pre-bucketing
    behavior) vs bucketed (each size-bounded bucket its own flushed async
    grouped allreduce overlapping the next bucket's fuse and the update
    math). Models: ``models/`` ResNet-50 and TransformerLM. Prints ONE
    JSON line; ci.sh gates numerics parity and bucketed-not-slower on
    the ResNet model. Step time is end-to-end (backward + sync + update),
    not a collective microbench."""
    hvd, n = _microbench_mesh()
    knobs = ("HVD_BUCKET_BYTES", "HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME")
    prev = {k: os.environ.get(k) for k in knobs}
    models = {}
    try:
        # timer quiet: every bucket flush comes from the explicit
        # "bucket" trigger (deterministic composition, no mid-step
        # timer fires on a loaded CI box). Chunking stays at its
        # DEFAULT in both modes — the whole-tree baseline legitimately
        # leans on PR-3 chunk pipelining (pinning it off would triple
        # the baseline's sync time and flatter the bucketing win).
        # Caveat: two in-flight chunked collectives on the 2-core XLA
        # CPU emulation occasionally land a schedule that slows every
        # bucketed step of one PROCESS ~1.5-2x (~1 in 4 runs observed;
        # whole-tree mode in the same run unaffected) — ci.sh retries
        # the gate in a fresh process, and docs/pipeline.md documents
        # the interaction.
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        for kind in ("resnet50", "transformer"):
            local_fn, params0, stats0, x, y, grad_bytes = _step_bench_case(
                kind, hvd, n, args)
            # interleaved A/B/A/B passes, per-mode median over the
            # pooled per-step samples: both modes see the same load
            # drift (a 2-core CI box emulating 8 chips swings 30%
            # run-to-run; back-to-back mode blocks would charge the
            # drift to whichever mode ran second)
            base_t1, base_params, _ = _run_step_mode(
                hvd, local_fn, params0, stats0, x, y, 0, args.step_iters)
            if kind == "transformer":
                # step-1 params from fixed init: the GSPMD lane's
                # numerics reference (same model, init, data, optimizer)
                transformer_step1 = base_params
            bkt_t1, bkt_params, overlap = _run_step_mode(
                hvd, local_fn, params0, stats0, x, y,
                args.step_bucket_bytes, args.step_iters)
            base_t2, _, _ = _run_step_mode(
                hvd, local_fn, params0, stats0, x, y, 0, args.step_iters)
            bkt_t2, _, _ = _run_step_mode(
                hvd, local_fn, params0, stats0, x, y,
                args.step_bucket_bytes, args.step_iters)
            base_ms = float(np.median(base_t1 + base_t2))
            bkt_ms = float(np.median(bkt_t1 + bkt_t2))
            match = all(np.allclose(a, b)
                        for a, b in zip(base_params, bkt_params))
            # gradient-sync latency on the model's real grad tree
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh, axisn = hvd.mesh(), hvd.axis_name()
            g, _, _ = local_fn(
                jax.device_put(params0, NamedSharding(mesh, P())),
                jax.device_put(
                    jax.tree.map(lambda a: np.broadcast_to(
                        a[None], (n,) + a.shape), stats0),
                    NamedSharding(mesh, P(axisn))), x, y)
            grads_pr = jax.tree.map(lambda a: hvd.PerRank(a), g)
            sync_whole = _grad_sync_ms(hvd, grads_pr, 0)
            sync_bkt = _grad_sync_ms(hvd, grads_pr,
                                     args.step_bucket_bytes)
            from horovod_tpu.optim import _bucket_layout
            n_buckets = len(_bucket_layout(
                [int(np.prod(l.shape)) * 4
                 for l in jax.tree.leaves(params0)],
                args.step_bucket_bytes))
            models[kind] = {
                "whole_tree_ms_per_step": round(base_ms, 3),
                "bucketed_ms_per_step": round(bkt_ms, 3),
                "reduction_pct": round(
                    (base_ms - bkt_ms) / base_ms * 100.0, 1) if base_ms
                    else 0.0,
                "grad_sync_whole_ms": round(sync_whole, 3),
                "grad_sync_bucketed_ms": round(sync_bkt, 3),
                "grad_sync_reduction_pct": round(
                    (sync_whole - sync_bkt) / sync_whole * 100.0, 1)
                    if sync_whole else 0.0,
                "numerics_match": bool(match),
                "grad_bytes": grad_bytes,
                "buckets": n_buckets,
                "pipeline_overlap": overlap,
            }
        # GSPMD execution mode of the same TransformerLM (ISSUE 16):
        # cached-program fast path vs the retrace-per-call status quo
        models["gspmd"] = _gspmd_step_lane(hvd, n, args, transformer_step1)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    print(json.dumps({
        "metric": "bucketed_backward_step_time_reduction",
        "value": models["resnet50"]["reduction_pct"],
        "unit": "% reduction in end-to-end eager DP step time, ResNet-50 "
                "(bucketed backward vs whole-tree allreduce)",
        "models": models,
        "pipeline_overlap": models["resnet50"]["pipeline_overlap"],
        "numerics_match": bool(all(m["numerics_match"]
                                   for m in models.values())),
        "baseline": "identical eager DP step with HVD_BUCKET_BYTES=0 "
                    "(whole gradient pytree as one post-backward grouped "
                    "allreduce — the pre-ISSUE-6 DistributedOptimizer "
                    "behavior)",
        "config": {"bucket_bytes": args.step_bucket_bytes,
                   "batch_per_chip": args.step_batch,
                   "image_size": args.step_image_size,
                   "seq_len": args.step_seq_len,
                   "iters": args.step_iters, "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def _gspmd_step_lane(hvd, n, args, eager_step1):
    """GSPMD execution mode of the step bench's TransformerLM: the whole
    train step — global-batch loss, backward, ``DistributedOptimizer``
    update riding the partitioner passthrough — is ONE jit program.
    Uncached builds a FRESH ``jax.jit`` wrapper per step (the
    retrace-per-call status quo MULTICHIP_r05 measured at 8.8 s); cached
    builds a fresh ``hvd.cached_step`` wrapper per step, which replays
    the recorded executable from the signature cache
    (ops/gspmd_cache.py). Numerics gate: step-1 params must match the
    eager-DP transformer lane (same init, data, and optimizer — eager's
    rank-averaged local-mean gradient IS the GSPMD global-mean
    gradient), and the cached step-1 params must match the uncached
    ones."""
    import optax
    import jax.numpy as jnp  # noqa: F811 - local for clarity
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.ops import dispatch_cache, gspmd_cache

    mesh, axis = hvd.mesh(), hvd.axis_name()
    batch, seq = args.step_batch, args.step_seq_len
    # keep in sync with the kind == "transformer" eager lane above
    cfg = TransformerConfig(vocab_size=32768, num_layers=2,
                            num_heads=8, d_model=256, d_ff=1024,
                            max_seq_len=seq, dtype=jnp.float32)
    model = TransformerLM(cfg)
    x_host = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(n * batch, seq))
    params0 = model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, seq), jnp.int32))["params"]
    x = jax.device_put(x_host, NamedSharding(mesh, P(axis)))
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))

    def make_step():
        # re-executed per step: structurally-identical fresh closures,
        # the per-call retrace pattern the signature cache exists to kill
        def train_step(params, opt, x):
            def loss_fn(p):
                logits = model.apply({"params": p}, x)
                tgt = jax.nn.one_hot(x[:, 1:], cfg.vocab_size)
                return -jnp.mean(jnp.sum(
                    tgt * jax.nn.log_softmax(logits[:, :-1]), -1))
            loss, g = jax.value_and_grad(loss_fn)(params)
            updates, new_opt = tx.update(g, opt, params)
            return optax.apply_updates(params, updates), new_opt, loss
        return train_step

    params = jax.device_put(params0, NamedSharding(mesh, P()))
    opt = jax.device_put(tx.init(params0), NamedSharding(mesh, P()))
    dispatch_cache.reset()
    gspmd_cache.reset_stats()

    # uncached (status quo): fresh jit wrapper per step — every step pays
    # trace+lower+compile. 2 steps bound the lane's wall-time cost; the
    # per-step times are compile-dominated and low-variance.
    uncached, state, step1 = [], (params, opt), None
    for i in range(2):
        t0 = time.perf_counter()
        p2, o2, loss = jax.jit(make_step())(state[0], state[1], x)
        jax.block_until_ready(loss)
        uncached.append((time.perf_counter() - t0) * 1e3)
        if i == 0:
            step1 = [np.asarray(l) for l in jax.tree.leaves(p2)]
        state = (p2, o2)

    # cached: a fresh cached_step wrapper per step — the first records,
    # every later one must replay with zero retraces
    cached, state, retraces, cold_ms = [], (params, opt), 0, None
    cached_step1 = None
    for i in range(args.step_iters + 1):
        s = gspmd_cache.cached_step(make_step())
        t0 = time.perf_counter()
        p2, o2, loss = s(state[0], state[1], x)
        jax.block_until_ready(loss)
        ms = (time.perf_counter() - t0) * 1e3
        if i == 0:
            cold_ms = ms
            cached_step1 = [np.asarray(l) for l in jax.tree.leaves(p2)]
        else:
            cached.append(ms)
            retraces += s.traces
        state = (p2, o2)

    unc_ms = float(np.median(uncached))
    warm_ms = float(np.median(cached))
    hits = dispatch_cache.stats()["hits_by_source"].get("gspmd", 0)
    # fp reassociation across execution modes: tolerance, not bitwise
    match_eager = (len(step1) == len(eager_step1) and all(
        np.allclose(a, b, rtol=1e-4, atol=1e-6)
        for a, b in zip(step1, eager_step1)))
    match_cached = all(np.allclose(a, b)
                       for a, b in zip(step1, cached_step1))
    return {
        "uncached_ms_per_step": round(unc_ms, 3),
        "cached_warm_ms_per_step": round(warm_ms, 3),
        "cold_record_ms": round(cold_ms, 3),
        "reduction_pct": round((unc_ms - warm_ms) / unc_ms * 100.0, 1)
            if unc_ms else 0.0,
        "warm_retraces": retraces,
        "cache_hits": hits,
        "numerics_match": bool(match_eager and match_cached),
        "cache": gspmd_cache.stats(),
        "baseline": "fresh jax.jit wrapper per step (retrace-per-call "
                    "status quo; jit keys on function object identity)",
    }


def _capture_bench_case(hvd, n, args):
    """Dispatch-bound eager DP transformer step for --capture-bench: a
    deep-but-narrow TransformerLM whose gradient tree has MANY small
    leaves (the per-parameter regime MULTICHIP_r05 showed drowning in
    eager dispatch), local backward jitted with no collectives inside —
    gradient sync through DistributedOptimizer's bucketed stream is the
    path capture records and replays."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import TransformerConfig, TransformerLM

    mesh = hvd.mesh()
    axis = hvd.axis_name()
    batch = args.capture_batch
    seq = args.capture_seq_len
    cfg = TransformerConfig(vocab_size=args.capture_vocab,
                            num_layers=args.capture_layers,
                            num_heads=4, d_model=args.capture_dmodel,
                            d_ff=4 * args.capture_dmodel,
                            max_seq_len=seq, dtype=jnp.float32)
    model = TransformerLM(cfg)
    x_host = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(n * batch, seq))
    params0 = model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, seq), jnp.int32))["params"]

    def local(p, x_i):
        def loss_fn(p):
            logits = model.apply({"params": p}, x_i)
            tgt = jax.nn.one_hot(x_i[:, 1:], cfg.vocab_size)
            return -jnp.mean(jnp.sum(
                tgt * jax.nn.log_softmax(logits[:, :-1]), -1))
        loss, g = jax.value_and_grad(loss_fn)(p)
        return g, loss

    def shard_fn(p, x_i):
        g, loss = local(p, x_i)
        return jax.tree.map(lambda a: a[None], g), loss[None]

    local_fn = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(P(), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False))
    x = jax.device_put(x_host, NamedSharding(mesh, P(axis)))
    grad_bytes = sum(int(np.prod(l.shape)) * 4
                     for l in jax.tree.leaves(params0))
    n_leaves = len(jax.tree.leaves(params0))
    return local_fn, params0, x, grad_bytes, n_leaves


def _run_capture_mode(hvd, local_fn, params0, x, capture_on, iters,
                      bucket_a, bucket_b):
    """One pass of the eager DP step with HVD_STEP_CAPTURE pinned:
    3 warmup steps (with capture on: record @1, compile the whole-step
    program + first replay @2), ``iters`` timed steps, then a FORCED
    DIVERGENCE phase — the bucket layout flips mid-run, so the replay
    must fall back to eager with correct results. The step is jitted
    backward → EAGER bucketed gradient sync (the
    ``allreduce_gradients_transform`` stage under test — the one part of
    an eager-DP step that cannot compile into the user's jit) → jitted
    optimizer update, so the measured delta is the dispatch machinery
    capture removes, not eager arithmetic around it. Returns (per-step
    times, final param leaves, capture stats)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import dispatch_cache, fusion_cycle

    os.environ["HVD_STEP_CAPTURE"] = "1" if capture_on else "0"
    os.environ["HVD_BUCKET_BYTES"] = str(bucket_a)
    dispatch_cache.reset()
    fusion_cycle.reset()
    mesh = hvd.mesh()

    params = jax.device_put(params0, NamedSharding(mesh, P()))
    sync_tx = hvd.allreduce_gradients_transform()
    sync_state = sync_tx.init(params0)
    inner = optax.sgd(0.01, momentum=0.9)
    opt = jax.device_put(inner.init(params0), NamedSharding(mesh, P()))
    state = {"params": params, "opt": opt}

    @jax.jit
    def apply_update(p, synced, o):
        updates, o = inner.update(synced, o, p)
        return optax.apply_updates(p, updates), o

    def one_step():
        g, loss = local_fn(state["params"], x)
        gt = jax.tree.map(lambda a: hvd.PerRank(a), g)
        synced, _ = sync_tx.update(gt, sync_state)
        state["params"], state["opt"] = apply_update(
            state["params"], synced, state["opt"])
        return loss

    for _ in range(3):
        one_step()
    jax.block_until_ready(jax.tree.leaves(state["params"]))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        one_step()
        jax.block_until_ready(jax.tree.leaves(state["params"]))
        times.append((time.perf_counter() - t0) * 1e3)
    # forced divergence: a different bucket layout changes the stream —
    # the captured plan must invalidate and the steps stay correct
    os.environ["HVD_BUCKET_BYTES"] = str(bucket_b)
    for _ in range(2):
        one_step()
    jax.block_until_ready(jax.tree.leaves(state["params"]))
    stats = hvd.fusion_stats()["capture"]
    leaves = [np.asarray(l) for l in jax.tree.leaves(state["params"])]
    return times, leaves, stats


def run_capture_bench(args) -> None:
    """Step capture-and-replay benchmark (CPU backend, virtual 8-chip
    mesh; ISSUE 8 tentpole): end-to-end eager DP transformer step —
    jitted local backward, bucketed DistributedOptimizer gradient sync —
    with ``HVD_STEP_CAPTURE`` off (the eager per-flush path: every
    bucket pays enqueue/flush/fuse/wire/split dispatch) vs on (step 1
    records the flush stream, later steps replay the whole step's
    collective work as ONE cached jitted program). Both modes end with a
    forced-divergence phase (bucket layout flips mid-run) proving the
    replay falls back to eager with correct results — the final params
    must match across modes INCLUDING the fallback steps. Prints ONE
    JSON line; ``value`` is the percent step-time reduction."""
    hvd, n = _microbench_mesh()
    knobs = ("HVD_STEP_CAPTURE", "HVD_BUCKET_BYTES", "HVD_CYCLE_TIME",
             "HVD_PENDING_CYCLE_TIME", "HVD_PIPELINE_THRESHOLD")
    prev = {k: os.environ.get(k) for k in knobs}
    try:
        # timer quiet: every flush comes from the deterministic "bucket"
        # trigger, so the recorded stream is stable run-to-run
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        # 1 MiB chunk threshold in BOTH modes: the eager flushes sit far
        # below it either way, but the captured program's step-fused
        # wire buffer crosses it — the multi-MiB monolithic reduction is
        # measurably slower than its chunked pieces on the CPU mesh
        # (the PR-3 finding, which step fusion would otherwise re-create)
        os.environ["HVD_PIPELINE_THRESHOLD"] = str(1 << 20)
        local_fn, params0, x, grad_bytes, n_leaves = _capture_bench_case(
            hvd, n, args)
        bucket_a = args.capture_bucket_bytes
        # 4x, not 2x: the deep-narrow default tree is dominated by
        # leaves that sit alone in their bucket at 2x too, which would
        # leave the layout (and so the stream) unchanged — no divergence
        bucket_b = 4 * bucket_a
        # interleaved A/B/A/B passes (same rationale as --step-bench:
        # both modes see the same CI load drift)
        eager_t1, eager_params, _ = _run_capture_mode(
            hvd, local_fn, params0, x, False, args.capture_iters,
            bucket_a, bucket_b)
        cap_t1, cap_params, cap_stats = _run_capture_mode(
            hvd, local_fn, params0, x, True, args.capture_iters,
            bucket_a, bucket_b)
        eager_t2, _, _ = _run_capture_mode(
            hvd, local_fn, params0, x, False, args.capture_iters,
            bucket_a, bucket_b)
        cap_t2, _, cap_stats2 = _run_capture_mode(
            hvd, local_fn, params0, x, True, args.capture_iters,
            bucket_a, bucket_b)
        eager_ms = float(np.median(eager_t1 + eager_t2))
        cap_ms = float(np.median(cap_t1 + cap_t2))
        match = all(np.allclose(a, b, atol=1e-5)
                    for a, b in zip(eager_params, cap_params))
        from horovod_tpu.ops import dispatch_cache
        cache_stats = dispatch_cache.stats()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    reduction = (eager_ms - cap_ms) / eager_ms * 100.0 if eager_ms else 0.0
    # BOTH capture passes' lifecycle counters, summed AND per-pass — a
    # single pass's numbers would let the other pass regress silently
    replayed_by_pass = [int(cap_stats["replayed_steps"]),
                        int(cap_stats2["replayed_steps"])]
    fallbacks_by_pass = [int(cap_stats["fallbacks"]),
                         int(cap_stats2["fallbacks"])]
    print(json.dumps({
        "metric": "step_capture_replay_step_time_reduction",
        "value": round(reduction, 1),
        "unit": "% reduction in end-to-end eager DP step time, "
                "TransformerLM (captured whole-step replay vs the eager "
                "per-flush path)",
        "eager": {"ms_per_step": round(eager_ms, 3)},
        "captured": {"ms_per_step": round(cap_ms, 3),
                     "capture_pass1": cap_stats,
                     "capture": cap_stats2,
                     # each pass resets the dispatch cache, so these
                     # cover the FINAL capture pass only (cross-check
                     # them against capture/cap_stats2, not the sums)
                     "final_pass_hits_by_source":
                         cache_stats["hits_by_source"],
                     "final_pass_step_plan_builds":
                         cache_stats["step_builds"]},
        "numerics_match": bool(match),
        # the forced mid-run bucket-layout flip: the replay must have
        # fallen back (counted, in EVERY capture pass) and the final
        # params still matched
        "divergence": {"fallbacks": sum(fallbacks_by_pass),
                       "fallbacks_by_pass": fallbacks_by_pass,
                       "invalidations": int(cap_stats["invalidations"])
                       + int(cap_stats2["invalidations"]),
                       "numerics_match": bool(match)},
        "replayed_steps": sum(replayed_by_pass),
        "replayed_steps_by_pass": replayed_by_pass,
        "pipeline_overlap": _pipeline_summary(),
        "baseline": "identical eager DP step with HVD_STEP_CAPTURE=0 "
                    "(bucketed per-flush dispatch through the fusion "
                    "cycle + pipelined executor — the pre-capture "
                    "behavior)",
        "config": {"model": "TransformerLM",
                   "vocab": args.capture_vocab,
                   "layers": args.capture_layers,
                   "d_model": args.capture_dmodel,
                   "seq_len": args.capture_seq_len,
                   "batch_per_chip": args.capture_batch,
                   "bucket_bytes": bucket_a,
                   "divergence_bucket_bytes": bucket_b,
                   "grad_bytes": grad_bytes, "grad_leaves": n_leaves,
                   "iters": args.capture_iters, "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_protocol_child(args) -> None:
    """One world of the protocol-scalability sweep, in a FRESH process
    whose XLA_FLAGS seeded exactly ``--protocol-child`` virtual devices
    (the parent sets that; one interpreter cannot re-initialize the CPU
    backend at three device counts). Boots a loopback world, runs
    warm-up + steady-state negotiated steps, and prints ONE JSON line of
    per-rank registry deltas: KV ops, busy negotiation rounds, round
    latency, response-cache hits/misses (docs/negotiation.md)."""
    import jax.numpy as jnp  # noqa: F811 - local for clarity

    import horovod_tpu as hvd
    from horovod_tpu import metrics as _hvd_metrics

    n = args.protocol_child
    cached = str(args.protocol_cache).strip().lower() not in (
        "0", "false", "no", "off", "")
    extra = {
        "HVD_RESPONSE_CACHE": "1" if cached else "0",
        "HVD_HIER_NEGOTIATION": "auto" if args.protocol_hier == "auto"
        else args.protocol_hier,
        # Many rank threads time-slicing a 2-core CI box can starve a
        # watchdog thread past the 30 s production default (compile
        # storms; the flat lane's 16-way gather pressure) — that is CPU
        # starvation of the emulation, not a protocol death; give the
        # bench worlds a budget that scales with world size
        "HVD_HEALTH_TIMEOUT": str(max(60, 2 * n)),
    }
    tensors = args.protocol_tensors
    warmup, steady = args.protocol_warmup, args.protocol_steps

    def _delta_sum(delta, name):
        return sum(v for (nm, _labels), v in delta.items() if nm == name)

    def body():
        r = hvd.rank()

        def one_step(step):
            outs = []
            for i in range(tensors):
                outs.append(hvd.allreduce(
                    jnp.full((16,), float(r + 1), jnp.float32),
                    op=hvd.Sum, name=f"pb{i}"))
            return outs

        expect = float(sum(range(1, n + 1)))
        ok = True
        for s in range(warmup):
            outs = one_step(s)
            ok = ok and all(np.allclose(np.asarray(o), expect)
                            for o in outs)
        s0 = _hvd_metrics.snapshot()
        t0 = time.perf_counter()
        for s in range(steady):
            outs = one_step(warmup + s)
        wall = time.perf_counter() - t0
        s1 = _hvd_metrics.snapshot()
        ok = ok and all(np.allclose(np.asarray(o), expect) for o in outs)
        d = _hvd_metrics.delta(s1, s0)
        from horovod_tpu import engine_service
        svc = engine_service.get_service()
        return {
            "ok": bool(ok),
            "transport": type(svc.transport).__name__,
            "kv_ops": _delta_sum(d, "hvd_kv_ops_total"),
            "rounds": _delta_sum(d, "hvd_negotiation_rounds_total"),
            "round_s_sum": _delta_sum(d, "hvd_negotiation_round_seconds_sum"),
            "round_s_count": _delta_sum(
                d, "hvd_negotiation_round_seconds_count"),
            "rc_hits": _delta_sum(d, "hvd_response_cache_hits_total"),
            "rc_misses": _delta_sum(d, "hvd_response_cache_misses_total"),
            "steady_wall_s": wall,
        }

    with hvd.loopback.world(n, extra_env=extra) as w:
        per_rank = [o.result for o in w.run(body)]

    capture_parity = None
    if args.protocol_capture_parity:
        # ISSUE-13 acceptance: the world also completes capture-on/off
        # parity training steps (PR-8 negotiate_step replay at scale).
        def parity_world(capture):
            env = dict(extra, HVD_STEP_CAPTURE="1" if capture else "0")
            with hvd.loopback.world(n, extra_env=env) as w2:
                def pbody():
                    r = hvd.rank()
                    vals = []
                    for step in range(3):
                        hvd.step_marker()
                        hs = [hvd.allreduce_async(
                                  jnp.full((4,), float(r + i + step)),
                                  op=hvd.Sum, name=f"cp{i}")
                              for i in range(2)]
                        vals.append([np.asarray(h.result()).tobytes()
                                     for h in hs])
                    hvd.step_marker()
                    return vals
                return [o.result for o in w2.run(pbody)]
        on, off = parity_world(True), parity_world(False)
        capture_parity = bool(all(a == b for a, b in zip(on, off)))

    steps = steady * max(1, len(per_rank))
    kv_per_rank_step = [p["kv_ops"] / steady for p in per_rank]
    rounds = sum(p["rounds"] for p in per_rank)
    round_sum = sum(p["round_s_sum"] for p in per_rank)
    round_count = sum(p["round_s_count"] for p in per_rank)
    hits = sum(p["rc_hits"] for p in per_rank)
    misses = sum(p["rc_misses"] for p in per_rank)
    print(json.dumps({
        "world": n,
        "cached": cached,
        "transport": per_rank[0]["transport"],
        "numerics_match": all(p["ok"] for p in per_rank),
        "steady_steps": steady,
        "tensors_per_step": tensors,
        # per-rank KV ops per steady step: the curve the ci gate reads
        "kv_ops_per_rank_step_mean": round(
            float(np.mean(kv_per_rank_step)), 3),
        "kv_ops_per_rank_step_max": round(
            float(np.max(kv_per_rank_step)), 3),
        "busy_rounds_per_rank_step": round(
            rounds / (steps or 1), 4),
        "round_latency_ms_mean": round(
            (round_sum / round_count * 1e3) if round_count else 0.0, 3),
        "cache_hit_rate": round(hits / (hits + misses), 4)
        if (hits + misses) else None,
        "steady_ms_per_step": round(float(np.median(
            [p["steady_wall_s"] for p in per_rank])) / steady * 1e3, 2),
        "capture_parity": capture_parity,
    }), flush=True)


def run_protocol_bench(args) -> None:
    """Protocol-scalability sweep (ROADMAP; ISSUE 13 — BENCH_r13):
    negotiation round latency, per-rank KV ops/step, and response-cache
    hit rate vs world ∈ --protocol-worlds, each world in a FRESH
    subprocess with its own virtual-device count, in two modes: today's
    flat uncached protocol vs hierarchy + coordinator ResponseCache.
    Prints ONE JSON line; ``value`` is the cached-mode per-rank KV
    ops/step growth factor from the smallest to the largest world —
    ≈1.0 means steady-state control-plane cost is independent of world
    size (ci.sh gates this plus the flat-mode latency-growth bound)."""
    worlds = sorted({int(w) for w in args.protocol_worlds.split(",") if w})
    results: dict = {}
    skipped_flat: list = []
    for world in worlds:
        for mode, (cache, hier) in (("flat", ("0", "0")),
                                    ("cached", ("1", "auto"))):
            if mode == "flat" and world > args.protocol_flat_max:
                # no silent caps: flat rounds grow superlinearly on the
                # CPU emulation (world=16 already measures ~0.8 s/round
                # here); world=64 flat would run for hours. The cached
                # lane still covers it; the skip is recorded.
                skipped_flat.append(world)
                print(f"protocol-bench: skipping flat mode at world="
                      f"{world} (> --protocol-flat-max="
                      f"{args.protocol_flat_max})", file=sys.stderr)
                continue
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={world}")
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--protocol-child", str(world),
                   "--protocol-cache", cache,
                   "--protocol-hier", hier,
                   "--protocol-steps", str(args.protocol_steps),
                   "--protocol-warmup", str(args.protocol_warmup),
                   "--protocol-tensors", str(args.protocol_tensors)]
            if cache == "1" and world >= 64:
                cmd.append("--protocol-capture-parity")
            proc = subprocess.run(
                cmd, env=env, capture_output=True, text=True,
                timeout=1800, cwd=os.path.dirname(os.path.abspath(__file__)))
            if proc.returncode != 0:
                raise RuntimeError(
                    f"protocol child world={world} mode={mode} failed:\n"
                    f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
            payload = json.loads(proc.stdout.strip().splitlines()[-1])
            results.setdefault(str(world), {})[mode] = payload
    lo, hi = str(worlds[0]), str(worlds[-1])
    cached_lo = results[lo]["cached"]["kv_ops_per_rank_step_mean"]
    cached_hi = results[hi]["cached"]["kv_ops_per_rank_step_mean"]
    # growth of steady-state per-rank control-plane traffic with world;
    # both sides are idle-heartbeat-only when the cache serves (busy
    # rounds are zero), so a tiny denominator means "already flat"
    kv_growth = (cached_hi / cached_lo) if cached_lo else 0.0
    flat_lat = {w: results[w]["flat"]["round_latency_ms_mean"]
                for w in results if "flat" in results[w]}
    hit_rates = {w: results[w]["cached"]["cache_hit_rate"]
                 for w in results}
    print(json.dumps({
        "metric": "protocol_scalability",
        "value": round(kv_growth, 3) if kv_growth is not None else None,
        "unit": f"cached per-rank KV-ops/step growth world {lo} -> {hi} "
                "(1.0 = flat in world)",
        "numerics_match": all(
            results[w][m]["numerics_match"]
            for w in results for m in results[w]),
        "worlds": results,
        "cache_hit_rate_by_world": hit_rates,
        "flat_round_latency_ms_by_world": flat_lat,
        "baseline": "flat KVTransport with HVD_RESPONSE_CACHE=0 at each "
                    "world (today's protocol)",
        "flat_mode_skipped_at": skipped_flat,
        "config": {"steps": args.protocol_steps,
                   "warmup": args.protocol_warmup,
                   "tensors_per_step": args.protocol_tensors,
                   "worlds": worlds,
                   "flat_max": args.protocol_flat_max},
    }))


def _pctl(samples, q):
    return float(np.percentile(np.asarray(samples), q)) * 1e3


def _latency_summary(samples) -> dict:
    return {"p50": round(_pctl(samples, 50), 3),
            "p95": round(_pctl(samples, 95), 3),
            "p99": round(_pctl(samples, 99), 3),
            "n": len(samples)}


def run_serve_bench(args) -> None:
    """Multi-tenant inference-serving QoS benchmark (CPU backend,
    virtual 8-chip mesh; ISSUE 12 tentpole): a continuous-batching
    serving driver over ``models/transformer.py`` issuing
    ``grouped_allreduce``/``allgather`` streams from two tenant process
    sets — a high-priority SERVE tenant (chips 0-3; per-request
    transformer grad-sync + activation gather, latency measured per
    request) and a low-priority BULK tenant (chips 4-7; a background
    thread keeping a deep async backlog that drives total pending bytes
    past ``HVD_FUSION_MAX_PENDING`` and its own unacked bytes past a
    shed quota). Phases interleave unloaded/loaded passes (box drift
    cancels) with QoS ON, then repeat the loaded passes with QoS OFF
    for the contrast. Prints ONE JSON line; ``value`` is the
    high-priority tenant's loaded p99 as a multiple of its unloaded p99
    with QoS on (ci.sh gates <= SERVE_P99_MULT, default 2.0), plus shed
    counters, backpressure evidence, slot shares, and a check that the
    ``hvd_qos_*`` series are live in the Prometheus scrape."""
    import threading

    import jax.numpy as jnp  # noqa: F811 - local for clarity

    from horovod_tpu import metrics as _hvd_metrics
    from horovod_tpu import qos as _hvd_qos
    from horovod_tpu.ops import dispatch_cache, fusion_cycle

    os.environ["HVD_DYNAMIC_PROCESS_SETS"] = "1"
    hvd, n = _microbench_mesh()
    assert n >= 8, f"serve bench needs the 8-chip CPU mesh, got {n}"

    knobs = ("HVD_QOS", "HVD_CYCLE_TIME", "HVD_PENDING_CYCLE_TIME",
             "HVD_FUSION_THRESHOLD", "HVD_FUSION_MAX_PENDING",
             "HVD_QOS_WINDOW")
    prev = {k: os.environ.get(k) for k in knobs}

    serve_ps = hvd.add_process_set([0, 1, 2, 3])
    bulk_ps = hvd.add_process_set([4, 5, 6, 7])
    m = 4  # tenant pset size

    # SERVE tenant payload: the real TransformerLM parameter tree (a
    # per-request gradient sync in a continuous-batching server) plus an
    # activation allgather — the grouped_allreduce/allgather stream the
    # ROADMAP names.
    from horovod_tpu.models import TransformerConfig, TransformerLM
    cfg = TransformerConfig(vocab_size=args.serve_vocab, num_layers=2,
                            num_heads=4, d_model=args.serve_dmodel,
                            d_ff=2 * args.serve_dmodel,
                            max_seq_len=32, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    leaves = [l for l in jax.tree.leaves(params)]
    serve_tensors = [
        hvd.per_rank([jnp.asarray(l) * float(r + 1) for r in range(m)],
                     process_set=serve_ps)
        for l in leaves]
    serve_bytes = sum(int(np.prod(l.shape)) * 4 for l in leaves)
    act = jnp.ones((args.serve_batch, cfg.d_model), jnp.float32)
    # numerics probe: sum over ranks of leaf * (r+1) = leaf * 10
    probe_leaf = np.asarray(leaves[0]) * float(sum(range(1, m + 1)))

    bulk_elems = args.serve_bulk_size // 4
    bulk_tensors = [
        hvd.per_rank([jnp.full((bulk_elems,), float(r + i + 1),
                               jnp.float32) for r in range(m)],
                     process_set=bulk_ps)
        for i in range(args.serve_bulk_tensors)]
    # bursts rotate over a few prescale factors = a few distinct fusion
    # queue signatures: bulk pending bytes then accumulate ACROSS queues
    # (each below the threshold) until the global
    # HVD_FUSION_MAX_PENDING backpressure drain fires — the "drives the
    # engine past the pending cap" evidence — while every drained batch
    # stays burst-sized, so the serve tenant's head-of-line blocking is
    # one small batch, not one giant backlog flush.
    _BULK_SIGNATURES = 6

    def serve_request(tag):
        t0 = time.perf_counter()
        h = hvd.grouped_allreduce_async(serve_tensors, op=hvd.Sum,
                                        process_set=serve_ps)
        hg = hvd.allgather_async(act, process_set=serve_ps)
        outs = hvd.synchronize(h)
        gathered = hvd.synchronize(hg)
        jax.block_until_ready([outs[0], gathered])
        return time.perf_counter() - t0, outs

    shed_seen = [0]
    bursts = [0]

    def bulk_flood(stop_evt):
        outstanding = []

        def reap(h):
            try:
                hvd.synchronize(h)
            except hvd.QosAdmissionError:
                shed_seen[0] += 1

        while not stop_evt.is_set():
            outstanding.append(hvd.grouped_allreduce_async(
                bulk_tensors, op=hvd.Sum, process_set=bulk_ps,
                prescale_factor=float(1 + bursts[0] % _BULK_SIGNATURES)))
            bursts[0] += 1
            if len(outstanding) >= args.serve_bulk_depth:
                reap(outstanding.pop(0))
            if args.serve_bulk_pace > 0:
                # paced arrivals (a continuous-batching producer, not a
                # GIL-starving busy loop); the engine still saturates —
                # the reap depth keeps a standing backlog
                time.sleep(args.serve_bulk_pace)
        for h in outstanding:
            reap(h)

    def measure_phase(requests, loaded):
        stop_evt = threading.Event()
        t = None
        if loaded:
            t = threading.Thread(target=bulk_flood, args=(stop_evt,),
                                 daemon=True)
            t.start()
            time.sleep(0.2)  # let the backlog build before measuring
        lat = []
        last_outs = None
        for i in range(requests):
            dt, last_outs = serve_request(i)
            lat.append(dt)
        if t is not None:
            stop_evt.set()
            t.join(timeout=120)
        return lat, last_outs

    def warm_bulk(seconds):
        """Run the flood solo so every bulk plan signature/composition
        compiles BEFORE measurement — a first-touch XLA compile under a
        measured serve request would charge a one-time cost to the
        steady-state tail."""
        stop_evt = threading.Event()
        t = threading.Thread(target=bulk_flood, args=(stop_evt,),
                             daemon=True)
        t.start()
        time.sleep(seconds)
        stop_evt.set()
        t.join(timeout=120)
        hvd.fusion_flush()

    try:
        # timer quiet (every flush from threshold/synchronize triggers);
        # small fusion threshold so the bulk backlog drains into many
        # modest batches (bounded head-of-line blocking); small global
        # pending cap so the bulk tenant demonstrably drives the engine
        # past HVD_FUSION_MAX_PENDING (backpressure flushes fire).
        burst_bytes = args.serve_bulk_tensors * args.serve_bulk_size
        os.environ["HVD_CYCLE_TIME"] = "500"
        os.environ["HVD_PENDING_CYCLE_TIME"] = "500"
        # threshold = 2 bursts: a signature's queue threshold-drains at a
        # STABLE two-burst composition (one plan per signature, warmed
        # below); pending still accumulates across the rotating
        # signatures to the global cap, so backpressure drains fire too
        # (those produce the 1-burst composition — also warmed).
        os.environ["HVD_FUSION_THRESHOLD"] = str(2 * burst_bytes)
        os.environ["HVD_FUSION_MAX_PENDING"] = str(
            (_BULK_SIGNATURES - 1) * burst_bytes)
        os.environ["HVD_QOS"] = "1"
        _hvd_qos.reset()
        # the serve tenant carries its own (generous, never-engaging)
        # block quota: a quota'd tenant gets the bounded non-stalling
        # backpressure drain when it crosses the global pending cap — a
        # quota-less tenant keeps the legacy producer-stalling
        # flush_all, which is exactly the tail-latency inversion this
        # workload measures (docs/qos.md "Interactions")
        hvd.set_qos(serve_ps, priority=1, weight=4.0,
                    pending_bytes_quota=64 << 20, policy="block")
        hvd.set_qos(bulk_ps, priority=0, weight=1.0,
                    pending_bytes_quota=args.serve_quota, policy="shed")

        dispatch_cache.reset()
        fusion_cycle.reset()
        # warm compile/plan caches for both tenants. Bulk flush batches
        # can carry 1, 2, or 3 bursts (threshold drains at 2; the
        # bounded backpressure drain can spare a 2-burst queue whose
        # next burst then threshold-drains at 3; 4+ is unreachable — a
        # 3-burst queue alone exceeds the half-cap drain target), so
        # compile every (signature x composition) plan OFF the clock: a
        # first-touch XLA compile (~200 ms) under a measured serve
        # request would otherwise charge a one-time cost to the
        # steady-state tail (observed as 10-20x p99 outliers).
        for sig in range(_BULK_SIGNATURES):
            for k in (1, 2, 3):
                hvd.grouped_allreduce(bulk_tensors * k, op=hvd.Sum,
                                      process_set=bulk_ps,
                                      prescale_factor=float(1 + sig))
        warm_bulk(1.0)
        _, warm_outs = measure_phase(2, loaded=False)
        numerics_match = np.allclose(np.asarray(warm_outs[0]), probe_leaf)

        # interleaved unloaded/loaded passes, QoS ON
        r = args.serve_requests
        unl1, _ = measure_phase(r, loaded=False)
        load1, outs1 = measure_phase(r, loaded=True)
        unl2, _ = measure_phase(r, loaded=False)
        load2, outs2 = measure_phase(r, loaded=True)
        numerics_match = bool(
            numerics_match
            and np.allclose(np.asarray(outs1[0]), probe_leaf)
            and np.allclose(np.asarray(outs2[0]), probe_leaf))
        stats_on = hvd.fusion_stats()
        scrape = _hvd_metrics.prometheus_text()
        qos_series_live = all(
            f"{name}{{" in scrape
            for name in ("hvd_qos_granted_bytes_total",
                         "hvd_qos_slot_share", "hvd_qos_shed_total"))
        wait_series = ("hvd_qos_admission_wait_seconds_count{" in scrape)
        sheds_on = int(sum(stats_on["qos"]["shed"].values()))
        shares = {t: round(v["share"], 3)
                  for t, v in stats_on["qos"].get("tenants", {}).items()}

        # contrast passes, QoS OFF (same load, single-tenant FIFO; the
        # dispatch-plan cache stays warm — plans are mode-independent,
        # so the contrast charges the scheduler, not recompiles)
        os.environ["HVD_QOS"] = "0"
        fusion_cycle.reset()
        measure_phase(2, loaded=False)
        off1, outs_off = measure_phase(r, loaded=True)
        off2, _ = measure_phase(r, loaded=True)
        numerics_match = bool(
            numerics_match
            and np.allclose(np.asarray(outs_off[0]), probe_leaf))
        stats_off = hvd.fusion_stats()
    finally:
        try:
            hvd.remove_process_set(serve_ps)
            hvd.remove_process_set(bulk_ps)
        except Exception:
            pass
        _hvd_qos.reset()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    unloaded = unl1 + unl2
    loaded_on = load1 + load2
    loaded_off = off1 + off2
    p99_unloaded = _pctl(unloaded, 99)
    p99_on = _pctl(loaded_on, 99)
    p99_off = _pctl(loaded_off, 99)
    ratio_on = p99_on / p99_unloaded if p99_unloaded else 0.0
    ratio_off = p99_off / p99_unloaded if p99_unloaded else 0.0
    backpressure = int(stats_on["flushes"]["backpressure"]
                       + stats_off["flushes"]["backpressure"])
    print(json.dumps({
        "metric": "serve_qos_p99_protection",
        "value": round(ratio_on, 3),
        "unit": "x multiple of the high-priority tenant's unloaded p99 "
                "grad-sync latency while the bulk tenant saturates the "
                "engine (QoS on; lower is better, 1.0 = full protection)",
        "qos_on": {
            "unloaded_ms": _latency_summary(unloaded),
            "loaded_ms": _latency_summary(loaded_on),
            "p99_protection_ratio": round(ratio_on, 3),
            "shed_total": sheds_on,
            "slot_share": shares,
        },
        "qos_off": {
            "loaded_ms": _latency_summary(loaded_off),
            "p99_protection_ratio": round(ratio_off, 3),
        },
        "qos_off_vs_on_p99": round(p99_off / p99_on, 2) if p99_on else None,
        "bulk": {"bursts": bursts[0], "sheds_observed": shed_seen[0],
                 "bytes_per_burst": args.serve_bulk_tensors
                 * args.serve_bulk_size,
                 "depth": args.serve_bulk_depth,
                 "quota": args.serve_quota},
        "backpressure_flushes": backpressure,
        "qos_series_in_scrape": bool(qos_series_live and wait_series),
        "numerics_match": bool(numerics_match),
        "baseline": "the same serve-request stream measured unloaded "
                    "(no bulk traffic) with QoS on; the qos_off block "
                    "repeats the loaded passes with HVD_QOS=0 (the "
                    "single-tenant FIFO pipeline) for contrast",
        "config": {"serve_pset": [0, 1, 2, 3], "bulk_pset": [4, 5, 6, 7],
                   "serve_grad_bytes": serve_bytes,
                   "serve_leaves": len(leaves),
                   "requests_per_phase": r,
                   "serve_class": {"priority": 1, "weight": 4.0},
                   "bulk_class": {"priority": 0, "weight": 1.0,
                                  "quota": args.serve_quota,
                                  "policy": "shed"},
                   "fusion_threshold": 2 * args.serve_bulk_tensors
                   * args.serve_bulk_size,
                   "fusion_max_pending": (_BULK_SIGNATURES - 1)
                   * args.serve_bulk_tensors * args.serve_bulk_size,
                   "bulk_signatures": _BULK_SIGNATURES,
                   "n_chips": n,
                   "backend": jax.devices()[0].platform},
    }))


def run_elastic_bench(args):
    """Elastic autoscaling as a measured scenario (docs/elastic.md;
    ISSUE 14 — BENCH_r14). Two loopback phases:

    * **churn** (world 4, all graceful, at_round-keyed so re-form
      latency cannot skew the schedule): preempt 4->3 (COLD: the shape
      was never shelved) -> scale-up 3->4 -> preempt 4->3 again (WARM:
      plans shelved at the grow, the coordinator ResponseCache re-armed
      after one digest round). Cold and warm are the IDENTICAL
      transition (same worlds, same graceful mechanism, same tensors),
      so ``value`` = warm/cold mean step time over the first
      post-re-form window isolates exactly the shape-keyed cache
      survival; a graceful preemption must also lose ZERO steps.
    * **abrupt** (world 3): a scheduled spot reclaim (remove) and a
      hard crash — the watchdog-detected paths — gate the recovery
      budget and the <=1-step crash loss.

    SLOs come off the rank-0 step log plus the ``hvd_elastic_*``
    registry (events by kind, re-form histogram, steps-lost counter,
    warm-reuse counter)."""
    from horovod_tpu.loopback.engine import _seed_xla_device_flags

    world_n = args.elastic_world
    _seed_xla_device_flags(world_n + 1)

    from horovod_tpu.utils import faults
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.loopback import elastic_run

    # Fast failure detection for the abrupt phase: the 30 s production
    # watchdog default would dominate every recovery measurement. The
    # timeout keeps headroom over GIL pauses (rank threads compiling XLA
    # programs on a small CI box can starve a beat thread for ~2 s).
    extra_env = {
        "HVD_RESPONSE_CACHE": "1",
        "HVD_HEALTH_INTERVAL": "0.3",
        "HVD_HEALTH_TIMEOUT": "4",
    }
    sleep_s = args.elastic_step_sleep
    n_tensors = args.elastic_tensors

    def phase(spec, hosts, np_, min_np, max_np, total_steps):
        os.environ["HVD_FAULT_SPEC"] = spec
        faults.refresh()
        disco = FixedHosts(dict(hosts))
        box = {}
        fired: list = []

        def body():
            import horovod_tpu as _hvd
            _hvd.init()
            state = _hvd.elastic.JaxState(step=0, log=[])

            @_hvd.elastic.run
            def train(state):
                from horovod_tpu import metrics as _metrics
                from horovod_tpu.utils import envs as _envs
                while state.step < total_steps:
                    out = _hvd.allreduce(jnp.ones(2), op=_hvd.Sum,
                                         name="w")
                    # several stable-named tensors per step: the
                    # post-re-form window measures real negotiation
                    # traffic (cold: wire rounds until the caches
                    # re-arm; warm: local serving), not the pacing sleep
                    p1 = 0.0
                    for j in range(n_tensors):
                        probe = _hvd.allreduce(
                            jnp.arange(8.0) + 1.0 + j, op=_hvd.Sum,
                            name=f"probe{j}")
                        if j == 0:
                            p1 = float(np.asarray(probe).reshape(-1)[1])
                    world = int(float(np.asarray(out).reshape(-1)[0]))
                    if _hvd.rank() == 0:
                        warm = {"plan": 0, "step": 0, "response": 0}
                        for li, v in \
                                _metrics.ELASTIC_WARM_REUSE.series(
                                    ).items():
                            k = dict(li).get("kind")
                            if k in warm:
                                warm[k] = int(v)
                        busy = int(sum(
                            _metrics.NEGOTIATION_ROUNDS.series(
                                ).values()))
                        state.log = state.log + [(
                            time.monotonic(), state.step, world, p1,
                            warm["plan"] + warm["step"],
                            warm["response"],
                            int(_metrics.ELASTIC_STEPS_LOST.value()),
                            _envs.get_int(_envs.ELASTIC_ROUND, -1),
                            busy)]
                    state.step += 1
                    time.sleep(sleep_s)
                    state.commit()
                return state.log

            log = train(state)
            if _hvd.rank() == 0:
                box["log"] = log
            return 0

        results, ok = elastic_run(
            body, np=np_, min_np=min_np, max_np=max_np,
            discovery=disco, timeout=180, extra_env=extra_env,
            churn_events=fired)
        return (box.get("log") or [], fired, ok,
                results.error_message)

    def transitions(log, window):
        evs = []
        for i in range(1, len(log)):
            (tp, sp, wp, _pp, warm_p, resp_p, lost_p, _rp,
             busy_p) = log[i - 1]
            (tc, sc, wc, _pc, warm_c, resp_c, lost_c, _rc,
             busy_c) = log[i]
            if wc == wp:
                continue
            # the phase: consecutive rows at the new world from here
            phase_dts = []
            for j in range(i, len(log) - 1):
                if log[j + 1][2] != wc:
                    break
                phase_dts.append(log[j + 1][0] - log[j][0])
            win = phase_dts[:window]
            # steady tail of the SAME phase (caches armed, serving
            # locally): normalizing the post-re-form window by it
            # cancels the box's phase-scale contention drift — a raw
            # wall-clock window swings ~1.5x run to run on a shared
            # 2-core box, drowning the re-arm signal
            tail = phase_dts[window:]
            steady = (sum(tail[-window:]) / len(tail[-window:])
                      if len(tail) >= 2 else None)
            post = (sum(win) / len(win)) if win else None
            # BUSY negotiation rounds spent over the same window: the
            # deterministic face of warm-vs-cold (a cold re-form pays
            # wire rounds per tensor until the caches re-arm; a warm
            # one serves locally after the digest round) — wall-clock
            # ratios on a shared CI box swing with contention, counts
            # do not
            wend = min(i + window, len(log) - 1)
            while wend > i and log[wend][2] != wc:
                wend -= 1
            window_busy = (log[wend][8] - busy_c) if wend > i else None
            evs.append({
                "from_world": wp, "to_world": wc, "at_step": sc,
                "recovery_s": round(tc - tp, 3),
                "steps_lost": lost_c - lost_p,
                "warm_plan_reuses": warm_c - warm_p,
                "warm_response_confirms": resp_c - resp_p,
                "post_step_ms": round(1e3 * post, 2) if post else None,
                "steady_step_ms": round(1e3 * steady, 2)
                if steady else None,
                "post_vs_steady": round(post / steady, 3)
                if post and steady else None,
                "window_busy_rounds": window_busy,
            })
        return evs

    def rows_of(log):
        if not log:
            return []
        t0 = log[0][0]
        return [[round(t - t0, 3), s, w, rd, warm, resp, lost, busy]
                for (t, s, w, _p, warm, resp, lost, rd, busy) in log]

    def numerics_of(log):
        return all(abs(p1 - 2.0 * world) < 1e-6
                   for (_t, _s, world, p1, *_rest) in log)

    t0 = time.monotonic()
    # Phase 1 — graceful churn, at_round-keyed: preempt(cold 4->3) ->
    # add(3->4, re-forms back into the shelved shape) -> preempt(warm
    # 4->3). Every event fires a fixed number of commits INSIDE the
    # round the previous event formed, so the schedule is immune to
    # re-form latency; all-graceful means no watchdog recovery variance
    # contaminates the warm/cold window comparison.
    e1, ek = args.elastic_e1, args.elastic_e2
    churn_spec = args.elastic_spec or (
        f"worker:preempt:rank={world_n - 1}:at_round=1:at_step={e1}"
        ":grace=30;"
        f"worker:add:rank=0:at_round=2:after={ek}:count=1;"
        f"worker:preempt:rank={world_n - 1}:at_round=3:after={ek}"
        ":grace=30")
    churn_hosts = {f"h{i}": 1 for i in range(world_n)}
    churn_log, churn_fired, churn_ok, churn_err = phase(
        churn_spec, churn_hosts, world_n, 2, world_n,
        args.elastic_steps)

    # Phase 2 — abrupt loss: ONE hard crash at a smaller world;
    # recovery runs the watchdog path (rank death -> silence detection
    # -> blacklist -> re-form -> restored last commit). A single event
    # keeps the phase deterministic — two interacting watchdog
    # recoveries (e.g. remove then crash) can overlap their re-forms on
    # a slow box; the abrupt-remove path keeps its coverage in
    # tests/test_elastic_churn.py.
    abrupt_spec = (
        f"worker:crash:rank=2:at_round=1:at_step={e1 + 4}")
    abrupt_log, abrupt_fired, abrupt_ok, abrupt_err = phase(
        abrupt_spec, {"a0": 1, "a1": 1, "a2": 1}, 3, 1, 3,
        args.elastic_abrupt_steps)
    elapsed = time.monotonic() - t0

    if not churn_ok or not churn_log or not abrupt_ok or not abrupt_log:
        print(json.dumps({
            "metric": "elastic_churn_warm_vs_cold",
            "value": None, "unit": "warm/cold re-form step-time ratio",
            "error": (churn_err or abrupt_err
                      or "no rank-0 log")[:500],
            "churn_ok": bool(churn_ok), "abrupt_ok": bool(abrupt_ok),
        }))
        return

    win = args.elastic_window
    churn_evs = transitions(churn_log, win)
    abrupt_evs = transitions(abrupt_log, win)
    shrinks = [e for e in churn_evs
               if (e["from_world"], e["to_world"])
               == (world_n, world_n - 1)]
    cold = shrinks[0] if shrinks else None
    warm_evt = shrinks[1] if len(shrinks) > 1 else None
    crash_evt = abrupt_evs[0] if abrupt_evs else None
    # The headline warm/cold metric is the DETERMINISTIC one: busy
    # wire rounds spent over the identical post-re-form window (cold
    # pays rounds per tensor until the caches re-arm; warm serves
    # locally after the digest round — measured 0 vs 14-17 on every
    # run). Wall-clock step-time ratios are recorded informationally:
    # on this repo's shared 2-core CI box they swing 0.6x-1.8x with
    # scheduler contention, drowning the very signal they would gate.
    ratio = None
    step_ratio = None
    if cold and warm_evt:
        wb = warm_evt.get("window_busy_rounds")
        cb = cold.get("window_busy_rounds")
        if wb is not None and cb:
            ratio = round(wb / cb, 3)
        if cold.get("post_step_ms") and warm_evt.get("post_step_ms"):
            step_ratio = round(
                warm_evt["post_step_ms"] / cold["post_step_ms"], 3)
    all_evs = churn_evs + abrupt_evs

    print(json.dumps({
        "metric": "elastic_churn_warm_vs_cold",
        "value": ratio,
        "unit": "warm/cold busy wire rounds over the first "
                f"{win}-step window after the two IDENTICAL graceful "
                f"{world_n}->{world_n - 1} re-forms (<1.0 = the "
                "shape-keyed shelve/restore left the warm re-form "
                "measurably less negotiation work; 0.0 = fully served "
                "locally). step_time_ratio carries the wall-clock "
                "twin, informational on a contended box",
        "step_time_ratio": step_ratio,
        "world": world_n,
        "schedule": {"churn": churn_spec, "abrupt": abrupt_spec},
        "events": all_evs,
        "churn_fired": [(e[1], e[2]) for e in churn_fired],
        "abrupt_fired": [(e[1], e[2]) for e in abrupt_fired],
        "cold_reform": cold,
        "warm_reform": warm_evt,
        "crash_reform": crash_evt,
        "recovery_s_max": max((e["recovery_s"] for e in all_evs),
                              default=None),
        "steps_total": len(churn_log) + len(abrupt_log),
        "elapsed_s": round(elapsed, 1),
        "numerics_ok": bool(numerics_of(churn_log)
                            and numerics_of(abrupt_log)),
        "fast_health": {"interval_s": 0.3, "timeout_s": 4.0},
        "rows": {"churn": rows_of(churn_log),
                 "abrupt": rows_of(abrupt_log)},
        "baseline": "the same run\'s FIRST graceful 4->3 re-form "
                    "(cold: the shape was never shelved) vs the SECOND "
                    "(warm: plans shelved at the grow, coordinator "
                    "cache re-armed after one digest round)",
    }))


def run_autoscale_bench(args):
    """Closed-loop elastic autoscaling end to end (docs/elastic.md
    "Autoscaler"; ISSUE 15 — BENCH_r15). Three loopback phases, none of
    them scripted — every membership change below is DECIDED by the
    ``HVD_AUTOSCALE`` policy from the metrics-registry sensors:

    * **load** (floor 2, ceiling 3): a fixed offered load shared by the
      world — heavy enough to breach the step-time SLO at the floor,
      under it at 3 — ramps in, breaches, then drops to idle. Gates:
      the policy scales UP within the latency budget of the breach
      starting (no script fired it), scales DOWN after sustained idle
      with ZERO steps lost (the PR-14 grace path), and the run ends at
      the floor.
    * **evict** (world 3): a fault-injected slow rank (``svc.exchange``
      delay, round-1-keyed so the replacement never inherits it) is
      blamed by the StragglerTracker windows, EVICTED through the grace
      window and replaced in the same re-form — the decision instrument
      names the planted rank, zero steps lost, warm shelves apply to
      the replacement's world.
    * **flap** (floor 2): an adversarial load alternating breach/idle
      faster than the hysteresis streaks — the oscillation bound: at
      most one membership decision over the whole phase (expected
      zero; +1 absorbs a pathological box stall aligning windows).
    """
    from horovod_tpu.loopback.engine import _seed_xla_device_flags

    _seed_xla_device_flags(4)

    from horovod_tpu.utils import faults
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.loopback import elastic_run

    base_env = {
        "HVD_HEALTH_INTERVAL": "0.3",
        "HVD_HEALTH_TIMEOUT": "6",
        "HVD_AUTOSCALE": "1",
        "HVD_AUTOSCALE_INTERVAL": "0.4",
        "HVD_AUTOSCALE_COOLDOWN": "3",
        "HVD_AUTOSCALE_GRACE": "30",
    }

    def phase(name, body_fn, hosts, np_, min_np, max_np, env, spec=None):
        os.environ.pop("HVD_FAULT_SPEC", None)
        if spec:
            os.environ["HVD_FAULT_SPEC"] = spec
        faults.refresh()
        disco = FixedHosts(dict(hosts))
        box, abox = {}, {}
        results, ok = elastic_run(
            body_fn(box), np=np_, min_np=min_np, max_np=max_np,
            discovery=disco, timeout=180,
            extra_env=dict(base_env, **env), autoscale_box=abox)
        return (box.get("log") or [], abox.get("decisions") or [], ok,
                results.error_message)

    def make_body(total, sleep_of, collect_warm=False):
        def factory(box):
            def body():
                import horovod_tpu as _hvd
                _hvd.init()
                state = _hvd.elastic.JaxState(step=0, log=[])

                @_hvd.elastic.run
                def train(state):
                    from horovod_tpu import metrics as _metrics
                    from horovod_tpu.ops import dispatch_cache
                    while state.step < total:
                        out = _hvd.allreduce(jnp.arange(4.0) + 1.0,
                                             op=_hvd.Sum, name="w")
                        # element 0 of sum(arange(4)+1) over `world`
                        # identical contributions is exactly world;
                        # element 1 is 2*world (the numerics check)
                        world = int(float(np.asarray(out).reshape(-1)[0]))
                        p1 = float(np.asarray(out).reshape(-1)[1])
                        if _hvd.rank() == 0:
                            state.log = state.log + [(
                                time.monotonic(), state.step, world, p1,
                                int(_metrics.ELASTIC_STEPS_LOST.value()),
                                dispatch_cache.stats()["warm_reuses"]
                                if collect_warm else 0)]
                        time.sleep(sleep_of(state.step, world))
                        state.step += 1
                        state.commit()
                    return state.log

                log = train(state)
                if _hvd.rank() == 0:
                    box["log"] = log
                return 0

            return body
        return factory

    def numerics_of(log):
        return all(abs(p1 - 2.0 * world) < 1e-6
                   for (_t, _s, world, p1, *_r) in log)

    t0 = time.monotonic()

    # -- phase 1: ramp -> breach -> idle ------------------------------------
    RAMP, BREACH_END, TOTAL = 8, 60, 230
    LOAD, LIGHT, SLO_MS = 0.60, 0.02, 220.0

    def load_sleep(step, world):
        if step < RAMP:
            return LIGHT
        if step < BREACH_END:
            return LOAD / max(world, 1)  # 300 ms at 2, 200 ms at 3
        return LIGHT

    load_log, load_dec, load_ok, load_err = phase(
        "load", make_body(TOTAL, load_sleep), {"l0": 1, "l1": 1},
        2, 2, 3, {
            "HVD_RESPONSE_CACHE": "1",
            "HVD_AUTOSCALE_SLO_MS": str(SLO_MS),
            "HVD_AUTOSCALE_BREACH_WINDOWS": "2",
            "HVD_AUTOSCALE_IDLE_WINDOWS": "3",
            "HVD_AUTOSCALE_IDLE_FACTOR": "0.6",
        })

    # -- phase 2: straggler eviction ----------------------------------------
    evict_log, evict_dec, evict_ok, evict_err = phase(
        "evict", make_body(46, lambda s, w: 0.0, collect_warm=True),
        {"e0": 1, "e1": 1, "e2": 1}, 3, 2, 4, {
            "HVD_RESPONSE_CACHE": "0",  # busy rounds feed the tracker
            "HVD_STRAGGLER_THRESHOLD": "0.15",
            "HVD_AUTOSCALE_EVICT_WINDOWS": "2",
        }, spec="svc.exchange:delay=0.4:rank=2:at_round=1")

    # -- phase 3: adversarial flapping --------------------------------------
    # Each load half must register as >= 1 policy window but flip before
    # the 3-window streak requirement: heavy = 3 steps x ~300 ms
    # (~2.2 windows at the 0.4 s interval), light = 25 steps x ~20 ms
    # (~1-2 windows with per-step overhead). Step-indexed, so the
    # pattern is rank-symmetric by construction.
    FLAP_HEAVY, FLAP_LIGHT = 3, 25
    FLAP_PERIOD = FLAP_HEAVY + FLAP_LIGHT
    FLAP_TOTAL = 4 * FLAP_PERIOD

    def flap_sleep(step, world):
        heavy = (step % FLAP_PERIOD) < FLAP_HEAVY
        return (LOAD / max(world, 1)) if heavy else LIGHT

    flap_log, flap_dec, flap_ok, flap_err = phase(
        "flap", make_body(FLAP_TOTAL, flap_sleep), {"f0": 1, "f1": 1},
        2, 2, 3, {
            "HVD_RESPONSE_CACHE": "1",
            "HVD_AUTOSCALE_SLO_MS": str(SLO_MS),
            "HVD_AUTOSCALE_BREACH_WINDOWS": "3",
            "HVD_AUTOSCALE_IDLE_WINDOWS": "3",
            "HVD_AUTOSCALE_IDLE_FACTOR": "0.6",
        })
    elapsed = time.monotonic() - t0

    err = None
    if not (load_ok and load_log):
        err = f"load phase: {load_err or 'no rank-0 log'}"
    elif not (evict_ok and evict_log):
        err = f"evict phase: {evict_err or 'no rank-0 log'}"
    elif not (flap_ok and flap_log):
        err = f"flap phase: {flap_err or 'no rank-0 log'}"
    if err is not None:
        print(json.dumps({"metric": "elastic_autoscale_closed_loop",
                          "value": None, "error": err[:500]}))
        return

    def acted(decisions):
        return [d for d in decisions if d["action"] != "hold"]

    # scale-up latency: breach start (first heavy step's wall time) to
    # the add decision — decisions and the step log share one monotonic
    # clock (driver and workers live in one loopback interpreter)
    breach_t0 = next((t for (t, s, *_r) in load_log if s >= RAMP), None)
    adds = [d for d in load_dec
            if d["action"] == "add" and d["reason"] == "slo-breach"]
    removes = [d for d in load_dec
               if d["action"] == "remove" and d["reason"] == "idle"]
    scale_up_latency_s = (round(adds[0]["t"] - breach_t0, 2)
                          if adds and breach_t0 is not None else None)
    load_worlds = [w for (_t, _s, w, *_r) in load_log]
    # steps lost across the idle scale-down (rank-0 counter deltas)
    down_lost = None
    for i in range(1, len(load_log)):
        if load_log[i][2] < load_log[i - 1][2]:
            down_lost = load_log[i][4] - load_log[i - 1][4]
    evicts = [d for d in evict_dec if d["action"] == "evict"]
    evict_worlds = [w for (_t, _s, w, *_r) in evict_log]

    print(json.dumps({
        "metric": "elastic_autoscale_closed_loop",
        "value": scale_up_latency_s,
        "unit": "seconds from SLO-breach load onset to the policy's "
                "un-scripted scale-up decision (sensor windows + "
                "hysteresis included); the other gates ride the "
                "phase blocks",
        "slo_ms": SLO_MS,
        "load": {
            "worlds": sorted(set(load_worlds)),
            "final_world": load_worlds[-1],
            "scale_up_latency_s": scale_up_latency_s,
            "scale_down_steps_lost": down_lost,
            "steps_lost_total": load_log[-1][4],
            "decisions": [(d["action"], d["reason"]) for d in
                          acted(load_dec)],
        },
        "evict": {
            "worlds": sorted(set(evict_worlds)),
            "final_world": evict_worlds[-1],
            "decisions": [(d["action"], d["reason"], d["rank"])
                          for d in acted(evict_dec)],
            "evicted_rank": evicts[0]["rank"] if evicts else None,
            "steps_lost_total": evict_log[-1][4],
            "warm_reuses": evict_log[-1][5],
        },
        "flap": {
            "decisions": [(d["action"], d["reason"]) for d in
                          acted(flap_dec)],
            "membership_decisions": len(acted(flap_dec)),
            "worlds": sorted(set(w for (_t, _s, w, *_r) in flap_log)),
        },
        "elapsed_s": round(elapsed, 1),
        "numerics_ok": bool(numerics_of(load_log)
                            and numerics_of(evict_log)
                            and numerics_of(flap_log)),
        "baseline": "PR-14 scripted churn: the identical membership "
                    "mechanics fired by a schedule; here every action "
                    "is policy-decided from the registry sensors",
    }))


def run_ckpt_recovery_bench(args):
    """Recovery-SLO lane for the checkpoint state plane
    (docs/checkpoint.md; BENCH_r18). For each model size, the IDENTICAL
    4->3->4 churn (graceful preempt, then a joiner that must be
    restored) runs twice:

    * **peer** — ``HVD_CKPT_PEER_RESTORE=1`` (the default): the joiner
      pulls per-rank shards from the survivors, so rank 0 serves only
      its ``1/len(survivors)`` share of the tree.
    * **broadcast** — ``HVD_CKPT_PEER_RESTORE=0``: the reference rank-0
      object broadcast, which re-syncs EVERY rank's full tree through
      rank 0.

    The gated numbers are the deterministic byte counters
    (``hvd_ckpt_restore_bytes_total{source=}``) measured as deltas from
    after the initial world formation (both lanes pay the same fresh
    broadcast there): peer must serve fewer rank-0 bytes than broadcast
    at EVERY size and its growth with model size must be sub-linear vs
    the broadcast baseline's. Wall-clock restore seconds ride along
    informationally — on a contended CI box they swing with scheduler
    noise. A final probe re-runs the smallest size with
    ``ckpt.shard_pull:error`` injected on every serve: the typed
    degraded path must fire exactly there and nowhere else."""
    from horovod_tpu.loopback.engine import _seed_xla_device_flags

    world_n = args.ckpt_recovery_world
    _seed_xla_device_flags(world_n + 1)

    from horovod_tpu.utils import faults
    from horovod_tpu.elastic.discovery import FixedHosts
    from horovod_tpu.loopback import elastic_run

    base_env = {
        "HVD_RESPONSE_CACHE": "1",
        "HVD_HEALTH_INTERVAL": "0.3",
        "HVD_HEALTH_TIMEOUT": "4",
        "HVD_METRICS": "1",
    }
    steps = args.ckpt_recovery_steps
    sleep_s = args.ckpt_recovery_step_sleep
    sizes = sorted(int(s) for s in
                   str(args.ckpt_recovery_sizes).split(","))
    churn_spec = (
        f"worker:preempt:rank={world_n - 1}:at_round=1:at_step=4"
        ":grace=30;"
        "worker:add:rank=0:at_round=2:after=4:count=1")

    # 8 equal param leaves: shards partition the FLATTENED tree by
    # leaf, so a single monolithic array would land whole in one
    # survivor's range and make rank 0's measured share degenerate
    n_parts = 8

    def lane(n_floats, peer_on, inject=None):
        spec = churn_spec + (";" + inject if inject else "")
        os.environ["HVD_FAULT_SPEC"] = spec
        faults.refresh()
        from horovod_tpu import metrics as _metrics
        _ckpt_insts = (_metrics.CKPT_RESTORE_BYTES,
                       _metrics.CKPT_PEER_SHARDS_PULLED,
                       _metrics.CKPT_DEGRADED_RESTORES,
                       _metrics.CKPT_RESTORE_SECONDS)
        # isolate this lane from earlier lanes in the same process
        _metrics.reset_all(*_ckpt_insts)
        box = {}

        def body():
            import horovod_tpu as _hvd
            from horovod_tpu import metrics as _metrics

            def tot(inst):
                # metric stores are per rank context (the joiner's pull
                # counters live on ITS thread's store): sum every store
                agg = {}
                for s in _metrics._all_stores():
                    for k, v in inst.series(s).items():
                        agg[k] = agg.get(k, 0) + v
                return agg

            _hvd.init()
            part = np.zeros(max(1, n_floats // n_parts), np.float32)
            state = _hvd.elastic.JaxState(
                params={f"w{i}": part.copy() for i in range(n_parts)},
                step=0, trans=0, lastw=0, p_ok=True)

            @_hvd.elastic.run
            def train(state):
                cap = steps * 4
                while state.step < cap and not (
                        state.step >= steps and state.trans >= 2):
                    if state.step == 0:
                        # founding ranks drop their formation-broadcast
                        # bytes from their OWN store so the lane counts
                        # only re-form restores; the joiner enters with
                        # the restored step > 0 and never resets — its
                        # pull counters are exactly what we measure
                        for inst in (
                                _metrics.CKPT_RESTORE_BYTES,
                                _metrics.CKPT_PEER_SHARDS_PULLED,
                                _metrics.CKPT_DEGRADED_RESTORES,
                                _metrics.CKPT_RESTORE_SECONDS):
                            inst.reset()
                    probe = _hvd.allreduce(jnp.arange(8.0) + 1.0,
                                           op=_hvd.Sum, name="probe")
                    flat = np.asarray(probe).reshape(-1)
                    world = int(round(float(flat[0])))
                    if abs(float(flat[1]) - 2.0 * world) > 1e-6:
                        state.p_ok = False
                    if state.lastw and world != state.lastw:
                        state.trans += 1
                    state.lastw = world
                    state.params = {
                        k: v + np.float32(1.0)
                        for k, v in state.params.items()}
                    state.step += 1
                    time.sleep(sleep_s)
                    state.commit()
                return state.step, state.trans, state.p_ok

            step_n, trans, p_ok = train(state)
            if _hvd.rank() == 0:
                srcs = {}
                for k, v in tot(_metrics.CKPT_RESTORE_BYTES).items():
                    src = dict(k).get("source", "?")
                    srcs[src] = srcs.get(src, 0) + int(v)
                rs_sum, rs_count = 0.0, 0
                for s in _metrics._all_stores():
                    for h in _metrics.CKPT_RESTORE_SECONDS.series(
                            s).values():
                        rs_sum += h.sum
                        rs_count += h.count
                box["result"] = {
                    "rank0_bytes": srcs.get("rank0", 0),
                    "peer_bytes": srcs.get("peer", 0),
                    "shards_pulled": int(sum(tot(
                        _metrics.CKPT_PEER_SHARDS_PULLED).values())),
                    "degraded": int(sum(tot(
                        _metrics.CKPT_DEGRADED_RESTORES).values())),
                    "steps": int(step_n),
                    "transitions": int(trans),
                    "numerics_ok": bool(p_ok),
                    "restore_s_sum": round(rs_sum, 3),
                    "restore_count": int(rs_count),
                }
            return 0

        env = dict(base_env)
        env["HVD_CKPT_PEER_RESTORE"] = "1" if peer_on else "0"
        results, ok = elastic_run(
            body, np=world_n, min_np=2, max_np=world_n,
            discovery=FixedHosts({f"h{i}": 1 for i in range(world_n)}),
            timeout=180, extra_env=env)
        if not ok or "result" not in box:
            return None, (results.error_message or "no rank-0 result")
        return box["result"], None

    t0 = time.monotonic()
    lanes = []
    err = None
    for n_floats in sizes:
        row = {"size": n_floats, "tree_bytes": n_floats * 4}
        for key, peer_on in (("peer", True), ("broadcast", False)):
            res, lane_err = lane(n_floats, peer_on)
            if lane_err:
                err = f"{key} lane at size {n_floats}: {lane_err}"
                break
            row[key] = res
        if err:
            break
        row["ratio"] = (
            round(row["peer"]["rank0_bytes"]
                  / row["broadcast"]["rank0_bytes"], 4)
            if row["broadcast"]["rank0_bytes"] else None)
        lanes.append(row)

    degraded_probe = None
    if err is None:
        degraded_probe, probe_err = lane(
            sizes[0], True, inject="ckpt.shard_pull:error")
        if probe_err:
            err = f"degraded probe: {probe_err}"
    elapsed = time.monotonic() - t0

    if err is not None:
        print(json.dumps({
            "metric": "ckpt_recovery_rank0_bytes",
            "value": None,
            "unit": "peer/broadcast rank-0 restore bytes at the "
                    "largest model size",
            "error": err[:500],
        }))
        return

    print(json.dumps({
        "metric": "ckpt_recovery_rank0_bytes",
        "value": lanes[-1]["ratio"],
        "unit": "peer/broadcast rank-0 restore bytes at the largest "
                "model size over the IDENTICAL 4->3->4 churn (<1.0 = "
                "the sharded peer restore serves measurably fewer "
                "bytes through rank 0 than the reference broadcast; "
                "~1/survivors = rank 0 serves only its own shard)",
        "world": world_n,
        "schedule": churn_spec,
        "sizes": sizes,
        "lanes": lanes,
        "degraded_probe": degraded_probe,
        "numerics_ok": bool(
            all(r[k]["numerics_ok"] for r in lanes
                for k in ("peer", "broadcast"))
            and degraded_probe["numerics_ok"]),
        "elapsed_s": round(elapsed, 1),
        "fast_health": {"interval_s": 0.3, "timeout_s": 4.0},
        "baseline": "the same churn with HVD_CKPT_PEER_RESTORE=0: the "
                    "reference rank-0 object broadcast re-syncing every "
                    "rank's full tree through rank 0",
    }))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=256,
                        help="per-chip batch size (256 measures ~1.5x the "
                             "throughput of 128 on v5e)")
    parser.add_argument("--num-iters", type=int, default=20,
                        help="total timed steps, rounded DOWN to a "
                             "multiple of --window (at least one window); "
                             "the JSON's timing.timed_steps reports the "
                             "actual count")
    parser.add_argument("--num-warmup", type=int, default=3,
                        help="untimed warmup steps (minimum 1: the first "
                             "step's loss is the training baseline and "
                             "compile must finish before timing)")
    parser.add_argument("--window", type=int, default=5,
                        help="steps per timed window (one device sync per "
                             "window; the chain serializes the steps)")
    parser.add_argument("--fp32", action="store_true",
                        help="compute in float32 instead of bfloat16")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize the forward in the backward "
                             "(jax.checkpoint): trades ~30%% more FLOPs "
                             "for activation memory, enabling per-chip "
                             "batches past HBM (e.g. 512 on v5e)")
    parser.add_argument("--dispatch-bench", action="store_true",
                        help="run the eager dispatch-overhead microbench "
                             "(CPU backend) instead of the ResNet-50 "
                             "training benchmark")
    parser.add_argument("--dispatch-iters", type=int, default=400,
                        help="timed calls per cache mode in "
                             "--dispatch-bench")
    parser.add_argument("--dispatch-tensors", type=int, default=16,
                        help="tensors per grouped_allreduce in "
                             "--dispatch-bench")
    parser.add_argument("--dispatch-size", type=int, default=1024,
                        help="per-rank elements per tensor in "
                             "--dispatch-bench")
    parser.add_argument("--cycle-bench", action="store_true",
                        help="run the cross-call fusion scheduler "
                             "microbench (CPU backend): "
                             "per-tensor allreduce_async loop, "
                             "scheduler on vs HVD_CYCLE_TIME=0")
    parser.add_argument("--cycle-iters", type=int, default=60,
                        help="timed submit+synchronize rounds per mode in "
                             "--cycle-bench")
    parser.add_argument("--cycle-tensors", type=int, default=64,
                        help="async allreduces per round in --cycle-bench")
    parser.add_argument("--cycle-size", type=int, default=4096,
                        help="bytes per tensor in --cycle-bench (default "
                             "4 KiB: the small-gradient regime the fusion "
                             "cycle exists for)")
    parser.add_argument("--pipeline-bench", action="store_true",
                        help="run the pipelined flush executor + chunk "
                             "pipeline microbench (CPU backend): "
                             "large-tensor "
                             "allreduce_async stream, "
                             "HVD_MAX_INFLIGHT_FLUSHES=2 + chunking vs "
                             "the synchronous executor")
    parser.add_argument("--pipeline-iters", type=int, default=20,
                        help="timed submit+synchronize rounds per mode in "
                             "--pipeline-bench")
    parser.add_argument("--pipeline-tensors", type=int, default=6,
                        help="async allreduces per round in "
                             "--pipeline-bench")
    parser.add_argument("--pipeline-size", type=int, default=4 * 1024 * 1024,
                        help="bytes per tensor in --pipeline-bench "
                             "(default 4 MiB: the large-tensor regime "
                             "chunk pipelining exists for)")
    parser.add_argument("--pipeline-chunks", type=int, default=4,
                        help="HVD_PIPELINE_CHUNKS for the pipelined mode "
                             "of --pipeline-bench")
    parser.add_argument("--overlap-bench", action="store_true",
                        help="run the flush-overlap microbench (CPU "
                             "backend): per-flush "
                             "allreduce_async stream, "
                             "HVD_MAX_INFLIGHT_FLUSHES=2 vs 1, gating "
                             "overlap_ratio > 0")
    parser.add_argument("--overlap-iters", type=int, default=12,
                        help="timed submit+synchronize rounds per mode in "
                             "--overlap-bench")
    parser.add_argument("--overlap-tensors", type=int, default=6,
                        help="async allreduces (= flushes) per round in "
                             "--overlap-bench")
    parser.add_argument("--overlap-size", type=int, default=1024 * 1024,
                        help="bytes per tensor in --overlap-bench "
                             "(default 1 MiB: big enough that a flush's "
                             "collective is still in flight when the next "
                             "flush dispatches)")
    parser.add_argument("--overlap-slots", type=int, default=2,
                        help="HVD_MAX_INFLIGHT_FLUSHES for the pipelined "
                             "mode of --overlap-bench")
    parser.add_argument("--step-bench", action="store_true",
                        help="run the end-to-end eager DP step-time "
                             "benchmark (CPU backend): "
                             "models/ ResNet-50 + TransformerLM, "
                             "bucketed backward (HVD_BUCKET_BYTES) vs "
                             "whole-tree allreduce")
    parser.add_argument("--step-iters", type=int, default=10,
                        help="timed steps per mode/model in --step-bench")
    parser.add_argument("--step-batch", type=int, default=2,
                        help="per-chip batch size in --step-bench")
    parser.add_argument("--step-image-size", type=int, default=16,
                        help="ResNet input resolution in --step-bench "
                             "(small: the bench isolates sync overlap, "
                             "not conv throughput)")
    parser.add_argument("--step-seq-len", type=int, default=64,
                        help="transformer sequence length in --step-bench")
    parser.add_argument("--step-bucket-bytes", type=int,
                        default=4 * 1024 * 1024,
                        help="HVD_BUCKET_BYTES for the bucketed mode of "
                             "--step-bench (default 4 MiB so the small "
                             "bench models split into several buckets; "
                             "production default is 64 MiB)")
    parser.add_argument("--capture-bench", action="store_true",
                        help="run the step capture-and-replay benchmark "
                             "(CPU backend): eager "
                             "DP TransformerLM step, HVD_STEP_CAPTURE on "
                             "(whole-step replay program) vs off (per-"
                             "flush dispatch), plus a forced-divergence "
                             "fallback check")
    parser.add_argument("--capture-iters", type=int, default=8,
                        help="timed steps per mode pass in --capture-bench")
    parser.add_argument("--capture-batch", type=int, default=1,
                        help="per-chip batch size in --capture-bench")
    parser.add_argument("--capture-seq-len", type=int, default=8,
                        help="sequence length in --capture-bench")
    parser.add_argument("--capture-vocab", type=int, default=1024,
                        help="vocab size in --capture-bench (small: the "
                             "bench isolates dispatch overhead, not "
                             "collective bandwidth)")
    parser.add_argument("--capture-layers", type=int, default=8,
                        help="transformer layers in --capture-bench "
                             "(deep-narrow: many small gradient leaves, "
                             "the per-parameter dispatch regime)")
    parser.add_argument("--capture-dmodel", type=int, default=64,
                        help="model width in --capture-bench")
    parser.add_argument("--capture-bucket-bytes", type=int, default=8192,
                        help="HVD_BUCKET_BYTES in --capture-bench (tiny: "
                             "~per-parameter dispatch, the reference's "
                             "per-layer hook stream; the divergence "
                             "phase quadruples it)")
    parser.add_argument("--metrics-bench", action="store_true",
                        help="run the metrics-registry overhead "
                             "microbench (CPU backend): "
                             "the --cycle-bench async stream with "
                             "the registry force-enabled vs disabled in "
                             "interleaved A/B chunks (docs/metrics.md "
                             "overhead contract; ci.sh gates <= 3%%)")
    parser.add_argument("--metrics-iters", type=int, default=60,
                        help="total timed rounds per mode in "
                             "--metrics-bench")
    parser.add_argument("--metrics-tensors", type=int, default=64,
                        help="async allreduces per round in "
                             "--metrics-bench")
    parser.add_argument("--metrics-size", type=int, default=4096,
                        help="bytes per tensor in --metrics-bench (small: "
                             "maximizes per-dispatch overhead visibility)")
    parser.add_argument("--conformance-bench", action="store_true",
                        help="run the conformance-recorder overhead "
                             "microbench (CPU backend): "
                             "the --metrics-bench async stream "
                             "with the recorder force-enabled vs disabled "
                             "in ABBA-interleaved chunks "
                             "(docs/conformance.md cost contract; ci.sh "
                             "gates <= 3%%)")
    parser.add_argument("--conformance-iters", type=int, default=60,
                        help="total timed rounds per mode in "
                             "--conformance-bench")
    parser.add_argument("--conformance-tensors", type=int, default=64,
                        help="async allreduces per round in "
                             "--conformance-bench")
    parser.add_argument("--conformance-size", type=int, default=4096,
                        help="bytes per tensor in --conformance-bench "
                             "(small: maximizes per-dispatch overhead "
                             "visibility)")
    parser.add_argument("--protocol-bench", action="store_true",
                        help="protocol-scalability sweep: negotiation "
                             "round latency + per-rank KV ops/step + "
                             "response-cache hit rate vs world, flat vs "
                             "hierarchy+cache (BENCH_r13; "
                             "docs/negotiation.md)")
    parser.add_argument("--protocol-worlds", default="4,16,64",
                        help="comma-separated loopback world sizes to "
                             "sweep (each in a fresh subprocess)")
    parser.add_argument("--protocol-child", type=int, default=0,
                        help="(internal) run ONE world of the sweep in "
                             "this process; XLA devices must already be "
                             "seeded by the parent")
    parser.add_argument("--protocol-cache", default="0",
                        help="(internal) HVD_RESPONSE_CACHE for the child")
    parser.add_argument("--protocol-hier", default="auto",
                        help="(internal) HVD_HIER_NEGOTIATION for the "
                             "child")
    parser.add_argument("--protocol-steps", type=int, default=8,
                        help="steady-state steps measured per world")
    parser.add_argument("--protocol-warmup", type=int, default=3,
                        help="warm-up steps before the measured window "
                             "(negotiate + confirm the response cache)")
    parser.add_argument("--protocol-tensors", type=int, default=4,
                        help="named negotiated allreduces per step")
    parser.add_argument("--protocol-flat-max", type=int, default=16,
                        help="largest world the FLAT (uncached) lane "
                             "runs at — its rounds grow superlinearly "
                             "on the CPU emulation; larger worlds run "
                             "the cached lane only (skip is recorded)")
    parser.add_argument("--protocol-capture-parity", action="store_true",
                        help="(internal) also run capture-on/off parity "
                             "steps in the child world")
    parser.add_argument("--elastic-bench", action="store_true",
                        help="elastic churn under load at a loopback "
                             "world (docs/elastic.md; BENCH_r14): a "
                             "seeded HVD_FAULT_SPEC schedule removes, "
                             "adds, preempts and crashes workers "
                             "mid-training and the recovery-time / "
                             "steps-lost / warm-vs-cold SLOs come off "
                             "the step log and hvd_elastic_* registry")
    parser.add_argument("--elastic-world", type=int, default=4,
                        help="starting loopback world size for "
                             "--elastic-bench")
    parser.add_argument("--elastic-steps", type=int, default=80,
                        help="committed training steps in --elastic-bench")
    parser.add_argument("--elastic-step-sleep", type=float, default=0.02,
                        help="seconds of compute stand-in per step in "
                             "--elastic-bench")
    parser.add_argument("--elastic-tensors", type=int, default=6,
                        help="stable-named allreduces per step in "
                             "--elastic-bench (negotiation traffic the "
                             "warm/cold window actually measures)")
    parser.add_argument("--elastic-window", type=int, default=6,
                        help="steps of the post-re-form window the "
                             "warm/cold step-time ratio averages over")
    parser.add_argument("--elastic-e1", type=int, default=6,
                        help="round-1 commit of each phase's first "
                             "event (cold preempt / abrupt remove)")
    parser.add_argument("--elastic-e2", type=int, default=8,
                        help="commits INSIDE each later round before "
                             "its event fires (at_round-keyed, so "
                             "re-form latency cannot skew the schedule)")
    parser.add_argument("--elastic-abrupt-steps", type=int, default=40,
                        help="committed steps in the abrupt-loss phase "
                             "of --elastic-bench")
    parser.add_argument("--elastic-spec", default=None,
                        help="HVD_FAULT_SPEC override for the CHURN "
                             "phase of --elastic-bench (replaces the "
                             "scheduled graceful default; the abrupt "
                             "phase keeps its own schedule)")
    parser.add_argument("--autoscale-bench", action="store_true",
                        help="closed-loop elastic autoscaling at a "
                             "loopback world (docs/elastic.md "
                             "'Autoscaler'; BENCH_r15): an un-scripted "
                             "SLO breach triggers a policy scale-up, "
                             "sustained idle a zero-loss scale-down, a "
                             "fault-injected slow rank is evicted and "
                             "named, and adversarial flapping produces "
                             "no oscillation")
    parser.add_argument("--ckpt-recovery-bench", action="store_true",
                        help="checkpoint state-plane recovery-SLO lane "
                             "(docs/checkpoint.md; BENCH_r18): the "
                             "identical 4->3->4 churn per model size "
                             "with peer-restore on vs the rank-0 "
                             "broadcast baseline, gated on the "
                             "deterministic hvd_ckpt_restore_bytes "
                             "counters, plus an injected "
                             "ckpt.shard_pull probe that must take the "
                             "typed degraded path")
    parser.add_argument("--ckpt-recovery-world", type=int, default=4,
                        help="starting loopback world size for "
                             "--ckpt-recovery-bench")
    parser.add_argument("--ckpt-recovery-steps", type=int, default=16,
                        help="committed steps per lane in "
                             "--ckpt-recovery-bench (the lane runs on "
                             "until both churn transitions were "
                             "observed, capped at 4x)")
    parser.add_argument("--ckpt-recovery-step-sleep", type=float,
                        default=0.02,
                        help="seconds of compute stand-in per step in "
                             "--ckpt-recovery-bench")
    parser.add_argument("--ckpt-recovery-sizes",
                        default="8192,65536,262144",
                        help="comma-separated float32 param counts (the "
                             "model-size sweep of --ckpt-recovery-bench"
                             "; default 32 KB / 256 KB / 1 MB trees)")
    parser.add_argument("--serve-bench", action="store_true",
                        help="run the multi-tenant inference-serving QoS "
                             "benchmark (CPU backend): "
                             "high-priority transformer serve "
                             "tenant vs a saturating bulk tenant, "
                             "HVD_QOS on vs off (docs/qos.md)")
    parser.add_argument("--serve-requests", type=int, default=25,
                        help="serve requests per measurement phase in "
                             "--serve-bench (4 phases QoS on, 2 off)")
    parser.add_argument("--serve-vocab", type=int, default=512,
                        help="transformer vocab in --serve-bench")
    parser.add_argument("--serve-dmodel", type=int, default=128,
                        help="transformer width in --serve-bench (sized "
                             "so a request's grad sync is ~1.6 MB — a "
                             "real per-request sync, not a microbench "
                             "ping)")
    parser.add_argument("--serve-batch", type=int, default=8,
                        help="activation rows allgathered per request in "
                             "--serve-bench")
    parser.add_argument("--serve-bulk-tensors", type=int, default=8,
                        help="tensors per bulk burst in --serve-bench")
    parser.add_argument("--serve-bulk-size", type=int, default=8 * 1024,
                        help="bytes per bulk tensor in --serve-bench "
                             "(small: bounded head-of-line blocking per "
                             "drained batch)")
    parser.add_argument("--serve-bulk-depth", type=int, default=8,
                        help="outstanding bulk bursts before the flood "
                             "thread reaps one in --serve-bench")
    parser.add_argument("--serve-bulk-pace", type=float, default=0.001,
                        help="seconds between bulk bursts in "
                             "--serve-bench (paced continuous-batching "
                             "arrivals; 0 = busy loop)")
    parser.add_argument("--serve-quota", type=int, default=256 * 1024,
                        help="bulk tenant pending-bytes shed quota in "
                             "--serve-bench (below depth x burst bytes "
                             "so a deep backlog sheds while the flood "
                             "continues)")
    args = parser.parse_args()

    if args.dispatch_bench:
        return run_dispatch_bench(args)
    if args.cycle_bench:
        return run_cycle_bench(args)
    if args.pipeline_bench:
        return run_pipeline_bench(args)
    if args.overlap_bench:
        return run_overlap_bench(args)
    if args.step_bench:
        return run_step_bench(args)
    if args.capture_bench:
        return run_capture_bench(args)
    if args.metrics_bench:
        return run_metrics_bench(args)
    if args.conformance_bench:
        return run_conformance_bench(args)
    if args.protocol_child:
        return run_protocol_child(args)
    if args.protocol_bench:
        return run_protocol_bench(args)
    if args.serve_bench:
        return run_serve_bench(args)
    if args.elastic_bench:
        return run_elastic_bench(args)
    if args.autoscale_bench:
        return run_autoscale_bench(args)
    if args.ckpt_recovery_bench:
        return run_ckpt_recovery_bench(args)

    device = require_tpu("bench.py's ResNet-50 lane")
    place_compile_cache()
    hvd.init()
    n = hvd.size()

    model = ResNet50(num_classes=1000,
                     dtype=jnp.float32 if args.fp32 else jnp.bfloat16,
                     axis_name=hvd.axis_name())
    # Reference benchmark uses plain SGD lr=0.01; gradient sync through the
    # framework's DistributedOptimizer (allreduce average over the mesh).
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    sharded_step, (params, batch_stats, opt_state), (images, labels) = \
        classifier_trainer(model, tx, image_size=224,
                           batch_per_chip=args.batch_size, remat=args.remat)

    # Per-device program FLOPs from the compiler itself. The compiled
    # executable is reused for the run so the program compiles once.
    sharded_step = sharded_step.lower(
        params, batch_stats, opt_state, images, labels).compile()
    flops_executed = float(sharded_step.cost_analysis()["flops"])
    if args.remat:
        # MFU convention counts MODEL flops only; the compiled program's
        # count includes the rematerialized forward, which would inflate
        # utilization by the recompute fraction. Keep the executed count
        # as a diagnostic, score MFU from the analytic model count.
        flops_per_step_per_chip = (
            ANALYTIC_RESNET50_TRAIN_FLOPS_PER_IMAGE * args.batch_size)
        flops_source = "analytic_model_flops_remat_excluded"
    else:
        flops_per_step_per_chip = flops_executed
        flops_source = "xla_cost_analysis"

    first_loss = None
    for _ in range(max(1, args.num_warmup)):
        params, batch_stats, opt_state, loss = sharded_step(
            params, batch_stats, opt_state, images, labels)
        if first_loss is None:
            first_loss = float(loss)  # step-1 loss: the training baseline
    jax.block_until_ready(loss)  # warmup fully complete before timing

    # Timed windows of chained steps: the data dependency (step i+1
    # consumes step i's params/stats/opt_state) serializes the steps on
    # device, so window_time/window = true steady-state step time; the
    # single D2H sync per window keeps the host round trip out of the
    # measurement (see module docstring).
    window = max(1, args.window)
    n_windows = max(1, args.num_iters // window)
    window_means = []
    last_loss = first_loss
    for _ in range(n_windows):
        start = time.perf_counter()
        for _ in range(window):
            params, batch_stats, opt_state, loss = sharded_step(
                params, batch_stats, opt_state, images, labels)
        last_loss = float(loss)  # D2H: the whole chained window finished
        window_means.append((time.perf_counter() - start) / window)

    times = np.asarray(window_means)
    mean_t = float(times.mean())
    img_per_sec_per_chip = args.batch_size / mean_t
    losses = [first_loss, last_loss]

    # no utilization off the chip: a CPU run has no peak to be scored against
    peak = chip_peak_flops(device) if device.platform == "tpu" else None
    mfu = (round(flops_per_step_per_chip / mean_t / peak, 4)
           if peak else None)

    print(json.dumps({
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": round(img_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec_per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 3),
        "baseline": BASELINE_DESC,
        "mfu": mfu,
        "flops_per_step_per_chip": flops_per_step_per_chip,
        "flops_executed_per_step_per_chip": flops_executed,
        "flops_source": flops_source,
        "remat": bool(args.remat),
        "chip_peak_bf16_flops": peak,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_chips": n,
        "batch_size_per_chip": args.batch_size,
        "step_time_ms": {
            "mean": round(mean_t * 1e3, 3),
            "p50": round(float(np.percentile(times, 50)) * 1e3, 3),
            "min": round(float(times.min()) * 1e3, 3),
            "max": round(float(times.max()) * 1e3, 3),
        },
        "timing": {"method": "chained_windows", "window": window,
                   "n_windows": n_windows,
                   "timed_steps": window * n_windows},
        "pipeline_overlap": _pipeline_summary(),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "loss_decreased": bool(losses[-1] < losses[0]),
    }))
    if not (np.isfinite(losses[-1]) and losses[-1] < losses[0]):
        raise RuntimeError(
            f"the timed step is not training: loss {losses[0]} -> "
            f"{losses[-1]}")


def _error_artifact(message: str) -> None:
    print(json.dumps({
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": None,
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "error": message[:500],
    }), flush=True)


def _on_sigterm(signum, frame):
    # A supervising driver's kill budget must not erase the evidence:
    # emit the parseable error artifact before dying (SIGKILL is
    # unsurvivable, but drivers normally TERM first).
    _error_artifact(f"terminated by signal {signum} while running/waiting")
    sys.exit(1)


if __name__ == "__main__":
    import signal
    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        main()
    except Exception as e:  # noqa: BLE001 — the artifact must always parse
        # Even a dead backend yields a parseable artifact that says exactly
        # what failed (round 4's rc=1 with empty stdout lost the evidence).
        import traceback
        traceback.print_exc()
        _error_artifact(f"{type(e).__name__}: {e}")
        sys.exit(1)  # the artifact parses, but the run did fail
