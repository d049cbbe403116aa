#!/usr/bin/env python
"""Chip smoke: drive the five-line training path once on the real TPU.

    python chip_smoke.py             # needs a TPU; anything else exits 1
    python chip_smoke.py --dry-run   # tiny sizes, any platform: debug here

One process, every local chip. Phases, each fatal on failure:

  native_build       the engine rebuilt from native/*.cc on this machine
  resnet50_traced    ResNet-50 bf16 224x224 batch 256/chip through
                     hvd.init / broadcast_parameters / DistributedOptimizer /
                     jit(shard_map): one compile, 10 chained steps, loss
                     down, no compilation in the window; on several chips
                     the batch sits on distinct devices, the parameters on
                     all of them, and the gradient all-reduce is in the HLO
  transformer_gspmd  TransformerLM at its default config through
                     hvd.cached_step; a re-created step replays, 0 retraces
  eager              grouped_allreduce over multi-MB per_rank tensors and one
                     eager DistributedOptimizer.update over a ResNet-50-shaped
                     gradient tree, against numpy (the bucketed / chunked /
                     ping-pong / chained branches, with real donation)
  flash_kernels      the three Pallas kernels, compiled by Mosaic, against
                     the float32 jnp formulation at three shapes, the
                     benchmark's (64, 1024, 64) among them
  multichip_*        __graft_entry__.dryrun_stages on the real mesh
                     (skipped below four chips)

The seconds printed per phase (wall_s, compile_s, run_s) are smoke
timings: they say the phase ran, and how the compile cache behaved. They
are not benchmark numbers.

A passing run ends with two stdout lines: ``[smoke] summary: {...}`` (the
phases, what was skipped, the totals, ``"claim": null``) and then, last,
exactly the object the driver reads:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
Any failure exits non-zero with no such line.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as hvd
from horovod_tpu import _native
from horovod_tpu.models import (ResNet18, ResNet50, TransformerConfig,
                                TransformerLM)
from horovod_tpu.models.train import classifier_trainer
from horovod_tpu.ops import flash
from horovod_tpu.utils.compile_cache import place_compile_cache

import __graft_entry__ as graft

# (batch*heads, sequence, head_dim): the shape the kernels' one recorded
# comparison used, and one that exercises the q/kv padding masks.
# (batch x heads, sequence, head): the long block the ring was sized for,
# a length off the tile grid, and what the benchmark's GPT-2 cells run
FLASH_SHAPES = ((16, 2048, 128), (16, 1000, 64), (64, 1024, 64))

# Lowering and backend compilation (or the fetch from the persistent
# cache). Tracing is left out: its events nest, one per inner jit.
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Seconds jax spent lowering and compiling, how many programs
    reached the backend, and how many of those the persistent cache
    served."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += seconds
            self.programs += event == _COMPILE_EVENTS[1]

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


class Stopwatch:
    """``with Stopwatch() as t: ...`` then ``t.seconds``."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0


def check(cond, message):
    if not cond:
        raise AssertionError(message)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_native_build(ctx):
    path = _native.build_native(force=True)
    check(os.path.getmtime(path) >= ctx["started"] - 1,
          f"{path} was not rebuilt by this run")
    return {"lib": os.path.relpath(path), "version": _native.version()}


def phase_resnet50_traced(ctx):
    import optax

    n, dry = hvd.size(), ctx["dry"]
    model = (ResNet18 if dry else ResNet50)(
        num_classes=1000, dtype=jnp.bfloat16, axis_name=hvd.axis_name())
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    step, (params, stats, opt), (images, labels) = classifier_trainer(
        model, tx, image_size=32 if dry else 224,
        batch_per_chip=4 if dry else 256)

    check(len({s.device for s in images.addressable_shards}) == n,
          "the batch's shards do not sit on every chip")
    leaf = jax.tree.leaves(params)[0]
    check(leaf.sharding.is_fully_replicated
          and len({s.device for s in leaf.addressable_shards}) == n,
          "parameters are not replicated on every chip")

    compiled = step.lower(params, stats, opt, images, labels).compile()
    if n > 1:
        check("all-reduce" in compiled.as_text(),
              "no all-reduce in the compiled step: gradients are not synced")

    params, stats, opt, loss = compiled(params, stats, opt, images, labels)
    first = float(loss)  # warm-up done; step-1 loss is the baseline
    programs = ctx["meter"].programs
    with Stopwatch() as window:
        for _ in range(10):
            params, stats, opt, loss = compiled(params, stats, opt, images,
                                                labels)
        last = float(jax.block_until_ready(loss))
    check(ctx["meter"].programs == programs,
          "a program was compiled after warm-up")
    check(np.isfinite(last) and last < first,
          f"loss did not go down: {first} -> {last}")
    if jax.devices()[0].platform == "tpu":
        in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        check(all(b > 0 for b in in_use), f"idle chips: {in_use}")
    return {"run_s": window.seconds, "steps": 10,
            "loss_first": round(first, 4), "loss_last": round(last, 4),
            "batch_per_chip": images.shape[0] // n}


def phase_transformer_gspmd(ctx):
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n, dry = hvd.size(), ctx["dry"]
    cfg = (TransformerConfig(vocab_size=256, num_layers=2, num_heads=4,
                             d_model=64, d_ff=128, max_seq_len=64)
           if dry else TransformerConfig())
    model = TransformerLM(cfg)
    seq, batch = cfg.max_seq_len, (1 if dry else 4) * n
    mesh = hvd.mesh()
    tokens = jax.device_put(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        NamedSharding(mesh, P(hvd.axis_name())))
    params = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, seq), jnp.int32))["params"])(jax.random.PRNGKey(0))
    params = jax.device_put(hvd.broadcast_parameters(params, 0),
                            NamedSharding(mesh, P()))
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt = jax.device_put(tx.init(params), NamedSharding(mesh, P()))

    def make_step():
        # re-running this builder yields a structurally identical closure:
        # the re-created per-step closure a plain jax.jit would retrace
        def train_step(params, opt, tokens):
            def loss_fn(p):
                logp = jax.nn.log_softmax(
                    model.apply({"params": p}, tokens)[:, :-1])
                picked = jnp.take_along_axis(
                    logp, tokens[:, 1:, None], axis=-1)
                return -jnp.mean(picked)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt2 = tx.update(grads, opt, params)
            return optax.apply_updates(params, updates), opt2, loss

        return train_step

    cold = hvd.cached_step(make_step())
    params, opt, loss0 = cold(params, opt, tokens)
    loss0 = float(loss0)
    check(cold.traces == 1, f"cold step traced {cold.traces} times")

    hits = hvd.gspmd_cache_stats()["hits"]
    warm = hvd.cached_step(make_step())
    with Stopwatch() as replay:
        params, opt, loss1 = warm(params, opt, tokens)
        loss1 = float(loss1)
    check(warm.traces == 0, f"replay retraced {warm.traces} times")
    check(hvd.gspmd_cache_stats()["hits"] == hits + 1,
          "replay did not hit the cached program")
    check(np.isfinite(loss1) and loss1 < loss0,
          f"loss did not go down: {loss0} -> {loss1}")
    return {"run_s": replay.seconds, "steps": 1, "retraces": warm.traces,
            "loss_first": round(loss0, 4), "loss_last": round(loss1, 4),
            "batch": batch, "seq": seq}


def phase_eager(ctx):
    import optax

    n = hvd.size()
    rng = np.random.default_rng(0)

    # a 24 MB fused wire buffer: past HVD_PIPELINE_THRESHOLD, so the plan
    # is the chunked one; later rounds reuse it and its recycled buffers
    sizes = (2 << 20, 3 << 20, 1 << 20)
    for _ in range(3):
        host = [[rng.standard_normal(s, dtype=np.float32) for _ in range(n)]
                for s in sizes]
        tensors = [hvd.per_rank(t) for t in host]
        with Stopwatch() as warm_round:  # the last one is what is reported
            out = jax.block_until_ready(
                hvd.grouped_allreduce(tensors, op=hvd.Sum))
        for got, per_rank in zip(out, host):
            np.testing.assert_allclose(
                np.asarray(got), np.sum(per_rank, axis=0, dtype=np.float32),
                rtol=1e-5, atol=1e-5)

    # ResNet-50's gradient tree (161 leaves, ~100 MB of float32): rank r
    # contributes base*(r+1), so the average and the first SGD-momentum
    # update are known in closed form
    base = jax.jit(lambda key: ResNet50(num_classes=1000).init(
        key, jnp.zeros((1, 32, 32, 3)), train=True)["params"])(
            jax.random.PRNGKey(1))
    ranks = jnp.arange(1.0, n + 1.0)
    grads = jax.tree.map(hvd.per_rank, jax.jit(lambda tree: jax.tree.map(
        lambda p: ranks.reshape((n,) + (1,) * p.ndim) * p, tree))(base))
    tx = hvd.DistributedOptimizer(optax.sgd(0.5, momentum=0.9))
    flushes = hvd.fusion_stats()["flushes"].get("bucket", 0)
    updates, _ = tx.update(grads, tx.init(base), base)
    want = jax.tree.map(lambda p: -0.5 * (n + 1) / 2.0 * np.asarray(p), base)
    for got, ref in zip(jax.tree.leaves(updates), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-7)
    buckets = hvd.fusion_stats()["flushes"].get("bucket", 0) - flushes
    check(buckets >= 2, f"the gradient tree went out in {buckets} bucket(s)")
    plans = hvd.dispatch_cache_stats()
    return {"run_s": warm_round.seconds, "steps": 1,
            "grad_leaves": len(jax.tree.leaves(base)), "buckets": buckets,
            "plan_hits": plans["hits"], "plan_misses": plans["misses"]}


def flash_programs(shape, interpret=False):
    """The three kernels at ``shape`` as ``[(name, fn, arg_specs), ...]``,
    one Mosaic call each: the ring's ``step`` (carries in and out), the
    ``whole`` local attention ``TransformerLM``'s "full" mode runs, and
    ``flash_block_grads`` (dq, dk and dv); causal, on bfloat16 q/k/v."""
    bh, s, d = shape
    zero = jnp.int32(0)
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    col = jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)
    acc = jax.ShapeDtypeStruct(shape, jnp.float32)

    def step(q, k, v, m, l, acc):
        return flash._flash_call(q, k, v, zero, zero, True, m, l, acc,
                                 interpret)

    def whole(q, k, v):
        return flash.flash_attend(q, k, v, True, interpret)

    def bwd(q, k, v, lse, dout, D):
        return flash.flash_block_grads(q, k, v, lse, dout, D, zero, zero,
                                       True, interpret=interpret)

    return [("step", step, (qkv, qkv, qkv, col, col, acc)),
            ("whole", whole, (qkv, qkv, qkv)),
            ("bwd", bwd, (qkv, qkv, qkv, col, qkv, col))]


def _flash_reference(shape):
    """Seeded inputs for the three programs and what the float32 jnp
    formulation makes of them: ``{name: (args, want)}``."""
    bh, s, d = shape
    zero = jnp.int32(0)

    @jax.jit
    def build(key):
        q, k, v, dout = (jax.random.normal(sub, shape, jnp.float32)
                         .astype(jnp.bfloat16)
                         for sub in jax.random.split(key, 4))
        q = (q * d ** -0.5).astype(jnp.bfloat16)
        carries = (jnp.full((bh, s, 1), flash.NEG_INF, jnp.float32),
                   jnp.zeros((bh, s, 1), jnp.float32),
                   jnp.zeros(shape, jnp.float32))
        f32 = [x.astype(jnp.float32) for x in (q, k, v, dout)]
        with jax.default_matmul_precision("highest"):
            m, l, acc = flash._attend_jnp(*f32[:3], zero, zero, True,
                                          *carries)
            lse = m + jnp.log(l)
            D = jnp.sum(f32[3] * acc / l, -1, keepdims=True)
            grads = flash.jnp_block_grads(*f32[:3], lse, f32[3], D, zero,
                                          zero, True)
        return {"step": ((q, k, v) + carries, (m, l, acc)),
                "whole": ((q, k, v), (acc / l, lse)),
                "bwd": ((q, k, v, lse, dout, D), grads)}

    return build(jax.random.PRNGKey(s))


def phase_flash_kernels(ctx):
    on_tpu = jax.devices()[0].platform == "tpu"
    errors, run_s = {}, 0.0
    for shape in FLASH_SHAPES[1:2] if ctx["dry"] else FLASH_SHAPES:
        reference = _flash_reference(shape)
        for name, fn, specs in flash_programs(shape, interpret=not on_tpu):
            lowered = jax.jit(fn).lower(*specs)
            if on_tpu:
                calls = lowered.as_text().count("tpu_custom_call")
                check(calls == 1,
                      f"{name}{shape}: {calls} Mosaic calls in the lowering")
            args, want = reference[name]
            kernel = lowered.compile()
            jax.block_until_ready(kernel(*args))
            with Stopwatch() as second:
                got = jax.block_until_ready(kernel(*args))
            run_s += second.seconds
            # bfloat16 operands on the MXU against a float32 reference:
            # errors scale with each output's magnitude
            err = max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
                      for g, w in zip(got, want))
            check(err < 2e-2, f"{name}{shape}: relative error {err:.2e}")
            errors[f"{name}{'x'.join(map(str, shape))}"] = float(f"{err:.2e}")
    return {"run_s": run_s, "steps": len(errors), "max_rel_err": errors,
            "interpret": not on_tpu}


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run_phase(ctx, name, fn):
    """Run one phase; any exception is fatal. The row it records:
    ``wall_s`` for the whole phase, ``compile_s`` of that spent lowering
    and compiling (or fetching from the compile cache), and ``run_s`` for
    the ``steps`` executions the phase made after its warm-up."""
    meter = ctx["meter"]
    compile0, hits0 = meter.seconds, meter.cache_hits
    try:
        with Stopwatch() as wall:
            detail = fn(ctx) or {}
    except BaseException:
        print(f"[smoke] {name}: FAIL after {wall.seconds:.1f}s "
              f"(platform: {ctx['device']['platform']})", flush=True)
        raise
    row = {"wall_s": wall.seconds, "compile_s": meter.seconds - compile0,
           "cache_hits": meter.cache_hits - hits0, **detail}
    row = {k: round(v, 3) if isinstance(v, float) else v
           for k, v in row.items()}
    ctx["phases"][name] = row
    print(f"[smoke] {name}: PASS (platform: {ctx['device']['platform']}; "
          f"smoke timings, not benchmark numbers) {json.dumps(row)}",
          flush=True)


def result_line(device):
    """The last stdout line of a passing run. The driver's contract:
    these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--dry-run", action="store_true",
        help="debugging aid: tiny sizes on whatever platform jax finds, "
             "Pallas kernels interpreted off the TPU; prints no result")
    args = parser.parse_args()
    started = time.time()

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] platform: {device['platform']}  device_kind: "
          f"{device['kind']}  devices: {device['count']}", flush=True)
    if first.platform != "tpu" and not args.dry_run:
        sys.exit(f"chip_smoke: needs a TPU, but jax found platform "
                 f"{first.platform!r} ({first.device_kind}). "
                 "--dry-run debugs the phases at tiny sizes off the chip.")

    ctx = {"dry": args.dry_run, "device": device, "started": started,
           "phases": {}, "meter": CompileMeter()}
    cache_dir = place_compile_cache()
    print(f"[smoke] compile cache: {cache_dir}", flush=True)
    run_phase(ctx, "native_build", phase_native_build)
    hvd.init()
    check(hvd.size() == device["count"],
          f"hvd.size()={hvd.size()} over {device['count']} local chips")
    run_phase(ctx, "resnet50_traced", phase_resnet50_traced)
    run_phase(ctx, "transformer_gspmd", phase_transformer_gspmd)
    run_phase(ctx, "eager", phase_eager)
    run_phase(ctx, "flash_kernels", phase_flash_kernels)
    skipped = []
    for name, stage in graft.dryrun_stages(hvd.size()):
        if hvd.size() >= 4:
            run_phase(ctx, f"multichip_{name}", lambda _ctx: stage())
        else:
            skipped.append(f"multichip_{name}")
            print(f"[smoke] multichip_{name}: skipped: needs ≥4 chips",
                  flush=True)
    hvd.shutdown()

    meter = ctx["meter"]
    totals = {"compile_s": round(meter.seconds, 1),
              "programs": meter.programs, "cache_hits": meter.cache_hits,
              "wall_s": round(time.time() - started, 1)}
    print(f"[smoke] totals (smoke timings): {json.dumps(totals)}", flush=True)
    if args.dry_run:
        print(f"[smoke] DRY RUN on platform {device['platform']}: every "
              "phase passed at tiny size. Not a chip result.", flush=True)
        return
    summary = {"phases": ctx["phases"], "skipped": skipped, "totals": totals,
               "compile_cache": cache_dir, "claim": None}
    print(f"[smoke] summary: {json.dumps(summary)}", flush=True)
    print(result_line(device), flush=True)


if __name__ == "__main__":
    main()
