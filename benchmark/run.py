#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds exactly the
chips the cell asks for. Everything about a cell comes from data:
``BENCHMARK.json`` (which cells and metrics exist),
``benchmark/workloads/<cell>.json``, the configuration's file, and the
modules those name (``models/``, ``jobs/``, ``layers/``). This file
holds no table of names. See ``benchmark/README.md``.

The last stdout line of a successful run on the chip is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
with ``--trace 1`` ``breakdown``). Off the TPU the run fails in seconds,
except under an explicit ``JAX_PLATFORMS=cpu``: then it is a rehearsal
at the ``tiny`` sizes the files carry, prints no result line and exits
with code 3.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import statistics
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, ".bench_out")   # traces; gitignored
# In steps, so that a cell's ``window_steps`` changes none of them: a
# cell of 8-step windows warms up at most 3 windows and traces 2, one of
# 1-step windows at most 24 and traces 16.
WARMUP_STEPS_MAX = 24  # warm-up fails if it still compiles after these
WARMUP_CLEAN_STEPS = 8  # consecutive steps, in whole windows, compiling nothing
TRACE_STEPS = 16       # steps under the profiler in a --trace 1 run
PLAIN_STEPS = 16       # steps before them, profiler off, same run
REHEARSAL_EXIT = 3


def log(message):
    print(f"[bench] {message}", flush=True)


def process_age_s():
    """Seconds since this process was created (the kernel's clock, 10 ms
    ticks): set-up starts at process start, not at the first import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name):
    """``(spec, cell, config)`` for the workload ``name``: its entry in
    BENCHMARK.json, its own file, and its configuration's file."""
    spec = load_json("BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        sys.exit(f"run.py: no workload {name!r} in BENCHMARK.json "
                 f"(have: {', '.join(sorted(entries))})")
    entry = entries[name]
    cell = load_json("benchmark", "workloads", name + ".json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            sys.exit(f"run.py: {name}: {key!r} is {cell[key]!r} in the "
                     f"cell's file and {entry[key]!r} in BENCHMARK.json")
    config_entry = next(c for c in spec["configs"]
                        if c["name"] == entry["config"])
    return spec, cell, load_json(config_entry["file"])


def metrics_of(spec, group, cell_name):
    return [m for m in spec[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def counters(hvd):
    """The program's own counters, read from outside."""
    return {"fusion": hvd.fusion_stats(),
            "dispatch": hvd.dispatch_cache_stats(),
            "gspmd": hvd.gspmd_cache_stats()}


def peak_memory_bytes(devices):
    """Peak HBM on the fullest chip, or ``None`` where the backend keeps
    no statistics (the CPU). On the TPU the allocator reports live buffers
    (``peak_bytes_in_use``: arguments, results, resident batches) apart
    from what loaded programs reserve for their temporaries
    (``peak_bytes_reserved``); a step needs both at once, and their sum is
    what the compiler's ``memory_analysis()`` predicts for the step."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    return max(s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
               for s in stats)


def median_ms(seconds):
    return statistics.median(seconds) * 1e3


def windows_for(steps, window_steps):
    """Whole windows that hold at least ``steps`` steps."""
    return -(-steps // window_steps)


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------

def replicas_identical(hvd, params):
    """Whether every chip holds the same bits of every parameter: two
    checksums (wrapping sum and xor of the 32-bit words) of each leaf,
    computed on each chip from its own replica, compared on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    def sums(tree):
        rows = []
        for leaf in jax.tree.leaves(tree):
            words = jax.lax.bitcast_convert_type(
                leaf.astype(jnp.float32), jnp.uint32).ravel()
            rows.append(jnp.stack([
                jnp.sum(words, dtype=jnp.uint32),
                jax.lax.reduce(words, np.uint32(0), jax.lax.bitwise_xor,
                               (0,))]))
        return jnp.stack(rows)[None]

    per_chip = np.asarray(jax.jit(jax.shard_map(
        sums, mesh=hvd.mesh(), in_specs=P(), out_specs=P(hvd.axis_name()),
        check_vma=False))(params))
    return bool((per_chip == per_chip[:1]).all())


def check_reference(env, mm, params, aux, first_loss, first_batch):
    """(a) loss and gradients of the repo's model on a seeded sample
    against the plain float32 reference, at the parameters the window
    left; (d) the loss the job's first step reported against the
    reference's loss on the same global batch at the seed's initial
    parameters. Returns the measured errors and their limits."""
    import jax
    import jax.numpy as jnp

    config, cell = env.config, env.cell
    model = mm.make_model(config)
    sample = jax.jit(lambda key: mm.make_batch(
        config, key, cell["sample"], cell["seq_len"]))(env.sample_key)

    # parameters, state and sample are arguments, not constants of the
    # programs: the same programs serve every seed from the compile cache
    system = jax.jit(jax.value_and_grad(
        lambda p, a, *rows: mm.loss(model, p, a, rows)[0]))
    reference = jax.jit(jax.value_and_grad(
        lambda p, a, *rows: mm.reference_loss(config, p, a, rows)[0]))

    @jax.jit
    def grad_error(got, want):
        diff = sum(jnp.sum(jnp.square(g.astype(jnp.float32) - w))
                   for g, w in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)))
        norm = sum(jnp.sum(jnp.square(w)) for w in jax.tree.leaves(want))
        return jnp.sqrt(diff / norm), jnp.sqrt(norm)

    loss_sys, grads_sys = system(params, aux, *sample)
    loss_ref, grads_ref = reference(params, aux, *sample)
    grad_rel, grad_norm = grad_error(grads_sys, grads_ref)
    del grads_sys, grads_ref, params
    loss_sys, loss_ref = float(loss_sys), float(loss_ref)

    fresh, fresh_aux = jax.jit(lambda key: mm.init(model, config, key))(
        env.init_key)
    first_ref = float(jax.jit(lambda p, a, *batch: mm.reference_loss(
        config, p, a, batch)[0])(fresh, fresh_aux, *first_batch))
    out = {
        "grad_rel_err": float(grad_rel), "grad_rel_tol": mm.GRAD_REL_TOL,
        "grad_norm": float(grad_norm),
        "loss_rel_err": abs(loss_sys - loss_ref) / abs(loss_ref),
        "first_loss": first_loss, "first_loss_reference": first_ref,
        "first_loss_rel_err": abs(first_loss - first_ref) / abs(first_ref),
        "loss_rel_tol": mm.LOSS_REL_TOL,
    }
    out["ok"] = bool(out["grad_rel_err"] < mm.GRAD_REL_TOL
                     and out["grad_norm"] > 0
                     and out["loss_rel_err"] < mm.LOSS_REL_TOL
                     and out["first_loss_rel_err"] < mm.LOSS_REL_TOL)
    return out


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    spec, cell, config = load_cell(args.workload)

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"platform: {platform}  device_kind: {kind}  devices: "
        f"{len(devices)}  cell: {cell['name']}  seed: {args.seed}")
    rehearsal = platform != "tpu"
    if rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"run.py: needs a TPU, but jax found platform "
                 f"{platform!r} ({kind}). Under an explicit "
                 "JAX_PLATFORMS=cpu it rehearses at tiny sizes.")
    if len(devices) != cell["chips"]:
        sys.exit(f"run.py: {cell['name']} is a {cell['chips']}-chip cell "
                 f"and jax found {len(devices)} device(s)")
    if rehearsal:
        cell, config = {**cell, **cell["tiny"]}, {**config, **config["tiny"]}
        log("REHEARSAL on the CPU at the files' tiny sizes: no result")
    from benchmark import peaks, timing

    peak = None if rehearsal else peaks.peak_flops(kind)
    knobs = sorted(k for k in os.environ if k.startswith("HVD_"))
    if knobs:
        print(f"run.py: warning: HVD_* variables are set ({knobs}); a "
              "cell runs at the program's defaults", file=sys.stderr)

    # ---- set-up ----------------------------------------------------------
    spans, meter = timing.Spans(), timing.CompileMeter()
    with spans("init"):
        import horovod_tpu as hvd
        from horovod_tpu.utils.compile_cache import place_compile_cache

        cache_dir = place_compile_cache()
        # No size cap. One GPT-2 step's executable and its init program
        # together pass the 192 MiB that the chip machine's
        # JAX_COMPILATION_CACHE_MAX_SIZE allows: under it every run
        # evicted what the next one needed, and each run compiled anew.
        jax.config.update("jax_compilation_cache_max_size", -1)
        hvd.init()
    if hvd.size() != cell["chips"]:
        sys.exit(f"run.py: hvd.size()={hvd.size()} on a "
                 f"{cell['chips']}-chip cell")
    from jax.sharding import NamedSharding, PartitionSpec as P

    mm = importlib.import_module(f"benchmark.models.{config['model']}")
    job_module = importlib.import_module(f"benchmark.jobs.{cell['job']}")
    init_key, data_key, sample_key = jax.random.split(
        jax.random.PRNGKey(args.seed), 3)
    global_batch = cell["batch_per_chip"] * cell["chips"]
    make_batch = jax.jit(
        lambda key: mm.make_batch(config, key, global_batch,
                                  cell["seq_len"]),
        out_shardings=NamedSharding(hvd.mesh(), P(hvd.axis_name())))
    with spans("make_batches"):
        ring = [make_batch(key)
                for key in jax.random.split(data_key, cell["ring"])]
    env = types.SimpleNamespace(
        hvd=hvd, model=mm, config=config, cell=cell, spans=spans,
        init_key=init_key, sample_key=sample_key, batch_shapes=ring[0])
    with spans("build_job"):
        job = job_module.build(env)
    window_steps = cell["window_steps"]
    windows = timing.Windows(job, ring, window_steps, spans, meter)
    with spans("warmup"):
        first_loss, clean = None, 0
        need = windows_for(WARMUP_CLEAN_STEPS, window_steps)
        while clean < need:
            if windows.steps >= WARMUP_STEPS_MAX and not clean:
                sys.exit("run.py: still compiling after "
                         f"{windows.steps} warm-up steps")
            clean = 0 if windows.run_one() else clean + 1
            if first_loss is None:
                first_loss = float(windows.first_loss)
    retraces_warm = job.retraces()
    windows.reset()
    setup = {"setup_s": process_age_s(), "compile_s": meter.seconds,
             "programs": meter.programs, "cache_hits": meter.cache_hits}
    log(f"set-up {setup['setup_s']:.1f} s (compile or cache fetch "
        f"{setup['compile_s']:.1f} s, {setup['programs']} programs, "
        f"{setup['cache_hits']} from the cache at {cache_dir}); spans: "
        + json.dumps({k: round(v, 2) for k, v in spans.seconds.items()}))

    # ---- the window ------------------------------------------------------
    trace = None
    before = counters(hvd)
    if args.trace:
        plain_windows = windows_for(PLAIN_STEPS, window_steps)
        trace_windows = windows_for(TRACE_STEPS, window_steps)
        for _ in range(plain_windows):
            windows.run_one()
        plain_step_ms = median_ms(windows.step_seconds)
        plain_enqueue = list(windows.enqueue_seconds)
        trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # no per-call Python events
        options.host_tracer_level = 1     # TraceAnnotations only
        traced_from = len(windows.step_seconds)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(trace_windows):
                windows.run_one()
        finally:
            jax.profiler.stop_trace()
        traced_step_ms = median_ms(windows.step_seconds[traced_from:])
        log(f"step_ms: {plain_step_ms:.3f} with the profiler off "
            f"({plain_windows} windows), {traced_step_ms:.3f} under it "
            f"({trace_windows} windows of {window_steps} steps)")
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if files:
            from benchmark import trace_reduce

            trace = trace_reduce.load(files[0])
            if not trace.chips:      # off the TPU: no device plane
                trace = None
    else:
        elapsed = windows.run_for(args.seconds)
        log(f"window: {len(windows.step_seconds)} windows of "
            f"{window_steps} steps in {elapsed:.2f} s")
    after = counters(hvd)
    attempted = len(windows.step_seconds) * window_steps
    memory_peak = peak_memory_bytes(devices)
    last_loss = windows.losses[-1]

    # ---- correct ---------------------------------------------------------
    params, aux, _ = windows.state
    identical = (replicas_identical(hvd, params)
                 if cell["chips"] > 1 else True)
    windows.state = job.state = None      # the optimizer's state can go
    with spans("check_reference"):
        reference = check_reference(env, mm, params, aux, first_loss,
                                    ring[0])
    del params, aux
    checks = {
        "reference": reference,
        "loss_first": first_loss, "loss_last": last_loss,
        "loss_fell": last_loss < first_loss,   # False for a NaN
        "compiled_in_window": windows.compiled_inside,
        "replicas_identical": identical,
    }
    correct = bool(reference["ok"] and checks["loss_fell"]
                   and windows.compiled_inside == 0 and identical
                   and windows.failed == 0)
    log(f"checks ({spans.seconds['check_reference']:.1f} s for the "
        "reference): " + json.dumps(checks))

    # ---- metrics ---------------------------------------------------------
    if args.trace:
        run = types.SimpleNamespace(
            cell=cell, config=config, trace=trace, setup=setup,
            spans=dict(spans.seconds), before=before, after=after,
            steps=attempted,
            traced_steps=trace_windows * window_steps,
            enqueue_seconds=plain_enqueue, peak_flops=peak,
            retraces=(None if retraces_warm is None
                      else job.retraces() - retraces_warm),
            model_flops=mm.model_flops(config, global_batch,
                                       cell["seq_len"]))
        values = {}
        for metric in metrics_of(spec, "per_layer", cell["name"]):
            reader = importlib.import_module(
                f"benchmark.layers.{metric['name']}")
            value = reader.read(run)
            if value is not None:
                values[metric["name"]] = {"value": float(value),
                                          "unit": metric["unit"]}
        extra = {"step_ms_profiler_off": plain_step_ms,
                 "step_ms_profiler_on": traced_step_ms}
    else:
        step_s = statistics.median(windows.step_seconds)
        flops = mm.model_flops(config, global_batch, cell["seq_len"])
        measured = {
            "step_ms": step_s * 1e3,
            "mfu": (100.0 * flops / step_s / (cell["chips"] * peak)
                    if peak else None),
            "peak_hbm_gib": (memory_peak / 2 ** 30
                             if memory_peak is not None else None),
            "setup_s": setup["setup_s"],
        }
        values = {m["name"]: {"value": measured[m["name"]],
                              "unit": m["unit"]}
                  for m in metrics_of(spec, "end_to_end", cell["name"])
                  if measured.get(m["name"]) is not None}
        extra = {"windows": len(windows.step_seconds),
                 "window_steps": window_steps,
                 "step_ms_min": min(windows.step_seconds) * 1e3,
                 "step_ms_max": max(windows.step_seconds) * 1e3,
                 "model_flops_per_step": flops,
                 # every window's reading, in order: what the median hides
                 "step_ms_windows": [round(s * 1e3, 3)
                                     for s in windows.step_seconds]}
    result = {
        "correct": correct, "attempted": attempted,
        "failed": windows.failed, "metrics": values,
        "device": {"platform": platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
        "workload": cell["name"], "seed": args.seed, "setup": setup,
        "extra": extra, "checks": checks,
    }
    if trace is not None:
        from benchmark import trace_reduce

        busy_s, window_s = trace_reduce.busy_and_window(trace)
        result["device"].update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace),
            "idle_gaps": trace_reduce.idle_gaps(trace)}
    hvd.shutdown()
    if rehearsal:
        log("rehearsal line (NOT a result): " + json.dumps(result))
        log(f"REHEARSAL {'OK' if correct else 'FAILED'} on platform "
            f"{platform}: every stage ran at tiny size. Not a chip result.")
        return REHEARSAL_EXIT if correct else 1
    if args.trace and "busy_s" not in result["device"]:
        sys.exit("run.py: the traced run found no device operations in "
                 "the profiler's trace")
    if not correct:
        log("NOT CORRECT: see the checks above")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
