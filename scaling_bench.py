#!/usr/bin/env python
"""Scaling-efficiency harness on a virtual device mesh — the rebuild's
analog of the reference's published scaling-efficiency metric
(``/root/reference/docs/benchmarks.rst:13-43``: 90% scaling efficiency for
ResNet-101/Inception-V3 at 512 GPUs, measured with
``examples/tensorflow2/tensorflow2_synthetic_benchmark.py``).

Real multi-chip hardware isn't available in this environment, so this
measures what *can* be measured honestly on N virtual CPU devices that
share one physical machine:

  **Fixed total work, sharded over n devices.** All virtual devices share
  the same cores, so weak scaling (n x work on the same silicon) is
  meaningless here. Instead the total batch is held constant and sharded
  over n ∈ {1,2,4,8}; ideal step time is flat, and any rise is the
  framework's collective/partitioning overhead — the quantity scaling
  efficiency actually stresses. efficiency(n) = t(1) / t(n).

Runs the framework's real collective layer (DistributedOptimizer ->
grouped_allreduce -> traced lax.psum) in ``flat`` mode and the two-level
ICI/DCN schedule (``ops/hierarchical.py``) in ``hier`` mode.

Writes SCALING_r{N}.json and prints one JSON line per configuration.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _make_model(name: str):
    """The reference's three published scaling models, in small-input
    form (docs/benchmarks.rst:13-14 runs ResNet-101/Inception-V3/VGG-16;
    the virtual-CPU harness uses the light family members so the signal
    is collective overhead, not CPU conv time). Returns
    (model, input_side, description) — the description is derived here so
    the recorded artifact metadata cannot drift from what ran."""
    import jax.numpy as jnp

    from horovod_tpu import models as M

    if name == "resnet":
        return (M.ResNet18(num_classes=10, dtype=jnp.float32,
                           axis_name=None), 32, "ResNet18/32x32")
    if name == "vgg":
        width = 256
        return (M.VGG16(num_classes=10, dtype=jnp.float32,
                        classifier_width=width), 32,
                f"VGG16(classifier_width={width})/32x32")
    if name == "inception":
        return M.InceptionV3(num_classes=10, dtype=jnp.float32), 75, \
            "InceptionV3/75x75"
    raise ValueError(f"unknown model {name!r}")




def _build_mode(mode: str, n: int, model, side, total_batch):
    """Compile one mode's train step and build its device state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import hierarchical

    devs = jax.devices()[:n]
    rng = jax.random.PRNGKey(0)
    images = np.random.default_rng(0).standard_normal(
        (total_batch, side, side, 3), dtype=np.float32)
    labels = np.random.default_rng(1).integers(0, 10, size=(total_batch,))

    variables = model.init(rng, jnp.zeros((1, side, side, 3), jnp.float32),
                           train=False)
    params = variables["params"]
    # VGG has no batch norm; ResNet/Inception do. Eval-mode apply keeps
    # the loss generic (the harness measures collective overhead, not
    # batch-norm bookkeeping) — stats ride along untouched.
    batch_stats = dict(variables.get("batch_stats", {}))
    inner = optax.sgd(0.05, momentum=0.9)

    def loss_fn(p, batch_stats, images, labels):
        vars_in = {"params": p}
        if batch_stats:
            vars_in["batch_stats"] = batch_stats
        logits = model.apply(vars_in, images, train=False)
        one_hot = jax.nn.one_hot(labels, 10)
        loss = -jnp.mean(jnp.sum(one_hot * jax.nn.log_softmax(logits), -1))
        return loss, batch_stats

    if mode == "flat":
        mesh = Mesh(np.array(devs), ("data",))
        tx = hvd.DistributedOptimizer(inner, axis_name="data")
        data_spec = P("data")
    elif mode == "nosync":
        # control: identical sharded execution with NO gradient sync —
        # isolates the shared-core partitioned-execution overhead from the
        # framework's collective overhead
        mesh = Mesh(np.array(devs), ("data",))
        tx = inner
        data_spec = P("data")
    elif mode == "hier":
        ici = 2 if n % 2 == 0 else 1
        mesh = Mesh(np.array(devs).reshape(n // ici, ici), ("dcn", "ici"))
        tx = inner  # grads reduced explicitly below via the two-level schedule
        data_spec = P(("dcn", "ici"))
    else:
        raise ValueError(mode)

    opt_state = tx.init(params)

    def train_step(params, batch_stats, opt_state, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, images, labels)
        if mode == "hier":
            grads = jax.tree.map(
                lambda g: hierarchical.hierarchical_allreduce_traced(
                    g, "ici", "dcn", op=hvd.ReduceOp.AVERAGE), grads)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, new_opt, loss

    # no donation: the state is reused across interleaved timing rounds
    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), data_spec, data_spec),
        out_specs=(P(), P(), P(), P()), check_vma=False))

    images = jax.device_put(images, NamedSharding(mesh, data_spec))
    labels = jax.device_put(labels, NamedSharding(mesh, data_spec))
    rep = NamedSharding(mesh, P())
    state = dict(params=jax.device_put(params, rep),
                 batch_stats=jax.device_put(batch_stats, rep),
                 opt_state=jax.device_put(opt_state, rep))
    return {"step": step, "state": state, "images": images, "labels": labels}


def child_main(n: int, modes: list, total_batch: int, iters: int,
               model_name: str = "resnet", rounds: int | None = None) -> None:
    """Measure ALL modes interleaved in ONE process: round-robin timing
    windows so machine-load drift hits every mode equally, then paired
    per-round ratios. Round-4's separate-child design produced impossible
    ratios (flat faster than its own nosync control at n=4, 0.848 at n=8)
    from exactly that drift."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    if rounds is None:
        # variance lives at ROUND granularity (drift between adjacent
        # windows), so reps buy precision as rounds, not window length
        rounds = int(os.environ.get("SCALING_ROUNDS", "5"))
    hvd.init()  # collective layer resolves the (global) process set
    model, side, _desc = _make_model(model_name)
    built = {m: _build_mode(m, n, model, side, total_batch) for m in modes}

    def run_window(b, k):
        s = b["state"]
        t0 = time.perf_counter()
        for _ in range(k):
            # block per step: XLA-CPU's in-process rendezvous deadlocks on
            # unbounded async pile-up of collective programs
            p, bs, o, loss = b["step"](s["params"], s["batch_stats"],
                                       s["opt_state"], b["images"],
                                       b["labels"])
            jax.block_until_ready(loss)
            s.update(params=p, batch_stats=bs, opt_state=o)
        return (time.perf_counter() - t0) / k

    for b in built.values():  # compile + settle caches
        run_window(b, 2)

    per_mode = {m: [] for m in modes}
    for _ in range(rounds):
        for m in modes:  # round-robin: drift lands on every mode equally
            per_mode[m].append(run_window(built[m], max(1, iters // rounds)))

    out = {}
    for m in modes:
        arr = np.asarray(per_mode[m])
        out[m] = {"n": n, "mode": m,
                  "step_ms": round(float(np.median(arr)) * 1e3, 3),
                  "step_ms_std": round(float(arr.std()) * 1e3, 3),
                  "rounds": rounds}
    if "nosync" in modes:
        base = np.asarray(per_mode["nosync"])
        for m in modes:
            if m == "nosync":
                continue
            ratios = base / np.asarray(per_mode[m])  # paired per round
            out[m]["collective_efficiency"] = round(
                float(np.median(ratios)), 3)
            out[m]["collective_efficiency_std"] = round(
                float(ratios.std()), 3)
    for m in modes:
        print(json.dumps(out[m]))


def _build_composed_lane(lane: str, total_batch: int, seq: int):
    """Compile one composed-parallelism lane's TransformerLM train step.

    Lanes (all world=8, float32 so the parity gates below are tight):

    * ``dp``        — pure data parallel: 1-D ``data`` mesh, flat sync.
    * ``dpsp``      — DP x SP: ``dcn=2 x ici_dp=2 x seq=2`` composed mesh,
                      ulysses attention over ``seq``, engine sync two-level
                      over the data axes only (``DistributedOptimizer``
                      ``mesh_spec`` path). Ulysses reshards without changing
                      FLOPs, so the ideal step-time ratio vs ``dp`` is 1.0.
    * ``dpep``      — DP x EP: ``dcn=2 x ici_dp=2 x expert=2``, MoE FFN over
                      ``expert``, two-level data-axis sync.
    * ``dpep_flat`` — the ``dpep`` control: identical model and mesh shape
                      but ONE flat ``data`` axis (``data=4 x expert=2``) and
                      flat sync — isolates the two-level schedule's cost on
                      a composed mesh (ideal ratio 1.0).

    The model-axis gradient reduction (pmean over seq/expert) belongs to
    the SCHEDULE and runs before ``tx.update``; the engine's collective
    then reduces only over the data axes — the composed-mesh contract
    (docs/mesh.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import parallel
    from horovod_tpu.models import TransformerConfig, TransformerLM

    base = dict(vocab_size=128, num_layers=2, num_heads=4, d_model=128,
                d_ff=256, max_seq_len=seq, dtype=jnp.float32)
    moe = lane in ("dpep", "dpep_flat")
    if moe:
        cfg = TransformerConfig(**base, moe_experts=2, moe_axis="expert")
    elif lane == "dpsp":
        cfg = TransformerConfig(**base, attn_mode="ulysses", seq_axis="seq")
    else:
        cfg = TransformerConfig(**base)
    model = TransformerLM(cfg)

    if lane == "dp":
        mesh = parallel.mesh_for_axes(("data",), (8,))
        tx = hvd.DistributedOptimizer(optax.sgd(0.05, momentum=0.9),
                                      axis_name="data")
        tok_spec, model_axis = P("data"), None
    elif lane == "dpsp":
        lay = parallel.layout((("seq", 2),), ici_size=4)
        mesh = parallel.composed_mesh(lay)
        tx = hvd.DistributedOptimizer(optax.sgd(0.05, momentum=0.9),
                                      mesh_spec=lay)
        tok_spec, model_axis = lay.batch_spec("seq"), "seq"
    elif lane == "dpep":
        lay = parallel.layout((("expert", 2),), ici_size=4)
        mesh = parallel.composed_mesh(lay)
        tx = hvd.DistributedOptimizer(optax.sgd(0.05, momentum=0.9),
                                      mesh_spec=lay)
        tok_spec, model_axis = lay.batch_spec(), "expert"
    elif lane == "dpep_flat":
        mesh = parallel.mesh_for_axes(("data", "expert"), (4, 2))
        tx = hvd.DistributedOptimizer(optax.sgd(0.05, momentum=0.9),
                                      axis_name="data")
        tok_spec, model_axis = P("data"), "expert"
    else:
        raise ValueError(lane)
    all_axes = mesh.axis_names

    def loss_fn(p, tokens, targets):
        if moe:
            logits, inter = model.apply({"params": p}, tokens,
                                        mutable=["intermediates"])
            aux = sum(jnp.sum(a) for a in
                      jax.tree_util.tree_leaves(inter["intermediates"]))
        else:
            logits, aux = model.apply({"params": p}, tokens), 0.0
        ce = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), targets[..., None], -1))
        return ce + 0.01 * aux

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        if model_axis is not None:
            # schedule-owned reduction over the model axis; the engine's
            # sync below never touches it
            grads = jax.tree.map(lambda g: lax.pmean(g, model_axis), grads)
        updates, new_opt = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_opt,
                lax.pmean(loss, all_axes))

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), tok_spec, tok_spec),
        out_specs=(P(), P(), P()), check_vma=False))

    rng = np.random.default_rng(7)
    tokens = rng.integers(0, cfg.vocab_size, size=(total_batch, seq))
    targets = np.roll(tokens, -1, axis=1)  # precomputed globally: local
    # roll would wrap within a sequence SHARD in the dpsp lane
    rep = NamedSharding(mesh, P())
    # identical init params per model family: the dense lanes share one
    # tree and the MoE lanes share another, so trajectories are comparable
    init_model = TransformerLM(dataclasses_replace_full(cfg))
    params = init_model.init(jax.random.PRNGKey(0),
                             jnp.asarray(tokens[:1]))["params"]
    opt_state = tx.init(params)
    return {
        "step": step, "mesh": mesh, "moe": moe,
        "state": dict(params=jax.device_put(params, rep),
                      opt_state=jax.device_put(opt_state, rep)),
        "tokens": jax.device_put(tokens, NamedSharding(mesh, tok_spec)),
        "targets": jax.device_put(targets, NamedSharding(mesh, tok_spec)),
    }


def dataclasses_replace_full(cfg):
    """Init-time twin of a lane config: same params, ``full`` attention
    (attn_mode never changes the param tree, and init never routes, so
    every lane of one model family inits to IDENTICAL trees)."""
    import dataclasses
    return dataclasses.replace(cfg, attn_mode="full")


def _composed_sync_bit_parity(composed_lane: str):
    """Bit-exactness gate for the composed gradient sync, in the
    exactness domain: integer-valued float32 contributions (every
    reduction order sums them exactly, and AVERAGE's divisors here are
    powers of two, which are exact in binary fp) — so the composed
    schedule (pmean over the model axis + two-level over the data axes)
    must match the pure-DP flat pmean over one 8-wide axis BIT FOR BIT.
    Any double-count, wrong-axis reduction, scatter-padding or scale bug
    still breaks equality in this domain; generic-float data would add
    ~1-ulp association noise and hide nothing extra. Shapes include an
    odd length (33) so the two-level path's pad-to-ici_dp logic is
    exercised."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import parallel
    from horovod_tpu.ops.reduce_ops import ReduceOp

    model_axis = {"dpsp": "seq", "dpep": "expert"}[composed_lane]
    lay = parallel.layout(((model_axis, 2),), ici_size=4)
    mesh_c = parallel.composed_mesh(lay)
    mesh_f = parallel.mesh_for_axes(("data",), (8,))
    shapes = [(33,), (4, 5), (16,)]

    def contrib(r):
        return [(jnp.arange(np.prod(s), dtype=jnp.float32).reshape(s)
                 * 3.0 + r * 7.0) for s in shapes]

    def composed_fn():
        d = lax.axis_index("dcn")
        i = lax.axis_index("ici_dp")
        m = lax.axis_index(model_axis)
        r = ((d * lay.ici_dp) + i) * 2 + m  # global rank, dcn-major
        xs = [lax.pmean(x, model_axis) for x in contrib(r)]
        return parallel.sync_gradients(xs, lay, op=ReduceOp.AVERAGE)

    def flat_fn():
        r = lax.axis_index("data")
        return [lax.pmean(x, "data") for x in contrib(r)]

    got = jax.jit(jax.shard_map(composed_fn, mesh=mesh_c, in_specs=(),
                                out_specs=P(), check_vma=False))()
    want = jax.jit(jax.shard_map(flat_fn, mesh=mesh_f, in_specs=(),
                                 out_specs=P(), check_vma=False))()
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(got, want))


def _grouped_two_level_parity():
    """world=8 eager ``grouped_allreduce``: two-level (ICI-then-DCN,
    ``HVD_HIERARCHICAL_ALLREDUCE=1``, island=4) vs flat — bitwise on
    integer-valued float32 (exactness domain, see above) plus the max
    relative error on gaussian data (association noise only, ~1 ulp)."""
    import numpy as np

    import horovod_tpu as hvd

    rng = np.random.default_rng(3)
    n = hvd.size()
    ints = [np.float32((rng.integers(-500, 500, size=s)))
            for s in [(33,), (8, 3)]]
    gauss = [np.float32(rng.standard_normal(s)) for s in [(33,), (8, 3)]]

    def run(two_level):
        os.environ["HVD_HIERARCHICAL_ALLREDUCE"] = "1" if two_level else "0"
        os.environ["HVD_HIERARCHICAL_ICI_SIZE"] = "4"
        per_int = [hvd.per_rank([x * 1.0 + r for r in range(n)])
                   for x in ints]
        per_g = [hvd.per_rank([x * (1.0 + 0.01 * r) for r in range(n)])
                 for x in gauss]
        oi = hvd.grouped_allreduce(per_int, op=hvd.ReduceOp.SUM)
        og = hvd.grouped_allreduce(per_g, op=hvd.ReduceOp.SUM)
        return ([np.asarray(t) for t in oi], [np.asarray(t) for t in og])

    try:
        flat_i, flat_g = run(two_level=False)
        two_i, two_g = run(two_level=True)
    finally:
        os.environ.pop("HVD_HIERARCHICAL_ALLREDUCE", None)
        os.environ.pop("HVD_HIERARCHICAL_ICI_SIZE", None)
    bitwise = all(np.array_equal(a, b) for a, b in zip(flat_i, two_i))
    rel = max(float(np.max(np.abs(a - b) / (np.abs(a) + 1e-6)))
              for a, b in zip(flat_g, two_g))
    return bitwise, rel


def composed_child_main(total_batch: int, iters: int, seq: int,
                        rounds: int | None = None) -> None:
    """All four composed lanes in ONE process: numerics gates first, then
    interleaved round-robin timing with paired per-round ratios (same
    drift rationale as :func:`child_main`)."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    if rounds is None:
        rounds = int(os.environ.get("SCALING_ROUNDS", "5"))
    hvd.init()
    lanes = ["dp", "dpsp", "dpep_flat", "dpep"]
    built = {m: _build_composed_lane(m, total_batch, seq) for m in lanes}

    # -- numerics gates (before timing mutates the states) ---------------
    numerics = {
        "dpsp_sync_bitwise": _composed_sync_bit_parity("dpsp"),
        "dpep_sync_bitwise": _composed_sync_bit_parity("dpep"),
    }
    bitwise, rel = _grouped_two_level_parity()
    numerics["grouped_two_level_bitwise"] = bitwise
    numerics["grouped_two_level_gauss_max_rel"] = float(f"{rel:.3e}")

    def run_steps(b, k, record=None):
        s = b["state"]
        t0 = time.perf_counter()
        for _ in range(k):
            p, o, loss = b["step"](s["params"], s["opt_state"],
                                   b["tokens"], b["targets"])
            jax.block_until_ready(loss)
            s.update(params=p, opt_state=o)
            if record is not None:
                record.append(float(np.ravel(np.asarray(loss))[0]))
        return (time.perf_counter() - t0) / k

    # -- trajectory parity: identical inits, 4 recorded steps ------------
    traj = {m: [] for m in lanes}
    for m in lanes:
        run_steps(built[m], 4, record=traj[m])
    sp = np.asarray(traj["dpsp"])
    dp = np.asarray(traj["dp"])
    ep = np.asarray(traj["dpep"])
    epf = np.asarray(traj["dpep_flat"])
    numerics["dpsp_traj_max_rel"] = float(
        f"{np.max(np.abs(sp - dp) / np.abs(dp)):.3e}")
    # dp vs dpsp: same math, different schedule (ulysses reshard + token
    # grouping + sync association) — float32 keeps this at ulp scale
    numerics["dpsp_traj_ok"] = bool(np.allclose(sp, dp, rtol=1e-4,
                                                atol=1e-6))
    # dpep vs its flat control: identical compute, only the data-axis
    # sync schedule differs
    numerics["dpep_traj_max_rel"] = float(
        f"{np.max(np.abs(ep - epf) / np.abs(epf)):.3e}")
    numerics["dpep_traj_ok"] = bool(np.allclose(ep, epf, rtol=5e-5,
                                                atol=1e-7))
    numerics["row"] = "composed_numerics"
    print(json.dumps(numerics))

    # -- timing: round-robin windows, paired per-round ratios ------------
    for b in built.values():
        run_steps(b, 2)
    per = {m: [] for m in lanes}
    for _ in range(rounds):
        for m in lanes:
            per[m].append(run_steps(built[m], max(1, iters // rounds)))
    eff_sp = np.asarray(per["dp"]) / np.asarray(per["dpsp"])
    eff_ep = np.asarray(per["dpep_flat"]) / np.asarray(per["dpep"])
    for m in lanes:
        arr = np.asarray(per[m])
        row = {"row": "composed_lane", "lane": m,
               "step_ms": round(float(np.median(arr)) * 1e3, 3),
               "step_ms_std": round(float(arr.std()) * 1e3, 3),
               "rounds": rounds}
        if m == "dpsp":
            row["per_axis_efficiency"] = round(float(np.median(eff_sp)), 3)
            row["per_axis_efficiency_std"] = round(float(eff_sp.std()), 3)
        if m == "dpep":
            row["per_axis_efficiency"] = round(float(np.median(eff_ep)), 3)
            row["per_axis_efficiency_std"] = round(float(eff_ep.std()), 3)
        print(json.dumps(row))


def run_composed_child(total_batch: int, iters: int, seq: int) -> dict:
    """Fresh-process composed run; returns {lane rows..., numerics row}.
    The child is CPU-pinned (``JAX_PLATFORMS=cpu``, 8 virtual devices):
    it needs no chip, whatever this parent process holds."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    for k in list(env):
        if k.startswith(("HVD_", "HOROVOD_")):
            env.pop(k)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_composed-child",
         str(total_batch), str(iters), str(seq)],
        env=env, cwd=HERE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"composed child failed:\n{proc.stderr[-4000:]}")
    rows = {}
    for ln in proc.stdout.strip().splitlines():
        if not ln.startswith("{"):
            continue
        try:
            row = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if row.get("row") == "composed_numerics":
            rows["numerics"] = row
        elif row.get("row") == "composed_lane":
            rows[row["lane"]] = row
    missing = [k for k in ("numerics", "dp", "dpsp", "dpep", "dpep_flat")
               if k not in rows]
    if missing:
        raise RuntimeError(
            f"composed child produced no rows for {missing}; stdout "
            f"tail:\n{proc.stdout[-2000:]}")
    return rows


def composed_main(args) -> None:
    rows = run_composed_child(args.total_batch, args.iters, args.seq)
    num = rows["numerics"]
    eff_sp = rows["dpsp"]["per_axis_efficiency"]
    eff_ep = rows["dpep"]["per_axis_efficiency"]
    out = args.out or os.path.join(HERE, "SCALING_composed_r17.json")
    payload = {
        "harness": "composed-parallelism lanes (TransformerLM, float32, "
                   "world=8 virtual CPU devices) interleaved round-robin "
                   "in ONE child with paired per-round ratios",
        "lanes": {m: rows[m] for m in ("dp", "dpsp", "dpep_flat", "dpep")},
        "numerics": num,
        "metric": "per_axis_efficiency(dpsp) = median t(dp)/t(dpsp) — "
                  "ulysses reshards without changing FLOPs so ideal is "
                  "1.0; per_axis_efficiency(dpep) = median "
                  "t(dpep_flat)/t(dpep), the two-level schedule's cost on "
                  "the composed mesh, ideal 1.0. Bitwise gates run in the "
                  "exactness domain (integer-valued float32 + power-of-two "
                  "divisors: every correct reduction order is exact, so "
                  "composed-vs-flat must agree bit for bit; generic floats "
                  "would only add ~1-ulp association noise). Trajectory "
                  "parity is paired per-step loss agreement at float32.",
        "gates": {"per_axis_efficiency_floor": 0.80,
                  "bitwise": ["dpsp_sync_bitwise", "dpep_sync_bitwise",
                              "grouped_two_level_bitwise"],
                  "trajectory": ["dpsp_traj_ok", "dpep_traj_ok"]},
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps({
        "metric": "composed_dpsp_per_axis_efficiency", "value": eff_sp,
        "unit": "ratio", "dpep_per_axis_efficiency": eff_ep,
        "dpsp_sync_bitwise": num["dpsp_sync_bitwise"],
        "dpep_sync_bitwise": num["dpep_sync_bitwise"],
        "grouped_two_level_bitwise": num["grouped_two_level_bitwise"],
        "dpsp_traj_ok": num["dpsp_traj_ok"],
        "dpep_traj_ok": num["dpep_traj_ok"],
        "dpsp_traj_max_rel": num["dpsp_traj_max_rel"],
        "out": out}))


def run_child(n: int, modes: list, total_batch: int, iters: int,
              max_devices: int, model: str = "resnet") -> list:
    """One fresh-process run at n devices. The child is CPU-pinned
    (``JAX_PLATFORMS=cpu``, ``max_devices`` virtual devices): it needs no
    chip, whatever this parent process holds."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={max_devices}")
    for k in list(env):
        if k.startswith(("HVD_", "HOROVOD_")):
            env.pop(k)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_child",
         str(n), ",".join(modes), str(total_batch), str(iters), model],
        env=env, cwd=HERE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"scaling child n={n} modes={modes} failed:\n{proc.stderr[-4000:]}")
    rows = {}
    for ln in proc.stdout.strip().splitlines():
        if not ln.startswith("{"):
            continue
        try:
            row = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if row.get("mode") in modes and row.get("n") == n:
            rows[row["mode"]] = row  # keyed: stray '{' lines can't alias
    missing = [m for m in modes if m not in rows]
    if missing:
        raise RuntimeError(
            f"scaling child n={n} produced no result rows for {missing}; "
            f"stdout tail:\n{proc.stdout[-2000:]}")
    return [rows[m] for m in modes]


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--_child", nargs=5,
                        metavar=("N", "MODE", "BATCH", "ITERS", "MODEL"))
    parser.add_argument("--_composed-child", nargs=3, dest="_composed_child",
                        metavar=("BATCH", "ITERS", "SEQ"))
    parser.add_argument("--composed", action="store_true",
                        help="composed-parallelism mode: TransformerLM "
                             "DP x SP and DP x EP lanes on one hierarchical "
                             "world=8 mesh vs the pure-DP lane (ISSUE 17)")
    parser.add_argument("--seq", type=int, default=64,
                        help="sequence length for --composed")
    parser.add_argument("--devices", default="1,2,4,8")
    parser.add_argument("--total-batch", type=int, default=64)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--model", default="resnet",
                        choices=("resnet", "vgg", "inception"),
                        help="the reference's three published scaling "
                             "models (docs/benchmarks.rst:13-14)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    if args._child:
        n, modes, batch, iters, model = args._child
        child_main(int(n), modes.split(","), int(batch), int(iters), model)
        return
    if args._composed_child:
        batch, iters, seq = args._composed_child
        composed_child_main(int(batch), int(iters), int(seq))
        return
    if args.composed:
        composed_main(args)
        return

    device_counts = [int(x) for x in args.devices.split(",")]
    max_devices = max(device_counts)
    results = []
    base_ms = None
    for n in device_counts:
        modes = ["flat"] if n == 1 else ["nosync", "flat", "hier"]
        for r in run_child(n, modes, args.total_batch, args.iters,
                           max_devices, args.model):
            if base_ms is None:
                base_ms = r["step_ms"]
            r["efficiency"] = round(base_ms / r["step_ms"], 3)
            if r["mode"] == "hier":
                r["note"] = ("single-host virtual mesh: both levels share "
                             "one core, so this row measures the two-level "
                             "schedule's pure overhead — there is no real "
                             "ICI/DCN asymmetry for it to exploit here")
            results.append(r)
            print(json.dumps(r))

    out = args.out or os.path.join(HERE, f"SCALING_{args.model}_r5.json")
    payload = {
        "harness": "fixed-total-work strong scaling on virtual CPU devices; "
                   "all modes of one n interleaved round-robin in ONE child "
                   "process with paired per-round ratios (machine-load "
                   "drift hits every mode equally)",
        "model": _make_model(args.model)[2],
        "total_batch": args.total_batch,
        "metric": "efficiency = t(1)/t(n), ideal 1.0; collective_efficiency "
                  "= median over paired rounds of t(nosync)/t(mode), "
                  "isolating the framework's collective overhead from the "
                  "shared-core partitioned-execution emulation overhead "
                  "(all virtual devices share one physical core here); "
                  "*_std columns are across-round standard deviations",
        "reference_target": ">=0.90 collective_efficiency, mirroring "
                            "docs/benchmarks.rst:13-14",
        "variance_note": (
            "reproducibility: on this shared-core emulation the paired "
            "ratios vary run-to-run by up to ~0.1 at n=8 depending on "
            "background load (same-day re-runs measured 0.87-0.95 for "
            "identical configs); run on an otherwise-idle machine. On "
            "real TPU ICI the gradient allreduce overlaps with backward "
            "compute, removing the overhead this proxy metric pays "
            "entirely."),
        "results": results,
    }
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps({"metric": "collective_efficiency_8dev_flat",
                      "value": next((r.get("collective_efficiency")
                                     for r in results
                                     if r["n"] == max_devices and r["mode"] == "flat"),
                                    None),
                      "unit": "ratio", "out": out}))


if __name__ == "__main__":
    main()
