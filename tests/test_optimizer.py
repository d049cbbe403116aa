"""DistributedOptimizer / gradient-tape tests (reference analog:
``test/parallel/test_torch.py`` optimizer tests and
``test_tensorflow2_keras.py`` aggregation tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu import metrics
from horovod_tpu import optim as hvd_optim

N = 8


def test_distributed_optimizer_traced_sgd(hvd):
    """SPMD data-parallel step: per-rank grads differ; after the wrapped
    update every rank applies the *mean* gradient."""
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    params = {"w": jnp.zeros((3,))}
    state = jax.eval_shape(lambda: None)  # placeholder
    x = jnp.arange(1.0, 9.0).reshape(N, 1)

    def step(xi):
        grads = {"w": jnp.full((3,), xi[0])}
        st = tx.init(params)
        updates, _ = tx.update(grads, st, params)
        return optax.apply_updates(params, updates)["w"]

    out = jax.jit(jax.shard_map(
        step, mesh=hvd.mesh(), in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))(x)
    got = np.asarray(out).reshape(N, 3)
    np.testing.assert_allclose(got, np.full((N, 3), -4.5), rtol=1e-6)


def test_value_and_grad_traced(hvd):
    def loss(w, xi):
        return jnp.sum(w * xi)

    vg = hvd.value_and_grad(loss, op=hvd.Average)
    x = jnp.arange(1.0, 9.0).reshape(N, 1)

    def step(xi):
        _, g = vg(jnp.ones((1,)), xi)
        return g

    out = jax.jit(jax.shard_map(
        step, mesh=hvd.mesh(), in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(N, 4.5))


def test_grad_wrapper(hvd):
    g = hvd.grad(lambda w: jnp.sum(w ** 2))
    out = g(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 2.0))


def test_compression_fp16(hvd):
    tensor = jnp.full((4,), 3.0)
    c, ctx = hvd.Compression.fp16.compress(tensor)
    assert c.dtype == jnp.float16
    d = hvd.Compression.fp16.decompress(c, ctx)
    assert d.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(d), 3.0)


def test_compression_bf16_in_tape(hvd):
    vg = hvd.value_and_grad(lambda w: jnp.sum(w * 2), compression=hvd.Compression.bf16)
    _, g = vg(jnp.ones((4,)))
    assert g.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(g), 2.0)


def test_backward_passes_per_step(hvd):
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), backward_passes_per_step=2)
    params = {"w": jnp.zeros((2,))}
    st = tx.init(params)
    g1 = {"w": jnp.full((2,), 1.0)}
    g2 = {"w": jnp.full((2,), 3.0)}
    u1, st = tx.update(g1, st, params)
    # first of 2 passes: no update applied yet
    np.testing.assert_allclose(np.asarray(u1["w"]), 0.0)
    u2, st = tx.update(g2, st, params)
    # second pass: mean grad (1+3)/2 = 2 -> update -2
    np.testing.assert_allclose(np.asarray(u2["w"]), -2.0)


def test_broadcast_parameters(hvd):
    params = {"a": jnp.ones((3,)), "b": {"c": jnp.zeros((2, 2))}}
    out = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["a"]), 1.0)
    np.testing.assert_allclose(np.asarray(out["b"]["c"]), 0.0)


def test_broadcast_optimizer_state(hvd):
    tx = optax.adam(1e-3)
    st = tx.init({"w": jnp.ones((3,))})
    out = hvd.broadcast_optimizer_state(st, root_rank=0)
    chex_leaves = jax.tree.leaves(out)
    assert len(chex_leaves) == len(jax.tree.leaves(st))


def test_broadcast_object(hvd):
    obj = {"epoch": 3, "name": "resnet"}
    assert hvd.broadcast_object(obj, 0) == obj


def test_allgather_object(hvd):
    assert hvd.allgather_object({"r": 1}) == [{"r": 1}]


def test_adasum_eager_two_orthogonal(hvd):
    """Orthogonal gradients should (nearly) add; parallel identical
    gradients should average to the same vector (scale invariance) —
    numerics per adasum.h:248-342."""
    ps = hvd.add_process_set([0, 1])
    a = jnp.array([1.0, 0.0])
    b = jnp.array([0.0, 1.0])
    out = hvd.allreduce(hvd.per_rank([a, b], ps), op=hvd.Adasum, process_set=ps)
    np.testing.assert_allclose(np.asarray(out), [1.0, 1.0], atol=1e-6)
    hvd.remove_process_set(ps)


def test_adasum_identical_gradients(hvd):
    """n identical gradients g: pairwise combine gives (1-1/2)g+(1-1/2)g = g,
    so the result stays g at every level."""
    g = jnp.array([2.0, -1.0, 0.5])
    out = hvd.allreduce(hvd.per_rank([g] * 8), op=hvd.Adasum)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=1e-6)


def test_grad_has_aux(hvd):
    def loss(w):
        return jnp.sum(w ** 2), {"n": w.shape[0]}

    grads, aux = hvd.grad(loss, has_aux=True)(jnp.ones((3,)))
    np.testing.assert_allclose(np.asarray(grads), 2.0)
    assert aux == {"n": 3}


# ------------------------------------------------------------------------
# the eager update's second stage: the wrapped optimizer's ``update`` as
# ONE compiled program (ISSUE 26; optim/__init__.py ``_sync_then_update``)
# ------------------------------------------------------------------------

OPTIMIZERS = {
    "sgd_momentum": lambda: optax.sgd(0.1, momentum=0.9),
    "adam": lambda: optax.adam(1e-3),
    "scheduled_inject_hyperparams": lambda: optax.inject_hyperparams(
        optax.sgd)(learning_rate=optax.linear_schedule(0.1, 0.01, 10),
                   momentum=0.9),
}


def inner_update_events():
    """``{event: count}`` of ``hvd_optimizer_inner_updates_total``."""
    return {dict(labels)["event"]: int(value) for labels, value
            in metrics.OPTIMIZER_INNER_UPDATES.series().items()}


def events_during(fn):
    before = inner_update_events()
    fn()
    return {event: count - before.get(event, 0)
            for event, count in inner_update_events().items()
            if count != before.get(event, 0)}


def span_calls(name):
    series = metrics.SPAN_SECONDS.series()
    return sum(hist.count for labels, hist in series.items()
               if dict(labels)["span"] == name)


def on_the_mesh(hvd, tx, shapes, seed=0):
    """Parameters and optimizer state as the five-line contract leaves
    them: broadcast, so replicated over the mesh."""
    rng = np.random.default_rng(seed)
    params = hvd.broadcast_parameters(
        {name: jnp.asarray(rng.standard_normal(shape), jnp.float32)
         for name, shape in shapes.items()}, 0)
    return params, hvd.broadcast_optimizer_state(tx.init(params), 0)


def per_rank_grads(hvd, params, rng):
    """One gradient per rank, in sixteenths: their mean over 8 ranks is
    exact in float32, whatever order the sum takes. Returns the bundles
    and the mean."""
    rows = jax.tree.map(
        lambda p: (rng.integers(-64, 64, (N,) + p.shape) / 16.0)
        .astype(np.float32), params)
    return (jax.tree.map(hvd.per_rank, rows),
            jax.tree.map(lambda r: jnp.asarray(r.mean(0)), rows))


SHAPES = {"conv": (5, 3), "bias": (700,)}


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_eager_update_is_the_wrapped_update_on_the_mean(hvd, name):
    """Three eager steps over per-rank gradients. Each equals the wrapped
    optimizer run by hand on the averaged gradients from the same state:
    the same tree structure and dtypes; **bitwise** the values of
    ``jax.jit(opt.update)``, because it is that program; and within a few
    roundings of the operation-by-operation values, because XLA fuses a
    multiply into an add (``0.9 * m + g`` rounds once, not twice: half an
    ulp of the larger term, which is many ulps of a sum near zero, so
    the tolerance is absolute, scaled by the leaf's largest value)."""
    opt = OPTIMIZERS[name]()
    tx = hvd.DistributedOptimizer(opt)
    params, state = on_the_mesh(hvd, tx, SHAPES)
    by_hand_jit = jax.jit(opt.update)
    rng = np.random.default_rng(1)
    for _ in range(3):
        grads, mean = per_rank_grads(hvd, params, rng)
        stepwise = opt.update(mean, state[1], params)
        jitted = by_hand_jit(mean, state[1], params)
        updates, state = tx.update(grads, state, params)
        got = (updates, state[1])
        assert jax.tree.structure(got) == jax.tree.structure(stepwise)
        for ours, same, close in zip(*map(jax.tree.leaves,
                                          (got, jitted, stepwise))):
            assert ours.dtype == close.dtype and ours.shape == close.shape
            np.testing.assert_array_equal(np.asarray(ours),
                                          np.asarray(same))
            scale = float(np.max(np.abs(np.asarray(close)))) or 1.0
            np.testing.assert_allclose(
                np.asarray(ours), np.asarray(close), rtol=0,
                atol=8 * np.finfo(np.float32).eps * scale)
        params = optax.apply_updates(params, updates)


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_steady_eager_steps_trace_the_update_once(hvd, name):
    """N steps of a fixed tree: N compiled updates, one trace, no
    fallback; a leaf of another shape traces once more, and only once."""
    tx = hvd.DistributedOptimizer(OPTIMIZERS[name]())
    rng = np.random.default_rng(2)

    def steps(shapes, n):
        params, state = on_the_mesh(hvd, tx, shapes)
        for _ in range(n):
            grads, _ = per_rank_grads(hvd, params, rng)
            updates, state = tx.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        jax.block_until_ready(params)

    assert events_during(lambda: steps(SHAPES, 4)) == {
        "compiled": 4, "trace": 1}
    assert events_during(lambda: steps(SHAPES, 2)) == {"compiled": 2}
    assert events_during(lambda: steps({**SHAPES, "bias": (701,)}, 3)) == {
        "compiled": 3, "trace": 1}


@pytest.mark.parametrize("bundled", [True, False],
                         ids=["per_rank", "plain_arrays"])
def test_eager_update_leaves_the_callers_arrays_readable(hvd, bundled):
    """Only the synced gradients, which the caller never saw, are
    donated: the old state, the parameters and the caller's own
    gradients read the same after the call as before it."""
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    params, state = on_the_mesh(hvd, tx, SHAPES)
    grads, mean = per_rank_grads(hvd, params, np.random.default_rng(3))
    if not bundled:
        grads = mean
    held = jax.tree.leaves((state, params)) + [
        g.array if bundled else g for g in jax.tree.leaves(grads)]
    before = [np.array(leaf) for leaf in held]
    for _ in range(2):      # the tracing call and a cached one
        updates, new_state = tx.update(grads, state, params)
        jax.block_until_ready((updates, new_state))
    for leaf, was in zip(held, before):
        assert not leaf.is_deleted()
        np.testing.assert_array_equal(np.asarray(leaf), was)
    assert int(new_state[1][0].count) == int(state[1][0].count) + 1


def test_gradients_the_sync_hands_through_are_not_donated(hvd, monkeypatch):
    """Were the sync stage ever to return the caller's own array (a
    one-rank set needs no exchange), the update must not take its
    buffer."""
    grads = {"w": jnp.arange(6.0)}
    bundle = {"w": hvd.per_rank(np.ones((N, 6), np.float32))}
    fresh = {"w": jnp.ones((6,))}
    assert hvd_optim._sync_made_them(fresh, grads)
    assert hvd_optim._sync_made_them(fresh, bundle)
    assert not hvd_optim._sync_made_them(grads, grads)
    assert not hvd_optim._sync_made_them({"w": bundle["w"].array}, bundle)

    monkeypatch.setattr(hvd_optim, "_allreduce_tree",
                        lambda tree, **_: tree)
    tx = hvd.DistributedOptimizer(optax.sgd(0.5))
    params = {"w": jnp.zeros((6,))}
    updates, _ = tx.update(grads, tx.init(params), params)
    np.testing.assert_array_equal(np.asarray(updates["w"]),
                                  -0.5 * np.arange(6.0))
    assert not grads["w"].is_deleted()
    np.testing.assert_array_equal(np.asarray(grads["w"]), np.arange(6.0))


def line_search_like():
    """An optimizer with optax's extra-args contract: ``value_fn`` is a
    callable (no jit argument), ``value`` an array."""
    def update(updates, state, params=None, *, value=None, value_fn=None,
               **_):
        scale = 1.0 if value_fn is None else value_fn(params)
        shift = 0.0 if value is None else value
        return jax.tree.map(lambda u: -scale * u + shift, updates), state

    return optax.GradientTransformationExtraArgs(
        lambda params: optax.EmptyState(), update)


@pytest.mark.parametrize("extra, events, expect", [
    ({"value_fn": lambda params: 2.0}, {"direct_extra_args": 1}, -2.0),
    ({"value": jnp.float32(0.25)}, {"compiled": 1, "trace": 1}, -0.75),
    ({}, {"compiled": 1, "trace": 1}, -1.0),
], ids=["callable_goes_direct", "array_is_compiled", "none_is_compiled"])
def test_extra_args_decide_the_path(hvd, extra, events, expect):
    """A leaf jit cannot take as an argument sends the update down the
    operation-by-operation path, counted by its reason; the span opens
    either way."""
    tx = hvd.DistributedOptimizer(line_search_like())
    params = {"w": jnp.zeros((4,))}
    state = tx.init(params)
    grads = {"w": jnp.ones((4,))}
    spans_before = span_calls("optimizer.inner_update")
    out = {}

    def update():
        out["updates"], _ = tx.update(grads, state, params, **extra)

    assert events_during(update) == events
    assert span_calls("optimizer.inner_update") == spans_before + 1
    np.testing.assert_allclose(np.asarray(out["updates"]["w"]), expect)


def test_plain_transformation_drops_extra_args_as_chain_does(hvd):
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    params = {"w": jnp.zeros((2,))}
    updates, _ = tx.update({"w": jnp.ones((2,))}, tx.init(params), params,
                           value_fn=lambda p: 0.0)
    np.testing.assert_allclose(np.asarray(updates["w"]), -1.0)


def _under_jit(hvd, tx, params, state):
    # plain jit: GSPMD passthrough, the gradients are already global
    step = jax.jit(lambda g, s, p: tx.update(g, s, p))
    for _ in range(2):
        jax.block_until_ready(step(params, state, params))


def _under_shard_map(hvd, tx, params, state):
    step = jax.jit(jax.shard_map(
        lambda g, s, p: tx.update(g, s, p), mesh=hvd.mesh(),
        in_specs=P(), out_specs=P(), check_vma=False))
    for _ in range(2):
        jax.block_until_ready(step(params, state, params))


def _eager_calls(hvd, tx, params, state):
    # MultiSteps runs the wrapped update inside lax.cond: under a trace
    for _ in range(4):
        _, state = tx.update(params, state, params)
    jax.block_until_ready(state)


@pytest.mark.parametrize("run, kwargs", [
    (_under_jit, {}), (_under_shard_map, {}),
    (_eager_calls, {"backward_passes_per_step": 2}),
], ids=["outer_jit", "shard_map", "backward_passes_per_step_2"])
def test_update_under_a_trace_is_part_of_the_callers_program(
        hvd, run, kwargs):
    """No nested program, no counter, no optimizer span: the wrapped
    ``update`` is called directly, as before."""
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9), **kwargs)
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = tx.init(params)
    spans_before = (span_calls("optimizer.inner_update"),
                    span_calls("optimizer.sync"))
    assert events_during(lambda: run(hvd, tx, params, state)) == {}
    assert (span_calls("optimizer.inner_update"),
            span_calls("optimizer.sync")) == spans_before


def test_state_tuple_is_chains(hvd):
    """``(sync state, wrapped optimizer's state)``, as ``optax.chain``
    made it before the two stages became one function."""
    opt = optax.adam(1e-3)
    params = {"w": jnp.ones((3,))}
    ours = hvd.DistributedOptimizer(opt).init(params)
    chain = optax.chain(hvd_optim.allreduce_gradients_transform(),
                        opt).init(params)
    assert jax.tree.structure(ours) == jax.tree.structure(chain)
    assert isinstance(ours, tuple) and ours[0] == optax.EmptyState()


# --- the traced sync's two emissions (ops/traced_exchange.py) --------------

def _seen(**changed):
    """What the predicate observes for a 16 MB float32 leaf of a four-chip
    data-parallel TPU job; ``changed`` overrides one observation."""
    from horovod_tpu.ops.reduce_ops import ReduceOp
    seen = dict(platform="tpu", axis_size=4, ring=(0, 1, 3, 2),
                op=ReduceOp.AVERAGE, groups=None, mesh_spec=None,
                compressed=False, fused_threshold=0, dtype=jnp.float32,
                nbytes=16 << 20, shape=(1024, 4096))
    seen.update(changed)
    return seen


def test_permute_rounds_selected_for_a_large_leaf_on_a_tpu_host():
    from horovod_tpu.ops import traced_exchange
    from horovod_tpu.ops.reduce_ops import ReduceOp
    assert traced_exchange.permute_rounds_selected(**_seen())
    assert traced_exchange.permute_rounds_selected(**_seen(op=ReduceOp.SUM))
    assert traced_exchange.permute_rounds_selected(
        **_seen(axis_size=8, ring=tuple(range(8))))
    assert traced_exchange.permute_rounds_selected(
        **_seen(dtype=jnp.bfloat16, nbytes=8 << 20))


@pytest.mark.parametrize("changed", [
    dict(axis_size=1, ring=None),                   # one chip
    dict(axis_size=0, ring=None),                   # the axis is not bound
    dict(axis_size=16, ring=tuple(range(16))),      # past one host's chips
    dict(ring=None),                                # no ring of neighbours
    dict(groups=[[0, 1], [2, 3]]),                  # a process set
    dict(mesh_spec=("dcn", "ici_dp")),              # the composed mesh
    dict(dtype=jnp.int32),                          # an integer leaf
    dict(nbytes=4096, shape=(1024,)),               # a layer-norm scale
    dict(shape=(50257, 1024), nbytes=50257 * 4096), # lanes would be split
    dict(shape=(50257, 7), nbytes=50257 * 7 * 4),   # k divides nothing
    dict(compressed=True),                          # a bf16 wire
    dict(platform="cpu"),
    dict(platform="gpu"),
    dict(fused_threshold=1 << 20),                  # the knob keeps its path
    dict(op="min"),
], ids=["size1", "unbound", "size16", "no-ring", "groups", "mesh_spec",
        "integer", "under-floor", "lanes", "indivisible", "compressed", "cpu", "gpu",
        "traced-fusion-knob", "min"])
def test_permute_rounds_not_selected(hvd, changed):
    from horovod_tpu.ops import traced_exchange
    if changed.get("op") == "min":
        changed = dict(op=hvd.Min)
    assert not traced_exchange.permute_rounds_selected(**_seen(**changed))


def _traced_step_text_and_result(hvd, tx, grads_of):
    params = {"big": jnp.zeros((128, 48)), "odd": jnp.zeros((7, 9)),
              "scale": jnp.zeros((16,))}

    def step(xi):
        updates, _ = tx.update(grads_of(params, xi[0]), tx.init(params),
                               params)
        return jax.tree.map(lambda u: u[None], updates)

    fn = jax.jit(jax.shard_map(
        step, mesh=hvd.mesh(), in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))
    x = jnp.arange(1.0, 9.0).reshape(N, 1)
    return fn.lower(x).as_text(), jax.tree.map(np.asarray, fn(x))


def _grads(params, xi):
    return jax.tree.map(
        lambda p: xi * (1.0 + jnp.arange(p.size, dtype=jnp.float32)
                        .reshape(p.shape)), params)


def _force_rounds(monkeypatch):
    """Steer the predicate as a TPU host's 8 chips would: the platform
    and the devices' coordinates are what a CPU lacks."""
    from horovod_tpu.ops import traced_exchange
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(traced_exchange, "neighbour_ring",
                        lambda devices: (0, 1, 2, 3, 7, 6, 5, 4))
    monkeypatch.setattr(traced_exchange, "MIN_LEAF_BYTES", 1024)


def test_traced_sync_emits_permute_rounds_for_the_large_leaves(
        hvd, monkeypatch):
    """Predicate forced true: the step lowers to ``collective_permute`` for
    the large divisible leaf and keeps ``all_reduce`` only for the small
    and the indivisible ones; the update is the one ``psum`` gives, the
    same bits on every member; the counter saw both paths."""
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    plain, want = _traced_step_text_and_result(hvd, tx, _grads)
    _force_rounds(monkeypatch)
    before = metrics.snapshot()
    text, got = _traced_step_text_and_result(hvd, tx, _grads)
    moved = metrics.delta(metrics.snapshot(), before)
    assert text.count("collective_permute") == 2 * 2 * (N - 1)
    # AVERAGE on the psum path is two all_reduces a leaf (the sum and the
    # axis's size); "odd" and "scale" keep theirs, "big" has none left
    assert plain.count("all_reduce") == 6 and "permute" not in plain
    assert text.count("all_reduce") == 4
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-6)
        assert all((got[name][i] == got[name][0]).all() for i in range(N))
    counted = {dict(k[1])["path"]: v for k, v in moved.items()
               if k[0] == "hvd_traced_exchange_total"}
    assert counted == {"permute_rounds": 1.0, "psum": 2.0}
    last = {s["labels"]["what"]: s["value"] for s in hvd.metrics_dump()[
        "hvd_traced_exchange_last_trace"]["series"]}
    assert last == {"buckets": 1.0, "rounds": 2.0 * (N - 1)}


@pytest.mark.parametrize("how", ["optimizer", "tape"])
def test_traced_sync_off_a_tpu_lowers_as_before(hvd, monkeypatch, how):
    """Predicate false (a CPU): the lowered step is, text for text, what
    one ``lax.psum`` a leaf gives, through the optimizer and the
    gradient tape alike."""
    def text():
        if how == "optimizer":
            tx = hvd.DistributedOptimizer(optax.sgd(1.0))
            return _traced_step_text_and_result(hvd, tx, _grads)[0]
        vg = hvd.value_and_grad(lambda w, xi: jnp.sum(w * xi))
        fn = jax.jit(jax.shard_map(
            lambda xi: vg(jnp.ones((64, 48)), xi)[1][None],
            mesh=hvd.mesh(), in_specs=P("hvd"), out_specs=P("hvd"),
            check_vma=False))
        return fn.lower(jnp.arange(1.0, 9.0).reshape(N, 1)).as_text()

    now = text()
    assert "collective_permute" not in now and "all_reduce" in now
    # the parent's emission: every traced leaf straight through ``sync``
    monkeypatch.setattr(
        hvd_optim, "_traced_sync",
        lambda leaves, sync, **observed: sync(leaves))
    assert text() == now


def test_traced_sync_buckets_follow_the_backward_pass(hvd, monkeypatch):
    """The large leaves go in the order the trace produced them, not the
    tree's: ``block_10`` sorts before ``block_2`` and is produced after
    it. Three leaves over the bucket's size make three chained buckets;
    the small leaf stays outside them."""
    from horovod_tpu.ops import traced_exchange
    _force_rounds(monkeypatch)
    monkeypatch.setattr(traced_exchange, "BUCKET_BYTES", 128 * 48 * 4)
    seen = []
    rounds = traced_exchange.allreduce_rounds
    monkeypatch.setattr(
        traced_exchange, "allreduce_rounds",
        lambda leaves, *a, **kw: (seen.append(
            [float(leaf.shape[-1]) for leaf in leaves])
            or rounds(leaves, *a, **kw)))
    tx = hvd.DistributedOptimizer(optax.sgd(1.0))
    names = ["block_10", "block_2", "block_9", "scale"]

    def step(xi):
        params = {"block_10": jnp.ones((128, 50)), "block_2": jnp.ones(
            (128, 48)), "block_9": jnp.ones((128, 49)),
            "scale": jnp.ones((16,))}

        def loss(p):            # forward 2, 9, 10: backward 10, 9, 2
            h = xi[0, 0] * jnp.sum(p["scale"])
            for name in ("block_2", "block_9", "block_10"):
                h = jnp.tanh(h + jnp.sum(p[name]))
            return h

        grads = jax.grad(loss)(params)
        updates, _ = tx.update(grads, tx.init(params), params)
        return jnp.stack([jnp.sum(updates[n]) for n in names])[None]

    fn = jax.jit(jax.shard_map(
        step, mesh=hvd.mesh(), in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))
    text = fn.lower(jnp.arange(1.0, 9.0).reshape(N, 1)).as_text()
    assert seen == [[50.0], [49.0], [48.0]]
    assert text.count("optimization_barrier") >= 2     # bucket behind bucket
    out = np.asarray(fn(jnp.arange(1.0, 9.0).reshape(N, 1)))
    assert np.isfinite(out).all() and (out == out[0]).all()


def test_production_order_falls_back_to_the_tree_backwards():
    from horovod_tpu.ops import traced_exchange
    assert traced_exchange.production_order(
        [jnp.ones(3), jnp.ones(2), jnp.ones(1)]) == [2, 1, 0]
