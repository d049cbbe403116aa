"""The program's span seam (ISSUE 24; docs/timeline.md "Program spans"):
every host-side layer goes through ``timeline.span`` — a fixed-name
``hvd:<layer>.<stage>`` ``TraceAnnotation`` that fires at program
defaults (no ``HVD_TIMELINE``), with its duration in
``hvd_span_seconds{span}``. Read here the way the benchmark reads it:
from the profiler's host plane and from ``hvd.metrics_dump()``."""

import collections
import glob
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu
from horovod_tpu import metrics, timeline
from horovod_tpu.ops import fusion_cycle

Event = collections.namedtuple("Event", "name thread start end fields")

BUCKET_BYTES = 25_000   # 48 KB trees below sync as two buckets


@pytest.fixture(autouse=True, scope="module")
def _two_quiet_buckets():
    # flushes come from the explicit "bucket" trigger only, never from
    # the cycle timer: the span counts below are exact
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_CYCLE_TIME", "2000")
        mp.setenv("HVD_PENDING_CYCLE_TIME", "2000")
        mp.setenv("HVD_BUCKET_BYTES", str(BUCKET_BYTES))
        fusion_cycle.reset()
        assert not timeline.timeline_active()
        yield
        fusion_cycle.reset()


def profiled(directory, fn):
    """``fn()`` under a ``jax.profiler`` session (annotations only, no
    Python tracer); the ``hvd:`` events of the host plane, by start."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(directory), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            events.extend(
                Event(e.name, thread, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
                for e in line.events
                if e.name.startswith(timeline.SPAN_PREFIX))
    return sorted(events, key=lambda e: e.start)


def span_counts():
    """``{span: calls}`` from the registry."""
    return {dict(labels)["span"]: hist.count
            for labels, hist in metrics.SPAN_SECONDS.series().items()}


def calls_during(fn):
    before = span_counts()
    fn()
    return {name: count - before.get(name, 0)
            for name, count in span_counts().items()
            if count != before.get(name, 0)}


def tree(leaves, floats):
    return {f"w{i:03d}": jnp.full((floats,), float(i + 1), jnp.float32)
            for i in range(leaves)}


def eager_update(params):
    """One warm eager ``DistributedOptimizer.update`` as a thunk."""
    tx = horovod_tpu.DistributedOptimizer(optax.sgd(0.1))
    state = tx.init(params)
    grads = jax.tree.map(jnp.ones_like, params)

    def update():
        jax.block_until_ready(tx.update(grads, state, params))

    update()     # plans built, programs compiled
    return update


# ------------------------------------------------- the eager step's spans

@pytest.fixture(scope="module")
def eager_events(tmp_path_factory):
    return profiled(tmp_path_factory.mktemp("eager"),
                    eager_update(tree(12, 1000)))


@pytest.mark.parametrize("name, calls", [
    ("hvd:optimizer.sync", 1), ("hvd:optimizer.inner_update", 1),
    ("hvd:collective.submit", 2), ("hvd:cycle.flush", 2),
    ("hvd:cycle.execute", 2), ("hvd:cycle.wait_result", 2),
    ("hvd:plan.lookup", 2), ("hvd:plan.run", 2)])
def test_eager_update_writes_span_at_defaults(eager_events, name, calls):
    """No timeline is active: the annotations reach the profiler's host
    plane anyway, once per update, bucket or flush — never per leaf."""
    assert sum(e.name == name for e in eager_events) == calls


def test_sync_then_inner_update_disjoint_on_one_thread(eager_events):
    sync, = [e for e in eager_events if e.name == "hvd:optimizer.sync"]
    inner, = [e for e in eager_events
              if e.name == "hvd:optimizer.inner_update"]
    assert sync.thread == inner.thread
    assert sync.start < sync.end <= inner.start < inner.end
    # what the sync caused on its own thread nests inside it
    for e in eager_events:
        if e.name in ("hvd:collective.submit", "hvd:cycle.flush",
                      "hvd:cycle.wait_result"):
            assert e.thread == sync.thread
            assert sync.start <= e.start and e.end <= sync.end


def test_executor_span_carries_the_submitters_flush_number(eager_events):
    flushes = [e for e in eager_events if e.name == "hvd:cycle.flush"]
    executes = [e for e in eager_events if e.name == "hvd:cycle.execute"]
    assert {e.fields["trigger"] for e in flushes} == {"bucket"}
    assert (sorted(e.fields["flush"] for e in flushes)
            == sorted(e.fields["flush"] for e in executes))
    assert len({e.fields["flush"] for e in flushes}) == 2
    # HVD_MAX_INFLIGHT_FLUSHES defaults to 2: the executor is another
    # thread, and each batch executes after its drain began
    by_number = {e.fields["flush"]: e for e in flushes}
    for e in executes:
        assert e.thread != by_number[e.fields["flush"]].thread
        assert e.start >= by_number[e.fields["flush"]].start
        assert e.fields["entries"] == 1 and e.fields["bytes"] > 0
    # the plan ran inside the executor's span, on its thread
    for run in (e for e in eager_events if e.name == "hvd:plan.run"):
        assert any(x.thread == run.thread and x.start <= run.start
                   and run.end <= x.end for x in executes)
        assert run.fields["tensor"] == "grouped_allreduce"
        assert run.fields["variant"] == "fused"


def test_span_calls_do_not_grow_with_the_leaf_count(hvd):
    """12 leaves and 120 leaves, both 48 KB in two buckets: the same
    spans fire the same number of times a step."""
    few = calls_during(eager_update(tree(12, 1000)))
    many = calls_during(eager_update(tree(120, 100)))
    assert few == many
    assert few["optimizer.sync"] == few["optimizer.inner_update"] == 1
    assert few["cycle.flush"] == few["cycle.execute"] == 2
    assert max(few.values()) <= 2


# ------------------------------------------------------ set-up's totals

@pytest.mark.parametrize("name", ["init", "broadcast_parameters"])
def test_setup_totals_are_in_metrics_dump(hvd, name):
    """Set-up runs before any profiler session: its spans are read from
    the registry."""
    def series():
        found = [s for s in hvd.metrics_dump()["hvd_span_seconds"]["series"]
                 if s["labels"]["span"] == name]
        return found[0] if found else {"count": 0, "sum": 0.0}

    before = series()
    hvd.init()      # the whole call is the span, an ignored repeat too
    params = hvd.broadcast_parameters(tree(6, 10), 0)
    after = series()
    assert jax.tree.leaves(params)[0].shape == (10,)
    assert after["count"] == before["count"] + 1
    assert after["sum"] > before["sum"]


def test_broadcast_waits_in_one_span_not_one_per_leaf(hvd):
    calls = calls_during(lambda: hvd.broadcast_parameters(tree(40, 10), 0))
    assert calls["broadcast_parameters"] == 1
    assert calls["collective.submit"] == 1
    assert calls["cycle.wait_result"] == 1
    assert max(calls.values()) <= 2, calls


def test_metrics_off_keeps_annotations_and_no_totals(hvd, tmp_path):
    update = eager_update(tree(12, 1000))
    metrics.set_enabled(False)
    try:
        before = span_counts()
        events = profiled(tmp_path, update)
        assert span_counts() == before
    finally:
        metrics.set_enabled(None)
    names = {e.name for e in events}
    assert {"hvd:optimizer.sync", "hvd:optimizer.inner_update",
            "hvd:cycle.flush", "hvd:cycle.execute",
            "hvd:plan.run"} <= names


# ------------------------------------------------------------ traced mode

@pytest.mark.parametrize("name", ["optimizer.sync",
                                  "optimizer.inner_update"])
def test_traced_update_enters_no_optimizer_span(hvd, name):
    """Under jit(shard_map) the update is traced once and compiled: a
    span there would time tracing, so neither stage enters one."""
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    params = tree(4, 16)
    state = tx.init(params)
    step = jax.jit(jax.shard_map(
        lambda g, s, p: tx.update(g, s, p), mesh=hvd.mesh(),
        in_specs=P(), out_specs=P(), check_vma=False))

    def run():
        for _ in range(2):      # the tracing call and a replay
            jax.block_until_ready(step(params, state, params))

    assert name not in calls_during(run)


# ------------------------------------------------------------ cached_step

def test_cached_step_spans(hvd, tmp_path):
    cached = hvd.cached_step(lambda x, y: (x * 2.0 + y, y + 1.0))
    x, y = jnp.ones((8, 4)), jnp.ones((8, 4))
    first = calls_during(lambda: jax.block_until_ready(cached(x, y)))
    assert first["cached_step.lookup"] == 1
    assert first["cached_step.build"] == 1
    assert first["cached_step.run"] == 1

    events = profiled(tmp_path,
                      lambda: jax.block_until_ready(cached(x, y)))
    replay = [e.name for e in events if e.name.startswith("hvd:cached_step")]
    assert replay == ["hvd:cached_step.lookup", "hvd:cached_step.run"]
    lookup, run = [e for e in events if e.name.startswith("hvd:cached_step")]
    assert lookup.end <= run.start
    # the plan cache's own lookup nests in the cached step's
    inner, = [e for e in events if e.name == "hvd:plan.lookup"]
    assert lookup.start <= inner.start and inner.end <= lookup.end
    assert span_counts()["cached_step.build"] >= 1
    assert "cached_step.build" not in calls_during(
        lambda: jax.block_until_ready(cached(x, y)))


# ------------------------------------------------------------- the catalog

def test_every_span_is_declared_once_and_documented():
    """docs/timeline.md's table names every declared span, and no other."""
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "docs",
                           "timeline.md")) as f:
        documented = set(re.findall(r"^\| `hvd:([a-z_.]+)` \|", f.read(),
                                    re.MULTILINE))
    assert documented == set(timeline.spans())
    with pytest.raises(ValueError):
        timeline.span("init")


def test_chrome_timeline_keeps_its_activities(hvd, tmp_path):
    """With ``HVD_TIMELINE`` on, only the spans that always had a Chrome
    activity write to the file: the new ones stay on the profiler's
    clock."""
    import json

    update = eager_update(tree(12, 1000))
    path = tmp_path / "timeline.json"
    hvd.start_timeline(str(path))
    try:
        update()
    finally:
        hvd.stop_timeline()
    text = path.read_text().strip().rstrip(",\n ")
    names = {e["name"] for e in json.loads(
        text if text.endswith("]") else text + "]")}
    assert "GROUPED_ALLREDUCE" in names
    chrome = {s.activity for s in timeline.spans().values() if s.activity}
    allowed = chrome | {"PIPELINE_FUSE", "PIPELINE_DISPATCH",
                        "PIPELINE_SPLIT"}
    for name in names:
        assert (name in allowed or name.startswith(
            ("PLAN_", "QUEUE_ENQUEUE", "CYCLE_FLUSH.", "INFLIGHT_DEPTH.",
             "process_name", "thread_name"))), name
