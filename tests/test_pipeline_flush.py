"""Pipelined flush executor + large-tensor chunk pipelining (ISSUE 3
tentpole): flush triggers only drain queues and hand batches to a single
FIFO dispatch thread with HVD_MAX_INFLIGHT_FLUSHES slots; fused wire
buffers past HVD_PIPELINE_THRESHOLD dispatch as HVD_PIPELINE_CHUNKS chunk
programs; HVD_MAX_INFLIGHT_FLUSHES=1 restores the synchronous PR-2
behavior; composition and per-signature FIFO result order stay
deterministic under producer threads and timer fire; abort() mid-pipeline
never deadlocks."""

import threading
import time
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import dispatch_cache, fusion_cycle
from horovod_tpu.ops.collectives import _chunk_layout, _pipeline_key
from horovod_tpu.utils import envs

N = 8
LONG_CYCLE_MS = "2000"


@pytest.fixture(autouse=True)
def _fresh_scheduler(monkeypatch):
    monkeypatch.setenv("HVD_CYCLE_TIME", LONG_CYCLE_MS)
    monkeypatch.setenv("HVD_PENDING_CYCLE_TIME", LONG_CYCLE_MS)
    fusion_cycle.reset()
    yield
    fusion_cycle.reset()


def _vals(shape=(8,), dtype=jnp.float32, mult=1.0):
    return [jnp.full(shape, (i + 1) * mult, dtype) for i in range(N)]


def _sum_expected(shape=(8,), mult=1.0):
    return np.full(shape, 36.0 * mult)


# ------------------------------------------------------------- executor mode

def test_pipelined_executor_runs_flushes_off_thread(hvd, monkeypatch):
    """Default (2 slots): a threshold trigger returns before the flush
    executes; the executor thread delivers, and the pipeline stats see
    the batches."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", "100")
    handles = [hvd.allreduce_async(hvd.per_rank(_vals(mult=i + 1)),
                                   op=hvd.Sum) for i in range(4)]
    for h in handles:
        assert h._entry.event.wait(10.0)
    for i, h in enumerate(handles):
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   _sum_expected(mult=i + 1))
    st = hvd.fusion_stats()
    assert st["pipeline"]["enabled"] is True
    assert st["pipeline"]["executed"] >= 1
    assert st["pipeline"]["submitted"] == st["pipeline"]["executed"]
    assert st["pipeline"]["queue_depth"] == 0


def test_inflight_one_is_synchronous_pr2_behavior(hvd, monkeypatch):
    """HVD_MAX_INFLIGHT_FLUSHES=1: flush triggers execute inline on the
    triggering thread (the PR-2 path), the executor never engages, and
    chunking is disabled."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "1")
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", "100")
    assert not envs.pipeline_enabled()
    assert _pipeline_key() is None
    handles = [hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
               for _ in range(4)]
    # the threshold trigger ran the flush synchronously before returning
    assert all(h._entry.done for h in handles)
    st = hvd.fusion_stats()
    assert st["pipeline"]["enabled"] is False
    assert st["pipeline"]["executed"] == 0
    for h in handles:
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   _sum_expected())


def test_flush_all_quiesces_executor(hvd, monkeypatch):
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    hs = [hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
          for _ in range(3)]
    hvd.barrier()  # flush_all("barrier") + quiesce
    assert all(h._entry.done for h in hs)
    st = hvd.fusion_stats()
    assert st["pipeline"]["queue_depth"] == 0
    assert st["pending_tensors"] == 0


def test_fusion_flush_api(hvd):
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    hvd.fusion_flush()
    assert h._entry.done
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())


def test_determinism_history_with_executor_on(hvd, monkeypatch):
    """Identical call streams on two schedulers produce identical flush
    compositions with the executor on (acceptance criterion): the
    composition record is written at DRAIN time on the trigger thread,
    so executor timing can never reorder it."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    histories = []
    for _ in range(2):
        fusion_cycle.reset()
        handles = [
            hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum,
                                name="d0"),
            hvd.broadcast_async(hvd.per_rank(_vals()), 0, name="d1"),
            hvd.allreduce_async(hvd.per_rank(_vals(mult=2.0)), op=hvd.Sum,
                                name="d2"),
        ]
        fusion_cycle.scheduler().flush_all("barrier")
        histories.append(list(fusion_cycle.scheduler().flush_history))
        for h in handles:
            hvd.synchronize(h)
    assert histories[0] == histories[1]
    comps = [(key[0], names) for (_t, key, names) in histories[0]]
    assert comps[0] == ("allreduce", ("d0", "d2"))
    assert ("broadcast", ("d1",)) in comps


# --------------------------------------------------------- chunk pipelining

def test_chunk_layout_shapes():
    f32 = jnp.dtype(jnp.float32)
    # one bucket of 1024 f32 = 4 KiB, threshold 1 KiB, 4 chunks
    metas = [(f32, [0], [(1024,)], [f32])]
    import os
    os.environ["HVD_PIPELINE_THRESHOLD"] = "1024"
    os.environ["HVD_PIPELINE_CHUNKS"] = "4"
    os.environ["HVD_MAX_INFLIGHT_FLUSHES"] = "2"
    try:
        layout = _chunk_layout(metas)
        assert layout == [(0, 0, 256), (0, 256, 512), (0, 512, 768),
                          (0, 768, 1024)]
        # non-divisible total: last chunk is the remainder
        metas2 = [(f32, [0, 1], [(500,), (510,)], [f32, f32])]
        layout2 = _chunk_layout(metas2)
        assert [b - a for (_bi, a, b) in layout2] == [253, 253, 253, 251]
        assert layout2[-1][2] == 1010
        # sub-threshold bucket stays one piece alongside a chunked one
        metas3 = [(f32, [0], [(16,)], [f32]), (f32, [1], [(1024,)], [f32])]
        layout3 = _chunk_layout(metas3)
        assert layout3[0] == (0, 0, 16) and len(layout3) == 5
        # everything sub-threshold -> no chunked plan at all
        assert _chunk_layout([(f32, [0], [(16,)], [f32])]) is None
        # executor off -> chunking off
        os.environ["HVD_MAX_INFLIGHT_FLUSHES"] = "1"
        assert _chunk_layout(metas) is None
    finally:
        for k in ("HVD_PIPELINE_THRESHOLD", "HVD_PIPELINE_CHUNKS",
                  "HVD_MAX_INFLIGHT_FLUSHES"):
            os.environ.pop(k, None)


def test_chunked_plan_numerics_match_unchunked(hvd, monkeypatch):
    """Chunked wire pipeline vs the monolithic wire program: identical
    results, sync and async, plan cache serving both variants under
    distinct keys."""
    elems = 64 * 1024  # 256 KiB/tensor
    tensors = [hvd.per_rank([jnp.full((elems,), float((r + 1) * (i + 1)),
                                      jnp.float32) for r in range(N)])
               for i in range(2)]
    ref = [np.asarray(o)
           for o in hvd.grouped_allreduce(tensors, op=hvd.Sum)]
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    monkeypatch.setenv("HVD_PIPELINE_THRESHOLD", str(128 * 1024))
    monkeypatch.setenv("HVD_PIPELINE_CHUNKS", "4")
    before = dispatch_cache.stats()["chunked_builds"]
    outs = hvd.grouped_allreduce(tensors, op=hvd.Sum)
    assert dispatch_cache.stats()["chunked_builds"] == before + 1
    for r, o in zip(ref, outs):
        np.testing.assert_allclose(r, np.asarray(o))
    # steady state: second call is a plan HIT on the chunked plan
    h0 = dispatch_cache.stats()["hits"]
    outs2 = hvd.grouped_allreduce(tensors, op=hvd.Sum)
    assert dispatch_cache.stats()["hits"] == h0 + 1
    for r, o in zip(ref, outs2):
        np.testing.assert_allclose(r, np.asarray(o))
    # and through the queue (async flush -> chunked plan)
    hs = [hvd.allreduce_async(t, op=hvd.Sum) for t in tensors]
    for r, h in zip(ref, hs):
        np.testing.assert_allclose(r, np.asarray(hvd.synchronize(h)))


def test_pingpong_recycling_numerics(hvd, monkeypatch):
    """HVD_PIPELINE_PINGPONG=1 (forced on CPU, where 'auto' is off):
    repeated same-signature flushes rotate recycled scratch sets; every
    flush's numerics must stay exact — a corrupted scratch (result
    aliasing the reused buffer) would show up immediately."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    monkeypatch.setenv("HVD_PIPELINE_THRESHOLD", str(64 * 1024))
    monkeypatch.setenv("HVD_PIPELINE_CHUNKS", "2")
    monkeypatch.setenv("HVD_PIPELINE_PINGPONG", "1")
    elems = 32 * 1024
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU: donation unsupported warns
        for step in range(1, 6):
            t = hvd.per_rank([jnp.full((elems,), float((r + 1) * step),
                                       jnp.float32) for r in range(N)])
            out, = hvd.grouped_allreduce([t], op=hvd.Sum)
            np.testing.assert_allclose(
                np.asarray(out), np.full((elems,), 36.0 * step))


# ------------------------------------------------- threaded stress (satellite)

def test_threaded_producers_fifo_and_numerics(hvd, monkeypatch):
    """N producer threads enqueue mixed allreduce_async/broadcast_async
    while the cycle timer fires: per-signature FIFO order (each
    producer's submissions appear in its submission order in the
    concatenated flush compositions), numerics equal to the analytic
    scheduler-off results, and no deadlock."""
    monkeypatch.setenv("HVD_CYCLE_TIME", "5")  # timer fires mid-stream
    monkeypatch.setenv("HVD_PENDING_CYCLE_TIME", "5")
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", "400")
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    fusion_cycle.reset()
    sched = fusion_cycle.scheduler()
    sched.flush_history = type(sched.flush_history)(maxlen=4096)

    n_threads, per_thread = 4, 12
    results: dict = {}
    errors: list = []

    def producer(tid):
        try:
            hs = []
            for i in range(per_thread):
                if i % 4 == 3:
                    h = hvd.broadcast_async(
                        hvd.per_rank(_vals(mult=tid + i + 1)), 0,
                        name=f"b{tid}.{i:02d}")
                    hs.append((i, "bcast", tid + i + 1, h))
                else:
                    h = hvd.allreduce_async(
                        hvd.per_rank(_vals(mult=tid * 100 + i + 1)),
                        op=hvd.Sum, name=f"a{tid}.{i:02d}")
                    hs.append((i, "sum", tid * 100 + i + 1, h))
            results[tid] = [(i, kind, mult, hvd.synchronize(h))
                            for i, kind, mult, h in hs]
        except BaseException as exc:  # pragma: no cover - surfaced below
            errors.append((tid, exc))

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "producer deadlocked"
    assert not errors, errors

    for tid, outs in results.items():
        for i, kind, mult, out in outs:
            if kind == "sum":
                np.testing.assert_allclose(np.asarray(out),
                                           _sum_expected(mult=mult))
            else:  # broadcast from rank 0: rank 0's value = 1 * mult
                np.testing.assert_allclose(np.asarray(out),
                                           np.full((8,), float(mult)))

    # per-signature FIFO: within each queue, each producer's names appear
    # in submission order across the concatenated flush compositions
    history = list(sched.flush_history)
    for prefix in ("a", "b"):
        for tid in range(n_threads):
            seen = [n for (_t, _k, names) in history for n in names
                    if n.startswith(f"{prefix}{tid}.")]
            assert seen == sorted(seen), (prefix, tid, seen)
            expected = per_thread // 4 if prefix == "b" \
                else per_thread - per_thread // 4
            assert len(seen) == expected


def test_abort_mid_pipeline_no_deadlock(hvd, monkeypatch):
    """abort() while producers are submitting and the executor is
    dispatching: every handle must resolve (result or error) within a
    bounded wait — aborted entries raise at synchronize, in-flight ones
    deliver; nothing hangs."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", "200")
    fusion_cycle.reset()
    handles: list = []
    hmu = threading.Lock()
    stop = threading.Event()

    def producer():
        i = 0
        while not stop.is_set() and i < 60:
            h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
            with hmu:
                handles.append(h)
            i += 1

    threads = [threading.Thread(target=producer) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    aborted = fusion_cycle.scheduler().abort("mid-pipeline abort test")
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "producer deadlocked after abort"
    delivered = failed = 0
    deadline = time.monotonic() + 30
    with hmu:
        snapshot = list(handles)
    for h in snapshot:
        while not hvd.poll(h):
            assert time.monotonic() < deadline, "handle never resolved"
            time.sleep(0.01)
        try:
            out = hvd.synchronize(h)
            np.testing.assert_allclose(np.asarray(out), _sum_expected())
            delivered += 1
        except RuntimeError as e:
            assert "abort" in str(e)
            failed += 1
    assert delivered + failed == len(snapshot)
    assert aborted >= 0  # abort count is whatever was still queued
    # the scheduler stays usable after the abort
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())


# ------------------------------------------- overlap metrics (stub device)

class _StubArray:
    """Deterministic device-completion stand-in: ``is_ready`` flips on
    command, ``block_until_ready`` (what slot admission calls through
    ``jax.block_until_ready``) waits for it. ``wait_entered`` observes the
    executor blocking on THIS array — releasing only after that makes the
    depth sample deterministic (sampling precedes blocking)."""

    def __init__(self):
        self._ready = threading.Event()
        self.wait_entered = threading.Event()

    def is_ready(self):
        return self._ready.is_set()

    def block_until_ready(self):
        self.wait_entered.set()
        assert self._ready.wait(30.0), "stub never released"
        return self

    def release(self):
        self._ready.set()


def test_stub_device_overlap_metrics(monkeypatch):
    """ISSUE 6 acceptance: with 2 slots and device completion controlled
    by hand, dispatch-time depth must reach 2 (two earlier flushes in
    flight when the third dispatches), overlap_ratio must be > 0, and
    slot blocking must accumulate device_wait_ms. The pre-fix accounting
    sampled depth AFTER eager retirement and slot blocking, which could
    never observe the full window."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    sched = fusion_cycle.FusionScheduler()
    stubs = [_StubArray() for _ in range(3)]

    def fake_execute(spec, entries, ticket=None, flush=0):
        for e in entries:
            e.results = [stubs[int(e.label)]]
            e.tensors = ()
            e.event.set()

    sched._execute = fake_execute
    spec = fusion_cycle._QueueSpec("allreduce", None, None)
    entries = [fusion_cycle._Entry([None], False, 8, [str(i)])
               for i in range(3)]
    try:
        for e in entries:
            sched._submit(fusion_cycle._Batch(spec, [e], "threshold"))
        # batches 0 and 1 dispatch without blocking (window not full);
        # batch 2's admission samples depth 2 (stubs 0 and 1 both
        # unready), then blocks on the OLDEST in-flight stub
        assert stubs[0].wait_entered.wait(10.0), \
            "executor never blocked on the full window"
        time.sleep(0.02)  # measurable device_wait_ms
        stubs[0].release()
        # stub 1 stays unready until batch 2 has dispatched (quiesce
        # returns after the batch completes): its post-blocking overlap
        # sample must deterministically see one live predecessor
        sched.quiesce()
        for s in stubs[1:]:
            s.release()
        p = sched.stats()["pipeline"]
        assert p["executed"] == 3
        assert p["inflight_peak"] == 2, p
        assert p["overlap_ratio"] == pytest.approx(2.0 / 3.0), p
        assert p["slot_waits"] == 1, p
        assert p["device_wait_ms"] > 0.0, p
    finally:
        for s in stubs:
            s.release()
        sched.stop()


def test_stub_device_slots1_reports_zero_overlap(monkeypatch):
    """slots=1 is the documented synchronous mode: every dispatch waits
    out its predecessor at slot admission, so overlap_ratio must read
    0.0 — the overlap sample is post-blocking — even though
    admission-time pressure (inflight_peak) sees each predecessor still
    in flight as the next batch arrives."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "1")
    sched = fusion_cycle.FusionScheduler()
    stubs = [_StubArray() for _ in range(3)]

    def fake_execute(spec, entries, ticket=None, flush=0):
        for e in entries:
            e.results = [stubs[int(e.label)]]
            e.tensors = ()
            e.event.set()

    sched._execute = fake_execute

    def _release_when_blocked_on():
        for s in stubs[:2]:  # the third is never blocked on
            s.wait_entered.wait(10.0)
            s.release()

    releaser = threading.Thread(target=_release_when_blocked_on,
                                daemon=True)
    releaser.start()
    spec = fusion_cycle._QueueSpec("allreduce", None, None)
    try:
        for i in range(3):
            sched._submit(fusion_cycle._Batch(
                spec, [fusion_cycle._Entry([None], False, 8, [str(i)])],
                "threshold"))
        sched.quiesce()
        p = sched.stats()["pipeline"]
        assert p["executed"] == 3
        assert p["overlap_ratio"] == 0.0, p
        assert p["inflight_peak"] == 1, p
        assert p["slot_waits"] == 2, p
        assert p["device_wait_ms"] > 0.0, p
    finally:
        for s in stubs:
            s.release()
        sched.stop()
        releaser.join(timeout=10)


def test_stub_device_no_overlap_when_synchronous(monkeypatch):
    """Control for the stub test: a stream whose flushes complete before
    the next admission reports zero overlap — the metric cannot invent
    overlap that did not happen."""
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    sched = fusion_cycle.FusionScheduler()

    def fake_execute(spec, entries, ticket=None, flush=0):
        for e in entries:
            stub = _StubArray()
            stub.release()  # device completes immediately
            e.results = [stub]
            e.tensors = ()
            e.event.set()

    sched._execute = fake_execute
    spec = fusion_cycle._QueueSpec("allreduce", None, None)
    try:
        for i in range(3):
            sched._submit(fusion_cycle._Batch(
                spec, [fusion_cycle._Entry([None], False, 8, [str(i)])],
                "threshold"))
        sched.quiesce()
        p = sched.stats()["pipeline"]
        assert p["executed"] == 3
        assert p["overlap_ratio"] == 0.0, p
        assert p["inflight_peak"] == 0, p
        assert p["device_wait_ms"] == 0.0, p
    finally:
        sched.stop()


# ------------------------------------------------------------------- stats

def test_fusion_stats_pipeline_fields(hvd):
    st = hvd.fusion_stats()
    p = st["pipeline"]
    for key in ("enabled", "max_inflight", "chunking", "submitted",
                "executed", "queue_depth", "overlap_ratio",
                "slot_occupancy", "inflight_peak", "slot_waits",
                "device_wait_ms"):
        assert key in p
    assert "wire_programs" in st


def test_overlap_ratio_counts_inflight_admissions(hvd, monkeypatch):
    monkeypatch.setenv("HVD_MAX_INFLIGHT_FLUSHES", "2")
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", "100")
    for i in range(8):
        hvd.allreduce_async(hvd.per_rank(_vals(mult=i + 1)), op=hvd.Sum)
    fusion_cycle.scheduler().flush_all("barrier")
    p = hvd.fusion_stats()["pipeline"]
    assert p["executed"] >= 2
    assert 0.0 <= p["overlap_ratio"] <= 1.0
    assert 0.0 < p["slot_occupancy"] <= 1.0
