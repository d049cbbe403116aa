"""Cycle-driven cross-call fusion scheduler (ISSUE 2 tentpole): *_async
submissions must queue per signature, flush on threshold / cycle time /
synchronize / poll / barrier / shutdown with rank-deterministic
composition, coalesce into grouped dispatches, and produce numerics
identical to the scheduler-off (immediate dispatch) path."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import fusion_cycle
from horovod_tpu.ops.compression import Compression

N = 8
LONG_CYCLE_MS = "2000"  # timer never fires during a test unless asked


@pytest.fixture(autouse=True)
def _fresh_scheduler(monkeypatch):
    monkeypatch.setenv("HVD_CYCLE_TIME", LONG_CYCLE_MS)
    # also pin the in-flight pace: after any dispatch the scheduler
    # flushes at PENDING_CYCLE_TIME for one cycle window, which would let
    # the timer fire mid-test (default: min(cycle/2, 2 ms))
    monkeypatch.setenv("HVD_PENDING_CYCLE_TIME", LONG_CYCLE_MS)
    fusion_cycle.reset()
    yield
    fusion_cycle.reset()


def _vals(shape=(8,), dtype=jnp.float32, mult=1.0):
    return [jnp.full(shape, (i + 1) * mult, dtype) for i in range(N)]


def _sum_expected(shape=(8,), mult=1.0):
    return np.full(shape, 36.0 * mult)


# ------------------------------------------------------------ flush triggers

@pytest.mark.parametrize("count", [6, 64])
def test_flush_on_synchronize_coalesces_whole_queue(hvd, count):
    """However long the queue (64: a per-parameter gradient loop's),
    one dispatch: the coalescing ratio is the queue's length."""
    handles = [hvd.allreduce_async(hvd.per_rank(_vals(mult=i + 1)),
                                   op=hvd.Sum) for i in range(count)]
    st = hvd.fusion_stats()
    assert st["pending_tensors"] == count
    assert all(not h._entry.done for h in handles)
    out0 = hvd.synchronize(handles[0])  # flushes the WHOLE queue
    # the batch's events are set in submission order after its one
    # dispatch; settle the peers before asserting done-ness (synchronize
    # only promises ITS entry — the whole-queue coalescing is what the
    # dispatch/coalesce stats below pin down)
    for h in handles[1:]:
        hvd.synchronize(h)
    assert all(h._entry.done for h in handles)
    st = hvd.fusion_stats()
    assert st["flushes"]["synchronize"] == 1
    assert st["dispatches"] == 1  # one grouped dispatch for all of them
    assert st["coalesce_ratio"] == float(count)
    assert st["pending_tensors"] == 0
    np.testing.assert_allclose(np.asarray(out0), _sum_expected(mult=1))
    for i, h in enumerate(handles):
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   _sum_expected(mult=i + 1))


def test_flush_on_threshold(hvd, monkeypatch):
    # per-rank payload: 8 f32 = 32 bytes; threshold trips on the 4th
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", "100")
    handles = [hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
               for _ in range(4)]
    st = hvd.fusion_stats()
    assert st["flushes"]["threshold"] == 1
    # the trigger only DRAINS the queue — execution happens on the
    # pipelined executor thread, so the enqueueing thread returns before
    # the entries complete (ISSUE 3 tentpole); the events carry completion
    for h in handles:
        assert h._entry.event.wait(10.0), "executor never ran the flush"
    for h in handles:
        np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                   _sum_expected())


def test_flush_on_cycle_time(hvd, monkeypatch):
    monkeypatch.setenv("HVD_CYCLE_TIME", "30")  # ms
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert h._entry.event.wait(5.0), "cycle timer never flushed the queue"
    st = hvd.fusion_stats()
    assert st["flushes"]["cycle"] >= 1
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())


def test_flush_on_barrier(hvd):
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert not h._entry.done
    hvd.barrier()
    assert h._entry.done
    assert hvd.fusion_stats()["flushes"]["barrier"] >= 1


def test_backpressure_cap(hvd, monkeypatch):
    monkeypatch.setenv("HVD_FUSION_THRESHOLD", str(1 << 30))
    monkeypatch.setenv("HVD_FUSION_MAX_PENDING", "100")
    handles = [hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
               for _ in range(4)]
    st = hvd.fusion_stats()
    assert st["flushes"]["backpressure"] >= 1
    assert st["pending_bytes"] <= 100
    for h in handles:
        hvd.synchronize(h)


# --------------------------------------------------------- handle semantics

def test_poll_triggers_own_flush(hvd):
    """ISSUE 2 satellite: poll() on an unflushed handle must trigger a
    flush of its own entry — otherwise a poll loop would spin forever on
    a dispatch nothing else triggers."""
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert not h._entry.done
    deadline = time.monotonic() + 5.0
    while not hvd.poll(h):
        assert time.monotonic() < deadline, "poll() never became ready"
    assert hvd.fusion_stats()["flushes"]["poll"] >= 1
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())


def test_synchronize_idempotent_and_cheap(hvd):
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    out1 = h.synchronize()
    assert h._synced
    out2 = h.synchronize()
    assert out2 is out1  # cached result object, no re-walk
    assert hvd.poll(h)
    # the immediate-dispatch Handle is idempotent too
    h2 = hvd.ops.collectives.Handle(jnp.ones(3))
    assert h2.synchronize() is h2.synchronize()


def test_grouped_async_entry_is_atomic(hvd):
    t1, t2 = _vals((4,)), _vals((2,), mult=10.0)
    hg = hvd.grouped_allreduce_async(
        [hvd.per_rank(t1), hvd.per_rank(t2)], op=hvd.Sum)
    hs = hvd.allreduce_async(hvd.per_rank(_vals((4,))), op=hvd.Sum)
    outs = hvd.synchronize(hg)
    np.testing.assert_allclose(np.asarray(outs[0]), _sum_expected((4,)))
    np.testing.assert_allclose(np.asarray(outs[1]),
                               _sum_expected((2,), mult=10.0))
    # the single rode the same flush (same signature queue)
    assert hs._entry.done
    st = hvd.fusion_stats()
    assert st["dispatches"] == 1 and st["flushed_tensors"] == 3


def test_aborted_entries_raise_at_synchronize(hvd):
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    aborted = fusion_cycle.scheduler().abort("test abort")
    assert aborted == 1
    # poll never raises: True means "synchronize() will not block"
    assert hvd.poll(h) is True
    with pytest.raises(RuntimeError, match="test abort"):
        hvd.synchronize(h)


def test_empty_group_async(hvd):
    h = hvd.grouped_allreduce_async([])
    assert hvd.synchronize(h) == []
    assert hvd.poll(h)


def test_mis_sized_bundle_raises_through_plan_path(hvd):
    """The plan-cache fast path must enforce the PerRank leading-axis
    check (_as_bundle's contract), not silently drop rows."""
    from horovod_tpu.ops.collectives import PerRank
    bad = PerRank(jnp.ones((2 * N, 4)))  # leading axis != pset size
    with pytest.raises(ValueError, match="leading axis"):
        hvd.allreduce(bad, op=hvd.Sum)
    h = hvd.allreduce_async(bad, op=hvd.Sum)
    with pytest.raises(ValueError, match="leading axis"):
        hvd.synchronize(h)


# ------------------------------------------------------- determinism contract

def _submit_stream(hvd, ps):
    """An interleaved mixed-dtype / mixed-pset / mixed-op submission
    stream with explicit names (deterministic across schedulers)."""
    sub = [jnp.full((4,), float(i + 1)) for i in range(4)]
    return [
        hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum, name="a0"),
        hvd.allreduce_async(hvd.per_rank(_vals(dtype=jnp.int32)),
                            op=hvd.Sum, name="a1"),
        hvd.allreduce_async(hvd.per_rank(sub, process_set=ps), op=hvd.Sum,
                            process_set=ps, name="a2"),
        hvd.broadcast_async(hvd.per_rank(_vals()), 0, name="b0"),
        hvd.allreduce_async(hvd.per_rank(_vals(mult=2.0)), op=hvd.Sum,
                            name="a3"),
        hvd.allreduce_async(hvd.per_rank(sub, process_set=ps),
                            op=hvd.Average, process_set=ps, name="a4"),
    ]


def test_flush_composition_deterministic(hvd):
    """Identical submission streams + identical trigger sequences must
    yield identical flush compositions (queue partitions and in-queue
    order), independent of scheduler instance — the single-controller
    statement of the reference coordinator's rank-determinism contract."""
    ps = hvd.add_process_set([0, 1, 2, 3])
    try:
        histories = []
        for _ in range(2):
            fusion_cycle.reset()
            handles = _submit_stream(hvd, ps)
            fusion_cycle.scheduler().flush_all("barrier")
            histories.append(list(fusion_cycle.scheduler().flush_history))
            for h in handles:
                hvd.synchronize(h)
        assert histories[0] == histories[1]
        # composition facts: mixed dtypes share the global allreduce queue
        # (wire bucketing happens inside the grouped dispatch); subset and
        # broadcast submissions get their own queues, in submission order
        comps = [(key[0], names) for (_t, key, names) in histories[0]]
        assert comps[0] == ("allreduce", ("a0", "a1", "a3"))
        assert comps[1][0] == "allreduce" and comps[1][1] == ("a2",)
        assert ("broadcast", ("b0",)) in comps
    finally:
        hvd.remove_process_set(ps)


def test_mixed_pset_results_correct(hvd):
    ps = hvd.add_process_set([0, 1, 2, 3])
    try:
        sub = [jnp.full((4,), float(i + 1)) for i in range(4)]
        handles = _submit_stream(hvd, ps)
        outs = [hvd.synchronize(h) for h in handles]
        np.testing.assert_allclose(np.asarray(outs[0]), _sum_expected())
        np.testing.assert_allclose(np.asarray(outs[1]),
                                   _sum_expected().astype(np.int32))
        np.testing.assert_allclose(np.asarray(outs[2]), np.full((4,), 10.0))
        np.testing.assert_allclose(np.asarray(outs[3]), np.full((8,), 1.0))
        np.testing.assert_allclose(np.asarray(outs[4]),
                                   _sum_expected(mult=2.0))
        np.testing.assert_allclose(np.asarray(outs[5]), np.full((4,), 2.5))
    finally:
        hvd.remove_process_set(ps)


# ------------------------------------------------------------ numerics parity

def test_numerics_parity_scheduler_on_off(hvd, monkeypatch):
    def run_all():
        h1 = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Average)
        h2 = hvd.grouped_allreduce_async(
            [hvd.per_rank(_vals((3,))), hvd.per_rank(_vals((5,), mult=3.0))],
            op=hvd.Sum)
        h3 = hvd.broadcast_async(hvd.per_rank(_vals((2,))), 3)
        h4 = hvd.allgather_async(hvd.per_rank(_vals((2,))))
        outs = [hvd.synchronize(h1), *hvd.synchronize(h2),
                hvd.synchronize(h3), hvd.synchronize(h4)]
        return [np.asarray(o) for o in outs]

    queued = run_all()
    monkeypatch.setenv("HVD_CYCLE_TIME", "0")  # scheduler off: immediate
    immediate = run_all()
    assert len(queued) == len(immediate)
    for q, im in zip(queued, immediate):
        np.testing.assert_allclose(q, im)


# ------------------------------------------------------------ queue lifecycle

def test_queue_drain_on_shutdown_hook(hvd):
    """drain() (called by hvd.shutdown) executes pending entries instead
    of dropping them."""
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert not h._entry.done
    fusion_cycle.drain()
    assert h._entry.done
    assert hvd.fusion_stats()["flushes"]["shutdown"] >= 1
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())


def test_scheduler_off_switch(hvd, monkeypatch):
    monkeypatch.setenv("HVD_CYCLE_TIME", "0")
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert type(h).__name__ == "Handle"  # immediate dispatch, no entry
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())


def test_broadcast_parameters_rides_queue(hvd):
    params = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              "b": jnp.ones((4,), jnp.int32)}
    synced = hvd.broadcast_parameters(params, root_rank=0)
    st = hvd.fusion_stats()
    assert st["enqueued_tensors"] >= 2
    assert st["flushes"]["synchronize"] >= 1
    np.testing.assert_allclose(np.asarray(synced["w"]),
                               np.arange(6).reshape(2, 3))


def test_sparse_async_rides_queue(hvd):
    from horovod_tpu.ops.sparse import SparseRows, sparse_allreduce_async
    rows = SparseRows(indices=jnp.asarray([0, 2]), values=jnp.ones((2, 3)),
                      num_rows=4)
    h = sparse_allreduce_async(rows, op=hvd.Sum)
    assert not h._entry.done  # deferred, not dispatched at submit
    out = hvd.synchronize(h)
    dense = np.asarray(hvd.rows_to_dense(out))
    np.testing.assert_allclose(dense[0], N * 1.0)
    np.testing.assert_allclose(dense[1], 0.0)


def test_allgather_async_rides_queue(hvd):
    h = hvd.allgather_async(hvd.per_rank(_vals((2,))))
    assert not h._entry.done
    out = hvd.synchronize(h)
    assert out.shape == (2 * N,)


# ------------------------------------------- wire-dtype fusion (satellite)

def test_wire_dtype_buckets_fuse_mixed_sources(hvd):
    """_fuse_by_dtype keyed by WIRE dtype: f32 and bf16 tensors routed
    through Compression.bf16 share ONE wire bucket; results decompress
    back to their source dtypes after the split."""
    from horovod_tpu.ops.collectives import (_fuse_by_dtype, _split_fused,
                                             _wire_dtype_of)
    bundles = [jnp.ones((N, 4), jnp.float32), jnp.ones((N, 6), jnp.bfloat16),
               jnp.ones((N, 3), jnp.int32)]
    wire = [_wire_dtype_of(b, Compression.bf16) for b in bundles]
    assert [w.name for w in wire] == ["bfloat16", "bfloat16", "int32"]
    fused, metas = _fuse_by_dtype(bundles, N, wire_dtypes=wire)
    assert len(fused) == 2  # one bf16 wire buffer + the int bucket
    assert fused[0].dtype == jnp.bfloat16 and fused[0].shape == (N, 10)
    out = _split_fused([f[0] for f in fused], metas, 3)
    assert out[0].dtype == jnp.float32  # decompressed after split
    assert out[1].dtype == jnp.bfloat16
    assert out[2].dtype == jnp.int32


def test_grouped_allreduce_compression_numerics(hvd):
    ts = [jnp.full((4,), 2.0, jnp.float32), jnp.full((6,), 1.0, jnp.bfloat16),
          jnp.arange(3, dtype=jnp.int32)]
    outs = hvd.grouped_allreduce(ts, op=hvd.Sum, compression=Compression.bf16)
    assert [o.dtype for o in outs] == [jnp.float32, jnp.bfloat16, jnp.int32]
    np.testing.assert_allclose(np.asarray(outs[0]), np.full((4,), 16.0))
    np.testing.assert_allclose(np.asarray(outs[1]), np.full((6,), 8.0))
    np.testing.assert_allclose(np.asarray(outs[2]), np.arange(3) * N)


def test_async_compression_queue_key(hvd):
    """Compressed and uncompressed submissions of the same signature land
    in different queues (wire dtype is part of the queue key)."""
    h1 = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum,
                             compression=Compression.bf16)
    h2 = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert h1._entry.queue_key != h2._entry.queue_key
    out1, out2 = hvd.synchronize(h1), hvd.synchronize(h2)
    assert out1.dtype == out2.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out1), _sum_expected())
    np.testing.assert_allclose(np.asarray(out2), _sum_expected())


def test_async_default_op_is_average(hvd):
    """allreduce_async with no op= must keep the reference default
    (Average), queued or not."""
    h = hvd.allreduce_async(hvd.per_rank(_vals()))
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected() / N)
    hg = hvd.grouped_allreduce_async([hvd.per_rank(_vals())])
    np.testing.assert_allclose(np.asarray(hvd.synchronize(hg)[0]),
                               _sum_expected() / N)


def test_none_compression_shares_queue(hvd):
    """Compression.none is the same wire behavior as no compression —
    the two spellings must coalesce into one queue."""
    h1 = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum,
                             compression=Compression.none)
    h2 = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert h1._entry.queue_key == h2._entry.queue_key
    hvd.synchronize(h1), hvd.synchronize(h2)
    assert hvd.fusion_stats()["dispatches"] == 1


def test_custom_compressor_still_applied(hvd):
    """A user Compressor subclass (compress/decompress, no wire_dtype)
    must wrap the collective, not be silently dropped."""
    calls = []

    class Halver(Compression.none):
        @staticmethod
        def compress(t):
            calls.append("c")
            return t * 0.5, None

        @staticmethod
        def decompress(t, ctx):
            calls.append("d")
            return t * 2.0

    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum,
                            compression=Halver)
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               _sum_expected())
    assert "c" in calls and "d" in calls
    # and through the optimizer-facing grouped path
    calls.clear()
    outs = hvd.grouped_allreduce([hvd.per_rank(_vals())], op=hvd.Sum,
                                 compression=Halver)
    np.testing.assert_allclose(np.asarray(outs[0]), _sum_expected())
    assert "c" in calls and "d" in calls


def test_inputs_released_after_flush(hvd):
    h = hvd.allreduce_async(hvd.per_rank(_vals()), op=hvd.Sum)
    assert len(h._entry.tensors) == 1
    hvd.synchronize(h)
    assert h._entry.tensors == ()  # inputs freed; handle keeps results


# ------------------------------------------------------------------- stats

def test_fusion_stats_shape(hvd):
    st = hvd.fusion_stats()
    assert st["enabled"] is True
    for trigger in fusion_cycle.FLUSH_TRIGGERS:
        assert trigger in st["flushes"]
    for key in ("coalesce_ratio", "tensors_per_flush", "pending_bytes",
                "enqueued_tensors", "dispatches"):
        assert key in st
