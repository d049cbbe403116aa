"""Dynamic engine semantics: negotiation, mismatch ERRORs, cache, fusion,
groups, join, stall inspection.

Ports the reference's core-runtime guarantees (exercised there by real
2-process mpirun jobs in ``test/parallel/test_{torch,tensorflow}.py`` and
``test/integration/test_stall.py``) onto the in-memory multi-engine
protocol driver — same negotiation code, no processes.
"""

import json
import os
import time

import pytest

from horovod_tpu import _native, dynamic
from horovod_tpu.dynamic import (
    REQ_ALLGATHER,
    REQ_ALLREDUCE,
    REQ_BARRIER,
    REQ_BROADCAST,
    REQ_JOIN,
    DuplicateNameError,
    NativeEngine,
    and_bitvectors,
    drive_cycle,
)

pytestmark = pytest.mark.skipif(
    not _native.available(), reason="native engine unavailable (no g++?)")


def make_world(n, **kw):
    return [NativeEngine(world_size=n, rank=r, **kw) for r in range(n)]


def close_world(engines):
    for e in engines:
        e.close()


@pytest.fixture()
def world2():
    engines = make_world(2)
    yield engines
    close_world(engines)


@pytest.fixture()
def world4():
    engines = make_world(4)
    yield engines
    close_world(engines)


class TestNegotiation:
    def test_not_ready_until_all_ranks(self, world2):
        a, b = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert plans[0] == [] and plans[1] == []
        b.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert [r.tensor_names for r in plans[0]] == [["t"]]
        # identical plan on every rank (symmetric protocol)
        assert plans[0] == plans[1]

    def test_plans_identical_across_ranks(self, world4):
        for i, e in enumerate(world4):
            e.enqueue("x", REQ_ALLREDUCE, shape=(8,))
            e.enqueue(f"y{i}", REQ_ALLREDUCE, shape=(2,))
        plans = drive_cycle(world4)
        assert plans[0] == plans[1] == plans[2] == plans[3]
        # only "x" is globally ready
        names = [n for r in plans[0] for n in r.tensor_names]
        assert names == ["x"]

    def test_ordering_by_first_submission(self, world2):
        a, b = world2
        a.enqueue("late", REQ_ALLREDUCE, shape=(1000000,), dtype=1)
        drive_cycle(world2)
        a.enqueue("early", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("early", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("late", REQ_ALLREDUCE, shape=(1000000,), dtype=1)
        plans = drive_cycle(world2)
        names = [n for r in plans[0] for n in r.tensor_names]
        # "late" was first submitted (cycle 1) so it schedules first
        assert names == ["late", "early"]

    def test_duplicate_name_rejected_while_pending(self, world2):
        a, _ = world2
        a.enqueue("d", REQ_ALLREDUCE, shape=(4,))
        with pytest.raises(DuplicateNameError, match="d"):
            a.enqueue("d", REQ_ALLREDUCE, shape=(4,))

    def test_name_reusable_after_completion(self, world2):
        a, b = world2
        for e in world2:
            e.enqueue("r", REQ_ALLREDUCE, shape=(4,))
        drive_cycle(world2)
        for e in world2:
            e.enqueue("r", REQ_ALLREDUCE, shape=(4,))  # no raise
        plans = drive_cycle(world2)
        assert [n for r in plans[0] for n in r.tensor_names] == ["r"]


class TestMismatchErrors:
    def test_shape_mismatch_is_error_response(self, world2):
        a, b = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("t", REQ_ALLREDUCE, shape=(5,))
        plans = drive_cycle(world2)
        assert plans[0] == plans[1]
        (err,) = plans[0]
        assert err.is_error
        assert "Mismatched ALLREDUCE tensor shapes" in err.error_message
        assert "[4]" in err.error_message and "[5]" in err.error_message
        assert "rank 0" in err.error_message and "rank 1" in err.error_message

    def test_dtype_mismatch(self, world2):
        a, b = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,), dtype=0)
        b.enqueue("t", REQ_ALLREDUCE, shape=(4,), dtype=2)
        (err,) = drive_cycle(world2)[0]
        assert err.is_error and "Mismatched data types" in err.error_message

    def test_op_mismatch(self, world2):
        a, b = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("t", REQ_ALLGATHER, shape=(4,))
        (err,) = drive_cycle(world2)[0]
        assert err.is_error
        assert "Mismatched collective operations" in err.error_message
        assert "ALLREDUCE" in err.error_message
        assert "ALLGATHER" in err.error_message

    def test_broadcast_root_mismatch(self, world2):
        a, b = world2
        a.enqueue("t", REQ_BROADCAST, shape=(4,), root_rank=0)
        b.enqueue("t", REQ_BROADCAST, shape=(4,), root_rank=1)
        (err,) = drive_cycle(world2)[0]
        assert err.is_error and "root" in err.error_message

    def test_allgather_first_dim_may_differ(self, world2):
        a, b = world2
        a.enqueue("g", REQ_ALLGATHER, shape=(2, 3))
        b.enqueue("g", REQ_ALLGATHER, shape=(5, 3))
        (resp,) = drive_cycle(world2)[0]
        assert not resp.is_error and resp.tensor_names == ["g"]
        # the negotiated per-rank first dims ride recv_splits (the ragged
        # allgatherv size exchange, collective_operations.h:143-178)
        assert resp.recv_splits == [2, 5]

    def test_allgather_dim0_digest_mismatch(self, world2):
        a, b = world2
        a.enqueue("g", REQ_ALLGATHER, shape=(2, 3), splits_crc=7)
        b.enqueue("g", REQ_ALLGATHER, shape=(5, 3), splits_crc=8)
        (err,) = drive_cycle(world2)[0]
        assert err.is_error
        assert "ALLGATHER size metadata" in err.error_message

    def test_allgather_later_dims_must_match(self, world2):
        a, b = world2
        a.enqueue("g", REQ_ALLGATHER, shape=(2, 3))
        b.enqueue("g", REQ_ALLGATHER, shape=(2, 4))
        (err,) = drive_cycle(world2)[0]
        assert err.is_error
        assert "all dimensions except the first" in err.error_message

    def test_name_reusable_after_error(self, world2):
        a, b = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("t", REQ_ALLREDUCE, shape=(5,))
        drive_cycle(world2)
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        (resp,) = drive_cycle(world2)[0]
        assert not resp.is_error


class TestFusion:
    def test_same_dtype_fused_under_threshold(self, world2):
        for e in world2:
            e.enqueue("a", REQ_ALLREDUCE, shape=(4,), dtype=1)
            e.enqueue("b", REQ_ALLREDUCE, shape=(6,), dtype=1)
            e.enqueue("c", REQ_ALLREDUCE, shape=(2,), dtype=1)
        plans = drive_cycle(world2)
        (fused,) = plans[0]
        assert fused.tensor_names == ["a", "b", "c"]
        assert fused.total_bytes == (4 + 6 + 2) * 4

    def test_dtype_change_breaks_fusion(self, world2):
        for e in world2:
            e.enqueue("a", REQ_ALLREDUCE, shape=(4,), dtype=1)
            e.enqueue("b", REQ_ALLREDUCE, shape=(4,), dtype=2)
        plans = drive_cycle(world2)
        assert [r.tensor_names for r in plans[0]] == [["a"], ["b"]]

    def test_threshold_splits_buckets(self):
        engines = make_world(2, fusion_threshold=64)
        try:
            for e in engines:
                e.enqueue("a", REQ_ALLREDUCE, shape=(8,), element_size=4)
                e.enqueue("b", REQ_ALLREDUCE, shape=(8,), element_size=4)
                e.enqueue("c", REQ_ALLREDUCE, shape=(8,), element_size=4)
            plans = drive_cycle(engines)
            assert [r.tensor_names for r in plans[0]] == [["a", "b"], ["c"]]
        finally:
            close_world(engines)

    def test_barrier_never_fused(self, world2):
        for e in world2:
            e.enqueue("a", REQ_ALLREDUCE, shape=(4,))
            e.enqueue("bar", REQ_BARRIER)
            e.enqueue("b", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        kinds = [(r.type_name, r.tensor_names) for r in plans[0]]
        assert ("BARRIER", ["bar"]) in kinds


class TestGroups:
    def test_group_waits_for_all_members(self, world2):
        a, b = world2
        for e in world2:
            e.register_group(7, 2)
        for e in world2:
            e.enqueue("g1", REQ_ALLREDUCE, shape=(4,), group_id=7)
        plans = drive_cycle(world2)
        assert plans[0] == []  # g2 not yet submitted anywhere
        for e in world2:
            e.enqueue("g2", REQ_ALLREDUCE, shape=(4,), group_id=7)
        plans = drive_cycle(world2)
        names = [n for r in plans[0] for n in r.tensor_names]
        assert sorted(names) == ["g1", "g2"]


class TestJoin:
    def test_join_completes_when_all_joined(self, world2):
        a, b = world2
        a.enqueue("j", REQ_JOIN)
        plans = drive_cycle(world2)
        assert all(not p for p in plans)
        b.enqueue("j2", REQ_JOIN)
        plans = drive_cycle(world2)
        assert [r.type_name for r in plans[0]] == ["JOIN"]
        assert plans[0] == plans[1]

    def test_joined_rank_counts_ready_for_others(self, world2):
        a, b = world2
        a.enqueue("j", REQ_JOIN)
        b.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        # rank 0 joined: its absence must not block rank 1's tensor
        names = [n for r in plans[1] for n in r.tensor_names]
        assert "t" in names


class TestResponseCache:
    def test_second_cycle_hits_cache(self, world2):
        for e in world2:
            e.enqueue("c", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert not plans[0][0].from_cache
        for e in world2:
            e.enqueue("c", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        (resp,) = plans[0]
        assert resp.from_cache and resp.tensor_names == ["c"]
        assert plans[0] == plans[1]

    def test_no_hit_until_all_ranks_resubmit(self, world2):
        a, b = world2
        for e in world2:
            e.enqueue("c", REQ_ALLREDUCE, shape=(4,))
        drive_cycle(world2)
        a.enqueue("c", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert plans[0] == [] and plans[1] == []
        b.enqueue("c", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert plans[0][0].from_cache

    def test_changed_shape_invalidates_consistently(self, world2):
        """Ranks enqueue the changed tensor in
        *different* cycles; invalidation is driven by the globally-ingested
        request stream so every rank erases on the same cycle and bit
        layouts never diverge."""
        a, b = world2
        for e in world2:
            e.enqueue("v", REQ_ALLREDUCE, shape=(4,))
            e.enqueue("w", REQ_ALLREDUCE, shape=(2,))
        drive_cycle(world2)
        assert a.cache_size() == b.cache_size() == 2

        # rank 0 submits changed "v" one cycle before rank 1
        a.enqueue("v", REQ_ALLREDUCE, shape=(9,))
        drive_cycle(world2)
        # both ranks must have erased "v" on the SAME cycle
        assert a.cache_size() == b.cache_size() == 1

        b.enqueue("v", REQ_ALLREDUCE, shape=(9,))
        # "w" cache entry must still be globally consistent: a cache hit
        # for "w" must be served on both ranks with aligned bit positions
        for e in world2:
            e.enqueue("w", REQ_ALLREDUCE, shape=(2,))
        plans = drive_cycle(world2)
        assert plans[0] == plans[1]
        by_name = {tuple(r.tensor_names): r for r in plans[0]}
        assert by_name[("w",)].from_cache
        assert not by_name[("v",)].from_cache  # re-negotiated after change

    def test_cache_capacity_evicts(self):
        engines = make_world(2, cache_capacity=2)
        try:
            for i in range(3):
                for e in engines:
                    e.enqueue(f"t{i}", REQ_ALLREDUCE, shape=(4,))
                drive_cycle(engines)
            assert engines[0].cache_size() == 2
            assert engines[0].cache_size() == engines[1].cache_size()
        finally:
            close_world(engines)


class TestStallInspector:
    def test_stall_reported_after_warn_threshold(self):
        engines = make_world(2, stall_warn=0.05)
        try:
            engines[0].enqueue("s", REQ_ALLREDUCE, shape=(4,))
            drive_cycle(engines)
            time.sleep(0.1)
            report, shutdown = engines[0].stall_report()
            assert not shutdown
            (entry,) = report
            assert entry.tensor_name == "s"
            assert entry.ready_ranks == [0]
            assert entry.missing_ranks(2) == [1]
            assert entry.waiting_seconds >= 0.05
        finally:
            close_world(engines)

    def test_no_stall_before_threshold(self):
        engines = make_world(2, stall_warn=30.0)
        try:
            engines[0].enqueue("s", REQ_ALLREDUCE, shape=(4,))
            drive_cycle(engines)
            report, shutdown = engines[0].stall_report()
            assert report == [] and not shutdown
        finally:
            close_world(engines)

    def test_shutdown_threshold(self):
        engines = make_world(2, stall_warn=0.01, stall_shutdown=0.05)
        try:
            engines[0].enqueue("s", REQ_ALLREDUCE, shape=(4,))
            drive_cycle(engines)
            time.sleep(0.1)
            _, shutdown = engines[0].stall_report()
            assert shutdown
        finally:
            close_world(engines)

    def test_stall_clears_when_all_arrive(self):
        engines = make_world(2, stall_warn=0.01)
        try:
            engines[0].enqueue("s", REQ_ALLREDUCE, shape=(4,))
            drive_cycle(engines)
            time.sleep(0.05)
            engines[1].enqueue("s", REQ_ALLREDUCE, shape=(4,))
            drive_cycle(engines)
            report, _ = engines[0].stall_report()
            assert report == []
        finally:
            close_world(engines)


class TestTimeline:
    def test_chrome_trace_json(self, tmp_path):
        path = str(tmp_path / "trace.json")
        e = NativeEngine(world_size=1, rank=0)
        try:
            e.timeline_start(path)
            e.timeline_record("tensor_a", "NEGOTIATE", 0)
            e.timeline_record("tensor_a", "NEGOTIATE", 1)
            e.timeline_record("tensor_b", "ALLREDUCE", 0)
            e.timeline_record("tensor_b", "ALLREDUCE", 1)
            e.timeline_record("tensor_a", "CYCLE", 2)
            e.timeline_stop()
        finally:
            e.close()
        with open(path) as f:
            events = json.load(f)  # must be valid JSON (the reference's
            # test_timeline.py validates the same way)
        names = {ev["name"] for ev in events}
        assert {"NEGOTIATE", "ALLREDUCE", "CYCLE"} <= names
        phases = {ev["ph"] for ev in events}
        assert {"B", "E", "i", "M"} <= phases
        # one lane per tensor, named via metadata events
        lanes = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
        assert lanes == {"tensor_a", "tensor_b"}

    def test_restart_same_engine(self, tmp_path):
        e = NativeEngine()
        try:
            p1, p2 = str(tmp_path / "t1.json"), str(tmp_path / "t2.json")
            e.timeline_start(p1)
            e.timeline_record("t", "A", 2)
            e.timeline_stop()
            e.timeline_start(p2)
            e.timeline_record("t", "B", 2)
            e.timeline_stop()
            for p in (p1, p2):
                with open(p) as f:
                    json.load(f)
        finally:
            e.close()


class TestAbandon:
    """Post-timeout retry path: abandon() clears local bookkeeping so a
    name can be enqueued again (the reference has no analog — its waits
    are unbounded)."""

    def test_abandon_before_send_allows_retry(self, world2):
        a, _ = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        assert a.abandon("t")
        assert not a.abandon("t")  # not outstanding anymore
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))  # must not raise

    def test_abandon_unsent_request_never_hits_the_wire(self):
        engines = make_world(2, stall_warn=0.05)
        try:
            a, b = engines
            a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
            a.abandon("t")
            plans = drive_cycle(engines)
            assert plans == [[], []]
            # past the (tiny) stall-warn threshold a ghost table entry on
            # the other rank would show up in its stall report
            time.sleep(0.1)
            report, _ = b.stall_report()
            assert report == []
        finally:
            close_world(engines)

    def test_retry_with_different_metadata_rejected(self, world2):
        a, _ = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        drive_cycle(world2)  # request went out; table entry live
        assert a.abandon("t")
        with pytest.raises(DuplicateNameError, match="different"):
            a.enqueue("t", REQ_ALLREDUCE, shape=(8,))
        # matching retry still fine
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))

    def test_retry_after_sent_reattaches_no_ghost(self, world2):
        a, b = world2
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        drive_cycle(world2)  # a's request goes out; b hasn't submitted
        assert a.abandon("t")
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))  # re-attach, no new wire req
        assert a.pop_requests() == b.pop_requests()  # both serialize empty
        b.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert [p[0].tensor_names for p in plans] == [["t"], ["t"]]
        # fully complete everywhere: name reusable, nothing stalled
        a.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        b.enqueue("t", REQ_ALLREDUCE, shape=(4,))
        plans = drive_cycle(world2)
        assert [p[0].tensor_names for p in plans] == [["t"], ["t"]]


class TestBitvectorAnd:
    def test_and(self):
        assert and_bitvectors([b"\xff\x0f", b"\xf0\xff"]) == b"\xf0\x0f"

    def test_unequal_lengths_pad_zero(self):
        assert and_bitvectors([b"\xff", b"\xff\xff"]) == b"\xff\x00"

    def test_empty(self):
        assert and_bitvectors([]) == b""


def test_join_lets_others_finish_and_reports_metadata():
    """Joined ranks count as ready (controller.cc:268-272) and responses
    carry shapes/op metadata for zero reconstruction (JoinOp analog)."""
    from horovod_tpu.dynamic import NativeEngine, drive_cycle, REQ_JOIN

    engines = [NativeEngine(world_size=2, rank=r) for r in range(2)]
    try:
        engines[0].enqueue("g", 0, dtype=11, element_size=4, shape=(4, 2),
                           reduce_op=1, prescale=1.0, postscale=0.5)
        engines[1].enqueue("join.0", REQ_JOIN)
        plans = drive_cycle(engines)
        # rank 0's allreduce is schedulable thanks to the joined rank
        assert len(plans[0]) == 1
        resp = plans[0][0]
        assert resp.type == 0 and resp.tensor_names == ["g"]
        assert resp.shapes == [(4, 2)]
        assert resp.group_ids == [-1]
        assert resp.reduce_op == 1 and resp.postscale == 0.5
        # JOIN not yet emitted: rank 0 hasn't joined
        assert all(r.type != 3 for r in plans[1])
        engines[0].enqueue("join.0", REQ_JOIN)
        plans = drive_cycle(engines)
        joins = [r for r in plans[0] if r.type == 3]
        assert len(joins) == 1
        assert joins[0].root_rank == 0  # last ingested join = rank 0
        assert "join.0" in joins[0].tensor_names
    finally:
        for e in engines:
            e.close()


def test_reduce_param_mismatch_is_error():
    from horovod_tpu.dynamic import NativeEngine, drive_cycle

    engines = [NativeEngine(world_size=2, rank=r) for r in range(2)]
    try:
        engines[0].enqueue("p", 0, dtype=11, element_size=4, shape=(4,),
                           reduce_op=1, postscale=0.5)
        engines[1].enqueue("p", 0, dtype=11, element_size=4, shape=(4,),
                           reduce_op=1, postscale=1.0)
        plans = drive_cycle(engines)
        assert plans[0][0].is_error
        assert "Mismatched reduce parameters" in plans[0][0].error_message
    finally:
        for e in engines:
            e.close()


class TestRandomizedSymmetry:
    """Property check on the engine's core guarantee: every rank computes
    the IDENTICAL response plan from the identical ingested stream — for
    randomized op sequences, arrival staggering across cycles, fusion
    boundaries, and cache interleaving (the reference asserts the same
    through determinism of its rank-0 master protocol; this engine is
    symmetric, so the property must hold on every rank independently)."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_random_schedules_produce_identical_plans(self, seed):
        import random
        rng = random.Random(seed)
        n = rng.choice((2, 3, 4))
        engines = make_world(n)
        try:
            ops = []
            for i in range(rng.randint(8, 20)):
                kind = rng.choice((REQ_ALLREDUCE, REQ_ALLGATHER,
                                   REQ_BROADCAST, REQ_BARRIER))
                shape = (rng.randint(1, 6), rng.randint(1, 4))
                ops.append(dict(
                    name=f"op{i}", request_type=kind,
                    shape=() if kind == REQ_BARRIER else shape,
                    root_rank=rng.randrange(n)
                    if kind == REQ_BROADCAST else -1,
                    reduce_op=0 if kind == REQ_ALLREDUCE else -1))
            # repeat some names in later cycles to exercise the cache
            repeats = [dict(op) for op in rng.sample(
                ops, k=min(3, len(ops))) if op["request_type"] not in
                (REQ_BARRIER, REQ_ALLGATHER)]

            # stagger arrivals: each rank enqueues each op in a cycle
            # chosen per (rank, op) — readiness must still converge
            n_cycles = 4
            schedule = {(r, i): rng.randrange(n_cycles)
                        for r in range(n) for i in range(len(ops))}
            plans = [[] for _ in range(n)]
            for cycle in range(n_cycles + n + 2):
                for r, e in enumerate(engines):
                    for i, op in enumerate(ops):
                        if schedule.get((r, i)) == cycle:
                            e.enqueue(**op)
                    if cycle == n_cycles + 1:
                        for op in repeats:
                            e.enqueue(**op)
                for r, resp in enumerate(drive_cycle(engines)):
                    plans[r].extend(resp)  # full dataclass equality below
            # every rank saw the identical plan stream
            for r in range(1, n):
                assert plans[r] == plans[0], (seed, r)
            # and everything completed: each op name appears exactly once
            # per submission round in the plan (no drops, no duplicates)
            names = [nm for p in plans[0] for nm in p.tensor_names]
            for i, op in enumerate(ops):
                expected = 1 + sum(1 for rep in repeats
                                   if rep["name"] == op["name"])
                assert names.count(f"op{i}") == expected, (seed, i)
        finally:
            close_world(engines)


class _LoopbackTransport:
    """world=1 transport: the exchange returns this process's own frame."""

    def exchange(self, cycle, req_bytes, bits, timeout):
        return [req_bytes], [bits]


class TestAdaptiveCycle:
    """Event-driven negotiation tick (reference 1 ms CycleTimeMs rationale,
    operations.cc:499-506): fresh enqueues wake the cycle loop instead of
    waiting out the idle cadence; HVD_ADAPTIVE_CYCLE=0 restores the fixed
    sleep."""

    def _service(self, cycle_time_s):
        from horovod_tpu.engine_service import DynamicService
        return DynamicService(NativeEngine(world_size=1, rank=0),
                              _LoopbackTransport(),
                              cycle_time_s=cycle_time_s)

    def test_enqueue_wakes_the_cycle(self, monkeypatch):
        monkeypatch.delenv("HVD_ADAPTIVE_CYCLE", raising=False)
        svc = self._service(cycle_time_s=0.5)
        try:
            time.sleep(0.1)  # loop is now in its long idle sleep
            t0 = time.monotonic()
            resp = svc.negotiate("adaptive_t", REQ_ALLREDUCE, shape=(4,))
            took = time.monotonic() - t0
            assert not resp.is_error
            assert took < 0.25, f"adaptive tick did not wake the loop: {took}s"
        finally:
            svc.stop()

    def test_fixed_cadence_with_knob_off(self, monkeypatch):
        monkeypatch.setenv("HVD_ADAPTIVE_CYCLE", "0")
        svc = self._service(cycle_time_s=0.4)
        try:
            time.sleep(0.05)  # the loop entered its fixed sleep
            t0 = time.monotonic()
            svc.negotiate("fixed_t", REQ_ALLREDUCE, shape=(4,))
            took = time.monotonic() - t0
            # must wait out the remainder of the fixed cycle (enqueue at
            # ~0.05 into a 0.4 s sleep -> served no earlier than ~0.3 s)
            assert took > 0.2, f"fixed cadence was not respected: {took}s"
        finally:
            svc.stop()
