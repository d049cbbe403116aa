"""Hierarchical negotiation control plane + coordinator ResponseCache.

ISSUE-13 coverage (docs/negotiation.md): static group-layout edge cases
(G ∤ world), the two-level member → leader → cross-leader → fan-down
exchange against a real KV server, the coordinator ResponseCache's
confirm-then-serve lifecycle with its invalidation paths (knob-override
epoch, pset change / service reset, re-form via coordinated abort) and
bit-vector-divergence re-negotiation, flat ↔ hierarchical numerics
parity at world=4, leader-death chaos, and the world=16 tier-1 smoke
(world=64 marked slow, swept by ci.sh).
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu import _native
from horovod_tpu import metrics as m
from horovod_tpu.dynamic import NativeEngine, REQ_ALLREDUCE, REQ_ALLGATHER
from horovod_tpu.exceptions import PeerFailureError
from horovod_tpu.loopback.context import RankKilled
from horovod_tpu.negotiation import GroupLayout, ResponseCache
from horovod_tpu.negotiation import response_cache as rcache_mod
from horovod_tpu.utils import envs
from horovod_tpu.utils import faults as _faults

pytestmark = pytest.mark.skipif(
    not _native.available(), reason="native engine unavailable")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAST_HEALTH = {"HVD_HEALTH_INTERVAL": "0.3", "HVD_HEALTH_TIMEOUT": "1.5"}
HIER_G2 = {"HVD_HIER_NEGOTIATION": "1", "HVD_NEGOTIATION_GROUP_SIZE": "2"}


# ---------------------------------------------------------------------------
# static group layout
# ---------------------------------------------------------------------------

class TestGroupLayout:
    def test_divisible(self):
        l = GroupLayout(8, 4)
        assert l.n_groups == 2
        assert l.leaders() == [0, 4]
        assert list(l.members_of(0)) == [0, 1, 2, 3]
        assert list(l.members_of(1)) == [4, 5, 6, 7]
        assert [l.group_of(r) for r in range(8)] == [0] * 4 + [1] * 4
        assert [l.is_leader(r) for r in range(8)] == \
            [True, False, False, False, True, False, False, False]

    def test_ragged_last_group(self):
        """G ∤ world: the last group is short; a one-member group leads
        itself."""
        l = GroupLayout(10, 4)
        assert l.n_groups == 3
        assert l.leaders() == [0, 4, 8]
        assert list(l.members_of(2)) == [8, 9]
        l1 = GroupLayout(9, 4)
        assert list(l1.members_of(2)) == [8]
        assert l1.is_leader(8)

    def test_degenerate_shapes(self):
        # G >= world: one group, rank 0 leads everyone
        l = GroupLayout(4, 8)
        assert l.n_groups == 1 and l.leaders() == [0]
        assert list(l.members_of(0)) == [0, 1, 2, 3]
        # G == 1: every rank is its own leader (pure cross-leader round)
        l1 = GroupLayout(4, 1)
        assert l1.n_groups == 4 and l1.leaders() == [0, 1, 2, 3]
        assert all(l1.is_leader(r) for r in range(4))
        # world == 1
        l2 = GroupLayout(1, 8)
        assert l2.n_groups == 1 and l2.is_leader(0)

    def test_partition_is_total_and_disjoint(self):
        for world, g in [(7, 3), (16, 8), (64, 8), (5, 5), (6, 4)]:
            l = GroupLayout(world, g)
            seen = []
            for gid in range(l.n_groups):
                members = list(l.members_of(gid))
                assert members[0] == l.leader_of(gid)
                for r in members:
                    assert l.group_of(r) == gid
                seen.extend(members)
            assert seen == list(range(world))

    def test_bounds_checked(self):
        l = GroupLayout(4, 2)
        with pytest.raises(ValueError):
            l.group_of(4)
        with pytest.raises(ValueError):
            l.members_of(2)
        with pytest.raises(ValueError):
            GroupLayout(0, 2)
        with pytest.raises(ValueError):
            GroupLayout(4, 0)


# ---------------------------------------------------------------------------
# coordinator ResponseCache: unit lifecycle
# ---------------------------------------------------------------------------

def _req(name="t", shape=(4,), rtype=REQ_ALLREDUCE, **kw):
    out = dict(name=name, request_type=rtype, dtype=0, element_size=4,
               shape=shape, root_rank=-1, group_id=-1, splits=(),
               reduce_op=-1, prescale=1.0, postscale=1.0, splits_crc=0)
    out.update(kw)
    return out


def _resp(name="t", from_cache=False):
    from horovod_tpu.dynamic import Response
    return Response(type=0, tensor_names=[name], from_cache=from_cache)


class TestResponseCacheUnit:
    def test_confirm_then_serve(self):
        rc = ResponseCache(8)
        req = _req()
        assert rc.lookup_confirmed(req) is None
        rc.note_response(req, _resp())  # fresh round: tentative
        assert rc.lookup_confirmed(req) is None
        rc.note_response(req, _resp(from_cache=True))  # AND-bit proof
        served = rc.lookup_confirmed(req)
        assert served is not None and served.tensor_names == ["t"]

    def test_signature_mismatch_never_serves(self):
        rc = ResponseCache(8)
        rc.note_response(_req(), _resp(from_cache=True))
        assert rc.lookup_confirmed(_req(shape=(5,))) is None
        assert rc.lookup_confirmed(_req(prescale=2.0)) is None
        assert rc.lookup_confirmed(_req(reduce_op=1)) is None
        assert rc.lookup_confirmed(_req()) is not None

    def test_uncacheable_types_skipped(self):
        rc = ResponseCache(8)
        for req in (_req(rtype=REQ_ALLGATHER),
                    _req(splits=(1, 2)),
                    _req(rtype=6)):  # barrier
            rc.note_response(req, _resp(from_cache=True))
            assert rc.lookup_confirmed(req) is None
        assert len(rc) == 0

    def test_error_and_fused_responses_not_cached(self):
        from horovod_tpu.dynamic import Response
        rc = ResponseCache(8)
        rc.note_response(_req(), Response(type=8, tensor_names=["t"],
                                          error_message="boom",
                                          from_cache=True))
        assert len(rc) == 0
        rc.note_response(_req(), Response(type=0, from_cache=True,
                                          tensor_names=["t", "u"]))
        assert len(rc) == 0

    def test_lru_capacity(self):
        rc = ResponseCache(2)
        for i in range(3):
            rc.note_response(_req(name=f"n{i}"),
                             _resp(name=f"n{i}", from_cache=True))
        assert len(rc) == 2
        assert rc.lookup_confirmed(_req(name="n0")) is None  # evicted
        assert rc.lookup_confirmed(_req(name="n2")) is not None

    def test_invalidate_and_drop(self):
        rc = ResponseCache(8)
        rc.note_response(_req(), _resp(from_cache=True))
        rc.note_response(_req(name="u"), _resp(name="u", from_cache=True))
        rc.drop_name("u")
        assert rc.lookup_confirmed(_req(name="u")) is None
        assert rc.lookup_confirmed(_req()) is not None
        assert rc.invalidate("test") == 1
        assert rc.lookup_confirmed(_req()) is None
        assert rc.stats()["invalidations"] == 1

    def test_capacity_zero_is_inert(self):
        rc = ResponseCache(0)
        rc.note_response(_req(), _resp(from_cache=True))
        assert rc.lookup_confirmed(_req()) is None
        assert len(rc) == 0


# ---------------------------------------------------------------------------
# hierarchical transport over a real KV server (no mesh programs)
# ---------------------------------------------------------------------------

class TestHierarchicalTransport:
    def _world(self, n, g, cycles=1):
        """Run `cycles` exchange rounds across n rank threads; returns
        each rank's (datas, bitvs, lags, KV round trips) per cycle."""
        from horovod_tpu.negotiation import HierarchicalTransport
        from horovod_tpu.runner.http_kv import KVServer, KVClient, \
            make_secret
        secret = make_secret()
        server = KVServer(secret=secret)
        port = server.start()
        out = [[None] * cycles for _ in range(n)]
        errors = []

        class CountingKV(KVClient):
            ops = 0

            def _request(self, *args, **kwargs):
                self.ops += 1
                return super()._request(*args, **kwargs)

        def rank_main(r):
            try:
                kv = CountingKV("127.0.0.1", port, secret=secret)
                t = HierarchicalTransport(kv, n, r, prefix="t",
                                          group_size=g)
                for c in range(cycles):
                    before = kv.ops
                    datas, bitvs = t.exchange(
                        c, f"req{r}c{c}".encode(), bytes([r]), timeout=30)
                    out[r][c] = (datas, bitvs, dict(t.last_lags),
                                 kv.ops - before)
            except Exception as e:  # pragma: no cover - assertion aid
                errors.append((r, e))

        threads = [threading.Thread(target=rank_main, args=(r,),
                                    daemon=True) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        server.stop()
        assert not errors, errors
        return out

    @pytest.mark.parametrize("n,g", [(4, 2), (5, 2), (6, 4), (3, 8),
                                     (16, 4)])
    def test_every_rank_gets_every_frame(self, n, g):
        layout = GroupLayout(n, g)
        out = self._world(n, g, cycles=2)
        for c in range(2):
            expect_datas = [f"req{r}c{c}".encode() for r in range(n)]
            expect_bits = [bytes([r]) for r in range(n)]
            for r in range(n):
                datas, bitvs, lags, kv_ops = out[r][c]
                assert datas == expect_datas, (r, c, datas)
                assert bitvs == expect_bits, (r, c, bitvs)
                # every member's server-receipt lag is attributed
                assert sorted(lags) == list(range(n)), lags
                assert min(lags.values()) == 0.0
                # a rank's KV round trips a round do not grow with the
                # world: a member puts and gathers, a leader puts and
                # gathers three times over; from the second round on each
                # deletes its own keys of the round before
                leads = layout.is_leader(r)
                assert kv_ops == (5 if leads else 2) + c * (3 if leads
                                                            else 1), \
                    (n, g, r, c, kv_ops)

    def test_matches_flat_transport(self):
        """Flat ↔ hierarchical parity: both transports deliver the
        identical rank-ordered (datas, bitvs) tables."""
        from horovod_tpu.engine_service import KVTransport
        from horovod_tpu.runner.http_kv import KVServer, KVClient, \
            make_secret
        n = 4
        secret = make_secret()
        server = KVServer(secret=secret)
        port = server.start()
        flat = [[None] for _ in range(n)]

        def rank_main(r):
            kv = KVClient("127.0.0.1", port, secret=secret)
            t = KVTransport(kv, n, r, prefix="flat")
            flat[r][0] = t.exchange(0, f"req{r}c0".encode(), bytes([r]),
                                    timeout=30)

        threads = [threading.Thread(target=rank_main, args=(r,),
                                    daemon=True) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        server.stop()
        hier = self._world(n, 2, cycles=1)
        for r in range(n):
            assert flat[r][0][0] == hier[r][0][0]  # datas
            assert flat[r][0][1] == hier[r][0][1]  # bitvs


# ---------------------------------------------------------------------------
# service-level ResponseCache over in-memory lockstep transports
# ---------------------------------------------------------------------------

class _BarrierWorld:
    """In-memory lockstep exchange for N in-process DynamicServices."""

    def __init__(self, n):
        self.n = n
        self.cond = threading.Condition()
        self.frames: dict = {}
        self.closed = False

    def exchange(self, rank, cycle, req, bits, timeout):
        with self.cond:
            fr = self.frames.setdefault(cycle, {})
            fr[rank] = (req, bits)
            self.cond.notify_all()
            end = time.monotonic() + min(timeout, 30.0)
            while len(fr) < self.n:
                if self.closed:
                    raise RuntimeError("barrier world closed")
                if time.monotonic() > end:
                    raise TimeoutError(f"cycle {cycle} incomplete")
                self.cond.wait(0.2)
            self.frames.pop(cycle - 2, None)  # bound memory
            return ([fr[r][0] for r in range(self.n)],
                    [fr[r][1] for r in range(self.n)])

    def close(self):
        with self.cond:
            self.closed = True
            self.cond.notify_all()


class _BarrierTransport:
    def __init__(self, world, rank):
        self.world_mem = world
        self.world_size = world.n
        self.rank = rank

    def exchange(self, cycle, req, bits, timeout):
        return self.world_mem.exchange(self.rank, cycle, req, bits, timeout)


class TestServiceResponseCache:
    def _services(self, monkeypatch, n=2, cache="1", capacities=None):
        from horovod_tpu.engine_service import DynamicService
        monkeypatch.setenv("HVD_RESPONSE_CACHE", cache)
        world = _BarrierWorld(n)
        svcs = [DynamicService(
                    NativeEngine(world_size=n, rank=r,
                                 cache_capacity=(capacities[r]
                                                 if capacities else None)),
                    _BarrierTransport(world, r))
                for r in range(n)]
        return world, svcs

    def _negotiate_all(self, svcs, name, shape=(4,)):
        """All ranks negotiate `name` concurrently; returns responses."""
        results = [None] * len(svcs)
        errors = []

        def one(i):
            try:
                results[i] = svcs[i].negotiate(name, REQ_ALLREDUCE,
                                               shape=shape, timeout=30)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(svcs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(40)
        assert not errors, errors
        return results

    def _teardown(self, world, svcs):
        world.close()
        for s in svcs:
            s.stop()

    def _warm_until_confirmed(self, svcs, name, rounds=12):
        for _ in range(rounds):
            self._negotiate_all(svcs, name)
            if all(s.response_cache_stats()["confirmed"] >= 1
                   for s in svcs):
                return True
        return False

    def test_steady_state_serves_locally(self, monkeypatch):
        world, svcs = self._services(monkeypatch)
        try:
            assert self._warm_until_confirmed(svcs, "g"), \
                [s.response_cache_stats() for s in svcs]
            base = [s.response_cache_stats()["hits"] for s in svcs]
            # a transport that reports a round time has its BUSY rounds
            # counted (engine_service._record_round_metrics)
            for s in svcs:
                s.transport.last_round_s = 0.0
            busy = sum(m.NEGOTIATION_ROUNDS.series().values())
            for _ in range(3):
                resps = self._negotiate_all(svcs, "g")
                assert all(r.tensor_names == ["g"] for r in resps)
            for s, b in zip(svcs, base):
                st = s.response_cache_stats()
                assert st["hits"] == b + 3, st
            # served locally: no request ever waited on the wire
            assert sum(m.NEGOTIATION_ROUNDS.series().values()) == busy
        finally:
            self._teardown(world, svcs)

    def test_knob_epoch_invalidates(self, monkeypatch):
        world, svcs = self._services(monkeypatch)
        try:
            assert self._warm_until_confirmed(svcs, "e")
            self._negotiate_all(svcs, "e")  # served locally
            envs.set_override("CYCLE_TIME", "33")
            try:
                self._negotiate_all(svcs, "e")  # epoch bump: full round
                for s in svcs:
                    st = s.response_cache_stats()
                    assert st["invalidations"] >= 1, st
            finally:
                envs.clear_override("CYCLE_TIME")
        finally:
            self._teardown(world, svcs)

    def test_bit_vector_divergence_forces_renegotiation(self, monkeypatch):
        """A rank whose native cache cannot hold the entry (capacity 0)
        drops the AND-ed bit vector every cycle: responses never come
        back from_cache, no rank ever confirms, and every submission
        keeps taking a full negotiation round — divergence can never be
        served stale."""
        world, svcs = self._services(monkeypatch, capacities=[1024, 0])
        try:
            for _ in range(6):
                resps = self._negotiate_all(svcs, "d")
                assert all(not r.is_error for r in resps)
            for s in svcs:
                st = s.response_cache_stats()
                assert st["hits"] == 0, st
                assert st["confirmed"] == 0, st
                assert st["misses"] > 0, st
        finally:
            self._teardown(world, svcs)

    def test_metadata_change_renegotiates(self, monkeypatch):
        """Same name, new shape (the stream legitimately changed on
        every rank): the signature lookup misses, the new round replaces
        the entry, and the old response is never served."""
        world, svcs = self._services(monkeypatch)
        try:
            assert self._warm_until_confirmed(svcs, "m")
            resps = self._negotiate_all(svcs, "m", shape=(9,))
            assert all(not r.is_error for r in resps)
            # and the new shape can itself reach steady state
            ok = False
            for _ in range(12):
                self._negotiate_all(svcs, "m", shape=(9,))
                if all(s.response_cache_stats()["hits"] > 0 for s in svcs):
                    ok = True
                    break
            assert ok, [s.response_cache_stats() for s in svcs]
        finally:
            self._teardown(world, svcs)

    def test_stop_invalidates(self, monkeypatch):
        """Service stop/reset — the path every pset change and elastic
        re-form takes — drops the cache."""
        world, svcs = self._services(monkeypatch)
        try:
            assert self._warm_until_confirmed(svcs, "s")
        finally:
            self._teardown(world, svcs)
        for s in svcs:
            st = s.response_cache_stats()
            assert st["entries"] == 0, st
            assert st["invalidations"] >= 1, st

    def test_served_path_respects_duplicate_name_guard(self, monkeypatch):
        """A name still registered by an in-flight REAL negotiation must
        raise DuplicateNameError even when the cache could serve it —
        and the in-flight registration must survive untouched (a served
        ticket popping it would orphan the real waiter into the full
        exchange deadline)."""
        from horovod_tpu.dynamic import DuplicateNameError
        from horovod_tpu.engine_service import _Pending
        world, svcs = self._services(monkeypatch)
        try:
            assert self._warm_until_confirmed(svcs, "dup")
            svc = svcs[0]
            fake = _Pending()
            with svc._mu:
                svc._pending["dup"] = fake
            try:
                with pytest.raises(DuplicateNameError):
                    svc.negotiate("dup", REQ_ALLREDUCE, shape=(4,),
                                  timeout=5)
                with svc._mu:
                    assert svc._pending.get("dup") is fake, \
                        "served path touched the in-flight registration"
            finally:
                with svc._mu:
                    svc._pending.pop("dup", None)
        finally:
            self._teardown(world, svcs)

    def test_cache_off_is_flat_protocol(self, monkeypatch):
        world, svcs = self._services(monkeypatch, cache="0")
        try:
            for _ in range(3):
                self._negotiate_all(svcs, "off")
            for s in svcs:
                assert s.response_cache_stats() is None
        finally:
            self._teardown(world, svcs)

    def test_mid_job_flip_on_resize_off_via_knob_epoch(self, monkeypatch):
        """Default-on rollout ergonomics: HVD_RESPONSE_CACHE flips land
        at the next knob-override epoch with NO service rebuild — ON
        starts cold (standard confirmation rounds), RESIZE rebuilds at
        the new capacity, OFF drops every entry and the flat protocol
        keeps negotiating."""
        from horovod_tpu.utils import envs
        world, svcs = self._services(monkeypatch, cache="0")
        # Unpin the env var: an env-set knob is FIXED (overrides lose to
        # the environment) — mid-job flips are an override-epoch feature.
        monkeypatch.delenv("HVD_RESPONSE_CACHE")
        try:
            self._negotiate_all(svcs, "flip")
            for s in svcs:
                assert s._rcache is None
                assert s.response_cache_stats() is None

            envs.set_override("RESPONSE_CACHE", "1")
            assert self._warm_until_confirmed(svcs, "flip"), \
                [s.response_cache_stats() for s in svcs]
            base = [s.response_cache_stats()["hits"] for s in svcs]
            self._negotiate_all(svcs, "flip")
            for s, b in zip(svcs, base):
                assert s.response_cache_stats()["hits"] == b + 1

            envs.set_override("RESPONSE_CACHE", "64")
            self._negotiate_all(svcs, "flip")  # epoch applies at submit
            for s in svcs:
                assert s._rcache is not None and s._rcache.capacity == 64
                # resize = rebuilt cache: counters start from zero (the
                # still-warm NATIVE caches may re-confirm in one round,
                # but nothing has been SERVED from the new cache yet)
                assert s.response_cache_stats()["hits"] == 0

            envs.set_override("RESPONSE_CACHE", "0")
            for _ in range(2):
                resps = self._negotiate_all(svcs, "flip")
                assert all(r.tensor_names == ["flip"] for r in resps)
            for s in svcs:
                assert s._rcache is None
                assert s.response_cache_stats() is None
        finally:
            envs.clear_override("RESPONSE_CACHE")
            self._teardown(world, svcs)

    def test_auto_capacity_tracks_hierarchy_regime(self):
        """`auto` (the default) turns the cache on exactly in the
        pod-scale regime: world > HVD_NEGOTIATION_GROUP_SIZE."""
        from horovod_tpu.utils import envs
        group = envs.negotiation_group_size()
        assert envs.response_cache_capacity(None) == 0
        assert envs.response_cache_capacity(group) == 0
        assert (envs.response_cache_capacity(group * 2)
                == envs.DEFAULT_RESPONSE_CACHE_CAPACITY)


# ---------------------------------------------------------------------------
# loopback worlds: flat ↔ hierarchical parity, cache under join
# ---------------------------------------------------------------------------

class TestLoopbackHierarchy:
    def _run_world(self, extra):
        with hvd.loopback.world(4, extra_env=extra) as w:
            def body():
                r = hvd.rank()
                outs = []
                for step in range(5):
                    o = hvd.allreduce(jnp.full((4,), float(r + 1 + step)),
                                      op=hvd.Sum, name="p")
                    outs.append(np.asarray(o).tobytes())
                    g = hvd.grouped_allreduce(
                        [jnp.full((2,), float(r + i)) for i in range(2)],
                        op=hvd.Sum)
                    outs.extend(np.asarray(x).tobytes() for x in g)
                from horovod_tpu import engine_service
                svc = engine_service.get_service()
                return outs, type(svc.transport).__name__, \
                    (svc.response_cache_stats() or {})
            return [o.result for o in w.run(body)]

    def test_flat_hier_numerics_and_name_parity(self):
        """The same program at world=4 over the flat and the forced
        two-level control plane (with the ResponseCache on) produces
        byte-identical results on every rank — negotiation names are
        stable dispatch-plan names, so steady-state rounds confirm and
        serve from cache."""
        flat = self._run_world({"HVD_HIER_NEGOTIATION": "0",
                                "HVD_RESPONSE_CACHE": "0"})
        hier = self._run_world(dict(HIER_G2, HVD_RESPONSE_CACHE="1"))
        for r, (f, h) in enumerate(zip(flat, hier)):
            assert f[1] == "KVTransport", f[1]
            assert h[1] == "HierarchicalTransport", h[1]
            assert f[0] == h[0], f"rank {r} numerics diverged"
            assert h[2].get("hits", 0) > 0, h[2]

    def test_response_cache_with_join(self):
        """Joins end local serving (docs/negotiation.md "Joins"): JOIN
        itself is never cached, steady-state steps before the join serve
        locally, and the join completes with correct semantics — the
        join latch means an uneven tail AFTER a join always negotiates
        for real, so a joined rank's zero executions are never
        starved."""
        extra = {"HVD_RESPONSE_CACHE": "1"}
        with hvd.loopback.world(2, extra_env=extra) as w:
            def body():
                outs = []
                for step in range(5):
                    o = hvd.allreduce(jnp.ones(4), op=hvd.Sum, name="j")
                    outs.append(float(np.asarray(o)[0]))
                from horovod_tpu import engine_service
                svc = engine_service.get_service()
                hits_before_join = svc.response_cache_stats()["hits"]
                hvd.join()
                # post-join uneven tail: rank 0 runs 2 more collectives
                # against the (re-armed) joined peer — these MUST take
                # real rounds (the latch), so the peer zero-contributes
                if hvd.rank() == 0:
                    for _ in range(2):
                        o = hvd.allreduce(jnp.ones(4), op=hvd.Sum,
                                          name="post")
                        outs.append(float(np.asarray(o)[0]))
                hvd.join()
                st = svc.response_cache_stats()
                return outs, hits_before_join, st["hits"]
            results = [o.result for o in w.run(body, timeout=240)]
        for r, (outs, hits_before, hits_after) in enumerate(results):
            assert outs[:5] == [2.0] * 5
            assert hits_before > 0, "no steady-state serving before join"
            assert hits_after == hits_before, \
                "local serving continued after a join"
        # rank 0's post-join tail reduced against the joined peer's zeros
        assert results[0][0][5:] == [1.0] * 2, results[0]


class TestChaosHierarchy:
    """ISSUE-13 chaos satellite: leader death mid-round surfaces
    PeerFailureError on every survivor within the watchdog budget, and a
    member is promotable on the next (re-formed) round."""

    def test_leader_death_fast_abort(self):
        os.environ["HVD_FAULT_SPEC"] = "worker:crash:rank=2:at_step=3"
        _faults.refresh()
        try:
            extra = dict(HIER_G2, **FAST_HEALTH)
            with hvd.loopback.world(4, extra_env=extra) as w:
                def body():
                    state = hvd.elastic.JaxState(step=0)
                    t0 = time.monotonic()
                    try:
                        for step in range(200):
                            hvd.allreduce(jnp.ones(2), op=hvd.Sum,
                                          name=f"s{step}")
                            state.step += 1
                            state.commit()  # rank 2 (a LEADER) dies here
                        return ("finished", None, None)
                    except PeerFailureError as e:
                        return ("peerfail", time.monotonic() - t0, str(e))

                outs = w.run(body, timeout=120, allow_failures=True)
            dead = next(o for o in outs if o.rank == 2)
            assert isinstance(dead.error, RankKilled), dead
            for o in outs:
                if o.rank == 2:
                    continue
                kind, dt, msg = o.result
                assert kind == "peerfail", o.result
                assert dt < 5.0, f"abort took {dt:.1f}s (budget 5s)"
                assert "rank 2" in msg, msg
        finally:
            os.environ.pop("HVD_FAULT_SPEC", None)
            _faults.refresh()

    def test_leader_death_promotes_member_on_reform(self):
        """Elastic loopback at world=2 with one-rank groups (every rank
        a leader): the leader of group 1 dies, the driver blacklists and
        re-forms at world=1, and the re-derived layout promotes the
        survivor to (sole) leader — training completes."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run
        from horovod_tpu.negotiation.layout import GroupLayout

        disco = FixedHosts({"lb-hA": 1, "lb-hB": 1})
        crashed: list = []
        box: dict = {}

        def body():
            hvd.init()
            state = hvd.elastic.JaxState(step=0, sizes=[])

            @hvd.elastic.run
            def train(state):
                while state.step < 16:
                    out = hvd.allreduce(jnp.ones(1), op=hvd.Sum)
                    state.sizes = state.sizes + [
                        int(float(np.asarray(out).reshape(-1)[0]))]
                    state.step += 1
                    if state.step == 5 and hvd.rank() == 1 and not crashed:
                        crashed.append(1)
                        raise RankKilled(1)
                    state.commit()
                return state.sizes

            sizes = train(state)
            if hvd.rank() == 0:
                layout = GroupLayout(hvd.size(), 1)
                box["sizes"] = sizes
                box["leads_after_reform"] = layout.is_leader(hvd.rank())
            return len(sizes)

        extra = dict(FAST_HEALTH, HVD_HIER_NEGOTIATION="1",
                     HVD_NEGOTIATION_GROUP_SIZE="1")
        results, ok = elastic_run(body, np=2, min_np=1, max_np=2,
                                  discovery=disco, timeout=60,
                                  extra_env=extra)
        assert ok, getattr(results, "error_message", results)
        assert box.get("sizes") is not None
        assert box["sizes"][-1] == 1 and box["sizes"][0] == 2
        assert box["leads_after_reform"] is True


# ---------------------------------------------------------------------------
# world=16 smoke (tier-1) and world=64 (slow; ci.sh second pass)
# ---------------------------------------------------------------------------

def _run_subworld(script: str, devices: int, timeout: float) -> str:
    env = dict(os.environ)
    env.pop("HVD_FAULT_SPEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


_W16_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.utils import envs

n = 16
assert envs.hier_negotiation_enabled(n)  # auto: 16 > default group of 8
with hvd.loopback.world(n, extra_env={"HVD_RESPONSE_CACHE": "1"}) as w:
    def body():
        r = hvd.rank()
        outs = []
        from horovod_tpu import engine_service
        svc = engine_service.get_service()
        for step in range(10):
            if step == 4:  # warm: what follows is the steady state
                warm = svc.response_cache_stats()
            o = hvd.allreduce(jnp.full((4,), float(r + 1)), op=hvd.Sum,
                              name="g")
            outs.append(np.asarray(o))
        st = svc.response_cache_stats()
        g = hvd.grouped_allreduce(
            [jnp.full((2,), float(r)), jnp.ones(3)], op=hvd.Sum)
        return (outs, [np.asarray(x) for x in g],
                type(svc.transport).__name__, warm, st)
    res = w.run(body)
    expect = float(sum(range(1, n + 1)))
    for o in res:
        outs, g, tname, warm, st = o.result
        assert tname == "HierarchicalTransport", tname
        assert all(np.allclose(x, expect) for x in outs), outs
        assert np.allclose(g[0], float(sum(range(n)))), g
        assert np.allclose(g[1], float(n)), g
        hits = st["hits"] - warm["hits"]
        misses = st["misses"] - warm["misses"]
        assert hits / (hits + misses) >= 0.95, (warm, st)
print("W16_OK")
"""


class TestWorld16Smoke:
    def test_world16_hier_cache_smoke(self):
        """Tier-1 world=16 smoke: a fresh interpreter with 16 virtual
        devices runs a 16-rank loopback world on the auto-engaged
        hierarchical control plane with the ResponseCache on — numerics
        exact, steady-state hits recorded."""
        out = _run_subworld(_W16_SCRIPT, devices=16, timeout=420)
        assert "W16_OK" in out, out


_W64_SCRIPT = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, jax.numpy as jnp
import horovod_tpu as hvd

n = 64

def run_world(capture):
    extra = {"HVD_RESPONSE_CACHE": "1",
             "HVD_STEP_CAPTURE": "1" if capture else "0"}
    with hvd.loopback.world(n, extra_env=extra) as w:
        def body():
            r = hvd.rank()
            vals = []
            for step in range(3):
                hvd.step_marker()
                hs = [hvd.allreduce_async(
                          jnp.full((4,), float(r + i + step)),
                          op=hvd.Sum, name=f"t{i}") for i in range(2)]
                vals.append([np.asarray(h.result()).tobytes() for h in hs])
            hvd.step_marker()
            from horovod_tpu import engine_service
            svc = engine_service.get_service()
            return vals, type(svc.transport).__name__
        return [o.result for o in w.run(body)]

on = run_world(True)
off = run_world(False)
for (v_on, t_on), (v_off, t_off) in zip(on, off):
    assert t_on == t_off == "HierarchicalTransport"
    assert v_on == v_off, "capture on/off numerics diverged at world=64"
print("W64_OK")
"""


@pytest.mark.slow
class TestWorld64:
    def test_world64_capture_parity(self):
        """ISSUE-13 acceptance: a world=64 loopback world (8 leader
        groups of 8) completes capture-on/off-parity training steps."""
        out = _run_subworld(_W64_SCRIPT, devices=64, timeout=900)
        assert "W64_OK" in out, out


# ---------------------------------------------------------------------------
# loopback scale fixes (ISSUE-13 satellite)
# ---------------------------------------------------------------------------

class TestLoopbackScaleFixes:
    def test_hub_shards_isolate_slots(self):
        """Unrelated slots rendezvous on unrelated shard conditions; a
        burst of distinct collectives across many threads completes with
        no cross-slot interference and an empty registry after."""
        from horovod_tpu.loopback.hub import LoopbackHub
        hub = LoopbackHub("t")
        n, slots = 4, 24
        results = [[None] * slots for _ in range(n)]

        def rank_main(r):
            for s in range(slots):
                results[r][s] = hub.exchange_compute(
                    ("slot", s), r, n, r + s, lambda vals: sum(vals),
                    timeout=30)

        threads = [threading.Thread(target=rank_main, args=(r,),
                                    daemon=True) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        for r in range(n):
            for s in range(slots):
                assert results[r][s] == sum(range(n)) + n * s
        assert hub.pending() == 0

    def test_hub_fail_all_sweeps_every_shard(self):
        from horovod_tpu.loopback.hub import LoopbackHub
        hub = LoopbackHub("t")
        errs = []

        def waiter(s):
            try:
                hub.exchange(("s", s), 0, 2, "x", timeout=30)
            except RuntimeError as e:
                errs.append(str(e))

        threads = [threading.Thread(target=waiter, args=(s,), daemon=True)
                   for s in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        hub.fail_all(RuntimeError("teardown"))
        for t in threads:
            t.join(10)
        assert len(errs) == 8 and all("teardown" in e for e in errs)
        with pytest.raises(RuntimeError):
            hub.exchange(("s", 99), 0, 2, "x", timeout=1)

    def test_xseq_lru_cap_deterministic(self):
        """The occurrence table is capped per scope and evicts in
        insertion order — the same order on every member rank."""
        from horovod_tpu.loopback import dispatch as lbd
        from horovod_tpu.loopback.context import RankContext

        ctx = RankContext(world=None, rank=0)
        scope = ("addr", "0", "0", (0, 1))
        cap = lbd._XSEQ_CAP
        for i in range(cap + 10):
            assert lbd._next_occurrence(ctx, scope, f"n{i}") == 0
        table = ctx.xseq[scope]
        assert len(table) == cap
        assert "n0" not in table and f"n{cap + 9}" in table
        # a surviving hot name keeps counting
        assert lbd._next_occurrence(ctx, scope, f"n{cap + 9}") == 1

    def test_xseq_stale_scope_prune(self):
        from horovod_tpu.loopback import dispatch as lbd
        from horovod_tpu.loopback.context import RankContext

        ctx = RankContext(world=None, rank=0)
        ctx.env = {"HVD_COORDINATOR_ADDR": "new", "HVD_COORDINATOR_PORT": "2"}
        live = ("new", "2", "0", (0, 1))
        stale = ("old", "1", "0", (0, 1))
        obj_live = ("obj", "new", "2")
        obj_stale = ("obj", "old", "1")
        from horovod_tpu.loopback import context as lbctx
        for s in (live, stale, obj_live, obj_stale):
            ctx.xseq[s] = {"": 1}
        with lbctx.activate(ctx):
            lbd.prune_stale_scopes(ctx)
        assert set(ctx.xseq) == {live, obj_live}

    def test_loopback_timeout_scales_with_world(self, monkeypatch):
        from horovod_tpu.loopback import dispatch as lbd
        monkeypatch.delenv("HVD_LOOPBACK_TIMEOUT", raising=False)
        # outside any initialized runtime the small-world default holds
        assert lbd._timeout_s() == lbd.DEFAULT_LOOPBACK_TIMEOUT_S
        monkeypatch.setenv("HVD_LOOPBACK_TIMEOUT", "7.5")
        assert lbd._timeout_s() == 7.5
