"""Elastic churn as a measured scenario (ISSUE 14; docs/elastic.md).

Scripted membership change through the ``HVD_FAULT_SPEC`` grammar
(``worker:add/remove/preempt``), warm re-form (shape-keyed dispatch-plan
shelves + coordinator ResponseCache re-arm), recovery SLOs, and the
typed ResponseCacheJoinError for the pre-join-latch serving race.
"""

import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu import _native
from horovod_tpu.dynamic import REQ_ALLREDUCE, REQ_JOIN, NativeEngine
from horovod_tpu.exceptions import ResponseCacheJoinError
from horovod_tpu.utils import envs
from horovod_tpu.utils import faults as _faults

pytestmark = pytest.mark.skipif(
    not _native.available(), reason="native engine unavailable")

FAST_HEALTH = {"HVD_HEALTH_INTERVAL": "0.2", "HVD_HEALTH_TIMEOUT": "2",
               "HVD_RESPONSE_CACHE": "1"}


@pytest.fixture
def fault_spec():
    """Install an HVD_FAULT_SPEC for the test and always clear it."""
    import os

    def install(spec):
        os.environ["HVD_FAULT_SPEC"] = spec
        _faults.refresh()

    yield install
    import os
    os.environ.pop("HVD_FAULT_SPEC", None)
    _faults.refresh()
    _faults.clear_membership_handler()


# ---------------------------------------------------------------------------
# grammar
# ---------------------------------------------------------------------------

class TestChurnGrammar:
    def test_membership_actions_parse(self):
        rules = _faults.parse_spec(
            "worker:add:at_step=3:count=2;"
            "worker:remove:rank=1:at_step=5;"
            "worker:preempt:rank=2:at_step=7:grace=12.5")
        add, rem, pre = rules
        assert (add.action, add.count, add.times) == ("add", 2, 1)
        assert (rem.action, rem.rank, rem.times) == ("remove", 1, 1)
        assert (pre.action, pre.grace_s) == ("preempt", 12.5)

    def test_membership_only_at_worker_site(self):
        with pytest.raises(_faults.FaultSpecError,
                           match="only legal at the 'worker' site"):
            _faults.parse_spec("kv.put:add:count=1")

    def test_bad_count_and_grace_rejected(self):
        with pytest.raises(_faults.FaultSpecError, match="count"):
            _faults.parse_spec("worker:add:count=0")
        with pytest.raises(_faults.FaultSpecError, match="grace"):
            _faults.parse_spec("worker:preempt:grace=-1")

    def test_at_round_parses_on_any_action(self):
        (r,) = _faults.parse_spec("worker:crash:rank=0:at_round=2")
        assert r.at_round == 2

    def test_at_round_filter_matches_elastic_round(self, fault_spec,
                                                   monkeypatch):
        """A rule keyed on at_round fires only in that elastic round —
        the deterministic way to target re-form boundaries (ISSUE 14
        satellite: at_step counts commits, which reset meaning across
        worlds; at_round does not)."""
        fired = []
        fault_spec("worker:remove:at_round=3")
        _faults.set_membership_handler(
            lambda action, rule: fired.append(action))
        monkeypatch.setenv("HVD_ELASTIC_ROUND", "2")
        _faults.inject("worker", rank=0, step=1)
        assert fired == []
        monkeypatch.setenv("HVD_ELASTIC_ROUND", "3")
        _faults.inject("worker", rank=0, step=2)
        assert fired == ["remove"]
        # membership actions default times=1: the schedule fires once
        _faults.inject("worker", rank=0, step=3)
        assert fired == ["remove"]

    def test_membership_without_handler_noops(self, fault_spec):
        fault_spec("worker:add:count=1")
        _faults.clear_membership_handler()
        _faults.inject("worker", rank=0, step=1)  # must not raise

    def test_has_membership_rules(self, fault_spec):
        fault_spec("kv.put:error:p=0.5")
        assert not _faults.has_membership_rules()
        fault_spec("kv.put:error:p=0.5;worker:preempt:rank=0:at_step=2")
        assert _faults.has_membership_rules()


# ---------------------------------------------------------------------------
# scripted churn end to end (loopback elastic)
# ---------------------------------------------------------------------------

def _train_body(box, total_steps, probe_name="w", sleep_s=0.03,
                collect_stats=False, until_transitions=0):
    # With ``until_transitions`` set, ``total_steps`` is a MINIMUM and
    # the body runs until that many world transitions have been
    # OBSERVED (hard-capped at 4x) — a fixed step budget races the
    # discovery/notify latency of the last scheduled event on a loaded
    # box (the ISSUE-15 scale tests hit exactly this). The transition
    # count lives on committed state and derives from the broadcast
    # world value, so every rank exits at the same commit.
    cap = total_steps * (4 if until_transitions else 1)

    def body():
        hvd.init()
        state = hvd.elastic.JaxState(step=0, log=[], trans=0, lastw=0)

        @hvd.elastic.run
        def train(state):
            from horovod_tpu import metrics as _metrics
            from horovod_tpu.ops import dispatch_cache
            while state.step < cap and not (
                    until_transitions and state.step >= total_steps
                    and state.trans >= until_transitions):
                out = hvd.allreduce(jnp.arange(4.0) + 1.0, op=hvd.Sum,
                                    name=probe_name)
                world = int(float(np.asarray(out).reshape(-1)[0]))
                if state.lastw and world != state.lastw:
                    state.trans += 1
                state.lastw = world
                if hvd.rank() == 0:
                    row = (state.step, world,
                           float(np.asarray(out).reshape(-1)[1]))
                    if collect_stats:
                        st = dispatch_cache.stats()
                        row = row + (st["warm_reuses"], int(
                            _metrics.ELASTIC_STEPS_LOST.value()))
                    state.log = state.log + [row]
                state.step += 1
                time.sleep(sleep_s)
                state.commit()
            return state.log

        log = train(state)
        if hvd.rank() == 0:
            box["log"] = log
        return 0

    return body


class TestScriptedChurn:
    def test_grow_2_to_4_numerics_parity(self, fault_spec):
        """Mid-training scale-up 2->4: after the re-form every logged
        allreduce equals exactly what an uninterrupted world-4 run
        computes, and committed steps never replay."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        fault_spec("worker:add:rank=0:at_step=2:count=2")
        disco = FixedHosts({"g2a": 1, "g2b": 1})
        box = {}
        results, ok = elastic_run(
            _train_body(box, 60), np=2, min_np=2, max_np=4,
            discovery=disco, timeout=90, extra_env=FAST_HEALTH)
        assert ok, results.error_message
        log = box["log"]
        worlds = [w for (_s, w, _p) in log]
        assert worlds[0] == 2 and worlds[-1] == 4, worlds
        assert sorted(set(worlds)) == [2, 4], worlds
        # numerics parity vs an uninterrupted run at the final world:
        # element 1 of sum(arange(4)+1) over `world` identical
        # contributions is exactly 2*world at every step
        for step, world, p1 in log:
            assert p1 == pytest.approx(2.0 * world), (step, world, p1)
        steps = [s for (s, _w, _p) in log]
        assert steps == sorted(set(steps)), "committed steps replayed"

    def test_shrink_4_to_2_numerics_parity(self, fault_spec):
        """Mid-training scale-down 4->2 via two scheduled removals."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        fault_spec("worker:remove:rank=3:at_step=2;"
                   "worker:remove:rank=2:at_step=14")
        disco = FixedHosts({f"s4{i}": 1 for i in range(4)})
        box = {}
        results, ok = elastic_run(
            _train_body(box, 40), np=4, min_np=2, max_np=4,
            discovery=disco, timeout=120, extra_env=FAST_HEALTH)
        assert ok, results.error_message
        log = box["log"]
        worlds = [w for (_s, w, _p) in log]
        assert worlds[0] == 4 and worlds[-1] == 2, worlds
        assert set(worlds) >= {4, 2}, worlds
        for step, world, p1 in log:
            assert p1 == pytest.approx(2.0 * world), (step, world, p1)

    def test_warm_reform_reuses_plans(self, fault_spec):
        """A resize back to a previously-seen shape must graft shelved
        dispatch plans: `dispatch_cache_stats()["warm_reuses"]` > 0
        after the second re-form (ISSUE 14 acceptance)."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        # the add is ROUND-keyed (fires inside the post-shrink round),
        # not step-keyed: on a loaded box a step-keyed add could land in
        # the same discovery window as the preempt's host removal and
        # merge into one 3->3 re-form that never exposes the 2-world
        # shape this test is about — and the body runs until both
        # transitions are observed rather than a fixed step budget
        # (the pre-existing flake this ordering race caused)
        fault_spec("worker:preempt:rank=2:at_round=1:at_step=4:grace=30;"
                   "worker:add:rank=0:at_round=2:after=5")
        disco = FixedHosts({"w3a": 1, "w3b": 1, "w3c": 1})
        box = {}
        results, ok = elastic_run(
            _train_body(box, 30, collect_stats=True,
                        until_transitions=2), np=3, min_np=2,
            max_np=3, discovery=disco, timeout=120, extra_env=FAST_HEALTH)
        assert ok, results.error_message
        log = box["log"]
        worlds = [w for row in log for w in (row[1],)]
        assert 2 in worlds and worlds[-1] == 3, worlds
        # the grow back to world=3 re-forms into a shape both survivors
        # shelved at the shrink: the first post-re-form plan build must
        # graft a shelved compiled stage
        assert log[-1][3] > 0, f"no warm plan reuse: {log[-1]}"

    def test_preempt_loses_zero_steps_crash_loses_at_most_one(
            self, fault_spec):
        """The ISSUE 14 SLO pair: a graceful preemption (drain + grace +
        slot-lost exit) rolls back nothing, while an abrupt kill loses
        at most the one in-flight step (commit-per-step)."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        # the crash is keyed on the ROUND, not a step count: under a
        # loaded box the preempt's re-form can take arbitrarily many
        # step-times, and a step-keyed crash racing it merges the two
        # transitions — at_round=2:after=5 fires deterministically on
        # rank 1's 6th commit INSIDE the post-preempt world
        fault_spec("worker:preempt:rank=2:at_step=4:grace=30;"
                   "worker:crash:rank=1:at_round=2:after=5")
        disco = FixedHosts({"pz0": 1, "pz1": 1, "pz2": 1})
        box = {}
        # min_np=1: after the crash only one host remains un-blacklisted,
        # and the job must finish there rather than wait for slots
        results, ok = elastic_run(
            _train_body(box, 40, collect_stats=True), np=3, min_np=1,
            max_np=3, discovery=disco, timeout=120, extra_env=FAST_HEALTH)
        assert ok, results.error_message
        log = box["log"]
        worlds = [row[1] for row in log]
        assert worlds[0] == 3 and worlds[-1] == 1, worlds
        # per-transition steps-lost deltas off the registry counter
        lost_at = {}
        for i in range(1, len(log)):
            if log[i][1] != log[i - 1][1]:
                lost_at[(log[i - 1][1], log[i][1])] = \
                    log[i][4] - log[i - 1][4]
        # preempt: 3 -> 2 with zero rolled-back steps; crash: 2 -> re-form
        # (2, with the dead host replaced or 2->2 restore) loses <= 1.
        assert lost_at, log
        assert (3, 2) in lost_at, (lost_at, worlds)  # preempt re-formed
        assert lost_at[(3, 2)] == 0, (lost_at, log)
        total_lost = log[-1][4]
        assert total_lost <= 1, (total_lost, lost_at)
        # committed steps never replay
        steps = [row[0] for row in log]
        assert steps == sorted(set(steps)), "committed steps replayed"


# ---------------------------------------------------------------------------
# driver-side grace + stale-report hygiene
# ---------------------------------------------------------------------------

class TestDriverChurnPlumbing:
    def test_fixed_hosts_mutators(self):
        from horovod_tpu.elastic.discovery import FixedHosts
        fh = FixedHosts({"a": 1})
        fh.add_hosts({"b": 2})
        assert fh.find_available_hosts_and_slots() == {"a": 1, "b": 2}
        assert fh.remove_host("a") is True
        assert fh.remove_host("a") is False
        assert fh.find_available_hosts_and_slots() == {"b": 2}

    def test_scripted_churn_handler(self, monkeypatch):
        from horovod_tpu.elastic.discovery import FixedHosts, ScriptedChurn
        fh = FixedHosts({"h0": 1})
        events = []
        churn = ScriptedChurn(fh, events=events)
        (add,) = _faults.parse_spec("worker:add:count=2")
        churn("add", add)
        hosts = fh.find_available_hosts_and_slots()
        assert hosts == {"h0": 1, "churn0": 1, "churn1": 1}
        monkeypatch.setenv("HVD_HOSTNAME", "churn0")

        class _Driver:
            grace = None

            def set_stale_grace(self, host, s):
                _Driver.grace = (host, s)

        churn.attach_driver(_Driver())
        (pre,) = _faults.parse_spec("worker:preempt:grace=7")
        churn("preempt", pre)
        assert _Driver.grace == ("churn0", 7.0)
        assert "churn0" not in fh.find_available_hosts_and_slots()
        assert [e[1] for e in events] == ["add", "preempt"]

    def test_stale_round_peer_report_ignored(self):
        """A peer-failure report resolved against a superseded round's
        rank numbering must not blacklist the innocent successor that
        inherited the rank number (the scripted-churn misattribution)."""
        import pickle

        from horovod_tpu.elastic import driver as drv

        class _KV(dict):
            def put(self, k, v):
                self[k] = v

            def get(self, k):
                return dict.get(self, k)

        recorded = []

        class _Registry:
            def record_failure(self, host, slot):
                recorded.append((host, slot))

        d = drv.ElasticDriver.__new__(drv.ElasticDriver)
        d._rendezvous = drv.ElasticRendezvous(_KV())
        d._rendezvous._round = 2
        d._worker_registry = _Registry()
        d._result_threads = []
        # round 1 had rank 2 on oldhost; round 2 reassigned rank 2 to
        # newhost (the replacement)
        d._rendezvous.kv.put(
            drv.ROUND_SPEC_KEY.format(1),
            pickle.dumps({"round": 1, "slots": [
                {"hostname": "oldhost", "rank": 2, "size": 3,
                 "local_rank": 0, "local_size": 1, "cross_rank": 2,
                 "cross_size": 3}]}))
        d._rank_assignments = {2: drv.slot_from_dict(
            {"hostname": "newhost", "rank": 2, "size": 3,
             "local_rank": 0, "local_size": 1, "cross_rank": 2,
             "cross_size": 3})}
        d.record_peer_failure(2, "silence", round_id=1)
        assert recorded == []  # stale report: hostnames differ -> ignored
        # a CURRENT-round report still records
        d.record_peer_failure(2, "silence", round_id=2)
        for t in d._result_threads:
            t.join(5)
        assert recorded == [("newhost", 0)]

    def test_resume_after_shutdown_noops(self):
        from horovod_tpu.elastic import driver as drv
        d = drv.ElasticDriver.__new__(drv.ElasticDriver)
        d._shutdown = threading.Event()
        d._shutdown.set()
        d.resume()  # must not raise / touch worker machinery


# ---------------------------------------------------------------------------
# ResponseCache: warm shelf mechanics + join-race typed error
# ---------------------------------------------------------------------------

class TestResponseCacheWarm:
    def _entry(self, name="t", world=2):
        from horovod_tpu.dynamic import Response
        req = {"name": name, "request_type": REQ_ALLREDUCE, "dtype": 0,
               "element_size": 4, "shape": (4,)}
        resp = Response(type=REQ_ALLREDUCE, tensor_names=[name])
        return req, resp

    def test_warm_restore_confirm_and_serve_gate(self):
        from horovod_tpu.negotiation.response_cache import ResponseCache
        rc = ResponseCache(8)
        req, resp = self._entry()
        rc.note_response(req, resp)
        exported = rc.export_entries()
        assert len(exported) == 0  # unconfirmed entries don't shelve
        resp.from_cache = True
        rc.note_response(req, resp)
        exported = rc.export_entries()
        assert len(exported) == 1

        rc2 = ResponseCache(8)
        assert rc2.restore_warm(exported) == 1
        assert rc2.warm_count() == 1
        # warm entries are present but NOT serveable pre-confirmation
        assert rc2.lookup_confirmed(req) is None
        assert rc2.confirm_warm() == 1
        assert rc2.warm_count() == 0
        assert rc2.lookup_confirmed(req) is not None

    def test_warm_digest_agreement_and_empty_marker(self):
        from horovod_tpu.negotiation.response_cache import ResponseCache
        req, resp = self._entry()
        resp.from_cache = True
        a, b, fresh = ResponseCache(8), ResponseCache(8), ResponseCache(8)
        a.note_response(req, resp)
        b.note_response(req, resp)
        a2, b2 = ResponseCache(8), ResponseCache(8)
        a2.restore_warm(a.export_entries())
        b2.restore_warm(b.export_entries())
        assert a2.warm_digest() == b2.warm_digest()
        assert fresh.warm_digest() == b"\x00" * 8  # the fresh-member veto
        assert a2.warm_digest() != fresh.warm_digest()
        assert b2.drop_warm() == 1
        assert b2.warm_count() == 0

    def test_shelf_lru_and_take(self):
        from horovod_tpu.negotiation import response_cache as rcm
        rcm.clear_shelf()
        try:
            rcm.shelve(("s", "global", 2, 0), [("n", ("sig",), None)])
            assert rcm.take_shelved(("s", "global", 2, 0)) is not None
            assert rcm.take_shelved(("s", "global", 2, 0)) is None
        finally:
            rcm.clear_shelf()


class _BarrierWorld:
    """In-memory lockstep exchange for N in-process DynamicServices
    (the test_negotiation fixture, re-used for the join-race test)."""

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
            self.frames.pop(cycle - 2, None)
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


class TestResponseCacheJoinRace:
    def _services(self, monkeypatch, n=2):
        from horovod_tpu.engine_service import DynamicService
        monkeypatch.setenv("HVD_RESPONSE_CACHE", "1")
        world = _BarrierWorld(n)
        svcs = [DynamicService(NativeEngine(world_size=n, rank=r),
                               _BarrierTransport(world, r))
                for r in range(n)]
        return world, svcs

    def _negotiate_all(self, svcs, name):
        results = [None] * len(svcs)
        errors = []

        def one(i):
            try:
                results[i] = svcs[i].negotiate(name, REQ_ALLREDUCE,
                                               shape=(4,), timeout=30)
            except Exception as e:
                errors.append(e)

        ts = [threading.Thread(target=one, args=(i,), daemon=True)
              for i in range(len(svcs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(40)
        assert not errors, errors
        return results

    def test_pre_join_serve_raises_typed_error(self, monkeypatch):
        """Rank 0 serves a batch locally from its confirmed coordinator
        cache in the same window rank 1's JOIN goes to the wire: the
        cycle that first observes the JOIN must fail rank 0's service
        with ResponseCacheJoinError NAMING rank 1 — not leave the
        locally-served, never-scheduled collective to burn the exchange
        deadline (ROADMAP protocol follow-on (a))."""
        world, svcs = self._services(monkeypatch)
        try:
            # steady state: confirm + begin serving locally
            for _ in range(12):
                self._negotiate_all(svcs, "g")
                if all(s.response_cache_stats()["confirmed"] >= 1
                       for s in svcs):
                    break
            assert all(s.response_cache_stats()["confirmed"] >= 1
                       for s in svcs)
            self._negotiate_all(svcs, "g")  # served locally everywhere

            # rank 1 joins while rank 0 serves the same window locally
            join_exc = []

            def joiner():
                try:
                    svcs[1].join("j.join", timeout=20)
                except Exception as e:  # the abort fails the join too
                    join_exc.append(e)

            jt = threading.Thread(target=joiner, daemon=True)
            jt.start()
            # rank 0's local serve needs no peer: it returns immediately
            t0 = time.monotonic()
            ticket = svcs[0].negotiate_many_submit([dict(
                name="g", request_type=REQ_ALLREDUCE, dtype=0,
                element_size=4, shape=(4,), root_rank=-1, group_id=-1,
                splits=(), reduce_op=-1, prescale=1.0, postscale=1.0,
                splits_crc=0)])
            assert ticket.served, "serve did not happen pre-join"
            svcs[0].negotiate_many_wait(ticket, timeout=30)
            # rank 0's next REAL negotiation observes the failure fast
            with pytest.raises(ResponseCacheJoinError) as ei:
                for _ in range(40):
                    svcs[0].negotiate(f"after.{_}", REQ_ALLREDUCE,
                                      shape=(4,), timeout=30)
                    time.sleep(0.05)
            assert time.monotonic() - t0 < 20.0
            assert "rank 1" in str(ei.value)
            assert ei.value.joining_rank == 1
            jt.join(10)
        finally:
            world.close()
            for s in svcs:
                s.stop()

    def test_join_without_serves_latches_quietly(self, monkeypatch):
        """A JOIN observed with no pre-join local serves just latches —
        no typed error, the normal join semantics."""
        world, svcs = self._services(monkeypatch)
        try:
            self._negotiate_all(svcs, "q")  # real rounds only, no serving
            results = [None, None]

            def joiner(i):
                results[i] = svcs[i].join(f"q.join.{i}", timeout=30)

            ts = [threading.Thread(target=joiner, args=(i,), daemon=True)
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(40)
            assert results[0] is not None and results[1] is not None
            for s in svcs:
                assert s._failure is None
        finally:
            world.close()
            for s in svcs:
                s.stop()


# ---------------------------------------------------------------------------
# request-frame parsing (the join-race scanner's wire twin)
# ---------------------------------------------------------------------------

class TestParseRequests:
    def test_roundtrip_via_native_pop(self):
        from horovod_tpu.dynamic import parse_requests
        eng = NativeEngine(world_size=2, rank=1)
        eng.enqueue("a", REQ_ALLREDUCE, dtype=1, element_size=4,
                    shape=(3, 2), reduce_op=0)
        eng.enqueue("b.join", REQ_JOIN)
        reqs = parse_requests(eng.pop_requests())
        assert [(r["rank"], r["request_type"], r["name"]) for r in reqs] \
            == [(1, REQ_ALLREDUCE, "a"), (1, REQ_JOIN, "b.join")]

    def test_empty(self):
        from horovod_tpu.dynamic import parse_requests
        assert parse_requests(b"") == []


# ---------------------------------------------------------------------------
# churn at scale (ISSUE 15: ROADMAP elastic follow-ons (a)/(d))
# ---------------------------------------------------------------------------

_REPO = str(pathlib.Path(__file__).resolve().parents[1])

# One full churn cycle at world N in a fresh interpreter: preempt
# N -> N-1 (cold: no shelf for either shape yet), scripted add back to
# N (the survivors re-form into the shape they shelved at the preempt —
# plan grafts; the fresh replacement's empty digest vetoes the response
# re-arm, by design), then preempt N -> N-1 again (every survivor
# shelved shape N-1 at the grow's teardown: plans graft AND the warm
# digest round re-arms local serving). Past world 4 this exercises the
# shelf sizing, the hierarchical beat/negotiation path (auto-on above
# one leader group), and — with CHURN_CAPTURE=1 — the svc StepPlan
# graft the ROADMAP flagged as untested past world 4.
_SCALE_SCRIPT = r"""
import os, json, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu import metrics as _metrics
from horovod_tpu.elastic.discovery import FixedHosts
from horovod_tpu.loopback import elastic_run
from horovod_tpu.utils import faults

N = int(os.environ["CHURN_WORLD"])
CAPTURE = os.environ.get("CHURN_CAPTURE", "0") == "1"
E1, EK = 4, 5
# The bodies run until the full shrink->grow->shrink cycle has been
# OBSERVED (the discovery poll + notify poll put ~8 commit-times of
# latency between an event firing and its re-form landing at this
# pacing — a fixed step budget either races the last transition or
# pads every run), with a hard cap so a wedged schedule still fails
# fast. The transition count lives on committed state and derives from
# the broadcast world value, so every rank exits the loop at the same
# commit (rank-symmetric by construction).
MIN_STEPS, HARD_CAP = 30, 140
# steps watched after a re-form: the busy negotiation rounds rank 0
# spends over them are the count a cold re-form pays and a warm one
# does not
WINDOW = 4

os.environ["HVD_FAULT_SPEC"] = (
    f"worker:preempt:rank={N-1}:at_round=1:at_step={E1}:grace=60;"
    f"worker:add:rank=0:at_round=2:after={EK};"
    f"worker:preempt:rank={N-1}:at_round=3:after=3:grace=60")
faults.refresh()

extra = {"HVD_RESPONSE_CACHE": "1", "HVD_HEALTH_INTERVAL": "0.3",
         "HVD_HEALTH_TIMEOUT": "8"}
if CAPTURE:
    extra["HVD_STEP_CAPTURE"] = "1"

disco = FixedHosts({f"h{i}": 1 for i in range(N)})
box = {}


def warm_counts():
    out = {"plan": 0, "step": 0, "response": 0}
    for li, v in _metrics.ELASTIC_WARM_REUSE.series().items():
        k = dict(li).get("kind")
        if k in out:
            out[k] = int(v)
    return out


def body():
    hvd.init()
    state = hvd.elastic.JaxState(step=0, log=[], trans=0, lastw=0,
                                 since=0)

    @hvd.elastic.run
    def train(state):
        while state.step < HARD_CAP and not (
                state.step >= MIN_STEPS and state.trans >= 3
                and state.since >= WINDOW):
            if CAPTURE:
                hvd.step_marker()
            # async pair: the fusion/negotiated stream (and, with
            # capture on, the svc StepPlan the warm graft must carry
            # across the re-form)
            h1 = hvd.allreduce_async(jnp.arange(4.0) + 1.0, op=hvd.Sum,
                                     name="wa")
            h2 = hvd.allreduce_async(jnp.ones(2), op=hvd.Sum, name="wb")
            p1 = float(np.asarray(hvd.synchronize(h1)).reshape(-1)[1])
            world = int(float(np.asarray(
                hvd.synchronize(h2)).reshape(-1)[0]))
            # sync call: the eager plan-cache path whose compiled
            # execute stage the shape-keyed shelf grafts (the async
            # stream composes per-negotiation and has no eager plan)
            ws = hvd.allreduce(jnp.arange(4.0) + 1.0, op=hvd.Sum,
                               name="ws")
            assert float(np.asarray(ws).reshape(-1)[1]) == p1
            if state.lastw and world != state.lastw:
                state.trans += 1
                state.since = 0
            else:
                state.since += 1
            state.lastw = world
            if hvd.rank() == 0:
                w = warm_counts()
                state.log = state.log + [(
                    state.step, world, p1, w["plan"], w["step"],
                    w["response"],
                    int(_metrics.ELASTIC_STEPS_LOST.value()),
                    int(sum(_metrics.NEGOTIATION_ROUNDS.series()
                            .values())))]
            state.step += 1
            time.sleep(0.05)
            state.commit()
        return state.log

    log = train(state)
    if hvd.rank() == 0:
        box["log"] = log
    return 0


results, ok = elastic_run(body, np=N, min_np=N - 1, max_np=N,
                          discovery=disco, extra_env=extra)
assert ok, results.error_message
log = box["log"]
worlds = [row[1] for row in log]
assert worlds[0] == N and worlds[-1] == N - 1, worlds
assert sorted(set(worlds)) == [N - 1, N], worlds
# the full cycle: shrink -> grow -> shrink
transitions = [(worlds[i - 1], worlds[i]) for i in range(1, len(worlds))
               if worlds[i] != worlds[i - 1]]
assert transitions == [(N, N - 1), (N - 1, N), (N, N - 1)], transitions
# numerics parity vs an uninterrupted run at each step's world
for row in log:
    assert row[2] == (2.0 * row[1]), row
# committed steps never replay; graceful churn loses zero
steps = [row[0] for row in log]
assert steps == sorted(set(steps)), "committed steps replayed"
assert log[-1][6] == 0, f"graceful churn lost steps: {log[-1]}"
final = {"plan": log[-1][3], "step": log[-1][4], "response": log[-1][5]}
assert final["plan"] > 0, f"no warm plan graft at world {N}: {final}"
assert final["response"] > 0, \
    f"warm digest never re-armed local serving at world {N}: {final}"
if CAPTURE:
    assert final["step"] > 0, \
        f"svc StepPlan never grafted across the re-form: {final}"
# the two shrinks are the same transition, the first into a shape nobody
# had shelved (cold), the second into the shelved one (warm): over the
# same window after it, the warm one spends fewer busy wire rounds
firsts = [i for i in range(1, len(worlds)) if worlds[i] != worlds[i - 1]]
cold, warm = (log[i + WINDOW][7] - log[i][7] for i in (firsts[0], firsts[2]))
assert worlds[firsts[0]:firsts[0] + WINDOW + 1] == [N - 1] * (WINDOW + 1)
assert warm < cold, f"warm re-form paid {warm} busy rounds, cold {cold}"
print("CHURN_SCALE_OK " + json.dumps({"world": N, "warm": final,
                                      "busy_rounds": [cold, warm],
                                      "rows": len(log)}))
"""


def _run_churn_world(world: int, capture: bool, timeout: float) -> str:
    env = dict(os.environ)
    env.pop("HVD_FAULT_SPEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={world}"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["CHURN_WORLD"] = str(world)
    env["CHURN_CAPTURE"] = "1" if capture else "0"
    proc = subprocess.run([sys.executable, "-c", _SCALE_SCRIPT],
                          cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-4000:]}"
    return proc.stdout


class TestChurnAtScale:
    def test_world8_churn_smoke(self):
        """Tier-1 smoke: the full preempt->add->preempt warm cycle at
        world=8 — twice the world the PR-14 suite exercises — with the
        warm digest exchange and shape shelf asserted live."""
        out = _run_churn_world(8, capture=False, timeout=600)
        assert "CHURN_SCALE_OK" in out, out

    @pytest.mark.slow
    def test_world16_churn_capture_full(self):
        """ISSUE 15 acceptance (ROADMAP elastic follow-ons (a)/(d)):
        the full churn cycle at world=16 on the auto-engaged
        hierarchical control plane with step capture on — warm digest
        re-arm, shelf sizing, and the svc StepPlan graft all past
        world 4."""
        out = _run_churn_world(16, capture=True, timeout=1200)
        assert "CHURN_SCALE_OK" in out, out


# ---------------------------------------------------------------------------
# dispatch-cache shelf unit coverage
# ---------------------------------------------------------------------------

class TestDispatchShelf:
    def test_restorable_filter(self):
        from horovod_tpu.ops import dispatch_cache as dc
        plan = dc.DispatchPlan("l", "A", 1, None, lambda t: t)
        assert dc._restorable(("allreduce", "n", ("r",), None, "g", 1),
                              plan)
        assert dc._restorable(("allreduce", "n", ("r",), None, 0, 1),
                              plan)  # the registered GLOBAL set (id 0)
        assert dc._restorable(("allreduce", "n", ("r",), None, (0, 1), 1),
                              plan)  # self-describing rank tuple
        assert not dc._restorable(
            ("allreduce", "n", ("r",), None, 3, 1), plan)  # other ids
        assert not dc._restorable(("k",), dc.UNPLANNABLE)

    def test_stats_expose_warm_fields(self):
        from horovod_tpu.ops import dispatch_cache as dc
        st = dc.stats()
        assert "warm_pool" in st and "warm_reuses" in st
