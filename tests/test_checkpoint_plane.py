"""Checkpoint state plane (docs/checkpoint.md): sharded async snapshots,
torn-tree-free restore, and peer-restore on re-form.

Unit layers run without a world (the plan algebra, the snapshot writer
against a tmpdir, the transfer protocol over an in-memory KV); the
loopback classes run real elastic churn at world>=4 and assert the
ISSUE acceptance: bitwise restore parity vs a no-churn control, zero
steps lost on graceful preempt, and survivor-death failover that never
hangs past the watchdog budget.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import _native
from horovod_tpu import checkpoint as ck
from horovod_tpu.utils import faults as _faults


@pytest.fixture
def fault_spec():
    """Install an HVD_FAULT_SPEC for the test and always clear it."""
    def install(spec):
        os.environ["HVD_FAULT_SPEC"] = spec
        _faults.refresh()

    yield install
    os.environ.pop("HVD_FAULT_SPEC", None)
    _faults.refresh()
    _faults.clear_membership_handler()


# ---------------------------------------------------------------------------
# partition algebra
# ---------------------------------------------------------------------------

class TestLeafRange:
    def test_covers_and_disjoint(self):
        for total in (0, 1, 3, 7, 16, 101):
            for n in (1, 2, 3, 4, 8):
                ranges = [ck.leaf_range(i, n, total) for i in range(n)]
                seen = [x for lo, hi in ranges for x in range(lo, hi)]
                assert seen == list(range(total)), (n, total, ranges)

    def test_balanced(self):
        for total, n in ((10, 3), (7, 4), (16, 5)):
            sizes = [hi - lo for lo, hi in
                     (ck.leaf_range(i, n, total) for i in range(n))]
            assert max(sizes) - min(sizes) <= 1, (total, n, sizes)

    def test_world_change_repartitions(self):
        """4->2 and 2->4: the same leaves fall into recomputed ranges —
        the single partition function is the whole re-partitioning
        story (survivors serve overlapping ranges of their live tree)."""
        four = [ck.leaf_range(i, 4, 10) for i in range(4)]
        two = [ck.leaf_range(i, 2, 10) for i in range(2)]
        assert four == [(0, 3), (3, 6), (6, 8), (8, 10)]
        assert two == [(0, 5), (5, 10)]
        # each 2-way range overlaps multiple 4-way shards and vice versa
        assert two[0][1] > four[0][1]


# ---------------------------------------------------------------------------
# restore-plan algebra
# ---------------------------------------------------------------------------

def _blob(rank, commits, n_leaves=4, struct=7):
    return {"rank": rank, "commits": commits, "n_leaves": n_leaves,
            "struct": struct, "manifest": -1}


class TestRestorePlan:
    def test_all_agree_no_needy(self):
        plan = ck.make_restore_plan(
            [_blob(0, 5), _blob(1, 5), _blob(2, 5)], world=3)
        assert (plan.survivors, plan.needy) == ((0, 1, 2), ())
        assert plan.degraded_reason is None and not plan.fresh

    def test_fresh_world(self):
        plan = ck.make_restore_plan(
            [_blob(0, 0), _blob(1, 0)], world=2)
        assert plan.fresh

    def test_joiner_is_needy(self):
        plan = ck.make_restore_plan(
            [_blob(0, 5), _blob(1, 5), _blob(2, 0)], world=3)
        assert plan.survivors == (0, 1) and plan.needy == (2,)
        assert plan.step == 5 and plan.degraded_reason is None

    def test_quorum_degrades(self):
        plan = ck.make_restore_plan(
            [_blob(0, 5), _blob(1, 0)], world=2, quorum=2)
        assert plan.degraded_reason == "quorum"

    def test_split_brain_degrades(self):
        """Equally-committed survivors with different structures: no
        consistent manifest exists to serve from."""
        plan = ck.make_restore_plan(
            [_blob(0, 5, struct=1), _blob(1, 5, struct=2)], world=2)
        assert plan.degraded_reason == "quorum"

    def test_structure_mismatch_degrades(self):
        plan = ck.make_restore_plan(
            [_blob(0, 5), _blob(1, 5), _blob(2, 2, n_leaves=9)], world=3)
        assert plan.degraded_reason == "structure"

    def test_transfer_schedule_and_failover(self):
        plan = ck.make_restore_plan(
            [_blob(0, 5), _blob(1, 5), _blob(2, 0), _blob(3, 0)],
            world=4)
        t0 = plan.transfers(0)
        # every needy rank pulls every survivor range, owner = range owner
        assert t0 == [(2, 0, 0, 0, 2), (2, 1, 1, 2, 4),
                      (3, 0, 0, 0, 2), (3, 1, 1, 2, 4)]
        # attempt 1 rotates each failed pull to the NEXT survivor
        t1 = plan.transfers(1, [(2, 0), (3, 1)])
        assert t1 == [(2, 1, 0, 0, 2), (3, 0, 1, 2, 4)]


# ---------------------------------------------------------------------------
# snapshot writer + on-disk restore (no world needed)
# ---------------------------------------------------------------------------

def _tree(v):
    return {"w": np.full((3, 2), float(v)),
            "opt": {"m": np.arange(4.0) * v, "count": np.int64(v)}}


class _FakeState:
    def __init__(self):
        self._commits = 0
        self._saved_state = {}

    def commit_tree(self, plane, v):
        self._commits += 1
        self._saved_state = _tree(v)
        plane.note_commit(self)


def _wait_for(pred, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    return False


def _assert_trees_equal(a, b):
    import jax
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestSnapshotPlane:
    def _plane(self, tmp_path, interval=1):
        return ck.StatePlane(str(tmp_path), rank=0, world=1,
                             interval=interval)

    def test_snapshot_round_trip(self, tmp_path):
        plane = self._plane(tmp_path)
        st = _FakeState()
        try:
            st.commit_tree(plane, 1)
            assert _wait_for(lambda: plane.last_manifest_step == 1)
        finally:
            plane.stop()
        with open(ck.latest_path(str(tmp_path))) as f:
            assert int(f.read()) == 1
        got = ck.restore_or_none(str(tmp_path), target=_tree(0))
        assert got is not None
        _assert_trees_equal(got, _tree(1))

    def test_interval_and_latest_wins(self, tmp_path):
        plane = self._plane(tmp_path, interval=2)
        st = _FakeState()
        try:
            for v in range(1, 7):
                st.commit_tree(plane, v)
            assert _wait_for(lambda: plane.last_manifest_step == 6)
        finally:
            plane.stop()
        steps = sorted(int(n.split("-")[1].split(".")[0])
                       for n in os.listdir(str(tmp_path))
                       if n.startswith("manifest-"))
        assert all(s % 2 == 0 for s in steps), steps
        got = ck.sharded_restore_or_none(str(tmp_path), target=_tree(0))
        _assert_trees_equal(got, _tree(6))

    def test_torn_write_restores_previous_step(self, tmp_path,
                                               fault_spec):
        """A rank killed mid-snapshot (ckpt.write fault) leaves a torn
        step directory: no sidecar, no manifest, `latest` unmoved —
        restore_or_none returns the previous complete step."""
        fault_spec("ckpt.write:error:at_step=2")
        plane = self._plane(tmp_path)
        st = _FakeState()
        try:
            st.commit_tree(plane, 1)
            assert _wait_for(lambda: plane.last_manifest_step == 1)
            st.commit_tree(plane, 2)  # this snapshot is killed
            st.commit_tree(plane, 3)
            assert _wait_for(lambda: plane.last_manifest_step == 3)
        finally:
            plane.stop()
        assert not os.path.exists(
            ck.manifest_path(str(tmp_path), 2))
        got = ck.sharded_restore_or_none(str(tmp_path), step=2,
                                         target=_tree(0))
        assert got is None  # step 2 is torn: never served
        _assert_trees_equal(
            ck.restore_or_none(str(tmp_path), target=_tree(0)), _tree(3))

    def test_corrupt_shard_falls_back_to_older_manifest(self, tmp_path):
        plane = self._plane(tmp_path)
        st = _FakeState()
        try:
            st.commit_tree(plane, 1)
            assert _wait_for(lambda: plane.last_manifest_step == 1)
            st.commit_tree(plane, 2)
            assert _wait_for(lambda: plane.last_manifest_step == 2)
        finally:
            plane.stop()
        # flip bytes in step 2's shard: its digest no longer verifies
        sdir = ck.step_dir(str(tmp_path), 2)
        shard = [n for n in os.listdir(sdir) if n.endswith(".bin")][0]
        with open(os.path.join(sdir, shard), "r+b") as f:
            f.write(b"\xff\xff\xff\xff")
        got = ck.restore_or_none(str(tmp_path), target=_tree(0))
        _assert_trees_equal(got, _tree(1))

    def test_restore_or_none_empty_dir(self, tmp_path):
        assert ck.restore_or_none(str(tmp_path)) is None
        assert ck.restore_or_none(
            str(tmp_path / "never-created")) is None

    def test_stop_is_idempotent_and_joins(self, tmp_path):
        plane = self._plane(tmp_path)
        st = _FakeState()
        st.commit_tree(plane, 1)
        plane.stop()
        plane.stop()
        st.commit_tree(plane, 2)  # post-stop commits are dropped
        assert plane._thread is None


# ---------------------------------------------------------------------------
# peer-transfer protocol over the KV fallback (no loopback world): this
# IS the fallback-channel coverage — outside a loopback context
# peer_channel() returns None and every shard rides the KV transport.
# ---------------------------------------------------------------------------

class _MemKV:
    """In-memory KVClient stand-in (put/wait/delete)."""

    def __init__(self):
        self.cv = threading.Condition()
        self.store = {}

    def put(self, key, value):
        with self.cv:
            self.store[key] = value
            self.cv.notify_all()

    def wait(self, key, timeout=60.0, poll_interval=0.1):
        end = time.monotonic() + min(timeout, 10.0)
        with self.cv:
            while key not in self.store:
                if time.monotonic() > end:
                    raise TimeoutError(key)
                self.cv.wait(0.05)
            return self.store[key]

    def delete(self, key):
        with self.cv:
            self.store.pop(key, None)


def _run_world_transfers(plan, trees, monkeypatch):
    """Run every rank's side of run_peer_transfers on its own thread,
    with a barrier allgather and the in-memory KV as the transport.
    Returns {rank: (new_leaves, reason)}."""
    import jax
    kv = _MemKV()
    monkeypatch.setattr(ck, "_kv_client", lambda: kv)
    n = plan.world
    barrier = {"cv": threading.Condition(), "calls": {}, "vals": {}}

    def allgather(obj):
        # lockstep allgather: the round is each thread's OWN call count
        # (a shared bumped counter races — a waiter can re-enter for the
        # next round before the bumper wakes and read stale deposits)
        cv = barrier["cv"]
        with cv:
            me = threading.current_thread().name
            rnd = barrier["calls"].get(me, 0)
            barrier["calls"][me] = rnd + 1
            barrier["vals"].setdefault(rnd, {})[me] = obj
            cv.notify_all()
            end = time.monotonic() + 15.0
            while len(barrier["vals"][rnd]) < n:
                if time.monotonic() > end:
                    raise TimeoutError("allgather barrier")
                cv.wait(0.05)
            vals = barrier["vals"][rnd]
            return [vals[k] for k in sorted(vals)]

    out = {}

    def one(rank):
        leaves = jax.tree_util.tree_leaves(trees[rank])
        out[rank] = ck.run_peer_transfers(plan, rank, leaves,
                                          allgather=allgather)

    ts = [threading.Thread(target=one, args=(r,), name=f"r{r:02d}",
                           daemon=True) for r in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive(), "transfer thread hung"
    return out


class TestPeerTransfersKV:
    @pytest.mark.parametrize("make_tree, payload_dominates", [
        (_tree, False),  # 88 bytes: the envelopes outweigh the leaves
        (lambda v: {f"w{i}": np.full(1024, float(v), np.float32)
                    for i in range(8)}, True),
    ], ids=["three_leaves", "eight_leaves_of_4KiB"])
    def test_two_joiners_pull_from_two_survivors(self, monkeypatch,
                                                 make_tree,
                                                 payload_dominates):
        """2 survivors re-serve a tree snapshotted 4-wide: ranges are
        re-partitioned 2-wide on the fly and both joiners assemble the
        survivors' exact leaves (2->4 world growth). Rank 0 serves its
        share of the leaves, not the tree: the bytes the joiners took
        from it stay under half of what the broadcast baseline moves
        through rank 0 (the whole tree to every other rank:
        ``JaxState.sync``'s accounting)."""
        import jax
        from horovod_tpu import metrics as _metrics
        good = make_tree(9)
        leaves = jax.tree_util.tree_leaves(good)
        plan = ck.make_restore_plan(
            [_blob_t(r, commits, make_tree)
             for r, commits in enumerate((5, 5, 0, 0))], world=4)
        trees = {0: good, 1: good, 2: make_tree(0), 3: make_tree(0)}
        _metrics.reset_all(_metrics.CKPT_RESTORE_BYTES)
        out = _run_world_transfers(plan, trees, monkeypatch)
        for r in (0, 1):
            assert out[r] == (None, None)  # survivors: nothing to apply
        for r in (2, 3):
            got, reason = out[r]
            assert reason is None
            for x, y in zip(got, leaves):
                np.testing.assert_array_equal(np.asarray(x),
                                              np.asarray(y))
        by_source = {dict(k)["source"]: v for k, v in
                     _metrics.CKPT_RESTORE_BYTES.series().items()}
        assert by_source["peer"] > 0 and by_source["rank0"] > 0
        if payload_dominates:
            broadcast_baseline = ck.tree_nbytes(leaves) * (plan.world - 1)
            assert by_source["rank0"] < 0.5 * broadcast_baseline, by_source

    def test_digest_mismatch_rejected_and_repulled(self, monkeypatch):
        """A corrupted shard (digest mismatch) is rejected and re-pulled
        from the next survivor on attempt 1 — restore still succeeds."""
        import jax
        plan = ck.make_restore_plan(
            [_blob_t(0, 5), _blob_t(1, 5), _blob_t(2, 0)], world=3)
        good = _tree(4)
        trees = {0: good, 1: good, 2: _tree(0)}
        corrupted = []

        def corrupt_once(tag, payload):
            # flip rank 0's served shard on attempt 0 only
            step, d, owner, lo, hi, attempt = tag
            if owner == 0 and attempt == 0:
                corrupted.append(tag)
                return b"\x00" + payload[1:]
            return payload

        monkeypatch.setattr(ck, "_corrupt_shard_hook", corrupt_once)
        out = _run_world_transfers(plan, trees, monkeypatch)
        assert corrupted, "hook never fired"
        got, reason = out[2]
        assert reason is None
        want = jax.tree_util.tree_leaves(good)
        for x, y in zip(got, want):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_unrecoverable_pulls_degrade(self, monkeypatch):
        """Every serve corrupt on every attempt: both attempts fail and
        every rank agrees on the typed degraded reason."""
        plan = ck.make_restore_plan(
            [_blob_t(0, 5), _blob_t(1, 5), _blob_t(2, 0)], world=3)
        trees = {0: _tree(4), 1: _tree(4), 2: _tree(0)}
        monkeypatch.setattr(ck, "_corrupt_shard_hook",
                            lambda tag, p: b"\x00" + p[1:])
        out = _run_world_transfers(plan, trees, monkeypatch)
        for r in range(3):
            assert out[r] == (None, "pull-failed"), (r, out[r])

    def test_shard_pull_fault_fails_over(self, monkeypatch, fault_spec):
        """The ckpt.shard_pull chaos seam: survivor 0 refuses its serves
        once; the pull fails over to survivor 1 and completes."""
        import jax
        fault_spec("ckpt.shard_pull:error:rank=0:times=1")
        plan = ck.make_restore_plan(
            [_blob_t(0, 5), _blob_t(1, 5), _blob_t(2, 0)], world=3)
        good = _tree(3)
        trees = {0: good, 1: good, 2: _tree(0)}
        out = _run_world_transfers(plan, trees, monkeypatch)
        got, reason = out[2]
        assert reason is None
        want = jax.tree_util.tree_leaves(good)
        for x, y in zip(got, want):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _blob_t(rank, commits, make_tree=_tree):
    """Fingerprint blob matching the tree's real structure."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(make_tree(0))
    return {"rank": rank, "commits": commits, "n_leaves": len(leaves),
            "struct": ck.structure_digest(leaves, treedef),
            "manifest": -1}


# ---------------------------------------------------------------------------
# verification guards
# ---------------------------------------------------------------------------

class TestShardVerification:
    def _payload(self, leaves):
        import pickle
        data = pickle.dumps(leaves, protocol=pickle.HIGHEST_PROTOCOL)
        return ("ok", ck.shard_digest(data), data)

    def test_accepts_matching(self):
        import jax
        leaves = jax.tree_util.tree_leaves(_tree(2))
        got = ck._verify_shard(self._payload(leaves[0:2]), leaves, 0, 2)
        assert len(got) == 2

    def test_rejects_digest_mismatch(self):
        import jax
        leaves = jax.tree_util.tree_leaves(_tree(2))
        ok, digest, data = self._payload(leaves[0:2])
        with pytest.raises(ck._ShardRejected, match="digest"):
            ck._verify_shard((ok, digest ^ 1, data), leaves, 0, 2)

    def test_rejects_refusal_and_shape_mismatch(self):
        import jax
        leaves = jax.tree_util.tree_leaves(_tree(2))
        with pytest.raises(ck._ShardRejected, match="refused"):
            ck._verify_shard(("err", "boom"), leaves, 0, 2)
        wrong = [np.zeros((9, 9)), np.zeros((9, 9))]
        with pytest.raises(ck._ShardRejected, match="mismatch"):
            ck._verify_shard(self._payload(wrong), leaves, 0, 2)


# ---------------------------------------------------------------------------
# KV server GC surface
# ---------------------------------------------------------------------------

class TestKVDelete:
    def test_server_side_prefix_delete(self):
        from horovod_tpu.runner.http_kv import KVServer
        srv = KVServer()
        srv.start(0)
        try:
            srv.put("ckpt/peer/1/a", b"x")
            srv.put("ckpt/peer/1/b", b"y")
            srv.put("elastic/round", b"3")
            srv.delete("ckpt/peer")
            assert srv.keys("ckpt/peer") == []
            assert srv.get("elastic/round") == b"3"
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# loopback churn end to end (the ISSUE acceptance)
# ---------------------------------------------------------------------------

needs_native = pytest.mark.skipif(
    not _native.available(), reason="native engine unavailable")

FAST_HEALTH = {"HVD_HEALTH_INTERVAL": "0.2", "HVD_HEALTH_TIMEOUT": "2",
               "HVD_RESPONSE_CACHE": "1", "HVD_METRICS": "1"}


def _param_body(box, total_steps, until_transitions=0, sleep_s=0.03):
    """Training body with a real param/opt pytree updated by a
    world-size-independent rule: the Average of identical 0.25
    contributions is bitwise 0.25 at every world size (0.25*w/w is an
    exact binary division), so two runs that commit the same number of
    steps — churned or not — must end bitwise identical. With
    ``until_transitions`` the run continues past ``total_steps`` until
    that many world transitions were observed (the churn-test idiom:
    a fixed budget races discovery latency on a loaded box)."""
    import jax.numpy as jnp

    cap = total_steps * (4 if until_transitions else 1)

    def body():
        hvd.init()
        state = hvd.elastic.JaxState(
            params={"w": np.zeros((4, 3), np.float32),
                    "b": np.zeros(3, np.float32)},
            opt_state={"m": np.zeros((4, 3), np.float32), "count": 0},
            step=0, trans=0, lastw=0)

        @hvd.elastic.run
        def train(state):
            from horovod_tpu import metrics as _metrics
            while state.step < cap and not (
                    until_transitions and state.step >= total_steps
                    and state.trans >= until_transitions):
                probe = hvd.allreduce(jnp.ones(1), op=hvd.Sum,
                                      name="ckpt_probe")
                world = int(round(float(np.asarray(probe)[0])))
                if state.lastw and world != state.lastw:
                    state.trans += 1
                state.lastw = world
                g = np.asarray(
                    hvd.allreduce(jnp.full((4, 3), 0.25),
                                  op=hvd.Average, name="ckpt_grad"),
                    np.float32)
                state.params = {"w": state.params["w"] + g,
                                "b": state.params["b"] + g[0]}
                state.opt_state = {
                    "m": np.float32(0.5) * state.opt_state["m"] + g,
                    "count": state.opt_state["count"] + 1}
                state.step += 1
                time.sleep(sleep_s)
                state.commit()
            def tot(inst):
                # metric stores are per rank context: the joiner's pull
                # counters live on ITS thread's store, so sum all stores
                out = {}
                for s in _metrics._all_stores():
                    for k, v in inst.series(s).items():
                        out[k] = out.get(k, 0) + v
                return out

            return (state.step, state.trans, state.params,
                    state.opt_state,
                    int(_metrics.ELASTIC_STEPS_LOST.value()),
                    {"pulled": tot(_metrics.CKPT_PEER_SHARDS_PULLED),
                     "degraded": tot(
                         _metrics.CKPT_DEGRADED_RESTORES)})

        result = train(state)
        if hvd.rank() == 0:
            box["result"] = result
        return 0

    return body


def _series_total(series_dict):
    return sum(int(v) for v in series_dict.values())


def _replay(steps):
    """The no-churn control, replayed with the body's exact float32
    numpy ops."""
    w = np.zeros((4, 3), np.float32)
    m = np.zeros((4, 3), np.float32)
    g = np.full((4, 3), 0.25, np.float32)
    for _ in range(steps):
        w = w + g
        m = np.float32(0.5) * m + g
    return w, m


CHURN_4_3_4 = ("worker:preempt:rank=3:at_round=1:at_step=4:grace=30;"
               "worker:add:rank=0:at_round=2:after=4")


@needs_native
class TestPeerRestoreChurn:
    def _run(self, fault_spec, spec=None, np_=4, min_np=2, steps=24,
             until_transitions=0, extra=None, timeout=180):
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        if spec is not None:
            fault_spec(spec)
        else:
            os.environ.pop("HVD_FAULT_SPEC", None)
            _faults.refresh()
            _faults.clear_membership_handler()
        # The body's counters sum over EVERY live store — drop what
        # earlier tests (this file's KV unit tests, prior loopback
        # worlds elsewhere in the session) already recorded, so the
        # assertions see only this run.
        from horovod_tpu import metrics as _metrics
        _metrics.reset_all(_metrics.CKPT_PEER_SHARDS_PULLED,
                           _metrics.CKPT_DEGRADED_RESTORES)
        disco = FixedHosts({f"c{i}": 1 for i in range(np_)})
        box = {}
        env = dict(FAST_HEALTH)
        env.update(extra or {})
        results, ok = elastic_run(
            _param_body(box, steps, until_transitions=until_transitions),
            np=np_, min_np=min_np, max_np=np_, discovery=disco,
            timeout=timeout, extra_env=env)
        assert ok, results.error_message
        return box["result"]

    def test_churn_restore_bitwise_parity_vs_control(self, fault_spec):
        """World 4 -> 3 (graceful preempt) -> 4 (joiner peer-restores
        from survivor shards): final params AND optimizer state are
        bitwise identical to an unchurned world-4 control committing
        the same number of steps, zero steps rolled back, shards
        actually pulled, zero degraded restores."""
        step, trans, params, opt, lost, m = self._run(
            fault_spec, CHURN_4_3_4, until_transitions=2)
        assert trans >= 2, f"churn never completed: {trans} transitions"
        assert lost == 0, "graceful preempt rolled back steps"
        assert _series_total(m["pulled"]) > 0, \
            f"no peer shards pulled: {m}"
        assert _series_total(m["degraded"]) == 0, \
            f"peer restore degraded: {m}"
        # the control commits exactly as many steps, with zero churn
        cstep, _ct, cparams, copt, _cl, _cm = self._run(
            fault_spec, None, steps=step)
        assert cstep == step
        for k in ("w", "b"):
            np.testing.assert_array_equal(params[k], cparams[k])
        np.testing.assert_array_equal(opt["m"], copt["m"])
        assert opt["count"] == copt["count"] == step

    def test_survivor_death_mid_serve_fails_over(self, fault_spec):
        """Chaos (docs/robustness.md): a survivor dying mid-shard-serve
        (``ckpt.shard_pull:crash``) must fail over — the watchdog turns
        the dead serve into a PeerFailureError re-form, never a hang —
        and the job still completes inside the run timeout."""
        step, trans, params, _opt, _lost, m = self._run(
            fault_spec,
            CHURN_4_3_4 + ";ckpt.shard_pull:crash:rank=1:times=1",
            min_np=1, until_transitions=2, timeout=240)
        # the failover re-form can be size-preserving (dead survivor out,
        # joiner in -> 3->3), which the numeric world probe cannot see:
        # completion inside the timeout + a restore that actually served
        # the joiner (peer or typed-degraded) is the acceptance here.
        assert trans >= 1, f"preempt shrink never observed: {trans}"
        assert (_series_total(m["pulled"])
                + _series_total(m["degraded"])) > 0, m
        w, _ = _replay(step)
        np.testing.assert_array_equal(params["w"], w)

    def test_degraded_pull_failure_takes_typed_broadcast(self,
                                                        fault_spec):
        """Every serve refused on every attempt: the restore degrades
        to the rank-0 broadcast, counted under its typed reason — and
        the run still completes with the exact control numerics."""
        step, trans, params, opt, _lost, m = self._run(
            fault_spec, CHURN_4_3_4 + ";ckpt.shard_pull:error",
            until_transitions=2)
        assert trans >= 2, f"churn never completed: {trans}"
        assert _series_total(m["degraded"]) >= 1, m
        w, mm = _replay(step)
        np.testing.assert_array_equal(params["w"], w)
        np.testing.assert_array_equal(opt["m"], mm)

    def test_snapshot_dir_written_during_churn(self, fault_spec,
                                               tmp_path):
        """With HVD_CKPT_DIR set the plane snapshots during training,
        and a from-disk restore_or_none after the run reassembles a
        committed step whose params equal the replayed update rule."""
        step, _t, _p, _o, _l, _m = self._run(
            fault_spec, "worker:preempt:rank=3:at_step=4:grace=30",
            extra={"HVD_CKPT_DIR": str(tmp_path),
                   "HVD_CKPT_INTERVAL": "2"})
        manifests = [n for n in os.listdir(str(tmp_path))
                     if n.startswith("manifest-")]
        assert manifests, os.listdir(str(tmp_path))
        target = {"params": {"w": np.zeros((4, 3), np.float32),
                             "b": np.zeros(3, np.float32)},
                  "opt_state": {"m": np.zeros((4, 3), np.float32),
                                "count": 0},
                  "step": 0, "trans": 0, "lastw": 0}
        got = ck.restore_or_none(str(tmp_path), target=target)
        assert got is not None
        assert 2 <= got["step"] <= step
        w, _ = _replay(got["step"])
        np.testing.assert_array_equal(got["params"]["w"], w)
