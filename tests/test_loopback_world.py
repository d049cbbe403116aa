"""Loopback multi-rank world: the full world>1 stack on the CPU backend.

The in-process twin of the spawn-based integration tests: the
negotiation protocol, joined-rank reconstruction, watchdog fast-abort,
elastic re-forming, and step-capture ``negotiate_step`` replay all run at
world>=4 inside ONE interpreter (docs/loopback.md), without the spawn
variants' process start-up (test_integration_*).
"""

import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
from backend_markers import loopback_world  # noqa: F401  (fixture)
from horovod_tpu import _native
from horovod_tpu.dynamic import HorovodCollectiveError
from horovod_tpu.exceptions import PeerFailureError
from horovod_tpu.loopback.context import RankKilled
from horovod_tpu.utils import faults as _faults

pytestmark = pytest.mark.skipif(
    not _native.available(), reason="native engine unavailable")


FAST_HEALTH = {"HVD_HEALTH_INTERVAL": "0.3", "HVD_HEALTH_TIMEOUT": "1.5"}


def _results(outs):
    return [o.result for o in outs]


class TestNegotiatedCollectives:
    def test_matching_metadata_succeeds(self, loopback_world):
        n = loopback_world.size

        def body():
            out = hvd.allreduce(jnp.ones(4), op=hvd.Sum, name="grads")
            assert out.shape == (4,)
            assert np.allclose(np.asarray(out), n)
            out2 = hvd.allreduce(jnp.ones(3), op=hvd.Sum)  # auto-named
            assert np.allclose(np.asarray(out2), n)
            return "OK"

        assert _results(loopback_world.run(body)) == ["OK"] * n

    def test_shape_mismatch_raises_informative_error(self, loopback_world):
        def body():
            shape = 4 if hvd.rank() == 0 else 5
            try:
                hvd.allreduce(jnp.ones(shape), op=hvd.Sum, name="bad")
                return "NO_ERROR"
            except HorovodCollectiveError as e:
                assert "Mismatched ALLREDUCE tensor shapes" in str(e), str(e)
                assert "[4]" in str(e) and "[5]" in str(e), str(e)
                return "GOT_MISMATCH_ERROR"

        outs = _results(loopback_world.run(body))
        assert outs == ["GOT_MISMATCH_ERROR"] * loopback_world.size

    def test_op_mismatch_raises(self, loopback_world):
        def body():
            try:
                if hvd.rank() == 0:
                    hvd.allreduce(jnp.ones(4), op=hvd.Sum, name="op_clash")
                else:
                    hvd.allgather(jnp.ones(4), name="op_clash")
                return "NO_ERROR"
            except HorovodCollectiveError as e:
                assert "Mismatched collective operations" in str(e), str(e)
                return "GOT_OP_ERROR"

        outs = _results(loopback_world.run(body))
        assert outs == ["GOT_OP_ERROR"] * loopback_world.size

    def test_engine_disabled_by_knob(self):
        with hvd.loopback.world(
                2, extra_env={"HVD_DYNAMIC_ENGINE": "0"}) as w:
            def body():
                from horovod_tpu import engine_service
                assert engine_service.get_service() is None
                return "OK"

            assert _results(w.run(body)) == ["OK", "OK"]

    def test_grouped_and_broadcast(self, loopback_world):
        n = loopback_world.size

        def body():
            r = hvd.rank()
            outs = hvd.grouped_allreduce(
                [jnp.full((3,), float(r)), jnp.ones(2)], op=hvd.Sum,
                name="grp")
            assert np.allclose(np.asarray(outs[0]), sum(range(n)))
            assert np.allclose(np.asarray(outs[1]), float(n))
            b = hvd.broadcast(jnp.full((3,), float(r)), root_rank=1,
                              name="bc")
            assert np.allclose(np.asarray(b), 1.0), b
            return "OK"

        assert _results(loopback_world.run(body)) == ["OK"] * n


class TestPerProcessSetNegotiation:
    """Subset eager ops negotiate among member processes only, at a real
    world>1 (the loopback port of the 2-of-3 spawn test)."""

    def test_subset_collectives_without_nonmember(self):
        with hvd.loopback.world(
                3, extra_env={"HVD_DYNAMIC_PROCESS_SETS": "1"}) as w:
            def body():
                rank = hvd.rank()
                ps = hvd.add_process_set([0, 1])
                if rank < 2:
                    x = hvd.per_rank(
                        [jnp.full((4,), float(q + 1)) for q in (0, 1)],
                        process_set=ps)
                    out = hvd.allreduce(x, op=hvd.Sum, process_set=ps,
                                        name="sub")
                    assert np.allclose(np.asarray(out), 3.0), out
                    out2 = hvd.allreduce(x, op=hvd.Sum, process_set=ps)
                    g = hvd.allgather(hvd.per_rank(
                        [jnp.full((1,), float(q)) for q in (0, 1)],
                        process_set=ps), process_set=ps)
                    assert np.allclose(np.asarray(g), [0.0, 1.0]), g
                # all three: auto-name counters must still agree
                out3 = hvd.allreduce(jnp.ones(3), op=hvd.Sum)
                assert np.allclose(np.asarray(out3), 3.0), out3
                return "OK"

            assert _results(w.run(body)) == ["OK"] * 3

    def test_subset_mismatch_detected_among_members(self):
        with hvd.loopback.world(
                3, extra_env={"HVD_DYNAMIC_PROCESS_SETS": "1"}) as w:
            def body():
                rank = hvd.rank()
                ps = hvd.add_process_set([0, 1])
                got = "WORKER_OK"
                if rank < 2:
                    shape = 4 if rank == 0 else 5
                    x = hvd.per_rank([jnp.ones(shape) for _ in (0, 1)],
                                     process_set=ps)
                    try:
                        hvd.allreduce(x, op=hvd.Sum, process_set=ps,
                                      name="clash")
                        got = "NO_ERROR"
                    except HorovodCollectiveError as e:
                        assert "Mismatched ALLREDUCE tensor shapes" \
                            in str(e), str(e)
                        got = "GOT_MISMATCH"
                return got

            outs = _results(w.run(body))
            assert outs[:2] == ["GOT_MISMATCH", "GOT_MISMATCH"], outs
            assert outs[2] == "WORKER_OK"


class TestRaggedAllgather:
    def test_local_tensors_with_different_first_dims(self):
        with hvd.loopback.world(2) as w:
            def body():
                rank = hvd.rank()
                d0 = 2 if rank == 0 else 5
                out = hvd.allgather(jnp.full((d0, 3), float(rank + 1)),
                                    name="rag")
                assert out.shape == (7, 3), out.shape
                assert np.allclose(np.asarray(out[:2]), 1.0), out
                assert np.allclose(np.asarray(out[2:]), 2.0), out
                d0b = 4 if rank == 0 else 1
                out2 = hvd.allgather(jnp.full((d0b, 3), float(rank + 1)),
                                     name="rag2")
                assert out2.shape == (5, 3), out2.shape
                return "OK"

            assert _results(w.run(body)) == ["OK", "OK"]

    def test_allgather_sizes_not_cache_stale(self):
        with hvd.loopback.world(2) as w:
            def body():
                rank = hvd.rank()
                for step, peer_d0 in enumerate((3, 6)):
                    d0 = 2 if rank == 0 else peer_d0
                    out = hvd.allgather(jnp.full((d0, 2), float(rank)),
                                        name=f"s{step}")
                    assert out.shape == (2 + peer_d0, 2), (step, out.shape)
                return "OK"

            assert _results(w.run(body)) == ["OK", "OK"]


class TestJoin:
    def test_uneven_steps_with_join(self):
        with hvd.loopback.world(2) as w:
            def body():
                rank = hvd.rank()
                if rank == 0:
                    for step in range(2):
                        out = hvd.allreduce(jnp.full((3,), 6.0),
                                            op=hvd.Average, name=f"g{step}")
                        # joined rank contributes zeros; average over world
                        assert np.allclose(np.asarray(out), 3.0), (step, out)
                return hvd.join()

            outs = _results(w.run(body))
            assert len(set(outs)) == 1, outs  # same last-joined rank

    def test_join_with_grouped_and_barrier(self):
        with hvd.loopback.world(2) as w:
            def body():
                if hvd.rank() == 0:
                    xs = [jnp.full((2,), float(i + 1)) for i in range(3)]
                    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="grp")
                    for i, o in enumerate(outs):
                        assert np.allclose(np.asarray(o), i + 1.0), (i, o)
                    hvd.barrier()
                    hvd.join()
                else:
                    hvd.join()
                return "OK"

            assert _results(w.run(body)) == ["OK", "OK"]

    def test_allgather_while_joined(self):
        with hvd.loopback.world(2) as w:
            def body():
                if hvd.rank() == 0:
                    out = hvd.allgather(jnp.full((3, 2), 7.0), name="g1")
                    assert out.shape == (3, 2), out.shape  # peer: 0 rows
                    assert np.allclose(np.asarray(out), 7.0), out
                    out2 = hvd.allgather(jnp.full((5,), 2.0), name="g2")
                    assert out2.shape == (5,), out2.shape
                    out3 = hvd.allgather(jnp.zeros((0, 3)), name="g3")
                    assert out3.shape == (0, 3), out3.shape
                    hvd.join()
                else:
                    hvd.join()
                return "OK"

            assert _results(w.run(body)) == ["OK", "OK"]

    def test_scalar_allgather_while_joined(self):
        """A SCALAR gather while the peer is joined: the joined rank
        must pair with the active rank's exchange contributing a zero
        scalar (the real path runs an (n, 1) program with zeros) —
        this deadlocked before the code-review fix."""
        with hvd.loopback.world(2) as w:
            def body():
                if hvd.rank() == 0:
                    out = hvd.allgather(jnp.float32(3.0), name="sg")
                    assert out.shape == (2,), out.shape
                    assert np.allclose(np.asarray(out), [3.0, 0.0]), out
                    hvd.join()
                else:
                    hvd.join()
                return "OK"

            assert _results(w.run(body, timeout=120)) == ["OK", "OK"]


class TestLoopbackEnvContract:
    """The loopback analog of the KV-bootstrap spawn test: the world
    seeds the full launcher contract; a half-configured environment must
    fail fast with a clear message instead of hanging on KV connect
    (ISSUE-10 satellite fix)."""

    def test_half_configured_overlay_rejected(self):
        with hvd.loopback.world(2) as w:
            def body():
                hvd.shutdown()
                from horovod_tpu.loopback import context as lbctx
                ctx = lbctx.current()
                ctx.env.pop("HVD_KV_ADDR", None)
                try:
                    hvd.init()
                    return "NO_ERROR"
                except RuntimeError as e:
                    assert "half-configured" in str(e), str(e)
                    return "REJECTED"

            outs = w.run(body, allow_failures=True)
            assert [o.result for o in outs] == ["REJECTED", "REJECTED"]

    def test_loopback_marker_without_context_rejected(self, monkeypatch):
        monkeypatch.setenv("HVD_LOOPBACK", "1")
        from horovod_tpu import runtime as rt
        # the session world is initialized; call the guarded branch
        # directly on a fresh-state probe: init() must raise before
        # touching any KV machinery
        with pytest.raises(RuntimeError, match="loopback rank context"):
            # session runtime is already initialized, so force the check
            # by calling init() — the loopback guard fires before the
            # "called twice" fast path
            rt.init()


class TestNumericsParity:
    """Acceptance: loopback world>=4 numerics are IDENTICAL to the
    world=1 (single-controller) path — bit for bit, because the
    completing rank runs the very same compiled program over the same
    sub-mesh."""

    def test_allreduce_bit_identical_to_single_controller(self):
        n = 4
        rng = np.random.RandomState(7)
        vals = [rng.randn(37).astype(np.float32) * (10.0 ** (i - 2))
                for i in range(n)]
        ps = hvd.add_process_set([0, 1, 2, 3])
        try:
            ref = hvd.allreduce(
                hvd.per_rank([jnp.asarray(v) for v in vals],
                             process_set=ps),
                op=hvd.Sum, process_set=ps, name="parity_ref")
            ref = np.asarray(ref)
        finally:
            hvd.remove_process_set(ps)

        with hvd.loopback.world(n) as w:
            def body():
                out = hvd.allreduce(jnp.asarray(vals[hvd.rank()]),
                                    op=hvd.Sum, name="parity")
                return np.asarray(out)

            outs = _results(w.run(body))
        for o in outs:
            assert o.tobytes() == ref.tobytes(), "loopback numerics drifted"


class TestStepCaptureReplay:
    """ISSUE-10 satellite: PR-8's multi-process ``negotiate_step`` replay
    exercised for real at world=4 — 3-step capture-on/off parity plus a
    forced mid-step divergence fallback."""

    def test_three_step_parity_capture_on_off(self):
        def run_world(capture: bool):
            env = {"HVD_STEP_CAPTURE": "1" if capture else "0"}
            with hvd.loopback.world(4, extra_env=env) as w:
                def body():
                    r = hvd.rank()
                    vals = []
                    for step in range(4):
                        hvd.step_marker()
                        hs = [hvd.allreduce_async(
                                  jnp.full((4,), float(r + i + step)),
                                  op=hvd.Sum, name=f"t{i}")
                              for i in range(3)]
                        vals.append([np.asarray(h.result()) for h in hs])
                    hvd.step_marker()
                    cap = hvd.fusion_stats()["capture"]
                    svc = None
                    from horovod_tpu import engine_service
                    s = engine_service.get_service()
                    if s is not None:
                        svc = s.step_negotiations
                    return vals, cap, svc

                return _results(w.run(body, timeout=240))

        on = run_world(True)
        off = run_world(False)
        for (vals_on, cap, svc), (vals_off, _c, _s) in zip(on, off):
            assert cap["recorded_steps"] == 1, cap
            assert cap["replayed_steps"] == 3, cap
            # the replay really batched the step's negotiations into
            # negotiate_step rounds (one per replayed step)
            assert svc == 3, svc
            for a, b in zip(vals_on, vals_off):
                for x, y in zip(a, b):
                    assert x.tobytes() == y.tobytes(), \
                        "capture on/off numerics diverged"

    def test_forced_mid_step_divergence_falls_back(self):
        with hvd.loopback.world(
                4, extra_env={"HVD_STEP_CAPTURE": "1"}) as w:
            def body():
                r = hvd.rank()
                results = []
                for step in range(4):
                    hvd.step_marker()
                    # step 2 diverges: an extra differently-shaped tensor
                    count = 3 if step != 2 else 2
                    hs = [hvd.allreduce_async(
                              jnp.full((4,), float(r + i)), op=hvd.Sum,
                              name=f"d{i}")
                          for i in range(count)]
                    if step == 2:
                        hs.append(hvd.allreduce_async(
                            jnp.full((9,), float(r)), op=hvd.Sum,
                            name="odd"))
                    results.append([np.asarray(h.result()) for h in hs])
                hvd.step_marker()
                cap = hvd.fusion_stats()["capture"]
                return results, cap

            outs = _results(w.run(body, timeout=240))
        for results, cap in outs:
            assert cap["fallbacks"] >= 1, cap  # the divergence fell back
            # numerics stayed correct through the fallback
            assert np.allclose(results[2][0], 0 + 1 + 2 + 3)
            assert np.allclose(results[2][-1], 0 + 1 + 2 + 3)


class TestChaos:
    """ISSUE-10 chaos gate: HVD_FAULT_SPEC rank death at world=4 under
    loopback surfaces PeerFailureError on every survivor in < 5 s and
    drives elastic blacklist + re-form (ci.sh runs this class under
    HVD_DEBUG_INVARIANTS=1)."""

    def test_rank_death_fast_abort_world4(self):
        os.environ["HVD_FAULT_SPEC"] = "worker:crash:rank=2:at_step=3"
        _faults.refresh()
        try:
            with hvd.loopback.world(4, extra_env=FAST_HEALTH) as w:
                def body():
                    state = hvd.elastic.JaxState(step=0)
                    t0 = time.monotonic()
                    try:
                        for step in range(200):
                            hvd.allreduce(jnp.ones(2), op=hvd.Sum,
                                          name=f"s{step}")
                            state.step += 1
                            state.commit()  # rank 2 crashes at commit #3
                        return ("finished", None)
                    except PeerFailureError as e:
                        return ("peerfail", time.monotonic() - t0, str(e))

                outs = w.run(body, timeout=120, allow_failures=True)
            survivors = [o for o in outs if o.rank != 2]
            dead = next(o for o in outs if o.rank == 2)
            assert isinstance(dead.error, RankKilled), dead
            for o in survivors:
                assert o.error is None, o
                kind, dt, msg = o.result
                assert kind == "peerfail", o.result
                assert dt < 5.0, f"abort took {dt:.1f}s (budget 5s)"
                assert "rank 2" in msg, msg
        finally:
            os.environ.pop("HVD_FAULT_SPEC", None)
            _faults.refresh()

    def test_crash_on_cycle_thread_still_surfaces(self):
        """A crash injected at a site that runs on a rank-owned HELPER
        thread (svc.exchange: the negotiation cycle loop) must still
        emulate process death — beats cease, survivors abort fast, and
        the dying rank's own main thread unwinds as killed (this leaked
        a zombie rank with live beats before the code-review fix)."""
        # after=30: the rank must die AFTER its first beats were
        # observed — a rank dead before ever beating is (by design) only
        # covered by the stall/exchange deadline, not silence detection
        os.environ["HVD_FAULT_SPEC"] = "svc.exchange:crash:rank=1:after=30"
        _faults.refresh()
        try:
            with hvd.loopback.world(2, extra_env=FAST_HEALTH) as w:
                def body():
                    t0 = time.monotonic()
                    try:
                        for step in range(200):
                            hvd.allreduce(jnp.ones(2), op=hvd.Sum,
                                          name=f"c{step}")
                        return ("finished", None)
                    except PeerFailureError:
                        return ("peerfail", time.monotonic() - t0)

                outs = w.run(body, timeout=120, allow_failures=True)
            dead = next(o for o in outs if o.rank == 1)
            survivor = next(o for o in outs if o.rank == 0)
            assert isinstance(dead.error, RankKilled), dead
            assert survivor.error is None, survivor
            kind, dt = survivor.result
            assert kind == "peerfail", survivor.result
            assert dt < 5.0, f"abort took {dt:.1f}s (budget 5s)"
        finally:
            os.environ.pop("HVD_FAULT_SPEC", None)
            _faults.refresh()

    def test_rank_death_drives_elastic_reform(self):
        """Worker dies mid-elastic-run at world=2: the survivor restores
        committed state, the driver blacklists the dead host, and the
        round re-forms at world=1 — the full recovery chain in-process."""
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        disco = FixedHosts({"lb-hostA": 1, "lb-hostB": 1})
        crashed: list = []
        box: dict = {}

        def body():
            hvd.init()
            state = hvd.elastic.JaxState(step=0, sizes=[])

            @hvd.elastic.run
            def train(state):
                while state.step < 20:
                    out = hvd.allreduce(jnp.ones(1), op=hvd.Sum)
                    world = int(float(np.asarray(out).reshape(-1)[0]))
                    state.sizes = state.sizes + [world]
                    state.step += 1
                    if state.step == 6 and hvd.rank() == 1 and not crashed:
                        crashed.append(1)
                        raise RankKilled(1)  # simulated hard death
                    state.commit()
                return state.sizes

            sizes = train(state)
            if hvd.rank() == 0:
                box["sizes"] = sizes
            return len(sizes)

        results, ok = elastic_run(body, np=2, min_np=1, max_np=2,
                                  discovery=disco, timeout=60,
                                  extra_env=FAST_HEALTH)
        assert ok, results.error_message
        sizes = box.get("sizes")
        assert sizes is not None
        assert len(sizes) >= 20
        assert sizes[0] == 2 and sizes[-1] == 1, sizes
        assert sorted(set(sizes)) == [1, 2], sizes


class TestElastic:
    def test_elastic_grow_world(self):
        from horovod_tpu.elastic.discovery import FixedHosts
        from horovod_tpu.loopback import elastic_run

        disco = FixedHosts({"lb-grow-A": 1})
        box: dict = {}

        def body():
            hvd.init()
            state = hvd.elastic.JaxState(step=0, sizes=[])

            @hvd.elastic.run
            def train(state):
                while state.step < 12 or (2 not in state.sizes
                                          and state.step < 200):
                    out = hvd.allreduce(jnp.ones(2), op=hvd.Sum)
                    world = int(float(np.asarray(out).reshape(-1)[0]))
                    state.sizes = state.sizes + [world]
                    state.step += 1
                    if state.step == 2 and hvd.rank() == 0:
                        disco.set({"lb-grow-A": 1, "lb-grow-B": 1})
                    time.sleep(0.03)
                    state.commit()
                return state.sizes

            sizes = train(state)
            if hvd.rank() == 0:
                box["sizes"] = sizes
            return len(sizes)

        results, ok = elastic_run(body, np=1, min_np=1, max_np=2,
                                  discovery=disco, timeout=60)
        assert ok, results.error_message
        sizes = box.get("sizes")
        assert sizes is not None
        assert sizes[0] == 1 and sizes[-1] == 2, sizes
        assert sorted(set(sizes)) == [1, 2], sizes
        assert len(sizes) < 200, "world never grew"
