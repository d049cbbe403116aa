"""Closed-loop elastic autoscaling policy (``HVD_AUTOSCALE``).

PR 14 made membership churn a *scripted*, measured scenario
(``worker:add/remove/preempt`` in the fault grammar); this module closes
the loop: the same membership actions are now chosen by a driver-side
controller reading the metrics registry as its sensor suite
(docs/elastic.md "Autoscaler"). Two halves:

* **Observer** (worker side, every rank) — hooked into
  ``State.commit()``: measures commit-to-commit step time, records it
  into the registry (``hvd_elastic_step_seconds`` /
  ``hvd_elastic_slo_violations_total``), and about twice per policy
  window publishes a compact sensor blob to the launcher KV under
  ``autoscale/sensor/<rank>`` — SLO violation share, fusion
  pending-bytes, QoS admission-wait mean, and this rank's
  :func:`~horovod_tpu.health.straggler_blames` deltas. Publishing is
  windowed *deltas* of registry snapshots, so the driver never has to
  reconcile counters across re-forms (ranks renumber per round; a blob
  is only meaningful inside the round it names).

* **Policy** (driver side) — :class:`AutoscalePolicy`, one daemon
  thread evaluating every ``HVD_AUTOSCALE_INTERVAL`` seconds:

  - **scale-up** when the mean SLO-violation share across reporting
    ranks exceeds half for ``HVD_AUTOSCALE_BREACH_WINDOWS``
    *consecutive* windows and the world is under the ceiling — a fresh
    host joins discovery and the driver grows the world at its next
    poll;
  - **scale-down** when *every* current rank reports a sustained-idle
    window (mean step time under ``HVD_AUTOSCALE_IDLE_FACTOR`` x SLO,
    zero violations, no queued backpressure) for
    ``HVD_AUTOSCALE_IDLE_WINDOWS`` consecutive windows and the world is
    above the floor — the newest (highest-rank) host gets the PR-14
    grace window and leaves through the slot-lost path: a policy
    scale-down loses **zero** steps, exactly like a scripted
    ``preempt``;
  - **evict-and-replace** when the aggregated straggler blames name the
    same global rank for ``HVD_AUTOSCALE_EVICT_WINDOWS`` consecutive
    windows — the slow-not-dead case the watchdog cannot touch: the
    blamed rank's host departs gracefully (grace window, zero steps
    lost) while a replacement host joins in the same discovery tick, so
    the world re-forms once at the same size and the replacement adopts
    the shape-keyed warm shelves (docs/elastic.md "Warm re-form").

**Robustness is the contract.** Decisions are driver-authoritative (no
rank ever branches on policy output — hvdlint pass 7 taints the policy
state exactly like ``rank()``), and **round-tagged**: a decision
evaluated against round R re-validates the round *and* the victim's
assignment at apply time, so an eviction racing a re-form — or blaming
a rank that just left — degrades to a counted ``hold``/``stale-round``
no-op instead of removing an innocent successor. Hysteresis (consecutive
-window streaks with an idle/breach dead band between the thresholds),
a post-decision cooldown, and the min/max world bounds jointly bound
oscillation: an adversarial load flapping faster than the streak
requirement produces **zero** membership changes
(``tests/test_autoscale.py``). A policy-evaluation
error of any kind degrades to "hold current world" with a typed
:class:`PolicyEvalError` warning — never a job failure — and every
decision (including holds) lands in
``hvd_elastic_policy_decisions_total{action,reason,rank}`` plus an
``AUTOSCALE.<action>.<reason>`` timeline instant, so a postmortem can
replay exactly why the world changed.
"""

from __future__ import annotations

import contextlib
import json
import weakref

from .. import health as _health
from .. import metrics as _metrics
from .. import timeline as _timeline
from ..loopback import context as _lbctx
from ..utils import envs
from ..utils import faults as _faults
from ..utils import invariants as _inv
from ..utils import logging as hvd_logging

SENSOR_KEY_PREFIX = "autoscale/sensor/"


class PolicyEvalError(RuntimeError):
    """A policy evaluation window failed (sensor read, aggregation, or
    actuation error). Never propagated into the job: the tick that
    raised it records a ``hold``/``error`` decision and the next window
    starts clean — an autoscaler bug must cost capacity agility, not
    the training run."""


def sensor_key(rank: int) -> str:
    return f"{SENSOR_KEY_PREFIX}{rank}"


# ---------------------------------------------------------------------------
# worker-side observer (the State.commit hook)
# ---------------------------------------------------------------------------

class CommitObserver:
    """One rank's sensor half: step timing at every commit, a sensor
    blob roughly twice per policy window (so the driver always has a
    fresh window to read). All values are windowed deltas of this
    rank's own registry store."""

    def __init__(self):
        self.rank = envs.get_int(envs.RANK, -1)
        self.slo_s = envs.autoscale_slo_s()
        self.interval_s = envs.autoscale_interval_s()
        self._last_commit_t: float | None = None
        self._last_publish_t = 0.0
        self._seq = 0
        self._steps = 0
        self._violations = 0
        self._step_s_sum = 0.0
        self._prev_blames: dict[int, int] = {}
        self._prev_qos: tuple[float, int] = (0.0, 0)
        self._prev_recovery: tuple[float, int] = (0.0, 0)
        self._client = None
        self._client_failed = False

    def _kv(self):
        if self._client is None and not self._client_failed:
            addr = envs.get(envs.KV_ADDR)
            if not addr:
                self._client_failed = True
                return None
            try:
                from ..runner.http_kv import KVClient
                self._client = KVClient(addr,
                                        envs.get_int(envs.KV_PORT, 0),
                                        secret=envs.get(envs.SECRET_KEY))
            except Exception as e:
                self._client_failed = True
                hvd_logging.warning(
                    "autoscale observer: KV client unavailable (%s); "
                    "sensors off for this worker", e)
        return self._client

    def note(self) -> None:
        """One ``State.commit()`` boundary on this rank's thread."""
        now = _inv.monotonic()
        prev = self._last_commit_t
        self._last_commit_t = now
        if prev is None:
            self._last_publish_t = now  # window starts at the 1st commit
            return
        dt = now - prev
        _metrics.ELASTIC_STEP_SECONDS.observe(dt)
        self._steps += 1
        self._step_s_sum += dt
        if self.slo_s > 0 and dt > self.slo_s:
            self._violations += 1
            _metrics.ELASTIC_SLO_VIOLATIONS.inc()
        if now - self._last_publish_t >= self.interval_s / 2.0:
            self._publish(now)

    def _publish(self, now: float) -> None:
        kv = self._kv()
        if kv is None:
            return
        blames = _health.straggler_blames()
        blame_delta = {r: c - self._prev_blames.get(r, 0)
                       for r, c in blames.items()
                       if c - self._prev_blames.get(r, 0) > 0}
        qos_sum, qos_count = _qos_wait_totals()
        d_sum = qos_sum - self._prev_qos[0]
        d_count = qos_count - self._prev_qos[1]
        rec_sum, rec_count = _recovery_totals()
        dr_sum = rec_sum - self._prev_recovery[0]
        dr_count = rec_count - self._prev_recovery[1]
        self._seq += 1
        blob = {
            "rank": envs.get_int(envs.RANK, self.rank),
            "round": envs.get_int(envs.ELASTIC_ROUND, -1),
            "seq": self._seq,
            "steps": self._steps,
            "violations": self._violations,
            "step_s_mean": (self._step_s_sum / self._steps
                            if self._steps else 0.0),
            "pending_bytes": float(_metrics.FUSION_PENDING_BYTES.value()),
            "qos_wait_s_mean": (d_sum / d_count if d_count else 0.0),
            # Measured recovery cost (re-form + state restore, windowed
            # delta): the scale-down brake's sensor — scaling down is
            # only worth it when the restore the next re-form will pay
            # stays inside the idle savings (docs/checkpoint.md).
            "restore_s_sum": dr_sum,
            "restore_count": dr_count,
            "straggler": {str(r): c for r, c in
                          sorted(blame_delta.items())},
        }
        self._prev_blames = blames
        self._prev_qos = (qos_sum, qos_count)
        self._prev_recovery = (rec_sum, rec_count)
        self._steps = 0
        self._violations = 0
        self._step_s_sum = 0.0
        self._last_publish_t = now
        try:
            kv.put(sensor_key(blob["rank"]), json.dumps(blob).encode())
        except Exception as e:
            # Sensor loss degrades the POLICY (it holds), never the job.
            hvd_logging.debug("autoscale sensor publish failed: %s", e)


def _qos_wait_totals() -> tuple[float, int]:
    """(sum_s, count) across this rank's QoS admission-wait series —
    the tail sensor collapses to a windowed mean at the observer."""
    total_s, total_n = 0.0, 0
    for _labels, h in _metrics.QOS_ADMISSION_WAIT.series().items():
        total_s += getattr(h, "sum", 0.0)
        total_n += getattr(h, "count", 0)
    return total_s, total_n


def _recovery_totals() -> tuple[float, int]:
    """(sum_s, count) across this rank's recovery-time series: the full
    re-form spans (catch -> re-rendezvous -> re-sync) plus the state
    restores measured by the checkpoint plane. Loopback ranks share one
    process registry, so the driver-side mean divides out the world."""
    total_s, total_n = 0.0, 0
    for hist in (_metrics.ELASTIC_REFORM_SECONDS,
                 _metrics.CKPT_RESTORE_SECONDS):
        for _labels, h in hist.series().items():
            total_s += getattr(h, "sum", 0.0)
            total_n += getattr(h, "count", 0)
    return total_s, total_n


# Per-world observer registry: one observer per loopback rank context
# (weak keys — a dead elastic round's contexts must not pin observers),
# one for a plain worker process. `False` caches "autoscale off" so the
# per-commit fast path is one dict probe.
_ctx_observers: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_process_observer: "CommitObserver | bool | None" = None


def note_commit() -> None:
    """The ``State.commit()`` seam: near-zero when ``HVD_AUTOSCALE`` is
    off (one registry probe + cached miss)."""
    ctx = _lbctx.current()
    if ctx is None:
        global _process_observer
        obs = _process_observer
        if obs is None:
            obs = _process_observer = (
                CommitObserver() if envs.autoscale_enabled() else False)
    else:
        obs = _ctx_observers.get(ctx)
        if obs is None:
            obs = (CommitObserver() if envs.autoscale_enabled()
                   else False)
            _ctx_observers[ctx] = obs
    if obs is not False:
        obs.note()


def reset_observer() -> None:
    """Drop the calling thread's observer (tests and worker teardown);
    the next commit re-reads the knob."""
    global _process_observer
    ctx = _lbctx.current()
    if ctx is None:
        _process_observer = None
    else:
        _ctx_observers.pop(ctx, None)


# ---------------------------------------------------------------------------
# driver-side policy
# ---------------------------------------------------------------------------

def _env_get(env: dict | None, name: str) -> str | None:
    """Knob lookup with a driver-side overlay: the elastic front ends
    pass the same ``extra_env`` dict they seed into worker overlays, so
    a job configured entirely through ``elastic_run(extra_env=...)``
    (the loopback/bench path — nothing touches ``os.environ``) drives
    the policy and the observers from ONE knob surface."""
    if env:
        for prefix in ("HVD_", "HOROVOD_"):
            v = env.get(prefix + name)
            if v is not None:
                return v
    return envs.get(name)


def _env_int(env, name, default: int) -> int:
    v = _env_get(env, name)
    try:
        return int(v) if v is not None else default
    except ValueError:
        return default


def _env_float(env, name, default: float) -> float:
    v = _env_get(env, name)
    try:
        return float(v) if v is not None else default
    except ValueError:
        return default


def _env_bool(env, name, default: bool = False) -> bool:
    v = _env_get(env, name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


class Decision:
    """One evaluated action, round-tagged at decision time."""

    __slots__ = ("action", "reason", "rank", "round_id", "detail", "t")

    def __init__(self, action: str, reason: str, round_id: int,
                 rank: int | None = None, detail: str = ""):
        self.action = action
        self.reason = reason
        self.rank = rank
        self.round_id = round_id
        self.detail = detail
        self.t = _inv.monotonic()

    def as_dict(self) -> dict:
        return {"action": self.action, "reason": self.reason,
                "rank": self.rank, "round": self.round_id,
                "detail": self.detail, "t": self.t}


class AutoscalePolicy:
    """The driver-side controller: sensors in, membership actions out.

    ``driver`` is the :class:`~horovod_tpu.elastic.driver.ElasticDriver`
    (round id, rank->host table, stale grace); ``hosts`` is the mutable
    discovery source (``FixedHosts``-shaped: ``add_hosts`` /
    ``remove_host``) the decisions actuate through — the same seam
    scripted churn mutates, so the driver's discovery loop applies
    policy output exactly like any other host change. ``kv`` is the
    driver-side KV server (direct in-memory reads)."""

    def __init__(self, driver, hosts, kv, *, min_np: int,
                 max_np: int | None = None, interval_s: float | None = None,
                 env: dict | None = None):
        self.driver = driver
        self.hosts = hosts
        self.kv = kv
        self.min_np = _env_int(env, envs.AUTOSCALE_MIN, min_np)
        self.max_np = _env_int(
            env, envs.AUTOSCALE_MAX,
            max_np if max_np is not None else min_np)
        self.interval_s = (interval_s if interval_s is not None
                           else _env_float(
                               env, envs.AUTOSCALE_INTERVAL,
                               envs.DEFAULT_AUTOSCALE_INTERVAL_S))
        self.slo_s = _env_float(env, envs.AUTOSCALE_SLO_MS, 0.0) / 1e3
        self.idle_factor = _env_float(
            env, envs.AUTOSCALE_IDLE_FACTOR,
            envs.DEFAULT_AUTOSCALE_IDLE_FACTOR)
        self.breach_windows = max(1, _env_int(
            env, envs.AUTOSCALE_BREACH_WINDOWS,
            envs.DEFAULT_AUTOSCALE_BREACH_WINDOWS))
        self.idle_windows = max(1, _env_int(
            env, envs.AUTOSCALE_IDLE_WINDOWS,
            envs.DEFAULT_AUTOSCALE_IDLE_WINDOWS))
        self.evict_windows = max(1, _env_int(
            env, envs.AUTOSCALE_EVICT_WINDOWS,
            envs.DEFAULT_AUTOSCALE_EVICT_WINDOWS))
        self.cooldown_s = _env_float(
            env, envs.AUTOSCALE_COOLDOWN, envs.DEFAULT_AUTOSCALE_COOLDOWN_S)
        self.grace_s = _env_float(env, envs.AUTOSCALE_GRACE,
                                  envs.DEFAULT_AUTOSCALE_GRACE_S)

        self._breach_streak = 0
        self._idle_streak = 0
        self._blame_rank: int | None = None
        self._blame_streak = 0
        # Running recovery-cost sensor (restore_s_sum/_count blob keys):
        # lifetime totals, because re-forms are rare events — a windowed
        # mean would usually be empty exactly when the remove decision
        # needs it.
        self._restore_s_sum = 0.0
        self._restore_count = 0
        self._cooldown_until = 0.0
        self._last_seq: dict[tuple[int, int], int] = {}
        self._added = 0
        self._evictions = 0
        # Decision log (most recent last) — the bench/tests read this;
        # the registry counter is the durable postmortem surface.
        self.decisions: list[Decision] = []
        self.last_decision: Decision | None = None
        self._mu = _inv.make_lock("elastic.policy.mu")
        self._stop = _inv.make_event("elastic.policy.stop")
        self._thread = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = _inv.spawn_thread(self._loop,
                                         name="hvd-autoscale-policy")

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            _inv.join_thread(t, timeout=5)

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.tick()

    # -- one evaluation window ---------------------------------------------

    def tick(self) -> Decision | None:
        """Evaluate one window. Any error degrades to a counted hold —
        the robustness contract: a policy bug must never fail the job."""
        try:
            _faults.inject("policy.eval")
            return self._evaluate()
        except Exception as e:
            err = PolicyEvalError(
                f"autoscale policy evaluation failed ({type(e).__name__}: "
                f"{e}); holding current world")
            hvd_logging.warning("%s", err)
            return self._record(Decision(
                "hold", "error", self._round(), detail=str(e)))

    def _round(self) -> int:
        return self.driver._rendezvous.round_id

    def _read_sensors(self, round_id: int) -> list[dict]:
        """Fresh blobs for ``round_id``: sequence-advanced since the
        last window and tagged with the decision round (a stale round's
        blob describes ranks that may have renumbered)."""
        # Rounds are monotonic: sequence state for older rounds can
        # never be read again, so prune it (a long churn history must
        # not grow this dict one entry per (round, rank) forever).
        stale = [k for k in self._last_seq if k[0] != round_id]
        for k in stale:
            del self._last_seq[k]
        blobs = []
        for key in self.kv.keys(SENSOR_KEY_PREFIX.rstrip("/")):
            raw = self.kv.get(key)
            if raw is None:
                continue
            try:
                blob = json.loads(raw.decode())
            except (ValueError, UnicodeDecodeError):
                continue
            if blob.get("round") != round_id:
                continue
            r, seq = int(blob.get("rank", -1)), int(blob.get("seq", 0))
            if seq <= self._last_seq.get((round_id, r), 0):
                continue
            self._last_seq[(round_id, r)] = seq
            blobs.append(blob)
        return blobs

    def _evaluate(self) -> Decision | None:
        now = _inv.monotonic()
        round_id = self._round()
        world = self.driver.world_size()
        blobs = self._read_sensors(round_id)
        if not blobs:
            return None  # nothing fresh: not a window, streaks hold

        # -- sensor aggregation (one window) --
        viol_share = 0.0
        steps = sum(b.get("steps", 0) for b in blobs)
        if steps:
            viol_share = sum(b.get("violations", 0)
                             for b in blobs) / steps
        breach = self.slo_s > 0 and viol_share >= 0.5
        idle = (self.slo_s > 0 and len(blobs) >= world and steps > 0
                and all(b.get("violations", 0) == 0
                        and b.get("step_s_mean", 0.0)
                        <= self.idle_factor * self.slo_s
                        and b.get("pending_bytes", 0.0) <= 0.0
                        for b in blobs))
        blames: dict[int, int] = {}
        for b in blobs:
            for r, c in (b.get("straggler") or {}).items():
                blames[int(r)] = blames.get(int(r), 0) + int(c)
        dominant = (max(sorted(blames), key=lambda r: blames[r])
                    if blames else None)
        for b in blobs:
            self._restore_s_sum += float(b.get("restore_s_sum", 0.0))
            self._restore_count += int(b.get("restore_count", 0))

        # -- streaks (hysteresis state) --
        self._breach_streak = self._breach_streak + 1 if breach else 0
        self._idle_streak = self._idle_streak + 1 if idle else 0
        if dominant is not None and dominant == self._blame_rank:
            self._blame_streak += 1
        elif dominant is not None:
            self._blame_rank, self._blame_streak = dominant, 1
        else:
            self._blame_rank, self._blame_streak = None, 0

        if now < self._cooldown_until:
            return None  # streaks accumulate; actions wait out cooldown

        # -- decide (evict > add > remove: a straggler inflates step
        # time, so replacing it must precede scaling around it) --
        if (self._blame_rank is not None
                and self._blame_streak >= self.evict_windows):
            return self._apply_evict(self._blame_rank, round_id)
        if self._breach_streak >= self.breach_windows:
            if world >= self.max_np:
                return None  # at the ceiling: breach rides, no action
            return self._apply_add(round_id, viol_share)
        if self._idle_streak >= self.idle_windows:
            if world <= self.min_np:
                return None  # at the floor
            # Recovery-cost brake (docs/checkpoint.md): a remove triggers
            # a re-form whose measured restore cost every surviving rank
            # pays; when that projected cost exceeds the idle time the
            # decision is trying to reclaim (the windows of idleness that
            # justified it), shrinking loses throughput on net — hold.
            cost = self._projected_restore_s()
            savings = self.idle_windows * self.interval_s
            if cost > savings:
                return self._record(Decision(
                    "hold", "restore-cost", round_id,
                    detail=f"projected restore {cost:.2f}s exceeds idle "
                           f"savings window {savings:.2f}s"))
            return self._apply_remove(round_id)
        return None

    def _projected_restore_s(self) -> float:
        """Mean measured per-rank recovery time (re-form + restore) —
        the cost the next deliberate re-form is projected to pay. Zero
        until a recovery has been observed: the first scale-down is
        allowed on faith and funds the sensor for the rest."""
        if self._restore_count <= 0:
            return 0.0
        return self._restore_s_sum / self._restore_count

    # -- actuation (round-tag re-validated) ---------------------------------

    def _stale(self, round_id: int) -> bool:
        return self._round() != round_id

    @contextlib.contextmanager
    def _apply_guard(self, round_id: int):
        """Make the round-tag re-validation ATOMIC with actuation: the
        stale check and the host mutation run under the driver's round
        lock, so a re-form can never land between them and have the
        decision actuate against a renamed world (the hvdsched
        ``autoscale-decision`` model's guarded shape). The acquire must
        NOT block: a resume() parked in ``wait_for_available_slots``
        holds the lock while depending on discovery picking up host
        changes — blocking here would deadlock the very scale-up that
        could unpark it (the same rule ``_on_hosts_updated`` follows).
        Yields None (degrade to a stale-round hold) when the lock is
        busy or the tag went stale; yields the decision round otherwise.
        """
        lock = self.driver._round_lock
        if not lock.acquire(blocking=False):
            yield None  # a re-form/resume owns the round right now
            return
        try:
            yield None if self._stale(round_id) else round_id
        finally:
            lock.release()

    def _post_action(self) -> None:
        """Every applied action opens the cooldown and resets the
        hysteresis streaks — the action's own re-form disruption must
        never read as the next window's signal."""
        self._cooldown_until = _inv.monotonic() + self.cooldown_s
        self._breach_streak = 0
        self._idle_streak = 0
        self._blame_rank, self._blame_streak = None, 0

    def _apply_add(self, round_id: int, viol_share: float) -> Decision:
        with self._apply_guard(round_id) as tag:
            if tag is None:
                return self._record(
                    Decision("hold", "stale-round", round_id))
            host = f"auto{self._added}"
            self._added += 1
            self.hosts.add_hosts({host: 1})
        self._post_action()
        return self._record(Decision(
            "add", "slo-breach", round_id,
            detail=f"+{host} (violation share {viol_share:.2f})"))

    def _victim_host(self) -> tuple[str, int] | None:
        """``(hostname, slot_count)`` of the newest (highest-rank) host
        — never one that carries rank 0, which holds the committed
        state the post-reset sync broadcasts from. The slot count bounds
        multi-slot removals (removing a host removes ALL its ranks)."""
        slots = self.driver._rank_assignments
        if not slots:
            return None
        host = slots[max(slots)].hostname
        members = [s for s in slots.values() if s.hostname == host]
        if any(s.rank == 0 for s in members):
            return None
        return host, len(members)

    def _apply_remove(self, round_id: int) -> Decision:
        with self._apply_guard(round_id) as tag:
            if tag is None:
                return self._record(
                    Decision("hold", "stale-round", round_id))
            victim = self._victim_host()
            if victim is None:
                return self._record(Decision(
                    "hold", "protected", round_id,
                    detail="no removable host"))
            host, nslots = victim
            if self.driver.world_size() - nslots < self.min_np:
                # removing a multi-slot host would punch through the
                # floor; hold until capacity justifies losing it whole
                return self._record(Decision(
                    "hold", "protected", round_id,
                    detail=f"removing {host} ({nslots} slots) would "
                           f"break the {self.min_np} floor"))
            self.driver.set_stale_grace(host, self.grace_s)
            self.hosts.remove_host(host)
        self._post_action()
        return self._record(Decision("remove", "idle", round_id,
                                     detail=f"-{host} (graceful)"))

    def _apply_evict(self, rank: int, round_id: int) -> Decision:
        """Evict-and-replace the blamed rank: graceful departure (grace
        window -> zero steps lost) plus a replacement host — matching
        the victim's slot count — in the SAME discovery tick, so the
        world re-forms once at the same size and the replacement adopts
        the shape-keyed warm shelves."""
        with self._apply_guard(round_id) as tag:
            if tag is None:
                return self._record(Decision("hold", "stale-round",
                                             round_id, rank=rank))
            slots = self.driver._rank_assignments
            slot = slots.get(rank)
            if slot is None or not self.driver.has_rank_assignment(
                    slot.hostname, slot.local_rank):
                # The blamed rank already left (re-form between the
                # blame windows and this apply): a stale blame must
                # never evict the successor that inherited the number.
                self._blame_rank, self._blame_streak = None, 0
                return self._record(Decision(
                    "hold", "stale-round", round_id, rank=rank,
                    detail="blamed rank not assigned"))
            members = [s for s in slots.values()
                       if s.hostname == slot.hostname]
            if any(s.rank == 0 for s in members):
                # rank 0's host carries the committed state; replacing
                # it forfeits the sync source. Drop the blame streak so
                # the breach/idle rules get to act on later windows
                # instead of this branch holding them out forever.
                self._blame_rank, self._blame_streak = None, 0
                return self._record(Decision(
                    "hold", "protected", round_id, rank=rank,
                    detail="refusing to evict rank 0's host"))
            replacement = f"auto{self._added}"
            self._added += 1
            self._evictions += 1
            self.driver.set_stale_grace(slot.hostname, self.grace_s)
            self.hosts.remove_host(slot.hostname)
            self.hosts.add_hosts({replacement: len(members)})
        self._post_action()
        return self._record(Decision(
            "evict", "straggler", round_id, rank=rank,
            detail=f"-{slot.hostname} +{replacement}"))

    # -- recording ----------------------------------------------------------

    def _record(self, d: Decision) -> Decision:
        _metrics.ELASTIC_POLICY_DECISIONS.inc(labels={
            "action": d.action, "reason": d.reason,
            "rank": "" if d.rank is None else str(d.rank)})
        _timeline.record_health_event(
            f"AUTOSCALE.{d.action}.{d.reason}")
        with self._mu:
            self.decisions.append(d)
            del self.decisions[:-512]  # registry counters are the
            self.last_decision = d     # durable surface; bound the log
        log = (hvd_logging.warning if d.reason == "error"
               else hvd_logging.info)
        log("autoscale: %s (%s)%s round=%d %s", d.action, d.reason,
            f" rank={d.rank}" if d.rank is not None else "", d.round_id,
            d.detail)
        return d

    def policy_stats(self) -> dict:
        """Controller introspection (tests/bench; rank-LOCAL like every
        dynamic runtime-state surface — hvdlint pass 7 taints reads of
        this under a collective submission)."""
        with self._mu:
            return {
                "world": self.driver.world_size(),
                "bounds": (self.min_np, self.max_np),
                "breach_streak": self._breach_streak,
                "idle_streak": self._idle_streak,
                "blame": (self._blame_rank, self._blame_streak),
                "cooldown_remaining_s": max(
                    0.0, self._cooldown_until - _inv.monotonic()),
                "decisions": [d.as_dict() for d in self.decisions],
            }


def maybe_start(driver, hosts, kv, *, min_np: int,
                max_np: int | None = None,
                env: dict | None = None) -> AutoscalePolicy | None:
    """Wire the policy into an elastic front end when ``HVD_AUTOSCALE``
    is on (process env or the front end's ``extra_env`` overlay) and
    the discovery source is mutable; the caller owns ``stop()``.
    Mirrors ``discovery.install_scripted_churn``'s posture: a
    non-mutable discovery warns and runs without a policy rather than
    failing the job."""
    if not _env_bool(env, envs.AUTOSCALE, False):
        return None
    if hosts is None or not hasattr(hosts, "add_hosts"):
        hvd_logging.warning(
            "HVD_AUTOSCALE=1 but the discovery source is not mutable "
            "(FixedHosts); the autoscale policy is off for this job")
        return None
    policy = AutoscalePolicy(driver, hosts, kv, min_np=min_np,
                             max_np=max_np, env=env)
    policy.start()
    return policy
