"""Cycle-driven cross-call fusion scheduler for eager async collectives.

The TPU-native rebuild of the reference's headline performance mechanism:
not the collective itself but the background cycle that coalesces
independently-submitted small tensors into large fusion buffers
(``operations.cc:385-806``: the coordinator negotiates readiness, fuses
ready tensors into buffers bounded by ``HOROVOD_FUSION_THRESHOLD``, and
flushes every ``HOROVOD_CYCLE_TIME``). Before this module, every
``*_async`` call dispatched its own collective synchronously — a
per-parameter eager loop over 100 small gradients paid 100 negotiations
and 100 wire launches.

Here, ``allreduce_async`` / ``broadcast_async`` / ``allgather_async`` /
``grouped_allreduce_async`` / ``sparse_allreduce_async`` enqueue into
**per-signature pending queues** instead of dispatching immediately. A
queue is keyed like the dispatch plan cache: op kind / process set /
reduce op / pre+post scales / hierarchical flag / wire dtype (the
compression class), so everything in one queue is legal to fuse into one
grouped dispatch. A flush fires when

* pending bytes in a queue reach ``HVD_FUSION_THRESHOLD`` (trigger
  ``threshold``),
* ``HVD_CYCLE_TIME`` elapses on the queue's oldest entry — or
  ``HVD_PENDING_CYCLE_TIME`` while work is in flight (trigger ``cycle``;
  a dispatch keeps the scheduler "in flight" for one cycle window),
* total pending bytes across all queues exceed ``HVD_FUSION_MAX_PENDING``
  (backpressure; trigger ``backpressure``),
* the user observes a handle: ``Handle.poll()`` / ``Handle.synchronize()``
  (triggers ``poll`` / ``synchronize``), or
* a synchronization point drains everything: ``hvd.barrier()`` (trigger
  ``barrier``) or ``hvd.shutdown()`` (trigger ``shutdown``).

A flush coalesces the queue into ONE grouped dispatch through the
existing dispatch plan cache (``ops/dispatch_cache.py``) — steady-state
training loops therefore pay one plan hit per flush instead of one full
dispatch per parameter.

Determinism contract (the reference coordinator's role): flush
*composition* must be identical on every rank. Composition derives from
submission order and deterministic negotiation names only — never from
wall-clock:

* **Single-controller jobs** (one process drives every chip — the normal
  SPMD deployment): the one process's queue IS the global view, so any
  flush trigger yields a rank-consistent composition by construction.
* **Multi-process jobs** (a negotiation service is running): each entry
  is assigned a deterministic negotiation name at *submission* time
  (per-set counters, identical across processes running the same
  program). A flush batches the drained entries' negotiations into one
  ``negotiate_many`` round (one KV cycle for the whole flush — the
  queue's multi-process win) but keeps each entry's *program composition*
  exactly as submitted: singles stay single programs, grouped entries
  stay their group. That mirrors the active-path programs a joined rank
  reconstructs from response metadata (``_execute_joined_zeros``), so
  composition can never diverge across processes — timer jitter on one
  process only changes *when* entries negotiate, never *what* program
  runs.

**Pipelined flush executor** (``HVD_MAX_INFLIGHT_FLUSHES``, default 2):
flush triggers only *drain* a queue and hand the entry batch to a
dedicated dispatch thread with a bounded in-flight window, so flush k+1's
host-side fuse (and, in multi-process jobs, its ``negotiate_many`` round,
submitted at the trigger point via the split
:meth:`~horovod_tpu.engine_service.DynamicService.negotiate_many_submit`)
overlaps flush k's in-flight device collective instead of serializing
against the triggering thread's enqueues. The executor is deliberately a
SINGLE thread consuming a FIFO queue: slot admission order derives from
submission order only (never completion timing), which preserves
per-signature FIFO result order, the PR-2 rank-deterministic composition
contract, and — critically — a serial collective program issue order
(two threads interleaving the per-device enqueues of two collectives
deadlock the backend rendezvous; see ``ops/program_issue.py``). The
slots bound how many dispatched flushes may be device-incomplete at
once: admitting a batch past the window first blocks on the oldest
in-flight flush (GIL released — producers keep enqueueing).
``HVD_MAX_INFLIGHT_FLUSHES=0/1`` restores the synchronous
execute-on-the-triggering-thread behavior byte-for-byte. Fused wire
buffers past ``HVD_PIPELINE_THRESHOLD`` additionally dispatch as
``HVD_PIPELINE_CHUNKS`` chunk programs (``collectives._chunk_layout``,
docs/pipeline.md).

**Multi-tenant QoS** (``HVD_QOS=1``; ``horovod_tpu/qos.py``,
docs/qos.md): batches route through a strict-priority + deficit-round-
robin admission gate in front of the executor FIFO instead of being
appended directly — per-process-set tenants get priority tiers, byte-
weighted fair shares of the executor slots, and pending-bytes quotas
(``block`` backpressure at enqueue / ``shed`` with a typed
``QosAdmissionError`` on the handle). Grant order stays a pure function
of submission order + static QoS config (window pumps and handle-
observation releases at rank-deterministic program points; executor-
demand grants for single-controller batches only), so the composition
contract above survives tenancy. ``HVD_QOS=0`` (default) keeps this
whole path byte-for-byte.

Statistics surface through :func:`stats` (exported as
``hvd.fusion_stats()``; the ``pipeline`` block carries slot occupancy and
overlap ratio); the timeline gains ``QUEUE_ENQUEUE``, ``CYCLE_FLUSH``,
and ``INFLIGHT_DEPTH`` instant events plus ``PIPELINE_*`` stage spans.
The scheduler's off switch is ``HVD_CYCLE_TIME=0`` (immediate dispatch,
the pre-queue behavior).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque

import jax.numpy as jnp
from jax._src.core import trace_state_clean as _trace_state_clean
import numpy as np

from .. import autotune as _autotune
from .. import conformance as _conformance
from .. import metrics as _metrics
from .. import qos as _qos
from .. import timeline as _timeline
from ..utils import envs
from ..utils import faults as _faults
from ..utils import invariants as _inv
from ..utils import logging as hvd_logging
from . import dispatch_cache as _dispatch_cache
from . import step_capture as _step_capture

FLUSH_TRIGGERS = ("threshold", "cycle", "synchronize", "poll", "barrier",
                  "join", "shutdown", "backpressure", "name-reuse",
                  "bucket")

# In-flight window multiplier: after a dispatch the scheduler flushes at
# the PENDING_CYCLE_TIME pace for one cycle window (see _age_limit_s).
_INFLIGHT_WINDOW_CYCLES = 1.0


# Program spans (docs/timeline.md). A flush drained on one thread and
# executed on the ``hvd-flush-pipeline`` thread carries the same
# ``flush=<n>`` keyword in ``cycle.flush`` and ``cycle.execute``.
_FLUSH = _timeline.span("cycle.flush")
_SLOT_WAIT = _timeline.span("cycle.slot_wait", "PIPELINE_SLOT_WAIT",
                            lane=_timeline.PIPELINE_LANE)
_EXECUTE = _timeline.span("cycle.execute")
_WAIT_RESULT = _timeline.span("cycle.wait_result")

# Bound registry series for the enqueue/flush hot paths: label
# resolution paid once per (tenant, trigger), after which a sample is a
# dict update under the registry's leaf lock (docs/metrics.md overhead
# contract; benign rebind race under the GIL).
_PENDING_BYTES_G = _metrics.FUSION_PENDING_BYTES.bind()
_INFLIGHT_DEPTH_G = _metrics.PIPELINE_INFLIGHT_DEPTH.bind()
_tenant_series: dict = {}


def _tenant_metrics(tenant: str) -> dict:
    t = _tenant_series.get(tenant)
    if t is None:
        t = _tenant_series[tenant] = {
            "enqueued": _metrics.FUSION_ENQUEUED_TENSORS.bind(
                {"process_set": tenant}),
            "tensors": _metrics.FUSION_FLUSHED_TENSORS.bind(
                {"process_set": tenant}),
            "bytes": _metrics.FUSION_FLUSHED_BYTES.bind(
                {"process_set": tenant}),
            "flushes": {},  # trigger -> bound counter
        }
    return t


def _flush_counter(tm: dict, tenant: str, trigger: str):
    c = tm["flushes"].get(trigger)
    if c is None:
        c = tm["flushes"][trigger] = _metrics.FUSION_FLUSHES.bind(
            {"process_set": tenant, "trigger": trigger})
    return c


def _pset_label(pset) -> str:
    """Tenant label for the registry's per-process-set fusion counters
    AND the QoS class registry: the one derivation lives in
    ``qos.tenant_label`` (``engine_service._set_key`` with the global
    set spelled ``"global"``), so fusion counters, negotiation
    instruments, and QoS classes can never drift apart on a tenant's
    identity."""
    return _qos.tenant_label(pset)


def _qos_tenant_counter(tenant: str, kind: str):
    """Bound per-tenant QoS counter (``shed`` / ``blocks``), cached in
    the same per-tenant series map as the fusion counters."""
    tm = _tenant_metrics(tenant)
    c = tm.get("qos_" + kind)
    if c is None:
        inst = (_metrics.QOS_SHED if kind == "shed"
                else _metrics.QOS_QUOTA_BLOCKS)
        c = tm["qos_" + kind] = inst.bind({"process_set": tenant})
    return c


def enabled() -> bool:
    """The scheduler queues async ops whenever ``HVD_CYCLE_TIME`` > 0.
    ``HVD_CYCLE_TIME=0`` restores immediate per-call dispatch (the
    reference's cycle likewise stops coalescing at a zero cycle time)."""
    return envs.cycle_time_ms() > 0.0


def max_pending_bytes() -> int:
    """Backpressure cap on total queued bytes across all queues
    (``HVD_FUSION_MAX_PENDING``; default 4x the fusion threshold)."""
    return envs.get_int(envs.FUSION_MAX_PENDING,
                        4 * envs.fusion_threshold_bytes())


def pending_cycle_time_ms() -> float:
    """Flush pace while work is in flight (``HVD_PENDING_CYCLE_TIME``;
    default: half the cycle time, capped at 2 ms like the engine
    service's transport floor)."""
    cycle = envs.cycle_time_ms()
    return envs.get_float(envs.PENDING_CYCLE_TIME, min(cycle / 2.0, 2.0))


class _QueueSpec:
    """Immutable per-queue dispatch parameters, captured at first
    enqueue. ``kind`` is one of allreduce/broadcast/allgather/sparse."""

    __slots__ = ("kind", "pset", "axis", "op", "pre", "post", "root_rank",
                 "compression", "svc")

    def __init__(self, kind, pset, axis, op=None, pre=1.0, post=1.0,
                 root_rank=-1, compression=None, svc=None):
        self.kind = kind
        self.pset = pset
        self.axis = axis
        self.op = op
        self.pre = pre
        self.post = post
        self.root_rank = root_rank
        self.compression = compression
        self.svc = svc


class _Entry:
    """One queued ``*_async`` submission: a single tensor or an atomic
    group (grouped entries never split across flushes). ``requests`` are
    the pre-built negotiation dicts (multi-process jobs only; names
    assigned at submission time so every process generates the same
    sequence). ``run`` is the opaque executor for sparse entries."""

    __slots__ = ("tensors", "count", "grouped", "nbytes", "names",
                 "requests", "run", "queue_key", "label", "event",
                 "results", "error", "sigs", "captured", "qos_tenant",
                 "qos_acked", "qos_inflight", "qos_epoch")

    def __init__(self, tensors, grouped, nbytes, names, requests=(),
                 run=None, label=""):
        self.tensors = tensors
        self.count = len(tensors)
        self.grouped = grouped
        self.nbytes = nbytes
        self.names = tuple(names)
        self.requests = tuple(requests)
        self.run = run
        self.queue_key = None
        self.label = label or (names[0] if names else "queued")
        self.event = _inv.make_event("fusion_cycle.entry")
        self.results = None
        self.error = None
        # normalized per-tensor plan signatures (step capture templates);
        # None = unplannable entry (opaque/sparse), never capturable
        self.sigs = None
        self.captured = False  # held by a step-capture replay
        # multi-tenant QoS accounting (docs/qos.md): the entry's tenant
        # label, whether its unacked bytes were released (synchronize
        # return), whether it currently charges granted-but-unsettled
        # bytes (set at executor admission, cleared at settle), and the
        # scheduler quota epoch it was charged under — abort() bumps
        # the epoch when it zeroes the accounting, so a stale ack or
        # settle from a pre-abort entry can never deflate charges made
        # by post-abort submissions
        self.qos_tenant = None
        self.qos_acked = False
        self.qos_inflight = False
        self.qos_epoch = 0

    @property
    def done(self) -> bool:
        return self.event.is_set()


class _Queue:
    __slots__ = ("spec", "entries", "nbytes", "oldest_t", "names")

    def __init__(self, spec):
        self.spec = spec
        self.entries: list[_Entry] = []
        self.nbytes = 0
        self.oldest_t = 0.0
        self.names: set = set()  # pending negotiation names (O(1) clash check)


class _Batch:
    """One drained flush handed to the pipelined executor: the queue's
    spec, its entries in submission order, the trigger that drained it,
    and — for multi-process queues — the negotiation ticket submitted at
    the (rank-deterministic) trigger point so the KV round overlaps
    earlier in-flight flushes. ``flush`` is the drain's sequence number
    (``FusionScheduler._flush_numbers``; 0 for a batch built elsewhere)."""

    __slots__ = ("spec", "entries", "trigger", "ticket", "flush")

    def __init__(self, spec, entries, trigger, ticket=None, flush=0):
        self.spec = spec
        self.entries = entries
        self.trigger = trigger
        self.ticket = ticket
        self.flush = flush  # flush_queue's number, for the spans' join


class FusionScheduler:
    """Owns the pending queues, the cycle timer thread, and the flush
    statistics. Normally a process-wide singleton (:func:`scheduler`);
    tests instantiate fresh ones to check composition determinism."""

    def __init__(self):
        self._mu = _inv.make_lock("fusion_cycle.scheduler.mu")
        self._queues: "OrderedDict[tuple, _Queue]" = OrderedDict()
        self._pending_tensors = 0
        self._pending_bytes = 0
        self._wake = _inv.make_event("fusion_cycle.scheduler.wake")
        self._stop = _inv.make_event("fusion_cycle.scheduler.stop")
        self._thread: threading.Thread | None = None
        self._inflight_until = 0.0
        self._stats = {
            "enqueued_tensors": 0,
            "enqueued_bytes": 0,
            "flushed_tensors": 0,
            "flushed_bytes": 0,
            "dispatches": 0,
            "wire_programs": 0,
            "flushes": {t: 0 for t in FLUSH_TRIGGERS},
        }
        # (trigger, queue key, entry names) per flush — the composition
        # record the determinism tests compare across schedulers.
        self.flush_history: deque = deque(maxlen=64)
        # -- pipelined flush executor state (see _exec_loop) --
        self._exec_cv = _inv.make_condition("fusion_cycle.scheduler.exec_cv")
        self._exec_q: "deque[_Batch]" = deque()
        self._exec_busy = False
        self._exec_stop = False
        self._exec_thread: threading.Thread | None = None
        self._exec_inflight: deque = deque()  # result leaves per batch
        self._exec_names: set = set()  # svc names submitted, not yet done
        self._pstats = {
            "submitted": 0, "executed": 0, "overlapped": 0,
            "depth_sum": 0, "inflight_peak": 0, "slot_waits": 0,
            "device_wait_ms": 0.0,
        }
        # numbers the drains (1, 2, ...): the ``flush`` keyword that
        # joins a cycle.flush span to its cycle.execute span
        self._flush_numbers = itertools.count(1)
        # -- multi-tenant QoS state (qos.py; all guarded by _exec_cv) --
        # admission gate (lazy: created at the first submission with
        # HVD_QOS=1), per-tenant unacknowledged bytes (enqueue ->
        # synchronize return; the rank-deterministic shed measure) and
        # granted-but-unsettled bytes (executor admission -> settle;
        # the block-policy backpressure measure)
        self._qos_gate = None
        self._qos_unacked: dict[str, float] = {}
        self._qos_inflight: dict[str, float] = {}
        self._qos_epoch = 0  # bumped by abort(); guards stale releases
        self._qos_stats = {"shed": {}, "quota_blocks": 0}
        # step capture-and-replay controller (HVD_STEP_CAPTURE;
        # ops/step_capture.py): records the marked step's flush stream,
        # then replays the whole step as one cached program
        self.capture = _step_capture.CaptureState(self)

    # -- enqueue -----------------------------------------------------------

    def enqueue(self, key: tuple, spec: _QueueSpec, entry: _Entry) -> None:
        # A flush execution must never re-enter the scheduler: on the
        # synchronous path it would self-deadlock on _mu, on the pipelined
        # path it would corrupt flush composition mid-drain.
        _inv.assert_outside("fusion-cycle-flush", "FusionScheduler.enqueue")
        entry.queue_key = key
        # Step replay intake: a submission matching the armed captured
        # stream is HELD for the whole-step program instead of queued;
        # a mismatch falls back to eager transparently (offer returns
        # False and the entry takes the normal path below).
        if self.capture.offer(key, spec, entry):
            return
        if _qos.enabled():
            tenant = _pset_label(spec.pset)
            entry.qos_tenant = tenant
            cls = _qos.get_class(tenant)
            if not self._qos_admit(entry, tenant, cls):
                return  # shed: the handle raises QosAdmissionError
        if entry.requests:
            # Multi-process entries negotiate the whole flush in ONE
            # negotiate_many batch, whose duplicate-name guard only spans
            # batches — a user-named submission repeating a name already
            # pending in the same queue would silently orphan the first
            # request and stall the flush. Flush the queue first so the
            # two negotiations stay sequential, like immediate dispatch.
            # With the pipelined executor the earlier submission's
            # negotiation may also still be in flight downstream of its
            # flush — quiesce the pipeline before reusing the name.
            with self._mu:
                q = self._queues.get(key)
                clash = q is not None and not q.names.isdisjoint(entry.names)
            with self._exec_cv:
                exec_clash = not self._exec_names.isdisjoint(entry.names)
            if clash:
                self.flush_queue(key, "name-reuse")
            if clash or exec_clash:
                # A clashing batch may be parked in the QoS admission
                # gate (names register at drain, before the grant):
                # force-grant it, or the wait below parks forever.
                gate = self._qos_gate
                if gate is not None:
                    gate.release_names(entry.names)
                # Wait for the clashing names specifically (not just an
                # executor quiesce): the earlier flush may still be
                # between its _mu-side name registration and its batch
                # submission, where the executor queue looks idle.
                self._wait_names_clear(entry.names)
        with self._mu:
            _inv.assert_holding(self._mu, "pending-queue mutation (enqueue)")
            q = self._queues.get(key)
            if q is None:
                q = _Queue(spec)
                q.oldest_t = _inv.monotonic()
                self._queues[key] = q
            q.entries.append(entry)
            q.names.update(entry.names)
            q.nbytes += entry.nbytes
            self._pending_tensors += entry.count
            self._pending_bytes += entry.nbytes
            self._stats["enqueued_tensors"] += entry.count
            self._stats["enqueued_bytes"] += entry.nbytes
            pending_bytes = self._pending_bytes
            over_threshold = q.nbytes >= envs.fusion_threshold_bytes()
            over_pending = self._pending_bytes >= max_pending_bytes()
            self._ensure_thread_locked()
        _tenant_metrics(_pset_label(spec.pset))["enqueued"].inc(entry.count)
        _PENDING_BYTES_G.set(pending_bytes)
        for name in entry.names:
            _timeline.record_queue_enqueue(name or entry.label)
        self._wake.set()
        if over_pending:
            if _qos.enabled() and entry.qos_tenant is not None and \
                    _qos.get_class(entry.qos_tenant).quota > 0:
                # QoS backpressure for a QUOTA'D tenant: drain back
                # under the cap (LOWEST tier first — the bulk backlog
                # is what moves out; latency tenants' queues drain at
                # their own synchronize) WITHOUT the flush_all
                # gate-release + quiesce — quiescing would block THIS
                # producer (possibly a latency tenant) on the whole
                # bulk backlog's execution, the exact inversion QoS
                # exists to prevent. The producer's memory stays
                # bounded by its OWN quota instead of by the stall. A
                # tenant with quota=0 (unlimited) has opted out of
                # that bound, so it keeps the legacy producer-stalling
                # flush_all below — otherwise nothing would bound it
                # at all (docs/qos.md "Interactions").
                self._drain_queues("backpressure",
                                   until_under=max_pending_bytes() // 2)
            else:
                # Backpressure: drain everything oldest-first so memory
                # held by pending wire payloads stays bounded.
                self.flush_all("backpressure")
        elif over_threshold:
            self.flush_queue(key, "threshold")

    # -- QoS admission control (docs/qos.md) -------------------------------

    def _qos_admit(self, entry: _Entry, tenant: str, cls) -> bool:
        """Per-tenant pending-bytes quota at enqueue. ``shed`` consults
        the unacknowledged-bytes measure — enqueue minus synchronize
        returns, both rank-deterministic stream points, so every member
        rank sheds the identical submissions — and fails the handle
        with :class:`QosAdmissionError`. ``block`` waits for
        granted-but-unsettled bytes to drop: work the executor WILL
        settle without any action from this (blocked) producer, and the
        wait never mutates the admission gate (a completion-timed grant
        would desynchronize the cross-rank grant order — the
        determinism contract's one forbidden move, and the planted
        priority-inversion shape hvdsched's qos-inversion-demo finds).
        Admission CHARGES the tenant's unacked bytes in the same
        critical section as the shed check — a separate check-then-
        reserve would let two same-tenant producer threads both pass
        against the same pending value and jointly overshoot the quota.
        Returns False when the entry was shed."""
        from ..exceptions import QosAdmissionError
        if cls.policy == "shed" and cls.quota > 0:
            with self._exec_cv:
                pending = self._qos_unacked.get(tenant, 0.0)
                if pending + entry.nbytes <= cls.quota:
                    self._qos_unacked[tenant] = pending + entry.nbytes
                    entry.qos_epoch = self._qos_epoch
                    return True
                shed = self._qos_stats["shed"]
                shed[tenant] = shed.get(tenant, 0) + 1
            # never charged: a synchronize() on the shed handle must not
            # deflate the unacked measure (the quota would leak headroom
            # equal to every shed-then-observed submission's size)
            entry.qos_acked = True
            entry.error = QosAdmissionError(tenant, entry.nbytes,
                                            int(pending), cls.quota)
            entry.tensors = ()
            entry.run = None
            entry.event.set()
            _qos_tenant_counter(tenant, "shed").inc()
            _timeline.record_qos("SHED", tenant)
            return False
        blocked = False
        with self._exec_cv:
            if cls.policy == "block" and cls.quota > 0:
                while True:
                    # granted-but-unsettled bytes PLUS parked single-
                    # controller bytes: both drain via the executor
                    # (settles and demand pulls) with no action from
                    # this blocked producer, so the wait cannot
                    # deadlock — while without the parked component a
                    # single-controller flood's backlog would sit in
                    # the gate unbounded, never engaging the quota.
                    # Parked NEGOTIATED bytes stay excluded (window-
                    # bounded; grantable only at deterministic points a
                    # blocked producer never reaches).
                    pending = self._qos_inflight.get(tenant, 0.0)
                    if self._qos_gate is not None:
                        pending += self._qos_gate.sc_parked_bytes_locked(
                            tenant)
                    # an entry larger than the quota admits once the
                    # tenant is fully drained — blocking would wait
                    # forever
                    if (pending <= 0.0
                            or pending + entry.nbytes <= cls.quota):
                        break
                    if not blocked:
                        blocked = True
                        self._qos_stats["quota_blocks"] += 1
                    # plain wait: grants (_emit_batch_locked),
                    # _qos_settle, and abort() all notify _exec_cv
                    self._exec_cv.wait()
            self._qos_unacked[tenant] = (
                self._qos_unacked.get(tenant, 0.0) + entry.nbytes)
            entry.qos_epoch = self._qos_epoch
        if blocked:
            _qos_tenant_counter(tenant, "blocks").inc()
            _timeline.record_qos("BLOCK", tenant)
        return True

    def _qos_ack(self, entry: _Entry) -> None:
        """Release the entry's unacknowledged bytes at a synchronize
        return (idempotent) — the deterministic retirement point of the
        shed measure. The acked test-and-set sits under ``_exec_cv``:
        two threads synchronizing one handle concurrently must not
        double-release the bytes (the per-op clamp would hide the
        tenant total undercounting, permanently leaking quota
        headroom)."""
        if entry.qos_tenant is None:
            return
        with self._exec_cv:
            if entry.qos_acked:
                return
            entry.qos_acked = True
            if entry.qos_epoch != self._qos_epoch:
                return  # charged under a world abort() already zeroed
            t = entry.qos_tenant
            self._qos_unacked[t] = max(
                0.0, self._qos_unacked.get(t, 0.0) - entry.nbytes)

    def _qos_settle(self, entries) -> None:
        """Release granted-but-unsettled bytes once entries settle (the
        block-policy quota's wait condition)."""
        charged = [e for e in entries if e.qos_inflight]
        if not charged:
            return
        with self._exec_cv:
            for e in charged:
                e.qos_inflight = False
                if e.qos_epoch != self._qos_epoch:
                    continue  # abort() already zeroed this charge
                t = e.qos_tenant
                self._qos_inflight[t] = max(
                    0.0, self._qos_inflight.get(t, 0.0) - e.nbytes)
            self._exec_cv.notify_all()

    # -- flushing ----------------------------------------------------------

    def flush_queue(self, key: tuple, trigger: str) -> None:
        """Flush one queue (no-op when it is already drained/being
        flushed by another thread — the entry events carry completion).

        With the pipelined executor on, this only DRAINS the queue,
        records the flush composition, and submits the batch — execution
        happens on the executor thread, so the triggering thread (a
        producer hitting the threshold, the cycle timer, a synchronize)
        returns immediately and flush k+1's enqueues overlap flush k's
        fuse/negotiate/collective. ``HVD_MAX_INFLIGHT_FLUSHES<=1``
        executes inline, the pre-pipeline behavior."""
        if key not in self._queues:
            # nothing pending (the usual case when a handle's flush or
            # synchronize follows a threshold/bucket drain): no span
            return
        flush = next(self._flush_numbers)
        with _FLUSH(trigger=trigger, flush=flush):
            pipelined = envs.pipeline_enabled()
            with self._mu:
                _inv.assert_holding(self._mu, "pending-queue mutation (drain)")
                q = self._queues.pop(key, None)
                if q is None or not q.entries:
                    return
                entries = q.entries
                self._pending_tensors -= sum(e.count for e in entries)
                self._pending_bytes -= q.nbytes
                self._stats["flushes"][trigger] += 1
                self._stats["flushed_tensors"] += sum(e.count for e in entries)
                self._stats["flushed_bytes"] += q.nbytes
                names = tuple(n for e in entries for n in e.names)
                self.flush_history.append((trigger, key, names))
                # Lockstep decision point (docs/conformance.md): the flush
                # composition every rank must derive identically. The
                # trigger is deliberately NOT hashed — WHEN a queue drains
                # may vary across ranks (timer jitter); WHAT drains may not.
                _conformance.record(
                    "ops/fusion_cycle.py::FusionScheduler.flush_queue",
                    "flush", (q.spec.kind, names))
                self._inflight_until = _inv.monotonic() + (
                    _INFLIGHT_WINDOW_CYCLES * envs.cycle_time_ms() / 1e3)
                if pipelined:
                    # Register svc names with the executor's guard set in the
                    # SAME critical section that removes them from q.names —
                    # a producer reusing a name can then never observe the
                    # window between the drain and the batch submission
                    # (enqueue's clash check reads both sets). _mu -> _exec_cv
                    # nesting is one-way; no path nests them in reverse.
                    svc_names = {n for e in entries if e.requests
                                 for n in e.names}
                    if svc_names:
                        with self._exec_cv:
                            self._exec_names.update(svc_names)
                pending_bytes = self._pending_bytes
            tenant = _pset_label(q.spec.pset)
            tm = _tenant_metrics(tenant)
            _flush_counter(tm, tenant, trigger).inc()
            tm["tensors"].inc(sum(e.count for e in entries))
            tm["bytes"].inc(q.nbytes)
            _PENDING_BYTES_G.set(pending_bytes)
            _timeline.record_cycle_flush(trigger)
            # Step capture recording: composition noted at the drain point
            # (submission order), while the entries still hold their tensors.
            self.capture.note_flush(q.spec, entries, trigger)
            if not pipelined:
                self._execute(q.spec, entries, flush=flush)
                return
            ticket = None
            if (q.spec.svc is not None and q.spec.kind in ("allreduce",
                                                           "broadcast")):
                # Overlapped negotiation: submit the whole flush's requests
                # NOW, at the rank-deterministic trigger point (preserving
                # the PR-2 negotiation-order contract), and let the executor
                # wait for the responses only when it reaches this batch —
                # the KV round trip then runs under flush k's collective.
                reqs = [r for e in entries for r in e.requests]
                if reqs:
                    try:
                        # Statically reachable from the cycle timer, but the
                        # timer never flushes svc queues (_loop skips them);
                        # only rank-deterministic user-thread triggers reach
                        # this negotiation submit.
                        ticket = q.spec.svc.negotiate_many_submit(reqs)  # hvdlint: disable=timer-purity
                    except BaseException as exc:
                        with self._exec_cv:  # batch never reaches the
                            # executor; release its guard names
                            self._exec_names.difference_update(
                                n for e in entries for n in e.names)
                            self._exec_cv.notify_all()
                        self._fail_entries(entries, exc)
                        hvd_logging.error(
                            "fusion cycle negotiation submit failed: %s", exc)
                        if not isinstance(exc, Exception):
                            raise
                        return
            self._submit(_Batch(q.spec, entries, trigger, ticket, flush))

    def flush_entry(self, entry: _Entry, trigger: str) -> None:
        if entry.done or entry.queue_key is None:
            return
        # A capture-held entry dispatches with the whole-step program
        # (or falls back eagerly right here when the trigger blocks
        # before the stream completed) — never through its queue.
        if self.capture.intercept_flush(entry, trigger):
            return
        self.flush_queue(entry.queue_key, trigger)
        # Handle observation is a rank-deterministic program point: if
        # the entry's batch is parked in the QoS admission gate, grant
        # it now (every rank's gate jumps at the same stream point, so
        # the cross-rank grant order stays identical — docs/qos.md).
        gate = self._qos_gate
        if gate is not None:
            gate.release_entry(entry)

    def _drain_queues(self, trigger: str, until_under: int | None = None
                      ) -> None:
        """Drain pending queues — first-enqueue order, or highest QoS
        tier first with HVD_QOS=1 (high-priority work negotiates and
        parks ahead of bulk backlogs; deterministic: the pending set at
        a drain point is a pure function of the submission stream).
        ``until_under`` stops once total pending bytes fall to/below it
        (the QoS backpressure path: drain the MINIMUM that restores the
        cap, instead of chasing an always-refilling backlog on whatever
        producer thread — possibly a latency tenant's — happened to
        cross it); a bounded drain evicts the LOWEST tier first — the
        bulk backlog is what backpressure exists to move out, and a
        latency tenant's queue is about to drain at its own synchronize
        anyway."""
        qos_on = _qos.enabled()
        bounded = until_under is not None
        while True:
            with self._mu:
                if bounded and self._pending_bytes <= until_under:
                    return
                key = None
                if qos_on:
                    best = None
                    for i, (k, q) in enumerate(self._queues.items()):
                        tier = _qos.get_class(
                            _pset_label(q.spec.pset)).priority
                        rank_key = (tier if bounded else -tier, i)
                        if best is None or rank_key < best:
                            best, key = rank_key, k
                else:
                    key = next(iter(self._queues), None)
            if key is None:
                return
            self.flush_queue(key, trigger)

    def flush_all(self, trigger: str) -> None:
        """Drain every queue (:meth:`_drain_queues`), then release the
        QoS admission gate and quiesce the pipelined executor (barrier /
        shutdown / backpressure): callers of flush_all need everything
        *dispatched* on return — a barrier psum issued before a
        still-queued flush's programs would break the cross-process
        program issue order."""
        self._drain_queues(trigger)
        # a replay caught mid-stream must dispatch its held prefix too
        self.capture.flush_pending(trigger)
        gate = self._qos_gate
        if gate is not None:
            gate.release_all()
        self.quiesce()

    def wait_result(self, entry: _Entry):
        """Synchronize path: flush the entry's queue if still pending,
        wait for its dispatch, re-raise any flush failure."""
        with _WAIT_RESULT(entry.label):
            return self._wait_result(entry)

    def wait_results(self, entries) -> list:
        """:meth:`wait_result` for a handle over many entries (a grouped
        broadcast), in submission order, as ONE span."""
        with _WAIT_RESULT(entries=len(entries)):
            return [self._wait_result(e) for e in entries]

    def _wait_result(self, entry: _Entry):
        self.flush_entry(entry, "synchronize")
        entry.event.wait()
        self._qos_ack(entry)
        if entry.error is not None:
            raise entry.error
        return entry.results

    def poll_entry(self, entry: _Entry) -> bool:
        """Poll path: an unflushed entry must first trigger its own flush
        (otherwise ``poll()`` on a queued handle would spin forever), then
        report whether the dispatch has landed."""
        self.flush_entry(entry, "poll")
        return entry.done

    # -- pipelined flush executor ------------------------------------------

    def _submit(self, batch: _Batch) -> None:
        # svc entry names were already registered in _exec_names by
        # flush_queue, inside the same _mu section that drained them from
        # q.names — THAT registration is the load-bearing one (no window
        # for a reused name to slip through); this method only queues.
        # With HVD_QOS=1 the batch routes through the admission gate
        # instead: it parks per tenant and the arbiter grants it into
        # the executor FIFO (window pump here, demand pull in
        # _exec_loop, forced release at handle observation).
        if _qos.enabled():
            with self._exec_cv:
                if self._qos_gate is None:
                    self._qos_gate = _qos.QosGate(
                        self._exec_cv, self._emit_batch_locked,
                        on_park=self._ensure_exec_thread_locked)
                gate = self._qos_gate
            tenant = _pset_label(batch.spec.pset)
            gate.submit(batch, tenant, _qos.get_class(tenant))
            return
        with self._exec_cv:
            self._emit_batch_locked(batch)

    def _ensure_exec_thread_locked(self) -> None:
        """Spawn the executor thread if needed (callers hold
        ``_exec_cv``). Also the QoS gate's ``on_park`` hook: a parked
        single-controller batch grants ONLY on executor demand, so the
        executor must exist the moment the gate holds work."""
        if self._exec_thread is None or not self._exec_thread.is_alive():
            self._exec_stop = False
            self._exec_thread = _inv.spawn_thread(
                self._exec_loop, name="hvd-flush-pipeline")

    def _emit_batch_locked(self, batch: _Batch) -> None:
        """Append one batch to the executor FIFO (callers hold
        ``_exec_cv``) — the executor admission point, where QoS
        granted-but-unsettled bytes are charged."""
        for e in batch.entries:
            if e.qos_tenant is not None:
                e.qos_inflight = True
                self._qos_inflight[e.qos_tenant] = (
                    self._qos_inflight.get(e.qos_tenant, 0.0) + e.nbytes)
        self._exec_q.append(batch)
        self._pstats["submitted"] += 1
        self._ensure_exec_thread_locked()
        self._exec_cv.notify_all()

    def _exec_loop(self) -> None:
        """The dedicated dispatch thread: one batch at a time, in strict
        submission (FIFO) order — slot admission order derives from
        submission order only, never from completion timing, so the flush
        composition AND the collective program issue order are identical
        for identical call streams (and concurrent collective launches,
        which deadlock the backend rendezvous, cannot happen between two
        queued flushes by construction)."""
        while True:
            with self._exec_cv:
                while not self._exec_q:
                    if self._exec_stop:
                        return
                    # QoS demand pull: a dry FIFO grants the fair-order
                    # pick among parked SINGLE-CONTROLLER batches
                    # (work-conserving priority scheduling; negotiated
                    # batches only grant at rank-deterministic points —
                    # docs/qos.md determinism contract)
                    if (self._qos_gate is not None
                            and self._qos_gate.demand_pull_locked()):
                        continue
                    # plain wait, no poll timeout: every producer path
                    # (submit, abort, stop) notifies under _exec_cv, so an
                    # idle pipeline sleeps instead of waking twice a second
                    self._exec_cv.wait()
                batch = self._exec_q.popleft()
                self._exec_busy = True
            try:
                try:
                    self._admit_slot()
                except BaseException:
                    # a failed earlier flush raises at block_until_ready;
                    # its entries already carry results — the error
                    # surfaces at THEIR synchronize, not this batch's
                    self._exec_inflight.clear()
                try:
                    self._execute(batch.spec, batch.entries, batch.ticket,
                                  batch.flush)
                except BaseException:
                    # entries were already marked failed by _execute; a
                    # KeyboardInterrupt on the daemon executor is spurious
                    # and must not kill the pipeline
                    hvd_logging.exception("pipelined flush failed")
                try:
                    self._track_inflight(batch.entries)
                except BaseException:  # accounting must never stall the
                    hvd_logging.exception("in-flight tracking failed")
            finally:
                with self._exec_cv:
                    self._exec_busy = False
                    self._pstats["executed"] += 1
                    for e in batch.entries:
                        if e.requests:
                            self._exec_names.difference_update(e.names)
                    self._exec_cv.notify_all()

    def _admit_slot(self) -> None:
        """Bound the in-flight window: at most ``HVD_MAX_INFLIGHT_FLUSHES``
        dispatched-but-device-incomplete flushes. Admission past the
        window blocks on the OLDEST in-flight flush (FIFO retirement —
        completion timing never reorders anything).

        Overlap metrics are sampled *before* eager retirement — the
        pre-ISSUE-6 accounting retired completed flushes first, so it
        read depth 0 whenever device completion beat the next admission,
        under-reporting any overlap that did happen. Two samples with
        distinct meanings: ``inflight_peak`` is the ADMISSION-time depth
        (pipeline pressure as the batch arrives at the window), while
        ``overlapped`` uses the POST-BLOCKING depth — a flush that had
        to wait out every predecessor before dispatching (slots=1, the
        documented synchronous mode) did not overlap anything and must
        not count. Slot-blocking time accumulates into
        ``device_wait_ms`` so a pipeline stalled on device completion is
        visible in ``fusion_stats()["pipeline"]`` instead of hiding
        inside dispatch wall time."""
        import jax
        # The in-flight window deque is executor-private state: only the
        # single dispatch thread may touch it (stop() clears it after the
        # thread is joined).
        _inv.assert_thread(self._exec_thread,
                           "in-flight window admission (_admit_slot)")
        slots = max(envs.max_inflight_flushes(), 1)

        def _done(leaves) -> bool:
            return all(getattr(l, "is_ready", lambda: True)()
                       for l in leaves)

        # admission-time sample, pre-retirement: earlier flushes still in
        # flight on device as this batch arrives at the window (pipeline
        # pressure — with 2 slots a saturated stream reads 2 here)
        depth = sum(1 for leaves in self._exec_inflight
                    if not _done(leaves))
        while self._exec_inflight and _done(self._exec_inflight[0]):
            self._exec_inflight.popleft()  # retire completed without blocking
        waited = False
        wait_s = 0.0
        while len(self._exec_inflight) >= slots:
            leaves = self._exec_inflight.popleft()
            waited = True
            t0 = _inv.monotonic()
            with _SLOT_WAIT():
                jax.block_until_ready(leaves)  # GIL released: producers run on
            wait_s += _inv.monotonic() - t0
        # overlap sample, post-blocking: a flush only counts as
        # OVERLAPPED if an earlier flush is still device-incomplete when
        # it actually dispatches — i.e. after slot admission released it.
        # Counting the pre-block depth would report overlap_ratio ~1.0
        # for a slots=1 saturated stream, whose every dispatch waited out
        # its predecessor (the documented synchronous mode).
        live = sum(1 for leaves in self._exec_inflight
                   if not _done(leaves))
        # window depth after retirement/blocking: what actually remains
        # in the slot window alongside the admitted batch (occupancy)
        window_depth = len(self._exec_inflight)
        with self._exec_cv:
            self._pstats["depth_sum"] += window_depth
            if live > 0:
                self._pstats["overlapped"] += 1
            if depth > self._pstats["inflight_peak"]:
                self._pstats["inflight_peak"] = depth
            if waited:
                self._pstats["slot_waits"] += 1
                self._pstats["device_wait_ms"] += wait_s * 1e3
        _INFLIGHT_DEPTH_G.set(depth)
        _timeline.record_inflight_depth(depth)

    def _track_inflight(self, entries: list[_Entry]) -> None:
        import jax
        _inv.assert_thread(self._exec_thread,
                           "in-flight window tracking (_track_inflight)")
        leaves = []
        for e in entries:
            for r in (e.results or ()):
                arr = getattr(r, "array", r)  # PerRank carries .array
                leaves.extend(x for x in jax.tree.leaves(arr)
                              if hasattr(x, "is_ready"))
        if leaves:
            # a batch with no readiness-bearing leaves (results already
            # materialized, or a failed dispatch) never occupies a slot
            self._exec_inflight.append(leaves)

    def quiesce(self) -> None:
        """Block until every submitted batch has been dispatched (entry
        events set; device completion is the slots'/handles' business).
        Safe to call with nothing pending; no-op from the executor thread
        itself (an executor-side dispatch can never wait on itself)."""
        if threading.current_thread() is self._exec_thread:
            return
        with self._exec_cv:
            while self._exec_q or self._exec_busy:
                # plain wait: _submit and the executor's batch-complete
                # finally block both notify under _exec_cv
                self._exec_cv.wait()

    def _wait_names_clear(self, names) -> None:
        """Block until none of ``names`` is tracked as an in-flight svc
        negotiation (name-reuse guard): covers the whole span from the
        drain-side registration through batch execution — including the
        submission window where the executor queue itself looks idle.
        With QoS on, every wakeup re-attempts the gate release: the
        clashing batch can PARK only after this waiter's enqueue-side
        release attempt (names register at drain, before the
        negotiate-submit round trip that precedes the park), and a
        parked batch under the arbitration window would otherwise never
        grant while its only observer sits here."""
        if threading.current_thread() is self._exec_thread:
            return
        names = set(names)
        with self._exec_cv:
            while not self._exec_names.isdisjoint(names):
                if self._qos_gate is not None:
                    self._qos_gate.release_names_locked(names)
                    if self._exec_names.isdisjoint(names):
                        break
                # plain wait: every path that removes names (batch
                # completion, abort, submit failure) notifies under
                # _exec_cv — and gate.submit notifies on every park
                self._exec_cv.wait()

    # -- execution ---------------------------------------------------------

    def _fail_entries(self, entries: list[_Entry], exc) -> None:
        """Mark every undelivered entry so waiters unblock (the error
        re-raises at synchronize())."""
        failed = []
        for e in entries:
            if not e.done:
                e.error = exc
                e.tensors = ()
                e.run = None
                e.event.set()
                failed.append(e)
        self._qos_settle(failed)

    def _execute(self, spec: _QueueSpec, entries: list[_Entry],
                 ticket=None, flush: int = 0) -> None:
        with _inv.section("fusion-cycle-flush"), \
                _dispatch_cache.dispatch_source("flush"), \
                _EXECUTE(flush=flush, kind=spec.kind, entries=len(entries),
                         bytes=sum(e.nbytes for e in entries)):
            self._execute_inner(spec, entries, ticket)

    def _execute_inner(self, spec: _QueueSpec, entries: list[_Entry],
                       ticket=None) -> None:
        try:
            # Chaos seam for the flush pipeline: an injected error here
            # exercises the _fail_entries path (entries marked failed,
            # waiters unblocked, handles raise at synchronize) exactly
            # like a real dispatch failure. No-op with HVD_FAULT_SPEC
            # unset (cached-bool fast path in utils/faults.py).
            _faults.inject("exec.dispatch")
            if spec.kind == "sparse":
                units = [[e] for e in entries]
                self._dispatch_units(units, self._run_opaque_unit)
            elif spec.kind == "allgather":
                units = [[e] for e in entries]
                self._dispatch_units(
                    units, lambda unit: self._run_allgather_unit(spec, unit))
            elif spec.svc is None:
                # Single-controller flush: ONE grouped dispatch for the
                # whole queue, through the dispatch plan cache — repeated
                # flush signatures go straight to the compiled programs.
                self._dispatch_units(
                    [entries], lambda unit: self._run_fused_unit(spec, unit))
            else:
                self._execute_negotiated(spec, entries, ticket)
        except BaseException as exc:
            self._fail_entries(entries, exc)
            hvd_logging.error("fusion cycle flush failed: %s", exc)
            if not isinstance(exc, Exception):
                # KeyboardInterrupt/SystemExit must interrupt the caller
                # (user-thread flushes run inside enqueue/synchronize);
                # the timer and executor loops catch it separately and
                # survive.
                raise

    def _dispatch_units(self, units, run_unit) -> None:
        """THE shared dispatch helper: a flush is a list of wire dispatch
        *units* (each a list of entries whose tensors travel together in
        one wire batch). Single-controller flushes are one unit; the
        multi-process allreduce path is one unit per entry (submission-
        time composition, matching the joined-rank reconstruction);
        allgather/sparse are per-entry by nature. Dispatch accounting is
        therefore uniform across modes: ``dispatches`` counts FLUSH-level
        dispatch rounds (so the coalesce ratio means the same thing in
        single-controller and multi-process jobs) and ``wire_programs``
        counts the actual program batches issued."""
        settled = []
        try:
            for unit in units:
                outs = run_unit(unit)
                i = 0
                for e in unit:
                    e.results = list(outs[i:i + e.count])
                    i += e.count
                    e.tensors = ()  # release inputs: handles keep results
                    e.run = None
                    settled.append(e)
        except BaseException:
            # a later unit failing must not poison earlier units whose
            # wire programs already ran (peers counted them as done):
            # settle the completed entries with their results before the
            # error reaches _fail_entries (which skips done entries)
            for e in settled:
                e.event.set()
            self._qos_settle(settled)
            raise
        with self._mu:
            self._stats["dispatches"] += 1
            self._stats["wire_programs"] += len(units)
        # Events last, results and stats first: the moment ANY waiter
        # wakes, the whole flush's accounting is final (a synchronize on
        # one entry of a batch used to race the remaining event sets and
        # the stats bump — observable as a peer entry briefly "not done"
        # after its batch already executed).
        for e in settled:
            e.event.set()
        self._qos_settle(settled)

    def _run_fused_unit(self, spec: _QueueSpec, unit: list[_Entry]) -> list:
        from . import collectives as _coll
        tensors = [t for e in unit for t in e.tensors]
        if spec.kind == "allreduce":
            return _coll.grouped_allreduce(
                tensors, op=spec.op, process_set=spec.pset,
                prescale_factor=spec.pre, postscale_factor=spec.post,
                axis_name=spec.axis, compression=spec.compression)
        return _coll.grouped_broadcast(
            tensors, spec.root_rank, process_set=spec.pset,
            axis_name=spec.axis)

    def _run_allgather_unit(self, spec: _QueueSpec,
                            unit: list[_Entry]) -> list:
        """Allgather entries dispatch per-entry in submission order (the
        engine's recv_splits can resize the program per call, so there is
        no fused multi-tensor gather program to coalesce into); the queue
        still defers them to the cycle so they overlap submission-side
        Python with in-flight device work."""
        from . import collectives as _coll
        e, = unit
        return [_coll.allgather(e.tensors[0], process_set=spec.pset,
                                axis_name=spec.axis, name=e.names[0])]

    def _run_opaque_unit(self, unit: list[_Entry]) -> list:
        e, = unit
        return [e.run()]

    def _execute_negotiated(self, spec: _QueueSpec, entries: list[_Entry],
                            ticket=None) -> None:
        """Multi-process flush: batch ALL drained negotiations into one
        ``negotiate_many`` round (one KV cycle per flush instead of one
        per call — submitted early by the pipelined flush trigger, waited
        here), then execute each entry with its submission-time program
        composition — identical to what a joined rank rebuilds from
        response metadata, so programs match across processes no matter
        when each process's cycle fired."""
        from . import collectives as _coll
        # Both negotiation calls are timer-unreachable at runtime: _loop
        # skips svc queues, so only user-thread triggers (rank-
        # deterministic program points) drain negotiated flushes.
        if ticket is not None:
            spec.svc.negotiate_many_wait(ticket)  # hvdlint: disable=timer-purity
        else:
            spec.svc.negotiate_many(  # hvdlint: disable=timer-purity
                [r for e in entries for r in e.requests])
        if spec.kind == "broadcast":
            # Broadcast is illegal while any rank is joined (reference
            # JoinOp covers allreduce/allgather/barrier only), so there is
            # no joined-rank program reconstruction to match — the whole
            # flushed queue fuses into one dispatch, like single-
            # controller mode (flush points are rank-deterministic, so
            # every process fuses the identical set).
            def run_bcast(unit):
                tensors = [t for e in unit for t in e.tensors]
                return _coll._run_queued_broadcast(
                    tensors, spec.pset, spec.axis, spec.root_rank,
                    unit[0].label)
            self._dispatch_units([entries], run_bcast)
            return

        def run_entry(unit):
            e, = unit
            return _coll._run_queued_allreduce(
                e.tensors, spec.pset, spec.axis, spec.op, spec.pre,
                spec.post, spec.compression, e.label)
        self._dispatch_units([[e] for e in entries], run_entry)

    # -- cycle timer -------------------------------------------------------

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._stop = _inv.make_event("fusion_cycle.scheduler.stop")
            self._thread = _inv.spawn_thread(
                self._loop, name="hvd-fusion-cycle")

    def _age_limit_s(self) -> float:
        """Queue age that triggers a cycle flush: CYCLE_TIME idle,
        PENDING_CYCLE_TIME while work is in flight (a dispatch happened
        within the last cycle window)."""
        cycle = envs.cycle_time_ms() / 1e3
        if _inv.monotonic() < self._inflight_until:
            return min(cycle, pending_cycle_time_ms() / 1e3)
        return cycle

    def _loop(self) -> None:
        stop = self._stop
        while not stop.is_set():
            self._wake.clear()
            now = _inv.monotonic()
            due: list[tuple] = []
            next_deadline = None
            with self._mu:
                limit = self._age_limit_s()
                for key, q in self._queues.items():
                    if q.spec.svc is not None:
                        # Multi-process queues NEVER flush from the timer:
                        # XLA programs must be issued in the identical
                        # order on every process, and only user-thread
                        # triggers (threshold at enqueue, synchronize,
                        # poll, barrier, shutdown) happen at rank-
                        # deterministic program points. Timer jitter on
                        # one process must not reorder dispatches.
                        continue
                    deadline = q.oldest_t + limit
                    if deadline <= now:
                        due.append(key)
                    elif next_deadline is None or deadline < next_deadline:
                        next_deadline = deadline
            for key in due:
                if stop.is_set():
                    return
                try:
                    self.flush_queue(key, "cycle")
                except BaseException:  # entries already marked failed; a
                    # KeyboardInterrupt on the daemon timer is spurious
                    # and must not kill the cycle loop
                    hvd_logging.exception("cycle flush failed on timer")
            if due:
                continue
            timeout = (None if next_deadline is None
                       else max(next_deadline - _inv.monotonic(), 0.0))
            self._wake.wait(timeout)

    # -- lifecycle / stats -------------------------------------------------

    def drain(self) -> None:
        """Execute everything still pending (clean shutdown: results of
        never-synchronized handles are materialized, not dropped)."""
        self.flush_all("shutdown")

    def abort(self, reason: str) -> int:
        """Fail everything still pending without executing (engine
        service reset / elastic world teardown — the world the entries
        were negotiated against no longer exists): the pending queues AND
        the batches sitting in the pipelined executor's submission queue
        (their negotiation tickets are cancelled so the names become
        reusable). The batch the executor is currently dispatching runs
        to completion or error on its own — its entries' events are set
        either way, so no waiter can deadlock on an abort mid-pipeline.
        Returns the number of entries aborted; their handles raise at
        synchronize()."""
        with self._mu:
            queues = list(self._queues.values())
            self._queues.clear()
            self._pending_tensors = 0
            self._pending_bytes = 0
        with self._exec_cv:
            batches = list(self._exec_q)
            self._exec_q.clear()
            if self._qos_gate is not None:
                # parked batches die with the world too (their
                # negotiation tickets cancel below, like queued ones)
                batches.extend(self._qos_gate.drain_locked())
            for b in batches:
                for e in b.entries:
                    if e.requests:
                        self._exec_names.difference_update(e.names)
                    e.qos_inflight = False
            # quota accounting dies with the world: zero it and bump
            # the epoch in the same critical section. EVERY pre-abort
            # entry — queued, parked, executor-queued, or already
            # executed but not yet synchronized — carries the old
            # epoch, so its late ack/settle is a no-op instead of
            # deflating charges made by post-abort submissions (the
            # shed quota would otherwise leak headroom equal to the
            # pre-abort pending). Then wake any quota-blocked
            # producers (their entries are failing below).
            self._qos_epoch += 1
            self._qos_unacked.clear()
            self._qos_inflight.clear()
            self._exec_cv.notify_all()
        n = 0
        err = lambda e: RuntimeError(
            f"queued collective {e.label!r} aborted: {reason}")
        for q in queues:
            for e in q.entries:
                e.error = err(e)
                e.tensors = ()
                e.run = None
                e.event.set()
                n += 1
        for b in batches:
            if b.ticket is not None:
                try:
                    b.spec.svc.negotiate_many_cancel(b.ticket)
                except Exception:  # hvdlint: disable=silent-except
                    pass  # service may already be gone
            for e in b.entries:
                if not e.done:
                    e.error = err(e)
                    e.tensors = ()
                    e.run = None
                    e.event.set()
                    n += 1
        # capture-held entries + the recorded/armed plan die with the
        # world they were recorded against (elastic re-form, service
        # reset, PeerFailureError teardown)
        n += self.capture.abort(reason)
        return n

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            _inv.join_thread(t, timeout=5)
        self._thread = None
        with self._exec_cv:
            self._exec_stop = True
            self._exec_cv.notify_all()
        t = self._exec_thread
        if t is not None and t is not threading.current_thread():
            _inv.join_thread(t, timeout=5)
        self._exec_thread = None
        self._exec_inflight.clear()

    def stats(self) -> dict:
        slots = max(envs.max_inflight_flushes(), 1)
        capture = self.capture.stats()
        with self._exec_cv:
            executed = self._pstats["executed"]
            qos = {"enabled": _qos.enabled(),
                   "shed": dict(self._qos_stats["shed"]),
                   "quota_blocks": self._qos_stats["quota_blocks"],
                   "unacked_bytes": dict(self._qos_unacked),
                   "inflight_bytes": dict(self._qos_inflight)}
            if self._qos_gate is not None:
                qos.update(self._qos_gate.stats_locked())
            pipeline = {
                "enabled": envs.pipeline_enabled(),
                "max_inflight": envs.max_inflight_flushes(),
                "chunking": envs.pipeline_chunking_enabled(),
                "pipeline_threshold_bytes": envs.pipeline_threshold_bytes(),
                "pipeline_chunks": envs.pipeline_chunks(),
                "submitted": self._pstats["submitted"],
                "executed": executed,
                "queue_depth": len(self._exec_q),
                "inflight_peak": self._pstats["inflight_peak"],
                "slot_waits": self._pstats["slot_waits"],
                # total ms the executor spent blocked on device
                # completion at slot admission (window full) — a
                # device-bound pipeline shows here, not in dispatch time
                "device_wait_ms": self._pstats["device_wait_ms"],
                # fraction of flushes dispatched while >=1 earlier flush
                # was still in flight on device — the overlap the
                # executor exists to create. Sampled BEFORE eager
                # retirement but AFTER slot blocking, so a slots=1
                # stream honestly reads 0.0 (docs/pipeline.md "Overlap
                # semantics").
                "overlap_ratio": (self._pstats["overlapped"] / executed
                                  if executed else 0.0),
                # mean fraction of the slot window occupied at admission
                # (the admitted batch itself counts as one slot;
                # post-retirement window depth)
                "slot_occupancy": (
                    (self._pstats["depth_sum"] / executed + 1.0) / slots
                    if executed else 0.0),
            }
        with self._mu:
            flushes = dict(self._stats["flushes"])
            dispatches = self._stats["dispatches"]
            flushed = self._stats["flushed_tensors"]
            total_flushes = sum(flushes.values())
            return {
                "enabled": enabled(),
                "cycle_time_ms": envs.cycle_time_ms(),
                "pending_cycle_time_ms": pending_cycle_time_ms(),
                "fusion_threshold_bytes": envs.fusion_threshold_bytes(),
                "max_pending_bytes": max_pending_bytes(),
                "enqueued_tensors": self._stats["enqueued_tensors"],
                "enqueued_bytes": self._stats["enqueued_bytes"],
                "pending_tensors": self._pending_tensors,
                "pending_bytes": self._pending_bytes,
                "flushes": {**flushes, "total": total_flushes},
                "flushed_tensors": flushed,
                "flushed_bytes": self._stats["flushed_bytes"],
                "dispatches": dispatches,
                "wire_programs": self._stats["wire_programs"],
                "tensors_per_flush": (flushed / total_flushes
                                      if total_flushes else 0.0),
                "bytes_per_flush": (self._stats["flushed_bytes"]
                                    / total_flushes if total_flushes
                                    else 0.0),
                # tensors coalesced per flush-level dispatch round — the
                # headline number: N small async calls -> N/coalesce
                # dispatches. Uniform across modes: a multi-process flush
                # is ONE dispatch round (one negotiate_many batch) even
                # though its submission-time composition issues one wire
                # program per entry (see wire_programs).
                "coalesce_ratio": (flushed / dispatches if dispatches
                                   else 0.0),
                "pipeline": pipeline,
                # multi-tenant QoS admission counters (docs/qos.md):
                # per-tenant grants/shares from the gate plus the
                # scheduler-side shed/quota accounting
                "qos": qos,
                # step capture-and-replay lifecycle counters
                # (docs/step_capture.md). Replayed entries never appear
                # in dispatches/wire_programs — the per-source plan-hit
                # split lives in dispatch_cache_stats()["hits_by_source"]
                "capture": capture,
            }

    def reset_stats(self) -> None:
        with self._mu:
            self._stats = {
                "enqueued_tensors": 0, "enqueued_bytes": 0,
                "flushed_tensors": 0, "flushed_bytes": 0, "dispatches": 0,
                "wire_programs": 0,
                "flushes": {t: 0 for t in FLUSH_TRIGGERS},
            }
            self.flush_history.clear()
        with self._exec_cv:
            self._pstats = {
                "submitted": 0, "executed": 0, "overlapped": 0,
                "depth_sum": 0, "inflight_peak": 0, "slot_waits": 0,
                "device_wait_ms": 0.0,
            }
            self._qos_stats = {"shed": {}, "quota_blocks": 0}
        self.capture.reset_stats()


# ---------------------------------------------------------------------------
# process-wide scheduler + the enqueue front door the async ops call
# ---------------------------------------------------------------------------

_scheduler: FusionScheduler | None = None
_scheduler_lock = threading.Lock()


def scheduler() -> FusionScheduler:
    from ..loopback import context as _lbctx
    ctx = _lbctx.current()
    if ctx is not None:
        # One scheduler per loopback rank: each rank's flush composition
        # and pipelined executor are its own, like one per process.
        if ctx.scheduler is None:
            with _scheduler_lock:
                if ctx.scheduler is None:
                    ctx.scheduler = FusionScheduler()
        return ctx.scheduler
    global _scheduler
    if _scheduler is None:
        with _scheduler_lock:
            if _scheduler is None:
                _scheduler = FusionScheduler()
    return _scheduler


def _current_scheduler() -> FusionScheduler | None:
    """The already-created scheduler for this thread's world (loopback
    rank or process-wide), without creating one."""
    from ..loopback import context as _lbctx
    ctx = _lbctx.current()
    if ctx is not None:
        return ctx.scheduler
    return _scheduler


def _plan_sigs(tensors):
    """Per-tensor dispatch signatures, or None when any tensor cannot be
    planned (python scalars, lists, ragged bundles keep the immediate
    generic path). Computed ONCE per submission — the enqueue hot path is
    exactly the per-call Python overhead this module exists to shrink."""
    from . import collectives as _coll
    sigs = [_coll._plan_sig(t) for t in tensors]
    return sigs if all(s is not None for s in sigs) else None


def _per_shapes(sigs):
    """Per-rank shapes from signatures (bundles drop the rank axis)."""
    return [s[1][1:] if s[0] == "b" else s[1] for s in sigs]


def _entry_nbytes(shapes, wire_dts) -> int:
    """Per-rank wire payload of one entry (what lands in a fusion
    buffer), in the wire dtype when compression is routed."""
    return sum(int(np.prod(shp) or 1) * dt.itemsize
               for shp, dt in zip(shapes, wire_dts))


def _negotiation_requests(request_type, names, shapes, wire_dts,
                          group_id=-1, **meta) -> list[dict]:
    """Pre-built negotiation payloads (multi-process jobs): metadata is
    frozen at submission time so every process emits the identical
    request sequence regardless of when its cycle fires. Built through
    ``collectives._request_dict``, the wire format's single owner."""
    from . import collectives as _coll
    return [_coll._request_dict(name, request_type, shape, dt,
                                group_id=group_id, **meta)
            for name, shape, dt in zip(names, shapes, wire_dts)]


def queue_allreduce(tensors, *, grouped: bool, op=None, process_set=None,
                    prescale_factor=1.0, postscale_factor=1.0, name=None,
                    axis_name=None, compression=None):
    """Enqueue an async (grouped) allreduce; returns a queued Handle, or
    None when the submission must take the immediate path (scheduler off,
    traced context, unplannable input, adasum, custom compressor)."""
    from ..process_sets import _resolve
    from . import collectives as _coll
    from .reduce_ops import ReduceOp, handle_average

    if op is None:
        op = ReduceOp.AVERAGE  # the allreduce()/reference default
    if not tensors or not enabled() or not _trace_state_clean():
        return None
    if op == ReduceOp.ADASUM:
        return None
    sigs = _plan_sigs(tensors)
    if sigs is None:
        return None
    if _coll._is_custom_compressor(compression):
        # custom (non-cast) compressor: only its own compress/decompress
        # pair defines the wire format — take the immediate path, which
        # wraps the call with it
        return None
    if getattr(compression, "wire_dtype", None) is None:
        compression = None  # none-compression == no compression: one queue
    pset = _resolve(process_set)
    axis = _coll._resolve_axis(axis_name)
    for t in tensors:
        _coll._check_op_dtype(
            op, jnp.result_type(t.array if isinstance(t, _coll.PerRank)
                                else t))
    from .. import engine_service
    from . import hierarchical
    svc = engine_service.get_service(pset)
    # Key the queue by the WIRE mapping itself, not the compressor's
    # class name — a compressor instance (or two classes sharing a name)
    # must never share a queue with a different wire format.
    wire = getattr(compression, "wire_dtype", None)
    comp_key = jnp.dtype(wire).name if wire is not None else None
    shapes = _per_shapes(sigs)
    wire_dts = [_coll._wire_dtype_of(t, compression) for t in tensors]
    key = ("allreduce", pset.dispatch_key(), axis, int(op),
           float(prescale_factor), float(postscale_factor),
           hierarchical.hierarchical_enabled_for(pset), comp_key)
    requests: list[dict] = []
    if grouped:
        base = name or _coll._auto_name("q_grouped_allreduce", pset)
        names = [f"{base}.{i}" for i in range(len(tensors))]
    elif name is not None:
        names = [name]
    else:
        names = [_coll._auto_name("q_allreduce", pset)]
    if svc is not None:
        from ..dynamic import REQ_ALLREDUCE
        lowered_op, post = handle_average(op, pset.size(), postscale_factor)
        gid = -1
        if grouped:
            import zlib
            gid = zlib.crc32(names[0].rsplit(".", 1)[0].encode()) & 0x7FFFFFFF
        requests = _negotiation_requests(
            REQ_ALLREDUCE, names, shapes, wire_dts,
            group_id=gid, reduce_op=int(lowered_op),
            prescale=float(prescale_factor), postscale=float(post))
    spec = _QueueSpec("allreduce", pset, axis, op=op,
                      pre=float(prescale_factor),
                      post=float(postscale_factor),
                      compression=compression, svc=svc)
    entry = _Entry(list(tensors), grouped,
                   _entry_nbytes(shapes, wire_dts), names, requests,
                   label=names[0])
    entry.sigs = tuple(sigs)
    scheduler().enqueue(key, spec, entry)
    return _coll._QueuedHandle(entry)


def queue_broadcast(tensor, root_rank: int, *, process_set=None, name=None,
                    axis_name=None):
    from ..process_sets import _resolve
    from . import collectives as _coll

    if not enabled() or not _trace_state_clean():
        return None
    sigs = _plan_sigs([tensor])
    if sigs is None:
        return None
    pset = _resolve(process_set)
    if root_rank not in pset.ranks:
        raise ValueError(
            f"root_rank {root_rank} not in process set {pset.ranks}")
    axis = _coll._resolve_axis(axis_name)
    from .. import engine_service
    svc = engine_service.get_service(pset)
    key = ("broadcast", pset.dispatch_key(), axis, int(root_rank))
    names = [name or _coll._auto_name("q_broadcast", pset)]
    shapes = _per_shapes(sigs)
    wire_dts = [jnp.dtype(sigs[0][2])]
    requests: list[dict] = []
    if svc is not None:
        from ..dynamic import REQ_BROADCAST
        requests = _negotiation_requests(
            REQ_BROADCAST, names, shapes, wire_dts,
            root_rank=int(root_rank))
    spec = _QueueSpec("broadcast", pset, axis, root_rank=int(root_rank),
                      svc=svc)
    entry = _Entry([tensor], False, _entry_nbytes(shapes, wire_dts), names,
                   requests, label=names[0])
    entry.sigs = tuple(sigs)
    scheduler().enqueue(key, spec, entry)
    return _coll._QueuedHandle(entry)


def queue_allgather(tensor, *, process_set=None, name=None, axis_name=None):
    from ..process_sets import _resolve
    from . import collectives as _coll

    if not enabled() or not _trace_state_clean():
        return None
    sigs = _plan_sigs([tensor])
    if sigs is None:
        return None
    pset = _resolve(process_set)
    axis = _coll._resolve_axis(axis_name)
    from .. import engine_service
    svc = engine_service.get_service(pset)
    key = ("allgather", pset.dispatch_key(), axis)
    # Negotiation happens inside allgather() at flush time (its program
    # shape depends on the negotiated recv_splits), but in multi-process
    # jobs the NAME is drawn from the shared allgather counter NOW, at the
    # submission point — drawing it at flush time would interleave
    # nondeterministically with sync allgather calls and desynchronize
    # names across processes. Single-controller jobs keep name=None so
    # repeated flushes share one dispatch plan.
    auto = _coll._auto_name("allgather", pset) if svc is not None else None
    names = [name if name is not None else auto]
    spec = _QueueSpec("allgather", pset, axis, svc=svc)
    entry = _Entry([tensor], False,
                   _entry_nbytes(_per_shapes(sigs),
                                 [jnp.dtype(sigs[0][2])]),
                   names, label=names[0] or "allgather")
    scheduler().enqueue(key, spec, entry)
    return _coll._QueuedHandle(entry)


def queue_opaque(kind: str, run, *, process_set=None, nbytes: int = 0,
                 label: str = "", extra_key=()):
    """Deferred-execution entry with its own executor (sparse async): no
    cross-entry fusion, but submissions still ride the cycle so a burst
    of sparse ops drains in one flush."""
    from ..process_sets import _resolve
    from . import collectives as _coll

    if not enabled() or not _trace_state_clean():
        return None
    pset = _resolve(process_set)
    from .. import engine_service
    key = (kind, pset.dispatch_key()) + tuple(extra_key)
    # svc pins the timer restriction: opaque executors negotiate inside
    # their run() (e.g. sparse -> allgather), so multi-process entries
    # must flush from user-thread triggers only, like every other kind.
    spec = _QueueSpec("sparse", pset, None,
                      svc=engine_service.get_service(pset))
    entry = _Entry([None], False, int(nbytes),
                   [label or _coll._auto_name("q_" + kind, pset)], run=run,
                   label=label)
    scheduler().enqueue(key, spec, entry)
    return _coll._QueuedHandle(entry)


# -- module-level conveniences (mirror dispatch_cache's surface) ------------

def flush_all(trigger: str = "barrier") -> None:
    sched = _current_scheduler()
    if sched is not None:
        sched.flush_all(trigger)


def fusion_flush() -> None:
    """User-visible flush point (exported as ``hvd.fusion_flush()``):
    drain every pending queue into the pipelined executor and wait until
    all of it is *dispatched*. Weaker than ``hvd.barrier()`` — no
    cross-rank rendezvous and no device-completion wait (synchronize a
    handle for that) — and useful before timing boundaries or memory
    checkpoints where queued-but-undispatched work would skew the
    measurement."""
    flush_all("barrier")


def drain() -> None:
    """Clean-shutdown hook (``hvd.shutdown()``): execute everything still
    queued so no submitted collective is silently dropped."""
    sched = _current_scheduler()
    if sched is not None:
        sched.drain()
        sched.stop()


def abort(reason: str) -> int:
    """Service-reset hook (elastic teardown): fail pending entries."""
    sched = _current_scheduler()
    if sched is not None:
        return sched.abort(reason)
    return 0


def stats() -> dict:
    """Scheduler counters (the ``hvd.fusion_stats()`` API)."""
    return scheduler().stats()


def reset() -> None:
    """Tests / teardown: drop queues (aborting pending entries), stop the
    timer, and zero the counters."""
    global _scheduler
    from ..loopback import context as _lbctx
    ctx = _lbctx.current()
    with _scheduler_lock:
        if ctx is not None:
            sched, ctx.scheduler = ctx.scheduler, None
        else:
            sched, _scheduler = _scheduler, None
    if sched is not None:
        sched.abort("fusion scheduler reset")
        sched.stop()
