"""Cross-rank lockstep conformance recorder (``HVD_CONFORMANCE``).

Every subsystem grown since PR 2 rests on one contract: all ranks make
byte-identical **rank-deterministic decisions** — fusion flush
composition, QoS grant order, step-capture seal keys and phase moves,
response-cache confirm/serve flips, dispatch/gspmd plan-key builds.
One divergent decision presents as a 600 s exchange-deadline hang with
no localization (the reference's stall inspector names *missing*
tensors, never *why* ranks diverged). This module is the runtime half
of the instrument that proves the contract mechanically: a per-rank
recorder hooks every decision point, content-hashes each event into
chained crc digests, and dumps per-rank trace files that
``python -m tools.hvdtrace`` (the offline half) cross-diffs down to the
FIRST divergent event.

**Event classes.** Not every decision is cross-rank comparable:

* ``lockstep`` events fold into per-stream digest chains — the claim is
  "every rank's chain for this stream is identical". Flush composition,
  QoS grants, capture seal/phase, response-cache confirm/serve, and
  knob-override epoch moves are lockstep.
* ``local`` events are recorded (and FSM-validated offline) but **not**
  chained: plan-key builds and warm-reform shelve/graft decisions are
  legitimately rank-asymmetric (a fresh replacement rank builds cold
  while survivors graft warm), as are service lifecycle and join
  events.

**Streams, not one chain.** Decisions from different subsystems are
made under different locks on different threads (the cycle thread
confirms cache entries while a producer thread drains a flush), so
their *interleaving* is timing, not contract. Each subsystem therefore
chains into its own stream (``flush``/``qos``/``capture``/``rcache``/
``epoch``); within a stream the owning lock totally orders events and
the order IS rank-deterministic.

**Cost contract.** With ``HVD_CONFORMANCE`` unset, :func:`record` is
one cached module-bool read and an early return (the ``utils/faults.py``
fast-path idiom); enabled, it changes no result
(``tests/test_metrics.py::test_recorder_on_off_same_bytes``) and its cost
is not measured on the chip. The record path is
timer-purity legal: content hashing is ``zlib.crc32`` over ``repr``
(the ``faults.py`` deterministic-draw idiom) — no wall clock, no
randomness, no set iteration.

**Coverage contract.** :data:`SITES` below is the registry of decision
points; hvdlint pass 9 (``trace-coverage``) checks both directions —
every registered site contains a ``conformance.record(...)`` call, no
``record()`` call sits outside a registered site, and the registry
round-trips against docs/conformance.md like the knob registry does
against docs/knobs.md.

Deliberately light on imports (stdlib + envs + the loopback context
seam) and deliberately on **plain** ``threading.Lock`` like metrics.py:
the recorder lock is a leaf — nothing is acquired under it and it never
blocks — so routing it through the invariants seam would only multiply
hvdsched's schedule space without adding an explorable conflict.
"""

from __future__ import annotations

import collections
import json
import os
import re
import threading
import weakref
import zlib

from .loopback import context as _lbctx
from .utils import envs

__all__ = [
    "LOCKSTEP", "LOCAL", "SITES", "STREAMS", "TRACE_SCHEMA",
    "Recorder", "record", "enabled", "refresh", "set_enabled",
    "conformance_dump", "conformance_stats", "maybe_dump", "reset",
]

TRACE_SCHEMA = 1

LOCKSTEP = "lockstep"
LOCAL = "local"

# ---------------------------------------------------------------------------
# decision-point registry (hvdlint pass 9 round-trips this against
# docs/conformance.md and against the call sites themselves)
# ---------------------------------------------------------------------------

# site ("<module>.py::<qualname>") -> (stream, event class). The site key
# format matches hvdlint's function index (paths relative to the
# horovod_tpu package root).
SITES = {
    # fusion flush composition order — THE founding lockstep decision
    "ops/fusion_cycle.py::FusionScheduler.flush_queue":
        ("flush", LOCKSTEP),
    # QoS grant history: the deterministic multi-tenant arbiter's output
    "qos.py::QosGate._grant_locked": ("qos", LOCKSTEP),
    # step-capture phase transitions + seal keys + replay completion
    "ops/step_capture.py::CaptureState.boundary": ("capture", LOCKSTEP),
    "ops/step_capture.py::CaptureState._seal_locked":
        ("capture", LOCKSTEP),
    "ops/step_capture.py::CaptureState._diverge_locked":
        ("capture", LOCKSTEP),
    "ops/step_capture.py::CaptureState._execute_replay":
        ("capture", LOCKSTEP),
    # response-cache confirm flips + serve decisions at negotiation index
    "negotiation/response_cache.py::ResponseCache.note_response":
        ("rcache", LOCKSTEP),
    "negotiation/response_cache.py::ResponseCache.count_served":
        ("rcache", LOCKSTEP),
    # warm re-form machinery: legitimately rank-asymmetric -> local
    "negotiation/response_cache.py::ResponseCache.restore_warm":
        ("rcache", LOCAL),
    "negotiation/response_cache.py::ResponseCache.confirm_warm":
        ("rcache", LOCAL),
    "negotiation/response_cache.py::ResponseCache.drop_warm":
        ("rcache", LOCAL),
    # dispatch/gspmd plan-key builds + warm shelve/graft decisions
    "ops/dispatch_cache.py::store": ("plans", LOCAL),
    "ops/dispatch_cache.py::shelve_for_reform": ("plans", LOCAL),
    "ops/dispatch_cache.py::restore_for_reform": ("plans", LOCAL),
    "ops/dispatch_cache.py::_warm_graft_locked": ("plans", LOCAL),
    # negotiation-service lifecycle + join latch (FSM-validated)
    "engine_service.py::DynamicService.__init__": ("service", LOCAL),
    "engine_service.py::DynamicService.stop": ("service", LOCAL),
    "engine_service.py::DynamicService._on_peer_failure":
        ("service", LOCAL),
    "engine_service.py::DynamicService.join": ("service", LOCAL),
    # checkpoint state plane: snapshot triggers fire at the commit
    # boundary on the training thread (the async writer only copies),
    # and the re-form restore protocol's agree/source decisions are
    # collective outputs — all three are lockstep by construction
    "checkpoint.py::StatePlane.note_commit": ("ckpt", LOCKSTEP),
    "elastic/state.py::JaxState.sync": ("ckpt", LOCKSTEP),
    "elastic/state.py::JaxState._peer_restore": ("ckpt", LOCKSTEP),
}

# The internal stream the recorder feeds itself: knob-override epoch
# moves (autotune) are lockstep context every divergence report quotes.
_EPOCH_STREAM = "epoch"

STREAMS = ("flush", "qos", "capture", "rcache", "plans", "service",
           "ckpt", _EPOCH_STREAM)

_STREAM_OF = {site: stream for site, (stream, _cls) in SITES.items()}
_CLASS_OF = {site: cls for site, (_stream, cls) in SITES.items()}


# ---------------------------------------------------------------------------
# enable gate (cached; near-zero when off)
# ---------------------------------------------------------------------------

_force_enabled: bool | None = None  # tests/bench override; None = knob


def _read_enabled() -> bool:
    if _force_enabled is not None:
        return _force_enabled
    return envs.conformance_enabled()


_enabled = _read_enabled()


def enabled() -> bool:
    """Whether decision-point hooks record (``HVD_CONFORMANCE``,
    default off)."""
    return _enabled


def refresh() -> None:
    """Re-read ``HVD_CONFORMANCE`` (tests toggle it after import)."""
    global _enabled
    _enabled = _read_enabled()


def set_enabled(value: bool | None) -> None:
    """Force the gate on/off (``None`` restores the knob) — the bench's
    interleaved on/off passes and tests use this; production uses the
    knob."""
    global _force_enabled
    _force_enabled = value
    refresh()


# ---------------------------------------------------------------------------
# per-rank recorders
# ---------------------------------------------------------------------------


def _crc(prev: int, *parts) -> int:
    """Chain one event into a crc digest — deterministic, wall-clock
    free, and cheap enough for the flush drain's critical section (the
    ``faults.py`` draw idiom keeps this legal in timer-reachable
    code)."""
    return zlib.crc32(repr(parts).encode(), prev) & 0xFFFFFFFF


class Recorder:
    """One rank's (or the process's) conformance event log: per-stream
    digest chains, the compact per-event index, and the bounded
    full-payload ring."""

    __slots__ = ("header", "chains", "events", "ring", "seq",
                 "dump_count", "_epoch", "_mu")

    def __init__(self):
        ctx = _lbctx.current()
        label = _lbctx.current_rank_label() or "proc"
        self.header = {
            "schema": TRACE_SCHEMA,
            "label": label,
            "rank": envs.get_int(envs.RANK, -1),
            "size": envs.get_int(envs.SIZE, -1),
            # the rendezvous coordinates group traces into comparable
            # worlds: loopback seeds the world NAME and the round index
            # here (LoopbackWorld.rank_env), processes their launcher's
            "world": envs.get(envs.COORDINATOR_ADDR, "") or "",
            "round": envs.get(envs.COORDINATOR_PORT, "") or "",
            "elastic_round": envs.get(envs.ELASTIC_ROUND, "") or "",
            "generation": getattr(ctx, "generation", 0) if ctx else 0,
        }
        self.chains = {s: 0 for s in STREAMS}
        # compact, unbounded: [seq, stream, cls, site, kind, crc] — crc
        # is the stream chain AFTER the event (lockstep) or the event's
        # own content crc (local); the chain localizes, the ring quotes
        self.events: list[list] = []
        self.ring = collections.deque(maxlen=envs.conformance_ring())
        self.seq = 0
        self.dump_count = 0
        self._epoch = envs.override_epoch()
        self._mu = threading.Lock()

    # -- recording ---------------------------------------------------------

    def note(self, site: str, kind: str, payload) -> None:
        stream = _STREAM_OF.get(site)
        if stream is None:
            # an unregistered call site is a schema bug pass 9 catches
            # statically; at runtime keep the event rather than lose it
            stream, cls = "service", LOCAL
        else:
            cls = _CLASS_OF[site]
        with self._mu:
            epoch = envs.override_epoch()
            if epoch != self._epoch:
                self._note_locked(
                    _EPOCH_STREAM, LOCKSTEP,
                    "conformance.py::Recorder.note", "epoch",
                    (self._epoch, epoch))
                self._epoch = epoch
            self._note_locked(stream, cls, site, kind, payload)

    def _note_locked(self, stream: str, cls: str, site: str, kind: str,
                     payload) -> None:
        seq = self.seq
        self.seq = seq + 1
        if cls == LOCKSTEP:
            crc = _crc(self.chains[stream], kind, payload)
            self.chains[stream] = crc
        else:
            crc = _crc(0, kind, payload)
        self.events.append([seq, stream, cls, site, kind, crc])
        if self.ring.maxlen:
            self.ring.append([seq, site, kind, repr(payload)])

    # -- export ------------------------------------------------------------

    def trace(self) -> dict:
        """The JSON-shaped trace document ``tools/hvdtrace`` consumes."""
        with self._mu:
            return {
                **self.header,
                "chains": dict(self.chains),
                "events": [list(e) for e in self.events],
                "ring": [list(r) for r in self.ring],
                "n_events": self.seq,
            }

    def stats(self) -> dict:
        with self._mu:
            per_stream: dict[str, int] = {s: 0 for s in STREAMS}
            for _seq, stream, _cls, _site, _kind, _crc in self.events:
                per_stream[stream] = per_stream.get(stream, 0) + 1
            return {
                "enabled": _enabled,
                "label": self.header["label"],
                "events": self.seq,
                "by_stream": per_stream,
                "chains": dict(self.chains),
                "ring": len(self.ring),
            }


_process_recorder: Recorder | None = None
# RankContext -> Recorder; weak keys so a dead loopback world's log is
# collected with it (RankContext carries __weakref__ for exactly this).
_ctx_recorders: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_reg_mu = threading.Lock()


def _recorder(ctx=None) -> Recorder:
    if ctx is None:
        ctx = _lbctx.current()
    if ctx is None:
        global _process_recorder
        with _reg_mu:
            if _process_recorder is None:
                _process_recorder = Recorder()
            return _process_recorder
    with _reg_mu:
        rec = _ctx_recorders.get(ctx)
        if rec is None:
            with _lbctx.activate(ctx):
                rec = Recorder()
            _ctx_recorders[ctx] = rec
        return rec


def _peek_recorder(ctx=None) -> Recorder | None:
    if ctx is None:
        ctx = _lbctx.current()
    with _reg_mu:
        return _process_recorder if ctx is None else _ctx_recorders.get(ctx)


def record(site: str, kind: str, payload) -> None:
    """Record one decision event at a registered ``site``. Near-zero
    when off: one cached module-bool read and an early return. Safe
    from timer-reachable code (no wall clock, no randomness)."""
    if not _enabled:
        return
    _recorder().note(site, kind, payload)


# ---------------------------------------------------------------------------
# dumping
# ---------------------------------------------------------------------------


def _trace_filename(header: dict, dump_count: int) -> str:
    raw = "hvdtrace-{}-r{}-g{}-{}".format(
        header.get("world") or "world", header.get("round") or "0",
        header.get("generation") or 0, header.get("label") or "proc")
    if dump_count:
        raw += f"-d{dump_count}"
    return re.sub(r"[^A-Za-z0-9._-]+", "_", raw) + ".json"


def conformance_dump(path: str | None = None) -> dict:
    """Snapshot the calling thread's (rank's) conformance trace. Writes
    it to ``path`` when given, else to ``HVD_CONFORMANCE_DIR`` when that
    knob is set; always returns the trace document (``hvd.
    conformance_dump()`` — the on-demand twin of the shutdown dump)."""
    rec = _recorder()
    doc = rec.trace()
    target = path
    if target is None:
        d = envs.conformance_dir()
        if d:
            target = os.path.join(d, _trace_filename(doc, rec.dump_count))
    if target is not None:
        os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
        with open(target, "w") as f:
            json.dump(doc, f)
        doc["path"] = target
    return doc


def maybe_dump(reason: str, ctx=None) -> str | None:
    """Shutdown/abort-path dump: when the recorder is enabled AND
    ``HVD_CONFORMANCE_DIR`` names a directory, write this world's trace
    file and return its path (else None). ``ctx`` lets the loopback
    supervisor dump a dead rank's trace from another thread. Never
    raises — a failed trace write must not mask the teardown (or abort)
    it rides on."""
    if not _enabled:
        return None
    rec = _peek_recorder(ctx)
    if rec is None or rec.seq == 0:
        return None
    try:
        with _lbctx.activate(ctx) if ctx is not None else _noop():
            d = envs.conformance_dir()
            if not d:
                return None
            doc = rec.trace()
            doc["dump_reason"] = reason
            target = os.path.join(
                d, _trace_filename(doc, rec.dump_count))
            rec.dump_count += 1
            os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
            with open(target, "w") as f:
                json.dump(doc, f)
            return target
    except Exception:  # pragma: no cover - diagnostic path
        from .utils import logging as hvd_logging
        hvd_logging.exception("conformance trace dump failed (%s)", reason)
        return None


class _noop:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def conformance_stats() -> dict:
    """Recorder counters for the calling thread's world (tests; the
    ``hvd.response_cache_stats()``-style observability twin)."""
    rec = _peek_recorder()
    if rec is None:
        return {"enabled": _enabled, "events": 0, "by_stream": {},
                "chains": {}, "ring": 0, "label": ""}
    return rec.stats()


def reset() -> None:
    """Drop the calling thread's recorder (process teardown / tests) —
    the next event starts a fresh trace incarnation."""
    global _process_recorder
    ctx = _lbctx.current()
    with _reg_mu:
        if ctx is None:
            _process_recorder = None
        else:
            _ctx_recorders.pop(ctx, None)
