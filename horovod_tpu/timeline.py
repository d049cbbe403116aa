"""Timeline: Chrome-trace recording of eager collectives.

TPU-native rebuild of the reference Timeline (``timeline.cc:1-678``, writer
thread + per-tensor lanes; runtime start/stop via ``horovod_start_timeline``
at ``operations.cc:1032-1064``). The writer lives in the native engine
(``native/timeline.cc``); this module owns the process-wide instance, the
``HVD_TIMELINE`` auto-start (seeded by ``hvdrun --timeline-filename``), and
the recording hooks the eager collectives call.

Traced-mode collectives compile into the XLA program, where a wall-clock
writer cannot see them — use ``jax.profiler`` traces for those.

This module is also the program's ONE seam for host spans and device
scopes. Host spans (:func:`span`): every
host-side layer — ``hvd.init``, ``broadcast_parameters``, the eager
optimizer's two stages, the fusion cycle, the plan cache, ``cached_step``
— times its work through a fixed-name ``hvd:<layer>.<stage>`` span that
is always a ``jax.profiler.TraceAnnotation`` (the NVTX analog,
``nvtx_op_range.cc``: on the profiler's clock beside the device, recorded
only while a profiler session runs), adds its duration to
``hvd_span_seconds{span}`` in the metrics registry, and, for the spans
that have a Chrome activity, writes the begin/end records of the Chrome
timeline while one is active. Device scopes (:func:`scope`): the code a
compiled step is traced from names its layers with fixed-name
``jax.named_scope("hvd:<layer>.<stage>")`` blocks, which exist at trace
time only: the name rides in every HLO instruction's ``op_name``, and a
profiler trace, which carries the optimized HLO, gives the device's time
by scope (``benchmark/device_scopes.py``; docs/timeline.md has both
tables).
"""

from __future__ import annotations

import threading
import time

from jax import named_scope as _named_scope
from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import metrics as _metrics
from .loopback import context as _lbctx
from .utils import envs
from .utils import logging as hvd_logging

# Rank suffix appended per process so concurrent multi-process jobs don't
# clobber one file (the reference writes coordinator-only; symmetric
# processes each write their own view).
_lock = threading.Lock()
_engine = None  # NativeEngine owning the active timeline writer
_active = False
_atexit_registered = False

NEGOTIATE = "NEGOTIATE"
QUEUE_ENQUEUE = "QUEUE_ENQUEUE"
CYCLE_FLUSH = "CYCLE_FLUSH"
PIPELINE_LANE = "pipeline"
INFLIGHT_DEPTH = "INFLIGHT_DEPTH"
HEALTH_LANE = "health"
RETRY = "RETRY"
PHASE_BEGIN = 0
PHASE_END = 1
PHASE_INSTANT = 2


def _get_engine():
    global _engine
    if _engine is None:
        from .dynamic import NativeEngine
        _engine = NativeEngine(world_size=1, rank=0)
    return _engine


_mark_cycles = False


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start recording eager collectives to ``file_path`` (Chrome trace
    JSON; open in ``chrome://tracing`` / Perfetto). Reference
    ``hvd.start_timeline`` → ``horovod_start_timeline``
    (``operations.cc:1032-1064``). With ``mark_cycles`` (or
    ``HVD_TIMELINE_MARK_CYCLES``) every negotiation cycle of the dynamic
    service drops an instant marker (``operations.cc:485-488``)."""
    global _active, _atexit_registered, _mark_cycles
    with _lock:
        _get_engine().timeline_start(file_path)
        _active = True
        _mark_cycles = bool(mark_cycles) or envs.get_bool(
            envs.TIMELINE_MARK_CYCLES)
        if not _atexit_registered:
            import atexit
            atexit.register(stop_timeline)  # flushes on interpreter exit
            _atexit_registered = True


def mark_cycle() -> None:
    """Instant 'CYCLE' marker, called by the dynamic service's loop when
    cycle marking is on (HOROVOD_TIMELINE_MARK_CYCLES analog)."""
    if _active and _mark_cycles:
        record("negotiation", "CYCLE", PHASE_INSTANT)


def stop_timeline() -> None:
    """Flush and close the timeline (reference ``hvd.stop_timeline``)."""
    global _active
    with _lock:
        if _engine is not None:
            _engine.timeline_stop()
        _active = False


def timeline_active() -> bool:
    return _active


def maybe_autostart() -> None:
    """Start the timeline when ``HVD_TIMELINE`` is seeded (by
    ``hvdrun --timeline-filename`` or the user). Called from
    ``hvd.init()``. ``DYNAMIC`` defers to an explicit
    :func:`start_timeline` call, like the reference
    (``operations.cc:466-488``)."""
    path = envs.get(envs.TIMELINE)
    if not path or path.upper() == "DYNAMIC" or _active:
        return
    from . import runtime
    if _lbctx.current() is not None:
        # Loopback rank threads share ONE process and so one writer:
        # the first rank's init starts the single file and every rank's
        # events land in it with a ``rank<N>/`` lane prefix (see
        # :func:`record`) — a per-rank ``.<rank>`` suffix here would
        # just mislabel the shared file with whichever rank won init.
        pass
    elif runtime.process_count() > 1:
        path = f"{path}.{runtime.process_rank()}"
    try:
        start_timeline(path)
    except Exception as e:  # IO error / native engine unavailable: a
        # missing timeline must never break init
        hvd_logging.error("cannot start timeline at %s: %s", path, e)


def record_dispatch(tensor: str, hit: bool) -> None:
    """Instant plan-cache marker on the op's lane (``PLAN_HIT`` /
    ``PLAN_MISS``) so steady-state dispatch behavior is visible next to
    the NEGOTIATE/op ranges. Cheap no-op guard on the hot path; full
    counters live in ``hvd.dispatch_cache_stats()``."""
    if _active:
        record(tensor, "PLAN_HIT" if hit else "PLAN_MISS", PHASE_INSTANT)


def record_queue_enqueue(tensor: str) -> None:
    """Instant ``QUEUE_ENQUEUE`` marker on the tensor's lane when an
    async submission lands in a fusion-cycle pending queue (the analog of
    the reference timeline's QUEUE state, ``timeline.cc`` negotiation
    phases) — the gap to the next CYCLE_FLUSH shows queueing latency."""
    if _active:
        record(tensor, QUEUE_ENQUEUE, PHASE_INSTANT)


def record_cycle_flush(trigger: str) -> None:
    """Instant ``CYCLE_FLUSH`` marker on the ``fusion_cycle`` lane, one
    per flush, labeled with the trigger (threshold/cycle/synchronize/...)
    so coalescing behavior is visible next to the op ranges."""
    if _active:
        record("fusion_cycle", f"{CYCLE_FLUSH}.{trigger}", PHASE_INSTANT)


def record_inflight_depth(depth: int) -> None:
    """Instant ``INFLIGHT_DEPTH.<n>`` marker on the ``pipeline`` lane when
    the flush executor admits a batch: ``n`` is how many earlier flushes
    are still in flight on device at dispatch time (sampled BEFORE eager
    retirement — docs/pipeline.md "Overlap semantics"), so achieved
    overlap (and bubbles — long stretches at depth 0) read straight off
    the trace."""
    if _active:
        record(PIPELINE_LANE, f"{INFLIGHT_DEPTH}.{int(depth)}",
               PHASE_INSTANT)


QOS_LANE = "qos"


def record_qos(event: str, tenant: str) -> None:
    """Instant ``QOS_<event>.<tenant>`` marker on the ``qos`` lane for
    admission-gate transitions (``PARK``/``GRANT``/``FORCE``/``SHED``/
    ``BLOCK``) so a tenant's admission waits — and any shed or
    quota-blocked submissions — are attributable next to the flush and
    pipeline lanes (docs/qos.md)."""
    if _active:
        record(QOS_LANE, f"QOS_{event}.{tenant}", PHASE_INSTANT)


CAPTURE_LANE = "step_capture"


def record_capture(event: str) -> None:
    """Instant ``CAPTURE_<event>`` marker on the ``step_capture`` lane for
    capture lifecycle transitions (``RECORD``/``SEAL``/``REPLAY``/
    ``REPLAY_DONE``/``FALLBACK``) so a replayed step — and any transparent
    fallback to eager — is attributable next to the op ranges
    (docs/step_capture.md)."""
    if _active:
        record(CAPTURE_LANE, f"CAPTURE_{event}", PHASE_INSTANT)


def record_retry(what: str, attempt: int) -> None:
    """Instant ``RETRY.<site>.<n>`` marker on the ``health`` lane when a
    retried RPC/KV call backs off (``utils/retry.py``) — a flapping
    transport shows as a burst of RETRY instants instead of silently
    stretching the neighboring op ranges."""
    if _active:
        record(HEALTH_LANE, f"{RETRY}.{what}.{int(attempt)}", PHASE_INSTANT)


def record_health_event(event: str) -> None:
    """Instant marker on the ``health`` lane for failure-domain state
    changes (``PEER_DEAD.<rank>``, ``POISON``, ``STRAGGLER.<rank>``) so
    a coordinated abort — or a sustained straggler — is attributable on
    the trace."""
    if _active:
        record(HEALTH_LANE, event, PHASE_INSTANT)


def record(tensor: str, activity: str, phase: int) -> None:
    """Record one event when the timeline is active (cheap no-op guard on
    the hot path). Loopback rank threads share ONE process — and so one
    writer and one file — so the lane is prefixed with the thread's rank
    from the :class:`~horovod_tpu.loopback.context.RankContext`: every
    rank's events stay attributable in the single merged trace (the
    multi-process path gets the same attribution from
    ``maybe_autostart``'s per-process ``<path>.<rank>`` files)."""
    if not _active:
        return
    eng = _engine
    if eng is not None:
        label = _lbctx.current_rank_label()
        if label:
            tensor = f"{label}/{tensor}"
        eng.timeline_record(tensor, activity, phase)


def merge_timelines(inputs, output: str) -> int:
    """Merge per-process timeline files into one Chrome trace, one pid per
    process (the reference writes a single coordinator-side file,
    ``timeline.cc``; the symmetric rebuild writes per-process files and
    merges after the run). Input order assigns pids; files named
    ``<base>.<rank>`` (the ``maybe_autostart`` convention) are labeled with
    their rank. Returns the number of events written.

    Also usable as a CLI: ``python -m horovod_tpu.timeline merged.json
    trace.0 trace.1 ...``.
    """
    import json
    import os
    import re

    events = []
    for i, path in enumerate(inputs):
        m = re.search(r"\.(\d+)$", os.path.basename(path))
        pid = int(m.group(1)) if m else i
        text = open(path).read().strip()
        # the writer appends events incrementally; tolerate a missing
        # closing bracket / trailing comma (Chrome's own loader does)
        text = text.rstrip(",\n ")
        if not text.endswith("]"):
            text += "]"
        for ev in json.loads(text):
            ev["pid"] = pid
            events.append(ev)
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": f"rank {pid}"}})
    events.sort(key=lambda e: e.get("ts", 0))
    with open(output, "w") as f:
        json.dump(events, f)
    return len(events)


# --------------------------------------------------------------------------
# the span seam
# --------------------------------------------------------------------------

SPAN_PREFIX = "hvd:"  # beside the benchmark's own "bench:" spans
_spans: "dict[str, Span]" = {}


class Span:
    """One fixed-name program span, ``hvd:<layer>.<stage>``. Created ONCE,
    at import of the module that uses it (:func:`span`), with its
    annotation name and its registry series precomputed; calling it
    gives the context manager for one occurrence. What varies per call
    (tensor label, flush trigger and sequence number, bytes) travels as
    keyword arguments into the annotation's metadata, never into the
    name: a trace reduction groups by name, and nothing is formatted on
    the hot path.

    ``activity`` is the span's Chrome-timeline activity (``None``: the
    span is not part of the Chrome file) and ``lane`` its Chrome lane
    where the call names no tensor."""

    __slots__ = ("name", "annotation", "activity", "lane", "_series")

    def __init__(self, name: str, activity: str | None, lane: str | None):
        self.name = name
        self.annotation = SPAN_PREFIX + name
        self.activity = activity
        self.lane = lane
        self._series = _metrics.SPAN_SECONDS.bind({"span": name})

    def __call__(self, tensor: str | None = None,
                 activity: str | None = None, **fields) -> "op_range":
        """One occurrence. ``tensor`` is the Chrome lane (default: the
        span's own) and the annotation's ``tensor`` keyword;
        ``activity`` overrides the span's Chrome activity where one span
        serves several (``plan.run`` runs every op's plan); ``fields``
        are the annotation's other keywords."""
        if tensor is not None:
            fields["tensor"] = tensor
        return op_range(self, tensor or self.lane,
                        activity or self.activity, fields)


def span(name: str, activity: str | None = None,
         lane: str | None = None) -> Span:
    """Declare the span ``hvd:<name>`` (module level, literal name; the
    table in docs/timeline.md lists them all). A name is declared
    once."""
    if name in _spans:
        raise ValueError(f"span {name!r} already declared")
    _spans[name] = Span(name, activity, lane)
    return _spans[name]


def spans() -> dict:
    """The declared spans: ``{name: Span}``."""
    return dict(_spans)


class op_range:
    """Context manager for one occurrence of a :class:`Span`: the
    ``TraceAnnotation`` always (outside a profiler session entering it
    is a flag test), the duration into ``hvd_span_seconds{span}``
    (under the ``HVD_METRICS`` gate), and the Chrome timeline's
    begin/end records while a timeline is active."""

    __slots__ = ("_span", "_lane", "_activity", "_ann", "_start")

    def __init__(self, span: Span, lane, activity, fields):
        self._span = span
        self._lane = lane
        self._activity = activity
        self._ann = _TraceAnnotation(span.annotation, **fields)

    def __enter__(self):
        if _active and self._activity is not None:
            record(self._lane, self._activity, PHASE_BEGIN)
        self._ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._start
        self._ann.__exit__(*exc)
        self._span._series.observe(seconds)
        if _active and self._activity is not None:
            record(self._lane, self._activity, PHASE_END)
        return False


# --------------------------------------------------------------------------
# device scopes: the same seam, inside compiled programs
# --------------------------------------------------------------------------

_scopes: "dict[str, Scope]" = {}


class Scope:
    """One fixed-name device scope, ``hvd:<layer>.<stage>``. Created ONCE,
    at import of the module that uses it (:func:`scope`); calling it
    gives ``jax.named_scope`` under that name and nothing else: no
    registry series, no Chrome record, nothing at run time. Every
    operation traced inside carries the name in its ``op_name``, under
    ``jvp(...)`` / ``transpose(jvp(...))`` where autodiff made it, and a
    ``jax.custom_vjp`` rule traced from inside a scope inherits the
    caller's; one *declared inside* the forward function does not reach
    the backward rule, which enters it again."""

    __slots__ = ("name", "annotation")

    def __init__(self, name: str):
        self.name = name
        self.annotation = SPAN_PREFIX + name

    def __call__(self):
        return _named_scope(self.annotation)


def scope(name: str) -> Scope:
    """Declare the device scope ``hvd:<name>`` (module level, literal
    name; the table in docs/timeline.md lists them all). A name is
    declared once; several modules that write one layer's work share the
    declaration (:func:`scopes`)."""
    if name in _scopes:
        raise ValueError(f"scope {name!r} already declared")
    _scopes[name] = Scope(name)
    return _scopes[name]


def scopes() -> dict:
    """The declared device scopes: ``{name: Scope}``."""
    return dict(_scopes)


if __name__ == "__main__":  # pragma: no cover - thin CLI
    import sys
    if len(sys.argv) < 3:
        print("usage: python -m horovod_tpu.timeline OUT.json IN.0 [IN.1 ...]",
              file=sys.stderr)
        raise SystemExit(2)
    n = merge_timelines(sys.argv[2:], sys.argv[1])
    print(f"merged {len(sys.argv) - 2} timelines ({n} events) -> {sys.argv[1]}")
