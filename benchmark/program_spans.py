"""The program's own spans in a traced run.

``horovod_tpu/timeline.py`` puts every host-side layer of the program
inside a ``jax.profiler.TraceAnnotation`` named ``hvd:<layer>.<stage>``
(docs/timeline.md has the table). In a ``--trace 1`` run they land in the
host planes of the same ``.xplane.pb`` that holds the device's
operations and the benchmark's ``bench:*`` spans, on one clock. This
module reads them, **per thread line** (the default
``HVD_MAX_INFLIGHT_FLUSHES=2`` runs every flush on the
``hvd-flush-pipeline`` thread beside the calling thread), and gives

* calls, wall time and own time per span name and thread (own time: the
  duration less the child spans on the same thread, the rule of
  ``trace_reduce.self_seconds``, which computes it);
* the first chip's idle time by the innermost span of either prefix the
  **calling thread** (the one that holds ``bench:step_call``) was in
  when the gap began, with the ``hvd:`` span the executor thread was in
  beside it (``trace_reduce.busy_intervals`` / ``window`` / ``subtract``
  unchanged);
* the totals of the spans that run before any profiler session
  (``hvd:init``, ``hvd:broadcast_parameters``, ``hvd:cached_step.build``)
  from the registry: ``hvd.metrics_dump()["hvd_span_seconds"]``.

``of(run)`` finds the traced run's file itself (``run`` carries no
path), reduces it once, and logs the whole table as one ``[bench]``
line; the readers in ``layers/`` take single numbers from it. On a
program without the spans (the parent of the PR that added them) every
reader returns ``None`` and nothing raises.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os

from benchmark import trace_reduce
from benchmark.trace_reduce import SPAN_PREFIX

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM_PREFIX = "hvd:"
STEP_CALL = SPAN_PREFIX + "step_call"    # marks the calling thread
EXECUTE = PROGRAM_PREFIX + "cycle.execute"   # marks the executor thread
CALLER, EXECUTOR = "caller", "executor"
OUTSIDE = "host:other"                   # as trace_reduce.idle_gaps has it
SPAN_SERIES = "hvd_span_seconds"


@dataclasses.dataclass
class Span(trace_reduce.Event):
    """A host span: ``label`` is its name (what ``self_seconds`` sums
    by), ``fields`` the annotation's keyword arguments."""

    fields: dict = dataclasses.field(default_factory=dict)

    @property
    def in_program(self):
        return self.name.startswith(PROGRAM_PREFIX)


def trace_file(cell_name):
    """The ``.xplane.pb`` of this cell's traced run, where ``run.py``
    writes it, or ``None``."""
    files = sorted(glob.glob(os.path.join(
        ROOT, ".bench_out", "trace", cell_name, "plugins", "profile", "*",
        "*.xplane.pb")))
    return files[0] if files else None


def load(path):
    """``{thread: [Span]}`` for every host thread line that holds a span
    of either prefix, each sorted by start (outer before inner). The
    calling thread is named ``caller``, the thread that executes flushes
    ``executor`` where it is another one; the rest keep their line's
    name and position."""
    from jax.profiler import ProfileData

    threads = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            spans = [Span(e.name, float(e.start_ns),
                          float(e.start_ns) + float(e.duration_ns),
                          opcode="span", label=e.name,
                          fields=dict(e.stats))
                     for e in line.events
                     if e.name.startswith((PROGRAM_PREFIX, SPAN_PREFIX))]
            if spans:
                spans.sort(key=lambda s: (s.start, -s.end))
                threads[f"{line.name}#{index}"] = spans
    named = {}
    for key, spans in threads.items():
        names = {s.name for s in spans}
        if STEP_CALL in names and CALLER not in named:
            named[CALLER] = spans
        elif EXECUTE in names and EXECUTOR not in named:
            named[EXECUTOR] = spans
        else:
            named[key] = spans
    return named


def totals(spans):
    """``{name: [calls, wall_s, own_s]}`` of one thread's spans."""
    out = {}
    for span in spans:
        row = out.setdefault(span.name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += span.seconds
    own = trace_reduce.self_seconds(trace_reduce.Chip(0, spans, []))
    for name, seconds in own.items():
        out[name][2] = seconds
    return out


def open_at(spans, times):
    """For each of ``times`` (ascending) the spans of one thread that are
    open at it, outermost first. Spans on a thread nest."""
    out, stack, at = [], [], 0
    for time in times:
        while at < len(spans) and spans[at].start <= time:
            while stack and stack[-1].end <= spans[at].start:
                stack.pop()
            stack.append(spans[at])
            at += 1
        while stack and stack[-1].end <= time:
            stack.pop()
        out.append(tuple(stack))
    return out


def idle_by_span(chip, threads):
    """The chip's idle time inside its window, by what the host was in
    when each gap began: ``(rows, idle_s, in_program_s)`` with ``rows``
    ``{(caller's innermost span, executor's innermost hvd: span or
    None): seconds}`` and ``in_program_s`` the part that began while the
    calling thread was inside any ``hvd:`` span."""
    gaps = trace_reduce.subtract([trace_reduce.window(chip)],
                                 trace_reduce.busy_intervals(chip))
    starts = [start for start, _ in gaps]
    calling = open_at(threads.get(CALLER, []), starts)
    executing = open_at([s for s in threads.get(EXECUTOR, [])
                         if s.in_program], starts)
    rows, idle, in_program = {}, 0.0, 0.0
    for (start, end), outer, inner in zip(gaps, calling, executing):
        seconds = (end - start) * 1e-9
        key = (outer[-1].name if outer else OUTSIDE,
               inner[-1].name if inner else None)
        rows[key] = rows.get(key, 0.0) + seconds
        idle += seconds
        if any(span.in_program for span in outer):
            in_program += seconds
    return rows, idle, in_program


def flush_pairs(threads):
    """``[(flush number, drain span, execute span)]``: a drain
    (``hvd:cycle.flush``) and the execution it caused
    (``hvd:cycle.execute``), on whichever threads, joined by their
    ``flush`` keyword."""
    drains, pairs = {}, []
    everything = [s for spans in threads.values() for s in spans]
    for span in everything:
        if span.name == PROGRAM_PREFIX + "cycle.flush":
            drains[span.fields.get("flush")] = span
    for span in everything:
        number = span.fields.get("flush")
        if span.name == EXECUTE and number in drains:
            pairs.append((number, drains[number], span))
    return sorted(pairs, key=lambda pair: pair[1].start)


def registry():
    """The ``hvd_span_seconds`` entry of ``hvd.metrics_dump()``, or
    ``None`` where the program has no span seam (the parent of the PR
    that added it)."""
    import horovod_tpu as hvd

    return hvd.metrics_dump().get(SPAN_SERIES)


def setup_totals():
    """``{span name without prefix: (calls, seconds)}`` since process
    start, from the program's registry."""
    entry = registry() or {"series": []}
    return {s["labels"]["span"]: (s["count"], s["sum"])
            for s in entry["series"]}


@dataclasses.dataclass
class Report:
    steps: int
    threads: dict          # {thread: {name: [calls, wall_s, own_s]}}
    idle_rows: dict        # idle_by_span's rows, or {} without a chip
    idle_s: float | None
    idle_in_program_s: float | None
    flush_lag_s: list      # per joined flush: execute start - drain start

    def total(self, name, thread=None):
        """``(calls, wall_s, own_s)`` of ``name`` on ``thread`` (any
        thread if ``None``), or ``None`` where it never ran."""
        rows = [totals[name] for key, totals in self.threads.items()
                if name in totals and thread in (None, key)]
        if not rows:
            return None
        return tuple(sum(column) for column in zip(*rows))

    def wall_ms_per_step(self, name, thread=None):
        found = self.total(name, thread)
        return None if found is None else found[1] * 1e3 / self.steps

    def table(self):
        """``[[name, thread, calls a step, wall ms a step, own ms a
        step, idle ms a step that began inside it], ...]``, most own
        time first. The idle column is by the calling thread's
        innermost span; an executor row shows the idle that began while
        it was what the executor was in."""
        idle = {}
        for (outer, inner), seconds in self.idle_rows.items():
            idle[CALLER, outer] = idle.get((CALLER, outer), 0.0) + seconds
            if inner:
                idle[EXECUTOR, inner] = (idle.get((EXECUTOR, inner), 0.0)
                                         + seconds)
        per_step = 1e3 / self.steps
        rows = [[name, thread, round(calls / self.steps, 2),
                 round(wall * per_step, 3), round(own * per_step, 3),
                 round(idle.get((thread, name), 0.0) * per_step, 3)]
                for thread, totals in self.threads.items()
                for name, (calls, wall, own) in totals.items()]
        outside = idle.get((CALLER, OUTSIDE))
        if outside:
            rows.append([OUTSIDE, CALLER, 0, 0.0, 0.0,
                         round(outside * per_step, 3)])
        return sorted(rows, key=lambda row: -row[4])


def reduce(threads, chip, steps):
    rows, idle, in_program = (idle_by_span(chip, threads)
                              if chip is not None and chip.ops
                              else ({}, None, None))
    return Report(
        steps=steps,
        threads={key: totals(spans) for key, spans in threads.items()},
        idle_rows=rows, idle_s=idle, idle_in_program_s=in_program,
        flush_lag_s=[(run.start - drain.start) * 1e-9
                     for _, drain, run in flush_pairs(threads)])


def log_line(report):
    """The whole table as one line: what a chip run shows of the spans
    although the result line carries only the per-layer metrics."""
    steps, lags = report.steps, sorted(report.flush_lag_s)
    return "program spans: " + json.dumps({
        "steps": steps,
        "columns": ["span", "thread", "calls/step", "wall_ms/step",
                    "own_ms/step", "idle_ms/step began inside"],
        "rows": report.table(),
        "idle_ms/step by [caller span, executor span]": sorted(
            ([outer, inner, round(seconds * 1e3 / steps, 3)]
             for (outer, inner), seconds in report.idle_rows.items()),
            key=lambda row: -row[2]),
        "flush -> execute lag ms [joined, median, max]": (
            [len(lags), round(lags[len(lags) // 2] * 1e3, 3),
             round(lags[-1] * 1e3, 3)] if lags else [0, None, None]),
        "setup_s since process start [calls, seconds]": {
            name: [calls, round(seconds, 3)]
            for name, (calls, seconds) in sorted(setup_totals().items())
            if name in ("init", "broadcast_parameters",
                        "cached_step.build")},
    })


def of(run):
    """The report of this traced run, reduced and logged once and kept
    on ``run``; ``None`` where the run left no trace file."""
    if not hasattr(run, "program_spans"):
        path = trace_file(run.cell["name"])
        run.program_spans = None
        if path is not None:
            chip = run.trace.chips[0] if run.trace is not None else None
            run.program_spans = reduce(load(path), chip, run.traced_steps)
            print("[bench] " + log_line(run.program_spans), flush=True)
    return run.program_spans


# --------------------------------------------------------------------------
# what the readers in layers/ take
# --------------------------------------------------------------------------

def wall_ms_per_step(run, name, thread=None):
    """Wall milliseconds a step of the span ``name`` on ``thread`` (any
    thread if ``None``); ``None`` where the trace has no such span."""
    report = of(run)
    return None if report is None else report.wall_ms_per_step(name, thread)


def wall_ms_per_call(run, name, thread=None):
    report = of(run)
    found = None if report is None else report.total(name, thread)
    return None if found is None else found[1] * 1e3 / found[0]


def idle_in_program_share(run):
    """Percent of the first chip's idle time in the traced window that
    began while the calling thread was inside any ``hvd:`` span: 0 where
    the step is one compiled program and no span opens in the window;
    ``None`` without a device trace or without the span seam."""
    report = of(run)
    if report is None or not report.idle_s or registry() is None:
        return None
    return 100.0 * report.idle_in_program_s / report.idle_s


def setup_seconds(name):
    """Total seconds of the span ``name`` since process start, from the
    program's registry; ``None`` where it has none."""
    found = setup_totals().get(name)
    return None if found is None else found[1]
