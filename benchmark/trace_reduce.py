"""From the profiler's ``.xplane.pb`` to numbers.

The file is read with ``jax.profiler.ProfileData`` and nothing else. What
is taken from it:

* every plane named ``/device:TPU:<n>`` is one chip; its line
  ``XLA Ops`` holds one event per executed HLO operation (nested where an
  operation such as ``while`` contains others), named by the
  instruction's text (``%fusion.4 = bf16[..]{..} fusion(...)``); its
  line ``Async XLA Ops``, where there is one, holds each asynchronous
  operation from its start to its end; its line ``XLA Modules`` one
  event per executed program (a launch);
* the host plane's ``bench:*`` events are the benchmark's own spans
  (``timing.Spans``), on the same clock.

All arithmetic is on integer-like nanoseconds as the file gives them;
results are seconds. Nothing here knows a model or a cell.
"""

from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"

# HLO opcodes that move data between chips. An asynchronous one shows as a
# "-start" and a "-done" event; the exchange is in flight from the first's
# start to the second's end.
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)(-start|-done)?$")
# operations that only contain others: their time is their children's
CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_SUFFIX = re.compile(r"\.(\d+)$")


def parse_op(text):
    """``(name, opcode, label)`` of an ``XLA Ops`` event. The trace names
    an operation by its HLO text, ``%name = type opcode(operands``; the
    label is that text without layouts and operands. A bare name (an
    older trace, a test) is its own opcode, less a numeric suffix."""
    text = text.strip()
    if " = " not in text:
        name = text.lstrip("%")
        return name, _SUFFIX.sub("", name), name
    name, rest = text.split(" = ", 1)
    rest = rest.strip()
    if rest.startswith("("):                 # a tuple type: skip it whole
        depth = 0
        for at, char in enumerate(rest):
            depth += (char == "(") - (char == ")")
            if depth == 0:
                break
        result, tail = rest[:at + 1], rest[at + 1:]
    else:
        result, _, tail = rest.partition(" ")
    opcode = tail.strip().split("(", 1)[0].strip()
    for _ in range(3):                       # layouts nest: {..T(8,128)..}
        result = _LAYOUT.sub("", result)
    name = name.lstrip("%")
    return name, opcode, f"{name} = {result} {opcode}"


@dataclasses.dataclass
class Event:
    """One event of a line. Built from a device operation's text alone,
    ``name``, ``opcode`` and ``label`` (what a breakdown shows) are
    parsed out of it."""

    name: str
    start: float      # ns
    end: float        # ns
    opcode: str = ""
    label: str = ""

    def __post_init__(self):
        if not self.opcode:
            self.name, self.opcode, self.label = parse_op(self.name)

    @property
    def seconds(self):
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class Chip:
    index: int
    ops: list         # [Event], the XLA Ops line
    modules: list     # [Event], the XLA Modules line
    in_flight: list = dataclasses.field(default_factory=list)
    # [Event], the Async XLA Ops line


@dataclasses.dataclass
class Trace:
    chips: list       # [Chip], by index
    spans: list       # [Event], the benchmark's host spans


def _events(line, **fields):
    out = [Event(e.name, float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns), **fields)
           for e in line.events]
    out.sort(key=lambda ev: (ev.start, -ev.end))
    return out


def load(path) -> Trace:
    """Read one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    chips, spans = [], []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            chips.append(Chip(int(match.group(1)), *(
                _events(lines[name]) if name in lines else []
                for name in (OPS_LINE, MODULES_LINE, ASYNC_LINE))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line, opcode="span")
                             if ev.name.startswith(SPAN_PREFIX))
    chips.sort(key=lambda chip: chip.index)
    spans.sort(key=lambda ev: (ev.start, -ev.end))
    return Trace(chips, spans)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def merge(intervals):
    """Union of ``(start, end)`` pairs as a sorted list of disjoint
    pairs."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def length(merged):
    return sum(end - start for start, end in merged)


def subtract(merged_a, merged_b):
    """The part of ``merged_a`` that ``merged_b`` does not cover; both
    are disjoint and sorted."""
    out, j = [], 0
    for start, end in merged_a:
        while j < len(merged_b) and merged_b[j][1] <= start:
            j += 1
        k, cursor = j, start
        while k < len(merged_b) and merged_b[k][0] < end:
            if merged_b[k][0] > cursor:
                out.append((cursor, merged_b[k][0]))
            cursor = max(cursor, merged_b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


# --------------------------------------------------------------------------
# per chip
# --------------------------------------------------------------------------

def busy_intervals(chip):
    return merge((ev.start, ev.end) for ev in chip.ops)


def window(chip):
    """``(start, end)`` of the chip's traced work: first operation's
    start to last operation's end."""
    if not chip.ops:
        return (0.0, 0.0)
    return (min(ev.start for ev in chip.ops),
            max(ev.end for ev in chip.ops))


def is_collective(event):
    return bool(COLLECTIVE.match(event.opcode))


def collective_intervals(chip):
    """One ``(start, end)`` per exchange. A synchronous collective is its
    own event. An asynchronous one runs from its ``-start`` event's start
    to the matching ``-done`` event's end: matched by the shared numeric
    suffix where there is one, else first started, first done; where the
    trace has a line of asynchronous operations, its events give the same
    intervals directly (the union does not count them twice)."""
    out = [(ev.start, ev.end) for ev in chip.in_flight if is_collective(ev)]
    pending = {}
    for ev in chip.ops:
        match = COLLECTIVE.match(ev.opcode)
        if not match:
            continue
        kind, phase = match.groups()
        suffix = (_SUFFIX.search(ev.name) or [None, None])[1]
        if phase is None:
            out.append((ev.start, ev.end))
        elif phase == "-start":
            pending.setdefault(kind, []).append((suffix, ev))
        else:
            queue = pending.get(kind, [])
            at = next((i for i, (s, _) in enumerate(queue) if s == suffix),
                      0 if queue else None)
            begin = queue.pop(at)[1].start if at is not None else ev.start
            out.append((begin, ev.end))
    for queue in pending.values():          # started, never seen done
        out.extend((ev.start, ev.end) for _, ev in queue)
    return out


def compute_intervals(chip):
    """Union of the intervals in which an operation that is neither a
    collective nor a mere container runs."""
    return merge((ev.start, ev.end) for ev in chip.ops
                 if not is_collective(ev) and ev.opcode not in CONTAINERS)


def collective_seconds(chip):
    """``(total, exposed)``: time with an exchange in flight, and the
    part of it during which no other operation runs on this chip."""
    in_flight = merge(collective_intervals(chip))
    exposed = subtract(in_flight, compute_intervals(chip))
    return length(in_flight) * 1e-9, length(exposed) * 1e-9


def self_seconds(chip):
    """``{label: seconds}`` of each operation's own time: its duration
    less what the operations nested inside it cover."""
    totals, stack = {}, []   # stack of [event, covered_ns]

    def close(upto):
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            own = max(0.0, (ev.end - ev.start) - covered)
            totals[ev.label] = totals.get(ev.label, 0.0) + own * 1e-9
            if stack:
                stack[-1][1] += ev.end - ev.start

    for ev in chip.ops:
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return totals


# --------------------------------------------------------------------------
# over the chips
# --------------------------------------------------------------------------

def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else 0.0


def busy_and_window(trace):
    """``(busy_s, window_s)``, each the mean over the chips."""
    busy = _mean(length(busy_intervals(c)) * 1e-9 for c in trace.chips)
    span = _mean((window(c)[1] - window(c)[0]) * 1e-9 for c in trace.chips)
    return busy, span


def idle_share(trace):
    busy, span = busy_and_window(trace)
    return 1.0 - busy / span if span > 0 else None


def launches(trace):
    """Programs executed, mean over the chips."""
    return _mean(len(c.modules) for c in trace.chips)


def collectives(trace):
    """``(total_s, exposed_s)``, each the mean over the chips."""
    pairs = [collective_seconds(c) for c in trace.chips]
    return _mean(p[0] for p in pairs), _mean(p[1] for p in pairs)


LABEL_CHARS = 120


def top_ops(trace, limit=10):
    """``[[name, seconds], ...]``: the operations with most own time,
    seconds the mean over the chips, named by the instruction's text
    without layouts and operands. (The framework's module path is not in
    the trace jax 0.9 writes on this chip.)"""
    totals = {}
    for chip in trace.chips:
        for label, seconds in self_seconds(chip).items():
            label = label[:LABEL_CHARS]
            totals[label] = totals.get(label, 0.0) + seconds
    n = max(1, len(trace.chips))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds / n] for name, seconds in ranked]


def idle_gaps(trace, limit=10):
    """``[[span, seconds], ...]``: idle time of the first chip inside its
    window, summed by the innermost benchmark span the host was in when
    the gap began (``host:other`` outside all of them), longest first."""
    if not trace.chips or not trace.chips[0].ops:
        return []
    chip = trace.chips[0]
    gaps = subtract([window(chip)], busy_intervals(chip))
    totals = {}
    for start, end in gaps:
        inside = [s for s in trace.spans if s.start <= start < s.end]
        name = (max(inside, key=lambda s: s.start).name[len(SPAN_PREFIX):]
                if inside else "host:other")
        totals[name] = totals.get(name, 0.0) + (end - start) * 1e-9
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]
