#!/usr/bin/env python3
"""Device time by the program's own scopes.

``horovod_tpu/timeline.py`` ``scope(name)`` puts the code a compiled step
is traced from inside ``jax.named_scope("hvd:<layer>.<stage>")`` blocks
(docs/timeline.md has the table). A scope exists at trace time only: its
name rides in the ``op_name`` of every HLO instruction traced inside it,
also inside a fused computation, under ``jvp(...)`` and
``transpose(jvp(...))`` where autodiff made the instruction. The v5e
trace names a device operation by its HLO text and nothing else; but the
profiler's ``.xplane.pb`` also carries the optimized, scheduled HLO module
of every program that ran, in its ``/host:metadata`` plane: one
event-metadata entry per program, named ``jit_step(<program id>)`` like
the program's ``XLA Modules`` events, with a bytes stat ``Hlo Proto``.
``jax.profiler.ProfileData`` does not show event-metadata stats, so this
module reads them from the raw file with a minimal protobuf wire reader,
turns each module into text with what jaxlib ships, and joins:

* ``{program: {instruction name: (scope, phase, mixed)}}`` from the text.
  ``scope`` is the innermost ``hvd:[a-z_.]+`` anywhere in ``op_name``,
  else ``unattributed``. ``phase`` is ``optimizer`` under
  ``hvd:optimizer.update``, ``exchange`` under ``hvd:exchange.*``, else
  ``backward`` where the path holds ``transpose(`` (a hand-written
  ``custom_vjp`` rule is traced under the transposed call and carries it
  too), else ``forward`` where it holds ``jvp(``, else ``other``. A fusion
  takes what its fused instructions say (those without ``op_name``,
  parameters, constants, broadcasts, bitcasts, tuples abstain): where
  they name two scopes it is ``mixed`` and takes the scope of its
  ``dot`` / ``convolution`` where it has a scoped one (a weight gradient
  with Adam fused in is the product's time), else the scope most of its
  scoped instructions carry, a tie going to the root's; instructions
  without a scope do not outvote scoped ones (Adam with the job's
  ``apply_updates`` fused in is the optimizer's), and a fusion none of
  whose instructions has a scope is ``unattributed``. Its phase is the
  chosen instructions' by the same rule. One kind of instruction loses
  its path on the way: the TPU compiler turns ``lax.ragged_dot`` into a
  Mosaic call it names itself (``op_name="ragged-dot-none"``). The
  program has one emitter of it (``parallel/moe.py`` ``grouped_matmul``,
  under ``hvd:moe.experts``), so the name says the scope
  (``COMPILER_NAMED``); its phase is ``backward`` where anything it reads
  is (through instructions that have no path of their own), else
  ``forward`` where anything it reads is; in a program without a scope
  anywhere it stays ``unattributed``.
* every ``XLA Ops`` event of a chip gets its program from the
  ``XLA Modules`` event that contains it in time and its attributes from
  its instruction name (``trace_reduce.parse_op``); **own time**
  (``trace_reduce.self_seconds``, unchanged) is summed by scope, by phase
  and by ``mixed``, mean over the chips.

``of(run)`` finds the traced run's file itself (as ``program_spans.py``
does), reduces it once and logs the whole table as one ``[bench]`` line;
the readers in ``layers/`` take single numbers from it. On a program
without scopes (the parent of the PR that added them) every reader
returns ``None`` and nothing raises.

    python3 benchmark/device_scopes.py <file.xplane.pb> [--steps N]

prints the same table for any trace: the operator's tool, for a user's own
five-line job under ``jax.profiler.trace``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
UNATTRIBUTED = "unattributed"
SCOPE = re.compile(r"hvd:[a-z_.]+")
OPTIMIZER, EXCHANGE = "hvd:optimizer.update", "hvd:exchange."
ROUNDS = "hvd:exchange.rounds"
FORWARD, BACKWARD, OTHER = "forward", "backward", "other"
PHASES = (FORWARD, BACKWARD, "optimizer", "exchange", OTHER)
# instructions of a fused computation that say nothing of whose work it is
ABSTAIN = ("parameter", "constant", "broadcast", "bitcast", "tuple",
           "get-tuple-element", "iota")
PRODUCTS = ("dot", "convolution")
# Kernels the compiler emits under an ``op_name`` of its own, without the
# path: the scope of their one emitter in the program.
COMPILER_NAMED = {"ragged-dot": "hvd:moe.experts"}
TOP_OPS = 3


# --------------------------------------------------------------------------
# the raw file: protobuf wire format, as far as the join needs it
# --------------------------------------------------------------------------

def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def fields(buf):
    """``(field number, value)`` of one serialized message: an int for a
    varint, a ``memoryview`` for a length-delimited or fixed-width one."""
    buf, at = memoryview(buf), 0
    while at < len(buf):
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, value


def _first(message, number, default=None):
    return next((v for n, v in fields(message) if n == number), default)


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane, number):
    """Values of a ``map<int64, Message>`` field of a plane."""
    return [_first(entry, 2) for n, entry in fields(plane) if n == number]


def hlo_protos(path):
    """``{program: serialized HloModuleProto}`` from the metadata plane of
    an ``.xplane.pb``: ``XSpace.planes=1``, ``XPlane.name=2 /
    event_metadata=4 / stat_metadata=5``, ``XEventMetadata.name=2 /
    stats=5``, ``XStat.metadata_id=1 / bytes_value=6``,
    ``HloProto.hlo_module=1``. Empty where the file holds none."""
    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for number, plane in fields(data):
        if number != 1 or _text(_first(plane, 2, b"")) != METADATA_PLANE:
            continue
        stat_names = {_first(meta, 1): _text(_first(meta, 2, b""))
                      for meta in _map_entries(plane, 5)}
        for meta in _map_entries(plane, 4):
            program = _text(_first(meta, 2, b""))
            for n, stat in fields(meta):
                if n == 5 and stat_names.get(_first(stat, 1)) == HLO_STAT:
                    proto = _first(stat, 6)
                    module = None if proto is None else _first(proto, 1)
                    if module is not None:
                        out[program] = bytes(module)
    return out


def module_text(module_proto):
    """The module as HLO text, by what jaxlib ships."""
    from jax._src.lib import xla_client

    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string()


# --------------------------------------------------------------------------
# the module's text: instruction -> (scope, phase, mixed)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Attr:
    scope: str = UNATTRIBUTED
    phase: str = OTHER
    mixed: bool = False


_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_REFERENCE = re.compile(r"%([\w.\-]+)")
# one instruction line: ``attr`` is ``None`` without an ``op_name``,
# ``calls`` a fusion's fused computation
_Row = collections.namedtuple("_Row", "name opcode root attr calls")


def compiler_named(op_name):
    """The scope of a kernel the compiler named itself (the last part of
    its ``op_name``; inside a loop's body the loop's path is before it),
    or ``None``."""
    last = op_name.rsplit("/", 1)[-1]
    return next((scope for stem, scope in COMPILER_NAMED.items()
                 if last == stem or last.startswith(stem + "-")), None)


def attr_of(op_name, named=True):
    """Scope and phase an ``op_name`` path says; ``named``: whether a
    kernel the compiler named itself takes its emitter's scope."""
    scopes = SCOPE.findall(op_name)
    scope = (named and compiler_named(op_name)) or (
        scopes[-1] if scopes else UNATTRIBUTED)
    if OPTIMIZER in op_name:
        phase = "optimizer"
    elif EXCHANGE in op_name:
        phase = "exchange"
    elif "transpose(" in op_name:
        phase = BACKWARD
    elif "jvp(" in op_name:
        phase = FORWARD
    else:
        phase = OTHER
    return Attr(scope, phase)


def _most(votes, root):
    """The value most of ``votes`` carry; a tie goes to ``root`` where it
    is one of the leaders, else to the first."""
    counts = {}
    for vote in votes:
        counts[vote] = counts.get(vote, 0) + 1
    best = max(counts.values())
    leaders = [vote for vote in counts if counts[vote] == best]
    return root if root in leaders else leaders[0]


def fusion_attr(fused):
    """What a fusion is, from its fused instructions ``[(opcode, Attr or
    None, is root)]``."""
    voters = [(opcode, attr, root) for opcode, attr, root in fused
              if attr is not None and opcode not in ABSTAIN]
    scoped = [v for v in voters if v[1].scope != UNATTRIBUTED]
    chosen = scoped or voters
    if not chosen:
        return Attr()
    mixed = len({attr.scope for _, attr, _ in scoped}) > 1
    products = [v for v in chosen if v[0] in PRODUCTS]
    if mixed and products:
        chosen = products
    root = next((attr for _, attr, is_root in chosen if is_root), None)
    scope = _most([attr.scope for _, attr, _ in chosen],
                  root and root.scope)
    of_scope = [v for v in chosen if v[1].scope == scope]
    root = next((attr for _, attr, is_root in of_scope if is_root), None)
    phase = _most([attr.phase for _, attr, _ in of_scope],
                  root and root.phase)
    return Attr(scope, phase, mixed)


def parse_module(text):
    """``{instruction name: Attr}`` of every instruction of a module's
    text outside its fused computations. A program without a scope
    anywhere (the parent of the PR that added them) names nothing: its
    compiler-named kernels stay ``unattributed`` too."""
    computations, current, pathless = {}, None, {}
    scoped = SCOPE.search(text) is not None
    for line in text.splitlines():
        if not line.startswith(" "):
            header = _HEADER.match(line)
            current = None
            if header:
                current = computations.setdefault(header.group(1), [])
            continue
        if current is None or " = " not in line:
            continue
        body = line.strip()
        root = body.startswith("ROOT ")
        name, opcode, _ = trace_reduce.parse_op(body[5:] if root else body)
        op_name = _OP_NAME.search(line)
        calls = _CALLS.search(line) if opcode == "fusion" else None
        op_name = op_name.group(1) if op_name else None
        if op_name is None or "/" not in op_name:
            pathless[name] = _REFERENCE.findall(body.split(" = ", 1)[1])
        current.append(_Row(
            name, opcode, root,
            None if op_name is None else attr_of(op_name, named=scoped),
            calls.group(1) if calls else None))
    fused = {row.calls for rows in computations.values() for row in rows
             if row.calls}
    out = {}
    for computation, rows in computations.items():
        if computation in fused:
            continue
        for row in rows:
            attr = row.attr
            if row.calls in computations:
                attr = fusion_attr([(f.opcode, f.attr, f.root)
                                    for f in computations[row.calls]])
            out[row.name] = attr or Attr()
    for name, attr in out.items():
        if attr.scope != UNATTRIBUTED and name in pathless:
            out[name] = Attr(attr.scope, _phase_read(name, out, pathless))
    return out


def _phase_read(name, table, pathless):
    """The phase of what ``name`` reads: ``backward`` where any operand
    is, else ``forward`` where any is; an operand without a path of its
    own (``pathless``: name -> what its line refers to) stands for what
    it reads in turn."""
    found, seen, stack = set(), {name}, list(pathless[name])
    while stack:
        operand = stack.pop()
        if operand in seen or operand not in table:
            continue
        seen.add(operand)
        if operand in pathless:
            stack.extend(pathless[operand])
        else:
            found.add(table[operand].phase)
    return next((phase for phase in (BACKWARD, FORWARD)
                 if phase in found), OTHER)


def programs(path):
    """``{program: {instruction name: Attr}}`` for a trace file."""
    return {program: parse_module(module_text(proto))
            for program, proto in hlo_protos(path).items()}


# --------------------------------------------------------------------------
# the join with the device's events
# --------------------------------------------------------------------------

def attribute(chip, tables):
    """One ``(event, Attr)`` per ``XLA Ops`` event of ``chip``: the
    program is that of the ``XLA Modules`` event that contains the
    operation's start; an operation outside every module, of a program
    the file has no text of, or of a name the text lacks, is
    ``unattributed``."""
    modules = sorted(chip.modules, key=lambda m: m.start)
    starts = [m.start for m in modules]
    out = []
    for ev in chip.ops:
        at = bisect.bisect_right(starts, ev.start) - 1
        table = (tables.get(modules[at].name, {})
                 if at >= 0 and ev.start < modules[at].end else {})
        out.append((ev, table.get(ev.name, Attr())))
    return out


def own_seconds(chip, tables):
    """``{(scope, phase, mixed, collective, label): seconds}`` of own
    time on one chip, by ``trace_reduce.self_seconds``' rule: the events
    are handed to it under a label that is the key."""
    keys, relabelled = {}, []
    for ev, attr in attribute(chip, tables):
        key = (attr.scope, attr.phase, attr.mixed,
               trace_reduce.is_collective(ev), ev.label)
        label = keys.setdefault(key, str(len(keys)))
        relabelled.append(trace_reduce.Event(
            ev.name, ev.start, ev.end, opcode=ev.opcode, label=label))
    own = trace_reduce.self_seconds(trace_reduce.Chip(0, relabelled, []))
    return {key: own.get(label, 0.0) for key, label in keys.items()}


@dataclasses.dataclass
class Report:
    steps: int
    chips: int
    rows: dict            # own_seconds' rows, summed over the chips
    programs: dict        # {program: instructions with a scope}
    reduce_s: float = 0.0

    def matching(self, scope=None, phase=None, mixed=None,
                 collective=None):
        """``[(label, seconds)]`` of the rows that match: ``scope`` a name
        or a tuple of names, the others a value; ``None`` matches all."""
        scopes = (scope,) if isinstance(scope, str) else scope
        return [(label, seconds)
                for (s, p, m, c, label), seconds in self.rows.items()
                if (scopes is None or s in scopes) and phase in (None, p)
                and mixed in (None, m) and collective in (None, c)]

    def seconds(self, **match):
        """Own seconds, mean over the chips, of the rows that match."""
        return (sum(seconds for _, seconds in self.matching(**match))
                / max(1, self.chips))

    @property
    def scoped(self):
        """Whether any program of the trace has a scope in its text."""
        return any(self.programs.values())

    def ms_per_step(self, **match):
        return self.seconds(**match) * 1e3 / self.steps

    def share(self, **match):
        total = self.seconds()
        return 100.0 * self.seconds(**match) / total if total else None

    def top(self, limit=TOP_OPS, **match):
        """``[[label, ms a step], ...]`` of the operations with most own
        time among the rows that match."""
        totals = {}
        for label, seconds in self.matching(**match):
            label = label[:trace_reduce.LABEL_CHARS]
            totals[label] = totals.get(label, 0.0) + seconds
        per_step = 1e3 / self.steps / max(1, self.chips)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
        return [[label, round(seconds * per_step, 4)]
                for label, seconds in ranked]

    def table(self):
        """``[[scope, forward, backward, optimizer, exchange, other,
        total, of it mixed], ...]`` in ms a step and chip, most first."""
        scopes = sorted({key[0] for key in self.rows},
                        key=lambda s: -self.seconds(scope=s))
        return [[scope]
                + [round(self.ms_per_step(scope=scope, phase=phase), 4)
                   for phase in PHASES]
                + [round(self.ms_per_step(scope=scope), 4),
                   round(self.ms_per_step(scope=scope, mixed=True), 4)]
                for scope in scopes]


def reduce(chips, tables, steps):
    rows = {}
    for chip in chips:
        for key, seconds in own_seconds(chip, tables).items():
            rows[key] = rows.get(key, 0.0) + seconds
    scoped = {program: sum(attr.scope != UNATTRIBUTED
                           for attr in table.values())
              for program, table in tables.items()}
    return Report(steps, len(chips), rows, scoped)


def report_of(path, chips, steps):
    """The report of the trace file ``path`` whose device planes are
    already loaded as ``chips``, with the seconds it took (reading the
    HLO, parsing it, the join) on it."""
    began = time.perf_counter()
    report = reduce(chips, programs(path), steps)
    report.reduce_s = time.perf_counter() - began
    return report


def count_by_scope(table):
    """``{scope: instructions}`` of one program: what a compile without a
    chip can say."""
    out = {}
    for attr in table.values():
        out[attr.scope] = out.get(attr.scope, 0) + 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def log_line(report):
    """The whole table as one line: what a chip run shows of the scopes
    although the result line carries only the per-layer metrics."""
    return "device scopes: " + json.dumps({
        "steps": report.steps, "chips": report.chips,
        "busy_ms/step": round(report.ms_per_step(), 4),
        "columns": ["scope"] + [f"{p}_ms/step" for p in PHASES]
        + ["own_ms/step", "mixed_ms/step"],
        "rows": report.table(),
        "phase_ms/step": {phase: round(report.ms_per_step(phase=phase), 4)
                          for phase in PHASES},
        "unattributed_share_%": report.share(scope=UNATTRIBUTED),
        "mixed_share_%": report.share(mixed=True),
        "largest unattributed [op, ms/step]":
            report.top(scope=UNATTRIBUTED),
        "largest mixed [op, ms/step]": report.top(mixed=True),
        "programs [scoped instructions]": report.programs,
        "reduce_s": round(report.reduce_s, 3),
    })


def of(run):
    """The report of this traced run, reduced and logged once and kept
    on ``run``; ``None`` where the run left no device trace, the file
    holds no HLO, or no program in it has a scope."""
    if not hasattr(run, "device_scopes"):
        from benchmark import program_spans

        run.device_scopes = None
        path = program_spans.trace_file(run.cell["name"])
        if path is not None and run.trace is not None:
            report = report_of(path, run.trace.chips, run.traced_steps)
            if report.scoped:
                run.device_scopes = report
                print("[bench] " + log_line(report), flush=True)
    return run.device_scopes


# --------------------------------------------------------------------------
# what the readers in layers/ take
# --------------------------------------------------------------------------

def ms_per_step(run, **match):
    """Own milliseconds a step and chip of the rows that match
    (``scope``: a name with its prefix, or a tuple of them; ``phase``;
    ``collective``); ``None`` without scopes, or where nothing matches."""
    report = of(run)
    if report is None:
        return None
    return report.ms_per_step(**match) or None


def phase_ms(run, phase):
    """As :func:`ms_per_step` for a phase, and 0 where the program has
    scopes and nothing of that phase ran."""
    report = of(run)
    return None if report is None else report.ms_per_step(phase=phase)


def share(run, **match):
    """Percent of the device's own time in the rows that match."""
    report = of(run)
    return None if report is None else report.share(**match)


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(
        description="device time by hvd: scope from a profiler trace")
    parser.add_argument("trace", help="a .xplane.pb file")
    parser.add_argument("--steps", type=int, default=1,
                        help="steps the trace holds (times are per step)")
    args = parser.parse_args(argv)
    if not hlo_protos(args.trace):
        sys.exit(f"{args.trace}: no '{HLO_STAT}' in a {METADATA_PLANE} "
                 "plane")
    chips = trace_reduce.load(args.trace).chips
    if not chips:
        sys.exit(f"{args.trace}: no /device:TPU:<n> plane (scopes by "
                 "instruction count: " + json.dumps({
                     program: count_by_scope(table) for program, table
                     in programs(args.trace).items()}) + ")")
    print(log_line(report_of(args.trace, chips, args.steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
