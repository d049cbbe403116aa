"""Builds a two-chip trace with a ``/host:metadata`` plane, so that the
join of ``device_scopes.py`` is known by construction (the numbers are in
``test_device_scopes.py``): two programs that share instruction names
under different scopes, a ``while`` nested in a ``while``, a fusion whose
fused instructions name two scopes, an operation without metadata, and a
permute round with an addition of its own. The device planes are encoded
with ``make_xplane.plane``; the metadata plane, which it cannot write
(stats on event metadata), by hand here. The programs are HLO text turned
into ``HloModuleProto`` bytes by jaxlib, whose serialization is not pinned
across versions: ``build()`` is called by the test, and no file is kept.

    python benchmark/tests/make_scopes_xplane.py OUT.xplane.pb
"""

from __future__ import annotations

import sys

from benchmark.tests.make_xplane import _bytes, _int, plane

STEP, APPLY = "jit_step(11)", "jit_apply(22)"
M = "f32[8,8]{1,0}"
S = f"(s32[], {M})"


def _meta(path):
    return f', metadata={{op_name="jit(step)/{path}"}}'


# One step: a stream pass, a weight gradient with Adam fused in (two
# scopes: the product's wins), a copy the compiler made, and an expert
# layer's loop of chunks with the experts' own loop inside.
STEP_TEXT = f"""HloModule jit_step, is_scheduled=true

%fused_stream (a.1: {M}) -> {M} {{
  %a.1 = {M} parameter(0)
  ROOT %neg.1 = {M} negate(%a.1){_meta("jvp(M)/hvd:model.stream/neg")}
}}

%fused_mixed (b.1: {M}, b.2: {M}) -> {M} {{
  %b.1 = {M} parameter(0)
  %b.2 = {M} parameter(1)
  %dot.1 = {M} dot(%b.1, %b.2), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}{_meta("transpose(jvp(M))/hvd:model.mlp/dot_general")}
  %mul.1 = {M} multiply(%dot.1, %b.2){_meta("hvd:optimizer.update/mul")}
  ROOT %add.1 = {M} add(%mul.1, %b.1){_meta("hvd:optimizer.update/add")}
}}

%inner_body (c.1: {S}) -> {S} {{
  %c.1 = {S} parameter(0)
  %c.2 = s32[] get-tuple-element(%c.1), index=0
  %c.3 = {M} get-tuple-element(%c.1), index=1
  %c.4 = s32[] constant(1)
  %c.5 = s32[] add(%c.2, %c.4)
  %exp.5 = {M} exponential(%c.3){_meta("jvp(M)/hvd:moe.experts/exp")}
  ROOT %c.6 = {S} tuple(%c.5, %exp.5)
}}

%inner_cond (d.1: {S}) -> pred[] {{
  %d.1 = {S} parameter(0)
  %d.2 = s32[] get-tuple-element(%d.1), index=0
  %d.3 = s32[] constant(2)
  ROOT %d.4 = pred[] compare(%d.2, %d.3), direction=LT
}}

%outer_body (e.1: {S}) -> {S} {{
  %e.1 = {S} parameter(0)
  %e.2 = s32[] get-tuple-element(%e.1), index=0
  %e.3 = {M} get-tuple-element(%e.1), index=1
  %tanh.7 = {M} tanh(%e.3){_meta("jvp(M)/hvd:moe.combine/hvd:moe.dispatch/tanh")}
  %e.4 = s32[] constant(0)
  %e.5 = {S} tuple(%e.4, %tanh.7)
  %while.6 = {S} while(%e.5), condition=%inner_cond, body=%inner_body{_meta("jvp(M)/hvd:moe.experts/while")}
  %e.6 = {M} get-tuple-element(%while.6), index=1
  %e.7 = s32[] constant(1)
  %e.8 = s32[] add(%e.2, %e.7)
  ROOT %e.9 = {S} tuple(%e.8, %e.6)
}}

%outer_cond (f.1: {S}) -> pred[] {{
  %f.1 = {S} parameter(0)
  %f.2 = s32[] get-tuple-element(%f.1), index=0
  %f.3 = s32[] constant(1)
  ROOT %f.4 = pred[] compare(%f.2, %f.3), direction=LT
}}

ENTRY %main (x.1: {M}) -> {M} {{
  %x.1 = {M} parameter(0)
  %fusion.1 = {M} fusion(%x.1), kind=kLoop, calls=%fused_stream{_meta("jvp(M)/hvd:model.stream/neg")}
  %fusion.2 = {M} fusion(%fusion.1, %x.1), kind=kOutput, calls=%fused_mixed{_meta("hvd:optimizer.update/add")}
  %copy.3 = {M} copy(%fusion.2)
  %g.1 = s32[] constant(0)
  %g.2 = {S} tuple(%g.1, %copy.3)
  %while.4 = {S} while(%g.2), condition=%outer_cond, body=%outer_body{_meta("jvp(M)/hvd:moe.combine/while")}
  ROOT %g.3 = {M} get-tuple-element(%while.4), index=1
}}
"""

# A second program with the same instruction names under other scopes:
# the optimizer's update, then one permute round with its own addition.
APPLY_TEXT = f"""HloModule jit_apply, is_scheduled=true

%fused_adam (a.1: {M}) -> {M} {{
  %a.1 = {M} parameter(0)
  %h.1 = {M} broadcast(%a.1), dimensions={{0,1}}{_meta("jvp(M)/hvd:model.stream/broadcast")}
  ROOT %sqrt.1 = {M} sqrt(%h.1){_meta("hvd:optimizer.update/sqrt")}
}}

ENTRY %main (x.1: {M}) -> {M} {{
  %x.1 = {M} parameter(0)
  %fusion.1 = {M} fusion(%x.1), kind=kLoop, calls=%fused_adam{_meta("hvd:optimizer.update/sqrt")}
  %collective-permute.8 = {M} collective-permute(%fusion.1), source_target_pairs={{{{0,1}},{{1,0}}}}{_meta("hvd:exchange.rounds/ppermute")}
  %add.9 = {M} add(%collective-permute.8, %x.1){_meta("hvd:exchange.rounds/add")}
  ROOT %copy.3 = {M} copy(%add.9){_meta("hvd:exchange.rounds/dynamic_update_slice")}
}}
"""

# (name, start ns, end ns) on chip 0; nested events follow their parent
STEP_OPS = [
    ("fusion.1", 1000, 2000),       # model.stream, forward
    ("fusion.2", 2000, 4000),       # mixed: model.mlp (its dot), backward
    ("copy.3", 4000, 4500),         # no metadata: unattributed, other
    ("while.4", 5000, 9000),        # moe.combine: own 500
    ("tanh.7", 5000, 5500),         # moe.dispatch (the innermost scope)
    ("while.6", 5500, 8500),        # moe.experts: own 1000
    ("exp.5", 5500, 6500),
    ("exp.5", 6500, 7500),
]
APPLY_OPS = [
    ("fusion.1", 10000, 11500),     # optimizer.update
    ("collective-permute.8", 11500, 11800),
    ("add.9", 11800, 12000),        # exchange.rounds, no collective
    ("copy.3", 12000, 12300),       # the same
    ("mystery.10", 12300, 12400),   # a name the text lacks
]


def _module_proto(text):
    from jax._src.lib import xla_client

    return xla_client._xla.hlo_module_from_text(
        text).as_serialized_hlo_module_proto()


def metadata_plane(programs, plane_id=9):
    """``/host:metadata``: one event-metadata entry per program with a
    bytes stat ``Hlo Proto`` (``HloProto.hlo_module = 1``)."""
    body = _int(1, plane_id) + _bytes(2, "/host:metadata")
    for mid, (name, text) in enumerate(programs.items(), 1):
        stat = _int(1, 1) + _bytes(6, _bytes(1, _module_proto(text)))
        body += _bytes(4, _int(1, mid) + _bytes(
            2, _int(1, mid) + _bytes(2, name) + _bytes(5, stat)))
    body += _bytes(5, _int(1, 1) + _bytes(2, _int(1, 1)
                                          + _bytes(2, "Hlo Proto")))
    return _bytes(1, body)


def chip_lines(shift, with_copy):
    ops = [(n, s + shift, e + shift, {}) for n, s, e in STEP_OPS + APPLY_OPS
           if with_copy or (n, s) != ("copy.3", 4000)]
    modules = [(STEP, 1000 + shift, 9000 + shift, {}),
               (APPLY, 10000 + shift, 12400 + shift, {})]
    return [("XLA Modules", modules), ("XLA Ops", ops)]


def build():
    return (plane("/device:TPU:0", chip_lines(0, True), 1)
            + plane("/device:TPU:1", chip_lines(100, False), 2)
            + plane("/host:CPU", [("python3", [("bench:window", 0, 13000,
                                                {})])], 3)
            + metadata_plane({STEP: STEP_TEXT, APPLY: APPLY_TEXT}))


if __name__ == "__main__":
    with open(sys.argv[1], "wb") as f:
        f.write(build())
    print(f"wrote {sys.argv[1]}")
