"""``device_scopes.py``: the wire reader against a real trace made here on
the CPU backend, and the join against ``make_scopes_xplane.build()``, whose
numbers are known by construction.

Chip 0 (ns). Program ``jit_step(11)`` 1000-9000: ``fusion.1`` 1000-2000
(``model.stream``, forward), ``fusion.2`` 2000-4000 (fused: a ``dot`` of
``model.mlp`` under ``transpose(`` and two instructions of
``optimizer.update``: mixed, the product's), ``copy.3`` 4000-4500 (no
metadata), ``while.4`` 5000-9000 (``moe.combine``) holding ``tanh.7``
5000-5500 (``moe.dispatch``, the innermost of two scopes) and ``while.6``
5500-8500 (``moe.experts``) holding ``exp.5`` twice, 1000 each. Program
``jit_apply(22)`` 10000-12400 has the same names under other scopes:
``fusion.1`` 10000-11500 (``optimizer.update``; its fused broadcast names
``model.stream`` and abstains), ``collective-permute.8`` 11500-11800,
``add.9`` 11800-12000 and ``copy.3`` 12000-12300 (``exchange.rounds``),
``mystery.10`` 12300-12400 (not in the text). Chip 1 is the same 100 ns
later without the step's ``copy.3``."""

import importlib
import json
import shutil
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import device_scopes as ds
from benchmark import program_spans as ps
from benchmark import trace_reduce as tr
from benchmark.tests import make_scopes_xplane, make_xplane

NS = 1e-9
CELL = "a-cell"
READERS = {  # per step and chip at 2 steps, mean of the two chips
    "fwd_ms": 5000 / 2, "bwd_ms": 2000 / 2, "optimizer_ms": 1500 / 2,
    "moe_routing_ms": 1000 / 2, "attn_prepare_ms": None,
    "attn_own_block_ms": None, "stream_ms": 1000 / 2,
    "exchange_core_ms": 500 / 2,
    "scope_unattributed_share": 100 * 350 / 9650,
    "scope_mixed_share": 100 * 2000 / 9650,
}


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("scopes") / "scopes.xplane.pb"
    out.write_bytes(make_scopes_xplane.build())
    return str(out)


@pytest.fixture(scope="module")
def report(path):
    return ds.reduce(tr.load(path).chips, ds.programs(path), steps=2)


def ns(seconds):
    return round(seconds / NS)


# ------------------------------------------------------------ wire reader

def test_wire_reader_finds_the_module_text_of_a_real_trace(tmp_path):
    """A CPU trace made here: the metadata plane holds the program's
    optimized HLO, and the text that comes back has the scope in it."""
    from horovod_tpu.models import scopes

    scope = scopes.STREAM

    @jax.jit
    def scoped_step(x):
        with scope():
            return jnp.tanh(x) @ x

    x = jnp.ones((8, 8))
    scoped_step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        scoped_step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    [file] = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    protos = ds.hlo_protos(str(file))
    [program] = [name for name in protos if name.startswith(
        "jit_scoped_step(")]
    text = ds.module_text(protos[program])
    assert "HloModule jit_scoped_step" in text
    assert "hvd:model.stream/tanh" in text
    table = ds.parse_module(text)
    assert ds.Attr("hvd:model.stream", ds.OTHER) in table.values()


def test_fields_reads_every_wire_type():
    message = (make_xplane._int(1, 300) + make_xplane._bytes(2, "ab")
               + bytes([3 << 3 | 1]) + bytes(8) + bytes([4 << 3 | 5])
               + bytes(4))
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in ds.fields(message)]
    assert got == [(1, 300), (2, b"ab"), (3, bytes(8)), (4, bytes(4))]
    with pytest.raises(ValueError):
        list(ds.fields(bytes([1 << 3 | 3])))


def test_a_file_without_the_plane_has_no_programs():
    assert ds.hlo_protos(make_xplane.PATH) == {}


# ------------------------------------------------------------- the tables

def test_programs_share_names_under_their_own_scopes(path):
    tables = ds.programs(path)
    step, apply = (tables[make_scopes_xplane.STEP],
                   tables[make_scopes_xplane.APPLY])
    assert step["fusion.1"] == ds.Attr("hvd:model.stream", ds.FORWARD)
    assert apply["fusion.1"] == ds.Attr("hvd:optimizer.update", "optimizer")
    assert step["fusion.2"] == ds.Attr("hvd:model.mlp", ds.BACKWARD, True)
    assert step["copy.3"] == ds.Attr()
    assert apply["copy.3"] == ds.Attr("hvd:exchange.rounds", "exchange")
    assert step["tanh.7"].scope == "hvd:moe.dispatch"     # the innermost
    assert step["while.6"] == ds.Attr("hvd:moe.experts", ds.FORWARD)
    assert "dot.1" not in step and "exp.5" in step   # fused ones are not ops


@pytest.mark.parametrize("fused,want", [
    # agree: theirs; parameters and broadcasts abstain
    ([("parameter", None, False),
      ("broadcast", ds.Attr("hvd:a", ds.FORWARD), False),
      ("add", ds.Attr("hvd:b", ds.BACKWARD), True)],
     ds.Attr("hvd:b", ds.BACKWARD)),
    # two scopes, no product: most, and mixed
    ([("add", ds.Attr("hvd:a", ds.FORWARD), False),
      ("add", ds.Attr("hvd:b", ds.BACKWARD), False),
      ("add", ds.Attr("hvd:b", ds.BACKWARD), True)],
     ds.Attr("hvd:b", ds.BACKWARD, True)),
    # a tie goes to the root
    ([("add", ds.Attr("hvd:a", ds.FORWARD), False),
      ("add", ds.Attr("hvd:b", ds.BACKWARD), True)],
     ds.Attr("hvd:b", ds.BACKWARD, True)),
    # the product's, against the many
    ([("convolution", ds.Attr("hvd:a", ds.BACKWARD), False),
      ("add", ds.Attr("hvd:b", "optimizer"), False),
      ("add", ds.Attr("hvd:b", "optimizer"), True)],
     ds.Attr("hvd:a", ds.BACKWARD, True)),
    # unscoped instructions neither outvote a scope nor make it mixed
    ([("add", ds.Attr(), False), ("add", ds.Attr(), False),
      ("sqrt", ds.Attr("hvd:b", "optimizer"), True)],
     ds.Attr("hvd:b", "optimizer")),
    # one scope, two phases: most of them, not mixed
    ([("add", ds.Attr("hvd:a", ds.FORWARD), False),
      ("add", ds.Attr("hvd:a", ds.BACKWARD), False),
      ("add", ds.Attr("hvd:a", ds.BACKWARD), True)],
     ds.Attr("hvd:a", ds.BACKWARD)),
    # nothing votes
    ([("parameter", None, False), ("add", None, True)], ds.Attr()),
    # no scope anywhere: the phase still reads
    ([("add", ds.Attr(ds.UNATTRIBUTED, ds.FORWARD), True)],
     ds.Attr(ds.UNATTRIBUTED, ds.FORWARD)),
], ids=["agree", "most", "tie-root", "product", "unscoped-abstain",
        "phases", "nothing", "unattributed"])
def test_fusion_rule(fused, want):
    assert ds.fusion_attr(fused) == want


@pytest.mark.parametrize("op_name,want", [
    ("jit(s)/jvp(M)/block_0/hvd:model.stream/add",
     ds.Attr("hvd:model.stream", ds.FORWARD)),
    ("jit(s)/transpose(jvp(M))/hvd:moe.combine/hvd:moe.dispatch/gather",
     ds.Attr("hvd:moe.dispatch", ds.BACKWARD)),
    ("jit(s)/transpose(jvp(hvd:model.mlp))/dot_general",
     ds.Attr("hvd:model.mlp", ds.BACKWARD)),
    ("jit(s)/hvd:optimizer.update/transpose(jvp(x))/mul",
     ds.Attr("hvd:optimizer.update", "optimizer")),
    ("jit(s)/hvd:exchange.psum/psum", ds.Attr("hvd:exchange.psum",
                                              "exchange")),
    ("jit(s)/jvp(jit(log_softmax))/sub",
     ds.Attr(ds.UNATTRIBUTED, ds.FORWARD)),
    ("jit(s)/add", ds.Attr()),
    # the compiler's own name for its grouped product, bare and in a loop
    ("ragged-dot-none", ds.Attr("hvd:moe.experts", ds.OTHER)),
    ("jit(s)/transpose(jvp(M))/hvd:moe.combine/while/body/jit(c)/"
     "ragged-dot-none", ds.Attr("hvd:moe.experts", ds.BACKWARD)),
    ("jit(s)/jvp(M)/hvd:moe.route/ragged-dotty", ds.Attr("hvd:moe.route",
                                                         ds.FORWARD)),
])
def test_attr_of_a_path(op_name, want):
    assert ds.attr_of(op_name) == want


def test_a_compiler_named_kernel_takes_the_phase_of_what_it_reads():
    """``ragged-dot-none.N`` has no path: the scope is its emitter's, the
    phase ``backward`` where anything it reads is, through instructions
    without a path of their own, else ``forward``."""
    meta = 'metadata={op_name="jit(s)/%s/hvd:moe.%s/mul"}'
    text = "\n".join([
        "HloModule m", "", "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %rows.1 = f32[8]{0} multiply(%p, %p), "
        + meta % ("jvp(M)", "dispatch"),
        "  %dout.2 = f32[8]{0} multiply(%p, %p), "
        + meta % ("transpose(jvp(M))", "experts"),
        "  %copy-start.3 = (f32[8]{0}, u32[]) copy-start(%dout.2)",
        "  %copy-done.3 = f32[8]{0} copy-done(%copy-start.3)",
        "  %ragged-dot-none.4 = f32[8]{0} custom-call(%rows.1, %p), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        "  %ragged-dot-none.5 = f32[8]{0} custom-call(%rows.1, "
        '%copy-done.3), custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        "  %ragged-dot-none.6 = f32[8]{0} custom-call(%p, %p), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        "  ROOT %copy.7 = f32[8]{0} copy(%ragged-dot-none.5)", "}", ""])
    table = ds.parse_module(text)
    experts = "hvd:moe.experts"
    assert table["ragged-dot-none.4"] == ds.Attr(experts, ds.FORWARD)
    assert table["ragged-dot-none.5"] == ds.Attr(experts, ds.BACKWARD)
    assert table["ragged-dot-none.6"] == ds.Attr(experts, ds.OTHER)
    assert table["copy-done.3"] == table["copy.7"] == ds.Attr()
    # a program without a scope anywhere names nothing, these neither
    unscoped = ds.parse_module(text.replace("hvd:", "xyz:"))
    assert {attr.scope for attr in unscoped.values()} == {ds.UNATTRIBUTED}
    assert unscoped["dout.2"].phase == ds.BACKWARD


# -------------------------------------------------------------- the sums

def test_own_time_by_scope_phase_and_mixed_to_the_nanosecond(report):
    by_scope = {scope: ns(report.seconds(scope=scope) * 2) for scope in (
        "hvd:model.stream", "hvd:model.mlp", "hvd:moe.combine",
        "hvd:moe.dispatch", "hvd:moe.experts", "hvd:optimizer.update",
        "hvd:exchange.rounds", ds.UNATTRIBUTED)}
    assert by_scope == {            # both chips together
        "hvd:model.stream": 2000, "hvd:model.mlp": 4000,
        "hvd:moe.combine": 1000, "hvd:moe.dispatch": 1000,
        "hvd:moe.experts": 6000, "hvd:optimizer.update": 3000,
        "hvd:exchange.rounds": 1600, ds.UNATTRIBUTED: 700}
    by_phase = {phase: ns(report.seconds(phase=phase) * 2)
                for phase in ds.PHASES}
    assert by_phase == {ds.FORWARD: 10000, ds.BACKWARD: 4000,
                        "optimizer": 3000, "exchange": 1600,
                        ds.OTHER: 700}
    assert ns(report.seconds(mixed=True) * 2) == 4000
    assert ns(report.seconds(scope=ds.ROUNDS, collective=False) * 2) == 1000
    assert ns(report.seconds(scope=("hvd:moe.combine",
                                    "hvd:moe.dispatch")) * 2) == 2000


def test_the_table_sums_to_self_seconds_total(path, report):
    chips = tr.load(path).chips
    want = sum(sum(tr.self_seconds(chip).values()) for chip in chips)
    assert ns(report.seconds() * 2) == ns(want) == 19300
    assert ns(sum(report.seconds(scope=row[0]) for row in report.table())
              * 2) == 19300
    busy, _ = tr.busy_and_window(tr.Trace(chips, []))
    assert ns(busy * 2) == 19300        # no gap inside an operation here


def test_largest_operations_of_a_kind(report):
    assert report.top(scope=ds.UNATTRIBUTED) == [
        ["copy.3", 0.0001], ["mystery.10", 0.0001]]
    assert report.top(mixed=True) == [["fusion.2", 0.001]]
    line = json.loads(ds.log_line(report)[len("device scopes: "):])
    assert line["rows"][0][0] == "hvd:moe.experts"
    assert line["programs [scoped instructions]"] == {
        make_scopes_xplane.STEP: 6, make_scopes_xplane.APPLY: 4}


def test_an_operation_outside_every_module_is_unattributed(path):
    chip = tr.load(path).chips[0]
    chip.modules = chip.modules[:1]
    rows = ds.own_seconds(chip, ds.programs(path))
    late = {key[0] for key in rows if key[4] in ("add.9", "mystery.10")}
    assert late == {ds.UNATTRIBUTED}


# ------------------------------------------------------------ the readers

def traced_run(tmp_path, monkeypatch, trace_bytes):
    folder = (tmp_path / ".bench_out" / "trace" / CELL / "plugins"
              / "profile" / "2026_10_05")
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(trace_bytes)
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    return types.SimpleNamespace(
        cell={"name": CELL}, traced_steps=2,
        trace=tr.load(str(folder / "host.xplane.pb")))


def read(run, name):
    return importlib.import_module(f"benchmark.layers.{name}").read(run)


def test_readers_take_their_numbers(tmp_path, monkeypatch, capsys):
    run = traced_run(tmp_path, monkeypatch, make_scopes_xplane.build())
    for name, want in READERS.items():
        got = read(run, name)
        if want is None:
            assert got is None, name
        elif name.endswith("_ms"):
            assert got == pytest.approx(want * 1e-6, rel=1e-9), name
        else:
            assert got == pytest.approx(want, rel=1e-9), name
    assert capsys.readouterr().out.count("[bench] device scopes: ") == 1


def test_readers_return_none_on_a_program_without_scopes(
        tmp_path, monkeypatch, capsys):
    """The parent of the PR that added the scopes: the trace holds its
    HLO, nothing in it is named, and every reader says ``None``."""
    unscoped = make_scopes_xplane.build().replace(b"hvd:", b"xyz:")
    run = traced_run(tmp_path, monkeypatch, unscoped)
    assert ds.hlo_protos(ps.trace_file(CELL))
    assert [read(run, name) for name in READERS] == [None] * len(READERS)
    assert "device scopes" not in capsys.readouterr().out


def test_readers_return_none_without_the_plane_or_the_trace(
        tmp_path, monkeypatch):
    with open(make_xplane.PATH, "rb") as f:
        run = traced_run(tmp_path, monkeypatch, f.read())
    assert [read(run, name) for name in READERS] == [None] * len(READERS)
    run = types.SimpleNamespace(cell={"name": "no-such-cell"},
                                traced_steps=2, trace=None)
    assert [read(run, name) for name in READERS] == [None] * len(READERS)


def test_every_new_metric_has_its_entry_and_its_reader(spec):
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["moves"] == "step_ms"
        assert entries[name]["better"] == "lower"
    everywhere = [name for name in READERS
                  if "workloads" not in entries[name]]
    assert everywhere == ["fwd_ms", "bwd_ms", "optimizer_ms",
                          "scope_unattributed_share", "scope_mixed_share"]
