"""``program_spans.py`` against ``data/spans.xplane.pb``, whose numbers
are known by construction (``make_spans_xplane.py``).

Two steps. The calling thread (ns): ``bench:window`` 0-20000 holding two
``bench:step_call`` (1000-9000, 9000-16000) and ``bench:window_sync``
16000-20000; in each step ``hvd:optimizer.sync`` (2000-6000,
10000-13000: submit 2100-2400 in the first only, flush 2400-2600 /
10200-10400 numbered 7 / 8, wait 2600-5800 / 10400-12800) then
``hvd:optimizer.inner_update`` (6000-8500, 13000-15500). The executor's
thread: ``hvd:cycle.execute`` 2700-5700 (flush 7: ``plan.run`` 3000-5500
with fuse 3100-4000 and wire 4000-5400) and 10500-12700 (flush 8:
``plan.run`` 10600-12600). The chip is busy 1500-3200, 3500-7000,
11000-17000, 18000-19000."""

import importlib
import json
import os
import shutil
import types

import pytest

from benchmark import program_spans as ps
from benchmark import trace_reduce as tr
from benchmark.tests import make_spans_xplane, make_xplane

NS = 1e-9
CELL = "a-cell"
READERS = ["sync_host_ms", "inner_update_host_ms", "flush_wait_ms",
           "plan_run_host_ms", "idle_in_program_share",
           "cached_step_lookup_ms", "cached_step_build_s", "hvd_init_s",
           "broadcast_s"]


@pytest.fixture(scope="module")
def threads():
    return ps.load(make_spans_xplane.PATH)


@pytest.fixture(scope="module")
def report(threads):
    chip = tr.load(make_spans_xplane.PATH).chips[0]
    return ps.reduce(threads, chip, steps=2)


def traced_run(tmp_path, monkeypatch, trace_path, registry):
    """A ``run`` as ``run.py`` hands it to a reader, with its trace file
    where ``run.py`` would have written it, on a program whose
    ``hvd_span_seconds`` entry is ``registry``."""
    folder = tmp_path / ".bench_out" / "trace" / CELL / "plugins" / \
        "profile" / "2026_09_28"
    folder.mkdir(parents=True)
    shutil.copy(trace_path, folder / "host.xplane.pb")
    monkeypatch.setattr(ps, "ROOT", str(tmp_path))
    monkeypatch.setattr(ps, "registry", lambda: registry)
    return types.SimpleNamespace(cell={"name": CELL}, traced_steps=2,
                                 trace=tr.load(trace_path))


@pytest.fixture()
def run(tmp_path, monkeypatch):
    return traced_run(tmp_path, monkeypatch, make_spans_xplane.PATH, {
        "series": [
            {"labels": {"span": "init"}, "count": 1, "sum": 0.25},
            {"labels": {"span": "broadcast_parameters"}, "count": 1,
             "sum": 4.5},
            {"labels": {"span": "cached_step.build"}, "count": 2,
             "sum": 6.75}]})


def test_fixture_is_what_the_builder_writes():
    with open(make_spans_xplane.PATH, "rb") as f:
        assert f.read() == make_spans_xplane.build()


def test_threads_are_read_line_by_line(threads):
    # the third host line holds no span of either prefix
    assert set(threads) == {ps.CALLER, ps.EXECUTOR}
    assert len(threads[ps.CALLER]) == 13       # PjitFunction is not ours
    assert {s.name for s in threads[ps.EXECUTOR]} == {
        "hvd:cycle.execute", "hvd:plan.run", "hvd:plan.fuse",
        "hvd:plan.wire"}
    assert threads[ps.CALLER][0].name == "bench:window"
    assert threads[ps.CALLER][4].fields == {"trigger": "bucket",
                                            "flush": "7"}


@pytest.mark.parametrize("thread, name, calls, wall, own", [
    (ps.CALLER, "hvd:optimizer.sync", 2, 7000, 700),
    (ps.CALLER, "hvd:optimizer.inner_update", 2, 5000, 5000),
    (ps.CALLER, "hvd:collective.submit", 1, 300, 300),
    (ps.CALLER, "hvd:cycle.flush", 2, 400, 400),
    (ps.CALLER, "hvd:cycle.wait_result", 2, 5600, 5600),
    (ps.CALLER, "bench:step_call", 2, 15000, 3000),
    (ps.CALLER, "bench:window", 1, 20000, 1000),
    (ps.EXECUTOR, "hvd:cycle.execute", 2, 5200, 700),
    (ps.EXECUTOR, "hvd:plan.run", 2, 4500, 2200),
    (ps.EXECUTOR, "hvd:plan.fuse", 1, 900, 900)])
def test_calls_wall_and_own_time(report, thread, name, calls, wall, own):
    """Own time is the duration less the child spans on the same thread:
    the executor's work is not taken off the caller's wait."""
    got = report.threads[thread][name]
    assert got[0] == calls
    assert got[1] == pytest.approx(wall * NS)
    assert got[2] == pytest.approx(own * NS)
    assert report.total(name) == pytest.approx((calls, wall * NS, own * NS))
    assert report.total(name, "no-such-thread") is None


def test_open_spans_at_a_time(threads):
    outer, inner = ps.open_at(threads[ps.CALLER], [2500, 8700, 30000])[:2], \
        ps.open_at(threads[ps.EXECUTOR], [3200, 6000])
    assert [s.name for s in outer[0]] == [
        "bench:window", "bench:step_call", "hvd:optimizer.sync",
        "hvd:cycle.flush"]
    assert [s.name for s in outer[1]] == ["bench:window", "bench:step_call"]
    assert [s.name for s in inner[0]] == [
        "hvd:cycle.execute", "hvd:plan.run", "hvd:plan.fuse"]
    assert inner[1] == ()
    assert ps.open_at(threads[ps.CALLER], [30000]) == [()]


def test_flush_join_across_threads(threads, report):
    pairs = ps.flush_pairs(threads)
    assert [(n, d.start, e.start) for n, d, e in pairs] == [
        ("7", 2400, 2700), ("8", 10200, 10500)]
    assert all(d in threads[ps.CALLER] and e in threads[ps.EXECUTOR]
               for _, d, e in pairs)
    assert report.flush_lag_s == pytest.approx([300 * NS, 300 * NS])


def test_idle_by_the_span_the_gap_began_in(report):
    """Gaps 3200-3500, 7000-11000, 17000-18000: by the calling thread's
    innermost span of either prefix, with the executor's beside it."""
    assert report.idle_rows == {
        ("hvd:cycle.wait_result", "hvd:plan.fuse"): pytest.approx(300 * NS),
        ("hvd:optimizer.inner_update", None): pytest.approx(4000 * NS),
        ("bench:window_sync", None): pytest.approx(1000 * NS)}
    assert report.idle_s == pytest.approx(5300 * NS)
    assert report.idle_in_program_s == pytest.approx(4300 * NS)
    rows = {(row[0], row[1]): row for row in report.table()}
    assert len(rows) == 12
    # per step, in ms: the executor's row shows what began beside it
    assert rows["hvd:plan.fuse", ps.EXECUTOR][2:] == [
        0.5, round(900e-6 / 2, 3), round(900e-6 / 2, 3),
        round(300e-6 / 2, 3)]
    assert rows["hvd:optimizer.inner_update", ps.CALLER][5] == 0.002


@pytest.mark.parametrize("metric, value", [
    ("sync_host_ms", 7000e-6 / 2),
    ("inner_update_host_ms", 5000e-6 / 2),
    ("flush_wait_ms", 5600e-6 / 2),
    ("plan_run_host_ms", 4500e-6 / 2),
    ("idle_in_program_share", 100 * 4300 / 5300),
    ("cached_step_build_s", 6.75), ("hvd_init_s", 0.25),
    ("broadcast_s", 4.5)])
def test_readers(run, capsys, metric, value):
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(run) == pytest.approx(value)
    assert reader.read(run) == pytest.approx(value)
    # the table is logged once, as one [bench] line that parses
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[bench] program spans: ")]
    if "ms" in metric or "share" in metric:
        table = json.loads(lines[0].split(": ", 1)[1])
        assert len(lines) == 1 and table["steps"] == 2
        assert len(table["rows"]) == 12
        assert table["flush -> execute lag ms [joined, median, max]"][0] == 2


def test_a_span_that_never_ran_reads_as_nothing(run):
    # this trace holds no cached step
    reader = importlib.import_module("benchmark.layers.cached_step_lookup_ms")
    assert reader.read(run) is None
    assert ps.wall_ms_per_call(run, "hvd:cycle.flush") == pytest.approx(
        400e-6 / 2)


@pytest.mark.parametrize("metric", READERS)
def test_readers_return_nothing_from_a_program_without_spans(
        metric, tmp_path, monkeypatch):
    """The parent of the PR that added the spans: a trace with the
    benchmark's spans only, and no ``hvd_span_seconds`` in the registry.
    Nothing raises; every metric is left out."""
    run = traced_run(tmp_path, monkeypatch, make_xplane.PATH, None)
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(run) is None
    # nor from a run that left no trace file at all
    assert reader.read(types.SimpleNamespace(
        cell={"name": "no-such-cell"}, traced_steps=2, trace=None)) is None


def test_no_span_in_the_window_is_a_share_of_zero(tmp_path, monkeypatch):
    """A compiled cell on a program that has the seam: no ``hvd:`` span
    opens in the window, so none of the idle began inside one."""
    run = traced_run(tmp_path, monkeypatch, make_xplane.PATH,
                     {"series": []})
    assert ps.idle_in_program_share(run) == 0.0
    assert ps.wall_ms_per_step(run, "hvd:optimizer.sync") is None


def test_setup_totals_come_from_the_programs_registry():
    import horovod_tpu as hvd
    from horovod_tpu import timeline

    with timeline.spans()["init"]():
        pass
    calls, seconds = ps.setup_totals()["init"]
    assert calls >= 1 and seconds > 0
    assert ps.SPAN_SERIES in hvd.metrics_dump()
