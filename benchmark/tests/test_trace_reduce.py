"""The trace reduction against ``data/synthetic.xplane.pb``, whose
numbers are known by construction (``make_xplane.py``).

Chip 0 (ns): fusion 1000-3000, all-reduce-start 3000-3100, fusion
3100-5000, all-reduce-done 5000-6000, idle 6000-7000, a ``while``
7000-10000 holding fusion / all-reduce / fusion of 1000 each, idle
10000-10500, copy 10500-11000; its line of asynchronous operations has
the all-reduce from 3000 to 6000. Chip 1: the same 200 ns later, without
the copy and without that line. Two launches on each chip. Operations
are named by their HLO text, as the TPU v5e's trace names them."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.tests import make_xplane


@pytest.fixture(scope="module")
def trace():
    return tr.load(make_xplane.PATH)


def test_fixture_is_what_the_builder_writes():
    with open(make_xplane.PATH, "rb") as f:
        assert f.read() == make_xplane.build()


def test_planes(trace):
    assert [chip.index for chip in trace.chips] == [0, 1]
    assert [len(chip.ops) for chip in trace.chips] == [9, 8]
    assert [len(chip.in_flight) for chip in trace.chips] == [1, 0]
    # the host's own event is not a benchmark span
    assert {s.name for s in trace.spans} == {
        "bench:window", "bench:step_call", "bench:window_sync"}


def test_busy_window_idle(trace):
    busy, window = tr.busy_and_window(trace)
    assert busy == pytest.approx((8500 + 8000) / 2 * 1e-9)
    assert window == pytest.approx((10000 + 9000) / 2 * 1e-9)
    assert tr.idle_share(trace) == pytest.approx(1 - 8250 / 9500)


def test_launches(trace):
    assert tr.launches(trace) == 2


def test_collectives(trace):
    # in flight: 3000-6000 (start to done) and 8000-9000 (synchronous).
    # exposed: 3000-3100 and 5000-6000 (fusion.2 hides 3100-5000), and
    # all of 8000-9000: the enclosing while is a container, not compute
    total, exposed = tr.collectives(trace)
    assert total == pytest.approx(4000e-9)
    assert exposed == pytest.approx(2100e-9)


def test_async_pairs_match_by_suffix_then_fifo():
    ops = [tr.Event("all-gather-start.7", 0, 10),
           tr.Event("all-gather-start.9", 20, 30),
           tr.Event("all-gather-done.9", 40, 50),
           tr.Event("all-gather-done", 60, 70),       # no suffix: FIFO
           tr.Event("reduce-scatter.3", 80, 90)]
    got = sorted(tr.collective_intervals(tr.Chip(0, ops, [])))
    assert got == [(0, 70), (20, 50), (80, 90)]


def test_parse_op():
    assert tr.parse_op(make_xplane.CONV) == (
        "fusion.1", "fusion", "fusion.1 = bf16[256,56,56,64] fusion")
    assert tr.parse_op(make_xplane.BN) == (
        "convert_reduce_fusion.2", "fusion",
        "convert_reduce_fusion.2 = (f32[64], bf16[256,56,56,64]) fusion")
    assert tr.parse_op(make_xplane.AR_START)[:2] == (
        "all-reduce-start.1", "all-reduce-start")
    assert tr.parse_op(make_xplane.WHILE)[:2] == ("while.1", "while")
    assert tr.parse_op("fusion.3") == ("fusion.3", "fusion", "fusion.3")


def test_self_time_and_names(trace):
    ops = dict(tr.top_ops(trace))
    # both executions of fusion.1, under its text less layouts, operands
    conv = "fusion.1 = bf16[256,56,56,64] fusion"
    assert ops[conv] == pytest.approx(3000e-9)
    assert list(ops)[0] == conv
    # all of the while's time is its children's
    assert ops["while.1 = (s32[], f32[64]) while"] == 0.0
    assert ops["copy.1 = f32[64] copy"] == pytest.approx(250e-9)  # 1 of 2


def test_idle_gaps_named_by_host_span(trace):
    # chip 0's gaps start at 6000 (inside the first step call) and at
    # 10000 (inside the window's sync)
    assert tr.idle_gaps(trace) == [["step_call", pytest.approx(1000e-9)],
                                   ["window_sync", pytest.approx(500e-9)]]


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)]) == [
        (1, 4), (5, 8)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) == [
        (0, 2), (3, 8), (22, 29)]


def test_no_device_plane_reads_as_nothing(tmp_path):
    path = os.path.join(tmp_path, "host_only.xplane.pb")
    with open(path, "wb") as f:
        f.write(make_xplane.plane("/host:CPU", [("python", [
            ("bench:window", 0, 10, {})])]))
    trace = tr.load(path)
    assert trace.chips == [] and len(trace.spans) == 1
    assert tr.idle_share(trace) is None and tr.idle_gaps(trace) == []
