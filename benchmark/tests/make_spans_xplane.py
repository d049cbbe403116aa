"""Writes ``data/spans.xplane.pb``: one chip with three idle gaps under
two host threads of nested ``bench:`` and ``hvd:`` spans, so that own
time, per-thread nesting, the ``flush`` join and the idle attribution of
``program_spans.py`` are known by construction (the numbers are in
``test_program_spans.py``). Encoded with ``make_xplane.plane``; stat
values are strings there, and the join is by equality.

    python benchmark/tests/make_spans_xplane.py     # rewrites the file
"""

from __future__ import annotations

import os

from benchmark.tests.make_xplane import plane

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "spans.xplane.pb")

# two steps on the calling thread (ns); a span's children follow it
CALLER = [
    ("bench:window", 0, 20000, {}),
    ("bench:step_call", 1000, 9000, {}),
    ("hvd:optimizer.sync", 2000, 6000, {}),
    ("hvd:collective.submit", 2100, 2400, {"tensors": "2"}),
    ("hvd:cycle.flush", 2400, 2600, {"trigger": "bucket", "flush": "7"}),
    ("hvd:cycle.wait_result", 2600, 5800, {"tensor": "q.0"}),
    ("hvd:optimizer.inner_update", 6000, 8500, {}),
    ("bench:step_call", 9000, 16000, {}),
    ("hvd:optimizer.sync", 10000, 13000, {}),
    ("hvd:cycle.flush", 10200, 10400, {"trigger": "bucket", "flush": "8"}),
    ("hvd:cycle.wait_result", 10400, 12800, {"tensor": "q.1"}),
    ("hvd:optimizer.inner_update", 13000, 15500, {}),
    ("bench:window_sync", 16000, 20000, {}),
    ("PjitFunction(apply)", 8600, 8900, {}),     # not a span of either
]
# the flush executor's thread
EXECUTOR = [
    ("hvd:cycle.execute", 2700, 5700, {"flush": "7", "entries": "1"}),
    ("hvd:plan.run", 3000, 5500, {"tensor": "grouped_allreduce"}),
    ("hvd:plan.fuse", 3100, 4000, {}),
    ("hvd:plan.wire", 4000, 5400, {}),
    ("hvd:cycle.execute", 10500, 12700, {"flush": "8", "entries": "1"}),
    ("hvd:plan.run", 10600, 12600, {"tensor": "grouped_allreduce"}),
]
# a thread with nothing of ours
OTHER = [("ThreadpoolListener::Record", 500, 700, {})]
# busy 1500-3200, 3500-7000, 11000-17000, 18000-19000: idle 3200-3500
# (caller waits for the executor, which fuses), 7000-11000 (began in the
# inner update), 17000-18000 (the window's sync)
OPS = [("fusion.1", 1500, 3200, {}), ("fusion.2", 3500, 7000, {}),
       ("fusion.3", 11000, 17000, {}), ("copy.4", 18000, 19000, {})]


def build():
    modules = [("jit_step(1)", 1500, 7000, {}),
               ("jit_step(1)", 11000, 19000, {})]
    return (plane("/device:TPU:0", [("XLA Modules", modules),
                                    ("XLA Ops", OPS)], 1)
            + plane("/host:CPU", [("python3", CALLER), ("python3", EXECUTOR),
                                  ("tf_pjrt", OTHER)], 2))


if __name__ == "__main__":
    with open(PATH, "wb") as f:
        f.write(build())
    print(f"wrote {PATH}")
