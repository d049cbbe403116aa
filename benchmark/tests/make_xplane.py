"""Writes ``data/synthetic.xplane.pb``: a two-chip trace whose idle share,
collective time, exposed collective time, launch count and idle-gap
attribution are known by construction (the numbers are in
``test_trace_reduce.py``). The protobuf wire format of tsl's ``XSpace`` is
encoded by hand so that neither tensorflow nor a proto compiler is
needed; the plane, line and stat names are those a TPU v5e trace from
jax 0.9 carries (PERF.md, PR 22).

    python benchmark/tests/make_xplane.py     # rewrites the file
"""

from __future__ import annotations

import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "synthetic.xplane.pb")


def _varint(n):
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _bytes(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def plane(name, lines, plane_id=0):
    """``lines``: ``[(line name, [(event name, start_ns, end_ns,
    {stat: str}), ...]), ...]``."""
    event_ids, stat_ids = {}, {}
    body = _int(1, plane_id) + _bytes(2, name)
    for line_id, (line_name, events) in enumerate(lines, 1):
        line = _int(1, line_id) + _bytes(2, line_name) + _int(3, 0)
        for event_name, start, end, stats in events:
            mid = event_ids.setdefault(event_name, len(event_ids) + 1)
            event = (_int(1, mid) + _int(2, start * 1000)
                     + _int(3, (end - start) * 1000))
            for key, value in stats.items():
                sid = stat_ids.setdefault(key, len(stat_ids) + 1)
                event += _bytes(4, _int(1, sid) + _bytes(5, value))
            line += _bytes(4, event)
        body += _bytes(3, line)
    for event_name, mid in event_ids.items():   # map<int64, XEventMetadata>
        body += _bytes(4, _int(1, mid)
                       + _bytes(2, _int(1, mid) + _bytes(2, event_name)))
    for key, sid in stat_ids.items():           # map<int64, XStatMetadata>
        body += _bytes(5, _int(1, sid)
                       + _bytes(2, _int(1, sid) + _bytes(2, key)))
    return _bytes(1, body)                      # XSpace.planes


# operations as the chip's trace names them: by their HLO text
CONV = ("%fusion.1 = bf16[256,56,56,64]{3,0,2,1:T(8,128)(2,1)} "
        "fusion(bf16[256,56,56,64]{3,0,2,1:T(8,128)(2,1)} %p.1, "
        "f32[64]{0:T(128)S(1)} %p.2), kind=kOutput")
BN = ("%convert_reduce_fusion.2 = (f32[64]{0:T(128)S(1)}, "
      "bf16[256,56,56,64]{3,0,2,1:T(8,128)(2,1)}) fusion(f32[64]{0:T(128)} "
      "%p.3), kind=kLoop")
AR_START = ("%all-reduce-start.1 = f32[1024]{0:T(1024)} "
            "all-reduce-start(f32[1024]{0:T(1024)} %p.4), replica_groups={}")
AR_DONE = ("%all-reduce-done.1 = f32[1024]{0:T(1024)} "
           "all-reduce-done(f32[1024]{0:T(1024)} %all-reduce-start.1)")
AR_SYNC = ("%all-reduce.2 = f32[64]{0:T(128)} all-reduce(f32[64]{0:T(128)} "
           "%p.5), to_apply=%add")
WHILE = "%while.1 = (s32[], f32[64]{0:T(128)}) while((s32[], f32[64]) %t)"
COPY = "%copy.1 = f32[64]{0:T(128)} copy(f32[64]{0:T(128)S(1)} %p.6)"


def chip_ops(shift, with_copy):
    ops = [
        (CONV, 1000, 3000),
        (AR_START, 3000, 3100),
        (BN, 3100, 5000),
        (AR_DONE, 5000, 6000),
        # idle 6000-7000
        (WHILE, 7000, 10000),
        ("fusion.3", 7000, 8000),       # a bare name parses too
        (AR_SYNC, 8000, 9000),
        (CONV, 9000, 10000),
    ]
    if with_copy:                       # idle 10000-10500
        ops.append((COPY, 10500, 11000))
    stats = {"device_offset_ps": "0"}
    return [(n, s + shift, e + shift, stats) for n, s, e in ops]


def build():
    modules = [("jit_train_step(10168504766699187510)", 1000, 6000, {}),
               ("jit_train_step(10168504766699187510)", 7000, 11000, {})]
    host = [("bench:window", 0, 12000, {}),
            ("bench:step_call", 900, 6500, {}),
            ("bench:step_call", 6500, 9000, {}),
            ("bench:window_sync", 9000, 12000, {}),
            ("PjitFunction(train_step)", 950, 6400, {})]
    return (plane("#Chip0 Host Interface", [], 9)
            + plane("/device:TPU:0", [
                ("Steps", [("0", 1000, 11000, {})]),
                ("XLA Modules", modules),
                ("XLA Ops", chip_ops(0, True)),
                ("Async XLA Ops", [(AR_START, 3000, 6000, {})])], 1)
            # no line of asynchronous operations: start and done pair up
            + plane("/device:TPU:1", [("XLA Modules", modules),
                                      ("XLA Ops", chip_ops(200, False))], 2)
            + plane("/host:CPU", [("python3", host)], 3))


if __name__ == "__main__":
    os.makedirs(os.path.dirname(PATH), exist_ok=True)
    with open(PATH, "wb") as f:
        f.write(build())
    print(f"wrote {PATH}")
