#!/usr/bin/env python3
"""Does an exchange written into a traced step overlap its compute?

A chain of bf16 matmuls of the GPT-2 step's shapes (4096 tokens a chip,
width 1024, inner 4096) and, after every few of them, ~50 MB of float32
"gradients" (three leaves made by matmuls of that layer) summed over the
host's chips, then consumed by an SGD-like update; a head-sized leaf
(1024 x 50257) comes first and an embedding-sized one (50257 x 1024)
last, as in the backward pass. The sum is emitted one of five ways:

    none            no exchange (the compute alone)
    psum            ``lax.psum`` a leaf (the traced sync before PR 31)
    scatter_gather  ``lax.psum_scatter`` + ``lax.all_gather`` a leaf
    permute_ring    ``ops/traced_exchange.py``'s rounds of ``lax.ppermute``
                    over the neighbour ring of the devices' coordinates
    permute_rank    the same rounds over the ring in rank order

    chiprun --chips 4 -- python tools/exchange_probe.py        # times
    JAX_PLATFORMS=cpu python tools/exchange_probe.py --aot     # no chip

On the chip each emission runs ``--steps`` chained steps on the host's
clock, then the same under ``jax.profiler``; ``benchmark/trace_reduce.py``
gives the time an exchange is in flight and the part of it no other
operation covers. ``--aot`` compiles each emission for a described
``v5e:2x2`` and reports what the scheduled program holds: which
collectives, how many asynchronous, how many of those have a matmul
between start and done. That is a count, never a speed. The script
refuses to time anything off a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EMISSIONS = ("none", "psum", "scatter_gather", "permute_ring",
             "permute_rank")
TOKENS, WIDTH, INNER, VOCAB = 4096, 1024, 4096, 50257


def build(emission, axis, ring, layers, reps, device):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.ops import traced_exchange

    k = len(ring)

    def exchange(leaves):
        if emission == "none":
            return leaves
        layouts = [traced_exchange.device_layout(device, g.dtype, g.shape)
                   for g in leaves]
        splits = [traced_exchange.split_dim(g.shape, k, layout)
                  for g, layout in zip(leaves, layouts)]
        # a leaf the rounds cannot chunk (the two vocabulary-sized ones)
        # is a psum in every emission, as in the library
        out = [lax.psum(g, axis) if emission == "psum" or split is None
               else None for g, split in zip(leaves, splits)]
        todo = [i for i, done in enumerate(out) if done is None]
        if emission == "scatter_gather":
            for i in todo:
                dim = splits[i][0]
                part = lax.psum_scatter(leaves[i], axis,
                                        scatter_dimension=dim, tiled=True)
                out[i] = lax.all_gather(part, axis, axis=dim, tiled=True)
        elif todo:
            order = ring if emission == "permute_ring" else tuple(range(k))
            for i, r in zip(todo, traced_exchange.allreduce_rounds(
                    [leaves[i] for i in todo], axis, order,
                    layouts=[layouts[i] for i in todo])):
                out[i] = r
        return out

    def grad_like(a, b):            # (tokens, m), (tokens, n) -> f32 (m, n)
        return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def step(params, x, y):
        head, embed, blocks = params
        h = x
        new_head = head - 1e-3 * exchange([grad_like(h, y)])[0]
        new_blocks = []
        for w1, w2, p in blocks:
            for _ in range(reps):
                mid = jax.nn.gelu(jnp.dot(h, w1))
                h = jnp.dot(mid, w2)
            raw = [grad_like(h, mid), grad_like(mid, h), grad_like(h, mid)]
            # the next layer waits for this layer's gradients, as the
            # backward pass produces them layer by layer
            h = h + sum(g[0, 0] for g in raw).astype(h.dtype) * 0
            grads = exchange(raw)
            new_blocks.append((w1, w2, [q - 1e-3 * g
                                        for q, g in zip(p, grads)]))
        new_embed = embed - 1e-3 * exchange([grad_like(y, h)])[0]
        return (new_head, new_embed, new_blocks), h

    def shapes():
        f32, bf16 = jnp.float32, jnp.bfloat16
        block = (((WIDTH, INNER), bf16), ((INNER, WIDTH), bf16),
                 [((WIDTH, INNER), f32), ((INNER, WIDTH), f32),
                  ((WIDTH, INNER), f32)])
        return ((((WIDTH, VOCAB), f32), ((VOCAB, WIDTH), f32),
                 [block] * layers),
                ((TOKENS, WIDTH), bf16), ((TOKENS, VOCAB), bf16))

    return step, shapes


def _is_shape(x):
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple)
            and all(isinstance(n, int) for n in x[0]))


def jitted(step, mesh, axis):
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(axis)), check_vma=False), donate_argnums=(0,))


_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z\-]+)\(")


def scheduled_summary(text):
    """Counts from the scheduled entry computation of a compiled module:
    collectives by opcode, and for each asynchronous pair whether a
    matmul-bearing operation sits between its start and its done."""
    entry = text[text.index("ENTRY"):]
    counts, open_at, in_flight, most = {}, {}, 0, 0
    overlapped = pairs = 0
    matmuls = 0
    for line in entry.splitlines():
        m = _OP.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        if opcode == "fusion" and "convolution" in line or \
                opcode == "convolution" or "kind=kOutput" in line:
            matmuls += 1
        if not re.match(r"(all-reduce|reduce-scatter|all-gather"
                        r"|collective-permute)(-start|-done)?$", opcode):
            continue
        counts[opcode] = counts.get(opcode, 0) + 1
        if opcode.endswith("-start"):
            open_at[name] = matmuls
            in_flight += 1
            most = max(most, in_flight)
        elif opcode.endswith("-done"):
            started = re.search(r"\(%?([\w.\-]+)",
                                line[line.index(" " + opcode + "("):])
            begun = open_at.pop(started.group(1), None) if started else None
            in_flight -= 1
            pairs += 1
            overlapped += bool(begun is not None and matmuls > begun)
    return {"ops": counts, "async_pairs": pairs,
            "pairs_with_a_matmul_inside": overlapped,
            "most_in_flight": most}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--emissions", default=",".join(EMISSIONS))
    parser.add_argument("--layers", type=int, default=12)
    parser.add_argument("--reps", type=int, default=3,
                        help="matmul pairs a layer before its exchange")
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--aot", action="store_true")
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "exchange_probe"))
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import traced_exchange

    axis = "d"
    if args.aot:
        from jax.experimental import topologies
        jax.config.update("jax_enable_compilation_cache", False)
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    else:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            sys.exit("exchange_probe: times come from a TPU; "
                     "use --aot for the counts a compile gives")
    devices = sorted(devices, key=lambda d: d.id)
    mesh = Mesh(np.array(devices), (axis,))
    k = len(devices)
    ring = traced_exchange.neighbour_ring(devices)
    print(f"[probe] {k} x {devices[0].device_kind}, coords "
          f"{[tuple(d.coords) for d in devices]}, neighbour ring {ring}",
          flush=True)
    if ring is None:
        sys.exit("exchange_probe: the devices form no neighbour ring")
    replicated, sharded = (NamedSharding(mesh, P()),
                           NamedSharding(mesh, P(axis)))
    os.makedirs(args.out, exist_ok=True)
    results = {}
    for emission in args.emissions.split(","):
        step, shapes = build(emission, axis, ring, args.layers, args.reps,
                             devices[0])
        param_shapes, x_shape, y_shape = shapes()
        fn = jitted(step, mesh, axis)

        def struct(sd, sharding, scale=1):
            shape, dtype = sd
            return jax.ShapeDtypeStruct((shape[0] * scale,) + shape[1:],
                                        dtype, sharding=sharding)

        abstract = (jax.tree.map(lambda sd: struct(sd, replicated),
                                 param_shapes, is_leaf=_is_shape),
                    struct(x_shape, sharded, k), struct(y_shape, sharded, k))
        began = time.perf_counter()
        compiled = fn.lower(*abstract).compile()
        compile_s = time.perf_counter() - began
        text = compiled.as_text()
        with open(os.path.join(args.out, f"{emission}.hlo.txt"), "w") as f:
            f.write(text)
        mem = compiled.memory_analysis()
        line = {"emission": emission, "compile_s": round(compile_s, 1),
                "temp_gib": round(mem.temp_size_in_bytes / 2 ** 30, 3),
                **scheduled_summary(text)}
        if not args.aot:
            line.update(run(compiled, abstract, args, emission))
        results[emission] = line
        print("[probe] " + json.dumps(line), flush=True)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(results, f, indent=1)


def run(compiled, abstract, args, emission):
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    def make(s):
        key = jax.random.PRNGKey(hash(s.shape) % (2 ** 31))
        return jax.jit(lambda: (jax.random.normal(key, s.shape, jnp.float32)
                                * 0.02).astype(s.dtype),
                       out_shardings=s.sharding)()

    params, x, y = jax.tree.map(make, abstract)

    def window(params):
        for _ in range(args.steps):
            params, h = compiled(params, x, y)
        jax.block_until_ready((params, h))
        return params

    params = window(params)                      # warm
    times = []
    for _ in range(3):
        began = time.perf_counter()
        params = window(params)
        times.append((time.perf_counter() - began) / args.steps * 1e3)
    trace_dir = os.path.join(args.out, f"trace_{emission}")
    jax.profiler.start_trace(trace_dir)
    params = window(params)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    trace = trace_reduce.load(path)
    in_flight, exposed = trace_reduce.collectives(trace)
    busy, span = trace_reduce.busy_and_window(trace)
    top = trace_reduce.top_ops(trace, limit=6)
    os.remove(path)                              # tens of MB a trace
    return {"step_ms": [round(t, 3) for t in times],
            "in_flight_ms": round(in_flight / args.steps * 1e3, 3),
            "exposed_ms": round(exposed / args.steps * 1e3, 3),
            "busy_ms": round(busy / args.steps * 1e3, 3),
            "window_ms": round(span / args.steps * 1e3, 3),
            "top_ops": [[n[:90], round(s / args.steps * 1e3, 3)]
                        for n, s in top]}


if __name__ == "__main__":
    main()
