"""Attention's q / k / v projection alone, on the chip: its weight
gradient followed by Adam, the part of a training step whose time
follows how the product is stated to the compiler (PERF.md section 6,
PR 36).

    chiprun -- python tools/projection_probe.py [--cells sdar,lfm2,gpt2]

One ``kernel`` leaf ``[d_model, heads, head_dim]`` in float32 with
``optax.adam``'s two moments, rows ``x`` ``[rows, d_model]`` and the
result's cotangent ``[rows, heads, head_dim]`` in bfloat16. A step takes
the weight gradient and applies Adam to the donated leaf and moments,
in three statements of the same product:

* ``dense_general``: the transpose of ``models/transformer.py``
  ``project_heads`` with ``flat=False``, the contraction over the
  three-dimensional leaf that ``nn.DenseGeneral(features=(heads,
  head_dim))`` hands the compiler;
* ``flat``: the same with ``flat=True``, a two-dimensional product over
  a flat view of the leaf;
* ``o_orientation``: ``einsum("shk,sd->hkd")``, the product the o
  projection's weight gradient is, transposed to the leaf.

For every cell's shape, the q projection and the k / v projection
(fewer heads): ``device_ms``, the device's busy time a step over
``--iters`` chained steps under ``jax.profiler`` (the union of the
operations' intervals, by ``benchmark/trace_reduce.py``), and the
product's TFLOP/s over it (Adam's passes are in the time and not in the
operations, so the number understates the product); ``host_ms``, the
host clock around the same steps ending in one ``block_until_ready``,
which one call's dispatch bounds from below at about a quarter of a
millisecond; and each statement's largest difference from the first's
gradient. ``--whole`` adds the forward product and the rows' gradient to
the step. Alone, the compiler lays out ``x`` and the cotangent as it
likes; in a training step their producers do, so a statement can read
better here than there (``dense_general`` at 32 heads: PERF.md).

One JSON line per row on stdout, all of them in
``chiprun_out/projection_probe.json``. Needs a TPU: a time from anything
else is not a kernel time. ``--tiny`` runs small shapes on any backend
for shapes and parity only, and reports no time.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import transformer

# (rows a step, d_model, query heads, key/value heads, head_dim)
CELLS = {"sdar": (8192, 2048, 32, 4, 128),
         "lfm2": (8192, 2048, 32, 8, 64),
         "gpt2": (4096, 1024, 16, 16, 64)}
TINY = {"sdar": (64, 48, 8, 1, 16),
        "lfm2": (64, 64, 8, 2, 8),
        "gpt2": (32, 32, 4, 4, 8)}


def dense_general(x, kernel):
    return transformer.project_heads(x, kernel, x.dtype, flat=False)


def flat(x, kernel):
    return transformer.project_heads(x, kernel, x.dtype, flat=True)


FORWARDS = {"dense_general": dense_general, "flat": flat}


def weight_gradients():
    """name -> ``(x, dy, kernel) -> d kernel`` in the operands' dtype."""
    def transposed(forward):
        def gradient(x, dy, kernel):
            _, pull = jax.vjp(lambda w: forward(x, w), kernel)
            return pull(dy)[0]
        return gradient

    def o_orientation(x, dy, kernel):
        return jnp.einsum("shk,sd->hkd", dy, x).transpose(2, 0, 1).astype(
            kernel.dtype)

    return {**{name: transposed(forward)
               for name, forward in FORWARDS.items()},
            "o_orientation": o_orientation}


def make_step(gradient, forward, tx):
    """One step over donated ``(kernel, opt_state)``; with ``forward`` the
    whole projection (result and the rows' gradient too)."""
    def step(x, dy, kernel, opt_state):
        extra = ()
        if forward is not None:
            out, pull = jax.vjp(lambda rows: forward(rows, kernel), x)
            extra = (out, pull(dy)[0])
        updates, opt_state = tx.update(gradient(x, dy, kernel), opt_state,
                                       kernel)
        return optax.apply_updates(kernel, updates), opt_state, extra
    return jax.jit(step, donate_argnums=(2, 3))


def timed(step, x, dy, kernel, opt_state, iters):
    """``(host_ms, device_ms)`` a step."""
    from benchmark import trace_reduce

    def window(kernel, opt_state):
        for _ in range(iters):
            kernel, opt_state, extra = step(x, dy, kernel, opt_state)
        jax.block_until_ready((kernel, extra))
        return kernel, opt_state

    state = window(kernel, opt_state)             # compiles, warms
    start = time.perf_counter()
    state = window(*state)
    host_ms = (time.perf_counter() - start) * 1e3 / iters
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            window(*state)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        busy, _ = trace_reduce.busy_and_window(trace_reduce.load(path))
    return host_ms, busy * 1e3 / iters


def probe(cell, which, shape, args, device):
    rows, d_model, heads, head_dim = shape
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    x = jax.random.normal(keys[0], (rows, d_model), jnp.bfloat16)
    dy = jax.random.normal(keys[1], (rows, heads, head_dim), jnp.bfloat16)
    kernel = jax.random.normal(keys[2], (d_model, heads, head_dim),
                               jnp.float32) / d_model ** 0.5
    tx = optax.adam(1e-3)
    want, out = None, []
    for name, gradient in weight_gradients().items():
        got = jax.jit(gradient)(x, dy, kernel)
        assert got.shape == kernel.shape and got.dtype == kernel.dtype, (
            name, got.shape, got.dtype)
        want = got if want is None else want
        row = {"cell": cell, "projection": which, "rows": rows,
               "d_model": d_model, "heads": heads, "head_dim": head_dim,
               "statement": name,
               "selected": ("flat" if transformer.flat_projection_selected(
                   heads, head_dim) else "dense_general") == name,
               "max_abs_diff_vs_dense_general": float(
                   jnp.max(jnp.abs(got - want))),
               "gradient_max_abs": float(jnp.max(jnp.abs(want)))}
        if not args.tiny:
            flops = 2.0 * rows * d_model * heads * head_dim
            host_ms, device_ms = timed(make_step(gradient, None, tx), x, dy,
                                       kernel + 0, tx.init(kernel), args.iters)
            row.update(device_ms=device_ms, host_ms=host_ms,
                       product_tflops=flops / device_ms / 1e9,
                       device_kind=device.device_kind)
            if args.whole and name in FORWARDS:
                row["whole_projection_device_ms"] = timed(
                    make_step(gradient, FORWARDS[name], tx), x, dy,
                    kernel + 0, tx.init(kernel), args.iters)[1]
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", default=",".join(CELLS))
    parser.add_argument("--iters", type=int, default=50)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--whole", action="store_true",
                        help="also time forward + both gradients + Adam")
    parser.add_argument("--tiny", action="store_true",
                        help="small shapes, any backend: parity, no times")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit("projection_probe: needs a TPU (or --tiny), jax found "
                 f"{device.platform!r}")
    rows = []
    for cell in args.cells.split(","):
        seq, d_model, heads, kv_heads, head_dim = (TINY if args.tiny
                                                   else CELLS)[cell]
        for which, n in dict(q=heads, kv=kv_heads).items():
            if which == "kv" and kv_heads == heads:
                continue                 # the same shape as q
            rows += probe(cell, which, (seq, d_model, n, head_dim), args,
                          device)
    if not args.tiny:
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "projection_probe.json"), "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
