"""The held-experts layer alone, on the chip (``parallel/moe.py``).

    chiprun -- python tools/moe_probe.py [--tokens 8192] [--agreement N]

Phase 1, the grouped matrix products: one SwiGLU expert layer's three
products forward + backward over the buffer ``moe_held_experts`` hands
them (``tokens * top_k`` rows of which ``tokens * top_k * held / routed``
are routed here, sorted by expert), through ``jax.lax.ragged_dot`` (what
``grouped_matmul`` runs) and through the Pallas ``megablox.gmm`` at
several tilings (what it was measured against); then the whole
layer (router, top-k, sort, gathers, products, combine) as the model
runs it. Milliseconds are host clock around ``--iters`` chained calls
ending in one ``block_until_ready``; every implementation's result is
compared with ``ragged_dot``'s first.

Phase 2 (``--agreement N``): the benchmark's ``lfm2-24b-a2b`` model at
its full size on N seeds: the share of (token, pick) pairs on which the
program and the float32 reference pick the same expert, at the seed's
initial parameters and after ``--steps`` Adam steps; with ``--errors``
also the whole gradient's relative error against the reference, as
configured and with every dense projection's result rounded through
float8 (what ``GRAD_REL_TOL`` has to refuse).

One JSON line per row on stdout, all of them in
``chiprun_out/moe_probe.json``. Needs a TPU: a time from anything else is
not a kernel time (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

ROWS = []


def emit(**row):
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def timed(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e3 / iters


def products(args):
    d, f, held, routed, k = args.d_model, args.d_ff, args.held, args.routed, \
        args.top_k
    pairs = args.tokens * k
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    rows = jax.random.normal(keys[0], (pairs, d), jnp.bfloat16)
    w1, w3 = (jax.random.normal(key, (held, d, f), jnp.float32) / d ** 0.5
              for key in keys[1:3])
    w2 = jax.random.normal(keys[3], (held, f, d), jnp.float32) / f ** 0.5
    # a multinomial draw of the load, as uniform routing gives it
    picks = jax.random.randint(keys[4], (pairs,), 0, routed)
    sizes = jnp.sum(picks[:, None] == jnp.arange(held)[None], 0,
                    dtype=jnp.int32)
    here = (jnp.arange(pairs) < jnp.sum(sizes))[:, None]
    rows = jnp.where(here, rows, 0)
    emit(phase="load", rows_held=int(jnp.sum(sizes)), buffer=pairs,
         group_sizes=[int(s) for s in sizes])

    def layer(product):
        def loss(rows, w1, w3, w2):
            cast = lambda w: w.astype(rows.dtype)
            h = jax.nn.silu(product(rows, cast(w1))) * product(rows, cast(w3))
            out = jnp.where(here, product(h, cast(w2)), 0)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    def ragged(lhs, w):
        return jax.lax.ragged_dot(lhs, w, sizes)

    impls = {"ragged_dot": ragged}
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    for tiling in args.tilings:
        impls["megablox " + "x".join(map(str, tiling))] = (
            lambda lhs, w, t=tiling: gmm(lhs, w, sizes, lhs.dtype, t))

    want = None
    for name, product in impls.items():
        try:
            fn = layer(product)
            (_, out), grads = fn(rows, w1, w3, w2)
            got = [out] + [jnp.where(here, grads[0], 0)] + list(grads[1:])
            got = [g.astype(jnp.float32) for g in got]
            if want is None:
                want = got
            err = max(float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                      for g, w in zip(got, want))
            emit(phase="products", impl=name,
                 ms=timed(fn, (rows, w1, w3, w2), args.iters),
                 rel_err_vs_ragged_dot=err)
        except Exception as e:  # a tiling the compiler refuses is a row
            emit(phase="products", impl=name,
                 error=str(e).splitlines()[0][:300])


def whole_layer(args):
    """``HeldExpertsMLP`` forward + backward as the model runs it."""
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.models.operators import HeldExpertsMLP

    cfg = TransformerConfig(
        d_model=args.d_model, moe_routed=args.routed,
        moe_held=(0, args.held), moe_d_ff=args.d_ff, moe_top_k=args.top_k,
        dtype=jnp.bfloat16)
    layer = HeldExpertsMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                          (1, args.tokens, args.d_model), jnp.float32)
    variables = jax.jit(layer.init)(jax.random.PRNGKey(args.seed), x[:, :8])

    @jax.jit
    def step(params, x):
        def loss(params, x):
            y, state = layer.apply(
                {"params": params, "routing": variables["routing"]}, x,
                mutable=["routing"])
            return jnp.sum(y.astype(jnp.float32) ** 2), state
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)

    (_, state), _ = step(variables["params"], x)
    emit(phase="layer", ms=timed(step, (variables["params"], x), args.iters),
         rows_held=int(state["routing"]["rows_held"]),
         load_max=int(jnp.max(state["routing"]["expert_load"])),
         load_min=int(jnp.min(state["routing"]["expert_load"])))


def agreement(args):
    import optax

    from benchmark import run as bench_run
    from benchmark.models import lfm2

    _, cell, config = bench_run.load_cell("lfm2-traced-1chip")
    model = lfm2.make_model(config)
    tx = lfm2.optimizer(config)
    agree = jax.jit(lambda p, a, *b: lfm2.routing_agreement(
        model, config, p, a, b))

    @jax.jit
    def train(params, aux, opt, *batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: lfm2.loss(model, p, aux, batch), has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), aux, opt, loss

    def grads(loss_fn):
        return jax.jit(jax.grad(lambda p, a, *b: loss_fn(p, a, b)[0]))

    @jax.jit
    def rel_error(got, want):
        diff = sum(jnp.sum(jnp.square(g.astype(jnp.float32) - w))
                   for g, w in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)))
        return jnp.sqrt(diff / sum(jnp.sum(jnp.square(w))
                                   for w in jax.tree.leaves(want)))

    reference = grads(lambda p, a, b: lfm2.reference_loss(config, p, a, b))
    program = grads(lambda p, a, b: lfm2.loss(model, p, a, b))
    float8 = grads(lambda p, a, b: lfm2.loss_rounded_through(
        jnp.float8_e4m3fn, model, p, a, b))

    for seed in range(args.seed, args.seed + args.agreement):
        k_init, k_data = jax.random.split(jax.random.PRNGKey(seed))
        params, aux = jax.jit(lambda k: lfm2.init(model, config, k))(k_init)
        batch = lfm2.make_batch(config, k_data, 1, cell["seq_len"])
        row = {"phase": "agreement", "seed": seed,
               "at_init": float(agree(params, aux, *batch))}
        if args.errors:
            want = reference(params, aux, *batch)
            row["grad_rel_err"] = float(rel_error(
                program(params, aux, *batch), want))
            row["grad_rel_err_float8_activations"] = float(rel_error(
                float8(params, aux, *batch), want))
            del want
        opt = jax.jit(tx.init)(params)
        for _ in range(args.steps):
            params, aux, opt, loss = train(params, aux, opt, *batch)
        del opt
        row[f"after_{args.steps}_steps"] = float(agree(params, aux, *batch))
        row["loss"] = float(loss)
        emit(**row)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--d-model", type=int, default=2048)
    parser.add_argument("--d-ff", type=int, default=1536)
    parser.add_argument("--held", type=int, default=8)
    parser.add_argument("--routed", type=int, default=64)
    parser.add_argument("--top-k", type=int, default=4)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tilings", default="128x128x128,512x1024x1024,"
                        "512x512x1024,256x1024x1024,512x2048x512")
    parser.add_argument("--agreement", type=int, default=0,
                        help="seeds to measure routing agreement on")
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--errors", action="store_true",
                        help="with --agreement: the gradient's relative "
                        "error against the reference, as configured and "
                        "with activations rounded through float8")
    parser.add_argument("--skip-products", action="store_true")
    args = parser.parse_args()
    args.tilings = [tuple(int(n) for n in t.split("x"))
                    for t in args.tilings.split(",")]

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"moe_probe.py: needs a TPU, found {device.platform!r}")
    emit(phase="device", kind=device.device_kind, count=jax.device_count())
    if not args.skip_products:
        products(args)
        whole_layer(args)
    if args.agreement:
        agreement(args)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "moe_probe.json"), "w") as f:
        json.dump(ROWS, f, indent=1)


if __name__ == "__main__":
    main()
