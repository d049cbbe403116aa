"""The held-experts layer alone, on the chip (``parallel/moe.py``).

    chiprun -- python tools/moe_probe.py [--tokens 8192] [--agreement N]

Phase 1, the grouped matrix products: one SwiGLU expert layer's three
products forward + backward over the buffer ``moe_held_experts`` hands
them (``tokens * top_k`` rows of which ``tokens * top_k * held / routed``
are routed here, sorted by expert), through ``jax.lax.ragged_dot`` (what
``grouped_matmul`` runs) and through the Pallas ``megablox.gmm`` at
several tilings (what it was measured against); then the two gathers
back to the tokens (the combine, and the dispatch's backward) from a
short buffer, three ways: one gather of ``tokens * top_k`` rows and a sum
over each token's picks (what ``moe_held_experts`` ran until PR 33),
``top_k`` gathers of ``tokens`` rows added up so that ``[tokens, top_k,
d]`` is never written (what it runs), and a ``segment_sum`` of the
buffer's rows by token; then
the whole layer (router, top-k, sort, gathers, products, combine) as the
model runs it, at the load the seed's router gives and at two loads that
pass the short buffer (a selection bias on one and on four of the held
experts), with ``rows_held`` and ``buffer_rows`` beside every time.
Milliseconds are host clock around ``--iters`` chained calls ending in
one ``block_until_ready``; every implementation's result is compared
with the first of its kind.

Phase 2 (``--agreement N``): the model of the benchmark's ``--workload``
(default ``lfm2-traced-1chip``) at its full size on N seeds: the share of (token, pick) pairs on which the
program and the float32 reference pick the same expert, at the seed's
initial parameters and after ``--steps`` Adam steps; with ``--errors``
also the whole gradient's relative error against the reference, as
configured and with every dense projection's result rounded through
float8 (what ``GRAD_REL_TOL`` has to refuse).

``--load-trace N``: the ``--workload`` cell's job (its model,
its ring of resident batches drawn from ``--seed`` as ``benchmark/run.py``
draws them, Adam) for N steps: ``rows_held`` and ``buffer_rows`` of every
expert layer at every step, their range a layer, and the steps on which
a layer left the short buffer; the whole series goes to
``chiprun_out/moe_load_trace.json``.

One JSON line per row on stdout, all of them in
``chiprun_out/moe_probe.json``. Needs a TPU: a time from anything else is
not a kernel time (PERF.md).
"""

from __future__ import annotations

import argparse
import functools
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


def token_side(args):
    """The two gathers whose result is ``tokens * top_k`` rows long by
    definition, from a short buffer, three ways each."""
    from horovod_tpu.parallel import moe

    tokens, k, d = args.tokens, args.top_k, args.d_model
    pairs = tokens * k
    short = moe.short_buffer_rows(pairs, args.held, args.routed)
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 2), 3)
    picks = jax.random.randint(keys[0], (pairs,), 0, args.routed)
    sort_key = jnp.where(picks < args.held, picks, args.held)
    order = jnp.argsort(sort_key, stable=True)
    inverse = jnp.argsort(order)
    held = jnp.sum(picks < args.held)
    rows = jax.random.normal(keys[1], (short, d), jnp.bfloat16)
    gate = jnp.where((picks < args.held).reshape(tokens, k),
                     jax.random.uniform(keys[2], (tokens, k)),
                     0).astype(jnp.bfloat16)
    ones = jnp.ones_like(gate)
    emit(phase="token_side_load", rows_held=int(held), buffer_rows=short)

    def read(rows, index, held):
        index = jnp.where(index < held, index, rows.shape[0])
        return rows.at[index].get(mode="fill", fill_value=0)

    def one_gather(rows, gate, order, inverse, held):
        back = read(rows, inverse, held).reshape(tokens, k, d)
        return jnp.einsum("tk,tkd->td", gate, back,
                          preferred_element_type=jnp.float32)

    def a_gather_a_pick(rows, gate, order, inverse, held):
        return moe._picks_summed(rows, inverse.reshape(tokens, k), held,
                                 gate.astype(jnp.float32))

    def segment_sum(rows, gate, order, inverse, held):
        here = (jnp.arange(short) < held)[:, None]
        order = order[:short]
        gated = jnp.where(here, gate.reshape(-1)[order][:, None]
                          .astype(jnp.float32) * rows, 0)
        return jax.ops.segment_sum(gated, order // k, num_segments=tokens)

    for what, weights in (("combine", gate), ("dispatch_backward", ones)):
        want = None
        for fn in (one_gather, a_gather_a_pick, segment_sum):
            operands = (rows, weights, order, inverse, held)
            got = jax.jit(fn)(*operands)
            want = got if want is None else want
            emit(phase="token_side", what=what, impl=fn.__name__,
                 ms=timed(jax.jit(fn), operands, args.iters),
                 rel_err_vs_one_gather=float(
                     jnp.linalg.norm(got - want) / jnp.linalg.norm(want)))


def whole_layer(args):
    """``HeldExpertsMLP`` forward + backward as the model runs it, at the
    seed's load and with the selection biased towards 1 and 4 of the held
    experts (loads that pass the short buffer)."""
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
    def step(params, routing, x):
        def loss(params, x):
            y, state = layer.apply({"params": params, "routing": routing},
                                   x, mutable=["routing"])
            return jnp.sum(y.astype(jnp.float32) ** 2), state
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)

    bias = variables["routing"]["expert_bias"]
    for favoured in (0, 1, min(args.top_k, args.held)):
        routing = {"expert_bias": bias.at[:favoured].add(10.0)}
        operands = (variables["params"], routing, x)
        (_, state), _ = step(*operands)
        load = state["routing"]
        emit(phase="layer", experts_favoured=favoured,
             ms=timed(step, operands, args.iters),
             rows_held=int(load["rows_held"]),
             buffer_rows=int(load["buffer_rows"]),
             load_max=int(jnp.max(load["expert_load"])),
             load_min=int(jnp.min(load["expert_load"])))


def cell_job(workload):
    """A held-experts cell of the benchmark (``--workload``), its model
    module and one donating Adam step over its model."""
    import importlib

    import optax

    from benchmark import run as bench_run

    _, cell, config = bench_run.load_cell(workload)
    lfm2 = importlib.import_module(f"benchmark.models.{config['model']}")
    model = lfm2.make_model(config)
    tx = lfm2.optimizer(config)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train(params, aux, opt, *batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: lfm2.loss(model, p, aux, batch), has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), aux, opt, loss

    return lfm2, cell, config, model, tx, train


def load_trace(args):
    """How far the load wanders while the benchmark's window trains."""
    lfm2, cell, config, model, tx, train = cell_job(args.workload)
    # keys and ring as benchmark/run.py draws them
    init_key, data_key, _ = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    ring = [jax.jit(lambda key: lfm2.make_batch(
        config, key, cell["batch_per_chip"], cell["seq_len"]))(key)
        for key in jax.random.split(data_key, cell["ring"])]
    params, aux = jax.jit(lambda key: lfm2.init(model, config, key))(
        init_key)
    opt = jax.jit(tx.init)(params)

    seen = []
    for step in range(args.load_trace):
        params, aux, opt, loss = train(params, aux, opt,
                                       *ring[step % len(ring)])
        seen.append(({name: {what: layer["moe"][what]
                             for what in ("rows_held", "buffer_rows")}
                      for name, layer in aux.items()}, loss))
    loads, losses = zip(*jax.device_get(seen))
    series = {name: {what: [int(load[name][what]) for load in loads]
                     for what in ("rows_held", "buffer_rows")}
              for name in loads[0]}
    for name, s in series.items():
        short = min(s["buffer_rows"])
        emit(phase="load_trace", layer=name, steps=len(loads),
             rows_held_min=min(s["rows_held"]),
             rows_held_max=max(s["rows_held"]),
             rows_held_first=s["rows_held"][0],
             rows_held_last=s["rows_held"][-1],
             rows_held_every_32nd=s["rows_held"][::32],
             workload=args.workload,
             buffer_rows=sorted(set(s["buffer_rows"])),
             steps_off_the_short_buffer=[
                 i for i, rows in enumerate(s["buffer_rows"])
                 if rows != short])
    losses = [float(loss) for loss in losses]
    emit(phase="load_trace", loss_first=losses[0], loss_last=losses[-1])
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "moe_load_trace.json"), "w") as f:
        json.dump({"seed": args.seed, "loss": losses, "layers": series}, f)


def agreement(args):
    lfm2, cell, config, model, tx, train = cell_job(args.workload)
    # a model module without ``routing_agreement`` gives the errors alone
    agree = (jax.jit(lambda p, a, *b: lfm2.routing_agreement(
        model, config, p, a, b)) if hasattr(lfm2, "routing_agreement")
        else lambda p, a, *b: float("nan"))

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
        row = {"phase": "agreement", "workload": args.workload, "seed": seed,
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
    parser.add_argument("--skip-layer", action="store_true",
                        help="neither the token-side gathers nor the "
                        "whole layer")
    parser.add_argument("--load-trace", type=int, default=0, metavar="N",
                        help="steps of the cell's job to read rows_held "
                        "and buffer_rows over")
    parser.add_argument("--workload", default="lfm2-traced-1chip",
                        help="the held-experts cell --agreement and "
                        "--load-trace run (sdar-traced-1chip: the errors "
                        "without the agreement, which its module lacks)")
    args = parser.parse_args()
    args.tilings = [tuple(int(n) for n in t.split("x"))
                    for t in args.tilings.split(",")]

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"moe_probe.py: needs a TPU, found {device.platform!r}")
    emit(phase="device", kind=device.device_kind, count=jax.device_count())
    if not args.skip_products:
        products(args)
    if not args.skip_layer:
        token_side(args)
        whole_layer(args)
    if args.agreement:
        agreement(args)
    if args.load_trace:
        load_trace(args)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "moe_probe.json"), "w") as f:
        json.dump(ROWS, f, indent=1)


if __name__ == "__main__":
    main()
