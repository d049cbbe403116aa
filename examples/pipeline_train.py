#!/usr/bin/env python
"""Pipeline-parallel language-model training over a dp x pp mesh.

Demonstrates ``horovod_tpu.parallel.pipeline_apply`` end to end on an
LM-shaped model: a replicated embedding, N residual-MLP blocks split
into one pipeline stage per 'pp' chip (params as plain pytrees — they
shard freely where flax module params cannot), and a replicated output
head. Gradients: dp pmean for data parallelism; the pipeline's own
custom-VJP conventions make stage grads exactly-once and embedding/head
grads replica-consistent over pp with no extra collectives.

Run (CPU mesh): JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/pipeline_train.py --smoke
Without JAX_PLATFORMS=cpu, --smoke runs on whatever device jax finds.
"""

import argparse
import sys


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--pp", type=int, default=2,
                        help="pipeline stages (chips along 'pp')")
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    if args.smoke:
        args.steps = 8

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel import (
        pipeline_apply,
        stack_stage_params,
        unstack_stage,
    )

    hvd.init()
    n = hvd.size()
    if args.pp < 1 or n % args.pp:
        raise SystemExit(
            f"--pp {args.pp} must be a positive divisor of the "
            f"{n}-device world")
    pp = args.pp
    dp = n // pp
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(dp, pp), ("dp", "pp"))

    vocab, d_model, seq, layers_per_stage = 64, 32, 16, 2
    rng = np.random.default_rng(0)

    def init_block():
        return {"wi": jnp.asarray(
                    rng.standard_normal((d_model, 4 * d_model)) * 0.05,
                    jnp.float32),
                "wo": jnp.asarray(
                    rng.standard_normal((4 * d_model, d_model)) * 0.05,
                    jnp.float32)}

    params = {
        "embed": jnp.asarray(rng.standard_normal((vocab, d_model)) * 0.1,
                             jnp.float32),
        "stages": stack_stage_params(
            [{"blocks": [init_block() for _ in range(layers_per_stage)]}
             for _ in range(pp)]),
        "head": jnp.asarray(rng.standard_normal((d_model, vocab)) * 0.1,
                            jnp.float32),
    }

    def stage_fn(stage_params, h):
        for blk in stage_params["blocks"]:
            h = h + jnp.tanh(h @ blk["wi"]) @ blk["wo"]  # residual MLP
        return h

    # toy task: predict the next token of a fixed random sequence
    tokens = rng.integers(0, vocab, (8 * dp, seq + 1))
    x_host, y_host = tokens[:, :-1], tokens[:, 1:]

    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            h = p["embed"][x]  # replicated embed, dp-sharded batch
            h = pipeline_apply(stage_fn, unstack_stage(p["stages"]), h,
                               "pp", n_microbatches=4)
            logits = h @ p["head"]
            one_hot = jax.nn.one_hot(y, vocab)
            return -jnp.mean(jnp.sum(
                one_hot * jax.nn.log_softmax(logits), -1))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "dp"))

    # Stage tensors shard over 'pp' (their leading dim is the stage);
    # embed/head and adam's scalar count replicate. Per-leaf specs make
    # both the shard_map signature and the device_put placements.
    def spec_of(leaf):
        if jnp.ndim(leaf) >= 1 and leaf.shape[:1] == (pp,):
            return P("pp")
        return P()

    def put_with_specs(tree, specs):
        return jax.tree.map(
            lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
            tree, specs)

    param_specs = {"embed": P(),
                   "stages": jax.tree.map(lambda _: P("pp"),
                                          params["stages"]),
                   "head": P()}
    opt_specs = jax.tree.map(spec_of, opt_state)
    in_specs = (param_specs, opt_specs, P("dp"), P("dp"))
    out_specs = (in_specs[0], in_specs[1], P())
    step = jax.jit(jax.shard_map(train_step, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    params = put_with_specs(params, param_specs)
    opt_state = put_with_specs(opt_state, opt_specs)
    xs = jax.device_put(x_host, NamedSharding(mesh, P("dp")))
    ys = jax.device_put(y_host, NamedSharding(mesh, P("dp")))

    losses = []
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, xs, ys)
        losses.append(float(jax.block_until_ready(loss)))
    print(f"pp={pp} dp={dp}: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"in {len(losses)} steps")
    assert losses[-1] < losses[0], losses
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
