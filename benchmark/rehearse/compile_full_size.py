#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's step at
its full size for a described ``v5e:2x2`` topology, with no chip
attached, and print what the TPU compiler's ``memory_analysis()`` says
each device has to hold. It is how the GPT-2 cells' batch was chosen
(PERF.md section 4). Nothing runs; nothing printed here is a speed.

    JAX_PLATFORMS=cpu python benchmark/rehearse/compile_full_size.py \
        --workload gpt2m-gspmd-1chip [--batch 2 4 8]

The jobs build their meshes from ``hvd.mesh()``, which is the CPU here,
so this script rebuilds each job's program around the described devices
from the same pieces (``jobs/common.py``, the model module).
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HBM_BYTES = 16 * 2 ** 30 * 0.98   # what the compiler leaves a program


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--batch", type=int, nargs="*",
                        help="per-chip batches to try (default: the cell's)")
    args = parser.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark import run
    from benchmark.jobs import common

    jax.config.update("jax_enable_compilation_cache", False)
    _, cell, config = run.load_cell(args.workload)
    mm = importlib.import_module(f"benchmark.models.{config['model']}")
    hvd.init()
    axis = hvd.axis_name()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = cell["chips"]
    mesh = Mesh(np.array(topo.devices[:chips]), (axis,))
    replicated, sharded = (NamedSharding(mesh, P()),
                           NamedSharding(mesh, P(axis)))

    def shaped(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    traced = cell["job"] != "gspmd"
    model = mm.make_model(config, axis_name=axis if traced else None)
    tx = hvd.DistributedOptimizer(mm.optimizer(config))
    key = jax.random.PRNGKey(0)
    params, aux = jax.eval_shape(lambda k: mm.init(model, config, k), key)
    opt = jax.eval_shape(tx.init, params)
    state = shaped((params, aux, opt), replicated)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"{cell['name']}: {n_params / 1e6:.1f} M parameters, "
          f"{len(jax.tree.leaves(params))} leaves, job {cell['job']}")

    for batch in args.batch or [cell["batch_per_chip"]]:
        inputs = shaped(jax.eval_shape(
            lambda k: mm.make_batch(config, k, batch * chips,
                                    cell["seq_len"]), key), sharded)
        if cell["job"] == "gspmd":
            step = jax.jit(common.step_body(mm, model, tx),
                           donate_argnums=(0, 1, 2))
        else:
            # traced, and the eager job's device work (the same step in
            # three programs instead of one)
            body = common.step_body(
                mm, model, tx,
                reduce_loss=lambda loss: jax.lax.pmean(loss, axis))
            step = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P()) + (P(axis),) * len(inputs),
                out_specs=(P(), P(), P(), P()), check_vma=False),
                donate_argnums=(0, 1, 2))
        try:
            compiled = step.lower(*state, *inputs).compile()
        except Exception as e:  # the compiler's refusal is the answer
            print(f"  batch {batch}/chip: REFUSED: "
                  f"{str(e).splitlines()[0][:300]}")
            continue
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        text = compiled.as_text()
        print(f"  batch {batch}/chip: arguments {m.argument_size_in_bytes / 2**30:.2f} "
              f"GiB, outputs {m.output_size_in_bytes / 2**30:.2f}, aliased "
              f"{m.alias_size_in_bytes / 2**30:.2f}, temporaries "
              f"{m.temp_size_in_bytes / 2**30:.2f}: {total / 2**30:.2f} GiB a "
              f"device ({'fits' if total < HBM_BYTES else 'DOES NOT FIT'}); "
              f"all-reduce ops in the program: {text.count(' all-reduce')}"
              f" (+{text.count('all-reduce-start')} async)")


if __name__ == "__main__":
    main()
