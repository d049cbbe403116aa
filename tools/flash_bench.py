"""Attention alone, on the chip: the two functions ``TransformerLM``'s
``full`` mode selects between (``models/transformer.py``): the blocked
Pallas kernels of ``horovod_tpu.ops.flash`` against ``jax.jit`` of the
materialised formulation, at the model's own layout ``(batch, seq,
heads, head_dim)``, causal, bfloat16, forward alone and forward +
backward (gradients of q, k and v).

    chiprun -- python tools/flash_bench.py --seqs 256,512,1024,2048 \
        --tiles 128,256,512,1024 --impls blocked,jax_kernel

``--mask block_diffusion`` times the block-diffusion mask instead
(``--seqs`` then counts the data tokens L; every implementation runs the
doubled 2L rows): ``blocked`` is ``parallel/sequence.py``
``_block_diffusion_flash`` (two sweeps of the kernels under limits that
skip what no row sees, and a noised row's own block in ``jax.numpy``),
``causal_rows`` the causal kernels over the same 2L rows (a wrong mask:
what the kernels cost without the new limits' skipping), and
``materialised`` the 2L x 2L form under ``block_diffusion_mask``, left
out where its float32 logits would pass ``MATERIALISED_MAX_GIB``:

    chiprun -- python tools/flash_bench.py --mask block_diffusion \
        --batch 1 --heads 32 --head-dim 128 --seqs 4096 --tiles 512 \
        --impls blocked,causal_rows

One JSON line per (sequence, implementation, tile pair) on stdout and all
of them in ``chiprun_out/flash_bench.json``. Milliseconds are host clock
around ``--iters`` chained calls ending in one ``block_until_ready``;
every blocked result is compared with the materialised one first. Needs
a TPU: a time from anything else is not a kernel time (PERF.md).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer
from horovod_tpu.ops import flash


MATERIALISED_MAX_GIB = 3.0  # float32 logits the materialised form may hold


def _scaled(q):
    return q / jnp.sqrt(q.shape[-1]).astype(q.dtype)  # as Attention does


def materialised(q, k, v):
    return transformer.materialised_attention(_scaled(q), k, v)


def blocked(q, k, v):
    return transformer.blocked_attention(_scaled(q), k, v)


def block_diffusion_impls(block):
    """The same three under the block-diffusion mask over 2L rows."""
    from horovod_tpu.parallel.sequence import _block_diffusion_flash

    def materialised(q, k, v):
        mask = transformer.block_diffusion_mask(q.shape[1] // 2, block)
        return transformer.materialised_attention(_scaled(q), k, v, mask)

    def blocked(q, k, v):
        return _block_diffusion_flash(_scaled(q), k, v, block, True, False)

    return materialised, blocked


def jax_kernel(block_q, block_k):
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    def attend(q, k, v):
        s = q.shape[1]
        bq, bk = min(block_q, s), min(block_k, s)
        sizes = fa.BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
            block_q_dq=bq)
        out = fa.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True,
            sm_scale=q.shape[-1] ** -0.5, block_sizes=sizes)
        return out.transpose(0, 2, 1, 3)

    return attend


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        last = fn(*args)
    jax.block_until_ready(last)
    return out, (time.perf_counter() - start) / iters * 1e3


def rel_err(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def measure(attend, inputs, iters, want=None):
    q, k, v, w = inputs
    fwd = jax.jit(attend)
    grad = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2)))
    out, fwd_ms = timed(fwd, (q, k, v), iters)
    grads, both_ms = timed(grad, (q, k, v), iters)
    row = {"fwd_ms": round(fwd_ms, 4), "fwd_bwd_ms": round(both_ms, 4)}
    if want is not None:
        row["out_err"] = rel_err(out, want[0])
        row["grad_err"] = max(rel_err(g, r) for g, r in zip(grads, want[1]))
    return row, (out, grads)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--heads", type=int, default=16)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--seqs", default="1024")
    parser.add_argument("--tiles", default="256,512",
                        help="q and kv tiles to cross")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--impls", default="blocked",
                        help="blocked (this repo's kernels), jax_kernel "
                             "(jax.experimental.pallas.ops.tpu."
                             "flash_attention at the same block sizes), "
                             "causal_rows (block_diffusion only: the causal "
                             "kernels over the doubled rows)")
    parser.add_argument("--mask", default="causal",
                        choices=transformer.ATTN_MASKS)
    parser.add_argument("--block-length", type=int, default=4)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"flash_bench: needs a TPU, jax found {device.platform!r}")

    tiles = [int(t) for t in args.tiles.split(",")]
    # a fresh function per tile pair: jit caches a trace by the function
    reference, attend = materialised, blocked
    if args.mask == "block_diffusion":
        reference, attend = block_diffusion_impls(args.block_length)
    impls = {"blocked": lambda tq, tk: lambda q, k, v: attend(q, k, v),
             "causal_rows": lambda tq, tk: lambda q, k, v: blocked(q, k, v),
             "jax_kernel": jax_kernel}
    impls = {name: impls[name] for name in args.impls.split(",")}
    rows = []
    for seq in (int(s) for s in args.seqs.split(",")):
        data_tokens = seq
        if args.mask == "block_diffusion":
            seq *= 2                     # the noised copy, then the clean
        shape = (args.batch, seq, args.heads, args.head_dim)
        inputs = tuple(
            jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
            for key in jax.random.split(jax.random.PRNGKey(seq), 4))

        def record(name, row, tile=None):
            rows.append({"impl": name, "mask": args.mask,
                         "data_tokens": data_tokens, "seq": seq,
                         "bh": shape[0] * shape[2],
                         "d": shape[3], "tile": tile, **row,
                         "device_kind": device.device_kind})
            print(json.dumps(rows[-1]), flush=True)

        want = None
        logits_gib = shape[0] * shape[2] * seq * seq * 4 / 2 ** 30
        if logits_gib <= MATERIALISED_MAX_GIB:
            row, want = measure(reference, inputs, args.iters)
            record("materialised", row)
        else:
            record("materialised", {"skipped": f"{logits_gib:.1f} GiB of "
                                    "float32 logits"})
        for tq, tk in itertools.product(tiles, tiles):
            if tq > seq or tk > seq:
                continue
            flash.DEFAULT_Q_TILE, flash.DEFAULT_KV_TILE = tq, tk
            for name, make in impls.items():
                try:
                    # causal_rows computes another mask: no comparison
                    row = measure(make(tq, tk), inputs, args.iters,
                                  None if name == "causal_rows" else want)[0]
                except Exception as exc:  # a tile Mosaic refuses: a finding
                    row = {"error": str(exc)[:300]}
                record(name, row, [tq, tk])

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_bench.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
