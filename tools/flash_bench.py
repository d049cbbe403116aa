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

``--kv-heads`` gives key and value fewer heads than the query, each
serving a group (grouped-query attention): ``blocked`` then hands the
kernels k and v at their own head count, as ``Attention`` does, and
``repeated`` repeats them to the query's count first (what ran before the
kernels took groups); the materialised reference repeats them too:

    chiprun -- python tools/flash_bench.py --mask block_diffusion \
        --batch 1 --heads 32 --kv-heads 4 --head-dim 128 --seqs 4096 \
        --tiles 512 --impls blocked,repeated

``--window W`` times the causal mask over the last ``W`` keys (a
``sliding_attention`` layer): ``blocked`` is ``Attention``'s call with the
window (sweeps as long as the window, ``ops/flash.py``), ``causal`` the
causal kernels at the same shapes (a wrong mask: what the kernels cost
without the window's skipping), the materialised reference masked by
``window_mask`` (skipped at 16384 rows, past 3 GiB of logits); on a TPU:

    python tools/flash_bench.py --window 4096 --batch 1 \
        --heads 28 --kv-heads 4 --head-dim 128 --seqs 16384 --tiles 512 \
        --impls blocked,causal

``--prepare`` times what lies between the q / k projections and the
kernels instead, at the same shapes: ``statements`` is ``Attention``'s
chain without the pass (``operators.RMSNorm``, ``operators.rotary``, the
query's ``/ sqrt(d)``, ``_rows``), ``prepare`` the one pass each way of
``horovod_tpu.ops.prepare_rows`` at each row tile of ``--tiles``; under
``--mask block_diffusion`` at each row's own position, as the doubled
sequence has them, on one TPU chip:

    python tools/flash_bench.py --prepare --mask \
        block_diffusion --batch 1 --heads 32 --kv-heads 4 --head-dim 128 \
        --seqs 4096 --tiles 256,512,1024

``--forward-tiles`` times the forward kernel alone instead, one Mosaic
call per limit over the kernels' ``(b * heads, s, d)`` rows, at each
``QxKV`` pick of the rows a grid step takes (``ops/flash.py``
``forward_tiles``, patched per pick; the backward is not run), the limits
of ``--mask`` / ``--window``; each pick's output and log-sum-exp are
compared with the first pick's, and ``tile_tflops`` counts two products
of every visible 512 x 512 score tile. The sweep at the benchmark's
attention shapes, on one TPU chip:

    for args in "--heads 28 --kv-heads 4 --head-dim 128 --seqs 16384" \
        "--heads 28 --kv-heads 4 --head-dim 128 --seqs 16384 --window 4096" \
        "--mask block_diffusion --heads 32 --kv-heads 4 --head-dim 128 \
         --seqs 4096" \
        "--heads 32 --head-dim 64 --seqs 8192" \
        "--batch 4 --heads 16 --head-dim 64 --seqs 1024"; do
      python tools/flash_bench.py --batch 1 $args --forward-tiles \
          512x512,1024x512,512x1024,1024x1024,512x2048,1024x2048,2048x512
    done

(the last line's ``--batch 4`` overrides the first's ``--batch 1``).

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


def repeated(attend):
    """``attend`` with k and v repeated to the query's head count."""
    def call(q, k, v):
        group = q.shape[2] // k.shape[2]
        return attend(q, *(jnp.repeat(x, group, axis=2) for x in (k, v)))
    return call


def block_diffusion_impls(block):
    """The same three under the block-diffusion mask over 2L rows."""
    from horovod_tpu.parallel.sequence import _block_diffusion_flash

    @repeated
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


def prepare_impls(head_dim, positions, theta=1e6, eps=1e-6):
    """q and k, their norms' scales -> their kernels' rows: as
    ``Attention``'s statements, and through the pass."""
    from horovod_tpu.models import operators
    from horovod_tpu.ops import prepare_rows
    from horovod_tpu.parallel.sequence import _rows

    class Cfg:
        norm_eps, dtype = eps, jnp.bfloat16

    def statements(q, k, q_scale, k_scale):
        q, k = (operators.RMSNorm(Cfg()).apply({"params": {"scale": s}}, x)
                for x, s in ((q, q_scale), (k, k_scale)))
        q, k = operators.rotary(q, k, theta, positions)
        return _rows(_scaled(q)), _rows(k)

    def prepare(q, k, q_scale, k_scale):
        tables = operators.rotary_tables(q.shape[1], head_dim, theta,
                                         positions)
        return tuple(prepare_rows.prepare_rows(
            x, s, tables, eps=eps, prescale=x is q)
            for x, s in ((q, q_scale), (k, k_scale)))

    return statements, prepare


def prepare_bench(args, device):
    """``--prepare``: the statements against the pass, one line each."""
    from horovod_tpu.ops import prepare_rows

    rows = []
    for seq in (int(s) for s in args.seqs.split(",")):
        data_tokens = seq
        positions = None
        if args.mask == "block_diffusion":
            seq *= 2
            positions = jnp.tile(jnp.arange(data_tokens, dtype=jnp.int32),
                                 (args.batch, 2))
        keys = jax.random.split(jax.random.PRNGKey(seq), 6)
        heads = (args.heads, args.kv_heads or args.heads)
        q, k = (jax.random.normal(key, (args.batch, seq, n, args.head_dim),
                                  jnp.float32).astype(jnp.bfloat16)
                for key, n in zip(keys, heads))
        scales = tuple(1 + 0.1 * jax.random.normal(key, (args.head_dim,))
                       for key in keys[2:4])
        weights = tuple(jax.random.normal(key, (args.batch * n, seq,
                                                args.head_dim))
                        for key, n in zip(keys[4:], heads))
        statements, prepare = prepare_impls(args.head_dim, positions)

        def measure_rows(fn, want=None):
            fwd = jax.jit(fn)
            grad = jax.jit(jax.grad(lambda *a: sum(
                jnp.sum(r.astype(jnp.float32) * w)
                for r, w in zip(fn(*a), weights)), argnums=(0, 1, 2, 3)))
            out, fwd_ms = timed(fwd, (q, k) + scales, args.iters)
            grads, both_ms = timed(grad, (q, k) + scales, args.iters)
            row = {"fwd_ms": round(fwd_ms, 4),
                   "fwd_bwd_ms": round(both_ms, 4)}
            if want is not None:
                row["out_err"] = max(rel_err(a, b)
                                     for a, b in zip(out, want[0]))
                row["grad_err"] = max(rel_err(a, b)
                                      for a, b in zip(grads, want[1]))
            return row, (out, grads)

        def record(name, row, tile=None):
            rows.append({"impl": name, "mask": args.mask,
                         "data_tokens": data_tokens, "seq": seq,
                         "heads": heads, "d": args.head_dim, "tile": tile,
                         **row, "device_kind": device.device_kind})
            print(json.dumps(rows[-1]), flush=True)

        row, want = measure_rows(statements)
        record("statements", row)
        for tile in (int(t) for t in args.tiles.split(",")):
            prepare_rows.ROW_TILES = (tile,)
            # a fresh function per tile: jit caches a trace by the function
            record("prepare", measure_rows(
                lambda *a: prepare(*a), want)[0], tile)
    return rows


def forward_bench(args, device):
    """``--forward-tiles``: the forward kernel alone at each pick, one
    line each."""
    picks = [tuple(int(x) for x in pick.split("x"))
             for pick in args.forward_tiles.split(",")]
    rows = []
    for seq in (int(s) for s in args.seqs.split(",")):
        if args.mask == "block_diffusion":  # two L x L sweeps
            limits = (flash.block_causal(args.block_length),
                      flash.earlier_blocks(args.block_length))
        else:
            limits = ((flash.sliding_window(args.window),) if args.window
                      else (flash.CAUSAL,))
        bh = args.batch * args.heads
        kv_bh = args.batch * (args.kv_heads or args.heads)
        keys = jax.random.split(jax.random.PRNGKey(seq), 3)
        q = (jax.random.normal(keys[0], (bh, seq, args.head_dim))
             * args.head_dim ** -0.5).astype(jnp.bfloat16)
        k, v = (jax.random.normal(key, (kv_bh, seq, args.head_dim)
                                  ).astype(jnp.bfloat16) for key in keys[1:])
        tiles = bh * sum(flash.visible_tiles(limit, seq, seq, 512, 512)
                         for limit in limits)
        first = None
        for pick in picks:
            flash.forward_tiles = lambda rows, d, limit, pick=pick: pick
            q_tiling, kv_tiling, kv_sub = flash._forward_tiling(
                seq, args.head_dim, limits[0])

            def attend(q, k, v):
                return [flash.flash_attend(q, k, v, limit)
                        for limit in limits]

            try:
                out, ms = timed(jax.jit(attend), (q, k, v), args.iters)
            except Exception as exc:  # a tiling Mosaic refuses: a finding
                row = {"error": str(exc)[:300]}
            else:
                row = {"fwd_ms": round(ms, 4),
                       "tile_tflops": round(
                           tiles * 4 * 512 * 512 * args.head_dim
                           / ms / 1e9, 2)}
                if first is None:
                    first = out
                else:
                    row["out_err"] = max(rel_err(a[0], b[0])
                                         for a, b in zip(out, first))
                    row["lse_err"] = max(rel_err(a[1], b[1])
                                         for a, b in zip(out, first))
            rows.append({"impl": "forward", "mask": args.mask,
                         "window": args.window, "seq": seq, "bh": bh,
                         "kv_bh": kv_bh, "d": args.head_dim,
                         "tiles": list(pick),
                         "resolved": [q_tiling[0], kv_tiling[0] * kv_sub],
                         "visible_512_tiles": tiles, **row,
                         "device_kind": device.device_kind})
            print(json.dumps(rows[-1]), flush=True)
    return rows


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--heads", type=int, default=16)
    parser.add_argument("--kv-heads", type=int, default=None,
                        help="key/value heads (default: --heads)")
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
                             "kernels over the doubled rows), causal (--window "
                             "only: the causal kernels), repeated (the "
                             "kernels with k and v repeated to --heads)")
    parser.add_argument("--mask", default="causal",
                        choices=transformer.ATTN_MASKS)
    parser.add_argument("--block-length", type=int, default=4)
    parser.add_argument("--window", type=int, default=0,
                        help="under --mask causal: keys a row sees, its "
                             "own and those before it")
    parser.add_argument("--prepare", action="store_true",
                        help="time q and k's way into the kernels: the "
                             "statements against ops/prepare_rows.py's "
                             "pass at the row tiles of --tiles")
    parser.add_argument("--forward-tiles", default=None,
                        help="time the forward kernel alone at these "
                             "QxKV rows a grid step, e.g. 512x512,1024x1024")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"flash_bench: needs a TPU, jax found {device.platform!r}")

    if args.prepare or args.forward_tiles:
        rows = (prepare_bench if args.prepare else forward_bench)(args,
                                                                  device)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/flash_bench.json", "w") as f:
            json.dump(rows, f, indent=1)
        return

    tiles = [int(t) for t in args.tiles.split(",")]
    # a fresh function per tile pair: jit caches a trace by the function
    reference, attend = repeated(materialised), blocked
    if args.mask == "block_diffusion":
        reference, attend = block_diffusion_impls(args.block_length)
    elif args.window:
        reference = repeated(lambda q, k, v: transformer.materialised_attention(
            _scaled(q), k, v, window=args.window))

        def attend(q, k, v):
            return transformer.blocked_attention(_scaled(q), k, v,
                                                 window=args.window)
    impls = {"blocked": lambda tq, tk: lambda q, k, v: attend(q, k, v),
             "repeated": lambda tq, tk: repeated(
                 lambda q, k, v: attend(q, k, v)),
             "causal_rows": lambda tq, tk: repeated(
                 lambda q, k, v: blocked(q, k, v)),
             "causal": lambda tq, tk: lambda q, k, v: blocked(q, k, v),
             "jax_kernel": lambda tq, tk: repeated(jax_kernel(tq, tk))}
    impls = {name: impls[name] for name in args.impls.split(",")}
    rows = []
    for seq in (int(s) for s in args.seqs.split(",")):
        data_tokens = seq
        if args.mask == "block_diffusion":
            seq *= 2                     # the noised copy, then the clean
        shape = (args.batch, seq, args.heads, args.head_dim)
        kv_shape = (args.batch, seq, args.kv_heads or args.heads,
                    args.head_dim)
        inputs = tuple(
            jax.random.normal(key, s, jnp.float32).astype(jnp.bfloat16)
            for key, s in zip(jax.random.split(jax.random.PRNGKey(seq), 4),
                              (shape, kv_shape, kv_shape, shape)))

        def record(name, row, tile=None):
            rows.append({"impl": name, "mask": args.mask,
                         "window": args.window,
                         "data_tokens": data_tokens, "seq": seq,
                         "bh": shape[0] * shape[2],
                         "kv_heads": kv_shape[2],
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
                    # causal_rows / causal compute another mask: no
                    # comparison
                    row = measure(make(tq, tk), inputs, args.iters,
                                  None if name.startswith("causal")
                                  else want)[0]
                except Exception as exc:  # a tile Mosaic refuses: a finding
                    row = {"error": str(exc)[:300]}
                record(name, row, [tq, tk])

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_bench.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
