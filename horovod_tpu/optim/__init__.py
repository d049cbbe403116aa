"""Distributed optimizer wrappers.

TPU-native rebuild of the reference's optimizer surface:

* ``DistributedOptimizer`` — the optax analog of
  ``/root/reference/horovod/torch/optimizer.py:131-343`` (per-param hook →
  allreduce → step) and ``/root/reference/horovod/tensorflow/__init__.py:443-630``.
  Here the allreduce is an ``optax.GradientTransformation`` stage, so under
  ``jit`` XLA fuses/overlaps the gradient collectives with the update math —
  the compiler plays the role of Horovod's fusion buffer + background cycle.
  In EAGER mode the stage buckets the gradient pytree by
  ``HVD_BUCKET_BYTES`` (default 64 MiB, the reference fusion-buffer scale)
  and issues each bucket as its own flushed async grouped allreduce so
  bucket k's collective hides under bucket k+1's host-side fuse and the
  update math — the reference's backward-pass comm/compute overlap
  (PAPER.md §L2), rebuilt on the pipelined flush executor. The update
  math itself, the wrapped optimizer's ``update``, then runs as ONE
  compiled program (``_sync_then_update``), not operation by operation.
* ``backward_passes_per_step`` — local gradient aggregation, the analog of
  ``LocalGradientAggregationHelper``
  (``/root/reference/horovod/tensorflow/gradient_aggregation*.py``), via
  ``optax.MultiSteps``.
* ``value_and_grad``/``grad`` — the ``DistributedGradientTape`` analog
  (``/root/reference/horovod/tensorflow/__init__.py:770-851``): wraps
  ``jax.value_and_grad`` and allreduces the gradient pytree.
"""

from __future__ import annotations

import contextlib
import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..ops import collectives
from ..ops import sparse as sparse_ops
from ..ops import step_capture
from ..ops import traced_exchange
from ..ops.compression import Compression, Compressor
from ..ops.reduce_ops import ReduceOp
from ..process_sets import ProcessSet, _resolve
from .. import metrics as _metrics
from .. import runtime
from .. import timeline as _timeline
from ..utils import envs

# Program spans (docs/timeline.md): the two stages of an EAGER
# ``DistributedOptimizer.update``. Not entered while jax traces the
# update (jit / shard_map): they would time tracing, not a step.
_SYNC = _timeline.span("optimizer.sync")
_INNER_UPDATE = _timeline.span("optimizer.inner_update")
_NO_SPAN = contextlib.nullcontext()
# Device scopes (docs/timeline.md), inside whatever program holds the
# update: the traced sync's ``lax.psum`` leaves (its permute rounds name
# themselves, ``ops/traced_exchange.py``) and the wrapped optimizer's
# ``update``, in a traced step and in the eager path's compiled one.
_SCOPE_PSUM = _timeline.scope("exchange.psum")
_SCOPE_UPDATE = _timeline.scope("optimizer.update")

# how an eager inner update ran (docs/metrics.md): label sets resolved once
_UPDATE_COMPILED = _metrics.OPTIMIZER_INNER_UPDATES.bind(
    {"event": "compiled"})
_UPDATE_DIRECT_EXTRA_ARGS = _metrics.OPTIMIZER_INNER_UPDATES.bind(
    {"event": "direct_extra_args"})
_UPDATE_TRACE = _metrics.OPTIMIZER_INNER_UPDATES.bind({"event": "trace"})


def _eager(span):
    return span() if collectives._trace_state_clean() else _NO_SPAN


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "idx", None)
        if key is None:
            key = getattr(p, "name", str(p))
        parts.append(str(key))
    return "/".join(parts)


def _sparse_rows_for(path_str: str, sparse_gradient_paths, sparse_max_rows):
    """max_rows for a sparse-routed leaf, or None for the dense path."""
    if not sparse_gradient_paths:
        return None
    for pat in sparse_gradient_paths:
        if re.search(pat, path_str):
            if isinstance(sparse_max_rows, dict):
                for k, v in sparse_max_rows.items():
                    if re.search(k, path_str):
                        return int(v)
                raise ValueError(
                    f"sparse gradient leaf {path_str!r} matched "
                    f"{pat!r} but sparse_max_rows has no entry for it")
            return int(sparse_max_rows)
    return None


def _leaf_nbytes(leaf) -> int:
    """Per-rank payload bytes of one gradient leaf (PerRank bundles drop
    the rank axis) — the accounting the bucket layout partitions on.
    Derives from static shape/dtype only, so every rank computes the
    identical layout for the same gradient tree."""
    if isinstance(leaf, collectives.PerRank):
        arr = leaf.array
        rows = max(int(arr.shape[0]), 1)
        return max(int(arr.nbytes) // rows, 1)
    nbytes = getattr(leaf, "nbytes", None)
    if nbytes is not None:
        return max(int(nbytes), 1)
    return int(jnp.dtype(jnp.result_type(leaf)).itemsize)


def _bucket_layout(sizes, cap: int) -> list[list[int]]:
    """Partition leaf indices into contiguous buckets of at most ``cap``
    bytes each, walking the flattened gradient tree in REVERSE traversal
    order — the backward pass produces the last layers' gradients first,
    so reverse-order buckets approximate gradient production order (the
    reference fusion buffer fills the same way). The layout is a pure
    function of the leaf sizes, so every rank issues the identical
    bucket stream in the identical order (the PR-2/3 rank-deterministic
    composition contract). A single leaf larger than ``cap`` forms its
    own bucket; indices stay reverse-traversal-ordered within and across
    buckets."""
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i in reversed(range(len(sizes))):
        if cur and cur_bytes + sizes[i] > cap:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += sizes[i]
    if cur:
        buckets.append(cur)
    return buckets


def _traced_sync(leaves, sync, *, op, process_set, compression,
                 prescale_factor, postscale_factor, axis_name, mesh_spec):
    """The dense leaves of a TRACED sync over a bound axis, by what
    ``traced_exchange.permute_rounds_selected`` says of each: the large
    floating leaves of a one-host data-parallel TPU job as ring
    reduce-scatter / all-gather rounds of ``lax.ppermute``; everything
    else through ``sync`` (one ``lax.psum`` a leaf), as before PR 31.
    Where any leaf takes the rounds, every leaf of at least
    ``MIN_LEAF_BYTES`` goes bucket by bucket (``BUCKET_BYTES``) in the
    order the backward pass produced them, each bucket behind an
    ``optimization_barrier`` on the one before. Measured on the chip
    (PERF.md section 6, PR 31): left to itself the compiler's scheduler
    keeps five permutes of any leaves in flight and the step is slower
    than with ``psum``; the chain is what makes the rounds a gain, and
    the order puts the head's gradient, whose inputs are the largest
    arrays of the step, first. ``hvd_traced_exchange_total{path}``
    counts each leaf once a trace."""
    axis = collectives._resolve_axis(axis_name)
    if not collectives._axis_is_bound(axis):
        return sync(leaves)     # plain jit: the GSPMD passthrough

    def psum(ts):
        with _SCOPE_PSUM():
            return sync(ts)

    size = jax.lax.axis_size(axis)
    devices = runtime.devices()
    ring = (traced_exchange.neighbour_ring(devices)
            if axis == runtime.axis_name() and size == len(devices)
            else None)
    seen = dict(
        platform=jax.default_backend(), axis_size=size, ring=ring, op=op,
        groups=_resolve(process_set).axis_index_groups(),
        mesh_spec=mesh_spec,
        compressed=compression not in (None, Compression.none),
        fused_threshold=envs.get_int(envs.TRACED_FUSION_THRESHOLD, 0))
    on_tpu = seen["platform"] == "tpu"      # elsewhere: nothing to lay out
    layouts = [traced_exchange.device_layout(
        devices[0], jnp.result_type(l), jnp.shape(l)) if on_tpu else None
        for l in leaves]
    rounds = [traced_exchange.permute_rounds_selected(
        dtype=jnp.result_type(l), nbytes=_leaf_nbytes(l),
        shape=jnp.shape(l), layout=layout, **seen)
        for l, layout in zip(leaves, layouts)]
    for selected in rounds:
        traced_exchange.count(selected)
    if not any(rounds):
        return psum(leaves)
    out = [None] * len(leaves)
    large = [i for i, l in enumerate(leaves)
             if _leaf_nbytes(l) >= traced_exchange.MIN_LEAF_BYTES]
    small = sorted(set(range(len(leaves))) - set(large))
    for i, r in zip(small, psum([leaves[i] for i in small]) if small else ()):
        out[i] = r
    # last produced first: _bucket_layout walks its sizes backwards
    large = [large[j] for j in reversed(traced_exchange.production_order(
        [leaves[i] for i in large]))]
    buckets = _bucket_layout([_leaf_nbytes(leaves[i]) for i in large],
                             traced_exchange.BUCKET_BYTES)
    done = None
    for bucket in buckets:
        idxs = [large[j] for j in bucket]
        grads = [leaves[i] for i in idxs]
        if done is not None:
            done, grads = jax.lax.optimization_barrier((done, grads))
        ringed = [j for j, i in enumerate(idxs) if rounds[i]]
        rest = [j for j, i in enumerate(idxs) if not rounds[i]]
        done = [None] * len(idxs)
        for j, r in zip(ringed, traced_exchange.allreduce_rounds(
                [grads[j] for j in ringed], axis, ring,
                average=op == ReduceOp.AVERAGE, pre=prescale_factor,
                post=postscale_factor,
                layouts=[layouts[idxs[j]] for j in ringed])
                if ringed else ()):
            done[j] = r
        for j, r in zip(rest, psum([grads[j] for j in rest]) if rest
                        else ()):
            done[j] = r
        for i, r in zip(idxs, done):
            out[i] = r
    traced_exchange.record_trace(len(buckets), sum(rounds), size)
    return out


def _bucketed_allreduce(leaves, *, op, process_set, compression,
                        prescale_factor, postscale_factor, axis_name,
                        mesh_spec=None):
    """Sync the dense gradient leaves with backward-pass comm/compute
    overlap (``HVD_BUCKET_BYTES``, default 64 MiB): partition into
    size-bounded reverse-traversal buckets, issue each bucket as its own
    ``grouped_allreduce_async`` and flush it immediately — bucket k's
    collective is then in flight on device while bucket k+1 fuses
    host-side and, downstream, the optax update math chains on completed
    buckets (results are collected without a device block; data
    dependencies order execution). Numerics are identical to the
    whole-tree grouped call: the reduction is elementwise per leaf, and
    fusion only changes wire packaging.

    Falls back to the single whole-tree grouped dispatch when bucketing
    is off (``HVD_BUCKET_BYTES=0``) or the tree fits one bucket. Tracers
    take :func:`_traced_sync`: nothing here overlaps a traced exchange
    for free. On the chip one ``lax.psum`` a leaf became 12 synchronous
    ``all-reduce`` operations after the backward pass, none beside
    another operation (``gpt2m-traced-4chip``: ``exposed_collective_ms``
    = ``collective_ms`` = 28.3 of a 121.5 ms step; ledger, PR 30), so
    the large leaves of a one-host TPU job are emitted as rounds of
    collective-permutes instead, which the compiler runs asynchronously.

    Where ``envs.eager_chain_enabled`` says consumer math must not chain
    on in-flight results (XLA CPU: its shared per-device thread pool
    lets the optax update programs starve an in-flight chunked
    collective's rendezvous — a reproduced hard deadlock), results are
    materialized before they return; overlap BETWEEN buckets is
    untouched (all buckets are submitted before the first collection
    blocks, and the flush executor pipelines them regardless)."""
    tracers = any(collectives._contains_tracer(l) for l in leaves)

    def sync(ts):
        out = collectives.grouped_allreduce(
            ts, op=op, process_set=process_set,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, axis_name=axis_name,
            compression=compression)
        if not tracers and not envs.eager_chain_enabled(
                jax.devices()[0].platform):
            jax.block_until_ready(collectives._result_arrays(out))
        return out

    if tracers:
        return _traced_sync(
            leaves, sync, op=op, process_set=process_set,
            compression=compression, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, axis_name=axis_name,
            mesh_spec=mesh_spec)
    cap = envs.bucket_bytes()
    if cap <= 0 or len(leaves) < 2:
        return sync(leaves)
    buckets = _bucket_layout([_leaf_nbytes(l) for l in leaves], cap)
    if len(buckets) < 2:
        return sync(leaves)
    # Step capture boundary (HVD_STEP_CAPTURE; ops/step_capture.py):
    # the bucket stream below is submit-then-collect — every bucket is
    # submitted and flushed before the first result is observed — which
    # is exactly the shape capture can record once and replay as ONE
    # whole-step program on later steps. The region is a no-op with the
    # knob off or when a user `hvd.step_marker()` region already spans
    # the step.
    with step_capture.auto_region():
        handles = []
        for idxs in buckets:
            h = collectives.grouped_allreduce_async(
                [leaves[i] for i in idxs], op=op, process_set=process_set,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor, axis_name=axis_name,
                compression=compression)
            # dispatch NOW (the "bucket" flush trigger): without this the
            # bucket would sit queued until a threshold/cycle/synchronize
            # trigger and nothing would overlap
            h.flush()
            handles.append((idxs, h))
        out = [None] * len(leaves)
        for idxs, h in handles:
            for i, r in zip(idxs, h.result()):
                out[i] = r
    return out


def _mesh_spec_sync(tree, mesh_spec, *, op, compression, prescale_factor,
                    postscale_factor):
    """Composed-mesh two-level gradient sync (``parallel/mesh.py``):
    when the spec's data axes are BOUND (the step runs inside
    ``shard_map`` over the composed mesh), every leaf reduces
    intra-slice over ``ici_dp`` (psum_scatter) then cross-slice over
    ``dcn`` (psum) with the standard pre/post scale split — model axes
    (seq/expert/stage) are never touched, and ``ReduceOp.ADASUM`` rides
    the ``dcn`` axis through the pairwise tree. Returns ``None`` when
    the axes are not bound (an eager call): the caller falls through to
    the bucketed eager path, keeping the PR-6 bucket pipelining and the
    PR-8 step capture exactly as for plain DP."""
    from ..parallel import mesh as composed
    dcn_axis, ici_axis = composed.resolve_data_axes(mesh_spec)
    if not (collectives._axis_is_bound(dcn_axis)
            and collectives._axis_is_bound(ici_axis)):
        return None
    from ..ops import adasum as adasum_ops
    from ..ops import hierarchical

    def sync_leaf(leaf):
        c, ctx = compression.compress(leaf)
        if op == ReduceOp.ADASUM:
            if prescale_factor != 1.0 or postscale_factor != 1.0:
                raise ValueError("Adasum is scale-invariant; pre/post "
                                 "scale factors do not apply")
            synced = adasum_ops.adasum_hierarchical_traced(
                c, ici_axis, dcn_axis)
        else:
            synced = hierarchical.hierarchical_allreduce_traced(
                c, ici_axis, dcn_axis, op=op,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor)
        return compression.decompress(synced, ctx)

    return jax.tree.map(sync_leaf, tree)


def _allreduce_tree(tree, *, op, process_set, compression, prescale_factor,
                    postscale_factor, axis_name,
                    sparse_gradient_paths=None, sparse_max_rows=None,
                    mesh_spec=None):
    """Allreduce every leaf of a gradient pytree with dtype-fused wire
    buffers (eager) or per-leaf psum (traced; XLA fuses). Leaves whose key
    path matches ``sparse_gradient_paths`` take the indexed-rows allgather
    path instead (wire traffic ∝ touched rows — the reference's
    IndexedSlices handling inside DistributedOptimizer).

    ``mesh_spec`` (a ``parallel.mesh.MeshLayout`` or a
    ``(dcn_axis, ici_dp_axis)`` name pair) routes BOUND-axis trees
    through the composed-mesh two-level sync — every leaf dense (the
    sparse allgather path is eager machinery); eager trees fall through
    to the bucketed path unchanged."""
    if mesh_spec is not None:
        synced = _mesh_spec_sync(
            tree, mesh_spec, op=op, compression=compression,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
        if synced is not None:
            return synced
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    if not path_leaves:
        return tree
    out: list = [None] * len(path_leaves)
    dense_idx, dense_leaves = [], []
    for i, (path, leaf) in enumerate(path_leaves):
        max_rows = _sparse_rows_for(_path_str(path), sparse_gradient_paths,
                                    sparse_max_rows)
        if max_rows is not None and getattr(leaf, "ndim", 0) == 2:
            axis = collectives._resolve_axis(axis_name)
            if (collectives._contains_tracer(leaf)
                    and not collectives._axis_is_bound(axis)):
                # Plain jit/pjit (GSPMD): the partitioner already globally
                # averaged the gradient — sync is the identity here exactly
                # as on the dense path (_gspmd_passthrough_check).
                collectives._gspmd_passthrough_check(op, "sparse_allreduce")
                scale = prescale_factor * postscale_factor
                out[i] = leaf if scale == 1.0 else leaf * scale
            else:
                # sparse leaves honor the same scaling/compression contract
                # as the dense leaves in the tree (compression casts the
                # wire dtype; scales bracket the reduction)
                scaled = leaf if prescale_factor == 1.0 \
                    else leaf * prescale_factor
                c, ctx = compression.compress(scaled)
                synced = sparse_ops.sparse_allreduce_to_dense(
                    c, max_rows, op=op, process_set=process_set,
                    axis_name=axis_name)
                synced = compression.decompress(synced, ctx)
                out[i] = synced if postscale_factor == 1.0 \
                    else synced * postscale_factor
        else:
            dense_idx.append(i)
            dense_leaves.append(leaf)
    if dense_leaves:
        # Wire compression is routed INTO the grouped dispatch: the fusion
        # buffers are keyed by wire dtype (mixed-source-dtype grads share
        # one compressed buffer) and results are decompressed after the
        # split — no per-leaf compress/decompress op storm around the call.
        # Eager trees larger than HVD_BUCKET_BYTES dispatch as a stream of
        # per-bucket async grouped allreduces so communication overlaps
        # the remaining host-side work (see _bucketed_allreduce).
        reduced = _bucketed_allreduce(
            dense_leaves, op=op, process_set=process_set,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor,
            axis_name=axis_name, compression=compression,
            mesh_spec=mesh_spec)
        for i, r in zip(dense_idx, reduced):
            out[i] = r
    return jax.tree.unflatten(treedef, out)


def allreduce_gradients_transform(
        *, op: ReduceOp = ReduceOp.AVERAGE,
        process_set: ProcessSet | None = None,
        compression: type[Compressor] = Compression.none,
        prescale_factor: float = 1.0, postscale_factor: float = 1.0,
        sparse_gradient_paths=None, sparse_max_rows=None,
        axis_name=None, mesh_spec=None) -> optax.GradientTransformation:
    """An optax stage that allreduces incoming gradients."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        with _eager(_SYNC):
            synced = _allreduce_tree(
                updates, op=op, process_set=process_set,
                compression=compression, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                sparse_gradient_paths=sparse_gradient_paths,
                sparse_max_rows=sparse_max_rows,
                axis_name=axis_name, mesh_spec=mesh_spec)
        return synced, state

    return optax.GradientTransformation(init_fn, update_fn)


def _jit_takes(leaf) -> bool:
    """Whether ``jax.jit`` accepts ``leaf`` as (part of) an argument."""
    return isinstance(leaf, (jax.Array, np.ndarray, np.generic,
                             bool, int, float, complex))


def _sync_made_them(synced, grads) -> bool:
    """Whether every synced leaf is a buffer the sync stage made: none
    is the caller's own gradient array handed through. Only such a tree
    may be donated."""
    callers = {id(leaf.array if isinstance(leaf, collectives.PerRank)
                  else leaf) for leaf in jax.tree.leaves(grads)}
    return not any(id(leaf) in callers for leaf in jax.tree.leaves(synced))


def _sync_then_update(sync: optax.GradientTransformation,
                      optimizer: optax.GradientTransformation):
    """``optax.chain(sync, optimizer)`` (its ``init``, its state tuple,
    its extra-args routing) whose EAGER second stage runs as ONE compiled
    program inside the ``optimizer.inner_update`` span.

    The program is the wrapped optimizer's own ``update`` under
    ``jax.jit``, created once per ``DistributedOptimizer``; its arguments
    are the synced gradients, the optimizer state, the parameters and the
    extra args, and its cache is jit's own (tree structure, shapes,
    dtypes, placement). Same arithmetic as a traced step's: XLA may fuse
    a multiply into an add, so a result can differ from the
    operation-by-operation one by a rounding of the larger term.

    Never donated: the caller's state and parameters (an eager caller may
    keep the old ones: elastic ``State.commit``, a before/after
    comparison). Donated: the synced gradients, which nobody outside this
    function has seen, so the updates take their buffers -- unless the
    sync handed one of the caller's own arrays through. Without it the
    eager step peaks one gradient-sized buffer higher (PERF.md, PR 26).

    The wrapped ``update`` is called directly where the input forces it:
    under a trace (jit / shard_map / ``optax.MultiSteps``' ``lax.cond``),
    where it is already part of somebody's program and a span would time
    tracing; and where ``extra_args`` hold a leaf jit cannot take as an
    argument (a line search's ``value_fn``)."""
    takes_extra_args = isinstance(optimizer,
                                  optax.GradientTransformationExtraArgs)

    def direct(updates, state, params, extra_args):
        with _SCOPE_UPDATE():
            return optimizer.update(updates, state, params, **extra_args)

    def program(updates, state, params, extra_args):
        # runs while jit traces, never on a cached call: counts traces
        _UPDATE_TRACE.inc()
        return direct(updates, state, params, extra_args)

    compiled = jax.jit(program)
    compiled_donating = jax.jit(program, donate_argnums=0)

    def way_to_run(synced, grads, extra_args):
        if not collectives._trace_state_clean():
            return direct
        if not all(map(_jit_takes, jax.tree.leaves(extra_args))):
            _UPDATE_DIRECT_EXTRA_ARGS.inc()
            return direct
        _UPDATE_COMPILED.inc()
        return (compiled_donating if _sync_made_them(synced, grads)
                else compiled)

    def init_fn(params):
        return sync.init(params), optimizer.init(params)

    def update_fn(grads, state, params=None, **extra_args):
        sync_state, state = state
        if not takes_extra_args:
            extra_args = {}
        synced, sync_state = sync.update(grads, sync_state, params)
        with _eager(_INNER_UPDATE):
            run = way_to_run(synced, grads, extra_args)
            updates, state = run(synced, state, params, extra_args)
        return updates, (sync_state, state)

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def DistributedOptimizer(
        optimizer: optax.GradientTransformation,
        *, op: ReduceOp = ReduceOp.AVERAGE,
        process_set: ProcessSet | None = None,
        compression: type[Compressor] = Compression.none,
        prescale_factor: float = 1.0, postscale_factor: float = 1.0,
        backward_passes_per_step: int = 1,
        sparse_gradient_paths=None, sparse_max_rows=None,
        axis_name=None, mesh_spec=None) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see globally-reduced gradients
    (reference ``hvd.DistributedOptimizer``).

    ``mesh_spec`` opts the sync into the composed-mesh contract
    (``parallel/mesh.py``, docs/mesh.md): pass the step's
    ``MeshLayout`` (or an explicit ``(dcn_axis, ici_dp_axis)`` pair)
    and a BOUND-axis step (``shard_map`` over ``hvd.composed_mesh()``)
    reduces its gradients two-level over the DATA axes only —
    intra-slice ``psum_scatter`` over ``ici_dp``, cross-slice ``psum``
    over ``dcn`` — leaving sequence/expert/stage model axes sharded.
    Eager steps with the same ``mesh_spec`` fall through to the
    bucketed pipeline below unchanged.

    With ``backward_passes_per_step > 1`` gradients accumulate locally
    (running mean, matching ``average_aggregated_gradients=True``) and the
    allreduce + inner update run every k-th step.

    Eager gradient trees larger than ``HVD_BUCKET_BYTES`` (default
    64 MiB; ``0`` disables) sync as a stream of per-bucket async grouped
    allreduces in stable reverse-traversal order — each bucket's
    collective is in flight while the next bucket fuses, and results are
    collected without a device block (where ``HVD_EAGER_CHAIN`` allows;
    auto = off on the XLA CPU backend, where consumer programs racing an
    in-flight collective deadlock its rendezvous) so the wrapped
    optimizer's update chains on completed buckets. Numerics are
    identical to the
    whole-tree call; bucket composition is a pure function of the leaf
    shapes, so multi-process jobs stay rank-deterministic. Traced
    (jit/shard_map) updates are untouched: XLA already schedules the
    collectives against the backward compute.

    The wrapped optimizer's eager ``update`` runs as one compiled program
    (``jax.jit`` of its own ``update``, made once here; same arithmetic
    as in a traced step). The old ``state`` and ``params`` stay the
    caller's: nothing of theirs is donated. It runs operation by
    operation only where ``extra_args`` carry a leaf jit cannot take as
    an argument (a callable); ``hvd_optimizer_inner_updates_total``
    (docs/metrics.md) counts both, and the program's traces: a job whose
    tree, shapes and placement stay fixed traces it once.

    ``sparse_gradient_paths`` is a list of regexes matched against each
    gradient leaf's ``/``-joined key path (e.g. ``["embedding"]``); matching
    2-D leaves sync via the indexed-rows allgather path with per-step wire
    traffic ∝ ``sparse_max_rows`` (an int, or a dict of path-regex → int)
    instead of the full table — the reference's IndexedSlices handling
    (``tensorflow/__init__.py:95-112``). ``HVD_SPARSE_AS_DENSE`` falls back
    to dense allreduce.
    """
    distributed = _sync_then_update(
        allreduce_gradients_transform(
            op=op, process_set=process_set, compression=compression,
            prescale_factor=prescale_factor, postscale_factor=postscale_factor,
            sparse_gradient_paths=sparse_gradient_paths,
            sparse_max_rows=sparse_max_rows,
            axis_name=axis_name, mesh_spec=mesh_spec),
        optimizer)
    if backward_passes_per_step > 1:
        return optax.MultiSteps(
            distributed, every_k_schedule=backward_passes_per_step)
    return distributed


def value_and_grad(fun, argnums=0, has_aux: bool = False,
                   *, op: ReduceOp = ReduceOp.AVERAGE,
                   process_set: ProcessSet | None = None,
                   compression: type[Compressor] = Compression.none,
                   axis_name=None, mesh_spec=None):
    """``jax.value_and_grad`` whose gradients are allreduced — the
    ``DistributedGradientTape`` analog. The loss value is *not* reduced
    (matches the reference, which only reduces gradients).
    ``mesh_spec`` routes bound-axis gradients through the composed-mesh
    two-level data sync (see :func:`DistributedOptimizer`)."""
    vg = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        value, grads = vg(*args, **kwargs)
        grads = _allreduce_tree(
            grads, op=op, process_set=process_set, compression=compression,
            prescale_factor=1.0, postscale_factor=1.0, axis_name=axis_name,
            mesh_spec=mesh_spec)
        return value, grads

    return wrapped


def grad(fun, argnums=0, has_aux: bool = False, **kwargs):
    """``jax.grad`` with allreduced gradients. With ``has_aux=True``
    returns ``(grads, aux)``, matching the jax.grad contract."""
    vg = value_and_grad(fun, argnums=argnums, has_aux=has_aux, **kwargs)

    def wrapped(*args, **kw):
        value, grads = vg(*args, **kw)
        if has_aux:
            _, aux = value
            return grads, aux
        return grads

    return wrapped
