"""Collective operations over the rank mesh.

TPU-native rebuild of the reference's collective op layer
(``/root/reference/horovod/common/ops/collective_operations.h:38-308`` and the
enqueue API ``EnqueueTensorAllreduce(s)/Allgather/Broadcast/Alltoall/Barrier``
at ``/root/reference/horovod/common/operations.cc:1357-1795``), with the
design inversion of SURVEY.md §7: the XLA compiler — not a background
runtime thread — schedules collectives.

Two execution modes:

* **Traced mode** — the call happens inside user code already running under
  ``jax.shard_map`` (or ``pmap``) with the runtime's mesh axis bound. The op
  lowers directly to ``lax.psum``/``all_gather``/``all_to_all``/
  ``psum_scatter``; XLA fuses and overlaps them (this replaces the
  reference's fusion buffer + cycle machinery for the jit hot path).
  Process-set subsets lower to ``axis_index_groups`` partitions.

* **Eager mode** — the call happens on concrete arrays. Per-rank inputs are
  carried in a :class:`PerRank` bundle (leading axis = ranks, sharded across
  chips); the op runs a cached ``jit(shard_map(...))`` over the process
  set's sub-mesh. A plain (unbundled) array is treated as the same value
  contributed by every rank.

Eager collectives return plain arrays when the result is identical on every
rank (allreduce/allgather/broadcast) and :class:`PerRank` bundles when it
differs (alltoall/reducescatter).
"""

from __future__ import annotations

import functools
import pickle
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.core import Tracer as _Tracer
# True when no jax trace is in progress: a concrete-value call site is
# definitely eager (jax 0.9 keeps this probe in jax._src.core only)
from jax._src.core import trace_state_clean as _trace_state_clean
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import autotune as _autotune
from .. import runtime
from .. import timeline as _timeline
from ..loopback import dispatch as _lb
from ..dynamic import (
    REQ_ALLGATHER,
    REQ_ALLREDUCE,
    REQ_ALLTOALL,
    REQ_BARRIER,
    REQ_BROADCAST,
    REQ_REDUCESCATTER,
)
from ..process_sets import ProcessSet, _resolve
from . import dispatch_cache as _dispatch
from . import hierarchical
from .program_issue import issue_serialized as _issue_serialized
from .reduce_ops import ReduceOp, handle_average
from ..utils import envs
from ..utils import logging as hvd_logging

# Program spans (docs/timeline.md). ``plan.*`` are the host-side dispatch
# of a plan's stages (device execution is asynchronous); the chunked
# plan's stages are also the Chrome timeline's PIPELINE_FUSE / _DISPATCH /
# _SPLIT spans on the ``pipeline`` lane. ``collective.<op>`` are the
# eager ops that run without a plan, on their tensor's lane.
_SUBMIT = _timeline.span("collective.submit")
_FUSE = _timeline.span("plan.fuse", lane=_timeline.PIPELINE_LANE)
_WIRE = _timeline.span("plan.wire", lane=_timeline.PIPELINE_LANE)
_SPLIT = _timeline.span("plan.split", lane=_timeline.PIPELINE_LANE)
(_ALLREDUCE, _GROUPED_ALLREDUCE, _ALLGATHER, _BROADCAST, _GROUPED_BROADCAST,
 _ALLTOALL, _REDUCESCATTER) = (
    _timeline.span(f"collective.{op}", op.upper(), lane=op)
    for op in ("allreduce", "grouped_allreduce", "allgather", "broadcast",
               "grouped_broadcast", "alltoall", "reducescatter"))


class PerRank:
    """Bundle of per-rank values: ``array[i]`` is rank *i*'s tensor (ranks
    ordered by position in the process set). The eager-mode analog of "each
    Horovod rank passes its local tensor".

    ``dim0s`` is set when the per-rank tensors have *different first
    dimensions* (the reference's ragged allgather/alltoall contract,
    ``collective_operations.h:143-178``): ``array`` is zero-padded to the
    max dim0 and ``dim0s[i]`` is rank *i*'s valid row count. ``None``
    means uniform."""

    __slots__ = ("array", "dim0s")

    def __init__(self, array, dim0s=None):
        self.array = array
        self.dim0s = tuple(int(d) for d in dim0s) if dim0s is not None \
            else None

    @property
    def shape(self):
        return self.array.shape

    @property
    def dtype(self):
        return self.array.dtype

    def __len__(self):
        return self.array.shape[0]

    def __getitem__(self, i):
        return self.array[i]

    def to_list(self):
        if self.dim0s is not None:
            return [self.array[i, :self.dim0s[i]]
                    for i in range(self.array.shape[0])]
        return [self.array[i] for i in range(self.array.shape[0])]

    def __repr__(self):
        ragged = f", dim0s={self.dim0s}" if self.dim0s is not None else ""
        return (f"PerRank(shape={tuple(self.array.shape)}, "
                f"dtype={self.array.dtype}{ragged})")


def per_rank(values, process_set: ProcessSet | None = None) -> PerRank:
    """Build a :class:`PerRank` bundle from a sequence of per-rank arrays
    (or an array whose leading axis already indexes ranks), sharded one
    slice per chip of the process set. Per-rank arrays whose *first*
    dimensions differ (trailing dims must match) produce a ragged bundle:
    zero-padded to the max first dim with ``dim0s`` recording the valid
    row counts — the input shape for ragged :func:`allgather` /
    :func:`alltoall`."""
    pset = _resolve(process_set)
    n = pset.size()
    dim0s = None
    if isinstance(values, (list, tuple)):
        arrs = [jnp.asarray(v) for v in values]
        if len(arrs) != n:
            raise ValueError(
                f"per_rank got {len(arrs)} arrays for process set size {n}")
        rests = {a.shape[1:] for a in arrs}
        ndims = {a.ndim for a in arrs}
        if len(ndims) > 1 or len(rests) > 1:
            raise ValueError(
                "per_rank arrays must agree on every dimension except the "
                f"first, got shapes {[tuple(a.shape) for a in arrs]}")
        d0s = [a.shape[0] if a.ndim else 1 for a in arrs]
        if arrs[0].ndim and len(set(d0s)) > 1:
            maxd = max(d0s)
            arrs = [jnp.concatenate(
                        [a, jnp.zeros((maxd - a.shape[0],) + a.shape[1:],
                                      a.dtype)]) if a.shape[0] < maxd else a
                    for a in arrs]
            dim0s = d0s
        arr = jnp.stack(arrs)
    else:
        arr = jnp.asarray(values)
    if arr.shape[0] != n:
        raise ValueError(
            f"per_rank leading axis {arr.shape[0]} != process set size {n}")
    sharding = NamedSharding(pset.mesh(), P(runtime.axis_name()))
    return PerRank(jax.device_put(arr, sharding), dim0s)


# ---------------------------------------------------------------------------
# mode detection
# ---------------------------------------------------------------------------

def _contains_tracer(x) -> bool:
    if isinstance(x, PerRank):
        x = x.array
    return isinstance(x, _Tracer)


def _axis_is_bound(axis) -> bool:
    """True when `axis` is a bound mapped axis (we are inside shard_map/pmap
    traced code). Outside any such context ``lax.axis_index`` raises
    NameError."""
    try:
        lax.axis_index(axis)
        return True
    except NameError:
        return False
    except TypeError:
        return False


def _resolve_axis(axis_name):
    return runtime.axis_name() if axis_name is None else axis_name


# ---------------------------------------------------------------------------
# traced-mode primitives (shared by eager inners, which run traced under
# shard_map over the sub-mesh with groups=None)
# ---------------------------------------------------------------------------

def _reduce(x, axis, op: ReduceOp, groups):
    if op == ReduceOp.SUM:
        return lax.psum(x, axis, axis_index_groups=groups)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis, axis_index_groups=groups)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis, axis_index_groups=groups)
    if op == ReduceOp.PRODUCT:
        if groups is not None:
            # ring reduce-scatter + ring allgather over the member chips:
            # 2(k-1)/k·|x| per member, the allreduce bandwidth optimum,
            # matching the subset allgather/alltoall rings (r3 VERDICT
            # weak #7 replaced the k·|x| gather-then-multiply). Non-members
            # keep their own value (singleton-group semantics).
            members = list(groups[0])
            prod = _product_ring(x, axis, members)
            member = jnp.isin(lax.axis_index(axis), jnp.array(members))
            return jnp.where(member, prod, x)
        g = lax.all_gather(x, axis)
        return jnp.prod(g, axis=0)
    if op == ReduceOp.ADASUM:
        from .adasum import adasum_reduce
        return adasum_reduce(x, axis, groups)
    raise ValueError(f"unsupported reduce op {op!r}")


def _product_ring(x, axis, ranks):
    """Bandwidth-optimal PRODUCT allreduce over the member chips of a
    process set: classic ring reduce-scatter (k-1 multiply-forward steps
    on 1/k-size chunks) followed by a ring allgather of the reduced
    chunks — 2(k-1)/k·|x| per member for any k (XLA has no product
    allreduce primitive, so the schedule is explicit like the file's
    other member rings). Non-member lanes compute garbage that the caller
    masks out."""
    k = len(ranks)
    if k == 1:
        return x
    orig_dtype = x.dtype
    xv = x.astype(jnp.int8) if orig_dtype == jnp.bool_ else x
    shape = xv.shape
    flat = xv.reshape(-1)
    n = flat.shape[0]
    chunk = -(-n // k)  # ceil
    flat = jnp.pad(flat, (0, k * chunk - n),
                   constant_values=jnp.ones((), xv.dtype))  # prod identity
    pos = _member_pos(axis, ranks)
    perm = [(ranks[i], ranks[(i + 1) % k]) for i in range(k)]

    def chunk_at(idx):
        return lax.dynamic_slice_in_dim(flat, (idx % k) * chunk, chunk)

    # reduce-scatter: after step s each member's carry holds the partial
    # product of chunk (pos - s - 1); after k-1 steps member p owns the
    # fully reduced chunk (p + 1) % k
    cur = chunk_at(pos)
    for s in range(k - 1):
        cur = lax.ppermute(cur, axis, perm) * chunk_at(pos - s - 1)

    # allgather the reduced chunks around the same ring
    out = jnp.zeros((k * chunk,), xv.dtype)
    own_idx = (pos + 1) % k
    out = lax.dynamic_update_slice_in_dim(out, cur, own_idx * chunk, 0)
    rolling = cur
    for s in range(1, k):
        rolling = lax.ppermute(rolling, axis, perm)
        src_idx = (pos - s + 1) % k
        out = lax.dynamic_update_slice_in_dim(out, rolling,
                                              src_idx * chunk, 0)
    out = out[:n].reshape(shape)
    return out.astype(orig_dtype)


def _axis_denominator(x, axis, groups):
    """Number of participants in this member's reduction group: the bound
    axis size (NOT the world size — the user may reduce over a sub-axis of
    a multi-dim mesh), or the group size under a process-set partition."""
    return lax.psum(jnp.ones((), jnp.float32), axis, axis_index_groups=groups)


def _allreduce_traced(x, axis, op, pre, post, groups):
    if pre != 1.0:
        x = x * pre
    if op == ReduceOp.AVERAGE:
        out = lax.psum(x, axis, axis_index_groups=groups)
        out = out / _axis_denominator(x, axis, groups).astype(out.dtype)
    else:
        out = _reduce(x, axis, op, groups)
    if post != 1.0:
        out = out * post
    return out


def _allgather_traced(x, axis, groups, ranks, pset_size):
    if groups is None:
        return lax.all_gather(x, axis, tiled=True)
    # Subset gather as a ring of ppermutes over the member chips only
    # (lax.all_gather requires equal-size axis_index_groups, which a
    # members+singletons partition is not). Each member moves (k-1)*|x|
    # over the ring — the bandwidth-optimal allgather schedule — and
    # non-members move nothing, vs the O(world*k*|x|) zero-padded psum
    # this replaces (r2 VERDICT weak #4).
    k = pset_size
    pos = _member_pos(axis, ranks)  # my slot in the set
    d0 = x.shape[0]
    orig_dtype = x.dtype
    if orig_dtype == jnp.bool_:
        x = x.astype(jnp.int8)
    out = jnp.zeros((k * d0,) + x.shape[1:], dtype=x.dtype)
    out = lax.dynamic_update_slice(
        out, x, (pos * d0,) + (0,) * (x.ndim - 1))
    perm = [(ranks[i], ranks[(i + 1) % k]) for i in range(k)]
    cur = x
    for step in range(1, k):
        cur = lax.ppermute(cur, axis, perm)
        src_pos = (pos - step) % k
        out = lax.dynamic_update_slice(
            out, cur, (src_pos * d0,) + (0,) * (x.ndim - 1))
    return out.astype(orig_dtype)


def _broadcast_traced(x, axis, root_rank, groups, ranks):
    idx = lax.axis_index(axis)
    orig_dtype = x.dtype
    xv = x.astype(jnp.int8) if orig_dtype == jnp.bool_ else x
    masked = jnp.where(idx == root_rank, xv, jnp.zeros_like(xv))
    out = lax.psum(masked, axis, axis_index_groups=groups)
    if groups is not None:
        member = jnp.isin(idx, jnp.array(ranks))
        out = jnp.where(member, out, xv)
    if orig_dtype == jnp.bool_:
        out = out.astype(jnp.bool_)
    return out


def _member_pos(axis, ranks):
    """This chip's position within the sorted member list (garbage for
    non-members — their lanes are excluded from the member perms)."""
    idx = lax.axis_index(axis)
    return jnp.sum((jnp.array(ranks) < idx).astype(jnp.int32))


def _alltoall_traced(x, axis, groups):
    if groups is None:
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    # Subset alltoall as k-1 chunk rotations over the member ring
    # (lax.all_to_all needs the whole axis). Chunk j of member i travels
    # j-i hops forward; bandwidth (k-1)/k·|x| per member, the alltoall
    # optimum. Non-member lanes produce garbage (never consumed).
    ranks = list(groups[0])
    k = len(ranks)
    if x.shape[0] % k:
        raise ValueError(
            f"alltoall dim0 ({x.shape[0]}) must divide by the process-set "
            f"size ({k})")
    chunk = x.shape[0] // k
    pos = _member_pos(axis, ranks)
    out = jnp.zeros_like(x)
    own = lax.dynamic_slice_in_dim(x, pos * chunk, chunk)
    out = lax.dynamic_update_slice_in_dim(out, own, pos * chunk, 0)
    for r in range(1, k):
        # rotation r: my chunk for member (pos+r) travels r hops forward
        perm = [(ranks[i], ranks[(i + r) % k]) for i in range(k)]
        dest = (pos + r) % k
        send = lax.dynamic_slice_in_dim(x, dest * chunk, chunk)
        recv = lax.ppermute(send, axis, perm)
        src = (pos - r) % k
        out = lax.dynamic_update_slice_in_dim(out, recv, src * chunk, 0)
    return out


def _reducescatter_traced(x, axis, op, post, groups):
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise NotImplementedError("reducescatter supports SUM/AVERAGE")
    if groups is None:
        out = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
        if op == ReduceOp.AVERAGE:
            out = out / _axis_denominator(x, axis, groups).astype(out.dtype)
        if post != 1.0:
            out = out * post
        return out
    # Subset reduce-scatter as a k-1 step accumulate ring over the member
    # list: member p ends holding chunk p fully reduced, each chunk
    # visiting every member once ((k-1)/k·|x| per member — optimal).
    ranks = list(groups[0])
    k = len(ranks)
    if x.shape[0] % k:
        raise ValueError(
            f"reducescatter dim0 ({x.shape[0]}) must divide by the "
            f"process-set size ({k})")
    chunk = x.shape[0] // k
    pos = _member_pos(axis, ranks)
    # accumulate in the native dtype like the global psum_scatter path
    # (int sums stay exact; AVERAGE on ints is rejected upstream)
    perm = [(ranks[i], ranks[(i + 1) % k]) for i in range(k)]
    acc = lax.dynamic_slice_in_dim(x, ((pos - 1) % k) * chunk, chunk)
    for t in range(k - 1):
        recv = lax.ppermute(acc, axis, perm)
        idx = (pos - t - 2) % k
        acc = recv + lax.dynamic_slice_in_dim(x, idx * chunk, chunk)
    if op == ReduceOp.AVERAGE:
        acc = acc / jnp.asarray(k, acc.dtype)
    if post != 1.0:
        acc = acc * post
    return acc.astype(x.dtype)


# ---------------------------------------------------------------------------
# eager machinery: cached jitted shard_maps over the process-set sub-mesh
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _eager_allreduce_fn(mesh: Mesh, axis: str, op: ReduceOp, pre: float,
                        post: float, bundled: bool = True,
                        row0: bool = False):
    """``bundled``: x is a (n, ...) per-rank bundle, one row per chip.
    Replicated (``bundled=False``): x is the raw array every rank
    contributes identically — ``in_specs=P()`` lets shard_map replicate it
    without the ``broadcast_to`` + device transfer a bundle would cost.
    ``row0`` (dispatch plans): return the replicated result row directly
    (``out_specs=P()``) so the caller needs no eager ``[0]`` slice — a
    cross-device gather — per call."""
    def inner(x):
        out = _allreduce_traced(x, axis, op, pre, post, None)
        return out[0] if (bundled and row0) else out
    in_spec = P(axis) if bundled else P()
    out_spec = P() if (row0 or not bundled) else P(axis)
    return _issue_serialized(jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
        check_vma=False)))


def _grouped_allreduce_smap(mesh: Mesh, axis: str, op: ReduceOp, pre: float,
                            post: float, num_bufs: int, bundled: bool):
    """Raw shard-mapped fused reduction (not jitted) — composed into the
    jitted wire programs below and into dispatch-plan programs that fold
    the wire-buffer split into the same compiled call."""
    def inner(*xs):
        return tuple(_allreduce_traced(x, axis, op, pre, post, None) for x in xs)
    spec = P(axis) if bundled else P()
    specs = tuple(spec for _ in range(num_bufs))
    return jax.shard_map(inner, mesh=mesh, in_specs=specs, out_specs=specs,
                         check_vma=False)


@functools.lru_cache(maxsize=None)
def _eager_grouped_allreduce_fn(mesh: Mesh, axis: str, op: ReduceOp, pre: float,
                                post: float, num_bufs: int,
                                bundled: bool = True,
                                donate: tuple = ()):
    """Fused wire-buffer program. ``donate`` marks which fused inputs are
    dispatcher-owned temporaries (never user arrays) — those buffers are
    donated so the reduction reuses their HBM instead of holding input and
    output live simultaneously."""
    return _issue_serialized(jax.jit(
        _grouped_allreduce_smap(mesh, axis, op, pre, post, num_bufs, bundled),
        donate_argnums=tuple(i for i, d in enumerate(donate) if d)))


@functools.lru_cache(maxsize=None)
def _eager_allgather_fn(mesh: Mesh, axis: str, bundled: bool = True):
    if bundled:
        def inner(x):  # (1, d0, ...) -> (n*d0, ...) replicated
            return lax.all_gather(x[0], axis, tiled=True)
        in_spec = P(axis)
    else:
        def inner(x):  # replicated (d0, ...) -> (n*d0, ...)
            return lax.all_gather(x, axis, tiled=True)
        in_spec = P()
    return _issue_serialized(jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=in_spec, out_specs=P(), check_vma=False)))


@functools.lru_cache(maxsize=None)
def _eager_broadcast_fn(mesh: Mesh, axis: str, root_pos: int,
                        bundled: bool = True):
    def inner(x):  # -> (...) replicated
        return _broadcast_traced(x[0] if bundled else x, axis, root_pos,
                                 None, None)
    return _issue_serialized(jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=P(axis) if bundled else P(),
        out_specs=P(), check_vma=False)))


def _grouped_broadcast_smap(mesh: Mesh, axis: str, root_pos: int,
                            num_bufs: int, bundled: bool):
    def inner(*xs):
        return tuple(_broadcast_traced(x[0] if bundled else x, axis,
                                       root_pos, None, None)
                     for x in xs)
    spec = P(axis) if bundled else P()
    specs = tuple(spec for _ in range(num_bufs))
    return jax.shard_map(inner, mesh=mesh, in_specs=specs,
                         out_specs=tuple(P() for _ in specs),
                         check_vma=False)


@functools.lru_cache(maxsize=None)
def _eager_grouped_broadcast_fn(mesh: Mesh, axis: str, root_pos: int,
                                num_bufs: int, bundled: bool = True,
                                donate: tuple = ()):
    return _issue_serialized(jax.jit(
        _grouped_broadcast_smap(mesh, axis, root_pos, num_bufs, bundled),
        donate_argnums=tuple(i for i, d in enumerate(donate) if d)))


def _wire_dtype_of(t, compression):
    """The dtype a tensor travels the wire in: its own dtype, or the
    compressor's wire dtype for floating tensors routed through
    ``Compression.bf16``/``fp16`` (integers pass through uncompressed,
    matching ``_CastCompressor.compress``)."""
    dt = jnp.result_type(t.array if isinstance(t, PerRank) else t)
    wire = getattr(compression, "wire_dtype", None)
    if wire is not None and jnp.issubdtype(dt, jnp.floating):
        return jnp.dtype(wire)
    return jnp.dtype(dt)


def _fusion_buckets(tensors, threshold: int, elem_count, dtype_of=None):
    """THE fusion bucketing rule, shared by the eager wire buffers and the
    opt-in traced fusion: group indices by dtype, then split each group
    into buckets whose total bytes stay <= ``threshold`` (a single
    oversized tensor gets its own bucket). ``elem_count(t)`` gives the
    per-rank element count of one tensor. Buckets are keyed by the WIRE
    dtype — ``dtype_of(i)`` when given (tensors routed through
    ``Compression.bf16``/``fp16`` fuse together instead of fragmenting
    into per-source-dtype buckets), else the tensor's own dtype. Yields
    (dtype, [indices])."""
    by_dtype: dict = {}
    for i, t in enumerate(tensors):
        dt = jnp.dtype(dtype_of(i)) if dtype_of is not None \
            else jnp.dtype(jnp.result_type(t))
        by_dtype.setdefault(dt, []).append(i)
    for dt, idxs in by_dtype.items():
        itemsize = jnp.dtype(dt).itemsize
        bucket: list = []
        bucket_bytes = 0
        for i in idxs:
            nbytes = elem_count(tensors[i]) * itemsize
            if bucket and bucket_bytes + nbytes > threshold:
                yield dt, bucket
                bucket, bucket_bytes = [], 0
            bucket.append(i)
            bucket_bytes += nbytes
        if bucket:
            yield dt, bucket


def _fuse_by_dtype(bundles: list, n: int, wire_dtypes=None):
    """Pack (n, ...) bundles into flat (n, total) wire buffers per WIRE
    dtype (the XLA analog of the reference's fusion buffer,
    ``fusion_buffer_manager.h:30-50``), each bucket capped at the fusion
    threshold (``HVD_FUSION_THRESHOLD``; reference default 128 MB,
    ``operations.cc:491-496`` — the autotuner tunes this knob at runtime).
    ``wire_dtypes[i]`` (compression routing) keys the buckets and casts on
    pack; :func:`_split_fused` casts back to each tensor's source dtype
    after the split. Returns (fused_inputs, metas)."""
    fused_inputs, metas = [], []
    wire_of = (lambda i: wire_dtypes[i]) if wire_dtypes is not None else None
    for dt, bidxs in _fusion_buckets(
            bundles, envs.fusion_threshold_bytes(),
            lambda b: int(np.prod(b.shape[1:]) or 1), dtype_of=wire_of):
        flat = [(bundles[i] if bundles[i].dtype == dt
                 else bundles[i].astype(dt)).reshape(n, -1) for i in bidxs]
        fused_inputs.append(jnp.concatenate(flat, axis=1))
        metas.append((dt, bidxs, [bundles[i].shape[1:] for i in bidxs],
                      [jnp.dtype(bundles[i].dtype) for i in bidxs]))
    return fused_inputs, metas


def _fusion_metas(per_shapes, src_dtypes, wire_dtypes):
    """Bucket layout (metas) from shapes/dtypes alone — the pure-metadata
    twin of :func:`_fuse_by_dtype` (and of the replicated-strategy fuse
    closure in :func:`_plan_fused_programs`) for plan builders,
    which only need the layout: materializing throwaway device bundles
    just to read it back would cost an O(payload) allocation per plan
    build (and plans rebuild on every autotune epoch flush)."""
    idxs = list(range(len(per_shapes)))
    metas = []
    for dt, bidxs in _fusion_buckets(
            idxs, envs.fusion_threshold_bytes(),
            lambda i: int(np.prod(per_shapes[i]) or 1),
            dtype_of=lambda i: wire_dtypes[i]):
        metas.append((dt, bidxs, [tuple(per_shapes[i]) for i in bidxs],
                      [jnp.dtype(src_dtypes[i]) for i in bidxs]))
    return metas


def _split_fused(fused_outputs, metas, count: int) -> list:
    """Inverse of :func:`_fuse_by_dtype` on flat per-dtype result vectors
    (decompressing — casting back to the source dtype — any tensor that
    traveled in a different wire dtype)."""
    results: list = [None] * count
    for vec, (dt, idxs, shapes, srcs) in zip(fused_outputs, metas):
        offset = 0
        for i, shp, src in zip(idxs, shapes, srcs):
            sz = int(np.prod(shp)) if shp else 1
            piece = vec[offset:offset + sz].reshape(shp)
            results[i] = piece if src == dt else piece.astype(src)
            offset += sz
    return results


@functools.lru_cache(maxsize=None)
def _eager_alltoall_fn(mesh: Mesh, axis: str):
    def inner(x):  # (1, s, ...) -> (s, ...) per-rank
        return _alltoall_traced(x[0], axis, None)
    return _issue_serialized(jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False)))


@functools.lru_cache(maxsize=None)
def _eager_uneven_alltoall_fn(mesh: Mesh, axis: str):
    """Padded uneven alltoall: each rank gathers its per-destination chunks
    (host-precomputed indices), zero-pads them to the global max chunk, and
    exchanges them with one ``lax.all_to_all``; the ragged valid parts are
    sliced back out by the caller (the reference's MPI_Alltoallv becomes
    pad + all_to_all + slice under XLA's static shapes)."""

    def inner(x, idx, mask):
        # x: (1, d0, ...); idx/mask: (1, n, max_chunk)
        sel = x[0][idx[0]]  # (n, max_chunk, ...) chunk for each destination
        m = mask[0].reshape(mask.shape[1:] + (1,) * (sel.ndim - 2))
        sel = jnp.where(m, sel, jnp.zeros((), sel.dtype))
        # recv[j] = the chunk rank j addressed to me
        return lax.all_to_all(sel, axis, split_axis=0, concat_axis=0,
                              tiled=True)

    return _issue_serialized(jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis), check_vma=False)))


@functools.lru_cache(maxsize=None)
def _eager_reducescatter_fn(mesh: Mesh, axis: str, op: ReduceOp, post: float):
    def inner(x):  # (1, d0, ...) -> (d0/n, ...) per-rank
        return _reducescatter_traced(x[0], axis, op, post, None)
    return _issue_serialized(jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False)))


def _as_bundle(tensor, pset: ProcessSet, allow_ragged: bool = False):
    """Canonicalize eager input to a (pset.size, ...) bundle array.

    Returns (bundle, was_bundled). Ragged bundles (``PerRank.dim0s`` set)
    are rejected unless the op supports per-rank first dims — otherwise
    the zero padding would silently enter the reduction/exchange."""
    n = pset.size()
    if isinstance(tensor, PerRank):
        if tensor.dim0s is not None and not allow_ragged:
            raise ValueError(
                "this collective requires uniform per-rank shapes; got a "
                f"ragged per_rank bundle with first dims {tensor.dim0s} "
                "(ragged first dims are supported by allgather and uneven "
                "alltoall only, matching the reference's contract)")
        arr = tensor.array
        if arr.shape[0] != n:
            raise ValueError(
                f"PerRank bundle leading axis {arr.shape[0]} != process set size {n}")
        return arr, True
    arr = jnp.asarray(tensor)
    return jnp.broadcast_to(arr[None], (n,) + arr.shape), False


def _member_process_view(pset: ProcessSet):
    """(member_procs, one_to_one, my_pos): the process-level view of a
    process set's chip ranks. ``one_to_one`` when the set's chips map 1:1
    onto its member processes (engine world == set positions — devices are
    rank-ordered process-major); ``my_pos`` is this process's position
    among the members, -1 when not 1:1 or not a member."""
    member_procs = sorted({runtime.process_of_rank(r) for r in pset.ranks})
    one_to_one = (len(member_procs) == len(pset.ranks)
                  and runtime.process_rank() in member_procs)
    my_pos = member_procs.index(runtime.process_rank()) if one_to_one else -1
    return member_procs, one_to_one, my_pos


def _i64_digest(values) -> int:
    """Stable non-zero crc32 digest of an int sequence (cross-process
    validation of size metadata every member must agree on)."""
    import zlib
    return zlib.crc32(np.ascontiguousarray(
        np.asarray(values, np.int64)).tobytes()) & 0x7FFFFFFF or 1


def _gspmd_passthrough_check(op: ReduceOp, name: str) -> None:
    """Inside plain jit/pjit only AVERAGE is the identity: gradients of a
    globally-sharded computation are already globally *averaged* by the
    partitioner (a mean loss over the global batch). SUM would differ from
    the local value by a factor of size() and anything else has no GSPMD
    meaning — both must run under shard_map where the semantics are
    explicit."""
    if op != ReduceOp.AVERAGE:
        raise RuntimeError(
            f"{name}(op={op.name}) was called inside jit/pjit without a "
            "bound mesh axis; only AVERAGE (gradient reduction) is an "
            "identity under GSPMD. Run it under jax.shard_map over "
            "hvd.mesh() so the op lowers to an explicit XLA collective.")
    hvd_logging.debug(
        "%s inside jit/pjit without a bound axis: GSPMD passthrough "
        "(gradients are already globally reduced by the partitioner)", name)
    # Trace-time tally: every sync the partitioner absorbed is a sync the
    # cached-program fast path (ops/gspmd_cache.py) never pays again on
    # replay. Function-level import — gspmd_cache imports this module's
    # siblings.
    from . import gspmd_cache
    gspmd_cache.note_passthrough()


def _check_op_dtype(op: ReduceOp, dtype):
    if op == ReduceOp.AVERAGE and jnp.issubdtype(dtype, jnp.integer):
        raise TypeError(
            "ReduceOp.AVERAGE is not supported for integer tensors "
            "(matches the reference's restriction); use SUM.")


# ---------------------------------------------------------------------------
# multi-process eager negotiation (dynamic engine gate)
# ---------------------------------------------------------------------------

import itertools as _itertools

# Stable dtype ids for cross-process metadata agreement checks (only
# equality matters; the table must be identical on every process).
_DTYPE_IDS = {name: i for i, name in enumerate((
    "bool", "int8", "uint8", "int16", "uint16", "int32", "uint32", "int64",
    "uint64", "float16", "bfloat16", "float32", "float64", "complex64",
    "complex128"))}


def _dtype_id(dt) -> int:
    known = _DTYPE_IDS.get(dt.name)
    if known is not None:
        return known
    # Unlisted dtypes (fp8 variants etc.) get a deterministic id derived
    # from the name — crc32 is stable across processes, unlike hash().
    import zlib
    return 0x4000_0000 | (zlib.crc32(dt.name.encode()) & 0x3FFF_FFFF)


_auto_counters: dict = {}


def _auto_counter_table() -> dict:
    """Auto-name counters for this thread's world: loopback rank threads
    each advance their OWN counters (the per-process contract — a shared
    table would let one rank's traffic desynchronize every rank's
    negotiation names)."""
    from ..loopback import context as _lbctx
    ctx = _lbctx.current()
    return ctx.auto_counters if ctx is not None else _auto_counters


def _reset_auto_counters() -> None:
    """World reset (engine_service.reset_service): names restart from
    zero in this thread's world."""
    _auto_counter_table().clear()


def _auto_name(kind: str, pset: ProcessSet) -> str:
    """Deterministic per-(kind, set) auto names. Counters are keyed by the
    set so processes outside a subset (which never see its ops) don't fall
    behind on a shared counter — a shared one would desynchronize the names
    of later *global* ops across processes."""
    from .. import engine_service
    key = (kind, engine_service._set_key(pset))
    counter = _auto_counter_table().setdefault(key, _itertools.count())
    n = next(counter)
    if key[1] == "0":
        return f"{kind}.{n}"
    return f"{kind}.ps{key[1]}.{n}"


def _negotiate_eager(kind: str, request_type: int, name: str | None,
                     shape, dtype, pset: ProcessSet,
                     root_rank: int = -1, splits=(), reduce_op: int = -1,
                     prescale: float = 1.0, postscale: float = 1.0,
                     splits_crc: int = 0):
    """Gate a multi-process eager collective through the dynamic engine
    (no-op for single-process jobs). Guarantees identical per-set op order
    and turns metadata disagreements into informative errors instead of
    hangs/corrupt reductions (the reference's negotiation role,
    ``controller.cc:73-430``). Returns the negotiated Response (None when
    no service runs) — uneven alltoall reads ``recv_splits`` off it.

    Each process set negotiates through its own service spanning only its
    member processes (the reference's per-ProcessSet controller,
    ``process_set.h:26-84``), so non-members legally never submitting a
    subset op is not reported as a stall.

    Returns ``(response, negotiated name)`` — ``(None, None)`` when no
    service runs. The name keys the loopback execution rendezvous
    (``loopback/dispatch.py``): it is the one token guaranteed unique
    while in flight AND identical across every member.
    """
    from .. import engine_service
    svc = engine_service.get_service(pset)
    if svc is None:
        return None, None
    neg_name = name or _auto_name(kind, pset)
    dt = jnp.dtype(dtype)
    return svc.negotiate(neg_name, request_type,
                         dtype=_dtype_id(dt),
                         element_size=dt.itemsize, shape=tuple(shape),
                         root_rank=root_rank, splits=splits,
                         reduce_op=reduce_op, prescale=prescale,
                         postscale=postscale,
                         splits_crc=splits_crc), neg_name


def _request_dict(name: str, request_type: int, shape, dtype,
                  group_id: int = -1, **meta) -> dict:
    """ONE negotiation request in the engine's wire format — the single
    source of truth shared by the sync path, the dispatch plans, and the
    fusion-cycle queue (the engine cross-validates these fields across
    processes, so every emitter must agree byte-for-byte)."""
    dt = jnp.dtype(dtype)
    return dict(name=name, request_type=request_type, dtype=_dtype_id(dt),
                element_size=dt.itemsize,
                shape=tuple(int(d) for d in shape), group_id=group_id,
                **meta)


def _group_requests(base: str, request_type: int, shapes_dtypes,
                    **meta) -> list[dict]:
    """The grouped negotiation payload: per-tensor requests named
    ``{base}.{i}`` sharing a group id derived from the base (identical on
    every process), which lets a joined rank reconstruct the group
    boundary from the response stream (``_execute_joined_zeros``) and the
    engine enforce joint fusion."""
    import zlib
    gid = zlib.crc32(base.encode()) & 0x7FFFFFFF
    return [_request_dict(f"{base}.{i}", request_type, shape, dtype,
                          group_id=gid, **meta)
            for i, (shape, dtype) in enumerate(shapes_dtypes)]


def _negotiate_eager_group(kind: str, request_type: int, name: str | None,
                           shapes_dtypes, pset: ProcessSet,
                           **meta) -> list | None:
    """Batch variant for grouped ops: all members land in one cycle.
    Returns the negotiated member names (``base.i``), or None when no
    service runs — the first name keys the loopback rendezvous."""
    from .. import engine_service
    svc = engine_service.get_service(pset)
    if svc is None:
        return None
    reqs = _group_requests(name or _auto_name(kind, pset),
                           request_type, shapes_dtypes, **meta)
    svc.negotiate_many(reqs)
    return [r["name"] for r in reqs]


# ---------------------------------------------------------------------------
# dispatch plans: steady-state eager fast path (see ops/dispatch_cache.py)
# ---------------------------------------------------------------------------

def _plan_sig(t):
    """Cache-key signature of one eager input: ("b", bundle shape, dtype)
    for uniform PerRank bundles, ("r", shape, dtype) for raw arrays every
    rank contributes identically. None = not plan-cacheable (ragged
    bundles, python scalars/lists — those keep the generic path)."""
    if isinstance(t, PerRank):
        if t.dim0s is not None:
            return None
        a = t.array
        return ("b", tuple(a.shape), jnp.dtype(a.dtype).name)
    shape = getattr(t, "shape", None)
    dtype = getattr(t, "dtype", None)
    if shape is None or dtype is None:
        return None
    try:
        return ("r", tuple(shape), jnp.dtype(dtype).name)
    except TypeError:
        return None


def _check_bundle_axis(sig, pset: ProcessSet) -> None:
    """Plan-path twin of ``_as_bundle``'s leading-axis validation: a
    PerRank bundle whose leading axis is not the process-set size must
    raise the clear error, never silently drop/misroute rows (plans are
    keyed by the bundle shape, so one check at build time covers every
    hit)."""
    if sig[0] == "b" and sig[1][0] != pset.size():
        raise ValueError(
            f"PerRank bundle leading axis {sig[1][0]} != process set "
            f"size {pset.size()}")


def _plan_negotiation(kind: str, request_type: int, name: str | None,
                      shape, dtype, pset: ProcessSet, **meta):
    """Pinned negotiation decision for a plan: None when no service applies
    (the per-call ``get_service`` + auto-name round is skipped on every
    hit), else a closure re-negotiating the SAME tensor name with the same
    precomputed metadata — which the native engine serves from its response
    cache via the bitvector AND (the reference ``ComputeResponseList`` HIT
    path) instead of a full metadata exchange."""
    from .. import engine_service
    if engine_service.get_service(pset) is None:
        return None
    neg_name = name or _auto_name(kind, pset)
    dt = jnp.dtype(dtype)
    kwargs = dict(dtype=_dtype_id(dt), element_size=dt.itemsize,
                  shape=tuple(int(d) for d in shape), **meta)

    def negotiate():
        # Re-resolve the service per call instead of pinning the build-time
        # object: an elastic re-form rebuilds services, and lazy resolution
        # is what lets a warm-grafted plan (docs/elastic.md) negotiate
        # against the NEW world. Table-hit resolution costs ~1us against a
        # millisecond-scale KV round.
        svc = engine_service.get_service(pset)
        if svc is None:
            raise RuntimeError(
                f"negotiation service gone for plan {neg_name!r} (world "
                "reset mid-call?); re-issue the collective")
        resp = svc.negotiate(neg_name, request_type, **kwargs)
        if resp is not None and resp.from_cache:
            _dispatch.note_negotiation_skip()
        return resp

    negotiate.neg_name = neg_name  # loopback rendezvous key (per plan)
    return negotiate


def _plan_group_negotiation(kind: str, request_type: int, name: str | None,
                            shapes_dtypes, pset: ProcessSet, **meta):
    """Grouped twin of :func:`_plan_negotiation`: the request batch is
    assembled once and replayed with stable names on every hit."""
    from .. import engine_service
    if engine_service.get_service(pset) is None:
        return None
    reqs = _group_requests(name or _auto_name(kind, pset), request_type,
                           shapes_dtypes, **meta)

    def negotiate():
        # lazy per-call resolution — see _plan_negotiation
        svc = engine_service.get_service(pset)
        if svc is None:
            raise RuntimeError(
                "negotiation service gone for grouped plan "
                f"{reqs[0]['name'] if reqs else '?'!r} (world reset "
                "mid-call?); re-issue the collective")
        resps = svc.negotiate_many(reqs)
        if resps and all(r.from_cache for r in resps):
            _dispatch.note_negotiation_skip()
        return resps

    negotiate.neg_name = reqs[0]["name"] if reqs else None
    return negotiate


def _bundle_of(t, shape, n: int):
    """Per-call canonicalization for the bundle strategy: PerRank arrays
    pass through; raw arrays are expanded to the (n, ...) bundle (only the
    mixed PerRank+raw grouped case still pays this — all-raw groups use the
    replicated strategy with no expansion at all)."""
    if isinstance(t, PerRank):
        return t.array
    return jnp.broadcast_to(jnp.asarray(t)[None], (n,) + shape)


def _grouped_donate_mask(metas, alias_risk) -> tuple:
    """Which fused wire buffers are safe to donate. A fused buffer is a
    dispatcher-owned temporary (concatenate/reshape output) EXCEPT when its
    bucket has a single member whose flatten is a no-op — jnp's reshape and
    single-array concatenate fast paths then hand back the caller's own
    array object, which must never be donated. ``alias_risk(i)`` says
    whether member ``i``'s flatten can no-op onto a user-held array; a
    wire-dtype cast (source dtype != bucket dtype) always produces a fresh
    dispatcher-owned array, so those buckets stay donatable."""
    return tuple(
        not (len(bidxs) == 1 and srcs[0] == dt and alias_risk(bidxs[0]))
        for (dt, bidxs, _shapes, srcs) in metas)


def _sig_donate_mask(metas, sigs, bundled: bool) -> tuple:
    """Donate mask from plan signatures — THE alias-risk rule in one
    place, shared by the per-flush plan builders and the step-capture
    whole-step programs (drift here would re-introduce the donation
    aliasing bug on exactly one of the two paths)."""
    if bundled:
        return _grouped_donate_mask(
            metas, lambda i: sigs[i][0] == "b" and len(sigs[i][1]) == 2)
    return _grouped_donate_mask(metas, lambda i: len(sigs[i][1]) == 1)


def _fuse_closure(metas, n: int, bundled: bool):
    """Shared fuse body (list of canonicalized inputs -> per-dtype wire
    buffers), traced inside the per-flush plan programs AND the
    step-capture whole-step programs — one definition, so wire
    packaging can never drift between the two paths."""
    if bundled:
        def fuse(arrs):
            return [jnp.concatenate([arrs[i].astype(dt).reshape(n, -1)
                                     for i in bidxs], axis=1)
                    for (dt, bidxs, _s, _src) in metas]
    else:
        def fuse(arrs):
            return [jnp.concatenate([arrs[i].astype(dt).reshape(-1)
                                     for i in bidxs])
                    if len(bidxs) > 1
                    else arrs[bidxs[0]].astype(dt).reshape(-1)
                    for (dt, bidxs, _s, _src) in metas]
    return fuse


def _canon_closure(shapes, n: int, bundled: bool):
    """Shared input canonicalizer (user tensors -> fuse-program inputs):
    PerRank bundles pass through / raw arrays expand under the bundle
    strategy; everything to jnp arrays under the replicated strategy."""
    if bundled:
        def canon(ts):
            return [_bundle_of(t, shp, n) for t, shp in zip(ts, shapes)]
    else:
        def canon(ts):
            return [jnp.asarray(t) for t in ts]
    return canon


def _build_allreduce_plan(sig, pset: ProcessSet, axis, op: ReduceOp,
                          pre_f: float, post_f: float, name: str | None):
    _check_bundle_axis(sig, pset)
    lowered_op, post = handle_average(op, pset.size(), post_f)
    pre, post = float(pre_f), float(post)
    bundled = sig[0] == "b"
    per_shape = sig[1][1:] if bundled else sig[1]
    dtype = jnp.dtype(sig[2])
    negotiate = _plan_negotiation(
        "allreduce", REQ_ALLREDUCE, name, per_shape, dtype, pset,
        reduce_op=int(lowered_op), prescale=pre, postscale=post)
    nbytes = int(np.prod(per_shape) or 1) * dtype.itemsize
    if negotiate is not None:
        # Multi-process job: compose EXACTLY like the joined-rank zero
        # reconstruction (``_execute_joined_zeros``: wire-dtype (n, ...)
        # bundle through ``_execute_allreduce_bundle``) so active and
        # joined processes lower identical multiprocess computations —
        # the ROADMAP open item on plan-path/join alignment. The row-0
        # program variant and the chunk pipeline stay single-controller
        # optimizations: a joined rank cannot reconstruct them from
        # response metadata.
        lb_key = negotiate.neg_name

        def execute(t):
            bundle, _ = _as_bundle(t, pset)
            return _execute_allreduce_bundle(bundle, pset, axis,
                                             lowered_op, pre, post,
                                             lb_key=lb_key)
        return _dispatch.DispatchPlan(name or "allreduce", "ALLREDUCE",
                                      nbytes, negotiate, execute)
    if (lowered_op == ReduceOp.SUM
            and hierarchical.hierarchical_enabled_for(pset)):
        fn = hierarchical._eager_hier_allreduce_fn(
            hierarchical.hierarchical_mesh(), lowered_op, pre, post,
            bundled, row0=bundled)
    else:
        fn = _eager_allreduce_fn(pset.mesh(), axis, lowered_op, pre, post,
                                 bundled, row0=bundled)
    if bundled:
        def execute(t):  # row0 program: replicated result, no eager slice
            return fn(t.array)
    else:
        def execute(t):
            return fn(jnp.asarray(t))
    return _dispatch.DispatchPlan(name or "allreduce", "ALLREDUCE", nbytes,
                                  negotiate, execute)


def _plan_fused_programs(metas, smap, n: int, count: int, bundled: bool,
                         donate: tuple, row0: bool):
    """The plan's two compiled stages. Stage 1 (``fuse``) canonicalizes
    user tensors into the per-dtype wire buffers in ONE program (the eager
    reshape+concatenate op storm this replaces dominated steady-state
    dispatch). Stage 2 (``wire``) runs the shard-mapped collective AND the
    wire-buffer split in one program, with the wire buffers donated —
    they are stage-1 outputs, so donation can only recycle
    dispatcher-owned memory (``donate`` additionally excludes buffers a
    backend's input-output forwarding could alias to a user array:
    identity-reshape single-tensor buckets)."""
    body = _fuse_closure(metas, n, bundled)

    def fuse(*arrs):
        return tuple(body(list(arrs)))

    if bundled:
        def wire(*fused):
            outs = smap(*fused)
            if row0:
                outs = [o[0] for o in outs]
            return tuple(_split_fused(list(outs), metas, count))
    else:
        def wire(*fused):
            return tuple(_split_fused(list(smap(*fused)), metas, count))
    fuse_fn = _issue_serialized(jax.jit(fuse))
    wire_fn = _issue_serialized(jax.jit(
        wire, donate_argnums=tuple(i for i, d in enumerate(donate) if d)))
    return fuse_fn, wire_fn


# ---------------------------------------------------------------------------
# chunked wire pipeline (large fused buffers; see docs/pipeline.md)
# ---------------------------------------------------------------------------

def _pipeline_key():
    """Plan-cache key component for the chunk pipeline: the knobs that
    change a chunked plan's program composition — including the ping-pong
    setting, which swaps the fuse/piece program shapes — or None when
    chunking is off (so disabling the pipelined executor reuses the
    pre-pipeline plans byte-for-byte)."""
    if not envs.pipeline_chunking_enabled():
        return None
    return (envs.pipeline_threshold_bytes(), envs.pipeline_chunks(),
            (envs.get(envs.PIPELINE_PINGPONG, "auto") or "auto")
            .strip().lower())


def _chunk_layout(metas):
    """Piece layout for the software pipeline: each wire bucket whose
    payload exceeds ``HVD_PIPELINE_THRESHOLD`` is split into
    ``HVD_PIPELINE_CHUNKS`` contiguous flat ranges, each dispatched as
    its own collective program (the collective of chunk i then overlaps
    the fuse/split — and the neighbors' per-device execution — of chunks
    i±1, ByteScheduler's tensor-partitioning insight applied to the
    fusion buffer). Sub-threshold buckets stay one piece. Returns a list
    of ``(bucket_idx, start_elem, end_elem)`` or None when no bucket
    chunks (the plan then keeps the one-program wire stage)."""
    if not envs.pipeline_chunking_enabled():
        return None
    threshold = envs.pipeline_threshold_bytes()
    chunks = envs.pipeline_chunks()
    layout, any_chunked = [], False
    for bi, (dt, _bidxs, shapes, _srcs) in enumerate(metas):
        total = sum(int(np.prod(shp) or 1) for shp in shapes)
        if total * jnp.dtype(dt).itemsize <= threshold or total < chunks:
            layout.append((bi, 0, total))
            continue
        any_chunked = True
        step = -(-total // chunks)  # ceil: last chunk may be smaller
        layout.extend((bi, a, min(a + step, total))
                      for a in range(0, total, step))
    return layout if any_chunked else None


@functools.lru_cache(maxsize=None)
def _piece_allreduce_fn(mesh: Mesh, axis: str, op: ReduceOp, pre: float,
                        post: float, bundled: bool, donate: bool,
                        recycle: bool):
    """One chunk's wire program: single-buffer shard-mapped reduction
    with the row-0 extract INSIDE the shard_map (``out_specs=P()`` hands
    back the replicated chunk directly — extracting row 0 outside the
    shard_map lowers to a cross-device gather that measured ~6x the
    collective itself on the CPU mesh). ``donate`` recycles the chunk
    buffer's HBM into the reduction (chunk buffers are fuse-stage
    outputs, always dispatcher-owned). ``recycle`` additionally returns
    the donated input as a second output — with real donation the output
    aliases the input's buffer, handing its memory back to the caller as
    the next flush's ping-pong scratch."""
    def inner(x):
        out = _allreduce_traced(x, axis, op, pre, post, None)
        return out[0] if bundled else out

    smap = jax.shard_map(inner, mesh=mesh,
                         in_specs=P(axis) if bundled else P(),
                         out_specs=P(), check_vma=False)

    def one(x):
        out = smap(x)
        return (out, x) if recycle else out

    return _issue_serialized(jax.jit(
        one, donate_argnums=(0,) if donate else ()))


def _plan_chunked_programs(metas, layout, mesh: Mesh, axis, op: ReduceOp,
                           pre: float, post: float, n: int, count: int,
                           bundled: bool, pingpong: bool, donate: bool):
    """Program set for a chunk-pipelined grouped allreduce plan.

    Stage 1 (``fuse``) packs user tensors into the per-dtype wire buffers
    AND slices them into the pipeline pieces, all in one program. Stage 2
    is one collective program per piece, dispatched back-to-back — JAX
    dispatch is asynchronous, so piece i+1 is enqueued while piece i's
    collective runs; the per-device queues then pipeline the pieces
    (measured ~30-40% wall-time reduction for 4 MiB buffers on the CPU
    mesh vs one monolithic wire program). Stage 3 (``split``) reassembles
    the piece results and splits them back into per-tensor outputs.

    With ``pingpong`` the fuse program takes a tuple of donated scratch
    buffers (pure memory donors, never read) and each piece program
    returns its donated input as a recycled buffer — steady-state flushes
    then rotate ``HVD_MAX_INFLIGHT_FLUSHES`` buffer sets instead of
    allocating fresh wire memory per flush."""
    piece_shapes = []
    for bi, a, b in layout:
        dt = metas[bi][0]
        piece_shapes.append(((n, b - a) if bundled else (b - a,), dt))

    def _bufs(inputs):
        if bundled:
            return [jnp.concatenate([inputs[i].astype(dt).reshape(n, -1)
                                     for i in bidxs], axis=1)
                    for (dt, bidxs, _s, _src) in metas]
        return [jnp.concatenate([inputs[i].astype(dt).reshape(-1)
                                 for i in bidxs])
                if len(bidxs) > 1
                else inputs[bidxs[0]].astype(dt).reshape(-1)
                for (dt, bidxs, _s, _src) in metas]

    def _slices(bufs):
        if bundled:
            return tuple(bufs[bi][:, a:b] for (bi, a, b) in layout)
        return tuple(bufs[bi][a:b] for (bi, a, b) in layout)

    if pingpong:
        def fuse(scratch, *inputs):
            del scratch  # memory donors only; outputs reuse their HBM
            return _slices(_bufs(list(inputs)))
        fuse_fn = _issue_serialized(jax.jit(fuse, donate_argnums=(0,)))
    else:
        def fuse(*inputs):
            return _slices(_bufs(list(inputs)))
        fuse_fn = _issue_serialized(jax.jit(fuse))

    piece_fns = [
        _piece_allreduce_fn(mesh, axis, op, pre, post, bundled,
                            donate=donate, recycle=pingpong)
        for _ in layout
    ]

    def split(*piece_outs):
        vecs = []
        for bi in range(len(metas)):
            parts = [piece_outs[j] for j, (b, _a, _e) in enumerate(layout)
                     if b == bi]
            vecs.append(parts[0] if len(parts) == 1
                        else jnp.concatenate(parts))
        return tuple(_split_fused(vecs, metas, count))

    split_fn = _issue_serialized(jax.jit(split))
    return fuse_fn, piece_fns, split_fn, piece_shapes


def _chunked_execute(fuse_fn, piece_fns, split_fn, piece_shapes,
                     canonicalize, pingpong: bool):
    """Execute closure for a chunked plan. ``canonicalize`` maps the user
    tensor list to the fuse program's inputs. The scratch pool (ping-pong
    buffer sets recycled by the piece programs) is per-plan state — i.e.
    per flush signature — bounded by the executor's slot count so at most
    one spare set exists per in-flight flush."""
    pool: list = []
    pool_lock = threading.Lock()

    def execute(ts):
        inputs = canonicalize(ts)
        with _FUSE(activity="PIPELINE_FUSE"):
            if pingpong:
                with pool_lock:
                    scratch = pool.pop() if pool else None
                if scratch is None:
                    scratch = tuple(jnp.zeros(shp, dt)
                                    for shp, dt in piece_shapes)
                pieces = fuse_fn(scratch, *inputs)
            else:
                pieces = fuse_fn(*inputs)
        outs, recycled = [], []
        with _WIRE(activity="PIPELINE_DISPATCH"):
            for piece, fn in zip(pieces, piece_fns):
                r = fn(piece)
                if pingpong:
                    outs.append(r[0])
                    recycled.append(r[1])
                else:
                    outs.append(r)
        if pingpong:
            with pool_lock:
                if len(pool) < max(envs.max_inflight_flushes(), 1):
                    pool.append(tuple(recycled))
        with _SPLIT(activity="PIPELINE_SPLIT"):
            return list(split_fn(*outs))

    return execute


def _fused_execute(fuse_fn, wire_fn, canon):
    """Execute closure for a fused (un-chunked) grouped plan: its two
    programs (:func:`_plan_fused_programs`; the split is inside the wire
    program), each dispatch in its span."""
    def execute(ts):
        with _FUSE():
            fused = fuse_fn(*canon(ts))
        with _WIRE():
            return list(wire_fn(*fused))
    return execute


def _build_grouped_allreduce_plan(tensors, sigs, pset: ProcessSet, axis,
                                  op: ReduceOp, pre_f: float, post_f: float,
                                  name: str | None, compression=None):
    for s in sigs:
        _check_bundle_axis(s, pset)
    lowered_op, post = handle_average(op, pset.size(), post_f)
    pre, post = float(pre_f), float(post)
    n = pset.size()
    count = len(tensors)
    bundled = any(s[0] == "b" for s in sigs)
    shapes = [s[1][1:] if s[0] == "b" else s[1] for s in sigs]
    wire_dts = [_wire_dtype_of(t, compression) for t in tensors]
    hier = (lowered_op == ReduceOp.SUM
            and hierarchical.hierarchical_enabled_for(pset))
    metas = _fusion_metas(shapes, [s[2] for s in sigs], wire_dts)
    # Negotiation metadata carries the WIRE dtype — that is what peers
    # must agree on (and what a joined rank's zero buffers reduce in).
    negotiate = _plan_group_negotiation(
        "grouped_allreduce", REQ_ALLREDUCE, name,
        [(shp, dt) for shp, dt in zip(shapes, wire_dts)], pset,
        reduce_op=int(lowered_op), prescale=pre, postscale=post)
    nbytes = sum(int(np.prod(shp) or 1) * dt.itemsize
                 for shp, dt in zip(shapes, wire_dts))
    if negotiate is not None:
        # Multi-process job: compose EXACTLY like the joined-rank zero
        # reconstruction and the queued flush path — canonical wire-dtype
        # bundles through ``_execute_grouped_bundles`` (eager fuse, one
        # jit(shard_map) wire program per bucket set, eager split), the
        # one composition a joined process can rebuild from response
        # metadata alone. Split fuse/wire jits, donation, and the chunk
        # pipeline remain single-controller-only (ROADMAP alignment item).
        lb_key = negotiate.neg_name

        def execute(ts):
            bundles = [_as_bundle(t, pset)[0] for t in ts]
            wire = [_wire_dtype_of(b, compression) for b in bundles]
            return _execute_grouped_bundles(bundles, pset, axis, lowered_op,
                                            pre, post, count,
                                            wire_dtypes=wire, lb_key=lb_key)
        return _dispatch.DispatchPlan(name or "grouped_allreduce",
                                      "GROUPED_ALLREDUCE", nbytes,
                                      negotiate, execute)
    donate = _sig_donate_mask(metas, sigs, bundled)
    layout = None if hier else _chunk_layout(metas)
    if layout is not None:
        # Chunk pipeline: fuse emits per-chunk wire buffers, each chunk's
        # collective is its own back-to-back-dispatched program, one split
        # program reassembles (see _plan_chunked_programs). Donation and
        # ping-pong buffer recycling engage where donation is real
        # (off-CPU — the CPU backend ignores donation but still charges
        # per-call bookkeeping for it); forcing HVD_PIPELINE_PINGPONG=1
        # forces both (the recycle output needs the donate intent).
        platform = pset.mesh().devices.flat[0].platform
        pingpong = (all(donate)
                    and envs.pipeline_pingpong_enabled(platform))
        piece_donate = envs.donation_effective(platform) or pingpong
        fuse_fn, piece_fns, split_fn, piece_shapes = _plan_chunked_programs(
            metas, layout, pset.mesh(), axis, lowered_op, pre, post, n,
            count, bundled, pingpong, piece_donate)
        canonicalize = _canon_closure(shapes, n, bundled)
        execute = _chunked_execute(fuse_fn, piece_fns, split_fn,
                                   piece_shapes, canonicalize, pingpong)
        return _dispatch.DispatchPlan(name or "grouped_allreduce",
                                      "GROUPED_ALLREDUCE", nbytes,
                                      negotiate, execute, variant="chunked",
                                      pieces=len(layout))
    if hier:
        smap = hierarchical._hier_grouped_allreduce_smap(
            hierarchical.hierarchical_mesh(), lowered_op, pre, post,
            len(metas), bundled)
    else:
        smap = _grouped_allreduce_smap(pset.mesh(), axis, lowered_op, pre,
                                       post, len(metas), bundled)
    fuse_fn, wire_fn = _plan_fused_programs(metas, smap, n, count, bundled,
                                            donate, row0=bundled)
    canon = _canon_closure(shapes, n, bundled)

    return _dispatch.DispatchPlan(name or "grouped_allreduce",
                                  "GROUPED_ALLREDUCE", nbytes, negotiate,
                                  _fused_execute(fuse_fn, wire_fn, canon))


def _build_broadcast_plan(sig, pset: ProcessSet, axis, root_rank: int,
                          name: str | None):
    _check_bundle_axis(sig, pset)
    bundled = sig[0] == "b"
    per_shape = sig[1][1:] if bundled else sig[1]
    dtype = jnp.dtype(sig[2])
    root_pos = pset.ranks.index(root_rank)
    negotiate = _plan_negotiation("broadcast", REQ_BROADCAST, name,
                                  per_shape, dtype, pset,
                                  root_rank=root_rank)
    nbytes = int(np.prod(per_shape) or 1) * dtype.itemsize
    if negotiate is not None and _lb.active():
        # Loopback plan variant (per-context cache: never serves a real
        # multi-process world): rendezvous the rows, root's row wins.
        lb_key = negotiate.neg_name

        def execute(t):
            bundle, _ = _as_bundle(t, pset)
            return _execute_broadcast_bundle(bundle, pset, axis, root_pos,
                                             lb_key=lb_key)
        return _dispatch.DispatchPlan(name or "broadcast", "BROADCAST",
                                      nbytes, negotiate, execute)
    fn = _eager_broadcast_fn(pset.mesh(), axis, root_pos, bundled)
    if bundled:
        def execute(t):
            return fn(t.array)
    else:
        def execute(t):
            return fn(jnp.asarray(t))
    return _dispatch.DispatchPlan(name or "broadcast", "BROADCAST", nbytes,
                                  negotiate, execute)


def _build_grouped_broadcast_plan(tensors, sigs, pset: ProcessSet, axis,
                                  root_rank: int, name: str | None):
    for s in sigs:
        _check_bundle_axis(s, pset)
    n = pset.size()
    count = len(tensors)
    root_pos = pset.ranks.index(root_rank)
    bundled = any(s[0] == "b" for s in sigs)
    shapes = [s[1][1:] if s[0] == "b" else s[1] for s in sigs]
    src_dts = [jnp.dtype(s[2]) for s in sigs]
    negotiate = _plan_group_negotiation(
        "grouped_broadcast", REQ_BROADCAST, name,
        [(shp, jnp.dtype(s[2])) for shp, s in zip(shapes, sigs)], pset,
        root_rank=root_rank)
    if negotiate is not None and _lb.active():
        lb_key = negotiate.neg_name

        def execute(ts):
            bundles = [_as_bundle(t, pset)[0] for t in ts]
            ch = _lb.channel(pset, lb_key)
            if ch is None:  # world torn down mid-plan: plain bundles
                fi, ms = _fuse_by_dtype(bundles, n)
                f = _eager_grouped_broadcast_fn(pset.mesh(), axis,
                                                root_pos, len(fi))
                return _split_fused(f(*fi), ms, count)
            return _lb_grouped_broadcast(ch, bundles, pset, axis,
                                         root_pos, count)
        return _dispatch.DispatchPlan(name or "grouped_broadcast",
                                      "GROUPED_BROADCAST", None, negotiate,
                                      execute)
    metas = _fusion_metas(shapes, src_dts, src_dts)
    donate = _sig_donate_mask(metas, sigs, bundled)
    smap = _grouped_broadcast_smap(pset.mesh(), axis, root_pos, len(metas),
                                   bundled)
    fuse_fn, wire_fn = _plan_fused_programs(metas, smap, n, count, bundled,
                                            donate, row0=False)
    canon = _canon_closure(shapes, n, bundled)

    return _dispatch.DispatchPlan(name or "grouped_broadcast",
                                  "GROUPED_BROADCAST", None, negotiate,
                                  _fused_execute(fuse_fn, wire_fn, canon))


def _build_allgather_plan(sig, pset: ProcessSet, axis, name: str | None):
    """Uniform-shape eager allgather plan. Returns None when a negotiation
    service runs — the engine's recv_splits can resize the program per
    call (ragged peers / joined processes), so multi-process allgather
    keeps the response-driven path. NOTE: ``allgather()`` already skips
    plan lookup entirely when a service exists (per-call unique async
    names would churn the cache with UNPLANNABLE entries), so this check
    only guards the race of a service appearing between the two calls."""
    from .. import engine_service
    if engine_service.get_service(pset) is not None:
        return None
    _check_bundle_axis(sig, pset)
    bundled = sig[0] == "b"
    per_shape = sig[1][1:] if bundled else sig[1]
    dtype = jnp.dtype(sig[2])
    nbytes = int(np.prod(per_shape) or 1) * dtype.itemsize
    if len(per_shape) >= 1 and per_shape[0] == 0:
        rest = per_shape[1:]

        def execute(t):
            # uniform zero-row gather: no data moves (XLA forbids the
            # zero-size gather dim); result empty on every rank
            return jnp.zeros((0,) + rest, dtype)
        return _dispatch.DispatchPlan(name or "allgather", "ALLGATHER",
                                      nbytes, None, execute)
    if hierarchical.hierarchical_allgather_enabled_for(pset):
        fn = hierarchical._eager_hier_allgather_fn(
            hierarchical.hierarchical_mesh(), bundled)
    else:
        fn = _eager_allgather_fn(pset.mesh(), axis, bundled)
    scalar = len(per_shape) == 0
    if bundled:
        if scalar:  # (n,) bundle of scalars -> (n,) vector
            def execute(t):
                return fn(t.array[:, None]).reshape(-1)
        else:
            def execute(t):
                return fn(t.array)
    else:
        if scalar:
            def execute(t):
                return fn(jnp.asarray(t).reshape(1)).reshape(-1)
        else:
            def execute(t):
                return fn(jnp.asarray(t))
    return _dispatch.DispatchPlan(name or "allgather", "ALLGATHER", nbytes,
                                  None, execute)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def allreduce(tensor, *, op: ReduceOp = ReduceOp.AVERAGE,
              process_set: ProcessSet | None = None,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              name: str | None = None, axis_name=None):
    """Allreduce (reference ``hvd.allreduce``; enqueue path
    ``operations.cc:1357-1512``). AVERAGE lowers to SUM + postscale 1/n
    (``operations.cc:1408-1416``). ``name`` labels the op in the timeline
    (``hvd.start_timeline``)."""
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    _check_op_dtype(op, jnp.result_type(tensor if not isinstance(tensor, PerRank)
                                       else tensor.array))
    if op == ReduceOp.ADASUM:
        from .adasum import adasum_allreduce
        return adasum_allreduce(tensor, process_set=pset, axis_name=axis)
    if _trace_state_clean():
        # definitely eager (no trace in progress): plan-cached dispatch.
        # HVD_CACHE_CAPACITY=0 (the off switch) keeps the original
        # build-everything-per-call path below.
        sig = _plan_sig(tensor) if _dispatch.enabled() else None
        if sig is not None:
            key = ("allreduce", name, sig, axis, pset.dispatch_key(),
                   int(op), float(prescale_factor), float(postscale_factor),
                   hierarchical.layout_key_for(pset))
            return _dispatch.lookup_or_build(
                key, lambda: _build_allreduce_plan(
                    sig, pset, axis, op, prescale_factor,
                    postscale_factor, name)).run(tensor)
    elif _axis_is_bound(axis):
        return _allreduce_traced(tensor, axis, op, prescale_factor,
                                 postscale_factor, pset.axis_index_groups())
    elif _contains_tracer(tensor):
        # Inside jit/pjit with no named axis: GSPMD semantics — gradients of
        # a globally-sharded computation are already globally reduced by
        # XLA's partitioner, so the allreduce is the identity (the design
        # inversion of SURVEY.md §7; the reference's XLA bridge
        # xla_mpi_ops.cc:165-260 calls back into the runtime instead).
        # Only the gradient-reduction ops have this equivalence.
        _gspmd_passthrough_check(op, "allreduce")
        scale = prescale_factor * postscale_factor
        return tensor if scale == 1.0 else tensor * scale
    # non-plannable eager input (python scalars/lists, ragged misuse) or a
    # jax build without the trace-state probe: generic bundle path
    lowered_op, post = handle_average(op, pset.size(), postscale_factor)
    bundle, _ = _as_bundle(tensor, pset)
    _resp, neg_name = _negotiate_eager(
        "allreduce", REQ_ALLREDUCE, name, bundle.shape[1:],
        bundle.dtype, pset, reduce_op=int(lowered_op),
        prescale=float(prescale_factor), postscale=float(post))
    _autotune.record(bundle.nbytes // max(bundle.shape[0], 1))
    with _ALLREDUCE(name):
        return _execute_allreduce_bundle(bundle, pset, axis, lowered_op,
                                         float(prescale_factor), float(post),
                                         lb_key=neg_name)


def _execute_allreduce_bundle(bundle, pset, axis, lowered_op, pre, post,
                              lb_key=None):
    """Dispatch one eager allreduce program for a (n, ...) bundle — shared
    by the caller path and the joined-rank zero-contribution path, which
    must produce the identical SPMD program.

    ``lb_key`` (the negotiated tensor name) routes a loopback world's
    execution through the rendezvous hub: each rank contributes its OWN
    bundle row, and the completing rank runs this very function's body
    over the reconstructed true bundle — so loopback numerics are the
    single-controller program's, bit for bit."""
    ch = _lb.channel(pset, lb_key)
    if ch is not None:
        return ch.compute(
            bundle[ch.pos],
            lambda rows: _execute_allreduce_bundle(
                jnp.stack(rows), pset, axis, lowered_op, pre, post))
    if (lowered_op == ReduceOp.SUM
            and hierarchical.hierarchical_enabled_for(pset)):
        # HVD_HIERARCHICAL_ALLREDUCE: two-phase ICI/DCN schedule (the
        # reference's NCCLHierarchicalAllreduce analog).
        fn = hierarchical._eager_hier_allreduce_fn(
            hierarchical.hierarchical_mesh(), lowered_op, pre, post)
        return fn(bundle)[0]
    fn = _eager_allreduce_fn(pset.mesh(), axis, lowered_op, pre, post)
    return fn(bundle)[0]


# timer-boundary: the fusion-cycle timer only flushes single-controller
# queues (svc is None -> no negotiation, composition trivially rank-
# consistent), so timer-purity traversal stops at this entry point.
def grouped_allreduce(tensors: Sequence, *, op: ReduceOp = ReduceOp.AVERAGE,  # hvdlint: timer-boundary
                      process_set: ProcessSet | None = None,
                      prescale_factor: float = 1.0, postscale_factor: float = 1.0,
                      name: str | None = None, axis_name=None,
                      compression=None):
    """Fused allreduce of a tensor list (reference ``grouped_allreduce``,
    ``EnqueueTensorAllreduces`` with a group at ``operations.cc:1384-1512``).

    Eager mode performs explicit tensor fusion: tensors are flattened and
    concatenated per WIRE dtype into single wire buffers (the XLA analog of
    the reference's fusion buffer, ``fusion_buffer_manager.h:30-50``),
    reduced in one compiled program, then split back. ``compression``
    (``hvd.Compression.bf16``/``fp16``) routes floating tensors over the
    wire in the compressed dtype: mixed-source-dtype tensors sharing a wire
    dtype fuse into ONE buffer instead of fragmenting per source dtype, and
    each result is cast back (decompressed) after the split.
    """
    if not tensors:
        return []
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    for t in tensors:
        _check_op_dtype(op, jnp.result_type(t if not isinstance(t, PerRank) else t.array))
    if op == ReduceOp.ADASUM:
        from .adasum import adasum_allreduce
        return [adasum_allreduce(t, process_set=pset, axis_name=axis) for t in tensors]
    if _is_custom_compressor(compression):
        # user Compressor subclass: only its compress/decompress pair
        # defines the wire format — wrap the call per leaf (the pre-wire-
        # fusion contract), no wire-dtype bucketing. Compressors see
        # arrays, so PerRank bundles are compressed through their array.
        def _comp(t):
            if isinstance(t, PerRank):
                c, ctx = compression.compress(t.array)
                return PerRank(c, t.dim0s), ctx
            return compression.compress(t)

        cs, ctxs = zip(*(_comp(t) for t in tensors))
        outs = grouped_allreduce(
            list(cs), op=op, process_set=pset,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, name=name, axis_name=axis)
        return [compression.decompress(o, ctx)
                for o, ctx in zip(outs, ctxs)]
    # plan/queue identity of the wire mapping: the wire dtype itself (a
    # class name would miss compressor instances and collide same-named
    # user classes with different wire formats)
    _wire = getattr(compression, "wire_dtype", None)
    comp_key = jnp.dtype(_wire).name if _wire is not None else None

    if _trace_state_clean():
        sigs = (tuple(_plan_sig(t) for t in tensors)
                if _dispatch.enabled() else (None,))
        if all(s is not None for s in sigs):
            key = ("grouped_allreduce", name, sigs, axis,
                   pset.dispatch_key(), int(op), float(prescale_factor),
                   float(postscale_factor),
                   hierarchical.layout_key_for(pset),
                   envs.fusion_threshold_bytes(), comp_key,
                   _pipeline_key())
            return _dispatch.lookup_or_build(
                key, lambda: _build_grouped_allreduce_plan(
                    tensors, sigs, pset, axis, op, prescale_factor,
                    postscale_factor, name, compression)).run(tensors)
    elif _axis_is_bound(axis):
        groups = pset.axis_index_groups()
        if comp_key is not None:
            # traced wire compression: cast, reduce, cast back per leaf
            # (XLA fuses the casts into the collective's producers)
            outs = []
            for t in tensors:
                wdt = _wire_dtype_of(t, compression)
                src = jnp.result_type(t)
                r = _allreduce_traced(t.astype(wdt) if src != wdt else t,
                                      axis, op, prescale_factor,
                                      postscale_factor, groups)
                outs.append(r.astype(src) if src != wdt else r)
            return outs
        traced_fusion = envs.get_int(envs.TRACED_FUSION_THRESHOLD, 0)
        if len(tensors) > 1 and traced_fusion > 0:
            return _grouped_allreduce_traced_fused(
                tensors, axis, op, prescale_factor, postscale_factor,
                groups, traced_fusion)
        return [_allreduce_traced(t, axis, op, prescale_factor,
                                  postscale_factor, groups)
                for t in tensors]
    elif any(_contains_tracer(t) for t in tensors):
        # GSPMD passthrough (see allreduce above). Nothing travels a wire,
        # so compression is the identity here too.
        _gspmd_passthrough_check(op, "grouped_allreduce")
        scale = prescale_factor * postscale_factor
        return list(tensors) if scale == 1.0 else [t * scale for t in tensors]
    lowered_op, post = handle_average(op, pset.size(), postscale_factor)

    # --- eager fusion path ---
    n = pset.size()
    bundles = [_as_bundle(t, pset)[0] for t in tensors]
    wire_dts = [_wire_dtype_of(b, compression) for b in bundles]
    neg_names = _negotiate_eager_group(
        "grouped_allreduce", REQ_ALLREDUCE, name,
        [(b.shape[1:], dt)
         for b, dt in zip(bundles, wire_dts)], pset,
        reduce_op=int(lowered_op),
        prescale=float(prescale_factor),
        postscale=float(post))
    _autotune.record(sum(int(np.prod(b.shape[1:]) or 1) * dt.itemsize
                         for b, dt in zip(bundles, wire_dts)))
    with _GROUPED_ALLREDUCE(name):
        return _execute_grouped_bundles(bundles, pset, axis, lowered_op,
                                        float(prescale_factor), float(post),
                                        len(tensors), wire_dtypes=wire_dts,
                                        lb_key=neg_names[0] if neg_names
                                        else None)


def _grouped_allreduce_traced_fused(tensors, axis, op, pre, post, groups,
                                    limit):
    """OPT-IN explicit tensor fusion on the TRACED path
    (``HVD_TRACED_FUSION_THRESHOLD`` > 0, bytes per fused buffer): pack
    same-dtype leaves into bounded flat buffers, ONE collective per
    buffer (every reduce op is elementwise, so fusing is exact) — the
    traced twin of the eager fusion buffer (reference
    ``fusion_buffer_manager.h:30-50``).

    OFF by default: the TPU compiler has an all-reduce combiner of its
    own. What it does NOT do at the options a user's ``jax.jit`` gets is
    overlap: per-leaf ``psum`` became 12 synchronous ``all-reduce``
    operations after the backward pass (``gpt2m-traced-4chip``:
    ``exposed_collective_ms`` = ``collective_ms`` = 28.3 ms of a 121.5 ms
    step; ledger, PR 30), and an explicit fused buffer waits for all of
    the backward pass just the same; neither path was measured against
    the other on the chip. ``DistributedOptimizer``'s own traced sync
    no longer leans on either (``optim._traced_sync``,
    ``ops/traced_exchange.py``); setting this knob keeps this path for
    it too. The knob exists for backends without a combiner pass and for
    experimentation."""
    out: list = [None] * len(tensors)
    for _dt, chunk in _fusion_buckets(tensors, limit,
                                      lambda t: int(t.size)):
        if len(chunk) == 1:  # nothing to fuse; skip the reshape round trip
            j = chunk[0]
            out[j] = _allreduce_traced(tensors[j], axis, op, pre, post,
                                       groups)
            continue
        fused = jnp.concatenate([jnp.ravel(tensors[j]) for j in chunk])
        red = _allreduce_traced(fused, axis, op, pre, post, groups)
        off = 0
        for j in chunk:
            size = tensors[j].size
            out[j] = red[off:off + size].reshape(jnp.shape(tensors[j]))
            off += size
    return out


def _execute_grouped_bundles(bundles, pset, axis, lowered_op, pre, post,
                             count, wire_dtypes=None, lb_key=None):
    """One fused eager grouped-allreduce program over (n, ...) bundles —
    shared by the caller path and the joined-rank zero path. ``lb_key``:
    see :func:`_execute_allreduce_bundle`."""
    ch = _lb.channel(pset, lb_key)
    if ch is not None:
        rows = tuple(b[ch.pos] for b in bundles)
        return ch.compute(
            rows,
            lambda allrows: _execute_grouped_bundles(
                [jnp.stack([r[i] for r in allrows])
                 for i in range(len(bundles))],
                pset, axis, lowered_op, pre, post, count,
                wire_dtypes=wire_dtypes))
    n = pset.size()
    fused_inputs, metas = _fuse_by_dtype(bundles, n, wire_dtypes=wire_dtypes)
    # No donation here: this generic path doubles as the HVD_CACHE_CAPACITY=0
    # reference behavior; buffer donation lives in the dispatch plans' wire
    # programs (_plan_fused_programs), where the wire buffers are provably
    # dispatcher-owned stage-1 outputs.
    if (lowered_op == ReduceOp.SUM
            and hierarchical.hierarchical_enabled_for(pset)):
        fn = hierarchical._eager_hier_grouped_allreduce_fn(
            hierarchical.hierarchical_mesh(), lowered_op, pre, post,
            len(fused_inputs))
    else:
        fn = _eager_grouped_allreduce_fn(pset.mesh(), axis, lowered_op,
                                         pre, post, len(fused_inputs))
    fused_outputs = fn(*fused_inputs)
    # row 0 of each (n, total) buffer: identical on every rank
    return _split_fused([buf[0] for buf in fused_outputs], metas, count)


# timer-boundary: the fusion-cycle timer never flushes svc allgather
# queues (_loop skips svc queues), and the single-controller path below
# has no negotiation — traversal stops here.
def allgather(tensor, *, process_set: ProcessSet | None = None,  # hvdlint: timer-boundary
              name: str | None = None, axis_name=None):
    """Allgather: concatenate per-rank tensors along dim 0 (reference
    ``hvd.allgather``; ``EnqueueTensorAllgather`` at ``operations.cc:1529``,
    displacement math at ``collective_operations.h:143-178``).

    Ragged first dimensions are supported in eager mode (the reference's
    allgatherv contract): pass a ragged :func:`per_rank` bundle
    (single-controller), or — in multi-process jobs — each process simply
    passes its local tensor and the per-rank row counts are exchanged
    through the dynamic engine (the displacement negotiation of
    ``collective_operations.h:143-178``). Joined processes contribute zero
    rows. Traced mode requires uniform shapes (SPMD static shapes).
    """
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    if _trace_state_clean():
        sig = _plan_sig(tensor) if _dispatch.enabled() else None
        if sig is not None:
            from .. import engine_service
            if engine_service.get_service(pset) is not None:
                # Response-driven path: the engine's recv_splits can
                # resize the program per call, so no plan can ever serve
                # — and per-call unique names (async queue entries) would
                # otherwise churn the cache with dead UNPLANNABLE keys,
                # evicting live plans.
                sig = None
        if sig is not None:
            key = ("allgather", name, sig, axis, pset.dispatch_key(),
                   hierarchical.allgather_layout_key_for(pset))
            plan = _dispatch.lookup_or_build(
                key, lambda: (_build_allgather_plan(sig, pset, axis, name)
                              or _dispatch.UNPLANNABLE))
            if plan is not _dispatch.UNPLANNABLE:
                return plan.run(tensor)
    elif _axis_is_bound(axis):
        return _allgather_traced(tensor, axis, pset.axis_index_groups(),
                                 pset.ranks, pset.size())
    elif _contains_tracer(tensor):
        raise RuntimeError(
            "allgather() was called inside jit/pjit without a bound mesh axis. "
            "Run it under jax.shard_map over hvd.mesh() (or pass axis_name=) "
            "so the op can lower to an XLA collective.")
    local_d0s = tensor.dim0s if isinstance(tensor, PerRank) else None
    bundle, _ = _as_bundle(tensor, pset, allow_ragged=True)

    # Negotiation shape: this process's own first dim (rank-local in the
    # engine, collective_operations.h:143-178); a digest of the full dim0s
    # vector cross-validates ragged per_rank bundles like the uneven
    # alltoall's splits matrix.
    member_procs, one_to_one, my_pos = _member_process_view(pset)
    crc = 0
    neg_shape = bundle.shape[1:]
    if local_d0s is not None:
        crc = _i64_digest(local_d0s)
        if one_to_one:
            neg_shape = (local_d0s[my_pos],) + bundle.shape[2:]
    resp, neg_name = _negotiate_eager("allgather", REQ_ALLGATHER, name,
                                      neg_shape, bundle.dtype, pset,
                                      splits_crc=crc)

    # Resolve the per-rank row counts. The routing rule must be a pure
    # function of the engine response so active and joined processes build
    # the SAME program (_execute_joined_zeros applies the identical rule):
    # all engine dims equal -> uniform program; otherwise ragged with the
    # padded dim = max over the ENGINE's rank view (not local padding).
    d0s = list(local_d0s) if local_d0s is not None else None
    maxd = max(d0s) if d0s else None
    if resp is not None and resp.recv_splits:
        pos = {p: i for i, p in enumerate(member_procs)}
        eng = [int(resp.recv_splits[pos[runtime.process_of_rank(r)]])
               for r in pset.ranks]
        if d0s is None:
            if len(set(eng)) > 1:
                d0s = eng  # peers contributed different first dims
                maxd = max(eng)
        else:
            if one_to_one:
                for i, (e, loc) in enumerate(zip(eng, d0s)):
                    if e not in (0, loc):
                        raise ValueError(
                            f"allgather dim0s disagree: engine negotiated "
                            f"{e} rows for rank {pset.ranks[i]} but the "
                            f"local per_rank bundle carries {loc}; processes "
                            "passed different ragged bundles")
            # engine view decides participation (0 = joined) AND the
            # program's padded dim — every process, including joined ones
            # reconstructing from recv_splits alone, derives the same value
            d0s = [0 if e == 0 else loc for e, loc in zip(eng, d0s)]
            maxd = max(eng)

    _autotune.record(bundle.nbytes // max(bundle.shape[0], 1))
    with _ALLGATHER(name):
        if d0s is None and bundle.ndim >= 2 and bundle.shape[1] == 0:
            # uniform zero-row gather: no data moves and XLA forbids a
            # zero-size gather dim — the result is empty on every rank
            # (joined peers — and loopback ranks — skip identically:
            # the engine negotiated every dim 0, so the decision is
            # rank-consistent)
            return jnp.zeros((0,) + bundle.shape[2:], bundle.dtype)
        if d0s is not None and max(d0s) == 0 and _lb.active():
            # loopback: an all-zero ragged gather skips the exchange
            # BEFORE a channel is created (channel creation advances the
            # per-name occurrence counter, and the joined-rank zero path
            # skips on the same predicate — the counters must not drift)
            return jnp.zeros((0,) + tuple(bundle.shape[2:]), bundle.dtype)
        ch = _lb.channel(pset, neg_name)
        if ch is not None:
            return _loopback_allgather(ch, bundle, d0s)
        if d0s is not None:
            return _execute_ragged_allgather(bundle, d0s, maxd, pset, axis)
        if hierarchical.hierarchical_allgather_enabled_for(pset):
            # HVD_HIERARCHICAL_ALLGATHER: ICI-then-DCN two-phase gather.
            hmesh = hierarchical.hierarchical_mesh()
            if bundle.ndim == 1:
                bundle = bundle[:, None]
                return hierarchical._eager_hier_allgather_fn(hmesh)(
                    bundle).reshape(-1)
            return hierarchical._eager_hier_allgather_fn(hmesh)(bundle)
        if bundle.ndim == 1:  # scalars per rank: gather to a vector
            bundle = bundle[:, None]
            return _eager_allgather_fn(pset.mesh(), axis)(bundle).reshape(-1)
        return _eager_allgather_fn(pset.mesh(), axis)(bundle)


def _lb_gather_parts(rest, dtype):
    """THE loopback allgather combiner, shared by the active path and the
    joined-rank zero contribution — whichever rank completes the slot
    runs it, so both must supply the identical closure."""
    rest = tuple(rest)

    def gather(parts):
        parts = [p for p in parts if p.shape[0] > 0]
        if not parts:
            return jnp.zeros((0,) + rest, dtype)
        return jnp.concatenate(parts, axis=0)

    return gather


def _lb_stack_parts(parts):
    """Scalar-allgather combiner (one scalar per rank -> (n,) vector).
    Module-level so the active path and the joined-rank zero
    contribution supply the literally identical function."""
    return jnp.stack(parts)


def _lb_grouped_broadcast(ch, bundles, pset, axis, root_pos, count):
    """THE loopback grouped-broadcast execution, shared by the plan,
    immediate, and queued paths — one combiner, so leader-dependent
    results cannot drift between the three call sites."""
    n = pset.size()

    def compute(allrows):
        bs = [jnp.stack([r[i] for r in allrows])
              for i in range(len(bundles))]
        fi, ms = _fuse_by_dtype(bs, n)
        f = _eager_grouped_broadcast_fn(pset.mesh(), axis, root_pos,
                                        len(fi))
        return _split_fused(f(*fi), ms, count)

    return ch.compute(tuple(b[ch.pos] for b in bundles), compute)


def _loopback_allgather(ch, bundle, d0s):
    """Loopback allgather execution: each rank contributes its valid rows
    (ragged: trimmed to its negotiated first dim — a joined rank's zero
    rows included), and the completing rank concatenates in set order.
    No arithmetic happens, so the result is exact."""
    if bundle.ndim == 1:  # scalar per rank -> (n,) vector; a joined
        # peer contributes a zero scalar, like the real (n, 1) program
        return ch.compute(bundle[ch.pos], _lb_stack_parts)
    rows = bundle[ch.pos]
    if d0s is not None:
        rows = rows[:d0s[ch.pos]]
    return ch.compute(rows, _lb_gather_parts(bundle.shape[2:],
                                             bundle.dtype))


def _execute_ragged_allgather(bundle, d0s, maxd, pset: ProcessSet, axis):
    """Ragged eager allgather: pad every rank's block to the negotiated max
    first dim, exchange with the uniform all-gather program (identical SPMD
    computation on every process — ``maxd`` is derived from the engine's
    shared view, so joined processes rebuild the same shape), then slice
    the valid rows back out and concatenate (the pad/exchange/slice scheme
    of the uneven alltoall applied to MPI_Allgatherv,
    ``collective_operations.h:143-178``)."""
    n = pset.size()
    rest = bundle.shape[2:]
    maxd = max(int(maxd), 1)
    if bundle.shape[1] < maxd:
        # local-tensor multi-process path: this process's rows are fewer
        # than the global max — pad with zeros (never read back)
        pad = jnp.zeros((n, maxd - bundle.shape[1]) + rest, bundle.dtype)
        bundle = jnp.concatenate([bundle, pad], axis=1)
    elif bundle.shape[1] > maxd:
        # joined peers shrank the global max below the local padding
        bundle = bundle[:, :maxd]
    gathered = _eager_allgather_fn(pset.mesh(), axis)(bundle)  # (n*maxd,...)
    parts = [gathered[r * maxd:r * maxd + d0s[r]] for r in range(n)
             if d0s[r] > 0]
    if not parts:
        return jnp.zeros((0,) + rest, bundle.dtype)
    return jnp.concatenate(parts, axis=0)


def broadcast(tensor, root_rank: int, *, process_set: ProcessSet | None = None,
              name: str | None = None, axis_name=None):
    """Broadcast from ``root_rank`` (a *global* rank, as in the reference's
    ``hvd.broadcast``; ``operations.cc:1568``)."""
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    if root_rank not in pset.ranks:
        raise ValueError(f"root_rank {root_rank} not in process set {pset.ranks}")
    if _trace_state_clean():
        sig = _plan_sig(tensor) if _dispatch.enabled() else None
        if sig is not None:
            key = ("broadcast", name, sig, axis, pset.dispatch_key(),
                   root_rank)
            return _dispatch.lookup_or_build(
                key, lambda: _build_broadcast_plan(
                    sig, pset, axis, root_rank, name)).run(tensor)
    elif _axis_is_bound(axis):
        return _broadcast_traced(tensor, axis, root_rank,
                                 pset.axis_index_groups(), pset.ranks)
    elif _contains_tracer(tensor):
        raise RuntimeError(
            "broadcast() was called inside jit/pjit without a bound mesh axis. "
            "Run it under jax.shard_map over hvd.mesh() (or pass axis_name=) "
            "so the op can lower to an XLA collective.")
    bundle, _ = _as_bundle(tensor, pset)
    root_pos = pset.ranks.index(root_rank)
    _resp, neg_name = _negotiate_eager("broadcast", REQ_BROADCAST, name,
                                       bundle.shape[1:], bundle.dtype, pset,
                                       root_rank=root_rank)
    _autotune.record(bundle.nbytes // max(bundle.shape[0], 1))
    with _BROADCAST(name):
        return _execute_broadcast_bundle(bundle, pset, axis, root_pos,
                                         lb_key=neg_name)


def _execute_broadcast_bundle(bundle, pset, axis, root_pos, lb_key=None):
    """One eager broadcast program for a (n, ...) bundle; under loopback,
    rows rendezvous first (see :func:`_execute_allreduce_bundle`)."""
    ch = _lb.channel(pset, lb_key)
    if ch is not None:
        return ch.compute(
            bundle[ch.pos],
            lambda rows: _execute_broadcast_bundle(
                jnp.stack(rows), pset, axis, root_pos))
    return _eager_broadcast_fn(pset.mesh(), axis, root_pos)(bundle)


# timer-boundary: see grouped_allreduce — timer flushes are single-
# controller only, so no negotiation is reachable through this entry.
def grouped_broadcast(tensors: Sequence, root_rank: int, *,  # hvdlint: timer-boundary
                      process_set: ProcessSet | None = None,
                      name: str | None = None, axis_name=None):
    """Fused broadcast of a tensor list from ``root_rank``. Eager mode packs
    the tensors into one wire buffer per dtype (same fusion scheme as
    :func:`grouped_allreduce`, the analog of the reference's fusion buffer)
    so ``broadcast_parameters`` over a large model dispatches O(dtypes)
    programs instead of O(leaves)."""
    if not tensors:
        return []
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    if root_rank not in pset.ranks:
        raise ValueError(f"root_rank {root_rank} not in process set {pset.ranks}")
    if _trace_state_clean():
        sigs = (tuple(_plan_sig(t) for t in tensors)
                if _dispatch.enabled() else (None,))
        if all(s is not None for s in sigs):
            key = ("grouped_broadcast", name, sigs, axis,
                   pset.dispatch_key(), root_rank,
                   envs.fusion_threshold_bytes())
            return _dispatch.lookup_or_build(
                key, lambda: _build_grouped_broadcast_plan(
                    tensors, sigs, pset, axis, root_rank,
                    name)).run(tensors)
    elif _axis_is_bound(axis):
        groups = pset.axis_index_groups()
        return [_broadcast_traced(t, axis, root_rank, groups, pset.ranks)
                for t in tensors]
    elif any(_contains_tracer(t) for t in tensors):
        raise RuntimeError(
            "grouped_broadcast() was called inside jit/pjit without a bound "
            "mesh axis. Run it under jax.shard_map over hvd.mesh() (or pass "
            "axis_name=) so the ops can lower to XLA collectives.")
    n = pset.size()
    root_pos = pset.ranks.index(root_rank)
    bundles = [_as_bundle(t, pset)[0] for t in tensors]
    fused_inputs, metas = _fuse_by_dtype(bundles, n)
    neg_names = _negotiate_eager_group(
        "grouped_broadcast", REQ_BROADCAST, name,
        [(b.shape[1:], b.dtype) for b in bundles], pset,
        root_rank=root_rank)
    with _GROUPED_BROADCAST(name):
        ch = _lb.channel(pset, neg_names[0] if neg_names else None)
        if ch is not None:
            return _lb_grouped_broadcast(ch, bundles, pset, axis,
                                         root_pos, len(tensors))
        fn = _eager_grouped_broadcast_fn(pset.mesh(), axis, root_pos,
                                         len(fused_inputs))
        fused_outputs = fn(*fused_inputs)
    return _split_fused(fused_outputs, metas, len(tensors))


def alltoall(tensor, splits=None, *, process_set: ProcessSet | None = None,
             name: str | None = None, axis_name=None):
    """All-to-all along dim 0 (reference ``hvd.alltoall``,
    ``operations.cc:1642-1727``).

    Even mode (``splits=None``): rank *i*'s j-th of ``size`` equal chunks
    goes to rank *j*; returns a :class:`PerRank`.

    Uneven mode (``splits`` given): eager only (the reference likewise has
    no jit path — dynamic output shapes). ``splits`` is either one row of
    length ``size`` (every rank sends the same split pattern) or the full
    ``(size, size)`` matrix ``splits[i][j]`` = rows rank *i* sends rank *j*
    (the single-controller eager model sees every rank's metadata, like
    :func:`per_rank` bundles carry every rank's data). Row sums may be less
    than dim 0 — trailing rows are simply not sent, matching the
    reference's ``sum <= first_dim`` contract (``operations.cc:1703-1707``).
    Returns ``(outputs, recv_splits)``: ``outputs[r]`` is rank *r*'s
    received concatenation and ``recv_splits[r][j]`` the rows it got from
    rank *j* (the reference's second output tensor,
    ``collective_operations.h:261-269``). In multi-process jobs the splits
    metadata is cross-validated through the dynamic engine
    (``AlltoallGetRecvSplits`` analog)."""
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    if splits is not None:
        return _alltoall_uneven(tensor, splits, pset, axis, name)
    if _axis_is_bound(axis):
        return _alltoall_traced(tensor, axis, pset.axis_index_groups())
    if _contains_tracer(tensor):
        raise RuntimeError(
            "alltoall() was called inside jit/pjit without a bound mesh axis. "
            "Run it under jax.shard_map over hvd.mesh() (or pass axis_name=) "
            "so the op can lower to an XLA collective.")
    bundle, _ = _as_bundle(tensor, pset)
    n = pset.size()
    if bundle.shape[1] % n != 0:
        raise ValueError(f"alltoall dim0 ({bundle.shape[1]}) must be divisible "
                         f"by process set size ({n})")
    _resp, neg_name = _negotiate_eager("alltoall", REQ_ALLTOALL, name,
                                       bundle.shape[1:], bundle.dtype, pset)
    with _ALLTOALL(name):
        ch = _lb.channel(pset, neg_name)
        if ch is not None:
            out = ch.compute(
                bundle[ch.pos],
                lambda rows: _eager_alltoall_fn(pset.mesh(), axis)(
                    jnp.stack(rows)))
        else:
            out = _eager_alltoall_fn(pset.mesh(), axis)(bundle)
    return PerRank(out.reshape((n, out.shape[0] // n) + out.shape[1:]))


def _alltoall_uneven(tensor, splits, pset: ProcessSet, axis,
                     name: str | None):
    """Uneven eager alltoall: pad each per-destination chunk to the global
    max split, exchange with one ``lax.all_to_all``, slice the ragged valid
    parts back out (MPI_Alltoallv under XLA's static shapes)."""
    if _contains_tracer(tensor) or _axis_is_bound(axis):
        raise RuntimeError(
            "alltoall with uneven splits is eager-only: output shapes "
            "depend on the splits, which XLA's static shapes cannot carry "
            "through jit (the reference's uneven path is likewise "
            "runtime-dispatched, operations.cc:1642-1727)")
    n = pset.size()
    local_d0s = tensor.dim0s if isinstance(tensor, PerRank) else None
    bundle, _ = _as_bundle(tensor, pset, allow_ragged=True)
    d0 = bundle.shape[1]
    smat = np.asarray(splits, dtype=np.int64)
    if smat.ndim == 1:
        smat = np.broadcast_to(smat, (n, n)).copy()
    if smat.shape != (n, n):
        raise ValueError(
            f"splits must be one row of length {n} or a ({n}, {n}) matrix, "
            f"got shape {tuple(smat.shape)}")
    if (smat < 0).any():
        raise ValueError("splits entries must be non-negative")
    if local_d0s is not None:
        # ragged per_rank bundle: each rank's row sum is bounded by that
        # rank's OWN first dimension, not the padded bundle's
        row_sums = smat.sum(axis=1)
        for i in range(n):
            if row_sums[i] > local_d0s[i]:
                raise ValueError(
                    f"sum of splits row {i} ({int(row_sums[i])}) exceeds "
                    f"rank {i}'s first dimension ({local_d0s[i]}) "
                    "(reference operations.cc:1703-1707)")
    elif (smat.sum(axis=1) > d0).any():
        raise ValueError(
            f"sum of splits entries exceeds the first dimension ({d0}) "
            "(reference operations.cc:1703-1707)")

    # The full matrix is always cross-validated symmetrically via its
    # digest (every process must fail, or none — a partial failure would
    # hang the processes whose columns happen to agree inside the XLA
    # collective). The per-row recv-splits negotiation additionally runs
    # when the set's chips map 1:1 onto its member processes (then the
    # engine's world == the matrix dimension; set positions and engine
    # ranks coincide because devices are rank-ordered process-major).
    crc = _i64_digest(smat)
    member_procs, one_to_one, my_pos = _member_process_view(pset)
    my_row = smat[my_pos] if one_to_one else ()
    resp, neg_name = _negotiate_eager(
        "alltoall", REQ_ALLTOALL, name, bundle.shape[1:],
        bundle.dtype, pset, splits=tuple(int(s) for s in my_row),
        splits_crc=crc)
    recv_splits = smat.T.copy()  # recv_splits[r][j] = rows rank j sends rank r
    if resp is not None and resp.recv_splits and one_to_one:
        mine = list(recv_splits[my_pos])
        if list(resp.recv_splits) != mine:
            raise ValueError(
                f"negotiated recv_splits {resp.recv_splits} disagree with "
                f"the local splits matrix column {mine}; processes passed "
                "different splits for the same alltoall")

    max_chunk = max(int(smat.max()), 1)
    offsets = np.zeros((n, n), np.int64)
    offsets[:, 1:] = np.cumsum(smat, axis=1)[:, :-1]
    k_range = np.arange(max_chunk)
    idx = np.minimum(offsets[:, :, None] + k_range[None, None, :], d0 - 1)
    mask = k_range[None, None, :] < smat[:, :, None]

    with _ALLTOALL(name):
        ch = _lb.channel(pset, neg_name)
        if ch is not None:
            # idx/mask derive from the cross-validated splits matrix, so
            # the leader's copies equal every rank's
            out = ch.compute(
                bundle[ch.pos],
                lambda rows: _eager_uneven_alltoall_fn(pset.mesh(), axis)(
                    jnp.stack(rows), jnp.asarray(idx, jnp.int32),
                    jnp.asarray(mask)))
        else:
            out = _eager_uneven_alltoall_fn(pset.mesh(), axis)(
                bundle, jnp.asarray(idx, jnp.int32), jnp.asarray(mask))
    # out: (n*n, max_chunk, ...); rows [r*n:(r+1)*n] = rank r's received
    # padded chunks, one per source rank
    out = out.reshape((n, n, max_chunk) + bundle.shape[2:])
    outputs = []
    for r in range(n):
        parts = [out[r, j, :int(recv_splits[r, j])] for j in range(n)]
        outputs.append(jnp.concatenate(parts, axis=0) if parts else
                       jnp.zeros((0,) + bundle.shape[2:], bundle.dtype))
    return outputs, recv_splits.astype(np.int32)


def reducescatter(tensor, *, op: ReduceOp = ReduceOp.SUM,
                  process_set: ProcessSet | None = None,
                  name: str | None = None, axis_name=None):
    """Reduce-scatter along dim 0: each rank receives one reduced chunk."""
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    _check_op_dtype(op, jnp.result_type(tensor if not isinstance(tensor, PerRank)
                                       else tensor.array))
    if _axis_is_bound(axis):
        return _reducescatter_traced(tensor, axis, op, 1.0,
                                     pset.axis_index_groups())
    lowered_op, post = handle_average(op, pset.size(), 1.0)
    if _contains_tracer(tensor):
        raise RuntimeError(
            "reducescatter() was called inside jit/pjit without a bound mesh axis. "
            "Run it under jax.shard_map over hvd.mesh() (or pass axis_name=) "
            "so the op can lower to an XLA collective.")
    bundle, _ = _as_bundle(tensor, pset)
    n = pset.size()
    if bundle.shape[1] % n != 0:
        raise ValueError(f"reducescatter dim0 ({bundle.shape[1]}) must be "
                         f"divisible by process set size ({n})")
    _resp, neg_name = _negotiate_eager("reducescatter", REQ_REDUCESCATTER,
                                       name, bundle.shape[1:], bundle.dtype,
                                       pset)
    with _REDUCESCATTER(name):
        ch = _lb.channel(pset, neg_name)
        if ch is not None:
            out = ch.compute(
                bundle[ch.pos],
                lambda rows: _eager_reducescatter_fn(
                    pset.mesh(), axis, lowered_op,
                    float(post))(jnp.stack(rows)))
        else:
            out = _eager_reducescatter_fn(pset.mesh(), axis, lowered_op,
                                          float(post))(bundle)
    return PerRank(out.reshape((n, out.shape[0] // n) + out.shape[1:]))


def barrier(*, process_set: ProcessSet | None = None, axis_name=None):
    """Block until every rank reaches the barrier (reference ``hvd.barrier``,
    ``operations.cc:1763-1795``). Under SPMD a device barrier is a tiny
    psum that we block on."""
    pset = _resolve(process_set)
    axis = _resolve_axis(axis_name)
    if _axis_is_bound(axis):
        return  # traced code is synchronous by construction
    # Queued async work must land before the barrier: every process
    # reaches this flush at the same program point, so the drain order is
    # rank-deterministic.
    from . import fusion_cycle
    fusion_cycle.flush_all("barrier")
    _negotiate_eager("barrier", REQ_BARRIER, None, (), jnp.int32, pset)
    fn = _eager_allreduce_fn(pset.mesh(), axis, ReduceOp.SUM, 1.0, 1.0)
    jax.block_until_ready(fn(jnp.zeros((pset.size(), 1), jnp.int32)))


_DTYPE_NAMES = {v: k for k, v in _DTYPE_IDS.items()}


def _execute_joined_zeros(responses) -> None:
    """Zero-contribution execution for a joined process (reference
    ``JoinOp``, ``collective_operations.h:275-290``: joined ranks allocate
    zero-filled buffers from response metadata and participate in the
    collective so the others can finish). Runs on the service cycle thread
    while the user thread blocks inside :func:`join`; programs are rebuilt
    through the same executors as the caller path so every process lowers
    the identical SPMD computation."""
    pset = _resolve(None)
    axis = _resolve_axis(None)
    n = pset.size()
    # ("barrier",) | ("allgather", dtype, rest, d0s, name) |
    # (dtype, shape, gid, op, pre, post, name) — the name is the
    # negotiated tensor name, which keys the loopback rendezvous so a
    # joined rank's zero contribution pairs with the active ranks'
    # executions (loopback/dispatch.py).
    items = []
    for resp in responses:
        if resp.type == REQ_BARRIER:
            items.append(("barrier",))
            continue
        if resp.type == REQ_ALLGATHER:
            dtype_name = _DTYPE_NAMES.get(resp.dtype)
            if dtype_name is None:
                raise RuntimeError(
                    f"hvd.join(): cannot reconstruct dtype id {resp.dtype} "
                    f"for zero contribution to {resp.tensor_names}")
            # This process is joined: its row count is 0 (the engine never
            # saw a request from it); peers' counts come on recv_splits.
            # The first enqueuer's full shape distinguishes scalar gathers
            # from zero-row tensor gathers and carries the trailing dims.
            first_shape = tuple(resp.shapes[0]) if resp.shapes else ()
            items.append(("allgather", jnp.dtype(dtype_name), first_shape,
                          tuple(int(s) for s in resp.recv_splits),
                          resp.tensor_names[0] if resp.tensor_names
                          else None))
            continue
        if resp.type != REQ_ALLREDUCE:
            raise RuntimeError(
                f"hvd.join(): another process scheduled a "
                f"{resp.type_name} ({resp.tensor_names}) while this one is "
                "joined; zero contribution is defined for allreduce/"
                "allgather/barrier only (reference JoinOp semantics)")
        dtype_name = _DTYPE_NAMES.get(resp.dtype)
        if dtype_name is None:
            raise RuntimeError(
                f"hvd.join(): cannot reconstruct dtype id {resp.dtype} for "
                f"zero contribution to {resp.tensor_names}")
        for tname, shape, gid in zip(resp.tensor_names, resp.shapes,
                                     resp.group_ids):
            items.append((jnp.dtype(dtype_name), tuple(shape), gid,
                          ReduceOp(resp.reduce_op), float(resp.prescale),
                          float(resp.postscale), tname))
    def _tensor_bytes(dt, shape):
        return int(np.prod(shape) or 1) * jnp.dtype(dt).itemsize

    i = 0
    while i < len(items):
        if items[i] == ("barrier",):
            fn = _eager_allreduce_fn(pset.mesh(), axis, ReduceOp.SUM,
                                     1.0, 1.0)
            jax.block_until_ready(fn(jnp.zeros((n, 1), jnp.int32)))
            i += 1
            continue
        if items[i][0] == "allgather":
            _, dt, first_shape, proc_d0s, tname = items[i]
            rest = first_shape[1:] if first_shape else ()
            # Expand per-process counts to per-rank rows and apply the
            # SAME routing rule as the active path (allgather() above):
            # all engine dims equal -> the uniform program; otherwise the
            # ragged program padded to max over the engine view.
            member_procs, _, _ = _member_process_view(pset)
            pos = {p: j for j, p in enumerate(member_procs)}
            d0s = [int(proc_d0s[pos[runtime.process_of_rank(r)]])
                   for r in pset.ranks]
            _autotune.record(int(np.prod(rest) or 1) * dt.itemsize
                             * max(max(d0s), 1))
            scalar = len(first_shape) == 0
            if scalar or max(d0s) > 0:
                ch = _lb.channel(pset, tname)
                if ch is not None:
                    # loopback: a joined rank contributes ZERO ROWS (a
                    # zero SCALAR for scalar gathers — the active path's
                    # ndim==1 branch stacks one value per rank, like the
                    # real (n, 1) program) and discards the result —
                    # participation parity with the active branch, which
                    # skips only the all-dims-zero non-scalar gather.
                    # The combiner must be the SAME closure the active
                    # side supplies: whichever rank completes the slot
                    # runs it.
                    if scalar:
                        ch.compute(jnp.zeros((), dt), _lb_stack_parts)
                    else:
                        ch.compute(jnp.zeros((0,) + tuple(rest), dt),
                                   _lb_gather_parts(rest, dt))
                    i += 1
                    continue
            if len(set(d0s)) == 1:
                # uniform (possibly zero-row) — mirror the active path's
                # uniform branch exactly, hierarchical knob included
                if len(first_shape) > 0 and d0s[0] == 0:
                    # zero-row uniform gather: active peers run NO program
                    i += 1
                    continue
                if len(first_shape) == 0:  # scalars: (n, 1) program
                    zb = jnp.zeros((n, 1), dt)
                else:
                    zb = jnp.zeros((n, d0s[0]) + tuple(rest), dt)
                if hierarchical.hierarchical_allgather_enabled_for(pset):
                    out = hierarchical._eager_hier_allgather_fn(
                        hierarchical.hierarchical_mesh())(zb)
                else:
                    out = _eager_allgather_fn(pset.mesh(), axis)(zb)
            else:
                maxd = max(d0s)
                out = _execute_ragged_allgather(
                    jnp.zeros((n, max(maxd, 1)) + tuple(rest), dt), d0s,
                    maxd, pset, axis)
            jax.block_until_ready(out)
            i += 1
            continue
        dt, shape, gid, op, pre, post, tname = items[i]
        if gid < 0:
            # mirror the caller path's autotune accounting so sample
            # boundaries (and the synced tuning decisions that ride them)
            # stay aligned across joined and active processes
            _autotune.record(_tensor_bytes(dt, shape))
            out = _execute_allreduce_bundle(
                jnp.zeros((n,) + shape, dt), pset, axis, op, pre, post,
                lb_key=tname)
            jax.block_until_ready(out)
            i += 1
        else:
            group = []
            while (i < len(items) and items[i] != ("barrier",)
                   and items[i][2] == gid):
                group.append(items[i])
                i += 1
            _autotune.record(sum(_tensor_bytes(d, shp)
                                 for d, shp, *_rest in group))
            bundles = [jnp.zeros((n,) + shp, d)
                       for d, shp, *_rest in group]
            outs = _execute_grouped_bundles(
                bundles, pset, axis, group[0][3], group[0][4], group[0][5],
                len(bundles), lb_key=group[0][6])
            jax.block_until_ready(outs)


def join() -> int:
    """Reference ``hvd.join`` (``operations.cc:1729-1761``): lets a process
    with uneven data drop out — until every process joins, it contributes
    zero-filled tensors to collectives the others schedule (allreduce and
    barrier; the reference's JoinOp covers the same). Returns the last
    joined rank.

    Single-process jobs (one controller sees every rank's data) have no
    uneven-participation problem; ``join`` degenerates to a barrier there.
    """
    pset = _resolve(None)
    from .. import engine_service
    svc = engine_service.get_service(pset)
    if svc is None:
        barrier()
        return runtime.size() - 1
    # A joining process first lands its own queued async work — after the
    # JOIN is negotiated it may only contribute zeros.
    from . import fusion_cycle
    fusion_cycle.flush_all("join")
    name = _auto_name("join", pset)
    last_proc = svc.join(name)
    if last_proc < 0:
        return runtime.size() - 1
    # last joined *process* -> its highest-owned chip rank
    return max(r for r in range(runtime.size())
               if runtime.process_of_rank(r) == last_proc)


# ---------------------------------------------------------------------------
# async handles (reference torch mpi_ops.py:914-953 poll/synchronize) over
# the cycle-driven fusion scheduler (ops/fusion_cycle.py): *_async calls
# enqueue into per-signature pending queues and dispatch at the next flush
# (threshold / cycle / synchronize / barrier), coalescing independently
# submitted small tensors into one grouped wire program — the reference's
# fusion-buffer cycle (operations.cc:385-806). HVD_CYCLE_TIME=0 restores
# immediate per-call dispatch.
# ---------------------------------------------------------------------------

def _result_arrays(result) -> list:
    """The device arrays carried by a handle result. PerRank bundles are
    opaque leaves to the jax.tree utilities — ``jax.block_until_ready``
    silently skips them and ``is_ready`` probes default to True — so
    readiness checks and device blocks must unwrap to ``.array``, inside
    grouped result lists too."""
    seq = result if isinstance(result, (list, tuple)) else [result]
    return [r.array if isinstance(r, PerRank) else r for r in seq]


class Handle:
    """Completion handle for *_async ops. The result may still be queued
    in the fusion cycle (dispatched at the next flush) or already in
    flight (JAX dispatch is itself asynchronous); ``synchronize`` flushes,
    blocks, and is idempotent — repeated calls return the cached result
    without re-walking the arrays."""

    __slots__ = ("_result", "_synced")

    def __init__(self, result=None):
        self._result = result
        self._synced = False

    def _materialize(self):
        """The dispatched result (queued subclass flushes first)."""
        return self._result

    def _dispatched(self) -> bool:
        return True

    def poll(self) -> bool:
        """True when the result landed. A still-queued handle first
        triggers a flush of its own entry — without that, polling an
        unflushed handle would spin forever waiting on a dispatch that
        nothing else triggers. A handle whose flush FAILED (or was
        aborted by a service reset) polls True — "synchronize() will not
        block" — and the error surfaces there; poll itself never raises
        (the reference's poll contract)."""
        if self._synced:
            return True
        if not self._dispatched():
            return False
        try:
            result = self._materialize()
        except Exception:
            return True  # completed in error; synchronize() raises it
        leaves = jax.tree.leaves(_result_arrays(result))
        return all(getattr(l, "is_ready", lambda: True)() for l in leaves)

    def synchronize(self):
        if self._synced:
            return self._result
        result = self._materialize()
        jax.block_until_ready(_result_arrays(result))
        self._result = result
        self._synced = True
        return self._result

    def flush(self) -> None:
        """Dispatch the op NOW if it is still queued in the fusion cycle
        (non-blocking; no-op on an already-dispatched handle). The
        bucketed optimizer path calls this after each bucket's submission
        so bucket k's collective is in flight while bucket k+1 fuses
        host-side — without waiting for a threshold or cycle trigger."""
        # immediate-path handles are already dispatched

    def result(self):
        """The dispatched result WITHOUT blocking on device completion
        (``synchronize()`` is the blocking wait): downstream eager ops
        chain on in-flight arrays through device-side data dependencies,
        so update math can run while later buckets' collectives are
        still on the wire. Re-raises a failed flush's error.

        On backends where that chaining is unsafe
        (``envs.eager_chain_enabled``: the XLA CPU client's shared
        thread pool lets consumer programs starve an in-flight
        collective's rendezvous — a reproduced deadlock) this degrades
        to ``synchronize()``."""
        if self._synced:
            return self._result
        if not envs.eager_chain_enabled(jax.devices()[0].platform):
            return self.synchronize()
        return self._materialize()


class _QueuedHandle(Handle):
    """Handle over a fusion-cycle queue entry (futures-style): the op has
    not dispatched yet; poll/synchronize flush the entry's queue."""

    __slots__ = ("_entry",)

    def __init__(self, entry):
        super().__init__(None)
        self._entry = entry

    def _dispatched(self) -> bool:
        from . import fusion_cycle
        return fusion_cycle.scheduler().poll_entry(self._entry)

    def _materialize(self):
        from . import fusion_cycle
        results = fusion_cycle.scheduler().wait_result(self._entry)
        return list(results) if self._entry.grouped else results[0]

    def flush(self) -> None:
        from . import fusion_cycle
        fusion_cycle.scheduler().flush_entry(self._entry, "bucket")


def _is_custom_compressor(compression) -> bool:
    """A user Compressor subclass with its own compress/decompress pair
    but no cast-style ``wire_dtype`` — only it knows the wire format, so
    it must wrap the call instead of routing through wire-dtype fusion."""
    from .compression import NoneCompressor
    return (compression is not None
            and getattr(compression, "wire_dtype", None) is None
            and hasattr(compression, "compress")
            and compression is not NoneCompressor)


def allreduce_async(tensor, *, compression=None, **kw) -> Handle:
    """Async allreduce (reference ``allreduce_async_``,
    ``torch/mpi_ops.py:124``): enqueues into the fusion cycle and returns
    immediately; the collective dispatches at the next flush, fused with
    other pending same-signature submissions. ``compression`` routes the
    tensor over the wire in the compressed dtype (decompressed on
    synchronize)."""
    from . import fusion_cycle
    h = fusion_cycle.queue_allreduce([tensor], grouped=False,
                                     compression=compression, **kw)
    if h is not None:
        return h
    if _is_custom_compressor(compression) \
            or getattr(compression, "wire_dtype", None) is not None:
        return Handle(grouped_allreduce([tensor], compression=compression,
                                        **kw)[0])
    return Handle(allreduce(tensor, **kw))


def grouped_allreduce_async(tensors, *, compression=None, **kw) -> Handle:
    """Handle over a fused grouped allreduce (reference
    ``grouped_allreduce_async``, ``torch/mpi_ops.py:375``). The group
    rides the fusion cycle atomically (never split across flushes) and
    may fuse further with other pending same-signature submissions."""
    if not tensors:
        return Handle([])
    from . import fusion_cycle
    with _SUBMIT(kw.get("name"), tensors=len(tensors)):
        h = fusion_cycle.queue_allreduce(list(tensors), grouped=True,
                                         compression=compression, **kw)
    if h is not None:
        return h
    return Handle(grouped_allreduce(tensors, compression=compression, **kw))


def allgather_async(tensor, **kw) -> Handle:
    from . import fusion_cycle
    h = fusion_cycle.queue_allgather(tensor, **kw)
    if h is not None:
        return h
    return Handle(allgather(tensor, **kw))


def broadcast_async(tensor, root_rank, **kw) -> Handle:
    from . import fusion_cycle
    h = fusion_cycle.queue_broadcast(tensor, root_rank, **kw)
    if h is not None:
        return h
    return Handle(broadcast(tensor, root_rank, **kw))


def grouped_broadcast_async(tensors, root_rank, *, process_set=None,
                            name=None, axis_name=None) -> Handle:
    """Handle over a fused broadcast of a tensor list: every leaf rides
    the broadcast queue (one entry per tensor, so independently-submitted
    broadcasts of the same root coalesce too); ``broadcast_parameters``
    synchronizes a whole model through one flush."""
    from . import fusion_cycle
    handles = []
    with _SUBMIT(name, tensors=len(tensors)):
        for i, t in enumerate(tensors):
            h = fusion_cycle.queue_broadcast(
                t, root_rank, process_set=process_set,
                name=None if name is None else f"{name}.{i}",
                axis_name=axis_name)
            if h is None:
                break
            handles.append(h)
    if len(handles) == len(tensors):
        return _MultiHandle(handles)
    # scheduler off / unplannable leaf: drain the queued prefix (keeps
    # submission order), then broadcast only the remaining tensors under
    # a distinct name base — reusing `name` would renegotiate the
    # prefix's "{name}.0..." names with the remainder's metadata
    prefix = [h.synchronize() for h in handles]
    rest = grouped_broadcast(tensors[len(handles):], root_rank,
                             process_set=process_set,
                             name=None if name is None else f"{name}.rest",
                             axis_name=axis_name)
    return Handle(prefix + rest)


class _MultiHandle(Handle):
    """Aggregate handle over per-tensor queued handles (grouped
    broadcast): synchronizes all, returns the result list."""

    __slots__ = ("_handles",)

    def __init__(self, handles):
        super().__init__(None)
        self._handles = handles

    def _dispatched(self) -> bool:
        return all(h._dispatched() for h in self._handles)

    def _materialize(self):
        # waits only on the dispatch events (no device block) — poll()
        # must stay non-blocking; synchronize() adds the
        # block_until_ready over the whole list in Handle. One scheduler
        # call, so the wait is one span, not one per tensor.
        from . import fusion_cycle
        return [r[0] for r in fusion_cycle.scheduler().wait_results(
            [h._entry for h in self._handles])]

    def flush(self) -> None:
        for h in self._handles:
            h.flush()


def alltoall_async(tensor, splits=None, **kw) -> Handle:
    return Handle(alltoall(tensor, splits, **kw))


def poll(handle: Handle) -> bool:
    return handle.poll()


def synchronize(handle: Handle):
    return handle.synchronize()


# -- queued-entry executors (multi-process flush path: negotiation already
#    batched by the scheduler, program composition = submission-time) -------

# timer-boundary: queued-entry executors only run for svc-backed flushes,
# which the cycle timer never drains (rank-deterministic triggers only).
def _run_queued_allreduce(tensors, pset: ProcessSet, axis, op: ReduceOp,  # hvdlint: timer-boundary
                          pre_f: float, post_f: float, compression,
                          label: str) -> list:
    """Execute one queued allreduce entry (single tensor or atomic group)
    with its submission-time composition — the same program shape a joined
    rank reconstructs from response metadata (``_execute_joined_zeros``),
    so active and joined processes always lower identical SPMD programs."""
    lowered_op, post = handle_average(op, pset.size(), post_f)
    pre, post = float(pre_f), float(post)
    bundles = [_as_bundle(t, pset)[0] for t in tensors]
    wire_dts = [_wire_dtype_of(b, compression) for b in bundles]
    _autotune.record(sum(int(np.prod(b.shape[1:]) or 1) * dt.itemsize
                         for b, dt in zip(bundles, wire_dts)))
    with (_ALLREDUCE if len(tensors) == 1 else _GROUPED_ALLREDUCE)(label):
        if len(bundles) == 1:
            # single entry: the un-fused program, the exact shape a joined
            # rank rebuilds from the response (wire-dtype zeros, gid=-1)
            b, src = bundles[0], bundles[0].dtype
            if wire_dts[0] != src:
                b = b.astype(wire_dts[0])
            out = _execute_allreduce_bundle(b, pset, axis, lowered_op,
                                            pre, post, lb_key=label)
            return [out.astype(src) if wire_dts[0] != src else out]
        return _execute_grouped_bundles(bundles, pset, axis, lowered_op,
                                        pre, post, len(tensors),
                                        wire_dtypes=wire_dts, lb_key=label)


def _run_queued_broadcast(tensors, pset: ProcessSet, axis, root_rank: int,  # hvdlint: timer-boundary
                          label: str) -> list:
    """Execute one queued broadcast entry (submission-time composition;
    see :func:`_run_queued_allreduce`)."""
    n = pset.size()
    root_pos = pset.ranks.index(root_rank)
    bundles = [_as_bundle(t, pset)[0] for t in tensors]
    _autotune.record(sum(b.nbytes // max(b.shape[0], 1) for b in bundles))
    with (_BROADCAST if len(tensors) == 1 else _GROUPED_BROADCAST)(label):
        if len(bundles) == 1:
            return [_execute_broadcast_bundle(bundles[0], pset, axis,
                                              root_pos, lb_key=label)]
        ch = _lb.channel(pset, label)
        if ch is not None:
            return _lb_grouped_broadcast(ch, bundles, pset, axis,
                                         root_pos, len(tensors))
        fused_inputs, metas = _fuse_by_dtype(bundles, n)
        fn = _eager_grouped_broadcast_fn(pset.mesh(), axis, root_pos,
                                         len(fused_inputs))
        return _split_fused(fn(*fused_inputs), metas, len(tensors))


# ---------------------------------------------------------------------------
# object collectives (reference torch/functions.py broadcast_object /
# allgather_object)
# ---------------------------------------------------------------------------

def broadcast_object(obj, root_rank: int = 0, *, name: str | None = None):
    """Broadcast a picklable object from the process owning global chip
    ``root_rank`` (reference ``broadcast_object``, ``torch/functions.py``).
    Objects live per controller process, so this is a process-level
    broadcast; ``root_rank`` is a chip rank like everywhere else in the
    API and is mapped to its owning process."""
    del name
    if runtime.process_count() <= 1:
        return obj
    ch = _lb.object_channel()
    if ch is not None:
        # Loopback worlds exchange through the hub: jax's multihost
        # utilities need a real multi-process backend. Only the root's
        # payload travels.
        root_process = runtime.process_of_rank(root_rank)
        mine = pickle.dumps(obj) if runtime.process_rank() == root_process \
            else b""
        payloads = ch.gather(mine)
        return pickle.loads(payloads[root_process])
    from jax.experimental import multihost_utils
    root_process = runtime.devices()[root_rank].process_index
    is_source = runtime.process_rank() == root_process
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    size = multihost_utils.broadcast_one_to_all(
        np.array(len(payload), np.int64), is_source=is_source)
    buf = np.zeros(int(size), np.uint8)
    if is_source:
        buf[:] = payload
    out = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
    return pickle.loads(out.tobytes())


def allgather_object(obj, *, name: str | None = None) -> list:
    """Gather a picklable object from every *process* (reference
    ``allgather_object``)."""
    del name
    if runtime.process_count() <= 1:
        return [obj]
    ch = _lb.object_channel()
    if ch is not None:
        return [pickle.loads(b) for b in ch.gather(pickle.dumps(obj))]
    return [pickle.loads(b) for b in _gather_bytes(pickle.dumps(obj))]


def _gather_bytes(data: bytes) -> list:
    from jax.experimental import multihost_utils
    n = runtime.process_count()
    size = np.array(len(data), np.int64)
    sizes = multihost_utils.process_allgather(size)
    max_size = int(np.max(sizes))
    buf = np.zeros(max_size, np.uint8)
    buf[: len(data)] = np.frombuffer(data, np.uint8)
    bufs = multihost_utils.process_allgather(buf)
    return [bufs[i, : int(sizes[i])].tobytes() for i in range(n)]
