"""The traced gradient sync's second emission: rounds of collective-permutes.

``DistributedOptimizer``'s traced sync sums the gradient tree over the
bound data-parallel axis. Emitted as one ``lax.psum`` a leaf, the TPU
compiler combines the sums into a dozen ``all-reduce`` operations and runs
each synchronously (ledger, PR 30, ``gpt2m-traced-4chip``:
``exposed_collective_ms`` = ``collective_ms`` = 28.3 of a 121.5 ms step).
``collective-permute`` is the collective it runs asynchronously at the
compile options a user's own ``jax.jit`` gets, so where
:func:`permute_rounds_selected` holds the same sum is emitted as a
bandwidth-optimal ring instead: a reduce-scatter in ``k - 1`` rounds on
``1/k``-size chunks, then an all-gather in ``k - 1`` rounds, each round one
``lax.ppermute`` between physical neighbours, each leaf split in two
halves that travel the ring in opposite directions so that both links of
a chip carry bytes in every round.

What that buys is bounded, and PERF.md (section 6, PR 31) has the
measurements: the chip's one core also has to do the rounds' additions and
copies (7 passes over the bytes, which the all-reduce does in its DMAs),
and the compiler's scheduler puts the rounds behind the backward pass,
beside the optimizer's update. The four-chip GPT-2 step went from 121.9 to
118.9 ms, not to the 84 of one chip.

Every chunk is summed in one fixed order and ends at one owner before it
is gathered: all members hold bit-identical results. Leaves are chunked
along a dimension they already have, as the chip lays them out
(:func:`device_layout`); nothing is flattened or concatenated. One
algorithm, two emissions: there is no knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout

from .. import metrics as _metrics
from .. import timeline as _timeline
from .reduce_ops import ReduceOp

# Device scope (docs/timeline.md): the rounds' permutes with the core's
# own additions, copies and slices between them.
_SCOPE_ROUNDS = _timeline.scope("exchange.rounds")

# Below this a leaf stays on ``lax.psum``, which the compiler combines
# into a few all-reduces. Measured on the chip (PERF.md section 6, PR 31):
# with GPT-2's 4 MiB attention kernels on the rounds too the step gains
# 0.45 ms more and holds 66 MB more, past the benchmark's 1 % bound on
# peak HBM; its 16 MiB MLP kernels are two thirds of the bytes.
MIN_LEAF_BYTES = 8 << 20
# The ring needs k - 1 rounds each way; past one host's chips the rounds'
# latency outgrows what a round carries.
MAX_AXIS_SIZE = 8
# The large leaves go bucket by bucket, each behind the one before: a
# transformer layer's ~50 MB of float32 gradients.
BUCKET_BYTES = 64 << 20

_PATH_PERMUTE = _metrics.TRACED_EXCHANGE.bind({"path": "permute_rounds"})
_PATH_PSUM = _metrics.TRACED_EXCHANGE.bind({"path": "psum"})
_LAST_BUCKETS = _metrics.TRACED_EXCHANGE_SHAPE.bind({"what": "buckets"})
_LAST_ROUNDS = _metrics.TRACED_EXCHANGE_SHAPE.bind({"what": "rounds"})


def neighbour_ring(devices):
    """Ranks of ``devices`` (in axis order) as a cycle of physical
    neighbours, or ``None`` where they do not form one: chips of one
    process and one slice whose ``coords`` differ by one step in one
    dimension from each to the next and from the last to the first. On a
    2x2 that is ranks ``(0, 1, 3, 2)``: rank order itself takes two
    diagonals."""
    k = len(devices)
    coords = [getattr(d, "coords", None) for d in devices]
    if (k < 2 or any(c is None for c in coords)
            or len({tuple(c) for c in coords}) != k
            or len({d.process_index for d in devices}) != 1
            or len({getattr(d, "slice_index", 0) for d in devices}) != 1):
        return None

    def adjacent(a, b):
        return sum(abs(x - y) for x, y in zip(coords[a], coords[b])) == 1

    def extend(path):
        if len(path) == k:
            return path if k == 2 or adjacent(path[-1], path[0]) else None
        for nxt in range(k):
            if nxt not in path and adjacent(path[-1], nxt):
                found = extend(path + [nxt])
                if found:
                    return found
        return None

    ring = extend([0])
    return tuple(ring) if ring else None


def device_layout(device, dtype, shape):
    """Dimensions of ``shape`` from major to minor as ``device`` lays such
    an array out by default. The TPU keeps no fixed order: it puts the
    dimension that fills its 128 lanes best last (``f32[1024,16,64]`` and
    GPT-2's ``f32[1024,50257]`` head are stored with their 1024 as the
    lanes), and a gradient takes its parameter's layout. Row-major where
    the backend does not say."""
    try:
        return tuple(Layout.from_pjrt_layout(device.client.get_default_layout(
            np.dtype(dtype), tuple(shape), device)).major_to_minor)
    except (AttributeError, TypeError, ValueError, RuntimeError):
        return tuple(range(len(shape)))


def split_dim(shape, k, layout=None, itemsize=4):
    """``(dimension, parts)`` to chunk a leaf of ``shape`` along: the
    most major dimension ``2k`` divides (a half for each direction), else
    the most major ``k`` divides (one direction); ``None`` where neither
    exists. ``layout`` is :func:`device_layout` (row-major by default),
    and only a split the tiled layout takes as a bitcast counts: never
    the minor-most dimension of a matrix (the lanes), and the one before
    it only in chunks of whole tiles (8 rows of 4 bytes). GPT-2's two
    vocabulary-sized leaves have none (50257 rows, and their 1024 columns
    are the lanes): splitting the lanes made the compiler pad and copy
    all 206 MB of each, twice."""
    order = tuple(range(len(shape))) if layout is None else tuple(layout)
    rows = 8 * max(1, 4 // itemsize)
    for parts in (2 * k, k):
        for at, dim in enumerate(order):
            n = shape[dim]
            if not n or n % parts or (len(order) > 1
                                      and at == len(order) - 1):
                continue
            if at == len(order) - 2 and (n // parts) % rows:
                continue
            return dim, parts
    return None


def permute_rounds_selected(*, platform, axis_size, ring, op, groups,
                            mesh_spec, compressed, fused_threshold,
                            dtype, nbytes, shape, layout=None) -> bool:
    """The selection rule of the traced sync, on its observations alone:
    ``axis_size`` is 0 for an axis that is not bound, ``ring`` is
    :func:`neighbour_ring` of the axis's devices, ``layout`` is
    :func:`device_layout` of the leaf."""
    return (platform == "tpu"
            and 2 <= axis_size <= MAX_AXIS_SIZE
            and ring is not None and len(ring) == axis_size
            and op in (ReduceOp.SUM, ReduceOp.AVERAGE)
            and groups is None and mesh_spec is None
            and not compressed and fused_threshold <= 0
            and jnp.issubdtype(dtype, jnp.floating)
            and nbytes >= MIN_LEAF_BYTES
            and split_dim(shape, axis_size, layout,
                          jnp.dtype(dtype).itemsize) is not None)


class _Leaf:
    """One leaf's way round the ring: ``dim`` split as ``(parts, n/parts)``
    with the part kept as a dimension of size one in every chunk, the
    first ``k`` parts travelling forward, the rest (if any) backward."""

    def __init__(self, x, k, layout):
        self.dim, parts = split_dim(x.shape, k, layout, x.dtype.itemsize)
        shape = x.shape
        self.pieces = x.reshape(shape[:self.dim]
                                + (parts, shape[self.dim] // parts)
                                + shape[self.dim + 1:])
        self.halves = ((0, +1), (k, -1)) if parts == 2 * k else ((0, +1),)
        self.acc = None         # per half: the chunk in flight

    def _at(self, part):
        at = [0] * self.pieces.ndim
        at[self.dim] = part
        return at

    def piece(self, part):
        sizes = list(self.pieces.shape)
        sizes[self.dim] = 1
        return lax.dynamic_slice(self.pieces, self._at(part), sizes)

    def place(self, chunk, part):
        self.pieces = lax.dynamic_update_slice(self.pieces, chunk,
                                               self._at(part))


def allreduce_rounds(leaves, axis, ring, *, average=False, pre=1.0,
                     post=1.0, layouts=None):
    """:func:`_rounds`, which is under a ``jax.jit`` of its own: the 24
    buckets of a 24-layer model are one bucket's shapes 24 times over, so
    the rounds are traced and lowered once and called 24 times (the
    compiler inlines the calls; the step's lowering stays what it was to
    a second). Called only while the user's step is traced: part of that
    program, never an eager dispatch of its own."""
    return _rounds(
        list(leaves), axis=axis, ring=tuple(ring), average=bool(average),
        pre=float(pre), post=float(post),
        layouts=None if layouts is None else tuple(
            None if layout is None else tuple(layout)
            for layout in layouts))


@functools.partial(jax.jit, static_argnames=(
    "axis", "ring", "average", "pre", "post", "layouts"))
def _rounds(leaves, *, axis, ring, average, pre, post, layouts):
    """Sum (or average) each of ``leaves`` over the bound ``axis`` by ring
    reduce-scatter and all-gather rounds of ``lax.ppermute``; ``ring`` is
    the axis indices in neighbour order. Every leaf has a dimension
    ``len(ring)`` divides (:func:`split_dim`, under its entry of
    ``layouts`` if given). Leaves come back in order,
    bit-identical on every member."""
    with _SCOPE_ROUNDS():
        k = len(ring)
        where = [0] * k
        for at, rank in enumerate(ring):
            where[rank] = at
        pos = jnp.asarray(where, jnp.int32)[lax.axis_index(axis)]
        perms = {+1: [(ring[i], ring[(i + 1) % k]) for i in range(k)],
                 -1: [(ring[i], ring[(i - 1) % k]) for i in range(k)]}
        # part[sign][t]: the chunk a member holds t steps along direction
        # sign: a partial sum moves one step a round and ends, complete, at
        # the member of its number; the gather sends it on round the ring
        part = {sign: [(pos - sign * t) % k for t in range(k + 1)]
                for sign in perms}
        scale = post / k if average else post

        def own(leaf, base, sign, t):
            chunk = leaf.piece(base + part[sign][t])
            return chunk if pre == 1.0 else chunk * pre

        work = [_Leaf(x, k, layout) for x, layout in zip(
            leaves, layouts or [None] * len(leaves))]
        for leaf in work:
            leaf.acc = [own(leaf, base, sign, 1) for base, sign in leaf.halves]
        for t in range(2, k + 1):                       # reduce-scatter
            for leaf in work:
                leaf.acc = [
                    lax.ppermute(acc, axis, perms[sign])
                    + own(leaf, base, sign, t)
                    for acc, (base, sign) in zip(leaf.acc, leaf.halves)]
        for leaf in work:
            # every read of the leaf happens before its first chunk is put
            # back, so the gather overwrites the gradient in place (without
            # this the compiler copies each leaf whole first)
            leaf.acc = lax.optimization_barrier(leaf.acc)
            if scale != 1.0:
                leaf.acc = [acc * jnp.asarray(scale, acc.dtype)
                            for acc in leaf.acc]
            for acc, (base, sign) in zip(leaf.acc, leaf.halves):
                leaf.place(acc, base + part[sign][0])
        for t in range(1, k):                           # all-gather
            for leaf in work:
                leaf.acc = [lax.ppermute(acc, axis, perms[sign])
                            for acc, (_, sign) in zip(leaf.acc, leaf.halves)]
                for acc, (base, sign) in zip(leaf.acc, leaf.halves):
                    leaf.place(acc, base + part[sign][t])
        return [leaf.pieces.reshape(x.shape) for leaf, x in zip(work, leaves)]


def production_order(leaves):
    """Positions into ``leaves`` in the order the trace produced them: a
    jaxpr numbers its variables as it makes them, and the backward pass
    makes the last layer's gradients first. A parameter tree's own order
    is no guide (``block_10`` sorts before ``block_2``). Where a leaf is
    no jaxpr variable, the tree backwards, as the eager buckets go."""
    made = [getattr(getattr(leaf, "val", None), "count", None)
            for leaf in leaves]
    if any(not isinstance(at, int) for at in made):
        return list(reversed(range(len(leaves))))
    return sorted(range(len(leaves)), key=made.__getitem__)


def count(selected: bool) -> None:
    """One leaf, one trace, one count (``hvd_traced_exchange_total``)."""
    (_PATH_PERMUTE if selected else _PATH_PSUM).inc()


def record_trace(buckets: int, leaves: int, k: int) -> None:
    """The last trace's emission (``hvd_traced_exchange_last_trace``): a
    leaf takes ``2(k - 1)`` rounds (two permutes a round where it
    travels both ways)."""
    _LAST_BUCKETS.set(buckets)
    _LAST_ROUNDS.set(2 * (k - 1) * leaves)
