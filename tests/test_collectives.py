"""Collective op tests, modeled on the reference's op×dtype×mode matrix
(``test/parallel/test_tensorflow.py`` / ``test_torch.py`` — allreduce
sum/average/min/max, allgather, broadcast, alltoall, grouped ops, barrier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P


N = 8


def _rank_values(shape=(4,), dtype=jnp.float32, mult=1.0):
    """values[i] = (i+1) * mult * ones(shape)"""
    return [jnp.full(shape, (i + 1) * mult, dtype=dtype) for i in range(N)]


# ---------------------------------------------------------------- eager mode

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_allreduce_sum_eager(hvd, dtype):
    vals = _rank_values(dtype=dtype)
    out = hvd.allreduce(hvd.per_rank(vals), op=hvd.Sum)
    expected = sum(range(1, N + 1))
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               np.full((4,), expected), rtol=1e-2)


def test_allreduce_average_eager(hvd):
    vals = _rank_values()
    out = hvd.allreduce(hvd.per_rank(vals), op=hvd.Average)
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 4.5), rtol=1e-6)


def test_allreduce_default_is_average(hvd):
    vals = _rank_values()
    out = hvd.allreduce(hvd.per_rank(vals))
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 4.5), rtol=1e-6)


def test_allreduce_min_max_product(hvd):
    vals = _rank_values()
    out_min = hvd.allreduce(hvd.per_rank(vals), op=hvd.Min)
    out_max = hvd.allreduce(hvd.per_rank(vals), op=hvd.Max)
    out_prod = hvd.allreduce(hvd.per_rank(vals), op=hvd.Product)
    np.testing.assert_allclose(np.asarray(out_min), np.full((4,), 1.0))
    np.testing.assert_allclose(np.asarray(out_max), np.full((4,), 8.0))
    np.testing.assert_allclose(np.asarray(out_prod),
                               np.full((4,), float(np.prod(range(1, 9)))))


def test_allreduce_prescale_postscale(hvd):
    vals = _rank_values()
    out = hvd.allreduce(hvd.per_rank(vals), op=hvd.Sum,
                        prescale_factor=2.0, postscale_factor=0.5)
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 36.0))


def test_allreduce_average_int_raises(hvd):
    with pytest.raises(TypeError):
        hvd.allreduce(hvd.per_rank(_rank_values(dtype=jnp.int32)), op=hvd.Average)


def test_allreduce_replicated_input(hvd):
    # plain array = same contribution from every rank
    out = hvd.allreduce(jnp.ones((3,)), op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(out), np.full((3,), 8.0))


def test_grouped_allreduce_eager(hvd):
    t1 = _rank_values((4,))
    t2 = _rank_values((2, 3), mult=10.0)
    t3 = [jnp.full((5,), i + 1, jnp.int32) for i in range(N)]
    outs = hvd.grouped_allreduce(
        [hvd.per_rank(t1), hvd.per_rank(t2), hvd.per_rank(t3)], op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(outs[0]), np.full((4,), 36.0))
    np.testing.assert_allclose(np.asarray(outs[1]), np.full((2, 3), 360.0))
    np.testing.assert_array_equal(np.asarray(outs[2]), np.full((5,), 36, np.int32))
    assert outs[2].dtype == jnp.int32


def test_allgather_eager(hvd):
    vals = [jnp.full((2, 3), i, jnp.float32) for i in range(N)]
    out = hvd.allgather(hvd.per_rank(vals))
    assert out.shape == (16, 3)
    for i in range(N):
        np.testing.assert_allclose(np.asarray(out[2 * i:2 * i + 2]), i)


def test_allgather_scalars(hvd):
    out = hvd.allgather(hvd.per_rank([jnp.float32(i) for i in range(N)]))
    np.testing.assert_allclose(np.asarray(out), np.arange(N, dtype=np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_allgather_ragged(hvd, dtype):
    """Per-rank different first dims (the reference's allgatherv contract,
    collective_operations.h:143-178): output concatenates each rank's
    valid rows in rank order."""
    d0s = [(i % 3) + 1 for i in range(N)]  # 1,2,3,1,2,3,...
    vals = [jnp.full((d0s[i], 3), i, dtype) for i in range(N)]
    bundle = hvd.per_rank(vals)
    assert bundle.dim0s == tuple(d0s)
    out = hvd.allgather(bundle)
    assert out.shape == (sum(d0s), 3)
    assert out.dtype == jnp.dtype(dtype)
    off = 0
    for i in range(N):
        np.testing.assert_allclose(
            np.asarray(out[off:off + d0s[i]], np.float64), i)
        off += d0s[i]


def test_allgather_ragged_zero_rows(hvd):
    """A rank may contribute zero rows (the joined-rank contribution)."""
    d0s = [2, 0, 1] + [1] * (N - 3)
    vals = [jnp.full((d0s[i], 2), float(i + 1)) for i in range(N)]
    out = hvd.allgather(hvd.per_rank(vals))
    assert out.shape == (sum(d0s), 2)
    np.testing.assert_allclose(np.asarray(out[:2]), 1.0)
    np.testing.assert_allclose(np.asarray(out[2:3]), 3.0)  # rank 1 skipped


def test_per_rank_ragged_trailing_dims_must_match(hvd):
    with pytest.raises(ValueError, match="except the first"):
        hvd.per_rank([jnp.ones((2, 3))] * (N - 1) + [jnp.ones((2, 4))])


def test_ragged_bundle_rejected_by_uniform_ops(hvd):
    """Ragged per_rank bundles must not slip zero padding into ops with
    uniform-shape contracts (code-review r4): allreduce, broadcast,
    reducescatter and even alltoall all reject them loudly."""
    ragged = hvd.per_rank([jnp.ones((1 + (i % 2), 2)) for i in range(N)])
    for op in (lambda: hvd.allreduce(ragged, op=hvd.Sum),
               lambda: hvd.broadcast(ragged, 0),
               lambda: hvd.reducescatter(ragged),
               lambda: hvd.alltoall(ragged)):
        with pytest.raises(ValueError, match="ragged"):
            op()


def test_alltoall_uneven_ragged_per_rank(hvd):
    """Uneven alltoall accepts a ragged per_rank bundle: row sums are
    validated against each rank's OWN first dim."""
    d0s = [(i % 2) + 1 for i in range(N)]  # 1,2,1,2,...
    vals = [jnp.arange(d0s[i] * 2, dtype=jnp.float32).reshape(d0s[i], 2)
            + 10 * i for i in range(N)]
    # rank i sends its single first row to rank 0, rest nowhere
    smat = np.zeros((N, N), np.int64)
    smat[:, 0] = 1
    outs, recv = hvd.alltoall(hvd.per_rank(vals), splits=smat)
    assert outs[0].shape == (N, 2)
    for i in range(N):
        np.testing.assert_allclose(np.asarray(outs[0][i]),
                                   np.asarray(vals[i][0]))
    # row sums beyond a rank's real rows must raise with the rank named
    bad = np.zeros((N, N), np.int64)
    bad[0, :2] = (1, 1)  # rank 0 only has 1 row
    with pytest.raises(ValueError, match="rank 0's first dimension"):
        hvd.alltoall(hvd.per_rank(vals), splits=bad)


def test_broadcast_eager(hvd):
    vals = _rank_values()
    for root in (0, 3, 7):
        out = hvd.broadcast(hvd.per_rank(vals), root)
        np.testing.assert_allclose(np.asarray(out), np.full((4,), root + 1.0))


def test_broadcast_bool(hvd):
    vals = [jnp.full((3,), i % 2 == 0) for i in range(N)]
    out = hvd.broadcast(hvd.per_rank(vals), 1)
    assert out.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(out), np.zeros((3,), bool))


def test_alltoall_eager(hvd):
    # rank i sends row j*1 chunk valued i*10+j to rank j
    vals = [jnp.arange(N, dtype=jnp.float32) + 10 * i for i in range(N)]
    out = hvd.alltoall(hvd.per_rank(vals))
    assert isinstance(out, hvd.PerRank)
    recv = np.asarray(out.array)
    for j in range(N):
        np.testing.assert_allclose(recv[j], np.array([10 * i + j for i in range(N)]))


def test_reducescatter_eager(hvd):
    vals = [jnp.arange(16, dtype=jnp.float32) * (i + 1) for i in range(N)]
    out = hvd.reducescatter(hvd.per_rank(vals), op=hvd.Sum)
    recv = np.asarray(out.array)
    total = np.arange(16, dtype=np.float32) * 36.0
    np.testing.assert_allclose(recv.reshape(-1), total)


def test_barrier_and_join(hvd):
    hvd.barrier()
    assert hvd.join() == hvd.size() - 1


def test_async_handles(hvd):
    h = hvd.allreduce_async(hvd.per_rank(_rank_values()), op=hvd.Sum)
    out = hvd.synchronize(h)
    assert hvd.poll(h)
    np.testing.assert_allclose(np.asarray(out), np.full((4,), 36.0))


# ---------------------------------------------------------------- traced mode

def _shard_mapped(hvd, fn, x, out_specs=P("hvd")):
    return jax.jit(jax.shard_map(
        fn, mesh=hvd.mesh(), in_specs=P("hvd"), out_specs=out_specs,
        check_vma=False))(x)


def test_allreduce_traced(hvd):
    x = jnp.arange(1.0, 9.0).reshape(N, 1)

    def step(v):
        return hvd.allreduce(v, op=hvd.Sum)

    out = _shard_mapped(hvd, step, x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(N, 36.0))


def test_allreduce_average_traced(hvd):
    x = jnp.arange(1.0, 9.0).reshape(N, 1)
    out = _shard_mapped(hvd, lambda v: hvd.allreduce(v, op=hvd.Average), x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(N, 4.5))


def test_allgather_traced(hvd):
    x = jnp.arange(8.0).reshape(N, 1)
    out = _shard_mapped(hvd, lambda v: hvd.allgather(v), x)
    # each rank gathers all 8 values -> global result is (8*8, 1) stacked
    assert out.shape == (64, 1)
    np.testing.assert_allclose(np.asarray(out[:8]).ravel(), np.arange(8.0))


def test_broadcast_traced(hvd):
    x = jnp.arange(1.0, 9.0).reshape(N, 1)
    out = _shard_mapped(hvd, lambda v: hvd.broadcast(v, 2), x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(N, 3.0))


def test_grouped_allreduce_traced(hvd):
    x = jnp.arange(1.0, 9.0).reshape(N, 1)

    def step(v):
        a, b = hvd.grouped_allreduce([v, v * 2], op=hvd.Sum)
        return a + b

    out = _shard_mapped(hvd, step, x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.full(N, 108.0))


def test_traced_inside_user_axis_name(hvd):
    # user meshes with their own axis names work via axis_name=
    import numpy as onp
    from jax.sharding import Mesh
    mesh = Mesh(onp.array(jax.devices()), ("dp",))
    x = jnp.arange(1.0, 9.0).reshape(N, 1)
    fn = jax.jit(jax.shard_map(
        lambda v: hvd.allreduce(v, op=hvd.Sum, axis_name="dp"),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(x)).ravel(), np.full(N, 36.0))


def test_allreduce_average_over_subaxis(hvd):
    """AVERAGE must divide by the bound axis size, not the world size
    (regression: dp-axis average on a (dp, tp) mesh)."""
    import numpy as onp
    from jax.sharding import Mesh
    mesh = Mesh(onp.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
    x = jnp.arange(16.0).reshape(8, 2)  # x[m, j] = 2m + j
    fn = jax.jit(jax.shard_map(
        lambda v: hvd.allreduce(v, op=hvd.Average, axis_name="dp"),
        mesh=mesh, in_specs=P("dp", "tp"), out_specs=P("dp", "tp"),
        check_vma=False))
    out = np.asarray(fn(x))
    # mean over the 4 dp shards of each (2, 1) block; world size is 8 —
    # dividing by 8 (the old bug) would halve these values
    np.testing.assert_allclose(out, np.tile([[6.0, 7.0], [8.0, 9.0]], (4, 1)))


def test_gspmd_passthrough_min_raises(hvd):
    with pytest.raises(RuntimeError):
        jax.jit(lambda v: hvd.allreduce(v, op=hvd.Min))(jnp.ones(2))


def test_grouped_allreduce_async(hvd):
    t1 = _rank_values((4,))
    t2 = _rank_values((2,), mult=10.0)
    h = hvd.grouped_allreduce_async(
        [hvd.per_rank(t1), hvd.per_rank(t2)], op=hvd.Sum)
    outs = hvd.synchronize(h)
    np.testing.assert_allclose(np.asarray(outs[0]), np.full((4,), 36.0))
    np.testing.assert_allclose(np.asarray(outs[1]), np.full((2,), 360.0))


def test_sparse_allreduce_async(hvd):
    from horovod_tpu.ops.sparse import SparseRows, sparse_allreduce_async
    rows = SparseRows(indices=jnp.asarray([0, 2]),
                      values=jnp.ones((2, 3)), num_rows=4)
    h = sparse_allreduce_async(rows, op=hvd.Sum)
    out = hvd.synchronize(h)
    dense = np.asarray(hvd.rows_to_dense(out))
    np.testing.assert_allclose(dense[0], N * 1.0)
    np.testing.assert_allclose(dense[1], 0.0)


@pytest.mark.parametrize("op_name", ["Average", "Sum", "Max"])
def test_grouped_allreduce_traced_fusion_exact(hvd, monkeypatch, op_name):
    """The traced fusion buffer (pack same-dtype leaves, ONE collective
    per HVD_TRACED_FUSION_THRESHOLD-bounded chunk) must be numerically
    identical to per-leaf collectives, across chunk boundaries, mixed
    shapes and dtypes, and every elementwise reduce op."""
    op = getattr(hvd, op_name)
    rng = np.random.default_rng(5)
    # mixed shapes/dtypes; threshold 64 bytes forces multiple f32 chunks
    leaves = [
        jnp.asarray(rng.standard_normal((N, 3)), jnp.float32),
        jnp.asarray(rng.standard_normal((N,)), jnp.float32),
        jnp.asarray(rng.standard_normal((N, 2, 2)), jnp.float32),
        # a genuinely distinct dtype group (x64 is off, float64 would
        # silently truncate to float32 and never split the groups)
        jnp.asarray(rng.standard_normal((N, 5)), jnp.bfloat16),
    ]
    monkeypatch.setenv("HVD_TRACED_FUSION_THRESHOLD", "64")

    def step(*vs):
        return tuple(hvd.grouped_allreduce(list(vs), op=op))

    mesh, axis = hvd.mesh(), hvd.axis_name()
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(axis),) * len(leaves),
        out_specs=(P(axis),) * len(leaves), check_vma=False))
    fused = [np.asarray(o) for o in fn(*leaves)]

    monkeypatch.setenv("HVD_TRACED_FUSION_THRESHOLD", "0")  # per-leaf
    fn2 = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(axis),) * len(leaves),
        out_specs=(P(axis),) * len(leaves), check_vma=False))
    unfused = [np.asarray(o) for o in fn2(*leaves)]
    for f, u in zip(fused, unfused):
        np.testing.assert_allclose(f, u, rtol=1e-6)


# --- the traced sync's permute rounds (ops/traced_exchange.py) -------------
# called through the schedule's internal entry: the predicate that selects
# it is false off a TPU (tests/test_optimizer.py has its cases)

RINGS = {2: (0, 1), 4: (0, 1, 3, 2), 8: (0, 1, 2, 3, 7, 6, 5, 4)}


def _rounds_and_psum(k, shapes, *, average, pre=1.0, post=1.0, seed=0):
    from jax.sharding import Mesh
    from horovod_tpu.ops import traced_exchange
    mesh = Mesh(np.array(jax.devices()[:k]), ("d",))
    rng = np.random.default_rng(seed)
    xs = [jnp.asarray(rng.standard_normal((k,) + s), jnp.float32)
          for s in shapes]

    def both(*leaves):
        leaves = [leaf[0] for leaf in leaves]
        got = traced_exchange.allreduce_rounds(
            leaves, "d", RINGS[k], average=average, pre=pre, post=post)
        reduce = lax.pmean if average else lax.psum
        want = [reduce(leaf * pre, "d") * post for leaf in leaves]
        return [g[None] for g in got], [w[None] for w in want]

    got, want = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
        check_vma=False))(*xs)
    return [np.asarray(g) for g in got], [np.asarray(w) for w in want]


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
@pytest.mark.parametrize("average", [False, True], ids=["sum", "average"])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_permute_rounds_equal_psum(k, average, scaled):
    """Ring reduce-scatter + all-gather rounds equal ``psum`` / ``pmean``
    for leaves of rank 1-4, chunked along whichever dimension ``2k`` (both
    directions) or only ``k`` (one direction) divides, and every member
    holds the same bits."""
    shapes = [(2 * k * 3,), (k * 8, 5), (7, 2 * k * 8, 3), (3, k, 8, 4)]
    pre, post = (0.5, 3.0) if scaled else (1.0, 1.0)
    got, want = _rounds_and_psum(k, shapes, average=average, pre=pre,
                                 post=post, seed=k)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-6)
        assert all((g[i] == g[0]).all() for i in range(k))


@pytest.mark.parametrize("shape, k, layout, expect", [
    ((1024, 4096), 4, None, (0, 8)),      # both directions along dim 0
    ((50257, 1024), 4, None, None),       # GPT-2's embedding: odd rows,
                                          # and the columns are the lanes
    ((16, 64, 1024), 8, None, (0, 16)),   # attention's output projection
    ((96, 5), 4, None, (0, 4)),           # 8 divides, not in 8-row tiles
    ((3, 3, 512, 512), 8, None, (2, 16)),     # a convolution kernel
    ((3, 3, 64, 64), 8, None, (2, 8)),    # 64 / 16 is half a tile: one way
    ((2048,), 4, None, (0, 8)),           # a vector has no tiles to respect
    ((50257, 7), 4, None, None),          # nothing divides
    ((), 2, None, None),
    # as the TPU lays them out (device_layout): 1024 as the lanes
    ((1024, 16, 64), 4, (1, 2, 0), (1, 8)),   # q, k, v: split the heads
    ((1024, 16, 64), 8, (1, 2, 0), (1, 16)),
    ((1024, 50257), 4, (1, 0), None),         # GPT-2's head: psum
    ((2048, 1000), 4, (1, 0), None),          # ResNet-50's: 250 rows a chunk
    ((1024, 4096), 4, (0, 1), (0, 8)),
])
def test_permute_rounds_split_dim(shape, k, layout, expect):
    from horovod_tpu.ops import traced_exchange
    assert traced_exchange.split_dim(shape, k, layout) == expect


def test_permute_rounds_respect_the_layout_given():
    """Chunked along dimension 1, as the TPU's layout of a q / k / v
    kernel asks, the rounds still equal ``psum``."""
    from jax.sharding import Mesh
    from horovod_tpu.ops import traced_exchange
    k = 4
    mesh = Mesh(np.array(jax.devices()[:k]), ("d",))
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (k, 24, 16, 5)), jnp.float32)

    def both(leaf):
        got, = traced_exchange.allreduce_rounds(
            [leaf[0]], "d", RINGS[k], layouts=[(1, 2, 0)])
        return got[None], lax.psum(leaf[0], "d")[None]

    jaxpr = str(jax.make_jaxpr(jax.shard_map(
        both, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
        check_vma=False))(x))
    assert "f32[24,1,2,5]" in jaxpr         # the heads, split 8 ways
    got, want = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_device_layout_is_row_major_where_the_backend_says_nothing():
    from horovod_tpu.ops import traced_exchange
    assert traced_exchange.device_layout(
        object(), jnp.float32, (4, 5, 6)) == (0, 1, 2)
    assert traced_exchange.device_layout(
        jax.devices()[0], jnp.float32, (1024, 16, 64)) == (0, 1, 2)


class _Chip:
    def __init__(self, coords, process_index=0, slice_index=0):
        self.coords, self.process_index = coords, process_index
        self.slice_index = slice_index


@pytest.mark.parametrize("chips, expect", [
    ([(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)], (0, 1, 3, 2)),   # 2x2
    ([(0, 0, 0), (1, 0, 0)], (0, 1)),
    ([(x, y, 0) for y in range(2) for x in range(4)],
     (0, 1, 2, 3, 7, 6, 5, 4)),                                     # 4x2
    ([(0, 0, 0), (1, 0, 0), (2, 0, 0)], None),      # a line closes no ring
    ([(0, 0, 0)], None),
    ([(0, 0, 0), (0, 0, 0)], None),                 # two cores of one chip
], ids=["2x2", "pair", "4x2", "line", "one", "same-chip"])
def test_neighbour_ring_from_coords(chips, expect):
    from horovod_tpu.ops import traced_exchange
    assert traced_exchange.neighbour_ring(
        [_Chip(c) for c in chips]) == expect


def test_neighbour_ring_needs_one_process_one_slice_and_coords():
    from horovod_tpu.ops import traced_exchange
    square = [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)]
    assert traced_exchange.neighbour_ring(
        [_Chip(c, process_index=i // 2)
         for i, c in enumerate(square)]) is None
    assert traced_exchange.neighbour_ring(
        [_Chip(c, slice_index=i // 2)
         for i, c in enumerate(square)]) is None
    assert traced_exchange.neighbour_ring(jax.devices()[:4]) is None  # CPU
