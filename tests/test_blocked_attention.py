"""``TransformerLM``'s "full" mode in blocked form: the Pallas kernels
(interpreted on the CPU) against the materialised formulation, with key
and value heads serving groups of query heads, the skip of score tiles
above the diagonal, the selection rule, the counters that say which path
a trace took, and the kernels at equal head counts lowering to the text
they had before groups."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import TransformerConfig, TransformerLM, transformer
from horovod_tpu.ops import flash
from horovod_tpu.parallel.sequence import _local_flash


def _qkv(key, shape, dtype, group=1):
    """q, k, v and an output weight; k and v with a ``group``-th of the
    query heads (axis 2)."""
    kv_shape = shape[:2] + (shape[2] // group,) + shape[3:]
    q, k, v, w = (jax.random.normal(sub, s, jnp.float32) for sub, s in zip(
        jax.random.split(key, 4), (shape, kv_shape, kv_shape, shape)))
    q = q * shape[-1] ** -0.5  # pre-scaled, as Attention hands it over
    return tuple(x.astype(dtype) for x in (q, k, v)) + (w,)


def _repeated(attend, group):
    """``attend`` over equal head counts, k and v repeated to them."""
    return lambda q, k, v: attend(q, *(jnp.repeat(x, group, axis=2)
                                       for x in (k, v)))


def _close(got, want, dtype):
    # bf16 operands round at 2**-9 per product; float32 paths agree to
    # accumulation order
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


# (q tile, kv tile) bounds standing in for the defaults at a size the
# interpreter affords; with them _tile_pad returns, over these sequences,
# the bound itself, its halvings (an off-grid length) and a sub-bound size
# (sequence, query heads a key/value head serves): 2 key/value heads,
# the query heads 2 x group, so that the groups of two key/value rows
# meet
@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("seq,group", [(256, 1), (384, 1), (200, 1),
                                       (72, 1), (256, 2), (200, 4),
                                       (384, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_blocked_matches_materialised(monkeypatch, dtype, seq, group, tiles):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", tiles[0])
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", tiles[1])
    q, k, v, w = _qkv(jax.random.PRNGKey(seq), (2, seq, 2 * group, 16),
                      dtype, group)

    def blocked(q, k, v):
        return _local_flash(q, k, v, True, False, True, prescaled=True)

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w)

    materialised = _repeated(transformer.materialised_attention, group)
    got = blocked(q, k, v)
    want = materialised(q, k, v)
    assert got.dtype == want.dtype == dtype
    _close(got, want, dtype)
    grads = jax.grad(loss(blocked), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(materialised), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, wants):
        assert g.dtype == dtype and g.shape == r.shape
        _close(g, r, dtype)


def _always_masked(causal, pos_ref, qi, j, q_tile, kv_tile, body):
    body(causal)


# the ring's case: traced block offsets, so that whole tiles lie above
# the diagonal (skipped), below it (unmasked) or across it
@pytest.mark.parametrize("qpos0,kpos0", [(0, 0), (32, 0), (0, 16), (16, 48),
                                         (64, 0), (0, 64)])
def test_tile_skipping_matches_no_skipping(monkeypatch, qpos0, kpos0):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 16)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 16)
    bh, sq, sk, d = 2, 48, 64, 8
    keys = jax.random.split(jax.random.PRNGKey(qpos0 * 100 + kpos0), 7)
    q, k, v, dout = (jax.random.normal(key, (bh, s, d), jnp.float32)
                     for key, s in zip(keys, (sq, sk, sk, sq)))
    # carries of an earlier block, so untouched rows must pass through
    m = jax.random.normal(keys[4], (bh, sq, 1), jnp.float32)
    l = jnp.exp(jax.random.normal(keys[5], (bh, sq, 1), jnp.float32))
    acc = jax.random.normal(keys[6], (bh, sq, d), jnp.float32)
    qp, kp = jnp.int32(qpos0), jnp.int32(kpos0)

    def run():
        carries = flash.block_attend(q, k, v, qp, kp, True, True, m, l, acc)
        m1, l1, acc1 = carries
        lse = m1 + jnp.log(l1)
        D = jnp.sum(dout * acc1 / l1, -1, keepdims=True)
        return carries, lse, D, flash.flash_block_grads(
            q, k, v, lse, dout, D, qp, kp, True, interpret=True)

    carries, lse, D, grads = run()
    monkeypatch.setattr(flash, "_for_visible_tile", _always_masked)
    jax.clear_caches()  # the kernels' own jit would replay the skipping trace
    carries_all, _, _, grads_all = run()
    jax.clear_caches()
    for got, want in zip(carries + grads, carries_all + grads_all):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    wants = (flash._attend_jnp(q, k, v, qp, kp, True, m, l, acc)
             + flash.jnp_block_grads(q, k, v, lse, dout, D, qp, kp, True))
    for got, want in zip(carries + grads, wants):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


BF16, F32 = jnp.bfloat16, jnp.float32
FLOOR = transformer.BLOCKED_MIN_SEQ


@pytest.mark.parametrize("platform,dtype,seq,local,initializing,want", [
    ("tpu", BF16, 1024, True, False, True),    # the GPT-2 cells
    ("tpu", BF16, FLOOR, True, False, True),   # at the crossover
    ("tpu", BF16, FLOOR - 1, True, False, False),
    ("tpu", BF16, 128, True, False, False),    # chip_smoke's default model
    ("cpu", BF16, 1024, True, False, False),   # tier-1, the rehearsals
    ("gpu", BF16, 1024, True, False, False),
    ("tpu", F32, 1024, True, False, False),    # the `tiny` float32 sizes
    ("tpu", jnp.float16, 1024, True, False, False),
    ("tpu", BF16, 1024, False, False, False),  # plain jit, several devices
    ("tpu", BF16, 1024, True, True, False),    # model.init
    ("tpu", "bfloat16", 8192, True, False, True),
])
def test_blocked_selected_table(platform, dtype, seq, local, initializing,
                                want):
    assert transformer.blocked_selected(
        platform, dtype, seq, local, initializing) is want


def test_local_to_one_device_follows_the_trace():
    seen = {}

    def probe(name):
        def fn(x):
            seen[name] = transformer._local_to_one_device()
            return x
        return fn

    x = jnp.zeros(8)
    jax.make_jaxpr(probe("jit"))(x)
    # conftest gives the process 8 virtual devices
    assert seen["jit"] is (jax.device_count() == 1)
    mesh = jax.make_mesh((4, 2), ("a", "b"))
    P = jax.sharding.PartitionSpec
    jax.make_jaxpr(jax.shard_map(probe("all"), mesh=mesh, in_specs=P("a"),
                                 out_specs=P("a"), check_vma=False))(x)
    assert seen["all"] is True
    jax.make_jaxpr(jax.shard_map(probe("some"), mesh=mesh, in_specs=P("a"),
                                 out_specs=P("a"), check_vma=False,
                                 axis_names={"a"}))(x)
    assert seen["some"] is False


def _attention_calls():
    return {dict(labels)["path"]: int(value) for labels, value
            in metrics.ATTENTION_CALLS.series().items()}


# 2 query heads over 1 key/value head: as groups at heads of 128
# (``transformer.grouped_selected``), repeated at heads of 16
@pytest.mark.parametrize("forced,path,kv_heads,head_dim", [
    (False, "materialised", None, None), (True, "blocked", None, None),
    (False, "materialised", 1, 128), (True, "blocked", 1, 128),
    (True, "blocked", 1, None)])
def test_counter_counts_one_call_a_layer_a_trace(monkeypatch, forced, path,
                                                 kv_heads, head_dim):
    cfg = TransformerConfig(vocab_size=64, num_layers=24, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=128,
                            num_kv_heads=kv_heads, head_dim=head_dim)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 128), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    if forced:  # what a TPU would answer; tracing a Mosaic call needs none
        monkeypatch.setattr(transformer, "blocked_selected",
                            lambda *observed: True)
    before = _attention_calls()
    step = jax.grad(lambda p, t: jnp.sum(model.apply(p, t)))
    text = str(jax.make_jaxpr(step)(params, tokens))
    moved = {p: n - before.get(p, 0) for p, n in _attention_calls().items()
             if n != before.get(p, 0)}
    grouped = ({"blocked_grouped": 24}
               if forced and kv_heads and head_dim == 128 else {})
    projection = ("projection_flat" if head_dim == 128
                  else "projection_dense_general")
    assert moved == {path: 24, projection: 72, **grouped}  # q, k, v
    assert metrics.ATTENTION_SHAPE.value({"what": "kv_group"}) \
        == (2 if kv_heads else 1)
    # the forward and the fused backward kernel, each traced ONCE under a
    # jit of its own that every layer calls (or no kernel at all)
    assert text.count("pallas_call") == (2 if forced else 0)


# sha256 of each kernel's Mosaic module (``get_asm`` without locations,
# which move with every line of ``ops/flash.py``), its kernel name and
# VMEM limit, as the tree before key/value groups lowered them (PR 37's,
# 47515d2; jax 0.9.0), for the TPU, at equal head counts: GPT-2's
# causal 16 heads of 64, the block limits at a padded length and at a
# tiled one, heads of 128
LOWERED_BEFORE_GROUPS = {
    "causal-1024-64": ("7ecb739d53f22767", "23e4b4e59ea215f7"),
    "block_causal-200-128": ("d1688f90d3eb7c56", "f98e572299ac76d1"),
    "earlier_blocks-512-128": ("12e1b184f629d1e0", "9a5332aa3255c6d8"),
}


@pytest.mark.parametrize("case", list(LOWERED_BEFORE_GROUPS))
def test_equal_head_counts_lower_as_before_groups(monkeypatch, case):
    from jax.experimental import mosaic

    name, seq, d = case.split("-")
    limit = flash.CAUSAL if name == "causal" else getattr(flash, name)(4)
    bh, seq, d = (8 if name == "causal" else 4), int(seq), int(d)
    seen = []
    lower = mosaic.lower_module_to_custom_call

    def spy(ctx, *args, module, **kwargs):
        seen.append(hashlib.sha256("\n".join((
            kwargs["kernel_name"], str(kwargs["vmem_limit_bytes"]),
            module.operation.get_asm(enable_debug_info=False)))
            .encode()).hexdigest()[:16])
        return lower(ctx, *args, module=module, **kwargs)

    monkeypatch.setattr(mosaic, "lower_module_to_custom_call", spy)
    rows = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16)
    col = jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32)
    for fn, specs in (
            (lambda q, k, v: flash.flash_attend(q, k, v, limit),
             (rows,) * 3),
            (lambda q, k, v, lse, do, D: flash.flash_block_grads(
                q, k, v, lse, do, D, 0, 0, limit, out_dtype=jnp.bfloat16),
             (rows, rows, rows, col, rows, col))):
        jax.jit(fn).trace(*specs).lower(lowering_platforms=("tpu",))
    assert tuple(seen) == LOWERED_BEFORE_GROUPS[case]


# the same hashes of the kernels grouped heads take (2 key/value heads
# serving `group` query heads each), lowered from the tree before the
# sliding window (470b23f): a full layer's 28 over 4 heads of 128
# (group 7), the block-diffusion limits at sdar's group 8, and heads of 64
LOWERED_BEFORE_WINDOWS = {
    "causal-7-1024-128": ("8d1744aefe9e14e6", "bc81ded67d28f447"),
    "block_causal-8-1024-128": ("849a456819fccace", "458c0d77203b2c22"),
    "earlier_blocks-8-512-128": ("7b232126aa050143", "3ec6a840c693cc9d"),
    "causal-4-2048-64": ("34ff6cba4151ccd1", "2dec8b51f837c32c"),
}


@pytest.mark.parametrize("case", list(LOWERED_BEFORE_WINDOWS))
def test_grouped_kernels_without_a_window_lower_as_before(monkeypatch, case):
    from jax.experimental import mosaic

    name, group, seq, d = case.split("-")
    limit = flash.CAUSAL if name == "causal" else getattr(flash, name)(4)
    group, seq, d = int(group), int(seq), int(d)
    seen = []
    lower = mosaic.lower_module_to_custom_call

    def spy(ctx, *args, module, **kwargs):
        seen.append(hashlib.sha256("\n".join((
            kwargs["kernel_name"], str(kwargs["vmem_limit_bytes"]),
            module.operation.get_asm(enable_debug_info=False)))
            .encode()).hexdigest()[:16])
        return lower(ctx, *args, module=module, **kwargs)

    monkeypatch.setattr(mosaic, "lower_module_to_custom_call", spy)
    rows = jax.ShapeDtypeStruct((2 * group, seq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, seq, d), jnp.bfloat16)
    col = jax.ShapeDtypeStruct((2 * group, seq, 1), jnp.float32)
    for fn, specs in (
            (lambda q, k, v: flash.flash_attend(q, k, v, limit),
             (rows, kv, kv)),
            (lambda q, k, v, lse, do, D: flash.flash_block_grads(
                q, k, v, lse, do, D, 0, 0, limit, out_dtype=jnp.bfloat16),
             (rows, kv, kv, col, rows, col))):
        jax.jit(fn).trace(*specs).lower(lowering_platforms=("tpu",))
    assert tuple(seen) == LOWERED_BEFORE_WINDOWS[case]


# --------------------------------------------------------------------------
# the sliding window: a lower bound beside the causal one
# --------------------------------------------------------------------------

def _dense_window(q, k, v, window):
    """Plain softmax attention over (bh, s, d) rows, key/value rows each
    serving a group of query rows, row i seeing keys i - window < j <= i:
    ``(out, lse)``."""
    group = q.shape[0] // k.shape[0]
    k, v = (jnp.repeat(x.astype(jnp.float32), group, axis=0) for x in (k, v))
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k)
    row = jnp.arange(q.shape[1])
    back = row[:, None] - row[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, -1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse), v), lse


# q tile 128 and kv tile 64 (the interpreter's size): windows below the kv
# tile, at it, at the q tile, at neither, and as long as the sequence
@pytest.mark.parametrize("window", [40, 64, 128, 100, 256, 1000])
@pytest.mark.parametrize("group", [1, 7])
def test_window_kernels_match_the_dense_softmax_and_the_jnp_twin(
        monkeypatch, window, group):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 128)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 64)
    bh, s, d = 2 * group, 256, 16
    keys = jax.random.split(jax.random.PRNGKey(window + group), 4)
    q = jax.random.normal(keys[0], (bh, s, d)) * d ** -0.5
    k, v = (jax.random.normal(key, (2, s, d)) for key in keys[1:3])
    dout = jax.random.normal(keys[3], (bh, s, d))
    limit = flash.sliding_window(window)
    out, lse = flash.flash_attend(q, k, v, limit, interpret=True)
    want, want_lse = _dense_window(q, k, v, window)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
    D = jnp.sum(dout * out, -1, keepdims=True)
    grads = flash.flash_block_grads(q, k, v, lse, dout, D, 0, 0, limit,
                                    interpret=True)
    _, vjp = jax.vjp(lambda q, k, v: _dense_window(q, k, v, window)[0],
                     q, k, v)
    for got, twin, dense in zip(grads, flash.jnp_block_grads(
            q, k, v, lse, dout, D, 0, 0, limit), vjp(dout)):
        assert got.shape == dense.shape
        np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got, twin, rtol=1e-4, atol=1e-4)


def test_a_window_as_long_as_the_sequence_is_causal_exactly(monkeypatch):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 128)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 64)
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, dout = (jax.random.normal(key, (14, 256, 16)) for key in keys[:2])
    k, v = (jax.random.normal(key, (2, 256, 16)) for key in keys[2:])

    def both(limit):
        out, lse = flash.flash_attend(q, k, v, limit, interpret=True)
        D = jnp.sum(dout * out, -1, keepdims=True)
        return (out, lse) + flash.flash_block_grads(
            q, k, v, lse, dout, D, 0, 0, limit, interpret=True)

    for got, want in zip(both(flash.sliding_window(256)), both(flash.CAUSAL)):
        np.testing.assert_array_equal(got, want)


# the ring's case: traced block offsets under a window, so that whole
# tiles lie before the window, inside it, after the diagonal or across
# either bound; each step's carries against the jnp twin
@pytest.mark.parametrize("qpos0,kpos0,window", [
    (0, 0, 20), (32, 0, 20), (0, 16, 40), (64, 0, 16), (96, 0, 40),
    (48, 16, 100)])
def test_window_tile_skipping_matches_no_skipping(monkeypatch, qpos0, kpos0,
                                                  window):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 16)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 16)
    bh, sq, sk, d = 2, 48, 64, 8
    keys = jax.random.split(jax.random.PRNGKey(qpos0 * 100 + kpos0), 7)
    q, k, v, dout = (jax.random.normal(key, (bh, s, d), jnp.float32)
                     for key, s in zip(keys, (sq, sk, sk, sq)))
    m = jax.random.normal(keys[4], (bh, sq, 1), jnp.float32)
    l = jnp.exp(jax.random.normal(keys[5], (bh, sq, 1), jnp.float32))
    acc = jax.random.normal(keys[6], (bh, sq, d), jnp.float32)
    qp, kp = jnp.int32(qpos0), jnp.int32(kpos0)
    limit = flash.sliding_window(window)

    def run():
        carries = flash.block_attend(q, k, v, qp, kp, limit, True, m, l, acc)
        m1, l1, acc1 = carries
        lse = m1 + jnp.log(l1)
        D = jnp.sum(dout * acc1 / l1, -1, keepdims=True)
        return carries, lse, D, flash.flash_block_grads(
            q, k, v, lse, dout, D, qp, kp, limit, interpret=True)

    carries, lse, D, grads = run()
    monkeypatch.setattr(flash, "_for_visible_tile", _always_masked)
    jax.clear_caches()
    carries_all, _, _, grads_all = run()
    jax.clear_caches()
    for got, want in zip(carries + grads, carries_all + grads_all):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    wants = (flash._attend_jnp(q, k, v, qp, kp, limit, m, l, acc)
             + flash.jnp_block_grads(q, k, v, lse, dout, D, qp, kp, limit))
    for got, want in zip(carries + grads, wants):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _tiles_seen(window, s, q_tile, kv_tile):
    """{(q tile, kv tile)} with a visible pair, by brute force over the
    dense mask."""
    row = np.arange(s)
    back = row[:, None] - row[None, :]
    seen = (back >= 0) & (back < window)
    return {(i, j) for i in range(s // q_tile) for j in range(s // kv_tile)
            if seen[i * q_tile:(i + 1) * q_tile,
                    j * kv_tile:(j + 1) * kv_tile].any()}


@pytest.mark.parametrize("window,s,q_tile,kv_tile", [
    (4096, 16384, 512, 512), (100, 1024, 128, 64), (64, 1024, 64, 128),
    (40, 512, 128, 128), (1, 256, 64, 64), (700, 512, 128, 64)])
def test_window_sweeps_visit_the_visible_tiles_in_few_steps(
        window, s, q_tile, kv_tile):
    """``visible_tiles`` against a brute-force count; each q tile's kv
    sweep (forward) and each kv tile's q sweep (backward) holds every tile
    with a visible pair in ``_sweep_steps`` steps from where it starts,
    which at 16384 rows and a window of 4096 is 10 of 32."""
    limit = flash.sliding_window(window)
    seen = _tiles_seen(window, s, q_tile, kv_tile)
    assert flash.visible_tiles(limit, s, s, q_tile, kv_tile) == len(seen)
    n_q, n_kv = s // q_tile, s // kv_tile
    kv_steps = flash._sweep_steps(limit, q_tile, kv_tile, n_kv)
    q_steps = flash._sweep_steps(limit, kv_tile, q_tile, n_q)
    origin = (0, 0)
    for qi in range(n_q):
        first = int(flash._kv_swept(limit, origin, qi, 0, q_tile, kv_tile))
        assert {j for i, j in seen if i == qi} <= set(
            range(first, first + kv_steps))
    for j in range(n_kv):
        first = int(flash._q_swept(limit, origin, j, 0, q_tile, kv_tile))
        assert {i for i, jj in seen if jj == j} <= set(
            range(first, first + q_steps))
    if (window, s) == (4096, 16384):
        assert (kv_steps, q_steps, n_kv) == (10, 10, 32)
        # q tile i sees kv tiles i - 8 .. i: 252 of the 1024
        assert len(seen) == sum(min(i + 1, 9) for i in range(32)) == 252


def test_without_a_window_the_sweeps_are_the_whole_sequence():
    for limit in (flash.CAUSAL, flash.block_causal(4),
                  flash.earlier_blocks(4), None):
        assert flash._sweep_steps(limit, 512, 512, 32) == 32
        assert flash._kv_swept(limit, (0, 0), 5, 3, 512, 512) == 3
        assert flash._q_swept(limit, (0, 0), 5, 3, 512, 512) == 3
    assert flash.CAUSAL == flash.Limit(1, 0, 0) and not flash.CAUSAL.window
    with pytest.raises(ValueError):
        flash.sliding_window(0)
    with pytest.raises(ValueError, match="causal upper bound alone"):
        flash._sweep_steps(flash.Limit(4, 3, 64), 512, 512, 32)
