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
