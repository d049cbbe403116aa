"""``TransformerLM``'s "full" mode in blocked form: the Pallas kernels
(interpreted on the CPU) against the materialised formulation, the skip
of score tiles above the diagonal, the selection rule and the counter
that says which path a trace took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import TransformerConfig, TransformerLM, transformer
from horovod_tpu.ops import flash
from horovod_tpu.parallel.sequence import _local_flash


def _qkv(key, shape, dtype):
    q, k, v, w = (jax.random.normal(sub, shape, jnp.float32)
                  for sub in jax.random.split(key, 4))
    q = q * shape[-1] ** -0.5  # pre-scaled, as Attention hands it over
    return tuple(x.astype(dtype) for x in (q, k, v)) + (w,)


def _close(got, want, dtype):
    # bf16 operands round at 2**-9 per product; float32 paths agree to
    # accumulation order
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


# (q tile, kv tile) bounds standing in for the defaults at a size the
# interpreter affords; with them _tile_pad returns, over these sequences,
# the bound itself, its halvings (an off-grid length) and a sub-bound size
@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("seq", [256, 384, 200, 72])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_blocked_matches_materialised(monkeypatch, dtype, seq, tiles):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", tiles[0])
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", tiles[1])
    q, k, v, w = _qkv(jax.random.PRNGKey(seq), (2, seq, 2, 16), dtype)

    def blocked(q, k, v):
        return _local_flash(q, k, v, True, False, True, prescaled=True)

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w)

    got = blocked(q, k, v)
    want = transformer.materialised_attention(q, k, v)
    assert got.dtype == want.dtype == dtype
    _close(got, want, dtype)
    grads = jax.grad(loss(blocked), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(transformer.materialised_attention),
                     argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, wants):
        assert g.dtype == dtype
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


@pytest.mark.parametrize("forced,path", [(False, "materialised"),
                                         (True, "blocked")])
def test_counter_counts_one_call_a_layer_a_trace(monkeypatch, forced, path):
    cfg = TransformerConfig(vocab_size=64, num_layers=24, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=128)
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
    assert moved == {path: 24, "projection_dense_general": 72}  # q, k, v
    # the forward and the fused backward kernel, each traced ONCE under a
    # jit of its own that every layer calls (or no kernel at all)
    assert text.count("pallas_call") == (2 if forced else 0)
