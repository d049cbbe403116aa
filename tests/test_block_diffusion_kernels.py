"""The block-diffusion mask in the blocked kernels: the limits
``ops/flash.py`` takes (``Limit``: causal, block-causal, the blocks before
a row's own), each against a dense mask in interpret mode, forward and
gradients, tile skipping against none, and the three parts joined
(``parallel/sequence.py`` ``_block_diffusion_flash``) against the
materialised form under ``block_diffusion_mask``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import sdar
from horovod_tpu.models import transformer
from horovod_tpu.ops import flash
from horovod_tpu.parallel.sequence import _block_diffusion_flash


# --------------------------------------------------------------------------
# the mask
# --------------------------------------------------------------------------

def test_mask_truth_table():
    length, block = 16, 4
    mask = np.asarray(transformer.block_diffusion_mask(length, block))
    assert mask.shape == (32, 32)
    for r in range(32):
        for s in range(32):
            r_clean, s_clean = r >= length, s >= length
            rb, sb = r % length // block, s % length // block
            if not r_clean and not s_clean:
                want = rb == sb          # a block sees itself, both ways
            elif not r_clean and s_clean:
                want = sb < rb           # and every clean block before it
            elif r_clean and s_clean:
                want = sb <= rb          # the clean copy is block-causal
            else:
                want = False             # a clean row sees no noised one
            assert mask[r, s] == want, (r, s)
    # block 0 of the noised copy sees its own block alone
    assert mask[:4].sum(1).tolist() == [4] * 4
    assert not mask[length:, :length].any()
    # every pair the mask lets through, as the benchmark counts them
    assert mask.sum() == sdar.visible_pairs({"block_length": block}, length)
    # the reference builds the same mask from copy, position and block
    np.testing.assert_array_equal(
        sdar._sees({"block_length": block}, length, 0, 32), mask)


LIMITS = {"causal": flash.CAUSAL, "block_causal": flash.block_causal(4),
          "earlier_blocks": flash.earlier_blocks(4)}


def _dense(limit, sq, sk, qpos0=0, kpos0=0):
    q = np.arange(qpos0, qpos0 + sq)[:, None]
    k = np.arange(kpos0, kpos0 + sk)[None]
    return k <= q // limit.block * limit.block + limit.offset


def test_limits_are_what_they_say():
    assert flash.CAUSAL == flash.Limit(1, 0) == flash._limit(True)
    assert flash._limit(False) is None
    np.testing.assert_array_equal(_dense(flash.CAUSAL, 12, 12),
                                  np.tril(np.ones((12, 12), bool)))
    rows = np.arange(12)
    np.testing.assert_array_equal(LIMITS["block_causal"].of(jnp.arange(12)),
                                  rows // 4 * 4 + 3)
    np.testing.assert_array_equal(
        LIMITS["earlier_blocks"].of(jnp.arange(12)), rows // 4 * 4 - 1)
    for limit in LIMITS.values():
        for kpos in range(12):
            sees = [q for q in range(16) if kpos <= limit.of(q)]
            assert limit.first_query(kpos) == sees[0]
    # the jnp twin masks by the same rule
    s = jnp.zeros((12, 12))
    for limit in LIMITS.values():
        np.testing.assert_array_equal(
            flash.causal_mask_scores(s, 0, 0, limit) == 0,
            _dense(limit, 12, 12))
    np.testing.assert_array_equal(flash.causal_mask_scores(s, 0, 0),
                                  flash.causal_mask_scores(s, 0, 0, True))


def _qkv(key, shape, dtype=jnp.float32):
    q, k, v, w = (jax.random.normal(sub, shape, jnp.float32)
                  for sub in jax.random.split(key, 4))
    return tuple(x.astype(dtype)
                 for x in (q * shape[-1] ** -0.5, k, v)) + (w,)


def _masked_attention(q, k, v, keep):
    """(bh, s, d) rows under a dense mask; a row that sees nothing is 0."""
    s = jnp.einsum("bqd,bkd->bqk", q, k)
    p = jnp.where(keep, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    total = jnp.sum(p, -1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", p / jnp.where(total > 0, total, 1), v)


# off the tile grid (200), on it (256), under one tile (72); head 128 as
# the sdar cell has it
@pytest.mark.parametrize("seq,d", [(256, 16), (200, 128), (72, 16)])
@pytest.mark.parametrize("name", list(LIMITS))
def test_each_limit_in_the_kernels_matches_a_dense_mask(monkeypatch, name,
                                                        seq, d):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 128)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 128)
    limit = LIMITS[name]
    q, k, v, w = _qkv(jax.random.PRNGKey(seq), (2, seq, d))
    keep = _dense(limit, seq, seq)

    out, lse = flash.flash_attend(q, k, v, limit, interpret=True)
    want = _masked_attention(q, k, v, keep)
    np.testing.assert_allclose(out, want, atol=2e-5)
    empty = ~keep.any(1)                 # block 0 under earlier_blocks
    assert empty.sum() == (4 if name == "earlier_blocks" else 0)
    assert bool(jnp.all(lse[:, empty] < flash.NEG_INF / 2))
    assert bool(jnp.all(out[:, empty] == 0))

    D = jnp.sum(w * out, -1, keepdims=True)
    grads = flash.flash_block_grads(q, k, v, lse, w, D, 0, 0, limit,
                                    interpret=True)
    wants = jax.grad(lambda q, k, v: jnp.sum(
        _masked_attention(q, k, v, keep) * w), argnums=(0, 1, 2))(q, k, v)
    twins = flash.jnp_block_grads(q, k, v, lse, w, D, 0, 0, limit)
    for got, want, twin in zip(grads, wants, twins):
        np.testing.assert_allclose(got, want, atol=5e-5)
        np.testing.assert_allclose(got, twin, atol=5e-5)
    if name == "causal":                 # today's spelling, bit for bit
        same = flash.flash_attend(q, k, v, True, interpret=True)
        np.testing.assert_array_equal(out, same[0])
        np.testing.assert_array_equal(lse, same[1])


def _always_masked(limit, pos_ref, qi, j, q_tile, kv_tile, body):
    body(limit)


@pytest.mark.parametrize("name", ["block_causal", "earlier_blocks"])
@pytest.mark.parametrize("qpos0,kpos0", [(0, 0), (32, 0), (0, 16), (16, 48)])
def test_tile_skipping_matches_no_skipping(monkeypatch, name, qpos0, kpos0):
    """The ring's case, traced offsets, under the block limits: tiles
    skipped, unmasked and crossed give what masking every tile gives."""
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 16)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 16)
    limit = LIMITS[name]
    bh, sq, sk, d = 2, 48, 64, 8
    keys = jax.random.split(jax.random.PRNGKey(qpos0 * 100 + kpos0), 7)
    q, k, v, dout = (jax.random.normal(key, (bh, s, d), jnp.float32)
                     for key, s in zip(keys, (sq, sk, sk, sq)))
    m = jax.random.normal(keys[4], (bh, sq, 1), jnp.float32)
    l = jnp.exp(jax.random.normal(keys[5], (bh, sq, 1), jnp.float32))
    acc = jax.random.normal(keys[6], (bh, sq, d), jnp.float32)
    qp, kp = jnp.int32(qpos0), jnp.int32(kpos0)

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


@pytest.mark.parametrize("length,block,d,dtype", [
    (256, 4, 16, jnp.float32), (200, 8, 128, jnp.float32),
    (72, 4, 16, jnp.float32), (128, 4, 128, jnp.bfloat16)])
@pytest.mark.parametrize("interpret", [True, False],
                         ids=["kernels", "jnp-twins"])
def test_blocked_matches_materialised_under_the_mask(monkeypatch, interpret,
                                                     length, block, d, dtype):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 128)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 128)
    q, k, v, w = _qkv(jax.random.PRNGKey(length), (2, 2 * length, 2, d),
                      dtype)
    mask = transformer.block_diffusion_mask(length, block)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5

    def close(got, want):
        assert got.dtype == want.dtype == dtype
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))

    def blocked(q, k, v):
        return _block_diffusion_flash(q, k, v, block, False, interpret)

    def materialised(q, k, v):
        return transformer.materialised_attention(q, k, v, mask)

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32) * w)

    close(blocked(q, k, v), materialised(q, k, v))
    grads = jax.grad(loss(blocked), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(materialised), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, wants):
        close(g, r)
    with pytest.raises(ValueError):      # half a block at the end
        _block_diffusion_flash(q[:, :-2], k[:, :-2], v[:, :-2], block,
                               False, interpret)
