"""The forward kernel's own tiling (``ops/flash.py`` ``forward_tiles``):
taller q tiles and several score tiles a grid step, each pick the rule
can make interpreted on the CPU against the ``jax.numpy`` reference,
under every limit, grouped key/value heads and a sequence off the tile
grid; the rule's table at the benchmark's cells, the backward's tiles
beside it unchanged."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash


def _always_masked(causal, pos_ref, qi, j, q_tile, kv_tile, body):
    body(causal)


def _reference(q, k, v, limit):
    """``(out, lse)`` of (bh, s, d) rows under ``limit`` through
    ``causal_mask_scores`` in ``jax.numpy``, key/value rows repeated over
    their groups; a row that sees nothing gives 0 and -inf."""
    group = q.shape[0] // k.shape[0]
    k, v = (jnp.repeat(x, group, axis=0) for x in (k, v))
    s = flash.causal_mask_scores(jnp.einsum("bqd,bkd->bqk", q, k), 0, 0,
                                 limit)
    seen = s > flash.NEG_INF / 2
    lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), -1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - lse), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v), lse


# the picks as (q tile, kv rows a step) in default tiles: at the
# interpreter's size the q tile is 64 and a score tile 32 keys, so a step
# takes 32, 64 or 128 keys; the windows are shorter than every such
# block, as long as two tiles, as four, and longer than all
FORWARD_PICKS = [(2, 1), (1, 2), (2, 2), (1, 4), (2, 4), (4, 4)]
FORWARD_LIMITS = {
    "causal": flash.CAUSAL, "window-24": flash.sliding_window(24),
    "window-64": flash.sliding_window(64),
    "window-128": flash.sliding_window(128),
    "window-300": flash.sliding_window(300),
    "block_causal": flash.block_causal(4),
    "earlier_blocks": flash.earlier_blocks(4)}


def _forced_tiles(monkeypatch, pick):
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 64)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 32)
    monkeypatch.setattr(flash, "forward_tiles",
                        lambda rows, d, limit: (64 * pick[0], 32 * pick[1]))


# on the tile grid with equal head counts; off it (250 rows: the kv rows
# padded to 256, the last score tile masked) with 4 query rows a
# key/value row
@pytest.mark.parametrize("seq,group", [(256, 1), (250, 4)])
@pytest.mark.parametrize("name", list(FORWARD_LIMITS))
@pytest.mark.parametrize("pick", FORWARD_PICKS,
                         ids=[f"q{a}-kv{b}" for a, b in FORWARD_PICKS])
def test_forward_tilings_match_the_jnp_reference(monkeypatch, pick, name,
                                                 seq, group):
    _forced_tiles(monkeypatch, pick)
    limit = FORWARD_LIMITS[name]
    keys = jax.random.split(jax.random.PRNGKey(seq + group), 3)
    q = jax.random.normal(keys[0], (2 * group, seq, 16)) * 16 ** -0.5
    k, v = (jax.random.normal(key, (2, seq, 16)) for key in keys[1:])
    (q_tile, _), (kv_tile, _), kv_sub = flash._forward_tiling(seq, 16, limit)
    assert (q_tile, kv_tile * kv_sub) == (64 * pick[0], 32 * pick[1])
    out, lse = flash.flash_attend(q, k, v, limit, interpret=True)
    want, want_lse = _reference(q, k, v, limit)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    empty = jnp.isinf(want_lse)          # block 0 under earlier_blocks
    assert int(empty.sum()) == (2 * group * 4 if name == "earlier_blocks"
                                else 0)
    assert bool(jnp.all(lse[empty] < flash.NEG_INF / 2))
    np.testing.assert_allclose(lse[~empty], want_lse[~empty], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("name", ["causal", "window-24", "window-64",
                                  "earlier_blocks"])
@pytest.mark.parametrize("pick", [(1, 4), (2, 2)],
                         ids=["q1-kv4", "q2-kv2"])
def test_forward_blocks_fold_as_every_tile_masked(monkeypatch, pick, name):
    """A block every row sees whole, folded with the carries held, and a
    block judged tile by tile give what masking every tile gives."""
    _forced_tiles(monkeypatch, pick)
    limit = FORWARD_LIMITS[name]
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (4, 256, 16))
    k, v = (jax.random.normal(key, (2, 256, 16)) for key in keys[1:])
    got = flash.flash_attend(q, k, v, limit, interpret=True)
    monkeypatch.setattr(flash, "_for_visible_tile", _always_masked)
    jax.clear_caches()
    want = flash.flash_attend(q, k, v, limit, interpret=True)
    jax.clear_caches()
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# each cell's sweeps: (rows, head_dim, limit) -> the forward's (q rows,
# kv rows) a grid step; the block-diffusion mask runs two sweeps over
# L = 4096 rows, the GPT-2 cells four sequences of 1024
CELL_TILES = {
    "gpt2m": (1024, 64, flash.CAUSAL, (512, 512)),
    "lfm2": (8192, 64, flash.CAUSAL, (1024, 1024)),
    "sdar-block_causal": (4096, 128, flash.block_causal(4), (1024, 1024)),
    "sdar-earlier_blocks": (4096, 128, flash.earlier_blocks(4),
                            (1024, 1024)),
    "smallthinker-full": (16384, 128, flash.CAUSAL, (1024, 1024)),
    "smallthinker-window": (16384, 128, flash.sliding_window(4096),
                            (1024, 1024)),
}


@pytest.mark.parametrize("cell", list(CELL_TILES))
def test_the_rule_at_the_cells_and_the_backward_as_before(monkeypatch,
                                                          cell):
    rows, d, limit, want = CELL_TILES[cell]
    assert flash.forward_tiles(rows, d, limit) == want
    (q_tile, s_q), (kv_tile, s_k), kv_sub = flash._forward_tiling(
        rows, d, limit)
    assert (q_tile, kv_tile * kv_sub) == want and s_q == s_k == rows
    seen = {}

    def spy(*args, q_tiling, kv_tiling, **kwargs):
        seen.update(q_tiling=q_tiling, kv_tiling=kv_tiling)
        return args[:3]

    monkeypatch.setattr(flash, "_flash_block_grads", spy)
    q = jax.ShapeDtypeStruct((2, rows, d), jnp.bfloat16)
    col = jax.ShapeDtypeStruct((2, rows, 1), jnp.float32)
    jax.eval_shape(lambda q, lse: flash.flash_block_grads(
        q, q, q, lse, q, lse, 0, 0, limit), q, col)
    assert seen == {"q_tiling": (512, rows), "kv_tiling": (512, rows)}


@pytest.mark.parametrize("forced", [True, False],
                         ids=["blocked", "materialised"])
def test_the_gauge_reads_the_forward_tiling_a_trace_took(monkeypatch,
                                                         forced):
    from horovod_tpu import metrics
    from horovod_tpu.models import TransformerConfig, TransformerLM
    from horovod_tpu.models import transformer

    _forced_tiles(monkeypatch, (2, 4))
    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            d_model=32, d_ff=64, max_seq_len=256)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    monkeypatch.setattr(transformer, "blocked_selected",
                        lambda *observed: forced)
    jax.make_jaxpr(model.apply)(params, tokens)
    read = {what: metrics.ATTENTION_SHAPE.value({"what": what})
            for what in ("forward_q_rows", "forward_kv_rows")}
    assert read == ({"forward_q_rows": 128, "forward_kv_rows": 128}
                    if forced else {"forward_q_rows": 0,
                                    "forward_kv_rows": 0})
