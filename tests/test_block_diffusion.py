"""Block-diffusion training through ``TransformerLM``: the mask over a
doubled sequence (``attn_mask="block_diffusion"``), the limits the blocked
kernels take for it (``ops/flash.py`` ``Limit``), a head size and
positions of the configuration's own, softmax routing in the held-experts
layer, and the objective (``models/block_diffusion.py``), against the plain
float32 reference the benchmark keeps (``benchmark/models/sdar.py``), at
small sizes on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import sdar
from horovod_tpu import metrics
from horovod_tpu.models import (TransformerConfig, TransformerLM,
                                block_diffusion, operators, transformer)
from horovod_tpu.ops import flash
from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(tiny=True, **overrides):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        config = json.load(f)
    if tiny:
        config = {**config, **config["tiny"]}
    return {**config, **overrides}


def rel_error(got, want):
    diff = sum(jnp.sum(jnp.square(g.astype(jnp.float32) - w))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    norm = sum(jnp.sum(jnp.square(w)) for w in jax.tree.leaves(want))
    return float(jnp.sqrt(diff / norm))


def _model(dtype="float32", **overrides):
    config = load_config(compute_dtype=dtype, **overrides)
    model = sdar.make_model(config)
    params, aux = jax.jit(lambda k: sdar.init(model, config, k))(
        jax.random.PRNGKey(0))
    return config, model, params, aux


# --------------------------------------------------------------------------
# the whole model against the reference
# --------------------------------------------------------------------------

# 200 tokens are 400 rows: no multiple of any tile. A quarter of the
# experts is held, so both sizes work in chunks of the short buffer
@pytest.mark.parametrize("seq,short", [(48, 1024), (200, 3584)])
def test_model_is_the_reference_in_float32(seq, short):
    config, model, params, aux = _model()
    data = sdar.make_batch(config, jax.random.PRNGKey(2), 2, seq)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: sdar.loss(model, p, aux, data), has_aux=True))(params)
    (ref_loss, ref_aux), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: sdar.reference_loss(config, p, aux, data),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == 5 * 12 + 3
    for (path, got), want in zip(leaves, jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(want))) > 0, jax.tree_util.keystr(path)
        assert rel_error(got, want) < 1e-4, jax.tree_util.keystr(path)
    for name, layer in aux.items():
        ref = ref_aux[name]["moe"]
        np.testing.assert_array_equal(layer["moe"]["expert_load"],
                                      ref["expert_load"])
        assert int(layer["moe"]["rows_held"]) == int(ref["rows_held"])
        chunks = -(-int(ref["rows_held"]) // short)
        assert int(layer["moe"]["buffer_rows"]) == min(chunks * short,
                                                       2 * 2 * seq * 8)
        # both copies' rows are routed: 2 sequences x 2 copies x 8 picks
        assert int(jnp.sum(layer["moe"]["expert_load"])) == 2 * 2 * seq * 8


def test_bfloat16_error_is_seen_and_small():
    config, model, params, aux = _model("bfloat16")
    data = sdar.make_batch(config, jax.random.PRNGKey(2), 2, 48)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: sdar.loss(model, p, aux, data)[0]))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: sdar.reference_loss(config, p, aux, data)[0]))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-2)
    assert 1e-4 < rel_error(grads, ref_grads) < sdar.GRAD_REL_TOL


def test_parameters_are_what_the_configuration_names():
    config, model, params, aux = _model()
    shapes = {jax.tree_util.keystr(path): leaf.shape for path, leaf
              in jax.tree_util.tree_leaves_with_path(params)}
    # heads of 16 over a stream of 64: 4 x 16 is the stream only by chance
    # of the tiny sizes; 8 heads of 16 are not
    assert shapes["['block_0']['attn']['q']['kernel']"] == (64, 4, 16)
    assert shapes["['block_0']['attn']['k']['kernel']"] == (64, 2, 16)
    assert shapes["['block_0']['attn']['o']['kernel']"] == (4, 16, 64)
    assert shapes["['block_4']['moe']['router']"] == (64, 16)
    assert shapes["['block_4']['moe']['w1']"] == (4, 64, 32)
    assert shapes["['lm_head']['kernel']"] == (64, 256)
    # softmax scoring carries no selection bias
    assert set(aux["block_0"]["moe"]) == {"expert_load", "rows_held",
                                          "buffer_rows"}
    wide = TransformerConfig(num_heads=8, head_dim=16, d_model=64,
                             positions="rotary")
    attn = transformer.Attention(wide)
    p = jax.eval_shape(attn.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8, 64)))["params"]
    assert p["q"]["kernel"].shape == (64, 8, 16)
    assert p["o"]["kernel"].shape == (8, 16, 64)


def test_unit_rms_embeddings_keep_the_random_router_near_uniform():
    """What ``sdar.init`` assumes, and why: at random weights attention
    hands every row the running mean of its keys' values, and five such
    layers over embeddings of norm 1 (flax's default) leave every row the
    same direction: the router then picks the same 8 experts for all of
    them. With rows of unit RMS each row keeps its own token."""
    config, model, params, aux = _model(
        hidden_size=128, head_dim=32, experts_routed=128, num_experts=16)
    data = sdar.make_batch(config, jax.random.PRNGKey(4), 1, 256)
    rows = 2 * 256

    @jax.jit
    def busiest(params):
        _, routing = sdar.loss(model, params, aux, data)
        return jnp.stack([jnp.max(layer["moe"]["expert_load"])
                          for layer in routing.values()])

    assert float(jnp.sqrt(jnp.mean(jnp.square(
        params["embed"]["embedding"])))) == pytest.approx(1.0, rel=0.05)
    # uniform routing gives an expert rows * 8 / 128 = 32 rows; the masked
    # rows share one embedding and go together, about half of the 256
    assert int(jnp.max(busiest(params))) < rows // 2
    small = {**params, "embed": {"embedding": params["embed"]["embedding"]
                                 / config["hidden_size"] ** 0.5}}
    assert int(jnp.max(busiest(small))) > 0.9 * rows


def test_logits_are_the_noised_copys_rows_at_their_own_positions():
    config, model, params, aux = _model(layer_types=["full_attention"] * 2)
    tokens, masked, _ = sdar.make_batch(config, jax.random.PRNGKey(3), 2, 32)
    ids, positions = block_diffusion.doubled_inputs(tokens, masked, 255)
    assert ids.shape == positions.shape == (2, 64)
    np.testing.assert_array_equal(ids[:, 32:], tokens)
    np.testing.assert_array_equal(ids[:, :32],
                                  np.where(masked, 255, tokens))
    np.testing.assert_array_equal(positions[0], list(range(32)) * 2)
    run = jax.jit(lambda ids: model.apply(
        {"params": params, "routing": aux}, ids, positions))
    logits = run(ids)
    assert logits.shape == (2, 32, 256) and logits.dtype == jnp.float32
    # the clean copy's later blocks are hidden from an earlier noised one:
    # a change to the last clean block moves only the last block's logits
    moved = run(ids.at[:, -4:].add(1))
    np.testing.assert_array_equal(moved[:, :-4], logits[:, :-4])
    # ... and nothing at all, being the last block: its own noised rows
    # see the clean blocks BEFORE theirs only
    np.testing.assert_array_equal(moved, logits)
    moved = run(ids.at[:, 32:36].add(1))
    np.testing.assert_array_equal(moved[:, :4], logits[:, :4])
    assert float(jnp.max(jnp.abs(moved[:, 4:] - logits[:, 4:]))) > 1e-4
    with pytest.raises(ValueError):      # learned positions take no rows'
        TransformerLM(TransformerConfig(num_layers=1)).init(
            jax.random.PRNGKey(0), ids, positions)
    with pytest.raises(ValueError):      # and a doubled sequence needs them
        model.apply({"params": params, "routing": aux}, ids)


def test_no_array_is_rows_by_rows_on_the_blocked_path(monkeypatch):
    """Traced as a TPU would (the selection forced), a step's jaxpr holds
    four Mosaic calls (two limits, forward and backward), each traced once,
    and nothing with two dimensions as long as the rows."""
    config, model, params, aux = _model("bfloat16")
    data = sdar.make_batch(config, jax.random.PRNGKey(2), 1, 512)
    monkeypatch.setattr(transformer, "blocked_selected",
                        lambda *observed: not observed[-1])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: sdar.loss(model, p, aux, data)[0]))(params)
    text = str(jaxpr)
    assert text.count("pallas_call") == 4
    rows = 1024

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield getattr(var.aval, "shape", ())
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    assert not [s for s in shapes(jaxpr.jaxpr)
                if sum(dim >= rows for dim in s) >= 2]


# --------------------------------------------------------------------------
# the objective
# --------------------------------------------------------------------------

def test_doubled_sequence_is_one_forward_a_block_after_the_clean_prefix():
    """The plain definition the doubled sequence stands for: block b's
    noised tokens run after the clean tokens of blocks 0..b-1, one forward
    a block under a dense block-causal mask, with the reference's own layer
    functions; the loss adds each block's masked rows at 1 / its rate."""
    config, model, params, aux = _model(layer_types=["full_attention"] * 2)
    block, length = config["block_length"], 16
    tokens, masked, rate = sdar.make_batch(config, jax.random.PRNGKey(5), 2,
                                           length)
    assert bool(masked.any()) and not bool(masked.all())
    noised = jnp.where(masked, config["vocab_size"] - 1, tokens)
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(length // block):
            at, end = b * block, (b + 1) * block
            ids = jnp.concatenate([tokens[:, :at], noised[:, at:end]], 1)
            position = jnp.arange(end)
            of = position // block

            def sees(first, size, of=of):
                return of[None] <= jax.lax.dynamic_slice_in_dim(
                    of, first, size)[:, None]

            @jax.jit
            def forward(ids, position=position, sees=sees):
                x = params["embed"]["embedding"][ids]
                for i in range(len(config["layer_types"])):
                    x, _ = sdar._layer(config, params[f"block_{i}"], x,
                                       position, sees)
                return sdar._head(config, params, x)

            logp = jax.nn.log_softmax(forward(ids)[:, at:end])
            picked = jnp.take_along_axis(
                logp, tokens[:, at:end, None], -1)[..., 0]
            total = total - jnp.sum(
                masked[:, at:end] * picked / rate[:, b, None])
    want = total / tokens.size
    got, _ = sdar.loss(model, params, aux, (tokens, masked, rate))
    ref, _ = sdar.reference_loss(config, params, aux, (tokens, masked, rate))
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert float(ref) == pytest.approx(float(want), rel=2e-5)


def test_masked_token_loss_weighs_each_masked_row_by_its_blocks_rate():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 5))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 5)
    masked = jnp.array([[1, 0, 0, 1, 1, 1, 0, 0], [0] * 8], bool)
    rate = jnp.array([[0.5, 0.25], [0.1, 1.0]])
    logp = np.asarray(jax.nn.log_softmax(logits))
    want = -sum(logp[0, i, int(tokens[0, i])] / (0.5 if i < 4 else 0.25)
                for i in (0, 3, 4, 5)) / 16
    got = block_diffusion.masked_token_loss(logits, tokens, masked, rate, 4)
    assert float(got) == pytest.approx(want, rel=1e-6)


# --------------------------------------------------------------------------
# softmax routing in the held-experts layer
# --------------------------------------------------------------------------

ROUTED, HELD, WIDTH, FF, TOP_K, ROWS = 128, 16, 32, 24, 8, 40
ROUTING = {"num_experts_per_tok": TOP_K, "norm_topk_prob": True}


def _expert_layer(key=0, skew=None):
    keys = jax.random.split(jax.random.PRNGKey(key), 5)
    router = jax.random.normal(keys[1], (WIDTH, ROUTED)) * 0.5
    if skew is not None:                 # every row favours these experts
        router = router.at[:, jnp.array(skew)].add(3.0)
    x = jax.random.normal(keys[0], (ROWS, WIDTH))
    return {"x": jnp.abs(x) if skew is not None else x, "router": router,
            "w1": jax.random.normal(keys[2], (ROUTED, WIDTH, FF)) * 0.2,
            "w3": jax.random.normal(keys[3], (ROUTED, WIDTH, FF)) * 0.2,
            "w2": jax.random.normal(keys[4], (ROUTED, FF, WIDTH)) * 0.2}


def _share(t, first, count):
    """The program's layer module holding experts [first, first + count)."""
    cfg = TransformerConfig(
        d_model=WIDTH, dtype=jnp.float32, moe_routed=ROUTED,
        moe_held=(first, count), moe_d_ff=FF, moe_top_k=TOP_K,
        moe_scoring="softmax")
    held = slice(first, first + count)
    variables = {"params": {"router": t["router"], "w1": t["w1"][held],
                            "w3": t["w3"][held], "w2": t["w2"][held]}}
    y, state = operators.HeldExpertsMLP(cfg).apply(
        variables, t["x"][None], mutable=["routing"])
    return y[0], state["routing"]


def _reference_share(t, first, count):
    held = slice(first, first + count)
    p = {"router": t["router"], "w1": t["w1"][held], "w3": t["w3"][held],
         "w2": t["w2"][held]}
    y, load = sdar._experts(ROUTING, p, t["x"][None], held=(first, count))
    return y[0], load


def test_the_eight_shares_add_up_to_the_uncut_layer():
    t = _expert_layer()
    whole, _ = _reference_share(t, 0, ROUTED)
    total, rows = 0, 0
    for first in range(0, ROUTED, HELD):
        y, load = _share(t, first, HELD)
        want, want_load = _reference_share(t, first, HELD)
        np.testing.assert_allclose(y, want, atol=2e-6)
        assert int(load["rows_held"]) == int(want_load["rows_held"])
        assert "expert_bias" not in load
        total, rows = total + y, rows + int(load["rows_held"])
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert rows == ROWS * TOP_K          # every pick landed on one share


def test_softmax_gates_are_the_picked_probabilities_over_their_sum():
    t = _expert_layer(1)
    logits = t["x"] @ t["router"]
    idx, gates = moe.route_top_k(logits, TOP_K)
    probs = jax.nn.softmax(logits, -1)
    _, want_idx = jax.lax.top_k(probs, TOP_K)
    np.testing.assert_array_equal(idx, want_idx)
    picked = jnp.take_along_axis(probs, idx, -1)
    np.testing.assert_allclose(gates, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    # the reference's gates: the same, spread over all 128, summing to 1
    ref_gates, ref_picked = sdar._routing(ROUTING, t, t["x"])
    np.testing.assert_allclose(ref_gates.sum(-1), 1.0, atol=1e-6)
    assert ref_picked.sum(-1).tolist() == [TOP_K] * ROWS
    np.testing.assert_allclose(
        jnp.take_along_axis(ref_gates, idx, -1), gates, rtol=1e-6)
    # norm_topk_prob false: the raw probabilities
    _, raw = moe.route_top_k(logits, TOP_K, renormalize=False)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)
    # one pick: raw unless told otherwise (the Switch convention stays)
    np.testing.assert_allclose(moe.route_top_k(logits, 1)[1],
                               probs.max(-1, keepdims=True), rtol=1e-6)


@pytest.mark.parametrize("favoured", [[0], list(range(8)), [40]],
                         ids=["one-held", "all-eight-picks-held",
                              "elsewhere"])
def test_nothing_drops_under_a_skewed_router(favoured):
    t = _expert_layer(2, skew=favoured)
    y, load = _share(t, 0, HELD)
    want, want_load = _reference_share(t, 0, HELD)
    np.testing.assert_allclose(y, want, atol=2e-6)
    for e in favoured:
        assert int(load["expert_load"][e]) == ROWS
    assert int(load["rows_held"]) == int(want_load["rows_held"])
    if len(favoured) == TOP_K:           # every pair landed here
        assert int(load["rows_held"]) == ROWS * TOP_K

    def loss(fn):
        def f(x, w1, router):
            return jnp.sum(jnp.sin(fn({**t, "x": x, "w1": w1,
                                       "router": router}, 0, HELD)[0]))
        return f

    args = (t["x"], t["w1"], t["router"])
    got = jax.grad(loss(_share), argnums=(0, 1, 2))(*args)
    ref = jax.grad(loss(_reference_share), argnums=(0, 1, 2))(*args)
    for g, r in zip(got, ref):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, r, atol=5e-6)


# --------------------------------------------------------------------------
# the configuration, the registry
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fields", [
    {"attn_mask": "sliding_window"},
    {"attn_mask": "block_diffusion", "attn_mode": "ring"},
    {"attn_mask": "block_diffusion", "attn_mode": "ring_zigzag"},
    {"attn_mask": "block_diffusion", "attn_mode": "ulysses"},
    {"attn_mask": "block_diffusion", "block_length": 0},
    {"head_dim": 0},
    {"head_dim": 15, "positions": "rotary"},   # rotate-half needs two halves
    {"head_dim": 16, "num_kv_heads": 3},       # 8 heads over 3
    {"moe_scoring": "argmax"},
])
def test_config_refuses_what_it_cannot_spell(fields):
    with pytest.raises(ValueError):
        TransformerConfig(**{"num_layers": 4, "num_heads": 8, **fields})


def _series(instrument, label):
    return {dict(labels)[label]: value for labels, value
            in instrument.series().items()}


@pytest.mark.parametrize("forced", [False, True],
                         ids=["materialised", "blocked"])
def test_registry_tells_a_block_diffusion_trace_from_a_causal_one(
        monkeypatch, forced):
    config, model, params, aux = _model("bfloat16", head_dim=32)
    data = sdar.make_batch(config, jax.random.PRNGKey(2), 1, 1024)
    if forced:  # what a TPU would answer; tracing a Mosaic call needs none
        monkeypatch.setattr(transformer, "blocked_selected",
                            lambda *observed: True)
    path = ("blocked" if forced else "materialised") + "_block_diffusion"
    before = _series(metrics.ATTENTION_CALLS, "path")
    routers = _series(metrics.MOE_CALLS, "path")
    jax.make_jaxpr(lambda p: sdar.loss(model, p, aux, data)[0])(params)
    after = _series(metrics.ATTENTION_CALLS, "path")
    moved = {p: n - before.get(p, 0) for p, n in after.items()
             if n != before.get(p, 0)}
    assert moved == {path: 5, "projection_dense_general": 15}  # q, k, v
    last = _series(metrics.ATTENTION_SHAPE, "what")
    assert last["head_dim"] == 32
    # two sweeps of 2 x 2 tiles of 512 over 1024 positions, 3 visited each,
    # of the 4 x 4 tiles of the 2048 x 2048 square
    assert last["visible_tile_share"] == (6 / 16 if forced else 1.0)
    moe_after = _series(metrics.MOE_CALLS, "path")
    assert moe_after["router_softmax"] - routers.get("router_softmax", 0) == 5
    assert moe_after.get("router_sigmoid_bias", 0) \
        == routers.get("router_sigmoid_bias", 0)
    assert _series(metrics.MOE_SHAPE, "what")["router_softmax"] == 1
    moe.route_sigmoid_top_k(jnp.zeros((4, 16)), jnp.zeros(16), 2)
    assert _series(metrics.MOE_SHAPE, "what")["router_softmax"] == 0


def test_visible_tile_share_at_the_cells_size():
    # L 4096 in tiles of 512: 36 tiles a sweep, two sweeps, of 16 x 16
    assert transformer.visible_tile_share(8192, "block_diffusion", 4) \
        == 72 / 256
    assert transformer.visible_tile_share(8192, "causal", 1) == 136 / 256
    assert flash.visible_tiles(flash.earlier_blocks(4), 4096, 4096, 512,
                               512) == 36
    assert flash.visible_tiles(flash.earlier_blocks(512), 4096, 4096, 512,
                               512) == 28      # the diagonal tiles go too
    assert flash.visible_tiles(None, 1024, 1024, 512, 512) == 4
