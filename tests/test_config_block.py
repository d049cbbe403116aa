"""The block ``TransformerConfig`` spells by configuration (RMS norm,
rotary positions, grouped-query heads with q/k norm, gated MLP, the gated
short convolution, a ``layer_types`` pattern, tied embeddings, window
layers beside full ones without positions, ReGLU experts, a router that
reads the operator's input) and a chip's share of routed experts
(``parallel/moe.py`` ``moe_held_experts``), against the plain float32
references the benchmark keeps (``benchmark/models/lfm2.py``,
``benchmark/models/smallthinker.py``), at small sizes on the CPU."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.models import gpt2, lfm2, smallthinker
from horovod_tpu import metrics
from horovod_tpu.models import TransformerConfig, TransformerLM, operators
from horovod_tpu.models import transformer
from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name, tiny=False, **overrides):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    if tiny:
        config = {**config, **config["tiny"]}
    return {**config, **overrides}


def rel_error(got, want):
    diff = sum(jnp.sum(jnp.square(g.astype(jnp.float32) - w))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    norm = sum(jnp.sum(jnp.square(w)) for w in jax.tree.leaves(want))
    return float(jnp.sqrt(diff / norm))


def _model(dtype, **overrides):
    config = load_config("lfm2-24b-a2b", tiny=True, compute_dtype=dtype,
                         **overrides)
    model = lfm2.make_model(config)
    params, aux = jax.jit(lambda k: lfm2.init(model, config, k))(
        jax.random.PRNGKey(0))
    return config, model, params, aux


def _both(dtype, batch=2, seq=48):
    config, model, params, aux = _model(dtype)
    data = lfm2.make_batch(config, jax.random.PRNGKey(2), batch, seq)
    system = jax.jit(jax.value_and_grad(
        lambda p: lfm2.loss(model, p, aux, data), has_aux=True))(params)
    reference = jax.jit(jax.value_and_grad(
        lambda p: lfm2.reference_loss(config, p, aux, data),
        has_aux=True))(params)
    return system, reference


# --------------------------------------------------------------------------
# the whole model against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seq,buffer_rows", [(48, 384), (512, 2048)],
                         ids=["whole-buffer", "short-buffer"])
def test_model_is_the_reference_in_float32(seq, buffer_rows):
    """Two sequences of 48 fill a buffer no longer than the short one
    would be; of 512 (4096 pairs, a quarter of the experts held) every
    expert layer runs in chunks, and the first, 2048 rows, holds the load."""
    ((loss, aux), grads), ((ref_loss, ref_aux), ref_grads) = _both(
        "float32", seq=seq)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == 49
    for (path, got), want in zip(leaves, jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(want))) > 0, jax.tree_util.keystr(path)
        assert rel_error(got, want) < 1e-4, jax.tree_util.keystr(path)
    # the load the step carries on: what each routed expert was picked
    for name, layer in aux.items():
        ref = ref_aux[name]["moe"]
        np.testing.assert_array_equal(layer["moe"]["expert_load"],
                                      ref["expert_load"])
        assert int(layer["moe"]["rows_held"]) == int(ref["rows_held"])
        assert int(layer["moe"]["buffer_rows"]) == buffer_rows
        assert int(jnp.sum(layer["moe"]["expert_load"])) == 2 * seq * 4


def test_bfloat16_error_is_seen_and_small():
    ((loss, _), grads), ((ref_loss, _), ref_grads) = _both("bfloat16")
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-2)
    assert 1e-4 < rel_error(grads, ref_grads) < lfm2.GRAD_REL_TOL


def test_parameters_are_what_the_configuration_names():
    config, _, params, aux = _model("float32")
    shapes = {jax.tree_util.keystr(path): leaf.shape for path, leaf
              in jax.tree_util.tree_leaves_with_path(params)}
    assert shapes["['block_0']['conv']['in_proj']['kernel']"] == (64, 192)
    assert shapes["['block_0']['conv']['kernel']"] == (3, 64)
    assert shapes["['block_0']['mlp']['w1']['kernel']"] == (64, 128)
    assert shapes["['block_1']['attn']['q']['kernel']"] == (64, 4, 16)
    assert shapes["['block_1']['attn']['k']['kernel']"] == (64, 2, 16)
    assert shapes["['block_1']['attn']['q_norm']['scale']"] == (16,)
    assert shapes["['block_1']['moe']['router']"] == (64, 8)
    assert shapes["['block_1']['moe']['w1']"] == (2, 64, 32)
    assert shapes["['block_4']['moe']['w2']"] == (2, 32, 64)
    assert shapes["['embed']['embedding']"] == (256, 64)
    assert not any("lm_head" in k or "pos_embed" in k for k in shapes)
    assert set(aux) == {"block_1", "block_2", "block_3", "block_4"}
    assert aux["block_1"]["moe"]["expert_bias"].shape == (8,)
    # seeded away from zero, so that selection is not the plain top-k
    assert float(jnp.max(jnp.abs(aux["block_1"]["moe"]["expert_bias"]))) > 0


# --------------------------------------------------------------------------
# the expert layer
# --------------------------------------------------------------------------

ROUTED, WIDTH, FF, TOP_K, TOKENS = 16, 32, 24, 4, 40


def _expert_layer(key=0):
    keys = jax.random.split(jax.random.PRNGKey(key), 6)
    return {
        "x": jax.random.normal(keys[0], (TOKENS, WIDTH)),
        "router": jax.random.normal(keys[1], (WIDTH, ROUTED)) * 0.5,
        "bias": 0.05 * jax.random.normal(keys[2], (ROUTED,)),
        "w1": jax.random.normal(keys[3], (ROUTED, WIDTH, FF)) * 0.2,
        "w3": jax.random.normal(keys[4], (ROUTED, WIDTH, FF)) * 0.2,
        "w2": jax.random.normal(keys[5], (ROUTED, FF, WIDTH)) * 0.2,
    }


def _share(t, first, count, bias=None):
    """The program: experts ``[first, first + count)`` of the layer."""
    bias = t["bias"] if bias is None else bias
    idx, weights = moe.route_sigmoid_top_k(t["x"] @ t["router"], bias, TOP_K)
    held = slice(first, first + count)

    def experts(rows, sizes):
        h = jax.nn.silu(moe.grouped_matmul(rows, t["w1"][held], sizes)) \
            * moe.grouped_matmul(rows, t["w3"][held], sizes)
        return moe.grouped_matmul(h, t["w2"][held], sizes)

    return moe.moe_held_experts(t["x"], idx, weights, experts, first=first,
                                count=count, n_routed=ROUTED)


def _reference_share(t, first, count, bias=None):
    """The plain reference's expert layer, given the same share."""
    # the reference counts its share from expert_shard * num_experts
    assert first % count == 0
    config = {"num_experts": count, "expert_shard": first // count,
              "num_experts_per_tok": TOP_K, "norm_topk_prob": True,
              "routed_scaling_factor": 1}
    held = slice(first, first + count)
    p = {"router": t["router"], "w1": t["w1"][held], "w3": t["w3"][held],
         "w2": t["w2"][held]}
    y, load, _ = lfm2._experts(config, p,
                               t["bias"] if bias is None else bias,
                               t["x"][None])
    return y[0], load


@pytest.mark.parametrize("count", [2, 8, 16])
def test_the_shares_add_up_to_the_whole_layer(count):
    t = _expert_layer()
    whole, _ = _reference_share(t, 0, ROUTED)
    total, rows = 0, 0
    for first in range(0, ROUTED, count):
        y, load = _share(t, first, count)
        want, want_load = _reference_share(t, first, count)
        np.testing.assert_allclose(y, want, atol=2e-6)
        assert int(load["rows_held"]) == int(want_load["rows_held"])
        total, rows = total + y, rows + int(load["rows_held"])
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert rows == TOKENS * TOP_K          # every pick landed on one share


@pytest.mark.parametrize("favoured", [[0], [0, 1, 2, 3], [5]],
                         ids=["one-held", "all-four-picks-held", "elsewhere"])
def test_nothing_drops_under_any_imbalance(favoured):
    """A bias that sends every token to the same experts: a capacity
    bucket would drop most of them; here each still matches the
    reference, values and gradients."""
    t = _expert_layer(1)
    bias = t["bias"].at[jnp.array(favoured)].add(10.0)
    first, count = 0, 4

    def loss(fn):
        def f(x, w1, router):
            y, _ = fn({**t, "x": x, "w1": w1, "router": router}, first,
                      count, bias)
            return jnp.sum(jnp.sin(y))
        return f

    y, load = _share(t, first, count, bias)
    want, _ = _reference_share(t, first, count, bias)
    np.testing.assert_allclose(y, want, atol=2e-6)
    here = [e for e in favoured if e < count]
    for e in favoured:
        assert int(load["expert_load"][e]) == TOKENS
    assert int(load["rows_held"]) >= TOKENS * len(here)
    if len(here) == TOP_K:                  # the buffer is full
        assert int(load["rows_held"]) == TOKENS * TOP_K
    args = (t["x"], t["w1"], t["router"])
    got = jax.grad(loss(_share), argnums=(0, 1, 2))(*args)
    ref = jax.grad(loss(_reference_share), argnums=(0, 1, 2))(*args)
    for g, r in zip(got, ref):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, r, atol=5e-6)


def test_selection_bias_selects_and_nothing_else():
    t = _expert_layer(2)
    logits = t["x"] @ t["router"]
    plain, w_plain = moe.route_sigmoid_top_k(logits, jnp.zeros(ROUTED), TOP_K)
    tilted = jnp.zeros(ROUTED).at[3].set(10.0)
    idx, weights = moe.route_sigmoid_top_k(logits, tilted, TOP_K)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))     # it selects
    assert not bool(jnp.all(jnp.any(plain == 3, axis=-1)))
    scores = jax.nn.sigmoid(logits)
    picked = jnp.take_along_axis(scores, idx, -1)        # unbiased scores
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(jnp.sin(
        moe.route_sigmoid_top_k(logits, b, TOP_K)[1])))(t["bias"])
    assert float(jnp.max(jnp.abs(grad))) == 0.0           # no gradient
    _, raw = moe.route_sigmoid_top_k(logits, t["bias"], TOP_K,
                                     renormalize=False, scaling=2.5)
    _, idx_b = jax.lax.top_k(scores + t["bias"], TOP_K)
    np.testing.assert_allclose(
        raw, 2.5 * jnp.take_along_axis(scores, idx_b, -1), rtol=1e-6)


def _moe_series(instrument, label):
    return {dict(labels)[label]: int(value) for labels, value
            in instrument.series().items()}


def test_counters_say_what_a_trace_emitted():
    config, model, params, aux = _model("float32")
    tokens = jnp.zeros((1, 16), jnp.int32)
    before = _moe_series(metrics.MOE_CALLS, "path")
    jax.make_jaxpr(lambda p: lfm2.loss(model, p, aux, (tokens,))[0])(params)
    after = _moe_series(metrics.MOE_CALLS, "path")
    assert after["held_share"] - before.get("held_share", 0) == 4
    assert after.get("alltoall", 0) == before.get("alltoall", 0)
    assert _moe_series(metrics.MOE_SHAPE, "what") == {
        "experts_held": 2, "experts_routed": 8, "top_k": 4,
        "buffer_rows_short": 0, "router_softmax": 0}

    mesh = jax.make_mesh((8,), ("ep",))
    x = jnp.ones((8 * 4, 16))
    jax.make_jaxpr(jax.shard_map(
        lambda x: moe.moe_alltoall(x, x[:, :8], lambda t: t, "ep")[0],
        mesh=mesh, in_specs=P("ep"), out_specs=P("ep"),
        check_vma=False))(x)
    assert _moe_series(metrics.MOE_CALLS, "path")["alltoall"] \
        == after.get("alltoall", 0) + 1


# a buffer as long as the load: 512 tokens x top-4 = 2048 pairs, 4 of 16
# experts held, so the short buffer is 1024 rows
LONG, HELD = 512, 4
PAIRS = LONG * TOP_K
SHORT = 1024


def _picks(load, tokens=LONG, held=HELD):
    """(tokens, TOP_K) picks, by hand, of which exactly ``load`` fall on
    experts 0..held-1: the first tokens pick as many of them as they can,
    the next one the remainder, the rest experts held elsewhere."""
    most = min(TOP_K, held)
    idx = np.empty((tokens, TOP_K), np.int32)
    for t in range(tokens):
        here = min(max(load - t * most, 0), most)
        away = [held + (t + j) % (ROUTED - held) for j in range(TOP_K)]
        idx[t] = list(range(here)) + away[here:]
    assert int(np.sum(idx < held)) == load
    return jnp.asarray(idx)


def _long_layer(tokens=LONG):
    t = _expert_layer(3)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    weights = jax.nn.softmax(jax.random.normal(keys[1], (tokens, TOP_K)))
    return {**t, "x": jax.random.normal(keys[0], (tokens, WIDTH)),
            "weights": weights}


def _held_program(held, idx, x, weights, w1, w3, w2):
    def experts(rows, sizes):
        h = jax.nn.silu(moe.grouped_matmul(rows, w1[:held], sizes)) \
            * moe.grouped_matmul(rows, w3[:held], sizes)
        return moe.grouped_matmul(h, w2[:held], sizes)

    return moe.moe_held_experts(x, idx, weights, experts, first=0,
                                count=held, n_routed=ROUTED)


def _held_dense(held, idx, x, weights, w1, w3, w2):
    """Every held expert over every token, masked by the picks."""
    y = 0
    for e in range(held):
        out = (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        y = y + jnp.sum(jnp.where(idx == e, weights, 0), -1)[:, None] * out
    return y


@pytest.mark.parametrize("pairs,count,routed,rows", [
    (32768, 8, 64, 8192),     # the benchmark's cell: twice the expected 4096
    (2048, 4, 16, 1024),
    (2048, 1, 64, 512),       # rounded up to 512 rows
    (3000, 3, 64, 512),       # 281.25 expected twice over: up, not down
    (160, 2, 16, 160),        # 512 would pass the pairs: the whole buffer
    (2048, 8, 16, 2048),      # half the experts held
    (2048, 16, 16, 2048),
])
def test_short_buffer_is_twice_the_expected_load(pairs, count, routed, rows):
    assert moe.short_buffer_rows(pairs, count, routed) == rows


@pytest.mark.parametrize("tokens,held,load", [
    (LONG, HELD, 300), (LONG, HELD, SHORT), (LONG, HELD, SHORT + 1),
    (LONG, HELD, PAIRS),
    (LONG, 2, 1024),          # 512-row chunks: two of the four hold rows
    (LONG, 1, 512),           # one expert, every token: exactly its 512
    (320, 2, 513),            # 1280 pairs in three chunks of 512: padded
    (320, 2, 640),
], ids=["below", "exactly-R", "R-plus-1", "every-pair", "two-of-four-chunks",
        "one-expert-exactly-R", "chunks-do-not-divide",
        "chunks-do-not-divide-every-pair"])
def test_both_buffers_give_the_layer(tokens, held, load):
    """Loads on either side of the short buffer's length: the same ``y``,
    ``load`` and gradients as the dense layer, however many chunks of the
    buffer ran, and ``buffer_rows`` says how many."""
    t, idx = _long_layer(tokens), _picks(load, tokens, held)
    args = (t["x"], t["weights"], t["w1"], t["w3"], t["w2"])
    pairs = tokens * TOP_K
    short = moe.short_buffer_rows(pairs, held, ROUTED)
    assert short < pairs

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(held, idx, *a)))

    (y, got_load) = jax.jit(_held_program, static_argnums=0)(held, idx, *args)
    np.testing.assert_allclose(y, _held_dense(held, idx, *args), atol=5e-6)
    assert int(got_load["rows_held"]) == load
    assert int(got_load["buffer_rows"]) == min(-(-load // short) * short,
                                               pairs)
    np.testing.assert_array_equal(
        got_load["expert_load"], np.bincount(np.asarray(idx).ravel(),
                                             minlength=ROUTED))
    got = jax.jit(jax.grad(loss(lambda *a: _held_program(*a)[0]),
                           argnums=range(5)))(*args)
    want = jax.grad(loss(_held_dense), argnums=range(5))(*args)
    for name, g, w in zip(("x", "weights", "w1", "w3", "w2"), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, w, atol=2e-5, err_msg=name)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _grad_jaxpr(count, tokens=LONG):
    t = _long_layer()

    def loss(x, weights, w1, w3, w2):
        y, _ = _held_program(count, _picks(300)[:tokens], x, weights, w1,
                             w3, w2)
        return jnp.sum(jnp.sin(y))

    return jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(
        t["x"][:tokens], t["weights"][:tokens], t["w1"], t["w3"],
        t["w2"]).jaxpr


@pytest.mark.parametrize("count,tokens", [(8, LONG), (16, LONG), (2, 40)],
                         ids=["half-held", "all-held", "few-tokens"])
def test_one_chunk_and_no_loop_where_the_short_buffer_is_the_whole(
        count, tokens):
    assert moe.short_buffer_rows(tokens * TOP_K, count, ROUTED) \
        == tokens * TOP_K
    names = {eqn.primitive.name for eqn in _equations(_grad_jaxpr(count,
                                                                  tokens))}
    assert "ragged_dot_general" in names or "ragged_dot" in names
    assert not names & {"cond", "while", "scan"}


def _long_passes(jaxpr):
    """Primitives that read or write ``PAIRS`` rows of activations: a
    grouped product with such an operand, anything with such a
    two-dimensional floating result (the counts of the load compare
    ``PAIRS`` picks with every expert: integers and booleans)."""
    found = set()
    for eqn in _equations(jaxpr):
        name = eqn.primitive.name
        if name.startswith("ragged_dot") and any(
                v.aval.shape[0] == PAIRS for v in eqn.invars):
            found.add(name)
        for v in eqn.outvars:
            shape = v.aval.shape
            if len(shape) == 2 and shape[0] == PAIRS and shape[1] > 1 \
                    and jnp.issubdtype(v.aval.dtype, jnp.floating):
                found.add(name)
    return found


def test_no_pass_is_as_long_as_the_pairs():
    """Forward and backward, the first chunk and the loops over the
    later ones (one ``while`` each way): no two-dimensional array is
    ``PAIRS`` rows long (the passes back to the tokens gather ``LONG``
    rows a pick) and every grouped product reads ``SHORT`` rows. Where
    the short buffer is the whole the passes are ``PAIRS`` rows long,
    which shows the test sees."""
    jaxpr = _grad_jaxpr(HELD)
    assert _long_passes(jaxpr) == set()
    products = [eqn for eqn in _equations(jaxpr)
                if eqn.primitive.name.startswith("ragged_dot")]
    assert products and all(eqn.invars[0].aval.shape[0] == SHORT
                            for eqn in products)
    loops = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "while"]
    assert len(loops) == 2
    assert all(any(e.primitive.name.startswith("ragged_dot")
                   for e in _equations(loop.params["body_jaxpr"].jaxpr))
               for loop in loops)
    assert _long_passes(_grad_jaxpr(8)) >= {"select_n", "mul"}


@pytest.mark.parametrize("count,short", [(HELD, SHORT), (8, 0)],
                         ids=["chunks", "one-chunk"])
def test_registry_says_whether_a_short_buffer_was_traced(count, short):
    before = _moe_series(metrics.MOE_CALLS, "path")
    _grad_jaxpr(count)
    after = _moe_series(metrics.MOE_CALLS, "path")
    assert after["held_share"] - before.get("held_share", 0) == 1
    assert after.get("held_share_short_buffer", 0) \
        - before.get("held_share_short_buffer", 0) == (1 if short else 0)
    assert _moe_series(metrics.MOE_SHAPE, "what")["buffer_rows_short"] \
        == short


# --------------------------------------------------------------------------
# the operators
# --------------------------------------------------------------------------

def test_short_convolution_is_causal_and_the_references():
    config, _, params, _ = _model("float32")
    cfg = lfm2.make_model(config).cfg
    conv = operators.ShortConv(cfg)
    p = params["block_0"]["conv"]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64))
    out = conv.apply({"params": p}, u)
    np.testing.assert_allclose(out, lfm2._short_conv(config, p, u),
                               atol=1e-5)
    at = 11
    moved = conv.apply({"params": p}, u.at[:, at].add(1.0))
    np.testing.assert_array_equal(moved[:, :at], out[:, :at])
    assert float(jnp.max(jnp.abs(moved[:, at] - out[:, at]))) > 1e-3
    # three taps: positions t, t+1, t+2 see the change, t+3 does not... but
    # through z = B * x only; the gate C is pointwise
    assert float(jnp.max(jnp.abs(moved[:, at + 2] - out[:, at + 2]))) > 1e-4
    np.testing.assert_allclose(moved[:, at + 3:], out[:, at + 3:], atol=1e-6)


def test_rotary_qk_norm_and_grouped_query_heads_against_plain_einsum():
    # 2 key/value heads under 8 query heads, as 8 under 32
    config = load_config("lfm2-24b-a2b", tiny=True, compute_dtype="float32",
                         num_attention_heads=8, num_key_value_heads=2)
    cfg = lfm2.make_model(config).cfg
    attn = transformer.Attention(cfg)
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 64))
    p = attn.init(jax.random.PRNGKey(5), u)["params"]
    assert p["k"]["kernel"].shape == (64, 2, 8)
    # scales away from 1, so that the norms' parameters are seen
    p = jax.tree.map(lambda x: x, p)
    p["q_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(6), (8,))
    p["k_norm"]["scale"] = 1 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(7), (8,))

    def plain(p, u):
        """No blocks, no helper of the reference's: one einsum a step."""
        head, group = 8, 4
        q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"])
        k = jnp.einsum("bsd,dhk->bshk", u, p["k"]["kernel"])
        v = jnp.einsum("bsd,dhk->bshk", u, p["v"]["kernel"])
        rms = lambda x, s: x / jnp.sqrt(
            jnp.mean(x * x, -1, keepdims=True) + 1e-5) * s
        q, k = rms(q, p["q_norm"]["scale"]), rms(k, p["k_norm"]["scale"])
        pos = jnp.arange(u.shape[1])[:, None]
        freq = 1e6 ** (-jnp.arange(0, head, 2) / head)[None]
        cos = jnp.cos(pos * freq)[None, :, None]
        sin = jnp.sin(pos * freq)[None, :, None]

        def rope(x):
            a, b = x[..., :head // 2], x[..., head // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        q, k = rope(q), rope(k)
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / head ** 0.5
        mask = jnp.tril(jnp.ones((u.shape[1],) * 2, bool))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return jnp.einsum("bshk,hkd->bsd", out, p["o"]["kernel"])

    got = attn.apply({"params": p}, u)
    np.testing.assert_allclose(got, plain(p, u), atol=2e-5)
    np.testing.assert_allclose(got, lfm2._attention(config, p, u), atol=2e-5)
    grads = jax.jit(jax.grad(
        lambda p: jnp.sum(jnp.sin(attn.apply({"params": p}, u)))))(p)
    wants = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(plain(p, u)))))(p)
    assert rel_error(grads, wants) < 1e-4


@pytest.mark.parametrize("mask", ["causal", "block_diffusion"])
@pytest.mark.parametrize("kv_heads,head_dim", [(8, 128), (2, 128), (1, 128),
                                               (2, 16)])
def test_grouped_heads_on_the_blocked_path_match_the_materialised(
        monkeypatch, mask, kv_heads, head_dim):
    """``Attention`` with 8 query heads over ``kv_heads``: the blocked
    path (the kernels interpreted; k and v at their own head count from
    heads of 128 up, repeated below) against the materialised one (k and
    v repeated), output and every gradient."""
    from horovod_tpu.ops import flash

    kernels = []

    def interpreted(kernel):
        def call(q, k, *args, **kwargs):
            kernels.append(q.shape[0] // k.shape[0])
            return kernel(q, k, *args, **{**kwargs, "interpret": True})
        return call

    for name in ("_flash_attend", "_flash_block_grads"):
        monkeypatch.setattr(flash, name, interpreted(getattr(flash, name)))
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 128)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 128)
    cfg = TransformerConfig(num_heads=8, num_kv_heads=kv_heads,
                            head_dim=head_dim, d_model=64, norm="rmsnorm",
                            positions="rotary", qk_norm=True,
                            dtype=jnp.float32, attn_mask=mask,
                            block_length=4 if mask != "causal" else 1)
    attn = transformer.Attention(cfg)
    u = jax.random.normal(jax.random.PRNGKey(8), (2, 200, 64))
    params = attn.init(jax.random.PRNGKey(9), u)["params"]
    assert params["k"]["kernel"].shape == (64, kv_heads, head_dim)

    def run(blocked):
        monkeypatch.setattr(transformer, "blocked_selected",
                            lambda *observed: blocked)
        return jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            attn.apply({"params": p}, u))))(params)

    (got, grads), (want, wants) = run(True), run(False)
    # forward and backward, once a sweep; as groups from heads of 128 up
    group = 8 // kv_heads if head_dim >= 128 else 1
    assert kernels == [group] * (2 if mask == "causal" else 4)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert rel_error(grads, wants) < 1e-5


@pytest.mark.parametrize("field,value", [
    ("norm", "batchnorm"), ("positions", "alibi"), ("mlp", "relu"),
    ("layer_types", ("conv", "full_attention")),         # two names, 4 layers
    ("layer_types", ("conv", "scan", "conv", "conv")),   # unknown operator
    ("num_kv_heads", 3),                                  # 8 heads over 3
    ("moe_held", (60, 8)),                                # past 64 routed
    ("attn_mode", "ring"),            # with rotary: no shard offset yet
    ("layer_types", ("sliding_attention",) * 4),          # no window size
    ("sliding_window", 16),                               # no window layer
    ("rope_layout", (0, 1)),                              # two of 4 layers
    ("rope_layout", (0, 1, 2, 1)),
    ("moe_activation", "gelu"), ("moe_router_input", "expert_norm"),
])
def test_config_refuses_what_it_cannot_spell(field, value):
    with pytest.raises(ValueError):
        TransformerConfig(**{"num_layers": 4, "num_heads": 8,
                             "moe_routed": 64, "moe_top_k": 4,
                             "positions": "rotary", field: value})


# --------------------------------------------------------------------------
# what stays as it was
# --------------------------------------------------------------------------

# sha256 of the five-line GPT-2 step's lowered text and of its parameter
# tree at the benchmark's tiny sizes, computed on the tree before the block
# became configurable (PR 31's, 04a8d58) by this file's own code.
GPT2_STEP_SHA256 = "36f152ce45629f2aad7d1b7f959db89c6061e0fb4796ba71ba8156f2e256f4e9"
GPT2_TREE_SHA256 = "df78fd43c97d27f176c7cbc7c97fd8024164d2f812357fae3dbe9337c9b66532"


def _gpt2_step(hvd):
    config = load_config("gpt2-medium", tiny=True)
    model = gpt2.make_model(config)
    params, aux = jax.eval_shape(
        lambda k: gpt2.init(model, config, k), jax.random.PRNGKey(0))
    tx = hvd.DistributedOptimizer(optax.adam(3e-4))
    opt = jax.eval_shape(tx.init, params)

    def train_step(params, aux, opt_state, tokens):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: gpt2.loss(model, p, aux, (tokens,)),
            has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), aux, opt_state,
                jax.lax.pmean(loss, hvd.axis_name()))

    step = jax.jit(jax.shard_map(
        train_step, mesh=hvd.mesh(),
        in_specs=(P(), P(), P(), P(hvd.axis_name())),
        out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
    tokens = jax.ShapeDtypeStruct((hvd.size() * 2, 64), jnp.int32)
    return params, step.lower(params, aux, opt, tokens).as_text()


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


def test_gpt2_step_lowers_to_the_text_it_had(hvd):
    params, text = _gpt2_step(hvd)
    tree = "\n".join(f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
                     for path, leaf
                     in jax.tree_util.tree_leaves_with_path(params))
    assert _sha(tree) == GPT2_TREE_SHA256
    assert _sha(text) == GPT2_STEP_SHA256


def test_import_loads_no_pallas_and_gpt2_loads_no_operators():
    code = (
        "import sys, jax, horovod_tpu\n"
        "assert not [m for m in sys.modules if 'pallas' in m], 'pallas'\n"
        "import jax.numpy as jnp\n"
        "from horovod_tpu.models import TransformerConfig, TransformerLM\n"
        "m = TransformerLM(TransformerConfig(vocab_size=64, num_layers=2,"
        " num_heads=2, d_model=32, d_ff=64, max_seq_len=16))\n"
        "t = jnp.zeros((1, 16), jnp.int32)\n"
        "jax.eval_shape(m.init, jax.random.PRNGKey(0), t)\n"
        "assert 'horovod_tpu.models.operators' not in sys.modules\n"
        "assert not [m for m in sys.modules if 'pallas' in m], 'pallas'\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


# --------------------------------------------------------------------------
# window layers beside full ones without positions, ReGLU experts, the
# router on the operator's input (benchmark/models/smallthinker.py)
# --------------------------------------------------------------------------

def _smallthinker(dtype="float32", **overrides):
    config = load_config("smallthinker-21b-a3b", tiny=True,
                         compute_dtype=dtype, **overrides)
    model = smallthinker.make_model(config)
    params, aux = jax.jit(lambda k: smallthinker.init(model, config, k))(
        jax.random.PRNGKey(0))
    return config, model, params, aux


@pytest.mark.parametrize("seq", [48, 512], ids=["inside-one-tile",
                                                "short-buffer"])
def test_window_nope_reglu_model_is_the_reference_in_float32(seq):
    """A full layer without positions, then three rotary layers over a
    window of 24 keys (crossed by every sequence here), 14 query heads
    over 2 (groups of 7), ReGLU experts routed from the attention's input:
    loss, every gradient and the load against the plain reference."""
    config, model, params, aux = _smallthinker()
    cfg = model.cfg
    assert cfg.layer_types == ("full_attention",) + ("sliding_attention",) * 3
    assert cfg.rope_layout == (0, 1, 1, 1) and cfg.sliding_window == 24
    assert (cfg.moe_activation, cfg.moe_router_input) == ("relu",
                                                          "operator_input")
    data = smallthinker.make_batch(config, jax.random.PRNGKey(2), 2, seq)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: smallthinker.loss(model, p, aux, data), has_aux=True))(
            params)
    (ref_loss, ref_aux), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: smallthinker.reference_loss(config, p, aux, data),
        has_aux=True))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                 jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(want))) > 0, jax.tree_util.keystr(path)
        assert rel_error(got, want) < 1e-4, jax.tree_util.keystr(path)
    for name, layer in aux.items():
        np.testing.assert_array_equal(layer["moe"]["expert_load"],
                                      ref_aux[name]["moe"]["expert_load"])


@pytest.mark.parametrize("field,value", [
    ("moe_router_input", "ffn_input"), ("moe_activation", "silu"),
    ("rope_layout", (1, 1, 1, 1)), ("sliding_window", 4096)])
def test_each_choice_of_the_block_is_seen(field, value):
    """The model with one field put back to what the other configurations
    run (the router on the ffn norm's output, SwiGLU, rotary at every
    layer, a window longer than the sequence) is not the reference's
    function: the reference follows each field."""
    import dataclasses

    config, model, params, aux = _smallthinker()
    other = TransformerLM(dataclasses.replace(model.cfg, **{field: value}))
    data = smallthinker.make_batch(config, jax.random.PRNGKey(3), 2, 48)
    ref_loss, _ = smallthinker.reference_loss(config, params, aux, data)
    loss, _ = smallthinker.loss(model, params, aux, data)
    moved, _ = smallthinker.loss(other, params, aux, data)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert abs(float(moved) - float(ref_loss)) > 1e-4 * float(ref_loss)


def test_the_router_reads_the_attention_input():
    """The picks of each layer are the reference's top-6 of
    ``softmax(norm_attn(x) W_r)``; from ``norm_ffn(h)`` they would be
    others."""
    config, model, params, aux = _smallthinker()
    tokens = smallthinker.make_batch(config, jax.random.PRNGKey(4), 2, 48)[0]
    _, state = model.apply({"params": params, "routing": aux}, tokens,
                           mutable=["intermediates", "routing"])
    p = params["block_0"]
    eps = config["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens]
    u = smallthinker._rms_norm(x, p["operator_norm"]["scale"], eps)
    h = x + smallthinker._attention(config, p["attn"], u, 0, 0)
    e = smallthinker._rms_norm(h, p["ffn_norm"]["scale"], eps)
    (idx,) = state["intermediates"]["block_0"]["moe"]["expert_idx"]
    picked = jnp.zeros((idx.shape[0], config["experts_routed"]), bool).at[
        jnp.arange(idx.shape[0])[:, None], idx].set(True)

    def picks(rows):
        return smallthinker._routing(config, p["moe"],
                                     rows.reshape(-1, rows.shape[-1]))[1]

    np.testing.assert_array_equal(picked, picks(u))
    assert not bool(jnp.all(picked == picks(e)))


def test_the_shares_of_a_window_layer_add_up_to_the_whole_layer():
    """The chip's-share cut of a window layer (program's ``Block``): each
    of the 4 shares of 16 experts computes the attention every chip
    computes alike and its own experts' part; the parts, with the
    attention counted once, are the uncut reference layer."""
    config, model, params, _ = _smallthinker()
    shares = config["experts_routed"] // config["moe_num_primary_experts"]
    whole = {**config, "moe_num_primary_experts": config["experts_routed"]}
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    p = params["block_1"]
    p = {**p, "moe": {**p["moe"], **{
        w: jax.random.normal(key, (config["experts_routed"],)
                             + p["moe"][w].shape[1:]) * 0.2
        for w, key in zip(("w1", "w3", "w2"), keys)}}}
    x = jax.random.normal(keys[3], (2, 48, config["hidden_size"]))
    want, _ = smallthinker._layer(whole, p, x, 1, 1)
    u = smallthinker._rms_norm(x, p["operator_norm"]["scale"],
                               config["rms_norm_eps"])
    h = x + smallthinker._attention(config, p["attn"], u, 1, 1)
    total = h
    for share in range(shares):
        cfg = smallthinker.make_model({**config, "expert_shard": share}).cfg
        first, count = cfg.moe_held
        held = slice(first, first + count)
        mine = {**p, "moe": {**p["moe"], **{
            w: p["moe"][w][held] for w in ("w1", "w3", "w2")}}}
        block = transformer.Block(cfg, "sliding_attention", experts=True,
                                  rotary=True)
        y, _ = block.apply({"params": mine}, x, mutable=["routing"])
        total = total + (y - h)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv_heads,head_dim", [(4, 128), (28, 16)])
@pytest.mark.parametrize("window", [40, 1000])
def test_window_attention_on_the_blocked_path_matches_the_materialised(
        monkeypatch, kv_heads, head_dim, window):
    """``Attention`` over a window: the blocked kernels (interpreted; 28
    query heads over 4 of 128 as groups of 7) against the materialised
    path under the window's mask, output and every gradient, with the
    counters and the visible share the trace leaves."""
    from horovod_tpu.ops import flash

    for name in ("_flash_attend", "_flash_block_grads"):
        real = getattr(flash, name)
        monkeypatch.setattr(flash, name, lambda *a, _real=real, **kw: _real(
            *a, **{**kw, "interpret": True}))
    monkeypatch.setattr(flash, "DEFAULT_Q_TILE", 128)
    monkeypatch.setattr(flash, "DEFAULT_KV_TILE", 64)
    # the statements before the kernels, which the CPU runs as they are
    monkeypatch.setattr(transformer, "prepare_rows_selected",
                        lambda *shapes: False)
    cfg = TransformerConfig(num_heads=28, num_kv_heads=kv_heads,
                            head_dim=head_dim, d_model=64, norm="rmsnorm",
                            positions="rotary", dtype=jnp.float32,
                            num_layers=1, layer_types=("sliding_attention",),
                            sliding_window=window)
    attn = transformer.Attention(cfg, window)
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 256, 64))
    params = attn.init(jax.random.PRNGKey(9), u)["params"]

    def run(blocked):
        monkeypatch.setattr(transformer, "blocked_selected",
                            lambda *observed: blocked)
        return jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            attn.apply({"params": p}, u))))(params)

    before = _moe_series(metrics.ATTENTION_CALLS, "path")
    (got, grads), (want, wants) = run(True), run(False)
    moved = {k: v - before.get(k, 0) for k, v in
             _moe_series(metrics.ATTENTION_CALLS, "path").items()
             if v != before.get(k, 0) and "projection" not in k}
    grouped = {"blocked_grouped": 1} if head_dim == 128 else {}
    assert moved == {"blocked_window": 1, "materialised_window": 1,
                     **grouped}
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert rel_error(grads, wants) < 1e-5
    assert transformer.visible_tile_share(256, "causal", 1, window) == (
        5 / 8 if window == 40 else 3 / 4)


def test_recomputed_blocks_change_no_number():
    """``remat_blocks`` (the configuration's per-block recomputation):
    loss, gradients and the load the step carries on are those of the
    model that keeps its activations, and the parameter tree is the same."""
    import dataclasses

    config, model, params, aux = _smallthinker()
    assert model.cfg.remat_blocks
    kept = TransformerLM(dataclasses.replace(model.cfg, remat_blocks=False))
    data = smallthinker.make_batch(config, jax.random.PRNGKey(6), 2, 48)

    def run(m):
        return jax.jit(jax.value_and_grad(
            lambda p: smallthinker.loss(m, p, aux, data), has_aux=True))(
                params)

    ((loss, got_aux), grads), ((want, want_aux), wants) = run(model), run(kept)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert rel_error(grads, wants) < 1e-6
    for name in got_aux:
        np.testing.assert_array_equal(got_aux[name]["moe"]["expert_load"],
                                      want_aux[name]["moe"]["expert_load"])
    shapes = jax.eval_shape(kept.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    assert jax.tree.structure(shapes["params"]) == jax.tree.structure(params)
