"""The ``smallthinker-21b-a3b`` configuration: its counts against a hand
count, its file against the catalog row it was cut from, its reference
against the program at the ``tiny`` sizes, its cell's rehearsal, and the
readers of the per-layer metrics it brought."""

import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace_reduce
from benchmark.models import smallthinker
from benchmark.tests.conftest import ROOT, load_config
from benchmark.tests.test_reference import rel_error
from benchmark.tests.test_rehearsal import run_cell

CELL = "smallthinker-traced-1chip"
LAYOUT = [0, 1, 1, 1] * 13
# what the catalog's row for SmallThinker-21BA3B-Instruct gives (its `config`)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}


def test_file_is_the_published_config_but_for_what_it_lists():
    config = load_config("smallthinker-21b-a3b")
    reduced = set(config["reduced"])
    assert reduced == {"layer_types", "moe_num_primary_experts",
                       "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # the floors of the chip's-share cut: one whole period of the layer
    # pattern, 8 experts, an eighth of the vocabulary
    assert config["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3
    assert config["moe_num_primary_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    shares = config["deployment"]["chips_sharing_a_layer"]
    assert (config["moe_num_primary_experts"] * shares
            == config["experts_routed"] == 64)
    assert config["vocab_size"] * shares == PUBLISHED["vocab_size"]
    assert {"router_input", "window", "activation", "qk_norm"} <= set(
        config["assumed"])
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    assert (cell["chips"], cell["batch_per_chip"], cell["seq_len"],
            cell["job"]) == (1, 1, 16384, "traced")
    # the tiny preset's window is crossed by its sequence
    tiny = load_config("smallthinker-21b-a3b", tiny=True)
    assert tiny["sliding_window_size"] < cell["tiny"]["seq_len"]


def test_parameters_and_model_flops_against_hand_count():
    config = load_config("smallthinker-21b-a3b")
    d = 2560
    attention = 2 * d * 28 * 128 + 2 * d * 4 * 128     # q, o, k, v
    assert attention == pytest.approx(20.97e6, rel=1e-3)
    experts = 8 * 3 * d * 768 + d * 64                  # held, the router
    layer = attention + experts + 2 * d                 # and two norms
    assert layer == pytest.approx(68.33e6, rel=1e-4)
    total = 4 * layer + 2 * 18992 * d + d
    assert total == 370_547_200
    assert total * 16 / 2 ** 30 == pytest.approx(5.52, rel=2e-3)
    model = smallthinker.make_model(config)
    shapes, _ = jax.eval_shape(
        lambda k: smallthinker.init(model, config, k), jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == total

    # what a row meets in a layer's matrix products: 0.75 of an expert
    n = attention + d * 64 + 0.75 * 3 * d * 768
    assert n == pytest.approx(25.56e6, rel=1e-3)
    assert smallthinker.layer_matmul_params(config) == n
    # the pairs the masks let through at 16384 rows
    assert smallthinker.visible_pairs(config, 16384) == 134_225_920
    assert smallthinker.visible_pairs(config, 16384, True) == (
        4096 * 4097 // 2 + 12288 * 4096) == 58_722_304
    per_pair = 12 * 28 * 128
    full, window = per_pair * 134_225_920, per_pair * 58_722_304
    assert smallthinker.window_attn_flops(config, 1, 16384) == 3 * window
    assert smallthinker.attn_flops(config, 1, 16384) == full + 3 * window
    assert full == pytest.approx(5.77e12, rel=2e-3)
    assert window == pytest.approx(2.53e12, rel=2e-3)
    layers, head = 6 * 4 * n * 16384, 6 * d * 18992 * 16384
    assert layers == pytest.approx(10.05e12, rel=2e-3)
    assert head == pytest.approx(4.78e12, rel=2e-3)
    step = smallthinker.model_flops(config, 1, 16384)
    assert step == layers + head + full + 3 * window
    assert step == pytest.approx(28.18e12, rel=2e-3)
    assert 3 * window / step == pytest.approx(0.27, abs=0.005)
    # the grouped products at the expected rows: 1536 an expert
    assert smallthinker.expected_rows(config, 16384) == 12288 == 8 * 1536
    assert smallthinker.gmm_flops(config, 16384) == 4 * 9 * 2 * 12288 * d * 768
    assert smallthinker.gmm_bytes(config, 16384) == 4 * 9 * 2 * (
        12288 * d + 12288 * 768 + 8 * d * 768)


@pytest.mark.parametrize("seq,window", [(10, 4), (7, 7), (5, 9)])
def test_attention_flops_count_the_pairs_by_hand(seq, window):
    """At a small size: every (query, key) pair the two masks let through,
    counted one by one."""
    config = {**load_config("smallthinker-21b-a3b", tiny=True),
              "sliding_window_size": window}
    full = sum(1 for i in range(seq) for j in range(seq) if j <= i)
    windowed = sum(1 for i in range(seq) for j in range(seq)
                   if i - window < j <= i)
    assert smallthinker.visible_pairs(config, seq) == full
    assert smallthinker.visible_pairs(config, seq, True) == windowed
    per_pair = 12 * config["num_attention_heads"] * config["head_dim"]
    assert smallthinker.attn_flops(config, 2, seq) == 2 * per_pair * (
        full + 3 * windowed)
    assert smallthinker.window_attn_flops(config, 2, seq) == (
        2 * per_pair * 3 * windowed)


def both(dtype, activations=None):
    """Gradients of the program (``compute_dtype`` ``dtype``) and of the
    reference at the tiny sizes, away from the initial point.
    ``activations``: a dtype every dense projection's result is rounded
    through on its way, and its cotangent, standing in for a step
    computed that coarsely."""
    config = load_config("smallthinker-21b-a3b", tiny=True,
                         compute_dtype=dtype)
    model = smallthinker.make_model(config)
    params, aux = jax.jit(lambda k: smallthinker.init(model, config, k))(
        jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1),
                            len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        p + 0.1 * jax.random.normal(k, p.shape)
        for p, k in zip(jax.tree.leaves(params), keys)])
    data = smallthinker.make_batch(config, jax.random.PRNGKey(2), 2, 64)

    def system_loss(p):
        if activations is None:
            return smallthinker.loss(model, p, aux, data)
        return smallthinker.loss_rounded_through(activations, model, p, aux,
                                                 data)

    system = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
    reference = jax.jit(jax.value_and_grad(
        lambda p: smallthinker.reference_loss(config, p, aux, data),
        has_aux=True))(params)
    return system, reference


def test_reference_is_the_models_function_in_float32():
    ((loss, aux), grads), ((ref_loss, ref_aux), ref_grads) = both("float32")
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert rel_error(grads, ref_grads) < 1e-4
    for name in aux:
        assert (aux[name]["moe"]["expert_load"]
                == ref_aux[name]["moe"]["expert_load"]).all()


def test_bfloat16_is_inside_the_tolerance_and_float8_is_not():
    ((loss, _), grads), ((ref_loss, _), ref_grads) = both("bfloat16")
    error = rel_error(grads, ref_grads)
    assert 1e-4 < error < smallthinker.GRAD_REL_TOL
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) \
        < smallthinker.LOSS_REL_TOL
    (_, grads8), _ = both("bfloat16", activations=jnp.float8_e4m3fn)
    assert rel_error(grads8, ref_grads) > smallthinker.GRAD_REL_TOL


def test_the_float8_control_is_not_a_convert_pair():
    """``reduce_precision`` survives compilation (a ``bf16 -> f8 -> bf16``
    convert pair may be folded away), and the per-tensor scale keeps a
    tensor far below float8's range from rounding to zero."""
    x = jnp.asarray([3e-6, -1.7e-6, 2.5e-7], jnp.bfloat16)
    got = smallthinker._round_scaled(x, jnp.float8_e4m3fn)
    assert (got != 0).all()
    assert (got != x).any()
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - x))) < 3e-7
    text = jax.jit(lambda v: smallthinker._round_scaled(
        v, jnp.float8_e4m3fn)).lower(x).compile().as_text()
    assert "reduce-precision" in text


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(smallthinker))
    for name in ("reference_loss", "reference_forward", "_layer",
                 "_attention", "_experts", "_routing", "_rotate",
                 "_rms_norm", "_reglu", "_next_token_loss"):
        fn = next(n for n in tree.body
                  if isinstance(n, ast.FunctionDef) and n.name == name)
        assert not [n for n in ast.walk(fn)
                    if isinstance(n, (ast.Import, ast.ImportFrom))], name
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not [n for n in top if "horovod" in ast.dump(n)]
    assert "default_matmul_precision(\"highest\")" in inspect.getsource(
        smallthinker.reference_forward)


def test_a_window_block_reads_only_the_keys_its_rows_see():
    """The reference's window layer at 2048 rows (four blocks of 512) and
    a window of 700 against the same layer with the whole sequence as its
    keys under the window's mask."""
    config = load_config("smallthinker-21b-a3b", tiny=True,
                         sliding_window_size=700)
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    d, heads, kv, head = 64, 14, 2, 16
    p = {"q": {"kernel": jax.random.normal(keys[0], (d, heads, head)) / 8},
         "k": {"kernel": jax.random.normal(keys[1], (d, kv, head)) / 8},
         "v": {"kernel": jax.random.normal(keys[2], (d, kv, head)) / 8},
         "o": {"kernel": jax.random.normal(keys[3], (heads, head, d)) / 8}}
    u = jax.random.normal(keys[4], (1, 2048, d))
    with jax.default_matmul_precision("highest"):
        got = smallthinker._attention(config, p, u, 1, 1)
        q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"])
        k = jnp.einsum("bsd,dhk->bshk", u, p["k"]["kernel"])
        v = jnp.einsum("bsd,dhk->bshk", u, p["v"]["kernel"])
        q, k = (smallthinker._rotate(x, 1.5e6) for x in (q, k))
        k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
        row = jnp.arange(2048)
        back = row[:, None] - row[None, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / head ** 0.5
        probs = jax.nn.softmax(jnp.where((back >= 0) & (back < 700), scores,
                                         -jnp.inf), -1)
        want = jnp.einsum("bshk,hkd->bsd",
                          jnp.einsum("bhqk,bkhd->bqhd", probs, v),
                          p["o"]["kernel"])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_rehearsal_runs_every_stage_and_prints_no_result():
    done = run_cell(CELL, 1, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode == 3, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert lines[-1].startswith("[bench] REHEARSAL OK")
    shown = json.loads(next(
        l for l in lines if "rehearsal line" in l).split(": ", 1)[1])
    assert shown["correct"] and shown["failed"] == 0
    assert shown["checks"]["compiled_in_window"] == 0
    # no device plane off the chip: the new readers leave their metrics out
    assert not set(READERS) & set(shown["metrics"])


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

READERS = ["window_attn_ms", "window_attn_roofline_share"]


def _run(trace, config="smallthinker-21b-a3b"):
    return types.SimpleNamespace(
        trace=trace, traced_steps=2, peak_flops=197e12,
        config=load_config(config),
        cell={"name": "no-such-cell", "batch_per_chip": 1, "seq_len": 16384,
              "chips": 1})


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_with_no_trace_returns_nothing(metric):
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(_run(None)) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_finds_nothing_in_a_program_without_the_scope(metric):
    """A trace whose file holds no scopes (the parent of this PR's
    program, or a program without window layers): nothing, no raise."""
    ops = [trace_reduce.Event("%fusion.1 = bf16[8,8]{1,0} fusion(%p)", 0, 50)]
    trace = trace_reduce.Trace([trace_reduce.Chip(0, ops, [])], [])
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(_run(trace)) is None


def test_the_share_is_the_window_flops_over_the_scope_time(monkeypatch):
    from benchmark import device_scopes
    from benchmark.layers import window_attn_ms, window_attn_roofline_share

    seen = {}

    def ms_per_step(run, **match):
        seen.update(match)
        return 40.0

    monkeypatch.setattr(device_scopes, "ms_per_step", ms_per_step)
    run = _run(object())
    assert window_attn_ms.read(run) == 40.0
    assert seen == {"scope": "hvd:attention.window"}
    flops = smallthinker.window_attn_flops(run.config, 1, 16384)
    assert window_attn_roofline_share.read(run) == pytest.approx(
        100 * flops / 197e12 / 40e-3)
    # another configuration's module counts no window: nothing
    assert window_attn_roofline_share.read(_run(object(), "sdar-30b-a3b")) \
        is None
