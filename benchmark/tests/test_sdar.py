"""The ``sdar-30b-a3b`` configuration: its counts against a hand count, its
file against the catalog row it was cut from, its reference against the
program at the ``tiny`` sizes, its cell's rehearsal, and the readers of
the per-layer metrics it brought."""

import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace_reduce
from benchmark.models import sdar
from benchmark.tests.conftest import ROOT, load_config
from benchmark.tests.test_reference import rel_error
from benchmark.tests.test_rehearsal import run_cell

CELL = "sdar-traced-1chip"
# what the catalog's row for SDAR-30B-A3B-Chat gives (its `config`)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def test_file_is_the_published_config_but_for_what_it_lists():
    config = load_config("sdar-30b-a3b")
    reduced = set(config["reduced"])
    assert reduced == {"layer_types", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # the floors of the chip's-share cut: at least four of the one kind of
    # layer, 8 experts, an eighth of the vocabulary
    assert set(config["layer_types"]) == {"full_attention"}
    assert len(config["layer_types"]) >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    shares = config["deployment"]["chips_sharing_a_layer"]
    assert config["num_experts"] * shares == config["experts_routed"] == 128
    assert config["vocab_size"] * shares == PUBLISHED["vocab_size"]
    # what the catalog lists as not given is stated as assumed
    assert {"block_length", "mask_rate_min"} <= set(config["assumed"])
    assert config["block_length"] == 4 and config["mask_rate_min"] == 1e-3
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    assert (cell["chips"], cell["batch_per_chip"], cell["seq_len"],
            cell["job"]) == (1, 1, 4096, "traced")
    assert cell["seq_len"] % config["block_length"] == 0


def test_parameters_and_model_flops_against_hand_count():
    config = load_config("sdar-30b-a3b")
    d = 2048
    attention = 2 * d * 4096 + 2 * d * 512 + 2 * 128   # q, o, k, v, 2 norms
    assert attention == pytest.approx(18.87e6, rel=1e-3)
    experts = 16 * 3 * d * 768 + d * 128               # held, the router
    assert experts == pytest.approx(75.76e6, rel=1e-3)
    layer = attention + experts + 2 * d
    assert layer == pytest.approx(94.64e6, rel=1e-3)
    total = 5 * layer + 2 * 18992 * d + d
    assert total == pytest.approx(550.9e6, rel=2e-4)
    assert total * 16 / 2 ** 30 == pytest.approx(8.21, rel=2e-3)
    model = sdar.make_model(config)
    shapes, _ = jax.eval_shape(lambda k: sdar.init(model, config, k),
                               jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == total

    # what a row meets in a layer's matrix products: 1 expert
    n = 2 * d * 4096 + 2 * d * 512 + d * 128 + 3 * d * 768
    assert n == pytest.approx(23.85e6, rel=1e-3)
    assert sdar.layer_matmul_params(config) == n
    # the pairs the mask lets through, and their operations
    assert sdar.visible_pairs(config, 4096) == 4096 * 4096 + 4096 * 4
    attn = 5 * 12 * 32 * 128 * (4096 * 4096 + 4096 * 4)
    assert sdar.attn_flops(config, 1, 4096) == attn
    assert attn == pytest.approx(4.13e12, rel=2e-3)
    layers = 6 * 5 * n * 8192                # both copies' rows
    head = 6 * d * 18992 * 4096              # the noised copy's only
    assert layers == pytest.approx(5.86e12, rel=2e-3)
    assert head == pytest.approx(0.956e12, rel=2e-3)
    assert sdar.model_flops(config, 1, 4096) == layers + head + attn
    assert sdar.model_flops(config, 1, 4096) == pytest.approx(10.94e12,
                                                              rel=2e-3)
    # the grouped products at the expected rows: 8192 a layer
    assert sdar.rows_per_step(1, 4096) == 8192
    assert sdar.expected_rows(config, 8192) == 8192
    assert sdar.gmm_flops(config, 8192) == 5 * 9 * 2 * 8192 * d * 768
    assert sdar.gmm_flops(config, 8192) == pytest.approx(1.16e12, rel=2e-3)
    assert sdar.gmm_bytes(config, 8192) == 5 * 9 * 2 * (
        8192 * d + 8192 * 768 + 16 * d * 768)


def both(dtype, activations=None):
    """Gradients of the program (``compute_dtype`` ``dtype``) and of the
    reference at the tiny sizes, away from the initial point.
    ``activations``: a dtype every dense projection's result is rounded
    through on its way, standing in for a step computed that coarsely."""
    config = load_config("sdar-30b-a3b", tiny=True, compute_dtype=dtype)
    model = sdar.make_model(config)
    params, aux = jax.jit(lambda k: sdar.init(model, config, k))(
        jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1),
                            len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        p + 0.1 * jax.random.normal(k, p.shape)
        for p, k in zip(jax.tree.leaves(params), keys)])
    data = sdar.make_batch(config, jax.random.PRNGKey(2), 2, 64)

    def system_loss(p):
        if activations is None:
            return sdar.loss(model, p, aux, data)
        return sdar.loss_rounded_through(activations, model, p, aux, data)

    system = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
    reference = jax.jit(jax.value_and_grad(
        lambda p: sdar.reference_loss(config, p, aux, data),
        has_aux=True))(params)
    return system, reference


def test_reference_is_the_models_function_in_float32():
    ((loss, aux), grads), ((ref_loss, ref_aux), ref_grads) = both("float32")
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert rel_error(grads, ref_grads) < 1e-4
    for name in aux:                       # the load a step carries on
        assert (aux[name]["moe"]["expert_load"]
                == ref_aux[name]["moe"]["expert_load"]).all()


def test_bfloat16_is_inside_the_tolerance_and_float8_is_not():
    ((loss, _), grads), ((ref_loss, _), ref_grads) = both("bfloat16")
    error = rel_error(grads, ref_grads)
    assert 1e-4 < error < sdar.GRAD_REL_TOL
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) \
        < sdar.LOSS_REL_TOL
    # one precision below: the products' results rounded through float8
    (_, grads8), _ = both("bfloat16", activations=jnp.float8_e4m3fn)
    assert rel_error(grads8, ref_grads) > sdar.GRAD_REL_TOL


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(sdar))
    for name in ("reference_loss", "reference_forward", "_layer",
                 "_attention", "_experts", "_routing", "_sees", "_rows",
                 "_rotate", "_rms_norm", "_swiglu", "_head",
                 "_weighted_loss"):
        fn = next(n for n in tree.body
                  if isinstance(n, ast.FunctionDef) and n.name == name)
        assert not [n for n in ast.walk(fn)
                    if isinstance(n, (ast.Import, ast.ImportFrom))], name
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not [n for n in top if "horovod" in ast.dump(n)]
    assert "default_matmul_precision(\"highest\")" in inspect.getsource(
        sdar.reference_forward)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_every_stage_and_prints_no_result(trace):
    done = run_cell(CELL, trace, {"JAX_PLATFORMS": "cpu"})
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

READERS = ["masked_attn_ms", "masked_attn_roofline_share",
           "moe_gmm_narrow_ms", "moe_gmm_narrow_roofline_share"]


def _run(trace, config="sdar-30b-a3b"):
    return types.SimpleNamespace(
        trace=trace, traced_steps=2, peak_flops=197e12,
        config=load_config(config),
        cell={"batch_per_chip": 1, "seq_len": 4096, "chips": 1})


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_with_no_trace_returns_nothing(metric):
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(_run(None)) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_finds_nothing_in_a_program_without_its_kernels(metric):
    """A program of fusions: no Mosaic call, no grouped product."""
    ops = [trace_reduce.Event("%fusion.1 = bf16[8,8]{1,0} fusion(%p)", 0, 50),
           trace_reduce.Event("%custom-call.3 = f32[8]{0} custom-call(%p)",
                              90, 95)]
    trace = trace_reduce.Trace([trace_reduce.Chip(0, ops, [])], [])
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(_run(trace)) is None


def test_the_shares_need_a_model_module_that_counts_for_them(monkeypatch):
    """Another configuration's module has no ``attn_flops`` /
    ``rows_per_step``: the shares return nothing and do not raise."""
    ms = 1_000_000
    text = "%{} = bf16[8192,768]{{1,0}} custom-call(%a, %b)"
    ops = [trace_reduce.Event(text.format("ragged-dot-none.3"), 0, 2 * ms),
           trace_reduce.Event(text.format("_flash_attend.1"), 2 * ms, 4 * ms)]
    run = _run(trace_reduce.Trace([trace_reduce.Chip(0, ops, [])], []),
               "gpt2-medium")
    from benchmark.layers import (masked_attn_roofline_share,
                                  moe_gmm_narrow_roofline_share)

    assert masked_attn_roofline_share.read(run) is None
    assert moe_gmm_narrow_roofline_share.read(run) is None


def test_readers_sum_the_kernels_own_time(monkeypatch):
    ms = 1_000_000  # ns
    text = "%{} = bf16[16384,768]{{1,0:T(8,128)(2,1)}} custom-call(%a, %b)"
    ops = [
        trace_reduce.Event(text.format("ragged-dot-none.3"), 0 * ms, 2 * ms),
        trace_reduce.Event(text.format("ragged-dot-none"), 2 * ms, 6 * ms),
        trace_reduce.Event(text.format("_flash_attend.1"), 6 * ms, 10 * ms),
        trace_reduce.Event(text.format("_flash_attend.2"), 10 * ms, 12 * ms),
        trace_reduce.Event(text.format("_flash_block_grads.1"), 12 * ms,
                           20 * ms),
        trace_reduce.Event(text.format("custom-call.7"), 20 * ms, 21 * ms),
        trace_reduce.Event("%fusion.9 = f32[8]{0} fusion(%_flash_attend.1)",
                           21 * ms, 30 * ms),
    ]
    run = _run(trace_reduce.Trace([trace_reduce.Chip(0, ops, [])], []))
    from benchmark.layers import (masked_attn_ms, masked_attn_roofline_share,
                                  moe_gmm_narrow_ms,
                                  moe_gmm_narrow_roofline_share)

    assert moe_gmm_narrow_ms.read(run) == pytest.approx(6 / 2)   # 2 steps
    assert masked_attn_ms.read(run) == pytest.approx(14 / 2)
    # visible pairs only: 4.13 TFLOP at 197 TFLOP/s = 20.96 ms
    assert masked_attn_roofline_share.read(run) == pytest.approx(
        100 * sdar.attn_flops(run.config, 1, 4096) / 197e12 / 7e-3)
    # compute-bound at these shapes, counted at 8192 rows, not 4096
    flops = sdar.gmm_flops(run.config, 8192)
    assert flops / 197e12 > sdar.gmm_bytes(run.config, 8192) / 819e9
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    assert moe_gmm_narrow_roofline_share.read(run) == pytest.approx(
        100 * flops / 197e12 / 3e-3)
