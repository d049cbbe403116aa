"""The ``lfm2-24b-a2b`` configuration: its counts against a hand count, its
file against the catalog row it was cut from, its reference against the
program at the ``tiny`` sizes, its cell's rehearsal, and the readers of
the per-layer metrics it brought."""

import importlib
import json
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace_reduce
from benchmark.models import lfm2
from benchmark.tests.conftest import load_config
from benchmark.tests.test_reference import rel_error
from benchmark.tests.test_rehearsal import run_cell

CELL = "lfm2-traced-1chip"
# what the catalog's row for LFM2-24B-A2B gives (its `config`)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


def test_file_is_the_published_config_but_for_what_it_lists():
    config = load_config("lfm2-24b-a2b")
    reduced = set(config["reduced"])
    assert reduced == {"layer_types", "num_dense_layers", "num_experts",
                       "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # the floors of the chip's-share cut: a whole period (1 attention to
    # 3 conv) after the leading dense layer, 8 experts, an eighth of the
    # vocabulary
    assert config["layer_types"][config["num_dense_layers"]:].count(
        "full_attention") * 3 == config["layer_types"][
            config["num_dense_layers"]:].count("conv")
    assert len(config["layer_types"]) - config["num_dense_layers"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    shares = config["deployment"]["chips_sharing_a_layer"]
    assert config["num_experts"] * shares == config["experts_routed"] == 64
    assert config["vocab_size"] * shares == PUBLISHED["vocab_size"]


def test_parameters_and_model_flops_against_hand_count():
    config = load_config("lfm2-24b-a2b")
    d = 2048
    conv = d * 3 * d + d * d + 3 * d          # in_proj, out_proj, the taps
    attention = 2 * d * d + 2 * d * 512 + 2 * 64
    norms = 2 * d
    dense_mlp = 3 * d * 11776
    experts = 8 * 3 * d * 1536 + d * 64       # held experts, the router
    assert conv + norms + dense_mlp == pytest.approx(89.14e6, rel=1e-3)
    assert attention + norms + experts == pytest.approx(86.12e6, rel=1e-3)
    assert conv + norms + experts == pytest.approx(92.42e6, rel=1e-3)
    total = ((conv + norms + dense_mlp) + (attention + norms + experts)
             + 3 * (conv + norms + experts) + 8192 * d + d)
    assert total == pytest.approx(469.3e6, rel=1e-3)
    model = lfm2.make_model(config)
    shapes, _ = jax.eval_shape(lambda k: lfm2.init(model, config, k),
                               jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == total

    # what a token meets in a matrix product: 0.5 expert an expert layer
    n = (4 * 4 * d * d + (2 * d * d + 2 * d * 512) + dense_mlp
         + 4 * (d * 64 + 0.5 * 3 * d * 1536) + d * 8192)
    assert n == pytest.approx(186.1e6, rel=1e-3)
    assert lfm2.matmul_params(config) == n
    per_token = 6 * n + 12 * 1 * 32 * 64 * 8192
    assert per_token == pytest.approx(1.318e9, rel=1e-3)
    assert lfm2.model_flops(config, 1, 8192) == per_token * 8192
    assert lfm2.model_flops(config, 1, 8192) == pytest.approx(10.8e12,
                                                              rel=5e-3)
    # the grouped products at the expected rows: 4096 an expert layer
    assert lfm2.expected_rows(config, 8192) == 4096
    assert lfm2.gmm_flops(config, 8192) == 4 * 9 * 2 * 4096 * d * 1536
    assert lfm2.gmm_bytes(config, 8192) == 4 * 9 * 2 * (
        4096 * d + 4096 * 1536 + 8 * d * 1536)


def both(dtype, activations=None):
    """Gradients of the program (``compute_dtype`` ``dtype``) and of the
    reference at the tiny sizes, away from the initial point.
    ``activations``: a dtype every matrix product's result is rounded
    through on its way, standing in for a step computed that coarsely."""
    config = load_config("lfm2-24b-a2b", tiny=True, compute_dtype=dtype)
    model = lfm2.make_model(config)
    params, aux = jax.jit(lambda k: lfm2.init(model, config, k))(
        jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1),
                            len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        p + 0.1 * jax.random.normal(k, p.shape)
        for p, k in zip(jax.tree.leaves(params), keys)])
    data = lfm2.make_batch(config, jax.random.PRNGKey(2), 2, 64)

    def system_loss(p):
        if activations is None:
            return lfm2.loss(model, p, aux, data)
        return lfm2.loss_rounded_through(activations, model, p, aux, data)

    system = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
    reference = jax.jit(jax.value_and_grad(
        lambda p: lfm2.reference_loss(config, p, aux, data),
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
    assert 1e-4 < error < lfm2.GRAD_REL_TOL
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) \
        < lfm2.LOSS_REL_TOL
    # one precision below: the products' results rounded through float8
    (_, grads8), _ = both("bfloat16", activations=jnp.float8_e4m3fn)
    assert rel_error(grads8, ref_grads) > lfm2.GRAD_REL_TOL


def test_routing_agreement_is_whole_in_float32():
    config = load_config("lfm2-24b-a2b", tiny=True)
    model = lfm2.make_model(config)
    params, aux = jax.jit(lambda k: lfm2.init(model, config, k))(
        jax.random.PRNGKey(0))
    data = lfm2.make_batch(config, jax.random.PRNGKey(2), 2, 64)
    assert float(lfm2.routing_agreement(model, config, params, aux,
                                        data)) == 1.0


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
    new = {"moe_gmm_ms", "moe_gmm_roofline_share", "attn_kernel_ms"}
    # no device plane off the chip: the new readers leave their metrics out
    assert not new & set(shown["metrics"])


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

READERS = ["moe_gmm_ms", "moe_gmm_roofline_share", "attn_kernel_ms"]


def _run(trace):
    return types.SimpleNamespace(
        trace=trace, traced_steps=2, peak_flops=197e12,
        config=load_config("lfm2-24b-a2b"),
        cell={"batch_per_chip": 1, "seq_len": 8192, "chips": 1})


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_with_no_trace_returns_nothing(metric):
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(_run(None)) is None


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_finds_nothing_in_a_program_without_its_kernels(metric):
    """The parent's program: fusions and an all-reduce, no such call."""
    ops = [trace_reduce.Event("%fusion.1 = bf16[8,8]{1,0} fusion(%p)", 0, 50),
           trace_reduce.Event("%ragged-dot_like = f32[8]{0} fusion(%p)", 50,
                              90),
           trace_reduce.Event("%custom-call.3 = f32[8]{0} custom-call(%p)",
                              90, 95)]
    trace = trace_reduce.Trace([trace_reduce.Chip(0, ops, [])], [])
    reader = importlib.import_module(f"benchmark.layers.{metric}")
    assert reader.read(_run(trace)) is None


def test_readers_sum_the_kernels_own_time(monkeypatch):
    ms = 1_000_000  # ns
    text = "%{} = bf16[32768,1536]{{1,0:T(8,128)(2,1)}} custom-call(%a, %b)"
    ops = [
        trace_reduce.Event(text.format("ragged-dot-none.3"), 0 * ms, 2 * ms),
        trace_reduce.Event(text.format("tgmm"), 2 * ms, 5 * ms),
        trace_reduce.Event(text.format("ragged-dot-none"), 5 * ms, 6 * ms),
        trace_reduce.Event(text.format("_flash_attend.1"), 6 * ms, 10 * ms),
        trace_reduce.Event(text.format("attn.12"), 10 * ms, 12 * ms),
        trace_reduce.Event(text.format("_flash_block_grads.1"), 12 * ms,
                           20 * ms),
        trace_reduce.Event(text.format("custom-call.7"), 20 * ms, 21 * ms),
        trace_reduce.Event("%fusion.9 = f32[8]{0} fusion(%ragged-dot-none.3)",
                           21 * ms, 30 * ms),
    ]
    run = _run(trace_reduce.Trace([trace_reduce.Chip(0, ops, [])], []))
    from benchmark.layers import (attn_kernel_ms, moe_gmm_ms,
                                  moe_gmm_roofline_share)

    assert moe_gmm_ms.read(run) == pytest.approx(6 / 2)        # 2 steps
    assert attn_kernel_ms.read(run) == pytest.approx(14 / 2)
    # compute-bound at these shapes: 0.928 TFLOP at 197 TFLOP/s = 4.71 ms
    flops = lfm2.gmm_flops(run.config, 8192)
    assert flops / 197e12 > lfm2.gmm_bytes(run.config, 8192) / 819e9
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    assert moe_gmm_roofline_share.read(run) == pytest.approx(
        100 * flops / 197e12 / 3e-3)
