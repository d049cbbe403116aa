"""``Attention``'s q / k / v projection (``models/transformer.py``
``HeadsProjection``) against the ``nn.DenseGeneral`` it replaced: the same
leaf under the same key, the same function and gradients; only the
statement of the product differs from 32 heads or heads of 128 up (a flat
view of the leaf), which is what the TPU compiler lowers differently
(``tests/test_flash_compile_tpu.py``, PERF.md section 6, PR 36)."""

import json
import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import TransformerConfig, TransformerLM, transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D_MODEL = 64
# (query heads, key/value heads, head_dim) of the three transformer
# configurations the benchmark runs, over a tiny d_model: head_dim is not
# d_model / heads in any of them
LAYOUTS = {"gpt2": (16, 16, 64), "lfm2": (32, 8, 64), "sdar": (32, 4, 128)}
PROJECTIONS = [pytest.param(heads, head_dim, id=f"{name}-{which}")
               for name, (q, kv, head_dim) in LAYOUTS.items()
               for which, heads in dict(q=q, kv=kv).items()
               if which == "q" or kv != q]


def _dense_general(cfg, heads, head_dim, name=None):
    """What ``Attention`` called until PR 36."""
    return nn.DenseGeneral((heads, head_dim), axis=-1, name=name,
                           dtype=cfg.dtype, param_dtype=jnp.float32,
                           use_bias=False)


def _cfg(dtype, **fields):
    return TransformerConfig(**{
        "vocab_size": 64, "num_layers": 3, "num_heads": 4,
        "d_model": D_MODEL, "d_ff": 128, "max_seq_len": 16, "dtype": dtype,
        **fields})


def _close(got, want, dtype):
    # the tolerances of test_blocked_attention.py: bf16 operands round at
    # 2**-9 per product; float32 paths agree to accumulation order
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def _same_tree(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    paths = [jax.tree_util.keystr(p) for p, _
             in jax.tree_util.tree_leaves_with_path(got)]
    for path, g, w in zip(paths, jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype), path
        # bit for bit: a routing load follows the initial values
        assert np.array_equal(np.asarray(g), np.asarray(w)), path


@pytest.mark.parametrize("heads,head_dim", PROJECTIONS)
def test_parameter_tree_is_dense_generals_bit_for_bit(heads, head_dim):
    cfg = _cfg(jnp.bfloat16)
    x = jnp.zeros((2, 16, D_MODEL), jnp.float32)
    key = jax.random.PRNGKey(heads * 1000 + head_dim)
    got = transformer.HeadsProjection(cfg, heads, head_dim).init(key, x)
    want = _dense_general(cfg, heads, head_dim).init(key, x)
    _same_tree(got, want)
    kernel = got["params"]["kernel"]
    assert kernel.shape == (D_MODEL, heads, head_dim)
    assert kernel.dtype == jnp.float32


@pytest.mark.parametrize("heads,head_dim,want", [
    (16, 64, False),     # the GPT-2 cells
    (8, 64, False),      # lfm2's k / v
    (32, 64, True),      # lfm2's q: the window
    (4, 128, True),      # sdar's k / v: the layout
    (32, 128, True),     # sdar's q: both
    (31, 127, False), (31, 128, True), (32, 8, True), (64, 256, True),
])
def test_flat_projection_selected_table(heads, head_dim, want):
    assert transformer.flat_projection_selected(heads, head_dim) is want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("statement", ["selected", "flat", "dense_general"])
@pytest.mark.parametrize("heads,head_dim", PROJECTIONS)
def test_output_and_gradients_match_dense_general(heads, head_dim, statement,
                                                  dtype):
    cfg = _cfg(dtype)
    keys = jax.random.split(jax.random.PRNGKey(heads + head_dim), 3)
    # the residual stream's dtype: the projection casts its operand itself
    x = jax.random.normal(keys[0], (2, 24, D_MODEL), jnp.float32)
    weight = jax.random.normal(keys[1], (2, 24, heads, head_dim), jnp.float32)
    module = transformer.HeadsProjection(cfg, heads, head_dim)
    theirs = _dense_general(cfg, heads, head_dim).apply
    params = module.init(keys[2], x)
    ours = module.apply
    if statement != "selected":        # either statement, at every layout
        def ours(params, x):
            return transformer.project_heads(
                x, params["params"]["kernel"], dtype, statement == "flat")

    def loss(apply):
        return lambda params, x: jnp.sum(
            apply(params, x).astype(jnp.float32) * weight)

    got, want = ours(params, x), theirs(params, x)
    assert got.dtype == want.dtype == dtype
    assert got.shape == want.shape == (2, 24, heads, head_dim)
    _close(got, want, dtype)
    grads = jax.grad(loss(ours), argnums=(0, 1))(params, x)
    wants = jax.grad(loss(theirs), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(wants)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        assert g.dtype == jnp.float32      # parameters and the stream
        _close(g, w, dtype)


@pytest.mark.parametrize("fields", [
    dict(num_heads=4),                                    # gpt2's layout
    dict(num_heads=4, num_kv_heads=2),                    # lfm2's
    dict(num_heads=4, num_kv_heads=2, head_dim=32),       # sdar's
], ids=["gpt2", "lfm2", "sdar"])
def test_transformer_lm_keeps_the_tree_it_had(monkeypatch, fields):
    cfg = _cfg(jnp.float32, **fields)
    tokens = jnp.zeros((2, 16), jnp.int32)
    key = jax.random.PRNGKey(7)
    after = TransformerLM(cfg).init(key, tokens)
    out_after = TransformerLM(cfg).apply(after, tokens + 3)
    monkeypatch.setattr(transformer, "HeadsProjection", _dense_general)
    before = TransformerLM(cfg).init(key, tokens)
    _same_tree(after, before)
    attn = after["params"]["block_0"]["attn"]
    head_dim = fields.get("head_dim", D_MODEL // 4)
    kv = fields.get("num_kv_heads", 4)
    assert {n: attn[n]["kernel"].shape for n in "qkv"} == {
        "q": (D_MODEL, 4, head_dim), "k": (D_MODEL, kv, head_dim),
        "v": (D_MODEL, kv, head_dim)}
    _close(out_after, TransformerLM(cfg).apply(before, tokens + 3),
           jnp.float32)


@pytest.mark.parametrize("fields,want", [
    (dict(num_heads=4), {"projection_dense_general": 9}),
    (dict(num_heads=32, num_kv_heads=8, head_dim=8),
     {"projection_flat": 3, "projection_dense_general": 6}),
    (dict(num_heads=4, num_kv_heads=2, head_dim=128), {"projection_flat": 9}),
], ids=["gpt2", "lfm2", "sdar"])
def test_counter_counts_one_per_projection_per_trace(fields, want):
    def calls():
        return {dict(labels)["path"]: int(value) for labels, value
                in metrics.ATTENTION_CALLS.series().items()}

    cfg = _cfg(jnp.bfloat16, **fields)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    before = calls()
    step = jax.grad(lambda p, t: jnp.sum(model.apply(p, t)))
    jax.make_jaxpr(step)(params, tokens)
    moved = {p: n - before.get(p, 0) for p, n in calls().items()
             if n != before.get(p, 0)}
    # q, k and v of each of the 3 layers by the statement their heads
    # select, once a trace (not per step; the backward traces nothing)
    assert moved == {"materialised": 3, **want}


def test_probe_runs_tiny_and_its_three_statements_agree():
    done = subprocess.run(
        [sys.executable, "tools/projection_probe.py", "--tiny"],
        capture_output=True, text=True, timeout=300,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith("{")]
    # q and k / v of two cells, q alone where the counts are equal
    assert len(rows) == 5 * 3
    assert {r["statement"] for r in rows} == {
        "dense_general", "flat", "o_orientation"}
    assert sum(row["selected"] for row in rows) == 5   # one a shape
    for row in rows:
        assert "device_ms" not in row and "host_ms" not in row  # off a chip
        assert (row["max_abs_diff_vs_dense_general"]
                <= 2e-2 * row["gradient_max_abs"])
