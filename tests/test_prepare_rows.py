"""The query's and the key's prepare as one pass each way
(``ops/prepare_rows.py``), interpreted on the CPU: against the statements
it stands for (``operators.RMSNorm`` -> ``operators.rotary`` -> ``/
sqrt(d)`` -> ``parallel/sequence.py`` ``_rows``) forward and backward, in
``Attention`` against the same module with the pass off, the counter, the
device scope it runs in, and the steps that have no chain to fuse lowering
to the text they had before the pass."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import device_scopes
from horovod_tpu import metrics
from horovod_tpu.models import TransformerConfig, TransformerLM, operators
from horovod_tpu.models import transformer
from horovod_tpu.ops import flash
from horovod_tpu.ops import prepare_rows as pr
from horovod_tpu.parallel.sequence import _rows

EPS, THETA = 1e-6, 1e6


class _Cfg:
    norm_eps = EPS
    dtype = jnp.bfloat16


def _statements(x, scale, positions, prescale, norm=True, rotation=True):
    """What ``Attention`` writes without the pass, in its order."""
    if norm:
        x = operators.RMSNorm(_Cfg()).apply({"params": {"scale": scale}}, x)
    if rotation:
        x, _ = operators.rotary(x, x, THETA, positions)
    if prescale:
        x = x / jnp.sqrt(x.shape[-1]).astype(x.dtype)
    return _rows(x)


def _pass(x, scale, positions, prescale, norm=True, rotation=True):
    b, s, _, d = x.shape
    tables = (operators.rotary_tables(s, d, THETA, positions)
              if rotation else None)
    return pr.prepare_rows(x, scale if norm else None, tables, eps=EPS,
                           prescale=prescale, interpret=True)


def _bf16(got, want, rel=2 ** -7):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= rel * np.max(np.abs(want))


# (batch, rows, query heads, key/value heads, head, positions per row):
# sdar's 32 over 4 heads of 128 at each row's own position; lfm2's 32 over
# 8 of 64 at positions 0..; three row tiles of 16 (an odd count), two
# sequences
SHAPES = {
    "sdar": (1, 64, 32, 4, 128, True),
    "lfm2": (1, 64, 32, 8, 64, False),
    "odd_tiles": (2, 48, 4, 2, 64, True),
}


@pytest.mark.parametrize("norm,rotation", [(True, True), (True, False),
                                           (False, True)],
                         ids=["norm_rotary", "norm", "rotary"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_pass_matches_the_statements(shape, norm, rotation):
    """q and k through the pass and through the statements: the rows,
    dq, dk and both scales' gradients to bfloat16's rounding."""
    b, s, heads, kv_heads, d, per_row = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(heads * d + s), 7)
    q, k = ((2 * jax.random.normal(key, (b, s, n, d))).astype(jnp.bfloat16)
            for key, n in zip(keys[:2], (heads, kv_heads)))
    scales = tuple(1 + 0.1 * jax.random.normal(key, (d,)) for key in keys[2:4])
    positions = (jax.random.randint(keys[4], (b, s), 0, 4096) if per_row
                 else None)
    cotangents = tuple(jax.random.normal(key, (b * n, s, d)).astype(
        jnp.bfloat16) for key, n in zip(keys[5:], (heads, kv_heads)))

    @functools.partial(jax.jit, static_argnums=0)
    def both(fn):
        def run(q, k, q_scale, k_scale):
            return (fn(q, q_scale, positions, True, norm, rotation),
                    fn(k, k_scale, positions, False, norm, rotation))
        out, vjp = jax.vjp(run, q, k, *scales)
        return out, vjp(cotangents)

    (got, grads), (want, wants) = both(_pass), both(_statements)
    for a, r in zip(got, want):
        assert a.dtype == jnp.bfloat16
        _bf16(a, r)
    for a, r in zip(grads[:2], wants[:2]):   # dq, dk
        assert a.dtype == jnp.bfloat16
        _bf16(a, r)
    for a, r in zip(grads[2:], wants[2:]):   # the scales' gradients
        if norm:
            _bf16(a, r, rel=1e-3)
        else:
            assert not np.any(np.asarray(r))


@pytest.mark.parametrize("heads,head_dim,rows,want", [
    (32, 128, 8192, True),    # sdar's query
    (4, 128, 8192, True),     # its key
    (8, 64, 8192, True),      # lfm2's key: two heads a lane row
    (32, 256, 1024, True),    # half a head a lane row: W 256
    (1, 64, 1024, False),     # half a lane row in all
    (3, 64, 1024, False),     # a head across two lane rows
    (2, 96, 1024, False),     # 96 is no divisor of 128
    (4, 128, 200, False),     # no row tile divides 200
    (4, 128, 48, True),
])
def test_supported_table(heads, head_dim, rows, want):
    assert pr.supported(heads, head_dim, rows) is want


def _interpreted(monkeypatch):
    """The blocked branch on the CPU: the selection forced, every Mosaic
    call (the attention kernels and the pass) interpreted."""
    monkeypatch.setattr(transformer, "blocked_selected",
                        lambda *seen: not seen[-1])
    for name in ("_flash_attend", "_flash_block_grads"):
        kernel = getattr(flash, name)
        monkeypatch.setattr(flash, name, lambda *a, _k=kernel, **kw: _k(
            *a, **{**kw, "interpret": True}))
    monkeypatch.setattr(pr, "prepare_rows",
                        functools.partial(pr.prepare_rows, interpret=True))


def _attention_calls():
    return {dict(labels)["path"]: int(value) for labels, value
            in metrics.ATTENTION_CALLS.series().items()}


# sdar's layer (32 heads of 128 over 4, the block-diffusion mask, the
# kernels take the groups) and lfm2's (heads of 64, causal, k and v
# repeated), each at 4 query heads
@pytest.mark.parametrize("kv_heads,head_dim,mask", [
    (1, 128, "block_diffusion"), (2, 64, "causal")])
def test_attention_with_the_pass_matches_the_statements(
        monkeypatch, kv_heads, head_dim, mask):
    _interpreted(monkeypatch)
    cfg = TransformerConfig(num_heads=4, num_kv_heads=kv_heads,
                            head_dim=head_dim, d_model=64, norm="rmsnorm",
                            positions="rotary", qk_norm=True,
                            dtype=jnp.bfloat16, attn_mask=mask,
                            block_length=4 if mask != "causal" else 1)
    attn = transformer.Attention(cfg)
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 64, 64))
    positions = (jnp.tile(jnp.arange(32), 2)[None] if mask != "causal"
                 else None)
    params = attn.init(jax.random.PRNGKey(9), u, positions)["params"]
    params = jax.tree.map(  # scales away from 1, so that they count
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                              p.shape), params)

    def run():
        return jax.jit(jax.value_and_grad(lambda p: jnp.sum(jnp.sin(
            attn.apply({"params": p}, u, positions).astype(jnp.float32)))))(
                params)

    before = _attention_calls().get("prepare_rows", 0)
    got, grads = run()
    assert _attention_calls()["prepare_rows"] == before + 1
    monkeypatch.setattr(transformer, "prepare_rows_selected",
                        lambda *seen: False)
    want, wants = run()
    np.testing.assert_allclose(got, want, rtol=1e-3)
    for path, a in jax.tree_util.tree_leaves_with_path(grads):
        r = wants
        for key in path:
            r = r[key.key]
        _bf16(a, r, rel=2e-2)


def test_counter_counts_one_pass_a_layer_a_trace(monkeypatch):
    """Five layers with a chain to fuse: five; GPT-2's layer: none."""
    monkeypatch.setattr(transformer, "blocked_selected",
                        lambda *seen: not seen[-1])
    tokens = jnp.zeros((1, 128), jnp.int32)
    for cfg, want in (
            (TransformerConfig(vocab_size=64, num_layers=5, num_heads=4,
                               num_kv_heads=1, head_dim=128, d_model=64,
                               d_ff=64, norm="rmsnorm", positions="rotary",
                               qk_norm=True, dtype=jnp.bfloat16), 5),
            (TransformerConfig(vocab_size=64, num_layers=5, num_heads=2,
                               d_model=128, d_ff=64, dtype=jnp.bfloat16), 0)):
        model = TransformerLM(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        before = _attention_calls().get("prepare_rows", 0)
        jax.make_jaxpr(jax.grad(lambda p: jnp.sum(model.apply(
            p, tokens).astype(jnp.float32))))(params)
        assert _attention_calls().get("prepare_rows", 0) - before == want


def test_pass_runs_in_the_prepare_scope_both_ways(monkeypatch):
    """A CPU compile of the gradient: every instruction of the two calls
    is ``hvd:attention.prepare``'s, the forward's under ``jvp(``, the
    backward's under ``transpose(``, as ``benchmark/device_scopes.py``
    reads them on the chip."""
    _interpreted(monkeypatch)
    cfg = TransformerConfig(num_heads=2, num_kv_heads=1, head_dim=128,
                            d_model=64, norm="rmsnorm", positions="rotary",
                            qk_norm=True, dtype=jnp.bfloat16)
    attn = transformer.Attention(cfg)
    u = jax.random.normal(jax.random.PRNGKey(8), (1, 64, 64))
    params = attn.init(jax.random.PRNGKey(9), u)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(attn.apply(
        {"params": p}, u).astype(jnp.float32)))).lower(
            params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    phases = {}
    for call in ("_forward_call", "_backward_call"):
        ours = [n for n in names if f"jit({call})" in n]
        assert ours
        attrs = {(a.scope, a.phase) for a in map(device_scopes.attr_of,
                                                  ours)}
        assert len(attrs) == 1
        phases[call] = attrs.pop()
    assert phases == {"_forward_call": ("hvd:attention.prepare", "forward"),
                      "_backward_call": ("hvd:attention.prepare",
                                         "backward")}
    assert device_scopes.count_by_scope(device_scopes.parse_module(text))[
        "hvd:attention.prepare"] > 0


# sha256 of the lowered (StableHLO, for the TPU, blocked branch forced)
# gradient of two 2-layer steps, the Mosaic calls' payloads replaced by
# their modules without locations, as the tree before the pass lowered
# them (83060503; jax 0.9.0): GPT-2's block, and one with RMS norms, the
# gated MLP and grouped heads of 128 but neither ``qk_norm`` nor rotary
LOWERED_BEFORE_THE_PASS = {
    "gpt2": "f802c9940685a13c",
    "no_chain": "45dfd2d8372134a5",
}


@pytest.mark.parametrize("name", list(LOWERED_BEFORE_THE_PASS))
def test_steps_without_a_chain_lower_as_before(monkeypatch, name):
    from jax.experimental import mosaic

    monkeypatch.setattr(transformer, "blocked_selected",
                        lambda *seen: not seen[-1])
    modules = []
    lower = mosaic.lower_module_to_custom_call

    def spy(ctx, *args, module, **kwargs):
        modules.append(module.operation.get_asm(enable_debug_info=False))
        return lower(ctx, *args, module=module, **kwargs)

    monkeypatch.setattr(mosaic, "lower_module_to_custom_call", spy)
    common = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=128,
                  d_ff=256, max_seq_len=512, dtype=jnp.bfloat16)
    cfg = TransformerConfig(**common) if name == "gpt2" else \
        TransformerConfig(**common, num_kv_heads=1, head_dim=128,
                          norm="rmsnorm", mlp="swiglu")
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 512), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    before = _attention_calls().get("prepare_rows", 0)
    step = jax.grad(lambda p, t: jnp.sum(model.apply(p, t).astype(
        jnp.float32)))
    text = jax.jit(step).trace(params, tokens).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _attention_calls().get("prepare_rows", 0) == before
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', '', text)
    digest = hashlib.sha256("\n".join([text] + modules).encode())
    assert digest.hexdigest()[:16] == LOWERED_BEFORE_THE_PASS[name]
