"""The program's device scopes (ISSUE 37; docs/timeline.md "Device
scopes"): the code a compiled step is traced from names its layers through
``timeline.scope`` — a fixed-name ``jax.named_scope("hvd:<layer>.<stage>")``
that exists at trace time only. Read here the way the benchmark reads it
(``benchmark/device_scopes.py``): from the ``op_name`` of the compiled
program's instructions, forward (``jvp(``) and backward (``transpose(``),
which is what catches a hand-written backward rule without its scope."""

import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.models import gpt2, lfm2, sdar, smallthinker
from horovod_tpu import timeline
from horovod_tpu.models import ResNet18, SyncBatchNorm, transformer
from horovod_tpu.parallel import sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATTENTION = {"attention.project", "attention.prepare", "attention.kernel"}
EXPERTS = {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"}
LM = {"model.stream", "model.embed", "model.head"}


def load_config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    return {**config, **config["tiny"]}


def op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def scopes_by_phase(names):
    """``(forward, backward, anywhere)``: the scopes (innermost of a path,
    without the prefix) among ``names`` under ``jvp(`` alone, under
    ``transpose(``, and at all."""
    forward, backward, anywhere = set(), set(), set()
    for name in names:
        found = re.findall(r"hvd:([a-z_.]+)", name)
        if not found:
            continue
        anywhere.add(found[-1])
        if "transpose(" in name:
            backward.add(found[-1])
        elif "jvp(" in name:
            forward.add(found[-1])
    return forward, backward, anywhere


@pytest.fixture()
def blocked_on_the_cpu(monkeypatch):
    """``Attention`` takes its blocked branch, and the branch the kernels'
    ``jax.numpy`` twins (what a TPU selects itself, as far as a CPU can
    run it)."""
    monkeypatch.setattr(transformer, "blocked_selected",
                        lambda *seen: not seen[-1])
    for name in ("_block_diffusion_flash", "_local_flash"):
        real = getattr(sequence, name)
        monkeypatch.setattr(
            sequence, name,
            lambda *args, _real=real, **kw: _real(
                *args[:-2], False, False, **kw))


def lm_grad(module, name, seq):
    config = load_config(name)
    model = module.make_model(config)
    params, aux = jax.jit(lambda k: module.init(model, config, k))(
        jax.random.PRNGKey(0))
    data = module.make_batch(config, jax.random.PRNGKey(2), 2, seq)
    return params, jax.jit(jax.value_and_grad(
        lambda p: module.loss(model, p, aux, data)[0]))


# ------------------------------------------------------------- the catalog

def test_a_scope_is_declared_once_and_documented():
    """docs/timeline.md's table names every declared scope, and no other;
    a scope is ``jax.named_scope`` under the spans' prefix."""
    with open(os.path.join(REPO, "docs", "timeline.md")) as f:
        section = f.read().split("## Device scopes")[1]
    documented = set(re.findall(r"^\| `([a-z_.]+)` \|", section,
                                re.MULTILINE))
    assert documented == set(timeline.scopes()) and len(documented) == 19
    with pytest.raises(ValueError):
        timeline.scope("model.stream")
    declared = timeline.scopes()["model.stream"]
    assert declared.annotation == "hvd:model.stream"

    def add(x):
        with declared():
            return x + 1

    assert "hvd:model.stream/add" in jax.jit(add).lower(1.0).as_text(
        debug_info=True)


def test_no_named_scope_outside_the_seam():
    """Every ``hvd:`` scope of the program goes through ``timeline.scope``."""
    offenders = []
    for folder, _, files in os.walk(os.path.join(REPO, "horovod_tpu")):
        for name in files:
            if name.endswith(".py") and name != "timeline.py":
                with open(os.path.join(folder, name)) as f:
                    if re.search(r"named_scope\(\s*[\"']hvd", f.read()):
                        offenders.append(name)
    assert not offenders


# ------------------------------------------------ what each path promises

@pytest.mark.parametrize("module,name,seq,promised", [
    (sdar, "sdar-30b-a3b", 64,
     ATTENTION | EXPERTS | LM | {"attention.own_block"}),
    (lfm2, "lfm2-24b-a2b", 512,
     ATTENTION | EXPERTS | LM | {"model.conv", "model.mlp"}),
    (gpt2, "gpt2-medium", 64, ATTENTION | LM | {"model.mlp"}),
    (smallthinker, "smallthinker-21b-a3b", 64,
     ATTENTION | EXPERTS | LM | {"attention.window"}),
], ids=["sdar", "lfm2", "gpt2", "smallthinker"])
def test_transformer_scopes_forward_and_backward(blocked_on_the_cpu, module,
                                                 name, seq, promised):
    """Held experts under the block-diffusion mask (and the three other
    transformer configurations, window layers and recomputed blocks among
    them): the compiled gradient holds every scope the table promises for
    the path, each under ``jvp(`` and under ``transpose(``: the
    hand-written backward rules (``_dispatch_bwd``, ``_combine_bwd``, the
    chunks', the two attention cores') among them."""
    params, grad = lm_grad(module, name, seq)
    forward, backward, _ = scopes_by_phase(
        op_names(grad.lower(params).compile()))
    assert forward == promised
    assert backward == promised


def test_materialised_attention_is_the_kernel_scope():
    params, grad = lm_grad(gpt2, "gpt2-medium", 64)
    names = op_names(grad.lower(params).compile())
    softmax = [n for n in names if "exp" in n.rsplit("/", 1)[-1]
               and "attn" in n]
    assert softmax and all("hvd:attention.kernel" in n for n in softmax)


def test_resnet_scopes_forward_and_backward():
    model = ResNet18(num_classes=10, num_filters=8)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = jax.jit(lambda k: model.init(k, x))(jax.random.PRNGKey(0))

    def loss(params):
        logits, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]}, x,
            mutable=["batch_stats"])
        return jnp.sum(jnp.square(logits))

    forward, backward, _ = scopes_by_phase(op_names(
        jax.jit(jax.grad(loss)).lower(variables["params"]).compile()))
    promised = {"model.conv", "model.batch_norm", "model.pool",
                "model.head"}
    assert forward == promised and backward == promised


def test_sync_batch_norm_is_the_batch_norm_scope(hvd):
    norm = SyncBatchNorm(use_running_average=False)
    x = jnp.ones((4, 8), jnp.float32)
    variables = norm.init(jax.random.PRNGKey(0), x)
    names = op_names(jax.jit(lambda v: norm.apply(
        v, x, mutable=["batch_stats"])[0]).lower(variables).compile())
    assert scopes_by_phase(names)[2] == {"model.batch_norm"}


def traced_step(hvd, tx, params):
    def step(params, state, x):
        grads = jax.tree.map(lambda p: p * x[0], params)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    state = tx.init(params)
    x = jnp.ones((hvd.size(),), jnp.float32)
    return jax.jit(jax.shard_map(
        step, mesh=hvd.mesh(), in_specs=(P(), P(), P(hvd.axis_name())),
        out_specs=(P(), P()), check_vma=False)).lower(params, state, x)


def test_traced_optimizer_step_scopes(hvd, monkeypatch):
    """A traced ``DistributedOptimizer`` step: the ``psum`` leaves, the
    permute rounds (predicate steered as ``tests/test_optimizer.py``
    does) and the wrapped update each under their scope, none under
    ``jvp(``: their phase is their scope's."""
    from horovod_tpu.ops import traced_exchange

    params = {"big": jnp.ones((64, 48)), "small": jnp.ones((7,))}
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    plain = scopes_by_phase(op_names(traced_step(hvd, tx, params).compile()))
    assert plain[2] == {"exchange.psum", "optimizer.update"}
    assert not plain[0] and not plain[1]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(traced_exchange, "neighbour_ring",
                        lambda devices: (0, 1, 2, 3, 7, 6, 5, 4))
    monkeypatch.setattr(traced_exchange, "MIN_LEAF_BYTES", 1024)
    text = traced_step(hvd, tx, params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert scopes_by_phase(names)[2] == {
        "exchange.psum", "exchange.rounds", "optimizer.update"}
    permutes = re.findall(r"collective-permute[^\n]*op_name=\"([^\"]*)\"",
                          text)
    assert permutes and all("hvd:exchange.rounds" in n for n in permutes)


def test_eager_compiled_update_is_under_the_optimizer_scope(hvd):
    """The eager path's compiled inner update (PR 26) carries the scope:
    its program is traced from the same ``direct``."""
    from horovod_tpu import optim

    tx = optim._sync_then_update(
        optax.GradientTransformation(
            lambda p: optax.EmptyState(), lambda u, s, p=None: (u, s)),
        optax.sgd(0.1, momentum=0.9))
    params = {"w": jnp.ones((8,))}
    jaxpr = jax.make_jaxpr(lambda g, s: tx.update(g, s, params))(
        params, tx.init(params))
    stacks = {str(eqn.source_info.name_stack) for eqn in jaxpr.eqns}
    assert stacks == {"hvd:optimizer.update"}


# ------------------------------------------------------- nothing but names

def test_scopes_change_no_bit(blocked_on_the_cpu, monkeypatch):
    """Loss and gradients with the scopes and with every scope a null
    context: the same bits."""
    def run():
        params, grad = lm_grad(sdar, "sdar-30b-a3b", 64)
        return grad(params)

    with_scopes = run()
    monkeypatch.setattr(timeline.Scope, "__call__",
                        lambda self: contextlib.nullcontext())
    params, grad = lm_grad(sdar, "sdar-30b-a3b", 64)
    assert not scopes_by_phase(op_names(grad.lower(params).compile()))[2]
    without = grad(params)
    for got, want in zip(jax.tree.leaves(with_scopes),
                         jax.tree.leaves(without)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
