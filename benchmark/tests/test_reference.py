"""Each plain reference against the repo's model, at a tiny size on the
CPU. In float32 the two must agree to rounding: that is what shows the
reference computes the same function. In bfloat16 the system's error has
to be visible to the comparison and still of the size the tolerance is
built around."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.models import gpt2, resnet
from benchmark.tests.conftest import load_config

CASES = [(resnet, "resnet50", 8, None), (gpt2, "gpt2-medium", 2, 64)]


def rel_error(got, want):
    diff = sum(jnp.sum(jnp.square(g - w)) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    norm = sum(jnp.sum(jnp.square(w)) for w in jax.tree.leaves(want))
    return float(jnp.sqrt(diff / norm))


def both(mm, name, batch, seq, dtype):
    config = load_config(name, tiny=True, compute_dtype=dtype)
    model = mm.make_model(config)
    params, aux = jax.jit(lambda k: mm.init(model, config, k))(
        jax.random.PRNGKey(0))
    # away from the initial point: ResNet's last batch-norm scales start
    # at 0, which would hide the convolutions behind them
    keys = jax.random.split(jax.random.PRNGKey(1),
                            len(jax.tree.leaves(params)))
    params = jax.tree.unflatten(jax.tree.structure(params), [
        p + 0.1 * jax.random.normal(k, p.shape)
        for p, k in zip(jax.tree.leaves(params), keys)])
    data = mm.make_batch(config, jax.random.PRNGKey(2), batch, seq)
    system = jax.value_and_grad(
        lambda p: mm.loss(model, p, aux, data), has_aux=True)(params)
    reference = jax.value_and_grad(
        lambda p: mm.reference_loss(config, p, aux, data),
        has_aux=True)(params)
    return system, reference


@pytest.mark.parametrize("mm,name,batch,seq", CASES,
                         ids=[c[1] for c in CASES])
def test_reference_is_the_models_function_in_float32(mm, name, batch, seq):
    ((loss, aux), grads), ((ref_loss, ref_aux), ref_grads) = both(
        mm, name, batch, seq, "float32")
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert rel_error(grads, ref_grads) < 1e-4
    if jax.tree.leaves(aux):      # the batch statistics a step carries on
        assert rel_error(aux, ref_aux) < 1e-5


@pytest.mark.parametrize("mm,name,batch,seq", CASES,
                         ids=[c[1] for c in CASES])
def test_bfloat16_error_is_seen(mm, name, batch, seq):
    (_, grads), (_, ref_grads) = both(mm, name, batch, seq, "bfloat16")
    error = rel_error(grads, ref_grads)
    # far above float32's rounding, and nowhere near a wrong gradient
    assert 1e-4 < error < 0.5
