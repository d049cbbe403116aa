"""GPT-2 through the repo's ``TransformerLM``, for the benchmark.

* ``make_model`` / ``init`` / ``loss`` - the repo's flax model at the
  configuration's sizes and the next-token loss a user trains on;
* ``make_batch`` - one seeded batch of token ids, made on the device;
* ``model_flops`` - the operations one training step requires;
* ``reference_loss`` - the same forward pass and loss in plain float32
  ``jax.numpy``, for ``correct``.

Departures of ``TransformerLM`` from the published GPT-2 (Radford et al.
2019; openai/gpt-2 ``src/model.py``), each followed by the reference so
that both compute the same function, none closed by code added to the
program: no biases on q/k/v/o and on the MLP's two matrices; the output
head is a matrix of its own, not the transposed token embedding (so
about 406 M parameters at the medium sizes, not 355 M); no dropout; layer
norm epsilon 1e-6 (flax's default) where GPT-2 has 1e-5. As published:
learned absolute positions, pre-layer-norm blocks, a final layer norm,
the tanh approximation of GELU, the 1/sqrt(head) scale, causal softmax
attention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

# Tolerances of `correct`, and why (run.py:check_reference). The system
# computes in bfloat16 (rounding 2**-9 per operation) with float32
# accumulation, softmax, logits and parameters; the reference in float32
# throughout. Through 24 blocks the relative L2 error of the whole
# gradient measured on the chip at the published widths is 0.008-0.012 on
# one sequence of 1024 (my chip runs, PR 22; float32 against the reference:
# 1e-6, benchmark/tests). The limit is about three times that. A step
# computed one precision below - float8 e4m3 rounds 16 times coarser than
# bfloat16 - lands far outside it. The loss agrees to 1e-5 (limit 1e-2).
GRAD_REL_TOL = 0.03
LOSS_REL_TOL = 1e-2

LN_EPS = 1e-6


def make_model(config, axis_name=None):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    del axis_name  # nothing in the model reduces over the batch
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"], num_layers=config["n_layer"],
        num_heads=config["n_head"], d_model=config["n_embd"],
        d_ff=config["n_inner"], max_seq_len=config["n_positions"],
        dtype=jnp.dtype(config["compute_dtype"])))


def init(model, config, key):
    """``(params, aux)``; ``aux`` is empty (no state besides weights)."""
    tokens = jnp.zeros((1, config["n_positions"]), jnp.int32)
    return model.init(key, tokens)["params"], {}


def optimizer(config):
    opt = config["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"gpt2: no optimizer {opt['name']!r}")
    return optax.adam(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                      eps=opt["eps"])


def make_batch(config, key, batch, seq_len):
    """Uniform token ids. Random text has nothing to learn but the
    uniform distribution and the batches themselves, which is enough for
    the loss to fall and costs no data set."""
    return (jax.random.randint(key, (batch, seq_len), 0,
                               config["vocab_size"], jnp.int32),)


def _next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1])
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss(model, params, aux, batch):
    (tokens,) = batch
    return _next_token_loss(model.apply({"params": params}, tokens),
                            tokens), aux


# --------------------------------------------------------------------------
# operations of one training step
# --------------------------------------------------------------------------

def matmul_params(config):
    """Parameters that take part in a matrix multiplication: q, k, v, o,
    the MLP's two matrices in every block, and the output head. The two
    embedding tables are looked up, not multiplied."""
    d, ff = config["n_embd"], config["n_inner"]
    return (config["n_layer"] * (4 * d * d + 2 * d * ff)
            + d * config["vocab_size"])


def model_flops(config, batch, seq_len):
    """Floating-point operations one training step requires, by the
    PaLM convention (Chowdhery et al. 2022, appendix B): per token
    6 N for the N matmul parameters (2 forward, 4 backward) plus
    12 L H Q T for attention's two S x S products (L layers, H heads of
    size Q, T the sequence length), counted over the full square - the
    causal mask is not credited. Layer norm, softmax, GELU, the loss and
    the optimizer are not counted, and nothing recomputed is."""
    head = config["n_embd"] // config["n_head"]
    per_token = (6 * matmul_params(config)
                 + 12 * config["n_layer"] * config["n_head"] * head * seq_len)
    return float(per_token * batch * seq_len)


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(
        jnp.sqrt(2 / jnp.pi) * (x + 0.044715 * x ** 3)))


def reference_loss(config, params, aux, batch):
    """``(loss, aux)``: the forward pass and next-token cross-entropy in
    float32 at the highest matmul precision, on the system's own
    parameter tree. No flax, no bfloat16, no kernels."""
    (tokens,) = batch
    seq = tokens.shape[1]
    head = config["n_embd"] // config["n_head"]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    with jax.default_matmul_precision("highest"):
        x = (params["embed"]["embedding"][tokens]
             + params["pos_embed"]["embedding"][:seq][None])
        for i in range(config["n_layer"]):
            p = params[f"block_{i}"]
            a = p["attn"]
            y = _layer_norm(x, p["LayerNorm_0"])
            q = jnp.einsum("bsd,dhk->bshk", y, a["q"]["kernel"]) / head ** 0.5
            k = jnp.einsum("bsd,dhk->bshk", y, a["k"]["kernel"])
            v = jnp.einsum("bsd,dhk->bshk", y, a["v"]["kernel"])
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            x = x + jnp.einsum("bshk,hkd->bsd", out, a["o"]["kernel"])
            y = _layer_norm(x, p["LayerNorm_1"])
            h = _gelu_tanh(y @ p["mlp"]["wi"]["kernel"])
            x = x + h @ p["mlp"]["wo"]["kernel"]
        x = _layer_norm(x, params["ln_f"])
        logits = x @ params["lm_head"]["kernel"]
        return _next_token_loss(logits, tokens), aux
