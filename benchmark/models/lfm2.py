"""LFM2-MoE (``model_type`` ``lfm2_moe``) through the repo's
``TransformerLM``, for the benchmark, as **one chip's share** of a
deployment in which ``deployment.chips_sharing_a_layer`` chips share each
layer: this chip holds ``num_experts`` of the ``experts_routed`` experts
(shard ``expert_shard``) and a slice of the vocabulary.

* ``make_model`` / ``init`` / ``loss`` - the repo's flax model at the
  configuration's sizes and the next-token loss a user trains on;
* ``make_batch`` - one seeded batch of token ids from the slice;
* ``model_flops``, ``gmm_flops``, ``gmm_bytes`` - operations of one
  training step, and operations and bytes of the expert layers' grouped
  matrix products;
* ``reference_loss`` - the same share of the same function in plain
  float32 ``jax.numpy``, for ``correct``.

Layer equations, as transformers' ``modeling_lfm2_moe.py`` has them
(``norm`` is RMS norm with a learned scale, eps ``norm_eps``; no biases):
layer i is ``h += op_i(norm(h))`` then ``h += ffn_i(norm(h))``. ``op`` of
a ``conv`` layer: ``[B, C, x] = split3(in_proj u)``, ``z = B * x``,
``c[t] = sum_j w[j] * z[t - 2 + j]`` (depthwise, causal, ``conv_L_cache``
taps), ``out_proj(C * c)``. ``op`` of a ``full_attention`` layer:
grouped-query heads, q and k RMS-normed per head, rotary over the whole
head (rotate-half), causal softmax scaled 1/sqrt(head). ``ffn`` of the
first ``num_dense_layers`` layers: ``w2(silu(w1 u) * w3 u)``; of the
others: scores ``sigmoid(router u)`` in float32, the ``num_experts_per_tok``
experts with the largest ``score + expert_bias``, weights the picked
scores over their sum (+1e-6) times ``routed_scaling_factor``, result the
weighted sum of the picked experts' SwiGLU **that are held here**. Then
``embedding_norm`` and logits against the tied embedding.

Departures, each followed by the reference: what the experts held on the
other chips would add to a layer's result is left out, and that partial
sum goes on to the next layer (the guide's chip's-share cut: no code
stands in for absent chips); ``expert_bias`` is seeded with small values
(std 0.01, so that the selection differs from the plain top-k of the
scores) and never updated (the published config gives no rate).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

# Tolerances of `correct`, and why (run.py:check_reference). The system
# computes its matrix products in bfloat16 with float32 accumulation and
# keeps parameters, residual stream, norms, router and logits in float32;
# the reference is float32 throughout. What separates them here is less
# the rounding of a product than the routing it can flip: the 4th and 5th
# of a token's 64 scores lie about 0.02 apart, and the program and the
# reference agree on 98.4 % of the (token, pick) pairs at the seed's
# parameters (98.6 % after 8 steps; tools/moe_probe.py --agreement, my
# chip run, PR 32, 2 seeds), so each held expert's gradient, summed over
# ~512 rows, is computed from a slightly different set of rows. Measured
# on the chip at the published widths, one sequence of 8192 (my chip
# runs, PR 32): relative L2 error of the whole gradient 0.055-0.061 at the
# parameters the window left (12 runs, 11 seeds) and 0.050-0.053 at the
# initial ones; float32 against the reference: 9e-7 (tests, tiny sizes).
# The limit is 2.5 times the largest reading, as ResNet-50's is 3 times
# its own. A step one precision below - every dense projection's result
# rounded through float8 e4m3 - reads 0.991 at the same widths (2 seeds;
# 0.99 at the tiny sizes, benchmark/tests/test_lfm2.py) and is refused.
# Before the residual stream and the router's input were float32 the
# routing was the risk the issue named; it was never run that way on the
# chip. The losses agree to under 1e-4 (limit 1e-2, the harness's).
GRAD_REL_TOL = 0.15
LOSS_REL_TOL = 1e-2

ATTENTION_BLOCK = 512  # query rows a block of the reference's attention


def _held(config):
    """``(first, count)`` of the experts this chip holds."""
    count = config["num_experts"]
    return config["expert_shard"] * count, count


def _head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def make_model(config, axis_name=None):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    del axis_name  # nothing in the model reduces over the batch
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"],
        num_layers=len(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        residual_dtype=jnp.dtype(config["residual_dtype"]),
        norm="rmsnorm", norm_eps=config["norm_eps"],
        positions="rotary",
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        qk_norm=True, mlp="swiglu",
        layer_types=tuple(config["layer_types"]),
        conv_kernel=config["conv_L_cache"], tie_embeddings=True,
        moe_routed=config["experts_routed"], moe_held=_held(config),
        moe_d_ff=config["moe_intermediate_size"],
        moe_top_k=config["num_experts_per_tok"],
        num_dense_layers=config["num_dense_layers"],
        moe_renormalize=config["norm_topk_prob"],
        moe_scaling=float(config["routed_scaling_factor"])))


def init(model, config, key):
    """``(params, aux)``; ``aux`` is the model's ``routing`` collection:
    per expert layer the selection bias, and the last step's per-expert
    load (``expert_load``: picks each of the routed experts got;
    ``rows_held``: how many of them landed on this chip)."""
    del config  # no table of positions: any length creates the weights
    variables = model.init(key, jnp.zeros((1, 8), jnp.int32))
    return variables["params"], variables["routing"]


def optimizer(config):
    opt = config["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"lfm2: no optimizer {opt['name']!r}")
    return optax.adam(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                      eps=opt["eps"])


def make_batch(config, key, batch, seq_len):
    """Uniform token ids over this chip's slice of the vocabulary."""
    return (jax.random.randint(key, (batch, seq_len), 0,
                               config["vocab_size"], jnp.int32),)


def _next_token_loss(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1])
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss(model, params, aux, batch):
    (tokens,) = batch
    logits, mutated = model.apply({"params": params, "routing": aux},
                                  tokens, mutable=["routing"])
    return _next_token_loss(logits, tokens), mutated["routing"]


# --------------------------------------------------------------------------
# operations of one training step
# --------------------------------------------------------------------------

def _expert_layers(config):
    return len(config["layer_types"]) - config["num_dense_layers"]


def _expert_params(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def matmul_params(config):
    """Parameters a token meets in a matrix multiplication on this chip:
    the operators' projections, the dense MLP, the router, the (tied)
    head, and of the experts the expected share under uniform routing:
    ``num_experts_per_tok * num_experts / experts_routed`` experts a token
    and expert layer (0.5 here), the same whatever the routing does. The
    embedding is looked up, the depthwise filter and the norms are not
    matrix products."""
    d = config["hidden_size"]
    kv = config["num_key_value_heads"] * _head_dim(config)
    operators = {"conv": 3 * d * d + d * d,
                 "full_attention": 2 * d * d + 2 * d * kv}
    experts_a_token = (config["num_experts_per_tok"] * config["num_experts"]
                       / config["experts_routed"])
    return (sum(operators[t] for t in config["layer_types"])
            + config["num_dense_layers"] * 3 * d * config["intermediate_size"]
            + _expert_layers(config) * (d * config["experts_routed"]
                                        + experts_a_token
                                        * _expert_params(config))
            + d * config["vocab_size"])


def model_flops(config, batch, seq_len):
    """Floating-point operations one training step requires, by the
    PaLM convention (Chowdhery et al. 2022, appendix B), as
    ``models/gpt2.py`` counts: per token 6 N for the N matmul parameters
    a token meets, plus 12 H Q T for each attention layer's two S x S
    products (H query heads of size Q, T the sequence length) over the
    full square. Norms, the rotation, the depthwise filter, softmax,
    routing (top-k, sort, gather, combine), the loss and the optimizer
    are not counted, and nothing recomputed is."""
    attention = sum(t == "full_attention" for t in config["layer_types"])
    per_token = (6 * matmul_params(config)
                 + 12 * attention * config["num_attention_heads"]
                 * _head_dim(config) * seq_len)
    return float(per_token * batch * seq_len)


def expected_rows(config, tokens):
    """(token, pick) pairs an expert layer routes to this chip under
    uniform routing."""
    return (tokens * config["num_experts_per_tok"] * config["num_experts"]
            / config["experts_routed"])


def gmm_flops(config, tokens):
    """Operations of the grouped matrix products of one step at the
    expected rows: three products an expert layer (w1, w3, w2), each
    forward, its row gradient and its weight gradient."""
    per_row = 2 * _expert_params(config)
    return float(3 * per_row * expected_rows(config, tokens)
                 * _expert_layers(config))


def gmm_bytes(config, tokens, itemsize=2):
    """Bytes the same nine products a layer have to move, whatever
    implements them: each reads two of (rows x k, rows x n, the held
    experts' k x n) and writes the third, in the compute dtype."""
    rows = expected_rows(config, tokens)
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    a_product = rows * d + rows * f + config["num_experts"] * d * f
    return float(9 * a_product * itemsize * _expert_layers(config))


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _short_conv(config, p, u):
    taps = config["conv_L_cache"]
    gate_in, gate_out, x = jnp.split(u @ p["in_proj"]["kernel"], 3, -1)
    z = gate_in * x
    seq = z.shape[1]
    c = jnp.zeros_like(z)
    for j in range(taps):
        shift = taps - 1 - j     # tap j reads z[t - shift]
        c = c.at[:, shift:].add(z[:, :seq - shift] * p["kernel"][j])
    return (gate_out * c) @ p["out_proj"]["kernel"]


def _rotate(x, theta):
    seq, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(config, p, u):
    """Grouped-query causal attention in blocks of query rows, one block
    at a time (``lax.map``), each under ``jax.checkpoint``: the heads x S x
    S float32 scores never exist whole, forward or backward."""
    eps = config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    kv_heads, head = config["num_key_value_heads"], _head_dim(config)
    group = config["num_attention_heads"] // kv_heads
    batch, seq, _ = u.shape
    q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"])
    k = jnp.einsum("bsd,dhk->bshk", u, p["k"]["kernel"])
    v = jnp.einsum("bsd,dhk->bshk", u, p["v"]["kernel"])
    q = _rotate(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rotate(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    q = q.reshape(batch, seq, kv_heads, group, head) / head ** 0.5

    @jax.checkpoint
    def block(rows):
        q_rows, first = rows
        scores = jnp.einsum("bqngd,bknd->bngqk", q_rows, k)
        at = first + jnp.arange(q_rows.shape[1])
        seen = at[:, None] >= jnp.arange(seq)[None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bngqk,bknd->bqngd", probs, v)

    size = ATTENTION_BLOCK if seq % ATTENTION_BLOCK == 0 else seq
    blocks = q.reshape(batch, seq // size, size, kv_heads, group, head)
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0),
                              jnp.arange(0, seq, size)))
    out = jnp.moveaxis(out, 0, 1)
    out = out.reshape(batch, seq, kv_heads * group, head)
    return jnp.einsum("bshk,hkd->bsd", out, p["o"]["kernel"])


def _swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def _routing(config, p, bias, u):
    """``(weights, picked)``, both (tokens, experts_routed): the picked
    scores renormalised and scaled, zero elsewhere, and the mask of the
    picks. The k picks are k argmaxes of ``score + bias``, each taken
    out before the next: no sort, no ``top_k``."""
    scores = jax.nn.sigmoid(u @ p["router"])
    biased = jax.lax.stop_gradient(scores + bias)
    picked = jnp.zeros(scores.shape, bool)
    for _ in range(config["num_experts_per_tok"]):
        best = jnp.argmax(jnp.where(picked, -jnp.inf, biased), -1)
        picked = picked | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    weights = jnp.where(picked, scores, 0.0)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return weights * config["routed_scaling_factor"], picked


def _experts(config, p, bias, u):
    """Every held expert applied to every token, one expert at a time
    (``lax.scan``), masked by the routing weights (the experts held
    elsewhere add nothing here). Returns the layer's result, its load
    and the mask of the picks."""
    first, count = _held(config)
    flat = u.reshape(-1, u.shape[-1])
    weights, picked = _routing(config, p, bias, flat)

    @jax.checkpoint
    def one(y, expert):
        w1, w3, w2, gate = expert
        return y + gate[:, None] * _swiglu(flat, w1, w3, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(flat), (
        p["w1"], p["w3"], p["w2"], weights[:, first:first + count].T))
    load = jnp.sum(picked, 0, dtype=jnp.int32)
    return y.reshape(u.shape), {
        "expert_load": load,
        "rows_held": jnp.sum(load[first:first + count])}, picked


def reference_forward(config, params, aux, tokens):
    """``(logits, aux, picks)`` in float32 at the highest matmul
    precision, on the system's own parameter tree. No flax, no bfloat16,
    no kernels, no sort. ``picks`` is, per expert layer, the
    (tokens, experts_routed) mask of the routing's picks."""
    eps = config["norm_eps"]
    new_aux, picks = {}, {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        for i, layer_type in enumerate(config["layer_types"]):
            name = f"block_{i}"
            p = params[name]
            u = _rms_norm(x, p["operator_norm"]["scale"], eps)
            if layer_type == "conv":
                x = x + _short_conv(config, p["conv"], u)
            else:
                x = x + _attention(config, p["attn"], u)
            u = _rms_norm(x, p["ffn_norm"]["scale"], eps)
            if i < config["num_dense_layers"]:
                m = p["mlp"]
                x = x + jax.checkpoint(_swiglu)(
                    u, m["w1"]["kernel"], m["w3"]["kernel"],
                    m["w2"]["kernel"])
            else:
                bias = aux[name]["moe"]["expert_bias"]
                y, load, picks[name] = _experts(config, p["moe"], bias, u)
                x = x + y
                new_aux[name] = {"moe": {"expert_bias": bias, **load}}
        x = _rms_norm(x, params["embedding_norm"]["scale"], eps)
        logits = x @ params["embed"]["embedding"].T
    return logits, new_aux, picks


def reference_loss(config, params, aux, batch):
    """``(loss, aux)``: next-token cross-entropy of the reference."""
    (tokens,) = batch
    logits, new_aux, _ = reference_forward(config, params, aux, tokens)
    return _next_token_loss(logits, tokens), new_aux


def loss_rounded_through(dtype, model, params, aux, batch):
    """``loss`` with every dense projection's result rounded through
    ``dtype`` on its way: what a step computed one precision below the
    configuration's would give, for showing that ``GRAD_REL_TOL`` sees it
    (``jnp.float8_e4m3fn`` under bfloat16)."""
    import flax.linen as nn

    def rounded(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if isinstance(context.module, (nn.Dense, nn.DenseGeneral)):
            out = out.astype(dtype).astype(out.dtype)
        return out

    with nn.intercept_methods(rounded):
        return loss(model, params, aux, batch)


def routing_agreement(model, config, params, aux, batch):
    """Share of the (token, pick) pairs, over all expert layers, on which
    the program (as configured: bfloat16 on the chip) and the float32
    reference pick the same expert. Not part of ``correct``; it says how
    much of ``grad_rel_err`` is routing that flipped."""
    (tokens,) = batch
    _, state = model.apply({"params": params, "routing": aux}, tokens,
                           mutable=["intermediates"])
    _, _, picks = reference_forward(config, params, aux, tokens)
    same = total = 0
    for name, mask in picks.items():
        (idx,) = state["intermediates"][name]["moe"]["expert_idx"]
        same += jnp.sum(jnp.take_along_axis(mask, idx, axis=-1))
        total += idx.size
    return same / total
