"""SmallThinker-21B-A3B (``model_name`` ``smallthinker_21b_instruct``)
through the repo's ``TransformerLM``, for the benchmark, as **one chip's
share** of a deployment in which ``deployment.chips_sharing_a_layer``
chips share each layer: this chip holds ``moe_num_primary_experts`` of
the ``experts_routed`` experts (shard ``expert_shard``) and a slice of
the vocabulary.

* ``make_model`` / ``init`` / ``loss`` - the repo's flax model at the
  configuration's sizes and the next-token loss a user trains on;
* ``make_batch`` - one seeded batch of token ids from the slice;
* ``model_flops``, ``attn_flops``, ``window_attn_flops``, ``gmm_flops``,
  ``gmm_bytes`` - operations of one training step, of its attention and
  of its window layers' attention, and operations and bytes of the
  expert layers' grouped matrix products;
* ``reference_loss`` - the same share of the same function in plain
  float32 ``jax.numpy``, for ``correct``.

The equations (``norm`` is RMS norm with a learned scale, eps
``rms_norm_eps``; no biases). Layer l: ``u = norm_attn(x)``, ``h = x +
attn_l(u)``, ``y = h + moe(norm_ffn(h), u)``; a final norm; an untied
head. ``attn_l``: ``num_attention_heads`` query heads and
``num_key_value_heads`` key/value heads of ``head_dim``, no q/k norm;
where ``rope_layout[l]`` is 1, q and k rotated over the whole head
(rotate-half, ``rope_theta``), where it is 0 no positions at all;
``softmax(q . k / sqrt(head_dim))`` over the keys ``j <= i`` of query
``i``, and where ``sliding_window_layout[l]`` is 1 only those with ``i -
j < sliding_window_size``; ``o`` back to ``hidden_size``. ``moe(e, u)``:
``p = softmax(u W_r)`` over all ``experts_routed`` experts in float32 -
the router reads ``u``, attention's input - the
``moe_num_active_primary_experts`` largest, gates ``p_i / sum of the
picked`` (``norm_topk_prob``), the weighted sum of the picked experts'
ReGLU ``w2(relu(w1 e) * w3 e)`` **that are held here**; no shared expert.

Departures, each followed by the reference: what the experts held on the
other chips would add to a layer's result is left out, and that partial
sum goes on to the next layer (the guide's chip's-share cut: no code
stands in for absent chips); no load-balancing loss (the config gives no
coefficient).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

# Tolerances of `correct`, and why (run.py:check_reference). The system
# computes its matrix products in bfloat16 with float32 accumulation and
# keeps parameters, residual stream, norms, router, softmax statistics and
# logits in float32; the reference is float32 throughout. As in
# models/sdar.py, what separates them is less a product's rounding than
# the routing it can flip: a row's 6th and 7th of 64 softmax scores lie
# close, and each held expert's gradient is summed over ~1536 rows picked
# slightly differently. Measured on the chip at the published widths, one
# sequence of 16384 tokens on a TPU v5e (PERF.md section 6 has them all):
# relative L2 error of the whole gradient 0.0123-0.0135 at the parameters
# the window left (10 seeds) and 0.0118-0.0134 at the initial ones (8
# seeds); float32 against the reference under 1e-4 (tests, tiny sizes).
# The same step one precision below (loss_rounded_through: every dense
# projection's result and its cotangent rounded to float8 e4m3, each
# tensor scaled by a power of two first, as a float8 step scales them)
# reads 0.0915-0.1010 (3 seeds), and 0.156 at the tiny sizes, where
# bfloat16 reads 0.024. The limit lies between: 2.2 times the largest
# sound reading, a third of the smallest float8 one. The forward alone
# rounded to float8 reads 0.0234-0.0243 there and is inside it. The
# losses agree to under 7e-6 (the float8 step's to 3.2e-5): limit 1e-2,
# the harness's.
GRAD_REL_TOL = 0.03
LOSS_REL_TOL = 1e-2

ATTENTION_BLOCK = 512  # query rows a block of the reference's attention


def _held(config):
    """``(first, count)`` of the experts this chip holds."""
    count = config["moe_num_primary_experts"]
    return config["expert_shard"] * count, count


def _layers(config):
    return len(config["layer_types"])


def _windowed(config):
    """Per layer run, whether it is a window layer: what
    ``sliding_window_layout`` gives, which ``layer_types`` spells."""
    layout = config["sliding_window_layout"][:_layers(config)]
    types = config["layer_types"]
    if [t == "sliding_attention" for t in types] != [bool(w) for w in layout]:
        raise ValueError(f"layer_types {types} is not sliding_window_layout "
                         f"{layout}")
    return layout


def make_model(config, axis_name=None):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    del axis_name  # nothing in the model reduces over the batch
    _windowed(config)
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"],
        num_layers=_layers(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_model=config["hidden_size"],
        max_seq_len=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        residual_dtype=jnp.dtype(config["residual_dtype"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        positions="rotary", rope_theta=float(config["rope_theta"]),
        rope_layout=tuple(config["rope_layout"][:_layers(config)]),
        layer_types=tuple(config["layer_types"]),
        sliding_window=config["sliding_window_size"],
        tie_embeddings=config["tie_word_embeddings"],
        moe_routed=config["experts_routed"], moe_held=_held(config),
        moe_d_ff=config["moe_ffn_hidden_size"],
        moe_top_k=config["moe_num_active_primary_experts"],
        moe_scoring="softmax", moe_renormalize=config["norm_topk_prob"],
        moe_activation="relu", moe_router_input="operator_input",
        remat_blocks=config.get("remat_blocks", False)))


def init(model, config, key):
    """``(params, aux)``; ``aux`` is the model's ``routing`` collection:
    per expert layer the last step's load (``expert_load``: picks each of
    the routed experts got; ``rows_held``: how many of them landed on
    this chip; ``buffer_rows``: rows of the buffer the step worked on)."""
    variables = model.init(key, jnp.zeros((1, 8), jnp.int32))
    params = variables["params"]
    # Token embeddings of unit RMS, as models/sdar.py and for its reason:
    # at rows of norm 1 attention at random weights hands every row the
    # running mean of its keys' values, the stream collapses onto one
    # direction and softmax routing sends every row to the same experts.
    params["embed"]["embedding"] = (params["embed"]["embedding"]
                                    * config["hidden_size"] ** 0.5)
    return params, variables["routing"]


def optimizer(config):
    opt = config["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"smallthinker: no optimizer {opt['name']!r}")
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

def _expert_params(config):
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def layer_matmul_params(config):
    """Parameters a row meets in a layer's matrix products on this chip:
    q, k, v, o, the router, and of the experts the expected share under
    uniform routing: ``moe_num_active_primary_experts *
    moe_num_primary_experts / experts_routed`` experts a row (0.75 here),
    whatever the routing does."""
    d, head = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    experts_a_row = (config["moe_num_active_primary_experts"]
                     * config["moe_num_primary_experts"]
                     / config["experts_routed"])
    return (2 * d * heads * head + 2 * d * kv * head
            + d * config["experts_routed"]
            + experts_a_row * _expert_params(config))


def visible_pairs(config, seq_len, window=False):
    """(query, key) pairs one sequence's attention lets through: ``S (S +
    1) / 2`` causal, or, over a window of W keys, ``sum min(i + 1, W)``:
    ``W (W + 1) / 2 + (S - W) W`` where the sequence is longer."""
    if not window:
        return seq_len * (seq_len + 1) // 2
    w = min(config["sliding_window_size"], seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def _attn_flops(config, batch, seq_len, layers):
    per_pair = 3 * 4 * config["num_attention_heads"] * config["head_dim"]
    return float(per_pair * batch * sum(
        visible_pairs(config, seq_len, bool(w)) for w in layers))


def attn_flops(config, batch, seq_len):
    """Operations of one step's attention, forward and backward, over the
    visible pairs only: a pair costs two products of ``head_dim``
    multiply-adds a head forward, and twice that backward. The same
    whatever computes it: a kernel that visits hidden pairs earns nothing
    for them."""
    return _attn_flops(config, batch, seq_len, _windowed(config))


def window_attn_flops(config, batch, seq_len):
    """:func:`attn_flops` of the window layers alone."""
    return _attn_flops(config, batch, seq_len,
                       [w for w in _windowed(config) if w])


def model_flops(config, batch, seq_len):
    """Floating-point operations one training step requires, by the PaLM
    convention (Chowdhery et al. 2022, appendix B) as ``models/gpt2.py``
    counts: 6 N a row for the N matmul parameters a row meets in the
    layers and in the head, and :func:`attn_flops`. Norms, the rotation,
    softmax, routing (top-k, sort, gather, combine), the loss and the
    optimizer are not counted, and nothing recomputed is."""
    rows = batch * seq_len
    head = config["hidden_size"] * config["vocab_size"]
    return float(6 * _layers(config) * layer_matmul_params(config) * rows
                 + 6 * head * rows + attn_flops(config, batch, seq_len))


def rows_per_step(batch, seq_len):
    """Rows every layer runs."""
    return batch * seq_len


def expected_rows(config, rows):
    """(row, pick) pairs an expert layer routes to this chip under
    uniform routing, of ``rows`` rows."""
    return (rows * config["moe_num_active_primary_experts"]
            * config["moe_num_primary_experts"] / config["experts_routed"])


def gmm_flops(config, rows):
    """Operations of the grouped matrix products of one step at the
    expected rows: three products an expert layer (w1, w3, w2), each
    forward, its row gradient and its weight gradient."""
    per_row = 2 * _expert_params(config)
    return float(3 * per_row * expected_rows(config, rows)
                 * _layers(config))


def gmm_bytes(config, rows, itemsize=2):
    """Bytes the same nine products a layer have to move, whatever
    implements them: each reads two of (rows x k, rows x n, the held
    experts' k x n) and writes the third, in the compute dtype."""
    held = expected_rows(config, rows)
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    a_product = held * d + held * f + config["moe_num_primary_experts"] * d * f
    return float(9 * a_product * itemsize * _layers(config))


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rotate(x, theta):
    seq, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(config, p, u, rotary, window):
    """Grouped-query causal attention in blocks of query rows, one block
    at a time (``lax.map``), each under ``jax.checkpoint``: the heads x
    rows x keys float32 scores never exist whole, forward or backward. A
    block of a window layer reads only the keys from ``window - 1`` before
    its first row up to its last row; a block of a full layer reads every
    key and masks those after its rows."""
    kv_heads, head = config["num_key_value_heads"], config["head_dim"]
    group = config["num_attention_heads"] // kv_heads
    batch, seq, _ = u.shape
    q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"])
    k = jnp.einsum("bsd,dhk->bshk", u, p["k"]["kernel"])
    v = jnp.einsum("bsd,dhk->bshk", u, p["v"]["kernel"])
    if rotary:
        theta = float(config["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    q = q.reshape(batch, seq, kv_heads, group, head) / head ** 0.5
    size = ATTENTION_BLOCK if seq % ATTENTION_BLOCK == 0 else seq
    reach = (min(config["sliding_window_size"], seq) - 1) if window else 0
    # a window block's keys: ``reach`` rows of zeros before the sequence,
    # so that every block slices the same length; those rows are masked
    k, v = (jnp.pad(t, ((0, 0), (reach, 0), (0, 0), (0, 0))) for t in (k, v))

    @jax.checkpoint
    def block(rows):
        q_rows, first = rows
        at = first + jnp.arange(size)
        if window:
            keys = reach + size
            k_rows = jax.lax.dynamic_slice_in_dim(k, first, keys, axis=1)
            v_rows = jax.lax.dynamic_slice_in_dim(v, first, keys, axis=1)
            key_at = first - reach + jnp.arange(keys)
            seen = ((key_at[None] <= at[:, None])
                    & (key_at[None] > at[:, None] - (reach + 1))
                    & (key_at[None] >= 0))
        else:
            k_rows, v_rows = k, v
            seen = at[:, None] >= jnp.arange(seq)[None]
        scores = jnp.einsum("bqngd,bknd->bngqk", q_rows, k_rows)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bngqk,bknd->bqngd", probs, v_rows)

    blocks = q.reshape(batch, seq // size, size, kv_heads, group, head)
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0),
                              jnp.arange(0, seq, size)))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, kv_heads * group, head)
    return jnp.einsum("bshk,hkd->bsd", out, p["o"]["kernel"])


def _reglu(e, w1, w3, w2):
    return (jax.nn.relu(e @ w1) * (e @ w3)) @ w2


def _routing(config, p, u):
    """``(gates, picked)``, both (rows, experts_routed): the picked
    probabilities over their sum, zero elsewhere, and the mask of the
    picks. The k picks are k argmaxes, each taken out before the next:
    no sort, no ``top_k``."""
    probs = jax.nn.softmax(u @ p["router"], -1)
    ranked = jax.lax.stop_gradient(probs)
    picked = jnp.zeros(probs.shape, bool)
    for _ in range(config["moe_num_active_primary_experts"]):
        best = jnp.argmax(jnp.where(picked, -jnp.inf, ranked), -1)
        picked = picked | jax.nn.one_hot(best, probs.shape[-1], dtype=bool)
    gates = jnp.where(picked, probs, 0.0)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates, picked


def _experts(config, p, e, u, held=None):
    """Every held expert applied to every row of ``e``, one expert at a
    time (``lax.scan``), masked by the gates the router gives from ``u``
    (the experts held elsewhere add nothing here). Returns the layer's
    result and its load."""
    first, count = held or _held(config)
    flat = e.reshape(-1, e.shape[-1])
    gates, picked = _routing(config, p, u.reshape(-1, u.shape[-1]))

    @jax.checkpoint
    def one(y, expert):
        w1, w3, w2, gate = expert
        return y + gate[:, None] * _reglu(flat, w1, w3, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(flat), (
        p["w1"], p["w3"], p["w2"], gates[:, first:first + count].T))
    load = jnp.sum(picked, 0, dtype=jnp.int32)
    return y.reshape(e.shape), {
        "expert_load": load,
        "rows_held": jnp.sum(load[first:first + count])}


def _layer(config, p, x, rotary, window):
    eps = config["rms_norm_eps"]
    u = _rms_norm(x, p["operator_norm"]["scale"], eps)
    x = x + _attention(config, p["attn"], u, rotary, window)
    y, load = _experts(config, p["moe"],
                       _rms_norm(x, p["ffn_norm"]["scale"], eps), u)
    return x + y, load


def reference_forward(config, params, tokens):
    """``(logits, aux)``: float32 logits (batch, seq, vocabulary) at the
    highest matmul precision, on the system's own parameter tree. No
    flax, no bfloat16, no kernels, no sort. A layer is recomputed in the
    backward pass (``jax.checkpoint``), which changes no number: the full
    size then fits beside the parameters and two sets of gradients."""
    new_aux = {}
    layers = len(config["layer_types"])
    rope = config["rope_layout"][:layers]
    window = config["sliding_window_layout"][:layers]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens]
        for i in range(layers):
            name = f"block_{i}"
            x, load = jax.checkpoint(
                lambda p, x, i=i: _layer(config, p, x, rope[i], window[i]))(
                    params[name], x)
            new_aux[name] = {"moe": load}
        x = _rms_norm(x, params["embedding_norm"]["scale"],
                      config["rms_norm_eps"])
        logits = x @ params["lm_head"]["kernel"]
    return logits, new_aux


def reference_loss(config, params, aux, batch):
    """``(loss, aux)``: next-token cross-entropy of the reference."""
    del aux  # softmax routing carries no state into a step
    (tokens,) = batch
    logits, new_aux = reference_forward(config, params, tokens)
    return _next_token_loss(logits, tokens), new_aux


def _round_scaled(x, dtype):
    """``x`` rounded to ``dtype``'s exponent and mantissa bits, first
    scaled by the power of two that puts its largest magnitude at the top
    of that range (a float8 step's per-tensor scale, so that nothing
    underflows that the scale could keep). ``reduce_precision`` and not a
    convert pair through ``dtype``: the compiler may fold ``bf16 -> f8 ->
    bf16`` away, and did on the TPU."""
    info = jnp.finfo(dtype)
    top = 2.0 ** (2 ** (info.nexp - 1) - 1) * (2 - 2.0 ** -info.nmant)
    wide = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(wide)), jnp.finfo(jnp.float32).tiny)
    scale = jnp.exp2(jnp.floor(jnp.log2(top / amax)))
    return (jax.lax.reduce_precision(wide * scale, exponent_bits=info.nexp,
                                     mantissa_bits=info.nmant)
            / scale).astype(x.dtype)


def loss_rounded_through(dtype, model, params, aux, batch):
    """``loss`` with every dense projection's result (q, k, v, o, the
    head) and its cotangent rounded through ``dtype`` on their way, each
    scaled as ``_round_scaled`` says: what a step computed one precision
    below the configuration's would give, for showing that
    ``GRAD_REL_TOL`` sees it (``jnp.float8_e4m3fn`` under bfloat16)."""
    import flax.linen as nn

    from horovod_tpu.models.transformer import HeadsProjection

    @jax.custom_vjp
    def round_both_ways(x):
        return _round_scaled(x, dtype)

    round_both_ways.defvjp(
        lambda x: (_round_scaled(x, dtype), None),
        lambda _, g: (_round_scaled(g, dtype),))

    def rounded(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if isinstance(context.module,
                      (nn.Dense, nn.DenseGeneral, HeadsProjection)):
            out = round_both_ways(out)
        return out

    with nn.intercept_methods(rounded):
        return loss(model, params, aux, batch)
