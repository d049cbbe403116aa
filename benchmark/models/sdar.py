"""SDAR-MoE (``model_type`` ``sdar_moe``) through the repo's
``TransformerLM``, for the benchmark, trained with the block-diffusion
objective, as **one chip's share** of a deployment in which
``deployment.chips_sharing_a_layer`` chips share each layer: this chip
holds ``num_experts`` of the ``experts_routed`` experts (shard
``expert_shard``) and a slice of the vocabulary.

* ``make_model`` / ``init`` / ``loss`` - the repo's flax model at the
  configuration's sizes and the loss a user trains on;
* ``make_batch`` - one seeded batch: token ids from the slice, which of
  them are masked, and the rate each block was masked at;
* ``model_flops``, ``attn_flops``, ``gmm_flops``, ``gmm_bytes`` -
  operations of one training step, of its attention, and operations and
  bytes of the expert layers' grouped matrix products;
* ``reference_loss`` - the same share of the same function in plain
  float32 ``jax.numpy``, for ``correct``.

The equations. The block is transformers' ``modeling_qwen3_moe.py`` block,
which ``sdar_moe`` keeps (``norm`` is RMS norm with a learned scale, eps
``rms_norm_eps``; no biases): ``h = x + attn(norm(x))``, ``y = h +
moe(norm(h))``, every layer alike; a final norm; an untied head. ``attn``:
``num_attention_heads`` query heads and ``num_key_value_heads`` key/value
heads of ``head_dim`` (not ``hidden_size / heads``), q and k RMS-normed
per head, rotary over the whole head (rotate-half, ``rope_theta``) at the
row's position, softmax of ``q . k / sqrt(head_dim)`` under the mask
below, ``o`` from ``heads * head_dim`` back to ``hidden_size``. ``moe``:
``p = softmax(router u)`` over all ``experts_routed`` experts in float32,
the ``num_experts_per_tok`` largest, gates ``p_i / sum of the picked``
(``norm_topk_prob``), the weighted sum of the picked experts' SwiGLU
**that are held here**; no shared expert, no bias, no scaling.

The mask and the objective are block diffusion as BD3-LMs
(arXiv:2503.09573), which SDAR follows. A sequence ``x0`` of L tokens is
run as 2L rows: rows 0..L-1 the noised copy ``xt``, rows L..2L-1 ``x0``.
Row r has copy ``c(r)``, position ``p(r) = r mod L`` and block ``b(r) =
p(r) // block_length``. Row r sees row s where: both noised and ``b(s) =
b(r)``; r noised, s clean and ``b(s) < b(r)``; both clean and ``b(s) <=
b(r)``. Each block draws a rate ``t ~ U[mask_rate_min, 1]`` and masks each
of its tokens with probability ``t`` (``xt`` = ``mask_token_id`` there).
Loss: ``(1 / L) sum over masked i of -log softmax(logits_xt[i])[x0[i]] /
t(block of i)``, the mean over the batch; the clean copy's rows give keys
and values and no loss term.

Departures, each followed by the reference: what the experts held on the
other chips would add to a layer's result is left out, and that partial
sum goes on to the next layer (the guide's chip's-share cut: no code
stands in for absent chips); the mask id is the last row of the
vocabulary's slice and data ids are drawn below it (the published id lies
outside an eighth of the vocabulary).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

# Tolerances of `correct`, and why (run.py:check_reference). The system
# computes its matrix products in bfloat16 with float32 accumulation and
# keeps parameters, residual stream, norms, router, softmax statistics and
# logits in float32; the reference is float32 throughout. As in
# models/lfm2.py, what separates them is less a product's rounding than
# the routing it can flip: a row's 8th and 9th of 128 softmax scores lie
# close, and each held expert's gradient is summed over ~512 rows picked
# slightly differently. Measured on the chip at the published widths, one
# sequence of 4096 tokens (my chip runs, PR 35; PERF.md section 6 has
# every reading): relative L2 error of the whole gradient 0.0055-0.0245 at
# the parameters the window left (7 runs, 7 seeds; one above 0.013) and
# 0.0076 / 0.0086 at the initial ones; 0.031 at the worst under training
# rates the configuration does not use; float32 against the reference
# 4e-7 (tests, tiny sizes). A step one precision below - every dense
# projection's result rounded through float8 e4m3 - reads 0.782 / 0.757
# at the same widths (0.97 before the embedding had unit RMS) and is
# refused. The limit is the geometric middle of the largest reading and
# the smallest float8 one (0.136), rounded: six times the one, a fifth of
# the other. The losses agree to under 6e-4 (limit 1e-2, the harness's).
GRAD_REL_TOL = 0.15
LOSS_REL_TOL = 1e-2

ATTENTION_BLOCK = 512  # query rows a block of the reference's attention


def _held(config):
    """``(first, count)`` of the experts this chip holds."""
    count = config["num_experts"]
    return config["expert_shard"] * count, count


def _mask_id(config):
    return config["vocab_size"] - 1


def make_model(config, axis_name=None):
    from horovod_tpu.models import TransformerConfig, TransformerLM

    del axis_name  # nothing in the model reduces over the batch
    return TransformerLM(TransformerConfig(
        vocab_size=config["vocab_size"],
        num_layers=len(config["layer_types"]),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        dtype=jnp.dtype(config["compute_dtype"]),
        residual_dtype=jnp.dtype(config["residual_dtype"]),
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        positions="rotary", rope_theta=float(config["rope_theta"]),
        qk_norm=True, mlp="swiglu",
        layer_types=tuple(config["layer_types"]),
        tie_embeddings=config["tie_word_embeddings"],
        attn_mask="block_diffusion", block_length=config["block_length"],
        moe_routed=config["experts_routed"], moe_held=_held(config),
        moe_d_ff=config["moe_intermediate_size"],
        moe_top_k=config["num_experts_per_tok"],
        moe_scoring="softmax", moe_renormalize=config["norm_topk_prob"]))


def init(model, config, key):
    """``(params, aux)``; ``aux`` is the model's ``routing`` collection:
    per expert layer the last step's load (``expert_load``: picks each of
    the routed experts got; ``rows_held``: how many of them landed on
    this chip; ``buffer_rows``: rows of the buffer the step worked on)."""
    rows = 2 * config["block_length"]
    variables = model.init(key, jnp.zeros((1, rows), jnp.int32),
                           jnp.zeros((1, rows), jnp.int32))
    params = variables["params"]
    # Token embeddings of unit RMS (flax's default is 1 / sqrt(width) an
    # element). At random weights attention hands every row the running
    # mean of its keys' values; five such layers over rows of norm 1 leave
    # the stream 93 % one common direction, the router then sends every
    # row to the same 8 experts and this chip's load is 0 or 8192 rows an
    # expert where a trained, balanced router gives ~512 (PERF.md section
    # 6, PR 35: measured). Rows that keep their own token apart from that
    # mean route near uniformly, as the checkpoint's do.
    params["embed"]["embedding"] = (params["embed"]["embedding"]
                                    * config["hidden_size"] ** 0.5)
    return params, variables["routing"]


def optimizer(config):
    opt = config["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"sdar: no optimizer {opt['name']!r}")
    return optax.adam(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                      eps=opt["eps"])


def make_batch(config, key, batch, seq_len):
    """``(tokens, masked, rate)``: uniform token ids over this chip's
    slice of the vocabulary less the mask id, (batch, seq_len) int32;
    which of them the noised copy masks, bool; and the rate of each block,
    (batch, seq_len / block_length) float32, uniform on
    [mask_rate_min, 1]."""
    block = config["block_length"]
    ids, rates, draws = jax.random.split(key, 3)
    tokens = jax.random.randint(ids, (batch, seq_len), 0, _mask_id(config),
                                jnp.int32)
    rate = jax.random.uniform(rates, (batch, seq_len // block), jnp.float32,
                              config["mask_rate_min"], 1.0)
    masked = (jax.random.uniform(draws, (batch, seq_len))
              < jnp.repeat(rate, block, axis=1))
    return tokens, masked, rate


def loss(model, params, aux, batch):
    from horovod_tpu.models import block_diffusion

    tokens, masked, rate = batch
    ids, positions = block_diffusion.doubled_inputs(
        tokens, masked, model.cfg.vocab_size - 1)
    logits, mutated = model.apply({"params": params, "routing": aux}, ids,
                                  positions, mutable=["routing"])
    return (block_diffusion.masked_token_loss(
        logits, tokens, masked, rate, model.cfg.block_length),
        mutated["routing"])


# --------------------------------------------------------------------------
# operations of one training step
# --------------------------------------------------------------------------

def _layers(config):
    return len(config["layer_types"])


def _expert_params(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_matmul_params(config):
    """Parameters a row meets in a layer's matrix products on this chip:
    q, k, v, o, the router, and of the experts the expected share under
    uniform routing: ``num_experts_per_tok * num_experts /
    experts_routed`` experts a row (1 here), whatever the routing does."""
    d, head = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    experts_a_row = (config["num_experts_per_tok"] * config["num_experts"]
                     / config["experts_routed"])
    return (2 * d * heads * head + 2 * d * kv * head
            + d * config["experts_routed"]
            + experts_a_row * _expert_params(config))


def visible_pairs(config, seq_len):
    """(query row, key row) pairs of one doubled sequence the mask lets
    through: ``L B`` within the noised copy, ``(L^2 - L B) / 2`` from it to
    the clean copy, ``(L^2 + L B) / 2`` within the clean copy."""
    return seq_len * seq_len + seq_len * config["block_length"]


def attn_flops(config, batch, seq_len):
    """Operations of one step's attention, forward and backward, over the
    visible pairs only: a pair costs two products of ``head_dim``
    multiply-adds a head forward, and twice that backward. The same
    whatever computes it: a kernel that visits hidden pairs earns nothing
    for them."""
    per_pair = 3 * 4 * config["num_attention_heads"] * config["head_dim"]
    return float(per_pair * visible_pairs(config, seq_len) * batch
                 * _layers(config))


def model_flops(config, batch, seq_len):
    """Floating-point operations one training step requires, by the PaLM
    convention (Chowdhery et al. 2022, appendix B) as ``models/gpt2.py``
    counts: 6 N a row for the N matmul parameters a row meets in the
    layers, over the 2 x ``seq_len`` rows of the doubled sequence; 6 x the
    head's parameters over the ``seq_len`` noised rows it runs on; and
    :func:`attn_flops`. Norms, the rotation, softmax, routing (top-k,
    sort, gather, combine), the loss and the optimizer are not counted,
    and nothing recomputed is."""
    rows = 2 * seq_len * batch
    head = config["hidden_size"] * config["vocab_size"]
    return float(6 * _layers(config) * layer_matmul_params(config) * rows
                 + 6 * head * seq_len * batch
                 + attn_flops(config, batch, seq_len))


def rows_per_step(batch, seq_len):
    """Rows every layer runs: both copies of each sequence."""
    return 2 * batch * seq_len


def expected_rows(config, rows):
    """(row, pick) pairs an expert layer routes to this chip under
    uniform routing, of ``rows`` rows (2 x ``seq_len`` a sequence)."""
    return (rows * config["num_experts_per_tok"] * config["num_experts"]
            / config["experts_routed"])


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
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    a_product = held * d + held * f + config["num_experts"] * d * f
    return float(9 * a_product * itemsize * _layers(config))


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rows(config, length):
    """``(copy, position, block)`` of the 2L rows of a doubled sequence:
    copy 0 the noised one."""
    row = jnp.arange(2 * length)
    position = row % length
    return row // length, position, position // config["block_length"]


def _sees(config, length, first, size):
    """(size, 2L) bool: what the query rows ``first .. first + size`` see,
    from each row's copy and block."""
    copy, _, block = _rows(config, length)
    q_copy = jax.lax.dynamic_slice_in_dim(copy, first, size)[:, None]
    q_block = jax.lax.dynamic_slice_in_dim(block, first, size)[:, None]
    k_copy, k_block = copy[None], block[None]
    own = (q_copy == 0) & (k_copy == 0) & (k_block == q_block)
    prefix = (q_copy == 0) & (k_copy == 1) & (k_block < q_block)
    clean = (q_copy == 1) & (k_copy == 1) & (k_block <= q_block)
    return own | prefix | clean


def _rotate(x, position, theta):
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    angle = position.astype(jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(config, p, u, position, sees):
    """Grouped-query attention in blocks of query rows, one block at a
    time (``lax.map``), each under ``jax.checkpoint``: the heads x rows x
    rows float32 scores never exist whole, forward or backward.
    ``position`` (rows,) is each row's rotary position and
    ``sees(first, size)`` the (size, rows) mask of a block of query
    rows."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    kv_heads, head = config["num_key_value_heads"], config["head_dim"]
    group = config["num_attention_heads"] // kv_heads
    batch, rows, _ = u.shape
    q = jnp.einsum("bsd,dhk->bshk", u, p["q"]["kernel"])
    k = jnp.einsum("bsd,dhk->bshk", u, p["k"]["kernel"])
    v = jnp.einsum("bsd,dhk->bshk", u, p["v"]["kernel"])
    q = _rotate(_rms_norm(q, p["q_norm"]["scale"], eps), position, theta)
    k = _rotate(_rms_norm(k, p["k_norm"]["scale"], eps), position, theta)
    q = q.reshape(batch, rows, kv_heads, group, head) / head ** 0.5

    size = ATTENTION_BLOCK if rows % ATTENTION_BLOCK == 0 else rows

    @jax.checkpoint
    def block(args):
        q_rows, first = args
        scores = jnp.einsum("bqngd,bknd->bngqk", q_rows, k)
        probs = jax.nn.softmax(
            jnp.where(sees(first, size), scores, -jnp.inf), -1)
        return jnp.einsum("bngqk,bknd->bqngd", probs, v)

    blocks = q.reshape(batch, rows // size, size, kv_heads, group, head)
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0),
                              jnp.arange(0, rows, size)))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, rows, kv_heads * group, head)
    return jnp.einsum("bshk,hkd->bsd", out, p["o"]["kernel"])


def _swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def _routing(config, p, u):
    """``(gates, picked)``, both (rows, experts_routed): the picked
    probabilities over their sum, zero elsewhere, and the mask of the
    picks. The k picks are k argmaxes, each taken out before the next:
    no sort, no ``top_k``."""
    probs = jax.nn.softmax(u @ p["router"], -1)
    ranked = jax.lax.stop_gradient(probs)
    picked = jnp.zeros(probs.shape, bool)
    for _ in range(config["num_experts_per_tok"]):
        best = jnp.argmax(jnp.where(picked, -jnp.inf, ranked), -1)
        picked = picked | jax.nn.one_hot(best, probs.shape[-1], dtype=bool)
    gates = jnp.where(picked, probs, 0.0)
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates, picked


def _experts(config, p, u, held=None):
    """Every held expert applied to every row, one expert at a time
    (``lax.scan``), masked by the gates (the experts held elsewhere add
    nothing here). Returns the layer's result and its load."""
    first, count = held or _held(config)
    flat = u.reshape(-1, u.shape[-1])
    gates, picked = _routing(config, p, flat)

    @jax.checkpoint
    def one(y, expert):
        w1, w3, w2, gate = expert
        return y + gate[:, None] * _swiglu(flat, w1, w3, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(flat), (
        p["w1"], p["w3"], p["w2"], gates[:, first:first + count].T))
    load = jnp.sum(picked, 0, dtype=jnp.int32)
    return y.reshape(u.shape), {
        "expert_load": load,
        "rows_held": jnp.sum(load[first:first + count])}


def _layer(config, p, x, position, sees):
    eps = config["rms_norm_eps"]
    x = x + _attention(config, p["attn"],
                       _rms_norm(x, p["operator_norm"]["scale"], eps),
                       position, sees)
    y, load = _experts(config, p["moe"],
                       _rms_norm(x, p["ffn_norm"]["scale"], eps))
    return x + y, load


def _head(config, params, x):
    x = _rms_norm(x, params["embedding_norm"]["scale"],
                  config["rms_norm_eps"])
    return x @ params["lm_head"]["kernel"]


def reference_forward(config, params, ids):
    """``(logits, aux)``: float32 logits (batch, L, vocabulary) of the
    noised copy's rows from the doubled ids (batch, 2L), at the highest
    matmul precision, on the system's own parameter tree. No flax, no
    bfloat16, no kernels, no sort. A layer is recomputed in the backward
    pass (``jax.checkpoint``), which changes no number: the full size
    then fits beside the parameters and two sets of gradients."""
    new_aux = {}
    length = ids.shape[1] // 2
    _, position, _ = _rows(config, length)
    sees = lambda first, size: _sees(config, length, first, size)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids]
        for i in range(_layers(config)):
            name = f"block_{i}"
            x, load = jax.checkpoint(lambda p, x: _layer(
                config, p, x, position, sees))(params[name], x)
            new_aux[name] = {"moe": load}
        logits = _head(config, params, x[:, :length])
    return logits, new_aux


def _weighted_loss(config, logits, tokens, masked, rate):
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
    weight = masked / jnp.repeat(rate, config["block_length"], axis=1)
    return -jnp.sum(weight * picked) / tokens.size


def reference_loss(config, params, aux, batch):
    """``(loss, aux)``: the block-diffusion loss of the reference."""
    del aux  # softmax routing carries no state into a step
    tokens, masked, rate = batch
    ids = jnp.concatenate(
        [jnp.where(masked, _mask_id(config), tokens), tokens], 1)
    logits, new_aux = reference_forward(config, params, ids)
    return _weighted_loss(config, logits, tokens, masked, rate), new_aux


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
