"""The block-diffusion training objective (BD3-LMs, arXiv:2503.09573) for
``TransformerLM`` under ``attn_mask="block_diffusion"``: what goes in, and
the loss on what comes out.

A sequence of L tokens ``x0`` is cut into blocks of ``block_length``. Each
block draws a rate ``t``, and each of its tokens is masked with
probability ``t``: ``xt`` holds the mask id there and ``x0`` elsewhere.
The model runs both copies in one pass, ``xt`` then ``x0`` (2L rows), under
the mask of ``models/transformer.py`` ``block_diffusion_mask``: a noised
block sees itself and the clean blocks before it, so one pass gives every
block's prediction given its clean prefix. The logits of the noised
copy's row i predict token i itself; the loss is over the masked rows,
each weighted by ``1 / t`` of its block.

    ids, positions = doubled_inputs(tokens, masked, mask_id)
    logits = model.apply(variables, ids, positions)       # (batch, L, V)
    loss = masked_token_loss(logits, tokens, masked, rate, block_length)

Drawing ``masked`` and ``rate`` is the caller's (a schedule is a choice
of the training job: ``benchmark/models/sdar.py`` draws rates uniform on
[1e-3, 1]).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def doubled_inputs(tokens, masked, mask_id):
    """``(ids, positions)``, both (batch, 2L) int32: the noised copy
    (``mask_id`` where ``masked``) then the clean copy, and each row's
    position, which both copies count from 0."""
    length = tokens.shape[1]
    ids = jnp.concatenate([jnp.where(masked, mask_id, tokens), tokens], 1)
    at = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32), tokens.shape)
    return ids.astype(jnp.int32), jnp.concatenate([at, at], 1)


def masked_token_loss(logits, tokens, masked, rate, block_length):
    """``mean over the batch of (1 / L) sum over masked i of
    -log softmax(logits[i])[tokens[i]] / rate[block of i]``: ``logits``
    (batch, L, V) float32 of the noised copy, ``masked`` (batch, L) bool,
    ``rate`` (batch, L / block_length) the rate each block was masked
    at."""
    logp = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    weight = masked / jnp.repeat(rate, block_length, axis=1)
    return -jnp.mean(weight * picked)
