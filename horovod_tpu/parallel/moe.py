"""Expert parallelism: Mixture-of-Experts dispatch/combine over alltoall.

The reference stops at the ``alltoall`` primitive (``operations.cc:1642``)
— SURVEY.md §2.3 marks expert parallelism "primitive only". This module
makes the MoE schedule itself first-class: top-k routing, a
capacity-bounded dispatch (Switch/GShard style — static shapes, overflow
tokens dropped), one shape-preserving ``lax.all_to_all`` to move each
token to its expert's chip, the expert computation on local tokens, the
inverse exchange, and the gate-weighted combine. One expert group lives
on each chip of the mesh axis; everything runs inside ``jax.shard_map``
and differentiates end-to-end (router gradients flow through the gate
weighting, the standard trick).

    def expert_fn(tokens):           # (N, d) on this chip's expert
        return nn.relu(tokens @ w_in) @ w_out

    y, aux = moe_alltoall(x, router_logits, expert_fn, axis)
    loss = task_loss(y) + 0.01 * aux  # Switch load-balance auxiliary

When the experts outnumber the chips, a chip holds several and is told
which: :func:`moe_held_experts` routes over all of them, sorts the
(token, pick) pairs that fall on its own ``[first, first + count)`` by
expert, and computes their part of the result with grouped matrix
products (:func:`grouped_matmul`). Nothing is dropped under any
imbalance; what the absent experts would add is left out (their chips
add it, through the exchange this module does not do yet). Routing for
it: :func:`route_sigmoid_top_k` (sigmoid scores, a selection bias that
takes no gradient, weights renormalised over the picks).
docs/expert_parallel.md has both layers.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from .. import metrics as _metrics

# how each expert layer was traced (docs/metrics.md)
_CALLS = {path: _metrics.MOE_CALLS.bind({"path": path})
          for path in ("held_share", "alltoall")}
_LAST = {what: _metrics.MOE_SHAPE.bind({"what": what})
         for what in ("experts_held", "experts_routed", "top_k")}


def route_top_k(router_logits, k: int = 1):
    """Top-k routing: returns ``(expert_idx, gates)`` of shape
    (tokens, k). For k=1 the gate is the RAW top softmax probability
    (Switch Transformer convention) — renormalizing would make it
    identically 1 and sever the router's task-loss gradient; for k>1
    the k gates are renormalized to a convex blend (GShard convention),
    through which router gradients still flow."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, expert_idx = lax.top_k(probs, k)
    if k > 1:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True),
                                    1e-9)
    return expert_idx, gates


def load_balance_loss(router_logits, expert_idx) -> jax.Array:
    """Switch Transformer auxiliary loss (eq. 4): n_expert times the dot
    of (fraction of tokens routed to e, mean router probability of e) —
    minimized by a uniform assignment."""
    n_expert = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits, axis=-1)
    onehot = jax.nn.one_hot(expert_idx[..., 0], n_expert,
                            dtype=probs.dtype)  # primary expert
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return n_expert * jnp.sum(frac_tokens * frac_probs)


def moe_alltoall(x, router_logits, expert_fn: Callable, axis, *,
                 k: int = 1, capacity: int | None = None,
                 capacity_factor: float = 1.25):
    """Route this chip's tokens through the mesh's experts and back.

    Inside ``shard_map`` with one expert (group) per chip of ``axis``:
    ``x`` (tokens, d) and ``router_logits`` (tokens, n_expert) are this
    chip's shard; ``expert_fn`` maps (N, d) -> (N, d_out) using THIS
    chip's expert parameters. Returns ``(y, aux)`` where ``y``
    (tokens, d_out) is the gate-weighted combine of each token's k expert
    outputs (dropped overflow tokens contribute zero, as in
    Switch/GShard) and ``aux`` the load-balance loss.

    ``capacity`` bounds tokens per (source chip, expert) pair; default
    ``ceil(capacity_factor * k * tokens / n_expert)``, floored at 4 so
    tiny shards keep a usable bucket.
    """
    _CALLS["alltoall"].inc()
    tokens, d = x.shape
    n_expert = int(lax.psum(1, axis))
    if router_logits.shape != (tokens, n_expert):
        raise ValueError(
            f"router_logits shape {router_logits.shape} != "
            f"({tokens}, axis size {n_expert})")
    if capacity is None:
        capacity = max(math.ceil(capacity_factor * k * tokens / n_expert),
                       4)

    expert_idx, gates = route_top_k(router_logits, k)

    # flatten the (token, pick) pairs and slot each into its expert's
    # capacity bucket in routing-priority order (pick 0 first)
    flat_expert = expert_idx.T.reshape(-1)          # (k*tokens,) pick-major
    flat_token = jnp.tile(jnp.arange(tokens), k)
    flat_gate = gates.T.reshape(-1)
    onehot = jax.nn.one_hot(flat_expert, n_expert, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = pos < capacity
    pos = jnp.minimum(pos, capacity - 1)

    dispatch = jnp.zeros((n_expert, capacity, d), x.dtype)
    dispatch = dispatch.at[flat_expert, pos].add(
        jnp.where(keep[:, None], x[flat_token], 0))

    # exchange: row s of this chip's buffer is now the bucket chip s
    # addressed to this chip's expert
    recv = lax.all_to_all(dispatch, axis, split_axis=0, concat_axis=0,
                          tiled=True)               # (n_src, capacity, d)
    out = expert_fn(recv.reshape(n_expert * capacity, d))
    d_out = out.shape[-1]
    out = out.reshape(n_expert, capacity, d_out)

    # inverse exchange: each chip's buckets come home, expert-major again
    back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                          tiled=True)               # (n_expert, cap, d_out)

    picked = back[flat_expert, pos] * \
        jnp.where(keep, flat_gate, 0)[:, None]      # (k*tokens, d_out)
    y = jnp.sum(picked.reshape(k, tokens, d_out), axis=0)
    return y, load_balance_loss(router_logits, expert_idx)


# --------------------------------------------------------------------------
# a chip's share of the experts: dropless, sorted rows, grouped products
# --------------------------------------------------------------------------

def route_sigmoid_top_k(router_logits, selection_bias, k: int, *,
                        renormalize: bool = True, scaling: float = 1.0):
    """Sigmoid routing with a selection bias: ``(expert_idx, weights)`` of
    shape (tokens, k). Scores are ``sigmoid(router_logits)`` in float32;
    the k experts are the top-k of ``scores + selection_bias``; the bias
    takes part in the selection only (no gradient reaches it, and the
    weights are the unbiased scores); ``renormalize`` divides the k
    picked scores by their sum (+1e-6), ``scaling`` multiplies them.
    Router gradients flow through the weights."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    _, expert_idx = lax.top_k(
        lax.stop_gradient(scores + selection_bias.astype(jnp.float32)), k)
    weights = jnp.take_along_axis(scores, expert_idx, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return expert_idx, weights * scaling


def grouped_matmul(rows, weights, group_sizes):
    """``rows[start_g:end_g] @ weights[g]`` for every group ``g``: rows
    (m, k) sorted by group, weights (groups, k, n) cast to the rows'
    dtype, ``group_sizes`` (groups,) int32. ``jax.lax.ragged_dot``: on a
    TPU the compiler's own grouped kernel (``ragged-dot`` custom calls,
    forward and both gradients), whose time follows ``sum(group_sizes)``,
    not ``m``. The Pallas ``megablox.gmm`` measured the same in a step and
    10 % faster alone at its best tiling, 4x slower at its default
    (PERF.md section 6, PR 32; ``tools/moe_probe.py`` times both), so
    there is one path, on every backend. Rows past ``sum(group_sizes)``
    belong to no group: treat what comes back for them as undefined and
    mask it. Differentiable in ``rows`` and ``weights``."""
    return lax.ragged_dot(rows, weights.astype(rows.dtype), group_sizes)


@jax.custom_vjp
def _dispatch(x, order, inverse):
    """``x[order // k]``: row r of the result is the token of the pair
    sorted to r. The transpose of this gather is a scatter-add; as
    ``order`` is a permutation of the pairs it is also the gather
    ``g[inverse]`` summed over each token's k picks, which is what the
    backward runs."""
    return x[order // (order.shape[0] // x.shape[0])]


def _dispatch_fwd(x, order, inverse):
    return _dispatch(x, order, inverse), (inverse, x.shape[0])


def _dispatch_bwd(res, g):
    inverse, tokens = res
    return (jnp.sum(g[inverse].reshape(tokens, -1, g.shape[-1]), axis=1),
            None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(rows, order, inverse):
    """``rows[inverse]``: sorted rows back in pair order. ``inverse`` is
    a permutation, so the transpose is the gather ``g[order]``."""
    return rows[inverse]


def _unsort_fwd(rows, order, inverse):
    return rows[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def moe_held_experts(x, expert_idx, weights, expert_fn: Callable, *,
                     first: int, count: int, n_routed: int):
    """This chip's share of an expert layer, without dropping a token.

    ``x`` (tokens, d); ``expert_idx`` / ``weights`` (tokens, k) from a
    router over all ``n_routed`` experts (:func:`route_sigmoid_top_k`,
    :func:`route_top_k`); the chip holds experts ``[first, first +
    count)``. ``expert_fn(rows, group_sizes)`` maps the (tokens * k, d)
    buffer of rows sorted by held expert, and the (count,) rows each
    expert got, to (tokens * k, d_out), through :func:`grouped_matmul`
    (rows past ``sum(group_sizes)`` are not routed here: they go in as
    zeros, and whatever comes back for them is discarded).

    Returns ``(y, load)``: ``y`` (tokens, d_out) is ``sum over the held
    experts e a token picked of w_e * expert_e(x)``; what experts held
    elsewhere would add is left out (on several chips their chips add it;
    one chip runs this without an exchange). ``load`` is
    ``{"expert_load": (n_routed,) int32 picks an expert got from these
    tokens, "rows_held": () int32 of them landed here}``.

    Static shapes: the buffer has room for every pair (the worst
    imbalance), the grouped products visit only the rows routed here, so
    their cost follows the load (expected ``tokens * k * count /
    n_routed``), the gathers and elementwise passes the buffer.
    """
    tokens, _ = x.shape
    k = expert_idx.shape[1]
    pairs = tokens * k
    _CALLS["held_share"].inc()
    for what, value in (("experts_held", count),
                        ("experts_routed", n_routed), ("top_k", k)):
        _LAST[what].set(value)

    flat = expert_idx.reshape(pairs)                # token-major pairs
    local = flat - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count)             # elsewhere: sorts last
    order = jnp.argsort(key, stable=True)           # row -> pair
    inverse = jnp.argsort(order)                    # pair -> row
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=key.dtype)[None],
        axis=0, dtype=jnp.int32)
    rows_held = jnp.sum(group_sizes)
    here = (jnp.arange(pairs) < rows_held)[:, None]

    rows = jnp.where(here, _dispatch(x, order, inverse), 0)
    out = jnp.where(here, expert_fn(rows, group_sizes), 0)
    back = _unsort(out, order, inverse).reshape(tokens, k, out.shape[-1])
    gate = jnp.where(held.reshape(tokens, k), weights, 0).astype(out.dtype)
    y = jnp.einsum("tk,tkd->td", gate, back)
    load = {
        "expert_load": jnp.sum(
            flat[:, None] == jnp.arange(n_routed, dtype=flat.dtype)[None],
            axis=0, dtype=jnp.int32),
        "rows_held": rows_held,
    }
    return y, load
