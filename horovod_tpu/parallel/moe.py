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
from .. import timeline as _timeline

# Device scopes (docs/timeline.md) of an expert layer: router and top-k;
# the sorts and the gather of token rows to sorted rows; the grouped
# products with what lies between them; gates and the gather back to the
# tokens. ``models/operators.py`` ``HeldExpertsMLP`` puts its router
# product and its casts under them too.
SCOPE_ROUTE = _timeline.scope("moe.route")
SCOPE_DISPATCH = _timeline.scope("moe.dispatch")
SCOPE_EXPERTS = _timeline.scope("moe.experts")
SCOPE_COMBINE = _timeline.scope("moe.combine")

# how each expert layer was traced (docs/metrics.md)
_CALLS = {path: _metrics.MOE_CALLS.bind({"path": path})
          for path in ("held_share", "held_share_short_buffer", "alltoall",
                       "router_softmax", "router_sigmoid_bias")}
_LAST = {what: _metrics.MOE_SHAPE.bind({"what": what})
         for what in ("experts_held", "experts_routed", "top_k",
                      "buffer_rows_short", "router_softmax")}


def route_top_k(router_logits, k: int = 1, renormalize: bool | None = None):
    """Top-k routing: returns ``(expert_idx, gates)`` of shape
    (tokens, k). For k=1 the gate is the RAW top softmax probability
    (Switch Transformer convention) — renormalizing would make it
    identically 1 and sever the router's task-loss gradient; for k>1
    the k gates are renormalized to a convex blend (GShard convention),
    through which router gradients still flow. ``renormalize`` says
    otherwise for either."""
    _CALLS["router_softmax"].inc()
    _LAST["router_softmax"].set(1)
    with SCOPE_ROUTE():
        probs = jax.nn.softmax(router_logits, axis=-1)
        gates, expert_idx = lax.top_k(probs, k)
        if k > 1 if renormalize is None else renormalize:
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
    _CALLS["router_sigmoid_bias"].inc()
    _LAST["router_softmax"].set(0)
    with SCOPE_ROUTE():
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        _, expert_idx = lax.top_k(
            lax.stop_gradient(scores + selection_bias.astype(jnp.float32)),
            k)
        weights = jnp.take_along_axis(scores, expert_idx, axis=-1)
        if renormalize:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
        return expert_idx, weights * scaling


def grouped_matmul(rows, weights, group_sizes):
    """``rows[start_g:end_g] @ weights[g]`` for every group ``g``: rows
    (m, k) sorted by group, weights (groups, k, n) cast to the rows'
    dtype, ``group_sizes`` (groups,) int32. ``jax.lax.ragged_dot``: on a
    TPU the compiler's own grouped kernel (``ragged-dot`` custom calls,
    forward and both gradients), whose time follows ``sum(group_sizes)``
    far more than ``m`` (a quarter of the rows at the same load: 12 %
    less, PERF.md section 6, PR 33). The Pallas ``megablox.gmm`` measured the same in a step and
    10 % faster alone at its best tiling, 4x slower at its default
    (PERF.md section 6, PR 32; ``tools/moe_probe.py`` times both), so
    there is one path, on every backend. Rows past ``sum(group_sizes)``
    belong to no group: treat what comes back for them as undefined and
    mask it. Differentiable in ``rows`` and ``weights``."""
    return lax.ragged_dot(rows, weights.astype(rows.dtype), group_sizes)


def _held_rows(rows, index, rows_held):
    """``rows[index]`` where ``index`` is in ``[0, rows_held)``, zero
    elsewhere: what lies past the load, or outside the rows themselves,
    is never read, and no pass over ``rows`` masks it."""
    held = (index >= 0) & (index < rows_held)
    return rows.at[jnp.where(held, index, rows.shape[0])].get(
        mode="fill", fill_value=0)


def _picks_summed(rows, index, rows_held, gate=None):
    """``sum_k gate[t, k] * rows[index[t, k]]`` in float32, a zero row
    for an ``index`` outside ``[0, rows_held)``. One gather of ``tokens``
    rows a pick, added up: the (tokens, k, d) array of every pair's row
    is never written (0.47 ms against 1.04 for one gather of all the
    pairs and a sum over k, and 1.11 for a ``segment_sum`` of the rows by
    token, at 8192 tokens x 4 picks of 2048 from 8192 rows:
    ``tools/moe_probe.py``, PERF.md section 6, PR 33)."""
    total = 0
    for pick in range(index.shape[1]):
        picked = _held_rows(rows, index[:, pick],
                            rows_held).astype(jnp.float32)
        total += picked if gate is None else gate[:, pick, None] * picked
    return total


@jax.custom_vjp
def _dispatch(x, order, row_of, rows_held):
    """``x[order // k]``, zero from row ``rows_held`` on: row r of the
    result is the token of the pair sorted to r. ``order`` holds the
    pairs of a run of sorted rows (all of them, or a chunk) and
    ``row_of`` (tokens, k) the row of every pair, counted from the run's
    first. The transpose of this gather is a scatter-add; as every row
    belongs to one pair it is also the gather ``g[row_of]`` (zero for a
    pair not held, or one outside the run) summed over each token's k
    picks, which is what the backward runs."""
    rows = x[order // row_of.shape[1]]
    here = jnp.arange(order.shape[0]) < rows_held
    return jnp.where(here[:, None], rows, 0)


def _dispatch_fwd(x, order, row_of, rows_held):
    return _dispatch(x, order, row_of, rows_held), (row_of, rows_held)


def _dispatch_bwd(res, g):
    row_of, rows_held = res
    with SCOPE_DISPATCH():
        return (_picks_summed(g, row_of, rows_held).astype(g.dtype),
                None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, gate, order, row_of, rows_held):
    """``sum_k gate[t, k] * out[row_of[t, k]]``, summed in float32: the
    sorted rows back at their tokens, weighted and summed over each token's
    picks. A pair not held, or one outside the run, reads a zero row (its
    gate is 0 already), so whatever ``out`` holds past ``rows_held`` is
    discarded unread. The backward stays on the sorted side: ``d_out[r]
    = gate[order[r]] * dy[order[r] // k]``, and the gate's gradient is
    one dot product a row, carried to its pair by ``row_of``."""
    return _picks_summed(out, row_of, rows_held, gate.astype(jnp.float32))


def _combine_fwd(out, gate, order, row_of, rows_held):
    return (_combine(out, gate, order, row_of, rows_held),
            (out, gate, order, row_of, rows_held))


def _combine_bwd(res, dy):
    out, gate, order, row_of, rows_held = res
    with SCOPE_COMBINE():
        dy_rows = dy[order // gate.shape[1]]
        d_out = gate.reshape(-1)[order][:, None] * dy_rows
        d_gate_rows = jnp.sum(dy_rows * out.astype(jnp.float32), axis=-1)
        d_gate = _held_rows(d_gate_rows, row_of, rows_held)
        return (d_out.astype(out.dtype), d_gate.astype(gate.dtype),
                None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def short_buffer_rows(pairs: int, count: int, n_routed: int) -> int:
    """Rows of the buffer :func:`moe_held_experts` works on while the
    load fits: twice the load uniform routing gives ``count`` of
    ``n_routed`` experts, rounded up to 512 rows, and never more than
    the ``pairs`` the worst imbalance sends here."""
    return min(pairs, -(-2 * pairs * count // (n_routed * 512)) * 512)


def moe_held_experts(x, expert_idx, weights, expert_fn: Callable, *,
                     first: int, count: int, n_routed: int):
    """This chip's share of an expert layer, without dropping a token.

    ``x`` (tokens, d); ``expert_idx`` / ``weights`` (tokens, k) from a
    router over all ``n_routed`` experts (:func:`route_sigmoid_top_k`,
    :func:`route_top_k`); the chip holds experts ``[first, first +
    count)``. ``expert_fn(rows, group_sizes)`` maps a (buffer, d) array
    of rows sorted by held expert, and the (count,) rows each expert got,
    to (buffer, d_out), through :func:`grouped_matmul` (rows past
    ``sum(group_sizes)`` are not routed here: they go in as zeros, and
    whatever comes back for them is never read).

    Returns ``(y, load)``: ``y`` (tokens, d_out) is ``sum over the held
    experts e a token picked of w_e * expert_e(x)``; what experts held
    elsewhere would add is left out (on several chips their chips add it;
    one chip runs this without an exchange). ``load`` is
    ``{"expert_load": (n_routed,) int32 picks an expert got from these
    tokens, "rows_held": () int32 of them landed here, "buffer_rows": ()
    int32 rows of the buffer this call worked on}``.

    Static shapes, and a buffer as long as the load. The worst imbalance
    sends all ``pairs = tokens * k`` pairs here; uniform routing sends
    ``pairs * count / n_routed``. ``R`` = :func:`short_buffer_rows` is
    twice that, and the layer is a sum over chunks of ``R`` sorted rows:
    the dispatch gathers ``R`` rows, ``expert_fn`` sees ``(R, d)`` and the
    rows each expert has inside the chunk, the mask, the activations and
    every backward pass on the sorted side are ``R`` rows. The two passes
    back to the tokens (the combine, and the dispatch's backward) gather
    ``tokens`` rows a pick and read a zero row for a pair outside the
    chunk or past the load, so no pass masks what ``expert_fn`` returns
    and nothing two-dimensional is ``pairs`` rows long.

    Where ``R == pairs`` (half or more of the experts held) there is the
    one chunk and nothing else. Otherwise the first chunk always runs,
    as plain code the compiler schedules with the rest of the step, and
    holds every routed row while ``rows_held <= R``; a loop whose trip
    count is read from the load (``ceil(rows_held / R) - 1``: none, then)
    takes the chunks after it. Reverse mode cannot differentiate such a
    loop, so the two together are one ``jax.custom_vjp``: the forward
    keeps the first chunk's residuals and the operands; the backward
    runs the first chunk's, then the same loop, which computes each
    later chunk's forward again and adds its gradients. So a step holds
    one chunk's residuals, the working set past them is one chunk's, and
    an imbalance costs in proportion to the rows it sends. Nothing is
    dropped or capped at any load, and ``buffer_rows`` says how far the
    step went. ``R`` is a
    function of the shapes and of ``count / n_routed`` alone: no
    argument, variable or field selects it.

    ``expert_fn`` is closure-converted (``jax.closure_convert``): the
    floating arrays it closes over become arguments of the
    ``custom_vjp`` and get their gradients; cast weights to the compute
    dtype before, not inside, so that what is kept between the passes is
    the cast. No collective may sit inside ``expert_fn``: on several
    chips each chip's loop takes its own count of turns.
    """
    tokens, _ = x.shape
    k = expert_idx.shape[1]
    pairs = tokens * k
    short = short_buffer_rows(pairs, count, n_routed)
    _CALLS["held_share"].inc()
    if short < pairs:
        _CALLS["held_share_short_buffer"].inc()
    for what, value in (("experts_held", count),
                        ("experts_routed", n_routed), ("top_k", k),
                        ("buffer_rows_short", short if short < pairs else 0)):
        _LAST[what].set(value)

    with SCOPE_DISPATCH():
        flat = expert_idx.reshape(pairs)            # token-major pairs
        local = flat - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)         # elsewhere: sorts last
        order = jnp.argsort(key, stable=True)       # row -> pair
        inverse = jnp.argsort(order)                # pair -> row
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=key.dtype)[None],
            axis=0, dtype=jnp.int32)
        rows_held = jnp.sum(group_sizes)
    with SCOPE_COMBINE():
        gate = jnp.where(held.reshape(tokens, k), weights, 0)

    if short == pairs:
        experts, consts = expert_fn, ()
    else:
        experts, consts = jax.closure_convert(
            expert_fn, jax.ShapeDtypeStruct((short, x.shape[1]), x.dtype),
            group_sizes)

    def chunk(start, x, gate, consts, order, inverse, group_sizes):
        """What the sorted rows ``[start, start + short)`` add to ``y``."""
        with SCOPE_DISPATCH():
            ends = jnp.cumsum(group_sizes)
            begins = jnp.clip(ends - group_sizes, start, start + short)
            sizes = jnp.clip(ends, start, start + short) - begins
            held_here = jnp.sum(sizes)
            order = lax.dynamic_slice_in_dim(order, start, short)
            row_of = (inverse - start).reshape(tokens, k)   # of the chunk
            rows = _dispatch(x, order, row_of, held_here)
        with SCOPE_EXPERTS():
            out = experts(rows, sizes, *consts)
        with SCOPE_COMBINE():
            return _combine(out, gate.astype(out.dtype), order, row_of,
                            held_here).astype(out.dtype)

    if short == pairs:
        y = chunk(0, x, gate, consts, order, inverse, group_sizes)
        chunks_run = 1
    else:
        with SCOPE_DISPATCH():
            chunks_run = jnp.maximum(-(-rows_held // short), 1)
            # the last chunk may reach past the pairs: any pair will do
            # there
            ints = (jnp.pad(order, (0, -pairs % short)), inverse,
                    group_sizes)

        # One trace each of a chunk's forward with its residuals and of
        # its backward from them: the first chunk and the loops' bodies
        # call the same two programs (the forward loop drops the
        # residuals, and the compiler what computes them).
        @jax.jit
        def chunk_vjp(start, x, gate, consts, ints):
            return jax.vjp(lambda x, gate, consts: chunk(
                start, x, gate, consts, *ints), x, gate, consts)

        @jax.jit
        def chunk_grads(vjp, dy):
            return vjp(dy)

        @jax.custom_vjp
        def chunks(x, gate, consts, ints, chunks_run):
            return chunks_fwd(x, gate, consts, ints, chunks_run)[0]

        def chunks_fwd(x, gate, consts, ints, chunks_run):
            y, first_vjp = chunk_vjp(jnp.int32(0), x, gate, consts, ints)
            y = lax.fori_loop(
                1, chunks_run,
                lambda c, y: y + chunk_vjp(c * short, x, gate, consts,
                                           ints)[0], y)
            return y, (first_vjp, x, gate, consts, ints, chunks_run)

        def chunks_bwd(res, dy):
            first_vjp, x, gate, consts, ints, chunks_run = res

            def add_chunk(c, grads):
                _, vjp = chunk_vjp(c * short, x, gate, consts, ints)
                return jax.tree.map(jnp.add, grads, chunk_grads(vjp, dy))

            return (*lax.fori_loop(1, chunks_run, add_chunk,
                                   chunk_grads(first_vjp, dy)), None, None)

        chunks.defvjp(chunks_fwd, chunks_bwd)
        # a chunk's three parts name themselves; what adds the chunks'
        # results (and, backward, their gradients) up goes with the combine
        with SCOPE_COMBINE():
            y = chunks(x, gate, tuple(consts), ints, chunks_run)
    with SCOPE_DISPATCH():
        buffer_rows = jnp.minimum(chunks_run * short,
                                  pairs).astype(jnp.int32)
        load = {
            "expert_load": jnp.sum(
                flat[:, None] == jnp.arange(n_routed,
                                            dtype=flat.dtype)[None],
                axis=0, dtype=jnp.int32),
            "rows_held": rows_held,
            "buffer_rows": buffer_rows,
        }
    return y, load
