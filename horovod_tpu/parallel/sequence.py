"""Sequence/context parallel attention schedules.

Long-context training shards the *sequence* dimension over chips; the two
standard schedules are both built from the framework's collective
primitives (the reference exposes the primitives but no schedule,
SURVEY.md §5.7):

* **Ring attention** (Liu et al. 2023): keep Q resident, rotate K/V
  blocks around a ``ppermute`` ring, accumulate with the online-softmax
  (flash-attention) recurrence. Per-step the ring moves one KV block over
  ICI while the MXU works on the previous one; attention *logits* never
  materialize (O(block²) working set instead of O(seq²)). Training
  memory is O(block) too: the backward is a **re-rotating recompute VJP**
  (``_ring_core``'s custom_vjp) — the forward saves only this chip's home
  Q/K/V blocks plus (out, lse); the backward restarts the ring from the
  home blocks and rotates dK/dV accumulators around with them, so no
  per-step K/V residuals ever accumulate. Causal runs also skip the
  attention math for blocks that are entirely in the future of the local
  Q block (a ``lax.cond``), recovering the ~2x FLOP overhead a naive
  causal ring wastes on fully-masked blocks.
* **Ulysses** (Jacobs et al. 2023): two ``all_to_all``\\ s reshard
  (seq-sharded, heads-full) → (seq-full, heads-sharded), run exact local
  attention over the full sequence, and reshard back. Cheaper collectives
  for moderate sequence lengths; requires ``num_heads %% axis_size == 0``.

Everything here runs inside ``jax.shard_map`` with the sequence axis
bound; tensors use the (batch, seq, heads, head_dim) layout of
:mod:`horovod_tpu.models.transformer`. Both paths are differentiable
(``ppermute``/``all_to_all`` have transposes), so they drop into training
steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import timeline as _timeline

# Device scopes (docs/timeline.md) of local attention in flash form: the
# transposes to the kernels' (bh, s, d) rows and back; the kernels' calls
# with their lse / D passes, and the same under a sliding window; the
# block-diffusion mask's own-block part. ``models/transformer.py`` puts
# what else lies between the projections and the kernels under the first,
# the materialised path under the second (the third under a window).
SCOPE_PREPARE = _timeline.scope("attention.prepare")
SCOPE_KERNEL = _timeline.scope("attention.kernel")
SCOPE_WINDOW = _timeline.scope("attention.window")
_OWN_BLOCK = _timeline.scope("attention.own_block")

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax
                 # rows finite (all-masked blocks produce 0 contributions)


def _ring_fwd_loop(qf, kf, vf, axis, causal, use_pallas, interpret):
    """Run the forward ring, returning normalized output and log-sum-exp.

    ``qf`` pre-scaled, (bh, sq, d); ``kf``/``vf`` (bh, sk, d) home blocks.
    Causal steps whose KV block lies entirely in the future of the local Q
    block skip the attention math through a ``lax.cond`` (the ppermute
    still runs so the ring stays aligned).
    """
    from ..ops import flash

    n = int(lax.psum(1, axis))
    my = lax.axis_index(axis)
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    m = jnp.full((bh, sq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bh, sq, 1), jnp.float32)
    acc = jnp.zeros((bh, sq, d), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]  # ring: send to next rank
    k_cur, v_cur = kf, vf
    for step in range(n):
        kv_idx = (my - step) % n  # block held at this step
        qpos0 = (my * sq).astype(jnp.int32)
        kpos0 = (kv_idx * sk).astype(jnp.int32)

        def attend(carry, _k=k_cur, _v=v_cur, _qp=qpos0, _kp=kpos0):
            m, l, acc = carry
            if use_pallas or interpret:
                return flash.block_attend(qf, _k, _v, _qp, _kp, causal,
                                          interpret, m, l, acc)
            return flash._attend_jnp(qf, _k, _v, _qp, _kp, causal,
                                     m, l, acc)

        if causal:
            # block entirely in the future of every local query row:
            # contributes nothing — skip its FLOPs at runtime
            fully_future = kpos0 > qpos0 + (sq - 1)
            m, l, acc = lax.cond(fully_future, lambda c: c, attend,
                                 (m, l, acc))
        else:
            m, l, acc = attend((m, l, acc))
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
    l_safe = jnp.maximum(l, 1e-30)
    return acc / l_safe, m + jnp.log(l_safe)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_core(qf, kf, vf, axis, causal, use_pallas, interpret):
    """Differentiable ring-attention core with O(block) training memory.

    Returns ``(out, lse)`` where ``out`` is the normalized attention
    output (float32) and ``lse`` the per-row log-sum-exp. The custom VJP
    saves ONLY the home blocks + (out, lse) — never the rotated per-step
    K/V blocks (which a plain ``jax.vjp`` through the loop would pin,
    making per-chip K/V activation memory O(sequence),
    the round-3 gap)."""
    return _ring_fwd_loop(qf, kf, vf, axis, causal, use_pallas, interpret)


def _ring_core_fwd(qf, kf, vf, axis, causal, use_pallas, interpret):
    out, lse = _ring_fwd_loop(qf, kf, vf, axis, causal, use_pallas,
                              interpret)
    # O(block) residuals: home Q/K/V + out + lse. Nothing per-step.
    return (out, lse), (qf, kf, vf, out, lse)


def _ring_core_bwd(axis, causal, use_pallas, interpret, res, cts):
    """Re-rotating backward: restart the ring from the home K/V blocks and
    carry dK/dV accumulators around with them. Uses the flash backward
    identities on the normalized softmax (p = exp(s - lse)):
    dV += pᵀ·dO, dS = p ∘ (dO·Vᵀ − D), dQ += dS·K, dK += dSᵀ·Q with
    D = rowsum(dO ∘ O). After n rotations each block's accumulator is back
    on its home rank, so the returned cotangents line up with the inputs.
    """
    qf, kf, vf, out, lse = res
    dout, _dlse = cts  # lse is a diagnostic output; its cotangent is zero
    dout = dout.astype(jnp.float32)
    n = int(lax.psum(1, axis))
    my = lax.axis_index(axis)
    bh, sq, d = qf.shape
    sk = kf.shape[1]
    D = jnp.sum(dout * out, axis=-1, keepdims=True)  # (bh, sq, 1)

    dq = jnp.zeros((bh, sq, d), jnp.float32)
    dk_acc = jnp.zeros((bh, sk, d), jnp.float32)
    dv_acc = jnp.zeros((bh, sk, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = kf, vf
    for step in range(n):
        kv_idx = (my - step) % n
        qpos0 = (my * sq).astype(jnp.int32)
        kpos0 = (kv_idx * sk).astype(jnp.int32)

        def block_grads(carry, _k=k_cur, _v=v_cur, _qp=qpos0, _kp=kpos0):
            from ..ops import flash

            dq, dk_a, dv_a = carry
            if use_pallas or interpret:
                # pallas backward: logits recomputed per tile in VMEM,
                # never materialized at O(sq*sk) in HBM
                dq_blk, dk_blk, dv_blk = flash.flash_block_grads(
                    qf, _k, _v, lse, dout, D, _qp, _kp, causal,
                    interpret=interpret)
            else:
                dq_blk, dk_blk, dv_blk = flash.jnp_block_grads(
                    qf, _k, _v, lse, dout, D, _qp, _kp, causal)
            return dq + dq_blk, dk_a + dk_blk, dv_a + dv_blk

        if causal:
            fully_future = kpos0 > qpos0 + (sq - 1)
            dq, dk_acc, dv_acc = lax.cond(
                fully_future, lambda c: c, block_grads, (dq, dk_acc, dv_acc))
        else:
            dq, dk_acc, dv_acc = block_grads((dq, dk_acc, dv_acc))

        # dK/dV travel WITH their block; the extra nth rotation (vs the
        # forward's n-1) returns every accumulator to its home rank.
        dk_acc = lax.ppermute(dk_acc, axis, perm)
        dv_acc = lax.ppermute(dv_acc, axis, perm)
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
    return (dq.astype(qf.dtype), dk_acc.astype(kf.dtype),
            dv_acc.astype(vf.dtype))


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


# --------------------------------------------------------------------------
# zigzag schedule: causal load balance.
#
# With contiguous blocks, the fully_future skip halves causal FLOPs but
# not wall-clock: at ring step s only ranks r >= s have work, yet every
# step still waits on a full block attend somewhere (rank n-1 works at
# EVERY step). The zigzag assignment (Liu et al.'s ring + the zigzag
# chunking used by zigzag ring/striped attention) splits the sequence
# into 2n chunks and hands rank r chunks (r, 2n-1-r); at every step every
# rank then does ~2 of its 4 (q-chunk, kv-chunk) sub-blocks — the causal
# 2x shows up in latency, not just energy.
# --------------------------------------------------------------------------


def _zig_rank_of(chunk: int, n: int) -> int:
    """Which rank owns global chunk id ``chunk`` in zigzag layout."""
    return chunk if chunk < n else 2 * n - 1 - chunk


def zigzag_shard(x, axis):
    """Convert a contiguous shard_map sequence block (dim 1) to the zigzag
    layout: rank r's (low, high) halves become global chunks (r, 2n-1-r).
    Two half-block ppermutes; inverse is :func:`zigzag_unshard`."""
    n = int(lax.psum(1, axis))
    my = lax.axis_index(axis)
    c = x.shape[1] // 2
    # rank r holds contiguous chunks (2r, 2r+1); route each to its owner
    perm_even = [(r, _zig_rank_of(2 * r, n)) for r in range(n)]
    perm_odd = [(r, _zig_rank_of(2 * r + 1, n)) for r in range(n)]
    recv_even = lax.ppermute(x[:, :c], axis, perm_even)   # even chunk ids
    recv_odd = lax.ppermute(x[:, c:], axis, perm_odd)     # odd chunk ids
    # my low chunk id is `my` (parity of `my` says which ppermute brought
    # it); my high chunk id 2n-1-my has the opposite parity
    even_is_low = (my % 2 == 0)
    low = jnp.where(even_is_low, recv_even, recv_odd)
    high = jnp.where(even_is_low, recv_odd, recv_even)
    return jnp.concatenate([low, high], axis=1)


def zigzag_unshard(x, axis):
    """Inverse of :func:`zigzag_shard`."""
    n = int(lax.psum(1, axis))
    my = lax.axis_index(axis)
    c = x.shape[1] // 2
    low, high = x[:, :c], x[:, c:]
    # my even-id chunk is `my` (low) when my is even, else 2n-1-my (high)
    even_is_low = (my % 2 == 0)
    payload_even = jnp.where(even_is_low, low, high)
    payload_odd = jnp.where(even_is_low, high, low)
    perm_even = [(_zig_rank_of(2 * r, n), r) for r in range(n)]
    perm_odd = [(_zig_rank_of(2 * r + 1, n), r) for r in range(n)]
    first = lax.ppermute(payload_even, axis, perm_even)   # chunk 2r
    second = lax.ppermute(payload_odd, axis, perm_odd)    # chunk 2r+1
    return jnp.concatenate([first, second], axis=1)


def _zig_halves(block, c):
    return block[:, :c], block[:, c:]


def _zig_positions(qi, ki, my, kv_rank, n, c):
    """Global token offsets of this rank's q-half ``qi`` and the arriving
    block's kv-half ``ki`` (chunk ids: low = rank, high = 2n-1-rank);
    ``qi``/``ki`` are Python ints, ``my``/``kv_rank`` traced scalars."""
    q_chunk = my if qi == 0 else 2 * n - 1 - my
    kv_chunk = kv_rank if ki == 0 else 2 * n - 1 - kv_rank
    return ((q_chunk * c).astype(jnp.int32),
            (kv_chunk * c).astype(jnp.int32))


def _zig_attend_step(qf, k_cur, v_cur, carries, my, kv_rank, n, use_pallas,
                     interpret):
    """One zigzag ring step: 4 (q-half, kv-half) causal sub-attends, each
    skipped entirely when the kv chunk is in the q chunk's future."""
    from ..ops import flash

    c = qf.shape[1] // 2
    q_halves = _zig_halves(qf, c)
    k_halves = _zig_halves(k_cur, c)
    v_halves = _zig_halves(v_cur, c)
    out = list(carries)
    for qi in range(2):
        for ki in range(2):
            m, l, acc = out[qi]
            qh, kh, vh = q_halves[qi], k_halves[ki], v_halves[ki]
            qpos0, kpos0 = _zig_positions(qi, ki, my, kv_rank, n, c)

            def attend(carry, _k=kh, _v=vh, _qp=qpos0, _kp=kpos0, _q=qh):
                m, l, acc = carry
                if use_pallas or interpret:
                    return flash.block_attend(_q, _k, _v, _qp, _kp, True,
                                              interpret, m, l, acc)
                return flash._attend_jnp(_q, _k, _v, _qp, _kp, True,
                                         m, l, acc)

            fully_future = kpos0 > qpos0 + (c - 1)
            out[qi] = lax.cond(fully_future, lambda cr: cr, attend,
                               (m, l, acc))
    return out


def _zigzag_fwd_loop(qf, kf, vf, axis, use_pallas, interpret):
    n = int(lax.psum(1, axis))
    my = lax.axis_index(axis)
    bh, sq, d = qf.shape
    c = sq // 2

    carries = [(jnp.full((bh, c, 1), NEG_INF, jnp.float32),
                jnp.zeros((bh, c, 1), jnp.float32),
                jnp.zeros((bh, c, d), jnp.float32)) for _ in range(2)]
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = kf, vf
    for step in range(n):
        kv_rank = (my - step) % n
        carries = _zig_attend_step(qf, k_cur, v_cur, carries, my, kv_rank,
                                   n, use_pallas, interpret)
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
    outs, lses = [], []
    for m, l, acc in carries:
        l_safe = jnp.maximum(l, 1e-30)
        outs.append(acc / l_safe)
        lses.append(m + jnp.log(l_safe))
    return (jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _zigzag_core(qf, kf, vf, axis, use_pallas, interpret):
    """Differentiable zigzag ring core (causal only), O(block) residuals
    like :func:`_ring_core`."""
    return _zigzag_fwd_loop(qf, kf, vf, axis, use_pallas, interpret)


def _zigzag_core_fwd(qf, kf, vf, axis, use_pallas, interpret):
    out, lse = _zigzag_fwd_loop(qf, kf, vf, axis, use_pallas, interpret)
    return (out, lse), (qf, kf, vf, out, lse)


def _zigzag_core_bwd(axis, use_pallas, interpret, res, cts):
    """Re-rotating recompute backward over zigzag sub-blocks: dK/dV
    accumulators rotate with their blocks, dQ halves accumulate locally
    (mirrors :func:`_ring_core_bwd`)."""
    from ..ops import flash

    qf, kf, vf, out, lse = res
    dout, _dlse = cts
    dout = dout.astype(jnp.float32)
    n = int(lax.psum(1, axis))
    my = lax.axis_index(axis)
    bh, sq, d = qf.shape
    c = sq // 2
    D = jnp.sum(dout * out, axis=-1, keepdims=True)

    dq = jnp.zeros((bh, sq, d), jnp.float32)
    dk_acc = jnp.zeros((bh, sq, d), jnp.float32)
    dv_acc = jnp.zeros((bh, sq, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = kf, vf
    for step in range(n):
        kv_rank = (my - step) % n
        for qi in range(2):
            for ki in range(2):
                qs = slice(qi * c, (qi + 1) * c)
                ks = slice(ki * c, (ki + 1) * c)
                qpos0, kpos0 = _zig_positions(qi, ki, my, kv_rank, n, c)

                def grads(carry, _qs=qs, _ks=ks, _qp=qpos0, _kp=kpos0,
                          _k=k_cur, _v=v_cur):
                    dq, dk_a, dv_a = carry
                    fn = (flash.flash_block_grads
                          if (use_pallas or interpret)
                          else flash.jnp_block_grads)
                    kwargs = ({"interpret": interpret}
                              if (use_pallas or interpret) else {})
                    dq_b, dk_b, dv_b = fn(
                        qf[:, _qs], _k[:, _ks], _v[:, _ks], lse[:, _qs],
                        dout[:, _qs], D[:, _qs], _qp, _kp, True, **kwargs)
                    return (dq.at[:, _qs].add(dq_b),
                            dk_a.at[:, _ks].add(dk_b),
                            dv_a.at[:, _ks].add(dv_b))

                fully_future = kpos0 > qpos0 + (c - 1)
                dq, dk_acc, dv_acc = lax.cond(
                    fully_future, lambda cr: cr, grads, (dq, dk_acc, dv_acc))
        # dK/dV travel WITH their block; the extra nth rotation returns
        # every accumulator home
        dk_acc = lax.ppermute(dk_acc, axis, perm)
        dv_acc = lax.ppermute(dv_acc, axis, perm)
        if step != n - 1:
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
    return (dq.astype(qf.dtype), dk_acc.astype(kf.dtype),
            dv_acc.astype(vf.dtype))


_zigzag_core.defvjp(_zigzag_core_fwd, _zigzag_core_bwd)


def ring_attention(q, k, v, axis, *, causal: bool = True,
                   use_pallas: bool | None = None,
                   interpret: bool = False,
                   schedule: str = "contiguous"):
    """Blockwise ring attention over mesh axis ``axis``.

    Inside ``shard_map`` with the sequence dimension sharded over
    ``axis``: ``q``/``k``/``v`` are this chip's (batch, seq_block, heads,
    head_dim) blocks. K/V rotate around the ring; after ``axis_size``
    steps every Q block has attended to the full sequence. Returns this
    chip's output block (same shape as ``q``).

    The per-step block update runs through the Pallas flash kernel
    (:mod:`horovod_tpu.ops.flash`) on TPU — logits never touch HBM — and
    through the jnp formulation elsewhere. ``use_pallas`` forces the
    choice; ``interpret`` runs the kernel in interpreter mode (CPU tests).
    Differentiating through this saves O(block) residuals (re-rotating
    recompute backward, :func:`_ring_core_bwd`), so per-chip training
    memory stays flat as the ring grows.

    ``schedule="zigzag"`` (causal only, even per-chip block length)
    rebalances causal work: the contiguous layout's fully-future skip
    halves FLOPs but not wall-clock (the last rank works at every step);
    zigzag hands each rank chunks (r, 2n-1-r) so every step does ~half a
    block everywhere and the 2x lands in latency. Inputs/outputs keep the
    contiguous layout — conversion costs eight half-block ppermutes per
    call (two each for q/k/v in, two for the output back), amortized
    over the n ring steps.
    """
    from ..ops import flash

    if use_pallas is None:
        use_pallas = flash.supported()
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)

    # kernel layout: one (batch x head) program per row
    qf = (q * scale).transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    if schedule == "zigzag":
        if not causal:
            raise ValueError("schedule='zigzag' is a causal load-balance; "
                             "use the contiguous schedule for non-causal")
        if sq != sk or sq % 2:
            raise ValueError(
                f"zigzag needs equal, even per-chip q/kv block lengths; "
                f"got sq={sq}, sk={sk}")
        qf = zigzag_shard(qf, axis)
        kf = zigzag_shard(kf, axis)
        vf = zigzag_shard(vf, axis)
        out, _lse = _zigzag_core(qf, kf, vf, axis, bool(use_pallas),
                                 bool(interpret))
        out = zigzag_unshard(out, axis)
    elif schedule == "contiguous":
        out, _lse = _ring_core(qf, kf, vf, axis, causal, bool(use_pallas),
                               bool(interpret))
    else:
        raise ValueError(f"unknown ring schedule {schedule!r}; valid: "
                         "'contiguous', 'zigzag'")
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3).astype(v.dtype)


def seq_to_heads(x, axis):
    """All-to-all reshard (batch, seq/n, heads, d) → (batch, seq,
    heads/n, d): trade sequence sharding for head sharding (the Ulysses
    forward switch)."""
    n = lax.psum(1, axis)
    if x.shape[2] % n:
        raise ValueError(
            f"num_heads {x.shape[2]} must divide by the sequence-parallel "
            f"axis size {n} for the Ulysses all-to-all")
    return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def heads_to_seq(x, axis):
    """Inverse of :func:`seq_to_heads`: (batch, seq, heads/n, d) →
    (batch, seq/n, heads, d)."""
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


def _local_flash_fwd_loop(qf, kf, vf, causal, use_pallas, interpret,
                          kv_chunk: int = 1024):
    """Full local attention in flash form over (bh, s, d) rows, returning
    ``(out, lse)``: ``out`` in the operands' dtype, ``lse`` (bh, s, 1)
    float32. ``kf`` / ``vf`` may hold fewer rows, each serving a group of
    query rows (``ops/flash.py`` ``kv_group``)."""
    from ..ops import flash

    if use_pallas or interpret:
        return flash.flash_attend(qf, kf, vf, causal, interpret)
    group = flash.kv_group(qf, kf)
    if group > 1:  # the CPU's path: the kernels' rows, repeated
        kf, vf = (jnp.repeat(x, group, axis=0) for x in (kf, vf))
    bh, s, d = qf.shape
    m = jnp.full((bh, s, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bh, s, 1), jnp.float32)
    acc = jnp.zeros((bh, s, d), jnp.float32)
    zero = jnp.asarray(0, jnp.int32)
    chunk = min(kv_chunk, s)
    if s % chunk:
        chunk = s
    for off in range(0, s, chunk):
        m, l, acc = flash._attend_jnp(
            qf, kf[:, off:off + chunk], vf[:, off:off + chunk],
            zero, jnp.asarray(off, jnp.int32), causal, m, l, acc)
    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe).astype(qf.dtype), m + jnp.log(l_safe)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _local_flash_core(qf, kf, vf, causal, use_pallas, interpret, kv_chunk):
    """Differentiable full local attention with flash-style memory: like
    :func:`_ring_core`, the custom VJP saves only (qf, kf, vf, out, lse)
    and the backward runs the Pallas block-gradient kernels (or the
    KV-chunked jnp identities), so the O(s²) logits never persist for
    the backward."""
    return _local_flash_fwd_loop(qf, kf, vf, causal, use_pallas, interpret,
                                 kv_chunk)


def _local_flash_core_fwd(qf, kf, vf, causal, use_pallas, interpret,
                          kv_chunk):
    out, lse = _local_flash_fwd_loop(qf, kf, vf, causal, use_pallas,
                                     interpret, kv_chunk)
    return (out, lse), (qf, kf, vf, out, lse)


def _local_flash_core_bwd(causal, use_pallas, interpret, kv_chunk, res,
                          cts):
    from ..ops import flash

    qf, kf, vf, out, lse = res
    dout, _dlse = cts  # in the operands' dtype, as ``out`` is
    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True)
    zero = jnp.asarray(0, jnp.int32)
    if use_pallas or interpret:
        return flash.flash_block_grads(qf, kf, vf, lse, dout, D, zero, zero,
                                       causal, interpret=interpret,
                                       out_dtype=qf.dtype)
    # same KV chunking as the forward: peak logits O(s * kv_chunk)
    dq, dk, dv = flash.jnp_block_grads(qf, kf, vf, lse, dout, D, zero, zero,
                                       causal, kv_chunk=kv_chunk)
    return (dq.astype(qf.dtype), dk.astype(kf.dtype), dv.astype(vf.dtype))


_local_flash_core.defvjp(_local_flash_core_fwd, _local_flash_core_bwd)


def _rows(x):
    """(b, s, heads, d) -> the kernels' (b * heads, s, d) rows."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _query_shape(q, v, qk_rows):
    """(b, s, query heads, d), from ``q`` as it comes or as rows."""
    if not qk_rows:
        return q.shape
    b, s = v.shape[:2]
    return b, s, q.shape[0] // b, q.shape[2]


def _local_flash(q, k, v, causal, use_pallas, interpret,
                 kv_chunk: int = 1024, prescaled: bool = False,
                 qk_rows: bool = False):
    """Exact local attention in flash form: (b, s, h, d) in/out, logits
    never materialized at O(s²) in forward OR backward — the Pallas
    kernels tile both; the jnp fallback loops ``kv_chunk``-sized KV slabs
    in both directions (peak logits O(s·kv_chunk)). ``prescaled``: ``q``
    already carries the 1/sqrt(d) (``TransformerLM``'s "full" mode). ``k``
    and ``v`` may hold fewer heads than ``q``, each serving a group of
    query heads: they stay at their own count, into the kernels and out.
    ``qk_rows``: ``q`` (pre-scaled) and ``k`` come as the kernels' rows
    already (``ops/prepare_rows.py``); ``v`` as above."""
    b, s, h, d = _query_shape(q, v, qk_rows)
    with SCOPE_PREPARE():
        if qk_rows:
            qf, kf, vf = q, k, _rows(v)
        else:
            if not prescaled:
                q = q * (1.0 / (d ** 0.5))
            qf, kf, vf = (_rows(x) for x in (q, k, v))
    # the backward rule inherits the call's scope
    with (SCOPE_WINDOW if getattr(causal, "window", 0) else SCOPE_KERNEL)():
        out, _lse = _local_flash_core(qf, kf, vf, causal, bool(use_pallas),
                                      bool(interpret), int(kv_chunk))
    with SCOPE_PREPARE():
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------
# block-diffusion attention over a doubled sequence: the noised copy's L
# rows, then the clean copy's. Row r is of copy c(r), position r mod L and
# block (r mod L) // B, and sees: within the noised copy its own block,
# both ways; from the noised copy the clean blocks before its own; within
# the clean copy every block up to its own. A clean row sees no noised one.
#
# Of the (2L)^2 square about L^2 pairs are visible. The two parts with
# clean keys are each a run of the blocked kernels under a limit
# (ops/flash.py ``block_causal`` / ``earlier_blocks``: L x L sweeps that
# skip what no row sees); a noised row's own block is B x B scores, plain
# jnp, joined to the kernel's part through the log-sum-exp. The backward
# runs the same three parts against the joined lse, as the ring's does
# against the whole ring's.
# --------------------------------------------------------------------------


def _copies(x):
    """(bh, 2L, .) -> the noised copy's rows, the clean copy's."""
    length = x.shape[1] // 2
    return x[:, :length], x[:, length:]


def _own_block(x, block, group=1):
    """(rows, L, d) -> (rows / group, group, L / block, block, d): query
    rows by their key/value row's group, key/value rows at ``group`` 1,
    so that the two broadcast over the group."""
    rows, length, d = x.shape
    return x.reshape(rows // group, group, length // block, block, d)


# A block's products are B x B x d with B a handful: written as a multiply
# and a sum, which the compiler fuses into one elementwise pass in
# float32, not as L / B matrix products of 4 rows each.
def _own_scores(a, b):
    """``sum_d a[.., i, d] * b[.., j, d]``: (.., B, d) twice -> (.., B, B)."""
    return jnp.sum(a[..., :, None, :].astype(jnp.float32)
                   * b[..., None, :, :].astype(jnp.float32), axis=-1)


def _own_values(p, x, over_group=False):
    """``sum_j p[.., i, j] * x[.., j, d]``: (.., B, B), (.., B, d) ->
    (.., B, d); ``over_group`` sums the group's axis (1) in the same
    pass, for a key/value row's gradient."""
    return jnp.sum(p[..., None] * x[..., None, :, :].astype(jnp.float32),
                   axis=(1, -2) if over_group else -2)


def _block_diffusion_fwd(qf, kf, vf, block, use_pallas, interpret,
                         kv_chunk):
    from ..ops import flash

    group = flash.kv_group(qf, kf)
    with _OWN_BLOCK():
        (q_n, q_c), (k_n, k_c), (v_n, v_c) = (_copies(qf), _copies(kf),
                                              _copies(vf))
    bh, length, d = q_n.shape
    with SCOPE_KERNEL():
        out_c, lse_c = _local_flash_fwd_loop(
            q_c, k_c, v_c, flash.block_causal(block), use_pallas, interpret,
            kv_chunk)
        # block 0's rows come back with l == 0: out 0 and lse ~ NEG_INF
        out_e, lse_e = _local_flash_fwd_loop(
            q_n, k_c, v_c, flash.earlier_blocks(block), use_pallas,
            interpret, kv_chunk)
    with _OWN_BLOCK():
        s_own = _own_scores(_own_block(q_n, block, group),
                            _own_block(k_n, block))
        lse_own = jax.nn.logsumexp(s_own, axis=-1).reshape(bh, length, 1)
        lse_n = jnp.logaddexp(lse_e, lse_own)
        p_own = jnp.exp(s_own - _own_block(lse_n, block, group))
        out_own = _own_values(p_own, _own_block(v_n, block))
        out_n = (jnp.exp(lse_e - lse_n) * out_e.astype(jnp.float32)
                 + out_own.reshape(bh, length, d)).astype(qf.dtype)
        return (jnp.concatenate([out_n, out_c], axis=1),
                jnp.concatenate([lse_n, lse_c], axis=1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _block_diffusion_core(qf, kf, vf, block, use_pallas, interpret,
                          kv_chunk):
    """``(out, lse)`` of block-diffusion attention over (bh, 2L, d) rows,
    ``qf`` pre-scaled, ``kf`` / ``vf`` at their own (key/value) rows;
    residuals (qf, kf, vf, out, lse), as :func:`_local_flash_core`."""
    return _block_diffusion_fwd(qf, kf, vf, block, use_pallas, interpret,
                                kv_chunk)


def _block_diffusion_core_fwd(qf, kf, vf, block, use_pallas, interpret,
                              kv_chunk):
    out, lse = _block_diffusion_fwd(qf, kf, vf, block, use_pallas,
                                    interpret, kv_chunk)
    return (out, lse), (qf, kf, vf, out, lse)


def _block_diffusion_core_bwd(block, use_pallas, interpret, kv_chunk, res,
                              cts):
    from ..ops import flash

    # a backward rule inherits the scope its forward was called in, not
    # the ones the forward opened: the two parts are named again here
    qf, kf, vf, out, lse = res
    dout, _dlse = cts
    group = flash.kv_group(qf, kf)
    with SCOPE_KERNEL():
        D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    zero = jnp.asarray(0, jnp.int32)
    with _OWN_BLOCK():
        (q_n, q_c), (k_n, k_c), (v_n, v_c) = (_copies(qf), _copies(kf),
                                              _copies(vf))
        (lse_n, lse_c), (do_n, do_c), (D_n, D_c) = (
            _copies(lse), _copies(dout), _copies(D))

    def kernel_grads(q, lse, do, D, limit):
        if use_pallas or interpret:
            return flash.flash_block_grads(
                q, k_c, v_c, lse, do, D, zero, zero, limit,
                interpret=interpret, out_dtype=qf.dtype)
        return flash.jnp_block_grads(q, k_c, v_c, lse, do, D, zero, zero,
                                     limit, kv_chunk=kv_chunk)

    with SCOPE_KERNEL():
        dq_c, dk_c, dv_c = kernel_grads(q_c, lse_c, do_c, D_c,
                                        flash.block_causal(block))
        dq_e, dk_e, dv_e = kernel_grads(q_n, lse_n, do_n, D_n,
                                        flash.earlier_blocks(block))
    with _OWN_BLOCK():
        # the noised rows' own blocks: the same identities on B x B scores,
        # a key/value row's gradients summed over its group in the pass
        q_o, do_o, lse_o, D_o = (_own_block(x, block, group)
                                 for x in (q_n, do_n, lse_n, D_n))
        k_o, v_o = _own_block(k_n, block), _own_block(v_n, block)
        f32 = jnp.float32
        p = jnp.exp(_own_scores(q_o, k_o) - lse_o)
        ds = p * (_own_scores(do_o, v_o) - D_o)
        dv_n = _own_values(jnp.swapaxes(p, -1, -2), do_o, over_group=True)
        dq_o = _own_values(ds, k_o)
        dk_n = _own_values(jnp.swapaxes(ds, -1, -2), q_o, over_group=True)
        dq_n = dq_e.astype(f32) + dq_o.reshape(q_n.shape)
        join = lambda a, b, like: jnp.concatenate(
            [a.astype(like.dtype), b.astype(like.dtype)], axis=1)
        return (join(dq_n, dq_c, qf),
                join(dk_n.reshape(k_n.shape),
                     dk_e.astype(f32) + dk_c.astype(f32), kf),
                join(dv_n.reshape(v_n.shape),
                     dv_e.astype(f32) + dv_c.astype(f32), vf))


_block_diffusion_core.defvjp(_block_diffusion_core_fwd,
                             _block_diffusion_core_bwd)


def _block_diffusion_flash(q, k, v, block, use_pallas, interpret,
                           kv_chunk: int = 1024, qk_rows: bool = False):
    """Block-diffusion attention in flash form: (b, 2L, h, d) in and out,
    the noised copy's rows then the clean copy's, blocks of ``block``
    positions, ``q`` pre-scaled; ``k`` and ``v`` at their own head count,
    and ``q`` and ``k`` as rows where ``qk_rows``, as in
    :func:`_local_flash`. No array is 2L x 2L, forward or backward."""
    b, rows, h, d = _query_shape(q, v, qk_rows)
    if rows % (2 * block):
        raise ValueError(f"{rows} rows are not two copies of whole blocks "
                         f"of {block}")
    with SCOPE_PREPARE():
        qf, kf, vf = (q, k, _rows(v)) if qk_rows else (_rows(q), _rows(k),
                                                         _rows(v))
    # its two parts name themselves, forward and backward
    out, _lse = _block_diffusion_core(
        qf, kf, vf, int(block), bool(use_pallas), bool(interpret),
        int(kv_chunk))
    with SCOPE_PREPARE():
        return out.reshape(b, h, rows, d).transpose(0, 2, 1, 3)


def ulysses_attention(q, k, v, axis, *, causal: bool = True,
                      use_pallas: bool | None = None,
                      interpret: bool = False):
    """Ulysses sequence parallelism: reshard to head-parallel with one
    all-to-all per tensor, run exact full-sequence attention on the local
    head group (in flash form — no O(seq²) logits in HBM), reshard the
    output back to sequence-parallel."""
    from ..ops import flash

    if use_pallas is None:
        use_pallas = flash.supported()
    q = seq_to_heads(q, axis)
    k = seq_to_heads(k, axis)
    v = seq_to_heads(v, axis)
    out = _local_flash(q, k, v, causal, use_pallas, interpret)
    return heads_to_seq(out, axis)
