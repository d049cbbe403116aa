"""Pallas flash-attention block kernel for the sequence-parallel hot path.

The ring/Ulysses schedules (:mod:`horovod_tpu.parallel.sequence`) spend
their FLOPs in the blockwise online-softmax update. The jnp formulation
materializes the (batch, heads, sq, sk) logits in HBM every ring step;
this kernel keeps the whole update — QKᵀ, masking, the online-softmax
rescale, and the PV accumulation — in VMEM, one pass per (batch × head)
program, so HBM traffic per step drops from O(sq·sk) logits to the K/V
blocks themselves (the flash-attention I/O shape, which is what the MXU
needs to stay busy on long sequences).

The kernel carries the running (m, l, acc) statistics **between**
invocations, so the ring loop can rotate K/V with ``ppermute`` and call it
once per step. Inside one invocation the grid tiles BOTH dimensions —
(batch·head, q-tile, kv-tile), the kv sweep innermost so the VMEM scratch
carries per q-tile — bounding VMEM at O(q_tile·d) instead of O(sq·d) and
extending the kernel to sequence blocks far beyond one tile.

Backward: BOTH schedules' custom VJPs (the ring's re-rotating backward
and the Ulysses/local one) route through :func:`flash_block_grads` — a dq
pass sweeping kv tiles innermost and a dk/dv pass sweeping q tiles
innermost, logits recomputed per tile in VMEM — with
:func:`jnp_block_grads` (the same identities, KV-chunked) as the
non-Pallas fallback. ``block_attend``'s own ``custom_vjp`` (jnp recompute
of one block update) only covers code that differentiates the op
directly. CPU tests run every kernel with ``interpret=True`` (an
explicit test argument; nothing on the default path passes it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def causal_mask_scores(s, qpos0, kpos0):
    """Mask future positions of a (bh|, sq, sk) score block to the NEG_INF
    sentinel. ``qpos0``/``kpos0`` are int32 global offsets of the blocks
    (int — f32 cannot represent token offsets past 2^24)."""
    sq, sk = s.shape[-2], s.shape[-1]
    qpos = qpos0 + jnp.arange(sq, dtype=jnp.int32)
    kpos = kpos0 + jnp.arange(sk, dtype=jnp.int32)
    keep = qpos[:, None] >= kpos[None, :]
    return jnp.where(jnp.expand_dims(keep, 0) if s.ndim == 3 else keep,
                     s, NEG_INF)


def zero_masked(p, s):
    """Zero softmax weights at sentinel-masked score positions. When every
    position seen so far is masked, the running max is still the NEG_INF
    sentinel and ``s - m == 0`` there — exp(0)=1 would silently admit
    garbage V rows. Zeroing explicitly makes any block visit order safe
    (a fully-masked row just keeps l == 0). Must stay in lockstep with
    the same guard inside the Pallas kernel (:func:`_flash_kernel`)."""
    return jnp.where(s > NEG_INF / 2, p, 0.0)


def _attend_jnp(q, k, v, qpos0, kpos0, causal, m, l, acc):
    """Reference jnp formulation of one block update (also the backward's
    recompute target). Shapes: q (bh, sq, d); k/v (bh, sk, d); m/l
    (bh, sq, 1); acc (bh, sq, d); qpos0/kpos0 int32 scalars."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32)
    if causal:
        s = causal_mask_scores(s, qpos0, kpos0)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if causal:
        p = zero_masked(p, s)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum(
        "bqk,bkd->bqd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_new, l_new, acc_new


DEFAULT_KV_TILE = 512
DEFAULT_Q_TILE = 1024  # bounds VMEM: scratch is O(q_tile*d), not O(sq*d)


def _tile_causal_mask(s, qpos_ref, kpos_ref, qi, j, q_tile, kv_tile):
    """Causal mask for one (q-tile, kv-tile) score block — THE masking
    rule, shared by the forward and both backward kernels so they cannot
    drift (the jnp twin is :func:`causal_mask_scores`). Mosaic iota must
    be integer-typed; int32 offsets are exact past 2^24."""
    tq, sk = s.shape
    qpos = (qpos_ref[0] + qi * q_tile
            + jax.lax.broadcasted_iota(jnp.int32, (tq, sk), 0))
    kpos = (kpos_ref[0] + j * kv_tile
            + jax.lax.broadcasted_iota(jnp.int32, (tq, sk), 1))
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                  acc_ref, mo_ref, lo_ref, acco_ref, m_s, l_s, acc_s, *,
                  causal, q_tile, kv_tile, sk_valid):
    qi = pl.program_id(1)  # q-tile index (kv sweep is the innermost dim,
    j = pl.program_id(2)   # so scratch carries are per-(bh, q-tile))
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():  # load this q-tile's incoming carries into scratch
        m_s[:] = m_ref[0]
        l_s[:] = l_ref[0]
        acc_s[:] = acc_ref[0]

    q = q_ref[0]          # (q_tile, d)
    k = k_ref[0]          # (kv_tile, d)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (q_tile, kv_tile), MXU
    if causal:
        s = _tile_causal_mask(s, qpos_ref, kpos_ref, qi, j, q_tile, kv_tile)
    if sk_valid is not None:
        s = _tile_pad_mask(s, j, kv_tile, sk_valid)
    m_prev = m_s[:]       # (q_tile, 1) f32
    l_prev = l_s[:]
    acc_prev = acc_s[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if causal or sk_valid is not None:
        # fully-masked rows: m_new may still be the NEG_INF sentinel, making
        # exp(s - m_new) == 1 at masked entries — zero them (see _attend_jnp)
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[:] = m_new
    l_s[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[:] = acc_prev * corr + pv

    @pl.when(j == n_kv - 1)
    def _flush():
        mo_ref[0] = m_s[:]
        lo_ref[0] = l_s[:]
        acco_ref[0] = acc_s[:]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_pad(size: int, default: int) -> tuple[int, int]:
    """``(tile, padded)``: tile <= default, ``padded`` the next tile
    multiple covering ``size``. Awkward (prime-ish) sizes PAD to the next
    tile boundary instead of shrinking the tile to a divisor — a divisor
    search hands e.g. sq=8191 a tile of 1, a grid of 1-row MXU ops and a
    Mosaic layout cliff (ADVICE r4). The padded tail is masked to the
    NEG_INF sentinel via ``sk_valid`` (kv) or zero inputs (q); sub-default
    sizes round up to the fp32 sublane quantum (8) so Mosaic gets an
    aligned block."""
    if size >= default:
        # A size just past a tile boundary would pay up to ~2x padded
        # compute at the full default tile (e.g. 1025 -> 2048): try the
        # default and two halvings, keep the least total padding (larger
        # tile on ties — fewer grid steps).
        cands = [t for t in (default, default // 2, default // 4)
                 if t >= 8] or [default]
        tile = min(cands, key=lambda t: (_round_up(size, t), -t))
        return tile, _round_up(size, tile)
    t = _round_up(size, 8)
    return t, t


def _pad_dim1(x, target: int):
    """Zero-pad dim 1 (the sequence dim of a (bh, s, d) block) to target."""
    if x.shape[1] == target:
        return x
    return jnp.pad(x, ((0, 0), (0, target - x.shape[1]), (0, 0)))


def _tile_pad_mask(s, j, kv_tile, sk_valid):
    """NEG_INF-mask score columns past the true (pre-padding) kv length.
    Shared by the forward and both backward kernels, like the causal
    twin :func:`_tile_causal_mask`."""
    tq, tk = s.shape
    kcol = j * kv_tile + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return jnp.where(kcol < sk_valid, s, NEG_INF)


def _flash_call(q, k, v, qpos0, kpos0, causal, m, l, acc, interpret):
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    kv_tile, sk_p = _tile_pad(sk, DEFAULT_KV_TILE)
    q_tile, sq_p = _tile_pad(sq, DEFAULT_Q_TILE)
    # Zero-pad to the tile grid; padded kv columns are NEG_INF-masked in
    # the kernel (sk_valid) and padded q rows are sliced off below (their
    # carries are well-defined: zero q rows give s=0 scores, no NaNs).
    q, k, v = _pad_dim1(q, sq_p), _pad_dim1(k, sk_p), _pad_dim1(v, sk_p)
    m, l, acc = (_pad_dim1(m, sq_p), _pad_dim1(l, sq_p),
                 _pad_dim1(acc, sq_p))
    n_kv = sk_p // kv_tile
    n_q = sq_p // q_tile
    kernel = functools.partial(_flash_kernel, causal=causal,
                               q_tile=q_tile, kv_tile=kv_tile,
                               sk_valid=sk if sk_p != sk else None)
    out = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1,), lambda i, qi, j: (0,)),       # qpos0
            pl.BlockSpec((1,), lambda i, qi, j: (0,)),       # kpos0
            pl.BlockSpec((1, q_tile, d), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, kv_tile, d), lambda i, qi, j: (i, j, 0)),
            pl.BlockSpec((1, kv_tile, d), lambda i, qi, j: (i, j, 0)),
            pl.BlockSpec((1, q_tile, 1), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, q_tile, 1), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, q_tile, d), lambda i, qi, j: (i, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q_tile, 1), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, q_tile, 1), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, q_tile, d), lambda i, qi, j: (i, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq_p, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_tile, 1), jnp.float32),
            pltpu.VMEM((q_tile, 1), jnp.float32),
            pltpu.VMEM((q_tile, d), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray([qpos0], jnp.int32).reshape(1),
      jnp.asarray([kpos0], jnp.int32).reshape(1),
      q, k, v, m, l, acc)
    if sq_p != sq:
        out = [o[:, :sq] for o in out]
    return tuple(out)


# --------------------------------------------------------------------------
# backward kernels: block gradients with the normalized-softmax identities
# (dV += pT.dO, dS = p o (dO.VT - D), dQ += dS.K, dK += dST.Q with
# p = exp(s - lse), D = rowsum(dO o O)) — the flash-attention backward.
# Two passes so each accumulator lives in VMEM: dQ sweeps kv tiles
# innermost, dK/dV sweep q tiles innermost. Logits are recomputed per tile
# and never reach HBM (the jnp fallback materializes the block logits).
# --------------------------------------------------------------------------


def _bwd_scores(q, k, qpos_ref, kpos_ref, lse, qi, j, q_tile, kv_tile,
                causal, sk_valid):
    """Recompute the normalized softmax block p = exp(s - lse), masked by
    the SAME :func:`_tile_causal_mask` / :func:`_tile_pad_mask` the
    forward kernel uses."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        s = _tile_causal_mask(s, qpos_ref, kpos_ref, qi, j, q_tile, kv_tile)
    if sk_valid is not None:
        s = _tile_pad_mask(s, j, kv_tile, sk_valid)
    p = jnp.exp(s - lse)
    if causal or sk_valid is not None:
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
    return p


def _flash_bwd_dq_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, lse_ref,
                         d_ref, do_ref, dq_ref, dq_s, *, causal, q_tile,
                         kv_tile, sk_valid):
    qi = pl.program_id(1)
    j = pl.program_id(2)  # kv sweep innermost: dq accumulates per q tile
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    p = _bwd_scores(q_ref[0], k_ref[0], qpos_ref, kpos_ref, lse_ref[0],
                    qi, j, q_tile, kv_tile, causal, sk_valid)
    do = do_ref[0]
    dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_ref[0])
    dq_s[:] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _flush():
        dq_ref[0] = dq_s[:]


def _flash_bwd_dkv_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, lse_ref,
                          d_ref, do_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                          causal, q_tile, kv_tile, sk_valid):
    j = pl.program_id(1)
    qi = pl.program_id(2)  # q sweep innermost: dk/dv accumulate per kv tile
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q = q_ref[0]
    p = _bwd_scores(q, k_ref[0], qpos_ref, kpos_ref, lse_ref[0],
                    qi, j, q_tile, kv_tile, causal, sk_valid)
    do = do_ref[0]
    dv_s[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - d_ref[0])
    dk_s[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = dk_s[:]
        dv_ref[0] = dv_s[:]


def jnp_block_grads(qf, kf, vf, lse, dout, D, qpos0, kpos0, causal,
                    kv_chunk: int | None = None):
    """jnp twin of :func:`flash_block_grads` — the flash backward
    identities, shared by the ring and local custom VJPs so the two
    backward paths cannot drift. ``kv_chunk`` bounds peak logits memory
    at O(sq·kv_chunk) by looping KV slabs (None = one slab)."""
    sk = kf.shape[1]
    chunk = sk if not kv_chunk else min(kv_chunk, sk)
    if sk % chunk:
        chunk = sk
    dq = jnp.zeros(qf.shape[:2] + (qf.shape[2],), jnp.float32)
    dks, dvs = [], []
    for off in range(0, sk, chunk):
        k_c = kf[:, off:off + chunk]
        v_c = vf[:, off:off + chunk]
        s = jnp.einsum("bqd,bkd->bqk", qf, k_c,
                       preferred_element_type=jnp.float32)
        if causal:
            s = causal_mask_scores(s, qpos0, kpos0 + off)
        p = jnp.exp(s - lse)  # normalized attention weights
        if causal:
            p = zero_masked(p, s)
        dvs.append(jnp.einsum("bqk,bqd->bkd", p, dout,
                              preferred_element_type=jnp.float32))
        dp = jnp.einsum("bqd,bkd->bqk", dout, v_c.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - D)
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, k_c.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        dks.append(jnp.einsum("bqk,bqd->bkd", ds, qf.astype(jnp.float32),
                              preferred_element_type=jnp.float32))
    return dq, jnp.concatenate(dks, axis=1), jnp.concatenate(dvs, axis=1)


def flash_block_grads(q, k, v, lse, dout, D, qpos0, kpos0, causal,
                      interpret=False):
    """Pallas block gradients for the ring/local flash backward:
    ``(dq, dk, dv)`` for one K/V block against the full saved ``lse``.
    Shapes: q/dout (bh, sq, d); k/v (bh, sk, d); lse/D (bh, sq, 1), with
    ``D = rowsum(dout * out)``. Float32 outputs. The jnp equivalent is the
    einsum block in :func:`horovod_tpu.parallel.sequence._ring_core_bwd`.
    """
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    q_tile, sq_p = _tile_pad(sq, DEFAULT_Q_TILE)
    kv_tile, sk_p = _tile_pad(sk, DEFAULT_KV_TILE)
    sk_valid = sk if sk_p != sk else None
    # Zero-pad to the tile grid (see _flash_call): padded kv columns are
    # sk_valid-masked; padded q rows contribute nothing because dout (and
    # hence dp, ds, and the dv outer product) is zero there.
    q, dout = _pad_dim1(q, sq_p), _pad_dim1(dout, sq_p)
    lse, D = _pad_dim1(lse, sq_p), _pad_dim1(D, sq_p)
    k, v = _pad_dim1(k, sk_p), _pad_dim1(v, sk_p)
    n_q, n_kv = sq_p // q_tile, sk_p // kv_tile
    qpos0 = jnp.asarray([qpos0], jnp.int32).reshape(1)
    kpos0 = jnp.asarray([kpos0], jnp.int32).reshape(1)
    pos_spec = pl.BlockSpec((1,), lambda i, a, b: (0,))

    def q_spec_dq(which):  # blocks indexed by the q-tile grid position
        return pl.BlockSpec((1, q_tile, which),
                            lambda i, qi, j: (i, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal,
                          q_tile=q_tile, kv_tile=kv_tile, sk_valid=sk_valid),
        grid=(bh, n_q, n_kv),
        in_specs=[pos_spec, pos_spec,
                  q_spec_dq(d),
                  pl.BlockSpec((1, kv_tile, d), lambda i, qi, j: (i, j, 0)),
                  pl.BlockSpec((1, kv_tile, d), lambda i, qi, j: (i, j, 0)),
                  q_spec_dq(1), q_spec_dq(1), q_spec_dq(d)],
        out_specs=q_spec_dq(d),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((q_tile, d), jnp.float32)],
        interpret=interpret,
    )(qpos0, kpos0, q, k, v, lse, D, dout)

    kv_spec = pl.BlockSpec((1, kv_tile, d), lambda i, j, qi: (i, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                          q_tile=q_tile, kv_tile=kv_tile, sk_valid=sk_valid),
        grid=(bh, n_kv, n_q),
        in_specs=[pos_spec, pos_spec,
                  pl.BlockSpec((1, q_tile, d), lambda i, j, qi: (i, qi, 0)),
                  kv_spec, kv_spec,
                  pl.BlockSpec((1, q_tile, 1), lambda i, j, qi: (i, qi, 0)),
                  pl.BlockSpec((1, q_tile, 1), lambda i, j, qi: (i, qi, 0)),
                  pl.BlockSpec((1, q_tile, d), lambda i, j, qi: (i, qi, 0))],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sk_p, d), jnp.float32),
                   jax.ShapeDtypeStruct((bh, sk_p, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((kv_tile, d), jnp.float32),
                        pltpu.VMEM((kv_tile, d), jnp.float32)],
        interpret=interpret,
    )(qpos0, kpos0, q, k, v, lse, D, dout)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def block_attend(q, k, v, qpos0, kpos0, causal, interpret, m, l, acc):
    """One flash block update: returns the new (m, l, acc) carries.

    Layout: q (bh, sq, d) pre-scaled; k/v (bh, sk, d); m/l (bh, sq, 1)
    float32; acc (bh, sq, d) float32; qpos0/kpos0 int32 scalars (global
    token offsets of the blocks for causal masking — integers, so offsets
    past 2^24 stay exact).
    """
    qpos0 = jnp.asarray(qpos0, jnp.int32)
    kpos0 = jnp.asarray(kpos0, jnp.int32)
    return _flash_call(q, k, v, qpos0, kpos0, causal, m, l, acc, interpret)


def _block_attend_fwd(q, k, v, qpos0, kpos0, causal, interpret, m, l, acc):
    out = block_attend(q, k, v, qpos0, kpos0, causal, interpret, m, l, acc)
    return out, (q, k, v, qpos0, kpos0, m, l, acc)


def _block_attend_bwd(causal, interpret, res, cts):
    import numpy as np

    q, k, v, qpos0, kpos0, m, l, acc = res
    # flash-style backward: recompute the block through the jnp
    # formulation and differentiate that (nothing but the carries saved)
    _, vjp = jax.vjp(
        lambda q, k, v, m, l, acc: _attend_jnp(
            q, k, v, qpos0, kpos0, causal, m, l, acc),
        q, k, v, m, l, acc)
    dq, dk, dv, dm, dl, dacc = vjp(tuple(cts))
    zero_int = np.zeros((), jax.dtypes.float0)  # int operands: float0 ct
    return dq, dk, dv, zero_int, zero_int, dm, dl, dacc


block_attend.defvjp(_block_attend_fwd, _block_attend_bwd)


def supported() -> bool:
    """Whether the compiled kernel path is selected: the
    ``HVD_FLASH_ATTENTION`` knob. Opt-in because the one v5e comparison
    on record had XLA's own fusion of the jnp formulation within ~10% of
    this kernel; the kernel's value is its bounded VMEM footprint (logits
    never materialize in HBM), which matters for very long blocks.

    Raises when the knob is set on a backend Mosaic cannot compile for:
    the jnp formulation quietly standing in would hide that the kernel
    the user asked for never ran."""
    from ..utils import envs
    if not envs.get_bool(envs.FLASH_ATTENTION):
        return False
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"HVD_FLASH_ATTENTION is set but the jax backend is "
            f"{backend!r}: the Pallas flash kernels compile for TPU only. "
            "Unset the knob to run the jnp formulation.")
    return True
