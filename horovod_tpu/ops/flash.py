"""Pallas flash-attention kernels: blocked exact attention on the TPU.

The jnp formulation materializes the (batch, heads, sq, sk) logits in
HBM; these kernels keep the whole update — QKᵀ, masking, the
online-softmax rescale, and the PV accumulation — in VMEM, one
(batch × head) row per program, so HBM traffic drops from O(sq·sk)
logits to q, k, v and the output (the flash-attention I/O shape). The
grid tiles BOTH dimensions — (batch·head, q-tile, kv-tile), the kv sweep
innermost so the VMEM scratch carries per q-tile — bounding VMEM at
O(q_tile·d). A masked run lets a query see the keys at positions up to
a limit that does not fall along the sequence (:class:`Limit`: its own
position, the end of its block, the end of the block before); it skips
the score tiles no row of which sees anything and masks only those the
limit crosses (:func:`_for_visible_tile`).

Three kernels, two users:

* :func:`flash_attend` — whole local attention, ``(out, lse)`` in one
  call: ``TransformerLM``'s "full" mode (selected from platform, dtype,
  sequence and placement in ``models/transformer.py``; no knob) and the
  Ulysses schedule.
* :func:`block_attend` — one ring step: the running (m, l, acc)
  statistics go in and come out, so the ring loop
  (:mod:`horovod_tpu.parallel.sequence`) can rotate K/V with
  ``ppermute`` between calls.
* :func:`flash_block_grads` — the backward of both: dq, dk and dv of one
  K/V block against the saved ``lse`` in ONE pass, logits recomputed per
  tile in VMEM — with :func:`jnp_block_grads` (the same identities,
  KV-chunked) as the non-Pallas fallback. ``block_attend``'s own
  ``custom_vjp`` (jnp recompute of one block update) only covers code
  that differentiates the op directly.

CPU tests run every kernel with ``interpret=True`` (an explicit test
argument; nothing on the default path passes it).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


class Limit(NamedTuple):
    """THE masking rule: a query at position ``qpos`` sees the keys at
    ``kpos <= qpos // block * block + offset``. Static (part of a
    kernel's jit key), and never falling as ``qpos`` grows, which is what
    lets a whole tile be judged from its first and last rows. Every
    ``causal`` argument in this module takes ``False`` (no mask),
    ``True`` (:data:`CAUSAL`) or one of these."""

    block: int = 1
    offset: int = 0

    def of(self, qpos):
        """The last key position a query at ``qpos`` (an int32 array, a
        traced scalar or an int, never negative) sees. ``lax.div`` on
        arrays: Mosaic lowers it; positions are not negative, so it is
        the floor."""
        if self == CAUSAL:
            return qpos
        blocks = (qpos // self.block if isinstance(qpos, int)
                  else jax.lax.div(qpos, jnp.int32(self.block)))
        return blocks * self.block + self.offset

    def first_query(self, kpos):
        """The first query position that sees a key at ``kpos`` (scalar)."""
        if self == CAUSAL:
            return kpos
        return -(-(kpos - self.offset) // self.block) * self.block


CAUSAL = Limit()


def block_causal(block: int) -> Limit:
    """A query sees every key up to the end of its own block of
    ``block`` positions."""
    return Limit(block, block - 1)


def earlier_blocks(block: int) -> Limit:
    """A query sees the keys of the blocks before its own and nothing of
    its own: the rows of block 0 see nothing at all."""
    return Limit(block, -1)


def _limit(causal) -> Limit | None:
    if isinstance(causal, Limit):
        return causal
    return CAUSAL if causal else None


def causal_mask_scores(s, qpos0, kpos0, causal=True):
    """Mask what ``causal`` (a :class:`Limit`; ``True``: future positions)
    hides of a (bh|, sq, sk) score block to the NEG_INF
    sentinel. ``qpos0``/``kpos0`` are int32 global offsets of the blocks
    (int — f32 cannot represent token offsets past 2^24)."""
    sq, sk = s.shape[-2], s.shape[-1]
    qpos = qpos0 + jnp.arange(sq, dtype=jnp.int32)
    kpos = kpos0 + jnp.arange(sk, dtype=jnp.int32)
    keep = _limit(causal).of(qpos)[:, None] >= kpos[None, :]
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
        s = causal_mask_scores(s, qpos0, kpos0, causal)
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
DEFAULT_Q_TILE = 512  # bounds VMEM: scratch is O(q_tile*d), not O(sq*d)


def _tile_causal_mask(s, limit, pos_ref, qi, j, q_tile, kv_tile, q_axis=0):
    """``limit``'s mask for one score tile, shared by the forward and
    backward kernels so they cannot drift (the jnp twin is
    :func:`causal_mask_scores`). ``pos_ref`` is the scalar-prefetched
    ``[qpos0, kpos0]``; ``q_axis`` says which dimension of ``s`` runs over
    q rows (0 for q·kᵀ, 1 for the backward's k·qᵀ). Mosaic iota must be
    integer-typed; int32 offsets are exact past 2^24."""
    qpos = (pos_ref[0] + qi * q_tile
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis))
    kpos = (pos_ref[1] + j * kv_tile
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    return jnp.where(limit.of(qpos) >= kpos, s, NEG_INF)


def _for_visible_tile(limit, pos_ref, qi, j, q_tile, kv_tile, body):
    """Run ``body(masked)`` for score tile (qi, j) unless ``limit`` hides
    all of it: a tile no row of which sees a key contributes nothing
    and is skipped, one every row sees whole runs without the mask
    (``masked=False``), one the limit crosses runs with it. A limit never
    falls along the rows, so the tile's last row says whether any row
    sees its first key and its first row whether all see its last.
    Decided from the same positions :func:`_tile_causal_mask` compares,
    so a ring block's traced offsets skip exactly what the mask would
    zero."""
    if not limit:
        body(False)
        return
    q_first = pos_ref[0] + qi * q_tile
    k_first = pos_ref[1] + j * kv_tile
    any_visible = k_first <= limit.of(q_first + (q_tile - 1))
    all_visible = k_first + (kv_tile - 1) <= limit.of(q_first)
    pl.when(all_visible)(lambda: body(False))
    pl.when(jnp.logical_and(any_visible,
                            jnp.logical_not(all_visible)))(lambda: body(True))


def _kv_sweep_maps(limit, q_tile, kv_tile, n_kv):
    """``(q_map, kv_map)``: block index maps of a (bh, q tile, kv tile)
    grid. The kv sweep is clamped to the last kv tile any row of q tile
    ``qi`` may see (0 when none), so the steps :func:`_for_visible_tile`
    skips fetch nothing new either."""

    def q_map(i, qi, j, pos):
        return (i, qi, 0)

    def kv_map(i, qi, j, pos):
        if limit:
            last_k = limit.of(pos[0] + qi * q_tile + (q_tile - 1))
            j = jnp.minimum(j, jnp.minimum(
                jnp.maximum(last_k - pos[1], 0) // kv_tile, n_kv - 1))
        return (i, j, 0)

    return q_map, kv_map


def visible_tiles(limit, sq, sk, q_tile, kv_tile) -> int:
    """Score tiles a (sq x sk) sweep from offsets 0 visits under
    ``limit``: what :func:`_for_visible_tile` does not skip."""
    return sum(1 for first in range(0, sq, q_tile)
               for k_first in range(0, sk, kv_tile)
               if not limit or k_first <= limit.of(first + q_tile - 1))


def _flash_kernel(pos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                  mo_ref, lo_ref, acco_ref, m_s, l_s, acc_s, *,
                  causal, q_tile, kv_tile, sk_valid):
    """One ring step: carries in, one K/V block folded in, carries out."""
    qi = pl.program_id(1)  # q-tile index (kv sweep is the innermost dim,
    j = pl.program_id(2)   # so scratch carries are per-(bh, q-tile))

    @pl.when(j == 0)
    def _init():  # load this q-tile's incoming carries into scratch
        m_s[:] = m_ref[0]
        l_s[:] = l_ref[0]
        acc_s[:] = acc_ref[0]

    def update(masked):
        q = q_ref[0]          # (q_tile, d)
        k = k_ref[0]          # (kv_tile, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (q_tile, kv_tile), MXU
        if masked:
            s = _tile_causal_mask(s, causal, pos_ref, qi, j, q_tile,
                                  kv_tile)
        if sk_valid is not None:
            s = _tile_pad_mask(s, j, kv_tile, sk_valid)
        m_prev = m_s[:]       # (q_tile, 1) f32
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked or sk_valid is not None:
            # fully-masked rows: m_new may still be the NEG_INF sentinel,
            # making exp(s - m_new) == 1 at masked entries — zero them
            # (see _attend_jnp)
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * corr + pv

    _for_visible_tile(causal, pos_ref, qi, j, q_tile, kv_tile, update)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        mo_ref[0] = m_s[:]
        lo_ref[0] = l_s[:]
        acco_ref[0] = acc_s[:]


def _flash_whole_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_s,
                        l_s, acc_s, *, causal, q_tile, kv_tile, sk_valid):
    """Whole local attention: the carries start and end in VMEM; what
    reaches HBM is the normalized output in the operands' dtype and the
    log-sum-exp. TRANSPOSED like the backward: the score tile is k.qT,
    (kv_tile, q_tile), so the softmax statistics are lane-dense rows (a
    ``(s, 1)`` float32 column costs a Mosaic operand 128 lanes a value),
    their reductions run down the sublanes (elementwise on the VPU, no
    cross-lane shuffles), and the accumulator is accT, (d, q_tile): on a
    v5e the forward takes 0.32 ms so, 0.49 ms the ring step's way
    (PERF.md section 6, PR 29)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def update(masked):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = _tile_causal_mask(s, causal, pos_ref, qi, j, q_tile,
                                  kv_tile, q_axis=1)
        if sk_valid is not None:
            s = _tile_pad_mask(s, j, kv_tile, sk_valid, kv_axis=0)
        m_prev = m_s[:]                                   # (1, q_tile)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        # no zero_masked guard where every q row sees column 0: kv tile 0
        # comes first, so m is finite before any masked score meets it.
        # A limit that ends before a row's own block leaves the rows of
        # block 0 with nothing to see: they keep l == 0
        if masked and causal.offset < 0:
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_s[:] = m_new
        l_s[:] = l_s[:] * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_s[:] = acc_s[:] * corr + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (d, q_tile)

    _for_visible_tile(causal, pos_ref, qi, j, q_tile, kv_tile, update)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        l_safe = jnp.maximum(l_s[:], 1e-30)
        o_ref[0] = (acc_s[:] / l_safe).T.astype(o_ref.dtype)
        lse_ref[0] = m_s[:] + jnp.log(l_safe)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_pad(size: int, default: int) -> tuple[int, int]:
    """``(tile, padded)``: tile <= default, ``padded`` the next tile
    multiple covering ``size``. Awkward (prime-ish) sizes PAD to the next
    tile boundary instead of shrinking the tile to a divisor — a divisor
    search hands e.g. sq=8191 a tile of 1, a grid of 1-row MXU ops and a
    Mosaic layout cliff. The padded tail is masked to the
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


def _q_tile_pad(sq: int) -> tuple[int, int]:
    """:func:`_tile_pad` for a q length that meets softmax statistics
    stored as ROWS (one lane a q position): whole 128-lane groups."""
    return _tile_pad(_round_up(sq, 128), DEFAULT_Q_TILE)


def _pad_dim1(x, target: int):
    """Zero-pad dim 1 (the sequence dim of a (bh, s, d) block) to target."""
    if x.shape[1] == target:
        return x
    return jnp.pad(x, ((0, 0), (0, target - x.shape[1]), (0, 0)))


def _tile_pad_mask(s, j, kv_tile, sk_valid, kv_axis=1):
    """NEG_INF-mask scores of kv positions past the true (pre-padding) kv
    length. Shared by the forward and backward kernels, like the causal
    twin :func:`_tile_causal_mask`."""
    kcol = j * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                  kv_axis)
    return jnp.where(kcol < sk_valid, s, NEG_INF)


def _positions(qpos0, kpos0):
    """``[qpos0, kpos0]`` as the int32 pair the kernels prefetch into
    SMEM (index maps and ``pl.when`` read scalars from there)."""
    return jnp.stack([jnp.asarray(qpos0, jnp.int32).reshape(()),
                      jnp.asarray(kpos0, jnp.int32).reshape(())])


def _compiler_params(q_tile, kv_tile, resident=0,
                     semantics=("parallel", "parallel", "arbitrary")):
    """The first grid dimensions independent, the last the sweep a
    scratch accumulator carries over. The scoped-VMEM default (16 MiB on
    a v5e) holds the float32 score-sized temporaries of a 512 x 512 tile;
    larger tiles, and ``resident`` bytes of blocks that stay put, ask for
    what they need."""
    from jax.experimental.pallas import tpu as pltpu

    scores = 6 * 4 * q_tile * kv_tile  # s, p, dp, ds and what Mosaic copies
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=max(scores + resident + (8 << 20), 16 << 20))


def _flash_call(q, k, v, qpos0, kpos0, causal, m, l, acc, interpret):
    from jax.experimental.pallas import tpu as pltpu

    causal = _limit(causal)
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

    q_map, kv_map = _kv_sweep_maps(causal, q_tile, kv_tile, n_kv)
    col = pl.BlockSpec((1, q_tile, 1), q_map)
    row = pl.BlockSpec((1, q_tile, d), q_map)
    kv = pl.BlockSpec((1, kv_tile, d), kv_map)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_q, n_kv),
            in_specs=[row, kv, kv, col, col, row],
            out_specs=[col, col, row],
            scratch_shapes=[
                pltpu.VMEM((q_tile, 1), jnp.float32),
                pltpu.VMEM((q_tile, 1), jnp.float32),
                pltpu.VMEM((q_tile, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq_p, d), jnp.float32),
        ],
        compiler_params=_compiler_params(q_tile, kv_tile),
        interpret=interpret,
    )(_positions(qpos0, kpos0), q, k, v, m, l, acc)
    if sq_p != sq:
        out = [o[:, :sq] for o in out]
    return tuple(out)


def flash_attend(q, k, v, causal, interpret=False):
    """Whole local attention over (bh, s, d) rows, ``q`` pre-scaled:
    ``(out, lse)`` with ``out`` normalized, in ``q``'s dtype, and ``lse``
    (bh, s, 1) float32. One Mosaic call; scores and softmax statistics
    never leave VMEM."""
    s = q.shape[1]
    return _flash_attend(q, k, v, causal=_limit(causal), interpret=interpret,
                         q_tiling=_q_tile_pad(s),
                         kv_tiling=_tile_pad(s, DEFAULT_KV_TILE))


# The Mosaic calls sit under a jit of their own: a model traces and lowers
# a kernel once, not once a layer (24 layers of GPT-2 medium took 6 s more
# to trace and lower without it, on every start). Tiles are resolved by the
# callers above, so they are part of the jit's key.
@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "q_tiling", "kv_tiling"))
def _flash_attend(q, k, v, *, causal, interpret, q_tiling, kv_tiling):
    from jax.experimental.pallas import tpu as pltpu

    bh, s, d = q.shape
    (q_tile, s_q), (kv_tile, s_k) = q_tiling, kv_tiling
    q, k, v = _pad_dim1(q, s_q), _pad_dim1(k, s_k), _pad_dim1(v, s_k)
    n_q, n_kv = s_q // q_tile, s_k // kv_tile
    kernel = functools.partial(_flash_whole_kernel, causal=causal,
                               q_tile=q_tile, kv_tile=kv_tile,
                               sk_valid=s if s_k != s else None)

    q_map, kv_map = _kv_sweep_maps(causal, q_tile, kv_tile, n_kv)
    row = pl.BlockSpec((1, q_tile, d), q_map)
    kv = pl.BlockSpec((1, kv_tile, d), kv_map)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_q, n_kv),
            in_specs=[row, kv, kv],
            out_specs=[row, pl.BlockSpec((1, 1, q_tile),
                                         lambda i, qi, j, pos: (i, 0, qi))],
            scratch_shapes=[
                pltpu.VMEM((1, q_tile), jnp.float32),
                pltpu.VMEM((1, q_tile), jnp.float32),
                pltpu.VMEM((d, q_tile), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32)],
        compiler_params=_compiler_params(q_tile, kv_tile),
        interpret=interpret,
    )(_positions(0, 0), q, k, v)
    return out[:, :s], lse[:, 0, :s, None]


# --------------------------------------------------------------------------
# backward kernel: block gradients with the normalized-softmax identities
# (dV += pT.dO, dS = p o (dO.VT - D), dQ += dS.K, dK += dST.Q with
# p = exp(s - lse), D = rowsum(dO o O)) — the flash-attention backward.
# One pass in the TRANSPOSED orientation: the score tile is k.qT,
# (kv_tile, q_tile), so lse and D enter as lane-dense rows that broadcast
# down the sublanes, dV and dK are plain products accumulated in VMEM
# over the q sweep, and only dQ needs a transposed operand; it accumulates
# in VMEM too, whole, over all the tiles of one (batch x head).
# Logits are recomputed per tile and never reach HBM (the jnp fallback
# materializes the block logits).
# --------------------------------------------------------------------------


def _flash_bwd_kernel(pos_ref, q_ref, k_ref, v_ref, lse_ref, d_ref, do_ref,
                      dq_ref, dk_ref, dv_ref, dq_s, dk_s, dv_s, *, causal,
                      q_tile, kv_tile, sk_valid):
    j = pl.program_id(1)
    qi = pl.program_id(2)  # q sweep innermost: dk/dv accumulate per kv tile
    last_q = qi == pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(j == 0, qi == 0))
    def _init_dq():
        dq_s[:] = jnp.zeros_like(dq_s)

    @pl.when(qi == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def update(masked):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        nt = (((1,), (1,)), ((), ()))
        # p = exp(s - lse), masked by the SAME rules as the forward
        s = jax.lax.dot_general(k, q, nt,
                                preferred_element_type=jnp.float32)
        if masked:
            s = _tile_causal_mask(s, causal, pos_ref, qi, j, q_tile,
                                  kv_tile, q_axis=1)
        if sk_valid is not None:
            s = _tile_pad_mask(s, j, kv_tile, sk_valid, kv_axis=0)
        p = jnp.exp(s - lse_ref[0])              # (kv_tile, q_tile)
        if masked or sk_valid is not None:
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        dv_s[:] += jnp.dot(p.astype(do.dtype), do,
                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, nt,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - d_ref[0])).astype(q.dtype)
        dk_s[:] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(qi * q_tile, q_tile), q_tile)
        dq_s[rows, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_visible_tile(causal, pos_ref, qi, j, q_tile, kv_tile, update)

    @pl.when(last_q)
    def _flush():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == pl.num_programs(1) - 1, last_q))
    def _flush_dq():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


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
            s = causal_mask_scores(s, qpos0, kpos0 + off, causal)
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
                      interpret=False, out_dtype=jnp.float32):
    """Pallas block gradients for the ring/local flash backward:
    ``(dq, dk, dv)`` for one K/V block against the full saved ``lse``.
    Shapes: q/dout (bh, sq, d); k/v (bh, sk, d); lse/D (bh, sq, 1), with
    ``D = rowsum(dout * out)``. Accumulated in float32, returned in
    ``out_dtype`` (the ring sums blocks, so it keeps float32). The jnp
    equivalent is :func:`jnp_block_grads`.
    """
    return _flash_block_grads(
        q, k, v, lse, dout, D, _positions(qpos0, kpos0),
        causal=_limit(causal),
        interpret=interpret, out_dtype=jnp.dtype(out_dtype),
        q_tiling=_q_tile_pad(q.shape[1]),
        kv_tiling=_tile_pad(k.shape[1], DEFAULT_KV_TILE))


@functools.partial(jax.jit, static_argnames=(
    "causal", "interpret", "out_dtype", "q_tiling", "kv_tiling"))
def _flash_block_grads(q, k, v, lse, dout, D, pos, *, causal, interpret,
                       out_dtype, q_tiling, kv_tiling):
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    (q_tile, sq_p), (kv_tile, sk_p) = q_tiling, kv_tiling
    # Zero-pad to the tile grid (see _flash_call): padded kv columns are
    # sk_valid-masked; padded q rows contribute nothing because dout (and
    # hence dp, ds, and the dv outer product) is zero there.
    q, dout = _pad_dim1(q, sq_p), _pad_dim1(dout, sq_p)
    # (bh, sq, 1) columns -> (bh, 1, sq) rows: the same bytes in HBM
    lse, D = (_pad_dim1(x, sq_p).reshape(bh, 1, sq_p) for x in (lse, D))
    k, v = _pad_dim1(k, sk_p), _pad_dim1(v, sk_p)
    n_q, n_kv = sq_p // q_tile, sk_p // kv_tile

    # grid (bh, kv tile, q tile); the q sweep is clamped to the first q
    # tile with a row that may see kv tile j, so skipped steps fetch
    # nothing new
    def first_q(j, qi, pos):
        if causal:
            first = causal.first_query(pos[1] + j * kv_tile)
            qi = jnp.maximum(qi, jnp.minimum(
                jnp.maximum(first - pos[0], 0) // q_tile, n_q - 1))
        return qi

    row = pl.BlockSpec((1, q_tile, d),
                       lambda i, j, qi, pos: (i, first_q(j, qi, pos), 0))
    stat = pl.BlockSpec((1, 1, q_tile),
                        lambda i, j, qi, pos: (i, 0, first_q(j, qi, pos)))
    kv = pl.BlockSpec((1, kv_tile, d), lambda i, j, qi, pos: (i, j, 0))
    whole_q = pl.BlockSpec((1, sq_p, d), lambda i, j, qi, pos: (i, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, causal=causal, q_tile=q_tile,
                          kv_tile=kv_tile,
                          sk_valid=sk if sk_p != sk else None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, n_kv, n_q),
            in_specs=[row, kv, kv, stat, stat, row],
            out_specs=[whole_q, kv, kv],
            scratch_shapes=[pltpu.VMEM((sq_p, d), jnp.float32),
                            pltpu.VMEM((kv_tile, d), jnp.float32),
                            pltpu.VMEM((kv_tile, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, sq_p, d), out_dtype),
                   jax.ShapeDtypeStruct((bh, sk_p, d), out_dtype),
                   jax.ShapeDtypeStruct((bh, sk_p, d), out_dtype)],
        compiler_params=_compiler_params(
            q_tile, kv_tile, resident=3 * 4 * sq_p * max(d, 128),
            semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(pos, q, k, v, lse, D, dout)
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
    """Whether the ring and Ulysses schedules run these kernels: the
    ``HVD_FLASH_ATTENTION`` knob, which governs nothing else.
    ``TransformerLM``'s "full" mode does not ask here: it selects the
    kernels from platform, dtype, sequence length and placement
    (``models/transformer.py``), where a v5e measured them faster
    (PERF.md section 6, PR 29). The schedules stay opt-in until a
    benchmark cell reaches them.

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
