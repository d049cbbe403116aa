"""The query's and the key's way into the blocked attention kernels, as one
Pallas pass forward and one backward.

Between a q / k projection and the kernels of :mod:`horovod_tpu.ops.flash`
lie a per-head RMS norm (float32 statistics, a learned scale), the rotary
rotation (rotate-half pairing, float32 angles), the query's ``1 /
sqrt(d)`` and the transpose to the kernels' head-major rows. Written as
``jax.numpy``, that chain holds a reduction and a concatenation, so the
compiler splits it into several fusions with float32 round trips through
HBM. Here it is one read of the projection's output in its own layout
and one write of the rows, forward; one read of the rows' cotangent and
of the projection's output and one write of its gradient, backward, with
the norm's statistics computed again from the tile in VMEM and the
scale's gradient as per-tile sums one XLA reduction adds up.

The results round where the ``jax.numpy`` chain rounds (after the norm,
after the rotation, after the scale), forward and backward: the same
function, not a more precise one.

A block is ``(row tile, W)`` of the projection's ``(b * s, heads * d)``
view, ``W = max(d, 128)`` lanes: ``W / d`` whole heads, whose norms are
reduced over their own lanes and whose halves are rotated by lane rolls.

CPU tests run the kernels with ``interpret=True``.
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANES = 128
# the largest that divides the rows: a v5e step moves 256 KiB of bf16 a
# block each way at 1024 rows, and the float32 temporaries of the
# backward stay well inside the scoped VMEM
ROW_TILES = (1024, 512, 256, 128, 64, 32, 16)


def _width(head_dim: int) -> int:
    return max(head_dim, LANES)


def row_tile(rows: int) -> int | None:
    """The rows of a block: the largest of :data:`ROW_TILES` dividing
    ``rows``, None where none does."""
    return next((t for t in ROW_TILES if rows % t == 0), None)


def supported(heads: int, head_dim: int, rows: int) -> bool:
    """Whether the pass takes these shapes: whole heads a block of
    whole lane rows, and rows that split into tiles."""
    width = _width(head_dim)
    return (width % head_dim == 0 and heads * head_dim % width == 0
            and row_tile(rows) is not None)


# --------------------------------------------------------------------------
# one tile, in VMEM
# --------------------------------------------------------------------------


def _lanes(shape, lo, hi):
    """(rows, W) bool: the lanes ``lo <= lane < hi``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= lo) & (lane < hi)


def _head_sum(t, head_dim):
    """Each head's sum of float32 ``t`` (rows, W) over its own lanes,
    broadcast back over them."""
    if t.shape[1] == head_dim:
        return jnp.sum(t, axis=1, keepdims=True)
    out = jnp.zeros_like(t)
    for at in range(0, t.shape[1], head_dim):
        inside = _lanes(t.shape, at, at + head_dim)
        out = jnp.where(inside, jnp.sum(jnp.where(inside, t, 0.0), axis=1,
                                        keepdims=True), out)
    return out


def _rotate_half(u, head_dim):
    """Each head's ``(-u[d/2:], u[:d/2])``, lane for lane."""
    from jax.experimental.pallas import tpu as pltpu

    width, half = u.shape[1], head_dim // 2
    behind = pltpu.roll(u, half, 1)           # lane i: u[i - d/2]
    ahead = behind if width == head_dim else pltpu.roll(u, width - half, 1)
    first = functools.reduce(jnp.logical_or, (
        _lanes(u.shape, at, at + half) for at in range(0, width, head_dim)))
    return jnp.where(first, -ahead, behind)


def _rms(xf, head_dim, eps):
    return jax.lax.rsqrt(_head_sum(xf * xf, head_dim) / head_dim + eps)


def _forward_kernel(*refs, head_dim, norm, rotary, eps, divisor):
    refs = list(refs)
    x_ref = refs.pop(0)
    scale_ref = refs.pop(0) if norm else None
    cos_ref, sin_ref = (refs.pop(0), refs.pop(0)) if rotary else (None,
                                                                  None)
    (o_ref,) = refs
    y = x_ref[...]
    dtype = y.dtype
    if norm:
        xf = y.astype(jnp.float32)
        y = (xf * _rms(xf, head_dim, eps) * scale_ref[...]).astype(dtype)
    if rotary:
        u = y.astype(jnp.float32)
        y = (u * cos_ref[...]
             + _rotate_half(u, head_dim) * sin_ref[...]).astype(dtype)
    if divisor is not None:
        y = (y.astype(jnp.float32) / divisor).astype(dtype)
    for j in range(o_ref.shape[0]):
        o_ref[j] = y[:, j * head_dim:(j + 1) * head_dim]


def _backward_kernel(*refs, head_dim, norm, rotary, eps, divisor):
    refs = list(refs)
    x_ref = refs.pop(0)
    scale_ref = refs.pop(0) if norm else None
    cos_ref, sin_ref = (refs.pop(0), refs.pop(0)) if rotary else (None,
                                                                  None)
    g_ref, dx_ref = refs.pop(0), refs.pop(0)
    dtype = x_ref.dtype
    heads = g_ref.shape[0]
    g = (g_ref[0] if heads == 1
         else jnp.concatenate([g_ref[j] for j in range(heads)], axis=1))
    if divisor is not None:
        g = (g.astype(jnp.float32) / divisor).astype(dtype)
    if rotary:
        gf = g.astype(jnp.float32)
        g = (gf * cos_ref[...]
             - _rotate_half(gf * sin_ref[...], head_dim)).astype(dtype)
    if norm:
        (ds_ref,) = refs
        xf = x_ref[...].astype(jnp.float32)
        r = _rms(xf, head_dim, eps)
        a = g.astype(jnp.float32)
        ds_ref[0] = jnp.sum(a * (xf * r), axis=0, keepdims=True)
        a = a * scale_ref[...]
        g = (a * r - xf * (r * r * r / head_dim)
             * _head_sum(a * xf, head_dim)).astype(dtype)
    dx_ref[...] = g


# --------------------------------------------------------------------------
# the two calls, under one custom_vjp
# --------------------------------------------------------------------------


def _grid(dims, tile):
    """``(batch, row tiles, lane columns)``: the lane column runs
    innermost, so a table block is fetched once a row tile."""
    b, s, heads, head_dim = dims
    return b, s // tile, heads * head_dim // _width(head_dim)


def _call(kernel, operands, outs, dims, tile, interpret, **statics):
    """One ``pallas_call`` of ``kernel`` over ``_grid``: ``operands`` and
    ``outs`` as (array or shape, block) pairs, None arrays left out. The
    blocks: ``flat``, the projection's view; ``scale``; ``table``, the
    angles' (one batch's, or each row's own); ``rows``, the kernels';
    ``partial``, a step's sums of the scale's gradient."""
    from jax.experimental.pallas import tpu as pltpu

    b, s, heads, head_dim = dims
    width = _width(head_dim)
    _, n_s, n_c = grid = _grid(dims, tile)
    operands = [(a, block) for a, block in operands if a is not None]
    per_row = any(a.shape[0] != s for a, block in operands
                  if block == "table")
    blocks = {
        "flat": pl.BlockSpec((tile, width),
                             lambda i, si, c: (i * n_s + si, c)),
        "scale": pl.BlockSpec((1, width), lambda i, si, c: (0, 0)),
        "table": pl.BlockSpec((tile, width), lambda i, si, c: (
            (i * n_s if per_row else 0) + si, 0)),
        "rows": pl.BlockSpec((width // head_dim, tile, head_dim),
                             lambda i, si, c: (i * n_c + c, si, 0)),
        "partial": pl.BlockSpec((1, 1, width), lambda i, si, c: (
            (i * n_s + si) * n_c + c, 0, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, head_dim=head_dim, **statics),
        grid=grid, in_specs=[blocks[block] for _, block in operands],
        out_specs=[blocks[block] for _, block in outs],
        out_shape=[shape for shape, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
    )(*(a for a, _ in operands))


# Under a jit of their own, as the attention kernels are: a model traces
# and lowers each call once, not once a layer.
@functools.partial(jax.jit, static_argnames=("dims", "eps", "divisor",
                                             "tile", "interpret"))
def _forward_call(flat, scale, cos, sin, *, dims, eps, divisor, tile,
                  interpret):
    b, s, heads, head_dim = dims
    rows = jax.ShapeDtypeStruct((b * heads, s, head_dim), flat.dtype)
    return _call(_forward_kernel,
                 [(flat, "flat"), (scale, "scale"), (cos, "table"),
                  (sin, "table")], [(rows, "rows")], dims, tile, interpret,
                 norm=scale is not None, rotary=cos is not None, eps=eps,
                 divisor=divisor)[0]


@functools.partial(jax.jit, static_argnames=("dims", "eps", "divisor",
                                             "tile", "interpret"))
def _backward_call(flat, scale, cos, sin, g, *, dims, eps, divisor, tile,
                   interpret):
    outs = [(jax.ShapeDtypeStruct(flat.shape, flat.dtype), "flat")]
    if scale is not None:
        steps = functools.reduce(operator.mul, _grid(dims, tile))
        outs.append((jax.ShapeDtypeStruct((steps, 1, scale.shape[1]),
                                          jnp.float32), "partial"))
    out = _call(_backward_kernel,
                [(flat, "flat"), (scale, "scale"), (cos, "table"),
                 (sin, "table"), (g, "rows")], outs, dims, tile, interpret,
                norm=scale is not None, rotary=cos is not None, eps=eps,
                divisor=divisor)
    # the scale's gradient: the steps' sums, added up here
    return out[0], None if scale is None else jnp.sum(out[1], axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _prepare(flat, scale, cos, sin, dims, eps, divisor, tile, interpret):
    return _forward_call(flat, scale, cos, sin, dims=dims, eps=eps,
                         divisor=divisor, tile=tile, interpret=interpret)


def _prepare_fwd(flat, scale, cos, sin, dims, eps, divisor, tile,
                 interpret):
    # residuals: the projection's output, which is live anyway, the scale
    # and the tables; no float32 copy of the input
    rows = _prepare(flat, scale, cos, sin, dims, eps, divisor, tile,
                    interpret)
    return rows, (flat, scale, cos, sin)


def _prepare_bwd(dims, eps, divisor, tile, interpret, res, g):
    flat, scale, cos, sin = res
    dx, dscale = _backward_call(flat, scale, cos, sin, g, dims=dims,
                                eps=eps, divisor=divisor, tile=tile,
                                interpret=interpret)
    return dx, dscale, None, None


_prepare.defvjp(_prepare_fwd, _prepare_bwd)


def prepare_rows(x, scale, tables, *, eps, prescale, interpret=False):
    """``x`` (b, s, heads, d), a q / k projection's output, through
    ``operators.RMSNorm`` (``scale`` (d,) float32; None: no norm),
    ``operators.rotary`` (``tables``: its ``rotary_tables``, cos and sin
    (b or 1, s, d) float32; None: no rotation) and, for the query
    (``prescale``), ``/ sqrt(d)``, into the kernels' rows (b * heads, s,
    d): what those statements and ``parallel/sequence.py`` ``_rows`` give,
    in one pass each way (:func:`supported` says for which shapes)."""
    b, s, heads, head_dim = x.shape
    width = _width(head_dim)
    if not supported(heads, head_dim, s):
        raise ValueError(f"{heads} heads of {head_dim} over {s} rows: no "
                         "whole heads a lane row, or no row tile")
    if scale is not None:
        scale = jnp.tile(scale, width // head_dim).reshape(1, width)
    cos = sin = None
    if tables is not None:
        cos, sin = (jnp.tile(t.reshape(-1, head_dim), (1, width // head_dim))
                    for t in tables)
    divisor = None
    if prescale:   # the statement's own divisor: sqrt(d) in x's dtype
        divisor = float(np.sqrt(np.float32(head_dim)).astype(x.dtype))
    return _prepare(x.reshape(b * s, heads * head_dim), scale, cos, sin,
                    (b, s, heads, head_dim), float(eps), divisor,
                    row_tile(s), bool(interpret))
