"""GPT-style Transformer LM, written for multi-axis mesh sharding.

No reference equivalent (Horovod is model-agnostic); this is the flagship
model for demonstrating the framework's tensor/sequence/data-parallel
shardings beyond the reference's data-parallel scope (SURVEY.md §2.3).

TPU-first: bfloat16 compute/fp32 params, head and MLP dims sized for the
MXU, and a ``shardings()`` helper producing PartitionSpecs for a
``('dp', 'tp')``(+ optional 'sp') mesh — Megatron-style column/row-parallel
splits expressed as GSPMD sharding constraints, letting XLA insert the
all-reduces over ICI.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import metrics as _metrics
from .. import timeline as _timeline
from ..parallel import sequence as _sequence
from . import scopes as _scopes

# Device scopes (docs/timeline.md): attention's products with its
# weights, and what lies between them and the kernels. The kernels' own
# scope is ``parallel/sequence.py``'s.
_PROJECT = _timeline.scope("attention.project")
_PREPARE = _sequence.SCOPE_PREPARE
_KERNEL = _sequence.SCOPE_KERNEL
_WINDOW = _sequence.SCOPE_WINDOW


# THE valid attention schedules — single source of truth for the config
# validator, the Attention dispatch, and the position-offset check.
RING_SCHEDULES = {"ring": "contiguous", "ring_zigzag": "zigzag"}
SEQ_PARALLEL_MODES = tuple(RING_SCHEDULES) + ("ulysses",)
ATTN_MODES = ("full",) + SEQ_PARALLEL_MODES
NORMS = ("layernorm", "rmsnorm")
POSITIONS = ("learned", "rotary")
MLPS = ("gelu", "swiglu")
LAYER_TYPES = ("full_attention", "sliding_attention", "conv")
ATTN_MASKS = ("causal", "block_diffusion")
MOE_SCORINGS = ("sigmoid_bias", "softmax")
MOE_ACTIVATIONS = ("silu", "relu")
MOE_ROUTER_INPUTS = ("ffn_input", "operator_input")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # long-context schedule: "full" (exact local attention), "ring"
    # (horovod_tpu.parallel.ring_attention — sequence sharded over
    # seq_axis, KV blocks rotate over ICI), "ring_zigzag" (the ring with
    # the causal load-balanced zigzag chunk schedule — the 2x causal
    # saving lands in wall-clock, not just FLOPs), or "ulysses"
    # (all-to-all seq<->head switch). All but "full" require the model to
    # run inside shard_map with seq_axis bound and the sequence sharded.
    attn_mode: str = "full"
    seq_axis: str = "sp"
    # expert parallelism: moe_experts > 0 replaces the dense MLP with an
    # expert-parallel MoE FFN (horovod_tpu.parallel.moe_alltoall) — one
    # expert per chip of moe_axis, which must be bound (shard_map) with
    # size == moe_experts at run time. The Switch load-balance loss is
    # sown under ("intermediates", "moe_aux"); collect it with
    # apply(..., mutable=["intermediates"]) and add it to the objective.
    moe_experts: int = 0
    moe_axis: str = "ep"
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    # -- the block, spelled by configuration. The defaults are the block
    # above (learned positions, LayerNorm, GELU MLP, as many key/value
    # heads as query heads, an untied head, attention at every depth);
    # its parameter tree and its lowered program do not change with the
    # fields below at their defaults. What they select lives in
    # ``models/operators.py``, imported only by a configuration that
    # needs it.
    norm: str = "layernorm"        # "rmsnorm": learned scale, no bias
    norm_eps: float = 1e-6         # read by "rmsnorm" only
    positions: str = "learned"     # "rotary": rotate-half over the head
    rope_theta: float = 10000.0
    num_kv_heads: int | None = None  # grouped-query: heads per kv head =
    #                                  num_heads // num_kv_heads
    head_dim: int | None = None    # None: d_model // num_heads
    # what a row may attend to. "block_diffusion": the model runs a
    # doubled sequence, a noised copy's L rows and then the clean copy's,
    # in blocks of ``block_length`` positions; a noised row sees its own
    # block and the clean blocks before it, a clean row the clean blocks
    # up to its own (models/block_diffusion.py has the inputs and the
    # loss). The caller gives each row's position (both copies count
    # from 0), and the logits are the noised copy's L rows.
    attn_mask: str = "causal"
    block_length: int = 1
    qk_norm: bool = False          # RMS norm of q and k per head
    mlp: str = "gelu"              # "swiglu": w2(silu(w1 x) * w3 x)
    # the operator of each layer: "full_attention" | "sliding_attention"
    # (causal attention over the ``sliding_window`` keys up to a row's
    # own, its own included) | "conv" (the gated short convolution);
    # None = full attention everywhere. Its length is the depth
    # (num_layers must agree).
    layer_types: tuple | None = None
    sliding_window: int = 0
    # which layers rotate q and k under ``positions`` "rotary": one 0 / 1
    # a layer (None: every layer). A 0 layer has no positions at all
    # (NoPE): its attention sees only the causal order.
    rope_layout: tuple | None = None
    conv_kernel: int = 3
    # recompute each block's activations in the backward pass
    # (``nn.remat`` of the block): what a step keeps between its passes is
    # each block's input, for one more forward pass of the blocks
    remat_blocks: bool = False
    tie_embeddings: bool = False   # logits against the token embedding
    # the residual stream's dtype (None = ``dtype``); float32 keeps what
    # feeds a float32 router from rounding at every layer
    residual_dtype: Any = None
    # a chip's share of routed experts (parallel/moe.py moe_held_experts):
    # layers from ``num_dense_layers`` on replace the MLP with
    # ``moe_held[1]`` gated experts of width ``moe_d_ff``, the experts
    # ``[moe_held[0], moe_held[0] + moe_held[1])`` of ``moe_routed``,
    # picked ``moe_top_k`` a token by sigmoid scores plus a selection
    # bias (collection "routing": ``expert_bias``, and the last call's
    # ``expert_load`` / ``rows_held``).
    moe_routed: int = 0
    moe_held: tuple = (0, 0)
    moe_d_ff: int = 0
    num_dense_layers: int = 0
    moe_renormalize: bool = True
    moe_scaling: float = 1.0
    # "softmax": scores are a softmax over all ``moe_routed`` experts,
    # the picks its ``moe_top_k`` largest, no selection bias
    moe_scoring: str = "sigmoid_bias"
    # the experts' gate: w2(act(w1 x) * w3 x), "silu" (SwiGLU) or "relu"
    # (ReGLU)
    moe_activation: str = "silu"
    # what the router reads: the ffn norm's output, which the experts
    # read too ("ffn_input"), or the block's operator input, the norm
    # before attention ("operator_input")
    moe_router_input: str = "ffn_input"

    def __post_init__(self):
        # An unknown mode would silently fall through to full LOCAL
        # attention per shard — training runs, logits are wrong.
        if self.attn_mode not in ATTN_MODES:
            raise ValueError(
                f"unknown attn_mode {self.attn_mode!r}; valid: "
                f"{ATTN_MODES}")
        for field, valid in (("norm", NORMS), ("positions", POSITIONS),
                             ("mlp", MLPS), ("attn_mask", ATTN_MASKS),
                             ("moe_scoring", MOE_SCORINGS),
                             ("moe_activation", MOE_ACTIVATIONS),
                             ("moe_router_input", MOE_ROUTER_INPUTS)):
            if getattr(self, field) not in valid:
                raise ValueError(f"unknown {field} "
                                 f"{getattr(self, field)!r}; valid: {valid}")
        if self.positions == "rotary" and self.attn_mode != "full":
            raise ValueError(
                "rotary positions count from 0 on every shard: attn_mode "
                f"{self.attn_mode!r} needs the shard's offset, which "
                "they do not take yet")
        if self.attn_mask == "block_diffusion" and (
                self.attn_mode != "full" or self.block_length < 1):
            raise ValueError(
                "the block-diffusion mask is spelled for attn_mode 'full' "
                f"and whole blocks: attn_mode {self.attn_mode!r}, "
                f"block_length {self.block_length}")
        if self.head_dim is not None and (
                self.head_dim < 1
                or self.positions == "rotary" and self.head_dim % 2):
            raise ValueError(f"head_dim {self.head_dim}: rotary positions "
                             "pair the two halves of a head")
        if self.layer_types is not None:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, "
                    f"num_layers is {self.num_layers}")
            unknown = set(self.layer_types) - set(LAYER_TYPES)
            if unknown:
                raise ValueError(f"unknown layer types {sorted(unknown)}; "
                                 f"valid: {LAYER_TYPES}")
        if ("sliding_attention" in (self.layer_types or ())) != (
                self.sliding_window > 0) or (self.sliding_window and (
                    self.attn_mode != "full" or self.attn_mask != "causal")):
            raise ValueError(
                f"sliding_window {self.sliding_window} is the window of "
                "'sliding_attention' layers, under the causal mask in "
                f"attn_mode 'full' (layer_types {self.layer_types}, "
                f"attn_mode {self.attn_mode!r}, attn_mask "
                f"{self.attn_mask!r})")
        if self.rope_layout is not None and (
                self.positions != "rotary"
                or len(self.rope_layout) != self.num_layers
                or set(self.rope_layout) - {0, 1}):
            raise ValueError(f"rope_layout {self.rope_layout}: one 0 / 1 a "
                             "layer, under rotary positions")
        if self.num_heads % (self.num_kv_heads or self.num_heads):
            raise ValueError(f"{self.num_heads} heads over "
                             f"{self.num_kv_heads} key/value heads")
        first, count = self.moe_held
        if self.moe_routed and not (
                0 <= first and 0 < count and first + count <= self.moe_routed
                and self.moe_top_k <= self.moe_routed):
            raise ValueError(
                f"moe_held {self.moe_held} / moe_top_k {self.moe_top_k} do "
                f"not fit {self.moe_routed} routed experts")


# "full" mode computes the same exact causal attention two ways: blocked
# (ops/flash.py: online softmax over score tiles that never leave VMEM;
# q, k, v, out and lse the only residuals) or materialised (the S x S
# logits and probabilities as arrays XLA fuses as it can). Which, is
# decided per call from what the code can observe. Below
# BLOCKED_MIN_SEQ the materialised program is the faster one on a v5e
# (PERF.md section 6, PR 29: both timed alone at bh 64, d 64).
BLOCKED_MIN_SEQ = 512

# how each "full" Attention call, and every q / k / v projection, was
# traced (docs/metrics.md)
_CALLS = {path: _metrics.ATTENTION_CALLS.bind({"path": path})
          for path in ("blocked", "materialised", "blocked_block_diffusion",
                       "materialised_block_diffusion", "blocked_grouped",
                       "projection_flat", "projection_dense_general",
                       "prepare_rows", "blocked_window",
                       "materialised_window")}
_LAST = {what: _metrics.ATTENTION_SHAPE.bind({"what": what})
         for what in ("head_dim", "visible_tile_share", "kv_group",
                      "forward_q_rows", "forward_kv_rows")}


def _local_to_one_device() -> bool:
    """Whether the arrays of the trace in progress live whole on one
    device: inside ``shard_map`` over every mesh axis, or under plain
    ``jit`` in a process with one device. Under plain ``jit`` over several
    devices the partitioner may split heads or batch, and a Mosaic call is
    opaque to it (it would gather them)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return mesh.are_all_axes_manual or mesh.size == 1


def blocked_selected(platform, dtype, seq, local, initializing) -> bool:
    """The selection rule of "full" mode, on its observations alone."""
    return (platform == "tpu" and jnp.dtype(dtype) == jnp.bfloat16
            and seq >= BLOCKED_MIN_SEQ and local and not initializing)


# Where key/value heads serve groups of query heads, the blocked kernels
# take k and v at their own head count for heads of 128 and up, a whole
# lane row: at 32 query heads over 4 of 128 the step ran 6 % faster so
# than with k and v repeated before the call; at 32 over 8 of 64 it ran
# 0.6 % slower (the kernels a tie, the k / v projections around them
# 0.8 ms slower a step), so heads of 64 keep the repeat (PERF.md
# section 6, PR 39).
GROUPED_MIN_HEAD_DIM = 128


def grouped_selected(group, head_dim) -> bool:
    """Whether the blocked kernels take the key/value heads as groups."""
    return group > 1 and head_dim >= GROUPED_MIN_HEAD_DIM


def prepare_rows_selected(heads, kv_heads, head_dim, rows) -> bool:
    """Whether ``Attention`` hands the blocked kernels q and k from
    ``ops/prepare_rows.py``'s pass: where it takes both shapes."""
    from ..ops import prepare_rows

    return (prepare_rows.supported(heads, head_dim, rows)
            and prepare_rows.supported(kv_heads, head_dim, rows))


def block_diffusion_mask(length, block):
    """(2L, 2L) bool: what row r (a query) sees of row s (a key) of a
    doubled sequence. The plain statement of the rule
    ``parallel/sequence.py`` ``_block_diffusion_flash`` computes in parts."""
    row = jnp.arange(2 * length)
    clean, at = row >= length, row % length // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_at, k_at = at[:, None], at[None, :]
    return jnp.where(q_clean, k_clean & (k_at <= q_at),
                     jnp.where(k_clean, k_at < q_at, k_at == q_at))


def window_mask(seq, window):
    """(S, S) bool: row i sees row j where ``i - window < j <= i``."""
    row = jnp.arange(seq)
    back = row[:, None] - row[None, :]
    return (back >= 0) & (back < window)


def materialised_attention(q, k, v, mask=None, window=0):
    """Attention over (batch, seq, heads, head_dim) with ``q``
    pre-scaled, through S x S logits and probabilities; ``mask`` (S, S)
    bool, causal when None (over the last ``window`` keys where that is
    not 0, under a device scope of its own)."""
    with (_WINDOW if window else _KERNEL)():
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        seq = q.shape[1]
        if mask is None:
            mask = (window_mask(seq, window) if window
                    else jnp.tril(jnp.ones((seq, seq), bool)))
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blocked_attention(q, k, v, qk_rows=False, window=0):
    """The same function in blocked form: scores accumulate in float32 and
    stay in VMEM, ``probs`` meet ``v`` in the operands' dtype as above.
    ``k`` and ``v`` may hold fewer heads than ``q``, each serving a group
    of query heads (the kernels map a query head to its key/value head).
    ``qk_rows``: ``q`` and ``k`` come as the kernels' rows already.
    ``window``: the causal mask over the last ``window`` keys, whose
    sweeps the kernels bound by the window (``ops/flash.py``)."""
    causal = True
    if window:
        from ..ops import flash

        causal = flash.sliding_window(window)
    return _sequence._local_flash(q, k, v, causal, True, False,
                                  prescaled=True, qk_rows=qk_rows)


def _limits(mask, block, window):
    """The kernels' limits for a full-mode call: the block-diffusion
    mask's two sweeps, or the causal mask (under ``window``, if any)."""
    from ..ops import flash

    if mask == "block_diffusion":
        return flash.block_causal(block), flash.earlier_blocks(block)
    return (flash.sliding_window(window) if window else flash.CAUSAL,)


def visible_tile_share(rows, mask, block, window=0) -> float:
    """Score tiles the blocked kernels' sweeps visit, of the tiles of the
    rows x rows square, at the tiles they would pick: from the static
    mask (and a causal mask's ``window``) alone."""
    from ..ops import flash

    if mask == "block_diffusion":
        rows //= 2
    (q_tile, rows_q), (kv_tile, rows_k) = (
        flash._q_tile_pad(rows), flash._tile_pad(rows, flash.DEFAULT_KV_TILE))
    limits = _limits(mask, block, window)
    visited = sum(flash.visible_tiles(limit, rows_q, rows_k, q_tile, kv_tile)
                  for limit in limits)
    square = len(limits) ** 2 * (rows_q // q_tile) * (rows_k // kv_tile)
    return visited / square


def forward_rows(rows, mask, block, head_dim, window=0) -> tuple[int, int]:
    """``(q rows, kv rows)`` a grid step of the forward kernel takes
    (``ops/flash.py`` ``forward_tiles``, as padding resolves it); the
    block-diffusion mask's sweeps are over half the rows."""
    from ..ops import flash

    if mask == "block_diffusion":
        rows //= 2
    (q_tile, _), (kv_tile, _), kv_sub = flash._forward_tiling(
        rows, head_dim, _limits(mask, block, window)[0])
    return q_tile, kv_tile * kv_sub


# The q / k / v projections multiply a ``[d_model, heads, head_dim]`` leaf.
# Stated over the three-dimensional leaf (what ``nn.DenseGeneral`` hands
# the compiler), the TPU compiler lowers the weight gradient to a
# convolution with a window of ``heads`` taps; stated over a flat
# ``[d_model, heads * head_dim]`` view of the leaf, to a plain matmul. On
# a v5e the windowed form runs at the product's roofline up to 16 heads
# of 64 (GPT-2's leaf, key/value leaves of 8 heads; the flat view there
# adds copies worth 2.7 % of a GPT-2 step) and loses twice: at 32 heads
# the window itself runs 3-5x off the product, and at heads of 128, a
# whole lane row, its result takes a layout that is not the leaf's, so
# the leaf and Adam's moments are copied into it and back (PERF.md
# section 6, PR 36: timed in the step at 16 x 64, 32 x 64, 8 x 64,
# 32 x 128, 4 x 128).
FLAT_MIN_HEADS = 32
FLAT_MIN_HEAD_DIM = 128


def flat_projection_selected(heads, head_dim) -> bool:
    """The statement of a q / k / v product, on what the module sees."""
    return heads >= FLAT_MIN_HEADS or head_dim >= FLAT_MIN_HEAD_DIM


def project_heads(x, kernel, dtype, flat):
    """``x`` (..., d_model) times ``kernel`` (d_model, heads, head_dim) as
    (..., heads, head_dim) in ``dtype``: over a ``flat`` view of the leaf,
    or as the contraction ``nn.DenseGeneral`` writes. The view is taken of
    the float32 leaf, before the cast: the compiler folds a view of the
    cast kernel back into the three-dimensional statement."""
    d_model, heads, head_dim = kernel.shape
    x = x.astype(dtype)
    if flat:
        out = x @ kernel.reshape(d_model, heads * head_dim).astype(dtype)
        return out.reshape(*x.shape[:-1], heads, head_dim)
    return jax.lax.dot_general(x, kernel.astype(dtype),
                               (((x.ndim - 1,), (0,)), ((), ())))


class HeadsProjection(nn.Module):
    """Attention's q / k / v projection: ``nn.DenseGeneral((heads,
    head_dim), use_bias=False)``'s ``kernel`` leaf (shape, float32, the
    values it draws under a given key), multiplied through
    :func:`project_heads`."""

    cfg: TransformerConfig
    heads: int
    head_dim: int

    @nn.compact
    def __call__(self, x):
        def init(key, shape, dtype):
            # as DenseGeneral draws it: over the flat shape, then reshaped
            flat = (shape[0], shape[1] * shape[2])
            return nn.linear.default_kernel_init(key, flat, dtype).reshape(
                shape)

        kernel = self.param("kernel", init,
                            (x.shape[-1], self.heads, self.head_dim),
                            jnp.float32)
        flat = flat_projection_selected(self.heads, self.head_dim)
        _CALLS["projection_flat" if flat
               else "projection_dense_general"].inc()
        return project_heads(x, kernel, self.cfg.dtype, flat)


class Attention(nn.Module):
    """``cfg``'s attention; ``window`` > 0: causal over the last
    ``window`` keys (a "sliding_attention" layer); ``rotary``: whether
    this layer rotates q and k where ``cfg.positions`` is "rotary"."""

    cfg: TransformerConfig
    window: int = 0
    rotary: bool = True

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        rotary = cfg.positions == "rotary" and self.rotary
        head_dim = cfg.head_dim or cfg.d_model // cfg.num_heads
        # qkv: column-parallel (heads split over 'tp')
        kv_heads = cfg.num_kv_heads or cfg.num_heads
        with _PROJECT():
            q = HeadsProjection(cfg, cfg.num_heads, head_dim, name="q")(x)
            k = HeadsProjection(cfg, kv_heads, head_dim, name="k")(x)
            v = HeadsProjection(cfg, kv_heads, head_dim, name="v")(x)
        rows = x.shape[1]
        local = (cfg.attn_mode not in SEQ_PARALLEL_MODES
                 or self.is_initializing())
        blocked = local and blocked_selected(
            jax.default_backend(), cfg.dtype, rows, _local_to_one_device(),
            self.is_initializing())
        # q and k as the kernels' rows, norm, rotation and scale in one
        # pass each way (ops/prepare_rows.py), where there is a chain to
        # fuse; the statements below otherwise
        prepared = (blocked and (cfg.qk_norm or rotary)
                    and prepare_rows_selected(cfg.num_heads, kv_heads,
                                              head_dim, rows))
        if prepared:
            q, k = self._prepared_rows(q, k, positions, head_dim, rotary)
        else:
            with _PREPARE():
                if cfg.qk_norm or rotary:
                    from . import operators

                    if cfg.qk_norm:
                        q = operators.RMSNorm(cfg, name="q_norm")(q)
                        k = operators.RMSNorm(cfg, name="k_norm")(k)
                    if rotary:
                        q, k = operators.rotary(q, k, cfg.rope_theta,
                                                positions)
        group = cfg.num_heads // kv_heads

        def repeat_kv(k, v):
            # each key/value head serves a group of query heads. The
            # blocked kernels map a query head to its key/value head
            # themselves (from heads of 128 up: grouped_selected); the
            # ring, zigzag, Ulysses and materialised paths take equal
            # counts (their kernels, all-to-alls and einsums are written
            # per head), so they get the heads repeated; k as rows
            # repeats each (batch, head) row
            if group == 1:
                return k, v
            with _PREPARE():
                return (jnp.repeat(k, group, axis=0 if prepared else 2),
                        jnp.repeat(v, group, axis=2))

        if cfg.attn_mode in RING_SCHEDULES and not self.is_initializing():
            from ..parallel import ring_attention
            k, v = repeat_kv(k, v)
            with _KERNEL():
                out = ring_attention(q, k, v, cfg.seq_axis, causal=True,
                                     schedule=RING_SCHEDULES[cfg.attn_mode])
        elif cfg.attn_mode == "ulysses" and not self.is_initializing():
            from ..parallel import ulysses_attention
            k, v = repeat_kv(k, v)
            with _KERNEL():
                out = ulysses_attention(q, k, v, cfg.seq_axis, causal=True)
        else:
            if not prepared:
                with _PREPARE():
                    q = q / jnp.sqrt(head_dim).astype(cfg.dtype)
            path = "blocked" if blocked else "materialised"
            _LAST["head_dim"].set(head_dim)
            _LAST["kv_group"].set(group)
            _LAST["visible_tile_share"].set(
                visible_tile_share(rows, cfg.attn_mask, cfg.block_length,
                                   self.window)
                if blocked else 1.0)
            q_rows, kv_rows = (
                forward_rows(rows, cfg.attn_mask, cfg.block_length,
                             head_dim, self.window) if blocked else (0, 0))
            _LAST["forward_q_rows"].set(q_rows)
            _LAST["forward_kv_rows"].set(kv_rows)
            if blocked and grouped_selected(group, head_dim):
                _CALLS["blocked_grouped"].inc()
            else:
                k, v = repeat_kv(k, v)
            if cfg.attn_mask == "block_diffusion":
                _CALLS[path + "_block_diffusion"].inc()
                if blocked:
                    out = _sequence._block_diffusion_flash(
                        q, k, v, cfg.block_length, True, False,
                        qk_rows=prepared)
                else:
                    out = materialised_attention(
                        q, k, v, block_diffusion_mask(rows // 2,
                                                      cfg.block_length))
            elif self.window:
                _CALLS[path + "_window"].inc()
                out = (blocked_attention(q, k, v, qk_rows=prepared,
                                         window=self.window)
                       if blocked else materialised_attention(
                           q, k, v, window=self.window))
            else:
                _CALLS[path].inc()
                out = (blocked_attention(q, k, v, qk_rows=prepared)
                       if blocked else materialised_attention(q, k, v))
        # output proj: row-parallel
        with _PROJECT():
            return nn.DenseGeneral(cfg.d_model, axis=(-2, -1), name="o",
                                   dtype=cfg.dtype, param_dtype=jnp.float32,
                                   use_bias=False)(out)

    def _prepared_rows(self, q, k, positions, head_dim, rotary):
        """``q`` (pre-scaled) and ``k`` through their norms and rotation
        into the kernels' rows, one pass each (``ops/prepare_rows.py``),
        with the parameters the statements' ``RMSNorm`` holds."""
        from ..ops.prepare_rows import prepare_rows
        from . import operators

        cfg = self.cfg
        _CALLS["prepare_rows"].inc()
        scales = (tuple(operators.NormScale(name=name)(head_dim)
                        for name in ("q_norm", "k_norm"))
                  if cfg.qk_norm else (None, None))
        with _PREPARE():
            tables = (operators.rotary_tables(q.shape[1], head_dim,
                                              cfg.rope_theta, positions)
                      if rotary else None)
            return tuple(
                prepare_rows(t, scale, tables, eps=cfg.norm_eps,
                             prescale=is_query)
                for t, scale, is_query in ((q, scales[0], True),
                                           (k, scales[1], False)))


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with _scopes.MLP():
            h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, param_dtype=jnp.float32,
                         use_bias=False, name="wi")(x)
            h = nn.gelu(h)
            return nn.Dense(cfg.d_model, dtype=cfg.dtype,
                            param_dtype=jnp.float32, use_bias=False,
                            name="wo")(h)


class MoeMLP(nn.Module):
    """Expert-parallel MoE FFN: one expert per chip of ``cfg.moe_axis``,
    routed through :func:`horovod_tpu.parallel.moe_alltoall`.

    Expert weights are stored REPLICATED with a leading (n_experts, ...)
    dim (flax's param shape check ties the stored leaf to its declared
    shape, so a per-chip-sharded leaf cannot flow through ``self.param``).
    Each chip produces nonzero grads only for its own expert's slice, so
    the module pre-scales the selected expert weights' gradient by
    axis_size (a forward-identical ``w·n − stop_gradient(w)·(n−1)``):
    the framework's standard AVERAGE gradient sync then yields exactly
    the per-expert gradient, with no special-casing of expert leaves.
    For the memory-scaling expert-parallel layout (each chip storing only
    its expert), call :func:`~horovod_tpu.parallel.moe_alltoall` directly
    with your own parameter pytree, as ``examples/moe.py`` does — plain
    pytrees shard freely where flax module params cannot.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        n_e, d = cfg.moe_experts, cfg.d_model
        router = nn.Dense(n_e, name="router", dtype=jnp.float32,
                          param_dtype=jnp.float32, use_bias=False)
        init = nn.initializers.lecun_normal()
        w_in = self.param("w_in", init, (n_e, d, cfg.d_ff), jnp.float32)
        w_out = self.param("w_out", init, (n_e, cfg.d_ff, d), jnp.float32)
        b, s, _ = x.shape
        flat = x.reshape(b * s, d).astype(cfg.dtype)
        logits = router(flat)
        if self.is_initializing():
            # no mesh axis bound at init: a dense pass through expert 0
            # creates the params; routing never runs here
            h = nn.gelu(flat @ w_in[0].astype(cfg.dtype))
            return (h @ w_out[0].astype(cfg.dtype)).reshape(b, s, d)

        from ..parallel import moe_alltoall

        idx = jax.lax.axis_index(cfg.moe_axis)

        def grad_boost(w):
            # forward-identical (up to one rounding step), backward xn:
            # each chip contributes grads for ONE expert, so the AVERAGE
            # sync's 1/n is pre-cancelled here and expert leaves need no
            # special treatment in the optimizer
            return w * n_e - jax.lax.stop_gradient(w) * (n_e - 1)

        def expert_fn(t):
            # replicated leaves: select this chip's expert
            wi = jax.lax.dynamic_index_in_dim(w_in, idx, 0, keepdims=False)
            wo = jax.lax.dynamic_index_in_dim(w_out, idx, 0, keepdims=False)
            h = nn.gelu(t @ grad_boost(wi).astype(t.dtype))
            return h @ grad_boost(wo).astype(t.dtype)

        y, aux = moe_alltoall(flat, logits, expert_fn, cfg.moe_axis,
                              k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor)
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(b, s, d).astype(cfg.dtype)


def _norm(cfg, layernorm_name, rmsnorm_name):
    """The configuration's norm, under the name its parameters have in
    each spelling (LayerNorm's are the names flax gave them unasked)."""
    if cfg.norm == "layernorm":
        return nn.LayerNorm(dtype=cfg.dtype, param_dtype=jnp.float32,
                            name=layernorm_name)
    from . import operators

    # in the residual stream's dtype: each product casts its operand to
    # ``dtype`` itself, and a float32 router reads what was not rounded
    return operators.RMSNorm(cfg, dtype=cfg.residual_dtype,
                             name=rmsnorm_name)


class Block(nn.Module):
    """``x + operator(u)`` with ``u = norm(x)``, then ``x + ffn(norm(x))``.
    ``operator`` is attention, full or over a sliding window, or the gated
    short convolution (``layer_type``); ``ffn`` the configuration's MLP,
    or an expert layer (``experts``), whose router reads ``u`` where
    ``cfg.moe_router_input`` says so. ``rotary``: whether this layer's
    attention takes rotary positions (``cfg.rope_layout``)."""

    cfg: TransformerConfig
    layer_type: str = "full_attention"
    experts: bool = False
    rotary: bool = True

    @nn.compact
    def __call__(self, x, positions=None):
        cfg = self.cfg
        # the norms and the residual additions are the stream's passes
        # (scope ``model.stream``); the operator and the ffn name their own
        stream = _scopes.STREAM
        with stream():
            u = _norm(cfg, "LayerNorm_0", "operator_norm")(x)
        if self.layer_type == "conv":
            from . import operators

            y = operators.ShortConv(cfg, name="conv")(u)
        else:
            window = (cfg.sliding_window
                      if self.layer_type == "sliding_attention" else 0)
            y = Attention(cfg, window, self.rotary, name="attn")(u, positions)
        with stream():
            x = x + y
            y = _norm(cfg, "LayerNorm_1", "ffn_norm")(x)
        if cfg.moe_experts > 0:
            y = MoeMLP(cfg, name="moe_mlp")(y)
        elif self.experts:
            from . import operators

            route_from = (u if cfg.moe_router_input == "operator_input"
                          else None)
            y = operators.HeldExpertsMLP(cfg, name="moe")(y, route_from)
        elif cfg.mlp == "swiglu":
            from . import operators

            y = operators.GatedMLP(cfg, name="mlp")(y)
        else:
            y = MLP(cfg, name="mlp")(y)
        with stream():
            return x + y


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None):
        """Logits (batch, seq, vocabulary) in float32. ``positions``
        (batch, seq) int32: each row's position, where it is not its
        index along the sequence (rotary positions only). Under the
        block-diffusion mask ``tokens`` is the doubled sequence
        (batch, 2L) and the logits are the noised copy's L rows."""
        cfg = self.cfg
        if positions is not None and cfg.positions != "rotary":
            raise ValueError("positions are given per row to rotary "
                             "positions only")
        if positions is None and cfg.attn_mask == "block_diffusion":
            raise ValueError("the two copies of a doubled sequence share "
                             "their positions: give each row's")
        # looked up in the residual stream's dtype: a float32 stream
        # starts from the table's own values, not their bfloat16 rounding
        embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                         dtype=cfg.residual_dtype or cfg.dtype,
                         param_dtype=jnp.float32, name="embed")
        with _scopes.EMBED():
            x = embed(tokens)
            if cfg.positions == "learned":
                positions = jnp.arange(tokens.shape[1])
                if (cfg.attn_mode in SEQ_PARALLEL_MODES
                        and not self.is_initializing()):
                    # sequence-parallel: this shard holds a block of the
                    # global sequence — positions are offset by the block
                    # index
                    positions = positions + jax.lax.axis_index(
                        cfg.seq_axis) * tokens.shape[1]
                pos = nn.Embed(cfg.max_seq_len, cfg.d_model,
                               dtype=cfg.dtype, param_dtype=jnp.float32,
                               name="pos_embed")(positions)
                x = x + pos[None]
        types = cfg.layer_types or ("full_attention",) * cfg.num_layers
        rope = cfg.rope_layout or (1,) * cfg.num_layers
        block = nn.remat(Block) if cfg.remat_blocks else Block
        for i, layer_type in enumerate(types):
            x = block(cfg, layer_type,
                      experts=bool(cfg.moe_routed
                                   and i >= cfg.num_dense_layers),
                      rotary=bool(rope[i]), name=f"block_{i}")(x, positions)
        with _scopes.STREAM():
            if cfg.attn_mask == "block_diffusion":
                # the clean copy's rows were keys and values; no loss term
                # reads their final states
                x = x[:, :tokens.shape[1] // 2]
            x = _norm(cfg, "ln_f", "embedding_norm")(x)
        with _scopes.HEAD():
            if cfg.tie_embeddings:
                # float32 accumulation and logits; operands in ``dtype``
                return jnp.einsum("bsd,vd->bsv", x.astype(cfg.dtype),
                                  embed.embedding.astype(cfg.dtype),
                                  preferred_element_type=jnp.float32)
            logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              param_dtype=jnp.float32, use_bias=False,
                              name="lm_head")(x)
            return logits.astype(jnp.float32)


def param_shardings(params, *, tp_axis: str = "tp"):
    """PartitionSpec pytree for Megatron-style tensor parallelism:
    column-parallel qkv/wi (split output dim over tp), row-parallel o/wo
    (split input dim), embeddings split over vocab/d_ff-free dims."""

    def spec_for(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        joined = "/".join(str(n) for n in names)
        nd = leaf.ndim
        if "attn" in joined and any(f"/{p}/" in joined + "/" for p in ("q", "k", "v")):
            # (d_model, heads, head_dim): split heads over tp
            return P(None, tp_axis, None) if nd == 3 else P(None, tp_axis)
        if "/o/" in joined + "/":
            # (heads, head_dim, d_model): split heads over tp
            return P(tp_axis, None, None) if nd == 3 else P(tp_axis, None)
        if joined.endswith("wi/kernel"):
            return P(None, tp_axis)
        if joined.endswith("wo/kernel"):
            return P(tp_axis, None)
        if joined.endswith("lm_head/kernel"):
            return P(None, tp_axis)
        if joined == "embed/embedding":  # vocab table only; pos_embed stays
            return P(tp_axis, None)      # replicated (seq rarely divides tp)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec_for, params)
