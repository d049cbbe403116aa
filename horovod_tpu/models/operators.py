"""What ``TransformerConfig`` can select besides the GPT-2 block: RMS norm,
rotary positions, the gated MLP, the gated short convolution, and a
chip's share of routed experts. ``models/transformer.py`` imports this
module only for a configuration that names one of them, so a model that
names none loads nothing from here.

All of it is plain ``jax.numpy`` / ``jax.lax`` under flax modules (the
experts' grouped products are ``lax.ragged_dot``, behind
``parallel/moe.py`` ``grouped_matmul``). Parameters are float32,
matrix products run in ``cfg.dtype`` with float32 accumulation; norms,
the rotation's angles, the router and its scores are float32.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes as _scopes


def _dense(cfg, features, name):
    return nn.Dense(features, dtype=cfg.dtype, param_dtype=jnp.float32,
                    use_bias=False, name=name)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last dimension, the
    statistics in float32, the result in ``dtype`` (None: ``cfg.dtype``)."""

    cfg: object
    dtype: object = None

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = x.astype(jnp.float32)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + self.cfg.norm_eps)
        return (y * scale).astype(self.dtype or self.cfg.dtype)


def rotary(q, k, theta, positions=None):
    """Rotary positions over the whole head, rotate-half pairing
    (dimension i with i + d/2), of (batch, seq, heads, head_dim):
    positions 0.. along axis 1, or each row's own from ``positions``
    (batch, seq) int32. Angles and the rotation in float32."""
    seq, d = q.shape[1], q.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    at = jnp.arange(seq) if positions is None else positions
    angles = at.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[..., None, :]

    def rotate(x):
        y = x.astype(jnp.float32)
        half = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], -1)
        return (y * cos + half * sin).astype(x.dtype)

    return rotate(q), rotate(k)


class GatedMLP(nn.Module):
    """SwiGLU: ``w2(silu(w1 x) * w3 x)`` at ``cfg.d_ff``."""

    cfg: object

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with _scopes.MLP():
            h = nn.silu(_dense(cfg, cfg.d_ff, "w1")(x)) \
                * _dense(cfg, cfg.d_ff, "w3")(x)
            return _dense(cfg, cfg.d_model, "w2")(h)


class ShortConv(nn.Module):
    """The gated short convolution: ``[B, C, x] = split3(in_proj u)``,
    ``z = B * x``, a depthwise causal convolution of ``cfg.conv_kernel``
    taps along the sequence (``c[t] = sum_j w[j] * z[t - K + 1 + j]``,
    zeros before the sequence), ``out_proj(C * c)``. No biases."""

    cfg: object

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        taps = cfg.conv_kernel
        with _scopes.CONV():
            gate_in, gate_out, x = jnp.split(
                _dense(cfg, 3 * cfg.d_model, "in_proj")(u), 3, axis=-1)
            # fan-in of a depthwise filter is its taps
            kernel = self.param(
                "kernel", nn.initializers.variance_scaling(
                    1.0, "fan_in", "truncated_normal", in_axis=0,
                    out_axis=1),
                (taps, cfg.d_model), jnp.float32).astype(cfg.dtype)
            z = gate_in * x
            seq = z.shape[1]
            padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
            c = sum(padded[:, j:j + seq] * kernel[j] for j in range(taps))
            return _dense(cfg, cfg.d_model, "out_proj")(gate_out * c)


class HeldExpertsMLP(nn.Module):
    """This chip's share of an expert layer
    (:func:`horovod_tpu.parallel.moe_held_experts`): a router over all
    ``cfg.moe_routed`` experts, scored as ``cfg.moe_scoring`` says
    (sigmoids plus a selection bias, or a softmax over all of them),
    ``cfg.moe_top_k`` picks a token, and the SwiGLU experts
    ``cfg.moe_held`` = (first, count) held here, as grouped matrix
    products over rows sorted by expert. No token is dropped; what the
    experts held elsewhere would add is left out.

    The router's product, scores and top-k are float32 at the highest
    matmul precision: on a TPU a float32 product is otherwise rounded to
    bfloat16 operands, and a score that moves by 1e-2 changes which
    expert a token's last pick is.

    Collection ``"routing"``: ``expert_bias`` (moe_routed,), seeded small
    and not trained (it takes part in the selection only; sigmoid
    scoring alone has one); with
    ``mutable=["routing"]`` each call also leaves ``expert_load``
    (moe_routed,), ``rows_held`` () and ``buffer_rows`` (): the picks
    every expert got from this call's tokens, how many landed here, and
    the rows of the buffer's chunks the call ran (one chunk, twice the
    expected load, while the load fits it; at most tokens x top-k).
    """

    cfg: object

    @nn.compact
    def __call__(self, x):
        from ..parallel import moe

        cfg = self.cfg
        first, count = cfg.moe_held
        d, f, routed = cfg.d_model, cfg.moe_d_ff, cfg.moe_routed
        # fan-in per expert: the leading dimension is a batch of matrices
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", batch_axis=(0,))
        w1 = self.param("w1", init, (count, d, f), jnp.float32)
        w3 = self.param("w3", init, (count, d, f), jnp.float32)
        w2 = self.param("w2", init, (count, f, d), jnp.float32)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, routed), jnp.float32)
        if cfg.moe_scoring == "sigmoid_bias":
            bias = self.variable(
                "routing", "expert_bias",
                lambda: 0.01 * jax.random.normal(self.make_rng("params"),
                                                 (routed,), jnp.float32))

        batch, seq, _ = x.shape
        flat = x.reshape(batch * seq, d)
        # the router reads its input as it came (float32 from a float32
        # stream); the experts read it in the compute dtype
        with moe.SCOPE_ROUTE():
            logits = jnp.dot(flat.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
        with moe.SCOPE_DISPATCH():
            flat = flat.astype(cfg.dtype)
        if cfg.moe_scoring == "sigmoid_bias":
            expert_idx, weights = moe.route_sigmoid_top_k(
                logits, bias.value, cfg.moe_top_k,
                renormalize=cfg.moe_renormalize, scaling=cfg.moe_scaling)
        else:
            expert_idx, weights = moe.route_top_k(
                logits, cfg.moe_top_k, renormalize=cfg.moe_renormalize)
            with moe.SCOPE_ROUTE():
                weights = weights * cfg.moe_scaling
        self.sow("intermediates", "expert_idx", expert_idx)

        # cast here, once: what `experts` closes over is what
        # moe_held_experts keeps between the forward and backward pass
        with moe.SCOPE_EXPERTS():
            w1, w3, w2 = (w.astype(cfg.dtype) for w in (w1, w3, w2))

        def experts(rows, group_sizes):
            h = nn.silu(moe.grouped_matmul(rows, w1, group_sizes)) \
                * moe.grouped_matmul(rows, w3, group_sizes)
            return moe.grouped_matmul(h, w2, group_sizes)

        y, load = moe.moe_held_experts(
            flat, expert_idx, weights, experts, first=first, count=count,
            n_routed=routed)
        if self.is_mutable_collection("routing"):
            for name, value in load.items():
                self.variable("routing", name, lambda: value).value = value
        with moe.SCOPE_COMBINE():
            return y.reshape(batch, seq, d).astype(cfg.dtype)
