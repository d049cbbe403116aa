"""ResNet v1.5 (bottleneck) for the benchmark.

Four things live here, all driven by the configuration file's sizes:

* ``make_model`` / ``init`` / ``loss`` - the repo's own flax model
  (``horovod_tpu.models.ResNet``) and the loss a user of it trains on;
* ``make_batch`` - one seeded batch, made on the device;
* ``model_flops`` - the operations one training step requires;
* ``reference_loss`` - the same forward pass and loss in plain float32
  ``jax.numpy``, for ``correct``.

Departures of the repo's model from He et al. (arXiv:1512.03385), which
the reference follows so that both compute the same function: the stride
of a down-sampling block sits on its 3x3 convolution ("v1.5", as in the
reference benchmark's Keras model, not on the first 1x1 as in the paper);
the last batch norm of every block starts with scale 0; no weight decay.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

# Tolerances of `correct`, and why (run.py:check_reference). The system
# computes in bfloat16 (rounding 2**-9 per operation) with float32
# accumulation, parameters and batch statistics; the reference in float32
# throughout. Through 53 convolutions, each followed by a batch norm whose
# backward pass subtracts nearly equal sums, the relative L2 error of the
# whole gradient measured on the chip at the published widths is
# 0.047-0.051 on 16 images (my chip runs, PR 22; float32 against the
# reference: 7e-6, benchmark/tests). The limit is three times that. A step
# computed one precision below - float8 e4m3 rounds 16 times coarser than
# bfloat16 - lands far outside it. The loss agrees to 2e-4 (limit 1e-2).
GRAD_REL_TOL = 0.15
LOSS_REL_TOL = 1e-2


def make_model(config, axis_name=None):
    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import BottleneckBlock

    return ResNet(stage_sizes=list(config["stage_sizes"]),
                  block_cls=BottleneckBlock,
                  num_classes=config["num_classes"],
                  num_filters=config["num_filters"],
                  dtype=jnp.dtype(config["compute_dtype"]),
                  axis_name=axis_name)


def init(model, config, key):
    """``(params, aux)``; call under ``jax.jit`` (an eager flax init
    dispatches hundreds of one-operation programs)."""
    size = config["image_size"]
    variables = model.init(
        key, jnp.zeros((1, size, size, config["channels"]), jnp.float32),
        train=True)
    return variables["params"], variables["batch_stats"]


def optimizer(config):
    opt = config["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"resnet: no optimizer {opt['name']!r}")
    return optax.sgd(opt["learning_rate"], momentum=opt["momentum"])


def make_batch(config, key, batch, seq_len=None):
    """Standard-normal float32 images and uniform labels, as the
    reference's synthetic benchmark feeds."""
    k_img, k_lab = jax.random.split(key)
    size = config["image_size"]
    images = jax.random.normal(
        k_img, (batch, size, size, config["channels"]), jnp.float32)
    labels = jax.random.randint(k_lab, (batch,), 0, config["num_classes"])
    return images, labels


def _cross_entropy(logits, labels, classes):
    one_hot = jax.nn.one_hot(labels, classes)
    return -jnp.mean(jnp.sum(one_hot * jax.nn.log_softmax(logits), -1))


def loss(model, params, aux, batch):
    """``(loss, new_aux)`` of the repo's model in training mode."""
    images, labels = batch
    logits, mutated = model.apply({"params": params, "batch_stats": aux},
                                  images, train=True, mutable=["batch_stats"])
    return (_cross_entropy(logits, labels, model.num_classes),
            mutated["batch_stats"])


# --------------------------------------------------------------------------
# operations of one training step
# --------------------------------------------------------------------------

def conv_table(config):
    """Every convolution as ``(name, out_hw, kernel, c_in, c_out)``, in
    forward order, then the classifier as a 1x1 'convolution' on a 1x1
    map. The one place the architecture's shapes are walked."""
    hw = config["image_size"] // 2          # conv_init: 7x7, stride 2
    rows = [("conv_init", hw, 7, config["channels"], config["num_filters"])]
    hw //= 2                                # max pool 3x3, stride 2
    c_in = config["num_filters"]
    for i, blocks in enumerate(config["stage_sizes"]):
        f = config["num_filters"] * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            tag = f"stage{i}.block{j}"
            rows.append((f"{tag}.conv1", hw, 1, c_in, f))
            hw //= stride                   # v1.5: the 3x3 strides
            rows.append((f"{tag}.conv2", hw, 3, f, f))
            rows.append((f"{tag}.conv3", hw, 1, f, 4 * f))
            if j == 0:                      # shape changes: projection
                rows.append((f"{tag}.proj", hw, 1, c_in, 4 * f))
            c_in = 4 * f
    rows.append(("fc", 1, 1, c_in, config["num_classes"]))
    return rows


def model_flops(config, batch, seq_len=None):
    """Floating-point operations one training step requires at ``batch``
    images: 2 per multiply-accumulate of every convolution and of the
    classifier; forward once, backward twice (gradient of the input and
    of the weights), except that the first convolution needs no gradient
    of its input (the images are data). Batch norm, ReLU, pooling, the
    loss and the optimizer are not counted (the usual convention: they
    are bandwidth, not arithmetic), and nothing recomputed is."""
    total = 0
    for name, hw, k, c_in, c_out in conv_table(config):
        macs = hw * hw * k * k * c_in * c_out
        total += 2 * macs * (2 if name == "conv_init" else 3)
    return float(total * batch)


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def _conv(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p, stats, momentum=0.9, eps=1e-5):
    """Training-mode batch norm over (N, H, W); returns the output and
    the new running statistics."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    new = {"mean": momentum * stats["mean"] + (1 - momentum) * mean,
           "var": momentum * stats["var"] + (1 - momentum) * var}
    return y, new


def reference_loss(config, params, aux, batch):
    """``(loss, new_aux)``: ResNet v1.5 forward in training mode and the
    softmax cross-entropy, in float32 at the highest matmul precision, on
    the system's own parameter tree. No flax, no bfloat16."""
    images, labels = batch
    new_aux = {}

    def bn(x, scope, name):
        y, new = _batch_norm(x, scope[0][name], scope[1][name])
        scope[2][name] = new
        return y

    with jax.default_matmul_precision("highest"):
        top = (params, aux, new_aux)
        x = _conv(images.astype(jnp.float32), params["conv_init"]["kernel"],
                  2, [(3, 3), (3, 3)])
        x = jax.nn.relu(bn(x, top, "bn_init"))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        n = 0
        for i, blocks in enumerate(config["stage_sizes"]):
            for j in range(blocks):
                stride = 2 if i > 0 and j == 0 else 1
                name = f"BottleneckBlock_{n}"
                n += 1
                p = params[name]
                new_aux[name] = {}
                scope = (p, aux[name], new_aux[name])
                y = _conv(x, p["Conv_0"]["kernel"], 1, "SAME")
                y = jax.nn.relu(bn(y, scope, "BatchNorm_0"))
                # flax's "SAME" at stride 2 pads (0, 1) on a 3x3 kernel
                # over an even map; lax computes the same split
                y = _conv(y, p["Conv_1"]["kernel"], stride, "SAME")
                y = jax.nn.relu(bn(y, scope, "BatchNorm_1"))
                y = _conv(y, p["Conv_2"]["kernel"], 1, "SAME")
                y = bn(y, scope, "BatchNorm_2")
                if "conv_proj" in p:
                    x = _conv(x, p["conv_proj"]["kernel"], stride, "SAME")
                    x = bn(x, scope, "norm_proj")
                x = jax.nn.relu(x + y)
        x = jnp.mean(x, axis=(1, 2))
        logits = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
        return _cross_entropy(logits, labels, config["num_classes"]), new_aux
