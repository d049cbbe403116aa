"""ResNet v1.5 family in flax, TPU-first.

The benchmark workhorse: the reference's headline numbers are ResNet-50/101
synthetic throughput and scaling efficiency
(``/root/reference/docs/benchmarks.rst:13-43``, harness
``/root/reference/examples/tensorflow2/tensorflow2_synthetic_benchmark.py``).

TPU-first choices: NHWC layout (XLA's native conv layout on TPU), bfloat16
compute with float32 parameters/batch-stats, and channel counts that are
multiples of 128 so convs tile cleanly onto the 128x128 MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp

from . import scopes as _scopes

ModuleDef = Any

# Device scopes (docs/timeline.md): a block's convolutions under
# ``model.conv``; batch statistics and normalisation, with the
# activations and residual additions the compiler fuses into them, under
# ``model.batch_norm``.
_CONV, _NORM = _scopes.CONV, _scopes.BATCH_NORM


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        with _CONV():
            y = self.conv(self.filters, (1, 1))(x)
        with _NORM():
            y = self.norm()(y)
            y = self.act(y)
        with _CONV():
            y = self.conv(self.filters, (3, 3),
                          (self.strides, self.strides))(y)
        with _NORM():
            y = self.norm()(y)
            y = self.act(y)
        with _CONV():
            y = self.conv(self.filters * 4, (1, 1))(y)
        with _NORM():
            y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            with _CONV():
                residual = self.conv(self.filters * 4, (1, 1),
                                     (self.strides, self.strides),
                                     name="conv_proj")(residual)
            with _NORM():
                residual = self.norm(name="norm_proj")(residual)
        with _NORM():
            return self.act(residual + y)


class BasicBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        with _CONV():
            y = self.conv(self.filters, (3, 3),
                          (self.strides, self.strides))(x)
        with _NORM():
            y = self.norm()(y)
            y = self.act(y)
        with _CONV():
            y = self.conv(self.filters, (3, 3))(y)
        with _NORM():
            y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            with _CONV():
                residual = self.conv(self.filters, (1, 1),
                                     (self.strides, self.strides),
                                     name="conv_proj")(residual)
            with _NORM():
                residual = self.norm(name="norm_proj")(residual)
        with _NORM():
            return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    axis_name: str | None = None  # set to sync batch-norm over the mesh axis

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       param_dtype=jnp.float32,
                       axis_name=self.axis_name if train else None)
        with _CONV():
            x = x.astype(self.dtype)
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        with _NORM():
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
        with _scopes.POOL():
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(self.num_filters * 2 ** i, strides,
                                   conv=conv, norm=norm, act=nn.relu)(x)
        with _scopes.POOL():
            x = jnp.mean(x, axis=(1, 2))
        with _scopes.HEAD():
            x = nn.Dense(self.num_classes, dtype=self.dtype,
                         param_dtype=jnp.float32)(x)
            return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)
