"""ResNets: CIFAR-style (20/32/56) and ImageNet-style (50), NHWC flax.

Model-family parity with the reference's vision examples
(examples/vision/cifar_resnet.py — CIFAR ResNet-20/32/56 with basic blocks
and identity-pad shortcuts; examples/torch_imagenet_resnet.py — torchvision
ResNet-50). Re-implemented TPU-first: NHWC layout (TPU conv native), bf16-
friendly (params/BN in fp32, activations castable), batch stats in a flax
``batch_stats`` collection.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfac_tpu import tracing

ModuleDef = Any


class BasicBlock(nn.Module):
    """3x3 + 3x3 residual block (CIFAR ResNets)."""

    filters: int
    strides: int = 1
    norm: ModuleDef = nn.BatchNorm
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        residual = x
        y = nn.Conv(
            self.filters, (3, 3), strides=self.strides, padding='SAME',
            use_bias=False, dtype=self.dtype, name='conv1',
        )(x)
        y = self.norm(name='bn1')(y)
        y = nn.relu(y)
        y = nn.Conv(
            self.filters, (3, 3), padding='SAME', use_bias=False,
            dtype=self.dtype, name='conv2',
        )(y)
        y = self.norm(name='bn2')(y)
        if residual.shape != y.shape:
            # Option-A shortcut from the original CIFAR ResNet: stride the
            # identity and zero-pad channels — parameter-free, so K-FAC sees
            # exactly the conv layers.
            residual = residual[:, :: self.strides, :: self.strides, :]
            pad = self.filters - residual.shape[-1]
            residual = jnp.pad(
                residual, ((0, 0), (0, 0), (0, 0), (pad // 2, pad - pad // 2))
            )
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck (ImageNet ResNets)."""

    filters: int
    strides: int = 1
    norm: ModuleDef = nn.BatchNorm
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype, name='conv1')(x)
        y = self.norm(name='bn1')(y)
        y = nn.relu(y)
        y = nn.Conv(
            self.filters, (3, 3), strides=self.strides, padding='SAME',
            use_bias=False, dtype=self.dtype, name='conv2',
        )(y)
        y = self.norm(name='bn2')(y)
        y = nn.relu(y)
        y = nn.Conv(4 * self.filters, (1, 1), use_bias=False, dtype=self.dtype, name='conv3')(y)
        y = self.norm(name='bn3', scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(
                4 * self.filters, (1, 1), strides=self.strides,
                use_bias=False, dtype=self.dtype, name='proj',
            )(residual)
            residual = self.norm(name='bn_proj')(residual)
        return nn.relu(y + residual)


class CifarResNet(nn.Module):
    """ResNet-(6n+2) for 32x32 inputs (n blocks per stage, 3 stages)."""

    depth: int = 20
    num_classes: int = 10
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        if (self.depth - 2) % 6 != 0:
            raise ValueError('CIFAR ResNet depth must be 6n+2')
        n = (self.depth - 2) // 6
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            dtype=jnp.float32,
        )
        with tracing.model_scope('stem'):
            x = nn.Conv(16, (3, 3), padding='SAME', use_bias=False, dtype=self.dtype, name='conv0')(x)
            x = norm(name='bn0')(x)
            x = nn.relu(x)
        for stage, filters in enumerate((16, 32, 64)):
            for block in range(n):
                strides = 2 if stage > 0 and block == 0 else 1
                with tracing.model_scope(f'stage{stage}'):
                    x = BasicBlock(
                        filters, strides=strides, norm=norm, dtype=self.dtype,
                        name=f'stage{stage}_block{block}',
                    )(x)
        with tracing.model_scope('head'):
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(self.num_classes, name='head')(
                x.astype(jnp.float32)
            )


class ImageNetResNet(nn.Module):
    """Bottleneck ResNet for 224x224 inputs (depth 50/101/152)."""

    stage_sizes: Sequence[int] = (3, 4, 6, 3)  # ResNet-50
    num_classes: int = 1000
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            dtype=jnp.float32,
        )
        with tracing.model_scope('stem'):
            x = nn.Conv(
                64, (7, 7), strides=2, padding=[(3, 3), (3, 3)],
                use_bias=False, dtype=self.dtype, name='conv0',
            )(x)
            x = norm(name='bn0')(x)
            x = nn.relu(x)
            x = nn.max_pool(
                x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)]
            )
        for stage, (blocks, filters) in enumerate(
            zip(self.stage_sizes, (64, 128, 256, 512))
        ):
            for block in range(blocks):
                strides = 2 if stage > 0 and block == 0 else 1
                with tracing.model_scope(f'stage{stage}'):
                    x = BottleneckBlock(
                        filters, strides=strides, norm=norm, dtype=self.dtype,
                        name=f'stage{stage}_block{block}',
                    )(x)
        with tracing.model_scope('head'):
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(self.num_classes, name='head')(
                x.astype(jnp.float32)
            )


def resnet20(**kw) -> CifarResNet:
    return CifarResNet(depth=20, **kw)


def resnet32(**kw) -> CifarResNet:
    return CifarResNet(depth=32, **kw)


def resnet56(**kw) -> CifarResNet:
    return CifarResNet(depth=56, **kw)


def resnet50(**kw) -> ImageNetResNet:
    return ImageNetResNet(stage_sizes=(3, 4, 6, 3), **kw)
