"""ResNet (bottleneck, v1.5: the stride sits in the 3x3) forward, loss and
gradients, plain: float32, ``Precision.HIGHEST``, train-mode BatchNorm on
the batch's own statistics, label-smoothed cross entropy. The parameter
tree is the one the program's model declares (names and shapes); nothing
of the program is imported. Every convolution and the head are K-FAC
layers; BatchNorm is not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.refs import kfac

HI = lax.Precision.HIGHEST
BN_EPS = 1e-5
DIMS = ('NHWC', 'HWIO', 'NHWC')


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.maximum(jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean, 0.0)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p['scale'] + p['bias']


def _conv(x, layer, slot, stride, padding):
    """A K-FAC convolution: output, and its A factor when ``slot`` (the
    zero whose gradient becomes the G factor) is given."""
    k = layer['kernel']
    y = lax.conv_general_dilated(
        x, k, (stride, stride), padding, dimension_numbers=DIMS,
        precision=HI,
    )
    if slot is None:
        return y, None
    a = kfac.conv_a(x, k.shape[:2], (stride, stride), padding)
    return kfac.g_tap(y, slot, kfac.conv_g), a


def _block(p, slots, x, stride):
    """conv1 1x1 -> conv2 3x3 (strided) -> conv3 1x1, projection shortcut
    where the shape changes."""
    def s(name):
        return None if slots is None else slots[name]

    a = {}
    y, a['conv1'] = _conv(x, p['conv1'], s('conv1'), 1, 'SAME')
    y = jax.nn.relu(_bn(y, p['bn1']))
    y, a['conv2'] = _conv(y, p['conv2'], s('conv2'), stride, 'SAME')
    y = jax.nn.relu(_bn(y, p['bn2']))
    y, a['conv3'] = _conv(y, p['conv3'], s('conv3'), 1, 'SAME')
    y = _bn(y, p['bn3'])
    if 'proj' in p:
        x, a['proj'] = _conv(x, p['proj'], s('proj'), stride, 'SAME')
        x = _bn(x, p['bn_proj'])
    return jax.nn.relu(y + x), a


def _forward(params, slots, images, labels, stage_sizes, smoothing):
    """Mean loss, and {layer: A factor} when ``slots`` is given."""
    def s(name):
        return None if slots is None else slots[name]

    a = {}
    x, a['conv0'] = _conv(
        images, params['conv0'], s('conv0'), 2, [(3, 3), (3, 3)]
    )
    x = jax.nn.relu(_bn(x, params['bn0']))
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)],
    )
    for stage, blocks in enumerate(stage_sizes):
        for b in range(blocks):
            name = f'stage{stage}_block{b}'
            stride = 2 if stage > 0 and b == 0 else 1
            # remat: a block keeps only its input for the backward pass
            x, a[name] = jax.checkpoint(
                functools.partial(_block, stride=stride)
            )(params[name], s(name), x)
    x = jnp.mean(x, axis=(1, 2))
    head = params['head']
    logits = jnp.matmul(x, head['kernel'], precision=HI) + head['bias']
    if slots is not None:
        a['head'] = kfac.dense_a(x, True)
        logits = kfac.g_tap(logits, slots['head'], kfac.dense_g)
    classes = logits.shape[-1]
    soft = (
        jax.nn.one_hot(labels, classes) * (1.0 - smoothing)
        + smoothing / classes
    )
    loss = -jnp.mean(jnp.sum(soft * jax.nn.log_softmax(logits), axis=-1))
    return loss, a


def kfac_layers(params) -> tuple[str, ...]:
    """Paths of the K-FAC layers: every sub-tree that has a ``kernel``."""
    out = []

    def walk(tree, prefix):
        if 'kernel' in tree:
            out.append(prefix)
            return
        for k, v in tree.items():
            if isinstance(v, dict):
                walk(v, f'{prefix}/{k}' if prefix else k)

    walk(params, '')
    return tuple(out)


def _g_slots(params):
    def walk(tree):
        if 'kernel' in tree:
            d = tree['kernel'].shape[-1]
            return jnp.zeros((d, d), jnp.float32)
        return {k: walk(v) for k, v in tree.items()
                if isinstance(v, dict) and _has_kernel(v)}

    return walk(params)


def _has_kernel(tree) -> bool:
    return 'kernel' in tree or any(
        isinstance(v, dict) and _has_kernel(v) for v in tree.values()
    )


def make(config: dict):
    """``(loss_and_grads, loss_grads_factors)`` for this configuration.

    ``loss_and_grads(params, batch) -> (loss, grads)``;
    ``loss_grads_factors(params, batch) -> (loss, grads, A, G)`` with the
    factors keyed by layer path."""
    stages = tuple(config['model']['stage_sizes'])
    smoothing = config['label_smoothing']

    @jax.jit
    def loss_and_grads(params, batch):
        images, labels = batch

        def f(p):
            return _forward(p, None, images, labels, stages, smoothing)[0]

        return jax.value_and_grad(f)(params)

    @jax.jit
    def loss_grads_factors(params, batch):
        images, labels = batch

        def f(p, slots):
            return _forward(p, slots, images, labels, stages, smoothing)

        (loss, a), (grads, g) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True
        )(params, _g_slots(params))
        return loss, grads, kfac.flatten(a), kfac.flatten(g)

    return loss_and_grads, loss_grads_factors
