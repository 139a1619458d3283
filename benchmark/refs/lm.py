"""GPT-2-style decoder (pre-LayerNorm, learned positions, biased
projections, tanh GELU, an untied bias-free head) forward, loss and
gradients, plain: float32, ``Precision.HIGHEST``, mean next-token cross
entropy. The parameter tree is the one the program's model declares;
nothing of the program is imported. Every projection of a block is a K-FAC
layer; the embeddings, the LayerNorms and the skipped head are not.

Rows are independent, so a batch runs in blocks of sequences and the
blocks' losses, gradients and factors are averaged: the whole batch in
float32 would not sit beside nothing else on a chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.refs import kfac

HI = lax.Precision.HIGHEST
LN_EPS = 1e-6  # the program's LayerNorm (flax's default), not GPT-2's 1e-5
BLOCK_ROWS = 2


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(x * x, axis=-1, keepdims=True) - mean * mean, 0.0
    )
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p['scale'] + p['bias']


def _dense(x, layer, slot):
    y = jnp.matmul(x, layer['kernel'], precision=HI) + layer['bias']
    if slot is None:
        return y, None
    return kfac.g_tap(y, slot, kfac.dense_g), kfac.dense_a(x, True)


def _block(p, slots, x, heads):
    def s(*names):
        if slots is None:
            return None
        out = slots
        for n in names:
            out = out[n]
        return out

    a = {'attn': {}}
    b, t, d = x.shape
    y = _ln(x, p['ln1'])
    q, a['attn']['q_proj'] = _dense(y, p['attn']['q_proj'], s('attn', 'q_proj'))
    k, a['attn']['k_proj'] = _dense(y, p['attn']['k_proj'], s('attn', 'k_proj'))
    v, a['attn']['v_proj'] = _dense(y, p['attn']['v_proj'], s('attn', 'v_proj'))
    q, k, v = (z.reshape(b, t, heads, d // heads) for z in (q, k, v))
    scores = jnp.einsum(
        'bqhd,bkhd->bhqk', q * (d // heads) ** -0.5, k, precision=HI
    )
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum(
        'bhqk,bkhd->bqhd', jax.nn.softmax(scores, axis=-1), v, precision=HI
    ).reshape(b, t, d)
    o, a['attn']['out_proj'] = _dense(
        out, p['attn']['out_proj'], s('attn', 'out_proj')
    )
    x = x + o
    y = _ln(x, p['ln2'])
    h, a['mlp_up'] = _dense(y, p['mlp_up'], s('mlp_up'))
    h = jax.nn.gelu(h, approximate=True)
    o, a['mlp_down'] = _dense(h, p['mlp_down'], s('mlp_down'))
    return x + o, a


def _forward(params, slots, tokens, targets, layers, heads):
    t = tokens.shape[-1]
    x = params['embed']['embedding'][tokens] + params['pos_embed'][:t]
    a = {}
    for i in range(layers):
        name = f'block{i}'
        x, a[name] = jax.checkpoint(
            lambda p, s, x: _block(p, s, x, heads)
        )(params[name], None if slots is None else slots[name], x)
    x = _ln(x, params['ln_f'])
    logits = jnp.matmul(x, params['lm_head']['kernel'], precision=HI)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), a


def kfac_layers(params) -> tuple[str, ...]:
    """Paths of the K-FAC layers: the six projections of every block."""
    out = []
    for name in sorted(k for k in params if k.startswith('block')):
        for proj in ('q_proj', 'k_proj', 'v_proj', 'out_proj'):
            out.append(f'{name}/attn/{proj}')
        out += [f'{name}/mlp_up', f'{name}/mlp_down']
    return tuple(out)


def _g_slots(params):
    def zero(layer):
        d = layer['kernel'].shape[-1]
        return jnp.zeros((d, d), jnp.float32)

    return {
        name: {
            'attn': {k: zero(v) for k, v in blk['attn'].items()},
            'mlp_up': zero(blk['mlp_up']),
            'mlp_down': zero(blk['mlp_down']),
        }
        for name, blk in params.items() if name.startswith('block')
    }


def _mean_over(fn, parts):
    """Mean of ``fn(*part)`` over the parts, one part resident at a time."""
    total = None
    for part in parts:
        out = fn(*part)
        total = out if total is None else jax.tree_util.tree_map(
            jnp.add, total, out
        )
    return jax.tree_util.tree_map(lambda x: x / len(parts), total)


def make(config: dict):
    """``(loss_and_grads, loss_grads_factors)``: see ``refs.vision.make``."""
    layers = config['model']['n_layer']
    heads = config['model']['n_head']

    @jax.jit
    def block_grads(params, tokens, targets):
        def f(p):
            return _forward(p, None, tokens, targets, layers, heads)[0]

        return jax.value_and_grad(f)(params)

    @jax.jit
    def block_factors(params, tokens, targets):
        def f(p, slots):
            return _forward(p, slots, tokens, targets, layers, heads)

        (loss, a), (grads, g) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True
        )(params, _g_slots(params))
        return loss, grads, kfac.flatten(a), kfac.flatten(g)

    def blocks(batch):
        tokens, targets = batch
        n = tokens.shape[0]
        step = BLOCK_ROWS if n % BLOCK_ROWS == 0 else 1
        return [
            (tokens[i:i + step], targets[i:i + step])
            for i in range(0, n, step)
        ]

    def loss_and_grads(params, batch):
        return _mean_over(
            lambda x, y: block_grads(params, x, y), blocks(batch)
        )

    def loss_grads_factors(params, batch):
        parts = blocks(batch)
        loss, grads, a, g = _mean_over(
            lambda x, y: block_factors(params, x, y), parts
        )
        # a block's output gradients are those of its own mean loss: of
        # the batch's mean loss they are 1/len(parts) of that, squared in G
        n = len(parts)
        return loss, grads, a, {k: v / (n * n) for k, v in g.items()}

    return loss_and_grads, loss_grads_factors
