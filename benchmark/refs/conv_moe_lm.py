"""Conv-hybrid sparse decoder (the LFM2-MoE family's layer pattern) forward,
loss and gradients, plain: float32, ``Precision.HIGHEST``, mean next-token
cross entropy over the vocabulary held. The parameter tree is the one the
program's model declares; nothing of the program is imported.

By the configuration's equations (``benchmark/configs/lfm2-24b-a2b.json``
has the source and every departure), ``u`` a block's normed input:

- plain RMSNorm ``x rsqrt(mean x^2 + eps) w``; blocks ``h = x + op(norm
  x); x' = h + ffn(norm h)``; block ``i``'s ``op`` by the run's
  ``layer_types[i]``, its ``ffn`` the dense gated MLP for ``i <
  num_dense_layers`` and the routed experts after;
- gated short convolution: ``B = u W_B, C = u W_C, x~ = u W_x``; ``z = B *
  x~``; ``c_t = sum_j k_j * z_{t - L + 1 + j}`` as ``L`` shifted products,
  zeros left of the sequence; ``op = (C * c) W_out``;
- attention with whole score matrices, a key/value head's group of query
  heads at a time: per-head RMSNorm on ``q`` and ``k``, rotary positions
  on the whole head in halves, causal softmax ``q k^T / sqrt(head_dim)``;
- routing ``s = sigmoid(x W_r)``; the experts chosen by ``top_k(s + b)``;
  their weights ``s`` there, over ``sum + 1e-6``, times
  ``routed_scaling_factor``; experts by boolean masks over the experts
  held: each held expert sees every token with the rows not routed to it
  zeroed. What absent experts would add is left out, as in the program;
- logits ``norm(x) E^T`` with the embedding ``E`` tied.

Every projection is a K-FAC layer but the dense MLP's three (first-order
update: the cell skips them), each routed expert's three apart with A and
G over *its own rows* (sums over the rows routed to it, divided by their
count). Not K-FAC: embedding, norm weights, the depthwise kernels,
``expert_bias``.

Sequences are independent, so a batch runs in blocks of sequences whose
losses, gradients and factor sums are combined (``refs/lm.py`` does the
same).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.refs import kfac

HI = lax.Precision.HIGHEST
BLOCK_ROWS = 1          # sequences a block
EXPERT_PROJS = ('gate_proj', 'up_proj', 'down_proj')
DENSE_MLP = 'mlp'       # the module K-FAC leaves to the first-order update


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _sum_sq(g):
    rows = g.reshape(-1, g.shape[-1])
    return jnp.matmul(rows.T, rows, precision=HI)


def _dense(x, layer, slot):
    y = jnp.matmul(x, layer['kernel'], precision=HI)
    if slot is None:
        return y, None
    return kfac.g_tap(y, slot, kfac.dense_g), kfac.dense_a(x, False)


def _short_conv(p, s, u, m):
    a, out = {}, {}
    for name in ('b_proj', 'c_proj', 'x_proj'):
        out[name], a[name] = _dense(u, p[name], s and s[name])
    kernel = p['conv']['kernel'][:, 0]                   # (L, channels)
    taps, t = kernel.shape[0], u.shape[1]
    z = out['b_proj'] * out['x_proj']
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(zp[:, j:j + t] * kernel[j] for j in range(taps))
    y, a['out_proj'] = _dense(
        out['c_proj'] * c, p['out_proj'], s and s['out_proj']
    )
    return y, a


def _rotary(x, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, s, u, m):
    a, out = {}, {}
    b_, t, _ = u.shape
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    hd, eps = m['head_dim'], m['norm_eps']
    for name in ('q_proj', 'k_proj', 'v_proj'):
        out[name], a[name] = _dense(u, p[name], s and s[name])
    q = _rotary(_rms(
        out['q_proj'].reshape(b_, t, h, hd), p['q_layernorm']['scale'], eps
    ), m['rope_theta'])
    k = _rotary(_rms(
        out['k_proj'].reshape(b_, t, hkv, hd), p['k_layernorm']['scale'], eps
    ), m['rope_theta'])
    v = out['v_proj'].reshape(b_, t, hkv, hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def group(xs):
        """One key/value head with its query heads: ``(B, T, h / hkv,
        hd)`` queries, whole ``(T, T)`` score matrices."""
        q_g, k_g, v_g = xs
        scores = jnp.einsum(
            'bqhd,bkd->bhqk', q_g * hd ** -0.5, k_g, precision=HI
        )
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        return jnp.einsum(
            'bhqk,bkd->bqhd', jax.nn.softmax(scores, axis=-1), v_g,
            precision=HI,
        )

    o = lax.map(group, (
        jnp.moveaxis(q.reshape(b_, t, hkv, h // hkv, hd), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0),
    ))                                              # (hkv, B, T, h / hkv, hd)
    o = jnp.moveaxis(o, 0, 2).reshape(b_, t, h * hd)
    y, a['o_proj'] = _dense(o, p['o_proj'], s and s['o_proj'])
    return y, a


def _gated_mlp(p, x):
    g = jnp.matmul(x, p['gate_proj']['kernel'], precision=HI)
    u = jnp.matmul(x, p['up_proj']['kernel'], precision=HI)
    return jnp.matmul(
        jax.nn.silu(g) * u, p['down_proj']['kernel'], precision=HI
    )


def _route(logits, bias, m):
    """The chosen experts of every token ``(tokens, k)`` and their
    weights."""
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(
        scores + bias if m['use_expert_bias'] else scores,
        m['num_experts_per_tok'],
    )
    wts = jnp.take_along_axis(scores, idx, axis=-1)
    if m['norm_topk_prob']:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-6)
    return wts * m['routed_scaling_factor'], idx


def _moe(p, s, x, m):
    """Returns the layer's output, the A entries (a held expert's are
    *sums* over its rows) and the rows of each held expert."""
    a = {'experts': {n: {} for n in EXPERT_PROJS}}
    first, held = m['experts_held']
    xf = x.reshape(-1, x.shape[-1])
    logits, a['router'] = _dense(xf, p['router'], s and s['router'])
    wts, idx = _route(logits, p.get('expert_bias'), m)
    y = jnp.zeros_like(xf)
    rows = []
    ex = p['experts']
    for j in range(held):
        name = f'e{j}'
        hit = idx == first + j                          # (tokens, k)
        mask = jnp.any(hit, -1)
        w = jnp.sum(jnp.where(hit, wts, 0.0), -1)
        rows.append(jnp.sum(mask))
        xe = jnp.where(mask[:, None], xf, 0.0)
        parts = {}
        for proj in ('gate_proj', 'up_proj'):
            out = jnp.matmul(xe, ex[proj][name]['kernel'], precision=HI)
            if s is not None:
                out = kfac.g_tap(out, s['experts'][proj][name], _sum_sq)
                a['experts'][proj][name] = _sum_sq(xe)
            parts[proj] = out
        hid = jax.nn.silu(parts['gate_proj']) * parts['up_proj']
        out = jnp.matmul(hid, ex['down_proj'][name]['kernel'], precision=HI)
        if s is not None:
            out = kfac.g_tap(out, s['experts']['down_proj'][name], _sum_sq)
            a['experts']['down_proj'][name] = _sum_sq(hid)
        y = y + w[:, None] * out
    return y.reshape(x.shape), a, jnp.stack(rows)


def _block(p, s, x, kind, m):
    eps = m['norm_eps']
    mixer = _short_conv if kind == 'conv' else _attention
    y, a_mixer = mixer(
        p['mixer'], s and s['mixer'], _rms(x, p['norm1']['scale'], eps), m
    )
    x = x + y
    u = _rms(x, p['norm2']['scale'], eps)
    if DENSE_MLP in p:
        return x + _gated_mlp(p[DENSE_MLP], u), {'mixer': a_mixer}, None
    y, a_moe, rows = _moe(p['moe'], s and s['moe'], u, m)
    return x + y, {'mixer': a_mixer, 'moe': a_moe}, rows


def _forward(params, slots, tokens, targets, m):
    table = params['embed']['embedding']
    x = table[tokens]
    a, rows = {}, {}
    for i, kind in enumerate(m['layer_types']):
        name = f'block{i}'
        x, a[name], got = jax.checkpoint(
            lambda p, s, x, kind=kind: _block(p, s, x, kind, m)
        )(params[name], None if slots is None else slots[name], x)
        if got is not None:
            rows[name] = got
    x = _rms(x, params['norm_f']['scale'], m['norm_eps'])
    logits = jnp.matmul(x, table.T, precision=HI)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), (a, rows)


def _is_layer(node) -> bool:
    return isinstance(node, dict) and set(node) == {'kernel'} and (
        len(node['kernel'].shape) == 2
    )


def _layer_paths(tree, prefix=''):
    out = []
    for key in sorted(tree):
        node = tree[key]
        path = f'{prefix}/{key}' if prefix else key
        if _is_layer(node):
            out.append(path)
        elif isinstance(node, dict) and key != DENSE_MLP:
            out += _layer_paths(node, path)
    return out


def kfac_layers(params) -> tuple[str, ...]:
    """Paths of the K-FAC layers: every bias-free dense kernel of the
    blocks (the routed experts' one a path each) outside the dense MLP."""
    return tuple(
        path for name in sorted(k for k in params if k.startswith('block'))
        for path in _layer_paths(params[name], name)
    )


def _g_slots(params):
    def zeros(tree):
        out = {}
        for key, node in tree.items():
            if _is_layer(node):
                d = node['kernel'].shape[-1]
                out[key] = jnp.zeros((d, d), jnp.float32)
            elif isinstance(node, dict) and key != DENSE_MLP:
                sub = zeros(node)
                if sub:
                    out[key] = sub
        return out

    return {
        name: zeros(blk) for name, blk in params.items()
        if name.startswith('block')
    }


def _is_expert(path: str) -> bool:
    return '/experts/' in path


def model_config(config: dict) -> dict:
    """The keys the equations read: the configuration's top-level values,
    the run's layer types (``source_layers`` of the published
    ``layer_types``) and the rotary base."""
    m = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    m['experts_held'] = tuple(config['experts_held'])
    m['layer_types'] = tuple(
        config['layer_types'][i] for i in config['source_layers']
    )
    m['rope_theta'] = float(config['rope_parameters']['rope_theta'])
    return m


def make(config: dict):
    """``(loss_and_grads, loss_grads_factors)``: see ``refs.vision.make``."""
    m = model_config(config)

    @jax.jit
    def block_grads(params, tokens, targets):
        def f(p):
            return _forward(p, None, tokens, targets, m)[0]

        return jax.value_and_grad(f)(params)

    @jax.jit
    def block_factors(params, tokens, targets):
        def f(p, slots):
            return _forward(p, slots, tokens, targets, m)

        (loss, (a, rows)), (grads, g) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True
        )(params, _g_slots(params))
        return loss, grads, kfac.flatten(a), kfac.flatten(g), rows

    def blocks(batch):
        tokens, targets = batch
        n = tokens.shape[0]
        step = BLOCK_ROWS if n % BLOCK_ROWS == 0 else 1
        return [
            (tokens[i:i + step], targets[i:i + step])
            for i in range(0, n, step)
        ]

    def total(fn, parts):
        out = None
        for part in parts:
            got = fn(*part)
            out = got if out is None else jax.tree_util.tree_map(
                jnp.add, out, got
            )
        return out

    def loss_and_grads(params, batch):
        parts = blocks(batch)
        return jax.tree_util.tree_map(
            lambda x: x / len(parts),
            total(lambda x, y: block_grads(params, x, y), parts),
        )

    def loss_grads_factors(params, batch):
        parts = blocks(batch)
        n = len(parts)
        loss, grads, a, g, rows = total(
            lambda x, y: block_factors(params, x, y), parts
        )

        def own_rows(path):  # 'block1/moe/experts/up_proj/e3' -> its rows
            block, expert = path.split('/')[0], path.rsplit('/', 1)[1]
            return jnp.maximum(rows[block][int(expert[1:])], 1)

        # dense layers: means over blocks of equal size; a block's output
        # gradients are those of its own mean loss, 1/n of the batch's,
        # squared in G. A held expert's: sums over its rows of all blocks
        # over the count of those rows (an expert with none keeps zeros)
        a = {
            k: v / own_rows(k) if _is_expert(k) else v / n
            for k, v in a.items()
        }
        g = {
            k: v / (n * n) / (own_rows(k) if _is_expert(k) else n)
            for k, v in g.items()
        }
        return loss / n, jax.tree_util.tree_map(lambda x: x / n, grads), a, g

    return loss_and_grads, loss_grads_factors
