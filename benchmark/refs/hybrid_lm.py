"""Sparse hybrid decoder (the Qwen3-Next family's layer pattern) forward,
loss and gradients, plain: float32, ``Precision.HIGHEST``, mean next-token
cross entropy over the vocabulary held. The parameter tree is the one the
program's model declares; nothing of the program is imported.

By the configuration's equations (``benchmark/configs/qwen3-next-80b-a3b
.json`` has the source and every departure):

- zero-centred RMSNorm ``x rsqrt(mean x^2 + eps) (1 + w)``; blocks
  ``x += mixer(norm x); x += moe(norm x)``; layer ``i`` is gated attention
  where ``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet;
- Gated DeltaNet by its definition, a position at a time (``lax.scan``
  over positions, in segments so that the backward pass keeps one state a
  segment): ``S <- S e^{g_t}; r = v_t - S^T k_t; S <- S + k_t (beta_t
  r)^T; o_t = S^T q_t``;
- gated attention with whole score matrices;
- experts by boolean masks over the experts held: each held expert sees
  every token with the rows not routed to it zeroed. What absent experts
  would add is left out, as in the program.

Every projection is a K-FAC layer, each routed expert's three apart with
A and G over *its own rows* (sums over the rows routed to it, divided by
their count). Not K-FAC: embedding, head, norm weights, ``conv1d``,
``A_log``, ``dt_bias``.

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
SCAN_SEGMENT = 64       # positions a rematerialised segment of the scan
EXPERT_PROJS = ('gate_proj', 'up_proj', 'down_proj')


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _sum_sq(g):
    rows = g.reshape(-1, g.shape[-1])
    return jnp.matmul(rows.T, rows, precision=HI)


def _dense(x, layer, slot):
    y = jnp.matmul(x, layer['kernel'], precision=HI)
    if slot is None:
        return y, None
    return kfac.g_tap(y, slot, kfac.dense_g), kfac.dense_a(x, False)


def _conv(x, kernel):
    """Causal depthwise convolution; ``kernel`` is ``(K, 1, channels)``."""
    k, t = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * kernel[j, 0] for j in range(k))


def _delta_rule(q, k, v, g, beta):
    """``(b, t, h, d)`` inputs, a position at a time."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum('bhkv,bhk->bhv', s, k_t, precision=HI)
        s = s + jnp.einsum('bhk,bhv->bhkv', k_t, beta_t[..., None] * r,
                           precision=HI)
        return s, jnp.einsum('bhkv,bhk->bhv', s, q_t, precision=HI)

    @jax.checkpoint
    def segment(s, xs):
        return lax.scan(step, s, xs)

    seg = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t
    xs = tuple(
        jnp.moveaxis(x, 1, 0).reshape(t // seg, seg, *x.shape[:1], *x.shape[2:])
        for x in (q, k, v, g, beta)
    )
    _, o = lax.scan(segment, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(t, b, h, dv), 0, 1)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _deltanet(p, s, x, m):
    a = {}
    b_, t, _ = x.shape
    hk, hv = m['linear_num_key_heads'], m['linear_num_value_heads']
    dk, dv = m['linear_key_head_dim'], m['linear_value_head_dim']
    out = {}
    for name in ('q_proj', 'k_proj', 'v_proj', 'z_proj', 'b_proj', 'a_proj'):
        out[name], a[name] = _dense(x, p[name], s and s[name])
    mixed = jnp.concatenate([out['q_proj'], out['k_proj'], out['v_proj']], -1)
    mixed = jax.nn.silu(_conv(mixed, p['conv1d']['kernel']))
    q = mixed[..., :hk * dk].reshape(b_, t, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b_, t, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b_, t, hv, dv)
    q = jnp.repeat(_l2(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(_l2(k), hv // hk, axis=2)
    beta = jax.nn.sigmoid(out['b_proj'])
    g = -jnp.exp(p['A_log']) * jax.nn.softplus(out['a_proj'] + p['dt_bias'])
    o = _delta_rule(q, k, v, g, beta)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m['rms_norm_eps'])
    o = p['scale'] * o * jax.nn.silu(out['z_proj'].reshape(b_, t, hv, dv))
    y, a['out_proj'] = _dense(
        o.reshape(b_, t, hv * dv), p['out_proj'], s and s['out_proj']
    )
    return y, a


def _rotary(x, rotary_dim, theta):
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary_dim:]], -1
    )


def _attention(p, s, x, m):
    a = {}
    b_, t, _ = x.shape
    h, hkv, hd = (
        m['num_attention_heads'], m['num_key_value_heads'], m['head_dim']
    )
    rot = int(hd * m['partial_rotary_factor'])
    out = {}
    for name in ('q_proj', 'gate_proj', 'k_proj', 'v_proj'):
        out[name], a[name] = _dense(x, p[name], s and s[name])
    q = out['q_proj'].reshape(b_, t, h, hd)
    k = out['k_proj'].reshape(b_, t, hkv, hd)
    v = out['v_proj'].reshape(b_, t, hkv, hd)
    q = _rotary(_rms(q, p['q_norm']['weight'], m['rms_norm_eps']), rot,
                m['rope_theta'])
    k = _rotary(_rms(k, p['k_norm']['weight'], m['rms_norm_eps']), rot,
                m['rope_theta'])
    k, v = (jnp.repeat(z, h // hkv, axis=2) for z in (k, v))
    scores = jnp.einsum('bqhd,bkhd->bhqk', q * hd ** -0.5, k, precision=HI)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum(
        'bhqk,bkhd->bqhd', jax.nn.softmax(scores, axis=-1), v, precision=HI
    ).reshape(b_, t, h * hd)
    y, a['o_proj'] = _dense(
        o * jax.nn.sigmoid(out['gate_proj']), p['o_proj'], s and s['o_proj']
    )
    return y, a


def _gated_mlp(p, s, x, a):
    g, a['gate_proj'] = _dense(x, p['gate_proj'], s and s['gate_proj'])
    u, a['up_proj'] = _dense(x, p['up_proj'], s and s['up_proj'])
    return _dense(jax.nn.silu(g) * u, p['down_proj'], s and s['down_proj'])


def _moe(p, s, x, m):
    """Returns the layer's output, the A entries (a held expert's are
    *sums* over its rows) and the rows of each held expert."""
    a = {'shared': {}, 'experts': {n: {} for n in EXPERT_PROJS}}
    first, held = m['experts_held']
    xf = x.reshape(-1, x.shape[-1])
    logits, a['router'] = _dense(xf, p['router'], s and s['router'])
    wts, idx = lax.top_k(jax.nn.softmax(logits, -1), m['num_experts_per_tok'])
    if m['norm_topk_prob']:
        wts = wts / jnp.sum(wts, -1, keepdims=True)
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
        for proj, inp in (('gate_proj', xe), ('up_proj', xe)):
            out = jnp.matmul(inp, ex[proj][name]['kernel'], precision=HI)
            if s is not None:
                out = kfac.g_tap(out, s['experts'][proj][name], _sum_sq)
                a['experts'][proj][name] = _sum_sq(inp)
            parts[proj] = out
        hid = jax.nn.silu(parts['gate_proj']) * parts['up_proj']
        out = jnp.matmul(hid, ex['down_proj'][name]['kernel'], precision=HI)
        if s is not None:
            out = kfac.g_tap(out, s['experts']['down_proj'][name], _sum_sq)
            a['experts']['down_proj'][name] = _sum_sq(hid)
        y = y + w[:, None] * out
    gate, a['shared_gate'] = _dense(xf, p['shared_gate'], s and s['shared_gate'])
    shared, a['shared']['down_proj'] = _gated_mlp(
        p['shared'], s and s['shared'], xf, a['shared']
    )
    y = y + jax.nn.sigmoid(gate) * shared
    return y.reshape(x.shape), a, jnp.stack(rows)


def _is_attention(i, m):
    return (i + 1) % m['full_attention_interval'] == 0


def _block(p, s, x, i, m):
    eps = m['rms_norm_eps']
    mixer = _attention if _is_attention(i, m) else _deltanet
    y, a_mixer = mixer(
        p['mixer'], s and s['mixer'], _rms(x, p['norm1']['weight'], eps), m
    )
    x = x + y
    y, a_moe, rows = _moe(
        p['moe'], s and s['moe'], _rms(x, p['norm2']['weight'], eps), m
    )
    return x + y, {'mixer': a_mixer, 'moe': a_moe}, rows


def _forward(params, slots, tokens, targets, m):
    x = params['embed']['embedding'][tokens]
    a, rows = {}, {}
    for i in range(m['num_hidden_layers']):
        name = f'block{i}'
        x, a[name], rows[name] = jax.checkpoint(
            lambda p, s, x, i=i: _block(p, s, x, i, m)
        )(params[name], None if slots is None else slots[name], x)
    x = _rms(x, params['norm_f']['weight'], m['rms_norm_eps'])
    logits = jnp.matmul(x, params['lm_head']['kernel'], precision=HI)
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
        elif isinstance(node, dict):
            out += _layer_paths(node, path)
    return out


def kfac_layers(params) -> tuple[str, ...]:
    """Paths of the K-FAC layers: every bias-free dense kernel of the
    blocks (the routed experts' one a path each). The head is skipped."""
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
            elif isinstance(node, dict):
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
    """The keys the equations read, from the configuration's top level."""
    m = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    m['experts_held'] = tuple(config['experts_held'])
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

        def own_rows(path):  # 'block0/moe/experts/up_proj/e3' -> its rows
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
