"""Latent-attention sparse decoder (the DeepSeek-V3 family's layer, as
``kanana-2-30b-a3b`` configures it) forward, loss and gradients, plain:
float32, ``Precision.HIGHEST``, mean next-token cross entropy over the
vocabulary held. The parameter tree is the one the program's model
declares; nothing of the program is imported.

By the configuration's equations (``benchmark/configs/kanana-2-30b-a3b.json``
has the source and every departure), ``u`` a block's normed input:

- plain RMSNorm ``x rsqrt(mean x^2 + eps) w``; blocks ``h = x + MLA(norm
  x); x' = h + ffn(norm h)``; ``ffn`` the dense gated MLP in the run's
  layers ahead of ``first_k_dense_replace`` and the routed experts after;
- latent attention by the source's *fused* products, on kernels put
  together from the six the program declares (the column map of the
  configuration's ``departures``): ``q = u W_q``, a head ``[q_nope |
  q_rope]``; ``[c | k_r] = u W_kva``; ``[k_nope | v] = norm(c) W_kvb``, a
  head at a time; rotary positions on ``q_rope`` and on the one ``k_r``
  by pairs ``(2j, 2j + 1)`` (``rope_interleave``), as complex products;
  whole score matrices a head, causal softmax of ``q k^T / sqrt(qk_nope +
  qk_rope)``, values ``v_head_dim`` wide;
- routing ``s = sigmoid(x W_r)``; the experts chosen by ``top_k(s + b)``;
  their weights ``s`` there, over ``sum + 1e-20``, times
  ``routed_scaling_factor``; experts by boolean masks over the experts
  held: each held expert sees every token with the rows not routed to it
  zeroed. What absent experts would add is left out, as in the program.
  The shared experts, one gated MLP ``n_shared_experts *
  moe_intermediate_size`` wide, are added with no gate;
- logits ``norm(x) W_head``, the head untied.

Every projection of the blocks is a K-FAC layer but the dense MLP's three
(first-order update: the cell skips them), the six declared ones of
a mixer apart (A of a fused product's input, G of its columns' slice: the
program's statistics), each routed expert's three apart with A and G over
*its own rows* (sums over the rows routed to it, divided by their count;
an expert with no rows keeps the identity its factors start from).
Not K-FAC: embedding, head (the cell skips it), norm weights,
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
MLP_PROJS = ('gate_proj', 'up_proj', 'down_proj')
HEADS_AT_ONCE = 4       # heads whose (T, T) scores exist together
DENSE_MLP = 'mlp'       # the module K-FAC leaves to the first-order update


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


_latent_norm = _rms     # the latent's norm, under a name of its own


def _sum_sq(g):
    rows = g.reshape(-1, g.shape[-1])
    return jnp.matmul(rows.T, rows, precision=HI)


def _dense(x, layer, slot):
    y = jnp.matmul(x, layer['kernel'], precision=HI)
    if slot is None:
        return y, None
    return kfac.g_tap(y, slot, kfac.dense_g), kfac.dense_a(x, False)


def _by_head(parts, heads):
    """Kernels ``(d, heads * w_i)`` side by side a head at a time:
    ``(d, heads * sum w_i)``."""
    d = parts[0].shape[0]
    return jnp.concatenate(
        [p.reshape(d, heads, -1) for p in parts], axis=-1
    ).reshape(d, -1)


def _fused(x, p, s, a, names, heads=None):
    """One product of ``x`` with the declared layers ``names``' kernels
    put together (a head at a time with ``heads``, else one after the
    other); returns each layer's columns of it, in ``names``' order, with
    that layer's G tap on them and its A entry filed."""
    kernels = [p[n]['kernel'] for n in names]
    fused = (
        jnp.concatenate(kernels, axis=-1) if heads is None
        else _by_head(kernels, heads)
    )
    y = jnp.matmul(x, fused, precision=HI)
    if heads is not None:
        y = y.reshape(*y.shape[:-1], heads, -1)
    out, at = [], 0
    for n, k in zip(names, kernels):
        width = k.shape[-1] // (heads or 1)
        part = y[..., at:at + width]
        if heads is not None:
            part = part.reshape(*part.shape[:-2], -1)
        at += width
        if s is not None:
            part = kfac.g_tap(part, s[n], kfac.dense_g)
            a[n] = kfac.dense_a(x, False)
        out.append(part)
    return out


def _rotary_pairs(x, theta):
    """Rotary positions on ``(B, T, H, D)`` by pairs ``(2j, 2j + 1)`` at
    frequency ``theta^(-2j / D)``: the source's interleaved layout,
    rotated in place. (The source de-interleaves first and rotates in
    halves; q and k are permuted alike, so the scores are these.)"""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape)


def _mla(p, s, u, m):
    a = {}
    b_, t, _ = u.shape
    h, vd = m['num_attention_heads'], m['v_head_dim']
    nope, rope = m['qk_nope_head_dim'], m['qk_rope_head_dim']
    q_nope, q_rope = _fused(u, p, s, a, ('q_nope_proj', 'q_rope_proj'), h)
    c, k_r = _fused(u, p, s, a, ('kv_a_proj', 'k_rope_proj'))
    cn = _latent_norm(c, p['kv_a_layernorm']['scale'], m['rms_norm_eps'])
    k_nope, v = _fused(cn, p, s, a, ('k_nope_proj', 'v_proj'), h)
    q = jnp.concatenate([
        q_nope.reshape(b_, t, h, nope),
        _rotary_pairs(q_rope.reshape(b_, t, h, rope), m['rope_theta']),
    ], axis=-1)
    k_r = _rotary_pairs(k_r[:, :, None, :], m['rope_theta'])[:, :, 0]
    k_nope = k_nope.reshape(b_, t, h, nope)
    v = v.reshape(b_, t, h, vd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def group(xs):
        """Some heads' whole ``(T, T)`` score matrices: the part without
        positions against each head's own keys, the rotary part against
        the one shared key."""
        q_g, k_g, v_g = xs
        scores = jnp.einsum(
            'bqhd,bkhd->bhqk', q_g[..., :nope], k_g, precision=HI
        ) + jnp.einsum('bqhd,bkd->bhqk', q_g[..., nope:], k_r, precision=HI)
        scores = jnp.where(causal[None, None], scores * scale, -jnp.inf)
        return jnp.einsum(
            'bhqk,bkhd->bqhd', jax.nn.softmax(scores, axis=-1), v_g,
            precision=HI,
        )

    n = HEADS_AT_ONCE if h % HEADS_AT_ONCE == 0 else 1

    def grouped(x):
        return jnp.moveaxis(x.reshape(b_, t, h // n, n, x.shape[-1]), 2, 0)

    o = lax.map(group, (grouped(q), grouped(k_nope), grouped(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(b_, t, h * vd)
    y, a['o_proj'] = _dense(o, p['o_proj'], s and s['o_proj'])
    return y, a


def _gated_mlp(p, s, x):
    a = {}
    g, a['gate_proj'] = _dense(x, p['gate_proj'], s and s['gate_proj'])
    u, a['up_proj'] = _dense(x, p['up_proj'], s and s['up_proj'])
    y, a['down_proj'] = _dense(
        jax.nn.silu(g) * u, p['down_proj'], s and s['down_proj']
    )
    return y, a


def _route(logits, bias, m):
    """The chosen experts of every token ``(tokens, k)`` and their
    weights."""
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + bias, m['num_experts_per_tok'])
    wts = jnp.take_along_axis(scores, idx, axis=-1)
    if m['norm_topk_prob']:
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return wts * m['routed_scaling_factor'], idx


def _moe(p, s, x, m):
    """Returns the layer's output, the A entries (a held expert's are
    *sums* over its rows) and the rows of each held expert."""
    a = {'experts': {n: {} for n in MLP_PROJS}}
    first, held = m['experts_held']
    xf = x.reshape(-1, x.shape[-1])
    logits, a['router'] = _dense(xf, p['router'], s and s['router'])
    wts, idx = _route(logits, p['expert_bias'], m)
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
    shared, a['shared'] = _gated_mlp(p['shared'], s and s['shared'], xf)
    return (y + shared).reshape(x.shape), a, jnp.stack(rows)


def _block(p, s, x, m):
    eps = m['rms_norm_eps']
    y, a_mixer = _mla(
        p['mixer'], s and s['mixer'], _rms(x, p['norm1']['scale'], eps), m
    )
    x = x + y
    u = _rms(x, p['norm2']['scale'], eps)
    if DENSE_MLP in p:
        y, _ = _gated_mlp(p[DENSE_MLP], None, u)
        return x + y, {'mixer': a_mixer}, None
    y, a_moe, rows = _moe(p['moe'], s and s['moe'], u, m)
    return x + y, {'mixer': a_mixer, 'moe': a_moe}, rows


def _logits(params, slots, tokens, m):
    """Logits ``(B, T, V)``, the A entries and each routed layer's rows a
    held expert."""
    x = params['embed']['embedding'][tokens]
    a, rows = {}, {}
    for i in range(m['num_hidden_layers']):
        name = f'block{i}'
        x, a[name], got = jax.checkpoint(
            lambda p, s, x: _block(p, s, x, m)
        )(params[name], None if slots is None else slots[name], x)
        if got is not None:
            rows[name] = got
    x = _rms(x, params['norm_f']['scale'], m['rms_norm_eps'])
    return jnp.matmul(x, params['lm_head']['kernel'], precision=HI), (a, rows)


def _forward(params, slots, tokens, targets, m):
    logits, aux = _logits(params, slots, tokens, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), aux


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
    blocks (the routed experts' one a path each) outside the dense MLP;
    not the head's."""
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
    """The keys the equations read: the configuration's top-level values
    and the share of the experts held."""
    m = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    m['experts_held'] = tuple(config['experts_held'])
    m['rope_theta'] = float(config['rope_theta'])
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
            return rows[block][int(expert[1:])]

        def over_rows(path, total):
            """A held expert's sum over its rows, over their count. An
            expert with no rows gives no evidence: its factor stays the
            identity it starts from (an identity folded into it leaves it
            so), as the engine's traffic-weighted average gives a capture
            without rows weight 0. (``refs/conv_moe_lm.py`` folds zeros
            in; its cell never meets the case, this one does on every
            seed: PERF.md section 7.)"""
            r = own_rows(path)
            return jnp.where(
                r > 0, total / jnp.maximum(r, 1),
                jnp.eye(total.shape[0], dtype=total.dtype),
            )

        # dense layers: means over blocks of equal size; a block's output
        # gradients are those of its own mean loss, 1/n of the batch's,
        # squared in G
        a = {
            k: over_rows(k, v) if _is_expert(k) else v / n
            for k, v in a.items()
        }
        g = {
            k: over_rows(k, v / (n * n)) if _is_expert(k) else v / (n * n * n)
            for k, v in g.items()
        }
        return loss / n, jax.tree_util.tree_map(lambda x: x / n, grads), a, g

    return loss_and_grads, loss_grads_factors
