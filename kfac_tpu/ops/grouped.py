"""Grouped products over blocks of rows that each belong to one expert.

An expert layer that drops no assignment has to be ready for the worst
load (every token choosing the same expert), and XLA wants that as a
static shape. The assignments to the experts held are therefore laid out
as a plan of ``(blocks, block_rows)`` rows sized for the worst case, each
expert's rows padded up to whole blocks (``models/moe.py``
``make_plan``), and the products here run a ``fori_loop`` over the blocks
*in use* only: the trip count is a device value, so the work follows the
load and the padding costs index memory, not time. Nothing as wide as the
model is ever laid out by row: a block's rows are gathered from the
tokens inside the loop (:func:`grouped_matmul_gather`) and scattered back
into them (:func:`grouped_matmul_combine`); only the narrow activations
between an expert's projections live in block form.

A loop with a dynamic trip count has no reverse-mode rule, so each
product carries its own (a loop of the same kind). Plain ``jax.numpy``
throughout: no kernel, nothing to dispatch.

Conventions: ``row_token`` is ``(blocks, rows)`` int32, the token a plan
row reads, and the number of tokens for a padding row (which reads zeros
and writes nowhere); ``block_expert`` is ``(blocks,)`` int32;
``n_blocks`` an int32 scalar.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _gather(x, idx):
    """Rows ``idx`` of ``x``; zeros where ``idx`` is past the last."""
    t = x.shape[0]
    rows = x[jnp.minimum(idx, t - 1)]
    return jnp.where((idx < t)[:, None], rows, jnp.zeros((), x.dtype))


def _scatter_add(y, idx, rows):
    """``y[idx] += rows``; an ``idx`` past the last row is dropped."""
    return y.at[idx].add(rows.astype(y.dtype), mode='drop')


# ------------------------------------------------------- tokens -> blocks


@jax.custom_vjp
def grouped_matmul_gather(x, w, row_token, block_expert, n_blocks):
    """``out[i] = x[row_token[i]] @ w[block_expert[i]]``: the rows of a
    block gathered from the tokens ``x`` ``(tokens, k)`` as the loop
    reaches it."""
    out = jnp.zeros(row_token.shape + (w.shape[-1],), x.dtype)

    def body(i, out):
        y = _dot(_gather(x, row_token[i]), w[block_expert[i]])
        return out.at[i].set(y.astype(out.dtype))

    return jax.lax.fori_loop(0, n_blocks, body, out)


def _gather_fwd(x, w, row_token, block_expert, n_blocks):
    out = grouped_matmul_gather(x, w, row_token, block_expert, n_blocks)
    return out, (x, w, row_token, block_expert, n_blocks)


def _gather_bwd(res, dy):
    x, w, row_token, block_expert, n_blocks = res

    def body(i, carry):
        dx, dw = carry
        e = block_expert[i]
        dx = _scatter_add(dx, row_token[i], _dot(dy[i], w[e].T))
        return dx, dw.at[e].add(_dot(_gather(x, row_token[i]).T, dy[i]))

    dx, dw = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros(w.shape, jnp.float32)),
    )
    return dx.astype(x.dtype), dw.astype(w.dtype), None, None, None


grouped_matmul_gather.defvjp(_gather_fwd, _gather_bwd)


# ------------------------------------------------------- blocks -> tokens


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def grouped_matmul_combine(
    hb, w, row_token, row_weight, block_expert, n_blocks, tokens
):
    """``y[row_token[i]] += row_weight[i] * (hb[i] @ w[block_expert[i]])``
    into ``(tokens, n)`` float32 zeros: an expert's last projection and the
    weighted sum over a token's experts in one pass."""
    y = jnp.zeros((tokens, w.shape[-1]), jnp.float32)

    def body(i, y):
        out = _dot(hb[i], w[block_expert[i]])
        return _scatter_add(y, row_token[i], out * row_weight[i][:, None])

    return jax.lax.fori_loop(0, n_blocks, body, y)


def _combine_fwd(hb, w, row_token, row_weight, block_expert, n_blocks, tokens):
    y = grouped_matmul_combine(
        hb, w, row_token, row_weight, block_expert, n_blocks, tokens
    )
    return y, (hb, w, row_token, row_weight, block_expert, n_blocks)


def _combine_bwd(tokens, res, dy):
    del tokens
    hb, w, row_token, row_weight, block_expert, n_blocks = res

    def body(i, carry):
        dh, dw, dwt = carry
        e = block_expert[i]
        g = _gather(dy, row_token[i])            # (rows, n) float32
        gw = (g * row_weight[i][:, None]).astype(hb.dtype)
        dh = dh.at[i].set(_dot(gw, w[e].T).astype(dh.dtype))
        dw = dw.at[e].add(_dot(hb[i].T, gw))
        dwt = dwt.at[i].set(jnp.sum(_dot(hb[i], w[e]) * g, axis=-1))
        return dh, dw, dwt

    dh, dw, dwt = jax.lax.fori_loop(
        0, n_blocks, body,
        (
            jnp.zeros_like(hb), jnp.zeros(w.shape, jnp.float32),
            jnp.zeros(row_weight.shape, jnp.float32),
        ),
    )
    return dh, dw.astype(w.dtype), None, dwt.astype(row_weight.dtype), None, None


grouped_matmul_combine.defvjp(_combine_fwd, _combine_bwd)


# ----------------------------------------------------------- covariances


@functools.partial(jax.jit, static_argnames=('experts',))
def grouped_cov(
    x, block_expert, n_blocks, experts: int, row_token=None, row_weight=None
):
    """``(experts, k, k)`` float32 sums of ``r^T r`` over each expert's
    rows ``r`` (un-normalised: the caller divides by the rows it counted).

    ``x`` is ``(blocks, rows, k)``, or ``(tokens, k)`` with ``row_token``
    to gather a block's rows from, each scaled by ``row_weight`` where
    given (the cotangent of an expert's output before the weighted sum).
    Zero rows add nothing, so padding needs no mask."""
    k = x.shape[-1]

    def body(i, out):
        r = x[i] if row_token is None else _gather(x, row_token[i])
        if row_weight is not None:
            r = r * row_weight[i][:, None].astype(r.dtype)
        return out.at[block_expert[i]].add(_dot(r.T, r))

    return jax.lax.fori_loop(
        0, n_blocks, body, jnp.zeros((experts, k, k), jnp.float32)
    )
