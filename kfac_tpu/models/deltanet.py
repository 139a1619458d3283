"""Gated DeltaNet: a linear-attention mixer with a delta-rule state.

Each value head keeps a ``(d_k, d_v)`` state ``S``, zero at a sequence's
start, and for every position ``t``::

    S <- S * exp(g_t)                 (g_t <= 0: the gate's log decay)
    r  = v_t - S^T k_t                (what the state gets wrong about k_t)
    S <- S + k_t (beta_t r)^T         (the delta rule, beta_t in (0, 1))
    o_t = S^T q_t

A scan over positions is a chain of rank-one updates the MXU cannot use,
so :func:`chunk_gated_delta_rule` computes the same outputs in chunks of
``chunk`` positions: inside a chunk the updates ``u_i = beta_i r_i`` solve a
unit lower-triangular system (``(I + A) U = beta V - (beta e^b K) S_0``
with ``A_ij = beta_i e^{b_i - b_j} k_i . k_j`` below the diagonal and ``b``
the running sum of ``g`` inside the chunk), and only the state crosses
chunk boundaries, in a ``lax.scan``. Its backward pass is the scan's own
reverse-mode rule: the g-taps of the projections around the mixer see
exactly the cotangents that flow through it.

How the system is solved (:func:`unit_lower_solve`): ``I + A`` is cut into
diagonal blocks of ``SOLVE_BLOCK`` rows (the chunk itself where it is
shorter), every block of every chunk and head is inverted at once by the
unrolled row substitution, the systems in the vectors' lanes, neighbouring
blocks merge to the whole inverse (``T21 = -T22 L21 T11``, two levels for a
chunk of 64), and ``U = T rhs`` is one product at ``HIGHEST``. The backward
pass is a ``jax.custom_vjp``: ``rhs_bar = T^T U_bar`` and ``A_bar =
-tril(rhs_bar U^T, -1)``, two products with the inverse the forward pass
built, so no second solve runs and none is differentiated through. Not
``jax.scipy.linalg.solve_triangular``: on the TPU it becomes XLA's
``InvertDiagBlocksLowerTriangular`` custom call, which inverts each 64 x 64
block whole by a serial row algorithm while the MXU idles (688 us for the
512 systems of a head group, four times a group and layer with its
transposed twin in the backward pass: 40 ms of Qwen's 203 ms step). Not the
Neumann doubling ``(I - A)(I + A^2)(I + A^4)...`` on the whole block
either: it is exact only in exact arithmetic, the powers of ``A`` reach
C(63, 31) on correlated keys (neighbouring tokens behind the causal
convolution are), and float32 then errs by 1e9 where the blocked form and
``solve_triangular`` err by 2e-7 (PERF.md section 6, PR 39).

What K-FAC does not factor here (``layers/registry.py`` pass-through
rule): the depthwise ``conv1d``, ``A_log``, ``dt_bias`` and the gated
norm's ``scale``. The six input projections and the output projection are
ordinary bias-free ``nn.Dense`` layers, declared unfused.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from kfac_tpu import tracing

HI = lax.Precision.HIGHEST


# Rows of a diagonal block of a chunk's system: a block is inverted by row
# substitution, one elementwise step a row, and blocks merge in pairs.
SOLVE_BLOCK = 16


def _product(x, y):
    """``x @ y`` for ``(r, k, M)`` and ``(k, c, M)`` with the systems in the
    last (lane) axis: ``k`` multiply-adds over ``(r, c, M)`` on the vector
    unit, exact float32. The blocks are too small for the MXU."""
    return jnp.sum(x[:, :, None] * y, axis=1)


def _substitute(d):
    """``(I + d)^-1`` for ``d`` ``(b, b, M)`` strictly lower triangular, by
    rows: row ``i`` of the inverse is ``e_i - sum_{k<i} d_ik (row k)``."""
    b = d.shape[0]
    t = jnp.broadcast_to(jnp.eye(b, dtype=d.dtype)[:, :, None], d.shape)
    d = d[:, :, None]
    for i in range(1, b):
        t = t.at[i].add(-jnp.sum(d[i, :i] * t[:i], axis=0))
    return t


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` ``(..., C, C)`` strictly lower triangular."""
    lead, c = a.shape[:-2], a.shape[-1]
    blk = min(SOLVE_BLOCK, c)
    nb = 1 << (-(-c // blk) - 1).bit_length()
    size = nb * blk
    # the systems go to the last axis: every step below is elementwise
    # over them, and C alone would fill an eighth of a vector's lanes
    a = jnp.moveaxis(a.reshape(-1, c, c), 0, -1)
    a = jnp.pad(a, ((0, size - c), (0, size - c), (0, 0)))
    m = a.shape[-1]

    def blocks(below, step):  # the diagonal's, or those under it: side by side
        return jnp.concatenate([
            a[i + below:i + below + blk, i:i + blk]
            for i in range(0, size, step)
        ], axis=-1)

    t = _substitute(blocks(0, blk))
    while blk < size:
        # [[T11, 0], [-T22 L21 T11, T22]] of each pair of neighbours
        t11, t22 = (
            jnp.concatenate([
                t[:, :, i:i + m] for i in range(first, t.shape[-1], 2 * m)
            ], axis=-1)
            for first in (0, m)
        )
        t21 = -_product(t22, _product(blocks(blk, 2 * blk), t11))
        t = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=1),
            jnp.concatenate([t21, t22], axis=1),
        ])
        blk *= 2
    return jnp.moveaxis(t[:c, :c], -1, 0).reshape(*lead, c, c)


@jax.custom_vjp
def unit_lower_solve(a, rhs):
    """``U`` of ``(I + a) U = rhs`` for ``a`` ``(..., C, C)`` strictly
    lower triangular and ``rhs`` ``(..., C, n)``, float32: the inverse by
    blocks (module docstring), then one product at ``HIGHEST``."""
    return _unit_lower_solve_fwd(a, rhs)[0]


def _unit_lower_solve_fwd(a, rhs):
    t = _unit_lower_inverse(a)
    u = jnp.matmul(t, rhs, precision=HI)
    return u, (t, u)


def _unit_lower_solve_bwd(res, u_bar):
    # the transposed solve is a product with the inverse the forward pass
    # built: no second substitution, and none differentiated
    t, u = res
    rhs_bar = jnp.einsum('...ji,...jn->...in', t, u_bar, precision=HI)
    a_bar = -jnp.einsum('...in,...jn->...ij', rhs_bar, u, precision=HI)
    return jnp.tril(a_bar, -1), rhs_bar


unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """The gated delta rule in chunks.

    Args:
        q, k: ``(B, T, H, d_k)``; ``k`` (and usually ``q``) L2-normalised a
            head, ``q`` already scaled.
        v: ``(B, T, H, d_v)``.
        g: ``(B, T, H)`` float32 log decay (``<= 0``).
        beta: ``(B, T, H)`` in ``(0, 1)``.
        chunk: positions a chunk; ``T`` need not be a multiple of it
            (the tail is padded with positions that change nothing).

    Returns ``(B, T, H, d_v)`` float32 outputs.
    """
    b_, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    n = (t + pad) // chunk

    def chunks(x):  # (B, T, H, ...) -> (N, B, H, C, ...)
        x = x.reshape(b_, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v = (chunks(x.astype(jnp.float32)) for x in (q, k, v))
    g, beta = chunks(g.astype(jnp.float32)), chunks(beta.astype(jnp.float32))
    b = jnp.cumsum(g, axis=-1)                               # (N, B, H, C)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = b[..., :, None] - b[..., None, :]
    # mask before the exponential: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.einsum('...id,...jd->...ij', kb, k, precision=HI) * decay
    a = jnp.where(jnp.tril(tri, -1), a, 0.0)
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * jnp.exp(b)[..., None]], axis=-1
    )
    sol = unit_lower_solve(a, rhs)
    w, kc = sol[..., :dv], sol[..., dv:]
    qk = jnp.einsum('...id,...jd->...ij', q, k) * decay

    def step(state, xs):
        q_i, k_i, w_i, kc_i, qk_i, b_i = xs
        u = w_i - jnp.einsum('...ck,...kv->...cv', kc_i, state)
        o = jnp.einsum(
            '...ck,...kv->...cv', q_i * jnp.exp(b_i)[..., None], state
        ) + jnp.einsum('...ij,...jv->...iv', qk_i, u)
        last = b_i[..., -1]
        state = state * jnp.exp(last)[..., None, None] + jnp.einsum(
            '...ck,...cv->...kv',
            k_i * jnp.exp(last[..., None] - b_i)[..., None], u,
        )
        return state, o

    _, o = lax.scan(
        step, jnp.zeros((b_, h, dk, dv), jnp.float32), (q, k, w, kc, qk, b)
    )
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)         # (B, N, C, H, dv)
    return o.reshape(b_, t + pad, h, dv)[:, :t]


def l2norm(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class CausalConv1d(nn.Module):
    """Depthwise causal convolution over positions, no bias: ``y_t = sum_j
    kernel[j] * x_{t - (K - 1) + j}``. Not a K-FAC layer."""

    kernel_size: int = 4

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        k = self.kernel_size
        kernel = self.param(
            'kernel', nn.initializers.lecun_normal(), (k, 1, x.shape[-1])
        )[:, 0].astype(x.dtype)
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        t = x.shape[1]
        return sum(xp[:, j:j + t] * kernel[j] for j in range(k))


# Value heads the scan takes at a time: its float32 temporaries (a few
# ``(chunks, batch, heads, chunk, d)`` values, and as many again in the
# backward pass) scale with the heads in flight, and the groups run one
# after another, each rematerialised in the backward pass.
SCAN_HEAD_GROUP = 8


def _mix(
    conv, z, a, b, a_log, dt_bias, scale, heads_k, heads_v, dk, dv, chunk,
    eps, dtype,
):
    """From the convolved ``[q, k, v]`` channels, ``z`` and the gate
    projections to the output projection's input: everything between the
    taps, which holds no parameter of a K-FAC layer, so that it is
    rematerialised whole in the backward pass and keeps only its inputs."""
    b_, t, _ = conv.shape
    conv = nn.silu(conv)
    kd = heads_k * dk
    rep = heads_v // heads_k
    group = min(heads_v, max(rep, SCAN_HEAD_GROUP))
    if heads_v % group:
        group = heads_v
    n = heads_v // group

    def groups(x, heads):  # (B, T, heads * d) -> (n, B, T, heads / n, d)
        x = x.reshape(b_, t, n, heads // n, -1)
        return jnp.moveaxis(x, 2, 0)

    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    )

    @jax.checkpoint
    def scan(xs):
        q, k, v, g, beta = xs
        q = jnp.repeat(l2norm(q) * dk ** -0.5, rep, axis=2)
        k = jnp.repeat(l2norm(k), rep, axis=2)
        return chunk_gated_delta_rule(q, k, v, g[..., 0], beta[..., 0], chunk)

    with tracing.model_scope('gdn_scan'):
        o = lax.map(scan, (
            groups(conv[..., :kd], heads_k),
            groups(conv[..., kd:2 * kd], heads_k),
            groups(conv[..., 2 * kd:], heads_v),
            groups(g, heads_v), groups(beta, heads_v),
        ))
    o = jnp.moveaxis(o, 0, 2).reshape(b_, t, heads_v, dv)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    z = z.reshape(b_, t, heads_v, dv).astype(jnp.float32)
    return (scale * o * nn.silu(z)).reshape(b_, t, heads_v * dv).astype(dtype)


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet mixer: six projections of the input (``q``,
    ``k``, ``v``, ``z``, ``b``, ``a``), a causal depthwise convolution and
    ``silu`` over ``[q, k, v]``, the gated delta rule a value head (each
    key head serving ``num_v_heads / num_k_heads`` value heads), then
    ``scale * rmsnorm_head(o) * silu(z)`` and the output projection."""

    num_k_heads: int = 16
    num_v_heads: int = 32
    head_k_dim: int = 128
    head_v_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        hk, hv, dk, dv = (
            self.num_k_heads, self.num_v_heads, self.head_k_dim,
            self.head_v_dim,
        )

        def dense(features, name, dtype=self.dtype):
            return nn.Dense(features, use_bias=False, dtype=dtype, name=name)

        q = dense(hk * dk, 'q_proj')(x)
        k = dense(hk * dk, 'k_proj')(x)
        v = dense(hv * dv, 'v_proj')(x)
        z = dense(hv * dv, 'z_proj')(x)
        # the gates in float32: they set a decay that compounds over
        # thousands of positions
        b = dense(hv, 'b_proj', jnp.float32)(x)
        a = dense(hv, 'a_proj', jnp.float32)(x)
        conv = CausalConv1d(self.conv_kernel, name='conv1d')(
            jnp.concatenate([q, k, v], axis=-1)
        )
        a_log = self.param('A_log', nn.initializers.normal(0.02), (hv,))
        dt_bias = self.param('dt_bias', nn.initializers.normal(0.02), (hv,))
        scale = self.param('scale', nn.initializers.ones, (dv,))
        o = jax.checkpoint(_mix, static_argnums=tuple(range(7, 14)))(
            conv, z, a, b, a_log, dt_bias, scale, hk, hv, dk, dv,
            self.chunk, self.eps, self.dtype,
        )
        return dense(x.shape[-1], 'out_proj')(o)
