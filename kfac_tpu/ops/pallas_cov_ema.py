"""Fused Pallas TPU kernel: covariance contraction + factor EMA.

Every engine's capture path runs ``get_cov`` (a^T a / scale) immediately
followed by ``ema_update`` (F <- beta*F + (1-beta)*cov) — two kernels
with a full (d, d) f32 round-trip through HBM between them, plus the
defensive symmetrization the unfused contraction needs. This module
is a triangular covariance kernel (the MXU runs only for output tiles on
or above the diagonal) with an EMA epilogue: at the last reduction step
of each such tile the kernel reads the matching tile of the running
factor and blends in place, so the covariance intermediate never exists
in HBM
(``F <- beta*F + (1-beta)*a^T a/scale`` in one pass) and the result is
exactly symmetric by the same mirror-the-upper-triangle construction —
no ``(C + C^T)/2`` needed.

Equivalence contract (pinned by tests/ops/test_fused_kernels.py): for
f32 inputs, ``fused_cov_ema(F, a, alpha, scale)`` is allclose to
``ema_update(F, get_cov(a, scale), alpha)`` and exactly symmetric for
symmetric ``F``.

It runs only where a raw Mosaic call can: a one-device process or a
fully-manual ``shard_map``.

Dispatch (:func:`use_fused_cov_ema_for`) follows the family's row in the
committed threshold artifact (:mod:`kfac_tpu.ops.dispatch_tables`,
family ``cov_ema``); off-TPU, below threshold, or under a contaminated
baseline sweep the caller falls back to the unfused pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kfac_tpu.ops.pallas_gate import interpret_mode

TILE = 128       # lane-aligned C-block edge
K_BLOCK = 512    # rows of `a` consumed per reduction step


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def _sym_cov_ema_kernel(a_i_ref, a_j_ref, f_ref, out_ref, *, beta, coeff):
    """Triangular cov tile with the EMA blend fused into the epilogue.

    ``beta``/``coeff`` are trace-time constants (the gate only fires for
    static decay factors): ``out = beta*F + coeff*(a^T a)`` at the last
    reduction step, where ``coeff = (1-beta)/scale``.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(j >= i)
    def _accumulate():
        out_ref[:] += jax.lax.dot_general(
            a_i_ref[:], a_j_ref[:],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # epilogue: the running-factor tile is read once, at the step where
    # the accumulated a^T a tile is complete and still VMEM-resident —
    # the unfused pair's d^2 HBM round-trip is exactly this read-modify-
    # write, done here for free
    @pl.when((j >= i) & (k == pl.num_programs(2) - 1))
    def _ema():
        out_ref[:] = (
            beta * f_ref[:].astype(jnp.float32) + coeff * out_ref[:]
        )


@functools.partial(
    jax.jit, static_argnames=('beta', 'coeff', 'interpret')
)
def _fused(
    f: jax.Array,
    a: jax.Array,
    beta: float,
    coeff: float,
    interpret: bool = False,
) -> jax.Array:
    """Padded kernel launch + lower-triangle mirror; returns f32 (d, d).

    ``f`` is the (d, d) running factor, ``a`` the (n, d) activation
    rows; the blend is ``beta*f + coeff*(a^T a)``.
    """
    n, d = a.shape
    n_pad = -(-n // K_BLOCK) * K_BLOCK
    d_pad = -(-d // TILE) * TILE
    ap = _pad_to(a, n_pad, d_pad)
    fp = _pad_to(f.astype(jnp.float32), d_pad, d_pad)
    nblk = d_pad // TILE
    nk = n_pad // K_BLOCK

    upper = pl.pallas_call(
        functools.partial(
            _sym_cov_ema_kernel, beta=beta, coeff=coeff
        ),
        out_shape=jax.ShapeDtypeStruct(
            (d_pad, d_pad), jnp.float32, vma=jax.typeof(ap).vma
        ),
        grid=(nblk, nblk, nk),
        in_specs=[
            pl.BlockSpec((K_BLOCK, TILE), lambda i, j, k: (k, i)),
            pl.BlockSpec((K_BLOCK, TILE), lambda i, j, k: (k, j)),
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j)),
        interpret=interpret,
        name='_sym_cov_ema_kernel',
    )(ap, ap, fp)

    # mirror the blended upper-triangle blocks; symmetric F means the
    # mirrored tile equals the directly-blended one would have
    rows = jnp.arange(d_pad)[:, None] // TILE
    cols = jnp.arange(d_pad)[None, :] // TILE
    full = jnp.where(cols >= rows, upper, upper.T)
    return full[:d, :d]


def use_fused_cov_ema_for(d: int, dtype) -> bool:
    """Dispatch the fused cov+EMA kernel only in its artifact-backed win
    regime (family ``cov_ema``), with the same conservative holds as the
    other gates: off-TPU and contaminated-baseline sweeps never dispatch
    (:func:`dispatch_tables.floor_contaminated`)."""
    from kfac_tpu import warnings as kfac_warnings
    from kfac_tpu.ops import dispatch_tables, pallas_gate

    if not (
        pallas_gate.enabled('cov_ema')
        and jax.default_backend() == 'tpu'
    ):
        return False
    sweep = dispatch_tables.floor_contaminated('cov_ema')
    if sweep is not None:
        kfac_warnings.warn_dispatch_event('cov_ema', sweep)
        return False
    from kfac_tpu.ops.pallas_attention import _mosaic_context_ok

    return (
        d >= dispatch_tables.family_min_dim('cov_ema', default=2 * TILE)
        and jnp.dtype(dtype).name in dispatch_tables.family_dtypes(
            'cov_ema', default=('float32',)
        )
        and _mosaic_context_ok()
    )


def fused_cov_ema(
    running: jax.Array | None,
    a: jax.Array,
    alpha: float,
    scale=None,
) -> jax.Array:
    """Drop-in fusion of ``ema_update(running, get_cov(a, scale), alpha)``.

    Dispatches the fused kernel inside its gate (TPU, artifact-backed
    threshold, a trace context a raw Mosaic call can run in); otherwise
    runs the unfused pair, so callers never need their own fallback.
    ``running=None`` follows ``ema_update``'s cold-start semantics
    (identity running factor). Returns the running factor's dtype (f32
    accumulation inside either path).
    """
    from kfac_tpu.ops import cov as cov_lib
    from kfac_tpu.ops import factors

    n, d = a.shape
    if scale is None:
        scale = n

    if not (
        isinstance(alpha, (int, float))
        and use_fused_cov_ema_for(d, a.dtype)
    ):
        return factors.ema_update(
            running, cov_lib.get_cov(a, scale=scale), alpha
        )

    if running is None:
        # ema_update's cold start: identity in the covariance's dtype
        running = jnp.eye(d, dtype=a.dtype)
    out_dtype = jnp.promote_types(running.dtype, a.dtype)

    beta = float(alpha)
    coeff = (1.0 - beta) / float(scale)
    out = _fused(running, a, beta, coeff, interpret=interpret_mode())
    return out.astype(out_dtype)
