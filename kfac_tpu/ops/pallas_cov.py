"""Pallas TPU kernel: symmetric covariance ``a^T a / scale``.

The factor-statistics hot spot computes ``C = a^T @ a`` where C is
symmetric — a plain matmul spends half its MXU FLOPs recomputing the lower
triangle. This kernel tiles C into (TILE x TILE) blocks on a
(row_blk, col_blk, k) grid and runs the MXU only for blocks on or above the
diagonal; the lower triangle is mirrored with a cheap elementwise select
afterwards. Numerically the result is exactly symmetric, so the reference's
defensive ``(C + C^T)/2`` symmetrization (kfac/layers/utils.py:18-59)
becomes a no-op by construction.

Where it runs: batch-sharded activation rows cannot flow into a plain
``pallas_call`` (XLA cannot partition an opaque custom call), so
``ops.cov.get_cov`` dispatches here only where a raw Mosaic call can
execute — a one-device process, or a fully-manual ``shard_map`` region,
where it runs on the device-local rows (:func:`use_pallas_for`). Under
GSPMD on several devices the plain ``a^T a`` contraction stays with XLA,
which partitions it as local rows + psum by itself. (A
``custom_partitioning`` wrapper used to carry the kernel through GSPMD;
on a four-chip v5e host under jax 0.9.0 / libtpu 0.0.34 its
``CustomSPMDPartitioning`` call reached the TPU backend unpartitioned —
"Custom emitter for CustomSPMDPartitioning not found", PR 21 — and it
went.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 128       # lane-aligned C-block edge
K_BLOCK = 512    # rows of `a` consumed per reduction step


def _sym_cov_kernel(a_i_ref, a_j_ref, out_ref):
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
            (((0,), (0,)), ((), ())),  # contract over the row (sample) dim
            preferred_element_type=jnp.float32,
        )


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


@functools.partial(jax.jit, static_argnames=('interpret',))
def sym_cov(a: jax.Array, scale=None, interpret: bool = False) -> jax.Array:
    """Symmetric second moment ``a^T @ (a / scale)`` via the triangular
    Pallas kernel. ``a`` is (N, D); returns (D, D) in ``a.dtype``.
    """
    n, d = a.shape
    if scale is None:
        scale = n
    out_dtype = a.dtype
    n_pad = -(-n // K_BLOCK) * K_BLOCK
    d_pad = -(-d // TILE) * TILE
    ap = _pad_to(a, n_pad, d_pad)  # zero rows/cols do not affect a^T a
    nblk = d_pad // TILE
    nk = n_pad // K_BLOCK

    # inside a vma-checked shard_map the output varies over the same mesh
    # axes as the (device-local) input rows
    upper = pl.pallas_call(
        _sym_cov_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (d_pad, d_pad), jnp.float32, vma=jax.typeof(ap).vma
        ),
        grid=(nblk, nblk, nk),
        in_specs=[
            pl.BlockSpec((K_BLOCK, TILE), lambda i, j, k: (k, i)),
            pl.BlockSpec((K_BLOCK, TILE), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j)),
        interpret=interpret,
        name='_sym_cov_kernel',
    )(ap, ap)

    # mirror the strictly-lower-triangle blocks from the computed uppers
    rows = jnp.arange(d_pad)[:, None] // TILE
    cols = jnp.arange(d_pad)[None, :] // TILE
    full = jnp.where(cols >= rows, upper, upper.T)
    cov = full[:d, :d] / scale
    return cov.astype(out_dtype)


def interpret_mode() -> bool:
    """Run the kernel in interpret mode off-TPU (tests, CPU meshes).

    Every Pallas family routes through this, so off a TPU the kernels
    silently become the Pallas interpreter — right for tests, and never
    a measurement. Nothing here proves Mosaic compiled anything:
    ``chip_smoke.py`` does, by refusing to run unless
    ``jax.devices()[0].platform == 'tpu'`` and by listing the
    ``tpu_custom_call`` kernels found in the compiled step programs.
    """
    return jax.default_backend() != 'tpu'


def use_pallas_for(d: int, dtype) -> bool:
    """Dispatch the kernel only inside its threshold regime.

    The thresholds come from the committed derivation artifact
    (:mod:`kfac_tpu.ops.dispatch_tables`,
    ``kfac_tpu/ops/dispatch_thresholds.json``) with the original
    constants as the load-or-default fallback:

    - factor dim spanning >= 2 MXU tiles (small factors are
      latency-bound either way), and
    - f32 inputs only: at bf16 XLA's native-input matmul was the faster
      one in the single 2026-07-31 chip session these priors come from.
      That session's f32 baseline sweep was latency-floor contaminated
      (flat across an 8x size range) and its records are gone, so no
      speed-up is claimed for the kernel: whether it earns its place is
      ROADMAP S5's question, on the benchmark.

    ``dtype`` is required so a call site cannot silently re-open the
    measured-loss bf16 regime. Overridable via ``KFAC_TPU_PALLAS``
    (:mod:`kfac_tpu.ops.pallas_gate`). When the committed artifact's own
    provenance marks the backing baseline sweep latency-floor
    contaminated, the gate does not trust the threshold at all: it holds
    the conservative XLA default and warns once, naming the sweep."""
    from kfac_tpu import warnings as kfac_warnings
    from kfac_tpu.ops import dispatch_tables, pallas_gate

    if not (
        pallas_gate.enabled('cov') and jax.default_backend() == 'tpu'
    ):
        return False
    sweep = dispatch_tables.floor_contaminated('cov')
    if sweep is not None:
        kfac_warnings.warn_dispatch_event('cov', sweep)
        return False
    from kfac_tpu.ops.pallas_attention import _mosaic_context_ok

    return (
        d >= dispatch_tables.cov_min_dim(default=2 * TILE)
        and jnp.dtype(dtype).name in dispatch_tables.cov_dtypes(
            default=('float32',)
        )
        and _mosaic_context_ok()
    )
