"""Pallas TPU kernels for kl-clip: a standalone pair, off the step path.

**Fused kl-clip** (:func:`fused_klclip_dot` / :func:`fused_klclip_scale`):
the second-moment contraction ``sum(pmat * gmat)`` and the scale
application ``pmat * scale``, tiled 128 x 128 with the scalar reduction
accumulated across the grid. Until PR 37 ``factors.kl_clip_terms`` /
``kl_clip_apply`` dispatched them from 512^2 elements on a one-device
TPU; since then those two are XLA's expressions everywhere and nothing
in the library calls this module. Measured on a v5e (``PERF.md``
section 6, PR 37): XLA's multiply-reduce and multiply are 2.1-2.9x
faster than the pair at every shape the benchmark's cells have, 3.2x
where the operands are slices of a stack in HBM (a custom call takes
whole buffers, so each slice is copied out for it; 64 KB an operand a
grid step is less than a grid step's own overhead), and the opaque
scale kept the compiler from fusing ``p * scale`` into the optimizer's
update. The module stays until ``benchmark/readings.py`` stops
importing it (ROADMAP D5); its tests hold it as a kernel of its own.

Equivalence contract (pinned by tests/ops/test_fused_kernels.py in the
interpreter, and on the chip by ``chip_smoke.py``): f32 allclose to the
unfused expressions above, contracted at f32 precision on both sides,
for dense and stacked (vmapped) tensors.

:func:`use_fused_klclip_for` says where the pair *can* run (backend,
shape, trace context); no caller asks it any more.

The module keeps its name from the fused Newton-Schulz pair it also held
until PR 26 (``_ns_xupdate_kernel`` / ``_ns_mx_resid_kernel``: both
products and the residual of one iteration in two Mosaic kernels). On a
v5e that pair in its 128^3 blocks ran at 8.9 TFLOP/s, 27% of the
six-pass f32 peak, against 27-30 for XLA's own tiling of the same two
``HIGHEST`` products (``ops.factors.newton_schulz_step``); in blocks of
640 to 1,152 it reached 30-31, 4-7% ahead of XLA at four widths and level
elsewhere, which was inside the run-to-run spread of every end-to-end
metric and cost a block chooser, a VMEM override and megabytes of
unrolled kernel code a bucket. So the iteration is XLA's at every width.
One thing the pair did buy: at 3,200 wide a cold solve reached residual
6e-6 to 8e-6 where XLA's tiling of the same product stops at 1.9e-5
(``PERF.md`` section 6, PR 26, has the table; section 7 the question).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kfac_tpu.ops.pallas_gate import interpret_mode, mosaic_context_ok

TILE = 128       # lane-aligned block edge

# The size from which the pair dispatched until PR 37: an off-chip prior
# the chip did not bear out. XLA's two expressions beat the pair at every
# shape measured (4 MB to 16 MB an operand, and slices of a 100 MB
# stack), so there is no size from which it wins and nothing reads this
# but ``use_fused_klclip_for``.
_MIN_KLCLIP_DIM = 4 * TILE


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


# ------------------------------------------------------------------ kl-clip


def _klclip_dot_kernel(p_ref, g_ref, part_ref):
    """Tiled f32 multiply-reduce: each tile writes the lane-shaped
    (1, TILE) column sums of ``p * g`` (Mosaic cannot store a scalar to
    VMEM); the caller sums the partials."""
    part_ref[:] = jnp.sum(
        p_ref[:].astype(jnp.float32) * g_ref[:].astype(jnp.float32),
        axis=0, keepdims=True,
    )


def _klclip_scale_kernel(s_ref, p_ref, out_ref):
    """Tiled f32 scale application ``p * s``; the traced scalar ``s`` is
    a (1, 1) SMEM operand."""
    out_ref[:] = p_ref[:].astype(jnp.float32) * s_ref[0, 0]


@functools.partial(jax.jit, static_argnames=('interpret',))
def fused_klclip_dot(
    p: jax.Array, g: jax.Array, interpret: bool = False
) -> jax.Array:
    """f32 scalar ``sum(p * g)`` over 2D tensors via the tiled Pallas
    multiply-reduce (padding with zeros is exact)."""
    r, c = p.shape
    r_pad = -(-r // TILE) * TILE
    c_pad = -(-c // TILE) * TILE
    pp = _pad_to(p, r_pad, c_pad)
    gp = _pad_to(g, r_pad, c_pad)
    grid = (r_pad // TILE, c_pad // TILE)
    parts = pl.pallas_call(
        _klclip_dot_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (*grid, 1, TILE), jnp.float32, vma=jax.typeof(pp).vma
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
            pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, 1, TILE), lambda i, j: (i, j, 0, 0)
        ),
        interpret=interpret,
        name='_klclip_dot_kernel',
    )(pp, gp)
    return jnp.sum(parts)


@functools.partial(jax.jit, static_argnames=('interpret',))
def fused_klclip_scale(
    p: jax.Array, scale: jax.Array, interpret: bool = False
) -> jax.Array:
    """f32 ``p * scale`` via the tiled Pallas scale kernel; ``scale`` is
    a traced scalar (it depends on the cross-layer vg sum)."""
    r, c = p.shape
    r_pad = -(-r // TILE) * TILE
    c_pad = -(-c // TILE) * TILE
    pp = _pad_to(p, r_pad, c_pad)
    s = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        _klclip_scale_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (r_pad, c_pad), jnp.float32, vma=jax.typeof(pp).vma
        ),
        grid=(r_pad // TILE, c_pad // TILE),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((TILE, TILE), lambda i, j: (i, j)),
        interpret=interpret,
        name='_klclip_scale_kernel',
    )(s, pp)
    return out[:r, :c]


def use_fused_klclip_for(shape: tuple[int, ...]) -> bool:
    """Whether the fused kl-clip pair can run on a tensor of this shape
    (no caller dispatches on it since PR 37),
    from what can be observed here: a TPU backend, a 2-D tensor of at
    least ``_MIN_KLCLIP_DIM ** 2`` elements (by element count, so
    rectangular weights with the traffic of a square one decide alike),
    and a trace context a raw Mosaic call can run in
    (:func:`pallas_gate.mosaic_context_ok`)."""
    return (
        jax.default_backend() == 'tpu'
        and len(shape) == 2
        and shape[0] * shape[1] >= _MIN_KLCLIP_DIM ** 2
        and mosaic_context_ok()
    )
