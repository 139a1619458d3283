"""Fused Pallas TPU kernels for the step path: Newton-Schulz iteration
and kl-clip.

**Fused NS iteration** (:func:`fused_ns_step`): the
``newton_schulz_inverse_info`` body costs two (d, d) matmuls plus a
residual reduction per iteration:

    x_new  = x @ (2I - mx)        # mx cached from the previous step
    mx_new = m @ x_new
    resid  = ||I - mx_new||_F / sqrt(d)

The unfused path materializes ``2I - mx`` in HBM (one d^2 write + read)
and runs the residual as a separate elementwise+reduce pass over
``mx_new`` (another d^2 read). The fused pair of kernels removes both:
the first builds each ``2I - mx`` tile in VMEM inside the matmul's
reduction loop (the identity is synthesized from the grid indices, never
stored), the second accumulates the identity-residual sum-of-squares in
the epilogue of the ``m @ x_new`` tile it just produced, while the tile
is still VMEM-resident. The stopping rule in
``newton_schulz_inverse_info`` consumes the returned residual unchanged.

**Fused kl-clip** (:func:`fused_klclip_dot` / :func:`fused_klclip_scale`):
the second-moment contraction ``sum(pmat * gmat)`` and the scale
application ``pmat * scale`` are each a full d^2 read the XLA path runs
as separate elementwise passes; the Pallas forms run them tiled with the
scalar reduction accumulated across the grid, which keeps the
contraction's f32 upcast in VMEM. The scalar *decision*
(``kl_clip_scale``: ``min(1, sqrt(kl/|vg|))``) is unchanged — it is
cross-layer, so it cannot fuse into any per-layer kernel.

Equivalence contract (pinned by tests/ops/test_fused_kernels.py in the
interpreter, and on the chip by ``chip_smoke.py``): f32 allclose to the
unfused expressions above (``ops.factors.newton_schulz_step`` for the
iteration, contracted at f32 precision on both sides), for dense and
stacked (vmapped) factors.

Dispatch: families ``ns`` and ``klclip`` in the committed threshold
artifact (:mod:`kfac_tpu.ops.dispatch_tables`); the NS kernels
additionally require whole (TILE, TILE) tiling (``d % TILE == 0``) so
the identity synthesis never needs a padding mask inside the iteration
loop. Off-TPU, below threshold, in partial-manual trace contexts, or
under a contaminated baseline sweep the callers fall back to the
unfused expressions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kfac_tpu.ops.factors import NS_PRECISION
from kfac_tpu.ops.pallas_cov import TILE, _pad_to, interpret_mode


def _eye_tile(i, j):
    """The (TILE, TILE) block (i, j) of the identity, synthesized from
    grid indices — never read from HBM."""
    gr = i * TILE + jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
    gc = j * TILE + jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
    return (gr == gc).astype(jnp.float32)


def _ns_xupdate_kernel(x_ref, mx_ref, out_ref):
    """``x_new[i,j] = sum_k x[i,k] @ (2I - mx)[k,j]`` with the
    ``2I - mx`` tile built in VMEM inside the reduction loop."""
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    y = 2.0 * _eye_tile(k, j) - mx_ref[:]
    out_ref[:] += jax.lax.dot_general(
        x_ref[:], y,
        (((1,), (0,)), ((), ())),
        # Mosaic's default contraction rounds f32 operands to bf16 too
        precision=NS_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _ns_mx_resid_kernel(m_ref, x_ref, out_ref, part_ref):
    """``mx_new[i,j] = sum_k m[i,k] @ x_new[k,j]`` with the identity
    residual ``(I - mx_new)^2`` reduced in the epilogue while the
    finished tile is VMEM-resident. Each (i, j) tile writes its own
    lane-shaped (1, TILE) partial — Mosaic cannot store a scalar to
    VMEM — and the caller sums the partials."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += jax.lax.dot_general(
        m_ref[:], x_ref[:],
        (((1,), (0,)), ((), ())),
        # Mosaic's default contraction rounds f32 operands to bf16 too
        precision=NS_PRECISION,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _resid():
        delta = _eye_tile(i, j) - out_ref[:]
        part_ref[:] = jnp.sum(delta * delta, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=('interpret',))
def fused_ns_step(
    m: jax.Array,
    x: jax.Array,
    mx: jax.Array,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused Newton-Schulz iteration: ``(x_new, mx_new, resid)``
    matching the unfused body of ``newton_schulz_inverse_info`` (f32).

    Requires ``d % TILE == 0`` (the gate enforces it); all three inputs
    are (d, d) f32.
    """
    d = m.shape[-1]
    nb = d // TILE
    grid = (nb, nb, nb)
    # inside a vma-checked shard_map (the stacked engine's sharded
    # inverse) the outputs vary over the same mesh axes as the factor
    vma = jax.typeof(m).vma
    tile_spec = pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, j))

    x_new = pl.pallas_call(
        _ns_xupdate_kernel,
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32, vma=vma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, k)),
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (k, j)),
        ],
        out_specs=tile_spec,
        interpret=interpret,
        name='_ns_xupdate_kernel',
    )(x, mx)

    mx_new, resid_parts = pl.pallas_call(
        _ns_mx_resid_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((nb, nb, 1, TILE), jnp.float32, vma=vma),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (i, k)),
            pl.BlockSpec((TILE, TILE), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            tile_spec,
            pl.BlockSpec((None, None, 1, TILE), lambda i, j, k: (i, j, 0, 0)),
        ],
        interpret=interpret,
        name='_ns_mx_resid_kernel',
    )(m, x_new)

    sqrt_d = jnp.sqrt(jnp.asarray(d, jnp.float32))
    resid = jnp.sqrt(jnp.sum(resid_parts)) / sqrt_d
    return x_new, mx_new, resid


def use_fused_ns_for(d: int) -> bool:
    """Dispatch the fused NS iteration only in its artifact-backed win
    regime (family ``ns``): TPU, whole-tile dims, a trace context a raw
    ``pallas_call`` can execute in, and a clean backing sweep."""
    from kfac_tpu import warnings as kfac_warnings
    from kfac_tpu.ops import dispatch_tables, pallas_gate
    from kfac_tpu.ops.pallas_attention import _mosaic_context_ok

    if not (
        pallas_gate.enabled('ns') and jax.default_backend() == 'tpu'
    ):
        return False
    sweep = dispatch_tables.floor_contaminated('ns')
    if sweep is not None:
        kfac_warnings.warn_dispatch_event('ns', sweep)
        return False
    return (
        d % TILE == 0
        and d >= dispatch_tables.family_min_dim('ns', default=4 * TILE)
        and _mosaic_context_ok()
    )


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
    """Dispatch the fused kl-clip kernels only in their artifact-backed
    win regime (family ``klclip``): the gate compares the tensor's
    element count against ``min_dim**2`` (the family's sweep is over
    square (d, d) preconditioned gradients), so rectangular weights with
    equivalent traffic dispatch consistently."""
    from kfac_tpu import warnings as kfac_warnings
    from kfac_tpu.ops import dispatch_tables, pallas_gate
    from kfac_tpu.ops.pallas_attention import _mosaic_context_ok

    if not (
        pallas_gate.enabled('klclip')
        and jax.default_backend() == 'tpu'
    ):
        return False
    sweep = dispatch_tables.floor_contaminated('klclip')
    if sweep is not None:
        kfac_warnings.warn_dispatch_event('klclip', sweep)
        return False
    if len(shape) != 2:
        return False
    min_dim = dispatch_tables.family_min_dim('klclip', default=4 * TILE)
    return shape[0] * shape[1] >= min_dim * min_dim and _mosaic_context_ok()
