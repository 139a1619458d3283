"""Covariance (Kronecker factor) numerics.

Pure jnp, jit-friendly: static shapes, no Python control flow on traced
values. Semantics match the reference math in
/root/reference/kfac/layers/utils.py:8-83 and
/root/reference/kfac/layers/modules.py:100-237, computed the XLA way
(``conv_general_dilated_patches`` instead of ``unfold``; reductions fuse into
the surrounding fwd/bwd).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from kfac_tpu import tracing


def append_bias_ones(x: jax.Array) -> jax.Array:
    """Append a column of ones to the last dimension of ``x``.

    Reference: kfac/layers/utils.py:8-15.
    """
    shape = x.shape[:-1] + (1,)
    return jnp.concatenate([x, jnp.ones(shape, dtype=x.dtype)], axis=-1)


def get_cov(
    a: jax.Array,
    b: jax.Array | None = None,
    scale: float | jax.Array | None = None,
) -> jax.Array:
    """Empirical second moment of a 2D tensor: ``a^T @ (b or a) / scale``.

    The self-covariance is symmetrized ``(C + C^T)/2`` to guard against
    floating-point asymmetry before eigh. Reference:
    kfac/layers/utils.py:18-59.

    On TPU, f32 self-covariances with factor dims spanning ≥ 2 MXU tiles
    dispatch to the triangular Pallas kernel (exactly symmetric by
    construction, half the MXU FLOPs; bf16 inputs stay on XLA) where a
    raw Mosaic call can run: in a one-device process, or on the local
    rows inside a fully-manual ``shard_map``
    (:func:`kfac_tpu.ops.pallas_cov.use_pallas_for`).
    """
    if a.ndim != 2:
        raise ValueError(f'expected 2D tensor, got shape {a.shape}')
    if b is not None and a.shape != b.shape:
        raise ValueError(f'shape mismatch: {a.shape} vs {b.shape}')
    if scale is None:
        scale = a.shape[0]
    if b is None:
        from kfac_tpu.ops import pallas_cov

        if pallas_cov.use_pallas_for(a.shape[1], a.dtype):
            # the gate has checked the trace context: one device, or a
            # fully-manual shard_map where the rows are device-local
            c = pallas_cov.sym_cov(
                a, scale=1.0, interpret=pallas_cov.interpret_mode()
            )
            return c / scale
        cov = a.T @ (a / scale)
        return (cov + cov.T) / 2.0
    return a.T @ (b / scale)


def reshape_data(
    tensors: Sequence[jax.Array],
    batch_first: bool = True,
    collapse_dims: bool = False,
) -> jax.Array:
    """Concatenate tensors along the batch dim, optionally collapsing to 2D.

    Reference: kfac/layers/utils.py:62-83.
    """
    d = jnp.concatenate(list(tensors), axis=int(not batch_first))
    if collapse_dims and d.ndim > 2:
        d = d.reshape(-1, d.shape[-1])
    return d


def extract_patches_nhwc(
    x: jax.Array,
    kernel_size: tuple[int, int],
    strides: tuple[int, int],
    padding: str | Sequence[tuple[int, int]],
) -> jax.Array:
    """im2col for NHWC images -> (batch, out_h, out_w, in_c * kh * kw).

    Feature ordering is channel-major (c, kh, kw), matching
    ``lax.conv_general_dilated_patches`` and the (out, in*kh*kw) weight
    matricization used by the conv helper. TPU-native replacement for the
    reference's ``Tensor.unfold`` chain
    (kfac/layers/modules.py:210-237).
    """
    if isinstance(padding, str):
        pad = padding
    else:
        pad = [tuple(p) for p in padding]
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=kernel_size,
        window_strides=strides,
        padding=pad,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
    )
    return patches


def linear_a_factor(
    a: jax.Array,
    has_bias: bool,
    dtype: jnp.dtype | None = None,
) -> jax.Array:
    """A factor for a dense layer from its input activations.

    Flattens leading dims into rows ((batch, seq, d) -> (batch*seq, d)),
    appends the bias column of ones, and returns the scaled covariance.
    Reference: kfac/layers/modules.py:123-132.
    """
    if dtype is not None:
        a = a.astype(dtype)
    a = a.reshape(-1, a.shape[-1])
    if has_bias:
        a = append_bias_ones(a)
    return get_cov(a)


def linear_g_factor(
    g: jax.Array,
    dtype: jnp.dtype | None = None,
) -> jax.Array:
    """G factor for a dense layer from the loss gradient w.r.t. its output.

    Reference: kfac/layers/modules.py:134-141.
    """
    if dtype is not None:
        g = g.astype(dtype)
    g = g.reshape(-1, g.shape[-1])
    return get_cov(g)


def routed_linear_a_factor(
    a: jax.Array,
    has_bias: bool,
    dtype: jnp.dtype | None = None,
) -> jax.Array:
    """A factor over only the NONZERO rows — exact per-expert statistics
    for row-masked (MoE-routed) dense layers.

    A routed expert sees a buffer where non-routed rows are identically
    zero; the plain :func:`linear_a_factor` then (a) normalizes by the
    TOTAL row count, scaling the factor by the routed fraction, and
    (b) appends bias ones to EVERY row, inflating the bias corner by the
    empty rows — the two documented approximations quantified in
    tests/test_moe.py. This variant detects the zero rows, appends the
    bias one only to live rows, and normalizes by the live count: the
    result equals the covariance computed from just the routed tokens
    (the per-expert oracle). An all-zero input returns zeros (count
    floors at one). The covariance still rides :func:`get_cov` (Pallas
    on TPU); the correction is one mask reduction plus a scalar rescale.

    Caveat (same as :func:`routed_linear_g_factor`'s): a ROUTED token
    whose layer input is exactly all-zero — e.g. a fully-dead ReLU hidden
    vector feeding an expert down-projection — is indistinguishable from
    an unrouted row, so it is miscounted as unrouted AND loses its
    bias-ones contribution. With saturating/sparse activations the A-side
    live count can therefore undercount; the resulting overnormalization
    is bounded by 1/n_live per such row.

    Exactness scope: PER CAPTURE, with cross-capture traffic weighting.
    Routed captures also emit their live-row fraction as an evidence
    weight (:func:`routed_live_fraction`, surfaced as
    ``CapturedStats.w``), and the dense and KAISA engines weight the
    factor EMA by it (``alpha_eff = 1 - (1-alpha)*w``): a capture where
    the expert received ZERO tokens leaves the running factor untouched
    (previously its all-zero matrix diluted the EMA toward zero), and
    light-traffic captures move the estimate proportionally less. The
    pipeline engine's in-schedule capture keeps the equal-weight
    convention (its stats path carries no weights); grad-accumulation
    micro-steps average factors equally and carry the mean live fraction
    as the combined weight.
    """
    if dtype is not None:
        a = a.astype(dtype)
    a = a.reshape(-1, a.shape[-1])
    nz = (jnp.max(jnp.abs(a), axis=-1) > 0).astype(a.dtype)
    n = jnp.maximum(jnp.sum(nz), 1.0)
    if has_bias:
        a = jnp.concatenate([a, nz[:, None]], axis=-1)
    return get_cov(a) * (a.shape[0] / n)


def routed_live_fraction(a: jax.Array) -> jax.Array:
    """Fraction of rows with any nonzero entry — the per-capture evidence
    weight for token-count-weighted factor EMA on routed layers.

    Uses the same zero-row detection as :func:`routed_linear_a_factor`
    (and shares its dead-activation caveat), so the weight and the
    factor normalization always count the same row set. Returns a scalar
    in [0, 1]; an expert that received no tokens this capture weighs 0,
    which makes the engines' weighted EMA leave its running factor
    untouched instead of diluting it toward zero.
    """
    a = a.reshape(-1, a.shape[-1])
    nz = jnp.max(jnp.abs(a), axis=-1) > 0
    return jnp.mean(nz.astype(jnp.float32))


def routed_linear_g_factor(
    g: jax.Array,
    dtype: jnp.dtype | None = None,
) -> jax.Array:
    """G factor normalized by the nonzero-cotangent row count (the routed
    tokens: non-routed rows have exactly-zero output cotangents). Caveat:
    a ROUTED row whose cotangent happens to vanish is miscounted as
    unrouted — generically measure-zero, and the resulting overnormalize
    is bounded by 1/n_e per such row.
    """
    if dtype is not None:
        g = g.astype(dtype)
    g = g.reshape(-1, g.shape[-1])
    nz = (jnp.max(jnp.abs(g), axis=-1) > 0).astype(g.dtype)
    n = jnp.maximum(jnp.sum(nz), 1.0)
    return get_cov(g) * (g.shape[0] / n)


def conv2d_a_factor(
    a: jax.Array,
    kernel_size: tuple[int, int],
    strides: tuple[int, int],
    padding: str | Sequence[tuple[int, int]],
    has_bias: bool,
    dtype: jnp.dtype | None = None,
) -> jax.Array:
    """A factor for a 2D conv layer (NHWC input).

    Patch rows are normalized by the spatial output size, mirroring the
    reference's KFC normalization (kfac/layers/modules.py:173-182).
    """
    if dtype is not None:
        a = a.astype(dtype)
    # the scope holds everything between the activations and the
    # covariance's operand: XLA rewrites most of the identity-kernel
    # convolutions into window copies fused under the division's root, so
    # the extraction alone keeps the name for a few layers only
    with tracing.capture_scope('patches'):
        patches = extract_patches_nhwc(a, kernel_size, strides, padding)
        spatial_size = patches.shape[1] * patches.shape[2]
        rows = patches.reshape(-1, patches.shape[-1])
        if has_bias:
            rows = append_bias_ones(rows)
        rows = rows / spatial_size
    return get_cov(rows)


def conv2d_g_factor(
    g: jax.Array,
    dtype: jnp.dtype | None = None,
) -> jax.Array:
    """G factor for a 2D conv layer from NHWC output gradients.

    Reference (NCHW variant): kfac/layers/modules.py:184-194.
    """
    if dtype is not None:
        g = g.astype(dtype)
    spatial_size = g.shape[1] * g.shape[2]
    rows = g.reshape(-1, g.shape[-1])
    rows = rows / spatial_size
    return get_cov(rows)
