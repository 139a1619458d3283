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


def _operand_precision(dtype) -> jax.lax.Precision | None:
    """``HIGHEST`` for operands the MXU would otherwise round (float32
    and wider); ``None`` for 16-bit operands, whose products are exact
    in the float32 accumulator at the default."""
    return (
        jax.lax.Precision.HIGHEST if jnp.dtype(dtype).itemsize >= 4 else None
    )


def get_cov(
    a: jax.Array,
    b: jax.Array | None = None,
    scale: float | jax.Array | None = None,
) -> jax.Array:
    """Empirical second moment of a 2D tensor: ``a^T @ (b or a) / scale``.

    One ``dot_general`` that contracts the row axis and accumulates in
    float32, the same on one device, under GSPMD (local rows, then the
    partitioner's sum) and on the local rows inside a ``shard_map``.
    The rule for its operands, for every caller:

    1. The covariance sees what the layer's own product sees. The
       operands are multiplied in the dtype they arrive in, which the
       capture taps make the dtype the layer rounds its input to (the
       flax module's ``dtype``; a cotangent already has it). Products of
       16-bit operands are exact in the float32 accumulator; float32
       (or wider) operands are multiplied at ``Precision.HIGHEST``.
    2. Scales leave the operands: ``scale`` (default: the row count)
       divides the ``d x d`` result, never the rows, so a 16-bit operand
       stays the exact value the layer multiplied (a bias column of ones
       is exact in any dtype).

    The result is float32 (or wider, with the operands). The
    self-covariance is symmetrized ``(C + C^T)/2`` to guard against
    floating-point asymmetry before eigh. Reference:
    kfac/layers/utils.py:18-59.
    """
    if a.ndim != 2:
        raise ValueError(f'expected 2D tensor, got shape {a.shape}')
    if b is not None and a.shape != b.shape:
        raise ValueError(f'shape mismatch: {a.shape} vs {b.shape}')
    if scale is None:
        scale = a.shape[0]
    rhs = a if b is None else b
    dtype = jnp.result_type(a, rhs)
    cov = jax.lax.dot_general(
        a, rhs,
        (((0,), (0,)), ((), ())),  # contract over the row (sample) dim
        precision=_operand_precision(dtype),
        preferred_element_type=jnp.promote_types(dtype, jnp.float32),
    ) / scale
    if b is None:
        cov = (cov + cov.T) / 2.0
    return cov


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
    (kfac/layers/modules.py:210-237). The identity-kernel convolution
    behind it copies 16-bit values exactly; float32 values go at
    ``Precision.HIGHEST``, as in :func:`get_cov`, or the MXU would round
    them on the way.
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
        precision=_operand_precision(x.dtype),
    )
    return patches


def _rows(x: jax.Array) -> jax.Array:
    """Leading dims flattened into covariance rows."""
    return x.reshape(-1, x.shape[-1])


def _live_rows(rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(mask, count)``: which rows hold any nonzero entry, and how many
    they are (float32: a 16-bit count would round)."""
    live = jnp.max(jnp.abs(rows), axis=-1) > 0
    return live, jnp.sum(live, dtype=jnp.float32)


def linear_a_factor(a: jax.Array, has_bias: bool) -> jax.Array:
    """A factor for a dense layer from its input activations.

    Flattens leading dims into rows ((batch, seq, d) -> (batch*seq, d)),
    appends the bias column of ones, and returns the covariance,
    multiplied as :func:`get_cov` states: the caller hands the input in
    the dtype the layer rounds it to. Reference:
    kfac/layers/modules.py:123-132.
    """
    rows = _rows(a)
    if has_bias:
        rows = append_bias_ones(rows)
    return get_cov(rows)


def linear_g_factor(g: jax.Array) -> jax.Array:
    """G factor for a dense layer from the loss gradient w.r.t. its output.

    Reference: kfac/layers/modules.py:134-141.
    """
    return get_cov(_rows(g))


def routed_linear_a_factor(a: jax.Array, has_bias: bool) -> jax.Array:
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
    floors at one). The covariance is :func:`get_cov`'s, scaled by the
    live count; the correction is one mask reduction.

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
    rows = _rows(a)
    live, n = _live_rows(rows)
    if has_bias:
        rows = jnp.concatenate(
            [rows, live[:, None].astype(rows.dtype)], axis=-1
        )
    return get_cov(rows, scale=jnp.maximum(n, 1.0))


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
    rows = _rows(a)
    return _live_rows(rows)[1] / rows.shape[0]


def routed_linear_g_factor(g: jax.Array) -> jax.Array:
    """G factor normalized by the nonzero-cotangent row count (the routed
    tokens: non-routed rows have exactly-zero output cotangents). Caveat:
    a ROUTED row whose cotangent happens to vanish is miscounted as
    unrouted — generically measure-zero, and the resulting overnormalize
    is bounded by 1/n_e per such row.
    """
    rows = _rows(g)
    return get_cov(rows, scale=jnp.maximum(_live_rows(rows)[1], 1.0))


def conv2d_a_factor(
    a: jax.Array,
    kernel_size: tuple[int, int],
    strides: tuple[int, int],
    padding: str | Sequence[tuple[int, int]],
    has_bias: bool,
) -> jax.Array:
    """A factor for a 2D conv layer (NHWC input).

    Patch rows are normalized by the spatial output size, mirroring the
    reference's KFC normalization (kfac/layers/modules.py:173-182):
    ``cov(rows / s) = rows^T rows / (N s^2)``, the scale on the result
    (:func:`get_cov`, rule 2), so the rows are extracted from the
    activations in the dtype the layer multiplies them in.
    """
    # the scope holds everything between the activations and the
    # covariance's operand: XLA rewrites most of the identity-kernel
    # convolutions into window copies, so the extraction alone keeps the
    # name for a few layers only
    with tracing.capture_scope('patches'):
        patches = extract_patches_nhwc(a, kernel_size, strides, padding)
        spatial_size = patches.shape[1] * patches.shape[2]
        rows = _rows(patches)
        if has_bias:
            rows = append_bias_ones(rows)
    return get_cov(rows, scale=float(rows.shape[0] * spatial_size**2))


def conv2d_g_factor(g: jax.Array) -> jax.Array:
    """G factor for a 2D conv layer from NHWC output gradients.

    Reference (NCHW variant): kfac/layers/modules.py:184-194.
    """
    spatial_size = g.shape[1] * g.shape[2]
    rows = _rows(g)
    return get_cov(rows, scale=float(rows.shape[0] * spatial_size**2))
