"""Covariance (Kronecker factor) numerics.

Pure jnp, jit-friendly: static shapes, no Python control flow on traced
values. Semantics match the reference math in
/root/reference/kfac/layers/utils.py:8-83 and
/root/reference/kfac/layers/modules.py:100-237, computed the XLA way
(reductions fuse into the surrounding fwd/bwd). A convolution's A factor
takes one of two routes by its geometry (:func:`conv2d_a_is_patchless`):
stride 1 with an odd kernel and padding that keeps the grid assembles it
from the activation's autocorrelation, the convolution XLA runs for a
weight gradient, and writes no patch row; every other geometry extracts
patch rows (``conv_general_dilated_patches`` instead of ``unfold``) and
multiplies them.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

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


def conv2d_a_is_patchless(
    kernel_size: tuple[int, int],
    strides: tuple[int, int],
    padding: str | Sequence[tuple[int, int]],
) -> bool:
    """Whether :func:`conv2d_a_factor` assembles this geometry's factor
    from the activation's autocorrelation and writes no patch row: stride
    1 both ways, an odd kernel larger than 1 x 1, and padding under which
    the output grid is the input grid (``'SAME'`` or the explicit
    ``(k - 1) / 2`` pairs)."""
    kh, kw = kernel_size
    if tuple(strides) != (1, 1) or kh % 2 == 0 or kw % 2 == 0 or kh * kw == 1:
        return False
    if isinstance(padding, str):
        return padding.upper() == 'SAME'
    return [tuple(p) for p in padding] == [(kh // 2,) * 2, (kw // 2,) * 2]


def _correlate(
    x: jax.Array, reach: tuple[int, int], groups: int = 1
) -> jax.Array:
    """``out[e, f, c', g, c] = sum_{b,i,j} x~[b, i+e-rh, j+f-rw, (g, c')]
    x[b, i, j, (g, c)]`` over the offsets within ``reach = (rh, rw)``
    either way, ``x~`` being NHWC ``x`` and zero outside it, the channels
    ``groups`` runs that are each correlated with themselves alone: the
    convolution XLA runs for a weight gradient (the batch contracted, the
    window the image), multiplied as :func:`get_cov` states.

    ``R[-d] = R[d]^T`` halves the work: along the first axis with a reach
    only the offsets from 0 on are multiplied, the negative ones are
    their mirror images, and offset 0 is averaged with its own, so the
    result is its own mirror image to the bit and so is a factor
    assembled from it. (The convolution's output order is the one that
    compiles: with ``c`` ahead of ``c'`` the TPU compiler takes four
    times as long over the same product.)
    """
    half = next((i for i, r in enumerate(reach) if r), None)
    out = jax.lax.conv_general_dilated(
        x, x,
        window_strides=(1, 1),
        padding=[(0 if i == half else r, r) for i, r in enumerate(reach)],
        dimension_numbers=('CHWN', 'IHWO', 'HWNC'),
        batch_group_count=groups,
        precision=_operand_precision(x.dtype),
        preferred_element_type=jnp.promote_types(x.dtype, jnp.float32),
    )
    run = x.shape[-1] // groups
    out = out.reshape(out.shape[:2] + (run, groups, run))

    def mirror(t):
        return jnp.transpose(jnp.flip(t, (0, 1)), (0, 1, 4, 3, 2))

    if half is None:
        return (out + mirror(out)) / 2.0
    zero = jax.lax.slice_in_dim(out, 0, 1, axis=half)
    rest = jax.lax.slice_in_dim(out, 1, None, axis=half)
    return jnp.concatenate(
        [mirror(rest), (zero + mirror(zero)) / 2.0, rest], axis=half
    )


def _halo(x: jax.Array, axis: int, p: int, high: bool) -> jax.Array:
    """What the ``p`` window positions beyond one edge of ``axis`` read
    from inside the image: ``axis`` shrinks to 1, the positions fold into
    the batch, and the ``p`` kernel offsets that reach inside fold into
    the channels (offset-major, ascending)."""
    n = x.shape[axis]
    band = jax.lax.slice_in_dim(
        x, n - p if high else 0, n if high else p, axis=axis
    )
    widths = [(0, 0)] * 4
    widths[axis] = (0, p - 1) if high else (p - 1, 0)
    band = jnp.pad(band, widths)
    windows = jnp.stack([
        jax.lax.slice_in_dim(band, i, i + p, axis=axis) for i in range(p)
    ])
    # (position, batch, other axis, offset, channel)
    windows = jnp.moveaxis(windows, axis + 1, -2)
    i, b, n_other, s, c = windows.shape
    return jnp.expand_dims(windows.reshape(i * b, n_other, s * c), axis)


def _halo_band(u: int, u2: int, p: int) -> tuple[int, int, int] | None:
    """Kernel offsets ``u, u2`` of ``2p + 1`` both reach inside from
    beyond the low edge (the last ``p`` of them) or both from beyond the
    high one (the first ``p``): which edge (0 low, 1 high) and their
    places in :func:`_halo`'s order; else ``None``."""
    if u > p and u2 > p:
        return 0, u - p - 1, u2 - p - 1
    if u < p and u2 < p:
        return 1, u, u2
    return None


def _autocorrelation_a_factor(
    x: jax.Array, kernel_size: tuple[int, int], has_bias: bool
) -> jax.Array:
    """The stride-1 ``SAME`` A factor with no patch rows.

    A patch is a window of the zero-extended activation, ``P[b, q, d, c]
    = x~[b, q + d, c]``, so the block of kernel offsets ``d, d'`` is
    ``sum_q x~[q + d] x~[q + d']^T`` over the output grid. Over EVERY
    ``q`` that sum is the autocorrelation ``R[d' - d]``, ``(2kh - 1)(2kw -
    1)`` offsets in place of ``(kh kw)^2`` blocks, one weight-gradient
    convolution of the activation with itself. What it counts beyond the
    grid is the halo: the positions within half a kernel of an edge,
    whose windows reach a band half a kernel deep. Rows beyond the top
    or bottom (every column, so still an autocorrelation along the
    columns), columns beyond the left or right, and the corners counted
    twice: ``A = R - rows - columns + corners``, each family one
    convolution over its bands, exact. The corrections are made on the
    few ``c x c`` blocks they touch; one gather then lays the blocks out.
    """
    (kh, kw), c = kernel_size, x.shape[-1]
    ph, pw = kh // 2, kw // 2
    batch, h, w = x.shape[:3]
    sides = (False, True)
    full = _correlate(x, (kh - 1, kw - 1))[..., 0, :]  # (e, f, c', c)
    # the block offsets of two kernel offsets inside one band
    de = np.arange(ph)[None, :] - np.arange(ph)[:, None] + kh - 1
    df = np.arange(pw)[None, :] - np.arange(pw)[:, None] + kw - 1
    # the c' x c blocks, corrected where a halo touches them: where each
    # kind starts, and the kinds in that order
    starts, tables = {}, []

    def add(kind, blocks):
        starts[kind] = sum(t.shape[0] for t in tables)
        tables.append(blocks.reshape(-1, c, c))

    add('full', full)
    if ph:
        bands = jnp.concatenate([_halo(x, 1, ph, s) for s in sides], axis=-1)
        # (edge, i, i', f, c', c)
        rows = jnp.transpose(
            _correlate(bands, (0, kw - 1), 2).reshape(
                2 * kw - 1, ph, c, 2, ph, c
            ),
            (3, 4, 1, 0, 2, 5),
        )
        add('rows', jnp.take(full, de, axis=0) - rows)
    if pw:
        bands = jnp.concatenate([_halo(x, 2, pw, s) for s in sides], axis=-1)
        # (edge, j, j', e, c', c)
        cols = jnp.transpose(
            _correlate(bands, (kh - 1, 0), 2).reshape(
                2 * kh - 1, pw, c, 2, pw, c
            ),
            (3, 4, 1, 0, 2, 5),
        )
        add('cols', jnp.moveaxis(jnp.take(full, df, axis=1), 0, 2) - cols)
    if ph and pw:
        bands = jnp.concatenate([
            _halo(_halo(x, 1, ph, r), 2, pw, s) for r in sides for s in sides
        ], axis=-1)
        # (row edge, column edge, i, i', j, j', c', c)
        corners = jnp.transpose(
            _correlate(bands, (0, 0), 4).reshape(pw, ph, c, 2, 2, pw, ph, c),
            (3, 4, 6, 1, 5, 0, 2, 7),
        )
        add(
            'both',
            jnp.take(jnp.take(full, de, axis=0), df, axis=2)
            - jnp.take(rows, df, axis=3)[:, None]
            - jnp.transpose(
                jnp.take(cols, de, axis=3), (0, 3, 4, 1, 2, 5, 6)
            )[None]
            + corners,
        )
    # where the block of kernel offsets (u, v), (u', v') lies in them
    index = np.zeros((kh, kw, kh, kw), np.int32)
    for u, v, u2, v2 in np.ndindex(*index.shape):
        e, f = u2 - u + kh - 1, v2 - v + kw - 1
        rb, cb = _halo_band(u, u2, ph), _halo_band(v, v2, pw)
        if rb and cb:
            index[u, v, u2, v2] = starts['both'] + np.ravel_multi_index(
                (rb[0], cb[0]) + rb[1:] + cb[1:], (2, 2, ph, ph, pw, pw))
        elif rb:
            index[u, v, u2, v2] = starts['rows'] + np.ravel_multi_index(
                rb + (f,), (2, ph, ph, 2 * kw - 1))
        elif cb:
            index[u, v, u2, v2] = starts['cols'] + np.ravel_multi_index(
                cb + (e,), (2, pw, pw, 2 * kh - 1))
        else:
            index[u, v, u2, v2] = e * (2 * kw - 1) + f
    # (u, v, u', v', c', c): whole c' x c blocks
    blocks = jnp.take(jnp.concatenate(tables), index, axis=0)
    n = c * kh * kw
    # channel-major (c, u, v) both ways with no shuffle along the lanes:
    # the rows regrouped to (c', u', v'), the matrix transposed, its rows
    # regrouped to (c, u, v)
    mat = jnp.transpose(blocks, (4, 2, 3, 0, 1, 5)).reshape(n, n)
    mat = jnp.transpose(mat.T.reshape(kh * kw, c, n), (1, 0, 2)).reshape(n, n)
    if has_bias:
        # the ones column against a patch entry: the activation's sum
        # over the positions whose window holds it, a box a kernel offset
        sums = jnp.sum(x, axis=0, dtype=mat.dtype)
        box = jax.lax.reduce_window(
            jnp.pad(sums, ((ph, ph), (pw, pw), (0, 0))), 0.0, jax.lax.add,
            (h, w, 1), (1, 1, 1), 'VALID',
        )
        edge = jnp.append(jnp.transpose(box, (2, 0, 1)).reshape(n),
                          batch * h * w / 2.0)
        edge = jnp.pad(edge[:, None], ((0, 0), (n, 0)))
        mat = jnp.pad(mat, ((0, 1), (0, 1))) + edge + edge.T
    return mat / float(batch * h * w * (h * w) ** 2)


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
    activations in the dtype the layer multiplies them in. Where the
    geometry allows (:func:`conv2d_a_is_patchless`) the same matrix is
    assembled from the activation's autocorrelation and no row is
    written (:func:`_autocorrelation_a_factor`).
    """
    half = (kernel_size[0] // 2, kernel_size[1] // 2)
    if conv2d_a_is_patchless(kernel_size, strides, padding) and (
        a.shape[1] >= half[0] and a.shape[2] >= half[1]
    ):
        return _autocorrelation_a_factor(a, kernel_size, has_bias)
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
