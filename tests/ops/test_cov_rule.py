"""The covariance product's rule (``ops.cov.get_cov``'s docstring), held.

One XLA ``dot_general`` over the rows with float32 accumulation, the same
on one device, under GSPMD and inside a ``shard_map``; operands multiplied
in the dtype the layer itself multiplies (16-bit products are exact in the
accumulator, float32 operands go at ``Precision.HIGHEST``); scales on the
``d x d`` result. References here are float64 products
in numpy over the very values the operands hold.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import kfac_tpu
from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.ops import cov

# float32 accumulation over a few thousand exact products, relative to
# the result's Frobenius norm; a rounded operand or a bfloat16 result is
# off by 2^-9, a thousand times this
ACC_TOL = 2e-6


def _f64(x) -> np.ndarray:
    return np.asarray(x.astype(jnp.float32), np.float64)


def _rel(got, ref) -> float:
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _normal(seed, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


def _products(fn, *args):
    """Every ``dot_general`` and ``conv_general_dilated`` equation in
    ``fn``'s jaxpr, nested ones too."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ('dot_general', 'conv_general_dilated'):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _dots(fn, *args):
    """Every ``dot_general`` equation among them."""
    return [
        e for e in _products(fn, *args) if e.primitive.name == 'dot_general'
    ]


def _row_contractions(fn, *args):
    """The covariance products among them: both operands contract every
    axis but their last, and nothing is batched."""
    def over_rows(eqn):
        (lhs, rhs), batch = eqn.params['dimension_numbers']
        rows = tuple(range(eqn.invars[0].aval.ndim - 1))
        return tuple(lhs) == tuple(rhs) == rows and batch == ((), ())

    return [e for e in _dots(fn, *args) if over_rows(e)]


def _is_highest(eqn) -> bool:
    precision = eqn.params['precision']
    if precision is None:
        return False
    if isinstance(precision, tuple):
        return all(p == jax.lax.Precision.HIGHEST for p in precision)
    return precision == jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- the product


@pytest.mark.parametrize(
    'n,d', [(4096, 96), (192, 640)], ids=['tall-thin', 'wide']
)
def test_bf16_rows_equal_the_float64_product_of_the_same_values(n, d):
    a = _normal(0, (n, d), jnp.bfloat16)
    got = cov.get_cov(a)
    assert got.dtype == jnp.float32
    a64 = _f64(a)
    assert _rel(got, a64.T @ a64 / n) < ACC_TOL


@pytest.mark.parametrize('pair', [False, True], ids=['self', 'cross'])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_scale_after_equals_scale_before(dtype, pair):
    """``cov(rows / s) / N == rows^T rows / (N s^2)``: on float32 data to
    rounding; on bfloat16 data the scale on the result is the exact one
    (dividing the rows by 49 would round them)."""
    n, d, s = 784, 72, 49.0
    a = _normal(1, (n, d), dtype)
    b = _normal(2, (n, d), dtype) if pair else None
    got = cov.get_cov(a, b, scale=n * s * s)
    a64 = _f64(a)
    b64 = a64 if b is None else _f64(b)
    assert _rel(got, (a64 / s).T @ (b64 / s) / n) < ACC_TOL
    if dtype == jnp.float32:
        before = cov.get_cov(a / s, None if b is None else b / s)
        np.testing.assert_allclose(got, before, rtol=2e-5, atol=1e-9)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize('n,d', [(4096, 96), (192, 640)])
def test_self_covariance_is_exactly_symmetric(n, d, dtype):
    c = np.asarray(cov.get_cov(_normal(3, (n, d), dtype)))
    assert np.array_equal(c, c.T)


# --------------------------------------------------------- the factor forms


def _im2col(x: np.ndarray, k: int, stride: int) -> np.ndarray:
    """SAME-padded ``k x k`` patches of NHWC ``x``, features channel-major
    (c, kh, kw): an independent loop, not ``conv_general_dilated_patches``."""
    b, h, w, c = x.shape
    oh, ow = -(-h // stride), -(-w // stride)
    ph = max((oh - 1) * stride + k - h, 0)
    pw = max((ow - 1) * stride + k - w, 0)
    xp = np.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                    (pw // 2, pw - pw // 2), (0, 0)))
    out = np.zeros((b, oh, ow, c, k, k))
    for i in range(oh):
        for j in range(ow):
            win = xp[:, i * stride:i * stride + k, j * stride:j * stride + k]
            out[:, i, j] = np.transpose(win, (0, 3, 1, 2))
    return out.reshape(b, oh, ow, c * k * k)


def _with_ones(rows: np.ndarray, ones: np.ndarray) -> np.ndarray:
    return np.concatenate([rows, ones[:, None]], axis=1)


def _routed_tap(seed, dtype):
    x = _normal(seed, (4, 32, 24), dtype)
    live = jax.random.bernoulli(jax.random.PRNGKey(seed + 1), 0.4, (4, 32, 1))
    return x * live.astype(dtype)


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('has_bias', [False, True], ids=['nobias', 'bias'])
@pytest.mark.parametrize('kind', ['linear', 'routed', 'conv'])
def test_a_factor_is_the_float64_factor_of_the_taps(kind, has_bias, dtype):
    if kind == 'conv':
        a = _normal(4, (3, 9, 9, 5), dtype)
        got = cov.conv2d_a_factor(a, (3, 3), (2, 2), 'SAME', has_bias)
        patches = _im2col(_f64(a), 3, 2)
        s = patches.shape[1] * patches.shape[2]
        rows = patches.reshape(-1, patches.shape[-1])
        if has_bias:
            rows = _with_ones(rows, np.ones(len(rows)))
        rows, count = rows / s, len(rows)
    else:
        a = _routed_tap(5, dtype) if kind == 'routed' else _normal(
            5, (4, 32, 24), dtype)
        fn = cov.routed_linear_a_factor if kind == 'routed' else (
            cov.linear_a_factor)
        got = fn(a, has_bias)
        rows = _f64(a).reshape(-1, a.shape[-1])
        live = (np.abs(rows).max(axis=1) > 0) if kind == 'routed' else (
            np.ones(len(rows), bool))
        count = live.sum()
        if has_bias:
            rows = _with_ones(rows, live.astype(np.float64))
    assert got.dtype == jnp.float32
    assert _rel(got, rows.T @ rows / count) < ACC_TOL
    assert np.array_equal(np.asarray(got), np.asarray(got).T)


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('kind', ['linear', 'routed', 'conv'])
def test_g_factor_is_the_float64_factor_of_the_cotangents(kind, dtype):
    if kind == 'conv':
        g = _normal(6, (3, 5, 5, 12), dtype)
        got = cov.conv2d_g_factor(g)
        rows = _f64(g).reshape(-1, 12) / 25.0
        count = len(rows)
    else:
        g = _routed_tap(7, dtype) if kind == 'routed' else _normal(
            7, (4, 32, 24), dtype)
        fn = cov.routed_linear_g_factor if kind == 'routed' else (
            cov.linear_g_factor)
        got = fn(g)
        rows = _f64(g).reshape(-1, g.shape[-1])
        count = (np.abs(rows).max(axis=1) > 0).sum() if kind == 'routed' else (
            len(rows))
    assert _rel(got, rows.T @ rows / count) < ACC_TOL


def test_conv_scale_beyond_int32():
    """``N s^2`` of a 224 px stem is 4e12: the scale is a float, not an
    int32 constant of the traced program."""
    x = _normal(10, (1, 64, 64, 2), jnp.bfloat16)
    s = 64 * 64  # N s^2 = 2^36
    x64 = _f64(x).reshape(-1, 2) / s
    got_a = jax.jit(
        lambda x: cov.conv2d_a_factor(x, (1, 1), (1, 1), 'VALID', False))(x)
    got_g = jax.jit(cov.conv2d_g_factor)(x)
    for got in (got_a, got_g):
        # two columns: the CPU's product sums 4,096 terms in a row
        assert _rel(got, x64.T @ x64 / s) < 10 * ACC_TOL


# ------------------------------ the convolution's A side without patch rows


def _im2col_factor(x, k, stride, has_bias):
    """The float64 factor of the im2col rows, ``rows^T rows / (N s^2)``."""
    patches = _im2col(_f64(x), k, stride)
    s = patches.shape[1] * patches.shape[2]
    rows = patches.reshape(-1, patches.shape[-1])
    if has_bias:
        rows = _with_ones(rows, np.ones(len(rows)))
    return rows.T @ rows / (len(rows) * s * s)


def _parent_conv_a(x, kernel_size, strides, padding, has_bias):
    """``conv2d_a_factor`` as it stood before the patchless route: patch
    rows, then ``get_cov``."""
    patches = cov.extract_patches_nhwc(x, kernel_size, strides, padding)
    s = patches.shape[1] * patches.shape[2]
    rows = patches.reshape(-1, patches.shape[-1])
    if has_bias:
        rows = cov.append_bias_ones(rows)
    return cov.get_cov(rows, scale=float(rows.shape[0] * s**2))


@pytest.mark.parametrize('explicit', [False, True], ids=['SAME', 'pairs'])
@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('has_bias', [False, True], ids=['nobias', 'bias'])
@pytest.mark.parametrize(
    'k,h,w', [(3, 6, 4), (3, 3, 3), (3, 1, 5), (5, 7, 9), (5, 5, 5),
              (5, 2, 3)],
    ids=lambda v: str(v),
)
def test_patchless_a_factor_is_the_float64_im2col_factor(
    k, h, w, has_bias, dtype, explicit
):
    """The autocorrelation, less the halo's rows and columns, plus its
    corners, is the factor of the patch rows: maps wider than tall, as
    small as the kernel and as small as the kernel's reach, both paddings
    that keep the grid."""
    x = _normal(20 + k, (3, h, w, 4), dtype)
    padding = [(k // 2, k // 2)] * 2 if explicit else 'SAME'
    assert cov.conv2d_a_is_patchless((k, k), (1, 1), padding)
    fn = jax.jit(
        lambda x: cov.conv2d_a_factor(x, (k, k), (1, 1), padding, has_bias))
    assert 'conv_general_dilated_patches' not in str(jax.make_jaxpr(fn)(x))
    assert all(
        e.params['dimension_numbers'].lhs_spec[0] == 3  # batch contracted
        for e in _products(fn, x)
    )
    got = fn(x)
    assert got.dtype == jnp.float32
    assert got.shape == (4 * k * k + has_bias,) * 2
    assert _rel(got, _im2col_factor(x, k, 1, has_bias)) < ACC_TOL
    assert np.array_equal(np.asarray(got), np.asarray(got).T)


@pytest.mark.parametrize(
    'kernel,strides,padding,patchless',
    [
        ((3, 3), (1, 1), 'SAME', True),
        ((3, 3), (1, 1), 'same', True),
        ((3, 3), (1, 1), [(1, 1), (1, 1)], True),
        ((5, 5), (1, 1), ((2, 2), (2, 2)), True),
        ((7, 7), (1, 1), 'SAME', True),
        ((1, 3), (1, 1), 'SAME', True),
        ((3, 3), (2, 2), 'SAME', False),
        ((3, 3), (1, 2), 'SAME', False),
        ((7, 7), (2, 2), [(3, 3), (3, 3)], False),
        ((1, 1), (1, 1), 'SAME', False),
        ((1, 1), (1, 1), 'VALID', False),
        ((2, 2), (1, 1), 'SAME', False),
        ((4, 3), (1, 1), 'SAME', False),
        ((3, 3), (1, 1), 'VALID', False),
        ((3, 3), (1, 1), [(0, 0), (0, 0)], False),
        ((3, 3), (1, 1), [(1, 1), (0, 2)], False),
        ((3, 3), (1, 1), [(2, 2), (2, 2)], False),
    ],
)
def test_route_predicate_and_the_fallback_to_the_bit(
    kernel, strides, padding, patchless
):
    """Stride, kernel and padding decide, nothing else; and a geometry
    that keeps im2col gives the parent's matrix bit for bit."""
    assert cov.conv2d_a_is_patchless(kernel, strides, padding) is patchless
    x = _normal(31, (2, 9, 8, 3), jnp.bfloat16)
    for has_bias in (False, True):
        got = cov.conv2d_a_factor(x, kernel, strides, padding, has_bias)
        parent = _parent_conv_a(x, kernel, strides, padding, has_bias)
        if patchless:
            assert _rel(got, _f64(parent)) < 2 * ACC_TOL
        else:
            assert np.array_equal(np.asarray(got), np.asarray(parent))


def test_a_map_inside_the_kernels_reach_keeps_im2col():
    """A 5 x 5 kernel reaches 2 beyond an edge; a map 1 tall has no band
    2 deep to read the halo from, and takes the rows."""
    x = _normal(32, (2, 1, 6, 3), jnp.bfloat16)
    got = cov.conv2d_a_factor(x, (5, 5), (1, 1), 'SAME', True)
    parent = _parent_conv_a(x, (5, 5), (1, 1), 'SAME', True)
    assert np.array_equal(np.asarray(got), np.asarray(parent))
    assert _rel(got, _im2col_factor(x, 5, 1, True)) < ACC_TOL


@pytest.mark.parametrize('use_bias', [False, True], ids=['nobias', 'bias'])
@pytest.mark.parametrize('k', [3, 5])
def test_patchless_factor_is_in_the_order_grads_to_matrix_packs(k, use_bias):
    """Channel-major ``(c, kh, kw)`` with the bias column last: with ``W``
    a real kernel packed by ``Conv2dHelper.grads_to_matrix``, ``sum |y|^2``
    of the layer's own output ``y = P W^T`` is ``N s^2 tr(W A W^T)``; any
    other order of A's features breaks it."""
    conv = nn.Conv(5, (k, k), padding='SAME', use_bias=use_bias)
    x = _normal(40 + k, (2, 6, 7, 3), jnp.float32)
    params = conv.init(jax.random.PRNGKey(1), x)['params']
    if use_bias:
        params = {**params, 'bias': _normal(41, (5,), jnp.float32)}
    registry = kfac_tpu.register_model(conv, x)
    helper, = registry.layers.values()
    assert helper.patchless
    a = np.asarray(helper.get_a_factor(x), np.float64)
    assert np.array_equal(a, a.T)
    w = np.asarray(helper.grads_to_matrix(params), np.float64)
    y = np.asarray(conv.apply({'params': params}, x), np.float64)
    n, s = y[..., 0].size, 6 * 7
    assert np.isclose(
        np.sum(y * y), n * s * s * np.trace(w @ a @ w.T), rtol=1e-5)
    # and not by symmetry of the data: a permuted order does break it
    perm = np.arange(a.shape[0])
    perm[:k * k] = perm[:k * k][::-1]
    assert not np.isclose(
        np.sum(y * y), n * s * s * np.trace(w @ a[perm][:, perm] @ w.T),
        rtol=1e-3)


def test_routed_factor_of_an_empty_buffer_is_zero():
    a = jnp.zeros((2, 8, 6), jnp.bfloat16)
    assert not np.asarray(cov.routed_linear_a_factor(a, True)).any()
    assert not np.asarray(cov.routed_linear_g_factor(a)).any()


# ------------------------------------------- precision follows the operands


FACTORS = {
    'linear_a': lambda x: cov.linear_a_factor(x, True),
    'linear_g': cov.linear_g_factor,
    'routed_a': lambda x: cov.routed_linear_a_factor(x, True),
    'conv_a': lambda x: cov.conv2d_a_factor(
        x.reshape(2, 8, 8, -1), (3, 3), (1, 1), 'SAME', False),
    'conv_g': lambda x: cov.conv2d_g_factor(x.reshape(2, 8, 8, -1)),
}


@pytest.mark.parametrize('kind', sorted(FACTORS))
def test_float32_factor_is_traced_at_highest_and_bf16_is_not(kind):
    """Whatever products a factor is made of (one row-contracting
    ``dot_general``; for the stride-1 3 x 3 convolution's A side the four
    correlations of the patchless route, and no ``dot_general``): operands
    in the dtype they arrive in, float32 accumulation, ``HIGHEST`` exactly
    for float32."""
    for dtype, highest in ((jnp.float32, True), (jnp.bfloat16, False)):
        ones = jnp.ones((128, 16), dtype)
        products = _products(FACTORS[kind], ones)
        names = sorted(e.primitive.name for e in products)
        if kind == 'conv_a':
            assert names == ['conv_general_dilated'] * 4
        else:
            assert names == ['dot_general']
            assert len(_row_contractions(FACTORS[kind], ones)) == 1
        for eqn in products:
            assert {v.aval.dtype for v in eqn.invars} == {jnp.dtype(dtype)}
            assert eqn.params['preferred_element_type'] == jnp.float32
            assert _is_highest(eqn) is highest


@pytest.mark.parametrize(
    'dtype,highest', [(jnp.float32, True), (jnp.bfloat16, False)]
)
def test_patch_rows_hold_the_activations_exactly(dtype, highest):
    """The identity-kernel convolution behind im2col goes at HIGHEST for
    float32 activations (a one-pass MXU would round them) and copies
    bfloat16 ones as they are, into bfloat16 rows."""
    x = _normal(11, (2, 6, 6, 3), dtype)
    jaxpr = jax.make_jaxpr(
        lambda x: cov.extract_patches_nhwc(x, (3, 3), (1, 1), 'SAME'))(x)
    conv, = [
        e for e in jaxpr.jaxpr.eqns
        if e.primitive.name == 'conv_general_dilated'
    ]
    # the activations' side; the 0/1 kernel is exact at any precision
    lhs = conv.params['precision'][0] if highest else None
    assert (lhs == jax.lax.Precision.HIGHEST) is highest
    patches = cov.extract_patches_nhwc(x, (3, 3), (1, 1), 'SAME')
    assert patches.dtype == dtype
    assert np.array_equal(_f64(patches), _im2col(_f64(x), 3, 1))


class _Mixed(nn.Module):
    """A float32 norm feeding a bfloat16 layer, then a float32 layer: the
    shape of every configuration in the benchmark."""

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=jnp.float32, name='ln')(x)
        x = nn.Dense(24, dtype=jnp.bfloat16, name='half')(x)
        return nn.Dense(8, name='full')(x.astype(jnp.float32))


def test_capture_multiplies_what_each_layer_multiplies():
    """Through ``CurvatureCapture``: the bfloat16 layer's tap arrives in
    float32 and is rounded as the layer rounds it; its cotangent is
    bfloat16 already; the float32 layer's two products go at HIGHEST."""
    model = _Mixed()
    x = _normal(8, (16, 12), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)['params']
    registry = kfac_tpu.register_model(model, x)
    run = kfac_tpu.CurvatureCapture(registry).value_stats_and_grad(
        lambda p, x: jnp.sum(model.apply({'params': p}, x) ** 2)
    )
    by_dtype = {}
    for eqn in _row_contractions(run, params, x):
        dtypes = {v.aval.dtype for v in eqn.invars}
        assert len(dtypes) == 1
        by_dtype.setdefault(dtypes.pop().name, []).append(_is_highest(eqn))
    # one A and one G product a layer (the layers' own weight gradients
    # contract rows too: they are the layers' products, not capture's,
    # and follow the same dtypes)
    assert sorted(by_dtype) == ['bfloat16', 'float32']
    assert not any(by_dtype['bfloat16']) and len(by_dtype['bfloat16']) >= 2
    assert by_dtype['float32'].count(True) >= 2

    _, _, stats = run(params, x)
    normed = nn.LayerNorm(dtype=jnp.float32).apply(
        {'params': params['ln']}, x)
    rows = _with_ones(_f64(normed.astype(jnp.bfloat16)), np.ones(16))
    assert _rel(stats.a['half'], rows.T @ rows / 16) < ACC_TOL
    assert stats.a['half'].dtype == stats.g['half'].dtype == jnp.float32


def test_layer_input_follows_the_module_dtype():
    f32 = jnp.ones((4, 8), jnp.float32)
    bf16 = f32.astype(jnp.bfloat16)
    half = nn.Dense(8, dtype=jnp.bfloat16)
    unset = nn.Dense(8)  # flax promotes input and float32 parameters
    assert capture_lib.layer_input(half, f32).dtype == jnp.bfloat16
    assert capture_lib.layer_input(unset, bf16).dtype == jnp.float32
    assert capture_lib.layer_input(
        nn.Dense(8, param_dtype=jnp.bfloat16), bf16
    ).dtype == jnp.bfloat16


# ------------------------------------ one product, in every trace context


def _mesh():
    return Mesh(np.array(jax.devices()).reshape(4, 2), ('a', 'b'))


def _local_rows_summed(axis_names):
    """``get_cov`` on device-local rows, summed over the row axis."""
    def run(x):
        def body(rows):
            return jax.lax.psum(cov.get_cov(rows, scale=x.shape[0]), 'a')

        return jax.shard_map(
            body, mesh=_mesh(), in_specs=P('a', None),
            out_specs=P(None, None), check_vma=False, **axis_names,
        )(x)

    return run


def _gspmd(x):
    return cov.get_cov(jax.lax.with_sharding_constraint(
        x, NamedSharding(_mesh(), P(('a', 'b'), None))))


CONTEXTS = {
    'one_device': cov.get_cov,
    'gspmd': _gspmd,
    'shard_map': _local_rows_summed({}),
    'partial_manual': _local_rows_summed({'axis_names': {'a'}}),
}


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('context', sorted(CONTEXTS))
def test_no_kernel_in_any_context_and_all_agree(monkeypatch, context, dtype):
    """With the backend answering ``tpu`` (the gates of the kernels that
    remain open there) the covariance traces to one ``dot_general`` and no
    ``pallas_call``, on one device, under GSPMD on the 8-device mesh and
    on local rows inside a ``shard_map``, fully or partly manual; and the
    contexts agree with one another and with float64."""
    a = _normal(9, (512, 256), dtype)
    fn = CONTEXTS[context]
    with monkeypatch.context() as m:
        m.setattr(jax, 'default_backend', lambda: 'tpu')
        if context == 'one_device':
            one = jax.devices()[:1]
            m.setattr(jax, 'devices', lambda *a: one)
        text = str(jax.make_jaxpr(fn)(a))
        products = _row_contractions(fn, a)
    assert 'pallas_call' not in text and 'custom_call' not in text
    assert len(products) == 1
    assert _is_highest(products[0]) is (dtype == jnp.float32)

    a64 = _f64(a)
    got = jax.jit(fn)(a)
    assert _rel(got, a64.T @ a64 / 512) < ACC_TOL
    np.testing.assert_allclose(
        got, jax.jit(cov.get_cov)(a), rtol=1e-5, atol=1e-6)


def _conv_a(x):
    return cov.conv2d_a_factor(x, (3, 3), (1, 1), 'SAME', True)


def _conv_a_local_batches(axis_names):
    def run(x):
        return jax.shard_map(
            lambda rows: jax.lax.pmean(_conv_a(rows), 'a'), mesh=_mesh(),
            in_specs=P('a'), out_specs=P(), check_vma=False, **axis_names,
        )(x)

    return run


CONV_CONTEXTS = {
    'gspmd': lambda x: _conv_a(jax.lax.with_sharding_constraint(
        x, NamedSharding(_mesh(), P(('a', 'b'))))),
    'shard_map': _conv_a_local_batches({}),
    'partial_manual': _conv_a_local_batches({'axis_names': {'a'}}),
}


@pytest.mark.parametrize('context', sorted(CONV_CONTEXTS))
def test_patchless_factor_over_a_sharded_batch(context):
    """The batch is what the route's convolutions contract: sharded under
    GSPMD the partitioner sums the devices' partial correlations, and in a
    ``shard_map`` (fully or partly manual) the mean of the local factors
    is the factor, as with the row-contracting product."""
    x = _normal(50, (8, 6, 5, 4), jnp.bfloat16)
    got = jax.jit(CONV_CONTEXTS[context])(x)
    assert _rel(got, _im2col_factor(x, 3, 1, True)) < ACC_TOL
    np.testing.assert_allclose(got, _conv_a(x), rtol=1e-5, atol=1e-7)
