"""Curvature capture tests: A/G statistics must equal hand-derived values.

The G oracle uses the perturbation identity: adding an explicit zero epsilon
to a layer's output and differentiating the loss w.r.t. it yields dL/dy,
from which the expected G = cov(dL/dy) is computed independently of the
g-tap custom_vjp machinery.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.layers import registry as registry_lib
from kfac_tpu.ops import cov
from testing import models


def _setup_tiny():
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=16, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = registry_lib.register_model(m, x)
    loss_fn = models.mse_loss(m)
    return m, params, (x, y), reg, loss_fn


def test_grads_match_plain_value_and_grad():
    m, params, batch, reg, loss_fn = _setup_tiny()
    cap = capture_lib.CurvatureCapture(reg)
    (loss, _), grads, _ = cap.value_stats_and_grad(loss_fn)(params, batch)
    loss0, grads0 = jax.value_and_grad(loss_fn)(params, batch)
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_a_stats_match_manual():
    m, params, batch, reg, loss_fn = _setup_tiny()
    cap = capture_lib.CurvatureCapture(reg)
    _, _, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    x, _ = batch
    expected_fc1 = cov.linear_a_factor(x, has_bias=True)
    np.testing.assert_allclose(stats.a['fc1'], expected_fc1, rtol=1e-5, atol=1e-6)
    # fc2 input = relu(fc1(x))
    h = nn.relu(x @ params['fc1']['kernel'] + params['fc1']['bias'])
    expected_fc2 = cov.linear_a_factor(h, has_bias=True)
    np.testing.assert_allclose(stats.a['fc2'], expected_fc2, rtol=1e-5, atol=1e-6)


def test_g_stats_match_perturbation_oracle():
    m, params, batch, reg, loss_fn = _setup_tiny()
    cap = capture_lib.CurvatureCapture(reg)
    _, _, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    x, y = batch

    def loss_with_eps(eps1, eps2):
        h = x @ params['fc1']['kernel'] + params['fc1']['bias'] + eps1
        out = nn.relu(h) @ params['fc2']['kernel'] + params['fc2']['bias'] + eps2
        return jnp.mean((out - y) ** 2)

    e1 = jnp.zeros((x.shape[0], 8))
    e2 = jnp.zeros((x.shape[0], 4))
    g1, g2 = jax.grad(loss_with_eps, argnums=(0, 1))(e1, e2)
    np.testing.assert_allclose(
        stats.g['fc1'], cov.linear_g_factor(g1), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_allclose(
        stats.g['fc2'], cov.linear_g_factor(g2), rtol=1e-5, atol=1e-7
    )


def test_capture_under_jit():
    m, params, batch, reg, loss_fn = _setup_tiny()
    cap = capture_lib.CurvatureCapture(reg)
    run = jax.jit(cap.value_stats_and_grad(loss_fn))
    (loss, _), grads, stats = run(params, batch)
    _, _, stats0 = cap.value_stats_and_grad(loss_fn)(params, batch)
    np.testing.assert_allclose(stats.a['fc1'], stats0.a['fc1'], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(stats.g['fc2'], stats0.g['fc2'], rtol=1e-5, atol=1e-7)


def test_shared_module_accumulates():
    m = models.SharedDense()
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 5))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = registry_lib.register_model(m, x)
    assert set(reg.names()) == {'shared'}

    def loss_fn(p, xx):
        return jnp.sum(m.apply({'params': p}, xx) ** 2)

    cap = capture_lib.CurvatureCapture(reg)
    (_, _), _, stats = cap.value_stats_and_grad(loss_fn)(params, x)
    # A-stat should be the average of the two call-site A factors
    h = nn.relu(x @ params['shared']['kernel'] + params['shared']['bias'])
    expected = (
        cov.linear_a_factor(x, True) + cov.linear_a_factor(h, True)
    ) / 2
    np.testing.assert_allclose(stats.a['shared'], expected, rtol=1e-5, atol=1e-6)


def test_conv_capture_shapes():
    m = models.TinyConvNet()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 1))
    y = jax.nn.one_hot(jnp.array([1, 2]), 10)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = registry_lib.register_model(m, x)

    def loss_fn(p, batch):
        xx, yy = batch
        logits = m.apply({'params': p}, xx)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * yy, axis=-1))

    cap = capture_lib.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))
    for name, h in reg.layers.items():
        assert stats.a[name].shape == h.a_factor_shape
        assert stats.g[name].shape == h.g_factor_shape
        assert not bool(jnp.isnan(stats.a[name]).any())
        assert not bool(jnp.isnan(stats.g[name]).any())
    # G stats should be nonzero (loss depends on every layer)
    assert float(jnp.abs(stats.g['conv1']).sum()) > 0


def test_grad_scale_unscaling():
    m, params, batch, reg, loss_fn = _setup_tiny()
    cap = capture_lib.CurvatureCapture(reg)

    def scaled_loss(p, b):
        return 128.0 * loss_fn(p, b)

    _, _, stats_scaled = cap.value_stats_and_grad(scaled_loss)(params, batch)
    _, _, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    unscaled = stats_scaled.scaled(128.0)
    np.testing.assert_allclose(
        unscaled.g['fc2'], stats.g['fc2'], rtol=1e-4, atol=1e-7
    )
    # A stats are unaffected by loss scaling
    np.testing.assert_allclose(stats_scaled.a['fc1'], stats.a['fc1'], rtol=1e-6)


class _SameConvTwice(nn.Module):
    """A stride-1 'SAME' convolution applied twice (its A factor is the
    autocorrelation route's), a strided one, and a head."""

    @nn.compact
    def __call__(self, x):
        same = nn.Conv(3, (3, 3), padding='SAME', name='same')
        x = nn.relu(same(x))
        x = nn.relu(same(x))
        x = nn.Conv(4, (3, 3), strides=(2, 2), name='strided')(x)
        return nn.Dense(2, name='head')(x.mean(axis=(1, 2)))


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def test_patchless_conv_contracts_after_the_gradients():
    """A convolution whose A factor is its input's autocorrelation hands
    its inputs on (one an invocation) and ``contract_late`` makes the
    factor once the gradients exist: the same statistics as contracting
    at the tap, behind an ``optimization_barrier`` that holds the
    gradients; a model without such a layer traces to no barrier."""
    m = _SameConvTwice()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 8, 3))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = registry_lib.register_model(m, x)
    assert [h.patchless for h in reg.layers.values() if hasattr(
        h, 'patchless')] == [True, False]

    def loss_fn(p, xx):
        return jnp.sum(m.apply({'params': p}, xx) ** 2)

    cap = capture_lib.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss_fn)
    (loss, _), grads, stats = jax.jit(run)(params, x)
    loss0, grads0 = jax.value_and_grad(loss_fn)(params, x)
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    # the tapped function hands on the two inputs, uncontracted
    _, (_, a_stats, counts, _) = cap.tapped(loss_fn)(
        params, cap.zero_gstats(), x)
    assert isinstance(a_stats['same'], tuple) and len(a_stats['same']) == 2
    assert int(counts['same']) == 2
    assert a_stats['strided'].shape == reg.layers['strided'].a_factor_shape
    h = nn.relu(nn.Conv(3, (3, 3), padding='SAME').apply(
        {'params': params['same']}, x))
    helper = reg.layers['same']
    expected = (helper.get_a_factor(x) + helper.get_a_factor(h)) / 2
    np.testing.assert_allclose(stats.a['same'], expected, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        stats.a['strided'], a_stats['strided'], rtol=1e-6)

    eqns = _primitives(jax.make_jaxpr(run)(params, x).jaxpr, [])
    barrier, = [e for e in eqns if e.primitive.name == 'optimization_barrier']
    # a scalar one and every gradient leaf go in; the convolutions of the
    # late factor come after it
    assert len(barrier.invars) == 1 + len(jax.tree_util.tree_leaves(grads))
    order = [e.primitive.name for e in eqns]
    late = [i for i, e in enumerate(eqns)
            if e.primitive.name == 'conv_general_dilated'
            and e.params['batch_group_count'] == 4]
    assert len(late) == 2 and min(late) > order.index('optimization_barrier')

    tiny = models.TinyModel()
    tx, ty = models.regression_data(jax.random.PRNGKey(1), n=16, dim=6)
    tparams = tiny.init(jax.random.PRNGKey(0), tx)['params']
    trun = capture_lib.CurvatureCapture(
        registry_lib.register_model(tiny, tx)
    ).value_stats_and_grad(models.mse_loss(tiny))
    assert 'optimization_barrier' not in str(
        jax.make_jaxpr(trun)(tparams, (tx, ty)))
