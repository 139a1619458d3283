"""Preconditioner state-machine and training-smoke tests.

Behavioral targets: reference tests/base_preconditioner_test.py (hooks /
state dict / step pipeline) and tests/training_test.py:15-79 (loss strictly
decreases over 20 steps of TinyModel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import enums
from kfac_tpu.ops import factors as factors_lib
from testing import models


def _setup(compute_method=enums.ComputeMethod.EIGEN, **kw):
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=32, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    loss_fn = models.mse_loss(m)
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method=compute_method, **kw
    )
    return m, params, (x, y), reg, loss_fn, kfac


def test_init_state_shapes():
    _, _, _, reg, _, kfac = _setup()
    state = kfac.init()
    assert int(state.step) == 0
    for name, h in reg.layers.items():
        assert state.a[name].shape == h.a_factor_shape
        assert state.g[name].shape == h.g_factor_shape
        np.testing.assert_allclose(state.a[name], np.eye(h.a_factor_shape[0]))
    assert state.a_inv == {}  # eigen method leaves inverse slots empty


def test_factor_ema_identity_init_semantics():
    _, params, batch, reg, loss_fn, kfac = _setup(factor_decay=0.9)
    cap = kfac_tpu.CurvatureCapture(reg)
    _, grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    state2 = kfac.update_factors(state, stats)
    expected = 0.9 * np.eye(7) + 0.1 * np.asarray(stats.a['fc1'])
    np.testing.assert_allclose(state2.a['fc1'], expected, rtol=1e-5, atol=1e-6)


def test_step_preconditions_and_advances():
    _, params, batch, reg, loss_fn, kfac = _setup(kl_clip=None)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    state, pgrads = jax.jit(kfac.step)(state, grads, stats)
    assert int(state.step) == 1
    # preconditioned grads differ from raw grads but are finite
    for name in reg.names():
        raw = grads[name]['kernel']
        new = pgrads[name]['kernel']
        assert new.shape == raw.shape
        assert bool(jnp.isfinite(new).all())
        assert float(jnp.abs(new - raw).max()) > 0


def test_unregistered_params_pass_through():
    m, params, batch, reg_full, loss_fn, _ = _setup()
    reg = kfac_tpu.register_model(m, batch[0], skip_layers=['fc2'])
    kfac = kfac_tpu.KFACPreconditioner(registry=reg)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    _, pgrads = kfac.step(state, grads, stats)
    np.testing.assert_array_equal(pgrads['fc2']['kernel'], grads['fc2']['kernel'])
    assert float(jnp.abs(pgrads['fc1']['kernel'] - grads['fc1']['kernel']).max()) > 0


def test_identity_factors_recover_sgd_direction():
    """With A=G=I and damping d, preconditioned grad = grad / (1 + d)."""
    _, params, batch, reg, loss_fn, kfac = _setup(kl_clip=None, damping=0.0)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, _ = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    # skip factor update entirely: factors stay identity; inverses at step 0
    state = kfac.update_inverses(state)
    pgrads = kfac.precondition(state, grads)
    np.testing.assert_allclose(
        pgrads['fc1']['kernel'], grads['fc1']['kernel'], rtol=1e-4, atol=1e-6
    )


@pytest.mark.parametrize('method', [enums.ComputeMethod.EIGEN, enums.ComputeMethod.INVERSE])
def test_eigen_and_inverse_methods_agree(method):
    """For PSD factors both methods solve the same damped Kronecker system."""
    _, params, batch, reg, loss_fn, _ = _setup()
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    results = {}
    for cm in (enums.ComputeMethod.EIGEN, enums.ComputeMethod.INVERSE):
        kfac = kfac_tpu.KFACPreconditioner(
            registry=reg, compute_method=cm, kl_clip=None, damping=0.01
        )
        state = kfac.init()
        state = kfac.update_factors(state, stats)
        state = kfac.update_inverses(state)
        results[cm] = kfac.precondition(state, grads)
    e = results[enums.ComputeMethod.EIGEN]['fc1']['kernel']
    i = results[enums.ComputeMethod.INVERSE]['fc1']['kernel']
    # eigen solves (G x A + l)^-1 exactly; inverse approximates with
    # (G + lI)^-1 (x) (A + lI)^-1 — close but not equal. Loose tolerance.
    np.testing.assert_allclose(e, i, rtol=0.35, atol=5e-3)


def test_kl_clip_bounds_update_norm():
    _, params, batch, reg, loss_fn, kfac = _setup(kl_clip=1e-8, lr=1.0)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    _, pgrads = kfac.step(state, grads, stats)
    _, pgrads_noclip = kfac_tpu.KFACPreconditioner(registry=reg, kl_clip=None).step(
        kfac.init(), grads, stats
    )
    n_clip = float(jnp.linalg.norm(pgrads['fc1']['kernel']))
    n_noclip = float(jnp.linalg.norm(pgrads_noclip['fc1']['kernel']))
    assert n_clip < n_noclip


def test_update_cadence():
    """Factors only move on factor_update_steps boundaries."""
    _, params, batch, reg, loss_fn, kfac = _setup(
        factor_update_steps=2, inv_update_steps=2, kl_clip=None
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    step_fn = jax.jit(kfac.step)
    state1, _ = step_fn(state, grads, stats)   # step 0: update
    a_after0 = np.asarray(state1.a['fc1'])
    state2, _ = step_fn(state1, grads, stats)  # step 1: no update
    np.testing.assert_array_equal(np.asarray(state2.a['fc1']), a_after0)
    state3, _ = step_fn(state2, grads, stats)  # step 2: update
    assert np.abs(np.asarray(state3.a['fc1']) - a_after0).max() > 0


def test_schedule_hyperparams():
    """Callable-or-constant hyperparams resolved on the traced step
    (reference: kfac/base_preconditioner.py:160-208)."""
    _, params, batch, reg, loss_fn, _ = _setup()
    damping_fn = lambda step: 0.01 * jnp.exp(-0.1 * step.astype(jnp.float32))
    kfac = kfac_tpu.KFACPreconditioner(registry=reg, damping=damping_fn, kl_clip=None)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    state, pg = jax.jit(kfac.step)(state, grads, stats)
    assert bool(jnp.isfinite(pg['fc1']['kernel']).all())


def test_rematerialize_after_restore():
    """Factors survive a save/load roundtrip; decomps are recomputed
    (reference semantics: kfac/base_preconditioner.py:296-308)."""
    _, params, batch, reg, loss_fn, kfac = _setup(kl_clip=None)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = kfac.init()
    state, _ = kfac.step(state, grads, stats)
    # simulate checkpoint: keep only step/a/g
    restored = kfac.init()._replace(step=state.step, a=state.a, g=state.g)
    restored = kfac.rematerialize(restored)
    np.testing.assert_allclose(
        np.asarray(restored.qa['fc1']), np.asarray(state.qa['fc1']),
        rtol=1e-4, atol=1e-5,
    )
    p1 = kfac.precondition(state, grads)
    p2 = kfac.precondition(restored, grads)
    np.testing.assert_allclose(
        p1['fc1']['kernel'], p2['fc1']['kernel'], rtol=1e-4, atol=1e-6
    )


def test_memory_usage_reports_bytes():
    _, _, _, reg, _, kfac = _setup()
    state = kfac.init()
    usage = kfac.memory_usage(state)
    assert usage['total'] > 0
    assert usage['a_factors'] == sum(
        np.prod(h.a_factor_shape) * 4 for h in reg.layers.values()
    )


@pytest.mark.parametrize('method', ['eigen', 'inverse'])
def test_training_loss_decreases(method):
    """20 K-FAC-SGD steps on TinyModel must strictly reduce the loss
    (analogue of reference tests/training_test.py:15-79)."""
    m, params, batch, reg, loss_fn, _ = _setup()
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method=method, damping=0.003, lr=0.05
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss_fn)
    state = kfac.init()

    @jax.jit
    def train_step(params, state, batch):
        (loss, _), grads, stats = run(params, batch)
        state, pgrads = kfac.step(state, grads, stats)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, pgrads)
        return params, state, loss

    losses = []
    for _ in range(20):
        params, state, loss = train_step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_training_conv_net_decreases():
    m = models.TinyConvNet()
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 32, 32, 1))
    labels = jnp.arange(8) % 10
    y = jax.nn.one_hot(labels, 10)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)

    def loss_fn(p, batch):
        xx, yy = batch
        logits = m.apply({'params': p}, xx)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * yy, axis=-1))

    kfac = kfac_tpu.KFACPreconditioner(registry=reg, damping=0.01, lr=0.05)
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss_fn)
    state = kfac.init()

    @jax.jit
    def train_step(params, state, batch):
        (loss, _), grads, stats = run(params, batch)
        state, pgrads = kfac.step(state, grads, stats)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, pgrads)
        return params, state, loss

    losses = []
    for _ in range(15):
        params, state, loss = train_step(params, state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------- platform defaults


def test_default_compute_method_per_platform():
    # TPU gets the matmul-only Newton-Schulz INVERSE path; everything else
    # keeps the reference's EIGEN default (kfac/preconditioner.py:245-256).
    assert kfac_tpu.default_compute_method('tpu') == (
        enums.ComputeMethod.INVERSE,
        'newton_schulz',
    )
    for platform in ('cpu', 'gpu', 'cuda'):
        assert kfac_tpu.default_compute_method(platform) == (
            enums.ComputeMethod.EIGEN,
            'cholesky',
        )


def test_unset_compute_method_resolves_to_platform_default():
    m = models.TinyModel()
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=8, dim=6)
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(registry=reg)
    # conftest pins JAX_PLATFORMS=cpu, so the resolved default is EIGEN.
    assert kfac.compute_method == enums.ComputeMethod.EIGEN
    assert kfac.inverse_solver == 'cholesky'


def test_forced_eigen_on_tpu_warns(monkeypatch):
    m = models.TinyModel()
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=8, dim=6)
    reg = kfac_tpu.register_model(m, x)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    with pytest.warns(kfac_tpu.warnings.TPUPerformanceWarning):
        kfac_tpu.KFACPreconditioner(registry=reg, compute_method='eigen')
    # unset on TPU: silent, resolves to the native path
    import warnings as stdlib_warnings

    with stdlib_warnings.catch_warnings():
        stdlib_warnings.simplefilter('error')
        kfac = kfac_tpu.KFACPreconditioner(registry=reg)
    assert kfac.compute_method == enums.ComputeMethod.INVERSE
    assert kfac.inverse_solver == 'newton_schulz'
