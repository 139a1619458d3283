"""KFAC-Laplace posterior tests (kfac_tpu/laplace/).

Round-trip determinism, the TunedPlan-style schema discipline of
POSTERIOR.json (versioned, unknown/missing keys rejected), and the
export refusals (quarantined health sentinel, spilled factor slots).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import health as health_lib
from kfac_tpu.laplace import LaplaceConfig
from kfac_tpu.models import MLP
from testing import models


@pytest.fixture(scope='module')
def trained():
    """One trained tiny classifier shared by every test in the module:
    the engine/capture compiles are the expensive part, not the asserts."""
    m = MLP(features=(8,), num_classes=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 4)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, health=health_lib.HealthConfig(warn=False)
    )

    def loss_fn(p, b):
        xx, yy = b
        logits = m.apply({'params': p}, xx)
        onehot = jax.nn.one_hot(yy, 4)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    cap = kfac_tpu.CurvatureCapture(reg)
    _, grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))
    state = kfac.init()
    state = kfac.update_factors(state, stats)

    def apply_fn(p, xx):
        return m.apply({'params': p}, xx)

    return m, params, (x, y), kfac, state, apply_fn


def _export(trained, path, **cfg_kw):
    _, params, _, kfac, state, _ = trained
    cfg = LaplaceConfig(**cfg_kw) if cfg_kw else None
    return kfac_tpu.export_posterior(
        kfac, state, params, path, config=cfg, overwrite=True
    )


def test_round_trip_determinism(trained, tmp_path):
    doc = _export(trained, tmp_path)
    post = kfac_tpu.load_posterior(tmp_path)
    assert post.fingerprint == doc['fingerprint']
    key = jax.random.PRNGKey(7)
    s1 = post.sample_params(key)
    s2 = post.sample_params(key)
    jax.tree_util.tree_map(np.testing.assert_array_equal, s1, s2)
    # a different key gives a different draw
    s3 = post.sample_params(jax.random.PRNGKey(8))
    assert float(
        jnp.abs(s1['dense0']['kernel'] - s3['dense0']['kernel']).max()
    ) > 0
    # jit matches eager: sample_params is pure in (key, stored arrays)
    s_jit = jax.jit(post.sample_params)(key)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6), s1, s_jit
    )
    # the doc itself is byte-stable across re-exports (no timestamps)
    doc_bytes = open(tmp_path / 'POSTERIOR.json', 'rb').read()
    _export(trained, tmp_path)
    assert open(tmp_path / 'POSTERIOR.json', 'rb').read() == doc_bytes


def test_predictive_is_a_distribution(trained, tmp_path):
    _, _, (x, y), _, _, apply_fn = trained
    _export(trained, tmp_path)
    post = kfac_tpu.load_posterior(tmp_path)
    probs = post.predictive(apply_fn, x, jax.random.PRNGKey(0), n_samples=4)
    assert probs.shape == (32, 4)
    np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.0, rtol=1e-5)
    post2, nlls = kfac_tpu.fit_prior_precision(
        post, apply_fn, (x, y), jax.random.PRNGKey(1),
        grid=(0.1, 1.0, 10.0), n_samples=4,
    )
    assert post2.config.prior_precision in (0.1, 1.0, 10.0)
    assert nlls[post2.config.prior_precision] == min(nlls.values())


def test_diag_and_last_layer_modes(trained, tmp_path):
    _, params, (x, _), _, _, apply_fn = trained
    _export(trained, tmp_path / 'diag', mode='diag')
    doc = json.load(open(tmp_path / 'diag' / 'POSTERIOR.json'))
    assert all(
        layer['arrays'] == ['da', 'dg'] for layer in doc['layers'].values()
    )
    post = kfac_tpu.load_posterior(tmp_path / 'diag')
    s = post.sample_params(jax.random.PRNGKey(0))
    assert s['head']['kernel'].shape == params['head']['kernel'].shape

    _export(trained, tmp_path / 'll', mode='last_layer')
    post_ll = kfac_tpu.load_posterior(tmp_path / 'll')
    assert sorted(post_ll.layers) == ['head']  # default: last registered
    # closed-form linearized variance: per-sample x per-class, positive
    phi = np.asarray(jax.nn.relu(x @ params['dense0']['kernel']
                                 + params['dense0']['bias']))
    var = post_ll.linearized_variance(phi)
    assert var.shape == (32, 4)
    assert float(np.min(np.asarray(var))) >= 0
    with pytest.raises(ValueError, match='last-layer'):
        kfac_tpu.load_posterior(tmp_path / 'diag').linearized_variance(phi)


def test_schema_version_rejected(trained, tmp_path):
    _export(trained, tmp_path)
    doc_path = tmp_path / 'POSTERIOR.json'
    doc = json.load(open(doc_path))
    doc['schema'] = 99
    doc_path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match='schema 99'):
        kfac_tpu.load_posterior(tmp_path)


def test_unknown_and_missing_keys_rejected(trained, tmp_path):
    _export(trained, tmp_path)
    doc_path = tmp_path / 'POSTERIOR.json'
    doc = json.load(open(doc_path))
    doc_path.write_text(json.dumps({**doc, 'surprise': 1}))
    with pytest.raises(ValueError, match='unknown'):
        kfac_tpu.load_posterior(tmp_path)
    missing = {k: v for k, v in doc.items() if k != 'fingerprint'}
    doc_path.write_text(json.dumps(missing))
    with pytest.raises(ValueError, match='missing'):
        kfac_tpu.load_posterior(tmp_path)
    os.unlink(doc_path)
    with pytest.raises(ValueError, match='no POSTERIOR.json'):
        kfac_tpu.load_posterior(tmp_path)


def test_existing_artifact_needs_overwrite(trained, tmp_path):
    _, params, _, kfac, state, _ = trained
    _export(trained, tmp_path)
    with pytest.raises(ValueError, match='already exists'):
        kfac_tpu.export_posterior(kfac, state, params, tmp_path)


def test_export_refuses_quarantined(trained, tmp_path):
    _, params, _, kfac, state, _ = trained
    name = next(iter(kfac.registry.layers))
    bad = state._replace(
        health=state.health._replace(
            quarantined={
                **state.health.quarantined, name: jnp.ones((), jnp.int32)
            }
        )
    )
    with pytest.raises(ValueError, match='quarantined'):
        kfac_tpu.export_posterior(
            kfac, bad, params, tmp_path / 'q', overwrite=True
        )


def test_laplace_config_validation():
    with pytest.raises(ValueError, match='mode'):
        LaplaceConfig(mode='banana')
    with pytest.raises(ValueError, match='prior_precision'):
        LaplaceConfig(prior_precision=0.0)
    with pytest.raises(ValueError, match='temperature'):
        LaplaceConfig(temperature=-1.0)
    with pytest.raises(ValueError, match='last_layer'):
        LaplaceConfig(last_layer='head')  # only meaningful in last_layer mode
    with pytest.raises(ValueError, match='n_samples'):
        LaplaceConfig(n_samples=0)


def test_frozen_layers_stay_at_map(tmp_path):
    """A mask-frozen layer is absent from the posterior: sampling returns
    its MAP value untouched (merged from params, no noise)."""
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=16, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x, mask={'fc2': False})
    kfac = kfac_tpu.KFACPreconditioner(registry=reg)
    cap = kfac_tpu.CurvatureCapture(reg)
    loss_fn = models.mse_loss(m)
    _, grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))
    state = kfac.update_factors(kfac.init(), stats)
    kfac_tpu.export_posterior(kfac, state, params, tmp_path, overwrite=True)
    post = kfac_tpu.load_posterior(tmp_path)
    assert sorted(post.layers) == ['fc1']
    s = post.sample_params(jax.random.PRNGKey(3))
    np.testing.assert_array_equal(s['fc2']['kernel'], params['fc2']['kernel'])
    assert float(jnp.abs(s['fc1']['kernel'] - params['fc1']['kernel']).max()) > 0
