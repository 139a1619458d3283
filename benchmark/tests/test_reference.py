"""The plain K-FAC step against numpy (float64) on a toy dense layer and a
toy convolution, and the optimizer chain against optax."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.refs import kfac


def test_dense_factors_and_preconditioning_match_numpy():
    rng = np.random.default_rng(0)
    n, d_in, d_out = 12, 5, 3
    x = rng.normal(size=(n, d_in))
    g = rng.normal(size=(n, d_out))
    a_rows = np.concatenate([x, np.ones((n, 1))], axis=1)
    a_np = a_rows.T @ a_rows / n
    g_np = g.T @ g / n
    np.testing.assert_allclose(
        kfac.dense_a(jnp.asarray(x, jnp.float32), True), a_np, rtol=1e-5
    )
    np.testing.assert_allclose(
        kfac.dense_g(jnp.asarray(g, jnp.float32)), g_np, rtol=1e-5
    )

    decay, damping, lr, kl_clip = 0.95, 0.003, 0.1, 0.001
    inv = lambda f: np.linalg.inv(
        decay * np.eye(len(f)) + (1 - decay) * f + damping * np.eye(len(f))
    )
    grads = {'dense': {
        'kernel': rng.normal(size=(d_in, d_out)), 'bias': rng.normal(size=d_out),
    }, 'other': rng.normal(size=4)}
    gmat = np.concatenate(
        [grads['dense']['kernel'].T, grads['dense']['bias'][:, None]], axis=1
    )
    pmat = inv(g_np) @ gmat @ inv(a_np)
    scale = min(1.0, np.sqrt(kl_clip / abs((pmat * gmat).sum() * lr ** 2)))
    assert scale < 1.0  # the clip bites, so it is tested
    want = pmat * scale

    f32 = lambda t: jax.tree_util.tree_map(lambda v: jnp.asarray(v, jnp.float32), t)
    a_inv = kfac.first_inverses({'dense': f32(a_np)}, decay, damping)
    g_inv = kfac.first_inverses({'dense': f32(g_np)}, decay, damping)
    out = kfac.precondition(
        f32(grads), a_inv, g_inv, lr, kl_clip, ('dense',)
    )
    np.testing.assert_allclose(out['dense']['kernel'], want[:, :-1].T, rtol=2e-4)
    np.testing.assert_allclose(out['dense']['bias'], want[:, -1], rtol=2e-4)
    np.testing.assert_array_equal(out['other'], f32(grads)['other'])


@pytest.mark.parametrize('d,spread', [(7, 1.0), (300, 30.0)])
def test_spd_inverse_is_the_float64_inverse(d, spread):
    rng = np.random.default_rng(d)
    rows = rng.normal(size=(2 * d, d)) * rng.uniform(0.1, spread, d)
    m = 0.953 * np.eye(d) + 0.05 * rows.T @ rows / len(rows)
    want = np.linalg.inv(m)
    got = kfac.spd_inverse(m.astype(np.float32))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, got.T)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6
    with pytest.raises(np.linalg.LinAlgError):
        kfac.spd_inverse(-np.eye(3, dtype=np.float32))


def test_conv_a_factor_matches_explicit_patches():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2, 5, 5, 3))
    # 3x3, stride 1, SAME: explicit im2col, channel-major (c, kh, kw)
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    rows = []
    for n in range(2):
        for i in range(5):
            for j in range(5):
                patch = xp[n, i:i + 3, j:j + 3, :]          # (kh, kw, c)
                rows.append(np.transpose(patch, (2, 0, 1)).ravel())
    rows = np.asarray(rows) / 25.0
    want = rows.T @ rows / len(rows)
    got = kfac.conv_a(jnp.asarray(x, jnp.float32), (3, 3), (1, 1), 'SAME')
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
    # and the kernel matricises in the same order
    k = rng.normal(size=(3, 3, 3, 4))
    mat = kfac.conv_to_matrix({'kernel': jnp.asarray(k)})
    assert mat.shape == (4, 27)
    assert float(mat[2, 1 * 9 + 2 * 3 + 0]) == pytest.approx(k[2, 0, 1, 2])
    back = kfac.matrix_to_conv(mat, {'kernel': jnp.asarray(k)})
    np.testing.assert_array_equal(back['kernel'], jnp.asarray(k))


def test_g_tap_returns_the_g_factor_as_a_gradient():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)

    def loss(w, slot):
        y = kfac.g_tap(x @ w, slot, kfac.dense_g)
        return jnp.mean(jnp.sum(jnp.tanh(y) ** 2, axis=-1))

    g_w, g_factor = jax.grad(loss, argnums=(0, 1))(w, jnp.zeros((3, 3)))
    dy = jax.grad(lambda y: jnp.mean(jnp.sum(jnp.tanh(y) ** 2, axis=-1)))(x @ w)
    np.testing.assert_allclose(g_factor, dy.T @ dy / 6, rtol=1e-5)
    np.testing.assert_allclose(g_w, x.T @ dy, rtol=1e-5)


@pytest.mark.parametrize('weight_decay,clip', [(5e-4, None), (0.0, 1.0)])
def test_sgd_step_is_optax_chain(weight_decay, clip):
    rng = np.random.default_rng(3)
    params = {'a': jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
              'b': jnp.asarray(rng.normal(size=3), jnp.float32)}
    chain = []
    if clip is not None:
        chain.append(optax.clip_by_global_norm(clip))
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay))
    opt = optax.chain(*chain, optax.sgd(0.05, momentum=0.9))
    state = opt.init(params)
    p_ref, p_got = params, params
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    for step in range(3):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32) * 3,
            params,
        )
        updates, state = opt.update(grads, state, p_ref)
        p_ref = optax.apply_updates(p_ref, updates)
        p_got, trace = kfac.sgd_step(
            p_got, trace, grads, 0.05, 0.9, weight_decay, clip
        )
    for k in params:
        np.testing.assert_allclose(p_got[k], p_ref[k], rtol=1e-5, atol=1e-6)
