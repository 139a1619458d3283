"""Unit tests for factor math: EMA, eigh, inverse, preconditioning, kl-clip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu.ops import factors


def _random_spd(n, seed):
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(n, n)).astype(np.float32)
    return m @ m.T / n + 0.1 * np.eye(n, dtype=np.float32)


def test_ema_update_identity_init():
    new = jnp.full((3, 3), 2.0)
    out = factors.ema_update(None, new, alpha=0.95)
    expected = 0.95 * np.eye(3) + 0.05 * 2.0 * np.ones((3, 3))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_ema_update_running():
    run = jnp.ones((2, 2))
    new = jnp.zeros((2, 2))
    out = factors.ema_update(run, new, alpha=0.5)
    np.testing.assert_allclose(out, 0.5 * np.ones((2, 2)))


def test_eigh_reconstructs_and_clamps():
    f = _random_spd(6, 0)
    dec = factors.compute_eigh(jnp.asarray(f))
    recon = np.asarray(dec.q) @ np.diag(np.asarray(dec.d)) @ np.asarray(dec.q).T
    np.testing.assert_allclose(recon, f, rtol=1e-4, atol=1e-5)
    assert (np.asarray(dec.d) >= 0).all()


def test_inverse_matches_numpy():
    f = _random_spd(5, 1)
    damping = 0.01
    inv = factors.compute_inverse(jnp.asarray(f), damping)
    expected = np.linalg.inv(f + damping * np.eye(5))
    np.testing.assert_allclose(inv, expected, rtol=1e-3, atol=1e-4)


def test_eigen_precondition_equals_explicit_inverse_formula():
    """qg [ (qg^T W qa) / (dg x da + l) ] qa^T == (G x A + l)^-1 applied."""
    a = _random_spd(4, 2)
    g = _random_spd(3, 3)
    grad = np.random.default_rng(4).normal(size=(3, 4)).astype(np.float32)
    damping = 0.05
    adec = factors.compute_eigh(jnp.asarray(a))
    gdec = factors.compute_eigh(jnp.asarray(g))
    got = factors.eigen_preconditioned_grad(jnp.asarray(grad), adec, gdec, damping)
    # explicit Kronecker solve: vec form with kron(A, G) (row-major vec)
    kron = np.kron(a, g) + damping * np.eye(12)
    vec = grad.T.reshape(-1)  # column-major stacking matches kron(A, G)
    expected = np.linalg.solve(kron, vec).reshape(4, 3).T
    np.testing.assert_allclose(got, expected, rtol=1e-3, atol=1e-4)


def test_prediv_matches_on_the_fly_division():
    a = _random_spd(4, 5)
    g = _random_spd(3, 6)
    grad = np.random.default_rng(7).normal(size=(3, 4)).astype(np.float32)
    damping = 0.01
    adec = factors.compute_eigh(jnp.asarray(a))
    gdec = factors.compute_eigh(jnp.asarray(g))
    direct = factors.eigen_preconditioned_grad(
        jnp.asarray(grad), adec, gdec, damping
    )
    dgda = factors.prediv_eigenvalues(adec, gdec, damping)
    v1 = np.asarray(gdec.q).T @ grad @ np.asarray(adec.q)
    via_prediv = np.asarray(gdec.q) @ (v1 * np.asarray(dgda)) @ np.asarray(adec.q).T
    np.testing.assert_allclose(direct, via_prediv, rtol=1e-4, atol=1e-5)


def test_inverse_precondition_formula():
    a_inv = _random_spd(4, 8)
    g_inv = _random_spd(3, 9)
    grad = np.random.default_rng(10).normal(size=(3, 4)).astype(np.float32)
    got = factors.inverse_preconditioned_grad(
        jnp.asarray(grad), jnp.asarray(a_inv), jnp.asarray(g_inv)
    )
    np.testing.assert_allclose(got, g_inv @ grad @ a_inv, rtol=1e-4, atol=1e-4)


def test_kl_clip_scale():
    assert float(factors.kl_clip_scale(jnp.asarray(0.0), 0.001)) == 1.0
    # |vg| tiny -> clipped at 1
    assert float(factors.kl_clip_scale(jnp.asarray(1e-9), 0.001)) == 1.0
    got = float(factors.kl_clip_scale(jnp.asarray(4.0), 0.001))
    np.testing.assert_allclose(got, np.sqrt(0.001 / 4.0), rtol=1e-6)
    got_neg = float(factors.kl_clip_scale(jnp.asarray(-4.0), 0.001))
    np.testing.assert_allclose(got_neg, np.sqrt(0.001 / 4.0), rtol=1e-6)


# one Newton-Schulz iteration (the body every engine's solve runs, XLA's
# products at every width): under one MXU tile, ragged, whole tiles
NS_STEP_DIMS = [64, 200, 256]


def _ns_start(d, seed):
    """A damped SPD factor and the Gershgorin cold start
    ``newton_schulz_inverse_info`` builds from it."""
    m = _random_spd(d, seed)
    x0 = np.eye(d, dtype=np.float32) / np.abs(m).sum(axis=1).max()
    return m, x0


@pytest.mark.parametrize('d', NS_STEP_DIMS)
def test_newton_schulz_step_chain_matches_float64(d):
    m, x0 = _ns_start(d, d)
    m64, x64, eye = m.astype(np.float64), x0.astype(np.float64), np.eye(d)
    x, mx = jnp.asarray(x0), jnp.asarray(m @ x0)
    for _ in range(3):
        x, mx, resid = factors.newton_schulz_step(jnp.asarray(m), x, mx)
        x64 = x64 @ (2.0 * eye - m64 @ x64)
        np.testing.assert_allclose(np.asarray(x), x64, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(mx), m64 @ x64, rtol=1e-4, atol=1e-5
        )
        want = np.linalg.norm(eye - m64 @ x64) / np.sqrt(d)
        assert float(resid) == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize('d', NS_STEP_DIMS)
def test_newton_schulz_step_residual_feeds_stopping_rule(d):
    # the rule runs while the residual strictly shrinks: in the
    # iteration's quadratic phase it must, and what is returned is the
    # residual OF the returned iterate (the carry invariant)
    m, x0 = _ns_start(d, d + 1)
    m = jnp.asarray(m)
    x, mx = jnp.asarray(x0), m @ jnp.asarray(x0)
    resids = []
    for _ in range(3):
        x, mx, resid = factors.newton_schulz_step(m, x, mx)
        own = jnp.linalg.norm(jnp.eye(d) - mx) / jnp.sqrt(float(d))
        assert float(resid) == pytest.approx(float(own), rel=1e-6)
        resids.append(float(resid))
    assert resids[0] > resids[1] > resids[2]


@pytest.mark.parametrize('slots,d', [(2, 128), (3, 200)])
def test_newton_schulz_step_stacked_vmap(slots, d):
    # the stacked engine runs one bucket's slots under vmap: each slot
    # gets its own residual, equal to the slot run alone
    starts = [_ns_start(d, 10 + i) for i in range(slots)]
    m = jnp.stack([jnp.asarray(s[0]) for s in starts])
    x0 = jnp.stack([jnp.asarray(s[1]) for s in starts])
    xs, mxs, rs = jax.vmap(factors.newton_schulz_step)(m, x0, m @ x0)
    assert rs.shape == (slots,)
    for i in range(slots):
        x, mx, r = factors.newton_schulz_step(m[i], x0[i], m[i] @ x0[i])
        np.testing.assert_allclose(
            np.asarray(xs[i]), np.asarray(x), rtol=1e-5, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(mxs[i]), np.asarray(mx), rtol=1e-5, atol=1e-6
        )
        assert float(rs[i]) == pytest.approx(float(r), rel=1e-5)


def test_newton_schulz_inverse_matches_cholesky():
    """The matmul-only solver converges to the direct damped inverse for
    well- and mildly ill-conditioned SPD factors."""
    for n, seed in ((16, 0), (128, 1)):
        f = jnp.asarray(_random_spd(n, seed))
        ns = factors.newton_schulz_inverse(f, 0.01)
        direct = factors.compute_inverse(f, 0.01)
        np.testing.assert_allclose(
            np.asarray(ns), np.asarray(direct), atol=5e-4
        )


def test_newton_schulz_handles_near_singular_factor():
    """Damping floors the spectrum, so a rank-deficient factor still
    inverts (the curvature-factor regime: PSD + damping*I)."""
    f = jnp.zeros((32, 32))  # zero factor: inverse is I/damping
    ns = factors.newton_schulz_inverse(f, 0.1)
    np.testing.assert_allclose(
        np.asarray(ns), np.eye(32) / 0.1, rtol=1e-3
    )


def test_newton_schulz_converges_for_ill_conditioned_factor():
    """Condition number ~1e6 (large-norm factor, small damping): the
    Gershgorin init + residual-monitored loop must still converge."""
    rng = np.random.default_rng(7)
    q, _ = np.linalg.qr(rng.normal(size=(64, 64)))
    evals = np.logspace(0, 4, 64)  # factor norm 1e4, damping 1e-2 -> 1e6
    f = jnp.asarray((q * evals) @ q.T, jnp.float32)
    ns = factors.newton_schulz_inverse(f, 0.01)
    direct = factors.compute_inverse(f, 0.01)
    m = np.asarray(f) + 0.01 * np.eye(64)
    # NS limiting accuracy in fp32 is O(kappa * eps) ~ 0.1 here (Cholesky's
    # backward-stable solve does better; for preconditioning the difference
    # is immaterial — see newton_schulz_inverse_info docstring)
    resid = np.abs(np.asarray(ns) @ m - np.eye(64)).max()
    assert resid < 5e-2, resid
    # and the two inverses agree where the spectrum is well-resolved
    assert np.median(np.abs(np.asarray(ns) - np.asarray(direct))) < 1e-5


def test_newton_schulz_early_exit_on_benign_factor():
    """The residual stopping rule exits well before the iteration cap on a
    well-conditioned factor, and reports a residual at/below tolerance."""
    f = jnp.asarray(_random_spd(64, 3))
    info = factors.newton_schulz_inverse_info(f, 0.01, max_iters=40)
    assert int(info.iterations) < 25, int(info.iterations)
    assert float(info.residual) <= 1e-6, float(info.residual)
    direct = factors.compute_inverse(f, 0.01)
    np.testing.assert_allclose(
        np.asarray(info.inverse), np.asarray(direct), atol=5e-4
    )


def test_newton_schulz_stagnation_stop_at_fp32_floor():
    """Spectrum spread ~1e9 with tiny damping: the fp32 iteration cannot
    reach tol, so the monotonicity rule must stop it at the accuracy floor
    (well under the cap) and report the honest, large residual."""
    rng = np.random.default_rng(11)
    q, _ = np.linalg.qr(rng.normal(size=(96, 96)))
    evals = np.logspace(-5, 4, 96)  # spread 1e9
    f = jnp.asarray((q * evals) @ q.T, jnp.float32)
    info = factors.newton_schulz_inverse_info(f, 1e-5, max_iters=100)
    assert float(info.residual) > 1e-6  # floor, not convergence
    assert int(info.iterations) < 100  # stagnation fired, not the cap


def test_newton_schulz_dead_relu_factor():
    """Activation covariance of a layer with mostly dead units: near-zero
    rows/cols except a small live block. Damping floors the dead subspace;
    NS must match Cholesky on the whole inverse."""
    rng = np.random.default_rng(13)
    # cov of activations where only the first 8 of 48 units ever fire
    acts = np.zeros((256, 48), np.float32)
    acts[:, :8] = rng.normal(size=(256, 8))
    a = acts.T @ acts / 256
    ns = factors.newton_schulz_inverse(jnp.asarray(a), 0.01)
    direct = factors.compute_inverse(jnp.asarray(a), 0.01)
    np.testing.assert_allclose(
        np.asarray(ns), np.asarray(direct), atol=5e-3, rtol=1e-3
    )


def test_damped_inverse_auto_falls_back_on_pathological_factor():
    """solver='auto': when the NS residual exceeds the fallback threshold
    (kappa ~1e9 in fp32), the result must be the Cholesky inverse."""
    rng = np.random.default_rng(17)
    q, _ = np.linalg.qr(rng.normal(size=(64, 64)))
    evals = np.logspace(-5, 4, 64)
    f = jnp.asarray((q * evals) @ q.T, jnp.float32)
    info = factors.newton_schulz_inverse_info(f, 1e-5, max_iters=100)
    assert float(info.residual) > factors.NS_FALLBACK_RESIDUAL  # premise
    auto = factors.damped_inverse(f, 1e-5, solver='auto', iters=100)
    direct = factors.compute_inverse(f, 1e-5)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(direct))


def test_damped_inverse_auto_keeps_ns_when_converged():
    """solver='auto' on a benign factor returns the NS inverse (bitwise:
    the cond must take the cheap branch), which matches Cholesky."""
    f = jnp.asarray(_random_spd(32, 19))
    auto = factors.damped_inverse(f, 0.01, solver='auto')
    ns = factors.newton_schulz_inverse(f, 0.01)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ns))
    direct = factors.compute_inverse(f, 0.01)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(direct), atol=5e-4)


def test_newton_schulz_warm_start_fewer_iters_and_safeguard():
    """Warm-starting from a near inverse converges in strictly fewer
    iterations to the same answer; a zeros/garbage x0 trips the
    safeguard and reproduces the cold start bitwise."""
    f = jnp.asarray(_random_spd(64, 31))
    cold = factors.newton_schulz_inverse_info(f, 0.01, max_iters=40)
    assert float(cold.residual) <= 1e-6

    # near inverse: the solution for a slightly different damping
    near = factors.newton_schulz_inverse(f, 0.0125)
    warm = factors.newton_schulz_inverse_info(f, 0.01, max_iters=40, x0=near)
    assert int(warm.iterations) < int(cold.iterations), (
        int(warm.iterations), int(cold.iterations)
    )
    assert float(warm.residual) <= 1e-6
    np.testing.assert_allclose(
        np.asarray(warm.inverse), np.asarray(cold.inverse),
        rtol=1e-4, atol=1e-6,
    )

    # safeguarded fallbacks: zeros (fresh state) and garbage both
    # reproduce the Gershgorin cold start exactly
    for bad in (jnp.zeros_like(f), jnp.full_like(f, 1e6)):
        fb = factors.newton_schulz_inverse_info(f, 0.01, max_iters=40, x0=bad)
        np.testing.assert_array_equal(
            np.asarray(fb.inverse), np.asarray(cold.inverse)
        )
        assert int(fb.iterations) == int(cold.iterations)


def test_newton_schulz_warm_start_outside_the_basin_restarts_cold():
    """A factor whose top eigenvalue grew between refreshes (the EMA
    forgetting its identity init): the old inverse passes the RMS
    safeguard (0.12 < 0.5) but ``I - M X0`` has spectral radius 1.76, so
    the iteration diverges from it. Found on a v5e (PR 21), where such
    slots were served with residuals of 0.5-1.6. The solve must notice,
    start over cold once, and land where the cold solve lands — alone,
    batched, and in the differentiable variant."""
    rng = np.random.default_rng(0)
    d = 256
    u = rng.normal(size=(d,))
    u /= np.linalg.norm(u)
    cov = 0.5 * np.eye(d) + 400.0 * np.outer(u, u)

    def ema(n):  # identity init, n captures at decay 0.95
        return jnp.asarray(
            0.95 ** n * np.eye(d) + (1 - 0.95 ** n) * cov, jnp.float32
        )

    old = factors.newton_schulz_inverse(ema(1), 0.003)
    f = ema(3)
    r0 = np.eye(d) - (np.asarray(f, np.float64) + 0.003 * np.eye(d)) @ (
        np.asarray(old, np.float64)
    )
    assert np.linalg.norm(r0) / np.sqrt(d) < 0.5  # premise: passes the RMS test
    assert np.abs(np.linalg.eigvals(r0)).max() > 1.0  # premise: diverges

    cold = factors.newton_schulz_inverse_info(f, 0.003)
    assert float(cold.residual) <= 1e-5
    for kwargs in ({}, {'differentiable': True}):
        warm = factors.newton_schulz_inverse_info(f, 0.003, x0=old, **kwargs)
        np.testing.assert_array_equal(
            np.asarray(warm.inverse), np.asarray(cold.inverse)
        )
        # the failed attempt is counted
        assert int(warm.iterations) > int(cold.iterations)
    # batched with a lane whose warm start is exact: that lane is untouched
    infos = jax.vmap(
        lambda ff, w: factors.newton_schulz_inverse_info(ff, 0.003, x0=w)
    )(jnp.stack([f, ema(1)]), jnp.stack([old, old]))
    assert float(infos.residual[0]) <= 1e-5
    assert int(infos.iterations[1]) == 0


def test_batched_auto_inverse_single_branch_per_slot_fallback():
    """batched_damped_inverse_auto_info: well-conditioned slots get the NS
    inverse bitwise (the scalar cond takes the cheap branch when ALL
    slots converge); with one pathological slot in the stack, only that
    slot becomes the Cholesky inverse and the good slot keeps NS."""
    rng = np.random.default_rng(17)
    good = jnp.asarray(_random_spd(64, 19))
    q, _ = np.linalg.qr(rng.normal(size=(64, 64)))
    bad = jnp.asarray((q * np.logspace(-5, 4, 64)) @ q.T, jnp.float32)
    info = factors.newton_schulz_inverse_info(bad, 1e-5, max_iters=100)
    assert float(info.residual) > factors.NS_FALLBACK_RESIDUAL  # premise

    # all-good stack: bitwise the batched NS result
    stack = jnp.stack([good, good])
    out = factors.batched_damped_inverse_auto_info(
        stack, 1e-5, iters=100
    ).inverse
    # (of the batched solve: a scaled step's ``2a I - a^2 MX`` rounds by
    # how the compiler fuses it, which batching may change; a plain
    # step's ``2I - MX`` is exact either way)
    ns_batched = jax.vmap(
        lambda m: factors.newton_schulz_inverse(m, 1e-5, iters=100)
    )(stack)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ns_batched))
    ns_good = np.asarray(
        factors.newton_schulz_inverse(good, 1e-5, iters=100)
    )
    np.testing.assert_allclose(
        np.asarray(out[0]), ns_good, rtol=1e-4, atol=1e-5
    )

    # mixed stack: per-slot selection. The good slot is allclose rather
    # than bitwise: the batched while_loop iterates until every lane
    # stops, so it may take extra (stable) NS trips vs the solo run.
    out = factors.batched_damped_inverse_auto_info(
        jnp.stack([good, bad]), 1e-5, iters=100
    ).inverse
    np.testing.assert_allclose(
        np.asarray(out[0]), ns_good, rtol=1e-4, atol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(out[1]),
        np.asarray(factors.compute_inverse(bad, 1e-5)),
    )


def test_host_eigh_matches_xla_eigh():
    """impl='host' (pure_callback -> LAPACK) reconstructs the factor and
    agrees with the device path on eigenvalues; batched input works
    without vmap (numpy eigh batches natively)."""
    f = jnp.asarray(_random_spd(24, 29))
    host = factors.compute_eigh(f, impl='host')
    xla = factors.compute_eigh(f, impl='xla')
    recon = np.asarray(host.q) @ np.diag(np.asarray(host.d)) @ np.asarray(host.q).T
    np.testing.assert_allclose(recon, np.asarray(f), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.sort(np.asarray(host.d)), np.sort(np.asarray(xla.d)),
        rtol=1e-4, atol=1e-5,
    )
    batch = jnp.stack([jnp.asarray(_random_spd(16, s)) for s in (1, 2, 3)])
    w, v = jax.jit(lambda b: factors.batched_eigh(b, 'host'))(batch)
    for i in range(3):
        recon = np.asarray(v[i]) @ np.diag(np.asarray(w[i])) @ np.asarray(v[i]).T
        np.testing.assert_allclose(
            recon, np.asarray(batch[i]), rtol=1e-4, atol=1e-5
        )


def test_batched_eigh_upcasts_bf16_host_under_vmap():
    """The fp32 upcast guard: a bf16 factor stack through the 'host'
    impl under vmap (the async host-refresh shape) decomposes in fp32 —
    outputs are fp32, finite, and reconstruct the upcast factors."""
    stack = jnp.stack([jnp.asarray(_random_spd(16, s)) for s in (7, 8, 9)])
    bf16 = stack.astype(jnp.bfloat16)
    w, v = jax.jit(
        jax.vmap(lambda m: factors.batched_eigh(m, impl='host'))
    )(bf16)
    assert w.dtype == jnp.float32 and v.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(w))) and bool(jnp.all(jnp.isfinite(v)))
    f32 = np.asarray(bf16.astype(jnp.float32))
    for i in range(3):
        recon = np.asarray(v[i]) @ np.diag(np.asarray(w[i])) @ np.asarray(v[i]).T
        np.testing.assert_allclose(recon, f32[i], rtol=1e-4, atol=1e-5)
    # the xla impl rides the same guard
    w2, _ = factors.batched_eigh(bf16, impl='xla')
    assert w2.dtype == jnp.float32
    # non-real inputs are rejected outright rather than silently cast
    with pytest.raises(TypeError, match='floating'):
        factors.batched_eigh(jnp.eye(4, dtype=jnp.int32), impl='host')


def test_gershgorin_condition_bound_bounds_true_condition():
    f = _random_spd(32, 23)
    damping = 0.01
    m = f + damping * np.eye(32, dtype=np.float32)
    true_cond = np.linalg.cond(m)
    bound = float(factors.gershgorin_condition_bound(jnp.asarray(f), damping))
    assert bound >= true_cond * 0.99, (bound, true_cond)
    # and it is not absurdly loose: within d * kappa
    assert bound <= true_cond * 32, (bound, true_cond)


def test_gershgorin_condition_bound_finite_at_zero_damping():
    """damping == 0 must saturate, not divide by zero: an inf (or 0/0 nan)
    bound would poison every downstream comparison in the health sentinel
    (inf * 0 in jnp.where, threshold compares)."""
    f = _random_spd(8, 5)
    bound = factors.gershgorin_condition_bound(jnp.asarray(f), 0.0)
    assert bool(jnp.isfinite(bound))
    # saturated: huge enough that any sane quarantine_threshold flags it
    assert float(bound) > 1e30
    # batched, with a per-matrix damping vector mixing zero and nonzero
    stack = jnp.stack([jnp.asarray(f)] * 3)
    damp = jnp.asarray([0.0, 1e-3, 1.0], jnp.float32)
    bounds = factors.gershgorin_condition_bound(stack, damp)
    assert bounds.shape == (3,)
    assert bool(jnp.isfinite(bounds).all())
    assert float(bounds[0]) > float(bounds[1]) > float(bounds[2])
    # a NaN factor still fails closed: NaN bound compares False vs any
    # threshold, so factor_ok quarantines it (health.factor_ok contract)
    nan_bound = factors.gershgorin_condition_bound(
        jnp.asarray(f) + jnp.nan, 0.01
    )
    assert not bool(nan_bound <= 1e8)


def test_eig_host_matches_eigh_on_symmetric():
    """The non-symmetric escape hatch (reference kfac/layers/eigen.py:
    295-348 symmetric=False, torch.linalg.eig real-part): on an actually
    symmetric factor it must agree with eigh up to eigenvector sign."""
    rng = np.random.default_rng(7)
    m = rng.normal(size=(12, 6)).astype(np.float32)
    cov = jnp.asarray(m.T @ m / 12)
    d_ref, q_ref = factors.batched_eigh(cov, impl='host')
    d_eig, q_eig = factors.batched_eigh(cov, impl='eig_host')
    np.testing.assert_allclose(np.asarray(d_eig), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-5)
    # eigenvectors match up to per-column sign
    dots = np.abs(np.sum(np.asarray(q_eig) * np.asarray(q_ref), axis=0))
    np.testing.assert_allclose(dots, np.ones(6), atol=1e-4)


def test_eig_host_handles_nonsymmetric_real_parts():
    """A factor that drifted numerically non-symmetric still decomposes
    (real parts, ascending order) instead of silently assuming symmetry."""
    rng = np.random.default_rng(8)
    m = rng.normal(size=(10, 5)).astype(np.float32)
    cov = m.T @ m / 10
    skew = cov + 1e-3 * rng.normal(size=(5, 5)).astype(np.float32)
    d, q = jax.jit(
        lambda c: factors.batched_eigh(c, impl='eig_host')
    )(jnp.asarray(skew))
    d, q = np.asarray(d), np.asarray(q)
    assert np.all(np.diff(d) >= 0)  # ascending, eigh convention
    assert d.dtype == np.float32 and q.dtype == np.float32
    # real-part eigenpairs still nearly diagonalize the nearly-symmetric
    # factor: reconstruction error at the perturbation scale
    recon = q @ np.diag(d) @ np.linalg.inv(q)
    assert np.abs(recon - skew).max() < 1e-2


def test_batched_eigh_rejects_unknown_impl():
    with pytest.raises(ValueError):
        factors.batched_eigh(jnp.eye(3), impl='cuda')


def test_newton_schulz_differentiable_variant():
    """The fixed-trip scan variant matches the while_loop outputs and is
    reverse-differentiable (the while_loop path has no transpose rule)."""
    rng = np.random.default_rng(9)
    m = rng.normal(size=(32, 8)).astype(np.float32)
    cov = jnp.asarray(m.T @ m / 32)
    info_w = factors.newton_schulz_inverse_info(cov, 0.01)
    info_s = factors.newton_schulz_inverse_info(cov, 0.01, differentiable=True)
    np.testing.assert_allclose(
        np.asarray(info_s.inverse), np.asarray(info_w.inverse),
        rtol=1e-6, atol=1e-7,
    )
    assert int(info_s.iterations) == int(info_w.iterations)
    np.testing.assert_allclose(
        float(info_s.residual), float(info_w.residual), rtol=1e-5, atol=1e-8
    )

    # reverse mode works through the scan variant...
    def loss(c):
        return jnp.sum(
            factors.newton_schulz_inverse(c, 0.01, differentiable=True)
        )

    g = jax.grad(loss)(cov)
    assert np.all(np.isfinite(np.asarray(g)))
    # ...and the gradient is correct: d/dc sum(inv(c+dI)) via the identity
    # d(M^-1) = -M^-1 dM M^-1  =>  grad = -(M^-T 1 M^-T)
    inv = np.linalg.inv(np.asarray(cov) + 0.01 * np.eye(8))
    expected = -(inv.T @ np.ones((8, 8)) @ inv.T)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-3, atol=1e-4)

    # the while_loop path indeed cannot transpose (documents the contract)
    with pytest.raises(Exception):
        jax.grad(
            lambda c: jnp.sum(factors.newton_schulz_inverse(c, 0.01))
        )(cov)
