"""The scaled phase of a cold Newton-Schulz solve (ops/factors.py):
``X <- a X (2I - a M X)``, ``a = 2/(1 + l)``, from the lower bound ``l``
on the eigenvalues of ``M X`` that a cold start knows. Float32 on the CPU:
counts and closeness, never a time."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu.ops import factors


def _spd(d, evals, seed):
    """A float32 SPD matrix with the given eigenvalues in a random basis."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    return jnp.asarray((q * evals) @ q.T, jnp.float32)


def _kappa(f, damping, floor):
    """The condition number a cold solve works against: the Gershgorin
    top over the bound it was given under the bottom."""
    m = np.asarray(f, np.float64) + damping * np.eye(f.shape[-1])
    return np.abs(m).sum(axis=1).max() / (floor + damping)


@pytest.fixture
def unscaled(monkeypatch):
    """Today's solve: with the switch point at 0 no bound is under it, so
    every step is the plain one."""
    def solve(*args, **kwargs):
        with monkeypatch.context() as mp:
            mp.setattr(factors, 'NS_SCALED_UNTIL', 0.0)
            return factors.newton_schulz_inverse_info(*args, **kwargs)

    return solve


def _true_error(info, f, damping):
    m = np.asarray(f, np.float64) + damping * np.eye(f.shape[-1])
    inv = np.linalg.inv(m)
    return np.linalg.norm(np.asarray(info.inverse, np.float64) - inv) / (
        np.linalg.norm(inv)
    )


def test_a_warm_solve_is_the_unscaled_solve_to_the_bit(unscaled):
    # the tail of every solve and every warm solve run alpha == 1, which
    # has to leave the step's operands as they are, not a rounding of them
    d = 96
    f = _spd(d, np.geomspace(0.5, 40.0, d), 3)
    near = factors.newton_schulz_inverse(f, 0.02)
    got = jax.jit(
        lambda f, w: factors.newton_schulz_inverse_info(f, 0.01, x0=w)
    )(f, near)
    want = unscaled(f, 0.01, x0=near)
    assert bool(got.warm) and int(got.scaled) == 0
    assert int(got.iterations) == int(want.iterations) > 0
    np.testing.assert_array_equal(
        np.asarray(got.inverse), np.asarray(want.inverse)
    )


@pytest.mark.parametrize('l', [1e-3, 0.05, 0.4])
def test_scaled_step_quadruples_the_bound(l):
    # eigenvalues of M X spread over [l, 1]: after one scaled step (the
    # plain step on (a x, a mx), a = 2/(1+l)) they lie in
    # [4l/(1+l)^2, 1]: both ends land on the new bound
    d = 64
    mu = np.geomspace(l, 1.0, d)
    m = _spd(d, mu, 5)
    x = jnp.eye(d, dtype=jnp.float32)  # so that M X == M
    a = 2.0 / (1.0 + l)
    _, mx, _ = factors.newton_schulz_step(m, a * x, a * m)
    got = np.linalg.eigvalsh(np.asarray((mx + mx.T) / 2, np.float64))
    bound = 4 * l / (1 + l) ** 2
    assert got.min() == pytest.approx(bound, rel=1e-3)
    assert got.max() <= 1.0 + 1e-5
    # a plain step only doubles it
    _, mx, _ = factors.newton_schulz_step(m, x, m)
    plain = np.linalg.eigvalsh(np.asarray((mx + mx.T) / 2, np.float64))
    assert plain.min() == pytest.approx(l * (2 - l), rel=1e-3)


# eigenvalue ratios of the factor; the kappa a solve works against is a
# few times that (the Gershgorin top over the floor it is handed)
RATIOS = [1e2, 1e4, 1e6]


@pytest.mark.parametrize('ratio', RATIOS)
def test_scaled_cold_solve_lands_where_the_unscaled_lands_in_fewer(
    ratio, unscaled
):
    # ``log4(kappa) + 6``: the phase's log4, three plain steps to square
    # 0.1 under 1e-6, and the trips the stopping rule spends at the
    # float32 floor (it stops at the first residual that does not fall,
    # so rounding decides between one and four: ROADMAP S2c; the matrix
    # is fixed, and XLA:CPU's float32 with it)
    d, damping = 32, 1e-3
    evals = np.geomspace(1.0, ratio, d)
    f = _spd(d, evals - damping, 1)
    floor = 1.0 - damping  # tight: the factor's own smallest eigenvalue
    kappa = _kappa(f, damping, floor)
    assert ratio <= kappa <= 10 * ratio

    plain = unscaled(f, damping, floor=floor)
    got = factors.newton_schulz_inverse_info(f, damping, floor=floor)
    assert int(plain.scaled) == 0
    assert 0 < int(got.scaled) < int(got.iterations)
    assert int(got.iterations) < int(plain.iterations)
    assert int(got.iterations) <= math.log(kappa, 4) + 6
    # the plain solve needs its log2: the gain is the point
    assert int(plain.iterations) >= math.log2(kappa)
    assert float(got.residual) <= 2.0 * float(plain.residual)
    # the same inverse within what float32 gives either of them
    err_plain = _true_error(plain, f, damping)
    err_got = _true_error(got, f, damping)
    assert err_got <= 2.0 * err_plain + 1e-6
    np.testing.assert_allclose(
        np.asarray(got.inverse), np.asarray(plain.inverse),
        atol=4.0 * (err_plain + 1e-6) * float(
            jnp.max(jnp.abs(plain.inverse))
        ) * math.sqrt(d),
    )


@pytest.mark.parametrize('wrong', ['zero', 'overstated-100x', 'beyond-1'])
def test_a_wrong_floor_costs_trips_never_convergence(wrong, unscaled):
    # float32's floor at this kappa (250 against the Gershgorin top) sits
    # at the solver's default tol of 1e-6, either side of it by rounding:
    # a tol three times that is one every variant has to get under
    d, damping, tol = 16, 0.01, 3e-6
    f = _spd(d, np.geomspace(0.05, 5.0, d) - damping, 11)
    true_floor = 0.05 - damping
    floor = {
        'zero': 0.0,
        'overstated-100x': 100.0 * true_floor,
        # a bound above the Gershgorin top: no eigenvalue is there
        'beyond-1': 1e6,
    }[wrong]
    plain = unscaled(f, damping, tol=tol)
    tight = factors.newton_schulz_inverse_info(
        f, damping, tol=tol, floor=true_floor
    )
    got = factors.newton_schulz_inverse_info(f, damping, tol=tol, floor=floor)
    assert float(got.residual) <= tol
    assert float(plain.residual) <= tol and float(tight.residual) <= tol
    assert int(tight.iterations) <= int(got.iterations)
    assert int(got.iterations) <= int(plain.iterations)
    if wrong == 'beyond-1':
        # nothing under the switch point: the plain solve, to the bit
        assert int(got.scaled) == 0
        np.testing.assert_array_equal(
            np.asarray(got.inverse), np.asarray(plain.inverse)
        )
    else:
        assert int(got.scaled) > 0
    np.testing.assert_allclose(
        np.asarray(got.inverse), np.asarray(tight.inverse),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize('damping', [1e-5, 1e-7, 0.0])
def test_beyond_float32_the_scaled_phase_degrades_as_the_plain_does(
    damping, unscaled
):
    # a rank-deficient factor under a damping float32 cannot resolve
    # (kappa 1e7 and up): no iteration inverts it, and the scaled phase,
    # which has no stagnation test, must still not run off (it did, to
    # inf, before its bound was held to NS_SCALE_FROM)
    d = 128
    evals = np.concatenate([np.geomspace(1.0, 300.0, d // 8),
                            np.zeros(d - d // 8)])
    f = _spd(d, evals, 23)
    plain = unscaled(f, damping, max_iters=100)
    got = factors.newton_schulz_inverse_info(f, damping, max_iters=100)
    assert np.isfinite(np.asarray(got.inverse)).all()
    assert float(got.residual) <= 1.25 * float(plain.residual)
    assert int(got.iterations) <= int(plain.iterations)
    # the phase is bounded: its bound starts no lower than NS_SCALE_FROM
    longest, l = 0, factors.NS_SCALE_FROM
    while l < factors.NS_SCALED_UNTIL:
        longest, l = longest + 1, 4 * l / (1 + l) ** 2
    assert int(got.scaled) <= longest


def _drifted(d=256):
    """tests/ops/test_factors.py's restart case: ``ema(1)``'s inverse
    passes the warm test on ``ema(3)`` and then diverges."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(d,))
    u /= np.linalg.norm(u)
    cov = 0.5 * np.eye(d) + 400.0 * np.outer(u, u)
    return lambda n: jnp.asarray(
        0.95 ** n * np.eye(d) + (1 - 0.95 ** n) * cov, jnp.float32
    )


@pytest.mark.parametrize('floor', [0.0, 0.95 ** 3])
def test_a_warm_start_that_holds_runs_todays_iterations(floor, unscaled):
    ema = _drifted(128)
    near = factors.newton_schulz_inverse(ema(3), 0.004)
    today = unscaled(ema(3), 0.003, x0=near)
    got = factors.newton_schulz_inverse_info(
        ema(3), 0.003, x0=near, floor=floor
    )
    assert bool(got.warm) and not bool(got.restarted)
    assert int(got.scaled) == 0
    assert int(got.iterations) == int(today.iterations)
    assert float(got.residual) == float(today.residual)
    np.testing.assert_array_equal(
        np.asarray(got.inverse), np.asarray(today.inverse)
    )


@pytest.mark.parametrize('differentiable', [False, True])
def test_a_probation_restart_counts_its_scaled_trips(differentiable, unscaled):
    ema = _drifted()
    floor = 0.95 ** 3
    old = factors.newton_schulz_inverse(ema(1), 0.003)
    cold = factors.newton_schulz_inverse_info(ema(3), 0.003, floor=floor)
    got = factors.newton_schulz_inverse_info(
        ema(3), 0.003, x0=old, floor=floor, differentiable=differentiable
    )
    assert bool(got.warm) and bool(got.restarted)
    assert int(got.scaled) == int(cold.scaled) > 0
    # the failed attempt's plain iterations, then the cold solve's
    assert int(got.iterations) > int(cold.iterations)
    np.testing.assert_array_equal(
        np.asarray(got.inverse), np.asarray(cold.inverse)
    )
    # and the restart is cheaper than today's by the cold solve's gain
    today = unscaled(ema(3), 0.003, x0=old)
    assert int(got.iterations) < int(today.iterations)


def test_vmap_over_slots_with_their_own_floor_and_damping():
    d = 96
    stack = jnp.stack([
        _spd(d, np.geomspace(0.6, 200.0, d), 1),
        _spd(d, np.geomspace(0.05, 900.0, d), 2),
        jnp.eye(d, dtype=jnp.float32),  # a store's padding slot
        _spd(d, np.geomspace(0.3, 30.0, d), 4),
    ])
    damping = jnp.asarray([0.003, 0.03, 0.003, 0.3], jnp.float32)
    floor = jnp.asarray([0.59, 0.0, 0.57, 300.0], jnp.float32)
    got = jax.vmap(
        lambda m, dm, fl: factors.newton_schulz_inverse_info(
            m, dm, floor=fl
        )
    )(stack, damping, floor)
    assert got.scaled.shape == got.iterations.shape == (4,)
    for i in range(4):
        solo = factors.newton_schulz_inverse_info(
            stack[i], damping[i], floor=floor[i]
        )
        # a lane's counters are its own, whatever the slowest lane took
        # (the phase's length follows from the bound alone; the tail's
        # trips at the float32 floor follow the batched program's rounding)
        assert int(got.scaled[i]) == int(solo.scaled)
        assert abs(int(got.iterations[i]) - int(solo.iterations)) <= 3
        assert float(got.residual[i]) == pytest.approx(
            float(solo.residual), rel=0.5, abs=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(got.inverse[i]), np.asarray(solo.inverse),
            atol=1e-3 * float(jnp.max(jnp.abs(solo.inverse))),
        )
    assert int(got.scaled[2]) == int(got.iterations[2]) == 0  # identity
    assert int(got.scaled[3]) == 0  # a floor beyond the top: plain
    assert (np.asarray(got.residual) <= 1e-3).all()  # lane 1: kappa 3e4
    # the batched 'auto' front end hands the vectors through
    auto = factors.batched_damped_inverse_auto_info(
        stack, damping, floor=floor
    )
    np.testing.assert_array_equal(
        np.asarray(auto.scaled), np.asarray(got.scaled)
    )
    np.testing.assert_array_equal(
        np.asarray(auto.iterations), np.asarray(got.iterations)
    )


@pytest.mark.parametrize('ratio', [1e2, 1e4])
def test_the_differentiable_scan_gives_the_same_outputs(ratio):
    d, damping = 64, 1e-3
    f = _spd(d, np.geomspace(1.0, ratio, d) - damping, 7)
    floor = 1.0 - damping
    loop = factors.newton_schulz_inverse_info(f, damping, floor=floor)
    scan = factors.newton_schulz_inverse_info(
        f, damping, floor=floor, differentiable=True
    )
    assert int(scan.scaled) == int(loop.scaled) > 0
    assert int(scan.iterations) == int(loop.iterations)
    np.testing.assert_allclose(
        float(scan.residual), float(loop.residual), rtol=1e-4, atol=1e-8
    )
    np.testing.assert_allclose(
        np.asarray(scan.inverse), np.asarray(loop.inverse),
        rtol=1e-5, atol=1e-7,
    )


def test_the_scan_differentiates_through_a_scaled_solve():
    # d(M^-1) = -M^-1 dM M^-1, scaled phase or not: the step sizes are
    # constants of the solve, not functions of the factor
    rng = np.random.default_rng(9)
    m = rng.normal(size=(32, 8)).astype(np.float32)
    cov = jnp.asarray(m.T @ m / 32)
    info = factors.newton_schulz_inverse_info(
        cov, 0.01, differentiable=True
    )
    assert int(info.scaled) > 0  # premise: the phase is differentiated

    def loss(c):
        return jnp.sum(
            factors.newton_schulz_inverse(c, 0.01, differentiable=True)
        )

    g = jax.grad(loss)(cov)
    inv = np.linalg.inv(np.asarray(cov, np.float64) + 0.01 * np.eye(8))
    np.testing.assert_allclose(
        np.asarray(g), -(inv.T @ np.ones((8, 8)) @ inv.T),
        rtol=1e-3, atol=1e-4,
    )


# ------------------------------------------------------------ the floor


@pytest.mark.parametrize('step,every,want_n', [
    (0, 10, 1), (9, 10, 1), (10, 10, 2), (100, 10, 11), (205, 10, 21),
    (7, 1, 8),
])
def test_identity_floor_counts_the_updates_a_step_can_have_seen(
    step, every, want_n
):
    got = factors.identity_floor(jnp.asarray(step, jnp.int32), 0.95, every)
    assert float(got) == pytest.approx(0.95 ** want_n, rel=1e-5)


@pytest.mark.parametrize('which', ['decay', 'cadence'])
def test_identity_floor_is_zero_under_a_schedule(which):
    decay = (lambda s: 0.95) if which == 'decay' else 0.95
    every = (lambda s: 10) if which == 'cadence' else 10
    assert factors.identity_floor(jnp.asarray(50), decay, every) == 0.0


@pytest.mark.parametrize('held_back', ['none', 'skipped', 'weighted'])
def test_identity_floor_is_under_the_factors_smallest_eigenvalue(held_back):
    # an EMA from the identity over rank-deficient covariances: the
    # floor is what is left of the identity, and whatever holds an
    # update back (a step without statistics, an evidence-weighted
    # decay) leaves more of it
    d, every, decay = 24, 5, 0.9
    rng = np.random.default_rng(4)
    span = rng.normal(size=(6, d)).astype(np.float32)  # what the rows see
    f = None
    for step in range(0, 41):
        if step % every:
            continue
        if held_back == 'skipped' and step in (10, 25):
            continue
        rows = rng.normal(size=(16, 6)).astype(np.float32) @ span
        alpha = decay
        if held_back == 'weighted':
            alpha = factors.effective_alpha(decay, jnp.float32(0.4))
        f = factors.ema_update(f, jnp.asarray(rows.T @ rows / 16), alpha)
        floor = float(factors.identity_floor(jnp.asarray(step), decay, every))
        lam_min = np.linalg.eigvalsh(np.asarray(f, np.float64)).min()
        assert floor <= lam_min * (1 + 1e-5)  # float32's own rounding
        if held_back == 'none':  # rank 6 of 24: the bound is tight
            assert floor == pytest.approx(lam_min, rel=1e-4)
    # and between updates, and at the step a checkpoint would hold
    for step in (41, 44):
        floor = float(factors.identity_floor(jnp.asarray(step), decay, every))
        assert floor <= (1 + 1e-5) * np.linalg.eigvalsh(
            np.asarray(f, np.float64)
        ).min()
