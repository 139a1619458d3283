"""The K-FAC step reports on itself (docs/OBSERVABILITY.md "A step that
reports on itself"): capture scopes in the compiled step programs,
Newton-Schulz refresh counters in the stacked engine's state, host spans
inside ``Trainer.step``, and the bounded wall-clock table.

All on the CPU: what is checked is names, counts and structure. No number
of these runs is a device time.
"""

import collections
import glob
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kfac_tpu
from kfac_tpu import checkpoint, tracing, training
from kfac_tpu.analysis.ir import visitor
from kfac_tpu.ops import factors
from kfac_tpu.parallel import DistributedKFAC, kaisa, kaisa_mesh
from testing import models


def _op_names(compiled_text):
    """Every ``op_name`` of a compiled program's text: where a reducer
    (benchmark/trace_reduce.py ``op_names``) takes a TPU trace's scopes
    from."""
    return re.findall(r'op_name="([^"]*)"', compiled_text)


# ------------------------------------------------------------ capture scopes


@pytest.fixture(scope='module')
def conv_trainer():
    model = models.TinyConvNet()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16, 3))
    y = jnp.zeros((8,), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x)['params']
    reg = kfac_tpu.register_model(model, x)
    engine = DistributedKFAC(
        kfac_tpu.KFACPreconditioner(
            registry=reg, compute_method='inverse',
            inverse_solver='newton_schulz',
            factor_update_steps=2, inv_update_steps=4,
        ),
        mesh=kaisa_mesh(),
    )

    def loss_fn(p, model_state, batch):
        xb, yb = batch
        logits = model.apply({'params': p}, xb)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, yb)
        return loss.mean(), model_state

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=engine
    )
    return trainer, trainer.init(params, None), (x, y)


@pytest.fixture(scope='module')
def step_texts(conv_trainer):
    trainer, state, batch = conv_trainer
    return {
        'capture': trainer._jit_with_stats.lower(state, batch).compile().as_text(),
        'plain': trainer._jit_no_stats.lower(state, batch).compile().as_text(),
    }


@pytest.mark.parametrize('scope', [
    tracing.CAPTURE_SCOPES['a'],
    tracing.CAPTURE_SCOPES['g'],
    # the convolution helper's im2col, nested under the A side
    tracing.CAPTURE_SCOPES['a'] + '/' + tracing.CAPTURE_SCOPES['patches'],
])
def test_capture_step_program_names_the_capture_scopes(step_texts, scope):
    names = _op_names(step_texts['capture'])
    assert any(scope + '/' in n for n in names), scope


def test_capture_g_is_in_the_backward_pass_and_a_in_the_forward(step_texts):
    names = _op_names(step_texts['capture'])
    g = [n for n in names if 'kfac.capture_g/' in n]
    a = [n for n in names if 'kfac.capture_a/' in n]
    assert g and all('transpose(' in n for n in g)
    assert a and not any('transpose(' in n for n in a)
    # neither side sits under an engine scope: the engine's own metrics
    # (dist_kfac.update_factors is the EMA alone) stay what they were
    assert not any('dist_kfac.' in n for n in a + g)


def test_plain_step_program_names_no_capture_scope(step_texts):
    assert 'kfac.capture' not in step_texts['plain']
    assert 'dist_kfac.precondition' in step_texts['plain']


# --------------------------------------------------- Newton-Schulz counters


def _drifted_factors(d=256):
    """A factor whose top eigenvalue grew between two refreshes (the case
    of tests/ops/test_factors.py's restart test): the inverse of
    ``ema(1)`` passes the warm start's RMS test on ``ema(3)`` and then
    diverges."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(d,))
    u /= np.linalg.norm(u)
    cov = 0.5 * np.eye(d) + 400.0 * np.outer(u, u)

    def ema(n):
        return jnp.asarray(
            0.95 ** n * np.eye(d) + (1 - 0.95 ** n) * cov, jnp.float32
        )

    return ema


def test_newton_schulz_info_says_warm_and_restarted():
    ema = _drifted_factors()
    cold = factors.newton_schulz_inverse_info(ema(3), 0.003)
    assert not bool(cold.warm) and not bool(cold.restarted)
    # a cold start's first iterations are scaled ones
    assert 0 < int(cold.scaled) < int(cold.iterations)
    near = factors.newton_schulz_inverse(ema(3), 0.004)
    kept = factors.newton_schulz_inverse_info(ema(3), 0.003, x0=near)
    assert bool(kept.warm) and not bool(kept.restarted)
    assert int(kept.scaled) == 0  # a warm start that holds runs plain
    assert int(kept.iterations) < int(cold.iterations)
    # zeros fail the up-front test: never warm, so never restarted
    refused = factors.newton_schulz_inverse_info(
        ema(3), 0.003, x0=jnp.zeros_like(near)
    )
    assert not bool(refused.warm) and not bool(refused.restarted)
    assert int(refused.scaled) == int(cold.scaled)
    old = factors.newton_schulz_inverse(ema(1), 0.003)
    for kwargs in ({}, {'differentiable': True}):
        poisoned = factors.newton_schulz_inverse_info(
            ema(3), 0.003, x0=old, **kwargs
        )
        assert bool(poisoned.warm) and bool(poisoned.restarted)
        assert int(poisoned.iterations) > int(cold.iterations)
        # the restart re-enters the scaled phase from the cold bound
        assert int(poisoned.scaled) == int(cold.scaled)


# What the parent commit's solver (0e77fb3, before the start selection)
# returned for the case ``drifted_keeps_warm``, read from a checkout of it on this CPU in
# float32: a warm start that is kept has to run that iteration to the bit.
_PARENT_KEPT = {
    'iterations': 3,
    'residual': float.fromhex('0x1.045f22p-19'),  # 1.9399e-06
    'inverse_sha256': 'ed93f3cdf930160c',
}
_IDENTITY_WEIGHT = 0.34  # what is left of a factor's identity init
_DAMPING = 0.003


_SOLO_CASES = (
    'identity_prefers_cold', 'drifted_keeps_warm', 'zero_x0_is_neither',
    'poisoned_restarts',
)


def _parent_plain_iteration(m, x, tol=1e-6, max_iters=40):
    """The parent's loop from an accepted warm start that holds: plain
    steps until the residual is under ``tol`` or stops falling."""
    eye = jnp.eye(m.shape[-1], dtype=jnp.float32)
    mx = jnp.matmul(m, x, precision=factors.NS_PRECISION)
    resid = jnp.linalg.norm(eye - mx) / jnp.sqrt(jnp.float32(m.shape[-1]))
    prev, k = jnp.inf, 0
    while k < max_iters and resid > tol and resid < prev:
        prev = resid
        x, mx, resid = factors.newton_schulz_step(m, x, mx)
        k += 1
    return x, resid, k


def _solve_for(case, **kwargs):
    """``(factor, x0, info)`` of one of :data:`_SOLO_CASES`, 64 wide."""
    ema = _drifted_factors(64)
    eye = jnp.eye(64, dtype=jnp.float32)
    factor, x0 = {
        # ``c I`` and the inverse of ``(c I + damping)/0.6``: a factor
        # still at its identity init a refresh later, the init's weight
        # having decayed by 0.6 in between. ``M X0 = 0.6 I``: residual
        # 0.40, inside the ``< 0.5`` test; ``I/||M||`` is the inverse
        'identity_prefers_cold': lambda: (
            _IDENTITY_WEIGHT * eye,
            0.6 / (_IDENTITY_WEIGHT + _DAMPING) * eye,
        ),
        # a real spectrum (cold bound ~1e-4) and the inverse of nearly
        # the same matrix
        'drifted_keeps_warm': lambda: (
            ema(3), factors.newton_schulz_inverse(ema(3), 0.004)
        ),
        'zero_x0_is_neither': lambda: (ema(3), 0.0 * eye),
        # passes the RMS test and diverges in the one grown direction
        'poisoned_restarts': lambda: (
            ema(3), factors.newton_schulz_inverse(ema(1), _DAMPING)
        ),
    }[case]()
    return factor, x0, factors.newton_schulz_inverse_info(
        factor, _DAMPING, x0=x0, **kwargs
    )


def _flags(info):
    return tuple(
        np.asarray(v).tolist()
        for v in (info.warm, info.restarted, info.cold_preferred)
    )


@pytest.mark.parametrize(
    'case', _SOLO_CASES + ('vmap_mixes_the_three', 'differentiable_agrees')
)
def test_newton_schulz_start_selection(case):
    """Which of its two starts a solve takes (``newton_schulz_inverse_info``,
    "Safeguarded three times"), a case a parameter, float32 on the CPU."""
    if case == 'identity_prefers_cold':
        factor, x0, got = _solve_for(case)
        m = factor + _DAMPING * jnp.eye(64)
        # premise: the parent's test would have accepted this start
        r_warm = float(jnp.linalg.norm(jnp.eye(64) - m @ x0) / 8.0)
        assert r_warm == pytest.approx(0.40, abs=1e-6)
        assert _flags(got) == (False, False, True)
        assert int(got.iterations) <= 1 and int(got.scaled) == 0
        assert float(got.residual) < 1e-6
        np.testing.assert_allclose(
            np.asarray(got.inverse), np.linalg.inv(np.asarray(m)), rtol=1e-6
        )
        # the same factor with no start at all: the same solve
        cold = factors.newton_schulz_inverse_info(factor, _DAMPING)
        assert _flags(cold) == (False, False, False)
        assert int(cold.iterations) == int(got.iterations)
        np.testing.assert_array_equal(
            np.asarray(cold.inverse), np.asarray(got.inverse)
        )
    elif case == 'drifted_keeps_warm':
        factor, x0, got = _solve_for(case)
        assert _flags(got) == (True, False, False)
        assert int(got.scaled) == 0
        # the parent's recorded values, exactly
        assert int(got.iterations) == _PARENT_KEPT['iterations']
        assert float(got.residual) == _PARENT_KEPT['residual']
        inverse = np.asarray(got.inverse)
        assert hashlib.sha256(inverse.tobytes()).hexdigest()[:16] == (
            _PARENT_KEPT['inverse_sha256']
        )
        # and the parent's loop, run by hand on this machine
        m = factor + _DAMPING * jnp.eye(64, dtype=jnp.float32)
        x, resid, k = _parent_plain_iteration(m, x0)
        assert k == int(got.iterations)
        assert float(resid) == float(got.residual)
        np.testing.assert_array_equal(np.asarray(x), inverse)
    elif case == 'zero_x0_is_neither':
        factor, _, got = _solve_for(case)
        assert _flags(got) == (False, False, False)
        cold = factors.newton_schulz_inverse_info(factor, _DAMPING)
        assert int(got.iterations) == int(cold.iterations)
        assert int(got.scaled) == int(cold.scaled) > 0
        np.testing.assert_array_equal(
            np.asarray(got.inverse), np.asarray(cold.inverse)
        )
    elif case == 'poisoned_restarts':
        factor, _, got = _solve_for(case)
        assert _flags(got) == (True, True, False)
        cold = factors.newton_schulz_inverse_info(factor, _DAMPING)
        assert int(got.iterations) > int(cold.iterations)
        assert float(got.residual) < 1e-5
    elif case == 'vmap_mixes_the_three':
        alone = [_solve_for(c) for c in _SOLO_CASES]
        stack = jnp.stack([f for f, _, _ in alone])
        starts = jnp.stack([w for _, w, _ in alone])
        got = jax.vmap(
            lambda f, w: factors.newton_schulz_inverse_info(
                f, _DAMPING, x0=w
            )
        )(stack, starts)
        assert _flags(got) == (
            [False, True, False, True], [False, False, False, True],
            [True, False, False, False],
        )
        for i, (_, _, solo) in enumerate(alone):
            assert int(got.iterations[i]) == int(solo.iterations)
            assert int(got.scaled[i]) == int(solo.scaled)
            np.testing.assert_allclose(
                np.asarray(got.inverse[i]), np.asarray(solo.inverse),
                rtol=1e-5, atol=1e-7,
            )
        assert not np.any(np.asarray(got.warm & got.cold_preferred))
        # the batched 'auto' pass hands the field through
        auto = factors.batched_damped_inverse_auto_info(
            stack, _DAMPING, x0=starts
        )
        assert _flags(auto) == _flags(got)
    else:
        for each in _SOLO_CASES:
            _, _, loop = _solve_for(each)
            _, _, scan = _solve_for(each, differentiable=True)
            assert _flags(scan) == _flags(loop), each
            assert int(scan.iterations) == int(loop.iterations), each
            np.testing.assert_array_equal(
                np.asarray(scan.inverse), np.asarray(loop.inverse)
            )


@pytest.mark.parametrize('differentiable', [False, True])
def test_choosing_the_start_adds_no_product_and_no_loop(differentiable):
    """The program-shape guard of the start selection: with an ``x0`` the
    solve holds three products (the warm start's ``M @ X0`` and the
    body's two) and one loop; without, the body's two. Choosing between
    the two starts is scalar arithmetic and selects."""
    d = 48
    m = jnp.eye(d, dtype=jnp.float32)
    for x0, products in ((m, 3), (None, 2)):
        jaxpr = jax.make_jaxpr(
            lambda f, w: factors.newton_schulz_inverse_info(
                f, 0.003, x0=w, floor=0.3, differentiable=differentiable
            )
        )(m, x0).jaxpr
        # sub-jaxprs (the loop's body and condition, a scan's) included
        got = collections.Counter(
            eqn.primitive.name for eqn, _ in visitor.iter_eqns(jaxpr)
        )
        assert got['dot_general'] == products
        assert got['while'] + got['scan'] == 1
        assert got['scan' if differentiable else 'while'] == 1
        assert not got['conv_general_dilated'] and not got['cond']


def test_batched_auto_info_keeps_the_iterations_own_fields():
    ema = _drifted_factors(64)
    stack = jnp.stack([ema(1), ema(3)])
    info = factors.batched_damped_inverse_auto_info(stack, 0.003)
    # no slot failed, so the served stack is the Newton-Schulz pass's own
    ns = jax.vmap(lambda m: factors.newton_schulz_inverse(m, 0.003))(stack)
    np.testing.assert_allclose(
        np.asarray(info.inverse), np.asarray(ns), rtol=1e-5, atol=1e-7
    )
    assert info.iterations.shape == info.residual.shape == (2,)
    assert (np.asarray(info.iterations) > 0).all()
    assert not np.asarray(info.warm).any()
    assert not np.asarray(info.restarted).any()
    assert info.scaled.shape == (2,)
    assert (np.asarray(info.scaled) > 0).all()
    assert not np.asarray(info.cold_preferred).any()  # no start to prefer


def _dense_engine(solver='newton_schulz', method='inverse', frac=1.0, dim=255):
    """One Dense layer whose A factor is ``dim + 1`` wide (bias)."""
    model = models.TinyModel(hidden=8, out=4)
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=16, dim=dim)
    reg = kfac_tpu.register_model(model, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method=method,
        inverse_solver=solver if method == 'inverse' else None,
        damping=0.003,
    )
    return DistributedKFAC(cfg, mesh=kaisa_mesh(grad_worker_fraction=frac))


def _with_a_factor(engine, state, layer, factor):
    key, slot = engine._a_slot[layer]
    stack = state.a[key]
    padded = kaisa.pad_factor(factor, stack.shape[-1])
    return state._replace(a={**state.a, key: stack.at[slot].set(padded)})


def _wide_layer(engine):
    return max(
        engine.registry.layers,
        key=lambda n: engine.registry.layers[n].a_factor_shape[0],
    )


@pytest.mark.parametrize('solver', ['newton_schulz', 'auto'])
def test_refresh_report_cold_then_warm_then_poisoned(solver):
    engine = _dense_engine(solver)
    layer = _wide_layer(engine)
    ema = _drifted_factors(engine.registry.layers[layer].a_factor_shape[0])
    refresh = jax.jit(engine.update_inverses)

    state = engine.init()
    assert engine.refresh_report(state) == {}  # nothing has been solved yet
    state = refresh(_with_a_factor(engine, state, layer, ema(1)))
    cold = engine.refresh_report(state)
    n_layers = len(engine.registry.layers)
    assert cold['totals']['slots'] == 2 * n_layers  # an A and a G each
    assert cold['totals']['warm_starts'] == 0
    assert cold['totals']['restarts'] == 0
    # a fresh state's zero inverses are no start to prefer anything to
    assert cold['totals']['cold_preferred'] == 0
    assert cold['totals']['iterations'] > 0
    assert cold['totals']['worst_residual'] < 1e-5
    key, slot = engine._a_slot[layer]
    bucket = cold['buckets']['a'][key]
    (sb,) = [sb for sb in engine.a_store if sb.key == key]
    assert len(bucket['iterations']) == len(sb.layers)  # a layer each
    assert bucket['trips'] == max(bucket['iterations'])
    # trips: every bucket runs until its slowest slot is done
    assert cold['totals']['trips'] == sum(
        b['trips'] for side in cold['buckets'].values() for b in side.values()
    )
    assert cold['totals']['trips'] <= cold['totals']['iterations']
    # the wide factor's cold solve took scaled steps; the others (a
    # fresh state's identities) took none, being solved where they start
    assert 0 < bucket['scaled_trips'] < bucket['trips']
    assert cold['totals']['scaled_trips'] == bucket['scaled_trips']

    # the same factors again: the wide factor starts from its own
    # inverse; the others are still the identity they were made as, whose
    # cold start is the inverse itself and is taken
    warm = engine.refresh_report(refresh(state))
    assert warm['buckets']['a'][key]['warm_starts'] == 1
    assert warm['buckets']['a'][key]['cold_preferred'] == (
        len(sb.layers) - 1
    )
    assert warm['totals']['warm_starts'] == 1
    assert warm['totals']['cold_preferred'] == warm['totals']['slots'] - 1
    assert warm['totals']['restarts'] == 0
    assert warm['totals']['scaled_trips'] == 0
    assert warm['totals']['iterations'] < cold['totals']['iterations']
    assert warm['totals']['worst_residual'] < 1e-5

    # the wide factor drifts out of its old inverse's basin: one restart
    state = refresh(_with_a_factor(engine, state, layer, ema(3)))
    poisoned = engine.refresh_report(state)
    assert poisoned['totals']['restarts'] == 1
    assert poisoned['buckets']['a'][key]['restarts'] == 1
    # the restarted slot's second attempt is a cold one: scaled steps
    assert poisoned['totals']['scaled_trips'] == (
        poisoned['buckets']['a'][key]['scaled_trips']
    ) > 0
    assert poisoned['totals']['warm_starts'] == 1
    assert poisoned['totals']['cold_preferred'] == (
        poisoned['totals']['slots'] - 1
    )
    assert (poisoned['buckets']['a'][key]['iterations'][slot]
            > cold['buckets']['a'][key]['iterations'][slot])
    assert poisoned['totals']['worst_residual'] < 1e-5
    # the solve's residual is the served inverse's
    ours = float(np.max(np.asarray(
        engine.inverse_residuals(state)['a'][key]
    )[:len(sb.layers)]))
    assert poisoned['buckets']['a'][key]['worst_residual'] == pytest.approx(
        ours, rel=0.2, abs=1e-7
    )


def test_refresh_report_on_a_sharded_mesh():
    engine = _dense_engine(frac=0.5, dim=6)
    state = jax.jit(engine.update_inverses)(engine.init())
    report = engine.refresh_report(state)
    stores = {'a': engine.a_store, 'g': engine.g_store}
    for side, store in stores.items():
        assert list(report['buckets'][side]) == [sb.key for sb in store]
        for sb in store:
            bucket = report['buckets'][side][sb.key]
            assert len(bucket['iterations']) == len(sb.layers)
            # a fresh state's factors are the identity: solved in 0 trips,
            # and still a refresh that filled the counters
            assert bucket['trips'] == max(bucket['iterations']) == 0
            assert bucket['scaled_trips'] == 0


def test_refresh_field_is_one_ephemeral_leaf():
    engine = _dense_engine(dim=6)
    state = engine.init()
    assert kaisa.DistKFACState._fields[-1] == 'refresh'
    stores = engine.a_store + engine.g_store
    # the device holds the solve's six columns; the layout is static
    assert kaisa.REFRESH_COLUMNS == (
        'iterations', 'residual', 'warm', 'restarted', 'scaled',
        'cold_preferred',
    )
    assert state.refresh.solved.shape == (
        sum(sb.padded for sb in stores), len(kaisa.REFRESH_COLUMNS)
    )
    assert state.refresh.solved.dtype == jnp.float32
    assert [b[1:] for b in state.refresh.buckets] == [
        (sb.key, sb.padded, len(sb.layers)) for sb in stores
    ]
    leaves = jax.tree_util.tree_leaves
    assert len(leaves(state.refresh)) == 1
    assert len(leaves(state)) == len(leaves(state._replace(refresh=None))) + 1
    assert 'refresh' not in checkpoint.durable_state(state)
    shardings = engine.state_shardings()
    assert shardings.refresh.solved.is_fully_replicated
    assert jax.tree_util.tree_structure(shardings.refresh) == (
        jax.tree_util.tree_structure(state.refresh)
    )


def test_no_refresh_keys_before_a_refresh_has_filled_the_counters():
    """``init()``'s array says nothing about a solve: a drain then carries
    no ``refresh/*`` key (a zero ``refresh/worst_residual`` would read as
    a healthy one)."""
    engine = _dense_engine(dim=6)
    state = engine.init()
    assert kaisa.refresh_totals(state.refresh) == {}
    assert not any(
        k.startswith('refresh/')
        for k in kfac_tpu.MetricsCollector().drain(state)
    )
    state = jax.jit(engine.update_inverses)(state)
    assert kfac_tpu.MetricsCollector().drain(state)['refresh/slots'] == (
        2 * len(engine.registry.layers)
    )


def test_async_refresh_modes_carry_no_counters():
    """The sliced mode's shadow solves and the host mode's LAPACK do not
    write the counters, so such an engine has none rather than its step-0
    bootstrap's forever."""
    model = models.TinyModel(hidden=8, out=4)
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=16, dim=6)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=kfac_tpu.register_model(model, x),
        compute_method='inverse', inverse_solver='newton_schulz',
        inv_update_steps=4, async_inverse=True,
    )
    engine = DistributedKFAC(cfg, mesh=kaisa_mesh())
    state = engine.init()
    assert state.refresh is None
    assert engine.state_shardings().refresh is None
    state = jax.jit(engine.update_inverses)(state)
    assert state.refresh is None and engine.refresh_report(state) == {}


@pytest.mark.parametrize('method,solver', [
    ('eigen', None), ('inverse', 'cholesky'),
])
def test_no_newton_schulz_no_counters(method, solver):
    engine = _dense_engine(solver=solver, method=method, dim=6)
    state = engine.init()
    assert state.refresh is None
    assert engine.state_shardings().refresh is None
    state = jax.jit(engine.update_inverses)(state)
    assert state.refresh is None
    assert engine.refresh_report(state) == {}
    assert not any(
        k.startswith('refresh/')
        for k in kfac_tpu.MetricsCollector().drain(state)
    )


#: every field of the two engines' states, in order: a field that comes
#: or goes is a new state layout and belongs in the table below
STATE_FIELDS = {
    'dense': (
        'step', 'a', 'g', 'qa', 'qg', 'da', 'dg', 'dgda', 'a_inv', 'g_inv',
        'health', 'metrics', 'flight', 'shadow',
    ),
    'kaisa': (
        'step', 'a', 'g', 'qa', 'qg', 'da', 'dg', 'dgda', 'a_inv', 'g_inv',
        'inv_damping', 'health', 'metrics', 'flight', 'shadow', 'traffic',
        'refresh',
    ),
}


@pytest.mark.parametrize('mode', [None, 'sliced', 'host'])
@pytest.mark.parametrize('method', ['eigen', 'inverse'])
@pytest.mark.parametrize('engine', ['dense', 'kaisa'])
def test_state_fields(engine, method, mode):
    """One state layout per (method, async mode): which fields are
    ``None`` and which decomposition dicts are empty follows from those
    two alone (with the options that hang a report on the state off, as
    the cells have them, and no stacked experts in the registry)."""
    model = models.TinyModel(hidden=8, out=4)
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=16, dim=6)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=kfac_tpu.register_model(model, x),
        compute_method=method, inverse_solver='newton_schulz',
        inv_update_steps=4, async_inverse=mode,
    )
    state = (
        cfg if engine == 'dense'
        else DistributedKFAC(cfg, mesh=kaisa_mesh(0.5))
    ).init()
    assert state._fields == STATE_FIELDS[engine]

    is_none = {'health', 'metrics', 'flight'}
    if mode != 'sliced':
        is_none.add('shadow')  # the sliced mode's second set of slots
    if engine == 'kaisa':
        is_none.add('traffic')
        if method == 'eigen' or mode is not None:
            # the synchronous Newton-Schulz refresh alone counts itself
            is_none.add('refresh')
    empty = (
        {'a_inv', 'g_inv', 'dgda'} if method == 'eigen'
        else {'qa', 'qg', 'da', 'dg', 'dgda'}
    )
    assert {f for f in state._fields if getattr(state, f) is None} == is_none
    assert {
        f for f in state._fields
        if isinstance(getattr(state, f), dict) and not getattr(state, f)
    } == empty
    # what is neither holds arrays: the durable three among them
    for field in ('step', 'a', 'g'):
        assert jax.tree_util.tree_leaves(getattr(state, field))


def test_refresh_counters_survive_a_restore(tmp_path):
    engine = _dense_engine(dim=6)
    state = jax.jit(engine.update_inverses)(engine.init())
    path = str(tmp_path / 'ckpt')
    checkpoint.save(path, state, engine=engine)
    restored, _ = checkpoint.restore(path, engine)
    # init() made the field; the restore's rematerialization filled it
    assert restored.refresh.solved.shape == state.refresh.solved.shape
    assert engine.refresh_report(restored)['totals']['slots'] == (
        2 * len(engine.registry.layers)
    )


def test_collector_folds_the_refresh_totals():
    engine = _dense_engine(dim=6)
    state = jax.jit(engine.update_inverses)(engine.init())
    record = kfac_tpu.MetricsCollector().drain(state)
    totals = engine.refresh_report(state)['totals']
    assert {k: v for k, v in record.items() if k.startswith('refresh/')} == {
        f'refresh/{k}': v for k, v in totals.items()
    }
    assert set(totals) == {
        'slots', 'iterations', 'trips', 'scaled_trips', 'warm_starts',
        'restarts', 'cold_preferred', 'worst_residual',
    }


# ------------------------------------------------------------- host spans


def _host_events(logdir):
    (path,) = glob.glob(f'{logdir}/**/*.xplane.pb', recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if plane.name != '/host:CPU':
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == 'train' or e.name.startswith('kfac.host.'):
                    events.append({
                        'name': e.name, 'start': e.start_ns,
                        'end': e.start_ns + e.duration_ns,
                        **{k: v for k, v in e.stats},
                    })
    return sorted(events, key=lambda e: e['start'])


def _profiled(tmp_path, fn):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _host_events(str(tmp_path))


def test_three_steps_hold_three_spans_each_on_one_step_number(
    conv_trainer, tmp_path
):
    trainer, state, batch = conv_trainer
    state, _ = trainer.step(state, batch)  # compiled and resynced before
    first = trainer._step_count

    def three():
        s = state
        for _ in range(3):
            s, loss = trainer.step(s, batch)
        jax.block_until_ready(s)

    _, events = _profiled(tmp_path, three)
    spans = tracing.HOST_SPANS
    for i, step in enumerate(range(first, first + 3)):
        mine = {
            e['name']: e for e in events
            if e.get('step', e.get('step_num')) == step
        }
        assert set(mine) == {'train', *spans.values()}, (step, sorted(mine))
        pre, launch, post, train = (
            mine[spans['pre_step']], mine[spans['launch']],
            mine[spans['post_step']], mine['train'],
        )
        assert pre['end'] <= train['start']
        assert train['start'] <= launch['start'] <= launch['end'] <= train['end']
        assert train['end'] <= post['start']
    assert sum(e['name'] == spans['launch'] for e in events) == 3


def test_the_step_count_sync_is_under_pre_step(conv_trainer, tmp_path):
    """The one call of a step that can read the device: a ``pre_step``
    span of its own, without a number (it reads the number)."""
    trainer, state, batch = conv_trainer
    trainer._step_count = None  # as on a first step or after a compiled scan

    def one():
        jax.block_until_ready(trainer.step(state, batch)[0])

    _, events = _profiled(tmp_path, one)
    pre = [e for e in events if e['name'] == tracing.HOST_SPANS['pre_step']]
    assert len(pre) == 2
    assert 'step' not in pre[0]
    assert pre[1]['step'] == trainer._step_count - 1
    assert pre[0]['end'] <= pre[1]['start']


def test_accumulate_and_scan_paths_get_the_launch_span_only(
    conv_trainer, tmp_path
):
    trainer, state, batch = conv_trainer
    stacked = jax.tree_util.tree_map(lambda b: jnp.stack([b, b]), batch)

    def run():
        s, _ = trainer.step_accumulate(state, [batch, batch])
        s, _ = trainer.step_accumulate_scan(s, stacked)
        s, _ = trainer.scan_steps(s, stacked)
        jax.block_until_ready(s)

    _, events = _profiled(tmp_path, run)
    names = [e['name'] for e in events]
    assert names.count(tracing.HOST_SPANS['launch']) == 3
    assert tracing.HOST_SPANS['pre_step'] not in names
    assert tracing.HOST_SPANS['post_step'] not in names


# --------------------------------------------------------- wall-clock table


def test_wall_clock_table_is_bounded():
    saved = dict(tracing._func_traces)
    tracing.clear_trace()
    try:
        @tracing.trace(name='bounded_probe')
        def f():
            return None

        for _ in range(tracing.TRACE_HISTORY + 50):
            f()
        assert len(tracing._func_traces['bounded_probe']) == tracing.TRACE_HISTORY
        assert tracing.get_trace()['bounded_probe'] >= 0.0
        total = tracing.get_trace(average=False, max_history=10)
        whole = tracing.get_trace(average=False)
        assert 0.0 <= total['bounded_probe'] <= whole['bounded_probe']
    finally:
        tracing.clear_trace()
        tracing._func_traces.update(saved)
