"""Distributed KAISA tests on the 8-virtual-device CPU mesh.

The analogue of the reference's forked-gloo distributed suite
(tests/layers/layers_test.py world {1,4} x {MEM,COMM}-OPT and
tests/training_test.py): the same SPMD programs that run on a TPU pod run
here on 8 host devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import enums
from kfac_tpu.parallel import DistributedKFAC, batch_sharding, kaisa_mesh, mesh as mesh_lib
from testing import models

WORLD = 8


def _setup(frac, compute_method='eigen', **cfg_kw):
    mesh = kaisa_mesh(grad_worker_fraction=frac)
    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=WORLD * 8, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method=compute_method, **cfg_kw
    )
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    loss_fn = models.mse_loss(m)
    return mesh, m, params, (x, y), reg, cfg, dk, loss_fn


@pytest.mark.parametrize('frac,shape', [(1.0, (8, 1)), (0.5, (4, 2)), (0.25, (2, 4)), (1 / 8, (1, 8))])
def test_mesh_shapes(frac, shape):
    mesh = kaisa_mesh(grad_worker_fraction=frac)
    assert (mesh_lib.grad_workers(mesh), mesh_lib.n_cols(mesh)) == shape
    assert mesh_lib.world_size(mesh) == WORLD


def test_bucketing_pads_to_world():
    _, _, _, _, reg, _, dk, _ = _setup(1.0)
    for b in dk.buckets:
        assert b.padded % WORLD == 0
        assert set(b.layers) <= set(reg.names())
    assert sum(len(b.layers) for b in dk.buckets) == len(reg)


@pytest.mark.parametrize('frac', [1.0, 0.5, 1 / 8])
def test_state_shardings_and_memory(frac):
    _, _, _, _, _, _, dk, _ = _setup(frac)
    state = dk.init()
    assert int(state.step) == 0
    usage = dk.memory_usage(state)
    assert usage['total'] > 0
    # MEM-OPT keeps strictly less resident than COMM-OPT
    if frac == 1 / 8:
        _, _, _, _, _, _, dk_comm, _ = _setup(1.0)
        comm_usage = dk_comm.memory_usage(dk_comm.init())
        assert usage['a_inverses'] < comm_usage['a_inverses']


@pytest.mark.parametrize(
    'frac,method',
    [
        (1.0, 'eigen'),
        (0.5, 'eigen'),
        (1 / 8, 'eigen'),
        (1.0, 'inverse'),
        (1 / 8, 'inverse'),
    ],
)
def test_distributed_matches_single_device(frac, method):
    """The sharded stacked engine must numerically match the dense
    single-device preconditioner (same stats, same grads)."""
    mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
        frac, compute_method=method, kl_clip=0.001, damping=0.01
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)

    # dense reference path
    ref_state = cfg.init()
    ref_state, ref_grads = cfg.step(ref_state, grads, stats)

    # distributed path
    state = dk.init()

    @jax.jit
    def dstep(state, grads, stats):
        return dk.step(state, grads, stats)

    state, dist_grads = dstep(state, grads, stats)
    assert int(state.step) == 1
    for name in reg.names():
        np.testing.assert_allclose(
            np.asarray(dist_grads[name]['kernel']),
            np.asarray(ref_grads[name]['kernel']),
            rtol=5e-3, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(dist_grads[name]['bias']),
            np.asarray(ref_grads[name]['bias']),
            rtol=5e-3, atol=1e-5,
        )


@pytest.mark.parametrize('frac', [1.0, 0.5, 1 / 8])
def test_distributed_training_loss_decreases(frac):
    """Full data-parallel training with sharded batch: loss must decrease
    (reference smoke: tests/training_test.py:15-79)."""
    mesh, m, params, (x, y), reg, cfg2, dk, loss_fn = _setup(
        frac, damping=0.003, lr=0.05
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss_fn)
    state = dk.init()
    bs = batch_sharding(mesh)
    x = jax.device_put(x, bs)
    y = jax.device_put(y, bs)

    @jax.jit
    def train_step(params, state, batch):
        (loss, _), grads, stats = run(params, batch)
        state, pgrads = dk.step(state, grads, stats)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, pgrads)
        return params, state, loss

    losses = []
    for _ in range(12):
        params, state, loss = train_step(params, state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_conv_model_distributed():
    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    m = models.TinyConvNet()
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 32, 32, 1))
    y = jax.nn.one_hot(jnp.arange(16) % 10, 10)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, damping=0.01)
    dk = DistributedKFAC(config=cfg, mesh=mesh)

    def loss_fn(p, batch):
        xx, yy = batch
        logits = m.apply({'params': p}, xx)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * yy, axis=-1))

    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss_fn)
    state = dk.init()
    bs = batch_sharding(mesh)
    x, y = jax.device_put(x, bs), jax.device_put(y, bs)

    @jax.jit
    def train_step(params, state, batch):
        (loss, _), grads, stats = run(params, batch)
        state, pgrads = dk.step(state, grads, stats)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, pgrads)
        return params, state, loss

    losses = []
    for _ in range(8):
        params, state, loss = train_step(params, state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_assignment_parity_object():
    _, _, _, _, _, _, dk, _ = _setup(0.5)
    kaisa = dk.assignment
    assert kaisa.mesh_shape() == (4, 2)
    assert kaisa.broadcast_gradients() and kaisa.broadcast_inverses()


def test_unexecuted_layer_keeps_factors():
    """Registered layers skipped by the loss_fn keep their factors (parity
    with the dense engine's update_factors)."""
    mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(0.5)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    # drop one layer's stats as if its module never ran
    partial = kfac_tpu.CapturedStats(
        a={k: v for k, v in stats.a.items() if k != 'fc2'},
        g={k: v for k, v in stats.g.items() if k != 'fc2'},
    )
    state = dk.init()
    state2 = jax.jit(dk.update_factors)(state, partial)
    # find fc2's bucket and slot: its factor row must be unchanged (identity)
    for b in dk.buckets:
        if 'fc2' in b.layers:
            i = b.layers.index('fc2')
            np.testing.assert_allclose(
                np.asarray(state2.a[b.key][i]), np.eye(b.da), atol=1e-6
            )
        if 'fc1' in b.layers:
            i = b.layers.index('fc1')
            assert np.abs(np.asarray(state2.a[b.key][i]) - np.eye(b.da)).max() > 0


def test_prediv_eigenvalues_distributed_matches_plain():
    """prediv fuses 1/(dg x da + damping) at inverse time; results must
    match the on-the-fly division path."""
    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    loss_fn = models.mse_loss(m)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))

    outs = {}
    for prediv in (False, True):
        cfg = kfac_tpu.KFACPreconditioner(
            registry=reg, damping=0.01, kl_clip=None,
            prediv_eigenvalues=prediv,
        )
        dk = DistributedKFAC(config=cfg, mesh=mesh)
        state = dk.init()
        if prediv:
            assert state.dgda and not state.da
        state, pg = jax.jit(dk.step)(state, grads, stats)
        outs[prediv] = pg
    np.testing.assert_allclose(
        np.asarray(outs[True]['fc1']['kernel']),
        np.asarray(outs[False]['fc1']['kernel']),
        rtol=1e-4, atol=1e-6,
    )


def test_prediv_memory_accounted():
    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    m = models.TinyModel()
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, prediv_eigenvalues=True)
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    usage = dk.memory_usage(dk.init())
    # the fused dgda buffer must be counted (it replaces da/dg)
    expected_dgda = sum(
        b.padded * b.dg * b.da * 4 for b in dk.buckets
    ) / mesh_lib.n_cols(mesh)
    assert usage['g_inverses'] >= expected_dgda


def test_bucketed_allreduce_matches_default():
    """ALLREDUCE_BUCKETED (triangle-packed single-buffer stat transport)
    must be numerically identical to the per-factor default — engaging the
    reference's symmetric bucketing (kfac/distributed.py:305-374,422-465)."""

    def run(method):
        mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
            0.5, kl_clip=0.001, damping=0.01,
            factor_update_steps=1, inv_update_steps=1,
            allreduce_method=method,
        )
        cap = kfac_tpu.CurvatureCapture(reg)
        runner = cap.value_stats_and_grad(loss_fn)
        state = dk.init()

        @jax.jit
        def step(params, state, batch):
            (l, _), grads, stats = runner(params, batch)
            state, pg = dk.step(state, grads, stats)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.05 * g, params, pg
            )
            return params, state, l

        bs = batch_sharding(mesh)
        batch = tuple(jax.device_put(b, bs) for b in batch)
        losses = []
        for _ in range(4):
            params, state, l = step(params, state, batch)
            losses.append(float(l))
        return losses, params

    l_def, p_def = run('allreduce')
    l_b, p_b = run('allreduce_bucketed')
    np.testing.assert_allclose(l_b, l_def, rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_def), jax.tree_util.tree_leaves(p_b)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


def test_bucketed_allreduce_chunked_matches_default():
    """A byte cap small enough to force one chunk per factor triangle must
    not change the numerics — only the packing granularity (the
    reference's 25 MB cap, kfac/distributed.py:305-374)."""

    def run(**kw):
        mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
            0.5, kl_clip=0.001, damping=0.01,
            factor_update_steps=1, inv_update_steps=1, **kw,
        )
        cap = kfac_tpu.CurvatureCapture(reg)
        runner = cap.value_stats_and_grad(loss_fn)
        state = dk.init()

        @jax.jit
        def step(params, state, batch):
            (l, _), grads, stats = runner(params, batch)
            state, pg = dk.step(state, grads, stats)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.05 * g, params, pg
            )
            return params, state, l

        bs = batch_sharding(mesh)
        batch = tuple(jax.device_put(b, bs) for b in batch)
        for _ in range(3):
            params, state, l = step(params, state, batch)
        return float(l), params

    l_def, p_def = run(allreduce_method='allreduce')
    # ~100-byte cap: every factor triangle in this model exceeds it, so
    # each rides its own chunk — maximal chunking
    l_c, p_c = run(
        allreduce_method='allreduce_bucketed',
        allreduce_bucket_cap_mb=1e-4,
    )
    np.testing.assert_allclose(l_c, l_def, rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_def), jax.tree_util.tree_leaves(p_c)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


@pytest.mark.parametrize('method', ['eigen', 'inverse'])
def test_colocate_factors_false_placement_and_numerics(method):
    """colocate_factors=False stores A and G in independent dimension
    buckets (different placement: one layer's factors in different
    stacks/slots, reference kfac/assignment.py:268-304) while the
    preconditioned gradients stay numerically identical to the dense
    engine."""
    import flax.linen as nn

    class Wide(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(16, name='p')(x))
            x = nn.relu(nn.Dense(16, name='q')(x))
            return nn.Dense(4, name='r')(x)

    m = Wide()
    x = jax.random.normal(jax.random.PRNGKey(0), (WORLD * 4, 16))
    y = jax.random.normal(jax.random.PRNGKey(1), (WORLD * 4, 4))
    params = m.init(jax.random.PRNGKey(2), x)['params']
    reg = kfac_tpu.register_model(m, x)

    def loss_fn(params, batch):
        xb, yb = batch
        return jnp.mean((m.apply({'params': params}, xb) - yb) ** 2)

    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method=method, damping=0.01, kl_clip=0.001,
        colocate_factors=False,
    )
    dk = DistributedKFAC(config=cfg, mesh=mesh)

    # placement: A side groups all three layers (shared da=17) in ONE
    # stack while G splits 16s from 4s — slots no longer pairwise aligned
    # (bucket_granularity resolves to 1 = exact dims on the CPU mesh)
    assert [sb.key for sb in dk.a_store] == ['a17']
    assert sorted(sb.key for sb in dk.g_store) == ['g16', 'g4']
    assert dk._a_slot['r'] == ('a17', 2)
    assert dk._g_slot['r'] == ('g4', 0)
    assert not dk.assignment.colocate_factors

    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))

    ref_cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method=method, damping=0.01, kl_clip=0.001,
    )
    ref_state, ref_grads = ref_cfg.step(ref_cfg.init(), grads, stats)

    state = dk.init()
    assert set(state.a) == {'a17'}
    assert set(state.g) == {'g16', 'g4'}

    @jax.jit
    def dstep(state, grads, stats):
        return dk.step(state, grads, stats)

    state, dist_grads = dstep(state, grads, stats)
    for name in reg.names():
        np.testing.assert_allclose(
            np.asarray(dist_grads[name]['kernel']),
            np.asarray(ref_grads[name]['kernel']),
            rtol=5e-3, atol=1e-5,
        )


def test_mem_opt_requires_colocated():
    mesh = kaisa_mesh(grad_worker_fraction=1 / WORLD)
    m = models.TinyModel(hidden=8, out=4)
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=8, dim=6)
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, colocate_factors=False)
    with pytest.raises(ValueError, match='MEM-OPT'):
        DistributedKFAC(config=cfg, mesh=mesh)


def test_memory_usage_reads_actual_shard_bytes():
    """memory_usage must report the real per-device shard footprint:
    factors always shard over the full mesh; decomps replicate under
    COMM-OPT and shard by column otherwise."""
    _, _, _, _, _, _, dk_comm, _ = _setup(1.0)
    st = dk_comm.init()
    usage = dk_comm.memory_usage(st)
    # compute the expectation straight from the arrays' shardings
    expect_a = sum(
        int(np.prod(v.sharding.shard_shape(v.shape))) * v.dtype.itemsize
        for v in st.a.values()
    )
    assert usage['a_factors'] == expect_a
    expect_qa = sum(
        int(np.prod(v.sharding.shard_shape(v.shape))) * v.dtype.itemsize
        for v in st.qa.values()
    )
    assert usage['a_inverses'] == expect_qa + sum(
        int(np.prod(v.sharding.shard_shape(v.shape))) * v.dtype.itemsize
        for v in st.da.values()
    )
    # COMM-OPT decomps are replicated: per-device bytes == global bytes
    for v in st.qa.values():
        assert np.prod(v.sharding.shard_shape(v.shape)) == v.size
    # MEM-OPT keeps a 1/world column shard
    _, _, _, _, _, _, dk_mem, _ = _setup(1 / WORLD)
    stm = dk_mem.init()
    um = dk_mem.memory_usage(stm)
    assert um['a_inverses'] < usage['a_inverses']
    for v in stm.qa.values():
        assert np.prod(v.sharding.shard_shape(v.shape)) * WORLD == v.size


def test_newton_schulz_solver_matches_cholesky_distributed():
    """inverse_solver='newton_schulz' (matmul-only, the TPU-native path)
    produces the same preconditioned grads as the Cholesky solver in the
    sharded stacked engine."""
    mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
        0.5, compute_method='inverse', kl_clip=None, damping=0.01,
        inverse_solver='newton_schulz',
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = dk.init()
    state, ns_grads = jax.jit(dk.step)(state, grads, stats)

    _, _, _, _, _, _, dk_chol, _ = _setup(
        0.5, compute_method='inverse', kl_clip=None, damping=0.01,
    )
    cstate = dk_chol.init()
    cstate, chol_grads = jax.jit(dk_chol.step)(cstate, grads, stats)
    for name in reg.names():
        np.testing.assert_allclose(
            np.asarray(ns_grads[name]['kernel']),
            np.asarray(chol_grads[name]['kernel']),
            rtol=5e-3, atol=5e-5,
        )


def test_describe_placement_matches_actual_shard_layout():
    """The dump's executed-placement section must report the device that
    REALLY holds each layer's factor slot (the greedy
    table alone misled load-imbalance debugging), and the greedy table is
    labeled as the cost-model view."""
    _, _, _, _, reg, _, dk, _ = _setup(0.5, kl_clip=None)
    state = dk.init()
    dump = dk.describe()
    assert 'NOT the executed placement' in dump
    assert 'executed placement' in dump
    for name in reg.names():
        for side in ('a', 'g'):
            claimed = dk.slot_device(side, name)
            key, i = (dk._a_slot if side == 'a' else dk._g_slot)[name]
            arr = (state.a if side == 'a' else state.g)[key]
            # find the device whose actual shard covers slot i
            owners = [
                dev
                for dev, idx in arr.sharding.devices_indices_map(
                    arr.shape
                ).items()
                if (idx[0].start or 0) <= i < (idx[0].stop or arr.shape[0])
            ]
            assert claimed in owners, (name, side, claimed, owners)
            # the dump names that device id on the layer's placement line
            placement = dump.split('executed placement')[1].split(
                'cost-model view'
            )[0]
            line = next(
                l
                for l in placement.splitlines()
                if l.strip().startswith(name + ':')
            )
            assert f'device {claimed.id}' in line


def test_host_eigh_impl_matches_xla_in_stacked_engine():
    """eigh_impl='host' (pure_callback -> LAPACK inside the shard_map)
    produces the same preconditioned grads as the device eigh — the EIGEN
    method's TPU escape hatch, exercised on the sharded stacked path."""
    mesh, m, params, batch, reg, cfg, dk_host, loss_fn = _setup(
        0.5, kl_clip=None, damping=0.01, eigh_impl='host'
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, batch)
    state = dk_host.init()
    state, host_grads = jax.jit(dk_host.step)(state, grads, stats)

    _, _, _, _, _, _, dk_xla, _ = _setup(0.5, kl_clip=None, damping=0.01)
    xstate = dk_xla.init()
    xstate, xla_grads = jax.jit(dk_xla.step)(xstate, grads, stats)
    for name in reg.names():
        np.testing.assert_allclose(
            np.asarray(host_grads[name]['kernel']),
            np.asarray(xla_grads[name]['kernel']),
            rtol=2e-4, atol=1e-6,
        )


def test_auto_solver_stacked_single_runtime_branch():
    """inverse_solver='auto' on the stacked engine runs the batched
    Cholesky behind ONE scalar runtime cond per device-local block
    (factors.batched_damped_inverse_auto_info) — no construction-time
    TPUPerformanceWarning anymore, and on well-conditioned factors the
    preconditioned grads match the pure newton_schulz engine."""
    import warnings as warnings_mod

    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter(
            'error', kfac_tpu.warnings.TPUPerformanceWarning
        )
        out = {}
        for solver in ('auto', 'newton_schulz'):
            mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
                0.5, compute_method='inverse', kl_clip=None, damping=0.01,
                inverse_solver=solver,
            )
            cap = kfac_tpu.CurvatureCapture(reg)
            (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(
                params, batch
            )
            state, pg = jax.jit(dk.step)(dk.init(), grads, stats)
            out[solver] = pg
    for name in out['auto']:
        np.testing.assert_allclose(
            np.asarray(out['auto'][name]['kernel']),
            np.asarray(out['newton_schulz'][name]['kernel']),
            rtol=1e-4, atol=1e-6,
        )


def test_size_classes_collapse_heterogeneous_shapes_exactly():
    """Heterogeneous factor dims collapse into few class buckets (the
    execution-side load balancing of the reference's greedy assignment,
    kfac/assignment.py:227-319) and the identity/zero padding is EXACT:
    preconditioned grads match a granularity=1 (exact-dims) run."""
    import flax.linen as nn

    from kfac_tpu.parallel.kaisa import size_class

    # classing rules: powers of two below the granularity, multiples above
    assert size_class(7, 128) == 8
    assert size_class(8, 128) == 8
    assert size_class(100, 128) == 128
    assert size_class(129, 128) == 256
    assert size_class(513, 256) == 768
    assert size_class(513, 1) == 513  # disabled
    # non-power-of-two granularity: the sub-granularity power-of-two class
    # is capped at the granularity (65 -> 100, not 128 > the class 100 that
    # a dim of exactly 100 gets)
    assert size_class(65, 100) == 100
    assert size_class(7, 100) == 8

    class Hetero(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(19, name='l0')(x))
            x = nn.relu(nn.Dense(23, name='l1')(x))
            x = nn.relu(nn.Dense(21, name='l2')(x))
            return nn.Dense(5, name='l3')(x)

    m = Hetero()
    x = jax.random.normal(jax.random.PRNGKey(0), (WORLD * 4, 13))
    y = jax.random.normal(jax.random.PRNGKey(1), (WORLD * 4, 5))
    params = m.init(jax.random.PRNGKey(2), x)['params']
    reg = kfac_tpu.register_model(m, x)

    def loss_fn(params, batch):
        xb, yb = batch
        return jnp.mean((m.apply({'params': params}, xb) - yb) ** 2)

    mesh = kaisa_mesh(grad_worker_fraction=1.0)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))

    def run(granularity):
        cfg = kfac_tpu.KFACPreconditioner(
            registry=reg, damping=0.01, kl_clip=0.001,
            bucket_granularity=granularity,
        )
        dk = DistributedKFAC(config=cfg, mesh=mesh)
        state, pgrads = jax.jit(dk.step)(dk.init(), grads, stats)
        return dk, pgrads

    dk_cls, pg_cls = run(128)
    dk_exact, pg_exact = run(1)
    # 4 distinct (da, dg) pairs collapse into 2 class buckets:
    # (14,19)->(16,32)... wait-free check by count
    assert len(dk_cls.buckets) < len(dk_exact.buckets)
    for a, b in zip(
        jax.tree_util.tree_leaves(pg_cls), jax.tree_util.tree_leaves(pg_exact)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        )


def test_inverse_residuals_out_of_band_monitoring():
    """The stacked INVERSE engine exposes per-slot
    damped-inverse residuals out-of-band; benign factors sit far below
    the NS fallback threshold, EIGEN configs refuse the query."""
    from kfac_tpu.ops import factors as factors_lib

    mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
        1.0, damping=0.01, compute_method='inverse',
        inverse_solver='newton_schulz',
        factor_update_steps=1, inv_update_steps=1,
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    runner = cap.value_stats_and_grad(loss_fn)
    state = dk.init()
    (l, _), grads, stats = runner(params, batch)
    state, _ = dk.step(state, grads, stats)
    res = jax.jit(dk.inverse_residuals)(state)
    assert set(res) == {'a', 'g'}
    for side in ('a', 'g'):
        assert res[side], 'residuals must cover every bucket'
        for key, r in res[side].items():
            r = np.asarray(r)
            assert r.ndim == 1 and np.all(np.isfinite(r))
            assert np.all(r < factors_lib.NS_FALLBACK_RESIDUAL), (key, r)

    # EIGEN method: the query is meaningless and must say so
    mesh2, m2, p2, b2, reg2, cfg2, dk2, lf2 = _setup(
        1.0, compute_method='eigen',
    )
    with pytest.raises(ValueError, match='INVERSE'):
        dk2.inverse_residuals(dk2.init())


def test_inverse_residuals_use_inversion_time_damping():
    """A scheduled damping must not poison the monitor: residuals measure
    the inverse against the damping it was BUILT with (state.inv_damping),
    not the current step's value — otherwise a perfect inverse shows a
    spurious |delta_damping| * ||F_inv|| floor."""
    from kfac_tpu.ops import factors as factors_lib

    # damping drops 100x right after the inversion step
    sched = lambda step: jnp.where(step < 1, 1.0, 0.01)
    mesh, m, params, batch, reg, cfg, dk, loss_fn = _setup(
        1.0, damping=sched, compute_method='inverse',
        inverse_solver='newton_schulz',
        factor_update_steps=1, inv_update_steps=10,  # invert at step 0 only
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    runner = cap.value_stats_and_grad(loss_fn)
    state = dk.init()
    for _ in range(3):  # step counter now well past the inversion
        (l, _), grads, stats = runner(params, batch)
        state, _ = dk.step(state, grads, stats)
    assert float(state.inv_damping) == 1.0  # built at step 0
    res = dk.inverse_residuals(state)
    worst = max(
        float(np.asarray(r).max())
        for side in res.values()
        for r in side.values()
    )
    assert worst < factors_lib.NS_FALLBACK_RESIDUAL, worst
