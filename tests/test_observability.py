"""Telemetry spine tests: in-jit metrics, sinks, comms accounting, lint.

Pins the contracts docs/OBSERVABILITY.md documents: the metric-key schema
is identical across both engines and both KAISA stat transports, metrics
add zero recompilations after step 1, the collector is a strict no-op
when disabled, and every public jitted engine entry point carries a named
scope (tools/lint_named_scopes.py).
"""

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import checkpoint, health, tracing
from kfac_tpu.observability import comms as comms_lib
from kfac_tpu.observability import metrics as metrics_lib
from kfac_tpu.observability import profiler as profiler_lib
from kfac_tpu.observability import sinks
from kfac_tpu.parallel import collectives
from testing import compile_pins, models


def _dense_setup(**cfg_kw):
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(registry=reg, **cfg_kw)
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(models.mse_loss(m))
    return m, params, (x, y), reg, kfac, run


def _run_steps(kfac, run, params, batch, n):
    state = kfac.init()
    step = compile_pins.watched_jit(kfac.step)
    for _ in range(n):
        (_, _), grads, stats = run(params, batch)
        state, _ = step(state, grads, stats)
    return state, step


# ------------------------------------------------------------ schema: dense


@pytest.mark.parametrize('method', ['eigen', 'inverse'])
def test_metric_schema_dense(method):
    """Drained keys == documented schema, for both compute methods."""
    _, params, batch, reg, kfac, run = _dense_setup(
        metrics=True, compute_method=method, kl_clip=0.001
    )
    state, _ = _run_steps(kfac, run, params, batch, 3)
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(state)
    expected = set(
        metrics_lib.metric_keys(kfac.metrics, list(reg.layers))
    ) | {'step'}
    assert set(rec) == expected
    assert rec['step'] == 3
    for k, v in rec.items():
        assert np.isfinite(v), k
    # factors/inverses refreshed this step (cadence 1): staleness is 0,
    # Gershgorin bounds bracket a PSD EMA factor
    for n in reg.names():
        assert rec[f'factor_staleness/{n}'] == 0.0
        assert rec[f'inv_staleness/{n}'] == 0.0
        assert rec[f'factor_lmax/a/{n}'] >= rec[f'factor_lmin/a/{n}']
        assert rec[f'grad_norm/{n}'] > 0.0
        assert rec[f'precond_grad_norm/{n}'] > 0.0
        assert rec[f'damping_eff/{n}'] > 0.0


def test_metrics_disabled_state_and_drain_noop():
    _, params, batch, _, kfac, run = _dense_setup(metrics=None)
    state, _ = _run_steps(kfac, run, params, batch, 1)
    assert state.metrics is None
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(state)
    assert rec == {}


def test_metrics_no_recompilation_across_steps():
    """The static key schema compiles the step exactly once."""
    _, params, batch, _, kfac, run = _dense_setup(metrics=True)
    _, step = _run_steps(kfac, run, params, batch, 5)
    compile_pins.assert_compiled_once(step)


def test_staleness_tracks_update_cadence():
    _, params, batch, reg, kfac, run = _dense_setup(
        metrics=True, factor_update_steps=2, inv_update_steps=2
    )
    state, _ = _run_steps(kfac, run, params, batch, 4)
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(state)
    # updates ran at steps 0 and 2 (internal step counter), so after 4
    # steps the last accepted update is 1 step old
    for n in reg.names():
        assert rec[f'factor_staleness/{n}'] == 1.0
        assert rec[f'inv_staleness/{n}'] == 1.0


def test_kl_clip_disabled_reports_unit_scale():
    _, params, batch, _, kfac, run = _dense_setup(metrics=True, kl_clip=None)
    state, _ = _run_steps(kfac, run, params, batch, 2)
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(state)
    assert rec['kl_clip_scale'] == 1.0


def test_collector_folds_health_counters():
    _, params, batch, reg, kfac, run = _dense_setup(metrics=True, health=True)
    state, _ = _run_steps(kfac, run, params, batch, 2)
    rec = kfac_tpu.MetricsCollector(include_health=True).drain(state)
    expected_health = set(health.health_metric_keys(reg.names()))
    assert expected_health <= set(rec)
    assert rec['health/skipped_steps'] == 0


def test_health_metric_keys_match_counters():
    """The documented health/* schema is exactly what drains emit."""
    _, params, batch, reg, kfac, run = _dense_setup(health=True)
    state, _ = _run_steps(kfac, run, params, batch, 1)
    counters = tracing.health_counters(state)
    assert set(counters) == set(health.health_metric_keys(reg.names()))


@pytest.mark.parametrize('ephemeral', ['metrics', 'flight'])
@pytest.mark.parametrize('engine', ['dense', 'kaisa'])
def test_checkpoint_roundtrip_ignores_metrics(tmp_path, engine, ephemeral):
    """Metrics state and the flight-recorder ring are ephemeral: a
    checkpoint holds neither, and restore rebuilds both fresh."""
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    _, params, batch, _, kfac, run = _dense_setup(
        kl_clip=0.001, **{ephemeral: True}
    )
    if engine == 'kaisa':
        kfac = DistributedKFAC(
            config=kfac, mesh=kaisa_mesh(grad_worker_fraction=0.5)
        )
    state, _ = _run_steps(kfac, run, params, batch, 2)
    assert set(checkpoint.durable_state(state)) == {'step', 'a', 'g'}
    path = str(tmp_path / 'ckpt')
    checkpoint.save(path, state)
    restored, _ = checkpoint.restore(path, kfac)
    assert int(restored.step) == 2
    # freshly initialized, not the saved live values (flight=True turns
    # the metrics on too: the ring records that schema)
    assert float(restored.metrics.as_dict()['kl_clip_scale']) == 1.0
    assert float(state.metrics.as_dict()['kl_clip_scale']) < 1.0
    if ephemeral == 'flight':
        assert int((state.flight.steps >= 0).sum()) == 2
        assert int((restored.flight.steps >= 0).sum()) == 0
    else:
        assert restored.flight is None


# -------------------------------------------------------------- config edges


def test_metrics_config_normalization():
    _, _, _, reg, kfac_on, _ = _dense_setup(metrics=True)
    assert isinstance(kfac_on.metrics, kfac_tpu.MetricsConfig)
    kfac_off = kfac_tpu.KFACPreconditioner(registry=reg, metrics=False)
    assert kfac_off.metrics is None
    with pytest.raises(TypeError):
        kfac_tpu.KFACPreconditioner(registry=reg, metrics='yes')


def test_metrics_config_rejects_all_disabled():
    with pytest.raises(ValueError):
        kfac_tpu.MetricsConfig(
            grad_norms=False, factor_bounds=False, staleness=False
        )


def test_partial_schema_drops_family_keys():
    _, params, batch, reg, kfac, run = _dense_setup(
        metrics=kfac_tpu.MetricsConfig(grad_norms=False, factor_bounds=False)
    )
    state, _ = _run_steps(kfac, run, params, batch, 1)
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(state)
    assert not any(k.startswith('grad_norm/') for k in rec)
    assert not any(k.startswith('factor_lmax/') for k in rec)
    for n in reg.names():
        assert f'factor_staleness/{n}' in rec


def test_gershgorin_bounds_reference_values():
    lmin, lmax = metrics_lib.gershgorin_bounds(jnp.eye(4))
    assert float(lmin) == 1.0 and float(lmax) == 1.0
    m = jnp.array([[2.0, 1.0], [1.0, 3.0]])
    lmin, lmax = metrics_lib.gershgorin_bounds(m)
    assert float(lmin) == 1.0 and float(lmax) == 4.0
    # stacked: bounds over the stack
    lmin, lmax = metrics_lib.gershgorin_bounds(jnp.stack([jnp.eye(2), m]))
    assert float(lmin) == 1.0 and float(lmax) == 4.0


# ------------------------------------------------------- schema: distributed


@pytest.mark.parametrize('transport', ['allreduce', 'allreduce_bucketed'])
def test_metric_schema_distributed(transport):
    """Same drained schema on the sharded engine, both stat transports."""
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, metrics=True, kl_clip=0.001,
        allreduce_method=transport,
    )
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(models.mse_loss(m))
    state = dk.init()
    step = compile_pins.watched_jit(dk.step)
    for _ in range(2):
        (_, _), grads, stats = run(params, (x, y))
        state, _ = step(state, grads, stats)
    compile_pins.assert_compiled_once(step)
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(state)
    expected = set(
        metrics_lib.metric_keys(cfg.metrics, list(reg.layers))
    ) | {'step'}
    assert set(rec) == expected
    for k, v in rec.items():
        assert np.isfinite(v), k
    for n in reg.names():
        assert rec[f'grad_norm/{n}'] > 0.0
        assert rec[f'factor_lmax/a/{n}'] >= rec[f'factor_lmin/a/{n}']


def test_distributed_metrics_match_dense():
    """Per-layer metric values agree with the dense engine on the same
    stats — the telemetry reads the same math both ways."""
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    mesh = kaisa_mesh(grad_worker_fraction=1.0)
    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, metrics=True, kl_clip=0.001, damping=0.01
    )
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    cap = kfac_tpu.CurvatureCapture(reg)
    (_, _), grads, stats = cap.value_stats_and_grad(
        models.mse_loss(m))(params, (x, y))

    ref_state, _ = cfg.step(cfg.init(), grads, stats)
    dist_state, _ = jax.jit(dk.step)(dk.init(), grads, stats)
    ref = kfac_tpu.MetricsCollector(include_health=False).drain(ref_state)
    dist = kfac_tpu.MetricsCollector(include_health=False).drain(dist_state)
    assert set(ref) == set(dist)
    for k in ref:
        np.testing.assert_allclose(ref[k], dist[k], rtol=5e-3, atol=1e-6)


# ------------------------------------------------------------ sinks


def test_jsonl_writer_roundtrip(tmp_path):
    path = tmp_path / 'metrics.jsonl'
    with sinks.JSONLWriter(path, append=False) as w:
        w.write({'step': np.int32(1), 'x': np.float32(0.5)})
        w.write({})  # empty drain: no line
        w.write({'step': 2, 'x': 0.25})
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert rows == [{'step': 1, 'x': 0.5}, {'step': 2, 'x': 0.25}]
    # append mode extends, write-after-close raises
    w2 = sinks.JSONLWriter(path)
    w2.write({'step': 3})
    w2.close()
    with pytest.raises(ValueError):
        w2.write({'step': 4})
    assert len(path.read_text().splitlines()) == 3


def test_rate_limited_logger(caplog):
    rl = sinks.RateLimitedLogger(min_interval_s=3600.0)
    with caplog.at_level(logging.INFO, logger='kfac_tpu.observability'):
        assert rl.emit({'step': 1, 'kl_clip_scale': 0.5, 'extra': 1.0})
        assert not rl.emit({'step': 2})  # inside the interval
    assert not rl.emit({})  # empty: never logs
    assert len(caplog.records) == 1
    assert 'kl_clip_scale' in caplog.records[0].message


# ------------------------------------------------------------ tracing


def test_trace_sync_blocks_full_pytree():
    tracing.clear_trace()

    @tracing.trace(sync=True, name='pytree_work')
    def work(x):
        return {'a': x * 2, 'b': (x + 1, jnp.sum(x))}

    out = work(jnp.arange(8.0))
    assert float(out['b'][1]) == 28.0
    assert tracing.get_trace()['pytree_work'] > 0
    tracing.clear_trace()


def test_force_sync_toggle():
    assert not tracing.sync_forced()
    tracing.force_sync(True)
    try:
        assert tracing.sync_forced()

        @tracing.trace(name='forced')
        def f(x):
            return x + 1

        f(jnp.zeros(4))
        assert 'forced' in tracing.get_trace()
    finally:
        tracing.force_sync(False)
        tracing.clear_trace()
    assert not tracing.sync_forced()


def test_trainer_step_paths_traced():
    """Trainer.step lands in the tracing table under its scope name."""
    import optax

    m, params, batch, reg, kfac, _ = _dense_setup(metrics=True)
    trainer = kfac_tpu.Trainer(
        loss_fn=lambda p, ms, b: (models.mse_loss(m)(p, b), ms),
        optimizer=optax.sgd(0.05),
        kfac=kfac,
    )
    tracing.clear_trace()
    tstate = trainer.init(params)
    tstate, _ = trainer.step(tstate, batch)
    assert 'trainer/step' in tracing.get_trace()
    # the collector unwraps TrainState.kfac_state
    rec = kfac_tpu.MetricsCollector(include_health=False).drain(tstate)
    assert rec['step'] == 1
    tracing.clear_trace()


def test_lint_named_scopes_clean():
    import sys
    sys.path.insert(0, 'tools')
    try:
        import lint_named_scopes
    finally:
        sys.path.pop(0)
    assert lint_named_scopes.check() == []


# ------------------------------------------------------------ comms


def _dist_engine(transport, fraction=0.5, **cfg_kw):
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    mesh = kaisa_mesh(grad_worker_fraction=fraction)
    m = models.TinyModel(hidden=8, out=4)
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, allreduce_method=transport, **cfg_kw
    )
    return DistributedKFAC(config=cfg, mesh=mesh)


def test_comms_report_transports():
    dense = _dist_engine('allreduce').comms_report()
    buck = _dist_engine('allreduce_bucketed').comms_report()
    assert dense['stat_transport']['method'] == 'ALLREDUCE'
    assert buck['stat_transport']['method'] == 'ALLREDUCE_BUCKETED'
    # triangles beat dense bytes; savings consistent
    assert buck['stat_transport']['bytes'] < buck['stat_transport']['dense_bytes']
    assert buck['stat_transport']['savings'] > 0
    for rep in (dense, buck):
        assert rep['grad_broadcast_bytes'] > 0
        assert rep['decomp_reshard_bytes'] > 0
        assert rep['grad_worker_fraction'] == 0.5
        totals = rep['padding_totals']
        per_class = rep['padding']
        assert totals['resident_bytes'] == sum(
            p['resident_bytes'] for p in per_class.values())


@pytest.mark.parametrize('transport', ['allreduce', 'allreduce_bucketed'])
@pytest.mark.parametrize('fraction', [1.0, 0.5, 0.125])
def test_wire_bytes_are_the_raw_bytes(fraction, transport):
    """The stat transport ships the payload as it is, under every
    strategy and both transports: ``wire_bytes`` (what the IR tier's
    KFL205 holds the traced program's collective bytes to), ``raw_bytes``
    and ``bytes`` are one number, chunk by chunk too, and the report has
    no sub-report of a transport or a store that is not there."""
    dk = _dist_engine(transport, fraction=fraction)
    rep = dk.comms_report()
    st = rep['stat_transport']
    assert st['wire_bytes'] == st['raw_bytes'] == st['bytes'] > 0
    assert st['wire_dtype'] == 'float32'
    assert 'compression' not in st and 'offload' not in rep
    for c in st['chunks']:
        assert c['wire_bytes'] == c['raw_bytes'] == c['bytes']
        assert c['bytes'] == c['elements'] * 4
    if transport == 'allreduce_bucketed':
        assert sum(c['bytes'] for c in st['chunks']) == st['wire_bytes']
        # upper triangles of the class-dim rows of every stored factor
        assert st['wire_bytes'] == 4 * sum(
            sb.d * (sb.d + 1) // 2 * len(sb.layers)
            for sb in dk.a_store + dk.g_store
        )
    else:
        assert st['chunks'] == []
        assert st['wire_bytes'] == 4 * sum(
            d * d for sb in dk.a_store + dk.g_store for d in sb.dims
        )


def test_comms_report_respects_bucket_cap():
    dk = _dist_engine('allreduce_bucketed', allreduce_bucket_cap_mb=1e-4)
    chunks = dk.comms_report()['stat_transport']['chunks']
    assert len(chunks) > 1
    # the cap is honored except for single oversized tensors
    for c in chunks:
        assert c['tensors'] == 1 or c['bytes'] <= 100


def test_plan_chunks_matches_concat_flat_chunked():
    tensors = [
        jnp.zeros(10, jnp.float32),
        jnp.zeros(300, jnp.bfloat16),
        jnp.zeros(5000, jnp.float32),
        jnp.zeros(7, jnp.float32),
    ]
    specs = [(int(t.size), t.dtype) for t in tensors]
    for cap in (None, 100, 1024, 10_000, 1e9):
        actual = collectives.concat_flat_chunked(tensors, max_bytes=cap)
        plan = collectives.plan_chunks(specs, max_bytes=cap)
        assert len(plan) == len(actual)
        for p, (buf, metas) in zip(plan, actual):
            assert p['tensors'] == len(metas)
            assert p['elements'] == int(buf.size)
            assert p['dtype'] == str(buf.dtype)
            assert p['bytes'] == buf.size * buf.dtype.itemsize


def test_memory_usage_padding_waste_consistent():
    dk = _dist_engine('allreduce')
    state = dk.init()
    usage = dk.memory_usage(state)
    waste = usage['padding_waste']
    per_class = waste['per_class']
    item = jnp.dtype(dk.config.factor_dtype).itemsize
    for side, store in (('a', dk.a_store), ('g', dk.g_store)):
        for sb in store:
            p = per_class[f'{side}/{sb.key}']
            assert (
                p['resident_bytes'] + p['identity_pad_bytes']
                + p['slot_pad_bytes'] == p['total_bytes']
            )
            assert p['total_bytes'] == sb.padded * sb.d * sb.d * item
            assert 0 < p['fill'] <= 1
    assert waste['resident_bytes'] == sum(
        p['resident_bytes'] for p in per_class.values())
    # the waste breakdown rides alongside, not inside, the byte categories
    assert usage['total'] == (
        usage['a_factors'] + usage['g_factors']
        + usage['a_inverses'] + usage['g_inverses']
    )


def test_describe_reports_fill_and_metrics():
    dk = _dist_engine('allreduce', metrics=True)
    d = dk.describe()
    assert 'fill' in d
    assert 'metrics:' in d


# ------------------------------------------------------------ profiler


def test_capture_steps_writes_trace(tmp_path):
    _, params, batch, _, kfac, run = _dense_setup(metrics=True)
    state = kfac.init()
    step = jax.jit(kfac.step)
    carry = {'state': state}

    def one(i):
        (_, _), grads, stats = run(params, batch)
        carry['state'], pg = step(carry['state'], grads, stats)
        return pg

    logdir = tmp_path / 'trace'
    out = profiler_lib.capture_steps(str(logdir), one, steps=2)
    assert out is not None
    assert int(carry['state'].step) == 2
    assert any(logdir.rglob('*')), 'profiler wrote nothing'
