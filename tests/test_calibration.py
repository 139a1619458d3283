"""Measurement-truth layer, host side: calibration.

Two surfaces, both CPU-runnable:

- :class:`kfac_tpu.observability.calibration.CalibrationMonitor`:
  residual-ratio math (warmup, rolling window, direction-free fold
  error), the ``calib/*`` record/annotate emission contract, the
  rotating :class:`~kfac_tpu.observability.sinks.JSONLWriter`, and the
  rate-limited logger's ``calib/model_error`` headline;
- observing and annotating every step leaves the jit cache at one entry
  on both engines (host-side only).
"""

import json
import os

import jax
import pytest

import kfac_tpu
from kfac_tpu.autotune import search as search_lib
from kfac_tpu.observability import calibration
from kfac_tpu.observability.sinks import JSONLWriter, RateLimitedLogger
from kfac_tpu.warnings import reset_layout_warnings
from testing import compile_pins, models

WORLD = 8


@pytest.fixture(autouse=True)
def _clean_warning_state():
    reset_layout_warnings()
    yield
    reset_layout_warnings()


# ------------------------------------------------------ JSONL rotation


def _lines(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_jsonl_rotation_off_by_default(tmp_path):
    path = tmp_path / 'metrics.jsonl'
    with JSONLWriter(path) as w:
        for i in range(50):
            w.write({'step': i, 'pad': 'x' * 64})
    assert len(_lines(path)) == 50
    assert not os.path.exists(f'{path}.1')


def test_jsonl_rotation_shifts_and_caps(tmp_path):
    path = str(tmp_path / 'metrics.jsonl')
    rec = {'step': 0, 'pad': 'x' * 40}
    size = len(json.dumps(rec, sort_keys=True)) + 1
    # room for exactly two records per file
    with JSONLWriter(path, max_bytes=2 * size + 1, max_files=2) as w:
        for i in range(9):
            w.write({'step': i, 'pad': 'x' * 40})
    # newest records in the active file, shifted history behind it,
    # oldest files deleted at the max_files cap
    assert [r['step'] for r in _lines(path)] == [8]
    assert [r['step'] for r in _lines(f'{path}.1')] == [6, 7]
    assert [r['step'] for r in _lines(f'{path}.2')] == [4, 5]
    assert not os.path.exists(f'{path}.3')


def test_jsonl_rotation_never_splits_a_record(tmp_path):
    path = str(tmp_path / 'metrics.jsonl')
    with JSONLWriter(path, max_bytes=64, max_files=4) as w:
        for i in range(12):
            w.write({'step': i, 'pad': 'y' * (i * 7)})
    # every surviving line — in every generation — parses whole, and the
    # step sequence across generations is a contiguous suffix
    steps = []
    for suffix in ('.4', '.3', '.2', '.1', ''):
        f = path + suffix
        if os.path.exists(f):
            steps.extend(r['step'] for r in _lines(f))
    assert steps == list(range(12 - len(steps), 12))


def test_jsonl_oversized_record_written_whole(tmp_path):
    path = str(tmp_path / 'metrics.jsonl')
    with JSONLWriter(path, max_bytes=16, max_files=2) as w:
        w.write({'huge': 'z' * 200})
    assert _lines(path) == [{'huge': 'z' * 200}]


def test_jsonl_rotation_validation(tmp_path):
    with pytest.raises(ValueError, match='max_bytes'):
        JSONLWriter(tmp_path / 'a.jsonl', max_bytes=-1)
    with pytest.raises(ValueError, match='max_files'):
        JSONLWriter(tmp_path / 'a.jsonl', max_files=0)


# -------------------------------------------------- calibration monitor


def test_calibration_config_validation():
    cfg = calibration.CalibrationConfig()
    assert (cfg.window, cfg.warmup_steps, cfg.prefix) == (32, 3, 'calib')
    with pytest.raises(ValueError, match='window'):
        calibration.CalibrationConfig(window=0)
    with pytest.raises(ValueError, match='warmup_steps'):
        calibration.CalibrationConfig(warmup_steps=-1)


def test_monitor_rejects_bad_predictions():
    with pytest.raises(ValueError, match='predicted_step_s'):
        calibration.CalibrationMonitor(0.0)
    # a non-positive spike prediction just disables the spike channel
    mon = calibration.CalibrationMonitor(0.01, refresh_spike_s=0.0)
    assert mon.refresh_spike_s is None
    assert mon.observe_spike(1.0) is None


def test_monitor_warmup_and_empty_record():
    cfg = calibration.CalibrationConfig(warmup_steps=2)
    mon = calibration.CalibrationMonitor(0.01, config=cfg)
    assert mon.record() == {}
    assert mon.observe_step(0.02) is None
    assert mon.observe_step(0.02) is None
    assert mon.record() == {}  # still no evidence
    assert mon.model_error() == 1.0  # idle monitor never looks drifted
    assert mon.observe_step(0.02) == pytest.approx(2.0)
    assert mon.record() != {}


def test_monitor_residual_math_and_fold_symmetry():
    cfg = calibration.CalibrationConfig(warmup_steps=0, window=8)
    mon = calibration.CalibrationMonitor(0.01, config=cfg)
    for _ in range(3):
        mon.observe_step(0.02)
    assert mon.step_ratio() == pytest.approx(2.0)
    assert mon.model_error() == pytest.approx(2.0)
    # a 2x-pessimistic model reads the same fold error
    pess = calibration.CalibrationMonitor(0.01, config=cfg)
    pess.observe_step(0.005)
    assert pess.step_ratio() == pytest.approx(0.5)
    assert pess.model_error() == pytest.approx(2.0)


def test_monitor_rolling_window_forgets():
    cfg = calibration.CalibrationConfig(warmup_steps=0, window=2)
    mon = calibration.CalibrationMonitor(1.0, config=cfg)
    mon.observe_step(1.0)
    mon.observe_step(1.0)
    mon.observe_step(3.0)
    mon.observe_step(3.0)
    assert mon.step_ratio() == pytest.approx(3.0)


def test_monitor_rejects_nonfinite_and_nonpositive():
    cfg = calibration.CalibrationConfig(warmup_steps=0)
    mon = calibration.CalibrationMonitor(0.01, config=cfg)
    for bad in (float('nan'), float('inf'), 0.0, -1.0):
        assert mon.observe_step(bad) is None
    assert mon.step_ratio() is None


def test_monitor_record_and_annotate_contract():
    cfg = calibration.CalibrationConfig(warmup_steps=0, window=4)
    mon = calibration.CalibrationMonitor(0.01, refresh_spike_s=0.5,
                                         config=cfg)
    mon.observe_step(0.02)
    mon.observe_step(0.02)
    assert mon.observe_spike(1.0) == pytest.approx(2.0)
    rec = mon.record()
    assert set(rec) == {
        'calib/predicted_step_s', 'calib/measured_step_s',
        'calib/step_ratio', 'calib/model_error', 'calib/n',
        'calib/predicted_spike_s', 'calib/spike_ratio',
    }
    assert rec['calib/predicted_step_s'] == pytest.approx(0.01)
    assert rec['calib/measured_step_s'] == pytest.approx(0.02)
    assert rec['calib/step_ratio'] == pytest.approx(2.0)
    assert rec['calib/model_error'] == pytest.approx(2.0)
    assert rec['calib/n'] == 2.0
    assert rec['calib/spike_ratio'] == pytest.approx(2.0)
    # annotate folds the same keys into a drained record, in place
    drained = {'step': 5, 'loss': 0.1}
    out = mon.annotate(drained)
    assert out is drained
    assert drained['calib/model_error'] == pytest.approx(2.0)
    assert drained['step'] == 5
    # custom prefix renames the metric namespace...
    alt = calibration.CalibrationMonitor(
        0.01, config=calibration.CalibrationConfig(
            warmup_steps=0, prefix='cm'))
    alt.observe_step(0.02)
    assert 'cm/model_error' in alt.record()


def test_monitor_from_real_tuned_plan():
    _, _, _, bare, _ = _setup()
    plan = _comm_opt_plan(bare)
    mon = calibration.CalibrationMonitor.from_plan(plan)
    assert mon.predicted_step_s == pytest.approx(
        plan.winner['predicted_step_s'])
    assert mon.predicted_step_s > 0
    row = calibration._winner_row(plan)
    assert row and row.get('knobs') == plan.knobs
    spike = row.get('refresh_spike_s')
    if spike is not None and spike > 0:
        assert mon.refresh_spike_s == pytest.approx(spike)
    else:
        assert mon.refresh_spike_s is None
    # plan dicts coerce through as_plan too
    mon2 = calibration.CalibrationMonitor.from_plan(plan.to_json())
    assert mon2.predicted_step_s == pytest.approx(mon.predicted_step_s)


def test_model_error_of_a_drifted_and_of_an_idle_monitor():
    cfg = calibration.CalibrationConfig(warmup_steps=0)
    mon = calibration.CalibrationMonitor(0.01, config=cfg)
    for _ in range(2):
        mon.observe_step(0.02)
    assert mon.model_error() == pytest.approx(2.0)
    # the fold is direction-free: twice too fast reads like twice too slow
    fast = calibration.CalibrationMonitor(0.01, config=cfg)
    fast.observe_step(0.005)
    assert fast.model_error() == pytest.approx(2.0)
    # and an uncalibrated monitor reads no drift
    idle = calibration.CalibrationMonitor(0.01, config=cfg)
    assert idle.model_error() == 1.0
    assert idle.record() == {}


def test_annotate_stamps_every_drained_record():
    cfg = calibration.CalibrationConfig(warmup_steps=0)
    mon = calibration.CalibrationMonitor(0.01, config=cfg)
    mon.observe_step(0.02)
    records = [mon.annotate(r) for r in ({'step': 1}, {'step': 2})]
    assert [r['step'] for r in records] == [1, 2]
    for rec in records:
        assert rec['calib/model_error'] == pytest.approx(2.0)
        assert rec['calib/step_ratio'] == pytest.approx(2.0)


def test_rate_limited_logger_headlines_model_error(caplog):
    assert 'calib/model_error' in RateLimitedLogger._HEADLINE
    rl = RateLimitedLogger(min_interval_s=0.0)
    with caplog.at_level('INFO'):
        assert rl.emit({'step': 3, 'calib/model_error': 2.0,
                        'calib/step_ratio': 2.0})
    assert 'calib/model_error=2' in caplog.text


def test_monitor_records_flow_through_jsonl(tmp_path):
    cfg = calibration.CalibrationConfig(warmup_steps=0)
    mon = calibration.CalibrationMonitor(0.01, config=cfg)
    path = tmp_path / 'metrics.jsonl'
    with JSONLWriter(path) as w:
        w.write(mon.record())  # empty pre-evidence record is a no-op
        mon.observe_step(0.02)
        w.write(mon.record())
    lines = _lines(path)
    assert len(lines) == 1
    assert lines[0]['calib/model_error'] == pytest.approx(2.0)


# ------------------------------------------------------- a tuned plan


def _setup():
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)

    def loss_fn(p, model_state, batch):
        bx, by = batch
        pred = m.apply({'params': p}, bx)
        return jax.numpy.mean((pred - by) ** 2), model_state

    def bare():
        return kfac_tpu.KFACPreconditioner(
            registry=reg, kl_clip=None, damping=1e-3, flight=8
        )

    return m, (x, y), params, bare, loss_fn


def _comm_opt_plan(bare):
    return search_lib.autotune(
        bare(), measure=False, world=WORLD,
        fractions=(1.0,), granularities=(1,),
    )


def test_memory_residual_reads_like_a_time_residual():
    """A 2x XLA-memory residual with step timings spot-on is a 2x model
    error; a monitor whose plan's memory and timings both hold reads
    none."""
    _, _, _, bare, _ = _setup()
    plan = _comm_opt_plan(bare)
    ccfg = calibration.CalibrationConfig(warmup_steps=0, window=4)

    drifted = calibration.CalibrationMonitor.from_plan(plan, ccfg)
    calm = calibration.CalibrationMonitor.from_plan(plan, ccfg)
    assert drifted.predicted_mem_bytes is not None  # plan carries memory
    for _ in range(4):
        drifted.observe_step(drifted.predicted_step_s)
        drifted.observe_memory(2.0 * drifted.predicted_mem_bytes)
        calm.observe_step(calm.predicted_step_s)
        calm.observe_memory(calm.predicted_mem_bytes)
    assert drifted.step_ratio() == pytest.approx(1.0)
    assert drifted.model_error() == pytest.approx(2.0)  # memory channel
    assert calm.model_error() == pytest.approx(1.0)


# ------------------------------------------------- no-recompile pinning


def _observe_loop(kfac_like, run, params, batch, monitor, n=5):
    state = kfac_like.init()
    step = compile_pins.watched_jit(kfac_like.step)
    for _ in range(n):
        (_, _), grads, stats = run(params, batch)
        state, _ = step(state, grads, stats)
        monitor.observe_step(0.02)
        monitor.annotate({'step': 1})
    return step


def test_calibration_is_jit_invisible_dense():
    """Observing/annotating every step is purely host-side: one cache
    entry, exactly like an uninstrumented run."""
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(registry=reg, metrics=True)
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m))
    mon = calibration.CalibrationMonitor(
        0.01, config=calibration.CalibrationConfig(warmup_steps=0))
    step = _observe_loop(kfac, run, params, (x, y), mon)
    compile_pins.assert_compiled_once(step)
    assert mon.model_error() == pytest.approx(2.0)


def test_calibration_is_jit_invisible_distributed():
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, metrics=True)
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m))
    mon = calibration.CalibrationMonitor(
        0.01, config=calibration.CalibrationConfig(warmup_steps=0))
    step = _observe_loop(dk, run, params, (x, y), mon, n=3)
    compile_pins.assert_compiled_once(step)
