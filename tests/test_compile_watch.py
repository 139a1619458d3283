"""Compile & memory truth tests (docs/OBSERVABILITY.md "Compile &
memory truth").

Pins PR 17's acceptance criteria:

- a shape change on a watched entry emits exactly ONE compile event
  whose fingerprint diff names the changed dimension, and an unchanged
  re-step emits ZERO events — on both engines and both KAISA stat
  transports (the batch-shaped surface is the Trainer step, whose args
  actually carry the batch; the engine ``step`` args are batch-size
  invariant, which the engine test pins directly);
- heartbeat journaling follows ``lowering -> compiling -> done`` with
  the fsync-before-blocking contract, and a subprocess SIGKILLed
  mid-compile (via the ``fault_compile_sleep_s`` injection knob) leaves
  a journal ``tools/kfac_inspect.py`` resolves to a "died compiling X"
  verdict naming the entry and the phase;
- ``memory_usage()`` vs XLA ``memory_analysis()`` parity on CPU is
  recorded as a calibration residual (``observe_memory``), never a hard
  failure;
- all four Trainer step paths count into the engine's watch;
- ``PostmortemWriter`` bundles carry ``compile_events.jsonl`` and
  ``compile_memory.json``;
- watched dispatch leaves the plain jit cache untouched.

Compile budget: the Trainer-paths and bundle tests share module-scope
fixtures (PR-15 convention); the attribution tests build the small
per-case engines they mutate.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import optax
import pytest

import kfac_tpu
from kfac_tpu import health as health_lib
from kfac_tpu import training
from kfac_tpu.observability import calibration
from kfac_tpu.observability import compile_watch as cw
from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh
from testing import faults, models

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), os.pardir, 'tools')
)
import kfac_inspect  # noqa: E402


def _setup(n=32, **cfg_kw):
    cfg_kw.setdefault('compile_watch', True)
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=n)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(registry=reg, **cfg_kw)
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m))
    return m, params, (x, y), reg, kfac, run


def _dist_setup(transport, **cfg_kw):
    cfg_kw.setdefault('compile_watch', True)
    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=64, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, allreduce_method=transport, **cfg_kw)
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m))
    return m, params, (x, y), reg, dk, run


# ------------------------------------------------------------------ config


def test_config_normalization():
    reg = _setup()[3]
    k = kfac_tpu.KFACPreconditioner(registry=reg, compile_watch=True)
    assert isinstance(k.compile_watch, cw.CompileWatchConfig)
    k = kfac_tpu.KFACPreconditioner(registry=reg, compile_watch=False)
    assert k.compile_watch is None
    assert k.compile_watcher() is None
    k = kfac_tpu.KFACPreconditioner(
        registry=reg, compile_watch='/tmp/j.jsonl')
    assert k.compile_watch.journal_path == '/tmp/j.jsonl'
    with pytest.raises(TypeError, match='compile_watch'):
        kfac_tpu.KFACPreconditioner(registry=reg, compile_watch=3.5)
    with pytest.raises(ValueError, match='max_events'):
        cw.CompileWatchConfig(max_events=0)
    with pytest.raises(ValueError, match='fault_compile_sleep_s'):
        cw.CompileWatchConfig(fault_compile_sleep_s=-1.0)


def test_journal_path_env_fallback(monkeypatch, tmp_path):
    """scripts/tpu_session2b.sh arms journaling fleet-wide via the
    KFAC_COMPILE_JOURNAL env var; an explicit path still wins."""
    p = str(tmp_path / 'env.jsonl')
    monkeypatch.setenv('KFAC_COMPILE_JOURNAL', p)
    assert cw.CompileWatchConfig().journal_path == p
    assert cw.CompileWatchConfig(journal_path='/x.jsonl').journal_path == \
        '/x.jsonl'
    monkeypatch.delenv('KFAC_COMPILE_JOURNAL')
    assert cw.CompileWatchConfig().journal_path is None


def test_watched_validation():
    kfac = _setup()[4]
    with pytest.raises(ValueError, match='unknown entry'):
        kfac.watched('nope')
    reg = kfac.registry
    off = kfac_tpu.KFACPreconditioner(registry=reg)
    with pytest.raises(ValueError, match='compile_watch'):
        off.watched('step')


# ------------------------------------------------------------ fingerprints


def test_fingerprint_conventions():
    """Array leaves -> shape+dtype; python int/float -> type only (weak-
    typed under jit, the value does not select the program); bool/str ->
    value; statics -> value."""
    a = jnp.ones((4, 3), jnp.float32)
    fp1 = cw.fingerprint_args((a, 2), {'flag': True})
    fp2 = cw.fingerprint_args((a, 99), {'flag': True})
    assert fp1 == fp2  # int value is not a program selector
    fp3 = cw.fingerprint_args((a, 2), {'flag': False})
    assert fp1 != fp3  # bool value IS
    spec = [v for k, v in fp1.items() if 'flag' not in k and v.get('shape')]
    assert spec[0]['shape'] == [4, 3] and spec[0]['dtype'] == 'float32'
    fps = cw.fingerprint_args((a,), {}, statics={'mode': 'fast'})
    assert fps['static:mode'] == {'static': 'str', 'value': "'fast'"}
    assert cw.fingerprint_key(fp1) != cw.fingerprint_key(fp3)
    assert len(cw.fingerprint_key(fp1)) == 16


def test_fingerprint_diff_names_the_change():
    a = jnp.ones((4, 3), jnp.float32)
    b = jnp.ones((5, 3), jnp.float32)
    old = cw.fingerprint_args((a,), {})
    assert cw.fingerprint_diff(None, old) is None  # first compile
    assert cw.fingerprint_diff(old, dict(old)) == []  # identical print
    diff = cw.fingerprint_diff(old, cw.fingerprint_args((b,), {}))
    assert diff == ['[0][0]: dim 0 4 -> 5']
    diff = cw.fingerprint_diff(
        old, cw.fingerprint_args((a.astype(jnp.bfloat16),), {}))
    assert diff == ["[0][0]: dtype 'float32' -> 'bfloat16'"]
    (line,) = cw.fingerprint_diff(old, cw.fingerprint_args((a, a), {}))
    assert line.startswith('[0][1]: new argument')
    (line,) = cw.fingerprint_diff(cw.fingerprint_args((a, a), {}), old)
    assert line.startswith('[0][1]: argument dropped')


def test_sharding_never_keys_the_dispatch_cache():
    """_program_view strips sharding: repr churn on an unchanged program
    must not look like a different executable key (the distributed
    engine's init-state vs step-output shardings differ in repr while
    the compiled program accepts both)."""
    a = jnp.ones((4, 3), jnp.float32)
    fp = cw.fingerprint_args((a,), {})
    doctored = {
        k: dict(v, sharding='NamedSharding(elsewhere)')
        for k, v in fp.items()
    }
    assert cw.fingerprint_key(cw._program_view(fp)) == \
        cw.fingerprint_key(cw._program_view(doctored))
    assert cw.fingerprint_key(fp) != cw.fingerprint_key(doctored)


# --------------------------------------- attribution: engines + transports


def test_engine_step_compiles_once_dense():
    """Engine step args are batch-size invariant: the whole loop is one
    compile, zero events after it — and the plain jit cache stays EMPTY
    (watched dispatch is AOT; nothing changes for unwatched callers)."""
    _, params, batch, _, kfac, run = _setup()
    step = kfac.watched('step')
    state = kfac.init()
    for _ in range(3):
        (_, _), grads, stats = run(params, batch)
        state, _ = step(state, grads, stats)
    watch = kfac.compile_watcher()
    assert watch.counters() == {'kfac.step': 1}
    assert watch.recompile_count() == 0
    assert len(watch.events) == 1
    assert watch.events[0]['diff'] is None
    assert step._fn._cache_size() == 0  # jit cache unchanged
    assert step.cache_size() == 1


@pytest.mark.parametrize('transport', ['allreduce', 'allreduce_bucketed'])
def test_engine_step_compiles_once_distributed(transport):
    """Same pin on the sharded engine, both stat transports — including
    across the init-state -> step-output resharding, which plain jit
    recompiles for but an AOT executable accepts."""
    _, params, batch, _, dk, run = _dist_setup(transport)
    step = dk.watched('step')
    state = dk.init()
    for _ in range(3):
        (_, _), grads, stats = run(params, batch)
        state, _ = step(state, grads, stats)
    watch = dk.compile_watcher()
    assert watch.counters() == {'dist_kfac.step': 1}
    assert watch.recompile_count() == 0


@pytest.mark.parametrize('flavor', ['dense', 'allreduce',
                                    'allreduce_bucketed'])
def test_batch_shape_change_emits_exactly_one_named_event(flavor):
    """The acceptance headline, on the surface whose args actually carry
    the batch (the Trainer step), for both engines and both transports:
    unchanged re-steps emit zero events; one batch-dim change emits
    exactly one event whose diff names dimension 0 and its sizes."""
    if flavor == 'dense':
        m, params, (x, y), _, eng, _ = _setup()
    else:
        m, params, (x, y), _, eng, _ = _dist_setup(flavor)

    def loss_fn(p, model_state, batch):
        xx, yy = batch
        pred = m.apply({'params': p}, xx)
        return jnp.mean((pred - yy) ** 2), model_state

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=eng)
    watch = eng.compile_watcher()
    state = trainer.init(params)
    state, _ = trainer.step(state, (x, y))          # compile 1
    before = len(watch.events)
    state, _ = trainer.step(state, (x, y))          # unchanged re-step
    assert len(watch.events) == before              # zero new events
    n = x.shape[0]
    state, _ = trainer.step(state, (x[:n - 8], y[:n - 8]))
    new = watch.events[before:]
    assert len(new) == 1                            # exactly one event
    assert new[0]['entry'] == 'trainer.step/with_stats'
    assert any(f'dim 0 {n} -> {n - 8}' in d for d in new[0]['diff'])
    assert watch.recompile_count('trainer.step/with_stats') == 1


# ----------------------------------------------------------- journal + kill


def test_journal_phase_sequence(tmp_path):
    path = tmp_path / 'journal.jsonl'
    # str shorthand: the config carries the journal path
    _, params, batch, _, kfac, run = _setup(compile_watch=str(path))
    assert kfac.compile_watch.journal_path == str(path)
    step = kfac.watched('step')
    state = kfac.init()
    (_, _), grads, stats = run(params, batch)
    state, _ = step(state, grads, stats)
    state, _ = step(state, grads, stats)  # cached: no new records
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r['phase'] for r in recs] == ['lowering', 'compiling', 'done']
    assert all(r['kind'] == 'compile' for r in recs)
    assert all(r['entry'] == 'kfac.step' for r in recs)
    assert all(r['n'] == 1 for r in recs)
    assert all(r['pid'] == os.getpid() for r in recs)
    assert 'fingerprint' in recs[0] and recs[0]['diff'] is None
    assert recs[1]['aot'] is True and recs[1]['lowering_s'] >= 0
    assert recs[2]['compile_s'] >= 0
    ts = [r['t'] for r in recs]
    assert ts == sorted(ts)


_KILL_CHILD = r"""
import os
import jax
import jax.numpy as jnp
from kfac_tpu.observability import compile_watch as cw

watch = cw.CompileWatch(cw.CompileWatchConfig(
    journal_path=os.environ['KFAC_TEST_JOURNAL'],
    fault_compile_sleep_s=120.0,
))
f = watch.wrap('victim.step', jax.jit(lambda a: (a @ a.T).sum()))
f(jnp.ones((8, 8), jnp.float32))   # parent SIGKILLs us inside the sleep
raise SystemExit('unreachable: the fault sleep outlives the test timeout')
"""


def test_sigkill_mid_compile_leaves_resolvable_verdict(tmp_path):
    """The acceptance crash drill: fault-inject a slow compile in a
    subprocess, SIGKILL it between the 'compiling' heartbeat and 'done',
    and resolve the leftover journal — kfac_inspect must name the entry
    and the phase it died in."""
    journal = tmp_path / 'journal.jsonl'
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               KFAC_TEST_JOURNAL=str(journal))
    env.pop('KFAC_COMPILE_JOURNAL', None)
    repo = os.path.join(os.path.dirname(__file__), os.pardir)
    proc = subprocess.Popen(
        [sys.executable, '-c', _KILL_CHILD], env=env, cwd=repo)
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            if journal.exists() and any(
                '"compiling"' in line
                for line in journal.read_text().splitlines()
            ):
                break
            if proc.poll() is not None:
                raise AssertionError(
                    f'child exited early with {proc.returncode}')
            time.sleep(0.05)
        else:
            raise AssertionError('never saw the compiling heartbeat')
        # the fsync contract: the heartbeat is durable BEFORE the
        # blocking phase — the child is now inside the fault sleep
        proc.kill()
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    records = kfac_inspect.load_jsonl(str(journal))
    compile_recs, metric_recs = kfac_inspect.split_compile_records(records)
    assert metric_recs == []
    comp = kfac_inspect.analyze_compile_journal(compile_recs)
    assert comp['verdict'] is not None
    assert "'victim.step'" in comp['verdict']
    assert "'compiling'" in comp['verdict']
    assert 'died compiling' in comp['verdict']
    (flight,) = comp['in_flight']
    assert flight['entry'] == 'victim.step'
    assert flight['phase'] == 'compiling'


# -------------------------------------------------------- memory accounting


@pytest.mark.parametrize('flavor', ['dense', 'distributed'])
def test_memory_report_parity_recorded_as_residual(flavor):
    """CPU backend reports real memory_analysis numbers; the gap against
    the model-side memory_usage() estimate is fed to the calibration
    monitor as a residual — by design NEVER a hard equality (the two
    count different things: persistent factor state vs whole-program
    arg/output/temp bytes)."""
    if flavor == 'dense':
        _, params, batch, _, eng, run = _setup()
    else:
        _, params, batch, _, eng, run = _dist_setup('allreduce')
    step = eng.watched('step')
    state = eng.init()
    (_, _), grads, stats = run(params, batch)
    state, _ = step(state, grads, stats)
    report = eng.compiled_memory_report()
    entry = ('kfac.step' if flavor == 'dense' else 'dist_kfac.step')
    snap = report[entry]
    assert snap['memory'] is not None  # CPU reports stats
    assert snap['hbm_bytes'] and snap['hbm_bytes'] > 0
    assert snap['hbm_bytes'] == cw.measured_hbm_bytes(snap['memory'])
    predicted = float(eng.memory_usage(state)['total'])
    assert predicted > 0
    mon = calibration.CalibrationMonitor(
        0.01, predicted_mem_bytes=predicted)
    mon.observe_memory_report(report)
    ratio = mon.mem_ratio()
    assert ratio is not None and ratio > 0  # residual, not a failure
    rec = mon.record()
    assert rec['calib/predicted_mem_bytes'] == predicted
    assert rec['calib/mem_ratio'] == pytest.approx(ratio)
    assert rec['calib/measured_mem_bytes'] == pytest.approx(
        ratio * predicted)


def test_memory_graceful_none():
    """Where the backend reports nothing, events carry memory=None and
    the report entry degrades — never an exception."""
    assert cw.measured_hbm_bytes(None) is None
    assert cw.measured_hbm_bytes({}) is None
    assert cw.measured_hbm_bytes(
        {'temp_size_in_bytes': 0, 'output_size_in_bytes': 0}) is None
    assert cw._memory_analysis(object()) is None


def test_persistent_cache_counters_singleton():
    c1 = cw.persistent_cache_counters()
    c2 = cw.persistent_cache_counters()
    assert c1 is c2
    snap = c1.snapshot()
    assert set(snap) == {
        'persistent_cache_hits', 'persistent_cache_misses',
        'persistent_cache_dir',
    }
    assert snap['persistent_cache_hits'] >= 0


# ----------------------------------------------------------- trainer paths


@pytest.fixture(scope='module')
def trainer_mod():
    """Module-scope shared-compile Trainer (PR-15 budget convention):
    every Trainer path driven once against one watched dense engine."""
    m, params, (x, y), reg, kfac, _ = _setup(n=32)

    def loss_fn(p, model_state, batch):
        xx, yy = batch
        pred = m.apply({'params': p}, xx)
        return jnp.mean((pred - yy) ** 2), model_state

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac)
    return trainer, params, (x, y), kfac


def test_all_trainer_paths_count_into_engine_watch(trainer_mod):
    trainer, params, (x, y), kfac = trainer_mod
    watch = kfac.compile_watcher()
    state = trainer.init(params)
    for _ in range(2):
        state, _ = trainer.step(state, (x, y))
    batches = (
        jnp.broadcast_to(x, (2,) + x.shape),
        jnp.broadcast_to(y, (2,) + y.shape),
    )
    state, _ = trainer.scan_steps(state, batches)
    state, _ = trainer.step_accumulate(state, [(x, y), (x, y)])
    state, _ = trainer.step_accumulate_scan(state, batches)
    counts = watch.counters()
    assert counts['trainer.step/with_stats'] == 1
    assert counts['trainer.scan_steps'] == 1
    assert counts['trainer.step_accumulate_scan'] == 1
    assert any(k.startswith('trainer.accumulate/') for k in counts)
    assert watch.recompile_count() == 0
    # memory report spans the trainer entries
    report = kfac.compiled_memory_report()
    assert 'trainer.step/with_stats' in report


def test_repeat_paths_zero_new_events(trainer_mod):
    """Re-driving every path after the module fixture warmed them adds
    zero compile events (ordering: runs after the counting test via the
    shared fixture, which is the point — the second pass is free)."""
    trainer, params, (x, y), kfac = trainer_mod
    watch = kfac.compile_watcher()
    state = trainer.init(params)
    state, _ = trainer.step(state, (x, y))
    before = len(watch.events)
    for _ in range(3):
        state, _ = trainer.step(state, (x, y))
    assert len(watch.events) == before
    assert watch.recompile_count() == 0


# -------------------------------------------------------- postmortem bundle


@pytest.mark.faults
def test_postmortem_bundle_carries_compile_events(tmp_path):
    m, params, (x, y), reg, kfac, _ = _setup(
        flight=8, health=health_lib.HealthConfig(warn=False))

    def loss_fn(p, model_state, batch):
        xx, yy = batch
        pred = m.apply({'params': p}, xx)
        return jnp.mean((pred - yy) ** 2), model_state

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac)
    state = trainer.init(params)
    for _ in range(2):
        state, _ = trainer.step(state, (x, y))
    pm = kfac_tpu.PostmortemWriter(tmp_path / 'pms', engine=kfac)
    coll = kfac_tpu.MetricsCollector()
    state, _ = trainer.step(state, faults.poison_batch((x, y), kind='nan'))
    bundle = pm.observe(state, coll.drain(state))
    assert bundle is not None
    events_path = os.path.join(bundle, 'compile_events.jsonl')
    assert os.path.exists(events_path)
    events = [json.loads(line)
              for line in open(events_path).read().splitlines()]
    assert any(e['entry'] == 'trainer.step/with_stats' for e in events)
    mem = json.load(open(os.path.join(bundle, 'compile_memory.json')))
    assert 'trainer.step/with_stats' in mem
    loaded = kfac_inspect.load_bundle(bundle)
    assert loaded['compile_events'] == events
    assert loaded['compile_memory'] == mem


# ------------------------------------------------------------ fast dispatch
#
# PR 45: a call is handed to the executable the entry ran last and is
# fingerprinted only when that cannot be right (a first call, a rejected
# one, changed static / bool / str values, an entry on plain dispatch).


class _Spy:
    """Counts what a steady-state call may not do: the two fingerprint
    functions, and ``str()`` / ``repr()`` of a ``NamedSharding``."""

    def __init__(self, monkeypatch):
        self.fingerprints = 0
        self.keys = 0
        self.sharding_strs = 0
        self.seen_args = []
        real_fp, real_key = cw.fingerprint_args, cw.fingerprint_key
        real_repr = jax.sharding.NamedSharding.__repr__

        def fp(args, kwargs, *a, **kw):
            self.fingerprints += 1
            self.seen_args.append(args)
            return real_fp(args, kwargs, *a, **kw)

        def key(fp_):
            self.keys += 1
            return real_key(fp_)

        def sharding_repr(sharding):
            self.sharding_strs += 1
            return real_repr(sharding)

        monkeypatch.setattr(cw, 'fingerprint_args', fp)
        monkeypatch.setattr(cw, 'fingerprint_key', key)
        monkeypatch.setattr(
            jax.sharding.NamedSharding, '__repr__', sharding_repr)


def _sharded(x, *spec):
    mesh = jax.sharding.Mesh(jax.devices()[:2], ('d',))
    return jax.device_put(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec)))


def _calls(watch, entry):
    c = watch.dispatch_counters()[entry]
    return c['fast'], c['fingerprinted']


def test_steady_state_takes_one_fingerprint_and_no_sharding_str(monkeypatch):
    """N calls of one signature: one fingerprint, one key, one compile
    event, and after the first call no ``str()`` of a sharding: the
    acceptance pin on ``WatchedFunction.__call__`` in steady state."""
    watch = cw.CompileWatch()
    f = watch.wrap('e', jax.jit(lambda t, b: (
        jax.tree.map(lambda x: x + b.sum(), t), b * 2)))
    tree = {f'k{i}': _sharded(jnp.ones((4, 2)), 'd') for i in range(12)}
    b = _sharded(jnp.arange(8.0))
    spy = _Spy(monkeypatch)
    tree, _ = f(tree, b)
    assert (spy.fingerprints, spy.keys) == (1, 1)
    assert spy.sharding_strs >= 13  # the one fingerprint records them
    spy.sharding_strs = 0
    for _ in range(9):
        tree, out = f(tree, b)
    assert (spy.fingerprints, spy.keys, spy.sharding_strs) == (1, 1, 0)
    assert float(tree['k0'][0, 0]) == 1 + 10 * 28.0
    assert float(out[1]) == 2.0
    assert len(watch.events) == 1 and watch.recompile_count() == 0
    assert _calls(watch, 'e') == (9, 1)
    assert f.cache_size() == 1 and len(f.executables()) == 1


def test_shape_change_after_fast_calls_is_one_named_event(monkeypatch):
    watch = cw.CompileWatch()
    f = watch.wrap('e', jax.jit(lambda p, x: (p + x.sum(), x * 2)))
    p = jnp.zeros(())
    for _ in range(4):
        p, _ = f(p, jnp.ones((8, 3)))
    assert _calls(watch, 'e') == (3, 1)
    spy = _Spy(monkeypatch)
    p, out = f(p, jnp.ones((6, 3)))
    assert out.shape == (6, 3) and float(p) == 4 * 24 + 18
    assert spy.fingerprints == 1  # the rejected fast attempt's
    (first, new) = watch.events
    assert first['diff'] is None
    assert new['diff'] == ['[0][1]: dim 0 8 -> 6']
    assert _calls(watch, 'e') == (3, 2)
    f(p, jnp.ones((6, 3)))
    assert _calls(watch, 'e') == (4, 2) and len(watch.events) == 2


def _alternating(kind):
    """(wrapped entry, its watch, call A, call B, A's result, B's)."""
    watch = cw.CompileWatch()
    if kind == 'shape':
        f = watch.wrap('e', jax.jit(lambda x: x.sum()))
        return (f, watch, lambda: f(jnp.ones((8,))),
                lambda: f(jnp.ones((5,))), (8.0, 'float32'),
                (5.0, 'float32'))
    if kind == 'static':
        f = watch.wrap(
            'e', jax.jit(lambda x, mode: x.sum() if mode == 'sum'
                         else x.max(), static_argnames=('mode',)),
            static_argnames=('mode',))
        x = jnp.arange(4.0)
        return (f, watch, lambda: f(x, mode='sum'),
                lambda: f(x, mode='max'), (6.0, 'float32'),
                (3.0, 'float32'))
    if kind == 'static_type':
        # 1 == True, but the fingerprint keys on the type too
        f = watch.wrap(
            'e', jax.jit(lambda x, n: x.sum() * (2 if n is True else 1),
                         static_argnames=('n',)), static_argnames=('n',))
        x = jnp.arange(4.0)
        return (f, watch, lambda: f(x, n=1), lambda: f(x, n=True),
                (6.0, 'float32'), (12.0, 'float32'))
    if kind == 'weak_scalar':
        # a Python scalar is weak-typed where an array of its dtype is
        # not: the executable's own check (shape and dtype) would let
        # one stand in for the other, and the result's dtype would be
        # the other program's
        f = watch.wrap('e', jax.jit(lambda x, k: (x * k)[3]))
        x = jnp.arange(4, dtype=jnp.int8)
        return (f, watch, lambda: f(x, 2), lambda: f(x, jnp.int32(2)),
                (6.0, 'int8'), (6.0, 'int32'))
    assert kind == 'bool_leaf'
    f = watch.wrap('e', jax.jit(
        lambda x, opts: jnp.where(opts['flip'], -x.sum(), x.sum())))
    x = jnp.arange(4.0)
    return (f, watch, lambda: f(x, {'flip': False}),
            lambda: f(x, {'flip': True}), (6.0, 'float32'),
            (-6.0, 'float32'))


@pytest.mark.parametrize(
    'kind', ['shape', 'static', 'static_type', 'bool_leaf', 'weak_scalar'])
def test_two_alternating_signatures_never_compile_a_third_time(
        kind, monkeypatch):
    """A short last batch, ``with_stats`` True/False, a ``bool`` leaf, a
    Python scalar where an array was: each keeps selecting its own
    program, as its fingerprint would; a switch costs at most one
    fingerprint and, after the first of each, no compile."""
    f, watch, call_a, call_b, want_a, want_b = _alternating(kind)
    spy = _Spy(monkeypatch)
    calls = 0
    for _ in range(3):
        for call, want in ((call_a, want_a), (call_a, want_a),
                           (call_b, want_b), (call_b, want_b)):
            out = call()
            assert (float(out), str(out.dtype)) == want
            calls += 1
    assert len(watch.events) == 2 and f.cache_size() == 2
    assert watch.compile_count('e') == 2
    assert spy.fingerprints == 6  # one a switch, none on a repeat
    assert _calls(watch, 'e') == (calls - 6, 6)
    assert len({e['fingerprint_key'] for e in watch.events}) == 2


def test_rejected_fast_attempt_leaves_donated_arguments_alive(monkeypatch):
    """The last executable refuses a call before anything runs or is
    donated: the program compiled next still finds its inputs."""
    watch = cw.CompileWatch()
    f = watch.wrap('e', jax.jit(
        lambda p, x: p + x.sum(), donate_argnums=(0,)))
    p = f(jnp.zeros((3,)), jnp.ones((8,)))
    p = f(p, jnp.ones((8,)))
    assert _calls(watch, 'e') == (1, 1)
    spy = _Spy(monkeypatch)
    donated = p
    out = f(donated, jnp.ones((5,)))      # fast attempt rejected
    (seen,) = spy.seen_args               # fingerprinted after it
    assert seen[0] is donated
    assert [float(v) for v in out] == [21.0] * 3
    assert donated.is_deleted()           # by the program that ran
    assert _calls(watch, 'e') == (1, 2) and len(watch.events) == 2


def test_sharding_rejected_input_recompiles_once_with_a_named_diff():
    """An unchanged program view whose committed sharding the executable
    refuses: the stale executable is dropped and one event names the
    sharding, as before; the new layout then dispatches fast."""
    watch = cw.CompileWatch()
    f = watch.wrap('e', jax.jit(lambda x: x * 2))
    x = jnp.arange(8.0)
    f(_sharded(x, 'd'))
    f(_sharded(x, 'd'))
    out = f(_sharded(x))                  # replicated: refused
    assert [float(v) for v in out] == [2.0 * i for i in range(8)]
    (first, new) = watch.events
    assert first['fingerprint_key'] == new['fingerprint_key']
    (line,) = new['diff']
    assert line.startswith('[0][0]: sharding ')
    assert f.cache_size() == 1
    f(_sharded(x))
    assert _calls(watch, 'e') == (2, 2) and len(watch.events) == 2


class _NoAOT:
    """A jitted callable whose ``lower`` fails: the watch pins its
    fingerprints to plain dispatch."""

    def __init__(self, fn):
        self._jit = jax.jit(fn)

    def __call__(self, *args, **kwargs):
        return self._jit(*args, **kwargs)

    def lower(self, *args, **kwargs):
        raise RuntimeError('no ahead-of-time lowering here')


def test_fallback_entry_keeps_fingerprinting_every_call():
    watch = cw.CompileWatch()
    f = watch.wrap('e', _NoAOT(lambda x: x.sum()))
    for n in (4, 4, 4, 6, 4):
        assert float(f(jnp.ones((n,)))) == n
    assert [e['aot'] for e in watch.events] == [False, False]
    assert watch.events[0]['aot_error'].startswith('lower: RuntimeError')
    assert f.cache_size() == 2 and f.executables() == []
    assert _calls(watch, 'e') == (0, 5)


def test_dispatch_counters_outlive_a_rewrapped_entry():
    """An entry wrapped a second time (a second Trainer on one engine)
    keeps counting: the counters are the watch's, by entry, and never
    reset."""
    watch = cw.CompileWatch()
    assert watch.dispatch_counters() == {}
    jitted = jax.jit(lambda x: x + 1)
    for _ in range(2):
        f = watch.wrap('e', jitted)
        for _ in range(3):
            f(jnp.ones((2,)))
    g = watch.wrap('other', jitted)
    g(jnp.ones((2,)))
    assert watch.dispatch_counters() == {
        'e': {'fast': 4, 'fingerprinted': 2},
        'other': {'fast': 0, 'fingerprinted': 1},
    }
    watch.dispatch_counters()['e']['fast'] = 0  # a copy
    assert _calls(watch, 'e') == (4, 2)
    assert watch.compile_count('e') == 2


def test_trainer_steps_dispatch_fast(trainer_mod):
    """The surface the benchmark times: after each step variant's first
    call every ``Trainer.step`` is a fast dispatch, and the counters
    add up to the calls made."""
    trainer, params, (x, y), kfac = trainer_mod
    watch = kfac.compile_watcher()
    state = trainer.init(params)
    state, _ = trainer.step(state, (x, y))
    def step_calls():
        by_entry = watch.dispatch_counters()
        return [sum(c[kind] for e, c in by_entry.items()
                    if e.startswith('trainer.step/'))
                for kind in ('fast', 'fingerprinted')]

    fast0, fingerprinted0 = step_calls()
    events = len(watch.events)
    for _ in range(6):
        state, _ = trainer.step(state, (x, y))
    fast, fingerprinted = step_calls()
    assert fast - fast0 >= 5  # a variant's first call may be new
    assert (fast - fast0) + (fingerprinted - fingerprinted0) == 6
    assert len(watch.events) <= events + 1


# ------------------------------------------------- the benchmark's reader


def test_benchmark_row_reads_the_dispatch_counters():
    """``launch_fast_share`` of ``BENCHMARK.json``: fast dispatches over
    all watched calls of the K-FAC trainer's watch, in percent, in all
    six cells; nothing on a watch without the counter (the parent's)."""
    import types

    from benchmark import harness

    def context(engine):
        run = types.SimpleNamespace(
            trainer=types.SimpleNamespace(kfac=engine))
        return harness.LayerContext(
            cell={}, run=run, devices=[], first_order_rows=[], rows=[],
            traced_rows=[], trace={'planes': []}, windows={},
            throughput=0.0,
        )

    def engine(watch):
        return types.SimpleNamespace(compile_watcher=lambda: watch)

    name = 'launch_fast_share'
    read = harness.read_layer_metric
    watch = cw.CompileWatch()
    assert read(name, context(engine(watch))) is None  # nothing called
    for entry, kind, n in (('trainer.step/with_stats', 'fingerprinted', 1),
                           ('trainer.step/with_stats', 'fast', 19),
                           ('trainer.step/no_stats', 'fingerprinted', 1),
                           ('trainer.step/no_stats', 'fast', 179)):
        for _ in range(n):
            watch._count_dispatch(entry, kind)
    assert read(name, context(engine(watch))) == pytest.approx(99.0)
    parent = types.SimpleNamespace(events=[], counters=dict)
    assert read(name, context(engine(parent))) is None
    assert read(name, context(engine(None))) is None
    assert read(name, context(types.SimpleNamespace())) is None
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cells = [w['name'] for w in bench['workloads']]
    assert len(cells) == 6
    row = next(m for m in bench['per_layer'] if m['name'] == name)
    assert dict(row, workloads=sorted(row['workloads'])) == {
        'name': name, 'unit': '%', 'better': 'higher',
        'source': 'program_counter', 'layer': 'trainer',
        'moves': 'throughput', 'workloads': sorted(cells),
    }
    for cell in cells:
        rows = harness.layer_rows(harness.load_cell(cell))
        assert name in {m['name'] for m in rows}
