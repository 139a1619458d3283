"""Tests for the preemption-safe checkpoint autopilot (kfac_tpu.resilience).

Covers the rotation invariants (fresh step dirs, atomic LATEST pointer,
keep-N pruning), the signal machinery (flag-only handlers, exit-outranks-
continue priority, on_step emergency flush), torn-write fallback via
testing/faults.corrupt_checkpoint, transient-I/O retry/backoff, elastic
restore across the dense engine and the three KAISA strategies through
``Trainer.restore_latest``, Trainer-integrated periodic
saves + resume continuity, and — slow-marked — a real ``kill -TERM``
against a subprocess training run that must leave a durable, resumable
checkpoint behind.
"""

import gc
import importlib.util
import json
import os
import signal as signal_mod
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kfac_tpu
from kfac_tpu import checkpoint
from kfac_tpu.resilience import CheckpointManager, Preempted, signals
from kfac_tpu.warnings import CheckpointResilienceWarning
from testing import models
from testing.faults import corrupt_checkpoint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, 'testing', 'resilience_worker.py')


@pytest.fixture(autouse=True)
def _clean_signal_state():
    signals.reset()
    yield
    signals.reset()


def _dense_setup(n=64):
    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=n)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(registry=reg, kl_clip=None)
    return m, (x, y), params, reg, kfac


def _run_steps(kfac, reg, m, params, batch, state=None, steps=1):
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m)
    )
    state = kfac.init() if state is None else state
    grads = None
    for _ in range(steps):
        (_, _), grads, stats = run(params, batch)
        state, pg = kfac.step(state, grads, stats)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.05 * g, params, pg
        )
    return state, params, grads


# ------------------------------------------------------------------ rotation


def test_rotation_keep_and_atomic_latest_pointer(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m)
    )
    mgr = CheckpointManager(
        tmp_path, engine=kfac, save_interval_steps=2, keep=2,
        install_signals=(),
    )
    state = kfac.init()
    for _ in range(6):
        (_, _), grads, stats = run(params, batch)
        state, pg = kfac.step(state, grads, stats)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.05 * g, params, pg
        )
        mgr.on_step(state)
    mgr.finalize()
    # saved on cadence at steps 2, 4, 6; keep=2 pruned step 2
    assert mgr.rotation_steps() == [6, 4]
    assert mgr.latest_step() == 6
    with open(tmp_path / 'LATEST') as f:
        assert f.read().strip() == 'step_00000006'
    assert not os.path.exists(mgr.step_dir(2))
    for s in (4, 6):
        assert mgr._is_committed(s)
        # manifest sidecar rode along (elastic restore stays available)
        assert os.path.exists(mgr.checkpoint_path(s) + '.manifest.json')


def test_restore_latest_roundtrip(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, params, grads = _run_steps(kfac, reg, m, params, batch, steps=2)
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False
    )
    path = mgr.save(state)
    result = mgr.restore_latest()
    assert result.step == 2
    assert result.path == path
    assert result.extra == {}
    np.testing.assert_allclose(
        np.asarray(result.state.a['fc1']), np.asarray(state.a['fc1']),
        rtol=1e-6,
    )
    p1 = kfac.precondition(state, grads)
    p2 = kfac.precondition(result.state, grads)
    np.testing.assert_allclose(
        np.asarray(p1['fc1']['kernel']), np.asarray(p2['fc1']['kernel']),
        rtol=1e-5, atol=1e-7,
    )


def test_restore_latest_empty_rotation(tmp_path):
    _, _, _, _, kfac = _dense_setup()
    mgr = CheckpointManager(tmp_path, engine=kfac, install_signals=())
    assert mgr.restore_latest() is None
    mgr2 = CheckpointManager(tmp_path / 'other', install_signals=())
    with pytest.raises(ValueError, match='engine'):
        mgr2.restore_latest()


@pytest.mark.faults
@pytest.mark.parametrize('mode', ['truncate', 'delete', 'metadata'])
def test_restore_falls_back_past_torn_checkpoint(tmp_path, mode):
    """A corrupt newest checkpoint (torn write, lost object, or missing
    commit markers) is skipped with a warning; the previous rotation
    entry restores — the run resumes instead of crashing."""
    m, batch, params, reg, kfac = _dense_setup()
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False, keep=3
    )
    state, params, _ = _run_steps(kfac, reg, m, params, batch)
    mgr.save(state)
    state, params, _ = _run_steps(
        kfac, reg, m, params, batch, state=state
    )
    newest = mgr.save(state)
    assert mgr.latest_step() == 2
    corrupt_checkpoint(newest, mode=mode)
    with pytest.warns(CheckpointResilienceWarning, match='falling back'):
        result = mgr.restore_latest()
    assert result.step == 1
    assert result.path == mgr.checkpoint_path(1)
    # the fallback warning is rate-limited per path: a second walk stays
    # quiet about the same corpse
    import warnings as warnings_mod

    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter('error', CheckpointResilienceWarning)
        assert mgr.restore_latest().step == 1


@pytest.mark.faults
def test_restore_survives_torn_latest_pointer(tmp_path):
    """A LATEST pointer torn mid-write (truncated, then trailing garbage
    bytes — ``corrupt_checkpoint(..., 'torn_latest')``) must degrade to
    "no pointer", not crash: ``latest_step`` returns None and
    ``restore_latest`` still finds the newest COMMITTED step via the
    rotation scan."""
    m, batch, params, reg, kfac = _dense_setup()
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False, keep=3
    )
    state, params, _ = _run_steps(kfac, reg, m, params, batch)
    mgr.save(state)
    state, params, _ = _run_steps(kfac, reg, m, params, batch, state=state)
    mgr.save(state)
    assert mgr.latest_step() == 2
    victim = corrupt_checkpoint(str(tmp_path), mode='torn_latest')
    assert victim == os.path.join(str(tmp_path), 'LATEST')
    # the torn pointer reads as garbage -> None, no UnicodeDecodeError
    assert mgr.latest_step() is None
    result = mgr.restore_latest()
    assert result.step == 2
    assert int(result.state.step) == 2


@pytest.mark.faults
def test_restore_walks_back_on_torn_latest_plus_torn_payload(tmp_path):
    """The chaos harness's ``torn_checkpoint`` fault class end-to-end:
    LATEST torn AND the newest payload truncated — the restore must walk
    back to the newest intact rotation entry instead of crashing on
    either corruption."""
    m, batch, params, reg, kfac = _dense_setup()
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False, keep=3
    )
    state, params, _ = _run_steps(kfac, reg, m, params, batch)
    mgr.save(state)
    state, params, _ = _run_steps(kfac, reg, m, params, batch, state=state)
    newest = mgr.save(state)
    corrupt_checkpoint(str(tmp_path), mode='torn_latest')
    corrupt_checkpoint(newest, mode='truncate')
    with pytest.warns(CheckpointResilienceWarning, match='falling back'):
        result = mgr.restore_latest()
    assert result.step == 1
    assert result.path == mgr.checkpoint_path(1)


def test_corrupt_checkpoint_rejects_unknown_mode(tmp_path):
    with pytest.raises(ValueError, match='unknown corruption mode'):
        corrupt_checkpoint(str(tmp_path), mode='bitflip')
    with pytest.raises(FileNotFoundError):
        corrupt_checkpoint(str(tmp_path / 'nope'), mode='truncate')
    with pytest.raises(FileNotFoundError):
        corrupt_checkpoint(str(tmp_path), mode='torn_latest')  # no LATEST


# ----------------------------------------------------- checkpoint.py policy


def test_save_overwrite_policy(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, params, _ = _run_steps(kfac, reg, m, params, batch)
    path = str(tmp_path / 'ckpt')
    checkpoint.save(path, state, engine=kfac)
    # the default refuses and the error names the path + the escape hatch
    with pytest.raises(ValueError, match='overwrite=True'):
        checkpoint.save(path, state)
    with pytest.raises(ValueError, match='ckpt'):
        checkpoint.save(path, state)
    state2, _, _ = _run_steps(kfac, reg, m, params, batch, state=state)
    checkpoint.save(path, state2, engine=kfac, overwrite=True)
    restored, _ = checkpoint.restore(path, kfac)
    assert int(restored.step) == 2


def test_async_handle_context_manager(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    path = str(tmp_path / 'actx')
    with checkpoint.save(path, state, engine=kfac, wait=False) as handle:
        pass
    # __exit__ waited: checkpoint durable and manifest finalized
    assert os.path.exists(path + '.manifest.json')
    restored, _ = checkpoint.restore(path, kfac)
    assert int(restored.step) == 1
    handle.wait_until_finished()  # idempotent


def test_async_handle_dropped_without_wait_warns(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    handle = checkpoint.save(str(tmp_path / 'adrop'), state, wait=False)
    ckptr = handle._ckptr  # keep orbax alive to drain its threads after
    with pytest.warns(ResourceWarning, match='wait_until_finished'):
        del handle
        gc.collect()
    ckptr.wait_until_finished()


def test_restore_without_manifest_warns(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    path = str(tmp_path / 'bare')
    checkpoint.save(path, state)  # no engine= -> no manifest sidecar
    with pytest.warns(CheckpointResilienceWarning, match='manifest'):
        restored, _ = checkpoint.restore(path, kfac)
    assert int(restored.step) == 1


# ------------------------------------------------------------------- signals


def test_signal_flag_priority_and_uninstall():
    before_term = signal_mod.getsignal(signal_mod.SIGTERM)
    before_usr1 = signal_mod.getsignal(signal_mod.SIGUSR1)
    with signals.install():
        assert signals.preemption_requested() is None
        os.kill(os.getpid(), signal_mod.SIGUSR1)
        assert signals.preemption_requested() == 'SIGUSR1'
        os.kill(os.getpid(), signal_mod.SIGTERM)
        assert signals.preemption_requested() == 'SIGTERM'
        # a continue-signal cannot demote a pending exit-signal
        os.kill(os.getpid(), signal_mod.SIGUSR1)
        assert signals.preemption_requested() == 'SIGTERM'
        assert signals.consume() == 'SIGTERM'
        assert signals.preemption_requested() is None
    assert signal_mod.getsignal(signal_mod.SIGTERM) is before_term
    assert signal_mod.getsignal(signal_mod.SIGUSR1) is before_usr1
    with pytest.raises(ValueError, match='SIGHUP'):
        signals.install(['SIGHUP'])


def test_signal_storm_redelivery_during_save_is_dropped():
    """Schedulers re-deliver SIGTERM every few seconds until the process
    dies. A re-delivery landing while the emergency save for that same
    signal is in flight must NOT re-arm the flag (it would re-enter
    save_emergency at the next boundary or leave a stale flag behind the
    Preempted unwind); an ESCALATION — SIGTERM during a SIGUSR1 save —
    must still latch."""
    with signals.install():
        # storm: N stacked SIGTERMs while the SIGTERM save runs
        with signals.save_in_flight('SIGTERM'):
            for _ in range(3):
                os.kill(os.getpid(), signal_mod.SIGTERM)
            assert signals.preemption_requested() is None
        assert signals.preemption_requested() is None  # nothing latched
        # escalation: SIGTERM during a SIGUSR1 snapshot save latches...
        with signals.save_in_flight('SIGUSR1'):
            os.kill(os.getpid(), signal_mod.SIGUSR1)  # re-delivery: dropped
            assert signals.preemption_requested() is None
            os.kill(os.getpid(), signal_mod.SIGTERM)  # escalation: latched
            assert signals.preemption_requested() == 'SIGTERM'
            # ...and a SIGUSR1 cannot demote the latched EXIT priority
            os.kill(os.getpid(), signal_mod.SIGUSR1)
            assert signals.preemption_requested() == 'SIGTERM'
        assert signals.consume() == 'SIGTERM'
    with pytest.raises(ValueError, match='SIGHUP'):
        with signals.save_in_flight('SIGHUP'):
            pass
    # reset() clears the in-flight marker too (crash-safety for tests)
    with signals.save_in_flight('SIGTERM'):
        assert signals.save_in_flight_signal() == 'SIGTERM'
        signals.reset()
        assert signals.save_in_flight_signal() is None


def test_save_emergency_idempotent_under_stacked_sigterm(tmp_path):
    """End-to-end storm idempotence: a second SIGTERM delivered WHILE
    save_emergency('SIGTERM') is writing must not re-enter the save or
    leave a pending flag; a SIGTERM delivered during a non-signal save
    (the postmortem writer's) must still latch — the preemption notice
    outlives that save."""
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    with CheckpointManager(
        tmp_path, engine=kfac, save_interval_steps=None, async_save=False
    ) as mgr:
        calls = []
        real_save = mgr.save

        def storming_save(state, step=None, block=True):
            calls.append(step)
            # the scheduler re-delivers mid-write, twice
            os.kill(os.getpid(), signal_mod.SIGTERM)
            os.kill(os.getpid(), signal_mod.SIGTERM)
            return real_save(state, step=step, block=block)

        mgr.save = storming_save
        path = mgr.save_emergency(state, reason='SIGTERM')
        assert calls == [1]
        assert path == mgr.checkpoint_path(1)
        # the storm was absorbed: no pending flag, nothing to re-enter
        assert signals.preemption_requested() is None
        # non-signal reason: a SIGTERM arriving DURING a degrade save
        # still latches — the preemption notice outlives that save
        mgr.save = storming_save
        mgr.save_emergency(state, reason='degrade', step=2)
        assert calls == [1, 2]
        assert signals.preemption_requested() == 'SIGTERM'
        signals.reset()


def test_on_step_sigusr1_saves_and_continues(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    with CheckpointManager(
        tmp_path, engine=kfac, save_interval_steps=None
    ) as mgr:
        assert mgr.on_step(state) is None  # no signal, periodic disabled
        os.kill(os.getpid(), signal_mod.SIGUSR1)
        path = mgr.on_step(state)
        assert path == mgr.checkpoint_path(1)
        assert mgr.latest_step() == 1
        assert signals.preemption_requested() is None  # consumed
        assert mgr.on_step(state) is None  # training continues normally


def test_on_step_sigterm_preempts_after_durable_save(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    with CheckpointManager(
        tmp_path, engine=kfac, save_interval_steps=None
    ) as mgr:
        os.kill(os.getpid(), signal_mod.SIGTERM)
        with pytest.raises(Preempted, match='SIGTERM') as excinfo:
            mgr.on_step(state)
        assert excinfo.value.step == 1
        # by the time Preempted unwinds, the checkpoint is durable
        assert mgr.latest_step() == 1
        assert mgr.restore_latest().step == 1


def test_multihost_coordination_defers_and_uses_agreed_step(
    tmp_path, monkeypatch
):
    """Multi-host (simulated): barrier participation depends only on the
    step cadence — an off-cadence local signal is deferred, not gathered;
    on the cadence step the pod-agreed (max) step names the rotation
    entry and the Preempted step, and a pod-wide EXIT is reported as
    SIGTERM even when this host only caught a SIGUSR1."""
    from kfac_tpu.parallel import multihost

    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    gathers, barriers = [], []
    monkeypatch.setattr(multihost, 'process_count', lambda: 2)
    monkeypatch.setattr(multihost, 'barrier', barriers.append)

    def fake_agree(code, step):
        # another host is 3 steps ahead and saw the SIGTERM
        gathers.append((code, step))
        return max(code, 2), step + 3

    monkeypatch.setattr(multihost, 'agree_emergency', fake_agree)
    with CheckpointManager(
        tmp_path, engine=kfac, save_interval_steps=None,
        coordinate_every=4,
    ) as mgr:
        os.kill(os.getpid(), signal_mod.SIGUSR1)
        # step 3 is off-cadence: no gather, the flag stays pending
        assert mgr.on_step(state, step=3) is None
        assert gathers == []
        assert signals.preemption_requested() == 'SIGUSR1'
        # step 4 coordinates: pod says EXIT at agreed step 7
        with pytest.raises(Preempted, match='SIGTERM') as excinfo:
            mgr.on_step(state, step=4)
        assert gathers == [(1, 4)]
        assert excinfo.value.step == 7
        assert excinfo.value.path == mgr.checkpoint_path(7)
        assert mgr.latest_step() == 7
        assert barriers  # rank 0's stale-dir clear is ordered before writes


def test_prune_removes_stale_uncommitted_dirs(tmp_path):
    """A torn corpse (step dir without orbax commit markers) older than
    the newest committed checkpoint is pruned at the next commit instead
    of accumulating forever; an uncommitted NEWER dir survives (it may be
    an async save still in flight)."""
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False
    )
    os.makedirs(os.path.join(mgr.step_dir(0), 'ckpt'))  # crashed attempt
    os.makedirs(os.path.join(mgr.step_dir(9), 'ckpt'))  # maybe in flight
    mgr.save(state)  # commits step 1 -> prune runs
    assert not os.path.exists(mgr.step_dir(0))
    assert os.path.exists(mgr.step_dir(9))
    assert mgr.latest_step() == 1


def test_save_emergency_reuses_committed_step(tmp_path):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False
    )
    path = mgr.save(state)
    sentinel = os.path.join(mgr.step_dir(1), 'sentinel')
    open(sentinel, 'w').close()
    # already durable: the grace window is not spent re-writing the bytes
    assert mgr.save_emergency(state, reason='test') == path
    assert os.path.exists(sentinel)


# ------------------------------------------------------------ retry/backoff


def test_retry_backoff_on_transient_io(tmp_path, monkeypatch):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    sleeps = []
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False,
        backoff_base=0.5, backoff_max=8.0, sleep=sleeps.append,
    )
    real_save = checkpoint.save
    calls = {'n': 0}

    def flaky(*args, **kwargs):
        calls['n'] += 1
        if calls['n'] <= 2:
            raise OSError('simulated transient I/O failure')
        return real_save(*args, **kwargs)

    monkeypatch.setattr(checkpoint, 'save', flaky)
    with pytest.warns(CheckpointResilienceWarning, match='retry'):
        mgr.save(state)
    assert calls['n'] == 3
    assert sleeps == [0.5, 1.0]  # capped exponential backoff
    monkeypatch.undo()
    assert mgr.restore_latest().step == 1


def test_retry_exhaustion_raises(tmp_path, monkeypatch):
    m, batch, params, reg, kfac = _dense_setup()
    state, _, _ = _run_steps(kfac, reg, m, params, batch)
    sleeps = []
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False,
        max_retries=1, backoff_base=0.5, sleep=sleeps.append,
    )

    def always_fail(*args, **kwargs):
        raise OSError('disk on fire')

    monkeypatch.setattr(checkpoint, 'save', always_fail)
    with pytest.warns(CheckpointResilienceWarning, match='retry'):
        with pytest.raises(OSError, match='disk on fire'):
            mgr.save(state)
    assert sleeps == [0.5]


# ------------------------------------------------------------------- elastic


#: the dense engine and the three KAISA strategies on the 8-device mesh,
#: by grad-worker fraction
LAYOUTS = {'dense': None, 'comm': 1.0, 'hybrid': 0.5, 'mem': 1.0 / 8}


def _trainer_on(layout, directory, **manager_kw):
    """A Trainer of ``TinyModel`` on one layout with a manager on
    ``directory``; ``(trainer, manager, params, batch)``."""
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=64)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    kfac = kfac_tpu.KFACPreconditioner(
        registry=kfac_tpu.register_model(m, x), kl_clip=None
    )
    if LAYOUTS[layout] is not None:
        kfac = DistributedKFAC(
            config=kfac,
            mesh=kaisa_mesh(grad_worker_fraction=LAYOUTS[layout]),
        )

    def loss_fn(p, model_state, batch):
        bx, by = batch
        pred = m.apply({'params': p}, bx)
        return jnp.mean((pred - by) ** 2), model_state

    manager_kw.setdefault('install_signals', ())
    mgr = CheckpointManager(directory, engine=kfac, **manager_kw)
    trainer = kfac_tpu.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac,
        checkpoints=mgr,
    )
    return trainer, mgr, params, (x, y)


@pytest.fixture(scope='module')
def saved_runs(tmp_path_factory):
    """Under each layout: two steps, one explicit save of the whole
    TrainState, two more steps. What a restore is held to."""
    runs = {}
    for layout in LAYOUTS:
        directory = tmp_path_factory.mktemp(f'saved_{layout}')
        trainer, mgr, params, batch = _trainer_on(
            layout, directory, async_save=False
        )
        state = trainer.init(params)
        for _ in range(2):
            state, _ = trainer.step(state, batch)
        mgr.save(state)
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        runs[layout] = {
            'directory': directory,
            'factors': jax.device_get(
                trainer.kfac.extract_factors(state.kfac_state)
            ),
            'params': jax.device_get(state.params),
            'preconditioned': jax.device_get(
                trainer.kfac.precondition(state.kfac_state, grads)
            ),
            'losses': [],
        }
        for _ in range(2):
            state, loss = trainer.step(state, batch)
            runs[layout]['losses'].append(float(loss))
    signals.reset()
    return runs


@pytest.mark.parametrize('restored', list(LAYOUTS))
@pytest.mark.parametrize('saved', list(LAYOUTS))
def test_elastic_restore_across_layouts(saved_runs, saved, restored):
    """A TrainState saved under any layout resumes under any other
    through ``Trainer.restore_latest`` (the job that comes back on
    another mesh): factors to the bit under the layout that wrote them
    and to rounding under the other, decompositions rematerialised, extras
    on the restoring engine's mesh, and the run goes on where the saved
    one went."""
    want = saved_runs[saved]
    trainer, mgr, params, batch = _trainer_on(restored, want['directory'])
    # the three strategies shard one stacked layout: between them a
    # restore is exact, and only dense <-> stacked goes through the
    # per-layer factors
    same_layout = (saved == 'dense') == (restored == 'dense')
    if same_layout:
        state = trainer.restore_latest(params)
    else:
        with pytest.warns(UserWarning, match='migrating'):
            state = trainer.restore_latest(params)
    assert int(jax.device_get(state.kfac_state.step)) == 2
    assert trainer._step_count == 2

    got = trainer.kfac.extract_factors(state.kfac_state)
    assert set(got) == set(want['factors'])
    for name, fg in want['factors'].items():
        for side in ('a', 'g'):
            if same_layout:
                np.testing.assert_array_equal(
                    np.asarray(got[name][side]), fg[side], f'{name}/{side}'
                )
            else:
                np.testing.assert_allclose(
                    np.asarray(got[name][side]), fg[side], rtol=1e-6,
                    err_msg=f'{name}/{side}',
                )
    # the decompositions are made again from the factors, not read: the
    # restored state preconditions as the saved one did
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    pre = trainer.kfac.precondition(state.kfac_state, grads)
    for layer in ('fc1', 'fc2'):
        np.testing.assert_allclose(
            np.asarray(pre[layer]['kernel']),
            want['preconditioned'][layer]['kernel'], rtol=1e-4, atol=1e-6,
        )

    # the extras: the saved values, placed where the next step needs them
    for layer in ('fc1', 'fc2'):
        np.testing.assert_array_equal(
            np.asarray(state.params[layer]['kernel']),
            want['params'][layer]['kernel'],
        )
    mesh = getattr(trainer.kfac, 'mesh', None)
    for leaf in jax.tree_util.tree_leaves((state.params, state.opt_state)):
        if mesh is None:
            assert len(leaf.sharding.device_set) == 1
        else:
            assert leaf.sharding.is_fully_replicated
            assert leaf.sharding.device_set == set(mesh.devices.flat)

    # the next step's loss reads the restored parameters alone; the one
    # after it has been through the restored curvature
    state, loss3 = trainer.step(state, batch)
    state, loss4 = trainer.step(state, batch)
    np.testing.assert_allclose(float(loss3), want['losses'][0], rtol=1e-6)
    np.testing.assert_allclose(float(loss4), want['losses'][1], rtol=1e-4)
    assert trainer._step_count == 4
    assert mgr.engine is trainer.kfac


@pytest.mark.faults
def test_restore_latest_every_candidate_corrupt_returns_none(tmp_path):
    """When EVERY rotation entry is unusable, restore_latest hands back
    None (the fresh-start contract) after warning exactly once per
    candidate — and a second walk over the same corpses stays quiet
    (the per-path rate limit)."""
    import warnings as warnings_mod

    m, batch, params, reg, kfac = _dense_setup()
    mgr = CheckpointManager(
        tmp_path, engine=kfac, install_signals=(), async_save=False, keep=3
    )
    state = None
    paths = []
    for _ in range(3):
        state, params, _ = _run_steps(
            kfac, reg, m, params, batch, state=state
        )
        paths.append(mgr.save(state))
    assert mgr.rotation_steps() == [3, 2, 1]
    for path in paths:
        corrupt_checkpoint(path, mode='truncate')
    with warnings_mod.catch_warnings(record=True) as caught:
        warnings_mod.simplefilter('always')
        assert mgr.restore_latest() is None
    unusable = [
        w for w in caught
        if isinstance(w.message, CheckpointResilienceWarning)
        and 'unusable' in str(w.message)
    ]
    assert len(unusable) == 3
    # rate-limited: the second walk re-visits no corpse loudly
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter('error', CheckpointResilienceWarning)
        assert mgr.restore_latest() is None


def test_elastic_restore_engine_overrides_manager_granularity(tmp_path):
    """restore_latest(engine=...) with a DIFFERENT bucket granularity
    than the manager's own engine migrates into the caller's layout —
    the manager binding is a default, not a constraint."""
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    m, batch, params, reg, _ = _dense_setup()

    def stacked(granularity):
        return DistributedKFAC(
            config=kfac_tpu.KFACPreconditioner(
                registry=reg, kl_clip=None, bucket_granularity=granularity
            ),
            mesh=kaisa_mesh(grad_worker_fraction=0.5),
        )

    dk64 = stacked(64)
    state, params, _ = _run_steps(dk64, reg, m, params, batch, steps=2)
    mgr = CheckpointManager(
        tmp_path, engine=dk64, install_signals=(), async_save=False
    )
    mgr.save(state)

    dk128 = stacked(128)
    with pytest.warns(UserWarning, match='migrating'):
        result = mgr.restore_latest(engine=dk128)
    assert result.step == 2
    assert mgr.engine is dk64  # the binding itself is untouched
    src = dk64.extract_factors(state)
    dst = dk128.extract_factors(result.state)
    for name, fg in src.items():
        for side in ('a', 'g'):
            np.testing.assert_allclose(
                np.asarray(dst[name][side]), np.asarray(fg[side]),
                rtol=1e-6, err_msg=f'{name}/{side}',
            )


# -------------------------------------------------------- Trainer lifecycle


@pytest.mark.parametrize('layout', list(LAYOUTS))
def test_trainer_periodic_saves_and_resume_continuity(tmp_path, layout):
    def make(directory):
        return _trainer_on(layout, directory, save_interval_steps=2, keep=2)

    trainer, mgr, params, (x, y) = make(tmp_path)
    state = trainer.init(params)
    losses, state_at_4 = [], None
    for i in range(5):
        state, loss = trainer.step(state, (x, y))
        losses.append(float(loss))
        if i == 3:
            state_at_4 = state
    mgr.finalize()
    assert mgr.latest_step() == 4
    assert mgr.rotation_steps() == [4, 2]

    trainer2, *_ = make(tmp_path)
    resumed = trainer2.restore_latest(params)
    assert resumed is not None
    assert int(jax.device_get(resumed.kfac_state.step)) == 4
    np.testing.assert_array_equal(
        np.asarray(resumed.params['fc1']['kernel']),
        np.asarray(state_at_4.params['fc1']['kernel']),
    )
    # continuity: the resumed run's next step reproduces the original
    # run's 5th step
    resumed, loss5 = trainer2.step(resumed, (x, y))
    np.testing.assert_allclose(float(loss5), losses[4], rtol=1e-6)
    assert trainer2._step_count == 5
    assert int(jax.device_get(resumed.kfac_state.step)) == 5

    # an empty rotation hands the caller back to a fresh start
    trainer3, *_ = make(tmp_path / 'empty')
    assert trainer3.restore_latest(params) is None


class _TickRecorder:
    """Stands where the CheckpointManager stands on a Trainer and keeps
    what each tick was handed."""

    def __init__(self):
        self.ticks = []

    def on_step(self, state, step=None):
        self.ticks.append((state, step))


@pytest.mark.parametrize(
    'path', ['step', 'scan_steps', 'step_accumulate', 'step_accumulate_scan']
)
def test_every_step_path_ticks_the_manager_once(path):
    """Each of the Trainer's four step paths hands the manager the state
    it returns, once, after the update: an optimizer step a tick on the
    three that the host drives step by step, with the step's number; a
    compiled scan a tick, without one (the manager reads the device's)."""
    m, (x, y), params, reg, kfac = _dense_setup()

    def loss_fn(p, model_state, batch):
        bx, by = batch
        return jnp.mean((m.apply({'params': p}, bx) - by) ** 2), model_state

    recorder = _TickRecorder()
    trainer = kfac_tpu.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac,
        checkpoints=recorder,
    )
    two = (jnp.stack([x, x]), jnp.stack([y, y]))
    calls = {
        'step': lambda s: trainer.step(s, (x, y)),
        'scan_steps': lambda s: trainer.scan_steps(s, two),
        'step_accumulate': lambda s: trainer.step_accumulate(
            s, [(x, y), (x, y)]
        ),
        'step_accumulate_scan': lambda s: trainer.step_accumulate_scan(
            s, two
        ),
    }
    state = trainer.init(params)
    returned = []
    for _ in range(2):
        state, _ = calls[path](state)
        returned.append(state)
    assert len(recorder.ticks) == 2
    steps_a_call = 2 if path == 'scan_steps' else 1
    for n, ((view, step), out) in enumerate(
        zip(recorder.ticks, returned), start=1
    ):
        assert view is out  # the state itself, no copy and no other view
        assert int(jax.device_get(view.kfac_state.step)) == n * steps_a_call
        assert step == (None if path == 'scan_steps' else n)


@pytest.mark.faults
def test_postmortem_degrade_flushes_emergency_checkpoint(tmp_path):
    """The health sentinel's degrade event, observed by the flight
    recorder's PostmortemWriter, flushes one emergency checkpoint into
    the manager's rotation and records its path in the bundle MANIFEST —
    the diverged state is preserved next to the telemetry."""
    from kfac_tpu import health as health_lib
    from testing import faults

    m, batch, params, reg, _ = _dense_setup()
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, kl_clip=None, flight=8,
        health=health_lib.HealthConfig(warn=False, degrade_after=1),
    )
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m)
    )
    step = jax.jit(kfac.step)
    mgr = CheckpointManager(
        tmp_path / 'rot', engine=kfac, install_signals=(),
        async_save=False, save_interval_steps=None,
    )
    pm = kfac_tpu.PostmortemWriter(
        tmp_path / 'pms', engine=kfac, checkpoint_manager=mgr
    )
    state = kfac.init()
    (_, _), grads, stats = run(params, batch)
    state, _ = step(state, grads, stats, loss=jnp.float32(1.0))
    assert pm.observe(state) is None
    assert mgr.latest_step() is None  # healthy steps save nothing
    state, _ = step(
        state, grads, faults.poison_stats(stats, 'fc2', side='a'),
        loss=jnp.float32(1.0),
    )
    bundle = pm.observe(state)
    assert bundle is not None and 'degrade' in os.path.basename(bundle)
    man = json.load(open(os.path.join(bundle, 'MANIFEST.json')))
    assert man['emergency_checkpoint'] == mgr.checkpoint_path(2)
    assert mgr.latest_step() == 2
    # the quarantine rolled the poisoned factor back, so the emergency
    # checkpoint holds healthy factors and restores cleanly
    assert mgr.restore_latest().step == 2


# --------------------------------------------------------------- subprocess


def _read_events(text):
    events = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith('{'):
            try:
                events.append(json.loads(line))
            except ValueError:
                continue
    return events


@pytest.mark.slow
def test_subprocess_sigterm_leaves_resumable_checkpoint(tmp_path):
    """Real preemption: kill -TERM a live training process mid-run. The
    worker must exit 0 with a durable emergency checkpoint, and a second
    invocation must resume from exactly that step and train on."""
    ckpt_dir = str(tmp_path / 'rot')
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)  # single-device worker: fastest compile
    env.setdefault(
        'JAX_COMPILATION_CACHE_DIR', os.path.join(REPO, '.jax_cache')
    )
    err_path = tmp_path / 'worker.err'
    with open(err_path, 'w') as errf:
        proc = subprocess.Popen(
            [sys.executable, WORKER, ckpt_dir, '1000', '2', '0.1'],
            stdout=subprocess.PIPE, stderr=errf, text=True, env=env,
            cwd=REPO,
        )
        events = []
        try:
            # the worker self-terminates only via Preempted, so the parent
            # must send the signal once training is demonstrably underway
            for line in proc.stdout:
                events.extend(_read_events(line))
                if events and events[-1].get('event') == 'step' and (
                    events[-1]['step'] >= 3
                ):
                    proc.send_signal(signal_mod.SIGTERM)
                    break
            out, _ = proc.communicate(timeout=300)
        finally:
            proc.kill()
    events.extend(_read_events(out))
    assert proc.returncode == 0, err_path.read_text()[-4000:]
    pre = [e for e in events if e.get('event') == 'preempted']
    assert pre, events
    assert pre[0]['signal'] == 'SIGTERM'
    saved = pre[0]['saved_step']
    assert saved >= 3
    assert pre[0]['latest'] == saved
    assert os.path.exists(os.path.join(ckpt_dir, 'LATEST'))

    # phase 2: a fresh process resumes from the emergency checkpoint and
    # runs two more steps to completion
    done_run = subprocess.run(
        [sys.executable, WORKER, ckpt_dir, str(saved + 2), '2'],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert done_run.returncode == 0, done_run.stderr[-4000:]
    ev2 = _read_events(done_run.stdout)
    start = next(e for e in ev2 if e['event'] == 'start')
    done = next(e for e in ev2 if e['event'] == 'done')
    assert start['resumed_step'] == saved
    assert done['final_step'] == saved + 2
    # one of the two extra steps hit the interval-2 cadence and its
    # finalized periodic save moved the pointer past the emergency one
    assert done['latest'] > saved


@pytest.mark.slow
def test_subprocess_sigterm_agreed_step_single_rotation_entry(tmp_path):
    """Real preemption under simulated pod skew: the worker shims
    ``agree_emergency`` so a peer is 3 steps ahead at coordination time.
    The emergency save must land under the POD-AGREED step — one
    rotation entry, pointed at by LATEST — never this host's local step
    (the PR-4 review fix: per-host saves at divergent steps tore the
    rotation). The saved state itself still carries the local counter,
    so a resume restarts from the local step inside the agreed entry."""
    skew = 3
    ckpt_dir = str(tmp_path / 'rot')
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)  # single-device worker: fastest compile
    env.setdefault(
        'JAX_COMPILATION_CACHE_DIR', os.path.join(REPO, '.jax_cache')
    )
    err_path = tmp_path / 'worker.err'
    with open(err_path, 'w') as errf:
        proc = subprocess.Popen(
            [
                sys.executable, WORKER, ckpt_dir, '1000', '2', '0.1',
                str(skew),
            ],
            stdout=subprocess.PIPE, stderr=errf, text=True, env=env,
            cwd=REPO,
        )
        events = []
        try:
            for line in proc.stdout:
                events.extend(_read_events(line))
                if events and events[-1].get('event') == 'step' and (
                    events[-1]['step'] >= 3
                ):
                    proc.send_signal(signal_mod.SIGTERM)
                    break
            out, _ = proc.communicate(timeout=300)
        finally:
            proc.kill()
    events.extend(_read_events(out))
    assert proc.returncode == 0, err_path.read_text()[-4000:]
    pre = [e for e in events if e.get('event') == 'preempted']
    assert pre, events
    local = pre[0]['local_step']
    saved = pre[0]['saved_step']
    assert local is not None and local >= 3
    # the agreed (skewed-peer) step names the checkpoint, not the local
    assert saved == local + skew
    assert pre[0]['latest'] == saved
    # exactly one rotation entry for the agreed step, on disk and in the
    # worker's own view of the rotation
    assert pre[0]['rotation'].count(saved) == 1
    assert os.path.isdir(os.path.join(ckpt_dir, f'step_{saved:08d}'))

    # the agreed entry is restorable; the state inside carries the local
    # counter (the peer was ahead, this host's weights are at `local`)
    resume = subprocess.run(
        [sys.executable, WORKER, ckpt_dir, str(local + 1), '2'],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert resume.returncode == 0, resume.stderr[-4000:]
    ev2 = _read_events(resume.stdout)
    start = next(e for e in ev2 if e['event'] == 'start')
    assert start['resumed_step'] == local


# ---------------------------------------------------------------- docs lint


def test_signal_doc_lint_in_sync():
    spec = importlib.util.spec_from_file_location(
        'lint_signals', os.path.join(REPO, 'tools', 'lint_signals.py')
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check(os.path.join(REPO, 'docs', 'ROBUSTNESS.md')) == []
