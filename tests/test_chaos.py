"""Tests for the pod-scale chaos harness (kfac_tpu/resilience/chaos.py).

Three tiers:

* Pure unit tests — config validation, storm schedule grammar
  (scripted + seeded), SLO reconciliation on synthetic pod records,
  report JSON, the committed-artifact loader. No processes.
* The tier-1 pod test — a REAL deterministic 4-process scripted storm:
  the conductor spawns gloo ``chaos_worker.py`` pods, delivers a
  SIGTERM wave, tears the rotation, shrinks the pod, snapshots via
  SIGUSR1, and the reconciled report must clear every SLO budget.
* A slow-marked seeded 16-process storm with a wall-clock budget.
"""

import dataclasses
import json
import os

import pytest

from kfac_tpu.resilience import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- config


def test_config_validation():
    with pytest.raises(ValueError, match='procs'):
        chaos.ChaosConfig(procs=1)
    with pytest.raises(ValueError, match='keep'):
        chaos.ChaosConfig(keep=1)
    with pytest.raises(ValueError, match='max_steps'):
        chaos.ChaosConfig(max_steps=0)
    with pytest.raises(ValueError, match='save_interval'):
        chaos.ChaosConfig(save_interval=0)
    with pytest.raises(ValueError, match='not both'):
        chaos.ChaosConfig(
            schedule=({'fault': 'sigterm_wave', 'at_step': 3},), seed=1
        )
    with pytest.raises(ValueError, match='unknown fault class'):
        chaos.ChaosConfig(schedule=({'fault': 'meteor', 'at_step': 3},))
    with pytest.raises(ValueError, match='fault_mix'):
        chaos.ChaosConfig(seed=1, fault_mix=('sigterm_wave', 'meteor'))


def test_scripted_storm_covers_committed_fault_classes():
    sched = chaos.resolve_schedule(chaos.ChaosConfig())
    faults = [e['fault'] for e in sched]
    # the three committed SLO fault classes plus the continue-signal path
    assert {'sigterm_wave', 'torn_checkpoint', 'shrink',
            'sigusr1'} <= set(faults)
    assert all(f in chaos.FAULT_CLASSES for f in faults)
    # kill points are ordered and leave room for the final run
    downs = [e['at_step'] for e in sched if e['fault'] != 'sigusr1']
    assert downs == sorted(downs)
    assert downs[-1] < chaos.ChaosConfig().max_steps


def test_explicit_schedule_wins_over_canonical():
    sched = ({'fault': 'sigterm_wave', 'ranks': (0,), 'at_step': 3},)
    assert chaos.resolve_schedule(
        chaos.ChaosConfig(schedule=sched)
    ) == sched


def test_seeded_storm_deterministic_and_valid():
    a = chaos.seeded_storm(chaos.ChaosConfig(seed=11, storm_events=4))
    b = chaos.seeded_storm(chaos.ChaosConfig(seed=11, storm_events=4))
    c = chaos.seeded_storm(chaos.ChaosConfig(seed=12, storm_events=4))
    assert a == b
    assert a != c
    downs = [e for e in a if e['fault'] != 'sigusr1']
    assert len(downs) == 4
    for ev in a:
        assert ev['fault'] in chaos.FAULT_CLASSES
        assert all(0 <= r < 4 for r in ev['ranks'])
        if ev['fault'] in ('shrink', 'grow'):
            assert ev['procs'] >= 2


# ---------------------------------------------------------------- reconcile


def _rec(procs, down, events, t_exit=10.0):
    r = chaos.RunRecord(procs=procs, skew=0.0, down_event=down)
    r.events = events
    r.t_exit = t_exit
    return r


def _step(rank, t, step, loss):
    return (rank, t, {'event': 'step', 'step': step, 'loss': loss})


def _start(rank, t, resumed, depth):
    return (rank, t, {
        'event': 'start', 'rank': rank, 'world': 2,
        'resumed_step': resumed, 'fallback_depth': depth,
    })


def _preempted(rank, t, saved):
    return (rank, t, {
        'event': 'preempted', 'signal': 'SIGTERM', 'saved_step': saved,
    })


_LOSSES = {1: 1.0, 2: 0.5, 3: 0.25, 4: 0.125}


def _clean_storm():
    down = {'fault': 'sigterm_wave', 'ranks': (0,), 'at_step': 2}
    runs = [{'down': down, 'snaps': ()}, {'down': None, 'snaps': ()}]
    records = [
        _rec(2, down, [_start(r, 1.0, 0, 0) for r in (0, 1)]
             + [_step(r, 2.0, s, _LOSSES[s])
                for r in (0, 1) for s in (1, 2)]
             + [_preempted(r, 3.0, 2) for r in (0, 1)]),
        _rec(2, None, [_start(r, 11.0, 2, 0) for r in (0, 1)]
             + [_step(r, 12.0, s, _LOSSES[s])
                for r in (0, 1) for s in (3, 4)]),
    ]
    control = _rec(2, None, [
        _step(r, 1.0, s, _LOSSES[s]) for r in (0, 1) for s in _LOSSES
    ])
    return runs, records, control


def test_reconcile_clean_storm_meets_budgets():
    runs, records, control = _clean_storm()
    cfg = chaos.ChaosConfig(procs=2, max_steps=4)
    report = chaos.reconcile(cfg, runs, records, control)
    assert report.ok
    assert report.blown == []
    row = report.rows['sigterm_wave']
    assert row['events'] == 1
    assert row['downtime_steps'] == 0  # resumed at the emergency step
    assert row['fallback_depth'] == 0
    assert row['max_divergence'] == 0.0
    js = report.to_json()
    assert js['ok'] is True
    json.dumps(js)  # artifact-serializable


def test_reconcile_counts_emergency_save_as_progress():
    """The boundary step's 'step' event is never emitted (Preempted
    unwinds inside trainer.step), so progress must come from the
    preempted event's saved_step — resuming AT it is zero downtime,
    resuming one rotation entry earlier is positive downtime."""
    runs, records, control = _clean_storm()
    assert records[0].progress() == 2  # saved_step, not max observed
    cfg = chaos.ChaosConfig(procs=2, max_steps=4)
    behind = [
        records[0],
        _rec(2, None, [_start(r, 11.0, 1, 1) for r in (0, 1)]
             + [_step(r, 12.0, s, _LOSSES[s])
                for r in (0, 1) for s in (2, 3, 4)]),
    ]
    report = chaos.reconcile(cfg, runs, behind, control)
    assert report.rows['sigterm_wave']['downtime_steps'] == 1


def test_reconcile_detects_divergence_and_rank_disagreement():
    runs, records, control = _clean_storm()
    cfg = chaos.ChaosConfig(procs=2, max_steps=4)
    diverged = [
        records[0],
        _rec(2, None, [_start(r, 11.0, 2, 0) for r in (0, 1)]
             + [_step(r, 12.0, s, _LOSSES[s] + 1e-3)
                for r in (0, 1) for s in (3, 4)]),
    ]
    report = chaos.reconcile(cfg, runs, diverged, control)
    assert not report.ok
    assert any('diverged' in b for b in report.blown)

    split_brain = [
        records[0],
        _rec(2, None, [_start(r, 11.0, 2, 0) for r in (0, 1)]
             + [_step(0, 12.0, 3, 0.25), _step(1, 12.0, 3, 0.26)]
             + [_step(r, 13.0, 4, _LOSSES[4]) for r in (0, 1)]),
    ]
    report2 = chaos.reconcile(cfg, runs, split_brain, control)
    assert any('disagrees' in b for b in report2.blown)


def test_reconcile_blows_budget_on_deep_fallback_and_incomplete_run():
    runs, records, control = _clean_storm()
    cfg = chaos.ChaosConfig(procs=2, max_steps=4)
    deep = [
        records[0],
        _rec(2, None, [_start(r, 11.0, 0, 3) for r in (0, 1)]
             + [_step(r, 12.0, s, _LOSSES[s])
                for r in (0, 1) for s in (1, 2, 3)]),  # never reaches 4
    ]
    report = chaos.reconcile(cfg, runs, deep, control)
    assert not report.ok
    assert any('fell back' in b for b in report.blown)
    assert any('never completed' in b for b in report.blown)


def test_reconcile_requires_torn_checkpoint_to_exercise_fallback():
    """A torn_checkpoint event whose restore did NOT fall back means the
    injected corruption was never exercised — the report must fail
    rather than certify an untested SLO."""
    runs, records, control = _clean_storm()
    runs[0]['down'] = dict(
        runs[0]['down'], fault='torn_checkpoint'
    )
    records[0].down_event = runs[0]['down']
    cfg = chaos.ChaosConfig(procs=2, max_steps=4)
    report = chaos.reconcile(cfg, runs, records, control)
    assert any('never exercised' in b for b in report.blown)


# ----------------------------------------------------------------- artifact


def test_committed_artifact_is_fresh_and_green():
    """The committed SLO artifact (kfac_tpu/resilience/chaos_slo.json)
    covers the three required fault classes, met every budget, and its
    knob snapshot matches the current ChaosConfig defaults (regenerate
    with ``python tools/kfac_chaos.py --out ...`` after changing
    either)."""
    artifact = chaos.load_slo_artifact()
    assert artifact is not None, (
        f'missing committed artifact {chaos.ARTIFACT_PATH}; generate with '
        'python tools/kfac_chaos.py --out kfac_tpu/resilience/chaos_slo.json'
    )
    assert artifact['ok'] is True
    assert artifact['blown'] == []
    rows = artifact['rows']
    for fault in ('sigterm_wave', 'torn_checkpoint', 'shrink'):
        assert fault in rows, f'artifact lacks SLO row for {fault!r}'
        assert rows[fault]['events'] >= 1
    # torn restore actually walked the rotation; clean wave did not
    assert rows['torn_checkpoint']['fallback_depth'] >= 1
    assert rows['sigterm_wave']['fallback_depth'] == 0
    assert rows['sigterm_wave']['max_divergence'] == 0.0
    cfg = artifact['config']
    defaults = dataclasses.asdict(chaos.ChaosConfig())
    stale = {
        k for k in defaults
        if k in cfg and json.loads(json.dumps(defaults[k])) != cfg[k]
    }
    assert not stale, (
        f'artifact config drifted from ChaosConfig defaults on {sorted(stale)}'
    )


def test_load_slo_artifact_tolerates_absence(tmp_path):
    assert chaos.load_slo_artifact(str(tmp_path / 'nope.json')) is None
    bad = tmp_path / 'bad.json'
    bad.write_text('{"not": "an artifact"}')
    assert chaos.load_slo_artifact(str(bad)) is None
    bad.write_text('not json at all')
    assert chaos.load_slo_artifact(str(bad)) is None


def test_chaos_cli_selftest():
    import subprocess
    import sys

    res = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'kfac_chaos.py'),
         '--selftest'],
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert 'chaos selftest ok' in res.stdout


# ------------------------------------------------------------- faults (unit)


def test_newest_step_dir_and_disk_faults(tmp_path):
    cond = chaos.ChaosConductor(
        chaos.ChaosConfig(), root=str(tmp_path / 'root')
    )
    ckpt = tmp_path / 'rot'
    assert cond._newest_step_dir(str(ckpt)) is None
    for step in (2, 10):
        d = ckpt / f'step_{step:08d}'
        d.mkdir(parents=True)
        (d / 'payload.bin').write_bytes(b'x' * 64)
    (ckpt / 'garbage').mkdir()
    (ckpt / 'LATEST').write_text('step_00000010')
    assert cond._newest_step_dir(str(ckpt)) == str(ckpt / 'step_00000010')

    victims = cond._apply_disk_fault(str(ckpt), 'torn_checkpoint')
    assert str(ckpt / 'LATEST') in victims
    assert any('step_00000010' in v for v in victims)
    # torn pointer: garbage bytes, and the newest payload got truncated
    assert (ckpt / 'LATEST').read_bytes() != b'step_00000010'
    assert (ckpt / 'step_00000010' / 'payload.bin').stat().st_size < 64

    victims2 = cond._apply_disk_fault(str(ckpt), 'corrupt_payload')
    assert victims2
    with pytest.raises(chaos.ChaosError, match='no step dir'):
        cond._apply_disk_fault(str(tmp_path / 'empty'), 'corrupt_payload')


# ------------------------------------------------------------ real pod storms


def test_scripted_storm_4proc_meets_slos(tmp_path):
    """THE tier-1 chaos test: a real 4-process gloo pod rides the
    canonical scripted storm — SIGTERM wave, torn checkpoint (LATEST +
    payload), topology shrink to 2, in-flight SIGUSR1 snapshot — and
    every recovery SLO budget must hold, with the storm trajectory
    bit-identical to control on same-world runs."""
    config = chaos.ChaosConfig(procs=4, max_steps=8)
    conductor = chaos.ChaosConductor(config, root=str(tmp_path))
    report = conductor.run()  # raises ChaosError with the report on blow
    assert report.ok
    faults = {f['fault'] for f in report.faults_applied}
    assert {'sigterm_wave', 'torn_checkpoint', 'shrink'} <= faults
    assert report.rows['torn_checkpoint']['fallback_depth'] >= 1
    assert report.rows['sigterm_wave']['max_divergence'] == 0.0
    assert report.rows['sigusr1']['events'] >= 1
    # the shrink run really ran elastic: world changed mid-trajectory
    assert any(r['world_changed'] for r in report.runs)
    json.dumps(report.to_json())


@pytest.mark.slow
def test_seeded_storm_16proc(tmp_path):
    """Pod-scale seeded storm: 16 gloo processes, randomized fault
    draw (deterministic per seed), wall-clock budgeted — each pod run
    is bounded by ``phase_timeout_s`` (the conductor kills a wedged pod
    and fails), and the whole storm must clear an end-to-end budget.
    The report must reconcile green: whatever the seed drew, the stack
    healed."""
    import time

    budget_s = 1800.0
    config = chaos.ChaosConfig(
        procs=16, max_steps=8, seed=1337, storm_events=2,
        phase_timeout_s=600.0,
    )
    conductor = chaos.ChaosConductor(config, root=str(tmp_path))
    t0 = time.monotonic()
    report = conductor.run()
    wall = time.monotonic() - t0
    assert report.ok
    assert wall < budget_s, (
        f'16-proc seeded storm took {wall:.0f}s > {budget_s:.0f}s budget'
    )
    assert sum(
        row['events'] for f, row in report.rows.items() if f != 'sigusr1'
    ) == 2


# ----------------------------------------------------------- lint rule


def test_kfl111_chaos_knobs_doc_in_sync():
    from kfac_tpu.analysis import drift

    assert drift.check_chaos_knobs() == []


def test_kfl111_detects_doc_drift(tmp_path):
    from kfac_tpu.analysis import drift

    doc = tmp_path / 'ROBUSTNESS.md'
    rows = ''.join(
        f'| `{f.name}` | x | x |\n'
        for f in dataclasses.fields(chaos.ChaosConfig)
        if f.name != 'procs'
    )
    doc.write_text(
        '### Chaos knobs\n\n| knob | default | meaning |\n|---|---|---|\n'
        + rows + '| `phantom_knob` | x | x |\n'
    )
    problems = drift.check_chaos_knobs(str(doc))
    assert any('procs' in p and 'undocumented' in p for p in problems)
    assert any('phantom_knob' in p and 'not a ChaosConfig' in p
               for p in problems)
