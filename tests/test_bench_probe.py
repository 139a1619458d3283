"""Unit tests for bench.py's orchestration: a run that finds no TPU
fails unless the caller pinned the host itself, and every run's record
survives on disk."""

import json

import pytest

import bench


def test_claim_backend_refuses_unpinned_cpu(tmp_path, monkeypatch):
    """A stage's first backend touch: anything but a TPU ends the stage
    non-zero with the reason on disk — it never measures on another
    platform under the device keys."""
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    out = tmp_path / 'stage.json'
    result: dict = {}
    with pytest.raises(SystemExit) as exc:
        bench._claim_backend(result, str(out), 'lm_tiny')
    assert exc.value.code not in (0, None)
    rec = json.loads(out.read_text())
    assert rec['platform'] == 'cpu'
    assert 'no TPU' in rec['error'] and "'cpu'" in rec['error']


def test_claim_backend_allows_caller_pinned_cpu(tmp_path, monkeypatch):
    """The tests' smoke: the caller set JAX_PLATFORMS=cpu itself, so the
    stage runs and its record says where."""
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    out = tmp_path / 'stage.json'
    result: dict = {}
    dev = bench._claim_backend(result, str(out), 'lm_tiny')
    assert dev.platform == 'cpu'
    rec = json.loads(out.read_text())
    assert rec['platform'] == 'cpu' and 'error' not in rec


def _fake_stages(monkeypatch, tmp_path, platform):
    """Replace stage subprocesses with writers of plausible records."""
    monkeypatch.setenv('BENCH_PARTIAL_PATH', str(tmp_path / 'part.json'))
    monkeypatch.setenv('BENCH_RUNS_DIR', str(tmp_path / 'runs'))
    monkeypatch.setenv('BENCH_DEADLINE_S', '100000')
    calls = []

    def fake_run_stage(name, argv, env, budget, stdout_path=None):
        calls.append((name, argv, env, stdout_path))
        # stage writes its json/jsonl record like the real subprocess
        if name.startswith('lm_') or name in bench._RESNET_CONFIGS:
            out = argv[argv.index('--out') + 1]
            rec = {'platform': platform, 'sgd_tokens_per_sec': 100.0,
                   'value': 90.0, 'vs_baseline': 0.9, 'mfu': 0.3,
                   'sgd_mfu': 0.33, 'ok': True}
            if name in bench._RESNET_CONFIGS:
                rec.update(kfac_images_per_sec=500.0)
            with open(out, 'w') as f:
                json.dump(rec, f)
        elif stdout_path:
            with open(stdout_path, 'w') as f:
                f.write(json.dumps({'platform': platform}) + '\n')
                f.write(json.dumps({'op': 'cov_512', 'max_err': 0.0}) + '\n')
        return 'ok'

    monkeypatch.setattr(bench, '_run_stage', fake_run_stage)
    return calls


def test_orchestrator_fails_when_first_stage_finds_no_tpu(
    tmp_path, monkeypatch
):
    """No probe, no fallback: the plan starts, the first stage reports the
    platform it found, and a non-TPU answer ends the run with nothing
    lifted into the headline."""
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    calls = _fake_stages(monkeypatch, tmp_path, platform='cpu')
    result = {'metric': 'm', 'value': 0.0, 'platform': 'unknown'}
    with pytest.raises(RuntimeError, match="platform 'cpu'"):
        bench._orchestrate(result)
    assert [c[0] for c in calls] == ['micro_safe']
    assert result['value'] == 0.0 and result['platform'] == 'unknown'
    assert 'fallback' not in result and 'tpu_replay' not in result


def test_main_exits_nonzero_without_tpu(tmp_path, monkeypatch, capsys):
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    _fake_stages(monkeypatch, tmp_path, platform='cpu')
    monkeypatch.setattr(bench.sys, 'argv', ['bench.py'])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 'not a TPU' in line['error'] and line['value'] == 0.0


def test_orchestrator_pinned_cpu_runs_the_tiny_smoke_alone(
    tmp_path, monkeypatch
):
    """JAX_PLATFORMS=cpu set by the caller: one lm_tiny stage plus the
    accuracy stage, recorded as platform cpu; no stage is handed a
    compile-cache directory (each places its own by the one rule)."""
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    calls = _fake_stages(monkeypatch, tmp_path, platform='cpu')
    result = {'metric': 'm', 'value': 0.0, 'platform': 'unknown'}
    bench._orchestrate(result)
    assert [c[0] for c in calls] == ['lm_tiny', 'acc']
    assert result['platform'] == 'cpu' and result['value'] == 90.0
    for _, _, env, _ in calls:
        assert 'JAX_COMPILATION_CACHE_DIR' not in env


def test_persist_writes_partial_snapshot(tmp_path, monkeypatch):
    """_persist leaves an atomic JSON snapshot flagged partial=True (plus
    this run's id for attribution), so a killed run's completed phases
    survive on disk."""
    path = tmp_path / 'part.json'
    monkeypatch.setenv('BENCH_PARTIAL_PATH', str(path))
    monkeypatch.setenv('BENCH_RUNS_DIR', str(tmp_path / 'runs'))
    bench._persist({'metric': 'm', 'value': 1.5})
    got = json.loads(path.read_text())
    assert got == {
        'metric': 'm', 'value': 1.5, 'partial': True, 'run_id': bench._RUN_ID
    }
    # completed runs re-stamp partial=False
    bench._persist({'metric': 'm', 'value': 1.5}, partial=False)
    assert json.loads(path.read_text())['partial'] is False
    # overwrite is atomic (no stale tmp files left behind)
    bench._persist({'metric': 'm', 'value': 2.5})
    assert json.loads(path.read_text())['value'] == 2.5
    assert list(tmp_path.glob('*.tmp.*')) == []
    # the per-run record carries the same payload, keyed by run id
    run_file = tmp_path / 'runs' / f'run_{bench._RUN_ID}.json'
    assert json.loads(run_file.read_text())['value'] == 2.5


def test_persist_refreshes_pointer_and_keeps_every_run(
    tmp_path, monkeypatch
):
    """The latest pointer follows the latest run whatever its platform
    (a record names its own); what protects an older run's data is its
    own ``run_<id>.json``, which no later run writes to."""
    path = tmp_path / 'part.json'
    monkeypatch.setenv('BENCH_PARTIAL_PATH', str(path))
    monkeypatch.setenv('BENCH_RUNS_DIR', str(tmp_path / 'runs'))
    older = tmp_path / 'runs' / 'run_older_tpu_run.json'
    older.parent.mkdir()
    older.write_text(json.dumps({'platform': 'tpu', 'value': 123.0}))
    path.write_text(older.read_text())
    bench._persist({'metric': 'm', 'platform': 'cpu', 'value': 1.0})
    assert json.loads(path.read_text())['platform'] == 'cpu'
    assert json.loads(older.read_text())['value'] == 123.0
    run_file = tmp_path / 'runs' / f'run_{bench._RUN_ID}.json'
    assert json.loads(run_file.read_text())['platform'] == 'cpu'


def test_mark_run_started_stamps_latest(tmp_path, monkeypatch):
    """Attribution marker: bench_partial.json describes the current run iff
    its run_id matches LATEST.json (the pointer lags after a
    pre-first-phase death)."""
    monkeypatch.setenv('BENCH_PARTIAL_PATH', str(tmp_path / 'part.json'))
    monkeypatch.setenv('BENCH_RUNS_DIR', str(tmp_path / 'runs'))
    bench._mark_run_started()
    latest = json.loads((tmp_path / 'runs' / 'LATEST.json').read_text())
    assert latest['run_id'] == bench._RUN_ID


def test_persist_disabled_with_empty_path(tmp_path, monkeypatch):
    monkeypatch.setenv('BENCH_PARTIAL_PATH', '')
    monkeypatch.chdir(tmp_path)
    bench._persist({'metric': 'm'})
    bench._mark_run_started()
    assert list(tmp_path.iterdir()) == []


def test_stage_config_cli_pairing():
    """--stage/--config/--out must be validated together at parse time —
    a mismatch discovered after the backend claim burns a chip-session
    stage budget (r5s3 lesson)."""
    import subprocess
    import sys

    cases = [
        (['--stage', 'resnet', '--config', 'large'], 'not a resnet config'),
        (['--config', 'large'], 'requires --stage'),
        (['--stage', 'lm'], 'requires --config'),
        (['--stage', 'lm', '--config', 'tiny'], 'requires --out'),
    ]
    bench_path = bench.os.path.abspath(bench.__file__)
    for argv, needle in cases:
        r = subprocess.run(
            [sys.executable, bench_path, *argv],
            capture_output=True, text=True,
            env={**bench.os.environ, 'JAX_PLATFORMS': 'cpu'},
        )
        assert r.returncode == 2, (argv, r.returncode, r.stderr)
        assert needle in r.stderr, (argv, r.stderr)


def test_orchestrator_tpu_plan_routes_stages(tmp_path, monkeypatch):
    """The TPU plan dispatches each stage with the right --stage/--config
    pair (incl. the opportunistic lm_large / resnet32_cifar tail), gates
    lm_flagship_pallas on micro_pallas, and lifts the flagship to the
    headline with opportunistic results as summary fields."""
    monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    calls = _fake_stages(monkeypatch, tmp_path, platform='tpu')
    result = {'metric': 'm', 'value': 0.0, 'platform': 'unknown'}
    bench._orchestrate(result)

    by_name = {c[0]: c[1] for c in calls}
    order = [c[0] for c in calls]
    assert order[:3] == ['micro_safe', 'lm_tiny', 'lm_flagship']
    assert order[-1] == 'acc'
    assert {'lm_large', 'resnet32_cifar'} <= set(order)

    def cfg_of(name):
        a = by_name[name]
        return a[a.index('--stage') + 1], a[a.index('--config') + 1]

    assert cfg_of('lm_tiny') == ('lm', 'tiny')
    assert cfg_of('lm_flagship') == ('lm', 'flagship')
    assert cfg_of('lm_large') == ('lm', 'large')
    assert cfg_of('resnet32_cifar') == ('resnet', 'resnet32_cifar')
    assert result['headline_stage'] == 'lm_flagship'
    assert result['large_mfu'] == 0.3
    assert result['resnet32_vs_baseline'] == 0.9
    assert result['resnet32_kfac_images_per_sec'] == 500.0
    # the kernel-enabled flagship rode along, never the headline
    assert result['pallas_tokens_per_sec'] == 90.0


@pytest.mark.slow
def test_resnet_stage_end_to_end_cpu(tmp_path, monkeypatch):
    """The vision stage runs a real SGD-vs-K-FAC measurement on a tiny
    config (this guards the stage code path itself)."""
    monkeypatch.setitem(
        bench._RESNET_CONFIGS, 'tiny_test',
        dict(arch='resnet20', batch=4, hw=32, classes=10),
    )
    out = tmp_path / 'rs.json'
    bench.run_resnet_stage('tiny_test', str(out))
    rec = json.loads(out.read_text())
    assert rec['ok'] and rec['vs_baseline'] > 0
    assert rec['n_kfac_layers'] == 20
    assert rec['sgd_images_per_sec'] > 0 and rec['kfac_images_per_sec'] > 0


@pytest.mark.slow
def test_async_spike_probe_flattens_refresh_spike():
    """ISSUE-6 acceptance: at d>=512 the sliced async backend holds the
    per-step refresh spike to <= 1.5x the median step, where the
    synchronous boundary refresh spikes multi-x."""
    out = bench._async_spike_probe(windows=2)
    assert out['refresh_spike_ratio'] <= 1.5, out
    assert out['refresh_spike_ratio_sync'] > out['refresh_spike_ratio'], out
    for k in ('step_p50_ms', 'step_p95_ms', 'step_max_ms'):
        assert out[k] > 0 and out[f'{k}_sync'] > 0


def test_pipeline_probe_folds_committed_bubble_table():
    """The pipeline probe republishes the committed measured-vs-simulated
    schedule table with its one-dispatch harness provenance, read-only."""
    out = bench._pipeline_probe()
    assert out['status'] == 'ok'
    assert out['clean_rows'] >= len(out['rows']) // 2
    covered = {(r['schedule'], r['p'], r['v']) for r in out['rows']}
    assert {('1f1b', 2, 1), ('interleaved', 4, 2)} <= covered
    for r in out['rows']:
        assert 0.0 <= r['predicted_fraction'] < 1.0
        assert r['wall_clock_p50_s'] > 0.0
        if not r['contaminated']:
            assert abs(
                r['measured_fraction'] - r['predicted_fraction']
            ) <= out['tolerance']
    harness = out['provenance']['harness']
    assert harness['harness_version'] == 2
    assert harness['dispatches'] == 1
