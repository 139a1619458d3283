#!/usr/bin/env python
"""Unified run-ledger CLI: cross-stream timelines and the bench sentinel.

Offline triage and CI gating over the ledger layer
(``kfac_tpu/observability/ledger.py``, see docs/OBSERVABILITY.md "Run
ledger"):

    # correlated anomaly timeline over a run directory of stream files
    python tools/kfac_ledger.py --timeline runs/2026-08-06/

    # build a perf baseline from round records (the paths are the
    # defaults; the repository commits neither rounds nor a baseline)
    python tools/kfac_ledger.py --build-baseline bench_runs/run_*.json \\
        --out bench_runs/LEDGER.json

    # gate one round against the baseline (CI: nonzero exit on
    # regression); exit 0 ok, 1 regressed, 2 provenance refused
    python tools/kfac_ledger.py --check bench_runs/run_X.json \\
        --baseline bench_runs/LEDGER.json

Deliberately runnable on machines without jax: the ledger module is
loaded standalone from its file, never through the package ``__init__``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from typing import Any

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_ledger() -> Any:
    """Load the stdlib-only ledger module without importing kfac_tpu
    (whose ``__init__`` imports jax)."""
    path = os.path.join(
        _REPO_ROOT, 'kfac_tpu', 'observability', 'ledger.py')
    spec = importlib.util.spec_from_file_location('_kfac_ledger', path)
    assert spec is not None and spec.loader is not None
    module = importlib.util.module_from_spec(spec)
    # dataclasses resolves the defining module through sys.modules
    sys.modules['_kfac_ledger'] = module
    spec.loader.exec_module(module)
    return module


def _load_round(path: str) -> dict[str, Any]:
    with open(path, encoding='utf-8') as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f'{path}: bench round must be a JSON object')
    return data


def _timeline(ledger: Any, path: str, as_json: bool) -> int:
    led = ledger.RunLedger()
    if os.path.isdir(path):
        counts = led.ingest_dir(path)
        if not counts:
            print(f'error: no recognizable stream files under {path}',
                  file=sys.stderr)
            return 2
    else:
        # a single mixed JSONL: compile heartbeats + metric records
        records = []
        with open(path, encoding='utf-8') as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
        compile_recs = [r for r in records
                        if r.get('kind') == 'compile' and 'phase' in r]
        metric_recs = [r for r in records if r not in compile_recs]
        if compile_recs:
            led.ingest('compile', compile_recs)
        if metric_recs:
            led.ingest('metrics', metric_recs)
        led.assign_steps()
    if as_json:
        json.dump(ledger.timeline_report(led), sys.stdout, indent=2,
                  sort_keys=True)
        print()
    else:
        sys.stdout.write(ledger.render_timeline(led))
    return 0


def _check(ledger: Any, round_path: str, baseline_path: str,
           as_json: bool) -> int:
    round_json = _load_round(round_path)
    baseline = None
    if os.path.exists(baseline_path):
        baseline = ledger.load_baseline(baseline_path)
    verdict = ledger.sentinel_check(round_json, baseline)
    if as_json:
        json.dump(verdict, sys.stdout, indent=2, sort_keys=True)
        print()
    else:
        status = verdict['status']
        print(f'ledger sentinel: {status}'
              + (f" ({verdict['reason']})" if status == 'refused' else ''))
        for key, entry in sorted(verdict['keys'].items()):
            ratio = entry.get('ratio')
            print(f"  {key:<22} {entry['verdict']:<10}"
                  f" measured={entry['measured']}"
                  f" baseline={entry['baseline']:g}"
                  f" tol={entry['tolerance']:g} ({entry['direction']})"
                  + (f' ratio={ratio:.3f}' if ratio is not None else ''))
        if verdict['regressed_keys']:
            print('  REGRESSED: ' + ', '.join(verdict['regressed_keys']))
    if verdict['status'] == 'regressed':
        return 1
    if verdict['status'] == 'refused':
        return 2
    return 0


def _build_baseline(ledger: Any, round_paths: list[str], out: str,
                    window: int | None) -> int:
    rounds = [_load_round(p) for p in round_paths]
    config = ledger.LedgerConfig(sentinel_window=window) if window \
        else ledger.LedgerConfig()
    baseline = ledger.build_baseline(
        rounds, config=config,
        sources=[os.path.basename(p) for p in round_paths])
    ledger.save_baseline(out, baseline)
    print(f"wrote {out}: platform={baseline['platform']}"
          f" rounds={baseline['n_rounds']}"
          f" (dropped {baseline['n_dropped_provenance']} off-provenance)"
          f" keys={','.join(sorted(baseline['keys']))}")
    return 0


def selftest() -> int:
    """Processless checks of the full ledger surface: adapters,
    correlation, sentinel verdicts, baseline determinism."""
    import tempfile
    ledger = _load_ledger()

    # header vs header-less run identification
    events = ledger.parse_metrics([
        ledger.run_header('abc123', 'metrics'),
        {'step': 0, 'loss': 1.0}])
    assert events[0]['run_id'] == 'abc123', events
    bare = ledger.parse_metrics([{'step': 0, 'loss': 1.0}])
    assert bare[0]['run_id'] is None, bare

    # correlated timeline over synthesized streams joins >= 3 streams
    led = ledger.RunLedger()
    led.ingest('chaos', [{'event': 'step', 'step': s, 't': 500.0 + s}
                         for s in (0, 4, 8)])
    led.ingest('compile', [
        {'kind': 'compile', 'phase': 'lowering', 'entry': 'kfac.step',
         'n': 2, 'pid': 7, 't': 503.1},
        {'kind': 'compile', 'phase': 'done', 'entry': 'kfac.step',
         'n': 2, 'pid': 7, 't': 503.9}])
    led.ingest('metrics', [
        {'step': s, 'step_time_s': 0.5 if s == 4 else 0.1}
        for s in range(8)])
    led.ingest('calibration', [{'step': 5, 'calib/model_error': 2.0}])
    led.ingest('fleet', [{'event': 'armed', 'step': 6, 'detail': ''}])
    led.assign_steps()
    annotations = led.correlations()
    cascade = [a for a in annotations if a['rule'] == 'recompile_cascade']
    assert cascade and len(cascade[0]['streams']) >= 3, annotations
    text = ledger.render_timeline(led)
    assert 'recompile_cascade' in text and 'step_time_spike' in text, text
    assert ledger.render_timeline(led) == text  # deterministic

    # clean negative: no recompile -> no cascade
    led2 = ledger.RunLedger()
    led2.ingest('metrics', [
        {'step': s, 'step_time_s': 0.5 if s == 4 else 0.1}
        for s in range(8)])
    led2.ingest('fleet', [{'event': 'armed', 'step': 6, 'detail': ''}])
    assert not [a for a in led2.correlations()
                if a['rule'].startswith('recompile')], led2.correlations()

    # died-compiling + divergence verdicts surface in ONE report
    led3 = ledger.RunLedger()
    led3.ingest('compile', [
        {'kind': 'compile', 'phase': 'lowering', 'entry': 'trainer.step',
         'n': 1, 'pid': 9, 't': 1.0}])
    led3.ingest('metrics', [{'step': 3, 'loss': float('nan')}])
    report = ledger.timeline_report(led3)
    assert 'died compiling trainer.step' in report['verdicts']['compile']
    assert 'nonfinite_loss' in report['verdicts']['divergence']

    # sentinel: pass / 1.5x regression / provenance refusal
    rounds = [{'parsed': {'platform': 'cpu', 'device_kind': 'cpu',
                          'value': 100.0 + n, 'sgd_tokens_per_sec': 140.0}}
              for n in range(5)]
    base = ledger.build_baseline(rounds, sources=['r%d' % n
                                                  for n in range(5)])
    ok = ledger.sentinel_check(
        {'parsed': {'platform': 'cpu', 'value': 101.0,
                    'sgd_tokens_per_sec': 139.0}}, base)
    assert ok['status'] == 'ok', ok
    bad = ledger.sentinel_check(
        {'parsed': {'platform': 'cpu', 'value': 102.0 / 1.5,
                    'sgd_tokens_per_sec': 139.0}}, base)
    assert bad['status'] == 'regressed', bad
    assert bad['regressed_keys'] == ['value'], bad
    refused = ledger.sentinel_check(
        {'parsed': {'platform': 'tpu', 'value': 1e6}}, base)
    assert refused['status'] == 'refused' and not refused['keys'], refused
    none = ledger.sentinel_check({'parsed': {'platform': 'cpu'}}, None)
    assert none['status'] == 'no_baseline', none

    # baseline artifact: atomic, deterministic, schema-checked
    with tempfile.TemporaryDirectory() as tmp:
        p1 = os.path.join(tmp, 'a.json')
        p2 = os.path.join(tmp, 'b.json')
        ledger.save_baseline(p1, base)
        ledger.save_baseline(p2, base)
        b1 = open(p1, 'rb').read()
        assert b1 == open(p2, 'rb').read()
        assert ledger.load_baseline(p1) == base
        with open(p1, 'w') as f:
            json.dump({'kind': 'something_else'}, f)
        try:
            ledger.load_baseline(p1)
            raise AssertionError('expected ValueError')
        except ValueError:
            pass
    print('kfac_ledger selftest: ok')
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--timeline', metavar='PATH',
                        help='run directory (or mixed JSONL) to render '
                             'as a correlated anomaly timeline')
    parser.add_argument('--build-baseline', nargs='+', metavar='ROUND',
                        help='bench round JSONs to fold into a baseline')
    parser.add_argument('--out', default='bench_runs/LEDGER.json',
                        help='baseline output path for --build-baseline')
    parser.add_argument('--window', type=int, default=None,
                        help='override the sentinel median window')
    parser.add_argument('--check', metavar='ROUND',
                        help='bench round JSON to gate against --baseline')
    parser.add_argument('--baseline', default='bench_runs/LEDGER.json',
                        help='baseline artifact for --check')
    parser.add_argument('--json', action='store_true',
                        help='emit machine-readable JSON instead of text')
    parser.add_argument('--selftest', action='store_true',
                        help='run the built-in checks and exit')
    args = parser.parse_args(argv)

    if args.selftest:
        return selftest()
    ledger = _load_ledger()
    if args.timeline:
        return _timeline(ledger, args.timeline, args.json)
    if args.build_baseline:
        return _build_baseline(
            ledger, args.build_baseline, args.out, args.window)
    if args.check:
        return _check(ledger, args.check, args.baseline, args.json)
    parser.error(
        'one of --timeline / --build-baseline / --check / --selftest '
        'is required')
    return 2


if __name__ == '__main__':
    sys.exit(main())
