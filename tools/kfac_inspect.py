#!/usr/bin/env python
"""Offline triage for K-FAC telemetry: divergence timelines from JSONL
metric logs or flight-recorder postmortem bundles.

Given either

- a metrics JSONL file (``observability.JSONLWriter`` output, one record
  per drain), or
- a postmortem bundle directory written by
  ``observability.PostmortemWriter`` (detected by ``MANIFEST.json``),

this prints what a paged-in human needs first: *which layer went bad
first, and when* — the step each layer's factor bounds first blew up or
went non-finite, when damping escalated, when the KL clip started biting,
where skip-step gaps appear in the recorded step sequence, and the first
non-finite loss. For bundles it also summarizes the trigger, health
counters, topology fingerprint, the comms/padding report, and the
compile-watch event tail (compile counts, recompiles, XLA memory).

A third input kind is the compile-watch heartbeat journal
(``CompileWatchConfig.journal_path`` — ``phase: lowering -> compiling ->
done`` records, fsynced before each blocking phase). A journal whose
last heartbeat for some entry never reached ``done`` yields the
"died compiling X" verdict: the entry name, the phase it died in, and
the elapsed time the journal proves — the postmortem for a run killed
mid-compile. Mixed files work:
compile records and metric records are partitioned and each analyzed.

Deliberately dependency-free (stdlib only — no jax, no numpy): bundles
are meant to be inspected on any machine, including ones without the
training environment.

``--timeline`` routes the input through the unified run ledger
(``kfac_tpu/observability/ledger.py``, loaded standalone — still no jax)
instead of the two separate analyses: a run directory of stream files
(or one mixed JSONL) becomes a single correlated anomaly timeline where
the "died compiling X" verdict and the divergence first-bad-signal
verdict from the same run appear in ONE report, joined across streams by
the ledger's correlation rules (see docs/OBSERVABILITY.md "Run ledger").

Usage:

    python tools/kfac_inspect.py metrics.jsonl
    python tools/kfac_inspect.py postmortems/postmortem-step00000042-skip
    python tools/kfac_inspect.py --timeline tests/data/mini_ledger
    python tools/kfac_inspect.py --json BUNDLE_OR_JSONL
    python tools/kfac_inspect.py --selftest

Run via ``make inspect BUNDLE=...``; ``--selftest`` (wired into
``make obs``) checks the analysis against synthesized divergences.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Any

#: factor-bound magnitude treated as "blown up" — matches the health
#: sentinel's default quarantine_threshold
HUGE = 1e8

#: damping_eff ratio over its own first observed value that counts as an
#: escalation event (the sentinel's default escalation step is 10x)
DAMPING_JUMP = 2.0

#: kl_clip_scale below this means the clip is biting hard
KL_HARD = 0.5

#: event-kind severity order for first-bad-layer tie-breaks (worst first)
_SEVERITY = ('nonfinite_factor', 'huge_factor', 'damping_escalation')


def _finite(v: Any) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


# ----------------------------------------------------------------- loading


def load_jsonl(path: str) -> list[dict[str, Any]]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    records.sort(key=lambda r: r.get('step', -1))
    return records


def load_bundle(bdir: str) -> dict[str, Any]:
    """Read the JSON half of a postmortem bundle (history.npz is the
    lossless archive; the JSONL mirror is what triage needs)."""
    bundle: dict[str, Any] = {'dir': bdir}
    with open(os.path.join(bdir, 'MANIFEST.json')) as f:
        bundle['manifest'] = json.load(f)
    hist = os.path.join(bdir, 'history.jsonl')
    bundle['history'] = load_jsonl(hist) if os.path.exists(hist) else []
    events = os.path.join(bdir, 'compile_events.jsonl')
    bundle['compile_events'] = (
        load_jsonl(events) if os.path.exists(events) else [])
    for name in ('health', 'comms', 'fingerprint', 'factors',
                 'compile_memory'):
        path = os.path.join(bdir, f'{name}.json')
        if os.path.exists(path):
            with open(path) as f:
                bundle[name] = json.load(f)
    return bundle


def split_compile_records(
    records: list[dict[str, Any]],
) -> tuple[list[dict[str, Any]], list[dict[str, Any]]]:
    """Partition a JSONL into (compile heartbeats, metric records) so a
    compile-watch journal — or a mixed log — routes to both analyses."""
    compile_recs: list[dict[str, Any]] = []
    metric_recs: list[dict[str, Any]] = []
    for r in records:
        if r.get('kind') == 'compile' and 'phase' in r:
            compile_recs.append(r)
        else:
            metric_recs.append(r)
    return compile_recs, metric_recs


# ---------------------------------------------------------------- analysis


def analyze(records: list[dict[str, Any]]) -> dict[str, Any]:
    """Divergence timeline over chronological drain/ring records.

    Returns ``{'events': [{'step', 'kind', 'layer'?, 'detail'}...],
    'first_bad_layer': {...}|None, 'steps': [lo, hi], 'n_records': N,
    'gaps': [[lo, hi]...]}``. Events are ordered by step, then severity.
    """
    events: list[dict[str, Any]] = []
    first_damping: dict[str, float] = {}
    seen: set[tuple[str, str]] = set()  # (kind, layer/key) fired once

    def fire(step: int, kind: str, layer: str | None, detail: str,
             dedup: str | None = None) -> None:
        key = (kind, dedup if dedup is not None else (layer or ''))
        if key in seen:
            return
        seen.add(key)
        ev: dict[str, Any] = {'step': step, 'kind': kind, 'detail': detail}
        if layer is not None:
            ev['layer'] = layer
        events.append(ev)

    steps = [int(r['step']) for r in records if 'step' in r]
    gaps: list[list[int]] = []
    for prev, cur in zip(steps, steps[1:]):
        if cur > prev + 1:
            gaps.append([prev + 1, cur - 1])

    for rec in records:
        step = int(rec.get('step', -1))
        loss = rec.get('loss')
        if loss is not None and not _finite(loss):
            fire(step, 'nonfinite_loss', None, f'loss = {loss}')
        for k, v in rec.items():
            if k.startswith(('factor_lmin/', 'factor_lmax/')):
                _, side, layer = k.split('/', 2)
                if not _finite(v):
                    fire(step, 'nonfinite_factor', layer,
                         f'{k} = {v}', dedup=f'{layer}/{side}')
                elif abs(v) >= HUGE:
                    fire(step, 'huge_factor', layer,
                         f'{k} = {v:.3g} (>= {HUGE:g})',
                         dedup=f'{layer}/{side}')
            elif k.startswith('damping_eff/') and _finite(v):
                layer = k.split('/', 1)[1]
                base = first_damping.setdefault(layer, float(v))
                if base > 0 and v >= DAMPING_JUMP * base:
                    fire(step, 'damping_escalation', layer,
                         f'{k}: {base:.3g} -> {v:.3g} '
                         f'({v / base:.1f}x)')
            elif k == 'kl_clip_scale' and _finite(v) and v < KL_HARD:
                fire(step, 'kl_clip_hard', None,
                     f'kl_clip_scale = {v:.3g} (< {KL_HARD})')
            elif k == 'grad_norm' and not _finite(v):
                fire(step, 'nonfinite_grad_norm', None, f'grad_norm = {v}')

    for lo, hi in gaps:
        n = hi - lo + 1
        events.append({
            'step': lo, 'kind': 'step_gap',
            'detail': (f'steps {lo}..{hi} unrecorded ({n} missing — '
                       'skip-step gate or drain cadence)'),
        })

    sev = {k: i for i, k in enumerate(_SEVERITY)}
    events.sort(key=lambda e: (e['step'], sev.get(e['kind'], len(sev))))

    first_bad = None
    for ev in events:
        if ev['kind'] in _SEVERITY and 'layer' in ev:
            first_bad = {'layer': ev['layer'], 'step': ev['step'],
                         'kind': ev['kind'], 'detail': ev['detail']}
            break

    return {
        'n_records': len(records),
        'steps': [min(steps), max(steps)] if steps else None,
        'gaps': gaps,
        'events': events,
        'first_bad_layer': first_bad,
    }


def analyze_compile_journal(
    records: list[dict[str, Any]],
) -> dict[str, Any]:
    """Triage a compile-watch heartbeat journal.

    Each compilation journals ``lowering -> compiling -> done`` records
    (fsynced before the blocking phase they announce), so the last
    heartbeat of a killed process is trustworthy. Returns::

        {'entries': {entry: {'compiles': N, 'total_compile_s': S}},
         'in_flight': [{'entry', 'phase', 'elapsed_s', ...}...],
         'verdict': 'died compiling ...' | None}

    ``in_flight`` lists compilations that never reached ``done`` —
    normally empty; after a mid-compile death it names the culprit.
    """
    entries: dict[str, dict[str, Any]] = {}
    open_compiles: dict[tuple[Any, Any, Any], dict[str, Any]] = {}
    for rec in records:
        phase = rec.get('phase')
        entry = rec.get('entry')
        key = (rec.get('pid'), entry, rec.get('n'))
        if phase == 'lowering':
            fp = rec.get('fingerprint') or {}
            open_compiles[key] = {
                'entry': entry,
                'phase': 'lowering',
                'started_t': rec.get('t'),
                'last_t': rec.get('t'),
                'pid': rec.get('pid'),
                'n_args': len(fp),
                'diff': rec.get('diff') or [],
            }
        elif key in open_compiles:
            oc = open_compiles[key]
            oc['last_t'] = rec.get('t', oc['last_t'])
            if phase == 'done':
                agg = entries.setdefault(
                    entry, {'compiles': 0, 'total_compile_s': 0.0})
                agg['compiles'] += 1
                agg['total_compile_s'] += float(rec.get('compile_s') or 0.0)
                del open_compiles[key]
            else:
                oc['phase'] = phase
                if rec.get('lowering_s') is not None:
                    oc['lowering_s'] = rec['lowering_s']

    in_flight = []
    for oc in open_compiles.values():
        started, last = oc.get('started_t'), oc.get('last_t')
        if isinstance(started, (int, float)) and isinstance(
                last, (int, float)):
            oc['elapsed_s'] = max(0.0, float(last) - float(started))
        in_flight.append(oc)

    verdict = None
    if in_flight:
        worst = in_flight[-1]  # journal order: the last one written
        elapsed = worst.get('elapsed_s')
        after = (f' after >= {elapsed:.1f}s'
                 if isinstance(elapsed, float) else '')
        verdict = (
            f"died compiling {worst['entry']!r}{after}: last heartbeat "
            f"in phase {worst['phase']!r} never reached 'done' "
            f"({worst.get('n_args', '?')} fingerprinted arg leaves, "
            f"pid {worst.get('pid', '?')})")
    return {'entries': entries, 'in_flight': in_flight, 'verdict': verdict}


# ---------------------------------------------------------------- printing


def _print_analysis(analysis: dict[str, Any]) -> None:
    span = analysis['steps']
    span_s = f'steps {span[0]}..{span[1]}' if span else 'no steps'
    print(f"{analysis['n_records']} records, {span_s}, "
          f"{len(analysis['gaps'])} gap(s)")
    if not analysis['events']:
        print('timeline: no divergence events detected')
    else:
        print('timeline:')
        for ev in analysis['events']:
            layer = f" [{ev['layer']}]" if 'layer' in ev else ''
            print(f"  step {ev['step']:>6}  {ev['kind']}{layer}: "
                  f"{ev['detail']}")
    fb = analysis['first_bad_layer']
    if fb:
        print(f"first bad layer: {fb['layer']} — {fb['kind']} at "
              f"step {fb['step']} ({fb['detail']})")
    else:
        print('first bad layer: none (no per-layer factor/damping events)')


def _print_compile_analysis(comp: dict[str, Any]) -> None:
    entries = comp['entries']
    total = sum(e['compiles'] for e in entries.values())
    print(f"compile journal: {total} completed compilation(s) across "
          f"{len(entries)} entry(ies)")
    for name, agg in sorted(entries.items()):
        print(f"  {name}: {agg['compiles']} compile(s), "
              f"{agg['total_compile_s']:.2f}s total")
    if comp['verdict']:
        print(f"VERDICT: {comp['verdict']}")
    else:
        print('no in-flight compilations: every heartbeat reached done')


def _print_compile_events(bundle: dict[str, Any]) -> None:
    events = bundle.get('compile_events') or []
    if not events:
        return
    counts: dict[str, int] = {}
    for ev in events:
        counts[ev.get('entry', '?')] = counts.get(ev.get('entry', '?'), 0) + 1
    recompiles = sum(c - 1 for c in counts.values() if c > 1)
    print(f"  compile events: {len(events)} compile(s) over "
          f"{len(counts)} entry(ies), {recompiles} recompile(s)")
    last = events[-1]
    diff = last.get('diff') or []
    if diff:
        print(f"    last recompile ({last.get('entry')}): {diff[0]}" +
              (f' (+{len(diff) - 1} more)' if len(diff) > 1 else ''))
    memory = bundle.get('compile_memory') or {}
    for name, snap in sorted(memory.items()):
        hbm = snap.get('hbm_bytes')
        if hbm:
            print(f"    {name}: XLA memory {hbm / 1e6:.2f} MB "
                  f"(arg+out+temp)")


def _print_bundle_header(bundle: dict[str, Any]) -> None:
    man = bundle['manifest']
    print(f"postmortem bundle: {bundle['dir']}")
    print(f"  reason: {man.get('reason')}  step: {man.get('step')}  "
          f"process: {man.get('process_index')}  "
          f"schema: {man.get('schema')}")
    fp = bundle.get('fingerprint', {})
    if fp:
        mesh = fp.get('mesh')
        mesh_s = (f"  mesh {mesh['axis_names']}x{mesh['shape']}"
                  if mesh else '')
        print(f"  jax {fp.get('jax')} ({fp.get('backend')}, "
              f"{fp.get('device_count')} device(s), "
              f"{fp.get('process_count')} process(es)){mesh_s}")
    health = bundle.get('health', {})
    if health.get('enabled'):
        skipped = health.get('skipped_steps', 0)
        layers = health.get('layers', {})
        flagged = {n: e for n, e in layers.items()
                   if e.get('status') != 'ok'}
        print(f"  health: {skipped} skipped step(s), "
              f"{len(flagged)}/{len(layers)} layer(s) flagged")
        for n, e in sorted(flagged.items()):
            print(f"    {n}: {e.get('status')} "
                  f"(damping_mult={e.get('damping_mult')}, "
                  f"bad_inv={e.get('bad_inv')}, "
                  f"quarantine_events={e.get('quarantine_events')})")
    comms = bundle.get('comms')
    if comms:
        st = comms.get('stat_transport', {})
        totals = comms.get('padding_totals', {})
        print(f"  comms: stat transport {st.get('method', '?')} "
              f"{st.get('bytes', '?')} B, grad broadcast "
              f"{comms.get('grad_broadcast_bytes', '?')} B, padding fill "
              f"{totals.get('fill', '?')}")
    _print_compile_events(bundle)


# ---------------------------------------------------------------- selftest


def selftest() -> int:
    """Analysis checks against synthesized divergences (no JAX needed)."""
    base = {'kl_clip_scale': 1.0,
            'damping_eff/fc1': 0.003, 'damping_eff/fc2': 0.003,
            'factor_lmin/a/fc1': 0.1, 'factor_lmax/a/fc1': 2.0,
            'factor_lmin/g/fc1': 0.1, 'factor_lmax/g/fc1': 2.0,
            'factor_lmin/a/fc2': 0.1, 'factor_lmax/a/fc2': 2.0,
            'factor_lmin/g/fc2': 0.1, 'factor_lmax/g/fc2': 2.0}
    records = []
    for s in range(8):
        rec = dict(base, step=s, loss=1.0 / (s + 1), grad_norm=1.0)
        if s >= 4:  # fc2's A factor blows up first...
            rec['factor_lmax/a/fc2'] = 3e9
        if s >= 5:  # ...then its damping escalates...
            rec['damping_eff/fc2'] = 0.03
        if s >= 6:  # ...fc1 follows with a non-finite bound...
            rec['factor_lmax/g/fc1'] = float('inf')
        if s == 7:  # ...and finally the loss goes over
            rec['loss'] = float('nan')
        records.append(rec)
    del records[3]  # a skipped step leaves a gap

    a = analyze(records)
    assert a['n_records'] == 7 and a['steps'] == [0, 7], a
    assert a['gaps'] == [[3, 3]], a['gaps']
    fb = a['first_bad_layer']
    assert fb and fb['layer'] == 'fc2' and fb['step'] == 4, fb
    assert fb['kind'] == 'huge_factor', fb
    kinds = [(e['step'], e['kind']) for e in a['events']]
    assert (4, 'huge_factor') in kinds
    assert (5, 'damping_escalation') in kinds
    assert (6, 'nonfinite_factor') in kinds
    assert (7, 'nonfinite_loss') in kinds
    # events fire once per (kind, layer/side), not once per record
    assert sum(1 for _, k in kinds if k == 'huge_factor') == 1

    # a clean run has an empty timeline
    clean = analyze([dict(base, step=s, loss=1.0, grad_norm=1.0)
                     for s in range(4)])
    assert clean['events'] == [] and clean['first_bad_layer'] is None

    # compile journal: a completed compile plus one killed mid-compile
    # (lowering + compiling heartbeats, never done) yields the verdict
    journal = [
        {'kind': 'compile', 'phase': 'lowering', 'entry': 'kfac.step',
         'n': 1, 'pid': 41, 't': 100.0,
         'fingerprint': {'[0]': {'shape': [8, 8], 'dtype': 'float32'}}},
        {'kind': 'compile', 'phase': 'compiling', 'entry': 'kfac.step',
         'n': 1, 'pid': 41, 't': 100.5, 'lowering_s': 0.5},
        {'kind': 'compile', 'phase': 'done', 'entry': 'kfac.step',
         'n': 1, 'pid': 41, 't': 103.0, 'compile_s': 2.5},
        {'kind': 'compile', 'phase': 'lowering', 'entry': 'trainer.step',
         'n': 1, 'pid': 41, 't': 110.0,
         'fingerprint': {'[0]': {'shape': [64, 6], 'dtype': 'float32'},
                         '[1]': {'shape': [64, 4], 'dtype': 'float32'}}},
        {'kind': 'compile', 'phase': 'compiling', 'entry': 'trainer.step',
         'n': 1, 'pid': 41, 't': 112.0, 'lowering_s': 2.0},
        # SIGKILL here: no 'done' for trainer.step
    ]
    comp = analyze_compile_journal(journal)
    assert comp['entries'] == {
        'kfac.step': {'compiles': 1, 'total_compile_s': 2.5}}, comp
    assert len(comp['in_flight']) == 1, comp
    flight = comp['in_flight'][0]
    assert flight['entry'] == 'trainer.step', flight
    assert flight['phase'] == 'compiling', flight
    assert flight['elapsed_s'] == 2.0, flight
    assert comp['verdict'] and 'trainer.step' in comp['verdict']
    assert "'compiling'" in comp['verdict']
    # a clean journal (every compile reached done) has no verdict
    clean_comp = analyze_compile_journal(journal[:3])
    assert clean_comp['verdict'] is None and not clean_comp['in_flight']
    # the partitioner routes mixed files to both analyses
    c_recs, m_recs = split_compile_records(journal + records)
    assert len(c_recs) == len(journal) and len(m_recs) == len(records)

    # bundle round-trip on a synthesized minimal bundle
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        bdir = os.path.join(tmp, 'postmortem-step00000007-nonfinite')
        os.makedirs(bdir)
        with open(os.path.join(bdir, 'MANIFEST.json'), 'w') as f:
            json.dump({'schema': 1, 'reason': 'nonfinite', 'step': 7,
                       'process_index': 0, 'record': {},
                       'files': ['history.jsonl',
                                 'compile_events.jsonl']}, f)
        with open(os.path.join(bdir, 'history.jsonl'), 'w') as f:
            for rec in records:
                f.write(json.dumps(rec) + '\n')
        with open(os.path.join(bdir, 'compile_events.jsonl'), 'w') as f:
            f.write(json.dumps({
                'entry': 'kfac.step', 'n': 2, 'compile_s': 1.5,
                'diff': ['[0][0]: dim 0 32 -> 64'],
                'memory': {'argument_size_in_bytes': 1024}}) + '\n')
        bundle = load_bundle(bdir)
        a2 = analyze(bundle['history'])
        assert a2['first_bad_layer']['layer'] == 'fc2'
        assert bundle['manifest']['reason'] == 'nonfinite'
        assert bundle['compile_events'][0]['entry'] == 'kfac.step'
        assert bundle['compile_events'][0]['diff'] == [
            '[0][0]: dim 0 32 -> 64']
    # --timeline: a mixed journal (killed mid-compile) + diverging
    # metrics routes BOTH verdicts through the ledger into one report
    ledger = _load_ledger()
    led = ledger.RunLedger()
    c_recs, m_recs = split_compile_records(journal[3:5] + records)
    led.ingest('compile', c_recs)
    led.ingest('metrics', m_recs)
    report = ledger.timeline_report(led)
    assert 'died compiling trainer.step' in report['verdicts']['compile'], \
        report['verdicts']
    assert 'first bad signal' in report['verdicts']['divergence'], \
        report['verdicts']

    print('kfac_inspect selftest ok')
    return 0


# -------------------------------------------------------------------- main


def _load_ledger() -> Any:
    """Load the stdlib-only ledger module from its file, bypassing the
    package ``__init__`` (which imports jax)."""
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'kfac_tpu', 'observability', 'ledger.py')
    spec = importlib.util.spec_from_file_location('_kfac_ledger', path)
    assert spec is not None and spec.loader is not None
    module = importlib.util.module_from_spec(spec)
    sys.modules['_kfac_ledger'] = module
    spec.loader.exec_module(module)
    return module


def timeline(path: str, as_json: bool = False) -> int:
    """One correlated report over a run directory or a mixed JSONL:
    compile verdicts and divergence verdicts from the same run, joined
    by the ledger instead of two separate CLI invocations."""
    ledger = _load_ledger()
    led = ledger.RunLedger()
    if os.path.isdir(path):
        if not led.ingest_dir(path):
            print(f'error: no recognizable stream files under {path}',
                  file=sys.stderr)
            return 2
    else:
        records = load_jsonl(path)
        compile_recs, metric_recs = split_compile_records(records)
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


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('path', nargs='?',
                        help='metrics JSONL file or postmortem bundle dir')
    parser.add_argument('--timeline', action='store_true',
                        help='render PATH (run dir or mixed JSONL) as a '
                             'correlated cross-stream anomaly timeline')
    parser.add_argument('--json', action='store_true',
                        help='emit the analysis as JSON instead of text')
    parser.add_argument('--selftest', action='store_true',
                        help='run the built-in analysis checks and exit')
    args = parser.parse_args(argv)

    if args.selftest:
        return selftest()
    if not args.path:
        parser.error('PATH required (or --selftest)')
    if args.timeline:
        return timeline(args.path, as_json=args.json)

    bundle = None
    if os.path.isdir(args.path):
        if not os.path.exists(os.path.join(args.path, 'MANIFEST.json')):
            print(f'error: {args.path} is a directory without '
                  'MANIFEST.json — not a postmortem bundle',
                  file=sys.stderr)
            return 2
        bundle = load_bundle(args.path)
        records = bundle['history']
    else:
        records = load_jsonl(args.path)

    compile_recs, metric_recs = split_compile_records(records)
    compile_analysis = (
        analyze_compile_journal(compile_recs) if compile_recs else None)
    analysis = analyze(metric_recs)
    if args.json:
        out = dict(analysis)
        if compile_analysis is not None:
            out['compile'] = compile_analysis
        if bundle is not None:
            out['manifest'] = bundle['manifest']
        json.dump(out, sys.stdout, indent=2)
        print()
        return 0
    if bundle is not None:
        _print_bundle_header(bundle)
    if compile_analysis is not None:
        _print_compile_analysis(compile_analysis)
    if metric_recs or compile_analysis is None:
        _print_analysis(analysis)
    return 0


if __name__ == '__main__':
    sys.exit(main())
