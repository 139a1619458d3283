#!/usr/bin/env python
"""Chaos-harness CLI: run a preemption storm, commit its SLO artifact.

Drives :class:`kfac_tpu.resilience.chaos.ChaosConductor` — a real
multi-process gloo pod under scripted or seeded preemption storms —
and writes the reconciled :class:`ChaosReport` JSON. The committed
artifact (``kfac_tpu/resilience/chaos_slo.json``) is what the
docs/ROBUSTNESS.md SLO table folds in.

Usage:

    python tools/kfac_chaos.py --selftest
        No-process sanity pass: schedule grammar, reconcile math, and
        budget detection on synthetic pod records (seconds, runs in CI).

    python tools/kfac_chaos.py [--procs 4] [--max-steps 12] [--seed N]
        Run the storm (canonical scripted storm unless --seed) in a
        temp root and print the SLO rows. Add
        ``--out kfac_tpu/resilience/chaos_slo.json`` to (re)commit the
        artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402

_common.bootstrap()


def selftest() -> int:
    """Processless checks of the conductor's pure machinery."""
    from kfac_tpu.resilience import chaos

    failures: list[str] = []

    def check(cond: bool, what: str) -> None:
        (failures.append(what) if not cond else None)
        print(f'  {"ok " if cond else "FAIL"} {what}')

    cfg = chaos.ChaosConfig()
    sched = chaos.resolve_schedule(cfg)
    check(
        {e['fault'] for e in sched} >= {
            'sigterm_wave', 'torn_checkpoint', 'shrink', 'sigusr1'},
        'canonical scripted storm covers the committed fault classes',
    )
    check(
        all(e['fault'] in chaos.FAULT_CLASSES for e in sched),
        'scripted storm uses only declared fault classes',
    )
    seeded = chaos.seeded_storm(chaos.ChaosConfig(seed=7))
    check(
        seeded == chaos.seeded_storm(chaos.ChaosConfig(seed=7)),
        'seeded storm is deterministic per seed',
    )
    check(
        seeded != chaos.seeded_storm(chaos.ChaosConfig(seed=8)),
        'different seeds draw different storms',
    )

    # reconcile math on synthetic pod records: a clean respawn and a
    # blown-budget respawn must classify correctly without any process
    def rec(procs, down, events):
        r = chaos.RunRecord(procs=procs, skew=0.0, down_event=down)
        r.events = events
        r.t_exit = 10.0
        return r

    def step_ev(rank, t, step, loss):
        return (rank, t, {'event': 'step', 'step': step, 'loss': loss})

    def start_ev(rank, t, resumed, depth):
        return (rank, t, {
            'event': 'start', 'rank': rank, 'world': 2,
            'resumed_step': resumed, 'fallback_depth': depth,
        })

    down = {'fault': 'sigterm_wave', 'ranks': (0,), 'at_step': 2}
    losses = {1: 1.0, 2: 0.5, 3: 0.25, 4: 0.125}
    runs = [{'down': down, 'snaps': ()}, {'down': None, 'snaps': ()}]
    records = [
        rec(2, down, [start_ev(r, 1.0, 0, 0) for r in (0, 1)]
            + [step_ev(r, 2.0, s, losses[s])
               for r in (0, 1) for s in (1, 2)]),
        rec(2, None, [start_ev(r, 11.0, 2, 0) for r in (0, 1)]
            + [step_ev(r, 12.0, s, losses[s])
               for r in (0, 1) for s in (3, 4)]),
    ]
    control = rec(2, None, [
        step_ev(r, 1.0, s, losses[s]) for r in (0, 1) for s in losses
    ])
    cfg4 = chaos.ChaosConfig(procs=2, max_steps=4)
    report = chaos.reconcile(cfg4, runs, records, control)
    check(report.ok, 'clean synthetic storm reconciles with no blown budget')
    check(
        report.rows['sigterm_wave']['downtime_steps'] == 0,
        'boundary-step resume counts zero downtime',
    )

    diverged = [
        records[0],
        rec(2, None, [start_ev(r, 11.0, 2, 0) for r in (0, 1)]
            + [step_ev(r, 12.0, s, losses[s] + 0.5)
               for r in (0, 1) for s in (3, 4)]),
    ]
    report2 = chaos.reconcile(cfg4, runs, diverged, control)
    check(
        any('diverged' in b for b in report2.blown),
        'trajectory divergence vs control is detected',
    )
    deep = [
        records[0],
        rec(2, None, [start_ev(r, 11.0, 0, 3) for r in (0, 1)]
            + [step_ev(r, 12.0, s, losses[s])
               for r in (0, 1) for s in (1, 2, 3, 4)]),
    ]
    report3 = chaos.reconcile(cfg4, runs, deep, control)
    check(
        any('fell back' in b for b in report3.blown),
        'over-budget fallback depth is detected',
    )

    if failures:
        print(f'chaos selftest: {len(failures)} FAILED')
        return 1
    print('chaos selftest ok')
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--selftest', action='store_true',
                    help='processless sanity checks, no pods spawned')
    ap.add_argument('--procs', type=int, default=4)
    ap.add_argument('--max-steps', type=int, default=12)
    ap.add_argument('--seed', type=int, default=None,
                    help='seeded random storm instead of the canonical '
                         'scripted one')
    ap.add_argument('--storm-events', type=int, default=3)
    ap.add_argument('--root', default=None,
                    help='conductor scratch dir (default: a tempdir)')
    ap.add_argument('--out', default=None,
                    help='write the full report JSON here (e.g. the '
                         'committed kfac_tpu/resilience/chaos_slo.json)')
    args = ap.parse_args()

    if args.selftest:
        return selftest()

    from kfac_tpu.resilience import chaos

    config = chaos.ChaosConfig(
        procs=args.procs,
        max_steps=args.max_steps,
        seed=args.seed,
        storm_events=args.storm_events,
    )
    root = args.root or tempfile.mkdtemp(prefix='kfac_chaos_')
    print(f'chaos storm: procs={config.procs} max_steps={config.max_steps} '
          f'{"seed=" + str(config.seed) if config.seed is not None else "scripted"} '
          f'root={root}')
    conductor = chaos.ChaosConductor(config, root=root)
    try:
        report = conductor.run()
    except chaos.ChaosError as err:
        report = getattr(err, 'report', None)
        print(f'CHAOS FAILED: {err}')
        if report is not None and args.out:
            with open(args.out, 'w') as f:
                json.dump(report.to_json(), f, indent=1, sort_keys=True)
        return 1
    print(json.dumps(report.rows, indent=1, sort_keys=True))
    if args.out:
        with open(args.out, 'w') as f:
            json.dump(report.to_json(), f, indent=1, sort_keys=True)
            f.write('\n')
        print(f'wrote {args.out}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
