"""Derive the Pallas dispatch-threshold artifact from microbench output.

Usage:
    python tools/derive_dispatch_tables.py SWEEP.jsonl [...] --out TABLE.json
    python tools/derive_dispatch_tables.py --selftest

Reads one or more ``tools/tpu_microbench.py`` JSONL sweeps, runs the
latency-floor check on every baseline series, and writes the versioned
threshold table the gate modules (``use_fused_cov_ema_for`` /
``use_fused_klclip_for`` / ``use_flash_for``) load-or-default. Contaminated or thin evidence HOLDS
the prior thresholds and says so in the artifact's ``provenance`` —
this tool can only move a gate on clean numbers.

The committed ``kfac_tpu/ops/dispatch_thresholds.json`` was produced by
this tool from ``bench_runs/cpu_session_20260806/micro_fused.jsonl`` (a
CPU sweep — see its provenance block). Re-run on an on-chip fori_loop
sweep to replace it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common

_common.bootstrap()

from kfac_tpu.ops import dispatch_tables


def read_jsonl(path: str) -> list[dict]:
    ops = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    ops.append(json.loads(line))
                except ValueError:
                    pass
    return ops


def selftest() -> None:
    """Synthetic derivation: a flat (contaminated) baseline sweep must
    hold the prior, a cleanly scaling sweep with a kernel win regime must
    move the threshold."""
    # fused step-path families: a flat (contaminated) unfused baseline
    # holds the prior, a clean sweep with a fused win suffix moves it
    flat_kl = [
        {'op': f'klclip_unfused_{d}', 'ms': 50.0 + (d % 5)}
        for d in (256, 512, 1024)
    ] + [
        {'op': f'klclip_fused_{d}', 'ms': 10.0} for d in (256, 512, 1024)
    ]
    t = dispatch_tables.derive_tables(flat_kl)
    assert (
        t['klclip']['min_dim']
        == dispatch_tables.DEFAULTS['klclip']['min_dim']
    )
    assert 'klclip_unfused' in t['provenance']['contaminated'], (
        t['provenance']
    )
    clean_kl = [
        {'op': f'klclip_unfused_{d}', 'ms': 0.01 * d * d / 256}
        for d in (256, 512, 1024, 2048)
    ] + [
        {'op': f'klclip_fused_{d}',
         'ms': 90.0 if d < 1024 else 0.002 * d * d / 256}
        for d in (256, 512, 1024, 2048)
    ]
    t = dispatch_tables.derive_tables(clean_kl)
    assert t['klclip']['min_dim'] == 1024, t
    assert 'klclip' in t['provenance'].get('derived', {}), t
    print('derive_dispatch_tables selftest: ok')


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument('sweeps', nargs='*',
                   help='tpu_microbench JSONL sweep file(s)')
    p.add_argument('--out', help='artifact path (default: stdout)')
    p.add_argument('--source', help='provenance label for the sweep '
                   '(default: the input paths)')
    p.add_argument('--selftest', action='store_true')
    args = p.parse_args()
    if args.selftest:
        selftest()
        return 0
    if not args.sweeps:
        p.error('at least one sweep JSONL is required (or --selftest)')
    ops: list[dict] = []
    for path in args.sweeps:
        ops.extend(read_jsonl(path))
    table = dispatch_tables.derive_tables(ops)
    header = next((o for o in ops if 'platform' in o and 'op' not in o), {})
    table['provenance']['source'] = {
        'sweeps': args.source or [os.path.relpath(s, _common.repo_root())
                                  for s in args.sweeps],
        'records': len(ops),
        'harness_version': header.get('harness_version', 1),
        'dispatch_mode': header.get('dispatch_mode', 'legacy'),
        'platform': header.get('platform'),
        'device_kind': header.get('device_kind'),
    }
    doc = json.dumps(table, indent=2, sort_keys=True) + '\n'
    if args.out:
        with open(args.out, 'w') as f:
            f.write(doc)
        held = table['provenance'].get('held', {})
        print(f'wrote {args.out} (held: {len(held)}, '
              f'attn.min_sk_dense={table["attn"]["min_sk_dense"]})')
    else:
        print(doc, end='')
    return 0


if __name__ == '__main__':
    sys.exit(main())
