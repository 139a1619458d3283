"""The readings a limit of ``correct`` is set from, on the chip, in one
process: the numbers compared over a dozen seeds of sound runs, then over a
few seeds of the control.

    python3 benchmark/readings.py --workload <cell> --seeds 12 --control-seeds 3

The control is the program with the Newton-Schulz solve's precision one
step below the ``Precision.HIGHEST`` the configuration states: ``HIGH``
(three bf16 passes), and ``DEFAULT`` (one) with ``--control default``: the
fault PR 21 found on the chip. It needs no measured window: the numbers
come from the first three steps. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BEGAN = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f'[readings +{time.perf_counter() - BEGAN:6.1f}s] {msg}', flush=True)


def read(cell, devices, seeds, label):
    from benchmark import harness, reference

    run = harness.build_run(cell, devices)
    config, workload = cell['config'], cell['workload']
    rows = []
    for seed in seeds:
        # a run unloads its reference before the program's first step
        run.reference = run.reference or reference.Reference(
            config['kind'], config, workload
        )
        ref, ref_s = harness.reference_steps(run, seed, lambda m: None)
        verdict = harness.check_first_steps(run, ref, lambda m: None)
        run.state = None
        rows.append({'seed': seed, **verdict['numbers']})
        log(f'{label} ' + json.dumps(rows[-1]) + f' (reference {ref_s:.1f}s)')
        rows[-1]['leaf_gaps'] = run.leaf_gaps
    return rows


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument('--workload', required=True)
    p.add_argument('--seeds', type=int, default=12)
    p.add_argument('--control-seeds', type=int, default=3)
    p.add_argument('--first-seed', type=int, default=2_200_000_001)
    p.add_argument('--control', choices=('high', 'default'), default='high')
    p.add_argument('--out', default=None)
    args = p.parse_args()

    import jax

    from benchmark import harness
    from kfac_tpu.ops import factors, pallas_ns
    from kfac_tpu.utils import compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < cell['chips']:
        sys.exit(f'{args.workload} needs {cell["chips"]} TPU chip(s)')
    devices = devices[:cell['chips']]
    compile_cache.configure()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_compilation_cache_max_size', -1)

    seeds = [args.first_seed + 7919 * i for i in range(max(
        args.seeds, args.control_seeds
    ))]
    sound = read(
        cell, devices, seeds[:args.seeds], 'sound'
    ) if args.seeds else []

    precision = {
        'high': jax.lax.Precision.HIGH, 'default': jax.lax.Precision.DEFAULT,
    }[args.control]
    factors.NS_PRECISION = precision
    pallas_ns.NS_PRECISION = precision
    # the fused kernel's step is a module-level jit: without this its
    # first trace, at the sound precision, would serve the control too
    jax.clear_caches()
    control = read(
        cell, devices, seeds[:args.control_seeds], f'control[{args.control}]'
    ) if args.control_seeds else []

    names = list(cell['workload']['limits'])
    summary = {
        'workload': args.workload, 'control': args.control,
        'sound_max': {n: max((r[n] for r in sound), default=None) for n in names},
        'sound_min': {n: min((r[n] for r in sound), default=None) for n in names},
        'control_min': {n: min((r[n] for r in control), default=None) for n in names},
        'control_max': {n: max((r[n] for r in control), default=None) for n in names},
        'sound': sound, 'control_rows': control,
    }
    log('summary ' + json.dumps({k: summary[k] for k in (
        'sound_max', 'sound_min', 'control_min', 'control_max')}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or '.', exist_ok=True)
        with open(args.out, 'w') as f:
            json.dump(summary, f, indent=1)


if __name__ == '__main__':
    main()
