"""One run of one benchmark cell, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails, with no result line, unless JAX finds a TPU with the chips the cell
asks for: there is no CPU path (the tests import the arithmetic instead).
The last line of standard output is the result object the benchmark's
contract fixes; the lines above it say what was compared with what, and so
do the object's last key (``compared``) and the last lines of standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BEGAN = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f'[bench +{time.perf_counter() - BEGAN:6.1f}s] {msg}', flush=True)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, required=True)
    p.add_argument('--seconds', type=float, required=True)
    p.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import jax

    from benchmark import harness
    from kfac_tpu.utils import compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < cell['chips']:
        sys.exit(
            f"{args.workload} needs {cell['chips']} TPU chip(s): JAX found "
            f'{len(devices)} x {devices[0].platform} '
            f'({devices[0].device_kind}). The benchmark runs nowhere else.'
        )
    devices = devices[:cell['chips']]
    # the program's one rule: JAX_COMPILATION_CACHE_DIR if set, else the
    # fixed <checkout>/.jax_cache; every program goes in, however small
    cache = compile_cache.configure()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    # no eviction: a step program of these models is tens of MB, and a cap
    # (the chip tool's machine sets 192 MiB) evicts one cell's programs
    # while the next run's are written, so that no run ever finds its own
    jax.config.update('jax_compilation_cache_max_size', -1)
    log(f'{args.workload} seed {args.seed} on {len(devices)} x '
        f'{devices[0].device_kind}; compile cache {cache}')
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices, BEGAN, log
    )
    for name, c in result['compared'].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == '__main__':
    main()
