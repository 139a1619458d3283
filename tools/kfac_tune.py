#!/usr/bin/env python
"""Offline layout-autotuner CLI: search the KAISA knobs, write a TunedPlan.

Runs the ``kfac_tpu.autotune`` search — analytic cost-model ranking over
the gradient-worker-fraction x bucket-granularity x transport x
inverse-cadence grid, then timed trials of the top-K real
``DistributedKFAC`` engines plus the three hand-configured strategy
baselines — on a benchmark MLP config shaped like your model, and writes
the winning knobs as a versioned JSON plan:

    python tools/kfac_tune.py --d-model 512 --layers 4 --out plan.json

Training then picks the plan up with
``Trainer(..., auto_layout='plan.json')`` or
``DistributedKFAC(config, auto_layout='plan.json')`` — applied only when
the topology+model fingerprint matches, ignored with a rate-limited
warning otherwise.

``--selftest`` (wired into ``make tune``) runs the whole pipeline on a
tiny config and asserts the plan round-trips, is deterministic, applies,
and is rejected on a tampered fingerprint.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any


def _pin_host_platform() -> None:
    """Default to the 8-virtual-device CPU mesh when no platform was
    pinned (the same environment the test suite runs against); a real
    TPU run sets JAX_PLATFORMS/XLA_FLAGS itself."""
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    os.environ.setdefault(
        'XLA_FLAGS', '--xla_force_host_platform_device_count=8'
    )


def build_benchmark(args: argparse.Namespace):
    """(base config, loss_fn, params, batch) for an MLP shaped by the
    CLI flags — the stand-in for the real model's layer-dimension mix."""
    import jax
    import jax.numpy as jnp

    import kfac_tpu
    from kfac_tpu.models import MLP

    model = MLP(
        features=(args.d_model,) * args.layers, num_classes=args.classes
    )
    x = jax.random.normal(
        jax.random.PRNGKey(args.seed), (args.batch, args.d_in)
    )
    registry = kfac_tpu.register_model(model, x)
    params = model.init(jax.random.PRNGKey(args.seed + 1), x)['params']
    base = kfac_tpu.KFACPreconditioner(
        registry=registry,
        damping=args.damping,
        lr=0.1,
        factor_update_steps=args.factor_update_steps,
        inv_update_steps=args.inv_update_steps,
    )

    def loss_fn(p: Any, batch: Any):
        return jnp.mean(model.apply({'params': p}, batch) ** 2)

    return base, loss_fn, params, x


def _csv_ints(s: str) -> tuple[int, ...]:
    return tuple(int(v) for v in s.split(',') if v.strip())


def _topo_col(knobs: dict[str, Any]) -> str:
    topo = knobs.get('topology')
    if not topo:
        return ''
    return (
        f"dp{topo['dp']}.tp{topo['tp']}.pp{topo['pp']} "
        f"v={topo['virtual_chunks']} m={topo['microbatches']} "
        f"{topo['schedule']:<11} "
    )


def summarize(plan: Any) -> str:
    lines = [
        f'TunedPlan (schema {plan.schema}): winner '
        f'{_topo_col(plan.knobs)}'
        f'{plan.knobs["strategy"]} frac={plan.knobs["grad_worker_fraction"]} '
        f'granularity={plan.knobs["bucket_granularity"]} '
        f'transport={plan.knobs["allreduce_method"]} '
        f'picked_by={plan.winner["picked_by"]}',
        'cost table (best-ranked first):',
    ]
    for row in plan.cost_table[:10]:
        k = row['knobs']
        meas = (
            f'{row["measured_step_s"]*1e3:8.2f} ms'
            if row.get('measured_step_s') is not None else '       --'
        )
        feas = '' if row['feasible'] else '  INFEASIBLE'
        lines.append(
            f'  {_topo_col(k)}'
            f'{k["strategy"]:>10} frac={k["grad_worker_fraction"]:<7.4g} '
            f'gran={k["bucket_granularity"]:<4} '
            f'{k["allreduce_method"]:<19} '
            f'pred {row["predicted_step_s"]*1e6:9.2f} us  '
            f'meas {meas}{feas}'
        )
    if len(plan.cost_table) > 10:
        lines.append(f'  ... {len(plan.cost_table) - 10} more rows')
    return '\n'.join(lines)


def run_search(args: argparse.Namespace) -> int:
    from kfac_tpu import autotune

    base, loss_fn, params, batch = build_benchmark(args)
    hardware = autotune.HardwareSpec(
        hbm_bytes=None if args.hbm_gb is None else args.hbm_gb * 2**30
    )
    if args.topology:
        # the 3D planner is predict-only: bubble fractions come from the
        # executed-schedule simulators + the committed measured table
        plan = autotune.autotune(
            base, measure=False, hardware=hardware, topology=True,
        )
        if args.json:
            json.dump(plan.to_json(), sys.stdout, indent=2, sort_keys=True)
            print()
        else:
            print(summarize(plan))
        if args.out:
            plan.save(args.out)
            print(f'wrote {args.out}')
        return 0
    plan = autotune.autotune(
        base,
        None if args.no_measure else loss_fn,
        params,
        batch,
        top_k=args.top_k,
        measure=not args.no_measure,
        hardware=hardware,
        granularities=_csv_ints(args.granularities),
        inv_cadences=(
            _csv_ints(args.inv_cadences) if args.inv_cadences else None
        ),
        warmup=args.warmup,
        iters=args.iters,
    )
    if args.json:
        json.dump(plan.to_json(), sys.stdout, indent=2, sort_keys=True)
        print()
    else:
        print(summarize(plan))
    if args.out:
        plan.save(args.out)
        print(f'wrote {args.out}')
    return 0


# ---------------------------------------------------------------- selftest


def selftest() -> int:
    import tempfile
    import warnings as pywarnings

    import kfac_tpu
    from kfac_tpu import autotune
    from kfac_tpu.parallel.kaisa import DistributedKFAC
    from kfac_tpu.parallel.mesh import kaisa_mesh
    from kfac_tpu.warnings import LayoutPlanWarning, reset_layout_warnings

    args = argparse.Namespace(
        d_model=16, layers=2, classes=4, batch=8, d_in=12, seed=0,
        damping=1e-3, factor_update_steps=1, inv_update_steps=1,
    )
    base, loss_fn, params, batch = build_benchmark(args)

    # deterministic model-only plan
    p1 = autotune.autotune(base, measure=False)
    p2 = autotune.autotune(base, measure=False)
    assert p1.to_json() == p2.to_json(), 'model-ranked plan not deterministic'

    # tiny measured run: the winner must not lose to any measured baseline
    plan = autotune.autotune(
        base, loss_fn, params, batch,
        top_k=1, warmup=0, iters=2, granularities=(1,),
    )
    measured = [
        r['measured_step_s'] for r in plan.cost_table if r['measured']
    ]
    assert measured and plan.winner['measured_step_s'] == min(measured)

    # round trip + application
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, 'plan.json')
        plan.save(path)
        loaded = kfac_tpu.TunedPlan.load(path)
        assert loaded.to_json() == plan.to_json(), 'round trip drift'
        eng = DistributedKFAC(config=base, auto_layout=path)
        assert eng.auto_layout_applied
        frac = plan.knobs['grad_worker_fraction']
        ref = DistributedKFAC(
            config=autotune.apply_knobs(base, plan.knobs),
            mesh=kaisa_mesh(grad_worker_fraction=frac),
        )
        assert eng.comms_report() == ref.comms_report(), 'plan != knobs'

    # tampered fingerprint falls back with a rate-limited warning
    bad = plan.to_json()
    bad['fingerprint'] = dict(bad['fingerprint'], device_count=12345)
    reset_layout_warnings()
    with pywarnings.catch_warnings(record=True) as rec:
        pywarnings.simplefilter('always')
        eng = DistributedKFAC(config=base, auto_layout=bad)
    assert not eng.auto_layout_applied
    assert any(isinstance(r.message, LayoutPlanWarning) for r in rec)

    # 3D topology planner: a pp>1 plan that round-trips byte-identically
    # through save/load and resolves to a pipeline mesh
    from kfac_tpu.autotune import plan as plan_mod
    from kfac_tpu.parallel.mesh import PIPE_AXIS

    topo_plan = autotune.autotune(base, measure=False, topology=True)
    topo = topo_plan.knobs['topology']
    assert topo and topo['pp'] > 1, f'planner picked a flat mesh: {topo}'
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, 'topo_plan.json')
        topo_plan.save(path)
        with open(path) as f:
            raw1 = f.read()
        loaded = kfac_tpu.TunedPlan.load(path)
        assert loaded.to_json() == topo_plan.to_json(), 'topology round trip'
        loaded.save(path)
        with open(path) as f:
            raw2 = f.read()
        assert raw1 == raw2, 'topology plan save is not byte-stable'
        cfg2, mesh2, applied = plan_mod.resolve_auto_layout(
            base, None, loaded
        )
        assert applied, 'topology plan did not apply'
        assert dict(mesh2.shape)[PIPE_AXIS] == topo['pp']

    # a pre-planner plan document (no topology knob) still loads and
    # defaults to the flat layout
    legacy_doc = plan.to_json()
    legacy_doc['knobs'] = {
        k: val for k, val in legacy_doc['knobs'].items() if k != 'topology'
    }
    legacy = kfac_tpu.TunedPlan.from_json(legacy_doc)
    assert legacy.knobs['topology'] is None
    eng = DistributedKFAC(config=base, auto_layout=legacy)
    assert eng.auto_layout_applied, 'pre-planner plan no longer applies'

    print('kfac_tune selftest ok')
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--out', default=None,
                        help='write the TunedPlan JSON here')
    parser.add_argument('--json', action='store_true',
                        help='print the full plan JSON instead of a summary')
    parser.add_argument('--selftest', action='store_true',
                        help='run the end-to-end pipeline self-check')
    bench = parser.add_argument_group('benchmark model')
    bench.add_argument('--d-model', type=int, default=128)
    bench.add_argument('--layers', type=int, default=2)
    bench.add_argument('--d-in', type=int, default=64)
    bench.add_argument('--classes', type=int, default=10)
    bench.add_argument('--batch', type=int, default=64)
    bench.add_argument('--seed', type=int, default=0)
    bench.add_argument('--damping', type=float, default=1e-3)
    bench.add_argument('--factor-update-steps', type=int, default=1)
    bench.add_argument('--inv-update-steps', type=int, default=1)
    search = parser.add_argument_group('search')
    search.add_argument('--top-k', type=int, default=3)
    search.add_argument('--iters', type=int, default=5)
    search.add_argument('--warmup', type=int, default=1)
    search.add_argument('--no-measure', action='store_true',
                        help='model-ranked only (no timed trials)')
    search.add_argument('--granularities', default='1,64,128,256')
    search.add_argument('--inv-cadences', default='',
                        help='CSV of inverse cadences to widen the grid '
                             '(default: keep the base cadence)')
    search.add_argument('--hbm-gb', type=float, default=None,
                        help='per-device HBM budget for feasibility pruning')
    search.add_argument('--topology', action='store_true',
                        help='rank DP×TP×PP mesh factorizations with the '
                             '3D planner instead of the flat KAISA grid')
    args = parser.parse_args(argv)

    _pin_host_platform()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _common
    _common.bootstrap()

    if args.selftest:
        return selftest()
    return run_search(args)


if __name__ == '__main__':
    sys.exit(main())
