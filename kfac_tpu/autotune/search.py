"""Measured layout search: enumerate, rank by model, time the top-K.

The grid is the cross product of the gradient-worker fractions the
world's divisor structure admits (``assignment.candidate_fractions``),
the bucket granularities {1, 64, 128, 256}, the stat-transport choices
(dense per-factor allreduce vs byte-capped triangle buffers at a chunk
cap), and the inverse cadence. The analytic model prunes and ranks it;
only the top-K candidates — plus, always, the three canonical strategy
baselines (COMM-OPT / HYBRID-OPT / MEM-OPT at the base granularity) —
are instantiated as real ``DistributedKFAC`` engines and timed under one
harness (compile excluded, warmup + median-of-N, steps wrapped in the
profiler's step annotations). Measuring the baselines guarantees the
winner is never slower than the best hand-configured strategy.

The inverse cadence defaults to the BASE config's cadence (one value):
unlike the layout knobs it trades preconditioner freshness, not just
speed, so the search widens it only when explicitly asked
(``inv_cadences=...`` / the CLI flag) — OR when the base config opts
into async refresh (``async_inverse=``). An async window amortizes the
refresh off the critical path, so longer cadences stop costing latency
spikes and become worth enumerating: the grid then widens to
{c, 2c, 4c} and every candidate carries the base's async mode.
"""

from __future__ import annotations

import statistics
import time
from typing import Any, Callable, Sequence

from kfac_tpu import assignment as assignment_lib
from kfac_tpu.autotune import model as model_lib
from kfac_tpu.autotune import plan as plan_lib

DEFAULT_GRANULARITIES = (1, 64, 128, 256)


def _static_cadence(value: Any, default: int = 1) -> int:
    """An int cadence from a config field (schedules fall back to the
    default: a callable cadence can't ride a JSON plan)."""
    return int(value) if isinstance(value, int) else default


def _async_mode(base: Any) -> str | None:
    """The base config's async-refresh mode name, or None when it runs
    the synchronous boundary refresh (accepts both the normalized
    AsyncInverseConfig and a raw mode string)."""
    acfg = getattr(base, 'async_inverse', None)
    return getattr(acfg, 'mode', acfg)


def enumerate_candidates(
    world: int,
    base: Any,
    *,
    fractions: Sequence[float] | None = None,
    granularities: Sequence[int] = DEFAULT_GRANULARITIES,
    transports: Sequence[tuple[str, float | None]] | None = None,
    inv_cadences: Sequence[int] | None = None,
) -> list[model_lib.Candidate]:
    """The candidate grid, in deterministic enumeration order."""
    if fractions is None:
        fractions = assignment_lib.candidate_fractions(world)
    if transports is None:
        transports = [
            ('ALLREDUCE', None),
            ('ALLREDUCE_BUCKETED', base.allreduce_bucket_cap_mb),
        ]
    async_mode = _async_mode(base)
    if inv_cadences is None:
        c = _static_cadence(base.inv_update_steps)
        # async refresh amortizes the window off the critical path, so
        # longer cadences become free speed rather than latency spikes —
        # widen the axis only then (freshness is otherwise the user's
        # explicit call, see the module docstring)
        inv_cadences = (c, 2 * c, 4 * c) if async_mode else (c,)
    factor_cadence = _static_cadence(base.factor_update_steps)
    out = []
    for frac in fractions:
        workers = assignment_lib.grad_worker_count(world, frac)
        for gran in granularities:
            for method, cap in transports:
                for inv in inv_cadences:
                    out.append(model_lib.Candidate(
                        grad_worker_fraction=frac,
                        bucket_granularity=int(gran),
                        allreduce_method=method,
                        allreduce_bucket_cap_mb=cap,
                        factor_update_steps=factor_cadence,
                        inv_update_steps=int(inv),
                        # MEM-OPT requires colocation; other strategies
                        # keep the base config's choice
                        colocate_factors=(
                            True if workers == 1
                            else bool(base.colocate_factors)
                        ),
                        async_inverse=async_mode,
                    ))
    return out


def baseline_candidates(world: int, base: Any) -> list[model_lib.Candidate]:
    """COMM-OPT, (when the world admits one) HYBRID-OPT, and MEM-OPT at
    the base config's granularity/transport — the hand-configured
    strategies the winner must beat or match."""
    fracs = [1.0]
    hybrids = [
        f for f in assignment_lib.candidate_fractions(world) if 0 < f < 1
        and assignment_lib.grad_worker_count(world, f) > 1
    ]
    if hybrids:
        # the most balanced grid: workers closest to sqrt(world)
        fracs.append(min(
            hybrids,
            key=lambda f: abs(
                assignment_lib.grad_worker_count(world, f) - world**0.5
            ),
        ))
    if world > 1:
        fracs.append(1.0 / world)
    method = base.allreduce_method.name
    # cap is only meaningful for the bucketed transport; normalize so
    # baselines dedup against identical grid candidates
    cap = (
        base.allreduce_bucket_cap_mb
        if method == 'ALLREDUCE_BUCKETED' else None
    )
    return [
        model_lib.Candidate(
            grad_worker_fraction=f,
            bucket_granularity=int(base.bucket_granularity),
            allreduce_method=method,
            allreduce_bucket_cap_mb=cap,
            factor_update_steps=_static_cadence(base.factor_update_steps),
            inv_update_steps=_static_cadence(base.inv_update_steps),
            colocate_factors=(
                True
                if assignment_lib.grad_worker_count(world, f) == 1
                else bool(base.colocate_factors)
            ),
            async_inverse=_async_mode(base),
        )
        for f in fracs
    ]


def measure_candidate(
    cand: model_lib.Candidate,
    base: Any,
    loss_fn: Callable[..., Any],
    params: Any,
    batch: Any,
    *,
    warmup: int = 1,
    iters: int = 5,
) -> float:
    """Median compiled-step seconds of a real engine built from ``cand``.

    One jitted function runs curvature capture + the full KAISA step; the
    first call compiles and is excluded; each timed step is wrapped in
    the profiler's step annotation so a surrounding
    ``profiler.profile_session`` attributes trial steps in the trace.
    """
    import jax

    from kfac_tpu.layers import capture as capture_lib
    from kfac_tpu.observability import profiler as profiler_lib
    from kfac_tpu.parallel import kaisa as kaisa_lib
    from kfac_tpu.parallel import mesh as mesh_lib

    cfg = model_lib.candidate_config(base, cand)
    mesh = mesh_lib.kaisa_mesh(
        grad_worker_fraction=cand.grad_worker_fraction
    )
    eng = kaisa_lib.DistributedKFAC(config=cfg, mesh=mesh)
    run = capture_lib.CurvatureCapture(cfg.registry).value_stats_and_grad(
        loss_fn
    )

    @jax.jit
    def step(state, params, batch):
        (loss, _), grads, stats = run(params, batch)
        return eng.step(state, grads, stats, loss=loss)

    state = eng.init()
    state, out = step(state, params, batch)  # compile — excluded
    jax.block_until_ready(out)
    times = []
    for i in range(warmup + iters):
        with profiler_lib.step_annotation(i):
            t0 = time.perf_counter()
            state, out = step(state, params, batch)
            jax.block_until_ready(out)
            elapsed = time.perf_counter() - t0
        if i >= warmup:
            times.append(elapsed)
    return statistics.median(times)


def autotune(
    base: Any,
    loss_fn: Callable[..., Any] | None = None,
    params: Any = None,
    batch: Any = None,
    *,
    world: int | None = None,
    top_k: int = 3,
    measure: bool = True,
    hardware: model_lib.HardwareSpec = model_lib.HardwareSpec(),
    fractions: Sequence[float] | None = None,
    granularities: Sequence[int] = DEFAULT_GRANULARITIES,
    transports: Sequence[tuple[str, float | None]] | None = None,
    inv_cadences: Sequence[int] | None = None,
    warmup: int = 1,
    iters: int = 5,
    topology: bool | Any = False,
    serving: Any = None,
) -> plan_lib.TunedPlan:
    """Run the full search and return the :class:`TunedPlan`.

    With ``measure=False`` (or no ``loss_fn``) the plan is purely
    model-ranked — deterministic and instant, for tests and dry runs;
    otherwise the top-K candidates and the strategy baselines are timed
    and the measured median picks the winner (ties break by predicted
    cost, then enumeration order, keeping the artifact deterministic).

    With ``topology`` truthy the KAISA grid is skipped entirely and the
    3D DP×TP×PP planner (:func:`kfac_tpu.planner.plan_topology`) ranks
    mesh factorizations instead; pass a
    :class:`~kfac_tpu.planner.TopologyConfig` to bound the factor grid.

    Pass ``serving=`` a :class:`~kfac_tpu.serving.ServingConfig` to also
    price the inference tier (:func:`kfac_tpu.autotune.model.price_serving`)
    into the winning plan's ``knobs['serving']`` — per-bucket MC and
    closed-form apply FLOPs plus per-replica HBM, so a deployment can
    shape replica counts from the same artifact it trains with.
    """
    import jax

    if world is None:
        world = jax.device_count()
    serving_knob = (
        None if serving is None
        else model_lib.price_serving(base.registry, serving, hardware)
    )
    if topology:
        from kfac_tpu import planner as planner_lib

        kwargs = {}
        if isinstance(topology, planner_lib.TopologyConfig):
            kwargs['config'] = topology
        topo_plan = planner_lib.plan_topology(
            base, world=world, hardware=hardware, **kwargs,
        )
        if serving_knob is not None:
            topo_plan.knobs['serving'] = serving_knob
        return topo_plan
    cands = enumerate_candidates(
        world, base, fractions=fractions, granularities=granularities,
        transports=transports, inv_cadences=inv_cadences,
    )
    baselines = baseline_candidates(world, base)
    for b in baselines:
        if b not in cands:
            cands.append(b)

    rows = [model_lib.predict(c, base, world, hardware) for c in cands]
    order = sorted(
        range(len(cands)),
        key=lambda i: (
            not rows[i]['feasible'], rows[i]['predicted_step_s'], i),
    )
    feasible = [i for i in order if rows[i]['feasible']]
    if not feasible:
        raise ValueError(
            'no candidate fits the HBM budget; raise hardware.hbm_bytes '
            'or shrink the model'
        )

    do_measure = measure and loss_fn is not None
    trial_set = list(dict.fromkeys(
        feasible[:top_k] + [
            i for i in (cands.index(b) for b in baselines)
            if rows[i]['feasible']
        ]
    ))
    for i, row in enumerate(rows):
        row['measured_step_s'] = None
        row['measured'] = False
    if do_measure:
        for i in trial_set:
            rows[i]['measured_step_s'] = measure_candidate(
                cands[i], base, loss_fn, params, batch,
                warmup=warmup, iters=iters,
            )
            rows[i]['measured'] = True
        winner_i = min(
            trial_set,
            key=lambda i: (rows[i]['measured_step_s'],
                           rows[i]['predicted_step_s'], i),
        )
        picked_by = 'measured'
    else:
        winner_i = feasible[0]
        picked_by = 'model'

    table = [rows[i] for i in order]
    win = rows[winner_i]
    win_knobs = dict(win['knobs'])
    if serving_knob is not None:
        # serving cost rides the winning plan only — cost_table rows keep
        # their grid knobs untouched
        win_knobs['serving'] = serving_knob
    return plan_lib.TunedPlan(
        fingerprint=plan_lib.plan_fingerprint(base.registry),
        knobs=win_knobs,
        cost_table=table,
        winner={
            'strategy': win['knobs']['strategy'],
            'predicted_step_s': win['predicted_step_s'],
            'measured_step_s': win['measured_step_s'],
            'picked_by': picked_by,
        },
        meta={
            'world': world,
            'grid_size': len(cands),
            'top_k': top_k,
            'measured_candidates': len(trial_set) if do_measure else 0,
            'warmup': warmup,
            'iters': iters,
        },
    )
