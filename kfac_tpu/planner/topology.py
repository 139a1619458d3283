"""DP×TP×PP factorization enumeration and the 3D step-cost model.

The KAISA autotuner (:mod:`kfac_tpu.autotune`) searches layout knobs on a
FIXED mesh; this module searches the mesh itself. A candidate is a
``(dp, tp, pp, v, microbatches, schedule)`` factorization of the device
count, and its predicted step cost composes three ingredient families:

- **pipeline terms** — the bubble fraction comes from EXECUTING the
  schedule simulators (:func:`kfac_tpu.parallel.interleaved.generate` /
  ``generate_single_slot``: exact per-rank tick and idle-slot counts),
  never the closed form, whenever the table is small enough to build;
  the closed form is only the overflow fallback. The committed
  measured-vs-predicted table (``planner/bubble_table.json``, see
  :mod:`kfac_tpu.planner.execute`) supplies a per-``(schedule, p, v)``
  wall-clock correction on top. Per-tick wire traffic is priced exactly
  as the scan bodies emit it (two activation/cotangent ``ppermute``
  payloads per tick, plus the interleaved scan's two int32 routing
  headers) — the parity the IR visitor's
  :func:`~kfac_tpu.analysis.ir.visitor.ppermute_bytes` check pins.
- **stage-local MEM-OPT K-FAC terms** — the reference hardwires MEM-OPT
  among pipe peers (kfac/gpt_neox/assignment.py:95-130); the planner
  PRICES that placement instead: a
  :class:`~kfac_tpu.autotune.model.StaticLayout` over the stage's dp
  group (fraction ``1/dp``) supplies the same ``comms_summary`` byte
  terms and decomposition/preconditioning FLOPs the KAISA model uses,
  scaled by the per-rank model share ``1/pp``. The base config's
  cadence and async-inverse knobs ride into the layout unchanged, so
  those knobs are co-planned with the mesh shape.
- **per-stage HBM** — params, activations in flight (residual ring +
  inboxes + microbatch feeds, ring depths exactly as the scan bodies
  allocate them) and second-order state, pruned against
  ``HardwareSpec.hbm_bytes``.

Host-side shape arithmetic only — no mesh, no arrays; ranking the full
8-device grid costs milliseconds.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

from kfac_tpu.autotune import model as model_lib
from kfac_tpu.autotune.model import HardwareSpec

#: int32 (next_chunk, microbatch, valid) routing header each payload
#: ppermute of the single-slot interleaved scan is paired with
PIPE_META_BYTES = 12

#: activation wire itemsize (the pipeline scans permute model-dtype
#: activations; both LM scans default to float32)
ACT_ITEMSIZE = 4


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Knobs of the 3D topology planner (the ``--topology`` search).

    The KFL109 lint pins the docs/AUTOTUNE.md "Topology knobs" table to
    these fields.
    """

    #: pipeline schedule families to consider: '1f1b' is the 2-slot
    #: combined scan (parallel/pipeline.py), 'interleaved' the
    #: single-slot virtual-chunk scan (parallel/interleaved_scan.py)
    schedules: tuple[str, ...] = ('1f1b', 'interleaved')
    #: explicit pipeline rank counts to enumerate; None = every divisor
    #: of the device count >= 2
    pipeline_ranks: tuple[int, ...] | None = None
    #: tensor-parallel (model-axis) widths to enumerate
    tensor_parallel: tuple[int, ...] = (1,)
    #: interleaving depths v for the single-slot schedule
    virtual_chunks: tuple[int, ...] = (1, 2, 4)
    #: microbatch counts per candidate, as multiples of pp (Megatron's
    #: m % p == 0 constraint is structural)
    microbatch_multiples: tuple[int, ...] = (2, 4)
    #: per-dp-shard rows of one microbatch (activation geometry)
    microbatch_rows: int = 1
    #: sequence length of the pipelined activations
    seq_len: int = 128
    #: model width of the ppermuted activations
    d_model: int = 128
    #: largest schedule table (ticks x ranks slots) the planner will
    #: simulate exactly; beyond it the closed form takes over
    max_sim_slots: int = 65536
    #: override path for the measured bubble table (None = the committed
    #: planner/bubble_table.json artifact)
    bubble_table: str | None = None


@dataclasses.dataclass(frozen=True)
class TopologyCandidate:
    """One mesh factorization: ``dp * tp * pp == device count``."""

    dp: int
    tp: int
    pp: int
    virtual_chunks: int
    microbatches: int
    schedule: str

    def as_knob(self) -> dict[str, Any]:
        """This candidate as the plan's ``knobs['topology']`` value."""
        return {
            'dp': self.dp,
            'tp': self.tp,
            'pp': self.pp,
            'virtual_chunks': self.virtual_chunks,
            'microbatches': self.microbatches,
            'schedule': self.schedule,
        }


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_topologies(
    world: int, config: TopologyConfig = TopologyConfig()
) -> list[TopologyCandidate]:
    """Every valid ``(dp, tp, pp, v, m, schedule)`` factorization.

    Structural constraints are enforced here, not priced: ``pp * tp``
    must divide the device count, ``m`` must be a positive multiple of
    ``pp``, and the 2-slot 1F1B scan has no virtual chunks (``v == 1``).
    ``pp == 1`` is excluded — the flat-mesh layouts are the KAISA
    autotuner's domain.
    """
    out: list[TopologyCandidate] = []
    pps = config.pipeline_ranks or tuple(
        d for d in _divisors(world) if d >= 2
    )
    for pp in pps:
        if pp < 2 or world % pp:
            continue
        for tp in config.tensor_parallel:
            if tp < 1 or world % (pp * tp):
                continue
            dp = world // (pp * tp)
            for schedule in config.schedules:
                chunk_axis = (
                    config.virtual_chunks
                    if schedule == 'interleaved' else (1,)
                )
                for v in chunk_axis:
                    if v < 1:
                        continue
                    for mult in config.microbatch_multiples:
                        m = int(mult) * pp
                        if m <= 0:
                            continue
                        out.append(TopologyCandidate(
                            dp=dp, tp=tp, pp=pp, virtual_chunks=v,
                            microbatches=m, schedule=schedule,
                        ))
    return out


# ------------------------------------------------------------- bubble terms


def _closed_form(schedule: str, p: int, v: int, m: int) -> dict[str, Any]:
    """Fill/drain closed forms — the overflow fallback only.

    1F1B (2 slots per rank per tick): ``ticks = m + 2p - 2``, idle
    slots per rank ``4(p-1)``; interleaved (single slot):
    ``ticks = 2mv + 2(p-1)``, idle per rank ``2(p-1)`` — the Megatron
    ``2(p-1)/v`` stage-unit reduction.
    """
    if schedule == 'interleaved':
        ticks = 2 * m * v + 2 * (p - 1)
        executed = 2 * m * v
        slots_per_tick = 1
    else:
        ticks = m + 2 * p - 2
        executed = 2 * m
        slots_per_tick = 2
    total = ticks * slots_per_tick
    idle = total - executed
    return {
        'schedule': schedule, 'p': p, 'v': v, 'microbatches': m,
        'ticks': ticks,
        'slots_per_tick': slots_per_tick,
        'executed_slots_per_rank': executed,
        'bubble_slots': idle * p,
        'fraction': idle / total if total else 0.0,
        'source': 'closed-form',
    }


def schedule_terms(
    schedule: str, p: int, v: int, m: int, *, max_sim_slots: int = 65536
) -> dict[str, Any]:
    """Exact tick/idle accounting for one ``(schedule, p, v, m)`` point.

    Executes the schedule simulator (``generate`` for the 2-slot 1F1B,
    ``generate_single_slot`` for the interleaved scan) whenever the
    table fits ``max_sim_slots``; the returned ``source`` says which
    tier produced the numbers.
    """
    from kfac_tpu.parallel import interleaved as interleaved_lib

    if schedule not in ('1f1b', 'interleaved'):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if p < 1 or v < 1 or m <= 0 or m % p:
        raise ValueError(
            f'invalid schedule point p={p} v={v} m={m} '
            f'(need p,v >= 1 and m a positive multiple of p)'
        )
    est_ticks = 2 * m * v + 2 * p if schedule == 'interleaved' else (
        m + 2 * p
    )
    if est_ticks * p > max_sim_slots:
        return _closed_form(schedule, p, v, m)
    if schedule == 'interleaved':
        sched = interleaved_lib.generate_single_slot(p, v, m)
        slots_per_tick = 1
        executed = 2 * m * v
    else:
        # the executed 2-slot scan has one chunk per rank; v rides as
        # stage DEPTH (blocks per stage), which the schedule cannot see
        sched = interleaved_lib.generate(p, 1, m)
        slots_per_tick = 2
        executed = 2 * m
    ticks = int(sched.ticks)
    bubble = int(sched.bubble_slots())
    total = ticks * slots_per_tick * p
    return {
        'schedule': schedule, 'p': p, 'v': v, 'microbatches': m,
        'ticks': ticks,
        'slots_per_tick': slots_per_tick,
        'executed_slots_per_rank': executed,
        'bubble_slots': bubble,
        'fraction': bubble / total if total else 0.0,
        'source': 'simulator',
    }


def bubble_fraction(
    schedule: str,
    p: int,
    v: int,
    m: int,
    *,
    max_sim_slots: int = 65536,
    bubble_table: str | None = None,
) -> float:
    """Simulator-exact bubble fraction, scaled by the measured
    correction from the committed bubble table when a clean row exists
    (1.0 otherwise — load-or-default, like the dispatch thresholds)."""
    from kfac_tpu.planner import execute as execute_lib

    sim = schedule_terms(schedule, p, v, m, max_sim_slots=max_sim_slots)
    corr = execute_lib.measured_bubble_correction(
        schedule, p, v, path=bubble_table
    )
    return min(0.99, sim['fraction'] * corr)


# ----------------------------------------------------------- pipeline wire


def pipeline_ppermute_bytes_per_tick(
    schedule: str,
    microbatch_rows: int,
    seq_len: int,
    d_model: int,
    act_itemsize: int = ACT_ITEMSIZE,
) -> int:
    """Per-rank ``ppermute`` bytes of ONE schedule tick, exactly as the
    scan bodies emit them.

    Both scans permute one activation and one cotangent payload of
    ``(microbatch_rows, seq_len, d_model)`` per tick (unconditionally —
    idle ticks send zeros); the single-slot interleaved scan adds one
    int32 ``(chunk, mb, valid)`` routing header per payload. The KFL205
    -style parity test diffs this number against
    :func:`kfac_tpu.analysis.ir.visitor.ppermute_bytes` of the traced
    scan body.
    """
    payload = int(microbatch_rows) * int(seq_len) * int(d_model) * int(
        act_itemsize
    )
    if schedule == 'interleaved':
        return 2 * payload + 2 * PIPE_META_BYTES
    return 2 * payload


def _ring_slots(schedule: str, p: int, v: int) -> int:
    """Residual-ring depth of the scan bodies (stage inputs in flight)."""
    if schedule == 'interleaved':
        return 2 * (p - 1) + (v - 1) * p + 1
    return 2 * p - 1


# -------------------------------------------------------------- cost model


def _base_candidate(base: Any, frac: float) -> model_lib.Candidate:
    """The base config's KAISA knobs as a Candidate at ``frac`` — the
    stage-group layout the planner prices (same extraction as
    ``search.baseline_candidates``)."""
    from kfac_tpu.autotune import search as search_lib

    method = base.allreduce_method.name
    cap = (
        base.allreduce_bucket_cap_mb
        if method == 'ALLREDUCE_BUCKETED' else None
    )
    return model_lib.Candidate(
        grad_worker_fraction=frac,
        bucket_granularity=int(base.bucket_granularity),
        allreduce_method=method,
        allreduce_bucket_cap_mb=cap,
        factor_update_steps=search_lib._static_cadence(
            base.factor_update_steps
        ),
        inv_update_steps=search_lib._static_cadence(base.inv_update_steps),
        colocate_factors=bool(base.colocate_factors),
        async_inverse=search_lib._async_mode(base),
    )


def predict_topology(
    cand: TopologyCandidate,
    base: Any,
    world: int,
    hardware: HardwareSpec = HardwareSpec(),
    config: TopologyConfig = TopologyConfig(),
) -> dict[str, Any]:
    """Cost-table row for one mesh factorization.

    The KAISA terms come from a :class:`StaticLayout` over the stage's
    dp group at fraction ``1/dp`` — stage-local MEM-OPT, the placement
    ``PipelineKFAC`` implements — scaled by the per-rank model share
    ``1/pp`` (stages split the registry's layers evenly; decomposition
    round-robins over the dp peers, preconditioning replicates on
    them). The pipeline terms come from the executed schedule simulator
    plus the exact per-tick ``ppermute`` wire bytes.
    """
    from kfac_tpu.observability import comms as comms_lib

    dp, tp, pp, v, m = (
        cand.dp, cand.tp, cand.pp, cand.virtual_chunks, cand.microbatches
    )
    if dp * tp * pp != world:
        raise ValueError(
            f'candidate {cand} does not factorize world={world}'
        )
    sim = schedule_terms(
        cand.schedule, pp, v, m, max_sim_slots=config.max_sim_slots
    )
    from kfac_tpu.planner import execute as execute_lib

    corr = execute_lib.measured_bubble_correction(
        cand.schedule, pp, v, path=config.bubble_table
    )
    bubble = min(0.99, sim['fraction'] * corr)

    group = max(dp, 1)
    frac = 1.0 / group
    kaisa_cand = _base_candidate(base, frac)
    cfg = model_lib.candidate_config(base, kaisa_cand)
    layout = model_lib.StaticLayout(cfg, group, frac)
    comms = layout.comms_report()
    share = 1.0 / pp  # each pipe rank holds 1/pp of the model's layers

    # stage-local collectives: factor-stat allreduce and decomposition
    # psum-share run inside the dp group only (no cross-stage gradient
    # broadcast — MEM-OPT among pipe peers has nothing to broadcast)
    stat_bytes = comms['stat_transport']['bytes'] * share if group > 1 else 0.0
    reshard_bytes = (
        comms['decomp_reshard_bytes'] * share if group > 1 else 0.0
    )
    f_cad = max(1, kaisa_cand.factor_update_steps)
    i_cad = max(1, kaisa_cand.inv_update_steps)
    kfac_bytes_per_step = stat_bytes / f_cad + reshard_bytes / i_cad

    # decomposition round-robins over the dp peers; preconditioning
    # replicates on them (each peer preconditions its own dp-replicated
    # grad stacks after the psum)
    decomp_dev = model_lib._decomp_flops(layout) * share / group
    precond_dev = model_lib._precond_flops(layout) * share
    host_transfer_s = 0.0
    if kaisa_cand.async_inverse == 'host':
        host_transfer_s = reshard_bytes / hardware.host_bandwidth
        refresh_spike_s = host_transfer_s
        kfac_flops = precond_dev
    elif kaisa_cand.async_inverse == 'sliced':
        n_slices = max(
            1, min(i_cad, model_lib._refresh_units(layout))
        )
        refresh_spike_s = decomp_dev / hardware.matmul_flops / n_slices
        kfac_flops = decomp_dev / i_cad + precond_dev
    else:
        refresh_spike_s = decomp_dev / hardware.matmul_flops
        kfac_flops = decomp_dev / i_cad + precond_dev

    # model compute: ~2 flops/MAC forward, 2x that for backward, split
    # over the pipe and model axes (the dp axis shards the batch, which
    # tokens_local already accounts for); the bubble inflates it
    fwd_per_token = float(sum(
        2.0 * h.a_factor_shape[0] * h.g_factor_shape[0]
        for h in base.registry.layers.values()
    ))
    tokens_local = float(m * config.microbatch_rows * config.seq_len)
    compute_dev = 3.0 * fwd_per_token * tokens_local / (pp * tp)
    compute_s = (
        compute_dev / hardware.matmul_flops / max(1e-9, 1.0 - bubble)
    )

    per_tick = pipeline_ppermute_bytes_per_tick(
        cand.schedule, config.microbatch_rows, config.seq_len,
        config.d_model,
    )
    pipe_bytes = float(sim['ticks'] * per_tick)

    # per-device HBM: stage params, activations in flight (residual
    # ring + inboxes + the m-deep microbatch feed and cotangent stack,
    # ring depths exactly as the scan bodies allocate), and the stage's
    # second-order state
    msg = (
        config.microbatch_rows * config.seq_len * config.d_model
        * ACT_ITEMSIZE
    )
    param_total = float(sum(
        h.a_factor_shape[0] * h.g_factor_shape[0] * 4
        for h in base.registry.layers.values()
    ))
    inbox = 2 if cand.schedule == '1f1b' else 4 * v
    factor_item = comms_lib._itemsize(cfg.factor_dtype)
    factor_total = float(sum(
        sb.padded * sb.d * sb.d * factor_item
        for store in (layout.a_store, layout.g_store)
        for sb in store
    ))
    memory = {
        'params': param_total / (pp * tp),
        'activations': float(
            (_ring_slots(cand.schedule, pp, v) + inbox + 2 * m) * msg
        ),
        'factors': factor_total * share / group,
        'decomps': comms['decomp_reshard_bytes'] * share,
        'grad_stacks': comms['grad_broadcast_bytes'] * share,
    }
    memory['total'] = sum(
        memory[k]
        for k in ('params', 'activations', 'factors', 'decomps',
                  'grad_stacks')
    )

    feasible = True
    reason = None
    if (
        hardware.hbm_bytes is not None
        and memory['total'] > hardware.hbm_bytes
    ):
        feasible = False
        reason = (
            f'per-stage memory {memory["total"]:.3e} B exceeds the '
            f'{hardware.hbm_bytes:.3e} B HBM budget'
        )

    knobs = kaisa_cand.knobs(group)
    knobs['topology'] = cand.as_knob()
    return {
        'knobs': knobs,
        'feasible': feasible,
        'infeasible_reason': reason,
        'schedule': {
            'ticks': sim['ticks'],
            'bubble_slots': sim['bubble_slots'],
            'bubble_fraction': bubble,
            'simulated_fraction': sim['fraction'],
            'measured_correction': corr,
            'source': sim['source'],
        },
        'bytes_per_occurrence': {
            'stat_transport': stat_bytes,
            'decomp_reshard': reshard_bytes,
            'ppermute_per_tick': per_tick,
        },
        'bytes_per_step': kfac_bytes_per_step + pipe_bytes,
        'flops_per_device_per_step': kfac_flops + compute_dev,
        'memory_per_device_bytes': memory,
        'refresh_spike_s': refresh_spike_s,
        'predicted_step_s': (
            compute_s
            + pipe_bytes / hardware.collective_bandwidth
            + kfac_flops / hardware.matmul_flops
            + kfac_bytes_per_step / hardware.collective_bandwidth
            + host_transfer_s / i_cad
        ),
    }


# ------------------------------------------------------------------ search


def plan_topology(
    base: Any,
    *,
    world: int | None = None,
    hardware: HardwareSpec = HardwareSpec(),
    config: TopologyConfig = TopologyConfig(),
) -> Any:
    """Rank every mesh factorization and return the winning 3D plan.

    The returned :class:`~kfac_tpu.autotune.plan.TunedPlan` carries the
    stage-group KAISA knobs plus the ``topology`` knob
    (:meth:`TopologyCandidate.as_knob`); it round-trips through
    ``save``/``load``/``resolve_auto_layout`` like any KAISA plan, and
    pre-topology consumers ignore the extra knob entirely.
    """
    import jax

    from kfac_tpu.autotune import plan as plan_lib

    if world is None:
        world = jax.device_count()
    cands = enumerate_topologies(world, config)
    if not cands:
        raise ValueError(
            f'no pipeline factorization of {world} devices admits '
            f'pp >= 2 under {config}'
        )
    rows = [
        predict_topology(c, base, world, hardware, config) for c in cands
    ]

    def _rank(i_row):
        i, row = i_row
        return (not row['feasible'], row['predicted_step_s'], i)

    order = sorted(enumerate(rows), key=_rank)
    win_i, win = order[0]
    from kfac_tpu.planner import execute as execute_lib

    table = execute_lib.load_bubble_table(config.bubble_table)

    def _jsonable(obj: Any) -> Any:
        # TunedPlan documents must survive save/load byte-identically;
        # tuples (TopologyConfig fields) come back as lists, so
        # normalize before the plan ever exists in memory
        return json.loads(json.dumps(obj))

    return plan_lib.TunedPlan(
        fingerprint=plan_lib.plan_fingerprint(base.registry),
        knobs=_jsonable(dict(win['knobs'])),
        cost_table=_jsonable(rows),
        winner=_jsonable({
            'knobs': dict(win['knobs']),
            'predicted_step_s': win['predicted_step_s'],
            'schedule': dict(win['schedule']),
            'picked_by': 'predicted',
            'index': win_i,
        }),
        meta=_jsonable({
            'planner': 'topology3d',
            'world': world,
            'grid_size': len(rows),
            'bubble_table': 'measured' if table else 'closed-form-fallback',
            'config': dataclasses.asdict(config),
        }),
    )
