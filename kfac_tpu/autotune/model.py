"""Analytic per-candidate step-cost model over the KAISA knob space.

Everything here is host-side shape arithmetic: a candidate's predicted
step cost is assembled from the engine's STATIC layout — the same
size-class buckets and storage stores ``DistributedKFAC.__post_init__``
would build (via ``parallel.kaisa.build_stores``), and the same byte
accounting ``comms_report()`` exposes (via
``observability.comms.comms_summary``), so the model and the measurement
share one source of truth. No mesh, no arrays, no backend init: ranking
a few hundred candidates costs milliseconds.

Cost terms (documented in docs/AUTOTUNE.md):

- **decomposition FLOPs** per size-class bucket (eigh or Newton-Schulz
  over (padded, d, d) stacks), sharded over every device, amortized by
  the inverse cadence;
- **preconditioning FLOPs** per pair bucket, sharded over the column
  axis (replicated under COMM-OPT, where n_cols == 1), every step;
- **collective bytes** along both KAISA mesh axes: stat transport per
  factor cadence, decomposition reshard (the inverse broadcast) per
  inverse cadence, gradient broadcast every step (free under COMM-OPT —
  the stacks are already replicated);
- **refresh spike** — the worst single step's decomposition overshoot,
  shaped by the ``async_inverse`` knob: the whole refresh lands on one
  boundary step synchronously, a slice of it per step under 'sliced',
  and only the boundary payload transfer under 'host';
- **padding waste** rides implicitly in every term through the padded
  class dims and slot counts;
- **per-device factor-state memory** against an HBM budget, pruning
  infeasible candidates before any is timed.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from kfac_tpu import assignment as assignment_lib
from kfac_tpu import enums

# NOTE: kfac_tpu.parallel / observability are imported lazily inside
# functions — same cycle-avoidance as observability/comms.py.

# FLOP-count constants. Deliberately coarse (the measured trial runner
# settles close calls); what matters for RANKING is the d^3-vs-d^2
# structure and the sharding denominators, which are exact.
EIGH_FLOPS_PER_DIM3 = 30.0  # batched symmetric eigh ~= 30 d^3
NS_FLOPS_PER_ITER_DIM3 = 4.0  # two (d, d) matmuls per Newton-Schulz iter


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the autotuner grid: the layout knobs under search.

    ``allreduce_method`` is the enum NAME (JSON-friendly);
    ``colocate_factors`` defaults True because MEM-OPT requires it.
    """

    grad_worker_fraction: float
    bucket_granularity: int
    allreduce_method: str = 'ALLREDUCE'
    allreduce_bucket_cap_mb: float | None = 25.0
    factor_update_steps: int = 1
    inv_update_steps: int = 1
    colocate_factors: bool = True
    # async refresh backend name ('sliced' | 'host') or None for the
    # synchronous boundary refresh; trailing with a default so existing
    # positional construction and old plans stay valid
    async_inverse: str | None = None

    def knobs(self, world: int) -> dict[str, Any]:
        """This candidate as a TunedPlan ``knobs`` dict (adds the derived
        strategy name)."""
        return {
            'grad_worker_fraction': self.grad_worker_fraction,
            'strategy': assignment_lib.strategy_for_fraction(
                world, self.grad_worker_fraction
            ).name,
            'bucket_granularity': self.bucket_granularity,
            'allreduce_method': self.allreduce_method,
            'allreduce_bucket_cap_mb': self.allreduce_bucket_cap_mb,
            'factor_update_steps': self.factor_update_steps,
            'inv_update_steps': self.inv_update_steps,
            'colocate_factors': self.colocate_factors,
            'async_inverse': self.async_inverse,
            # KAISA-grid candidates carry no mesh factorization; the 3D
            # planner (kfac_tpu.planner) overrides this on its rows
            'topology': None,
            # serving-tier pricing is not part of the training grid —
            # autotune(serving=...) attaches price_serving() output to
            # the winning plan's knobs after the search
            'serving': None,
        }


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Device constants converting FLOPs/bytes into predicted seconds.

    Defaults are one-significant-figure CPU-agnostic placeholders — fine
    for RANKING (every candidate shares them); set real numbers (e.g.
    ~2e14 matmul FLOP/s and chip interconnect bandwidth on TPU) for
    absolute predictions, and ``hbm_bytes`` to enable the memory budget.
    """

    matmul_flops: float = 5e12  # sustained per-device matmul FLOP/s
    collective_bandwidth: float = 1e11  # logical payload drain, bytes/s
    hbm_bytes: float | None = None  # per-device factor-state budget
    host_bandwidth: float = 1e10  # host<->device transfer, bytes/s


def candidate_config(base: Any, cand: Candidate) -> Any:
    """A copy of ``base`` with the candidate's config-side knobs applied
    (the mesh-side knob — the fraction — picks the mesh, not the
    config)."""
    from kfac_tpu.autotune import plan as plan_lib

    return plan_lib.apply_knobs(base, {
        'bucket_granularity': cand.bucket_granularity,
        'allreduce_method': cand.allreduce_method,
        'allreduce_bucket_cap_mb': cand.allreduce_bucket_cap_mb,
        'factor_update_steps': cand.factor_update_steps,
        'inv_update_steps': cand.inv_update_steps,
        'colocate_factors': cand.colocate_factors,
        'async_inverse': cand.async_inverse,
    })


class StaticLayout:
    """A ``DistributedKFAC``-shaped static layout without mesh or arrays.

    Exposes exactly the attribute surface ``observability.comms``
    consumes (``config``, ``a_store``/``g_store``, ``buckets``,
    ``strategy``, ``grad_workers``/``world``/``total_devices``,
    ``_eigen``/``_prediv``, and ``n_cols`` in place of a mesh), built
    through the same ``build_buckets``/``build_stores`` calls as the
    engine — :meth:`comms_report` is therefore byte-identical to the
    report of the engine this layout describes.
    """

    def __init__(self, config: Any, world: int, grad_worker_fraction: float):
        from kfac_tpu.parallel import kaisa as kaisa_lib

        self.config = config
        self.registry = config.registry
        self.world = world
        self.total_devices = world
        self.grad_workers = assignment_lib.grad_worker_count(
            world, grad_worker_fraction
        )
        self.n_cols = world // self.grad_workers
        self.strategy = assignment_lib.strategy_for_fraction(
            world, grad_worker_fraction
        )
        self.granularity = int(config.bucket_granularity)
        self.a_groups = kaisa_lib.stored_a_groups(config)
        self.buckets = kaisa_lib.build_buckets(
            self.registry, world, self.granularity, self.a_groups
        )
        self.colocate = bool(config.colocate_factors)
        self.a_store, self.g_store = kaisa_lib.build_stores(
            self.registry, world, self.granularity, self.colocate,
            self.buckets, self.a_groups,
        )
        self._eigen = config.compute_method == enums.ComputeMethod.EIGEN
        self._prediv = self._eigen and config.prediv_eigenvalues
        # as the engine: a synchronous Newton-Schulz refresh's counters
        # ride with the inverse stacks (``comms.decomp_reshard_bytes``)
        self._ns_refresh = (
            not self._eigen
            and config.inverse_solver in kaisa_lib._NS_SOLVERS
            and config.async_inverse is None
        )

    def comms_report(self) -> dict[str, Any]:
        from kfac_tpu.observability import comms as comms_lib

        return comms_lib.comms_summary(self)


def _decomp_flops(layout: StaticLayout) -> float:
    """Global FLOPs of one inverse refresh (batched eigh or NS stacks)."""
    cfg = layout.config
    if layout._eigen:
        k = EIGH_FLOPS_PER_DIM3
    else:
        k = NS_FLOPS_PER_ITER_DIM3 * float(cfg.newton_schulz_iters)
    return float(sum(
        sb.padded * k * sb.d**3
        for store in (layout.a_store, layout.g_store)
        for sb in store
    ))


def decomp_flops(layout: StaticLayout) -> float:
    """Public decomposition-FLOP pricing, verified against the lowered IR.

    The KFL205 lint (kfac_tpu/analysis/ir) counts eigh/Newton–Schulz
    FLOPs straight out of the traced update_inverses jaxpr and diffs them
    against this number — keep the constants above in sync with the real
    decomposition kernels or the lint will say so.
    """
    return _decomp_flops(layout)


def _refresh_units(layout: StaticLayout) -> int:
    """How many independently refreshable decomposition units the layout
    has — the upper bound on the sliced backend's slice count (mirrors
    ``async_inverse.sliced.kaisa_units``: one unit per storage bucket,
    or one per pair bucket under the fused prediv path)."""
    if layout._prediv:
        return len(layout.buckets)
    return len(layout.a_store) + len(layout.g_store)


def _precond_flops(layout: StaticLayout) -> float:
    """Global FLOPs of one preconditioning pass over the grad stacks.

    EIGEN projects each (dg, da) grad into the eigenbasis and back (four
    stack matmuls); INVERSE is the two-sided inverse product (two)."""
    m = 4.0 if layout._eigen else 2.0
    return float(sum(
        b.padded * m * b.dg * b.da * (b.dg + b.da) for b in layout.buckets
    ))


def predict(
    cand: Candidate,
    base: Any,
    world: int,
    hardware: HardwareSpec = HardwareSpec(),
) -> dict[str, Any]:
    """Cost-table row for one candidate: byte/FLOP/memory terms and the
    predicted per-step seconds, plus feasibility under the HBM budget.

    The byte terms are lifted VERBATIM from ``comms_summary`` of the
    candidate's static layout — the parity the tests assert against the
    instantiated engine.
    """
    from kfac_tpu.observability import comms as comms_lib

    cfg = candidate_config(base, cand)
    layout = StaticLayout(cfg, world, cand.grad_worker_fraction)
    comms = layout.comms_report()

    stat_bytes = comms['stat_transport']['bytes']
    grad_bytes = comms['grad_broadcast_bytes']
    reshard_bytes = comms['decomp_reshard_bytes']
    comm_opt = layout.strategy == enums.DistributedStrategy.COMM_OPT
    bytes_per_step = (
        stat_bytes / cand.factor_update_steps
        + reshard_bytes / cand.inv_update_steps
        + (0 if comm_opt else grad_bytes)
    )

    # One full inverse refresh, in per-device seconds. Synchronously it
    # lands on a single boundary step; the async backends reshape it:
    # 'sliced' spreads the same device work over the window's slices,
    # 'host' moves the FLOPs off-device entirely and the step only pays
    # the boundary device_put of the refreshed payload.
    decomp_dev_flops = _decomp_flops(layout) / world
    refresh_s = decomp_dev_flops / hardware.matmul_flops
    host_transfer_s = 0.0
    if cand.async_inverse == 'host':
        host_transfer_s = reshard_bytes / hardware.host_bandwidth
        refresh_spike_s = host_transfer_s
        flops_per_step = _precond_flops(layout) / layout.n_cols
    elif cand.async_inverse == 'sliced':
        n_slices = max(1, min(cand.inv_update_steps, _refresh_units(layout)))
        refresh_spike_s = refresh_s / n_slices
        flops_per_step = (
            decomp_dev_flops / cand.inv_update_steps
            + _precond_flops(layout) / layout.n_cols
        )
    else:
        refresh_spike_s = refresh_s
        flops_per_step = (
            decomp_dev_flops / cand.inv_update_steps
            + _precond_flops(layout) / layout.n_cols
        )

    factor_item = comms_lib._itemsize(cfg.factor_dtype)
    factor_total = sum(
        sb.padded * sb.d * sb.d * factor_item
        for store in (layout.a_store, layout.g_store)
        for sb in store
    )
    memory = {
        # factor stacks shard over EVERY device; decompositions live in
        # the strategy's resident layout (per column, replicated under
        # COMM-OPT where n_cols == 1); the preconditioned grad stacks
        # end replicated on every device
        'factors': factor_total / world,
        'decomps': reshard_bytes / layout.n_cols,
        'grad_stacks': float(grad_bytes),
    }
    memory['total'] = (
        memory['factors'] + memory['decomps'] + memory['grad_stacks']
    )

    feasible = True
    reason = None
    if hardware.hbm_bytes is not None and memory['total'] > hardware.hbm_bytes:
        feasible = False
        reason = (
            f'factor-state memory {memory["total"]:.3e} B exceeds the '
            f'{hardware.hbm_bytes:.3e} B HBM budget'
        )

    return {
        'knobs': cand.knobs(world),
        'feasible': feasible,
        'infeasible_reason': reason,
        'bytes_per_occurrence': {
            'stat_transport': stat_bytes,
            'grad_broadcast': grad_bytes,
            'decomp_reshard': reshard_bytes,
        },
        'bytes_per_step': bytes_per_step,
        'flops_per_device_per_step': flops_per_step,
        'memory_per_device_bytes': memory,
        # worst single step's refresh overshoot above steady state — the
        # latency-jitter term the async backends exist to flatten
        'refresh_spike_s': refresh_spike_s,
        'predicted_step_s': (
            flops_per_step / hardware.matmul_flops
            + bytes_per_step / hardware.collective_bandwidth
            + host_transfer_s / cand.inv_update_steps
        ),
    }


def _layer_dims(registry: Any) -> list[tuple[int, int]]:
    """Per-layer (da, dg) in the posterior's deterministic layer order
    (``sample_params`` folds keys over ``sorted(layers)`` — same here)."""
    return [
        (registry.layers[name].a_factor_shape[0],
         registry.layers[name].g_factor_shape[0])
        for name in sorted(registry.layers)
    ]


def price_serving(
    registry: Any,
    serving: Any,
    hardware: HardwareSpec = HardwareSpec(),
) -> dict[str, Any]:
    """Serving-tier cost summary for a plan's ``serving`` knob.

    Same host-side shape arithmetic as :func:`predict`, applied to the
    inference engine (``kfac_tpu/serving/engine.py``) instead of the
    training step:

    - **MC path** per padded bucket: ``n_samples`` posterior draws (the
      kron sample is two stacked matmuls per layer, ``2 dg da (dg+da)``
      FLOPs) plus ``n_samples`` forward applies of the padded batch
      (``2 b da dg`` per layer);
    - **closed-form path** per bucket: one MAP apply plus the last-layer
      linearized variance (the ``phi @ qa`` rotation and eigen-weighted
      square, ``~2 b da (da+1)``, plus the ``(qg*qg) @ inv_g`` diagonal);
    - **per-replica HBM**: MAP params plus the posterior arrays every
      replica holds resident (``qa``/``qg``/``da``/``dg`` per layer, f32).

    Buckets come from ``serving.warmup_batches`` through the same
    ``size_class`` grammar the engine pads with; with no warmup list the
    granularity floor and ``max_batch`` ceiling bound the range. The
    returned dict is what ``autotune(serving=...)`` writes into
    ``TunedPlan.knobs['serving']``.
    """
    from kfac_tpu.parallel import kaisa as kaisa_lib

    dims = _layer_dims(registry)
    if not dims:
        raise ValueError('price_serving needs a registry with layers')
    gran = int(serving.bucket_granularity)
    max_batch = int(serving.max_batch)
    n_mc = int(serving.n_samples or 1)
    n_esc = int(serving.escalated_n_samples)

    sizes = tuple(serving.warmup_batches) or (gran, max_batch)
    buckets = sorted({
        kaisa_lib.size_class(min(int(b), max_batch), gran) for b in sizes
    })

    apply_flops = float(sum(2.0 * da * dg for da, dg in dims))  # per example
    sample_flops = float(sum(2.0 * dg * da * (dg + da) for da, dg in dims))
    # closed-form variance prices against the LAST layer only — the path
    # exists only for mode='last_layer' exports
    da_ll, dg_ll = dims[-1]
    rows = []
    for b in buckets:
        mc = n_mc * (sample_flops + b * apply_flops)
        cf = (
            b * apply_flops
            + 2.0 * b * da_ll * (da_ll + 1.0)
            + 2.0 * dg_ll * dg_ll
        )
        rows.append({
            'bucket': int(b),
            'mc_flops': mc,
            'cf_flops': cf,
            'escalated_mc_flops': n_esc * (sample_flops + b * apply_flops),
            'mc_s': mc / hardware.matmul_flops,
            'cf_s': cf / hardware.matmul_flops,
        })

    param_bytes = float(sum(4.0 * da * dg for da, dg in dims))
    posterior_bytes = float(sum(
        4.0 * (da * da + dg * dg + da + dg) for da, dg in dims
    ))
    return {
        'bucket_granularity': gran,
        'max_batch': max_batch,
        'n_samples': n_mc,
        'escalated_n_samples': n_esc,
        'buckets': rows,
        'hbm_bytes_per_replica': param_bytes + posterior_bytes,
    }
