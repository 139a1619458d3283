"""Self-driving elastic fleet: retune-on-restore + drift-triggered
live layout migration.

KAISA's premise is that the layout (one scalar, the gradient-worker
fraction, plus the bucket/transport knobs hanging off it) should track
the *deployment*, not a hand-config. Two deployment events break a
hand-picked — or even a tuned — layout mid-job:

1. **Preemption onto a different topology.** A :class:`~kfac_tpu
   .autotune.TunedPlan` is fingerprint-guarded, so restoring a job onto
   a resized pod silently discards the plan and falls back to defaults
   (``resolve_auto_layout``). The fleet controller instead re-runs the
   autotuner's **cost-model-only fast path** (``measure=False`` — the
   analytic model ranks the same candidate grid, no trial engines, no
   devices timed, deterministic and instant), rebuilds the engine under
   the fresh plan, and restores elastically through the rotation's
   layout manifests (``CheckpointManager.restore_latest(engine=...)``).
   Retune attempts retry with exponential backoff; if the tuned restore
   itself fails, the controller falls back to the canonical layout so
   the job always comes back up.

2. **Comms drift in steady state.** A long-running job's cross-host
   skew (stragglers, congested links) makes the once-optimal layout
   stale. The controller watches the flight recorder's cross-host skew
   columns (``drain_flight``'s ``skew_min/max/mean`` per headline key)
   against configurable thresholds; sustained drift triggers a
   model-only retune, and — when the retuned knobs actually differ —
   a pod-coordinated live migration at the **next checkpoint
   boundary**: blocking save → rebuild engine under the new plan →
   elastic restore → resume. Every host votes on the outcome through
   :func:`kfac_tpu.parallel.multihost.agree_decision`; any host's
   failure aborts the migration pod-wide.

Rollback semantics: the migration mutates NOTHING until it is verified
— the old engine, the in-memory TrainState, and the manager's engine
binding are only swapped after the elastic restore succeeded on every
host at the expected step. An abort therefore *is* the rollback:
training continues on the last-good layout and state bit-for-bit, the
pending plan is dropped, and a cooldown suppresses immediate re-arming.

Wiring: ``Trainer(fleet=FleetController(...))`` drives
:meth:`FleetController.on_step` from all four step paths and delegates
``restore_latest`` to :meth:`FleetController.restore_elastic`. See
docs/ROBUSTNESS.md ("Self-driving fleet").
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Sequence

import jax

from kfac_tpu import warnings as warnings_lib
from kfac_tpu.autotune import model as model_lib
from kfac_tpu.autotune import plan as plan_lib
from kfac_tpu.autotune import search as search_lib
from kfac_tpu.observability import flight_recorder as flight_lib
from kfac_tpu.parallel import multihost
from kfac_tpu.resilience import manager as manager_lib

#: search.autotune keyword arguments a controller may constrain
#: (everything else about the fast path is fixed: measure=False, the
#: live world size, the controller's HardwareSpec)
SEARCH_OVERRIDE_KEYS = (
    'fractions', 'granularities', 'transports', 'inv_cadences', 'top_k',
)

#: the plan artifact's filename inside the checkpoint rotation directory
#: — the plan travels WITH the rotation, so a restore on a new topology
#: finds the layout the job was actually running
PLAN_FILENAME = 'PLAN.json'

#: Introspectable migration state machine for the kfaclint pod tier
#: (KFL305). The pod rules parse this literal from the AST (never
#: importing this module) and model-check it under the fault alphabet
#: (crash at any state, vote outcome): every state reachable, both vote
#: outcomes handled wherever one is, controller state mutated ONLY on a
#: ``vote-commit`` transition, and abort transitions mutating nothing —
#: the mutate-nothing-until-verified contract of
#: :meth:`FleetController._maybe_migrate` as a checkable artifact. The
#: declared ``vote_op`` is additionally cross-checked against the ops
#: reachable from ``_maybe_migrate``, so dropping the real
#: ``agree_decision`` call breaks the lint even with the table intact.
#: Keep it a pure literal.
MIGRATION_PROTOCOL = {
    'machine': 'state',
    'name': 'fleet-migration',
    'function': 'FleetController._maybe_migrate',
    'vote_op': 'agree_decision',
    'states': ('idle', 'armed', 'boundary', 'committed', 'aborted'),
    'initial': 'idle',
    'transitions': (
        {'from': 'idle', 'event': 'drift', 'to': 'armed', 'mutates': ()},
        {'from': 'armed', 'event': 'checkpoint-boundary', 'to': 'boundary',
         'mutates': ()},
        {'from': 'boundary', 'event': 'vote-commit', 'to': 'committed',
         'mutates': ('plan', 'engine', 'train_state')},
        {'from': 'boundary', 'event': 'vote-abort', 'to': 'aborted',
         'mutates': ()},
        {'from': 'committed', 'event': 'cooldown', 'to': 'idle',
         'mutates': ()},
        {'from': 'aborted', 'event': 'cooldown', 'to': 'idle',
         'mutates': ()},
    ),
}


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Policy knobs of the self-driving fleet controller.

    All steady-state cadences are in engine steps. The KFL106 lint pins
    the knob table in docs/ROBUSTNESS.md to these fields.

    Args:
        check_every: drift-check cadence — every this-many steps the
            controller drains the flight recorder and evaluates the skew
            columns. Multi-host, the drain itself is one DCN gather, so
            this is also the fleet's added collective cadence.
        drift_keys: flight-recorder record keys whose cross-host skew is
            watched (each needs ``skew_min/max/mean`` columns, i.e. must
            be in the drain's skew keys — the controller's default drain
            requests exactly these).
        drift_threshold: relative skew ``(skew_max - skew_min) /
            |skew_mean|`` above which a window counts as drifted.
        drift_window: records (newest-first) averaged per drift check;
            checks are skipped until the ring holds a full window.
        drift_patience: consecutive over-threshold checks required
            before a retune triggers — one straggling drain must not
            re-layout the job.
        cooldown_steps: steps after any fleet event (migration, abort,
            failed or no-op retune) during which drift checks are
            suppressed, bounding the worst-case migration rate.
        retune_max_retries: extra cost-model retune attempts after the
            first failure.
        retune_backoff_base: first retry delay, seconds; attempt ``k``
            waits ``min(backoff_max, base * 2**k)``.
        retune_backoff_max: retry delay ceiling, seconds.
    """

    check_every: int = 16
    drift_keys: tuple[str, ...] = ('grad_norm', 'loss')
    drift_threshold: float = 0.5
    drift_window: int = 4
    drift_patience: int = 2
    cooldown_steps: int = 64
    retune_max_retries: int = 2
    retune_backoff_base: float = 0.5
    retune_backoff_max: float = 8.0

    def __post_init__(self) -> None:
        if self.check_every < 1:
            raise ValueError(
                f'check_every must be >= 1, got {self.check_every}'
            )
        if not self.drift_keys:
            raise ValueError('drift_keys must name at least one record key')
        object.__setattr__(self, 'drift_keys', tuple(self.drift_keys))
        if self.drift_threshold <= 0:
            raise ValueError(
                f'drift_threshold must be > 0, got {self.drift_threshold}'
            )
        if self.drift_window < 1:
            raise ValueError(
                f'drift_window must be >= 1, got {self.drift_window}'
            )
        if self.drift_patience < 1:
            raise ValueError(
                f'drift_patience must be >= 1, got {self.drift_patience}'
            )
        if self.cooldown_steps < 0:
            raise ValueError(
                f'cooldown_steps must be >= 0, got {self.cooldown_steps}'
            )
        if self.retune_max_retries < 0:
            raise ValueError(
                'retune_max_retries must be >= 0, got '
                f'{self.retune_max_retries}'
            )
        if self.retune_backoff_base <= 0 or self.retune_backoff_max <= 0:
            raise ValueError('retune backoff delays must be > 0')


class FleetController:
    """Owns the layout lifecycle of one training job.

    Args:
        manager: the :class:`~kfac_tpu.resilience.CheckpointManager`
            whose rotation the fleet saves into and restores from. The
            controller takes over its ``engine`` binding.
        config: :class:`FleetConfig` policy knobs.
        plan: initial tuned plan (TunedPlan / JSON dict / path). Default:
            the rotation directory's ``PLAN.json`` when present,
            otherwise the controller tunes one at :meth:`attach` (reason
            ``'startup'``).
        plan_path: where (re)tuned plans are persisted (rank 0, atomic
            write). Default: ``PLAN.json`` inside the manager's rotation
            directory.
        hardware: :class:`~kfac_tpu.autotune.model.HardwareSpec` fed to
            the cost model.
        search_overrides: optional :data:`SEARCH_OVERRIDE_KEYS` kwargs
            constraining every retune's candidate grid (an operator's
            standing layout constraints).
        drain: flight-recorder drain ``drain(state) -> records``;
            default drains with ``skew_keys=config.drift_keys``.
            Injectable for tests/bench (``testing.faults.skewed_drain``).
        sleep: retune-backoff sleep (injectable for tests).
    """

    def __init__(
        self,
        manager: Any,
        config: FleetConfig | None = None,
        *,
        plan: Any = None,
        plan_path: str | os.PathLike[str] | None = None,
        hardware: model_lib.HardwareSpec | None = None,
        search_overrides: dict[str, Any] | None = None,
        drain: Callable[[Any], list[dict[str, Any]]] | None = None,
        sleep: Callable[[float], None] = time.sleep,
    ) -> None:
        self.manager = manager
        self.config = config if config is not None else FleetConfig()
        self.hardware = (
            hardware if hardware is not None else model_lib.HardwareSpec()
        )
        self.search_overrides = dict(search_overrides or {})
        unknown = set(self.search_overrides) - set(SEARCH_OVERRIDE_KEYS)
        if unknown:
            raise ValueError(
                f'unknown search_overrides {sorted(unknown)}; expected a '
                f'subset of {SEARCH_OVERRIDE_KEYS}'
            )
        self.plan_path = (
            os.path.join(manager.directory, PLAN_FILENAME)
            if plan_path is None else os.fspath(plan_path)
        )
        self._initial_plan = plan
        self._drain = drain
        self._sleep = sleep
        self.base: Any = None
        self.engine: Any = None
        self._plan: plan_lib.TunedPlan | None = None
        self._pending_plan: plan_lib.TunedPlan | None = None
        self._armed_step: int | None = None
        self._drift_hits = 0
        self._last_check_step: int | None = None
        self._last_event_step: int | None = None
        #: chronological fleet events ({'event', 'step', 'detail'})
        self.events: list[dict[str, Any]] = []
        #: headline counters/timings
        self.stats: dict[str, Any] = {
            'retunes': 0, 'migrations': 0, 'aborts': 0,
            'retune_s': None, 'migration_s': None, 'downtime_steps': None,
        }

    # ---------------------------------------------------------------- attach

    @property
    def plan(self) -> plan_lib.TunedPlan | None:
        """The plan the live engine is running under (None: canonical)."""
        return self._plan

    def attach(self, base: Any) -> Any:
        """Resolve the engine for ``base`` (a bare
        :class:`~kfac_tpu.KFACPreconditioner` config) under the best
        available plan.

        A plan whose fingerprint matches the live topology applies
        as-is; a stale or missing plan triggers the cost-model-only
        retune (the fingerprint mismatch is the "restored onto a changed
        topology" signal — topology is part of the fingerprint). Returns
        the built engine and binds it to the checkpoint manager.
        """
        if hasattr(base, 'mesh'):
            raise ValueError(
                'FleetController.attach takes the bare KFACPreconditioner '
                'config, not a built engine — the fleet must be free to '
                'pick the mesh'
            )
        self.base = base
        plan: plan_lib.TunedPlan | None = None
        source = self._initial_plan
        if source is None and os.path.exists(self.plan_path):
            source = self.plan_path
        if source is not None:
            try:
                plan = plan_lib.as_plan(source)
            except (TypeError, ValueError, OSError) as exc:
                warnings_lib.warn_fleet_event(
                    'plan-unreadable',
                    f'{type(exc).__name__}: {exc}; retuning from scratch',
                )
                plan = None
        current = plan_lib.plan_fingerprint(base.registry)
        if plan is not None and not plan_lib.fingerprint_matches(
            plan.fingerprint, current
        ):
            diff = plan_lib.fingerprint_diff(plan.fingerprint, current)
            warnings_lib.warn_fleet_event(
                'topology-changed',
                f'plan fingerprint differs on {"/".join(diff) or "?"}; '
                'running the cost-model-only retune',
            )
            plan = self._retune('topology-changed')
        elif plan is not None and not self._topology_fits(plan):
            topo = plan.knobs.get('topology') or {}
            warnings_lib.warn_fleet_event(
                'topology-changed',
                f"plan pipeline factorization pp={topo.get('pp')} "
                f"tp={topo.get('tp')} does not divide the "
                f'{jax.device_count()}-device world; running the '
                'cost-model-only retune',
            )
            plan = self._retune('topology-changed')
        elif plan is None:
            plan = self._retune('startup')
        engine, applied = self._build_engine(plan)
        self._plan = plan if applied else None
        self.engine = engine
        self.manager.engine = engine
        if self._plan is not None:
            self._persist(self._plan)
        return engine

    # ---------------------------------------------------------------- retune

    def _retune(self, reason: str) -> plan_lib.TunedPlan | None:
        """Cost-model-only fast path: rank the candidate grid with the
        analytic model (no measured trials, no engines built) under
        retry/backoff. Returns None after exhausting retries."""
        if self.base is None:
            raise ValueError('FleetController is not attached to a config')
        cfg = self.config
        t0 = time.monotonic()
        for attempt in range(cfg.retune_max_retries + 1):
            try:
                plan = search_lib.autotune(
                    self.base,
                    measure=False,
                    world=jax.device_count(),
                    hardware=self.hardware,
                    **self.search_overrides,
                )
                break
            except KeyboardInterrupt:
                raise
            except Exception as exc:
                if attempt == cfg.retune_max_retries:
                    warnings_lib.warn_fleet_event(
                        'retune-failed',
                        f'{type(exc).__name__}: {exc}; the canonical '
                        'layout stands',
                    )
                    self._event('retune-failed', detail=str(exc))
                    return None
                self._sleep(min(
                    cfg.retune_backoff_max,
                    cfg.retune_backoff_base * (2 ** attempt),
                ))
        plan.meta['retune_reason'] = reason
        plan.meta['fleet'] = True
        self.stats['retunes'] += 1
        self.stats['retune_s'] = time.monotonic() - t0
        self._event('retune', detail=reason)
        return plan

    @staticmethod
    def _topology_fits(plan: plan_lib.TunedPlan) -> bool:
        """A 3D-planner plan fits only when its ``pp * tp`` factors the
        live device count — an elastic shrink/grow can break that even
        when the coarse fingerprint still matches (same backend, same
        device kind, restored before the count is re-fingerprinted)."""
        topo = (plan.knobs or {}).get('topology')
        if not topo:
            return True
        pp = int(topo.get('pp', 1))
        tp = int(topo.get('tp', 1))
        return pp >= 1 and tp >= 1 and jax.device_count() % (pp * tp) == 0

    def _build_engine(
        self, plan: plan_lib.TunedPlan | None
    ) -> tuple[Any, bool]:
        """(engine, plan_applied). No controller state is mutated here —
        the migration path builds speculative engines it may discard."""
        from kfac_tpu.parallel.kaisa import DistributedKFAC

        if plan is None:
            return DistributedKFAC(config=self.base), False
        if (plan.knobs or {}).get('topology'):
            # topology plans drive pipeline engines (PipelinedLM /
            # PipelineKFAC own the pipe mesh); the flat KAISA engine
            # cannot honor them, so the fleet runs canonically
            warnings_lib.warn_fleet_event(
                'plan-not-applied',
                'plan carries a 3D topology; the fleet drives the flat '
                'KAISA engine, rebuilding under the canonical layout',
            )
            return DistributedKFAC(config=self.base), False
        engine = DistributedKFAC(config=self.base, auto_layout=plan)
        if not engine.auto_layout_applied:
            warnings_lib.warn_fleet_event(
                'plan-not-applied',
                'rebuilding under the canonical layout',
            )
            return DistributedKFAC(config=self.base), False
        return engine, True

    def _persist(self, plan: plan_lib.TunedPlan) -> None:
        if multihost.process_index() != 0:
            return
        try:
            plan.save(self.plan_path)
        except OSError as exc:
            warnings_lib.warn_fleet_event(
                'plan-persist-failed', f'{type(exc).__name__}: {exc}'
            )

    def _event(
        self, event: str, step: int | None = None, detail: str = ''
    ) -> None:
        self.events.append({'event': event, 'step': step, 'detail': detail})

    # --------------------------------------------------------------- restore

    def _has_committed(self) -> bool:
        return any(
            self.manager._is_committed(s)
            for s in self.manager.rotation_steps()
        )

    def restore_elastic(
        self, extra_template: dict[str, Any] | None = None
    ) -> manager_lib.RestoreResult | None:
        """Restore the newest good checkpoint into the tuned engine.

        The engine :meth:`attach` built already reflects the freshest
        plan for THIS topology, so the restore is elastic by
        construction (the rotation's manifests reshard the factors into
        the tuned layout). If the tuned restore fails while the rotation
        does hold committed checkpoints, the controller gracefully falls
        back: it rebuilds the canonical (plan-less) engine, restores
        into that, and rebinds. Returns None only on a genuinely empty
        or unrestorable rotation.
        """
        if self.engine is None:
            raise ValueError(
                'FleetController.restore_elastic before attach(): the '
                'Trainer calls attach for you, or call it explicitly'
            )
        result = None
        try:
            result = self.manager.restore_latest(
                engine=self.engine, extra_template=extra_template
            )
        except KeyboardInterrupt:
            raise
        except Exception as exc:
            warnings_lib.warn_fleet_event(
                'tuned-restore-failed',
                f'{type(exc).__name__}: {exc}; retrying under the '
                'canonical layout',
            )
        if result is not None:
            return result
        if not self._has_committed():
            return None  # fresh start, nothing to restore
        warnings_lib.warn_fleet_event(
            'tuned-restore-failed',
            'no rotation candidate restored under the tuned layout; '
            'retrying under the canonical layout',
        )
        engine, _ = self._build_engine(None)
        result = self.manager.restore_latest(
            engine=engine, extra_template=extra_template
        )
        if result is None:
            return None
        self._plan = None
        self.engine = engine
        self.manager.engine = engine
        self._event('restore-fallback', step=result.step)
        return result

    # ---------------------------------------------------------- steady state

    def on_step(self, trainer: Any, state: Any) -> Any:
        """Steady-state tick, called by the Trainer after each completed
        step (all four step paths). Returns the (possibly migrated)
        TrainState.

        SPMD symmetry: everything the decision depends on — the step
        cadence, the drained skew columns (already pod-aggregated), the
        deterministic cost model — is identical on every host, so every
        host arms and migrates on the same step; the explicit
        ``agree_decision`` vote then catches per-host *execution*
        failures (a bad filesystem, a failed reshard) rather than
        decision divergence.
        """
        cfg = self.config
        step = trainer._step_count
        if step is None:
            kstate = getattr(state, 'kfac_state', state)
            if kstate is None:
                return state
            step = int(jax.device_get(kstate.step))
        if self._pending_plan is not None:
            return self._maybe_migrate(trainer, state, step)
        if (
            self._last_event_step is not None
            and step - self._last_event_step < cfg.cooldown_steps
        ):
            return state
        if step % cfg.check_every != 0 or step == self._last_check_step:
            return state
        self._last_check_step = step
        drain = self._drain
        records = (
            drain(state) if drain is not None
            else flight_lib.drain_flight(state, skew_keys=cfg.drift_keys)
        )
        window = records[-cfg.drift_window:]
        if len(window) < cfg.drift_window:
            return state
        worst = max(
            sum(flight_lib.skew_ratio(rec, key) for rec in window)
            / len(window)
            for key in cfg.drift_keys
        )
        if worst <= cfg.drift_threshold:
            self._drift_hits = 0
            return state
        self._drift_hits += 1
        if self._drift_hits < cfg.drift_patience:
            return state
        self._drift_hits = 0
        self._event(
            'drift', step=step,
            detail=f'relative skew {worst:.3f} > {cfg.drift_threshold}',
        )
        if self.manager.save_interval_steps is None:
            warnings_lib.warn_fleet_event(
                'migration-disabled',
                'periodic saves are off — no checkpoint boundary to '
                'migrate at',
            )
            self._last_event_step = step
            return state
        plan = self._retune('drift')
        if plan is None:
            self._last_event_step = step
            return state
        if self._plan is not None and json.loads(
            json.dumps(plan.knobs)
        ) == json.loads(json.dumps(self._plan.knobs)):
            self._event('retune-noop', step=step,
                        detail='tuned knobs unchanged')
            self._last_event_step = step
            return state
        self._pending_plan = plan
        self._armed_step = step
        self._event('armed', step=step)
        return state

    def _maybe_migrate(self, trainer: Any, state: Any, step: int) -> Any:
        """Execute the armed migration once a checkpoint boundary
        arrives; mutate-nothing-until-verified (see module docstring)."""
        interval = self.manager.save_interval_steps
        if interval is None or step <= 0 or step % interval != 0:
            return state
        t0 = time.monotonic()
        ok = False
        result = None
        new_engine = None
        detail = ''
        try:
            # make this exact step durable first (idempotent when the
            # periodic save just committed it) — the rollback target
            self.manager.save_emergency(
                state, reason='fleet-migration', step=step
            )
            new_engine, applied = self._build_engine(self._pending_plan)
            if applied:
                _, template = manager_lib._split_train_state(state)
                result = self.manager.restore_latest(
                    engine=new_engine, extra_template=template
                )
                ok = result is not None and result.step == step
                if not ok:
                    detail = 'elastic restore failed or landed off-step'
            else:
                detail = 'pending plan did not apply'
        except KeyboardInterrupt:
            raise
        except Exception as exc:
            detail = f'{type(exc).__name__}: {exc}'
        ok = multihost.agree_decision(ok)
        pending, self._pending_plan = self._pending_plan, None
        armed_step, self._armed_step = self._armed_step, None
        self._last_event_step = step
        if not ok:
            self.stats['aborts'] += 1
            warnings_lib.warn_fleet_event(
                'migration-aborted',
                f'{detail or "a peer host failed"}; training continues '
                'on the last-good layout',
            )
            self._event('migration-aborted', step=step, detail=detail)
            return state
        self._plan = pending
        self.engine = new_engine
        self.manager.engine = new_engine
        self._persist(pending)
        new_state = state._replace(
            params=result.extra['params'],
            opt_state=result.extra['opt_state'],
            kfac_state=result.state,
            model_state=result.extra.get('model_state', state.model_state),
        )
        trainer.rebind_engine(new_engine)
        trainer.resume(new_state)
        self.stats['migrations'] += 1
        self.stats['migration_s'] = time.monotonic() - t0
        self.stats['downtime_steps'] = step - (
            armed_step if armed_step is not None else step
        )
        self._event(
            'migrated', step=step,
            detail=f'downtime {self.stats["downtime_steps"]} steps',
        )
        return new_state
