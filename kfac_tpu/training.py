"""Training engine: K-FAC-preconditioned train steps with capture cadence.

Counterpart of the reference's example engine/optimizer glue
(examples/vision/engine.py:44-104, examples/vision/optimizers.py:16-114):
chains curvature capture, the preconditioner, and any optax optimizer into
jitted train steps.

Cadence the XLA way: the reference's hooks early-exit when
``steps % factor_update_steps != 0`` (kfac/base_preconditioner.py:444-455).
Under jit, skipping the covariance computation requires a different traced
program, so the engine compiles TWO step variants — with and without
curvature capture — and dispatches on the host-side step counter (the
schedule is deterministic, so this costs one extra compile, not a recompile
per step).
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, NamedTuple

import jax
import optax

from kfac_tpu import health as health_lib
from kfac_tpu import tracing
from kfac_tpu.async_inverse import host as async_host_lib
from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.observability import ledger as ledger_lib


def _replicate_onto(mesh, tree: Any) -> Any:
    """Replicate a host-resident pytree onto every device of ``mesh``.

    Single-process, a plain ``device_put`` suffices. When the mesh spans
    OS processes (multi-controller), ``device_put`` refuses shardings
    with non-addressable devices — each process must instead construct
    the global array from its local shards (every process holds the
    full replicated value, e.g. extras a checkpoint restore produced
    into a single-device template)."""
    import numpy as np

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    if all(
        d.process_index == jax.process_index()
        for d in mesh.devices.flat
    ):
        return jax.device_put(tree, rep)

    def leaf(x):
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, rep, lambda idx: arr[idx]
        )

    return jax.tree_util.tree_map(leaf, tree)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    kfac_state: Any
    model_state: Any  # mutable collections (e.g. batch_stats), or None


@dataclasses.dataclass
class Trainer:
    """Builds and dispatches K-FAC train steps.

    Args:
        loss_fn: ``loss_fn(params, model_state, batch) -> (loss,
            new_model_state)``; ``model_state`` may be None for stateless
            models. Must call the flax model inside so capture can intercept.
        donate_state: donate the TrainState buffers to each step (halves
            peak memory for params/opt/K-FAC state). Off by default because
            donation also invalidates the arrays the state was built from
            (e.g. the params passed to ``init``); enable for production
            training loops that never touch stale state.
        kfac: a :class:`kfac_tpu.KFACPreconditioner` or
            :class:`kfac_tpu.parallel.DistributedKFAC` (or None for a
            first-order baseline).
        optimizer: any optax gradient transformation.
        registry: layer registry (required when kfac is set).
        checkpoints: optional
            :class:`kfac_tpu.resilience.CheckpointManager`. Every step
            path (:meth:`step`, :meth:`scan_steps`,
            :meth:`step_accumulate`, :meth:`step_accumulate_scan`) calls
            its ``on_step`` after the update, so periodic async saves and
            preemption-signal emergency flushes ride the training loop
            with no extra plumbing; :meth:`restore_latest` resumes from
            its rotation.
        auto_layout: a :class:`kfac_tpu.autotune.TunedPlan` (or a path to
            one) from ``tools/kfac_tune.py``. Requires ``kfac`` to be a
            bare :class:`kfac_tpu.KFACPreconditioner` config: the Trainer
            builds the :class:`~kfac_tpu.parallel.DistributedKFAC` itself
            so the plan can pick both the config knobs and the mesh. A
            fingerprint mismatch falls back to the default layout with a
            rate-limited :class:`~kfac_tpu.warnings.LayoutPlanWarning`.
        run_id: shared run identifier threaded into every telemetry
            stream this Trainer touches (the engine's compile-watch
            journal stamps it per record; :meth:`run_header` builds the
            header for ``JSONLWriter``/``PostmortemWriter``), so the run
            ledger (``observability/ledger.py``) can join streams from
            one run. Auto-generated when left None.
    """

    loss_fn: Callable[..., Any]
    optimizer: optax.GradientTransformation
    kfac: Any = None
    registry: Any = None
    factor_update_steps: int = 1
    donate_state: bool = False
    checkpoints: Any = None
    auto_layout: Any = None
    run_id: str | None = None

    def __post_init__(self) -> None:
        if self.run_id is None:
            self.run_id = ledger_lib.new_run_id()
        if self.auto_layout is not None:
            if self.kfac is None:
                raise ValueError(
                    'Trainer(auto_layout=...) requires kfac: the plan '
                    'configures a KFAC preconditioner'
                )
            if hasattr(self.kfac, 'mesh'):
                raise ValueError(
                    'Trainer(auto_layout=...) takes the bare '
                    'KFACPreconditioner config, not a built engine — the '
                    'plan must pick the mesh; pass '
                    'DistributedKFAC(config, auto_layout=plan) yourself '
                    'to combine a plan with an explicit mesh'
                )
            from kfac_tpu.parallel.kaisa import DistributedKFAC

            self.kfac = DistributedKFAC(
                config=self.kfac, auto_layout=self.auto_layout
            )
        # Host-side mirror of kfac_state.step, used only for cadence
        # dispatch. None = not yet synced: the first step()/step_accumulate()
        # reads the device counter, so a Trainer driving a state restored by
        # ``checkpoint.restore`` at step N stays aligned with the device-side
        # lax.cond cadence instead of silently freezing factor updates
        # (host picks no-stats variant while device cond expects stats).
        self._step_count: int | None = None
        # whether the preconditioner's step accepts the loss (for the
        # flight-recorder ring); duck-typed so engine objects with the
        # bare (state, grads, stats) signature keep working unchanged
        self._kfac_takes_loss = (
            self.kfac is not None
            and 'loss' in inspect.signature(self.kfac.step).parameters
        )
        if self.checkpoints is not None and self.kfac is None:
            raise ValueError(
                'Trainer(checkpoints=...) requires a kfac preconditioner: '
                'the CheckpointManager persists the K-FAC durable state'
            )
        if self.kfac is not None:
            if self.registry is None:
                self.registry = self.kfac.config.registry if hasattr(
                    self.kfac, 'config'
                ) else self.kfac.registry
            cap = capture_lib.CurvatureCapture(self.registry)

            def wrapped_loss(params, args):
                model_state, batch = args
                return self.loss_fn(params, model_state, batch)

            self._run_stats = cap.value_stats_and_grad(wrapped_loss, has_aux=True)
            cfg = self.kfac.config if hasattr(self.kfac, 'config') else self.kfac
            self.factor_update_steps = cfg.factor_update_steps
        donate = (0,) if self.donate_state else ()
        self._jit_with_stats = self._watched(
            'trainer.step/with_stats',
            jax.jit(self._step_with_stats, donate_argnums=donate),
        )
        self._jit_no_stats = self._watched(
            'trainer.step/no_stats',
            jax.jit(self._step_no_stats, donate_argnums=donate),
        )
        watch = self._compile_watch()
        if watch is not None:
            watch.run_id = self.run_id

    # ------------------------------------------------------------- builders

    def init(self, params: Any, model_state: Any = None) -> TrainState:
        return TrainState(
            params=params,
            opt_state=self.optimizer.init(params),
            kfac_state=None if self.kfac is None else self.kfac.init(),
            model_state=model_state,
        )

    def _apply_update(self, state: TrainState, grads, new_model_state):
        with tracing.trainer_scope('optimizer'):
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        return params, opt_state, new_model_state

    def _health_cfg(self):
        """The engine's HealthConfig, or None when the sentinel is off."""
        if self.kfac is None:
            return None
        cfg = self.kfac.config if hasattr(self.kfac, 'config') else self.kfac
        return getattr(cfg, 'health', None)

    def _compile_watch(self):
        """The engine's CompileWatch when ``compile_watch`` is enabled on
        its config — the Trainer's step paths count into the engine's
        watch, so engine.compiled_memory_report() covers both surfaces."""
        watcher = getattr(self.kfac, 'compile_watcher', None)
        return watcher() if callable(watcher) else None

    def run_header(self, stream: str) -> dict[str, Any]:
        """The shared run-header record for one telemetry stream — pass
        to ``JSONLWriter(path, run_header=trainer.run_header('metrics'))``
        so metrics, flight drains, and the compile journal from this run
        self-identify to the run ledger."""
        return ledger_lib.run_header(self.run_id, stream)

    def _watched(self, entry, fn, static_argnames=()):
        """Route a jitted step path through the engine's compile watch
        (see docs/OBSERVABILITY.md "Compile & memory truth"); identity
        when the watch is off."""
        watch = self._compile_watch()
        if watch is None:
            return fn
        return watch.wrap(entry, fn, static_argnames=static_argnames)

    def _finish_step(self, state: TrainState, grads, stats, new_model_state,
                     loss=None) -> TrainState:
        """Run the preconditioner + optimizer update — or skip it wholesale.

        With the health sentinel's ``skip_nonfinite`` guard armed, a single
        fused finiteness reduction over the loss and every gradient leaf
        gates the entire update through one ``lax.cond``: on a poisoned
        batch the params, optimizer state, curvature factors, AND mutable
        model state (batch stats) all stay put; only the step clock and
        ``skipped_steps`` advance (the reference's grad-scaler-overflow
        semantics, kfac/base_preconditioner.py:126-130, with the check on
        device instead of a host ``.item()`` sync).
        """

        def apply(_):
            if loss is not None and self._kfac_takes_loss:
                kstate, pgrads = self.kfac.step(
                    state.kfac_state, grads, stats, loss=loss
                )
            else:
                kstate, pgrads = self.kfac.step(
                    state.kfac_state, grads, stats
                )
            params, opt_state, model_state = self._apply_update(
                state, pgrads, new_model_state
            )
            return TrainState(params, opt_state, kstate, model_state)

        hc = self._health_cfg()
        if (
            hc is None
            or not hc.skip_nonfinite
            or state.kfac_state.health is None
        ):
            return apply(None)

        def skip(_):
            return state._replace(
                kfac_state=health_lib.mark_skipped(state.kfac_state)
            )

        checks = (grads,) if loss is None else (loss, grads)
        return jax.lax.cond(
            health_lib.all_finite(*checks), apply, skip, None
        )

    def _step_with_stats(self, state: TrainState, batch):
        (loss, new_model_state), grads, stats = self._run_stats(
            state.params, (state.model_state, batch)
        )
        new_state = self._finish_step(
            state, grads, stats, new_model_state, loss=loss
        )
        return new_state, loss

    def _step_no_stats(self, state: TrainState, batch):
        if self.kfac is None:
            def plain(params, model_state, batch):
                return self.loss_fn(params, model_state, batch)

            (loss, new_model_state), grads = jax.value_and_grad(
                plain, has_aux=True
            )(state.params, state.model_state, batch)
            params, opt_state, model_state = self._apply_update(
                state, grads, new_model_state
            )
            return TrainState(
                params, opt_state, state.kfac_state, model_state
            ), loss
        (loss, new_model_state), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True
        )(state.params, state.model_state, batch)
        new_state = self._finish_step(
            state, grads, None, new_model_state, loss=loss
        )
        return new_state, loss

    # ------------------------------------------------------------- dispatch

    def resume(self, state: TrainState) -> None:
        """Align cadence dispatch with a (restored) TrainState's step.

        Called automatically on the first ``step``; call explicitly after
        swapping in a different state mid-run.
        """
        ks = state.kfac_state
        self._step_count = (
            0 if ks is None else int(jax.device_get(ks.step))
        )

    def _sync_step_count(self, state: TrainState) -> None:
        if self._step_count is None:
            self.resume(state)

    def _capture_now(self) -> bool:
        """Evaluate the factor cadence host-side (schedules are pure
        functions of the step, so the host can run them concretely)."""
        cadence = self.factor_update_steps
        if callable(cadence):
            cadence = max(1, int(cadence(self._step_count)))
        return self._step_count % cadence == 0

    def check_health(self, state: TrainState) -> dict[str, Any]:
        """Host-side health snapshot + rate-limited first-occurrence
        warnings (quarantine / degradation per layer).

        Returns :func:`kfac_tpu.health.summary`'s dict, or ``{}`` when the
        sentinel is disabled. Synchronizes with the device (one small
        transfer) — the eager step paths call this automatically when
        ``HealthConfig.warn`` is set; compiled loops (:meth:`scan_steps`)
        never do, so call it between scans if you want the warnings.
        """
        hc = self._health_cfg()
        ks = state.kfac_state
        if hc is None or ks is None or getattr(ks, 'health', None) is None:
            return {}
        return health_lib.check_and_warn(hc, ks.health, step=self._step_count)

    def _maybe_warn(self, state: TrainState) -> None:
        hc = self._health_cfg()
        if hc is not None and hc.warn:
            self.check_health(state)

    def _drive_async(
        self, state: TrainState, step: int | None
    ) -> TrainState:
        """Promote a completed host-offloaded inverse refresh into the
        K-FAC state (``async_inverse`` mode ``'host'``; no-op otherwise).

        With ``step``: swaps only at window boundaries, blocking until the
        in-flight refresh lands (the swap stays boundary-atomic). Without
        one (the scan paths, where the host cannot intervene mid-scan):
        applies any already-completed payload non-blocking at entry.
        """
        if (
            self.kfac is None
            or state.kfac_state is None
            or getattr(self.kfac, '_async_mode', None) != 'host'
        ):
            return state
        ks = async_host_lib.pump(self.kfac, state.kfac_state, step=step)
        if ks is state.kfac_state:
            return state
        return state._replace(kfac_state=ks)

    def _drive_checkpoints(self, state: TrainState) -> None:
        """Tick the checkpoint autopilot after a completed step.

        ``self._step_count`` (when synced) spares the manager a device
        read; after :meth:`scan_steps` it is None and the manager reads
        the device counter itself. A :class:`kfac_tpu.resilience
        .Preempted` raised here propagates out of the step call — by
        then the emergency checkpoint is already durable.
        """
        if self.checkpoints is not None:
            self.checkpoints.on_step(state, step=self._step_count)

    def restore_latest(
        self, params: Any, model_state: Any = None
    ) -> TrainState | None:
        """Resume from the ``checkpoints`` manager's newest good
        checkpoint.

        ``params``/``model_state`` serve as restore templates (shapes,
        dtypes, shardings — e.g. from ``model.init``); they are never
        mutated. Returns ``None`` when the rotation holds no restorable
        checkpoint (fresh start — call :meth:`init` with the same
        templates to begin training). On success the returned TrainState
        carries the restored params, optimizer state, model state, and
        rematerialized K-FAC state, and the Trainer's cadence dispatch
        is re-aligned to the restored step.
        """
        if self.checkpoints is None:
            raise ValueError(
                'Trainer has no checkpoints manager: construct with '
                'checkpoints=CheckpointManager(...)'
            )
        template: dict[str, Any] = {
            'params': params,
            'opt_state': self.optimizer.init(params),
        }
        if model_state is not None:
            template['model_state'] = model_state
        result = self.checkpoints.restore_latest(
            engine=self.kfac, extra_template=template
        )
        if result is None:
            return None
        state = TrainState(
            params=result.extra['params'],
            opt_state=result.extra['opt_state'],
            kfac_state=result.state,
            model_state=result.extra.get('model_state', model_state),
        )
        mesh = getattr(self.kfac, 'mesh', None)
        if mesh is not None:
            # the extras restored into the CALLER's template placement
            # (typically one device, from model.init); the engine state
            # is committed to the mesh — replicate the extras onto it so
            # the next step's jit sees one consistent device set
            state = state._replace(
                params=_replicate_onto(mesh, state.params),
                opt_state=_replicate_onto(mesh, state.opt_state),
                model_state=(
                    None if state.model_state is None
                    else _replicate_onto(mesh, state.model_state)
                ),
            )
        self.resume(state)
        return state

    @tracing.trace(name='trainer/step')
    def step(self, state: TrainState, batch) -> tuple[TrainState, jax.Array]:
        """One optimization step; picks the capture variant on cadence.

        Recorded in the tracing table as ``trainer/step`` (dispatch cost
        only unless ``tracing.force_sync`` is on) and annotated with
        ``jax.profiler.StepTraceAnnotation`` so profiler captures group
        device activity per training step.

        Inside it, three host spans (``tracing.HOST_SPANS``) that carry the
        same step number split the call into what runs before the jitted
        program is launched, the launch, and what runs after: in a
        profile they say which of them the device waited for.
        """
        if self._step_count is None:
            # a first step, or one after a compiled scan: the one call of
            # a step that can block on a device read. It is pre_step's
            # too, in a span of its own that cannot carry the number yet
            with tracing.host_span('pre_step', None):
                self._sync_step_count(state)
        step = self._step_count
        with tracing.host_span('pre_step', step):
            state = self._drive_async(state, step)
            capture = self.kfac is not None and self._capture_now()
        with jax.profiler.StepTraceAnnotation('train', step_num=step):
            with tracing.host_span('launch', step):
                if capture:
                    out = self._jit_with_stats(state, batch)
                else:
                    out = self._jit_no_stats(state, batch)
        self._step_count += 1
        with tracing.host_span('post_step', step):
            self._maybe_warn(out[0])
            self._drive_checkpoints(out[0])
        return out

    # ------------------------------------------------------- compiled loops

    def _executed_layers(self, state: TrainState, batch) -> set[str]:
        """Registered layers that this loss_fn actually executes.

        Discovered once by abstractly tracing the capture (eval_shape, no
        FLOPs). The zero-stats template must cover exactly this subset:
        covering ALL registry layers would (a) make the two cadence-cond
        branches structurally different and (b) feed zero statistics into
        the factor EMA for unexecuted layers, decaying their factors toward
        zero instead of leaving them untouched (the engines treat
        stats-absent layers as "keep current value").
        """
        if not hasattr(self, '_executed'):
            out = jax.eval_shape(
                self._run_stats, state.params, (state.model_state, batch)
            )
            self._executed = set(out[2].g.keys())
        return self._executed

    def _zero_stats(self, executed: set[str]):
        """Stats-shaped zeros for the no-capture branch of a device-side
        cadence cond (ignored downstream: kfac.step's own cond skips the
        factor EMA on exactly the same steps)."""
        reg = self.registry
        return capture_lib.CapturedStats(
            # a follower of an A group has no A statistic of its own
            a={
                n: jax.numpy.zeros(h.a_factor_shape, h.factor_dtype)
                for n, h in reg.layers.items()
                if n in executed and reg.a_leader(n) == n
            },
            g={
                n: jax.numpy.zeros(h.g_factor_shape, h.factor_dtype)
                for n, h in reg.layers.items()
                if n in executed
            },
            # weighted (routed) layers carry a capture weight; the cond
            # branches must produce identical pytree structures (values
            # unused: kfac.step skips the factor EMA on exactly the
            # no-capture steps). `weighted` is the helper contract's own
            # predicate for "capture emits a w entry".
            w={
                n: jax.numpy.zeros((), jax.numpy.float32)
                for n, h in reg.layers.items()
                if n in executed and getattr(h, 'weighted', False)
            },
            traffic={
                n: jax.numpy.zeros((len(t.slots) + 1,), jax.numpy.float32)
                for n, t in reg.stacks.items()
                if t.slots[0] in executed
            },
        )

    def _scan_body(self, state: TrainState, batch, executed: set[str]):
        """One train step with DEVICE-side cadence dispatch (lax.cond picks
        the capture branch, XLA executes only the taken one), so the whole
        loop compiles into a single lax.scan — no per-step host round trip.
        """
        if self.kfac is None:
            return self._step_no_stats(state, batch)
        kstate = state.kfac_state
        cadence = self.factor_update_steps
        if callable(cadence):
            cadence = jax.numpy.maximum(1, cadence(kstate.step))
        capture_now = kstate.step % cadence == 0

        def with_cap(_):
            (loss, new_ms), grads, stats = self._run_stats(
                state.params, (state.model_state, batch)
            )
            return loss, new_ms, grads, stats

        def no_cap(_):
            (loss, new_ms), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True
            )(state.params, state.model_state, batch)
            return loss, new_ms, grads, self._zero_stats(executed)

        loss, new_ms, grads, stats = jax.lax.cond(
            capture_now, with_cap, no_cap, None
        )
        new_state = self._finish_step(state, grads, stats, new_ms, loss=loss)
        return new_state, loss

    @tracing.trace(name='trainer/scan_steps')
    def scan_steps(
        self, state: TrainState, batches
    ) -> tuple[TrainState, jax.Array]:
        """Run ``len(batches)`` steps as ONE compiled ``lax.scan``.

        ``batches`` is a pytree with a leading steps axis. The eager
        :meth:`step` dispatches the capture variant host-side (two jitted
        programs); here the cadence cond lives on device so the loop can sit
        inside profiled/compiled outer loops — the XLA equivalent of the
        reference's hook-driven epoch loop with no Python in the hot path.
        Returns (final_state, per-step losses).
        """
        state = self._drive_async(state, None)
        if not hasattr(self, '_jit_scan'):
            donate = (0,) if self.donate_state else ()
            executed = (
                self._executed_layers(
                    state, jax.tree_util.tree_map(lambda b: b[0], batches)
                )
                if self.kfac is not None
                else set()
            )

            def run(state, batches):
                return jax.lax.scan(
                    lambda s, b: self._scan_body(s, b, executed),
                    state,
                    batches,
                )

            self._jit_scan = self._watched(
                'trainer.scan_steps', jax.jit(run, donate_argnums=donate)
            )
        with tracing.host_span('launch', self._step_count):
            state, losses = self._jit_scan(state, batches)
        self._step_count = None  # host mirror resyncs from the device step
        self._drive_checkpoints(state)
        return state, losses

    # --------------------------------------------------------- accumulation

    def _grads_and_stats(self, params, model_state, batch):
        (loss, new_ms), grads, stats = self._run_stats(
            params, (model_state, batch)
        )
        return loss, new_ms, grads, stats

    def _ensure_accum_jits(self) -> None:
        if not hasattr(self, '_jit_grads_stats'):
            self._jit_grads_stats = self._watched(
                'trainer.accumulate/grads_stats',
                jax.jit(self._grads_and_stats),
            )
            self._jit_grads_only = self._watched(
                'trainer.accumulate/grads_only',
                jax.jit(jax.value_and_grad(self.loss_fn, has_aux=True)),
            )
            self._jit_apply_kfac = self._watched(
                'trainer.accumulate/apply',
                jax.jit(
                    self._apply_accumulated, static_argnames=('with_stats',)
                ),
                static_argnames=('with_stats',),
            )

    # ------------------------------------------- incremental accumulation

    def accumulate_microbatch(
        self, state: TrainState, microbatch
    ) -> jax.Array:
        """Accumulate one micro-batch's gradients/statistics without
        stepping; finish with :meth:`apply_accumulated` or discard with
        :meth:`reset_batch`.

        This is the incremental counterpart of :meth:`step_accumulate` for
        loops that must be able to abandon a batch mid-accumulation — the
        reference's AMP flow, where a grad-scaler overflow calls
        ``reset_batch`` to drop the poisoned mini-step accumulation
        (kfac/base_preconditioner.py:126-130, 384-387). Returns this
        micro-batch's loss.
        """
        from kfac_tpu.layers import capture as capture_lib

        if self.kfac is None:
            raise ValueError('accumulation requires a kfac preconditioner')
        self._sync_step_count(state)
        self._ensure_accum_jits()
        acc = getattr(self, '_accum', None)
        if acc is None:
            acc = self._accum = {
                'grads': None, 'stats': None, 'loss': 0.0, 'count': 0,
                'model_state': state.model_state,
                'capture': self._capture_now(),
            }
        if acc['capture']:
            loss, model_state, grads, stats = self._jit_grads_stats(
                state.params, acc['model_state'], microbatch
            )
            acc['stats'] = capture_lib.accumulate_stats(acc['stats'], stats)
        else:
            (loss, model_state), grads = self._jit_grads_only(
                state.params, acc['model_state'], microbatch
            )
        acc['model_state'] = model_state
        acc['loss'] = acc['loss'] + loss
        acc['grads'] = (
            grads
            if acc['grads'] is None
            else jax.tree_util.tree_map(jnp_add, acc['grads'], grads)
        )
        acc['count'] += 1
        return loss

    def reset_batch(self) -> None:
        """Discard the pending micro-batch accumulation.

        The reference's ``BaseKFACPreconditioner.reset_batch``
        (kfac/base_preconditioner.py:384-387): called when a gradient-scaler
        overflow poisons the accumulated statistics/gradients mid-batch.
        The next :meth:`accumulate_microbatch` starts a fresh accumulation;
        the K-FAC step counter and factors are untouched.
        """
        self._accum = None

    def apply_accumulated(
        self, state: TrainState
    ) -> tuple[TrainState, jax.Array]:
        """Finish an incremental accumulation: average, precondition, step.

        Equivalent to :meth:`step_accumulate` over the micro-batches fed to
        :meth:`accumulate_microbatch` since the last reset/apply.
        """
        acc = getattr(self, '_accum', None)
        if acc is None or acc['count'] == 0:
            raise ValueError(
                'no pending accumulation: call accumulate_microbatch first'
            )
        from kfac_tpu.layers import capture as capture_lib

        n = acc['count']
        grads_avg = jax.tree_util.tree_map(lambda g: g / n, acc['grads'])
        stats_avg = (
            capture_lib.average_stats(acc['stats'], n)
            if acc['capture']
            else None
        )
        loss = acc['loss'] / n
        state = self._drive_async(state, self._step_count)
        with tracing.host_span('launch', self._step_count):
            new_state = self._jit_apply_kfac(
                state,
                grads_avg,
                stats_avg,
                acc['model_state'],
                loss,
                with_stats=acc['capture'],
            )
        self._accum = None
        self._step_count += 1
        self._maybe_warn(new_state)
        self._drive_checkpoints(new_state)
        return new_state, loss

    @tracing.trace(name='trainer/step_accumulate')
    def step_accumulate(
        self, state: TrainState, microbatches
    ) -> tuple[TrainState, jax.Array]:
        """One optimization step over several gradient-accumulation
        micro-batches.

        Gradients and curvature statistics are averaged across micro-batches
        before the preconditioner step — the reference's mini-step counting
        (kfac/base_preconditioner.py:126-130,444-455; examples use
        ``model.no_sync()`` accumulation, examples/vision/engine.py:63-75).
        Off the factor-update cadence, micro-batches run the no-capture
        forward (no covariance FLOPs), same as :meth:`step`.
        """
        if self.kfac is None:
            raise ValueError('step_accumulate requires a kfac preconditioner')
        if getattr(self, '_accum', None) is not None:
            raise ValueError(
                'an incremental accumulation is pending: finish it with '
                'apply_accumulated or drop it with reset_batch before '
                'step_accumulate'
            )
        for mb in microbatches:
            self.accumulate_microbatch(state, mb)
        return self.apply_accumulated(state)

    @tracing.trace(name='trainer/step_accumulate_scan')
    def step_accumulate_scan(
        self, state: TrainState, microbatches
    ) -> tuple[TrainState, jax.Array]:
        """:meth:`step_accumulate` with the micro-batch loop compiled.

        ``microbatches`` is a pytree with a leading micro-batch axis; the
        accumulation runs as a ``lax.scan`` inside ONE jitted program
        (the eager variant dispatches one jit call per micro-batch — pure
        Python-loop overhead on small models).
        """
        if self.kfac is None:
            raise ValueError(
                'step_accumulate_scan requires a kfac preconditioner'
            )
        self._sync_step_count(state)
        state = self._drive_async(state, self._step_count)
        capture_now = self._capture_now()
        if not hasattr(self, '_jit_accum_scan'):
            executed = self._executed_layers(
                state, jax.tree_util.tree_map(lambda b: b[0], microbatches)
            )

            def accum(state, mbs, with_stats):
                n = jax.tree_util.tree_leaves(mbs)[0].shape[0]

                def body(carry, mb):
                    model_state, loss_acc, grads_acc, stats_acc = carry
                    if with_stats:
                        (loss, new_ms), grads, stats = self._run_stats(
                            state.params, (model_state, mb)
                        )
                        stats_acc = capture_lib.accumulate_stats(
                            stats_acc, stats
                        )
                    else:
                        (loss, new_ms), grads = jax.value_and_grad(
                            self.loss_fn, has_aux=True
                        )(state.params, model_state, mb)
                    grads_acc = jax.tree_util.tree_map(
                        jnp_add, grads_acc, grads
                    )
                    return (new_ms, loss_acc + loss, grads_acc, stats_acc), None

                zero_grads = jax.tree_util.tree_map(
                    jax.numpy.zeros_like, state.params
                )
                carry0 = (
                    state.model_state,
                    jax.numpy.zeros((), jax.numpy.float32),
                    zero_grads,
                    self._zero_stats(executed),
                )
                (model_state, loss_sum, grads_sum, stats_sum), _ = (
                    jax.lax.scan(body, carry0, mbs)
                )
                grads_avg = jax.tree_util.tree_map(
                    lambda g: g / n, grads_sum
                )
                stats_avg = (
                    capture_lib.average_stats(stats_sum, n)
                    if with_stats
                    else None
                )
                loss_avg = loss_sum / n
                new_state = self._finish_step(
                    state, grads_avg, stats_avg, model_state, loss=loss_avg
                )
                return new_state, loss_avg

            self._jit_accum_scan = self._watched(
                'trainer.step_accumulate_scan',
                jax.jit(accum, static_argnames=('with_stats',)),
                static_argnames=('with_stats',),
            )
        with tracing.host_span('launch', self._step_count):
            out = self._jit_accum_scan(
                state, microbatches, with_stats=capture_now
            )
        self._step_count += 1
        self._maybe_warn(out[0])
        self._drive_checkpoints(out[0])
        return out

    def _apply_accumulated(
        self, state: TrainState, grads, stats, new_model_state, loss,
        with_stats,
    ):
        # a single poisoned micro-batch propagates NaN into the summed
        # grads, so the skip-step gate inside _finish_step drops the whole
        # accumulated batch (and its model_state) in one decision; the
        # averaged loss rides along for the skip gate's finiteness check
        # and the flight-recorder ring
        return self._finish_step(
            state, grads, stats if with_stats else None, new_model_state,
            loss=loss,
        )


def jnp_add(a, b):
    return a + b
