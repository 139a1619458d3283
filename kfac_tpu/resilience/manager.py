"""Checkpoint autopilot: keep-N rotation, atomic LATEST pointer,
periodic async saves, emergency flush on preemption, last-good fallback
restore.

The primitives live in :mod:`kfac_tpu.checkpoint` (orbax async save,
layout manifests, cross-layout factor migration); this module composes
them into a loop that survives the pod-scale failure modes: SIGTERM in
the middle of an async save, a torn write in the newest checkpoint, a
restore onto a different topology. Invariants:

- Every save goes to a FRESH step-numbered directory
  (``<root>/step_00000042/ckpt``), so no write ever touches the bytes of
  an existing checkpoint.
- The ``LATEST`` pointer is a one-line file updated by atomic
  ``os.replace`` and committed only after ``wait_until_finished()`` — a
  crash at any instant leaves the previous pointer valid and pointing at
  a durable checkpoint.
- Rotation pruning keeps the newest ``keep`` committed checkpoints and
  never deletes the ``LATEST`` target.
- :meth:`CheckpointManager.restore_latest` walks newest → oldest,
  validating each candidate (orbax commit metadata, manifest sidecar,
  and ``checkpoint.restore``'s factor finiteness/shape checks) and falls
  back to the last good one with a rate-limited warning.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import time
from typing import Any, Callable, NamedTuple

import jax

from kfac_tpu import checkpoint as checkpoint_lib
from kfac_tpu.resilience import signals as signals_lib
from kfac_tpu.warnings import CheckpointResilienceWarning

import warnings as _warnings

_STEP_PREFIX = 'step_'
_LATEST = 'LATEST'
_CKPT_NAME = 'ckpt'

#: emergency codes carried through the multihost barrier (max wins):
#: 0 = no request, 1 = save-and-continue, 2 = save-and-exit
_CODE_NONE, _CODE_CONTINUE, _CODE_EXIT = 0, 1, 2

#: Introspectable save-protocol table for the kfaclint pod tier
#: (KFL305). The pod rules parse this literal straight from the AST
#: (never importing this module), model-check it under the fault
#: alphabet (crash after any step, signal re-entry), and cross-check
#: every ``barrier``/``wait`` step against the protocol ops actually
#: reachable from :meth:`CheckpointManager.save` — so the table cannot
#: rot away from the code, and deleting the real barrier breaks the
#: lint even with the table intact. Step order is the LOGICAL commit
#: order; the async path defers wait+commit to the next
#: ``on_step``/``finalize`` but never reorders them. Keep it a pure
#: literal.
SAVE_PROTOCOL = {
    'machine': 'sequence',
    'name': 'checkpoint-save',
    'function': 'CheckpointManager.save',
    'steps': (
        {'op': 'flush_pending', 'rank': 'all', 'kind': 'host'},
        {'op': 'clear_stale_dir', 'rank': 0, 'kind': 'mutate',
         'effect': 'mutate_dir'},
        {'op': 'barrier', 'rank': 'all', 'kind': 'barrier'},
        {'op': 'write_checkpoint', 'rank': 'all', 'kind': 'mutate',
         'effect': 'write_step_dir'},
        {'op': 'wait_until_finished', 'rank': 'all', 'kind': 'wait'},
        {'op': 'commit_latest', 'rank': 0, 'kind': 'mutate',
         'effect': 'point_latest'},
    ),
}


class Preempted(RuntimeError):
    """Raised by :meth:`CheckpointManager.on_step` after a successful
    emergency save for an exit-semantics signal (SIGTERM): the state is
    durable, unwind the training loop now — the platform's hard kill is
    coming. ``step`` (and the rotation entry) is the pod-agreed step
    from the coordination barrier, identical on every host."""

    def __init__(self, signal_name: str, step: int, path: str) -> None:
        super().__init__(
            f'preempted by {signal_name} at step {step}; emergency '
            f'checkpoint is durable at {path!r} — resume with '
            'CheckpointManager.restore_latest()'
        )
        self.signal_name = signal_name
        self.step = step
        self.path = path


class RestoreResult(NamedTuple):
    """What :meth:`CheckpointManager.restore_latest` hands back."""

    state: Any
    extra: dict[str, Any]
    step: int
    path: str


class _PendingSave(NamedTuple):
    handle: Any
    step: int


def _host_step(state: Any) -> int:
    """Host int of an engine state's step counter (dict states included)."""
    step = state['step'] if isinstance(state, dict) else state.step
    return int(jax.device_get(step))


def _split_train_state(state: Any) -> tuple[Any, dict[str, Any] | None]:
    """(engine_state, extra-trees) from either a Trainer ``TrainState``
    or a bare engine state (duck-typed on ``kfac_state``)."""
    if hasattr(state, 'kfac_state'):
        extra: dict[str, Any] = {
            'params': state.params, 'opt_state': state.opt_state,
        }
        if state.model_state is not None:
            extra['model_state'] = state.model_state
        return state.kfac_state, extra
    return state, None


class CheckpointManager:
    """Owns a rotation of step-numbered checkpoint directories.

    Args:
        directory: rotation root (created if missing). Must be a local or
            shared filesystem path — each step's checkpoint lands in
            ``<directory>/step_<NNNNNNNN>/ckpt``.
        engine: the preconditioner engine (dense ``KFACPreconditioner``
            or ``parallel.DistributedKFAC``); passed through to
            ``checkpoint.save(engine=...)`` so every rotation entry
            carries a layout manifest and restores elastically.
        save_interval_steps: periodic-save cadence for :meth:`on_step`
            (``None`` disables periodic saves; signals still work).
        keep: committed checkpoints retained by the rotation.
        async_save: periodic saves return immediately and commit their
            ``LATEST`` pointer at the next :meth:`on_step` /
            :meth:`finalize` (emergency saves always block).
        install_signals: install the flag-setting handlers from
            :mod:`kfac_tpu.resilience.signals` for these signal names at
            construction (``()`` to manage handlers yourself).
        coordinate_every: multi-host only — every this-many steps,
            :meth:`on_step` runs the ``multihost.allgather_scalars``
            barrier that propagates one host's preemption signal to the
            whole pod. This is the pod's reaction latency: a signal seen
            between coordinated steps stays pending until the next one
            (every host enters the barrier on exactly the same steps, so
            the collective always pairs up). 1 (default) reacts within a
            step; raise it if the per-step DCN gather matters. Must be
            identical on all hosts.
        max_retries / backoff_base / backoff_max: transient-I/O retry
            policy — each failed save attempt retries after
            ``min(backoff_max, backoff_base * 2**attempt)`` seconds.
    """

    def __init__(
        self,
        directory: str | os.PathLike[str],
        engine: Any = None,
        *,
        save_interval_steps: int | None = 100,
        keep: int = 3,
        async_save: bool = True,
        install_signals: tuple[str, ...] = ('SIGTERM', 'SIGUSR1'),
        coordinate_every: int = 1,
        max_retries: int = 3,
        backoff_base: float = 0.5,
        backoff_max: float = 8.0,
        sleep: Callable[[float], None] = time.sleep,
    ) -> None:
        if keep < 1:
            raise ValueError(f'keep must be >= 1, got {keep}')
        if save_interval_steps is not None and save_interval_steps < 1:
            raise ValueError(
                'save_interval_steps must be >= 1 or None, got '
                f'{save_interval_steps}'
            )
        if coordinate_every < 1:
            raise ValueError(
                f'coordinate_every must be >= 1, got {coordinate_every}'
            )
        self.directory = os.fspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.engine = engine
        self.save_interval_steps = save_interval_steps
        self.keep = int(keep)
        self.async_save = bool(async_save)
        self.coordinate_every = int(coordinate_every)
        self.max_retries = int(max_retries)
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self._sleep = sleep
        self._pending: _PendingSave | None = None
        self._last_saved_step: int | None = None
        self._warned_paths: set[str] = set()
        self._signal_handle = (
            signals_lib.install(install_signals) if install_signals else None
        )

    # ------------------------------------------------------------ rotation

    def step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f'{_STEP_PREFIX}{step:08d}')

    def checkpoint_path(self, step: int) -> str:
        return os.path.join(self.step_dir(step), _CKPT_NAME)

    def _latest_path(self) -> str:
        return os.path.join(self.directory, _LATEST)

    def rotation_steps(self) -> list[int]:
        """Step numbers present in the rotation, newest first (presence =
        the step dir exists; commit state is checked per candidate)."""
        steps = []
        try:
            entries = os.listdir(self.directory)
        except FileNotFoundError:
            return []
        for name in entries:
            if name.startswith(_STEP_PREFIX):
                try:
                    steps.append(int(name[len(_STEP_PREFIX):]))
                except ValueError:
                    continue
        return sorted(steps, reverse=True)

    def latest_step(self) -> int | None:
        """The committed ``LATEST`` pointer's step, or None.

        A torn pointer — truncated or overwritten with garbage bytes by
        a crashing writer or filesystem rollback — is treated as absent
        (the bytes are read raw and decoded leniently: a torn pointer
        must degrade to the rotation-scan fallback, never crash the
        restore)."""
        try:
            with open(self._latest_path(), 'rb') as f:
                name = f.read().decode('utf-8', errors='replace').strip()
        except OSError:
            return None
        if not name.startswith(_STEP_PREFIX):
            return None
        try:
            return int(name[len(_STEP_PREFIX):])
        except ValueError:
            return None

    def _is_committed(self, step: int) -> bool:
        """Orbax commit markers present for the rotation entry."""
        ckpt = self.checkpoint_path(step)
        return os.path.isdir(ckpt) and all(
            os.path.exists(os.path.join(ckpt, marker))
            for marker in ('_CHECKPOINT_METADATA', '_METADATA')
        )

    def _commit(self, step: int) -> None:
        """Atomically point ``LATEST`` at ``step``; prune the rotation.

        Rank 0 only (the rotation lives on a shared filesystem; on
        single-host runs rank 0 is the only rank). Called strictly after
        ``wait_until_finished()``, so the pointer can never name an
        uncommitted checkpoint.
        """
        self._last_saved_step = step
        if jax.process_index() != 0:
            return
        latest = self._latest_path()
        tmp = f'{latest}.tmp.{os.getpid()}'
        with open(tmp, 'w') as f:
            f.write(os.path.basename(self.step_dir(step)) + '\n')
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, latest)
        self._prune(protect=step)

    def _prune(self, protect: int) -> None:
        """Drop rotation entries beyond ``keep``, never the protected
        (LATEST) step, and never an uncommitted dir newer than the
        newest committed step (an async save may still be writing it).
        Uncommitted dirs *older* than the newest committed step can no
        longer be in-flight (saves are sequential and commit before the
        next one starts) — they are torn corpses from crashed attempts,
        pruned so the rotation walk stays bounded."""
        steps = self.rotation_steps()
        committed = [s for s in steps if self._is_committed(s)]
        for step in committed[self.keep:]:
            if step == protect:
                continue
            shutil.rmtree(self.step_dir(step), ignore_errors=True)
        if committed:
            newest, live = committed[0], set(committed)
            for step in steps:
                if step < newest and step not in live and step != protect:
                    shutil.rmtree(self.step_dir(step), ignore_errors=True)

    # --------------------------------------------------------------- saving

    def _with_retries(self, what: str, fn: Callable[[], Any]) -> Any:
        for attempt in range(self.max_retries + 1):
            try:
                return fn()
            except OSError as exc:
                if attempt == self.max_retries:
                    raise
                delay = min(
                    self.backoff_max, self.backoff_base * (2 ** attempt)
                )
                _warnings.warn(
                    f'{what} failed with transient I/O error ({exc}); '
                    f'retry {attempt + 1}/{self.max_retries} in '
                    f'{delay:.1f}s',
                    CheckpointResilienceWarning,
                    stacklevel=3,
                )
                self._sleep(delay)

    def _flush_pending(self) -> None:
        """Finish an in-flight async save and commit its LATEST pointer."""
        if self._pending is None:
            return
        pending, self._pending = self._pending, None
        self._with_retries(
            f'finishing async checkpoint for step {pending.step}',
            pending.handle.wait_until_finished,
        )
        self._commit(pending.step)

    def save(
        self,
        state: Any,
        step: int | None = None,
        block: bool | None = None,
    ) -> str:
        """Save ``state`` (a Trainer ``TrainState`` or a bare engine
        state) into a fresh rotation entry; returns the checkpoint path.

        Blocking saves commit their ``LATEST`` pointer before returning;
        async saves commit at the next :meth:`on_step` /
        :meth:`finalize` — either way the pointer only ever moves after
        ``wait_until_finished()``.
        """
        self._flush_pending()
        kstate, extra = _split_train_state(state)
        if step is None:
            step = _host_step(kstate)
        block = (not self.async_save) if block is None else block
        sdir = self.step_dir(step)
        from kfac_tpu.parallel import multihost

        if multihost.process_index() == 0 and os.path.exists(sdir):
            # a dead earlier attempt at this step (crashed mid-write, or a
            # re-save after restore): the rotation never reuses bytes, so
            # clear it and write fresh. Rank 0 only — on a shared
            # filesystem concurrent rmtrees race each other (entries
            # vanishing underneath a peer's walk raise OSError)
            self._with_retries(
                f'clearing stale rotation entry for step {step}',
                lambda: shutil.rmtree(sdir),
            )
        if multihost.process_count() > 1:
            # unconditional (the per-host exists-check may disagree under
            # filesystem lag): no host starts writing until rank 0's
            # clear above has finished
            multihost.barrier(f'kfac-resilience-save-{step}')
        path = self.checkpoint_path(step)

        def attempt():
            os.makedirs(sdir, exist_ok=True)
            return checkpoint_lib.save(
                path, kstate, extra=extra, engine=self.engine,
                wait=block,
            )

        handle = self._with_retries(
            f'checkpoint save for step {step}', attempt
        )
        if block:
            self._commit(step)
        else:
            self._pending = _PendingSave(handle, step)
        return path

    def save_emergency(
        self, state: Any, reason: str = 'signal', step: int | None = None,
    ) -> str:
        """Blocking save + commit for preemption / health events.

        ``step`` defaults to the state's own counter; multi-host callers
        must pass the same value on every host (``on_step`` passes the
        pod-agreed step from the coordination barrier, so skewed hosts
        still land in one rotation entry).

        Idempotent per step: if this step is already durable in the
        rotation (e.g. the periodic async save just committed it), the
        existing checkpoint is pointed at and no second write happens —
        the SIGTERM grace window is too precious to spend re-writing
        bytes that are already safe.

        Signal storms (schedulers re-deliver SIGTERM until the process
        dies) are dropped for the save's duration: the whole body runs
        under :func:`signals.save_in_flight`, so a re-delivery of the
        triggering signal cannot re-arm the flag and re-enter here —
        only an escalation (SIGTERM during a SIGUSR1 save) still
        latches.
        """
        # only a SIGNAL-driven save suppresses re-deliveries; a health or
        # degrade save must still latch an incoming SIGTERM (the
        # preemption notice outlives this save)
        bracket = (
            signals_lib.save_in_flight(reason)
            if reason in signals_lib.HANDLED_SIGNALS
            else contextlib.nullcontext()
        )
        with bracket:
            self._flush_pending()
            if step is None:
                kstate, _ = _split_train_state(state)
                step = _host_step(kstate)
            _warnings.warn(
                f'emergency checkpoint requested at step {step} ({reason})',
                CheckpointResilienceWarning,
                stacklevel=2,
            )
            if self._is_committed(step):
                if self._last_saved_step != step:
                    self._commit(step)
                return self.checkpoint_path(step)
            return self.save(state, step=step, block=True)

    # -------------------------------------------------------------- driving

    def _poll_emergency(self, step: int) -> tuple[int, int]:
        """Local signal flag -> pod-wide agreed ``(code, step)``.

        Multi-host, barrier participation depends ONLY on data every
        host computes identically (the step cadence): a signal seen on
        an off-cadence step stays pending until the next coordinated
        step, so the allgather always pairs up host-for-host.
        ``coordinate_every`` is therefore the pod's reaction latency to
        a preemption signal, never a correctness knob.
        """
        local = signals_lib.preemption_requested()
        code = _CODE_NONE
        if local is not None:
            code = _CODE_EXIT if signals_lib.exits(local) else _CODE_CONTINUE
        from kfac_tpu.parallel import multihost

        if multihost.process_count() > 1:
            if step % self.coordinate_every != 0:
                # defer — acting on the local flag here would either skip
                # the barrier (per-host saves at divergent steps) or enter
                # it on a step where unsignaled hosts don't gather
                return _CODE_NONE, step
            code, step = multihost.agree_emergency(code, step)
        return code, step

    def on_step(self, state: Any, step: int | None = None) -> str | None:
        """Drive the autopilot from a training loop, once per step.

        Checks the preemption flag (coordinating across hosts), flushes
        an emergency blocking save when one is pending — raising
        :class:`Preempted` for exit-semantics signals (SIGTERM) once the
        state is durable — and otherwise starts the periodic
        (default async) save on cadence. Returns the path saved this
        call, or None. ``kfac_tpu.Trainer`` calls this automatically when
        constructed with ``checkpoints=<manager>``.
        """
        kstate, _ = _split_train_state(state)
        if step is None:
            step = _host_step(kstate)
        code, agreed_step = self._poll_emergency(step)
        if code != _CODE_NONE:
            local = signals_lib.consume()
            if code == _CODE_EXIT and (
                local is None or not signals_lib.exits(local)
            ):
                # the pod outranks the local view: another host saw the
                # exit signal — name the exit cause, not whatever
                # continue-semantics signal this host happened to catch
                name = 'SIGTERM'
            else:
                name = local or 'SIGUSR1'
            path = self.save_emergency(state, reason=name, step=agreed_step)
            if code == _CODE_EXIT:
                raise Preempted(name, agreed_step, path)
            return path
        if (
            self.save_interval_steps is not None
            and step > 0
            and step % self.save_interval_steps == 0
            and step != self._last_saved_step
            and (self._pending is None or self._pending.step != step)
        ):
            return self.save(state, step=step)
        return None

    # ------------------------------------------------------------ restoring

    def restore_latest(
        self,
        engine: Any = None,
        extra_template: dict[str, Any] | None = None,
    ) -> RestoreResult | None:
        """Restore the newest good checkpoint, falling back across the
        rotation.

        Candidates are walked newest → oldest, starting from the
        ``LATEST`` pointer's target. Each is validated before use: orbax
        commit metadata present, layout-manifest sidecar present (its
        absence is tolerated with a warning — same-layout restores still
        work), and the restore itself runs ``checkpoint.restore``'s
        factor finiteness/shape validation. A candidate failing any check
        falls back to the next older one with a rate-limited
        :class:`CheckpointResilienceWarning`. After a successful restore,
        all hosts verify they agreed on the restored step.

        Returns None when the rotation holds no restorable checkpoint.
        ``engine`` defaults to the manager's engine — pass a different
        one for elastic restore onto a new topology/layout.
        """
        engine = self.engine if engine is None else engine
        if engine is None:
            raise ValueError(
                'restore_latest needs an engine: construct the manager '
                'with engine=..., or pass one explicitly'
            )
        seen: set[int] = set()
        candidates: list[int] = []
        latest = self.latest_step()
        if latest is not None:
            candidates.append(latest)
            seen.add(latest)
        for step in self.rotation_steps():
            if step not in seen:
                candidates.append(step)
        for step in candidates:
            path = self.checkpoint_path(step)
            if not self._is_committed(step):
                self._warn_fallback(
                    path, 'missing orbax commit metadata (torn or '
                          'in-flight write)'
                )
                continue
            try:
                state, extra = checkpoint_lib.restore(
                    path, engine, extra_template=extra_template
                )
            except KeyboardInterrupt:
                raise
            except Exception as exc:
                self._warn_fallback(path, f'{type(exc).__name__}: {exc}')
                continue
            restored_step = _host_step(
                state if not hasattr(state, 'kfac_state') else
                state.kfac_state
            )
            from kfac_tpu.parallel import multihost

            multihost.assert_same_step(restored_step)
            self._last_saved_step = restored_step
            return RestoreResult(state, extra, restored_step, path)
        return None

    def _warn_fallback(self, path: str, why: str) -> None:
        if path in self._warned_paths:
            return
        self._warned_paths.add(path)
        _warnings.warn(
            f'checkpoint candidate {path!r} is unusable ({why}); falling '
            'back to the previous rotation entry',
            CheckpointResilienceWarning,
            stacklevel=3,
        )

    # ------------------------------------------------------------- lifecycle

    def finalize(self) -> None:
        """Flush any in-flight async save (commit its pointer)."""
        self._flush_pending()

    def close(self) -> None:
        """Finalize and restore any signal handlers this manager
        installed."""
        self.finalize()
        if self._signal_handle is not None:
            self._signal_handle.uninstall()
            self._signal_handle = None

    def __enter__(self) -> 'CheckpointManager':
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
