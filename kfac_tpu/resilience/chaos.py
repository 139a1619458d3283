"""Pod-scale chaos harness: preemption storms with measured recovery SLOs.

Every resilience ingredient in this repo ships — and is tested —
separately: signal-driven emergency saves (``signals.py`` +
``CheckpointManager.on_step``), rotation fallback across torn
checkpoints (``restore_latest``), elastic restore onto a changed
topology (``restore_latest(engine=)``), and real gloo CPU collectives
across OS processes (``tests/parallel/test_multihost.py``). This module composes
them under sustained adversarial pressure and measures how fast the
stack actually heals.

Architecture — one conductor, many victims:

* :class:`ChaosConductor` (parent process, never inside jax) owns the
  pod lifecycle: it spawns ``testing/chaos_worker.py`` OS processes
  that rendezvous through ``jax.distributed.initialize`` (the same
  KFAC_TPU_* env surface ``run_pod.sh`` exports per node), streams
  their per-rank JSONL event feeds, delivers scripted signal waves
  (SIGTERM / SIGUSR1) mid-run, corrupts the checkpoint rotation
  between runs (``testing/faults.py``), shrinks or grows the pod, and
  respawns. A storm is a sequence of such fault events
  (:func:`scripted_storm` grammar below); a seeded storm
  (:func:`seeded_storm`) draws events from ``random.Random(seed)``.

* The worker side (:func:`run_worker` / :func:`worker_recover`, called
  by ``testing/chaos_worker.py``) runs the REAL stack — Trainer +
  DistributedKFAC over the global gloo mesh + CheckpointManager — and
  emits one JSON line per event
  (the ``resilience_worker.py`` convention). Its pod choreography is
  declared in :data:`CHAOS_RECOVERY_PROTOCOL` /
  :data:`CHAOS_STORM_PROTOCOL` so kfaclint's pod tier (KFL301–KFL305)
  bounded-model-checks it like the save protocol.

* :class:`ChaosReport` reconciles the per-rank streams into
  per-fault-class SLO rows — downtime steps (work re-executed after
  the fault), recovery wall-clock (pod down → first post-restore step
  completed), restore fallback depth (rotation entries walked past),
  and trajectory divergence against an uninterrupted control run — and
  fails loudly (:class:`ChaosError`) when a configured budget is
  blown.

Storm schedule grammar (``ChaosConfig.schedule``) — a tuple of fault
events, each a dict:

* ``{'fault': 'sigterm_wave', 'ranks': (0, 2), 'at_step': 3}`` —
  deliver SIGTERM to the given ranks once any rank reports a step
  ``>= at_step``. One signalled rank downs the WHOLE pod cleanly: the
  flag propagates through ``agree_emergency``'s max-reduction, every
  rank lands the same emergency save and exits 0 (``Preempted``).
  The conductor then respawns the full pod, which resumes.
* ``{'fault': 'torn_checkpoint', 'ranks': (0,), 'at_step': 6}`` —
  SIGTERM wave as above, then tear the rotation while the pod is
  down: the ``LATEST`` pointer is truncated to garbage AND the newest
  step dir's payload is corrupted, so the respawned pod must walk
  back to the next committed rotation entry (fallback depth >= 1).
* ``{'fault': 'shrink', 'procs': 2, 'at_step': 9}`` (or ``'grow'``) —
  SIGTERM wave, then respawn with a different process count: the
  elastic-restore path (changed topology fingerprint).
* ``{'fault': 'skew', 'ratio': 2.0, 'at_step': 6}`` — SIGTERM wave,
  then respawn with ``skew`` in the worker's config. The worker's one
  reader of it, the fleet controller's drain, is gone: the class now
  downs the pod and loosens nothing but the divergence check.
* ``{'fault': 'sigusr1', 'ranks': (1,), 'at_step': 10}`` — in-flight
  continue-signal: the pod snapshots at the agreed boundary and keeps
  training (no respawn).

Every event except ``sigusr1`` ends the current run; the pod's final
run (after the last schedule entry) trains to ``max_steps`` and exits
``done``. SLO rows attribute the recovery cost of transition ``k →
k+1`` to the fault event that caused it.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import signal as signal_lib
import subprocess
import sys
import threading
import time
import warnings
from typing import Any, Callable

import jax

from kfac_tpu.parallel import multihost
from kfac_tpu.resilience.manager import Preempted
from kfac_tpu.warnings import CheckpointResilienceWarning

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_WORKER = os.path.join(REPO_ROOT, 'testing', 'chaos_worker.py')

#: committed SLO artifact (written by ``tools/kfac_chaos.py --out``):
#: the canonical scripted storm's reconciled report
ARTIFACT_PATH = os.path.join(os.path.dirname(__file__), 'chaos_slo.json')


def load_slo_artifact(path: str = ARTIFACT_PATH) -> dict | None:
    """The committed chaos SLO artifact, or None when absent/unreadable.

    Read-only by design: bench rounds and docs tables fold the last
    MEASURED storm rather than re-running one (a storm spawns O(10) OS
    processes — minutes, not bench-probe seconds)."""
    try:
        with open(path) as f:
            artifact = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(artifact, dict) or 'rows' not in artifact:
        return None
    return artifact

#: Fault classes a storm can inject. ``sigusr1`` is the only in-flight
#: (non-pod-down) event; all others end the current run and attribute
#: the respawn's recovery cost to themselves.
FAULT_CLASSES = (
    'sigterm_wave', 'torn_checkpoint', 'corrupt_payload',
    'shrink', 'grow', 'skew', 'sigusr1',
)

#: Pod-down fault classes (everything except the in-flight snapshot).
_DOWN_FAULTS = tuple(f for f in FAULT_CLASSES if f != 'sigusr1')


# ------------------------------------------------------------- protocols
#
# The worker-side choreography, declared for kfaclint's pod tier
# (KFL305 model-checks the tables; its crosscheck asserts the named
# functions still reach ops of the declared kinds — delete the real
# barrier and the lint rots, not just this prose).

CHAOS_RECOVERY_PROTOCOL = {
    'machine': 'sequence',
    'name': 'chaos-recovery',
    'function': 'worker_recover',
    'steps': (
        # every (re)spawned rank rendezvouses before touching the
        # rotation: a fast rank must not race a peer still in jax
        # bring-up into a restore of different vintage
        {'op': 'rendezvous', 'rank': 'all', 'kind': 'barrier'},
        # newest-committed walk over the (possibly torn) rotation;
        # pure reads — mutation is SAVE_PROTOCOL's business
        {'op': 'restore_walk', 'rank': 'all', 'kind': 'host'},
        # unanimous vote that every rank's walk succeeded: a rank that
        # restored garbage must down the whole pod, not train alone
        {'op': 'agree_outcome', 'rank': 'all', 'kind': 'vote'},
        # all ranks verify they restored the SAME step before stepping
        {'op': 'align_step', 'rank': 'all', 'kind': 'collective'},
    ),
}

CHAOS_STORM_PROTOCOL = {
    'machine': 'state',
    'name': 'chaos-storm-worker',
    'function': 'run_worker',
    'vote_op': 'agree_decision',
    'states': ('down', 'recovering', 'running', 'storm', 'quiesced'),
    'initial': 'down',
    'transitions': (
        # conductor respawns the pod; each rank enters recovery
        {'from': 'down', 'event': 'spawn', 'to': 'recovering',
         'mutates': ()},
        # pod-unanimous restore agreement (reads only: the restore
        # mutates nothing durable — SAVE_PROTOCOL owns disk mutation)
        {'from': 'recovering', 'event': 'vote-commit', 'to': 'running',
         'mutates': ()},
        {'from': 'recovering', 'event': 'vote-abort', 'to': 'down',
         'mutates': ()},
        # a signal on ANY rank storms the whole pod via the
        # agree_emergency max-reduction at the next boundary
        {'from': 'running', 'event': 'preempt-signal', 'to': 'storm',
         'mutates': ()},
        {'from': 'storm', 'event': 'checkpoint-boundary', 'to': 'quiesced',
         'mutates': ()},
        # exit-semantics signal (SIGTERM): unwind, conductor respawns
        {'from': 'quiesced', 'event': 'exit', 'to': 'down',
         'mutates': ()},
        # continue-semantics signal (SIGUSR1): snapshot taken, train on
        {'from': 'quiesced', 'event': 'continue', 'to': 'running',
         'mutates': ()},
    ),
}


class ChaosError(AssertionError):
    """A blown SLO budget, a worker that died uncleanly, or a pod that
    wedged past its phase timeout. Inherits AssertionError so pytest
    renders the report verbatim."""


# ---------------------------------------------------------------- config


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Storm shape, fault mix, and SLO budgets (KFL111 pins the knob
    table in docs/ROBUSTNESS.md to these fields).

    Args:
        procs: initial pod size (OS processes; gloo ranks).
        devices_per_proc: virtual CPU devices per process — the global
            mesh spans ``procs * devices_per_proc`` devices.
        max_steps: steps the trajectory trains to (across all runs).
        save_interval: checkpoint cadence in steps; also bounds the
            work a clean preemption can lose.
        keep: rotation depth — must cover the deepest fallback a storm
            can force (torn newest entry -> at least 2).
        schedule: scripted storm, a tuple of fault-event dicts (module
            docstring grammar). Empty with ``seed=None`` selects
            :func:`scripted_storm`'s canonical small storm.
        seed: draw a random storm from :func:`seeded_storm` with this
            seed instead of using ``schedule`` (None: scripted).
        storm_events: pod-down events in a seeded storm.
        fault_mix: fault classes a seeded storm draws from.
        step_sleep_s: per-step worker sleep so signal delivery lands
            mid-run deterministically on a loaded host.
        budget_downtime_steps: max steps of re-executed work per
            pod-down event before the report fails.
        budget_recovery_s: max pod-down -> first-post-restore-step
            wall-clock per event (CPU-container scale, includes
            process spawn + jax bring-up + rendezvous + compile).
        budget_fallback_depth: max rotation entries a restore may walk
            past (non-torn faults must not fall back at all).
        divergence_atol: max |storm loss - control loss| at equal step
            for same-world runs (0.0: bit-identical replay).
        elastic_divergence_rtol: relative loss tolerance after a
            shrink/grow (changed world re-lays-out reductions; exact
            bit equality is not defined across topologies).
        phase_timeout_s: per-run wall-clock limit before the conductor
            kills the pod and raises (a wedged rendezvous must not
            hang the suite).
    """

    procs: int = 4
    devices_per_proc: int = 1
    max_steps: int = 12
    save_interval: int = 2
    keep: int = 3
    schedule: tuple = ()
    seed: int | None = None
    storm_events: int = 3
    fault_mix: tuple = (
        'sigterm_wave', 'torn_checkpoint', 'corrupt_payload', 'shrink',
        'sigusr1',
    )
    step_sleep_s: float = 0.05
    budget_downtime_steps: int = 6
    budget_recovery_s: float = 600.0
    budget_fallback_depth: int = 1
    divergence_atol: float = 0.0
    elastic_divergence_rtol: float = 1e-4
    phase_timeout_s: float = 600.0

    def __post_init__(self) -> None:
        if self.procs < 2:
            raise ValueError(f'procs must be >= 2, got {self.procs}')
        if self.devices_per_proc < 1:
            raise ValueError(
                f'devices_per_proc must be >= 1, got '
                f'{self.devices_per_proc}'
            )
        if self.max_steps < 1:
            raise ValueError(f'max_steps must be >= 1, got {self.max_steps}')
        if self.save_interval < 1:
            raise ValueError(
                f'save_interval must be >= 1, got {self.save_interval}'
            )
        if self.keep < 2:
            raise ValueError(
                f'keep must be >= 2 (torn-checkpoint storms walk back '
                f'one rotation entry), got {self.keep}'
            )
        if self.schedule and self.seed is not None:
            raise ValueError(
                'pass schedule= (scripted) or seed= (random), not both'
            )
        unknown = {
            e.get('fault') for e in self.schedule
        } - set(FAULT_CLASSES)
        if unknown:
            raise ValueError(
                f'unknown fault class(es) {sorted(map(str, unknown))}; '
                f'expected a subset of {FAULT_CLASSES}'
            )
        bad_mix = set(self.fault_mix) - set(FAULT_CLASSES)
        if bad_mix:
            raise ValueError(
                f'unknown fault_mix class(es) {sorted(bad_mix)}; '
                f'expected a subset of {FAULT_CLASSES}'
            )


def resolve_schedule(config: ChaosConfig) -> tuple:
    """The storm the config describes: explicit schedule, seeded draw,
    or the canonical scripted small storm."""
    if config.schedule:
        return tuple(config.schedule)
    if config.seed is not None:
        return seeded_storm(config)
    return scripted_storm(config)


def scripted_storm(config: ChaosConfig) -> tuple:
    """The canonical deterministic small storm: one clean SIGTERM wave,
    one torn checkpoint, one topology shrink, one in-flight SIGUSR1
    snapshot — the three committed SLO fault classes plus the
    continue-signal path, sized to ``max_steps``."""
    s = config.save_interval
    kill1 = max(s + 1, config.max_steps // 4)
    kill2 = min(config.max_steps - 3, max(kill1 + s, config.max_steps // 2))
    # leave >= 2 steps of final-run headroom: a wave at max_steps - 1
    # races the pod's own completion, and a shrink that lands after
    # `done` measures an empty run instead of an elastic resume
    kill3 = min(config.max_steps - 2, kill2 + s)
    return (
        {'fault': 'sigterm_wave', 'ranks': (0, config.procs - 1),
         'at_step': kill1},
        {'fault': 'torn_checkpoint', 'ranks': (0,), 'at_step': kill2},
        {'fault': 'shrink', 'procs': max(2, config.procs // 2),
         'at_step': kill3},
        {'fault': 'sigusr1', 'ranks': (min(1, config.procs - 1),),
         'at_step': kill3},
    )


def seeded_storm(config: ChaosConfig) -> tuple:
    """Draw ``storm_events`` pod-down events (plus possible sigusr1
    snapshots) from ``random.Random(seed)``. Deterministic per seed."""
    rng = random.Random(config.seed)
    events: list[dict] = []
    procs = config.procs
    # kill points spread across the trajectory, always leaving room for
    # the final run to make progress
    lo, hi = config.save_interval + 1, max(
        config.save_interval + 2, config.max_steps - 2
    )
    downs = sorted(
        rng.randint(lo, hi) for _ in range(config.storm_events)
    )
    down_mix = [f for f in config.fault_mix if f != 'sigusr1']
    for at in downs:
        fault = rng.choice(down_mix) if down_mix else 'sigterm_wave'
        n_ranks = rng.randint(1, max(1, procs // 2))
        ranks = tuple(sorted(rng.sample(range(procs), n_ranks)))
        ev: dict[str, Any] = {'fault': fault, 'ranks': ranks, 'at_step': at}
        if fault == 'shrink':
            procs = max(2, procs // 2)
            ev['procs'] = procs
        elif fault == 'grow':
            procs = min(config.procs, procs * 2)
            ev['procs'] = procs
        elif fault == 'skew':
            ev['ratio'] = rng.choice((1.5, 2.0, 3.0))
        events.append(ev)
    if 'sigusr1' in config.fault_mix and rng.random() < 0.75:
        events.append({
            'fault': 'sigusr1',
            'ranks': (rng.randrange(procs),),
            'at_step': max(1, config.max_steps - 2),
        })
    return tuple(events)


# ------------------------------------------------------------ worker side
#
# Called from testing/chaos_worker.py inside each pod process. Keep the
# collective choreography branch-free and identical across ranks: the
# pod lint tier abstractly interprets this code over virtual ranks.


def worker_recover(trainer: Any, params: Any) -> tuple[Any, dict]:
    """Pod-coordinated restore — CHAOS_RECOVERY_PROTOCOL as code.

    Every rank: rendezvous barrier, walk the rotation for the newest
    committed checkpoint (counting fallback warnings), vote unanimously
    that the walk succeeded, then verify all ranks landed on the same
    step. Returns ``(state, meta)`` where meta carries the resumed
    step, fallback depth, and restore wall-clock."""
    multihost.barrier('kfac-chaos-recover')
    t0 = time.monotonic()
    err: Exception | None = None
    state = None
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        try:
            state = trainer.restore_latest(params)
        except KeyboardInterrupt:
            raise
        except Exception as exc:  # noqa: BLE001 - vote on ANY failure
            err = exc
    resilience_warnings = [
        str(w.message) for w in caught
        if issubclass(w.category, CheckpointResilienceWarning)
    ]
    fallback_depth = sum(
        'falling back' in msg for msg in resilience_warnings
    )
    ok = multihost.agree_decision(err is None)
    if not ok:
        raise ChaosError(
            'pod-wide restore agreement failed '
            f'(this rank: {err!r}) — no rank may train alone on a '
            'divergent restore'
        ) from err
    if state is None:
        state = trainer.init(params)
    step = int(jax.device_get(state.kfac_state.step))
    multihost.assert_same_step(step, 'chaos recovery')
    return state, {
        'step': step,
        'fallback_depth': fallback_depth,
        'restore_s': time.monotonic() - t0,
        'warnings': resilience_warnings,
    }


def run_worker(
    trainer: Any,
    manager: Any,
    params: Any,
    make_batch: Callable[[Any], Any],
    max_steps: int,
    emit: Callable[..., None],
    step_sleep_s: float = 0.0,
) -> int:
    """One pod process's life inside the storm — CHAOS_STORM_PROTOCOL
    as code.

    Recover (pod-coordinated), then train to ``max_steps`` emitting one
    JSON line per step. A SIGTERM anywhere in the pod surfaces here as
    :class:`Preempted` after the coordinated emergency save — exit 0,
    the conductor respawns. ``make_batch(trainer)`` is called every
    step so the batch lands on the trainer's engine's mesh."""
    state, meta = worker_recover(trainer, params)
    emit(
        event='start',
        rank=multihost.process_index(),
        world=multihost.process_count(),
        resumed_step=meta['step'],
        fallback_depth=meta['fallback_depth'],
        restore_s=round(meta['restore_s'], 3),
        warnings=meta['warnings'],
    )
    loss = None
    try:
        for _ in range(meta['step'], max_steps):
            state, loss = trainer.step(state, make_batch(trainer))
            emit(
                event='step',
                step=int(jax.device_get(state.kfac_state.step)),
                loss=float(jax.device_get(loss)),
            )
            if step_sleep_s:
                time.sleep(step_sleep_s)
        manager.finalize()
        multihost.barrier('kfac-chaos-done')
        emit(
            event='done',
            final_step=int(jax.device_get(state.kfac_state.step)),
            latest=manager.latest_step(),
            rotation=manager.rotation_steps(),
        )
    except Preempted as exc:
        emit(
            event='preempted',
            signal=exc.signal_name,
            saved_step=exc.step,
            latest=manager.latest_step(),
            rotation=manager.rotation_steps(),
        )
    return 0


# --------------------------------------------------------------- conductor


@dataclasses.dataclass
class RunRecord:
    """One pod run between respawns, as observed by the conductor."""

    procs: int
    skew: float
    #: fault event that ended this run (None: ran to completion)
    down_event: dict | None
    #: (rank, t_monotonic, payload) in arrival order
    events: list = dataclasses.field(default_factory=list)
    t_launch: float = 0.0
    t_exit: float = 0.0
    t_kill: float | None = None
    returncodes: tuple = ()

    def per_rank(self, kind: str) -> dict[int, list[dict]]:
        out: dict[int, list[dict]] = {}
        for rank, _, payload in self.events:
            if payload.get('event') == kind:
                out.setdefault(rank, []).append(payload)
        return out

    def max_step(self) -> int:
        steps = [
            p['step'] for _, _, p in self.events
            if p.get('event') == 'step'
        ]
        return max(steps) if steps else 0

    def progress(self) -> int:
        """Furthest durable-or-observed step: a preemption unwinds from
        INSIDE the boundary step, so the emergency save can be one step
        past the last emitted step event."""
        saved = [
            p['saved_step'] for _, _, p in self.events
            if p.get('event') == 'preempted'
            and p.get('saved_step') is not None
        ]
        return max([self.max_step(), *saved])

    def losses(self) -> dict[int, dict[int, float]]:
        """rank -> {step: loss}."""
        out: dict[int, dict[int, float]] = {}
        for rank, _, p in self.events:
            if p.get('event') == 'step':
                out.setdefault(rank, {})[p['step']] = p['loss']
        return out

    def first_step_time(self) -> float | None:
        for _, t, p in self.events:
            if p.get('event') == 'step':
                return t
        return None


class ChaosConductor:
    """Owns the pod: spawn, signal, corrupt, respawn, measure.

    ``root`` holds the storm rotation (``<root>/storm``), the control
    rotation (``<root>/control``), per-rank stderr files, and the
    worker config JSON. The conductor itself never imports the worker's
    jax world — all coupling is argv + env + JSONL, exactly like a real
    pod scheduler."""

    def __init__(
        self,
        config: ChaosConfig,
        root: str,
        worker: str | None = None,
    ) -> None:
        self.config = config
        self.root = os.fspath(root)
        self.worker = worker or DEFAULT_WORKER
        os.makedirs(self.root, exist_ok=True)

    # ------------------------------------------------------------- pod ops

    def _worker_env(self, n: int, pid: int, port: int) -> dict:
        env = dict(os.environ)
        env['JAX_PLATFORMS'] = 'cpu'  # pods are CPU-only: never a chip
        flags = ' '.join(
            f for f in env.get('XLA_FLAGS', '').split()
            if 'xla_force_host_platform_device_count' not in f
        )
        env['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count='
            f'{self.config.devices_per_proc}'
        ).strip()
        env['KFAC_TPU_COORDINATOR'] = f'127.0.0.1:{port}'
        env['KFAC_TPU_NUM_PROCESSES'] = str(n)
        env['KFAC_TPU_PROCESS_ID'] = str(pid)
        # all pod members share the repo's persistent compile cache:
        # n concurrent cold compiles contending for one core would
        # push the rendezvous past its timeout
        env.setdefault(
            'JAX_COMPILATION_CACHE_DIR',
            os.path.join(REPO_ROOT, '.jax_cache'),
        )
        return env

    def _spawn_pod(
        self, tag: str, ckpt_dir: str, n: int, skew: float, port: int
    ) -> list[subprocess.Popen]:
        cfg_path = os.path.join(self.root, f'worker_{tag}.json')
        with open(cfg_path, 'w') as f:
            json.dump({
                'ckpt_dir': ckpt_dir,
                'max_steps': self.config.max_steps,
                'save_interval': self.config.save_interval,
                'keep': self.config.keep,
                'step_sleep_s': self.config.step_sleep_s,
                'skew': skew,
            }, f)
        procs = []
        for pid in range(n):
            stderr = open(  # noqa: SIM115 - lives past this scope
                os.path.join(self.root, f'stderr_{tag}_r{pid}.log'), 'w'
            )
            procs.append(subprocess.Popen(
                [sys.executable, self.worker, cfg_path],
                env=self._worker_env(n, pid, port),
                cwd=REPO_ROOT,
                stdout=subprocess.PIPE,
                stderr=stderr,
                text=True,
            ))
        return procs

    def _stderr_tails(self, tag: str, n: int) -> str:
        tails = []
        for pid in range(n):
            path = os.path.join(self.root, f'stderr_{tag}_r{pid}.log')
            try:
                with open(path) as f:
                    tail = f.read()[-1500:]
            except OSError:
                tail = '<unreadable>'
            tails.append(f'--- rank {pid} stderr ---\n{tail}')
        return '\n'.join(tails)

    def _run_pod(
        self,
        tag: str,
        ckpt_dir: str,
        n: int,
        skew: float,
        down_event: dict | None,
        snapshots: tuple = (),
    ) -> RunRecord:
        """One pod run: spawn n ranks, stream events, deliver scripted
        signals, collect. Raises ChaosError on unclean exits or a
        wedged pod."""
        import socket

        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            port = s.getsockname()[1]
        rec = RunRecord(procs=n, skew=skew, down_event=down_event)
        rec.t_launch = time.monotonic()
        procs = self._spawn_pod(tag, ckpt_dir, n, skew, port)
        lock = threading.Lock()
        kill_trigger = threading.Event()
        snap_triggers = [threading.Event() for _ in snapshots]
        kill_at = down_event.get('at_step') if down_event else None

        def _reader(rank: int, proc: subprocess.Popen) -> None:
            for line in proc.stdout:
                line = line.strip()
                if not line.startswith('{'):
                    continue
                try:
                    payload = json.loads(line)
                except ValueError:
                    continue
                with lock:
                    rec.events.append((rank, time.monotonic(), payload))
                if payload.get('event') != 'step':
                    continue
                step = payload.get('step', 0)
                if kill_at is not None and step >= kill_at:
                    kill_trigger.set()
                for snap, trig in zip(snapshots, snap_triggers):
                    if step >= snap.get('at_step', 0):
                        trig.set()

        threads = [
            threading.Thread(target=_reader, args=(i, p), daemon=True)
            for i, p in enumerate(procs)
        ]
        for t in threads:
            t.start()

        deadline = time.monotonic() + self.config.phase_timeout_s
        try:
            delivered_snaps = [False] * len(snapshots)
            killed = False
            while True:
                alive = [p for p in procs if p.poll() is None]
                for i, (snap, trig) in enumerate(
                    zip(snapshots, snap_triggers)
                ):
                    if trig.is_set() and not delivered_snaps[i]:
                        delivered_snaps[i] = True
                        self._signal(procs, snap.get('ranks', (0,)),
                                     signal_lib.SIGUSR1)
                if kill_trigger.is_set() and not killed:
                    killed = True
                    rec.t_kill = time.monotonic()
                    self._signal(
                        procs,
                        down_event.get('ranks', (0,)),
                        signal_lib.SIGTERM,
                    )
                if not alive:
                    break
                if time.monotonic() > deadline:
                    for p in procs:
                        p.kill()
                    raise ChaosError(
                        f'chaos pod {tag!r} wedged past '
                        f'{self.config.phase_timeout_s:.0f}s '
                        f'(killed={killed}, events={len(rec.events)}):\n'
                        + self._stderr_tails(tag, n)
                    )
                time.sleep(0.02)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                p.wait()
            for t in threads:
                t.join(timeout=10)
        rec.t_exit = time.monotonic()
        rec.returncodes = tuple(p.returncode for p in procs)
        if any(rc != 0 for rc in rec.returncodes):
            raise ChaosError(
                f'chaos pod {tag!r} exited uncleanly '
                f'(returncodes={rec.returncodes}) — a preempted worker '
                'must save and exit 0:\n' + self._stderr_tails(tag, n)
            )
        return rec

    @staticmethod
    def _signal(procs, ranks, sig) -> None:
        for rank in ranks:
            if 0 <= rank < len(procs) and procs[rank].poll() is None:
                procs[rank].send_signal(sig)

    # ------------------------------------------------------------- faults

    def _apply_disk_fault(self, ckpt_dir: str, fault: str) -> list[str]:
        """Corrupt the rotation while the pod is down. Returns the
        victim paths (for the report)."""
        # lazy import: testing/ is the dev-harness package; the library
        # proper must stay importable without it
        from testing import faults

        victims = []
        if fault == 'torn_checkpoint':
            victims.append(faults.corrupt_checkpoint(ckpt_dir, 'torn_latest'))
            newest = self._newest_step_dir(ckpt_dir)
            if newest is not None:
                victims.append(faults.corrupt_checkpoint(newest, 'truncate'))
        elif fault == 'corrupt_payload':
            newest = self._newest_step_dir(ckpt_dir)
            if newest is None:
                raise ChaosError(
                    'corrupt_payload scheduled but the rotation at '
                    f'{ckpt_dir!r} holds no step dir'
                )
            victims.append(faults.corrupt_checkpoint(newest, 'truncate'))
        return [str(v) for v in victims]

    @staticmethod
    def _newest_step_dir(ckpt_dir: str) -> str | None:
        steps = []
        try:
            entries = os.listdir(ckpt_dir)
        except FileNotFoundError:
            return None
        for name in entries:
            if name.startswith('step_'):
                try:
                    steps.append((int(name[len('step_'):]), name))
                except ValueError:
                    continue
        if not steps:
            return None
        return os.path.join(ckpt_dir, max(steps)[1])

    # --------------------------------------------------------------- storm

    def run(self) -> 'ChaosReport':
        """Drive the full storm plus the uninterrupted control run and
        reconcile. Raises :class:`ChaosError` when a budget is blown."""
        schedule = resolve_schedule(self.config)
        storm_dir = os.path.join(self.root, 'storm')
        control_dir = os.path.join(self.root, 'control')
        os.makedirs(storm_dir, exist_ok=True)
        os.makedirs(control_dir, exist_ok=True)

        # split the schedule into pod runs: each pod-down event ends a
        # run; sigusr1 events ride inside the run they precede
        runs: list[dict] = []
        pending_snaps: list[dict] = []
        for ev in schedule:
            if ev['fault'] == 'sigusr1':
                pending_snaps.append(ev)
            else:
                runs.append({'down': ev, 'snaps': tuple(pending_snaps)})
                pending_snaps = []
        runs.append({'down': None, 'snaps': tuple(pending_snaps)})

        records: list[RunRecord] = []
        faults_applied: list[dict] = []
        procs = self.config.procs
        skew = 0.0
        for k, run in enumerate(runs):
            rec = self._run_pod(
                f'storm{k}', storm_dir, procs, skew,
                run['down'], run['snaps'],
            )
            records.append(rec)
            down = run['down']
            if down is None:
                continue
            applied = {'fault': down['fault'], 'event': dict(down)}
            if down['fault'] in ('torn_checkpoint', 'corrupt_payload'):
                applied['victims'] = self._apply_disk_fault(
                    storm_dir, down['fault']
                )
            if down['fault'] in ('shrink', 'grow'):
                procs = int(down['procs'])
            if down['fault'] == 'skew':
                skew = float(down.get('ratio', 2.0))
            faults_applied.append(applied)

        control = self._run_pod(
            'control', control_dir, self.config.procs, 0.0, None, ()
        )
        report = reconcile(self.config, runs, records, control)
        report.faults_applied = faults_applied
        if report.blown:
            err = ChaosError(
                'chaos SLO budget blown:\n  - '
                + '\n  - '.join(report.blown)
                + '\n' + json.dumps(report.rows, indent=1, sort_keys=True)
            )
            err.report = report
            raise err
        return report


# ---------------------------------------------------------------- report


@dataclasses.dataclass
class ChaosReport:
    """Reconciled storm outcome: per-fault-class SLO rows plus the
    blown-budget list (empty = all SLOs met)."""

    config: dict
    schedule: tuple
    rows: dict
    runs: list
    blown: list
    faults_applied: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.blown

    def to_json(self) -> dict:
        return {
            'config': self.config,
            'schedule': list(self.schedule),
            'rows': self.rows,
            'runs': self.runs,
            'blown': list(self.blown),
            'faults_applied': self.faults_applied,
            'ok': self.ok,
        }


def reconcile(
    config: ChaosConfig,
    runs: list[dict],
    records: list[RunRecord],
    control: RunRecord,
) -> ChaosReport:
    """Fold the per-rank event streams into SLO rows.

    Per pod-down event (the ``k -> k+1`` respawn transition):

    * ``downtime_steps`` — work re-executed: the highest step the dying
      pod reached minus the step the respawned pod resumed from.
    * ``recovery_s`` — wall-clock from the dying pod fully exiting to
      the respawned pod completing its first step (spawn + jax
      bring-up + rendezvous + restore + compile).
    * ``fallback_depth`` — max rotation entries any rank's restore
      walked past.
    * divergence — every storm step's loss is compared to the control
      run at the same step: bit-identical (``divergence_atol``) for
      same-world runs, ``elastic_divergence_rtol`` after shrink/grow.
    """
    blown: list[str] = []
    control_losses = _merged_losses(control, blown, 'control')

    rows: dict[str, dict] = {}
    run_summaries: list[dict] = []
    for k, (run, rec) in enumerate(zip(runs, records)):
        starts = rec.per_rank('start')
        resumed = {r: evs[0]['resumed_step'] for r, evs in starts.items()}
        fallback = {r: evs[0]['fallback_depth'] for r, evs in starts.items()}
        if len(set(resumed.values())) > 1:
            blown.append(
                f'run {k}: ranks resumed from different steps {resumed} '
                '(assert_same_step should have caught this)'
            )
        losses = _merged_losses(rec, blown, f'run {k}')
        same_world = rec.procs == control.procs and rec.skew == 0.0
        div = _divergence(losses, control_losses)
        if div is not None:
            limit_kind = 'atol' if same_world else 'rtol'
            limit = (
                config.divergence_atol if same_world
                else config.elastic_divergence_rtol
            )
            value = div['abs'] if same_world else div['rel']
            if value > limit:
                blown.append(
                    f'run {k}: trajectory diverged from control '
                    f'({limit_kind} {value:.3e} > {limit:.3e} at step '
                    f'{div["step"]})'
                )
        run_summaries.append({
            'run': k,
            'procs': rec.procs,
            'skew': rec.skew,
            'fault': run['down']['fault'] if run['down'] else None,
            'resumed_step': min(resumed.values()) if resumed else None,
            'max_step': rec.max_step(),
            'fallback_depth': max(fallback.values()) if fallback else 0,
            'steps_observed': len(losses),
            'divergence': div,
            'world_changed': not same_world,
            'restore_warnings': sorted({
                w for evs in starts.values()
                for w in evs[0].get('warnings', ())
            }),
        })

        # SLO row for the fault that ended the PREVIOUS run
        if k == 0:
            continue
        prev, prev_rec = runs[k - 1], records[k - 1]
        down = prev['down']
        if down is None:
            continue
        fault = down['fault']
        first_step_t = rec.first_step_time()
        recovery_s = (
            first_step_t - prev_rec.t_exit
            if first_step_t is not None else None
        )
        resumed_step = min(resumed.values()) if resumed else 0
        downtime = prev_rec.progress() - resumed_step
        depth = max(fallback.values()) if fallback else 0
        row = rows.setdefault(fault, {
            'events': 0, 'downtime_steps': 0, 'recovery_s': 0.0,
            'fallback_depth': 0, 'max_divergence': 0.0,
        })
        row['events'] += 1
        row['downtime_steps'] = max(row['downtime_steps'], downtime)
        if recovery_s is not None:
            row['recovery_s'] = round(
                max(row['recovery_s'], recovery_s), 3
            )
        row['fallback_depth'] = max(row['fallback_depth'], depth)
        if div is not None:
            row['max_divergence'] = max(row['max_divergence'], div['abs'])
        if downtime > config.budget_downtime_steps:
            blown.append(
                f'{fault}: downtime {downtime} steps > budget '
                f'{config.budget_downtime_steps}'
            )
        if downtime < 0:
            blown.append(
                f'{fault}: respawned pod resumed AHEAD of the dying '
                f'pod ({resumed_step} > {prev_rec.progress()}) — the '
                'rotation restored a future step'
            )
        if recovery_s is not None and (
            recovery_s > config.budget_recovery_s
        ):
            blown.append(
                f'{fault}: recovery {recovery_s:.1f}s > budget '
                f'{config.budget_recovery_s:.1f}s'
            )
        if depth > config.budget_fallback_depth:
            blown.append(
                f'{fault}: restore fell back {depth} rotation entries '
                f'> budget {config.budget_fallback_depth}'
            )
        if fault == 'torn_checkpoint' and depth < 1:
            blown.append(
                'torn_checkpoint: restore did not fall back at all — '
                'the injected corruption was never exercised'
            )

    # the trajectory must COMPLETE: final run reaches max_steps. A
    # fast pod can finish the trajectory before the last wave lands;
    # the respawned final run then restores AT max_steps and exits
    # done with zero step events — that resumed_step is completion,
    # not a stall.
    final = records[-1]
    final_resumed = [
        p['resumed_step'] for _, _, p in final.events
        if p.get('event') == 'start' and p.get('resumed_step') is not None
    ]
    final_progress = max([final.max_step(), *final_resumed], default=0)
    if final_progress < config.max_steps:
        blown.append(
            f'storm never completed: final run reached step '
            f'{final_progress} < max_steps {config.max_steps}'
        )
    if control.max_step() < config.max_steps:
        blown.append(
            f'control run reached step {control.max_step()} < '
            f'max_steps {config.max_steps}'
        )

    # in-flight snapshots: pod kept training (no respawn), so their SLO
    # row is just the event count + divergence already checked above
    for run, rec in zip(runs, records):
        for snap in run['snaps']:
            row = rows.setdefault('sigusr1', {
                'events': 0, 'downtime_steps': 0, 'recovery_s': 0.0,
                'fallback_depth': 0, 'max_divergence': 0.0,
            })
            row['events'] += 1

    return ChaosReport(
        config=dataclasses.asdict(config),
        schedule=tuple(
            dict(r['down']) for r in runs if r['down'] is not None
        ),
        rows=rows,
        runs=run_summaries,
        blown=blown,
    )


def _merged_losses(
    rec: RunRecord, blown: list[str], tag: str
) -> dict[int, float]:
    """Per-step losses, asserting all ranks agree bit-for-bit (the
    training math is replicated over the pod)."""
    per_rank = rec.losses()
    merged: dict[int, float] = {}
    for rank, losses in per_rank.items():
        for step, loss in losses.items():
            if step in merged and merged[step] != loss:
                blown.append(
                    f'{tag}: rank {rank} loss at step {step} '
                    f'({loss!r}) disagrees with a peer ({merged[step]!r})'
                )
            merged.setdefault(step, loss)
    return merged


def _divergence(
    losses: dict[int, float], control: dict[int, float]
) -> dict | None:
    """Worst |storm - control| over the overlapping steps."""
    common = sorted(set(losses) & set(control))
    if not common:
        return None
    worst = {'step': None, 'abs': 0.0, 'rel': 0.0}
    for step in common:
        a, b = losses[step], control[step]
        d = abs(a - b)
        rel = d / max(abs(b), 1e-30)
        if d >= worst['abs']:
            worst = {'step': step, 'abs': d, 'rel': rel}
    return worst
