"""Flight recorder: rolling in-jit telemetry history + postmortem bundles.

K-FAC failures are temporal: a factor EMA is poisoned steps before the
loss visibly diverges, so the record that matters is the *history* of the
steps leading up to the event — exactly what a single
:class:`~kfac_tpu.observability.metrics.MetricsCollector` drain cannot
show. This module adds:

- :class:`FlightRecorderState` — a fixed-capacity on-device ring buffer
  carried next to ``MetricsState`` in the engine state. Each engine step
  writes one slot via ``.at[step % N].set`` (a dynamic-index update, so
  a single compiled program serves every step): the full packed metric
  scalar vector, the training loss (when the Trainer provides one), and
  the global gradient norm. Zero host syncs between drains, no
  recompilation in steady state.
- :func:`drain_flight` — host-side drain: one ``device_get`` of the ring,
  records returned oldest-first. On multi-host meshes each record gains a
  ``process_index`` tag and cross-host ``skew_min/skew_max/skew_mean``
  columns for a small set of headline scalars (gathered through
  :mod:`kfac_tpu.parallel.multihost`).
- :class:`PostmortemWriter` — a drain-time sink that watches the PR-1
  health sentinel's counters (skip-step, quarantine, degradation) and
  the ring's latest loss/scalars; when an event fires it dumps a
  self-contained bundle directory (history npz + JSONL, per-layer factor
  summaries, health counters, ``describe()``/``comms_report()`` output,
  config, and a mesh/topology + library-version fingerprint) that
  ``tools/kfac_inspect.py`` turns into a divergence timeline offline.

Import discipline: like the rest of :mod:`kfac_tpu.observability`, this
module must not import the engines at top level (they import it); engine
introspection inside :class:`PostmortemWriter` is duck-typed and the
health/comms helpers are imported lazily at write time.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kfac_tpu.observability import metrics as metrics_lib

#: headline scalars that get cross-host skew columns on drain
DEFAULT_SKEW_KEYS = ('loss', 'grad_norm', 'kl_clip_scale')

#: bundle format version stamped into MANIFEST.json
BUNDLE_SCHEMA = 1


@dataclasses.dataclass(frozen=True)
class FlightRecorderConfig:
    """Knobs of the in-jit flight recorder.

    Pass an instance as ``KFACPreconditioner(flight=...)`` (or
    ``flight=True`` for these defaults, or ``flight=<int>`` as a capacity
    shorthand). Enabling the flight recorder auto-enables ``metrics``
    (the ring records the metric scalar schema).

    Args:
        capacity: ring slots — the last ``capacity`` engine steps are
            retained. Memory cost is ``capacity * (n_keys + 4) * 4``
            bytes (see docs/OBSERVABILITY.md for sizing guidance); the
            default holds a ~110-key schema in ~29 KB.
        skew_keys: headline record keys that get cross-host
            ``skew_min/skew_max/skew_mean`` columns at drain time.
    """

    capacity: int = 64
    skew_keys: tuple[str, ...] = DEFAULT_SKEW_KEYS

    def __post_init__(self) -> None:
        if self.capacity < 1:
            raise ValueError(
                f'flight recorder capacity must be >= 1, got {self.capacity}'
            )
        object.__setattr__(self, 'skew_keys', tuple(self.skew_keys))


@jax.tree_util.register_pytree_node_class
class FlightRecorderState:
    """Fixed-capacity on-device telemetry ring riding in the engine state.

    Five device buffers regardless of capacity or key count:

    - ``steps``: ``(N,)`` int32, the engine step recorded in each slot
      (-1 = slot never written; skipped steps leave no record, so gaps in
      the drained step sequence are themselves a signal).
    - ``scalars``: ``(N, n_keys)`` float32 rows in ``keys`` order — the
      packed :func:`~kfac_tpu.observability.metrics.metric_keys` schema.
    - ``loss``: ``(N,)`` float32 training loss; ``loss_valid``: ``(N,)``
      bool — False when the engine stepped without a loss (bare
      ``kfac.step`` calls outside a Trainer), so postmortem non-finite
      triggers can't false-positive on a placeholder.
    - ``grad_norm``: ``(N,)`` float32 global (all-parameter) L2 gradient
      norm.

    ``keys`` is static aux data, so tracing sees only the arrays. Like
    ``metrics``, this state is ephemeral: never checkpointed, rebuilt by
    ``init()`` on restore.
    """

    __slots__ = ('keys', 'steps', 'loss', 'loss_valid', 'grad_norm',
                 'scalars')

    def __init__(
        self,
        keys: tuple[str, ...],
        steps: jax.Array,
        loss: jax.Array,
        loss_valid: jax.Array,
        grad_norm: jax.Array,
        scalars: jax.Array,
    ) -> None:
        object.__setattr__(self, 'keys', tuple(keys))
        object.__setattr__(self, 'steps', steps)
        object.__setattr__(self, 'loss', loss)
        object.__setattr__(self, 'loss_valid', loss_valid)
        object.__setattr__(self, 'grad_norm', grad_norm)
        object.__setattr__(self, 'scalars', scalars)

    def __setattr__(self, name: str, value: Any) -> None:
        raise AttributeError('FlightRecorderState is immutable; use _replace')

    def tree_flatten(self):
        return (
            (self.steps, self.loss, self.loss_valid, self.grad_norm,
             self.scalars),
            (self.keys,),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        (keys,) = aux
        return cls(keys, *children)

    def _replace(self, **kw: Any) -> 'FlightRecorderState':
        fields = {s: kw.pop(s, getattr(self, s)) for s in self.__slots__}
        if kw:
            raise TypeError(
                f'unknown FlightRecorderState fields: {sorted(kw)}'
            )
        return FlightRecorderState(**fields)

    @property
    def capacity(self) -> int:
        return int(self.steps.shape[0])

    def __repr__(self) -> str:
        return (
            f'FlightRecorderState(capacity={self.capacity}, '
            f'n_keys={len(self.keys)})'
        )


def init_flight(
    config: FlightRecorderConfig, keys: Sequence[str]
) -> FlightRecorderState:
    """Empty ring (all slots unwritten) for the given scalar key schema."""
    n = int(config.capacity)
    keys = tuple(keys)
    return FlightRecorderState(
        keys=keys,
        steps=jnp.full((n,), -1, jnp.int32),
        loss=jnp.zeros((n,), jnp.float32),
        loss_valid=jnp.zeros((n,), jnp.bool_),
        grad_norm=jnp.zeros((n,), jnp.float32),
        scalars=jnp.zeros((n, len(keys)), jnp.float32),
    )


def global_grad_norm(grads: Any) -> jax.Array:
    """Global (all-leaf) L2 norm, f32, as one stacked fused reduction.

    Same fusion pattern as ``health.all_finite``: XLA folds the per-leaf
    sum-of-squares into passes the backward already materializes.
    """
    sq = []
    for leaf in jax.tree_util.tree_leaves(grads):
        x = jnp.asarray(leaf)
        if jnp.issubdtype(x.dtype, jnp.inexact):
            x32 = x.astype(jnp.float32)
            sq.append(jnp.sum(x32 * x32))
    if not sq:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(jnp.stack(sq).sum())


def record(
    flight: FlightRecorderState,
    step: jax.Array,
    scalars: jax.Array,
    loss: jax.Array | None = None,
    grad_norm: jax.Array | None = None,
) -> FlightRecorderState:
    """Write one ring slot at ``step % capacity`` (in-jit).

    Dynamic-index ``.at[].set`` writes: the slot index is a traced value,
    so one compiled program serves every step — no recompilation, no host
    sync. ``loss=None`` (a trace-time constant, not a traced branch)
    marks the slot's loss invalid; both variants of a Trainer's dispatch
    pass a loss, so ring records from any Trainer path carry one.
    """
    n = flight.capacity
    i = jax.lax.rem(jnp.asarray(step, jnp.int32), jnp.int32(n))
    has_loss = loss is not None
    return flight._replace(
        steps=flight.steps.at[i].set(jnp.asarray(step, jnp.int32)),
        scalars=flight.scalars.at[i].set(
            jnp.asarray(scalars, jnp.float32)),
        loss=flight.loss.at[i].set(
            jnp.asarray(loss, jnp.float32) if has_loss
            else jnp.zeros((), jnp.float32)),
        loss_valid=flight.loss_valid.at[i].set(
            jnp.asarray(has_loss, jnp.bool_)),
        grad_norm=flight.grad_norm.at[i].set(
            jnp.asarray(grad_norm, jnp.float32) if grad_norm is not None
            else jnp.zeros((), jnp.float32)),
    )


# ------------------------------------------------------------------- drain


def _pull(flight: FlightRecorderState) -> dict[str, np.ndarray]:
    """One ``device_get`` of the whole ring."""
    return jax.device_get({
        'steps': flight.steps,
        'loss': flight.loss,
        'loss_valid': flight.loss_valid,
        'grad_norm': flight.grad_norm,
        'scalars': flight.scalars,
    })


def drain_flight(
    state: Any,
    skew_keys: Sequence[str] | None = DEFAULT_SKEW_KEYS,
) -> list[dict[str, Any]]:
    """Drain the ring into chronological records (oldest first).

    Accepts an engine state (``KFACState`` / ``DistKFACState``), a
    Trainer ``TrainState``, or a bare :class:`FlightRecorderState`;
    returns ``[]`` when the flight recorder is disabled. One
    ``device_get`` total.

    Each record is ``{'step', 'grad_norm', 'process_index', <metric
    keys...>}`` plus ``'loss'`` when the slot was recorded with one.
    With ``skew_keys`` (default: loss, grad_norm, kl_clip_scale), every
    record additionally carries ``skew_min/<k>``, ``skew_max/<k>``,
    ``skew_mean/<k>`` aggregated across hosts via
    ``parallel.multihost`` — on a single-process mesh these equal the
    local value and the gather is a pure-numpy no-op, so rank-0 sinks
    expose stragglers without per-host log scraping.
    """
    flight = state if isinstance(state, FlightRecorderState) else getattr(
        getattr(state, 'kfac_state', state), 'flight', None)
    if flight is None:
        return []
    pulled = _pull(flight)
    steps = pulled['steps']
    valid = np.flatnonzero(steps >= 0)
    order = valid[np.argsort(steps[valid], kind='stable')]
    records: list[dict[str, Any]] = []
    pidx = jax.process_index()
    for i in order:
        rec: dict[str, Any] = {
            'step': int(steps[i]),
            'process_index': pidx,
            'grad_norm': float(pulled['grad_norm'][i]),
        }
        if bool(pulled['loss_valid'][i]):
            rec['loss'] = float(pulled['loss'][i])
        rec.update({
            k: float(v) for k, v in zip(flight.keys, pulled['scalars'][i])
        })
        records.append(rec)
    if records and skew_keys:
        _add_skew_columns(records, tuple(skew_keys))
    return records


def _add_skew_columns(
    records: list[dict[str, Any]], skew_keys: tuple[str, ...]
) -> None:
    """Fold cross-host min/max/mean of headline scalars into each record.

    One gather for the whole drain: the (records x keys) matrix crosses
    DCN once, not once per record. SPMD symmetry makes the matrix shape
    identical on every process (same compiled program, same ring), which
    is what lets the gather be a single fixed-shape collective.
    """
    from kfac_tpu.parallel import multihost

    mat = np.full((len(records), len(skew_keys)), np.nan, np.float32)
    for i, rec in enumerate(records):
        for j, k in enumerate(skew_keys):
            if k in rec:
                mat[i, j] = rec[k]
    gathered = multihost.allgather_scalars(mat)  # (P, R, S)
    for i, rec in enumerate(records):
        for j, k in enumerate(skew_keys):
            if k not in rec:
                continue
            col = gathered[:, i, j]
            rec[f'skew_min/{k}'] = float(np.min(col))
            rec[f'skew_max/{k}'] = float(np.max(col))
            rec[f'skew_mean/{k}'] = float(np.mean(col))


def fingerprint(engine: Any = None) -> dict[str, Any]:
    """Library-version + mesh/topology snapshot for offline triage.

    Everything a postmortem reader needs to know about *where* the run
    executed without access to the machine: jax/jaxlib/numpy versions,
    backend, device kinds, process topology, and (when the engine is
    distributed) the mesh axes.
    """
    info: dict[str, Any] = {
        'jax': jax.__version__,
        'numpy': np.__version__,
        'backend': jax.default_backend(),
        'device_count': jax.device_count(),
        'local_device_count': jax.local_device_count(),
        'device_kinds': sorted({d.device_kind for d in jax.devices()}),
        'process_count': jax.process_count(),
        'process_index': jax.process_index(),
    }
    try:
        import jaxlib

        info['jaxlib'] = jaxlib.__version__
    except (ImportError, AttributeError):  # pragma: no cover
        info['jaxlib'] = None
    mesh = getattr(engine, 'mesh', None)
    if mesh is not None and hasattr(mesh, 'axis_names'):
        info['mesh'] = {
            'axis_names': list(mesh.axis_names),
            'shape': [int(s) for s in np.shape(mesh.devices)],
        }
    return info


def _config_snapshot(cfg: Any) -> dict[str, Any]:
    """JSON-serializable view of a config dataclass.

    The registry (layer helpers, closures) is summarized, sub-config
    dataclasses are expanded, enums/dtypes/callables become strings —
    enough to reproduce the configuration by hand, nothing that drags
    device objects into the bundle.
    """
    if not dataclasses.is_dataclass(cfg):
        return {'repr': repr(cfg)}
    out: dict[str, Any] = {}
    for field in dataclasses.fields(cfg):
        value = getattr(cfg, field.name, None)
        if field.name == 'registry':
            layers = getattr(value, 'layers', {})
            out['registry'] = {
                'n_layers': len(layers),
                'layers': list(layers),
            }
        elif dataclasses.is_dataclass(value) and not isinstance(value, type):
            out[field.name] = dataclasses.asdict(value)
        elif isinstance(value, (bool, int, float, str, type(None))):
            out[field.name] = value
        elif isinstance(value, (tuple, list)) and all(
            isinstance(v, (bool, int, float, str, type(None))) for v in value
        ):
            out[field.name] = list(value)
        else:
            out[field.name] = str(value)
    return out


def _np_gershgorin(mat: np.ndarray) -> tuple[float, float]:
    """Host-side Gershgorin bounds (mirror of metrics.gershgorin_bounds)."""
    f = np.asarray(mat, np.float64)
    absrow = np.sum(np.abs(f), axis=-1)
    diag = np.diagonal(f, axis1=-2, axis2=-1)
    lmax = float(np.max(absrow))
    lmin = float(np.min(diag - (absrow - np.abs(diag))))
    return lmin, lmax


def _json_dump(path: str, obj: Any) -> None:
    with open(path, 'w') as f:
        json.dump(obj, f, indent=2, sort_keys=True, default=str)
        f.write('\n')


# ---------------------------------------------------------------- postmortem


class PostmortemWriter:
    """Drain-time sink: health events and non-finite telemetry trigger a
    self-contained bundle directory.

    Drive it next to your regular sinks::

        pm = observability.PostmortemWriter('postmortems/', engine=kfac)
        collector = observability.MetricsCollector()
        ...
        rec = collector.drain(state)
        jsonl.write(rec)
        bundle = pm.observe(state, rec)   # None, or the new bundle's path

    Triggers (each fires a bundle exactly once per *event*, tracked
    against the last observed counters):

    - ``skip`` — ``health/skipped_steps`` advanced since the last observe
      (the PR-1 skip-step gate dropped at least one batch).
    - ``quarantine`` — cumulative ``quarantine_events`` advanced (a
      factor update was rolled back).
    - ``degrade`` — a layer newly crossed ``degrade_after`` (its
      preconditioner is bypassed).
    - ``nonfinite`` — the ring's latest record carries a non-finite loss
      or scalar (deduplicated per engine step).

    Bundle layout (see docs/OBSERVABILITY.md):

    ``history.npz``/``history.jsonl`` (the drained ring), ``factors.json``
    (per-layer Gershgorin bounds / Frobenius norms / staleness),
    ``health.json``, ``describe.txt``, ``comms.json`` (distributed engine
    only), ``config.json``, ``fingerprint.json``, ``MANIFEST.json``.

    On multi-host meshes only process 0 writes (records already carry the
    cross-host skew columns); pass ``all_processes=True`` to write one
    bundle per host, suffixed with the process index.
    """

    def __init__(
        self,
        root: str | os.PathLike[str],
        engine: Any,
        collector: 'metrics_lib.MetricsCollector | None' = None,
        max_bundles: int = 16,
        all_processes: bool = False,
        checkpoint_manager: Any = None,
        run_id: str | None = None,
    ) -> None:
        self.root = os.fspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.engine = engine
        self.collector = collector or metrics_lib.MetricsCollector()
        self.max_bundles = int(max_bundles)
        self.all_processes = bool(all_processes)
        # optional shared run identifier (ledger.new_run_id()): stamped
        # into MANIFEST.json so bundles join the run ledger's streams
        self.run_id = run_id
        # a resilience.CheckpointManager: a degrade event additionally
        # flushes ONE emergency blocking checkpoint (the state that
        # diverged, preserved for offline replay next to the bundle)
        self.checkpoint_manager = checkpoint_manager
        self.bundles: list[str] = []
        self._seen_skipped = 0
        self._seen_events = 0
        self._seen_degraded: set[str] = set()
        self._last_nonfinite_step: int | None = None

    # ------------------------------------------------------------- helpers

    def _config(self) -> Any:
        return getattr(self.engine, 'config', self.engine)

    def _skew_keys(self) -> tuple[str, ...]:
        fc = getattr(self._config(), 'flight', None)
        if isinstance(fc, FlightRecorderConfig):
            return fc.skew_keys
        return DEFAULT_SKEW_KEYS

    @staticmethod
    def _health_events(record: dict[str, Any]) -> tuple[int, int]:
        skipped = int(record.get('health/skipped_steps', 0))
        events = sum(
            int(v) for k, v in record.items()
            if k.startswith('health/') and k.endswith('/quarantine_events')
        )
        return skipped, events

    def _degraded_layers(self, record: dict[str, Any]) -> set[str]:
        hc = getattr(self._config(), 'health', None)
        if hc is None:
            return set()
        out = set()
        for k, v in record.items():
            if k.startswith('health/') and k.endswith('/bad_inv'):
                name = k[len('health/'):-len('/bad_inv')]
                if int(v) >= hc.degrade_after:
                    out.add(name)
        return out

    @staticmethod
    def _nonfinite(record: dict[str, Any]) -> bool:
        for k, v in record.items():
            if k == 'process_index':
                continue
            if isinstance(v, float) and not np.isfinite(v):
                return True
        return False

    # ------------------------------------------------------------- observe

    def observe(
        self, state: Any, record: dict[str, Any] | None = None
    ) -> str | None:
        """Check for new health/non-finite events; write a bundle if any.

        ``record`` is an optional pre-drained collector record (so
        callers already draining for a JSONL sink don't pay a second
        ``device_get``); when omitted the writer drains itself. Returns
        the new bundle's directory path, or ``None``.
        """
        kstate = getattr(state, 'kfac_state', state)
        if record is None:
            record = self.collector.drain(kstate)
        if 'health/skipped_steps' not in record:
            # caller drained without health fold-in; the triggers need it
            from kfac_tpu import tracing

            record = dict(record)
            record.update(tracing.health_counters(kstate))

        reasons: list[str] = []
        skipped, events = self._health_events(record)
        if skipped > self._seen_skipped:
            reasons.append('skip')
        if events > self._seen_events:
            reasons.append('quarantine')
        degraded = self._degraded_layers(record)
        if degraded - self._seen_degraded:
            reasons.append('degrade')
        self._seen_skipped = max(self._seen_skipped, skipped)
        self._seen_events = max(self._seen_events, events)
        self._seen_degraded |= degraded

        history = drain_flight(kstate, skew_keys=self._skew_keys())
        latest = history[-1] if history else None
        step = int(record.get(
            'step', latest['step'] if latest else -1))
        if (latest is not None and self._nonfinite(latest)) or (
            self._nonfinite(record)
        ):
            if step != self._last_nonfinite_step:
                reasons.append('nonfinite')
                self._last_nonfinite_step = step
        if not reasons:
            return None
        emergency_ckpt = None
        if 'degrade' in reasons and self.checkpoint_manager is not None:
            # every process enters the blocking save (SPMD symmetry for
            # sharded state), exactly once per degrade event because the
            # trigger above already dedupes against _seen_degraded
            emergency_ckpt = self.checkpoint_manager.save_emergency(
                state, reason='degrade'
            )
        if not self.all_processes and jax.process_index() != 0:
            return None
        if len(self.bundles) >= self.max_bundles:
            return None
        return self.write_bundle(
            kstate, '-'.join(reasons), record=record, history=history,
            step=step, emergency_checkpoint=emergency_ckpt,
        )

    # ---------------------------------------------------------- the bundle

    def write_bundle(
        self,
        state: Any,
        reason: str,
        record: dict[str, Any] | None = None,
        history: list[dict[str, Any]] | None = None,
        step: int | None = None,
        emergency_checkpoint: str | None = None,
    ) -> str:
        """Dump one bundle directory unconditionally; returns its path.

        ``observe`` is the gated entry point; call this directly to force
        a snapshot (e.g. at clean shutdown).
        """
        kstate = getattr(state, 'kfac_state', state)
        if record is None:
            record = self.collector.drain(kstate)
        if history is None:
            history = drain_flight(kstate, skew_keys=self._skew_keys())
        if step is None:
            step = int(record.get(
                'step', history[-1]['step'] if history else -1))

        tag = '' if not self.all_processes else f'-p{jax.process_index()}'
        base = f'postmortem-step{max(step, 0):08d}-{reason}{tag}'
        bdir = os.path.join(self.root, base)
        n = 2
        while os.path.exists(bdir):
            bdir = os.path.join(self.root, f'{base}-{n}')
            n += 1
        os.makedirs(bdir)
        files: list[str] = []

        flight = getattr(kstate, 'flight', None)
        if flight is not None:
            pulled = _pull(flight)
            np.savez(
                os.path.join(bdir, 'history.npz'),
                keys=np.asarray(flight.keys),
                **pulled,
            )
            files.append('history.npz')
        if history:
            with open(os.path.join(bdir, 'history.jsonl'), 'w') as f:
                for rec in history:
                    f.write(json.dumps(rec, sort_keys=True) + '\n')
            files.append('history.jsonl')

        _json_dump(os.path.join(bdir, 'factors.json'),
                   self._factor_summaries(kstate, record))
        files.append('factors.json')

        _json_dump(os.path.join(bdir, 'health.json'),
                   self._health_snapshot(kstate, record))
        files.append('health.json')

        describe = getattr(self.engine, 'describe', None)
        if callable(describe):
            with open(os.path.join(bdir, 'describe.txt'), 'w') as f:
                f.write(describe() + '\n')
            files.append('describe.txt')

        comms_report = getattr(self.engine, 'comms_report', None)
        if callable(comms_report):
            _json_dump(os.path.join(bdir, 'comms.json'), comms_report())
            files.append('comms.json')

        _json_dump(os.path.join(bdir, 'config.json'),
                   _config_snapshot(self._config()))
        files.append('config.json')

        _json_dump(os.path.join(bdir, 'fingerprint.json'),
                   fingerprint(self.engine))
        files.append('fingerprint.json')

        # compile-watch truth (docs/OBSERVABILITY.md "Compile & memory
        # truth"): the event tail attributes any recompile churn leading
        # up to the event, and the per-entry XLA memory snapshot records
        # what the programs actually allocate
        watcher = getattr(self.engine, 'compile_watcher', None)
        watch = watcher() if callable(watcher) else None
        if watch is not None and watch.events:
            with open(os.path.join(bdir, 'compile_events.jsonl'), 'w') as f:
                for event in watch.events:
                    f.write(json.dumps(event, sort_keys=True,
                                       default=str) + '\n')
            files.append('compile_events.jsonl')
            _json_dump(os.path.join(bdir, 'compile_memory.json'),
                       watch.memory_report())
            files.append('compile_memory.json')

        _json_dump(os.path.join(bdir, 'MANIFEST.json'), {
            'schema': BUNDLE_SCHEMA,
            'run_id': self.run_id,
            'reason': reason,
            'step': step,
            'process_index': jax.process_index(),
            'record': record,
            'files': sorted(files),
            # rotation path of the emergency checkpoint flushed for this
            # event (degrade events with a CheckpointManager wired in),
            # so offline replay can load the exact diverged state
            'emergency_checkpoint': emergency_checkpoint,
        })
        self.bundles.append(bdir)
        return bdir

    def _factor_summaries(
        self, kstate: Any, record: dict[str, Any]
    ) -> dict[str, Any]:
        """Per-layer factor triage data: bounds, norms, staleness."""
        extract = getattr(self.engine, 'extract_factors', None)
        if not callable(extract):
            return {}
        factors = jax.device_get(extract(kstate))
        out: dict[str, Any] = {}
        for name, fg in factors.items():
            entry: dict[str, Any] = {}
            for side in ('a', 'g'):
                mat = np.asarray(fg[side])
                lmin, lmax = _np_gershgorin(mat)
                entry[side] = {
                    'dim': int(mat.shape[-1]),
                    'gershgorin_lmin': lmin,
                    'gershgorin_lmax': lmax,
                    'fro_norm': float(np.linalg.norm(mat)),
                    'finite': bool(np.isfinite(mat).all()),
                }
            for key in ('factor_staleness', 'inv_staleness'):
                if f'{key}/{name}' in record:
                    entry[key] = record[f'{key}/{name}']
            for key in ('damping_mult', 'quarantine_events', 'bad_inv'):
                if f'health/{name}/{key}' in record:
                    entry[key] = record[f'health/{name}/{key}']
            out[name] = entry
        return out

    def _health_snapshot(
        self, kstate: Any, record: dict[str, Any]
    ) -> dict[str, Any]:
        hc = getattr(self._config(), 'health', None)
        health = getattr(kstate, 'health', None)
        if hc is None or health is None:
            return {
                'enabled': False,
                'counters': {
                    k: v for k, v in record.items()
                    if k.startswith('health/')
                },
            }
        from kfac_tpu import health as health_lib

        snap = health_lib.summary(hc, health)
        snap['enabled'] = True
        return snap
