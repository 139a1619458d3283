"""Compile & memory truth: recompile attribution, XLA memory accounting,
and crash-safe mid-compile heartbeats.

Third leg of the measurement truth layer. PR 13 closed the predicted-vs-
measured gap for *time* (:mod:`kfac_tpu.observability.calibration`); this
module closes it for *compilation* and *memory*:

1. **Recompile attribution.** :meth:`CompileWatch.wrap` turns a jitted
   entry point into a :class:`WatchedFunction` that dispatches through
   ahead-of-time ``lower()``/``compile()``. Its executables are keyed by
   an argument *fingerprint* (shape/dtype/sharding per leaf, value for
   static scalars), but a call is fingerprinted only when its program
   may have changed: it is first handed to the executable the entry ran
   last, whose own C++ call path checks the argument tree and every
   leaf's shape and dtype and raises before anything runs or is donated
   when they differ. A first call, a rejected one, and one whose
   static / ``bool`` / ``str`` values differ from the last call's take a
   fingerprint (:meth:`CompileWatch.dispatch_counters` counts both
   kinds); a steady-state call does no Python work per leaf.
   Every compilation emits exactly one structured event —
   entry name, compile wall-clock, the fingerprint, and a diff against
   the previous fingerprint for that entry naming exactly which
   dimension/dtype/sharding changed. The old ``jit._cache_size() == 1``
   test pins become a first-class runtime counter
   (:meth:`CompileWatch.recompile_count`).

2. **XLA memory accounting.** After each compile the event folds in
   ``compiled.memory_analysis()`` (argument / output / temp / alias /
   generated-code bytes). Where the backend doesn't report memory stats
   this degrades to ``memory: None`` — a documented graceful no-op, never
   an error. Engines surface the latest per-entry snapshot via
   ``compiled_memory_report()`` next to the model-side ``memory_usage()``
   estimate; the residual between the two feeds
   :class:`~kfac_tpu.observability.calibration.CalibrationMonitor`'s
   memory channel and from there the existing fleet drift → retune path.

3. **Mid-compile postmortems.** When ``journal_path`` is set, each
   compilation journals ``phase: lowering → compiling → done`` heartbeat
   records to a crash-safe JSONL: each line is written **and fsynced
   before entering the blocking phase it announces**, so a process
   SIGKILLed mid-compile leaves a record naming the entry, its shapes,
   and how far it got. ``tools/kfac_inspect.py`` turns a truncated
   journal into a "died compiling X" verdict; ``PostmortemWriter``
   bundles carry the journal tail.

Fingerprint conventions (chosen to mirror jax's own cache key):

- array-like leaves -> shape + dtype (+ sharding when
  ``include_sharding`` and the leaf carries one);
- python ``int``/``float`` leaves -> *type only* — they are weak-typed
  under jit, so different values share one executable and including the
  value would fabricate recompile events;
- ``bool``/``str`` leaves and declared ``static_argnames`` values ->
  the value itself, because those *do* select a different program.

AOT dispatch detail: static argnames are passed to ``lower()`` but must
be stripped before calling the compiled executable (its input pytree
excludes them); :class:`WatchedFunction` handles this. If AOT lowering
fails for an exotic entry the wrapper falls back to plain dispatch for
that fingerprint and still counts/journals the compile.

See docs/OBSERVABILITY.md "Compile & memory truth" for the event schema
and the knob table (pinned by lint rule KFL112).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
from typing import Any, Callable, Mapping, Sequence

__all__ = [
    'CompileWatch',
    'CompileWatchConfig',
    'WatchedFunction',
    'PersistentCacheCounters',
    'fingerprint_args',
    'fingerprint_diff',
    'measured_hbm_bytes',
    'persistent_cache_counters',
]


@dataclasses.dataclass(frozen=True)
class CompileWatchConfig:
    """Knobs of the compile watch.

    The field set here is pinned to the knob table in
    docs/OBSERVABILITY.md "Compile-watch knobs" by lint rule KFL112.

    Args:
        journal_path: crash-safe heartbeat JSONL path; ``None`` (the
            default) disables journaling — events are still recorded
            in memory. When ``None`` and the ``KFAC_COMPILE_JOURNAL``
            environment variable is set, that path is used instead, so
            a launcher can arm journaling fleet-wide without touching
            configs.
        include_sharding: record each array leaf's sharding repr in the
            fingerprint, so a resharding-forced recompile names its
            cause in the event diff. Shardings never key the dispatch
            cache (see ``_program_view``): a compatible executable is
            reused even when the repr changed. Disable only if sharding
            reprs are unstable in your environment.
        max_events: in-memory event ring size per watch; the journal is
            never truncated by this.
        fsync: fsync each journal line before entering the phase it
            announces (the crash-safety contract). Disable only for
            throughput experiments where losing the tail is acceptable.
        fault_compile_sleep_s: fault injection — sleep this long between
            the ``compiling`` heartbeat and the actual compile, so tests
            can SIGKILL a process deterministically mid-compile. Keep 0
            in production.
    """

    journal_path: str | None = None
    include_sharding: bool = True
    max_events: int = 256
    fsync: bool = True
    fault_compile_sleep_s: float = 0.0

    def __post_init__(self) -> None:
        if self.journal_path is None:
            env = os.environ.get('KFAC_COMPILE_JOURNAL')
            if env:
                object.__setattr__(self, 'journal_path', env)
        if self.max_events < 1:
            raise ValueError(f'max_events must be >= 1, got {self.max_events}')
        if self.fault_compile_sleep_s < 0.0:
            raise ValueError(
                'fault_compile_sleep_s must be >= 0, '
                f'got {self.fault_compile_sleep_s}')


# ---------------------------------------------------------------------------
# fingerprints


def _leaf_spec(leaf: Any, include_sharding: bool) -> dict[str, Any]:
    if isinstance(leaf, bool):
        return {'static': 'bool', 'value': leaf}
    if isinstance(leaf, (int, float, complex)):
        # weak-typed under jit: the value does not select the program
        return {'py': type(leaf).__name__}
    if isinstance(leaf, (str, bytes)):
        return {'static': type(leaf).__name__, 'value': str(leaf)}
    if leaf is None:
        return {'py': 'none'}
    shape = getattr(leaf, 'shape', None)
    dtype = getattr(leaf, 'dtype', None)
    if shape is not None and dtype is not None:
        spec: dict[str, Any] = {
            'shape': [int(d) for d in shape],
            'dtype': str(dtype),
        }
        if include_sharding:
            sharding = getattr(leaf, 'sharding', None)
            if sharding is not None:
                spec['sharding'] = str(sharding)
        return spec
    return {'py': type(leaf).__name__}


def fingerprint_args(
    args: Sequence[Any],
    kwargs: Mapping[str, Any],
    statics: Mapping[str, Any] | None = None,
    include_sharding: bool = True,
) -> dict[str, dict[str, Any]]:
    """Flat ``{leaf path: spec}`` fingerprint of a call's arguments.

    Paths come from :func:`jax.tree_util.tree_flatten_with_path` over
    ``(args, kwargs)`` (e.g. ``[0][0]['params']``); declared static
    argument values are folded in under ``static:<name>`` keys.
    """
    from jax import tree_util

    leaves, _ = tree_util.tree_flatten_with_path(
        (tuple(args), dict(kwargs)),
        is_leaf=lambda x: x is None,
    )
    fp = {
        tree_util.keystr(path): _leaf_spec(leaf, include_sharding)
        for path, leaf in leaves
    }
    for name, value in sorted((statics or {}).items()):
        fp[f'static:{name}'] = {'static': type(value).__name__,
                                'value': repr(value)}
    return fp


def fingerprint_key(fp: Mapping[str, Any]) -> str:
    """Stable short hash of a fingerprint (the executable-cache key)."""
    blob = json.dumps(fp, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _program_view(fp: Mapping[str, Mapping[str, Any]]) -> dict[str, Any]:
    """The fingerprint minus sharding — the dispatch-cache key view.

    Shardings are *recorded* (fingerprints, diffs) but do not key the
    executable cache: a compiled executable often serves inputs whose
    sharding repr changed but whose physical layout is compatible (e.g.
    an uncommitted init state vs its committed step output). Dispatch
    tries the cached executable first and recompiles only when XLA
    actually rejects the input — so a sharding-driven recompile is
    counted exactly when it really happens, with the diff naming it.
    """
    return {
        path: {k: v for k, v in spec.items() if k != 'sharding'}
        for path, spec in fp.items()
    }


def _spec_diff(path: str, old: Mapping[str, Any],
               new: Mapping[str, Any]) -> list[str]:
    out = []
    old_shape, new_shape = old.get('shape'), new.get('shape')
    if old_shape is not None and new_shape is not None:
        if len(old_shape) != len(new_shape):
            out.append(f'{path}: rank {len(old_shape)} -> {len(new_shape)} '
                       f'({old_shape} -> {new_shape})')
        else:
            for i, (a, b) in enumerate(zip(old_shape, new_shape)):
                if a != b:
                    out.append(f'{path}: dim {i} {a} -> {b}')
    elif old_shape != new_shape:
        out.append(f'{path}: shape {old_shape} -> {new_shape}')
    for field in ('dtype', 'sharding', 'py', 'static', 'value'):
        a, b = old.get(field), new.get(field)
        if a != b:
            out.append(f'{path}: {field} {a!r} -> {b!r}')
    return out


def fingerprint_diff(
    old: Mapping[str, Mapping[str, Any]] | None,
    new: Mapping[str, Mapping[str, Any]],
) -> list[str] | None:
    """Human-readable lines naming exactly what changed between two
    fingerprints: ``None`` for a first compile (nothing to diff
    against), ``[]`` for identical prints."""
    if old is None:
        return None
    out = []
    for path in sorted(set(old) | set(new)):
        if path not in old:
            out.append(f'{path}: new argument {dict(new[path])}')
        elif path not in new:
            out.append(f'{path}: argument dropped (was {dict(old[path])})')
        else:
            out.extend(_spec_diff(path, old[path], new[path]))
    return out


# ---------------------------------------------------------------------------
# XLA memory accounting

_MEMORY_FIELDS = (
    'argument_size_in_bytes',
    'output_size_in_bytes',
    'temp_size_in_bytes',
    'alias_size_in_bytes',
    'generated_code_size_in_bytes',
)


def _memory_analysis(executable: Any) -> dict[str, int] | None:
    """Extract ``CompiledMemoryStats`` fields from a compiled executable;
    None where the backend doesn't report (the documented no-op)."""
    try:
        stats = executable.memory_analysis()
    except Exception:
        return None
    if stats is None:
        return None
    out = {}
    for field in _MEMORY_FIELDS:
        value = getattr(stats, field, None)
        if value is not None:
            try:
                out[field] = int(value)
            except (TypeError, ValueError):
                continue
    return out or None


def measured_hbm_bytes(memory: Mapping[str, int] | None) -> float | None:
    """Live-bytes view of a memory snapshot: argument + output + temp —
    what the compiled program holds resident, the number comparable to
    ``memory_usage()`` / ``HardwareSpec.hbm_bytes``."""
    if not memory:
        return None
    total = sum(
        memory.get(k, 0)
        for k in ('argument_size_in_bytes', 'output_size_in_bytes',
                  'temp_size_in_bytes'))
    return float(total) if total > 0 else None


# ---------------------------------------------------------------------------
# persistent compile-cache counters

_CACHE_EVENTS = {
    '/jax/compilation_cache/cache_hits': 'hits',
    '/jax/compilation_cache/cache_misses': 'misses',
}


class PersistentCacheCounters:
    """Process-wide hit/miss counters for jax's persistent compilation
    cache, fed by ``jax.monitoring`` events. Counts accumulate from
    :meth:`install` onward; consumers diff :meth:`snapshot` around the
    region they care about."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.installed = False

    def install(self) -> 'PersistentCacheCounters':
        if self.installed:
            return self
        try:
            from jax import monitoring

            monitoring.register_event_listener(self._on_event)
            self.installed = True
        except Exception:
            pass
        return self

    def _on_event(self, event: str, *args: Any, **kwargs: Any) -> None:
        name = _CACHE_EVENTS.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)

    def snapshot(self) -> dict[str, Any]:
        return {
            'persistent_cache_hits': self.hits,
            'persistent_cache_misses': self.misses,
            'persistent_cache_dir': self._cache_dir(),
        }

    @staticmethod
    def _cache_dir() -> str | None:
        from kfac_tpu.utils import compile_cache

        return compile_cache.current_dir()


_GLOBAL_COUNTERS: PersistentCacheCounters | None = None
_GLOBAL_COUNTERS_LOCK = threading.Lock()


def persistent_cache_counters() -> PersistentCacheCounters:
    """The process singleton (installed on first use) — listener
    registration is append-only in jax, so one shared instance avoids
    double counting."""
    global _GLOBAL_COUNTERS
    with _GLOBAL_COUNTERS_LOCK:
        if _GLOBAL_COUNTERS is None:
            _GLOBAL_COUNTERS = PersistentCacheCounters().install()
        return _GLOBAL_COUNTERS


# ---------------------------------------------------------------------------
# the watch

_FALLBACK = object()  # sentinel: AOT failed for this fingerprint, dispatch plain


class CompileWatch:
    """Per-engine compile observer: wraps jitted entry points, records
    one structured event per compilation, journals crash-safe phase
    heartbeats, and answers counter/memory queries."""

    def __init__(self, config: CompileWatchConfig | None = None) -> None:
        self.config = config or CompileWatchConfig()
        self.events: list[dict[str, Any]] = []
        # optional shared run identifier (ledger.new_run_id(), threaded
        # in by Trainer): stamped into journal records and events so the
        # compile stream self-identifies to the run ledger. An attribute
        # rather than a config field: it is per-run state, not a knob.
        self.run_id: str | None = None
        self._counts: dict[str, int] = {}
        # per entry, calls by how they were dispatched ('fast' without a
        # fingerprint, 'fingerprinted' with one); kept here, not on the
        # wrapper, so that an entry wrapped again (a second Trainer on one
        # engine) keeps counting
        self._dispatched: dict[str, dict[str, int]] = {}
        self._last_fp: dict[str, dict[str, Any]] = {}
        self._wrapped: dict[str, WatchedFunction] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------- wrapping

    def wrap(
        self,
        entry: str,
        fn: Callable[..., Any],
        static_argnames: Sequence[str] = (),
    ) -> 'WatchedFunction':
        """Wrap a jitted callable as a watched entry point. ``fn`` must
        support ``.lower()`` (i.e. be a ``jax.jit`` product); declared
        ``static_argnames`` must match the jit's own."""
        watched = WatchedFunction(self, entry, fn, tuple(static_argnames))
        self._wrapped[entry] = watched
        return watched

    # ------------------------------------------------------------- counters

    def compile_count(self, entry: str | None = None) -> int:
        """Compilations seen — total, or for one entry."""
        if entry is not None:
            return self._counts.get(entry, 0)
        return sum(self._counts.values())

    def recompile_count(self, entry: str | None = None) -> int:
        """Compilations beyond the first per entry — the number the old
        ``jit._cache_size() == 1`` pins asserted to be zero."""
        if entry is not None:
            return max(0, self._counts.get(entry, 0) - 1)
        return sum(max(0, c - 1) for c in self._counts.values())

    def counters(self) -> dict[str, int]:
        """Per-entry compile counts (a copy)."""
        return dict(self._counts)

    def dispatch_counters(self) -> dict[str, dict[str, int]]:
        """Per entry, the calls made so far: ``fast`` were handed to the
        executable of the entry's previous call with no fingerprint
        taken, ``fingerprinted`` took one (a first call, a rejected fast
        attempt, changed static values, an entry pinned to plain
        dispatch). Their sum is the entry's calls; never reset."""
        with self._lock:
            return {e: dict(c) for e, c in self._dispatched.items()}

    def _count_dispatch(self, entry: str, kind: str) -> None:
        with self._lock:
            counts = self._dispatched.setdefault(
                entry, {'fast': 0, 'fingerprinted': 0})
            counts[kind] += 1

    def events_for(self, entry: str) -> list[dict[str, Any]]:
        return [e for e in self.events if e['entry'] == entry]

    def executables(self) -> dict[str, list[Any]]:
        """The compiled executables each wrapped entry currently
        dispatches to (``jax.stages.Compiled``; ``.as_text()`` is the
        optimized HLO — where ``chip_smoke.py`` looks for the Pallas
        kernels that really are in the step programs)."""
        return {
            entry: watched.executables()
            for entry, watched in self._wrapped.items()
        }

    def memory_report(self) -> dict[str, dict[str, Any]]:
        """Latest XLA memory snapshot per entry: ``{entry: {'memory':
        {...} | None, 'hbm_bytes': float | None, 'compile_s': ...,
        'n': per-entry compile ordinal}}``. Entries whose backend
        reported nothing carry ``memory: None`` (graceful no-op)."""
        report: dict[str, dict[str, Any]] = {}
        for event in self.events:
            report[event['entry']] = {
                'memory': event['memory'],
                'hbm_bytes': measured_hbm_bytes(event['memory']),
                'compile_s': event['compile_s'],
                'n': event['n'],
            }
        return report

    # -------------------------------------------------------------- journal

    def _journal(self, record: dict[str, Any], fsync: bool) -> None:
        path = self.config.journal_path
        if not path:
            return
        record = dict(record)
        record.setdefault('kind', 'compile')
        record.setdefault('pid', os.getpid())
        if self.run_id is not None:
            record.setdefault('run_id', self.run_id)
        line = json.dumps(record, sort_keys=True, default=str)
        with self._lock:
            with open(path, 'a', encoding='utf-8') as f:
                f.write(line + '\n')
                f.flush()
                if fsync and self.config.fsync:
                    os.fsync(f.fileno())

    def _record_event(self, event: dict[str, Any]) -> None:
        with self._lock:
            if self.run_id is not None:
                event.setdefault('run_id', self.run_id)
            entry = event['entry']
            self._counts[entry] = self._counts.get(entry, 0) + 1
            event['n'] = self._counts[entry]
            self._last_fp[entry] = event['fingerprint']
            self.events.append(event)
            while len(self.events) > self.config.max_events:
                self.events.pop(0)


def _same_values(old: Sequence[Any], new: Sequence[Any]) -> bool:
    """Whether two sequences of static values agree, type for type: the
    fingerprint keys on ``True`` and ``1`` apart."""
    return len(old) == len(new) and all(
        type(a) is type(b) and a == b for a, b in zip(old, new))


def _leaves(args: Sequence[Any], kwargs: Mapping[str, Any]) -> list[Any]:
    from jax import tree_util

    return tree_util.tree_leaves((args, kwargs))


@dataclasses.dataclass(frozen=True)
class _LastCall:
    """What an entry's previous call ran, and what of that call the
    executable's own check does not see while the fingerprint keys on
    it: the static values, each leaf's Python type (a Python scalar is
    weak-typed where an array of its dtype is not) and the values of the
    ``bool``/``str``/``bytes`` leaves. While a call agrees in these, the
    executable accepts it exactly when its fingerprint's program view
    would be the last call's."""

    executable: Any
    statics: tuple
    leaf_types: list[type]
    selectors: dict[int, Any]  # leaf position -> bool/str/bytes value

    @classmethod
    def of(cls, executable: Any, statics: tuple, args: Sequence[Any],
           kwargs: Mapping[str, Any]) -> '_LastCall':
        leaves = _leaves(args, kwargs)
        return cls(
            executable, statics, list(map(type, leaves)),
            {i: leaf for i, leaf in enumerate(leaves)
             if isinstance(leaf, (bool, str, bytes))})

    def selects(self, statics: tuple, args: Sequence[Any],
                kwargs: Mapping[str, Any]) -> bool:
        """One flatten and one comparison of two lists of types, both
        in C: no Python runs a leaf unless a selector leaf is there."""
        if not _same_values(self.statics, statics):
            return False
        leaves = _leaves(args, kwargs)
        return list(map(type, leaves)) == self.leaf_types and all(
            leaves[i] == value for i, value in self.selectors.items())


class WatchedFunction:
    """A jitted entry point dispatched through the watch's own
    fingerprint-keyed AOT executable cache (see module docstring)."""

    def __init__(
        self,
        watch: CompileWatch,
        entry: str,
        fn: Callable[..., Any],
        static_argnames: tuple[str, ...],
    ) -> None:
        self._watch = watch
        self.entry = entry
        self._fn = fn
        self._static = static_argnames
        self._cache: dict[str, Any] = {}
        self._last: _LastCall | None = None

    def cache_size(self) -> int:
        """Distinct fingerprints compiled so far for this wrapper."""
        return len(self._cache)

    def executables(self) -> list[Any]:
        """The AOT-compiled executables this wrapper dispatches to."""
        return [e for e in self._cache.values() if e is not _FALLBACK]

    @property
    def watch(self) -> 'CompileWatch':
        """The :class:`CompileWatch` this wrapper reports into."""
        return self._watch

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        """Delegate to the wrapped jit's ``lower`` (AOT introspection
        such as ``cost_analysis`` stays available through the wrapper;
        nothing is counted — only :meth:`__call__` compiles count)."""
        return self._fn.lower(*args, **kwargs)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        statics = {k: kwargs[k] for k in self._static if k in kwargs}
        call_kwargs = (
            {k: v for k, v in kwargs.items() if k not in statics}
            if statics else kwargs)
        static_values = tuple(statics.values())
        rejected = None
        last = self._last
        if last is not None and last.selects(
                static_values, args, call_kwargs):
            try:
                # jax.stages.Compiled checks the argument tree and each
                # leaf's shape and dtype (then committed shardings and
                # layouts) in its own call path, and raises before
                # anything runs or is donated: the check a fingerprint
                # key would only repeat, in Python and a leaf at a time
                out = last.executable(*args, **call_kwargs)
            except (TypeError, ValueError):
                rejected = last.executable
            else:
                self._watch._count_dispatch(self.entry, 'fast')
                return out
        self._watch._count_dispatch(self.entry, 'fingerprinted')
        fp = fingerprint_args(
            args, call_kwargs, statics,
            include_sharding=self._watch.config.include_sharding)
        key = fingerprint_key(_program_view(fp))
        executable = self._cache.get(key)
        if executable is _FALLBACK:
            return self._fn(*args, **kwargs)
        if executable is not None and executable is not rejected:
            try:
                out = executable(*args, **call_kwargs)
            except (TypeError, ValueError):
                pass
            else:
                self._last = _LastCall.of(
                    executable, static_values, args, call_kwargs)
                return out
        # no executable under this key, or XLA rejected the input
        # (sharding/layout changed under an unchanged program view, or a
        # fingerprint collision): drop the stale one and compile — the
        # event's diff names what moved
        self._cache.pop(key, None)
        out = self._compile_and_call(fp, key, args, kwargs, call_kwargs)
        executable = self._cache[key]
        if executable is not _FALLBACK:
            self._last = _LastCall.of(
                executable, static_values, args, call_kwargs)
        return out

    def _compile_and_call(
        self,
        fp: dict[str, Any],
        key: str,
        args: tuple[Any, ...],
        kwargs: dict[str, Any],
        call_kwargs: dict[str, Any],
    ) -> Any:
        watch = self._watch
        cfg = watch.config
        ordinal = watch._counts.get(self.entry, 0) + 1
        started = time.time()
        diff = fingerprint_diff(watch._last_fp.get(self.entry), fp)
        # heartbeat contract: each line lands on disk BEFORE the blocking
        # phase it announces, so a SIGKILL leaves the true last phase
        watch._journal(
            {'phase': 'lowering', 'entry': self.entry, 'n': ordinal,
             't': started, 'fingerprint': fp, 'diff': diff},
            fsync=True)
        perf0 = time.perf_counter()
        aot = True
        aot_error = None  # why AOT gave way: never dropped on the floor
        executable = None
        lowering_s = 0.0
        try:
            lowered = self._fn.lower(*args, **kwargs)
            lowering_s = time.perf_counter() - perf0
        except Exception as exc:
            aot, aot_error = False, f'lower: {type(exc).__name__}: {exc}'
        watch._journal(
            {'phase': 'compiling', 'entry': self.entry, 'n': ordinal,
             't': time.time(), 'lowering_s': lowering_s, 'aot': aot},
            fsync=True)
        if cfg.fault_compile_sleep_s > 0.0:
            time.sleep(cfg.fault_compile_sleep_s)
        result = None
        have_result = False
        perf1 = time.perf_counter()
        if aot:
            try:
                executable = lowered.compile()
            except Exception as exc:
                aot, aot_error = False, f'compile: {type(exc).__name__}: {exc}'
        if not aot:
            # plain dispatch still compiles under the hood on first call;
            # time that as the compile cost and pin this fingerprint to
            # the fallback path
            result = self._fn(*args, **kwargs)
            have_result = True
        compile_s = time.perf_counter() - perf1
        memory = _memory_analysis(executable) if aot else None
        event = {
            'entry': self.entry,
            't': started,
            'lowering_s': lowering_s,
            'compile_s': compile_s,
            'total_s': lowering_s + compile_s,
            'fingerprint': fp,
            'fingerprint_key': key,
            'diff': diff,
            'aot': aot,
            'aot_error': aot_error,
            'memory': memory,
        }
        watch._record_event(event)
        watch._journal(
            {'phase': 'done', 'entry': self.entry, 'n': event['n'],
             't': time.time(), 'compile_s': compile_s, 'aot': aot,
             'memory_total_bytes': measured_hbm_bytes(memory)},
            fsync=False)
        if aot:
            self._cache[key] = executable
            return executable(*args, **call_kwargs)
        self._cache[key] = _FALLBACK
        if have_result:
            return result
        return self._fn(*args, **kwargs)
