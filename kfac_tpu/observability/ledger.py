"""Unified run ledger: one normalized event schema over every stream.

The repo emits eight telemetry streams — metrics JSONL, flight-recorder
drains, compile-watch journals/events, calibration records, trace-attrib
breakdowns, fleet events, chaos worker events, serving-engine request
records — plus bench round JSON.
Each is independently useful; none joins. This module is the synthesis
layer: per-stream adapters parse the formats **already committed** (no
producer rewrite) into one event shape keyed by
``(run_id, stream, step, wall_clock)``, a correlation engine joins
anomalies across streams into causal timeline annotations, and a
perf-regression sentinel gates bench rounds against a committed
provenance-aware baseline (``bench_runs/LEDGER.json``).

Deliberately stdlib-only, like ``tools/kfac_inspect.py``: postmortem triage happens on machines without
jax. CLIs load this file standalone via
``importlib.util.spec_from_file_location`` so importing it never drags
in the package ``__init__`` (which imports jax).

Event schema (a plain dict; every adapter emits exactly these keys)::

    {'run_id': str | None,   # from the optional run-header record
     'stream': str,          # adapter name ('metrics', 'compile', ...)
     'step':   int | None,   # training step; estimated for t-only events
     't':      float | None, # wall clock (epoch seconds) when carried
     'kind':   str,          # 'record', 'compile_phase', 'fleet_event', ...
     'detail': str,          # one-line human rendering
     'data':   dict}         # the raw parsed record

Producers stay untouched except for the optional shared run-header: a
first JSONL record ``{'kind': 'run_header', 'schema': 1, 'run_id': ...,
'stream': ...}`` written by :class:`~kfac_tpu.observability.sinks.
JSONLWriter` when constructed with ``run_header=``. Header-less files
parse exactly as before with ``run_id=None``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import tempfile
import uuid
from typing import Any, Callable, Iterable, Sequence

#: ledger event/baseline format version (run-header ``schema`` field and
#: ``bench_runs/LEDGER.json`` ``schema`` field)
LEDGER_SCHEMA = 1

#: metric keys scanned (in order; first present wins) for the per-step
#: host wall-clock used by spike detection
STEP_TIME_KEYS = ('step_time_s', 'time/step_s', 'step_time_ms')

#: calibration keys scanned (in order; first folding key wins per
#: record) for model-fold anomalies
CALIB_FOLD_KEYS = ('calib/model_error', 'calib/mem_ratio', 'calib/step_ratio')

#: fleet controller events treated as reactions worth a timeline entry
FLEET_REACTION_EVENTS = ('drift', 'retune', 'armed', 'migrated')


def new_run_id() -> str:
    """A fresh 12-hex-char run identifier."""
    return uuid.uuid4().hex[:12]


def run_header(run_id: str, stream: str) -> dict[str, Any]:
    """The shared run-header record stamped first into each JSONL stream."""
    return {
        'kind': 'run_header',
        'run_id': str(run_id),
        'schema': LEDGER_SCHEMA,
        'stream': str(stream),
    }


@dataclasses.dataclass(frozen=True)
class LedgerConfig:
    """Knobs for correlation and anomaly derivation.

    Attributes:
        spike_factor: a step time >= ``spike_factor`` x the windowed
            median of prior steps is a ``step_time_spike`` anomaly.
        spike_window: number of prior step times the spike median is
            taken over (at least 3 must exist before any spike fires).
        join_steps: max step distance between consecutive links of a
            correlation-rule chain.
        join_seconds: max wall-clock distance for chain links when
            either event has no (estimated) step.
        calib_fold_threshold: a calibration ratio >= this is a
            ``calib_fold`` anomaly (predicted/measured model fold).
        huge_factor: finite metric magnitudes >= this are
            ``huge_factor`` anomalies (matches kfac_inspect's bound).
        sentinel_window: bench rounds per key folded into the baseline
            median by :func:`build_baseline`.
    """

    spike_factor: float = 1.5
    spike_window: int = 5
    join_steps: int = 4
    join_seconds: float = 30.0
    calib_fold_threshold: float = 1.5
    huge_factor: float = 1e8
    sentinel_window: int = 5

    def __post_init__(self) -> None:
        if self.spike_factor <= 1.0:
            raise ValueError(
                f'spike_factor must be > 1, got {self.spike_factor}')
        if self.spike_window < 3:
            raise ValueError(
                f'spike_window must be >= 3, got {self.spike_window}')
        if self.join_steps < 0:
            raise ValueError(
                f'join_steps must be >= 0, got {self.join_steps}')
        if self.join_seconds <= 0:
            raise ValueError(
                f'join_seconds must be > 0, got {self.join_seconds}')
        if self.calib_fold_threshold <= 0:
            raise ValueError('calib_fold_threshold must be > 0, got '
                             f'{self.calib_fold_threshold}')
        if self.huge_factor <= 0:
            raise ValueError(
                f'huge_factor must be > 0, got {self.huge_factor}')
        if self.sentinel_window < 1:
            raise ValueError(
                f'sentinel_window must be >= 1, got {self.sentinel_window}')


# --------------------------------------------------------------- parsing

def _make_event(
    stream: str,
    kind: str,
    detail: str,
    data: dict[str, Any],
    run_id: str | None = None,
    step: int | None = None,
    t: float | None = None,
) -> dict[str, Any]:
    return {'run_id': run_id, 'stream': stream, 'step': step, 't': t,
            'kind': kind, 'detail': detail, 'data': data}


def _records(source: Any) -> list[dict[str, Any]]:
    """Records from a JSONL path or an already-parsed iterable of dicts.

    Corrupt / blank lines are skipped (a crashed run's torn final write
    must never block triage of the lines before it)."""
    if isinstance(source, (str, os.PathLike)):
        out: list[dict[str, Any]] = []
        with open(source, encoding='utf-8') as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
        return out
    return [r for r in source if isinstance(r, dict)]


def _split_header(
    records: list[dict[str, Any]],
) -> tuple[str | None, list[dict[str, Any]]]:
    """Pop the optional run-header; header-less streams -> run_id None."""
    if records and records[0].get('kind') == 'run_header':
        header, rest = records[0], records[1:]
        rid = header.get('run_id')
        return (str(rid) if rid is not None else None), rest
    return None, records


def _num(value: Any) -> float | None:
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    return float(value)


def _step_of(record: dict[str, Any], key: str = 'step') -> int | None:
    v = record.get(key)
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return int(v)


def _parse_step_records(source: Any, stream: str) -> list[dict[str, Any]]:
    run_id, records = _split_header(_records(source))
    events = []
    for rec in records:
        step = _step_of(rec)
        if step is None and stream != 'calibration':
            continue
        events.append(_make_event(
            stream, 'record', f'step {step}', rec,
            run_id=run_id, step=step, t=_num(rec.get('t'))))
    return events


def parse_metrics(source: Any) -> list[dict[str, Any]]:
    """Metrics-collector drains: one record per step, flat metric keys."""
    return _parse_step_records(source, 'metrics')


def parse_flight(source: Any) -> list[dict[str, Any]]:
    """Flight-recorder ring drains / postmortem ``history.jsonl``."""
    return _parse_step_records(source, 'flight')


def parse_calibration(source: Any) -> list[dict[str, Any]]:
    """Records carrying ``calib/*`` keys (standalone file or drains)."""
    return _parse_step_records(source, 'calibration')


def parse_compile(source: Any) -> list[dict[str, Any]]:
    """Compile-watch journal heartbeats and ``compile_events.jsonl``.

    Journal records carry ``phase`` (``lowering``/``compiling``/
    ``done``); drained in-memory events carry timings but no phase."""
    run_id, records = _split_header(_records(source))
    events = []
    for rec in records:
        rid = rec.get('run_id', run_id)
        entry = rec.get('entry', '?')
        t = _num(rec.get('t'))
        n = rec.get('n')
        if 'phase' in rec:
            phase = rec['phase']
            detail = f'{phase} {entry}' + (f' n={n}' if n is not None else '')
            events.append(_make_event(
                'compile', 'compile_phase', detail, rec, run_id=rid, t=t))
        else:
            detail = f'{entry}' + (f' n={n}' if n is not None else '')
            events.append(_make_event(
                'compile', 'compile_done', detail, rec, run_id=rid, t=t))
    return events


def parse_fleet(source: Any) -> list[dict[str, Any]]:
    """Fleet controller events: ``{'event', 'step', 'detail'}``."""
    run_id, records = _split_header(_records(source))
    events = []
    for rec in records:
        name = rec.get('event')
        if not isinstance(name, str):
            continue
        detail = name
        if rec.get('detail'):
            detail += f": {rec['detail']}"
        events.append(_make_event(
            'fleet', 'fleet_event', detail, rec,
            run_id=run_id, step=_step_of(rec), t=_num(rec.get('t'))))
    return events


def parse_chaos(source: Any) -> list[dict[str, Any]]:
    """Chaos worker emissions: start/step/preempted/done lines."""
    run_id, records = _split_header(_records(source))
    events = []
    for rec in records:
        name = rec.get('event')
        if not isinstance(name, str):
            continue
        step = _step_of(rec)
        if step is None:
            step = _step_of(rec, 'saved_step')
        if step is None:
            step = _step_of(rec, 'resumed_step')
        events.append(_make_event(
            'chaos', 'chaos_event', name, rec,
            run_id=run_id, step=step, t=_num(rec.get('t'))))
    return events


def parse_trace(source: Any) -> list[dict[str, Any]]:
    """A saved per-step per-scope device-time attribution (JSON:
    ``{'steps': {step: {scope: ms}}, 'per_step_ms': {scope: ms}}``)."""
    if isinstance(source, (str, os.PathLike)):
        with open(source, encoding='utf-8') as f:
            data = json.load(f)
    else:
        data = source
    if not isinstance(data, dict):
        return []
    rid = data.get('run_id')
    run_id = str(rid) if rid is not None else None
    events = []
    for step, scopes in sorted(
            (data.get('steps') or {}).items(), key=lambda kv: int(kv[0])):
        events.append(_make_event(
            'trace', 'trace_step', f'step {int(step)} device ms', scopes,
            run_id=run_id, step=int(step)))
    if data.get('per_step_ms'):
        events.append(_make_event(
            'trace', 'trace_summary', 'mean per-step device ms',
            data['per_step_ms'], run_id=run_id))
    return events


def parse_bench(source: Any) -> list[dict[str, Any]]:
    """A bench round: a driver round file (``{'parsed': ...}``) or a
    flat ``bench_runs/run_*.json`` record."""
    if isinstance(source, (str, os.PathLike)):
        with open(source, encoding='utf-8') as f:
            data = json.load(f)
    else:
        data = source
    if not isinstance(data, dict):
        return []
    parsed = data.get('parsed') if isinstance(data.get('parsed'), dict) \
        else data
    rid = data.get('run_id', parsed.get('run_id'))
    metric = parsed.get('metric', '?')
    value = parsed.get('value')
    detail = f'{metric}={value:g}' if _num(value) is not None \
        else str(metric)
    return [_make_event(
        'bench', 'bench_round', detail, parsed,
        run_id=str(rid) if rid is not None else None)]


def parse_serving(source: Any) -> list[dict[str, Any]]:
    """Serving-engine request records (``kfac_tpu/serving/engine.py``
    metrics JSONL): one ``serve`` event per answered request batch,
    carrying path, request count, bucket(s), sample count, escalations,
    and latency. Step-less — serving happens outside the training step
    clock — so events order by wall clock."""
    run_id, records = _split_header(_records(source))
    events = []
    for rec in records:
        if rec.get('kind') not in (None, 'serve'):
            continue
        lat = _num(rec.get('latency_ms'))
        detail = (
            f"{rec.get('path', '?')} requests={rec.get('requests', '?')} "
            + (f'{lat:g}ms' if lat is not None else '?ms'))
        if _num(rec.get('n_escalated')):
            detail += f" escalated={rec['n_escalated']}"
        events.append(_make_event(
            'serving', 'serve', detail, rec,
            run_id=run_id, t=_num(rec.get('t'))))
    return events


#: stream-adapter registry: stream name -> parse callable. Pinned to the
#: docs/OBSERVABILITY.md stream-adapter matrix by KFL113.
ADAPTERS: dict[str, Callable[[Any], list[dict[str, Any]]]] = {
    'metrics': parse_metrics,
    'flight': parse_flight,
    'compile': parse_compile,
    'calibration': parse_calibration,
    'trace': parse_trace,
    'fleet': parse_fleet,
    'chaos': parse_chaos,
    'serving': parse_serving,
    'bench': parse_bench,
}

#: filename conventions for :meth:`RunLedger.ingest_dir` autodiscovery,
#: first match wins (``history.jsonl``/``compile_events.jsonl`` are the
#: postmortem-bundle names)
_DISCOVERY: tuple[tuple[str, str], ...] = (
    # 'serving' outranks 'metrics' so a producer's serving_metrics.jsonl
    # lands on the serving adapter, not the training-metrics one
    ('serving', 'serving'),
    ('metrics', 'metrics'),
    ('history', 'flight'),
    ('flight', 'flight'),
    ('compile', 'compile'),
    ('calib', 'calibration'),
    ('trace', 'trace'),
    ('fleet', 'fleet'),
    ('chaos', 'chaos'),
    ('bench', 'bench'),
    ('round', 'bench'),
)


# ---------------------------------------------------------------- ledger

def _sort_key(event: dict[str, Any]) -> tuple:
    step = event['step']
    t = event['t']
    return (
        0 if step is not None else 1, step if step is not None else 0,
        0 if t is not None else 1, t if t is not None else 0.0,
        event['stream'], event['kind'], event['detail'],
    )


class RunLedger:
    """Normalized events from any number of streams, plus derived
    anomalies and correlation annotations."""

    def __init__(self, config: LedgerConfig | None = None) -> None:
        self.config = config or LedgerConfig()
        self.events: list[dict[str, Any]] = []

    # ---------------------------------------------------------- ingest

    def ingest(self, stream: str, source: Any) -> int:
        """Parse one source through the named adapter; returns events
        added."""
        if stream not in ADAPTERS:
            raise ValueError(
                f'unknown stream {stream!r}; adapters: '
                f'{", ".join(sorted(ADAPTERS))}')
        events = ADAPTERS[stream](source)
        self.events.extend(events)
        return len(events)

    def ingest_dir(self, root: str | os.PathLike[str]) -> dict[str, int]:
        """Autodiscover stream files in a directory by filename
        convention (a postmortem bundle dir works too: ``history.jsonl``
        -> flight, ``compile_events.jsonl`` -> compile)."""
        root = os.fspath(root)
        counts: dict[str, int] = {}
        for name in sorted(os.listdir(root)):
            path = os.path.join(root, name)
            if not os.path.isfile(path):
                continue
            if not (name.endswith('.json') or name.endswith('.jsonl')):
                continue
            low = name.lower()
            if low.startswith(('ledger', 'manifest')):
                continue
            for token, stream in _DISCOVERY:
                if token in low:
                    counts[stream] = counts.get(stream, 0) \
                        + self.ingest(stream, path)
                    break
        self.assign_steps()
        return counts

    # ------------------------------------------------------ step clock

    def step_clock(self) -> list[tuple[int, float]]:
        """(step, wall_clock) anchor pairs from every event carrying
        both — any such stream teaches the ledger this run's step
        clock."""
        anchors: dict[int, float] = {}
        for e in self.events:
            if e['step'] is not None and e['t'] is not None \
                    and not e['data'].get('step_est'):
                anchors.setdefault(e['step'], e['t'])
        return sorted(anchors.items())

    def assign_steps(self) -> int:
        """Estimate steps for wall-clock-only events (compile heartbeats)
        by interpolating the step clock. Returns events assigned."""
        clock = self.step_clock()
        if len(clock) < 2:
            return 0
        assigned = 0
        for e in self.events:
            if e['step'] is not None or e['t'] is None:
                continue
            e['step'] = _interp_step(clock, e['t'])
            e['data'] = dict(e['data'], step_est=True)
            assigned += 1
        return assigned

    # ------------------------------------------------------- accessors

    def runs(self) -> list[str]:
        return sorted({e['run_id'] for e in self.events
                       if e['run_id'] is not None})

    def streams(self) -> list[str]:
        return sorted({e['stream'] for e in self.events})

    def sorted_events(self) -> list[dict[str, Any]]:
        return sorted(self.events, key=_sort_key)

    def anomalies(self) -> list[dict[str, Any]]:
        return derive_anomalies(self.sorted_events(), self.config)

    def correlations(self) -> list[dict[str, Any]]:
        return correlate(self.anomalies(), self.config)


def _interp_step(clock: Sequence[tuple[int, float]], t: float) -> int:
    """Piecewise-linear step estimate (floored: an event at wall time t
    happened during the step whose window contains t)."""
    lo = clock[0]
    hi = clock[-1]
    if t <= lo[1]:
        seg = (clock[0], clock[1])
    elif t >= hi[1]:
        seg = (clock[-2], clock[-1])
    else:
        seg = (clock[0], clock[1])
        for a, b in zip(clock, clock[1:]):
            if a[1] <= t <= b[1]:
                seg = (a, b)
                break
    (s0, t0), (s1, t1) = seg
    if t1 == t0:
        return s0
    return int(math.floor(s0 + (t - t0) * (s1 - s0) / (t1 - t0)))


# ------------------------------------------------------------- anomalies

def _fmt(value: float) -> str:
    return f'{value:.3g}'


def derive_anomalies(
    events: Sequence[dict[str, Any]],
    config: LedgerConfig | None = None,
) -> list[dict[str, Any]]:
    """Anomaly events derived from normalized record events.

    Kinds: ``step_time_spike``, ``nonfinite_loss``, ``nonfinite_metric``,
    ``huge_factor``, ``calib_fold``, ``recompile``, ``died_compiling``,
    ``fleet_reaction``, ``preempted``, ``recovered``. Each keeps the
    source stream so correlation rules can name it."""
    cfg = config or LedgerConfig()
    out: list[dict[str, Any]] = []
    step_times: list[float] = []
    seen: set[tuple[str, str]] = set()
    # (pid, entry) -> last heartbeat record, cleared on 'done'
    in_flight: dict[tuple[Any, str], dict[str, Any]] = {}

    def emit(src: dict[str, Any], kind: str, detail: str) -> None:
        out.append(_make_event(
            src['stream'], kind, detail, src['data'],
            run_id=src['run_id'], step=src['step'], t=src['t']))

    for e in events:
        data = e['data']
        if e['kind'] == 'record':
            # host step-time spike vs windowed median of prior steps
            for key in STEP_TIME_KEYS:
                v = _num(data.get(key))
                if v is None:
                    continue
                if len(step_times) >= 3:
                    med = statistics.median(
                        step_times[-cfg.spike_window:])
                    if med > 0 and v >= cfg.spike_factor * med:
                        emit(e, 'step_time_spike',
                             f'{key} {_fmt(v)} >= '
                             f'{_fmt(cfg.spike_factor)}x median {_fmt(med)}')
                step_times.append(v)
                break
            # calibration model fold (first folding key per record)
            for key in CALIB_FOLD_KEYS:
                v = _num(data.get(key))
                if v is not None and v >= cfg.calib_fold_threshold:
                    emit(e, 'calib_fold',
                         f'{key} {_fmt(v)} >= '
                         f'{_fmt(cfg.calib_fold_threshold)}')
                    break
            # nonfinite / huge metric evidence (first hit per key)
            for key in sorted(data):
                if key in ('step', 't', 'n', 'process_index'):
                    continue
                v = _num(data.get(key))
                if v is None:
                    continue
                if not math.isfinite(v):
                    kind = ('nonfinite_loss' if key == 'loss'
                            else 'nonfinite_metric')
                    if (kind, key) not in seen:
                        seen.add((kind, key))
                        emit(e, kind, f'{key} is non-finite')
                elif abs(v) >= cfg.huge_factor:
                    if ('huge_factor', key) not in seen:
                        seen.add(('huge_factor', key))
                        emit(e, 'huge_factor',
                             f'{key} {_fmt(v)} >= {_fmt(cfg.huge_factor)}')
        elif e['kind'] == 'compile_phase':
            key = (data.get('pid'), data.get('entry', '?'))
            if data.get('phase') == 'done':
                in_flight.pop(key, None)
                if isinstance(data.get('n'), int) and data['n'] >= 2:
                    emit(e, 'recompile',
                         f"{data.get('entry', '?')} n={data['n']}")
            else:
                in_flight[key] = e
        elif e['kind'] == 'compile_done':
            if isinstance(data.get('n'), int) and data['n'] >= 2:
                emit(e, 'recompile', f"{data.get('entry', '?')} n={data['n']}")
        elif e['kind'] == 'fleet_event':
            if data.get('event') in FLEET_REACTION_EVENTS:
                emit(e, 'fleet_reaction', e['detail'])
        elif e['kind'] == 'chaos_event':
            name = data.get('event')
            if name == 'preempted':
                emit(e, 'preempted',
                     f"signal={data.get('signal')} "
                     f"saved_step={data.get('saved_step')}")
            elif name == 'start' and (_step_of(data, 'resumed_step') or 0) > 0:
                emit(e, 'recovered',
                     f"resumed_step={data.get('resumed_step')} "
                     f"fallback_depth={data.get('fallback_depth')}")
    # compiles still in flight when the stream ended: the process died
    # (or is still dying) inside XLA — the "died compiling X" verdict
    for hb in in_flight.values():
        emit(hb, 'died_compiling',
             f"{hb['data'].get('entry', '?')} last phase "
             f"{hb['data'].get('phase', '?')} (pid {hb['data'].get('pid')})")
    return sorted(out, key=_sort_key)


# ------------------------------------------------------------ correlation

@dataclasses.dataclass(frozen=True)
class CorrelationRule:
    """A declarative causal chain over anomaly kinds.

    ``chain`` is an ordered tuple of ``(stream, kind)`` links; stream
    ``'*'`` matches any. An annotation fires only when EVERY link
    matches, each within ``join_steps`` (or ``join_seconds`` when
    step-less) of the previous link — a missing link is a clean
    negative, not a partial match."""

    name: str
    chain: tuple[tuple[str, str], ...]
    description: str

    def __post_init__(self) -> None:
        if len(self.chain) < 2:
            raise ValueError(
                f'rule {self.name!r} needs >= 2 links, got {self.chain!r}')


#: built-in rules. Pinned to the docs/OBSERVABILITY.md correlation-rule
#: table by KFL113.
DEFAULT_RULES: tuple[CorrelationRule, ...] = (
    CorrelationRule(
        'recompile_cascade',
        (('compile', 'recompile'), ('*', 'step_time_spike'),
         ('*', 'calib_fold'), ('fleet', 'fleet_reaction')),
        'recompile -> step-time spike -> calibration fold -> fleet reaction',
    ),
    CorrelationRule(
        'recompile_step_spike',
        (('compile', 'recompile'), ('*', 'step_time_spike')),
        'a recompile stalls the step path',
    ),
    CorrelationRule(
        'calib_fleet_reaction',
        (('*', 'calib_fold'), ('fleet', 'fleet_reaction')),
        'a calibration fold wakes the fleet controller',
    ),
    CorrelationRule(
        'factor_divergence',
        (('*', 'huge_factor'), ('*', 'nonfinite_loss')),
        'a blown-up factor precedes a non-finite loss',
    ),
    CorrelationRule(
        'preempt_recovery',
        (('chaos', 'preempted'), ('chaos', 'recovered')),
        'a preemption followed by a successful resume',
    ),
)


def _link_matches(link: tuple[str, str], event: dict[str, Any]) -> bool:
    stream, kind = link
    return event['kind'] == kind and stream in ('*', event['stream'])


def _within(prev: dict[str, Any], nxt: dict[str, Any],
            cfg: LedgerConfig) -> bool:
    ps, ns = prev['step'], nxt['step']
    if ps is not None and ns is not None:
        return ps <= ns <= ps + cfg.join_steps
    pt, nt = prev['t'], nxt['t']
    if pt is not None and nt is not None:
        return pt <= nt <= pt + cfg.join_seconds
    return False


def correlate(
    anomalies: Sequence[dict[str, Any]],
    config: LedgerConfig | None = None,
    rules: Sequence[CorrelationRule] = DEFAULT_RULES,
) -> list[dict[str, Any]]:
    """Apply declarative rules; one annotation per matched anchor event.

    Returns dicts: ``{'rule', 'run_id', 'step', 'streams', 'chain',
    'summary'}`` where ``chain`` holds one ``{stream, kind, step,
    detail}`` entry per link."""
    cfg = config or LedgerConfig()
    ordered = sorted(anomalies, key=_sort_key)
    annotations = []
    for rule in rules:
        for anchor in ordered:
            if not _link_matches(rule.chain[0], anchor):
                continue
            chain = [anchor]
            for link in rule.chain[1:]:
                nxt = next(
                    (e for e in ordered
                     if _link_matches(link, e) and e is not chain[-1]
                     and _within(chain[-1], e, cfg)),
                    None)
                if nxt is None:
                    break
                chain.append(nxt)
            if len(chain) != len(rule.chain):
                continue
            annotations.append({
                'rule': rule.name,
                'run_id': anchor['run_id'],
                'step': anchor['step'],
                'streams': sorted({e['stream'] for e in chain}),
                'chain': [{'stream': e['stream'], 'kind': e['kind'],
                           'step': e['step'], 'detail': e['detail']}
                          for e in chain],
                'summary': ' -> '.join(
                    f"{e['stream']}.{e['kind']}" for e in chain),
            })
    return annotations


# --------------------------------------------------------------- timeline

def _verdicts(anomalies: Sequence[dict[str, Any]]) -> dict[str, str]:
    """The unified triage verdicts: kfac_inspect's divergence first-bad
    signal and the compile journal's died-compiling verdict, from ONE
    ingest instead of two CLI invocations."""
    died = [a for a in anomalies if a['kind'] == 'died_compiling']
    if died:
        compile_v = 'died compiling ' + '; '.join(a['detail'] for a in died)
    else:
        compile_v = 'ok - every watched compile completed'
    bad = next((a for a in anomalies if a['kind'] in
                ('nonfinite_loss', 'nonfinite_metric', 'huge_factor')), None)
    if bad is None:
        divergence_v = 'none - no nonfinite/huge factor evidence'
    else:
        where = f'step {bad["step"]}' if bad['step'] is not None else '?'
        divergence_v = (
            f'first bad signal {bad["kind"]} at {where}: {bad["detail"]}')
    return {'compile': compile_v, 'divergence': divergence_v}


def render_timeline(ledger: RunLedger) -> str:
    """Deterministic one-report rendering: anomaly timeline, correlation
    annotations, and the unified compile/divergence verdicts."""
    anomalies = ledger.anomalies()
    annotations = correlate(anomalies, ledger.config)
    runs = ledger.runs()
    lines = [
        'run ledger: runs=' + (','.join(runs) if runs else '<none>')
        + f' streams={len(ledger.streams())}'
        + f' events={len(ledger.events)} anomalies={len(anomalies)}',
        'timeline:',
    ]
    if not anomalies:
        lines.append('  (no anomalies)')
    for a in anomalies:
        step = f'step {a["step"]}' if a['step'] is not None else 'step ?'
        lines.append(
            f'  {step:<9} {a["stream"]:<12} {a["kind"]:<16} {a["detail"]}')
    lines.append('correlations:')
    if not annotations:
        lines.append('  (none)')
    for c in annotations:
        steps = [e['step'] for e in c['chain'] if e['step'] is not None]
        span = (f'step {steps[0]} -> {steps[-1]}' if steps else 'step ?')
        n_streams = len(c['streams'])
        lines.append(
            f'  {c["rule"]:<22} {span}: {c["summary"]}'
            f' ({n_streams} stream{"s" if n_streams != 1 else ""})')
    verdicts = _verdicts(anomalies)
    lines.append('verdicts:')
    lines.append(f'  compile: {verdicts["compile"]}')
    lines.append(f'  divergence: {verdicts["divergence"]}')
    return '\n'.join(lines) + '\n'


def timeline_report(ledger: RunLedger) -> dict[str, Any]:
    """The machine-readable counterpart of :func:`render_timeline`."""
    anomalies = ledger.anomalies()
    return {
        'schema': LEDGER_SCHEMA,
        'runs': ledger.runs(),
        'streams': ledger.streams(),
        'n_events': len(ledger.events),
        'anomalies': anomalies,
        'correlations': correlate(anomalies, ledger.config),
        'verdicts': _verdicts(anomalies),
    }


# --------------------------------------------------------------- sentinel

#: headline bench keys gated by the sentinel: per-key tolerance (relative
#: to the baseline median) and regression direction. Pinned to the
#: docs/OBSERVABILITY.md sentinel tolerance table by KFL113.
DEFAULT_SENTINEL_KEYS: dict[str, dict[str, Any]] = {
    'value': {'direction': 'higher', 'tolerance': 0.15},
    'sgd_tokens_per_sec': {'direction': 'higher', 'tolerance': 0.15},
    'eager_tokens_per_sec': {'direction': 'higher', 'tolerance': 0.15},
    'scan_tokens_per_sec': {'direction': 'higher', 'tolerance': 0.15},
    'mfu': {'direction': 'higher', 'tolerance': 0.15},
    'acc_step_ratio': {'direction': 'lower', 'tolerance': 0.25},
    'acc_time_ratio': {'direction': 'lower', 'tolerance': 0.25},
    # serving headline keys: latency is
    # lower-is-better, throughput higher; 0.25 absorbs shared-host
    # timing jitter like the acc ratios above
    'serving_mc_p50_ms': {'direction': 'lower', 'tolerance': 0.25},
    'serving_mc_p95_ms': {'direction': 'lower', 'tolerance': 0.25},
    'serving_cf_p50_ms': {'direction': 'lower', 'tolerance': 0.25},
    'serving_cf_p95_ms': {'direction': 'lower', 'tolerance': 0.25},
    'serving_mc_requests_per_sec': {'direction': 'higher', 'tolerance': 0.25},
    'serving_cf_requests_per_sec': {'direction': 'higher', 'tolerance': 0.25},
}


def _round_parsed(round_json: dict[str, Any]) -> dict[str, Any]:
    parsed = round_json.get('parsed')
    return parsed if isinstance(parsed, dict) else round_json


def build_baseline(
    rounds: Sequence[dict[str, Any]],
    config: LedgerConfig | None = None,
    keys: dict[str, dict[str, Any]] | None = None,
    sources: Sequence[str] = (),
) -> dict[str, Any]:
    """Windowed-median baseline from same-provenance bench rounds.

    Provenance comes from the first round carrying a ``platform``;
    provenance-less rounds and rounds with a different platform are
    dropped (and counted) rather than polluting the median — a baseline
    never mixes CPU-fallback and TPU evidence."""
    cfg = config or LedgerConfig()
    spec = keys or DEFAULT_SENTINEL_KEYS
    parsed = [p for p in (_round_parsed(r) for r in rounds)
              if p.get('platform') is not None]
    if not parsed:
        raise ValueError(
            'build_baseline needs at least one round with provenance '
            '(a parsed `platform` key)')
    platform = parsed[0].get('platform')
    same = [p for p in parsed if p.get('platform') == platform]
    out_keys: dict[str, Any] = {}
    for key in sorted(spec):
        values = [v for p in same
                  if (v := _num(p.get(key))) is not None
                  and math.isfinite(v)]
        if not values:
            continue
        window = values[-cfg.sentinel_window:]
        out_keys[key] = {
            'median': statistics.median(window),
            'n': len(window),
            'values': window,
            'direction': spec[key]['direction'],
            'tolerance': spec[key]['tolerance'],
        }
    return {
        'schema': LEDGER_SCHEMA,
        'kind': 'bench_baseline',
        'platform': platform,
        'device_kinds': sorted(
            {str(p['device_kind']) for p in same if p.get('device_kind')}),
        'window': cfg.sentinel_window,
        'n_rounds': len(same),
        'n_dropped_provenance': len(list(rounds)) - len(same),
        'sources': sorted(sources),
        'keys': out_keys,
    }


def save_baseline(path: str | os.PathLike[str],
                  baseline: dict[str, Any]) -> None:
    """Atomic, deterministic write (the TunedPlan artifact convention:
    mkstemp + os.replace, sorted keys, no timestamps — same inputs give
    byte-identical files)."""
    path = os.fspath(path)
    parent = os.path.dirname(path) or '.'
    os.makedirs(parent, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=parent, suffix='.tmp')
    try:
        with os.fdopen(fd, 'w', encoding='utf-8') as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write('\n')
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise


def load_baseline(path: str | os.PathLike[str]) -> dict[str, Any]:
    with open(path, encoding='utf-8') as f:
        baseline = json.load(f)
    if not isinstance(baseline, dict) \
            or baseline.get('kind') != 'bench_baseline':
        raise ValueError(f'{os.fspath(path)}: not a bench_baseline artifact')
    if baseline.get('schema') != LEDGER_SCHEMA:
        raise ValueError(
            f'{os.fspath(path)}: baseline schema '
            f'{baseline.get("schema")!r} != {LEDGER_SCHEMA}')
    return baseline


def sentinel_check(
    round_json: dict[str, Any],
    baseline: dict[str, Any] | None,
) -> dict[str, Any]:
    """Gate one bench round against the committed baseline.

    Statuses: ``ok``, ``regressed`` (any named key outside tolerance),
    ``refused`` (provenance mismatch — a CPU-fallback round is NEVER
    compared against TPU medians, the PR-11 replay-defense lesson; keys
    stay empty), ``no_baseline``."""
    parsed = _round_parsed(round_json)
    platform = parsed.get('platform')
    if baseline is None:
        return {'status': 'no_baseline', 'platform': platform,
                'baseline_platform': None, 'keys': {}, 'regressed_keys': []}
    base_platform = baseline.get('platform')
    if platform != base_platform:
        return {
            'status': 'refused', 'platform': platform,
            'baseline_platform': base_platform, 'keys': {},
            'regressed_keys': [],
            'reason': (
                f'round provenance {platform!r} != baseline provenance '
                f'{base_platform!r}: not compared'),
        }
    keys: dict[str, Any] = {}
    regressed: list[str] = []
    for key, spec in sorted(baseline.get('keys', {}).items()):
        measured = _num(parsed.get(key))
        median = float(spec['median'])
        tol = float(spec['tolerance'])
        direction = spec['direction']
        entry: dict[str, Any] = {
            'baseline': median, 'tolerance': tol, 'direction': direction,
            'measured': measured,
        }
        if measured is None or not math.isfinite(measured) or median == 0:
            entry['verdict'] = 'missing'
        else:
            ratio = measured / median
            entry['ratio'] = ratio
            bad = (ratio < 1.0 - tol if direction == 'higher'
                   else ratio > 1.0 + tol)
            entry['verdict'] = 'regressed' if bad else 'ok'
            if bad:
                regressed.append(key)
        keys[key] = entry
    return {
        'status': 'regressed' if regressed else 'ok',
        'platform': platform, 'baseline_platform': base_platform,
        'keys': keys, 'regressed_keys': regressed,
    }
