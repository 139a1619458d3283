"""Per-step, per-scope DEVICE-time attribution from XLA profiler traces.

``bench.py``'s host-side phase timing (jit each phase alone, wall-clock
around ``block_until_ready``) measures dispatch latency plus device time
plus whatever else the host was doing — the dispatch term dominates
small ops. The profiler trace
:func:`~kfac_tpu.observability.profiler.capture_steps` writes already
contains the truth: every device-lane event, microsecond-timed by the
chip, with the engine's ``__kfac_scope__`` named scopes
(:mod:`kfac_tpu.tracing`, linted by KFL101) embedded in the event names
and ``StepTraceAnnotation`` group ids tying events to steps.

This module parses that trace (Chrome trace-event JSON, gzipped —
stdlib only, no TF/profiler deps) into per-step per-scope device-time
breakdowns. Attribution rules:

- only DEVICE lanes count (``process_name`` metadata matching
  ``/device:``): host-side tracing/dispatch never pollutes the numbers;
- an event belongs to the deepest named scope occurring in its name (or
  its args), on an identifier boundary — so ``dist_kfac.step`` never
  miscounts as ``kfac.step``;
- an event belongs to the step whose ``group_id`` it carries (the
  ``StepTraceAnnotation`` contract), else to the host step window
  overlapping its timestamp, else to no step (still counted in the
  all-steps totals).

See docs/OBSERVABILITY.md "Measurement truth".
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from typing import Any, Iterable, Mapping, Sequence

#: the engine's named scopes (the KFL101 lint keeps the decorators on the
#: entry points; this list keys attribution). Order does not matter —
#: matching is deepest-occurrence, longest-name.
KFAC_SCOPES: tuple[str, ...] = (
    'kfac.step',
    'kfac.update_factors',
    'kfac.update_inverses',
    'kfac.precondition',
    'kfac.async_refresh',
    'kfac.async_host_launch',
    'kfac.async_host_pump',
    'kfac.offload_pump',
    'dist_kfac.step',
    'dist_kfac.update_factors',
    'dist_kfac.update_inverses',
    'dist_kfac.precondition',
    'dist_kfac.async_refresh',
    'dist_kfac.async_host_launch',
    'trainer/step',
    'trainer/scan_steps',
    'trainer/step_accumulate',
    'trainer/step_accumulate_scan',
)

#: the StepTraceAnnotation name profiler.step_annotation uses
STEP_ANNOTATION = 'train'

_IDENT = set('abcdefghijklmnopqrstuvwxyz'
             'ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.')


# ------------------------------------------------------------------ loading


def find_trace_files(logdir: str | os.PathLike[str]) -> list[str]:
    """Every ``*.trace.json.gz`` under a profiler logdir (the XLA
    profiler nests them at ``plugins/profile/<run>/<host>.trace.json.gz``;
    a bare ``trace.json.gz`` or a direct file path also resolves)."""
    logdir = os.fspath(logdir)
    if os.path.isfile(logdir):
        return [logdir]
    found = glob.glob(
        os.path.join(logdir, '**', '*trace.json.gz'), recursive=True
    )
    return sorted(found)


def load_events(path: str | os.PathLike[str]) -> list[dict[str, Any]]:
    """The ``traceEvents`` list of one gzipped Chrome-trace file."""
    with gzip.open(os.fspath(path), 'rt', encoding='utf-8',
                   errors='replace') as f:
        doc = json.load(f)
    events = doc.get('traceEvents', []) if isinstance(doc, dict) else []
    return [e for e in events if isinstance(e, dict)]


# ------------------------------------------------------------------ parsing


def device_pids(events: Iterable[Mapping[str, Any]]) -> set[Any]:
    """pids whose ``process_name`` metadata names a device lane."""
    pids = set()
    for e in events:
        if e.get('ph') == 'M' and e.get('name') == 'process_name':
            name = str((e.get('args') or {}).get('name', ''))
            if '/device:' in name.lower() or name.startswith('TPU'):
                pids.add(e.get('pid'))
    return pids


def match_scope(
    name: str, scopes: Sequence[str] = KFAC_SCOPES
) -> str | None:
    """The deepest (latest-starting, then longest) scope occurring in
    ``name`` on an identifier boundary.

    Boundary matters: ``dist_kfac.update_factors`` contains the
    substring ``kfac.update_factors``, but preceded by ``_`` — not a
    scope entry. Nested scopes (``.../kfac.step/kfac.precondition/...``)
    attribute to the innermost, so phase totals don't double-count their
    parent.
    """
    best: tuple[int, int] | None = None
    best_scope = None
    for scope in scopes:
        start = 0
        while True:
            pos = name.find(scope, start)
            if pos < 0:
                break
            start = pos + 1
            if pos > 0 and name[pos - 1] in _IDENT:
                continue
            key = (pos, len(scope))
            if best is None or key > best:
                best, best_scope = key, scope
    return best_scope


def _step_windows(
    events: Iterable[Mapping[str, Any]],
) -> tuple[dict[Any, int], list[tuple[float, float, int]]]:
    """(group_id -> step_num, [(ts, end, step_num)]) from the host
    ``StepTraceAnnotation`` events."""
    groups: dict[Any, int] = {}
    windows: list[tuple[float, float, int]] = []
    for e in events:
        if e.get('ph') != 'X' or e.get('name') != STEP_ANNOTATION:
            continue
        args = e.get('args') or {}
        step = args.get('step_num')
        if step is None:
            continue
        step = int(step)
        if 'group_id' in args:
            groups[args['group_id']] = step
        ts, dur = e.get('ts'), e.get('dur')
        if isinstance(ts, (int, float)) and isinstance(dur, (int, float)):
            windows.append((float(ts), float(ts) + float(dur), step))
    return groups, windows


def _event_step(
    e: Mapping[str, Any],
    groups: Mapping[Any, int],
    windows: Sequence[tuple[float, float, int]],
) -> int | None:
    gid = (e.get('args') or {}).get('group_id')
    if gid in groups:
        return groups[gid]
    ts = e.get('ts')
    if isinstance(ts, (int, float)):
        mid = float(ts) + float(e.get('dur') or 0.0) / 2.0
        for lo, hi, step in windows:
            if lo <= mid < hi:
                return step
    return None


def step_attribution(
    logdir: str | os.PathLike[str],
    scopes: Sequence[str] = KFAC_SCOPES,
) -> dict[str, Any]:
    """Parse every trace file under ``logdir`` into device-time truth.

    Returns::

        {
          'steps':       {step_num: {scope: ms, ..., 'unattributed': ms}},
          'total_ms':    {scope: ms, ...},   # across all device events
          'per_step_ms': {scope: ms, ...},   # mean over annotated steps
          'n_steps': int, 'n_device_events': int, 'trace_files': [...],
        }

    Empty dicts (``n_device_events == 0``) mean the trace carried no
    device lanes — e.g. a CPU-backend capture — not an error: callers
    keep their host-side numbers and skip the device view.
    """
    steps: dict[int, dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float)
    )
    total: dict[str, float] = collections.defaultdict(float)
    n_dev = 0
    files = find_trace_files(logdir)
    for path in files:
        try:
            events = load_events(path)
        except (OSError, ValueError):
            continue
        pids = device_pids(events)
        groups, windows = _step_windows(events)
        for e in events:
            if e.get('ph') != 'X' or e.get('pid') not in pids:
                continue
            dur = e.get('dur')
            if not isinstance(dur, (int, float)) or dur <= 0:
                continue
            n_dev += 1
            name = str(e.get('name', ''))
            args = e.get('args') or {}
            scope = match_scope(name, scopes)
            if scope is None:
                for v in args.values():
                    if isinstance(v, str):
                        scope = match_scope(v, scopes)
                        if scope is not None:
                            break
            key = scope if scope is not None else 'unattributed'
            ms = float(dur) / 1e3  # trace-event ts/dur are microseconds
            total[key] += ms
            step = _event_step(e, groups, windows)
            if step is not None:
                steps[step][key] += ms
    per_step: dict[str, float] = {}
    if steps:
        for rec in steps.values():
            for k, v in rec.items():
                per_step[k] = per_step.get(k, 0.0) + v
        per_step = {
            k: round(v / len(steps), 4) for k, v in per_step.items()
        }
    return {
        'steps': {
            s: {k: round(v, 4) for k, v in sorted(rec.items())}
            for s, rec in sorted(steps.items())
        },
        'total_ms': {k: round(v, 4) for k, v in sorted(total.items())},
        'per_step_ms': per_step,
        'n_steps': len(steps),
        'n_device_events': n_dev,
        'trace_files': [os.fspath(p) for p in files],
    }


def device_breakdown_ms(
    logdir: str | os.PathLike[str],
    scopes: Sequence[str] = KFAC_SCOPES,
) -> dict[str, float]:
    """Mean per-step device milliseconds per scope — the drop-in device
    counterpart of bench.py's host-clock ``step_breakdown_ms``. Empty
    when the trace has no device lanes or no annotated steps."""
    return step_attribution(logdir, scopes)['per_step_ms']
