"""One map of a traced step: every device operation of the stretch in
exactly one bucket, read in one pass a device plane for all the rows of
PR 38 (``dev_ms.embed`` ... ``dev_ms.other_programs``).

The program names the parts of a step (``kfac_tpu/tracing.py``:
``MODEL_SCOPES``, ``TRAINER_SCOPES``, ``CAPTURE_SCOPES``, the engines'
``tracing.scope`` decorators); as ``_program.py`` and ``_hybrid.py`` do,
this file keeps the benchmark's own copy of the names. An operation of a
K-FAC step program belongs to the deepest of them on its ``op_name``
(``harness.traced_run`` wrote the compiled programs' ``op_name``s into the
events), to ``kfac_step_self`` where that is the engine's ``*kfac.step``
itself (under the step and under none of its three parts), and to
``unscoped`` where it is none. A fusion the compiler rooted in an
instruction of its own making (a packed predicate, a re-tiled update)
carries no ``op_name``: it goes where the instructions fused into it say,
if they all say one thing (the step programs' text is read for that, once
a trace). Any other operation without an ``op_name`` (a copy, an
asynchronous prefetch, a loop's plumbing) inside a ``while`` or a
``conditional`` goes where that loop goes. An operation of a program that
is not a step program (nothing of it carries an ``op_name``: the feed's
``jit__multi_slice``, stray converts) is ``other_programs``.

The buckets are a partition of the stretch's busy time, not sums of
durations: each nanosecond in which something ran goes to the innermost
operation running then, so a loop under one scope around a body under
another is counted once (the body's time to the body's bucket, the loop's
own to the loop's), and the buckets of a plane add up to
``trace_reduce.busy_ns`` of it.

A step program loaded from a compile-cache entry older than the scopes
(JAX's cache key ignores ``op_name``) carries the old names: where a step
program of the trace shows no ``trainer.optimizer``, which every program
of this tree has, nothing is read and every row is left out, as on the
parent commit.
"""

from __future__ import annotations

import collections
import functools
import re
import time

from benchmark import trace_reduce
from benchmark.layer_metrics import _hybrid, _program

OPTIMIZER = 'trainer.optimizer'
# scope -> bucket; a bucket is a row's name without ``dev_ms.``
SCOPES = {
    'model.embed': 'embed',
    'model.mixer': 'mixer',
    'model.attention': 'attention',
    _hybrid.GDN_SCAN: 'gdn_scan',
    'model.short_conv': 'short_conv',
    'model.mlp': 'mlp',
    _hybrid.MOE_ROUTE: 'moe_route',
    _hybrid.MOE_EXPERTS: 'moe_experts',
    'model.norm': 'norm',
    'model.head': 'head',
    'model.loss': 'loss',
    'model.stem': 'stem',
    'model.stage0': 'stage0',
    'model.stage1': 'stage1',
    'model.stage2': 'stage2',
    'model.stage3': 'stage3',
    OPTIMIZER: 'optimizer',
    _program.CAPTURE_A: 'capture_a',
    _program.CAPTURE_G: 'capture_g',
    'kfac.update_factors': 'update_factors',
    'dist_kfac.update_factors': 'update_factors',
    'kfac.update_inverses': 'update_inverses',
    'dist_kfac.update_inverses': 'update_inverses',
    'kfac.precondition': 'precondition',
    'dist_kfac.precondition': 'precondition',
    'kfac.step': 'kfac_step_self',
    'dist_kfac.step': 'kfac_step_self',
}
UNSCOPED = 'unscoped'
OTHER_PROGRAMS = 'other_programs'
PASSES = ('forward', 'remat', 'backward', 'none')
TOP_UNSCOPED = 20
_COMPUTATION = re.compile(r'^%?(?P<name>[^\s(]+) \(.*\{\s*$')
_CALLS = re.compile(r'calls=%?(?P<name>[^\s,)]+)')
_OP_NAME = re.compile(r'op_name="(?P<op>[^"]*)"')
# 'broadcast.562.clone' -> 'broadcast': numbers change with every compile
_FAMILY = re.compile(r'(\.\d+|\.clone|\.remat\d*)+$')


@functools.lru_cache(maxsize=1 << 17)
def classify(op_name: str) -> tuple[str, str]:
    """``(bucket, pass)`` of an operation by its ``op_name``: the deepest
    scope of the map on it; the backward pass is under
    ``transpose(jvp(...))``, a forward pass run again for it under
    ``rematted_computation``."""
    bucket = SCOPES.get(trace_reduce.match_scope(op_name, SCOPES), UNSCOPED)
    if 'rematted_computation' in op_name:
        return bucket, 'remat'
    if 'transpose(' in op_name:
        return bucket, 'backward'
    return bucket, 'forward' if 'jvp(' in op_name else 'none'


def _flax_path(op_name: str) -> str:
    """An ``op_name`` without the jit wrappers at its head."""
    parts = op_name.split('/')
    while len(parts) > 1 and parts[0].startswith(('jit(', 'pjit(')):
        parts = parts[1:]
    return '/'.join(parts)


def fused_buckets(hlo_text: str) -> dict:
    """{computation: ``(bucket, pass)``} of a compiled program's text, for
    the computations whose instructions' ``op_name``s all :func:`classify`
    into one bucket (``pass`` is ``'none'`` where they are of several):
    what a fusion without a name of its own is made of."""
    found: dict = {}
    name = None
    for line in hlo_text.splitlines():
        if not line.startswith(' '):
            m = _COMPUTATION.match(line)
            name = m.group('name') if m is not None else None
        elif name is not None:
            m = _OP_NAME.search(line)
            if m is not None:
                found.setdefault(name, set()).add(classify(m.group('op')))
    out = {}
    for name, seen in found.items():
        buckets = {b for b, _ in seen}
        if len(buckets) == 1:
            passes = {p for _, p in seen}
            out[name] = (
                buckets.pop(), passes.pop() if len(passes) == 1 else 'none'
            )
    return out


def _fused_of(ctx) -> dict:
    """{module: :func:`fused_buckets` of its text} of the K-FAC trainer's
    compiled step programs, as ``harness._program_op_names`` finds them;
    empty where the context has no trainer."""
    kfac = getattr(getattr(ctx.run, 'trainer', None), 'kfac', None)
    watch = kfac.compile_watcher() if kfac is not None else None
    fused = {}
    for exes in (watch.executables().values() if watch is not None else ()):
        for compiled in exes:
            text = compiled.as_text()
            header = text.split(',', 1)[0].split()
            if len(header) >= 2 and header[0] == 'HloModule':
                fused[header[1]] = fused_buckets(text)
    return fused


def _step_runs(plane: dict, window) -> tuple:
    """``(events, run_of, runs, step_runs, stale)`` of a plane: its
    operations by start (an enclosing one ahead of what it encloses); for
    each the index of the program run that holds its start (``None``
    outside every run); the runs; the indices of the step programs' runs
    (a step program is one whose operations carry ``op_name``s); and
    whether one of those shows no :data:`OPTIMIZER`, or none ran at all."""
    runs = trace_reduce.module_runs(plane, window)
    events = sorted(
        trace_reduce.ops(plane, window),
        key=lambda e: (e['start_ns'], -e['duration_ns']),
    )
    run_of, r = [], -1
    shows = collections.defaultdict(bool)  # step program -> the optimizer
    for e in events:
        while r + 1 < len(runs) and runs[r + 1]['start_ns'] <= e['start_ns']:
            r += 1
        inside = r >= 0 and e['start_ns'] < (
            runs[r]['start_ns'] + runs[r]['duration_ns']
        )
        op = e['stats'].get('op_name')
        if inside and op is not None:
            shows[_module(runs[r])] |= OPTIMIZER in op
        run_of.append(r if inside else None)
    step_runs = [i for i, run in enumerate(runs) if _module(run) in shows]
    stale = not shows or not all(shows.values())
    return events, run_of, runs, step_runs, stale


def _module(run: dict) -> str:
    """A program run's module name, without the fingerprint."""
    return run['name'].split('(')[0]


def _sweep(events, run_of, runs, step_runs, window, fused: dict) -> dict:
    """{(run, bucket, pass, label): ns}: each nanosecond of ``window`` in
    which something ran, given to the innermost operation running then.
    ``label`` names an unscoped operation for the log."""
    is_step_run = set(step_runs)
    cells: dict = {}
    stack: list = []      # [end_ns, cell, bucket, pass], innermost last
    clock = window[0]

    def close(until):
        nonlocal clock
        while stack and stack[-1][0] <= until:
            end, cell = stack.pop()[:2]
            if end > clock:
                cell[0] += end - clock
                clock = end

    for e, run in zip(events, run_of):
        lo = max(e['start_ns'], window[0])
        hi = min(e['start_ns'] + e['duration_ns'], window[1])
        close(lo)
        if stack and lo > clock:
            stack[-1][1][0] += lo - clock
        clock = max(clock, lo)
        op = e['stats'].get('op_name')
        label = None
        if run not in is_step_run:
            bucket, pass_ = OTHER_PROGRAMS, 'none'
        elif op is not None:
            bucket, pass_ = classify(op)
            if bucket == UNSCOPED:
                label = _flax_path(op)
        else:
            calls = _CALLS.search(e['name'])
            made_of = calls and fused.get(_module(runs[run]), {}).get(
                calls.group('name')
            )
            if made_of:
                bucket, pass_ = made_of
            elif stack and stack[-1][0] >= hi:  # inside a loop: the loop's
                bucket, pass_ = stack[-1][2:]
            else:
                bucket, pass_ = UNSCOPED, 'none'
            if bucket == UNSCOPED:
                label = '(no op_name) ' + _FAMILY.sub(
                    '', trace_reduce.instruction(e['name'])[0]
                )
        cell = cells.setdefault((run, bucket, pass_, label), [0.0])
        stack.append([hi, cell, bucket, pass_])
    close(float('inf'))
    return {key: ns for key, (ns,) in cells.items()}


def _tables(cells: dict, step_runs: list, traced_rows: list) -> dict:
    """A plane's sums: ``total`` {bucket: ns}; ``by_pass`` {pass: {bucket:
    ns}}; ``by_kind`` {step kind: {bucket: ns}}, or ``None`` where the step
    programs' runs do not pair with the traced rows; ``unscoped`` {label:
    ns}."""
    kind_of = (
        {i: row['kind'] for i, row in zip(step_runs, traced_rows)}
        if len(step_runs) == len(traced_rows) else None
    )
    total = collections.defaultdict(float)
    by_pass = {p: collections.defaultdict(float) for p in PASSES}
    by_kind = None if kind_of is None else collections.defaultdict(
        lambda: collections.defaultdict(float)
    )
    unscoped = collections.defaultdict(float)
    for (run, bucket, pass_, label), ns in cells.items():
        total[bucket] += ns
        by_pass[pass_][bucket] += ns
        if by_kind is not None and run in kind_of:
            by_kind[kind_of[run]][bucket] += ns
        if label is not None:
            unscoped[label] += ns
    return {
        'total': dict(total), 'by_pass': by_pass, 'by_kind': by_kind,
        'unscoped': dict(unscoped),
    }


def partition(ctx) -> list | None:
    """The map of every device plane of ``ctx.trace``: one pass over each,
    printed to the run's log. ``None`` where a step program is older than
    the scopes (said in the log, and nothing else read)."""
    began = time.perf_counter()
    staged = []
    for plane in trace_reduce.device_planes(ctx.trace):
        window = ctx.windows[plane['name']]
        *found, stale = _step_runs(plane, window)
        if stale:
            print(f'[stepmap] {plane["name"]}: a step program shows no '
                  f'{OPTIMIZER} (the parent commit, or an executable from a '
                  'compile-cache entry older than the scopes): the rows of '
                  'the step map are left out', flush=True)
            return None
        staged.append((plane, window, found))
    fused = _fused_of(ctx) if staged else {}
    planes = []
    for plane, window, (events, run_of, runs, step_runs) in staged:
        cells = _sweep(events, run_of, runs, step_runs, window, fused)
        planes.append({
            'name': plane['name'],
            'busy_ns': trace_reduce.busy_ns(plane, window),
            **_tables(cells, step_runs, ctx.traced_rows),
        })
    if not planes:
        return None
    for line in report(planes, ctx.traced_rows, time.perf_counter() - began):
        print('[stepmap] ' + line, flush=True)
    return planes


def report(planes: list, traced_rows: list, seconds: float) -> list:
    """The log's lines: of the busiest plane the buckets in milliseconds a
    traced step, every step kind together, by step kind (where the runs
    pair with the rows) and by pass; then its largest unscoped
    operations."""
    plane = max(planes, key=lambda p: p['busy_ns'])
    steps = len(traced_rows)
    kinds = collections.Counter(r['kind'] for r in traced_rows)
    in_buckets = sum(plane['total'].values())
    lines = [
        f'{plane["name"]}: {steps} traced steps, busy '
        f'{plane["busy_ns"] / 1e6:.3f} ms, in the buckets '
        f'{in_buckets / 1e6:.3f} ms; {len(planes)} plane(s) in {seconds:.1f} s'
    ]
    if plane['by_kind'] is None:
        lines.append("the step programs' runs do not pair with the traced "
                     'rows: no table by step kind')
        kinds = {}
    lines.append(' '.join(
        ['ms a step'.ljust(15), 'all'.rjust(9)]
        + [f'{k}x{n}'.rjust(10) for k, n in kinds.items()]
        + ['|'] + [p.rjust(9) for p in PASSES]
    ))
    for bucket in sorted(plane['total'], key=lambda b: -plane['total'][b]):
        cols = [
            bucket.ljust(15), f'{plane["total"][bucket] / 1e6 / steps:9.3f}'
        ]
        cols += [
            f'{plane["by_kind"][k].get(bucket, 0.0) / 1e6 / n:10.3f}'
            for k, n in kinds.items()
        ]
        cols += ['|'] + [
            f'{plane["by_pass"][p].get(bucket, 0.0) / 1e6 / steps:9.3f}'
            for p in PASSES
        ]
        lines.append(' '.join(cols))
    ranked = sorted(plane['unscoped'].items(), key=lambda kv: -kv[1])
    for label, ns in ranked[:TOP_UNSCOPED]:
        lines.append(f'unscoped {ns / 1e6 / steps:9.4f} ms a step  {label}')
    return lines


def of(ctx) -> list | None:
    """:func:`partition` of the context's trace, made once a trace."""
    memo = getattr(ctx, '_stepmap', None)
    if memo is None or memo[0] is not ctx.trace:
        memo = ctx._stepmap = (ctx.trace, partition(ctx))
    return memo[1]


def read(ctx, bucket: str):
    """Device milliseconds in ``bucket`` per traced step, every step kind
    together, on the device with most; 0 is a reading. ``None`` where the
    step programs are older than the scopes."""
    steps = ctx.count(None)
    planes = of(ctx)
    if not steps or planes is None:
        return None
    return max(p['total'].get(bucket, 0.0) for p in planes) / 1e6 / steps
