"""From a profiler trace to the per-layer device numbers.

Works on a *neutral* trace, so that the tests can feed it a small recorded
one (``benchmark/tests/data``)::

    {'planes': [{'name': str, 'lines': [{'name': str, 'events': [
        {'name': str, 'start_ns': float, 'duration_ns': float,
         'stats': {str: value}}]}]}]}

``from_xplane`` builds that from the ``.xplane.pb`` the JAX profiler
writes, with ``jax.profiler.ProfileData`` and nothing else. What a TPU v5e
trace looks like (read by hand in PR 23, see PERF.md section 3): one plane
``/device:TPU:<n>`` per chip. Its line ``XLA Modules`` holds one event per
executed program (``jit__step_with_stats(<fingerprint>)``), and ``XLA Ops``
one per executed HLO instruction, *named by the instruction's whole text*
(``%fusion.12 = f32[...] fusion(...), kind=kLoop, ...``) with no
``jax.named_scope`` path anywhere: a conditional or a while loop is an
event that spans the events of its body. So scopes come from the compiled
programs' own text (``op_name`` metadata, by instruction name), which
:func:`annotate` writes into the events, and every sum over events is a
union of intervals. Host threads are lines of the plane ``/host:CPU``; the
``TraceAnnotation`` spans are on the line ``python3``, in the device's
time base.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
COLLECTIVE = re.compile(
    r'^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all'
    r'|collective-broadcast)(-start|-done)?$'
)
# instructions whose event spans the events of a computation they call
PARENTS = ('conditional', 'while', 'call')
_INSTRUCTION = re.compile(r'^%?(?P<name>[^\s=]+) = ')
_OPCODE = re.compile(r'[\s)]([a-z][\w\-]*)\(')
_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?(?P<name>[^\s=]+) = .*?op_name="(?P<op>[^"]*)"'
)
_IDENT = set(
    'abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.'
)


# ------------------------------------------------------------------ loading


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(logdir, '**', '*.xplane.pb'), recursive=True
    ))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {logdir}')
    return found[-1]


def from_xplane(path: str, keep_lines=None) -> dict:
    """The neutral trace of one ``.xplane.pb``. ``keep_lines(plane, line)
    -> bool`` drops lines nobody reads (a trace has millions of events)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_lines is not None and not keep_lines(plane.name, line.name):
                continue
            events = []
            for e in line.events:
                stats = {}
                for k, v in e.stats:
                    if isinstance(v, (str, int, float)):
                        stats[k] = v
                events.append({
                    'name': e.name, 'start_ns': float(e.start_ns),
                    'duration_ns': float(e.duration_ns), 'stats': stats,
                })
            lines.append({'name': line.name, 'events': events})
        planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def wanted_line(plane: str, line: str) -> bool:
    """The lines the reductions below read."""
    if DEVICE_PLANE.match(plane):
        return line in (OPS_LINE, MODULES_LINE)
    return plane == HOST_PLANE


def instruction(text: str) -> tuple[str, str]:
    """``(name, opcode)`` of an event named by an HLO instruction's text:
    ``%fusion.12 = f32[8]{0:T(256)} fusion(...)`` -> ``('fusion.12',
    'fusion')``. A name that is no instruction text is its own name and
    has the opcode of its family (``all-reduce.3`` -> ``all-reduce``)."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return text, _family(text)
    rest = text[m.end() - 1:]
    op = _OPCODE.search(rest)
    return m.group('name'), op.group(1) if op else _family(m.group('name'))


def op_names(hlo_text: str) -> dict:
    """{instruction name: ``op_name`` metadata} of one compiled program's
    text (``jax.stages.Compiled.as_text()``): where the program's
    ``jax.named_scope`` paths are."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m is not None:
            out[m.group('name')] = m.group('op')
    return out


def annotate(trace: dict, programs: dict) -> int:
    """Write each device operation's ``op_name`` into its statistics.
    ``programs``: {module name as the trace's ``XLA Modules`` events have
    it before the fingerprint, e.g. ``jit__step_with_stats``: {instruction
    name: op_name}}. An operation belongs to the module event that holds
    its start. Returns how many operations got a name."""
    named = 0
    for plane in device_planes(trace):
        modules = sorted(
            (e for l in plane['lines'] if l['name'] == MODULES_LINE
             for e in l['events']),
            key=lambda e: e['start_ns'],
        )
        starts = [e['start_ns'] for e in modules]
        for e in ops(plane):
            i = bisect.bisect_right(starts, e['start_ns']) - 1
            if i < 0:
                continue
            mod = modules[i]
            if e['start_ns'] >= mod['start_ns'] + mod['duration_ns']:
                continue
            table = programs.get(mod['name'].split('(')[0])
            if table is None:
                continue
            op = table.get(instruction(e['name'])[0])
            if op is not None:
                e['stats']['op_name'] = op
                named += 1
    return named


# ---------------------------------------------------------------- selection


def device_planes(trace: dict) -> list:
    return sorted(
        (p for p in trace['planes'] if DEVICE_PLANE.match(p['name'])),
        key=lambda p: int(p['name'].rsplit(':', 1)[1]),
    )


def ops(plane: dict, window=None) -> list:
    """The device's executed operations, clipped to ``window``
    ``(t0_ns, t1_ns)`` when given, sorted by start."""
    out = []
    for line in plane['lines']:
        if line['name'] != OPS_LINE:
            continue
        for e in line['events']:
            if e['duration_ns'] <= 0:
                continue
            if window is not None:
                end = e['start_ns'] + e['duration_ns']
                if end <= window[0] or e['start_ns'] >= window[1]:
                    continue
            out.append(e)
    return sorted(out, key=lambda e: e['start_ns'])


def host_spans(trace: dict, names) -> list:
    """Host ``TraceAnnotation`` events named in ``names``, by start."""
    out = []
    for plane in trace['planes']:
        if plane['name'] != HOST_PLANE:
            continue
        for line in plane['lines']:
            out += [e for e in line['events'] if e['name'] in names]
    return sorted(out, key=lambda e: e['start_ns'])


# --------------------------------------------------------------- arithmetic


def _intervals(events, window=None) -> list:
    spans = []
    for e in events:
        lo, hi = e['start_ns'], e['start_ns'] + e['duration_ns']
        if window is not None:
            lo, hi = max(lo, window[0]), min(hi, window[1])
        if hi > lo:
            spans.append((lo, hi))
    return spans


def merge(spans: list) -> list:
    """Union of intervals as disjoint sorted intervals."""
    out: list = []
    for lo, hi in sorted(spans):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def length(spans: list) -> float:
    return sum(hi - lo for lo, hi in spans)


def subtract(spans: list, holes: list) -> list:
    """The part of disjoint sorted ``spans`` outside disjoint sorted
    ``holes``."""
    out = []
    for lo, hi in spans:
        cur = lo
        for hlo, hhi in holes:
            if hhi <= cur or hlo >= hi:
                continue
            if hlo > cur:
                out.append((cur, hlo))
            cur = max(cur, hhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def busy_ns(plane: dict, window) -> float:
    """Nanoseconds of ``window`` in which some operation ran on the
    device: the union of its operations' intervals."""
    return length(merge(_intervals(ops(plane, window), window)))


def match_scope(text: str, scopes) -> str | None:
    """The deepest (latest-starting, then longest) of ``scopes`` occurring
    in ``text`` on an identifier boundary: ``dist_kfac.step`` holds the
    letters of ``kfac.step`` and is not it."""
    best, best_scope = None, None
    for scope in scopes:
        start = 0
        while True:
            pos = text.find(scope, start)
            if pos < 0:
                break
            start = pos + 1
            if pos > 0 and text[pos - 1] in _IDENT:
                continue
            end = pos + len(scope)
            if end < len(text) and text[end] in _IDENT:
                continue
            key = (pos, len(scope))
            if best is None or key > best:
                best, best_scope = key, scope
    return best_scope


def event_scope(event: dict, scopes) -> str | None:
    found = match_scope(event['name'], scopes)
    if found is not None:
        return found
    for value in event['stats'].values():
        if isinstance(value, str):
            found = match_scope(value, scopes)
            if found is not None:
                return found
    return None


def scope_ns(plane: dict, window, scopes) -> dict:
    """{scope: device nanoseconds of the operations under it} in
    ``window``, each operation under its deepest scope."""
    spans = collections.defaultdict(list)
    # an instruction runs every step and a loop's body many times a step:
    # the texts are matched once each (Qwen's 13 steps are 615 k events,
    # and a reader's pass over them took 5 s of a run that has 360)
    seen: dict = {}
    for e in ops(plane, window):
        key = (e['name'], *(
            v for v in e['stats'].values() if isinstance(v, str)
        ))
        if key not in seen:
            seen[key] = event_scope(e, scopes)
        scope = seen[key]
        if scope is not None:
            spans[scope] += _intervals([e], window)
    # a loop under a scope spans its body's operations under it: a union
    return {k: length(merge(v)) for k, v in spans.items()}


def module_runs(plane: dict, window=None) -> list:
    """The device's executed programs (the line ``XLA Modules``: one
    event a run), clipped to ``window`` when given, sorted by start."""
    out = []
    for line in plane['lines']:
        if line['name'] != MODULES_LINE:
            continue
        for e in line['events']:
            end = e['start_ns'] + e['duration_ns']
            if e['duration_ns'] > 0 and (
                window is None or (end > window[0] and e['start_ns'] < window[1])
            ):
                out.append(e)
    return sorted(out, key=lambda e: e['start_ns'])


def is_collective(event: dict) -> bool:
    return COLLECTIVE.match(instruction(event['name'])[1]) is not None


def collective_ns(plane: dict, window) -> tuple[float, float]:
    """``(total, exposed)`` nanoseconds of collective operations in
    ``window``; exposed is the part during which nothing else ran on the
    device."""
    events = ops(plane, window)
    coll = merge(_intervals([e for e in events if is_collective(e)], window))
    rest = merge(_intervals(
        [e for e in events if not is_collective(e)], window
    ))
    return length(coll), length(subtract(coll, rest))


def is_pallas(event: dict) -> bool:
    """A Mosaic kernel: XLA's custom call to ``tpu_custom_call``."""
    texts = [event['name']] + [
        v for v in event['stats'].values() if isinstance(v, str)
    ]
    return any(
        'tpu_custom_call' in t or 'pallas_call' in t or 'mosaic' in t.lower()
        for t in texts
    )


def pallas_share(plane: dict, window) -> float:
    """Share (%) of the device's busy time spent in Mosaic kernels."""
    events = ops(plane, window)
    busy = length(merge(_intervals(events, window)))
    mosaic = length(merge(_intervals(
        [e for e in events if is_pallas(e)], window
    )))
    return 100.0 * mosaic / busy if busy else 0.0


def _family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: instruction numbers change with every
    compile, families do not."""
    return re.sub(r'[.\d]+$', '', name) or name


def top_ops(plane: dict, window, n: int = 10) -> list:
    """The ``n`` operation families with most device time:
    ``[[name, seconds], ...]``. A family is an instruction's category
    where the trace gives one, else its opcode, and for a fusion or a
    custom call (a Mosaic kernel) its name without the number."""
    total = collections.defaultdict(float)
    for e in ops(plane, window):
        name, opcode = instruction(e['name'])
        if opcode in PARENTS:
            continue  # its body's operations are events of their own
        key = e['stats'].get('hlo_category') or (
            # a Mosaic kernel or a fusion is told by its name's family
            _family(name) if opcode in ('custom-call', 'fusion') else opcode
        )
        total[str(key)] += length(_intervals([e], window))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(plane: dict, window, spans: list, n: int = 10) -> list:
    """Idle time of the device in ``window`` by what the host was doing:
    each gap between operations is split over the host ``spans`` that
    overlap it (the innermost where they nest) and ``host_other`` for the
    rest; ``[[name, seconds], ...]``, longest first."""
    gaps = subtract([window], merge(_intervals(ops(plane, window), window)))
    by_name = collections.defaultdict(float)
    # a gap is split over the spans that overlap it, and over no others:
    # gaps come sorted and disjoint, so the spans are swept beside them
    # (every span tried on every gap is gaps x spans pieces of work, and
    # Qwen's 13 traced steps are 616 k operations and 78 spans, in a run
    # that has 360 s)
    by_start = sorted(enumerate(spans), key=lambda p: p[1]['start_ns'])
    active: list = []
    upcoming = 0
    for gap in gaps:
        while (upcoming < len(by_start)
               and by_start[upcoming][1]['start_ns'] < gap[1]):
            active.append(by_start[upcoming])
            upcoming += 1
        active = [
            p for p in active if p[1]['start_ns'] + p[1]['duration_ns'] > gap[0]
        ]
        left = [gap]
        # innermost first: shorter spans claim their part of a gap before
        # the spans around them (of two as long, the first as given)
        for _, s in sorted(active, key=lambda p: (p[1]['duration_ns'], p[0])):
            if not left:
                break
            span = [(s['start_ns'], s['start_ns'] + s['duration_ns'])]
            inside = length(left) - length(subtract(left, span))
            if inside > 0:
                by_name[s['name']] += inside
                left = subtract(left, span)
        by_name['host_other'] += length(left)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked if v > 0]
