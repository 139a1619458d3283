"""The trace reducer on small recorded traces: a hand-made one whose every
number is worked out below, and one cut from a v5e chip run (PR 23)."""

import json
import os

import pytest

from benchmark import harness
from benchmark import trace_reduce as tr

# the scopes the rows of benchmark/layer_metrics/*.json name
SCOPES = harness.trace_scopes()

DATA = os.path.join(os.path.dirname(__file__), 'data')


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope='module')
def trace():
    return _load('synthetic_trace.json')


def test_device_planes_in_order_and_ops_line_only(trace):
    planes = tr.device_planes(trace)
    assert [p['name'] for p in planes] == ['/device:TPU:0', '/device:TPU:1']
    names = [e['name'] for e in tr.ops(planes[0])]
    # the module line's event and the zero-length copy are not operations
    assert names == [
        'fusion.1', 'fusion.2', 'all-reduce.3', 'fusion.4', 'custom-call.5'
    ]


def test_busy_is_the_union_of_intervals(trace):
    plane = tr.device_planes(trace)[0]
    # [100,300) u [250,350) u [300,500) u [400,450) = [100,500); [700,800)
    assert tr.busy_ns(plane, (0, 1000)) == 500
    # clipped to a window that cuts both runs
    assert tr.busy_ns(plane, (200, 750)) == 300 + 50
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]


def test_scope_on_an_identifier_boundary(trace):
    assert tr.match_scope('a/dist_kfac.step/dist_kfac.precondition/mul',
                          SCOPES) == 'dist_kfac.precondition'
    # dist_kfac.* holds the letters of kfac.* and is not it
    assert tr.match_scope('dist_kfac.update_factors/dot', SCOPES) == (
        'dist_kfac.update_factors')
    assert tr.match_scope('kfac.update_factors/dot', SCOPES) == (
        'kfac.update_factors')
    assert tr.match_scope('not_dist_kfac.precondition_x/y', SCOPES) is None
    assert tr.match_scope('kfac.preconditioner', SCOPES) is None
    p0, p1 = tr.device_planes(trace)
    got = tr.scope_ns(p0, (0, 1000), SCOPES)
    assert got == {
        # fusion.2 [250,350) u all-reduce.3 [300,500): a union, not a sum
        'dist_kfac.update_factors': 250.0,
        'dist_kfac.precondition': 50.0,
        'dist_kfac.update_inverses': 100.0,
    }
    assert tr.scope_ns(p1, (0, 1000), SCOPES) == {}


def test_the_scopes_are_the_metric_rows_own():
    assert set(SCOPES) == {
        'kfac.update_factors', 'kfac.update_inverses', 'kfac.precondition',
        'dist_kfac.update_factors', 'dist_kfac.update_inverses',
        'dist_kfac.precondition',
    }


def test_collective_total_and_exposed(trace):
    plane = tr.device_planes(trace)[0]
    total, exposed = tr.collective_ns(plane, (0, 1000))
    assert total == 200
    # compute covers [100,350) and [400,450): exposed [350,400) u [450,500)
    assert exposed == 100
    assert tr.collective_ns(tr.device_planes(trace)[1], (0, 1000)) == (0, 0)
    for name in ('all-gather-start.12', 'reduce-scatter', 'all-to-all.1',
                 'collective-permute-done.4'):
        assert tr.is_collective({'name': name})
    for name in ('fusion.7', 'all-reduce-fusion', 'reduce.3'):
        assert not tr.is_collective({'name': name})


def test_pallas_share_top_ops_and_idle_gaps(trace):
    plane = tr.device_planes(trace)[0]
    assert tr.pallas_share(plane, (0, 1000)) == pytest.approx(100 * 100 / 500)
    top = dict(tr.top_ops(plane, (0, 1000)))
    assert top['convolution'] == pytest.approx(300e-9)
    assert top['all-reduce'] == pytest.approx(200e-9)
    assert top['custom-call'] == pytest.approx(100e-9)
    spans = tr.host_spans(
        trace, ('bench.input', 'bench.dispatch', 'bench.sync')
    )
    assert [s['name'] for s in spans] == [
        'bench.input', 'bench.dispatch', 'bench.sync', 'bench.input'
    ]
    gaps = dict(tr.idle_gaps(plane, (0, 1000), spans))
    # idle: [0,100) input; [500,700) sync; [800,1000): 150 input, 50 nothing
    assert gaps == pytest.approx({
        'bench.input': 250e-9, 'bench.sync': 200e-9, 'host_other': 50e-9,
    })
    assert sum(gaps.values()) == pytest.approx(
        (1000 - tr.busy_ns(plane, (0, 1000))) * 1e-9
    )


HLO = """HloModule jit__step_with_stats, is_scheduled=true, entry_computation_layout={()}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %inner.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(_step_with_stats)/dist_kfac.precondition/mul"}
}

ENTRY %main {
  %fusion.7 = f32[8]{0:T(256)} fusion(f32[8]{0:T(256)} %p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step_with_stats)/jit(main)/dist_kfac.step/dist_kfac.update_factors/dot_general" source_file="x.py" source_line=3}
  %_ns_xupdate_kernel.2 = f32[256,256]{1,0:T(8,128)S(1)} custom-call(f32[256,256]{1,0} %a, f32[256,256]{1,0} %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step_with_stats)/dist_kfac.step/cond/branch_1_fun/dist_kfac.update_inverses/while/body/fused_ns_step/pallas_call"}
  ROOT %cond.3 = (f32[8]{0}, f32[]) conditional(s32[] %i, (f32[8]{0}) %t), branch_computations={%b0, %b1}, metadata={op_name="jit(_step_with_stats)/dist_kfac.step/cond"}
  %bare.4 = f32[] constant(0)
}
"""


def test_instruction_name_and_opcode_from_the_events_text():
    text = ('%fusion.3326 = f32[2176,2176]{1,0:T(8,128)S(1)} fusion(f32[1,2176,'
            '2176]{2,1,0:T(8,128)S(1)} %get-tuple-element.14187, pred[]{:T(512)}'
            ' %bitcast.2548), kind=kLoop, calls=%fused_computation.650')
    assert tr.instruction(text) == ('fusion.3326', 'fusion')
    cond = ('%cond.132 = (f32[1,1024,1024]{2,1,0:T(8,128)}, /*index=5*/f32[]{:T(128)})'
            ' conditional(s32[]{:T(128)} %convert_element_type.1127, (f32[1]) %x)')
    assert tr.instruction(cond) == ('cond.132', 'conditional')
    kern = ('%_ns_xupdate_kernel.102 = f32[2176,2176]{1,0:T(8,128)S(1)} custom-call('
            'f32[2176,2176]{1,0:T(8,128)S(1)} %fusion.3326), custom_call_target='
            '"tpu_custom_call"')
    assert tr.instruction(kern) == ('_ns_xupdate_kernel.102', 'custom-call')
    assert tr.instruction('%all-reduce-start.4 = f32[8]{0} all-reduce-start(f32[8]{0} %x)')[1] == 'all-reduce-start'
    assert tr.instruction('all-reduce.3') == ('all-reduce.3', 'all-reduce')
    assert tr.is_pallas({'name': kern, 'stats': {}})
    assert not tr.is_pallas({'name': text, 'stats': {}})


def test_scopes_come_from_the_programs_text():
    names = tr.op_names(HLO)
    assert set(names) == {'inner.1', 'fusion.7', '_ns_xupdate_kernel.2', 'cond.3'}
    assert tr.match_scope(names['_ns_xupdate_kernel.2'], SCOPES) == (
        'dist_kfac.update_inverses')
    trace = {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': [
            {'name': 'jit__step_no_stats(77)', 'start_ns': 0,
             'duration_ns': 100, 'stats': {}},
            {'name': 'jit__step_with_stats(123)', 'start_ns': 200,
             'duration_ns': 500, 'stats': {}}]},
        {'name': 'XLA Ops', 'events': [
            # same instruction name in another program: not this table's
            {'name': '%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)',
             'start_ns': 10, 'duration_ns': 50, 'stats': {}},
            {'name': '%cond.3 = (f32[8]{0}) conditional(s32[] %i)',
             'start_ns': 210, 'duration_ns': 400, 'stats': {}},
            {'name': '%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)',
             'start_ns': 220, 'duration_ns': 50, 'stats': {}},
            {'name': '%_ns_xupdate_kernel.2 = f32[256,256]{1,0} custom-call('
                     'f32[256,256]{1,0} %a), custom_call_target="tpu_custom_call"',
             'start_ns': 300, 'duration_ns': 100, 'stats': {}},
            {'name': '%_ns_xupdate_kernel.2 = f32[256,256]{1,0} custom-call('
                     'f32[256,256]{1,0} %a), custom_call_target="tpu_custom_call"',
             'start_ns': 450, 'duration_ns': 100, 'stats': {}}]}]}]}
    named = tr.annotate(trace, {'jit__step_with_stats': names})
    assert named == 4
    plane = tr.device_planes(trace)[0]
    assert tr.scope_ns(plane, (0, 1000), SCOPES) == {
        'dist_kfac.update_factors': 50, 'dist_kfac.update_inverses': 200,
    }
    # the conditional spans its branch's kernels: it is not an operation
    # family of its own, and busy time does not count it twice
    assert dict(tr.top_ops(plane, (0, 1000))) == pytest.approx({
        '_ns_xupdate_kernel': 200e-9, 'fusion': 100e-9,
    })
    assert tr.busy_ns(plane, (0, 1000)) == 50 + 400
    assert tr.pallas_share(plane, (0, 1000)) == pytest.approx(100 * 200 / 450)
    assert [e['name'] for e in tr.module_runs(plane)] == [
        'jit__step_no_stats(77)', 'jit__step_with_stats(123)'
    ]
    assert len(tr.module_runs(plane, (150, 1000))) == 1


def test_a_slice_of_a_v5e_trace():
    """1.26 ms of the first traced plain step of `resnet50.kfac-10-100` on
    one v5e chip (my chip run, PR 23), as `trace_look.py --cut` wrote it:
    events are named by whole HLO instructions and carry no scope."""
    trace = _load('v5e_cut.json')
    (plane,) = tr.device_planes(trace)
    events = tr.ops(plane)
    assert len(events) == 175
    opcodes = {tr.instruction(e['name'])[1] for e in events}
    assert opcodes == {
        'copy', 'copy-start', 'copy-done', 'fusion', 'async-start',
        'async-done', 'convert', 'custom-call',
    }
    assert all(e['name'].startswith('%') for e in events)
    assert all(tr.event_scope(e, SCOPES) is None for e in events)
    window = (events[0]['start_ns'],
              max(e['start_ns'] + e['duration_ns'] for e in events))
    assert window[1] - window[0] == 1256837.0
    assert tr.busy_ns(plane, window) == 1255506.0
    top = tr.top_ops(plane, window)
    assert top[0][0] == 'convert_reduce_fusion'
    assert top[0][1] == pytest.approx(508.275e-6)
    (module,) = [e for l in plane['lines'] if l['name'] == 'XLA Modules'
                 for e in l['events']]
    assert module['name'].startswith('jit__step_no_stats(')
    # the host's spans are on the device's clock: the step was dispatched
    # 9 ms before its first operation ran
    dispatch = tr.host_spans(trace, ('bench.dispatch',))[0]
    assert 8e6 < events[0]['start_ns'] - dispatch['start_ns'] < 10e6
    named = tr.annotate(trace, {'jit__step_no_stats': {
        tr.instruction(events[-1]['name'])[0]:
            'jit(_step_no_stats)/dist_kfac.step/dist_kfac.precondition/mul',
    }})
    assert named >= 1
    assert tr.scope_ns(plane, window, SCOPES)['dist_kfac.precondition'] > 0


def _step(start, ops):
    """A program run from ``start`` and its operations ``(name, at, ns)``."""
    end = max(at + ns for _, at, ns in ops)
    module = {'name': 'jit__step(1)', 'start_ns': start,
              'duration_ns': end - start, 'stats': {}}
    return module, [
        {'name': f'%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %x)',
         'start_ns': at, 'duration_ns': ns, 'stats': {}}
        for name, at, ns in ops
    ]


def test_capture_readers_on_a_stretch_of_steps():
    """Three steps: plain, capture, plain. The capture step runs two
    covariance products and a copy the plain steps do not."""
    import types

    steps = [
        _step(0, [('fusion.1', 0, 100)]),
        _step(200, [('fusion.1', 200, 100), ('fusion.3', 300, 40),
                    ('copy.9', 340, 15), ('fusion.4', 360, 60)]),
        _step(500, [('fusion.1', 500, 110)]),
    ]
    plane = {'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': [m for m, _ in steps]},
        {'name': 'XLA Ops', 'events': [e for _, ops in steps for e in ops]},
    ]}
    rows = [{'kind': k} for k in ('plain', 'capture', 'plain')]
    ctx = types.SimpleNamespace(
        trace={'planes': [plane]}, windows={plane['name']: (0, 610)},
        traced_rows=rows,
        count=lambda kind: sum(
            r['kind'] in {'capture': ('capture', 'refresh')}.get(kind, (kind,))
            for r in rows
        ),
    )
    # no operation here carries a capture scope: the products' row finds
    # nothing to read (a product has no name of its own to go by)
    assert harness.read_layer_metric('dev_ms.sym_cov', ctx) is None
    # busy 100 + 40 + 15 + 60 in the capture step, median 105 in a plain one
    assert harness.read_layer_metric(
        'capture_dev_extra_ms', ctx
    ) == pytest.approx((215 - 105) * 1e-6)
    # nothing under an engine scope here: nothing to read
    assert harness.read_layer_metric('dev_ms.update_factors', ctx) is None
    ctx.traced_rows = rows[:2]  # a program the rows do not know ran too
    assert harness.read_layer_metric('capture_dev_extra_ms', ctx) is None


def _idle_gaps_every_span_on_every_gap(plane, window, spans):
    """``idle_gaps`` as it was until PR 31: each span tried on each gap."""
    gaps = tr.subtract(
        [window], tr.merge(tr._intervals(tr.ops(plane, window), window))
    )
    by_name = {}
    ordered = sorted(spans, key=lambda e: e['duration_ns'])
    for gap in gaps:
        left = [gap]
        for s in ordered:
            if not left:
                break
            span = [(s['start_ns'], s['start_ns'] + s['duration_ns'])]
            inside = tr.length(left) - tr.length(tr.subtract(left, span))
            if inside > 0:
                by_name[s['name']] = by_name.get(s['name'], 0.0) + inside
                left = tr.subtract(left, span)
        by_name['host_other'] = by_name.get('host_other', 0.0) + tr.length(left)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return [[k, v / 1e9] for k, v in ranked if v > 0]


@pytest.mark.parametrize('seed', range(8))
def test_idle_gaps_swept_reads_what_every_span_on_every_gap_read(seed):
    """Operations with gaps and overlaps, host spans that nest, overlap,
    repeat a length and come in no order: the sweep gives the same split,
    to the bit."""
    import random

    rng = random.Random(seed)
    events, t = [], 0
    for i in range(300):
        t += rng.choice((0, 0, 3, 40))
        d = rng.randint(1, 60)
        events.append({
            'name': f'%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
            'start_ns': t - rng.choice((0, 0, 5)), 'duration_ns': d,
            'stats': {},
        })
        t += d
    plane = {'name': '/device:TPU:0', 'lines': [
        {'name': tr.OPS_LINE, 'events': events},
    ]}
    spans = [
        {'name': rng.choice('abcde'), 'start_ns': rng.randint(-50, t),
         'duration_ns': rng.choice((0, 7, 7, 90, 90, 400, 2500))}
        for _ in range(60)
    ]
    window = (10, t - 10)
    want = _idle_gaps_every_span_on_every_gap(plane, window, spans)
    assert want and tr.idle_gaps(plane, window, spans, n=99) == want
