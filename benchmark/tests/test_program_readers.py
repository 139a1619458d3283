"""The readers of what the program reports on itself (PR 25, and the
covariance products' row pointed at their scopes in PR 31), on a
hand-made neutral trace and a stub state whose every number is worked out
below. Three traced steps (plain, capture, refresh) on one device; times in
nanoseconds.

    step 0, plain    bench.input [0,200)      bench.dispatch [200,1050)
                     pre_step [210,260) launch [300,900) post_step [910,950)
                     bench.sync [1050,1150)   device busy [1000,1100)
    step 1, capture  bench.input [1150,1300)  bench.dispatch [1300,1700)
                     pre_step [1310,1330) launch [1400,1600)
                     post_step [1610,1640)
                     bench.sync [1700,2150)   device busy [1500,2100)
    step 2, refresh  bench.input [2150,2250)  bench.dispatch [2250,2500)
                     pre_step [2260,2280) launch [2300,2450)
                     post_step [2460,2490)
                     bench.sync [2500,3000)   device busy [2400,2900)
"""

import dataclasses
import types

import numpy as np
import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program

WINDOW = (0, 3000)
KINDS = ('plain', 'capture', 'refresh')
PATH = 'jit(_step_with_stats)/jit(main)/'


def _op(name, start, ns, op_name):
    return {
        'name': f'%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
        'start_ns': start, 'duration_ns': ns, 'stats': {'op_name': op_name},
    }


def _span(name, start, end, step=None):
    stats = {} if step is None else {'step': step}
    return {'name': name, 'start_ns': start, 'duration_ns': end - start,
            'stats': stats}


def _device_plane():
    ops = [
        _op('fusion.1', 1000, 100, PATH + 'dist_kfac.step/dist_kfac.precondition/mul'),
        # step 1: precondition, the A side (im2col then the product), the G
        # side (a product and a fusion that overlap: a union), the EMA
        _op('fusion.1', 1500, 100, PATH + 'dist_kfac.step/dist_kfac.precondition/mul'),
        _op('convolution.2', 1600, 100,
            PATH + 'jvp(Net)/kfac.capture_a/patches/conv_general_dilated'),
        _op('fusion.3', 1700, 100,
            PATH + 'jvp(Net)/kfac.capture_a/dot_general'),
        _op('fusion.4', 1800, 150,
            PATH + 'transpose(jvp(Net))/kfac.capture_g/dot_general'),
        _op('fusion.5', 1900, 100,
            PATH + 'transpose(jvp(Net))/kfac.capture_g/div'),
        _op('fusion.6', 2000, 100,
            PATH + 'dist_kfac.step/dist_kfac.update_factors/add'),
        # step 2: both sides again, then the refresh
        _op('fusion.7', 2400, 100, PATH + 'jvp(Net)/kfac.capture_a/dot_general'),
        _op('fusion.8', 2500, 100,
            PATH + 'transpose(jvp(Net))/kfac.capture_g/dot_general'),
        _op('while.9', 2600, 300,
            PATH + 'dist_kfac.step/dist_kfac.update_inverses/while'),
    ]
    modules = [
        {'name': 'jit__step_no_stats(1)', 'start_ns': 1000,
         'duration_ns': 100, 'stats': {}},
        {'name': 'jit__step_with_stats(2)', 'start_ns': 1500,
         'duration_ns': 600, 'stats': {}},
        {'name': 'jit__step_with_stats(2)', 'start_ns': 2400,
         'duration_ns': 500, 'stats': {}},
    ]
    return {'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': modules},
        {'name': 'XLA Ops', 'events': ops},
    ]}


def _host_plane(program_spans=True):
    bench = [
        _span('bench.input', 0, 200), _span('bench.dispatch', 200, 1050),
        _span('bench.sync', 1050, 1150),
        _span('bench.input', 1150, 1300), _span('bench.dispatch', 1300, 1700),
        _span('bench.sync', 1700, 2150),
        _span('bench.input', 2150, 2250), _span('bench.dispatch', 2250, 2500),
        _span('bench.sync', 2500, 3000),
    ]
    program = [
        _span('kfac.host.pre_step', 210, 260, 189),
        _span('kfac.host.launch', 300, 900, 189),
        _span('kfac.host.post_step', 910, 950, 189),
        _span('kfac.host.pre_step', 1310, 1330, 190),
        _span('kfac.host.launch', 1400, 1600, 190),
        _span('kfac.host.post_step', 1610, 1640, 190),
        _span('kfac.host.pre_step', 2260, 2280, 191),
        _span('kfac.host.launch', 2300, 2450, 191),
        _span('kfac.host.post_step', 2460, 2490, 191),
    ]
    return {'name': '/host:CPU', 'lines': [
        {'name': 'python3', 'events': bench + (program if program_spans else [])},
    ]}


def _refresh():
    """Three buckets: two slots and a padding, one slot and a padding, one
    slot. Columns: iterations, residual, warm, restarted."""
    from kfac_tpu.parallel import kaisa

    return kaisa.RefreshState(
        buckets=(('a', 'd8', 3, 2), ('a', 'd16', 2, 1), ('g', 'd8', 1, 1)),
        solved=np.array([
            [7, 5e-7, 1, 0],
            [9, 8e-7, 1, 1],   # accepted, then restarted cold
            [0, 0.0, 1, 0],    # identity padding: in no total
            [4, 3e-7, 1, 0],
            [0, 0.0, 1, 0],
            [12, 9e-7, 0, 0],  # its warm start was refused up front
        ], np.float32),
    )


def _engine_with_report():
    from kfac_tpu.parallel import kaisa

    def refresh_report(kstate):
        return {'buckets': {}, 'totals': {
            k.split('/', 1)[1]: v
            for k, v in kaisa.refresh_totals(kstate.refresh).items()
        }}

    return types.SimpleNamespace(refresh_report=refresh_report)


def _ctx(plane=None, host=None, engine=None):
    plane = plane or _device_plane()
    rows = [{'kind': k} for k in KINDS]
    return types.SimpleNamespace(
        trace={'planes': [plane, host or _host_plane()]},
        windows={plane['name']: WINDOW},
        traced_rows=rows,
        run=types.SimpleNamespace(
            trainer=types.SimpleNamespace(
                kfac=engine or _engine_with_report()
            ),
            state=types.SimpleNamespace(
                kfac_state=types.SimpleNamespace(refresh=_refresh())
            ),
        ),
        count=lambda kind: len(rows) if kind is None else sum(
            r['kind'] in {'capture': ('capture', 'refresh')}.get(kind, (kind,))
            for r in rows
        ),
    )


EXPECTED = {
    # A side: [1600,1800) and [2400,2500), two capturing steps
    'dev_ms.capture_a': (200 + 100) / 2 * 1e-6,
    # G side: [1800,1950) u [1900,2000) is 200, and [2500,2600)
    'dev_ms.capture_g': (200 + 100) / 2 * 1e-6,
    # buckets run one after another, each until its slowest slot: 9 + 4 + 12
    'ns_trips_refresh': 25.0,
    'ns_restarts_refresh': 1.0,
    # 3 of 4 live slots were accepted warm, 1 of them restarted: 2 of 4 kept
    'ns_warm_share': 50.0,
    'ns_worst_residual': 9e-7,
    'host_ms.pre_step': 20e-6,    # median of 50, 20, 20
    'host_ms.launch': 200e-6,     # median of 600, 200, 150
    'host_ms.post_step': 30e-6,   # median of 40, 30, 30
    # the device ran nothing in [300,900), [1400,1500), [2300,2400)
    'idle_ms.launch': (600 + 100 + 100) / 3 * 1e-6,
    'idle_ms.pre_step': (50 + 20 + 20) / 3 * 1e-6,
    # the im2col of step 1, [1600,1700), over the two capturing steps;
    # capture_a above holds it too
    'dev_ms.capture_patches': 100 / 2 * 1e-6,
    # both sides' products without the patch rows: 100 + 100 and 200 + 100
    'dev_ms.sym_cov': (200 + 300) / 2 * 1e-6,
}


def test_benchmark_json_lists_the_readers():
    """By name, wherever a row stands: later PRs append rows and cells.
    Which cells a row lists is ``BENCHMARK.json``'s to say (the cells in
    which its reader finds something to read); held here is that they are
    cells, and that only a model with convolutions has patch rows."""
    bench = harness.load_cell('resnet50.kfac-10-100')['bench']
    rows = {m['name']: m for m in bench['per_layer']}
    cells = [w['name'] for w in bench['workloads']]
    assert set(EXPECTED) <= set(rows)
    for name in EXPECTED:
        listed = rows[name].get('workloads', cells)
        assert listed and set(listed) <= set(cells), name
        assert rows[name]['moves'] in {e['name'] for e in bench['end_to_end']}
        assert callable(harness.layer_reader(name).read)
    patches = rows['dev_ms.capture_patches']['workloads']
    assert 'resnet50.kfac-10-100' in patches
    for cell in patches:  # GPT-2 has no convolution, so no patches
        assert harness.load_cell(cell)['config']['kind'] == 'vision'


@pytest.mark.parametrize('name', list(EXPECTED))
def test_reader_on_the_hand_made_trace(name):
    got = harness.read_layer_metric(name, _ctx())
    assert got == pytest.approx(EXPECTED[name], rel=1e-6)


def test_an_idle_gap_goes_to_the_innermost_span():
    """Step 0's gap [200,1000) lies inside ``bench.dispatch``; the
    program's spans nest inside that and claim their parts first."""
    ctx = _ctx()
    spans = tr.host_spans(ctx.trace, _program.BENCH_SPANS + (
        _program.PRE_STEP, _program.LAUNCH, _program.POST_STEP))
    gaps = dict(tr.idle_gaps(ctx.trace['planes'][0], WINDOW, spans, n=20))
    assert gaps == pytest.approx({
        'kfac.host.launch': 800e-9,
        'kfac.host.pre_step': 90e-9,
        'kfac.host.post_step': 40e-9,           # [910,950); later ones ran busy
        'bench.dispatch': (110 + 80 + 30) * 1e-9,  # what no inner span covers
        'bench.input': (200 + 150 + 100) * 1e-9,
        'bench.sync': (50 + 50 + 100) * 1e-9,
    })
    busy = tr.busy_ns(ctx.trace['planes'][0], WINDOW)
    assert sum(gaps.values()) == pytest.approx((3000 - busy) * 1e-9)
    # without the program's spans the whole of it is bench.dispatch's, as
    # the ledger's idle_gaps had it before PR 25
    outer = dict(tr.idle_gaps(
        ctx.trace['planes'][0], WINDOW,
        tr.host_spans(ctx.trace, _program.BENCH_SPANS),
    ))
    assert outer['bench.dispatch'] == pytest.approx(
        (800 + 90 + 40 + 220) * 1e-9
    )


def test_the_rows_still_read_what_they_read():
    """The new scopes are not among the rows' scopes, so an operation
    under an engine scope is attributed as before."""
    assert not set(harness.trace_scopes()) & {
        _program.CAPTURE_A, _program.CAPTURE_G
    }
    ctx = _ctx()
    assert harness.read_layer_metric(
        'dev_ms.update_factors', ctx
    ) == pytest.approx(100 / 2 * 1e-6)
    assert harness.read_layer_metric(
        'dev_ms.update_inverses', ctx
    ) == pytest.approx(300e-6)
    assert harness.read_layer_metric(
        'dev_ms.precondition', ctx
    ) == pytest.approx(200 / 3 * 1e-6)


def _parent_ctx():
    """A program without any of it (the parent commit): operations carry
    no capture scope, the host line no program span, the engine no
    ``refresh_report``."""
    plane = _device_plane()
    for line in plane['lines']:
        for e in line['events']:
            if 'kfac.capture' in e['stats'].get('op_name', ''):
                e['stats'] = {}
    return _ctx(
        plane=plane, host=_host_plane(program_spans=False),
        engine=types.SimpleNamespace(),
    )


@pytest.mark.parametrize('name', list(EXPECTED))
def test_reader_returns_none_where_the_program_reports_nothing(name):
    assert harness.read_layer_metric(name, _parent_ctx()) is None


@pytest.mark.parametrize('name', [n for n in EXPECTED if n.startswith('ns_')])
def test_counter_readers_return_none_without_a_newton_schulz_solve(name):
    # the eigen method and the Cholesky solver: refresh_report gives {}
    engine = types.SimpleNamespace(refresh_report=lambda kstate: {})
    assert harness.read_layer_metric(name, _ctx(engine=engine)) is None


@pytest.mark.parametrize('name', [n for n in EXPECTED if n.startswith('ns_')])
def test_counter_readers_return_none_before_the_first_refresh(name):
    ctx = _ctx()
    state = ctx.run.state.kfac_state
    blank = np.zeros_like(state.refresh.solved)
    blank[:, 0] = -1  # init()'s array: iterations -1, the rest 0
    state.refresh = dataclasses.replace(state.refresh, solved=blank)
    assert harness.read_layer_metric(name, ctx) is None


def test_no_convolution_no_patches():
    plane = _device_plane()
    for line in plane['lines']:
        line['events'] = [
            e for e in line['events']
            if '/patches/' not in e['stats'].get('op_name', '')
        ]
    ctx = _ctx(plane=plane)
    assert harness.read_layer_metric('dev_ms.capture_patches', ctx) is None
    assert harness.read_layer_metric('dev_ms.capture_a', ctx) == pytest.approx(
        (100 + 100) / 2 * 1e-6
    )


def test_capture_readers_need_a_capturing_step():
    ctx = _ctx()
    ctx.count = lambda kind: 0 if kind == 'capture' else 3
    assert harness.read_layer_metric('dev_ms.capture_a', ctx) is None
    assert harness.read_layer_metric('dev_ms.capture_g', ctx) is None
    assert harness.read_layer_metric('dev_ms.capture_patches', ctx) is None
