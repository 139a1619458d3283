"""The step map's reader (``layer_metrics/_stepmap.py``, PR 38) on a
hand-made neutral trace whose every number is worked out below. One device,
three traced steps (plain, capture, refresh) of two step programs, the
feed's program between them; times in nanoseconds. The events carry no
scope: as in a traced run, ``trace_reduce.annotate`` writes the programs'
``op_name``s into them from the programs' text.

    step 0, plain, jit__step_no_stats [1000,2000)
        embed [1000,1100); a while under model.mixer [1100,1400) around a
        body under model.gdn_scan [1150,1350) and a copy with no op_name
        [1360,1380); mlp, backward [1400,1500); precondition [1500,1600);
        the engine's own [1600,1650); optimizer [1650,1750); a residual
        add under no scope [1750,1800); a copy with no op_name [1800,1850)
    jit__multi_slice [2100,2200): one operation; a stray one [2300,2320)
    step 1, capture, jit__step_with_stats [3000,4000)
        an A tap inside model.mlp [3000,3100); a G tap inside model.mixer
        [3100,3250); the EMA [3250,3300); optimizer [3300,3400); the loss
        inside the head, backward [3400,3500); attention, rematerialised
        [3500,3600)
    step 2, refresh, jit__step_with_stats [5000,6000)
        a conditional under update_inverses [5000,5400) around a while
        [5050,5350) around a fusion with no op_name [5100,5200); optimizer
        [5400,5500); patch rows inside model.stage0 [5500,5600); stem
        [5600,5700); norm [5700,5750)
"""

import types

import pytest

from benchmark import harness
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _stepmap

WINDOW = (0, 7000)
KINDS = ('plain', 'capture', 'refresh')
FWD = 'jit(_step)/jit(main)/jvp(Net)/'
BWD = 'jit(_step)/jit(main)/transpose(jvp(Net))/'
ENGINE = 'jit(_step)/jit(main)/dist_kfac.step/'

# module -> [(instruction, op_name or None)]
PROGRAMS = {
    'jit__step_no_stats': [
        ('fusion.1', FWD + 'model.embed/embed/take'),
        ('while.2', FWD + 'block0/model.mixer/mixer/checkpoint/while'),
        ('fusion.3', FWD + 'block0/model.mixer/mixer/checkpoint/'
                     'model.gdn_scan/while/body/dot_general'),
        ('copy.4', None),
        ('fusion.5', BWD + 'block0/model.mlp/mlp_up/dot_general'),
        ('fusion.6', ENGINE + 'dist_kfac.precondition/dot_general'),
        ('fusion.7', ENGINE + 'add'),
        ('fusion.8', 'jit(_step)/jit(main)/trainer.optimizer/add'),
        ('fusion.9', FWD + 'block0/add'),
        ('copy.10', None),
    ],
    'jit__step_with_stats': [
        ('fusion.1', FWD + 'block0/model.mlp/mlp_up/kfac.capture_a/'
                     'dot_general'),
        ('fusion.2', BWD + 'block0/model.mixer/attn/q_proj/kfac.capture_g/'
                     'dot_general'),
        ('fusion.3', ENGINE + 'dist_kfac.update_factors/add'),
        ('fusion.4', 'jit(_step)/jit(main)/trainer.optimizer/add'),
        ('fusion.5', BWD + 'model.head/checkpoint/model.loss/sub'),
        ('fusion.6', BWD + 'block0/model.mixer/attn/checkpoint/'
                     'rematted_computation/model.attention/exp'),
        ('conditional.7', ENGINE + 'dist_kfac.update_inverses/cond'),
        ('while.8', ENGINE + 'dist_kfac.update_inverses/cond/branch_1_fun/'
                    'while'),
        ('fusion.9', None),
        ('fusion.10', FWD + 'model.stage0/stage0_block0/conv1/kfac.capture_a/'
                      'patches/conv_general_dilated'),
        ('fusion.11', FWD + 'model.stem/conv0/conv_general_dilated'),
        ('fusion.12', FWD + 'block0/model.norm/ln1/mul'),
    ],
}
RUNS = [
    ('jit__step_no_stats(1)', 1000, 1000, [
        ('fusion.1', 1000, 100), ('while.2', 1100, 300),
        ('fusion.3', 1150, 200), ('copy.4', 1360, 20),
        ('fusion.5', 1400, 100), ('fusion.6', 1500, 100),
        ('fusion.7', 1600, 50), ('fusion.8', 1650, 100),
        ('fusion.9', 1750, 50), ('copy.10', 1800, 50),
    ]),
    ('jit__multi_slice(7)', 2100, 100, [('slice.1', 2100, 100)]),
    (None, 0, 0, [('convert.1', 2300, 20)]),
    ('jit__step_with_stats(2)', 3000, 1000, [
        ('fusion.1', 3000, 100), ('fusion.2', 3100, 150),
        ('fusion.3', 3250, 50), ('fusion.4', 3300, 100),
        ('fusion.5', 3400, 100), ('fusion.6', 3500, 100),
    ]),
    ('jit__step_with_stats(2)', 5000, 1000, [
        ('conditional.7', 5000, 400), ('while.8', 5050, 300),
        ('fusion.9', 5100, 100), ('fusion.4', 5400, 100),
        ('fusion.10', 5500, 100), ('fusion.11', 5600, 100),
        ('fusion.12', 5700, 50),
    ]),
]
# nanoseconds of the stretch in each bucket
EXPECTED = {
    'embed': 100, 'gdn_scan': 200, 'mixer': 100, 'mlp': 100,
    'precondition': 100, 'kfac_step_self': 50, 'optimizer': 300,
    'unscoped': 100, 'other_programs': 120, 'capture_a': 200,
    'capture_g': 150, 'update_factors': 50, 'loss': 100, 'attention': 100,
    'update_inverses': 400, 'stem': 100, 'norm': 50,
}
NEW_ROWS = (
    'embed', 'mixer', 'attention', 'mlp', 'norm', 'stem', 'stage0', 'stage1',
    'stage2', 'stage3', 'head', 'loss', 'optimizer', 'kfac_step_self',
    'unscoped', 'other_programs',
)


def _text(module, without=()):
    """A compiled program's text, as far as ``trace_reduce.op_names`` reads
    it."""
    lines = [f'HloModule {module}, entry_computation_layout={{()->f32[]}}']
    for name, op in PROGRAMS[module]:
        meta = '' if op is None or any(w in op for w in without) else (
            f', metadata={{op_name="{op}"}}'
        )
        lines.append(f'  %{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x){meta}')
    return '\n'.join(lines)


def _run_with(texts):
    """A run whose K-FAC engine's compile watch holds programs of these
    texts, as far as ``_stepmap._fused_of`` asks."""
    exes = [types.SimpleNamespace(as_text=lambda t=t: t) for t in texts]
    watch = types.SimpleNamespace(executables=lambda: {'step': exes})
    return types.SimpleNamespace(trainer=types.SimpleNamespace(
        kfac=types.SimpleNamespace(compile_watcher=lambda: watch)
    ))


def _ctx(without=(), kinds=KINDS, runs=RUNS, run=None):
    modules, ops = [], []
    for module, start, ns, events in runs:
        if module is not None:
            modules.append({'name': module, 'start_ns': start,
                            'duration_ns': ns, 'stats': {}})
        ops += [
            {'name': f'%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
             'start_ns': lo, 'duration_ns': d, 'stats': {}}
            for name, lo, d in events
        ]
    plane = {'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Modules', 'events': modules},
        {'name': 'XLA Ops', 'events': ops},
    ]}
    trace = {'planes': [plane]}
    tr.annotate(trace, {m: tr.op_names(_text(m, without)) for m in PROGRAMS})
    return harness.LayerContext(
        cell={}, run=run, devices=[], first_order_rows=None, rows=[],
        traced_rows=[{'kind': k} for k in kinds], trace=trace,
        windows={plane['name']: WINDOW}, throughput=0.0,
    )


def _read(name, ctx):
    return harness.read_layer_metric(name, ctx)


def test_every_operation_goes_to_one_bucket():
    ctx = _ctx()
    plane, = _stepmap.of(ctx)
    assert plane['total'] == pytest.approx(EXPECTED)
    busy = tr.busy_ns(ctx.trace['planes'][0], WINDOW)
    assert busy == 2320
    assert sum(plane['total'].values()) == pytest.approx(busy)
    assert plane['busy_ns'] == busy


@pytest.mark.parametrize('bucket', NEW_ROWS)
def test_reader_on_the_hand_made_trace(bucket):
    got = _read('dev_ms.' + bucket, _ctx())
    assert got == pytest.approx(EXPECTED.get(bucket, 0) / 3 / 1e6)


def test_a_loop_around_a_body_under_another_scope_is_counted_once():
    """``while.2`` under ``model.mixer`` spans 300 ns, 200 of them its
    body's under ``model.gdn_scan``: the scan's reader reads the body
    as before and the mixer gets the rest, the unnamed copy in the loop
    with it."""
    ctx = _ctx()
    assert _read('dev_ms.gdn_scan', ctx) == pytest.approx(200 / 3 / 1e6)
    assert _read('dev_ms.mixer', ctx) == pytest.approx(100 / 3 / 1e6)


def test_an_unnamed_operation_in_a_loop_goes_where_the_loop_goes():
    plane, = _stepmap.of(_ctx())
    # fusion.9 inside while.8 inside conditional.7: the refresh's, so the
    # bucket reads what dev_ms.update_inverses reads (the conditional whole)
    assert plane['total']['update_inverses'] == 400
    assert _read('dev_ms.update_inverses', _ctx()) == pytest.approx(400 / 1e6)
    # copy.10 lies in no loop
    assert plane['unscoped'] == {
        'jvp(Net)/block0/add': 50, '(no op_name) copy': 50,
    }


def test_a_nameless_fusion_goes_where_its_instructions_say():
    """The compiler roots a fusion in an instruction of its own (a packed
    predicate): the event has no ``op_name``, the program's text says what
    was fused into it. ``copy.10`` [1800,1850) of step 0 stands in for two
    such fusions in turn: one made of the attention core's instructions,
    one of two parts' (which says nothing)."""
    text = _text('jit__step_no_stats') + """

%fused_computation.7 (p: f32[8]) -> u16[8] {
  %p = f32[8]{0} parameter(0)
  %le.1 = pred[8]{0} compare(%p, %p), direction=LE, metadata={op_name="<fwd>block0/model.mixer/attn/model.attention/le"}
  ROOT %reduce.2 = u16[8]{0} reduce(%le.1), dimensions={}
}

%fused_computation.8 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %a.1 = f32[8]{0} add(%p, %p), metadata={op_name="<fwd>block0/model.mlp/add"}
  ROOT %m.2 = f32[8]{0} multiply(%a.1, %p), metadata={op_name="<bwd>block0/model.norm/mul"}
}
""".replace('<fwd>', FWD).replace('<bwd>', BWD)
    assert _stepmap.fused_buckets(text) == {
        'fused_computation.7': ('attention', 'forward'),
    }
    for calls, bucket in (('fused_computation.7', 'attention'),
                          ('fused_computation.8', 'unscoped'),
                          ('fused_computation.9', 'unscoped')):
        ctx = _ctx(run=_run_with([text]))
        for e in ctx.trace['planes'][0]['lines'][1]['events']:
            if e['start_ns'] == 1800:
                e['name'] = ('%fusion.10 = u16[8]{0} fusion(f32[8]{0} %x), '
                             f'kind=kLoop, calls=%{calls}')
        plane, = _stepmap.of(ctx)
        expected = dict(EXPECTED, unscoped=50)
        expected[bucket] = expected.get(bucket, 0) + 50
        assert plane['total'] == pytest.approx(expected), calls


def test_a_program_without_op_names_is_another_program():
    plane, = _stepmap.of(_ctx())
    assert plane['total']['other_programs'] == 100 + 20
    assert plane['by_kind']['plain'].get('other_programs') is None


def test_the_buckets_add_up_with_the_rows_that_were_there():
    """The partition of section (C): the new rows, the model rows and the
    capture and engine rows that existed, each times the steps it is taken
    over, are the stretch's busy time."""
    ctx = _ctx()
    per_step = [_read('dev_ms.' + b, ctx) for b in NEW_ROWS]
    per_step += [
        _read('dev_ms.gdn_scan', ctx), _read('dev_ms.precondition', ctx)
    ]
    per_capture = [_read('dev_ms.' + n, ctx) for n in
                   ('capture_a', 'capture_g', 'update_factors')]
    total = (3 * sum(per_step) + 2 * sum(per_capture)
             + _read('dev_ms.update_inverses', ctx))
    busy = tr.busy_ns(ctx.trace['planes'][0], WINDOW)
    assert total * 1e6 == pytest.approx(busy)
    for part in ('moe_route', 'moe_experts', 'short_conv'):
        assert _read('dev_ms.' + part, ctx) is None


def test_by_kind_and_by_pass():
    plane, = _stepmap.of(_ctx())
    assert plane['by_kind']['plain'] == pytest.approx({
        'embed': 100, 'gdn_scan': 200, 'mixer': 100, 'mlp': 100,
        'precondition': 100, 'kfac_step_self': 50, 'optimizer': 100,
        'unscoped': 100,
    })
    assert plane['by_kind']['refresh'] == pytest.approx({
        'update_inverses': 400, 'optimizer': 100, 'capture_a': 100,
        'stem': 100, 'norm': 50,
    })
    assert plane['by_pass']['backward'] == pytest.approx(
        {'mlp': 100, 'capture_g': 150, 'loss': 100}
    )
    assert plane['by_pass']['remat'] == pytest.approx({'attention': 100})
    assert plane['by_pass']['forward']['gdn_scan'] == 200
    assert plane['by_pass']['none']['optimizer'] == 300
    lines = _stepmap.report([plane], [{'kind': k} for k in KINDS], 0.0)
    assert 'busy 0.002 ms, in the buckets 0.002 ms' in lines[0]
    assert any(l.startswith('unscoped') and 'block0/add' in l for l in lines)


def test_no_table_by_kind_where_the_runs_do_not_pair():
    # a fourth row and three runs of step programs: not guessed
    plane, = _stepmap.of(_ctx(kinds=KINDS + ('plain',)))
    assert plane['by_kind'] is None
    assert plane['total'] == pytest.approx(EXPECTED)
    lines = _stepmap.report([plane], [{'kind': k} for k in KINDS], 0.0)
    assert any('no table by step kind' in l for l in lines)


@pytest.mark.parametrize('without', [
    ('trainer.optimizer',),           # both programs older than the scopes
    ('trainer.optimizer/add', FWD),   # the same, told by another spelling
])
def test_a_stale_program_is_not_read(without, capsys):
    ctx = _ctx(without=without)
    for bucket in NEW_ROWS:
        assert _read('dev_ms.' + bucket, ctx) is None, bucket
    assert 'shows no trainer.optimizer' in capsys.readouterr().out
    # the rows that were there read what they read
    assert _read('dev_ms.precondition', ctx) == pytest.approx(100 / 3 / 1e6)


def test_one_stale_program_of_two_is_enough():
    runs = [r for r in RUNS if r[0] != 'jit__step_no_stats(1)']
    assert _stepmap.of(_ctx(kinds=KINDS[1:], runs=runs)) is not None
    text = _text('jit__step_no_stats', without=('trainer.optimizer',))
    ctx = _ctx()
    for e in ctx.trace['planes'][0]['lines'][1]['events']:
        if 1000 <= e['start_ns'] < 2000:
            e['stats'] = {}
    tr.annotate(ctx.trace, {'jit__step_no_stats': tr.op_names(text)})
    assert _stepmap.of(ctx) is None


def test_sixteen_reads_make_one_pass(monkeypatch):
    calls = []
    partition = _stepmap.partition

    def counted(ctx):
        calls.append(ctx)
        return partition(ctx)

    monkeypatch.setattr(_stepmap, 'partition', counted)
    ctx = _ctx()
    for bucket in NEW_ROWS:
        assert _read('dev_ms.' + bucket, ctx) is not None
    assert len(NEW_ROWS) == 16 and len(calls) == 1
    # a stale trace is looked at once too
    stale = _ctx(without=('trainer.optimizer',))
    for bucket in NEW_ROWS:
        assert _read('dev_ms.' + bucket, stale) is None
    assert len(calls) == 2


def test_benchmark_json_lists_the_new_rows():
    bench = harness.load_cell('resnet50.kfac-10-100')['bench']
    rows = {m['name']: m for m in bench['per_layer']}
    cells = {w['name']: w for w in bench['workloads']}
    kinds = {
        name: harness.load_cell(name)['config']['kind'] for name in cells
    }
    for bucket in NEW_ROWS:
        row = rows['dev_ms.' + bucket]
        assert row['unit'] == 'ms' and row['better'] == 'lower'
        assert row['source'] == 'device_trace'
        assert callable(harness.layer_reader(row['name']).read)
        listed = set(row['workloads'])
        if bucket in ('embed', 'mixer', 'attention', 'mlp', 'norm'):
            assert listed == {c for c, k in kinds.items() if k != 'vision'}
        elif bucket.startswith('sta') or bucket == 'stem':
            assert listed == {c for c, k in kinds.items() if k == 'vision'}
        else:
            assert listed == set(cells)
    assert rows['dev_ms.kfac_step_self']['moves'] == 'kfac_overhead'
    assert rows['dev_ms.optimizer']['layer'] == 'trainer'
    # every scope of the program's tables is on the map, under its name
    from kfac_tpu import tracing

    for scope in (*tracing.MODEL_SCOPES.values(),
                  *tracing.TRAINER_SCOPES.values(),
                  tracing.CAPTURE_SCOPES['a'], tracing.CAPTURE_SCOPES['g']):
        assert scope in _stepmap.SCOPES, scope
    for part, scope in tracing.MODEL_SCOPES.items():
        assert _stepmap.SCOPES[scope] == part
    # and no .json row names one of them: harness.trace_scopes() is the
    # engine's six, so the rows that were there read what they read
    assert not set(harness.trace_scopes()) & set(
        s for s in _stepmap.SCOPES if 'kfac.' not in s or 'capture' in s
    )
