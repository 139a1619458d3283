"""Which metrics a cell reports (PR 31, after the check refused
``stall_ms`` on ``qwen3-next-80b-a3b.kfac-10-100``): an end-to-end metric
may list its cells, a per-layer row is read where the metric it moves is
reported, and a row ``{"reads": ...}`` is another row's reader under
another name."""

import json
import os
import types

import pytest

from benchmark import harness

with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
    BENCH = json.load(f)
CELLS = [w['name'] for w in BENCH['workloads']]
QWEN = 'qwen3-next-80b-a3b.kfac-10-100'
ALIASES = sorted(
    m['name'] for m in BENCH['per_layer'] if m['name'].endswith('.overhead')
)


def _reports(metric, cell):
    return cell in metric.get('workloads', CELLS)


@pytest.mark.parametrize('name', CELLS)
def test_a_cell_reports_the_end_to_end_metrics_that_list_it(name):
    cell = harness.load_cell(name)
    got = [m['name'] for m in cell['bench']['end_to_end']]
    assert got == [m['name'] for m in BENCH['end_to_end'] if _reports(m, name)]
    # the contract: set-up, one more, and something per layer
    assert 'setup_s' in got and len(got) >= 2
    assert ('stall_ms' in got) == (name != QWEN)
    # per_layer stays whole: tests and tools ask it for other cells' rows
    assert cell['bench']['per_layer'] == BENCH['per_layer']


@pytest.mark.parametrize('name', CELLS)
def test_a_cell_reads_the_rows_that_move_what_it_reports(name):
    cell = harness.load_cell(name)
    reported = {m['name'] for m in cell['bench']['end_to_end']}
    read = harness.layer_rows(cell)
    assert read and all(m['moves'] in reported for m in read)
    # a row that lists the cell is read there: its metric is reported
    for m in BENCH['per_layer']:
        if 'workloads' in m:
            assert (m in read) == (name in m['workloads']), m['name']
        else:
            assert (m in read) == (m['moves'] in reported), m['name']


def test_every_listed_cell_reports_the_metric_the_row_moves():
    end_to_end = {m['name']: m for m in BENCH['end_to_end']}
    for m in BENCH['per_layer']:
        for cell in m.get('workloads', ()):
            assert _reports(end_to_end[m['moves']], cell), (m['name'], cell)


@pytest.mark.parametrize('name', ALIASES)
def test_an_overhead_row_is_the_stall_row_for_the_other_cells(name):
    rows = {m['name']: m for m in BENCH['per_layer']}
    base = name[:-len('.overhead')]
    assert harness._read_under(name) == base
    assert harness.layer_reader(name) is harness.layer_reader(base)
    stall = next(m for m in BENCH['end_to_end'] if m['name'] == 'stall_ms')
    assert rows[base]['moves'] == 'stall_ms'
    assert rows[name]['moves'] == 'kfac_overhead'
    assert rows[name]['workloads'] == [
        c for c in CELLS if c not in stall['workloads']
    ]
    for key in ('unit', 'better', 'source', 'layer'):
        assert rows[name][key] == rows[base][key]


def _ctx(seconds, kinds, inv_every=4):
    rows = [{'seconds': s, 'kind': k} for s, k in zip(seconds, kinds)]
    return types.SimpleNamespace(
        rows=rows, run=types.SimpleNamespace(inv_every=inv_every)
    )


def test_longest_step_is_the_longest_of_the_whole_periods():
    kinds = ['plain', 'capture', 'refresh', 'plain'] * 2 + ['plain']
    ctx = _ctx([0.1, 0.2, 0.9, 0.1, 0.1, 0.2, 1.3, 0.1, 5.0], kinds)
    # the ninth step is of no whole period, as for stall_ms
    assert harness.read_layer_metric('longest_step_ms', ctx) == 1300.0
    short = _ctx([0.1], ['plain'])
    assert harness.read_layer_metric('longest_step_ms', short) is None
    # the alias reads its row's reader on the same rows
    want = harness.read_layer_metric('refresh_extra_ms', ctx)
    assert want == pytest.approx(900.0)
    assert harness.read_layer_metric('refresh_extra_ms.overhead', ctx) == want
