"""``ns_scaled_trips_refresh`` (PR 30): the reader over a hand-made
refresh state, and nothing where the program's report has no such total
(the parent commit's has ``trips`` and not ``scaled_trips``)."""

import json
import os
import types

import numpy as np
import pytest

from benchmark import harness

NAME = 'ns_scaled_trips_refresh'


def _refresh():
    """Two A buckets (two slots and a padding; one slot solved in two
    groups of one) and a G bucket. Columns: iterations, residual, warm,
    restarted, scaled."""
    from kfac_tpu.parallel import kaisa

    return kaisa.RefreshState(
        buckets=(('a', 'd8', 3, 2), ('a', 'd16', 2, 2), ('g', 'd8', 1, 1)),
        solved=np.array([
            [12, 5e-7, 0, 0, 7],   # cold: seven scaled steps of twelve
            [15, 8e-7, 1, 1, 6],   # warm, restarted: the cold attempt's six
            [0, 0.0, 1, 0, 0],     # identity padding
            [11, 3e-7, 0, 0, 5],   # a group of its own
            [4, 3e-7, 1, 0, 0],    # a group of its own: warm, none scaled
            [3, 9e-7, 1, 0, 0],
        ], np.float32),
        groups=(1, 2, 1),
    )


def _ctx(report):
    return types.SimpleNamespace(run=types.SimpleNamespace(
        trainer=types.SimpleNamespace(kfac=types.SimpleNamespace(
            **({} if report is None else {'refresh_report': report})
        )),
        state=types.SimpleNamespace(
            kfac_state=types.SimpleNamespace(refresh=_refresh())
        ),
    ))


def _report(kstate):
    from kfac_tpu.parallel import kaisa

    return {'buckets': {}, 'totals': {
        k.split('/', 1)[1]: v
        for k, v in kaisa.refresh_totals(kstate.refresh).items()
    }}


def test_reader_sums_each_buckets_longest_scaled_phase():
    # 7 (the slower phase of the first bucket's two), 5 + 0 (two groups,
    # one after another), 0; beside trips of 15 + (11 + 4) + 3
    ctx = _ctx(_report)
    assert harness.read_layer_metric(NAME, ctx) == 12.0
    assert harness.read_layer_metric('ns_trips_refresh', ctx) == 33.0


def _parent_report(kstate):
    totals = _report(kstate)['totals']
    del totals['scaled_trips']
    return {'buckets': {}, 'totals': totals}


@pytest.mark.parametrize('report', [
    None,               # an engine with no report at all
    lambda kstate: {},  # no Newton-Schulz refresh, or none yet
    _parent_report,     # the parent commit: trips, no scaled_trips
], ids=['no-report', 'empty-report', 'parent'])
def test_reader_returns_none_where_the_program_reports_nothing(report):
    assert harness.read_layer_metric(NAME, _ctx(report)) is None
    if report is _parent_report:
        assert harness.read_layer_metric('ns_trips_refresh', _ctx(report)) == 33.0


def test_benchmark_json_lists_it_in_every_cell():
    """By name, wherever the row stands. It moves ``stall_ms`` in the cells
    that report ``stall_ms`` and ``kfac_overhead`` (as
    ``ns_scaled_trips_refresh.overhead``, the same reader) in the others."""
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    rows = {m['name']: m for m in bench['per_layer']}
    (stall,) = [m for m in bench['end_to_end'] if m['name'] == 'stall_ms']
    cells = [w['name'] for w in bench['workloads']]
    steady = stall.get('workloads', cells)
    assert rows[NAME] == {
        'name': NAME, 'unit': 'count', 'better': 'lower',
        'source': 'program_counter', 'layer': 'factor math',
        'moves': 'stall_ms', 'workloads': steady,
    }
    others = [c for c in cells if c not in steady]
    if others:
        assert rows[NAME + '.overhead'] == {
            **rows[NAME], 'name': NAME + '.overhead',
            'moves': 'kfac_overhead', 'workloads': others,
        }
        ctx = _ctx(_report)
        assert harness.read_layer_metric(NAME + '.overhead', ctx) == 12.0
