"""The options the benchmark's cells set are options the program has.

A cell reaches the K-FAC config through three hands: its ``kfac`` block
(``benchmark/workloads/<cell>.json``), the flags the benchmark's job makes
of it (``benchmark.jobs.kfac_namespace``), and the keywords
``examples.common.build_kfac`` makes of those. Read here, none edited: the
removal of an option that a cell sets fails this file, not a chip run.
"""

import dataclasses
import glob
import json
import os

import pytest

import kfac_tpu
from benchmark import jobs
from examples import common

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = sorted(
    os.path.basename(p)[:-len('.json')]
    for p in glob.glob(os.path.join(REPO, 'benchmark', 'workloads', '*.json'))
)
#: what the job makes a mesh and a registry of, not a config field
JOB_KEYS = {'strategy', 'skip_layers'}
#: every keyword ``build_kfac`` hands the config: ten of its 24 fields
CELL_OPTIONS = {
    'registry', 'factor_update_steps', 'inv_update_steps', 'damping',
    'factor_decay', 'kl_clip', 'lr', 'compute_method', 'bucket_granularity',
    'compile_watch',
}


def _workload(cell):
    with open(os.path.join(REPO, 'benchmark', 'workloads', cell + '.json')) as f:
        return json.load(f)


def _config_keywords(monkeypatch, workload):
    """The keywords ``build_kfac`` calls ``KFACPreconditioner`` with for a
    cell, caught by a stub in the config's place."""
    caught = {}

    def stub(**kwargs):
        caught.update(kwargs)
        return 'the config'

    monkeypatch.setattr(kfac_tpu, 'KFACPreconditioner', stub)
    args = jobs.kfac_namespace(workload, lr=0.1)
    assert common.build_kfac(args, registry='the registry') == 'the config'
    return caught


def test_the_cells_are_the_six():
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        listed = json.load(f)['workloads']
    assert len(CELLS) == 6
    assert sorted(w['name'] for w in listed) == CELLS


@pytest.mark.parametrize('cell', CELLS)
def test_a_cell_sets_only_options_the_config_has(monkeypatch, cell):
    fields = {f.name for f in dataclasses.fields(kfac_tpu.KFACPreconditioner)}
    workload = _workload(cell)
    block = workload['kfac']
    passed = _config_keywords(monkeypatch, workload)
    assert set(passed) <= fields
    for key, value in block.items():
        if key in JOB_KEYS:
            continue
        assert key in fields, f'{cell}: {key} is no field of the config'
        assert key in passed, f'{cell}: build_kfac drops {key}'
        if key == 'compute_method' and value == 'auto':
            value = None  # the config's own default picks by platform
        assert passed[key] == value


def test_the_options_the_cells_set(monkeypatch):
    passed = _config_keywords(monkeypatch, _workload(CELLS[0]))
    assert set(passed) == CELL_OPTIONS
    # and the benchmark's Trainer is built with four of its nine fields
    # (benchmark/harness.py: loss_fn, optimizer, kfac, donate_state)
    trainer_fields = {f.name for f in dataclasses.fields(kfac_tpu.Trainer)}
    assert {'loss_fn', 'optimizer', 'kfac', 'donate_state'} <= trainer_fields
