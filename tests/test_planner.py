"""3D topology planner: enumeration, executed-schedule bubble terms, the
committed measured bubble table, ppermute wire parity against the traced
scans, and the plan plumbing (resolve_auto_layout).

The committed-artifact test re-derives every row of
``planner/bubble_table.json`` from the schedule simulators: the
executed-tick counts must match EXACTLY (they are structural), and every
row the measured tier called clean must sit within the artifact's own
documented tolerance — the acceptance gate for the measured tier.
"""

import json
import os
import warnings as pywarnings

import jax
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu.autotune import plan as plan_mod
from kfac_tpu.planner import execute, topology
from testing import models

WORLD = 8


@pytest.fixture(scope='module')
def base_config():
    m = models.TinyModel(hidden=8, out=4)
    x, _ = models.regression_data(jax.random.PRNGKey(1), n=16, dim=6)
    reg = kfac_tpu.register_model(m, x)
    return kfac_tpu.KFACPreconditioner(registry=reg, damping=1e-3, lr=0.1)


# ------------------------------------------------------------- enumeration


def test_enumerate_topologies_factorizes_world():
    cands = topology.enumerate_topologies(WORLD)
    assert cands
    for c in cands:
        assert c.dp * c.tp * c.pp == WORLD
        assert c.pp >= 2  # pp == 1 is the KAISA autotuner's domain
        assert c.microbatches % c.pp == 0
        if c.schedule == '1f1b':
            assert c.virtual_chunks == 1  # 2-slot scan has no chunks
    # both schedule families and every pipe divisor >= 2 appear
    assert {c.schedule for c in cands} == {'1f1b', 'interleaved'}
    assert {c.pp for c in cands} == {2, 4, 8}


def test_enumerate_topologies_respects_bounds():
    cfg = topology.TopologyConfig(
        schedules=('interleaved',), pipeline_ranks=(2,),
        virtual_chunks=(4,), microbatch_multiples=(2,),
    )
    cands = topology.enumerate_topologies(WORLD, cfg)
    assert [
        (c.dp, c.tp, c.pp, c.virtual_chunks, c.microbatches) for c in cands
    ] == [(4, 1, 2, 4, 4)]


# ----------------------------------------------------------- bubble terms


@pytest.mark.parametrize('schedule', ['1f1b', 'interleaved'])
@pytest.mark.parametrize('p,v,m', [(2, 1, 4), (2, 2, 8), (4, 2, 8)])
def test_schedule_terms_executes_simulator(schedule, p, v, m):
    if schedule == '1f1b':
        v = 1
    terms = topology.schedule_terms(schedule, p, v, m)
    assert terms['source'] == 'simulator'
    # the executed tables happen to agree with the fill/drain closed
    # forms at these sizes — the simulator must reproduce them, slot for
    # slot (the closed form is only the overflow fallback)
    closed = topology._closed_form(schedule, p, v, m)
    assert terms['ticks'] == closed['ticks']
    assert terms['bubble_slots'] == closed['bubble_slots']
    assert terms['fraction'] == pytest.approx(closed['fraction'])


def test_schedule_terms_overflow_falls_back_to_closed_form():
    terms = topology.schedule_terms('interleaved', 2, 2, 4, max_sim_slots=4)
    assert terms['source'] == 'closed-form'


def test_schedule_terms_rejects_bad_points():
    with pytest.raises(ValueError, match='multiple'):
        topology.schedule_terms('interleaved', 2, 2, 3)
    with pytest.raises(ValueError, match='schedule'):
        topology.schedule_terms('gpipe2', 2, 1, 4)


def test_bubble_fraction_applies_measured_correction(tmp_path):
    sim = topology.schedule_terms('interleaved', 2, 2, 8)['fraction']
    doc = {
        'schema': execute.SCHEMA_VERSION,
        'tolerance': 0.45,
        'rows': [{
            'schedule': 'interleaved', 'p': 2, 'v': 2,
            'predicted_fraction': sim,
            'measured': {'fraction': sim * 1.5},
            'contaminated': False,
        }],
    }
    path = os.path.join(tmp_path, 'table.json')
    with open(path, 'w') as f:
        json.dump(doc, f)
    got = topology.bubble_fraction('interleaved', 2, 2, 8, bubble_table=path)
    assert got == pytest.approx(min(0.99, sim * 1.5))
    # unknown rows and missing tables degrade to the raw simulator value
    assert topology.bubble_fraction(
        '1f1b', 2, 1, 8, bubble_table=path
    ) == pytest.approx(topology.schedule_terms('1f1b', 2, 1, 8)['fraction'])
    assert topology.bubble_fraction(
        'interleaved', 2, 2, 8,
        bubble_table=os.path.join(tmp_path, 'missing.json'),
    ) == pytest.approx(sim)


def test_measured_correction_is_clipped(tmp_path):
    doc = {
        'schema': execute.SCHEMA_VERSION,
        'rows': [{
            'schedule': '1f1b', 'p': 2, 'v': 1,
            'predicted_fraction': 0.1,
            'measured': {'fraction': 0.9},
            'contaminated': False,
        }],
    }
    path = os.path.join(tmp_path, 'table.json')
    with open(path, 'w') as f:
        json.dump(doc, f)
    assert execute.measured_bubble_correction('1f1b', 2, 1, path=path) == 2.0


# ------------------------------------------------------ committed artifact


def test_committed_bubble_table_matches_simulators():
    """Every row of the committed artifact re-derives from the schedule
    simulators (exact tick agreement) and every clean row's measured
    fraction sits within the artifact's own documented tolerance."""
    table = execute.load_bubble_table(execute.ARTIFACT_PATH)
    assert table, 'committed planner/bubble_table.json failed to load'
    assert table['schema'] == execute.SCHEMA_VERSION
    tol = float(table['tolerance'])
    rows = table['rows']
    covered = {(r['schedule'], r['p'], r['v']) for r in rows}
    assert covered == {
        (s, p, v)
        for s in ('1f1b', 'interleaved') for p in (2, 4) for v in (1, 2, 4)
    }
    clean = 0
    for row in rows:
        s, p, v, m = row['schedule'], row['p'], row['v'], row['microbatches']
        sim = topology.schedule_terms(s, p, v, m)
        assert sim['source'] == 'simulator'
        assert row['predicted_ticks'] == sim['ticks'], row
        assert row['predicted_bubble_slots'] == sim['bubble_slots'], row
        assert row['predicted_fraction'] == pytest.approx(sim['fraction'])
        assert row['executed_ticks'] == sim['ticks'], (
            'executed tick count diverged from the simulator', row
        )
        if not row['contaminated']:
            clean += 1
            err = abs(row['measured']['fraction'] - row['predicted_fraction'])
            assert err <= tol, (
                f'clean row {s} p={p} v={v} off by {err:.3f} > {tol}'
            )
    assert clean >= len(rows) // 2, 'most rows should be floor-clean'


# --------------------------------------------------------- ppermute parity


@pytest.mark.parametrize('schedule', ['1f1b', 'interleaved'])
def test_ppermute_bytes_parity_with_traced_scan(schedule):
    """KFL205-style parity: the planner's per-tick ppermute byte term
    equals ``analysis.ir.visitor.ppermute_bytes`` of the actual traced
    scan (each scan-body permute appears once in the jaxpr = one tick of
    one rank), so the cost model cannot drift from the executed code."""
    from kfac_tpu.analysis.ir import visitor

    p, v, m = 2, (2 if schedule == 'interleaved' else 1), 4
    model, params, batch = execute._build(schedule, p, v, m)
    jaxpr = jax.make_jaxpr(model.loss_and_stats)(params, batch)
    traced = visitor.ppermute_bytes(jaxpr.jaxpr)
    g = execute.GEOMETRY
    predicted = topology.pipeline_ppermute_bytes_per_tick(
        schedule, m // m, g['seq_len'], g['d_model']
    )
    assert traced == predicted, (traced, predicted)


# ---------------------------------------------------------------- plumbing


def test_plan_topology_is_deterministic_and_complete(base_config):
    p1 = topology.plan_topology(base_config, world=WORLD)
    p2 = topology.plan_topology(base_config, world=WORLD)
    assert p1.to_json() == p2.to_json()
    topo = p1.knobs['topology']
    assert topo['pp'] >= 2
    assert set(p1.knobs) == set(plan_mod.KNOB_KEYS)
    assert p1.meta['planner'] == 'topology3d'
    assert p1.meta['grid_size'] == len(p1.cost_table)
    # every cost row prices a real factorization with simulator terms
    for row in p1.cost_table:
        t = row['knobs']['topology']
        assert t['dp'] * t['tp'] * t['pp'] == WORLD
        assert row['schedule']['source'] == 'simulator'
        assert row['predicted_step_s'] > 0.0


def test_resolve_auto_layout_topology(base_config):
    from kfac_tpu.parallel.mesh import PIPE_AXIS
    from kfac_tpu.warnings import LayoutPlanWarning, reset_layout_warnings

    plan = topology.plan_topology(base_config, world=WORLD)
    cfg, mesh, applied = plan_mod.resolve_auto_layout(
        base_config, None, plan
    )
    assert applied
    assert dict(mesh.shape)[PIPE_AXIS] == plan.knobs['topology']['pp']

    # a factorization that does not divide this world is a fingerprint
    # mismatch: warn, fall back, never build a broken mesh
    bad = plan_mod.TunedPlan.from_json(plan.to_json())
    bad.knobs['topology'] = dict(bad.knobs['topology'], pp=3, tp=1)
    reset_layout_warnings()
    with pywarnings.catch_warnings(record=True) as rec:
        pywarnings.simplefilter('always')
        cfg, mesh, applied = plan_mod.resolve_auto_layout(
            base_config, None, bad
        )
    assert not applied and mesh is None
    assert any(isinstance(r.message, LayoutPlanWarning) for r in rec)


def test_load_bubble_table_env_override(tmp_path, monkeypatch):
    doc = {'schema': execute.SCHEMA_VERSION, 'rows': []}
    path = os.path.join(tmp_path, 'env_table.json')
    with open(path, 'w') as f:
        json.dump(doc, f)
    monkeypatch.setenv(execute.ENV_VAR, path)
    execute.invalidate_cache()
    try:
        assert execute.load_bubble_table()['rows'] == []
        # schema mismatch degrades to empty (load-or-default), not a crash
        with open(path, 'w') as f:
            json.dump({'schema': 999, 'rows': []}, f)
        execute.invalidate_cache()
        assert execute.load_bubble_table() == {}
    finally:
        execute.invalidate_cache()


@pytest.mark.slow
def test_measure_row_smoke():
    """One real measured-tier row on the CPU mesh: structural fields
    populated, executed ticks == simulator, provenance from the
    one-dispatch harness."""
    row = execute.measure_row('interleaved', 2, 1, iters=2, repeats=1)
    sim = topology.schedule_terms('interleaved', 2, 1, row['microbatches'])
    assert row['executed_ticks'] == sim['ticks']
    assert row['predicted_bubble_slots'] == sim['bubble_slots']
    assert row['measured']['wall_clock_p50_s'] > 0.0
    assert all(w > 0.0 for w in row['measured']['wall_s'].values())
    assert row['provenance']['harness_version'] == 2
    assert isinstance(row['contaminated'], bool)
