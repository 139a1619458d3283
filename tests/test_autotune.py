"""Layout autotuner tests on the 8-virtual-device CPU mesh.

Covers the ISSUE acceptance surface: cost-model byte parity with
``observability.comms.comms_summary`` for all three KAISA strategies,
TunedPlan round-trip into an identical engine configuration, fingerprint
gating with the rate-limited fallback warning, model-only determinism,
HBM feasibility pruning, and the measured search (winner never worse
than the hand-configured strategy baselines).
"""

import json

import jax
import jax.numpy as jnp
import optax
import pytest

import kfac_tpu
from kfac_tpu import assignment, autotune, training
from kfac_tpu.autotune import model as model_lib
from kfac_tpu.autotune import plan as plan_lib
from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh
from kfac_tpu.warnings import LayoutPlanWarning, reset_layout_warnings
from testing import models

WORLD = 8


def _base(**kw):
    m = models.TinyModel(hidden=16, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=WORLD * 4, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, damping=1e-3, **kw)
    loss_fn = models.mse_loss(m)
    return cfg, m, params, (x, y), loss_fn


# ------------------------------------------------------------ candidate grid


def test_candidate_fractions_follow_divisor_structure():
    assert assignment.candidate_fractions(8) == (1.0, 0.5, 0.25, 0.125)
    assert assignment.candidate_fractions(6) == (1.0, 0.5, 1 / 3, 1 / 6)
    assert assignment.candidate_fractions(1) == (1.0,)
    with pytest.raises(ValueError):
        assignment.candidate_fractions(0)
    # every fraction yields an integer worker count
    for f in assignment.candidate_fractions(12):
        assignment.grad_worker_count(12, f)


def test_enumerate_candidates_grid_and_baselines():
    cfg, *_ = _base()
    cands = autotune.enumerate_candidates(WORLD, cfg)
    # fractions x granularities x transports x one inverse cadence
    assert len(cands) == 4 * 4 * 2
    assert len(set(cands)) == len(cands)
    # MEM-OPT candidates always colocate (single owner holds both sides)
    for c in cands:
        if assignment.grad_worker_count(WORLD, c.grad_worker_fraction) == 1:
            assert c.colocate_factors
    bases = autotune.baseline_candidates(WORLD, cfg)
    assert [c.grad_worker_fraction for c in bases] == [1.0, 0.25, 0.125]
    # baselines reuse the base transport, so they dedup against the grid
    assert all(b in cands for b in bases)


# ----------------------------------------------- cost model vs comms_summary


@pytest.mark.parametrize('frac', [1.0, 0.5, 0.125])
def test_static_layout_byte_parity_with_engine(frac):
    """The model's layout must report the exact comms_summary() bytes the
    real engine does — the model prices the same layout it predicts."""
    cfg, *_ = _base()
    layout = model_lib.StaticLayout(cfg, WORLD, frac)
    eng = DistributedKFAC(
        config=cfg, mesh=kaisa_mesh(grad_worker_fraction=frac)
    )
    assert layout.comms_report() == eng.comms_report()


def test_predict_terms_present_and_consistent():
    cfg, *_ = _base()
    cand = model_lib.Candidate(grad_worker_fraction=0.5, bucket_granularity=64)
    row = model_lib.predict(cand, cfg, WORLD, model_lib.HardwareSpec())
    assert row['feasible'] and row['infeasible_reason'] is None
    assert row['predicted_step_s'] > 0
    mem = row['memory_per_device_bytes']
    assert mem['total'] == (
        mem['factors'] + mem['decomps'] + mem['grad_stacks']
    )
    for k in ('stat_transport', 'grad_broadcast', 'decomp_reshard'):
        assert row['bytes_per_occurrence'][k] >= 0
    # COMM-OPT's grads are already replicated: the broadcast payload is
    # reported (comms_summary parity) but never billed per step
    comm = model_lib.predict(
        model_lib.Candidate(grad_worker_fraction=1.0, bucket_granularity=64),
        cfg, WORLD, model_lib.HardwareSpec(),
    )
    occ = comm['bytes_per_occurrence']
    assert comm['bytes_per_step'] == (
        occ['stat_transport'] + occ['decomp_reshard']
    )
    occ = row['bytes_per_occurrence']
    assert row['bytes_per_step'] == (
        occ['stat_transport'] + occ['decomp_reshard'] + occ['grad_broadcast']
    )


def test_hbm_budget_prunes_and_exhaustion_raises():
    cfg, *_ = _base()
    tight = model_lib.HardwareSpec(hbm_bytes=1)  # nothing fits in 1 byte
    cand = model_lib.Candidate(grad_worker_fraction=1.0, bucket_granularity=1)
    row = model_lib.predict(cand, cfg, WORLD, tight)
    assert not row['feasible'] and 'memory' in row['infeasible_reason']
    with pytest.raises(ValueError, match='HBM budget'):
        autotune.autotune(cfg, measure=False, hardware=tight)


def test_hbm_pruning_consistent_with_xla_reported_memory():
    """Cross-check the cost model's HBM pruning against XLA's own memory
    accounting: drive the distributed step under compile-watch, read the
    compiled program's reported temp+output bytes, and assert a budget
    set to exactly that figure does NOT prune the layout the program
    implements — the model's persistent-state prediction must fit inside
    what XLA says the step actually touches."""
    cfg, _, params, batch, loss_fn = _base(compile_watch=True)
    mesh = kaisa_mesh(grad_worker_fraction=0.5)
    eng = DistributedKFAC(config=cfg, mesh=mesh)
    run = kfac_tpu.CurvatureCapture(cfg.registry).value_stats_and_grad(loss_fn)
    (_, _), grads, stats = jax.jit(run)(params, batch)
    state = eng.init()
    state, _ = eng.watched('step')(state, grads, stats)
    jax.block_until_ready(state)

    snap = eng.compiled_memory_report()['dist_kfac.step']
    mem = snap['memory']
    assert mem is not None, 'CPU backend reports memory_analysis()'
    temp_out = mem['temp_size_in_bytes'] + mem['output_size_in_bytes']
    assert temp_out > 0

    cand = model_lib.Candidate(grad_worker_fraction=0.5, bucket_granularity=1)
    row = model_lib.predict(
        cand, cfg, WORLD, model_lib.HardwareSpec(hbm_bytes=float(temp_out)))
    # the layout the compiled program implements stays feasible under a
    # budget of exactly the XLA-reported transient+output footprint ...
    assert row['feasible'], row.get('infeasible_reason')
    assert row['memory_per_device_bytes']['total'] <= temp_out
    # ... while the same budget scaled far below the prediction prunes
    tight = model_lib.HardwareSpec(
        hbm_bytes=0.01 * row['memory_per_device_bytes']['total'])
    assert not model_lib.predict(cand, cfg, WORLD, tight)['feasible']


# ------------------------------------------------------------- plan artifact


def test_model_only_plan_is_deterministic():
    cfg, *_ = _base()
    p1 = autotune.autotune(cfg, measure=False)
    p2 = autotune.autotune(cfg, measure=False)
    assert p1.to_json() == p2.to_json()
    assert p1.winner['picked_by'] == 'model'
    # cost table is ranked: feasible rows ascending by predicted cost
    preds = [r['predicted_step_s'] for r in p1.cost_table if r['feasible']]
    assert preds == sorted(preds)
    # serialized form is stable too (sorted keys, no timestamps)
    assert json.dumps(p1.to_json(), sort_keys=True) == json.dumps(
        p2.to_json(), sort_keys=True
    )


def test_plan_roundtrip_reproduces_engine_config(tmp_path):
    cfg, *_ = _base()
    plan = autotune.autotune(cfg, measure=False)
    path = tmp_path / 'plan.json'
    plan.save(path)
    loaded = kfac_tpu.TunedPlan.load(path)
    assert loaded.to_json() == plan.to_json()

    eng = DistributedKFAC(config=cfg, auto_layout=str(path))
    assert eng.auto_layout_applied
    frac = plan.knobs['grad_worker_fraction']
    ref = DistributedKFAC(
        config=autotune.apply_knobs(cfg, plan.knobs),
        mesh=kaisa_mesh(grad_worker_fraction=frac),
    )
    assert eng.describe() == ref.describe()
    assert eng.comms_report() == ref.comms_report()
    assert eng.granularity == plan.knobs['bucket_granularity']
    # the plan object and the raw dict apply identically
    eng2 = DistributedKFAC(config=cfg, auto_layout=plan.to_json())
    assert eng2.auto_layout_applied
    assert eng2.describe() == eng.describe()


def test_from_json_validates_schema():
    cfg, *_ = _base()
    good = autotune.autotune(cfg, measure=False).to_json()
    with pytest.raises(ValueError, match='schema'):
        kfac_tpu.TunedPlan.from_json(dict(good, schema=999))
    missing = dict(good)
    del missing['winner']
    with pytest.raises(ValueError, match='winner'):
        kfac_tpu.TunedPlan.from_json(missing)
    with pytest.raises(ValueError, match='unknown'):
        kfac_tpu.TunedPlan.from_json(dict(good, extra=1))
    bad_knobs = dict(good, knobs={'strategy': 'COMM_OPT'})
    with pytest.raises(ValueError):
        kfac_tpu.TunedPlan.from_json(bad_knobs)


def test_fingerprint_mismatch_falls_back_with_one_warning():
    cfg, *_ = _base()
    plan = autotune.autotune(cfg, measure=False).to_json()
    plan['fingerprint'] = dict(plan['fingerprint'], device_count=4096)
    reset_layout_warnings()
    with pytest.warns(LayoutPlanWarning):
        eng = DistributedKFAC(config=cfg, auto_layout=plan)
    assert not eng.auto_layout_applied
    # fell back to the explicit/default layout: full COMM-OPT mesh
    assert eng.grad_workers == WORLD
    # the warning is rate-limited: same cause never re-warns...
    import warnings as pywarnings

    with pywarnings.catch_warnings(record=True) as rec:
        pywarnings.simplefilter('always')
        eng2 = DistributedKFAC(config=cfg, auto_layout=plan)
    assert not eng2.auto_layout_applied
    assert not [r for r in rec if isinstance(r.message, LayoutPlanWarning)]
    # ...until reset (test isolation hook)
    reset_layout_warnings()
    with pytest.warns(LayoutPlanWarning):
        DistributedKFAC(config=cfg, auto_layout=plan)


def test_fingerprint_diff_reports_both_directions():
    cfg, *_ = _base()
    current = plan_lib.plan_fingerprint(cfg.registry)
    # a plan from an OLDER writer: one field doctored, one field the
    # current fingerprint carries missing entirely, and one extra field
    # only the plan has — the diff must surface all three
    stale = json.loads(json.dumps(current))
    stale['device_count'] = 4096
    missing = sorted(set(stale) - {'layers'})[0]
    del stale[missing]
    stale['legacy_only_field'] = 1
    diff = plan_lib.fingerprint_diff(stale, current)
    assert 'device_count' in diff
    assert missing in diff  # current-only key (old one-sided scan got this)
    assert 'legacy_only_field' in diff  # plan-only key (it missed this)
    assert diff == sorted(diff)
    # identical fingerprints (JSON-normalized tuples included) diff empty
    assert plan_lib.fingerprint_diff(current, json.loads(
        json.dumps(current))) == []
    # and the resolve-time warning names the plan-only key too
    doctored = autotune.autotune(cfg, measure=False).to_json()
    doctored['fingerprint']['legacy_only_field'] = 1
    reset_layout_warnings()
    with pytest.warns(LayoutPlanWarning, match='legacy_only_field'):
        eng = DistributedKFAC(config=cfg, auto_layout=doctored)
    assert not eng.auto_layout_applied
    reset_layout_warnings()


def test_model_fingerprint_mismatch_rejected():
    cfg, *_ = _base()
    plan = autotune.autotune(cfg, measure=False)
    other_cfg, *_ = _base()
    doctored = plan.to_json()
    doctored['fingerprint']['layers'] = {'not_my_model': [3, 3]}
    reset_layout_warnings()
    with pytest.warns(LayoutPlanWarning, match='fingerprint'):
        eng = DistributedKFAC(config=other_cfg, auto_layout=doctored)
    assert not eng.auto_layout_applied


# ------------------------------------------------------------ measured search


def test_measured_winner_not_worse_than_strategy_baselines():
    cfg, m, params, batch, loss_fn = _base(
        factor_update_steps=1, inv_update_steps=1
    )
    plan = autotune.autotune(
        cfg, loss_fn, params, batch,
        top_k=1, warmup=0, iters=1, granularities=(1,),
    )
    assert plan.winner['picked_by'] == 'measured'
    # by row, not by strategy: the model's own pick shares its strategy
    # with a baseline, and either may be the faster of the two
    measured = [r for r in plan.cost_table if r['measured']]
    # all three hand-configured strategies were actually timed
    assert {'COMM_OPT', 'HYBRID_OPT', 'MEM_OPT'} <= {
        r['knobs']['strategy'] for r in measured
    }
    assert plan.winner['measured_step_s'] == min(
        r['measured_step_s'] for r in measured
    )
    # the plan drives a real engine end to end
    eng = DistributedKFAC(config=cfg, auto_layout=plan)
    assert eng.auto_layout_applied
    state = eng.init()
    run = kfac_tpu.CurvatureCapture(cfg.registry).value_stats_and_grad(
        loss_fn
    )
    (loss, _), grads, stats = run(params, batch)
    state, pgrads = eng.step(state, grads, stats, loss=loss)
    assert all(
        bool(jnp.all(jnp.isfinite(v)))
        for v in jax.tree_util.tree_leaves(pgrads)
    )


def test_trainer_auto_layout_wiring(tmp_path):
    cfg, m, params, (x, y), _ = _base(lr=0.05)
    plan = autotune.autotune(cfg, measure=False)
    path = tmp_path / 'plan.json'
    plan.save(path)

    def loss_fn(p, model_state, batch):
        xx, yy = batch
        pred = m.apply({'params': p}, xx)
        return jnp.mean((pred - yy) ** 2), model_state

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=cfg,
        auto_layout=str(path),
    )
    assert trainer.kfac.auto_layout_applied
    state = trainer.init(params)
    losses = []
    for _ in range(3):
        state, loss = trainer.step(state, (x, y))
        losses.append(float(loss))
    assert all(jnp.isfinite(jnp.asarray(losses)))

    # guard rails: a plan needs a config to configure...
    with pytest.raises(ValueError, match='requires kfac'):
        training.Trainer(
            loss_fn=loss_fn, optimizer=optax.sgd(0.05),
            auto_layout=str(path),
        )
    # ...and a bare config, not an already-built engine
    eng = DistributedKFAC(config=cfg)
    with pytest.raises(ValueError, match='bare'):
        training.Trainer(
            loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=eng,
            auto_layout=str(path),
        )


# --------------------------------------------------------- async refresh knob


def test_predict_prices_async_refresh_spike():
    """The model prices the refresh spike the async backends flatten:
    sliced divides the boundary spike by the slice count, host drops the
    device decomposition FLOPs and pays only the payload transfer."""
    cfg, *_ = _base()
    hw = model_lib.HardwareSpec()

    def row(mode):
        cand = model_lib.Candidate(
            grad_worker_fraction=0.5, bucket_granularity=64,
            inv_update_steps=4, async_inverse=mode,
        )
        return model_lib.predict(cand, cfg, WORLD, hw)

    sync, sliced, host = row(None), row('sliced'), row('host')
    assert sync['refresh_spike_s'] > 0
    # sliced: same total device work, spread over the window's slices
    assert (
        sliced['flops_per_device_per_step']
        == sync['flops_per_device_per_step']
    )
    assert sliced['refresh_spike_s'] < sync['refresh_spike_s']
    # host: decomposition FLOPs leave the device entirely; the spike is
    # the boundary device_put of the refreshed payload
    assert (
        host['flops_per_device_per_step'] < sync['flops_per_device_per_step']
    )
    assert host['refresh_spike_s'] == (
        sync['bytes_per_occurrence']['decomp_reshard'] / hw.host_bandwidth
    )
    for r in (sync, sliced, host):
        assert r['predicted_step_s'] > 0


def test_async_base_widens_inverse_cadence_grid():
    cfg, *_ = _base(
        factor_update_steps=2, inv_update_steps=2, async_inverse='sliced'
    )
    cands = autotune.enumerate_candidates(WORLD, cfg)
    # fractions x granularities x transports x {c, 2c, 4c}
    assert len(cands) == 4 * 4 * 2 * 3
    assert {c.inv_update_steps for c in cands} == {2, 4, 8}
    assert all(c.async_inverse == 'sliced' for c in cands)
    bases = autotune.baseline_candidates(WORLD, cfg)
    assert all(b.async_inverse == 'sliced' for b in bases)
    assert all(b in cands for b in bases)
    # a sync base keeps the original one-cadence grid
    sync_cfg, *_ = _base()
    assert len(autotune.enumerate_candidates(WORLD, sync_cfg)) == 4 * 4 * 2


def test_async_knob_rides_the_plan_roundtrip(tmp_path):
    cfg, *_ = _base(inv_update_steps=2, async_inverse='host')
    plan = autotune.autotune(cfg, measure=False)
    assert plan.knobs['async_inverse'] == 'host'
    path = tmp_path / 'plan.json'
    plan.save(path)
    loaded = kfac_tpu.TunedPlan.load(path)
    new = autotune.apply_knobs(cfg, loaded.knobs)
    assert new.async_inverse == kfac_tpu.AsyncInverseConfig(mode='host')


def test_pre_async_plan_document_still_loads():
    """Plans written before the async knob existed lack
    ``knobs.async_inverse``; loading fills the sync default."""
    cfg, *_ = _base()
    doc = autotune.autotune(cfg, measure=False).to_json()
    legacy = json.loads(json.dumps(doc))
    del legacy['knobs']['async_inverse']
    loaded = kfac_tpu.TunedPlan.from_json(legacy)
    assert loaded.knobs['async_inverse'] is None
    applied = autotune.apply_knobs(cfg, loaded.knobs)
    assert applied.async_inverse is None


@pytest.mark.parametrize('knob,value', [
    ('stat_compression', None), ('offload', False),
    ('stat_compression', 'int8'), ('offload', True),
])
def test_plan_with_a_removed_knob(knob, value):
    """Every plan written while stat compression and cold-factor offload
    were knobs carries both: at its off value the key is ignored (the plan
    loads and applies as it always did), set it asks for a layout nothing
    builds any more and is refused like any malformed plan, by name."""
    cfg, *_ = _base()
    doc = autotune.autotune(cfg, measure=False).to_json()
    assert knob not in doc['knobs']  # no plan is written with it now
    old = json.loads(json.dumps(doc))
    old['knobs'][knob] = value
    if value:
        with pytest.raises(ValueError, match=knob):
            kfac_tpu.TunedPlan.from_json(old)
        return
    loaded = kfac_tpu.TunedPlan.from_json(old)
    assert loaded.knobs == doc['knobs']
    applied = autotune.apply_knobs(cfg, loaded.knobs)
    assert applied == autotune.apply_knobs(cfg, doc['knobs'])
    assert not hasattr(applied, knob)


def test_apply_knobs_only_touches_layout_fields():
    cfg, *_ = _base()
    plan = autotune.autotune(cfg, measure=False)
    new = autotune.apply_knobs(cfg, plan.knobs)
    assert new.bucket_granularity == plan.knobs['bucket_granularity']
    assert new.allreduce_method.name == plan.knobs['allreduce_method']
    assert new.colocate_factors == plan.knobs['colocate_factors']
    # non-layout fields ride through untouched
    assert new.damping == cfg.damping
    assert new.registry is cfg.registry
