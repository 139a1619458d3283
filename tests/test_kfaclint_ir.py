"""IR-tier (KFL201–KFL205) analyzer suite.

Each rule gets a true-positive fixture (synthetic jaxpr or doctored
trace) and a clean negative; the cost-model parity tests assert the
acceptance bar from ISSUE 9 directly — jaxpr-counted collective bytes
for the three canonical KAISA strategies equal ``comms_report()``
byte-for-byte, and decomposition FLOPs equal
``autotune.model.decomp_flops()`` exactly. The full strategy × method ×
transport matrix runs behind the ``slow`` marker.
"""

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu import analysis
from kfac_tpu.analysis import drift
from kfac_tpu.analysis.ir import harness, rules, visitor

ALL_CHECKS = (
    rules.check_dtype_drift,
    rules.check_collective_axes,
    rules.check_sharding_contract,
    rules.check_step_callbacks,
    rules.check_cost_model_parity,
)


def run_all(suite):
    out = []
    for check in ALL_CHECKS:
        out.extend(check(suite))
    return out


@pytest.fixture(scope='session')
def smoke_suite():
    return harness.build('smoke')


@pytest.fixture(scope='session')
def default_suite():
    return harness.build('default')


def make_trace(fn, *args, tainted=None, step_path=False, allow=frozenset(),
               entry='step', **over):
    """Synthetic EngineTrace around a hand-written traced function."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    n = len(jaxpr.jaxpr.invars)
    return harness.EngineTrace(
        config_name='synthetic', engine='kaisa', entry=entry, jaxpr=jaxpr,
        path='kfac_tpu/analysis/ir/harness.py', line=1,
        world=len(jax.devices()), step_path=step_path,
        tainted_invars=list(tainted) if tainted is not None else [True] * n,
        callback_allowlist=allow, cfg=None, **over,
    )


def suite_of(*traces, errors=()):
    return harness.Suite('synthetic', list(traces), list(errors))


# ------------------------------------------------------------------ KFL201


def test_kfl201_flags_bf16_demotion_in_factor_math():
    def factor_update(a, stat):
        ema = 0.95 * a + 0.05 * stat.astype(jnp.bfloat16)  # the bug
        return ema @ ema.T

    x = jnp.zeros((4, 4), jnp.float32)
    findings = rules.check_dtype_drift(suite_of(make_trace(factor_update, x, x)))
    assert findings and all(f.code == 'KFL201' for f in findings)
    assert any('bfloat16' in f.message for f in findings)


def test_kfl201_flags_f64_promotion():
    with jax.enable_x64(True):
        def factor_update(a):
            return a @ a.astype(jnp.float64).T

        x = jnp.zeros((4, 4), jnp.float32)
        trace = make_trace(factor_update, x)
    findings = rules.check_dtype_drift(suite_of(trace))
    assert findings and all(f.code == 'KFL201' for f in findings)
    assert any('float64' in f.message for f in findings)


def test_kfl201_clean_on_f32_math_with_untainted_low_precision():
    def factor_update(a, wire):
        # a bf16 value NOT derived from factor math is not a finding
        # (e.g. activations in a mixed-precision fwd pass)
        _ = wire.astype(jnp.bfloat16)
        return 0.95 * a + 0.05 * (a @ a.T)

    x = jnp.zeros((4, 4), jnp.float32)
    trace = make_trace(factor_update, x, x, tainted=[True, False])
    assert rules.check_dtype_drift(suite_of(trace)) == []


def test_kfl201_taint_flows_through_while_loop():
    def ns_iter(a):
        def body(carry):
            i, m = carry
            return i + 1, (m @ m).astype(jnp.bfloat16).astype(jnp.float32)

        return jax.lax.while_loop(
            lambda c: c[0] < 3, body, (jnp.int32(0), a)
        )[1]

    x = jnp.zeros((4, 4), jnp.float32)
    findings = rules.check_dtype_drift(suite_of(make_trace(ns_iter, x)))
    assert any('bfloat16' in f.message for f in findings)


def test_kfl201_reports_trace_errors_once():
    suite = suite_of(errors=[('broken-config', '<config>', 'ValueError: x')])
    findings = rules.check_dtype_drift(suite)
    assert len(findings) == 1 and 'failed to trace' in findings[0].message


# ------------------------------------------------------------------ KFL202


def _rogue_mesh_trace():
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ('rogue',))
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec('rogue'))

    def pin(x):
        return jax.lax.with_sharding_constraint(x, spec)

    return make_trace(pin, jnp.zeros((len(jax.devices()),), jnp.float32))


def test_kfl202_flags_undeclared_axis():
    findings = rules.check_collective_axes(suite_of(_rogue_mesh_trace()))
    assert findings and all(f.code == 'KFL202' for f in findings)
    assert any("'rogue'" in f.message for f in findings)


def test_kfl202_clean_on_declared_axes(smoke_suite):
    assert rules.check_collective_axes(smoke_suite) == []


def test_kfl202_flags_chunk_plan_mismatch(smoke_suite):
    t = next(x for x in smoke_suite.traces if x.entry == 'update_factors')
    bad = copy.copy(t)
    bad.comms = copy.deepcopy(t.comms)
    st = bad.comms['stat_transport']
    st['chunks'] = []  # doctored plan: declares a count the IR can't match
    st['collectives'] = 999
    findings = rules.check_collective_axes(suite_of(bad))
    assert [f.code for f in findings] == ['KFL202']
    assert 'chunk plan' in findings[0].message


# ------------------------------------------------------------------ KFL203


def test_kfl203_flags_undeclared_state_field(smoke_suite):
    t = next(x for x in smoke_suite.traces
             if x.entry == 'step' and x.declared_shardings is not None)
    bad = copy.copy(t)
    # doctor the declared tree so its structure no longer matches the
    # real state — the drifted-contract hazard the rule exists for
    bad.declared_shardings = {'doctored': t.declared_shardings}
    findings = rules.check_sharding_contract(suite_of(bad))
    assert [f.code for f in findings] == ['KFL203']
    assert 'differs from the real state tree' in findings[0].message


def test_kfl203_clean_on_real_contract(smoke_suite):
    assert rules.check_sharding_contract(smoke_suite) == []


def test_kfl203_dense_engine_has_no_contract_and_is_skipped(default_suite):
    dense = [t for t in default_suite.traces if t.engine == 'dense']
    assert dense, 'default profile must include the dense engine'
    assert all(t.declared_shardings is None for t in dense)


# ------------------------------------------------------------------ KFL204


def _callback_step_trace(allow):
    def step(x):
        jax.experimental.io_callback(
            lambda v: None, None, x, ordered=False
        )
        return x + 1

    return make_trace(step, jnp.zeros((2,), jnp.float32),
                      step_path=True, allow=allow)


def test_kfl204_flags_undeclared_step_callback():
    findings = rules.check_step_callbacks(suite_of(_callback_step_trace(
        frozenset()
    )))
    assert [f.code for f in findings] == ['KFL204']
    assert 'io_callback' in findings[0].message


def test_kfl204_allowlisted_callback_is_clean():
    assert rules.check_step_callbacks(suite_of(_callback_step_trace(
        frozenset({'io_callback'})
    ))) == []


def test_kfl204_async_host_config_is_allowlisted(default_suite):
    t = next(x for x in default_suite.traces
             if 'async-host' in x.config_name and x.entry == 'step')
    # the callback is really there AND really allowlisted — the rule's
    # pass on this config is a decision, not absence of signal
    assert visitor.callback_eqns(t.jaxpr)
    assert 'io_callback' in t.callback_allowlist
    assert rules.check_step_callbacks(default_suite) == []


def test_kfl204_ignores_off_step_path_entries():
    trace = _callback_step_trace(frozenset())
    trace.step_path = False
    assert rules.check_step_callbacks(suite_of(trace)) == []


# ------------------------------------------------------------------ KFL205

#: world=8 maps the canonical fracs onto the three KAISA strategies
CANONICAL = {1.0: 'COMM_OPT', 0.5: 'HYBRID_OPT', 0.125: 'MEM_OPT'}


@pytest.fixture(scope='session')
def canonical_traces():
    world = len(jax.devices())
    out = {}
    for frac in CANONICAL:
        spec = harness._ConfigSpec(
            f'parity-f{frac}', 'kaisa', 16, frac, {}
        )
        out[frac] = {t.entry: t for t in harness._trace_config(spec, world)}
    return out


@pytest.mark.parametrize('frac', sorted(CANONICAL))
def test_kfl205_byte_parity_three_canonical_strategies(
    canonical_traces, frac
):
    # the acceptance bar: jaxpr-counted collective bytes == comms_report,
    # byte-for-byte, for COMM_OPT / HYBRID_OPT / MEM_OPT
    by = canonical_traces[frac]
    comms = by['update_factors'].comms
    assert comms['strategy'] == CANONICAL[frac]

    uf = visitor.constraint_pins(by['update_factors'].jaxpr)
    assert visitor.replicated_pin_bytes(uf) == (
        comms['stat_transport']['wire_bytes']
    )

    ui = visitor.constraint_pins(by['update_inverses'].jaxpr)
    assert visitor.total_pin_bytes(ui) == comms['decomp_reshard_bytes']

    pc = visitor.constraint_pins(by['precondition'].jaxpr)
    mult = 2 if comms['strategy'] == 'COMM_OPT' else 1  # documented: the
    # replicated eigenbasis under COMM_OPT pins the broadcast twice
    assert visitor.rank3_replicated_pin_bytes(pc) == (
        comms['grad_broadcast_bytes'] * mult
    )


def test_kfl205_comm_opt_with_inverses_pins_no_stack():
    # explicit inverses resident on every device: each layer multiplies
    # against its own slots, so precondition holds no rank-3 pin at all
    import kfac_tpu

    spec = harness._ConfigSpec(
        'parity-ns-comm', 'kaisa', 16, 1.0,
        dict(compute_method=kfac_tpu.ComputeMethod.INVERSE,
             inverse_solver='newton_schulz', newton_schulz_iters=6),
    )
    traces = harness._trace_config(spec, len(jax.devices()))
    t = next(x for x in traces if x.entry == 'precondition')
    assert t.comms['strategy'] == 'COMM_OPT'
    pins = visitor.constraint_pins(t.jaxpr)
    assert visitor.rank3_replicated_pin_bytes(pins) == 0
    assert rules.check_cost_model_parity(suite_of(*traces)) == []


def test_kfl205_eigh_flop_parity(canonical_traces):
    t = canonical_traces[0.5]['update_inverses']
    got = visitor.eigh_flops(t.jaxpr) * t.world
    assert got == t.expected_decomp_flops  # exact, not approximate


def test_kfl205_newton_schulz_flop_parity():
    import kfac_tpu

    world = len(jax.devices())
    spec = harness._ConfigSpec(
        'parity-ns', 'kaisa', 16, 0.5,
        dict(compute_method=kfac_tpu.ComputeMethod.INVERSE,
             inverse_solver='newton_schulz', newton_schulz_iters=6),
    )
    by = {t.entry: t for t in harness._trace_config(spec, world)}
    t = by['update_inverses']
    got = visitor.while_dot_flops(t.jaxpr, t.cfg.newton_schulz_iters) * world
    assert got == t.expected_decomp_flops


def test_kfl205_flags_model_divergence(smoke_suite):
    t = next(x for x in smoke_suite.traces if x.entry == 'update_factors')
    bad = copy.copy(t)
    bad.comms = copy.deepcopy(t.comms)
    bad.comms['stat_transport']['wire_bytes'] += 4
    findings = rules.check_cost_model_parity(suite_of(bad))
    assert [f.code for f in findings] == ['KFL205']
    assert 'cost model' in findings[0].message


def test_kfl205_clean_at_head(default_suite):
    assert rules.check_cost_model_parity(default_suite) == []


def test_kfl205_skips_async_host_decomposition(default_suite):
    # async-host moves the decomposition out of the traced program; its
    # update_inverses must be skipped by parity, not falsely flagged
    t = next(x for x in default_suite.traces
             if 'async-host' in x.config_name and x.entry == 'update_inverses')
    assert not rules._decomp_in_jit(t.cfg)


# ------------------------------------------------------- head-clean + wiring


def test_smoke_profile_clean_at_head(smoke_suite):
    findings = run_all(smoke_suite)
    assert findings == [], [f.render() for f in findings]
    assert smoke_suite.errors == []


def test_default_profile_clean_at_head(default_suite):
    findings = run_all(default_suite)
    assert findings == [], [f.render() for f in findings]
    assert default_suite.errors == []


@pytest.mark.slow
def test_full_matrix_clean_at_head():
    suite = harness.build('full')
    assert suite.errors == []
    # the full matrix must include prediv, host-eigh and the sub-unity
    # fractions — guard against silent profile shrinkage
    names = {t.config_name for t in suite.traces}
    assert any('prediv' in n for n in names)
    assert any('eigh-host' in n for n in names)
    findings = run_all(suite)
    assert findings == [], [f.render() for f in findings]


def test_ir_rules_registered_with_ir_kind():
    by_code = {r.code: r for r in analysis.all_rules()}
    for code in analysis.IR_RULE_CODES:
        assert code in by_code, code
        assert by_code[code].kind == 'ir'


def test_both_engines_register_entry_points():
    from kfac_tpu import preconditioner
    from kfac_tpu.parallel import kaisa

    for cls in (preconditioner.KFACPreconditioner, kaisa.DistributedKFAC):
        assert cls.IR_ENTRY_POINTS == (
            'update_factors', 'update_inverses', 'precondition', 'step',
        )
        assert set(cls.IR_STEP_PATH) <= set(cls.IR_ENTRY_POINTS)
        for entry in cls.IR_ENTRY_POINTS:
            assert callable(getattr(cls, entry))


def test_trace_targets_cover_both_engines(default_suite):
    engines = {t.engine for t in default_suite.traces}
    assert engines == {'kaisa', 'dense'}
    entries = {t.entry for t in default_suite.traces}
    assert entries == set(
        ('update_factors', 'update_inverses', 'precondition', 'step')
    )


def test_finding_paths_anchor_to_real_entry_defs(smoke_suite):
    for t in smoke_suite.traces:
        assert os.path.exists(os.path.join(drift.REPO_ROOT, t.path)), t.path
        assert t.line > 1


def test_cli_ir_smoke_exits_clean(monkeypatch):
    import sys  # noqa: F401

    monkeypatch.syspath_prepend(os.path.join(drift.REPO_ROOT, 'tools'))
    import kfaclint

    assert kfaclint.main(['--ir', '--smoke']) == 0


def test_invalid_profile_rejected():
    with pytest.raises(ValueError, match='unknown IR profile'):
        harness.set_profile('warp')
