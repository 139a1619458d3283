"""Measurement truth layer: the one-dispatch microbench harness, the
latency-floor detector, and the dispatch-threshold artifact.

All CPU-runnable: the harness's fori_loop and legacy dispatch modes are
the SAME chained math (pinned by equivalence here), so everything but
the absolute numbers is testable off-chip. See docs/OBSERVABILITY.md
"Measurement truth".
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu.ops import dispatch_tables

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', 'tools'))
)
import tpu_microbench as mb  # noqa: E402

import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_tables(monkeypatch):
    """Each test sees the real committed artifact unless it overrides
    the env var itself; the cache never leaks across tests."""
    monkeypatch.delenv(dispatch_tables.ENV_VAR, raising=False)
    dispatch_tables.invalidate_cache()
    yield
    dispatch_tables.invalidate_cache()


# ------------------------------------------------------ harness equivalence


def test_chain_result_fori_equals_legacy():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(16, 16)),
                    jnp.float32)

    def fn(a):
        return a @ a.T * 0.5 + 1.0

    fori = mb.chain_result(fn, x, iters=4, warmup=2, mode='fori_loop')
    legacy = mb.chain_result(fn, x, iters=4, warmup=2, mode='legacy')
    np.testing.assert_allclose(np.asarray(fori), np.asarray(legacy),
                               rtol=1e-5, atol=1e-5)


def test_chain_result_equivalence_pytree_multi_arg():
    rng = np.random.default_rng(1)
    tree = {
        'a': jnp.asarray(rng.normal(size=(8, 8)), jnp.float32),
        'ids': jnp.arange(8),  # int leaf must pass through unscaled
    }
    damping = jnp.float32(0.1)

    def fn(t, d):
        return {'y': t['a'] * (1.0 + d), 'z': jnp.sum(t['a'], axis=0)}

    fori = mb.chain_result(fn, tree, damping, iters=3, mode='fori_loop')
    legacy = mb.chain_result(fn, tree, damping, iters=3, mode='legacy')
    for k in ('y', 'z'):
        np.testing.assert_allclose(np.asarray(fori[k]),
                                   np.asarray(legacy[k]),
                                   rtol=1e-5, atol=1e-5)


def test_chain_is_a_real_dependency():
    """Successive iterations must produce different values (the perturbed
    scale) — a memoizable constant chain would defeat the measurement."""
    x = jnp.ones((4, 4), jnp.float32)
    one = mb.chain_result(lambda a: a * 2.0, x, iters=1, mode='legacy')
    two = mb.chain_result(lambda a: a * 2.0, x, iters=2, mode='legacy')
    assert not np.allclose(np.asarray(one), np.asarray(two))


# ----------------------------------------------------------- timeit contract


def test_timeit_fori_is_one_dispatch():
    x = jnp.ones((8, 8), jnp.float32)
    t = mb.timeit(lambda a: a @ a, x, iters=5, mode='fori_loop')
    assert isinstance(t, mb.Timing)
    assert float(t) > 0.0
    assert t.provenance == {
        'harness_version': mb.HARNESS_VERSION,
        'dispatch_mode': 'fori_loop',
        'dispatches': 1,
        'iters': 5,
    }


def test_timeit_legacy_mode_counts_dispatches():
    x = jnp.ones((8, 8), jnp.float32)
    t = mb.timeit(lambda a: a @ a, x, iters=4, mode='legacy')
    assert t.provenance['dispatch_mode'] == 'legacy'
    assert t.provenance['dispatches'] == 4


def test_timeit_falls_back_when_fn_cannot_trace():
    """AOT executables / host-round-trip callables can't run under jit:
    the harness must degrade to the legacy host loop, and say so."""
    x = jnp.ones((4, 4), jnp.float32)

    def untraceable(a):
        return jnp.asarray(np.asarray(a) * 2.0)  # concretizes: no tracers

    t = mb.timeit(untraceable, x, iters=3, mode='fori_loop')
    assert t.provenance['dispatch_mode'] == 'legacy'
    assert t.provenance['dispatches'] == 3


def test_report_lifts_provenance(capsys):
    mb.report('some_op', mb.Timing(0.002, {'dispatch_mode': 'fori_loop',
                                           'dispatches': 1}))
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec == {'op': 'some_op', 'ms': 2.0,
                   'dispatch_mode': 'fori_loop', 'dispatches': 1}


def test_bench_measurement_block_matches_harness():
    """bench.py hardcodes the provenance block (it must not import jax
    via tpu_microbench at orchestrator scope) — pin the copies."""
    assert bench._MEASUREMENT['harness_version'] == mb.HARNESS_VERSION
    assert bench._MEASUREMENT['dispatch_mode'] == mb._dispatch_mode()


# -------------------------------------------------------- floor detector


def test_floor_detector_flags_flat_sweep():
    verdict = dispatch_tables.latency_floor_verdict(
        [256, 512, 1024, 2048], [0.0716, 0.0756, 0.0828, 0.0753],
    )
    assert verdict is not None and verdict['contaminated']
    assert verdict['expected_ratio'] == 64.0
    assert verdict['n'] == 4
    assert verdict['floor_ms'] == pytest.approx(71.6)


def test_floor_detector_passes_scaling_sweep():
    sizes = [256, 512, 1024, 2048]
    verdict = dispatch_tables.latency_floor_verdict(
        sizes, [0.001 * (s / 256) ** 2 for s in sizes],
    )
    assert verdict is not None and not verdict['contaminated']


def test_floor_detector_abstains_without_evidence():
    # one point: nothing to compare
    assert dispatch_tables.latency_floor_verdict([512], [0.01]) is None
    # the sweep never leaves the latency-bound regime (work ratio < 4x)
    assert dispatch_tables.latency_floor_verdict(
        [128, 160], [0.01, 0.0101]) is None
    # None entries (errored ops) are dropped before judging
    assert dispatch_tables.latency_floor_verdict(
        [128, 256, 512], [None, 0.01, None]) is None


def test_report_floor_verdicts_emits_lines(capsys):
    verdicts = mb.report_floor_verdicts({
        'cov_dense_f32': (2.0, [(256, 0.075), (512, 0.076), (1024, 0.08),
                                (2048, 0.075)]),
        'eigh': (3.0, [(128, None)]),  # too thin: no line
    })
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln['op'] for ln in lines] == ['floor/cov_dense_f32']
    assert lines[0]['contaminated'] is True
    assert set(verdicts) == {'cov_dense_f32'}


# --------------------------------------------------------- artifact loading


def test_committed_artifact_loads_from_a_clean_sweep():
    doc = dispatch_tables.load_tables(dispatch_tables.ARTIFACT_PATH)
    assert doc['schema'] == dispatch_tables.SCHEMA_VERSION
    assert doc['cov_ema']['min_dim'] == 256
    assert doc['cov_ema']['dtypes'] == ['float32']
    assert doc['attn']['min_sk_dense'] == 2048
    # re-derived from the clean one-dispatch sweep: no contaminated
    # baselines remain (the latency-floor-contaminated v1 numbers are
    # retired), and everything still at its prior says why
    assert doc['provenance']['contaminated'] == {}
    assert 'cov_ema' in doc['provenance']['held']
    assert doc['provenance']['source']['records'] > 0


def test_accessors_fall_back_on_missing_artifact(monkeypatch, tmp_path):
    monkeypatch.setenv(dispatch_tables.ENV_VAR,
                       str(tmp_path / 'does_not_exist.json'))
    dispatch_tables.invalidate_cache()
    assert dispatch_tables.load_tables() == {}
    assert dispatch_tables.family_min_dim('cov_ema', default=321) == 321
    assert dispatch_tables.family_dtypes('cov_ema') == ('float32',)
    assert dispatch_tables.flash_min_sk_dense(default=4096) == 4096


def test_accessors_fall_back_on_schema_mismatch(monkeypatch, tmp_path):
    p = tmp_path / 'future.json'
    p.write_text(json.dumps({'schema': 99, 'cov_ema': {'min_dim': 1}}))
    monkeypatch.setenv(dispatch_tables.ENV_VAR, str(p))
    dispatch_tables.invalidate_cache()
    assert dispatch_tables.load_tables() == {}
    assert dispatch_tables.family_min_dim('cov_ema', default=256) == 256


def test_env_override_redirects_the_gates(monkeypatch, tmp_path):
    p = tmp_path / 'tuned.json'
    p.write_text(json.dumps({
        'schema': 1,
        'cov_ema': {'min_dim': 512, 'dtypes': ['float32', 'bfloat16']},
        'attn': {'min_sk_dense': 1024},
    }))
    monkeypatch.setenv(dispatch_tables.ENV_VAR, str(p))
    dispatch_tables.invalidate_cache()
    assert dispatch_tables.family_min_dim('cov_ema', default=256) == 512
    assert dispatch_tables.family_dtypes('cov_ema') == (
        'float32', 'bfloat16')
    assert dispatch_tables.flash_min_sk_dense(default=2048) == 1024


def test_gate_functions_consume_the_tables(monkeypatch, tmp_path):
    """use_fused_cov_ema_for / use_flash_for read the artifact through
    the accessors (off-TPU both still return False — backend check — so
    this pins the plumbing via the accessors the gates call)."""
    from kfac_tpu.ops import pallas_attention, pallas_cov_ema

    assert pallas_cov_ema.use_fused_cov_ema_for(1024, jnp.float32) is False
    assert pallas_attention.use_flash_for(128, 2048, 128, dense=True) is False
    # and the threshold values they would compare against come from the
    # committed artifact
    assert dispatch_tables.family_min_dim('cov_ema', default=0) == 256
    assert dispatch_tables.flash_min_sk_dense(default=0) == 2048


# -------------------------------------------------------------- derivation


def test_derive_attn_needs_min_win_points():
    ops = [{'op': f'attn_einsum_s{s}', 'ms': m}
           for s, m in [(512, 1.0), (1024, 4.0), (2048, 290.0)]]
    ops += [{'op': f'attn_flash_s{s}', 'ms': m}
            for s, m in [(512, 5.0), (1024, 6.0), (2048, 0.9)]]
    t = dispatch_tables.derive_tables(ops)
    assert t['attn']['min_sk_dense'] == (
        dispatch_tables.DEFAULTS['attn']['min_sk_dense'])
    assert 'attn/min_sk_dense' in t['provenance']['held']
    # two winning lengths flips it
    ops[-2]['ms'] = 2.0
    t = dispatch_tables.derive_tables(ops)
    assert t['attn']['min_sk_dense'] == 1024


def test_derive_tool_selftest_runs():
    import derive_dispatch_tables

    derive_dispatch_tables.selftest()
