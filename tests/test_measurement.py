"""Measurement truth layer: the one-dispatch microbench harness and its
latency-floor detector.

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

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', 'tools'))
)
import tpu_microbench as mb  # noqa: E402


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


# -------------------------------------------------------- floor detector


def test_floor_detector_flags_flat_sweep():
    verdict = mb.latency_floor_verdict(
        [256, 512, 1024, 2048], [0.0716, 0.0756, 0.0828, 0.0753],
    )
    assert verdict is not None and verdict['contaminated']
    assert verdict['expected_ratio'] == 64.0
    assert verdict['n'] == 4
    assert verdict['floor_ms'] == pytest.approx(71.6)


def test_floor_detector_passes_scaling_sweep():
    sizes = [256, 512, 1024, 2048]
    verdict = mb.latency_floor_verdict(
        sizes, [0.001 * (s / 256) ** 2 for s in sizes],
    )
    assert verdict is not None and not verdict['contaminated']


def test_floor_detector_abstains_without_evidence():
    # one point: nothing to compare
    assert mb.latency_floor_verdict([512], [0.01]) is None
    # the sweep never leaves the latency-bound regime (work ratio < 4x)
    assert mb.latency_floor_verdict(
        [128, 160], [0.01, 0.0101]) is None
    # None entries (errored ops) are dropped before judging
    assert mb.latency_floor_verdict(
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
