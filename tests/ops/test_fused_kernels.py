"""Fused step-path kernels (docs/ARCHITECTURE.md "Fused step-path
kernels"): equivalence contracts, dispatch gates, threshold derivation,
autotune FLOP parity (KFL205-style), and the two lint rules that pin the
family (KFL110 doc drift, KFL206 kernel allowlist).

Everything runs in Pallas interpret mode (CPU backend); the shared
inputs and the expensive fused/unfused result pairs are module-scope
fixtures so each kernel compiles once per session.
"""

import json
import warnings as pywarnings
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu import warnings as kfac_warnings
from kfac_tpu.ops import dispatch_tables, factors, pallas_cov_ema, pallas_ns
from kfac_tpu.ops.cov import get_cov
from kfac_tpu.ops.pallas_cov_ema import K_BLOCK, TILE

BETA = 0.95
N, D = 512, 256


# ----------------------------------------------------- shared inputs


@pytest.fixture(scope='module')
def rng():
    return np.random.default_rng(20260806)


@pytest.fixture(scope='module')
def cov_inputs(rng):
    a = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    f = jnp.asarray(rng.standard_normal((D, D)), jnp.float32)
    f = 0.5 * (f + f.T)  # the running factor is symmetric by invariant
    return f, a


@pytest.fixture(scope='module')
def cov_ema_pair(cov_inputs):
    """(fused, unfused) cov+EMA results — one compile each, shared by
    the equivalence and symmetry tests."""
    f, a = cov_inputs
    coeff = (1.0 - BETA) / N
    fused = pallas_cov_ema._fused(f, a, BETA, coeff, interpret=True)
    unfused = factors.ema_update(f, get_cov(a, scale=N), BETA)
    return np.asarray(fused), np.asarray(unfused)


# ----------------------------------------------------- cov+EMA fusion


def test_fused_cov_ema_matches_unfused_pair(cov_ema_pair):
    fused, unfused = cov_ema_pair
    np.testing.assert_allclose(fused, unfused, rtol=1e-5, atol=1e-5)


def test_fused_cov_ema_exactly_symmetric(cov_ema_pair):
    fused, _ = cov_ema_pair
    # mirror-the-upper-triangle construction: symmetry is exact, not
    # approximate — no defensive (C + C^T)/2 anywhere downstream
    assert np.array_equal(fused, fused.T)


def test_fused_cov_ema_padding_case(rng):
    # n, d both off the K_BLOCK/TILE grid: padded rows/cols contribute
    # exact zeros to the contraction and the pad-region EMA is cropped
    n, d = 640, 192
    assert n % K_BLOCK != 0 and d % TILE != 0
    a = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    f = jnp.asarray(rng.standard_normal((d, d)), jnp.float32)
    f = 0.5 * (f + f.T)
    coeff = (1.0 - BETA) / n
    fused = pallas_cov_ema._fused(f, a, BETA, coeff, interpret=True)
    unfused = factors.ema_update(f, get_cov(a, scale=n), BETA)
    np.testing.assert_allclose(fused, unfused, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(fused), np.asarray(fused).T)


def test_fused_cov_ema_stacked_vmap(rng):
    fs = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.float32)
    fs = 0.5 * (fs + jnp.swapaxes(fs, -1, -2))
    As = jnp.asarray(rng.standard_normal((2, 256, 128)), jnp.float32)
    coeff = (1.0 - BETA) / 256
    fused = jax.vmap(
        lambda f, a: pallas_cov_ema._fused(f, a, BETA, coeff, interpret=True)
    )(fs, As)
    unfused = jax.vmap(
        lambda f, a: factors.ema_update(f, get_cov(a, scale=256), BETA)
    )(fs, As)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(unfused), rtol=1e-5, atol=1e-5
    )


def test_fused_cov_ema_dispatcher_falls_back_on_cpu(cov_inputs):
    # off-TPU the dispatcher must run literally the unfused pair, so the
    # outputs are bitwise identical — not merely allclose
    f, a = cov_inputs
    out = pallas_cov_ema.fused_cov_ema(f, a, BETA)
    ref = factors.ema_update(f, get_cov(a, scale=N), BETA)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_fused_cov_ema_cold_start_matches_ema_update(rng):
    a = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    out = pallas_cov_ema.fused_cov_ema(None, a, BETA)
    ref = factors.ema_update(None, get_cov(a, scale=256), BETA)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# ----------------------------------------------------- kl-clip fusion


def test_fused_klclip_dot_matches(rng):
    # rectangular + off-tile dims: zero padding is exact for the
    # multiply-reduce; tiled accumulation order differs from XLA's, so
    # allclose rather than bitwise
    p = jnp.asarray(rng.standard_normal((200, 72)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((200, 72)), jnp.float32)
    got = pallas_ns.fused_klclip_dot(p, g, interpret=True)
    want = jnp.sum(p * g)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-3)


def test_fused_klclip_scale_matches(rng):
    p = jnp.asarray(rng.standard_normal((200, 72)), jnp.float32)
    s = jnp.asarray(0.37, jnp.float32)
    got = pallas_ns.fused_klclip_scale(p, s, interpret=True)
    assert got.shape == p.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(p * s), rtol=1e-6, atol=0
    )


# ----------------------------------------------------- dispatch gates


def test_gates_stay_off_cpu_even_when_enabled(monkeypatch):
    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    assert not pallas_cov_ema.use_fused_cov_ema_for(4096, jnp.float32)
    assert not pallas_ns.use_fused_klclip_for((4096, 4096))


def test_gate_win_regimes_under_committed_artifact(monkeypatch):
    """The committed artifact holds every fused family at its prior
    (cov_ema 256/f32, klclip 512); faking the TPU backend pins the gates
    to exactly those regimes."""
    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(jax, 'devices', lambda *a: [object()])
    assert pallas_cov_ema.use_fused_cov_ema_for(256, jnp.float32)
    assert not pallas_cov_ema.use_fused_cov_ema_for(128, jnp.float32)
    assert not pallas_cov_ema.use_fused_cov_ema_for(256, jnp.bfloat16)
    assert pallas_ns.use_fused_klclip_for((512, 512))
    assert pallas_ns.use_fused_klclip_for((1024, 256))  # same traffic
    assert not pallas_ns.use_fused_klclip_for((64, 64))
    assert not pallas_ns.use_fused_klclip_for((512, 512, 2))


@pytest.fixture
def contaminated_artifact(monkeypatch, tmp_path):
    """Point the gates at an artifact whose fused baselines are all
    latency-floor contaminated, with the warning dedupe reset."""
    art = json.loads(json.dumps(dispatch_tables.DEFAULTS))
    art['schema'] = dispatch_tables.SCHEMA_VERSION
    art['provenance'] = {'contaminated': {
        f'{fam}_unfused': {'contaminated': True, 'reason': 'flat'}
        for fam in ('cov_ema', 'klclip')
    }}
    p = tmp_path / 'contaminated.json'
    p.write_text(json.dumps(art))
    monkeypatch.setenv(dispatch_tables.ENV_VAR, str(p))
    dispatch_tables.invalidate_cache()
    kfac_warnings.reset_dispatch_warnings()
    yield p
    dispatch_tables.invalidate_cache()
    kfac_warnings.reset_dispatch_warnings()


def test_gate_holds_on_contaminated_floor_and_warns_once(
    monkeypatch, contaminated_artifact
):
    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(jax, 'devices', lambda *a: [object()])
    with pytest.warns(kfac_warnings.DispatchTableWarning) as rec:
        assert not pallas_cov_ema.use_fused_cov_ema_for(4096, jnp.float32)
    assert 'cov_ema_unfused' in str(rec[0].message)
    # once per family: the repeat gate check stays silent
    with pywarnings.catch_warnings():
        pywarnings.simplefilter('error')
        assert not pallas_cov_ema.use_fused_cov_ema_for(4096, jnp.float32)
    with pytest.warns(kfac_warnings.DispatchTableWarning):
        assert not pallas_ns.use_fused_klclip_for((4096, 4096))


# ----------------------------------------------------- threshold derivation


def _fused_sweep(fam, unfused_ms, fused_ms, sizes=(256, 512, 1024, 2048)):
    suffix = '_f32' if fam == 'cov_ema' else ''
    return (
        [{'op': f'{fam}_unfused_{d}{suffix}', 'ms': unfused_ms(d)}
         for d in sizes]
        + [{'op': f'{fam}_fused_{d}{suffix}', 'ms': fused_ms(d)}
           for d in sizes]
    )


def test_derive_fused_holds_prior_on_contaminated_baseline():
    t = dispatch_tables.derive_tables(_fused_sweep(
        'klclip', lambda d: 50.0 + d % 5, lambda d: 1.0))
    assert t['klclip'] == dispatch_tables.DEFAULTS['klclip']
    assert 'klclip_unfused' in t['provenance']['contaminated']
    assert 'klclip' in t['provenance']['held']


def test_derive_fused_moves_threshold_on_clean_win_suffix():
    t = dispatch_tables.derive_tables(_fused_sweep(
        'klclip',
        lambda d: 0.01 * d * d / 256,
        lambda d: 90.0 if d < 1024 else 0.002 * d * d / 256,
    ))
    assert t['klclip']['min_dim'] == 1024
    assert t['provenance']['derived']['klclip']['win_from_dim'] == 1024


def test_derive_fused_rejects_single_point_win():
    t = dispatch_tables.derive_tables(_fused_sweep(
        'cov_ema',
        lambda d: 0.01 * d * d / 256,
        lambda d: 9000.0 if d < 2048 else 1.0,
    ))
    assert t['cov_ema'] == dispatch_tables.DEFAULTS['cov_ema']
    assert 'cov_ema' in t['provenance']['held']


def test_derive_fused_rejects_non_suffix_wins():
    # wins at 256 and 512 but a loss at 2048: no clean win regime
    t = dispatch_tables.derive_tables(_fused_sweep(
        'klclip',
        lambda d: 0.004 * d * d / 256,
        lambda d: 0.5 if d <= 512 else 9000.0,
    ))
    assert t['klclip'] == dispatch_tables.DEFAULTS['klclip']
    assert 'no clean win regime' in t['provenance']['held']['klclip']


def test_derive_ignores_sweeps_of_a_family_that_left():
    # the committed CPU sweep still holds ns_unfused_* / ns_fused_* rows
    # of the Newton-Schulz pair: they derive nothing and hold nothing
    t = dispatch_tables.derive_tables(_fused_sweep(
        'ns', lambda d: 0.001 * d ** 3, lambda d: 0.0001 * d ** 3))
    assert 'ns' not in t
    assert not any('ns' in k for k in t['provenance']['held'])
    assert t['provenance']['contaminated'] == {}
    assert t['klclip'] == dispatch_tables.DEFAULTS['klclip']


def test_artifact_with_a_row_of_a_family_that_left_still_loads(
    monkeypatch, tmp_path
):
    # an artifact derived before PR 26 has an ``ns`` row: the gates that
    # remain read their own rows from it as before
    art = json.loads(json.dumps(dispatch_tables.DEFAULTS))
    art.update(schema=dispatch_tables.SCHEMA_VERSION, ns={'min_dim': 512})
    art['klclip'] = {'min_dim': 1024}
    p = tmp_path / 'old.json'
    p.write_text(json.dumps(art))
    monkeypatch.setenv(dispatch_tables.ENV_VAR, str(p))
    dispatch_tables.invalidate_cache()
    try:
        assert dispatch_tables.family_min_dim('klclip', default=512) == 1024
        assert dispatch_tables.floor_contaminated('klclip') is None
    finally:
        dispatch_tables.invalidate_cache()


def test_committed_artifact_is_clean_and_has_fused_families():
    """Satellite: the committed thresholds were re-derived from a clean
    one-dispatch sweep — no contaminated baselines remain, every fused
    family has a row, and provenance names its source sweep."""
    tables = dispatch_tables.load_tables()
    assert tables.get('schema') == dispatch_tables.SCHEMA_VERSION
    for fam in ('cov_ema', 'klclip'):
        assert 'min_dim' in tables[fam]
        assert dispatch_tables.floor_contaminated(fam) is None
    assert tables['provenance']['contaminated'] == {}
    assert tables['provenance']['source']['records'] > 0


# ----------------------------------------------------- autotune FLOP parity


def test_kfl205_fused_cov_ema_flop_parity(cov_inputs):
    """Jaxpr-counted MXU FLOPs (triangular executing subset of the
    launch grid × per-tile dot FLOPs) must equal the autotune price
    EXACTLY — the pricing model and the kernel share their geometry."""
    from kfac_tpu.analysis.ir import visitor
    from kfac_tpu.autotune import model

    f, a = cov_inputs
    jaxpr = jax.make_jaxpr(
        lambda ff, aa: pallas_cov_ema._fused(
            ff, aa, BETA, (1.0 - BETA) / N, interpret=True
        )
    )(f, a)
    (summary,) = [
        s for s in visitor.pallas_call_summaries(jaxpr)
        if s['name'] == '_sym_cov_ema_kernel'
    ]
    nblk_i, nblk_j, nk = summary['grid']
    assert nblk_i == nblk_j
    executing_tiles = nk * nblk_i * (nblk_i + 1) // 2
    counted = executing_tiles * summary['dot_flops_per_tile']
    assert counted == model.fused_cov_ema_flops(N, D)


def test_fused_klclip_price_pads_to_tiles():
    from kfac_tpu.autotune import model

    assert model.fused_klclip_flops((512, 512)) == 3.0 * 512 * 512
    assert model.fused_klclip_flops((200, 72)) == 3.0 * 256 * 128


def test_fused_hbm_saved_is_one_f32_roundtrip():
    from kfac_tpu.autotune import model

    assert model.fused_cov_ema_hbm_saved(1024) == 8.0 * 1024 * 1024


# ----------------------------------------------------- lint rules


def test_kfl110_fused_dispatch_doc_in_sync():
    from kfac_tpu.analysis import drift

    assert drift.check_fused_dispatch_table() == []


def test_kfl110_detects_doc_drift(tmp_path):
    from kfac_tpu.analysis import drift

    doc = tmp_path / 'ARCH.md'
    doc.write_text(
        '### Fused-kernel dispatch families\n\n'
        '| family | kernel |\n|---|---|\n'
        '| `cov` | x |\n| `attn` | x |\n| `cov_ema` | x |\n'
        '| `ghost` | x |\n'
    )
    problems = drift.check_fused_dispatch_table(str(doc))
    assert any('klclip' in p and 'undocumented' in p for p in problems)
    assert any('ghost' in p for p in problems)


def test_kfl206_allowlist_passes_fused_kernels(rng):
    from kfac_tpu.analysis.ir import rules

    p = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)

    def klclip(pp, gg):
        s = pallas_ns.fused_klclip_dot(pp, gg, interpret=True)
        return pallas_ns.fused_klclip_scale(pp, s, interpret=True)

    jaxpr = jax.make_jaxpr(klclip)(p, p)
    trace = SimpleNamespace(
        path='tests/fake.py', line=1, display='fake:step', jaxpr=jaxpr
    )
    suite = SimpleNamespace(traces=[trace], errors=[])
    assert rules.check_pallas_allowlist(suite) == []


# a kernel nobody registered, and the two of the Newton-Schulz pair that
# left the step path (PR 26): coming back takes the wiring again
@pytest.mark.parametrize('kernel_name', [
    '_rogue_kernel', '_ns_xupdate_kernel', '_ns_mx_resid_kernel',
])
def test_kfl206_flags_unlisted_kernel(kernel_name):
    from jax.experimental import pallas as pl

    from kfac_tpu.analysis.ir import rules

    def _rogue_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    def run(x):
        return pl.pallas_call(
            _rogue_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True,
            name=kernel_name,
        )(x)

    jaxpr = jax.make_jaxpr(run)(jnp.zeros((8, 128), jnp.float32))
    trace = SimpleNamespace(
        path='tests/fake.py', line=1, display='fake:step', jaxpr=jaxpr
    )
    suite = SimpleNamespace(traces=[trace], errors=[])
    findings = rules.check_pallas_allowlist(suite)
    assert len(findings) == 1
    assert findings[0].code == 'KFL206'
    assert kernel_name in findings[0].message
