"""The step path's Pallas kernels (docs/ARCHITECTURE.md "Step-path
kernels"): the kl-clip pair's equivalence contract, its choice on and
off a TPU, and the lint rule that pins the kernels' names (KFL206).

Everything runs in Pallas interpret mode (CPU backend).
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu.ops import pallas_ns
from kfac_tpu.ops.pallas_ns import TILE


@pytest.fixture(scope='module')
def rng():
    return np.random.default_rng(20260806)


# ----------------------------------------------------- kl-clip fusion

# rows x columns against the 128 x 128 tile: zero padding is exact for
# the multiply-reduce, and the scale's padded region is cropped
KLCLIP_SHAPES = {
    'square': (2 * TILE, 2 * TILE),
    'tall': (3 * TILE, TILE),
    'wide': (TILE, 3 * TILE),
    'both-edges-off-tile': (200, 72),
    'one-tile': (TILE, TILE),
    'one-row': (1, TILE),
}


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('shape', list(KLCLIP_SHAPES))
@pytest.mark.parametrize('kernel', ['dot', 'scale'])
def test_fused_klclip_matches(rng, kernel, shape, dtype):
    """Against the expressions of ``factors.kl_clip_terms`` and
    ``factors.kl_clip_apply``: both sides upcast to f32 first."""
    r, c = KLCLIP_SHAPES[shape]
    p = jnp.asarray(rng.standard_normal((r, c)), dtype)
    p32 = p.astype(jnp.float32)
    if kernel == 'dot':
        g = jnp.asarray(rng.standard_normal((r, c)), dtype)
        got = pallas_ns.fused_klclip_dot(p, g, interpret=True)
        want = jnp.sum(p32 * g.astype(jnp.float32))
        assert got.dtype == jnp.float32 and got.shape == ()
        # tiled accumulation order differs from XLA's: allclose, not
        # bitwise
        assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-3)
    else:
        s = jnp.asarray(0.37, jnp.float32)
        got = pallas_ns.fused_klclip_scale(p, s, interpret=True)
        assert got.dtype == jnp.float32 and got.shape == (r, c)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(p32 * s), rtol=1e-6, atol=0
        )


# ----------------------------------------------------- the choice


def test_klclip_stays_off_cpu():
    assert not pallas_ns.use_fused_klclip_for((4096, 4096))


def test_klclip_win_regime(monkeypatch):
    """Faking the TPU backend pins the pair to its constant's regime
    (``_MIN_KLCLIP_DIM`` squared elements, two dimensions)."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(jax, 'devices', lambda *a: [object()])
    assert pallas_ns.use_fused_klclip_for((512, 512))
    assert pallas_ns.use_fused_klclip_for((1024, 256))  # same traffic
    assert not pallas_ns.use_fused_klclip_for((64, 64))
    assert not pallas_ns.use_fused_klclip_for((512, 512, 2))


# ----------------------------------------------------- lint rules


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
