"""Triangular Pallas covariance kernel vs dense oracle (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kfac_tpu.ops import pallas_cov


@pytest.mark.parametrize(
    'n,d',
    [(64, 96), (512, 128), (700, 300), (1024, 256)],
)
def test_sym_cov_matches_dense(n, d):
    rng = np.random.default_rng(0)
    a = rng.normal(size=(n, d)).astype(np.float32)
    got = pallas_cov.sym_cov(jnp.asarray(a), interpret=True)
    expected = a.T @ a / n
    np.testing.assert_allclose(np.asarray(got), expected, rtol=1e-4, atol=1e-4)
    # exact symmetry by construction
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got).T)


def test_sym_cov_scale_and_dtype():
    rng = np.random.default_rng(1)
    a = rng.normal(size=(130, 140)).astype(np.float32)
    got = pallas_cov.sym_cov(jnp.asarray(a, jnp.bfloat16), scale=10.0, interpret=True)
    assert got.dtype == jnp.bfloat16
    expected = a.T @ a / 10.0
    np.testing.assert_allclose(
        np.asarray(got, np.float32), expected, rtol=0.05, atol=0.5
    )


def test_use_pallas_heuristic_cpu_off():
    # on the CPU test backend the dispatch heuristic must stay off
    import jax.numpy as jnp
    assert not pallas_cov.use_pallas_for(4096, jnp.float32)


def test_get_cov_dispatches_to_pallas_only_where_a_raw_call_can_run(
    monkeypatch
):
    """On a TPU get_cov routes f32 factors >= 2 tiles through the kernel
    in a one-device process and on the local rows inside a fully-manual
    shard_map; under GSPMD on several devices the contraction stays with
    XLA (nothing can partition a Mosaic call). Each route matches the
    dense covariance. The backend is faked; the kernel runs interpreted."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from kfac_tpu.ops import cov

    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(pallas_cov, 'interpret_mode', lambda: True)
    a = jax.random.normal(jax.random.PRNGKey(1), (64, 256))
    ref = np.asarray(a).T @ (np.asarray(a) / 64)
    ref = (ref + ref.T) / 2

    def kernels(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count('pallas_call')

    mesh = Mesh(np.array(jax.devices()).reshape(8), ('x',))
    a_sharded = jax.device_put(a, NamedSharding(mesh, P('x', None)))
    assert kernels(cov.get_cov, a_sharded) == 0  # 8 devices, GSPMD: XLA
    out_jit = jax.jit(cov.get_cov)(a_sharded)
    np.testing.assert_allclose(np.asarray(out_jit), ref, rtol=1e-5, atol=1e-4)

    def body(a_local):
        c = cov.get_cov(a_local, scale=1.0)  # local rows, unscaled
        return jax.lax.psum(c, 'x')

    manual = jax.shard_map(
        body, mesh=mesh, in_specs=P('x', None), out_specs=P()
    )
    assert kernels(manual, a_sharded) == 1
    out_sm = jax.jit(manual)(a_sharded) / 64
    np.testing.assert_allclose(np.asarray(out_sm), ref, rtol=1e-5, atol=1e-4)

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, 'devices', lambda *args: one)
    assert kernels(cov.get_cov, a) == 1  # one chip: the kernel
    np.testing.assert_allclose(
        np.asarray(jax.jit(cov.get_cov)(a)), ref, rtol=1e-5, atol=1e-4
    )
