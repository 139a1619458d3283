"""The Pallas dispatch gate: default ON since the round-5 on-chip
validation, with dispatch restricted to each kernel's measured win
regime; KFAC_TPU_PALLAS=0 restores the pure-XLA paths."""

import pytest

from kfac_tpu.ops import pallas_attention, pallas_cov, pallas_gate


@pytest.mark.parametrize(
    'val,cov,attn',
    [
        (None, True, True),       # unset: default ON (validated on-chip r5)
        ('0', False, False),
        ('', False, False),
        ('off', False, False),
        ('1', True, True),
        ('true', True, True),
        ('all', True, True),
        ('cov', True, False),
        ('attn', False, True),
        ('cov,attn', True, True),
        (' cov , attn ', True, True),
        ('bogus', False, False),
    ],
)
def test_enabled_parsing(monkeypatch, val, cov, attn):
    if val is None:
        monkeypatch.delenv('KFAC_TPU_PALLAS', raising=False)
    else:
        monkeypatch.setenv('KFAC_TPU_PALLAS', val)
    assert pallas_gate.enabled('cov') is cov
    assert pallas_gate.enabled('attn') is attn


def test_dispatch_stays_off_cpu_even_when_enabled(monkeypatch):
    # the gate only ever ADDS a restriction: enabling it off-TPU must not
    # flip the backend check
    import jax.numpy as jnp

    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    assert not pallas_cov.use_pallas_for(4096, jnp.float32)
    assert not pallas_attention.use_flash_for(1024, 1024, 128)


def test_dispatch_default_on_but_cpu_backend_off(monkeypatch):
    # default gate is ON since the round-5 on-chip validation, but the
    # CPU test backend still never dispatches
    monkeypatch.delenv('KFAC_TPU_PALLAS', raising=False)
    import jax.numpy as jnp

    assert pallas_gate.enabled('cov') and pallas_gate.enabled('attn')
    assert not pallas_cov.use_pallas_for(4096, jnp.float32)
    assert not pallas_attention.use_flash_for(1024, 1024, 128)


def test_dispatch_win_regimes(monkeypatch):
    """Dispatch regimes: cov f32-only; flash s_k>=2048 on the dense path.
    Verified by faking the TPU backend check."""
    import jax as _jax
    import jax.numpy as jnp

    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    monkeypatch.setattr(_jax, 'default_backend', lambda: 'tpu')
    # single-device process (one chip): mesh-less dispatch allowed
    monkeypatch.setattr(_jax, 'devices', lambda *a: [object()])
    assert pallas_cov.use_pallas_for(4096, jnp.float32)     # f32: win
    assert not pallas_cov.use_pallas_for(4096, jnp.bfloat16)  # bf16: loss
    assert not pallas_cov.use_pallas_for(128, jnp.float32)  # < 2 tiles
    # dense path: XLA's fused attention wins below s=2048 (measured)
    assert pallas_attention.use_flash_for(2048, 2048, 128, dense=True)
    assert not pallas_attention.use_flash_for(512, 512, 128, dense=True)
    # blockwise-partials path (ring steps): no length floor — the
    # alternative is the unfused einsum partials the kernel beat 300x
    assert pallas_attention.use_flash_for(512, 512, 128)


def test_mosaic_context_guard(monkeypatch):
    """Raw Mosaic calls cannot be auto-partitioned (measured on-chip:
    NotImplementedError from a flash dispatch inside the pipeline's
    partial shard_map). The dispatch heuristics must refuse
    partial-manual contexts and allow fully-manual ones."""
    import jax as _jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    monkeypatch.setattr(_jax, 'default_backend', lambda: 'tpu')

    mesh = Mesh(np.array(_jax.devices()).reshape(4, 2), ('a', 'b'))
    n_real_devices = len(_jax.devices())
    seen = {}

    def body_full(x):
        seen['full'] = pallas_attention.use_flash_for(512, 512, 128)
        return x

    def body_partial(x):
        seen['partial'] = pallas_attention.use_flash_for(512, 512, 128)
        return x

    x = np.zeros((8, 8), np.float32)
    _jax.eval_shape(
        _jax.shard_map(body_full, mesh=mesh, in_specs=P('a', 'b'),
                       out_specs=P('a', 'b')), x)
    _jax.eval_shape(
        _jax.shard_map(body_partial, mesh=mesh, in_specs=P('a', None),
                       out_specs=P('a', None), axis_names={'a'}), x)
    assert seen['full'] is True       # fully-manual: kernel allowed
    assert seen['partial'] is False   # partial-manual: einsum fallback
    # no mesh + multi-device process: inputs may arrive sharded via
    # device_put(NamedSharding) with no mesh context — refuse
    assert n_real_devices > 1
    assert not pallas_attention.use_flash_for(512, 512, 128)
    # no mesh + single device: plain jit — allowed
    monkeypatch.setattr(_jax, 'devices', lambda *a: [object()])
    assert pallas_attention.use_flash_for(512, 512, 128)


def test_get_cov_partial_manual_falls_back_to_xla(monkeypatch):
    """get_cov inside a partial-manual shard_map must use the XLA
    contraction (neither kernel form can trace there) and still produce
    the exact symmetric covariance."""
    import jax as _jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from kfac_tpu.ops import cov as cov_lib

    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    # force the size/dtype heuristic on so only the context logic decides
    monkeypatch.setattr(pallas_cov, 'use_pallas_for',
                        lambda d, dtype: True)

    mesh = Mesh(np.array(_jax.devices()).reshape(4, 2), ('a', 'b'))
    a = _jax.random.normal(_jax.random.PRNGKey(0), (64, 32), jnp.float32)

    def body(x):
        # rows sharded over manual axis 'a'; axis 'b' stays automatic
        c = cov_lib.get_cov(x, scale=64.0)
        return _jax.lax.psum(c, 'a')

    got = _jax.jit(
        _jax.shard_map(body, mesh=mesh, in_specs=P('a', None),
                       out_specs=P(None, None), axis_names={'a'},
                       check_vma=False)
    )(a)
    ref = np.asarray(a).T @ (np.asarray(a) / 64.0)
    ref = (ref + ref.T) / 2
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)
