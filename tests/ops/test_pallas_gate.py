"""The Pallas dispatch gate: default ON since the round-5 on-chip
validation, with dispatch restricted to each kernel's measured win
regime; KFAC_TPU_PALLAS=0 restores the pure-XLA paths."""

import pytest

from kfac_tpu.ops import pallas_attention, pallas_gate


@pytest.mark.parametrize(
    'val,klclip,attn',
    [
        (None, True, True),       # unset: default ON (validated on-chip r5)
        ('0', False, False),
        ('', False, False),
        ('off', False, False),
        ('1', True, True),
        ('true', True, True),
        ('all', True, True),
        ('klclip', True, False),
        ('attn', False, True),
        ('klclip,attn', True, True),
        (' klclip , attn ', True, True),
        ('bogus', False, False),
    ],
)
def test_enabled_parsing(monkeypatch, val, klclip, attn):
    if val is None:
        monkeypatch.delenv('KFAC_TPU_PALLAS', raising=False)
    else:
        monkeypatch.setenv('KFAC_TPU_PALLAS', val)
    assert pallas_gate.enabled('klclip') is klclip
    assert pallas_gate.enabled('attn') is attn


def test_dispatch_stays_off_cpu_even_when_enabled(monkeypatch):
    # the gate only ever ADDS a restriction: enabling it off-TPU must not
    # flip the backend check
    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    assert not pallas_attention.use_flash_for(1024, 1024, 128)


def test_dispatch_default_on_but_cpu_backend_off(monkeypatch):
    # default gate is ON since the round-5 on-chip validation, but the
    # CPU test backend still never dispatches
    monkeypatch.delenv('KFAC_TPU_PALLAS', raising=False)
    assert pallas_gate.enabled('klclip') and pallas_gate.enabled('attn')
    assert not pallas_attention.use_flash_for(1024, 1024, 128)


def test_dispatch_win_regimes(monkeypatch):
    """Dispatch regimes: flash s_k>=2048 on the dense path. Verified by
    faking the TPU backend check."""
    import jax as _jax

    monkeypatch.setenv('KFAC_TPU_PALLAS', '1')
    monkeypatch.setattr(_jax, 'default_backend', lambda: 'tpu')
    # single-device process (one chip): mesh-less dispatch allowed
    monkeypatch.setattr(_jax, 'devices', lambda *a: [object()])
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
