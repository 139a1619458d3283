"""What the flash kernel's choice asks of the backend and of the trace
context (``pallas_gate.mosaic_context_ok``); the decision tables are in
tests/ops/test_kernel_choice.py."""

from kfac_tpu.ops import pallas_attention


def test_flash_stays_off_cpu():
    # every other condition holds (whole tiles, d on 128, K+V inside the
    # VMEM budget): the CPU test backend alone keeps the kernel off
    assert not pallas_attention.use_flash_for(1024, 1024, 128)


def test_dense_flash_stays_off_cpu():
    # the dense path at its floor length: still the backend that decides
    assert not pallas_attention.use_flash_for(2048, 2048, 128, dense=True)


def test_dispatch_win_regimes(monkeypatch):
    """Dispatch regimes: flash s_k>=2048 on the dense path. Verified by
    faking the TPU backend check."""
    import jax as _jax

    monkeypatch.setattr(_jax, 'default_backend', lambda: 'tpu')
    # single-device process (one chip): mesh-less dispatch allowed
    monkeypatch.setattr(_jax, 'devices', lambda *a: [object()])
    # dense path: XLA's fused attention below s=2048 (an off-chip prior)
    assert pallas_attention.use_flash_for(2048, 2048, 128, dense=True)
    assert not pallas_attention.use_flash_for(512, 512, 128, dense=True)
    # blockwise-partials path (ring steps): no length floor — the
    # alternative is the unfused einsum partials
    assert pallas_attention.use_flash_for(512, 512, 128)


def test_mosaic_context_guard(monkeypatch):
    """Raw Mosaic calls cannot be auto-partitioned (measured on-chip:
    NotImplementedError from a flash dispatch inside the pipeline's
    partial shard_map). The dispatch heuristics must refuse
    partial-manual contexts and allow fully-manual ones."""
    import jax as _jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

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
