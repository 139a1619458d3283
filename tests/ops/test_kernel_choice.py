"""Which kernel runs, as decision tables: ``use_flash_for`` and
``use_fused_klclip_for`` decide from backend, shape, dtype size, a
constant of their own module and the trace context, and nothing else.

The backend and the device list are faked (no chip here): the tables pin
the choice, never a speed. The shapes named after a configuration are
the ones its cell of ``BENCHMARK.json`` traces.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kfac_tpu.ops import pallas_attention, pallas_gate, pallas_ns


def _fake(monkeypatch, backend='tpu', n_devices=1):
    monkeypatch.setattr(jax, 'default_backend', lambda: backend)
    monkeypatch.setattr(jax, 'devices', lambda *a: [object()] * n_devices)


# everything but one TPU chip under no mesh: no Mosaic kernel
not_one_tpu = pytest.mark.parametrize('backend,n_devices', [
    ('cpu', 1), ('gpu', 1), ('tpu', 4),
], ids=['cpu', 'gpu', 'tpu-four-devices-no-mesh'])


# (s_q, s_k, d, itemsize, dense) -> flash?; K+V of one head are staged
# whole: 2 * s_k * d * itemsize bytes against _VMEM_KV_BYTES (8 MiB)
FLASH_CASES = {
    # the configurations' own attends
    'gpt2-small-dense-1024x64-bf16': ((1024, 1024, 64, 2, True), False),
    'qwen3-next-chunk-1024x256-bf16': ((1024, 1024, 256, 2, False), True),
    # the dense path's floor, _MIN_FLASH_SK_DENSE = 2048
    'dense-1920-below-floor': ((1920, 1920, 128, 4, True), False),
    'dense-2047-off-block': ((2047, 2047, 128, 4, True), False),
    'dense-2048-at-floor': ((2048, 2048, 128, 4, True), True),
    'dense-2176-above-floor': ((2176, 2176, 128, 4, True), True),
    'dense-short-queries-long-keys': ((128, 2048, 128, 4, True), True),
    # the partials path has no floor
    'partials-one-block': ((128, 128, 128, 4, False), True),
    'partials-512': ((512, 512, 128, 4, False), True),
    'partials-ring-chunk-q256-k1024': ((256, 1024, 128, 2, False), True),
    # whole lane-aligned tiles
    's_q-off-block': ((130, 512, 128, 4, False), False),
    's_k-off-block': ((512, 520, 128, 4, False), False),
    'd-64': ((512, 512, 64, 4, False), False),
    'd-192': ((512, 512, 192, 4, False), False),
    # the VMEM budget, at both item sizes
    'kv-bf16-at-budget': ((128, 16384, 128, 2, False), True),
    'kv-bf16-one-block-over': ((128, 16512, 128, 2, False), False),
    'kv-f32-at-budget': ((128, 8192, 128, 4, False), True),
    'kv-f32-one-block-over': ((128, 8320, 128, 4, False), False),
    'kv-bf16-length-in-f32': ((128, 16384, 128, 4, False), False),
    'kv-wide-head-f32': ((128, 4096, 256, 4, False), True),
    'kv-wide-head-f32-over': ((128, 4224, 256, 4, False), False),
}


@pytest.mark.parametrize('case', list(FLASH_CASES))
def test_use_flash_for(monkeypatch, case):
    _fake(monkeypatch)
    (s_q, s_k, d, itemsize, dense), want = FLASH_CASES[case]
    got = pallas_attention.use_flash_for(s_q, s_k, d, itemsize, dense=dense)
    assert got is want


@not_one_tpu
def test_use_flash_for_needs_one_tpu(monkeypatch, backend, n_devices):
    # Qwen's chunk, which one TPU chip runs through the kernel
    _fake(monkeypatch, backend, n_devices)
    assert not pallas_attention.use_flash_for(1024, 1024, 256, 2)


# preconditioned-gradient shape (out, in [+ bias]) -> fused kl-clip?;
# the pair runs from _MIN_KLCLIP_DIM ** 2 = 512 ** 2 elements
KLCLIP_CASES = {
    'square-at-floor': ((512, 512), True),
    'one-row-short': ((511, 512), False),
    'rectangle-with-the-floor-count': ((256, 1024), True),
    'one-dimension': ((512 * 512,), False),
    'three-dimensions': ((512, 512, 2), False),
    'no-dimensions': ((), False),
    # resnet50: 53 convolutions (out, kh * kw * in) and the head
    'resnet50-smallest-64x64': ((64, 64), False),
    'resnet50-stem-64x147': ((64, 147), False),
    'resnet50-largest-below-128x1152': ((128, 1152), False),
    'resnet50-smallest-above-1024x256': ((1024, 256), True),
    'resnet50-largest-512x4608': ((512, 4608), True),
    'resnet50-head-1000x2049': ((1000, 2049), True),
    # gpt2-small: every registered layer is above
    'gpt2-small-smallest-768x769': ((768, 769), True),
    'gpt2-small-mlp-in-3072x769': ((3072, 769), True),
    'gpt2-small-largest-768x3073': ((768, 3073), True),
    # qwen3-next-80b-a3b: the shared expert's gate and DeltaNet's b and a
    # projections are below, experts and mixers above
    'qwen3-next-smallest-1x2048': ((1, 2048), False),
    'qwen3-next-deltanet-gates-32x2048': ((32, 2048), False),
    'qwen3-next-expert-512x2048': ((512, 2048), True),
    'qwen3-next-expert-down-2048x512': ((2048, 512), True),
    'qwen3-next-largest-2048x4096': ((2048, 4096), True),
}


@pytest.mark.parametrize('case', list(KLCLIP_CASES))
def test_use_fused_klclip_for(monkeypatch, case):
    _fake(monkeypatch)
    shape, want = KLCLIP_CASES[case]
    assert pallas_ns.use_fused_klclip_for(shape) is want


@not_one_tpu
def test_use_fused_klclip_for_needs_one_tpu(monkeypatch, backend, n_devices):
    _fake(monkeypatch, backend, n_devices)
    assert not pallas_ns.use_fused_klclip_for((512, 4608))


def _asked_inside(manual_axes):
    """``mosaic_context_ok()`` as a trace over the test mesh sees it:
    under ``shard_map`` manual over ``manual_axes``, or under none."""
    seen = []

    def body(x):
        seen.append(pallas_gate.mosaic_context_ok())
        return x

    x = np.zeros((8, 8), np.float32)
    if manual_axes is None:
        jax.eval_shape(body, x)
    else:
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ('a', 'b'))
        spec = P(*manual_axes)
        jax.eval_shape(
            jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                          axis_names=set(manual_axes)), x)
    (ok,) = seen
    return ok


@pytest.mark.parametrize('manual_axes,one_device,want', [
    (None, True, True),
    (None, False, False),
    (('a', 'b'), False, True),
    (('a',), False, False),
], ids=[
    'no-mesh-one-device', 'no-mesh-several-devices',
    'shard_map-manual-over-every-axis', 'shard_map-manual-over-some',
])
def test_mosaic_context_ok(monkeypatch, manual_axes, one_device, want):
    # a process of several devices runs a raw Mosaic call only inside a
    # fully-manual region: why the four-chip cell's programs hold none
    assert len(jax.devices()) > 1
    if one_device:
        monkeypatch.setattr(jax, 'devices', lambda *a: [object()])
    assert _asked_inside(manual_axes) is want
