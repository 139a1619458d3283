"""The operation counts against hand-worked values."""

import json
import os

import pytest

from benchmark.flops import lm, vision

CONFIGS = os.path.join(os.path.dirname(__file__), '..', 'configs')


def _config(name):
    with open(os.path.join(CONFIGS, f'{name}.json')) as f:
        return json.load(f)


def test_resnet50_forward_is_4_1_gmac():
    cfg = _config('resnet50')
    layers = vision.conv_layers(cfg)
    assert len(layers) == 53  # and the head: 54 K-FAC layers
    by_name = {n: rest for n, *rest in layers}
    # stem: 112 x 112 outputs of 7 x 7 x 3 -> 64
    assert by_name['conv0'] == [112, 7, 3, 64]
    # the stride sits in the 3x3: stage1's first 1x1 still sees 56 x 56
    assert by_name['stage1_block0/conv1'] == [56, 1, 256, 128]
    assert by_name['stage1_block0/conv2'] == [28, 3, 128, 128]
    assert by_name['stage3_block2/conv2'] == [7, 3, 512, 512]
    stem = 112 * 112 * 49 * 3 * 64
    assert stem == 118_013_952
    macs = vision.forward_macs_per_sample(cfg)
    assert macs == pytest.approx(4.09e9, rel=2e-3)
    assert vision.train_flops_per_sample(cfg) == 6 * macs


def test_gpt2_small_is_6n_plus_12lds():
    cfg = _config('gpt2-small')
    block = 4 * 768 * 768 + 2 * 768 * 3072
    n = 12 * block + 768 * 50257
    assert lm.matmul_params(cfg) == n == 123_532_032
    per_token = 6 * n + 12 * 12 * 768 * 1024
    assert lm.train_flops_per_token(cfg) == per_token
    assert per_token == pytest.approx(854e6, rel=1e-3)
    assert lm.train_flops_per_sample(cfg) == 1024 * per_token
