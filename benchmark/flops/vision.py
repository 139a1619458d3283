"""Bottleneck-ResNet operation count from the configuration's shapes."""

from __future__ import annotations

WIDTHS = (64, 128, 256, 512)
EXPANSION = 4


def conv_layers(config: dict) -> list:
    """``(name, out_hw, kernel_hw, c_in, c_out)`` of every convolution, in
    order; the stride of a down-sampling block sits in its 3x3."""
    m = config['model']
    hw = m['image_size'] // 2  # 7x7 stem, stride 2
    layers = [('conv0', hw, 7, 3, 64)]
    hw //= 2  # 3x3 max pool, stride 2
    c_in = 64
    for stage, blocks in enumerate(m['stage_sizes']):
        f = WIDTHS[stage]
        for b in range(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            name = f'stage{stage}_block{b}'
            out_hw = hw // stride
            layers.append((f'{name}/conv1', hw, 1, c_in, f))
            layers.append((f'{name}/conv2', out_hw, 3, f, f))
            layers.append((f'{name}/conv3', out_hw, 1, f, EXPANSION * f))
            if c_in != EXPANSION * f or stride != 1:
                layers.append(
                    (f'{name}/proj', out_hw, 1, c_in, EXPANSION * f)
                )
            hw, c_in = out_hw, EXPANSION * f
    return layers


def forward_macs_per_sample(config: dict) -> int:
    macs = sum(
        hw * hw * k * k * c_in * c_out
        for _, hw, k, c_in, c_out in conv_layers(config)
    )
    return macs + WIDTHS[-1] * EXPANSION * config['model']['num_classes']


def train_flops_per_sample(config: dict) -> int:
    """Two operations a multiply-add; the backward pass twice the forward
    (gradients w.r.t. inputs and w.r.t. weights)."""
    return 3 * 2 * forward_macs_per_sample(config)
