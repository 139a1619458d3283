"""Trace: device time under ``kfac.capture_a`` per capturing step: the A
side of capture (im2col patches, the covariance of a layer's input), in
the forward pass."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.capture_ms(ctx, _program.CAPTURE_A)
