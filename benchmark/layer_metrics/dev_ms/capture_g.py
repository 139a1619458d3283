"""Trace: device time under ``kfac.capture_g`` per capturing step: the G
side of capture (the covariance of a layer's output gradient), in the
backward pass."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.capture_ms(ctx, _program.CAPTURE_G)
