"""Trace: device time under ``kfac.capture_a/patches`` per capturing step:
the convolution helper's patch rows (im2col, the reshape to rows, the bias
column, the scaling), the part of the A side (``dev_ms.capture_a`` holds it
too) that a fusion of patch extraction into the covariance would take
away. ``None`` for a model without convolutions."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.capture_ms(ctx, _program.CAPTURE_PATCHES)
