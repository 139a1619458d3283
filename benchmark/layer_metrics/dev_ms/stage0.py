"""Trace: device time under scope ``model.stage0`` and under nothing deeper,
per traced step: the blocks of a ResNet's first stage. One bucket of the
step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'stage0')
