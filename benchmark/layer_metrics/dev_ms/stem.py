"""Trace: device time under scope ``model.stem`` and under nothing deeper, per
traced step: a ResNet's ``conv0``, ``bn0``, relu and max-pool. One bucket of
the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'stem')
