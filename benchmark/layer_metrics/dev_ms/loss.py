"""Trace: device time under scope ``model.loss`` and under nothing deeper, per
traced step: the loss, inside the head where the head computes it in chunks.
One bucket of the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'loss')
