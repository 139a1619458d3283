"""Trace: device time under scope ``model.head`` and under nothing deeper, per
traced step: the final norm and the (tied, chunked) head of an LM outside
its loss, or a ResNet's pool and classifier. One bucket of the step map
(``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'head')
