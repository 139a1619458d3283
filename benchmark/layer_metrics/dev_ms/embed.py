"""Trace: device time under scope ``model.embed`` and under nothing deeper, per
traced step: the token (and learned position) embedding and its cast,
forward, and the embedding's gradient. One bucket of the step map
(``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'embed')
