"""Trace: device time under scope ``model.attention`` and under nothing deeper,
per traced step: the attention core between its projections: QK-norm,
rotary, scores, softmax or the flash partials, values, the gate. One bucket
of the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'attention')
