"""Trace: device time under scope ``model.norm`` and under nothing deeper, per
traced step: the blocks' norms (QK-norm is the attention core's, the final
norm the head's). One bucket of the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'norm')
