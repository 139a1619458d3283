"""Trace: device time under scope ``model.mlp`` and under nothing deeper, per
traced step: dense and gated MLPs: a block's ``mlp_up``/``mlp_down``, the
leading dense layer's gated MLP, a shared expert with its sigmoid gate. One
bucket of the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'mlp')
