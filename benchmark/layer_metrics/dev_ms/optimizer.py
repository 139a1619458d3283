"""Trace: device time under scope ``trainer.optimizer`` and under nothing
deeper, per traced step: the optimizer's update and its application to the
parameters. One bucket of the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'optimizer')
