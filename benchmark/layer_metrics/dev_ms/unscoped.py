"""Trace: device time per traced step of the K-FAC step programs' operations
under no scope of the step map (``_stepmap.py``): how much of the map is
still blank. The run's log lists the largest of them by the path their
``op_name`` carries."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'unscoped')
