"""Trace: device time per traced step of programs that are not the K-FAC
trainer's step programs (the feed's ``jit__multi_slice`` on several chips,
stray converts between steps). One bucket of the step map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'other_programs')
