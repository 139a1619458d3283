"""Trace: device time in collective operations per traced step, on the
device with most (several-chip cells only)."""

from benchmark.layer_metrics import _collective


def read(ctx):
    return _collective.per_step_ms(ctx, exposed=False)
