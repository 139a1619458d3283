"""Trace: device idle time per traced step while the host was inside
``kfac.host.launch`` (innermost span first): its part of the gap between a
step's dispatch and its first operation on the device."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.idle_ms(ctx, _program.LAUNCH)
