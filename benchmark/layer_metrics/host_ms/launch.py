"""Span: median duration of ``kfac.host.launch`` over the traced steps:
the call of the jitted step program (argument handling of a state of
several hundred leaves, and the enqueue)."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.host_ms(ctx, _program.LAUNCH)
