"""Span: median duration of ``kfac.host.pre_step`` over the traced steps:
what ``Trainer.step`` runs before it launches the jitted program (the
async-inverse and offload pumps, the cadence decision)."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.host_ms(ctx, _program.PRE_STEP)
