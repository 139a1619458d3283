"""Span: median duration of ``kfac.host.post_step`` over the traced steps:
what ``Trainer.step`` runs after the launch (health warnings, the
checkpoint autopilot, the fleet controller)."""

from benchmark.layer_metrics import _program


def read(ctx):
    return _program.host_ms(ctx, _program.POST_STEP)
