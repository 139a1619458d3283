"""Trace: the part of ``collective_ms`` during which nothing else ran on
the device: what overlap did not hide (several-chip cells only)."""

from benchmark.layer_metrics import _collective


def read(ctx):
    return _collective.per_step_ms(ctx, exposed=True)
