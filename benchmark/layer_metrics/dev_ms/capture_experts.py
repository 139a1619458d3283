"""Trace: device time under ``kfac.capture_a/experts`` and
``kfac.capture_g/experts`` per capturing step: the stacked per-expert
covariances over each held expert's own rows (a part of ``dev_ms.capture_a``
and ``dev_ms.capture_g``)."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.scope_ms(
        ctx, _hybrid.CAPTURE_EXPERTS, 'capture', _hybrid.CAPTURE_EXPERTS
    )
