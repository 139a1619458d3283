"""Trace: device time under scope ``model.short_conv`` per traced step: the
gated short convolution between its projections (``B * x~``, the depthwise
taps, ``C * conv``), forward, rematerialised forward and backward. A fusion
carries its root instruction's name, so what the compiler makes an epilogue
or a prologue of a neighbouring projection's product is that product's and
is not read here (PERF.md section 5 says which part that is at the cell's
size). ``None`` on a program without the scope."""

from benchmark.layer_metrics import _hybrid

SHORT_CONV = 'model.short_conv'


def read(ctx):
    return _hybrid.scope_ms(ctx, (SHORT_CONV,), among=(SHORT_CONV,))
