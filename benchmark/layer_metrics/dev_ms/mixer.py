"""Trace: device time under scope ``model.mixer`` and under nothing deeper, per
traced step: a block's token mixer outside its core: the projections
(q/k/v/o, ``x_proj``/``out_proj``, the DeltaNet's in-projections and causal
convolution) and the glue around ``model.attention``, ``model.gdn_scan`` and
``model.short_conv``, which are rows of their own. One bucket of the step
map (``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'mixer')
