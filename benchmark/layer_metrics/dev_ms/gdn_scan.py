"""Trace: device time under scope ``model.gdn_scan`` per traced step: the
chunked gated delta rule of the DeltaNet layers, its forward pass, the
forward pass again where the backward pass rematerialises it, and its
backward pass."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.scope_ms(ctx, (_hybrid.GDN_SCAN,))
