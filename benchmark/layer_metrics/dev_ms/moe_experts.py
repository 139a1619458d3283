"""Trace: device time under scope ``model.moe_experts`` per traced step:
the held experts' three grouped products over the plan's blocks in use
(rows gathered from the tokens and summed back into them inside the loops),
forward and backward; the experts' capture inside the scope is capture's
(``dev_ms.capture_experts``)."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.scope_ms(ctx, (_hybrid.MOE_EXPERTS,))
