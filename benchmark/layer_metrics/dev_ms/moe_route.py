"""Trace: device time under scope ``model.moe_route`` per traced step: the
router over all the experts, its softmax and top-k, and the row plan of the
assignments to the experts held (two sorts and gathers), forward and
backward. The gathers and the weighted sum into the tokens run inside the
grouped products (``dev_ms.moe_experts``)."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.scope_ms(ctx, (_hybrid.MOE_ROUTE,))
