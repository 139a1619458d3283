"""What a sparse hybrid LM's step reports on itself, read for the
per-layer metrics of PR 27: the model's device scopes (the chunked
delta-rule scan, the router with its plan and combine, the grouped expert
products), the experts' part of capture, and the engine's expert-traffic
counters. As ``_program.py``: the names are the benchmark's own copy, and
on a program without them (the parent commit, a dense model) every reader
returns ``None`` and the line leaves the metric out.
"""

from benchmark import trace_reduce
from benchmark.layer_metrics import _program

GDN_SCAN = 'model.gdn_scan'
MOE_ROUTE = 'model.moe_route'
MOE_EXPERTS = 'model.moe_experts'
CAPTURE_EXPERTS = (
    _program.CAPTURE_A + '/experts', _program.CAPTURE_G + '/experts'
)
# an operation belongs to the deepest of these on its path: capture work
# that runs inside a model scope (the experts' taps) is capture's
_ALL = (GDN_SCAN, MOE_ROUTE, MOE_EXPERTS, _program.CAPTURE_A, _program.CAPTURE_G)


def scope_ms(ctx, scopes, kind=None, among=_ALL):
    """Device milliseconds under ``scopes`` (summed) per traced step of
    ``kind`` (``None``: every step), on the device with most."""
    steps = ctx.count(kind)
    if not steps:
        return None
    worst = 0.0
    for plane in trace_reduce.device_planes(ctx.trace):
        under = trace_reduce.scope_ns(plane, ctx.windows[plane['name']], among)
        worst = max(worst, sum(under.get(s, 0.0) for s in scopes))
    return worst / 1e6 / steps if worst else None


def traffic(ctx, column):
    """A column of ``DistributedKFAC.traffic_report`` for the state the
    traced stretch left (its last capture). ``None`` where the engine has
    no such report or the model no stacked experts."""
    report = getattr(ctx.run.trainer.kfac, 'traffic_report', None)
    if report is None:
        return None
    return report(ctx.run.state.kfac_state).get(column)
