"""Trace: 1 - (union of the device's operation intervals) / (its traced
window), on the idlest device, in percent."""

from benchmark import trace_reduce


def read(ctx):
    shares = []
    for plane in trace_reduce.device_planes(ctx.trace):
        lo, hi = ctx.windows[plane['name']]
        shares.append(
            100.0 * (1.0 - trace_reduce.busy_ns(plane, (lo, hi)) / (hi - lo))
        )
    return max(shares)
