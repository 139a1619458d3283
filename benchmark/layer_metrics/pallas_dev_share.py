"""Trace: share of the device's busy time spent in Mosaic (Pallas)
kernels over the traced stretch, on the device with the largest share."""

from benchmark import trace_reduce


def read(ctx):
    return max(
        trace_reduce.pallas_share(p, ctx.windows[p['name']])
        for p in trace_reduce.device_planes(ctx.trace)
    )
