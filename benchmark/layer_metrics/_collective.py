"""Device time in collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all and their ``-start`` /
``-done`` halves), read off the device trace by instruction name
(``trace_reduce.collective_ns``): what KAISA's placement costs across
chips: the gradient all-reduce, the factor reduction, the inverses'
gather or the preconditioned gradients' broadcast. A one-chip program
holds none: the readers return ``None`` there and the line leaves the
metric out.
"""

from benchmark import trace_reduce


def per_step_ms(ctx, exposed: bool):
    """Milliseconds per traced step, on the device with most: of the
    union of the collectives' intervals, or (``exposed``) of the part of
    it during which nothing else ran on that device. An exposed part of
    zero is a reading; no collective on any device is none."""
    steps = ctx.count(None)
    pairs = [
        trace_reduce.collective_ns(plane, ctx.windows[plane['name']])
        for plane in trace_reduce.device_planes(ctx.trace)
    ]
    if not steps or not any(total for total, _ in pairs):
        return None
    return max(pair[exposed] for pair in pairs) / 1e6 / steps
