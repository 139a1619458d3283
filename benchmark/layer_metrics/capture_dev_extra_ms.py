"""Trace: device time of a step that captures and does not refresh, minus
the median plain step's: what capture costs on the device, the covariance
kernels with the patch copies and reshapes around them (they run under the
model's taps, outside every engine scope). A step is one run of a program
on the ``XLA Modules`` line; the traced rows say which kind each was."""

import statistics

from benchmark import trace_reduce


def read(ctx):
    worst = None
    for plane in trace_reduce.device_planes(ctx.trace):
        runs = trace_reduce.module_runs(plane, ctx.windows[plane['name']])
        if len(runs) != len(ctx.traced_rows):
            return None  # another program ran in the stretch: no pairing
        busy = {}
        for row, run in zip(ctx.traced_rows, runs):
            window = (run['start_ns'], run['start_ns'] + run['duration_ns'])
            busy.setdefault(row['kind'], []).append(
                trace_reduce.busy_ns(plane, window)
            )
        if 'capture' not in busy or 'plain' not in busy:
            return None
        extra = (
            statistics.median(busy['capture']) - statistics.median(busy['plain'])
        ) / 1e6
        worst = extra if worst is None else max(worst, extra)
    return worst
