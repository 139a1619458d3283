"""Host clock: median gap between a step's completion and the next step's
dispatch, over the untraced K-FAC steps: the feed (``device_put`` of a
fresh batch) and whatever else the host does between steps."""

from benchmark import schedule


def read(ctx):
    return schedule.input_wait_ms(ctx.rows)
