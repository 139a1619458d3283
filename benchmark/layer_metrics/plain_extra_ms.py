"""Host clock: median plain K-FAC step minus median first-order step:
what preconditioning every step costs."""

import statistics

from benchmark import schedule

# compares with the first-order stretch, which runs once the K-FAC
# trainer's state has left the device
AFTER_FIRST_ORDER = True


def read(ctx):
    kinds = schedule.by_kind(ctx.rows)
    if 'plain' not in kinds:
        return None
    first_order = statistics.median(r['seconds'] for r in ctx.first_order_rows)
    return 1e3 * (kinds['plain'] - first_order)
