"""Host clock: the longest of all the K-FAC steps of the untraced whole
periods: what ``stall_ms`` reads end to end, for a cell in which the
seed moves it by more than a bound can hold (in a quiet run it is the
refresh step, whose Newton-Schulz trips follow the seed)."""

from benchmark import schedule


def read(ctx):
    periods = schedule.whole_periods(ctx.rows, ctx.run.inv_every)
    if not periods:
        return None
    return 1e3 * max(r['seconds'] for p in periods for r in p)
