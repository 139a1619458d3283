"""Host clock: median refresh step minus median capture step: the inverse
refresh (a refresh step captures too)."""

from benchmark import schedule


def read(ctx):
    kinds = schedule.by_kind(ctx.rows)
    if 'refresh' not in kinds or 'capture' not in kinds:
        return None
    return 1e3 * (kinds['refresh'] - kinds['capture'])
