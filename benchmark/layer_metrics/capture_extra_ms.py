"""Host clock: median capture step minus median plain step: the covariance
contractions and the factor update."""

from benchmark import schedule


def read(ctx):
    kinds = schedule.by_kind(ctx.rows)
    if 'capture' not in kinds or 'plain' not in kinds:
        return None
    return 1e3 * (kinds['capture'] - kinds['plain'])
