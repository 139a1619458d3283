"""Counter: live rows of the mean held expert at the last capture of the
traced stretch (``DistKFACState.traffic``); tokens * top-k * held / experts
under even routing."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.traffic(ctx, 'rows_mean')
