"""Counter: live rows of the emptiest held expert, over every expert layer,
at the last capture of the traced stretch (``DistKFACState.traffic``): the
evidence behind the thinnest per-expert factor."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.traffic(ctx, 'rows_min')
