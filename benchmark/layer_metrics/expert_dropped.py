"""Counter: assignments to held experts that a row plan left out at the last
capture of the traced stretch (``DistKFACState.traffic``). Has to read 0:
the plan holds the worst load."""

from benchmark.layer_metrics import _hybrid


def read(ctx):
    return _hybrid.traffic(ctx, 'dropped')
