"""Counter: slots of the traced stretch's refresh whose warm start was
accepted and then abandoned for the cold start (each pays both solves)."""

from benchmark.layer_metrics import _program


def read(ctx):
    totals = _program.refresh_totals(ctx)
    return None if totals is None else totals['restarts']
