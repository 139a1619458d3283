"""Counter: slots of the traced stretch's refresh whose warm start was
accepted and kept, over slots solved, in percent: useful outcomes over
attempts."""

from benchmark.layer_metrics import _program


def read(ctx):
    totals = _program.refresh_totals(ctx)
    if totals is None or not totals['slots']:
        return None
    kept = totals['warm_starts'] - totals['restarts']
    return 100.0 * kept / totals['slots']
