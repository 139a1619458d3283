"""Counter: loop trips the device executed in the traced stretch's
refresh: each bucket's vmapped Newton-Schulz loop runs until its slowest
slot is done, so the sum over buckets and sides of the largest iteration
count of each."""

from benchmark.layer_metrics import _program


def read(ctx):
    totals = _program.refresh_totals(ctx)
    return None if totals is None else totals['trips']
