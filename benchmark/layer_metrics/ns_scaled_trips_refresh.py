"""Counter: those of the traced stretch's refresh trips in which some
slot of the bucket took a scaled Newton-Schulz step (the first phase of a
cold solve: ``kfac_tpu.ops.factors.newton_schulz_inverse_info``), formed
as ``ns_trips_refresh`` is: the sum over buckets and sides of the largest
count of each. Nothing on a program whose report has no such total."""

from benchmark.layer_metrics import _program


def read(ctx):
    totals = _program.refresh_totals(ctx)
    return None if totals is None else totals.get('scaled_trips')
