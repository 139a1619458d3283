"""Counter: the solve's own final residual ``||I - (F + damping I) X||_F /
sqrt(d)``, worst slot, of the traced stretch's warm-started refresh. Not
part of ``correct``: that reads the step-0 cold solve, recomputed by the
benchmark."""

from benchmark.layer_metrics import _program


def read(ctx):
    totals = _program.refresh_totals(ctx)
    return None if totals is None else totals['worst_residual']
