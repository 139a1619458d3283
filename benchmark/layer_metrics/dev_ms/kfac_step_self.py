"""Trace: device time per traced step under ``kfac.step`` / ``dist_kfac.step``
and under none of its three parts (``update_factors``, ``update_inverses``,
``precondition``): the engine's own glue. One bucket of the step map
(``_stepmap.py``)."""

from benchmark.layer_metrics import _stepmap


def read(ctx):
    return _stepmap.read(ctx, 'kfac_step_self')
