"""Trace: device time of the covariance products per capturing step: what
runs under ``kfac.capture_a`` and ``kfac.capture_g`` outside the patch
rows' scope and the stacked experts' scopes, both sides summed. That is
``ops/cov.get_cov``'s row-contracting product with what the compiler fuses
around it (scaling, the bias column), which took the place of the
``_sym_cov_kernel`` calls this row read by name until PR 28."""

from benchmark.layer_metrics import _hybrid, _program

_SIDES = (_program.CAPTURE_A, _program.CAPTURE_G)


def read(ctx):
    return _hybrid.scope_ms(
        ctx, _SIDES, kind='capture',
        among=_SIDES + (_program.CAPTURE_PATCHES,) + _hybrid.CAPTURE_EXPERTS,
    )
