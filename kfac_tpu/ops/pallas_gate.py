"""Dispatch gate for the Pallas TPU kernels.

Each kernel family (``cov_ema``, ``klclip``, ``attn``)
dispatches on a TPU only inside the regime its ``use_*_for`` heuristic
accepts (shape, dtype, trace context, the thresholds in
``dispatch_thresholds.json``). Those thresholds were derived off-chip
and no kernel's speed has been measured on today's code; what IS
checked on the chip is that every kernel compiles under Mosaic and
agrees with the XLA expression it replaces (``chip_smoke.py``).

The gate defaults ON. Override via the ``KFAC_TPU_PALLAS`` environment
variable:

    KFAC_TPU_PALLAS=1 (default)  kernels dispatch in their regimes
    KFAC_TPU_PALLAS=klclip       enable only the kl-clip pair
    KFAC_TPU_PALLAS=attn         enable only the flash-attention kernel
    KFAC_TPU_PALLAS=klclip,attn  comma-separated combination
    KFAC_TPU_PALLAS=0            XLA paths only

The gate is read at trace time (each kl-clip / attention dispatch),
so flipping the variable between jit traces takes effect without a
process restart; already-compiled programs are unaffected.

Off-TPU backends are unaffected by the gate: the dispatch heuristics
already return False there, and interpret-mode tests call the kernels
directly.
"""

from __future__ import annotations

import os

_TRUE = frozenset({'1', 'true', 'on', 'all'})
_FALSE = frozenset({'', '0', 'false', 'off', 'none'})


def enabled(kernel: str) -> bool:
    """Whether the named Pallas kernel family may dispatch on TPU."""
    val = os.environ.get('KFAC_TPU_PALLAS', '1').strip().lower()
    if val in _TRUE:
        return True
    if val in _FALSE:
        return False
    return kernel in {t.strip() for t in val.split(',')}


def interpret_mode() -> bool:
    """Run the kernels in interpret mode off-TPU (tests, CPU meshes).

    Every Pallas family routes through this, so off a TPU the kernels
    silently become the Pallas interpreter — right for tests, and never
    a measurement. Nothing here proves Mosaic compiled anything:
    ``chip_smoke.py`` does, by refusing to run unless
    ``jax.devices()[0].platform == 'tpu'`` and by listing the
    ``tpu_custom_call`` kernels found in the compiled step programs.
    """
    import jax

    return jax.default_backend() != 'tpu'


def manual_context() -> tuple[bool, bool, bool]:
    """``(has_mesh, any_manual, all_manual)`` for the current trace context.

    The single source of truth for whether a raw ``pallas_call`` may run
    here (Mosaic kernels cannot be automatically partitioned). Inside
    shard_map regions — ``check_vma=True`` or ``False`` — the abstract
    mesh's ``axis_types`` carries ``Manual`` for exactly the manual
    axes; aval ``vma`` is NOT a reliable signal (empty under
    ``check_vma=False``), so axis types alone decide.
    """
    import jax

    am = jax.sharding.get_abstract_mesh()
    manual = [t == jax.sharding.AxisType.Manual for t in am.axis_types]
    return bool(am.axis_names), any(manual), bool(manual) and all(manual)
