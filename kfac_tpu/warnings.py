"""Warning categories (reference parity: kfac/warnings.py:6-9) plus the
rate-limited numerical-health event channel (kfac_tpu/health.py)."""

from __future__ import annotations

import warnings as _warnings


class ExperimentalFeatureWarning(Warning):
    """Feature is experimental and may change or underperform."""


class TPUPerformanceWarning(Warning):
    """Configuration known to be pathologically slow on TPU backends."""


class NumericalHealthWarning(Warning):
    """A layer was quarantined or degraded by the health sentinel."""


class CheckpointResilienceWarning(Warning):
    """Checkpoint durability/restore anomaly that was handled gracefully
    (manifest-less restore, fallback to an older rotation entry, retried
    transient I/O) but an operator should know about."""


class LayoutPlanWarning(Warning):
    """A tuned layout plan (kfac_tpu/autotune) could not be applied —
    topology/model fingerprint mismatch, incompatible mesh — and the
    engine fell back to its explicit/default configuration."""


# (layer, cause) pairs already warned about — each fires ONCE per process,
# not once per step: a persistently sick layer would otherwise spam the log
# at training-step frequency while saying nothing new.
_health_events_emitted: set[tuple[str, str]] = set()


def warn_health_event(
    layer: str,
    step: int | None,
    cause: str,
    detail: str = '',
) -> bool:
    """Emit a structured, rate-limited :class:`NumericalHealthWarning`.

    ``cause`` is a short event tag (``'quarantined'``, ``'degraded'``).
    Returns True when a warning was actually emitted (first occurrence of
    this (layer, cause)), False when rate-limited.
    """
    key = (layer, cause)
    if key in _health_events_emitted:
        return False
    _health_events_emitted.add(key)
    at = f' at step {step}' if step is not None else ''
    msg = f'kfac-tpu health: layer {layer!r} {cause}{at}'
    if detail:
        msg += f' ({detail})'
    _warnings.warn(msg, NumericalHealthWarning, stacklevel=2)
    return True


def reset_health_warnings() -> None:
    """Forget emitted health events (tests; or after operator intervention
    so a recurrence warns again)."""
    _health_events_emitted.clear()


# plan-fallback causes already warned about — once per process, like the
# health channel: a stale plan would otherwise warn on every engine (or
# Trainer) construction in a sweep while saying nothing new.
_layout_events_emitted: set[str] = set()


def warn_layout_event(cause: str, detail: str = '') -> bool:
    """Emit a rate-limited :class:`LayoutPlanWarning` (once per ``cause``).

    Returns True when a warning was actually emitted."""
    if cause in _layout_events_emitted:
        return False
    _layout_events_emitted.add(cause)
    msg = f'kfac-tpu autotune: tuned plan not applied — {cause}'
    if detail:
        msg += f' ({detail})'
    msg += '; falling back to the explicit/default layout'
    _warnings.warn(msg, LayoutPlanWarning, stacklevel=2)
    return True


def reset_layout_warnings() -> None:
    """Forget emitted plan-fallback events (tests)."""
    _layout_events_emitted.clear()
