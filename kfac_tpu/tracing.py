"""Opt-in timing/tracing instrumentation.

Counterpart of the reference's tracing module (kfac/tracing.py:19-108).
Differences forced by the execution model: JAX dispatch is async, so honest
wall times require blocking on the traced function's outputs —
``sync=True`` calls ``jax.block_until_ready`` (the role the reference's
``dist.barrier`` plays for honest distributed timings). For on-device
profiling, stages are additionally wrapped in ``jax.named_scope`` so they
are attributable in XLA profiler traces.

The names a profile of a K-FAC step carries, all defined here (how to read
them: docs/OBSERVABILITY.md "A step that reports on itself"):

- device scopes (``jax.named_scope``: they reach the compiled programs'
  ``op_name`` metadata; a TPU trace's events carry no scope, so a reducer
  joins the two by instruction name): the engine entry points
  (``dist_kfac.step`` / ``.update_factors`` / ``.update_inverses`` /
  ``.precondition``, :func:`scope`), the capture layer's two sides,
  :data:`CAPTURE_SCOPES` (:func:`capture_scope`), the model parts of
  :data:`MODEL_SCOPES` (:func:`model_scope`), and the trainer's own part
  of a step, :data:`TRAINER_SCOPES` (:func:`trainer_scope`). Together
  they are one map of a step program: every operation lies under the
  deepest of them on its ``op_name``, or under none;
- host spans (``jax.profiler.TraceAnnotation``: they land on the
  profile's ``/host:CPU`` line in the device's time base):
  :data:`HOST_SPANS` inside ``Trainer.step`` (:func:`host_span`).

None of them has a switch: with no profiler session running a span or a
scope costs well under a microsecond a step.
"""

from __future__ import annotations

import collections
import functools
import logging
import time
from typing import Any, Callable, TypeVar

import jax

F = TypeVar('F', bound=Callable[..., Any])

# Wall times a key keeps: the table is read over a recent window
# (``MetricsCollector`` takes the last 256), so a process that steps for
# weeks holds this many floats per decorated function and no more.
TRACE_HISTORY = 1024

_func_traces: dict[str, collections.deque[float]] = collections.defaultdict(
    lambda: collections.deque(maxlen=TRACE_HISTORY)
)
_force_sync: bool = False

# Device scopes of the capture layer (kfac_tpu/layers/capture.py): the A
# side runs in the forward pass under the module interceptor, the G side
# in the backward pass under the g-taps' vjp rule. 'patches' nests under
# the A side ('kfac.capture_a/patches'): the convolution helper's patch
# rows (im2col, the reshape to rows, the bias column, the scaling), all
# of a convolution's A side but the covariance itself. 'experts' nests
# under either side ('kfac.capture_a/experts', 'kfac.capture_g/experts'):
# the stacked per-expert covariances of a routed expert projection.
CAPTURE_SCOPES = {
    'a': 'kfac.capture_a',
    'g': 'kfac.capture_g',
    'patches': 'patches',
    'experts': 'experts',
}

# Device scopes of the model's parts, one vocabulary over the model
# families (kfac_tpu/models/): an operation belongs to the deepest scope on
# its path, so what is under ``model.mixer`` and under nothing deeper is a
# token mixer's projections and glue, ``model.attention`` the attention
# core between them (QK-norm, rotary, scores, softmax or the flash
# partials, values, the gate), ``model.gdn_scan`` the chunked delta-rule
# scan and ``model.short_conv`` the gated short convolution (``B * x~``,
# the depthwise taps, ``C * conv``), ``model.mla_latent`` latent
# attention's glue between its projections and the core (rotary on the
# rotary parts, the shared key head broadcast, the heads put together).
# ``model.mlp`` is a dense or gated MLP
# (a shared expert with its gate too), ``model.moe_route`` the router with
# its top-k and row plan, ``model.moe_experts`` the grouped expert
# products. ``model.norm`` is a block's norms (QK-norm is the attention
# core's, the final norm the head's), ``model.head`` the final norm with
# the (tied, chunked) head, or a ResNet's pool and classifier,
# ``model.loss`` the loss, inside the head where the head computes it in
# chunks. A ResNet reads by ``model.stem`` and ``model.stage<n>``. The
# backward pass carries the same names under ``transpose(jvp(...))``, and
# a rematerialised forward carries them again.
MODEL_SCOPES = {
    'embed': 'model.embed',
    'mixer': 'model.mixer',
    'attention': 'model.attention',
    'gdn_scan': 'model.gdn_scan',
    'short_conv': 'model.short_conv',
    'mla_latent': 'model.mla_latent',
    'mlp': 'model.mlp',
    'moe_route': 'model.moe_route',
    'moe_experts': 'model.moe_experts',
    'norm': 'model.norm',
    'head': 'model.head',
    'loss': 'model.loss',
    'stem': 'model.stem',
    'stage0': 'model.stage0',
    'stage1': 'model.stage1',
    'stage2': 'model.stage2',
    'stage3': 'model.stage3',
}

# Device scope of the trainer's own part of a step program
# (kfac_tpu/training.py): the optimizer's update and its application to
# the parameters. Every step program a Trainer lowers carries it.
TRAINER_SCOPES = {'optimizer': 'trainer.optimizer'}

# Host spans inside one Trainer step, in order: what runs before the jitted
# call (the async-inverse pump, the cadence decision), the call itself,
# and what runs after it (health warnings, checkpoint autopilot).
HOST_SPANS = {
    'pre_step': 'kfac.host.pre_step',
    'launch': 'kfac.host.launch',
    'post_step': 'kfac.host.post_step',
}

logger = logging.getLogger(__name__)


def clear_trace() -> None:
    """Drop all recorded timings (reference kfac/tracing.py:19)."""
    _func_traces.clear()


def force_sync(enabled: bool) -> None:
    """Globally promote every ``@trace`` call site to ``sync=True``.

    The one-call switch for honest timings: hot paths are decorated with
    ``sync=False`` (dispatch-only cost, async pipelining preserved);
    flipping this blocks each traced call on its full output pytree so the
    recorded times are execution wall times, the role the reference's
    ``dist.barrier`` plays for honest distributed timings
    (kfac/tracing.py:82-108). Turn it back off after the measurement.
    """
    global _force_sync
    _force_sync = bool(enabled)


def sync_forced() -> bool:
    """Whether :func:`force_sync` is currently engaged."""
    return _force_sync


def _block_all(out: Any) -> None:
    """Block on EVERY array leaf of ``out``.

    ``jax.block_until_ready`` historically blocked on only the first leaf
    jax happened to return for some container types; honest step timing
    must wait for the whole output pytree (the last collective of a
    sharded step can trail the first leaf by the entire comms phase), so
    the sync walks every leaf explicitly.
    """
    for leaf in jax.tree_util.tree_leaves(out):
        block = getattr(leaf, 'block_until_ready', None)
        if block is not None:
            block()


def trace(sync: bool = False, name: str | None = None) -> Callable[[F], F]:
    """Decorator recording wall times of each call into a global table.

    Each call also runs under ``jax.named_scope`` so the stage is
    attributable in XLA profiler traces, and the wrapper is stamped with
    ``__kfac_scope__`` for the named-scope lint
    (tools/lint_named_scopes.py).

    Args:
        sync: block on the function's FULL jax output pytree before
            stopping the clock (async dispatch otherwise makes times
            meaningless). :func:`force_sync` promotes every call site.
        name: override the recorded name (defaults to the function name).
    """

    def decorator(func: F) -> F:
        key = name or func.__name__

        @functools.wraps(func)
        def wrapped(*args: Any, **kwargs: Any):
            start = time.perf_counter()
            with jax.named_scope(key):
                out = func(*args, **kwargs)
            if sync or _force_sync:
                _block_all(out)
            _func_traces[key].append(time.perf_counter() - start)
            return out

        wrapped.__kfac_scope__ = key  # type: ignore[attr-defined]
        return wrapped  # type: ignore[return-value]

    return decorator


def scope(name: str) -> Callable[[F], F]:
    """``jax.named_scope``-only decorator for in-jit hot paths.

    Engine methods run inside a jitted step: a wall clock there measures
    trace time, not execution, so they get profiler attribution without
    the timing table (the Trainer's host-side dispatch paths use
    :func:`trace`). The marker attribute feeds the same lint as
    :func:`trace`.
    """

    def decorator(func: F) -> F:
        @functools.wraps(func)
        def wrapped(*args: Any, **kwargs: Any):
            with jax.named_scope(name):
                return func(*args, **kwargs)

        wrapped.__kfac_scope__ = name  # type: ignore[attr-defined]
        return wrapped  # type: ignore[return-value]

    return decorator


def capture_scope(side: str):
    """``jax.named_scope`` of one side of the capture layer: ``'a'``,
    ``'g'``, or inside one of them ``'patches'`` or ``'experts'``
    (:data:`CAPTURE_SCOPES`)."""
    return jax.named_scope(CAPTURE_SCOPES[side])


def model_scope(part: str):
    """``jax.named_scope`` of one of :data:`MODEL_SCOPES`."""
    return jax.named_scope(MODEL_SCOPES[part])


def trainer_scope(part: str):
    """``jax.named_scope`` of one of :data:`TRAINER_SCOPES`."""
    return jax.named_scope(TRAINER_SCOPES[part])


def host_span(part: str, step: int | None):
    """``jax.profiler.TraceAnnotation`` of one part of a Trainer step
    (:data:`HOST_SPANS`), carrying ``step`` so that the spans of one step
    share an identifier with its ``StepTraceAnnotation('train',
    step_num=step)``. ``step`` is ``None`` where the host does not know it
    (after a compiled scan): the span then carries none."""
    if step is None:
        return jax.profiler.TraceAnnotation(HOST_SPANS[part])
    return jax.profiler.TraceAnnotation(HOST_SPANS[part], step=step)


def get_trace(
    average: bool = True,
    max_history: int | None = None,
) -> dict[str, float]:
    """Return recorded times per function, averaged or summed over the
    last ``max_history`` calls (reference kfac/tracing.py:24-47); ``None``
    takes all that the table keeps, :data:`TRACE_HISTORY` calls a key."""
    out: dict[str, float] = {}
    for key, times in _func_traces.items():
        window = times if max_history is None else list(times)[-max_history:]
        if not window:
            continue
        out[key] = sum(window) / len(window) if average else sum(window)
    return out


def log_trace(
    level: int = logging.INFO,
    label: str = 'timing:',
    **kwargs: Any,
) -> None:
    """Log the trace table (reference kfac/tracing.py:50-71)."""
    for key, value in sorted(get_trace(**kwargs).items()):
        logger.log(level, f'{label} {key}: {value:.6f}s')


def health_counters(state: Any) -> dict[str, Any]:
    """Flat numeric snapshot of an engine state's health counters.

    Accepts a ``KFACState``/``DistKFACState`` (or a bare ``HealthState``)
    and returns metric-logger-friendly scalars:
    ``{'health/skipped_steps': ..., 'health/<layer>/damping_mult': ...,
    'health/<layer>/quarantined': ..., 'health/<layer>/bad_inv': ...,
    'health/<layer>/quarantine_events': ...}``. Empty when the health
    sentinel is disabled. Synchronizes with the device (small transfer).
    """
    health = getattr(state, 'health', state)
    if health is None or not hasattr(health, 'skipped_steps'):
        return {}
    vals = jax.device_get(health._asdict())
    out: dict[str, Any] = {'health/skipped_steps': int(vals['skipped_steps'])}
    for field in ('damping_mult', 'quarantined', 'bad_inv',
                  'quarantine_events'):
        for name, v in vals[field].items():
            cast = float if field == 'damping_mult' else int
            out[f'health/{name}/{field}'] = cast(v)
    return out


def log_health(state: Any, level: int = logging.INFO) -> None:
    """Log the health counter snapshot (no-op when health is disabled)."""
    for key, value in sorted(health_counters(state).items()):
        logger.log(level, f'health: {key}: {value}')
