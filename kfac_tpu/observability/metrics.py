"""In-jit per-layer metrics: state pytree, schema, and host-side collector.

The telemetry spine's device half. Engines thread a :class:`MetricsState`
through their jitted step as a trailing state field: per-layer scalars
(gradient / preconditioned-gradient norms, effective damping, Gershgorin
eigenvalue bounds of the EMA'd Kronecker factors, factor/inverse staleness
in steps) are computed inside the step — no extra host syncs — and the
user drains them whenever convenient with :class:`MetricsCollector`, which
performs exactly one ``jax.device_get``.

Design constraints honored here:

- The scalar schema is STATIC per configuration (:func:`metric_keys`),
  pre-populated by :func:`init_metrics`, and stored PACKED — one f32
  vector for every scalar, one int32 vector per step tracker — so
  ``lax.cond`` branches and repeated jitted steps see an identical
  3-buffer pytree: metrics on/off never changes compile counts after
  step 1, and carrying them adds no per-key buffer traffic.
- This module must not import the engines (they import it); it depends
  only on jax and the health/tracing helpers at drain time.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MetricsConfig:
    """Which per-layer scalar families to record.

    All families are cheap (reductions over tensors the step already
    materializes); toggles exist to shrink the drained record, not to
    save meaningful compute.
    """

    grad_norms: bool = True
    factor_bounds: bool = True
    staleness: bool = True

    def __post_init__(self) -> None:
        if not (self.grad_norms or self.factor_bounds or self.staleness):
            raise ValueError(
                'MetricsConfig with every family disabled records nothing; '
                'pass metrics=None/False to the engine instead')


@jax.tree_util.register_pytree_node_class
class MetricsState:
    """Device-resident telemetry riding in the engine state.

    Exactly THREE device buffers regardless of layer count — that is the
    point. A dict-of-scalars layout was measured to cost ~0.5 ms/step of
    pure buffer bookkeeping at ~110 keys on a 1-core CPU host; packing
    every scalar into one vector (and the two step trackers into one
    int32 vector each) makes carrying the telemetry through a jitted
    step nearly free, and lets :class:`MetricsCollector` drain with one
    contiguous ``device_get``.

    ``last_factor_step`` / ``last_inv_step``: ``(n_layers,)`` int32 —
    per layer (in ``names`` order), the engine step at which a factor /
    inverse update was last ACCEPTED (health rollbacks do not advance
    them); staleness derives from these. ``scalars``: ``(n_keys,)``
    float32 in ``keys`` order (the :func:`metric_keys` schema).

    ``names`` and ``keys`` are static aux data of the pytree, so tracing
    sees only the three arrays and the schema travels with the state for
    labeling at drain time. Like ``health``, this state is ephemeral: it
    is not part of ``checkpoint.durable_state`` and is rebuilt by
    ``init()`` on restore.
    """

    __slots__ = ('names', 'keys', 'last_factor_step', 'last_inv_step',
                 'scalars')

    def __init__(
        self,
        names: tuple[str, ...],
        keys: tuple[str, ...],
        last_factor_step: jax.Array,
        last_inv_step: jax.Array,
        scalars: jax.Array,
    ) -> None:
        object.__setattr__(self, 'names', tuple(names))
        object.__setattr__(self, 'keys', tuple(keys))
        object.__setattr__(self, 'last_factor_step', last_factor_step)
        object.__setattr__(self, 'last_inv_step', last_inv_step)
        object.__setattr__(self, 'scalars', scalars)

    def __setattr__(self, name: str, value: Any) -> None:
        raise AttributeError('MetricsState is immutable; use _replace')

    def tree_flatten(self):
        return (
            (self.last_factor_step, self.last_inv_step, self.scalars),
            (self.names, self.keys),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, keys = aux
        return cls(names, keys, *children)

    def _replace(self, **kw: Any) -> 'MetricsState':
        fields = {s: kw.pop(s, getattr(self, s)) for s in self.__slots__}
        if kw:
            raise TypeError(f'unknown MetricsState fields: {sorted(kw)}')
        return MetricsState(**fields)

    def as_dict(self) -> dict[str, jax.Array]:
        """Scalar vector as ``{key: 0-d array}`` (host-side convenience)."""
        return {k: self.scalars[i] for i, k in enumerate(self.keys)}

    def __repr__(self) -> str:
        return (
            f'MetricsState(n_layers={len(self.names)}, '
            f'n_keys={len(self.keys)})'
        )


def metric_keys(config: MetricsConfig, names: list[str]) -> list[str]:
    """The documented, order-stable scalar key schema for ``names``.

    See docs/OBSERVABILITY.md for the table; tests pin this schema for
    both engines and both KAISA transports.
    """
    keys = ['kl_clip_scale']
    for n in names:
        if config.grad_norms:
            keys.append(f'grad_norm/{n}')
            keys.append(f'precond_grad_norm/{n}')
        keys.append(f'damping_eff/{n}')
        if config.factor_bounds:
            keys.append(f'factor_lmin/a/{n}')
            keys.append(f'factor_lmax/a/{n}')
            keys.append(f'factor_lmin/g/{n}')
            keys.append(f'factor_lmax/g/{n}')
        if config.staleness:
            keys.append(f'factor_staleness/{n}')
            keys.append(f'inv_staleness/{n}')
    return keys


def init_metrics(config: MetricsConfig, names: list[str]) -> MetricsState:
    """Zero-initialized state with every schema key pre-populated.

    ``kl_clip_scale`` starts at 1.0 (the no-clip identity) so a drain
    before the first preconditioned step reads as 'no rescaling'.
    """
    names = tuple(names)
    keys = tuple(metric_keys(config, list(names)))
    scalars = jnp.zeros((len(keys),), jnp.float32)
    scalars = scalars.at[keys.index('kl_clip_scale')].set(1.0)
    return MetricsState(
        names=names,
        keys=keys,
        last_factor_step=jnp.zeros((len(names),), jnp.int32),
        last_inv_step=jnp.zeros((len(names),), jnp.int32),
        scalars=scalars,
    )


def update_scalars(
    ms: MetricsState, updates: dict[str, jax.Array]
) -> MetricsState:
    """Scatter ``{key: value}`` into the packed scalar vector (one op)."""
    if not updates:
        return ms
    index = {k: i for i, k in enumerate(ms.keys)}
    idxs = jnp.asarray([index[k] for k in updates], jnp.int32)
    vals = jnp.stack([jnp.asarray(v, jnp.float32) for v in updates.values()])
    return ms._replace(scalars=ms.scalars.at[idxs].set(vals))


def advance_last(
    last: jax.Array,
    names: tuple[str, ...],
    touched: dict[str, jax.Array | None],
    step: jax.Array,
) -> jax.Array:
    """Advance per-layer last-accepted-step entries, one scatter.

    ``touched[name]`` is the health verdict for this phase: ``None``
    means unconditionally accepted (health off), a bool array gates the
    advance (a rolled-back update keeps the old step, so staleness keeps
    growing through a quarantine).
    """
    idxs, vals = [], []
    for i, n in enumerate(names):
        if n not in touched:
            continue
        acc = touched[n]
        idxs.append(i)
        vals.append(step if acc is None else jnp.where(acc, step, last[i]))
    if not idxs:
        return last
    return last.at[jnp.asarray(idxs, jnp.int32)].set(
        jnp.stack([jnp.asarray(v, jnp.int32) for v in vals]))


def gershgorin_bounds(factor: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gershgorin eigenvalue bounds of (a stack of) symmetric factors.

    For each trailing ``(d, d)`` matrix: ``lmax = max_i sum_j |a_ij|``
    and ``lmin = min_i (a_ii - sum_{j!=i} |a_ij|)``. O(d^2) versus the
    O(d^3) eigendecomposition, which is why the per-step telemetry uses
    it; ``lmin`` can be negative for diagonally non-dominant factors even
    when the true spectrum is positive — it is a bound, not an estimate.
    Leading batch dimensions are reduced away (bounds over the stack).
    """
    f32 = factor.astype(jnp.float32)
    absrow = jnp.sum(jnp.abs(f32), axis=-1)
    diag = jnp.diagonal(f32, axis1=-2, axis2=-1)
    lmax = jnp.max(absrow, axis=-1)
    lmin = jnp.min(diag - (absrow - jnp.abs(diag)), axis=-1)
    if lmax.ndim:
        lmax = jnp.max(lmax)
        lmin = jnp.min(lmin)
    return lmin, lmax


def finalize(
    metrics: MetricsState,
    config: MetricsConfig,
    step: jax.Array,
) -> MetricsState:
    """Derive the staleness scalars for the step ending at ``step``.

    Called once per engine ``step()`` after the factor/inverse phases
    have refreshed ``last_*_step``; staleness is 'how many steps ago was
    the curvature information last accepted', so an update accepted this
    very step reads 0.
    """
    if not config.staleness:
        return metrics
    index = {k: i for i, k in enumerate(metrics.keys)}
    f_idx = jnp.asarray(
        [index[f'factor_staleness/{n}'] for n in metrics.names], jnp.int32)
    i_idx = jnp.asarray(
        [index[f'inv_staleness/{n}'] for n in metrics.names], jnp.int32)
    scalars = metrics.scalars.at[f_idx].set(
        (step - metrics.last_factor_step).astype(jnp.float32))
    scalars = scalars.at[i_idx].set(
        (step - metrics.last_inv_step).astype(jnp.float32))
    return metrics._replace(scalars=scalars)


class MetricsCollector:
    """Host-side drain for the in-jit metrics state.

    One ``drain(state)`` call performs a single ``jax.device_get`` of the
    scalar dict, the engine step and, where the state carries them
    (``DistKFACState.refresh``), the last Newton-Schulz refresh's counters,
    whose totals become ``refresh/*`` keys once a refresh has filled them;
    it folds in the host-side families: ``tracing.health_counters`` when
    the health sentinel is on, and optionally the ``tracing`` wall-time
    table as ``time/*`` keys.
    Between drains the telemetry costs zero host syncs.
    """

    def __init__(
        self,
        include_health: bool = True,
        include_trace: bool = False,
        trace_max_history: int | None = 256,
    ) -> None:
        self.include_health = include_health
        self.include_trace = include_trace
        # averaging everything the tracing table keeps
        # (tracing.TRACE_HISTORY calls a key) skews time/* toward old
        # steps (a warm-up compile dominates for a thousand steps), so
        # the fold-in reads a shorter most-recent window by default.
        # None = all the table keeps.
        self.trace_max_history = trace_max_history

    def drain(self, state: Any) -> dict[str, Any]:
        """Snapshot ``state``'s telemetry as a flat JSON-friendly dict.

        Accepts an engine state (``KFACState`` / ``DistKFACState``) or a
        ``Trainer`` ``TrainState`` (its ``kfac_state`` is unwrapped).
        Returns ``{}`` when metrics are disabled and no host-side family
        applies, so sinks can be driven unconditionally.
        """
        kstate = getattr(state, 'kfac_state', state)
        record: dict[str, Any] = {}
        metrics = getattr(kstate, 'metrics', None)
        refresh = getattr(kstate, 'refresh', None)
        pulled = jax.device_get({
            'step': kstate.step if metrics is not None else None,
            'scalars': metrics.scalars if metrics is not None else None,
            'refresh': refresh,
        })
        if metrics is not None:
            record['step'] = int(pulled['step'])
            record.update({
                k: float(v)
                for k, v in zip(metrics.keys, pulled['scalars'])
            })
        if self.include_health:
            from kfac_tpu import tracing
            record.update(tracing.health_counters(kstate))
        if refresh is not None:
            from kfac_tpu.parallel import kaisa
            # {} until a refresh has filled the counters: no key, rather
            # than a zero residual that reads as a healthy solve
            record.update(kaisa.refresh_totals(pulled['refresh']))
        if self.include_trace:
            from kfac_tpu import tracing
            trace = tracing.get_trace(
                average=True, max_history=self.trace_max_history
            )
            for key, seconds in trace.items():
                record[f'time/{key}'] = seconds
        return record
