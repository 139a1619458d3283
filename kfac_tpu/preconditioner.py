"""K-FAC preconditioner: functional state machine over a layer registry.

The TPU-native counterpart of the reference's
``BaseKFACPreconditioner``/``KFACPreconditioner``
(kfac/base_preconditioner.py:22-479, kfac/preconditioner.py:34-334), restated
for JAX: no hooks, no in-place ``.grad`` mutation, no per-rank branching.
All second-order state lives in an explicit :class:`KFACState` pytree and
``step`` is a pure function — jit/pjit it, donate the state, chain the result
into any optax optimizer.

Distribution model (vs reference L1/L4/L5):
- factor "allreduce" is implicit: with the loss computed under pjit over a
  ``data`` mesh axis, the covariance contraction ``a^T a / N`` is a sharded
  matmul and XLA inserts the psum (reference: kfac/layers/base.py:282-336).
- eigendecomposition work sharding (KAISA's grad-worker fraction) is provided
  by :mod:`kfac_tpu.parallel` as sharded batched-eigh over padded buckets,
  driven by the same greedy assignment (see kfac_tpu/assignment.py).
"""

from __future__ import annotations

import dataclasses
import functools
import operator
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from kfac_tpu import enums
from kfac_tpu import health as health_lib
from kfac_tpu import tracing
from kfac_tpu import warnings as kfac_warnings
from kfac_tpu.async_inverse import config as async_config_lib
from kfac_tpu.async_inverse import host as async_host
from kfac_tpu.async_inverse import sliced as async_sliced
from kfac_tpu.async_inverse import slots as async_slots
from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.layers import helpers as helpers_lib
from kfac_tpu.layers import registry as registry_lib
from kfac_tpu.observability import compile_watch as compile_watch_lib
from kfac_tpu.observability import flight_recorder as flight_lib
from kfac_tpu.observability import metrics as metrics_lib
from kfac_tpu.ops import factors as factors_lib

ScalarOrSchedule = float | Callable[[jax.Array], jax.Array | float]


def default_compute_method(
    platform: str | None = None,
) -> tuple[enums.ComputeMethod, str]:
    """Platform-appropriate ``(compute_method, inverse_solver)`` defaults.

    The reference defaults to EIGEN everywhere
    (kfac/preconditioner.py:245-256) because cuSOLVER makes eigh cheap on
    GPU. On TPU, eigh/cholesky lower to sequential panel algorithms that are
    MXU-hostile: a single distinct-shape EIGEN step was measured never to
    finish compiling inside a 20-minute budget on v5e, while
    the Newton-Schulz damped inverse is 2*iters large matmuls. So:

    - ``tpu`` -> (INVERSE, ``'newton_schulz'``)
    - anything else (cpu, gpu/cuSOLVER) -> (EIGEN, ``'cholesky'``), the
      reference's default behavior.
    """
    if platform is None:
        platform = jax.default_backend()
    if platform == 'tpu':
        return enums.ComputeMethod.INVERSE, 'newton_schulz'
    return enums.ComputeMethod.EIGEN, 'cholesky'


def _resolve(value: ScalarOrSchedule, step: jax.Array) -> jax.Array | float:
    """Callable-or-constant hyperparameters, resolved against the step counter.

    Reference semantics: kfac/base_preconditioner.py:160-208.
    """
    if callable(value):
        return value(step)
    return value


def _view_sum(fn: Callable[..., jax.Array], *views: dict) -> jax.Array:
    """``fn`` of the views' matching arrays, summed over a view's keys."""
    terms = [fn(*(v[k] for v in views)) for k in views[0]]
    return functools.reduce(operator.add, terms)


def _view_norm(view: dict[str, jax.Array]) -> jax.Array:
    def sumsq(x):
        x32 = x.astype(jnp.float32)
        return jnp.sum(x32 * x32)

    return jnp.sqrt(_view_sum(sumsq, view))


def finish_precondition(
    cfg: 'KFACPreconditioner',
    state: Any,
    views: dict[str, tuple[dict[str, jax.Array], dict[str, jax.Array]]],
    metrics_out: dict[str, jax.Array] | None,
) -> dict[str, dict[str, jax.Array]]:
    """What every engine does with its layers' preconditioned gradients,
    once it has them: telemetry, graceful degradation, the one kl-clip
    scale across layers, and the way back to flax param layout.

    ``views`` maps each layer to its gradient and its preconditioned
    gradient as matching views (``LayerHelper.grad_view``): every
    reduction here is a sum of elementwise products, so a view's layout
    does not matter to it. ``state`` is the engine's (``step``,
    ``health``). Returns each layer's leaves, in the gradient's dtype.
    """
    damping = _resolve(cfg.damping, state.step)
    lr = _resolve(cfg.lr, state.step)
    mcfg = cfg.metrics if metrics_out is not None else None
    vg = jnp.zeros((), jnp.float32)
    kept: dict[str, dict[str, jax.Array]] = {}
    for name, (gview, pview) in views.items():
        if mcfg is not None:
            if mcfg.grad_norms:
                metrics_out[f'grad_norm/{name}'] = _view_norm(gview)
            eff = (
                damping * state.health.damping_mult[name]
                if cfg.health is not None else damping
            )
            metrics_out[f'damping_eff/{name}'] = jnp.asarray(
                eff, jnp.float32)
        if cfg.health is not None:
            # graceful degradation: a layer past degrade_after consecutive
            # quarantined inversions bypasses its preconditioner — the raw
            # gradient flows through (still KL-clipped with the rest),
            # first-order for this layer only
            degraded = health_lib.is_degraded(
                cfg.health, state.health.bad_inv[name]
            )
            pview = {
                k: jnp.where(degraded, gview[k].astype(p.dtype), p)
                for k, p in pview.items()
            }
        if mcfg is not None and mcfg.grad_norms:
            # pre-scale norm, next to the kl_clip reduction's read of the
            # same arrays (one fused pass); rescaled by kl_clip_scale below
            # instead of re-reading the scaled tensor
            metrics_out[f'precond_grad_norm/{name}'] = _view_norm(pview)
        if cfg.kl_clip is not None:
            vg = vg + _view_sum(
                lambda p, g: factors_lib.kl_clip_terms(p, g, lr),
                pview, gview,
            )
        kept[name] = pview

    if cfg.kl_clip is not None and kept:
        scale = factors_lib.kl_clip_scale(
            vg, _resolve(cfg.kl_clip, state.step)
        )
    else:
        scale = None
    if mcfg is not None:
        metrics_out['kl_clip_scale'] = (
            scale.astype(jnp.float32) if scale is not None
            else jnp.ones((), jnp.float32)
        )

    out: dict[str, dict[str, jax.Array]] = {}
    for name, pview in kept.items():
        gview = views[name][0]
        if scale is not None:
            pview = {
                k: factors_lib.kl_clip_apply(p, scale)
                for k, p in pview.items()
            }
            if mcfg is not None and mcfg.grad_norms:
                metrics_out[f'precond_grad_norm/{name}'] = (
                    metrics_out[f'precond_grad_norm/{name}']
                    * jnp.abs(scale.astype(jnp.float32)))
        out[name] = helpers_lib.view_to_grads(
            cfg.registry.layers[name],
            {k: p.astype(gview[k].dtype) for k, p in pview.items()},
        )
    return out


class KFACState(NamedTuple):
    """All K-FAC second-order state as one pytree.

    ``a``/``g``: EMA Kronecker factors (fp32 by default).
    ``qa``/``qg``/``da``/``dg``: eigendecompositions (EIGEN method).
    ``a_inv``/``g_inv``: explicit inverses (INVERSE method).
    ``dgda``: fused ``1/(dg (x) da + damping)`` when prediv is enabled.
    ``health``: :class:`kfac_tpu.health.HealthState` counters when the
    numerical-health sentinel is enabled, else ``None`` (an empty pytree
    subtree — zero state, zero cost).
    ``metrics``: :class:`kfac_tpu.observability.MetricsState` per-layer
    telemetry scalars when metrics are enabled, else ``None`` — same
    contract as ``health``: ephemeral (not checkpointed; rebuilt by
    ``init``), zero cost when off.
    ``flight``: :class:`kfac_tpu.observability.FlightRecorderState`
    rolling last-N-step telemetry ring when the flight recorder is
    enabled, else ``None`` — same ephemeral contract as ``metrics``.
    ``shadow``: :class:`kfac_tpu.async_inverse.ShadowSlots` double-buffer
    twin of the decomposition slots when async inverse refresh is enabled,
    else ``None`` — ephemeral like ``metrics`` (not checkpointed; restore
    rematerializes the active decompositions and resets the shadow).
    Unused method slots hold empty dicts so the pytree structure is static
    per-configuration.
    """

    step: jax.Array
    a: dict[str, jax.Array]
    g: dict[str, jax.Array]
    qa: dict[str, jax.Array]
    qg: dict[str, jax.Array]
    da: dict[str, jax.Array]
    dg: dict[str, jax.Array]
    dgda: dict[str, jax.Array]
    a_inv: dict[str, jax.Array]
    g_inv: dict[str, jax.Array]
    health: Any = None
    metrics: Any = None
    flight: Any = None
    shadow: Any = None


@dataclasses.dataclass
class KFACPreconditioner:
    """Configuration + pure step functions for K-FAC preconditioning.

    Mirrors the reference's constructor surface
    (kfac/preconditioner.py:54-154) where it translates; distribution options
    are mesh-based and live in :mod:`kfac_tpu.parallel`.

    Args:
        registry: output of :func:`kfac_tpu.layers.registry.register_model`.
        factor_update_steps: steps between factor EMA updates (int or a
            schedule of the step counter, the LambdaParamScheduler
            equivalent — reference kfac/scheduler.py:119-167).
        inv_update_steps: steps between eigendecomposition updates (int or
            schedule).
        damping: Tikhonov damping (constant or schedule of step).
        factor_decay: EMA alpha (constant or schedule of step).
        kl_clip: KL clipping bound, or None to disable.
        lr: learning rate used in the KL-clip scale (constant or schedule).
        compute_method: EIGEN or INVERSE. Default (``None``) is selected per
            platform by :func:`default_compute_method` — EIGEN off-TPU (the
            reference's default, kfac/preconditioner.py:245-256) and
            INVERSE+Newton-Schulz on TPU, where EIGEN is pathological.
            Forcing EIGEN on a TPU backend raises
            :class:`~kfac_tpu.warnings.TPUPerformanceWarning`.
        prediv_eigenvalues: precompute 1/(dg x da + damping) at inv time.
        factor_dtype / inv_dtype: storage dtypes (decomps always run fp32).

    For sharded KAISA execution over a mesh use
    :class:`kfac_tpu.parallel.DistributedKFAC`, which reads its
    hyperparameters from an instance of this class.
    """

    # Entry points the IR analyzer (kfac_tpu/analysis/ir) traces to
    # jaxprs; IR_STEP_PATH marks the ones on the per-step critical path
    # (KFL204 callback policing). Unannotated on purpose: class
    # constants, not dataclass fields.
    IR_ENTRY_POINTS = (
        'update_factors', 'update_inverses', 'precondition', 'step',
    )
    IR_STEP_PATH = ('step',)

    registry: registry_lib.Registry
    # Optax-style trainability mask over the model params (prefix pytree
    # of bools; True = trainable, unmentioned paths trainable). Frozen
    # layers are dropped from the registry at construction
    # (registry.masked_registry): no capture taps, no factor state, no
    # KAISA bucket/assignment slots, no metrics keys — and their
    # gradients pass through precondition() untouched (unregistered
    # parameters already do). None (the default) touches nothing: the
    # registry is used exactly as given, bit-identical to a maskless
    # config. The distributed engine inherits the masked registry through
    # config.registry.
    mask: Any = None
    factor_update_steps: int | Callable[[jax.Array], jax.Array] = 1
    inv_update_steps: int | Callable[[jax.Array], jax.Array] = 1
    damping: ScalarOrSchedule = 0.001
    factor_decay: ScalarOrSchedule = 0.95
    kl_clip: ScalarOrSchedule | None = 0.001
    lr: ScalarOrSchedule = 0.1
    compute_method: enums.ComputeMethod | str | None = None
    # INVERSE-method solver: 'cholesky' (direct, best off-TPU),
    # 'newton_schulz' — residual-monitored matmul-only damped inversion
    # (ops/factors.newton_schulz_inverse), the TPU-native choice: on v5e a
    # single distinct-shape eigh/cholesky costs tens of seconds of compile
    # and ~140 ms/run at d=2048, while Newton-Schulz is <= 2*iters MXU
    # matmuls with residual-based early exit — or 'auto' (Newton-Schulz
    # with a Cholesky fallback when the final residual says the factor was
    # too ill-conditioned for the fp32 iteration; see
    # ops/factors.damped_inverse for the vmap cost caveat).
    # None selects per platform (see default_compute_method).
    inverse_solver: str | None = None
    # EIGEN-method decomposition backend: 'xla' (device eigh), 'host'
    # (jax.pure_callback to LAPACK on the host CPU — the escape hatch for
    # TPU, where the device eigh's compile alone is pathological; factors
    # are small, so the transfer is cheap), or 'eig_host' (general
    # non-symmetric eig on the host, real parts — the reference's
    # symmetric=False handling, kfac/layers/eigen.py:295-348, for factors
    # that drift numerically non-symmetric; here factors are symmetric by
    # construction, so this is a robustness corner only). See
    # ops/factors.batched_eigh.
    eigh_impl: str = 'xla'
    # Iteration cap for the Newton-Schulz solver. The residual stopping
    # rule exits earlier on benign factors (~15 iterations at kappa 1e4);
    # 40 reaches the fp32 accuracy floor past kappa 1e9, so raising it
    # further buys nothing — see ops/factors.newton_schulz_inverse_info.
    newton_schulz_iters: int = 40
    prediv_eigenvalues: bool = False
    factor_dtype: Any = jnp.float32
    inv_dtype: Any = jnp.float32
    # Size-class granularity for the distributed engine's factor buckets:
    # dims round up to a class (next multiple of this, powers of two below
    # it) so heterogeneous layer shapes (a ResNet's dozens of conv dims)
    # collapse into a few batched decompositions instead of dozens of
    # mostly-padding ones — the execution-side counterpart of the
    # reference's greedy cost balancing (kfac/assignment.py:227-319).
    # Padding is exact (identity-block factors, zero-block grads). 1
    # disables classing. None resolves per platform: 128 on TPU (the
    # per-distinct-shape compile dominates there) and 1 elsewhere (on
    # CPU/GPU the padded eigh FLOPs dominate — measured ~5x slower on a
    # ResNet at class 128 on the CPU test mesh). NOTE: stacked-layout
    # checkpoints (checkpoint.save) encode the resolved granularity, so a
    # platform-default checkpoint does NOT restore on a platform that
    # resolves differently — pin an explicit value for cross-platform
    # restores, or use checkpoint.save_factors (layout-independent).
    # Ignored by the dense engine.
    bucket_granularity: int | None = None
    # Whether the distributed engine stores/decomposes a layer's A and G in
    # the same stack slot (same device). False buckets A and G factors
    # independently by dimension, so the two eigendecompositions of a large
    # layer can run on different devices — the reference's
    # colocate_factors=False placement split (kfac/assignment.py:268-304) —
    # at the cost of replicating the assembled decompositions for
    # preconditioning. Ignored by the dense engine.
    colocate_factors: bool = True
    # How the distributed engine transports factor statistics into the
    # stacked layout: ALLREDUCE gathers each factor individually (XLA fuses
    # on ICI); ALLREDUCE_BUCKETED packs all upper triangles of a bucket into
    # one flat buffer first — fewer, larger collectives and half the bytes,
    # the reference's symmetric 25MB bucketing (kfac/distributed.py:305-374,
    # 422-465) for DCN-bound multihost meshes. Ignored by the dense engine
    # (no transport).
    allreduce_method: enums.AllreduceMethod = enums.AllreduceMethod.ALLREDUCE
    # Byte cap per packed buffer under ALLREDUCE_BUCKETED, in MB (the
    # reference's bucket cap, default 25 MB, kfac/distributed.py:305-374).
    # Bounds the transient pack/unpack footprint on large models — without
    # a cap, one buffer holds a second copy of every factor triangle at
    # once — and keeps each collective inside the interconnect's
    # comfortable message size. None = unbounded (single buffer).
    allreduce_bucket_cap_mb: float | None = 25.0
    # Numerical-health sentinel (kfac_tpu/health.py, docs/ROBUSTNESS.md):
    # skip-step, per-layer factor quarantine with escalated damping, and
    # graceful degradation to raw-gradient updates. None disables all
    # health machinery (reference semantics: a non-finite capture poisons
    # the run); True enables HealthConfig defaults; or pass a
    # health.HealthConfig to tune thresholds. Honored by both engines and
    # by Trainer's skip-step gate.
    health: health_lib.HealthConfig | bool | None = None
    # In-jit per-layer telemetry (kfac_tpu/observability,
    # docs/OBSERVABILITY.md): grad/preconditioned-grad norms, kl_clip
    # scale, effective damping, Gershgorin factor bounds, and
    # factor/inverse staleness, computed inside the jitted step and
    # drained host-side with observability.MetricsCollector. None disables
    # (zero state, zero cost); True enables MetricsConfig defaults; or
    # pass an observability.MetricsConfig to select scalar families.
    # Honored by both engines.
    metrics: 'metrics_lib.MetricsConfig | bool | None' = None
    # Flight recorder (kfac_tpu/observability/flight_recorder.py,
    # docs/OBSERVABILITY.md): fixed-capacity on-device ring buffer
    # recording the last N steps of the metric scalar schema plus loss
    # and global grad norm, written in-jit (no host syncs, no
    # recompilation); drained with observability.drain_flight and
    # consumed by observability.PostmortemWriter / tools/kfac_inspect.py.
    # None disables; True enables FlightRecorderConfig defaults; an int
    # is a capacity shorthand; or pass a FlightRecorderConfig. Enabling
    # it auto-enables `metrics` (the ring records that schema). Honored
    # by both engines and all Trainer step paths (the Trainer supplies
    # the loss).
    flight: 'flight_lib.FlightRecorderConfig | bool | int | None' = None
    # Async inverse refresh (kfac_tpu/async_inverse, docs/ARCHITECTURE.md):
    # double-buffered active/shadow decomposition slots where the
    # inv_update_steps window's eigh/inverse work runs as an overlapped
    # side computation — 'sliced' (one balanced unit bucket per step,
    # in-jit, bit-identical results one window staler) or 'host'
    # (io_callback offload to a LAPACK worker thread, zero decomposition
    # work in the step program; the Trainer drives the boundary swap).
    # None keeps the synchronous boundary refresh; True selects 'sliced';
    # or pass an async_inverse.AsyncInverseConfig. Requires a static int
    # inv_update_steps (the window phase is compiled into the dispatch).
    # Honored by both engines.
    async_inverse: 'async_config_lib.AsyncInverseConfig | str | bool | None' = (
        None
    )
    # Compile watch (kfac_tpu/observability/compile_watch.py,
    # docs/OBSERVABILITY.md "Compile & memory truth"): recompile
    # attribution, per-compile XLA memory accounting, and crash-safe
    # mid-compile heartbeat journaling for every IR entry point and
    # every Trainer step path bound to this config. None disables (zero
    # cost, plain jit dispatch); True enables CompileWatchConfig
    # defaults; a str is a journal_path shorthand; or pass a
    # CompileWatchConfig. Honored by both engines; the Trainer routes
    # its own jitted step paths through the engine's watch.
    compile_watch: (
        'compile_watch_lib.CompileWatchConfig | str | bool | None'
    ) = None

    def __post_init__(self) -> None:
        if self.mask is not None:
            # drop mask-frozen layers up front so EVERY registry consumer
            # (engine state, capture, KAISA assignment via config.registry,
            # metrics, checkpoints) sees only trainable layers
            self.registry = registry_lib.masked_registry(
                self.registry, self.mask
            )
        # the A groups this engine stores one A factor for
        # (``Registry.a_groups``). The async refresh modes walk the state
        # layer by layer on both sides and keep every layer's own A (equal
        # matrices held several times: nothing else differs)
        self.a_groups = (
            dict(self.registry.a_groups) if self.async_inverse is None
            else {}
        )
        # counted once, from the registry: the share of the convolutions
        # with a kernel larger than 1 x 1 whose A factor is assembled from
        # the activation's autocorrelation (``None``: no such convolution)
        self.patchless_share = helpers_lib.patchless_share(
            self.registry.layers.values()
        )
        if self.metrics is True:
            self.metrics = metrics_lib.MetricsConfig()
        elif self.metrics is False:
            self.metrics = None
        elif self.metrics is not None and not isinstance(
            self.metrics, metrics_lib.MetricsConfig
        ):
            raise TypeError(
                'metrics must be a MetricsConfig, True, False, or None; '
                f'got {self.metrics!r}'
            )
        if self.flight is True:
            self.flight = flight_lib.FlightRecorderConfig()
        elif self.flight is False:
            self.flight = None
        elif isinstance(self.flight, int) and not isinstance(
            self.flight, bool
        ):
            self.flight = flight_lib.FlightRecorderConfig(
                capacity=self.flight
            )
        elif self.flight is not None and not isinstance(
            self.flight, flight_lib.FlightRecorderConfig
        ):
            raise TypeError(
                'flight must be a FlightRecorderConfig, True, False, an '
                f'int capacity, or None; got {self.flight!r}'
            )
        if self.flight is not None and self.metrics is None:
            # the ring records the metric scalar schema; an empty schema
            # would make it a loss-only recorder, which is never what a
            # flight=True caller wants
            self.metrics = metrics_lib.MetricsConfig()
        if self.compile_watch is True:
            self.compile_watch = compile_watch_lib.CompileWatchConfig()
        elif self.compile_watch is False:
            self.compile_watch = None
        elif isinstance(self.compile_watch, str):
            self.compile_watch = compile_watch_lib.CompileWatchConfig(
                journal_path=self.compile_watch
            )
        elif self.compile_watch is not None and not isinstance(
            self.compile_watch, compile_watch_lib.CompileWatchConfig
        ):
            raise TypeError(
                'compile_watch must be a CompileWatchConfig, True, False, '
                f'a journal path str, or None; got {self.compile_watch!r}'
            )
        if self.health is True:
            self.health = health_lib.HealthConfig()
        elif self.health is False:
            self.health = None
        elif self.health is not None and not isinstance(
            self.health, health_lib.HealthConfig
        ):
            raise TypeError(
                'health must be a HealthConfig, True, False, or None; got '
                f'{self.health!r}'
            )
        if isinstance(self.compute_method, str):
            try:
                self.compute_method = enums.ComputeMethod[self.compute_method.upper()]
            except KeyError:
                raise ValueError(
                    f'unknown compute_method {self.compute_method!r}; '
                    f'expected one of {[m.name.lower() for m in enums.ComputeMethod]}'
                ) from None
        platform = jax.default_backend()

        if self.eigh_impl not in ('xla', 'host', 'eig_host'):
            raise ValueError(
                f"unknown eigh_impl {self.eigh_impl!r}; expected 'xla', "
                "'host', or 'eig_host'"
            )
        if self.compute_method is None:
            self.compute_method = default_compute_method(platform)[0]
        elif (
            self.compute_method == enums.ComputeMethod.EIGEN
            # host offload (symmetric or general) sidesteps the hazard
            and self.eigh_impl not in ('host', 'eig_host')
            and platform == 'tpu'
        ):
            warnings.warn(
                'compute_method=EIGEN on a TPU backend: eigh lowers to a '
                'sequential panel algorithm whose compile alone was measured '
                'in tens of minutes on v5e. The TPU-native path is '
                "compute_method='inverse' with inverse_solver="
                "'newton_schulz' (the default when compute_method is left "
                "unset); to keep EIGEN semantics, pass eigh_impl='host' to "
                'offload the decomposition to the host CPU (LAPACK).',
                kfac_warnings.TPUPerformanceWarning,
                stacklevel=2,
            )
        if self.inverse_solver is None:
            self.inverse_solver = (
                default_compute_method(platform)[1]
                if self.compute_method == enums.ComputeMethod.INVERSE
                else 'cholesky'
            )
        if self.bucket_granularity is None:
            self.bucket_granularity = 128 if platform == 'tpu' else 1
        elif self.bucket_granularity < 1:
            raise ValueError(
                f'bucket_granularity must be >= 1 (or None for the '
                f'platform default), got {self.bucket_granularity}'
            )
        if isinstance(self.allreduce_method, str):
            try:
                self.allreduce_method = enums.AllreduceMethod[
                    self.allreduce_method.upper()
                ]
            except KeyError:
                raise ValueError(
                    f'unknown allreduce_method {self.allreduce_method!r}; '
                    f'expected one of '
                    f'{[m.name.lower() for m in enums.AllreduceMethod]}'
                ) from None
        if (
            self.allreduce_bucket_cap_mb is not None
            and self.allreduce_bucket_cap_mb <= 0
        ):
            raise ValueError(
                f'allreduce_bucket_cap_mb must be > 0 (or None for '
                f'unbounded), got {self.allreduce_bucket_cap_mb}'
            )
        if self.inverse_solver not in ('cholesky', 'newton_schulz', 'auto'):
            raise ValueError(
                f'unknown inverse_solver {self.inverse_solver!r}; expected '
                "'cholesky', 'newton_schulz', or 'auto'"
            )
        if (
            self.inverse_solver in ('newton_schulz', 'auto')
            and self.compute_method == enums.ComputeMethod.EIGEN
        ):
            warnings.warn(
                f'inverse_solver={self.inverse_solver!r} has no effect with '
                'the EIGEN compute method (it replaces the INVERSE-method '
                "solve); pass compute_method='inverse' to use it",
                stacklevel=2,
            )
        for name in ('factor_update_steps', 'inv_update_steps'):
            value = getattr(self, name)
            if not callable(value) and value < 1:
                raise ValueError(f'{name} must be >= 1, got {value}')
        if (
            not callable(self.factor_update_steps)
            and not callable(self.inv_update_steps)
            and self.inv_update_steps % self.factor_update_steps != 0
        ):
            warnings.warn(
                'inv_update_steps is not a multiple of factor_update_steps; '
                'some inverse updates will recompute from unchanged factors',
                stacklevel=2,
            )
        self.async_inverse = async_config_lib.as_async_config(
            self.async_inverse
        )
        if self.async_inverse is not None and callable(self.inv_update_steps):
            raise ValueError(
                'async_inverse requires a static int inv_update_steps (the '
                'refresh window phase is compiled into the step dispatch); '
                'got a schedule'
            )
        self._plan_async()

    def _plan_async(self) -> None:
        """Precompute the async refresh plan (slice buckets, window size).

        Attribute surface shared with the distributed engine:
        ``_async_mode`` (None | 'sliced' | 'host'), ``_async_n_steps``
        (window length), and for sliced mode ``_async_slices`` /
        ``_async_n_slices`` (the balanced per-step unit buckets).
        """
        acfg = self.async_inverse
        self._async_mode = None if acfg is None else acfg.mode
        self._async_worker = None
        self._async_apply_cache = None
        if acfg is None:
            return
        self._async_n_steps = int(self.inv_update_steps)
        if acfg.mode == 'sliced':
            units = async_sliced.dense_units(self)
            n = min(self._async_n_steps, acfg.max_slices or len(units))
            self._async_slices = async_slots.plan_slices(units, n)
            self._async_n_slices = len(self._async_slices)

    def a_leader(self, name: str) -> str:
        """The layer under whose name ``name``'s A factor, its
        decomposition and its inverse are kept: its A group's leader
        (``Registry.a_groups``), or itself."""
        return self.a_groups.get(name, name)

    # ------------------------------------------------------------------ init

    def init(self) -> KFACState:
        """Eagerly allocate factor state (identity factors, zero decomps).

        The reference lazily materializes factors at first update with
        identity init (kfac/layers/base.py:375-405); eager identity init is
        equivalent because the first EMA update sees the same identity.
        """
        a = {}
        g = {}
        qa, qg, da, dg, dgda = {}, {}, {}, {}, {}
        a_inv, g_inv = {}, {}
        eigen = self.compute_method == enums.ComputeMethod.EIGEN
        for name, h in self.registry.layers.items():
            na = h.a_factor_shape[0]
            ng = h.g_factor_shape[0]
            # the A side under the group's leader alone (``a_leader``)
            leads = self.a_leader(name) == name
            if leads:
                a[name] = jnp.eye(na, dtype=self.factor_dtype)
            g[name] = jnp.eye(ng, dtype=self.factor_dtype)
            if eigen:
                if leads:
                    qa[name] = jnp.zeros((na, na), dtype=self.inv_dtype)
                qg[name] = jnp.zeros((ng, ng), dtype=self.inv_dtype)
                if self.prediv_eigenvalues:
                    dgda[name] = jnp.zeros((ng, na), dtype=self.inv_dtype)
                else:
                    if leads:
                        da[name] = jnp.zeros((na,), dtype=self.inv_dtype)
                    dg[name] = jnp.zeros((ng,), dtype=self.inv_dtype)
            else:
                if leads:
                    a_inv[name] = jnp.zeros((na, na), dtype=self.inv_dtype)
                g_inv[name] = jnp.zeros((ng, ng), dtype=self.inv_dtype)
        state = KFACState(
            step=jnp.asarray(0, dtype=jnp.int32),
            a=a, g=g, qa=qa, qg=qg, da=da, dg=dg, dgda=dgda,
            a_inv=a_inv, g_inv=g_inv,
            health=(
                health_lib.init_health(self.registry.layers)
                if self.health is not None else None
            ),
            metrics=(
                metrics_lib.init_metrics(
                    self.metrics, list(self.registry.layers)
                )
                if self.metrics is not None else None
            ),
            flight=(
                flight_lib.init_flight(
                    self.flight,
                    metrics_lib.metric_keys(
                        self.metrics, list(self.registry.layers)
                    ),
                )
                if self.flight is not None else None
            ),
        )
        # host mode keeps no device-side shadow: the double buffer lives in
        # the worker payload until the boundary apply
        if self._async_mode == 'sliced':
            state = state._replace(
                shadow=async_sliced.dense_shadow(self, state)
            )
        return state

    # --------------------------------------------------------------- factors

    @tracing.scope('kfac.update_factors')
    def update_factors(
        self,
        state: KFACState,
        stats: capture_lib.CapturedStats,
    ) -> KFACState:
        """EMA-update running factors from per-batch statistics.

        Reference: kfac/layers/base.py:375-405. Statistics must already be
        averaged over data-parallel replicas (automatic under pjit).
        """
        alpha = _resolve(self.factor_decay, state.step)
        # Layers registered but not executed by this loss_fn simply keep
        # their factors (in the reference, hooks for unexecuted modules
        # never fire). Layers with a capture weight (routed MoE) decay by
        # alpha_eff = 1 - (1-alpha)*w: the EMA moves proportionally to the
        # evidence this capture actually carried — a zero-traffic expert's
        # factors stay put instead of diluting toward zero.
        weights = getattr(stats, 'w', None) or {}

        def eff_alpha(n):
            if n in weights:
                return factors_lib.effective_alpha(alpha, weights[n])
            return alpha

        # the .astype pins the result to factor_dtype: a traced alpha or a
        # float32 capture weight would otherwise promote bf16 factor state
        # and break the step's lax.cond branch-type equality
        # (a capture files a group's one A under the registry's leader)
        a_stats = {
            n: capture_lib.a_stat(stats, self.registry, n) for n in state.a
        }
        new_a = {
            n: factors_lib.ema_update(
                state.a[n], a_stats[n].astype(self.factor_dtype),
                eff_alpha(n),
            ).astype(self.factor_dtype)
            if a_stats[n] is not None else state.a[n]
            for n in state.a
        }
        new_g = {
            n: factors_lib.ema_update(
                state.g[n], stats.g[n].astype(self.factor_dtype), eff_alpha(n)
            ).astype(self.factor_dtype)
            if n in stats.g else state.g[n]
            for n in state.g
        }
        # per-layer acceptance verdicts (health sentinel); layers without a
        # verdict were accepted unconditionally — the metrics block below
        # reads this to advance last_factor_step only for accepted updates
        ok_verdicts: dict[str, jax.Array] = {}
        new_health = state.health
        if self.health is not None:
            # factor quarantine: a non-finite or
            # quarantine-threshold-violating candidate rolls BOTH of the
            # layer's factors back to their previous (healthy) values and
            # escalates the layer's damping multiplier; healthy updates
            # decay the multiplier back toward 1. Layers not in this
            # capture (unexecuted) get no verdict — their factors did not
            # move. The verdict is taken at the layer's EFFECTIVE damping:
            # an already-escalated layer is judged by the inverse it would
            # actually compute.
            cfg = self.health
            h = state.health
            damping = _resolve(self.damping, state.step)
            mult = dict(h.damping_mult)
            quarantined = dict(h.quarantined)
            events = dict(h.quarantine_events)
            cand_a = dict(new_a)
            for n in state.g:
                la = self.a_leader(n)
                if a_stats[la] is None and n not in stats.g:
                    continue
                eff = damping * h.damping_mult[n]
                ok = health_lib.factor_ok(
                    cand_a[la], eff, cfg.quarantine_threshold
                ) & health_lib.factor_ok(
                    new_g[n], eff, cfg.quarantine_threshold
                )
                ok_verdicts[n] = ok
                # a group's one A goes back with any member's rollback
                new_a[la] = jnp.where(ok, new_a[la], state.a[la])
                new_g[n] = jnp.where(ok, new_g[n], state.g[n])
                mult[n], quarantined[n], events[n] = (
                    health_lib.quarantine_update(
                        cfg, ok, h.damping_mult[n], h.quarantined[n],
                        h.quarantine_events[n],
                    )
                )
            new_health = h._replace(
                damping_mult=mult, quarantined=quarantined,
                quarantine_events=events,
            )
        state = state._replace(a=new_a, g=new_g, health=new_health)
        if self.metrics is not None and state.metrics is not None:
            state = state._replace(
                metrics=self._record_factor_metrics(
                    state, stats, ok_verdicts
                )
            )
        return state

    def _record_factor_metrics(
        self,
        state: KFACState,
        stats: capture_lib.CapturedStats,
        ok_verdicts: dict[str, jax.Array],
    ) -> metrics_lib.MetricsState:
        """Factor-phase telemetry on the POST-rollback factors.

        Gershgorin bounds describe the factors that will actually be
        decomposed; ``last_factor_step`` advances only for layers whose
        update this capture touched AND the health sentinel accepted.
        """
        mcfg = self.metrics
        ms = state.metrics
        scalars: dict[str, jax.Array] = {}
        touched: dict[str, jax.Array | None] = {}
        for n in state.g:
            if (
                capture_lib.a_stat(stats, self.registry, n) is None
                and n not in stats.g
            ):
                continue
            if mcfg.factor_bounds:
                lmin_a, lmax_a = metrics_lib.gershgorin_bounds(
                    state.a[self.a_leader(n)])
                lmin_g, lmax_g = metrics_lib.gershgorin_bounds(state.g[n])
                scalars[f'factor_lmin/a/{n}'] = lmin_a
                scalars[f'factor_lmax/a/{n}'] = lmax_a
                scalars[f'factor_lmin/g/{n}'] = lmin_g
                scalars[f'factor_lmax/g/{n}'] = lmax_g
            touched[n] = ok_verdicts.get(n)
        return metrics_lib.update_scalars(ms, scalars)._replace(
            last_factor_step=metrics_lib.advance_last(
                ms.last_factor_step, ms.names, touched, state.step))

    # -------------------------------------------------------------- inverses

    @tracing.scope('kfac.update_inverses')
    def update_inverses(self, state: KFACState) -> KFACState:
        """Recompute eigendecompositions (or inverses) from current factors.

        Reference: kfac/layers/eigen.py:295-348, kfac/layers/inverse.py:186-213.

        With the health sentinel enabled, each layer's decomposition runs at
        its EFFECTIVE damping (``damping * damping_mult``); a non-finite
        result rolls back to the layer's previous decomposition, and the
        degradation counter (``bad_inv``) advances whenever the refresh was
        *quarantined* — ran from a quarantined factor or produced a
        non-finite output — and recovers on healthy refreshes.
        """
        damping = _resolve(self.damping, state.step)
        cfg = self.health
        h = state.health
        bad_inv = dict(h.bad_inv) if cfg is not None else {}
        inv_ok: dict[str, jax.Array] = {}

        def eff_damping(name):
            if cfg is None:
                return damping
            return damping * h.damping_mult[name]

        def outputs_ok(*arrays):
            flags = [jnp.isfinite(x).all() for x in arrays]
            return jnp.stack(flags).all()

        if self.compute_method == enums.ComputeMethod.EIGEN:
            qa, qg = dict(state.qa), dict(state.qg)
            da, dg = dict(state.da), dict(state.dg)
            dgda = dict(state.dgda)
            adecs: dict[str, factors_lib.EigenDecomp] = {}
            for name in self.registry.layers:
                # a group's A is decomposed once, under its leader (the
                # first of its members met here)
                la = self.a_leader(name)
                if la not in adecs:
                    adecs[la] = factors_lib.compute_eigh(
                        state.a[la], self.inv_dtype, self.eigh_impl
                    )
                adec = adecs[la]
                gdec = factors_lib.compute_eigh(
                    state.g[name], self.inv_dtype, self.eigh_impl
                )
                cand = {'qa': adec.q, 'qg': gdec.q}
                if self.prediv_eigenvalues:
                    cand['dgda'] = factors_lib.prediv_eigenvalues(
                        adec, gdec, eff_damping(name)
                    ).astype(self.inv_dtype)
                else:
                    cand['da'], cand['dg'] = adec.d, gdec.d
                if cfg is not None:
                    ok = outputs_ok(*cand.values())
                    inv_ok[name] = ok
                    prev = {
                        'qa': state.qa[la], 'qg': state.qg[name],
                        'dgda': state.dgda.get(name),
                        'da': state.da.get(la), 'dg': state.dg.get(name),
                    }
                    cand = {
                        k: jnp.where(ok, v, prev[k]) for k, v in cand.items()
                    }
                    bad_inv[name] = health_lib.inversion_update(
                        cfg, ok, h.quarantined[name], h.bad_inv[name]
                    )
                qg[name] = cand['qg']
                if la == name:
                    qa[name] = cand['qa']
                if self.prediv_eigenvalues:
                    dgda[name] = cand['dgda']
                else:
                    dg[name] = cand['dg']
                    if la == name:
                        da[name] = cand['da']
            state = state._replace(qa=qa, qg=qg, da=da, dg=dg, dgda=dgda)
        else:
            # warm-start Newton-Schulz from the previous inverse: the factor
            # EMA drifts slowly between inv_update_steps refreshes, so the
            # old inverse is deep in the quadratic basin (the safeguard
            # inside newton_schulz_inverse_info falls back to the Gershgorin
            # cold start for the all-zeros inverses of a fresh state, whose
            # scaled phase starts from what is left of the factors'
            # identity initialisation)
            floor = factors_lib.identity_floor(
                state.step, self.factor_decay, self.factor_update_steps
            )
            inv = lambda f, prev, dmp: factors_lib.damped_inverse(
                f, dmp, self.inv_dtype, self.inverse_solver,
                self.newton_schulz_iters, x0=prev, floor=floor,
            )
            a_inv, g_inv = dict(state.a_inv), dict(state.g_inv)
            solved_a: dict[str, jax.Array] = {}
            for name in state.g:
                # a group's A is solved once, at its leader's damping
                la = self.a_leader(name)
                if la not in solved_a:
                    solved_a[la] = inv(
                        state.a[la], state.a_inv[la], eff_damping(la)
                    )
                cand_a = solved_a[la]
                cand_g = inv(state.g[name], state.g_inv[name], eff_damping(name))
                if cfg is not None:
                    ok = outputs_ok(cand_a, cand_g)
                    inv_ok[name] = ok
                    if la == name:
                        cand_a = jnp.where(ok, cand_a, state.a_inv[name])
                    cand_g = jnp.where(ok, cand_g, state.g_inv[name])
                    bad_inv[name] = health_lib.inversion_update(
                        cfg, ok, h.quarantined[name], h.bad_inv[name]
                    )
                if la == name:
                    a_inv[name] = cand_a
                g_inv[name] = cand_g
            state = state._replace(a_inv=a_inv, g_inv=g_inv)
        if cfg is not None:
            state = state._replace(health=h._replace(bad_inv=bad_inv))
        if self.metrics is not None and state.metrics is not None:
            ms = state.metrics
            touched = {n: inv_ok.get(n) for n in self.registry.layers}
            state = state._replace(metrics=ms._replace(
                last_inv_step=metrics_lib.advance_last(
                    ms.last_inv_step, ms.names, touched, state.step)))
        return state

    # --------------------------------------------------------- precondition

    def _precondition_one(
        self,
        state: KFACState,
        name: str,
        gview: dict[str, jax.Array],
        damping: jax.Array | float,
    ) -> dict[str, jax.Array]:
        """One layer's preconditioned gradient, as a view like ``gview``:
        the matrix form (``helpers.matrix_view``) for the eigen methods,
        the helper's own (``LayerHelper.grad_view``) for explicit
        inverses."""
        la = self.a_leader(name)
        if self.compute_method == enums.ComputeMethod.EIGEN:
            grad_mat = gview[helpers_lib.MATRIX]
            if self.prediv_eigenvalues:
                v1 = state.qg[name].T @ grad_mat.astype(self.inv_dtype) @ state.qa[la]
                v2 = v1 * state.dgda[name]
                pmat = (state.qg[name] @ v2 @ state.qa[la].T).astype(grad_mat.dtype)
            else:
                pmat = factors_lib.eigen_preconditioned_grad(
                    grad_mat,
                    factors_lib.EigenDecomp(q=state.qa[la], d=state.da[la]),
                    factors_lib.EigenDecomp(q=state.qg[name], d=state.dg[name]),
                    damping,
                )
            return {helpers_lib.MATRIX: pmat}
        return self.registry.layers[name].inverse_precondition(
            gview, state.a_inv[la], state.g_inv[name]
        )

    @tracing.scope('kfac.precondition')
    def precondition(
        self,
        state: KFACState,
        grads: Any,
        metrics_out: dict[str, jax.Array] | None = None,
    ) -> Any:
        """Precondition a params-shaped gradient pytree.

        Unregistered parameters pass through unchanged. KL clipping applies
        one fused scalar reduction over all layers — no per-layer host syncs
        (cf. reference's ``.item()`` loop,
        kfac/base_preconditioner.py:411-435).

        With explicit inverses a Dense layer is multiplied on its leaves as
        they lie (``DenseHelper.inverse_precondition``); the eigen methods
        and the other helpers go through the packed matrix.

        ``metrics_out``, when given, is filled in-place with this phase's
        telemetry scalars (grad/preconditioned-grad norms, effective
        damping, kl_clip scale) — values the preconditioning math already
        materializes, so collection adds no extra passes; ``step`` merges
        them into ``state.metrics``.
        """
        damping = _resolve(self.damping, state.step)
        layer_grads = registry_lib.slice_layer_grads(grads, self.registry)
        eigen = self.compute_method == enums.ComputeMethod.EIGEN
        views = {}
        for name, helper in self.registry.layers.items():
            gview = (
                helpers_lib.matrix_view(helper, layer_grads[name]) if eigen
                else helper.grad_view(layer_grads[name])
            )
            # per-layer escalated damping bites here for the non-prediv
            # EIGEN method (its damping enters at precondition time); the
            # other methods bake it into update_inverses
            eff = (
                damping * state.health.damping_mult[name]
                if self.health is not None else damping
            )
            views[name] = (
                gview, self._precondition_one(state, name, gview, eff)
            )
        out = finish_precondition(self, state, views, metrics_out)
        return registry_lib.merge_layer_grads(grads, out, self.registry)

    # ------------------------------------------------------------------ step

    @tracing.scope('kfac.step')
    def step(
        self,
        state: KFACState,
        grads: Any,
        stats: capture_lib.CapturedStats | None,
        loss: jax.Array | None = None,
    ) -> tuple[KFACState, Any]:
        """One K-FAC step: maybe update factors/inverses, precondition grads.

        The factor/inverse cadence is evaluated with ``lax.cond`` on the
        traced step counter, so a single compiled program serves every step
        (reference control flow: kfac/base_preconditioner.py:310-382).
        Passing ``stats=None`` skips factor updates statically — use when the
        training loop compiles a separate no-capture variant for off-cadence
        steps (cheaper forward).

        ``loss``, when given, is recorded in the flight-recorder ring
        next to this step's scalars (the Trainer passes it on every
        path); without one the ring slot's loss is marked invalid.
        """
        if stats is not None:
            state = jax.lax.cond(
                state.step % _resolve(self.factor_update_steps, state.step) == 0,
                lambda s: self.update_factors(s, stats),
                lambda s: s,
                state,
            )
        if self._async_mode == 'sliced':
            state = async_sliced.dense_async_step(self, state)
        elif self._async_mode == 'host':
            state = async_host.dense_host_step(self, state)
        else:
            state = jax.lax.cond(
                state.step % _resolve(self.inv_update_steps, state.step) == 0,
                self.update_inverses,
                lambda s: s,
                state,
            )
        if self.metrics is not None and state.metrics is not None:
            scal: dict[str, jax.Array] = {}
            new_grads = self.precondition(state, grads, metrics_out=scal)
            ms = metrics_lib.update_scalars(state.metrics, scal)
            state = state._replace(
                metrics=metrics_lib.finalize(ms, self.metrics, state.step)
            )
        else:
            new_grads = self.precondition(state, grads)
        if self.flight is not None and state.flight is not None:
            # one dynamic-index slot write AFTER finalize, so the ring row
            # holds exactly what a collector drain would see for this step
            state = state._replace(flight=flight_lib.record(
                state.flight,
                state.step,
                state.metrics.scalars,
                loss=loss,
                grad_norm=flight_lib.global_grad_norm(grads),
            ))
        state = state._replace(step=state.step + 1)
        return state, new_grads

    # ------------------------------------------------------------- utilities

    def rematerialize(self, state: KFACState) -> KFACState:
        """Recompute decompositions from factors (e.g. after checkpoint load).

        The reference stores only factors and recomputes inverses on resume
        (kfac/base_preconditioner.py:296-308); checkpoints of
        :class:`KFACState` should save ``step``/``a``/``g`` and call this.

        Under async refresh the shadow is also reset (shadow slots are
        ephemeral): the first boundary after a mid-window restore finds an
        incomplete shadow and skips the swap — deterministic, no torn
        slot — and the following window refreshes normally.
        """
        state = self.update_inverses(state)
        if self._async_mode == 'sliced':
            state = state._replace(
                shadow=async_sliced.dense_shadow(self, state)
            )
        elif self._async_mode == 'host':
            async_host.reset_worker(self)
        return state

    def extract_factors(
        self, state: KFACState
    ) -> dict[str, dict[str, jax.Array]]:
        """Per-layer factors, the topology-independent checkpoint content
        (dense state is already layer-keyed; this mirrors the distributed
        engine's API so checkpoints move between engines/configs)."""
        return {
            name: {'a': state.a[self.a_leader(name)], 'g': state.g[name]}
            for name in state.g
        }

    def insert_factors(
        self,
        state: KFACState,
        factors: dict[str, dict[str, jax.Array]],
    ) -> KFACState:
        """Inverse of :meth:`extract_factors`; call :meth:`rematerialize`
        afterwards. A group's A is its leader's entry: the followers' own
        ``'a'`` entries (equal to it where this engine wrote them, each
        layer's own in a checkpoint from before the groups) are dropped."""
        new_a = dict(state.a)
        new_g = dict(state.g)
        for name, fg in factors.items():
            if name in new_a:
                new_a[name] = fg['a'].astype(self.factor_dtype)
            if name in new_g:
                new_g[name] = fg['g'].astype(self.factor_dtype)
        return state._replace(a=new_a, g=new_g)

    def describe(self) -> str:
        """Human-readable registration dump.

        The reference logs every registered module and the k-fac options at
        construction (kfac/preconditioner.py:264-268,300); here the dump is
        pull-based (pure construction, no logging side effects) — print it
        or hand it to your logger.
        """
        lines = [
            f'KFACPreconditioner: {len(self.registry.layers)} registered '
            f'layers, compute_method={self.compute_method.name}, '
            f'inverse_solver={self.inverse_solver}',
        ]
        if self.mask is not None:
            lines.append(
                '  mask: trainability mask active — frozen layers are '
                'unregistered (no factors, gradients pass through)'
            )
        if self.health is not None:
            hc = self.health
            lines.append(
                f'  health: skip_nonfinite={hc.skip_nonfinite} '
                f'quarantine_threshold={hc.quarantine_threshold} '
                f'damping_escalation={hc.damping_escalation} '
                f'degrade_after={hc.degrade_after}'
            )
        if self.metrics is not None:
            mc = self.metrics
            lines.append(
                f'  metrics: grad_norms={mc.grad_norms} '
                f'factor_bounds={mc.factor_bounds} staleness={mc.staleness}'
            )
        lines.extend('  ' + line for line in self.describe_patchless())
        for name, h in self.registry.layers.items():
            lines.append(
                f'  {name}: {type(h).__name__} '
                f'A={h.a_factor_shape[0]}x{h.a_factor_shape[0]} '
                f'G={h.g_factor_shape[0]}x{h.g_factor_shape[0]}'
                f'{" +bias" if h.has_bias else ""}'
            )
        lines.append(self.describe_a_groups())
        return '\n'.join(lines)

    def describe_patchless(self) -> list[str]:
        """The line of :meth:`describe` for :attr:`patchless_share`, or
        none where no convolution wider than 1 x 1 is registered."""
        if self.patchless_share is None:
            return []
        return [
            'convolution A factors with no patch rows: '
            f'{self.patchless_share:.1%} of the kernels larger than 1x1'
        ]

    def describe_a_groups(self) -> str:
        """The A groups as this engine stores them (each group once)."""
        reg = self.registry
        if reg.a_groups and not self.a_groups:
            return (
                f'A groups: {len(reg.a_members())} found, none stored (the '
                'async inverse refresh keeps every layer\'s own A factor)'
            )
        return reg.describe()

    def topology(self) -> dict[str, Any]:
        """Process/device topology snapshot, recorded (informationally)
        into checkpoint layout manifests so an elastic restore can report
        what it moved between; the dense engine has no mesh, so this is
        the world shape only."""
        return {
            'process_count': jax.process_count(),
            'device_count': jax.device_count(),
            'backend': jax.default_backend(),
        }

    def compile_watcher(
        self,
    ) -> 'compile_watch_lib.CompileWatch | None':
        """This engine's :class:`~kfac_tpu.observability.compile_watch.
        CompileWatch` (created lazily from ``compile_watch``; None when
        disabled). One watch per engine instance: the Trainer's step
        paths and :meth:`watched` entry points all count into it."""
        if self.compile_watch is None:
            return None
        watch = getattr(self, '_compile_watcher', None)
        if watch is None:
            watch = compile_watch_lib.CompileWatch(self.compile_watch)
            self._compile_watcher = watch
        return watch

    def watched(self, entry: str) -> Callable[..., Any]:
        """A jitted, watch-wrapped IR entry point (``'step'``,
        ``'update_factors'``, ...) — the observable way to drive the
        engine directly. Requires ``compile_watch`` enabled."""
        if entry not in self.IR_ENTRY_POINTS:
            raise ValueError(
                f'unknown entry {entry!r}; expected one of '
                f'{self.IR_ENTRY_POINTS}'
            )
        watch = self.compile_watcher()
        if watch is None:
            raise ValueError(
                'watched() requires compile_watch enabled on this config'
            )
        cache = getattr(self, '_watched_entries', None)
        if cache is None:
            cache = {}
            self._watched_entries = cache
        if entry not in cache:
            cache[entry] = watch.wrap(
                f'kfac.{entry}', jax.jit(getattr(self, entry))
            )
        return cache[entry]

    def compiled_memory_report(self) -> dict[str, dict[str, Any]]:
        """Latest XLA ``memory_analysis()`` snapshot per watched entry —
        the measured counterpart of :meth:`memory_usage`'s model-side
        estimate (see compile_watch.CompileWatch.memory_report). Empty
        when the watch is off, nothing compiled yet, or the backend
        doesn't report memory stats (graceful no-op)."""
        watch = self.compile_watcher()
        return {} if watch is None else watch.memory_report()

    def memory_usage(self, state: KFACState) -> dict[str, int]:
        """Approximate bytes held per category (reference:
        kfac/base_preconditioner.py:389-409)."""

        def nbytes(d: dict[str, jax.Array]) -> int:
            return int(sum(v.size * v.dtype.itemsize for v in d.values()))

        sizes = {
            'a_factors': nbytes(state.a),
            'g_factors': nbytes(state.g),
            'a_inverses': nbytes(state.qa) + nbytes(state.da) + nbytes(state.a_inv),
            'g_inverses': (
                nbytes(state.qg) + nbytes(state.dg)
                + nbytes(state.dgda) + nbytes(state.g_inv)
            ),
        }
        sizes['total'] = sum(sizes.values())
        return sizes
