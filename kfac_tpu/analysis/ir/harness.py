"""Trace harness: engine entry points → ClosedJaxprs on abstract inputs.

The KFL2xx rules analyze the *lowered program*, not source text, so the
harness must actually build engines. Everything runs on abstract values
(``jax.eval_shape`` + ``jax.make_jaxpr``): no FLOP is ever executed, no
device memory allocated — a trace costs 0.1–1.5 s of Python/tracing time
per engine config, which is why profiles exist:

- ``smoke``   — the single dense-transport d=64 eigen KAISA config;
  bounded wall-clock for ``make lint`` / tier-1 CI.
- ``default`` — smoke + the dense engine + a Newton–Schulz bucketed
  config + an async-host config, so every rule has real coverage.
- ``full``    — the strategy × method × transport matrix including
  host-eigh; used by the ``slow``-marked tests.

Entry points are *registered by the engines themselves* via the
``IR_ENTRY_POINTS`` class attribute (see ``kfac_tpu/preconditioner.py``
and ``kfac_tpu/parallel/kaisa.py``); the harness refuses to guess method
names so a renamed entry fails loudly here rather than silently dropping
coverage.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import re
from typing import Any, Callable

from kfac_tpu.analysis import drift

#: state leaves that ARE the factor/inverse math for dtype-taint purposes
FACTOR_FIELD_RE = re.compile(
    r'^\.(a|g|qa|qg|da|dg|dgda|a_inv|g_inv)(\[|\.|$)'
)

_PROFILES = ('smoke', 'default', 'full')
_active_profile = 'default'
_cache: dict[str, 'Suite'] = {}


def set_profile(profile: str) -> None:
    if profile not in _PROFILES:
        raise ValueError(
            f'unknown IR profile {profile!r}; expected one of {_PROFILES}'
        )
    global _active_profile
    _active_profile = profile


def active_profile() -> str:
    return _active_profile


@dataclasses.dataclass
class EngineTrace:
    """One traced entry point of one engine configuration."""

    config_name: str
    engine: str  # 'kaisa' | 'dense'
    entry: str  # method name, e.g. 'update_factors'
    jaxpr: Any  # ClosedJaxpr
    path: str  # repo-relative source path of the entry method
    line: int
    world: int
    step_path: bool
    tainted_invars: list[bool]
    callback_allowlist: frozenset[str]
    cfg: Any  # the KFACPreconditioner config
    comms: dict[str, Any] | None = None  # KAISA comms_report()
    expected_decomp_flops: float | None = None
    # sharding-contract pieces, attached to the 'step' trace of engines
    # that declare state_shardings():
    declared_shardings: Any = None
    abstract_args: tuple | None = None
    step_fn: Callable[..., Any] | None = None

    @property
    def display(self) -> str:
        return f'{self.config_name}:{self.entry}'


@dataclasses.dataclass
class Suite:
    profile: str
    traces: list[EngineTrace]
    #: (config name, entry, error message) for entry points that failed
    #: to trace — surfaced as findings by the rule layer
    errors: list[tuple[str, str, str]]


def _entry_location(engine_obj: Any, entry: str) -> tuple[str, int]:
    fn = inspect.unwrap(getattr(type(engine_obj), entry))
    path = inspect.getsourcefile(fn) or '<unknown>'
    try:
        _, line = inspect.getsourcelines(fn)
    except (OSError, TypeError):
        line = 1
    rel = os.path.relpath(path, drift.REPO_ROOT)
    return rel.replace(os.sep, '/'), line


def _callback_allowlist(cfg: Any) -> frozenset[str]:
    allow: set[str] = set()
    acfg = getattr(cfg, 'async_inverse', None)
    if acfg is not None and getattr(acfg, 'mode', None) == 'host':
        allow.add('io_callback')
    if getattr(cfg, 'eigh_impl', 'xla') in ('host', 'eig_host'):
        allow.add('pure_callback')
    return frozenset(allow)


def _taint_mask(args: tuple, factor_arg: int, stat_args: tuple[int, ...]):
    """Boolean mask over ``tree_leaves(args)``: True for leaves that feed
    factor/inverse math (factor state fields and raw statistics)."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(args)
    mask = []
    for path, _leaf in leaves:
        key = jax.tree_util.keystr(path)
        # keystr of a tuple arg starts '[i]'; strip the arg index
        m = re.match(r'^\[(\d+)\]', key)
        arg_idx = int(m.group(1)) if m else -1
        rest = key[m.end():] if m else key
        if arg_idx in stat_args:
            mask.append(True)
        elif arg_idx == factor_arg:
            mask.append(bool(FACTOR_FIELD_RE.match(rest)))
        else:
            mask.append(False)
    return mask


@dataclasses.dataclass(frozen=True)
class _ConfigSpec:
    name: str
    engine: str  # 'kaisa' | 'dense'
    hidden: int
    frac: float | None  # grad_worker_fraction; None for the dense engine
    kwargs: dict[str, Any]


def _specs(profile: str, world: int) -> list[_ConfigSpec]:
    import kfac_tpu

    bucketed = dict(
        allreduce_method=kfac_tpu.AllreduceMethod.ALLREDUCE_BUCKETED,
        bucket_granularity=8,
    )
    ns = dict(
        compute_method=kfac_tpu.ComputeMethod.INVERSE,
        inverse_solver='newton_schulz',
        newton_schulz_iters=6,
    )
    smoke = [
        _ConfigSpec('kaisa-eigen-dense-d64-f1.0', 'kaisa', 64, 1.0, {}),
    ]
    if profile == 'smoke':
        return smoke
    default = smoke + [
        _ConfigSpec('dense-eigen', 'dense', 16, None, {}),
        _ConfigSpec('kaisa-ns-bucketed-f0.5', 'kaisa', 16, 0.5,
                    {**ns, **bucketed}),
        _ConfigSpec('kaisa-eigen-async-host-f1.0', 'kaisa', 16, 1.0,
                    dict(async_inverse='host')),
    ]
    if profile == 'default':
        return _feasible(default, world)
    full = default + [
        _ConfigSpec('kaisa-eigen-dense-f0.5', 'kaisa', 16, 0.5, {}),
        _ConfigSpec('kaisa-eigen-dense-f0.125', 'kaisa', 16, 0.125, {}),
        _ConfigSpec('kaisa-ns-dense-f0.125', 'kaisa', 16, 0.125, ns),
        # COMM-OPT with explicit inverses: precondition builds no stack
        _ConfigSpec('kaisa-ns-dense-f1.0', 'kaisa', 16, 1.0, ns),
        _ConfigSpec('kaisa-eigen-prediv-f0.5', 'kaisa', 16, 0.5,
                    dict(prediv_eigenvalues=True)),
        _ConfigSpec('dense-eigh-host', 'dense', 16, None,
                    dict(eigh_impl='host')),
    ]
    return _feasible(full, world)


def _feasible(specs: list[_ConfigSpec], world: int) -> list[_ConfigSpec]:
    """Drop fractions the device count cannot host (frac·world ≥ 1)."""
    return [
        s for s in specs
        if s.frac is None or s.frac * world >= 1.0
    ]


_ENTRY_TAINT = {
    # entry -> (index of the state arg, indices of raw-statistics args)
    'update_factors': (0, (1,)),
    'update_inverses': (0, ()),
    'precondition': (0, ()),
    'step': (0, (2,)),
}


def _trace_config(spec: _ConfigSpec, world: int) -> list[EngineTrace]:
    import jax

    import kfac_tpu
    from kfac_tpu.autotune import model as model_lib
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh
    from testing import models

    m = models.TinyModel(hidden=spec.hidden, out=4)
    x, y = models.regression_data(
        jax.random.PRNGKey(1), n=max(world, 1) * 4, dim=6
    )
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, damping=1e-3, **spec.kwargs
    )
    loss_fn = models.mse_loss(m)
    if spec.engine == 'kaisa':
        eng: Any = DistributedKFAC(
            config=cfg, mesh=kaisa_mesh(grad_worker_fraction=spec.frac)
        )
        comms = eng.comms_report()
        layout = model_lib.StaticLayout(cfg, world, spec.frac)
        decomp_flops = model_lib.decomp_flops(layout)
    else:
        eng, comms, decomp_flops = cfg, None, None

    state = jax.eval_shape(eng.init)
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(loss_fn)
    (_, _), grads, stats = jax.eval_shape(run, params, (x, y))

    entry_args: dict[str, tuple] = {
        'update_factors': (state, stats),
        'update_inverses': (state,),
        'precondition': (state, grads),
        'step': (state, grads, stats),
    }
    allow = _callback_allowlist(cfg)
    traces: list[EngineTrace] = []
    for entry in type(eng).IR_ENTRY_POINTS:
        args = entry_args[entry]
        fn = getattr(eng, entry)
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
        path, line = _entry_location(eng, entry)
        factor_arg, stat_args = _ENTRY_TAINT[entry]
        trace = EngineTrace(
            config_name=spec.name,
            engine=spec.engine,
            entry=entry,
            jaxpr=jaxpr,
            path=path,
            line=line,
            world=world,
            step_path=entry in type(eng).IR_STEP_PATH,
            tainted_invars=_taint_mask(args, factor_arg, stat_args),
            callback_allowlist=allow,
            cfg=cfg,
            comms=comms,
            expected_decomp_flops=(
                decomp_flops if entry == 'update_inverses' else None
            ),
        )
        if entry == 'step' and hasattr(eng, 'state_shardings'):
            trace.declared_shardings = eng.state_shardings()
            trace.abstract_args = args
            trace.step_fn = fn
        traces.append(trace)
    return traces


def build(profile: str | None = None) -> Suite:
    """Build (and memoize) the trace suite for ``profile``."""
    import jax

    profile = profile or _active_profile
    if profile in _cache:
        return _cache[profile]
    world = len(jax.devices())
    traces: list[EngineTrace] = []
    errors: list[tuple[str, str, str]] = []
    for spec in _specs(profile, world):
        try:
            traces.extend(_trace_config(spec, world))
        except Exception as exc:  # noqa: BLE001 — a rule must report, not crash
            errors.append((spec.name, '<config>', f'{type(exc).__name__}: {exc}'))
    _cache[profile] = Suite(profile=profile, traces=traces, errors=errors)
    return _cache[profile]


def clear_cache() -> None:
    _cache.clear()
