"""TunedPlan: the persisted artifact of a layout-autotuner search.

A plan is a small versioned JSON document carrying (1) the winning KAISA
layout knobs, (2) the model/measured cost table the search evaluated, and
(3) a topology+model-shape fingerprint that guards against silently
applying a plan tuned for a different pod or a different network. The
engine/Trainer entry point is ``auto_layout=``: the plan applies only
when the fingerprint matches this process; otherwise the explicit/default
configuration stands and a rate-limited
:class:`~kfac_tpu.warnings.LayoutPlanWarning` fires.

``tools/lint_plan_schema.py`` keeps :func:`plan_schema_keys` in sync with
the schema table in docs/AUTOTUNE.md.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Any

from kfac_tpu import enums
from kfac_tpu import warnings as warnings_lib

PLAN_SCHEMA_VERSION = 1

# Top-level JSON document keys, in serialization order.
PLAN_KEYS = ('schema', 'fingerprint', 'knobs', 'cost_table', 'winner', 'meta')

# The layout knobs a plan carries — exactly the KFACPreconditioner fields
# (plus the mesh aspect ratio) the search enumerates. apply_knobs() is
# the ONE place these are written onto a config.
KNOB_KEYS = (
    'grad_worker_fraction',
    'strategy',
    'bucket_granularity',
    'allreduce_method',
    'allreduce_bucket_cap_mb',
    'factor_update_steps',
    'inv_update_steps',
    'colocate_factors',
    'async_inverse',
    'topology',
    'serving',
)

# Knobs plans carried (at these names) until the options they set were
# removed: ignored at their off values, refused when set.
REMOVED_KNOBS = ('stat_compression', 'offload')

# Knobs added after schema-v1 plans shipped: absent in older documents,
# filled with these defaults on load so old plans keep applying cleanly.
OPTIONAL_KNOBS: dict[str, Any] = {
    'async_inverse': None,
    # PR-14 3D planner output: {dp, tp, pp, virtual_chunks, microbatches,
    # schedule} or None for pure-KAISA plans. Mesh-side like strategy /
    # grad_worker_fraction — resolve_auto_layout consumes it, apply_knobs
    # leaves the config untouched.
    'topology': None,
    # PR-20 serving-tier cost summary (model.price_serving output):
    # {bucket_granularity, max_batch, n_samples, escalated_n_samples,
    # buckets: [{bucket, mc_flops, cf_flops, ...}, ...],
    # hbm_bytes_per_replica} or None when the plan wasn't priced for
    # inference. Consumed by the serving tier (docs/SERVING.md);
    # apply_knobs leaves the training config untouched.
    'serving': None,
}


def plan_schema_keys() -> tuple[str, ...]:
    """Every documented plan key: top-level plus ``knobs.*`` (the drift
    guard's source of truth)."""
    return PLAN_KEYS + tuple(f'knobs.{k}' for k in KNOB_KEYS)


# Topology fields reused from the flight recorder's fingerprint.json
# (observability/flight_recorder.py:fingerprint). Version and
# process_index fields are deliberately dropped: a jax upgrade or a
# different host rank doesn't change which layout is fastest.
_FLIGHT_FP_KEYS = (
    'backend',
    'device_count',
    'local_device_count',
    'device_kinds',
    'process_count',
)


def plan_fingerprint(registry: Any) -> dict[str, Any]:
    """Topology + model-shape fingerprint a plan is valid for.

    Topology comes from the flight-recorder fingerprint fields; the model
    shape is the per-layer (A dim, G dim) map — the only model property
    the layout cost depends on.
    """
    from kfac_tpu.observability import flight_recorder as flight_lib

    fp = flight_lib.fingerprint()
    out: dict[str, Any] = {k: fp[k] for k in _FLIGHT_FP_KEYS}
    out['layers'] = {
        name: [h.a_factor_shape[0], h.g_factor_shape[0]]
        for name, h in registry.layers.items()
    }
    return out


def fingerprint_matches(a: dict[str, Any], b: dict[str, Any]) -> bool:
    """Exact fingerprint equality, after JSON normalization (a loaded
    plan's tuples became lists)."""
    return json.loads(json.dumps(a)) == json.loads(json.dumps(b))


def fingerprint_diff(a: dict[str, Any], b: dict[str, Any]) -> list[str]:
    """Keys whose values differ between two fingerprints, in EITHER
    direction (sorted), after JSON normalization.

    A one-sided scan would miss keys present in only one fingerprint —
    e.g. a plan from a newer schema carrying a field this process
    doesn't produce — and report an empty diff for a real mismatch.
    """
    na = json.loads(json.dumps(a))
    nb = json.loads(json.dumps(b))
    return sorted(k for k in set(na) | set(nb) if na.get(k) != nb.get(k))


@dataclasses.dataclass
class TunedPlan:
    """Versioned, serializable result of a layout search.

    Attributes:
        fingerprint: :func:`plan_fingerprint` of the tuning run.
        knobs: winning :data:`KNOB_KEYS` values.
        cost_table: one row per evaluated candidate (knobs + predicted
            cost terms + ``measured_step_s`` when timed + feasibility).
        winner: summary of the chosen row (predicted/measured seconds,
            how it was picked).
        meta: search provenance (world size, grid bounds, trial counts).
        schema: :data:`PLAN_SCHEMA_VERSION` at write time.
    """

    fingerprint: dict[str, Any]
    knobs: dict[str, Any]
    cost_table: list[dict[str, Any]]
    winner: dict[str, Any]
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)
    schema: int = PLAN_SCHEMA_VERSION

    def to_json(self) -> dict[str, Any]:
        return {k: getattr(self, k) for k in PLAN_KEYS}

    @classmethod
    def from_json(cls, doc: dict[str, Any]) -> 'TunedPlan':
        missing = [k for k in PLAN_KEYS if k not in doc]
        unknown = [k for k in doc if k not in PLAN_KEYS]
        if missing or unknown:
            raise ValueError(
                f'malformed TunedPlan document: missing keys {missing}, '
                f'unknown keys {unknown}'
            )
        if doc['schema'] != PLAN_SCHEMA_VERSION:
            raise ValueError(
                f'TunedPlan schema {doc["schema"]} is not the supported '
                f'version {PLAN_SCHEMA_VERSION}'
            )
        knob_missing = [
            k for k in KNOB_KEYS
            if k not in doc['knobs'] and k not in OPTIONAL_KNOBS
        ]
        if knob_missing:
            raise ValueError(f'TunedPlan knobs missing {knob_missing}')
        # every plan written while these were knobs carries them; one that
        # turned either on asks for a layout no engine builds any more
        knob_removed = [k for k in REMOVED_KNOBS if doc['knobs'].get(k)]
        if knob_removed:
            raise ValueError(
                f'TunedPlan knobs set {knob_removed}, which are no longer '
                'supported: tune again'
            )
        fields = {k: doc[k] for k in PLAN_KEYS}
        fields['knobs'] = {
            **OPTIONAL_KNOBS,
            **{
                k: v for k, v in fields['knobs'].items()
                if k not in REMOVED_KNOBS
            },
        }
        return cls(**fields)

    def save(self, path: str | os.PathLike[str]) -> None:
        """Atomic write (tmp + rename), stable key order."""
        path = os.fspath(path)
        parent = os.path.dirname(path) or '.'
        os.makedirs(parent, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=parent, suffix='.tmp')
        try:
            with os.fdopen(fd, 'w') as f:
                json.dump(self.to_json(), f, indent=2, sort_keys=True)
                f.write('\n')
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise

    @classmethod
    def load(cls, path: str | os.PathLike[str]) -> 'TunedPlan':
        with open(os.fspath(path)) as f:
            return cls.from_json(json.load(f))


def as_plan(obj: Any) -> TunedPlan:
    """Coerce an ``auto_layout=`` argument: TunedPlan, JSON dict, or a
    path to a plan file."""
    if isinstance(obj, TunedPlan):
        return obj
    if isinstance(obj, dict):
        return TunedPlan.from_json(obj)
    if isinstance(obj, (str, os.PathLike)):
        return TunedPlan.load(obj)
    raise TypeError(
        f'auto_layout must be a TunedPlan, a plan JSON dict, or a path; '
        f'got {type(obj).__name__}'
    )


def apply_knobs(config: Any, knobs: dict[str, Any]) -> Any:
    """A copy of ``config`` with a plan's layout knobs applied.

    ``strategy``/``grad_worker_fraction`` live in the mesh shape, not the
    config — :func:`resolve_auto_layout` handles those.
    """
    return dataclasses.replace(
        config,
        bucket_granularity=int(knobs['bucket_granularity']),
        allreduce_method=enums.AllreduceMethod[knobs['allreduce_method']],
        allreduce_bucket_cap_mb=(
            None
            if knobs['allreduce_bucket_cap_mb'] is None
            else float(knobs['allreduce_bucket_cap_mb'])
        ),
        factor_update_steps=int(knobs['factor_update_steps']),
        inv_update_steps=int(knobs['inv_update_steps']),
        colocate_factors=bool(knobs['colocate_factors']),
        # normalized by the config's __post_init__ (mode string or None)
        async_inverse=knobs.get('async_inverse'),
    )


def resolve_auto_layout(
    config: Any,
    mesh: Any,
    auto_layout: Any,
) -> tuple[Any, Any, bool]:
    """Apply a tuned plan to an engine's (config, mesh) if it is valid here.

    Returns ``(config, mesh, applied)``. On a fingerprint mismatch, or a
    caller-provided mesh whose gradient-worker count contradicts the
    plan, the inputs come back untouched (``applied=False``) after a
    rate-limited :class:`~kfac_tpu.warnings.LayoutPlanWarning` — training
    proceeds on the explicit/default layout rather than dying on a stale
    artifact.
    """
    from kfac_tpu import assignment as assignment_lib
    from kfac_tpu.parallel import mesh as mesh_lib

    plan = as_plan(auto_layout)
    current = plan_fingerprint(config.registry)
    if not fingerprint_matches(plan.fingerprint, current):
        diff = fingerprint_diff(plan.fingerprint, current)
        warnings_lib.warn_layout_event(
            'fingerprint-mismatch',
            f'plan was tuned for a different {"/".join(diff) or "setup"}',
        )
        return config, mesh, False
    topo = plan.knobs.get('topology')
    if topo:
        import jax

        pp = int(topo.get('pp', 1))
        tp = int(topo.get('tp', 1))
        world = (
            len(mesh.devices.reshape(-1)) if mesh is not None
            else jax.device_count()
        )
        if pp < 1 or tp < 1 or world % (pp * tp) != 0:
            # a topology plan that doesn't factor the live device count
            # was tuned for a different pod — same failure class as a
            # fingerprint mismatch, same non-fatal outcome
            warnings_lib.warn_layout_event(
                'fingerprint-mismatch',
                f'plan topology pp={pp} tp={tp} does not divide the '
                f'{world}-device world',
            )
            return config, mesh, False
        if mesh is not None:
            have_pp = dict(mesh.shape).get(mesh_lib.PIPE_AXIS, 1)
            if have_pp != pp:
                warnings_lib.warn_layout_event(
                    'mesh-mismatch',
                    f'given mesh has {have_pp} pipeline stages, plan '
                    f'wants {pp}',
                )
                return config, mesh, False
        else:
            mesh = mesh_lib.pipeline_mesh(n_stages=pp, model=tp)
        return apply_knobs(config, plan.knobs), mesh, True
    frac = float(plan.knobs['grad_worker_fraction'])
    if mesh is not None:
        world = mesh_lib.grad_workers(mesh) * mesh_lib.n_cols(mesh)
        want = assignment_lib.grad_worker_count(world, frac)
        if mesh_lib.grad_workers(mesh) != want:
            warnings_lib.warn_layout_event(
                'mesh-mismatch',
                f'given mesh has {mesh_lib.grad_workers(mesh)} gradient '
                f'workers, plan wants {want}',
            )
            return config, mesh, False
    else:
        mesh = mesh_lib.kaisa_mesh(grad_worker_fraction=frac)
    return apply_knobs(config, plan.knobs), mesh, True
