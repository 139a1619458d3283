"""Export a KFAC-Laplace posterior from a live engine state.

The artifact is a directory::

    <path>/POSTERIOR.json   # versioned schema doc (written LAST, atomic)
    <path>/arrays/          # orbax checkpoint: MAP params + per-layer
                            # eigenbases/eigenvalues (mode-dependent)

following the :class:`kfac_tpu.autotune.plan.TunedPlan` artifact
conventions: a fingerprint (:func:`kfac_tpu.autotune.plan
.plan_fingerprint`) guards against serving a posterior exported from a
different model/topology, the doc carries no timestamps (byte-stable
across re-exports of the same state), the JSON write is tmp+rename
atomic, and :func:`kfac_tpu.laplace.posterior.load_posterior` rejects
unknown/missing keys and schema-version mismatches up front. Because the
doc is written only after the arrays are durable, a POSTERIOR.json on
disk always describes a complete artifact — a crash mid-export leaves no
doc, and the load path reports the directory as not-a-posterior.

Factors come out of the engine through ``extract_factors`` (per-layer
true-dim form, layout-independent — the same migration surface
checkpoint.py uses), so the export works identically for the dense
:class:`kfac_tpu.KFACPreconditioner` and the stacked
:class:`kfac_tpu.parallel.DistributedKFAC`. Eigendecompositions run
host-side in float64: export is off the training path, and the small
symmetric eigh is exactly the op the TPU backend is worst at
(docs/ARCHITECTURE.md on the eigh pathology).
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Any

import jax
import numpy as np

from kfac_tpu.laplace import config as config_lib

POSTERIOR_SCHEMA_VERSION = 1

#: top-level POSTERIOR.json keys, in serialization order
POSTERIOR_KEYS = ('schema', 'fingerprint', 'config', 'layers', 'meta')

#: per-layer arrays each mode persists
MODE_ARRAYS = {
    'kron': ('qa', 'da', 'qg', 'dg'),
    'diag': ('da', 'dg'),
    'last_layer': ('qa', 'da', 'qg', 'dg'),
}


def posterior_schema_keys() -> tuple[str, ...]:
    """Every documented posterior-doc key: top-level plus ``config.*``
    (the KFL107 drift guard's source of truth for the schema half)."""
    return POSTERIOR_KEYS + tuple(
        f'config.{f.name}' for f in dataclasses.fields(config_lib.LaplaceConfig)
    )


def _eigh(factor: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host float64 eigendecomposition; eigenvalues clipped at zero (EMA'd
    covariances are PSD up to roundoff; a tiny negative eigenvalue would
    poison every ``1/sqrt(d + sqrt(p))`` downstream)."""
    sym = np.asarray(factor, np.float64)
    sym = (sym + sym.T) / 2.0
    d, q = np.linalg.eigh(sym)
    return q, np.clip(d, 0.0, None)


def _exportable_layers(registry: Any, cfg: config_lib.LaplaceConfig) -> list[str]:
    names = list(registry.layers)
    if not names:
        raise ValueError(
            'cannot export a Laplace posterior from an engine with no '
            'registered layers (did a trainability mask freeze everything?)'
        )
    if cfg.mode != 'last_layer':
        return names
    target = cfg.last_layer if cfg.last_layer is not None else names[-1]
    if target not in registry.layers:
        raise ValueError(
            f'LaplaceConfig.last_layer={target!r} is not a registered layer '
            f'(registered: {names})'
        )
    return [target]


def _refuse_unhealthy(state: Any) -> None:
    """Exporting quarantined curvature would bake known-bad factors into a
    served posterior."""
    health = getattr(state, 'health', None)
    if health is None:
        return
    flagged = {
        name: (int(jax.device_get(q)), int(jax.device_get(health.bad_inv[name])))
        for name, q in health.quarantined.items()
        if int(jax.device_get(q)) > 0
        or int(jax.device_get(health.bad_inv[name])) > 0
    }
    if flagged:
        raise ValueError(
            'cannot export a Laplace posterior while layers are numerically '
            f'quarantined (layer: (quarantined, bad_inv) = {flagged}): the '
            'posterior would be built from factors the health sentinel has '
            'flagged as unusable. Train past the quarantine (counters reset '
            'on the first healthy update) and re-export.'
        )


def _helper_doc(helper: Any) -> dict[str, Any]:
    """JSON-safe constructor record: enough to rebuild the helper at load
    time without the model (class name + dataclass fields, dtype by name)."""
    fields = dataclasses.asdict(helper)
    fields['factor_dtype'] = np.dtype(fields['factor_dtype']).name
    return {'kind': type(helper).__name__, 'fields': fields}


def export_posterior(
    engine: Any,
    state: Any,
    params: Any,
    path: str | os.PathLike[str],
    config: config_lib.LaplaceConfig | None = None,
    overwrite: bool = False,
) -> dict[str, Any]:
    """Snapshot a serving posterior from ``(engine, state, params)``.

    Args:
        engine: :class:`kfac_tpu.KFACPreconditioner` or
            :class:`kfac_tpu.parallel.DistributedKFAC` (anything with
            ``registry`` + ``extract_factors``).
        state: the engine's state at export time. Refused while any
            layer is under numerical quarantine.
        params: the MAP parameter pytree (stored in the artifact; the
            posterior samples around it).
        path: artifact directory (created; refused if it already holds a
            POSTERIOR.json unless ``overwrite``).
        config: :class:`~kfac_tpu.laplace.LaplaceConfig` (default: kron).
        overwrite: replace an existing posterior at ``path``.

    Returns the POSTERIOR.json document (also written to disk).
    """
    import orbax.checkpoint as ocp

    from kfac_tpu.autotune import plan as plan_lib
    from kfac_tpu import checkpoint as checkpoint_lib

    cfg = config if config is not None else config_lib.LaplaceConfig()
    path = os.fspath(path)
    doc_path = os.path.join(path, 'POSTERIOR.json')
    if os.path.exists(doc_path) and not overwrite:
        raise ValueError(
            f'posterior artifact already exists at {path!r}; pass '
            'overwrite=True to replace it'
        )
    _refuse_unhealthy(state)
    registry = engine.registry
    names = _exportable_layers(registry, cfg)

    factors = jax.device_get(engine.extract_factors(state))
    arrays: dict[str, dict[str, np.ndarray]] = {}
    layers_doc: dict[str, Any] = {}
    for name in names:
        a = np.asarray(factors[name]['a'])
        g = np.asarray(factors[name]['g'])
        if cfg.mode == 'diag':
            entry = {
                'da': np.ascontiguousarray(np.diagonal(a)).astype(np.float32),
                'dg': np.ascontiguousarray(np.diagonal(g)).astype(np.float32),
            }
        else:
            qa, da = _eigh(a)
            qg, dg = _eigh(g)
            entry = {
                'qa': qa.astype(np.float32),
                'da': da.astype(np.float32),
                'qg': qg.astype(np.float32),
                'dg': dg.astype(np.float32),
            }
        arrays[name] = entry
        layers_doc[name] = {
            **_helper_doc(registry.layers[name]),
            'param_path': list(registry.param_paths[name]),
            'arrays': list(MODE_ARRAYS[cfg.mode]),
        }

    step = state['step'] if isinstance(state, dict) else state.step
    doc = {
        'schema': POSTERIOR_SCHEMA_VERSION,
        'fingerprint': plan_lib.plan_fingerprint(registry),
        'config': dataclasses.asdict(cfg),
        'layers': layers_doc,
        'meta': {
            'step': int(jax.device_get(step)),
            'layout_manifest': checkpoint_lib.layout_manifest(engine),
        },
    }

    os.makedirs(path, exist_ok=True)
    arrays_path = os.path.join(os.path.abspath(path), 'arrays')
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(
        arrays_path,
        {'params': jax.device_get(params), 'layers': arrays},
        force=True,
    )
    ckptr.wait_until_finished()
    # doc last, atomically: its presence certifies a complete artifact
    fd, tmp = tempfile.mkstemp(dir=path, suffix='.tmp')
    try:
        with os.fdopen(fd, 'w') as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write('\n')
        os.replace(tmp, doc_path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return doc
