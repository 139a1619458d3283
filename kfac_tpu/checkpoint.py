"""Checkpoint / resume for K-FAC state (orbax-backed).

Reference semantics (kfac/base_preconditioner.py:215-308): persist only the
step counter and the running factors A/G; eigendecompositions are
*recomputed* on load — they are derived state, and factors are smaller and
dtype-stable. Works for both the dense :class:`kfac_tpu.KFACState` and the
stacked :class:`kfac_tpu.parallel.DistKFACState`; with sharded arrays orbax
writes one shard per host (the TPU equivalent of the reference's
per-inv-worker sharded factor directory, kfac/gpt_neox/preconditioner.py:
427-447).
"""

from __future__ import annotations

import dataclasses
import json
import os
import warnings as _warnings
from typing import Any

import jax

try:
    import orbax.checkpoint as ocp

    _HAS_ORBAX = True
except Exception:  # pragma: no cover - orbax is in the image; belt+braces
    _HAS_ORBAX = False


def layout_manifest(engine: Any) -> dict[str, Any]:
    """JSON-serializable description of an engine's durable-state layout.

    The stacked KAISA layout depends on config (``bucket_granularity``,
    ``colocate_factors``) AND platform defaults, so two runs of "the same"
    training script can produce incompatible :func:`save` payloads — the
    reference never hits this because its ``state_dict`` is always
    layer-keyed (kfac/base_preconditioner.py:215-265). The manifest makes
    the layout explicit so :func:`restore` can diagnose a mismatch and
    migrate through per-layer factors instead of surfacing an orbax shape
    error.
    """
    man: dict[str, Any] = {'format': 1, 'engine': type(engine).__name__}
    cfg = getattr(engine, 'config', engine)
    cm = getattr(cfg, 'compute_method', None)
    man['compute_method'] = getattr(cm, 'name', str(cm))
    # informational (NOT a layout key: a topology change alone never forces
    # factor migration — orbax reshards same-layout payloads through the
    # restore template's shardings); recorded so an elastic restore can
    # report what it moved between
    topo = getattr(engine, 'topology', None)
    if callable(topo):
        man['topology'] = topo()
    if hasattr(engine, 'a_store'):  # stacked KAISA engine
        man['bucket_granularity'] = int(cfg.bucket_granularity)
        man['colocate_factors'] = bool(cfg.colocate_factors)
        man['a_store'] = [_bucket_entry(sb) for sb in engine.a_store]
        man['g_store'] = [_bucket_entry(sb) for sb in engine.g_store]
    if hasattr(engine, 'n_stages'):  # pipeline engine
        man['n_stages'] = int(engine.n_stages)
    # the A groups the engine stores one A factor for (member -> leader):
    # the payload holds a group's A under its leader alone. Absent where
    # there is none, so that a layout without groups reads as it did.
    groups = getattr(engine, 'a_groups', None)
    if groups:
        man['a_groups'] = dict(groups)
    return man


def _bucket_entry(sb: Any) -> dict[str, Any]:
    return {
        'key': str(sb.key),
        'layers': list(sb.layers),
        'd': int(sb.d),
        'padded': int(sb.padded),
        'dims': [int(d) for d in sb.dims],
    }


# Manifest keys that determine the shape/keying of the durable payload
# (compute_method does not: only step + a + g are durable).
_LAYOUT_KEYS = (
    'engine', 'bucket_granularity', 'colocate_factors', 'a_store',
    'g_store', 'n_stages', 'a_groups',
)


def _layout_view(man: dict[str, Any]) -> dict[str, Any]:
    return {k: man[k] for k in _LAYOUT_KEYS if k in man}


def _manifest_path(path: str) -> str | None:
    """Local sidecar path for the layout manifest, or ``None`` for remote
    URIs (``gs://``, ``s3://``, ...): ``os.path.abspath`` would mangle the
    scheme and the builtin ``open`` cannot write there — orbax handles the
    checkpoint itself through its own path layer, but the sidecar is
    plain-file IO. Remote saves skip the manifest with a warning (restore
    then runs manifest-less: same-layout restores work, cross-layout
    migration is unavailable)."""
    p = str(path)
    if '://' in p:
        return None
    return os.path.abspath(p) + '.manifest.json'


def _factors_from_saved(
    kfac_payload: dict[str, Any], saved_man: dict[str, Any]
) -> dict[str, dict[str, Any]] | None:
    """Reconstruct per-layer true-dim factors from a raw :func:`save`
    payload using the manifest it was written with.

    Returns None when the saved layout is not migratable this way
    (pipeline states carry a stage axis whose re-partition is unsupported,
    as in the reference).
    """
    if 'n_stages' in saved_man:
        return None
    out: dict[str, dict[str, Any]] = {}
    if 'a_store' in saved_man:  # stacked KAISA payload: slice slots out
        for side in ('a', 'g'):
            for entry in saved_man[f'{side}_store']:
                stack = kfac_payload[side][entry['key']]
                for i, name in enumerate(entry['layers']):
                    d = entry['dims'][i]
                    out.setdefault(name, {})[side] = stack[i, :d, :d]
    else:
        # dense payload: already layer-keyed
        for name, a in kfac_payload['a'].items():
            out.setdefault(name, {})['a'] = a
        for name, g in kfac_payload['g'].items():
            out.setdefault(name, {})['g'] = g
    # a follower of an A group was saved without an A of its own: its
    # leader's is it
    for name, leader in saved_man.get('a_groups', {}).items():
        if name in out and 'a' not in out[name] and leader in out:
            out[name]['a'] = out[leader]['a']
    return out


def durable_state(state: Any) -> dict[str, Any]:
    """The persistent slice of a K-FAC state: step + factors, plus the
    numerical-health counters when the sentinel is enabled.

    Works for the NamedTuple states of the dense/KAISA engines and the
    dict state of :class:`kfac_tpu.parallel.PipelineKFAC`. The health
    counters are stored as a plain field dict of per-layer scalars —
    layout-independent, so they also survive cross-layout migration.
    """
    if isinstance(state, dict):
        return {'step': state['step'], 'a': state['a'], 'g': state['g']}
    out = {'step': state.step, 'a': state.a, 'g': state.g}
    health = getattr(state, 'health', None)
    if health is not None:
        out['health'] = health._asdict()
    return out


def _with_durable(state: Any, loaded: dict[str, Any]) -> Any:
    if isinstance(state, dict):
        return {
            **state,
            'step': loaded['step'], 'a': loaded['a'], 'g': loaded['g'],
        }
    state = state._replace(
        step=loaded['step'], a=loaded['a'], g=loaded['g']
    )
    if 'health' in loaded and getattr(state, 'health', None) is not None:
        state = state._replace(health=_health_from_saved(loaded['health']))
    return state


def _refuse_comp_ef(path: str, saved_kfac: Any) -> None:
    """A checkpoint written under the removed ``stat_compression`` option
    holds error-feedback residuals (``kfac/comp_ef``): deferred factor mass
    this engine has nowhere to put. Refuse it by name; restoring the
    factors and dropping the residuals would bias the next EMA silently."""
    if 'comp_ef' in saved_kfac:
        raise ValueError(
            f'checkpoint at {path!r} carries error-feedback residuals of '
            'a compressed stat transport (kfac/comp_ef), which are no '
            'longer supported: no engine of this version can restore them, '
            'and the factors are not restored without them. Restore it '
            'with the version that wrote it and save again with '
            'stat_compression off.'
        )


def _health_from_saved(saved: Any) -> Any:
    """Rebuild a :class:`kfac_tpu.health.HealthState` from its saved field
    dict (or pass one through that orbax already restored structured)."""
    from kfac_tpu import health as health_lib

    if isinstance(saved, health_lib.HealthState):
        return saved
    return health_lib.HealthState(
        skipped_steps=saved['skipped_steps'],
        damping_mult=dict(saved['damping_mult']),
        quarantined=dict(saved['quarantined']),
        bad_inv=dict(saved['bad_inv']),
        quarantine_events=dict(saved['quarantine_events']),
    )


def _validate_restored_factors(path: str, engine: Any, state: Any) -> None:
    """Reject corrupt checkpoints up front with a layer-named error.

    A factor that went to disk with inf/NaN (e.g. saved before the health
    sentinel existed, or written by a run that diverged) would otherwise
    surface steps later as an unexplained eigh failure; a wrong per-layer
    shape (model width changed between save and restore) would silently
    precondition with garbage. Both checks run on the per-layer true-dim
    view, so the error names the layer, not a stacked bucket slot.
    """
    import numpy as np

    if not hasattr(engine, 'extract_factors'):
        return
    # pipeline states stack a stage axis onto the per-layer factors; only
    # the finiteness check applies there
    check_shapes = not isinstance(state, dict)
    reg = getattr(engine, 'registry', None)
    for name, fg in engine.extract_factors(state).items():
        helper = reg.layers.get(name) if reg is not None else None
        for side in ('a', 'g'):
            arr = np.asarray(jax.device_get(fg[side]))
            if not np.isfinite(arr).all():
                bad = int(arr.size - np.isfinite(arr).sum())
                raise ValueError(
                    f'checkpoint at {path!r}: restored {side.upper()} '
                    f'factor for layer {name!r} contains {bad} non-finite '
                    'values — the checkpoint is corrupt (saved from a '
                    'diverged run?); restore a different one or reinitialize '
                    'the preconditioner state.'
                )
            if helper is not None and check_shapes:
                exp = tuple(
                    helper.a_factor_shape if side == 'a'
                    else helper.g_factor_shape
                )
                if tuple(arr.shape) != exp:
                    raise ValueError(
                        f'checkpoint at {path!r}: restored {side.upper()} '
                        f'factor for layer {name!r} has shape '
                        f'{tuple(arr.shape)} but the engine expects {exp} — '
                        'the model architecture changed between save and '
                        'restore.'
                    )


def save(
    path: str,
    state: Any,
    extra: dict[str, Any] | None = None,
    engine: Any | None = None,
    wait: bool = True,
    overwrite: bool = False,
) -> Any:
    """Write the durable K-FAC state (plus optional extra trees, e.g. model
    params / optax state) to ``path``.

    Pass ``engine`` to also write a layout manifest sidecar
    (``<path>.manifest.json``): :func:`restore` uses it to detect a layout
    mismatch up front and to MIGRATE the factors into a differently-laid-out
    engine (other ``bucket_granularity``/``colocate_factors``, dense vs
    distributed) instead of failing on an orbax shape error.

    ``wait=False`` returns immediately after orbax snapshots the arrays
    and finishes the write on background threads — training continues
    while the checkpoint streams out (the pod-scale pattern; the
    reference's torch.save always blocks). Returns a handle: call its
    ``.wait_until_finished()`` before relying on the files, and before
    starting another save to the same path. The manifest sidecar is
    written only once the checkpoint is DURABLE (at wait time), so a
    manifest's presence always implies a committed checkpoint — a crash
    mid-async-save leaves neither.

    ``overwrite`` controls the policy for a pre-existing ``path``: the
    default refuses up front (orbax's ``StandardCheckpointer`` would fail
    anyway, with a less actionable message), ``overwrite=True`` replaces
    the old checkpoint. Production rotations should prefer fresh
    step-numbered directories (:class:`kfac_tpu.resilience
    .CheckpointManager`) so a crashed overwrite can never destroy the
    only good checkpoint.
    """
    if not _HAS_ORBAX:
        raise RuntimeError('orbax-checkpoint is not available')
    if not overwrite and '://' not in str(path) and os.path.exists(path):
        raise ValueError(
            f'checkpoint path {path!r} already exists; pass '
            'overwrite=True to replace it, or save each step to a fresh '
            'step-numbered directory (kfac_tpu.resilience.CheckpointManager '
            'manages such a rotation with an atomic LATEST pointer)'
        )
    payload = {'kfac': durable_state(state)}
    if extra:
        payload.update(extra)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, payload, force=overwrite)
    # remove any STALE sidecar from an earlier save at this path
    # immediately (before the async return): whatever happens next — crash
    # pre-commit (no checkpoint, no manifest) or crash between orbax's
    # commit and the caller's wait (new checkpoint, no manifest: restore
    # runs manifest-less) — a manifest on disk can only describe THIS save
    mpath0 = _manifest_path(path)
    if jax.process_index() == 0 and mpath0 is not None and (
        os.path.exists(mpath0)
    ):
        os.remove(mpath0)

    def _finalize_manifest() -> None:
        if jax.process_index() != 0:
            return
        mpath = _manifest_path(path)
        if engine is not None:
            if mpath is None:
                _warnings.warn(
                    f'checkpoint path {path!r} is a remote URI: the layout '
                    f'manifest sidecar is plain-file IO and is skipped — '
                    f'cross-layout factor migration will be unavailable '
                    f'for this checkpoint',
                    stacklevel=3,
                )
            else:
                with open(mpath, 'w') as f:
                    json.dump(layout_manifest(engine), f, indent=1)

    if wait:
        ckptr.wait_until_finished()
        _finalize_manifest()
        return ckptr
    return _AsyncSaveHandle(ckptr, _finalize_manifest)


class _AsyncSaveHandle:
    """Returned by ``save(..., wait=False)``: finishing the write also
    finalizes the manifest sidecar, preserving the invariant that a
    manifest on disk implies a durable checkpoint.

    Usable as a context manager (``with save(..., wait=False):`` waits on
    exit). Dropping the handle without ``wait_until_finished()`` warns: the
    orbax background threads may still commit the checkpoint, but the
    manifest is never finalized — a durable checkpoint that silently lost
    its cross-layout migration metadata.
    """

    def __init__(self, ckptr, finalize):
        self._ckptr = ckptr
        self._finalize = finalize
        self._done = False

    def wait_until_finished(self) -> None:
        self._ckptr.wait_until_finished()
        if not self._done:
            self._done = True
            self._finalize()

    def __enter__(self) -> '_AsyncSaveHandle':
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.wait_until_finished()

    def __del__(self) -> None:
        if getattr(self, '_done', True):
            return
        try:  # pragma: no cover - interpreter-shutdown ordering
            _warnings.warn(
                'async checkpoint save handle dropped without '
                'wait_until_finished(): the checkpoint may commit in the '
                'background but its layout manifest is never written '
                '(cross-layout migration will be unavailable); hold the '
                'handle and wait on it, or use it as a context manager',
                ResourceWarning,
                stacklevel=2,
            )
        except Exception:
            pass


def restore(
    path: str,
    engine: Any,
    extra_template: dict[str, Any] | None = None,
) -> tuple[Any, dict[str, Any]]:
    """Load factors into a fresh state from ``engine.init()`` and recompute
    decompositions via ``engine.rematerialize``.

    ``engine`` is a :class:`kfac_tpu.KFACPreconditioner` or
    :class:`kfac_tpu.parallel.DistributedKFAC`. Returns ``(state, extra)``.

    If the checkpoint carries a layout manifest (written by
    ``save(..., engine=engine)``) and the layout differs from ``engine``'s
    — other ``bucket_granularity``/``colocate_factors`` (including the
    platform-resolved defaults changing across hosts), or a dense vs
    distributed engine swap — the factors are MIGRATED automatically
    through their per-layer true-dim form (with a warning). Only
    stage-stacked pipeline states refuse cross-layout moves (a stage
    re-partition is unsupported, as in the reference).
    """
    if not _HAS_ORBAX:
        raise RuntimeError('orbax-checkpoint is not available')
    template_state = engine.init()
    template = {'kfac': durable_state(template_state)}
    if extra_template:
        template.update(extra_template)
    ckptr = ocp.StandardCheckpointer()

    saved_man = None
    mpath = _manifest_path(path)
    if mpath is not None and os.path.exists(mpath):
        with open(mpath) as f:
            saved_man = json.load(f)
    elif mpath is not None and os.path.isdir(path):
        # the checkpoint committed but its sidecar never landed: either a
        # crash between orbax's commit and the manifest finalize (the
        # async-save window CheckpointManager's rotation tolerates) or a
        # save() without engine= — restore proceeds layout-exact either way
        from kfac_tpu.warnings import CheckpointResilienceWarning

        _warnings.warn(
            f'checkpoint at {path!r} has no layout-manifest sidecar '
            '(saved without engine=, or the writer died between the orbax '
            'commit and the manifest finalize): restoring manifest-less — '
            'cross-layout migration is unavailable for this checkpoint',
            CheckpointResilienceWarning,
            stacklevel=2,
        )
    if saved_man is not None:
        cur_man = layout_manifest(engine)
        if _layout_view(saved_man) != _layout_view(cur_man):
            return _migrate_restore(
                path, engine, template_state, saved_man, cur_man,
                extra_template, ckptr,
            )

    try:
        payload = ckptr.restore(path, target=template)
    except (ValueError, KeyError) as exc:
        payload = _retry_health_mismatch(
            ckptr, path, template, template_state, engine, exc
        )
    state = _with_durable(template_state, payload['kfac'])
    _validate_restored_factors(path, engine, state)
    loaded_health = (
        getattr(state, 'health', None)
        if not isinstance(state, dict)
        else None
    )
    state = engine.rematerialize(state)
    if loaded_health is not None:
        # rematerialize ticks the degradation counters from ITS verdicts on
        # the freshly recomputed decompositions; the checkpoint's counters
        # are the durable truth for a resumed run, so they win
        state = state._replace(health=loaded_health)
    extra = {k: v for k, v in payload.items() if k != 'kfac'}
    return state, extra


def _retry_health_mismatch(
    ckptr: Any,
    path: str,
    template: dict[str, Any],
    template_state: Any,
    engine: Any,
    exc: Exception,
) -> dict[str, Any]:
    """Structure-mismatch fallback: tolerate config-presence drift.

    A checkpoint written without health counters must restore into a
    health-enabled engine (counters start fresh), and one written WITH
    them must restore into a health-disabled engine (counters dropped) —
    toggling the sentinel between runs is configuration, not a layout
    change. A checkpoint that carries error-feedback residuals is refused
    by name (:func:`_refuse_comp_ef`). Anything else re-raises the layout
    diagnosis."""
    kfac_t = template['kfac']
    health_toggled = None
    if 'health' in kfac_t:
        health_toggled = {
            k: v for k, v in kfac_t.items() if k != 'health'
        }
    else:
        reg = getattr(engine, 'registry', None)
        if reg is not None and not isinstance(template_state, dict):
            from kfac_tpu import health as health_lib

            health_toggled = {
                **kfac_t,
                'health': health_lib.init_health(reg.names())._asdict(),
            }
    if health_toggled is not None:
        try:
            payload = ckptr.restore(
                path, target={**template, 'kfac': health_toggled}
            )
        except (ValueError, KeyError):
            pass
        else:
            # either health direction resolves to "no health in the loaded
            # payload": a sentinel-less checkpoint keeps init()'s fresh
            # counters; a sentinel-less engine drops the saved ones.
            payload['kfac'].pop('health', None)
            return payload
    _refuse_comp_ef(path, _saved_tree_metadata(path).get('kfac', {}))
    raise ValueError(
        f'checkpoint at {path!r} does not match the engine state '
        'layout. For DistributedKFAC the stacked bucket keys/shapes '
        'depend on the config (notably bucket_granularity and '
        'colocate_factors): restore with the SAME values the '
        'checkpoint was saved under — or write checkpoints with '
        'save(..., engine=engine) so restore can diagnose and migrate '
        f'layout changes. Original error: {exc}'
    ) from exc


def _saved_tree_metadata(path: str) -> Any:
    """The saved payload's tree of per-leaf metadata (orbax wraps it in
    StepMetadata): what a checkpoint holds, read without its arrays."""
    reader = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
    return reader.metadata(path).item_metadata.tree


def _raw_host_restore(path: str) -> dict[str, Any]:
    """Target-less restore of a checkpoint's full payload to HOST numpy.

    A bare ``StandardCheckpointer.restore(path)`` rebuilds every array
    with the checkpoint's SAVED sharding, whose serialized device mesh
    names the WRITER's devices — on an elastic restore after the pod
    shrank or grew, orbax cannot map those device ids and dies with
    "available devices are different". Restoring against the checkpoint's
    own metadata with the sharding stripped forces plain ``np.ndarray``
    leaves (scalars keep their python types), which never touches device
    placement; the migration path re-shards through the engine template
    anyway.
    """
    import numpy as np

    from orbax.checkpoint import checkpoint_utils

    reader = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
    meta = jax.tree_util.tree_map(
        lambda m: (
            dataclasses.replace(m, sharding=None)
            if dataclasses.is_dataclass(m) and hasattr(m, 'sharding')
            else m
        ),
        _saved_tree_metadata(path),
    )
    restore_args = checkpoint_utils.construct_restore_args(meta)
    raw = reader.restore(
        path, args=ocp.args.PyTreeRestore(restore_args=restore_args)
    )
    return jax.tree_util.tree_map(np.asarray, raw)


def _migrate_restore(
    path: str,
    engine: Any,
    template_state: Any,
    saved_man: dict[str, Any],
    cur_man: dict[str, Any],
    extra_template: dict[str, Any] | None,
    ckptr: Any,
) -> tuple[Any, dict[str, Any]]:
    """Cross-layout restore: raw-load the saved payload, slice per-layer
    factors out of it using the SAVED manifest, insert them into the
    current engine's layout, and rematerialize."""
    import jax.numpy as jnp

    import numpy as np

    diff = [
        k
        for k in _LAYOUT_KEYS
        if saved_man.get(k) != cur_man.get(k)
    ]
    # no target shapes needed; materialized to HOST numpy — a raw restore
    # through the SAVED shardings would both commit arrays to device 0
    # (conflicting with the engine's mesh-sharded template inside
    # insert_factors' scatter) and break outright when the device set
    # changed (elastic shrink/grow)
    raw = _raw_host_restore(path)
    _refuse_comp_ef(path, raw['kfac'])
    factors = _factors_from_saved(raw['kfac'], saved_man)
    if factors is None or 'n_stages' in cur_man:
        raise ValueError(
            f'checkpoint at {path!r} was saved under a different, '
            f'non-migratable state layout (differing fields: {diff}; '
            f"saved engine {saved_man.get('engine')}, restoring into "
            f"{cur_man.get('engine')}). Stage-stacked pipeline factors "
            'only restore into an identical pipeline layout; use '
            'checkpoint.save_factors / load_factors for portable factor '
            'checkpoints.'
        )
    saved_layers = set(factors)
    reg = getattr(engine, 'registry', None)
    if reg is not None and set(reg.names()) != saved_layers:
        raise ValueError(
            f'checkpoint at {path!r} stores factors for layers '
            f'{sorted(saved_layers)} but the restoring engine registers '
            f'{sorted(reg.names())}; factor migration requires identical '
            'layer sets.'
        )
    if reg is not None:
        # Same names but different layer WIDTHS (e.g. the script's d_model
        # changed between save and resume) must error: insert_factors would
        # otherwise silently identity-pad the stale factors into the wider
        # slots and train with a numerically wrong preconditioner.
        for name, fg in factors.items():
            h = reg.layers.get(name)
            if h is None:
                continue
            exp = (tuple(h.a_factor_shape), tuple(h.g_factor_shape))
            got = (tuple(fg['a'].shape), tuple(fg['g'].shape))
            if exp != got:
                raise ValueError(
                    f'checkpoint at {path!r}: layer {name!r} stores factor '
                    f'shapes {got} but the restoring engine expects {exp} '
                    '— the model architecture changed between save and '
                    'restore; factors cannot migrate across layer widths.'
                )
    _warnings.warn(
        f'checkpoint at {path!r} was saved under a different state layout '
        f'(differing fields: {diff}); migrating through per-layer factors '
        '(slower than a layout-exact restore, numerically identical)',
        stacklevel=3,
    )
    state = engine.insert_factors(template_state, factors)
    step_t = (
        template_state['step']
        if isinstance(template_state, dict)
        else template_state.step
    )
    step = jax.device_put(
        jnp.asarray(raw['kfac']['step'], jnp.asarray(step_t).dtype),
        step_t.sharding,
    )
    if isinstance(state, dict):
        state['step'] = step
    else:
        state = state._replace(step=step)
    state = engine.rematerialize(state)
    if (
        not isinstance(state, dict)
        and getattr(template_state, 'health', None) is not None
        and isinstance(raw.get('kfac'), dict)
        and 'health' in raw['kfac']
    ):
        # per-layer health counters are layout-independent (keyed by layer
        # name, scalar values) — they migrate verbatim
        saved_h = jax.tree_util.tree_map(
            jnp.asarray, raw['kfac']['health']
        )
        state = state._replace(health=_health_from_saved(saved_h))

    # pin the migrated state to the new engine's declared shardings: the
    # insert/rematerialize path mostly lands there already, but factors
    # that round-tripped through host numpy (and the scalar step) may sit
    # committed to default placement — an elastic restore onto a different
    # mesh must hand back arrays jit can consume without a resharding
    # surprise on the first donated step
    shard_fn = getattr(engine, 'state_shardings', None)
    if callable(shard_fn):
        shardings = shard_fn()
        if shardings is not None and jax.tree_util.tree_structure(
            state
        ) == jax.tree_util.tree_structure(shardings):
            state = jax.device_put(state, shardings)

    if extra_template:
        # The target-less restore flattens custom pytree nodes (optax
        # namedtuples and the like) into dicts/lists, so the extras must be
        # re-read against their real templates. The raw kfac payload serves
        # as its own target (saved structure/shapes by construction), which
        # lets one structured restore recover the extras with the
        # template's pytree types AND shardings.
        payload = ckptr.restore(
            path, target={'kfac': raw['kfac'], **extra_template}
        )
        extra = {k: v for k, v in payload.items() if k != 'kfac'}
    else:
        extra = {k: v for k, v in raw.items() if k != 'kfac'}
    return state, extra


def save_factors(path: str, engine: Any, state: Any) -> None:
    """Write per-layer TRUE-DIM factors + step, independent of layout.

    Unlike :func:`save` (which persists the engine's stacked arrays
    verbatim), this stores layer-named (d, d) factors, so the checkpoint
    restores into a DIFFERENT engine configuration — other
    bucket_granularity, colocate_factors, mesh, or even dense vs
    distributed. The reference's per-layer factor-dir checkpointing
    (kfac/gpt_neox/preconditioner.py:394-447) serves the same
    topology-migration role.
    """
    if not _HAS_ORBAX:
        raise RuntimeError('orbax-checkpoint is not available')
    step = state['step'] if isinstance(state, dict) else state.step
    payload = {
        'step': step,
        'factors': engine.extract_factors(state),
    }
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, payload)
    ckptr.wait_until_finished()


def load_factors(path: str, engine: Any) -> Any:
    """Restore a :func:`save_factors` checkpoint into ``engine``'s layout.

    Returns a fresh state with the loaded factors inserted and
    decompositions rematerialized. The engine must register EXACTLY the
    stored layer names with the stored true dims (layout — granularity,
    colocation, mesh, dense vs distributed — is free to differ; the layer
    set is not, and pipeline stage-stacked factors only reload into a
    pipeline engine with the same stage count).
    """
    if not _HAS_ORBAX:
        raise RuntimeError('orbax-checkpoint is not available')
    state = engine.init()
    step = state['step'] if isinstance(state, dict) else state.step
    template = {
        'step': step,
        'factors': engine.extract_factors(state),
    }
    ckptr = ocp.StandardCheckpointer()
    try:
        payload = ckptr.restore(path, target=template)
    except (ValueError, KeyError) as exc:
        raise ValueError(
            f'factor checkpoint at {path!r} does not match this engine: '
            'the registered layer names and their factor dims must equal '
            'those the checkpoint was saved with (engine LAYOUT may '
            'differ; the layer set may not, and pipeline stage counts '
            f'must match). Original error: {exc}'
        ) from exc
    state = engine.insert_factors(state, payload['factors'])
    if isinstance(state, dict):
        state['step'] = payload['step']
    else:
        state = state._replace(step=payload['step'])
    return engine.rematerialize(state)
