"""Sliced on-device async refresh: the window's eigh work, one slice per step.

Replaces the synchronous inverse-cadence ``lax.cond`` in the engines'
``step`` with a three-stage in-jit dispatcher:

1. **swap** (``phase == 0``): promote a complete, finite, non-quarantined
   shadow into the active slots, advance ``last_inv_step`` for the layers
   that actually swapped (staleness metrics stay truthful), update the
   health degradation counters, and reset slice progress.
2. **cold start** (``step == 0``): one synchronous ``update_inverses`` so
   the first window never preconditions with zero decompositions — same
   as the synchronous path's step-0 refresh.
3. **slice** (``lax.switch`` on the window phase): refresh this phase's
   unit bucket into the shadow from the CURRENT factors. Slices use the
   very same decomposition kernels as the synchronous path
   (``compute_eigh`` / ``damped_inverse`` / the distributed engine's
   sharded batched eigh), so a swapped shadow is bit-identical to what a
   synchronous refresh would have produced from the same factors — the
   active decompositions are simply one window staler.

Units are balanced across slices by the n^3 compute weighting
(:func:`kfac_tpu.assignment.compute_work_costs` heuristic): the dense
engine slices per (factor side, layer) — per layer when fused prediv ties
the sides together — and the distributed engine per storage bucket (per
pair bucket under prediv), so one size-class batched eigh runs per step.

Quarantine interaction (PR-1 sentinel): a layer quarantined at the
boundary has its in-flight shadow refresh DISCARDED, not swapped — the
factors that produced it were suspect. The degradation counter advances
through :func:`kfac_tpu.health.inversion_update` exactly as a quarantined
synchronous refresh would.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from kfac_tpu import enums
from kfac_tpu import health as health_lib
from kfac_tpu import tracing
from kfac_tpu.async_inverse import slots as slots_lib
from kfac_tpu.observability import metrics as metrics_lib
from kfac_tpu.ops import factors as factors_lib


def _resolve(value, step):
    if callable(value):
        return value(step)
    return value


def decomp_fields(compute_method, prediv: bool) -> tuple[str, ...]:
    """The engine-state decomposition fields a config actually uses."""
    if compute_method == enums.ComputeMethod.EIGEN:
        if prediv:
            return ('qa', 'qg', 'dgda')
        return ('qa', 'qg', 'da', 'dg')
    return ('a_inv', 'g_inv')


# --------------------------------------------------------------------- dense


def dense_units(engine) -> list[tuple[tuple[str, str], float]]:
    """Refresh units for the dense engine: ``((side, layer), n^3 cost)``.

    The two factor sides of a layer decompose independently, so they are
    separate units (halving the worst slice) — except under fused prediv,
    where ``dgda`` needs both sides' eigenvalues in one place.
    """
    units: list[tuple[tuple[str, str], float]] = []
    eigen = engine.compute_method == enums.ComputeMethod.EIGEN
    fused = eigen and engine.prediv_eigenvalues
    for name, h in engine.registry.layers.items():
        na = float(h.a_factor_shape[0]) ** 3
        ng = float(h.g_factor_shape[0]) ** 3
        if fused:
            units.append((('ag', name), na + ng))
        else:
            units.append((('a', name), na))
            units.append((('g', name), ng))
    return units


def dense_shadow(engine, state) -> slots_lib.ShadowSlots:
    """A zeroed shadow mirroring the dense state's decomposition slots."""
    fields = decomp_fields(engine.compute_method, engine.prediv_eigenvalues)
    return slots_lib.empty_shadow(
        {f: getattr(state, f) for f in fields}
    )


def dense_swap_core(engine, state, cand, complete):
    """Gated promotion of candidate decompositions into the active slots.

    ``cand`` maps field name -> {layer: array} (already in ``inv_dtype``);
    ``complete`` is a traced bool — False leaves everything untouched.
    Shared by the sliced swap (candidates from the shadow) and the host
    backend's apply (candidates from the worker payload, complete=True).

    Per layer, all fields swap together (no torn A/G mixtures), gated on
    finiteness (health only — the synchronous path doesn't finite-check
    either when the sentinel is off) and on the quarantine flag.
    ``last_inv_step`` advances only for layers that swapped.
    """
    cfg = engine.health
    h = state.health
    fields = decomp_fields(engine.compute_method, engine.prediv_eigenvalues)
    new = {f: dict(getattr(state, f)) for f in fields}
    bad_inv = dict(h.bad_inv) if cfg is not None else {}
    touched: dict[str, jax.Array] = {}
    for name in engine.registry.layers:
        if cfg is not None:
            ok = jnp.stack(
                [jnp.isfinite(cand[f][name]).all() for f in fields]
            ).all()
            swapped = complete & ok & (h.quarantined[name] <= 0)
            bad_inv[name] = jnp.where(
                complete,
                health_lib.inversion_update(
                    cfg, ok, h.quarantined[name], h.bad_inv[name]
                ),
                h.bad_inv[name],
            )
        else:
            swapped = jnp.asarray(complete)
        for f in fields:
            new[f][name] = jnp.where(
                swapped, cand[f][name], getattr(state, f)[name]
            )
        touched[name] = swapped
    state = state._replace(**new)
    if cfg is not None:
        state = state._replace(health=h._replace(bad_inv=bad_inv))
    if engine.metrics is not None and state.metrics is not None:
        ms = state.metrics
        state = state._replace(metrics=ms._replace(
            last_inv_step=metrics_lib.advance_last(
                ms.last_inv_step, ms.names, touched, state.step)))
    return state


def _dense_swap(engine, state):
    sh = state.shadow
    fields = decomp_fields(engine.compute_method, engine.prediv_eigenvalues)
    state = dense_swap_core(
        engine, state,
        {f: getattr(sh, f) for f in fields},
        sh.progress >= engine._async_n_slices,
    )
    # progress resets unconditionally: it counts slices since the last
    # boundary, and every unit is recomputed each window regardless of
    # whether this boundary's swap fired
    return state._replace(
        shadow=state.shadow._replace(progress=jnp.zeros((), jnp.int32))
    )


def _dense_slice(engine, state, units):
    """Refresh one slice's units into the shadow from CURRENT factors."""
    sh = state.shadow
    cfg = engine.health
    h = state.health
    damping = _resolve(engine.damping, state.step)
    eigen = engine.compute_method == enums.ComputeMethod.EIGEN
    fields = decomp_fields(engine.compute_method, engine.prediv_eigenvalues)
    upd = {f: dict(getattr(sh, f)) for f in fields}

    def eff(name):
        if cfg is None:
            return damping
        return damping * h.damping_mult[name]

    floor = factors_lib.identity_floor(
        state.step, engine.factor_decay, engine.factor_update_steps
    )
    for side, name in units:
        if eigen:
            if side in ('a', 'ag'):
                adec = factors_lib.compute_eigh(
                    state.a[name], engine.inv_dtype, engine.eigh_impl
                )
                upd['qa'][name] = adec.q
                if not engine.prediv_eigenvalues:
                    upd['da'][name] = adec.d
            if side in ('g', 'ag'):
                gdec = factors_lib.compute_eigh(
                    state.g[name], engine.inv_dtype, engine.eigh_impl
                )
                upd['qg'][name] = gdec.q
                if not engine.prediv_eigenvalues:
                    upd['dg'][name] = gdec.d
            if side == 'ag':
                upd['dgda'][name] = factors_lib.prediv_eigenvalues(
                    adec, gdec, eff(name)
                ).astype(engine.inv_dtype)
        else:
            # warm-start from the ACTIVE inverse: the factor EMA drifts
            # slowly across a window, so it is deep in the quadratic basin
            # (same rationale as the synchronous path's warm start)
            if side == 'a':
                upd['a_inv'][name] = factors_lib.damped_inverse(
                    state.a[name], eff(name), engine.inv_dtype,
                    engine.inverse_solver, engine.newton_schulz_iters,
                    x0=state.a_inv[name], floor=floor,
                )
            else:
                upd['g_inv'][name] = factors_lib.damped_inverse(
                    state.g[name], eff(name), engine.inv_dtype,
                    engine.inverse_solver, engine.newton_schulz_iters,
                    x0=state.g_inv[name], floor=floor,
                )
    return state._replace(shadow=sh._replace(
        progress=sh.progress + 1,
        damping=jnp.asarray(damping, jnp.float32),
        **upd,
    ))


@tracing.scope('kfac.async_refresh')
def dense_async_step(engine, state):
    """The dense engine's in-jit async dispatcher (replaces the inverse
    cadence cond). See the module docstring for the three stages."""
    phase = jnp.mod(state.step, engine._async_n_steps)
    state = jax.lax.cond(
        phase == 0, partial(_dense_swap, engine), lambda s: s, state
    )
    state = jax.lax.cond(
        state.step == 0, engine.update_inverses, lambda s: s, state
    )
    n_slices = engine._async_n_slices
    branches = [
        partial(_dense_slice, engine, units=u) for u in engine._async_slices
    ] + [lambda s: s]
    return jax.lax.switch(jnp.minimum(phase, n_slices), branches, state)


# --------------------------------------------------------------- distributed


def kaisa_units(engine) -> list[tuple[tuple[str, str], float]]:
    """Refresh units for the distributed engine: one storage bucket's
    sharded batched decomposition per unit (``(side, bucket_key)``), or
    one pair bucket (``('ag', key)``) under fused prediv. Costs are the
    stack's total n^3 FLOPs — the padded slot count times the class dim
    cubed — matching what :meth:`_sharded_eigh` actually executes."""
    units: list[tuple[tuple[str, str], float]] = []
    if engine._prediv:
        for b in engine.buckets:
            units.append(
                (('ag', b.key), b.padded * (float(b.da) ** 3 + float(b.dg) ** 3))
            )
        return units
    for sb in engine.a_store:
        units.append((('a', sb.key), sb.padded * float(sb.d) ** 3))
    for sb in engine.g_store:
        units.append((('g', sb.key), sb.padded * float(sb.d) ** 3))
    return units


def kaisa_shadow(engine, state) -> slots_lib.ShadowSlots:
    """A zeroed shadow mirroring the stacked decomposition slots (shapes,
    dtypes, and — outside jit — shardings follow the active fields)."""
    fields = decomp_fields(engine.config.compute_method, engine._prediv)
    return slots_lib.empty_shadow(
        {f: getattr(state, f) for f in fields}
    )


def kaisa_swap_core(engine, state, cand, cand_damping, complete):
    """Stacked-layout swap: per-layer gates scattered onto per-slot masks.

    A layer's A and G slots (possibly in different stacks under
    ``colocate_factors=False``) swap together or not at all — the
    per-layer verdict (finite on every field, not quarantined) is
    scattered into each storage bucket's ``(L,)`` mask with the same
    update-slice assembly as ``_slot_mask`` (GSPMD stack hazard).
    ``inv_damping`` is promoted to the damping the candidates were built
    at. Shared by the sliced swap and the host backend's apply.
    """
    from jax.sharding import NamedSharding

    cfg = engine.config
    hc = cfg.health
    h = state.health
    dec = NamedSharding(engine.mesh, engine._decomp_spec())
    eigen = engine._eigen
    prediv = engine._prediv

    def slot_finite(arrays):
        ok = jnp.isfinite(arrays[0]).all(
            axis=tuple(range(1, arrays[0].ndim))
        )
        for x in arrays[1:]:
            ok = ok & jnp.isfinite(x).all(axis=tuple(range(1, x.ndim)))
        return ok

    bad_inv = dict(h.bad_inv) if hc is not None else {}
    touched: dict[str, jax.Array] = {}
    if hc is not None:
        # per-slot finite verdicts per store, then combined per layer
        ok_a = {
            sb.key: slot_finite(
                [cand['qa'][sb.key]]
                + ([cand['da'][sb.key]] if eigen and not prediv else [])
                if eigen else [cand['a_inv'][sb.key]]
            )
            for sb in engine.a_store
        }
        ok_g = {
            sb.key: slot_finite(
                [cand['qg'][sb.key]]
                + ([cand['dg'][sb.key]] if eigen and not prediv else [])
                if eigen else [cand['g_inv'][sb.key]]
            )
            for sb in engine.g_store
        }
        ok_fused = (
            {b.key: slot_finite([cand['dgda'][b.key]]) for b in engine.buckets}
            if prediv else {}
        )
        swap_flags: dict[str, jax.Array] = {}
        for n in engine.registry.layers:
            ak, ai = engine._a_slot[n]
            gk, gi = engine._g_slot[n]
            okn = ok_a[ak][ai] & ok_g[gk][gi]
            if prediv:
                okn = okn & ok_fused[ak][ai]
            swapped = complete & okn & (h.quarantined[n] <= 0)
            swap_flags[n] = swapped
            touched[n] = swapped
            bad_inv[n] = jnp.where(
                complete,
                health_lib.inversion_update(
                    hc, okn, h.quarantined[n], h.bad_inv[n]
                ),
                h.bad_inv[n],
            )

        def store_mask(layers, padded):
            return engine._slot_mask(swap_flags, layers, padded)
    else:
        for n in engine.registry.layers:
            touched[n] = jnp.asarray(complete)

    def swap_stack(store, field):
        out = {}
        for sb in store:
            active = getattr(state, field)[sb.key]
            c = cand[field][sb.key]
            if hc is None:
                gate = jnp.asarray(complete)
            else:
                gate = store_mask(sb.layers, sb.padded)
            shaped = gate.reshape(gate.shape + (1,) * (c.ndim - gate.ndim))
            out[sb.key] = jax.lax.with_sharding_constraint(
                jnp.where(shaped, c, active), dec
            )
        return out

    if eigen:
        upd = {
            'qa': swap_stack(engine.a_store, 'qa'),
            'qg': swap_stack(engine.g_store, 'qg'),
        }
        if prediv:
            upd['dgda'] = swap_stack(engine.buckets, 'dgda')
        else:
            upd['da'] = swap_stack(engine.a_store, 'da')
            upd['dg'] = swap_stack(engine.g_store, 'dg')
    else:
        upd = {
            'a_inv': swap_stack(engine.a_store, 'a_inv'),
            'g_inv': swap_stack(engine.g_store, 'g_inv'),
        }
    state = state._replace(
        **upd,
        inv_damping=jnp.where(complete, cand_damping, state.inv_damping),
    )
    if hc is not None:
        state = state._replace(health=h._replace(bad_inv=bad_inv))
    if cfg.metrics is not None and state.metrics is not None:
        ms = state.metrics
        state = state._replace(metrics=ms._replace(
            last_inv_step=metrics_lib.advance_last(
                ms.last_inv_step, ms.names, touched, state.step)))
    return state


def _kaisa_swap(engine, state):
    sh = state.shadow
    fields = decomp_fields(engine.config.compute_method, engine._prediv)
    state = kaisa_swap_core(
        engine, state,
        {f: getattr(sh, f) for f in fields},
        sh.damping,
        sh.progress >= engine._async_n_slices,
    )
    return state._replace(
        shadow=state.shadow._replace(progress=jnp.zeros((), jnp.int32))
    )


def _kaisa_slice(engine, state, units):
    """Refresh one slice's storage buckets into the stacked shadow.

    Same kernels and shardings as the synchronous
    :meth:`DistributedKFAC.update_inverses` — sharded batched eigh over
    ``P(all_axes)``, then a resident-layout constraint on the shadow write
    (spreading the inverse-broadcast reshard across the window too).
    """
    from jax.sharding import NamedSharding

    cfg = engine.config
    hc = cfg.health
    h = state.health
    sh = state.shadow
    damping = _resolve(cfg.damping, state.step)
    dec = NamedSharding(engine.mesh, engine._decomp_spec())
    fields = decomp_fields(cfg.compute_method, engine._prediv)
    upd = {f: dict(getattr(sh, f)) for f in fields}

    def slot_damping(layers, padded):
        if hc is None:
            return damping
        return damping * engine._slot_mults(h, layers, padded)

    def store_by_key(store, key):
        return next(sb for sb in store if sb.key == key)

    for side, key in units:
        if engine._eigen:
            if side in ('a', 'ag'):
                q_, d_a = engine._sharded_eigh(state.a[key])
                upd['qa'][key] = jax.lax.with_sharding_constraint(
                    q_.astype(cfg.inv_dtype), dec
                )
                if not engine._prediv:
                    upd['da'][key] = jax.lax.with_sharding_constraint(
                        d_a.astype(cfg.inv_dtype), dec
                    )
            if side in ('g', 'ag'):
                q_, d_g = engine._sharded_eigh(state.g[key])
                upd['qg'][key] = jax.lax.with_sharding_constraint(
                    q_.astype(cfg.inv_dtype), dec
                )
                if not engine._prediv:
                    upd['dg'][key] = jax.lax.with_sharding_constraint(
                        d_g.astype(cfg.inv_dtype), dec
                    )
            if side == 'ag':
                b = store_by_key(engine.buckets, key)
                fused = jax.vmap(
                    lambda da_, dg_, dm: factors_lib.prediv_eigenvalues(
                        factors_lib.EigenDecomp(q=None, d=da_),
                        factors_lib.EigenDecomp(q=None, d=dg_),
                        dm,
                    )
                )(
                    d_a, d_g,
                    jnp.broadcast_to(
                        jnp.asarray(
                            slot_damping(b.layers, b.padded), jnp.float32
                        ),
                        (b.padded,),
                    ),
                )
                upd['dgda'][key] = jax.lax.with_sharding_constraint(
                    fused.astype(cfg.inv_dtype), dec
                )
        else:
            sb = store_by_key(
                engine.a_store if side == 'a' else engine.g_store, key
            )
            factor = state.a[key] if side == 'a' else state.g[key]
            prev = state.a_inv[key] if side == 'a' else state.g_inv[key]
            cand = engine._sharded_inv(
                factor, slot_damping(sb.layers, sb.padded), prev=prev,
                floor=factors_lib.identity_floor(
                    state.step, cfg.factor_decay, cfg.factor_update_steps
                ),
            )[0].astype(cfg.inv_dtype)
            upd['a_inv' if side == 'a' else 'g_inv'][key] = (
                jax.lax.with_sharding_constraint(cand, dec)
            )
    return state._replace(shadow=sh._replace(
        progress=sh.progress + 1,
        damping=jnp.asarray(damping, jnp.float32),
        **upd,
    ))


@tracing.scope('dist_kfac.async_refresh')
def kaisa_async_step(engine, state):
    """The distributed engine's in-jit async dispatcher (replaces the
    inverse cadence cond). Same three stages as
    :func:`dense_async_step`."""
    phase = jnp.mod(state.step, engine._async_n_steps)
    state = jax.lax.cond(
        phase == 0, partial(_kaisa_swap, engine), lambda s: s, state
    )
    state = jax.lax.cond(
        state.step == 0, engine.update_inverses, lambda s: s, state
    )
    n_slices = engine._async_n_slices
    branches = [
        partial(_kaisa_slice, engine, units=u) for u in engine._async_slices
    ] + [lambda s: s]
    return jax.lax.switch(jnp.minimum(phase, n_slices), branches, state)
