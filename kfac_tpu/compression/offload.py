"""Cold-factor host offload: spill factor stacks to host RAM between
cadence boundaries, prefetch them back ahead of the next one.

Why the FACTOR stacks and not (as a naive ZeRO reading would suggest)
the decomposition slots: ``precondition`` reads the resident
decompositions (qa/qg/da/dg/dgda or a_inv/g_inv) EVERY step — they are
hot by construction. The genuinely cold state is ``state.a``/``state.g``
between factor-EMA events: with ``factor_update_steps = F`` and
``inv_update_steps = C`` the stacks are consumed only on steps where
``step % F == 0`` (EMA read-modify-write) or ``step % C == 0`` (inverse
refresh / async-host boundary launch), and are HBM dead weight for the
``F - 1`` interior steps — the dominant durable term in
``memory_usage()``.

Execution model (mirrors ``async_inverse/host.py``'s pump contract): the
offload is driven from the HOST between steps, never from inside the
compiled program. :func:`pump` runs at step entry on the Trainer's eager
paths; it swaps the state's factor dicts for zero-size placeholder
arrays when spilling (host copies live in the :class:`OffloadManager`),
and swaps real arrays back in before any step whose trace or runtime
needs them. The engines' ``step`` detects the placeholders at TRACE time
(:func:`is_spilled`) and statically skips the factor/inverse conds, so
the steady state is two stable compiled programs — the interior spilled
step (no factor work at all) and the boundary resident step — with no
recompilation churn in between. Spill/restore round-trips move bytes
verbatim (same dtype ``device_get``/``device_put``), so training with
offload on is bit-identical to offload off.

State lifecycle: offload slots are EPHEMERAL — never checkpointed
(``checkpoint.durable_state`` refuses a spilled state;
:meth:`OffloadManager.host_view` hands the checkpoint autopilot a
resident view straight from the host copies with zero device traffic)
and a restore rematerializes a resident state with a reset manager.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kfac_tpu import tracing


def _cfg(engine: Any) -> Any:
    """The hyperparameter carrier: ``engine.config`` for DistributedKFAC,
    the engine itself for the dense KFACPreconditioner."""
    return getattr(engine, 'config', engine)


def is_spilled(state: Any) -> bool:
    """True when the state's factor dicts hold offload placeholders.

    Placeholders are zero-size 1-D arrays — statically distinguishable
    at trace time from both dense ``(d, d)`` factors and stacked
    ``(L, d, d)`` buckets, so the engines' ``step`` can skip the
    factor/inverse branches without a host sync.
    """
    a = getattr(state, 'a', None)
    if not a:
        return False
    v = next(iter(a.values()))
    return v.ndim == 1 and v.shape[0] == 0


class OffloadManager:
    """Host-side owner of spilled factor stacks for one engine.

    Holds the numpy copies while the device state carries placeholders,
    runs the asynchronous prefetch, and keeps the traffic/hit counters
    ``comms_report()`` reads. Purely
    host state — construction touches no device.
    """

    def __init__(self, engine: Any) -> None:
        self.engine = engine
        self.cfg = _cfg(engine).offload
        self.spilled = False
        self._host: dict[str, dict[str, np.ndarray]] | None = None
        self._inflight: dict[str, dict[str, jax.Array]] | None = None
        self._shardings: Any = None
        self.stats = {
            'spills': 0,
            'restores': 0,
            'prefetch_hits': 0,
            'prefetch_misses': 0,
            'bytes_to_host': 0,
            'bytes_to_device': 0,
        }

    def reset(self) -> None:
        """Forget any spilled/in-flight copies (checkpoint restore,
        ``rematerialize``): the state the caller holds is resident."""
        self.spilled = False
        self._host = None
        self._inflight = None

    # ----------------------------------------------------------- transfers

    def _factor_sharding(self, side: str, key: str) -> Any:
        if self._shardings is None:
            fn = getattr(self.engine, 'state_shardings', None)
            self._shardings = fn() if fn is not None else False
        if self._shardings is False:  # dense engine: default placement
            return None
        return getattr(self._shardings, side)[key]

    def _put_all(self) -> dict[str, dict[str, jax.Array]]:
        """Asynchronous device_put of every host copy (JAX dispatches the
        transfers eagerly and returns immediately; consumers block only
        if they run before the copy lands)."""
        out: dict[str, dict[str, jax.Array]] = {}
        for side, arrs in self._host.items():
            put = {}
            for key, arr in arrs.items():
                sh = self._factor_sharding(side, key)
                put[key] = (
                    jax.device_put(arr) if sh is None
                    else jax.device_put(arr, sh)
                )
            out[side] = put
        return out

    def spill(self, state: Any) -> Any:
        """Copy factors to host RAM and substitute placeholders."""
        if self.spilled:
            return state
        self._host = {
            side: {
                k: np.asarray(jax.device_get(v))
                for k, v in getattr(state, side).items()
            }
            for side in ('a', 'g')
        }
        self.stats['spills'] += 1
        self.stats['bytes_to_host'] += sum(
            arr.nbytes for d in self._host.values() for arr in d.values()
        )
        self.spilled = True
        return state._replace(
            a={k: jnp.zeros((0,), v.dtype) for k, v in state.a.items()},
            g={k: jnp.zeros((0,), v.dtype) for k, v in state.g.items()},
        )

    def start_prefetch(self) -> None:
        """Kick off the async transfer back to device (idempotent)."""
        if not self.spilled or self._inflight is not None:
            return
        self._inflight = self._put_all()

    def restore(self, state: Any) -> Any:
        """Swap real factor arrays back into the state.

        A prefetch started early enough has already landed (hit); without
        one the device_put runs here and the next consumer blocks on it
        (miss) — recorded either way.
        """
        if not self.spilled:
            return state
        if self._inflight is not None:
            self.stats['prefetch_hits'] += 1
            bufs = self._inflight
        else:
            self.stats['prefetch_misses'] += 1
            bufs = self._put_all()
        self.stats['restores'] += 1
        self.stats['bytes_to_device'] += sum(
            arr.nbytes for d in self._host.values() for arr in d.values()
        )
        state = state._replace(a=bufs['a'], g=bufs['g'])
        self.reset()
        return state

    def host_view(self, state: Any) -> Any:
        """A resident view of a spilled state built from the host copies
        (numpy, zero device traffic) — what the checkpoint autopilot
        persists when a save lands inside a spill window."""
        if not self.spilled:
            return state
        return state._replace(
            a=dict(self._host['a']), g=dict(self._host['g'])
        )


def _next_use(step: int, f: int, c: int) -> int:
    """First step >= ``step`` that consumes the factor stacks: a factor
    EMA (``% f``) or an inverse refresh / async-host launch (``% c``)."""
    return min(step + (-step) % f, step + (-step) % c)


@tracing.trace(name='kfac.offload_pump')
def pump(engine: Any, state: Any, step: int | None = None) -> Any:
    """Drive the offload state machine at step entry (host-side).

    With ``step`` (the eager Trainer paths): restores before any step
    that consumes the factors, starts the prefetch ``prefetch_lead``
    steps ahead of that boundary, and spills after the last consuming
    step once the next boundary is ``min_cold_steps`` or more away.
    Without one (the scan paths, where the host cannot intervene
    mid-scan): restores residency unconditionally and leaves the stacks
    resident for the whole scan.

    The restore-before-boundary guarantee is what lets the engines'
    ``step`` statically skip factor/inverse work on spilled states: a
    spilled state is never stepped through a cadence boundary.
    """
    mgr = getattr(engine, '_offload_manager', None)
    if mgr is None:
        return state
    if step is None:
        return mgr.restore(state)
    cfg = _cfg(engine)
    f = int(cfg.factor_update_steps)
    c = int(cfg.inv_update_steps)
    nu = _next_use(step, f, c)
    if mgr.spilled:
        if nu == step:
            return mgr.restore(state)
        if nu - step <= mgr.cfg.prefetch_lead:
            mgr.start_prefetch()
        return state
    if nu > step and nu - step >= mgr.cfg.min_cold_steps:
        return mgr.spill(state)
    return state
