"""Deterministic numerical-fault injection for the health sentinel tests.

Each injector takes healthy data and returns a poisoned copy — no RNG, no
mutation of the input — so a fault test is exactly reproducible and the
healthy original stays available for bitwise "nothing moved" assertions.
Faults mirror the real-world failure modes the sentinel defends against
(kfac_tpu/health.py): a corrupt input batch (dead loss/grads), a corrupt
micro-batch inside an accumulation, poisoned curvature statistics, a
factor blow-up past the conditioning bound, factors corrupted at rest
(e.g. a bad checkpoint), and torn checkpoint writes on disk (host crash
or preemption mid-write — the resilience rotation's fallback trigger).
"""

from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp

from kfac_tpu.layers import capture as capture_lib

#: supported non-finite poison values by name
POISONS = {
    'nan': float('nan'),
    'inf': float('inf'),
    '-inf': float('-inf'),
}


def _poison_value(kind: str) -> float:
    try:
        return POISONS[kind]
    except KeyError:
        raise ValueError(
            f'unknown poison kind {kind!r}; expected one of {sorted(POISONS)}'
        ) from None


def poison_batch(batch: Any, kind: str = 'nan', index: int = 0) -> Any:
    """Poison one element of every array leaf of a ``(x, y, ...)`` batch.

    Flattens each leaf and sets position ``index`` to the poison value —
    a single bad training example is enough to drive loss and every
    gradient non-finite, the skip-step trigger.
    """
    val = _poison_value(kind)

    def leaf(x):
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.inexact):
            return x
        flat = x.reshape(-1)
        return flat.at[index].set(val).reshape(x.shape)

    return jax.tree_util.tree_map(leaf, batch)


def poison_microbatch(
    microbatches: Any, which: int, kind: str = 'nan'
) -> Any:
    """Poison micro-batch ``which`` of a stacked micro-batch pytree.

    ``microbatches`` has a leading micro-batch axis on every leaf (the
    :meth:`kfac_tpu.Trainer.step_accumulate_scan` input convention). One
    poisoned micro-batch must make the whole accumulated step skip.
    """
    val = _poison_value(kind)

    def leaf(x):
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.inexact):
            return x
        flat = x[which].reshape(-1)
        return x.at[which].set(flat.at[0].set(val).reshape(x[which].shape))

    return jax.tree_util.tree_map(leaf, microbatches)


def poison_stats(
    stats: capture_lib.CapturedStats,
    layers: Any,
    side: str = 'a',
    kind: str = 'nan',
) -> capture_lib.CapturedStats:
    """Poison the captured ``A`` (or ``G``) statistics of the given layers.

    Builds a NEW CapturedStats (custom pytree — no ``_replace``): the
    factor-quarantine trigger, while grads stay finite so the skip-step
    gate does NOT fire and the engine-level quarantine is isolated.
    """
    if side not in ('a', 'g'):
        raise ValueError(f"side must be 'a' or 'g', got {side!r}")
    if isinstance(layers, str):
        layers = [layers]
    val = _poison_value(kind)
    a = dict(stats.a)
    g = dict(stats.g)
    tgt = a if side == 'a' else g
    for name in layers:
        if name not in tgt:
            raise KeyError(
                f'layer {name!r} not in captured stats {sorted(tgt)}'
            )
        tgt[name] = tgt[name] + val  # NaN/inf poisons every entry
    return capture_lib.CapturedStats(a=a, g=g, w=dict(stats.w))


def huge_stats(
    stats: capture_lib.CapturedStats,
    layers: Any,
    scale: float = 1e30,
    side: str = 'a',
) -> capture_lib.CapturedStats:
    """Blow the given layers' statistics up by ``scale`` — FINITE values
    that push the factor's Gershgorin conditioning estimate past any sane
    ``quarantine_threshold``, exercising the bound-based (rather than
    finiteness-based) quarantine path."""
    if side not in ('a', 'g'):
        raise ValueError(f"side must be 'a' or 'g', got {side!r}")
    if isinstance(layers, str):
        layers = [layers]
    a = dict(stats.a)
    g = dict(stats.g)
    tgt = a if side == 'a' else g
    for name in layers:
        if name not in tgt:
            raise KeyError(
                f'layer {name!r} not in captured stats {sorted(tgt)}'
            )
        tgt[name] = tgt[name] * scale
    return capture_lib.CapturedStats(a=a, g=g, w=dict(stats.w))


def poison_factors(
    engine: Any,
    state: Any,
    layers: Any,
    side: str = 'a',
    kind: str = 'nan',
) -> Any:
    """Corrupt resident factors in an engine state (any engine layout).

    Round-trips through ``extract_factors``/``insert_factors`` so the same
    injector poisons the dense per-layer dicts and the stacked KAISA slot
    buckets — the "factors corrupted at rest" scenario (bad checkpoint,
    bit flip) that inversion-time health verdicts and
    ``checkpoint.restore`` validation must catch.
    """
    if isinstance(layers, str):
        layers = [layers]
    val = _poison_value(kind)
    factors = engine.extract_factors(state)
    out = {}
    for name, fg in factors.items():
        fg = dict(fg)
        if name in layers:
            fg[side] = fg[side] + val
        out[name] = fg
    missing = set(layers) - set(factors)
    if missing:
        raise KeyError(f'layers {sorted(missing)} not in engine factors')
    return engine.insert_factors(state, out)


#: supported on-disk checkpoint corruption modes
CHECKPOINT_CORRUPTIONS = (
    'truncate', 'delete', 'garbage', 'metadata', 'torn_latest'
)


def corrupt_checkpoint(path: str, mode: str = 'truncate') -> str:
    """Deterministically corrupt a committed orbax checkpoint directory.

    Simulates a torn write / partial loss after commit (host crash during
    an fsync-less copy, filesystem rollback, bit rot): the checkpoint
    still LOOKS committed (its metadata markers remain for every mode but
    ``'metadata'``), so only an actual restore attempt discovers the
    damage — exactly the case :meth:`kfac_tpu.resilience
    .CheckpointManager.restore_latest` must survive by falling back to
    the previous rotation entry.

    The victim is chosen deterministically (largest payload file, path as
    the tie-break), no RNG. Modes:

    - ``'truncate'``: cut the victim to half its size (torn write).
    - ``'delete'``: remove the victim (lost object).
    - ``'garbage'``: overwrite the victim's first bytes in place
      (bit rot / torn page).
    - ``'metadata'``: remove the orbax commit markers — the checkpoint no
      longer looks committed at all (crash before commit).
    - ``'torn_latest'``: tear the rotation's ``LATEST`` pointer itself —
      ``path`` is the ROTATION ROOT (the CheckpointManager directory),
      not a step dir. The pointer is truncated to half and garbage bytes
      appended, so ``latest_step()`` cannot parse it; the payload step
      dirs stay intact and ``restore_latest`` must recover via the
      rotation scan instead of crashing on the pointer. Distinct from
      the payload modes: the fault is in the commit pointer, not the
      checkpoint bytes.

    Returns the corrupted/removed file's path.
    """
    if mode not in CHECKPOINT_CORRUPTIONS:
        raise ValueError(
            f'unknown corruption mode {mode!r}; expected one of '
            f'{CHECKPOINT_CORRUPTIONS}'
        )
    if not os.path.isdir(path):
        raise FileNotFoundError(f'checkpoint dir {path!r} does not exist')
    if mode == 'torn_latest':
        victim = os.path.join(path, 'LATEST')
        if not os.path.exists(victim):
            raise FileNotFoundError(
                f'no LATEST pointer under {path!r} — pass the rotation '
                'root (the CheckpointManager directory), not a step dir'
            )
        size = os.path.getsize(victim)
        with open(victim, 'r+b') as f:
            f.truncate(size // 2)
            f.seek(0, os.SEEK_END)
            f.write(b'\xde\xad\xbe\xef')
        return victim
    if mode == 'metadata':
        victim = None
        for marker in ('_CHECKPOINT_METADATA', '_METADATA'):
            mpath = os.path.join(path, marker)
            if os.path.exists(mpath):
                os.remove(mpath)
                victim = mpath
        if victim is None:
            raise FileNotFoundError(
                f'no orbax metadata markers under {path!r}'
            )
        return victim
    candidates = []
    for root, _, files in os.walk(path):
        for name in files:
            if name.startswith('_'):  # keep commit markers intact
                continue
            fp = os.path.join(root, name)
            candidates.append((-os.path.getsize(fp), fp))
    if not candidates:
        raise FileNotFoundError(f'no payload files under {path!r}')
    _, victim = min(candidates)
    if mode == 'delete':
        os.remove(victim)
    elif mode == 'truncate':
        size = os.path.getsize(victim)
        with open(victim, 'r+b') as f:
            f.truncate(size // 2)
    else:  # garbage
        with open(victim, 'r+b') as f:
            f.write(b'\xde\xad\xbe\xef' * 16)
    return victim
