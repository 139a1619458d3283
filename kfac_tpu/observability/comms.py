"""Comms/memory accounting for the KAISA transports.

KAISA's value proposition is a measurable memory<->communication trade
governed by the gradient worker fraction (Pauloski et al., SC'21); this
module makes the communication side of that trade observable WITHOUT
tracing a step: every number here is derived on the host from the
engine's static layout (size-class buckets, storage stores, transport
config, strategy), mirroring exactly what the jitted step makes XLA emit.

Accounted flows, per ``DistributedKFAC``:

- **factor stat transport** (every ``factor_update_steps`` step): either
  one replication pin per captured (d, d) factor (``ALLREDUCE``) or the
  byte-capped flat buffers of packed upper triangles
  (``ALLREDUCE_BUCKETED``); the report carries the chunk plan from
  :func:`kfac_tpu.parallel.collectives.plan_chunks`.
- **inverse/decomposition reshard** (every ``inv_update_steps`` step):
  factor-sharded eigh/inverse outputs resharded to the strategy's
  resident layout — the KAISA "inverse broadcast".
- **gradient broadcast** (every step): preconditioned gradient stacks
  replicated from the grad-worker column layout.
- **padding waste**: resident factor bytes split into true-dim content,
  identity padding inside each size-class slot, and whole padding slots
  added to round stacks to the device count.

Bytes are global logical bytes moved per occurrence of each flow (what
you would compare across transports/configs), not per-device wire bytes
— the per-device split depends on the collective algorithm XLA picks.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from kfac_tpu import enums

# NOTE: kfac_tpu.parallel is imported lazily inside functions. The engines
# import this package (for the metrics state), and kfac_tpu.parallel
# imports the engines — a top-level import here would close that cycle.


def _itemsize(dtype: Any) -> int:
    return int(jnp.dtype(dtype).itemsize)


def padding_report(engine: Any) -> dict[str, dict[str, Any]]:
    """Resident vs. padding bytes per size-class storage bucket.

    For each A/G storage bucket: ``resident_bytes`` is the true-dim
    factor content, ``identity_pad_bytes`` the identity-block padding
    embedding true dims into the class dim, ``slot_pad_bytes`` the whole
    identity slots rounding the stack to the device count, and ``fill``
    the resident fraction of the stack. Keys are ``'a/<key>'`` /
    ``'g/<key>'``.
    """
    item = _itemsize(engine.config.factor_dtype)
    out: dict[str, dict[str, Any]] = {}
    for side, store in (('a', engine.a_store), ('g', engine.g_store)):
        for sb in store:
            resident = sum(d * d for d in sb.dims) * item
            layer_slots = len(sb.layers) * sb.d * sb.d * item
            total = sb.padded * sb.d * sb.d * item
            out[f'{side}/{sb.key}'] = {
                'layers': len(sb.layers),
                'slots': sb.padded,
                'class_dim': sb.d,
                'resident_bytes': resident,
                'identity_pad_bytes': layer_slots - resident,
                'slot_pad_bytes': total - layer_slots,
                'total_bytes': total,
                'fill': resident / total if total else 1.0,
            }
    return out


def transport_report(engine: Any) -> dict[str, Any]:
    """Bytes moved by the factor stat transport on a capture step.

    ``ALLREDUCE``: each captured factor is pinned to replicated on its
    own — one small collective per factor, true-dim dense bytes.
    ``ALLREDUCE_BUCKETED``: the upper triangles of every CLASS-dim row
    (state rows for unexecuted layers included — the transport packs the
    stacked rows, padded to class dims) ride byte-capped flat buffers;
    ``savings`` is relative to shipping the same rows dense.

    Every entry carries ``raw_bytes`` (the payload at its transport
    dtype — the PROMOTED chunk dtype for bucketed buffers, not a blanket
    factor-dtype assumption) and ``wire_bytes`` (what crosses the
    interconnect): the transport ships the payload as it is, so the two
    are equal, and ``bytes`` equals both.
    """
    cfg = engine.config
    item = _itemsize(cfg.factor_dtype)
    bucketed = cfg.allreduce_method == enums.AllreduceMethod.ALLREDUCE_BUCKETED
    if not bucketed:
        dense = sum(
            d * d
            for store in (engine.a_store, engine.g_store)
            for sb in store
            for d in sb.dims
        ) * item
        return {
            'method': 'ALLREDUCE',
            'collectives': sum(
                len(sb.layers)
                for store in (engine.a_store, engine.g_store)
                for sb in store
            ),
            'bytes': dense,
            'raw_bytes': dense,
            'wire_bytes': dense,
            'wire_dtype': str(jnp.dtype(cfg.factor_dtype)),
            'dense_bytes': dense,
            'savings': 0.0,
            'chunks': [],
        }
    # same row order as _stack_stats' flat_rows: all A rows, then all G
    specs = [
        (sb.d * (sb.d + 1) // 2, jnp.dtype(cfg.factor_dtype))
        for store in (engine.a_store, engine.g_store)
        for sb in store
        for _ in sb.layers
    ]
    from kfac_tpu.parallel import collectives

    cap = cfg.allreduce_bucket_cap_mb
    chunks = [
        dict(
            c, raw_bytes=c['bytes'], wire_bytes=c['bytes'],
            wire_dtype=c['dtype'],
        )
        for c in collectives.plan_chunks(
            specs, max_bytes=None if cap is None else cap * 1e6)
    ]
    wire = sum(c['bytes'] for c in chunks)
    wire_dtypes = sorted({str(c['dtype']) for c in chunks})
    dense = sum(
        sb.d * sb.d * len(sb.layers) * item
        for store in (engine.a_store, engine.g_store)
        for sb in store
    )
    return {
        'method': 'ALLREDUCE_BUCKETED',
        'collectives': len(chunks),
        'bytes': wire,
        'raw_bytes': wire,
        'wire_bytes': wire,
        'wire_dtype': '|'.join(wire_dtypes) if wire_dtypes else str(
            jnp.dtype(cfg.factor_dtype)),
        'dense_bytes': dense,
        'savings': 1.0 - wire / dense if dense else 0.0,
        'chunks': chunks,
    }


def grad_broadcast_bytes(engine: Any) -> int:
    """Bytes of the per-step KAISA gradient broadcast.

    The preconditioned gradient stacks — one ``(padded, dg, da)`` buffer
    per pair bucket at ``inv_dtype`` — are resharded from the strategy's
    column layout to replicated after preconditioning. Under COMM-OPT
    the stacks are already replicated and the constraint is free; the
    returned figure is the stack payload the broadcast covers either way.
    """
    item = _itemsize(engine.config.inv_dtype)
    return sum(b.padded * b.dg * b.da * item for b in engine.buckets)


def decomp_reshard_bytes(engine: Any) -> int:
    """Bytes of the inverse-refresh reshard (the KAISA inverse broadcast).

    Eigh/inverse outputs are computed factor-sharded over the whole mesh
    and resharded to the strategy's resident layout: the full
    decomposition payload — eigenvector stacks + eigenvalue vectors
    (EIGEN), fused eigenvalue grids (prediv), or inverse stacks
    (INVERSE) — at ``inv_dtype``, per ``inv_update_steps`` occurrence;
    with the inverse stacks of a Newton-Schulz solve, its counters.
    """
    item = _itemsize(engine.config.inv_dtype)
    total = 0
    if getattr(engine, '_prediv', False):
        for store in (engine.a_store, engine.g_store):
            for sb in store:
                total += sb.padded * sb.d * sb.d * item  # qa/qg
        for b in engine.buckets:
            total += b.padded * b.dg * b.da * item  # dgda
    elif engine._eigen:
        for store in (engine.a_store, engine.g_store):
            for sb in store:
                total += sb.padded * sb.d * sb.d * item  # qa/qg
                total += sb.padded * sb.d * item  # da/dg
    else:
        for store in (engine.a_store, engine.g_store):
            for sb in store:
                total += sb.padded * sb.d * sb.d * item  # a_inv/g_inv
        if engine._ns_refresh:
            # the Newton-Schulz solve's counters ride along, replicated:
            # DistKFACState.refresh, a float32 row a slot
            from kfac_tpu.parallel import kaisa

            total += 4 * len(kaisa.REFRESH_COLUMNS) * sum(
                sb.padded for sb in engine.a_store + engine.g_store
            )
    return total


def comms_summary(engine: Any) -> dict[str, Any]:
    """Full comms/padding accounting for a ``DistributedKFAC`` engine.

    The host-side counterpart of the in-jit metrics: everything here is
    static per configuration. ``engine.comms_report()`` is the public
    entry point; the autotuner's mesh-less ``StaticLayout``
    (kfac_tpu/autotune/model.py) satisfies the same attribute surface —
    carrying ``n_cols`` directly instead of a mesh — so the cost model
    and the engine share this one byte-accounting implementation.
    """
    mesh = getattr(engine, 'mesh', None)
    if mesh is not None:
        from kfac_tpu.parallel import mesh as mesh_lib

        n_cols = mesh_lib.n_cols(mesh)
    else:
        n_cols = int(engine.n_cols)

    padding = padding_report(engine)
    return {
        'strategy': engine.strategy.name,
        'grad_worker_fraction': engine.grad_workers / engine.world,
        'devices': engine.total_devices,
        'grad_workers': engine.grad_workers,
        'n_cols': n_cols,
        'stat_transport': transport_report(engine),
        'grad_broadcast_bytes': grad_broadcast_bytes(engine),
        'decomp_reshard_bytes': decomp_reshard_bytes(engine),
        'padding': padding,
        'padding_totals': {
            'resident_bytes': sum(
                p['resident_bytes'] for p in padding.values()),
            'identity_pad_bytes': sum(
                p['identity_pad_bytes'] for p in padding.values()),
            'slot_pad_bytes': sum(
                p['slot_pad_bytes'] for p in padding.values()),
        },
    }
