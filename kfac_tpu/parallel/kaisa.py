"""KAISA distributed execution: sharded second-order work on a device mesh.

The reference expresses KAISA imperatively — per-rank ``if rank ==
inv_worker`` branches, explicit broadcasts, NCCL groups
(kfac/base_preconditioner.py:310-382, kfac/assignment.py:121-225). That
shape is anti-SPMD: under XLA every device runs one traced program. Here the
same strategy space is expressed as *data layout*:

- Per-layer factors are stacked into shape buckets ``(L, d, d)`` — batched
  eigh and batched preconditioning keep the MXU busy instead of launching
  per-layer kernels.
- The stacked layer axis is sharded over the whole mesh for the
  eigendecomposition (every device decomposes its assigned slice — the
  greedy assignment's load balance, kfac/assignment.py:227-319, degenerates
  to round-robin because bucket entries are shape-uniform).
- Decompositions are then resharded to the strategy's resident layout:
  replicated for COMM-OPT (the "inverse broadcast"), sharded over the column
  axis for HYBRID/MEM-OPT. Preconditioned gradients are computed under that
  layout: sharded by column, gradient stacks laid out like the
  decompositions are multiplied batched and resharded to replicated (the
  "gradient broadcast"); replicated, every device holds every inverse, so
  with explicit inverses each layer multiplies its own gradient against its
  two inverse slots, a Dense kernel as it lies (same-shaped neighbours of a
  bucket joined as they lie for one batched product), and no padded,
  transposed stack is built or sliced. XLA inserts exactly the all-gathers
  KAISA prescribes; grad_worker_fraction is the mesh aspect ratio
  (kfac_tpu/assignment.py:mesh_shape).

Memory matches the strategy: MEM-OPT keeps 1/world of the second-order state
per device, COMM-OPT replicates it — the same trade the gradient worker
fraction buys in the reference (kfac/enums.py:40-54).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from kfac_tpu import assignment as assignment_lib
from kfac_tpu import enums
from kfac_tpu import health as health_lib
from kfac_tpu import tracing
from kfac_tpu.async_inverse import host as async_host
from kfac_tpu.async_inverse import sliced as async_sliced
from kfac_tpu.async_inverse import slots as async_slots
from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.layers import helpers as helpers_lib
from kfac_tpu.layers import registry as registry_lib
from kfac_tpu.observability import comms as comms_lib
from kfac_tpu.observability import compile_watch as compile_watch_lib
from kfac_tpu.observability import flight_recorder as flight_lib
from kfac_tpu.observability import metrics as metrics_lib
from kfac_tpu.ops import factors as factors_lib
from kfac_tpu.parallel import collectives
from kfac_tpu.parallel import mesh as mesh_lib
from kfac_tpu.preconditioner import (
    KFACPreconditioner,
    _resolve,
    finish_precondition,
)


def size_class(d: int, granularity: int) -> int:
    """Round a factor dimension up to its size class.

    Execution-side load balancing for heterogeneous factor shapes: a
    ResNet-50 has dozens of distinct conv factor dims, often 1-2 layers
    each; bucketing by EXACT dims turns the inverse update into dozens of
    sequential mostly-padding batched decompositions. Rounding dims into a
    few classes collapses them so one batched decomposition spans layers of
    different true sizes — the role the reference's greedy cost-model
    assignment plays (kfac/assignment.py:227-319), solved shape-side for
    XLA's static-shape world. Padding is mathematically exact: factors pad
    with an identity block (decoupled eigenspace), gradients with zeros
    (see ``pad_factor``/``pad_grad``).

    ``granularity <= 1`` disables classing (exact dims). Dims below the
    granularity round to the next power of two (>= 8), capped at the
    granularity, so tiny layers don't pay a full-class decomposition (the
    cap matters for non-power-of-two granularities, where the next power
    of two could overshoot the class a d >= granularity dim would get);
    larger dims round to the next multiple of the granularity (MXU-tile
    friendly).
    """
    if granularity <= 1 or d == 0:
        return d
    if d >= granularity:
        return -(-d // granularity) * granularity
    c = 8
    while c < d:
        c *= 2
    return min(c, granularity)


def pad_factor(m: jax.Array, c: int) -> jax.Array:
    """Embed a (d, d) factor into its (c, c) class slot, identity block in
    the padding. blockdiag(A, I) has a decoupled unit eigenspace, and the
    matching gradient rows/cols are zero, so eigen/inverse preconditioning
    of the real block is unchanged (basis-invariance of matrix functions)."""
    d = m.shape[0]
    if d == c:
        return m
    out = jnp.zeros((c, c), m.dtype).at[:d, :d].set(m)
    idx = jnp.arange(d, c)
    return out.at[idx, idx].set(jnp.ones((c - d,), m.dtype))


def pad_grad(m: jax.Array, cg: int, ca: int) -> jax.Array:
    """Zero-pad a (dg, da) gradient matrix into its (cg, ca) class slot."""
    if m.shape == (cg, ca):
        return m
    return jnp.zeros((cg, ca), m.dtype).at[: m.shape[0], : m.shape[1]].set(m)


class Bucket(NamedTuple):
    """Layers sharing factor size classes, stacked along a leading slot
    axis. ``da``/``dg`` are CLASS dims; ``dims`` carries each layer's true
    (da, dg) for grad embedding/extraction."""

    key: str
    layers: tuple[str, ...]
    da: int
    dg: int
    padded: int  # slots incl. padding to a multiple of world size
    dims: tuple[tuple[int, int], ...]


def build_buckets(
    registry: registry_lib.Registry, world: int, granularity: int = 128,
    a_groups: dict[str, str] | None = None,
) -> list[Bucket]:
    """Group registered layers by (A class, G class), pad to the world
    size.

    A bucket's layers lie in registration order; with ``a_groups`` (member
    -> leader, :func:`stored_a_groups`) the leaders and the layers in no
    group come first, those with the most followers ahead, and then the
    followers, the first of every group, then the second, each in its
    leader's order. A follower reads its leader's A slot, and so the
    followers of one rank read consecutive A slots from consecutive G
    slots: one batched product (``DistributedKFAC._resident_views``'s
    runs) and not one a layer. With no group the order is the
    registration's.
    """
    a_groups = a_groups or {}
    members = registry_lib.group_members(a_groups, registry.layers)
    index = {name: i for i, name in enumerate(registry.layers)}

    def order(name):
        group = members.get(a_groups.get(name), (name,))
        return (group.index(name), -len(group), index[group[0]])

    groups: dict[tuple[int, int], list[tuple[str, int, int]]] = {}
    for name in sorted(registry.layers, key=order):
        h = registry.layers[name]
        da, dg = h.a_factor_shape[0], h.g_factor_shape[0]
        key = (size_class(da, granularity), size_class(dg, granularity))
        groups.setdefault(key, []).append((name, da, dg))
    buckets = []
    for (ca, cg), rows in sorted(groups.items()):
        n = len(rows)
        padded = -(-n // world) * world
        buckets.append(
            Bucket(
                key=f'{ca}x{cg}',
                layers=tuple(r[0] for r in rows),
                da=ca,
                dg=cg,
                padded=padded,
                dims=tuple((r[1], r[2]) for r in rows),
            )
        )
    return buckets


class StorageBucket(NamedTuple):
    """One side's (A or G) factor storage: layers stacked along slots.

    With ``colocate_factors=True`` these mirror the (da, dg) pair buckets,
    so a layer's A and G share a slot index (same owning device). With
    ``False`` each side groups by its own dimension only — A and G of one
    layer can land in different stacks/slots, splitting its two
    eigendecompositions across devices (reference
    kfac/assignment.py:268-304).
    """

    key: str
    layers: tuple[str, ...]
    d: int  # class dim
    padded: int
    dims: tuple[int, ...]  # true per-layer dims


def build_side_buckets(
    registry: registry_lib.Registry,
    world: int,
    side: str,
    granularity: int = 128,
    a_groups: dict[str, str] | None = None,
) -> list[StorageBucket]:
    """Group layers by a single factor size class (non-colocated
    storage); on the A side the followers of ``a_groups`` have no slot."""
    groups: dict[int, list[tuple[str, int]]] = {}
    for name, h in registry.layers.items():
        if side == 'a' and a_groups and a_groups.get(name, name) != name:
            continue
        d = h.a_factor_shape[0] if side == 'a' else h.g_factor_shape[0]
        groups.setdefault(size_class(d, granularity), []).append((name, d))
    return [
        StorageBucket(
            key=f'{side}{c}',
            layers=tuple(r[0] for r in rows),
            d=c,
            padded=-(-len(rows) // world) * world,
            dims=tuple(r[1] for r in rows),
        )
        for c, rows in sorted(groups.items())
    ]


def build_stores(
    registry: registry_lib.Registry,
    total_devices: int,
    granularity: int,
    colocate: bool,
    buckets: list[Bucket],
    a_groups: dict[str, str] | None = None,
) -> tuple[list[StorageBucket], list[StorageBucket]]:
    """Factor STORAGE layout (A store, G store) for a configuration.

    ``a_groups`` (member -> leader, :func:`stored_a_groups`): the A store
    is built over the leaders alone: a follower has its G slot and reads
    its leader's A slot, wherever that lies. With no follower the layout
    is the one below to the last slot.

    Colocated stores mirror the (da, dg) pair buckets (A and G share a
    slot/device); non-colocated stores bucket each side by its own
    dimension so a layer's two eigendecompositions can run on different
    devices (reference kfac/assignment.py:268-304). Pure host-side shape
    arithmetic — shared by ``DistributedKFAC.__post_init__`` and the
    autotuner's mesh-less ``StaticLayout`` (kfac_tpu/autotune/model.py)
    so the analytic cost model prices exactly the layout the engine
    would build.
    """
    a_groups = a_groups or {}
    if colocate:
        a_store = []
        for b in buckets:
            # a pair bucket's leaders keep the bucket's key and order (a
            # bucket of followers alone has no A stack)
            lead = [
                i for i, n in enumerate(b.layers)
                if a_groups.get(n, n) == n
            ]
            if not lead:
                continue
            a_store.append(StorageBucket(
                b.key, tuple(b.layers[i] for i in lead), b.da,
                -(-len(lead) // total_devices) * total_devices,
                tuple(b.dims[i][0] for i in lead),
            ))
        g_store = [
            StorageBucket(
                b.key, b.layers, b.dg, b.padded,
                tuple(d[1] for d in b.dims),
            )
            for b in buckets
        ]
        return a_store, g_store
    return (
        build_side_buckets(
            registry, total_devices, 'a', granularity, a_groups
        ),
        build_side_buckets(registry, total_devices, 'g', granularity),
    )


def stored_a_groups(config: KFACPreconditioner) -> dict[str, str]:
    """The A groups (``Registry.a_groups``) the stacked engine keeps one A
    slot for under ``config``: the dense engine's own (none under an async
    refresh), and none where the eigenvalues are pre-divided, whose fused
    grid lies slot by slot beside a pair bucket's own A stack."""
    if (
        config.compute_method == enums.ComputeMethod.EIGEN
        and config.prediv_eigenvalues
    ):
        return {}
    return dict(config.a_groups)


class DistKFACState(NamedTuple):
    """Stacked K-FAC state: bucket key -> (L, d, d) arrays.

    ``inv_damping`` records the damping the RESIDENT decompositions were
    built with (schedules resolve per step, so it can differ from the
    current step's damping) — consumed by
    :meth:`DistributedKFAC.inverse_residuals` so quality monitoring
    measures the inverse against the system it actually solved. Derived
    state: recomputed with the decompositions, never checkpointed.

    ``health``: :class:`kfac_tpu.health.HealthState` counters when the
    numerical-health sentinel is enabled, else ``None``. Per-layer scalars
    (replicated — layout-independent, so the same counters ride the dense
    and stacked states and survive cross-layout checkpoint migration).

    ``metrics``: :class:`kfac_tpu.observability.MetricsState` per-layer
    telemetry when metrics are enabled, else ``None``. Like ``health``,
    layer-keyed replicated scalars — the same drained schema as the dense
    engine, layout-independent.

    ``flight``: :class:`kfac_tpu.observability.FlightRecorderState`
    rolling telemetry ring when the flight recorder is enabled, else
    ``None``. Replicated (small fixed-size buffers, layout-independent);
    same ephemeral contract as ``metrics``.
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
    inv_damping: jax.Array
    health: Any = None
    metrics: Any = None
    flight: Any = None
    # double-buffered shadow decomposition slots when async_inverse mode
    # 'sliced' is enabled (kfac_tpu/async_inverse); ephemeral like
    # metrics/flight — a restore rematerializes and resets it
    shadow: Any = None
    # what the last capture saw of the routed experts held here, where the
    # registry has stacked expert projections (``Registry.stacks``):
    # float32 :data:`TRAFFIC_COLUMNS`. ``None`` without them. Ephemeral
    # like ``refresh`` below. Read with :meth:`DistributedKFAC.traffic_report`.
    traffic: Any = None
    # what the last Newton-Schulz refresh (``update_inverses``) reported of
    # itself: a :class:`RefreshState`, ONE float32 leaf. ``None`` where no
    # synchronous Newton-Schulz refresh runs (the eigen method, the
    # Cholesky solver, the async refresh modes). Ephemeral like
    # metrics/flight: ``init()`` makes it, no checkpoint holds it. Read
    # with :meth:`DistributedKFAC.refresh_report`.
    refresh: Any = None


# ``DistKFACState.traffic``: live rows of the emptiest held expert and of
# the mean one over every stacked projection's experts at the last capture,
# and the assignments to held experts that a plan left out (must be 0).
TRAFFIC_COLUMNS = ('rows_min', 'rows_mean', 'dropped')

# The most the engine holds of one value of a bucket's stack beside the
# state: a wider stack is solved in equal groups of slots, one after
# another (``_sharded_inv``), so that a Newton-Schulz solve's temporaries
# (several values of the stack) stay a few times this, and its statistics
# are folded into the factors row by row, never stacked
# (``_stack_stats``, ``update_factors``). 512 MiB is above every stack of
# the dense models run so far (12 slots 3,200 wide: 469 MiB) and a third
# of a sparse model's 78 slots 2,048 wide.
SOLVE_GROUP_BYTES = 512 * 2**20


def _solve_groups(slots: int, d: int) -> int:
    """Groups to solve ``slots`` factors ``d`` wide in: the fewest that
    divide them with a group's float32 stack inside
    :data:`SOLVE_GROUP_BYTES`."""
    for groups in range(1, slots + 1):
        if slots % groups == 0 and (
            (slots // groups) * d * d * 4 <= SOLVE_GROUP_BYTES
        ):
            return groups
    return slots


# Columns of ``RefreshState.solved``: each slot's
# ``factors.NewtonSchulzInfo`` without the inverse (``iterations``; final
# ``residual``; ``warm``: the previous inverse was accepted as the start;
# ``restarted``: and then abandoned for the cold start; ``scaled``: the
# iterations of a cold start's scaled phase, a part of ``iterations``;
# ``cold_preferred``: the previous inverse passed the start's test and the
# cold start was taken all the same, being provably no worse).
REFRESH_COLUMNS = (
    'iterations', 'residual', 'warm', 'restarted', 'scaled', 'cold_preferred',
)
_NS_SOLVERS = ('newton_schulz', 'auto')


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=['solved'], meta_fields=['buckets', 'groups'],
)
@dataclasses.dataclass(frozen=True)
class RefreshState:
    """``DistKFACState.refresh``. ``solved``: ``(slots, 6)`` float32, a
    row a slot and :data:`REFRESH_COLUMNS` across, ``iterations`` -1 in
    every row until a refresh has filled it. ``buckets`` is static aux
    data (as ``MetricsState.keys``: the layout travels with the state and
    costs the device nothing): ``(side, key, padded, live)`` for every
    bucket of ``DistributedKFAC.a_store`` and then of ``g_store``, in the
    order of the rows; a bucket's first ``live`` slots hold a layer, the
    rest identity padding. ``groups``: the equal groups of slots each
    bucket's stack is solved in, one after another (:func:`_solve_groups`;
    1 for all but the widest stacks)."""

    buckets: tuple[tuple[str, str, int, int], ...]
    solved: jax.Array
    groups: tuple[int, ...] = ()


def _refresh_by_bucket(refresh: RefreshState) -> list[dict[str, Any]]:
    """A :class:`RefreshState` on the host (one ``device_get``), bucket by
    bucket: ``side``, ``key``, the live slots' ``iterations``,
    ``warm_starts``, ``restarts``, ``cold_preferred`` and
    ``worst_residual``, and the
    ``trips`` its loop ran (the vmapped ``while_loop`` runs every slot of
    a device's block until the slowest is done: the largest
    ``iterations`` of all its slots, summed over the groups a wide stack
    is solved in; identity padding converges in 0 or, warm-started across
    a damping change, in one or two) with ``scaled_trips``, those of them
    that some slot took scaled (a cold start's first phase,
    ``factors.newton_schulz_inverse_info``), formed the same way. Empty
    while no refresh has filled the array."""
    rows = np.asarray(jax.device_get(refresh.solved), np.float64)
    # (the benchmark's hand-made states predate ``scaled`` and
    # ``cold_preferred``: they read 0)
    rows = np.pad(rows, ((0, 0), (0, len(REFRESH_COLUMNS) - rows.shape[1])))
    col = dict(zip(REFRESH_COLUMNS, rows.T))
    if not (col['iterations'] >= 0).any():
        return []
    out, start = [], 0
    groups = refresh.groups or (1,) * len(refresh.buckets)
    for (side, key, padded, live), n in zip(refresh.buckets, groups):
        its = col['iterations'][start:start + live]

        def slowest(column):  # of each group's slots, summed over groups
            return int(sum(
                group.max()
                for group in np.split(col[column][start:start + padded], n)
            ))

        def flagged(column):  # live slots that raised the flag
            return int(col[column][start:start + live].sum())

        out.append({
            'side': side,
            'key': key,
            'iterations': [int(v) for v in its],
            'trips': slowest('iterations'),
            'scaled_trips': slowest('scaled'),
            'warm_starts': flagged('warm'),
            'restarts': flagged('restarted'),
            'cold_preferred': flagged('cold_preferred'),
            # np.max, not max(): a NaN residual has to show
            'worst_residual': float(
                np.max(col['residual'][start:start + live])
            ),
        })
        start += padded
    return out


def refresh_totals(refresh: RefreshState) -> dict[str, float]:
    """Totals of a :class:`RefreshState` (one ``device_get``), as flat
    ``refresh/*`` keys for a metrics record; ``{}`` while no refresh has
    filled it:

    - ``refresh/slots``: slots that hold a layer;
    - ``refresh/iterations``: Newton-Schulz iterations summed over them:
      the useful work;
    - ``refresh/trips``: loop trips the device executed: buckets are
      solved one after another, each until its slowest slot is done, so
      the sum over buckets of the largest ``iterations`` of each;
    - ``refresh/scaled_trips``: those of them in which some slot took a
      scaled step (cold starts' first phase; 0 where every slot started
      warm): the sum over buckets of the largest ``scaled`` of each;
    - ``refresh/warm_starts``, ``refresh/restarts``: slots whose previous
      inverse was accepted as the start, and those of them that were
      restarted cold;
    - ``refresh/cold_preferred``: slots whose previous inverse would have
      been accepted and whose cold start was taken instead, because its
      worst direction was provably no worse (none of them is among
      ``refresh/warm_starts``);
    - ``refresh/worst_residual``: the largest final residual (NaN if any
      slot's is).
    """
    return _refresh_totals(_refresh_by_bucket(refresh))


def _refresh_totals(buckets: list[dict[str, Any]]) -> dict[str, float]:
    if not buckets:
        return {}
    return {
        'refresh/slots': float(sum(len(b['iterations']) for b in buckets)),
        'refresh/iterations': float(
            sum(sum(b['iterations']) for b in buckets)
        ),
        'refresh/trips': float(sum(b['trips'] for b in buckets)),
        'refresh/scaled_trips': float(
            sum(b['scaled_trips'] for b in buckets)
        ),
        'refresh/warm_starts': float(sum(b['warm_starts'] for b in buckets)),
        'refresh/restarts': float(sum(b['restarts'] for b in buckets)),
        'refresh/cold_preferred': float(
            sum(b['cold_preferred'] for b in buckets)
        ),
        'refresh/worst_residual': float(
            np.max([b['worst_residual'] for b in buckets])
        ),
    }


@dataclasses.dataclass
class DistributedKFAC:
    """KAISA preconditioning over a ``kaisa_mesh``.

    Args:
        config: hyperparameter/config carrier (cadences, damping, decay,
            kl_clip, lr, compute_method, dtypes are read from it).
        mesh: mesh from :func:`kfac_tpu.parallel.mesh.kaisa_mesh`; its shape
            encodes the gradient worker fraction. ``None`` builds the
            default COMM-OPT mesh — or the tuned plan's mesh when
            ``auto_layout`` applies.
        auto_layout: a :class:`kfac_tpu.autotune.TunedPlan` (or a path to
            one) from ``tools/kfac_tune.py``. When its topology+model
            fingerprint matches this process, the plan's knobs override
            the config's layout fields and, if no ``mesh`` was given, the
            plan's gradient-worker fraction picks the mesh; on a mismatch
            the plan is ignored with a rate-limited
            :class:`~kfac_tpu.warnings.LayoutPlanWarning`.
    """

    # Entry points the IR analyzer (kfac_tpu/analysis/ir) traces to
    # jaxprs; IR_STEP_PATH marks the per-step critical path (KFL204).
    # Unannotated on purpose: class constants, not dataclass fields.
    IR_ENTRY_POINTS = (
        'update_factors', 'update_inverses', 'precondition', 'step',
    )
    IR_STEP_PATH = ('step',)

    config: KFACPreconditioner
    mesh: Any = None
    auto_layout: Any = None

    def __post_init__(self) -> None:
        if self.auto_layout is not None:
            from kfac_tpu.autotune import plan as plan_lib

            self.config, self.mesh, self.auto_layout_applied = (
                plan_lib.resolve_auto_layout(
                    self.config, self.mesh, self.auto_layout
                )
            )
        else:
            self.auto_layout_applied = False
        if self.mesh is None:
            self.mesh = mesh_lib.kaisa_mesh()
        self.registry = self.config.registry
        # The KAISA strategy grid is the data-parallel mesh portion, but the
        # eigendecomposition work and factor storage shard over EVERY mesh
        # axis — model/seq-parallel devices pull their weight too.
        self.world = mesh_lib.grad_workers(self.mesh) * mesh_lib.n_cols(self.mesh)
        self.grad_workers = mesh_lib.grad_workers(self.mesh)
        self.all_axes = tuple(self.mesh.axis_names)
        self.total_devices = int(self.mesh.devices.size)
        self.strategy = assignment_lib.strategy_for_fraction(
            self.world, self.grad_workers / self.world
        )
        # resolved (never None) by KFACPreconditioner.__post_init__
        self.granularity = int(self.config.bucket_granularity)
        # member -> leader of the A groups stored as one slot
        self.a_groups = stored_a_groups(self.config)
        self.buckets = build_buckets(
            self.registry, self.total_devices, self.granularity,
            self.a_groups,
        )
        self.colocate = bool(self.config.colocate_factors)
        # Parity object: cost-model view of the placement for reporting and
        # for API compatibility with the reference's query surface (also
        # enforces MEM-OPT => colocated, as the reference does).
        self.assignment = assignment_lib.KAISAAssignment(
            assignment_lib.compute_work_costs(self.registry.layers),
            world_size=self.world,
            grad_worker_fraction=self.grad_workers / self.world,
            colocate_factors=self.colocate,
            a_groups=self.a_groups,
        )
        self.a_store, self.g_store = build_stores(
            self.registry, self.total_devices, self.granularity,
            self.colocate, self.buckets, self.a_groups,
        )
        # every layer's A slot: a follower's is its leader's
        self._a_slot = {
            n: (sb.key, i)
            for sb in self.a_store
            for i, n in enumerate(sb.layers)
        }
        self._a_slot = {
            n: self._a_slot[self.a_leader(n)] for n in self.registry.layers
        }
        # pair buckets whose A stack lies slot by slot beside their G
        # stack (colocated, and no layer of theirs follows another's A)
        self._a_aligned = {
            b.key for b in self.buckets
            if self.colocate and all(
                self._a_slot[n] == (b.key, i)
                for i, n in enumerate(b.layers)
            )
        }
        self._g_slot = {
            n: (sb.key, i)
            for sb in self.g_store
            for i, n in enumerate(sb.layers)
        }
        self._eigen = self.config.compute_method == enums.ComputeMethod.EIGEN
        self._prediv = self._eigen and self.config.prediv_eigenvalues
        if self._prediv and not self.colocate:
            raise NotImplementedError(
                'prediv_eigenvalues stores the fused per-layer eigenvalue '
                'grid, which requires colocate_factors=True'
            )
        if self.config.prediv_eigenvalues and not self._eigen:
            import warnings as _warnings

            _warnings.warn(
                'prediv_eigenvalues has no effect with the INVERSE compute '
                'method; ignoring',
                stacklevel=2,
            )
        # Which operand layout ``precondition`` runs in, read off what the
        # engine holds: with explicit inverses resident on every device
        # (COMM-OPT: ``_decomp_spec`` replicated, and so on any one-device
        # mesh) each layer multiplies its own gradient against its inverse
        # slots, a Dense kernel as it lies. Sharded by column, the stack
        # IS the placement; the eigen methods keep it too.
        self._in_layout = (
            not self._eigen and self._decomp_spec() == P()
        )
        # the share of preconditioned gradient elements that are multiplied
        # in their parameter's own layout, packed and unpacked by nobody
        sizes = [
            (helpers_lib.matrix_param_count(h), h.in_layout)
            for h in self.registry.layers.values()
        ]
        self.in_layout_share = sum(
            n for n, own in sizes if own and self._in_layout
        ) / max(1, sum(n for n, _ in sizes))
        # the share of the convolutions wider than 1 x 1 whose A factor is
        # assembled with no patch rows (``None``: no such convolution)
        self.patchless_share = self.config.patchless_share
        # logical bytes of factors and inverses by the part of a block a
        # layer lies under (the second component of its name: 'mixer',
        # 'mlp', 'moe'; a name of one component is its own part): a
        # square a side for the factor and one for its inverse, the A
        # side counted once a group, slot padding not counted
        item = (
            jnp.dtype(self.config.factor_dtype).itemsize
            + jnp.dtype(self.config.inv_dtype).itemsize
        )
        self.state_bytes_by_part: dict[str, int] = {}
        for name, h in self.registry.layers.items():
            part = name.split('/')[:2][-1]
            dims = [h.g_factor_shape[0]]
            if self.a_leader(name) == name:
                dims.append(h.a_factor_shape[0])
            self.state_bytes_by_part[part] = self.state_bytes_by_part.get(
                part, 0
            ) + item * sum(d * d for d in dims)
        # inverse_solver='auto' is served by
        # factors.batched_damped_inverse_auto_info: one scalar runtime cond
        # per device-local block, so the batched Cholesky runs only when some
        # slot's Newton-Schulz residual fails (it used to be a vmapped
        # per-slot cond -> select paying both branches unconditionally,
        # which warranted a TPUPerformanceWarning here).
        self._plan_async()
        # the synchronous refresh reports on itself where it is a
        # Newton-Schulz solve (``DistKFACState.refresh``); the async modes
        # refresh elsewhere (a shadow slice a step, the host's LAPACK) and
        # carry no counters rather than stale ones
        self._ns_refresh = (
            not self._eigen
            and self.config.inverse_solver in _NS_SOLVERS
            and self._async_mode is None
        )

    def _plan_async(self) -> None:
        """Precompute the async refresh plan over the STACKED layout
        (units are storage buckets — one sharded batched decomposition per
        slice — not layers; same attribute surface as the dense engine's
        ``_plan_async``)."""
        acfg = self.config.async_inverse
        self._async_mode = None if acfg is None else acfg.mode
        self._async_worker = None
        self._async_apply_cache = None
        if acfg is None:
            return
        self._async_n_steps = int(self.config.inv_update_steps)
        if acfg.mode == 'sliced':
            units = async_sliced.kaisa_units(self)
            n = min(self._async_n_steps, acfg.max_slices or len(units))
            self._async_slices = async_slots.plan_slices(units, n)
            self._async_n_slices = len(self._async_slices)

    def a_leader(self, name: str) -> str:
        """The layer whose A slot ``name`` reads: its A group's leader
        (:func:`stored_a_groups`), or itself."""
        return self.a_groups.get(name, name)

    # ------------------------------------------------------------ shardings

    def _factor_spec(self) -> P:
        """Factors live sharded over every mesh axis (their only consumer is
        the device that decomposes them)."""
        return P(self.all_axes)

    def _decomp_spec(self) -> P:
        """Resident layout of decompositions: the KAISA strategy knob."""
        if self.strategy == enums.DistributedStrategy.COMM_OPT:
            return P()  # replicated == inverses broadcast to all grad workers
        return P(mesh_lib.COL_AXIS)  # sharded by column == HYBRID/MEM-OPT

    def state_shardings(self) -> Any:
        """NamedSharding pytree for :class:`DistKFACState` (for jit
        in_shardings / donation)."""
        fac = NamedSharding(self.mesh, self._factor_spec())
        dec = NamedSharding(self.mesh, self._decomp_spec())
        rep = NamedSharding(self.mesh, P())

        def adict(sh):
            return {sb.key: sh for sb in self.a_store}

        def gdict(sh):
            return {sb.key: sh for sb in self.g_store}

        eigen = self._eigen
        if self.config.health is not None:
            names = list(self.registry.layers)
            health_sh = health_lib.HealthState(
                skipped_steps=rep,
                damping_mult={n: rep for n in names},
                quarantined={n: rep for n in names},
                bad_inv={n: rep for n in names},
                quarantine_events={n: rep for n in names},
            )
        else:
            health_sh = None
        if self.config.metrics is not None:
            names = tuple(self.registry.layers)
            metrics_sh = metrics_lib.MetricsState(
                names=names,
                keys=tuple(metrics_lib.metric_keys(
                    self.config.metrics, list(names))),
                last_factor_step=rep,
                last_inv_step=rep,
                scalars=rep,
            )
        else:
            metrics_sh = None
        if self.config.flight is not None:
            keys = tuple(metrics_lib.metric_keys(
                self.config.metrics, list(self.registry.layers)))
            flight_sh = flight_lib.FlightRecorderState(
                keys=keys,
                steps=rep,
                loss=rep,
                loss_valid=rep,
                grad_norm=rep,
                scalars=rep,
            )
        else:
            flight_sh = None
        if self._async_mode == 'sliced':
            from kfac_tpu.async_inverse import slots as _slots

            shadow_sh = _slots.ShadowSlots(
                qa=adict(dec) if eigen else {},
                qg=gdict(dec) if eigen else {},
                da=adict(dec) if eigen and not self._prediv else {},
                dg=gdict(dec) if eigen and not self._prediv else {},
                dgda=(
                    {b.key: dec for b in self.buckets}
                    if self._prediv else {}
                ),
                a_inv={} if eigen else adict(dec),
                g_inv={} if eigen else gdict(dec),
                progress=rep,
                damping=rep,
            )
        else:
            shadow_sh = None
        return DistKFACState(
            step=rep,
            a=adict(fac),
            g=gdict(fac),
            qa=adict(dec) if eigen else {},
            qg=gdict(dec) if eigen else {},
            da=adict(dec) if eigen and not self._prediv else {},
            dg=gdict(dec) if eigen and not self._prediv else {},
            dgda={b.key: dec for b in self.buckets} if self._prediv else {},
            a_inv={} if eigen else adict(dec),
            g_inv={} if eigen else gdict(dec),
            inv_damping=rep,
            health=health_sh,
            metrics=metrics_sh,
            flight=flight_sh,
            shadow=shadow_sh,
            refresh=(
                RefreshState(
                    self._refresh_buckets(), rep, self._refresh_groups()
                )
                if self._ns_refresh else None
            ),
            traffic=rep if self.registry.stacks else None,
        )

    # ----------------------------------------------------------------- init

    def init(self) -> DistKFACState:
        """Allocate sharded stacked state (identity factors, zero decomps)."""

        def build() -> DistKFACState:
            cfg = self.config
            a, g, qa, qg, da, dg, dgda, a_inv, g_inv = ({} for _ in range(9))
            for sb in self.a_store:
                a[sb.key] = jnp.broadcast_to(
                    jnp.eye(sb.d, dtype=cfg.factor_dtype),
                    (sb.padded, sb.d, sb.d),
                )
                if self._eigen:
                    qa[sb.key] = jnp.zeros(
                        (sb.padded, sb.d, sb.d), cfg.inv_dtype
                    )
                    if not self._prediv:
                        da[sb.key] = jnp.zeros((sb.padded, sb.d), cfg.inv_dtype)
                else:
                    a_inv[sb.key] = jnp.zeros(
                        (sb.padded, sb.d, sb.d), cfg.inv_dtype
                    )
            for sb in self.g_store:
                g[sb.key] = jnp.broadcast_to(
                    jnp.eye(sb.d, dtype=cfg.factor_dtype),
                    (sb.padded, sb.d, sb.d),
                )
                if self._eigen:
                    qg[sb.key] = jnp.zeros(
                        (sb.padded, sb.d, sb.d), cfg.inv_dtype
                    )
                    if not self._prediv:
                        dg[sb.key] = jnp.zeros((sb.padded, sb.d), cfg.inv_dtype)
                else:
                    g_inv[sb.key] = jnp.zeros(
                        (sb.padded, sb.d, sb.d), cfg.inv_dtype
                    )
            if self._prediv:
                for b in self.buckets:
                    dgda[b.key] = jnp.zeros(
                        (b.padded, b.dg, b.da), cfg.inv_dtype
                    )
            return DistKFACState(
                step=jnp.asarray(0, jnp.int32),
                a=a, g=g, qa=qa, qg=qg, da=da, dg=dg, dgda=dgda,
                a_inv=a_inv, g_inv=g_inv,
                inv_damping=jnp.asarray(
                    _resolve(cfg.damping, jnp.asarray(0, jnp.int32)),
                    jnp.float32,
                ),
                health=(
                    health_lib.init_health(self.registry.layers)
                    if cfg.health is not None else None
                ),
                metrics=(
                    metrics_lib.init_metrics(
                        cfg.metrics, list(self.registry.layers)
                    )
                    if cfg.metrics is not None else None
                ),
                flight=(
                    flight_lib.init_flight(
                        cfg.flight,
                        metrics_lib.metric_keys(
                            cfg.metrics, list(self.registry.layers)
                        ),
                    )
                    if cfg.flight is not None else None
                ),
                refresh=(
                    self._pack_refresh([
                        # iterations -1: no refresh has filled the row
                        jnp.zeros(
                            (sb.padded, len(REFRESH_COLUMNS)), jnp.float32
                        ).at[:, 0].set(-1)
                        for sb in self.a_store + self.g_store
                    ])
                    if self._ns_refresh else None
                ),
                traffic=(
                    jnp.zeros((len(TRAFFIC_COLUMNS),), jnp.float32)
                    if self.registry.stacks else None
                ),
            )

        def build_with_shadow() -> DistKFACState:
            state = build()
            if self._async_mode == 'sliced':
                state = state._replace(
                    shadow=async_sliced.kaisa_shadow(self, state)
                )
            return state

        return jax.jit(
            build_with_shadow, out_shardings=self.state_shardings()
        )()

    # ------------------------------------------------------------- stacking

    def _a_stats(
        self, stats: capture_lib.CapturedStats
    ) -> dict[str, jax.Array]:
        """A capture's A statistics under the A store's layers' names
        (``capture.a_stat``); a layer the capture did not run is absent."""
        found = {
            n: capture_lib.a_stat(stats, self.registry, n)
            for sb in self.a_store for n in sb.layers
        }
        return {n: v for n, v in found.items() if v is not None}

    def _stack_stats(
        self, state: DistKFACState, stats: capture_lib.CapturedStats
    ) -> tuple[dict[str, jax.Array], dict[str, jax.Array]]:
        """Stack per-layer stats into bucket layout.

        Registered layers absent from ``stats`` (not executed by this
        loss_fn) take their current state value, so the EMA leaves them
        unchanged — same semantics as the dense engine
        (kfac_tpu/preconditioner.py:update_factors) and the reference's
        hooks, which simply never fire for unexecuted modules.

        Returns ``(a_stacks, g_stacks)``. A bucket whose float32 stack is
        :data:`SOLVE_GROUP_BYTES` or wider comes back as the list of its
        layers' rows, not stacked.
        """
        cfg = self.config
        bucketed = (
            cfg.allreduce_method == enums.AllreduceMethod.ALLREDUCE_BUCKETED
        )
        # Pin each captured factor to replicated BEFORE stacking: under
        # GSPMD the capture contraction can leave per-layer covariances with
        # inferred shardings over model/seq axes, and concatenating
        # mixed-sharding rows forces XLA's "involuntary full
        # rematerialization" (replicate the whole stack, then re-slice).
        # ALLREDUCE pins (all-gathers) each small (d, d) matrix on its own;
        # ALLREDUCE_BUCKETED packs the upper triangles of every factor into
        # one flat buffer and pins that — one large collective carrying
        # half the bytes (factors are symmetric), the reference's bucketed
        # symmetric transport (kfac/distributed.py:305-374, 422-465) for
        # DCN-bound multihost meshes.
        rep = NamedSharding(self.mesh, P())

        def pin(m):
            return m if bucketed else jax.lax.with_sharding_constraint(m, rep)

        def side_rows(store, side_stats, side_state):
            rows: dict[str, list] = {}
            for sb in store:
                r = []
                for i, n in enumerate(sb.layers):
                    if n in side_stats:
                        # embed the true-dim statistic into its size-class
                        # slot (identity padding — exact, see pad_factor)
                        r.append(
                            pad_factor(
                                pin(
                                    side_stats[n].astype(cfg.factor_dtype)
                                ),
                                sb.d,
                            )
                        )
                    else:
                        # state slices are factor-sharded — pin them too so
                        # the stack never mixes shardings (already
                        # class-size)
                        r.append(pin(side_state[sb.key][i]))
                rows[sb.key] = r
            return rows

        rows_a = side_rows(self.a_store, self._a_stats(stats), state.a)
        rows_g = side_rows(self.g_store, stats.g, state.g)

        if bucketed:
            flat_rows = [
                m for sb in self.a_store for m in rows_a[sb.key]
            ] + [m for sb in self.g_store for m in rows_g[sb.key]]
            tris = [collectives.get_triu(m) for m in flat_rows]
            # byte-capped chunks (reference 25 MB default): bounds the
            # transient pack footprint and the per-collective message size
            cap = cfg.allreduce_bucket_cap_mb
            packed = collectives.concat_flat_chunked(
                tris, max_bytes=None if cap is None else cap * 1e6
            )
            chunks = [
                (jax.lax.with_sharding_constraint(flat, rep), specs)
                for flat, specs in packed
            ]
            unpacked = iter(
                collectives.fill_triu(m.shape, t)
                for m, t in zip(
                    flat_rows, collectives.split_flat_chunked(chunks)
                )
            )
            for sb in self.a_store:  # same order as flat_rows: a then g
                rows_a[sb.key] = [next(unpacked) for _ in rows_a[sb.key]]
            for sb in self.g_store:
                rows_g[sb.key] = [next(unpacked) for _ in rows_g[sb.key]]

        def stack_side(store, rows):
            stacks = {}
            for sb in store:
                r = rows[sb.key]
                if sb.padded * sb.d * sb.d * 4 >= SOLVE_GROUP_BYTES:
                    # a stack too wide to hold whole beside the state and
                    # the statistics themselves: its rows stay apart and
                    # ``update_factors`` folds them into the state in
                    # place, one by one (padding slots keep their identity)
                    stacks[sb.key] = r
                    continue
                pad = sb.padded - len(sb.layers)
                if pad:
                    r = r + [jnp.eye(sb.d, dtype=cfg.factor_dtype)] * pad
                stacks[sb.key] = jnp.stack(r)
            return stacks

        return (
            stack_side(self.a_store, rows_a),
            stack_side(self.g_store, rows_g),
        )

    # --------------------------------------------------------------- health

    def _slot_mults(
        self, health, layers: tuple[str, ...], padded: int
    ) -> jax.Array:
        """(L,) per-slot damping multipliers for a stack's layers (padding
        slots at 1.0). Assembled by update-slice, not jnp.stack: GSPMD
        mispartitions stacks of replicated scalars on fractional
        grad-worker meshes (see the gstack note in ``precondition``)."""
        out = jnp.ones((padded,), jnp.float32)
        for i, n in enumerate(layers):
            out = out.at[i].set(health.damping_mult[n])
        return out

    def _slot_mask(
        self,
        flags: dict[str, jax.Array],
        layers: tuple[str, ...],
        padded: int,
    ) -> jax.Array | None:
        """(L,) bool from per-layer flags; layers without a flag (and
        padding slots) are False. None when no slot carries a flag.
        Update-slice assembly for the same reason as ``_slot_mults``."""
        if not any(n in flags for n in layers):
            return None
        out = jnp.zeros((padded,), bool)
        for i, n in enumerate(layers):
            if n in flags:
                out = out.at[i].set(flags[n])
        return out

    # ------------------------------------------------------- factor updates

    @tracing.scope('dist_kfac.update_factors')
    def update_factors(
        self, state: DistKFACState, stats: capture_lib.CapturedStats
    ) -> DistKFACState:
        """EMA update on the stacked factors (sharded, local per device).

        Statistics arrive already global-batch-averaged (the covariance
        contraction under pjit psums over the data-sharded row axis — the
        reference's explicit factor allreduce, kfac/layers/base.py:282-336).
        """
        alpha = _resolve(self.config.factor_decay, state.step)
        a_stacks, g_stacks = self._stack_stats(state, stats)
        fac = NamedSharding(self.mesh, self._factor_spec())
        # Capture weights (routed MoE layers): per-slot effective decay
        # alpha_eff = 1 - (1-alpha)*w so the EMA moves proportionally to
        # the evidence each layer's capture carried. Slots without a
        # weight (ordinary layers, unexecuted layers — whose stacked stat
        # is their own state value — and size-class padding) use w=1,
        # which reduces exactly to the unweighted update.
        weights = getattr(stats, 'w', None) or {}
        a_stats = self._a_stats(stats)

        def slot_alphas(store_bucket, seen):
            if not any(
                n in weights and n in seen for n in store_bucket.layers
            ):
                return None
            w = [
                weights[n] if (n in weights and n in seen)
                else jnp.float32(1.0)
                for n in store_bucket.layers
            ]
            w += [jnp.float32(1.0)] * (store_bucket.padded - len(w))
            return factors_lib.effective_alpha(alpha, jnp.stack(w))

        def ema(store, side_state, stacks, seen):
            out = {}
            for sb in store:
                av = slot_alphas(sb, seen)
                if isinstance(stacks[sb.key], list):
                    # rows apart (see ``_stack_stats``): a chain of
                    # in-place row updates of the donated stack, each
                    # reading the row it is about to replace
                    new = side_state[sb.key]
                    for i, row in enumerate(stacks[sb.key]):
                        a_i = alpha if av is None else av[i].astype(row.dtype)
                        new = new.at[i].set(a_i * new[i] + (1 - a_i) * row)
                    out[sb.key] = new
                    continue
                s = jax.lax.with_sharding_constraint(stacks[sb.key], fac)
                if av is None:
                    out[sb.key] = alpha * side_state[sb.key] + (1 - alpha) * s
                else:
                    av = av[:, None, None].astype(s.dtype)
                    out[sb.key] = av * side_state[sb.key] + (1 - av) * s
            return out

        new_a = ema(self.a_store, state.a, a_stacks, a_stats)
        new_g = ema(self.g_store, state.g, g_stacks, stats.g)
        updated = set(stats.a) | set(stats.g)
        ok: dict[str, jax.Array] = {}
        new_health = state.health
        if self.config.health is not None:
            # factor quarantine, stacked form: one batched verdict per
            # storage bucket (finite + Gershgorin at each slot's effective
            # damping), combined per LAYER across its A and G slots so both
            # factors roll back together — same semantics as the dense
            # engine's per-layer loop
            # (kfac_tpu/preconditioner.py:update_factors). Layers absent
            # from this capture get no verdict (their stacked stat is their
            # own state value — the EMA left them unchanged).
            hc = self.config.health
            h = state.health
            damping = _resolve(self.config.damping, state.step)

            def verdicts(store, stacks):
                return {
                    sb.key: health_lib.factor_ok(
                        stacks[sb.key],
                        damping * self._slot_mults(h, sb.layers, sb.padded),
                        hc.quarantine_threshold,
                    )
                    for sb in store
                }

            ok_a = verdicts(self.a_store, new_a)
            ok_g = verdicts(self.g_store, new_g)
            for n in self.registry.layers:
                if n not in updated:
                    continue
                ak, ai = self._a_slot[n]
                gk, gi = self._g_slot[n]
                ok[n] = ok_a[ak][ai] & ok_g[gk][gi]
            roll = {n: ~v for n, v in ok.items()}
            # a group's one A slot goes back with any member's rollback
            roll_a: dict[str, jax.Array] = {}
            for n, bad in roll.items():
                la = self.a_leader(n)
                roll_a[la] = roll_a[la] | bad if la in roll_a else bad

            def rollback(store, old, new, roll):
                out = {}
                for sb in store:
                    mask = self._slot_mask(roll, sb.layers, sb.padded)
                    out[sb.key] = (
                        new[sb.key] if mask is None
                        else jnp.where(
                            mask[:, None, None], old[sb.key], new[sb.key]
                        )
                    )
                return out

            mult = dict(h.damping_mult)
            quarantined = dict(h.quarantined)
            events = dict(h.quarantine_events)
            for n, okn in ok.items():
                mult[n], quarantined[n], events[n] = (
                    health_lib.quarantine_update(
                        hc, okn, h.damping_mult[n], h.quarantined[n],
                        h.quarantine_events[n],
                    )
                )
            new_a = rollback(self.a_store, state.a, new_a, roll_a)
            new_g = rollback(self.g_store, state.g, new_g, roll)
            new_health = h._replace(
                damping_mult=mult, quarantined=quarantined,
                quarantine_events=events,
            )
        state = state._replace(a=new_a, g=new_g, health=new_health)
        traffic = getattr(stats, 'traffic', None)
        if traffic and state.traffic is not None:
            seen = [traffic[n] for n in sorted(traffic)]
            rows = jnp.concatenate([t[:-1] for t in seen])
            state = state._replace(traffic=jnp.stack([
                jnp.min(rows), jnp.mean(rows), sum(t[-1] for t in seen),
            ]))
        if self.config.metrics is not None and state.metrics is not None:
            state = state._replace(
                metrics=self._record_factor_metrics(state, updated, ok)
            )
        return state

    def _record_factor_metrics(
        self,
        state: DistKFACState,
        updated: set[str],
        ok_verdicts: dict[str, jax.Array],
    ) -> metrics_lib.MetricsState:
        """Factor-phase telemetry from the post-rollback stacked factors.

        Gershgorin bounds are taken on each layer's TRUE-dim block sliced
        out of its class slot (the identity padding would otherwise clamp
        both bounds toward 1), giving exact value parity with the dense
        engine's per-layer bounds.
        """
        mcfg = self.config.metrics
        ms = state.metrics
        scalars: dict[str, jax.Array] = {}
        touched: dict[str, jax.Array | None] = {}
        for n, helper in self.registry.layers.items():
            if n not in updated:
                continue
            if mcfg.factor_bounds:
                ak, ai = self._a_slot[n]
                gk, gi = self._g_slot[n]
                da = helper.a_factor_shape[0]
                dg = helper.g_factor_shape[0]
                lmin_a, lmax_a = metrics_lib.gershgorin_bounds(
                    state.a[ak][ai, :da, :da])
                lmin_g, lmax_g = metrics_lib.gershgorin_bounds(
                    state.g[gk][gi, :dg, :dg])
                scalars[f'factor_lmin/a/{n}'] = lmin_a
                scalars[f'factor_lmax/a/{n}'] = lmax_a
                scalars[f'factor_lmin/g/{n}'] = lmin_g
                scalars[f'factor_lmax/g/{n}'] = lmax_g
            touched[n] = ok_verdicts.get(n)
        return metrics_lib.update_scalars(ms, scalars)._replace(
            last_factor_step=metrics_lib.advance_last(
                ms.last_factor_step, ms.names, touched, state.step))

    # ------------------------------------------------------------- inverses

    def _sharded_eigh(self, stack: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Batched eigh with the slot axis sharded over the full mesh.

        shard_map guarantees each device decomposes only its slice — the
        SPMD realization of per-rank ``compute_a_inv`` work division
        (reference kfac/base_preconditioner.py:341-343).
        """

        def local(block):
            d, q = factors_lib.batched_eigh(
                block, self.config.eigh_impl
            )
            return q, jnp.clip(d, 0.0)

        spec = P(self.all_axes)
        q, d = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=spec,
            out_specs=(spec, spec),
        )(stack)
        return q, d

    def _sharded_inv(
        self, stack: jax.Array, damping, prev: jax.Array | None = None,
        floor=0.0,
    ) -> tuple[jax.Array, jax.Array | None]:
        """Batched sharded damped inverse; ``prev`` (the resident inverse
        stack) warm-starts Newton-Schulz per slot — safeguarded inside
        the solver, so a fresh state's zero inverses cold-start.
        ``damping`` may be a scalar or a per-slot (L,) vector (per-layer
        escalated damping under factor quarantine) — the vector rides the
        shard_map with the same slot sharding as the stack, and so does
        ``floor`` (``factors.identity_floor``: what a cold Newton-Schulz
        solve may assume under every slot's smallest eigenvalue; identity
        padding has all of them at 1).

        Returns the inverse stack and, of a Newton-Schulz solve, what each
        slot's solve reported: an ``(L, 5)`` float32 array,
        :data:`REFRESH_COLUMNS` across (``None`` under the Cholesky
        solver)."""
        dmp, flr = (
            jnp.broadcast_to(jnp.asarray(v, jnp.float32), stack.shape[:1])
            for v in (damping, floor)
        )
        solver = self.config.inverse_solver
        iters = self.config.newton_schulz_iters

        def local(*blocks):
            groups = _solve_groups(blocks[0].shape[0], blocks[0].shape[-1])
            if groups > 1:
                def split(x):
                    return x.reshape(groups, -1, *x.shape[1:])

                out = jax.lax.map(
                    lambda t: solve(*t), tuple(split(b) for b in blocks)
                )
                return jax.tree_util.tree_map(
                    lambda x: x.reshape(-1, *x.shape[2:]), out
                )
            return solve(*blocks)

        def solve(block, prev_block, dmp_block, flr_block):
            if solver == 'auto':
                # one scalar cond per device-local block: Cholesky runs
                # at runtime only when some slot's NS residual fails —
                # not the vmapped per-slot cond that lowers to a
                # pay-both-branches select
                info = factors_lib.batched_damped_inverse_auto_info(
                    block, dmp_block, jnp.float32, iters, x0=prev_block,
                    floor=flr_block,
                )
            elif solver == 'newton_schulz':
                info = jax.vmap(
                    lambda m, w, dm, fl: (
                        factors_lib.newton_schulz_inverse_info(
                            m, dm, jnp.float32, max_iters=iters, x0=w,
                            floor=fl,
                        )
                    )
                )(block, prev_block, dmp_block, flr_block)
            else:
                return jax.vmap(
                    lambda m, w, dm: factors_lib.damped_inverse(
                        m, dm, jnp.float32, solver, iters, x0=w,
                    )
                )(block, prev_block, dmp_block)
            return info.inverse, jnp.stack(
                [
                    getattr(info, c).astype(jnp.float32)
                    for c in REFRESH_COLUMNS
                ],
                axis=-1,
            )

        if prev is None:
            prev = jnp.zeros_like(stack)
        spec = P(self.all_axes)
        # prev stays in its own dtype (inv_dtype, typically f32): casting
        # to a bf16 factor dtype would inflate the warm residual by
        # eps_bf16 * kappa and reject the warm start exactly in the
        # high-kappa regime where it saves the most
        out = jax.shard_map(
            local, mesh=self.mesh, in_specs=(spec,) * 4,
            out_specs=(spec, spec) if solver in _NS_SOLVERS else spec,
        )(stack, prev, dmp, flr)
        return out if solver in _NS_SOLVERS else (out, None)

    @tracing.scope('dist_kfac.update_inverses')
    def update_inverses(self, state: DistKFACState) -> DistKFACState:
        cfg = self.config
        hc = cfg.health
        h = state.health
        damping = _resolve(cfg.damping, state.step)
        dec = NamedSharding(self.mesh, self._decomp_spec())
        # per-slot verdicts on this refresh's outputs, per storage bucket —
        # combined per layer below into the degradation counter
        ok_a_slots: dict[str, jax.Array] = {}
        ok_g_slots: dict[str, jax.Array] = {}
        ok_fused: dict[str, jax.Array] = {}

        def slot_damping(layers, padded):
            if hc is None:
                return damping
            return damping * self._slot_mults(h, layers, padded)

        if self._eigen:
            qa, qg, da, dg, dgda = {}, {}, {}, {}, {}
            # Reshard to the strategy's resident layout: XLA inserts the
            # KAISA inverse "broadcast" (all-gather over gw, or over the
            # world for COMM-OPT) at these constraints. With
            # colocate_factors=False the A and G loops run over different
            # stacks — a layer's two eigendecompositions land on whichever
            # devices own their side's slots.
            d_a_by_key, d_g_by_key = {}, {}

            def side(store, side_state, prev_q, prev_d, q_out, d_out,
                     d_by_key, ok_slots):
                for sb in store:
                    q_, d_ = self._sharded_eigh(side_state[sb.key])
                    qc = q_.astype(cfg.inv_dtype)
                    if hc is not None:
                        okv = jnp.isfinite(q_).all(axis=(-2, -1)) & jnp.isfinite(
                            d_
                        ).all(axis=-1)
                        ok_slots[sb.key] = okv
                        # non-finite decomposition: keep the previous one
                        qc = jnp.where(okv[:, None, None], qc, prev_q[sb.key])
                    q_out[sb.key] = jax.lax.with_sharding_constraint(qc, dec)
                    d_by_key[sb.key] = d_
                    if not self._prediv:
                        dc = d_.astype(cfg.inv_dtype)
                        if hc is not None:
                            dc = jnp.where(
                                ok_slots[sb.key][:, None], dc, prev_d[sb.key]
                            )
                        d_out[sb.key] = jax.lax.with_sharding_constraint(
                            dc, dec
                        )

            side(self.a_store, state.a, state.qa, state.da, qa, da,
                 d_a_by_key, ok_a_slots)
            side(self.g_store, state.g, state.qg, state.dg, qg, dg,
                 d_g_by_key, ok_g_slots)
            if self._prediv:
                # colocate-only (enforced in __post_init__): side keys are
                # the pair-bucket keys, so eigenvalue stacks align by slot
                for b in self.buckets:
                    fused = jax.vmap(
                        lambda da_, dg_, dm: factors_lib.prediv_eigenvalues(
                            factors_lib.EigenDecomp(q=None, d=da_),
                            factors_lib.EigenDecomp(q=None, d=dg_),
                            dm,
                        )
                    )(
                        d_a_by_key[b.key], d_g_by_key[b.key],
                        jnp.broadcast_to(
                            jnp.asarray(
                                slot_damping(b.layers, b.padded), jnp.float32
                            ),
                            (b.padded,),
                        ),
                    )
                    fc = fused.astype(cfg.inv_dtype)
                    if hc is not None:
                        okv = jnp.isfinite(fused).all(axis=(-2, -1))
                        ok_fused[b.key] = okv
                        fc = jnp.where(
                            okv[:, None, None], fc, state.dgda[b.key]
                        )
                    dgda[b.key] = jax.lax.with_sharding_constraint(fc, dec)
            state = state._replace(
                qa=qa, qg=qg, da=da, dg=dg, dgda=dgda,
                inv_damping=jnp.asarray(damping, jnp.float32),
            )
        else:
            a_inv, g_inv = {}, {}
            solved = []  # per bucket, A store then G store: (L, 5)
            # what is left of the identity the factors started from: a
            # cold Newton-Schulz solve's lower bound (the stores' padding
            # slots are identity itself)
            floor = factors_lib.identity_floor(
                state.step, cfg.factor_decay, cfg.factor_update_steps
            )

            def side(store, side_state, prev, out, ok_slots):
                for sb in store:
                    cand, told = self._sharded_inv(
                        side_state[sb.key],
                        slot_damping(sb.layers, sb.padded),
                        prev=prev[sb.key], floor=floor,
                    )
                    cand = cand.astype(cfg.inv_dtype)
                    solved.append(told)
                    if hc is not None:
                        okv = jnp.isfinite(cand).all(axis=(-2, -1))
                        ok_slots[sb.key] = okv
                        cand = jnp.where(
                            okv[:, None, None], cand, prev[sb.key]
                        )
                    out[sb.key] = jax.lax.with_sharding_constraint(cand, dec)

            side(self.a_store, state.a, state.a_inv, a_inv, ok_a_slots)
            side(self.g_store, state.g, state.g_inv, g_inv, ok_g_slots)
            state = state._replace(
                a_inv=a_inv, g_inv=g_inv,
                inv_damping=jnp.asarray(damping, jnp.float32),
            )
            if self._ns_refresh:
                state = state._replace(refresh=self._pack_refresh(solved))
        ok_layer: dict[str, jax.Array] = {}
        if hc is not None:
            # degradation counter: a refresh is quarantined when it ran
            # from a quarantined (rolled-back) factor or produced a
            # non-finite output on either side
            bad_inv = {}
            for n in self.registry.layers:
                ak, ai = self._a_slot[n]
                gk, gi = self._g_slot[n]
                okn = ok_a_slots[ak][ai] & ok_g_slots[gk][gi]
                if self._prediv:
                    okn = okn & ok_fused[ak][ai]
                ok_layer[n] = okn
                bad_inv[n] = health_lib.inversion_update(
                    hc, okn, h.quarantined[n], h.bad_inv[n]
                )
            state = state._replace(health=h._replace(bad_inv=bad_inv))
        if cfg.metrics is not None and state.metrics is not None:
            ms = state.metrics
            touched = {n: ok_layer.get(n) for n in self.registry.layers}
            state = state._replace(metrics=ms._replace(
                last_inv_step=metrics_lib.advance_last(
                    ms.last_inv_step, ms.names, touched, state.step)))
        return state

    def _refresh_buckets(self) -> tuple[tuple[str, str, int, int], ...]:
        """``RefreshState.buckets``: the A store's then the G store's."""
        return tuple(
            (side, sb.key, sb.padded, len(sb.layers))
            for side, store in (('a', self.a_store), ('g', self.g_store))
            for sb in store
        )

    def _refresh_groups(self) -> tuple[int, ...]:
        """``RefreshState.groups``: the groups ``_sharded_inv`` solves a
        device's block of each bucket in, in ``_refresh_buckets``' order."""
        return tuple(
            _solve_groups(sb.padded // self.total_devices, sb.d)
            for sb in self.a_store + self.g_store
        )

    def _pack_refresh(self, solved: list[jax.Array]) -> RefreshState:
        """``DistKFACState.refresh`` from what ``_sharded_inv`` returned
        for every bucket of the A store and then of the G store."""
        return RefreshState(
            self._refresh_buckets(),
            jax.lax.with_sharding_constraint(
                jnp.concatenate(solved), NamedSharding(self.mesh, P())
            ),
            self._refresh_groups(),
        )

    def refresh_report(self, state: DistKFACState) -> dict[str, Any]:
        """What the last inverse refresh reported of itself, on the host
        (a few KB off the device; the solve computes it anyway).

        ``{'buckets': {'a': {key: {...}}, 'g': {...}}, 'totals': {...}}``.
        Per bucket: ``iterations`` of each layer's slot (in the order of
        the store's ``layers``); ``trips``, the loop trips the device
        executed (the vmapped ``while_loop`` runs every slot of a block
        until its slowest is done); ``warm_starts`` accepted and
        ``restarts`` among them; ``cold_preferred``, the previous inverses
        set aside for a cold start that was provably no worse;
        ``worst_residual``. ``totals``:
        :func:`refresh_totals` without the ``refresh/`` prefix. ``{}``
        where the state carries no counters (``DistKFACState.refresh``)
        and until the first refresh has filled them.
        """
        if state.refresh is None:
            return {}
        by_bucket = _refresh_by_bucket(state.refresh)
        if not by_bucket:
            return {}
        totals = _refresh_totals(by_bucket)
        buckets: dict[str, dict[str, Any]] = {'a': {}, 'g': {}}
        for got in by_bucket:
            buckets[got.pop('side')][got.pop('key')] = got
        return {
            'buckets': buckets,
            'totals': {k.split('/', 1)[1]: v for k, v in totals.items()},
        }

    def traffic_report(self, state: DistKFACState) -> dict[str, float]:
        """:data:`TRAFFIC_COLUMNS` of the last capture, on the host (one
        ``device_get``); ``{}`` for a registry without stacked expert
        projections."""
        if getattr(state, 'traffic', None) is None:
            return {}
        return dict(zip(
            TRAFFIC_COLUMNS,
            (float(v) for v in jax.device_get(state.traffic)),
        ))

    def inverse_residuals(
        self, state: DistKFACState
    ) -> dict[str, dict[str, jax.Array]]:
        """Per-slot relative identity residuals of the CURRENT damped
        inverses: ``||I - (F + damping*I) F_inv||_F / sqrt(d)``.

        Out-of-band quality monitoring for the stacked INVERSE engine:
        an independent recomputation (the solve's own final residuals
        are in :meth:`refresh_report`, for free), so callers sample this
        between steps (e.g. each ``inv_update_steps``) and alert on
        values above :data:`kfac_tpu.ops.factors.NS_FALLBACK_RESIDUAL`.
        (``'auto'`` already self-corrects in-band: its single scalar
        runtime cond — ``factors.batched_damped_inverse_auto_info`` — swaps
        failed slots to the Cholesky inverse at build time.)
        Identity-padded slots report ~0. Returns
        ``{'a': {bucket_key: (L,)}, 'g': {...}}``; jit-friendly.
        """
        if self._eigen:
            raise ValueError(
                'inverse_residuals applies to the INVERSE compute method; '
                'the EIGEN path reconstructs from eigendecompositions '
                'whose quality is a property of eigh, not an iteration'
            )
        # the damping the resident inverses were BUILT with — a scheduled
        # damping resolved at the current step would add a spurious
        # |delta_damping| * ||F_inv|| floor to a perfect inverse
        damping = state.inv_damping

        def residuals(f, finv):
            d = f.shape[-1]
            eye = jnp.eye(d, dtype=jnp.float32)
            m = f.astype(jnp.float32) + damping * eye
            # f32 products: at a TPU's default (bf16) matmul precision
            # the monitor's own rounding is ~kappa * 2^-9 and would
            # drown the residual it reports
            r = eye - jnp.einsum(
                'lij,ljk->lik', m, finv.astype(jnp.float32),
                precision=factors_lib.NS_PRECISION,
            )
            return jnp.sqrt(jnp.sum(r * r, axis=(-2, -1)) / d)

        return {
            'a': {
                sb.key: residuals(state.a[sb.key], state.a_inv[sb.key])
                for sb in self.a_store
            },
            'g': {
                sb.key: residuals(state.g[sb.key], state.g_inv[sb.key])
                for sb in self.g_store
            },
        }

    # --------------------------------------------------------- precondition

    @tracing.scope('dist_kfac.precondition')
    def precondition(
        self,
        state: DistKFACState,
        grads: Any,
        metrics_out: dict[str, jax.Array] | None = None,
    ) -> Any:
        """Precondition a params-shaped grad pytree.

        One algorithm, two operand layouts, chosen by what the engine
        holds (``_in_layout``). Where every device holds every inverse
        (COMM-OPT), each layer multiplies its own gradient against its two
        inverse slots, in the layout it has, and no padded stack is built
        (:meth:`_resident_views`).
        Where the decompositions are sharded by column (HYBRID/MEM-OPT)
        the gradients are stacked like them, so each column preconditions
        only its layers (its devices are the layer's "grad workers"), and
        the final replication constraint is the KAISA gradient broadcast
        (:meth:`_stacked_views`; reference kfac/layers/base.py:224-252).

        ``metrics_out``, when given, collects this phase's telemetry
        scalars at the replicated per-layer true-dim level (the same
        place degradation/KL run — stack-level reductions would hit the
        GSPMD partial-sum hazard described in :meth:`_stacked_views`);
        ``step`` merges them into ``state.metrics``.
        """
        layer_grads = registry_lib.slice_layer_grads(grads, self.registry)
        views = (
            self._resident_views if self._in_layout else self._stacked_views
        )(state, layer_grads)
        out = finish_precondition(self.config, state, views, metrics_out)
        return registry_lib.merge_layer_grads(grads, out, self.registry)

    def _resident_views(
        self, state: DistKFACState, layer_grads: dict[str, Any]
    ) -> dict[str, tuple[dict[str, jax.Array], dict[str, jax.Array]]]:
        """Each layer's gradient and preconditioned gradient from the
        inverses as they are resident on every device: the layer's own
        view (``LayerHelper.grad_view``: a Dense kernel as it lies, a
        convolution's packed matrix) against its A and G slots at their
        true dims. A class slot's padding rows and columns meet only the
        stacked gradient's zero padding, so the true-dims block gives the
        stack path's number.

        Layers of one bucket that follow each other in its slots with the
        same view (helper type, shapes, true dims) are a *run*: one
        batched product of their views, joined along a new leading axis
        in the layout they have, against that slice of the inverse
        stacks. The numbers are the per-layer products'; the program is
        not: the TPU compiler emits each product's code anew, ~1 MB a
        layer at LFM2's widths (offline compile, PR 34), which a run
        shares.

        The gradient view handed on for ``finish_precondition``'s
        reductions is held as a value of its own
        (``optimization_barrier``), the product's operand is not: the
        backward pass then writes each leaf once in its own dtype beside
        its slot of the run's stack, as it did when a kernel read the
        leaf. Left free, the TPU compiler carries two bfloat16 copies of
        every gradient instead, holds less across the refresh
        conditional, and plans that branch's scan buffers 0.43 GB wider
        (LFM2's capture program, offline compile, PR 37)."""
        rep = NamedSharding(self.mesh, P())
        # ((form, first A slot, first G slot), [(layer, its view), ...])
        runs: list[tuple[tuple, list[tuple[str, dict[str, jax.Array]]]]] = []
        for b in self.buckets:
            for name, dims in zip(b.layers, b.dims):
                helper = self.registry.layers[name]
                # pinned to replicated like the stack path's matrices:
                # TP/SP leaves per-layer grads model-sharded
                gview = {
                    k: jax.lax.with_sharding_constraint(v, rep)
                    for k, v in helper.grad_view(layer_grads[name]).items()
                }
                (a_key, a_i), (g_key, g_i) = (
                    self._a_slot[name], self._g_slot[name]
                )
                form = (
                    type(helper), a_key, g_key, dims,
                    tuple((k, v.shape, v.dtype) for k, v in gview.items()),
                )
                if runs and runs[-1][0] == (
                    form, a_i - len(runs[-1][1]), g_i - len(runs[-1][1])
                ):
                    runs[-1][1].append((name, gview))
                else:
                    runs.append(((form, a_i, g_i), [(name, gview)]))

        held = jax.lax.optimization_barrier
        views = {}
        for (form, a_i, g_i), members in runs:
            _, a_key, g_key, (da, dg), _ = form
            n = len(members)
            product = self.registry.layers[members[0][0]].inverse_precondition
            if n == 1:
                name, gview = members[0]
                views[name] = (held(gview), product(
                    gview,
                    state.a_inv[a_key][a_i, :da, :da],
                    state.g_inv[g_key][g_i, :dg, :dg],
                ))
                continue
            joined = jax.vmap(product)(
                {
                    k: jnp.stack([gview[k] for _, gview in members])
                    for k in members[0][1]
                },
                state.a_inv[a_key][a_i:a_i + n, :da, :da],
                state.g_inv[g_key][g_i:g_i + n, :dg, :dg],
            )
            for i, (name, gview) in enumerate(members):
                views[name] = (
                    held(gview), {k: v[i] for k, v in joined.items()}
                )
        return views

    def _stacked_views(
        self, state: DistKFACState, layer_grads: dict[str, Any]
    ) -> dict[str, tuple[dict[str, jax.Array], dict[str, jax.Array]]]:
        """Each layer's gradient and preconditioned gradient in matrix
        form, by batched math on gradient stacks laid out like the
        decompositions."""
        cfg = self.config
        damping = _resolve(cfg.damping, state.step)
        dec = NamedSharding(self.mesh, self._decomp_spec())
        rep = NamedSharding(self.mesh, P())

        pmats: dict[str, jax.Array] = {}
        for b in self.buckets:
            # pin each matrix to replicated before inserting: TP/SP leaves
            # per-layer grads model-sharded, and mixed shardings force
            # XLA's involuntary full rematerialization of the stack (same
            # pattern as _stack_stats). Built by dynamic-update-slice into
            # a zeros buffer rather than concatenate: GSPMD mispartitions
            # the concat-of-broadcasts under the slot-sharded constraint
            # on fractional grad-worker meshes, resolving the unused row
            # axis as partial-sum and inflating the stack by the
            # grad-worker count.
            gstack = jnp.zeros((b.padded, b.dg, b.da), cfg.inv_dtype)
            for i, n in enumerate(b.layers):
                gm = jax.lax.with_sharding_constraint(
                    self.registry.layers[n].grads_to_matrix(layer_grads[n]),
                    rep,
                )
                gstack = gstack.at[i].set(
                    pad_grad(gm, b.dg, b.da).astype(cfg.inv_dtype)
                )
            gstack = jax.lax.with_sharding_constraint(gstack, dec)

            def asm(side_dict, slot_map, row_shape):
                """Assemble this pair bucket's decomp stack from side slots.

                Colocated: side keys are pair keys and slots align — use the
                resident stack as-is (no extra collective). Non-colocated:
                gather each layer's row from its side stack and replicate
                the assembly — the decomposition exchange non-colocation
                buys its eigh parallelism with (the reference ships inverses
                to grad workers the same way, kfac/assignment.py:268-304).
                The A side of a bucket that holds a follower of an A group
                is assembled the same way, the leader's row under each of
                its members, so every member's gradient workers hold it.
                """
                aligned = (
                    b.key in self._a_aligned if slot_map is self._a_slot
                    else self.colocate
                )
                if aligned:
                    return side_dict[b.key]
                rws = [
                    jax.lax.with_sharding_constraint(
                        side_dict[slot_map[n][0]][slot_map[n][1]], rep
                    )
                    for n in b.layers
                ]
                pad_n = b.padded - len(b.layers)
                if pad_n:
                    rws += [jnp.zeros(row_shape, rws[0].dtype)] * pad_n
                return jax.lax.with_sharding_constraint(jnp.stack(rws), rep)

            if self._prediv:
                def prec_fused(gm, qa_, qg_, fused_):
                    v1 = qg_.T @ gm @ qa_
                    return qg_ @ (v1 * fused_) @ qa_.T

                pstack = jax.vmap(prec_fused)(
                    gstack, state.qa[b.key], state.qg[b.key],
                    state.dgda[b.key],
                )
            elif self._eigen:
                qa = asm(state.qa, self._a_slot, (b.da, b.da))
                qg = asm(state.qg, self._g_slot, (b.dg, b.dg))
                dada = asm(state.da, self._a_slot, (b.da,))
                dgdg = asm(state.dg, self._g_slot, (b.dg,))
                # per-slot escalated damping bites here for the non-prediv
                # EIGEN method (its damping enters at precondition time);
                # prediv/INVERSE bake it into update_inverses
                if cfg.health is not None:
                    dmp = damping * self._slot_mults(
                        state.health, b.layers, b.padded
                    )
                else:
                    dmp = jnp.broadcast_to(
                        jnp.asarray(damping, jnp.float32), (b.padded,)
                    )

                def prec(gm, qa_, qg_, da_, dg_, dm):
                    v1 = qg_.T @ gm @ qa_
                    v2 = v1 / (jnp.outer(dg_, da_) + dm)
                    return qg_ @ v2 @ qa_.T

                pstack = jax.vmap(prec)(gstack, qa, qg, dada, dgdg, dmp)
            else:
                pstack = jax.vmap(lambda gm, ai, gi: gi @ gm @ ai)(
                    gstack,
                    asm(state.a_inv, self._a_slot, (b.da, b.da)),
                    asm(state.g_inv, self._g_slot, (b.dg, b.dg)),
                )
            pmats[b.key] = pstack

        # Extraction happens on replicated per-layer true-dim matrices, and
        # so do graceful degradation and KL clipping (finish_precondition)
        # — NOT at stack level. Mixing gstack into outputs or reductions at
        # stack level flips its row-axis replication to partial-sum under
        # GSPMD at fractional grad-worker meshes and inflates values by
        # the grad-worker count; the per-layer form also matches the dense
        # engine's vg semantics exactly.
        views = {}
        for b in self.buckets:
            # KAISA gradient broadcast: replicate the preconditioned stack.
            pstack = jax.lax.with_sharding_constraint(pmats[b.key], rep)
            for i, name in enumerate(b.layers):
                dag, dgg = b.dims[i]
                views[name] = (
                    helpers_lib.matrix_view(
                        self.registry.layers[name], layer_grads[name]
                    ),
                    {helpers_lib.MATRIX: pstack[i][:dgg, :dag]},
                )
        return views

    # ------------------------------------------------------------------ step

    @tracing.scope('dist_kfac.step')
    def step(
        self,
        state: DistKFACState,
        grads: Any,
        stats: capture_lib.CapturedStats | None,
        loss: jax.Array | None = None,
    ) -> tuple[DistKFACState, Any]:
        """One KAISA step (same pipeline as the dense engine,
        kfac_tpu/preconditioner.py:step). ``loss``, when given, rides
        into the flight-recorder ring next to this step's scalars."""
        cfg = self.config
        if stats is not None:
            state = jax.lax.cond(
                state.step % _resolve(cfg.factor_update_steps, state.step) == 0,
                lambda s: self.update_factors(s, stats),
                lambda s: s,
                state,
            )
        if self._async_mode == 'sliced':
            state = async_sliced.kaisa_async_step(self, state)
        elif self._async_mode == 'host':
            state = async_host.kaisa_host_step(self, state)
        else:
            state = jax.lax.cond(
                state.step % _resolve(cfg.inv_update_steps, state.step) == 0,
                self.update_inverses,
                lambda s: s,
                state,
            )
        if cfg.metrics is not None and state.metrics is not None:
            scal: dict[str, jax.Array] = {}
            new_grads = self.precondition(state, grads, metrics_out=scal)
            ms = metrics_lib.update_scalars(state.metrics, scal)
            state = state._replace(
                metrics=metrics_lib.finalize(ms, cfg.metrics, state.step)
            )
        else:
            new_grads = self.precondition(state, grads)
        if cfg.flight is not None and state.flight is not None:
            # same placement as the dense engine: after finalize, so the
            # ring row equals what a collector drain would read this step
            state = state._replace(flight=flight_lib.record(
                state.flight,
                state.step,
                state.metrics.scalars,
                loss=loss,
                grad_norm=flight_lib.global_grad_norm(grads),
            ))
        state = state._replace(step=state.step + 1)
        return state, new_grads

    def rematerialize(self, state: DistKFACState) -> DistKFACState:
        """Recompute decompositions from factors after a checkpoint restore
        (reference semantics: kfac/base_preconditioner.py:296-308).

        Under async refresh the shadow is reset (host mode: in-flight
        worker output discarded) — the first boundary after a mid-window
        restore skips the swap, the next window refreshes normally.
        """
        state = self.update_inverses(state)
        if self._async_mode == 'sliced':
            state = state._replace(
                shadow=async_sliced.kaisa_shadow(self, state)
            )
        elif self._async_mode == 'host':
            async_host.reset_worker(self)
        return state

    def extract_factors(
        self, state: DistKFACState
    ) -> dict[str, dict[str, jax.Array]]:
        """Per-layer true-dim factors from the stacked state.

        A topology-independent view: bucket keys, size classes, slot
        padding, and colocation are all layout choices of THIS engine
        config — the layer-named (d, d) factors are the portable content
        (the reference's per-layer factor-dir checkpoints,
        kfac/gpt_neox/preconditioner.py:394-447).
        """
        out: dict[str, dict[str, jax.Array]] = {}
        for name, h in self.registry.layers.items():
            # a follower of an A group reads its leader's slot
            key, i = self._a_slot[name]
            d = h.a_factor_shape[0]
            out[name] = {'a': state.a[key][i, :d, :d]}
        for sb in self.g_store:
            for i, name in enumerate(sb.layers):
                d = sb.dims[i]
                out.setdefault(name, {})['g'] = state.g[sb.key][i, :d, :d]
        return out

    def insert_factors(
        self,
        state: DistKFACState,
        factors: dict[str, dict[str, jax.Array]],
    ) -> DistKFACState:
        """Write per-layer factors into this engine's stacked layout
        (inverse of :meth:`extract_factors`; layers absent from
        ``factors`` keep their current rows). A group's A slot takes its
        leader's entry; its followers' ``'a'`` entries are dropped (equal
        to it where this engine wrote them, each layer's own in a
        checkpoint from before the groups). Call
        :meth:`rematerialize` afterwards to rebuild decompositions."""

        def rewrite(store, side):
            out = {}
            for sb in store:
                stack = (
                    state.a[sb.key] if side == 'a' else state.g[sb.key]
                )
                idxs = [
                    i for i, n in enumerate(sb.layers) if n in factors
                ]
                if idxs:
                    # one scatter per bucket, not one full-stack copy per
                    # layer
                    rows = jnp.stack([
                        pad_factor(
                            factors[sb.layers[i]][side].astype(
                                self.config.factor_dtype
                            ),
                            sb.d,
                        )
                        for i in idxs
                    ])
                    stack = stack.at[jnp.asarray(idxs)].set(rows)
                out[sb.key] = stack
            return out

        return state._replace(
            a=rewrite(self.a_store, 'a'), g=rewrite(self.g_store, 'g')
        )

    def describe(self) -> str:
        """Registration + placement dump: the reference's construction-time
        assignment logging (kfac/preconditioner.py:264-268,300) as a
        pull-based table — strategy, bucket layout, and per-layer inverse
        workers from the KAISA assignment."""
        lines = [
            f'DistributedKFAC: {len(self.registry.layers)} layers over '
            f'{self.total_devices} devices '
            f'(grid {self.grad_workers}x{mesh_lib.n_cols(self.mesh)}), '
            f'strategy={self.strategy.name}, colocate={self.colocate}, '
            f'method={self.config.compute_method.name}',
            'preconditioned in the parameters\' own layout: '
            f'{self.in_layout_share:.1%} of the gradient elements ('
            + (
                'each layer against its inverse slots, resident on every '
                'device' if self._in_layout else
                'gradient stacks laid out like the decompositions'
            ) + ')',
            *self.config.describe_patchless(),
            'factor and inverse bytes by part of a block: ' + ', '.join(
                f'{part} {n / 1e9:.3f} GB'
                for part, n in self.state_bytes_by_part.items()
            ),
            self.config.describe(),
            'stat transport buckets (stacked batched decompositions):',
        ]
        for b in self.buckets:
            lines.append(
                f'  bucket da={b.da} dg={b.dg}: '
                f'{len(b.layers)} layers, {b.padded} padded slots'
            )
        lines.append(
            'factor storage fill (resident vs padding bytes per size '
            'class):'
        )
        for key, p in comms_lib.padding_report(self).items():
            lines.append(
                f'  {key}: {p["layers"]} layers in {p["slots"]} slots, '
                f'resident {p["resident_bytes"]} B, '
                f'identity-pad {p["identity_pad_bytes"]} B, '
                f'slot-pad {p["slot_pad_bytes"]} B, '
                f'fill {p["fill"]:.0%}'
            )
        lines.append(
            'executed placement (slot round-robin within stacked buckets; '
            'decomposition runs where the slot lives):'
        )
        for name in self.registry.names():
            a_key, a_i = self._a_slot[name]
            g_key, g_i = self._g_slot[name]
            a_dev = self.slot_device('a', name)
            g_dev = self.slot_device('g', name)
            lines.append(
                f'  {name}: A slot {a_key}[{a_i}] -> device {a_dev.id}, '
                f'G slot {g_key}[{g_i}] -> device {g_dev.id}'
            )
        lines.append(
            'inverse workers, cost-model view (KAISA greedy assignment — '
            'reference-parity diagnostic, NOT the executed placement above):'
        )
        for layer in self.assignment.get_layers():
            workers = {
                f: self.assignment.inv_worker(layer, f)
                for f in self.assignment.get_factors(layer)
            }
            lines.append(f'  {layer}: {workers}')
        # (the A groups are in the config's lines above)
        if self.config.a_groups and not self.a_groups:
            lines.append(
                'A groups: none stored in the stacked layout (pre-divided '
                'eigenvalues lie slot by slot beside every layer\'s own A '
                'factor)'
            )
        return '\n'.join(lines)

    def topology(self) -> dict[str, Any]:
        """Process/device/mesh topology snapshot, recorded
        (informationally) into checkpoint layout manifests so an elastic
        restore can report which topologies it moved a checkpoint
        between."""
        import numpy as _np

        return {
            'process_count': jax.process_count(),
            'device_count': jax.device_count(),
            'backend': jax.default_backend(),
            'mesh_axes': list(self.mesh.axis_names),
            'mesh_shape': [int(s) for s in _np.shape(self.mesh.devices)],
        }

    def slot_device(self, side: str, name: str) -> Any:
        """The device that stores AND decomposes ``name``'s A or G factor.

        Factor stacks shard their leading slot axis over every mesh axis
        (``_factor_spec``), so mesh-linear device ``j`` owns slots
        ``[j*spd, (j+1)*spd)`` with ``spd = padded / total_devices`` —
        the executed counterpart of the reference's per-rank inv_worker
        query (kfac/assignment.py), asserted against the real shard layout
        in tests.
        """
        slot_map = self._a_slot if side == 'a' else self._g_slot
        store = self.a_store if side == 'a' else self.g_store
        key, i = slot_map[name]
        padded = next(sb.padded for sb in store if sb.key == key)
        spd = padded // self.total_devices
        import numpy as _np

        return _np.asarray(self.mesh.devices).reshape(-1)[i // spd]

    def comms_report(self) -> dict[str, Any]:
        """Host-side comms/padding byte accounting for this configuration.

        See :func:`kfac_tpu.observability.comms.comms_summary`: stat
        transport bytes and chunk plan, inverse-reshard and
        gradient-broadcast payloads, and per-size-class padding waste —
        the measurable side of the KAISA gradient-worker-fraction trade.
        """
        return comms_lib.comms_summary(self)

    def compile_watcher(
        self,
    ) -> 'compile_watch_lib.CompileWatch | None':
        """This engine's :class:`~kfac_tpu.observability.compile_watch.
        CompileWatch`, built lazily from ``config.compile_watch`` (None
        when disabled). The Trainer's step paths count into the same
        watch, so one report covers the whole program surface."""
        if self.config.compile_watch is None:
            return None
        watch = getattr(self, '_compile_watcher', None)
        if watch is None:
            watch = compile_watch_lib.CompileWatch(self.config.compile_watch)
            self._compile_watcher = watch
        return watch

    def watched(self, entry: str) -> Any:
        """A jitted, watch-wrapped IR entry point (``'step'``,
        ``'update_factors'``, ...). Requires ``config.compile_watch``."""
        if entry not in self.IR_ENTRY_POINTS:
            raise ValueError(
                f'unknown entry {entry!r}; expected one of '
                f'{self.IR_ENTRY_POINTS}'
            )
        watch = self.compile_watcher()
        if watch is None:
            raise ValueError(
                'watched() requires compile_watch enabled on config'
            )
        cache = getattr(self, '_watched_entries', None)
        if cache is None:
            cache = {}
            self._watched_entries = cache
        if entry not in cache:
            cache[entry] = watch.wrap(
                f'dist_kfac.{entry}', jax.jit(getattr(self, entry))
            )
        return cache[entry]

    def compiled_memory_report(self) -> dict[str, dict[str, Any]]:
        """Latest XLA ``memory_analysis()`` snapshot per watched entry —
        the measured counterpart of :meth:`memory_usage` (which estimates
        from shard shapes) and the number autotune's
        ``HardwareSpec.hbm_bytes`` pruning should be checked against.
        Empty when the watch is off or the backend doesn't report."""
        watch = self.compile_watcher()
        return {} if watch is None else watch.memory_report()

    def memory_usage(self, state: DistKFACState) -> dict[str, Any]:
        """Per-device bytes by category, read from the ACTUAL shard layout.

        Each array's per-device footprint is its sharding's shard shape —
        the truth for asymmetric/real layouts — rather than fraction
        arithmetic from the strategy (estimates mislead on
        asymmetric layouts). Falls back to strategy fractions only for
        abstract values (e.g. under trace).

        ``total`` sums the four factor/inverse categories;
        ``padding_waste`` (nested, GLOBAL logical bytes — not per-device)
        breaks resident factor bytes out of the size-class padding, per
        storage bucket plus totals, so the cost of bucket granularity is
        visible next to the resident footprint.
        """
        shard_f = 1.0 / self.total_devices
        if self.strategy == enums.DistributedStrategy.COMM_OPT:
            shard_d = 1.0
        else:
            shard_d = 1.0 / mesh_lib.n_cols(self.mesh)

        def per_device(v: jax.Array, frac: float) -> int:
            sharding = getattr(v, 'sharding', None)
            if sharding is not None and hasattr(sharding, 'shard_shape'):
                try:
                    shape = sharding.shard_shape(v.shape)
                except Exception:  # abstract/manual values
                    return int(v.size * v.dtype.itemsize * frac)
                n = 1
                for s in shape:
                    n *= int(s)
                return n * v.dtype.itemsize
            return int(v.size * v.dtype.itemsize * frac)

        def nbytes(d: dict[str, jax.Array], frac: float) -> int:
            return int(sum(per_device(v, frac) for v in d.values()))

        sizes = {
            'a_factors': nbytes(state.a, shard_f),
            'g_factors': nbytes(state.g, shard_f),
            'a_inverses': nbytes(state.qa, shard_d) + nbytes(state.da, shard_d)
            + nbytes(state.a_inv, shard_d),
            'g_inverses': nbytes(state.qg, shard_d) + nbytes(state.dg, shard_d)
            + nbytes(state.dgda, shard_d) + nbytes(state.g_inv, shard_d),
        }
        sizes['total'] = sum(sizes.values())
        padding = comms_lib.padding_report(self)
        sizes['padding_waste'] = {
            'per_class': padding,
            'resident_bytes': sum(
                p['resident_bytes'] for p in padding.values()),
            'identity_pad_bytes': sum(
                p['identity_pad_bytes'] for p in padding.values()),
            'slot_pad_bytes': sum(
                p['slot_pad_bytes'] for p in padding.values()),
        }
        return sizes
