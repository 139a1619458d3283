"""Multi-host (multi-slice / DCN) initialization and mesh construction.

The reference scales across nodes with torchrun + NCCL/MPI process groups
(scripts/run_imagenet.sh:35-75, kfac/distributed.py). The JAX equivalent is
``jax.distributed.initialize`` (one process per host, all devices visible
as one global world) plus a mesh whose *outer* axes span hosts: collectives
on inner axes ride ICI, outer axes ride DCN. KAISA's layout maps naturally:
put the KAISA grid's receiver axis (gradient broadcasts, infrequent) across
DCN and keep factor/eigh traffic inside a slice.
"""

from __future__ import annotations

import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from kfac_tpu import assignment as assignment_lib
from kfac_tpu.parallel import mesh as mesh_lib

#: The cross-host protocol op registry. Every host-side operation that
#: participates in cross-rank coordination is declared here, by function
#: name, with its protocol kind:
#:
#: - ``barrier``    — blocks until every process arrives (name-checked).
#: - ``collective`` — fixed-shape all-gather; every process must call it
#:   at the same point in its call sequence.
#: - ``vote``       — a collective whose result gates a pod-wide
#:   decision (commit/abort semantics).
#: - ``wait``       — host-local durability edge (async-save completion);
#:   orders a subsequent single-writer mutation after the written bytes.
#:
#: The kfaclint pod tier (``kfac_tpu/analysis/pod/``) reads this table
#: *from the AST* (it never imports this module) and uses it to extract
#: per-rank protocol traces, so adding a coordination primitive here is
#: what makes KFL301–KFL305 aware of it. Keep the dict a pure literal.
PROTOCOL_OPS = {
    'barrier': 'barrier',
    'sync_global_devices': 'barrier',
    'allgather_scalars': 'collective',
    'process_allgather': 'collective',
    'agree_emergency': 'collective',
    'assert_same_step': 'collective',
    'agree_decision': 'vote',
    'wait_until_finished': 'wait',
}


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Bring up the JAX distributed runtime (no-op if single-process).

    On TPU pods the arguments are auto-detected from the environment; on
    other platforms pass them explicitly or export
    ``KFAC_TPU_COORDINATOR`` / ``KFAC_TPU_NUM_PROCESSES`` /
    ``KFAC_TPU_PROCESS_ID`` (what ``scripts/run_pod.sh`` sets per node —
    the torchrun-rendezvous equivalent).
    """
    if coordinator_address is None:
        coordinator_address = os.environ.get('KFAC_TPU_COORDINATOR')
    if num_processes is None and 'KFAC_TPU_NUM_PROCESSES' in os.environ:
        num_processes = int(os.environ['KFAC_TPU_NUM_PROCESSES'])
    if process_id is None and 'KFAC_TPU_PROCESS_ID' in os.environ:
        process_id = int(os.environ['KFAC_TPU_PROCESS_ID'])
    if num_processes is not None and num_processes <= 1:
        return
    if coordinator_address is None and num_processes is None:
        # No explicit rendezvous: initialize only when the environment
        # says this host is part of a MULTI-host pod/cluster; on a single
        # host (incl. single-worker TPU VMs, which still export
        # TPU_WORKER_HOSTNAMES with one entry) there is nothing to set up
        # and jax.distributed.initialize would raise.
        hosts = os.environ.get('TPU_WORKER_HOSTNAMES', '')
        n_tpu_hosts = len([h for h in hosts.split(',') if h.strip()])
        n_slurm = int(os.environ.get('SLURM_JOB_NUM_NODES', '1') or 1)
        multislice = 'MEGASCALE_COORDINATOR_ADDRESS' in os.environ
        if n_tpu_hosts <= 1 and n_slurm <= 1 and not multislice:
            return
        # in a detected multi-host environment, failures are real and
        # must surface
    if (jax.config.jax_platforms or '').startswith('cpu'):
        # the default XLA CPU client rejects multiprocess computations;
        # the gloo transport (what the multi-process CPU tests rendezvous
        # over) must be selected before the backend is created
        jax.config.update('jax_cpu_collectives_implementation', 'gloo')
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def hybrid_kaisa_mesh(
    grad_worker_fraction: float = 1.0,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """KAISA mesh laid out for multi-host topology.

    Devices are ordered host-major, so with the KAISA grid built as
    (gw, col) = reshape(devices), the *column* (gradient-worker group /
    second-order state sharing) stays within a host's slice whenever
    grad_workers <= devices-per-host — inverse traffic rides ICI while only
    the row-wise gradient broadcast crosses DCN. Single-host it degrades to
    :func:`kfac_tpu.parallel.mesh.kaisa_mesh`.

    Note on device numbering: this grid is a *permutation* of the input
    device order (host-contiguous columns), so KAISAAssignment's device
    indices are logical mesh coordinates here, not jax.devices() positions;
    resolve them with :func:`kfac_tpu.parallel.mesh.device_at`. Execution is
    unaffected (all layouts are mesh-relative).
    """
    devices = list(devices if devices is not None else jax.devices())
    world = len(devices)
    workers = assignment_lib.grad_worker_count(world, grad_worker_fraction)
    per_host: dict[int, list[jax.Device]] = {}
    for d in devices:
        per_host.setdefault(getattr(d, 'process_index', 0), []).append(d)
    ordered: list[jax.Device] = []
    for pid in sorted(per_host):
        ordered.extend(per_host[pid])
    # lay columns out as host-contiguous blocks: grid[g, c] = ordered[c*W+g],
    # so a grad-worker group (fixed c, varying g) is a consecutive device
    # run within one host whenever workers <= devices-per-host
    grid = np.asarray(ordered, dtype=object).reshape(
        world // workers, workers
    ).T
    return Mesh(grid, (mesh_lib.GW_AXIS, mesh_lib.COL_AXIS))


def allgather_scalars(values: np.ndarray | Sequence[float]) -> np.ndarray:
    """All-gather a small host-local float array across processes.

    Returns a ``(process_count, *values.shape)`` numpy array ordered by
    process index. Single-process this is a pure-numpy reshape (no device
    work at all); multi-host it is one fixed-shape
    ``multihost_utils.process_allgather`` — callers (the flight-recorder
    drain's skew columns) batch everything they need into ONE call so a
    drain costs at most one DCN collective. Every process must call this
    with an identically-shaped array (SPMD symmetry).
    """
    arr = np.asarray(values, np.float32)
    if jax.process_count() == 1:
        return arr[None, ...]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr))


def barrier(name: str) -> None:
    """Block until every process reaches this point (single-process:
    no-op).

    Used by ``resilience.CheckpointManager.save`` to order rank 0's
    removal of a stale step directory before any host starts writing
    into it. Every process must call this with the same ``name`` at the
    same point in its call sequence (SPMD symmetry);
    ``sync_global_devices`` raises if the names ever mismatch, turning a
    skewed call pattern into a loud error instead of a silent pair-up of
    unrelated collectives.
    """
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def agree_emergency(code: int, step: int) -> tuple[int, int]:
    """Cross-host barrier for emergency-checkpoint requests.

    Each host contributes ``(code, step)`` — ``code`` 0 when it saw no
    preemption signal, higher values for more urgent semantics (see
    ``resilience.signals``) — and every host receives the pod-wide
    ``(max code, max step)``. A SIGTERM delivered to a single host
    therefore drives ALL hosts into the same emergency save at the same
    agreed step. Built on :func:`allgather_scalars`, so single-process it
    is a pure-numpy identity; every process must call it at the same step
    cadence (SPMD symmetry).
    """
    if jax.process_count() == 1:
        return int(code), int(step)
    gathered = allgather_scalars([float(code), float(step)])
    return int(gathered[:, 0].max()), int(gathered[:, 1].max())


def agree_decision(ok: bool) -> bool:
    """Pod-unanimous go/no-go vote: True only when EVERY process voted
    True.

    The chaos worker's recovery uses this as its gate — any host whose
    restore walk failed vetoes the resume pod-wide, so no host ever trains
    from a state its peers failed to reach. Built on :func:`allgather_scalars` (min-reduction
    over one fixed-shape gather), so single-process it is a pure-Python
    identity; every process must call it at the same point in its call
    sequence (SPMD symmetry).
    """
    if jax.process_count() == 1:
        return bool(ok)
    gathered = allgather_scalars([1.0 if ok else 0.0])
    return bool(gathered[:, 0].min() >= 0.5)


def assert_same_step(step: int, what: str = 'restored checkpoint') -> None:
    """Verify every process agrees on ``step``; raise naming the spread.

    Used after ``resilience.CheckpointManager.restore_latest``: hosts
    walking divergent local rotations (torn NFS caches, one host missing
    the newest dir) would otherwise silently resume from different steps
    and corrupt the run at the first collective.
    """
    if jax.process_count() == 1:
        return
    gathered = allgather_scalars([float(step)])[:, 0]
    if not (gathered == gathered[0]).all():
        raise RuntimeError(
            f'{what}: processes disagree on the step — per-process view '
            f'{[int(s) for s in gathered]}; the checkpoint rotation is '
            'inconsistent across hosts (shared filesystem lag or a torn '
            'rotation); re-sync the checkpoint directory before resuming'
        )


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()
