"""Pod member for the chaos harness (kfac_tpu/resilience/chaos.py).

Launched by :class:`ChaosConductor` as a real OS process with the
KFAC_TPU_* rendezvous env surface set. Builds the REAL stack — a
DistributedKFAC engine over the global gloo mesh, a CheckpointManager
rotation shared by every rank, and a Trainer — then hands control to :func:`kfac_tpu.resilience.chaos
.run_worker`, which recovers via the pod-coordinated
CHAOS_RECOVERY_PROTOCOL and trains to ``max_steps`` emitting one JSON
line per event (the ``resilience_worker.py`` convention).

Usage: ``python chaos_worker.py <config.json>`` where the JSON carries
``ckpt_dir`` / ``max_steps`` / ``save_interval`` / ``keep`` /
``step_sleep_s`` (written by the conductor).

Determinism is the contract: model init keys, the per-step batch, and
the optimizer are fixed, so the loss at step k is a pure function of k
— the conductor's zero-divergence check compares the storm-ridden
trajectory bit-for-bit against an uninterrupted control pod.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update('jax_platforms', 'cpu')

from kfac_tpu.parallel import multihost  # noqa: E402

multihost.initialize()

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import kfac_tpu  # noqa: E402
from kfac_tpu.parallel import DistributedKFAC, batch_sharding  # noqa: E402
from kfac_tpu.resilience import CheckpointManager, chaos  # noqa: E402
from testing import models  # noqa: E402


def emit(**payload) -> None:
    print(json.dumps(payload), flush=True)


def _global_put(arr, sharding):
    """Host array -> global jax.Array across processes (every process
    passes the same full array; each contributes its local shards)."""
    arr = np.asarray(arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def main() -> int:
    with open(sys.argv[1]) as f:
        cfg = json.load(f)

    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=32, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    bare = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method='eigen', damping=0.01, lr=0.1,
        kl_clip=None, bucket_granularity=1,
    )

    def loss_fn(params, model_state, batch):
        bx, by = batch
        pred = m.apply({'params': params}, bx)
        return jnp.mean((pred - by) ** 2), model_state

    engine = DistributedKFAC(
        config=bare, mesh=multihost.hybrid_kaisa_mesh(0.5)
    )
    manager = CheckpointManager(
        cfg['ckpt_dir'], engine=engine,
        save_interval_steps=cfg['save_interval'], keep=cfg['keep'],
    )
    trainer = kfac_tpu.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=engine,
        checkpoints=manager,
    )

    def make_batch(trainer):
        mesh = getattr(trainer.kfac, 'mesh', None)
        if mesh is None:
            return (x, y)
        bs = batch_sharding(mesh)
        return (_global_put(x, bs), _global_put(y, bs))

    return chaos.run_worker(
        trainer,
        trainer.checkpoints,
        params,
        make_batch,
        int(cfg['max_steps']),
        emit,
        step_sleep_s=float(cfg.get('step_sleep_s') or 0.0),
    )


if __name__ == '__main__':
    sys.exit(main())
