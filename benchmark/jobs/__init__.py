"""Job builders, one module per ``kind`` a configuration names.

``benchmark/jobs/<kind>.py`` exposes ``build(config, workload, devices) ->
Job``. It builds the job through the library's normal path, as the example
trainer of that kind does: the program's model, ``register_model``,
``examples.common.build_kfac`` on ``kaisa_mesh`` / ``train_mesh``,
``training.Trainer``. A new kind of model is a new module here; a new size
of a kind that exists is a configuration file alone.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
from typing import Any, Callable


@dataclasses.dataclass
class Job:
    """What the harness needs of a job, whatever its kind."""

    kind: str
    model: Any
    mesh: Any
    # shapes of model.init's variables: {'params': ..., ['batch_stats': ...]}
    variable_shapes: Any
    registry: Any
    loss_fn: Callable  # loss_fn(params, model_state, batch) -> (loss, state)
    make_optimizer: Callable  # lr schedule -> optax transformation
    lr_schedule: Callable
    kfac_args: argparse.Namespace
    batch_sharding: Any
    global_batch: int  # samples per step, all chips together
    # (seed, n) -> n distinct host batches (inputs, targets), from the seed
    make_ring: Callable


def load(kind: str):
    return importlib.import_module(f'benchmark.jobs.{kind}')


def kfac_namespace(workload: dict, lr: float) -> argparse.Namespace:
    """The flags ``examples.common.build_kfac`` reads, from the cell's
    ``kfac`` block (the reference's documented defaults unless the cell
    says otherwise)."""
    k = workload['kfac']
    return argparse.Namespace(
        kfac=True,
        kfac_factor_update_steps=k['factor_update_steps'],
        kfac_inv_update_steps=k['inv_update_steps'],
        kfac_damping=k['damping'],
        kfac_factor_decay=k['factor_decay'],
        kfac_kl_clip=k['kl_clip'],
        kfac_compute_method=k.get('compute_method', 'auto'),
        kfac_strategy=k.get('strategy', 'comm-opt'),
        kfac_skip_layers=list(k.get('skip_layers', [])),
        kfac_bucket_granularity=None,
        kfac_compile_watch=True,
        kfac_verbose=False,
        lr=lr,
    )


def warmup_schedule(base_lr: float, warmup_steps: int) -> Callable:
    """Linear warm-up to ``base_lr``, then constant: the start of
    ``examples.common.make_lr_schedule`` (its decay boundaries lie far
    beyond any window)."""
    import jax.numpy as jnp

    def schedule(step):
        return base_lr * jnp.minimum(1.0, (step + 1) / max(1, warmup_steps))

    return schedule
