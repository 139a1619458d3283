"""Shared example-trainer glue: flags, metrics, schedules, checkpoints.

Parity with the reference's example utilities (examples/utils.py: Metric,
accuracy, LabelSmoothLoss, create_lr_schedule; examples/vision/
optimizers.py: the K-FAC flag surface).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import optax

import kfac_tpu
from kfac_tpu import tracing


def distributed_init() -> None:
    """Join the multi-host world before first backend use (no-op on a
    single host). Trainers call this first so ``jax.devices()`` sees the
    global world under ``scripts/run_pod.sh`` / TPU pod launches, and so
    the persistent compile cache is placed before the first compile."""
    from kfac_tpu.parallel import multihost
    from kfac_tpu.utils import compile_cache

    compile_cache.configure()
    multihost.initialize()


def add_kfac_args(parser: argparse.ArgumentParser) -> None:
    """The reference's K-FAC CLI surface
    (examples/torch_cifar10_resnet.py:148-237)."""
    g = parser.add_argument_group('kfac')
    g.add_argument('--kfac', action='store_true', default=True)
    g.add_argument('--no-kfac', dest='kfac', action='store_false')
    g.add_argument('--kfac-factor-update-steps', type=int, default=10)
    g.add_argument('--kfac-inv-update-steps', type=int, default=100)
    g.add_argument('--kfac-damping', type=float, default=0.003)
    g.add_argument('--kfac-factor-decay', type=float, default=0.95)
    g.add_argument('--kfac-kl-clip', type=float, default=0.001)
    g.add_argument(
        '--kfac-compute-method',
        choices=('auto', 'eigen', 'inverse'),
        default='auto',
        help='auto picks per platform: eigen off-TPU (reference default), '
        'inverse+Newton-Schulz on TPU where eigh is pathological',
    )
    g.add_argument(
        '--kfac-strategy',
        choices=('comm-opt', 'mem-opt', 'hybrid-opt'),
        default='comm-opt',
        help='maps to grad_worker_fraction 1 / 1/world / 0.5',
    )
    g.add_argument('--kfac-skip-layers', nargs='*', default=[])
    g.add_argument(
        '--kfac-bucket-granularity', type=int, default=None,
        help='size-class rounding for distributed factor buckets '
        '(1 = exact dims; default picks per platform: 128 on TPU, 1 '
        'elsewhere). Pin an explicit value when a stacked checkpoint '
        'must restore on a different platform; see '
        'KFACPreconditioner.bucket_granularity',
    )
    g.add_argument(
        '--kfac-compile-watch', action='store_true',
        help='dispatch the jitted steps through CompileWatch: lowering and '
        'compile seconds, recompile counts and XLA memory per entry '
        '(docs/OBSERVABILITY.md "Compile & memory truth")',
    )
    g.add_argument(
        '--kfac-verbose', action='store_true',
        help='print the registration/assignment dump at construction '
        '(the reference logs this by default, kfac/preconditioner.py:264)',
    )


def add_metrics_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group('metrics')
    g.add_argument(
        '--metrics-csv', default=None,
        help='append step,name,value rows here (TensorBoard-writer slot of '
        'the reference vision engine, examples/vision/engine.py:106-113)',
    )


def add_train_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group('training')
    g.add_argument('--epochs', type=int, default=3)
    g.add_argument('--batch-size', type=int, default=128)
    g.add_argument('--lr', type=float, default=0.1)
    g.add_argument('--momentum', type=float, default=0.9)
    g.add_argument('--weight-decay', type=float, default=5e-4)
    g.add_argument('--warmup-epochs', type=float, default=1)
    g.add_argument('--lr-decay', nargs='*', type=float, default=[0.5, 0.75])
    g.add_argument('--seed', type=int, default=42)
    g.add_argument('--data-dir', default=None)
    g.add_argument('--checkpoint-dir', default=None)
    g.add_argument(
        '--resume', action='store_true',
        help='resume from the latest checkpoint in --checkpoint-dir',
    )
    g.add_argument(
        '--augment', action='store_true', default=None,
        help='random crop + flip on training images (default: on when '
             'training on a real dataset)',
    )
    g.add_argument(
        '--no-augment', dest='augment', action='store_false'
    )
    g.add_argument('--bf16', action='store_true')
    g.add_argument('--limit-steps', type=int, default=None,
                   help='cap steps per epoch (smoke runs)')


def strategy_fraction(name: str, world: int) -> float:
    if world < 1:
        raise ValueError(
            f'data-parallel world is {world}; model/seq shards exceed the '
            'device count'
        )
    if name == 'mem-opt':
        return 1.0 / world
    return {'comm-opt': 1.0, 'hybrid-opt': 0.5}[name]


def make_lr_schedule(base_lr, steps_per_epoch, epochs, warmup_epochs, decay_at):
    """Warmup + stepwise decay (reference examples/utils.py:92-114)."""
    boundaries = [int(d * epochs * steps_per_epoch) for d in decay_at]
    warmup = int(warmup_epochs * steps_per_epoch)
    piece = optax.piecewise_constant_schedule(
        base_lr, {b: 0.1 for b in boundaries}
    )

    def schedule(step):
        w = jnp.minimum(1.0, (step + 1) / max(1, warmup))
        return piece(step) * w

    return schedule


def label_smoothing_loss(logits, labels, num_classes, smoothing=0.1):
    """Label-smoothed cross entropy (reference examples/utils.py:41-63),
    via the optax built-ins."""
    with tracing.model_scope('loss'):
        soft = optax.smooth_labels(
            jax.nn.one_hot(labels, num_classes), smoothing
        )
        return optax.softmax_cross_entropy(
            logits.astype(jnp.float32), soft
        ).mean()


def cross_entropy_loss(logits, labels, num_classes):
    del num_classes
    with tracing.model_scope('loss'):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean()


class MetricsWriter:
    """Append-only CSV metrics log (one row per step/epoch event).

    The TensorBoard-writer slot of the reference's vision engine
    (examples/vision/engine.py:106-113) without the TensorBoard dependency:
    rows are ``step,name,value`` so any notebook/pandas/TensorBoard-import
    path can consume them. The file is flushed per write so a killed run
    keeps its trail.
    """

    def __init__(self, path: str | None) -> None:
        self._f = None
        if path:
            import os as _os

            _os.makedirs(_os.path.dirname(path) or '.', exist_ok=True)
            self._f = open(path, 'a', buffering=1)
            if self._f.tell() == 0:
                self._f.write('step,name,value\n')

    def write(self, step: int, name: str, value) -> None:
        if self._f is not None:
            self._f.write(f'{step},{name},{float(value):.8g}\n')

    def write_many(self, step: int, metrics: dict) -> None:
        for name, value in metrics.items():
            self.write(step, name, value)

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


class Metric:
    """Streaming average (the allreduce is implicit: metrics are computed on
    global arrays; reference examples/utils.py:66-89)."""

    def __init__(self) -> None:
        self.total = 0.0
        self.count = 0

    def update(self, value, n: int = 1) -> None:
        self.total += float(value) * n
        self.count += n

    @property
    def avg(self) -> float:
        return self.total / max(1, self.count)


def accuracy(logits, labels) -> float:
    return float((jnp.argmax(logits, -1) == labels).mean())


def build_kfac(args, registry, mesh=None, lr=None, verbose_dump=True):
    """Construct the (distributed) preconditioner from CLI flags.

    ``lr`` should be the live optimizer schedule so the KL-clip scale
    ``min(1, sqrt(kl_clip/|vg*lr^2|))`` tracks warmup/decay the way the
    reference reads the optimizer's current lr (kfac/preconditioner.py
    lr-callable); falls back to the constant base lr.
    """
    if not args.kfac:
        return None
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry,
        factor_update_steps=args.kfac_factor_update_steps,
        inv_update_steps=args.kfac_inv_update_steps,
        damping=args.kfac_damping,
        factor_decay=args.kfac_factor_decay,
        kl_clip=args.kfac_kl_clip,
        lr=args.lr if lr is None else lr,
        compute_method=(
            None
            if args.kfac_compute_method == 'auto'
            else args.kfac_compute_method
        ),
        bucket_granularity=args.kfac_bucket_granularity,
        compile_watch=args.kfac_compile_watch or None,
    )
    if mesh is not None:
        from kfac_tpu.parallel import DistributedKFAC

        dk = DistributedKFAC(config=cfg, mesh=mesh)
        if verbose_dump and getattr(args, 'kfac_verbose', False):
            print(dk.describe())
        return dk
    # verbose_dump=False lets callers that wrap cfg in another engine
    # (PipelineKFAC) print that engine's dump instead of a duplicate
    if verbose_dump and getattr(args, 'kfac_verbose', False):
        print(cfg.describe())
    return cfg


def inverse_residuals(kfac_engine, kfac_state) -> dict[str, float] | None:
    """Worst damped-inverse residual per storage bucket (``'a/<key>'``,
    ``'g/<key>'``) of a DistributedKFAC INVERSE engine — out-of-band
    Newton-Schulz quality monitoring (the stacked vmapped solve cannot
    surface convergence info in-band), to compare against
    ``kfac_tpu.ops.factors.NS_FALLBACK_RESIDUAL``. None for engines or
    methods the query does not apply to."""
    if kfac_engine is None or not hasattr(kfac_engine, 'inverse_residuals'):
        return None

    # the reduction runs under jit to replicated scalars: the state
    # arrays are sharded (non-addressable on multi-host pods), so eager
    # ops / np.asarray on them would fail exactly where this monitoring
    # matters most. jnp.max propagates NaN — a diverged solve reports NaN.
    def _worst(state):
        res = kfac_engine.inverse_residuals(state)
        return {
            f'{side}/{key}': jnp.max(r)
            for side, buckets in res.items() for key, r in buckets.items()
        }

    try:
        worst = jax.jit(_worst)(kfac_state)
    except ValueError:  # EIGEN method: the query is meaningless
        return None
    return {k: float(v) for k, v in worst.items()}


def log_inverse_residuals(args, kfac_engine, kfac_state) -> None:
    """Under ``--kfac-verbose``, print the worst of
    :func:`inverse_residuals`."""
    if not getattr(args, 'kfac_verbose', False):
        return
    residuals = inverse_residuals(kfac_engine, kfac_state)
    if residuals is None:
        return
    import numpy as np

    worst = float(np.max(list(residuals.values())))  # NaN propagates
    from kfac_tpu.ops.factors import NS_FALLBACK_RESIDUAL

    # NaN must flag as bad (all NaN comparisons are False, so test the
    # HEALTHY direction — the library's own convention, ops/factors.py)
    flag = '' if worst <= NS_FALLBACK_RESIDUAL else (
        '  [ABOVE FALLBACK THRESHOLD]'
    )
    print(f'  kfac inverse residual (worst slot): {worst:.2e}{flag}')


def make_epoch_batches(
    args,
    x_train,
    y_train,
    augment: bool,
    start_epoch: int = 0,
    normalize_stats=None,
):
    """Shared trainer input pipeline: native prefetch loader when requested
    (with in-worker crop/flip and shuffle fast-forward to ``start_epoch``
    for resumed runs), else seeded python batches with numpy augmentation.
    ``normalize_stats=(mean, std)`` applies per-batch normalization — used
    when the source is a read-only memmap that cannot be normalized in
    place. Returns ``epoch_batches(epoch)``.
    """
    from examples import data as data_lib

    prefetcher = None
    if getattr(args, 'native_loader', False):
        from kfac_tpu.utils import native_loader

        # an explicit --native-loader that cannot load is an error
        # (NativeLoaderUnavailable), never a quiet switch to python batches
        prefetcher = native_loader.PrefetchLoader(
            x_train, y_train, batch_size=args.batch_size, seed=args.seed,
            augment={'pad': 4, 'flip': True} if augment else None,
            start_epoch=start_epoch,
        )

    def epoch_batches(epoch):
        import numpy as np

        if prefetcher is not None:
            it = prefetcher.epoch_batches()
            aug_rng = None  # augmentation happened in the worker
        else:
            it = data_lib.batches(
                x_train, y_train, args.batch_size, args.seed + epoch
            )
            aug_rng = (
                np.random.default_rng(args.seed * 1000 + epoch)
                if augment
                else None
            )
        for xb, yb in it:
            if aug_rng is not None:
                xb = data_lib.augment_images(xb, aug_rng)
            if normalize_stats is not None:
                xb = data_lib.normalize(xb, *normalize_stats)
            yield xb, yb

    return epoch_batches


class Timer:
    def __init__(self) -> None:
        self.start = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.start


def timed_step(trainer, state, batch, on_step=None):
    """One ``trainer.step`` timed to completion: returns ``(state,
    loss)`` with the loss already on the host. ``on_step(trainer, state,
    loss, seconds)``, when given, sees every step — ``chip_smoke.py``
    records its per-step evidence through it. With ``donate_state`` the
    state it receives is consumed by the next step, so a callback keeps
    only what it reads from it, or the last one."""
    timer = Timer()
    state, loss = trainer.step(state, batch)
    jax.block_until_ready(state)
    loss = float(loss)
    if on_step is not None:
        on_step(trainer, state, loss, timer.elapsed())
    return state, loss


def _extra_payload(state, epoch: int):
    """Everything beyond the K-FAC durable state needed to resume exactly:
    params, optimizer state (momentum), mutable model state (batch_stats),
    and the epoch to restart from."""
    import numpy as np

    extra = {
        'params': state.params,
        'opt_state': state.opt_state,
        'epoch': np.asarray(epoch, np.int32),
    }
    if state.model_state is not None:
        extra['model_state'] = state.model_state
    return extra


def _epoch_dir(checkpoint_dir: str, epoch: int) -> str:
    import os

    return os.path.join(os.path.abspath(checkpoint_dir), f'e{epoch:05d}')


def save_checkpoint(
    checkpoint_dir, state, epoch: int = 0, kfac_engine=None
) -> None:
    """Write the full training state via orbax into an epoch-versioned
    subdirectory (the reference keeps per-epoch files and resumes the
    latest, examples/torch_cifar10_resnet.py:313-354). Pass ``kfac_engine``
    to record the state-layout manifest so later restores under a changed
    config (e.g. another platform's bucket_granularity default) migrate
    instead of failing."""
    from kfac_tpu import checkpoint

    path = _epoch_dir(checkpoint_dir, epoch)
    extra = _extra_payload(state, epoch)
    if state.kfac_state is not None:
        checkpoint.save(
            path + '/kfac', state.kfac_state, extra=extra, engine=kfac_engine
        )
    else:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckptr.save(path + '/plain', extra)
        ckptr.wait_until_finished()
    print(f'checkpoint written to {path}')


def latest_checkpoint(checkpoint_dir) -> tuple[str, int] | None:
    """Scan for the newest epoch-versioned checkpoint; None if absent."""
    import os
    import re

    root = os.path.abspath(checkpoint_dir)
    if not os.path.isdir(root):
        return None
    epochs = [
        int(m.group(1))
        for d in os.listdir(root)
        if (m := re.fullmatch(r'e(\d+)', d))
    ]
    # newest epoch whose payload actually committed (orbax writes the
    # kfac/plain subdir atomically by rename; a bare eNNNNN dir means the
    # process died mid-save — fall back to the previous complete one)
    for e in sorted(epochs, reverse=True):
        path = _epoch_dir(checkpoint_dir, e)
        if os.path.isdir(os.path.join(path, 'kfac')) or os.path.isdir(
            os.path.join(path, 'plain')
        ):
            return path, e
    return None


def restore_checkpoint(checkpoint_dir, state_template, kfac_engine):
    """Restore the latest checkpoint into ``state_template``'s structure.

    Returns ``(state, next_epoch)`` or None when no checkpoint exists.
    K-FAC decompositions are recomputed from the restored factors
    (reference semantics: derived state is not persisted,
    kfac/base_preconditioner.py:215-308).
    """
    from kfac_tpu import checkpoint

    found = latest_checkpoint(checkpoint_dir)
    if found is None:
        return None
    path, epoch = found
    extra_t = _extra_payload(state_template, 0)
    if state_template.kfac_state is not None:
        kstate, extra = checkpoint.restore(
            path + '/kfac', kfac_engine, extra_template=extra_t
        )
    else:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        extra = ckptr.restore(path + '/plain', target=extra_t)
        kstate = None
    mesh = getattr(kfac_engine, 'mesh', None)
    if mesh is not None:
        # orbax returns committed single-device arrays; replicate them over
        # the training mesh so they compose with the sharded K-FAC state
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        extra = jax.tree_util.tree_map(
            lambda r: jax.device_put(r, rep), extra
        )
    state = state_template._replace(
        params=extra['params'],
        opt_state=extra['opt_state'],
        kfac_state=kstate,
        model_state=extra.get('model_state', state_template.model_state),
    )
    print(f'resumed from {path} (epoch {epoch})')
    return state, epoch + 1
