"""ImageNet-class ResNet-50 trainer with K-FAC (reference parity:
examples/torch_imagenet_resnet.py).

Label-smoothing loss and the reference's K-FAC cadence defaults
(inv every 100 steps, factors every 10: torch_imagenet_resnet.py:158-167).
Without an on-disk dataset it runs on ImageNet-shaped synthetic data —
useful for throughput and K-FAC-overhead measurement on real hardware.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, '.')
import kfac_tpu
from examples import common, data
from kfac_tpu import training
from kfac_tpu.models import resnet
from kfac_tpu.parallel import batch_sharding, kaisa_mesh


def main(argv=None, on_step=None) -> float:
    """``on_step``: see :func:`examples.common.timed_step`."""
    p = argparse.ArgumentParser(description='ImageNet ResNet-50 + K-FAC')
    p.add_argument('--image-size', type=int, default=224)
    p.add_argument(
        '--arch', default='resnet50',
        choices=['resnet50', 'resnet20', 'resnet32', 'resnet56'],
        help='resnet50 is the reference configuration '
        '(torch_imagenet_resnet.py); the CIFAR-style depths exist for '
        'smoke tests and small-image runs — a full ResNet-50 K-FAC '
        'compile takes tens of minutes on a 1-core host',
    )
    p.add_argument('--label-smoothing', type=float, default=0.1)
    p.add_argument(
        '--native-loader', action='store_true',
        help='C++ prefetch loader; reads memory-mapped imagenet_x_train.npy '
             'directly from disk with in-worker crop/flip augmentation',
    )
    common.add_train_args(p)
    common.add_kfac_args(p)
    common.add_metrics_args(p)
    args = p.parse_args(argv)

    common.distributed_init()

    world = len(jax.devices())
    frac = common.strategy_fraction(args.kfac_strategy, world)
    mesh = kaisa_mesh(grad_worker_fraction=frac)
    bs = batch_sharding(mesh)

    real_data = data.imagenet_on_disk(args.data_dir)
    # synthetic data (no --data-dir): enough images for --limit-steps
    # batches whatever the device count made of the global batch
    (x_train, y_train), (x_test, y_test) = data.imagenet_like(
        args.data_dir, image_size=args.image_size,
        n_train=max(args.batch_size * max(8, args.limit_steps or 0), 1024),
        n_test=args.batch_size * 2,
    )
    augment = real_data if args.augment is None else args.augment
    model = getattr(resnet, args.arch)(
        num_classes=1000, dtype=jnp.bfloat16 if args.bf16 else jnp.float32
    )
    sample = jnp.asarray(x_train[: args.batch_size])
    variables = model.init(jax.random.PRNGKey(args.seed), sample, train=True)
    registry = kfac_tpu.register_model(
        model, sample, train=False, skip_layers=args.kfac_skip_layers
    )
    print(f'registered {len(registry)} K-FAC layers on {world} devices')

    steps_per_epoch = len(x_train) // args.batch_size
    if args.limit_steps:
        steps_per_epoch = min(steps_per_epoch, args.limit_steps)
    lr_sched = common.make_lr_schedule(
        args.lr, steps_per_epoch, args.epochs, args.warmup_epochs, args.lr_decay
    )
    kfac = common.build_kfac(args, registry, mesh=mesh, lr=lr_sched)
    optimizer = optax.chain(
        optax.add_decayed_weights(args.weight_decay),
        optax.sgd(lr_sched, momentum=args.momentum),
    )

    def loss_fn(params, model_state, batch):
        xb, yb = batch
        logits, updates = model.apply(
            {'params': params, 'batch_stats': model_state}, xb, train=True,
            mutable=['batch_stats'],
        )
        return (
            common.label_smoothing_loss(logits, yb, 1000, args.label_smoothing),
            updates['batch_stats'],
        )

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optimizer, kfac=kfac, donate_state=True
    )
    state = trainer.init(variables['params'], variables['batch_stats'])

    start_epoch = 0
    if args.resume and args.checkpoint_dir:
        restored = common.restore_checkpoint(args.checkpoint_dir, state, kfac)
        if restored is not None:
            state, start_epoch = restored
            trainer.resume(state)

    # x_train may be a read-only float32 memmap (the native loader's worker
    # then reads pages straight from disk), so normalization happens
    # per-batch rather than in place
    epoch_batches = common.make_epoch_batches(
        args, x_train, y_train, augment, start_epoch=start_epoch,
        normalize_stats=(
            (data.IMAGENET_MEAN, data.IMAGENET_STD) if real_data else None
        ),
    )

    # jitted: un-jitted, a ResNet-50 forward compiles op by op on a TPU
    @jax.jit
    def predict(params, batch_stats, xb):
        return model.apply(
            {'params': params, 'batch_stats': batch_stats}, xb, train=False
        )

    acc_val = 0.0
    writer = common.MetricsWriter(args.metrics_csv)
    for epoch in range(start_epoch, args.epochs):
        epoch_timer = common.Timer()
        train_loss = common.Metric()
        n_steps = 0
        for step, (xb, yb) in enumerate(epoch_batches(epoch)):
            if args.limit_steps and step >= args.limit_steps:
                break
            batch = (
                jax.device_put(jnp.asarray(xb), bs),
                jax.device_put(jnp.asarray(yb), bs),
            )
            state, loss = common.timed_step(trainer, state, batch, on_step)
            train_loss.update(loss, len(xb))
            n_steps += 1
        train_secs = epoch_timer.elapsed()
        acc = common.Metric()
        for eval_step, (xb, yb) in enumerate(
            data.batches(x_test, y_test, args.batch_size, 0)
        ):
            if args.limit_steps and eval_step >= args.limit_steps:
                break
            if real_data:
                xb = data.normalize(xb, data.IMAGENET_MEAN, data.IMAGENET_STD)
            logits = predict(
                state.params, state.model_state, jnp.asarray(xb)
            )
            acc.update(common.accuracy(logits, jnp.asarray(yb)), len(xb))
        acc_val = acc.avg
        imgs = n_steps * args.batch_size
        print(
            f'epoch {epoch}: loss={train_loss.avg:.4f} acc={acc_val:.4f} '
            f'{imgs / max(train_secs, 1e-9):.1f} img/s'
        )
        writer.write_many(
            epoch,
            {'train_loss': train_loss.avg, 'test_acc': acc_val,
             'img_per_s': imgs / max(train_secs, 1e-9)},
        )
        if args.checkpoint_dir:
            common.save_checkpoint(
                args.checkpoint_dir, state, epoch, kfac_engine=trainer.kfac
            )
    writer.close()
    return acc_val


if __name__ == '__main__':
    main()
