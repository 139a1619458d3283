"""Image-classification job, built as ``examples/train_imagenet_resnet.py``
builds it: ``ImageNetResNet`` -> ``register_model`` -> ``build_kfac`` on
``kaisa_mesh`` -> label-smoothed cross entropy, weight decay + SGD momentum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import kfac_tpu
from examples import common
from kfac_tpu.models import resnet
from kfac_tpu.parallel import batch_sharding, kaisa_mesh

from benchmark import jobs, weights

_DTYPES = {'bfloat16': jnp.bfloat16, 'float32': jnp.float32}


def build(config: dict, workload: dict, devices) -> jobs.Job:
    m, opt = config['model'], config['optimizer']
    world = len(devices)
    args = jobs.kfac_namespace(workload, opt['lr'])
    mesh = kaisa_mesh(
        grad_worker_fraction=common.strategy_fraction(
            args.kfac_strategy, world
        ),
        devices=devices,
    )
    global_batch = config['batch_per_chip'] * world
    size, classes = m['image_size'], m['num_classes']
    model = resnet.ImageNetResNet(
        stage_sizes=tuple(m['stage_sizes']), num_classes=classes,
        dtype=_DTYPES[config['compute_dtype']],
    )
    # shapes only: registration and eval_shape never run the model
    sample = jnp.zeros((world, size, size, 3), jnp.float32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample, train=True)
    )
    registry = kfac_tpu.register_model(
        model, sample, train=False, skip_layers=args.kfac_skip_layers
    )
    smoothing = config['label_smoothing']

    def loss_fn(params, model_state, batch):
        xb, yb = batch
        logits, updates = model.apply(
            {'params': params, 'batch_stats': model_state}, xb, train=True,
            mutable=['batch_stats'],
        )
        return (
            common.label_smoothing_loss(logits, yb, classes, smoothing),
            updates['batch_stats'],
        )

    def make_optimizer(lr_sched):
        return optax.chain(
            optax.add_decayed_weights(opt['weight_decay']),
            optax.sgd(lr_sched, momentum=opt['momentum']),
        )

    @jax.jit
    def one_batch(key):
        kx, ky = jax.random.split(key)
        return (
            jax.random.normal(kx, (global_batch, size, size, 3), jnp.float32),
            jax.random.randint(ky, (global_batch,), 0, classes, jnp.int32),
        )

    def make_ring(seed, n):
        # one batch at a time: a ring of four-chip batches would not sit
        # on one device beside anything else
        key = jax.random.fold_in(weights.seed_key(seed), 0xDA7A)
        return [
            jax.device_get(one_batch(jax.random.fold_in(key, i)))
            for i in range(n)
        ]

    return jobs.Job(
        kind='vision', model=model, mesh=mesh, variable_shapes=shapes,
        registry=registry, loss_fn=loss_fn, make_optimizer=make_optimizer,
        lr_schedule=jobs.warmup_schedule(opt['lr'], opt['warmup_steps']),
        kfac_args=args, batch_sharding=batch_sharding(mesh),
        global_batch=global_batch, make_ring=make_ring,
    )
