"""Language-model job, built as ``examples/train_language_model.py`` builds
it: ``TransformerLM`` -> ``register_model`` (``lm_head`` skipped, as the
reference example skips its decoder) -> ``build_kfac`` on ``train_mesh`` ->
next-token cross entropy, global-norm clip + SGD momentum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kfac_tpu
from examples import common
from kfac_tpu.models import TransformerLM, lm_loss
from kfac_tpu.parallel import token_sharding, train_mesh

from benchmark import jobs

_DTYPES = {'bfloat16': jnp.bfloat16, 'float32': jnp.float32}


def build(config: dict, workload: dict, devices) -> jobs.Job:
    m, opt = config['model'], config['optimizer']
    world = len(devices)
    args = jobs.kfac_namespace(workload, opt['lr'])
    mesh = train_mesh(
        grad_worker_fraction=common.strategy_fraction(
            args.kfac_strategy, world
        ),
        devices=devices,
    )
    global_batch = config['batch_per_chip'] * world
    seq, vocab = config['seq_len'], m['vocab_size']
    model = TransformerLM(
        vocab_size=vocab, d_model=m['n_embd'], num_heads=m['n_head'],
        num_layers=m['n_layer'], mlp_ratio=m['mlp_ratio'],
        max_len=m['n_positions'], dtype=_DTYPES[config['compute_dtype']],
    )
    # shapes only: registration and eval_shape never run the model
    sample = jnp.zeros((world, seq), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample)
    )
    registry = kfac_tpu.register_model(
        model, sample, skip_layers=args.kfac_skip_layers
    )
    loss = lm_loss(model)

    def loss_fn(params, model_state, batch):
        return loss(params, batch), model_state

    def make_optimizer(lr_sched):
        return optax.chain(
            optax.clip_by_global_norm(opt['clip_global_norm']),
            optax.sgd(lr_sched, momentum=opt['momentum']),
        )

    def make_ring(seed, n):
        # Zipf(1.3) token ids clipped to the vocabulary, as
        # examples.data.lm_corpus draws its synthetic stream; every
        # sequence a window of its own
        rng = np.random.default_rng([int(seed), 0x70C5])
        toks = rng.zipf(1.3, size=(n, global_batch, seq + 1))
        toks = np.clip(toks, 1, vocab - 1).astype(np.int32)
        return [(t[:, :-1], t[:, 1:]) for t in toks]

    return jobs.Job(
        kind='lm', model=model, mesh=mesh, variable_shapes=shapes,
        registry=registry, loss_fn=loss_fn, make_optimizer=make_optimizer,
        lr_schedule=jobs.warmup_schedule(opt['lr'], opt['warmup_steps']),
        kfac_args=args, batch_sharding=token_sharding(mesh),
        global_batch=global_batch, make_ring=make_ring,
    )
