"""Latent-attention sparse LM job (multi-head latent attention, a leading
dense MLP, then scaled sigmoid-routed experts of which a share is held
beside ungated shared experts), built as ``examples/train_language_model.py
--model latent-moe`` builds it: ``LatentMoELM`` -> ``register_model``
(``lm_head`` skipped) -> ``build_kfac`` on ``train_mesh`` -> next-token
cross entropy over the vocabulary held, global-norm clip + SGD momentum.

The configuration's sizes sit at its top level under the source's own keys
(``benchmark/configs/<name>.json``). ``source_layers`` names the source's
layers that are here, in order (those ahead of ``first_k_dense_replace``
carry the dense MLP); ``experts_held`` is ``[first, count]``, and
``router_width`` the experts the router scores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kfac_tpu
from examples import common
from kfac_tpu.models import LatentMoELM, hybrid_lm_loss
from kfac_tpu.parallel import token_sharding, train_mesh

from benchmark import jobs
from benchmark.flops import latent_moe_lm as flops

_DTYPES = {'bfloat16': jnp.bfloat16, 'float32': jnp.float32}
# what the model computes and has no option for: a configuration that says
# otherwise is another model
_FIXED = {
    'q_lora_rank': None, 'rope_scaling': None, 'rope_interleave': True,
    'attention_bias': False, 'tie_word_embeddings': False,
    'hidden_act': 'silu', 'scoring_func': 'sigmoid',
    'topk_method': 'noaux_tc', 'n_group': 1, 'topk_group': 1,
    'moe_layer_freq': 1,
}


def model_of(config: dict) -> LatentMoELM:
    c = config
    other = {k: c[k] for k, v in _FIXED.items() if c[k] != v}
    if other:
        raise ValueError(f'LatentMoELM has no option for {other}')
    if len(c['source_layers']) != c['num_hidden_layers']:
        raise ValueError(
            f"source_layers names {len(c['source_layers'])} layers, "
            f"num_hidden_layers {c['num_hidden_layers']}"
        )
    return LatentMoELM(
        vocab_size=c['vocab_size'], d_model=c['hidden_size'],
        num_layers=c['num_hidden_layers'],
        num_dense_layers=flops.dense_layers(c),
        dense_width=c['intermediate_size'],
        num_heads=c['num_attention_heads'],
        qk_nope_head_dim=c['qk_nope_head_dim'],
        qk_rope_head_dim=c['qk_rope_head_dim'], v_head_dim=c['v_head_dim'],
        kv_lora_rank=c['kv_lora_rank'], rope_theta=float(c['rope_theta']),
        num_experts=c['router_width'], top_k=c['num_experts_per_tok'],
        expert_width=c['moe_intermediate_size'],
        num_shared_experts=c['n_shared_experts'],
        experts_held=tuple(c['experts_held']),
        norm_topk_prob=c['norm_topk_prob'],
        routed_scale=c['routed_scaling_factor'], norm_eps=c['rms_norm_eps'],
        attention_chunk=c.get('attention_chunk', 1024),
        expert_block_rows=c.get('expert_block_rows', 256),
        loss_chunk=c.get('loss_chunk', 1024),
        dtype=_DTYPES[c['compute_dtype']],
    )


def build(config: dict, workload: dict, devices) -> jobs.Job:
    opt = config['optimizer']
    world = len(devices)
    args = jobs.kfac_namespace(workload, opt['lr'])
    mesh = train_mesh(
        grad_worker_fraction=common.strategy_fraction(
            args.kfac_strategy, world
        ),
        devices=devices,
    )
    global_batch = config['batch_per_chip'] * world
    seq, vocab = config['seq_len'], config['vocab_size']
    model = model_of(config)
    # shapes only: registration and eval_shape never run the model
    sample = jnp.zeros((world, seq), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample)
    )
    registry = kfac_tpu.register_model(
        model, sample, skip_layers=args.kfac_skip_layers
    )
    loss = hybrid_lm_loss(model)

    def loss_fn(params, model_state, batch):
        return loss(params, batch), model_state

    def make_optimizer(lr_sched):
        return optax.chain(
            optax.clip_by_global_norm(opt['clip_global_norm']),
            optax.sgd(lr_sched, momentum=opt['momentum']),
        )

    def make_ring(seed, n):
        # Zipf(1.3) token ids clipped to the vocabulary held, every
        # sequence a window of its own (jobs/hybrid_lm.py's stream)
        rng = np.random.default_rng([int(seed), 0x70C5])
        toks = rng.zipf(1.3, size=(n, global_batch, seq + 1))
        toks = np.clip(toks, 1, vocab - 1).astype(np.int32)
        return [(t[:, :-1], t[:, 1:]) for t in toks]

    return jobs.Job(
        kind='latent_moe_lm', model=model, mesh=mesh, variable_shapes=shapes,
        registry=registry, loss_fn=loss_fn, make_optimizer=make_optimizer,
        lr_schedule=jobs.warmup_schedule(opt['lr'], opt['warmup_steps']),
        kfac_args=args, batch_sharding=token_sharding(mesh),
        global_batch=global_batch, make_ring=make_ring,
    )
