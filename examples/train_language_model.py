"""Transformer LM trainer with K-FAC (reference example parity:
examples/torch_language_model.py).

Like the reference, attention projections and the output head can be
excluded from K-FAC via skip patterns (the reference skips
embedding/decoder/self_attn by default, torch_language_model.py:163-168);
here the default preconditioners everything dense and ``--kfac-skip-layers
'.*attn.*' lm_head`` reproduces the reference default.

Supports context parallelism (``--seq-shards``) via ring attention and
tensor parallelism (``--model-shards``) via Megatron-style layout rules.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, '.')
import kfac_tpu
from examples import common, data
from kfac_tpu import training
from kfac_tpu.models import (
    ConvMoELM,
    HybridLM,
    LatentMoELM,
    TransformerLM,
    hybrid_lm_loss,
    lm_loss,
)
from kfac_tpu.parallel import tensor_parallel, token_sharding, train_mesh
from kfac_tpu.parallel.mesh import SEQ_AXIS


def _run_epochs(args, tokens_np, step_fn, start_epoch=0, on_epoch_end=None):
    """Shared epoch/step loop: corpus windows, limit-steps, perplexity.

    ``step_fn(xb, yb) -> loss`` advances whatever training state the caller
    closes over; ``on_epoch_end(epoch)`` handles checkpoints.
    """
    timer = common.Timer()
    writer = common.MetricsWriter(getattr(args, 'metrics_csv', None))
    final_ppl = float('inf')
    for epoch in range(start_epoch, args.epochs):
        lm = common.Metric()
        for step, (xb, yb) in enumerate(
            data.lm_batches(tokens_np, args.batch_size, args.seq_len,
                            args.seed + epoch)
        ):
            if args.limit_steps and step >= args.limit_steps:
                break
            lm.update(float(step_fn(xb, yb)), xb.size)
        final_ppl = float(np.exp(min(20.0, lm.avg)))
        print(
            f'epoch {epoch}: train_loss={lm.avg:.4f} ppl={final_ppl:.1f} '
            f'elapsed={timer.elapsed():.1f}s'
        )
        writer.write_many(
            epoch,
            {'train_loss': lm.avg, 'ppl': final_ppl,
             'elapsed_s': timer.elapsed()},
        )
        if on_epoch_end is not None:
            on_epoch_end(epoch)
    writer.close()
    return final_ppl


def _steps_per_epoch(args, tokens_np) -> int:
    steps = (len(tokens_np) - 1) // (args.seq_len * args.batch_size)
    if args.limit_steps:
        steps = min(steps, args.limit_steps)
    return steps


def main(argv=None, on_step=None) -> float:
    """``on_step``: see :func:`examples.common.timed_step` (not called on
    the ``--pipeline-stages`` path, which has its own step)."""
    p = argparse.ArgumentParser(description='Transformer LM + K-FAC')
    p.add_argument('--d-model', type=int, default=256)
    p.add_argument('--num-heads', type=int, default=8)
    p.add_argument('--num-layers', type=int, default=4)
    p.add_argument('--seq-len', type=int, default=256)
    p.add_argument('--vocab-size', type=int, default=8192)
    p.add_argument(
        '--model',
        choices=['transformer', 'hybrid', 'conv-moe', 'latent-moe'],
        default='transformer',
        help="'hybrid': the sparse hybrid decoder (models.HybridLM: Gated "
        'DeltaNet layers with a gated-attention layer every fourth, top-k '
        'routed experts with a shared expert), sized from --d-model: heads '
        'of d/8 (attention) and d/16 (DeltaNet), experts d/4 wide. '
        "'conv-moe': the conv-hybrid sparse decoder (models.ConvMoELM: "
        'gated short convolutions with a grouped-query attention layer '
        'every fourth, one leading dense MLP 4 d wide, then sigmoid-routed '
        'experts d/2 wide with a selection bias); name the dense MLP in '
        "--kfac-skip-layers ('block0/mlp/.*') to leave it to the "
        "first-order update. 'latent-moe': the latent-attention sparse "
        'decoder (models.LatentMoELM: multi-head latent attention with a '
        'latent d/4 wide and heads of d/heads without positions beside a '
        'rotary part half as wide, one leading dense MLP 3 d wide, then '
        'sigmoid-routed experts 3 d/8 wide with a selection bias and a '
        'routing scale beside two ungated shared experts, an untied head)',
    )
    p.add_argument(
        '--num-experts', type=int, default=16,
        help='hybrid, conv-moe, latent-moe: experts the router scores',
    )
    p.add_argument('--experts-per-token', type=int, default=2)
    p.add_argument(
        '--experts-held', type=int, nargs=2, default=None,
        metavar=('FIRST', 'COUNT'),
        help='hybrid, conv-moe, latent-moe: the share of every layer\'s '
        'experts that lives in this process (an expert-parallel rank\'s); '
        'the router still scores '
        'all of them, and what the absent ones would add is left out. '
        'Default: all',
    )
    p.add_argument('--model-shards', type=int, default=1)
    p.add_argument('--seq-shards', type=int, default=1)
    p.add_argument(
        '--pipeline-stages', type=int, default=0,
        help='pipeline the transformer blocks over this many stages '
        '(remaining devices become data-parallel peers); the reference '
        'reaches this via kfac.gpt_neox + DeepSpeed pipeline configs',
    )
    p.add_argument('--pipeline-microbatches', type=int, default=4)
    p.add_argument(
        '--pipeline-schedule',
        choices=['gpipe', '1f1b', 'interleaved'], default='1f1b',
        help="'interleaved' runs the single-slot Megatron virtual-stage "
        'schedule (--virtual-chunks model chunks per rank; microbatches '
        'must be a multiple of the stage count)',
    )
    p.add_argument(
        '--virtual-chunks', type=int, default=2,
        help='model chunks per pipeline rank under '
        '--pipeline-schedule=interleaved (bubble ~ 2*(p-1)/v stage-units)',
    )
    common.add_train_args(p)
    common.add_kfac_args(p)
    common.add_metrics_args(p)
    args = p.parse_args(argv)

    common.distributed_init()

    if args.pipeline_stages:
        return _pipeline_main(args)

    world = len(jax.devices())
    dp = world // (args.model_shards * args.seq_shards)
    frac = common.strategy_fraction(args.kfac_strategy, dp)
    mesh = train_mesh(
        grad_worker_fraction=frac, model=args.model_shards,
        seq=args.seq_shards,
    )
    tokens_np, vocab = data.lm_corpus(args.data_dir, args.vocab_size)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    sparse = args.model in ('hybrid', 'conv-moe', 'latent-moe')
    if sparse and (args.model_shards > 1 or args.seq_shards > 1):
        raise SystemExit(f'--model {args.model} runs data-parallel only')
    experts_held = tuple(args.experts_held) if args.experts_held else None
    if args.model == 'conv-moe':
        d = args.d_model
        model = ConvMoELM(
            vocab_size=vocab, d_model=d,
            layer_types=tuple(
                'full_attention' if i % 4 == 1 else 'conv'
                for i in range(args.num_layers)
            ),
            num_dense_layers=1, dense_width=4 * d, num_heads=args.num_heads,
            num_kv_heads=max(1, args.num_heads // 4),
            head_dim=d // args.num_heads,
            num_experts=args.num_experts, top_k=args.experts_per_token,
            expert_width=d // 2, experts_held=experts_held,
            attention_chunk=min(1024, args.seq_len), dtype=dtype,
        )
    elif args.model == 'latent-moe':
        d = args.d_model
        model = LatentMoELM(
            vocab_size=vocab, d_model=d, num_layers=args.num_layers,
            num_dense_layers=1, dense_width=3 * d, num_heads=args.num_heads,
            qk_nope_head_dim=d // args.num_heads,
            qk_rope_head_dim=d // args.num_heads // 2,
            v_head_dim=d // args.num_heads, kv_lora_rank=d // 4,
            num_experts=args.num_experts, top_k=args.experts_per_token,
            expert_width=3 * d // 8, experts_held=experts_held,
            attention_chunk=min(1024, args.seq_len), dtype=dtype,
        )
    elif args.model == 'hybrid':
        d = args.d_model
        model = HybridLM(
            vocab_size=vocab, d_model=d, num_layers=args.num_layers,
            num_heads=args.num_heads,
            num_kv_heads=max(1, args.num_heads // 4),
            head_dim=d // args.num_heads * 2,
            linear_num_key_heads=args.num_heads,
            linear_num_value_heads=2 * args.num_heads,
            linear_key_head_dim=d // args.num_heads,
            linear_value_head_dim=d // args.num_heads,
            num_experts=args.num_experts, top_k=args.experts_per_token,
            expert_width=d // 4, shared_expert_width=d // 4,
            experts_held=experts_held,
            attention_chunk=min(1024, args.seq_len), dtype=dtype,
        )
    else:
        model = TransformerLM(
            vocab_size=vocab,
            d_model=args.d_model,
            num_heads=args.num_heads,
            num_layers=args.num_layers,
            max_len=args.seq_len,
            dtype=dtype,
            ring_mesh=mesh if args.seq_shards > 1 else None,
            ring_axis=SEQ_AXIS if args.seq_shards > 1 else None,
        )
    sample = jnp.zeros((args.batch_size, args.seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.seed), sample)['params']
    if args.model_shards > 1:
        params = tensor_parallel.shard_params(params, mesh)
    registry = kfac_tpu.register_model(
        model, sample, skip_layers=args.kfac_skip_layers
    )
    print(f'registered {len(registry)} K-FAC layers; mesh {dict(mesh.shape)}')

    loss = (hybrid_lm_loss if sparse else lm_loss)(model)

    def loss_fn(params, model_state, batch):
        return loss(params, batch), model_state

    lr_sched = common.make_lr_schedule(
        args.lr, _steps_per_epoch(args, tokens_np), args.epochs,
        args.warmup_epochs, args.lr_decay,
    )
    kfac = common.build_kfac(args, registry, mesh=mesh, lr=lr_sched)
    optimizer = optax.chain(
        optax.clip_by_global_norm(1.0),  # grad-norm clip before precondition
        optax.sgd(lr_sched, momentum=args.momentum),
    )
    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optimizer, kfac=kfac, donate_state=True
    )
    state = trainer.init(params)

    start_epoch = 0
    if args.resume and args.checkpoint_dir:
        restored = common.restore_checkpoint(args.checkpoint_dir, state, kfac)
        if restored is not None:
            state, start_epoch = restored
            trainer.resume(state)

    ts = token_sharding(mesh)

    def step_fn(xb, yb):
        nonlocal state
        batch = (
            jax.device_put(jnp.asarray(xb), ts),
            jax.device_put(jnp.asarray(yb), ts),
        )
        state, l = common.timed_step(trainer, state, batch, on_step)
        return l

    def on_epoch_end(epoch):
        if args.checkpoint_dir:
            common.save_checkpoint(
                args.checkpoint_dir, state, epoch, kfac_engine=trainer.kfac
            )

    return _run_epochs(
        args, tokens_np, step_fn, start_epoch=start_epoch,
        on_epoch_end=on_epoch_end,
    )


def _pipeline_main(args) -> float:
    """Pipeline-parallel training path (DP x PP on one mesh).

    K-FAC state is stage-sharded (MEM-OPT among pipe peers); the 1F1B
    schedule computes loss, grads, and curvature stats in one scan.
    """
    from kfac_tpu.parallel import PipelinedLM, PipelineKFAC
    from kfac_tpu.parallel.mesh import pipeline_mesh

    if args.seq_shards > 1:
        raise SystemExit(
            '--pipeline-stages does not compose with --seq-shards; '
            'sequence parallelism requires the non-pipelined path'
        )
    # DP x TP x PP on one mesh: --model-shards shards stage weights over
    # the (automatic) model axis inside the pipeline schedule
    pmesh = pipeline_mesh(
        n_stages=args.pipeline_stages, model=args.model_shards
    )
    tokens_np, vocab = data.lm_corpus(args.data_dir, args.vocab_size)
    kw = dict(
        mesh=pmesh,
        vocab_size=vocab,
        d_model=args.d_model,
        num_heads=args.num_heads,
        num_layers=args.num_layers,
        n_microbatches=args.pipeline_microbatches,
        max_len=args.seq_len,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        skip_layers=tuple(args.kfac_skip_layers),
    )
    if args.pipeline_schedule == 'interleaved':
        from kfac_tpu.parallel import InterleavedPipelinedLM

        plm = InterleavedPipelinedLM(
            virtual_chunks=args.virtual_chunks, **kw
        )
    else:
        plm = PipelinedLM(schedule=args.pipeline_schedule, **kw)
    params = plm.init(jax.random.PRNGKey(args.seed))
    print(
        f'pipeline: {args.pipeline_stages} ranks x '
        f'{dict(pmesh.shape)} mesh, {args.pipeline_microbatches} '
        f'microbatches, schedule={args.pipeline_schedule} '
        f'({plm.n_stages} logical stages); '
        f'{len(plm.stage_registry)} K-FAC layers per stage'
    )

    lr_sched = common.make_lr_schedule(
        args.lr, _steps_per_epoch(args, tokens_np), args.epochs,
        args.warmup_epochs, args.lr_decay,
    )
    cfg = common.build_kfac(
        args, plm.stage_registry, lr=lr_sched, verbose_dump=False
    )
    pk = PipelineKFAC(config=cfg, model=plm) if cfg is not None else None
    if pk is not None and args.kfac_verbose:
        print(pk.describe())
    optimizer = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.sgd(lr_sched, momentum=args.momentum),
    )
    pstate = pk.init() if pk is not None else None
    opt_state = optimizer.init(params)

    start_epoch = 0
    if args.resume and args.checkpoint_dir and pk is not None:
        from kfac_tpu import checkpoint as ckpt_lib

        found = common.latest_checkpoint(args.checkpoint_dir)
        if found is not None:
            path, epoch = found
            pstate, extra = ckpt_lib.restore(
                path + '/kfac', pk,
                extra_template={
                    'params': params,
                    'opt_state': opt_state,
                    'epoch': np.asarray(0, np.int32),
                },
            )
            params, opt_state = extra['params'], extra['opt_state']
            start_epoch = int(extra['epoch']) + 1
            print(f'resumed from {path} (epoch {epoch})')

    @jax.jit
    def train_step(params, pstate, opt_state, batch):
        loss, grads, stats = plm.loss_and_stats(params, batch)
        if pk is not None:
            pstate, grads = pk.step(pstate, grads, stats)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), pstate, opt_state, loss

    def step_fn(xb, yb):
        nonlocal params, pstate, opt_state
        params, pstate, opt_state, l = train_step(
            params, pstate, opt_state, (jnp.asarray(xb), jnp.asarray(yb))
        )
        return l

    def on_epoch_end(epoch):
        if args.checkpoint_dir and pk is not None:
            from kfac_tpu import checkpoint as ckpt_lib

            path = common._epoch_dir(args.checkpoint_dir, epoch)
            ckpt_lib.save(
                path + '/kfac', pstate,
                extra={
                    'params': params,
                    'opt_state': opt_state,
                    'epoch': np.asarray(epoch, np.int32),
                },
                engine=pk,
            )
            print(f'checkpoint written to {path}')

    return _run_epochs(
        args, tokens_np, step_fn, start_epoch=start_epoch,
        on_epoch_end=on_epoch_end,
    )


if __name__ == '__main__':
    main()
