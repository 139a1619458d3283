"""Rehearsals that cost no chip time (on-chip-measurement guide, section 2).

    python3 benchmark/rehearse.py tiny <cell>
        the cell end to end on the CPU at a tiny preset (which exists only
        here), through the same harness.run_cell as a chip run. It prints
        what the run printed and never a result under a device metric's name.
    python3 benchmark/rehearse.py memory <cell> [--batch N]
        the cell's capture step and first-order step compiled for a
        described v5e (no chip attached) at the real size, to read the
        memory the compiler plans: this is how batch_per_chip was chosen
        (beside the other trainer's state until PR 31; each alone since).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY = {
    'vision': {
        'model': {'stage_sizes': [1, 1, 1, 1], 'image_size': 32,
                  'num_classes': 10},
        'batch_per_chip': 4, 'compute_dtype': 'float32',
    },
    'lm': {
        'model': {'n_embd': 32, 'n_layer': 2, 'n_head': 4, 'n_positions': 32,
                  'vocab_size': 128, 'mlp_ratio': 4},
        'seq_len': 32, 'batch_per_chip': 4, 'compute_dtype': 'float32',
    },
    'hybrid_lm': {
        'hidden_size': 32, 'head_dim': 16, 'num_attention_heads': 4,
        'num_key_value_heads': 2, 'linear_num_key_heads': 2,
        'linear_num_value_heads': 4, 'linear_key_head_dim': 8,
        'linear_value_head_dim': 8, 'moe_intermediate_size': 16,
        'shared_expert_intermediate_size': 16, 'num_experts_per_tok': 3,
        'router_width': 16, 'experts_held': [4, 4], 'num_experts': 4,
        'vocab_size': 64, 'seq_len': 19, 'compute_dtype': 'float32',
        'scan_chunk': 4, 'attention_chunk': 8, 'expert_block_rows': 4,
        'batch_per_chip': 3,
    },
}


def tiny_cell(cell: dict) -> dict:
    """The cell at the rehearsal's size and a cadence that fits in a few
    steps; the inverse method named, because off a TPU the library's
    default is the eigen method and a chip run never takes that."""
    cell = copy.deepcopy(cell)
    preset = TINY[cell['config']['kind']]
    for key, value in preset.items():
        if isinstance(value, dict):
            cell['config'][key].update(value)
        else:
            cell['config'][key] = value
    cell['workload']['kfac'].update(
        factor_update_steps=4, inv_update_steps=8, compute_method='inverse'
    )
    cell['workload'].update(first_order_steps=3, ring=4)
    return cell


def run_tiny(args) -> None:
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax

    from benchmark import harness

    cell = tiny_cell(harness.load_cell(args.cell))
    devices = jax.devices()[:cell['chips']]
    result = harness.run_cell(
        cell, args.seed, args.seconds, False, devices, time.perf_counter()
    )
    # a CPU rehearsal reports no device number: only what it counted
    print('rehearsal:', json.dumps({
        'correct': result['correct'], 'attempted': result['attempted'],
        'failed': result['failed'], 'platform': result['device']['platform'],
        'metrics_reported': sorted(result['metrics']),
    }))


def compile_for_v5e(args) -> None:
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    from examples import common
    from kfac_tpu import training

    from benchmark import harness, jobs

    # the dispatch gates and the platform defaults ask the backend: answer
    # as the chip would, in this script and nowhere in the program
    jax.default_backend = lambda: 'tpu'
    jax.config.update('jax_enable_compilation_cache', False)

    cell = harness.load_cell(args.cell)
    if args.batch:
        cell['config']['batch_per_chip'] = args.batch
    topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
    devices = list(topo.devices)[:cell['chips']]
    job = jobs.load(cell['config']['kind']).build(
        cell['config'], cell['workload'], devices
    )
    job.kfac_args.kfac_compile_watch = False
    rep = NamedSharding(job.mesh, PartitionSpec())

    def placed(tree, sharding):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
            tree,
        )

    variables = placed(job.variable_shapes, rep)
    lr = job.lr_schedule
    report = {}
    for name, with_kfac in (('kfac', True), ('first_order', False)):
        engine = (
            common.build_kfac(job.kfac_args, job.registry, mesh=job.mesh, lr=lr)
            if with_kfac else None
        )
        trainer = training.Trainer(
            loss_fn=job.loss_fn, optimizer=job.make_optimizer(lr),
            kfac=engine, donate_state=True,
        )
        state = jax.eval_shape(
            lambda: trainer.init(
                variables['params'], variables.get('batch_stats')
            )
        )
        kfac_sh = engine.state_shardings() if with_kfac else None
        state = training.TrainState(
            params=placed(state.params, rep),
            opt_state=placed(state.opt_state, rep),
            kfac_state=None if not with_kfac else jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                state.kfac_state, kfac_sh,
            ),
            model_state=(
                None if state.model_state is None
                else placed(state.model_state, rep)
            ),
        )
        batch = _batch_shapes(cell, job)
        steps = {'capture': trainer._jit_with_stats} if with_kfac else {}
        steps['plain'] = trainer._jit_no_stats
        for variant, fn in steps.items():
            began = time.perf_counter()
            compiled = fn.lower(state, batch).compile()
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            report[f'{name}/{variant}'] = {
                'argument_gb': mem.argument_size_in_bytes / 1e9,
                'output_gb': mem.output_size_in_bytes / 1e9,
                'alias_gb': mem.alias_size_in_bytes / 1e9,
                'temp_gb': mem.temp_size_in_bytes / 1e9,
                'code_gb': mem.generated_code_size_in_bytes / 1e9,
                'live_gb': (
                    mem.argument_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes + mem.temp_size_in_bytes
                    + mem.generated_code_size_in_bytes
                ) / 1e9,
                'tpu_custom_calls': text.count('tpu_custom_call'),
                'compile_s': round(time.perf_counter() - began, 1),
            }
            print(name, variant, json.dumps(report[f'{name}/{variant}']),
                  flush=True)
    if cell['config']['kind'] == 'vision':  # the lm reference runs in row blocks
        import importlib

        ref = importlib.import_module('benchmark.refs.vision')
        _, loss_grads_factors = ref.make(cell['config'])
        began = time.perf_counter()
        mem = loss_grads_factors.lower(
            variables['params'], _batch_shapes(cell, job)
        ).compile().memory_analysis()
        print('reference step 0', json.dumps({
            'live_gb': (
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
            ) / 1e9,
            'temp_gb': mem.temp_size_in_bytes / 1e9,
            'compile_s': round(time.perf_counter() - began, 1),
        }), flush=True)
    # one trainer's state is on the chip at a time: the larger decides
    print(json.dumps({
        'cell': args.cell, 'batch_per_chip': cell['config']['batch_per_chip'],
        'capture_step_live_gb': report['kfac/capture']['live_gb'],
        'first_order_step_live_gb': report['first_order/plain']['live_gb'],
    }))


def _batch_shapes(cell, job):
    import jax
    import jax.numpy as jnp

    cfg = cell['config']
    n = job.global_batch
    if cfg['kind'] == 'vision':
        size = cfg['model']['image_size']
        shapes = ((n, size, size, 3), jnp.float32), ((n,), jnp.int32)
    else:
        shapes = ((n, cfg['seq_len']), jnp.int32), ((n, cfg['seq_len']), jnp.int32)
    return tuple(
        jax.ShapeDtypeStruct(s, d, sharding=job.batch_sharding)
        for s, d in shapes
    )


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest='mode', required=True)
    t = sub.add_parser('tiny')
    t.add_argument('cell')
    t.add_argument('--seed', type=int, default=3000000019)
    t.add_argument('--seconds', type=float, default=2.0)
    m = sub.add_parser('memory')
    m.add_argument('cell')
    m.add_argument('--batch', type=int, default=0)
    args = p.parse_args()
    if args.mode == 'tiny':
        run_tiny(args)
    else:
        compile_for_v5e(args)


if __name__ == '__main__':
    main()
