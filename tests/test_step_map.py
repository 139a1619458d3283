"""One map of a step (``kfac_tpu/tracing.py`` ``MODEL_SCOPES``,
``TRAINER_SCOPES``; docs/OBSERVABILITY.md "A step that reports on itself"):
the model's parts, the optimizer's update and the engine's own glue are
named in the compiled step programs of every model family a benchmark cell
runs, in the forward and the backward pass, and the benchmark's reader of
the map (``benchmark/layer_metrics/_stepmap.py``) puts each ``op_name``
into one bucket.

Each family's cell at a tiny size, built as ``benchmark/harness.py`` builds
it, compiled on the CPU: what is checked is names on the programs' text. No
number here is a device time.
"""

import functools
import os
import re
import sys

import jax
import pytest
from jax.sharding import NamedSharding, PartitionSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, rehearse, weights  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.layer_metrics import _stepmap  # noqa: E402
from kfac_tpu import tracing  # noqa: E402

CELLS = {
    'lm': 'gpt2-small.kfac-10-100',
    'vision': 'resnet50.kfac-10-100',
    'hybrid_lm': 'qwen3-next-80b-a3b.kfac-10-100',
    'conv_moe_lm': 'lfm2-24b-a2b.kfac-10-100',
}
# tests/test_conv_moe_lm.py's size (rehearse.py has no preset for the kind)
CONV_TINY = dict(
    hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=16, num_experts_per_tok=3,
    router_width=16, experts_held=[4, 4], num_experts=4, vocab_size=64,
    seq_len=19, compute_dtype='float32', attention_chunk=8,
    expert_block_rows=4, batch_per_chip=3,
)
# the parts each family has
PARTS = {
    'lm': ('embed', 'mixer', 'attention', 'mlp', 'norm', 'head', 'loss'),
    'vision': ('stem', 'stage0', 'stage1', 'stage2', 'stage3', 'head', 'loss'),
    'hybrid_lm': ('embed', 'mixer', 'attention', 'gdn_scan', 'mlp',
                  'moe_route', 'moe_experts', 'norm', 'head', 'loss'),
    'conv_moe_lm': ('embed', 'mixer', 'attention', 'short_conv', 'mlp',
                    'moe_route', 'moe_experts', 'norm', 'head', 'loss'),
}
# most instructions with a path for an ``op_name`` that may lie in no
# bucket, a family, as a share of those that have one: the residual add
# behind a routed MLP, and in a capture step the averages that
# ``CurvatureCapture.value_stats_and_grad`` takes behind the backward pass
UNSCOPED_LIMIT = {
    'lm': 0.005, 'vision': 0.005, 'hybrid_lm': 0.03, 'conv_moe_lm': 0.03,
}


def _tiny_cell(family):
    cell = harness.load_cell(CELLS[family])
    if family in rehearse.TINY:
        return rehearse.tiny_cell(cell)
    cell['config'].update(CONV_TINY)
    cell['workload']['kfac'].update(
        factor_update_steps=4, inv_update_steps=8, compute_method='inverse'
    )
    return cell


@functools.lru_cache(maxsize=None)
def _programs(family):
    """{'capture' | 'plain': [op_name, ...]} of the K-FAC trainer's two
    compiled step programs, compiled once a family."""
    run = harness.build_run(_tiny_cell(family), jax.devices()[:1])
    job = run.job
    variables = weights.maker(
        job.variable_shapes, NamedSharding(job.mesh, PartitionSpec())
    )(weights.seed_key(3))
    state = run.trainer.init(variables['params'], variables.get('batch_stats'))
    batch = run.put(job.make_ring(3, 1)[0])
    return {
        kind: list(tr.op_names(
            fn.lower(state, batch).compile().as_text()
        ).values())
        for kind, fn in (
            ('capture', run.trainer._jit_with_stats),
            ('plain', run.trainer._jit_no_stats),
        )
    }


def _under(names, scope):
    return [n for n in names if tr.match_scope(n, (scope,)) is not None]


@pytest.mark.parametrize('family,part', [
    (family, part) for family, parts in PARTS.items() for part in parts
])
def test_plain_step_program_names_the_part_in_both_passes(family, part):
    # the reader's copy of the name reads the program's into the part's
    # bucket: operations whose deepest scope is the part's, in each pass
    assert _stepmap.SCOPES[tracing.MODEL_SCOPES[part]] == part
    passes = {
        p for b, p in map(_stepmap.classify, _programs(family)['plain'])
        if b == part
    }
    assert {'forward', 'backward'} <= passes, (part, passes)


@pytest.mark.parametrize('family', list(PARTS))
def test_a_family_names_no_part_it_does_not_have(family):
    names = _programs(family)['plain']
    for part, scope in tracing.MODEL_SCOPES.items():
        if part not in PARTS[family]:
            assert not _under(names, scope), scope


@pytest.mark.parametrize('family,outer,side', [
    ('lm', 'mlp', 'a'), ('lm', 'mixer', 'g'), ('vision', 'stage0', 'a'),
    ('vision', 'stage0', 'g'), ('hybrid_lm', 'mlp', 'g'),
    ('conv_moe_lm', 'mixer', 'a'), ('hybrid_lm', 'moe_experts', 'a'),
])
def test_a_capture_tap_inside_a_part_is_captures(family, outer, side):
    outer = tracing.MODEL_SCOPES[outer]
    tap = tracing.CAPTURE_SCOPES[side]
    both = [n for n in _under(_programs(family)['capture'], outer)
            if tr.match_scope(n, (tap,))]
    assert both, (outer, tap)
    for n in both:
        assert n.index(outer) < n.index(tap)
        assert _stepmap.classify(n)[0] == 'capture_' + side


@pytest.mark.parametrize('family,outer,inner', [
    ('lm', 'mixer', 'attention'), ('hybrid_lm', 'mixer', 'attention'),
    ('hybrid_lm', 'mixer', 'gdn_scan'), ('conv_moe_lm', 'mixer', 'attention'),
    ('conv_moe_lm', 'mixer', 'short_conv'), ('hybrid_lm', 'head', 'loss'),
    ('conv_moe_lm', 'head', 'loss'),
])
def test_the_deeper_part_wins(family, outer, inner):
    outer, inner = tracing.MODEL_SCOPES[outer], tracing.MODEL_SCOPES[inner]
    names = _under(_programs(family)['plain'], inner)
    nested = [n for n in names if tr.match_scope(n, (outer,))]
    # a mixer's core is always inside the mixer; the loss is inside the
    # head where the head computes it in chunks (its mean is outside)
    assert nested and (len(nested) == len(names) or inner == 'model.loss')
    for n in nested:
        assert n.index(outer) < n.index(inner), n
        assert _stepmap.classify(n)[0] == _stepmap.SCOPES[inner]


@pytest.mark.parametrize('family', list(PARTS))
def test_the_optimizer_is_named_in_both_step_programs(family):
    scope = tracing.TRAINER_SCOPES['optimizer']
    assert scope == _stepmap.OPTIMIZER
    for kind, names in _programs(family).items():
        found = _under(names, scope)
        assert found, kind
        # beside the engine's step, not inside it, and no model part's
        assert not any('kfac.' in n or 'model.' in n for n in found), kind
        assert {_stepmap.classify(n) for n in found} == {('optimizer', 'none')}


@pytest.mark.parametrize('family', list(PARTS))
def test_the_engines_own_glue_is_a_bucket(family):
    names = _programs(family)['plain']
    buckets = {_stepmap.classify(n)[0] for n in names if 'kfac.step' in n}
    assert 'precondition' in buckets and 'kfac_step_self' in buckets
    assert buckets <= {
        'precondition', 'update_inverses', 'update_factors', 'kfac_step_self'
    }


@pytest.mark.parametrize('family', list(PARTS))
@pytest.mark.parametrize('kind', ['plain', 'capture'])
def test_little_of_a_step_program_is_off_the_map(family, kind):
    # instructions of reduction bodies carry a bare name ('reduce_sum'):
    # they are no events of a trace; an executed one has a path
    names = [n for n in _programs(family)[kind] if '/' in n]
    off = [n for n in names if _stepmap.classify(n)[0] == _stepmap.UNSCOPED]
    assert len(off) <= UNSCOPED_LIMIT[family] * len(names), (
        len(off), len(names), sorted(set(
            re.sub(r'^(jit\([^)]*\)/)+', '', n) for n in off
        ))[:20],
    )


def test_passes_are_told_by_the_op_name():
    path = 'jit(_step_no_stats)/jit(main)/'
    assert _stepmap.classify(
        path + 'jvp(HybridLM)/block0/model.mixer/mixer/q_proj/dot_general'
    ) == ('mixer', 'forward')
    assert _stepmap.classify(
        path + 'transpose(jvp(HybridLM))/block0/model.mixer/mixer/checkpoint/'
        'rematted_computation/model.gdn_scan/while'
    ) == ('gdn_scan', 'remat')
    assert _stepmap.classify(
        path + 'transpose(jvp(HybridLM))/model.head/checkpoint/model.loss/sub'
    ) == ('loss', 'backward')
    assert _stepmap.classify(path + 'jvp(HybridLM)/block0/add') == (
        'unscoped', 'forward'
    )
    # dist_kfac.step holds the letters of kfac.step and is not under it
    assert _stepmap.classify(path + 'dist_kfac.step/add') == (
        'kfac_step_self', 'none'
    )
