"""The conv-hybrid sparse LM (gated short convolutions + grouped-query
attention with QK-norm + sigmoid-routed experts with a selection bias, of
which a share is held, behind a leading dense MLP) against the benchmark's
plain reference and against per-part oracles, at a small size on the CPU.

``benchmark/refs/conv_moe_lm.py`` imports nothing of ``kfac_tpu``: it runs
the convolution as shifted products, attention with whole score matrices
and the experts by boolean masks, so agreement here is between two
independent implementations.
"""

import json
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import kfac_tpu  # noqa: E402
from benchmark import harness, weights  # noqa: E402
from benchmark.flops import conv_moe_lm as flops  # noqa: E402
from benchmark.jobs import conv_moe_lm as job  # noqa: E402
from benchmark.jobs import hybrid_lm as hybrid_job  # noqa: E402
from benchmark.refs import conv_moe_lm as ref  # noqa: E402
from kfac_tpu import enums, preconditioner, tracing  # noqa: E402
from kfac_tpu.layers import registry as registry_lib  # noqa: E402
from kfac_tpu.models import conv_moe, hybrid_lm_loss, moe  # noqa: E402
from kfac_tpu.parallel import kaisa  # noqa: E402

CELL = 'lfm2-24b-a2b.kfac-10-100'
TINY = dict(
    hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, moe_intermediate_size=16, num_experts_per_tok=3,
    router_width=16, experts_held=[4, 4], num_experts=4, vocab_size=64,
    seq_len=19, compute_dtype='float32', attention_chunk=8,
    expert_block_rows=4, batch_per_chip=3,
)
SKIP = ['block0/mlp/gate_proj', 'block0/mlp/up_proj', 'block0/mlp/down_proj']
MOE_BLOCKS = (1, 2, 3, 4)


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision('highest'):
        yield


def tiny_config(**over):
    with open(os.path.join(ROOT, 'benchmark/configs/lfm2-24b-a2b.json')) as f:
        config = json.load(f)
    config.update(TINY)
    config.update(over)
    return config


def seeded(config, seed=5):
    model = job.model_of(config)
    tok = jnp.zeros((1, config['seq_len']), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok))
    params = weights.make(shapes, weights.seed_key(seed))['params']
    registry = kfac_tpu.register_model(model, tok, skip_layers=SKIP)
    return model, params, registry


def batch_of(config, seed=0):
    t = np.random.default_rng(seed).integers(
        1, config['vocab_size'],
        size=(config['batch_per_chip'], config['seq_len'] + 1),
    ).astype(np.int32)
    return jnp.asarray(t[:, :-1]), jnp.asarray(t[:, 1:])


def flat(tree):
    return {
        '/'.join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))


# ------------------------------------------------- program against reference


@pytest.fixture(scope='module')
def compared():
    with jax.default_matmul_precision('highest'):
        config = tiny_config()
        model, params, registry = seeded(config)
        batch = batch_of(config)
        _, loss_grads_factors = ref.make(config)
        r_loss, r_grads, r_a, r_g = loss_grads_factors(params, batch)
        cap = kfac_tpu.CurvatureCapture(registry)
        (p_loss, _), p_grads, stats = jax.jit(
            cap.value_stats_and_grad(hybrid_lm_loss(model))
        )(params, batch)
    return types.SimpleNamespace(
        config=config, params=params, registry=registry, stats=stats,
        loss=(float(p_loss), float(r_loss)),
        grads=(flat(p_grads), flat(r_grads)), a=r_a, g=r_g,
    )


def test_the_run_is_the_leading_dense_layer_and_the_period_after(compared):
    config = compared.config
    assert ref.model_config(config)['layer_types'] == (
        'conv', 'full_attention', 'conv', 'conv', 'conv'
    )
    assert job.model_of(config).layer_types == (
        'conv', 'full_attention', 'conv', 'conv', 'conv'
    )
    assert 'mlp' in compared.params['block0']
    for i in MOE_BLOCKS:
        assert 'moe' in compared.params[f'block{i}']
    with pytest.raises(ValueError, match='source_layers'):
        job.model_of(tiny_config(num_hidden_layers=4))


def test_loss_matches_the_reference(compared):
    program, reference = compared.loss
    assert program == pytest.approx(reference, rel=1e-6)


def test_every_gradient_leaf_matches_the_reference(compared):
    program, reference = compared.grads
    assert set(program) == set(reference)
    for name, want in reference.items():
        if name.endswith('expert_bias'):
            # selection only: no gradient reaches it on either side
            assert not np.any(np.asarray(want)), name
            assert not np.any(np.asarray(program[name])), name
        else:
            assert rel(program[name], want) < 2e-4, name


def test_registered_layers_are_the_references(compared):
    names = ref.kfac_layers(compared.params)
    assert set(names) == set(compared.registry.layers)
    # 4 conv mixers x 4 and 1 attention mixer x 4 projections, 4 routers,
    # 3 x 4 held experts in each of 4 layers; not the dense MLP's three
    assert len(names) == 16 + 4 + 4 + 4 * 12
    assert not any('/mlp/' in n for n in names)


@pytest.mark.parametrize('side', ['a', 'g'])
def test_every_factor_matches_the_reference(compared, side):
    program = getattr(compared.stats, side)
    reference = getattr(compared, side)
    names = ref.kfac_layers(compared.params)
    lead = compared.registry.a_leader
    # a group's one A statistic is filed under its leader, and has to be
    # the reference's A of every member
    assert set(program) == (
        {lead(n) for n in names} if side == 'a' else set(names)
    )
    for name in names:
        key = lead(name) if side == 'a' else name
        assert rel(program[key], reference[name]) < 2e-4, (side, name)


def test_traffic_counts_rows_and_no_drop(compared):
    traffic = compared.stats.traffic
    assert len(traffic) == 12  # three projections of four routed layers
    for name, row in traffic.items():
        rows, dropped = np.asarray(row[:-1]), float(row[-1])
        assert dropped == 0
        assert rows.shape == (compared.config['experts_held'][1],)
        other = name.rsplit('/', 1)[0] + '/gate_proj'
        np.testing.assert_array_equal(rows, np.asarray(traffic[other][:-1]))


def test_three_kfac_steps_through_the_harness(monkeypatch):
    """The cell at a tiny size through ``harness.run_cell``: the program's
    first three K-FAC steps (a capture and refresh, two plain) against the
    reference's, by the numbers ``correct`` compares, then a window."""
    monkeypatch.setattr(
        preconditioner, 'default_compute_method',
        lambda platform=None: (enums.ComputeMethod.INVERSE, 'newton_schulz'),
    )
    cell = harness.load_cell(CELL)
    cell['config'].update(TINY)
    assert cell['workload']['kfac']['skip_layers'] == SKIP
    cell['workload']['kfac'].update(
        factor_update_steps=4, inv_update_steps=8, compute_method='inverse'
    )
    cell['workload'].update(first_order_steps=3, ring=4, limits={
        'loss_gap': 1e-5, 'first_grad_norm_gap': 1e-3,
        'update_norm_gap': 5e-3, 'inverse_residual': 3e-6,
    })
    lines = []
    result = harness.run_cell(
        cell, 2_147_483_659, 0.5, False, jax.devices()[:1],
        time.perf_counter(), lines.append,
    )
    assert result['correct'] is True, lines
    assert result['failed'] == 0
    bench = cell['bench']
    assert set(result['metrics']) == {m['name'] for m in bench['end_to_end']}
    assert 'stall_ms' in result['metrics']


# ------------------------------------------------- the short convolution


def test_short_convolution_is_the_loop_over_positions():
    """``C * conv(B * x~)`` against a position-by-position loop, and its
    gradients against the loop's."""
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    b_, t, ch, taps = 2, 7, 5, 3
    b, c, x = (jax.random.normal(k, (b_, t, ch)) for k in key[:3])
    kernel = jax.random.normal(key[3], (taps, ch))

    def loop(b, c, x, kernel):
        z = b * x
        rows = []
        for pos in range(t):
            acc = jnp.zeros((b_, ch))
            for j in range(taps):
                src = pos - (taps - 1) + j
                if src >= 0:
                    acc = acc + kernel[j] * z[:, src]
            rows.append(c[:, pos] * acc)
        return jnp.stack(rows, axis=1)

    np.testing.assert_allclose(
        conv_moe.short_conv(b, c, x, kernel), loop(b, c, x, kernel),
        rtol=1e-6, atol=1e-6,
    )
    probe = jax.random.normal(jax.random.PRNGKey(9), (b_, t, ch))
    got, want = (
        jax.grad(lambda *a, f=f: jnp.sum(f(*a) * probe), range(4))(
            b, c, x, kernel
        )
        for f in (conv_moe.short_conv, loop)
    )
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)
    # the first position sees only the last tap: zeros left of the sequence
    np.testing.assert_allclose(
        conv_moe.short_conv(b, c, x, kernel)[:, 0],
        c[:, 0] * kernel[-1] * (b * x)[:, 0], rtol=1e-6,
    )


def test_short_conv_scope_is_a_model_scope():
    assert tracing.MODEL_SCOPES['short_conv'] == 'model.short_conv'
    b = jnp.ones((1, 4, 2))
    text = jax.jit(conv_moe.short_conv).lower(
        b, b, b, jnp.ones((3, 2))
    ).as_text(debug_info=True)
    assert 'model.short_conv' in text


# ------------------------------------------------- sigmoid routing with a bias


def _sigmoid_layer(held=None, experts=16, k=3, width=8, bias=True):
    return moe.SparseMoE(
        experts, k, width, experts_held=held, block_rows=4,
        scoring='sigmoid', selection_bias=bias, renorm_eps=1e-6,
    )


def _dense_oracle(params, x, k):
    """Every expert on every token, weighted by the routing's formula:
    sigmoid scores, the top-k of score + bias, the *unbiased* scores of
    the chosen over their sum + 1e-6."""
    scores = jax.nn.sigmoid(x @ params['router']['kernel'])
    chosen = jnp.argsort(-(scores + params['expert_bias']), axis=-1)[:, :k]
    hit = jnp.any(chosen[..., None] == jnp.arange(scores.shape[-1]), axis=1)
    w = jnp.where(hit, scores, 0.0)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    y = jnp.zeros_like(x)
    for e in range(scores.shape[-1]):
        kernels = {
            proj: params['experts'][proj][f'e{e}']['kernel']
            for proj in ('gate_proj', 'up_proj', 'down_proj')
        }
        hid = jax.nn.silu(x @ kernels['gate_proj']) * (x @ kernels['up_proj'])
        y = y + w[:, e:e + 1] * (hid @ kernels['down_proj'])
    return y, chosen, w


def test_sigmoid_bias_routing_is_the_dense_oracle():
    layer = _sigmoid_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (11, 16))
    params = weights.make(
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x)),
        weights.seed_key(3),
    )['params']
    assert params['expert_bias'].shape == (16,)
    want, chosen, w = _dense_oracle(params, x, 3)
    np.testing.assert_allclose(
        layer.apply({'params': params}, x), want, rtol=2e-5, atol=2e-6
    )
    # a bias that flips a selection: an expert token 0 did not choose is
    # now chosen everywhere; its weight is still its unbiased score's
    out = int(jnp.argmin(jax.nn.sigmoid(x[0] @ params['router']['kernel'])))
    assert out not in np.asarray(chosen[0])
    flipped = dict(params)
    flipped['expert_bias'] = params['expert_bias'].at[out].set(10.0)
    want2, chosen2, w2 = _dense_oracle(flipped, x, 3)
    assert all(out in row for row in np.asarray(chosen2))
    assert float(jnp.max(w2[:, out])) < 0.5   # never the bias's 10
    got2 = layer.apply({'params': flipped}, x)
    np.testing.assert_allclose(got2, want2, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(want2 - want))) > 1e-3
    # no gradient reaches the bias; the router's comes through the scores
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    grads = jax.grad(
        lambda p: jnp.sum(layer.apply({'params': p}, x) * probe)
    )(flipped)
    assert not np.any(np.asarray(grads['expert_bias']))
    oracle = jax.grad(
        lambda p: jnp.sum(_dense_oracle(p, x, 3)[0] * probe)
    )(flipped)
    np.testing.assert_allclose(
        grads['router']['kernel'], oracle['router']['kernel'],
        rtol=2e-4, atol=2e-6,
    )


def test_softmax_in_place_of_sigmoid_is_another_layer():
    """The planted fault the cell's limits are held against: it changes
    the result (and a layer without the bias declares no such leaf)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (11, 16))
    sig, soft = _sigmoid_layer(bias=False), moe.SparseMoE(
        16, 3, 8, block_rows=4
    )
    params = weights.make(
        jax.eval_shape(lambda: soft.init(jax.random.PRNGKey(0), x)),
        weights.seed_key(3),
    )['params']
    assert set(params) == {'router', 'experts'}
    a = sig.apply({'params': params}, x)
    b = soft.apply({'params': params}, x)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    with pytest.raises(ValueError, match='scoring'):
        moe.SparseMoE(16, 3, 8, scoring='tanh').init(jax.random.PRNGKey(0), x)


# ------------------------------------------------------------ the share test


def test_eight_shares_add_up_to_the_uncut_layer():
    """64 experts in 8 shares of 8: the shares' parts (no shared expert to
    count once) are the uncut reference's layer output."""
    d, experts, k, width = 32, 64, 4, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, d))
    whole = _sigmoid_layer(None, experts, k, width)
    params = weights.make(
        jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x)),
        weights.seed_key(11),
    )['params']

    def share_params(first):
        p = dict(params)
        p['experts'] = {
            proj: {f'e{j}': sub[f'e{first + j}'] for j in range(8)}
            for proj, sub in params['experts'].items()
        }
        return p

    m = {
        'experts_held': (0, experts), 'num_experts_per_tok': k,
        'norm_topk_prob': True, 'use_expert_bias': True,
        'routed_scaling_factor': 1,
    }
    uncut, _, rows = ref._moe(params, None, x, m)
    assert int(jnp.sum(rows)) == 2 * 11 * k
    shares = sum(
        _sigmoid_layer((first, 8), experts, k, width).apply(
            {'params': share_params(first)}, x
        )
        for first in range(0, experts, 8)
    )
    np.testing.assert_allclose(shares, uncut, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        whole.apply({'params': params}, x), uncut, rtol=2e-5, atol=2e-6
    )


# ------------------------------------------------------- pass-through rule


def test_passthrough_report_names_the_dense_mlp_and_the_rest():
    _, params, registry = seeded(tiny_config())
    expected = {
        'embed/embedding': 'embedding',
        'norm_f/scale': 'elementwise',
        'block0/mlp/gate_proj/kernel': 'skipped',
        'block0/mlp/up_proj/kernel': 'skipped',
        'block0/mlp/down_proj/kernel': 'skipped',
        'block1/mixer/q_layernorm/scale': 'elementwise',
        'block1/mixer/k_layernorm/scale': 'elementwise',
    }
    for i in range(5):
        expected[f'block{i}/norm1/scale'] = 'elementwise'
        expected[f'block{i}/norm2/scale'] = 'elementwise'
    for i in (0, 2, 3, 4):
        expected[f'block{i}/mixer/conv/kernel'] = 'convolution'
    for i in MOE_BLOCKS:
        expected[f'block{i}/moe/expert_bias'] = 'elementwise'
    assert registry.passthrough == expected
    assert set(expected.values()) <= set(registry_lib.PASSTHROUGH_RULE)
    leaves = set(flat(params))
    assert leaves == set(registry.passthrough) | set(registry.kfac_leaves)


def test_dense_mlp_is_in_no_slot_of_the_engine_and_passes_unchanged():
    config = tiny_config()
    model, params, registry = seeded(config)
    cap = kfac_tpu.CurvatureCapture(registry)
    (_, _), grads, stats = jax.jit(
        cap.value_stats_and_grad(hybrid_lm_loss(model))
    )(params, batch_of(config))
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry, damping=0.003, lr=0.1, compute_method='inverse',
        inverse_solver='newton_schulz',
    )
    engine = kaisa.DistributedKFAC(config=cfg, mesh=None)
    assert len(engine._a_slot) == len(engine._g_slot) == 72
    assert not any('/mlp/' in name for name in engine._a_slot)
    assert not any('/mlp/' in name for name in stats.a)
    state, out = jax.jit(engine.step)(engine.init(), grads, stats)
    before, after = flat(grads), flat(out)
    for leaf in registry.passthrough:
        np.testing.assert_array_equal(after[leaf], before[leaf])
    moved = [
        leaf for leaf in registry.kfac_leaves
        if float(jnp.max(jnp.abs(after[leaf] - before[leaf]))) > 0
    ]
    assert len(moved) == len(registry.kfac_leaves) == 72


# ------------------------------------------- the other sparse LM, unchanged


def test_hybrid_lm_tree_and_outputs_are_what_they_were():
    """``SparseMoE`` gained its scoring options for this model; the
    Qwen3-Next-shaped ``HybridLM`` declares the leaves it declared and
    computes what it computed (loss and gradient mass of
    ``tests/test_hybrid_lm.py``'s tiny size on seed 5, from the commit
    before)."""
    hybrid_tiny = dict(
        hidden_size=32, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, num_experts_per_tok=3,
        router_width=16, experts_held=[4, 4], num_experts=4, vocab_size=64,
        seq_len=19, compute_dtype='float32', scan_chunk=4, attention_chunk=8,
        expert_block_rows=4, batch_per_chip=3,
    )
    with open(os.path.join(ROOT, 'benchmark/configs/qwen3-next-80b-a3b.json')) as f:
        config = json.load(f)
    config.update(hybrid_tiny)
    model = hybrid_job.model_of(config)
    tok = jnp.zeros((1, config['seq_len']), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok))
    params = weights.make(shapes, weights.seed_key(5))['params']
    leaves = flat(params)
    assert len(leaves) == 119
    assert not any(name.endswith('expert_bias') for name in leaves)
    t = np.random.default_rng(0).integers(1, 64, size=(3, 20)).astype(np.int32)
    batch = (jnp.asarray(t[:, :-1]), jnp.asarray(t[:, 1:]))
    loss, grads = jax.value_and_grad(hybrid_lm_loss(model))(params, batch)
    mass = sum(jnp.sum(jnp.abs(g)) for g in jax.tree_util.tree_leaves(grads))
    assert float(loss) == pytest.approx(4.522747993469238, rel=1e-6)
    assert float(mass) == pytest.approx(5869.673828125, rel=1e-5)


# ------------------------------------------------- the operation count


def test_flops_are_6n_plus_attention():
    with open(os.path.join(ROOT, 'benchmark/configs/lfm2-24b-a2b.json')) as f:
        config = json.load(f)
    d = 2048
    conv, att = 4 * d * d, d * (2048 + 2 * 512) + 2048 * d
    dense = 3 * d * 11776
    routed = d * 64 + (4 * 8 / 64) * 3 * d * 1536
    n = 4 * conv + att + dense + 4 * routed + d * 8192
    assert flops.layer_types(config) == [
        'conv', 'full_attention', 'conv', 'conv', 'conv'
    ]
    assert flops.matmul_params(config) == n == 186_122_240
    per_token = 6 * n + 12 * 32 * 64 * 4096
    assert flops.train_flops_per_token(config) == per_token
    assert flops.train_flops_per_sample(config) == 4096 * per_token


# ------------------------------------------------- readers, synthetic trace


PATH = 'jit(_step_with_stats)/jit(main)/'


def _op(name, start, ns, op_name):
    return {
        'name': f'%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
        'start_ns': start, 'duration_ns': ns, 'stats': {'op_name': op_name},
    }


def _ctx(ops, kinds, registry=None, shapes=None):
    plane = {'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': ops},
    ]}
    run = types.SimpleNamespace(
        job=types.SimpleNamespace(
            registry=registry, variable_shapes={'params': shapes or {}}
        ),
    )
    return harness.LayerContext(
        cell={}, run=run, devices=[], first_order_rows=[], rows=[],
        traced_rows=[{'kind': k} for k in kinds], trace={'planes': [plane]},
        windows={plane['name']: (0, 10_000)}, throughput=0.0,
    )


def test_short_conv_row_on_a_synthetic_trace():
    fwd, bwd = 'jvp(ConvMoELM)/', 'transpose(jvp(ConvMoELM))/'
    scope = 'block0/mixer/checkpoint/model.short_conv/'
    ops = [
        # a plain step: forward 100, rematerialised 80, backward 120
        _op('fusion.1', 0, 100, PATH + fwd + scope + 'mul'),
        _op('fusion.2', 100, 80, PATH + bwd + 'block0/mixer/checkpoint/'
            'rematted_computation/model.short_conv/mul'),
        _op('fusion.3', 200, 120, PATH + bwd + scope + 'mul'),
        # the projections around it are not the convolution's
        _op('fusion.4', 400, 500, PATH + fwd + 'block0/mixer/b_proj/dot_general'),
        # a capture step: the same 100, and a g-tap's product under capture
        _op('fusion.5', 1000, 100, PATH + fwd + scope + 'mul'),
        _op('fusion.6', 1100, 70, PATH + bwd + 'block0/mixer/b_proj/'
            'kfac.capture_g/dot_general'),
    ]
    ctx = _ctx(ops, ['plain', 'capture'])
    assert harness.read_layer_metric('dev_ms.short_conv', ctx) == (
        pytest.approx((300 + 100) / 2 / 1e6)
    )
    # a reader of its own, not a row of scopes: the engine rows' scopes
    # (harness.trace_scopes) stay what benchmark/tests pins them to
    assert 'model.short_conv' not in harness.trace_scopes()
    assert harness.layer_reader('dev_ms.short_conv').SHORT_CONV == (
        tracing.MODEL_SCOPES['short_conv']
    )
    # a program without the scope (the parent, another model): nothing
    dense = [_op('fusion.1', 0, 100, PATH + fwd + 'block0/attn/dot')]
    assert harness.read_layer_metric(
        'dev_ms.short_conv', _ctx(dense, ['plain'])
    ) is None


def test_first_order_share_reads_the_registrys_report():
    shapes = {
        'embed': {'embedding': jax.ShapeDtypeStruct((10, 4), jnp.float32)},
        'mlp': {'up': {'kernel': jax.ShapeDtypeStruct((4, 15), jnp.float32)}},
        'proj': {'kernel': jax.ShapeDtypeStruct((4, 25), jnp.float32)},
    }
    registry = types.SimpleNamespace(passthrough={
        'embed/embedding': 'embedding', 'mlp/up/kernel': 'skipped',
    })
    ctx = _ctx([], ['plain'], registry, shapes)
    assert harness.read_layer_metric('kfac_first_order_share', ctx) == (
        pytest.approx(100.0 * (40 + 60) / 200)
    )
    # a registry that reports nothing (registered from an apply_fn)
    empty = _ctx([], ['plain'], types.SimpleNamespace(passthrough={}), shapes)
    assert harness.read_layer_metric('kfac_first_order_share', empty) is None
    bare = _ctx([], ['plain'], types.SimpleNamespace(), shapes)
    assert harness.read_layer_metric('kfac_first_order_share', bare) is None


def test_first_order_share_of_the_tiny_model():
    config = tiny_config()
    model, params, registry = seeded(config)
    shapes = jax.eval_shape(lambda: params)
    ctx = _ctx([], ['plain'], registry, shapes)
    sizes = {k: v.size for k, v in flat(params).items()}
    want = 100.0 * sum(sizes[k] for k in registry.passthrough) / sum(
        sizes.values()
    )
    assert harness.read_layer_metric('kfac_first_order_share', ctx) == (
        pytest.approx(want)
    )
    assert 10 < want < 60


def test_new_rows_of_the_benchmark_name_the_new_cell():
    bench = harness.load_cell(CELL)['bench']
    rows = {m['name']: m for m in bench['per_layer']}
    assert rows['dev_ms.short_conv'] == {
        'name': 'dev_ms.short_conv', 'unit': 'ms', 'better': 'lower',
        'source': 'device_trace', 'layer': 'model', 'moves': 'throughput',
        'workloads': [CELL],
    }
    assert rows['kfac_first_order_share'] == {
        'name': 'kfac_first_order_share', 'unit': '%', 'better': 'lower',
        'source': 'program_counter', 'layer': 'engine',
        'moves': 'kfac_overhead',
        'workloads': [CELL, 'kanana-2-30b-a3b.kfac-10-100'],
    }
    read = {m['name'] for m in harness.layer_rows(harness.load_cell(CELL))}
    for name in (
        'dev_ms.short_conv', 'kfac_first_order_share', 'dev_ms.moe_route',
        'dev_ms.moe_experts', 'dev_ms.capture_experts', 'expert_rows_mean',
        'expert_rows_min', 'expert_dropped', 'ns_trips_refresh',
        'refresh_extra_ms', 'dev_ms.update_inverses', 'mfu',
    ):
        assert name in read, name
    for name in (
        'dev_ms.gdn_scan', 'dev_ms.capture_patches', 'collective_ms',
        'longest_step_ms', 'refresh_extra_ms.overhead',
    ):
        assert name not in read, name


def test_the_configuration_keeps_every_published_width():
    with open(os.path.join(ROOT, 'benchmark/configs/lfm2-24b-a2b.json')) as f:
        config = json.load(f)
    published = {
        'hidden_size': 2048, 'intermediate_size': 11776,
        'moe_intermediate_size': 1536, 'num_attention_heads': 32,
        'num_key_value_heads': 8, 'num_experts_per_tok': 4,
        'conv_L_cache': 3, 'norm_eps': 1e-5, 'routed_scaling_factor': 1,
        'use_expert_bias': True, 'norm_topk_prob': True, 'router_width': 64,
    }
    for key, value in published.items():
        assert config[key] == value, key
    assert config['rope_parameters']['rope_theta'] == 1_000_000
    assert len(config['layer_types']) == 40
    assert config['reduced'] == [
        'num_hidden_layers', 'num_dense_layers', 'num_experts', 'vocab_size'
    ]
    assert config['published'] == {
        'num_hidden_layers': 40, 'num_dense_layers': 2, 'num_experts': 64,
        'vocab_size': 65536,
    }
    assert [config[k] for k in config['reduced']] == [5, 1, 8, 8192]
    assert config['experts_held'] == [0, 8]
