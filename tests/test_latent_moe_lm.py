"""The latent-attention sparse LM (multi-head latent attention + a leading
dense MLP + scaled sigmoid-routed experts, of which a share is held, beside
ungated shared experts) against the benchmark's plain reference and against
per-part oracles, at a small size on the CPU with every ratio of the cell's
kept: query-key heads of 8 + 4 over value heads of 8, a latent (12) that is
no head's width, two shared experts, a routing scale that is not 1.

``benchmark/refs/latent_moe_lm.py`` imports nothing of ``kfac_tpu``: it
multiplies the source's three fused kernels, rotates interleaved pairs in
place, forms whole score matrices and runs the experts by boolean masks, so
agreement here is between two independent implementations.
"""

import dataclasses
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
from benchmark.flops import latent_moe_lm as flops  # noqa: E402
from benchmark.jobs import conv_moe_lm as conv_job  # noqa: E402
from benchmark.jobs import latent_moe_lm as job  # noqa: E402
from benchmark.refs import latent_moe_lm as ref  # noqa: E402
from kfac_tpu import enums, preconditioner, tracing  # noqa: E402
from kfac_tpu.models import hybrid_lm_loss, mla, moe  # noqa: E402
from kfac_tpu.parallel import kaisa  # noqa: E402

CELL = 'kanana-2-30b-a3b.kfac-10-100'
CONFIG = os.path.join(ROOT, 'benchmark/configs/kanana-2-30b-a3b.json')
TINY = dict(
    hidden_size=32, num_attention_heads=4, qk_nope_head_dim=8,
    qk_rope_head_dim=4, qk_head_dim=12, v_head_dim=8, kv_lora_rank=12,
    intermediate_size=48, moe_intermediate_size=16, num_experts_per_tok=3,
    router_width=16, experts_held=[4, 4], n_routed_experts=4, vocab_size=64,
    seq_len=19, compute_dtype='float32', attention_chunk=8,
    expert_block_rows=4, batch_per_chip=3,
)


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision('highest'):
        yield


def full_config():
    with open(CONFIG) as f:
        return json.load(f)


def tiny_config(**over):
    config = full_config()
    config.update(TINY)
    config.update(over)
    return config


def skip_layers():
    workload = harness.load_json('workloads', CELL + '.json')
    return workload['kfac']['skip_layers']


def seeded(config, seed=5, skip=None):
    """The model, its seeded weights and its registry, the cell's own
    ``skip_layers`` left to the first-order update unless ``skip`` says."""
    skip = skip_layers() if skip is None else skip
    model = job.model_of(config)
    tok = jnp.zeros((1, config['seq_len']), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok))
    params = weights.make(shapes, weights.seed_key(seed))['params']
    registry = kfac_tpu.register_model(model, tok, skip_layers=list(skip))
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
        m = ref.model_config(config)
        _, loss_grads_factors = ref.make(config)
        r_loss, r_grads, r_a, r_g = loss_grads_factors(params, batch)
        cap = kfac_tpu.CurvatureCapture(registry)
        (p_loss, _), p_grads, stats = jax.jit(
            cap.value_stats_and_grad(hybrid_lm_loss(model))
        )(params, batch)
        logits = (
            model.apply({'params': params}, batch[0]),
            ref._logits(params, None, batch[0], m)[0],
        )
    return types.SimpleNamespace(
        config=config, model=model, params=params, registry=registry,
        stats=stats, m=m, loss=(float(p_loss), float(r_loss)), logits=logits,
        grads=(flat(p_grads), flat(r_grads)), a=r_a, g=r_g,
    )


def test_loss_and_logits_match_the_reference(compared):
    program, reference = compared.loss
    assert program == pytest.approx(reference, rel=1e-6)
    assert rel(*compared.logits) < 1e-5


def test_every_gradient_leaf_matches_the_reference(compared):
    """2e-5 of a leaf's largest entry: the float32 reordering of the
    chunked softmax and the blocked expert products (the worst leaves read
    4e-6); a bfloat16 product anywhere reads 1e-3 and more."""
    program, reference = compared.grads
    assert set(program) == set(reference)
    for name, want in reference.items():
        if name.endswith('expert_bias'):
            # selection only: no gradient reaches it on either side
            assert not np.any(np.asarray(want)), name
            assert not np.any(np.asarray(program[name])), name
        else:
            assert rel(program[name], want) < 2e-5, name


@pytest.mark.parametrize('side', ['a', 'g'])
def test_every_factor_matches_the_reference(compared, side):
    program = getattr(compared.stats, side)
    reference = getattr(compared, side)
    names = ref.kfac_layers(compared.params)
    assert set(names) == set(compared.registry.layers)
    lead = compared.registry.a_leader
    assert set(program) == (
        {lead(n) for n in names} if side == 'a' else set(names)
    )
    for name in names:
        key = lead(name) if side == 'a' else name
        assert rel(program[key], reference[name]) < 2e-5, (side, name)


def test_the_mixer_alone_matches_the_reference(compared):
    """``LatentAttention`` on a block's parameters against the reference's
    ``_mla`` (fused products, pairs rotated in place, whole scores), and
    its input gradient against the reference's."""
    c = compared.config
    layer = mla.LatentAttention(
        c['num_attention_heads'], c['qk_nope_head_dim'],
        c['qk_rope_head_dim'], c['v_head_dim'], c['kv_lora_rank'],
        float(c['rope_theta']), c['rms_norm_eps'], c['attention_chunk'],
    )
    p = compared.params['block2']['mixer']
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 32))
    probe = jax.random.normal(jax.random.PRNGKey(4), (2, 19, 32))

    def program(u):
        return layer.apply({'params': p}, u)

    def reference(u):
        return ref._mla(p, None, u, compared.m)[0]

    assert rel(program(u), reference(u)) < 1e-5
    got, want = (
        jax.grad(lambda u, f=f: jnp.sum(f(u) * probe))(u)
        for f in (program, reference)
    )
    assert rel(got, want) < 1e-5


def test_three_kfac_steps_through_the_harness(monkeypatch):
    """The cell at a tiny size through ``harness.run_cell``: the program's
    first three K-FAC steps (a capture and refresh, two plain) through
    ``Trainer`` against ``benchmark/reference.py``'s, by the numbers
    ``correct`` compares, then a window."""
    result, lines = _tiny_run(monkeypatch, window=True)
    assert result['correct'] is True, lines
    assert result['failed'] == 0
    reported = set(result['metrics'])
    assert reported == {
        'throughput', 'kfac_overhead', 'peak_hbm_gb', 'setup_s'
    }


@pytest.mark.parametrize('fault', ['routed_scale', 'latent_norm'])
def test_a_planted_fault_reads_not_correct(monkeypatch, fault):
    """The routing scale left out of the program; the latent's norm left
    out of the reference: the tiny cell's limits refuse both."""
    if fault == 'routed_scale':
        sound = job.model_of
        monkeypatch.setattr(
            job, 'model_of', lambda c: sound(c).clone(routed_scale=1.0)
        )
    else:
        monkeypatch.setattr(ref, '_latent_norm', lambda c, w, eps: c * w)
    verdict, lines = _tiny_run(monkeypatch, window=False)
    assert verdict['ok'] is False, lines
    over = {r['number'] for r in verdict['rows'] if not r['ok']}
    assert over & {'first_grad_norm_gap', 'update_norm_gap'}, verdict['rows']


def _tiny_run(monkeypatch, window):
    monkeypatch.setattr(
        preconditioner, 'default_compute_method',
        lambda platform=None: (enums.ComputeMethod.INVERSE, 'newton_schulz'),
    )
    cell = harness.load_cell(CELL)
    cell['config'].update(TINY)
    cell['workload']['kfac'].update(
        factor_update_steps=4, inv_update_steps=8, compute_method='inverse'
    )
    cell['workload'].update(first_order_steps=3, ring=4, limits={
        'loss_gap': 1e-5, 'first_grad_norm_gap': 1e-3,
        'update_norm_gap': 5e-3, 'inverse_residual': 3e-6,
    })
    lines = []
    # on this seed (as on most at 57 Zipf-drawn tokens) held experts see no
    # row at the capture step: the reference keeps their factors at the
    # identity, as the engine does, or their later updates read 10% apart
    devices, seed = jax.devices()[:1], 2_147_483_659
    if window:
        return harness.run_cell(
            cell, seed, 0.5, False, devices, time.perf_counter(),
            lines.append,
        ), lines
    _, verdict, _ = harness.set_up(cell, seed, devices, lines.append)
    return verdict, lines


# ------------------------------------- the declared six and the fused three


def _source_attention(fused, p, u, c):
    """The source's forward pass (``deepseek_v3`` semantics), on its three
    fused kernels: split as it splits, de-interleave and rotate in halves
    as it does with ``rope_interleave``, the one rotary key expanded."""
    b, t, _ = u.shape
    h, nope, rope = (
        c['num_attention_heads'], c['qk_nope_head_dim'],
        c['qk_rope_head_dim'],
    )
    vd, rank = c['v_head_dim'], c['kv_lora_rank']
    q = (u @ fused['q_proj']).reshape(b, t, h, nope + rope)
    q_pass, q_rot = q[..., :nope], q[..., nope:]
    kv = u @ fused['kv_a_proj_with_mqa']
    k_pass, k_rot = kv[..., :rank], kv[..., rank:]
    k_pass = k_pass * jax.lax.rsqrt(
        jnp.mean(k_pass * k_pass, -1, keepdims=True) + c['rms_norm_eps']
    ) * p['kv_a_layernorm']['scale']
    k_pass = (k_pass @ fused['kv_b_proj']).reshape(b, t, h, nope + vd)
    k_pass, v = k_pass[..., :nope], k_pass[..., nope:]

    inv = float(c['rope_theta']) ** (-jnp.arange(0, rope, 2) / rope)
    ang = jnp.arange(t)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]

    def rotate(x):
        x = x.reshape(*x.shape[:-1], rope // 2, 2)
        x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], rope)
        half = jnp.concatenate([-x[..., rope // 2:], x[..., :rope // 2]], -1)
        return x * cos + half * sin

    k_rot = jnp.broadcast_to(rotate(k_rot[:, :, None]), (b, t, h, rope))
    q = jnp.concatenate([q_pass, rotate(q_rot)], -1)
    k = jnp.concatenate([k_pass, k_rot], -1)
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k) * (nope + rope) ** -0.5
    scores = jnp.where(
        jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], scores, -jnp.inf
    )
    out = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v)
    return out.reshape(b, t, h * vd) @ p['o_proj']['kernel']


def test_the_six_declared_projections_are_the_sources_three(compared):
    c = compared.config
    p = compared.params['block1']['mixer']
    h = c['num_attention_heads']
    fused = mla.fused_kernels(p, h)
    assert fused['q_proj'].shape == (32, h * 12)
    assert fused['kv_a_proj_with_mqa'].shape == (32, 12 + 4)
    assert fused['kv_b_proj'].shape == (12, h * (8 + 8))
    # the column map the configuration's departures state: head 2's q_nope
    # and q_rope columns of q_proj, the latent then the rotary key, head
    # 3's k_nope and v columns of kv_b_proj
    np.testing.assert_array_equal(
        fused['q_proj'][:, 24:32], p['q_nope_proj']['kernel'][:, 16:24]
    )
    np.testing.assert_array_equal(
        fused['q_proj'][:, 32:36], p['q_rope_proj']['kernel'][:, 8:12]
    )
    np.testing.assert_array_equal(
        fused['kv_a_proj_with_mqa'][:, 12:], p['k_rope_proj']['kernel']
    )
    np.testing.assert_array_equal(
        fused['kv_b_proj'][:, 48:56], p['k_nope_proj']['kernel'][:, 24:32]
    )
    np.testing.assert_array_equal(
        fused['kv_b_proj'][:, 56:64], p['v_proj']['kernel'][:, 24:32]
    )
    module = mla.LatentAttention(
        h, 8, 4, 8, 12, float(c['rope_theta']), c['rms_norm_eps'], 8
    )
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 19, 32))
    assert rel(
        module.apply({'params': p}, u), _source_attention(fused, p, u, c)
    ) < 1e-5


def test_rotary_in_halves_where_the_source_interleaves_is_another_layer(
    compared, monkeypatch
):
    """One of the planted faults the cell's limits are held against."""
    c = compared.config
    module = mla.LatentAttention(
        4, 8, 4, 8, 12, float(c['rope_theta']), c['rms_norm_eps'], 8
    )
    p = compared.params['block1']['mixer']
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 19, 32))
    sound = module.apply({'params': p}, u)
    monkeypatch.setattr(mla, '_deinterleave', lambda x: x)
    jax.clear_caches()  # jax.checkpoint keeps the sound function's trace
    assert rel(module.apply({'params': p}, u), sound) > 1e-3


# ------------------------------------------------------------ the share test


def _layer(held, shared=2 * 8, scale=2.448, gated=False):
    return moe.SparseMoE(
        16, 3, 8, shared, held, block_rows=4, scoring='sigmoid',
        selection_bias=True, renorm_eps=1e-20, routed_scale=scale,
        shared_gated=gated,
    )


def test_four_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """16 experts in 4 shares of 4: the shares' routed parts, scaled once
    each, plus the shared experts counted once are the uncut reference's
    layer output."""
    d = 32
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, d))
    whole = _layer(None)
    params = weights.make(
        jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x)),
        weights.seed_key(11),
    )['params']
    assert set(params) == {'router', 'experts', 'shared', 'expert_bias'}

    def share_params(first):
        p = dict(params)
        p['experts'] = {
            proj: {f'e{j}': sub[f'e{first + j}'] for j in range(4)}
            for proj, sub in params['experts'].items()
        }
        return p

    m = {
        'experts_held': (0, 16), 'num_experts_per_tok': 3,
        'norm_topk_prob': True, 'routed_scaling_factor': 2.448,
    }
    uncut, _, rows = ref._moe(params, None, x, m)
    assert int(jnp.sum(rows)) == 2 * 11 * 3
    shared = moe.GatedMLP(16).apply({'params': params['shared']}, x)
    shares = [
        _layer((first, 4)).apply({'params': share_params(first)}, x)
        for first in range(0, 16, 4)
    ]
    routed = sum(s - shared for s in shares)
    np.testing.assert_allclose(routed + shared, uncut, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        whole.apply({'params': params}, x), uncut, rtol=2e-5, atol=2e-6
    )
    # the scale is on the routed part alone, and it is not 1
    plain = _layer(None, scale=1.0).apply({'params': params}, x)
    np.testing.assert_allclose(
        whole.apply({'params': params}, x) - shared,
        2.448 * (plain - shared), rtol=2e-5, atol=2e-6,
    )


def test_sparse_moe_defaults_are_the_layer_it_was():
    """``routed_scale`` 1 and ``shared_gated`` true are the expressions of
    before: the gated layer declares ``shared_gate``, the ungated none."""
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
    gated = moe.SparseMoE(16, 3, 8, 16, block_rows=4)
    assert (gated.routed_scale, gated.shared_gated) == (1.0, True)
    shapes = jax.eval_shape(lambda: gated.init(jax.random.PRNGKey(0), x))
    assert set(shapes['params']) == {
        'router', 'experts', 'shared', 'shared_gate'
    }
    params = weights.make(shapes, weights.seed_key(3))['params']
    y = gated.apply({'params': params}, x)
    routed = moe.SparseMoE(16, 3, 8, 0, block_rows=4).apply(
        {'params': {k: params[k] for k in ('router', 'experts')}}, x
    )
    want = routed + jax.nn.sigmoid(
        x @ params['shared_gate']['kernel']
    ) * moe.GatedMLP(16).apply({'params': params['shared']}, x)
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)


def test_the_other_sparse_lm_is_what_it_was():
    """``SparseMoE`` gained two options for this model and ``ConvMoELM``'s
    chunked loss moved into ``transformer.head_or_nll``; the LFM2-shaped
    decoder declares the leaves it declared and computes what it computed
    (loss and gradient mass of ``tests/test_conv_moe_lm.py``'s tiny size on
    seed 5, from the commit before; the Qwen3-Next-shaped ``HybridLM``'s
    are held in that file)."""
    tiny = dict(
        hidden_size=32, head_dim=8, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=48,
        moe_intermediate_size=16, num_experts_per_tok=3, router_width=16,
        experts_held=[4, 4], num_experts=4, vocab_size=64, seq_len=19,
        compute_dtype='float32', attention_chunk=8, expert_block_rows=4,
        batch_per_chip=3,
    )
    with open(os.path.join(ROOT, 'benchmark/configs/lfm2-24b-a2b.json')) as f:
        config = json.load(f)
    config.update(tiny)
    model = conv_job.model_of(config)
    tok = jnp.zeros((1, 19), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok))
    params = weights.make(shapes, weights.seed_key(5))['params']
    assert len(flat(params)) == CONV_MOE_LEAVES
    loss, grads = jax.value_and_grad(hybrid_lm_loss(model))(
        params, batch_of(config)
    )
    mass = sum(jnp.sum(jnp.abs(g)) for g in jax.tree_util.tree_leaves(grads))
    assert float(loss) == pytest.approx(CONV_MOE_LOSS, rel=1e-6)
    assert float(mass) == pytest.approx(CONV_MOE_MASS, rel=1e-5)


CONV_MOE_LEAVES, CONV_MOE_LOSS, CONV_MOE_MASS = (
    97, 4.153321743011475, 161.3258514404297
)


# ------------------------------------------------------- registration


def _expected_groups(blocks, held):
    groups = {}
    for i in range(blocks):
        mixer = f'block{i}/mixer/'
        groups[mixer + 'q_nope_proj'] = {
            mixer + n for n in ('q_rope_proj', 'kv_a_proj', 'k_rope_proj')
        }
        groups[mixer + 'k_nope_proj'] = {mixer + 'v_proj'}
        if i == 0:
            groups['block0/mlp/gate_proj'] = {'block0/mlp/up_proj'}
            continue
        base = f'block{i}/moe/'
        groups[base + 'shared/gate_proj'] = {base + 'shared/up_proj'}
        for j in range(held):
            groups[base + f'experts/gate_proj/e{j}'] = {
                base + f'experts/up_proj/e{j}'
            }
    return groups


def test_a_groups_are_the_mixers_two_and_each_gated_pair():
    """In the cell's compute type the groups are exactly: the four
    projections of a mixer's input, the two of its normed latent, each
    held expert's gate and up, the shared experts' and the dense MLP's
    gate and up. (In float32 the float32 router joins the shared pair: it
    is handed the same array at the same type.)"""
    _, _, registry = seeded(
        tiny_config(compute_dtype='bfloat16'), skip=['lm_head']
    )
    members = registry.a_members()
    got = {lead: set(m) - {lead} for lead, m in members.items()}
    assert got == _expected_groups(5, 4)
    # 7 projections a mixer, the dense MLP's 3, then a layer's router, 3
    # shared and 3 x 4 held; A slots: 3 a mixer, 2, then 1 + 2 + 2 x 4
    assert len(registry.layers) == 5 * 7 + 3 + 4 * (1 + 3 + 12) == 102
    leaders = {registry.a_leader(n) for n in registry.layers}
    assert len(leaders) == 5 * 3 + 2 + 4 * (1 + 2 + 8) == 61
    _, _, f32 = seeded(tiny_config(), skip=['lm_head'])
    assert f32.a_leader('block1/moe/shared/up_proj') == 'block1/moe/router'


def test_the_cells_own_registration_counts_what_the_issue_counts():
    """The published widths, registered (shapes only): 150 K-FAC layers
    and 93 A slots with everything preconditioned, 147 and 91 with the
    dense MLP left to the first-order update as the cell leaves it; the
    engine's count of factor and inverse bytes by part."""
    config = full_config()
    model = job.model_of(config)
    tok = jnp.zeros((1, config['seq_len']), jnp.int32)
    whole = kfac_tpu.register_model(model, tok, skip_layers=['lm_head'])
    assert len(whole.layers) == 150
    assert len({whole.a_leader(n) for n in whole.layers}) == 93
    got = {lead: set(m) - {lead} for lead, m in whole.a_members().items()}
    assert got == _expected_groups(5, 8)
    registry = kfac_tpu.register_model(model, tok, skip_layers=skip_layers())
    dense = [n for n in whole.layers if n not in registry.layers]
    assert dense == [n for n in whole.layers if n.startswith('block0/mlp/')]
    assert len(dense) in (0, 3)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry, damping=0.003, lr=0.1, compute_method='inverse',
        inverse_solver='newton_schulz',
    )
    parts = kaisa.DistributedKFAC(config=cfg, mesh=None).state_bytes_by_part
    mixer = 8 * 5 * (
        2048 ** 2 + 512 ** 2 + 4096 ** 2            # A: u, latent, o's input
        + 3 * 4096 ** 2 + 2 * 2048 ** 2 + 512 ** 2 + 64 ** 2
    )
    experts = 8 * 4 * 8 * (2048 ** 2 + 768 ** 2 + 2 * 768 ** 2 + 2048 ** 2)
    shared = 8 * 4 * (2048 ** 2 + 1536 ** 2 + 2 * 1536 ** 2 + 2048 ** 2)
    router = 8 * 4 * (2048 ** 2 + 128 ** 2)
    assert parts['mixer'] == mixer == 3_208_806_400
    assert parts['moe'] == experts + shared + router
    assert parts.get('mlp', 0) == (0 if dense else 8 * (
        2048 ** 2 + 6144 ** 2 + 2 * 6144 ** 2 + 2048 ** 2
    ))
    assert sum(parts.values()) == pytest.approx(
        6.44e9 if dense else 7.41e9, rel=2e-3
    )


def test_state_bytes_by_part_on_the_tiny_engine(compared):
    _, _, registry = seeded(compared.config, skip=['lm_head'])
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry, damping=0.003, lr=0.1,
        compute_method='inverse', inverse_solver='newton_schulz',
    )
    engine = kaisa.DistributedKFAC(config=cfg, mesh=None)
    parts = engine.state_bytes_by_part
    assert set(parts) == {'mixer', 'mlp', 'moe'}
    # float32: A of u (32), of the latent (12), of o's input (32); G of
    # q_nope 32, q_rope 16, kv_a 12, k_rope 4, k_nope 32, v 32, o 32
    a = 32 ** 2 + 12 ** 2 + 32 ** 2
    g = 4 * 32 ** 2 + 16 ** 2 + 12 ** 2 + 4 ** 2
    assert parts['mixer'] == 5 * 8 * (a + g)
    assert parts['mlp'] == 8 * (32 ** 2 + 48 ** 2 + 2 * 48 ** 2 + 32 ** 2)
    no_groups = kaisa.DistributedKFAC(
        config=kfac_tpu.KFACPreconditioner(
            registry=dataclasses.replace(registry, a_groups={}),
            damping=0.003, lr=0.1, compute_method='inverse',
            inverse_solver='newton_schulz',
        ), mesh=None,
    ).state_bytes_by_part
    assert no_groups['mixer'] == 5 * 8 * (a + g + 3 * 32 ** 2 + 12 ** 2)
    text = engine.describe()
    assert 'factor and inverse bytes by part of a block: mixer ' in text


# ------------------------------------------------- the scope in the program


def test_the_step_program_carries_the_scope_forward_and_backward(compared):
    assert tracing.MODEL_SCOPES['mla_latent'] == 'model.mla_latent'
    batch = batch_of(compared.config)
    text = jax.jit(
        jax.value_and_grad(hybrid_lm_loss(compared.model))
    ).lower(compared.params, batch).compile().as_text()
    names = [
        line.split('op_name="', 1)[1].split('"', 1)[0]
        for line in text.splitlines() if 'op_name="' in line
    ]
    under = [n for n in names if 'model.mla_latent' in n]
    assert under
    # inside the mixer's scope, beside the core's and not inside it
    assert all('model.mixer' in n for n in under)
    assert not any('model.attention' in n for n in under)
    assert any('model.attention' in n for n in names)
    assert any('transpose(' in n for n in under)
    assert any('jvp(' in n and 'transpose(' not in n for n in under)


# ------------------------------------------------- the operation count


def test_flops_are_6n_plus_the_core():
    config = full_config()
    d = 2048
    mixer = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    dense = 3 * d * 6144
    routed = d * 128 + (6 * 8 / 128 + 2) * 3 * d * 768
    n = 5 * mixer + dense + 4 * routed + d * 16032
    assert flops.dense_layers(config) == 1
    assert flops.matmul_params(config) == n
    seq = config['seq_len']
    core = 6 * 32 * (192 + 128) * seq
    assert flops.core_flops_per_token(config) == core
    assert flops.train_flops_per_token(config) == 6 * n + 5 * core
    assert flops.train_flops_per_sample(config) == seq * (6 * n + 5 * core)


# ------------------------------------------------- readers, synthetic trace


PATH = 'jit(_step_with_stats)/jit(main)/'


def _op(name, start, ns, op_name):
    return {
        'name': f'%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
        'start_ns': start, 'duration_ns': ns, 'stats': {'op_name': op_name},
    }


def _ctx(ops, kinds, engine=None):
    plane = {'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': ops},
    ]}
    run = types.SimpleNamespace(trainer=types.SimpleNamespace(kfac=engine))
    return harness.LayerContext(
        cell={}, run=run, devices=[], first_order_rows=[], rows=[],
        traced_rows=[{'kind': k} for k in kinds], trace={'planes': [plane]},
        windows={plane['name']: (0, 10_000)}, throughput=0.0,
    )


def test_mla_latent_row_on_a_synthetic_trace():
    fwd, bwd = 'jvp(LatentMoELM)/', 'transpose(jvp(LatentMoELM))/'
    scope = 'block0/model.mixer/mixer/checkpoint/model.mla_latent/'
    ops = [
        # a plain step: forward 100, rematerialised 80, backward 120
        _op('fusion.1', 0, 100, PATH + fwd + scope + 'concatenate'),
        _op('fusion.2', 100, 80, PATH + bwd + 'block0/model.mixer/mixer/'
            'checkpoint/rematted_computation/model.mla_latent/mul'),
        _op('fusion.3', 200, 120, PATH + bwd + scope + 'mul'),
        # the projections and the core around it are not the glue's
        _op('fusion.4', 400, 500, PATH + fwd + 'block0/model.mixer/mixer/'
            'q_nope_proj/dot_general'),
        _op('fusion.5', 900, 50, PATH + fwd + 'block0/model.mixer/mixer/'
            'checkpoint/model.attention/dot_general'),
        # a capture step: the same 100
        _op('fusion.6', 1000, 100, PATH + fwd + scope + 'concatenate'),
    ]
    ctx = _ctx(ops, ['plain', 'capture'])
    assert harness.read_layer_metric('dev_ms.mla_latent', ctx) == (
        pytest.approx((300 + 100) / 2 / 1e6)
    )
    # a reader of its own, not a row of scopes: the engine rows' scopes
    # (harness.trace_scopes) stay what benchmark/tests pins them to
    assert 'model.mla_latent' not in harness.trace_scopes()
    assert harness.layer_reader('dev_ms.mla_latent').MLA_LATENT == (
        tracing.MODEL_SCOPES['mla_latent']
    )
    # a program without the scope (the parent, another model): nothing
    other = [_op('fusion.1', 0, 100, PATH + fwd + 'block0/attn/dot')]
    assert harness.read_layer_metric(
        'dev_ms.mla_latent', _ctx(other, ['plain'])
    ) is None


def test_mixer_state_row_reads_the_engines_counter():
    engine = types.SimpleNamespace(
        state_bytes_by_part={'mixer': 3_208_806_400, 'moe': 1}
    )
    assert harness.read_layer_metric(
        'mixer_state_gb', _ctx([], ['plain'], engine)
    ) == pytest.approx(3.2088064)
    # an engine without the counter (the parent), a model without mixers
    for engine in (
        types.SimpleNamespace(),
        types.SimpleNamespace(state_bytes_by_part={'stage0': 5}),
    ):
        assert harness.read_layer_metric(
            'mixer_state_gb', _ctx([], ['plain'], engine)
        ) is None


# ------------------------------------------------- the three parts together


NEW_ROWS = {
    'dev_ms.mla_latent': {
        'name': 'dev_ms.mla_latent', 'unit': 'ms', 'better': 'lower',
        'source': 'device_trace', 'layer': 'model', 'moves': 'throughput',
        'workloads': [CELL],
    },
    'mixer_state_gb': {
        'name': 'mixer_state_gb', 'unit': 'GB', 'better': 'lower',
        'source': 'program_counter', 'layer': 'engine',
        'moves': 'peak_hbm_gb', 'workloads': [CELL],
    },
}


def test_configuration_cell_and_rows_hold_together():
    """A configuration that no cell runs is never measured (PR 36): the
    ``configs`` entry, the ``workloads`` entry and the new rows are all
    there, every file they name exists, and the cell loads."""
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    listed = {c['name']: c for c in bench['configs']}['kanana-2-30b-a3b']
    assert listed['file'] == 'benchmark/configs/kanana-2-30b-a3b.json'
    assert os.path.exists(os.path.join(ROOT, listed['file']))
    assert {w['name']: w for w in bench['workloads']}[CELL] == {
        'name': CELL, 'config': 'kanana-2-30b-a3b', 'traffic': 'kfac-10-100',
        'chips': 1, 'why': bench['workloads'][-1]['why'],
    }
    rows = {m['name']: m for m in bench['per_layer']}
    for name, row in NEW_ROWS.items():
        assert rows[name] == row
    for part in (
        'workloads/' + CELL + '.json', 'jobs/latent_moe_lm.py',
        'refs/latent_moe_lm.py', 'flops/latent_moe_lm.py',
        'layer_metrics/dev_ms/mla_latent.py',
        'layer_metrics/mixer_state_gb.py',
    ):
        assert os.path.exists(os.path.join(ROOT, 'benchmark', part)), part
    cell = harness.load_cell(CELL)
    config = cell['config']
    assert config['kind'] == 'latent_moe_lm' and cell['chips'] == 1
    assert config['source'] == listed['source']
    assert listed['reduced'] == config['reduced'] == [
        k for k in config['published'] if config[k] != config['published'][k]
    ] == list(config['published'])
    assert [m['name'] for m in cell['bench']['end_to_end']] == [
        'throughput', 'kfac_overhead', 'peak_hbm_gb', 'setup_s'
    ]
    end_to_end = {m['name']: m for m in bench['end_to_end']}
    assert CELL not in end_to_end['stall_ms']['workloads']
    assert CELL in rows['longest_step_ms']['workloads']
    read = {m['name'] for m in harness.layer_rows(cell)}
    for name in (
        'dev_ms.mla_latent', 'mixer_state_gb', 'longest_step_ms',
        'refresh_extra_ms.overhead', 'dev_ms.update_inverses.overhead',
        'ns_trips_refresh.overhead', 'dev_ms.moe_route', 'dev_ms.moe_experts',
        'dev_ms.capture_experts', 'expert_rows_mean', 'expert_dropped',
        'kfac_first_order_share', 'precondition_in_layout_share',
        'dev_ms.mixer', 'dev_ms.attention', 'dev_ms.mlp', 'kfac_state_gb',
        'mfu', 'idle_share',
    ):
        assert name in read, name
    for name in (
        'dev_ms.gdn_scan', 'dev_ms.short_conv', 'dev_ms.capture_patches',
        'collective_ms', 'refresh_extra_ms', 'dev_ms.update_inverses',
        'ns_trips_refresh',
    ):
        assert name not in read, name
    for name in read:
        # every row the cell reads has a reader the harness finds
        assert harness.layer_reader(name) is not None or os.path.exists(
            os.path.join(ROOT, 'benchmark/layer_metrics', name + '.json')
        ), name


def test_the_configuration_keeps_every_published_width():
    config = full_config()
    published = {
        'hidden_size': 2048, 'intermediate_size': 6144,
        'moe_intermediate_size': 768, 'num_attention_heads': 32,
        'num_key_value_heads': 32, 'head_dim': 64, 'kv_lora_rank': 512,
        'q_lora_rank': None, 'qk_head_dim': 192, 'qk_nope_head_dim': 128,
        'qk_rope_head_dim': 64, 'v_head_dim': 128, 'num_experts_per_tok': 6,
        'n_shared_experts': 2, 'routed_scaling_factor': 2.448,
        'first_k_dense_replace': 1, 'moe_layer_freq': 1, 'n_group': 1,
        'topk_group': 1, 'norm_topk_prob': True, 'scoring_func': 'sigmoid',
        'topk_method': 'noaux_tc', 'rms_norm_eps': 1e-6,
        'rope_theta': 1_000_000, 'rope_interleave': True,
        'rope_scaling': None, 'attention_bias': False, 'hidden_act': 'silu',
        'tie_word_embeddings': False, 'max_position_embeddings': 32768,
        'model_type': 'deepseek_v3', 'router_width': 128,
    }
    for key, value in published.items():
        assert config[key] == value, key
    assert config['published'] == {
        'num_hidden_layers': 48, 'n_routed_experts': 128,
        'vocab_size': 128256,
    }
    assert [config[k] for k in config['reduced']] == [5, 8, 16032]
    assert config['vocab_size'] * 8 == 128256
    assert config['experts_held'] == [0, 8]
    assert config['source_layers'] == [0, 1, 2, 3, 4]
    for key in ('deployment', 'assumed', 'departures', 'batch_set_by'):
        assert config[key], key
    model = job.model_of(config)
    assert (model.num_layers, model.num_dense_layers) == (5, 1)
    assert model.routed_scale == 2.448 and model.num_shared_experts == 2
    with pytest.raises(ValueError, match='no option'):
        job.model_of(dict(config, q_lora_rank=1536))
    with pytest.raises(ValueError, match='source_layers'):
        job.model_of(dict(config, num_hidden_layers=4))
