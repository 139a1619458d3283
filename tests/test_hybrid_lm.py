"""The sparse hybrid LM (Gated DeltaNet + gated attention + top-k routed
experts of which a share is held) against the benchmark's plain reference
and against per-part oracles, at a small size on the CPU.

``benchmark/refs/hybrid_lm.py`` imports nothing of ``kfac_tpu``: it runs
the delta rule a position at a time, attention with whole score matrices
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
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.jobs import hybrid_lm as job  # noqa: E402
from benchmark.layer_metrics import _hybrid  # noqa: E402
from benchmark.refs import hybrid_lm as ref  # noqa: E402
from kfac_tpu import enums, preconditioner  # noqa: E402
from kfac_tpu.layers import capture as capture_lib  # noqa: E402
from kfac_tpu.layers import registry as registry_lib  # noqa: E402
from kfac_tpu.models import attention, deltanet, hybrid_lm_loss, moe  # noqa: E402
from kfac_tpu.ops import grouped  # noqa: E402
from kfac_tpu.parallel import kaisa  # noqa: E402

CELL = 'qwen3-next-80b-a3b.kfac-10-100'
TINY = dict(
    hidden_size=32, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, num_experts_per_tok=3,
    router_width=16, experts_held=[4, 4], num_experts=4, vocab_size=64,
    seq_len=19, compute_dtype='float32', scan_chunk=4, attention_chunk=8,
    expert_block_rows=4, batch_per_chip=3,
)


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision('highest'):
        yield


def tiny_config(**over):
    with open(os.path.join(ROOT, 'benchmark/configs/qwen3-next-80b-a3b.json')) as f:
        config = json.load(f)
    config.update(TINY)
    config.update(over)
    return config


def seeded(config, seed=5):
    model = job.model_of(config)
    tok = jnp.zeros((1, config['seq_len']), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok))
    params = weights.make(shapes, weights.seed_key(seed))['params']
    registry = kfac_tpu.register_model(model, tok, skip_layers=['lm_head'])
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


def test_loss_matches_the_reference(compared):
    program, reference = compared.loss
    assert program == pytest.approx(reference, rel=1e-6)


def test_every_gradient_leaf_matches_the_reference(compared):
    program, reference = compared.grads
    assert set(program) == set(reference)
    for name, want in reference.items():
        assert rel(program[name], want) < 2e-4, name


def test_registered_layers_are_the_references(compared):
    names = ref.kfac_layers(compared.params)
    assert set(names) == set(compared.registry.layers)
    # 7 + 7 + 7 + 5 projections, 5 shared parts a layer, 3 x 4 experts a layer
    assert len(names) == 26 + 4 * 5 + 4 * 12


@pytest.mark.parametrize('side', ['a', 'g'])
def test_every_factor_matches_the_reference(compared, side):
    program = getattr(compared.stats, side)
    reference = getattr(compared, side)
    for name in ref.kfac_layers(compared.params):
        # a group's one A statistic is filed under its leader, and has to
        # be the reference's A of every member
        key = compared.registry.a_leader(name) if side == 'a' else name
        assert rel(program[key], reference[name]) < 2e-4, (side, name)


def test_traffic_counts_rows_and_no_drop(compared):
    traffic = compared.stats.traffic
    assert len(traffic) == 12  # three projections of four layers
    config = compared.config
    for name, row in traffic.items():
        rows, dropped = np.asarray(row[:-1]), float(row[-1])
        assert dropped == 0
        assert rows.shape == (config['experts_held'][1],)
        # every expert of a layer saw the same rows through its three taps
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


# ------------------------------------------------------------ the share test


def test_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the shares' routed parts plus the
    shared expert once are the uncut reference's layer output."""
    d, experts, k, width = 32, 16, 3, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, d))

    def layer(held):
        return moe.SparseMoE(
            experts, k, width, shared_width=width, experts_held=held,
            block_rows=4,
        )

    whole = layer(None)
    params = weights.make(
        jax.eval_shape(lambda: whole.init(jax.random.PRNGKey(0), x)),
        weights.seed_key(11),
    )['params']

    def share_params(first):
        p = dict(params)
        p['experts'] = {
            proj: {f'e{j}': sub[f'e{first + j}'] for j in range(4)}
            for proj, sub in params['experts'].items()
        }
        return p

    m = {
        'experts_held': (0, experts), 'num_experts_per_tok': k,
        'norm_topk_prob': True,
    }
    uncut, _, rows = ref._moe(params, None, x, m)
    assert int(jnp.sum(rows)) == 2 * 11 * k

    def shared_only(p):
        xf = x.reshape(-1, d)
        gate, _ = ref._dense(xf, p['shared_gate'], None)
        out, _ = ref._gated_mlp(p['shared'], None, xf, {})
        return (jax.nn.sigmoid(gate) * out).reshape(x.shape)

    shared = shared_only(params)
    routed = sum(
        layer((first, 4)).apply({'params': share_params(first)}, x) - shared
        for first in range(0, experts, 4)
    )
    np.testing.assert_allclose(routed + shared, uncut, rtol=2e-5, atol=2e-6)
    # and the program's own uncut layer is the reference's
    np.testing.assert_allclose(
        whole.apply({'params': params}, x), uncut, rtol=2e-5, atol=2e-6
    )


# ----------------------------------------- stacked capture, per-expert oracle


def _moe_capture(top_k, held, tokens=24, experts=8, seed=3, x=None):
    d, width = 16, 8
    layer = moe.SparseMoE(
        experts, top_k, width, experts_held=held, block_rows=4
    )
    if x is None:
        x = jax.random.normal(jax.random.PRNGKey(seed), (tokens, d))
    params = weights.make(
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x)),
        weights.seed_key(seed),
    )['params']
    registry = kfac_tpu.register_model(layer, x)
    probe = jax.random.normal(jax.random.PRNGKey(seed + 1), x.shape)

    def loss(p, x):
        return jnp.sum(layer.apply({'params': p}, x) * probe)

    cap = kfac_tpu.CurvatureCapture(registry)
    (_, _), grads, stats = cap.value_stats_and_grad(loss)(params, x)
    return layer, params, registry, stats, x, probe


def test_stacked_capture_is_the_per_expert_oracle_under_topk_weights():
    top_k, (first, held) = 2, (2, 4)
    layer, params, registry, stats, x, probe = _moe_capture(top_k, (first, held))
    logits = x @ params['router']['kernel']
    wts, idx = jax.lax.top_k(jax.nn.softmax(logits), top_k)
    wts = wts / jnp.sum(wts, -1, keepdims=True)
    for j in range(held):
        hit = idx == first + j
        mine = np.asarray(jnp.any(hit, -1))
        w = jnp.sum(jnp.where(hit, wts, 0.0), -1)[mine]
        rows = x[mine]
        assert len(rows) > 0
        kernels = {
            proj: params['experts'][proj][f'e{j}']['kernel']
            for proj in ('gate_proj', 'up_proj', 'down_proj')
        }

        def expert(rows, kernels=kernels):
            g = rows @ kernels['gate_proj']
            u = rows @ kernels['up_proj']
            return g, u, jax.nn.silu(g) * u

        g, u, hid = expert(rows)
        # the expert's own rows, its own count: inputs ...
        for proj, inp in (('gate_proj', rows), ('up_proj', rows),
                          ('down_proj', hid)):
            want = inp.T @ inp / len(rows)
            got = stats.a[registry.a_leader(f'experts/{proj}/e{j}')]
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
        # ... and output gradients: of the down projection's rows, the
        # token's times the routing weight
        dy = probe[mine] * w[:, None]
        want = dy.T @ dy / len(rows)
        np.testing.assert_allclose(
            stats.g[f'experts/down_proj/e{j}'], want, rtol=2e-5, atol=1e-6
        )
        dh = dy @ kernels['down_proj'].T
        _, pull = jax.vjp(lambda g, u: jax.nn.silu(g) * u, g, u)
        dg, du = pull(dh)
        for proj, grad in (('gate_proj', dg), ('up_proj', du)):
            np.testing.assert_allclose(
                stats.g[f'experts/{proj}/e{j}'], grad.T @ grad / len(rows),
                rtol=2e-5, atol=1e-6,
            )
        assert float(stats.w[f'experts/gate_proj/e{j}']) == 1.0


def test_expert_without_rows_keeps_its_factors():
    """Rows that all route to experts 0 and 1: held experts 2.. see none.
    Their capture weight is 0 and the engine's EMA leaves their factors as
    they were (the identity), while an expert with rows moves."""
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(0), (1, 16)), (8, 1))
    _, params, registry, stats, _, _ = _moe_capture(1, (0, 8), x=x)
    chosen = int(jnp.argmax(x[0] @ params['router']['kernel']))
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry, factor_decay=0.5, damping=0.01, lr=0.1,
        compute_method='inverse', inverse_solver='newton_schulz',
    )
    engine = kaisa.DistributedKFAC(config=cfg, mesh=None)
    state = engine.init()
    new = jax.jit(engine.update_factors)(state, stats)
    report = engine.traffic_report(new)
    assert report == {'rows_min': 0.0, 'rows_mean': 1.0, 'dropped': 0.0}
    for j in range(8):
        name = f'experts/gate_proj/e{j}'
        assert float(stats.w[name]) == (1.0 if j == chosen else 0.0)
        key, slot = engine._a_slot[name]
        before, after = state.a[key][slot], new.a[key][slot]
        if j == chosen:
            assert float(jnp.max(jnp.abs(after - before))) > 1e-3
        else:
            np.testing.assert_array_equal(after, before)


def test_plan_holds_the_worst_load_and_counts_no_drop():
    """Every token chooses the same held experts: the plan places every
    assignment (capacity is the worst case) and says so."""
    tokens, k, held, block = 13, 3, 2, 4
    idx = jnp.tile(jnp.asarray([[5, 4, 9]], jnp.int32), (tokens, 1))
    wts = jnp.full((tokens, k), 1.0 / k)
    plan = moe.make_plan(idx, wts, 4, held, block)
    assert int(plan.dropped) == 0
    np.testing.assert_array_equal(plan.rows, [tokens, tokens])
    assert int(plan.n_blocks) == 2 * -(-tokens // block)
    placed = np.asarray(plan.row_token).ravel()
    assert sorted(placed[placed < tokens]) == sorted(list(range(tokens)) * 2)
    assert float(jnp.sum(plan.row_weight)) == pytest.approx(2 * tokens / k)


# ------------------------------------------------------- grouped products


def test_grouped_products_match_dense_and_their_gradients():
    tokens, d, n, experts, k, block = 17, 8, 6, 3, 2, 4
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(key[0], (tokens, d))
    w = jax.random.normal(key[1], (experts, d, n))
    w2 = jax.random.normal(key[2], (experts, n, d))
    idx = jax.random.randint(key[3], (tokens, k), 0, experts + 2)
    wts = jnp.full((tokens, k), 0.5)
    plan = moe.make_plan(idx, wts, 0, experts, block)

    def program(x, w, w2):
        h = grouped.grouped_matmul_gather(
            x, w, plan.row_token, plan.block_expert, plan.n_blocks
        )
        return grouped.grouped_matmul_combine(
            jnp.tanh(h), w2, plan.row_token, plan.row_weight,
            plan.block_expert, plan.n_blocks, tokens,
        )

    def dense(x, w, w2):
        y = jnp.zeros((tokens, d))
        for e in range(experts):
            gate = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)[:, None]
            y = y + gate * (jnp.tanh(x @ w[e]) @ w2[e])
        return y

    np.testing.assert_allclose(
        program(x, w, w2), dense(x, w, w2), rtol=1e-5, atol=1e-5
    )
    probe = jax.random.normal(jax.random.PRNGKey(9), (tokens, d))
    got = jax.grad(lambda *a: jnp.sum(program(*a) * probe), (0, 1, 2))(x, w, w2)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * probe), (0, 1, 2))(x, w, w2)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ chunked scan


def _scan_inputs(t, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, h, dk, dv = 2, 3, 8, 6
    q = deltanet.l2norm(jax.random.normal(key[0], (b, t, h, dk))) * dk ** -0.5
    k = deltanet.l2norm(jax.random.normal(key[1], (b, t, h, dk)))
    v = jax.random.normal(key[2], (b, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(key[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (b, t, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize('t,chunk', [(23, 8), (16, 8), (5, 8)])
def test_chunked_scan_is_the_recurrence(t, chunk):
    """Forward and every input's gradient against the reference's scan
    over positions, at lengths that are and are not multiples of the
    chunk."""
    inputs = _scan_inputs(t)
    want = ref._delta_rule(*inputs)
    got = deltanet.chunk_gated_delta_rule(*inputs, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    probe = jax.random.normal(jax.random.PRNGKey(7), want.shape)
    grads = [
        jax.grad(lambda *a, f=f: jnp.sum(f(*a) * probe), argnums=range(5))(
            *inputs
        )
        for f in (
            lambda *a: deltanet.chunk_gated_delta_rule(*a, chunk=chunk),
            ref._delta_rule,
        )
    ]
    for got_g, want_g in zip(*grads):
        np.testing.assert_allclose(got_g, want_g, rtol=2e-4, atol=2e-5)


def _chunk_system(keys, c, seed=0):
    """One chunk's ``a`` and right-hand side as the scan builds them, for
    three kinds of keys: drawn apart; neighbours 0.9-correlated with
    ``beta`` 0.98 and hardly any decay; one key repeated with ``beta`` 1
    and no decay, where the inverse's entries grow fastest."""
    lead, d, n = (3, 2), 16, 12
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(key[0], lead + (c, d))
    beta = jax.nn.sigmoid(jax.random.normal(key[1], lead + (c,)))
    g = -jax.nn.softplus(jax.random.normal(key[2], lead + (c,)))
    if keys == 'correlated':
        k = 0.9 * k[..., :1, :] + (1 - 0.81) ** 0.5 * k
        beta, g = jnp.full_like(beta, 0.98), jnp.full_like(g, -1e-3)
    elif keys == 'repeated':
        k = jnp.broadcast_to(k[..., :1, :], k.shape)
        beta, g = jnp.ones_like(beta), jnp.zeros_like(g)
    k = deltanet.l2norm(k, eps=0.0)
    b = jnp.cumsum(g, axis=-1)
    a = jnp.einsum('...id,...jd->...ij', k * beta[..., None], k)
    a = jnp.tril(a * jnp.exp(b[..., :, None] - b[..., None, :]), -1)
    return a, jax.random.normal(key[3], lead + (c, n))


@pytest.mark.parametrize('c', [64, 8, 5])
@pytest.mark.parametrize('keys', ['random', 'correlated', 'repeated'])
def test_unit_lower_solve_is_the_float64_solve(keys, c):
    """Forward within 2e-6 of the largest entry and both cotangents within
    2e-5, at the cell's chunk, at one block and at a chunk no block
    divides."""
    a, rhs = _chunk_system(keys, c)
    probe = jax.random.normal(jax.random.PRNGKey(7), rhs.shape)

    def value_and_cotangents(solve, *args):
        return solve(*args), jax.grad(
            lambda *z: jnp.sum(solve(*z) * probe), (0, 1)
        )(*args)

    with jax.enable_x64():
        want, want_bar = value_and_cotangents(
            lambda a, rhs: jax.scipy.linalg.solve_triangular(
                a + jnp.eye(c), rhs, lower=True, unit_diagonal=True
            ),
            np.asarray(a, np.float64), np.asarray(rhs, np.float64),
        )
    got, got_bar = value_and_cotangents(deltanet.unit_lower_solve, a, rhs)
    assert got.dtype == jnp.float32
    for g, w, tol in zip(
        (got, *got_bar), (want, *want_bar), (2e-6, 2e-5, 2e-5)
    ):
        g, w = np.asarray(g, np.float64), np.asarray(w)
        assert w.dtype == np.float64
        assert np.abs(g - w).max() <= tol * np.abs(w).max()
    assert not np.triu(got_bar[0]).any()


def test_no_triangular_solve_in_the_scan_or_its_gradient():
    """XLA lowers ``triangular_solve`` on the TPU to a custom call that
    inverts each block whole on one serial row algorithm (688 us for the
    cell's 512 systems, 48 times a step: PERF.md section 6, PR 39), and a
    CPU run cannot see it come back: the jaxpr can."""
    inputs = _scan_inputs(16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(deltanet.chunk_gated_delta_rule(*a, chunk=8)),
        argnums=range(5),
    ))(*inputs)
    assert 'triangular_solve' not in str(jaxpr)
    assert 'custom_vjp_call' in str(jax.make_jaxpr(
        lambda *a: deltanet.chunk_gated_delta_rule(*a, chunk=8)
    )(*inputs))


def test_blockwise_attention_is_dense_attention_with_grouped_queries():
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (2, 32, 4, 8))
    k = jax.random.normal(key[1], (2, 32, 2, 8))
    v = jax.random.normal(key[2], (2, 32, 2, 8))

    def dense(q, k, v):
        k, v = (jnp.repeat(z, 2, axis=2) for z in (k, v))
        s = jnp.einsum('bqhd,bkhd->bhqk', q * 8 ** -0.5, k)
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, -1), v)

    def chunked(q, k, v):
        return attention.blockwise_causal_attention(q, k, v, chunk=8)

    np.testing.assert_allclose(
        chunked(q, k, v), dense(q, k, v), rtol=2e-5, atol=2e-6
    )
    got = jax.grad(lambda *a: jnp.sum(chunked(*a) ** 2), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_loss_in_chunks_is_the_whole_softmax():
    config = tiny_config(seq_len=20, loss_chunk=5)
    model, params, _ = seeded(config)
    tokens, targets = batch_of(config)
    logits = model.apply({'params': params}, tokens)
    want = -jnp.take_along_axis(
        jax.nn.log_softmax(logits), targets[..., None], -1
    )[..., 0]
    got = model.apply({'params': params}, tokens, targets)
    assert got.shape == targets.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- pass-through rule


def test_passthrough_rule_names_what_kfac_does_not_factor():
    _, params, registry = seeded(tiny_config())
    expected = {
        'embed/embedding': 'embedding',
        'lm_head/kernel': 'skipped',
        'norm_f/weight': 'elementwise',
    }
    for i in range(4):
        expected[f'block{i}/norm1/weight'] = 'elementwise'
        expected[f'block{i}/norm2/weight'] = 'elementwise'
    for i in range(3):
        expected.update({
            f'block{i}/mixer/conv1d/kernel': 'convolution',
            f'block{i}/mixer/A_log': 'elementwise',
            f'block{i}/mixer/dt_bias': 'elementwise',
            f'block{i}/mixer/scale': 'elementwise',
        })
    expected['block3/mixer/q_norm/weight'] = 'elementwise'
    expected['block3/mixer/k_norm/weight'] = 'elementwise'
    assert registry.passthrough == expected
    assert set(expected.values()) <= set(registry_lib.PASSTHROUGH_RULE)
    # every leaf is one or the other, and K-FAC's are the layers' kernels
    leaves = set(flat(params))
    assert leaves == set(registry.passthrough) | set(registry.kfac_leaves)
    assert set(registry.kfac_leaves) == {
        '/'.join(path) + '/kernel' for path in registry.param_paths.values()
    }


def test_passthrough_leaves_pass_the_preconditioner_unchanged():
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
    state, out = jax.jit(engine.step)(engine.init(), grads, stats)
    before, after = flat(grads), flat(out)
    for leaf in registry.passthrough:
        np.testing.assert_array_equal(after[leaf], before[leaf])
    moved = [
        leaf for leaf in registry.kfac_leaves
        if float(jnp.max(jnp.abs(after[leaf] - before[leaf]))) > 0
    ]
    assert len(moved) == len(registry.kfac_leaves)


def test_dense_registries_report_passthrough_too():
    from kfac_tpu.models import TransformerLM

    model = TransformerLM(
        vocab_size=32, d_model=16, num_heads=2, num_layers=1, max_len=8
    )
    registry = kfac_tpu.register_model(
        model, jnp.zeros((1, 8), jnp.int32), skip_layers=['lm_head']
    )
    assert registry.stacks == {}
    assert registry.passthrough['embed/embedding'] == 'embedding'
    assert registry.passthrough['pos_embed'] == 'unsupported'
    assert registry.passthrough['lm_head/kernel'] == 'skipped'
    assert registry.passthrough['block0/ln1/scale'] == 'elementwise'
    assert 'block0/mlp_up/kernel' in registry.kfac_leaves
    assert 'block0/mlp_up/bias' in registry.kfac_leaves


# ------------------------------------------------------- solve in groups


@pytest.mark.parametrize('slots,d,groups', [
    (12, 3200, 1),    # gpt2-small's widest stack: whole, as before
    (48, 896, 1),
    (3, 4608, 1),
    (78, 2048, 3),    # the sparse model's: three groups of 26
    (36, 2048, 2),
    (7, 8192, 7),
])
def test_solve_groups(slots, d, groups):
    assert kaisa._solve_groups(slots, d) == groups


def test_grouped_solve_is_the_whole_solve(monkeypatch):
    x = jnp.zeros((4, 12))
    registry = kfac_tpu.register_model(
        kfac_tpu.models.MLP(features=(12, 12, 12, 12)), x
    )
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry, damping=0.01, lr=0.1, compute_method='inverse',
        inverse_solver='newton_schulz',
    )
    engine = kaisa.DistributedKFAC(
        config=cfg, mesh=kaisa.mesh_lib.kaisa_mesh(devices=jax.devices()[:1])
    )
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, (4, 16, 16))
    stack = jnp.einsum('lij,lkj->lik', m, m) / 16
    whole, told = jax.jit(engine._sharded_inv)(stack, 0.01)
    monkeypatch.setattr(kaisa, 'SOLVE_GROUP_BYTES', 2 * 16 * 16 * 4)
    assert kaisa._solve_groups(4, 16) == 2
    parts, told_parts = jax.jit(engine._sharded_inv)(stack, 0.01)
    np.testing.assert_allclose(parts, whole, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(told_parts[:, 0], told[:, 0])


def test_refresh_report_counts_trips_a_group():
    """A stack solved in two groups ran each group's loop to its own
    slowest slot: 9 + 5 trips, not the bucket's 9; and so with the
    scaled steps among them."""
    solved = np.zeros((6, len(kaisa.REFRESH_COLUMNS)), np.float32)
    solved[:, 0] = [9, 3, 4, 5, 2, 0]
    solved[:, 4] = [6, 0, 0, 4, 0, 0]
    refresh = kaisa.RefreshState(
        (('a', '16x16', 4, 4), ('g', '16x16', 2, 2)), jnp.asarray(solved),
        (2, 1),
    )
    by_bucket = kaisa._refresh_by_bucket(refresh)
    assert [b['trips'] for b in by_bucket] == [9 + 5, 2]
    assert kaisa.refresh_totals(refresh)['refresh/trips'] == 16.0
    assert [b['scaled_trips'] for b in by_bucket] == [6 + 4, 0]
    assert kaisa.refresh_totals(refresh)['refresh/scaled_trips'] == 10.0
    # a state from before the groups were recorded reads as one group each
    old = kaisa.RefreshState(refresh.buckets, refresh.solved)
    assert [b['trips'] for b in kaisa._refresh_by_bucket(old)] == [9, 2]


# ------------------------------------------------- readers, synthetic trace


PATH = 'jit(_step_with_stats)/jit(main)/'


def _op(name, start, ns, op_name):
    return {
        'name': f'%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
        'start_ns': start, 'duration_ns': ns, 'stats': {'op_name': op_name},
    }


def _ctx(ops, kinds, report=None):
    plane = {'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': ops},
    ]}
    engine = types.SimpleNamespace()
    if report is not None:
        engine.traffic_report = lambda state: report
    run = types.SimpleNamespace(
        trainer=types.SimpleNamespace(kfac=engine),
        state=types.SimpleNamespace(kfac_state=None),
    )
    return harness.LayerContext(
        cell={}, run=run, devices=[], first_order_rows=[], rows=[],
        traced_rows=[{'kind': k} for k in kinds], trace={'planes': [plane]},
        windows={plane['name']: (0, 10_000)}, throughput=0.0,
    )


def _hybrid_ops():
    fwd, bwd = 'jvp(HybridLM)/', 'transpose(jvp(HybridLM))/'
    return [
        # a plain step: scan 300 (forward 100, remat 80, backward 120),
        # route 50, experts 200
        _op('fusion.1', 0, 100, PATH + fwd + 'block0/mixer/checkpoint/model.gdn_scan/while'),
        _op('fusion.2', 100, 80, PATH + bwd + 'block0/mixer/checkpoint/rematted_computation/model.gdn_scan/while'),
        _op('fusion.3', 200, 120, PATH + bwd + 'block0/mixer/checkpoint/model.gdn_scan/while'),
        _op('sort.4', 400, 50, PATH + fwd + 'block0/moe/model.moe_route/sort'),
        _op('while.5', 500, 200, PATH + fwd + 'block0/moe/model.moe_experts/experts/gate_proj/while'),
        # a capture step: the same scan 100 and experts 100, and the
        # experts' capture inside the experts' scope: 60 on the A side, 40
        # on the G side, which are capture's and not the model's
        _op('fusion.6', 1000, 100, PATH + fwd + 'block0/mixer/checkpoint/model.gdn_scan/while'),
        _op('while.7', 1200, 100, PATH + fwd + 'block0/moe/model.moe_experts/experts/gate_proj/while'),
        _op('while.8', 1300, 60, PATH + fwd + 'block0/moe/model.moe_experts/experts/kfac.capture_a/experts/while'),
        _op('while.9', 1400, 40, PATH + bwd + 'block0/moe/model.moe_experts/experts/kfac.capture_g/experts/while'),
        # dense capture is neither
        _op('fusion.10', 1500, 70, PATH + fwd + 'block0/mixer/q_proj/kfac.capture_a/dot_general'),
    ]


def _read(name, ctx):
    return harness.read_layer_metric(name, ctx)


def test_model_scope_readers_on_a_synthetic_trace():
    ctx = _ctx(_hybrid_ops(), ['plain', 'capture'])
    assert _read('dev_ms.gdn_scan', ctx) == pytest.approx((300 + 100) / 2 / 1e6)
    assert _read('dev_ms.moe_route', ctx) == pytest.approx(50 / 2 / 1e6)
    # the experts' capture runs inside the scope and is not counted in it
    assert _read('dev_ms.moe_experts', ctx) == pytest.approx((200 + 100) / 2 / 1e6)
    # per capturing step, both sides, and only the experts' part
    assert _read('dev_ms.capture_experts', ctx) == pytest.approx(100 / 1 / 1e6)


def test_readers_return_nothing_on_a_program_without_the_names():
    """The parent commit, or a dense model: no such scope, no such report."""
    dense = [_op('fusion.1', 0, 100, PATH + 'jvp(TransformerLM)/block0/attn/dot')]
    ctx = _ctx(dense, ['plain', 'capture'])
    for name in ('dev_ms.gdn_scan', 'dev_ms.moe_route', 'dev_ms.moe_experts',
                 'dev_ms.capture_experts', 'expert_rows_min',
                 'expert_rows_mean', 'expert_dropped'):
        assert _read(name, ctx) is None, name
    # an engine with the report and a model without stacked experts
    ctx = _ctx(dense, ['plain'], report={})
    assert _read('expert_dropped', ctx) is None
    # no capturing step in the stretch: nothing to divide by
    assert _read('dev_ms.capture_experts', _ctx(_hybrid_ops(), ['plain'])) is None


def test_traffic_readers_read_the_engines_report():
    report = {'rows_min': 3.0, 'rows_mean': 159.5, 'dropped': 0.0}
    ctx = _ctx([], ['capture'], report=report)
    assert _read('expert_rows_min', ctx) == 3.0
    assert _read('expert_rows_mean', ctx) == 159.5
    assert _read('expert_dropped', ctx) == 0.0


def test_new_rows_of_the_benchmark_name_the_new_cell_only():
    bench = harness.load_cell(CELL)['bench']
    new = {
        'dev_ms.gdn_scan', 'dev_ms.moe_route', 'dev_ms.moe_experts',
        'dev_ms.capture_experts', 'expert_rows_min', 'expert_rows_mean',
        'expert_dropped',
    }
    rows = {m['name']: m for m in bench['per_layer']}
    assert new <= set(rows)
    for name in new:
        assert CELL in rows[name]['workloads']
    assert _hybrid.CAPTURE_EXPERTS == (
        'kfac.capture_a/experts', 'kfac.capture_g/experts'
    )
    assert tr.match_scope(
        PATH + 'x/model.moe_experts/experts/kfac.capture_a/experts/while',
        _hybrid._ALL,
    ) == 'kfac.capture_a'
