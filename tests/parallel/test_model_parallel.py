"""Tensor-parallel + context-parallel K-FAC training tests (8-device mesh).

Behavioral targets: the reference's GPT-NeoX e2e suite
(tests/gpt_neox/gpt_preconditioner_test.py) — K-FAC over model-parallel
layers — plus context parallelism the reference lacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu.models import TransformerLM, lm_loss
from kfac_tpu.parallel import (
    DistributedKFAC,
    tensor_parallel,
)
from kfac_tpu.parallel import mesh as mesh_lib
from kfac_tpu.parallel.mesh import token_sharding, train_mesh


def _lm(mesh=None, ring_axis=None, **kw):
    cfg = dict(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2, max_len=32
    )
    cfg.update(kw)
    return TransformerLM(ring_mesh=mesh, ring_axis=ring_axis, **cfg)


def test_train_mesh_axes():
    mesh = train_mesh(grad_worker_fraction=1.0, model=2, seq=2)
    assert dict(mesh.shape) == {
        'kfac_gw': 2, 'kfac_col': 1, 'model': 2, 'seq': 2,
    }
    with pytest.raises(ValueError):
        train_mesh(model=3, seq=1)  # 8 % 3 != 0


def test_param_specs_rules():
    m = _lm()
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = m.init(jax.random.PRNGKey(0), tokens)['params']
    specs = tensor_parallel.param_specs(params)
    from jax.sharding import PartitionSpec as P

    assert specs['block0']['attn']['q_proj']['kernel'] == P(None, 'model')
    assert specs['block0']['attn']['out_proj']['kernel'] == P('model', None)
    assert specs['block0']['attn']['out_proj']['bias'] == P()
    assert specs['block0']['mlp_up']['bias'] == P('model')
    assert specs['embed']['embedding'] == P()
    assert specs['lm_head']['kernel'] == P(None, 'model')


def test_tp_kfac_training_matches_replicated():
    """K-FAC over TP-sharded params must match the fully-replicated run."""
    mesh = train_mesh(grad_worker_fraction=1.0, model=2)
    m = _lm()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = m.init(jax.random.PRNGKey(1), tokens)['params']
    reg = kfac_tpu.register_model(m, tokens)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, damping=0.01, lr=0.1)
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    loss = lm_loss(m)
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss)

    def step(params, state, batch):
        (l, _), grads, stats = run(params, batch)
        state, pg = dk.step(state, grads, stats)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, pg)
        return params, state, l

    # TP run: params sharded over the model axis
    tp_params = tensor_parallel.shard_params(params, mesh)
    batch = (
        jax.device_put(tokens, token_sharding(mesh)),
        jax.device_put(targets, token_sharding(mesh)),
    )
    state = dk.init()
    tp_step = jax.jit(step)
    p_tp, s_tp, l_tp = tp_step(tp_params, state, batch)
    # replicated run (same math, no TP layout)
    p_rep, s_rep, l_rep = tp_step(params, dk.init(), (tokens, targets))
    np.testing.assert_allclose(float(l_tp), float(l_rep), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p_tp['block0']['attn']['q_proj']['kernel']),
        np.asarray(p_rep['block0']['attn']['q_proj']['kernel']),
        rtol=2e-3, atol=2e-5,
    )
    # the TP params actually live sharded
    assert 'model' in str(
        p_tp['block0']['attn']['q_proj']['kernel'].sharding.spec
    )


def test_context_parallel_kfac_training():
    """Ring-attention LM with the sequence sharded trains under K-FAC and
    matches the dense-attention model's loss trajectory."""
    mesh = train_mesh(grad_worker_fraction=1.0, seq=4)
    m_ring = _lm(mesh=mesh, ring_axis=mesh_lib.SEQ_AXIS)
    m_dense = _lm()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 32), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = m_dense.init(jax.random.PRNGKey(1), tokens)['params']
    reg = kfac_tpu.register_model(m_ring, tokens)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, damping=0.01, lr=0.1)
    dk = DistributedKFAC(config=cfg, mesh=mesh)

    def make_step(model):
        loss = lm_loss(model)
        cap = kfac_tpu.CurvatureCapture(reg)
        run = cap.value_stats_and_grad(loss)

        @jax.jit
        def step(params, state, batch):
            (l, _), grads, stats = run(params, batch)
            state, pg = dk.step(state, grads, stats)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, params, pg
            )
            return params, state, l

        return step

    ring_step = make_step(m_ring)
    dense_step = make_step(m_dense)
    ts = token_sharding(mesh)
    batch_ring = (jax.device_put(tokens, ts), jax.device_put(targets, ts))

    p_r, s_r = params, dk.init()
    p_d, s_d = params, dk.init()
    for _ in range(3):
        p_r, s_r, l_r = ring_step(p_r, s_r, batch_ring)
        p_d, s_d, l_d = dense_step(p_d, s_d, (tokens, targets))
    np.testing.assert_allclose(float(l_r), float(l_d), rtol=1e-3)
    assert np.isfinite(float(l_r))


def test_tp_with_hybrid_kaisa():
    """TP (model=2) composed with HYBRID-OPT KAISA (dp=4 -> 2x2 grid)."""
    mesh = train_mesh(grad_worker_fraction=0.5, model=2)
    assert dict(mesh.shape) == {
        'kfac_gw': 2, 'kfac_col': 2, 'model': 2, 'seq': 1,
    }
    m = _lm()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = tensor_parallel.shard_params(
        m.init(jax.random.PRNGKey(1), tokens)['params'], mesh
    )
    reg = kfac_tpu.register_model(m, tokens)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, damping=0.01)
    dk = DistributedKFAC(config=cfg, mesh=mesh)
    assert dk.world == 4 and dk.grad_workers == 2
    loss = lm_loss(m)
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss)

    @jax.jit
    def step(params, state, batch):
        (l, _), grads, stats = run(params, batch)
        state, pg = dk.step(state, grads, stats)
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, pg)
        return params, state, l

    ts = token_sharding(mesh)
    batch = (jax.device_put(tokens, ts), jax.device_put(targets, ts))
    state = dk.init()
    losses = []
    for _ in range(4):
        params, state, l = step(params, state, batch)
        losses.append(float(l))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


class _GenericNet:
    """A model with names unlike anything in kfac_tpu.models — proves the
    registry-derived TP rules need no name table."""

    def build(self):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                x = nn.relu(nn.Dense(128, name='expander')(x))
                x = nn.Dense(32, name='contractor')(x)
                return nn.Dense(10, name='classify_out', use_bias=False)(x)

        return Net()


def test_registry_derived_tp_rules_generic_model():
    from jax.sharding import PartitionSpec as P

    m = _GenericNet().build()
    x = jnp.zeros((4, 32))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)

    kinds = tensor_parallel.derive_layer_kinds(reg)
    assert kinds == {
        'expander': 'column',      # 32 -> 128 expands
        'contractor': 'row',       # 128 -> 32 contracts
        'classify_out': 'row',     # 32 -> 10 contracts
    }
    # user override: keep the head replicated
    kinds = tensor_parallel.derive_layer_kinds(
        reg, overrides=[('classify_out', 'replicated')]
    )
    assert kinds['classify_out'] == 'replicated'

    specs = tensor_parallel.registry_param_specs(
        params, reg, overrides=[('classify_out', 'replicated')],
        warn_unmatched=False,
    )
    assert specs['expander']['kernel'] == P(None, 'model')
    assert specs['expander']['bias'] == P('model')
    assert specs['contractor']['kernel'] == P('model', None)
    assert specs['contractor']['bias'] == P()
    assert specs['classify_out']['kernel'] == P()


def test_registry_tp_warns_on_unmatched_params():
    import warnings as pywarnings

    import flax.linen as nn

    class WithNorm(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(64, name='wide')(x)
            x = nn.LayerNorm(name='normalizer')(x)
            return nn.Dense(8, name='narrow')(x)

    m = WithNorm()
    x = jnp.zeros((2, 16))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    with pywarnings.catch_warnings(record=True) as rec:
        pywarnings.simplefilter('always')
        tensor_parallel.registry_param_specs(params, reg)
    msgs = [str(w.message) for w in rec
            if issubclass(w.category, tensor_parallel.UnshardedParamWarning)]
    assert msgs and 'normalizer' in msgs[0]


def test_row_parallel_a_factor_matches_gathered_oracle():
    """The reference gathers a row-parallel layer's model-sharded input
    activations before computing A (kfac/gpt_neox/layer.py:129-163). Under
    GSPMD the captured A factor of a row-parallel layer must equal the
    oracle computed from the unsharded activations."""
    mesh = train_mesh(grad_worker_fraction=1.0, model=4)
    m = _GenericNet().build()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    y = jax.nn.one_hot(jnp.arange(16) % 10, 10)
    params = m.init(jax.random.PRNGKey(1), x)['params']
    reg = kfac_tpu.register_model(m, x)

    def loss_fn(params, batch):
        xb, yb = batch
        logits = m.apply({'params': params}, xb)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * yb, -1))

    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss_fn)

    # oracle: fully replicated params/batch
    (_, _), _, stats_rep = jax.jit(run)(params, (x, y))

    # TP: 'contractor' is row-parallel, so its input activations (the
    # 'expander' output) are model-sharded under GSPMD
    tp_params = tensor_parallel.shard_params_from_registry(
        params, mesh, reg, warn_unmatched=False
    )
    bs = mesh_lib.batch_sharding(mesh)
    batch = (jax.device_put(x, bs), jax.device_put(jnp.asarray(y), bs))
    (_, _), _, stats_tp = jax.jit(run)(tp_params, batch)

    for name in ('contractor', 'expander'):
        np.testing.assert_allclose(
            np.asarray(stats_tp.a[name]), np.asarray(stats_rep.a[name]),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(stats_tp.g[name]), np.asarray(stats_rep.g[name]),
            rtol=1e-4, atol=1e-6,
        )
