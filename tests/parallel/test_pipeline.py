"""Pipeline-parallel LM + K-FAC tests (GPipe schedule over a pipe axis).

Behavioral targets: the reference's GPT-NeoX pipeline e2e suite
(tests/gpt_neox/gpt_preconditioner_test.py: preconditioner over pipeline
stages {1,2,4}) — here the schedule itself is also validated against an
unpipelined sequential application of the same stage weights.
"""

import flax.linen as flax_nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import kfac_tpu
from kfac_tpu.parallel import pipeline


def _mesh(n_stages):
    return Mesh(
        np.asarray(jax.devices()[:n_stages]).reshape(n_stages), ('pipe',)
    )


def _model(n_stages, num_layers=4, micro=4, d=32):
    return pipeline.PipelinedLM(
        mesh=_mesh(n_stages),
        vocab_size=64,
        d_model=d,
        num_heads=4,
        num_layers=num_layers,
        n_microbatches=micro,
        max_len=16,
    )


def _sequential_logits(model, params, tokens):
    """Oracle: apply stages one after another without the pipeline."""
    x = model._embed(params, tokens)
    for s in range(model.n_stages):
        sp = jax.tree_util.tree_map(lambda v: v[s], params['stages'])
        x = model.stage.apply({'params': sp}, x)
    x = model.ln_f.apply({'params': params['ln_f']}, x.astype(jnp.float32))
    return model.head.apply({'params': params['head']}, x)


@pytest.mark.parametrize(
    'n_stages,layers',
    [(1, 2), (2, 4), pytest.param(4, 4, marks=pytest.mark.slow)],
)
def test_pipeline_forward_matches_sequential(n_stages, layers):
    model = _model(n_stages, num_layers=layers)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1))
    logits, a_stats, counts = jax.jit(model.apply)(params, tokens)
    expected = _sequential_logits(model, params, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(expected), rtol=2e-3, atol=2e-4
    )
    # every stage processed all microbatches
    np.testing.assert_allclose(np.asarray(counts), model.n_microbatches)
    for name, h in model.stage_registry.layers.items():
        assert a_stats[name].shape == (n_stages,) + h.a_factor_shape


def test_pipeline_stats_match_dense_capture():
    """Stage-stacked A/G stats must equal the dense interceptor capture on
    the equivalent unpipelined model (single stage)."""
    model = _model(1, num_layers=2, micro=2)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    params = model.init(jax.random.PRNGKey(1))
    targets = jnp.roll(tokens, -1, 1)
    loss, grads, stats = model.loss_and_stats(params, (tokens, targets))

    # dense oracle: same computation as a flat flax model via the standard
    # capture machinery
    def flat_loss(stage_params, batch):
        tk, tg = batch
        x = model._embed(params, tk)
        x = model.stage.apply({'params': stage_params}, x)
        x = model.ln_f.apply({'params': params['ln_f']}, x.astype(jnp.float32))
        logits = model.head.apply({'params': params['head']}, x)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, tg[..., None], -1))

    cap = kfac_tpu.CurvatureCapture(model.stage_registry)
    sp0 = jax.tree_util.tree_map(lambda v: v[0], params['stages'])
    (loss0, _), grads0, stats0 = cap.value_stats_and_grad(flat_loss)(
        sp0, (tokens, targets)
    )
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for name in stats0.a:
        np.testing.assert_allclose(
            np.asarray(stats.a[name][0]), np.asarray(stats0.a[name]),
            rtol=1e-3, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(stats.g[name][0]), np.asarray(stats0.g[name]),
            rtol=1e-3, atol=1e-6,
        )
    # stage grads match too
    np.testing.assert_allclose(
        np.asarray(
            grads['stages']['block0']['attn']['q_proj']['kernel'][0]
        ),
        np.asarray(grads0['block0']['attn']['q_proj']['kernel']),
        rtol=1e-3, atol=1e-6,
    )


@pytest.mark.parametrize(
    'n_stages', [2, pytest.param(4, marks=pytest.mark.slow)]
)
def test_pipeline_kfac_training(n_stages):
    model = _model(n_stages, num_layers=4, micro=4)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = model.init(jax.random.PRNGKey(1))
    cfg = kfac_tpu.KFACPreconditioner(
        registry=model.stage_registry, damping=0.01, lr=0.1
    )
    pk = pipeline.PipelineKFAC(config=cfg, model=model)
    state = pk.init()

    @jax.jit
    def train_step(params, state, batch):
        loss, grads, stats = model.loss_and_stats(params, batch)
        state, grads = pk.step(state, grads, stats)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads
        )
        return params, state, loss

    losses = []
    for _ in range(6):
        params, state, loss = train_step(params, state, (tokens, targets))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))
    assert int(state['step']) == 6
    # stage factor state is actually sharded over pipe
    key = next(iter(state['a']))
    assert 'pipe' in str(state['a'][key].sharding.spec)


@pytest.mark.slow
def test_pipeline_dp_matches_pipe_only():
    """PP composed with DP: the (2 pipe x 4 data) mesh must produce the
    same loss trajectory as the pipe-only 2-stage run on the same global
    batch — proving the batch shard / stat psum / grad reduction over the
    data axes is exact (the reference's DP factor allreduce,
    kfac/gpt_neox/layer.py:61-93)."""
    from kfac_tpu.parallel import mesh as mesh_lib

    def run(mesh, steps=5):
        model = pipeline.PipelinedLM(
            mesh=mesh, vocab_size=64, d_model=32, num_heads=4,
            num_layers=4, n_microbatches=2, max_len=16,
        )
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
        targets = jnp.roll(tokens, -1, 1)
        params = model.init(jax.random.PRNGKey(1))
        cfg = kfac_tpu.KFACPreconditioner(
            registry=model.stage_registry, damping=0.01, lr=0.1,
            factor_update_steps=2, inv_update_steps=2,
        )
        pk = pipeline.PipelineKFAC(config=cfg, model=model)
        state = pk.init()

        @jax.jit
        def train_step(params, state, batch):
            loss, grads, stats = model.loss_and_stats(params, batch)
            state, grads = pk.step(state, grads, stats)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, params, grads
            )
            return params, state, loss

        losses = []
        for _ in range(steps):
            params, state, loss = train_step(params, state, (tokens, targets))
            losses.append(float(loss))
        return losses, model

    dp_mesh = mesh_lib.pipeline_mesh(n_stages=2)
    assert dict(dp_mesh.shape) == {
        'pipe': 2, 'kfac_gw': 1, 'kfac_col': 4, 'model': 1,
    }
    losses_dp, model_dp = run(dp_mesh)
    losses_pp, _ = run(_mesh(2))
    np.testing.assert_allclose(losses_dp, losses_pp, rtol=2e-4)
    assert losses_dp[-1] < losses_dp[0]


@pytest.mark.slow
def test_pipeline_dp_stats_match_dense_capture():
    """A/G statistics captured under PP x DP equal the dense interceptor
    capture of the same single-stage model on the full batch."""
    from kfac_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.pipeline_mesh(n_stages=1)
    model = pipeline.PipelinedLM(
        mesh=mesh, vocab_size=64, d_model=32, num_heads=4,
        num_layers=2, n_microbatches=2, max_len=16,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (16, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = model.init(jax.random.PRNGKey(1))
    loss, grads, stats = model.loss_and_stats(params, (tokens, targets))

    def flat_loss(stage_params, batch):
        tk, tg = batch
        x = model._embed(params, tk)
        x = model.stage.apply({'params': stage_params}, x)
        x = model.ln_f.apply({'params': params['ln_f']}, x.astype(jnp.float32))
        logits = model.head.apply({'params': params['head']}, x)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, tg[..., None], -1))

    cap = kfac_tpu.CurvatureCapture(model.stage_registry)
    sp0 = jax.tree_util.tree_map(lambda v: v[0], params['stages'])
    (loss0, _), grads0, stats0 = cap.value_stats_and_grad(flat_loss)(
        sp0, (tokens, targets)
    )
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for name in stats0.a:
        np.testing.assert_allclose(
            np.asarray(stats.a[name][0]), np.asarray(stats0.a[name]),
            rtol=1e-3, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(stats.g[name][0]), np.asarray(stats0.g[name]),
            rtol=1e-3, atol=1e-6,
        )


# deliberately NOT slow-marked: this is the equivalence guard on the
# hardest scheduling code (the fast tier must keep
# it); ~60 s warm-cache on the 1-core container
def test_1f1b_matches_gpipe_loss_grads_stats():
    """The combined-scan 1F1B schedule computes the same loss, parameter
    gradients, and A/G statistics as the GPipe autodiff path — on a
    DP x PP mesh (2 pipe x 2 data)."""
    from kfac_tpu.parallel.mesh import pipeline_mesh

    mesh = pipeline_mesh(n_stages=2, devices=jax.devices()[:4])
    kw = dict(
        mesh=mesh, vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        n_microbatches=4, max_len=16,
    )
    gp = pipeline.PipelinedLM(**kw, schedule='gpipe')
    ob = pipeline.PipelinedLM(**kw, schedule='1f1b')
    params = gp.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    l_g, g_g, s_g = jax.jit(gp.loss_and_stats)(params, (tokens, targets))
    l_o, g_o, s_o = jax.jit(ob.loss_and_stats)(params, (tokens, targets))
    np.testing.assert_allclose(float(l_g), float(l_o), rtol=1e-5)
    flat_g = jax.tree_util.tree_leaves_with_path(g_g)
    flat_o = jax.tree_util.tree_leaves_with_path(g_o)
    for (pg, vg), (po, vo) in zip(flat_g, flat_o):
        assert pg == po
        np.testing.assert_allclose(
            np.asarray(vg), np.asarray(vo), rtol=2e-4, atol=2e-6,
            err_msg=str(pg),
        )
    for k in s_g.a:
        np.testing.assert_allclose(
            np.asarray(s_g.a[k]), np.asarray(s_o.a[k]),
            rtol=1e-4, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(s_g.g[k]), np.asarray(s_o.g[k]),
            rtol=1e-4, atol=1e-7,
        )


@pytest.mark.slow
def test_1f1b_kfac_training():
    """End-to-end: PipelineKFAC trains on the 1F1B schedule, many
    microbatches (the regime the O(stages) residual ring exists for)."""
    model = pipeline.PipelinedLM(
        mesh=_mesh(2), vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        n_microbatches=8, max_len=16, schedule='1f1b',
    )
    tokens = jax.random.randint(jax.random.PRNGKey(0), (16, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = model.init(jax.random.PRNGKey(1))
    cfg = kfac_tpu.KFACPreconditioner(
        registry=model.stage_registry, damping=0.01, lr=0.1
    )
    pk = pipeline.PipelineKFAC(config=cfg, model=model)
    state = pk.init()

    @jax.jit
    def train_step(params, state, batch):
        loss, grads, stats = model.loss_and_stats(params, batch)
        state, grads = pk.step(state, grads, stats)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads
        )
        return params, state, loss

    losses = []
    for _ in range(6):
        params, state, loss = train_step(params, state, (tokens, targets))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))


def test_1f1b_rejects_unknown_schedule():
    with pytest.raises(ValueError):
        pipeline.PipelinedLM(
            mesh=_mesh(2), vocab_size=64, d_model=32, num_heads=4,
            num_layers=2, schedule='2f2b',
        )


@pytest.mark.slow
def test_pipeline_inverse_method_matches_eigen():
    """INVERSE (Newton-Schulz) and EIGEN solve the same damped Kronecker
    system, so pipelined training trajectories coincide."""
    def run(**cfg_kw):
        model = _model(2, num_layers=2, micro=4)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
        targets = jnp.roll(tokens, -1, 1)
        params = model.init(jax.random.PRNGKey(1))
        cfg = kfac_tpu.KFACPreconditioner(
            registry=model.stage_registry, damping=0.01, lr=0.1,
            kl_clip=None, **cfg_kw,
        )
        pk = pipeline.PipelineKFAC(config=cfg, model=model)
        state = pk.init()

        @jax.jit
        def train_step(params, state, batch):
            loss, grads, stats = model.loss_and_stats(params, batch)
            state, grads = pk.step(state, grads, stats)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, params, grads
            )
            return params, state, loss

        losses = []
        for _ in range(5):
            params, state, loss = train_step(
                params, state, (tokens, targets)
            )
            losses.append(float(loss))
        return losses

    eig = run(compute_method='eigen')
    inv = run(compute_method='inverse', inverse_solver='newton_schulz')
    chol = run(compute_method='inverse')
    assert all(np.isfinite(eig)) and eig[-1] < eig[0]
    np.testing.assert_allclose(eig, inv, rtol=2e-3)
    np.testing.assert_allclose(chol, inv, rtol=2e-3)


@pytest.mark.slow
def test_pipeline_checkpoint_roundtrip(tmp_path):
    """PipelineKFAC state saves/restores through kfac_tpu.checkpoint:
    factors persist, decompositions rematerialize, trajectories continue
    identically."""
    pytest.importorskip('orbax.checkpoint')
    from kfac_tpu import checkpoint as ckpt_lib

    model = _model(2, num_layers=2, micro=2)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = model.init(jax.random.PRNGKey(1))
    cfg = kfac_tpu.KFACPreconditioner(
        registry=model.stage_registry, damping=0.01, lr=0.1
    )
    pk = pipeline.PipelineKFAC(config=cfg, model=model)
    state = pk.init()

    @jax.jit
    def train_step(params, state, batch):
        loss, grads, stats = model.loss_and_stats(params, batch)
        state, grads = pk.step(state, grads, stats)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads
        )
        return params, state, loss

    for _ in range(3):
        params, state, _ = train_step(params, state, (tokens, targets))

    ckpt_lib.save(str(tmp_path / 'pp'), state, extra={'params': params})
    restored, extra = ckpt_lib.restore(
        str(tmp_path / 'pp'), pk, extra_template={'params': params}
    )
    assert int(restored['step']) == int(state['step'])
    key = next(iter(state['a']))
    np.testing.assert_allclose(
        np.asarray(restored['a'][key]), np.asarray(state['a'][key])
    )
    # decompositions rematerialized from factors, not zeros
    assert float(jnp.abs(restored['qa'][key]).max()) > 0

    # training continues identically from the restored state
    p1, s1, l1 = train_step(params, state, (tokens, targets))
    p2, s2, l2 = train_step(extra['params'], restored, (tokens, targets))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jax.tree_util.tree_leaves(p1)[0]),
        np.asarray(jax.tree_util.tree_leaves(p2)[0]),
        rtol=1e-5,
    )


@pytest.mark.parametrize(
    'schedule', [pytest.param('gpipe', marks=pytest.mark.slow), '1f1b']
)
def test_tp_pp_matches_pp_dp_only(schedule):
    """3D composition (pipe=2 x dp=2 x model=2) must reproduce the
    (pipe=2 x dp=4) loss trajectory on the same global batch: tensor
    parallelism enters only through the auto model axis + param shardings,
    so GSPMD's Megatron all-reduces cannot change the math (the
    reference's DeepSpeed 3D topology, gpt_neox/preconditioner.py:70-73).
    """
    from kfac_tpu.parallel import mesh as mesh_lib

    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)

    def run(tp):
        mesh = mesh_lib.pipeline_mesh(n_stages=2, model=tp)
        model = pipeline.PipelinedLM(
            mesh=mesh, vocab_size=64, d_model=32, num_heads=4,
            num_layers=2, n_microbatches=2, max_len=16, schedule=schedule,
        )
        params = model.init(jax.random.PRNGKey(1))
        cfg = kfac_tpu.KFACPreconditioner(
            registry=model.stage_registry, damping=0.01, lr=0.1
        )
        pk = pipeline.PipelineKFAC(config=cfg, model=model)
        state = pk.init()

        @jax.jit
        def train_step(params, state, batch):
            loss, grads, stats = model.loss_and_stats(params, batch)
            state, grads = pk.step(state, grads, stats)
            params = jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, params, grads
            )
            return params, state, loss

        losses = []
        for _ in range(3):
            params, state, loss = train_step(params, state, (tokens, targets))
            losses.append(float(loss))
        return losses, model, params

    losses_3d, model_3d, params_3d = run(tp=2)
    losses_dp, _, _ = run(tp=1)
    np.testing.assert_allclose(losses_3d, losses_dp, rtol=2e-4)
    assert losses_3d[-1] < losses_3d[0]
    # TP actually sharded the Megatron pairs over the model axis
    spec = params_3d['stages']['block0']['attn']['q_proj']['kernel'].sharding.spec
    assert 'model' in str(spec), spec
    spec = params_3d['stages']['block0']['mlp_down']['kernel'].sharding.spec
    assert 'model' in str(spec), spec
    # ... and the LM head is vocab-parallel: its (d, V) kernel shards V
    # over the model axis, so the head matmul + fused-NLL softmax run at
    # 1/tp per device instead of replicated per microbatch
    hspec = params_3d['head']['kernel'].sharding.spec
    assert hspec == jax.sharding.PartitionSpec(None, 'model'), hspec


class _MLPStage(flax_nn.Module):
    """Non-transformer stage: a residual MLP over the feature dim."""

    width: int = 64

    @flax_nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        h = flax_nn.Dense(self.width, name='up')(x)
        h = flax_nn.relu(h)
        return x + flax_nn.Dense(d, name='down')(h)


def test_pipeline_custom_stage_module_trains():
    """Any flax (B,S,D)->(B,S,D) module pipelines with K-FAC (reference
    wraps arbitrary DeepSpeed PipelineModules,
    gpt_neox/preconditioner.py:161-165): registry, capture, and both
    schedule paths are derived from the module itself."""
    from kfac_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.pipeline_mesh(n_stages=2)
    model = pipeline.PipelinedLM(
        mesh=mesh, vocab_size=64, d_model=32, num_heads=4,
        num_layers=2, n_microbatches=2, max_len=16, schedule='1f1b',
        stage_module=_MLPStage(width=48),
    )
    assert set(model.stage_registry.layers) == {'up', 'down'}
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, 1)
    params = model.init(jax.random.PRNGKey(1))
    cfg = kfac_tpu.KFACPreconditioner(
        registry=model.stage_registry, damping=0.01, lr=0.1
    )
    pk = pipeline.PipelineKFAC(config=cfg, model=model)
    state = pk.init()

    @jax.jit
    def train_step(params, state, batch):
        loss, grads, stats = model.loss_and_stats(params, batch)
        state, grads = pk.step(state, grads, stats)
        params = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads
        )
        return params, state, loss

    losses = []
    for _ in range(6):
        params, state, loss = train_step(params, state, (tokens, targets))
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))
    # factor state carries the custom module's layers, stage-stacked
    assert state['a']['up'].shape[0] == 2


def test_pipeline_rejects_shape_changing_stage():
    from kfac_tpu.parallel import mesh as mesh_lib

    with pytest.raises(ValueError, match='map'):
        pipeline.PipelinedLM(
            mesh=mesh_lib.pipeline_mesh(n_stages=2), vocab_size=64,
            d_model=32, num_heads=4, num_layers=2, n_microbatches=2,
            max_len=16, stage_module=flax_nn.Dense(16),
        )


def test_custom_stage_tp_overrides_shard_over_model_axis():
    """A custom stage module with square layers plus explicit tp_overrides
    shards over the model axis (the heuristic would replicate squares);
    without overrides, the silent-replication warning fires."""
    import warnings as stdlib_warnings

    from kfac_tpu.parallel import mesh as mesh_lib
    from kfac_tpu.parallel.tensor_parallel import UnshardedParamWarning

    class SquarePair(flax_nn.Module):
        @flax_nn.compact
        def __call__(self, x):
            d = x.shape[-1]
            h = flax_nn.relu(flax_nn.Dense(d, name='first')(x))
            return x + flax_nn.Dense(d, name='second')(h)

    mesh = mesh_lib.pipeline_mesh(n_stages=2, model=2)

    def build(overrides):
        return pipeline.PipelinedLM(
            mesh=mesh, vocab_size=64, d_model=32, num_heads=4,
            num_layers=2, n_microbatches=2, max_len=16,
            stage_module=SquarePair(), tp_overrides=overrides,
        )

    # no matching override: everything replicates, loudly
    with stdlib_warnings.catch_warnings(record=True) as w:
        stdlib_warnings.simplefilter('always')
        build(()).init(jax.random.PRNGKey(0))
    assert any(isinstance(x.message, UnshardedParamWarning) for x in w)

    # explicit Megatron pairing: kernels shard over model, silently
    plm = build((('.*first', 'column'), ('.*second', 'row')))
    with stdlib_warnings.catch_warnings(record=True) as w:
        stdlib_warnings.simplefilter('always')
        params = plm.init(jax.random.PRNGKey(0))
    assert not any(isinstance(x.message, UnshardedParamWarning) for x in w)
    first = params['stages']['first']['kernel']
    second = params['stages']['second']['kernel']
    assert str(first.sharding.spec) == str(
        jax.sharding.PartitionSpec('pipe', None, 'model')
    )
    assert str(second.sharding.spec) == str(
        jax.sharding.PartitionSpec('pipe', 'model', None)
    )
    # and the sharded stage trains
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 64)
    loss, grads, stats = jax.jit(plm.loss_and_stats)(
        params, (tokens, jnp.roll(tokens, -1, 1))
    )
    assert np.isfinite(float(loss))
    assert set(stats.a) == {'first', 'second'}
