"""``DistributedKFAC.precondition``'s two operand layouts.

Where every device holds every inverse (COMM-OPT) each layer multiplies
its own gradient against its inverse slots, a Dense kernel as it lies
(``_resident_views``); where the decompositions are sharded by column the
gradients are stacked like them (``_stacked_views``). Both, and the dense
engine, are one algorithm: held here element by element on seeded
inverses, and by the primitives the replicated program is made of.
"""

import json
import os
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import health as health_lib
from kfac_tpu.layers import registry as registry_lib
from kfac_tpu.models import moe
from kfac_tpu.parallel import DistributedKFAC, kaisa, kaisa_mesh
from benchmark import harness
from testing import models


class BiasFree(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(20, use_bias=False, name='fc1')(x))
        x = nn.relu(nn.Dense(20, use_bias=False, name='fc2')(x))
        return nn.Dense(8, use_bias=False, name='fc3')(x)


class BiasedWide(nn.Module):
    """A 768-wide biased layer: its A factor is 769 wide, in the 896 class."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(24, name='wide')(x))
        return nn.Dense(8, name='head')(x)


class BiasedRun(nn.Module):
    """Three biased layers of one shape: one batched product, the bias's
    rank-one terms with it."""

    @nn.compact
    def __call__(self, x):
        for name in ('fc1', 'fc2', 'fc3'):
            x = nn.relu(nn.Dense(12, name=name)(x))
        return x


def _case(name):
    """(model, example input, engine keywords, degraded layer or None)."""
    key = jax.random.PRNGKey(3)
    if name == 'biased_run':
        return BiasedRun(), jax.random.normal(key, (16, 12)), {}, None
    if name == 'bias_free':
        return BiasFree(), jax.random.normal(key, (16, 12)), {}, None
    if name == 'biased_padded':
        return BiasedWide(), jax.random.normal(key, (16, 768)), {}, None
    if name == 'expert_slots':
        layer = moe.SparseMoE(8, 2, 8, experts_held=(2, 4), block_rows=4)
        return layer, jax.random.normal(key, (24, 16)), {}, None
    if name == 'conv_and_head':
        return (
            models.TinyConvNet(), jax.random.normal(key, (4, 28, 28, 1)), {},
            None,
        )
    if name == 'degraded':
        cfg = health_lib.HealthConfig(degrade_after=2, warn=False)
        return (
            BiasFree(), jax.random.normal(key, (16, 12)), {'health': cfg},
            'fc2',
        )
    raise ValueError(name)


def _spd(key, d):
    r = jax.random.normal(key, (d, d), jnp.float32)
    return r @ r.T / d + jnp.eye(d, dtype=jnp.float32)


def _seeded(reg):
    """Per-layer symmetric positive-definite inverses, from a seed."""
    inv = {}
    for i, (name, h) in enumerate(reg.layers.items()):
        ka, kg = jax.random.split(jax.random.PRNGKey(100 + i))
        inv[name] = (
            _spd(ka, h.a_factor_shape[0]), _spd(kg, h.g_factor_shape[0])
        )
    # the members of an A group hold one A inverse, their leader's
    return {n: (inv[reg.a_leader(n)][0], g) for n, (_, g) in inv.items()}


def _stacked_state(dk, state, inv):
    """``state`` with each layer's seeded inverses in its class slots."""
    def fill(store, side, stacks):
        out = {}
        for sb in store:
            rows = [
                kaisa.pad_factor(inv[n][side], sb.d) for n in sb.layers
            ]
            rows += [jnp.zeros((sb.d, sb.d), jnp.float32)] * (
                sb.padded - len(rows)
            )
            out[sb.key] = jnp.stack(rows).astype(stacks[sb.key].dtype)
        return out

    return state._replace(
        a_inv=fill(dk.a_store, 0, state.a_inv),
        g_inv=fill(dk.g_store, 1, state.g_inv),
    )


def _grads(params, dtype=jnp.float32):
    """A seeded gradient for every leaf of ``params``."""
    return jax.tree_util.tree_map(
        lambda p: jax.random.normal(
            jax.random.PRNGKey(p.size % 97), p.shape
        ).astype(dtype),
        params,
    )


def _degrade(state, layer):
    if layer is None:
        return state
    bad = dict(state.health.bad_inv)
    bad[layer] = jnp.asarray(5, bad[layer].dtype)
    return state._replace(health=state.health._replace(bad_inv=bad))


@pytest.mark.parametrize('kl_clip', [None, 0.001], ids=['noclip', 'klclip'])
@pytest.mark.parametrize(
    'case',
    [
        'bias_free', 'biased_padded', 'biased_run', 'expert_slots',
        'conv_and_head', 'degraded',
    ],
)
def test_in_layout_stack_and_dense_engine_agree(case, kl_clip):
    """The in-layout path, the stack path and the dense engine give the
    same preconditioned gradient and the same telemetry, element by
    element, from the same float32 inverses."""
    model, x, kw, degraded = _case(case)
    params = model.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(model, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method='inverse', kl_clip=kl_clip, lr=0.5,
        damping=0.01, metrics=True, **kw,
    )
    mesh = kaisa_mesh(devices=jax.devices()[:1])
    resident = DistributedKFAC(config=cfg, mesh=mesh)
    stacked = DistributedKFAC(config=cfg, mesh=mesh)
    assert resident._in_layout and stacked._in_layout
    stacked._in_layout = False  # the test's switch, not an option
    inv = _seeded(reg)
    grads = _grads(params)

    dstate = _degrade(_stacked_state(resident, resident.init(), inv), degraded)
    dense_state = cfg.init()
    dense_state = _degrade(dense_state._replace(
        a_inv={n: inv[n][0] for n in dense_state.a_inv},
        g_inv={n: inv[n][1] for n in reg.layers},
    ), degraded)

    def run(engine, state):
        def fn(state, grads):
            scal = {}
            return engine.precondition(state, grads, metrics_out=scal), scal

        return jax.jit(fn)(state, grads)

    got = {
        'resident': run(resident, dstate),
        'stacked': run(stacked, dstate),
        'dense': run(cfg, dense_state),
    }
    want_tree, want_scal = got['stacked']
    assert set(want_scal) >= {f'grad_norm/{n}' for n in reg.layers}
    assert set(want_scal) >= {f'precond_grad_norm/{n}' for n in reg.layers}
    for path in ('resident', 'dense'):
        tree, scal = got[path]
        for (kp, a), b in zip(
            jax.tree_util.tree_leaves_with_path(tree),
            jax.tree_util.tree_leaves(want_tree),
        ):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5,
                atol=2e-5 * float(np.max(np.abs(b)) + 1e-30),
                err_msg=f'{path} {jax.tree_util.keystr(kp)}',
            )
        assert set(scal) == set(want_scal)
        for k in want_scal:
            np.testing.assert_allclose(
                np.asarray(scal[k]), np.asarray(want_scal[k]), rtol=2e-5,
                err_msg=f'{path} {k}',
            )
    # every layer is preconditioned; a degraded one passes as it came
    raw_layers = registry_lib.slice_layer_grads(grads, reg)
    for name, leaves in registry_lib.slice_layer_grads(want_tree, reg).items():
        same = all(
            np.array_equal(np.asarray(leaves[k]), np.asarray(raw_layers[name][k]))
            for k in leaves
        )
        assert same == (name == degraded and kl_clip is None), name
    if degraded is not None and kl_clip is None:
        for path in got:
            np.testing.assert_array_equal(
                np.asarray(got[path][0][degraded]['kernel']),
                np.asarray(grads[degraded]['kernel']),
            )


@pytest.mark.parametrize(
    'dtype', [jnp.float32, jnp.bfloat16], ids=['f32', 'bf16']
)
@pytest.mark.parametrize('case', ['expert_slots', 'biased_run', 'bias_free'])
def test_kl_clip_scale_against_float64(case, dtype):
    """The one scale across layers and the leaves it scales, against
    numpy in float64: ``min(1, sqrt(kl / |sum_layers sum(p * g) * lr^2|))``
    with ``p = G^-1 g A^-1`` in matrix form, for a run of stacked experts,
    a run of biased layers and lone layers, float32 and bfloat16
    gradients. The contraction is XLA's multiply-reduce on each product
    where it lies (``factors.kl_clip_terms``)."""
    model, x, kw, _ = _case(case)
    params = model.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(model, x)
    lr, kl = 0.5, 0.001
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method='inverse', kl_clip=kl, lr=lr,
        damping=0.01, metrics=True, **kw,
    )
    dk = DistributedKFAC(
        config=cfg, mesh=kaisa_mesh(devices=jax.devices()[:1])
    )
    assert dk._in_layout
    inv = _seeded(reg)
    grads = _grads(params, dtype)

    def fn(state, grads):
        scal = {}
        return dk.precondition(state, grads, metrics_out=scal), scal

    tree, scal = jax.jit(fn)(_stacked_state(dk, dk.init(), inv), grads)

    def f64(a):
        return np.asarray(a.astype(jnp.float32), np.float64)

    layer_grads = registry_lib.slice_layer_grads(grads, reg)
    pmats, vg = {}, 0.0
    for name, helper in reg.layers.items():
        gm = f64(helper.grads_to_matrix(layer_grads[name]))
        pmats[name] = f64(inv[name][1]) @ gm @ f64(inv[name][0])
        vg += float(np.sum(pmats[name] * gm)) * lr ** 2
    want_scale = min(1.0, np.sqrt(kl / abs(vg)))
    assert want_scale < 0.5  # the clip bites
    np.testing.assert_allclose(
        float(scal['kl_clip_scale']), want_scale, rtol=2e-5
    )
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -7
    for name, leaves in registry_lib.slice_layer_grads(tree, reg).items():
        want = reg.layers[name].matrix_to_grads(
            jnp.asarray(pmats[name] * want_scale, jnp.float32)
        )
        for k, leaf in leaves.items():
            assert leaf.dtype == dtype
            np.testing.assert_allclose(
                f64(leaf), f64(want[k]), rtol=tol,
                atol=tol * float(np.max(np.abs(f64(want[k])))),
                err_msg=f'{name} {k}',
            )


def test_kl_clip_terms_of_a_run_is_the_sum_of_its_layers():
    """``kl_clip_terms`` sums over every axis: a run's stacked products
    against its stacked gradients give the per-layer terms' sum."""
    from kfac_tpu.ops import factors

    kp, kg = jax.random.split(jax.random.PRNGKey(7))
    # same-signed terms: float32's summation order moves the sum in its
    # seventh digit, not by the terms' cancellation
    p = jnp.abs(jax.random.normal(kp, (8, 96, 40), jnp.float32))
    g = jnp.abs(jax.random.normal(kg, (8, 96, 40), jnp.bfloat16))
    run = factors.kl_clip_terms(p, g, 0.5)
    layers = sum(factors.kl_clip_terms(p[i], g[i], 0.5) for i in range(8))
    want = 0.25 * np.sum(
        np.asarray(p, np.float64)
        * np.asarray(g.astype(jnp.float32), np.float64)
    )
    np.testing.assert_allclose(float(run), float(layers), rtol=1e-6)
    np.testing.assert_allclose(float(run), want, rtol=1e-6)


def test_comm_opt_matches_hybrid_opt_on_the_cpu_mesh():
    """COMM-OPT (inverses on every device: the in-layout path) against
    HYBRID-OPT (sharded by column: the stack path) on the 8-device mesh,
    a whole step from the same weights, gradients and statistics."""
    m = BiasedWide()
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 768))
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cap = kfac_tpu.CurvatureCapture(reg)

    def loss_fn(p, batch):
        return jnp.mean((m.apply({'params': p}, batch[0]) - batch[1]) ** 2)

    (_, _), grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))
    out = {}
    for frac in (1.0, 0.5):
        cfg = kfac_tpu.KFACPreconditioner(
            registry=reg, compute_method='inverse', kl_clip=0.001,
            damping=0.01,
        )
        dk = DistributedKFAC(config=cfg, mesh=kaisa_mesh(frac))
        assert dk._in_layout == (frac == 1.0)
        _, out[frac] = jax.jit(dk.step)(dk.init(), grads, stats)
    for a, b in zip(
        jax.tree_util.tree_leaves(out[1.0]),
        jax.tree_util.tree_leaves(out[0.5]),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4,
            atol=1e-5 * float(np.max(np.abs(b))),
        )


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


class BiasFreeTwins(nn.Module):
    """``fc2`` and ``fc3`` have one shape: a run of two in their bucket."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(20, use_bias=False, name='fc1')(x))
        x = nn.relu(nn.Dense(20, use_bias=False, name='fc2')(x))
        x = nn.relu(nn.Dense(20, use_bias=False, name='fc3')(x))
        return nn.Dense(8, use_bias=False, name='fc4')(x)


# what packing into a padded gradient stack, or into the matrix form, is
# made of
_RELAYOUT = {
    'transpose', 'pad', 'scatter', 'scatter-add', 'scatter_add',
    'dynamic_update_slice',
}


@pytest.mark.parametrize(
    'model', [BiasFree(), BiasFreeTwins()], ids=['distinct', 'twins']
)
def test_replicated_engine_of_bias_free_dense_layers_lays_nothing_out(model):
    """The structure, with no chip: on a replicated engine the program of
    ``precondition`` over bias-free Dense layers holds no re-layout
    primitive; layers of one shape are joined as they lie for one batched
    product (a ``concatenate`` and nothing else), layers of distinct
    shapes not even that. Under HYBRID-OPT the same engine still builds
    its padded stack."""
    x = jnp.ones((16, 12))
    params = model.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(model, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method='inverse', kl_clip=0.001,
        metrics=True, health=True,
    )
    found = {}
    for frac in (1.0, 0.5):
        dk = DistributedKFAC(config=cfg, mesh=kaisa_mesh(frac))

        def fn(state, grads):
            scal = {}
            return dk.precondition(state, grads, metrics_out=scal), scal

        found[frac] = _primitives(
            jax.make_jaxpr(fn)(dk.init(), params).jaxpr
        )
    assert 'dot_general' in found[1.0]
    assert not found[1.0] & _RELAYOUT, found[1.0] & _RELAYOUT
    assert ('concatenate' in found[1.0]) == isinstance(model, BiasFreeTwins)
    assert found[0.5] & {'scatter', 'dynamic_update_slice'}
    assert 'transpose' in found[0.5]


def test_a_run_is_one_batched_product():
    """Same-shaped layers that follow each other in a bucket's slots are
    multiplied together: the twins' program holds fewer products than
    layers, and a lone layer none with a batch axis."""
    def dots(model):
        x = jnp.ones((16, 12))
        params = model.init(jax.random.PRNGKey(0), x)['params']
        reg = kfac_tpu.register_model(model, x)
        cfg = kfac_tpu.KFACPreconditioner(
            registry=reg, compute_method='inverse', kl_clip=None,
        )
        dk = DistributedKFAC(config=cfg, mesh=kaisa_mesh(1.0))
        jaxpr = jax.make_jaxpr(dk.precondition)(dk.init(), params).jaxpr
        return [
            len(e.params['dimension_numbers'][1][0])
            for e in jaxpr.eqns if e.primitive.name == 'dot_general'
        ]

    assert dots(BiasFree()) == [0] * 6  # three layers, two products each
    assert sorted(dots(BiasFreeTwins())) == [0, 0, 0, 0, 1, 1]


def _share(model, x, frac=1.0, **kw):
    reg = kfac_tpu.register_model(model, x)
    cfg = kfac_tpu.KFACPreconditioner(registry=reg, **kw)
    dk = DistributedKFAC(config=cfg, mesh=kaisa_mesh(frac))
    return reg, dk


def test_in_layout_share_counts_gradient_elements():
    """The counter, once at construction: every element of a language
    model's Dense layers, none where the stack is the placement or the
    method is not the explicit inverse, the head's share of a
    convolutional network."""
    lm_x = jnp.ones((16, 768))
    _, dk = _share(BiasedWide(), lm_x, compute_method='inverse')
    assert dk.in_layout_share == 1.0
    assert 'own layout: 100.0%' in dk.describe()
    _, dk = _share(BiasedWide(), lm_x, frac=0.5, compute_method='inverse')
    assert dk.in_layout_share == 0.0
    assert 'own layout: 0.0%' in dk.describe()
    _, dk = _share(BiasedWide(), lm_x, compute_method='eigen')
    assert dk.in_layout_share == 0.0

    reg, dk = _share(
        models.TinyConvNet(), jnp.ones((2, 28, 28, 1)),
        compute_method='inverse',
    )
    sizes = {
        n: h.a_factor_shape[0] * h.g_factor_shape[0]
        for n, h in reg.layers.items()
    }
    dense = sizes['fc1'] + sizes['fc2']
    assert dk.in_layout_share == pytest.approx(dense / sum(sizes.values()))
    assert 0.0 < dk.in_layout_share < 1.0


class _FourConvs(nn.Module):
    """A stem, a stride-1 and a stride-2 3 x 3, a 1 x 1 and a head: the
    kinds of convolution a bottleneck ResNet registers."""

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(4, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, name='stem')(x)
        x = nn.Conv(4, (3, 3), padding='SAME', use_bias=False, name='same')(x)
        x = nn.Conv(4, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)],
                    use_bias=False, name='strided')(x)
        x = nn.Conv(8, (1, 1), use_bias=False, name='pointwise')(x)
        return nn.Dense(3, name='head')(x.mean(axis=(1, 2)))


def test_patchless_share_counts_the_convolutions_wider_than_1x1():
    """The capture layer's counter, once at construction from the
    registry, on both engines: of the convolutions with a kernel larger
    than 1 x 1 the share whose A factor is assembled with no patch rows;
    ``None`` where none is registered."""
    reg, dk = _share(_FourConvs(), jnp.ones((2, 16, 16, 3)))
    assert dk.patchless_share == dk.config.patchless_share == 1 / 3
    assert [h.patchless for h in reg.layers.values() if hasattr(
        h, 'patchless')] == [False, True, False, False]
    for text in (dk.describe(), dk.config.describe()):
        assert 'no patch rows: 33.3% of the kernels larger than 1x1' in text
    _, dk = _share(models.TinyConvNet(), jnp.ones((2, 28, 28, 1)))
    assert dk.patchless_share == 0.0  # two 5 x 5 'VALID' convolutions
    _, dk = _share(BiasedWide(), jnp.ones((16, 768)))
    assert dk.patchless_share is None and dk.config.patchless_share is None
    assert 'patch rows' not in dk.describe() + dk.config.describe()


def _reader_context(engine):
    """What a per-layer reader is handed, around ``engine`` alone."""
    run = types.SimpleNamespace(trainer=types.SimpleNamespace(kfac=engine))
    return harness.LayerContext(
        cell={}, run=run, devices=[], first_order_rows=[], rows=[],
        traced_rows=[], trace={'planes': []}, windows={}, throughput=0.0,
    )


def test_benchmark_row_reads_the_patchless_counter():
    """``capture_patchless_share`` of ``BENCHMARK.json``: the engine's
    counter in percent in the two ResNet-50 cells, nothing where no
    convolution is registered or the engine has no such counter."""
    name = 'capture_patchless_share'
    _, dk = _share(_FourConvs(), jnp.ones((2, 16, 16, 3)))
    read = harness.read_layer_metric
    assert read(name, _reader_context(dk)) == pytest.approx(100 / 3)
    _, lm = _share(BiasedWide(), jnp.ones((16, 768)))
    assert read(name, _reader_context(lm)) is None
    assert read(name, _reader_context(types.SimpleNamespace())) is None
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    cells = ['resnet50.kfac-10-100', 'resnet50.kaisa-hybrid-4chip']
    assert next(m for m in bench['per_layer'] if m['name'] == name) == {
        'name': name, 'unit': '%', 'better': 'higher',
        'source': 'program_counter', 'layer': 'capture',
        'moves': 'kfac_overhead', 'workloads': cells,
    }
    for w in bench['workloads']:
        rows = harness.layer_rows(harness.load_cell(w['name']))
        assert (name in {m['name'] for m in rows}) is (w['name'] in cells)


def test_benchmark_row_reads_the_engines_counter():
    """``precondition_in_layout_share`` of ``BENCHMARK.json``: the engine's
    counter in percent in every cell, nothing on a program without it."""
    name = 'precondition_in_layout_share'
    _, dk = _share(
        models.TinyConvNet(), jnp.ones((2, 28, 28, 1)),
        compute_method='inverse',
    )
    assert harness.read_layer_metric(
        name, _reader_context(dk)
    ) == pytest.approx(
        100.0 * dk.in_layout_share
    )
    assert harness.read_layer_metric(
        name, _reader_context(types.SimpleNamespace())) is None
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    # by name, wherever the row stands: later PRs append rows behind it
    assert next(m for m in bench['per_layer'] if m['name'] == name) == {
        'name': name, 'unit': '%', 'better': 'higher',
        'source': 'program_counter', 'layer': 'engine',
        'moves': 'kfac_overhead',
        'workloads': [w['name'] for w in bench['workloads']],
    }
    for w in bench['workloads']:
        rows = harness.layer_rows(harness.load_cell(w['name']))
        assert name in {m['name'] for m in rows}
