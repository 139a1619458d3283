"""A groups in the engines: one A factor, one slot, one solve a group.

Every engine, with the groups and with the very same registry with its
group map emptied, gives the same factors, inverses and preconditioned
gradients: sharing changes how many times a matrix is kept, never which
matrix.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import checkpoint, models
from kfac_tpu.autotune import model as autotune_model
from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh


class Net(nn.Module):
    """Three projections of one ``x`` (two output widths, so two pair
    buckets), an output projection on an array of its own, routed experts
    (gate and up on one ``(x, plan)``), router and shared gate on one
    float32 array beside the bfloat16 shared expert."""

    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def dense(n, name, bias=True):
            return nn.Dense(n, use_bias=bias, dtype=self.dtype, name=name)

        q, k, v = dense(12, 'q')(x), dense(12, 'k')(x), dense(6, 'v')(x)
        h = dense(16, 'o')(jnp.concatenate([q * k, v], axis=-1))
        y = models.SparseMoE(
            num_experts=8, top_k=2, width=8, shared_width=8,
            experts_held=(2, 4), block_rows=4, dtype=self.dtype, name='moe',
        )(h)
        return dense(3, 'head', bias=False)(y.astype(self.dtype))


def loss_fn(params, batch):
    x, y = batch
    out = Net().apply({'params': params}, x).astype(jnp.float32)
    return jnp.mean((out - y) ** 2)


@pytest.fixture(scope='module')
def setup():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 10))
    y = jax.random.normal(jax.random.PRNGKey(2), (64, 3))
    params = Net().init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(Net(), x)
    plain = dataclasses.replace(reg, a_groups={})
    out = {}
    for tag, registry in (('grouped', reg), ('plain', plain)):
        cap = kfac_tpu.CurvatureCapture(registry)
        out[tag] = jax.jit(cap.value_stats_and_grad(loss_fn))(params, (x, y))
    return reg, plain, out


def test_the_groups_of_the_net(setup):
    reg, _, _ = setup
    want = {
        'q': ('q', 'k', 'v'),
        'moe/router': ('moe/router', 'moe/shared_gate'),
        'moe/shared/gate_proj': ('moe/shared/gate_proj', 'moe/shared/up_proj'),
    }
    for e in range(4):
        want[f'moe/experts/gate_proj/e{e}'] = (
            f'moe/experts/gate_proj/e{e}', f'moe/experts/up_proj/e{e}'
        )
    assert reg.a_members() == want


def test_a_capture_hands_over_the_leaders_statistics_exactly(setup):
    reg, _, out = setup
    (_, _), _, stats = out['grouped']
    (_, _), _, stats0 = out['plain']
    followers = {n for n, l in reg.a_groups.items() if n != l}
    assert sorted(stats.a) == sorted(set(reg.layers) - followers)
    for name in reg.layers:
        np.testing.assert_array_equal(
            stats.a[reg.a_leader(name)], stats0.a[name], err_msg=name
        )
        np.testing.assert_array_equal(stats.g[name], stats0.g[name])
    assert sorted(stats.w) == sorted(stats0.w)
    for name in stats0.w:
        np.testing.assert_array_equal(stats.w[name], stats0.w[name])


ENGINES = {
    'dense': dict(frac=None),
    'dense-eigen': dict(frac=None, compute_method='eigen'),
    'dense-prediv': dict(
        frac=None, compute_method='eigen', prediv_eigenvalues=True
    ),
    'comm-opt': dict(frac=1.0),
    'hybrid-opt': dict(frac=0.5),
    'mem-opt': dict(frac=1 / 8),
    'comm-opt-apart': dict(frac=1.0, colocate_factors=False),
    'hybrid-opt-eigen': dict(frac=0.5, compute_method='eigen'),
    'mem-opt-health': dict(frac=1 / 8, health=True),
    'comm-opt-bucketed': dict(frac=1.0, allreduce_method='allreduce_bucketed'),
}


def make_engine(registry, frac=None, health=False, **kw):
    kw.setdefault('compute_method', 'inverse')
    cfg = kfac_tpu.KFACPreconditioner(
        registry=registry, damping=0.01, lr=0.1, kl_clip=0.001,
        factor_update_steps=2, inv_update_steps=2,
        health=kfac_tpu.HealthConfig() if health else None, **kw,
    )
    if frac is None:
        return cfg
    return DistributedKFAC(
        config=cfg, mesh=kaisa_mesh(grad_worker_fraction=frac)
    )


def two_steps(engine, grads, stats):
    """A capture step with a refresh, then a plain step."""
    state = engine.init()
    state, first = jax.jit(engine.step)(state, grads, stats)
    state, second = jax.jit(lambda s, g: engine.step(s, g, None))(
        state, grads
    )
    return state, first, second


def close(a, b, rtol, atol, what):
    for (path, x), y in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_leaves(b),
    ):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol, err_msg=f'{what} {path}',
        )


@pytest.mark.parametrize('engine', sorted(ENGINES))
def test_grouped_engine_equals_the_ungrouped_one(setup, engine):
    reg, plain, out = setup
    (_, _), grads, stats = out['grouped']
    (_, _), grads0, stats0 = out['plain']
    eng, eng0 = (make_engine(r, **ENGINES[engine]) for r in (reg, plain))
    assert eng.a_groups == reg.a_groups and eng0.a_groups == {}
    state, first, second = two_steps(eng, grads, stats)
    state0, first0, second0 = two_steps(eng0, grads0, stats0)

    # the factors after the EMA: float32 rounding
    factors, factors0 = eng.extract_factors(state), eng0.extract_factors(state0)
    assert sorted(factors) == sorted(factors0) == sorted(reg.layers)
    close(factors, factors0, 1e-6, 1e-7, 'factor')
    # the decompositions, layer by layer, and what they precondition: the
    # solver's tolerance
    eigen = ENGINES[engine].get('compute_method') == 'eigen'
    if not eigen:
        if isinstance(eng, DistributedKFAC):
            def inverses(e, s):
                out = {}
                for name, h in e.registry.layers.items():
                    (ak, ai), (gk, gi) = e._a_slot[name], e._g_slot[name]
                    da, dg = h.a_factor_shape[0], h.g_factor_shape[0]
                    out[name] = (
                        s.a_inv[ak][ai, :da, :da], s.g_inv[gk][gi, :dg, :dg]
                    )
                return out
        else:
            def inverses(e, s):
                return {
                    n: (s.a_inv[e.a_leader(n)], s.g_inv[n])
                    for n in e.registry.layers
                }
        close(inverses(eng, state), inverses(eng0, state0), 1e-4, 1e-5,
              'inverse')
    close(first, first0, 2e-4, 1e-6, 'capture step')
    close(second, second0, 2e-4, 1e-6, 'plain step')

    # one A slot a group: the state is smaller by exactly the followers'
    followers = [n for n, l in reg.a_groups.items() if n != l]
    usage, usage0 = eng.memory_usage(state), eng0.memory_usage(state0)
    assert usage['g_factors'] == usage0['g_factors']
    if isinstance(eng, DistributedKFAC):
        assert sum(len(sb.layers) for sb in eng.a_store) == (
            len(reg.layers) - len(followers)
        )
        assert sum(len(sb.layers) for sb in eng.g_store) == len(reg.layers)
    else:
        saved = sum(
            4 * reg.layers[n].a_factor_shape[0] ** 2 for n in followers
        )
        assert usage0['a_factors'] - usage['a_factors'] == saved
        if not eigen:
            assert usage0['a_inverses'] - usage['a_inverses'] == saved


def test_kfac_state_falls_by_exactly_the_followers_slots_on_the_lm_presets():
    """``memory_usage`` on one device (what ``kfac_state_gb`` adds up): a
    follower's A factor and A inverse are gone, nothing else moves."""
    from tests.layers.test_a_groups import conv_moe, hybrid

    mesh = kaisa_mesh(devices=jax.devices()[:1])
    presets = {
        'hybrid': hybrid(jnp.bfloat16),
        'conv_moe': conv_moe(),
        'transformer': models.TransformerLM(
            vocab_size=64, d_model=16, num_heads=2, num_layers=2, max_len=8
        ),
    }
    for kind, model in presets.items():
        tokens = jnp.zeros((1, 8 if kind == 'transformer' else 16), jnp.int32)
        reg = kfac_tpu.register_model(model, tokens, skip_layers=['lm_head'])
        plain = dataclasses.replace(reg, a_groups={})
        followers = [n for n, l in reg.a_groups.items() if n != l]
        assert followers, kind
        used = {}
        for tag, r in (('grouped', reg), ('plain', plain)):
            eng = DistributedKFAC(
                config=kfac_tpu.KFACPreconditioner(
                    registry=r, compute_method='inverse',
                    bucket_granularity=1,
                ),
                mesh=mesh,
            )
            used[tag] = eng.memory_usage(eng.init())
        slots = sum(
            4 * reg.layers[n].a_factor_shape[0] ** 2 for n in followers
        )
        for part in ('a_factors', 'a_inverses'):
            assert used['plain'][part] - used['grouped'][part] == slots, kind
        for part in ('g_factors', 'g_inverses'):
            assert used['plain'][part] == used['grouped'][part], kind


def test_pre_divided_eigenvalues_and_async_refresh_keep_every_a(setup):
    reg, _, out = setup
    (_, _), grads, stats = out['grouped']
    prediv = make_engine(
        reg, frac=1.0, compute_method='eigen', prediv_eigenvalues=True
    )
    assert prediv.a_groups == {} and prediv.config.a_groups == reg.a_groups
    assert 'none stored' in prediv.describe()
    # the capture's one contraction a group serves every member's own slot
    state, first, _ = two_steps(prediv, grads, stats)
    ref, ref_first, _ = two_steps(
        make_engine(reg, frac=1.0, compute_method='eigen'), grads, stats
    )
    close(first, ref_first, 2e-4, 1e-6, 'prediv')
    sliced = make_engine(
        reg, async_inverse=kfac_tpu.AsyncInverseConfig(mode='sliced')
    )
    assert sliced.a_groups == {}
    assert sorted(sliced.init().a) == sorted(reg.layers)


def test_describe_prints_each_group_once_and_layouts_agree(setup):
    reg, _, _ = setup
    eng = make_engine(reg, frac=0.5)
    text = eng.describe()
    assert text.count('q <- k, v') == 1
    assert text.count('moe/router <- moe/shared_gate') == 1
    assert make_engine(reg).describe().count('q <- k, v') == 1
    # a follower reads its leader's slot, on its leader's device
    assert eng._a_slot['k'] == eng._a_slot['q'] == eng._a_slot['v']
    assert eng.slot_device('a', 'v') == eng.slot_device('a', 'q')
    # the autotuner's mesh-less layout prices the same stores
    layout = autotune_model.StaticLayout(eng.config, 8, 0.5)
    assert layout.a_store == eng.a_store and layout.g_store == eng.g_store
    assert layout.comms_report() == {
        k: v for k, v in eng.comms_report().items()
    }
    # the assignment places a group as a unit: one column, the leader's A
    assign = eng.assignment
    for leader, group in reg.a_members().items():
        columns = {assign.grad_worker_group(n) for n in group}
        assert len(columns) == 1, leader
        for n in group:
            assert assign.inv_worker(n, 'A') == assign.inv_worker(leader, 'A')


# ------------------------------------------------------------------ checkpoints


@pytest.mark.parametrize('frac', [None, 0.5], ids=['dense', 'hybrid-opt'])
def test_factors_round_trip_by_layer_name(setup, frac):
    reg, plain, out = setup
    (_, _), grads, stats = out['grouped']
    eng = make_engine(reg, frac=frac)
    state, _, _ = two_steps(eng, grads, stats)
    factors = eng.extract_factors(state)
    for name, leader in reg.a_groups.items():
        np.testing.assert_array_equal(factors[name]['a'], factors[leader]['a'])
    again = eng.rematerialize(eng.insert_factors(eng.init(), factors))
    close(eng.extract_factors(again), factors, 0, 0, 'round trip')
    # into an engine that keeps every layer's own A, and back
    eng0 = make_engine(plain, frac=frac)
    there = eng0.insert_factors(eng0.init(), factors)
    close(eng0.extract_factors(there), factors, 0, 0, 'to ungrouped')
    back = eng.insert_factors(eng.init(), eng0.extract_factors(there))
    close(eng.extract_factors(back), factors, 0, 0, 'and back')


@pytest.mark.parametrize('frac', [None, 0.5], ids=['dense', 'hybrid-opt'])
def test_checkpoint_round_trip_and_a_checkpoint_from_before_the_groups(
    setup, frac, tmp_path
):
    reg, plain, out = setup
    (_, _), grads, stats = out['grouped']
    (_, _), grads0, stats0 = out['plain']
    eng, eng0 = make_engine(reg, frac=frac), make_engine(plain, frac=frac)
    state, _, _ = two_steps(eng, grads, stats)
    state0, _, _ = two_steps(eng0, grads0, stats0)

    new = str(tmp_path / 'new')
    checkpoint.save(new, state, engine=eng)
    restored, _ = checkpoint.restore(new, eng)
    close(eng.extract_factors(restored), eng.extract_factors(state), 0, 0,
          'same layout')
    assert int(restored.step) == int(state.step)

    # what the parent commit wrote: every layer's own A, no group map in
    # the manifest. It loads; the followers' A entries are dropped for
    # their leader's (equal matrices: the same contraction under the same
    # EMA)
    old = str(tmp_path / 'old')
    checkpoint.save(old, state0, engine=eng0)
    assert 'a_groups' not in checkpoint.layout_manifest(eng0)
    with pytest.warns(UserWarning, match='different state layout'):
        migrated, _ = checkpoint.restore(old, eng)
    close(eng.extract_factors(migrated), eng0.extract_factors(state0),
          1e-6, 1e-7, 'from before the groups')
    # and a grouped checkpoint into an engine that keeps every A
    with pytest.warns(UserWarning, match='different state layout'):
        widened, _ = checkpoint.restore(new, eng0)
    close(eng0.extract_factors(widened), eng.extract_factors(state), 0, 0,
          'to ungrouped')

    portable = str(tmp_path / 'factors')
    checkpoint.save_factors(portable, eng0, state0)
    loaded = checkpoint.load_factors(portable, eng)
    close(eng.extract_factors(loaded), eng0.extract_factors(state0),
          1e-6, 1e-7, 'factor checkpoint')
