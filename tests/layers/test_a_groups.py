"""A groups: Dense layers handed one input array hold one A factor.

What ``register_model`` finds (by the identity of the array at the probe,
nothing else), what it leaves apart, and that capture contracts a group's
A once.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kfac_tpu
from kfac_tpu import models
from kfac_tpu.layers import registry as registry_lib


def members(registry):
    return {k: list(v[1:]) for k, v in registry.a_members().items()}


# ------------------------------------------- the three LM kinds' tiny presets


def test_transformer_lm_groups_member_by_member():
    model = models.TransformerLM(
        vocab_size=64, d_model=16, num_heads=2, num_layers=2, max_len=8
    )
    reg = kfac_tpu.register_model(model, jnp.zeros((1, 8), jnp.int32))
    attn = {
        f'{b}/attn/q_proj': [f'{b}/attn/k_proj', f'{b}/attn/v_proj']
        for b in ('block0', 'block1')
    }
    assert members(reg) == attn
    # the output projection and the MLP read arrays of their own
    alone = [n for n in reg.layers if n not in reg.a_groups]
    assert sorted(alone) == sorted(
        [f'{b}/{n}' for b in ('block0', 'block1')
         for n in ('attn/out_proj', 'mlp_up', 'mlp_down')] + ['lm_head']
    )


def hybrid(dtype):
    return models.HybridLM(
        vocab_size=64, d_model=32, num_layers=4, full_attention_interval=4,
        num_heads=4, num_kv_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, num_experts=16, top_k=3, expert_width=16,
        shared_expert_width=16, experts_held=(4, 4), scan_chunk=4,
        attention_chunk=8, expert_block_rows=4, loss_chunk=8, dtype=dtype,
    )


def hybrid_groups(dtype):
    reg = kfac_tpu.register_model(
        hybrid(dtype), jnp.zeros((1, 16), jnp.int32), skip_layers=['lm_head']
    )
    return reg, members(reg)


def test_hybrid_lm_groups_member_by_member_in_bfloat16():
    reg, got = hybrid_groups(jnp.bfloat16)
    want = {}
    for b in range(4):
        mixer, moe = f'block{b}/mixer', f'block{b}/moe'
        if b < 3:  # Gated DeltaNet: four bfloat16 projections, two float32
            want[f'{mixer}/q_proj'] = [
                f'{mixer}/{n}' for n in ('k_proj', 'v_proj', 'z_proj')
            ]
            want[f'{mixer}/b_proj'] = [f'{mixer}/a_proj']
        else:  # gated attention
            want[f'{mixer}/q_proj'] = [
                f'{mixer}/{n}' for n in ('k_proj', 'v_proj', 'gate_proj')
            ]
        # router and shared gate multiply one float32 array; the shared
        # expert's gate and up the bfloat16 one it was made from
        want[f'{moe}/router'] = [f'{moe}/shared_gate']
        want[f'{moe}/shared/gate_proj'] = [f'{moe}/shared/up_proj']
        for e in range(4):
            want[f'{moe}/experts/gate_proj/e{e}'] = [
                f'{moe}/experts/up_proj/e{e}'
            ]
    assert got == want
    assert len(reg.layers) == 94
    assert sum(len(v) for v in want.values()) == 39
    # different arrays: never grouped
    for b in range(4):
        for n in ('mixer/out_proj', 'mixer/o_proj', 'moe/shared/down_proj',
                  'moe/experts/down_proj/e0'):
            assert f'block{b}/{n}' not in reg.a_groups


def test_one_array_at_two_dtypes_is_two_groups():
    """``b_proj``/``a_proj`` are float32 beside bfloat16 siblings on the
    very same ``x``: in float32 all six are one group, in bfloat16 two."""
    _, in_f32 = hybrid_groups(jnp.float32)
    assert in_f32['block0/mixer/q_proj'] == [
        f'block0/mixer/{n}'
        for n in ('k_proj', 'v_proj', 'z_proj', 'b_proj', 'a_proj')
    ]
    _, in_bf16 = hybrid_groups(jnp.bfloat16)
    assert 'block0/mixer/b_proj' not in in_bf16['block0/mixer/q_proj']
    assert in_bf16['block0/mixer/b_proj'] == ['block0/mixer/a_proj']


def conv_moe():
    return models.ConvMoELM(
        vocab_size=64, d_model=32,
        layer_types=('conv', 'full_attention', 'conv', 'conv'),
        num_dense_layers=1, dense_width=48, num_heads=4, num_kv_heads=2,
        head_dim=8, num_experts=16, top_k=3, expert_width=16,
        experts_held=(4, 4), attention_chunk=8, expert_block_rows=4,
        loss_chunk=8, dtype=jnp.bfloat16,
    )


def test_conv_moe_lm_groups_member_by_member():
    reg = kfac_tpu.register_model(
        conv_moe(), jnp.zeros((1, 16), jnp.int32), skip_layers=['lm_head']
    )
    want = {'block0/mlp/gate_proj': ['block0/mlp/up_proj']}
    for b, kind in enumerate(('conv', 'full_attention', 'conv', 'conv')):
        mixer = f'block{b}/mixer'
        if kind == 'conv':
            want[f'{mixer}/b_proj'] = [f'{mixer}/c_proj', f'{mixer}/x_proj']
        else:
            want[f'{mixer}/q_proj'] = [f'{mixer}/k_proj', f'{mixer}/v_proj']
        if b >= 1:
            for e in range(4):
                want[f'block{b}/moe/experts/gate_proj/e{e}'] = [
                    f'block{b}/moe/experts/up_proj/e{e}'
                ]
    assert members(reg) == want
    # the router has no float32 sibling here, and leads nothing
    assert 'block1/moe/router' not in reg.a_groups


# -------------------------------------------------------------- the non-groups


class Probe(nn.Module):
    """Two Dense on one ``x``, two convolutions on one image, two LoRA
    units on one ``x``, one Dense called twice, one on another array."""

    @nn.compact
    def __call__(self, x, image):
        a = nn.Dense(5, name='a')(x)
        b = nn.Dense(5, name='b')(x)
        c = nn.Dense(5, use_bias=False, name='no_bias')(x)
        other = nn.Dense(5, name='other_array')(x + 0.0)
        wide = nn.Dense(5, dtype=jnp.bfloat16, name='other_dtype')(x)
        twice = nn.Dense(4, name='twice')
        t = twice(x) + twice(x * 2.0)
        la = models.LoRADense(5, rank=2, name='lora_a')(x)
        lb = models.LoRADense(5, rank=2, name='lora_b')(x)
        ca = nn.Conv(3, (3, 3), name='conv_a')(image)
        cb = nn.Conv(3, (3, 3), name='conv_b')(image)
        last = nn.Dense(5, name='last')(x)
        return (
            a + b + c + other + wide.astype(a.dtype) + la + lb + last
        ).sum() + t.sum() + (ca + cb).sum()


PROBE_ARGS = (jnp.ones((6, 4)), jnp.ones((2, 8, 8, 2)))


def test_what_does_not_group():
    reg = kfac_tpu.register_model(Probe(), *PROBE_ARGS)
    assert members(reg) == {'a': ['b', 'last']}
    for name in ('no_bias', 'other_array', 'other_dtype', 'twice', 'lora_a',
                 'lora_b', 'conv_a', 'conv_b'):
        assert name in reg.layers and name not in reg.a_groups, name
    assert reg.a_leader('b') == 'a' and reg.a_leader('twice') == 'twice'
    text = reg.describe()
    assert text.count('a <- b, last') == 1 and '2 of 11 layers' in text


@pytest.mark.parametrize('how', ['skip_layers', 'mask'])
def test_the_next_member_leads_when_the_leader_goes(how):
    if how == 'skip_layers':
        reg = kfac_tpu.register_model(Probe(), *PROBE_ARGS, skip_layers=['a'])
    else:
        reg = kfac_tpu.register_model(Probe(), *PROBE_ARGS, mask={'a': False})
    assert 'a' not in reg.layers
    assert members(reg) == {'b': ['last']}


def test_a_group_left_with_one_member_is_none():
    reg = kfac_tpu.register_model(
        Probe(), *PROBE_ARGS, mask={'a': False, 'last': False}
    )
    assert reg.a_groups == {}
    assert 'none' in reg.describe()


def test_masking_a_stack_promotes_the_other():
    reg, got = hybrid_groups(jnp.bfloat16)
    masked = registry_lib.masked_registry(
        reg, {'block0': {'moe': {'experts': {'gate_proj': False}}}}
    )
    assert 'block0/moe/experts/gate_proj/e0' not in masked.layers
    assert 'block0/moe/experts/up_proj/e0' not in masked.a_groups
    assert masked.a_groups['block1/moe/experts/up_proj/e0'] == (
        'block1/moe/experts/gate_proj/e0'
    )


def test_merged_registries_keep_their_groups():
    a = kfac_tpu.register_model(Probe(), *PROBE_ARGS)
    model = models.TransformerLM(
        vocab_size=64, d_model=16, num_heads=2, num_layers=1, max_len=8
    )
    b = kfac_tpu.register_model(model, jnp.zeros((1, 8), jnp.int32))
    both = registry_lib.merge_registries(
        dataclasses.replace(
            a, layers={n: h for n, h in a.layers.items() if n != 'lm_head'}
        ),
        b,
    )
    assert both.a_groups == {**a.a_groups, **b.a_groups}


# --------------------------------------------------------------------- capture


def probe_loss(params, batch):
    return Probe().apply({'params': params}, *batch)


def test_capture_contracts_a_group_once():
    reg = kfac_tpu.register_model(Probe(), *PROBE_ARGS)
    plain = dataclasses.replace(reg, a_groups={})
    batch = (
        jax.random.normal(jax.random.PRNGKey(1), (6, 4)),
        jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 2)),
    )
    params = Probe().init(jax.random.PRNGKey(0), *batch)['params']

    def run(registry):
        cap = kfac_tpu.CurvatureCapture(registry)
        return jax.jit(cap.value_stats_and_grad(probe_loss))(params, batch)

    (loss, _), grads, stats = run(reg)
    (loss0, _), grads0, stats0 = run(plain)
    assert float(loss) == float(loss0)
    assert sorted(stats.g) == sorted(stats0.g) == sorted(reg.layers)
    assert sorted(stats0.a) == sorted(reg.layers)
    assert sorted(stats.a) == sorted(set(reg.layers) - {'b', 'last'})
    for name in stats0.a:  # the leader's is each member's, to the last bit
        np.testing.assert_array_equal(
            stats.a[reg.a_leader(name)], stats0.a[name], err_msg=name
        )
    for name in stats0.g:
        np.testing.assert_array_equal(stats.g[name], stats0.g[name])
    # the program holds one A contraction a group: two fewer (4+1)-wide
    text = jax.jit(
        kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(probe_loss)
    ).lower(params, batch).as_text()
    text0 = jax.jit(
        kfac_tpu.CurvatureCapture(plain).value_stats_and_grad(probe_loss)
    ).lower(params, batch).as_text()
    assert text0.count('dot_general') - text.count('dot_general') == 2
    # a pytree like any other
    leaves, tree = jax.tree_util.tree_flatten(stats)
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert sorted(again.a) == sorted(stats.a) and sorted(again.g) == sorted(stats.g)


def test_capture_refuses_a_follower_handed_another_array():
    reg = kfac_tpu.register_model(Probe(), *PROBE_ARGS)

    class Other(nn.Module):
        @nn.compact
        def __call__(self, x):
            return (
                nn.Dense(5, name='a')(x) + nn.Dense(5, name='b')(x * 2.0)
            ).sum()

    params = Other().init(jax.random.PRNGKey(0), PROBE_ARGS[0])['params']
    sub = dataclasses.replace(
        reg,
        layers={n: reg.layers[n] for n in ('a', 'b')},
        param_paths={n: reg.param_paths[n] for n in ('a', 'b')},
        taps={}, a_groups={'a': 'a', 'b': 'a'},
    )
    cap = kfac_tpu.CurvatureCapture(sub)
    with pytest.raises(ValueError, match='shares the A factor'):
        cap.value_stats_and_grad(
            lambda p, x: Other().apply({'params': p}, x)
        )(params, PROBE_ARGS[0])
