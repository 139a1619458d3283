"""Every Pallas entry point lowers for TPU from the CPU.

The kernel tests elsewhere run with ``interpret=True``, which never
reaches Mosaic's lowering rules — that is how ``fused_klclip_dot``
shipped with a scalar store into VMEM that no TPU could lower. Here each entry point is traced on the CPU and lowered with
``lowering_platforms=('tpu',)``: the Pallas->Mosaic lowering runs for
real and the text must carry a ``tpu_custom_call``. This is the check to
run before spending chip time; it does not replace compiling on the chip
(``chip_smoke.py``).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from kfac_tpu.models import moe
from kfac_tpu.ops import pallas_attention, pallas_ns


@pytest.fixture(autouse=True)
def _as_on_tpu(monkeypatch):
    # the dispatchers pick interpret mode and open their gates from the
    # backend; answer as the chip would so they lower the Mosaic kernel
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')


def _kernels_in(fn, *args) -> int:
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=('tpu',)
    ).as_text()
    return text.count('tpu_custom_call')


def _f32(*shape):
    return jnp.ones(shape, jnp.float32)


# (rows, cols) of a preconditioned gradient
KLCLIP_SHAPES = [(512, 512), (1000, 2049)]
BATCH = 3


def _stack(x):
    return jnp.stack([x] * BATCH)


@pytest.mark.parametrize('r,c', KLCLIP_SHAPES)
def test_fused_klclip_lowers(r, c):
    p = _f32(r, c)
    scale = jnp.float32(0.5)
    assert _kernels_in(pallas_ns.fused_klclip_dot, p, p) == 1
    assert _kernels_in(
        jax.vmap(pallas_ns.fused_klclip_dot), _stack(p), _stack(p)
    ) == 1
    assert _kernels_in(pallas_ns.fused_klclip_scale, p, scale) == 1
    # the scale is cross-layer, so under vmap it is either shared...
    assert _kernels_in(
        jax.vmap(lambda p: pallas_ns.fused_klclip_scale(p, scale)),
        _stack(p),
    ) == 1
    # ...or one scalar per slot (a batched SMEM operand)
    assert _kernels_in(
        jax.vmap(pallas_ns.fused_klclip_scale), _stack(p), jnp.ones(BATCH)
    ) == 1


@pytest.mark.parametrize('s_q,s_k', [(256, 256), (128, 384)])
def test_flash_partials_lower(s_q, s_k):
    # blocks must divide the sequence (the dispatcher enforces it), so
    # the second shape is an uneven ring chunk pair, not a ragged one
    q = jnp.ones((2, s_q, 2, 128), jnp.bfloat16)
    kv = jnp.ones((2, s_k, 2, 128), jnp.bfloat16)
    assert _kernels_in(
        pallas_attention.flash_attention_partials, q, kv, kv
    ) == 1
    assert _kernels_in(
        jax.vmap(pallas_attention.flash_attention_partials),
        _stack(q), _stack(kv), _stack(kv),
    ) == 1


# a bucket below every kernel's threshold, the widths the Mosaic pair
# once took (whole tiles from 512: 896, 2,304, 3,200) and a ragged one
@pytest.mark.parametrize('d', [256, 896, 2049, 2304, 3200])
def test_newton_schulz_is_xla_at_every_width(monkeypatch, d):
    """End of the chain the README quick-start hits on a TPU: with every
    gate open (backend, thresholds, one device) the Newton-Schulz inverse
    lowers with no Mosaic kernel in its loop, dense or stacked: its two
    products are XLA's (``factors.newton_schulz_step``)."""
    from kfac_tpu.ops import factors

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, 'devices', lambda *a: one)
    assert pallas_ns.use_fused_klclip_for((512, 512))  # gates are open
    f = jnp.eye(d, dtype=jnp.float32)

    def solve(f):
        return factors.newton_schulz_inverse(f, 0.003)

    assert _kernels_in(solve, f) == 0
    assert _kernels_in(jax.vmap(solve), _stack(f)) == 0


class _DenseConvExperts(nn.Module):
    """A Dense layer with bias, a convolution and an 8-expert stack, each
    gradient over 512^2 elements: the size from which the kl-clip pair
    dispatched until PR 37."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(512, (3, 3), name='conv')(x))
        x = nn.relu(nn.Dense(512, name='fc')(x.mean((1, 2))))
        return moe.SparseMoE(8, 2, 512, block_rows=8, name='moe')(x)


@pytest.mark.parametrize('kl_clip', [None, 0.001], ids=['noclip', 'klclip'])
@pytest.mark.parametrize('engine', ['dense', 'comm_opt'])
def test_precondition_is_xla_with_every_gate_open(
    monkeypatch, engine, kl_clip
):
    """With every gate open (TPU lowering, one device) the step path's
    preconditioning lowers with no Mosaic kernel, kl-clip or not:
    ``finish_precondition``'s contraction and scale are XLA's
    (``factors.kl_clip_terms``, ``kl_clip_apply``), through the dense
    engine and through ``DistributedKFAC`` with every inverse resident."""
    import kfac_tpu
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, 'devices', lambda *a: one)
    assert pallas_ns.use_fused_klclip_for((512, 512))  # gates are open
    model = _DenseConvExperts()
    x = jnp.ones((16, 8, 8, 64))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(model, x)
    assert {'conv', 'fc'} < set(reg.layers)
    assert sum('/experts/down_proj/e' in n for n in reg.layers) == 8
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method='inverse', kl_clip=kl_clip,
    )
    if engine == 'comm_opt':
        eng = DistributedKFAC(config=cfg, mesh=kaisa_mesh(devices=one))
        assert eng._in_layout
    else:
        eng = cfg
    state = jax.eval_shape(eng.init)
    assert _kernels_in(eng.precondition, state, params) == 0
