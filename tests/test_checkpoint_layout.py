"""What a checkpoint holds, leaf by leaf, and what one may not hold.

The durable slice of the K-FAC state (``checkpoint.durable_state``) is the
checkpoint's format: a leaf that comes or goes there forks every checkpoint
on disk. ``DURABLE_LEAVES`` was printed by the tree BEFORE stat compression
left the state (commit bebe883: ``durable_state(engine.init())`` of
``TinyConvNet`` on the 8-device CPU mesh, unedited since), so a checkpoint
that tree wrote with the option off is one this tree reads. A checkpoint it
wrote with the option on carries error-feedback residuals: refused by name.
"""

import jax
import jax.numpy as jnp
import pytest

import kfac_tpu
from kfac_tpu import checkpoint
from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh
from testing import models

_DENSE = [
    ("['a']['conv1']", (76, 76), 'float32'),
    ("['a']['conv2']", (151, 151), 'float32'),
    ("['a']['fc1']", (17, 17), 'float32'),
    ("['a']['fc2']", (33, 33), 'float32'),
    ("['g']['conv1']", (6, 6), 'float32'),
    ("['g']['conv2']", (16, 16), 'float32'),
    ("['g']['fc1']", (32, 32), 'float32'),
    ("['g']['fc2']", (10, 10), 'float32'),
    ("['step']", (), 'int32'),
]
# the stacked layout does not depend on the strategy (factors shard over
# every device under all three) nor on the method (decompositions are
# rematerialised, never saved)
_STACKED = [
    ("['a']['151x16']", (8, 151, 151), 'float32'),
    ("['a']['17x32']", (8, 17, 17), 'float32'),
    ("['a']['33x10']", (8, 33, 33), 'float32'),
    ("['a']['76x6']", (8, 76, 76), 'float32'),
    ("['g']['151x16']", (8, 16, 16), 'float32'),
    ("['g']['17x32']", (8, 32, 32), 'float32'),
    ("['g']['33x10']", (8, 10, 10), 'float32'),
    ("['g']['76x6']", (8, 6, 6), 'float32'),
    ("['step']", (), 'int32'),
]
DURABLE_LEAVES = {
    (engine, method): _DENSE if engine == 'dense' else _STACKED
    for engine in ('dense', 'comm', 'hybrid', 'mem')
    for method in ('eigen', 'inverse')
}
FRACTIONS = {'dense': None, 'comm': 1.0, 'hybrid': 0.5, 'mem': 1.0 / 8}


@pytest.fixture(scope='module')
def registry():
    return kfac_tpu.register_model(
        models.TinyConvNet(), jnp.zeros((8, 16, 16, 3))
    )


def _engine(registry, name, **cfg_kw):
    cfg = kfac_tpu.KFACPreconditioner(registry=registry, **cfg_kw)
    if FRACTIONS[name] is None:
        return cfg
    return DistributedKFAC(
        config=cfg, mesh=kaisa_mesh(grad_worker_fraction=FRACTIONS[name])
    )


@pytest.mark.parametrize('method', ['eigen', 'inverse'])
@pytest.mark.parametrize('engine', list(FRACTIONS))
def test_durable_leaves(registry, engine, method):
    state = _engine(registry, engine, compute_method=method).init()
    leaves = jax.tree_util.tree_flatten_with_path(
        checkpoint.durable_state(state)
    )[0]
    got = [
        (jax.tree_util.keystr(path), tuple(leaf.shape), str(leaf.dtype))
        for path, leaf in leaves
    ]
    assert got == DURABLE_LEAVES[engine, method]


@pytest.mark.parametrize('template', ['dense', 'hybrid'])
def test_comp_ef_checkpoint_is_refused(
    registry, tmp_path, monkeypatch, template
):
    """A checkpoint with ``kfac/comp_ef`` leaves is refused whether the
    restoring engine shares the writer's layout (the exact path: orbax
    finds a leaf the template lacks) or not (the migration path: the raw
    payload holds it), and the error says what the leaves are."""
    writer = _engine(registry, 'hybrid')
    state = writer.init()
    durable = checkpoint.durable_state

    def with_residuals(s):
        return {**durable(s), 'comp_ef': {'c0': jnp.zeros((7,), jnp.float32)}}

    path = str(tmp_path / 'ckpt')
    with monkeypatch.context() as patched:
        patched.setattr(checkpoint, 'durable_state', with_residuals)
        checkpoint.save(path, state, engine=writer)
    with pytest.raises(ValueError, match='error-feedback residuals') as err:
        checkpoint.restore(path, _engine(registry, template))
    assert 'no longer supported' in str(err.value)
    assert 'comp_ef' in str(err.value)
    # the same state saved as this tree saves it restores under both
    clean = str(tmp_path / 'clean')
    checkpoint.save(clean, state, engine=writer)
    if template == 'dense':
        with pytest.warns(UserWarning, match='migrating'):
            restored, _ = checkpoint.restore(clean, _engine(registry, template))
    else:
        restored, _ = checkpoint.restore(clean, _engine(registry, template))
    assert int(restored.step) == 0
