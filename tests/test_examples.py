"""Example-trainer smoke tests (CLI surface, tiny synthetic runs)."""

import sys

import pytest


@pytest.mark.slow
def test_cifar_example_smoke(monkeypatch):
    from examples import train_cifar_resnet

    acc = train_cifar_resnet.main(
        [
            '--model', 'resnet20', '--epochs', '1', '--batch-size', '32',
            '--limit-steps', '3', '--kfac-factor-update-steps', '1',
            '--kfac-inv-update-steps', '1', '--kfac-strategy', 'hybrid-opt',
        ]
    )
    assert 0.0 <= acc <= 1.0


def test_lm_example_smoke():
    from examples import train_language_model

    ppl = train_language_model.main(
        [
            '--epochs', '1', '--batch-size', '8', '--seq-len', '32',
            '--d-model', '32', '--num-heads', '4', '--num-layers', '2',
            '--vocab-size', '128', '--limit-steps', '3',
            '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
        ]
    )
    assert ppl > 0


def test_lm_example_trains_on_real_tokenized_corpus(tmp_path):
    """End-to-end real-text path: raw text -> tools/tokenize_corpus ->
    memmapped corpus.npy + vocab.json -> LM trainer via --data-dir (the
    reference's PTB flow, examples/torch_language_model.py:80-85)."""
    import numpy as np

    from examples import data, train_language_model
    from tools import tokenize_corpus

    text = tmp_path / 'corpus.txt'
    sentences = [
        'the quick brown fox jumps over the lazy dog',
        'a stitch in time saves nine',
        'all that glitters is not gold',
        'the early bird catches the worm',
    ]
    text.write_text('\n'.join(sentences * 200) + '\n')
    out = tmp_path / 'tok'
    tokenize_corpus.main(
        [str(text), '--out-dir', str(out), '--vocab-size', '64']
    )

    # the loader memory-maps and reports the tokenizer's vocab size
    toks, vocab = data.lm_corpus(str(out))
    assert isinstance(toks, np.memmap)
    assert vocab == len(
        __import__('json').load(open(out / 'vocab.json'))['itos']
    )
    assert toks.max() < vocab

    ppl = train_language_model.main(
        [
            '--epochs', '1', '--batch-size', '8', '--seq-len', '16',
            '--d-model', '32', '--num-heads', '4', '--num-layers', '2',
            '--limit-steps', '3', '--data-dir', str(out),
            '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
        ]
    )
    import math

    assert math.isfinite(ppl) and ppl < math.exp(20.0)


def test_tokenize_corpus_rejects_empty_input(tmp_path):
    from tools import tokenize_corpus

    empty = tmp_path / 'empty.txt'
    empty.write_text('\n  \n')
    with pytest.raises(SystemExit, match='no tokens'):
        tokenize_corpus.main(
            [str(empty), '--out-dir', str(tmp_path / 'out')]
        )


def test_lm_batches_resume_consistent():
    """The window sampler is a pure function of (seed + epoch): a resumed
    run replays the uninterrupted run's batches exactly."""
    import numpy as np

    from examples import data

    toks = np.arange(1000, dtype=np.int32) % 97
    a = list(data.lm_batches(toks, 4, 16, seed=7))
    b = list(data.lm_batches(toks, 4, 16, seed=7))
    assert len(a) == len(b) > 0
    for (xa, ya), (xb, yb) in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_lm_example_with_tp_and_sp():
    from examples import train_language_model

    ppl = train_language_model.main(
        [
            '--epochs', '1', '--batch-size', '4', '--seq-len', '32',
            '--d-model', '32', '--num-heads', '4', '--num-layers', '2',
            '--vocab-size', '128', '--limit-steps', '2',
            '--model-shards', '2', '--seq-shards', '2',
            '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
        ]
    )
    assert ppl > 0


@pytest.mark.slow
def test_cifar_example_no_kfac():
    from examples import train_cifar_resnet

    acc = train_cifar_resnet.main(
        [
            '--no-kfac', '--epochs', '1', '--batch-size', '32',
            '--limit-steps', '2',
        ]
    )
    assert 0.0 <= acc <= 1.0


def test_cifar_real_npz_with_augmentation(tmp_path):
    """Real-dataset path: a cifar10.npz on disk trains with normalization
    and crop/flip augmentation (reference examples train real
    CIFAR, examples/vision/datasets.py:1-154)."""
    import numpy as np

    from examples import data as data_lib
    from examples import train_cifar_resnet

    rng = np.random.default_rng(0)
    x, y = data_lib.synthetic_classification(256, (32, 32, 3), 10, seed=3)
    np.savez(
        tmp_path / 'cifar10.npz',
        x_train=x, y_train=y,
        x_test=x[:64], y_test=y[:64],
    )
    acc = train_cifar_resnet.main(
        [
            '--model', 'resnet20', '--epochs', '1', '--batch-size', '32',
            '--limit-steps', '3', '--data-dir', str(tmp_path),
            '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
        ]
    )
    assert 0.0 <= acc <= 1.0


@pytest.mark.slow
def test_cifar_resume_matches_uninterrupted(tmp_path):
    """Interrupted-then-resumed training must match the uninterrupted run:
    same batches (epoch-seeded), factors restored bit-exact, decomps
    rematerialized every step (cadence 1) — so final params agree
    (reference resume: torch_cifar10_resnet.py:313-354)."""
    import numpy as np

    from examples import train_cifar_resnet
    from kfac_tpu import checkpoint as ckpt_lib

    base = [
        '--model', 'resnet20', '--batch-size', '32', '--limit-steps', '2',
        '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
    ]

    # uninterrupted 2-epoch run
    d_full = str(tmp_path / 'full')
    train_cifar_resnet.main(
        base + ['--epochs', '2', '--checkpoint-dir', d_full]
    )

    # same config "killed" right after the epoch-0 checkpoint, then resumed
    # with identical flags (so the lr schedule is identical)
    from examples import common

    d_r = str(tmp_path / 'resumable')
    orig_save = common.save_checkpoint
    die = {'armed': True}

    def save_and_die(ckpt_dir, state, epoch=0, **kw):
        orig_save(ckpt_dir, state, epoch, **kw)
        if die['armed'] and epoch == 0:
            raise KeyboardInterrupt

    common.save_checkpoint = save_and_die
    try:
        import pytest as _pytest

        with _pytest.raises(KeyboardInterrupt):
            train_cifar_resnet.main(
                base + ['--epochs', '2', '--checkpoint-dir', d_r]
            )
        die['armed'] = False
        train_cifar_resnet.main(
            base + ['--epochs', '2', '--checkpoint-dir', d_r, '--resume']
        )
    finally:
        common.save_checkpoint = orig_save

    import orbax.checkpoint as ocp

    ckptr = ocp.PyTreeCheckpointer()
    full = ckptr.restore(d_full + '/e00001/kfac')
    res = ckptr.restore(d_r + '/e00001/kfac')

    # factors agree between the resumed and uninterrupted runs (to float
    # tolerance: separate processes recompile, and threaded CPU matmuls are
    # not bit-reproducible across processes; bit-exactness of the
    # save/restore round-trip itself is asserted in
    # test_restore_checkpoint_roundtrip_bit_exact)
    for key in full['kfac']['a']:
        np.testing.assert_allclose(
            np.asarray(full['kfac']['a'][key]),
            np.asarray(res['kfac']['a'][key]),
            rtol=1e-3, atol=1e-5,
        )
    np.testing.assert_array_equal(
        np.asarray(full['kfac']['step']), np.asarray(res['kfac']['step'])
    )
    # params agree to float tolerance
    flat_f = jax_flat(full['params'])
    flat_r = jax_flat(res['params'])
    for a, b in zip(flat_f, flat_r):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def jax_flat(tree):
    import jax
    import numpy as np

    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


@pytest.mark.slow
def test_restore_checkpoint_roundtrip_bit_exact(tmp_path):
    """common.save_checkpoint -> common.restore_checkpoint restores factors
    and params bit-exact (the durable state; decomps rematerialize)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kfac_tpu
    from examples import common
    from kfac_tpu import training

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4, name='d1')(nn.relu(nn.Dense(16, name='d0')(x)))

    m = M()
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 8))
    y = jax.nn.one_hot(jnp.arange(32) % 4, 4)
    params = m.init(jax.random.PRNGKey(1), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, damping=0.01, factor_update_steps=1, inv_update_steps=1
    )

    def loss_fn(params, model_state, batch):
        xb, yb = batch
        logits = m.apply({'params': params}, xb)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * yb, -1)), model_state

    trainer = training.Trainer(loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac)
    state = trainer.init(params)
    for _ in range(3):
        state, _ = trainer.step(state, (x, y))

    common.save_checkpoint(str(tmp_path), state, epoch=0)
    restored = common.restore_checkpoint(str(tmp_path), trainer.init(params), kfac)
    assert restored is not None
    rstate, next_epoch = restored
    assert next_epoch == 1
    for name in state.kfac_state.a:
        np.testing.assert_array_equal(
            np.asarray(state.kfac_state.a[name]), np.asarray(rstate.kfac_state.a[name])
        )
        np.testing.assert_array_equal(
            np.asarray(state.kfac_state.g[name]), np.asarray(rstate.kfac_state.g[name])
        )
    for a, b in zip(jax_flat(state.params), jax_flat(rstate.params)):
        np.testing.assert_array_equal(a, b)
    assert int(rstate.kfac_state.step) == int(state.kfac_state.step)


@pytest.mark.slow
def test_imagenet_memmap_layout_and_normalization(tmp_path):
    """The on-disk memmap ImageNet layout trains through the native loader
    with per-batch normalization (x stays a read-only memmap)."""
    import numpy as np

    from examples import data as data_lib
    from examples import train_imagenet_resnet

    rng = np.random.default_rng(0)
    for split, n in (('train', 64), ('test', 16)):
        x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 1000, n).astype(np.int32)
        np.save(tmp_path / f'imagenet_x_{split}.npy', x)
        np.save(tmp_path / f'imagenet_y_{split}.npy', y)
    (xt, yt), _ = data_lib.imagenet_like(str(tmp_path), image_size=32)
    assert isinstance(xt, np.memmap)
    acc = train_imagenet_resnet.main(
        [
            '--image-size', '32', '--epochs', '1', '--batch-size', '16',
            '--limit-steps', '2', '--data-dir', str(tmp_path),
            '--native-loader', '--arch', 'resnet20',
            '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
        ]
    )
    assert 0.0 <= acc <= 1.0


@pytest.mark.slow
def test_lm_pipeline_example_smoke():
    """The LM trainer's pipeline path (DP x PP, 1F1B) runs end to end."""
    from examples import train_language_model

    ppl = train_language_model.main(
        [
            '--d-model', '32', '--num-heads', '4', '--num-layers', '2',
            '--seq-len', '16', '--vocab-size', '64', '--epochs', '1',
            '--batch-size', '8', '--limit-steps', '3',
            '--pipeline-stages', '2', '--pipeline-microbatches', '2',
            '--kfac-factor-update-steps', '1', '--kfac-inv-update-steps', '1',
        ]
    )
    # exp(20) is the divergence cap: reaching it means loss blew up
    import math

    assert math.isfinite(ppl) and ppl < math.exp(20.0)
