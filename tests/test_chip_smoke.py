"""chip_smoke.py off the chip: it refuses to run, its run function
returns every field it promises at a tiny width on the CPU mesh, and the
compile cache follows the one rule."""

import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(args, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    env.update({'JAX_PLATFORMS': 'cpu', **env_extra})
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300,
    )


def test_smoke_refuses_to_run_off_the_chip():
    r = _python([os.path.join(REPO, 'chip_smoke.py')], {})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and 'TPU' in r.stderr
    # no result line: nothing on stdout parses as the success object
    assert '"ok"' not in r.stdout


def test_smoke_alone_fails_and_prints_nothing(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it fails even where there is a TPU (faked here), with no output."""
    import shutil

    shutil.copy(os.path.join(REPO, 'chip_smoke.py'), tmp_path)
    code = (
        'import jax, runpy, types; '
        'd = types.SimpleNamespace(platform="tpu", device_kind="fake"); '
        'jax.devices = lambda: [d]; '
        'runpy.run_path("chip_smoke.py", run_name="__main__")'
    )
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    r = subprocess.run(
        [sys.executable, '-c', code], cwd=tmp_path, capture_output=True,
        env={**env, 'JAX_PLATFORMS': 'cpu'}, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert 'ImportError' in r.stderr or 'ModuleNotFoundError' in r.stderr
    assert r.stdout == ''


def test_result_line_has_the_contract_keys_and_no_others():
    import json

    import jax

    got = json.loads(chip_smoke.result_line(jax.devices()))
    assert got == {
        'ok': True,
        'device': {'platform': 'cpu', 'kind': 'cpu', 'count': 8},
    }


@pytest.mark.parametrize('env_dir', ['/tmp/some/where/else', None])
def test_compile_cache_rule(env_dir):
    """JAX_COMPILATION_CACHE_DIR set: that value, and no code sets
    another. Unset: <checkout>/.jax_cache."""
    code = (
        'from kfac_tpu.utils import compile_cache; '
        'print("DIR", compile_cache.configure())'
    )
    extra = {'JAX_COMPILATION_CACHE_DIR': env_dir} if env_dir else {}
    r = _python(['-c', code], extra)
    assert r.returncode == 0, r.stderr
    got = [ln for ln in r.stdout.splitlines() if ln.startswith('DIR ')]
    assert got == ['DIR ' + (env_dir or os.path.join(REPO, '.jax_cache'))]


TINY_KERNEL_SHAPES = {
    'cov': [(256, 256), (40, 130)],
    'klclip': [(128, 256), (100, 130)],
}


def test_kernel_checks_run_in_the_interpreter():
    rows = chip_smoke.check_kernels(TINY_KERNEL_SHAPES)
    assert {r['kernel'] for r in rows} == {
        'get_cov', 'fused_klclip_dot', 'fused_klclip_scale',
    }
    assert sorted(
        (r['shape'], r['dtype']) for r in rows if r['kernel'] == 'get_cov'
    ) == [([40, 130], 'bfloat16'), ([40, 130], 'float32'),
          ([256, 256], 'bfloat16'), ([256, 256], 'float32')]
    for r in rows:
        assert r['max_err'] <= r['tol']


def test_run_training_returns_every_field_at_tiny_width():
    """The smoke's own run function, on the LM trainer at a toy width over
    the 8-device CPU mesh: same cadence as on the chip, so a capture
    step, a plain step and two inverse refreshes all happen."""
    from examples import train_language_model

    report = chip_smoke.run_training(train_language_model.main, [
        '--epochs', '1', '--batch-size', '8', '--seq-len', '32',
        '--d-model', '32', '--num-heads', '4', '--num-layers', '2',
        '--vocab-size', '128', '--kfac-skip-layers', 'lm_head',
        '--kfac-compute-method', 'inverse',
        '--kfac-factor-update-steps', str(chip_smoke.FACTOR_UPDATE_STEPS),
        '--kfac-inv-update-steps', str(chip_smoke.INV_UPDATE_STEPS),
        '--limit-steps', '6', '--kfac-compile-watch',
    ])
    assert report['platform'] == 'cpu' and report['device_count'] == 8
    assert set(report['versions']) == {'jax', 'jaxlib', 'libtpu'}
    assert report['model']['kfac_layers'] == 12
    assert report['compute_method'] == 'INVERSE'
    assert report['inverse_solver'] == 'cholesky'
    assert report['bucket_granularity'] == 1
    assert report['mesh']['kfac_gw'] * report['mesh']['kfac_col'] == 8
    assert report['strategy'] == 'COMM_OPT'
    entries = {'trainer.step/with_stats', 'trainer.step/no_stats'}
    # interpreter off the chip: no Mosaic kernel is compiled, and it says so
    assert report['pallas_kernels'] == dict.fromkeys(entries, 'none')
    assert set(report['compile']) == entries
    for c in report['compile'].values():
        assert c['lowering_s'] >= 0 and c['compile_s'] >= 0 and c['aot']
    assert report['recompiles'] == 0
    assert len(report['step_seconds']) == len(report['losses']) == 6
    assert report['kfac_step'] == 6
    assert max(report['inverse_residuals'].values()) <= 0.05
    sums = report['inverse_checksums']
    assert sums[3] == sums[0] != sums[4] == sums[5]
    assert report['compile_cache']['dir'] == os.path.join(REPO, '.jax_cache')
    assert {'hits', 'misses'} <= set(report['compile_cache'])
    mem = report['memory']
    assert set(mem['peak_bytes_in_use']) == {str(i) for i in range(8)}
    assert sum(mem['kfac_state_bytes'].values()) > 0
    assert mem['kfac_model_per_device']['total'] > 0


def test_check_training_fails_what_it_should():
    """Each failure the docstring names raises; the evidence rides the
    message."""
    good = {
        'losses': [1.0, 0.9, 0.8, 0.7, 0.6, 0.5], 'kfac_step': 6,
        'compute_method': 'INVERSE', 'inverse_residuals': {'a/8x8': 1e-3},
        'model': {'argv': ['--kfac-inv-update-steps', '4']},
        'inverse_checksums': [1.0, 1.0, 1.0, 1.0, 2.0, 2.0],
        'recompiles': 0,
        'compile': {'e': {'aot': True, 'aot_error': None}},
    }
    chip_smoke.check_training(good)
    for patch, why in [
        ({'losses': [1.0] * 5 + [float('nan')]}, 'non-finite loss'),
        ({'kfac_step': 5}, 'step counter'),
        ({'inverse_residuals': {'a/8x8': 0.3}}, 'inverse residuals above'),
        ({'inverse_residuals': {'a/8x8': float('nan')}}, 'residuals above'),
        ({'inverse_checksums': [1.0] * 6}, 'out of cadence'),
        ({'inverse_checksums': [0.0] * 6}, 'never built'),
        ({'recompiles': 1}, 'recompile'),
        ({'compile': {'e': {'aot': False, 'aot_error': 'compile: boom'}}},
         'ahead-of-time dispatch: .*boom'),
    ]:
        with pytest.raises(RuntimeError, match=why):
            chip_smoke.check_training({**good, **patch})
