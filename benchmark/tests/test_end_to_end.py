"""A tiny cell through ``harness.run_cell`` on the CPU: the result line's
keys, and that ``correct`` comes out false when the timed path is broken or
computed in a lower precision than the configuration states.

``run.py``'s look for a chip is skipped (these call the function behind
it); everything else of a run is driven. The sizes exist only here and in
``rehearse.py``; no number of these runs is a device number.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, rehearse
from kfac_tpu import enums, preconditioner, training
from kfac_tpu.ops import factors

CELL = 'gpt2-small.kfac-10-100'
SEED = 2_147_483_659  # past 2**31, as the driver's are


@pytest.fixture(autouse=True)
def _the_chips_solver(monkeypatch):
    # off a TPU the library defaults to the eigen method and a Cholesky
    # solve; a chip run takes the inverse method and Newton-Schulz
    monkeypatch.setattr(
        preconditioner, 'default_compute_method',
        lambda platform=None: (enums.ComputeMethod.INVERSE, 'newton_schulz'),
    )


def _cell():
    cell = rehearse.tiny_cell(harness.load_cell(CELL))
    cell['config']['model']['n_layer'] = 1
    return cell


def _run(cell=None, seconds=0.5):
    lines = []
    result = harness.run_cell(
        cell or _cell(), SEED, seconds, False, jax.devices()[:1],
        time.perf_counter(), lines.append,
    )
    return result, lines


@pytest.fixture
def sound():
    return _run()


def test_result_line_has_the_contract_keys(sound):
    result, lines = sound
    assert list(result) == [
        'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'
    ]
    assert result['correct'] is True
    assert result['failed'] == 0 and result['attempted'] > 0
    bench = harness.load_cell(CELL)['bench']
    assert set(result['metrics']) == {m['name'] for m in bench['end_to_end']}
    units = {m['name']: m['unit'] for m in bench['end_to_end']}
    for name, m in result['metrics'].items():
        assert set(m) == {'value', 'unit'} and m['unit'] == units[name]
        assert isinstance(m['value'], float) and m['value'] > 0 or (
            name == 'peak_hbm_gb'  # the CPU backend reports no peak
        )
    assert set(result['device']) == {
        'platform', 'kind', 'count', 'memory_peak_bytes'
    }
    json.dumps(result)
    # every number compared is printed beside its limit
    compared = [json.loads(l[len('check: '):]) for l in lines
                if l.startswith('check: {')]
    assert [c['number'] for c in compared] == list(_cell()['workload']['limits'])
    assert all(c['ok'] and c['value'] <= c['limit'] for c in compared)
    # and once more under the result line's last key, the window's two
    # counts after the check's numbers
    assert list(result['compared']) == [c['number'] for c in compared] + [
        'programs_built_in_window', 'non_finite_losses'
    ]
    for c in compared:
        assert result['compared'][c['number']] == {
            'value': c['value'], 'limit': c['limit']
        }
    assert result['compared']['programs_built_in_window'] == {
        'value': 0, 'limit': 0
    }


def test_programs_built_are_counted():
    counter = harness.BuildCounter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0))
    assert counter.count >= 1


def test_same_seed_same_inputs_other_seed_other_inputs():
    from benchmark import jobs, weights

    cell = _cell()
    job = jobs.load('lm').build(cell['config'], cell['workload'], jax.devices()[:1])
    a, b, c = (job.make_ring(s, 2) for s in (SEED, SEED, SEED + 1))
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert not (a[0][0] == c[0][0]).all()
    assert not (a[0][0] == a[1][0]).all()  # the ring's batches differ
    assert len({tuple(r) for r in a[0][0].tolist()}) == len(a[0][0])
    k1, k2 = weights.seed_key(SEED), weights.seed_key(SEED + 1)
    assert not (jax.random.key_data(k1) == jax.random.key_data(k2)).all()


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(monkeypatch):
    def frozen(self, state, grads, new_model_state):
        del grads
        return state.params, state.opt_state, new_model_state

    monkeypatch.setattr(training.Trainer, '_apply_update', frozen)
    result, lines = _run()
    assert result['correct'] is False
    failed = [json.loads(l[len('check: '):]) for l in lines
              if l.startswith('check: {') and '"ok": false' in l]
    assert {f['number'] for f in failed} >= {'update_norm_gap'}
    gap = result['compared']['update_norm_gap']
    assert gap['value'] > gap['limit']


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    put = harness.Run.put

    def half(self, batch):
        # the second half of the rows never reaches the step: the first
        # half stands in for it
        n = len(batch[0]) // 2
        return put(self, tuple(
            jnp.concatenate([jnp.asarray(b[:n])] * 2) for b in batch
        ))

    # only the program's feed: the reference is fed before the trainer exists
    real_init = training.Trainer.init

    def init(self, *args, **kw):
        if self.kfac is not None:
            monkeypatch.setattr(harness.Run, 'put', half)
        return real_init(self, *args, **kw)

    monkeypatch.setattr(training.Trainer, 'init', init)
    result, _ = _run()
    assert result['correct'] is False


def test_newton_schulz_in_a_lower_precision_is_not_correct(monkeypatch):
    """The control, at a size a test can hold. On the chip the control is
    the program with ``NS_PRECISION`` one step down (PERF.md section 2);
    the CPU has one float32 matmul, so here the solve's products round
    their operands to bfloat16, which is what a TPU's default precision
    does to them."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def rounded_step(m, x, mx):
        d = m.shape[-1]
        eye = jnp.eye(d, dtype=jnp.float32)
        x_new = jnp.matmul(bf16(x), bf16(2.0 * eye - mx))
        mx_new = jnp.matmul(bf16(m), bf16(x_new))
        resid = jnp.linalg.norm(eye - mx_new) / jnp.sqrt(
            jnp.asarray(d, jnp.float32)
        )
        return x_new, mx_new, resid

    monkeypatch.setattr(factors, 'newton_schulz_step', rounded_step)
    result, lines = _run()
    assert result['correct'] is False
    failed = [json.loads(l[len('check: '):]) for l in lines
              if l.startswith('check: {') and '"ok": false' in l]
    assert 'inverse_residual' in {f['number'] for f in failed}


def test_a_compile_inside_the_window_is_not_correct(monkeypatch):
    real = harness.window

    def window(run, seconds, first_order_steps):
        out = real(run, seconds, first_order_steps)
        jax.jit(lambda x: x - 0.12345)(jnp.arange(3.0))  # a new program
        return out

    monkeypatch.setattr(harness, 'window', window)
    result, _ = _run()
    assert result['correct'] is False
