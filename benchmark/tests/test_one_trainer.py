"""One trainer's state on the device at a time (PR 31): a tiny ``lm`` cell
and a tiny ``hybrid_lm`` cell through ``harness.run_cell`` on the CPU, with
every sample of device memory watched, and the traced run's two rounds of
readers on a stub trace. No number of these runs is a device number.
"""

import time

import jax
import pytest

from benchmark import harness, rehearse
from benchmark import trace_reduce as tr
from kfac_tpu import enums, preconditioner

SEED = 2_147_483_693  # past 2**31, as the driver's are
CELLS = {
    'lm': 'gpt2-small.kfac-10-100',
    'hybrid_lm': 'qwen3-next-80b-a3b.kfac-10-100',
}
# float32 on the CPU against the float32 reference (tests/test_hybrid_lm.py)
TINY_LIMITS = {
    'loss_gap': 1e-4, 'first_grad_norm_gap': 2e-3, 'update_norm_gap': 5e-3,
    'inverse_residual': 3e-6,
}


def _tiny(kind):
    cell = rehearse.tiny_cell(harness.load_cell(CELLS[kind]))
    cell['workload']['limits'] = dict(TINY_LIMITS)
    return cell


def _live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


@pytest.fixture(scope='module')
def patch():
    with pytest.MonkeyPatch.context() as mp:
        # a chip run takes the inverse method and Newton-Schulz
        mp.setattr(
            preconditioner, 'default_compute_method',
            lambda platform=None: (
                enums.ComputeMethod.INVERSE, 'newton_schulz'
            ),
        )
        yield mp


@pytest.fixture(scope='module', params=list(CELLS))
def watched(request, patch):
    """An untraced run with every ``sample_memory`` call recorded: which
    state the run holds, whether the reference is loaded, the bytes of
    every live array."""
    samples = []
    real = harness.Run.sample_memory

    def sample(run):
        samples.append({
            'kfac': run.state is not None,
            'first_order': run.first_order_state is not None,
            'reference': run.reference is not None,
            'live': _live_bytes(),
        })
        real(run)

    patch.setattr(harness.Run, 'sample_memory', sample)
    cell = _tiny(request.param)
    lines = []
    with jax.default_matmul_precision('highest'):
        result = harness.run_cell(
            cell, SEED, 0.5, False, jax.devices()[:1], time.perf_counter(),
            lines.append,
        )
    patch.setattr(harness.Run, 'sample_memory', real)
    return cell, result, lines, samples


def test_one_state_on_the_device_and_no_reference(watched):
    _, result, lines, samples = watched
    assert result['correct'] is True, lines
    assert samples
    for s in samples:
        assert not (s['kfac'] and s['first_order'])
        assert not s['reference']
    # the attributes could lie: the arrays cannot. A first-order step
    # holds less than any K-FAC step of the window did (parameters and
    # momentum against those and the factors), which it would not beside
    # the K-FAC trainer's state
    kfac = [s['live'] for s in samples if s['kfac']]
    first_order = [s['live'] for s in samples if s['first_order']]
    assert kfac and first_order
    assert max(first_order) < min(kfac)


def test_the_result_line_and_the_rows_are_as_before(watched):
    cell, result, lines, samples = watched
    assert list(result) == [
        'correct', 'attempted', 'failed', 'metrics', 'device', 'compared'
    ]
    assert set(result['metrics']) == {
        m['name'] for m in cell['bench']['end_to_end']
    }
    assert set(result['device']) == {
        'platform', 'kind', 'count', 'memory_peak_bytes'
    }
    workload = cell['workload']
    every = workload['kfac']['inv_update_steps']
    (said,) = [l for l in lines if l.startswith('window: ')]
    periods = int(said.split(' periods of ')[0].rsplit(' ', 1)[1])
    assert said.startswith(f"window: {workload['first_order_steps']} first-order")
    assert periods >= 1
    # timed rows: the first-order steps and whole periods, nothing else;
    # sampled besides: 3 warm steps in set-up, 3 checked, 3 warm again
    assert result['attempted'] == workload['first_order_steps'] + periods * every
    assert len(samples) == result['attempted'] + 3 * 3


def test_nothing_is_built_inside_the_window(watched):
    _, result, lines, _ = watched
    (said,) = [l for l in lines if l.startswith('programs built inside')]
    assert said.startswith('programs built inside the window: 0 (limit 0)')
    # what the device held is noted with the K-FAC state still on it
    (memory,) = [l for l in lines if l.startswith('memory: ')]
    assert 'after the K-FAC steps' in memory


def test_the_window_keeps_room_for_the_first_order_steps(patch):
    """Whole periods while another fits *beside the first-order steps*:
    with 10 s of them to come, a window of 10 s holds one period, where
    it would hold hundreds of these."""
    cell = _tiny('lm')
    cell['config']['model']['n_layer'] = 1
    with jax.default_matmul_precision('highest'):
        run, verdict, _ = harness.set_up(
            cell, SEED, jax.devices()[:1], lambda m: None
        )
        assert verdict['ok'] and run.first_order_step_s > 0
        assert run.first_order_state is None and run.reference is None
        run.first_order_step_s = 10.0 / cell['workload']['first_order_steps']
        fo_rows, periods = harness.window(
            run, 10.0, cell['workload']['first_order_steps']
        )
    assert len(periods) == 1
    assert len(fo_rows) == cell['workload']['first_order_steps']
    assert run.state is None and run.first_order_state is None


def _stub_trace(rows):
    """One device plane with an operation a traced step, 100 ns each."""
    ops = [
        {'name': f'%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %x)',
         'start_ns': 1000 * i, 'duration_ns': 100, 'stats': {}}
        for i in range(rows)
    ]
    return {'planes': [{'name': '/device:TPU:0', 'lines': [
        {'name': 'XLA Ops', 'events': ops},
    ]}]}


@pytest.mark.parametrize('kind', list(CELLS))
def test_the_traced_run_reads_in_two_rounds(kind, patch, monkeypatch):
    """``traced_run`` on a stub trace (the CPU has no device plane): the
    readers of what the K-FAC steps left read with that state on the
    device and no first-order rows yet; ``plain_extra_ms`` reads after the
    first-order stretch, the K-FAC state gone."""
    monkeypatch.setattr(jax.profiler, 'start_trace', lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, 'stop_trace', lambda: None)
    monkeypatch.setattr(tr, 'find_xplane', lambda logdir: logdir)
    monkeypatch.setattr(tr, 'from_xplane', lambda path, keep: _stub_trace(7))
    seen = {}
    real = harness.read_layer_metric

    def read(name, ctx):
        seen[name] = {
            'kfac': ctx.run.state is not None,
            'first_order_rows': ctx.first_order_rows is not None,
        }
        # the CPU is in no table of peaks
        return None if name == 'mfu' else real(name, ctx)

    monkeypatch.setattr(harness, 'read_layer_metric', read)
    cell = _tiny(kind)
    lines = []
    with jax.default_matmul_precision('highest'):
        result = harness.run_cell(
            cell, SEED, 0.5, True, jax.devices()[:1], time.perf_counter(),
            lines.append,
        )
    assert result['correct'] is True, lines
    names = [m['name'] for m in harness.layer_rows(cell)]
    assert set(seen) == set(names)
    late = {n for n, s in seen.items() if s['first_order_rows']}
    assert late == {'plain_extra_ms'}
    for name, s in seen.items():
        assert s['kfac'] == (name not in late), name
    # the host-clock and counter rows read on the CPU as on the chip, and
    # come in BENCHMARK.json's order
    got = list(result['metrics'])
    assert got == [n for n in names if n in got]
    # a cell that reports no stall_ms reads the refresh's rows under the
    # names that move what it does report, and the longest step per layer
    refresh = {'refresh_extra_ms', 'ns_trips_refresh'}
    if 'stall_ms' not in {m['name'] for m in cell['bench']['end_to_end']}:
        assert not refresh & set(got)
        refresh = {n + '.overhead' for n in refresh} | {'longest_step_ms'}
    assert {'plain_extra_ms', 'capture_extra_ms', 'kfac_state_gb',
            'input_wait_ms'} | refresh <= set(got)
    assert set(result['device']) >= {'busy_s', 'window_s', 'memory_peak_bytes'}
    said = [l for l in lines if l.startswith('programs built inside')]
    assert said[0].startswith('programs built inside the window: 0 ')
