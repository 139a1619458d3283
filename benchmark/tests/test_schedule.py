"""Period, stall and overhead arithmetic on a synthetic step table."""

import pytest

from benchmark import schedule


def _rows(seconds, start=0.0, gap=0.01, first_step=None):
    rows, t = [], start
    for i, s in enumerate(seconds):
        row = {'begin': t, 'dispatch': t + gap, 'end': t + gap + s,
               'seconds': s}
        if first_step is not None:
            row['step'] = first_step + i
            row['kind'] = schedule.step_kind(row['step'], 10, 100)
        rows.append(row)
        t = row['end']
    return rows


def _period(first_step):
    secs = []
    for step in range(first_step, first_step + 100):
        kind = schedule.step_kind(step, 10, 100)
        secs.append({'plain': 0.1, 'capture': 0.5, 'refresh': 1.5}[kind])
    return secs


def test_step_kinds_of_a_period():
    kinds = [schedule.step_kind(s, 10, 100) for s in range(3, 103)]
    assert kinds.count('refresh') == 1
    assert kinds.count('capture') == 9
    assert kinds.count('plain') == 90
    assert schedule.step_kind(100, 10, 100) == 'refresh'
    assert schedule.step_kind(110, 10, 100) == 'capture'


def test_end_to_end_numbers():
    first_order = _rows([0.08] * 40)
    rows = _rows(_period(3) + _period(103), start=10.0, first_step=3)
    periods = schedule.whole_periods(rows, 100)
    assert len(periods) == 2
    got = schedule.end_to_end(first_order, periods, batch=128)
    # a period: 90 * 0.1 + 9 * 0.5 + 1.5 = 15 s of steps + 100 gaps of 10 ms
    assert got['throughput'] == pytest.approx(128 * 200 / 32.0)
    assert got['kfac_overhead'] == pytest.approx(0.16 / 0.09)
    assert got['stall_ms'] == pytest.approx(1500.0)


@pytest.mark.parametrize('slow,want', [
    ({}, 1500.0),  # three refresh steps of 1.5 s
    ({197: 2.5}, 2500.0),  # step 200: one period's refresh ran long
    ({197: 2.5, 250: 9.0}, 9000.0),  # a hiccup on a plain step is a stall
])
def test_stall_is_the_longest_step_of_all_the_periods(slow, want):
    secs = _period(3) + _period(103) + _period(203)
    for i, s in slow.items():
        secs[i] = s
    rows = _rows(secs, first_step=3)
    got = schedule.end_to_end(
        _rows([0.1] * 4), schedule.whole_periods(rows, 100), batch=1
    )
    assert got['stall_ms'] == pytest.approx(want)


def test_a_remainder_is_not_a_period():
    rows = _rows(_period(3) + [0.1] * 37, first_step=3)
    assert [len(p) for p in schedule.whole_periods(rows, 100)] == [100]
    with pytest.raises(ValueError):
        schedule.end_to_end(_rows([0.1]), [], batch=1)


def test_by_kind_and_input_wait():
    rows = _rows(_period(3), gap=0.02, first_step=3)
    kinds = schedule.by_kind(rows)
    assert kinds == pytest.approx(
        {'plain': 0.1, 'capture': 0.5, 'refresh': 1.5}
    )
    assert schedule.input_wait_ms(rows) == pytest.approx(20.0)


@pytest.mark.parametrize('after,want', [
    (103, (189, 201)), (3, (89, 101)), (190, (289, 301)), (189, (189, 201)),
])
def test_traced_stretch_holds_capture_and_refresh(after, want):
    first, last = schedule.traced_stretch(after, 10, 100)
    assert (first, last) == want
    assert first >= after
    kinds = [schedule.step_kind(s, 10, 100) for s in range(first, last + 1)]
    assert kinds.count('refresh') == 1 and kinds.count('capture') == 1
    assert kinds[-1] == 'plain'
