"""From a table of timed steps to the end-to-end numbers.

A *period* is ``inv_update_steps`` consecutive K-FAC steps: with factors
every 10 and inverses every 100 it holds 90 plain steps, 9 steps that
capture and 1 that captures and refreshes the inverses, wherever it starts.
Every rate is taken over all the work and all the wall-clock of whole
periods, batch feeding included, and the tail is the tail of every step of
those periods. Pure Python: the tests feed it synthetic tables.
"""

from __future__ import annotations

import statistics


def step_kind(step: int, factor_every: int, inv_every: int) -> str:
    """What K-FAC step number ``step`` does besides preconditioning."""
    if step % inv_every == 0:
        return 'refresh'
    if step % factor_every == 0:
        return 'capture'
    return 'plain'


def whole_periods(rows: list, period: int) -> list:
    """``rows`` (consecutive K-FAC steps) cut into whole periods; a
    remainder is dropped."""
    return [
        rows[i:i + period] for i in range(0, len(rows) - period + 1, period)
    ]


def wall(rows: list) -> float:
    """Wall-clock of consecutive steps: from the first one's feed to the
    last one's completion."""
    return rows[-1]['end'] - rows[0]['begin']


def throughput(periods: list, batch: int) -> float:
    """Samples a second over all the steps and all the wall-clock of whole
    K-FAC periods."""
    if not periods:
        raise ValueError('a window needs one whole period')
    steps = sum(len(p) for p in periods)
    return batch * steps / sum(wall(p) for p in periods)


def end_to_end(first_order: list, periods: list, batch: int) -> dict:
    """The window's numbers. ``first_order``: the first-order stretch's
    rows; ``periods``: whole K-FAC periods; a row has ``begin`` (its feed
    starts), ``end`` (it completed) and ``seconds`` (``timed_step``'s).

    ``stall_ms`` is the longest of all the periods' steps. With inverses
    every 100 steps the refresh is one step in a hundred, so a percentile
    at or under the 99th would read a capture step instead; and a step
    that a stall of the host or the device stretched is a stall too."""
    if not periods or not first_order:
        raise ValueError('a window needs first-order steps and one period')
    steps = sum(len(p) for p in periods)
    period_wall = sum(wall(p) for p in periods)
    first_order_step = wall(first_order) / len(first_order)
    return {
        'throughput': throughput(periods, batch),
        'kfac_overhead': (period_wall / steps) / first_order_step,
        'stall_ms': 1e3 * max(r['seconds'] for p in periods for r in p),
    }


def by_kind(rows: list) -> dict:
    """{kind: median ``seconds``} over K-FAC rows that carry ``kind``."""
    kinds: dict[str, list] = {}
    for r in rows:
        kinds.setdefault(r['kind'], []).append(r['seconds'])
    return {k: statistics.median(v) for k, v in kinds.items()}


def input_wait_ms(rows: list) -> float:
    """Median gap between one step's completion and the next one's
    dispatch: the feed, and whatever else the host does between steps."""
    gaps = [
        b['dispatch'] - a['end'] for a, b in zip(rows, rows[1:])
    ]
    return 1e3 * statistics.median(gaps)


def traced_stretch(
    after: int, factor_every: int, inv_every: int
) -> tuple[int, int]:
    """``(first, last)`` K-FAC step numbers of the shortest stretch that
    starts at or after step ``after`` and holds a capture-only step, the
    plain steps up to the next refresh, and that refresh with one plain
    step behind it."""
    k = -(-(after + factor_every + 1) // inv_every)
    return k * inv_every - factor_every - 1, k * inv_every + 1
