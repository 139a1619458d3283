"""Benchmark: K-FAC-preconditioned Transformer LM training throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

A staged orchestrator. The orchestrator itself never touches JAX (a chip
belongs to one process at a time), so each stage runs in its OWN
subprocess, in sequence, under a SIGTERM-grace watchdog — a wedged XLA
compile costs one stage, not the run:

    1. ``micro_safe``      tools/tpu_microbench.py --no-pallas (per-op
                           signal on XLA ops; cheapest first)
    2. ``lm_tiny``         a 2-layer d128 K-FAC LM step (proves K-FAC
                           compiles+runs on the chip at minimum cost)
    3. ``lm_flagship``     the headline config (Pallas gated OFF)
    4. ``micro_pallas``    tools/tpu_microbench.py --pallas-only
    5. ``lm_flagship_pallas``  the flagship again with KFAC_TPU_PALLAS=1,
                           only if stage 4 passed

Every run writes a per-run timestamped record ``bench_runs/run_<ts>.json``
that nothing ever overwrites, plus the ``bench_partial.json`` latest
pointer. Each stage persists phase-by-phase partials to its own file; the
orchestrator merges after every stage, so the answer to "what stalled" is
always on disk (stage name + last announced op).

**A run that finds no TPU fails.** Every stage's first act is to read
``jax.devices()[0]``; unless it is a TPU the stage exits non-zero and so
does the run — no result line is published from another platform under
the device keys. The one exception is a caller that pinned the host
itself with ``JAX_PLATFORMS=cpu`` (the tests' smoke): then the ``lm_tiny``
stage runs alone and its record says ``platform: cpu``.

Measured quantity per LM stage: tokens/sec of a jitted K-FAC train step
(the platform-default compute path: INVERSE + Newton-Schulz on TPU, EIGEN
elsewhere — see kfac_tpu.default_compute_method; factor update every 10
steps, inverse update every 100 — the reference's ImageNet cadence,
examples/torch_imagenet_resnet.py:158-167) against the same model trained
with plain SGD on identical hardware in the same process. ``vs_baseline``
is the throughput ratio kfac/sgd: the *cost* of adding second-order
preconditioning (1.0 = free). KAISA's value proposition is fewer steps to
target quality at small per-step overhead.

Extra fields in the JSON line:
- ``platform`` / ``device_kind``: where the numbers were measured, as the
  first stage's process read them from JAX.
- ``mfu``: model FLOPs utilization of the K-FAC step — model FLOPs only
  (6*N per token plus the 12*L*d*S attention term, the standard accounting),
  excluding the K-FAC factor/eigh work itself, over the chip's peak bf16
  FLOP/s. ``null`` when the peak for the platform is unknown (CPU).
- ``stages``: per-stage status + key numbers from this run.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_T0 = time.time()
# stage subprocesses inherit the orchestrator's run id via the env
_RUN_ID = os.environ.get('BENCH_RUN_ID') or time.strftime('%Y%m%d_%H%M%S')


def _log(msg: str) -> None:
    """Phase progress to stderr: a killed-by-outer-timeout run still leaves
    a diagnosable trail (round-1 lesson: rc=124 with an empty log)."""
    print(f'[bench +{time.time() - _T0:7.1f}s] {msg}', file=sys.stderr, flush=True)


def _atomic_write(path: str, payload: dict) -> None:
    tmp = f'{path}.tmp.{os.getpid()}'
    try:
        with open(tmp, 'w') as f:
            json.dump(payload, f)
        os.replace(tmp, path)
    except Exception:  # persistence is best-effort; never kill the bench
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _persist(result: dict, partial: bool = True) -> None:
    """Snapshot the result-so-far after every completed phase.

    Two sinks (``BENCH_PARTIAL_PATH=''`` disables both):
    - ``bench_runs/run_<RUN_ID>.json``: this run's own record; append-only
      across runs, so no later run can destroy this one's data.
    - ``BENCH_PARTIAL_PATH`` (default ``bench_partial.json``): the latest
      pointer, refreshed on every call. A run killed before its first
      phase never writes it, so consumers attribute it by comparing its
      ``run_id`` against ``bench_runs/LATEST.json`` (written at every run
      start by :func:`_mark_run_started`).
    """
    path = os.environ.get('BENCH_PARTIAL_PATH', 'bench_partial.json')
    if not path:
        return
    payload = {**result, 'partial': partial, 'run_id': _RUN_ID}
    runs_dir = os.environ.get('BENCH_RUNS_DIR', 'bench_runs')
    try:
        os.makedirs(runs_dir, exist_ok=True)
        _atomic_write(os.path.join(runs_dir, f'run_{_RUN_ID}.json'), payload)
    except Exception:
        pass
    _atomic_write(path, payload)


def _mark_run_started() -> None:
    """Stamp ``bench_runs/LATEST.json`` with this run's id at process
    start. The latest-pointer file may belong to an OLDER run (a run
    killed pre-first-phase writes none), so attribution goes through
    this marker: ``bench_partial.json`` describes the current run
    iff its ``run_id`` matches ``LATEST.json``'s."""
    if not os.environ.get('BENCH_PARTIAL_PATH', 'bench_partial.json'):
        return
    runs_dir = os.environ.get('BENCH_RUNS_DIR', 'bench_runs')
    try:
        os.makedirs(runs_dir, exist_ok=True)
        _atomic_write(
            os.path.join(runs_dir, 'LATEST.json'),
            {'run_id': _RUN_ID, 'started_unix': round(_T0, 1)},
        )
    except Exception:
        pass


# bf16 peak FLOP/s per chip, keyed by device_kind substring (lowercase).
_PEAK_FLOPS = {
    'v6e': 918e12,
    'v6 lite': 918e12,
    'v5p': 459e12,
    'v5e': 197e12,
    'v5 lite': 197e12,
    'v5': 459e12,
    'v4': 275e12,
    'v3': 123e12,
    'v2': 46e12,
}


def _peak_flops(device_kind: str) -> float | None:
    kind = device_kind.lower()
    # Longest key first so 'v5e'/'v5 lite' can never be shadowed by 'v5'.
    for key in sorted(_PEAK_FLOPS, key=len, reverse=True):
        if key in kind:
            return _PEAK_FLOPS[key]
    return None


def _timeit(step_for_iter, args, warmup: int = 5, iters: int = 100) -> float:
    """Average seconds/step of a cadence-dispatched step sequence.

    ``step_for_iter(i)`` returns the jitted step function for global step i,
    so the measured loop amortizes capture/inverse cadence exactly like a
    real training run. The default window of 100 steps (measured steps
    5..104) contains 10 factor captures and exactly one inverse/eigh update
    at step 100 — the full inv_update_steps cadence, so the eigh cost is
    represented at its true 1/100 proportion rather than excluded.
    """
    import jax

    out = None
    for i in range(warmup):
        # per-iteration announcements: warmup i=0 is the capture-variant
        # compile, i=1 the plain variant — a stalled run's last stderr
        # line names which program wedged (the r5s3 lm_large lesson)
        t0 = time.perf_counter()
        out = step_for_iter(i)(*args)
        jax.block_until_ready(out)
        _log(f'  warmup {i}: {time.perf_counter() - t0:.1f}s')
        args = (out[0], out[1], out[2], args[3])
    jax.block_until_ready(out)
    start = time.perf_counter()
    for i in range(warmup, warmup + iters):
        out = step_for_iter(i)(*args)
        args = (out[0], out[1], out[2], args[3])
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters


def _async_spike_probe(d: int = 512, window: int = 8, windows: int = 3) -> dict:
    """Per-step latency series of a d>=512 MLP: synchronous boundary
    refresh vs the sliced async backend (``kfac_tpu.async_inverse``).

    Builds its own model rather than reusing the stage's — the refresh
    spike only shows where the boundary eigh (~30 d^3) dominates a step,
    and the CPU-smoke LM never reaches that regime. Reports p50/p95/max
    per-step milliseconds for both paths plus ``refresh_spike_ratio``
    (max step / median step over ``windows`` full cadence windows): the
    sync path spikes multi-x at every boundary, the sliced path must
    stay flat (acceptance bar: <= 1.5).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kfac_tpu
    from kfac_tpu.models import MLP

    model = MLP(features=(d, d, d), num_classes=32)
    x = jax.random.normal(jax.random.PRNGKey(3), (256, d))
    y = jax.random.normal(jax.random.PRNGKey(4), (256, 32))

    def loss(p, batch):
        xx, yy = batch
        return jnp.mean((model.apply({'params': p}, xx) - yy) ** 2)

    params = model.init(jax.random.PRNGKey(5), x)['params']
    reg = kfac_tpu.register_model(model, x)
    opt = optax.sgd(0.05)

    def series(async_inverse):
        kfac = kfac_tpu.KFACPreconditioner(
            registry=reg, damping=1e-3, lr=0.1,
            factor_update_steps=window, inv_update_steps=window,
            async_inverse=async_inverse,
        )
        run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(loss)

        @jax.jit
        def step(p, kstate, opt_state, batch):
            (l, _), grads, stats = run(p, batch)
            kstate, pgrads = kfac.step(kstate, grads, stats)
            updates, opt_state = opt.update(pgrads, opt_state, p)
            return optax.apply_updates(p, updates), kstate, opt_state, l

        args = (params, kfac.init(), opt.init(params), (x, y))
        out = None
        for _ in range(window + 1):  # compile + one full warm window
            out = step(*args)
            args = (out[0], out[1], out[2], args[3])
        jax.block_until_ready(out[3])
        times = []
        for _ in range(window * windows):
            t0 = time.perf_counter()
            out = step(*args)
            jax.block_until_ready(out[3])
            times.append((time.perf_counter() - t0) * 1e3)
            args = (out[0], out[1], out[2], args[3])
        return np.asarray(times)

    t_sync = series(None)
    t_sliced = series('sliced')

    def stats(prefix, ts):
        return {
            f'step_p50_ms{prefix}': round(float(np.percentile(ts, 50)), 3),
            f'step_p95_ms{prefix}': round(float(np.percentile(ts, 95)), 3),
            f'step_max_ms{prefix}': round(float(np.max(ts)), 3),
            f'refresh_spike_ratio{prefix}': round(
                float(np.max(ts) / np.median(ts)), 3
            ),
        }

    out = {'async_probe_config': f'mlp_d{d}_b256_w{window}'}
    out.update(stats('', t_sliced))
    out.update(stats('_sync', t_sync))
    return out


def _compression_probe(d: int = 256, steps: int = 24) -> dict:
    """Compressed-transport + cold-factor-offload probe
    (docs/ARCHITECTURE.md "Compression & offload").

    A/B's the distributed bucketed engine on the same MLP at the f32 vs
    int8 wire: reports the static wire-bytes ratio from
    ``comms_report()`` (the >= 3x acceptance figure) next to eager
    per-step medians for both wires. Then runs a short eager offload
    Trainer loop (factor cadence 8, ``min_cold_steps=2``,
    ``prefetch_lead=1``) and reports the live ``OffloadManager``
    counters — ``prefetch_hit_rate`` 1.0 means every restore found its
    host->device transfer already in flight.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kfac_tpu
    from kfac_tpu import training
    from kfac_tpu.models import MLP
    from kfac_tpu.parallel import DistributedKFAC, kaisa_mesh

    model = MLP(features=(d, d), num_classes=16)
    x = jax.random.normal(jax.random.PRNGKey(6), (128, d))
    y = jax.random.normal(jax.random.PRNGKey(7), (128, 16))
    params = model.init(jax.random.PRNGKey(8), x)['params']
    reg = kfac_tpu.register_model(model, x)

    def loss(p, batch):
        xx, yy = batch
        return jnp.mean((model.apply({'params': p}, xx) - yy) ** 2)

    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(loss)
    mesh = kaisa_mesh(grad_worker_fraction=1.0)

    def series(stat_compression):
        cfg = kfac_tpu.KFACPreconditioner(
            registry=reg, damping=1e-3, lr=0.1,
            allreduce_method='allreduce_bucketed',
            stat_compression=stat_compression,
        )
        eng = DistributedKFAC(config=cfg, mesh=mesh)

        @jax.jit
        def step(state, p, batch):
            (l, _), grads, stats = run(p, batch)
            return eng.step(state, grads, stats, loss=l)

        state = eng.init()
        state, pg = step(state, params, (x, y))  # compile — excluded
        jax.block_until_ready(pg)
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            state, pg = step(state, params, (x, y))
            jax.block_until_ready(pg)
            times.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(times)), eng.comms_report()['stat_transport']

    t_f32, st_f32 = series(None)
    t_int8, st_int8 = series('int8')
    out = {
        'compression_probe_config': f'mlp_d{d}_b128_bucketed',
        'wire_ratio_int8': round(
            st_int8['raw_bytes'] / st_int8['wire_bytes'], 3),
        'stat_wire_bytes_f32': st_f32['wire_bytes'],
        'stat_wire_bytes_int8': st_int8['wire_bytes'],
        'step_p50_ms_f32_wire': round(t_f32, 3),
        'step_p50_ms_int8_wire': round(t_int8, 3),
    }

    # cold-factor offload: the eager Trainer loop is what drives the
    # host-side pump, so the counters only move on this path
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, damping=1e-3, lr=0.1,
        factor_update_steps=8, inv_update_steps=8,
        offload=kfac_tpu.OffloadConfig(min_cold_steps=2, prefetch_lead=1),
    )

    def loss3(p, model_state, batch):
        return loss(p, batch), model_state

    trainer = training.Trainer(
        loss_fn=loss3, optimizer=optax.sgd(0.05), kfac=kfac
    )
    tstate = trainer.init(params)
    last = None
    for _ in range(steps):
        tstate, last = trainer.step(tstate, (x, y))
    jax.block_until_ready(last)
    counters = dict(trainer.kfac._offload_manager.stats)
    attempts = counters['prefetch_hits'] + counters['prefetch_misses']
    counters['prefetch_hit_rate'] = (
        round(counters['prefetch_hits'] / attempts, 3) if attempts else None
    )
    out['offload'] = counters
    return out


def _fleet_probe(steps: int = 6) -> dict:
    """Self-driving fleet probe (docs/ROBUSTNESS.md "Self-driving fleet").

    Drives a tiny fleet-managed Trainer with a skew-injecting drain
    (``testing/faults.skewed_drain``) so the drift detector arms a
    model-only retune and executes a live layout migration at the first
    checkpoint boundary. Reports the retune wall-clock (the cost-model
    fast path the controller runs in-job), the end-to-end migration
    wall-clock (blocking save -> rebuild -> elastic restore -> swap) and
    the migration downtime in steps (boundary step minus arming step —
    the window the job kept training on the stale layout). The HBM
    budget handed to the cost model is sized between the MEM-OPT and
    COMM-OPT footprints so the retune MUST move off the starting
    COMM-OPT layout.
    """
    import tempfile
    import warnings as pywarnings

    import jax
    import jax.numpy as jnp
    import optax

    import kfac_tpu
    from kfac_tpu.autotune import model as autotune_model
    from kfac_tpu.autotune import search as autotune_search
    from kfac_tpu.models import MLP
    from testing import faults

    # d=16 keeps the cost-model ranking honest for the story below:
    # unconstrained, COMM-OPT genuinely wins (comm-free grad workers),
    # so the starting plan is a real frac-1.0 layout; under the tight
    # budget the frac-1.0 footprint is infeasible and MEM-OPT takes it
    d = 16
    world = jax.device_count()
    model = MLP(features=(d, d), num_classes=8)
    x = jax.random.normal(jax.random.PRNGKey(9), (64, d))
    y = jax.random.normal(jax.random.PRNGKey(10), (64, 8))
    params = model.init(jax.random.PRNGKey(11), x)['params']
    reg = kfac_tpu.register_model(model, x)

    def loss_fn(p, model_state, batch):
        xx, yy = batch
        pred = model.apply({'params': p}, xx)
        return jnp.mean((pred - yy) ** 2), model_state

    def bare():
        return kfac_tpu.KFACPreconditioner(
            registry=reg, damping=1e-3, lr=0.1, flight=8
        )

    # the stale starting point: a plan genuinely tuned to COMM-OPT
    plan = autotune_search.autotune(
        bare(), measure=False, world=world,
        fractions=(1.0,), granularities=(1,),
    )
    rows = [
        autotune_model.predict(c, bare(), world)
        for c in autotune_search.baseline_candidates(world, bare())
    ]
    mems = sorted(r['memory_per_device_bytes']['total'] for r in rows)
    tight = autotune_model.HardwareSpec(hbm_bytes=(mems[0] + mems[-1]) / 2)

    with tempfile.TemporaryDirectory() as td:
        mgr = kfac_tpu.CheckpointManager(
            td, save_interval_steps=4, keep=2,
            install_signals=(), async_save=False,
        )
        ctrl = kfac_tpu.FleetController(
            mgr,
            kfac_tpu.FleetConfig(
                check_every=2, drift_keys=('grad_norm',),
                drift_threshold=0.5, drift_window=2, drift_patience=1,
                cooldown_steps=8,
            ),
            plan=plan, hardware=tight,
            drain=faults.skewed_drain('grad_norm', 2.0),
        )
        trainer = kfac_tpu.Trainer(
            loss_fn=loss_fn, optimizer=optax.sgd(0.05),
            kfac=bare(), fleet=ctrl,
        )
        workers_before = ctrl.engine.grad_workers
        state = trainer.init(params)
        with pywarnings.catch_warnings():
            pywarnings.simplefilter('ignore')
            for _ in range(steps):
                state, last = trainer.step(state, (x, y))
        jax.block_until_ready(last)
        return {
            'fleet_probe_config': f'mlp_d{d}_world{world}',
            'migrations': ctrl.stats['migrations'],
            'aborts': ctrl.stats['aborts'],
            'retune_wall_s': round(ctrl.stats['retune_s'] or 0.0, 6),
            'migration_wall_s': round(ctrl.stats['migration_s'] or 0.0, 3),
            'migration_downtime_steps': ctrl.stats['downtime_steps'],
            'grad_workers_before': workers_before,
            'grad_workers_after': ctrl.engine.grad_workers,
            'events': [e['event'] for e in ctrl.events],
        }


def _pipeline_probe() -> dict:
    """3D-planner pipeline-schedule probe (docs/AUTOTUNE.md "3D topology
    planner").

    Folds the committed measured-vs-predicted bubble table
    (``kfac_tpu/planner/bubble_table.json``) into the round JSON: per
    ``(schedule, p, v)`` the simulator's predicted bubble fraction, the
    measured fraction, the p50 step wall-clock, and the floor-verdict
    flag, under the one-dispatch harness provenance the measured tier
    recorded (harness_version / dispatch_mode / dispatches). Read-only —
    it loads the artifact rather than re-measuring, so a bench round
    stays bounded while still publishing how far each schedule's
    wall-clock sits from its simulated prediction.
    """
    from kfac_tpu.planner import execute

    table = execute.load_bubble_table(execute.ARTIFACT_PATH)
    if not table:
        return {'status': 'missing'}
    rows = [
        {
            'schedule': r['schedule'], 'p': r['p'], 'v': r['v'],
            'predicted_fraction': round(r['predicted_fraction'], 4),
            'measured_fraction': round(r['measured']['fraction'], 4),
            'wall_clock_p50_s': r['measured']['wall_clock_p50_s'],
            'contaminated': r['contaminated'],
        }
        for r in table['rows']
    ]
    return {
        'status': 'ok',
        'schema': table['schema'],
        'tolerance': table['tolerance'],
        'clean_rows': sum(not r['contaminated'] for r in rows),
        'rows': rows,
        'provenance': table.get('provenance', {}),
    }


def _chaos_probe() -> dict:
    """Chaos-harness recovery SLOs (docs/ROBUSTNESS.md "Chaos harness").

    Folds the committed storm artifact
    (``kfac_tpu/resilience/chaos_slo.json``, written by
    ``tools/kfac_chaos.py --out``) into the round JSON: per fault class
    the measured downtime steps, recovery wall-clock, restore fallback
    depth, and worst divergence vs the uninterrupted control run, plus
    the storm's shape and whether every SLO budget held. Read-only — a
    storm spawns a real multi-process pod (minutes), so bench rounds
    publish the last measured storm rather than re-running one.
    """
    from kfac_tpu.resilience import chaos

    artifact = chaos.load_slo_artifact()
    if artifact is None:
        return {'status': 'missing'}
    cfg = artifact.get('config', {})
    return {
        'status': 'ok' if artifact.get('ok') else 'blown',
        'rows': artifact['rows'],
        'procs': cfg.get('procs'),
        'max_steps': cfg.get('max_steps'),
        'schedule': [e.get('fault') for e in artifact.get('schedule', ())],
        'blown': artifact.get('blown', []),
    }


def _ledger_probe(result: dict) -> dict:
    """Perf-regression sentinel verdict (docs/OBSERVABILITY.md "Run
    ledger"): this round's headline keys vs the committed baseline
    ``bench_runs/LEDGER.json``, per-key ok/regressed/missing plus a
    top-level status. Provenance-aware — a CPU-fallback round is never
    compared against TPU medians (status ``refused``), and a missing
    baseline is ``no_baseline``, not a failure. Read-only and advisory
    inside the round: CI gates on ``tools/kfac_ledger.py --check``,
    whose exit code carries the same verdict.
    """
    try:
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            'kfac_tpu', 'observability', 'ledger.py')
        spec = importlib.util.spec_from_file_location('_kfac_ledger', path)
        assert spec is not None and spec.loader is not None
        ledger = importlib.util.module_from_spec(spec)
        sys.modules['_kfac_ledger'] = ledger
        spec.loader.exec_module(ledger)
        baseline_path = os.path.join(
            os.environ.get('BENCH_RUNS_DIR', 'bench_runs'), 'LEDGER.json')
        baseline = (ledger.load_baseline(baseline_path)
                    if os.path.exists(baseline_path) else None)
        verdict = ledger.sentinel_check(result, baseline)
        return {
            'status': verdict['status'],
            'regressed_keys': verdict['regressed_keys'],
            'baseline_platform': verdict['baseline_platform'],
            'keys': {k: v['verdict'] for k, v in verdict['keys'].items()},
        }
    except Exception as exc:  # never kill the round over the sentinel
        return {'status': 'error', 'error': f'{type(exc).__name__}: {exc}'}


def _fused_kernel_probe(d: int = 256, rows: int = 512) -> dict:
    """Within-run A/B of the fused step-path kernels vs their unfused
    XLA expressions (docs/ARCHITECTURE.md "Fused step-path kernels").

    Per family (cov_ema / klclip): p50 wall-clock of each variant,
    timed back-to-back in THIS process so the comparison shares one
    host-load regime, plus per-variant device milliseconds attributed
    from a short profiler trace when the backend has device lanes
    (empty off-TPU — the host p50s stand alone). Off-TPU the fused
    variants run in interpret mode, so their numbers measure the
    emulation, not Mosaic; the ``interpret`` flag says which regime the
    record is from.
    """
    import jax
    import jax.numpy as jnp

    from kfac_tpu.ops import pallas_cov_ema, pallas_ns

    interp = pallas_ns.interpret_mode()
    a = jax.random.normal(jax.random.PRNGKey(7), (rows, d), jnp.float32)
    eye = jnp.eye(d, dtype=jnp.float32)
    cov = a.T @ a / rows + 0.003 * eye
    gmat = 0.5 * cov + 0.1 * eye
    beta, coeff = 0.95, 0.05 / rows

    def ema_unfused(f, x):
        acc = jax.lax.dot_general(
            x, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return beta * f + coeff * acc

    def kl_unfused(p, g):
        return p * jnp.sum(p * g)

    def kl_fused(p, g):
        s = pallas_ns.fused_klclip_dot(p, g, interpret=interp)
        return pallas_ns.fused_klclip_scale(p, s, interpret=interp)

    pairs = {
        'cov_ema': (ema_unfused,
                    lambda f, x: pallas_cov_ema._fused(
                        f, x, beta, coeff, interpret=interp),
                    (eye, a)),
        'klclip': (kl_unfused, kl_fused, (cov, gmat)),
    }

    def p50_ms(fn, args, n=9):
        jax.block_until_ready(fn(*args))  # compile outside the clock
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return round(ts[len(ts) // 2] * 1e3, 3)

    out: dict = {'config': f'd{d}_rows{rows}', 'interpret': interp}
    jitted: dict = {}
    for fam, (unfused, fused, args) in pairs.items():
        scopes = {}
        for variant, fn in (('unfused', unfused), ('fused', fused)):
            name = f'fused_probe.{fam}_{variant}'
            scopes[variant] = name
            jitted[name] = (
                jax.jit(lambda *xs, _f=fn, _n=name: (
                    jax.named_scope(_n)(_f)(*xs)
                )),
                args,
            )
        row = {'unfused_p50_ms': p50_ms(*jitted[scopes['unfused']])}
        try:
            row['fused_p50_ms'] = p50_ms(*jitted[scopes['fused']])
            row['speedup'] = round(
                row['unfused_p50_ms'] / max(row['fused_p50_ms'], 1e-9), 3
            )
        except Exception as exc:  # one variant's failure costs one row
            row['fused_error'] = f'{type(exc).__name__}: {exc}'
        out[fam] = row

    return out


def _compile_probe(reg, run, params, data) -> dict:
    """Compile & memory truth probe (docs/OBSERVABILITY.md "Compile &
    memory truth").

    Routes a watched ``step`` on BOTH engines through the compile watch
    and reports: per-entry lowering/compile wall-clock and XLA-reported
    memory (``memory_analysis``), the recompile count after warm
    re-steps — the "jit cache stays at 1" pin as a bench headline, must
    be 0 on both engines — and the process persistent compile-cache
    hit/miss counters (``jax.monitoring``) as deltas over the probe, so
    a round can tell a warm-cache start from a cold one.
    """
    import jax

    import kfac_tpu
    from kfac_tpu.observability import compile_watch as compile_watch_lib
    from kfac_tpu.parallel import DistributedKFAC

    counters = compile_watch_lib.persistent_cache_counters()
    before = counters.snapshot()
    out: dict = {'entries': {}, 'recompiles_after_warmup': {}}

    (_, _), grads, stats = jax.jit(run)(params, data)

    def dense():
        return kfac_tpu.KFACPreconditioner(
            registry=reg, compile_watch=True)

    def distributed():
        return DistributedKFAC(config=kfac_tpu.KFACPreconditioner(
            registry=reg, compile_watch=True))

    for label, build in (('dense', dense), ('distributed', distributed)):
        engine = build()
        step = engine.watched('step')
        state = engine.init()
        for _ in range(3):  # first call compiles; the rest must not
            state, _ = step(state, grads, stats)
        jax.block_until_ready(state)
        watch = engine.compile_watcher()
        out['recompiles_after_warmup'][label] = watch.recompile_count()
        report = engine.compiled_memory_report()
        for name, snap in report.items():
            event = watch.events_for(name)[-1]
            out['entries'][name] = {
                'lowering_s': round(event['lowering_s'], 3),
                'compile_s': round(event['compile_s'], 3),
                'compiles': watch.compile_count(name),
                'hbm_bytes': snap['hbm_bytes'],
            }

    after = counters.snapshot()
    out['persistent_cache'] = {
        'hits': (after['persistent_cache_hits']
                 - before['persistent_cache_hits']),
        'misses': (after['persistent_cache_misses']
                   - before['persistent_cache_misses']),
        'dir': after['persistent_cache_dir'],
        'counters_installed': counters.installed,
    }
    return out


_SERVING_SHAPES = (8, 32, 64)


def _serving_probe() -> dict:
    """Posterior serving probe (docs/SERVING.md): request latency
    p50/p95 and requests/s at three batch shapes through BOTH compiled
    paths (MC predictive and closed-form last-layer variance), the AOT
    warmup record, and the steady-state recompile count, which must be
    0: every batch shape lands in a pre-compiled padding bucket.

    Latencies come from the warmed-up engine so the numbers describe a
    replica in steady state, not one paying first-compile costs.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import kfac_tpu
    from kfac_tpu import health as health_lib
    from kfac_tpu.models import MLP
    from kfac_tpu.serving import ServingConfig, ServingEngine

    # toy classifier: one factor update is all the export needs
    m = MLP(features=(8,), num_classes=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 4)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, health=health_lib.HealthConfig(warn=False))

    def loss_fn(p, b):
        xx, yy = b
        logits = m.apply({'params': p}, xx)
        onehot = jax.nn.one_hot(yy, 4)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    cap = kfac_tpu.CurvatureCapture(reg)
    _, grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))
    state = kfac.update_factors(kfac.init(), stats)

    post_dir = tempfile.mkdtemp(prefix='serving_probe_post_')
    kfac_tpu.export_posterior(
        kfac, state, params, post_dir,
        config=kfac_tpu.laplace.LaplaceConfig(mode='last_layer'),
        overwrite=True,
    )
    post = kfac_tpu.load_posterior(post_dir)

    def apply_fn(p, xx):
        return m.apply({'params': p}, xx)

    def phi_fn(p, xx):
        h = xx.reshape(xx.shape[0], -1)
        return jax.nn.relu(h @ p['dense0']['kernel'] + p['dense0']['bias'])

    cfg = ServingConfig(
        bucket_granularity=8, max_batch=64, n_samples=8,
        warmup_batches=_SERVING_SHAPES,
    )

    eng = ServingEngine(post, apply_fn, phi_fn=phi_fn, config=cfg)
    out: dict = {
        'warmup': eng.warmup(x_spec=x[:1], key=jax.random.PRNGKey(0)),
        'shapes': {},
    }

    paths = ['mc']
    if eng.closed_form_available:
        paths.append('closed_form')
    for b in _SERVING_SHAPES:
        xb = x[:b]
        for path in paths:
            lats = []
            for i in range(20):
                res = eng.serve(
                    xb, key=jax.random.PRNGKey(100 + i), path=path)
                lats.append(res.latency_s)
            p50 = float(np.percentile(lats, 50)) * 1e3
            p95 = float(np.percentile(lats, 95)) * 1e3
            out['shapes'][f'{path}.b{b}'] = {
                'batch': b,
                'p50_ms': round(p50, 3),
                'p95_ms': round(p95, 3),
                'requests_per_sec': round(b / (p50 / 1e3), 1),
            }
    out['recompiles_after_warmup'] = eng.recompiles_after_warmup()

    # flat headline keys at the biggest shape — the DEFAULT_SENTINEL_KEYS
    # surface the perf sentinel gates (latency lower-is-better)
    big = _SERVING_SHAPES[-1]
    for path, tag in (('mc', 'mc'), ('closed_form', 'cf')):
        row = out['shapes'].get(f'{path}.b{big}')
        if row is None:
            continue
        out[f'serving_{tag}_p50_ms'] = row['p50_ms']
        out[f'serving_{tag}_p95_ms'] = row['p95_ms']
        out[f'serving_{tag}_requests_per_sec'] = row['requests_per_sec']
    eng.close()
    return out


def _obs_probe(result, out_path, reg, run, loss, opt, params, data):
    """Observability probe: per-step metrics JSONL, metrics-on overhead vs
    a metrics-off loop timed back-to-back, and a phase-level step-time
    breakdown.

    Exercises the telemetry spine (docs/OBSERVABILITY.md) on the same
    model the stage just timed. The overhead A/B re-times the metrics-off
    loop here rather than reusing the stage's earlier K-FAC figure —
    minutes-apart measurements on a shared host drift by more than the
    overhead being measured. The caller guards it: a probe failure is
    recorded (``obs_probe_error``) but never kills the stage's headline.
    """
    import jax
    import optax

    import kfac_tpu
    from kfac_tpu.observability import sinks

    def build(metrics):
        kfac = kfac_tpu.KFACPreconditioner(
            registry=reg, damping=0.003, lr=0.1,
            factor_update_steps=10, inv_update_steps=100,
            metrics=metrics,
        )

        @jax.jit
        def cap_step(params, kstate, opt_state, batch):
            (l, _), grads, stats = run(params, batch)
            kstate, pgrads = kfac.step(kstate, grads, stats)
            updates, opt_state = opt.update(pgrads, opt_state, params)
            return optax.apply_updates(params, updates), kstate, opt_state, l

        @jax.jit
        def plain_step(params, kstate, opt_state, batch):
            l, grads = jax.value_and_grad(loss)(params, batch)
            kstate, pgrads = kfac.step(kstate, grads, None)
            updates, opt_state = opt.update(pgrads, opt_state, params)
            return optax.apply_updates(params, updates), kstate, opt_state, l

        return kfac, cap_step, plain_step

    kfac_m, cap_step, plain_step = build(True)

    # 12-step eager loop draining the in-jit metrics to JSONL per step —
    # the documented training-loop integration, verbatim
    collector = kfac_tpu.MetricsCollector()
    mpath = out_path + '.metrics.jsonl'
    args = (params, kfac_m.init(), opt.init(params), data)
    out = None
    with sinks.JSONLWriter(mpath, append=False) as w:
        for i in range(12):
            fn = cap_step if i % 10 == 0 else plain_step
            out = fn(*args)
            args = (out[0], out[1], out[2], args[3])
            w.write(collector.drain(out[1]))
    jax.block_until_ready(out)
    result['metrics_jsonl'] = mpath
    # one compiled program per dispatch variant; anything above 2 means
    # the metrics state retriggered compilation across steps
    result['metrics_compilations'] = (
        cap_step._cache_size() + plain_step._cache_size())

    # metrics on/off A/B, alternating rounds back-to-back so shared-host
    # load drift hits both sides equally (acceptance bar: < 5%)
    kfac_o, cap_o, plain_o = build(None)
    t_on = t_off = float('inf')
    for _ in range(2):
        t_off = min(t_off, _timeit(
            lambda i: cap_o if i % 10 == 0 else plain_o,
            (params, kfac_o.init(), opt.init(params), data),
            warmup=2, iters=40,
        ))
        t_on = min(t_on, _timeit(
            lambda i: cap_step if i % 10 == 0 else plain_step,
            (params, kfac_m.init(), opt.init(params), data),
            warmup=2, iters=40,
        ))
    result['metrics_overhead_pct'] = round((t_on / t_off - 1.0) * 100.0, 2)

    # phase-level breakdown: each engine phase jitted alone and timed to
    # completion — where a step's milliseconds actually go
    phases: dict = {}

    def _phase(name, fn, *a, n=10):
        o = fn(*a)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(n):
            o = fn(*a)
        jax.block_until_ready(o)
        phases[name] = round((time.perf_counter() - t0) / n * 1e3, 3)
        return o

    kstate = kfac_m.init()
    jrun = jax.jit(run)
    (_, _), grads, stats = jrun(params, data)
    _phase('capture_ms', jrun, params, data)
    kstate = _phase('factors_ms', jax.jit(kfac_m.update_factors),
                    kstate, stats)
    kstate = _phase('inverses_ms', jax.jit(kfac_m.update_inverses), kstate)
    _phase('precondition_ms', jax.jit(kfac_m.precondition), kstate, grads)
    result['step_breakdown_ms'] = phases

    # async refresh spike probe, after the headline breakdown is safe on
    # disk — a failure here surfaces as obs_probe_error without losing it
    _atomic_write(out_path, result)
    _log('  async refresh spike probe (sync vs sliced, d=512)')
    phases.update(_async_spike_probe())
    result['step_breakdown_ms'] = phases

    # compressed-wire + offload probe, same guarded-by-caller contract
    _atomic_write(out_path, result)
    _log('  compression/offload probe (int8 vs f32 wire, cold factors)')
    result['compression_probe'] = _compression_probe()

    # self-driving fleet probe: drift retune + live migration downtime
    _atomic_write(out_path, result)
    _log('  fleet probe (model-only retune + migration downtime)')
    result['fleet_probe'] = _fleet_probe()

    # 3D-planner schedule table: measured-vs-predicted bubble fractions
    _atomic_write(out_path, result)
    _log('  pipeline probe (bubble table: measured vs simulated)')
    result['pipeline_probe'] = _pipeline_probe()

    # fused step-path kernel A/B: fused vs unfused, same process
    _atomic_write(out_path, result)
    _log('  fused kernel probe (cov+EMA / kl-clip, fused vs unfused)')
    result['fused_kernel_probe'] = _fused_kernel_probe()

    # chaos-harness SLOs: committed storm artifact, read-only
    _atomic_write(out_path, result)
    _log('  chaos probe (preemption-storm recovery SLOs, committed artifact)')
    result['chaos_probe'] = _chaos_probe()

    # compile & memory truth: recompile attribution + XLA memory + cache
    _atomic_write(out_path, result)
    _log('  compile probe (recompile attribution + XLA memory + cache hit/miss)')
    result['compile_probe'] = _compile_probe(reg, run, params, data)

    # posterior serving tier: bucketed latency + AOT warmup record
    _atomic_write(out_path, result)
    _log('  serving probe (p50/p95 both paths, AOT warmup)')
    probe = _serving_probe()
    result['serving_probe'] = probe
    # lift the sentinel-gated flat keys (DEFAULT_SENTINEL_KEYS) so the
    # ledger probe can diff them against the committed baseline
    for k in ('serving_mc_p50_ms', 'serving_mc_p95_ms',
              'serving_cf_p50_ms', 'serving_cf_p95_ms',
              'serving_mc_requests_per_sec', 'serving_cf_requests_per_sec'):
        if k in probe:
            result[k] = probe[k]


# ---------------------------------------------------------------------------
# LM measurement stage (runs in its own subprocess: `bench.py --stage lm`)
# ---------------------------------------------------------------------------

_LM_CONFIGS = {
    # smallest-first: prove a K-FAC step compiles+executes on the chip at
    # minimum compile cost before paying for the flagship
    'tiny': dict(batch=4, seq=128, d_model=128, layers=2, vocab=512),
    'flagship': dict(batch=16, seq=512, d_model=512, layers=6, vocab=8192),
    # manual-only configs (not in the orchestrator plan; run via
    # `bench.py --stage lm --config <name>`): 'large' amortizes dispatch
    # over bigger matmuls; 'longctx' puts s_k=2048 attention in range of
    # the flash kernel's dense dispatch for an end-to-end A/B.
    'large': dict(batch=8, seq=1024, d_model=1024, layers=8, vocab=8192),
    'longctx': dict(batch=4, seq=2048, d_model=512, layers=6, vocab=8192),
}


def _claim_backend(result: dict, out_path: str, tag: str):
    """First backend touch: place the compile cache, record the platform
    fields, and refuse to measure anywhere but on a TPU unless the caller
    pinned the host itself (``JAX_PLATFORMS=cpu``, the tests' smoke)."""
    import jax

    from kfac_tpu.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    result['platform'] = dev.platform
    result['device_kind'] = getattr(dev, 'device_kind', '')
    _log(f'{tag}: backend up: {dev.platform} {result["device_kind"]}')
    if dev.platform != 'tpu' and os.environ.get('JAX_PLATFORMS') != 'cpu':
        result['error'] = (
            f'no TPU: jax.devices()[0] is {dev.platform!r} and the caller '
            'did not pin JAX_PLATFORMS=cpu'
        )
        _atomic_write(out_path, result)
        sys.exit(f'{tag}: {result["error"]}')
    _atomic_write(out_path, result)
    return dev


def run_lm_stage(config_name: str, out_path: str) -> None:
    """Measure SGD vs K-FAC LM throughput at one config; write phase-by-
    phase partials to ``out_path`` so a watchdog kill preserves everything
    measured so far."""
    cfg = _LM_CONFIGS[config_name]
    result: dict = {'stage': f'lm_{config_name}', 'run_id': _RUN_ID}
    tp = _active_plan()
    if tp is not None:
        result['tuned_plan'] = tp
    dev = _claim_backend(result, out_path, f'lm_{config_name}')
    on_tpu = dev.platform != 'cpu'

    import jax
    import jax.numpy as jnp
    import optax

    import kfac_tpu
    from kfac_tpu.models import TransformerLM, lm_loss

    batch, seq = cfg['batch'], cfg['seq']
    d_model, layers, vocab = cfg['d_model'], cfg['layers'], cfg['vocab']
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    result['model_config'] = (
        f'lm_L{layers}_d{d_model}_s{seq}_b{batch}_v{vocab}'
    )

    # 4 heads -> head_dim = d_model/4: lane-aligned at the flagship's d512
    # for the (gated) Pallas flash-attention kernel
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, num_heads=4, num_layers=layers,
        max_len=seq, dtype=dtype,
    )
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch, seq), 0, vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(1), tokens)['params']
    loss = lm_loss(model)

    # The output head is excluded from K-FAC, as in the reference's LM
    # example (its decoder layer is skipped by default,
    # examples/torch_language_model.py:163-168): the head's G factor is
    # vocab x vocab — an 8192^2 eigendecomposition that costs more than the
    # entire rest of the step and is why second-order methods skip LM heads.
    # Its gradient still flows (SGD-updated), so model FLOPs are unchanged.
    reg = kfac_tpu.register_model(model, tokens, skip_layers=['lm_head'])
    # compute_method is left unset: the library's platform-aware default
    # (kfac_tpu.default_compute_method) picks INVERSE+Newton-Schulz on TPU
    # (eigh lowers to a sequential panel algorithm there; the EIGEN step was
    # measured never to finish compiling inside a 20-minute budget on v5e)
    # and EIGEN — the reference's default — on the CPU smoke config.
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, damping=0.003, lr=0.1,
        factor_update_steps=10, inv_update_steps=100,
    )
    cap = kfac_tpu.CurvatureCapture(reg)
    run = cap.value_stats_and_grad(loss)
    opt = optax.sgd(0.1, momentum=0.9)

    @jax.jit
    def kfac_step_capture(params, kstate, opt_state, batch):
        (l, _), grads, stats = run(params, batch)
        kstate, pgrads = kfac.step(kstate, grads, stats)
        updates, opt_state = opt.update(pgrads, opt_state, params)
        return optax.apply_updates(params, updates), kstate, opt_state, l

    @jax.jit
    def kfac_step_plain(params, kstate, opt_state, batch):
        l, grads = jax.value_and_grad(loss)(params, batch)
        kstate, pgrads = kfac.step(kstate, grads, None)
        updates, opt_state = opt.update(pgrads, opt_state, params)
        return optax.apply_updates(params, updates), kstate, opt_state, l

    @jax.jit
    def sgd_step(params, _unused, opt_state, batch):
        l, grads = jax.value_and_grad(loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), _unused, opt_state, l

    data = (tokens, targets)
    _log(f'lm_{config_name}: timing SGD step (compile + 100 iters)')
    t_sgd = _timeit(lambda i: sgd_step, (params, 0, opt.init(params), data))
    result['sgd_tokens_per_sec'] = round(batch * seq / t_sgd, 1)
    _atomic_write(out_path, result)
    _log(f'lm_{config_name}: sgd {t_sgd * 1e3:.1f} ms/step; '
         'timing K-FAC eager steps')
    t_kfac = _timeit(
        lambda i: kfac_step_capture if i % 10 == 0 else kfac_step_plain,
        (params, kfac.init(), opt.init(params), data),
    )
    result['eager_tokens_per_sec'] = round(batch * seq / t_kfac, 1)
    _atomic_write(out_path, result)
    _log(f'lm_{config_name}: kfac eager {t_kfac * 1e3:.1f} ms/step; '
         'timing scan loop')

    # Fully-compiled loop: 100 steps as one lax.scan with device-side
    # cadence (Trainer.scan_steps) — no per-step host dispatch. The scan
    # window spans the full inverse cadence, like _timeit's.
    from kfac_tpu import training as training_lib

    trainer = training_lib.Trainer(
        loss_fn=lambda p, ms, b: (loss(p, b), ms), optimizer=opt, kfac=kfac
    )
    scan_steps_n = 100
    scan_batches = (
        jnp.broadcast_to(tokens, (scan_steps_n,) + tokens.shape),
        jnp.broadcast_to(targets, (scan_steps_n,) + targets.shape),
    )
    sstate = trainer.init(params)
    sstate, _ = trainer.scan_steps(sstate, scan_batches)  # compile + warm
    jax.block_until_ready(sstate.params)
    t0 = time.perf_counter()
    sstate, scan_losses = trainer.scan_steps(sstate, scan_batches)
    jax.block_until_ready(scan_losses)
    t_scan = (time.perf_counter() - t0) / scan_steps_n
    _log(f'lm_{config_name}: scan {t_scan * 1e3:.1f} ms/step; finalizing')

    # Model FLOPs (fwd+bwd = 3x fwd): 6*N per token for the parameter
    # matmuls plus 12*L*d*S per token for self-attention scores/values.
    # Embedding/positional tables are gathers/adds, not matmuls — they carry
    # no 2*p FLOPs per token, so they are excluded from the matmul count
    # (the lm_head output projection is a real matmul and stays in).
    n_params = 0
    n_matmul_params = 0
    for path, p in jax.tree_util.tree_flatten_with_path(params)[0]:
        size = int(p.size)
        n_params += size
        if not any('embed' in str(k).lower() for k in path):
            n_matmul_params += size
    flops_per_step = batch * seq * (
        6 * n_matmul_params + 12 * layers * d_model * seq
    )
    peak = _peak_flops(result['device_kind']) if on_tpu else None

    # headline: the faster K-FAC stepping mode (eager dispatch vs compiled
    # scan loop); both are recorded
    t_best = min(t_kfac, t_scan)
    tokens_per_sec = batch * seq / t_best
    result.update(
        value=round(tokens_per_sec, 1),
        vs_baseline=round(t_sgd / t_best, 4),
        scan_tokens_per_sec=round(batch * seq / t_scan, 1),
        n_params=n_params,
        mfu=(round(flops_per_step / t_best / peak, 4) if peak else None),
        sgd_mfu=(round(flops_per_step / t_sgd / peak, 4) if peak else None),
        ok=True,
    )
    _atomic_write(out_path, result)

    _log(f'lm_{config_name}: observability probe')
    try:
        _obs_probe(result, out_path, reg, run, loss, opt, params, data)
    except Exception as e:  # never let telemetry kill the headline
        result['obs_probe_error'] = f'{type(e).__name__}: {e}'
    _atomic_write(out_path, result)


# ---------------------------------------------------------------------------
# ResNet measurement stage (manual-only: `bench.py --stage resnet --config X`)
# ---------------------------------------------------------------------------

_RESNET_CONFIGS = {
    # BASELINE.json's vision configs (the reference's CIFAR/ImageNet
    # entrypoints, examples/torch_cifar10_resnet.py and
    # torch_imagenet_resnet.py), shape-faithful synthetic batches
    'resnet32_cifar': dict(arch='resnet32', batch=256, hw=32, classes=10),
    'resnet50_imagenet': dict(arch='resnet50', batch=32, hw=224, classes=1000),
}


def run_resnet_stage(config_name: str, out_path: str) -> None:
    """SGD vs K-FAC ResNet step throughput at the reference's ImageNet
    cadence (factors every 10 steps, inverses every 100). Phase-by-phase
    partials go to ``out_path``; MFU uses XLA's own cost model for the
    conv FLOPs (the 6N rule only covers matmul parameters)."""
    cfg = _RESNET_CONFIGS[config_name]
    result: dict = {
        'stage': config_name, 'run_id': _RUN_ID,
        'model_config': f"{cfg['arch']}_b{cfg['batch']}_{cfg['hw']}px",
    }
    tp = _active_plan()
    if tp is not None:
        result['tuned_plan'] = tp
    dev = _claim_backend(result, out_path, config_name)
    on_tpu = dev.platform != 'cpu'

    import jax
    import jax.numpy as jnp
    import optax

    import kfac_tpu
    from kfac_tpu import training as training_lib
    from kfac_tpu.models import resnet as resnet_lib

    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    batch, hw, classes = cfg['batch'], cfg['hw'], cfg['classes']
    model = getattr(resnet_lib, cfg['arch'])(num_classes=classes, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, hw, hw, 3), dtype)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, classes)
    variables = model.init(jax.random.PRNGKey(2), x, train=True)
    registry = kfac_tpu.register_model(model, x, train=False)
    result['n_kfac_layers'] = len(registry)

    def loss_fn(params, model_state, b):
        xb, yb = b
        logits, updates = model.apply(
            {'params': params, 'batch_stats': model_state}, xb, train=True,
            mutable=['batch_stats'],
        )
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=1).mean()
        return nll, updates['batch_stats']

    opt = optax.sgd(0.1, momentum=0.9)
    data = (x, y)

    def time_trainer(trainer, warmup: int = 5, iters: int = 100) -> float:
        # Warmup compiles both cadence variants (step 0 captures+inverts);
        # the measured window (steps 5..104) then spans 10 factor captures
        # and the step-100 inverse — the full cadence at true proportion,
        # matching _timeit's accounting for the LM stages.
        state = trainer.init(variables['params'], variables['batch_stats'])
        loss = None
        for _ in range(warmup):
            state, loss = trainer.step(state, data)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = trainer.step(state, data)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / iters

    sgd_tr = training_lib.Trainer(loss_fn=loss_fn, optimizer=opt)
    _log(f'{config_name}: timing SGD (compile + 100 iters)')
    t_sgd = time_trainer(sgd_tr)
    result['sgd_images_per_sec'] = round(batch / t_sgd, 1)
    try:
        state0 = sgd_tr.init(variables['params'], variables['batch_stats'])
        ca = sgd_tr._jit_no_stats.lower(state0, data).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        result['step_gflops_xla'] = round(float(ca['flops']) / 1e9, 2)
    except Exception as exc:  # cost-model availability varies by backend
        _log(f'{config_name}: cost_analysis unavailable ({exc})')
    _atomic_write(out_path, result)
    _log(f'{config_name}: sgd {t_sgd * 1e3:.1f} ms/step; timing K-FAC '
         '(factors/10, inverses/100)')

    kfac = kfac_tpu.KFACPreconditioner(
        registry=registry, damping=0.003, lr=0.1,
        factor_update_steps=10, inv_update_steps=100,
    )
    kfac_tr = training_lib.Trainer(loss_fn=loss_fn, optimizer=opt, kfac=kfac)
    t_kfac = time_trainer(kfac_tr)
    peak = _peak_flops(result['device_kind']) if on_tpu else None
    gflops = result.get('step_gflops_xla')
    result.update(
        kfac_images_per_sec=round(batch / t_kfac, 1),
        value=round(batch / t_kfac, 1),
        vs_baseline=round(t_sgd / t_kfac, 4),
        mfu=(round(gflops * 1e9 / t_kfac / peak, 4)
             if peak and gflops else None),
        sgd_mfu=(round(gflops * 1e9 / t_sgd / peak, 4)
                 if peak and gflops else None),
        ok=True,
    )
    _atomic_write(out_path, result)
    _log(f'{config_name}: kfac {t_kfac * 1e3:.1f} ms/step '
         f'({result["vs_baseline"]:.3f}x SGD)')


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------


def _run_stage(
    name: str,
    argv: list[str],
    env_extra: dict[str, str],
    budget_s: float,
    stdout_path: str | None = None,
) -> str:
    """Run one stage as a subprocess under a SIGTERM-grace watchdog.

    stderr is inherited (the progress trail interleaves into this
    process's log); stdout optionally captured to ``stdout_path`` (the
    microbench stages emit JSON lines there). Returns
    'ok' | 'timeout' | 'rc=N'.
    """
    _log(f'stage {name}: starting (budget {budget_s:.0f}s)')
    stdout_f = open(stdout_path, 'w') if stdout_path else subprocess.DEVNULL
    try:
        proc = subprocess.Popen(
            argv, stdout=stdout_f, env={**os.environ, **env_extra}
        )
        status = 'ok'
        try:
            rc = proc.wait(timeout=budget_s)
            if rc != 0:
                status = f'rc={rc}'
        except subprocess.TimeoutExpired:
            status = 'timeout'
            # SIGTERM first, so the stage can flush its partial record
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    finally:
        if stdout_path:
            stdout_f.close()
    _log(f'stage {name}: {status}')
    return status


def _active_plan() -> dict | None:
    """Identity of the tuned layout plan driving this run, if any.

    ``KFAC_TUNE_PLAN=/path/to/plan.json`` (see docs/AUTOTUNE.md) makes
    bench runs self-describing: the record carries the plan's knobs and
    fingerprint so A/B throughput numbers can be attributed to a layout.
    """
    path = os.environ.get('KFAC_TUNE_PLAN')
    if not path:
        return None
    try:
        with open(path) as f:
            plan = json.load(f)
        return {
            'path': path,
            'schema': plan.get('schema'),
            'knobs': plan.get('knobs'),
            'fingerprint': plan.get('fingerprint'),
        }
    except Exception as exc:  # noqa: BLE001 - a bad plan must not kill a run
        return {'path': path, 'error': f'{type(exc).__name__}: {exc}'}


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except Exception:
        return {}


def _read_jsonl(path: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        pass
    except OSError:
        pass
    return out


# measurement provenance stamped into every round record (and echoed by
# the microbench stages' own header lines). Hardcoded rather than
# imported from tools/tpu_microbench — importing it pulls in jax at
# module scope, which the orchestrator must not do before stages pin
# their own JAX_PLATFORMS; tests/test_measurement.py pins this block to
# tpu_microbench.HARNESS_VERSION / the default dispatch mode.
_MEASUREMENT = {'harness_version': 2, 'dispatch_mode': 'fori_loop'}


_HEADLINE_KEYS = (
    'platform', 'device_kind', 'model_config',
    'sgd_tokens_per_sec', 'eager_tokens_per_sec', 'scan_tokens_per_sec',
    'value', 'vs_baseline', 'n_params', 'mfu', 'sgd_mfu',
    # resnet-stage fields (never lifted to the top level: the headline
    # pick stays lm_flagship/lm_tiny)
    'sgd_images_per_sec', 'kfac_images_per_sec', 'n_kfac_layers',
    'step_gflops_xla',
    # observability-probe fields (docs/OBSERVABILITY.md)
    'metrics_jsonl', 'metrics_compilations', 'metrics_overhead_pct',
    'step_breakdown_ms', 'obs_probe_error',
    # compressed-wire + cold-factor-offload probe (docs/ARCHITECTURE.md
    # "Compression & offload")
    'compression_probe',
    # 3D-planner bubble table: measured vs simulated schedule fractions
    # under the one-dispatch harness provenance (docs/AUTOTUNE.md)
    'pipeline_probe',
    # fused step-path kernel A/B: per-family fused-vs-unfused p50 + the
    # traced device attribution (docs/ARCHITECTURE.md "Fused step-path
    # kernels")
    'fused_kernel_probe',
    # chaos-harness recovery SLOs: per-fault-class downtime / recovery
    # wall-clock / fallback depth / divergence from the committed storm
    # artifact (docs/ROBUSTNESS.md "Chaos harness")
    'chaos_probe',
    # compile & memory truth: per-entry compile wall-clock + XLA-reported
    # HBM bytes, recompiles-after-warmup (must be 0 on both engines), and
    # persistent compile-cache hit/miss deltas (docs/OBSERVABILITY.md
    # "Compile & memory truth")
    'compile_probe',
    # posterior serving tier: per-bucket p50/p95 + req/s on both paths,
    # the AOT warmup record, recompiles-after-warmup (must be 0),
    # plus the flat sentinel-gated latency/throughput keys
    # (docs/SERVING.md)
    'serving_probe',
    'serving_mc_p50_ms', 'serving_mc_p95_ms',
    'serving_cf_p50_ms', 'serving_cf_p95_ms',
    'serving_mc_requests_per_sec', 'serving_cf_requests_per_sec',
    # perf-regression sentinel verdict: this round's headline keys vs the
    # committed provenance-aware baseline bench_runs/LEDGER.json
    # (docs/OBSERVABILITY.md "Run ledger")
    'ledger_probe',
    # active tuned layout plan, when KFAC_TUNE_PLAN is set (docs/AUTOTUNE.md)
    'tuned_plan',
)


def _orchestrate(result: dict) -> None:
    _mark_run_started()
    # the orchestrator never touches JAX: the platform is whatever the
    # first stage's process reads from jax.devices(), and only a caller
    # who pinned the host gets the CPU smoke
    pinned_cpu = os.environ.get('JAX_PLATFORMS') == 'cpu'
    tp = _active_plan()
    if tp is not None:
        result['tuned_plan'] = tp
    result['measurement'] = dict(_MEASUREMENT)
    _persist(result)

    deadline_ts = _T0 + float(os.environ.get('BENCH_DEADLINE_S', '1350'))

    def remaining() -> float:
        return deadline_ts - time.time()

    here = os.path.dirname(os.path.abspath(__file__))
    run_dir = os.path.join(
        os.environ.get('BENCH_RUNS_DIR', 'bench_runs'), f'stages_{_RUN_ID}'
    )
    os.makedirs(run_dir, exist_ok=True)
    # every stage places the persistent compile cache itself
    # (kfac_tpu.utils.compile_cache), so stages and runs share it
    cache_env = {
        'BENCH_RUN_ID': _RUN_ID,
        # pin the gate OFF for every stage that isn't explicitly measuring
        # the kernels — an operator's exported KFAC_TPU_PALLAS=1 must not
        # silently put unvalidated kernels on the 'default path' headline
        'KFAC_TPU_PALLAS': '0',
    }
    stages: dict[str, dict] = {}
    result['stages'] = stages

    def stage_argv(stage: str, config: str, out: str) -> list[str]:
        return [
            sys.executable, os.path.join(here, 'bench.py'),
            '--stage', stage, '--config', config, '--out', out,
        ]

    def micro_argv(*flags: str) -> list[str]:
        return [
            sys.executable, os.path.join(here, 'tools', 'tpu_microbench.py'),
            '--sizes', '512', '1024', '--iters', '8', '--rows', '8192',
            *flags,
        ]

    def acc_stage(env: dict[str, str]) -> None:
        """Steps-to-target vs SGD on digits (the metric BASELINE.json
        names, in the driver-recorded line itself). Skipped when the
        remaining budget is tight."""
        budget = min(300.0, remaining() - 30.0)
        if budget < 60.0:
            stages['acc'] = {'status': 'skipped_no_budget'}
            return
        out = os.path.join(run_dir, 'acc.jsonl')
        status = _run_stage(
            'acc',
            [
                sys.executable,
                os.path.join(here, 'tools', 'bench_accuracy.py'),
                '--tasks', 'digits_mlp',
                '--out', os.path.join(run_dir, 'acc.md'),
            ],
            env, budget, stdout_path=out,
        )
        rows = [r for r in _read_jsonl(out) if 'step_ratio' in r]
        entry: dict = {'status': status}
        if rows:
            entry.update(rows[-1])
            result['acc_task'] = rows[-1].get('task')
            result['acc_step_ratio'] = rows[-1].get('step_ratio')
            result['acc_time_ratio'] = rows[-1].get('time_ratio')
        stages['acc'] = entry

    if pinned_cpu:
        # CPU smoke: one tiny stage; the stage inherits the caller's pin
        out = os.path.join(run_dir, 'lm_tiny.json')
        env = {**cache_env}
        status = _run_stage(
            'lm_tiny', stage_argv('lm', 'tiny', out), env,
            max(120.0, min(700.0, remaining() - 120.0)),
        )
        stage = _read_json(out)
        stages['lm_tiny'] = {'status': status, **{
            k: stage[k] for k in _HEADLINE_KEYS if k in stage
        }}
        for k in _HEADLINE_KEYS:
            if k in stage:
                result[k] = stage[k]
        _persist(result)
        acc_stage(env)
        result['ledger_probe'] = _ledger_probe(result)
        _persist(result, partial=not stage.get('ok', False))
        return

    # --- TPU plan, smallest-first ----------------------------------------
    plan = [
        # (name, argv_builder, env, cap_s, reserve_for_later_s)
        ('micro_safe', micro_argv('--no-pallas'), {**cache_env}, 360.0, 420.0),
        ('lm_tiny', None, {**cache_env}, 300.0, 300.0),
        ('lm_flagship', None, {**cache_env}, 600.0, 90.0),
        ('micro_pallas', micro_argv('--pallas-only'),
         {**cache_env, 'KFAC_TPU_PALLAS': '1'}, 240.0, 60.0),
        ('lm_flagship_pallas', None,
         {**cache_env, 'KFAC_TPU_PALLAS': '1'}, 600.0, 30.0),
        # opportunistic: only run on leftover budget (reserve keeps the
        # acc stage's slice). lm_large amortizes dispatch over bigger
        # matmuls (its d1024 K-FAC compile is cold-cache slow — fine to
        # lose to the skip guard); resnet32 is the reference's CIFAR
        # vision config.
        ('lm_large', None, {**cache_env}, 420.0, 330.0),
        # reserve covers acc's 60s floor PLUS the kill-path overshoot
        # (up to 30s SIGTERM grace + 10s settle beyond the budget)
        ('resnet32_cifar', None, {**cache_env}, 420.0, 150.0),
    ]
    for name, argv, env, cap, reserve in plan:
        budget = min(cap, remaining() - reserve)
        if budget < 60.0:
            stages[name] = {'status': 'skipped_no_budget'}
            _log(f'stage {name}: skipped (remaining {remaining():.0f}s)')
            continue
        if name == 'lm_flagship_pallas':
            micro = stages.get('micro_pallas', {})
            if micro.get('status') != 'ok' or micro.get('pallas_errors'):
                stages[name] = {'status': 'skipped_kernels_unvalidated'}
                _log(f'stage {name}: skipped (micro_pallas not clean)')
                continue
        if name.startswith('lm_') or name in _RESNET_CONFIGS:
            out = os.path.join(run_dir, f'{name}.json')
            if name in _RESNET_CONFIGS:
                sargv = stage_argv('resnet', name, out)
            else:
                config = {'lm_tiny': 'tiny', 'lm_large': 'large'}.get(
                    name, 'flagship'
                )
                sargv = stage_argv('lm', config, out)
            status = _run_stage(name, sargv, env, budget)
            stage = _read_json(out)
            stages[name] = {'status': status, **{
                k: stage[k] for k in _HEADLINE_KEYS if k in stage
            }}
            if 'error' in stage:
                stages[name]['error'] = stage['error']
            if stage.get('platform') not in (None, 'tpu'):
                raise RuntimeError(
                    f'stage {name} found platform {stage["platform"]!r}, '
                    'not a TPU: nothing is published from it'
                )
        else:
            out = os.path.join(run_dir, f'{name}.jsonl')
            status = _run_stage(name, argv, env, budget, stdout_path=out)
            ops = _read_jsonl(out)
            entry: dict = {'status': status, 'ops': ops}
            # a kernel miscompiling on real hardware shows up as wrong
            # NUMBERS, not an exception — gate on the reported oracle
            # error too (both comparisons accumulate in fp32, so the
            # honest bound is small even for bf16 inputs)
            errs = [
                o['op'] for o in ops
                if o.get('error')
                or (isinstance(o.get('max_err'), (int, float))
                    and o['max_err'] > 0.05)
            ]
            if errs:
                entry['pallas_errors'] = errs
            # measurement provenance: which harness produced these
            # numbers, and the per-family latency-floor verdicts the
            # harness appended (docs/OBSERVABILITY.md "Measurement
            # truth") — a contaminated family means the sweep's absolute
            # numbers are dispatch floor, not op time
            header = next(
                (o for o in ops if 'platform' in o and 'op' not in o), {})
            if header.get('platform') not in (None, 'tpu'):
                raise RuntimeError(
                    f'stage {name} found platform {header["platform"]!r}, '
                    'not a TPU: nothing is published from it'
                )
            entry['measurement'] = {
                'harness_version': header.get('harness_version', 1),
                'dispatch_mode': header.get('dispatch_mode', 'legacy'),
                'dispatches': sorted({
                    o['dispatches'] for o in ops
                    if isinstance(o.get('dispatches'), int)
                }),
            }
            floors = {
                str(o['op']).split('/', 1)[1]: {
                    k: o[k]
                    for k in ('contaminated', 'spread', 'expected_ratio',
                              'floor_ms', 'n')
                    if k in o
                }
                for o in ops if str(o.get('op', '')).startswith('floor/')
            }
            if floors:
                entry['floor_verdicts'] = floors
                bad = sorted(
                    f for f, v in floors.items() if v.get('contaminated'))
                if bad:
                    entry['floor_contaminated'] = bad
            stages[name] = entry
        _persist(result)

    # headline: the default-path flagship if it produced numbers, else tiny
    for pick in ('lm_flagship', 'lm_tiny'):
        stage = stages.get(pick, {})
        if 'value' in stage or 'sgd_tokens_per_sec' in stage:
            for k in _HEADLINE_KEYS:
                if k in stage:
                    result[k] = stage[k]
            result['headline_stage'] = pick
            break
    # the kernel-enabled flagship rides along as a comparison, never the
    # headline (the headline must be the default path)
    pallas = stages.get('lm_flagship_pallas', {})
    if 'value' in pallas:
        result['pallas_tokens_per_sec'] = pallas['value']
        result['pallas_mfu'] = pallas.get('mfu')
    # opportunistic stages ride along as summary fields, never the headline
    large = stages.get('lm_large', {})
    if large.get('mfu') is not None:
        result['large_mfu'] = large['mfu']
        result['large_sgd_mfu'] = large.get('sgd_mfu')
        result['large_tokens_per_sec'] = large.get('value')
    r32 = stages.get('resnet32_cifar', {})
    if 'vs_baseline' in r32:
        result['resnet32_vs_baseline'] = r32['vs_baseline']
        result['resnet32_kfac_images_per_sec'] = r32.get(
            'kfac_images_per_sec'
        )
    acc_stage({**cache_env})
    result['ledger_probe'] = _ledger_probe(result)
    done = stages.get(result.get('headline_stage', ''), {}).get('status')
    _persist(result, partial=done != 'ok')


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--stage', choices=['lm', 'resnet'])
    parser.add_argument(
        '--config', choices=sorted(_LM_CONFIGS) + sorted(_RESNET_CONFIGS)
    )
    parser.add_argument('--out')
    args = parser.parse_args()

    if args.config and not args.stage:
        parser.error('--config requires --stage (lm or resnet)')
    if args.stage:
        if not args.config:
            parser.error(f'--stage {args.stage} requires --config')
        table = _LM_CONFIGS if args.stage == 'lm' else _RESNET_CONFIGS
        if args.config not in table:
            parser.error(
                f'--config {args.config} is not a {args.stage} config '
                f'(choose from {", ".join(sorted(table))})'
            )
        if not args.out:
            parser.error('--stage requires --out (the stage partial path)')
        stage_fn = run_lm_stage if args.stage == 'lm' else run_resnet_stage
        stage_fn(args.config, args.out)
        return

    result = {
        'metric': 'kfac_lm_tokens_per_sec',
        'value': 0.0,
        'unit': 'tokens/s',
        'vs_baseline': 0.0,
        'platform': 'unknown',
    }
    failed = False
    try:
        _orchestrate(result)
    except BaseException as exc:  # noqa: BLE001 - JSON line must still print
        result['error'] = f'{type(exc).__name__}: {exc}'
        failed = True
    print(json.dumps(result))
    _persist(result, partial=failed)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
