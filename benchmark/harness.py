"""One run of one cell: set-up, the output check, the window, the numbers.

``run.py`` looks for the chip and calls :func:`run_cell`; the tests call it
on the CPU with a tiny configuration, which is the only other caller and
never prints a result line.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib
import json
import math
import os
import shutil
import statistics
import tempfile
import time
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

from examples import common
from kfac_tpu import training
from kfac_tpu.observability import compile_watch

from benchmark import check, jobs, reference, schedule, trace_reduce, weights
from benchmark.refs import kfac as ref_kfac

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

# JAX's monitoring events that mean "a program was built": tracing to
# MLIR, and the backend's compile. A persistent-cache hit skips the second
# and not the first; neither may happen inside a window.
_BUILD_EVENTS = (
    '/jax/core/compile/jaxpr_to_mlir_module_duration',
    '/jax/core/compile/backend_compile_duration',
)


def load_json(*parts: str) -> Any:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """A cell by its name: its entry in ``BENCHMARK.json``, its workload
    file and its configuration's file. ``bench['end_to_end']`` holds the
    end-to-end metrics this cell reports: one that lists ``workloads``
    without the cell is left out (``per_layer`` stays whole; which of its
    rows the cell reads is :func:`layer_rows`'s to say)."""
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    if not any(w['name'] == name for w in bench['workloads']):
        known = [w['name'] for w in bench['workloads']]
        raise SystemExit(f'unknown workload {name!r}; BENCHMARK.json has {known}')
    bench['end_to_end'] = [
        m for m in bench['end_to_end'] if name in m.get('workloads', (name,))
    ]
    workload = load_json('workloads', f'{name}.json')
    listed = next(c for c in bench['configs'] if c['name'] == workload['config'])
    with open(os.path.join(ROOT, listed['file'])) as f:
        config = json.load(f)
    return {
        'name': name, 'chips': workload['chips'], 'bench': bench,
        'workload': workload, 'config': config,
    }


class BuildCounter:
    """Counts programs built (see ``_BUILD_EVENTS``) while installed."""

    def __init__(self) -> None:
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw: Any) -> None:
        del duration, kw
        if event in _BUILD_EVENTS:
            self.count += 1


@dataclasses.dataclass
class Run:
    """A job with its trainers and the state they step. Of ``state`` and
    ``first_order_state`` one at most is on the device at a time, and the
    plain reference is unloaded before either exists: a user holds the
    K-FAC trainer's state alone, and ``footprint`` reads that job."""

    job: jobs.Job
    ring: list
    trainer: Any
    state: Any
    first_order: Any
    first_order_state: Any
    reference: Any
    limits: dict
    ring_size: int
    factor_every: int
    inv_every: int
    fed: int = 0  # batches fed so far: the ring index
    fresh_variables: Any = None  # the seed's weights, made anew each call
    first_order_step_s: float = 0.0  # an untimed first-order step, fed
    footprint: int = 0  # most device memory seen after a step of the window
    memory_after_kfac: Any = None  # the first device's, its last K-FAC step done
    leaf_gaps: Any = None  # every leaf's norm gaps of the last check

    def sample_memory(self) -> None:
        self.footprint = max(
            self.footprint,
            max(footprint_bytes(d) for d in self.job.mesh.devices.flat),
        )

    def put(self, batch):
        # as the example trainers feed: the host batch to the default
        # device, then onto the mesh's batch sharding
        return tuple(
            jax.device_put(jnp.asarray(b), self.job.batch_sharding)
            for b in batch
        )

    def next_batch(self):
        batch = self.ring[self.fed % len(self.ring)]
        self.fed += 1
        return batch


def footprint_bytes(device) -> int:
    """What the device holds now: live buffers plus what the runtime has
    reserved for the loaded programs' scratch. On a TPU the two are apart:
    ``bytes_in_use`` counts buffers (state, batches), ``bytes_reserved`` the
    arena in which the largest loaded program keeps its temporaries; the
    two and the free bytes sum to ``bytes_limit`` (my chip run, PR 23).
    ``peak_bytes_in_use`` alone would leave out every activation."""
    stats = device.memory_stats() or {}
    return stats.get('bytes_in_use', 0) + stats.get('bytes_reserved', 0)


def _momentum_trace(opt_state):
    found = [
        s for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: isinstance(x, optax.TraceState)
        ) if isinstance(s, optax.TraceState)
    ]
    if len(found) != 1:
        raise ValueError(f'expected one momentum trace, found {len(found)}')
    return found[0].trace


@jax.jit
def worst_inverse_residual(kstate) -> jax.Array:
    """The benchmark's own reading of how good the engine's damped inverses
    are: the largest, over every slot of every bucket, of
    ``||I - (F + damping I) X||_F / sqrt(d)`` for factor ``F`` and resident
    inverse ``X``, every product in float32 at ``Precision.HIGHEST``. The
    formula is the library's (``DistributedKFAC.inverse_residuals``); the
    code is not."""
    worst = jnp.zeros((), jnp.float32)
    for f_side, x_side in ((kstate.a, kstate.a_inv), (kstate.g, kstate.g_inv)):
        for key, f in f_side.items():
            d = f.shape[-1]
            eye = jnp.eye(d, dtype=jnp.float32)
            m = f.astype(jnp.float32) + kstate.inv_damping * eye
            r = eye - jnp.einsum(
                'lij,ljk->lik', m, x_side[key].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            slot = jnp.sqrt(jnp.sum(r * r, axis=(-2, -1)) / d)
            # max() drops a NaN; a diverged solve has to show
            worst = jnp.where(
                jnp.any(jnp.isnan(slot)), jnp.nan,
                jnp.maximum(worst, jnp.max(slot)),
            )
    return worst


def timed_rows(run: Run, trainer, state, n: int, kfac_step=None, spans=False):
    """``n`` steps of ``trainer`` from ``state`` through
    ``examples.common.timed_step``, a fresh batch of the ring each. One
    row a step: ``begin`` (its feed starts), ``dispatch`` (its step is
    called), ``end`` (it completed), ``seconds`` (``timed_step``'s own),
    ``loss``, and for K-FAC steps their number and ``kind``.

    ``spans``: the same four lines as ``timed_step``, under the
    benchmark's host spans for the profiler."""
    rows = []
    seen = {}

    def on_step(_trainer, _state, loss, seconds):
        seen.update(loss=loss, seconds=seconds)

    for i in range(n):
        row = {'begin': time.perf_counter()}
        if spans:
            with jax.profiler.TraceAnnotation('bench.input'):
                batch = run.put(run.next_batch())
            row['dispatch'] = time.perf_counter()
            with jax.profiler.TraceAnnotation('bench.dispatch'):
                state, loss = trainer.step(state, batch)
            with jax.profiler.TraceAnnotation('bench.sync'):
                jax.block_until_ready(state)
                loss = float(loss)
            row['end'] = time.perf_counter()
            seen.update(loss=loss, seconds=row['end'] - row['dispatch'])
        else:
            batch = run.put(run.next_batch())
            row['dispatch'] = time.perf_counter()
            state, _ = common.timed_step(trainer, state, batch, on_step)
            row['end'] = time.perf_counter()
        row.update(seen)
        run.sample_memory()
        if kfac_step is not None:
            row['step'] = kfac_step + i
            row['kind'] = schedule.step_kind(
                row['step'], run.factor_every, run.inv_every
            )
        rows.append(row)
    return state, rows


def build_run(cell: dict, devices) -> Run:
    """The job, its two trainers and its plain reference: everything of a
    run that does not depend on the seed."""
    workload, config = cell['workload'], cell['config']
    job = jobs.load(config['kind']).build(config, workload, devices)
    k = workload['kfac']
    lr = job.lr_schedule
    engine = common.build_kfac(job.kfac_args, job.registry, mesh=job.mesh, lr=lr)
    return Run(
        job=job, ring=[], state=None, first_order_state=None,
        trainer=training.Trainer(
            loss_fn=job.loss_fn, optimizer=job.make_optimizer(lr),
            kfac=engine, donate_state=True,
        ),
        first_order=training.Trainer(
            loss_fn=job.loss_fn, optimizer=job.make_optimizer(lr), kfac=None,
            donate_state=True,
        ),
        reference=reference.Reference(config['kind'], config, workload),
        limits=workload['limits'], ring_size=workload['ring'],
        factor_every=k['factor_update_steps'], inv_every=k['inv_update_steps'],
    )


def reference_steps(run: Run, seed: int, log) -> tuple[dict, float]:
    """From the seed: the ring and the weights' maker, then the plain
    reference's first three steps on the first three batches, before any
    state of the program exists. The reference is unloaded when this
    returns, so that no step of the program is sampled beside its programs
    (the runtime's reserved scratch is that of the largest loaded program,
    and has to be the job's own). Returns the reference's numbers and the
    seconds it took."""
    job = run.job
    make = weights.maker(
        job.variable_shapes, NamedSharding(job.mesh, PartitionSpec())
    )
    key = weights.seed_key(seed)
    run.fresh_variables = lambda: make(key)
    run.ring = job.make_ring(seed, run.ring_size)

    began = time.perf_counter()
    variables = run.fresh_variables()
    ref = run.reference.first_steps(
        variables['params'],
        [run.put(b) for b in run.ring[:reference.STEPS]],
    )
    del variables
    reference_s = time.perf_counter() - began
    log(f'reference: {reference.STEPS} steps in {reference_s:.1f}s, of '
        f'which {run.reference.seconds}')
    # the leaves K-FAC preconditions: names only, no program
    ref['kfac_layers'] = run.reference.ref.kfac_layers(
        job.variable_shapes['params']
    )
    run.reference = None
    ref_kfac.unload()
    gc.collect()
    return ref, reference_s


def check_first_steps(run: Run, ref: dict, log) -> dict:
    """The program's first three steps from the seed's weights, through
    the window's own call and feed, on the batches the reference took
    (:func:`reference_steps` gave ``ref``), and the comparison. The state
    that leaves here is the one the window steps. Returns the verdict."""
    run.fed = 0
    variables = run.fresh_variables()
    state = run.trainer.init(variables['params'], variables.get('batch_stats'))
    del variables
    run.trainer.resume(state)
    losses = []
    for step in range(reference.STEPS):
        state, rows = timed_rows(run, run.trainer, state, 1, kfac_step=step)
        losses.append(rows[0]['loss'])
        if step == 0:
            residual = float(worst_inverse_residual(state.kfac_state))
            first_grad = jax.device_get(
                ref_kfac.leaf_norms(_momentum_trace(state.opt_state))
            )
    update = jax.device_get(ref_kfac.leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, state.params, run.fresh_variables()['params']
    )))
    run.state = state

    # the norms of the leaves K-FAC preconditions (the registered layers'
    # kernels and biases) decide; the rest (BatchNorm and LayerNorm scales,
    # embeddings) pass the preconditioner unchanged, and in bfloat16 the
    # first stages' BatchNorm gradients, sums of a million cancelling terms,
    # read 0.1 to 0.5 off the float32 reference in sound runs (PERF.md 2)
    layers = ref['kfac_layers']
    first_grad = {n: float(v) for n, v in first_grad.items()}
    update = {n: float(v) for n, v in update.items()}

    def preconditioned(norms):
        return {
            n: v for n, v in norms.items()
            if any(n.startswith(layer + '/') for layer in layers)
        }

    grad_gap, grad_leaf = check.norm_gap(
        preconditioned(first_grad), preconditioned(ref['first_grad_norms'])
    )
    update_gap, update_leaf = check.norm_gap(
        preconditioned(update), preconditioned(ref['update_norms'])
    )
    numbers = {
        'loss_gap': check.loss_gap(losses, ref['losses']),
        'first_grad_norm_gap': grad_gap,
        'update_norm_gap': update_gap,
        'inverse_residual': residual,
    }
    run.leaf_gaps = {
        'first_grad': check.leaf_gaps(first_grad, ref['first_grad_norms']),
        'update': check.leaf_gaps(update, ref['update_norms']),
    }
    ok, rows = check.decide(numbers, run.limits)
    for row in rows:
        log('check: ' + json.dumps(row))
    log(f'check: worst leaves: first gradient {grad_leaf}, update {update_leaf}')
    for what, gaps in run.leaf_gaps.items():
        name, gap = max(gaps.items(), key=lambda kv: kv[1])
        log(f'check: {what}, every leaf (not judged): worst {gap:.3g} at '
            f'{name}, median {statistics.median(gaps.values()):.3g}')
    return {'ok': ok, 'numbers': numbers, 'rows': rows}


def first_order_stretch(run: Run, steps: int) -> list:
    """The first-order baseline from the seed's weights: its state made on
    the device, ``reference.STEPS`` untimed steps (their first builds the
    programs in set-up; momentum exists after them), then ``steps`` timed
    ones, whose rows it returns. The state is gone when this returns. Only
    while the K-FAC trainer's state is off the device."""
    variables = run.fresh_variables()
    run.first_order_state = run.first_order.init(
        variables['params'], variables.get('batch_stats')
    )
    del variables
    run.first_order_state, warm = timed_rows(
        run, run.first_order, run.first_order_state, reference.STEPS
    )
    # what the window keeps free for each first-order step, feed included
    # (in set-up the first of the three built the programs)
    run.first_order_step_s = min(r['end'] - r['begin'] for r in warm)
    run.first_order_state, rows = timed_rows(
        run, run.first_order, run.first_order_state, steps
    )
    run.first_order_state = None
    return rows


def drop_kfac_state(run: Run) -> None:
    """The K-FAC steps are done: note what the device holds with their
    state on it, then free it for the first-order trainer's. A full
    collection, as set-up ran one ahead of these steps while they came
    first: what a cycle holds of that state goes before the next is made,
    and no full collection (0.08 s of such a heap) falls due in 40 steps."""
    run.memory_after_kfac = run.job.mesh.devices.flat[0].memory_stats()
    run.state = None
    gc.collect()


def set_up(cell: dict, seed: int, devices, log) -> tuple[Run, dict, float]:
    """Build the job; run and unload the plain reference; build and warm
    the first-order baseline's programs and drop its state; then the
    program's first three steps, checked against the reference's. Returns
    the run, the check's verdict and the seconds the reference took (not
    part of set-up)."""
    run = build_run(cell, devices)
    ref, reference_s = reference_steps(run, seed, log)
    first_order_stretch(run, 0)
    return run, check_first_steps(run, ref, log), reference_s


def window(run: Run, seconds: float, first_order_steps: int):
    """The measured window: whole periods of K-FAC steps while another
    fits beside the first-order stretch, never fewer than one; then, with
    the K-FAC trainer's state off the device, the first-order steps."""
    began = time.perf_counter()
    run.footprint = 0  # set-up's samples go: see device_report
    kept = first_order_steps * run.first_order_step_s
    periods = []
    next_step = reference.STEPS
    while True:
        run.state, rows = timed_rows(
            run, run.trainer, run.state, run.inv_every, kfac_step=next_step
        )
        next_step += run.inv_every
        periods.append(rows)
        elapsed = time.perf_counter() - began
        if elapsed + kept + schedule.wall(rows) > seconds:
            break
    drop_kfac_state(run)
    return first_order_stretch(run, first_order_steps), periods


def device_report(run: Run, devices) -> dict:
    """The device as JAX reports it. ``memory_peak_bytes``: the most the
    fullest chip held after any step of the window, buffers and reserved
    program scratch together: see :func:`footprint_bytes`. The plain
    reference's memory is not in it (it is unloaded before the program's
    first step), nor a second trainer's state (the K-FAC trainer's and the
    first-order one's take turns), nor set-up's steps: between the checked
    steps the check's own programs run and its own buffers live, and what
    of them was still held at a sample followed the clock, not the seed
    (Qwen: 240 MB there in seven runs of nine; my chip runs, PR 31)."""
    return {
        'platform': devices[0].platform,
        'kind': devices[0].device_kind,
        'count': len(devices),
        'memory_peak_bytes': run.footprint,
    }


def non_finite_losses(rows) -> int:
    return sum(not math.isfinite(r['loss']) for r in rows)


def run_cell(
    cell: dict, seed: int, seconds: float, trace: bool, devices, began: float,
    log=print,
) -> dict:
    """One run. ``began``: ``time.perf_counter()`` at process start.
    Returns the result line as a dict."""
    workload = cell['workload']
    builds = BuildCounter()
    cache = compile_watch.persistent_cache_counters()
    run, verdict, reference_s = set_up(cell, seed, devices, log)
    setup_s = time.perf_counter() - began - reference_s
    log(f'set-up {setup_s:.1f}s (reference {reference_s:.1f}s apart)')
    watch = run.trainer.kfac.compile_watcher()
    for e in (watch.events if watch is not None else []):
        log(f"compiled {e['entry']}: lowering {e['lowering_s']:.1f}s, "
            f"compile {e['compile_s']:.1f}s")
    log(f'persistent compile cache: {cache.snapshot()}')

    built_before = builds.count
    if trace:
        metrics, extra, rows = traced_run(cell, run, devices, log)
    else:
        fo_rows, periods = window(run, seconds, workload['first_order_steps'])
        kfac_rows = [r for p in periods for r in p]
        rows, extra = fo_rows + kfac_rows, {}
        e2e = schedule.end_to_end(fo_rows, periods, run.job.global_batch)
        log(f'window: {len(fo_rows)} first-order steps, {len(periods)} '
            f'periods of {run.inv_every}; by kind {schedule.by_kind(kfac_rows)}'
            f'; a first-order step '
            f"{statistics.median(r['seconds'] for r in fo_rows)}"
            f"; the longest K-FAC step {e2e['stall_ms']} ms")
    built_in_window = builds.count - built_before
    device = device_report(run, devices)
    log(f'memory: {run.memory_after_kfac} after the K-FAC steps; '
        f'{devices[0].memory_stats()} at the end')
    if not trace:
        e2e.update(
            peak_hbm_gb=device['memory_peak_bytes'] / 1e9, setup_s=setup_s
        )
        metrics = {
            m['name']: {'value': e2e[m['name']], 'unit': m['unit']}
            for m in cell['bench']['end_to_end'] if m['name'] in e2e
        }
    failed = non_finite_losses(rows)
    log(f'programs built inside the window: {built_in_window} (limit 0); '
        f'non-finite losses: {failed} of {len(rows)} (limit 0)')
    device.update(extra.pop('device', {}))
    return {
        'correct': bool(
            verdict['ok'] and failed == 0 and built_in_window == 0
        ),
        'attempted': len(rows),
        'failed': failed,
        'metrics': metrics,
        'device': device,
        **extra,
        'compared': compared(verdict['rows'], built_in_window, failed),
    }


def compared(check_rows, built_in_window: int, failed: int) -> dict:
    """Every number ``correct`` rests on beside its limit, for the result
    line's last key and the run's last lines on standard error. A number
    that is not finite goes as its name ('nan'), which strict JSON holds."""
    out = {
        r['number']: {
            'value': r['value'] if math.isfinite(r['value'])
            else repr(r['value']),
            'limit': r['limit'],
        }
        for r in check_rows
    }
    out['programs_built_in_window'] = {'value': built_in_window, 'limit': 0}
    out['non_finite_losses'] = {'value': failed, 'limit': 0}
    return out


# ------------------------------------------------------------- traced run


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader may read. Most readers read while
    the K-FAC trainer's state (``run.state``) is on the device, before the
    first-order stretch has run; one whose module says
    ``AFTER_FIRST_ORDER = True`` reads after it, with ``first_order_rows``
    there and ``run.state`` gone."""

    cell: dict
    run: Run
    devices: list
    first_order_rows: list | None
    rows: list            # untraced K-FAC rows (host clock)
    traced_rows: list     # the profiled stretch's rows
    trace: dict           # neutral trace (benchmark.trace_reduce)
    windows: dict         # device plane name -> (t0_ns, t1_ns)
    throughput: float     # samples/s over the untraced whole periods

    def count(self, kind: str | None) -> int:
        """Traced steps of a kind ('capture' counts the refresh step too:
        it captures as well); all of them for ``None``."""
        if kind is None:
            return len(self.traced_rows)
        kinds = {'capture': ('capture', 'refresh')}.get(kind, (kind,))
        return sum(r['kind'] in kinds for r in self.traced_rows)


def trace_scopes() -> tuple:
    """Every ``jax.named_scope`` that some ``layer_metrics/*.json`` row
    reads: an operation belongs to the deepest of them on its path."""
    found = set()
    for path in glob.glob(os.path.join(HERE, 'layer_metrics', '*.json')):
        with open(path) as f:
            found.update(json.load(f).get('scopes', ()))
    return tuple(sorted(found))


def layer_rows(cell: dict) -> list:
    """The rows of ``per_layer`` that a traced run of ``cell`` reads: those
    that list the cell under ``workloads``, and of those that list nothing
    the ones whose ``moves`` names an end-to-end metric the cell reports.
    A quantity whose cells report different end-to-end metrics has a row
    for each (``refresh_extra_ms`` moves ``stall_ms``;
    ``refresh_extra_ms.overhead`` moves ``kfac_overhead`` where no
    ``stall_ms`` is reported). A row that does not list the cell finds
    nothing to read there, and some take a pass over the trace to say so."""
    name = cell['name']
    reported = {m['name'] for m in cell['bench']['end_to_end']}
    return [
        m for m in cell['bench']['per_layer']
        if name in m.get('workloads', (name,)) and m['moves'] in reported
    ]


def _row_file(name: str) -> str:
    return os.path.join(HERE, 'layer_metrics', name + '.json')


def _read_under(name: str) -> str:
    """The metric whose reader reads ``name``: itself, or the one a row
    ``layer_metrics/<name>.json`` of the form ``{"reads": "<metric>"}``
    names (the same quantity under another name, for cells that report
    another end-to-end metric)."""
    while os.path.exists(_row_file(name)):
        other = load_json('layer_metrics', name + '.json').get('reads')
        if other is None:
            break
        name = other
    return name


def layer_reader(name: str):
    """A per-layer metric's reader by the metric's name: the module
    ``layer_metrics/<name>.py``, or ``None`` where the metric is a row
    ``layer_metrics/<name>.json`` of scopes."""
    name = _read_under(name)
    if os.path.exists(_row_file(name)):
        return None
    return importlib.import_module(f'benchmark.layer_metrics.{name}')


def read_layer_metric(name: str, ctx: LayerContext):
    """A per-layer metric by its name: its module's ``read(ctx)``, or a
    row ``layer_metrics/<name>.json`` for device milliseconds per step of
    a kind on the worst device under the row's ``scopes``. ``None`` where
    there is nothing to read."""
    name = _read_under(name)
    module = layer_reader(name)
    if module is not None:
        return module.read(ctx)
    row = load_json('layer_metrics', name + '.json')
    steps = ctx.count(row.get('per'))
    if not steps:
        return None
    scopes = trace_scopes()
    worst = 0.0
    for plane in trace_reduce.device_planes(ctx.trace):
        under = trace_reduce.scope_ns(
            plane, ctx.windows[plane['name']], scopes
        )
        worst = max(worst, sum(under.get(s, 0.0) for s in row['scopes']))
    return worst / 1e6 / steps if worst else None


def _program_op_names(trainer) -> dict:
    """{module name: {instruction: op_name}} of the K-FAC trainer's
    compiled step programs, for :func:`trace_reduce.annotate`."""
    watch = trainer.kfac.compile_watcher()
    programs = {}
    for compiled in (
        exe for exes in watch.executables().values() for exe in exes
    ):
        text = compiled.as_text()
        header = text.split(',', 1)[0].split()
        if len(header) >= 2 and header[0] == 'HloModule':
            programs[header[1]] = trace_reduce.op_names(text)
    return programs


def traced_run(cell, run: Run, devices, log):
    """The ``--trace 1`` run: at least one whole period untraced, for the
    numbers read off the host clock (tracing slows the host); a profiled
    stretch that holds a capture step, a refresh step and the plain steps
    between them; the readers of what the K-FAC steps left; then, with
    that state off the device, the first-order steps (untraced) and the
    readers that compare with them."""
    workload = cell['workload']
    run.footprint = 0  # set-up's samples go: see device_report
    first, last = schedule.traced_stretch(
        reference.STEPS + run.inv_every, run.factor_every, run.inv_every
    )
    run.state, rows = timed_rows(
        run, run.trainer, run.state, first - reference.STEPS,
        kfac_step=reference.STEPS,
    )

    logdir = tempfile.mkdtemp(prefix='kfac_bench_trace_')
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            run.state, traced = timed_rows(
                run, run.trainer, run.state, last - first + 1,
                kfac_step=first, spans=True,
            )
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.from_xplane(
            trace_reduce.find_xplane(logdir), trace_reduce.wanted_line
        )
        named = trace_reduce.annotate(trace, _program_op_names(run.trainer))
        log(f'trace: {named} device operations carry a scope path')
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    planes = trace_reduce.device_planes(trace)
    if not planes:
        raise RuntimeError('the trace holds no device plane')
    # a device's window: from its first operation of the stretch to its
    # last; the host's clock and the device's need not agree on an origin
    windows = {}
    for plane in planes:
        events = trace_reduce.ops(plane)
        if not events:
            raise RuntimeError(f'no operation ran on {plane["name"]}')
        windows[plane['name']] = (
            events[0]['start_ns'],
            max(e['start_ns'] + e['duration_ns'] for e in events),
        )
    ctx = LayerContext(
        cell=cell, run=run, devices=list(devices), first_order_rows=None,
        rows=rows, traced_rows=traced, trace=trace, windows=windows,
        throughput=schedule.throughput(
            schedule.whole_periods(rows, run.inv_every), run.job.global_batch
        ),
    )
    listed = layer_rows(cell)
    names = [m['name'] for m in listed]
    late = {
        n for n in names
        if getattr(layer_reader(n), 'AFTER_FIRST_ORDER', False)
    }
    values = {n: read_layer_metric(n, ctx) for n in names if n not in late}
    drop_kfac_state(run)
    fo_rows = first_order_stretch(run, workload['first_order_steps'])
    ctx = dataclasses.replace(ctx, first_order_rows=fo_rows)
    values.update((n, read_layer_metric(n, ctx)) for n in late)
    metrics = {
        m['name']: {'value': float(values[m['name']]), 'unit': m['unit']}
        for m in listed if values[m['name']] is not None
    }

    busy = [
        trace_reduce.busy_ns(p, windows[p['name']]) / 1e9 for p in planes
    ]
    lengths = [(w[1] - w[0]) / 1e9 for w in windows.values()]
    spans = trace_reduce.host_spans(
        trace, ('bench.input', 'bench.dispatch', 'bench.sync')
    )
    # the breakdown is the busiest-idle device's: the one a fix would help
    worst = max(
        planes, key=lambda p: (
            (windows[p['name']][1] - windows[p['name']][0])
            - trace_reduce.busy_ns(p, windows[p['name']])
        ),
    )
    extra = {
        'device': {
            'busy_s': sum(busy) / len(busy),
            'window_s': sum(lengths) / len(lengths),
        },
        'breakdown': {
            'device_ops': trace_reduce.top_ops(worst, windows[worst['name']]),
            'idle_gaps': trace_reduce.idle_gaps(
                worst, windows[worst['name']], spans
            ),
        },
    }
    log(f'traced steps {first}..{last}: by kind {schedule.by_kind(traced)}')
    return metrics, extra, fo_rows + rows + traced
