"""Multi-process multihost validation.

The reference exercises its distributed code under real forked process
groups (testing/distributed.py:24-141, gloo). Until round 4 the repo's
``parallel/multihost.py`` had only ever executed its single-process
early-return branch; these tests launch 2 or 4 OS processes that rendezvous
through ``jax.distributed.initialize`` (CPU backend, the KFAC_TPU_* env
surface run_pod.sh sets per node), build a ``hybrid_kaisa_mesh`` spanning
both, run a real DistributedKFAC step over it, and check the numbers
against the same step computed in a single process.
"""

import json
import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKER = os.path.join(REPO, 'testing', 'multihost_worker.py')
VOTE_WORKER = os.path.join(REPO, 'testing', 'multihost_vote_worker.py')
PIPELINE_WORKER = os.path.join(
    REPO, 'testing', 'multihost_pipeline_worker.py'
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _launch_workers(
    n: int, port: int, worker: str = WORKER, devices_per_proc: int = 2
):
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env['JAX_PLATFORMS'] = 'cpu'
        flags = ' '.join(
            f
            for f in env.get('XLA_FLAGS', '').split()
            if 'xla_force_host_platform_device_count' not in f
        )
        env['XLA_FLAGS'] = (
            flags
            + f' --xla_force_host_platform_device_count={devices_per_proc}'
        ).strip()
        env['KFAC_TPU_COORDINATOR'] = f'127.0.0.1:{port}'
        env['KFAC_TPU_NUM_PROCESSES'] = str(n)
        env['KFAC_TPU_PROCESS_ID'] = str(pid)
        # share the suite's persistent compile cache: n concurrent COLD
        # compiles contending for this container's single core could push
        # a worker past the communicate timeout
        env.setdefault(
            'JAX_COMPILATION_CACHE_DIR', os.path.join(REPO, '.jax_cache')
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, worker],
                env=env,
                cwd=REPO,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    return procs


def _collect_results(procs, timeout: int = 600):
    """JSON result line per worker; kills the pod on any failure so a
    blocked rendezvous never orphans workers on this single core."""
    results = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                # collect every worker's stderr tail — a hang with no
                # diagnostics is undebuggable (the finally kills them)
                tails = []
                for qi, q in enumerate(procs):
                    q.kill()
                    try:
                        _, qerr = q.communicate(timeout=30)
                    except Exception:  # noqa: BLE001
                        qerr = '<unreadable>'
                    tails.append(
                        f'--- worker {qi} stderr ---\n{qerr[-1500:]}'
                    )
                raise AssertionError(
                    'multihost rendezvous timed out:\n' + '\n'.join(tails)
                ) from None
            assert p.returncode == 0, f'worker failed:\n{err[-3000:]}'
            line = [l for l in out.splitlines() if l.startswith('{')][-1]
            results.append(json.loads(line))
    finally:
        # ANY exit (a failed worker's assert included) must not orphan the
        # rest of the rendezvous — blocked workers would spin on this
        # container's single core for their full timeout
        for q in procs:
            if q.poll() is None:
                q.kill()
                q.wait()
    return results


@pytest.mark.slow
@pytest.mark.parametrize('n_procs', [2, 4])
def test_multi_process_step_matches_single_process(n_procs):
    """{2, 4} OS processes x 2 virtual devices each rendezvous through
    jax.distributed.initialize and run a real DistributedKFAC step over a
    hybrid mesh; replicated outputs agree across processes and match the
    same step computed in one process. The 4-process case exercises a
    4-host x 2-device hybrid grid (the DCN-topology shape multihost.
    hybrid_kaisa_mesh exists for) rather than the minimal pair."""
    if len(jax.devices()) < 2 * n_procs:
        pytest.skip(
            f'single-process reference needs {2 * n_procs} virtual '
            f'devices (XLA_FLAGS overrides the conftest default)'
        )
    port = _free_port()
    procs = _launch_workers(n_procs, port)
    results = _collect_results(procs)

    # every process saw the full world and agrees bit-for-bit on the
    # replicated outputs
    for r in results:
        assert r['n_processes'] == n_procs
        assert r['n_devices'] == 2 * n_procs
    for r in results[1:]:
        assert r['loss'] == results[0]['loss']
        assert r['checksum'] == results[0]['checksum']

    # and the multi-process numbers match the same step computed in ONE
    # process over the suite's virtual devices (identical mesh grid:
    # hybrid_kaisa_mesh orders host-major, which degenerates to device
    # order here)
    import jax.numpy as jnp

    import kfac_tpu
    from kfac_tpu.parallel import batch_sharding, multihost
    from testing import models

    mesh = multihost.hybrid_kaisa_mesh(
        0.5, devices=jax.devices()[: 2 * n_procs]
    )
    m = models.TinyModel(hidden=8, out=4)
    x, y = models.regression_data(jax.random.PRNGKey(1), n=32, dim=6)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    cfg = kfac_tpu.KFACPreconditioner(
        registry=reg, compute_method='eigen', damping=0.01, lr=0.1,
        bucket_granularity=1,
    )
    dk = kfac_tpu.parallel.DistributedKFAC(config=cfg, mesh=mesh)
    run = kfac_tpu.CurvatureCapture(reg).value_stats_and_grad(
        models.mse_loss(m)
    )
    bs = batch_sharding(mesh)
    batch = (jax.device_put(x, bs), jax.device_put(y, bs))

    @jax.jit
    def step(params, state, batch):
        (loss, _), grads, stats = run(params, batch)
        state, pg = dk.step(state, grads, stats)
        return state, pg, loss

    _, pg, loss = step(params, dk.init(), batch)
    checksum = float(
        sum(
            jnp.sum(jnp.abs(leaf.astype(jnp.float32)))
            for leaf in jax.tree_util.tree_leaves(pg)
        )
    )
    np.testing.assert_allclose(results[0]['loss'], float(loss), rtol=1e-5)
    np.testing.assert_allclose(results[0]['checksum'], checksum, rtol=1e-4)


@pytest.mark.slow
def test_eight_process_protocol_smoke():
    """8 OS processes x 1 virtual device rendezvous and drive the raw
    coordination protocol — no model step, just the ops the pod
    analyzer verifies statically: unanimous and dissenting
    ``agree_decision`` rounds, ``agree_emergency`` (max code, max step)
    convergence under a one-rank signal plus a one-rank step skew,
    ``assert_same_step`` on both the agreeing and the diverging path,
    barriers, and the (4, 2) host-major ``hybrid_kaisa_mesh`` grid over
    a world wider than any single host."""
    n_procs = 8
    port = _free_port()
    procs = _launch_workers(
        n_procs, port, worker=VOTE_WORKER, devices_per_proc=1
    )
    results = _collect_results(procs)

    assert sorted(r['process'] for r in results) == list(range(n_procs))
    for r in results:
        assert r['n_processes'] == n_procs
        assert r['vote_unanimous'] is True
        # rank 3's veto must reach every rank (unanimous min-reduction)
        assert r['vote_dissent'] is False
        # rank 2's signal code and rank 5's skewed step, pod-wide
        assert (r['agreed_code'], r['agreed_step']) == (2, 18)
        assert r['skew_raises'] is True
        # 8 devices at grad_worker_fraction 0.5 -> (gw=4, col=2),
        # host-major: the first column is whole hosts 0..3
        assert r['mesh_shape'] == [4, 2]
        assert r['mesh_axes'] == ['kfac_gw', 'kfac_col']
        assert r['col0_hosts'] == [0, 1, 2, 3]


@pytest.mark.slow
def test_two_process_pipeline_matches_single_process():
    """2 OS processes x 1 virtual device run the interleaved pipeline
    scan (p=2, v=2, m=4) over a pipeline mesh that SPANS the process
    boundary — every per-tick ppermute crosses the coordination-service
    transport. The replicated loss and embed/head/ln_f gradient checksum
    agree across ranks and match the same scan computed in one process,
    and each rank's executed (F, B, idle) tick-counter row equals the
    static schedule table's per-rank prediction."""
    port = _free_port()
    procs = _launch_workers(
        2, port, worker=PIPELINE_WORKER, devices_per_proc=1
    )
    results = _collect_results(procs)

    assert sorted(r['process'] for r in results) == [0, 1]
    for r in results[1:]:
        assert r['loss'] == results[0]['loss']
        assert r['checksum'] == results[0]['checksum']

    # single-process reference over 2 of the suite's virtual devices,
    # identical geometry and PRNG streams (multihost_pipeline_worker.GEOM)
    import jax.numpy as jnp

    from kfac_tpu.parallel import interleaved_scan
    from kfac_tpu.parallel.mesh import pipeline_mesh
    from testing import multihost_pipeline_worker as worker_mod

    geom = worker_mod.GEOM
    mesh = pipeline_mesh(n_stages=2, devices=jax.devices()[:2])
    model = interleaved_scan.InterleavedPipelinedLM(
        mesh=mesh, virtual_chunks=2, **geom
    )
    params = model.init(jax.random.PRNGKey(0))
    m, s = geom['n_microbatches'], geom['max_len']
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (m, s), 0, geom['vocab_size']
    )
    targets = jax.random.randint(
        jax.random.PRNGKey(2), (m, s), 0, geom['vocab_size']
    )
    loss, grads, _, ticks = jax.jit(model.loss_stats_and_ticks)(
        params, (tokens, targets)
    )
    checksum = float(
        sum(
            jnp.sum(jnp.abs(leaf.astype(jnp.float32)))
            for key in ('embed', 'pos_embed', 'head', 'ln_f')
            for leaf in jax.tree_util.tree_leaves(grads[key])
        )
    )
    np.testing.assert_allclose(results[0]['loss'], float(loss), rtol=1e-5)
    np.testing.assert_allclose(results[0]['checksum'], checksum, rtol=1e-4)

    # executed counters, per rank, against the schedule table — the
    # cross-process run must execute the exact same slot sequence the
    # simulator prices
    report = model.tick_report(np.asarray(ticks))
    assert report['matches_schedule'], report
    predicted = report['predicted']
    by_rank = {r['process']: r['ticks'] for r in results}
    for rank in (0, 1):
        assert by_rank[rank] == [
            predicted['executed_f'][rank],
            predicted['executed_b'][rank],
            predicted['idle'][rank],
        ], (rank, by_rank[rank], predicted)


@pytest.mark.slow
def test_initialize_noop_without_rendezvous_env():
    """Single process, no KFAC_TPU_*/pod env: initialize() must be a no-op
    (the branch every in-process test exercises implicitly — asserted
    explicitly here in a subprocess with a clean env)."""
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    for var in (
        'KFAC_TPU_COORDINATOR', 'KFAC_TPU_NUM_PROCESSES',
        'KFAC_TPU_PROCESS_ID', 'TPU_WORKER_HOSTNAMES',
        'SLURM_JOB_NUM_NODES', 'MEGASCALE_COORDINATOR_ADDRESS',
    ):
        env.pop(var, None)
    code = (
        'import jax; jax.config.update("jax_platforms", "cpu");\n'
        'from kfac_tpu.parallel import multihost\n'
        'multihost.initialize()\n'
        'assert jax.process_count() == 1\n'
        'print("noop-ok")\n'
    )
    out = subprocess.run(
        [sys.executable, '-c', code],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert 'noop-ok' in out.stdout
