"""The quickest proof that kfac-tpu still starts on the chip.

    python chip_smoke.py          # one process, whatever jax.devices() holds

Drives the normal training path once — ``register_model`` ->
``CurvatureCapture`` -> ``DistributedKFAC`` on the mesh of all devices ->
``Trainer.step`` — through ``examples.train_imagenet_resnet.main`` at the
full width of ResNet-50 (224 px, 1000 classes, bf16, 32 images per chip,
synthetic data from a seed), with every library default for method,
solver, kernels and granularity, and a cadence that puts every step
variant inside ten steps. Before that it compiles the kl-clip pair of
``ops/pallas_ns.py`` (a standalone kernel: the step path runs XLA's
expressions since PR 37) at a real ResNet-50 shape and holds it against
the XLA expression it stands for, and holds the covariance product
(``ops.cov.get_cov``) at two of the benchmark's shapes against a float64
product of the same values on the host.

It fails — non-zero, no result line — unless ``jax.devices()[0]`` is a
TPU (it never pins or falls back to the CPU), if any phase raises, if a
kernel disagrees with its reference, if a loss is not finite, if the
worst Newton-Schulz residual exceeds the library's own
``NS_FALLBACK_RESIDUAL``, if the K-FAC step counter or the inverses did
not advance, or if a step recompiled after its variant's first compile.
On success the last two lines of stdout are JSON objects: first the
report (every field :func:`run_training` returns, plus the kernel checks),
then, last, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as JAX reports it. It reports times, never utilisation.
"""

from __future__ import annotations

import importlib.metadata
import json
import re
import sys
import time

_T0 = time.perf_counter()

FACTOR_UPDATE_STEPS = 2
INV_UPDATE_STEPS = 4
STEPS = 10
IMAGES_PER_CHIP = 32

# A kernel may not be less accurate than the XLA expression it replaces.
# Both are measured against that expression at precision=HIGHEST, and the
# kernel's error may be at most twice XLA's plus 2^-16. Kl-clip has no
# matmul: what is left is the accumulation order over up to 4,608 terms
# (the floor). A kernel that dropped a tile, a mask or a scale is off by
# orders of magnitude more.
KERNEL_TOL_FACTOR = 2.0
KERNEL_TOL_FLOOR = 2.0 ** -16

# The covariance product against float64 (relative Frobenius norm): what
# float32 accumulation over the rows leaves, for bfloat16 rows (exact
# products) and for float32 rows (HIGHEST). An operand rounded to
# bfloat16 on the way reads 2^-9, two hundred times this.
COV_TOL = 2.0 ** -16

# Covariance rows (rows, width) at the size of the benchmark's widest
# factors, one column past whole tiles: ResNet-50's 3x3 patch rows of the
# last stage at 128 images, GPT-2 small's MLP tap at 8 sequences. Kl-clip:
# the preconditioned gradients of ResNet-50's stage3 conv2 and of its
# head (ragged).
KERNEL_SHAPES = {
    'cov': [(6272, 4609), (8192, 3073)],
    'klclip': [(512, 4608), (1000, 2049)],
}


def log(msg: str) -> None:
    print(f'[smoke +{time.perf_counter() - _T0:6.1f}s] {msg}', flush=True)


def resnet50_argv(n_devices: int) -> list[str]:
    return [
        '--arch', 'resnet50', '--image-size', '224', '--bf16',
        '--batch-size', str(IMAGES_PER_CHIP * n_devices),
        '--kfac-factor-update-steps', str(FACTOR_UPDATE_STEPS),
        '--kfac-inv-update-steps', str(INV_UPDATE_STEPS),
        '--limit-steps', str(STEPS), '--epochs', '1',
        '--kfac-compile-watch',
    ]


# ------------------------------------------------------------------ kernels


def check_kernels(shapes=KERNEL_SHAPES) -> list[dict]:
    """Compile the kl-clip pair (``ops/pallas_ns.py``) on a TPU and
    compare it with the XLA expression it stands for, and the
    covariance product with float64. One row per kernel and shape;
    raises if a row misses its tolerance. Off a TPU the same calls run
    the Pallas interpreter (tests only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kfac_tpu.ops import cov, pallas_gate, pallas_ns

    interpret = pallas_gate.interpret_mode()
    rows: list[dict] = []

    @jax.jit
    def rel_err(got, ref, magnitude):
        # worst error over the outputs, each relative to ``magnitude`` or
        # else to that output's largest reference magnitude
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(g - r))
            / (jnp.max(jnp.abs(r)) if magnitude is None else magnitude)
            for g, r in zip(
                jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)
            )
        ]))

    def row(kernel, shape, kernel_fn, xla_fn, args, magnitude=None):
        with jax.default_matmul_precision('highest'):
            ref = jax.jit(xla_fn)(*args)
        xla = jax.jit(xla_fn)(*args)
        got = jax.jit(kernel_fn)(*args)
        xla_err = float(rel_err(xla, ref, magnitude))
        r = {
            'kernel': kernel, 'shape': list(shape),
            'max_err': float(rel_err(got, ref, magnitude)),
            'xla_err': xla_err,
            'tol': KERNEL_TOL_FACTOR * xla_err + KERNEL_TOL_FLOOR,
        }
        log(f"kernel {kernel} {r['shape']}: err {r['max_err']:.2e} "
            f"(xla {xla_err:.2e}, tol {r['tol']:.2e})")
        # healthy direction, so a NaN fails
        if not r['max_err'] <= r['tol']:
            raise RuntimeError(f'kernel check failed: {r}')
        rows.append(r)

    def normal(seed, shape):
        return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)

    for n, d in shapes['cov']:
        for dtype in (jnp.bfloat16, jnp.float32):
            a = normal(0, (n, d)).astype(dtype)
            a64 = np.asarray(a.astype(jnp.float32), np.float64)
            ref = a64.T @ a64 / n
            got = np.asarray(jax.jit(cov.get_cov)(a), np.float64)
            r = {
                'kernel': 'get_cov', 'shape': [n, d],
                'dtype': jnp.dtype(dtype).name,
                'max_err': float(
                    np.linalg.norm(got - ref) / np.linalg.norm(ref)
                ),
                'tol': COV_TOL,
            }
            log(f"get_cov {r['shape']} {r['dtype']}: err {r['max_err']:.2e} "
                f"against float64 (tol {COV_TOL:.2e})")
            if not r['max_err'] <= r['tol']:
                raise RuntimeError(f'covariance check failed: {r}')
            rows.append(r)

    for r_, c in shapes['klclip']:
        p, g = normal(3, (r_, c)), normal(4, (r_, c))
        # the signed sum cancels, so its rounding is bounded by the sum
        # of magnitudes, not by the result
        row('fused_klclip_dot', (r_, c),
            lambda p, g: pallas_ns.fused_klclip_dot(
                p, g, interpret=interpret),
            lambda p, g: jnp.sum(p * g), (p, g),
            magnitude=jnp.sum(jnp.abs(p * g)))
        row('fused_klclip_scale', (r_, c),
            lambda p, s: pallas_ns.fused_klclip_scale(
                p, s, interpret=interpret),
            lambda p, s: p * s, (p, jnp.float32(0.37)))
    return rows


# ----------------------------------------------------------------- training


def _pallas_kernels(compiled) -> list[str]:
    """Names of the Mosaic kernels in one compiled program, from its
    optimized HLO: every ``pallas_call`` in ``kfac_tpu/ops`` is named, and
    the name rides the custom call's ``op_name`` metadata."""
    names = []
    for line in compiled.as_text().splitlines():
        if 'tpu_custom_call' not in line:
            continue
        m = re.search(r'op_name="[^"]*?([\w.]+)/pallas_call', line)
        names.append(m.group(1) if m else 'unnamed')
    return sorted(set(names))


def run_training(train_main, argv: list[str]) -> dict:
    """Run one example trainer's own ``main`` and return the evidence of
    what happened: every field the module docstring promises. Raises if
    the run was not right (see :func:`check_training`)."""
    import jax
    import jax.numpy as jnp
    import jaxlib

    from examples import common
    from kfac_tpu.observability import compile_watch
    from kfac_tpu.utils import compile_cache

    devices = jax.devices()
    cache = compile_watch.persistent_cache_counters()
    cache_before = cache.snapshot()
    steps: list[dict] = []
    last: dict = {}

    @jax.jit
    def inverse_checksum(kstate):
        return sum(
            jnp.sum(jnp.abs(v.astype(jnp.float32)))
            for side in (kstate.a_inv, kstate.g_inv, kstate.qa, kstate.qg)
            for v in side.values()
        )

    def on_step(trainer, state, loss, seconds):
        steps.append({
            'loss': loss,
            'seconds': round(seconds, 4),
            'inverse_checksum': float(inverse_checksum(state.kfac_state)),
        })
        log(f'step {len(steps) - 1}: loss {loss:.4f} in {seconds:.3f}s')
        last.update(trainer=trainer, state=state)

    log(f'training: {" ".join(argv)}')
    result = train_main(argv, on_step=on_step)
    trainer, state = last['trainer'], last['state']
    engine = trainer.kfac
    watch = engine.compile_watcher()
    cfg = engine.config
    cache_after = cache.snapshot()

    kfac_bytes: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(state.kfac_state):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            kfac_bytes[key] = kfac_bytes.get(key, 0) + shard.data.nbytes
    usage = engine.memory_usage(state.kfac_state)

    report = {
        'platform': devices[0].platform,
        'device_kind': devices[0].device_kind,
        'device_count': len(devices),
        'versions': {
            'jax': jax.__version__,
            'jaxlib': jaxlib.__version__,
            'libtpu': _version_or_none('libtpu'),
        },
        'model': {
            'argv': argv,
            'kfac_layers': len(engine.registry),
            'params': sum(
                int(p.size) for p in jax.tree_util.tree_leaves(state.params)
            ),
            'widest_factor': max(
                sb.d for sb in (*engine.a_store, *engine.g_store)
            ),
        },
        'compute_method': cfg.compute_method.name,
        'inverse_solver': cfg.inverse_solver,
        'bucket_granularity': cfg.bucket_granularity,
        'mesh': {k: int(v) for k, v in engine.mesh.shape.items()},
        'strategy': engine.strategy.name,
        'pallas_kernels': {
            entry: sorted({k for exe in exes for k in _pallas_kernels(exe)})
            or 'none'
            for entry, exes in watch.executables().items()
        },
        'compile': {
            e['entry']: {
                'lowering_s': round(e['lowering_s'], 2),
                'compile_s': round(e['compile_s'], 2),
                'aot': e['aot'],
                'aot_error': e['aot_error'],
            }
            for e in watch.events
        },
        'recompiles': watch.recompile_count(),
        'step_seconds': [s['seconds'] for s in steps],
        'losses': [s['loss'] for s in steps],
        'inverse_checksums': [s['inverse_checksum'] for s in steps],
        'kfac_step': int(state.kfac_state.step),
        # worst slot per storage bucket, after the last refresh
        'inverse_residuals': common.inverse_residuals(
            engine, state.kfac_state
        ),
        'compile_cache': {
            'dir': compile_cache.current_dir(),
            'hits': cache_after['persistent_cache_hits']
            - cache_before['persistent_cache_hits'],
            'misses': cache_after['persistent_cache_misses']
            - cache_before['persistent_cache_misses'],
        },
        'memory': {
            'peak_bytes_in_use': {
                str(d.id): (d.memory_stats() or {}).get('peak_bytes_in_use')
                for d in devices
            },
            'bytes_in_use': {
                str(d.id): (d.memory_stats() or {}).get('bytes_in_use')
                for d in devices
            },
            'kfac_state_bytes': kfac_bytes,
            'kfac_model_per_device': {
                k: v for k, v in usage.items() if k != 'padding_waste'
            },
        },
        'trainer_result': result,
    }
    check_training(report)
    return report


def _version_or_none(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def check_training(report: dict) -> None:
    """Raise unless the run the report describes was right."""
    import math

    from kfac_tpu.ops.factors import NS_FALLBACK_RESIDUAL

    def fail(why: str):
        raise RuntimeError(f'smoke failed: {why}\n{json.dumps(report)}')

    losses = report['losses']
    if not losses or not all(math.isfinite(x) for x in losses):
        fail(f'non-finite loss in {losses}')
    if report['kfac_step'] != len(losses):
        fail(f"K-FAC step counter {report['kfac_step']} after "
             f'{len(losses)} steps')
    if report['compute_method'] == 'INVERSE':
        # healthy direction, so a NaN fails
        bad = {
            k: v for k, v in report['inverse_residuals'].items()
            if not v <= NS_FALLBACK_RESIDUAL
        }
        if bad:
            fail(f'inverse residuals above {NS_FALLBACK_RESIDUAL}: {bad}')
    # the inverses move at every refresh and only there; the cadence is
    # read back from the argv the trainer was given
    argv = report['model']['argv']
    every = int(argv[argv.index('--kfac-inv-update-steps') + 1])
    sums = report['inverse_checksums']
    if not math.isfinite(sums[-1]) or sums[0] == 0.0:
        fail(f'inverses never built: checksums {sums}')
    for i in range(1, len(sums)):
        if (sums[i] != sums[i - 1]) != (i % every == 0):
            fail(f'inverses out of cadence at step {i}: checksums {sums}')
    if report['recompiles']:
        fail(f"{report['recompiles']} recompile(s) after a variant's first "
             'compile')
    fell_back = {
        k: c['aot_error'] for k, c in report['compile'].items() if not c['aot']
    }
    if fell_back:
        fail(f'a step fell back from ahead-of-time dispatch: {fell_back}')


# --------------------------------------------------------------------- main


def result_line(devices) -> str:
    """The last line of a pass: these keys and no others, the device as
    JAX reports it. Everything else is in the report, the line above."""
    return json.dumps({
        'ok': True,
        'device': {
            'platform': devices[0].platform,
            'kind': devices[0].device_kind,
            'count': len(devices),
        },
    })


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        sys.exit(
            f'chip_smoke needs a TPU: jax.devices()[0] is {dev.platform!r} '
            f'({dev.device_kind}). It does not run anywhere else.'
        )
    # before the first line of output: without the repo beside it the
    # script fails here and prints nothing
    from examples import train_imagenet_resnet
    from kfac_tpu.utils import compile_cache

    n = len(jax.devices())
    log(f'device: {dev.platform} {dev.device_kind} x{n}')
    log(f'compile cache: {compile_cache.configure()}')
    kernels = check_kernels()
    report = run_training(train_imagenet_resnet.main, resnet50_argv(n))
    print(json.dumps({
        'seconds': round(time.perf_counter() - _T0, 1),
        'kernel_checks': kernels,
        **report,
    }), flush=True)
    print(result_line(jax.devices()), flush=True)


if __name__ == '__main__':
    main()
