"""TPU microbenchmarks for the K-FAC hot ops: run on the real chip to pick
factor-op implementations (eigh vs Cholesky vs Newton-Schulz) and validate
the Pallas kernels against the XLA expressions they replace.

Usage: python tools/tpu_microbench.py [--sizes 512 2048] [--iters 20]
Prints one JSON line per measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common

# repo-root import only — no bootstrap(): this script must keep the real
# TPU platform, not the CPU pin the lint/CLI scripts default to
sys.path.insert(0, _common.repo_root())

import jax
import jax.numpy as jnp

#: measurement-harness version stamped on every reported line. v1 was the
#: per-iteration host dispatch loop; v2 is the one-dispatch in-jit
#: fori_loop chain (a reported line carries this so a number is
#: attributable to the harness that produced it).
HARNESS_VERSION = 2

#: env override for the dispatch mode ('fori_loop' | 'legacy'); the
#: --dispatch flag sets it for child measurements too
DISPATCH_ENV = 'KFAC_MICROBENCH_DISPATCH'


def _dispatch_mode():
    mode = os.environ.get(DISPATCH_ENV, 'fori_loop')
    return mode if mode in ('fori_loop', 'legacy') else 'fori_loop'


def _scale(tree, c):
    """Multiply every floating leaf of a pytree by c (ints pass through:
    token ids must stay valid)."""
    return jax.tree_util.tree_map(
        lambda a: a * jnp.asarray(c, a.dtype)
        if jnp.issubdtype(jnp.result_type(a), jnp.floating) else a,
        tree,
    )


def _chain(tree, out):
    """Add a zero derived from the previous output to every floating leaf,
    creating a cross-iteration data dependency. The zero sums one element
    of EVERY floating output leaf so the whole previous program — not just
    its cheapest output — must finish before the next dispatch."""
    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if jnp.issubdtype(jnp.result_type(x), jnp.floating)]
    if not leaves:
        return tree
    z = sum((jnp.ravel(x)[0] * 0.0).astype(jnp.float32) for x in leaves)
    # inject into ONE input leaf only: an executable cannot launch until
    # all input buffers are ready, so one dependency serializes the chain;
    # per-leaf adds would put O(n_leaves) extra dispatches in the timed
    # region for pytree inputs
    done = False

    def add_once(a):
        nonlocal done
        if done or not jnp.issubdtype(jnp.result_type(a), jnp.floating):
            return a
        done = True
        return a + z.astype(a.dtype)

    return jax.tree_util.tree_map(add_once, tree)


class Timing(float):
    """Measured seconds plus how they were measured.

    Arithmetic degrades to plain float; ``report`` lifts ``provenance``
    (harness version, dispatch mode, dispatch count) onto the JSON line
    so every persisted number is self-labeling.
    """

    def __new__(cls, seconds, provenance=None):
        self = super().__new__(cls, seconds)
        self.provenance = dict(provenance or {})
        return self


def _chain_body(fn, first, rest, warmup):
    """One chained perturbed iteration: scale the base input by an
    iteration-dependent 1% (offset past the warmup range — reusing a
    warmup scale plus _chain's exact 0.0 would hand the memoizer a
    bitwise-identical input), feed a zero derived from the previous
    output into it, run fn. Works with a Python int i (legacy host loop)
    or a traced i (in-jit fori_loop) — the SAME math either way, which
    is what tests/test_measurement.py pins.
    """

    def body(i, out):
        c = 1.0 + 0.01 * (warmup + i + 1.0)
        return fn(_chain(_scale(first, c), out), *rest)

    return body


def _warm(fn, first, rest, warmup):
    out = None
    for i in range(warmup):
        out = fn(_scale(first, 1.0 + 0.01 * (i + 1)), *rest)
    return out


def chain_result(fn, *args, iters=20, warmup=1, mode='fori_loop'):
    """Final output of the chained perturbed iteration sequence, via
    either dispatch mode — the equivalence oracle for the two timeit
    paths (no timing, just the math)."""
    first, rest = args[0], args[1:]
    out = _warm(fn, first, rest, warmup)
    body = _chain_body(fn, first, rest, warmup)
    if mode == 'fori_loop':
        looped = jax.jit(
            lambda out0: jax.lax.fori_loop(0, iters, body, out0)
        )
        return looped(out)
    for i in range(iters):
        out = body(i, out)
    return out


def timeit(fn, *args, iters=20, warmup=1, mode=None):
    """Time fn over ITERATION-CHAINED perturbed iterations, ONE dispatch
    per measurement.

    Two hazards of timing from the host:
    - a backend may serve repeated identical computations from a result
      cache, so same-input loops report cache hits. A 1%
      iteration-dependent scale forces real execution (additive 1e-6
      would round away in bf16).
    - INDEPENDENT dispatches overlap, so block_until_ready(last) times
      only the final call. Feeding a zero derived from iteration i's
      output into iteration i+1's input serializes the chain without
      changing the math.

    The v1 harness ran that chain as iters host dispatches, so every
    number still carried one host round-trip per iteration — the
    latency floor that flattened the cov sweep. v2
    moves the chain INSIDE jit as a ``lax.fori_loop``: the whole
    measurement is one dispatch, so per-iteration time contains at most
    1/iters of the dispatch latency. Callables that cannot trace under
    jit (AOT-compiled executables, host callbacks) fall back to the
    legacy host loop; the returned :class:`Timing` records which mode
    actually ran and how many dispatches the timed region contained.
    """
    mode = mode or _dispatch_mode()
    first, rest = args[0], args[1:]
    out0 = _warm(fn, first, rest, warmup)
    jax.block_until_ready(out0)
    body = _chain_body(fn, first, rest, warmup)
    looped = None
    if mode == 'fori_loop' and warmup >= 1:
        try:
            looped = jax.jit(
                lambda o0, f, r: jax.lax.fori_loop(
                    0, iters, _chain_body(fn, f, r, warmup), o0
                )
            )
            # untimed compile + warm run of the whole chain
            jax.block_until_ready(looped(out0, first, rest))
        except Exception:  # noqa: BLE001 - e.g. AOT executables don't trace
            looped = None
    if looped is not None:
        t0 = time.perf_counter()
        jax.block_until_ready(looped(out0, first, rest))
        seconds = (time.perf_counter() - t0) / iters
        mode, dispatches = 'fori_loop', 1
    else:
        out = out0
        t0 = time.perf_counter()
        for i in range(iters):
            out = body(i, out)
        jax.block_until_ready(out)
        seconds = (time.perf_counter() - t0) / iters
        mode, dispatches = 'legacy', iters
    return Timing(seconds, {
        'harness_version': HARNESS_VERSION,
        'dispatch_mode': mode,
        'dispatches': dispatches,
        'iters': iters,
    })


def measured(name, thunk, iters, post=None):
    """announce + time + report with per-op isolation: one unsupported op
    (e.g. a runtime without host callbacks, where eigh_host raises) must
    cost one line, not the session.

    ``post``: optional callable receiving the measured seconds, returning
    extra report fields computed only on success (oracle checks, derived
    ratios). Errors report ``ms: None`` — NOT NaN, which json.dump would
    emit as a bare non-standard token that breaks strict consumers of the
    persisted bench partials."""
    announce(name)
    try:
        t = thunk(iters)
        report(name, t, **(post(t) if post else {}))
        return t
    except Exception as exc:  # noqa: BLE001
        print(json.dumps({'op': name, 'ms': None,
                          'error': f'{type(exc).__name__}: {exc}'}),
              flush=True)
        return None


def announce(name):
    """Pre-announce each measurement on stderr: when a TPU program wedges
    mid-op, the last announced line names the culprit (the round-4 bench
    died silently at an unnamed compile — never again)."""
    print(f'[micro] timing {name}', file=sys.stderr, flush=True)


def report(name, seconds, **extra):
    rec = {'op': name, 'ms': round(seconds * 1e3, 3)}
    rec.update(getattr(seconds, 'provenance', None) or {})
    rec.update(extra)
    print(json.dumps(rec), flush=True)


def latency_floor_verdict(
    sizes,
    seconds,
    work_exponent: float = 2.0,
    flat_tol: float = 0.25,
    min_work_ratio: float = 4.0,
):
    """Flag a size sweep whose timings are flat while the work scales.

    A real op timed across sizes spanning a ``min_work_ratio``-fold work
    range (work ~ size**work_exponent) cannot be flat; measurements
    whose max/min spread stays within ``flat_tol`` over such a range are
    dominated by a fixed per-dispatch latency (host round-trip, queue
    depth), and every number in the sweep is the floor, not the op.

    Returns None when the series is too short or spans too little work
    to judge; otherwise a verdict dict with ``contaminated`` (bool),
    the measured ``spread``, the ``expected_ratio`` of work, and the
    implied ``floor_ms``.
    """
    pts = [
        (float(s), float(t))
        for s, t in zip(sizes, seconds)
        if t is not None and t > 0.0
    ]
    if len(pts) < 2:
        return None
    pts.sort()
    lo_s, hi_s = pts[0][0], pts[-1][0]
    if lo_s <= 0 or hi_s <= lo_s:
        return None
    expected = (hi_s / lo_s) ** work_exponent
    if expected < min_work_ratio:
        return None  # the sweep never leaves the latency-bound regime
    times = [t for _, t in pts]
    spread = max(times) / min(times)
    flat = spread <= 1.0 + flat_tol
    return {
        'contaminated': bool(flat),
        'spread': round(spread, 3),
        'expected_ratio': round(expected, 1),
        'n': len(pts),
        'floor_ms': round(min(times) * 1e3, 3),
    }


def report_floor_verdicts(sweeps):
    """Latency-floor check per sweep family, one ``floor/<family>`` JSON
    line each: a family whose timings stayed flat while the sweep's work
    scaled is contaminated — every number in it is the dispatch floor,
    not the op (measured: cov_dense f32 flat at 72-83 ms across
    d=256-2048 under the v1 host-loop harness).

    ``sweeps``: family -> (work_exponent, [(size, seconds|None), ...]).
    Returns the verdicts keyed by family.
    """
    verdicts = {}
    for family, (exponent, points) in sorted(sweeps.items()):
        sizes = [s for s, t in points if t is not None]
        times = [t for _, t in points if t is not None]
        verdict = latency_floor_verdict(
            sizes, times, work_exponent=exponent
        )
        if verdict is not None:
            verdicts[family] = verdict
            print(json.dumps({'op': f'floor/{family}', **verdict}),
                  flush=True)
    return verdicts


def newton_schulz_inverse(a, damping, iters=25):
    """(a + damping*I)^-1 by Newton-Schulz: X_{k+1} = X_k (2I - M X_k).

    Pure matmuls (MXU-native). Converges when ||I - M X_0|| < 1; the init
    X_0 = I/trace(M) guarantees that for SPD M since trace(M) > lambda_max.
    """
    d = a.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)
    m = a.astype(jnp.float32) + damping * eye
    x = eye / jnp.trace(m)
    for _ in range(iters):
        x = x @ (2.0 * eye - m @ x)
    return x


def bench_resnet50_inverse_update(iters: int) -> None:
    """Inverse-update wall-clock on ResNet-50's real factor shapes, exact
    dims vs size-class buckets (dozens of per-shape
    batched decompositions, mostly padding). One device: measures compile
    + batched-op dispatch amortization, the thing classing buys."""
    import kfac_tpu
    from kfac_tpu.models import resnet
    from kfac_tpu.parallel import DistributedKFAC
    from kfac_tpu.parallel.mesh import kaisa_mesh

    m = resnet.resnet50()
    x = jnp.zeros((2, 224, 224, 3), jnp.float32)
    reg = kfac_tpu.register_model(m, x)
    mesh = kaisa_mesh(1.0, devices=jax.devices()[:1])
    for granularity in (1, 128, 256):
        cfg = kfac_tpu.KFACPreconditioner(
            registry=reg, damping=0.003, compute_method='inverse',
            inverse_solver='newton_schulz',
            bucket_granularity=granularity,
        )
        dk = DistributedKFAC(config=cfg, mesh=mesh)
        state = dk.init()
        f = jax.jit(dk.update_inverses)
        tc0 = time.perf_counter()
        jax.block_until_ready(f(state).a_inv if not dk._eigen else None)
        compile_s = time.perf_counter() - tc0
        t0 = time.perf_counter()
        reps = max(2, iters // 4)
        out = state
        for i in range(reps):
            # input-varying factors: defeat result memoization (see
            # timeit)
            out = f(
                out._replace(
                    a={
                        k: v * (1.0 + 0.01 * (i + 1))
                        for k, v in out.a.items()
                    }
                )
            )
        jax.block_until_ready(out.a_inv)
        report(
            f'resnet50_inv_update_gran{granularity}',
            (time.perf_counter() - t0) / reps,
            n_buckets=len(dk.buckets),
            compile_s=round(compile_s, 2),
        )


def bench_pipeline(iters: int) -> None:
    """Pipelined-LM throughput vs the dense LM (the
    1F1B backward-slot recompute trade was a comment, not a number).

    Single-device (pipe=1): isolates pure schedule overhead — scan
    machinery, masking, and 1F1B's ~2-forwards-per-microbatch recompute —
    with zero bubble, so `tokens_per_s / dense tokens_per_s` IS the
    schedule cost. Bubble cost on real stages is (2S-2)/(M+2S-2) on top.
    """
    import kfac_tpu
    from kfac_tpu.models import TransformerLM, lm_loss
    from kfac_tpu.parallel import PipelinedLM
    from kfac_tpu.parallel.mesh import pipeline_mesh

    on_tpu = jax.devices()[0].platform == 'tpu'
    b, s, d, layers, vocab = (16, 512, 512, 4, 8192) if on_tpu else (
        4, 64, 64, 2, 128
    )
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    tokens = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, vocab)
    targets = jnp.roll(tokens, -1, 1)

    dense = TransformerLM(
        vocab_size=vocab, d_model=d, num_heads=4, num_layers=layers,
        max_len=s, dtype=dtype,
    )
    dparams = dense.init(jax.random.PRNGKey(1), tokens)['params']
    dloss = lm_loss(dense)
    g = jax.jit(jax.value_and_grad(dloss))
    t_dense = timeit(
        lambda p, bt: g(p, bt)[0], dparams, (tokens, targets),
        iters=max(3, iters // 2),
    )
    report('lm_dense_loss_grad', t_dense,
           tokens_per_s=round(b * s / t_dense, 1))

    mesh = pipeline_mesh(n_stages=1, devices=jax.devices()[:1])
    for schedule in ('gpipe', '1f1b'):
        for micro in (2, 4):
            plm = PipelinedLM(
                mesh=mesh, vocab_size=vocab, d_model=d, num_heads=4,
                num_layers=layers, n_microbatches=micro, max_len=s,
                dtype=dtype, schedule=schedule,
            )
            pparams = plm.init(jax.random.PRNGKey(1))
            f = jax.jit(
                lambda p, bt, _plm=plm: _plm.loss_and_stats(p, bt)[0]
            )
            t = timeit(
                lambda p, bt, _f=f: _f(p, bt), pparams, (tokens, targets),
                iters=max(3, iters // 2),
            )
            report(
                f'lm_pipeline_{schedule}_m{micro}', t,
                tokens_per_s=round(b * s / t, 1),
                vs_dense=round(t_dense / t, 3),
            )


def bench_vocab_head(iters: int) -> None:
    """Vocab-parallel LM head: per-device cost of head matmul + fused NLL
    must scale ~1/tp when the (d, V) kernel shards V over the model axis
    (the replicated head is a real MFU tax at V~50k).

    Reports the compiled per-device FLOPs (the SPMD program's own cost
    model — honest on any backend, including a 1-core CPU mesh where
    wall-clock parallelism is fake) plus wall-clock for reference.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from kfac_tpu.ops import losses as losses_lib

    b, s, d = 8, 128, 256
    vocab = 8192
    devs = jax.devices()
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (b, s, d), jnp.float32)
    targets = jax.random.randint(jax.random.PRNGKey(4), (b, s), 0, vocab)
    kernel = jax.random.normal(
        jax.random.PRNGKey(5), (d, vocab), jnp.float32
    ) * 0.02

    def loss(k, x, t):
        logits = x @ k
        return jnp.mean(losses_lib.vocab_parallel_nll(logits, t))

    import math

    grad = jax.jit(jax.value_and_grad(loss))
    tp = 1
    base_flops = None
    while tp <= len(devs):
        mesh = Mesh(devs[:tp], ('model',))
        ks = jax.device_put(kernel, NamedSharding(mesh, P(None, 'model')))
        compiled = grad.lower(ks, x, targets).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float((ca or {}).get('flops', float('nan')))
        if base_flops is None:
            base_flops = flops
        # time the AOT executable directly (a fresh grad(...) dispatch
        # would re-trace and compile the same program a second time —
        # compiles dominate on this 1-core container)
        t = timeit(lambda k_, x_, t_: compiled(k_, x_, t_)[0],
                   ks, x, targets, iters=max(3, iters // 2))
        known = not math.isnan(flops) and base_flops and not math.isnan(
            base_flops
        )
        report(
            f'vocab_head_tp{tp}', t,
            flops_per_device=None if math.isnan(flops) else flops,
            vs_tp1_flops=round(flops / base_flops, 4) if known else None,
        )
        tp *= 2


def bench_bubble() -> None:
    """Interleaved-1F1B schedule bubble accounting (kfac_tpu.parallel.
    interleaved): idle chunk-slots per total, normalized to stage-time
    units so v configurations are comparable. Pure schedule math — the
    cross-v comparison holds on any hardware. Two tick models: the
    combined-scan (F,B)-pair model caps the interleaving gain (~25% at
    p=4); the SINGLE-SLOT tables (one F OR B chunk per tick — the model
    InterleavedPipelinedLM executes) realize the full 2*(p-1)/v Megatron
    reduction."""
    from kfac_tpu.parallel import interleaved

    for p, m in ((4, 16), (8, 32)):
        base = None
        for v in (1, 2, 4):
            sched = interleaved.generate(p, v, m)
            idle = sched.bubble_slots() // p  # per-rank idle chunk-slots
            stage_units = idle / v  # chunk time = stage time / v
            if base is None:
                base = stage_units
            single = interleaved.generate_single_slot(p, v, m)
            ss_units = single.bubble_slots() / p / v
            # schedule math, not a timed measurement: no ms field
            print(json.dumps({
                'op': f'pipeline_bubble_p{p}_v{v}_m{m}',
                'ticks': sched.ticks,
                'bubble_frac': round(idle / (2 * sched.ticks), 4),
                'bubble_stage_units': round(stage_units, 2),
                'vs_v1': round(stage_units / base, 3),
                'single_slot_stage_units': round(ss_units, 2),
                'single_slot_ring': single.ring,
            }), flush=True)


def _gdn_system(chunks, heads, c, n):
    """A chunk's system as ``chunk_gated_delta_rule`` builds it: ``a``
    strictly lower ``(chunks, 1, heads, c, c)`` from normalised keys,
    ``rhs`` ``(..., c, n)``. The keys are 0.9-correlated with ``beta`` 0.98
    and hardly any decay: what neighbouring tokens behind the causal
    convolution give, and the hard case for the solve."""
    from kfac_tpu.models import deltanet

    lead = (chunks, 1, heads)
    key = jax.random.split(jax.random.PRNGKey(11), 2)
    k = jax.random.normal(key[0], lead + (c, 128), jnp.float32)
    k = deltanet.l2norm(0.9 * k[..., :1, :] + (1 - 0.81) ** 0.5 * k, 0.0)
    b = -1e-3 * jnp.arange(1, c + 1, dtype=jnp.float32)
    a = 0.98 * jnp.einsum('...id,...jd->...ij', k, k, precision='highest')
    a = jnp.tril(a * jnp.exp(b[:, None] - b[None, :]), -1)
    return a, jax.random.normal(key[1], lead + (c, n), jnp.float32)


def bench_gdn_solve(iters: int) -> None:
    """The DeltaNet chunk's unit-triangular system ``(I + a) U = rhs`` at
    the Qwen cell's shape (64 chunks x 8 heads of 64 x 64, 256 columns),
    forward and forward + backward, one dispatch a measurement:

    - ``xla``: ``jax.scipy.linalg.solve_triangular`` (the
      ``InvertDiagBlocksLowerTriangular`` custom call and a product),
    - ``merged_b<k>``: ``deltanet.unit_lower_solve`` (diagonal blocks of k
      rows by substitution, merged to the whole inverse, one product; its
      backward pass reuses the inverse),
    - ``rows_b16``: the four block rows of the substitution run on the
      right-hand side itself, products 16 deep, backward the same
      transposed,
    - ``floor``: one pass that reads both operands and writes the result.

    Each line carries the worst error of the forward result against a
    float64 solve on the host, over the largest entry."""
    import numpy as np

    from kfac_tpu.models import deltanet

    hi = jax.lax.Precision.HIGHEST
    c, n = 64, 256
    a, rhs = _gdn_system(64, 8, c, n)
    probe = jax.random.normal(jax.random.PRNGKey(12), rhs.shape)

    def xla(a, rhs):
        with jax.default_matmul_precision('float32'):
            return jax.scipy.linalg.solve_triangular(
                a + jnp.eye(c, dtype=a.dtype), rhs, lower=True,
                unit_diagonal=True,
            )

    blk = 16

    def rows_fwd(a, rhs):
        t, us = deltanet._unit_lower_inverse(jnp.stack([
            a[..., i:i + blk, i:i + blk] for i in range(0, c, blk)
        ], axis=-3)), []
        for p, i in enumerate(range(0, c, blk)):
            r = rhs[..., i:i + blk, :]
            if us:
                r = r - jnp.matmul(
                    a[..., i:i + blk, :i], jnp.concatenate(us, -2),
                    precision=hi,
                )
            us.append(jnp.matmul(t[..., p, :, :], r, precision=hi))
        u = jnp.concatenate(us, -2)
        return u, (a, t, u)

    def rows_bwd(res, u_bar):
        a, t, u = res
        gs = []
        for p, i in reversed(list(enumerate(range(0, c, blk)))):
            r = u_bar[..., i:i + blk, :]
            if gs:
                r = r - jnp.einsum(
                    '...ji,...jn->...in', a[..., i + blk:, i:i + blk],
                    jnp.concatenate(gs, -2), precision=hi,
                )
            gs.insert(0, jnp.einsum(
                '...ji,...jn->...in', t[..., p, :, :], r, precision=hi
            ))
        g = jnp.concatenate(gs, -2)
        a_bar = -jnp.einsum('...in,...jn->...ij', g, u, precision=hi)
        return jnp.tril(a_bar, -1), g

    rows = jax.custom_vjp(lambda a, rhs: rows_fwd(a, rhs)[0])
    rows.defvjp(rows_fwd, rows_bwd)

    def merged(blk):
        def solve(a, rhs):
            # the block size is read when the function is traced
            deltanet.SOLVE_BLOCK, keep = blk, deltanet.SOLVE_BLOCK
            try:
                return deltanet.unit_lower_solve(a, rhs)
            finally:
                deltanet.SOLVE_BLOCK = keep
        return solve

    forms = {'floor': lambda a, rhs: rhs + a[..., :1], 'xla': xla,
             'rows_b16': rows}
    forms.update({f'merged_b{k}': merged(k) for k in (8, 16, 32)})
    few = slice(0, 2)
    want = np.linalg.solve(
        np.eye(c) + np.asarray(a[few], np.float64),
        np.asarray(rhs[few], np.float64),
    )
    for name, solve in forms.items():
        fwd = jax.jit(solve)
        both = jax.jit(lambda a, rhs, solve=solve: jax.grad(
            lambda a, rhs: jnp.sum(solve(a, rhs) * probe), (0, 1)
        )(a, rhs))

        def err(_t, fwd=fwd):
            got = np.asarray(fwd(a, rhs)[few], np.float64)
            return {'fwd_err': float(
                np.abs(got - want).max() / np.abs(want).max()
            )}

        measured(f'gdn_solve_{name}_fwd',
                 lambda k, f=fwd: timeit(f, a, rhs, iters=k), iters,
                 post=None if name == 'floor' else err)
        measured(f'gdn_solve_{name}_fwd_bwd',
                 lambda k, f=both: timeit(f, a, rhs, iters=k), iters)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--sizes', type=int, nargs='*',
                   default=[256, 512, 1024, 2048, 4096])
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--rows', type=int, default=8192)
    p.add_argument('--resnet', action='store_true',
                   help='ResNet-50 inverse-update: exact vs size-class '
                   'buckets')
    p.add_argument('--pipeline', action='store_true',
                   help='pipeline schedule overhead vs the dense LM')
    p.add_argument('--head', action='store_true',
                   help='vocab-parallel head: per-device cost vs tp')
    p.add_argument('--bubble', action='store_true',
                   help='interleaved-1F1B schedule bubble fractions '
                   '(pure schedule math, no device work)')
    p.add_argument('--gdn-solve', action='store_true',
                   help="the DeltaNet chunk's unit-triangular solve: "
                   "XLA's call against the blocked forms")
    p.add_argument('--skip-factor-ops', action='store_true')
    p.add_argument('--dispatch', choices=['fori_loop', 'legacy'],
                   help='measurement dispatch mode: fori_loop (default; '
                   'ONE dispatch per measurement, the chain runs in-jit) '
                   'or legacy (v1 per-iteration host dispatches, kept '
                   'for A/B-ing the harness itself)')
    p.add_argument('--smoke', action='store_true',
                   help='CI-sized pass: shrink the clock-check matmul and '
                   'skip the attention A/B so the sweep runs in seconds '
                   'on a CPU host (make prof)')
    p.add_argument('--no-pallas', action='store_true',
                   help='skip the Pallas kernels (fused pairs + flash attention): '
                   'measure only validated XLA ops — the safe first pass '
                   'on an untested chip')
    p.add_argument('--pallas-only', action='store_true',
                   help='measure ONLY the Pallas kernels vs their XLA '
                   'oracles (on-chip validation pass; run after the safe '
                   'ops have succeeded)')
    args = p.parse_args()
    if args.dispatch:
        os.environ[DISPATCH_ENV] = args.dispatch

    from kfac_tpu.utils import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    print(json.dumps({'platform': dev.platform,
                      'device_kind': getattr(dev, 'device_kind', ''),
                      'harness_version': HARNESS_VERSION,
                      'dispatch_mode': _dispatch_mode()}),
          flush=True)

    run_pallas = not args.no_pallas
    xla_ops = not args.pallas_only
    #: family -> (work exponent wrt the swept size, [(size, seconds)]);
    #: fed to the latency-floor check after the sweep
    sweeps: dict = {}

    def track(family, exponent, size, t):
        sweeps.setdefault(family, (exponent, []))[1].append((size, t))
        return t

    # --- clock validation: known-FLOPs matmul chain -----------------------
    n = 512 if args.smoke else 4096
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)

    @jax.jit
    def mm_chain(a):
        x = a
        for _ in range(8):
            x = x @ a
        return x

    announce(f'matmul{n}_bf16_chain8')
    t = timeit(mm_chain, a, iters=args.iters)
    flops = 8 * 2 * n**3
    report(f'matmul{n}_bf16_chain8', t, tflops=round(flops / t / 1e12, 1))

    # --- flash attention kernel vs einsum attention (TPU only: the
    # kernel needs real Mosaic, and the einsum path at this size is
    # minutes on CPU) ------------------------------------------------------
    from kfac_tpu.models import attention as att
    from kfac_tpu.ops import pallas_attention as pa

    on_tpu = dev.platform == 'tpu'
    if not args.smoke:
        b, s, h, hd = (4, 2048, 4, 128) if on_tpu else (1, 256, 1, 128)
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
        qkv = tuple(
            jax.random.normal(kx, (b, s, h, hd), jnp.bfloat16)
            for kx in (kq, kk, kv)
        )
        dense_att = jax.jit(
            lambda q, k, v: att._finish(
                pa.attend_partials_einsum(q, k, v, 0, 0, True)
            )
        )
        announce(f'attn_einsum_s{s}')
        t = timeit(dense_att, *qkv, iters=args.iters)
        report(f'attn_einsum_s{s}', t)
        if on_tpu and run_pallas:
            flash = jax.jit(
                lambda q, k, v: att._finish(
                    pa.flash_attention_partials(q, k, v, causal=True)
                )
            )

            def flash_check(t2, _t_einsum=t):
                err = float(jnp.abs(
                    flash(*qkv).astype(jnp.float32)
                    - dense_att(*qkv).astype(jnp.float32)
                ).max())
                return {'max_err': round(err, 5),
                        'speedup': round(_t_einsum / t2, 2)}

            measured(f'attn_flash_s{s}',
                     lambda n: timeit(flash, *qkv, iters=n), args.iters,
                     post=flash_check)

    if not args.skip_factor_ops:
        for d in args.sizes:
            m = jax.random.normal(jax.random.PRNGKey(d), (args.rows, d),
                                  jnp.float32)
            cov = (m.T @ m) / args.rows  # SPD test matrix

            if xla_ops:
                qiters = max(3, args.iters // 4)
                f = jax.jit(lambda c: jnp.linalg.eigh(c))
                track('eigh', 3.0, d,
                      measured(f'eigh_{d}',
                               lambda n: timeit(f, cov, iters=n), qiters))

                # host-offloaded eigh (pure_callback -> LAPACK): the EIGEN
                # method's TPU escape hatch — measures the d^2 transfer +
                # host syevd against the device eigh above and
                # Newton-Schulz below. (A runtime without host send/recv
                # callbacks reports the error line.)
                from kfac_tpu.ops import factors as factors_lib

                fh = jax.jit(
                    lambda c: factors_lib.batched_eigh(c, impl='host')
                )
                track('eigh_host', 3.0, d,
                      measured(f'eigh_host_{d}',
                               lambda n: timeit(fh, cov, iters=n), qiters))

                # cholesky factor + solve against identity (INVERSE method)
                def chol_inv(c):
                    l = jax.scipy.linalg.cho_factor(
                        c + 0.003 * jnp.eye(d, dtype=c.dtype)
                    )
                    return jax.scipy.linalg.cho_solve(
                        l, jnp.eye(d, dtype=c.dtype)
                    )

                track('cholesky_inv', 3.0, d,
                      measured(f'cholesky_inv_{d}',
                               lambda n: timeit(
                                   jax.jit(chol_inv), cov, iters=n
                               ),
                               qiters))

                # Newton-Schulz damped inverse: 2*iters MXU matmuls, the
                # library's TPU default (default_compute_method)
                ns = jax.jit(lambda c: newton_schulz_inverse(c, 0.003))

                def ns_residual(_t):
                    x = ns(cov)
                    err = float(jnp.abs(
                        x @ (cov + 0.003 * jnp.eye(d)) - jnp.eye(d)
                    ).max())
                    return {'residual_inf': round(err, 6)}

                track('newton_schulz25', 3.0, d,
                      measured(f'newton_schulz25_{d}',
                               lambda n: timeit(ns, cov, iters=n), qiters,
                               post=ns_residual))

                # warm-started refresh at factor-EMA drift (the library
                # passes the previous inverse as x0 at every
                # inv_update_steps refresh; residual-based early exit
                # means wall-clock ~ iterations actually taken)
                from kfac_tpu.ops import factors as fwarm

                drift = 0.95 * cov + 0.05 * jnp.eye(d, dtype=cov.dtype)
                prev_inv = fwarm.newton_schulz_inverse(cov, 0.003)
                warm = jax.jit(
                    lambda c: fwarm.newton_schulz_inverse(
                        c, 0.003, x0=prev_inv
                    )
                )

                def warm_iters(_t):
                    info = fwarm.newton_schulz_inverse_info(
                        drift, 0.003, x0=prev_inv
                    )
                    cold = fwarm.newton_schulz_inverse_info(drift, 0.003)
                    return {
                        'warm_iters': int(info.iterations),
                        'cold_iters': int(cold.iterations),
                    }

                track('newton_schulz_warm', 3.0, d,
                      measured(f'newton_schulz_warm_{d}',
                               lambda n: timeit(warm, drift, iters=n),
                               qiters, post=warm_iters))

            # the kl-clip pair vs its unfused XLA expression (interpret
            # mode off-TPU: numerics-true, never a measurement)
            if run_pallas:
                from kfac_tpu.ops import pallas_ns

                interp = pallas_ns.interpret_mode()
                gmat = 0.5 * cov + 0.1 * jnp.eye(d, dtype=jnp.float32)

                def kl_unfused(p, g):
                    return p * jnp.sum(p * g)

                def kl_fused(p, g):
                    s = pallas_ns.fused_klclip_dot(p, g, interpret=interp)
                    return pallas_ns.fused_klclip_scale(
                        p, s, interpret=interp
                    )

                track('klclip_unfused', 2.0, d, measured(
                    f'klclip_unfused_{d}',
                    lambda n: timeit(jax.jit(kl_unfused), cov, gmat,
                                     iters=n),
                    args.iters,
                ))
                track('klclip_fused', 2.0, d, measured(
                    f'klclip_fused_{d}',
                    lambda n: timeit(jax.jit(kl_fused), cov, gmat,
                                     iters=n),
                    args.iters,
                ))

    if sweeps:
        report_floor_verdicts(sweeps)

    if args.resnet:
        bench_resnet50_inverse_update(args.iters)
    if args.pipeline:
        bench_pipeline(args.iters)
    if args.head:
        bench_vocab_head(args.iters)
    if args.bubble:
        bench_bubble()
    if args.gdn_solve:
        bench_gdn_solve(args.iters)


if __name__ == '__main__':
    main()
