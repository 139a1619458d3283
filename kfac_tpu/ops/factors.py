"""Second-order factor math: EMA updates, decompositions, preconditioning.

All functions are pure and jit-friendly. Decompositions run in float32 (TPU
eigh / linear algebra want fp32; bf16 eigendecompositions are not stable) and
results are cast to a configurable ``inv_dtype`` — the same numerics policy as
the reference (kfac/layers/eigen.py:295-348, kfac/layers/inverse.py:186-213).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


def ema_update(
    running: jax.Array | None,
    new: jax.Array,
    alpha: float | jax.Array,
) -> jax.Array:
    """Running average ``alpha * running + (1 - alpha) * new``.

    With ``running=None`` the running value is initialized to the identity,
    matching the reference's identity-init then immediate EMA
    (kfac/layers/base.py:375-405).
    """
    if running is None:
        running = jnp.eye(new.shape[0], dtype=new.dtype)
    return alpha * running + (1.0 - alpha) * new


def effective_alpha(
    alpha: float | jax.Array, w: jax.Array
) -> jax.Array:
    """Evidence-weighted EMA decay: ``1 - (1-alpha) * w``.

    The ONE formula behind traffic-weighted factor updates (dense and
    KAISA engines): a capture carrying weight ``w`` in [0, 1] moves the
    running factor by ``(1-alpha)*w`` — nothing at all for a starved
    (w=0) capture, the plain EMA step at w=1.
    """
    return 1.0 - (1.0 - alpha) * w


def identity_floor(
    step: jax.Array,
    factor_decay: Any,
    factor_update_steps: Any,
) -> jax.Array | float:
    """What an engine knows under a factor's smallest eigenvalue at
    ``step``: the weight of the identity initialisation still in it.

    A factor is ``decay^n I + (PSD)`` after ``n`` EMA updates from
    :func:`ema_update`'s identity, and an engine updates at most once every
    ``factor_update_steps`` steps, so ``n <= step // factor_update_steps
    + 1`` by the time it inverts at ``step`` and every eigenvalue is at
    least ``decay^(that)``. Whatever holds an update back leaves more of
    the identity in, never less: a step without statistics, a layer the
    loss did not run, a quarantined update rolled back, a routed layer's
    evidence-weighted decay (:func:`effective_alpha` >= alpha). The count
    travels with the factors (``step`` is checkpointed beside them). It
    is not known, and this returns 0, where either hyperparameter is a
    schedule of the step. It can overstate the floor where factors were
    made by another cadence than this engine's (an engine swapped in
    mid-run with a longer ``factor_update_steps``) or are not a sum of
    PSD terms (a lossy statistics transport): that is safe, see
    :func:`newton_schulz_inverse_info`, and costs the trips the bound
    would have saved.
    """
    if callable(factor_decay) or callable(factor_update_steps):
        return 0.0
    n = step // factor_update_steps + 1
    return jnp.asarray(factor_decay, jnp.float32) ** n


class EigenDecomp(NamedTuple):
    """Eigendecomposition of a symmetric PSD factor.

    ``q``: eigenvectors (d, d); ``d``: eigenvalues clamped >= 0 (d,).
    Reference state: kfac/layers/eigen.py:20-115.
    """

    q: jax.Array
    d: jax.Array


def batched_eigh(
    factor: jax.Array, impl: str = 'xla'
) -> tuple[jax.Array, jax.Array]:
    """``(eigenvalues, eigenvectors)`` of a (..., d, d) symmetric stack.

    ``impl='xla'``: ``jnp.linalg.eigh`` — on TPU this lowers to a
    sequential panel algorithm that leaves the MXU idle and compiles
    pathologically slowly at LM factor sizes (measured on v5e: tens of
    seconds of compile per distinct shape; the batched vmap form never
    finished compiling in 20 min), which is why the repo's TPU default
    is INVERSE+Newton-Schulz.

    ``impl='host'``: ``jax.pure_callback`` to LAPACK (``numpy.linalg.eigh``,
    syevd) on the host CPU. Factors are small (d^2 fp32: 4 MB at d=1024),
    so the PCIe round-trip is cheap next to a pathological device eigh —
    the same host-offload escape hatch the reference gets for free by
    running eigh wherever torch places it. Under vmap the callback receives
    the batched operand directly (numpy eigh batches natively); inside
    shard_map each device's host runs LAPACK on just its slots, preserving
    the KAISA work division. ``pure_callback`` makes NO ordering guarantee
    (XLA may reorder, batch, or elide calls) — safe here precisely because
    the callback is pure; never add host-side state to it.

    ``impl='eig_host'``: the NON-symmetric escape hatch — a general
    ``numpy.linalg.eig`` on the host with real parts taken and eigenpairs
    sorted ascending, the reference's ``symmetric=False`` handling for
    factors that drift numerically non-symmetric
    (kfac/layers/eigen.py:295-348, ``torch.linalg.eig`` real-part). In
    this framework factors are symmetric BY CONSTRUCTION (``get_cov``
    symmetrizes; the Pallas kernel is exactly symmetric), so this exists
    as a robustness corner, not a default: general eigenvectors are not
    orthogonal, and the preconditioning formula uses ``q.T`` as the
    approximate inverse exactly as the reference does. ``jnp.linalg.eig``
    has no TPU lowering, so this path always rides the host callback.
    """
    # fp32 upcast guard: decompositions NEVER run in half precision. The
    # module contract ("bf16 eigendecompositions are not stable") is
    # enforced here rather than trusted to every caller — a bf16/fp16
    # factor stack (AMP factor_dtype, async shadow payloads) is upcast
    # before any eigh, device or host, and non-real inputs are rejected.
    if not jnp.issubdtype(factor.dtype, jnp.floating):
        raise TypeError(
            'batched_eigh requires a real floating factor stack; got '
            f'{jnp.dtype(factor.dtype).name}'
        )
    f = factor.astype(jnp.float32)
    if impl in ('host', 'eig_host'):
        import numpy as np

        def _host_eigh(m):
            w, v = np.linalg.eigh(m)
            return np.asarray(w, np.float32), np.asarray(v, np.float32)

        def _host_eig(m):
            w, v = np.linalg.eig(m)
            w, v = np.real(w), np.real(v)
            order = np.argsort(w, axis=-1)
            w = np.take_along_axis(w, order, -1)
            v = np.take_along_axis(v, order[..., None, :], -1)
            return np.ascontiguousarray(w, np.float32), np.ascontiguousarray(
                v, np.float32
            )

        return jax.pure_callback(
            _host_eigh if impl == 'host' else _host_eig,
            (
                jax.ShapeDtypeStruct(f.shape[:-1], jnp.float32),
                jax.ShapeDtypeStruct(f.shape, jnp.float32),
            ),
            f,
            vmap_method='expand_dims',
        )
    if impl != 'xla':
        raise ValueError(
            f"unknown eigh impl {impl!r}: 'xla', 'host', or 'eig_host'"
        )
    return jnp.linalg.eigh(f)


def compute_eigh(
    factor: jax.Array,
    inv_dtype: jnp.dtype = jnp.float32,
    impl: str = 'xla',
) -> EigenDecomp:
    """Eigendecompose a (symmetrized) factor in fp32, clamp eigvals >= 0.

    Reference: kfac/layers/eigen.py:295-348. ``impl`` selects the device
    (``'xla'``), host-offloaded symmetric (``'host'``), or host-offloaded
    general real-part (``'eig_host'``, the reference's ``symmetric=False``
    escape hatch) decomposition — see :func:`batched_eigh`.
    """
    d, q = batched_eigh(factor, impl)
    return EigenDecomp(q=q.astype(inv_dtype), d=jnp.clip(d, 0.0).astype(inv_dtype))


def compute_inverse(
    factor: jax.Array,
    damping: float | jax.Array,
    inv_dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """Tikhonov-damped explicit inverse in fp32.

    Reference: kfac/layers/inverse.py:186-213. Solved via Cholesky (factors
    are symmetric PSD + damping*I, so this is both faster and more stable on
    TPU than LU-based general inverse).
    """
    f = factor.astype(jnp.float32)
    f = f + damping * jnp.eye(f.shape[0], dtype=f.dtype)
    eye = jnp.eye(f.shape[0], dtype=f.dtype)
    cho = jax.scipy.linalg.cho_factor(f)
    inv = jax.scipy.linalg.cho_solve(cho, eye)
    return inv.astype(inv_dtype)


def gershgorin_condition_bound(
    factor: jax.Array,
    damping: float | jax.Array,
) -> jax.Array:
    """Cheap upper bound on cond(factor + damping*I) for a PSD factor.

    Gershgorin's max absolute row sum bounds ``lambda_max``; damping floors
    ``lambda_min``, so ``kappa <= ||M||_inf / damping``. One reduction —
    usable inside jit to size Newton-Schulz iteration budgets
    (``log4(kappa) + 5`` iterations of a cold solve reach the fp32 floor when
    the bound is tight, ``log2(kappa) + 5`` at worst) or to flag factors
    whose fp32 inverse (by ANY solver — Cholesky's backward-stable solve
    also has forward error ``O(kappa * eps)``) cannot be trusted.

    Batched: a ``(..., d, d)`` stack yields per-matrix bounds ``(...,)``;
    ``damping`` broadcasts (scalar, or per-matrix ``(...,)`` for per-layer
    escalated damping). At ``damping == 0`` the eigenvalue floor vanishes
    and the true condition number of a PSD factor may genuinely be
    infinite, but an ``inf``/``0/0`` here poisons every downstream
    comparison (``inf * 0``, health thresholds), so the denominator is
    floored at fp32 ``tiny`` and the quotient is capped at fp32 ``max``
    (``lam_max / tiny`` itself overflows to inf for any ``lam_max``
    above ~4) — the bound saturates at a huge-but-finite value that any
    sane threshold still flags. A NaN factor still propagates NaN (fails
    closed in ``health.factor_ok``'s threshold compare).
    """
    f = factor.astype(jnp.float32)
    d = jnp.asarray(damping, jnp.float32)
    eye = jnp.eye(f.shape[-1], dtype=jnp.float32)
    m = f + d[..., None, None] * eye
    lam_max = jnp.max(jnp.sum(jnp.abs(m), axis=-1), axis=-1)
    fi = jnp.finfo(jnp.float32)
    return jnp.minimum(lam_max / jnp.maximum(d, fi.tiny), fi.max)


# Newton-Schulz and its residual monitor are f32 algorithms: the stopping
# rule, the 1e-6 tolerance and NS_FALLBACK_RESIDUAL all assume f32
# products. A TPU's DEFAULT matmul precision rounds f32 operands to bf16
# (one MXU pass, ~2^-9 per product), and the iteration's attainable
# residual is ~kappa times that: measured on a v5e (PR 21) on factors
# with kappa ~300, the default stalled at 1-2e-2 where HIGHEST reaches
# 1e-6 in as many iterations. So every product of the solve — here and
# in the engines' residual monitor — names this precision.
NS_PRECISION = jax.lax.Precision.HIGHEST

# A cold solve's scaled phase (``newton_schulz_inverse_info``) runs while
# its lower bound on the eigenvalues of ``M X`` is under NS_SCALED_UNTIL.
# From 0.99 two plain steps reach a 1e-6 residual (errors 1e-2, 1e-4, 1e-8).
# Chosen on a v5e from the trips (PR 30): gpt2-small's 72 A factors after 11
# EMA updates, every bucket solved cold from ``l`` of 4e-4 to 4e-3, took
# 54 trips plain and 36 / 38 / 38 / 35 / 34 / 34 with the switch at 0.5 /
# 0.8 / 0.9 / 0.95 / 0.99 / 0.999 (1,055 ms; 697 / 704 / 706 / 694 / 640 /
# 639): a late scaled step squares and quarters the worst error where a
# plain one only squares it, and what is left is the stopping rule's own
# one to four trips at the float32 floor.
# It starts from a bound no smaller than NS_SCALE_FROM: under it float32
# cannot invert the factor by any iteration, and measured on ill-conditioned
# factors (kappa 1e8 and up, CPU float32, PR 30) a phase started from 1e-7
# ended on a residual a quarter worse than the plain iteration's and one
# from the true bound on inf, where one from 1e-6 ended level with it.
NS_SCALED_UNTIL = 0.99
NS_SCALE_FROM = 1e-6


def newton_schulz_step(
    m: jax.Array, x: jax.Array, mx: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One Newton-Schulz iteration on ``m = factor + damping*I``
    with ``mx`` the cached ``m @ x``: ``(x_new, mx_new, resid)`` where
    ``x_new = x (2I - mx)``, ``mx_new = m x_new`` and ``resid =
    ||I - mx_new||_F / sqrt(d)``. A scaled step ``a x (2I - a mx)``
    (:func:`newton_schulz_inverse_info`) is this step on ``(a x, a mx)``:
    the pair stays consistent (``m (a x) = a mx``), so the step has one
    form and three arguments (the benchmark's control swaps it for one
    that rounds its operands). XLA's own tiling of the two products
    runs at 27-30 TFLOP/s of the six-pass f32 peak's 32.8 on a v5e (PR
    26), level with a Mosaic pair that fused the residual in (see
    ``pallas_ns``'s docstring for what that pair was and cost)."""
    d = m.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)
    x_new = jnp.matmul(x, 2.0 * eye - mx, precision=NS_PRECISION)
    mx_new = jnp.matmul(m, x_new, precision=NS_PRECISION)
    resid = jnp.linalg.norm(eye - mx_new) / jnp.sqrt(
        jnp.asarray(d, jnp.float32)
    )
    return x_new, mx_new, resid


class NewtonSchulzInfo(NamedTuple):
    """Result of the residual-monitored Newton-Schulz inversion.

    ``inverse``: the damped inverse (inv_dtype); ``residual``: final
    relative identity residual ``||I - M X||_F / sqrt(d)`` (fp32 scalar);
    ``iterations``: matmul-pair iterations actually executed (int32 scalar,
    <= the cap when the tolerance or the fp32 floor was reached early);
    ``warm``: the warm start ``x0`` passed its up-front test and the
    iteration began from it (bool scalar; False without an ``x0``);
    ``restarted``: that warm start was then abandoned, and the solve
    started over from the cold init (bool scalar: ``warm & ~restarted``
    is a warm start that paid off);
    ``scaled``: the iterations among ``iterations`` that were scaled
    steps of a cold start (int32 scalar; 0 for a warm start that held);
    ``cold_preferred``: ``x0`` passed the ``< 0.5`` test and was set
    aside all the same, because the cold start's worst direction was
    provably no worse than the warm start's average one (bool scalar;
    the iteration then began cold, so ``warm & cold_preferred`` is never
    true).
    """

    inverse: jax.Array
    residual: jax.Array
    iterations: jax.Array
    warm: jax.Array
    restarted: jax.Array
    scaled: jax.Array
    cold_preferred: jax.Array


def newton_schulz_inverse_info(
    factor: jax.Array,
    damping: float | jax.Array,
    inv_dtype: jnp.dtype = jnp.float32,
    max_iters: int = 40,
    tol: float = 1e-6,
    differentiable: bool = False,
    x0: jax.Array | None = None,
    floor: float | jax.Array = 0.0,
) -> NewtonSchulzInfo:
    """Tikhonov-damped inverse by Newton-Schulz — matmuls only, with a
    residual-based stopping rule and convergence diagnostics.

    ``x0`` optionally warm-starts the iteration — engines pass the
    PREVIOUS inverse at each ``inv_update_steps`` refresh: the factor EMA
    moves slowly, so the old inverse sits deep inside the quadratic
    convergence basin and the refresh needs a handful of iterations
    instead of the cold ~log4(kappa)+5. Safeguarded three times. Up
    front, the warm init is used only when its own residual
    ``r_warm = ||I - M X0||_F/sqrt(d) < 0.5``, else the Gershgorin cold
    start runs — an all-zeros x0 (a fresh engine state) therefore falls
    back automatically. Free: the safeguard's ``M @ X0`` product is the
    iteration's first cached ``mx``, so a warm call costs no extra
    matmuls over a cold one. Also up front, a warm start that passed is
    still set aside where the cold start is provably the cheap one:
    ``r_warm >= 1 - l``, ``l`` the cold start's lower bound on the
    eigenvalues of ``M X_cold`` ("Scaled phase" below, before its clip).
    The iteration squares ``I - M X`` every trip, so the worst direction
    sets the trips. The cold start's is at most ``1 - l``: its error
    matrix ``I - M/||M||_inf`` is symmetric with eigenvalues in
    ``[0, 1 - l]``. A warm start's is at least about its RMS residual
    (an average never exceeds the largest; exactly equal where
    ``I - M X0`` is a multiple of the identity, which is the measured
    case: through the early phase of a run a factor is its identity init
    to 1e-10, the cold start *is* its inverse, and the previous inverse
    is off by the identity weight's decay, residual 0.40, four trips).
    So the comparison holds a *bound* on one side's worst against an
    *RMS* of the other's, and errs only towards keeping the warm start:
    where it flips, the cold start converges no slower in its worst
    direction, costs no product to form and is not on probation. And
    since ``r_warm < 0.5``, a flip implies ``l > 0.5``: at most two
    scaled trips (0.5 -> 0.889 -> 0.9965) and two plain ones, the four a
    warm start from 0.4 pays (0.4 -> 0.16 -> 0.026 -> 6.5e-4 -> 4e-7),
    and fewer the nearer ``l`` is to 1, down to none. A factor with a
    real spectrum has ``l`` near 0 and keeps its warm start exactly as
    before, bit for bit (``cold_preferred`` says which happened).
    Afterwards: neither test can see the spectral radius of
    ``I - M X0``, which is what convergence depends on, so a
    warm-started solve that ends above ``NS_FALLBACK_RESIDUAL`` starts
    over once from the cold init inside the same loop (``iterations``
    counts both attempts).

    ``X_{k+1} = X_k (2I - M X_k)`` with ``M = factor + damping*I`` converges
    quadratically to ``M^{-1}`` whenever ``||I - M X_0|| < 1``; the init
    ``X_0 = I / ||M||_inf`` guarantees that for symmetric PSD ``M``
    (Gershgorin: the max absolute row sum bounds lambda_max — much tighter
    than trace, whose overshoot costs log2(d) extra iterations). Per
    eigenvalue the plain step's error is ``(1 - lam/||M||_inf)^(2^k)``: the
    smallest eigenvalue of ``M X`` only doubles an iteration until it is
    near 1, ~``log2(kappa) + 5`` iterations in all. The default cap of 40
    covers condition numbers beyond 1e9 — far past the fp32 accuracy floor,
    so in practice the *stopping rule* ends the loop, not the cap.

    Scaled phase (cold starts only; Pan & Schreiber 1991). With the
    eigenvalues of ``M X`` known to lie in ``[l, 1]``, the step
    ``X <- a X (2I - a M X)`` with ``a = 2/(1 + l)`` maps them into
    ``[l', 1]``, ``l' = 4l/(1 + l)^2``: the bound *quadruples* an
    iteration, for the same two products, and ``a -> 1`` as ``l -> 1``. A
    cold start knows ``l = (floor + damping) / ||M||_inf``, where ``floor``
    is what the caller knows under the *factor's* smallest eigenvalue (0 if
    nothing; the engines pass :func:`identity_floor`; a per-slot vector
    under ``vmap``), or ``1 - r_0 sqrt(d)`` where its own residual says
    more (no eigenvalue's error exceeds the root of the sum of their
    squares: a factor that is nearly a multiple of the identity is not
    folded down to a loose ``damping / ||M||_inf`` and brought back), and
    carries ``l`` through the loop: while ``l < NS_SCALED_UNTIL`` the step
    is scaled, after that it is the plain one, ~``log4(kappa) + 5``
    iterations in all when the bound is tight. No
    margin is taken off the bound, because none is needed: ``a < 2`` and
    eigenvalues ``<= 1`` keep every eigenvalue in ``(0, 1]`` whatever ``l``
    is, so a bound that is wrong or loose costs iterations (one per factor
    of 4 too low; and too high by ``c`` ends the phase ``log4(c)`` steps
    early, the plain steps doubling from there), never convergence, and a
    relative error in it does not grow. Two limits: a bound under
    ``NS_SCALE_FROM`` is read as that (it claims a condition number float32
    cannot invert, and a scaled step has no slack for the rounding of
    ``M X`` above 1 beyond ``l`` itself), and a bound at or above
    ``NS_SCALED_UNTIL`` means no scaled step at all. A warm start that
    passes its test runs the plain iteration from its first step (nothing
    bounds its eigenvalues from below); one that restarts from probation
    re-enters the scaled phase from the cold ``l``.

    The loop (``lax.while_loop``) monitors the relative identity residual
    ``r_k = ||I - M X_k||_F / sqrt(d)`` — computed from the ``M @ X``
    product the iteration needs anyway, so monitoring costs one elementwise
    pass + reduction per iteration, no extra matmul — and stops when ANY of:

    - ``r_k <= tol`` (converged: early exit saves the remaining matmuls);
    - ``r_k >= r_{k-1}`` (stagnation: the iteration hit its fp32 limiting
      accuracy ``O(kappa * eps)`` — quadratic convergence means the
      residual strictly shrinks until roundoff takes over, so the first
      non-improving step marks the floor; continuing would only oscillate).
      Not applied against a scaled step's residual, which need not fall:
      the directions already converged are pushed out to ``(1-l)/(1+l)``
      on purpose. The plain steps after the phase decide the returned
      residual, by this rule as before;
    - ``k == max_iters`` (cap — a backstop, see above).

    The returned ``residual`` is the honest quality statement: callers that
    need a guarantee check it (``damped_inverse(solver='auto')`` falls back
    to Cholesky above a threshold) instead of trusting a fixed iteration
    count. A NaN/Inf factor yields a NaN residual, which compares False
    against the improvement test and exits on the next iteration — the
    diagnostics surface the poison instead of looping on it.

    This is the TPU-native decomposition path: ``eigh``/``cholesky`` lower
    to sequential panel algorithms that leave the MXU idle and compile
    slowly (measured on v5e: eigh(2048) ~140 ms and tens of seconds of
    compile per distinct shape), while Newton-Schulz is ``2*iters`` dense
    matmuls that XLA tiles perfectly. It fills the role cuSOLVER plays for
    the reference (kfac/layers/inverse.py:186-213) with the hardware's
    preferred primitive. The batched form is just ``jax.vmap`` (all lanes
    run until the slowest lane's stopping rule fires).

    Differentiability: ``lax.while_loop`` has no transpose rule, so the
    default path is NOT reverse-differentiable — callers that
    differentiate THROUGH the preconditioner (meta-learning on the K-FAC
    step) must pass ``differentiable=True``, which runs a fixed
    ``max_iters``-step ``lax.scan`` with ``where``-frozen lanes: identical
    outputs (once a lane stops, nothing changes), reverse-mode works, but
    every call pays all ``2 * max_iters`` matmuls regardless of early
    convergence.
    """
    f = factor.astype(jnp.float32)
    d = f.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)
    m = f + damping * eye
    lam_max = jnp.max(jnp.sum(jnp.abs(m), axis=-1))  # Gershgorin bound
    sqrt_d = jnp.sqrt(jnp.asarray(d, jnp.float32))

    def residual(mx):
        return jnp.linalg.norm(eye - mx) / sqrt_d

    # Carry invariant: ``resid`` is the residual OF the carried ``x``
    # (``mx`` is the cached ``m @ x`` it was measured from), so the
    # diagnostics returned on exit describe the matrix the caller receives
    # — including on a stagnation stop, where the last update made things
    # (marginally) worse and the reported residual honestly says so. Each
    # body still costs exactly two matmuls: the update reuses the cached
    # ``mx`` and the new residual's product is next iteration's cache.
    def running(resid, prev, k):
        return (k < max_iters) & (resid > tol) & (resid < prev)

    # A warm start is on probation. The ``< 0.5`` test below reads the
    # RMS residual, which says nothing about the one direction that
    # decides convergence: the iteration squares ``I - M X`` each step,
    # so it converges only if that matrix's spectral radius is < 1, and a
    # factor whose top eigenvalue grew between refreshes (early training,
    # while the EMA still forgets its identity init) breaks that in a
    # single direction the RMS barely registers — measured on a v5e
    # (PR 21): the d512 LM's q/k/v/o A factors warm-started at RMS 0.1,
    # diverged, and were served with residual 0.5-1.6. Norm bounds on the
    # radius are sound but sit near 1 even for a good start, so the test
    # is the outcome: a warm-started solve that stops above the
    # usable-residual line starts over from the Gershgorin init, once.
    def needs_restart(resid, prev, k, on_probation):
        return (
            on_probation
            & ~running(resid, prev, k)
            & ~(resid <= NS_FALLBACK_RESIDUAL)  # NaN restarts too
            & (k < max_iters)
        )

    def cond(carry):
        _, _, resid, prev, k, on_probation, _, _ = carry
        return running(resid, prev, k) | needs_restart(
            resid, prev, k, on_probation
        )

    x_cold = eye / lam_max
    mx_cold = m / lam_max  # == m @ x_cold, sans the matmul
    r_cold = residual(mx_cold)
    # the cold start's eigenvalue bound (see "Scaled phase" above); the
    # schedule of step sizes, and which start is taken, are no part of
    # what a caller differentiates
    l_bound = jax.lax.stop_gradient(
        jnp.maximum((floor + damping) / lam_max, 1.0 - r_cold * sqrt_d)
    )
    l_cold = jnp.clip(l_bound, NS_SCALE_FROM, NS_SCALED_UNTIL)
    inf = lam_max * 0.0 + jnp.inf

    def body(carry):
        """One iteration — from the cold init instead, if the warm start
        just failed."""
        x, mx, resid, prev, k, on_probation, l, scaled = carry
        restart = needs_restart(resid, prev, k, on_probation)
        x = jnp.where(restart, x_cold, x)
        mx = jnp.where(restart, mx_cold, mx)
        resid = jnp.where(restart, r_cold, resid)
        l = jnp.where(restart, l_cold, l)
        scaling = l < NS_SCALED_UNTIL
        # alpha = 1 leaves both operands as they are, to the bit
        alpha = jnp.where(scaling, 2.0 / (1.0 + l), 1.0)
        x_new, mx_new, r_new = newton_schulz_step(m, alpha * x, alpha * mx)
        l_new = jnp.where(scaling, 4.0 * l / jnp.square(1.0 + l), l)
        # a scaled step's residual need not fall (the converged directions
        # are pushed out to (1-l)/(1+l) on purpose): no stagnation test
        # against it, which is what an infinite ``prev`` says
        return (
            x_new, mx_new, r_new, jnp.where(scaling, inf, resid), k + 1,
            on_probation & ~restart, l_new,
            scaled + scaling.astype(jnp.int32),
        )

    if x0 is not None:
        # safeguarded warm start: keep the caller's init only if it is
        # plausibly inside the convergence region, else the Gershgorin
        # cold start (jnp.where keeps this vmap/shard_map-friendly). The
        # m @ warm product doubles as the iteration's cached mx0, and the
        # cold init's product is a scalar rescale of m — so the warm
        # start costs NO extra matmul over a cold start. And keep it
        # only if the cold start is not provably as good: the trips go
        # by the worst direction of ``I - M X``, which for the cold
        # start is at most ``1 - l_bound`` (a bound) and for the warm
        # one at least about ``r_warm`` (an RMS: the worst is no
        # smaller), so ``r_warm >= 1 - l_bound`` can only flip a slot
        # whose cold solve is the cheaper or the same, two scalars that
        # are both on hand.
        warm = x0.astype(jnp.float32)
        m_warm = jnp.matmul(m, warm, precision=NS_PRECISION)
        r_warm = residual(m_warm)
        accepted = r_warm < 0.5
        use_warm = accepted & (r_warm < 1.0 - l_bound)
        cold_preferred = accepted & ~use_warm
        x0 = jnp.where(use_warm, warm, x_cold)
        mx0 = jnp.where(use_warm, m_warm, mx_cold)
        # a warm start runs the plain iteration: nothing bounds its
        # eigenvalues from below
        l0 = jnp.where(use_warm, NS_SCALED_UNTIL, l_cold)
    else:
        x0, mx0, l0 = x_cold, mx_cold, l_cold
        use_warm = lam_max < 0.0  # False, typed like the rest of the carry
        cold_preferred = use_warm

    # prev starts at inf so the first step always runs; it and the
    # counter derive from lam_max (not a fresh constant) so that under
    # shard_map the carry init has the same varying-manual-axes type as
    # what the body computes from ``m``.
    init = (
        x0, mx0, residual(mx0), inf, 0, use_warm, l0,
        (lam_max * 0.0).astype(jnp.int32),
    )
    if differentiable:
        # fixed-trip scan with where-frozen lanes: same outputs as the
        # while_loop (frozen lanes never change), reverse-differentiable
        def scan_body(carry, _):
            active = cond(carry)
            return jax.tree_util.tree_map(
                lambda n, c: jnp.where(active, n, c), body(carry), carry
            ), None

        (x, _, resid, _, k, on_probation, _, scaled), _ = jax.lax.scan(
            scan_body, init, None, length=max_iters
        )
    else:
        x, _, resid, _, k, on_probation, _, scaled = jax.lax.while_loop(
            cond, body, init
        )
    return NewtonSchulzInfo(
        inverse=x.astype(inv_dtype),
        residual=resid,
        iterations=jnp.asarray(k, jnp.int32),
        warm=use_warm,
        # probation starts as ``use_warm`` and ends only at a restart
        restarted=use_warm & ~on_probation,
        scaled=scaled,
        cold_preferred=cold_preferred,
    )


def newton_schulz_inverse(
    factor: jax.Array,
    damping: float | jax.Array,
    inv_dtype: jnp.dtype = jnp.float32,
    iters: int = 40,
    tol: float = 1e-6,
    differentiable: bool = False,
    x0: jax.Array | None = None,
    floor: float | jax.Array = 0.0,
) -> jax.Array:
    """Newton-Schulz damped inverse (see ``newton_schulz_inverse_info`` for
    the iteration, stopping rule, accuracy, warm start, the ``floor`` of
    a cold start's scaled phase, and the ``differentiable`` fixed-trip
    variant for callers that differentiate through it)."""
    return newton_schulz_inverse_info(
        factor, damping, inv_dtype, max_iters=iters, tol=tol,
        differentiable=differentiable, x0=x0, floor=floor,
    ).inverse


# Residual above which an fp32 inverse is considered unusable for
# preconditioning and 'auto' re-solves via Cholesky: 5e-2 relative identity
# residual means per-direction errors of a few percent — well past the
# factor-EMA noise floor a preconditioner tolerates. Below it, NS at its
# fp32 limiting accuracy is comparable to any fp32 solve (both are
# O(kappa * eps)) and the fallback would buy nothing.
NS_FALLBACK_RESIDUAL = 5e-2


def damped_inverse(
    factor: jax.Array,
    damping: float | jax.Array,
    inv_dtype: jnp.dtype = jnp.float32,
    solver: str = 'cholesky',
    iters: int = 40,
    x0: jax.Array | None = None,
    floor: float | jax.Array = 0.0,
) -> jax.Array:
    """Solver-dispatched damped inverse — the single place the
    ``inverse_solver`` config option is interpreted (dense, KAISA, and
    pipeline engines all call this).

    Solvers: ``'cholesky'`` (direct, backward-stable), ``'newton_schulz'``
    (matmul-only, residual-monitored — the TPU default), ``'auto'``
    (Newton-Schulz, then ``lax.cond``-falls back to Cholesky when the final
    residual exceeds ``NS_FALLBACK_RESIDUAL``, i.e. the factor was too
    ill-conditioned for the fp32 iteration). Note ``'auto'`` under ``vmap``
    lowers the cond to a select that executes BOTH branches batched; for
    stacked/batched callers use :func:`batched_damped_inverse_auto_info`,
    whose single scalar cond pays the Cholesky only when some slot
    actually needs it (the stacked KAISA engine does this). ``floor``
    (what the engine knows under the factor's smallest eigenvalue:
    :func:`identity_floor`) only speeds a cold Newton-Schulz solve; the
    Cholesky solver ignores it.
    """
    if solver == 'newton_schulz':
        return newton_schulz_inverse(
            factor, damping, inv_dtype, iters=iters, x0=x0, floor=floor
        )
    if solver == 'auto':
        info = newton_schulz_inverse_info(
            factor, damping, jnp.float32, max_iters=iters, x0=x0, floor=floor
        )
        bad = ~(info.residual <= NS_FALLBACK_RESIDUAL)  # NaN residual -> bad
        out = jax.lax.cond(
            bad,
            lambda: compute_inverse(factor, damping, jnp.float32),
            lambda: info.inverse,
        )
        return out.astype(inv_dtype)
    return compute_inverse(factor, damping, inv_dtype)


def batched_damped_inverse_auto_info(
    stack: jax.Array,
    damping: float | jax.Array,
    inv_dtype: jnp.dtype = jnp.float32,
    iters: int = 40,
    x0: jax.Array | None = None,
    floor: float | jax.Array = 0.0,
) -> NewtonSchulzInfo:
    """Batched ``'auto'`` inverse paying Cholesky only when NS fails.

    ``vmap(damped_inverse(..., 'auto'))`` lowers the per-matrix
    ``lax.cond`` to a select that executes BOTH solvers for every slot —
    the batched Cholesky is paid unconditionally. Here the Newton-Schulz
    pass runs batched, and ONE scalar ``lax.cond`` over the whole stack
    (a real runtime branch — legal at rank 0, e.g. inside shard_map's
    per-device body where the stacked engine calls this) runs the
    batched Cholesky only when some slot's residual exceeds
    ``NS_FALLBACK_RESIDUAL``, then selects per slot. The common
    (well-conditioned) case costs pure MXU matmuls.

    ``damping`` may be a scalar or a per-slot ``(n,)`` vector (per-layer
    escalated damping under factor quarantine) — broadcast into the vmap,
    as ``floor`` is (``newton_schulz_inverse_info``).

    Returns the batched Newton-Schulz pass's :class:`NewtonSchulzInfo`
    with ``inverse`` replaced by the served stack: the other fields stay
    the iteration's own (a slot served by Cholesky keeps the residual
    that condemned it).
    """
    dmp, flr = (
        jnp.broadcast_to(jnp.asarray(v, jnp.float32), stack.shape[:-2])
        for v in (damping, floor)
    )
    if x0 is None:
        infos = jax.vmap(
            lambda m, dm, fl: newton_schulz_inverse_info(
                m, dm, jnp.float32, max_iters=iters, floor=fl
            )
        )(stack, dmp, flr)
    else:
        infos = jax.vmap(
            lambda m, dm, fl, w: newton_schulz_inverse_info(
                m, dm, jnp.float32, max_iters=iters, x0=w, floor=fl
            )
        )(stack, dmp, flr, x0)
    bad = ~(infos.residual <= NS_FALLBACK_RESIDUAL)  # (n,); NaN -> bad

    def fallback(_):
        chol = jax.vmap(
            lambda m, dm: compute_inverse(m, dm, jnp.float32)
        )(stack, dmp)
        return jnp.where(bad[:, None, None], chol, infos.inverse)

    out = jax.lax.cond(jnp.any(bad), fallback, lambda _: infos.inverse, None)
    return infos._replace(inverse=out.astype(inv_dtype))


def eigen_preconditioned_grad(
    grad: jax.Array,
    a: EigenDecomp,
    g: EigenDecomp,
    damping: float | jax.Array,
) -> jax.Array:
    """Precondition a (d_out, d_in) gradient via the eigen basis.

    ``qg @ [ (qg^T grad qa) / (dg (x) da + damping) ] @ qa^T`` — four matmuls
    plus one elementwise op, all MXU-friendly. Reference:
    kfac/layers/eigen.py:350-385.
    """
    grad_dtype = grad.dtype
    grad = grad.astype(a.q.dtype)
    v1 = g.q.T @ grad @ a.q
    v2 = v1 / (jnp.outer(g.d, a.d) + damping)
    out = g.q @ v2 @ a.q.T
    return out.astype(grad_dtype)


def prediv_eigenvalues(
    a: EigenDecomp,
    g: EigenDecomp,
    damping: float | jax.Array,
) -> jax.Array:
    """Precompute ``1 / (dg (x) da + damping)`` (d_out, d_in).

    Trades memory (d_out*d_in) for one fewer elementwise pass per step.
    Reference: kfac/layers/eigen.py:345-348.
    """
    return 1.0 / (jnp.outer(g.d, a.d) + damping)


def inverse_preconditioned_grad(
    grad: jax.Array,
    a_inv: jax.Array,
    g_inv: jax.Array,
) -> jax.Array:
    """Precondition via explicit inverses: ``g_inv @ grad @ a_inv``.

    Reference: kfac/layers/inverse.py:215-234.
    """
    grad_dtype = grad.dtype
    grad = grad.astype(a_inv.dtype)
    return (g_inv @ grad @ a_inv).astype(grad_dtype)


def kl_clip_scale(
    vg_sum: jax.Array,
    kl_clip: float | jax.Array,
) -> jax.Array:
    """Gradient scale ``min(1, sqrt(kl_clip / |sum v*g*lr^2|))``.

    ``vg_sum`` is the single fused reduction over all layers of
    ``precond_grad * grad * lr^2`` — computed on device as one scalar, unlike
    the reference's per-layer ``.item()`` host syncs
    (kfac/base_preconditioner.py:411-435).
    """
    vg_abs = jnp.abs(vg_sum)
    safe = jnp.where(vg_abs == 0.0, 1.0, vg_abs)
    scale = jnp.minimum(1.0, jnp.sqrt(kl_clip / safe))
    return jnp.where(vg_abs == 0.0, 1.0, scale)


def kl_clip_terms(
    pmat: jax.Array,
    gmat: jax.Array,
    lr: float | jax.Array,
) -> jax.Array:
    """One layer's (or one run of layers') term of the kl-clip second
    moment: ``sum(pmat * gmat) * lr^2`` in f32, over every axis.

    This is the contraction every engine sums across layers before
    :func:`kl_clip_scale`: XLA's multiply-reduce for every shape, backend
    and device count. It reads its operands where they lie (a slice of a
    batched product is an operand of the fusion, not a copy), which the
    Mosaic pair in :mod:`kfac_tpu.ops.pallas_ns` that ran here until
    PR 37 could not: a custom call takes whole buffers. On a v5e eight
    2,048 x 1,536 slices of a stack reduce at 732 GB/s this way and at
    231 GB/s through the pair's 128 x 128 tiles; alone, with its
    operands in fast memory, the pair is 2.1-2.9x slower at every shape
    the benchmark's cells have (``PERF.md`` section 6, PR 37).
    """
    dot = jnp.sum(pmat.astype(jnp.float32) * gmat.astype(jnp.float32))
    return dot * (lr ** 2)


def kl_clip_apply(pmat: jax.Array, scale: jax.Array) -> jax.Array:
    """Apply the kl-clip scale to one preconditioned gradient:
    ``(pmat_f32 * scale)`` cast back to ``pmat``'s dtype.

    An elementwise multiply the compiler fuses into whatever reads the
    gradient next (the cast to the leaf's dtype, the optimizer's update):
    it writes no buffer of its own.
    """
    return (pmat.astype(jnp.float32) * scale).astype(pmat.dtype)
