"""Curvature capture: A/G statistics as part of the differentiated program.

TPU-native replacement for the reference's autograd hooks
(kfac/base_preconditioner.py:132-135,437-479; kfac/layers/base.py:345-373).
JAX has no hooks and no mutable ``.grad``; instead:

- **A factors** are computed inside the forward trace by a flax method
  interceptor and returned as auxiliary outputs. Only the d_in^2 covariance is
  kept — never the raw activations — so activation memory is O(d^2), not
  O(batch*d) (the reference reduces in-hook for the same reason).
- **G factors** use a ``custom_vjp`` identity "g-tap" on each layer output:
  its backward rule computes ``g^T g / N`` *inside the backward pass* and
  routes it out as the cotangent of a zero dummy argument. One
  ``jax.value_and_grad`` call therefore yields loss, gradients, A stats, and
  G stats, and XLA fuses the covariance matmuls into fwd/bwd — the analogue of
  the reference's hook-async overlap (SURVEY.md section 3.2) falls out for
  free from XLA scheduling.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfac_tpu import tracing
from kfac_tpu.layers import helpers as helpers_lib
from kfac_tpu.layers import registry as registry_lib


def layer_input(module: nn.Module, a: jax.Array) -> jax.Array:
    """The A-side tap as the layer's own product sees it: rounded to the
    dtype the module computes in (flax: its ``dtype``, or without one the
    promotion of input and parameters). A norm in float32 feeding a
    bfloat16 layer hands over float32 values the layer then rounds, and
    the covariance multiplies what the layer multiplies
    (:func:`kfac_tpu.ops.cov.get_cov`). The G side needs no such step: a
    cotangent arrives in the dtype of the layer's output.
    """
    return jax.lax.stop_gradient(a).astype(
        registry_lib.compute_dtype(module, a.dtype)
    )


def _make_gtap(helper: helpers_lib.LayerHelper) -> Callable[..., jax.Array]:
    """Identity on ``y`` whose vjp emits the layer G factor into ``gstat``."""

    @jax.custom_vjp
    def gtap(y: jax.Array, gstat: Any) -> jax.Array:
        del gstat
        return y

    def fwd(y: jax.Array, gstat: Any):
        del gstat
        return y, None

    def bwd(_, ybar: jax.Array):
        # weighted (routed) helpers emit (w_i * G_i, w_i) with the
        # weight derived from the COTANGENT's live rows (matching
        # routed_linear_g_factor's own row detection), so repeated
        # invocations sum traffic-weighted and the divisor tracks G
        # mass rather than input mass (see g_factor_for_sum /
        # g_capture_weight)
        with tracing.capture_scope('g'):
            if helper.weighted:
                return ybar, (
                    helper.g_factor_for_sum(ybar),
                    helper.g_capture_weight(ybar),
                )
            return ybar, helper.g_factor_for_sum(ybar)

    gtap.defvjp(fwd, bwd)
    return gtap


def _make_role_gtap(
    helper: helpers_lib.LoRAHelper, role: str
) -> Callable[..., jax.Array]:
    """Identity g-tap for one adapter of a fused LoRA unit.

    Its vjp emits the role's G block embedded in the unit's
    block-diagonal G factor. Both roles' taps share the unit's single
    zero dummy argument, so their cotangents SUM — the role blocks are
    pre-scaled by the role count (helpers.LoRAHelper._embed) and the
    capture's shared invocation counter divides the sum back to the true
    block-diagonal factor.
    """

    @jax.custom_vjp
    def gtap(y: jax.Array, gstat: Any) -> jax.Array:
        del gstat
        return y

    def fwd(y: jax.Array, gstat: Any):
        del gstat
        return y, None

    def bwd(_, ybar: jax.Array):
        with tracing.capture_scope('g'):
            return ybar, helper.role_g_factor(role, ybar)

    gtap.defvjp(fwd, bwd)
    return gtap


def _make_stack_gtap(
    tap: helpers_lib.ExpertStackTap,
) -> Callable[..., jax.Array]:
    """Identity g-tap of a stacked expert projection: its vjp emits, as
    the cotangent of the stack's one dummy argument, every held expert's
    G factor over its own rows pre-scaled by its capture weight (1 with
    rows, 0 without), those weights, and the plan's traffic counters."""

    @jax.custom_vjp
    def gtap(y: jax.Array, gstat: Any, plan: Any) -> jax.Array:
        del gstat, plan
        return y

    def fwd(y: jax.Array, gstat: Any, plan: Any):
        del gstat
        return y, plan

    def bwd(plan: Any, ybar: jax.Array):
        with tracing.capture_scope('g'), tracing.capture_scope('experts'):
            # no ``* live``: an expert without rows has zero sums already
            g = tap.g_factors(ybar, plan)
        return ybar, (g, tap.live(plan), tap.traffic(plan)), None

    gtap.defvjp(fwd, bwd)
    return gtap


def contract_late(
    registry: registry_lib.Registry, a_stats: dict[str, Any], after: Any
) -> dict[str, jax.Array]:
    """The A factors :meth:`CurvatureCapture.tapped` left uncontracted
    (``LayerHelper.contracts_late``), contracted once ``after`` (the
    gradients) exists.

    Such a layer hands on its inputs, a tuple with one entry an
    invocation, and its factor (their factors' sum) is made here from
    each input times a one the compiler cannot see through before
    ``after`` is computed. What the products read is then a value of
    their own, made from what the backward pass keeps anyway and gone
    with them, and the step's fullest moment (the start of the backward
    pass) holds none of their results; contracted in the forward pass
    the layer's input is written out whole there, its weight gradient
    keeps that copy in place of recomputing it, and the finished factors
    wait beside it (0.5 GB of ResNet-50's capture step). The patch rows'
    products need no such help: the compiler already leaves rows nine
    times their input for last.
    """
    late = {n: v for n, v in a_stats.items() if isinstance(v, tuple)}
    if not late:
        return a_stats
    out = dict(a_stats)
    with tracing.capture_scope('a'):
        one, _ = jax.lax.optimization_barrier(
            (jnp.ones((), jnp.float32), after)
        )
        for name, inputs in late.items():
            out[name] = sum(
                registry.layers[name].get_a_factor(a * one.astype(a.dtype))
                for a in inputs
            )
    return out


def expand_stacks(
    registry: registry_lib.Registry, g_stats: dict[str, Any]
) -> tuple[dict[str, Any], dict[str, jax.Array]]:
    """Replace each stacked projection's entry of the g-tap cotangents by
    its experts' ``(weighted G sum, weight)`` pairs under their own layer
    names; returns them with the stacks' traffic counters."""
    out = {n: v for n, v in g_stats.items() if n not in registry.stacks}
    traffic = {}
    for name, tap in registry.stacks.items():
        if name not in g_stats:
            continue
        g, live, traffic[name] = g_stats[name]
        for j, slot in enumerate(tap.slots):
            out[slot] = (g[j], live[j])
    return out, traffic


class CurvatureCapture:
    """Wraps a loss function to also emit per-layer curvature statistics.

    Usage::

        cap = CurvatureCapture(registry)
        (loss, aux), grads, stats = cap.value_stats_and_grad(
            loss_fn, has_aux=False)(params, batch)

    ``loss_fn(params, *args)`` must evaluate the flax model via
    ``model.apply`` (any number of registered modules, shared modules
    allowed — repeated calls accumulate, tracked by ``counts``).
    """

    def __init__(self, registry: registry_lib.Registry):
        self.registry = registry
        # an expert of a stacked projection is tapped through its stack
        self._slots = frozenset(
            slot for tap in registry.stacks.values() for slot in tap.slots
        )
        self._gtaps = {
            name: _make_gtap(helper)
            for name, helper in registry.layers.items()
            if not isinstance(helper, helpers_lib.LoRAHelper)
            and name not in self._slots
        }
        self._stack_gtaps = {
            name: _make_stack_gtap(tap)
            for name, tap in registry.stacks.items()
        }
        # fused units (LoRA adapter pairs) tap at their CHILD module
        # paths; Registry.taps routes each child to (unit, role)
        self._role_gtaps = {
            tap: _make_role_gtap(registry.layers[unit], role)
            for tap, (unit, role) in registry.taps.items()
        }
        # layers (and stacked projections, whose experts follow slot by
        # slot) that read their group leader's A factor: g-tapped like any
        # other, their A contraction left to the leader
        # (``Registry.a_groups``)
        self._followers = {
            n: leader for n, leader in registry.a_groups.items()
            if leader != n
        }
        slot_stack = {
            slot: name for name, tap in registry.stacks.items()
            for slot in tap.slots
        }
        self._follower_stacks = {
            name: slot_stack[self._followers[tap.slots[0]]]
            for name, tap in registry.stacks.items()
            if tap.slots[0] in self._followers
        }

    def zero_gstats(self) -> dict[str, Any]:
        """Zero dummy arguments whose gradients are the G factors.

        Weighted (routed) helpers get a ``(factor, weight)`` pair so the
        g-tap can route out the cotangent live fraction next to the
        weighted G sum; the pairing is static per helper, so the pytree
        structure is stable across steps.
        """
        def zero(h: helpers_lib.LayerHelper):
            fac = jnp.zeros(h.g_factor_shape, dtype=h.factor_dtype)
            if h.weighted:
                return (fac, jnp.zeros((), dtype=h.factor_dtype))
            return fac

        out = {
            name: zero(h) for name, h in self.registry.layers.items()
            if name not in self._slots
        }
        # a stacked projection: its experts' G sums, their capture
        # weights, and the plan's traffic counters (rows, then dropped)
        for name, tap in self.registry.stacks.items():
            e, d = len(tap.slots), tap.out_features
            out[name] = (
                jnp.zeros((e, d, d), tap.factor_dtype),
                jnp.zeros((e,), tap.factor_dtype),
                jnp.zeros((e + 1,), jnp.float32),
            )
        return out

    def tapped(
        self,
        loss_fn: Callable[..., Any],
        has_aux: bool = False,
    ) -> Callable[..., Any]:
        """Return ``f(params, gstats, *args) ->
        (loss, (aux, a_stats, counts, weights))``.

        Differentiating w.r.t. ``gstats`` yields the G factors.
        ``weights`` holds per-capture evidence weights for layers whose
        helper defines one (routed MoE layers); other layers are absent.
        ``a_stats`` holds a layer's summed A factors, or for a layer that
        contracts late the tuple of its inputs: :func:`contract_late`
        makes the factor of those once the gradients exist.
        """
        registry = self.registry
        gtaps = self._gtaps
        role_gtaps = self._role_gtaps
        stack_gtaps = self._stack_gtaps
        followers = self._followers
        follower_stacks = self._follower_stacks

        def wrapped(params: Any, gstats: dict[str, jax.Array], *args: Any, **kwargs: Any):
            a_stats: dict[str, jax.Array] = {}
            counts: dict[str, jax.Array] = {}
            weights: dict[str, jax.Array] = {}
            # what each group leader was last handed: a follower has to
            # meet the very same arrays, as it did at the probe
            led: dict[str, tuple] = {}

            def accumulate(name, fac, weight=None):
                # repeated invocations of one layer sum; ``counts`` (and,
                # for weighted layers, the summed weights) divide in run().
                # ``fac`` None: a follower of an A group, which counts its
                # invocations (its G sums divide by them) and contracts
                # nothing
                if name in counts:
                    if fac is not None:
                        a_stats[name] = a_stats[name] + fac
                    counts[name] = counts[name] + 1
                    if weight is not None:
                        weights[name] = weights[name] + weight
                else:
                    if fac is not None:
                        a_stats[name] = fac
                    counts[name] = jnp.asarray(1, dtype=jnp.int32)
                    if weight is not None:
                        weights[name] = weight

            def follows(name, leader, *handed):
                got = led.get(leader)
                if got is None or len(got) != len(handed) or any(
                    x is not y for x, y in zip(got, handed)
                ):
                    raise ValueError(
                        f'{name!r} shares the A factor of {leader!r} '
                        '(Registry.a_groups: the probe saw both handed one '
                        'array), but this loss_fn hands it another array, '
                        'or calls it first. Register the model the way the '
                        'loss applies it, or empty the map '
                        '(dataclasses.replace(registry, a_groups={})).'
                    )

            def role_tap(mod, name, iargs, ikwargs, next_fun):
                # fused-unit child projection: embed this role's A block
                # into the unit's block-diagonal accumulator and g-tap the
                # child output into the unit's shared G dummy (cotangents
                # of the two roles sum there)
                unit, role = registry.taps[name]
                uhelper = registry.layers[unit]
                with tracing.capture_scope('a'):
                    a = layer_input(mod, iargs[0])
                    a_fac = uhelper.role_a_factor(role, a)
                accumulate(unit, a_fac)
                y = next_fun(*iargs, **ikwargs)
                return role_gtaps[name](y, gstats[unit])

            def stack_tap(name, iargs, ikwargs, next_fun):
                # a stacked expert projection: one tap for every held
                # expert's A factor (over its own rows), filed under the
                # experts' own layer names with weight 1 or, without a
                # row, 0; one g-tap on the projection's output
                tap = registry.stacks[name]
                x, plan = jax.lax.stop_gradient(iargs[0]), iargs[1]
                shared = name in follower_stacks
                if shared:
                    follows(name, follower_stacks[name], iargs[0], plan)
                else:
                    led[name] = (iargs[0], plan)
                with tracing.capture_scope('a'), tracing.capture_scope(
                    'experts'
                ):
                    # no ``* live``: zero sums without rows already
                    live = tap.live(plan)
                    facs = None if shared else tap.a_factors(x, plan)
                for j, slot in enumerate(tap.slots):
                    accumulate(slot, None if shared else facs[j], live[j])
                y = next_fun(*iargs, **ikwargs)
                return stack_gtaps[name](y, gstats[name], plan)

            def interceptor(next_fun, iargs, ikwargs, context):
                mod = context.module
                if context.method_name != '__call__' or not iargs:
                    return next_fun(*iargs, **ikwargs)
                name = registry_lib.path_name(mod.path)
                if name in registry.stacks:
                    return stack_tap(name, iargs, ikwargs, next_fun)
                helper = registry.layers.get(name)
                if isinstance(helper, helpers_lib.LoRAHelper):
                    # the unit module itself carries no tap; its children
                    # (Registry.taps) do
                    return next_fun(*iargs, **ikwargs)
                if helper is None:
                    if name in registry.taps:
                        return role_tap(mod, name, iargs, ikwargs, next_fun)
                    return next_fun(*iargs, **ikwargs)
                shared = name in followers
                if shared:
                    follows(name, followers[name], iargs[0])
                elif name in registry.a_groups:
                    led[name] = (iargs[0],)
                with tracing.capture_scope('a'):
                    a = layer_input(mod, iargs[0])
                    if helper.contracts_late:
                        a_stats[name] = a_stats.get(name, ()) + (a,)
                        a_fac = None
                    else:
                        a_fac = None if shared else helper.get_a_factor(a)
                    if helper.weighted:
                        # traffic-weighted accumulation: sum w_i * F_i
                        # here, divide by sum w_i in run() — a repeated
                        # invocation that saw no tokens contributes
                        # nothing instead of dragging the within-capture
                        # average toward zero (same convention as
                        # accumulate_stats/average_stats)
                        w = helper.capture_weight(a)
                        if not shared:
                            a_fac = a_fac * w
                accumulate(name, a_fac, w if helper.weighted else None)
                y = next_fun(*iargs, **ikwargs)
                return gtaps[name](y, gstats[name])

            with nn.intercept_methods(interceptor):
                out = loss_fn(params, *args, **kwargs)
            if has_aux:
                loss, aux = out
            else:
                loss, aux = out, None
            return loss, (aux, a_stats, counts, weights)

        return wrapped

    def value_stats_and_grad(
        self,
        loss_fn: Callable[..., Any],
        has_aux: bool = False,
    ) -> Callable[..., Any]:
        """One call computing loss, grads, and curvature statistics.

        Returns a function ``f(params, *args) ->
        ((loss, aux), grads, CapturedStats)``. The counts divide repeated
        module invocations (weight sharing / multiple calls), matching the
        reference's per-call accumulation (kfac/layers/base.py:345-373).
        """
        tapped = self.tapped(loss_fn, has_aux=has_aux)
        grad_fn = jax.value_and_grad(tapped, argnums=(0, 1), has_aux=True)

        def run(params: Any, *args: Any, **kwargs: Any):
            gstats_in = self.zero_gstats()
            (loss, (aux, a_stats, counts, weights)), (grads, g_stats) = (
                grad_fn(params, gstats_in, *args, **kwargs)
            )
            g_stats, traffic = expand_stacks(self.registry, g_stats)
            g_sums, g_weights = split_g_stats(g_stats)
            a_stats = contract_late(self.registry, a_stats, grads)
            a_avg = weighted_average(a_stats, counts, weights)
            g_avg = weighted_average(
                {n: g_sums[n] for n in counts}, counts, g_weights
            )
            w_avg = {
                n: weights[n] / counts[n].astype(weights[n].dtype)
                for n in weights
            }
            stats = CapturedStats(a=a_avg, g=g_avg, w=w_avg, traffic=traffic)
            return (loss, aux), grads, stats

        return run


@jax.tree_util.register_pytree_node_class
class CapturedStats:
    """Per-batch factor statistics: name -> A and name -> G matrices.

    ``g`` has every captured layer; ``a`` the layers that contracted an A
    of their own: a follower of an A group (``Registry.a_groups``) reads
    its leader's entry.

    ``w`` optionally carries per-layer evidence weights in [0, 1] (routed
    MoE layers: the live-row fraction). Engines use them to weight the
    factor EMA by actual token traffic (``alpha_eff = 1 - (1-alpha)*w``):
    a capture where an expert saw no tokens leaves its factors unchanged
    instead of diluting them, and light-traffic captures move the running
    estimate proportionally less. Layers absent from ``w`` weigh 1, which
    reduces exactly to the unweighted EMA.
    """

    def __init__(
        self,
        a: dict[str, jax.Array],
        g: dict[str, jax.Array],
        w: dict[str, jax.Array] | None = None,
        traffic: dict[str, jax.Array] | None = None,
    ):
        self.a = a
        self.g = g
        self.w = {} if w is None else w
        # stacked expert projection -> (E_here + 1,) float32: the live
        # rows of each held expert at this capture, then the assignments
        # its plan left out (``helpers.ExpertStackTap.traffic``). Counters
        # for the engine to keep, not statistics: no factor reads them.
        self.traffic = {} if traffic is None else traffic

    def tree_flatten(self):
        # ``a`` and ``g`` each under their own names: a follower of an A
        # group has a G statistic and no A of its own
        groups = (self.a, self.g, self.w, self.traffic)
        names = tuple(tuple(sorted(d)) for d in groups)
        leaves = tuple(d[n] for d, ns in zip(groups, names) for n in ns)
        return leaves, names

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        it = iter(leaves)
        a, g, w, traffic = ({n: next(it) for n in ns} for ns in aux)
        return cls(a=a, g=g, w=w, traffic=traffic)

    def scaled(self, grad_scale: jax.Array | float) -> 'CapturedStats':
        """Unscale G stats computed under a scaled loss (AMP loss scaling).

        G is quadratic in g, so dividing by ``grad_scale**2`` matches the
        reference's per-tensor unscale (kfac/layers/base.py:365-366).
        """
        s2 = grad_scale**2
        return CapturedStats(
            a=self.a,
            g={n: v / s2 for n, v in self.g.items()},
            w=self.w,
            traffic=self.traffic,
        )


def a_stat(
    stats: CapturedStats, registry: registry_lib.Registry, name: str
) -> jax.Array | None:
    """``name``'s A statistic of a capture, ``None`` where the capture did
    not run the layer: its own entry, or the one filed under its A group's
    leader (``Registry.a_groups``: a group's one contraction), whatever
    the engine that asks stores."""
    got = stats.a.get(name)
    return stats.a.get(registry.a_leader(name)) if got is None else got


# Floor for traffic-weight denominators: a fully-starved layer keeps
# factor 0 with weight 0 (the EMA then ignores it) instead of dividing
# 0/0. Shared by every averaging site so the convention cannot drift.
WEIGHT_FLOOR = 1e-8


def split_g_stats(
    g_stats: dict[str, Any],
) -> tuple[dict[str, jax.Array], dict[str, jax.Array]]:
    """Split g-tap cotangents into (factor sums, G-side weight sums).

    Weighted (routed) helpers route out ``(sum w_i G_i, sum w_i)`` pairs
    with ``w_i`` the COTANGENT live fraction; unweighted helpers a bare
    factor sum. Shared by :meth:`CurvatureCapture.value_stats_and_grad`
    and the EP combined capture so both divide weighted G sums by the
    same G-side denominator.
    """
    sums: dict[str, jax.Array] = {}
    g_weights: dict[str, jax.Array] = {}
    for n, v in g_stats.items():
        if isinstance(v, tuple):
            sums[n], g_weights[n] = v
        else:
            sums[n] = v
    return sums, g_weights


def weighted_average(
    sums: dict[str, jax.Array],
    counts: dict[str, jax.Array],
    weights: dict[str, jax.Array],
) -> dict[str, jax.Array]:
    """Average per-invocation accumulator sums into per-capture factors.

    Weighted (routed) layers accumulated ``w_i * F_i`` and divide by
    their summed traffic weight; others divide by the invocation count.
    The ONE implementation of the convention — used by
    :meth:`CurvatureCapture.value_stats_and_grad` and the EP combined
    capture (parallel/expert_parallel.py).
    """
    def denom(n, dtype):
        if n in weights:
            return jnp.maximum(weights[n], WEIGHT_FLOOR).astype(dtype)
        return counts[n].astype(dtype)

    return {n: v / denom(n, v.dtype) for n, v in sums.items()}


def _traffic_scaled(stats: CapturedStats) -> CapturedStats:
    """Scale weighted (routed) layers' factors by their capture weight.

    The accumulator holds ``sum_i w_i * F_i`` for weighted layers and
    plain ``sum_i F_i`` for the rest; :func:`average_stats` divides by
    ``sum_i w_i`` resp. ``num_steps``, so weighted layers combine as the
    traffic-weighted mean of their micro-captures — a micro-step where an
    expert saw no tokens contributes nothing instead of dragging the
    average toward zero.
    """
    return CapturedStats(
        a={
            n: stats.a[n] * stats.w[n] if n in stats.w else stats.a[n]
            for n in stats.a
        },
        g={
            n: stats.g[n] * stats.w[n] if n in stats.w else stats.g[n]
            for n in stats.g
        },
        w=stats.w,
        traffic=stats.traffic,
    )


def accumulate_stats(
    acc: CapturedStats | None,
    new: CapturedStats,
) -> CapturedStats:
    """Sum statistics across gradient-accumulation micro-steps.

    Divide by the number of micro-steps with :func:`average_stats` before
    passing to ``update_factors``, mirroring the reference's accumulation
    counter (kfac/layers/base.py:375-405). Weighted (routed) layers
    accumulate ``w_i * F_i`` — see :func:`_traffic_scaled`.
    """
    new = _traffic_scaled(new)
    if acc is None:
        return new
    return CapturedStats(
        a={n: acc.a[n] + new.a[n] for n in acc.a},
        g={n: acc.g[n] + new.g[n] for n in acc.g},
        w={n: acc.w[n] + new.w[n] for n in acc.w},
        # rows and drops of all the micro-steps together
        traffic={n: acc.traffic[n] + new.traffic[n] for n in acc.traffic},
    )


def average_stats(acc: CapturedStats, num_steps: int | jax.Array) -> CapturedStats:
    """Average accumulated statistics over ``num_steps`` micro-steps.

    Weighted (routed) layers divide by their accumulated traffic weight
    instead — the traffic-weighted mean ``sum(w_i F_i) / sum(w_i)`` — so
    the combined factor matches what one capture over the concatenated
    micro-batches would have produced (up to each micro-capture's own
    normalization). The combined weight is the mean live fraction; a
    layer starved across EVERY micro-step keeps factor 0 with weight 0,
    which the engines' weighted EMA then ignores entirely.
    """
    def div(n, v):
        if n in acc.w:
            return v / jnp.maximum(acc.w[n], WEIGHT_FLOOR)
        return v / num_steps

    return CapturedStats(
        a={n: div(n, v) for n, v in acc.a.items()},
        g={n: div(n, v) for n, v in acc.g.items()},
        w={n: v / num_steps for n, v in acc.w.items()},
        traffic=acc.traffic,
    )
