"""Expert parallelism: all-to-all MoE dispatch over an ``expert`` mesh axis
with exact per-expert K-FAC capture.

Beyond the reference (gpauloski/kfac-pytorch has no MoE/EP support;
SURVEY.md section 2.3) and beyond the TP-overrides expert layout in
:mod:`kfac_tpu.models.moe`: at pod scale experts live on DIFFERENT
devices, tokens travel to their expert and back over the ICI with two
``lax.all_to_all`` collectives, and each device runs only its local
experts on only the tokens routed to them — the Switch/GShard execution
model, expressed as a ``shard_map`` over the mesh's ``expert`` axis
(:func:`kfac_tpu.parallel.mesh.train_mesh` with ``expert > 1``).

Design:

- **Same parameter layout as** :class:`kfac_tpu.models.moe.MoEMLP`
  (``router`` / ``expert{e}_up`` / ``expert{e}_down`` named Dense-style
  dicts), so a dense-trained model serves expert-parallel and vice versa,
  checkpoints interchange, and the K-FAC engines see ordinary per-layer
  gradients with no adapter. The per-expert weights are stacked at trace
  time; the stack's transpose routes gradients back per expert.
- **Dispatch**: tokens shard over data+expert axes. Each device packs its
  local tokens into per-expert capacity buffers via one-hot einsums
  (static shapes, MXU-friendly — same scheme as MoEMLP's capacity path),
  then ``all_to_all`` over the expert axis splits the E dim and
  concatenates the slot dim: every device ends with ITS experts' buffers
  holding tokens from ALL expert-axis peers. After the expert FFN, the
  inverse ``all_to_all`` returns outputs to their tokens' devices for the
  local combine. Both collectives are differentiable (their transpose is
  the opposite all-to-all), so one ``value_and_grad`` spans the whole
  exchange.
- **Exact per-expert K-FAC capture**, matching the routed-capture
  semantics (``ops.cov.routed_linear_{a,g}_factor``: live-row
  normalization, bias ones on live rows only — the per-expert oracle):
  A factors are computed inside the body from the received buffers and
  psum over the data axes; G factors ride custom_vjp g-taps whose dummy
  inputs are replicated over the data axes, so ``shard_map``'s transpose
  inserts the data-axis psum of the local ``g^T g`` sums for free. The
  router captures standard (non-routed) factors reduced over data+expert.
  Stats come out as the same ``{name: factor}`` dicts the interceptor
  capture produces, so :class:`kfac_tpu.KFACPreconditioner` preconditions
  expert layers unchanged.

Equivalence (tested): with enough capacity to avoid drops, output, loss,
gradients, AND captured statistics match ``MoEMLP``'s dense masked path
with routed registry capture on the same parameters.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.layers import helpers as helpers_lib
from kfac_tpu.layers import registry as registry_lib
from kfac_tpu.parallel import mesh as mesh_lib


@dataclasses.dataclass(frozen=True)
class EPSwitchFFN:
    """Expert-parallel top-1 (switch) FFN over a mesh with an expert axis.

    ``capacity_factor`` sizes each expert's LOCAL slot buffer as
    ``ceil(capacity_factor * local_tokens / num_experts)``; global
    capacity per expert is that times the expert-axis size. Overflow
    tokens drop to the residual path (standard switch semantics;
    ``capacity_factor >= num_experts`` can never drop).
    """

    mesh: Mesh
    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.0
    expert_axis: str = mesh_lib.EXPERT_AXIS
    name_prefix: str = ''

    def __post_init__(self):
        if self.expert_axis not in self.mesh.shape:
            raise ValueError(
                f'mesh has no {self.expert_axis!r} axis (axes: '
                f'{tuple(self.mesh.shape)}); build it with '
                f'train_mesh(expert=N) (the axis is only added for N > 1)'
            )
        ep = self.mesh.shape[self.expert_axis]
        if self.num_experts % ep != 0:
            raise ValueError(
                f'num_experts={self.num_experts} not divisible by the '
                f'{self.expert_axis!r} axis size {ep}'
            )

    # ------------------------------------------------------------ naming

    def _names(self) -> tuple[str, list[str], list[str]]:
        pre = self.name_prefix
        return (
            f'{pre}router',
            [f'{pre}expert{e}_up' for e in range(self.num_experts)],
            [f'{pre}expert{e}_down' for e in range(self.num_experts)],
        )

    def _data_axes(self) -> tuple[str, ...]:
        return tuple(
            a for a in mesh_lib.DATA_AXES if a in self.mesh.shape
        )

    # ------------------------------------------------------------ params

    def init(self, key: jax.Array, d_model: int) -> dict[str, Any]:
        """Named params, MoEMLP layout: flax default init (lecun_normal
        kernels, zero biases)."""
        router, ups, downs = self._names()
        h = self.mlp_ratio * d_model
        init = jax.nn.initializers.lecun_normal()
        keys = jax.random.split(key, 2 * self.num_experts + 1)
        params: dict[str, Any] = {
            router: {
                'kernel': init(keys[0], (d_model, self.num_experts)),
                'bias': jnp.zeros((self.num_experts,)),
            }
        }
        for e in range(self.num_experts):
            params[ups[e]] = {
                'kernel': init(keys[1 + 2 * e], (d_model, h)),
                'bias': jnp.zeros((h,)),
            }
            params[downs[e]] = {
                'kernel': init(keys[2 + 2 * e], (h, d_model)),
                'bias': jnp.zeros((d_model,)),
            }
        return params

    def registry(self, d_model: int) -> registry_lib.Registry:
        """Registry over router + experts (experts routed — exact
        per-expert statistics), so the dense
        :class:`kfac_tpu.KFACPreconditioner` preconditions them like any
        interceptor-registered layer."""
        router, ups, downs = self._names()
        h = self.mlp_ratio * d_model
        layers: dict[str, helpers_lib.LayerHelper] = {
            router: helpers_lib.DenseHelper(
                name=router, has_bias=True,
                in_features=d_model, out_features=self.num_experts,
            )
        }
        for e in range(self.num_experts):
            layers[ups[e]] = helpers_lib.DenseHelper(
                name=ups[e], has_bias=True,
                in_features=d_model, out_features=h, routed=True,
            )
            layers[downs[e]] = helpers_lib.DenseHelper(
                name=downs[e], has_bias=True,
                in_features=h, out_features=d_model, routed=True,
            )
        return registry_lib.Registry(
            layers=layers,
            param_paths={n: (n,) for n in layers},
        )

    # ------------------------------------------------------------- apply

    def zero_gstats(self, d_model: int) -> dict[str, jax.Array]:
        reg = self.registry(d_model)
        return {
            n: jnp.zeros(h.g_factor_shape, jnp.float32)
            for n, h in reg.layers.items()
        }

    def apply(
        self,
        params: dict[str, Any],
        x: jax.Array,
        gstats: dict[str, jax.Array] | None = None,
    ):
        """EP forward. ``x``: (B, S, d) sharded batch-over-data+expert.

        Returns ``y`` when ``gstats`` is None, else
        ``(y, a_stats, weights)`` where ``a_stats`` maps layer name -> A
        factor, differentiating w.r.t. ``gstats`` yields the G factors
        (CurvatureCapture's contract), and ``weights`` maps expert layer
        name -> live token fraction (the evidence weight for the engines'
        traffic-weighted factor EMA).
        """
        router, ups, downs = self._names()
        e_total = self.num_experts
        ep = self.mesh.shape[self.expert_axis]
        e_loc = e_total // ep
        d = x.shape[-1]
        h = self.mlp_ratio * d
        capture = gstats is not None
        axis = self.expert_axis
        data_axes = self._data_axes()
        batch_axes = data_axes + (axis,)

        wr = params[router]['kernel']
        br = params[router]['bias']
        w_up = jnp.stack([params[n]['kernel'] for n in ups])      # (E, d, h)
        b_up = jnp.stack([params[n]['bias'] for n in ups])        # (E, h)
        w_dn = jnp.stack([params[n]['kernel'] for n in downs])    # (E, h, d)
        b_dn = jnp.stack([params[n]['bias'] for n in downs])      # (E, d)

        if capture:
            g_router = gstats[router]
            g_up = jnp.stack([gstats[n] for n in ups])            # (E, h, h)
            g_dn = jnp.stack([gstats[n] for n in downs])          # (E, d, d)
        else:
            g_router = jnp.zeros((e_total, e_total))
            g_up = jnp.zeros((e_total, h, h))
            g_dn = jnp.zeros((e_total, d, d))

        def body(x_loc, wr, br, w_up, b_up, w_dn, b_dn, g_router, g_up, g_dn):
            lead = x_loc.shape[:-1]
            t_loc = math.prod(lead)
            cap = max(
                1, math.ceil(self.capacity_factor * t_loc / e_total)
            )
            xf = x_loc.reshape(t_loc, d)

            # ---- routing (router weights replicated; MoEMLP semantics)
            logits = xf @ wr + br
            if capture:
                logits = _router_gtap(data_axes + (axis,))(
                    logits, g_router
                )
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            idx = jnp.argmax(probs, axis=-1)                     # (T,)
            gate = jnp.take_along_axis(probs, idx[:, None], -1)  # (T, 1)

            # ---- local dispatch tables (MoEMLP._capacity_dispatch)
            onehot = jax.nn.one_hot(idx, e_total, dtype=jnp.int32)
            pos = jnp.cumsum(onehot, axis=0) * onehot - 1        # (T, E)
            pos = jnp.where(pos < cap, pos, -1)                  # drop
            de = jax.nn.one_hot(pos, cap, dtype=x_loc.dtype)     # (T, E, C)
            bufs = jnp.einsum('tec,td->ecd', de, xf)             # (E, C, d)
            used = jnp.einsum('tec->ec', de)                     # (E, C)

            # ---- to the experts: split E over the axis, concat slots
            bufs = jax.lax.all_to_all(
                bufs, axis, split_axis=0, concat_axis=1, tiled=True
            )                                                    # (E/ep, ep*C, d)
            used = jax.lax.all_to_all(
                used, axis, split_axis=0, concat_axis=1, tiled=True
            )                                                    # (E/ep, ep*C)
            live = used[..., None]                               # (E/ep, R, 1)

            a_stats_out = ()
            if capture:
                # exact per-expert A factors (routed semantics): bias ones
                # on live slots only, normalized by the GLOBAL live count
                live_raw = jax.lax.psum(
                    jnp.sum(used, axis=-1), data_axes
                )                                                # (E/ep,)
                live_n = jnp.maximum(live_raw, 1.0)
                rows_up = jnp.concatenate(
                    [bufs.astype(jnp.float32), live.astype(jnp.float32)], -1
                )                                                # (E/ep, R, d+1)
                a_up = jax.lax.psum(
                    jnp.einsum('erd,erf->edf', rows_up, rows_up), data_axes
                ) / live_n[:, None, None]
                # router A: standard dense factor over ALL tokens
                t_glob = t_loc * 1.0
                for a in batch_axes:
                    t_glob = t_glob * jax.lax.psum(1, a)
                xa = jnp.concatenate(
                    [
                        xf.astype(jnp.float32),
                        jnp.ones((t_loc, 1), jnp.float32),
                    ],
                    -1,
                )
                a_router = jax.lax.psum(
                    xa.T @ xa, batch_axes
                ) / t_glob

            # ---- local experts on their received buffers (the stacked
            # weight args are the LOCAL (E/ep, ...) slices inside the body)
            up_lin = (
                jnp.einsum('erd,edh->erh', bufs, w_up)
                + b_up[:, None, :]
            )
            if capture:
                up_lin = _expert_gtap(data_axes, live_n)(up_lin, g_up)
            hcur = jax.nn.gelu(up_lin) * live.astype(up_lin.dtype)
            if capture:
                rows_dn = jnp.concatenate(
                    [hcur.astype(jnp.float32), live.astype(jnp.float32)], -1
                )
                a_dn = jax.lax.psum(
                    jnp.einsum('erh,erg->ehg', rows_dn, rows_dn), data_axes
                ) / live_n[:, None, None]
                # per-expert evidence weight (live fraction of the GLOBAL
                # token count) for the engines' traffic-weighted factor
                # EMA — the EP analogue of cov.routed_live_fraction
                w_live = live_raw.astype(jnp.float32) / t_glob
                a_stats_out = (a_router, a_up, a_dn, w_live)
            dn_lin = (
                jnp.einsum('erh,ehd->erd', hcur, w_dn)
                + b_dn[:, None, :]
            )
            if capture:
                dn_lin = _expert_gtap(data_axes, live_n)(dn_lin, g_dn)
            y_bufs = dn_lin.astype(x_loc.dtype)

            # ---- back to the tokens: inverse all_to_all
            y_bufs = jax.lax.all_to_all(
                y_bufs, axis, split_axis=1, concat_axis=0, tiled=True
            )                                                    # (E, C, d)
            out_f = jnp.einsum('tec,ecd->td', de, y_bufs)
            out = (out_f * gate.astype(out_f.dtype)).reshape(*lead, d)
            return (out,) + a_stats_out

        espec3 = P(axis, None, None)
        espec2 = P(axis, None)
        in_specs = (
            P(batch_axes, None, None),   # x (B, S, d)
            P(), P(),                    # router kernel/bias (replicated)
            espec3, espec2,              # up kernel/bias
            espec3, espec2,              # down kernel/bias
            P(),                         # router gstat dummy (replicated)
            espec3, espec3,              # expert gstat dummies
        )
        out_specs = (
            (P(batch_axes, None, None), P(), espec3, espec3, P(axis))
            if capture
            else (P(batch_axes, None, None),)
        )
        out = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
        )(x, wr, br, w_up, b_up, w_dn, b_dn, g_router, g_up, g_dn)
        if not capture:
            return out[0]
        y, a_router, a_up, a_dn, w_live = out
        a_stats = {router: a_router}
        weights: dict[str, jax.Array] = {}
        for e in range(e_total):
            a_stats[ups[e]] = a_up[e]
            a_stats[downs[e]] = a_dn[e]
            # up and down projections see the same routed token set
            weights[ups[e]] = w_live[e]
            weights[downs[e]] = w_live[e]
        return y, a_stats, weights

    # ----------------------------------------------------------- capture

    def value_stats_and_grad(
        self, loss_fn: Callable[..., jax.Array]
    ) -> Callable[..., Any]:
        """CurvatureCapture-shaped runner for a model whose MoE block is
        this EP FFN. ``loss_fn(params, batch, ffn)`` must compute the loss
        using ``ffn(params, x)`` for the MoE block (``ffn`` closes over
        the capture taps). Returns
        ``run(params, batch) -> ((loss, None), grads, CapturedStats)``.
        Multi-block models use :func:`combined_value_stats_and_grad`.
        """
        return combined_value_stats_and_grad(
            lambda params, batch, ffns: loss_fn(params, batch, ffns[0]),
            ep_ffns=(self,),
        )


def combined_value_stats_and_grad(
    loss_fn: Callable[..., jax.Array],
    registry: Any = None,
    ep_ffns: tuple[EPSwitchFFN, ...] = (),
) -> Callable[..., Any]:
    """One ``value_and_grad`` spanning interceptor capture (ordinary flax
    layers registered in ``registry``) AND any number of EP FFN blocks.

    ``loss_fn(params, batch, ffns)`` computes the loss; flax modules run
    normally (the interceptor taps them), the i-th MoE block runs as
    ``ffns[i](params, x)``. Each :class:`EPSwitchFFN` needs a distinct
    ``name_prefix`` so its layer names cannot collide. Returns
    ``run(params, batch) -> ((loss, None), grads, CapturedStats)`` with
    the merged per-layer statistics dicts — exactly what the K-FAC
    engines consume (merge the registries likewise for the engine).
    """
    prefixes = [ffn.name_prefix for ffn in ep_ffns]
    if len(set(prefixes)) != len(prefixes):
        raise ValueError(
            f'EP FFN name_prefixes must be distinct, got {prefixes}'
        )
    cap = (
        capture_lib.CurvatureCapture(registry)
        if registry is not None and len(registry.layers)
        else None
    )

    def run(params: dict[str, Any], batch: Any):
        d_models = [
            params[ffn._names()[0]]['kernel'].shape[0] for ffn in ep_ffns
        ]
        boxes: list[dict[str, jax.Array]] = [{} for _ in ep_ffns]
        wboxes: list[dict[str, jax.Array]] = [{} for _ in ep_ffns]

        def tapped(params, flax_gstats, ep_gstats, batch):
            calls = [0] * len(ep_ffns)

            def make_ffn(i):
                def ffn(p, x):
                    # one invocation per block per loss evaluation: a
                    # second call would overwrite A stats while G-taps
                    # kept summing into the same dummies
                    if calls[i]:
                        raise ValueError(
                            f'EP block {i} ({prefixes[i]!r}) called more '
                            'than once per loss evaluation; use one '
                            'EPSwitchFFN (distinct name_prefix) per block'
                        )
                    calls[i] += 1
                    y, a_stats, ep_w = ep_ffns[i].apply(p, x, ep_gstats[i])
                    boxes[i].clear()
                    boxes[i].update(a_stats)
                    wboxes[i].clear()
                    wboxes[i].update(ep_w)
                    return y

                return ffn

            ffns = [make_ffn(i) for i in range(len(ep_ffns))]
            if cap is not None:
                loss, (_, a_stats, counts, wts) = cap.tapped(
                    lambda p, b: loss_fn(p, b, ffns)
                )(params, flax_gstats, batch)
            else:
                loss = loss_fn(params, batch, ffns)
                a_stats, counts, wts = {}, {}, {}
            # an uninvoked block would contribute all-zero G factors (the
            # unused dummies' gradients) with NO matching A factors —
            # silent curvature corruption; fail like the double-call case
            missing = [
                prefixes[i] for i in range(len(ep_ffns)) if not calls[i]
            ]
            if missing:
                raise ValueError(
                    f'EP block(s) {missing} were never called by loss_fn; '
                    'every ffn in ep_ffns must run exactly once per loss '
                    'evaluation'
                )
            return loss, (
                a_stats, counts, wts,
                [dict(b) for b in boxes], [dict(b) for b in wboxes],
            )

        flax_g0 = cap.zero_gstats() if cap is not None else {}
        ep_g0 = [
            ffn.zero_gstats(d) for ffn, d in zip(ep_ffns, d_models)
        ]
        (loss, (fa, counts, wts, ep_a, ep_w)), (grads, flax_g, ep_g) = (
            jax.value_and_grad(tapped, argnums=(0, 1, 2), has_aux=True)(
                params, flax_g0, ep_g0, batch
            )
        )
        # interceptor stats average over repeated module calls (weight
        # sharing) via the shared convention (capture_lib.weighted_average:
        # weighted layers divide by summed traffic weight — A-side from
        # the inputs, G-side from the cotangents — others by invocation
        # count); EP stats are already normalized in-body
        g_sums, g_wts = capture_lib.split_g_stats(flax_g)
        if cap is not None:
            fa = capture_lib.contract_late(cap.registry, fa, grads)
        a_all = dict(capture_lib.weighted_average(fa, counts, wts))
        g_all = dict(
            capture_lib.weighted_average(
                {n: g_sums[n] for n in counts}, counts, g_wts
            )
        )
        w_all: dict[str, jax.Array] = {
            n: wts[n] / counts[n].astype(wts[n].dtype) for n in wts
        }
        for a_i, g_i, w_i in zip(ep_a, ep_g, ep_w):
            a_all.update(a_i)
            g_all.update(g_i)
            w_all.update(w_i)
        stats = capture_lib.CapturedStats(a=a_all, g=g_all, w=w_all)
        return (loss, None), grads, stats

    return run


def _router_gtap(reduce_axes: tuple[str, ...]):
    """G-tap for the router: standard dense G factor (g^T g / T_global).

    The dummy input is fully replicated, so (under shard_map's vma
    checking) the bwd cotangent must be invariant too: the data+expert
    reduction happens with an explicit psum INSIDE the rule."""

    @jax.custom_vjp
    def gtap(y, gstat):
        del gstat
        return y

    def fwd(y, gstat):
        del gstat
        t_glob = y.shape[0] * 1.0
        for a in reduce_axes:
            t_glob = t_glob * jax.lax.psum(1, a)
        return y, t_glob

    def bwd(t_glob, ybar):
        yb = ybar.astype(jnp.float32)
        return ybar, jax.lax.psum(yb.T @ yb, reduce_axes) / t_glob

    gtap.defvjp(fwd, bwd)
    return gtap


def _expert_gtap(data_axes: tuple[str, ...], live_n: jax.Array):
    """G-tap for a stacked local-expert output (E_loc, R, f): per-expert
    routed G factor ``sum_live g g^T / live_global``. The dummy input
    varies only over the expert axis, so the cotangent psums over the
    data axes inside the rule to match (shard_map vma contract)."""

    @jax.custom_vjp
    def gtap(y, gstat):
        del gstat
        return y

    def fwd(y, gstat):
        del gstat
        return y, jax.lax.stop_gradient(live_n)

    def bwd(live_n, ybar):
        yb = ybar.astype(jnp.float32)
        g = jax.lax.psum(
            jnp.einsum('erf,erg->efg', yb, yb), data_axes
        ) / live_n[:, None, None]
        return ybar, g

    gtap.defvjp(fwd, bwd)
    return gtap
