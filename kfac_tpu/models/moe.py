"""Mixture-of-Experts MLP with per-expert K-FAC factors.

Beyond the reference (gpauloski/kfac-pytorch has no MoE support at all;
SURVEY.md section 2.3 lists EP as absent in both) — a natural extension of
the stacked-bucket KAISA design:

- Every expert's projections are ordinary named ``nn.Dense`` submodules
  (``expert{e}_up`` / ``expert{e}_down``), so each registers as its own
  K-FAC layer. Experts share factor shapes, so they land in ONE stacked
  bucket and the distributed engine shards their eigendecompositions across
  the mesh automatically — "EP factor buckets" fall out of the existing
  layout with zero engine changes.
- Dispatch is top-1 (switch-style) with two execution paths sharing one
  parameter structure:
  * ``capacity_factor=None`` — dense masked dispatch: every expert sees
    every (masked) token row. Simple, exact, E× FLOPs; right for tests
    and tiny expert counts.
  * ``capacity_factor=c`` — capacity dispatch: tokens are packed into
    per-expert buffers of ``C = ceil(c * T / E)`` slots through one-hot
    dispatch einsums (MXU-friendly, differentiable; the Mesh-TF/Switch
    formulation), each expert runs on its C rows only, and outputs
    combine back by the transposed einsum. Total FFN FLOPs are
    ``c * T`` tokens' worth regardless of E; tokens beyond an expert's
    capacity are dropped (residual passthrough, standard switch
    semantics).
  In both paths non-routed/empty rows are zeroed before the up-projection
  AND between up and down (so the up-bias cannot leak constant
  activations into the down layer). Captured factors need no
  MoE-specific path; the approximation vs a per-expert-normalized oracle
  is exactly characterized (and quantified in
  tests/test_moe.py::test_moe_factor_approximation_identity_and_precond_bound):
  the captured A of expert e equals ``f_e * A_oracle +
  (1 - f_e) * e_bias e_bias^T`` with ``f_e`` the routed fraction (empty
  rows contribute only the homogeneous bias-ones outer product), so
  preconditioning with it IS per-expert preconditioning at effective
  damping ``damping / f_e`` with the empty-row bias corner inflated by
  ``(1 - f_e) / f_e``. Consequence (measured): accurate for high-traffic
  experts (direction cosine vs the oracle > 0.9 at f_e >= 0.3, default
  damping) but REAL error for low-traffic ones (cosine ~0.3 at
  f_e ~ 0.13, damping 1e-3), shrinking as damping grows. To remove the
  approximation entirely, register with
  ``routed_layers=[r'.*expert\\d+_(up|down)']``: routed capture
  normalizes each expert's factors by its LIVE row count with bias ones
  on live rows only, making the captured statistics exactly the
  per-expert oracle (verified to float precision in
  tests/test_moe.py::test_routed_capture_matches_per_expert_oracle_exactly).
- Expert parallelism is a layout choice: stack the expert axis over the
  ``model`` mesh axis by passing TP overrides (column for ``*_up``, row for
  ``*_down``) to :func:`kfac_tpu.parallel.tensor_parallel
  .shard_params_from_registry`, or shard different experts' weights to
  different devices with per-expert override rules — GSPMD turns the masked
  dispatch into the corresponding collective.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp


class MoEMLP(nn.Module):
    """Top-1 (switch) routed MLP: ``num_experts`` independent FFNs.

    Router probabilities are sown under ``intermediates/router_probs`` so
    callers can add :func:`load_balance_loss`.

    ``capacity_factor=None`` runs the dense masked path (every expert sees
    all tokens, exact); a float enables capacity dispatch with
    ``ceil(capacity_factor * tokens / num_experts)`` slots per expert —
    sparse compute, overflow tokens dropped. Both paths share the same
    parameter structure, so a model can train dense and serve sparse.
    """

    num_experts: int
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    capacity_factor: float | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        logits = nn.Dense(self.num_experts, dtype=self.dtype, name='router')(x)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        idx = jnp.argmax(probs, axis=-1)                       # (B, S)
        gate = jnp.take_along_axis(probs, idx[..., None], -1)  # (B, S, 1)
        self.sow('intermediates', 'router_probs', probs)
        self.sow('intermediates', 'expert_index', idx)

        if self.capacity_factor is not None:
            return self._capacity_dispatch(x, idx) * gate.astype(x.dtype)

        out = jnp.zeros_like(x)
        for e in range(self.num_experts):
            mask = (idx == e).astype(x.dtype)[..., None]
            xe = x * mask
            h = nn.Dense(
                self.mlp_ratio * d, dtype=self.dtype, name=f'expert{e}_up'
            )(xe)
            # re-mask: unrouted rows would otherwise carry gelu(b_up) into
            # the down projection (and its captured A factor)
            h = nn.gelu(h) * mask
            y = nn.Dense(d, dtype=self.dtype, name=f'expert{e}_down')(h)
            out = out + y * mask
        return out * gate.astype(out.dtype)

    def _capacity_dispatch(self, x: jax.Array, idx: jax.Array) -> jax.Array:
        """Pack routed tokens into per-expert capacity buffers and run each
        expert on its buffer only.

        The dispatch tensor ``disp[t, e, s]`` is 1 when flat token t holds
        slot s of expert e (one-hot over slots; all-zero for dropped or
        unrouted tokens), so dispatch and combine are plain matmuls the MXU
        tiles well, and both are exactly differentiable — the backward pass
        is the transposed einsum, which is the combine/dispatch of the
        cotangents (XLA sees static shapes throughout; no dynamic gather).
        """
        d = x.shape[-1]
        lead = x.shape[:-1]
        t = math.prod(lead)
        cap = max(1, math.ceil(self.capacity_factor * t / self.num_experts))
        xf = x.reshape(t, d)
        idxf = idx.reshape(t)
        onehot = jax.nn.one_hot(idxf, self.num_experts, dtype=jnp.int32)
        # slot of token t within its expert's buffer (arrival order); -1
        # for the experts it is not routed to
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1           # (T, E)
        pos = jnp.where(pos < cap, pos, -1)                      # drop overflow
        out_f = jnp.zeros_like(xf)
        for e in range(self.num_experts):
            de = jax.nn.one_hot(pos[:, e], cap, dtype=x.dtype)   # (T, C)
            xe = jnp.einsum('tc,td->cd', de, xf)                 # (C, d)
            h = nn.Dense(
                self.mlp_ratio * d, dtype=self.dtype, name=f'expert{e}_up'
            )(xe)
            # zero empty slots between up and down: gelu(b_up) must not
            # reach the down projection (same hygiene as the dense path)
            used = jnp.sum(de, axis=0)[:, None].astype(h.dtype)  # (C, 1)
            h = nn.gelu(h) * used
            y = nn.Dense(d, dtype=self.dtype, name=f'expert{e}_down')(h)
            out_f = out_f + jnp.einsum('tc,cd->td', de, y)
        return out_f.reshape(*lead, d)


def load_balance_loss(probs: jax.Array, idx: jax.Array, num_experts: int):
    """Switch-Transformer auxiliary load-balancing loss.

    ``num_experts * sum_e f_e * P_e`` where f_e is the fraction of tokens
    routed to expert e and P_e the mean router probability — minimized (=1)
    at uniform load.
    """
    onehot = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)
    f = onehot.reshape(-1, num_experts).mean(0)
    p = probs.reshape(-1, num_experts).mean(0)
    return num_experts * jnp.sum(f * p)


def expert_tp_overrides() -> list[tuple[str, str]]:
    """TP override rules sharding every expert Megatron-style (up =
    column-parallel, down = row-parallel) over the model axis — the
    simplest expert-parallel layout. Matches any expert index."""
    return [
        (r'.*expert\d+_up', 'column'),
        (r'.*expert\d+_down', 'row'),
    ]


# ---------------------------------------------------------------------------
# Top-k routing over a share of the experts (stacked expert weights).
#
# ``SparseMoE`` is the expert layer of today's sparse LMs: a router over ALL
# the layer's experts, top-k with renormalised weights, bias-free gated-MLP
# experts and an optional shared expert behind a sigmoid gate. It is told
# which experts it holds (``experts_held``: first index and count, the
# chip's share of an expert-parallel group), routes over all of them and
# computes its own experts' part of the result; what the absent experts
# would add is left out (no code stands in for the absent chips).
#
# Among the experts held no assignment is dropped: the plan's row capacity
# is the worst load (every token choosing every held expert it can), and
# the products loop over the blocks in use (``ops/grouped.py``), so the
# work follows the real load. ``ExpertPlan.dropped`` counts what the plan
# failed to place and has to read 0.
#
# K-FAC: every expert's three projections are K-FAC layers with their own
# A and G factors (``<moe>/experts/<proj>/e<j>``: slots of one size class
# in the engine's buckets), but the *program* holds one stacked product
# and ONE capture tap a projection (``ExpertProjection`` ->
# ``layers.helpers.ExpertStackTap``): factors are stacked ``(E_here, d,
# d)`` sums over each expert's own rows, normalised by its live rows; an
# expert with no row in a capture carries weight 0 and keeps its factors.
# The parameters stay one 2-D ``kernel`` leaf an expert (stacked when the
# layer is called), so that optimizers, checkpoints and the benchmark's
# plain reference see ordinary dense layers.


class ExpertPlan(NamedTuple):
    """Where each assignment to a held expert lives, in blocks of rows.

    ``blocks = ceil(tokens * min(top_k, held) / block_rows) + held``:
    room for the worst load with every expert's rows padded to whole
    blocks. Rows past an expert's last are padding: they read zeros,
    weigh 0 and write nowhere.
    """

    row_token: jax.Array     # (blocks, rows) int32; ``tokens`` for padding
    row_weight: jax.Array    # (blocks, rows) float32 routing weight
    block_expert: jax.Array  # (blocks,) int32, local index of the expert
    n_blocks: jax.Array      # int32 scalar: blocks in use
    rows: jax.Array          # (held,) int32: live rows of each expert
    dropped: jax.Array       # int32 scalar: assignments left out (0)


@jax.custom_vjp
def _row_weights(wts, assign, valid, dest):
    """``wts.ravel()[assign]`` where ``valid`` (else 0), with a backward
    pass that is a gather too (``dest``: assignment -> plan row), not a
    scatter of tens of thousands of scalars."""
    del dest
    return jnp.where(valid, wts.reshape(-1)[assign], 0.0)


def _row_weights_fwd(wts, assign, valid, dest):
    return _row_weights(wts, assign, valid, dest), (wts.shape, dest)


def _row_weights_bwd(res, d_rows):
    shape, dest = res
    flat = jnp.concatenate([d_rows.reshape(-1), jnp.zeros((1,), d_rows.dtype)])
    return flat[dest].reshape(shape), None, None, None


_row_weights.defvjp(_row_weights_fwd, _row_weights_bwd)


def make_plan(
    idx: jax.Array, wts: jax.Array, first: int, held: int, block_rows: int
) -> ExpertPlan:
    """The row plan of a routing: ``idx`` ``(tokens, k)`` int32 expert
    choices, ``wts`` their weights; experts ``first .. first + held - 1``
    live here. Two sorts and gathers, no scatter."""
    tokens, k = idx.shape
    n = tokens * k
    blocks = -(-tokens * min(k, held) // block_rows) + held
    local = (idx - first).reshape(n)
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held)
    order = jnp.argsort(key, stable=True)   # sorted position -> assignment
    place = jnp.argsort(order)              # assignment -> sorted position
    rows = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0,
        dtype=jnp.int32,
    )
    group_start = jnp.cumsum(rows) - rows   # in sorted order
    blocks_of = -(-rows // block_rows)
    block_end = jnp.cumsum(blocks_of)
    n_blocks = block_end[-1]
    row_start = (block_end - blocks_of) * block_rows
    block_expert = jnp.minimum(
        jnp.searchsorted(block_end, jnp.arange(blocks), side='right'),
        held - 1,
    ).astype(jnp.int32)
    r = jnp.arange(blocks * block_rows, dtype=jnp.int32)
    e_r = jnp.repeat(block_expert, block_rows)
    off = r - row_start[e_r]
    valid = (off < rows[e_r]) & (r < n_blocks * block_rows)
    assign = order[jnp.clip(group_start[e_r] + off, 0, n - 1)]
    e_n = jnp.minimum(key, held - 1)
    dest = jnp.where(
        is_held, row_start[e_n] + place - group_start[e_n],
        blocks * block_rows,
    )
    shape = (blocks, block_rows)
    return ExpertPlan(
        row_token=jnp.where(valid, assign // k, tokens)
        .astype(jnp.int32).reshape(shape),
        row_weight=_row_weights(
            wts.astype(jnp.float32), assign, valid, dest
        ).reshape(shape),
        block_expert=block_expert,
        n_blocks=n_blocks.astype(jnp.int32),
        rows=rows,
        # counted from the plan as built, not from the routing: a capacity
        # or placement fault shows here
        dropped=(jnp.sum(is_held) - jnp.sum(valid)).astype(jnp.int32),
    )


class _ExpertKernel(nn.Module):
    """One expert's ``kernel`` leaf (a dense layer's, to everything that
    walks the parameter tree)."""

    shape: tuple[int, int]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param('kernel', nn.initializers.lecun_normal(), self.shape)


class ExpertProjection(nn.Module):
    """One projection of every held expert: a stacked ``(E_here, d_in,
    d_out)`` product over the plan's blocks. ``mode``: ``'gather'`` reads
    token rows ``(tokens, d_in)`` and returns blocks; ``'combine'`` maps
    blocks to ``(tokens, d_out)``, each row weighted by its routing weight
    and summed into its token.

    ``register_model`` registers its experts as ``<path>/e<j>`` slots and
    the capture layer taps it once (``_kfac_expert_stack``)."""

    _kfac_expert_stack = True

    experts: int
    features: int
    mode: str  # 'gather' | 'combine'
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, x: jax.Array, plan: ExpertPlan, tokens: int | None = None
    ) -> jax.Array:
        from kfac_tpu.ops import grouped

        w = jnp.stack([
            _ExpertKernel((x.shape[-1], self.features), name=f'e{j}')()
            .astype(self.dtype) for j in range(self.experts)
        ])
        x = x.astype(self.dtype)
        if self.mode == 'gather':
            return grouped.grouped_matmul_gather(
                x, w, plan.row_token, plan.block_expert, plan.n_blocks
            )
        return grouped.grouped_matmul_combine(
            x, w, plan.row_token, plan.row_weight, plan.block_expert,
            plan.n_blocks, tokens,
        )


class Experts(nn.Module):
    """The held experts' gated MLPs over a plan: ``down(silu(gate x) *
    up x)``, weighted and summed into the tokens."""

    experts: int
    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, plan: ExpertPlan) -> jax.Array:
        def proj(features, mode, name):
            return ExpertProjection(
                self.experts, features, mode, dtype=self.dtype, name=name
            )

        g = proj(self.width, 'gather', 'gate_proj')(x, plan)
        u = proj(self.width, 'gather', 'up_proj')(x, plan)
        return proj(x.shape[-1], 'combine', 'down_proj')(
            nn.silu(g) * u, plan, tokens=x.shape[0]
        )


class GatedMLP(nn.Module):
    """Bias-free ``down(silu(gate x) * up x)``."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from kfac_tpu import tracing

        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        with tracing.model_scope('mlp'):
            h = nn.silu(dense(self.width, 'gate_proj')(x)) * dense(
                self.width, 'up_proj'
            )(x)
            return dense(x.shape[-1], 'down_proj')(h)


class SparseMoE(nn.Module):
    """Top-k routed gated-MLP experts, a share of them held here.

    ``y = sum_{e in top-k, e held} w_e expert_e(x) + sigmoid(x w_s)
    shared(x)``. Router logits and scores in float32 over all
    ``num_experts``; the top-k weights renormalised (``norm_topk_prob``).
    ``experts_held = (first, count)``; ``None`` holds every expert.
    ``shared_width`` 0 leaves the shared expert out.

    ``scoring``: ``'softmax'`` scores the experts by the softmax of the
    logits and takes the top-k of the scores; ``'sigmoid'`` by each
    logit's sigmoid (the scores do not sum to one). ``selection_bias``
    adds ``expert_bias`` (one value an expert; no K-FAC layer's) to the
    scores *in the selection only*: the chosen experts' weights are their
    unbiased scores and no gradient reaches the bias (an aux-loss-free
    balancer would set it from the load; nothing here moves it).
    ``renorm_eps`` is added to the sum the top-k weights are divided by.

    ``routed_scale`` multiplies the (renormalised) top-k weights: the
    source's ``routed_scaling_factor``. ``shared_gated=False`` adds the
    shared expert as it is, ``y + shared(x)``: the layer then declares no
    ``shared_gate`` and K-FAC has no layer for it.
    """

    num_experts: int
    top_k: int
    width: int
    shared_width: int = 0
    experts_held: tuple[int, int] | None = None
    norm_topk_prob: bool = True
    block_rows: int = 256
    dtype: Any = jnp.float32
    scoring: str = 'softmax'
    selection_bias: bool = False
    renorm_eps: float = 0.0
    routed_scale: float = 1.0
    shared_gated: bool = True

    def _route(self, logits: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The top-k experts of every token and their weights, before
        renormalisation."""
        if self.scoring == 'softmax':
            scores = jax.nn.softmax(logits, axis=-1)
        elif self.scoring == 'sigmoid':
            scores = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f'scoring {self.scoring!r}')
        if not self.selection_bias:
            return jax.lax.top_k(scores, self.top_k)
        bias = self.param(
            'expert_bias', nn.initializers.zeros, (self.num_experts,)
        )
        _, idx = jax.lax.top_k(
            jax.lax.stop_gradient(scores + bias), self.top_k
        )
        # the chosen scores by a one-hot sum: its backward pass is a
        # product too, not a scatter of tokens * k scalars
        chosen = jax.nn.one_hot(idx, self.num_experts, dtype=scores.dtype)
        return jnp.sum(scores[:, None, :] * chosen, axis=-1), idx

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from kfac_tpu import tracing

        lead, d = x.shape[:-1], x.shape[-1]
        xf = x.reshape(-1, d)
        first, held = self.experts_held or (0, self.num_experts)
        # one float32 array for the router and the shared gate: they read
        # the same input, and K-FAC keeps one A factor for layers handed
        # one array (``Registry.a_groups``)
        xf32 = xf.astype(jnp.float32)
        with tracing.model_scope('moe_route'):
            logits = nn.Dense(
                self.num_experts, use_bias=False, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, name='router',
            )(xf32)
            wts, idx = self._route(logits)
            if self.norm_topk_prob:
                total = jnp.sum(wts, axis=-1, keepdims=True)
                if self.renorm_eps:
                    total = total + self.renorm_eps
                wts = wts / total
            if self.routed_scale != 1.0:
                wts = wts * self.routed_scale
            plan = make_plan(idx, wts, first, held, self.block_rows)
        with tracing.model_scope('moe_experts'):
            y = Experts(held, self.width, dtype=self.dtype, name='experts')(
                xf, plan
            )
        if self.shared_width:
            with tracing.model_scope('mlp'):
                if self.shared_gated:
                    gate = nn.Dense(
                        1, use_bias=False, dtype=jnp.float32,
                        name='shared_gate',
                    )(xf32)
                shared = GatedMLP(
                    self.shared_width, dtype=self.dtype, name='shared'
                )(xf)
                if self.shared_gated:
                    y = y + jax.nn.sigmoid(gate) * shared.astype(jnp.float32)
                else:
                    y = y + shared.astype(jnp.float32)
        return y.reshape(*lead, d)
