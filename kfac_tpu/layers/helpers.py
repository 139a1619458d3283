"""Layer helpers: factor shapes, factor computation, grad matricization.

The TPU-native analogue of the reference's ``ModuleHelper`` hierarchy
(kfac/layers/modules.py:13-237). Instead of mutating ``module.weight.grad``,
helpers convert between a layer's slice of the gradient pytree (flax param
layout) and the dense (d_out, d_in [+ bias]) matrix form that the Kronecker
preconditioner operates on.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp

from kfac_tpu.ops import cov

#: the one key of a gradient view in matrix form (:func:`matrix_view`)
MATRIX = 'matrix'


@dataclasses.dataclass(frozen=True)
class LayerHelper:
    """Base helper. Subclasses describe one supported layer kind.

    Attributes:
        name: registry name (flax module path joined with '/').
        has_bias: whether a bias column is folded into the A factor / grad.
    """

    name: str
    has_bias: bool

    @property
    def a_factor_shape(self) -> tuple[int, int]:
        raise NotImplementedError

    @property
    def g_factor_shape(self) -> tuple[int, int]:
        raise NotImplementedError

    def get_a_factor(self, a: jax.Array) -> jax.Array:
        """Per-batch A factor from the layer input (forward tap)."""
        raise NotImplementedError

    #: whether the capture leaves :meth:`get_a_factor` for after the
    #: backward pass (:func:`kfac_tpu.layers.capture.contract_late`)
    contracts_late = False

    @property
    def weighted(self) -> bool:
        """Whether this helper's captures carry an evidence weight.

        The single source of truth for every weight-sensitive code path:
        :meth:`capture_weight` returns non-None, the capture accumulates
        traffic-weighted sums, and ``Trainer._zero_stats`` emits a
        matching ``w`` entry — all iff this is True.
        """
        return False

    def capture_weight(self, a: jax.Array) -> jax.Array | None:
        """Per-capture evidence weight for the factor EMA, from the layer
        input. ``None`` (implicit weight 1) unless :attr:`weighted`;
        routed dense layers return their live-row fraction so the engines
        can weight captures by actual token traffic (see
        cov.routed_live_fraction)."""
        del a
        return None

    def g_factor_for_sum(self, g: jax.Array) -> jax.Array:
        """Per-invocation G contribution for the capture accumulator.

        Equals :meth:`get_g_factor` for unweighted helpers. Weighted
        (routed) helpers return the factor PRE-SCALED by its own live
        fraction, so summing invocations and dividing by the summed
        G-side weights (:meth:`g_capture_weight`) yields the
        traffic-weighted mean ``sum(w_i G_i)/sum(w_i)`` — the same
        convention as cross-micro-step accumulation.
        """
        return self.get_g_factor(g)

    def g_capture_weight(self, g: jax.Array) -> jax.Array | None:
        """Per-capture G-side evidence weight, from the COTANGENT.

        ``None`` (implicit weight 1) unless :attr:`weighted`. Routed
        helpers return the cotangent live-row fraction — the same row
        detection ``routed_linear_g_factor`` normalizes by — so the
        G-sum divisor tracks the rows that actually carried G mass. The
        A-side :meth:`capture_weight` is NOT a valid G divisor: an
        all-zero-input invocation can still see a nonzero cotangent
        (e.g. through a bias path), and dividing its G sum by the ~0
        input weight would amplify that spurious mass unboundedly.
        """
        del g
        return None

    def get_g_factor(self, g: jax.Array) -> jax.Array:
        """Per-batch G factor from dL/d(layer output) (backward tap)."""
        raise NotImplementedError

    def grads_to_matrix(self, grads: dict[str, jax.Array]) -> jax.Array:
        """Pack this layer's grad pytree leaves into (d_out, d_in[+1])."""
        raise NotImplementedError

    def matrix_to_grads(self, mat: jax.Array) -> dict[str, jax.Array]:
        """Unpack a preconditioned matrix back into flax param layout."""
        raise NotImplementedError

    # ---- the gradient as the explicit-inverse product reads it ----------
    #
    # A *view* is a dict of arrays over which the engines' per-layer
    # reductions (norms, the kl-clip ``sum(P * G)``) are plain sums of
    # elementwise products, so they do not care about its layout. The
    # matrix form (:func:`matrix_view`) serves every helper; a helper whose
    # leaves can be multiplied as they lie (``in_layout``) hands those out
    # instead and saves the packing and the unpacking.

    #: whether :meth:`grad_view` is in the parameters' own layout
    in_layout = False

    def grad_view(self, grads: dict[str, jax.Array]) -> dict[str, jax.Array]:
        """This layer's gradient as :meth:`inverse_precondition` reads it:
        the packed matrix, unless the helper can multiply its leaves as
        they lie."""
        return matrix_view(self, grads)

    def inverse_precondition(
        self,
        view: dict[str, jax.Array],
        a_inv: jax.Array,
        g_inv: jax.Array,
    ) -> dict[str, jax.Array]:
        """A view of the preconditioned gradient from :meth:`grad_view`
        and the layer's two explicit (symmetric) inverses at their true
        dims: ``(G^-1 M) A^-1`` on the matrix form, in the inverses' dtype
        (reference: kfac/layers/inverse.py:215-234)."""
        mat = view[MATRIX].astype(a_inv.dtype)
        return {MATRIX: (g_inv @ mat) @ a_inv}


def matrix_view(
    helper: LayerHelper, grads: dict[str, jax.Array]
) -> dict[str, jax.Array]:
    """A layer's gradient as a view in matrix form."""
    return {MATRIX: helper.grads_to_matrix(grads)}


def view_to_grads(
    helper: LayerHelper, view: dict[str, jax.Array]
) -> dict[str, jax.Array]:
    """A view back in flax param layout: a matrix unpacked, leaves that
    were viewed as they lie handed back."""
    if MATRIX in view:
        return helper.matrix_to_grads(view[MATRIX])
    return dict(view)


@dataclasses.dataclass(frozen=True)
class DenseHelper(LayerHelper):
    """Helper for dense layers (flax kernel layout (d_in, d_out)).

    Reference equivalent: LinearModuleHelper
    (kfac/layers/modules.py:100-141). A is ((d_in+bias), (d_in+bias)); G is
    (d_out, d_out); leading batch/sequence dims collapse into covariance rows
    so sequence models need no special casing.
    """

    in_features: int
    out_features: int
    factor_dtype: Any = jnp.float32
    # Routed (row-masked) capture: normalize factors by the NONZERO row
    # count and put bias ones only on live rows — exact per-expert
    # statistics for MoE expert layers (see cov.routed_linear_a_factor;
    # opt in via register_model(..., routed_layers=[...])).
    routed: bool = False

    @property
    def a_factor_shape(self) -> tuple[int, int]:
        n = self.in_features + int(self.has_bias)
        return (n, n)

    @property
    def g_factor_shape(self) -> tuple[int, int]:
        return (self.out_features, self.out_features)

    def get_a_factor(self, a: jax.Array) -> jax.Array:
        factor = (
            cov.routed_linear_a_factor if self.routed else cov.linear_a_factor
        )
        return factor(a, self.has_bias).astype(self.factor_dtype)

    def get_g_factor(self, g: jax.Array) -> jax.Array:
        factor = (
            cov.routed_linear_g_factor if self.routed else cov.linear_g_factor
        )
        return factor(g).astype(self.factor_dtype)

    @property
    def weighted(self) -> bool:
        return self.routed

    def capture_weight(self, a: jax.Array) -> jax.Array | None:
        if not self.routed:
            return None
        return cov.routed_live_fraction(a)

    def g_factor_for_sum(self, g: jax.Array) -> jax.Array:
        # routed G x its live fraction == the plain total-rows
        # normalization: get_cov(g)*(rows/n) * (n/rows) = g^T g / rows
        if self.routed:
            return cov.linear_g_factor(g).astype(self.factor_dtype)
        return self.get_g_factor(g)

    def g_capture_weight(self, g: jax.Array) -> jax.Array | None:
        if not self.routed:
            return None
        return cov.routed_live_fraction(g).astype(self.factor_dtype)

    def grads_to_matrix(self, grads: dict[str, jax.Array]) -> jax.Array:
        mat = grads['kernel'].T
        if self.has_bias:
            mat = jnp.concatenate([mat, grads['bias'][:, None]], axis=1)
        return mat

    def matrix_to_grads(self, mat: jax.Array) -> dict[str, jax.Array]:
        if self.has_bias:
            return {'kernel': mat[:, :-1].T, 'bias': mat[:, -1]}
        return {'kernel': mat.T}

    in_layout = True

    def grad_view(self, grads: dict[str, jax.Array]) -> dict[str, jax.Array]:
        return {
            k: grads[k] for k in ('kernel', 'bias')[: 1 + self.has_bias]
        }

    def inverse_precondition(
        self,
        view: dict[str, jax.Array],
        a_inv: jax.Array,
        g_inv: jax.Array,
    ) -> dict[str, jax.Array]:
        """On the leaves as they lie: with symmetric inverses
        ``((G^-1 K^T) A^-1)^T = A^-1 (K G^-1)`` for the ``(d_in, d_out)``
        kernel gradient ``K``, the matrix form's two products in the same
        association, with no transposed copy on the way in or out.

        A bias is the last row of ``[K; b^T]``; it enters as the exact
        rank-one terms of the bordered ``A^-1 = [[A11, a], [a^T, alpha]]``
        rather than by joining that row to ``K`` (a copy of ``K``):
        ``P_K = A11 (K G^-1) + a (b^T G^-1)`` and
        ``P_b = a^T (K G^-1) + alpha (b^T G^-1)``. ``a`` is read as the
        border's row both times: sliced as a column out of a stack of
        inverses it made the TPU compiler re-lay the whole stack
        batch-minor on every step (offline compile, PR 34).
        """
        kg = view['kernel'].astype(a_inv.dtype) @ g_inv
        if not self.has_bias:
            return {'kernel': a_inv @ kg}
        d = self.in_features
        bg = view['bias'].astype(a_inv.dtype) @ g_inv
        border = a_inv[d, :d]
        return {
            'kernel': a_inv[:d, :d] @ kg + jnp.outer(border, bg),
            'bias': border @ kg + a_inv[d, d] * bg,
        }


@dataclasses.dataclass(frozen=True)
class Conv2dHelper(LayerHelper):
    """Helper for 2D convolutions (flax NHWC / HWIO layout).

    Reference equivalent: Conv2dModuleHelper
    (kfac/layers/modules.py:144-237). Patch features are channel-major
    (c, kh, kw), so the kernel matricizes as
    ``transpose(k, (3, 2, 0, 1)).reshape(d_out, -1)`` — verified against
    ``lax.conv_general_dilated`` output equality.
    """

    in_channels: int
    out_channels: int
    kernel_size: tuple[int, int]
    strides: tuple[int, int]
    padding: Any  # str or sequence of (lo, hi) pairs
    factor_dtype: Any = jnp.float32

    @property
    def a_factor_shape(self) -> tuple[int, int]:
        n = (
            self.in_channels * self.kernel_size[0] * self.kernel_size[1]
            + int(self.has_bias)
        )
        return (n, n)

    @property
    def g_factor_shape(self) -> tuple[int, int]:
        return (self.out_channels, self.out_channels)

    @property
    def patchless(self) -> bool:
        """Whether the A factor is assembled from the activation's
        autocorrelation, no patch row written: the geometry decides
        (:func:`kfac_tpu.ops.cov.conv2d_a_is_patchless`)."""
        return cov.conv2d_a_is_patchless(
            self.kernel_size, self.strides, self.padding
        )

    @property
    def contracts_late(self) -> bool:
        """The autocorrelation's products read the layer's input whole:
        left where the tap is, they hold it and their results through
        the step's fullest moment (``capture.contract_late``)."""
        return self.patchless

    def get_a_factor(self, a: jax.Array) -> jax.Array:
        return cov.conv2d_a_factor(
            a,
            kernel_size=self.kernel_size,
            strides=self.strides,
            padding=self.padding,
            has_bias=self.has_bias,
        ).astype(self.factor_dtype)

    def get_g_factor(self, g: jax.Array) -> jax.Array:
        return cov.conv2d_g_factor(g).astype(self.factor_dtype)

    def grads_to_matrix(self, grads: dict[str, jax.Array]) -> jax.Array:
        k = grads['kernel']  # (kh, kw, in, out)
        mat = jnp.transpose(k, (3, 2, 0, 1)).reshape(k.shape[3], -1)
        if self.has_bias:
            mat = jnp.concatenate([mat, grads['bias'][:, None]], axis=1)
        return mat

    def matrix_to_grads(self, mat: jax.Array) -> dict[str, jax.Array]:
        kh, kw = self.kernel_size
        cin, cout = self.in_channels, self.out_channels
        out: dict[str, jax.Array] = {}
        w = mat[:, :-1] if self.has_bias else mat
        k = w.reshape(cout, cin, kh, kw)
        out['kernel'] = jnp.transpose(k, (2, 3, 1, 0))
        if self.has_bias:
            out['bias'] = mat[:, -1]
        return out


@dataclasses.dataclass(frozen=True)
class LoRAHelper(LayerHelper):
    """Fused helper for a LoRA adapter pair registered as ONE unit.

    A :class:`kfac_tpu.models.lora.LoRADense` computes
    ``base(x) + up(down(x)) * (alpha/rank)`` with the base projection
    frozen; K-FAC preconditions the trainable ``down`` (d_in -> rank) and
    ``up`` (rank -> d_out) kernels jointly as one registered unit with
    BLOCK-DIAGONAL Kronecker factors::

        A = [[A_down, 0], [0, A_up]]   ((d_in+rank)^2, from x and h)
        G = [[G_down, 0], [0, G_up]]   ((rank+d_out)^2, from dh and dy)

    Block-diagonal factors invert block-wise, and the packed gradient
    matrix is block-diagonal too, so the preconditioned result is EXACTLY
    two-layer K-FAC over the adapters — the cross-adapter covariance
    blocks are the (documented, zeroed) approximation. Each child module
    carries its own capture tap (``Registry.taps`` routes it here by
    role); a role's block arrives pre-scaled by the role count so the
    capture's shared invocation counter averages back to the true
    block-diagonal factor. G blocks use the ROUTED normalization
    (cov.routed_linear_g_factor): at the standard zero-init of the up
    kernel every down cotangent is identically zero, and the live-row
    normalization keeps that dead G block at zero (EMA leaves the
    identity) instead of diluting it with 0/N mass.

    The adapters carry no bias (``has_bias`` is always False); the frozen
    base bias stays outside the unit entirely.
    """

    in_features: int = 0
    rank: int = 0
    out_features: int = 0
    factor_dtype: Any = jnp.float32

    ROLES = ('down', 'up')

    def __post_init__(self) -> None:
        if self.has_bias:
            raise ValueError(
                'LoRAHelper has no bias column: adapter projections are '
                'bias-free and the frozen base bias is not preconditioned'
            )

    @property
    def a_factor_shape(self) -> tuple[int, int]:
        n = self.in_features + self.rank
        return (n, n)

    @property
    def g_factor_shape(self) -> tuple[int, int]:
        n = self.rank + self.out_features
        return (n, n)

    def _embed(self, block: jax.Array, dim: int, lo: int) -> jax.Array:
        out = jnp.zeros((dim, dim), dtype=block.dtype)
        # pre-scale by the role count: the capture accumulator counts each
        # role tap as one invocation, so the shared divisor (2 per forward
        # call) averages the embedded blocks back to weight 1 each
        return out.at[
            lo : lo + block.shape[0], lo : lo + block.shape[0]
        ].set(block * len(self.ROLES))

    def role_a_factor(self, role: str, a: jax.Array) -> jax.Array:
        dim = self.a_factor_shape[0]
        fac = cov.linear_a_factor(a, has_bias=False).astype(
            self.factor_dtype
        )
        lo = 0 if role == 'down' else self.in_features
        return self._embed(fac, dim, lo)

    def role_g_factor(self, role: str, g: jax.Array) -> jax.Array:
        dim = self.g_factor_shape[0]
        fac = cov.routed_linear_g_factor(g).astype(self.factor_dtype)
        lo = 0 if role == 'down' else self.rank
        return self._embed(fac, dim, lo)

    def get_a_factor(self, a: jax.Array) -> jax.Array:
        raise NotImplementedError(
            'LoRA units capture through per-role taps (Registry.taps), '
            'not a module-level A tap'
        )

    def get_g_factor(self, g: jax.Array) -> jax.Array:
        raise NotImplementedError(
            'LoRA units capture through per-role taps (Registry.taps), '
            'not a module-level g-tap'
        )

    def grads_to_matrix(self, grads: dict[str, Any]) -> jax.Array:
        r, di, do = self.rank, self.in_features, self.out_features
        mat = jnp.zeros((r + do, di + r), dtype=grads['down']['kernel'].dtype)
        mat = mat.at[:r, :di].set(grads['down']['kernel'].T)
        mat = mat.at[r:, di:].set(grads['up']['kernel'].T)
        return mat

    def matrix_to_grads(self, mat: jax.Array) -> dict[str, Any]:
        r, di = self.rank, self.in_features
        return {
            'down': {'kernel': mat[:r, :di].T},
            'up': {'kernel': mat[r:, di:].T},
        }


@dataclasses.dataclass(frozen=True)
class ExpertStackTap:
    """The one capture tap of a stacked expert projection
    (:class:`kfac_tpu.models.moe.ExpertProjection`).

    The engines see each held expert's projection as a layer of its own
    (``slots``: routed bias-free :class:`DenseHelper` entries of the
    registry, one size class, so they land side by side in a bucket); the
    program runs one stacked product, and this tap computes all the
    experts' factors from its one input and its one output cotangent:
    ``(E_here, d, d)`` sums of ``r^T r`` over each expert's own rows
    (:func:`kfac_tpu.ops.grouped.grouped_cov`, which walks the plan's
    blocks in use), divided by the rows the plan counted for that expert.
    An expert with no row gives zeros with weight 0, which the factor EMA
    ignores: its factors stay as they were.

    ``mode`` is the projection's: ``'gather'`` reads token rows and
    returns plan blocks, ``'combine'`` reads blocks and returns tokens
    (each row weighted by its routing weight and summed into its token:
    the cotangent of a row of the expert's own output is the token's,
    times that weight).
    """

    name: str
    slots: tuple[str, ...]
    out_features: int
    mode: str
    factor_dtype: Any = jnp.float32

    def _factors(self, sums: jax.Array, plan: Any) -> jax.Array:
        # one elementwise pass over the sums (each ``r^T r`` is symmetric
        # as computed; a second pass to symmetrise would hold a second
        # ``(E_here, d, d)`` value a stack at the step's memory peak)
        scale = 1.0 / jnp.maximum(plan.rows, 1).astype(jnp.float32)
        return (sums * scale[:, None, None]).astype(self.factor_dtype)

    def a_factors(self, x: jax.Array, plan: Any) -> jax.Array:
        """``(E_here, d_in, d_in)``: each expert's input second moment
        over its own rows; zeros for an expert without rows."""
        from kfac_tpu.ops import grouped

        return self._factors(grouped.grouped_cov(
            x, plan.block_expert, plan.n_blocks, len(self.slots),
            row_token=plan.row_token if self.mode == 'gather' else None,
        ), plan)

    def g_factors(self, ybar: jax.Array, plan: Any) -> jax.Array:
        """The same of the output cotangent's rows."""
        from kfac_tpu.ops import grouped

        combine = self.mode == 'combine'
        return self._factors(grouped.grouped_cov(
            ybar, plan.block_expert, plan.n_blocks, len(self.slots),
            row_token=plan.row_token if combine else None,
            row_weight=plan.row_weight if combine else None,
        ), plan)

    def live(self, plan: Any) -> jax.Array:
        """``(E_here,)`` capture weights: 1 where the expert saw a row."""
        return (plan.rows > 0).astype(self.factor_dtype)

    def traffic(self, plan: Any) -> jax.Array:
        """``(E_here + 1,)`` float32: each expert's live rows, then the
        assignments to held experts that the plan left out."""
        return jnp.concatenate(
            [plan.rows, plan.dropped[None]]
        ).astype(jnp.float32)


def patchless_share(layers: Iterable[LayerHelper]) -> float | None:
    """The share of the convolutions with a kernel larger than 1 x 1
    whose A factor takes the patchless route
    (:attr:`Conv2dHelper.patchless`); ``None`` where there is none."""
    routes = [
        h.patchless for h in layers
        if isinstance(h, Conv2dHelper) and tuple(h.kernel_size) != (1, 1)
    ]
    return sum(routes) / len(routes) if routes else None


def matrix_param_count(helper: LayerHelper) -> int:
    """Number of elements in the packed gradient matrix for a helper."""
    return helper.g_factor_shape[0] * helper.a_factor_shape[0]
