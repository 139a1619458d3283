"""Tensor-parallel parameter sharding rules (Megatron-style).

Capability parity with the reference's GPT-NeoX subpackage
(kfac/gpt_neox/: ColumnParallelLinear/RowParallelLinear recognition,
gather-precondition-rescatter of sharded layers, TP-aware factor shapes).
Under pjit the machinery dissolves into *layout rules*:

- Column-parallel (output-sharded) and row-parallel (input-sharded) weights
  are just PartitionSpecs over the ``model`` axis; activations between the
  paired projections stay sharded over ``model`` and XLA inserts the same
  all-reduce Megatron does by hand.
- K-FAC factor statistics are computed from *global* activations/cotangents
  (the interceptor sees global arrays), so the reference's primary-rank
  gather of sharded activations (kfac/gpt_neox/layer.py:129-163) becomes an
  XLA-chosen collective in the covariance contraction.
- Preconditioning a sharded weight gathers its gradient into the stacked
  bucket, preconditions, and reshards on write-back — semantically the
  reference's gather -> precondition -> scatter (kfac/gpt_neox/layer.py:
  165-311), scheduled by the compiler.

Rules are regex -> PartitionSpec over flattened param paths, in the spirit
of flax's logical partitioning but without requiring model changes.
"""

from __future__ import annotations

import re
import warnings as _warnings
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_tpu.layers import helpers as helpers_lib
from kfac_tpu.parallel import mesh as mesh_lib
from kfac_tpu.warnings import ExperimentalFeatureWarning

# (path regex, spec) — first match wins; default replicated.
TRANSFORMER_TP_RULES: tuple[tuple[str, P], ...] = (
    # column-parallel: shard output features
    (r'.*(q_proj|k_proj|v_proj|mlp_up)/kernel', P(None, mesh_lib.MODEL_AXIS)),
    (r'.*(q_proj|k_proj|v_proj|mlp_up)/bias', P(mesh_lib.MODEL_AXIS)),
    # row-parallel: shard input features; bias replicated
    (r'.*(out_proj|mlp_down)/kernel', P(mesh_lib.MODEL_AXIS, None)),
    # output head: vocab-sharded
    (r'.*lm_head/kernel', P(None, mesh_lib.MODEL_AXIS)),
)


def param_specs(
    params: Any,
    rules: Sequence[tuple[str, P]] = TRANSFORMER_TP_RULES,
) -> Any:
    """PartitionSpec pytree for ``params`` from path-regex rules."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(path, leaf) -> P:
        name = '/'.join(str(getattr(k, 'key', k)) for k in path)
        for pat, spec in compiled:
            if pat.fullmatch(name):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_params(
    params: Any,
    mesh: Mesh,
    rules: Sequence[tuple[str, P]] = TRANSFORMER_TP_RULES,
) -> Any:
    """Place ``params`` on the mesh according to the TP rules."""
    specs = param_specs(params, rules)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


class UnshardedParamWarning(ExperimentalFeatureWarning):
    """A parameter matched no TP rule and stays replicated."""


def _layer_specs(helper, kind: str, axis: str) -> dict[str, P]:
    """kernel/bias PartitionSpecs for one layer given its parallel kind.

    flax layouts: Dense kernel (in, out); Conv kernel (kh, kw, in, out).
    column-parallel shards the output features (bias sharded with them);
    row-parallel shards the input features (bias replicated, since outputs
    are partial sums that all-reduce before the bias add) — the reference's
    ColumnParallelLinear / RowParallelLinear layouts (kfac/gpt_neox/).
    """
    is_conv = isinstance(helper, helpers_lib.Conv2dHelper)
    if kind == 'column':
        kernel = (
            P(None, None, None, axis) if is_conv else P(None, axis)
        )
        return {'kernel': kernel, 'bias': P(axis)}
    if kind == 'row':
        kernel = (
            P(None, None, axis, None) if is_conv else P(axis, None)
        )
        return {'kernel': kernel, 'bias': P()}
    return {'kernel': P(), 'bias': P()}


def derive_layer_kinds(
    registry: Any,
    overrides: Sequence[tuple[str, str]] | None = None,
) -> dict[str, str]:
    """Per-registered-layer parallel kind: 'column', 'row', or 'replicated'.

    ``overrides`` are (layer-name regex, kind) pairs — the user-declaration
    analogue of the reference's ColumnParallelLinear/RowParallelLinear
    module types (kfac/gpt_neox/). Layers matched by no override get the
    shard-the-wide-side default: expanding layers (out > in) are
    column-parallel, contracting layers (out < in) row-parallel — the
    Megatron MLP pairing — and square layers stay replicated (sharding them
    needs a declaration of which side their neighbours shard).
    """
    compiled = [(re.compile(pat), kind) for pat, kind in (overrides or [])]
    for _, kind in compiled:
        if kind not in ('column', 'row', 'replicated'):
            raise ValueError(f'unknown parallel kind {kind!r}')
    kinds: dict[str, str] = {}
    for name, helper in registry.layers.items():
        kind = None
        for pat, k in compiled:
            if pat.fullmatch(name):
                kind = k
                break
        if kind is None:
            d_out = helper.g_factor_shape[0]
            d_in = helper.a_factor_shape[0] - int(helper.has_bias)
            kind = (
                'column' if d_out > d_in
                else 'row' if d_out < d_in
                else 'replicated'
            )
        kinds[name] = kind
    return kinds


def registry_param_specs(
    params: Any,
    registry: Any,
    overrides: Sequence[tuple[str, str]] | None = None,
    axis: str = mesh_lib.MODEL_AXIS,
    warn_unmatched: bool = True,
) -> Any:
    """PartitionSpec pytree derived from the K-FAC registry.

    Works on any registered model (no dependence on this repo's layer
    names). Parameters belonging to no registered layer (embeddings, norms,
    skipped layers) stay replicated; with ``warn_unmatched`` a warning lists
    them once so silent full replication of a model the user meant to shard
    is visible (the regex table silently replicated
    unknown models).
    """
    kinds = derive_layer_kinds(registry, overrides)
    spec_by_path: dict[tuple[str, ...], dict[str, P]] = {
        registry.param_paths[name]: _layer_specs(
            registry.layers[name], kind, axis
        )
        for name, kind in kinds.items()
    }

    unmatched: list[str] = []

    def spec_for(path, leaf) -> P:
        keys = tuple(str(getattr(k, 'key', k)) for k in path)
        layer_spec = spec_by_path.get(keys[:-1])
        if layer_spec is not None and keys[-1] in layer_spec:
            return layer_spec[keys[-1]]
        unmatched.append('/'.join(keys))
        return P()

    specs = jax.tree_util.tree_map_with_path(spec_for, params)
    if warn_unmatched and unmatched:
        shown = ', '.join(unmatched[:5])
        more = f' (+{len(unmatched) - 5} more)' if len(unmatched) > 5 else ''
        _warnings.warn(
            f'{len(unmatched)} params matched no TP rule and stay '
            f'replicated: {shown}{more}',
            UnshardedParamWarning,
            stacklevel=2,
        )
    return specs


def shard_params_from_registry(
    params: Any,
    mesh: Mesh,
    registry: Any,
    overrides: Sequence[tuple[str, str]] | None = None,
    axis: str = mesh_lib.MODEL_AXIS,
    warn_unmatched: bool = True,
) -> Any:
    """Shard ``params`` using registry-derived TP rules (any model)."""
    specs = registry_param_specs(
        params, registry, overrides, axis, warn_unmatched
    )
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
