"""Model analysis: discover supported layers in a flax model.

TPU-native replacement for the reference's module registration walk
(kfac/layers/register.py:20-95). Instead of iterating ``model.modules()`` and
attaching hooks, we trace the model once under ``jax.eval_shape`` with a flax
method interceptor, recording every supported module invocation (path, kind,
shapes, bias) — the same trace machinery later computes the curvature taps, so
registration and capture can never disagree about which layers exist.
"""

from __future__ import annotations

import dataclasses
import re
from collections.abc import Mapping
from typing import Any, Callable, Iterable

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfac_tpu.layers import helpers

def path_name(path: Iterable[str]) -> str:
    return '/'.join(path)


def any_match(query: str, patterns: list[re.Pattern[str]]) -> bool:
    """True if any pattern fully matches the query.

    Reference: kfac/layers/register.py:46-54.
    """
    return any(p.fullmatch(query) is not None for p in patterns)


def _normalize_conv_geometry(mod: nn.Conv) -> tuple[tuple[int, int], tuple[int, int], Any]:
    ks = mod.kernel_size
    if isinstance(ks, int):
        ks = (ks, ks)
    strides = mod.strides or (1, 1)
    if isinstance(strides, int):
        strides = (strides, strides)
    padding = mod.padding
    if isinstance(padding, int):
        padding = [(padding, padding), (padding, padding)]
    elif not isinstance(padding, str):
        # flax allows Sequence[int] or Sequence[(lo, hi)]; normalize to pairs
        padding = [
            (p, p) if isinstance(p, int) else tuple(p) for p in padding
        ]
    return tuple(ks), tuple(strides), padding


def _conv_is_dilated(mod: nn.Conv) -> bool:
    def nontrivial(d: Any) -> bool:
        if d is None:
            return False
        if isinstance(d, int):
            return d != 1
        return any(x != 1 for x in d)

    return nontrivial(mod.kernel_dilation) or nontrivial(mod.input_dilation)


def make_helper(
    module: nn.Module,
    name: str,
    input_shape: tuple[int, ...],
    factor_dtype: Any = jnp.float32,
) -> helpers.LayerHelper | None:
    """Build a LayerHelper for a supported flax module, else None.

    Type dispatch analogue of kfac/layers/register.py:36-43.
    """
    if isinstance(module, nn.Dense):
        return helpers.DenseHelper(
            name=name,
            has_bias=module.use_bias,
            in_features=input_shape[-1],
            out_features=module.features,
            factor_dtype=factor_dtype,
        )
    if isinstance(module, nn.Conv):
        if len(input_shape) != 4:
            return None  # only 2D convs (NHWC) are supported, like reference
        ks, strides, padding = _normalize_conv_geometry(module)
        if len(ks) != 2:
            return None
        if getattr(module, 'feature_group_count', 1) != 1:
            return None  # grouped/depthwise convs unsupported (as in reference)
        if _conv_is_dilated(module):
            return None  # patch extraction assumes undilated receptive field
        if isinstance(module.padding, str) and module.padding.upper() not in (
            'SAME', 'VALID',
        ):
            # flax implements CIRCULAR/CAUSAL/REFLECT by pre-padding; the
            # patch geometry would be wrong, so leave such convs unregistered
            return None
        return helpers.Conv2dHelper(
            name=name,
            has_bias=module.use_bias,
            in_channels=input_shape[-1],
            out_channels=module.features,
            kernel_size=ks,
            strides=strides,
            padding=padding,
            factor_dtype=factor_dtype,
        )
    return None


@dataclasses.dataclass(frozen=True)
class Registry:
    """Immutable result of model analysis.

    ``layers`` maps registry name -> LayerHelper;
    ``param_paths`` maps registry name -> tuple path into the params pytree
    (the module path), used to slice gradients in and out.
    ``taps`` maps a capture-time module path -> ``(unit_name, role)`` for
    multi-module registered units (LoRA adapter pairs): the unit itself
    has no ``__call__`` tap; its child projections do, and each routes its
    statistics into the unit's block of the fused factors. Empty for
    ordinary registries, so the capture fast path never consults it.
    """

    layers: dict[str, helpers.LayerHelper]
    param_paths: dict[str, tuple[str, ...]]
    taps: dict[str, tuple[str, str]] = dataclasses.field(
        default_factory=dict
    )
    # capture-time module path of a stacked expert projection -> its one
    # tap; the experts themselves are ``layers`` entries (the tap's
    # ``slots``), so the engines need to know nothing of stacks
    stacks: dict[str, helpers.ExpertStackTap] = dataclasses.field(
        default_factory=dict
    )
    # parameter leaves that no registered layer owns, 'a/b/c' -> why: they
    # pass the preconditioner unchanged and take the first-order update
    # (:func:`passthrough_leaves`). Empty where registration saw no
    # parameters (``apply_fn``).
    passthrough: dict[str, str] = dataclasses.field(default_factory=dict)
    # the leaves the registered layers do own, in the same form
    kfac_leaves: tuple[str, ...] = ()
    # A groups, member -> leader (:func:`find_a_groups`): Dense layers that
    # the probe saw handed the same input array at the same compute dtype
    # have one A factor, the leader's (the first of them in registration
    # order; it maps to itself). A layer in no group of two or more is
    # absent. Capture contracts a group's A once and the engines keep one
    # A slot for it; emptied (``dataclasses.replace(reg, a_groups={})``)
    # every layer keeps its own.
    a_groups: dict[str, str] = dataclasses.field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.layers)

    def names(self) -> list[str]:
        return list(self.layers)

    def a_leader(self, name: str) -> str:
        """The layer whose A factor ``name`` preconditions with: its
        group's leader, or itself."""
        return self.a_groups.get(name, name)

    def a_members(self) -> dict[str, tuple[str, ...]]:
        """leader -> its group's members (itself first), in registration
        order; groups of two or more only."""
        return group_members(self.a_groups, self.layers)

    def describe(self) -> str:
        """The A groups, one line each: ``leader <- follower, ...``."""
        groups = self.a_members()
        if not groups:
            return 'A groups: none (every layer keeps its own A factor)'
        shared = sum(len(m) - 1 for m in groups.values())
        lines = [
            f'A groups: {len(groups)} ({shared} of {len(self.layers)} '
            'layers read their leader\'s A factor)'
        ]
        for leader, members in groups.items():
            lines.append(f'  {leader} <- ' + ', '.join(members[1:]))
        return '\n'.join(lines)


def compute_dtype(module: nn.Module, dtype: Any) -> Any:
    """The dtype ``module`` multiplies an input of ``dtype`` in (flax: its
    ``dtype``, or without one the promotion of input and parameters)."""
    own = getattr(module, 'dtype', None)
    if own is not None:
        return jnp.dtype(own)
    return jnp.promote_types(dtype, getattr(module, 'param_dtype', dtype))


def group_members(
    a_groups: dict[str, str], names: Iterable[str]
) -> dict[str, tuple[str, ...]]:
    """leader (as ``a_groups`` names it) -> the members among ``names``,
    in their order."""
    out: dict[str, list[str]] = {}
    for name in names:
        if name in a_groups:
            out.setdefault(a_groups[name], []).append(name)
    return {k: tuple(v) for k, v in out.items()}


def regroup(
    a_groups: dict[str, str], names: Iterable[str]
) -> dict[str, str]:
    """``a_groups`` over the layers of ``names`` (in their order) that are
    left: a group's first surviving member leads, and a group left with
    one member is none."""
    return {
        name: group[0]
        for group in group_members(a_groups, names).values()
        if len(group) > 1
        for name in group
    }


def _mask_value(mask: Any, path: tuple[str, ...], name: str) -> bool:
    """Resolve an optax-style trainability mask at one layer's param path.

    The mask is a prefix pytree of bools over the params: a bool at any
    prefix covers the whole subtree beneath it, and a path the mask does
    not mention is trainable (``True``) — so ``{'backbone': False}``
    freezes every backbone layer without spelling out its leaves, exactly
    like ``optax.masked``'s pytree convention. A layer whose OWN subtree
    mixes True and False leaves is an error: K-FAC preconditions the
    layer's kernel+bias jointly, so per-leaf splits inside one layer have
    no factor-level meaning.
    """
    node = mask
    for key in path:
        if isinstance(node, bool):
            return node
        if not isinstance(node, Mapping):
            raise TypeError(
                f'mask node at a prefix of layer {name!r} is '
                f'{type(node).__name__}; expected a bool or a mapping '
                '(optax-style prefix pytree of bools)'
            )
        if key not in node:
            return True
        node = node[key]
    if isinstance(node, bool):
        return node
    leaves = jax.tree_util.tree_leaves(node)
    if not leaves:
        return True
    values = {bool(v) for v in leaves}
    if len(values) > 1:
        raise ValueError(
            f'mask splits layer {name!r} into trainable and frozen '
            'leaves; K-FAC preconditions a layer jointly, so mask whole '
            'layers (a bool at the layer path or a uniform subtree)'
        )
    return values.pop()


def masked_registry(registry: Registry, mask: Any) -> Registry:
    """Registry with mask-frozen layers removed (``mask=None`` is identity).

    This is THE mask mechanism: every downstream consumer — capture taps,
    engine factor state, KAISA bucketing/assignment, the autotune cost
    model, metrics keys, checkpoints, ``describe()`` — keys off
    ``registry.layers``, and unregistered parameters already pass through
    the preconditioner untouched, so dropping a layer here excludes it
    everywhere at once (the reference's frozen-parameter skip,
    kfac/layers/register.py:31-33). LoRA units resolve the mask at their
    adapter paths (``down``/``up``); the ``base`` projection inside a
    unit is never preconditioned, so freezing it does not freeze the
    unit, but the two adapters must agree.
    """
    if mask is None:
        return registry
    keep: dict[str, helpers.LayerHelper] = {}
    paths: dict[str, tuple[str, ...]] = {}
    for name, helper in registry.layers.items():
        path = registry.param_paths[name]
        if isinstance(helper, helpers.LoRAHelper):
            roles = {
                role: _mask_value(mask, path + (role,), name)
                for role in ('down', 'up')
            }
            if len(set(roles.values())) > 1:
                raise ValueError(
                    f'mask freezes one adapter of LoRA unit {name!r} but '
                    f'not the other ({roles}); the pair preconditions as '
                    'one unit, so mask both the same way'
                )
            trainable = roles['down']
        else:
            trainable = _mask_value(mask, path, name)
        if trainable:
            keep[name] = helper
            paths[name] = path
    taps = {
        tap: (unit, role)
        for tap, (unit, role) in registry.taps.items()
        if unit in keep
    }
    stacks = {}
    for name, tap in registry.stacks.items():
        kept = [slot in keep for slot in tap.slots]
        if any(kept) and not all(kept):
            raise ValueError(
                f'mask freezes some experts of the stacked projection '
                f'{name!r} and not others; its experts are captured by '
                'one tap, so mask the projection whole'
            )
        if all(kept):
            stacks[name] = tap
    frozen = {
        '/'.join(registry.param_paths[n]) for n in registry.layers
        if n not in keep
    }
    passthrough = dict(registry.passthrough)
    passthrough.update({
        leaf: 'frozen by the mask'
        for leaf in registry.kfac_leaves
        if any(leaf.startswith(f + '/') for f in frozen)
    })
    return Registry(
        layers=keep, param_paths=paths, taps=taps, stacks=stacks,
        passthrough=passthrough,
        kfac_leaves=tuple(
            leaf for leaf in registry.kfac_leaves
            if leaf not in passthrough
        ),
        a_groups=regroup(registry.a_groups, keep),
    )


# Which parameters K-FAC does not factor. A leaf is K-FAC's only if it
# belongs to a registered layer: the kernel (and bias) of an ``nn.Dense``,
# of a 2-D undilated ungrouped ``nn.Conv``, of a LoRA adapter pair, or of an
# expert of a stacked projection, that no ``skip_layers`` pattern names and
# no mask freezes. Every other leaf passes the preconditioner unchanged and
# takes the optimizer's first-order update; ``Registry.passthrough`` lists
# them with the clause that applies:
PASSTHROUGH_RULE = {
    'skipped': 'its layer matches a skip_layers pattern',
    'embedding': 'an embedding table is a look-up, not a product with an '
                 'activation: its A factor would be as wide as the '
                 'vocabulary',
    'convolution': 'a convolution that is not 2-D, or is grouped '
                   '(depthwise), dilated or padded by wrapping: no patch '
                   'covariance is defined for it here',
    'elementwise': 'a vector that its module applies elementwise (a norm '
                   'weight, a gate\'s decay or bias): it has no '
                   'Kronecker-factored curvature',
    'unsupported': 'a matrix of a module kind that has no helper',
}


def passthrough_leaves(
    params: Any,
    param_paths: dict[str, tuple[str, ...]],
    modules: dict[tuple[str, ...], tuple[str, bool]],
) -> tuple[dict[str, str], tuple[str, ...]]:
    """Sort the leaves of ``params`` by :data:`PASSTHROUGH_RULE`.

    ``modules``: path -> (class name, whether ``skip_layers`` named it) of
    every module the probe called. Returns ``({leaf: clause}, kfac
    leaves)``, leaves as ``'a/b/c'``.
    """
    owned = set(param_paths.values())
    out: dict[str, str] = {}
    kfac: list[str] = []
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, value in flat:
        keys = tuple(str(getattr(k, 'key', k)) for k in path)
        leaf = '/'.join(keys)
        if any(keys[:i] in owned for i in range(1, len(keys))):
            kfac.append(leaf)
            continue
        cls, skipped = 'module', False
        for i in range(len(keys) - 1, 0, -1):
            if keys[:i] in modules:
                cls, skipped = modules[keys[:i]]
                break
        if skipped:
            out[leaf] = 'skipped'
        elif cls == 'embed':
            out[leaf] = 'embedding'
        elif 'conv' in cls:
            out[leaf] = 'convolution'
        elif len(value.shape) > 1:
            out[leaf] = 'unsupported'
        else:
            out[leaf] = 'elementwise'
    return out, tuple(kfac)


def register_model(
    model: nn.Module,
    *args: Any,
    skip_layers: list[str] | None = None,
    routed_layers: list[str] | None = None,
    mask: Any = None,
    factor_dtype: Any = jnp.float32,
    apply_fn: Callable[..., Any] | None = None,
    **kwargs: Any,
) -> Registry:
    """Analyze ``model`` on example inputs and return its K-FAC registry.

    Runs ``model.init`` under ``jax.eval_shape`` (no FLOPs, no memory) with an
    interceptor that records each supported module call. ``skip_layers`` are
    regex patterns matched against both the layer path name and the module
    class name (reference semantics: kfac/layers/register.py:57-95).

    ``routed_layers`` (regexes over the layer path, dense layers only)
    mark row-masked layers — MoE expert projections whose input buffers
    zero the non-routed rows — for routed capture: factors normalize by
    the live row count and bias ones attach only to live rows, making the
    captured statistics EXACTLY the per-expert oracle instead of the
    routed-fraction-scaled approximation (e.g.
    ``routed_layers=[r'.*expert\\d+_(up|down)']`` for ``models/moe.py``).

    ``mask`` is an optax-style trainability pytree of bools over the
    params (prefix semantics: a bool at any prefix covers its subtree,
    unmentioned paths are trainable): layers whose params the mask
    freezes are dropped from the registry, so they get no capture taps,
    no factors, no engine slots, and their gradients pass through the
    preconditioner untouched — see :func:`masked_registry`.

    Modules declaring ``_kfac_lora_unit = True``
    (:class:`kfac_tpu.models.lora.LoRADense`) register as ONE unit: the
    adapter pair's factors are block-diagonal in a single fused helper
    (:class:`kfac_tpu.layers.helpers.LoRAHelper`), their child taps
    recorded in ``Registry.taps``; the frozen ``base`` projection and any
    modules nested under a unit are not registered separately.

    Modules declaring ``_kfac_expert_stack = True``
    (:class:`kfac_tpu.models.moe.ExpertProjection`) register every held
    expert as a layer of its own, ``<path>/e<j>`` (a routed bias-free
    dense helper), and one :class:`~kfac_tpu.layers.helpers
    .ExpertStackTap` for the projection in ``Registry.stacks``.

    The registry also reports what K-FAC leaves alone:
    ``Registry.passthrough`` maps every parameter leaf that no registered
    layer owns to the clause of :data:`PASSTHROUGH_RULE` that applies.
    """
    skip_patterns = [re.compile(p) for p in (skip_layers or [])]
    routed_patterns = [re.compile(p) for p in (routed_layers or [])]
    found: dict[str, helpers.LayerHelper] = {}
    param_paths: dict[str, tuple[str, ...]] = {}
    taps: dict[str, tuple[str, str]] = {}
    stacks: dict[str, helpers.ExpertStackTap] = {}
    unit_prefixes: list[tuple[str, ...]] = []
    modules: dict[tuple[str, ...], tuple[str, bool]] = {}
    # for the A groups: how often the probe called each Dense layer and
    # stacked projection, and what it was handed the first time (the
    # arrays by identity: ``alive`` keeps them, so that no id is reused)
    calls: dict[str, int] = {}
    handed: dict[str, tuple] = {}
    alive: list[Any] = []

    def interceptor(next_fun, iargs, ikwargs, context):
        mod = context.module
        if context.method_name != '__call__' or not iargs:
            return next_fun(*iargs, **ikwargs)
        x = iargs[0]
        if not hasattr(x, 'shape'):
            return next_fun(*iargs, **ikwargs)
        name = path_name(mod.path)
        cls_name = type(mod).__name__.lower()
        skipped = any_match(name, skip_patterns) or any_match(
            cls_name, skip_patterns
        )
        modules[tuple(mod.path)] = (cls_name, skipped)
        if skipped:
            return next_fun(*iargs, **ikwargs)
        path = tuple(mod.path)
        if getattr(type(mod), '_kfac_expert_stack', False):
            calls[name] = calls.get(name, 0) + 1
            if name not in stacks:
                plan = iargs[1] if len(iargs) > 1 else ikwargs.get('plan')
                alive.extend((x, plan))
                handed[name] = (
                    'stack', id(x), id(plan), compute_dtype(mod, x.dtype),
                    int(mod.experts),
                )
                slots = tuple(f'{name}/e{j}' for j in range(mod.experts))
                for j, slot in enumerate(slots):
                    found[slot] = helpers.DenseHelper(
                        name=slot, has_bias=False,
                        in_features=int(x.shape[-1]),
                        out_features=int(mod.features),
                        factor_dtype=factor_dtype, routed=True,
                    )
                    param_paths[slot] = path + (f'e{j}',)
                stacks[name] = helpers.ExpertStackTap(
                    name=name, slots=slots,
                    out_features=int(mod.features), mode=mod.mode,
                    factor_dtype=factor_dtype,
                )
            return next_fun(*iargs, **ikwargs)
        if getattr(type(mod), '_kfac_lora_unit', False):
            if name not in found:
                found[name] = helpers.LoRAHelper(
                    name=name,
                    has_bias=False,
                    in_features=int(x.shape[-1]),
                    rank=int(mod.rank),
                    out_features=int(mod.features),
                    factor_dtype=factor_dtype,
                )
                param_paths[name] = path
                taps[f'{name}/down'] = (name, 'down')
                taps[f'{name}/up'] = (name, 'up')
                unit_prefixes.append(path)
            return next_fun(*iargs, **ikwargs)
        if any(path[: len(p)] == p for p in unit_prefixes):
            # children of a registered unit (base/down/up projections)
            # belong to the unit's fused helper, never to the registry
            # directly
            return next_fun(*iargs, **ikwargs)
        helper = make_helper(mod, name, tuple(x.shape), factor_dtype)
        if helper is not None:
            calls[name] = calls.get(name, 0) + 1
        if helper is not None and name not in found:
            if any_match(name, routed_patterns):
                if not isinstance(helper, helpers.DenseHelper):
                    raise ValueError(
                        f'routed_layers matched {name!r}, which is not a '
                        'dense layer (routed capture is defined for '
                        'row-masked dense inputs only)'
                    )
                helper = dataclasses.replace(helper, routed=True)
            found[name] = helper
            param_paths[name] = tuple(mod.path)
            if isinstance(helper, helpers.DenseHelper):
                alive.append(x)
                handed[name] = (
                    'dense', id(x), compute_dtype(mod, x.dtype),
                    helper.a_factor_shape, helper.has_bias, helper.routed,
                )
        return next_fun(*iargs, **ikwargs)

    def is_traceable(v: Any) -> bool:
        return hasattr(v, 'shape') and hasattr(v, 'dtype')

    # Abstract exactly the array-like pytree leaves under eval_shape (so no
    # real FLOPs/memory are spent), while non-array leaves (train=False
    # flags etc.) stay static so model control flow on them works during
    # the probe. Containers are handled per-leaf.
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    traced_positions = [i for i, leaf in enumerate(leaves) if is_traceable(leaf)]

    def probe(traced_leaves):
        full = list(leaves)
        for pos, v in zip(traced_positions, traced_leaves):
            full[pos] = v
        full_args, full_kwargs = jax.tree_util.tree_unflatten(treedef, full)
        with nn.intercept_methods(interceptor):
            if apply_fn is not None:
                return apply_fn(*full_args, **full_kwargs)
            return model.init(jax.random.PRNGKey(0), *full_args, **full_kwargs)

    probed = jax.eval_shape(probe, [leaves[i] for i in traced_positions])
    if routed_patterns:
        unmatched = [
            p.pattern
            for p in routed_patterns
            if not any(p.fullmatch(name) for name in found)
        ]
        if unmatched:
            raise ValueError(
                f'routed_layers patterns {unmatched} matched no registered '
                'layer — a typo here silently reverts the expert layers to '
                'the approximate shared-normalization capture, so it is an '
                f'error. Registered layers: {sorted(found)}'
            )
    passthrough, kfac_leaves = {}, ()
    if apply_fn is None and isinstance(probed, Mapping) and 'params' in probed:
        passthrough, kfac_leaves = passthrough_leaves(
            probed['params'], param_paths, modules
        )
    registry = Registry(
        layers=dict(found),
        param_paths=dict(param_paths),
        taps=dict(taps),
        stacks=dict(stacks),
        passthrough=passthrough,
        kfac_leaves=kfac_leaves,
        a_groups=find_a_groups(found, stacks, handed, calls),
    )
    return masked_registry(registry, mask)


def find_a_groups(
    layers: dict[str, helpers.LayerHelper],
    stacks: dict[str, helpers.ExpertStackTap],
    handed: dict[str, tuple],
    calls: dict[str, int],
) -> dict[str, str]:
    """The A groups of a probe, member -> leader (``Registry.a_groups``).

    ``A = E[a a^T]`` depends on a layer's input alone, so layers that were
    handed the same array and multiply it at the same dtype
    (:func:`compute_dtype`, what ``capture.layer_input`` rounds to) hold
    equal A factors: they form a group, told by the array's identity at
    the probe and by nothing else. ``handed``: what each Dense layer
    (``nn.Dense``: not a convolution, whose A is of patches, nor a LoRA
    unit's children) or stacked expert projection met at its first call,
    as ``register_model`` recorded it; ``calls``: how often it was called
    (a module called more than once sums several inputs' statistics and
    stays alone). Two stacked projections handed the same ``(x, plan)``
    pair their experts slot by slot.
    """
    leaders: dict[tuple, str] = {}
    out: dict[str, str] = {}
    for name, key in handed.items():
        if calls.get(name, 0) != 1:
            continue
        first = leaders.setdefault(key, name)
        if first == name:
            continue
        if key[0] == 'stack':
            pairs = zip(stacks[name].slots, stacks[first].slots)
        else:
            pairs = [(name, first)]
        for member, leader in pairs:
            out[leader] = leader
            out[member] = leader
    # in registration order, leaders first
    return regroup(out, layers)


def slice_layer_grads(
    grads: Any,
    registry: Registry,
) -> dict[str, dict[str, jax.Array]]:
    """Extract each registered layer's grad leaves from a params-shaped pytree."""
    out: dict[str, dict[str, jax.Array]] = {}
    for name, path in registry.param_paths.items():
        node = grads
        for key in path:
            node = node[key]
        out[name] = dict(node)
    return out


def merge_layer_grads(
    grads: Any,
    layer_grads: dict[str, dict[str, jax.Array]],
    registry: Registry,
) -> Any:
    """Write preconditioned layer grads back into a full grad pytree (pure)."""

    def replace(node: Any, path: tuple[str, ...], value: dict[str, jax.Array]) -> Any:
        if not path:
            new = dict(node)
            new.update(value)
            return new
        new = dict(node)
        new[path[0]] = replace(node[path[0]], path[1:], value)
        return new

    out = grads
    for name, value in layer_grads.items():
        out = replace(out, registry.param_paths[name], value)
    return out


def merge_registries(*registries: Registry) -> Registry:
    """Union of disjoint registries into one (e.g. a model's interceptor
    registry plus per-block EP registries, so a single K-FAC engine
    preconditions every layer). Name collisions are an error — give each
    EP block a distinct ``name_prefix``."""
    layers: dict[str, helpers.LayerHelper] = {}
    paths: dict[str, tuple[str, ...]] = {}
    taps: dict[str, tuple[str, str]] = {}
    stacks: dict[str, helpers.ExpertStackTap] = {}
    passthrough: dict[str, str] = {}
    kfac_leaves: tuple[str, ...] = ()
    a_groups: dict[str, str] = {}
    for r in registries:
        overlap = set(layers) & set(r.layers)
        if overlap:
            raise ValueError(
                f'layer names collide across registries: {sorted(overlap)}'
            )
        layers.update(r.layers)
        paths.update(r.param_paths)
        taps.update(r.taps)
        stacks.update(r.stacks)
        passthrough.update(r.passthrough)
        kfac_leaves += r.kfac_leaves
        a_groups.update(r.a_groups)
    return Registry(
        layers=layers, param_paths=paths, taps=taps, stacks=stacks,
        passthrough=passthrough, kfac_leaves=kfac_leaves,
        a_groups=a_groups,
    )
