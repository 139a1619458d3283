"""Jaxpr visitor utilities for the KFL2xx IR rules.

Pure functions over ``ClosedJaxpr``/``Jaxpr`` objects — no engine imports,
so tests can exercise every check on tiny hand-traced programs. The
recursion descends into every sub-jaxpr a primitive carries (``pjit``,
``shard_map``, ``cond`` branches, ``while`` cond/body, ``scan``), which is
where all the interesting eqns live: the engines' collectives and
decompositions sit inside ``shard_map`` bodies and ``lax.cond`` cadence
gates.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable, Iterator

import numpy as np

#: params keys under which a primitive stows a single sub-jaxpr
_SUBJAXPR_KEYS = ('jaxpr', 'call_jaxpr', 'cond_jaxpr', 'body_jaxpr')

#: eqn params keys that name collective axes
_AXIS_PARAM_KEYS = ('axes', 'axis_name', 'axis_index_groups')

#: primitives that execute host code from inside a traced program
CALLBACK_PRIMS = ('io_callback', 'pure_callback')


def _inner(sub: Any):
    """Unwrap ClosedJaxpr -> Jaxpr; pass Jaxpr through."""
    return getattr(sub, 'jaxpr', sub)


def subjaxprs(eqn) -> Iterator[Any]:
    for key in _SUBJAXPR_KEYS:
        sub = eqn.params.get(key)
        if sub is not None:
            yield _inner(sub)
    for br in eqn.params.get('branches', ()) or ():
        yield _inner(br)


def iter_eqns(jaxpr, depth: int = 0) -> Iterator[tuple[Any, int]]:
    """Yield ``(eqn, depth)`` for every eqn, recursing into sub-jaxprs."""
    jaxpr = _inner(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub, depth + 1)


def aval_bytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize


def _constraint_spec(eqn):
    sharding = eqn.params.get('sharding')
    return getattr(sharding, 'spec', None)


def is_replicated_spec(spec) -> bool:
    """True for a fully-replicated PartitionSpec (all entries None)."""
    return spec is not None and all(s is None for s in spec)


@dataclasses.dataclass(frozen=True)
class ConstraintPin:
    """One ``sharding_constraint`` eqn, summarized."""

    shape: tuple[int, ...]
    dtype: str
    bytes: int
    replicated: bool
    spec: str


def constraint_pins(jaxpr) -> list[ConstraintPin]:
    pins = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != 'sharding_constraint':
            continue
        spec = _constraint_spec(eqn)
        aval = eqn.invars[0].aval
        pins.append(ConstraintPin(
            shape=tuple(aval.shape),
            dtype=str(aval.dtype),
            bytes=aval_bytes(aval),
            replicated=is_replicated_spec(spec),
            spec=str(spec),
        ))
    return pins


def replicated_pin_bytes(pins: Iterable[ConstraintPin]) -> int:
    return sum(p.bytes for p in pins if p.replicated)


def total_pin_bytes(pins: Iterable[ConstraintPin]) -> int:
    return sum(p.bytes for p in pins)


def rank3_replicated_pin_bytes(pins: Iterable[ConstraintPin]) -> int:
    return sum(p.bytes for p in pins if p.replicated and len(p.shape) == 3)


# ------------------------------------------------------------ axis names


def _flatten_axis_names(value) -> Iterator[str]:
    if value is None:
        return
    if isinstance(value, str):
        yield value
        return
    if isinstance(value, dict):
        for v in value.values():
            yield from _flatten_axis_names(v)
        return
    if isinstance(value, (tuple, list, frozenset, set)):
        for v in value:
            yield from _flatten_axis_names(v)


def collective_axis_uses(jaxpr) -> list[tuple[str, str]]:
    """``(primitive name, axis name)`` for every named-axis reference.

    Covers explicit collectives (``psum``/``all_gather``/``ppermute``/
    ``all_to_all``/``axis_index``, via their ``axes``/``axis_name``
    params) and ``shard_map`` bindings (``in_names``/``out_names``).
    """
    uses: list[tuple[str, str]] = []
    for eqn, _ in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim == 'shard_map':
            for key in ('in_names', 'out_names'):
                for name in _flatten_axis_names(eqn.params.get(key)):
                    uses.append((prim, name))
            continue
        if prim == 'sharding_constraint':
            spec = _constraint_spec(eqn)
            if spec is not None:
                for name in _flatten_axis_names(tuple(spec)):
                    uses.append((prim, name))
            continue
        for key in _AXIS_PARAM_KEYS:
            if key in eqn.params:
                for name in _flatten_axis_names(eqn.params[key]):
                    uses.append((prim, name))
    return uses


def ppermute_bytes(jaxpr, axis_name: str | None = None) -> int:
    """Per-occurrence ``ppermute`` payload bytes in the traced program.

    Each ``ppermute`` equation is counted ONCE (a ``lax.scan`` body is
    symbolic — one equation per permute regardless of trip count), so for
    the pipeline scans this is the per-TICK wire traffic of one rank;
    multiply by the schedule's tick count for the per-step total. Pass
    ``axis_name`` to restrict the count to one mesh axis (e.g. the
    ``'pipe'`` ring).
    """
    total = 0
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != 'ppermute':
            continue
        if axis_name is not None:
            names = set(_flatten_axis_names(eqn.params.get('axis_name')))
            if axis_name not in names:
                continue
        total += sum(aval_bytes(v.aval) for v in eqn.invars)
    return total


def mesh_axis_names(jaxpr) -> set[str]:
    """Axis names of every mesh mentioned by ``shard_map``/sharding eqns."""
    names: set[str] = set()
    for eqn, _ in iter_eqns(jaxpr):
        mesh = eqn.params.get('mesh')
        axes = getattr(mesh, 'axis_names', None)
        if axes:
            names.update(axes)
        sharding = eqn.params.get('sharding')
        smesh = getattr(sharding, 'mesh', None)
        axes = getattr(smesh, 'axis_names', None)
        if axes:
            names.update(axes)
    return names


# ---------------------------------------------------------- dtype dataflow


@dataclasses.dataclass(frozen=True)
class DtypeViolation:
    primitive: str
    dtype: str
    kind: str  # 'demote' | 'promote'
    depth: int


def _float_kind(dtype, floor_bits: int) -> str | None:
    dt = np.dtype(dtype)
    # ml_dtypes extension floats (bfloat16, float8_*) register with
    # numpy kind 'V', not 'f' — match them by name
    if dt.kind != 'f' and 'float' not in dt.name:
        return None  # integer values are not factor math
    bits = dt.itemsize * 8
    if bits < floor_bits:
        return 'demote'
    if bits > floor_bits:
        return 'promote'
    return None


def dtype_flow(
    jaxpr,
    tainted_invars: Iterable[bool],
    floor_bits: int = 32,
) -> list[DtypeViolation]:
    """Track tainted (factor-math) values through the program and flag any
    floating-point result below ``floor_bits`` (silent demotion) or above
    it (accidental f64 promotion).

    Taint propagates eqn-by-eqn: any tainted operand taints every output.
    Sub-jaxprs are entered with taint mapped positionally onto their
    invars when the arity matches (``while`` maps const/carry blocks via
    ``cond_nconsts``/``body_nconsts``); on any mismatch the walk falls
    back to tainting the whole sub-program, which can only over-report.
    """
    jaxpr = _inner(jaxpr)
    violations: list[DtypeViolation] = []
    seen: set[tuple[str, str, str, int]] = set()

    def record(eqn, outvar, depth):
        kind = _float_kind(outvar.aval.dtype, floor_bits)
        if kind is None:
            return
        key = (eqn.primitive.name, str(outvar.aval.dtype), kind, depth)
        if key in seen:
            return
        seen.add(key)
        violations.append(DtypeViolation(
            primitive=eqn.primitive.name,
            dtype=str(outvar.aval.dtype),
            kind=kind,
            depth=depth,
        ))

    def run(jx, taint_in: list[bool], depth: int) -> list[bool]:
        tainted: set[int] = set()
        for var, t in zip(jx.invars, taint_in):
            if t:
                tainted.add(id(var))

        def eqn_pass() -> None:
            for eqn in jx.eqns:
                in_taint = [id(v) in tainted for v in eqn.invars]
                if not any(in_taint):
                    continue
                self_descend(eqn, in_taint)
                for outvar in eqn.outvars:
                    tainted.add(id(outvar))
                    record(eqn, outvar, depth)

        def self_descend(eqn, in_taint: list[bool]) -> None:
            prim = eqn.primitive.name
            if prim == 'while':
                cn = eqn.params.get('cond_nconsts', 0)
                bn = eqn.params.get('body_nconsts', 0)
                body = _inner(eqn.params['body_jaxpr'])
                carry = in_taint[cn + bn:]
                body_in = in_taint[cn:cn + bn] + carry
                if len(body_in) == len(body.invars):
                    # one extra pass lets taint flow around the carry
                    out = run(body, body_in, depth + 1)
                    merged = [a or b for a, b in zip(carry, out)]
                    run(body, in_taint[cn:cn + bn] + merged, depth + 1)
                else:
                    run(body, [True] * len(body.invars), depth + 1)
                return
            if prim == 'scan':
                body = _inner(eqn.params['jaxpr'])
                if len(eqn.invars) == len(body.invars):
                    run(body, in_taint, depth + 1)
                else:
                    run(body, [True] * len(body.invars), depth + 1)
                return
            if prim == 'cond':
                ops = in_taint[1:]  # invars[0] is the branch index
                for br in eqn.params.get('branches', ()) or ():
                    inner = _inner(br)
                    if len(ops) == len(inner.invars):
                        run(inner, ops, depth + 1)
                    else:
                        run(inner, [True] * len(inner.invars), depth + 1)
                return
            for sub in subjaxprs(eqn):
                if len(in_taint) == len(sub.invars):
                    run(sub, in_taint, depth + 1)
                else:
                    run(sub, [True] * len(sub.invars), depth + 1)

        eqn_pass()
        return [id(v) in tainted for v in jx.outvars]

    taint = list(tainted_invars)
    if len(taint) != len(jaxpr.invars):
        raise ValueError(
            f'taint mask has {len(taint)} entries for '
            f'{len(jaxpr.invars)} jaxpr invars'
        )
    run(jaxpr, taint, 0)
    return violations


# ------------------------------------------------------------- FLOP counts


def eigh_flops(jaxpr, flops_per_dim3: float = 30.0) -> float:
    """Σ over ``eigh`` eqns of ``flops_per_dim3 · batch · d³`` (per device;
    multiply by world size for the global count)."""
    total = 0.0
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != 'eigh':
            continue
        shape = eqn.invars[0].aval.shape
        batch = int(np.prod(shape[:-2], dtype=np.int64)) if (
            len(shape) > 2
        ) else 1
        total += flops_per_dim3 * batch * shape[-1] ** 3
    return total


def _dot_flops(eqn) -> float:
    """2·M·N·K FLOPs of one ``dot_general`` (batched)."""
    dnums = eqn.params['dimension_numbers']
    (lhs_contract, _), _ = dnums
    lhs = eqn.invars[0].aval.shape
    out = eqn.outvars[0].aval.shape
    k = int(np.prod([lhs[i] for i in lhs_contract], dtype=np.int64))
    return 2.0 * int(np.prod(out, dtype=np.int64)) * k


def while_dot_flops(jaxpr, iters: int) -> float:
    """FLOPs of ``dot_general`` eqns inside ``while`` bodies × ``iters``.

    The jaxpr shows ONE symbolic loop body; the engine's Newton–Schulz
    iteration count is a trace-time constant the caller supplies.
    """
    total = 0.0
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != 'while':
            continue
        body = _inner(eqn.params['body_jaxpr'])
        for sub, _ in iter_eqns(body):
            if sub.primitive.name == 'dot_general':
                total += _dot_flops(sub)
    return total * iters


def pallas_call_summaries(jaxpr) -> list[dict[str, Any]]:
    """One summary dict per ``pallas_call`` eqn in the program.

    ``name`` is the ``pallas_call``'s ``name=`` (the kernels in
    ``kfac_tpu/ops`` pass their function's name), else the kernel
    function's name from the body's debug info,
    ``grid`` the launch grid, and ``dot_flops_per_tile`` the summed
    ``dot_general`` FLOPs of ONE kernel-body invocation. The caller owns
    the grid arithmetic: total MXU FLOPs = Σ over executing grid points
    of the per-tile count (for the triangular cov kernels that is the
    upper-triangle subset, not the full grid product — see the KFL205
    fused parity test).
    """
    out: list[dict[str, Any]] = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != 'pallas_call':
            continue
        grid_mapping = eqn.params.get('grid_mapping')
        inner = eqn.params['jaxpr']
        dot = sum(
            _dot_flops(sub)
            for sub, _ in iter_eqns(inner)
            if sub.primitive.name == 'dot_general'
        )
        out.append({
            'name': eqn.params.get('name') or inner.debug_info.func_name,
            'grid': tuple(getattr(grid_mapping, 'grid', ()) or ()),
            'dot_flops_per_tile': dot,
        })
    return out


# --------------------------------------------------------------- callbacks


def callback_eqns(jaxpr) -> list[str]:
    """Primitive names of every host-callback eqn in the program."""
    out: list[str] = []
    for eqn, _ in iter_eqns(jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMS:
            out.append(eqn.primitive.name)
    return out
