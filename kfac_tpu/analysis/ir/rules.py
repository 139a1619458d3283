"""KFL2xx: IR-tier rules over traced engine entry points.

Each check is a pure function ``Suite -> list[Finding]`` so tests can run
them on synthetic suites; the registered ``kind='ir'`` wrappers bind the
harness' active profile. Findings anchor to the *entry method's*
definition site (the jaxpr has no useful source spans), so an inline
suppression on the ``def`` line works the same way it does for AST rules.

Trace failures are findings, not crashes (mirroring the AST tier's
parse-error handling); they surface once, under KFL201.
"""

from __future__ import annotations

from typing import Callable

from kfac_tpu.analysis import core
from kfac_tpu.analysis.ir import harness, visitor

#: relative tolerance for FLOP parity (bytes are compared exactly — both
#: sides count the same tensors, so any drift is a real model bug)
FLOP_RTOL = 1e-6


def _finding(trace: harness.EngineTrace, code: str, msg: str) -> core.Finding:
    return core.Finding(
        path=trace.path, line=trace.line, code=code,
        message=f'[{trace.display}] {msg}',
    )


# ------------------------------------------------------------------ KFL201


def check_dtype_drift(suite: harness.Suite) -> list[core.Finding]:
    """Factor/inverse math silently demoted below f32 or promoted to f64."""
    findings: list[core.Finding] = []
    for name, entry, msg in suite.errors:
        findings.append(core.Finding(
            path='kfac_tpu/analysis/ir/harness.py', line=1, code='KFL201',
            message=f'[{name}:{entry}] entry point failed to trace: {msg}',
        ))
    for t in suite.traces:
        for v in visitor.dtype_flow(t.jaxpr, t.tainted_invars):
            verb = ('demoted below float32'
                    if v.kind == 'demote' else 'promoted to float64')
            findings.append(_finding(
                t, 'KFL201',
                f'factor-math value {verb}: {v.primitive} produces '
                f'{v.dtype} (jaxpr depth {v.depth}); curvature math must '
                'stay exactly f32 (docs/NUMERICS.md)',
            ))
    return findings


# ------------------------------------------------------------------ KFL202


def check_collective_axes(suite: harness.Suite) -> list[core.Finding]:
    """Collective axis names must exist on the declared KAISA mesh, and
    the stat-transport constraint count must match the chunk plan."""
    from kfac_tpu.parallel import mesh as mesh_lib

    declared = {mesh_lib.GW_AXIS, mesh_lib.COL_AXIS}
    findings: list[core.Finding] = []
    for t in suite.traces:
        mesh_axes = visitor.mesh_axis_names(t.jaxpr) or declared
        for prim, axis in visitor.collective_axis_uses(t.jaxpr):
            if axis not in declared or axis not in mesh_axes:
                findings.append(_finding(
                    t, 'KFL202',
                    f'{prim} references axis {axis!r} which is not a '
                    f'declared mesh axis {sorted(declared)}',
                ))
        if t.entry == 'update_factors' and t.comms is not None:
            st = t.comms['stat_transport']
            chunks = st.get('chunks') or []
            want = len(chunks) if chunks else st['collectives']
            pins = [
                p for p in visitor.constraint_pins(t.jaxpr) if p.replicated
            ]
            if len(pins) != want:
                findings.append(_finding(
                    t, 'KFL202',
                    f'stat transport lowers to {len(pins)} replicated '
                    f'collective pin(s) but the chunk plan declares '
                    f'{want} ({st["method"]}, {len(chunks)} chunk(s))',
                ))
    return findings


# ------------------------------------------------------------------ KFL203


def check_sharding_contract(suite: harness.Suite) -> list[core.Finding]:
    """state_shardings() must match the real state tree and the step
    function must actually lower under the declared shardings."""
    import jax

    findings: list[core.Finding] = []
    for t in suite.traces:
        if t.declared_shardings is None or t.abstract_args is None:
            continue
        state = t.abstract_args[0]
        decl_td = jax.tree_util.tree_structure(t.declared_shardings)
        state_td = jax.tree_util.tree_structure(state)
        if decl_td != state_td:
            decl_keys = {
                jax.tree_util.keystr(p) for p, _ in
                jax.tree_util.tree_flatten_with_path(t.declared_shardings)[0]
            }
            state_keys = {
                jax.tree_util.keystr(p) for p, _ in
                jax.tree_util.tree_flatten_with_path(state)[0]
            }
            missing = sorted(state_keys - decl_keys)[:4]
            extra = sorted(decl_keys - state_keys)[:4]
            findings.append(_finding(
                t, 'KFL203',
                'state_shardings() tree differs from the real state tree '
                f'(undeclared leaves: {missing or "none"}; stale declared '
                f'leaves: {extra or "none"})',
            ))
            continue
        n_args = len(t.abstract_args)
        in_shardings = (t.declared_shardings,) + (None,) * (n_args - 1)
        try:
            jax.jit(
                t.step_fn,
                in_shardings=in_shardings,
                out_shardings=(t.declared_shardings, None),
            ).lower(*t.abstract_args)
        except Exception as exc:  # noqa: BLE001 — any lowering failure is the finding
            findings.append(_finding(
                t, 'KFL203',
                'step does not lower under the declared state_shardings: '
                f'{type(exc).__name__}: {exc}',
            ))
    return findings


# ------------------------------------------------------------------ KFL204


def check_step_callbacks(suite: harness.Suite) -> list[core.Finding]:
    """Host callbacks inside step-path programs must be declared (async
    host refresh, host eigh) — anything else is a per-step host
    round-trip."""
    findings: list[core.Finding] = []
    for t in suite.traces:
        if not t.step_path:
            continue
        for prim in visitor.callback_eqns(t.jaxpr):
            if prim not in t.callback_allowlist:
                findings.append(_finding(
                    t, 'KFL204',
                    f'{prim} in the step program is not on the config '
                    f'allowlist {sorted(t.callback_allowlist) or "[]"}; '
                    'host callbacks on the step path serialize every step '
                    'on a device->host round-trip',
                ))
    return findings


# ------------------------------------------------------------------ KFL205


def _decomp_in_jit(cfg) -> bool:
    """False when the decomposition runs outside the traced program
    (async host refresh / host eigh) — byte/FLOP parity is meaningless
    for those configs and they are skipped, not excused."""
    acfg = getattr(cfg, 'async_inverse', None)
    if acfg is not None:
        return False
    return getattr(cfg, 'eigh_impl', 'xla') not in ('host', 'eig_host')


def _stack_pins(strategy: str, cfg) -> int:
    """How often ``precondition`` pins a gradient stack to replicated.
    Sharded by column: once, the KAISA gradient broadcast. COMM_OPT keeps
    the decompositions replicated (spec == P()): with explicit inverses
    each layer multiplies against its resident slots and no stack exists;
    the eigen methods still stack, and the gstack pin duplicates the
    broadcast pin byte-for-byte — a counting artifact, priced once by the
    model."""
    import kfac_tpu

    if strategy != 'COMM_OPT':
        return 1
    return 2 if cfg.compute_method == kfac_tpu.ComputeMethod.EIGEN else 0


def check_cost_model_parity(suite: harness.Suite) -> list[core.Finding]:
    """Bytes/FLOPs counted from the lowered IR must equal the autotuner
    model's predictions (``StaticLayout``/``comms_report``)."""
    import kfac_tpu

    findings: list[core.Finding] = []
    for t in suite.traces:
        if t.comms is None or t.entry == 'step':
            continue  # dense engine has no transport; step double-counts
        pins = visitor.constraint_pins(t.jaxpr)
        strategy = t.comms['strategy']
        if t.entry == 'update_factors':
            got = visitor.replicated_pin_bytes(pins)
            want = t.comms['stat_transport']['wire_bytes']
            what = 'stat-transport wire bytes'
        elif t.entry == 'update_inverses':
            if not _decomp_in_jit(t.cfg):
                continue
            got = visitor.total_pin_bytes(pins)
            want = t.comms['decomp_reshard_bytes']
            what = 'decomposition reshard bytes'
        elif t.entry == 'precondition':
            got = visitor.rank3_replicated_pin_bytes(pins)
            want = t.comms['grad_broadcast_bytes'] * _stack_pins(
                strategy, t.cfg
            )
            what = 'grad-broadcast bytes'
        else:
            continue
        if got != want:
            findings.append(_finding(
                t, 'KFL205',
                f'{what}: IR counts {got} but the cost model prices '
                f'{want} ({strategy}); autotune/model.py and the engine '
                'have diverged',
            ))
        if t.entry == 'update_inverses' and (
            t.expected_decomp_flops is not None and _decomp_in_jit(t.cfg)
        ):
            if t.cfg.compute_method == kfac_tpu.ComputeMethod.EIGEN:
                got_f = visitor.eigh_flops(t.jaxpr) * t.world
            elif getattr(t.cfg, 'inverse_solver', None) == 'newton_schulz':
                got_f = visitor.while_dot_flops(
                    t.jaxpr, t.cfg.newton_schulz_iters
                ) * t.world
            else:
                continue  # cholesky is priced as NS-equivalent; no IR analog
            want_f = t.expected_decomp_flops
            if abs(got_f - want_f) > FLOP_RTOL * max(abs(want_f), 1.0):
                findings.append(_finding(
                    t, 'KFL205',
                    f'decomposition FLOPs: IR counts {got_f:.6g} but '
                    f'StaticLayout prices {want_f:.6g} '
                    f'(rtol {FLOP_RTOL:g}); the autotuner would mis-rank '
                    'layouts by this ratio',
                ))
    return findings


# ------------------------------------------------------------------ KFL206

#: kernel function names allowed to appear as ``pallas_call`` eqns in
#: traced engine programs — the registry the step-path kernels pin
#: themselves to (kfac_tpu/ops/pallas_{ns,attention}.py).
#: An unlisted kernel on the step path is either a new kernel that
#: skipped its equivalence test and its ``use_*_for`` choice, or a
#: renamed one whose docs and trace readers now point at nothing.
STEP_PALLAS_ALLOWLIST = frozenset({
    '_klclip_dot_kernel',
    '_klclip_scale_kernel',
    '_flash_kernel',
})


def check_pallas_allowlist(suite: harness.Suite) -> list[core.Finding]:
    """Every pallas_call kernel in a traced engine program must be on
    :data:`STEP_PALLAS_ALLOWLIST`."""
    findings: list[core.Finding] = []
    for t in suite.traces:
        for summary in visitor.pallas_call_summaries(t.jaxpr):
            name = summary['name']
            if name not in STEP_PALLAS_ALLOWLIST:
                findings.append(_finding(
                    t, 'KFL206',
                    f'pallas_call kernel {name!r} (grid '
                    f'{summary["grid"]}) is not on the step-path kernel '
                    'allowlist; register it in '
                    'analysis/ir/rules.STEP_PALLAS_ALLOWLIST alongside '
                    'its equivalence test and its use_*_for choice',
                ))
    return findings


# -------------------------------------------------------------- registration


def _bind(fn: Callable[[harness.Suite], list[core.Finding]]):
    def check() -> list[core.Finding]:
        return fn(harness.build())
    return check


core.register(core.Rule(
    code='KFL201', name='ir-dtype-drift',
    what='factor/inverse math whose lowered IR silently demotes below '
         'f32 or promotes to f64, tracked by dataflow through the jaxpr',
    why='a stray bf16 cast in the curvature path is invisible in tests '
        'that only check convergence, and wrecks eigh conditioning',
    check=_bind(check_dtype_drift), kind='ir',
))

core.register(core.Rule(
    code='KFL202', name='ir-collective-axis-mismatch',
    what='collective/shard_map axis names not on the declared KAISA '
         'mesh, and stat-transport pins that disagree with the chunk plan',
    why='a renamed mesh axis or dropped bucket compiles fine single-host '
        'and deadlocks (or silently partial-reduces) on a real slice',
    check=_bind(check_collective_axes), kind='ir',
))

core.register(core.Rule(
    code='KFL203', name='ir-sharding-contract',
    what='state_shardings() trees that drift from the real engine state '
         '(ephemeral trailing fields included) or fail to lower on step',
    why='a state field added without its sharding turns every step into '
        'an implicit all-gather of that field at the jit boundary',
    check=_bind(check_sharding_contract), kind='ir',
))

core.register(core.Rule(
    code='KFL204', name='ir-callback-in-step-path',
    what='io_callback/pure_callback eqns inside step-path programs that '
         'are not on the config\'s async/host-eigh allowlist',
    why='an undeclared host callback serializes every training step on '
        'a device->host round-trip — the exact failure async_inverse '
        'exists to avoid',
    check=_bind(check_step_callbacks), kind='ir',
))

core.register(core.Rule(
    code='KFL205', name='ir-cost-model-parity',
    what='collective bytes and eigh/NS FLOPs counted from the jaxpr '
         'diffed against StaticLayout.predict()/comms_report()',
    why='the layout autotuner is only as good as its pricing; IR parity '
        'turns the cost model from tested-by-convention into verified',
    check=_bind(check_cost_model_parity), kind='ir',
))

core.register(core.Rule(
    code='KFL206', name='ir-pallas-kernel-allowlist',
    what='pallas_call eqns in traced engine programs whose kernel name '
         'is not on the registered step-path allowlist',
    why='a kernel that bypasses the allowlist also bypassed its '
        'equivalence test and its use_*_for choice — the contract that '
        'keeps hand-written Mosaic honest',
    check=_bind(check_pallas_allowlist), kind='ir',
))
