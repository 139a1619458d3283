"""KFL100–KFL114: the migrated docs-vs-code drift linters.

These are ``kind='project'`` rules — unlike the AST rules they import
the live ``kfac_tpu`` modules and compare real objects (metric schemas,
signal tables, plan schemas, scope markers) against the checked-in
documentation. All paths resolve from the repo root derived from this
file, so the rules work regardless of the caller's cwd; the thin
``tools/lint_*`` wrappers keep their historical ``check()`` signatures
on top of these functions.

KFL100 is the self-referential one: it pins the rule table in
``docs/ANALYSIS.md`` to the registry itself, so adding a rule without a
doc row (or vice versa) fails the lint that the doc documents.
"""

from __future__ import annotations

import os
import re

from kfac_tpu.analysis import core

#: repo root: parent of the kfac_tpu package
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))

ANALYSIS_DOC = 'docs/ANALYSIS.md'
OBSERVABILITY_DOC = 'docs/OBSERVABILITY.md'
AUTOTUNE_DOC = 'docs/AUTOTUNE.md'
ROBUSTNESS_DOC = 'docs/ROBUSTNESS.md'
SERVING_DOC = 'docs/SERVING.md'
LAPLACE_DOC = 'docs/LAPLACE.md'

#: documented metric keys that are drain-record fields, not metric_keys
#: entries (KFL102)
EXTRA_DOC_KEYS = frozenset({'step'})

#: jitted entry points that must carry __kfac_scope__ (KFL101);
#: (module, class-or-None, callables) — a None class means module-level
SCOPE_TARGETS: list[tuple[str, str | None, tuple[str, ...]]] = [
    (
        'kfac_tpu.preconditioner',
        'KFACPreconditioner',
        ('step', 'update_factors', 'update_inverses', 'precondition'),
    ),
    (
        'kfac_tpu.parallel.kaisa',
        'DistributedKFAC',
        ('step', 'update_factors', 'update_inverses', 'precondition'),
    ),
    (
        'kfac_tpu.training',
        'Trainer',
        ('step', 'scan_steps', 'step_accumulate', 'step_accumulate_scan'),
    ),
    (
        'kfac_tpu.async_inverse.sliced',
        None,
        ('dense_async_step', 'kaisa_async_step'),
    ),
    (
        'kfac_tpu.async_inverse.host',
        None,
        ('dense_host_step', 'kaisa_host_step', 'pump'),
    ),
]


def _abspath(doc_path: str) -> str:
    if os.path.isabs(doc_path):
        return doc_path
    return os.path.join(REPO_ROOT, doc_path)


def doc_section(
    doc_path: str, section: str, next_heading: str = r'^#{2,3} '
) -> tuple[str, int]:
    """(section body, 1-based line of the heading). Raises ValueError if
    the heading is missing — a renamed section is itself drift."""
    with open(_abspath(doc_path), encoding='utf-8') as f:
        text = f.read()
    try:
        start = text.index(section)
    except ValueError:
        raise ValueError(f'{doc_path} has no {section!r} section')
    line = text[:start].count('\n') + 1
    rest = text[start + len(section):]
    m = re.search(next_heading, rest, re.MULTILINE)
    return (rest[: m.start()] if m else rest), line


def table_first_cells(section: str) -> set[str]:
    """Backticked tokens from the first cell of each table row."""
    keys: set[str] = set()
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith('| `'):
            continue
        keys.update(re.findall(r'`([^`]+)`', line.split('|')[1]))
    return keys


def _doc_findings(
    code: str, doc_path: str, line: int, problems: list[str]
) -> list[core.Finding]:
    return [
        core.Finding(path=doc_path, line=line, code=code, message=p)
        for p in problems
    ]


# --------------------------------------------------------- KFL100 rule table


def check_rule_table(doc_path: str = ANALYSIS_DOC) -> list[str]:
    """Drift between the docs/ANALYSIS.md rule table and the registry."""
    section, _ = doc_section(doc_path, '## Rule table')
    documented: dict[str, str] = {}
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith('| `KFL'):
            continue
        cells = [c.strip() for c in line.split('|')]
        m = re.match(r'`(KFL\d+)`', cells[1])
        if m:
            documented[m.group(1)] = cells[2].strip('` ')
    registered = {r.code: r.name for r in core.all_rules()}
    problems = []
    for code in sorted(set(registered) - set(documented)):
        problems.append(
            f'registered rule has no row in {doc_path}: {code} '
            f'({registered[code]})'
        )
    for code in sorted(set(documented) - set(registered)):
        problems.append(f'documented rule is not registered: {code}')
    for code in sorted(set(documented) & set(registered)):
        if documented[code] != registered[code]:
            problems.append(
                f'{code}: doc table names it {documented[code]!r} but the '
                f'registry says {registered[code]!r}'
            )
    return problems


def _rule_table(**_: object) -> list[core.Finding]:
    try:
        _, line = doc_section(ANALYSIS_DOC, '## Rule table')
        problems = check_rule_table()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL100', ANALYSIS_DOC, 1, [str(exc)])
    return _doc_findings('KFL100', ANALYSIS_DOC, line, problems)


# ------------------------------------------------------- KFL101 named scopes


def _missing_scopes() -> list[tuple[str, str]]:
    """(module name, 'module[.Class].method') per unannotated entry."""
    import importlib
    import inspect

    missing: list[tuple[str, str]] = []
    for mod_name, cls_name, methods in SCOPE_TARGETS:
        mod = importlib.import_module(mod_name)
        holder = mod if cls_name is None else getattr(mod, cls_name)
        for meth in methods:
            # getattr_static avoids triggering descriptors/binding; the
            # decorators stamp the underlying function object.
            fn = inspect.getattr_static(holder, meth)
            fn = getattr(fn, '__func__', fn)
            if not getattr(fn, '__kfac_scope__', None):
                where = (
                    mod_name if cls_name is None
                    else f'{mod_name}.{cls_name}'
                )
                missing.append((mod_name, f'{where}.{meth}'))
    return missing


def check_named_scopes() -> list[str]:
    """'module.Class.method' for every entry point missing a scope."""
    return [name for _, name in _missing_scopes()]


def _named_scopes() -> list[core.Finding]:
    return [
        core.Finding(
            path=mod_name.replace('.', '/') + '.py',
            line=1, code='KFL101',
            message=f'jitted entry point missing tracing.trace/scope '
                    f'annotation: {name}',
        )
        for mod_name, name in _missing_scopes()
    ]


# -------------------------------------------------------- KFL102 metric keys


def check_metric_keys(doc_path: str = OBSERVABILITY_DOC) -> list[str]:
    section, _ = doc_section(doc_path, '### Metric-key schema')
    documented = table_first_cells(section)
    from kfac_tpu import health
    from kfac_tpu.observability import metrics as metrics_lib

    names = ['<layer>']
    actual = set(metrics_lib.metric_keys(metrics_lib.MetricsConfig(), names))
    actual |= set(health.health_metric_keys(names))
    actual |= EXTRA_DOC_KEYS
    problems = []
    for k in sorted(actual - documented):
        problems.append(f'undocumented key (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(f'documented key not produced by the code: {k}')
    return problems


def _metric_keys() -> list[core.Finding]:
    _, line = doc_section(OBSERVABILITY_DOC, '### Metric-key schema')
    return _doc_findings(
        'KFL102', OBSERVABILITY_DOC, line, check_metric_keys()
    )


# -------------------------------------------------------- KFL103 plan schema


def check_plan_schema(doc_path: str = AUTOTUNE_DOC) -> list[str]:
    section, _ = doc_section(doc_path, '### Plan schema')
    documented = table_first_cells(section)
    from kfac_tpu.autotune import plan as plan_lib

    produced = set(plan_lib.plan_schema_keys())
    problems = []
    for k in sorted(produced - documented):
        problems.append(f'undocumented plan field (add to {doc_path}): {k}')
    for k in sorted(documented - produced):
        problems.append(f'documented field not in the plan schema: {k}')
    return problems


def _plan_schema() -> list[core.Finding]:
    _, line = doc_section(AUTOTUNE_DOC, '### Plan schema')
    return _doc_findings('KFL103', AUTOTUNE_DOC, line, check_plan_schema())


# ------------------------------------------------------------ KFL104 signals


def doc_signals(doc_path: str = ROBUSTNESS_DOC) -> dict[str, bool]:
    """{signal name: exits} parsed from the section's table rows."""
    section, _ = doc_section(
        doc_path, '## Signal semantics', next_heading=r'^#{1,3} '
    )
    out: dict[str, bool] = {}
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith('| `'):
            continue
        cells = line.split('|')
        names = re.findall(r'`(SIG[A-Z0-9]+)`', cells[1])
        if not names:
            continue
        semantics = cells[2].lower()
        exits = 'exit' in semantics
        if not exits and 'continue' not in semantics:
            raise ValueError(
                f'{doc_path}: signal-table row for {names} states '
                f'neither "exit" nor "continue": {cells[2].strip()!r}'
            )
        for name in names:
            out[name] = exits
    return out


def check_signals(doc_path: str = ROBUSTNESS_DOC) -> list[str]:
    documented = doc_signals(doc_path)
    from kfac_tpu.resilience import signals

    actual = {
        name: spec.exits for name, spec in signals.HANDLED_SIGNALS.items()
    }
    problems = []
    for name in sorted(set(actual) - set(documented)):
        problems.append(
            f'handled signal not documented (add to {doc_path}): {name}'
        )
    for name in sorted(set(documented) - set(actual)):
        problems.append(
            f'documented signal has no handler in signals.py: {name}'
        )
    for name in sorted(set(actual) & set(documented)):
        if actual[name] != documented[name]:
            problems.append(
                f'{name}: docs say '
                f'{"exit" if documented[name] else "continue"} but '
                f'HANDLED_SIGNALS.exits={actual[name]}'
            )
    return problems


def _signals() -> list[core.Finding]:
    _, line = doc_section(
        ROBUSTNESS_DOC, '## Signal semantics', next_heading=r'^#{1,3} '
    )
    return _doc_findings('KFL104', ROBUSTNESS_DOC, line, check_signals())


# ---------------------------------------------------- KFL107 laplace knobs


def check_laplace_knobs(doc_path: str = LAPLACE_DOC) -> list[str]:
    """Drift between docs/LAPLACE.md and the Laplace serving surface:
    the knob table vs the ``LaplaceConfig`` dataclass fields, and the
    posterior-schema table vs ``posterior_schema_keys()`` (the keys
    POSTERIOR.json actually persists)."""
    import dataclasses

    from kfac_tpu.laplace import config as laplace_config_lib
    from kfac_tpu.laplace import export as laplace_export_lib

    problems = []
    section, _ = doc_section(doc_path, '### LaplaceConfig knobs')
    documented = table_first_cells(section)
    actual = {
        f.name for f in dataclasses.fields(laplace_config_lib.LaplaceConfig)
    }
    for k in sorted(actual - documented):
        problems.append(f'undocumented config field (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(f'documented knob is not a LaplaceConfig field: {k}')

    section, _ = doc_section(doc_path, '### Posterior schema')
    documented = table_first_cells(section)
    produced = set(laplace_export_lib.posterior_schema_keys())
    for k in sorted(produced - documented):
        problems.append(
            f'undocumented posterior field (add to {doc_path}): {k}'
        )
    for k in sorted(documented - produced):
        problems.append(f'documented field not in the posterior schema: {k}')
    return problems


def _laplace_knobs() -> list[core.Finding]:
    try:
        _, line = doc_section(LAPLACE_DOC, '### LaplaceConfig knobs')
        problems = check_laplace_knobs()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL107', LAPLACE_DOC, 1, [str(exc)])
    return _doc_findings('KFL107', LAPLACE_DOC, line, problems)


# ------------------------------------------------ KFL108 calibration knobs


def check_calibration_knobs(doc_path: str = OBSERVABILITY_DOC) -> list[str]:
    """Drift between the docs/OBSERVABILITY.md "Calibration knobs" table
    and the ``CalibrationConfig`` dataclass fields — the knobs of the
    cost-model calibration monitor."""
    import dataclasses

    section, _ = doc_section(doc_path, '### Calibration knobs')
    documented = table_first_cells(section)
    from kfac_tpu.observability import calibration as calibration_lib

    actual = {
        f.name
        for f in dataclasses.fields(calibration_lib.CalibrationConfig)
    }
    problems = []
    for k in sorted(actual - documented):
        problems.append(f'undocumented config field (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(
            f'documented knob is not a CalibrationConfig field: {k}')
    return problems


def _calibration_knobs() -> list[core.Finding]:
    try:
        _, line = doc_section(OBSERVABILITY_DOC, '### Calibration knobs')
        problems = check_calibration_knobs()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL108', OBSERVABILITY_DOC, 1, [str(exc)])
    return _doc_findings('KFL108', OBSERVABILITY_DOC, line, problems)


# --------------------------------------------------- KFL109 topology knobs


def check_topology_knobs(doc_path: str = AUTOTUNE_DOC) -> list[str]:
    """Drift between the docs/AUTOTUNE.md "Topology knobs" table and the
    ``TopologyConfig`` dataclass fields — the grid bounds of the 3D
    DP×TP×PP planner."""
    import dataclasses

    section, _ = doc_section(doc_path, '### Topology knobs')
    documented = table_first_cells(section)
    from kfac_tpu.planner import topology as topology_lib

    actual = {
        f.name for f in dataclasses.fields(topology_lib.TopologyConfig)
    }
    problems = []
    for k in sorted(actual - documented):
        problems.append(f'undocumented config field (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(
            f'documented knob is not a TopologyConfig field: {k}')
    return problems


def _topology_knobs() -> list[core.Finding]:
    try:
        _, line = doc_section(AUTOTUNE_DOC, '### Topology knobs')
        problems = check_topology_knobs()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL109', AUTOTUNE_DOC, 1, [str(exc)])
    return _doc_findings('KFL109', AUTOTUNE_DOC, line, problems)


# ------------------------------------------------------ KFL111 chaos knobs


def check_chaos_knobs(doc_path: str = ROBUSTNESS_DOC) -> list[str]:
    """Drift between the docs/ROBUSTNESS.md chaos knob table and the
    ``ChaosConfig`` dataclass fields — the storm-shape and SLO-budget
    knobs the chaos conductor actually accepts."""
    import dataclasses

    section, _ = doc_section(doc_path, '### Chaos knobs')
    documented = table_first_cells(section)
    from kfac_tpu.resilience import chaos as chaos_lib

    actual = {f.name for f in dataclasses.fields(chaos_lib.ChaosConfig)}
    problems = []
    for k in sorted(actual - documented):
        problems.append(f'undocumented config field (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(f'documented knob is not a ChaosConfig field: {k}')
    return problems


def _chaos_knobs() -> list[core.Finding]:
    try:
        _, line = doc_section(ROBUSTNESS_DOC, '### Chaos knobs')
        problems = check_chaos_knobs()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL111', ROBUSTNESS_DOC, 1, [str(exc)])
    return _doc_findings('KFL111', ROBUSTNESS_DOC, line, problems)


# ----------------------------------------------- KFL112 compile-watch knobs


def check_compile_watch_knobs(doc_path: str = OBSERVABILITY_DOC) -> list[str]:
    """Drift between the docs/OBSERVABILITY.md "Compile-watch knobs"
    table and the ``CompileWatchConfig`` dataclass fields — the knobs of
    the recompile-attribution / XLA-memory / mid-compile-heartbeat
    watch."""
    import dataclasses

    section, _ = doc_section(doc_path, '### Compile-watch knobs')
    documented = table_first_cells(section)
    from kfac_tpu.observability import compile_watch as compile_watch_lib

    actual = {
        f.name
        for f in dataclasses.fields(compile_watch_lib.CompileWatchConfig)
    }
    problems = []
    for k in sorted(actual - documented):
        problems.append(f'undocumented config field (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(
            f'documented knob is not a CompileWatchConfig field: {k}')
    return problems


def _compile_watch_knobs() -> list[core.Finding]:
    try:
        _, line = doc_section(OBSERVABILITY_DOC, '### Compile-watch knobs')
        problems = check_compile_watch_knobs()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL112', OBSERVABILITY_DOC, 1, [str(exc)])
    return _doc_findings('KFL112', OBSERVABILITY_DOC, line, problems)


# --------------------------------------------------- KFL113 run-ledger tables


def check_ledger_tables(doc_path: str = OBSERVABILITY_DOC) -> list[str]:
    """Drift between the docs/OBSERVABILITY.md "Run ledger" chapter and
    the ledger module: the "Ledger knobs" table vs the ``LedgerConfig``
    dataclass fields, the "Stream adapters" matrix vs the ``ADAPTERS``
    registry, the "Correlation rules" table vs ``DEFAULT_RULES``, and
    the "Sentinel tolerances" table vs ``DEFAULT_SENTINEL_KEYS``."""
    import dataclasses

    from kfac_tpu.observability import ledger as ledger_lib

    pinned: list[tuple[str, set[str], str]] = [
        ('### Ledger knobs',
         {f.name for f in dataclasses.fields(ledger_lib.LedgerConfig)},
         'LedgerConfig field'),
        ('### Stream adapters',
         set(ledger_lib.ADAPTERS),
         'ADAPTERS stream'),
        ('### Correlation rules',
         {r.name for r in ledger_lib.DEFAULT_RULES},
         'DEFAULT_RULES rule'),
        ('### Sentinel tolerances',
         set(ledger_lib.DEFAULT_SENTINEL_KEYS),
         'DEFAULT_SENTINEL_KEYS key'),
    ]
    problems = []
    for heading, actual, what in pinned:
        section, _ = doc_section(doc_path, heading)
        documented = table_first_cells(section)
        for k in sorted(actual - documented):
            problems.append(
                f'undocumented {what} (add to {doc_path} "{heading}"): {k}')
        for k in sorted(documented - actual):
            problems.append(
                f'documented entry in "{heading}" is not a {what}: {k}')
    return problems


def _ledger_tables() -> list[core.Finding]:
    try:
        _, line = doc_section(OBSERVABILITY_DOC, '## Run ledger')
        problems = check_ledger_tables()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL113', OBSERVABILITY_DOC, 1, [str(exc)])
    return _doc_findings('KFL113', OBSERVABILITY_DOC, line, problems)


# ------------------------------------------------- KFL114 serving-tier knobs


def check_serving_knobs(doc_path: str = SERVING_DOC) -> list[str]:
    """Drift between the docs/SERVING.md "Serving knobs" table and the
    ``ServingConfig`` dataclass fields — the bucketing, sampling,
    escalation and metrics knobs the posterior serving engine accepts."""
    import dataclasses

    section, _ = doc_section(doc_path, '### Serving knobs')
    documented = table_first_cells(section)
    from kfac_tpu.serving import config as serving_config_lib

    actual = {
        f.name
        for f in dataclasses.fields(serving_config_lib.ServingConfig)
    }
    problems = []
    for k in sorted(actual - documented):
        problems.append(f'undocumented config field (add to {doc_path}): {k}')
    for k in sorted(documented - actual):
        problems.append(
            f'documented knob is not a ServingConfig field: {k}')
    return problems


def _serving_knobs() -> list[core.Finding]:
    try:
        _, line = doc_section(SERVING_DOC, '### Serving knobs')
        problems = check_serving_knobs()
    except (OSError, ValueError) as exc:
        return _doc_findings('KFL114', SERVING_DOC, 1, [str(exc)])
    return _doc_findings('KFL114', SERVING_DOC, line, problems)


# --------------------------------------------------------------- registration


core.register(core.Rule(
    code='KFL100',
    name='doc-rule-table',
    what='drift between the docs/ANALYSIS.md rule table and the live '
         'rule registry (missing rows, stale rows, renamed rules)',
    why='a rule that is not in the table is invisible to the people it '
        'is supposed to teach; this is the same doc-vs-code contract the '
        'repo already enforces for metrics, plans and signals',
    check=_rule_table,
    kind='project',
))

core.register(core.Rule(
    code='KFL101',
    name='named-scopes',
    what='jitted engine entry points (step/update_factors/'
         'update_inverses/precondition/async pumps) missing the '
         '`__kfac_scope__` stamp from tracing.trace/tracing.scope',
    why='XLA profiler attribution of device time to K-FAC phases '
        '(docs/OBSERVABILITY.md) dies silently when a refactor drops a '
        'named scope',
    check=_named_scopes,
    kind='project',
))

core.register(core.Rule(
    code='KFL102',
    name='metric-keys-doc',
    what='drift between the docs/OBSERVABILITY.md metric-key tables and '
         '`metric_keys()` + `health_metric_keys()`',
    why='dashboards and kfac_inspect key off the drained-record schema; '
        'an undocumented key is an unmonitorable one',
    check=_metric_keys,
    kind='project',
))

core.register(core.Rule(
    code='KFL103',
    name='plan-schema-doc',
    what='drift between the docs/AUTOTUNE.md plan-schema table and '
         '`plan_schema_keys()`',
    why='tuned plans are persisted JSON read across sessions; schema '
        'drift bricks saved plans without an error message',
    check=_plan_schema,
    kind='project',
))

core.register(core.Rule(
    code='KFL104',
    name='signal-semantics-doc',
    what='drift between the docs/ROBUSTNESS.md signal table and '
         '`resilience.signals.HANDLED_SIGNALS` (including exit-vs-'
         'continue semantics)',
    why='cluster launch scripts send SIGTERM/SIGUSR1 expecting exactly '
        'the documented behavior; a flipped exits flag strands jobs',
    check=_signals,
    kind='project',
))

core.register(core.Rule(
    code='KFL107',
    name='laplace-knobs-doc',
    what='drift between the docs/LAPLACE.md "LaplaceConfig knobs" / '
         '"Posterior schema" tables and the LaplaceConfig dataclass '
         'fields / posterior_schema_keys()',
    why='exported posteriors are persisted, versioned JSON served across '
        'sessions, and the knobs change the served uncertainty; schema '
        'drift bricks saved posteriors and an undocumented knob mis-'
        'calibrates them by folklore',
    check=_laplace_knobs,
    kind='project',
))

core.register(core.Rule(
    code='KFL108',
    name='calibration-knobs-doc',
    what='drift between the docs/OBSERVABILITY.md "Calibration knobs" '
         'table and the CalibrationConfig dataclass fields',
    why='the calibration monitor says how far the tuned plan\'s cost '
        'model is off; an undocumented (or phantom) knob means the window '
        'that verdict is averaged over is configured by folklore',
    check=_calibration_knobs,
    kind='project',
))

core.register(core.Rule(
    code='KFL111',
    name='chaos-knobs-doc',
    what='drift between the docs/ROBUSTNESS.md "Chaos knobs" table and '
         'the resilience.chaos ChaosConfig dataclass fields',
    why='the chaos harness is the only measured evidence that the '
        'preemption/restore stack meets its recovery SLOs; an '
        'undocumented (or phantom) storm knob means the committed SLO '
        'artifact was produced by a configuration nobody can reproduce',
    check=_chaos_knobs,
    kind='project',
))

core.register(core.Rule(
    code='KFL112',
    name='compile-watch-knobs-doc',
    what='drift between the docs/OBSERVABILITY.md "Compile-watch knobs" '
         'table and the CompileWatchConfig dataclass fields',
    why='the compile watch is the truth layer for recompiles and XLA '
        'memory, and its heartbeat journal is what a mid-compile crash '
        'postmortem reads; an undocumented (or phantom) knob means the '
        'crash-safety and fault-injection behavior is configured by '
        'folklore',
    check=_compile_watch_knobs,
    kind='project',
))

core.register(core.Rule(
    code='KFL113',
    name='run-ledger-doc',
    what='drift between the docs/OBSERVABILITY.md "Run ledger" chapter '
         '(knob / stream-adapter / correlation-rule / sentinel-tolerance '
         'tables) and the ledger module (LedgerConfig, ADAPTERS, '
         'DEFAULT_RULES, DEFAULT_SENTINEL_KEYS)',
    why='the ledger is the cross-stream triage entry point and the bench '
        'regression gate; an undocumented adapter or rule means operators '
        'triage against tables that lie, and a phantom sentinel key means '
        'CI enforces a tolerance nobody can look up',
    check=_ledger_tables,
    kind='project',
))

core.register(core.Rule(
    code='KFL114',
    name='serving-knobs-doc',
    what='drift between the docs/SERVING.md "Serving knobs" table and '
         'the serving.ServingConfig dataclass fields',
    why='the serving engine is the uncertainty-inference front door over '
        'the Laplace export, and its bucket/escalation knobs decide both '
        'compile count and answer quality; an undocumented (or phantom) '
        'knob means production routing behavior is configured by '
        'folklore',
    check=_serving_knobs,
    kind='project',
))

core.register(core.Rule(
    code='KFL109',
    name='topology-knobs-doc',
    what='drift between the docs/AUTOTUNE.md "Topology knobs" table and '
         'the planner TopologyConfig dataclass fields',
    why='the 3D planner\'s grid bounds decide which DP×TP×PP meshes a '
        'pod will even consider; an undocumented (or phantom) knob means '
        'the mesh factorization of a training run is chosen by folklore',
    check=_topology_knobs,
    kind='project',
))
