"""Measured dispatch thresholds for the Pallas kernels, as a versioned
artifact instead of folklore constants.

Win-regime thresholds hard-coded from one microbench run are dangerous:
a sweep can be dispatch-latency contaminated (flat timings across an 8x
size range — a latency floor, not a measurement), and the thresholds it
justifies then rest on numbers that never touched the work being timed.
This module makes the derivation itself an artifact:

- :func:`latency_floor_verdict` flags a size sweep whose timings are
  flat while the underlying work scales — the signature of measuring
  dispatch latency instead of the op.
- :func:`derive_tables` turns a microbench JSONL sweep into a threshold
  table, refusing to move a threshold off its prior when the evidence is
  floor-contaminated or too thin (fewer than ``min_win_points`` winning
  sizes), and recording *why* in the artifact's provenance.
- :func:`load_tables` / the ``threshold_*`` accessors are what the gate
  modules call at trace time: the committed
  ``kfac_tpu/ops/dispatch_thresholds.json`` when readable, else the
  caller's own prior constant (load-or-default — a missing or mangled
  artifact can never change dispatch behavior, only a committed one).

Stdlib-only on purpose: the gates run inside traces and the derivation
runs in CI; neither may pull in jax.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Iterable, Mapping, Sequence

SCHEMA_VERSION = 1

#: committed derivation artifact the gates load (override via env)
ARTIFACT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), 'dispatch_thresholds.json'
)
ENV_VAR = 'KFAC_TPU_DISPATCH_TABLE'

#: prior thresholds (the constants the gates shipped with) — the
#: derivation's starting point and the load-or-default fallback. The
#: fused step-path families (cov_ema, klclip) start at conservative
#: priors sized off the unfused kernels' win regimes; only a clean sweep
#: moves them (docs/ARCHITECTURE.md "Fused step-path kernels").
DEFAULTS: dict[str, Any] = {
    'attn': {'min_sk_dense': 2048},
    'cov_ema': {'min_dim': 256, 'dtypes': ['float32']},
    'klclip': {'min_dim': 512},
}

#: microbench op-name prefix of each family's BASELINE (unfused) sweep —
#: what :func:`floor_contaminated` scans the artifact provenance for, and
#: what :func:`derive_tables` writes verdicts under
BASELINE_SWEEP_PREFIX: dict[str, str] = {
    'attn': 'attn_einsum',
    'cov_ema': 'cov_ema_unfused',
    'klclip': 'klclip_unfused',
}

#: a kernel must win at this many distinct sweep sizes before the
#: derivation will flip its gate (one anomalous point must not re-open a
#: measured-loss regime)
MIN_WIN_POINTS = 2

_cache: dict[str, dict[str, Any]] = {}


# ------------------------------------------------------------- floor verdict


def latency_floor_verdict(
    sizes: Sequence[float],
    seconds: Sequence[float],
    work_exponent: float = 2.0,
    flat_tol: float = 0.25,
    min_work_ratio: float = 4.0,
) -> dict[str, Any] | None:
    """Flag a size sweep whose timings are flat while the work scales.

    A real op timed across sizes spanning a ``min_work_ratio``-fold work
    range (work ~ size**work_exponent) cannot be flat; measurements
    whose max/min spread stays within ``flat_tol`` over such a range are
    dominated by a fixed per-dispatch latency (host round-trip, queue
    depth), and every number in the sweep is the floor, not the op.

    Returns None when the series is too short or spans too little work
    to judge; otherwise a verdict dict with ``contaminated`` (bool),
    the measured ``spread``, the ``expected_ratio`` of work, and the
    implied ``floor_ms``.
    """
    pts = [
        (float(s), float(t))
        for s, t in zip(sizes, seconds)
        if t is not None and t > 0.0
    ]
    if len(pts) < 2:
        return None
    pts.sort()
    lo_s, hi_s = pts[0][0], pts[-1][0]
    if lo_s <= 0 or hi_s <= lo_s:
        return None
    expected = (hi_s / lo_s) ** work_exponent
    if expected < min_work_ratio:
        return None  # the sweep never leaves the latency-bound regime
    times = [t for _, t in pts]
    spread = max(times) / min(times)
    flat = spread <= 1.0 + flat_tol
    return {
        'contaminated': bool(flat),
        'spread': round(spread, 3),
        'expected_ratio': round(expected, 1),
        'n': len(pts),
        'floor_ms': round(min(times) * 1e3, 3),
    }


# ------------------------------------------------------------------- loading


def _read(path: str) -> dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or doc.get('schema') != SCHEMA_VERSION:
        raise ValueError(
            f'dispatch table {path!r}: schema '
            f'{doc.get("schema") if isinstance(doc, dict) else type(doc)} '
            f'!= {SCHEMA_VERSION}'
        )
    return doc


def load_tables(path: str | None = None) -> dict[str, Any]:
    """The committed threshold tables, or ``{}`` when unavailable.

    Resolution order: explicit ``path`` arg, the :data:`ENV_VAR`
    override, then the committed :data:`ARTIFACT_PATH`. Unreadable or
    schema-mismatched artifacts degrade to ``{}`` — the gates then run
    on their built-in priors, which is always a safe dispatch decision.
    Cached per path (the gates call this at trace time).
    """
    resolved = path or os.environ.get(ENV_VAR) or ARTIFACT_PATH
    if resolved in _cache:
        return _cache[resolved]
    try:
        doc = _read(resolved)
    except (OSError, ValueError):
        doc = {}
    _cache[resolved] = doc
    return doc


def invalidate_cache() -> None:
    """Drop the load cache (tests point :data:`ENV_VAR` at fixtures)."""
    _cache.clear()


def _get(table: Mapping[str, Any], section: str, key: str) -> Any:
    sec = table.get(section)
    if isinstance(sec, Mapping):
        return sec.get(key)
    return None


def flash_min_sk_dense(default: int) -> int:
    """Minimum s_k at which dense-path flash beats XLA's fused
    attention."""
    v = _get(load_tables(), 'attn', 'min_sk_dense')
    return int(v) if isinstance(v, (int, float)) and v > 0 else default


def family_min_dim(family: str, default: int) -> int:
    """Smallest swept dim the named fused family wins at (generic
    accessor for the cov_ema/klclip gates)."""
    v = _get(load_tables(), family, 'min_dim')
    return int(v) if isinstance(v, (int, float)) and v > 0 else default


def family_dtypes(
    family: str, default: Sequence[str] = ('float32',)
) -> tuple[str, ...]:
    """Input dtype names the named fused family wins at."""
    v = _get(load_tables(), family, 'dtypes')
    if isinstance(v, (list, tuple)) and all(isinstance(s, str) for s in v):
        return tuple(v)
    return tuple(default)


def floor_contaminated(family: str) -> str | None:
    """Name of the latency-floor-contaminated sweep backing the family's
    threshold, or None when the backing evidence is clean.

    A threshold whose BASELINE sweep was flagged by
    :func:`latency_floor_verdict` never measured the op — every number in
    it is the dispatch floor — so the gates must not trust it: they hold
    the conservative (XLA) default instead and name the sweep in a
    once-per-family warning (``kfac_tpu.warnings.warn_dispatch_event``).
    Scans the loaded artifact's ``provenance.contaminated`` keys for the
    family's baseline prefix (:data:`BASELINE_SWEEP_PREFIX`).
    """
    prefix = BASELINE_SWEEP_PREFIX.get(family, family)
    prov = load_tables().get('provenance')
    if not isinstance(prov, Mapping):
        return None
    cont = prov.get('contaminated')
    if not isinstance(cont, Mapping):
        return None
    for key in sorted(cont):
        if key == prefix or key.startswith(prefix + '_'):
            verdict = cont[key]
            if isinstance(verdict, Mapping) and not verdict.get(
                'contaminated', True
            ):
                continue
            return key
    return None


# ---------------------------------------------------------------- derivation

_ATTN_RE = re.compile(r'^attn_(einsum|flash)_s(\d+)$')
_FUSED_RE = re.compile(
    r'^(cov_ema|klclip)_(unfused|fused)_(\d+)(?:_f32)?$'
)

#: work ~ size**exponent for each fused family's floor verdict: the
#: cov+EMA contraction is n·d² at fixed rows, the kl-clip
#: contraction+apply is elementwise d²
FUSED_WORK_EXPONENT: dict[str, float] = {
    'cov_ema': 2.0,
    'klclip': 2.0,
}


def _best_ms(ops: Iterable[Mapping[str, Any]]) -> dict[str, float]:
    """op name -> best (min) reported ms across a possibly-concatenated
    set of sweeps."""
    best: dict[str, float] = {}
    for rec in ops:
        name, ms = rec.get('op'), rec.get('ms')
        if not isinstance(name, str) or not isinstance(ms, (int, float)):
            continue
        if name not in best or ms < best[name]:
            best[name] = float(ms)
    return best


def derive_tables(
    ops: Iterable[Mapping[str, Any]],
    prior: Mapping[str, Any] | None = None,
    *,
    flat_tol: float = 0.25,
    min_win_points: int = MIN_WIN_POINTS,
) -> dict[str, Any]:
    """Derive the threshold tables from microbench JSON records.

    ``ops`` is the parsed JSONL a ``tools/tpu_microbench.py`` sweep
    prints (``{'op': ..., 'ms': ...}`` lines; provenance fields ride
    along untouched). The derivation is deliberately conservative:

    - a baseline sweep flagged by :func:`latency_floor_verdict` cannot
      move its threshold (the numbers measure the dispatch, not the op);
    - a dtype/length flips its gate only on ``min_win_points`` distinct
      winning sizes;
    - everything held back is named in ``provenance`` so the artifact is
      self-explaining.
    """
    prior = dict(prior) if prior is not None else json.loads(
        json.dumps(DEFAULTS)
    )
    best = _best_ms(ops)
    provenance: dict[str, Any] = {'held': {}, 'contaminated': {}}

    # --- attn: flash vs einsum per sequence length ----------------------
    attn: dict[str, dict[int, float]] = {}
    for name, ms in best.items():
        m = _ATTN_RE.match(name)
        if m:
            attn.setdefault(m.group(1), {})[int(m.group(2))] = ms
    attn_prior = prior.get('attn', DEFAULTS['attn'])
    min_sk = int(
        attn_prior.get('min_sk_dense', DEFAULTS['attn']['min_sk_dense'])
    )
    both = sorted(set(attn.get('einsum', {})) & set(attn.get('flash', {})))
    wins = [s for s in both if attn['flash'][s] < attn['einsum'][s]]
    if len(wins) >= min_win_points:
        min_sk = min(wins)
        provenance.setdefault('derived', {})['attn/min_sk_dense'] = {
            'win_from_sk': min_sk, 'sizes': both,
        }
    elif both:
        provenance['held']['attn/min_sk_dense'] = (
            f'only {len(wins)} winning length(s) < {min_win_points}; '
            'prior stands'
        )
    # --- fused step-path families: fused vs unfused per size ------------
    fused_series: dict[str, dict[str, dict[int, float]]] = {}
    for name, ms in best.items():
        m = _FUSED_RE.match(name)
        if m:
            fam, impl, d = m.group(1), m.group(2), int(m.group(3))
            fused_series.setdefault(fam, {}).setdefault(impl, {})[d] = ms
    fused_out: dict[str, dict[str, Any]] = {}
    for fam in ('cov_ema', 'klclip'):
        fam_prior = dict(prior.get(fam, DEFAULTS[fam]))
        fam_min = int(fam_prior.get('min_dim', DEFAULTS[fam]['min_dim']))
        impls = fused_series.get(fam, {})
        unfused = impls.get('unfused', {})
        fused = impls.get('fused', {})
        both = sorted(set(unfused) & set(fused))
        verdict = latency_floor_verdict(
            both,
            [unfused[d] * 1e-3 for d in both],
            work_exponent=FUSED_WORK_EXPONENT[fam],
            flat_tol=flat_tol,
        )
        if verdict and verdict['contaminated']:
            provenance['contaminated'][f'{fam}_unfused'] = verdict
            provenance['held'][fam] = (
                'baseline sweep is latency-floor contaminated; threshold '
                'held at prior'
            )
        elif both:
            wins = [d for d in both if fused[d] < unfused[d]]
            if len(wins) < min_win_points:
                provenance['held'][fam] = (
                    f'only {len(wins)} winning size(s) < {min_win_points}; '
                    'prior stands'
                )
            else:
                suffix = None
                for d in sorted(both, reverse=True):
                    if d in wins:
                        suffix = d
                    else:
                        break
                if suffix is None:
                    provenance['held'][fam] = (
                        'wins are not a suffix of the sweep (no clean win '
                        'regime); prior stands'
                    )
                else:
                    fam_min = suffix
                    provenance.setdefault('derived', {})[fam] = {
                        'win_from_dim': suffix, 'sizes': both,
                    }
        fam_prior['min_dim'] = fam_min
        fused_out[fam] = fam_prior
    return {
        'schema': SCHEMA_VERSION,
        'attn': {'min_sk_dense': min_sk},
        **fused_out,
        'provenance': provenance,
    }
