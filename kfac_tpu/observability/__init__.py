"""Telemetry spine: in-jit metrics, flight recorder, sinks, profiler, comms.

Five small modules, one per concern:

- :mod:`kfac_tpu.observability.metrics` — the in-jit per-layer scalar
  state threaded through both engines and the one-``device_get`` drain.
- :mod:`kfac_tpu.observability.flight_recorder` — fixed-capacity
  on-device ring buffer of the last N steps' scalars + loss + grad norm,
  cross-host skew aggregation at drain time, and the health-triggered
  :class:`PostmortemWriter` bundle sink.
- :mod:`kfac_tpu.observability.sinks` — JSONL writer and rate-limited
  logging adapter for the drained records.
- :mod:`kfac_tpu.observability.profiler` — XLA profiler session helpers
  (``StepTraceAnnotation`` per step, one-call capture).
- :mod:`kfac_tpu.observability.comms` — host-side byte accounting for
  the KAISA transports and size-class padding waste.
- :mod:`kfac_tpu.observability.calibration` — live comparison of
  measured step/spike times (and XLA-reported HBM bytes) against the
  autotune plan's cost model.
- :mod:`kfac_tpu.observability.compile_watch` — recompile attribution
  (per-entry compile events with fingerprint diffs), per-compile XLA
  ``memory_analysis()`` accounting, and crash-safe mid-compile heartbeat
  journaling for the engines' and Trainer's jitted entry points.
- :mod:`kfac_tpu.observability.ledger` — the unified run ledger:
  per-stream adapters normalizing every telemetry stream into one event
  schema keyed by ``(run_id, stream, step, wall_clock)``, a declarative
  correlation engine joining anomalies across streams into causal
  timeline annotations, and the provenance-aware bench perf-regression
  sentinel (``bench_runs/LEDGER.json``).

See docs/OBSERVABILITY.md for the metric-key schema, flight-recorder
sizing guidance, the postmortem bundle layout, and quickstarts.
"""

from kfac_tpu.observability import calibration
from kfac_tpu.observability import comms
from kfac_tpu.observability import compile_watch
from kfac_tpu.observability import flight_recorder
from kfac_tpu.observability import ledger
from kfac_tpu.observability import metrics
from kfac_tpu.observability import profiler
from kfac_tpu.observability import sinks
from kfac_tpu.observability.calibration import (
    CalibrationConfig,
    CalibrationMonitor,
)
from kfac_tpu.observability.comms import comms_summary
from kfac_tpu.observability.compile_watch import (
    CompileWatch,
    CompileWatchConfig,
    PersistentCacheCounters,
    measured_hbm_bytes,
    persistent_cache_counters,
)
from kfac_tpu.observability.flight_recorder import (
    FlightRecorderConfig,
    FlightRecorderState,
    PostmortemWriter,
    drain_flight,
)
from kfac_tpu.observability.ledger import (
    CorrelationRule,
    LedgerConfig,
    RunLedger,
    build_baseline,
    new_run_id,
    render_timeline,
    run_header,
    sentinel_check,
)
from kfac_tpu.observability.metrics import (
    MetricsCollector,
    MetricsConfig,
    MetricsState,
    metric_keys,
)
from kfac_tpu.observability.profiler import (
    capture_steps,
    profile_session,
    step_annotation,
)
from kfac_tpu.observability.sinks import JSONLWriter, RateLimitedLogger

__all__ = [
    'CalibrationConfig',
    'CalibrationMonitor',
    'CompileWatch',
    'CompileWatchConfig',
    'CorrelationRule',
    'FlightRecorderConfig',
    'FlightRecorderState',
    'JSONLWriter',
    'LedgerConfig',
    'MetricsCollector',
    'MetricsConfig',
    'MetricsState',
    'PersistentCacheCounters',
    'PostmortemWriter',
    'RateLimitedLogger',
    'RunLedger',
    'build_baseline',
    'calibration',
    'capture_steps',
    'comms',
    'comms_summary',
    'compile_watch',
    'drain_flight',
    'flight_recorder',
    'ledger',
    'measured_hbm_bytes',
    'metric_keys',
    'metrics',
    'new_run_id',
    'persistent_cache_counters',
    'profile_session',
    'profiler',
    'render_timeline',
    'run_header',
    'sentinel_check',
    'sinks',
    'step_annotation',
]
