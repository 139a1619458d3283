"""Live cost-model calibration: measured step times vs the tuned plan.

The autotune layer (:mod:`kfac_tpu.autotune`) picks a layout by an
analytic cost model — ``predicted_step_s`` for steady-state steps and
``refresh_spike_s`` for the inverse-refresh overshoot. Those predictions
are only as good as the hardware constants behind them, and nothing in
the running job checked them: a 2x-wrong model silently ships a 2x-wrong
layout until the next offline retune.

:class:`CalibrationMonitor` closes that loop. Feed it the wall-clock of
each optimizer step (and, when you can see them, refresh-spike steps,
plus XLA-reported HBM bytes via :meth:`CalibrationMonitor.observe_memory`
/ :meth:`CalibrationMonitor.observe_memory_report` — the compile-watch
bridge, see docs/OBSERVABILITY.md "Compile & memory truth");
it maintains rolling residual ratios ``measured / predicted``, exposes
them as ``calib/*`` metric keys for the JSONL / rate-limited-logger
sinks, and folds a headline ``calib/model_error`` into drained
flight-recorder records. Purely host-side: nothing new is jitted, no
recompilation (the no-recompile test pins this).

See docs/OBSERVABILITY.md "Measurement truth" for the knob table
(linted by KFL108) and a worked quickstart.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Iterable, Sequence


@dataclasses.dataclass(frozen=True)
class CalibrationConfig:
    """Knobs of the cost-model calibration monitor.

    The field set here is pinned to the knob table in
    docs/OBSERVABILITY.md "Calibration knobs" by lint rule KFL108.

    Args:
        window: rolling window (in observations) over which step and
            spike residual ratios are averaged. Small windows react
            faster; large windows reject step-time noise.
        warmup_steps: leading ``observe_step`` calls to discard —
            compile and autotune warmup steps are not model residuals.
        prefix: metric-key namespace for emitted keys
            (``<prefix>/step_ratio`` etc.). Change it only if ``calib/``
            collides with a user metric.
    """

    window: int = 32
    warmup_steps: int = 3
    prefix: str = 'calib'

    def __post_init__(self) -> None:
        if self.window < 1:
            raise ValueError(f'window must be >= 1, got {self.window}')
        if self.warmup_steps < 0:
            raise ValueError(
                f'warmup_steps must be >= 0, got {self.warmup_steps}')


def _winner_row(plan: Any) -> dict[str, Any]:
    """The cost-table row the plan's knobs came from (the winner's full
    prediction record, including ``refresh_spike_s``)."""
    knobs = getattr(plan, 'knobs', None)
    for row in getattr(plan, 'cost_table', None) or []:
        if isinstance(row, dict) and row.get('knobs') == knobs:
            return row
    return {}


class CalibrationMonitor:
    """Rolling comparison of measured step/phase times against a tuned
    plan's cost-model predictions.

    Residuals are tracked as ratios ``measured / predicted`` (1.0 =
    perfect model). ``step_ratio()``/``spike_ratio()`` are rolling means
    over the config window; ``model_error()`` is the fold error
    ``max(r, 1/r)`` of the step ratio — direction-free, so a model
    that's 2x optimistic and one that's 2x pessimistic both read 2.0.
    """

    def __init__(
        self,
        predicted_step_s: float,
        refresh_spike_s: float | None = None,
        config: CalibrationConfig | None = None,
        predicted_mem_bytes: float | None = None,
    ) -> None:
        if not (predicted_step_s > 0.0):
            raise ValueError(
                f'predicted_step_s must be > 0, got {predicted_step_s}')
        if refresh_spike_s is not None and refresh_spike_s <= 0.0:
            # a plan with no spike prediction (sync refresh folded into
            # the step) just disables the spike channel
            refresh_spike_s = None
        if predicted_mem_bytes is not None and predicted_mem_bytes <= 0.0:
            # a plan with no memory prediction disables the memory channel
            predicted_mem_bytes = None
        self.config = config or CalibrationConfig()
        self.predicted_step_s = float(predicted_step_s)
        self.refresh_spike_s = (
            None if refresh_spike_s is None else float(refresh_spike_s))
        self.predicted_mem_bytes = (
            None if predicted_mem_bytes is None else float(predicted_mem_bytes))
        self._steps: collections.deque[float] = collections.deque(
            maxlen=self.config.window)
        self._spikes: collections.deque[float] = collections.deque(
            maxlen=self.config.window)
        self._mems: collections.deque[float] = collections.deque(
            maxlen=self.config.window)
        self._seen = 0
        self._skipped = 0

    @classmethod
    def from_plan(
        cls, plan: Any, config: CalibrationConfig | None = None
    ) -> 'CalibrationMonitor':
        """Build from a ``TunedPlan`` (or plan dict / path — anything
        :func:`kfac_tpu.autotune.plan.as_plan` coerces)."""
        from kfac_tpu.autotune import plan as plan_lib

        p = plan_lib.as_plan(plan)
        predicted = float((p.winner or {}).get('predicted_step_s', 0.0))
        row = _winner_row(p)
        spike = row.get('refresh_spike_s')
        mem = row.get('memory_per_device_bytes') or {}
        mem_total = mem.get('total') if isinstance(mem, dict) else None
        return cls(
            predicted_step_s=predicted,
            refresh_spike_s=None if spike is None else float(spike),
            predicted_mem_bytes=(
                None if mem_total is None else float(mem_total)),
            config=config,
        )

    # --------------------------------------------------------- observation

    def observe_step(self, seconds: float) -> float | None:
        """Record one optimizer step's wall-clock; returns the residual
        ratio, or None while warming up / for non-finite input."""
        if self._skipped < self.config.warmup_steps:
            self._skipped += 1
            return None
        seconds = float(seconds)
        if not math.isfinite(seconds) or seconds <= 0.0:
            return None
        ratio = seconds / self.predicted_step_s
        self._steps.append(ratio)
        self._seen += 1
        return ratio

    def observe_spike(self, seconds: float) -> float | None:
        """Record one refresh-spike overshoot (the wall-clock EXCESS of a
        refresh step over a steady step); None when the plan predicted
        no spike."""
        if self.refresh_spike_s is None:
            return None
        seconds = float(seconds)
        if not math.isfinite(seconds) or seconds <= 0.0:
            return None
        ratio = seconds / self.refresh_spike_s
        self._spikes.append(ratio)
        return ratio

    def observe_memory(self, measured_bytes: float) -> float | None:
        """Record an XLA-reported per-device HBM measurement (e.g. the
        argument+output+temp bytes of the compiled step — see
        :func:`kfac_tpu.observability.compile_watch.measured_hbm_bytes`)
        against the plan's ``memory_per_device_bytes['total']``
        prediction; None when the plan predicted no memory. No warmup:
        the XLA report is deterministic per compile, not a noisy
        wall-clock."""
        if self.predicted_mem_bytes is None:
            return None
        measured_bytes = float(measured_bytes)
        if not math.isfinite(measured_bytes) or measured_bytes <= 0.0:
            return None
        ratio = measured_bytes / self.predicted_mem_bytes
        self._mems.append(ratio)
        return ratio

    def observe_memory_report(
        self, report: dict[str, Any], entries: Sequence[str] | None = None
    ) -> float | None:
        """Feed an ``engine.compiled_memory_report()`` straight into the
        memory channel: sums ``hbm_bytes`` over the report's entries
        (optionally restricted to ``entries``) and observes the total.
        A report with no backend memory stats is a no-op, not an error."""
        total = 0.0
        for name, snap in (report or {}).items():
            if entries is not None and name not in entries:
                continue
            bytes_ = (snap or {}).get('hbm_bytes')
            if bytes_:
                total += float(bytes_)
        if total <= 0.0:
            return None
        return self.observe_memory(total)

    # ----------------------------------------------------------- residuals

    @staticmethod
    def _mean(xs: Iterable[float]) -> float | None:
        xs = list(xs)
        return sum(xs) / len(xs) if xs else None

    def step_ratio(self) -> float | None:
        """Rolling mean ``measured_step / predicted_step`` (None until
        the first post-warmup observation)."""
        return self._mean(self._steps)

    def spike_ratio(self) -> float | None:
        return self._mean(self._spikes)

    def mem_ratio(self) -> float | None:
        """Rolling mean ``measured_hbm / predicted_hbm`` (None until the
        first memory observation)."""
        return self._mean(self._mems)

    @staticmethod
    def _fold(ratio: float | None) -> float:
        if ratio is None or ratio <= 0.0:
            return 1.0
        return max(ratio, 1.0 / ratio)

    def model_error(self) -> float:
        """Direction-free fold error of the cost model: the worst of the
        step-time and memory folds ``max(r, 1/r)``; 1.0 with no evidence
        yet, so an idle monitor never looks drifted. A 2x-wrong memory
        model therefore reads exactly like a 2x-wrong time model."""
        return max(self._fold(self.step_ratio()), self._fold(self.mem_ratio()))

    # ------------------------------------------------------------ emission

    def record(self) -> dict[str, float]:
        """Current residuals as a flat metrics record for the sinks
        (:class:`~kfac_tpu.observability.sinks.JSONLWriter` /
        ``RateLimitedLogger``). Empty until the first post-warmup
        observation (step-time or memory — a compile-watch-only monitor
        still emits its HBM residual), so
        ``writer.write(monitor.record())`` is a safe unconditional
        call."""
        r = self.step_ratio()
        m = self.mem_ratio()
        if r is None and m is None:
            return {}
        p = self.config.prefix
        rec = {
            f'{p}/model_error': self.model_error(),
            f'{p}/n': float(self._seen),
        }
        if r is not None:
            rec[f'{p}/predicted_step_s'] = self.predicted_step_s
            rec[f'{p}/measured_step_s'] = r * self.predicted_step_s
            rec[f'{p}/step_ratio'] = r
        s = self.spike_ratio()
        if s is not None and self.refresh_spike_s is not None:
            rec[f'{p}/predicted_spike_s'] = self.refresh_spike_s
            rec[f'{p}/spike_ratio'] = s
        if m is not None and self.predicted_mem_bytes is not None:
            rec[f'{p}/predicted_mem_bytes'] = self.predicted_mem_bytes
            rec[f'{p}/measured_mem_bytes'] = m * self.predicted_mem_bytes
            rec[f'{p}/mem_ratio'] = m
        return rec

    def annotate(self, record: dict[str, Any]) -> dict[str, Any]:
        """Fold the ``calib/*`` keys into a drained record in place (and
        return it) — the flight-recorder headline path."""
        record.update(self.record())
        return record
