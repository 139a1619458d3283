"""Preemption-safe training: checkpoint autopilot + signal handling.

``CheckpointManager`` owns a keep-N rotation of step-numbered checkpoint
directories with an atomically-updated ``LATEST`` pointer, drives
periodic async saves from the Trainer step paths, flushes an emergency
blocking save when a preemption signal arrives, and restores the newest
*good* checkpoint with last-good fallback and elastic cross-topology
migration. See docs/ROBUSTNESS.md ("Preemption & resume").

``ChaosConductor`` turns all of the above into a measured claim: it
drives a real multi-process gloo pod through scripted or seeded
preemption storms (SIGTERM waves, torn checkpoints, topology
shrink/grow, injected skew) and reconciles per-rank event streams into
recovery SLO rows — downtime steps, recovery wall-clock, restore
fallback depth, zero-divergence vs an uninterrupted control run —
failing loudly when a budget is blown. See docs/ROBUSTNESS.md ("Chaos
harness").
"""

from kfac_tpu.resilience import signals
from kfac_tpu.resilience.chaos import (
    ChaosConductor,
    ChaosConfig,
    ChaosError,
    ChaosReport,
)
from kfac_tpu.resilience.manager import (
    CheckpointManager,
    Preempted,
    RestoreResult,
)

__all__ = [
    'ChaosConductor',
    'ChaosConfig',
    'ChaosError',
    'ChaosReport',
    'CheckpointManager',
    'Preempted',
    'RestoreResult',
    'signals',
]
