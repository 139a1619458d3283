"""kfac_tpu: TPU-native K-FAC / KAISA second-order preconditioning for JAX.

A from-scratch JAX/XLA framework with the capabilities of the reference
K-FAC implementation surveyed in SURVEY.md: Kronecker-factored curvature
preconditioning (eigen + inverse methods), KAISA-style distributed work
placement over device meshes, hyperparameter schedules, tracing, and
checkpointing — built on pjit/shard_map collectives instead of
torch.distributed.
"""

from kfac_tpu import checkpoint, enums, health, hyperparams, tracing, warnings
from kfac_tpu import autotune
from kfac_tpu import observability
from kfac_tpu import resilience
from kfac_tpu.autotune import TunedPlan
from kfac_tpu.async_inverse import AsyncInverseConfig
from kfac_tpu.resilience import CheckpointManager, Preempted
from kfac_tpu.health import HealthConfig, HealthState
from kfac_tpu.observability import (
    CompileWatch,
    CompileWatchConfig,
    FlightRecorderConfig,
    MetricsCollector,
    MetricsConfig,
    PostmortemWriter,
)
from kfac_tpu.preconditioner import default_compute_method
from kfac_tpu.enums import (
    AllreduceMethod,
    AssignmentStrategy,
    ComputeMethod,
    DistributedStrategy,
)
from kfac_tpu import laplace
from kfac_tpu.laplace import (
    LaplaceConfig,
    LaplacePosterior,
    export_posterior,
    fit_prior_precision,
    load_posterior,
)
from kfac_tpu import serving
from kfac_tpu.serving import ServingConfig, ServingEngine
from kfac_tpu.layers.capture import CapturedStats, CurvatureCapture
from kfac_tpu.layers.registry import (
    Registry,
    masked_registry,
    merge_registries,
    register_model,
)
from kfac_tpu.preconditioner import KFACPreconditioner, KFACState
from kfac_tpu.training import Trainer, TrainState

__version__ = '0.1.0'

__all__ = [
    'AllreduceMethod',
    'AssignmentStrategy',
    'AsyncInverseConfig',
    'CapturedStats',
    'CheckpointManager',
    'ComputeMethod',
    'CurvatureCapture',
    'DistributedStrategy',
    'CompileWatch',
    'CompileWatchConfig',
    'FlightRecorderConfig',
    'HealthConfig',
    'HealthState',
    'KFACPreconditioner',
    'KFACState',
    'LaplaceConfig',
    'LaplacePosterior',
    'MetricsCollector',
    'MetricsConfig',
    'PostmortemWriter',
    'Preempted',
    'Registry',
    'ServingConfig',
    'ServingEngine',
    'TunedPlan',
    'health',
    'resilience',
    'TrainState',
    'Trainer',
    'autotune',
    'checkpoint',
    'default_compute_method',
    'enums',
    'export_posterior',
    'fit_prior_precision',
    'hyperparams',
    'laplace',
    'load_posterior',
    'masked_registry',
    'merge_registries',
    'observability',
    'register_model',
    'serving',
    'tracing',
    'warnings',
]
