"""ray_tpu.train — distributed training over host actors + pjit.

Role analog: ``python/ray/train`` (SURVEY §2.5, §3.4). Public surface
mirrors the reference — ``JaxTrainer`` stands where ``TorchTrainer`` does,
``report``/``get_context``/``get_checkpoint`` match ``ray.train.*`` — but the
data plane is pjit over a device mesh: gradient sync is XLA collectives over
ICI (no process groups), parallelism is declared as a MeshConfig, and
checkpoints save sharded param pytrees host-side.
"""

from ray_tpu.train.checkpoint import (AsyncSave, Checkpoint,
                                      load_pytree, save_pytree,
                                      save_pytree_async)
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    Result,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train.backend import Backend, BackendConfig, JaxConfig
from ray_tpu.train.backend_executor import (
    ElasticWorldSizeError,
    TrainingProtocolError,
    TrainingWorkerError,
    WorkerDeathError,
)
from ray_tpu.train.session import (
    TrainContext,
    get_checkpoint,
    get_context,
    get_dataset_shard,
    report,
)
from ray_tpu.train.trainer import (
    BaseTrainer,
    DataParallelTrainer,
    JaxTrainer,
    TrainingFailedError,
)
from ray_tpu.train.telemetry import StepTelemetry, get_step_telemetry

# The jax-side names load on first use (PEP 562): a driver that only
# builds a JaxTrainer must stay off jax — a process that has touched jax
# holds the chip its workers need.
_LAZY = {
    "merge_microbatches": "ray_tpu.train.pipeline",
    "pipeline_apply": "ray_tpu.train.pipeline",
    "split_microbatches": "ray_tpu.train.pipeline",
    "TrainLoopHelper": "ray_tpu.train.train_state",
    "create_train_state": "ray_tpu.train.train_state",
    "make_train_step": "ray_tpu.train.train_state",
    "state_shardings": "ray_tpu.train.train_state",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        value = getattr(importlib.import_module(_LAZY[name]), name)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "Checkpoint",
    "save_pytree",
    "save_pytree_async",
    "AsyncSave",
    "load_pytree",
    "CheckpointConfig",
    "FailureConfig",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "Backend",
    "BackendConfig",
    "JaxConfig",
    "TrainingWorkerError",
    "TrainingProtocolError",
    "WorkerDeathError",
    "ElasticWorldSizeError",
    "TrainContext",
    "get_checkpoint",
    "get_context",
    "get_dataset_shard",
    "report",
    "BaseTrainer",
    "DataParallelTrainer",
    "JaxTrainer",
    "TrainingFailedError",
    "StepTelemetry",
    "get_step_telemetry",
]
