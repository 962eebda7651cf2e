"""FL runtime: the composable round pipeline (repro.fl.api + repro.fl.phases),
the sync/async scheduler layer driving it (repro.fl.sched) behind the
single-host simulation entry point (repro.fl.engine), and the cross-silo
distributed runtime over a TPU mesh (repro.fl.cross_silo)."""

from repro.fl.api import (
    CodecConfig,
    ExecutionConfig,
    FaultConfig,
    FLConfig,
    FLModel,
    PersonalizationConfig,
    RoundPipeline,
    SchedulerConfig,
    SelectionConfig,
    TrainConfig,
    build_chunk_step,
    build_round_step,
    pipeline_from_config,
)
from repro.fl.faults import FaultPlan, compile_fault_plan
from repro.fl.engine import FLHistory, make_round_step, run_federated
from repro.fl.sched import AsyncScheduler, SyncScheduler, make_scheduler
from repro.fl.shard import build_sharded_round_step

__all__ = [
    "FLConfig",
    "FLModel",
    "SelectionConfig",
    "PersonalizationConfig",
    "CodecConfig",
    "SchedulerConfig",
    "ExecutionConfig",
    "TrainConfig",
    "FaultConfig",
    "FaultPlan",
    "compile_fault_plan",
    "FLHistory",
    "RoundPipeline",
    "pipeline_from_config",
    "build_round_step",
    "build_chunk_step",
    "build_sharded_round_step",
    "run_federated",
    "make_round_step",
    "SyncScheduler",
    "AsyncScheduler",
    "make_scheduler",
]
