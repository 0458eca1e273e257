"""Core: TPU-native MetaSchedule — probabilistic tensor-program tuning.

Public API:
    Workload, Schedule, HardwareConfig / V5E, tune(), TuningDatabase,
    DeviceRunner (the chip) / InterpretRunner / AnalyticRunner,
    best_schedule()/kernel_params().
"""

from repro.core.hardware import (HardwareConfig, V5E, V5E_VMEM32, V5E_VMEM64,
                                 V5E_MXU256, INTERPRET, SWEEP)
from repro.core.workload import (Workload, matmul, qmatmul, gemv, vmacc,
                                 attention)
from repro.core.schedule import Schedule, Decision
from repro.core.space import (space_for, concretize, concretize_cache_stats,
                              clear_concretize_cache, DecisionDistribution,
                              KernelParams, SpaceProgram, flat_space_v1,
                              tile_candidates, v1_distinct_configs)
from repro.core.build_cache import (BuildCache, build_cache_stats,
                                    clear_build_cache, global_build_cache)
from repro.core.sampler import TraceSampler
from repro.core.static_analysis import (Diagnostic, SpaceReport, analyze,
                                        lint_space, pruned_program)
from repro.core.cost_model import (RidgeCostModel, features,
                                   pretrain_from_database)
from repro.core.runner import (AnalyticRunner, DeviceRunner,
                               InterpretRunner, run_batch, xla_latency)
from repro.core.measure_scheduler import (AdaptiveDepthPolicy,
                                          MeasureScheduler, MeasureTicket,
                                          SerialMeasureQueue)
from repro.core.database import (TuningDatabase, default_db_path,
                                 global_database, reset_global_database)
from repro.core.tuner import tune, TuneDriver, TuneResult
from repro.core.session import (BudgetLedger, EntropyStopPolicy,
                                TuningSession, SessionResult, WorkloadReport,
                                dedup_workloads, split_budget)
from repro.core.traffic import (ContinuousTuner, TrafficEntry, TrafficLog,
                                installed_log, set_traffic_log)
from repro.core.dispatch import (best_schedule, ensure_tuned,
                                 fixed_library_schedule,
                                 invalidate_dispatch_caches, kernel_params)

__all__ = [
    "HardwareConfig", "V5E", "V5E_VMEM32", "V5E_VMEM64", "V5E_MXU256",
    "INTERPRET", "SWEEP", "Workload", "matmul", "qmatmul", "gemv", "vmacc",
    "attention", "Schedule", "Decision", "space_for", "concretize",
    "concretize_cache_stats", "clear_concretize_cache",
    "BuildCache", "build_cache_stats", "clear_build_cache",
    "global_build_cache",
    "DecisionDistribution", "KernelParams", "SpaceProgram", "flat_space_v1",
    "tile_candidates", "v1_distinct_configs", "TraceSampler",
    "Diagnostic", "SpaceReport", "analyze", "lint_space", "pruned_program",
    "RidgeCostModel", "features", "pretrain_from_database",
    "DeviceRunner", "InterpretRunner", "AnalyticRunner",
    "AdaptiveDepthPolicy", "MeasureScheduler", "MeasureTicket",
    "SerialMeasureQueue",
    "run_batch", "xla_latency",
    "TuningDatabase", "default_db_path", "global_database",
    "reset_global_database",
    "tune", "TuneDriver", "TuneResult",
    "BudgetLedger", "EntropyStopPolicy",
    "TuningSession", "SessionResult", "WorkloadReport", "dedup_workloads",
    "split_budget",
    "ContinuousTuner", "TrafficEntry", "TrafficLog", "installed_log",
    "set_traffic_log",
    "best_schedule", "ensure_tuned", "fixed_library_schedule",
    "invalidate_dispatch_caches", "kernel_params",
]
