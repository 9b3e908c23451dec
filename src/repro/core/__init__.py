"""repro.core — the CODO dataflow compiler (the paper's contribution).

Public API:

    from repro.core import (DataflowGraph, codo_opt, CodoOptions, lower,
                            graph_latency, autoschedule)
"""

from .artifact import (SCHEMA_VERSION, ArtifactError, ArtifactWarning,
                       artifact_summary, artifact_weights, export_artifact,
                       import_artifact, validate_artifact)
from .buffers import BufferPlan, determine_buffers, downgrade_to_pingpong
from .cache import CacheStats, CompileCache
from .coarse import eliminate_coarse
from .compiler import (BatchJob, BatchResult, CodoOptions, CompiledDataflow,
                       PassBudgetError, ablation_jobs, batch_workloads,
                       codo_opt, codo_opt_batch, default_cache,
                       default_manager, enforce_pass_budgets,
                       kernel_workloads, verify_violation_free)
from .costmodel import V5E, GraphCost, HwParams, graph_latency, sequential_latency, task_cost
from .fine import eliminate_fine
from .frontend import (GB, ShapedBuffer, TraceError, Tracer, trace, trace_io,
                       weight_init)
from .graph import (FIFO, PINGPONG, Access, Buffer, DataflowGraph, Loop, Task,
                    conv2d_task, copy_task, ewise_task, full_index, idx,
                    matmul_task, pad_task, pool_task, reduce_task, retarget_fn)
from .lowering import (LOWER_CACHE_STATS, FusionGroup, LoweredProgram,
                       clear_lower_cache, fusion_groups, lower,
                       lower_artifact, verify_lowering, verify_routing)
from .offchip import TransferPlan, host_manifest, plan_offchip
from .ops import (OpSpec, UnknownOpError, materialize, op_impl, register_op,
                  registered_ops)
from .passes import (ABLATION_PRESETS, DEFAULT_PASS_BUDGETS,
                     CompileDiagnostics, Pass, PassManager,
                     PassRecord, PASS_RUN_COUNTS, default_passes)
from .patterns import (coarse_violations, fine_violations, violation_report,
                       access_sig, arrival_order)
from .reuse import generate_reuse_buffers, parallel_safety
from .routing import (KernelPattern, RoutedKernel, XLA_FUSED,
                      pallas_disabled, register_kernel_pattern,
                      registered_patterns, route_plan)
from .schedule import assign_stages, autoschedule

__all__ = [
    "ABLATION_PRESETS", "Access", "ArtifactError", "ArtifactWarning",
    "BatchJob", "BatchResult", "Buffer", "DEFAULT_PASS_BUDGETS",
    "PassBudgetError",
    "BufferPlan", "CacheStats", "CodoOptions", "CompileCache",
    "CompileDiagnostics", "CompiledDataflow", "DataflowGraph", "FIFO",
    "FusionGroup", "GB", "GraphCost", "HwParams", "KernelPattern",
    "LOWER_CACHE_STATS", "Loop", "LoweredProgram",
    "OpSpec", "PINGPONG", "PASS_RUN_COUNTS", "Pass", "PassManager",
    "RoutedKernel", "XLA_FUSED",
    "PassRecord", "SCHEMA_VERSION", "ShapedBuffer", "Task", "TraceError",
    "Tracer", "TransferPlan", "UnknownOpError",
    "V5E",
    "ablation_jobs", "access_sig", "arrival_order", "artifact_summary",
    "artifact_weights",
    "assign_stages", "batch_workloads", "enforce_pass_budgets",
    "kernel_workloads",
    "autoschedule", "clear_lower_cache", "coarse_violations", "codo_opt",
    "codo_opt_batch", "conv2d_task", "copy_task", "default_cache",
    "default_manager", "default_passes", "determine_buffers",
    "downgrade_to_pingpong", "eliminate_coarse", "eliminate_fine",
    "ewise_task", "export_artifact", "fine_violations", "full_index",
    "fusion_groups", "generate_reuse_buffers", "graph_latency",
    "host_manifest", "idx", "import_artifact", "lower", "lower_artifact",
    "materialize", "matmul_task", "op_impl", "pad_task",
    "pallas_disabled", "parallel_safety", "plan_offchip", "pool_task",
    "reduce_task",
    "register_kernel_pattern", "register_op",
    "registered_ops", "registered_patterns", "retarget_fn", "route_plan",
    "sequential_latency", "task_cost", "trace", "trace_io",
    "validate_artifact",
    "verify_lowering", "verify_routing", "verify_violation_free",
    "violation_report",
    "weight_init",
]
