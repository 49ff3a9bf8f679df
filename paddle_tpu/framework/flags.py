"""Runtime flag registry.

reference: paddle/common/flags.h:38-89 (PD_DEFINE_* macros),
paddle/common/flags_native.cc (native parser), surfaced as
paddle.set_flags/get_flags (python/paddle/base/framework.py:132,157).

TPU-native: most of the ~190 reference flags control CUDA allocators,
cuDNN autotune, NCCL — irrelevant under XLA. We keep the registry shape
(env-var override `FLAGS_*`, set/get API) and define the flags that
matter on TPU.
"""

from __future__ import annotations

import os
from typing import Any

_REGISTRY: dict[str, dict] = {}


def define_flag(name: str, default: Any, help_: str = ""):
    env = os.environ.get("FLAGS_" + name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _REGISTRY[name] = {"value": value, "default": default, "help": help_}
    return value


def set_flags(flags: dict):
    """paddle.set_flags"""
    for k, v in flags.items():
        k = k.removeprefix("FLAGS_")
        if k not in _REGISTRY:
            raise ValueError(f"unknown flag FLAGS_{k}")
        _REGISTRY[k]["value"] = v
        _apply_side_effect(k, v)


def _apply_side_effect(name, value):
    """Flags that configure jax/XLA directly take effect on set."""
    if name == "matmul_precision":
        import jax
        jax.config.update("jax_default_matmul_precision",
                          None if value == "default" else value)
    elif name == "observability":
        from ..observability import disable, enable
        s = str(value).lower()
        if s in ("1", "true", "yes", "on"):
            enable()
        else:
            disable()
    elif name == "fault_injection":
        from ..resilience import faults
        faults.arm_spec(value)   # "" disarms; bad specs raise here


def get_flags(flags):
    """paddle.get_flags"""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        k2 = k.removeprefix("FLAGS_")
        if k2 not in _REGISTRY:
            raise ValueError(f"unknown flag {k}")
        out[k] = _REGISTRY[k2]["value"]
    return out


def flag_value(name: str):
    return _REGISTRY[name]["value"]


# ---- TPU-relevant flags (counterparts noted) ------------------------------
define_flag("check_nan_inf", False, "scan op outputs for NaN/Inf (ref: FLAGS_check_nan_inf)")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >0: log only")
define_flag("use_bfloat16_matmul", True, "prefer bf16 matmul accumulation on MXU")
define_flag("log_memory_stats", False, "log live buffer stats (ref: FLAGS_log_memory_stats)")
define_flag("benchmark", False, "sync after each op for timing (ref: FLAGS_benchmark)")
define_flag("jit_default_backend", "xla", "compiled-step backend")
define_flag("flash_attention_backend", "auto",
            "attention kernel: auto (the rule on the shape in "
            "ops/pallas/attention_router) | pallas | xla")
define_flag("enable_auto_remat", False, "apply jax.checkpoint policy to compiled blocks")

# numerics / precision (ref: FLAGS_use_mkldnn-era precision knobs collapse
# into XLA precision config)
define_flag("matmul_precision", "default", "default|high|highest -> jax default_matmul_precision")
define_flag("cudnn_deterministic", False, "ref FLAGS_cudnn_deterministic: on TPU maps to XLA deterministic reductions (informational)")
define_flag("embedding_deterministic", 0, "ref FLAGS_embedding_deterministic; TPU scatters are deterministic (informational)")
define_flag("low_precision_op_list", 0, "ref FLAGS_low_precision_op_list: log AMP casts when >0")
# memory (ref: FLAGS_fraction_of_gpu_memory_to_use family -> XLA_PYTHON_CLIENT_*)
define_flag("fraction_of_gpu_memory_to_use", 0.92, "ref name kept; forwards to XLA_PYTHON_CLIENT_MEM_FRACTION at init")
define_flag("allocator_strategy", "auto_growth", "ref FLAGS_allocator_strategy; XLA BFC always (informational)")
define_flag("gpu_memory_limit_mb", 0, "ref FLAGS_gpu_memory_limit_mb; 0 = no cap")
define_flag("eager_delete_tensor_gb", 0.0, "ref FLAGS_eager_delete_tensor_gb; XLA frees by liveness (informational)")
define_flag("use_pinned_memory", True, "ref FLAGS_use_pinned_memory; jax pins host staging buffers (informational)")
# distributed / collectives
define_flag("dynamic_static_unified_comm", True, "ref FLAGS_dynamic_static_unified_comm; one comm stack here by design")
define_flag("nccl_blocking_wait", False, "ref FLAGS_nccl_blocking_wait; XLA collectives are in-program (informational)")
define_flag("distributed_watchdog_timeout_s", 600, "step-watchdog timeout (ref: comm task watchdog)")
define_flag("mesh_rpc_timeout_s", 30.0, "per-op reply budget for the serving-mesh transport (inference/mesh/transport.py EngineProxy); an expired wait raises typed TransportTimeout — the worker is treated gray (reply still owed), never latched lost. A request deadline_s tightens the budget per call; the pool's op_timeout_s overrides")
define_flag("mesh_worker_accept_timeout_s", 120.0, "how long the parent waits for a spawned mesh worker's transport connection (and the worker for its parent's listener) before typed TransportTimeout; engine_spec accept_timeout_s overrides per pool")
define_flag("stop_check_timeout", 3600, "ref FLAGS_stop_check_timeout: elastic trainer liveness window")
define_flag("retain_grad_for_all_tensor", False, "ref FLAGS_retain_grad_for_all_tensor: keep .grad on non-leaf tensors")
# compiled-step behavior
define_flag("use_stride_kernel", False, "ref FLAGS_use_stride_kernel; XLA has no stride kernels (informational)")
define_flag("jit_donate_buffers", True, "donate param/opt buffers in compiled train steps")
# PIR-lite compiler layer (paddle_tpu/pir/; ref: paddle/pir + FLAGS_enable_pir_api)
define_flag("pir", True, "route to_static/serving compilation through the PIR pass pipeline (ref FLAGS_enable_pir_api); off = plain jax.jit")
define_flag("pir_passes", "fold,cse,pattern,fuse,dce,shard_search,shard_prop,overlap", "ordered comma list of PIR passes to run (registered: dce,fold,cse,pattern,fuse,shard_search,shard_prop,overlap); each individually toggleable by omission. The three sharding passes no-op outside a shard_prop.mesh_scope / without input annotations, so the single-chip path is unchanged; fuse runs after pattern (never crosses pt.* boundaries) and before dce (which reaps duplicated layout ops)")
define_flag("pir_verify", "boundary", "structural IR verifier (pir/verifier.py): off | boundary (after capture + after the final pass) | on (after capture + after every pass; tests/tools). A rejection degrades the compile to plain jax.jit, counted pir_fallback_total{stage=verify}")
define_flag("compile_cache_dir", "", "persistent PIR compile-cache directory ('' = off): sha256-verified StableHLO artifacts keyed by canonical IR hash + sharding + flags + jax version")
define_flag("compile_cache_max_bytes", 1 << 28, "PIR compile-cache size cap; least-recently-read artifacts are evicted past it")
define_flag("jit_signature_cache_size", 64, "max compiled input signatures kept per StaticFunction (LRU); shape churn past it shows up in jit_retrace_total")
define_flag("pipeline_schedule", "FThenB", "default pipeline schedule: FThenB|1F1B")
define_flag("prim_all", False, "ref FLAGS_prim_all: decompose big ops before autodiff (jax does this inherently; informational)")
define_flag("cinn_bucket_compile", False, "ref FLAGS_cinn_bucket_compile; XLA owns fusion (informational)")
# profiler / debug
define_flag("observability", False, "runtime observability layer (paddle_tpu.observability): metrics registry + span tracing + SLO telemetry; off = zero-cost no-op fast path")
define_flag("flight_recorder_dir", "", "directory flight-recorder postmortem dumps land in ('' = the tempdir); read from the environment by observability/recorder.py so standalone loads see it too")
define_flag("fault_injection", "", "chaos harness spec (paddle_tpu.resilience.faults): 'site:nth:Exc' / 'site:rand(p)@seed:Exc' entries joined by ';'; '' = disarmed (one global load per site)")
define_flag("enable_host_event_recorder_hook", False, "ref FLAGS_enable_host_event_recorder_hook: record host events in profiler")
define_flag("call_stack_level", 1, "ref FLAGS_call_stack_level: error-message stack detail")
define_flag("api_benchmark", False, "per-op wall-time logging in execute()")
define_flag("max_inplace_grad_add", 0, "ref FLAGS_max_inplace_grad_add (informational; tape adds functionally)")
