"""Canonical metric-name catalog.

EVERY metric the framework emits is declared here — instrumentation
sites fetch handles via `metric(name, **labels)`, which refuses names
not in the catalog, and OBSERVABILITY.md's table is generated from /
checked against this dict (tests/test_observability.py pins both
directions, so docs and code cannot drift).

Entry: name -> (type, help, labelnames, buckets_or_None).
"""

from __future__ import annotations

import re

from . import metrics as _metrics

__all__ = ["CATALOG", "TRACE_SCOPES", "TRACE_PASSES", "KERNEL_NAMES",
           "trace_pass", "metric", "register_all"]

# latency bucket families (seconds)
_TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                 10.0, 30.0)
_TPOT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                 0.025, 0.05, 0.1, 0.25, 1.0)
_STEP_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, 5.0, 15.0, 60.0)
# ratio buckets (0..1) — acceptance rates and other fractions
_RATE_BUCKETS = (0.05, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)
# phase segments span sub-ms marks to multi-second cold compiles
_PHASE_BUCKETS = (0.00005, 0.0002, 0.001, 0.005, 0.025, 0.1, 0.5, 2.5,
                  10.0, 60.0)
# measured/predicted cost ratios, log-ish around the ideal 1.0
_COST_RATIO_BUCKETS = (0.1, 0.2, 0.5, 0.8, 1.0, 1.25, 2.0, 5.0, 10.0)
# wire bytes of one paged-KV handoff: tiny CPU-proxy prompts land in the
# low KB buckets, production-shape blocks in the MB range
_HANDOFF_BUCKETS = (1e3, 4e3, 16e3, 64e3, 256e3, 1e6, 4e6, 16e6, 64e6)

CATALOG = {
    # -- serving (inference/serving.py ContinuousBatchingEngine) ------------
    "serving_ttft_seconds": (
        "histogram", "time from add_request to the first sampled token",
        (), _TTFT_BUCKETS),
    "serving_tpot_seconds": (
        "histogram", "per-token decode latency: dispatch->readback wall "
        "time of one fused K-step decode tile over K (all active lanes "
        "advance K tokens per dispatch)", (), _TPOT_BUCKETS),
    "serving_prefill_seconds": (
        "histogram", "one prefill chunk program call (chunked prompt)",
        (), _STEP_BUCKETS),
    "serving_queue_depth": (
        "gauge", "requests waiting for admission", (), None),
    "serving_batch_occupancy": (
        "gauge", "active lanes / max_batch (0..1)", (), None),
    "serving_kv_free_blocks": (
        "gauge", "free blocks in the paged KV pool", (), None),
    "serving_admitted_total": (
        "counter", "requests admitted to a decode lane", (), None),
    "serving_retired_total": (
        "counter", "requests finished and released", (), None),
    "serving_rejected_total": (
        "counter", "requests rejected as unservable",
        ("reason",), None),
    "serving_deferred_total": (
        "counter", "admissions deferred (request stays queued)",
        ("reason",), None),
    "serving_preempted_total": (
        "counter", "mid-flight decode-lane preemptions by the SLO "
        "scheduler (unlabelled total; serving_preemptions_total is the "
        "by-class sibling)", (), None),
    "serving_preemptions_total": (
        "counter", "decode-lane preemptions by priority class of the "
        "preempted request — paged-KV blocks stay resident and the "
        "stream resumes byte-identically", ("class",), None),
    "serving_brownout_level": (
        "gauge", "current brownout-ladder level index (0 = normal; the "
        "closed, ordered registry is inference/scheduler.py "
        "BROWNOUT_LEVELS, documented in RESILIENCE.md)", (), None),
    "serving_brownout_transitions_total": (
        "counter", "brownout-ladder level transitions by direction (up "
        "= escalate under SLO pressure, down = recover with "
        "hysteresis)", ("direction",), None),
    "serving_quota_deferrals_total": (
        "counter", "admissions deferred because the tenant sits at its "
        "lane quota (the DRR pick skips it; the request stays queued)",
        ("tenant",), None),
    "serving_tokens_total": (
        "counter", "tokens emitted across all requests", (), None),
    "serving_finished_total": (
        "counter", "requests finished by finish_reason "
        "(eos/length/timeout/shed/rejected) — degraded completions are "
        "distinguishable", ("reason",), None),
    "serving_timeouts_total": (
        "counter", "per-request deadlines expired, by where the request "
        "was (queue/decode/preempted, plus the router-side 'handoff' "
        "sweep for streams parked between replicas — invisible to both "
        "engines' own sweeps)", ("where",), None),
    "serving_shed_total": (
        "counter", "decode-OOM lane sheds (request requeued for a fresh "
        "prefill, or finished 'shed' past max_sheds)", (), None),
    "serving_backpressure_total": (
        "counter", "add_request refusals at max_queue (BackpressureError)",
        (), None),
    "serving_pool_exhausted_total": (
        "counter", "paged-KV-pool reservations refused "
        "(KVPoolExhaustedError raised; caller defers or sheds)", (), None),
    "serving_lane_state_uploads_total": (
        "counter", "device lane-state refreshes from the host mirrors "
        "(only on lane membership change: admit/retire/shed; steady-state "
        "decode uploads nothing)", (), None),
    "serving_decode_dispatches_total": (
        "counter", "fused K-step decode tiles dispatched (compare with "
        "serving_lane_state_uploads_total: uploads << dispatches)",
        (), None),
    "serving_dispatch_ahead_depth": (
        "gauge", "in-flight decode tiles at dispatch time (1 = "
        "double-buffered: host bookkeeping overlaps device compute)",
        (), None),
    "serving_hostsync_seconds": (
        "histogram", "host blocked reading back a decode token tile "
        "(device->host sync; the overlap design keeps this small)",
        (), _TPOT_BUCKETS),
    "serving_hostsync_retries_total": (
        "counter", "transient token-tile readback failures (tile kept "
        "in flight, retried next step)", (), None),
    "serving_prefill_chunks_total": (
        "counter", "prefill chunk program calls (long prompts interleave "
        "with decode instead of head-of-line blocking)", (), None),
    "serving_draft_tokens_total": (
        "counter", "draft tokens proposed by the speculative decoder "
        "(draft_depth per lane per scan step)", (), None),
    "serving_accepted_tokens_total": (
        "counter", "draft tokens accepted by the batched verify forward "
        "(accepted/drafted is the acceptance rate; the committed stream "
        "also gets one correction token per step on top)", (), None),
    "serving_spec_acceptance_rate": (
        "histogram", "per-drained-tile draft acceptance rate (0..1); the "
        "exemplar carries the trace id of the WORST-accepting request in "
        "the tile, so a low bucket links to the request to turn "
        "speculation off for", (), _RATE_BUCKETS),
    "serving_kv_dequant_seconds": (
        "histogram", "wall time of a whole-pool KV dequantization (the "
        "serve.kv_dequant drop-to-bf16 degradation path)", (),
        _STEP_BUCKETS),
    "serving_tokens_per_dispatch": (
        "gauge", "tokens credited from the last drained decode tile (one "
        "dispatch): K per lane without speculation, up to K*(draft_depth"
        "+1) per lane with it", (), None),
    "serving_runtime_degradations_total": (
        "counter", "permanent runtime degradations taken by the engine "
        "(speculation_off: draft/verify fault -> non-speculative decode; "
        "kv_bf16: dequant fault -> pool dequantized to the native dtype; "
        "sched_fifo: scheduler decision fault -> plain FIFO admission; "
        "prefix_miss: prefix-index fault -> that one lookup/insert "
        "treated as a cache miss, full prefill, stream unchanged)",
        ("what",), None),
    "serving_prefix_hits_total": (
        "counter", "admissions whose prompt resolved >= 1 leading block "
        "from the cross-request prefix cache (prefill runs only on the "
        "unmatched tail)", (), None),
    "serving_prefix_misses_total": (
        "counter", "admissions (prefix cache enabled) whose prompt "
        "resolved nothing from the index — including lookups degraded "
        "by a serve.prefix_match fault", (), None),
    "serving_prefix_tokens_saved_total": (
        "counter", "prompt tokens NOT prefilled because their blocks "
        "were resolved from the prefix cache (hit_rate * mean matched "
        "length in one number; the bench prefill-skip evidence)",
        (), None),
    "serving_prefix_shared_blocks": (
        "gauge", "paged-KV blocks currently pinned by the prefix index "
        "(each holds one block-aligned prompt chunk; refcount-shared "
        "with any resident requests that adopted it)", (), None),
    "serving_prefix_evictions_total": (
        "counter", "prefix-index entries evicted (LRU leaf under pool "
        "pressure or the prefix_cache_blocks cap, plus whole-index "
        "clears on a block-format degradation)", (), None),
    "serving_prefix_cow_forks_total": (
        "counter", "copy-on-write block forks: a block-aligned "
        "full-prefix match re-prefills its final prompt position into a "
        "private copy of the last shared block (the only write that can "
        "target a shared block)", (), None),
    "serving_adapter_loads_total": (
        "counter", "adapter hot-loads into a device pool slot, by "
        "adapter name (bounded by the store's closed registry)",
        ("adapter",), None),
    "serving_adapter_evictions_total": (
        "counter", "idle adapter slots LRU-evicted to make room for a "
        "cold acquire, by the evicted adapter's name", ("adapter",),
        None),
    "serving_adapter_resident": (
        "gauge", "named adapters currently resident in the device "
        "weight pool (slot 0, the all-zeros base, is not counted)",
        (), None),
    "serving_adapter_load_failures_total": (
        "counter", "adapter acquisitions that failed typed (unknown "
        "name, all slots pinned, or an injected serve.adapter_load / "
        "serve.adapter_gather fault); each one is a "
        "finish_reason=rejected admission, never a wrong-weights "
        "stream", (), None),
    "serving_adapter_upload_seconds": (
        "histogram", "host dispatch wall of one adapter's A/B pool "
        "upload (the copy itself is async and overlaps in-flight "
        "decode tiles)", (), _STEP_BUCKETS),
    "serving_adapter_quota_deferrals_total": (
        "counter", "admission picks skipped because the candidate's "
        "adapter was at its concurrent-lane quota (adapter DRR riding "
        "the tenant scheduler)", ("adapter",), None),
    "serving_adapter_ttft_seconds": (
        "histogram", "per-adapter time to first token (label 'base' = "
        "slot-0 requests; cardinality bounded by the store's closed "
        "registry)", ("adapter",), _TTFT_BUCKETS),
    "serving_adapter_tpot_seconds": (
        "histogram", "per-adapter per-token decode latency (same tile "
        "wall as serving_tpot_seconds, attributed to each adapter the "
        "tile advanced)", ("adapter",), _TPOT_BUCKETS),
    "serving_phase_seconds": (
        "histogram", "one phase-attributed segment of engine step wall "
        "time, by profiler phase (closed registry in "
        "paddle_tpu/profiler/phases.py; segments partition the step)",
        ("phase",), _PHASE_BUCKETS),
    "serving_phase_coverage_ratio": (
        "gauge", "cumulative phase-attributed time / measured engine "
        "step wall time (0..1); the harness gates on >= 0.95", (), None),
    "serving_tenant_ttft_seconds": (
        "histogram", "per-tenant time to first token (bounded-cardinality "
        "sibling of serving_ttft_seconds; unattributed tenant is '-', "
        "overflow past the cap collapses to 'overflow')",
        ("tenant",), _TTFT_BUCKETS),
    "serving_tenant_tpot_seconds": (
        "histogram", "per-tenant per-token decode latency "
        "(bounded-cardinality sibling of serving_tpot_seconds)",
        ("tenant",), _TPOT_BUCKETS),
    "serving_tenant_finished_total": (
        "counter", "requests finished, by tenant and finish_reason "
        "(bounded-cardinality sibling of serving_finished_total)",
        ("tenant", "reason"), None),
    "serving_overload": (
        "gauge", "1.0 while the engine is saturated (predicted service "
        "demand exceeds capacity: slo_headroom <= 0), else 0.0 — the "
        "shed-before-collapse early-warning the loadgen harness asserts "
        "on", (), None),

    # -- generation (generation.py) -----------------------------------------
    "generation_requests_total": (
        "counter", "generate() calls by execution path",
        ("path",), None),

    # -- attention kernels (ops/pallas/autotune.py) -------------------------
    "attention_backend_failures_total": (
        "counter", "flash-kernel tile candidates the TPU compiler or device "
        "refused while the autotuner timed them (site autotune); each is "
        "also logged with the compiler's message", ("site",), None),

    # -- training telemetry (observability.stepwatch.StepWatch) -------------
    "train_step_seconds": (
        "histogram", "train-step wall time", (), _STEP_BUCKETS),
    "train_tokens_total": (
        "counter", "training tokens consumed", (), None),
    "train_loss": ("gauge", "latest training loss", (), None),
    "train_grad_norm": ("gauge", "latest global grad norm", (), None),
    "train_tokens_per_s": ("gauge", "online training throughput", (), None),
    "train_mfu": (
        "gauge", "online model-FLOPs utilization (needs flops_per_token "
        "and peak_flops)", (), None),
    "moe_held_assignment_share": (
        "gauge", "routed (token, choice) assignments that landed on the "
        "experts this program holds / tokens x top-k, over every layer's "
        "own input on one sequence (held / routed experts under even "
        "routing); a statistic of the weights it was taken at, set by "
        "whoever builds the model, outside the step", (), None),
    "moe_expert_load_max_over_mean": (
        "gauge", "largest / mean number of assignments over the held "
        "experts, same sequence, the layers' mean (1.0 = even load)", (),
        None),
    "attn_window_visited_pair_share": (
        "gauge", "query-key pairs the three windowed flash kernels' sweeps "
        "visit at sub-block granularity / three times the pairs the band "
        "leaves, at the window layers' shape (ops/pallas/attention_router "
        "Decision.visited_pair_share; 1.0 at best); a count from the "
        "tiles, set by whoever builds the model, outside the step", (),
        None),
    "retention_mean_horizon_tokens": (
        "gauge", "mean over layers and state heads of 1 / (1 - mean_t g_t), "
        "the tokens a power-retention state remembers, from the model's "
        "own gate (models/brumby.py retention_log_gate) on every layer's "
        "own input on one sequence; a statistic of the weights it was "
        "taken at, set by whoever builds the model, outside the step", (),
        None),
    "train_nonfinite_skips_total": (
        "counter", "batches skipped by the TrainSupervisor for a "
        "non-finite loss", (), None),
    "train_preemptions_total": (
        "counter", "SIGTERM preemptions handled gracefully (final "
        "checkpoint + clean exit)", (), None),

    # -- elastic / distributed recovery --------------------------------------
    "elastic_membership_changes_total": (
        "counter", "ElasticManager.watch observed the alive set change",
        (), None),
    "elastic_restarts_total": (
        "counter", "ElasticManager returned RESTART (regroup requested)",
        (), None),
    "elastic_pod_restarts_total": (
        "counter", "launcher restarted the local pod after worker failure",
        (), None),
    "checkpoint_saves_total": (
        "counter", "distributed checkpoint save_state_dict calls", (), None),
    "checkpoint_loads_total": (
        "counter", "distributed checkpoint load_state_dict calls (resume "
        "path after elastic restart)", (), None),
    "elastic_heartbeat_recoveries_total": (
        "counter", "heartbeat store writes that succeeded after >=1 retry "
        "(transient store fault survived)", (), None),
    "elastic_watch_recoveries_total": (
        "counter", "membership-watch store reads that succeeded after "
        ">=1 retry", (), None),
    "elastic_beat_failures_total": (
        "counter", "threaded-heartbeat iterations that failed past the "
        "retry budget (the daemon beat loop keeps going — the lease may "
        "still survive within its ttl; never raised into serving)",
        (), None),

    # -- resilience (paddle_tpu/resilience/: faults, retry) ------------------
    "fault_injected_total": (
        "counter", "faults fired by the injection harness, by site "
        "(FLAGS_fault_injection / resilience.faults)", ("site",), None),
    "resilience_retries_total": (
        "counter", "transient-failure retries by RetryPolicy, by op",
        ("op",), None),
    "resilience_retry_giveups_total": (
        "counter", "retry budgets exhausted (last error re-raised), by op",
        ("op",), None),
    "resilience_circuit_open_total": (
        "counter", "circuit breakers tripping open, by op", ("op",), None),

    # -- PIR compiler layer (paddle_tpu/pir/: capture, passes, cache) --------
    "pir_captures_total": (
        "counter", "programs captured (jaxpr -> pir.Program lowerings)",
        (), None),
    "pir_pass_seconds": (
        "histogram", "wall time of one PIR pass run, by pass",
        ("pass",), _STEP_BUCKETS),
    "pir_pass_edits_total": (
        "counter", "IR edits applied (ops removed/folded/merged/"
        "rewritten), by pass", ("pass",), None),
    "pir_fallback_total": (
        "counter", "pipeline degradations to plain jax.jit, by stage "
        "(capture/verify/fuse/passes/evaluator)", ("stage",), None),
    "pir_verify_seconds": (
        "histogram", "wall time of one structural verifier run over a "
        "captured program (pir/verifier.py; after capture and after "
        "passes per FLAGS_pir_verify)", (), _STEP_BUCKETS),
    "pir_verify_failures_total": (
        "counter", "programs rejected by the IR verifier, by rule "
        "(def-before-use/single-def/arity/dangling-value/dead-code/"
        "effect-order/type-mismatch/donation-alias/sharding-conflict/"
        "verifier-error); each rejection degrades that compile to "
        "plain jax.jit", ("rule",), None),
    "jit_retrace_total": (
        "counter", "compiled-program (re)constructions: StaticFunction "
        "traces for a new input signature, plus serving decode/prefill "
        "program builds (shape or variant churn is visible here; the "
        "adapter hot-swap contract pins its delta to 0 across churn)",
        (), None),
    "compile_cache_hit_total": (
        "counter", "persistent compile-cache hits (verified artifact "
        "deserialized; XLA compile skipped)", (), None),
    "compile_cache_miss_total": (
        "counter", "persistent compile-cache misses (fresh compile)",
        (), None),
    "compile_cache_write_total": (
        "counter", "compile-cache artifacts written", (), None),
    "compile_cache_corrupt_total": (
        "counter", "artifacts that failed sha256/format verification "
        "(typed CompileCacheCorruptionError; recovered by recompile)",
        (), None),
    "compile_cache_evict_total": (
        "counter", "artifacts LRU-evicted past the size cap", (), None),
    "compile_cache_bytes": (
        "gauge", "compile-cache directory size after the last write",
        (), None),
    "pir_cost_ratio": (
        "gauge", "measured / roofline-predicted wall time of the last "
        "dispatch of the named compiled program (pir/analysis.py "
        "CostModel; 1.0 = the static price was exact)", ("program",), None),
    "pir_cost_model_error": (
        "histogram", "measured/predicted cost ratio per dispatch, all "
        "programs pooled; the exemplar carries the PROGRAM NAME, so the "
        "top bucket's exemplar names the worst-predicted program",
        (), _COST_RATIO_BUCKETS),
    "pir_sharding_annotations_total": (
        "counter", "Value.sharding annotations committed by the "
        "sharding-propagation pass (pir/shard_prop.py), by program — "
        "fixpoint output, not user input: input annotations spread "
        "through the whole IR land here", ("program",), None),
    "pir_shard_search_seconds": (
        "histogram", "wall time of one cost-driven sharding search "
        "(pir/shard_search.py; bounded candidate enumeration priced "
        "by the CostModel roofline+ICI estimate)", (), _STEP_BUCKETS),
    "pir_exposed_comm_seconds": (
        "gauge", "CostModel exposed-communication seconds of the named "
        "program after the collective-overlap pass committed a "
        "schedule (pir/overlap.py; comm the overlap credit did not "
        "hide)", ("program",), None),
    "pir_fusion_groups_total": (
        "counter", "pt.fused_region groups committed by the auto-fusion "
        "pass (pir/fuse.py), by program — each group passed the strict "
        "predicted bytes-traffic-decrease criterion", ("program",), None),
    "pir_fusion_bytes_saved": (
        "counter", "predicted HBM bytes-traffic saved by committed "
        "fusion groups (CostModel.group_bytes_saved: unfused member "
        "traffic minus fused boundary traffic), by program",
        ("program",), None),
    "pir_fusion_groups_by_kind_total": (
        "counter", "committed fusion groups by provenance kind — chain "
        "(v1 single-output), multi_output (promoted sibling-shared "
        "results), epilogue (dot_general / nested-region anchor "
        "absorbed) — by program (pir/fuse.py GROUP_KINDS)",
        ("program", "kind"), None),
    "pir_fuse_seconds": (
        "histogram", "wall time of one auto-fusion pass run (planning "
        "walk + group commits; pir/fuse.py)", (), _STEP_BUCKETS),

    # -- telemetry loop (tracing ring, flight recorder, SLO engine) ----------
    "tracer_dropped_spans_total": (
        "counter", "finished spans evicted when the bounded tracer ring "
        "wrapped (raise Tracer(maxlen=...) or export more often)", (), None),
    "flight_recorder_dumps_total": (
        "counter", "flight-recorder postmortem dumps written, by reason "
        "(unhandled_error/preempt/drill:<site>/manual)", ("reason",), None),
    "slo_compliance": (
        "gauge", "1.0 when the named SLO currently meets its objective, "
        "else 0.0 (slo.SLOEngine.evaluate)", ("slo",), None),
    "slo_burn_rate": (
        "gauge", "error-budget burn rate of the named SLO (1.0 = burning "
        "exactly the budget; >1 exhausts it early); for quantile SLOs, "
        "observed/target ratio", ("slo",), None),
    "slo_headroom": (
        "gauge", "remaining serving capacity as a fraction of capacity: "
        "1 - arrival_rate * predicted_seconds_per_request (cost-model "
        "calibrated); <= 0 means offered load exceeds what the engine "
        "can serve and goodput will collapse unless load sheds", (), None),

    # -- load generator (inference/loadgen.py + tools/loadgen.py) ------------
    "loadgen_arrivals_total": (
        "counter", "requests injected by the open-loop traffic harness, "
        "by scenario", ("scenario",), None),
    "loadgen_ticks_skipped_total": (
        "counter", "harness clock ticks skipped after a "
        "serve.loadgen_tick fault (arrivals from the skipped tick are "
        "re-issued on the next one — open-loop schedule preserved)",
        (), None),

    # -- serving mesh (inference/mesh/: router, disaggregated handoff) -------
    "mesh_routed_total": (
        "counter", "requests the mesh router committed to the named "
        "replica (after the mesh.route fault site and the replica's "
        "CircuitBreaker both let the pick through)", ("replica",), None),
    "mesh_handoffs_total": (
        "counter", "serialized paged-KV prefill->decode handoffs, by "
        "outcome (ok / retried / re_prefill — re_prefill means the "
        "wire transfer was abandoned and the decode side re-ran "
        "prefill from the prompt)", ("outcome",), None),
    "mesh_failovers_total": (
        "counter", "requests re-routed off a replica, by reason "
        "(replica_down / circuit_open / route_fault / admit_failed)",
        ("reason",), None),
    "mesh_handoff_bytes": (
        "histogram", "serialized wire size of one paged-KV handoff "
        "(payload + scales + prompt metadata; quantized block formats "
        "shrink this ~2-4x at identical streams)", (), _HANDOFF_BUCKETS),
    "mesh_replica_headroom": (
        "gauge", "per-replica slo_headroom snapshot the router balanced "
        "on at its last pick (1 - offered_load * predicted service "
        "seconds; <=0 = saturated, routed around when possible)",
        ("replica",), None),
    "mesh_transport_frames_total": (
        "counter", "framed request/response round trips between the "
        "router and process-backed workers, by frame kind (transport.py; "
        "loopback and socket transports both count)", ("kind",), None),
    "mesh_controller_actions_total": (
        "counter", "autoscale controller actions taken on advisor "
        "verdicts (scale_up / drain_begin / scale_down / drain_forced / "
        "latch_off — latch_off means a controller failure flipped it "
        "back to advisory-only)", ("action",), None),
    "mesh_rpc_timeouts_total": (
        "counter", "transport op waits that expired past their budget, "
        "by op (frame kind): client-side result()/drain expiry AND "
        "worker-side rejection of already-expired work both count — "
        "every one raises typed TransportTimeout, the gray-failure "
        "signal (reply still owed, replica NOT latched lost)",
        ("op",), None),
    "mesh_replica_suspicion": (
        "gauge", "per-replica phi-accrual suspicion score from the "
        "health detector (inter-progress latency while busy; 0 = "
        "progressing or idle; crosses the SLOW threshold before the "
        "DEAD one by construction)", ("replica",), None),
    "mesh_slow_demotions_total": (
        "counter", "health-detector SLOW verdicts per replica: the "
        "replica is demoted out of _ranked (no new placements, existing "
        "streams keep running) until it progresses again — the gray "
        "middle ground between healthy and the replica_down path",
        ("replica",), None),
    "mesh_hedges_total": (
        "counter", "hedged recoveries, by outcome: launched (a parked "
        "handoff or in-flight prefill outlived the latency budget and a "
        "speculative duplicate started on the next-best replica) / win "
        "(the hedge committed first) / cancelled (the losing duplicate "
        "was withdrawn from its worker) — first finish wins through the "
        "at-most-once commit map, streams byte-identical",
        ("outcome",), None),

    # -- observability plane (timeseries.py sampler + mesh federation) -------
    "obs_samples_total": (
        "counter", "successful MetricsSampler scrape ticks (timeseries.py; "
        "one per landed tick across every sampler in the process)", (), None),
    "obs_plane_degradations_total": (
        "counter", "observability-plane failures that flipped a sampler or "
        "collector to degraded (plane off, serving untouched), by failure "
        "class (obs.sample fault site)", ("what",), None),
}

# The closed set of component scopes (jax.named_scope) on device
# operations: what a profiler trace and the lowered program call the
# part of the model a fusion or kernel belongs to. Entered where the
# work is built; backward and rematerialised operations keep the name
# inside transpose(jvp(...)) / checkpoint, and PIR replay restores it
# (pir/ir.py Operation.evaluate). tools/static_check.py --rule
# trace-scopes pins every named_scope("pt. ...") literal to this dict
# and every row to OBSERVABILITY.md, both directions. No component may
# equal an entry of pir/verifier.py EFFECT_SCOPES.
TRACE_SCOPES = {
    "pt.embed": "token (+ position) embedding lookup",
    "pt.attn": "attention sub-block: its parts pt.attn.in, pt.attn.pos, "
               "pt.attn.out around the attention kernel or paged attention "
               "(the serving programs enter no part)",
    "pt.attn.in": "inside pt.attn: the input norm and the q, k, v "
                  "projections",
    "pt.attn.pos": "inside pt.attn: per-head q/k RMSNorm and RoPE, tables "
                   "included (models/rope.py); absent where a model has "
                   "neither",
    "pt.attn.out": "inside pt.attn: the output projection and the "
                   "residual add",
    "pt.attn.sliding": "inside pt.attn, a window layer's whole mixer "
                       "(models/mellum.py sliding_attention: default RoPE, "
                       "the faw_* kernels)",
    "pt.attn.full": "inside pt.attn, a full-attention layer's whole mixer "
                    "in a model that mixes kinds (models/mellum.py "
                    "full_attention: YaRN RoPE, the fa_* kernels; "
                    "models/phi4flash.py: the differential full layer "
                    "whose keys and values the cross layers read)",
    "pt.attn.cross": "inside pt.attn, a cross-decoder layer's whole mixer "
                     "(models/phi4flash.py cross_attention: queries alone, "
                     "the full layer's keys and values, the fa_* kernels)",
    "pt.attn.diff": "inside pt.attn, differential attention's combination "
                    "of its two maps: lambda, the difference and the "
                    "per-head sub-norm (models/phi4flash.py)",
    "pt.gmu": "gated memory unit sub-block (models/phi4flash.py): its "
              "norm, the gate's projection, the memory times the gate, the "
              "output projection and the residual add",
    "pt.mlp": "MLP sub-block with its norm (in an expert layer: the norm, "
              "the shared expert and the residual)",
    "pt.ssm": "state-space mixer: Mamba-2 (its parts pt.ssm.in, "
              "pt.ssm.conv, pt.ssm.scan, pt.ssm.gate, pt.ssm.out) or "
              "Mamba-1 (pt.ssm.in, pt.ssm.conv, pt.ssm.sel, pt.ssm.out)",
    "pt.ssm.in": "inside pt.ssm: the input norm, in_proj and its split "
                 "into z, xBC and dt (Mamba-1: x and z)",
    "pt.ssm.conv": "inside pt.ssm: the causal depthwise conv with its silu "
                   "and dt's softplus (Mamba-1: with x_proj and dt_proj, "
                   "which make delta, B and C from its output)",
    "pt.ssm.scan": "the chunked state-space scan alone (ops/mamba2.py)",
    "pt.ssm.sel": "Mamba-1's selective scan alone with its z gate "
                  "(ops/selective_scan.py; on a TPU the selscan_* kernels "
                  "and the layout of B, C and their cotangents around "
                  "them)",
    "pt.ssm.gate": "inside pt.ssm: the gated RMSNorm of the scan's output",
    "pt.ssm.out": "inside pt.ssm: out_proj and the residual add",
    "pt.retn": "power-retention mixer (models/brumby.py): its parts "
               "pt.retn.in, pt.retn.pos, pt.retn.scan, pt.retn.out",
    "pt.retn.in": "inside pt.retn: the input norm, the q, k, v projections "
                  "and the gate's (retention_log_gate)",
    "pt.retn.pos": "inside pt.retn: per-head q/k RMSNorm and RoPE, tables "
                   "included (models/rope.py)",
    "pt.retn.scan": "the chunked power retention alone (ops/"
                    "power_retention.py): expansion, in-chunk products, "
                    "state products, the carried state, the normaliser",
    "pt.retn.out": "inside pt.retn: the output projection and the "
                   "residual add",
    "pt.moe": "routed experts: router, dispatch, grouped expert matmuls "
              "with their activation, combine",
    "pt.moe.route": "what in pt.moe is no expert work: router logits, "
                    "top-k, sort, the dispatch and combine gathers",
    "pt.head": "final norm + LM head matmul",
    "pt.loss": "cross entropy over the vocabulary",
    "pt.opt": "gradient clipping + optimizer update of the train step",
    "pt.recompute": "inside a custom_vjp backward rule, a forward product "
                    "made again by hand (ops/mamba2.py _conv_bwd: the "
                    "conv's taps); trace_pass reads it as recompute",
    "pt.serve.gather": "paged attention: block-table gather of K/V "
                       "(+ dequantisation) out of the pool",
    "pt.serve.attend": "paged attention: scores, mask, softmax, PV",
    "pt.serve.sample": "on-device argmax / categorical sampling in the "
                       "decode scan",
}

# The pass of a device operation: which part of a train step made it.
# Closed like the scopes; trace_pass is the one rule that reads it off the
# names a compiled step carries, and tests/test_trace_names.py holds the
# rule against the compiled step of every model with a training cell: it
# is the contract with jax's name stack (jvp / transpose / checkpoint /
# rematted_computation are jax's words, not ours) and with XLA's
# instruction names (".remat").
TRACE_PASSES = ("forward", "recompute", "xla_remat", "backward", "update")

_UPDATE = re.compile(r"(?<![\w.])pt\.opt(?![\w.])")
_RECOMPUTE = re.compile(r"rematted_computation|(?<![\w.])pt\.recompute"
                        r"(?![\w.])")


def trace_pass(op_name, instruction_name=""):
    """The TRACE_PASSES entry of one operation of a compiled step, from
    its op_name (the name stack in the HLO's metadata) and its instruction
    name. In this order: under pt.opt, `update`; an instruction XLA
    rematerialised itself (".remat" in its name), `xla_remat`; a
    jax.checkpoint's second forward (`rematted_computation` anywhere in
    the stack: a loop body inside a checkpointed block keeps it) or a
    custom_vjp rule's own (scope pt.recompute), `recompute`; under a
    `transpose(`, `backward` (a custom_vjp backward rule lands under
    transpose(jvp(<scope>))); everything else, an operation without a name
    too, `forward`. What no name can show: recomputation inside a Pallas
    kernel (the flash backward's scores), and a fusion takes one member's
    name, so a recomputed elementwise chain fused into a backward matmul
    counts as backward."""
    if _UPDATE.search(op_name):
        return "update"
    if ".remat" in instruction_name:
        return "xla_remat"
    if _RECOMPUTE.search(op_name):
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


# `name=` of the Pallas kernels (ops/pallas/flash_attention.py,
# ops/pallas/power_retention.py, ops/pallas/rope_norm.py,
# ops/pallas/selective_scan.py, ops/pallas/moe_gather.py): the Mosaic
# kernel name and the innermost scope of the call on the trace.
KERNEL_NAMES = {
    "fa_fwd": "flash attention forward (+ fused RMS epilogue)",
    "fa_bwd_dq": "flash attention backward, dQ",
    "fa_bwd_dkv": "flash attention backward, dK and dV",
    "faw_fwd": "flash attention forward under a window that hides "
               "something: the band's sub-blocks alone",
    "faw_bwd_dq": "windowed flash attention backward, dQ",
    "faw_bwd_dkv": "windowed flash attention backward, dK and dV",
    "retn_read": "power retention: phi(u) @ M, the expansion made a "
                 "rotation at a time in VMEM (the state read; in the "
                 "backward, the cotangent of what was written)",
    "retn_write": "power retention: phi(u)^T @ W over the row grid (the "
                  "state's update; in the backward, the state's cotangent)",
    "retn_back": "power retention: the chain rule through phi back to q "
                 "or k, phi's own cotangent never in memory",
    "normrope_fwd": "per-head q/k RMSNorm, its weight and rotate-half RoPE "
                    "in one pass over a projection (models/rope.py "
                    "norm_rope), float32 in VMEM, rounded where the "
                    "jax.numpy composition rounds",
    "normrope_bwd": "its backward from the cotangent and the raw "
                    "projection: the rotation's transpose, the weight, the "
                    "norm's chain rule, the weight's gradient as float32 "
                    "partial sums a row tile",
    "selscan_fwd": "Mamba-1 selective scan forward with its z gate: the "
                   "(state, channels) float32 state in VMEM, one row of "
                   "time at a time; writes the state before every 128 "
                   "steps (ops/pallas/selective_scan.py)",
    "selscan_bwd": "its backward, a chunk of 128 steps a grid step from "
                   "the last: the chunk's states made again from the saved "
                   "one, then the adjoint walked back through them",
    "moe_gather": "the routed experts' combine (and the dispatch's "
                  "transpose): a tile of tokens a grid step, the 8-row "
                  "blocks its live slots name fetched once by DMA, each "
                  "slot's row picked by a one-hot product on the MXU, the "
                  "columns added in float32 in order "
                  "(ops/pallas/moe_gather.py)",
}


def register_all(registry=None):
    """Define every catalog metric on `registry` (default: the process
    registry). Idempotent; conflicting duplicates raise in the registry."""
    reg = registry or _metrics.get_registry()
    for name, (mtype, help_, labelnames, buckets) in CATALOG.items():
        if mtype == "histogram":
            reg.histogram(name, help_, labelnames,
                          buckets or _metrics.DEFAULT_BUCKETS)
        elif mtype == "gauge":
            reg.gauge(name, help_, labelnames)
        else:
            reg.counter(name, help_, labelnames)
    return reg


def metric(name, **labels):
    """Instrumentation-site handle: get-or-register `name` from the
    catalog on the default registry; unknown names raise (add them to
    the CATALOG + OBSERVABILITY.md first — that is the point)."""
    try:
        mtype, help_, labelnames, buckets = CATALOG[name]
    except KeyError:
        raise KeyError(f"{name!r} is not in the observability catalog "
                       "(paddle_tpu/observability/catalog.py)") from None
    reg = _metrics.get_registry()
    if mtype == "histogram":
        fam = reg.histogram(name, help_, labelnames,
                            buckets or _metrics.DEFAULT_BUCKETS)
    elif mtype == "gauge":
        fam = reg.gauge(name, help_, labelnames)
    else:
        fam = reg.counter(name, help_, labelnames)
    return fam.labels(**labels) if labels else fam
