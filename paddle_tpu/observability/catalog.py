"""The metric catalog: every framework metric name, declared once.

Names follow ``pt_<subsystem>_<what>[_total|_ms]``; the subsystem token
right after ``pt_`` scopes the ``metrics-registry`` lint the way
failpoint prefixes scope the failpoint lint — a ``pt_train_...`` /
``pt_serving_...`` reference in tests or docs must exist HERE, while an
unrelated ``pt_batch_...`` shm tag is ignored.  The catalog is mirrored
row-for-row by the table in ``docs/observability.md`` (lint-checked,
like the guardian EVENT_SCHEMA table).

Conventions:

- ``*_total`` counters are cumulative since process start (prometheus
  counter semantics); gauges are point-in-time; ``*_ms`` histograms
  observe milliseconds with the default latency buckets.
- every value recorded is a host number the call site already owned —
  recording NEVER forces a device readback (see metrics.py docstring
  for the machine-checked contract).
"""

__all__ = ["METRICS", "subsystems"]

_C, _G, _H = "counter", "gauge", "histogram"

METRICS = {
    # -- training (hapi Model.fit stepper) --------------------------------
    "pt_train_steps_total": {
        "type": _C, "labels": ("outcome",),
        "help": "train steps by guardian verdict: ok | skip | rollback"},
    "pt_train_step_latency_ms": {
        "type": _H, "labels": (),
        "help": "wall time of one train step incl. the per-step host "
                "sync (loss readback)"},
    "pt_train_host_gap_ms": {
        "type": _H, "labels": (),
        "help": "host share of a compiled fit step: end of fit.readback "
                "(the loss read) to end of the next step's fit.dispatch"},
    "pt_train_tokens_total": {
        "type": _C, "labels": (),
        "help": "input elements trained on (batch x seq of the first "
                "input)"},
    "pt_train_tokens_per_sec": {
        "type": _G, "labels": (),
        "help": "instantaneous training throughput (last step)"},
    "pt_train_loss": {
        "type": _G, "labels": (),
        "help": "last train-step loss (host value from the existing "
                "per-step readback)"},
    # -- serving (inference/serving.py + scheduler) -----------------------
    "pt_serving_ttft_ms": {
        "type": _H, "labels": (),
        "help": "time to first token, stamped at the chunk-boundary "
                "sync (quantized to chunk cadence)"},
    "pt_serving_queue_wait_ms": {
        "type": _H, "labels": (),
        "help": "submit -> slot admission wait"},
    "pt_serving_host_gap_ms": {
        "type": _H, "labels": (),
        "help": "host share of an engine cycle: end of serving.sync to "
                "end of the next serving.decode_chunk dispatch, while "
                "requests stay in flight"},
    "pt_serving_slot_occupancy": {
        "type": _G, "labels": (),
        "help": "in-flight slots after the latest admit/release"},
    "pt_serving_queue_depth": {
        "type": _G, "labels": (),
        "help": "requests queued behind the slot pool"},
    "pt_serving_admissions_total": {
        "type": _C, "labels": (),
        "help": "requests admitted into a slot (bucket prefill "
                "dispatched)"},
    "pt_serving_evictions_total": {
        "type": _C, "labels": ("reason",),
        "help": "slots freed by finish reason: eos | budget"},
    "pt_serving_decoded_tokens_total": {
        "type": _C, "labels": (),
        "help": "useful tokens streamed at chunk-boundary syncs"},
    "pt_serving_useful_tokens_per_sec": {
        "type": _G, "labels": (),
        "help": "useful-token throughput of the last run()"},
    "pt_serving_chunks_total": {
        "type": _C, "labels": (),
        "help": "compiled decode-chunk dispatches"},
    "pt_serving_moe_pairs_routed_total": {
        "type": _C, "labels": (),
        "help": "token-expert pairs the expert layers' routers chose "
                "(real tokens x experts per token x expert layers), "
                "summed on the device and read at the chunk's sync"},
    "pt_serving_moe_pairs_here_total": {
        "type": _C, "labels": (),
        "help": "of those pairs, the ones routed to an expert this "
                "chip holds (experts_held): the rows the grouped "
                "matmul computed"},
    "pt_serving_moe_experts_touched_total": {
        "type": _C, "labels": (),
        "help": "held experts with at least one row, summed over "
                "decode steps and expert layers (the weights a "
                "bandwidth-bound step had to read)"},
    "pt_serving_moe_decode_layer_steps_total": {
        "type": _C, "labels": (),
        "help": "decode steps x expert layers counted (the "
                "denominator of experts touched per step)"},
    "pt_serving_moe_max_expert_rows": {
        "type": _G, "labels": (),
        "help": "most rows one held expert got in one layer of one "
                "program (the skew a dropless layer absorbs)"},
    "pt_serving_prefills_total": {
        "type": _C, "labels": ("bucket",),
        "help": "compiled bucket prefill dispatches by bucket length"},
    "pt_serving_quant_bytes_saved": {
        "type": _G, "labels": (),
        "help": "resident weight bytes saved by the engine's quant_mode "
                "pass (quantized vs original dtype, scale planes "
                "counted against the win; host arithmetic over static "
                "shapes)"},
    # -- speculative decoding (inference/speculative.py) ------------------
    "pt_serving_spec_proposed_total": {
        "type": _C, "labels": (),
        "help": "draft tokens proposed to verification (gamma per "
                "participating slot-step)"},
    "pt_serving_spec_accepted_total": {
        "type": _C, "labels": (),
        "help": "draft tokens accepted and emitted (greedy match "
                "against the target's argmax)"},
    "pt_serving_spec_accept_len": {
        "type": _H, "labels": (),
        "help": "accepted drafts per verify step per slot (0..gamma; "
                "emitted tokens = this + 1)"},
    "pt_serving_spec_draft_chunks_total": {
        "type": _C, "labels": (),
        "help": "compiled draft-verify chunk dispatches (the spec "
                "engine's decode chunks)"},
    "pt_serving_spec_verify_steps_total": {
        "type": _C, "labels": (),
        "help": "batched gamma+1-wide target verify forwards that "
                "carried at least one active slot"},
    # -- serving fleet router (inference/router.py) -----------------------
    "pt_router_requests_total": {
        "type": _C, "labels": ("priority",),
        "help": "requests submitted to the fleet router, by priority "
                "class: interactive | standard | batch"},
    "pt_router_routed_total": {
        "type": _C, "labels": ("reason",),
        "help": "routing decisions by pick reason: affinity (prefix-"
                "digest match) | least_loaded (queue-depth x occupancy "
                "fallback) | rebalance (idle replica stole parked "
                "work)"},
    "pt_router_shed_total": {
        "type": _C, "labels": ("priority",),
        "help": "best-effort requests shed by SLO admission control "
                "(terminal callback with reason 'shed')"},
    "pt_router_queue_depth": {
        "type": _G, "labels": (),
        "help": "fleet-level queue depth after the latest dispatch gap "
                "(excludes per-replica queues)"},
    "pt_router_route_wait_ms": {
        "type": _H, "labels": (),
        "help": "submit (or requeue) -> replica-dispatch wait in the "
                "fleet queue (the `route` trace span's duration)"},
    "pt_router_replica_queue_depth": {
        "type": _G, "labels": ("replica",),
        "help": "per-replica engine queue depth at the latest dispatch "
                "gap (the least-loaded score's first component)"},
    "pt_router_replica_active": {
        "type": _G, "labels": ("replica",),
        "help": "per-replica in-flight slots at the latest dispatch "
                "gap (the least-loaded score's tie-breaker)"},
    "pt_router_replica_deaths_total": {
        "type": _C, "labels": (),
        "help": "replicas detected dead (worker crash / failpoint) and "
                "drained"},
    "pt_router_requeued_total": {
        "type": _C, "labels": (),
        "help": "requests drained off a dead or retired replica and "
                "requeued for re-routing (they resume by recompute)"},
    "pt_router_aged_total": {
        "type": _C, "labels": (),
        "help": "requests promoted at least one priority rank by anti-"
                "starvation aging while waiting in the fleet queue"},
    "pt_router_scale_hint": {
        "type": _G, "labels": (),
        "help": "latest autoscale recommendation: +1 scale up, -1 "
                "scale down, 0 steady (keyed on queue-depth and "
                "occupancy)"},
    # -- prefill/decode handoff (inference/handoff.py) --------------------
    "pt_handoff_transfers_total": {
        "type": _C, "labels": (),
        "help": "KV bundles that completed the full reserve -> import "
                "-> arm protocol (the decode slot armed without any "
                "suffix re-prefill)"},
    "pt_handoff_bytes_total": {
        "type": _C, "labels": (),
        "help": "payload bytes of successfully armed KV bundles "
                "(page buffers incl. int8 scale planes)"},
    "pt_handoff_retries_total": {
        "type": _C, "labels": (),
        "help": "retried handoff protocol attempts (jittered backoff "
                "under the reservation TTL, framework/retry.py)"},
    "pt_handoff_fallbacks_total": {
        "type": _C, "labels": ("reason",),
        "help": "requests degraded to local re-prefill on a decode "
                "replica, by terminal failure: prefill_replica_death | "
                "reserve_timeout | reserve_ttl_expired | "
                "decode_pool_pressure | decode_replica_death | "
                "no_decode_replica | no_prefill_replica | "
                "import_rejected (checksum/manifest)"},
    "pt_handoff_reserve_expired_total": {
        "type": _C, "labels": (),
        "help": "page reservations released by TTL expiry (the bundle "
                "never arrived — a dead prefill replica cannot leak "
                "its decode home's pool pages)"},
    "pt_handoff_transfer_ms": {
        "type": _H, "labels": (),
        "help": "launch -> slot-armed wall per successful handoff "
                "(reserve + stub prefill + export/verify/import)"},
    # -- paged KV cache (inference/kvcache.py) ----------------------------
    "pt_kvcache_pages_in_use": {
        "type": _G, "labels": (),
        "help": "physical KV pages currently referenced (slot page "
                "tables + prefix-cache entries); trash page excluded"},
    "pt_kvcache_resident_kv_bytes": {
        "type": _G, "labels": (),
        "help": "bytes of KV actually resident (pages in use x bytes "
                "per page across layers, incl. int8 scale planes) — "
                "scales with live tokens, not slots x max_seq_len"},
    "pt_kvcache_page_evictions_total": {
        "type": _C, "labels": (),
        "help": "pages freed by page-pressure preemption (requests "
                "requeued to resume by recompute)"},
    "pt_kvcache_prefix_hits_total": {
        "type": _C, "labels": (),
        "help": "admissions whose prompt matched a cached page-aligned "
                "prefix (shared pages mapped copy-on-write, prefill "
                "runs over the suffix only)"},
    "pt_kvcache_prefix_misses_total": {
        "type": _C, "labels": (),
        "help": "admissions that prefilled their whole prompt cold"},
    "pt_kvcache_prefix_saved_tokens_total": {
        "type": _C, "labels": (),
        "help": "prompt tokens NOT re-prefilled thanks to prefix-cache "
                "hits (prefill FLOPs saved is proportional)"},
    # -- flight recorder + SLO watchdog (observability/flight.py,
    #    observability/watch.py) -------------------------------------------
    "pt_watch_evals_total": {
        "type": _C, "labels": (),
        "help": "watch-rule evaluation sweeps (one per recorded flight "
                "sample; zero device cost by construction)"},
    "pt_watch_alerts_total": {
        "type": _C, "labels": ("rule",),
        "help": "watchdog rule trips by rule name — each one also "
                "emitted a guardian watch_alert event"},
    "pt_flight_samples": {
        "type": _G, "labels": (),
        "help": "flight-recorder rolling-window occupancy after the "
                "latest sample (bounded by the window size)"},
    "pt_flight_dumps_total": {
        "type": _C, "labels": (),
        "help": "forensic bundles written to PADDLE_FLIGHT_DIR "
                "(atomic tmp+rename, keep-last-K retention)"},
    "pt_flight_dump_ms": {
        "type": _H, "labels": (),
        "help": "wall time of one forensic bundle dump (runs on the "
                "dump thread, off the hot path)"},
    # -- compile telemetry (observability/compilestats.py) ----------------
    "pt_compile_compiles_total": {
        "type": _C, "labels": ("surface",),
        "help": "distinct-signature compiles per tracked jit surface "
                "(one AOT lower+compile each)"},
    "pt_compile_wall_ms": {
        "type": _H, "labels": ("surface",),
        "help": "wall time of each call that compiled: trace, lower, "
                "compile and launch (host work jax does anyway) plus "
                "the wrapper's analysis readout"},
    "pt_compile_flops": {
        "type": _G, "labels": ("surface",),
        "help": "analytical FLOPs of ONE dispatch from the lowering's "
                "cost_analysis (last compiled signature)"},
    "pt_compile_bytes_accessed": {
        "type": _G, "labels": ("surface",),
        "help": "analytical bytes accessed per dispatch from "
                "cost_analysis (last compiled signature)"},
    "pt_compile_memory_bytes": {
        "type": _G, "labels": ("surface",),
        "help": "executable memory footprint (argument + output + temp "
                "bytes from memory_analysis; last compiled signature)"},
    "pt_compile_retraces_total": {
        "type": _C, "labels": ("surface",),
        "help": "compiles past the surface's declared budget — each "
                "one also raised a guardian compile_retrace event"},
    "pt_compile_dispatch_total": {
        "type": _C, "labels": ("surface", "path"),
        "help": "calls of a tracked jit surface by path: 'fast' = jax's "
                "own dispatch knew the call and nothing was walked in "
                "Python, 'signature' = the call was new to it (a "
                "compile, as a rule), so the signature was walked"},
    "pt_compile_dispatch_ms": {
        "type": _H, "labels": ("surface",),
        "help": "measured wall time of ONE dispatch of this surface, "
                "recorded where a latency-clean measurement exists "
                "(bench steady-state loops) — the roofline join's "
                "measured half"},
    # -- kernel registry (ops/registry.py) --------------------------------
    "pt_kernel_selects_total": {
        "type": _C, "labels": ("kernel", "impl"),
        "help": "kernel-registry selections by implementation (one per "
                "dispatch decision: trace time for jitted surfaces, "
                "per call for eager dispatches); attention books "
                "impl=pallas_transposed beside pallas for a call the "
                "flash kernels take only through a transposed "
                "(B*H, S, D) copy (head size and per-shard head count "
                "outside the lane-tile rule, docs/kernels.md); "
                "kernel=paged_attention books the form that RUNS, "
                "once a layer of a compiled program: impl=pallas is "
                "the paged decode kernel engaged, impl=xla the gather "
                "path"},
    "pt_kernel_fallbacks_total": {
        "type": _C, "labels": ("kernel", "reason"),
        "help": "calls the platform policy routed to a Pallas impl but "
                "a kernel contract sent to the XLA path instead: "
                "mask | scale | dropout | cross-seq | short-seq | "
                "pad-noncausal | mask-large | unaligned-vocab | "
                "incubate-shape | head-dim (varlen_attention, "
                "paged_attention) | multi-token | int8-kv | "
                "partitioned | cache-dtype | kv-heads "
                "(paged_attention: a paged call that is not a decode "
                "step over a full-precision pool the kernel tiles) | "
                "fp8-unavailable (no float8_e4m3fn in this jax build; "
                "weights degraded to int8) | fp8-weight-only (fp8 "
                "always streams through the XLA weight-only path — "
                "no Pallas fp8 kernel by design)"},
    # -- HBM memory ledger (observability/memory.py) ----------------------
    "pt_memory_static_bytes": {
        "type": _G, "labels": ("surface", "kind"),
        "help": "compiled-executable footprint per jit surface from "
                "memory_analysis, by kind: argument | output | temp | "
                "generated_code | total (XLA:CPU under-reports — "
                "absent kinds are simply not booked)"},
    "pt_memory_budget_frac": {
        "type": _G, "labels": ("surface",),
        "help": "surface static total vs the configured device HBM "
                "envelope (PADDLE_HBM_BYTES); > 1.0 also raised the "
                "guardian memory_budget event"},
    "pt_memory_live_bytes": {
        "type": _G, "labels": ("pool",),
        "help": "live-buffer census bytes by pool: total (all "
                "jax.live_arrays) | kv_pages (registered page-pool "
                "device buffers) | other (total minus kv_pages); "
                "sampled only at existing sync points"},
    "pt_memory_live_buffers": {
        "type": _G, "labels": (),
        "help": "live device arrays counted by the latest census"},
    "pt_memory_kv_occupancy": {
        "type": _G, "labels": (),
        "help": "KV page occupancy across registered pools (pages in "
                "use / allocatable pages; trash page excluded)"},
    "pt_memory_kv_headroom_bytes": {
        "type": _G, "labels": (),
        "help": "bytes of free KV pages remaining across registered "
                "pools (free pages x page bytes)"},
    "pt_memory_steps_to_exhaustion": {
        "type": _G, "labels": (),
        "help": "linear-trend OOM forecast: censuses left until "
                "headroom hits zero at the current growth slope "
                "(-1 = no computable upward trend)"},
    # -- request tracing (observability/tracing.py) -----------------------
    "pt_trace_requests_total": {
        "type": _C, "labels": (),
        "help": "serving requests whose trace reached finish"},
    "pt_trace_spans_total": {
        "type": _C, "labels": ("phase",),
        "help": "request-trace spans booked, by lifecycle phase: "
                "queue_wait | prefill | decode | spec_decode | "
                "page_evict"},
    "pt_trace_tpot_ms": {
        "type": _H, "labels": (),
        "help": "time per output token after the first (decode-phase "
                "span time / (tokens - 1)), booked at request finish"},
    "pt_trace_dropped_spans_total": {
        "type": _C, "labels": (),
        "help": "request-trace spans dropped by ring overflow — the "
                "trace view under-reports while this grows (report "
                "--requests flags it)"},
    # -- collectives (distributed/collective.py) --------------------------
    "pt_collective_calls_total": {
        "type": _C, "labels": ("op",),
        "help": "collective API calls issued (inside a trace this "
                "counts tracings, not executions)"},
    "pt_collective_bytes_total": {
        "type": _C, "labels": ("op",),
        "help": "payload bytes of issued collectives (from static "
                "shape/dtype metadata — no readback)"},
    "pt_collective_latency_ms": {
        "type": _H, "labels": ("op",),
        "help": "host-blocking collectives only (barrier/wait under "
                "the watchdog); traced collectives have no host-"
                "observable latency"},
    "pt_collective_grad_buckets": {
        "type": _G, "labels": (),
        "help": "bucket count of the last grad_comm reducer build "
                "(distributed/grad_comm.py bucketed all-reduce plan)"},
    "pt_collective_overlap_fraction": {
        "type": _G, "labels": (),
        "help": "byte share of grad buckets whose all-reduce can hide "
                "under remaining backward compute (structural, from "
                "the bucket plan — everything but the final bucket)"},
    "pt_collective_wire_bytes_per_step": {
        "type": _G, "labels": (),
        "help": "analytical bytes one step's gradient reduction puts "
                "on the wire under the current grad_comm plan (static "
                "shapes + wire mode; roofline comm input)"},
    # -- TCPStore client (distributed/store.py) ---------------------------
    "pt_store_ops_total": {
        "type": _C, "labels": ("op",),
        "help": "store client operations: set | get | add | wait"},
    "pt_store_op_latency_ms": {
        "type": _H, "labels": ("op",),
        "help": "wall time per store op incl. connect/retry envelope"},
    "pt_store_retries_total": {
        "type": _C, "labels": (),
        "help": "Python-client reconnect/retry attempts (native client "
                "retries internally, uncounted)"},
    # -- dataloader (io/) -------------------------------------------------
    "pt_dataloader_queue_depth": {
        "type": _G, "labels": (),
        "help": "prefetch-queue depth observed at each consumer pop"},
    "pt_dataloader_wait_ms": {
        "type": _H, "labels": (),
        "help": "time the consumer blocked waiting for the next batch "
                "(producer slack; 0-ish means the pipeline keeps up)"},
    # -- checkpoint (distributed/checkpoint) ------------------------------
    "pt_checkpoint_save_ms": {
        "type": _H, "labels": (),
        "help": "save_state_dict D2H snapshot + shard write + metadata "
                "commit wall time"},
    "pt_checkpoint_load_ms": {
        "type": _H, "labels": (),
        "help": "load_state_dict wall time (one committed step dir)"},
    "pt_checkpoint_bytes_total": {
        "type": _C, "labels": ("direction",),
        "help": "checkpoint payload bytes by direction: save | load"},
    "pt_checkpoint_fallbacks_total": {
        "type": _C, "labels": ("kind",),
        "help": "step dirs skipped while resolving a root: torn "
                "(uncommitted debris) | corrupt (CRC/restore failure)"},
    "pt_checkpoint_reshard_total": {
        "type": _C, "labels": ("kind",),
        "help": "checkpoints crossing a topology change: load "
                "(manifest-aware restore onto a different mesh) | "
                "relaunch (launcher restart at the observed elastic "
                "member count)"},
    "pt_checkpoint_reshard_ms": {
        "type": _H, "labels": (),
        "help": "wall time of a manifest-aware load whose target "
                "topology differed from the saving one (reshard-on-"
                "restore cost)"},
}


def subsystems():
    """The registered ``pt_<subsystem>`` prefixes (lint scoping)."""
    return {n.split("_", 2)[1] for n in METRICS}
