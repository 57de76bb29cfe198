"""Central registry of every ``pathway_*`` metric family this process emits.

Observability drifts silently: a renamed series breaks dashboards without
breaking a single test.  Every emitter (operator stats, connectors, the
serving scheduler, breakers, the error log, tracing stage histograms,
freshness watermarks, XLA compile counters) declares its families here and
``tests/test_observability.py`` greps the tree for emitted ``pathway_*``
literals and fails on any series not declared — the lint that keeps the
README metric table honest across PRs.

This module is a dependency LEAF (stdlib only): ``flight_recorder.py``,
``monitoring.py`` and the xpack emitters all import it, so it must never
import back into the package.  The shared OpenMetrics helpers
(:func:`escape_label_value`, :class:`Histogram`) live here for the same
reason — one escaping implementation for every emitter instead of five
ad-hoc ``.replace()`` calls.
"""

from __future__ import annotations

__all__ = ["METRICS", "declared_metric_names", "escape_label_value", "Histogram"]


#: family name -> (type, help).  ``histogram`` families emit
#: ``_bucket``/``_sum``/``_count`` samples; everything else emits samples
#: under the family name itself.
METRICS: dict[str, tuple[str, str]] = {
    # engine / operator plane (internals/monitoring.py)
    "pathway_uptime_seconds": ("gauge", "seconds since the monitor started"),
    "pathway_current_timestamp": ("gauge", "engine frontier timestamp"),
    "pathway_operator_rows_total": ("counter", "rows emitted per operator"),
    "pathway_operator_busy_seconds": ("counter", "cumulative flush time per operator"),
    "pathway_operator_flush_ms": ("histogram", "per-operator flush latency"),
    # connector plane (internals/monitoring.py)
    "pathway_connector_messages_total": ("counter", "messages committed per connector"),
    "pathway_connector_finished": ("gauge", "1 once a finite connector closed"),
    "pathway_connector_scans_total": (
        "counter",
        "polls of a watched path per connector and lister (native|python)",
    ),
    "pathway_connector_files_total": (
        "counter",
        "files emitted per connector and the pass of a poll that found them "
        "(listing: a new name; verify: a known file that changed)",
    ),
    # serving scheduler (xpacks/llm/_scheduler.py)
    "pathway_scheduler_submitted_total": ("counter", "work items admitted"),
    "pathway_scheduler_completed_total": ("counter", "work items completed"),
    "pathway_scheduler_failed_total": ("counter", "work items failed"),
    "pathway_scheduler_shed_deadline_total": ("counter", "items shed past deadline"),
    "pathway_scheduler_shed_queue_total": ("counter", "admissions refused at max_queue"),
    "pathway_scheduler_batches_total": ("counter", "device-step batches executed"),
    "pathway_scheduler_multi_item_batches_total": ("counter", "batches with >1 item"),
    "pathway_scheduler_queue_depth": ("gauge", "current admission-queue depth"),
    "pathway_scheduler_queue_depth_max": ("gauge", "high-watermark queue depth"),
    "pathway_scheduler_batch_occupancy_max": ("gauge", "largest batch executed"),
    "pathway_scheduler_batch_occupancy_mean": ("gauge", "mean batch occupancy"),
    "pathway_scheduler_wait_ms": ("histogram", "queue wait before dispatch"),
    # unified device-tick runtime (pathway_tpu/runtime/executor.py) —
    # every series carries a qos label (interactive/llm_rerank/bulk_ingest)
    # except the tick-level families
    "pathway_runtime_submitted_total": ("counter", "work items admitted per QoS class"),
    "pathway_runtime_completed_total": ("counter", "work items completed per QoS class"),
    "pathway_runtime_failed_total": ("counter", "work items failed per QoS class"),
    "pathway_runtime_shed_deadline_total": (
        "counter",
        "items shed past deadline per QoS class",
    ),
    "pathway_runtime_admission_rejected_total": (
        "counter",
        "sheddable admissions refused at the class queue-depth target",
    ),
    "pathway_runtime_inline_total": (
        "counter",
        "re-entrant submits executed inline inside the running tick",
    ),
    "pathway_runtime_queue_depth": ("gauge", "current per-class queue depth"),
    "pathway_runtime_queue_depth_max": ("gauge", "high-watermark per-class queue depth"),
    "pathway_runtime_ticks_total": ("counter", "device ticks composed and executed"),
    "pathway_runtime_preemptions_total": (
        "counter",
        "ticks where interactive work displaced queued lower-class work",
    ),
    "pathway_runtime_wait_ms": ("histogram", "per-class queue wait before dispatch"),
    "pathway_runtime_tick_occupancy": ("histogram", "work items per device tick"),
    "pathway_runtime_tick_tokens": ("histogram", "estimated token mass per device tick"),
    "pathway_runtime_starvation_share": (
        "histogram",
        "bulk-ingest share of contended ticks (the starvation bound, observed)",
    ),
    # multi-chip serving mesh (pathway_tpu/parallel/index.py) — every
    # series carries an index label; shard_rows adds a shard label
    "pathway_mesh_devices": (
        "gauge",
        "devices the sharded KNN index's data axis spans",
    ),
    "pathway_mesh_shard_rows": (
        "gauge",
        "live rows per shard of a mesh-sharded index (row-balance observable)",
    ),
    "pathway_mesh_sharded_ticks_total": (
        "counter",
        "fused embed→search ticks answered by a mesh-sharded index",
    ),
    # circuit breakers (xpacks/llm/_breaker.py)
    "pathway_breaker_state": ("gauge", "0=closed 1=half_open 2=open"),
    "pathway_breaker_trips_total": ("counter", "closed/half_open -> open transitions"),
    "pathway_breaker_refused_total": ("counter", "calls refused while open"),
    "pathway_breaker_failures_total": ("counter", "failures recorded"),
    "pathway_breaker_successes_total": ("counter", "successes recorded"),
    # error log (internals/errors.py)
    "pathway_errors_total": ("counter", "failure-domain events per kind"),
    "pathway_errors_last_minute": ("gauge", "errors in the trailing 60 s"),
    # request tracing (internals/flight_recorder.py)
    "pathway_request_stage_ms": (
        "histogram",
        "stage latency: request stages (queue_wait / embed / search / serialize / "
        "total) and every flight_recorder.span(stage=...) of the ingest path "
        "(connector.scan, connector.verify, engine.flush, index.*, tick.*, embed.*) "
        "and the observations without a span (ingest.read_to_indexed and its "
        "seven segments, ingest.read_to_commit ... ingest.embedded_to_indexed, "
        "once per indexed engine timestamp and connector; connector.period, "
        "from one listing pass's start to the next one's)",
    ),
    "pathway_flight_recorder_spans_total": (
        "counter",
        "spans recorded into the in-process ring buffer",
    ),
    "pathway_trace_dropped_total": (
        "counter",
        "spans evicted from the flight-recorder ring before any read — "
        'nonzero means a "no slow spans found" answer may be a lie',
    ),
    # observability plane (pathway_tpu/observability/) — the unified HBM
    # ledger; every series carries a component label, shard optional
    "pathway_hbm_bytes": (
        "gauge",
        "device-resident bytes per registered allocation (component=, shard=)",
    ),
    "pathway_hbm_total_bytes": (
        "gauge",
        "sum of every ledger-attributed device allocation in this process",
    ),
    "pathway_hbm_unattributed_bytes": (
        "gauge",
        "device bytes_in_use minus the attributed total, emitted only while "
        "drift exceeds PATHWAY_HBM_DRIFT_FRAC (TPU reconcile)",
    ),
    # SLO engine (pathway_tpu/observability/slo.py) — endpoint label on
    # the histogram; burn gauges carry slo/objective/window labels
    "pathway_endpoint_latency_ms": (
        "histogram",
        "per-endpoint request latency with trace-id exemplars on buckets",
    ),
    "pathway_slo_burn_rate": (
        "gauge",
        "error-budget burn rate per SLO/objective/window (SRE workbook: "
        "both windows >= 14.4 means the budget is burning)",
    ),
    # end-to-end freshness (io/streaming.py read-time stamps through
    # internals/monitoring.py) — connector label
    "pathway_freshness_seconds": (
        "gauge",
        "connector read-time -> queryable lag, end to end per connector "
        "(the index-level freshness gauge is one stage of this)",
    ),
    # data freshness (internals/monitoring.py + stdlib/indexing/lowering.py)
    "pathway_index_freshness_seconds": (
        "gauge",
        "ingest -> queryable lag of the last index update, per index",
    ),
    # index quantization (pathway_tpu/ops/knn.py) — every series carries
    # an index label; dtype adds a dtype label
    "pathway_index_dtype": (
        "gauge",
        "resident storage dtype of each live KNN index (f32/bf16/int8)",
    ),
    "pathway_index_hbm_bytes": (
        "gauge",
        "resident device bytes per index (codes+scales+rescore ring when int8)",
    ),
    "pathway_index_rescore_depth": (
        "gauge",
        "stage-1 candidate funnel depth of the quantized rescore (0 = unquantized)",
    ),
    # tiered index (pathway_tpu/tiering/index.py) — every series carries
    # an index label; rows adds a tier label, migrations a direction label
    "pathway_tier_rows": (
        "gauge",
        "live rows per tier (hot = HBM-resident, cold = host-RAM) of each tiered index",
    ),
    "pathway_tier_migrations_total": (
        "counter",
        "online tier reassignments per direction (promote = cold→HBM, demote = HBM→cold)",
    ),
    "pathway_tier_probe_partitions": (
        "gauge",
        "cold partitions probed per query (the routing fan-out knob, observed config)",
    ),
    # XLA compilation (internals/flight_recorder.py, wrapped jit entry points)
    "pathway_xla_compile_total": (
        "counter",
        "XLA compilations per jit entry point (bucket_q/bucket_k pin: flat under serving)",
    ),
    # fused serving tick (ops/fused_serving.py) — per-stage device
    # dispatch counts on the serving search path; the fused megakernel's
    # ≤2-launches-per-tick pin is readable straight off the stage= split
    "pathway_serving_launches_total": (
        "counter",
        "serving-path device dispatches by stage (fused/prep/score/topk/rescore/wire)",
    ),
    # ingest plane (internals/flight_recorder.py accumulators fed by
    # models/encoder.py packed dispatch, xpacks/llm/_ingest.py pipeline,
    # stdlib/indexing/lowering.py index adds, models/tokenizer.py cache)
    "pathway_ingest_docs_total": (
        "counter",
        "documents embedded and applied to a live index",
    ),
    # /v1/inputs answers (xpacks/llm/vector_store.py inputs_answer)
    "pathway_inputs_rows_total": (
        "counter",
        "document metadata rows assembled into /v1/inputs answers",
    ),
    "pathway_embed_padding_efficiency": (
        "gauge",
        "real tokens / padded tokens across embed dispatches (1.0 = no padding waste)",
    ),
    "pathway_embed_intra_bucket_efficiency": (
        "gauge",
        "real tokens / row-layout tokens: token padding INSIDE buckets only "
        "(~0.906 packed-bucket, ~1.0 ragged)",
    ),
    # routed-expert counters of a forward with experts (ops/routed_experts.py
    # launch_counters, added up by flight_recorder.record_moe_launch)
    "pathway_moe_launches_total": (
        "counter", "launches of a forward with routed experts",
    ),
    "pathway_moe_routed_tokens_total": (
        "counter", "(token, expert) pairs routed to an expert held here, summed over "
                   "the routed layers",
    ),
    "pathway_moe_experts_touched_total": (
        "counter", "experts held here that got a token, summed over the routed layers",
    ),
    "pathway_moe_max_expert_tokens_sum": (
        "counter", "each routed layer's fullest expert, summed over layers and launches",
    ),
    "pathway_moe_max_expert_tokens": (
        "gauge", "the fullest expert of the last launch",
    ),
    # counted on the host a launch, by the implementation of the grouped
    # product the forward's program was traced with (ops/grouped_matmul.py)
    "pathway_moe_grouped_launches_total": (
        "counter", "launches of a forward with routed experts, by the grouped "
                   "product's implementation (impl=pallas|xla)",
    ),
    # launch counters of a forward with latent attention
    # (models/causal_moe_embedder.py _counters; they ride the array
    # flight_recorder.record_moe_launch adds up)
    "pathway_mla_launches_total": (
        "counter", "launches of a forward with latent attention",
    ),
    "pathway_mla_documents_total": (
        "counter", "documents (rows that hold a token) those launches carried",
    ),
    "pathway_mla_tokens_total": (
        "counter", "real tokens those launches carried",
    ),
    "pathway_mla_bucket_tokens_total": (
        "counter", "tokens of those launches' buckets, padding included",
    ),
    "pathway_mla_attention_pairs_total": (
        "counter", "(query, key) pairs the causal mask let through, L(L+1)/2 a document, "
                   "counted once a launch",
    ),
    # launch counters of a forward with conv layers (the gated short
    # convolution; models/causal_moe_embedder.py _counters; they ride the
    # array flight_recorder.record_moe_launch adds up)
    "pathway_conv_launches_total": (
        "counter", "launches of a forward with conv layers",
    ),
    "pathway_conv_documents_total": (
        "counter", "documents (rows that hold a token) those launches carried",
    ),
    "pathway_conv_tokens_total": (
        "counter", "real tokens those launches carried",
    ),
    "pathway_conv_bucket_tokens_total": (
        "counter", "tokens of those launches' buckets, padding included",
    ),
    # launch counters of a forward with state-space layers
    # (models/causal_hybrid_embedder.py, added up by
    # flight_recorder.record_ssm_launch)
    "pathway_ssm_launches_total": (
        "counter", "launches of a forward with state-space layers",
    ),
    "pathway_ssm_documents_total": (
        "counter", "documents (rows that hold a token) those launches carried",
    ),
    "pathway_ssm_tokens_total": (
        "counter", "real tokens those launches carried",
    ),
    "pathway_ssm_bucket_tokens_total": (
        "counter", "tokens of those launches' buckets, padding included",
    ),
    "pathway_attention_impl": (
        "gauge",
        "encoders built per attention implementation (flax/fused/pallas/ragged)",
    ),
    "pathway_tokenizer_cache_hits_total": (
        "counter",
        "tokenizer LRU memoization hits per encoder (dedup-heavy live streams)",
    ),
    "pathway_tokenizer_cache_misses_total": (
        "counter",
        "tokenizer LRU memoization misses per encoder",
    ),
    # serving query cache stack (xpacks/llm/_query_cache.py) — every
    # series carries a layer label (embed / result)
    "pathway_query_cache_hits_total": (
        "counter",
        "serving-cache hits per layer (embed = encoder skipped, result = whole query skipped)",
    ),
    "pathway_query_cache_misses_total": (
        "counter",
        "serving-cache misses per layer (includes watermark-invalidated entries)",
    ),
    "pathway_query_cache_stale_served_total": (
        "counter",
        "result-cache entries served inside the stale-while-revalidate window",
    ),
    "pathway_query_cache_evictions_total": (
        "counter",
        "LRU evictions per cache layer",
    ),
    "pathway_collab_embeds_total": (
        "counter",
        "queries embedded on host CPU by the WindVE collaborative path under queue pressure",
    ),
    # paged-KV continuous-batching decode (pathway_tpu/generation/)
    "pathway_decode_live_sequences": (
        "gauge",
        "sequences currently advancing per decode tick across live DecodeSessions",
    ),
    "pathway_decode_kv_blocks": (
        "gauge",
        "paged KV pool blocks per state (used / free) — the token-budget admission signal",
    ),
    "pathway_decode_tokens_total": (
        "counter",
        "tokens generated by the paged continuous-batching decode path",
    ),
    "pathway_decode_prefill_tokens_total": (
        "counter",
        "prompt tokens prefilled into paged KV blocks (ragged packed launches)",
    ),
    "pathway_decode_shed_total": (
        "counter",
        "decode requests shed (queue-depth backpressure or deadline passed while queued)",
    ),
    "pathway_decode_retired_total": (
        "counter",
        "sequences retired (EOS or max_new_tokens reached; blocks freed unless retained)",
    ),
    "pathway_decode_prefix_hit_blocks_total": (
        "counter",
        "KV blocks adopted from the content-addressed prefix index instead of prefilled",
    ),
    "pathway_decode_shared_blocks": (
        "gauge",
        "KV blocks currently referenced by two or more sequences (refcount >= 2)",
    ),
    "pathway_decode_cow_copies_total": (
        "counter",
        "copy-on-write block duplications before a write into a shared KV block",
    ),
    "pathway_decode_draft_proposed_total": (
        "counter",
        "speculative draft tokens proposed by host-side prompt-lookup drafting",
    ),
    "pathway_decode_draft_accepted_total": (
        "counter",
        "speculative draft tokens accepted by the multi-position verify launch",
    ),
    # -- generation-plane fault containment (ISSUE 18) --
    "pathway_decode_fault_retries_total": (
        "counter",
        "transient device-launch failures retried in place (PATHWAY_DECODE_FAULT_RETRIES)",
    ),
    "pathway_decode_fault_contained_total": (
        "counter",
        "launch failures contained to their own sequences (blast-radius isolation)",
    ),
    "pathway_decode_fault_replays_total": (
        "counter",
        "sequences resurrected by replay re-prefill after a fatal pool quarantine",
    ),
    "pathway_kv_pool_rebuilds_total": (
        "counter",
        "paged-KV pools quarantined and reallocated fresh after a FATAL device error",
    ),
    # -- replicated serving fleet (fleet/router.py /status) --
    "pathway_fleet_replicas": (
        "gauge",
        "replicas known to the fleet router by state (ready/draining/detached)",
    ),
    "pathway_fleet_requests_total": (
        "counter",
        "proxied serving requests by outcome (ok = some replica answered)",
    ),
    "pathway_fleet_failovers_total": (
        "counter",
        "dispatch attempts that moved to the next replica (503 or transport error)",
    ),
    "pathway_fleet_affinity_spills_total": (
        "counter",
        "queries routed off their consistent-hash owner because it was hot",
    ),
    "pathway_fleet_epoch_restarts_total": (
        "counter",
        "replica process-epoch changes observed (restart detected; history re-verified)",
    ),
    "pathway_fleet_ingest_batches_total": (
        "counter",
        "ingest batches fanned out to the fleet under a fresh watermark",
    ),
    "pathway_fleet_ingest_watermark": (
        "gauge",
        "per-replica ingest/queryable freshness watermark (convergence probe input)",
    ),
    "pathway_fleet_autoscale_total": (
        "counter",
        "autoscale actions taken by the burn-verdict controller (spawn/drain)",
    ),
    # -- per-launch decode telemetry (generation/engine.py launch guards) —
    # every series carries a kind label (prefill / decode_step / verify)
    "pathway_decode_launch_ms": (
        "histogram",
        "device-launch wall time per guarded generation launch (kind=)",
    ),
    "pathway_decode_batch_rows": (
        "histogram",
        "sequences riding each guarded generation launch (kind=)",
    ),
    # -- telemetry federation (observability/federation.py via the fleet
    # router's /status) — replica-labeled re-exposition plus aggregates
    "pathway_fleet_aggregate_total": (
        "counter",
        "fleet-wide sum of a counter family across live replicas "
        "(family= names the source family; restart-safe, never decreases)",
    ),
    "pathway_fleet_scrapes_total": (
        "counter",
        "replica /status scrapes completed by the federation plane",
    ),
    "pathway_fleet_scrape_errors_total": (
        "counter",
        "replica /status scrapes that failed (replica unreachable or "
        "exposition unparsable)",
    ),
    "pathway_fleet_slo_burn_rate": (
        "gauge",
        "fleet-level error-budget burn rate per endpoint/window, computed "
        "from the federated per-endpoint latency histograms",
    ),
    "pathway_fleet_slo_verdict": (
        "gauge",
        "fleet-level burn verdict per endpoint (0=ok 1=warn 2=burning)",
    ),
}


def declared_metric_names() -> set[str]:
    """All sample names the registry allows: family names plus the
    histogram suffixes."""
    names: set[str] = set()
    for family, (kind, _help) in METRICS.items():
        names.add(family)
        if kind == "histogram":
            names.update(
                {f"{family}_bucket", f"{family}_sum", f"{family}_count"}
            )
    return names


def escape_label_value(value: object) -> str:
    """Escape a label value per the OpenMetrics exposition format:
    backslash, double-quote and line feed must be escaped (in that order —
    escaping ``\\`` last would corrupt the other two)."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


class Histogram:
    """Fixed-bucket histogram with OpenMetrics rendering.

    NOT internally locked — every holder (StatsMonitor, the stage-metrics
    table in flight_recorder) already serializes observes under its own
    lock, and double-locking the hot path buys nothing.
    """

    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, buckets: tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # +1 for the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        self.sum += value
        self.count += 1
        for i, le in enumerate(self.buckets):
            if value <= le:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def openmetrics_lines(self, family: str, labels: str = "") -> list[str]:
        """``_bucket``/``_sum``/``_count`` samples (no ``# TYPE`` line —
        the caller declares the family once for all label sets)."""
        sep = "," if labels else ""
        lines = []
        cum = 0
        for le, n in zip((*self.buckets, float("inf")), self.counts):
            cum += n
            le_s = "+Inf" if le == float("inf") else f"{le:g}"
            lines.append(
                f'{family}_bucket{{{labels}{sep}le="{le_s}"}} {cum}'
            )
        brace = f"{{{labels}}}" if labels else ""
        lines.append(f"{family}_sum{brace} {self.sum:.3f}")
        lines.append(f"{family}_count{brace} {self.count}")
        return lines
