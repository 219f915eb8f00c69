"""Monitor-event tag schema registry for the serving/fleet namespaces
— and, since ISSUE 13, the observatory's JSONL time-series field names.

The monitor API is stringly typed (`write_events([(tag, value, step)])`),
which makes one bug class invisible: a silently typo'd tag publishes a
metric nobody's dashboard reads while the intended series goes flat.
This registry is the single source of truth for every `serving/*` and
`fleet/*` tag the package publishes — exact names for the fixed tags,
anchored regexes for the parameterized families (per-replica, per-pool)
— and a tier-1 test drives every publish path in the package and
asserts each emitted tag is registered (tests/test_tracing.py).

The observatory's per-tick samplers (`serving/observatory/metrics.py`)
have the same failure mode in their JSONL rows: a typo'd field name
ships a series nobody's tooling reads.  Their field names are
registered here too (`LOOP_TIMESERIES_FIELDS` /
`FLEET_TIMESERIES_FIELDS` / `TIMELINE_FIELDS` / `RECOMPILE_FIELDS`)
and the tier-1 gate in tests/test_observatory.py sweeps emitted rows
against `check_timeseries_fields`.

Adding a new tag or series field is a two-line change: emit it,
register it here.  Forgetting the second line fails the tier-1 gate,
which is the point.  `InMemoryMonitor(strict_schema=True)` applies the
tag check at write time for tests that want the failure at the
offending publish.
"""
from __future__ import annotations

import re
from typing import Iterable, List

__all__ = ["SERVING_TAGS", "FLEET_TAGS", "GRAMMAR_TAGS", "TAG_PATTERNS",
           "LOOP_TIMESERIES_FIELDS", "FLEET_TIMESERIES_FIELDS",
           "TIMELINE_FIELDS", "RECOMPILE_FIELDS",
           "is_registered", "unregistered", "check_tags",
           "unregistered_fields", "check_timeseries_fields"]

#: exact `serving/*` tags (`ServingTelemetry.publish`)
SERVING_TAGS = frozenset(
    # counters (ServingTelemetry.counters)
    ["serving/" + k for k in (
        "submitted", "admitted", "completed", "cancelled", "timed_out",
        "failed", "rejected_queue_full", "rejected_invalid",
        "prefix_hits", "prefix_misses", "drained_unserved",
        "rejected_draining", "evicted_in_flight", "spec_drafted",
        "spec_accepted", "handoff_parked",
        "sampled_on_device", "sampled_on_host",
        "steps_run_ahead", "steps_collected_at_once", "rows_overrun",
        "moe_picks", "moe_zero_picks", "moe_local_rows",
        "moe_busiest_rows", "moe_router_calls",
        "moe_router_tokens", "moe_group_hit_tokens",
        "moe_expert_weight_fetches", "moe_experts_reached",
        "moe_expert_items",
        # two-kind cache (a window + global stack): block x layer units
        # held and what one kind would hold, window-kind blocks handed
        # back, admissions refused by the kind that was short
        "kv_blocks_held", "kv_blocks_full_cache", "kv_window_released",
        "admit_blocked_by_kind_global", "admit_blocked_by_kind_window",
        # token streaming + SLO-aware preemption (ISSUE 15):
        # exactly-once delivery accounting and the swap-or-recompute
        # preemption lifecycle
        "tokens_streamed", "tokens_replayed", "streams_resumed",
        "preemptions", "kv_swapped_out", "kv_swapped_in",
        # multi-tenant QoS (serving/tenancy): submits shed at a
        # tenant's token-bucket rate limit
        "rejected_rate_limited",
        # structured generation (serving/structured): constrained
        # submits; draft tokens the grammar pre-filter truncated
        "grammar_requests", "grammar_drafts_filtered",
        # per-tenant KV quota: admissions deferred at the tenant cap
        "quota_deferred")]
    # per-step gauges
    + ["serving/" + k for k in (
        "queue_depth", "batch_occupancy", "prefill_tokens_step",
        "decode_tokens_step", "prefill_tokens_saved",
        "prefix_cached_blocks",
        # the host-clock log (utils/spans.py): seconds of garbage
        # collection inside serve steps; steps whose host time was a pause
        "gc_seconds", "long_steps",
        # host KV spill tier (serving/kv_tier.py): occupancy gauge +
        # demotion/promotion block and byte counters
        "host_cached_blocks", "kv_demoted_blocks",
        "kv_promoted_blocks", "kv_demoted_bytes",
        "kv_promoted_bytes",
        # paged multi-LoRA adapter pool (serving/tenancy/adapter_pool):
        # AdapterPool.stats() occupancy gauges + lifecycle counters
        "adapter_pool_blocks", "adapter_hbm_blocks",
        "adapter_host_max_blocks", "adapter_host_blocks",
        "adapter_resident", "adapter_spilled", "adapter_demotes",
        "adapter_promotes", "adapter_dropped")]
    # expert-paged MoE decode (serving/experts.ExpertPool.stats()):
    # residency gauges + router-census counters, published as the
    # serving/expert/* family
    + ["serving/expert/" + k for k in (
        "slots", "resident", "spilled", "pinned", "demotes",
        "promotes", "routed", "rerouted", "drop_rate",
        "load_imbalance")]
    # SLA percentiles ("itl" is the streaming inter-token latency)
    + [f"serving/{name}_{q}_s" for name in ("ttft", "tpot", "e2e",
                                            "tpot_burst", "itl")
       for q in ("p50", "p95")]
    # speculative decoding
    + ["serving/spec_acceptance_rate", "serving/spec_tokens_per_dispatch"]
    # step timeline profiler (serving/tracing.StepTimeline; "promote"
    # is the host-KV-tier promotion share of the admission window)
    + [f"serving/phase_{p}_s" for p in ("finalize", "admission",
                                        "promote", "prefill", "decode")])

#: exact `fleet/*` tags (`FleetTelemetry.publish`)
FLEET_TAGS = frozenset(
    [f"fleet/routed_{r}" for r in (
        "prefix", "least_loaded", "round_robin", "failover", "handoff")]
    + [f"fleet/health_{e}" for e in (
        "demoted_heartbeat", "demoted_error_burst", "promoted",
        "failovers", "scale_ups", "scale_downs")]
    + ["fleet/" + k for k in (
        "stale_view_corrections", "migrations", "migrated_blocks",
        "migrated_bytes", "migration_failures",
        "migration_backoff_skips", "failover_requeued",
        "failover_failed", "failover_cancelled", "snapshots_published",
        "handoffs", "handoff_blocks", "handoff_bytes",
        "handoff_cold_fallbacks", "handoff_failures", "handoff_expired",
        "fleet_prefill_tokens_saved", "fleet_spec_drafted",
        "fleet_spec_accepted", "prefix_hit_rate",
        "spec_acceptance_rate", "spec_tokens_per_dispatch")])

_POOL_KEYS = ("replicas", "completed", "handoff_parked", "ttft_p50_s",
              "ttft_p95_s", "tpot_p50_s", "tpot_p95_s",
              "tpot_burst_p95_s", "ttft_sla_violations",
              "tpot_sla_violations")

#: parameterized tag families, as fully-anchored regexes
TAG_PATTERNS = tuple(re.compile(p) for p in (
    # per-pool SLA splits (disaggregated serving)
    r"^fleet/pool_(prefill|decode|unified)/(%s)$" % "|".join(_POOL_KEYS),
    # per-replica gauges; disagg fleets insert the pool role segment
    r"^fleet/replica_\d+(/(prefill|decode|unified))?"
    r"/(queue_depth|batch_occupancy)$",
    # per-tenant counters (ServingTelemetry.TENANT_KEYS; tenant names
    # are caller-chosen, hence a pattern not an enumeration)
    r"^serving/tenant/[A-Za-z0-9_.-]+/(submitted|admitted|completed|"
    r"rejected_rate_limited|preempted|tokens|sla_ttft_violations|"
    r"quota_deferred)$",
))

#: exact `grammar/*` tags — the structured-generation automaton cache
#: (`serving/structured.AutomatonCache.stats()`, published live by
#: `ServingTelemetry.publish` when a grammar cache is wired)
GRAMMAR_TAGS = frozenset(
    "grammar/" + k for k in (
        "size", "capacity", "hits", "misses", "compiles", "evictions",
        "states", "bytes", "epoch"))


#: per-tick serve-loop time-series row fields
#: (`observatory.MetricsSampler.sample_loop`)
LOOP_TIMESERIES_FIELDS = frozenset((
    "step", "t", "queue_depth", "active_seqs", "parked", "free_slots",
    "free_blocks", "batch_occupancy", "prefill_tokens_step",
    "decode_tokens_step", "admitted_total", "completed_total",
    "rejected_queue_full_total", "sla_ttft_violations_total",
    "sla_tpot_violations_total", "recompiles", "prefix_cached_blocks",
    "host_cached_blocks", "spec_acceptance_rate"))

#: per-tick fleet time-series row fields
#: (`observatory.FleetMetricsSampler.sample_fleet`)
FLEET_TIMESERIES_FIELDS = frozenset((
    "step", "t", "replicas_live", "queue_depth_total", "active_total",
    "parked_total", "free_blocks_total", "load_mean", "load_max",
    "routed_total", "handoffs_total", "failovers_total",
    "completed_total", "pool_prefill_load", "pool_decode_load",
    "pool_unified_load"))

#: step-timeline ring row fields (`serving.tracing.StepTimeline`)
TIMELINE_FIELDS = frozenset((
    "step", "finalize_s", "admission_s", "promote_s", "prefill_s",
    "decode_s", "admitted", "finished", "prefill_tokens",
    "decode_tokens", "queue_depth", "free_blocks"))

#: recompile flight-recorder ring row fields
#: (`observatory.RecompileFlightRecorder`)
RECOMPILE_FIELDS = frozenset(("t", "event", "duration_s"))

_FIELD_REGISTRIES = {
    "loop": LOOP_TIMESERIES_FIELDS,
    "fleet": FLEET_TIMESERIES_FIELDS,
    "timeline": TIMELINE_FIELDS,
    "recompile": RECOMPILE_FIELDS,
}


def unregistered_fields(fields: Iterable[str],
                        kind: str = "loop") -> List[str]:
    """Time-series field names not registered for ring `kind` (one of
    'loop', 'fleet', 'timeline', 'recompile'), first-seen order.
    Underscore-prefixed keys pass free — the JSONL export's trailing
    meta row uses them exclusively, so sweeping a whole `to_jsonl`
    file's keys through here needs no row filtering."""
    if kind not in _FIELD_REGISTRIES:
        raise ValueError(
            f"unknown time-series kind {kind!r} (one of "
            f"{sorted(_FIELD_REGISTRIES)})")
    allowed = _FIELD_REGISTRIES[kind]
    out: List[str] = []
    seen = set()
    for f in fields:
        if f in seen or f.startswith("_"):
            continue
        seen.add(f)
        if f not in allowed:
            out.append(f)
    return out


def check_timeseries_fields(fields: Iterable[str],
                            kind: str = "loop") -> None:
    """Raise ValueError naming every unregistered series field."""
    bad = unregistered_fields(fields, kind)
    if bad:
        raise ValueError(
            f"unregistered {kind} time-series field(s) {bad}: every "
            f"field a sampler emits must be declared in "
            f"deepspeed_tpu/monitor/schema.py (the silent-typo guard, "
            f"extended to the JSONL series)")


def is_registered(tag: str) -> bool:
    """True when `tag` is a registered serving/fleet/grammar tag — or
    outside those namespaces entirely (the registry only governs its
    own)."""
    if not (tag.startswith("serving/") or tag.startswith("fleet/")
            or tag.startswith("grammar/")):
        return True
    if tag in SERVING_TAGS or tag in FLEET_TAGS or tag in GRAMMAR_TAGS:
        return True
    return any(p.match(tag) for p in TAG_PATTERNS)


def unregistered(tags: Iterable[str]) -> List[str]:
    """The serving/fleet tags in `tags` the registry does not know, in
    first-seen order (deduplicated)."""
    out: List[str] = []
    seen = set()
    for tag in tags:
        if tag in seen:
            continue
        seen.add(tag)
        if not is_registered(tag):
            out.append(tag)
    return out


def check_tags(tags: Iterable[str]) -> None:
    """Raise ValueError naming every unregistered serving/fleet tag."""
    bad = unregistered(tags)
    if bad:
        raise ValueError(
            f"unregistered monitor tag(s) {bad}: every tag in the "
            f"serving and fleet namespaces must be declared in "
            f"deepspeed_tpu/monitor/schema.py (the silent-typo guard)")
