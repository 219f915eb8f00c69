"""Serving telemetry: per-request SLAs + per-step gauges.

Reference: the FastGen benchmarking methodology
(blogs/deepspeed-fastgen/README.md — throughput at fixed load, TTFT /
per-token latency percentiles) and the ZeRO++ discipline of measuring
the quantities a design claims to control instead of inferring them.

Everything is recorded host-side from the serve loop's clock, so the
numbers include queue wait and host scheduling — what a client actually
experiences — and fan out through the existing `monitor.MonitorMaster`
sink API (`write_events([(tag, value, step)])`).
"""
from __future__ import annotations

import gc
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import spans
from .request import Request, RequestState

__all__ = ["ServingTelemetry", "FleetTelemetry"]


def _prometheus_emitter(lines: List[str]):
    """A line emitter for the Prometheus text exposition format that
    writes each metric family's `# TYPE` header exactly once, however
    many labeled series the family carries (the format requires it)."""
    typed: set = set()

    def emit(name: str, value, kind: str = "gauge",
             labels: str = "") -> None:
        if value is None:
            return
        if name not in typed:
            typed.add(name)
            lines.append(f"# TYPE {name} {kind}")
        lines.append(f"{name}{labels} {float(value):g}")

    return emit


class ServingTelemetry:
    """Counters, per-request SLA samples, and per-step gauges."""

    def __init__(self, monitor=None, monitor_interval_steps: int = 0):
        """`monitor`: any object with `write_events([(tag, value, step)])`
        (e.g. `monitor.MonitorMaster` or `InMemoryMonitor`).  Events are
        published every `monitor_interval_steps` serve steps (0 = only on
        explicit `publish()`)."""
        self.monitor = monitor
        self.monitor_interval_steps = monitor_interval_steps
        self.counters: Dict[str, int] = {
            "submitted": 0, "admitted": 0, "completed": 0,
            "cancelled": 0, "timed_out": 0, "failed": 0,
            "rejected_queue_full": 0,
            "rejected_invalid": 0, "prefix_hits": 0, "prefix_misses": 0,
            # multi-tenant QoS (serving/tenancy/qos.py): submits shed
            # at a tenant's token-bucket rate limit
            "rejected_rate_limited": 0,
            "drained_unserved": 0, "rejected_draining": 0,
            "evicted_in_flight": 0,
            # speculative decoding (serving/speculative.py): draft
            # tokens proposed / accepted across verify dispatches
            # (rejected = drafted - accepted)
            "spec_drafted": 0, "spec_accepted": 0,
            # disaggregated serving (serving/fleet/disagg): requests
            # this PREFILL-role replica ran to prompt completion and
            # parked for the cross-pool handoff
            "handoff_parked": 0,
            # per-step path (serving/server.py step 5): tokens taken
            # from the engine's on-device argmax, and tokens sampled on
            # the host from a fetched logits row (temperature > 0,
            # grammar-masked rows, engines that return host rows)
            "sampled_on_device": 0, "sampled_on_host": 0,
            # per-step path, one of the two per serve step that launched
            # engine work: its tokens left uncollected on the device
            # until the next step has been dispatched, or collected
            # before the step returned; and rows whose token was dropped
            # when collected, their sequence flushed since the dispatch
            # (`engine.collect`: the request had ended or was preempted),
            # plus the rows of a step dropped whole (`drop_pending`)
            "steps_run_ahead": 0, "steps_collected_at_once": 0,
            "rows_overrun": 0,
            # latent MoE stacks (inference/v2/expert_ffn.count_names),
            # drained from the device every COUNT_DRAIN_STEPS serve steps:
            # top-k picks, those on identity experts, those on the
            # experts held here, the busiest local expert's rows
            # (summed over router calls), router calls; under a router
            # with expert groups also the valid tokens it scored (summed
            # over layers) and those whose kept groups include a group
            # this chip holds experts of; where the grouped matmuls are
            # the kernel, the expert-weight fetches its grid made, the
            # experts its passes reached and its lists' live (expert, row
            # tile) items: items over reached is the grid steps a reached
            # expert's weights stay for (1 at a few rows an expert; about
            # one more a segment where segments lie end to end than where
            # each starts on a tile edge)
            "moe_picks": 0, "moe_zero_picks": 0, "moe_local_rows": 0,
            "moe_busiest_rows": 0, "moe_router_calls": 0,
            "moe_router_tokens": 0, "moe_group_hit_tokens": 0,
            "moe_expert_weight_fetches": 0, "moe_experts_reached": 0,
            "moe_expert_items": 0,
            # two-kind cache (a window + global stack): summed over decode
            # steps, in block x layer units, what the step's rows held of
            # both kinds, what one kind over all layers would have held
            # for them, and the window-kind blocks handed back since the
            # step before; admissions refused for want of blocks, by the
            # kind that was short
            "kv_blocks_held": 0, "kv_blocks_full_cache": 0,
            "kv_window_released": 0, "admit_blocked_by_kind_global": 0,
            "admit_blocked_by_kind_window": 0,
            # token streaming (serving/streaming.py): tokens delivered
            # through request streams, tokens regenerated after a
            # failover and suppressed as verified replay (exactly-once
            # accounting), and streams that resumed emission past a
            # non-empty log (failover replay or preemption resume)
            "tokens_streamed": 0, "tokens_replayed": 0,
            "streams_resumed": 0,
            # SLO-aware preemption (ServeLoop._preempt_for_admission):
            # victims preempted; live KV blocks swapped arena -> host
            # at preemption and promoted host -> arena at resume
            "preemptions": 0, "kv_swapped_out": 0, "kv_swapped_in": 0,
            # structured generation (serving/structured): constrained
            # submits accepted; draft tokens the grammar pre-filter
            # truncated before verify (filter_draft)
            "grammar_requests": 0, "grammar_drafts_filtered": 0,
            # per-tenant KV quota (tenancy.kv_block_quota): admission
            # attempts deferred because the tenant's active reservations
            # were at their cap (capacity was NOT the blocker)
            "quota_deferred": 0,
        }
        # the host-clock log of utils/spans.py (`record_host_step`):
        # seconds this loop's serve steps spent in garbage collections,
        # and steps whose host time (duration minus the wait inside
        # `engine.fetch`) reached `spans.LONG_SPAN_NS`: a pause.  Measured
        # times, so apart from `counters`, which repeat run for run
        self.gc_seconds = 0.0
        self.long_steps = 0
        self.longest_step_s = self.longest_host_step_s = 0.0
        # the process's collections by generation when this loop began
        self._gc_collections0 = [g["collections"] for g in gc.get_stats()]
        # REQUEST-dispatch shares: one count per request per verify
        # dispatch it rode (a 16-row dispatch adds 16), with the tokens
        # that request gained.  spec_tokens_per_dispatch is therefore
        # the effective tokens A REQUEST advances per verify dispatch —
        # the per-stream number speculation exists to raise above 1 —
        # not a compiled-program launch count.
        self.spec_dispatches = 0
        self.spec_emitted = 0
        # prompt tokens whose prefill was skipped via shared prefix KV
        self.prefill_tokens_saved = 0
        # latest shared-block occupancy of the prefix cache (None when
        # the cache is off)
        self.prefix_cached_blocks: Optional[int] = None
        # latest host KV-tier stats dict (HostKVTier.stats(): occupancy
        # gauge + demotion/promotion block and byte counters; None when
        # the tier is off — the off path publishes nothing new)
        self.host_tier: Optional[Dict[str, int]] = None
        # multi-tenant accounting (serving/tenancy): per-tenant counter
        # rows, populated only when the serve loop enables
        # `track_tenants` (tenancy on) — the off path keeps summary(),
        # publish(), and prometheus_text() byte-identical
        self.track_tenants = False
        self.tenants: Dict[str, Dict[str, int]] = {}
        # latest AdapterPool.stats() dict (occupancy gauges +
        # demote/promote/drop counters; None when no pool is configured)
        self.adapter_pool: Optional[Dict[str, int]] = None
        # latest ExpertPool.stats() dict (expert-paged MoE decode,
        # serving/experts.py: residency gauges + census counters; None
        # when paging is off — the off path publishes nothing new)
        self.expert_pool: Optional[Dict[str, float]] = None
        # the serve loop's compiled-automaton cache (serving/structured
        # AutomatonCache), wired by ServeLoop when structured generation
        # is configured — publish() reads .stats() live so grammar/*
        # tags track the cache without per-step copying; None keeps
        # summary/publish/prometheus byte-identical (off-path parity)
        self.grammar_cache = None
        # trace entries dropped at the per-request caps, accumulated as
        # traced requests FINISH (the trace rides the Request, so
        # finish is where its drop count becomes final) — surfaced in
        # prometheus_text alongside the monitor's dropped_events, so a
        # truncated observation is a visible number, not a silent gap
        self.trace_dropped_entries = 0
        # per-request SLA samples (seconds), appended at finish
        self.ttft: List[float] = []
        self.tpot: List[float] = []
        self.e2e: List[float] = []
        self.tokens_out: List[int] = []
        # per-replica SLA targets + INCREMENTAL violation counters
        # (bumped at record time): the autoscaler's SLA-pressure signal
        # reads these — O(1) per finish and monotonic per replica, so
        # per-tick deltas survive replica retirement, unlike re-counting
        # the pooled sample lists.  Targets are propagated by the fleet
        # router from DisaggConfig; None = never counted.
        self.sla_ttft_target_s: Optional[float] = None
        self.sla_tpot_target_s: Optional[float] = None
        self.sla_ttft_violations = 0
        self.sla_tpot_violations = 0
        # per-burst decode observations (wall seconds, tokens covered):
        # under burst serving ONE host observation covers N tokens, so
        # honest per-token percentiles must weight each sample by the
        # tokens it covers — a lone slow 1-token tail burst must not
        # count the same as a 32-token burst (see _pct_weighted)
        self.burst_obs: List[tuple] = []
        # inter-token-latency observations (wall seconds between
        # consecutive STREAM emissions of one request, tokens the
        # emission carried): what a streaming consumer actually waits
        # between tokens — queue stalls, preemption gaps, and failover
        # replay windows included, which tpot (finish-time mean) hides.
        # Token-weighted like burst_obs; empty with streaming off.
        self.itl_obs: List[tuple] = []
        # per-step gauges (latest values; history kept for occupancy math)
        self.steps = 0
        self.queue_depth = 0
        self.batch_occupancy = 0.0
        self.prefill_tokens_step = 0
        self.decode_tokens_step = 0
        self._occupancy_sum = 0.0
        # step timeline profiler (serving/tracing.StepTimeline), wired
        # by ServeLoop when `ServingConfig.tracing.step_timeline` > 0;
        # None = profiler off (summary/publish skip it entirely)
        self.timeline = None

    # -- recording --------------------------------------------------------
    def count(self, key: str, n: int = 1) -> None:
        self.counters[key] += n

    #: the per-tenant counter keys `count_tenant` accepts — a fixed
    #: vocabulary so the monitor schema can register the tag family
    TENANT_KEYS = ("submitted", "admitted", "completed",
                   "rejected_rate_limited", "preempted", "tokens",
                   "sla_ttft_violations", "quota_deferred")

    def count_tenant(self, tenant: str, key: str, n: int = 1) -> None:
        """Bump one tenant's counter row (creating the row on first
        touch).  Loud on unknown keys — a typo'd key would otherwise
        mint an unregistered monitor tag downstream."""
        if key not in self.TENANT_KEYS:
            raise ValueError(
                f"unknown tenant counter {key!r} (one of "
                f"{self.TENANT_KEYS})")
        row = self.tenants.setdefault(
            tenant, {k: 0 for k in self.TENANT_KEYS})
        row[key] += n

    def record_host_step(self, rec: spans.StepRecord) -> None:
        """What a closed `serve.step` span left on the host-clock log."""
        host_ns = rec.duration - rec.wait
        self.gc_seconds += rec.gc * 1e-9
        if host_ns >= spans.LONG_SPAN_NS:
            self.long_steps += 1
        self.longest_step_s = max(self.longest_step_s, rec.duration * 1e-9)
        self.longest_host_step_s = max(self.longest_host_step_s,
                                       host_ns * 1e-9)

    def host_summary(self) -> Dict[str, Any]:
        """`summary()["host"]`: this loop's steps on the host-clock log,
        beside two things that are the process's: collections by
        generation since this loop began, and the longest record of each
        of the five span names with the longest records in the long-span
        ring (set-up's builds and compiling dispatches, long fetches,
        collections: docs/OBSERVABILITY.md says how to read a pause)."""
        longest: Dict[str, spans.LongRecord] = {}
        for rec in list(spans.long_spans()):
            if (rec.name not in longest
                    or rec.duration > longest[rec.name].duration):
                longest[rec.name] = rec
        top = sorted(longest.values(), key=lambda r: -r.duration)[:5]
        return {
            "gc_s": self.gc_seconds, "long_steps": self.long_steps,
            "gc_collections": [g["collections"] - g0 for g, g0 in zip(
                gc.get_stats(), self._gc_collections0)],
            "longest_step_s": self.longest_step_s,
            "longest_host_step_s": self.longest_host_step_s,
            "long_spans": [{"name": r.name, "parent": r.parent,
                            "step": r.step, "seconds": r.duration * 1e-9,
                            **r.attrs} for r in top]}

    def record_finish(self, req: Request) -> None:
        if req.state is RequestState.DONE:
            self.counters["completed"] += 1
        elif req.state is RequestState.CANCELLED:
            self.counters["cancelled"] += 1
        elif req.state is RequestState.TIMED_OUT:
            self.counters["timed_out"] += 1
        elif req.state is RequestState.FAILED:
            self.counters["failed"] += 1
        trace = getattr(req, "trace", None)
        if trace is not None and trace.dropped:
            self.trace_dropped_entries += trace.dropped
        if self.track_tenants:
            if req.state is RequestState.DONE:
                self.count_tenant(req.tenant, "completed")
            self.count_tenant(req.tenant, "tokens", len(req.generated))
        if req.ttft is not None:
            self.ttft.append(req.ttft)
            if (self.sla_ttft_target_s is not None
                    and req.ttft > self.sla_ttft_target_s):
                self.sla_ttft_violations += 1
                if self.track_tenants:
                    self.count_tenant(req.tenant, "sla_ttft_violations")
        if req.tpot is not None:
            self.tpot.append(req.tpot)
            if (self.sla_tpot_target_s is not None
                    and req.tpot > self.sla_tpot_target_s):
                self.sla_tpot_violations += 1
        if req.e2e_latency is not None and req.state is RequestState.DONE:
            self.e2e.append(req.e2e_latency)
            self.tokens_out.append(len(req.generated))

    def record_burst(self, wall_s: float, n_tokens: int) -> None:
        """One burst-decode host observation: `n_tokens` generated across
        the batch in `wall_s` of wall clock (the whole compiled burst —
        queue wait excluded, dispatch included, which is what a client's
        inter-token gap is made of under burst serving)."""
        if n_tokens > 0:
            self.burst_obs.append((wall_s, int(n_tokens)))

    def record_itl(self, wall_s: float, n_tokens: int) -> None:
        """One stream-emission gap: `n_tokens` arrived on a request's
        token stream `wall_s` serve-clock seconds after its previous
        emission (first emissions carry no gap and are not recorded)."""
        if n_tokens > 0:
            self.itl_obs.append((wall_s, int(n_tokens)))

    def record_spec(self, drafted: int, accepted: int,
                    emitted: int) -> None:
        """One REQUEST's share of a draft-and-verify dispatch: `drafted`
        tokens proposed, `accepted` of them adopted, `emitted` tokens
        delivered (accepted + the bonus/replacement token, after
        EOS-free host truncation at the lease cap).  Called once per
        request per verify dispatch it participates in."""
        self.counters["spec_drafted"] += int(drafted)
        self.counters["spec_accepted"] += int(accepted)
        self.spec_dispatches += 1
        self.spec_emitted += int(emitted)

    def record_prefix(self, covered_tokens: int) -> None:
        """One admitted request's prefix-cache outcome: `covered_tokens`
        of its prompt attached as shared KV (0 = miss)."""
        if covered_tokens > 0:
            self.counters["prefix_hits"] += 1
            self.prefill_tokens_saved += covered_tokens
        else:
            self.counters["prefix_misses"] += 1

    def record_step(self, queue_depth: int, live_seqs: int, max_seqs: int,
                    prefill_tokens: int, decode_tokens: int,
                    prefix_cached_blocks: Optional[int] = None,
                    host_tier: Optional[Dict[str, int]] = None,
                    adapter_pool: Optional[Dict[str, int]] = None,
                    expert_pool: Optional[Dict[str, float]] = None) -> None:
        self.steps += 1
        if prefix_cached_blocks is not None:
            self.prefix_cached_blocks = prefix_cached_blocks
        if host_tier is not None:
            self.host_tier = host_tier
        if adapter_pool is not None:
            self.adapter_pool = adapter_pool
        if expert_pool is not None:
            self.expert_pool = expert_pool
        self.queue_depth = queue_depth
        self.batch_occupancy = live_seqs / max_seqs if max_seqs else 0.0
        self._occupancy_sum += self.batch_occupancy
        self.prefill_tokens_step = prefill_tokens
        self.decode_tokens_step = decode_tokens
        if (self.monitor is not None and self.monitor_interval_steps
                and self.steps % self.monitor_interval_steps == 0):
            self.publish()

    # -- aggregation ------------------------------------------------------
    @staticmethod
    def _pct(samples: List[float], q: float) -> Optional[float]:
        if not samples:
            return None
        arr = np.asarray(samples, np.float64)  # dstpu: noqa[DST001] samples are host floats appended by record_finish, never device arrays
        return float(np.percentile(arr, q))

    @staticmethod
    def _pct_weighted(samples: List[tuple], q: float) -> Optional[float]:
        """Token-weighted percentile of per-token times from (wall_s,
        n_tokens) burst observations: each observation contributes its
        per-token mean wall_s/n, weighted by the n tokens it covers, so
        percentiles stay honest when one observation spans a whole
        burst."""
        if not samples:
            return None
        per_tok = np.asarray([w / n for w, n in samples], np.float64)
        weights = np.asarray([n for _, n in samples], np.float64)
        order = np.argsort(per_tok)
        per_tok, weights = per_tok[order], weights[order]
        cum = np.cumsum(weights)
        return float(per_tok[np.searchsorted(cum, q / 100.0 * cum[-1],
                                             side="left")])

    def summary(self, elapsed_s: Optional[float] = None) -> Dict[str, Any]:
        """Aggregate snapshot.  With `elapsed_s`, adds goodput: generated
        tokens of requests that COMPLETED (met their deadline; timed-out /
        cancelled work counts as waste, the FastGen goodput definition)
        per second."""
        out: Dict[str, Any] = dict(self.counters)
        out.update(
            steps=self.steps,
            queue_depth=self.queue_depth,
            batch_occupancy_mean=(self._occupancy_sum / self.steps
                                  if self.steps else 0.0),
            ttft_p50_s=self._pct(self.ttft, 50),
            ttft_p95_s=self._pct(self.ttft, 95),
            tpot_p50_s=self._pct(self.tpot, 50),
            tpot_p95_s=self._pct(self.tpot, 95),
            e2e_p50_s=self._pct(self.e2e, 50),
            e2e_p95_s=self._pct(self.e2e, 95),
            # burst-mode inter-token percentiles (token-weighted; None
            # outside burst serving)
            tpot_burst_p50_s=self._pct_weighted(self.burst_obs, 50),
            tpot_burst_p95_s=self._pct_weighted(self.burst_obs, 95),
            burst_tokens_mean=(
                float(np.mean([n for _, n in self.burst_obs]))
                if self.burst_obs else None),
            # streaming inter-token latency (token-weighted; None with
            # streaming off or before any second emission)
            itl_p50_s=self._pct_weighted(self.itl_obs, 50),
            itl_p95_s=self._pct_weighted(self.itl_obs, 95),
            # prefix-cache reuse (None hit rate when no request was ever
            # eligible, i.e. the cache is off)
            prefix_hit_rate=(
                self.counters["prefix_hits"]
                / (self.counters["prefix_hits"]
                   + self.counters["prefix_misses"])
                if (self.counters["prefix_hits"]
                    + self.counters["prefix_misses"]) else None),
            prefill_tokens_saved=self.prefill_tokens_saved,
            prefix_cached_blocks=self.prefix_cached_blocks,
            # host KV tier (None occupancy when the tier is off)
            host_cached_blocks=(self.host_tier["host_cached_blocks"]
                                if self.host_tier is not None else None),
            kv_demoted_blocks=(self.host_tier["kv_demoted_blocks"]
                               if self.host_tier is not None else None),
            kv_promoted_blocks=(self.host_tier["kv_promoted_blocks"]
                                if self.host_tier is not None else None),
            kv_demoted_bytes=(self.host_tier["kv_demoted_bytes"]
                              if self.host_tier is not None else None),
            kv_promoted_bytes=(self.host_tier["kv_promoted_bytes"]
                               if self.host_tier is not None else None),
            # speculative decoding (None when no verify dispatch ran,
            # i.e. speculation is off)
            spec_rejected=(self.counters["spec_drafted"]
                           - self.counters["spec_accepted"]),
            spec_acceptance_rate=(
                self.counters["spec_accepted"]
                / self.counters["spec_drafted"]
                if self.counters["spec_drafted"] else None),
            spec_tokens_per_dispatch=(
                self.spec_emitted / self.spec_dispatches
                if self.spec_dispatches else None),
            spec_dispatches=self.spec_dispatches,
        )
        if elapsed_s is not None and elapsed_s > 0:
            out["goodput_tok_s"] = sum(self.tokens_out) / elapsed_s
        out["host"] = self.host_summary()
        if self.timeline is not None:
            out["step_phases"] = self.timeline.aggregates()
        # multi-tenant view: only present when tenancy produced rows /
        # a pool reported stats — the single-tenant summary dict keeps
        # its exact pre-tenancy key set (parity)
        if self.tenants:
            out["tenants"] = {t: dict(row)
                              for t, row in sorted(self.tenants.items())}
        if self.adapter_pool is not None:
            out["adapter_pool"] = dict(self.adapter_pool)
        if self.expert_pool is not None:
            out["expert_pool"] = dict(self.expert_pool)
        if self.grammar_cache is not None:
            out["grammar_cache"] = self.grammar_cache.stats()
        return out

    def publish(self) -> None:
        """Fan the current state out through the monitor sinks."""
        if self.monitor is None:
            return
        gauges = [
            ("serving/queue_depth", self.queue_depth),
            ("serving/batch_occupancy", self.batch_occupancy),
            ("serving/prefill_tokens_step", self.prefill_tokens_step),
            ("serving/decode_tokens_step", self.decode_tokens_step),
            ("serving/prefill_tokens_saved", self.prefill_tokens_saved),
            ("serving/gc_seconds", self.gc_seconds),
            ("serving/long_steps", self.long_steps),
        ]
        if self.prefix_cached_blocks is not None:
            gauges.append(("serving/prefix_cached_blocks",
                           self.prefix_cached_blocks))
        if self.host_tier is not None:
            gauges.append(("serving/host_cached_blocks",
                           self.host_tier["host_cached_blocks"]))
            for k in ("kv_demoted_blocks", "kv_promoted_blocks",
                      "kv_demoted_bytes", "kv_promoted_bytes"):
                gauges.append((f"serving/{k}", self.host_tier[k]))
        if self.adapter_pool is not None:
            for k, v in self.adapter_pool.items():
                gauges.append((f"serving/{k}", v))
        if self.expert_pool is not None:
            # ExpertPool.stats() keys are "expert_<name>"; the tag
            # family is serving/expert/<name> (registered in
            # monitor/schema.py SERVING_TAGS)
            for k, v in self.expert_pool.items():
                gauges.append((f"serving/expert/{k[len('expert_'):]}", v))
        if self.grammar_cache is not None:
            for k, v in self.grammar_cache.stats().items():
                gauges.append((f"grammar/{k}", v))
        for t, row in sorted(self.tenants.items()):
            for k, v in row.items():
                gauges.append((f"serving/tenant/{t}/{k}", v))
        events = [(f"serving/{k}", float(v), self.steps)
                  for k, v in self.counters.items()]
        events += [(tag, float(v), self.steps) for tag, v in gauges]
        for name, samples in (("ttft", self.ttft), ("tpot", self.tpot),
                              ("e2e", self.e2e)):
            p50, p95 = self._pct(samples, 50), self._pct(samples, 95)
            if p50 is not None:
                events.append((f"serving/{name}_p50_s", p50, self.steps))
                events.append((f"serving/{name}_p95_s", p95, self.steps))
        p50 = self._pct_weighted(self.burst_obs, 50)
        if p50 is not None:
            events.append(("serving/tpot_burst_p50_s", p50, self.steps))
            events.append(("serving/tpot_burst_p95_s",
                           self._pct_weighted(self.burst_obs, 95),
                           self.steps))
        p50 = self._pct_weighted(self.itl_obs, 50)
        if p50 is not None:
            events.append(("serving/itl_p50_s", p50, self.steps))
            events.append(("serving/itl_p95_s",
                           self._pct_weighted(self.itl_obs, 95),
                           self.steps))
        if self.spec_dispatches:
            events.append(("serving/spec_acceptance_rate",
                           self.counters["spec_accepted"]
                           / max(self.counters["spec_drafted"], 1),
                           self.steps))
            events.append(("serving/spec_tokens_per_dispatch",
                           self.spec_emitted / self.spec_dispatches,
                           self.steps))
        if self.timeline is not None and self.timeline.rows:
            # latest step's phase walls — the profiler's dashboard view
            last = self.timeline.last()
            for p in self.timeline.PHASES:
                events.append((f"serving/phase_{p}_s",
                               float(last[f"{p}_s"]), self.steps))  # dstpu: noqa[DST001] timeline rows hold host clock deltas (python floats), never device values
        self.monitor.write_events(events)

    def prometheus_text(self, prefix: str = "dstpu_serving") -> str:
        """The current state in Prometheus text exposition format, so a
        fleet replica is scrapeable without a sink package: counters as
        `<prefix>_<name>_total`, gauges plain, latency percentiles as
        explicit-quantile summary lines.  Pure string rendering — no
        network listener here; serve it from whatever endpoint owns the
        process."""
        lines: List[str] = []
        emit = _prometheus_emitter(lines)

        for key, v in self.counters.items():
            emit(f"{prefix}_{key}_total", v, "counter")
        emit(f"{prefix}_steps_total", self.steps, "counter")
        emit(f"{prefix}_gc_seconds_total", self.gc_seconds, "counter")
        emit(f"{prefix}_long_steps_total", self.long_steps, "counter")
        emit(f"{prefix}_queue_depth", self.queue_depth)
        emit(f"{prefix}_batch_occupancy", self.batch_occupancy)
        emit(f"{prefix}_prefill_tokens_step", self.prefill_tokens_step)
        emit(f"{prefix}_decode_tokens_step", self.decode_tokens_step)
        emit(f"{prefix}_prefill_tokens_saved_total",
             self.prefill_tokens_saved, "counter")
        if self.prefix_cached_blocks is not None:
            emit(f"{prefix}_prefix_cached_blocks",
                 self.prefix_cached_blocks)
        if self.host_tier is not None:
            emit(f"{prefix}_host_cached_blocks",
                 self.host_tier["host_cached_blocks"])
            for k in ("kv_demoted_blocks", "kv_promoted_blocks",
                      "kv_demoted_bytes", "kv_promoted_bytes",
                      "kv_host_dropped_blocks"):
                emit(f"{prefix}_{k}_total", self.host_tier[k], "counter")
        if self.adapter_pool is not None:
            for k in ("adapter_pool_blocks", "adapter_hbm_blocks",
                      "adapter_host_max_blocks", "adapter_host_blocks",
                      "adapter_resident", "adapter_spilled"):
                emit(f"{prefix}_{k}", self.adapter_pool[k])
            for k in ("adapter_demotes", "adapter_promotes",
                      "adapter_dropped"):
                emit(f"{prefix}_{k}_total", self.adapter_pool[k],
                     "counter")
        if self.expert_pool is not None:
            for k in ("expert_slots", "expert_resident", "expert_spilled",
                      "expert_pinned", "expert_drop_rate",
                      "expert_load_imbalance"):
                emit(f"{prefix}_{k}", self.expert_pool[k])
            for k in ("expert_demotes", "expert_promotes",
                      "expert_routed", "expert_rerouted"):
                emit(f"{prefix}_{k}_total", self.expert_pool[k],
                     "counter")
        if self.grammar_cache is not None:
            st = self.grammar_cache.stats()
            for k in ("size", "capacity", "states", "bytes", "epoch"):
                emit(f"{prefix}_grammar_{k}", st[k])
            for k in ("hits", "misses", "compiles", "evictions"):
                emit(f"{prefix}_grammar_{k}_total", st[k], "counter")
        for t, row in sorted(self.tenants.items()):
            for k, v in row.items():
                emit(f"{prefix}_tenant_{k}_total", v, "counter",
                     f'{{tenant="{t}"}}')
        emit(f"{prefix}_sla_ttft_violations_total",
             self.sla_ttft_violations, "counter")
        emit(f"{prefix}_sla_tpot_violations_total",
             self.sla_tpot_violations, "counter")
        # observation-loss accounting (ISSUE 13): entries the bounded
        # traces dropped + events the bounded monitor sink dropped — a
        # dashboard reading this scrape can tell "nothing happened"
        # from "it happened but fell off the ring"
        emit(f"{prefix}_trace_dropped_entries_total",
             self.trace_dropped_entries, "counter")
        dropped = getattr(self.monitor, "dropped_events", None)
        if dropped is not None:
            emit(f"{prefix}_monitor_dropped_events_total", dropped,
                 "counter")
        for name, samples in (("ttft", self.ttft), ("tpot", self.tpot),
                              ("e2e", self.e2e)):
            if not samples:
                continue
            lines.append(f"# TYPE {prefix}_{name}_seconds summary")
            for q in (50, 95):
                lines.append(
                    f'{prefix}_{name}_seconds{{quantile="{q / 100:g}"}} '
                    f"{self._pct(samples, q):g}")
            lines.append(f"{prefix}_{name}_seconds_count {len(samples)}")
        if self.itl_obs:
            # token-weighted streaming inter-token-latency summary (the
            # weighting discipline of tpot_burst, applied to emissions)
            lines.append(f"# TYPE {prefix}_itl_seconds summary")
            for q in (50, 95):
                lines.append(
                    f'{prefix}_itl_seconds{{quantile="{q / 100:g}"}} '
                    f"{self._pct_weighted(self.itl_obs, q):g}")
            lines.append(f"{prefix}_itl_seconds_count "
                         f"{sum(n for _, n in self.itl_obs)}")
        if self.timeline is not None and self.timeline.rows:
            agg = self.timeline.aggregates()
            for p in self.timeline.PHASES:
                emit(f"{prefix}_phase_{p}_seconds_mean",
                     agg.get(f"{p}_mean_s"))
                emit(f"{prefix}_phase_{p}_seconds_p95",
                     agg.get(f"{p}_p95_s"))
        return "\n".join(lines) + "\n"


class FleetTelemetry:
    """Fleet-router observability (serving/fleet): routing decisions by
    reason, stale-view corrections, migrated prefix blocks/bytes, and a
    fleet-wide view aggregated over the per-replica `ServingTelemetry`
    objects.  Host-side counters only — the router is bookkeeping, so
    everything here is measured at the routing decision, not inferred."""

    #: every routing decision lands in exactly one reason bucket
    #: ("handoff" = a prefill-finished request adopted onto the decode
    #: pool by the disagg coordinator)
    ROUTE_REASONS = ("prefix", "least_loaded", "round_robin", "failover",
                     "handoff")

    #: supervisor/autoscaler lifecycle events land in exactly one bucket
    HEALTH_EVENTS = ("demoted_heartbeat", "demoted_error_burst",
                     "promoted", "failovers", "scale_ups", "scale_downs")

    def __init__(self, monitor=None):
        self.monitor = monitor
        self.routed: Dict[str, int] = {r: 0 for r in self.ROUTE_REASONS}
        self.stale_view_corrections = 0
        self.migrated_blocks = 0
        self.migrated_bytes = 0
        self.migrations = 0
        self.migration_failures = 0
        self.migration_backoff_skips = 0
        self.snapshots_published = 0
        self.steps = 0
        # supervisor/autoscaler: health transitions + failover accounting
        self.health_events: Dict[str, int] = {
            e: 0 for e in self.HEALTH_EVENTS}
        self.failover_requeued = 0        # in-flight requests re-queued
        self.failover_failed = 0          # retry budget exhausted -> FAILED
        self.failover_cancelled = 0       # no surviving capacity -> CANCELLED
        # disaggregated prefill/decode handoff (serving/fleet/disagg)
        self.handoffs = 0                 # requests adopted onto the decode pool
        self.handoff_blocks = 0           # prompt KV blocks streamed
        self.handoff_bytes = 0            # bytes on the handoff wire
        self.handoff_cold_fallbacks = 0   # adopted WITHOUT migrated KV
        #                                   (transport fault / backoff /
        #                                   cache eviction): the decode
        #                                   replica re-prefills
        self.handoff_failures = 0         # transport faults mid-handoff
        self.handoff_expired = 0          # cancelled/timed out while parked
        # per-pool SLA targets (seconds), set by the router from
        # DisaggConfig; violations are counted in summary()["pools"]
        self.sla_ttft_target_s: Optional[float] = None
        self.sla_tpot_target_s: Optional[float] = None

    def record_route(self, reason: str) -> None:
        if reason not in self.routed:
            raise ValueError(
                f"unknown routing reason {reason!r} (one of "
                f"{self.ROUTE_REASONS})")
        self.routed[reason] += 1

    def record_stale_correction(self) -> None:
        self.stale_view_corrections += 1

    def record_migration(self, blocks: int, bytes_moved: int) -> None:
        self.migrations += 1
        self.migrated_blocks += blocks
        self.migrated_bytes += bytes_moved

    def record_handoff(self, blocks: int, bytes_moved: int) -> None:
        """One prefill->decode handoff adopted: `blocks` prompt KV
        blocks crossed the wire carrying `bytes_moved` bytes (0/0 = a
        cold fallback, counted separately by the caller)."""
        self.handoffs += 1
        self.handoff_blocks += blocks
        self.handoff_bytes += bytes_moved

    def record_health_event(self, event: str, n: int = 1) -> None:
        if event not in self.health_events:
            raise ValueError(
                f"unknown health event {event!r} (one of "
                f"{self.HEALTH_EVENTS})")
        self.health_events[event] += n

    @staticmethod
    def _unpack(item):
        """A replicas item is (rid, telemetry) or (rid, telemetry,
        role) — the router passes the pool role under disaggregated
        serving; plain fleets default to "unified"."""
        if len(item) == 2:
            rid, t = item
            return rid, t, "unified"
        rid, t, role = item
        return rid, t, str(role)

    def _pool_rows(self, replicas) -> Dict[str, Dict[str, Any]]:
        """Per-pool split: replica counts, completions, and TTFT/TPOT
        percentile splits pooled over each pool's per-request samples —
        the numbers that make prefill/decode interference (and the win
        of removing it) directly observable.  SLA targets, when set,
        add violation counts: TTFT is attributed to the prefill pool's
        responsibility but measured where requests finish (the decode
        pool under disagg), so the violation count rides the fleet-wide
        sample set; TPOT violations count against the pool that decoded
        them."""
        buckets: Dict[str, Dict[str, Any]] = {}
        for item in replicas:
            rid, t, role = self._unpack(item)
            b = buckets.setdefault(role, {
                "replicas": 0, "completed": 0, "handoff_parked": 0,
                "_ttft": [], "_tpot": [], "_burst": []})
            b["replicas"] += 1
            b["completed"] += t.counters["completed"]
            b["handoff_parked"] += t.counters["handoff_parked"]
            b["_ttft"].extend(t.ttft)
            b["_tpot"].extend(t.tpot)
            b["_burst"].extend(t.burst_obs)
        pools: Dict[str, Dict[str, Any]] = {}
        for role, b in buckets.items():
            row: Dict[str, Any] = {
                "replicas": b["replicas"],
                "completed": b["completed"],
                "handoff_parked": b["handoff_parked"],
                "ttft_p50_s": ServingTelemetry._pct(b["_ttft"], 50),
                "ttft_p95_s": ServingTelemetry._pct(b["_ttft"], 95),
                "tpot_p50_s": ServingTelemetry._pct(b["_tpot"], 50),
                "tpot_p95_s": ServingTelemetry._pct(b["_tpot"], 95),
                "tpot_burst_p95_s": ServingTelemetry._pct_weighted(
                    b["_burst"], 95),
            }
            if self.sla_ttft_target_s is not None:
                row["ttft_sla_target_s"] = self.sla_ttft_target_s
                row["ttft_sla_violations"] = sum(
                    1 for x in b["_ttft"] if x > self.sla_ttft_target_s)
            if self.sla_tpot_target_s is not None:
                row["tpot_sla_target_s"] = self.sla_tpot_target_s
                row["tpot_sla_violations"] = sum(
                    1 for x in b["_tpot"] if x > self.sla_tpot_target_s)
            pools[role] = row
        return pools

    def summary(self, replicas=()) -> Dict[str, Any]:
        """Fleet snapshot.  `replicas`: iterable of (replica_id,
        ServingTelemetry) or (replica_id, ServingTelemetry, pool_role) —
        per-replica occupancy is reported per id and prefix hit counters
        aggregate to the fleet-wide hit rate (the number cache-aware
        routing exists to raise); pool roles additionally split SLA
        percentiles per pool (see _pool_rows)."""
        replicas = [self._unpack(item) for item in replicas]
        hits = misses = saved = 0
        drafted = accepted = dispatches = emitted = 0
        per_replica: Dict[str, Dict[str, Any]] = {}
        for rid, t, role in replicas:
            hits += t.counters["prefix_hits"]
            misses += t.counters["prefix_misses"]
            saved += t.prefill_tokens_saved
            drafted += t.counters["spec_drafted"]
            accepted += t.counters["spec_accepted"]
            dispatches += t.spec_dispatches
            emitted += t.spec_emitted
            per_replica[str(rid)] = {
                "role": role,
                "queue_depth": t.queue_depth,
                "batch_occupancy": t.batch_occupancy,
                "completed": t.counters["completed"],
                "failed": t.counters["failed"],
                "prefix_hits": t.counters["prefix_hits"],
                "prefix_misses": t.counters["prefix_misses"],
                "drained_unserved": t.counters["drained_unserved"],
                "evicted_in_flight": t.counters["evicted_in_flight"],
                "spec_drafted": t.counters["spec_drafted"],
                "spec_accepted": t.counters["spec_accepted"],
                "handoff_parked": t.counters["handoff_parked"],
            }
        return {
            "routed": dict(self.routed),
            "routed_total": sum(self.routed.values()),
            "stale_view_corrections": self.stale_view_corrections,
            "migrations": self.migrations,
            "migrated_blocks": self.migrated_blocks,
            "migrated_bytes": self.migrated_bytes,
            "migration_failures": self.migration_failures,
            "migration_backoff_skips": self.migration_backoff_skips,
            "health_events": dict(self.health_events),
            "failover_requeued": self.failover_requeued,
            "failover_failed": self.failover_failed,
            "failover_cancelled": self.failover_cancelled,
            "handoffs": self.handoffs,
            "handoff_blocks": self.handoff_blocks,
            "handoff_bytes": self.handoff_bytes,
            "handoff_cold_fallbacks": self.handoff_cold_fallbacks,
            "handoff_failures": self.handoff_failures,
            "handoff_expired": self.handoff_expired,
            "pools": self._pool_rows(replicas),
            "snapshots_published": self.snapshots_published,
            "fleet_prefix_hit_rate": (hits / (hits + misses)
                                      if hits + misses else None),
            "fleet_prefill_tokens_saved": saved,
            # fleet-wide speculative stats (None rates when no replica
            # ran a verify dispatch — speculation off everywhere)
            "fleet_spec_drafted": drafted,
            "fleet_spec_accepted": accepted,
            "fleet_spec_acceptance_rate": (accepted / drafted
                                           if drafted else None),
            "fleet_spec_tokens_per_dispatch": (emitted / dispatches
                                               if dispatches else None),
            "per_replica": per_replica,
        }

    def publish(self, replicas=()) -> None:
        """Fan the fleet state out through the monitor sinks as
        `fleet/*` events (same `write_events` API the serving telemetry
        uses)."""
        if self.monitor is None:
            return
        s = self.summary(replicas)
        events = [(f"fleet/routed_{r}", float(n), self.steps)
                  for r, n in s["routed"].items()]
        events += [(f"fleet/health_{e}", float(n), self.steps)
                   for e, n in s["health_events"].items()]
        for key in ("stale_view_corrections", "migrations",
                    "migrated_blocks", "migrated_bytes",
                    "migration_failures", "migration_backoff_skips",
                    "failover_requeued", "failover_failed",
                    "failover_cancelled", "snapshots_published",
                    "handoffs", "handoff_blocks", "handoff_bytes",
                    "handoff_cold_fallbacks", "handoff_failures",
                    "handoff_expired",
                    "fleet_prefill_tokens_saved", "fleet_spec_drafted",
                    "fleet_spec_accepted"):
            events.append((f"fleet/{key}", float(s[key]), self.steps))
        # per-pool SLA splits (disaggregated serving): one event stream
        # per pool role so the prefill/decode interference split is a
        # first-class dashboard series.  The lone "unified" pool of a
        # plain fleet is omitted — its numbers already ride the
        # per-replica events, and the plain fleet's event surface stays
        # exactly the pre-disagg one (parity).
        pools = s["pools"]
        if set(pools) - {"unified"}:
            for role, row in pools.items():
                for key in ("replicas", "completed", "handoff_parked",
                            "ttft_p50_s", "ttft_p95_s", "tpot_p50_s",
                            "tpot_p95_s", "tpot_burst_p95_s",
                            "ttft_sla_violations",
                            "tpot_sla_violations"):
                    v = row.get(key)
                    if v is not None:
                        events.append((f"fleet/pool_{role}/{key}",
                                       float(v), self.steps))
        if s["fleet_prefix_hit_rate"] is not None:
            events.append(("fleet/prefix_hit_rate",
                           float(s["fleet_prefix_hit_rate"]), self.steps))
        if s["fleet_spec_acceptance_rate"] is not None:
            events.append(("fleet/spec_acceptance_rate",
                           float(s["fleet_spec_acceptance_rate"]),
                           self.steps))
            events.append(("fleet/spec_tokens_per_dispatch",
                           float(s["fleet_spec_tokens_per_dispatch"]),
                           self.steps))
        for rid, r in s["per_replica"].items():
            # disaggregated fleets tag every per-replica event with the
            # replica's pool role; a plain fleet (all unified) keeps the
            # pre-disagg tag names bit-for-bit
            tag = (f"fleet/replica_{rid}" if r["role"] == "unified"
                   else f"fleet/replica_{rid}/{r['role']}")
            events.append((f"{tag}/queue_depth",
                           float(r["queue_depth"]), self.steps))
            events.append((f"{tag}/batch_occupancy",
                           float(r["batch_occupancy"]), self.steps))
        self.monitor.write_events(events)

    def prometheus_text(self, replicas=(),
                        prefix: str = "dstpu_fleet") -> str:
        """Fleet snapshot in Prometheus text exposition format (same
        `replicas` iterable as `summary()`): fleet-wide scalars plain,
        routing/health splits and per-replica/per-pool rows as labeled
        series — one scrape covers the whole fleet."""
        s = self.summary(replicas)
        lines: List[str] = []
        emit = _prometheus_emitter(lines)

        for reason, n in s["routed"].items():
            emit(f"{prefix}_routed_total", n, "counter",
                 f'{{reason="{reason}"}}')
        for event, n in s["health_events"].items():
            emit(f"{prefix}_health_events_total", n, "counter",
                 f'{{event="{event}"}}')
        for key in ("stale_view_corrections", "migrations",
                    "migrated_blocks", "migrated_bytes",
                    "migration_failures", "migration_backoff_skips",
                    "failover_requeued", "failover_failed",
                    "failover_cancelled", "snapshots_published",
                    "handoffs", "handoff_blocks", "handoff_bytes",
                    "handoff_cold_fallbacks", "handoff_failures",
                    "handoff_expired", "fleet_prefill_tokens_saved"):
            emit(f"{prefix}_{key}_total", s[key], "counter")
        emit(f"{prefix}_prefix_hit_rate", s["fleet_prefix_hit_rate"])
        emit(f"{prefix}_spec_acceptance_rate",
             s["fleet_spec_acceptance_rate"])
        dropped = getattr(self.monitor, "dropped_events", None)
        if dropped is not None:
            emit(f"{prefix}_monitor_dropped_events_total", dropped,
                 "counter")
        for role, row in s["pools"].items():
            for key, v in row.items():
                if v is None or key.endswith("_target_s"):
                    continue
                emit(f"{prefix}_pool_{key}", v, "gauge",
                     f'{{pool="{role}"}}')
        for rid, r in s["per_replica"].items():
            labels = f'{{replica="{rid}",role="{r["role"]}"}}'
            emit(f"{prefix}_replica_queue_depth", r["queue_depth"],
                 "gauge", labels)
            emit(f"{prefix}_replica_batch_occupancy",
                 r["batch_occupancy"], "gauge", labels)
            emit(f"{prefix}_replica_completed_total", r["completed"],
                 "counter", labels)
            emit(f"{prefix}_replica_failed_total", r["failed"],
                 "counter", labels)
        return "\n".join(lines) + "\n"
