"""Bounded metric time series for the serving stack.

PR 11 gave the serve loop *point-in-time* observability (counters,
percentiles, the step-phase profiler); this module adds the TIME
dimension: one bounded ring of per-tick metric rows, sampled at the
existing tick seams (`ServeLoop.step`, `FleetRouter.step`), exportable
as JSONL (grep/jq/pandas) and Prometheus text.

Design rules, inherited from the rest of the observability stack:

- **One ring implementation.**  `MetricRing` is the single bounded-ring
  seam: the PR 11 `StepTimeline` now rides it (`serving/tracing.py`),
  the per-tick samplers here ride it, and the recompile flight recorder
  (`observatory/recompile.py`) rides it — eviction + drop accounting
  behave identically everywhere.
- **Bounded, with counted eviction.**  The newest `capacity` rows are
  kept; older rows are evicted and counted (`evicted`), never silently
  lost vs a claimed full history (the InMemoryMonitor lesson).
- **Registered field names.**  Every row key a sampler emits is
  declared in `monitor/schema.py` (`TIMESERIES_FIELDS`) and a tier-1
  gate sweeps emitted rows against the registry — the same silent-typo
  guard the monitor tags get, extended to the JSONL series
  (tests/test_observatory.py).
- **Default off is bit-for-bit.**  Sampling hangs off
  `ServingConfig.tracing.metrics_ring` (0 by default); the loop's off
  path does not even read the clock for it (locked by test).
"""
from __future__ import annotations

import json
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

__all__ = ["MetricRing", "MetricsSampler", "FleetMetricsSampler"]


class MetricRing:
    """A bounded ring of metric rows (flat dicts of scalars).

    `record()` appends one row; once full, the oldest row is evicted
    and counted.  `aggregates()`/`series()` are the read side;
    `to_jsonl()`/`prometheus_text()` are the export side."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(
                f"ring capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.rows: deque = deque(maxlen=capacity)
        self.evicted = 0
        self.total_rows = 0

    def record(self, row: Dict[str, Any]) -> None:
        if len(self.rows) == self.capacity:
            self.evicted += 1
        self.rows.append(row)
        self.total_rows += 1

    def last(self) -> Optional[Dict[str, Any]]:
        return self.rows[-1] if self.rows else None

    def series(self, field: str) -> List[Any]:
        """The ring-resident values of one field, oldest first (rows
        missing the field are skipped)."""
        return [r[field] for r in self.rows if field in r]

    def fields(self) -> List[str]:
        """Every field name any ring-resident row carries, in
        first-seen order."""
        seen: List[str] = []
        for r in self.rows:
            for k in r:
                if k not in seen:
                    seen.append(k)
        return seen

    def aggregates(self, fields: Optional[Iterable[str]] = None
                   ) -> Dict[str, Any]:
        """Ring occupancy plus mean/p95 of each numeric field (the
        requested `fields`, or every field present)."""
        import numpy as np
        out: Dict[str, Any] = {
            "rows": len(self.rows), "capacity": self.capacity,
            "evicted": self.evicted, "total_rows": self.total_rows,
        }
        for f in (fields if fields is not None else self.fields()):
            vals = [r[f] for r in self.rows
                    if isinstance(r.get(f), (int, float))]
            if vals:
                arr = np.asarray(vals, np.float64)
                out[f"{f}_mean"] = float(arr.mean())
                out[f"{f}_p95"] = float(np.percentile(arr, 95))
        return out

    def to_jsonl(self, path: str) -> str:
        """One JSON object per ring-resident row, oldest first, plus a
        trailing meta row (`"_meta": true`) carrying the eviction
        accounting — a consumer that cares about completeness checks
        `_evicted` there.  Every meta key is underscore-prefixed so the
        schema gate's field sweep (which exempts `_*`) passes the whole
        export unmodified."""
        with open(path, "w", encoding="utf-8") as f:
            for r in self.rows:
                f.write(json.dumps(r) + "\n")
            f.write(json.dumps({"_meta": True, "_rows": len(self.rows),
                                "_capacity": self.capacity,
                                "_evicted": self.evicted,
                                "_total_rows": self.total_rows}) + "\n")
        return path

    def prometheus_text(self, prefix: str,
                        fields: Optional[Iterable[str]] = None) -> str:
        """The LATEST row's numeric fields as gauges, plus ring
        occupancy/eviction — the scrape view of the series."""
        lines: List[str] = []

        def emit(name: str, value) -> None:
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {float(value):g}")

        last = self.last() or {}
        for f in (fields if fields is not None else last.keys()):
            v = last.get(f)
            if isinstance(v, (int, float)):
                emit(f"{prefix}_{f}", v)
        emit(f"{prefix}_ring_rows", len(self.rows))
        emit(f"{prefix}_ring_evicted", self.evicted)
        return "\n".join(lines) + "\n"


class MetricsSampler:
    """Per-tick serve-loop sampler: one `MetricRing` row per
    `ServeLoop.step()` recording the queue/arena/cache/speculation
    state a capacity investigation needs, on the serve clock.

    Created by `ServeLoop` when `ServingConfig.tracing.metrics_ring`
    > 0; every field below is registered in
    `monitor.schema.LOOP_TIMESERIES_FIELDS` (tier-1 gated)."""

    def __init__(self, capacity: int):
        self.ring = MetricRing(capacity)
        # optional recompile flight recorder
        # (observatory/recompile.py): attaching one turns mid-serve
        # recompiles into a per-tick `recompiles` field
        self.recorder = None
        self._recorder_seen = 0

    def attach_recorder(self, recorder) -> None:
        self.recorder = recorder
        self._recorder_seen = recorder.total_events

    def sample_loop(self, loop, now: float) -> Dict[str, Any]:
        """One row from a just-completed serve step.  Pure host reads —
        no device sync anywhere (sampling must stay cheap beside a serve
        step)."""
        t = loop.telemetry
        recompiles = 0
        if self.recorder is not None:
            total = self.recorder.total_events
            recompiles = total - self._recorder_seen
            self._recorder_seen = total
        row: Dict[str, Any] = {
            "step": t.steps,
            "t": now,
            "queue_depth": loop.scheduler.queue_depth,
            "active_seqs": len(loop.scheduler.active),
            "parked": len(loop._handoff_ready),
            "free_slots": loop.engine.free_slots,
            "free_blocks": int(loop.engine.free_blocks),  # dstpu: noqa[DST001] a host count: an int, or a two-kind cache's KindCounts (its scarcest kind)
            "batch_occupancy": t.batch_occupancy,
            "prefill_tokens_step": t.prefill_tokens_step,
            "decode_tokens_step": t.decode_tokens_step,
            "admitted_total": t.counters["admitted"],
            "completed_total": t.counters["completed"],
            "rejected_queue_full_total": t.counters["rejected_queue_full"],
            "sla_ttft_violations_total": t.sla_ttft_violations,
            "sla_tpot_violations_total": t.sla_tpot_violations,
            "recompiles": recompiles,
        }
        if t.prefix_cached_blocks is not None:
            row["prefix_cached_blocks"] = t.prefix_cached_blocks
        if t.host_tier is not None:
            row["host_cached_blocks"] = t.host_tier["host_cached_blocks"]
        if t.counters["spec_drafted"]:
            row["spec_acceptance_rate"] = (
                t.counters["spec_accepted"] / t.counters["spec_drafted"])
        self.ring.record(row)
        return row


class FleetMetricsSampler:
    """Per-tick fleet sampler: one row per `FleetRouter.step()` with
    the fleet-wide load/pool/handoff view (per-replica detail stays on
    each replica's own sampler).  Fields registered in
    `monitor.schema.FLEET_TIMESERIES_FIELDS`."""

    def __init__(self, capacity: int):
        self.ring = MetricRing(capacity)

    def sample_fleet(self, fleet, now: float) -> Dict[str, Any]:
        t = fleet.telemetry
        live = [rep for rep in fleet.replicas
                if rep.health.value != "drained"]
        live_loads = [(rep, rep.load()) for rep in live]
        loads = [ld for _, ld in live_loads]
        row: Dict[str, Any] = {
            "step": fleet._steps,
            "t": now,
            "replicas_live": len(live),
            "queue_depth_total": sum(
                rep.loop.scheduler.queue_depth for rep in fleet.replicas),
            "active_total": sum(
                len(rep.loop.scheduler.active) for rep in fleet.replicas),
            "parked_total": sum(
                len(rep.loop._handoff_ready) for rep in fleet.replicas),
            "free_blocks_total": sum(
                int(rep.loop.engine.free_blocks) for rep in fleet.replicas),
            "load_mean": (sum(loads) / len(loads)) if loads else 0.0,
            "load_max": max(loads) if loads else 0.0,
            "routed_total": sum(t.routed.values()),
            "handoffs_total": t.handoffs,
            "failovers_total": t.health_events["failovers"],
            "completed_total": sum(
                rep.loop.telemetry.counters["completed"]
                for rep in fleet.replicas),
        }
        # per-pool mean load (disagg): one field per role with live
        # members — a plain fleet emits only pool_unified_load, so its
        # series surface is stable as pools come and go
        by_role: Dict[str, List[float]] = {}
        for rep, ld in live_loads:
            by_role.setdefault(rep.role.value, []).append(ld)
        for role, vals in by_role.items():
            row[f"pool_{role}_load"] = sum(vals) / len(vals)
        self.ring.record(row)
        return row
