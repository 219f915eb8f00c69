"""Distributed request tracing + the step timeline profiler.

The serving stack before this module measured *aggregates*
(`ServingTelemetry.summary()` — counters and percentiles): good for
dashboards, useless for "why was THIS request slow".  This module adds
the per-request half, the way the reference stack treats observability
as a first-class layer (DeepSpeed's monitor/ + flops profiler +
CommsLogger): every request carries a **span tree** covering its whole
fleet lifecycle — queued, routed (with the routing reason), admitted,
prefill chunks, prefix-cache hit, disagg handoff + KV migration, each
decode burst / speculative verify dispatch, failover demote / re-queue /
adopt, terminal state.

Design constraints, in order:

- **Default-off is bit-for-bit.**  Tracing hangs off
  `ServingConfig.tracing` (None by default); every hook in the serve
  loop / router / supervisor / handoff guards on `req.trace is None` or
  `self._tracer is None`, so an untraced fleet executes exactly the
  PR-10 code path (locked by test).
- **Spans ride the `Request` object.**  Drain, failover adoption, and
  the disagg handoff all move the SAME `Request` across replicas, so a
  trace survives every re-homing for free and a failed-over request's
  tree naturally spans two replicas — the thing aggregate counters can
  never show.
- **One clock.**  Every timestamp is the serve loop's clock (the shared
  `FakeClock` in tests — deterministic, zero sleeps; `time.monotonic`
  in production), the same clock SLAs and health deadlines ride.
- **Bounded.**  Each trace caps its entry count
  (`TracingConfig.max_spans_per_request`); overflow increments a
  `dropped` counter instead of growing without limit (the
  InMemoryMonitor lesson, applied from birth).

Exporters: `chrome_trace()` renders traces as Chrome trace-event JSON
(load it in Perfetto / chrome://tracing — one process row per replica,
one thread per request, so a failover is visibly a span tree jumping
rows) and `write_trace_jsonl()` streams one entry per line for ad-hoc
tooling.  See docs/OBSERVABILITY.md for the span catalogue.
"""
from __future__ import annotations

import itertools
import json
from typing import Any, Dict, Iterable, List, Optional

from .observatory.metrics import MetricRing
from .request import Request, RequestState, TERMINAL_STATES

__all__ = ["RequestTrace", "RequestTracer", "StepTimeline",
           "chrome_trace", "write_chrome_trace", "write_trace_jsonl",
           "SPAN_NAMES", "EVENT_NAMES"]

#: the span catalogue (docs/OBSERVABILITY.md) — phase spans cover the
#: request's time in that lifecycle stage; work spans cover one unit of
#: engine work the request rode
SPAN_NAMES = (
    "queued",          # phase: submitted, waiting for admission
    "prefill",         # phase: owns an engine slot, prompt in flight
    "decode",          # phase: generating (first token -> terminal)
    "handoff",         # phase: parked on a prefill-pool replica /
    #                    crossing the pool boundary (disagg)
    "prefill_chunk",   # work: one serve step's prefill progress
    "decode_burst",    # work: one compiled decode burst
    "spec_verify",     # work: one draft-and-verify dispatch
    "kv_migrate",      # work: prefix KV streamed across the wire
)

#: instant events (points on the request's timeline)
EVENT_NAMES = (
    "submit", "route", "admit", "prefix_hit", "first_token",
    "park", "adopt", "demote", "requeue", "rollback", "finish",
    "preempt",
)


#: process-wide trace identity: request uids are only unique per
#: ServeLoop (and adoption REASSIGNS them), so exporters key threads on
#: this counter instead — two requests can never merge into one
#: perfetto row however they re-home
_TRACE_IDS = itertools.count()


class RequestTrace:
    """The span tree of one request.  Entries are flat dicts (kind
    "span" or "event") ordered by insertion; the tree structure is the
    phase nesting, reconstructed by the exporters from the entry order.
    Attached to `Request.trace` by `RequestTracer`; every mutation is a
    cheap append guarded by the entry cap."""

    __slots__ = ("trace_id", "uid", "replica", "entries", "dropped",
                 "_max", "_phase", "_phase_t0")

    def __init__(self, uid: int, t0: float, replica: str,
                 max_entries: int):
        self.trace_id = next(_TRACE_IDS)
        self.uid = uid                  # current loop-local uid (adopt
        #                                 updates it with the re-homing)
        self.replica = replica          # current owning replica label
        self.entries: List[Dict[str, Any]] = []
        self.dropped = 0
        self._max = max_entries
        self._phase: Optional[str] = "queued"
        self._phase_t0 = t0
        self.event("submit", t0)

    # -- recording --------------------------------------------------------
    def _add(self, entry: Dict[str, Any]) -> None:
        if len(self.entries) >= self._max:
            self.dropped += 1
            return
        self.entries.append(entry)

    def event(self, name: str, t: float,
              replica: Optional[str] = None, **attrs: Any) -> None:
        self._add({"kind": "event", "name": name, "t": t,
                   "replica": replica or self.replica, **attrs})

    def span(self, name: str, t0: float, t1: float,
             replica: Optional[str] = None, **attrs: Any) -> None:
        self._add({"kind": "span", "name": name, "t0": t0, "t1": t1,
                   "replica": replica or self.replica, **attrs})

    def phase(self, name: Optional[str], t: float, **attrs: Any) -> None:
        """Close the open lifecycle phase as a span and open `name`
        (None = close only, the terminal transition)."""
        if self._phase is not None:
            self.span(self._phase, self._phase_t0, t, **attrs)
        self._phase = name
        self._phase_t0 = t

    # -- lifecycle hooks (called from Request / the serve loop) -----------
    def on_transition(self, old: RequestState, new: RequestState,
                      now: float) -> None:
        if new is RequestState.PREFILL:
            self.phase("prefill", now)
            self.event("admit", now)
        elif new is RequestState.DECODE:
            self.phase("decode", now)
            self.event("first_token", now)
        elif new in TERMINAL_STATES:
            self.phase(None, now)
            self.event("finish", now, state=new.value)

    def on_requeue(self, now: float, retries: int) -> None:
        """Failover: the request was pulled off a dead replica
        (in-flight work discarded) and returned to QUEUED for adoption
        elsewhere."""
        self.phase("queued", now, aborted=True)
        self.event("requeue", now, retries=retries)

    def on_rollback(self, now: float) -> None:
        """Crash-atomic admission rollback: put() never completed, the
        request returns to the queue of the SAME loop."""
        self.phase("queued", now, aborted=True)
        self.event("rollback", now)

    def on_preempt(self, now: float, preemptions: int) -> None:
        """SLO-aware preemption: the request's live decode was swapped
        out (or parked for recompute) to admit an urgent request; it
        re-queues with its generated tokens intact and stream-resumes
        when capacity returns."""
        self.phase("queued", now, preempted=True)
        self.event("preempt", now, preemptions=preemptions)

    def on_park(self, now: float) -> None:
        """Disagg prefill pool: prompt finished, parked for the
        cross-pool handoff coordinator."""
        self.phase("handoff", now)
        self.event("park", now)

    def on_adopt(self, now: float, replica: str, uid: int) -> None:
        """The request moved onto `replica` (failover adoption or the
        disagg handoff), where it holds loop-local uid `uid`."""
        if self._phase == "handoff":
            # the handoff phase ends where the decode pool takes over
            self.phase("queued", now)
        self.replica = replica
        self.uid = uid
        self.event("adopt", now, replica=replica, uid=uid)

    # -- views ------------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        return [e for e in self.entries if e["kind"] == "span"
                and (name is None or e["name"] == name)]

    def events(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        return [e for e in self.entries if e["kind"] == "event"
                and (name is None or e["name"] == name)]

    def replicas(self) -> List[str]:
        """Distinct replica labels touched, in first-touch order."""
        seen: List[str] = []
        for e in self.entries:
            r = e.get("replica")
            if r and r not in seen:
                seen.append(r)
        return seen


class RequestTracer:
    """Per-loop tracing front door: attaches a `RequestTrace` to every
    submitted request when tracing is enabled.  Owned by `ServeLoop`
    (None when `ServingConfig.tracing` is off — the parity state)."""

    def __init__(self, max_spans_per_request: int):
        self.max_spans_per_request = max_spans_per_request
        self.traces_started = 0

    def attach(self, req: Request, replica: str) -> RequestTrace:
        trace = RequestTrace(req.uid, req.arrival_time, replica,
                             self.max_spans_per_request)
        req.trace = trace
        self.traces_started += 1
        return trace


class StepTimeline(MetricRing):
    """Per-step phase durations and work counts in a bounded ring.

    One row per `ServeLoop.step()`: how long the step spent finalizing
    expiries, admitting, in the engine's prefill call, and in the
    decode/burst phase, plus the tokens/blocks the step moved.  The
    ring IS the observatory's `MetricRing` (ISSUE 13 made that the one
    bounded-series seam — eviction and drop accounting behave
    identically here, in the per-tick samplers, and in the recompile
    recorder): the most recent `capacity` rows are kept, older rows are
    evicted and counted, never silently lost vs a claimed full history.
    Aggregates surface through
    `ServingTelemetry.summary()["step_phases"]` and the monitor sinks
    as `serving/phase_*` gauges."""

    # "promote" is the host-KV-tier promotion share of the admission
    # window (serving/kv_tier.py) — 0.0 on every step without a tier,
    # so pre-tier rows and tier-off loops carry the same field shape
    PHASES = ("finalize", "admission", "promote", "prefill", "decode")

    @property
    def total_steps(self) -> int:
        return self.total_rows

    def record(self, step: int, phases: Dict[str, float],
               **counts: Any) -> None:
        row = {"step": step}
        row.update({f"{p}_s": float(phases.get(p, 0.0))  # dstpu: noqa[DST001] phase walls are host clock deltas (python floats), never device values
                    for p in self.PHASES})
        row.update(counts)
        MetricRing.record(self, row)

    def aggregates(self) -> Dict[str, Any]:
        out = MetricRing.aggregates(self, fields=())
        out["total_steps"] = out.pop("total_rows")
        import numpy as np
        for p in self.PHASES:
            vals = [r[f"{p}_s"] for r in self.rows]
            if vals:
                arr = np.asarray(vals, np.float64)
                out[f"{p}_mean_s"] = float(arr.mean())
                out[f"{p}_p95_s"] = float(np.percentile(arr, 95))
        return out


# -- exporters -------------------------------------------------------------

def _traces(requests: Iterable[Request]) -> List[RequestTrace]:
    return [r.trace for r in requests if getattr(r, "trace", None)
            is not None]


def chrome_trace(requests: Iterable[Request],
                 recompiles=None) -> Dict[str, Any]:
    """Render traces as a Chrome trace-event document (Perfetto /
    chrome://tracing loadable): one process per replica (named via
    `process_name` metadata), one thread per request, spans as complete
    ("X") events and instants as "i" events.  Timestamps are serve-clock
    seconds scaled to microseconds — relative time, which is all the
    viewers need.

    `recompiles`: an `observatory.RecompileFlightRecorder` (or its
    event-row list) — its compile events render as instants on their
    own "recompiles" process row, so a compile stall is visibly lined
    up with the request spans that straddled it."""
    events: List[Dict[str, Any]] = []
    pids: Dict[str, int] = {}

    def pid(replica: Optional[str]) -> int:
        label = replica or "unattributed"
        if label not in pids:
            pids[label] = len(pids) + 1
            events.append({"ph": "M", "name": "process_name",
                           "pid": pids[label], "tid": 0,
                           "args": {"name": label}})
        return pids[label]

    for trace in _traces(requests):
        tid = trace.trace_id
        for e in trace.entries:
            args = {k: v for k, v in e.items()
                    if k not in ("kind", "name", "t", "t0", "t1",
                                 "replica")}
            args["request"] = trace.trace_id
            args["uid"] = trace.uid
            if e["kind"] == "span":
                events.append({
                    "ph": "X", "name": e["name"], "cat": "serving",
                    "pid": pid(e.get("replica")), "tid": tid,
                    "ts": e["t0"] * 1e6,
                    "dur": max(e["t1"] - e["t0"], 0.0) * 1e6,
                    "args": args})
            else:
                events.append({
                    "ph": "i", "s": "t", "name": e["name"],
                    "cat": "serving", "pid": pid(e.get("replica")),
                    "tid": tid, "ts": e["t"] * 1e6, "args": args})
    if recompiles is not None:
        rows = (recompiles.events() if hasattr(recompiles, "events")
                else recompiles)
        for r in rows:
            events.append({
                "ph": "i", "s": "p", "name": "recompile",
                "cat": "serving", "pid": pid("recompiles"), "tid": 0,
                "ts": r["t"] * 1e6,
                "args": {"event": r.get("event"),
                         "duration_s": r.get("duration_s")}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(requests: Iterable[Request], path: str,
                       recompiles=None) -> str:
    doc = chrome_trace(requests, recompiles=recompiles)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
        f.write("\n")
    return path


def write_trace_jsonl(requests: Iterable[Request], path: str) -> str:
    """One JSON object per line: every entry of every trace, stamped
    with its request uid — the streaming-friendly format (grep/jq)."""
    with open(path, "w", encoding="utf-8") as f:
        for trace in _traces(requests):
            for e in trace.entries:
                rec = {"request": trace.trace_id, "uid": trace.uid}
                rec.update(e)
                f.write(json.dumps(rec) + "\n")
    return path
