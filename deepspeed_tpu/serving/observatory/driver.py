"""Open-loop driver: submit on the arrival schedule, no matter what.

A closed loop (the benchmark's `closed_loop` traffic kind) submits a
client's next request when its previous one COMPLETES — the server can
never be offered more load than it serves.  `OpenLoopDriver` submits each
`WorkloadItem` the moment the serve clock reaches its `arrival_s`,
regardless of completions: under-capacity the queue stays shallow,
past capacity it grows without bound, and the knee between the two is
the measurement (DistServe/FastGen methodology).

The driver runs on the serve loop's OWN clock and works against
anything with the loop contract (`submit`/`step`/`has_work` — a bare
`ServeLoop`, a `FleetRouter`, a disaggregated fleet).  Two time modes:

- **virtual** (`step_dt` set): the clock is a `FakeClock` the driver
  advances by `step_dt` per serve step — a fully deterministic
  queueing simulation with REAL serving mechanics (admission gate, KV
  ledger, bursts, prefix cache, handoffs) and real model tokens.
  Offered load ρ is then exact: `rate_rps` against a service rate
  measured by `calibrate_service_rate`.  This is what
  tests/test_observatory.py's ramp runs.
- **measured** (`step_dt=None`): the clock must be real
  (`time.monotonic`-like); each step costs its actual wall time.  Same
  driver, real latencies — the mode a run on the chip would use.

Backpressure is part of the measurement: a submit rejected by the
bounded queue (`QueueFullError`) is counted in `rejected`, never
retried (an open-loop client does not wait), and never raises out of
the driver — admission-gate saturation becomes a number instead of a
crash.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..request import Request, RequestState
from ..scheduler import AdmissionError, QueueFullError
from ..tenancy import RateLimitedError
from .workload import WorkloadItem

__all__ = ["VirtualClock", "OpenLoopResult", "OpenLoopDriver",
           "calibrate_service_rate"]


class VirtualClock:
    """The canonical virtual serve clock: call it for *now*,
    `advance()` to move time.  This is the clock object
    `OpenLoopDriver`'s virtual mode expects (and what every ServeLoop /
    FleetRouter in a deterministic run should be built on — one shared
    instance, so SLAs, health deadlines, and arrival schedules agree on
    what time it is).  `serving.fleet.faults.FakeClock` is this class
    under its historical name."""

    def __init__(self, t0: float = 0.0):
        self.t = float(t0)

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> float:
        if seconds < 0:
            raise ValueError(f"clock cannot go backward ({seconds})")
        self.t += float(seconds)
        return self.t


@dataclass
class OpenLoopResult:
    """What one open-loop run produced."""

    requests: List[Request] = field(default_factory=list)
    finished: List[Request] = field(default_factory=list)
    rejected: int = 0              # QueueFullError at submit
    rejected_invalid: int = 0      # AdmissionError at submit
    rejected_rate_limited: int = 0  # RateLimitedError at submit
    #                                 (tenant QoS shed; a policy
    #                                 outcome, not request loss)
    steps: int = 0
    elapsed_s: float = 0.0         # serve-clock time, first arrival -> idle

    @property
    def lost(self) -> int:
        """Accepted requests that did NOT complete DONE (the zero-loss
        assert reads this)."""
        done = sum(1 for r in self.requests
                   if r.state is RequestState.DONE)
        return len(self.requests) - done


class OpenLoopDriver:
    """Drive one workload through one serve target, open-loop."""

    def __init__(self, loop, clock, items: List[WorkloadItem],
                 step_dt: Optional[float] = None,
                 sla_ttft_s: Optional[float] = None,
                 sla_tpot_s: Optional[float] = None,
                 max_steps: int = 1_000_000):
        """`loop`: ServeLoop or FleetRouter.  `clock`: the SAME clock
        object the loop was built on; in virtual mode it must expose
        `advance(dt)` (the serve FakeClock).  `sla_*_s` set the
        telemetry's SLA targets (serve-clock seconds) so violation
        onset is counted where requests finish."""
        self.loop = loop
        self.clock = clock
        self.items = sorted(items, key=lambda it: (it.arrival_s, it.index))
        self.step_dt = step_dt
        self.max_steps = max_steps
        if step_dt is not None and not hasattr(clock, "advance"):
            raise ValueError(
                "virtual-time mode (step_dt set) needs a clock with "
                "advance() — the serve FakeClock")
        for t in self._telemetries():
            if sla_ttft_s is not None:
                t.sla_ttft_target_s = sla_ttft_s
            if sla_tpot_s is not None:
                t.sla_tpot_target_s = sla_tpot_s

    def _telemetries(self):
        reps = getattr(self.loop, "replicas", None)
        if reps is not None:                      # FleetRouter
            return [rep.loop.telemetry for rep in reps]
        return [self.loop.telemetry]

    def sla_violations(self) -> Dict[str, int]:
        return {
            "ttft": sum(t.sla_ttft_violations for t in
                        self._telemetries()),
            "tpot": sum(t.sla_tpot_violations for t in
                        self._telemetries()),
        }

    def run(self) -> OpenLoopResult:
        """Submit every item on schedule, step until idle.  In virtual
        mode the clock jumps straight to the next arrival when the
        target is idle (no empty spin steps)."""
        import time as _time
        res = OpenLoopResult()
        pending = list(self.items)
        t0 = self.clock()

        def due():
            while pending and pending[0].arrival_s + t0 <= self.clock():
                item = pending.pop(0)
                kw = {}
                if item.tenant != "default" or item.adapter_id is not None:
                    # only tenant workloads pass the tenancy kwargs, so
                    # a plain workload drives a pre-tenancy loop (or a
                    # FleetRouter) through the exact old call shape
                    kw = dict(tenant=item.tenant,
                              adapter_id=item.adapter_id)
                try:
                    req = self.loop.submit(
                        item.prompt,
                        max_new_tokens=item.max_new_tokens,
                        priority=item.priority, **kw)
                except QueueFullError:
                    res.rejected += 1
                except RateLimitedError:
                    res.rejected_rate_limited += 1
                except AdmissionError:
                    res.rejected_invalid += 1
                else:
                    res.requests.append(req)

        due()
        while pending or self.loop.has_work:
            if res.steps >= self.max_steps:
                raise RuntimeError(
                    f"open-loop run still has work after "
                    f"{self.max_steps} steps: starvation or wedge")
            if not self.loop.has_work:
                # idle gap before the next arrival
                if self.step_dt is not None:
                    gap = pending[0].arrival_s + t0 - self.clock()
                    if gap > 0:
                        self.clock.advance(gap)
                else:
                    _time.sleep(
                        max(0.0, pending[0].arrival_s + t0
                            - self.clock()))
                due()
                continue
            res.finished.extend(self.loop.step())
            if self.step_dt is not None:
                self.clock.advance(self.step_dt)
            res.steps += 1
            due()
        res.elapsed_s = self.clock() - t0
        return res


def calibrate_service_rate(make_loop, items: List[WorkloadItem],
                           step_dt: float) -> float:
    """Measured service capacity, in requests per virtual second: run
    the whole workload fully BACKLOGGED (every arrival at t=0) through
    a fresh loop and divide.  Deterministic, so the sweep's ρ axis
    (`rate_rps = rho * mu`) means the same thing on every run.

    `make_loop` returns a fresh `(loop, clock)` pair — calibration must
    not warm the loop the measured arms run on (prefix caches,
    schedulers), though sharing one ENGINE with the arms is fine (and
    keeps compile caches warm)."""
    loop, clock = make_loop()
    backlog = [WorkloadItem(index=it.index, arrival_s=0.0,
                            prompt=it.prompt,
                            max_new_tokens=it.max_new_tokens,
                            priority=it.priority,
                            shared_prefix=it.shared_prefix)
               for it in items]
    res = OpenLoopDriver(loop, clock, backlog, step_dt=step_dt).run()
    if res.lost or res.rejected or res.rejected_invalid:
        raise RuntimeError(
            f"calibration run lost work (lost={res.lost} "
            f"rejected={res.rejected} invalid={res.rejected_invalid}): "
            f"size the queue/engine to hold the whole workload")
    if res.elapsed_s <= 0:
        raise RuntimeError("calibration run took zero virtual time")
    return len(items) / res.elapsed_s
