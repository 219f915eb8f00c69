"""Recompile flight recorder: mid-serve XLA compiles as first-class,
counted, timestamped events.

A serving recompile is the silent latency cliff: a request shape that
misses every warmed program bucket stalls the whole batch for a
multi-second compile, and before this module the only way to catch it
was a side effect — `transfer_guard="disallow"` happening to trip on
the fresh trace constants (PR 4).  The recorder makes it direct:

- **Compile-event hook.**  jax publishes per-compile durations through
  `jax.monitoring` (`/jax/core/compile/backend_compile_duration` fires
  once per backend compile — probed, not assumed).  The package has ONE
  listener on that stream, `utils.device.CompileCounter`'s (it keeps the
  process's log by phase and function name); an armed recorder
  subscribes to it and is held weakly, so recorders come and go without
  leaking listeners.
- **Timestamped + bounded.**  Each event lands in a `MetricRing` row
  {t, event, duration_s} on the recorder's clock (the serve FakeClock
  in tests — deterministic), evicted-and-counted past `capacity`.
- **Trace-visible.**  `chrome_trace(requests, recompiles=recorder)`
  renders the events as instants on their own process row, so a
  perfetto timeline shows exactly which requests' spans straddle a
  compile stall.
- **Program-cache census.**  `census(engine)` snapshots the compiled-
  variant count of every serving program (the module-level jitted
  `ragged_ops` entry points + anything cache-bearing on the engine's
  program namespace); `scan()` diffs against the last snapshot, so a
  recompile is attributable to the PROGRAM that grew, not just to "jax
  compiled something".

The recorder observes only while armed (`start()`/`stop()` or the
context manager): a stopped recorder is not subscribed and costs
nothing.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

from ...utils.device import CompileCounter
from .metrics import MetricRing

__all__ = ["RecompileFlightRecorder", "COMPILE_EVENTS",
           "program_cache_census"]

#: the jax.monitoring duration events that mean "a backend compile
#: happened" (trace/lowering events are excluded
#: on purpose — re-tracing a cached program is not a recompile)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


def program_cache_census(engine=None) -> Dict[str, int]:
    """Compiled-variant count per serving program: every module-level
    jitted `ragged_ops` entry point, plus — given an engine — whatever
    its `_programs` namespace binds (the fused-TP programs carry their
    own jitted members).  Keys are stable program names; values are
    `jax.jit`'s `_cache_size()` (distinct compiled shapes)."""
    import functools
    out: Dict[str, int] = {}
    seen_fns: set = set()

    def add(name: str, fn) -> None:
        while isinstance(fn, functools.partial):
            fn = fn.func
        if id(fn) in seen_fns:
            return      # an engine _programs member partial-binding a
        #                 module-level program is the SAME program
        size = getattr(fn, "_cache_size", None)
        if callable(size):
            seen_fns.add(id(fn))
            out[name] = int(size())

    from ...inference.v2 import ragged_ops
    for name in ("prefill_chunks", "prefill_full", "decode_step",
                 "decode_tokens", "verify_tokens",
                 "sample_tokens_compiled"):
        fn = getattr(ragged_ops, name, None)
        if fn is not None:
            add(f"ragged_ops.{name}", fn)
    programs = getattr(engine, "_programs", None)
    if programs is not None:
        for name, fn in vars(programs).items():
            if name.startswith("_") or not callable(fn):
                continue
            add(f"engine.{name}", fn)
    return out


class RecompileFlightRecorder:
    """Armed window of compile events + program-cache attribution."""

    def __init__(self, clock=None, capacity: int = 1024, engine=None):
        self.clock = clock or time.monotonic
        self.engine = engine
        self.ring = MetricRing(capacity)
        self.total_events = 0
        self.total_compile_s = 0.0
        self._armed = False
        self._baseline: Dict[str, int] = {}

    # -- arming -----------------------------------------------------------
    def start(self) -> "RecompileFlightRecorder":
        if not self._armed:
            CompileCounter.subscribe(self._on_compile)
        self._armed = True
        self._baseline = program_cache_census(self.engine)
        return self

    def stop(self) -> None:
        self._armed = False
        CompileCounter.unsubscribe(self._on_compile)

    def __enter__(self) -> "RecompileFlightRecorder":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def armed(self) -> bool:
        return self._armed

    # -- the hook ---------------------------------------------------------
    def _on_compile(self, event: str, duration_s: float) -> None:
        if not self._armed or event not in COMPILE_EVENTS:
            return
        self.total_events += 1
        self.total_compile_s += float(duration_s)
        self.ring.record({"t": float(self.clock()), "event": event,
                          "duration_s": float(duration_s)})

    # -- attribution ------------------------------------------------------
    def scan(self) -> Dict[str, int]:
        """Serving programs whose compiled-variant count GREW since the
        last `start()`/`scan()` — the census attribution of whatever
        compile events just fired.  (Compiles outside the serving
        programs — a user jit, a bench helper — show up in the event
        count but not here, which is itself diagnostic.)"""
        now = program_cache_census(self.engine)
        grew = {name: n - self._baseline.get(name, 0)
                for name, n in now.items()
                if n > self._baseline.get(name, 0)}
        self._baseline = now
        return grew

    def events(self) -> List[Dict[str, Any]]:
        """The ring-resident compile events, oldest first."""
        return list(self.ring.rows)

    def summary(self) -> Dict[str, Any]:
        return {
            "recompiles": self.total_events,
            "compile_wall_s": self.total_compile_s,
            "ring_rows": len(self.ring.rows),
            "ring_evicted": self.ring.evicted,
        }
