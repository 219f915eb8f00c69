"""The device this process runs on: one question, one answer.

Every kernel gate, the accelerator auto-detect and `chip_smoke.py` ask
`platform()`; nothing else in the package probes the backend.  Exceptions
from `jax.devices()` propagate: a process that cannot reach its device
must fail there, not run a slower path that looks plausible.

Also here, because they are process-wide the same way: where the
persistent compile cache lives, and the log of what JAX traced, lowered
and compiled (the package's one `jax.monitoring` listener).
"""
from __future__ import annotations

import collections
import os
import time
import weakref
from typing import Deque, List, NamedTuple, Optional

import jax
import jax.monitoring

__all__ = ["platform", "on_tpu", "place_compile_cache", "CompileCounter",
           "CompileEvent", "COMPILE_PHASES"]


def platform() -> str:
    """Platform name of the first device JAX reports ("tpu", "cpu", ...)."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return platform() == "tpu"


def place_compile_cache(min_compile_secs: float = 0.0) -> str:
    """Point JAX's persistent compilation cache somewhere it can be found
    again.  Where `JAX_COMPILATION_CACHE_DIR` is set JAX already reads it:
    do nothing and set no directory in code.  Otherwise
    `<checkout>/.cache/xla`: fixed, derived from the package's location
    (the directory is part of the cache key — a path that moves never
    hits).  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".cache", "xla")
    jax.config.update("jax_compilation_cache_dir", path)
    # default floor 0: JAX's own default skips anything that compiled in
    # under a second, which is most of the serving programs' buckets
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# -- what JAX compiled: the package's one `jax.monitoring` listener ------------
# the three phases jax reports with the name of the function they worked
# on, and the persistent cache's own read
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    # wraps the look in the persistent cache: on a hit it is the
    # retrieval, on a miss the compile (and the write)
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_request",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
# bounded like the rings of utils/spans.py; a traced program reports every
# jitted function traced inside it, thousands of events a serving set-up
COMPILE_RING = 65_536


class CompileEvent(NamedTuple):
    phase: str                 # a value of COMPILE_PHASES or CACHE_EVENTS
    fun_name: Optional[str]    # as jax names the function; the cache: None
    start: int                 # `time.perf_counter_ns`, as a span's `t0`
    seconds: float             # 0.0 for a cache event


_events: Deque[CompileEvent] = collections.deque(maxlen=COMPILE_RING)
_totals = {"requests": 0, "hits": 0, "compile_s": 0.0}
_subscribers: List[weakref.WeakMethod] = []


def _on_event(name: str, **kw) -> None:
    phase = CACHE_EVENTS.get(name)
    if phase is None:
        return
    if phase == "cache_request":
        _totals["requests"] += 1
    elif phase == "cache_hit":
        _totals["hits"] += 1
    _events.append(CompileEvent(phase, None, time.perf_counter_ns(), 0.0))


def _on_duration(name: str, seconds: float, **kw) -> None:
    phase = COMPILE_PHASES.get(name)
    if phase is None:
        return
    if phase == "backend_compile":
        _totals["compile_s"] += seconds
    _events.append(CompileEvent(
        phase, kw.get("fun_name"),
        time.perf_counter_ns() - int(seconds * 1e9), float(seconds)))
    for ref in list(_subscribers):
        callback = ref()
        if callback is None:
            _subscribers.remove(ref)
        else:
            callback(name, seconds)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


class CompileCounter:
    """What JAX compiled and what its persistent cache served since this
    counter was made.  The listener is the module's, installed once and
    listening from the import on: `events()` is the whole process's log,
    by phase and function name."""

    def __init__(self):
        self._base = dict(_totals)

    requests = property(lambda self: _totals["requests"]
                        - self._base["requests"])
    hits = property(lambda self: _totals["hits"] - self._base["hits"])
    compile_s = property(lambda self: _totals["compile_s"]
                         - self._base["compile_s"])

    @staticmethod
    def events() -> Deque[CompileEvent]:
        """The process's compile events, oldest first (the ring itself)."""
        return _events

    @staticmethod
    def subscribe(callback) -> None:
        """`callback(jax event name, seconds)` (a bound method, held
        weakly) at every duration event of COMPILE_PHASES from now on."""
        _subscribers.append(weakref.WeakMethod(callback))

    @staticmethod
    def unsubscribe(callback) -> None:
        _subscribers[:] = [ref for ref in _subscribers
                           if ref() not in (None, callback)]

    def snapshot(self) -> dict:
        return {"compiled": self.requests - self.hits,
                "from_cache": self.hits,
                "backend_compile_s": round(self.compile_s, 2)}
