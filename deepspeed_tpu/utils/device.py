"""The device this process runs on: one question, one answer.

Every kernel gate, the accelerator auto-detect and `chip_smoke.py` ask
`platform()`; nothing else in the package probes the backend.  Exceptions
from `jax.devices()` propagate: a process that cannot reach its device
must fail there, not run a slower path that looks plausible.

Also here, because they are process-wide the same way: where the
persistent compile cache lives, and the count of what JAX compiled.
"""
from __future__ import annotations

import os

import jax

__all__ = ["platform", "on_tpu", "place_compile_cache", "CompileCounter"]


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


class CompileCounter:
    """Counts what JAX compiled and what its persistent cache served."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"compiled": self.requests - self.hits,
                "from_cache": self.hits,
                "backend_compile_s": round(self.compile_s, 2)}
