"""Seconds of set-up that JAX spent in the named phases of making a
program, from the program's one compile counter
(`deepspeed_tpu/utils/device.py` `CompileCounter.events()`: every
`jax.monitoring` duration event of the process by `phase` and `fun_name`,
always on).  A program without that log (an older commit) gives nothing to
read.

`phases` (any of): `jaxpr_trace` (Python -> jaxpr: what a new kernel or a
new layer adds to every program that holds it, warm cache or cold),
`jaxpr_to_mlir_module` (jaxpr -> StableHLO, Mosaic kernels lowered here),
`backend_compile` (XLA: on a warm persistent cache the look-up and the
read, on a cold one the compile) and `cache_retrieval` (the read alone; it
lies inside `backend_compile`).  Events nest (a jitted function traced
inside another reports its own `jaxpr_trace`), so the seconds are those
COVERED by the phases' intervals, not their sum.

The phase of the run is cut where `span_log.window_of` cuts it: set-up is
everything before the window's first step record.  On its first call the
reader prints one `[bench] {"compile_time": ...}` line on stderr: covered
seconds by phase before and inside the window, the cache's hits and
misses, the ten functions that took the most, and how many `events` it
saw (the counter's ring is bounded: at its size the oldest were lost).
"""
import json
import sys
from typing import Dict, List

from benchmark.readers.span_log import LINE_RECORDS, window_of
from benchmark.trace_reduce import covered, union

_printed = False


def covered_s(events, phases) -> float:
    return covered(union(
        (e.start, e.start + int(e.seconds * 1e9))
        for e in events if e.phase in phases)) * 1e-9


def _line(before: List, inside: List) -> dict:
    names = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile",
             "cache_retrieval")
    by_fun: Dict[str, float] = {}
    for e in before:
        # nested traces report the inner function too: the outermost
        # names (`jit(...)` modules and the traced entry points) lead
        if e.fun_name is not None:
            by_fun[e.fun_name] = by_fun.get(e.fun_name, 0.0) + e.seconds
    counts = {k: sum(1 for e in before if e.phase == k)
              for k in ("cache_request", "cache_hit", "cache_miss")}
    return {"events": len(before) + len(inside),
            "setup_s": {k: covered_s(before, (k,)) for k in names},
            "window_s": {k: covered_s(inside, (k,)) for k in names},
            "setup_cache": counts,
            "setup_s_by_function": dict(sorted(
                by_fun.items(), key=lambda kv: -kv[1])[:LINE_RECORDS])}


def read(view, phases):
    global _printed
    try:
        from deepspeed_tpu.utils.device import CompileCounter
        events = list(CompileCounter.events())
    except (ImportError, AttributeError):
        return None
    w = window_of(view)
    if w is None:
        return None
    before = [e for e in events if e.start < w["t_first"]]
    inside = [e for e in events if w["t_first"] <= e.start < w["t_last"]]
    if not _printed:
        _printed = True
        print("[bench] " + json.dumps({"compile_time": _line(before,
                                                             inside)}),
              file=sys.stderr, flush=True)
    if not any(e.phase in phases for e in before):
        return None
    return covered_s(before, phases)
