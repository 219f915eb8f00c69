"""Host spans in the profiler's own trace (reference: utils/nvtx.py
`instrument_w_nvtx` -> nsight ranges; here `jax.profiler.TraceAnnotation`
-> the xplane file of a `jax.profiler` session, beside the device's
"XLA Ops" line and on the same clock).

`span` is the one place the program opens a host span.  With no profiler
session open an annotation costs about half a microsecond, so there is no
switch: the spans are always written, and read only when somebody traces
(`python3 -m benchmark.run ... --trace 1`, or any `jax.profiler.trace`).
An attribute known only at the span's end is added with the annotation's
own `set_metadata(**attrs)`.  docs/OBSERVABILITY.md has the catalogue.
"""
from __future__ import annotations

import jax

__all__ = ["span", "SPAN_NAMES"]

# every span name the program emits (tests/test_tracing.py holds the code
# to it): the serve step's phases, the engine's three per program call,
# the train step's
SPAN_NAMES = (
    "serve.step", "serve.finalize", "serve.admission", "serve.engine",
    "serve.sample", "serve.bookkeep", "serve.moe_census",
    "engine.plan", "engine.dispatch", "engine.fetch",
    "train.step", "train.shard_batch", "train.dispatch",
)


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """Context manager: the named range on this thread's line of the
    profiler trace, `attrs` as its stats."""
    return jax.profiler.TraceAnnotation(name, **attrs)
