"""Host spans in the profiler's own trace (reference: utils/nvtx.py
`instrument_w_nvtx` -> nsight ranges; here `jax.profiler.TraceAnnotation`
-> the xplane file of a `jax.profiler` session, beside the device's
"XLA Ops" line and on the same clock), and a host-clock log of the few of
them that matter when nobody traces.

`span` is the one place the program opens a host span.  There is no
switch: a span is always written as a profiler annotation (read only when
somebody traces: `python3 -m benchmark.run ... --trace 1`, or any
`jax.profiler.trace`) and always reads `time.perf_counter_ns` at both
ends.  What it does with the reading:

- a STEP span (`serve.step`, `train.step`) leaves one `StepRecord` in a
  bounded ring (`steps()`), every time: its duration, the part of it the
  thread spent inside `engine.fetch` spans (`wait`: for the device and
  the copy, so `duration - wait` is the host's own time) and the part the
  process spent collecting garbage (`gc`);
- any other span leaves a `LongRecord` in a second ring (`long_spans()`)
  only when it lasted `LONG_SPAN_NS` or more: in steady state nothing on
  the hot path does, so that ring holds set-up (builds, first dispatches
  that trace and compile), long fetches and pauses, and nothing else;
- garbage collection is a span too (`host.gc`, opened and closed from
  `gc.callbacks`): nothing about WHEN Python collects is changed.

Both rings are process-wide and appended to from any thread.  An
attribute known only at the span's end is added with `set_metadata(
**attrs)`.  docs/OBSERVABILITY.md has the catalogue and how to read a
pause from the rings.
"""
from __future__ import annotations

import collections
import gc
import threading
from time import perf_counter_ns
from typing import Any, Deque, Dict, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["span", "steps", "long_spans", "SPAN_NAMES", "STEP_SPANS",
           "STEP_RING", "LONG_RING", "LONG_SPAN_NS", "StepRecord",
           "LongRecord"]

# every span name the program emits (tests/test_tracing.py holds the code
# to it): the serve step's phases, the engine's three per program call,
# the train step's, and what set-up is made of
SPAN_NAMES = (
    "serve.step", "serve.finalize", "serve.admission", "serve.engine",
    "serve.sample", "serve.bookkeep", "serve.moe_census",
    "engine.plan", "engine.dispatch", "engine.fetch",
    "train.step", "train.shard_batch", "train.dispatch",
    "host.import", "host.gc", "engine.build", "train.build",
)
STEP_SPANS = ("serve.step", "train.step")
# the span a step's `wait` is made of: the thread blocks there until the
# device has the tokens and they are copied
WAIT_SPAN = "engine.fetch"
# more step records than the longest window any benchmark cell runs has
# steps (90 s of 13 ms steps: ~7,000)
STEP_RING = 16_384
LONG_RING = 4_096
LONG_SPAN_NS = 50_000_000


class StepRecord(NamedTuple):
    """One step span, in `perf_counter_ns` nanoseconds."""
    name: str
    step: Optional[int]      # the span's `step` attribute
    t0: int
    duration: int
    wait: int                # inside `engine.fetch` spans, this thread
    gc: int                  # inside garbage collections, any thread


class LongRecord(NamedTuple):
    """One other span that lasted `LONG_SPAN_NS` or more."""
    name: str
    parent: Optional[str]    # the span it was opened under, this thread
    step: Optional[int]      # of the step span around it, if any
    t0: int
    duration: int
    attrs: Dict[str, Any]


_steps: Deque[StepRecord] = collections.deque(maxlen=STEP_RING)
_long: Deque[LongRecord] = collections.deque(maxlen=LONG_RING)


def steps() -> Deque[StepRecord]:
    """The ring of step records, oldest first (the ring itself: take
    `list(...)` of it to keep a view)."""
    return _steps


def long_spans() -> Deque[LongRecord]:
    """The ring of long-span records, oldest first."""
    return _long


class _Thread(threading.local):
    top: Optional["_Span"] = None    # the innermost open span
    wait_ns = 0                      # in WAIT_SPAN spans since it was zeroed


_thread = _Thread()
# nanoseconds the process has spent in garbage collections.  One
# collection runs at a time and every thread stands still meanwhile, so a
# step charges itself whatever was added while it was open, whichever
# thread collected
_gc_ns = 0
_gc_open: Optional["_Span"] = None


_enter, _exit = TraceAnnotation.__enter__, TraceAnnotation.__exit__


class _Span(TraceAnnotation):
    """The profiler annotation, timed on the host's clock as well (`span`
    sets `name` and `attrs`)."""

    __slots__ = ("name", "attrs", "parent", "t0")

    def set_metadata(self, **attrs) -> None:
        self.attrs.update(attrs)
        TraceAnnotation.set_metadata(self, **attrs)

    def begun(self, t0_ns: int) -> None:
        """The work began at `t0_ns`, before this span could be opened
        (the package's import: the primitive is part of it)."""
        self.t0 = t0_ns

    def __enter__(self) -> "_Span":
        _enter(self)
        self.parent, _thread.top = _thread.top, self
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, exc_type=None, exc=None, tb=None):
        duration = perf_counter_ns() - self.t0
        _thread.top = self.parent
        if duration >= LONG_SPAN_NS:
            self._long(duration)
        return _exit(self, exc_type, exc, tb)

    def _long(self, duration: int) -> None:
        up = self.parent
        while up is not None and up.name not in STEP_SPANS:
            up = up.parent
        _long.append(LongRecord(
            self.name, self.parent.name if self.parent else None,
            up.attrs.get("step") if up else None, self.t0, duration,
            self.attrs))


class _WaitSpan(_Span):
    __slots__ = ()

    def __exit__(self, exc_type=None, exc=None, tb=None):
        _thread.wait_ns += perf_counter_ns() - self.t0
        return _Span.__exit__(self, exc_type, exc, tb)


class _GcSpan(_Span):
    __slots__ = ()

    def __exit__(self, exc_type=None, exc=None, tb=None):
        global _gc_ns
        _gc_ns += perf_counter_ns() - self.t0
        return _Span.__exit__(self, exc_type, exc, tb)


class _StepSpan(_Span):
    """`record`, once closed, is what the span left in the ring."""

    __slots__ = ("gc0", "record")

    def __enter__(self) -> "_StepSpan":
        _thread.wait_ns = 0
        self.gc0 = _gc_ns
        return _Span.__enter__(self)

    def __exit__(self, exc_type=None, exc=None, tb=None):
        self.record = StepRecord(
            self.name, self.attrs.get("step"), self.t0,
            perf_counter_ns() - self.t0, _thread.wait_ns,
            _gc_ns - self.gc0)
        _steps.append(self.record)
        _thread.top = self.parent
        return _exit(self, exc_type, exc, tb)


_KINDS = {WAIT_SPAN: _WaitSpan, "host.gc": _GcSpan,
          **{name: _StepSpan for name in STEP_SPANS}}


def span(name: str, **attrs) -> _Span:
    """Context manager: the named range on this thread's line of the
    profiler trace, `attrs` as its stats, and on the host-clock log what
    the module's docstring says of its kind."""
    opened = _KINDS.get(name, _Span)(name, **attrs)
    opened.name, opened.attrs = name, attrs
    return opened


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """`gc.callbacks`: a collection is the span `host.gc`."""
    global _gc_open
    if phase == "start":
        _gc_open = span("host.gc", generation=info["generation"])
        _gc_open.__enter__()
    elif _gc_open is not None:
        opened, _gc_open = _gc_open, None
        opened.set_metadata(collected=info["collected"])
        opened.__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
