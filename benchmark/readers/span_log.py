"""A statistic of the program's host-clock log over a phase of the run.

The program keeps two process-wide rings whether or not anybody traces
(`deepspeed_tpu/utils/spans.py`): one record a step span (`serve.step`,
`train.step`: duration, the part of it spent waiting inside `engine.fetch`,
the part spent in garbage collections) and one record for any other span
that lasted 50 ms or more (set-up's import, builds and compiling
dispatches; long fetches; collections).  A program without them (an older
commit) gives nothing to read.

The window on the log's clock (`window_of`): the traffic kinds hand readers
`stats["counters"]["steps"]`, the steps they counted inside the window, and
no clock.  No step runs after the window closes, so the window is the last
`steps` step records of the process; `train_steps` leaves one more step
queued when it closes ("the queued step is not counted"), so there the one
after them is left out (`QUEUED`).  Set-up is everything before the
window's first step.  One run a process, as `python3 -m benchmark.run` is.

`stat`:
  step_max_ms       the longest step record of the window
  step_host_max_ms  the largest `duration - wait` of them: a pause on the
                    host's side (where `step_max_ms` is seconds and this is
                    not, the wait was for the device or the runtime)
  gc_ms_per_s       the window's steps' `gc` summed, per second from the
                    first of them to the end of the last
  setup_s           seconds covered before the window by the long-span
                    records whose name matches `spans`

On its first call the reader prints one `[bench] {"long_spans": ...}` line
on stderr: numbers say that a pause happened, the line says under which
span.  `benchmark/SETUP_AND_PAUSES.md` has the rest.
"""
import json
import re
import sys
from typing import Dict, List, Optional

from benchmark.trace_reduce import covered, union

# step records after the window's last, by step span: what the traffic
# kind had dispatched and did not count when the window closed
QUEUED = {"serve.step": 0, "train.step": 1}
LINE_RECORDS = 10

_printed = False


def window_of(view: dict, queued: Optional[Dict[str, int]] = None
              ) -> Optional[dict]:
    """{"span": the step span's name, "steps": the window's step records,
    "t_first", "t_last": its extent in the log's nanoseconds, "long": every
    long-span record}, or None where there is no log or it holds fewer step
    records than the window counted."""
    try:
        from deepspeed_tpu.utils import spans
        records, long = list(spans.steps()), list(spans.long_spans())
    except (ImportError, AttributeError):
        return None
    n = int(view["stats"].get("counters", {}).get("steps") or 0)
    if not n or not records:
        return None
    name = records[-1].name
    records = [r for r in records if r.name == name]
    after = (QUEUED if queued is None else queued).get(name, 0)
    if len(records) < n + after:
        return None
    steps = records[len(records) - after - n:len(records) - after]
    return {"span": name, "steps": steps, "long": long,
            "t_first": steps[0].t0,
            "t_last": steps[-1].t0 + steps[-1].duration}


def _ms(ns: int) -> float:
    return ns * 1e-6


def _line(w: dict) -> dict:
    """The `[bench]` line: set-up's and the window's longest records."""
    by_step = {r.step: r for r in w["steps"]}

    def long_row(r) -> dict:
        row = {"name": r.name, "parent": r.parent, "step": r.step,
               "ms": _ms(r.duration)}
        row.update({k: v for k, v in r.attrs.items()
                    if isinstance(v, (int, float, str)) and k != "step"})
        return row

    def longest(rows: List[dict]) -> List[dict]:
        return sorted(rows, key=lambda row: -row["ms"])[:LINE_RECORDS]

    before = [r for r in w["long"] if r.t0 < w["t_first"]]
    inside = [r for r in w["long"]
              if w["t_first"] <= r.t0 < w["t_last"]]
    seconds: Dict[str, float] = {}
    for r in before:
        seconds[r.name] = seconds.get(r.name, 0.0) + r.duration * 1e-9
    in_window = [{"name": r.name, "parent": None, "step": r.step,
                  "ms": _ms(r.duration), "wait": _ms(r.wait),
                  "gc": _ms(r.gc)} for r in w["steps"]]
    # the steps with the most host time (duration - wait), which the
    # longest steps hide where every long step is a wait for a prefill
    host = [dict(row, under={r.name: _ms(r.duration) for r in inside
                             if r.step == row["step"]})
            for row in sorted(in_window, key=lambda row: row["wait"]
                              - row["ms"])[:LINE_RECORDS // 2]]
    for r in inside:
        row, step = long_row(r), by_step.get(r.step)
        if step is not None:
            row.update(wait=_ms(step.wait), gc=_ms(step.gc))
        in_window.append(row)
    return {"span": w["span"], "steps": len(w["steps"]),
            "window_s": (w["t_last"] - w["t_first"]) * 1e-9,
            "setup_s_by_span": dict(sorted(seconds.items(),
                                           key=lambda kv: -kv[1])),
            "setup": longest([long_row(r) for r in before]),
            "window": longest(in_window),
            "window_host": host}


def read(view, stat: str, span: Optional[str] = None,
         spans: Optional[str] = None, queued: Optional[dict] = None):
    global _printed
    w = window_of(view, queued)
    if w is None:
        return None
    if not _printed:
        _printed = True
        print("[bench] " + json.dumps({"long_spans": _line(w)}, default=str),
              file=sys.stderr, flush=True)
    if stat == "setup_s":
        found = [(r.t0, r.t0 + r.duration) for r in w["long"]
                 if r.t0 < w["t_first"] and re.search(spans, r.name)]
        return covered(union(found)) * 1e-9 if found else None
    if span is not None and span != w["span"]:
        return None
    steps = w["steps"]
    if stat == "step_max_ms":
        return _ms(max(r.duration for r in steps))
    if stat == "step_host_max_ms":
        return _ms(max(r.duration - r.wait for r in steps))
    if stat == "gc_ms_per_s":
        return _ms(sum(r.gc for r in steps)) \
            / ((w["t_last"] - w["t_first"]) * 1e-9)
    raise ValueError(f"span_log: no statistic {stat!r}")
