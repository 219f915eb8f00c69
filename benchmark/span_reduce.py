"""What the PROGRAM says about a traced run: the device's idle time by the
program's own host spans, and its busy time by the program's own scopes and
kernel names.

The program opens `jax.profiler.TraceAnnotation`s (`serve.*`, `engine.*`,
`train.*`: deepspeed_tpu/utils/spans.py) and names its device work
(`jax.named_scope`, `pallas_call(name=)`); with a profiler session open
both land in the xplane file `harness.run_cell` has the profiler write,
beside the "XLA Ops" line and on its clock.  `trace_reduce` keeps the
`bench.*` spans only and hands readers its own reduction, so the readers of
this module open the file again, once per process (`of_view`).  A program
that emits no such span (an older commit) gives them nothing to read: they
return None, and the metric is left out of the result line.

Over the window `trace_reduce` uses (first to last `bench.*` mark):

- idle by innermost span: every idle gap of the device (the complement of
  the union of its "XLA Ops" intervals) is cut at span edges and each piece
  charged to the innermost program span covering it, on the thread that
  holds the step spans (`serve.step`, `train.step`); a piece inside a step
  under no child goes to the step span itself, a piece outside every
  program span to `_outside_`.  The charges partition the idle time.
- steps: the step spans that lie wholly inside the window.
- device self time by scope, per program: an op is keyed by the scope path
  of its HLO `op_name` and by its instruction name
  (`%paged_attention_decode.3` -> a kernel's `name=`).  On the v5e the
  instruction name is the event's name, but the `op_name` path is a stat
  (`tf_op`) of the event's METADATA, which `ProfileData` does not hand out
  (`ProfileEvent.stats` holds the event's own three: device offset,
  duration, time scale).  `event_scopes` therefore reads the metadata
  tables straight from the file's protobuf wire format.

`reduce_planes` takes any objects of `ProfileData`'s shape, so all of this
is tested on a synthetic trace (tests/benchmark/test_span_reduce.py).
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from benchmark.trace_reduce import (ANNOTATION, DEVICE_PLANE, MODULES_LINE,
                                    OPS_LINE, covered, program_name,
                                    self_times, union)

U64 = (1 << 64) - 1
PROGRAM_SPAN = re.compile(r"^(serve|engine|train)\.")
STEP_SPANS = ("serve.step", "train.step")
OUTSIDE = "_outside_"
# the stats of an "XLA Ops" event that may hold the op's `op_name` path
# ("jit(decode_step)/.../attention/kv_write/scatter"), first found wins
SCOPE_STATS = ("tf_op", "op_name", "hlo_op_name")

Span = Tuple[int, int, str]


def base_name(event_name: str) -> str:
    """An annotation with attributes may appear as "name#k=v#"."""
    return event_name.split("#", 1)[0]


def instruction(event_name: str) -> str:
    """"%flash_attention_fwd.2 = (bf16[..." -> "flash_attention_fwd.2"."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")[:80]


def scope_path(event, program_id=None, scopes=None) -> str:
    """The op's `op_name` path: a stat of the event itself, else of its
    metadata (`scopes`: `event_scopes` of the file, by the enclosing
    program's id and the event's name), else ""."""
    for key, value in getattr(event, "stats", None) or ():
        if key in SCOPE_STATS and isinstance(value, str):
            return value.rstrip(":")
    if scopes:
        return scopes.get((program_id, event.name)) \
            or scopes.get((None, event.name), "")
    return ""


def program_id(module_event_name: str) -> Optional[int]:
    """"jit_decode_step(8175759840812209609)" -> 8175759840812209609."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return int(m.group(1)) & U64 if m else None


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: a varint
    as int, a length-delimited field as bytes."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        x = shift = 0
        while True:
            c = buf[i]
            i += 1
            x |= (c & 0x7F) << shift
            shift += 7
            if not c & 0x80:
                return x

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, wire, varint()
        elif wire == 2:
            size = varint()
            yield field, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield field, wire, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire}")


def event_scopes(path: str) -> Dict[Tuple[Optional[int], str], str]:
    """{(program id, event name): op_name path} from the event metadata of
    the device planes of an xplane file (tsl/profiler/protobuf/
    xplane.proto: XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5 (maps: key=1, value=2); XEventMetadata.name=2,
    stats=5; XStatMetadata.name=2; XStat.metadata_id=1, uint64_value=3,
    int64_value=4, str_value=5).  Also under (None, name), for an op met
    outside any program run."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[Tuple[Optional[int], str], str] = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((v for f, _, v in parts if f == 2), b"").decode()
        if not DEVICE_PLANE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        for f, _, entry in parts:
            if f == 5:
                kv = {a: v for a, _, v in _fields(entry)}
                meta = {a: v for a, _, v in _fields(kv.get(2, b""))}
                stat_names[kv.get(1, 0)] = meta.get(2, b"").decode()
        for f, _, entry in parts:
            if f != 4:
                continue
            kv = {a: v for a, _, v in _fields(entry)}
            ev_name, scope, prog = "", None, None
            for a, _, v in _fields(kv.get(2, b"")):
                if a == 2:
                    ev_name = v.decode(errors="replace")
                elif a == 5:
                    stat = {b: x for b, _, x in _fields(v)}
                    stat_name = stat_names.get(stat.get(1))
                    if stat_name in SCOPE_STATS and 5 in stat:
                        scope = stat[5].decode(errors="replace").rstrip(":")
                    elif stat_name == "program_id":
                        prog = stat.get(3, stat.get(4))
            if scope:
                if prog is not None:
                    out[(prog & U64, ev_name)] = scope
                out.setdefault((None, ev_name), scope)
    return out


def scope_label(path: str) -> str:
    """The path without its program prefix ("jit(f)/jit(main)/") and its
    last component (the primitive): what the scope table is keyed by."""
    parts = [p for p in path.split("/") if p]
    while parts and re.match(r"^(jit|pjit)\(", parts[0]):
        parts.pop(0)
    return "/".join(parts[:-1])


def innermost_segments(spans: List[Span]) -> List[Span]:
    """Properly nested spans of one thread -> non-overlapping (start, end,
    name) pieces covering the same time, each named by the innermost span
    over it."""
    out: List[Span] = []
    stack: List[Tuple[int, str]] = []            # (end, name)
    at = 0

    def advance(to: int) -> None:
        """The piece [at, to) belongs to the top of the stack."""
        nonlocal at
        if stack and to > at:
            out.append((at, to, stack[-1][1]))
        at = max(at, to) if stack else to

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        advance(s)
        # a child never outlasts its parent (clock jitter at the edges)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return out


def charge_gaps(gaps: List[Tuple[int, int]], segments: List[Span]
                ) -> Dict[str, int]:
    """Nanoseconds of `gaps` (sorted, disjoint) under each segment name;
    what no segment covers goes to `_outside_`."""
    out: Dict[str, int] = {}
    first = 0
    for a, b in gaps:
        while first < len(segments) and segments[first][1] <= a:
            first += 1
        under = 0
        for s, e, name in segments[first:]:
            if s >= b:
                break
            cover = min(e, b) - max(s, a)
            if cover > 0:
                out[name] = out.get(name, 0) + cover
                under += cover
        if b - a > under:
            out[OUTSIDE] = out.get(OUTSIDE, 0) + (b - a - under)
    return out


def _program_thread(planes) -> List[Span]:
    """The program's spans on the one host line that holds the most step
    spans."""
    best: List[Span] = []
    best_steps = 0
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            spans = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                      base_name(e.name)) for e in line.events
                     if PROGRAM_SPAN.match(e.name)]
            steps = sum(1 for sp in spans if sp[2] in STEP_SPANS)
            if steps > best_steps:
                best, best_steps = spans, steps
    return best


def reduce_planes(planes, scopes=None) -> dict:
    """The whole reduction; seconds, averaged over device planes.  `scopes`:
    `event_scopes` of the file the planes came from."""
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError(f"no device plane in the trace "
                         f"(planes: {[p.name for p in planes]})")
    marks: List[int] = []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            for e in line.events:
                if ANNOTATION.match(e.name):
                    marks += [int(e.start_ns),
                              int(e.start_ns) + int(e.duration_ns)]
    spans = _program_thread(planes)
    segments = innermost_segments(spans)
    n = len(devices)
    idle: Dict[str, float] = {}
    programs: Dict[str, dict] = {}
    window_s = busy_s = 0.0
    lo = hi = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line")
        events = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e)
                  for e in lines[OPS_LINE].events]
        lo = min(marks) if marks else min(s for s, _, _ in events)
        hi = max(marks) if marks else max(e for _, e, _ in events)
        busy = [(max(a, lo), min(b, hi))
                for a, b in union((s, e) for s, e, _ in events)
                if min(b, hi) > max(a, lo)]
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, ns in charge_gaps(gaps, segments).items():
            idle[name] = idle.get(name, 0.0) + ns * 1e-9 / n
        window_s += (hi - lo) * 1e-9 / n
        busy_s += covered(busy) * 1e-9 / n
        events.sort(key=lambda t: t[0])
        starts = [op[0] for op in events]
        for m in (lines[MODULES_LINE].events if MODULES_LINE in lines
                  else ()):
            ms, me = int(m.start_ns), int(m.start_ns) + int(m.duration_ns)
            pid = program_id(m.name)
            inside = [(s, e, scope_path(ev, pid, scopes) + " "
                       + instruction(ev.name))
                      for s, e, ev in events[bisect.bisect_left(starts, ms):
                                             bisect.bisect_right(starts, me)]
                      if e <= me]
            rec = programs.setdefault(program_name(m.name),
                                      {"device_s": 0.0, "_events": []})
            rec["device_s"] += covered(
                union((a, b) for a, b, _ in inside)) * 1e-9 / n
            rec["_events"] += inside
    for rec in programs.values():
        rec["ops"] = {k: v * 1e-9 / n
                      for k, v in self_times(rec.pop("_events")).items()}
    steps = [sp for sp in spans if sp[2] in STEP_SPANS
             and sp[0] >= lo and sp[1] <= hi]
    starts = sorted(sp[0] for sp in steps)
    return {
        "devices": n, "window_s": window_s, "busy_s": busy_s,
        "steps": len(steps), "idle_s": idle,
        "seen": sorted({sp[2] for sp in spans}),
        "step_span_ms_p50": statistics.median(
            (sp[1] - sp[0]) * 1e-6 for sp in steps) if steps else None,
        "step_period_ms_p50": statistics.median(
            (b - a) * 1e-6 for a, b in zip(starts, starts[1:]))
        if len(starts) > 1 else None,
        "programs": programs,
    }


def scope_table(reduced: dict) -> Dict[str, Dict[str, float]]:
    """Per program, device self seconds by scope label (a kernel or an op
    under no scope goes by its instruction name)."""
    out: Dict[str, Dict[str, float]] = {}
    for prog, rec in reduced["programs"].items():
        table: Dict[str, float] = {}
        for key, v in rec["ops"].items():
            path, _, instr = key.rpartition(" ")
            label = scope_label(path) or re.sub(r"\.\d+$", "", instr)
            table[label] = table.get(label, 0.0) + v
        out[prog] = dict(sorted(table.items(), key=lambda kv: -kv[1]))
    return out


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return files[-1] if files else None


_cache: Dict[Tuple[str, float], dict] = {}


def of_view(view: dict) -> Optional[dict]:
    """The reduction of the run a reader's `view` belongs to, or None where
    the run was not traced or left no file.  Computed once per file, and
    the whole split printed once as a `[bench]` line on stderr."""
    if not view.get("trace"):
        return None
    path = newest_xplane(os.path.join(
        os.path.dirname(view["bench_dir"]), ".cache", "bench_trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        from jax.profiler import ProfileData
        r = reduce_planes(list(ProfileData.from_file(path).planes),
                          event_scopes(path))
        _cache.clear()
        _cache[key] = r
        idle = sorted(r["idle_s"].items(), key=lambda kv: -kv[1])
        line = {"steps": r["steps"], "window_s": r["window_s"],
                "busy_s": r["busy_s"],
                "step_span_ms_p50": r["step_span_ms_p50"],
                "step_period_ms_p50": r["step_period_ms_p50"]}
        if r["steps"]:
            line["idle_ms_per_step"] = {k: 1e3 * v / r["steps"]
                                        for k, v in idle}
        else:                    # a program without step spans
            line["idle_s"] = dict(idle)
        line["scope_self_s"] = {
            p: dict(list(t.items())[:24])
            for p, t in scope_table(r).items()
            if r["programs"][p]["device_s"] >= 0.01 * r["busy_s"]}
        print("[bench] " + json.dumps({"program_spans": line}),
              file=sys.stderr, flush=True)
    return _cache[key]
