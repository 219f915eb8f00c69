"""From a profiler trace (xplane) to numbers: device busy and idle time,
device time per program and per op, idle gaps named by what the host was
doing.

Reads `jax.profiler.ProfileData`: planes -> lines -> events with `name`,
`start_ns`, `duration_ns`.  On a TPU the device planes are named
"/device:TPU:<n>"; their "XLA Modules" line holds one event per program run
and their "XLA Ops" line one per op (a `while` or a `call` holds its body's
ops inside its own interval, so an op's time here is its SELF time: its
interval less its children's).  The benchmark's `TraceAnnotation`s are
events named "bench.*" on a host plane's thread lines.

`reduce_planes` takes any objects of that shape, so the reduction is tested
on a small synthetic trace (tests/benchmark/test_trace_reduce.py).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANNOTATION = re.compile(r"^bench\.")

Interval = Tuple[int, int]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """events (start, end, name), possibly nested -> self nanoseconds by
    name: an event's interval less the intervals of the events inside it."""
    out: Dict[str, int] = {}
    stack: List[List] = []            # [start, end, name, child_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            s, e, name, child = stack.pop()
            out[name] = out.get(name, 0) + (e - s) - child
            if stack:
                stack[-1][3] += e - s

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        stack.append([s, e, name, 0])
    close(1 << 62)
    return out


def op_name(event_name: str) -> str:
    """An "XLA Ops" event is named by its whole HLO line; keep the op's
    name, its opcode, its (first) output shape and, for a custom call, its
    target: "%closed_call.10 = bf16[64,28,128]{...} custom-call(...),
    custom_call_target="tpu_custom_call"" -> "closed_call.10 custom-call
    bf16[64,28,128] tpu_custom_call"."""
    name, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:80]
    depth = 0
    for i, ch in enumerate(rest):        # the shape ends at the first
        depth += ch in "({[" and 1       # space outside any bracket
        depth -= ch in ")}]" and 1
        if ch == " " and depth == 0:
            break
    shape = re.match(r"\(?([a-z0-9]+\[[^\]]*\])", rest[:i])
    opcode = re.match(r"[\w\-]+", rest[i + 1:])
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    parts = [name.lstrip("%"), opcode.group(0) if opcode else "",
             shape.group(1) if shape else "",
             target.group(1) if target else ""]
    return " ".join(p for p in parts if p)


def program_name(event_name: str) -> str:
    """"jit_decode_step(1234567)" -> "jit_decode_step"."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce_planes(planes) -> dict:
    """The whole reduction; times in seconds, averaged over device planes."""
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError(f"no device plane in the trace "
                         f"(planes: {[p.name for p in planes]})")
    host_spans: List[Tuple[int, int, str]] = []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            for ev in line.events:
                if ANNOTATION.match(ev.name):
                    s = int(ev.start_ns)
                    host_spans.append((s, s + int(ev.duration_ns), ev.name))
    per_device = [_reduce_device(p, host_spans) for p in devices]
    n = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device) / n  # noqa: E731

    def merged(key):
        acc: Dict[str, float] = {}
        for d in per_device:
            for name, v in d[key].items():
                acc[name] = acc.get(name, 0.0) + v / n
        return acc

    programs = {}
    for d in per_device:
        for name, rec in d["programs"].items():
            tot = programs.setdefault(name, {"runs": 0.0, "device_s": 0.0,
                                             "run_s": [], "ops": {}})
            tot["runs"] += rec["runs"] / n
            tot["device_s"] += rec["device_s"] / n
            tot["run_s"] += rec["run_s"]       # every run of every device
            for op, v in rec["ops"].items():
                tot["ops"][op] = tot["ops"].get(op, 0.0) + v / n
    ops, gaps = merged("ops"), merged("gaps")
    return {
        "devices": n, "window_s": mean("window_s"), "busy_s": mean("busy_s"),
        "programs": programs, "ops": ops,
        "top_ops": sorted(([k, v] for k, v in ops.items()),
                          key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
    }


def _reduce_device(plane, host_spans) -> dict:
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        raise ValueError(f"{plane.name} has no {OPS_LINE!r} line "
                         f"(lines: {sorted(lines)})")
    ops = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
            op_name(e.name)) for e in lines[OPS_LINE].events]
    modules = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                program_name(e.name))
               for e in lines[MODULES_LINE].events] \
        if MODULES_LINE in lines else []
    busy = union((s, e) for s, e, _ in ops)
    # the window of this device: from the first host span or device event
    # to the last (the profiler runs a little longer on either side)
    marks = [s for s, _, _ in host_spans] + [e for _, e, _ in host_spans]
    lo = min(marks) if marks else min(s for s, _, _ in ops)
    hi = max(marks) if marks else max(e for _, e, _ in ops)
    busy = [(max(a, lo), min(b, hi)) for a, b in busy
            if min(b, hi) > max(a, lo)]
    # idle gaps, charged to the host spans they lie under, by overlap
    # (the benchmark's spans follow one another on one thread)
    gaps: Dict[str, float] = {}
    spans = sorted(host_spans)
    first = 0
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        while first < len(spans) and spans[first][1] <= a:
            first += 1
        under = 0
        for s, e, name in spans[first:]:
            if s >= b:
                break
            cover = min(e, b) - max(s, a)
            if cover > 0:
                gaps[name] = gaps.get(name, 0.0) + cover * 1e-9
                under += cover
        if b - a > under:
            gaps["_no_annotation_"] = gaps.get("_no_annotation_", 0.0) \
                + (b - a - under) * 1e-9
    # per program: its runs, its device time, and its ops' self time
    programs: Dict[str, dict] = {}
    mod_sorted = sorted(modules)
    for s, e, name in mod_sorted:
        rec = programs.setdefault(name, {"runs": 0, "device_s": 0.0,
                                         "run_s": [], "ops": {},
                                         "_events": []})
        rec["runs"] += 1
        inside = [op for op in ops if op[0] >= s and op[1] <= e]
        rec["run_s"].append(
            covered(union((a, b) for a, b, _ in inside)) * 1e-9)
        rec["device_s"] += rec["run_s"][-1]
        rec["_events"].extend(inside)
    for rec in programs.values():
        rec["ops"] = {k: v * 1e-9
                      for k, v in self_times(rec.pop("_events")).items()}
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": covered(busy) * 1e-9,
        "programs": programs, "gaps": gaps,
        "ops": {k: v * 1e-9 for k, v in self_times(ops).items()},
    }


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the newest `*.xplane.pb` under a `jax.profiler` directory."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    return reduce_planes(list(ProfileData.from_file(files[-1]).planes))
