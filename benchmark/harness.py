"""One run of one cell: find the cell's files by the names in
`BENCHMARK.json`, hand them to the traffic kind, reduce what comes back to
the contract's result line.

Nothing here names a configuration, a traffic kind, a reader or a metric:
    BENCHMARK.json workloads[] {name, config, traffic}
    benchmark/configs/<config>.json          sizes, program section, reference
    benchmark/traffic/<traffic>.json         {"kind": ..., parameters}
    benchmark/traffic_kinds/<kind>.py        run(ctx) -> outcome
    benchmark/references/<reference>.py      the family's module: sizes,
                                             seeded weights, the plain
                                             float32 reference, the counts
    benchmark/metrics/<metric>.json          {"reader": ..., "params": ...}
    benchmark/readers/<reader>.py            read(view, **params) -> number|None
so a later PR adds cells and metrics as files and edits none.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(bench_dir: str, group: str, name: str):
    """benchmark/<group>/<name>.py, found by name."""
    path = os.path.join(bench_dir, group, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {group[:-1]} {name!r}: {path} is missing")
    mod_name = f"benchmark.{group}.{name}"
    if mod_name in sys.modules and getattr(
            sys.modules[mod_name], "__file__", None) == path:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def peaks_of(view: dict) -> dict:
    """The published peaks of the device a reader's view ran on; a kind
    that `peaks.json` does not list is an error, never a default."""
    peaks = load_json(view["bench_dir"], "peaks.json")
    if view["device_kind"] not in peaks:
        raise KeyError(f"no published peaks for {view['device_kind']!r} "
                       f"in peaks.json")
    return peaks[view["device_kind"]]


def load_cell(root: str, bench_dir: str, workload: str) -> dict:
    """The `workloads` entry of that name with the whole `BENCHMARK.json`
    (`bench`) and the cell's two files read (`config_file`,
    `traffic_file`): the one lookup every entry goes through."""
    bench = load_json(root, "BENCHMARK.json")
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return {
                "bench": bench, **cell,
                "config_file": load_json(bench_dir, "configs",
                                         cell["config"] + ".json"),
                "traffic_file": load_json(bench_dir, "traffic",
                                          cell["traffic"] + ".json")}
    raise BenchmarkError(
        f"no workload {workload!r} in BENCHMARK.json "
        f"(have: {[c['name'] for c in bench['workloads']]})")


def pin_environment(config: dict, argv, t_start: float) -> None:
    """A configuration file may state process environment its deployment
    sets (`environment`, e.g. the allocator's tunables, which a process
    reads only as it starts).  Where it is not set yet, start again with
    it; the set-up clock keeps running."""
    wanted = config.get("environment", {})
    if any(os.environ.get(k) != v for k, v in wanted.items()):
        env = dict(os.environ, _BENCH_T_START=repr(t_start), **wanted)
        sys.stdout.flush()
        os.execve(sys.executable,
                  [sys.executable, "-m", "benchmark.run", *argv], env)


def require_chips(chips: int):
    """The devices of this run, or an error: a measurement that finds no
    chip, or fewer than the cell asks for, does not fall back."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(
            f"this benchmark measures a TPU and found platform "
            f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX reports {len(devices)}")
    return devices[:chips]


class Context:
    """What a traffic kind gets, and the marks it sets as it goes."""

    trace_seconds = 3.0

    def __init__(self, *, bench_dir, config, traffic, seed, seconds, trace,
                 devices, t_start, trace_dir, control=None):
        self.bench_dir, self.config, self.traffic = bench_dir, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        # None, or the lower precision (e.g. "int8") in which the kind puts
        # the reference in the program's place: the control of `correct`
        self.control = control
        self.devices, self.t_start, self.trace_dir = devices, t_start, trace_dir
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes: Optional[int] = None
        self.notes: Dict[str, Any] = {}
        self.compiles_at_open = self.compiles_in_window = None
        self._compiles = _CompileCount()
        self._tracing = False

    # -- marks -----------------------------------------------------------
    def window_opens(self) -> None:
        self.setup_s = time.time() - self.t_start
        self.compiles_at_open = self._compiles.n

    def window_closes(self) -> None:
        self.compiles_in_window = self._compiles.n - self.compiles_at_open

    def read_memory_peak(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak_bytes = int(max(peaks))

    def note(self, **fields) -> None:
        self.notes.update(fields)
        print("[bench] " + json.dumps(fields, default=str), file=sys.stderr,
              flush=True)

    # -- spans and the profiler ---------------------------------------------
    def annotate(self, name: str):
        if not self._tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        # the Python tracer hooks every call of the host loop and slows
        # it by a fifth; the annotations and the runtime's own spans stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True

    def stop_trace(self) -> None:
        import jax
        self._tracing = False
        jax.profiler.stop_trace()

    def reference(self):
        return load_module(self.bench_dir, "references",
                           self.config["reference"])


class _CompileCount:
    """Programs JAX was asked to compile since construction, whether the
    persistent cache then served them or not (`jax.monitoring`): inside
    the window there should be none."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_listener(self._on)

    def _on(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1


def per_layer_metrics(bench_dir: str, cell: dict, outcome: dict, reduced,
                      ctx: Context) -> Dict[str, dict]:
    """Every per-layer metric that lists this cell, read by its own reader."""
    view = {"stats": outcome["stats"], "trace": reduced,
            "config": ctx.config, "traffic": ctx.traffic,
            "model": ctx.reference() if "reference" in ctx.config else None,
            "end_to_end": outcome["end_to_end"],
            "device_kind": ctx.devices[0].device_kind,
            "chips": len(ctx.devices), "bench_dir": bench_dir}
    out = {}
    for m in cell["bench"]["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        spec = load_json(bench_dir, "metrics", m["name"] + ".json")
        reader = load_module(bench_dir, "readers", spec["reader"])
        value = reader.read(view, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, bench_dir: str = BENCH_DIR,
             t_start: Optional[float] = None, devices=None,
             control: Optional[str] = None) -> dict:
    """The result object of one run of a cell (`load_cell`).  `devices`
    skips the look for a chip (tests).  `control` names a lower precision:
    the kind then puts the reference, computed in it, in the program's
    place, and `correct` has to come out false (`benchmark/control.py`)."""
    t_start = time.time() if t_start is None else t_start
    workload = cell["name"]
    config, traffic = cell["config_file"], cell["traffic_file"]
    kind = load_module(bench_dir, "traffic_kinds", traffic["kind"])
    if devices is None:
        devices = require_chips(cell["chips"])
    ctx = Context(bench_dir=bench_dir, config=config, traffic=traffic,
                  seed=seed, seconds=seconds, trace=trace, devices=devices,
                  t_start=t_start, control=control,
                  trace_dir=os.path.join(root, ".cache", "bench_trace"))
    outcome = kind.run(ctx)
    ctx.note(end_to_end=outcome["end_to_end"], setup_s=ctx.setup_s,
             compiles_in_window=ctx.compiles_in_window)

    compared = {k: {"value": float(v), "limit": float(lim)}
                for k, (v, lim) in outcome["compared"].items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    names = {m["name"]: m for m in cell["bench"]["end_to_end"]}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": outcome["attempted"],
        "failed": outcome["failed"]}
    if trace:
        from benchmark import trace_reduce
        reduced = trace_reduce.reduce_dir(ctx.trace_dir)
        ctx.note(traced_programs={
            name: {"runs": rec["runs"], "device_s": rec["device_s"],
                   "longest_run_s": max(rec["run_s"]),
                   "shortest_run_s": min(rec["run_s"])}
            for name, rec in sorted(reduced["programs"].items(),
                                    key=lambda kv: -kv[1]["device_s"])[:8]})
        result["metrics"] = per_layer_metrics(bench_dir, cell, outcome,
                                              reduced, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        values = dict(outcome["end_to_end"], setup_s=ctx.setup_s)
        result["metrics"] = {
            n: {"value": float(values[n]), "unit": names[n]["unit"]}
            for n in names
            if workload in names[n].get("workloads", [workload])
            and values.get(n) is not None}
    result["device"] = device
    result["compiles_in_window"] = ctx.compiles_in_window
    result["control"] = control
    result["notes"] = ctx.notes
    result["compared"] = compared          # last, as the contract asks
    return result


def print_result(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    stderr, the result object as the last line of stdout."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"[compared] {name} = {c['value']:.6g}  limit {c['limit']:.6g}"
              f"  {'ok' if c['value'] <= c['limit'] else 'OVER'}",
              file=sys.stderr)
    print(f"[compared] correct = {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result, default=str), flush=True)


def main(argv, t_start: float) -> int:
    """`python3 -m benchmark.run ...`: one run of one cell."""
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(ROOT, BENCH_DIR, args.workload)
        pin_environment(cell["config_file"], argv, t_start)
        # libtpu's own log files stay out of /tmp
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from deepspeed_tpu.utils.device import place_compile_cache
        place_compile_cache()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print_result(result)
    return 0
