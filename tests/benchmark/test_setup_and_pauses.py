"""The readers of the program's host-clock log (`benchmark/readers/
span_log.py`, `compile_time.py`) on the tiny cells, through the harness as
it runs them: every metric file over them reads a number, set-up's parts lie
under `setup_s`, and a planted pause shows where it should."""
import gc
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce

from bench_testlib import REPO

LOOP = "serve loop (serving/server.py ServeLoop.step, host side)"
TRAIN = "train engine (runtime/engine.py train_batch)"
SETUP = "set-up (package import, engine build, program trace and compile)"
CLOSED, TRAINED = "qwen2-tiny.closed", "opt-tiny.train"
SETUP_METRICS = ("setup_import_s", "setup_build_s", "setup_trace_lower_s",
                 "setup_xla_compile_s")
# every metric file this PR brought, as an entry over the tiny cells (the
# twins that move `ttft_ms_per_ktok_p50` read the closed cell here)
ENTRIES = [
    {"name": name, "unit": "s", "better": "lower", "layer": SETUP,
     "source": source, "moves": "setup_s", "workloads": [CLOSED, TRAINED]}
    for name, source in zip(SETUP_METRICS, (
        "program_span", "program_span", "program_counter",
        "program_counter"))
] + [
    {"name": name + suffix, "unit": unit, "better": "lower",
     "source": "program_span", "layer": LOOP, "moves": "ttft_p50_ms",
     "workloads": [CLOSED]}
    for suffix in (".closed", ".ktok.closed")
    for name, unit in (("step_ms_max", "ms"), ("step_host_ms_max", "ms"),
                       ("gc_ms_per_s", "ms/s"))
] + [
    {"name": name, "unit": unit, "better": "lower",
     "source": "program_span", "layer": TRAIN, "moves": "train_tok_s_chip",
     "workloads": [TRAINED]}
    for name, unit in (("step_ms_max.train", "ms"),
                       ("gc_ms_per_s.train", "ms/s"))
]


def fresh_log(programs_too=False):
    """What a new process has: empty rings, no compile event, and the
    package's import on record (this process imported it long ago; a ring
    may have dropped it since); with `programs_too`, no program in jit's
    own caches either, so that set-up traces and lowers again whatever an
    earlier test of this worker ran."""
    import jax
    from deepspeed_tpu.utils import spans
    from deepspeed_tpu.utils.device import CompileCounter
    if programs_too:
        jax.clear_caches()
    spans.steps().clear()
    spans.long_spans().clear()
    CompileCounter.events().clear()
    with spans.span("host.import") as imported:
        imported.begun(time.perf_counter_ns() - 100_000_000)


@pytest.fixture
def run_new(bench_root, run_tiny, monkeypatch):
    """A traced run of a tiny cell that reads this PR's metrics alone (the
    CPU has no device plane: the device's side of the trace is made up)."""
    path = os.path.join(bench_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["per_layer"] = ENTRIES
    json.dump(bench, open(path, "w"))
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda trace_dir: {
        "programs": {}, "busy_s": 0.9, "window_s": 3.0, "top_ops": [],
        "idle_gaps": []})

    def run(workload, programs_too=False):
        fresh_log(programs_too)
        return run_tiny(workload, seconds=1.0, trace=True)
    return run


def test_the_entries_that_wait_are_the_files_that_are_there():
    """Every entry above has its metric file and reader; the five that
    `BENCHMARK.json` could take are there word for word, and the seven the
    benchmark's own tests refuse (`benchmark/SETUP_AND_PAUSES.md`) are
    not."""
    real = {m["name"]: m
            for m in json.load(open(os.path.join(REPO, "BENCHMARK.json")))[
                "per_layer"]}
    listed = set()
    for entry in ENTRIES:
        spec = harness.load_json(harness.BENCH_DIR, "metrics",
                                 entry["name"] + ".json")
        assert callable(harness.load_module(
            harness.BENCH_DIR, "readers", spec["reader"]).read)
        if entry["name"] in real:
            listed.add(entry["name"])
            assert {k: real[entry["name"]][k] for k in (
                "unit", "better", "source", "layer", "moves")} \
                == {k: entry[k] for k in (
                    "unit", "better", "source", "layer", "moves")}
    assert listed == {"step_ms_max.closed", "step_host_ms_max.closed",
                      "gc_ms_per_s.closed", "step_ms_max.train",
                      "gc_ms_per_s.train"}


@pytest.mark.parametrize("workload", [CLOSED, TRAINED])
def test_every_new_metric_is_a_number_and_set_up_lies_under_setup_s(
        run_new, workload, capfd, monkeypatch):
    from deepspeed_tpu.utils import spans
    # a tiny engine is built in less than the 50 ms a record takes
    monkeypatch.setattr(spans, "LONG_SPAN_NS", 1_000_000)
    res = run_new(workload, programs_too=True)
    assert res["correct"] and res["failed"] == 0, res["compared"]
    got = res["metrics"]
    assert set(got) == {e["name"] for e in ENTRIES
                        if workload in e["workloads"]}
    for name, m in got.items():
        assert m["value"] == m["value"] and m["value"] >= 0, name
    setup_s = res["notes"]["setup_s"]
    parts = {name: got[name]["value"] for name in SETUP_METRICS}
    assert 0.1 <= parts["setup_import_s"] < 0.3      # `fresh_log`'s plant
    assert all(v > 0 for v in parts.values()), parts
    # the compiles run inside the build and the warm-up's dispatches, so
    # the four overlap; each alone, and import + build, lie under set-up
    assert max(parts.values()) < setup_s
    assert parts["setup_import_s"] + parts["setup_build_s"] < setup_s + 0.1
    step_max = got["step_ms_max.closed" if workload == CLOSED
                   else "step_ms_max.train"]["value"]
    assert 0 < step_max < 1e3 * res["notes"].get("window_s", 60.0)
    if workload == CLOSED:
        assert got["step_host_ms_max.closed"]["value"] <= step_max
        for name in ("step_ms_max", "step_host_ms_max", "gc_ms_per_s"):
            assert got[name + ".ktok.closed"] == got[name + ".closed"]
    # the `[bench]` lines: under which span the time went
    err = capfd.readouterr().err
    lines = {}
    for line in err.splitlines():
        if line.startswith("[bench] "):
            lines.update(json.loads(line[len("[bench] "):]))
    spans_line = lines["long_spans"]
    assert spans_line["steps"] == len(spans_line["window"]) \
        or len(spans_line["window"]) == 10
    assert {"name", "parent", "step", "ms"} <= set(spans_line["setup"][0])
    assert {"wait", "gc"} <= set(spans_line["window"][0])
    assert "host.import" in spans_line["setup_s_by_span"]
    assert lines["compile_time"]["setup_s"]["backend_compile"] > 0


def _plant(monkeypatch, where, what):
    """`what()` once, in the first serve step after the window opened:
    inside its `engine.fetch` span (`where` "fetch": the thread waits
    there for the device) or in its bookkeeping (the host's own time)."""
    import jax
    from deepspeed_tpu.serving.telemetry import ServingTelemetry
    from deepspeed_tpu.utils import spans
    armed = []          # holds something from the window's opening on
    opens = harness.Context.window_opens

    def window_opens(ctx):
        opens(ctx)
        armed.append(True)
    monkeypatch.setattr(harness.Context, "window_opens", window_opens)

    def once():
        if armed:
            armed.clear()
            what()
    if where == "fetch":
        device_get = jax.device_get

        def get(x):
            if getattr(spans._thread.top, "name", None) == "engine.fetch":
                once()
            return device_get(x)
        monkeypatch.setattr(jax, "device_get", get)
    else:
        record_step = ServingTelemetry.record_step

        def record(self, *args, **kw):
            once()
            return record_step(self, *args, **kw)
        monkeypatch.setattr(ServingTelemetry, "record_step", record)


def test_a_blocked_fetch_reads_as_wait(run_new, monkeypatch):
    _plant(monkeypatch, "fetch", lambda: time.sleep(0.1))
    from deepspeed_tpu.utils import spans
    got = run_new(CLOSED)["metrics"]
    assert got["step_ms_max.closed"]["value"] >= 100.0
    assert got["step_host_ms_max.closed"]["value"] \
        < got["step_ms_max.closed"]["value"]
    # the step that waited: its 100 ms are `wait`, not the host's own time
    # (the window's largest host time is some other step's, and under a
    # loaded machine anything: only the order above is held)
    longest = max(spans.steps(), key=lambda r: r.duration)
    assert longest.wait >= 100e6
    assert longest.duration - longest.wait < longest.wait


def test_a_host_sleep_reads_as_host_time(run_new, monkeypatch):
    _plant(monkeypatch, "bookkeep", lambda: time.sleep(0.1))
    got = run_new(CLOSED)["metrics"]
    assert got["step_ms_max.closed"]["value"] >= 100.0
    assert got["step_host_ms_max.closed"]["value"] >= 100.0


def test_a_forced_collection_reads_as_host_time_and_as_gc(run_new,
                                                          monkeypatch):
    took = []

    def collect():
        t0 = time.perf_counter()
        gc.collect()
        took.append(1e3 * (time.perf_counter() - t0))
    _plant(monkeypatch, "bookkeep", collect)
    res = run_new(CLOSED)
    got, window_s = res["metrics"], res["notes"]["window_s"]
    assert len(took) == 1 and took[0] > 1.0     # a full collection: ms
    floor = 0.8 * took[0]        # the span lies inside the timed call
    assert got["step_ms_max.closed"]["value"] >= floor
    assert got["step_host_ms_max.closed"]["value"] >= floor
    assert got["gc_ms_per_s.closed"]["value"] >= floor / (1.2 * window_s)
