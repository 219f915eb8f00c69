"""Tests: serving observability (deepspeed_tpu.serving.tracing +
monitor schema registry + bounded InMemoryMonitor) — request span
trees, default-off bit-for-bit parity (both directions), trace
continuity across supervised failover, the step timeline profiler,
Prometheus text dumps, and the monitor-event tag schema gate.

Determinism discipline matches test_fleet_supervisor.py: fake engines
with a real allocator where blocks matter, one shared fault-harness
FakeClock advanced manually, fleets driven lock-step — every span
timestamp below is an exact serve-clock value, no sleeps anywhere.
"""
import json

import numpy as np
import pytest

from test_fleet import BS, PrefixFakeEngine, _prompt
from test_serving import FakeEngine, FakeBurstEngine, _expected_tokens

from deepspeed_tpu.config.config import (ConfigError, DeepSpeedTPUConfig,
                                         DisaggConfig, FleetConfig,
                                         ServingConfig, SupervisorConfig,
                                         TracingConfig)
from deepspeed_tpu.monitor import InMemoryMonitor, schema
from deepspeed_tpu.serving import (FleetRouter, RequestState, ServeLoop,
                                   StepTimeline, chrome_trace,
                                   write_chrome_trace, write_trace_jsonl)
from deepspeed_tpu.serving.fleet.faults import (FakeClock, FaultInjector,
                                                FaultPlan)

pytestmark = pytest.mark.serving


def _tracing_cfg(**kw):
    kw.setdefault("enabled", True)
    return TracingConfig(**kw)


# -- config ----------------------------------------------------------------
def test_tracing_config_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"tracing": {"enabled": True,
                                 "max_spans_per_request": 64,
                                 "step_timeline": 128}}})
    tr = cfg.serving.tracing
    assert tr.enabled and tr.max_spans_per_request == 64
    assert tr.step_timeline == 128
    # absent block = None = off (the parity default)
    assert DeepSpeedTPUConfig.from_json({"serving": {}}).serving.tracing \
        is None
    for bad in ({"max_spans_per_request": 4}, {"step_timeline": -1}):
        with pytest.raises(ConfigError):
            TracingConfig.from_dict(bad)


# -- default-off parity (both directions) ----------------------------------
def _serve_stream(cfg):
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(max_seqs=4, budget=8), cfg, clock=clock)
    prompts = [np.asarray([3, 7], np.int32), np.asarray([5, 1, 2], np.int32),
               np.asarray([11], np.int32)]
    reqs = [loop.submit(p, max_new_tokens=4) for p in prompts]
    steps = 0
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
        steps += 1
    return loop, reqs, steps


def test_tracing_off_is_bit_for_bit_both_directions():
    """Direction 1: the default (tracing=None) and an explicit all-off
    block behave identically and attach NO trace.  Direction 2: tracing
    ON changes nothing observable — same tokens, same counters, same
    step count — it only ADDS the trace object."""
    base_loop, base_reqs, base_steps = _serve_stream(ServingConfig())
    off_loop, off_reqs, off_steps = _serve_stream(
        ServingConfig(tracing=TracingConfig(enabled=False)))
    on_loop, on_reqs, on_steps = _serve_stream(
        ServingConfig(tracing=_tracing_cfg()))
    for reqs in (base_reqs, off_reqs, on_reqs):
        assert all(r.state is RequestState.DONE for r in reqs)
    for a, b in zip(base_reqs, off_reqs):
        assert list(a.output_tokens) == list(b.output_tokens)
        assert a.trace is None and b.trace is None
    for a, c in zip(base_reqs, on_reqs):
        assert list(a.output_tokens) == list(c.output_tokens)
        assert c.trace is not None
    assert base_steps == off_steps == on_steps
    assert base_loop.telemetry.counters == off_loop.telemetry.counters \
        == on_loop.telemetry.counters
    assert base_loop._tracer is None and off_loop._tracer is None


# -- single-loop span structure --------------------------------------------
def test_trace_records_lifecycle_spans_on_the_serve_clock():
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(max_seqs=2, budget=2),
                     ServingConfig(tracing=_tracing_cfg()), clock=clock)
    p = np.asarray([4, 5, 6], np.int32)     # 3 prompt tokens, budget 2
    req = loop.submit(p, max_new_tokens=3)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert list(req.output_tokens) == _expected_tokens(p, 3)
    tr = req.trace
    names = [e["name"] for e in tr.events()]
    assert names[0] == "submit" and names[-1] == "finish"
    assert "admit" in names and "first_token" in names
    # lifecycle phases cover submit -> finish contiguously
    phases = [s for s in tr.spans()
              if s["name"] in ("queued", "prefill", "decode")]
    assert [s["name"] for s in phases] == ["queued", "prefill", "decode"]
    for a, b in zip(phases, phases[1:]):
        assert a["t1"] == b["t0"]           # no gaps on the serve clock
    assert phases[0]["t0"] == req.arrival_time
    assert phases[-1]["t1"] == req.finish_time
    # chunked prefill left one span per step that advanced the prompt
    chunks = tr.spans("prefill_chunk")
    assert sum(s["tokens"] for s in chunks) == len(p)
    assert tr.events("finish")[0]["state"] == "done"


def test_trace_burst_spans_cover_generated_tokens():
    clock = FakeClock()
    loop = ServeLoop(FakeBurstEngine(max_seqs=2, budget=8),
                     ServingConfig(decode_burst=4,
                                   tracing=_tracing_cfg()), clock=clock)
    req = loop.submit(np.asarray([3, 7], np.int32), max_new_tokens=6)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert req.state is RequestState.DONE
    bursts = req.trace.spans("decode_burst")
    assert bursts
    # every generated token after the first rode a traced burst (the
    # span's `tokens` attr is what the DISPATCH returned — host
    # truncation at max_new_tokens may drop a tail)
    assert sum(s["tokens"] for s in bursts) >= len(req.generated) - 1
    assert all(s["t1"] >= s["t0"] for s in bursts)


def test_trace_prefix_hit_event_carries_coverage():
    clock = FakeClock()
    cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                        tracing=_tracing_cfg())
    loop = ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
    primer = loop.submit(_prompt(0), max_new_tokens=4)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert primer.state is RequestState.DONE
    assert primer.trace.events("prefix_hit") == []   # cold cache
    # second request re-uses the primed shared prefix -> prefix_hit
    req = loop.submit(_prompt(1), max_new_tokens=4)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    hits = req.trace.events("prefix_hit")
    assert hits and hits[0]["covered_tokens"] == 4 * BS


def test_trace_entry_cap_counts_drops_instead_of_growing():
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(max_seqs=2, budget=2,
                                max_tokens_per_seq=256),
                     ServingConfig(
                         tracing=_tracing_cfg(max_spans_per_request=16)),
                     clock=clock)
    # 100 prompt tokens at budget 2 = 50 prefill_chunk spans, far over
    # the 16-entry cap
    req = loop.submit(np.arange(100, dtype=np.int32) % 32,
                      max_new_tokens=2)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert req.state is RequestState.DONE
    assert len(req.trace.entries) == 16
    assert req.trace.dropped > 0


# -- exporters -------------------------------------------------------------
def test_chrome_trace_and_jsonl_exports(tmp_path):
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(), ServingConfig(tracing=_tracing_cfg()),
                     clock=clock)
    reqs = [loop.submit(np.asarray([i + 1, i + 2], np.int32),
                        max_new_tokens=2) for i in range(2)]
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    doc = chrome_trace(reqs)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs == {"M", "X", "i"}
    # the metadata event names the replica row; spans carry the
    # PROCESS-UNIQUE trace id (request uids are only loop-local and
    # adoption reassigns them — two requests must never share a thread)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "loop"
    ids = {r.trace.trace_id for r in reqs}
    assert len(ids) == 2
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["args"]["request"] in ids
            assert e["tid"] == e["args"]["request"]
            assert e["args"]["uid"] in (0, 1)
    path = write_chrome_trace(reqs, str(tmp_path / "trace.json"))
    loaded = json.load(open(path))
    assert loaded["traceEvents"]              # perfetto-loadable JSON
    jl = write_trace_jsonl(reqs, str(tmp_path / "trace.jsonl"))
    lines = [json.loads(line) for line in open(jl)]
    assert len(lines) == sum(len(r.trace.entries) for r in reqs)
    assert {rec["request"] for rec in lines} == ids


# -- trace continuity across failover (the tentpole acceptance) ------------
def _supervised_cfg(tracing=None):
    return ServingConfig(
        prefix_cache_blocks=16, audit_blocks=True,
        tracing=tracing,
        fleet=FleetConfig(
            replicas=3, snapshot_interval_steps=1,
            supervisor=SupervisorConfig(
                heartbeat_timeout_s=3.0, error_burst=2,
                error_window_s=100.0, failover_after_s=6.0,
                recovery_ticks=3, max_request_retries=2)))


def _chaos_run(cfg):
    """Kill the replica serving request 0 mid-decode; return the
    finished requests (same stream every call — deterministic)."""
    clock = FakeClock()
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(3)]
    fleet = FleetRouter(loops, cfg)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=4) for i in range(3)]
    for _ in range(2):                       # admit + first decode steps
        fleet.step()
        clock.advance(1.0)
    victim = next(rep for rep in fleet.replicas
                  if any(r is reqs[0]
                         for r in rep.loop.scheduler.active.values()))
    assert reqs[0].state is RequestState.DECODE
    FaultInjector(victim.loop, FaultPlan.replica_death(0))
    steps = 0
    while fleet.has_work and steps < 300:
        fleet.step()
        clock.advance(1.0)
        steps += 1
    assert all(r.state is RequestState.DONE for r in reqs)
    return fleet, reqs, victim


def test_trace_survives_failover_with_ordered_spans_on_shared_clock():
    fleet, reqs, victim = _chaos_run(
        _supervised_cfg(tracing=_tracing_cfg(step_timeline=64)))
    tr = reqs[0].trace
    assert reqs[0].retries == 1
    # the span tree crosses two replicas: the victim and the adopter
    replicas = tr.replicas()
    assert len(replicas) == 2
    assert replicas[0] == f"replica{victim.id}"
    # demote -> requeue -> adopt present, in order, monotone timestamps
    names = [e["name"] for e in tr.events()]
    for a, b in (("route", "demote"), ("demote", "requeue"),
                 ("requeue", "adopt"), ("adopt", "finish")):
        assert names.index(a) < names.index(b), names
    ts = [e["t"] for e in tr.events()]
    assert ts == sorted(ts)
    # the aborted decode phase on the victim closed at the demotion
    aborted = [s for s in tr.spans() if s.get("aborted")]
    assert aborted and aborted[0]["replica"] == f"replica{victim.id}"
    # adoption re-attributes: everything after rides the adopter, and
    # the trace follows the uid the adopting loop assigned while its
    # process-unique trace_id keeps the exported thread unambiguous
    adopt = tr.events("adopt")[0]
    assert adopt["replica"] != f"replica{victim.id}"
    assert tr.events("finish")[0]["replica"] == adopt["replica"]
    assert tr.uid == reqs[0].uid == adopt["uid"]
    assert len({r.trace.trace_id for r in reqs}) == len(reqs)
    # and the whole thing exports (the bench artifact's code path)
    doc = chrome_trace(reqs)
    row_names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
    assert {f"replica{victim.id}", adopt["replica"]} <= row_names


def test_chaos_outputs_bit_for_bit_with_tracing_on_vs_off():
    """The chaos parity lock: the identical supervised chaos stream
    with tracing ON and OFF produces identical tokens, retries, and
    fleet health history — tracing is observe-only through failover."""
    f_off, r_off, _ = _chaos_run(_supervised_cfg(tracing=None))
    f_on, r_on, _ = _chaos_run(_supervised_cfg(tracing=_tracing_cfg()))
    for a, b in zip(r_off, r_on):
        assert list(a.output_tokens) == list(b.output_tokens)
        assert a.retries == b.retries
        assert a.trace is None and b.trace is not None
    assert f_off.summary()["health_events"] == \
        f_on.summary()["health_events"]
    assert f_off.summary()["health"] == f_on.summary()["health"]


# -- step timeline profiler ------------------------------------------------
def test_step_timeline_ring_bounds_and_aggregates():
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(max_seqs=2, budget=4,
                                max_tokens_per_seq=128),
                     ServingConfig(tracing=TracingConfig(
                         enabled=False, step_timeline=8)), clock=clock)
    req = loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=40)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert req.state is RequestState.DONE
    tl = loop._timeline
    assert tl is not None and loop._tracer is None   # timeline-only mode
    assert len(tl.rows) == 8                         # ring is bounded
    assert tl.total_steps > 8 and tl.evicted == tl.total_steps - 8
    agg = loop.telemetry.summary()["step_phases"]
    assert agg["rows"] == 8 and agg["evicted"] == tl.evicted
    for p in StepTimeline.PHASES:
        assert f"{p}_mean_s" in agg and f"{p}_p95_s" in agg
    # token accounting rides the rows (FakeClock -> zero durations)
    assert sum(r["decode_tokens"] for r in tl.rows) > 0
    with pytest.raises(ValueError, match="capacity"):
        StepTimeline(0)


def test_step_timeline_publishes_phase_gauges_and_prometheus_text():
    sink = InMemoryMonitor(strict_schema=True)
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(),
                     ServingConfig(monitor_interval_steps=1,
                                   tracing=TracingConfig(
                                       enabled=False, step_timeline=32)),
                     clock=clock, monitor=sink)
    loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=3)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    tags = {tag for tag, _, _ in sink.events}
    for p in StepTimeline.PHASES:
        assert f"serving/phase_{p}_s" in tags
    text = loop.telemetry.prometheus_text()
    assert "# TYPE dstpu_serving_completed_total counter" in text
    assert "dstpu_serving_completed_total 1" in text
    assert 'dstpu_serving_ttft_seconds{quantile="0.5"}' in text
    assert "dstpu_serving_phase_decode_seconds_mean" in text
    # TYPE headers are unique per metric family (the exposition format)
    type_lines = [ln for ln in text.splitlines()
                  if ln.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines))


def test_fleet_prometheus_text_labels_replicas_and_pools():
    clock = FakeClock()
    cfg = ServingConfig(
        prefix_cache_blocks=16, audit_blocks=True,
        fleet=FleetConfig(replicas=3, snapshot_interval_steps=1,
                          disagg=DisaggConfig(prefill_replicas=1,
                                              decode_replicas=2)))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(3)]
    fleet = FleetRouter(loops, cfg)
    req = fleet.submit(_prompt(0), max_new_tokens=3)
    fleet.run_until_idle(max_steps=200)
    assert req.state is RequestState.DONE
    text = fleet.telemetry.prometheus_text(
        (rep.id, rep.loop.telemetry, rep.role.value)
        for rep in fleet.replicas)
    assert 'dstpu_fleet_routed_total{reason="handoff"} 1' in text
    assert 'dstpu_fleet_pool_completed{pool="decode"}' in text
    assert 'dstpu_fleet_replica_queue_depth{replica="0",role="prefill"}' \
        in text
    type_lines = [ln for ln in text.splitlines()
                  if ln.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines))


# -- bounded InMemoryMonitor (regression) ----------------------------------
def test_in_memory_monitor_bounds_events_and_counts_drops():
    mon = InMemoryMonitor(max_events=8)
    for i in range(5):
        mon.write_events([(f"serving/queue_depth", float(i), i),
                          (f"serving/completed", float(i), i),
                          (f"serving/batch_occupancy", float(i), i)])
    assert len(mon.events) == 8                  # bounded
    assert mon.dropped_events == 7               # 15 written - 8 kept
    # the NEWEST events are the ones kept
    assert mon.events[-1] == ("serving/batch_occupancy", 4.0, 4)
    assert mon.events[0][2] >= 2
    with pytest.raises(ValueError, match="max_events"):
        InMemoryMonitor(max_events=0)


# -- monitor tag schema registry -------------------------------------------
def test_every_published_serving_and_fleet_tag_is_registered():
    """Drive every publish path in the package — serving gauges +
    percentiles + spec + prefix + timeline, fleet health/failover,
    disagg pools, per-replica rows — into a strict-schema sink: an
    unregistered (typo'd) tag raises at the offending write."""
    sink = InMemoryMonitor(strict_schema=True)
    clock = FakeClock()
    cfg = ServingConfig(
        prefix_cache_blocks=16, audit_blocks=True,
        monitor_interval_steps=1,
        tracing=TracingConfig(enabled=True, step_timeline=16),
        fleet=FleetConfig(
            replicas=3, snapshot_interval_steps=1,
            supervisor=SupervisorConfig(
                heartbeat_timeout_s=3.0, error_burst=2,
                error_window_s=100.0, failover_after_s=6.0,
                recovery_ticks=3, max_request_retries=2),
            disagg=DisaggConfig(prefill_replicas=1, decode_replicas=2)))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock,
                       monitor=sink) for _ in range(3)]
    fleet = FleetRouter(loops, cfg, monitor=sink)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(3)]
    for _ in range(3):
        fleet.step()
        clock.advance(1.0)
    # kill a decode replica mid-stream so failover/health tags publish
    victim = next(rep for rep in fleet.replicas
                  if rep.role.value == "decode" and rep.loop.has_work)
    FaultInjector(victim.loop, FaultPlan.replica_death(0))
    steps = 0
    while fleet.has_work and steps < 300:
        fleet.step()
        clock.advance(1.0)
        steps += 1
    assert all(r.state is RequestState.DONE for r in reqs)
    fleet.publish()                               # fleet/* events
    tags = {tag for tag, _, _ in sink.events}
    assert any(t.startswith("fleet/pool_") for t in tags)
    assert any(t.startswith("fleet/replica_") for t in tags)
    assert any(t.startswith("fleet/health_") for t in tags)
    assert schema.unregistered(tags) == []


def test_schema_rejects_typod_tags():
    assert not schema.is_registered("serving/queue_dpeth")
    assert not schema.is_registered("fleet/routed_prefx")
    assert not schema.is_registered("fleet/pool_prefill/nope")
    assert schema.is_registered("train/loss")     # other namespaces free
    assert schema.is_registered("fleet/replica_12/decode/queue_depth")
    assert schema.unregistered(["serving/queue_depth", "serving/oops",
                                "serving/oops"]) == ["serving/oops"]
    with pytest.raises(ValueError, match="serving/oops"):
        schema.check_tags(["serving/oops"])
    mon = InMemoryMonitor(strict_schema=True)
    with pytest.raises(ValueError, match="unregistered"):
        mon.write_events([("serving/typo_tag", 1.0, 0)])


# -- profile-guided DST001 (analysis/profile_guided.py) --------------------
def test_transfer_profiler_attributes_calls_and_bytes_to_sites():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.analysis import TransferProfiler

    x = jnp.arange(1024, dtype=jnp.float32)       # staged OUTSIDE
    real_get = jax.device_get
    with TransferProfiler() as prof:
        jax.device_get(x)
        jax.device_get((x, x))                    # pytree payload
    assert jax.device_get is real_get             # patch restored
    d2h = [s for s in prof.by_cost() if s.direction == "d2h"]
    assert sum(s.calls for s in d2h) == 2
    assert prof.total_bytes("d2h") == 3 * 1024 * 4
    for s in d2h:
        assert s.path.endswith("test_tracing.py")
        assert s.func == \
            "test_transfer_profiler_attributes_calls_and_bytes_to_sites"
    with pytest.raises(RuntimeError, match="reentrant"):
        with TransferProfiler() as p2:
            with p2:
                pass


def test_rank_findings_orders_by_measured_bytes():
    from deepspeed_tpu.analysis import (Finding, TransferProfiler,
                                        rank_findings)
    from deepspeed_tpu.analysis.profile_guided import TransferSite

    hot = Finding(rule="DST001", path="deepspeed_tpu/a.py", line=10,
                  col=0, message="m", symbol="f")
    warm = Finding(rule="DST001", path="deepspeed_tpu/a.py", line=20,
                   col=0, message="m", symbol="g")
    cold = Finding(rule="DST001", path="deepspeed_tpu/b.py", line=5,
                   col=0, message="m", symbol="h")
    other = Finding(rule="DST004", path="deepspeed_tpu/a.py", line=10,
                    col=0, message="m", symbol="f")
    prof = TransferProfiler()
    for site in (TransferSite("deepspeed_tpu/a.py", 20, "g", "d2h",
                              calls=4, bytes=400),
                 TransferSite("deepspeed_tpu/a.py", 10, "f", "d2h",
                              calls=1, bytes=4000),
                 TransferSite("deepspeed_tpu/c.py", 1, "x", "d2h",
                              calls=2, bytes=9000),
                 TransferSite("deepspeed_tpu/a.py", 10, "f", "h2d",
                              calls=9, bytes=10 ** 6)):  # wrong direction
        prof.sites[site.key] = site
    ranked, unmatched = rank_findings([cold, warm, hot, other], prof)
    assert [r.finding.symbol for r in ranked] == ["f", "g", "h"]
    assert [r.bytes for r in ranked] == [4000, 400, 0]
    assert [r.measured for r in ranked] == [True, True, False]
    # measured traffic with no static finding is reported, not dropped
    assert [(s.path, s.bytes) for s in unmatched] == \
        [("deepspeed_tpu/c.py", 9000)]


def test_profile_rank_cli_ranks_the_real_serve_window(capsys):
    """`dstpu_lint --profile-rank`: a real tiny serve window on this
    CPU container, measured d2h traffic attributed to the engine's
    explicit-fetch seams and joined against the static DST001 set."""
    import pathlib
    from deepspeed_tpu.analysis.__main__ import main

    repo = pathlib.Path(__file__).resolve().parent.parent
    rc = main(["--profile-rank", "--format", "json",
               str(repo / "deepspeed_tpu" / "serving"),
               str(repo / "deepspeed_tpu" / "inference")])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    ranked = out["ranked"]
    assert all(r["path"].startswith(("deepspeed_tpu/serving",
                                     "deepspeed_tpu/inference"))
               for r in ranked)
    hot = [r for r in ranked if r["measured"]]
    assert hot, "the serve window must execute some explicit-fetch seam"
    # measured sites rank first, by bytes descending; cold tail after
    costs = [r["bytes"] for r in ranked]
    assert costs == sorted(costs, reverse=True)
    assert hot[0]["path"] == "deepspeed_tpu/inference/v2/engine_v2.py"
    assert hot[0]["calls"] > 0 and hot[0]["bytes"] > 0
    # the burst decode fetch — THE once-per-burst d2h — is measured hot
    assert any(r["symbol"].endswith("decode_burst_step") for r in hot)


def test_schema_covers_every_tag_literal_in_the_source():
    """Static sweep: every `serving/`- or `fleet/`-prefixed string
    literal in the package must be a registered tag or a registered
    tag's prefix (f-string head) — a typo'd literal fails here even if
    no test happens to drive its publish path."""
    import re
    from pathlib import Path
    import deepspeed_tpu

    root = Path(deepspeed_tpu.__file__).parent
    lit = re.compile(r'f?"((?:serving|fleet)/[^"{]*)')
    known = sorted(schema.SERVING_TAGS | schema.FLEET_TAGS)
    # parameterized families (schema.TAG_PATTERNS)
    heads = {"fleet/pool_", "fleet/replica_", "serving/tenant/"}
    bad = []
    for path in root.rglob("*.py"):
        for m in lit.finditer(path.read_text(encoding="utf-8")):
            s = m.group(1)
            ok = (schema.is_registered(s)
                  or any(k.startswith(s) for k in known)
                  or any(s.startswith(h) or h.startswith(s)
                         for h in heads))
            if not ok:
                bad.append(f"{path.relative_to(root)}: {s!r}")
    assert bad == [], bad


# -- program spans in the profiler's own trace (utils/spans.py) -------------
def _program_spans(trace_dir):
    """Every `serve.*`/`engine.*`/`train.*` event of the newest xplane
    under `trace_dir`, per host line: [(start, end, name, stats)]."""
    import glob
    import os
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    assert files, "the profiler session wrote no xplane file"
    lines = []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            evs = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                    e.name.split("#", 1)[0], dict(e.stats))
                   for e in line.events
                   if e.name.startswith(("serve.", "engine.", "train."))]
            if evs:
                lines.append(sorted(evs, key=lambda t: (t[0], -t[1])))
    return lines


def _traced(tmp_path):
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return jax.profiler.trace(str(tmp_path), profiler_options=options)


def _inside(ev, outer):
    return any(o[0] <= ev[0] and ev[1] <= o[1] for o in outer)


def test_span_catalogue_holds_the_source_to_one_primitive():
    """Every span name the package opens is in `SPAN_NAMES`, every host
    span goes through `utils.spans.span`, and the nvtx-style leftovers
    are gone."""
    import re
    from pathlib import Path
    import deepspeed_tpu
    from deepspeed_tpu.utils import spans

    root = Path(deepspeed_tpu.__file__).parent
    # `span(` itself, not a request trace's `.span(` (the serve clock's)
    opened = re.compile(r'(?:(?<![\w.])span|phases\.enter)\(\s*"([^"]+)"')
    found, direct = set(), []
    for path in root.rglob("*.py"):
        text = path.read_text(encoding="utf-8")
        found |= set(opened.findall(text))
        if "TraceAnnotation(" in text and path.name != "spans.py":
            direct.append(str(path.relative_to(root)))
        for gone in ("range_push", "range_pop", "instrument_w_nvtx"):
            assert f"def {gone}" not in text, (path, gone)
    assert direct == []
    assert found == set(spans.SPAN_NAMES)
    assert {"host.import", "host.gc", "engine.build", "train.build"} <= found
    assert not (root / "utils" / "nvtx.py").exists()
    # one `jax.monitoring` listener in the package: `CompileCounter`'s
    listeners = [str(path.relative_to(root)) for path in root.rglob("*.py")
                 if re.search(r"register_event\w*listener\(",
                              path.read_text(encoding="utf-8"))]
    assert listeners == ["utils/device.py"]


@pytest.mark.parametrize("burst", [1, 4], ids=["per_step", "burst"])
def test_serve_spans_land_in_a_real_profiler_trace(tmp_path, burst):
    """A tiny CPU engine driven for a few steps under a real
    `jax.profiler` session: the xplane read back holds the serve step's
    spans, named from the catalogue, nested inside `serve.step`, one
    `engine.fetch` per explicit device-to-host fetch, and `serve.step`'s
    `step` is the key of the step's timeline row."""
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine)
    from deepspeed_tpu.utils.spans import SPAN_NAMES
    eng = build_engine("gpt2", "tiny",
                       engine_config=RaggedInferenceEngineConfig(
                           num_blocks=32, block_size=8, max_blocks_per_seq=8,
                           max_seqs=4, prefill_chunk_size=8))
    loop = ServeLoop(eng, ServingConfig(
        decode_burst=burst,
        tracing=TracingConfig(enabled=False, step_timeline=64)))
    rng = np.random.RandomState(0)
    for n in (5, 11, 3):
        loop.submit(rng.randint(0, eng.cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=6)
    fetches0 = eng.profile["d2h_fetches"]
    with _traced(tmp_path):
        steps = 0
        while loop.has_work and steps < 40:
            loop.step()
            steps += 1
    assert not loop.has_work
    lines = [ln for ln in _program_spans(tmp_path)
             if any(e[2] == "serve.step" for e in ln)]
    assert len(lines) == 1, "one serve thread"
    evs = lines[0]
    names = {e[2] for e in evs}
    assert names <= set(SPAN_NAMES)
    assert {"serve.step", "serve.finalize", "serve.admission",
            "serve.engine", "serve.sample", "serve.bookkeep",
            "engine.plan", "engine.dispatch", "engine.fetch"} <= names
    step_spans = [e for e in evs if e[2] == "serve.step"]
    assert len(step_spans) == steps
    for e in evs:
        if e[2] != "serve.step":
            assert _inside(e, step_spans), e
    # the engine's spans lie inside the phase that called it
    callers = [e for e in evs if e[2] in ("serve.engine", "serve.sample")]
    for e in evs:
        if e[2].startswith("engine."):
            assert _inside(e, callers), e
    fetch = [e for e in evs if e[2] == "engine.fetch"]
    assert len(fetch) == eng.profile["d2h_fetches"] - fetches0
    assert all(e[3]["bytes"] > 0 and e[3]["program"] for e in fetch)
    assert all("rows" in e[3] for e in evs if e[2] == "engine.plan")
    # the join with the serve clock: the timeline rows carry the same key
    assert [e[3]["step"] for e in step_spans] \
        == [r["step"] for r in loop._timeline.rows]
    admitted = [e[3]["admitted"] for e in evs if e[2] == "serve.admission"]
    assert sum(admitted) == 3


def test_train_spans_land_in_a_real_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as dstpu

    def loss_fn(params, batch, rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    engine = dstpu.initialize(
        loss_fn=loss_fn, params={"w": jnp.zeros((8, 4), jnp.float32)},
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 1}, "steps_per_print": 0})
    n = engine.config.train_batch_size
    batch = {"x": np.ones((n, 8), np.float32),
             "y": np.ones((n, 4), np.float32)}
    engine.train_batch(batch)                      # compiles
    with _traced(tmp_path):
        for _ in range(2):
            jax.block_until_ready(engine.train_batch(batch)["loss"])
    lines = [ln for ln in _program_spans(tmp_path)
             if any(e[2] == "train.step" for e in ln)]
    assert len(lines) == 1
    evs = lines[0]
    assert {e[2] for e in evs} == {"train.step", "train.shard_batch",
                                   "train.dispatch"}
    steps = [e for e in evs if e[2] == "train.step"]
    assert [e[3]["step"] for e in steps] == [2, 3]
    for name in ("train.shard_batch", "train.dispatch"):
        inner = [e for e in evs if e[2] == name]
        assert len(inner) == 2 and all(_inside(e, steps) for e in inner)


# -- the host-clock log: spans outside a profiler session -------------------
class BlockedFetchEngine(FakeEngine):
    """A fake engine whose step `block_at` waits for its tokens inside an
    `engine.fetch` span, as the real one does."""

    def __init__(self, block_at, seconds=0.1, **kw):
        super().__init__(**kw)
        self.block_at, self.seconds, self.steps = block_at, seconds, 0

    def step(self, decode=True):
        from deepspeed_tpu.utils.spans import span
        import time
        self.steps += 1
        with span("engine.fetch", program="fake", bytes=4) as fetch:
            if self.steps == self.block_at:
                time.sleep(self.seconds)        # the one planted sleep
            fetch.set_metadata(rows=len(self.state.seqs))
        return super().step(decode=decode)


def test_every_step_leaves_a_record_whose_wait_and_gc_add_up():
    """No profiler session: each `serve.step` still leaves one record; a
    fetch that blocks is the step's `wait`, a forced collection its `gc`
    (and its host time), and the operator's summary says both."""
    import gc
    from deepspeed_tpu.utils import spans
    spans.steps().clear()
    spans.long_spans().clear()
    loop = ServeLoop(BlockedFetchEngine(block_at=3, max_seqs=2, budget=8),
                     ServingConfig(), clock=FakeClock())
    loop.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
    took = []
    record_step = loop.telemetry.record_step

    def collecting(*args, **kw):
        if loop.telemetry.steps == 4:           # inside the fifth step
            t0 = spans.perf_counter_ns()
            gc.collect()
            took.append(spans.perf_counter_ns() - t0)
        return record_step(*args, **kw)
    loop.telemetry.record_step = collecting
    while loop.has_work:
        loop.step()
    recs = list(spans.steps())
    assert [r.name for r in recs] == ["serve.step"] * loop.telemetry.steps
    assert [r.step for r in recs] == list(range(1, len(recs) + 1))
    blocked, collected = recs[2], recs[4]
    assert blocked.wait >= 100e6 and blocked.duration >= blocked.wait
    assert blocked.duration - blocked.wait < blocked.wait   # host's share
    assert len(took) == 1
    assert 0.8 * took[0] <= collected.gc <= took[0] <= collected.duration
    assert collected.wait < collected.gc
    assert all(0 <= r.wait <= r.duration for r in recs)
    # the blocked fetch is a long span under the phase that made it (which
    # is as long, and so recorded too, after it: it closed later)
    long = [r for r in spans.long_spans() if r.step == 3]
    assert [(r.name, r.parent) for r in long] == [
        ("engine.fetch", "serve.engine"), ("serve.engine", "serve.step")]
    assert long[0].attrs == {"program": "fake", "bytes": 4, "rows": 1}
    assert long[0].duration == pytest.approx(blocked.wait, rel=0.05)
    # the operator's side
    host = loop.telemetry.summary()["host"]
    assert 0.1 <= host["longest_step_s"] == pytest.approx(
        max(r.duration for r in recs) * 1e-9)
    assert host["longest_host_step_s"] == pytest.approx(
        (collected.duration - collected.wait) * 1e-9)
    assert host["gc_s"] == pytest.approx(sum(r.gc for r in recs) * 1e-9)
    assert host["gc_s"] >= 0.8e-9 * took[0]
    assert host["gc_collections"][2] >= 1
    long_steps = host["long_steps"]
    assert long_steps == loop.telemetry.long_steps == (
        1 if collected.duration - collected.wait >= 50e6 else 0)
    # measured times stay out of `counters`, which repeat run for run
    assert not {"gc_seconds", "long_steps"} & set(loop.telemetry.counters)
    by_name = {r["name"]: r for r in host["long_spans"]}
    assert len(by_name) == len(host["long_spans"]) <= 5
    assert by_name["engine.fetch"]["seconds"] >= 0.1
    assert by_name["engine.fetch"]["parent"] == "serve.engine"
    assert by_name["engine.fetch"]["program"] == "fake"
    text = loop.telemetry.prometheus_text()
    assert f"dstpu_serving_gc_seconds_total {host['gc_s']:g}" in text
    assert f"dstpu_serving_long_steps_total {long_steps:g}" in text
    assert schema.unregistered(["serving/gc_seconds",
                                "serving/long_steps"]) == []


def test_other_spans_are_recorded_from_50_ms_and_not_below():
    from deepspeed_tpu.utils import spans
    spans.long_spans().clear()
    now = spans.perf_counter_ns
    with spans.span("engine.plan", rows=3) as plan:
        plan.begun(now() - spans.LONG_SPAN_NS + 10_000_000)   # 40 ms
    assert list(spans.long_spans()) == []
    with spans.span("serve.step", step=41):
        with spans.span("serve.engine"):
            with spans.span("engine.dispatch", program="p") as late:
                late.begun(now() - spans.LONG_SPAN_NS)
                late.set_metadata(tokens=7)
    (rec,) = spans.long_spans()
    assert (rec.name, rec.parent, rec.step) \
        == ("engine.dispatch", "serve.engine", 41)
    assert rec.attrs == {"program": "p", "tokens": 7}
    assert rec.duration >= spans.LONG_SPAN_NS
    with spans.span("engine.build") as built:      # under no span at all
        built.begun(now() - 2 * spans.LONG_SPAN_NS)
    assert (spans.long_spans()[-1].parent, spans.long_spans()[-1].step) \
        == (None, None)


def test_the_rings_are_bounded_and_drop_the_oldest():
    from deepspeed_tpu.utils import spans
    spans.steps().clear()
    spans.long_spans().clear()
    for i in range(spans.STEP_RING + 5):
        with spans.span("train.step", step=i):
            pass
    assert len(spans.steps()) == spans.STEP_RING == 16_384
    assert (spans.steps()[0].step, spans.steps()[-1].step) \
        == (5, spans.STEP_RING + 4)
    for i in range(spans.LONG_RING + 3):
        with spans.span("engine.plan", rows=i) as old:
            old.begun(spans.perf_counter_ns() - spans.LONG_SPAN_NS)
    assert len(spans.long_spans()) == spans.LONG_RING == 4_096
    assert spans.long_spans()[0].attrs == {"rows": 3}
    spans.steps().clear()
    spans.long_spans().clear()


def test_a_step_counts_the_collections_of_other_threads_too():
    """A collection stops every thread, so the open step is charged it
    whichever thread collected; `wait` is the step's own thread's."""
    import gc
    import threading
    from deepspeed_tpu.utils import spans

    def elsewhere():
        with spans.span("engine.fetch", program="other", bytes=1) as f:
            f.begun(spans.perf_counter_ns() - spans.LONG_SPAN_NS)
        gc.collect()
    with spans.span("serve.step", step=1) as step:
        worker = threading.Thread(target=elsewhere)
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
    assert step.record.gc > 0 and step.record.wait == 0


def test_host_gc_lands_in_the_xplane_and_in_the_open_steps_gc(tmp_path):
    """With a session open a forced collection is a `host.gc` event with
    its generation and what it collected, inside the step that was open;
    the step's record carries the same collection's time."""
    import gc
    import glob
    import os
    from jax.profiler import ProfileData
    from deepspeed_tpu.utils import spans
    with _traced(tmp_path):
        with spans.span("serve.step", step=9) as step:
            step.set_metadata(decode_rows=5)
            gc.collect()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = [(e.name.split("#", 1)[0], int(e.start_ns),
               int(e.start_ns) + int(e.duration_ns), dict(e.stats))
              for plane in ProfileData.from_file(files[-1]).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(("host.gc", "serve.step"))]
    (outer,) = [e for e in events if e[0] == "serve.step"]
    # attributes and `set_metadata` still reach the profiler's trace
    assert outer[3]["step"] == 9 and outer[3]["decode_rows"] == 5
    full = [e for e in events if e[0] == "host.gc"
            and e[3]["generation"] == 2]
    assert len(full) == 1 and "collected" in full[0][3]
    assert outer[1] <= full[0][1] and full[0][2] <= outer[2]
    assert step.record.gc >= 0.5 * (full[0][2] - full[0][1])
    assert step.record.gc <= step.record.duration
    assert step.attrs == {"step": 9, "decode_rows": 5}


def test_compile_counter_keeps_the_phases_apart_by_function():
    """The package's one listener: trace, lowering and backend compile of
    a function by its name, each with its start on the spans' clock."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.utils import spans
    from deepspeed_tpu.utils.device import (COMPILE_PHASES, CompileCounter)

    def a_named_program(x):
        return x * 5 - 2
    counter = CompileCounter()
    t0 = spans.perf_counter_ns()
    jax.jit(a_named_program)(jnp.ones(3))
    t1 = spans.perf_counter_ns()
    # by time, not by index: the ring may be full, and then it does not grow
    mine = [e for e in list(CompileCounter.events())
            if e.start >= t0 and e.fun_name
            and "a_named_program" in e.fun_name]
    assert {e.phase for e in mine} == {"jaxpr_trace", "jaxpr_to_mlir_module",
                                       "backend_compile"}
    assert set(COMPILE_PHASES.values()) == {
        "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile",
        "cache_retrieval"}
    assert all(t0 <= e.start <= t1 and e.seconds > 0 for e in mine)
    assert all(e.start + e.seconds * 1e9 <= t1 + 1e6 for e in mine)
    backend = [e for e in mine if e.phase == "backend_compile"]
    assert counter.compile_s >= sum(e.seconds for e in backend) > 0
    # a second counter starts from zero; the first keeps its total
    assert CompileCounter().compile_s == 0.0 < counter.compile_s
    jax.jit(a_named_program)(jnp.ones(3))       # jit's own cache: no event
    assert [e for e in list(CompileCounter.events())
            if e.start > t1 and e.phase == "backend_compile"] == []


def test_submit_due_starts_the_request_clock_when_the_caller_says():
    """`submit(due=)`: arrival, and with it the queue wait, TTFT, the
    `queued` span and the deadline, count from when the request was due;
    the default is the clock at submit, as before."""
    clock = FakeClock()
    clock.advance(10.0)
    loop = ServeLoop(FakeEngine(max_seqs=2, budget=8),
                     ServingConfig(tracing=_tracing_cfg()), clock=clock)
    late = loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=2,
                       timeout_s=5.0, due=7.5)
    now = loop.submit(np.asarray([3], np.int32), max_new_tokens=2,
                      timeout_s=5.0)
    assert (late.arrival_time, late.deadline) == (7.5, 12.5)
    assert (now.arrival_time, now.deadline) == (10.0, 15.0)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert late.state is RequestState.DONE
    assert late.admit_time - late.arrival_time == 2.5
    assert late.ttft == now.ttft + 2.5
    queued = [e for e in late.trace.entries if e.get("name") == "queued"]
    assert queued and queued[0]["t0"] == 7.5
