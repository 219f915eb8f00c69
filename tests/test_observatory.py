"""Tests: the serving observatory (ISSUE 13) — seeded open-loop
workload generation, the open-loop driver against bare loops / fleets /
disagg pools, the bounded metric time series + its schema gate, the
recompile flight recorder (positive AND negative control), and the
cross-run perf-regression ledger (ingest of the committed BENCH_*
artifacts, the classification table, the tier-1 ledger-schema gate).

Determinism discipline matches the rest of the serving tier: fake
engines where blocks don't matter, a real DSStateManager fake where
they do, one tiny REAL engine for the ramp integration test, shared
FakeClocks, zero sleeps.
"""
import json
import os

import numpy as np
import pytest

from test_fleet import PrefixFakeEngine, _prompt
from test_serving import FakeEngine

from deepspeed_tpu.config.config import (ConfigError, DisaggConfig,
                                         DeepSpeedTPUConfig, FleetConfig,
                                         ServingConfig, TracingConfig)
from deepspeed_tpu.monitor import InMemoryMonitor, schema
from deepspeed_tpu.serving import (FleetRouter, RequestState, ServeLoop,
                                   StepTimeline, chrome_trace)
from deepspeed_tpu.serving.fleet.faults import FakeClock
from deepspeed_tpu.serving.observatory import (
    MetricRing, OpenLoopDriver, RecompileFlightRecorder,
    WorkloadGenerator, calibrate_service_rate, program_cache_census)
from deepspeed_tpu.benchmarks import bench_history

pytestmark = pytest.mark.serving

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _items_equal(a, b):
    return (len(a) == len(b)
            and all(x.arrival_s == y.arrival_s
                    and np.array_equal(x.prompt, y.prompt)
                    and x.max_new_tokens == y.max_new_tokens
                    and x.priority == y.priority
                    and x.shared_prefix == y.shared_prefix
                    for x, y in zip(a, b)))


def _gen(**kw):
    kw.setdefault("vocab_size", 32)
    kw.setdefault("seed", 7)
    kw.setdefault("prompt_len_mean", 6.0)
    kw.setdefault("prompt_len_min", 2)
    kw.setdefault("prompt_len_max", 12)
    kw.setdefault("output_len_mean", 4.0)
    kw.setdefault("output_len_min", 2)
    kw.setdefault("output_len_max", 8)
    return WorkloadGenerator(**kw)


# -- workload generation ---------------------------------------------------
def test_workload_is_deterministic_under_fixed_seed():
    a = _gen(arrival="poisson", rate_rps=2.0,
             shared_prefix_len=4, shared_prefix_frac=0.5,
             priority_mix={0: 0.7, 2: 0.3}).generate(40)
    b = _gen(arrival="poisson", rate_rps=2.0,
             shared_prefix_len=4, shared_prefix_frac=0.5,
             priority_mix={0: 0.7, 2: 0.3}).generate(40)
    assert _items_equal(a, b)
    c = _gen(seed=8, arrival="poisson", rate_rps=2.0,
             shared_prefix_len=4, shared_prefix_frac=0.5,
             priority_mix={0: 0.7, 2: 0.3}).generate(40)
    assert not _items_equal(a, c)
    # a longer run EXTENDS the schedule, never reshuffles the prefix —
    # item for item (arrivals, prompts, lengths, mixes), not just the
    # arrival times: per-quantity child streams keep every draw's
    # offset independent of n
    d = _gen(arrival="poisson", rate_rps=2.0,
             shared_prefix_len=4, shared_prefix_frac=0.5,
             priority_mix={0: 0.7, 2: 0.3}).generate(60)
    assert _items_equal(d[:40], a)


def test_workload_arrival_processes_have_their_shapes():
    det = _gen(arrival="deterministic", rate_rps=4.0).generate(9)
    gaps = np.diff([it.arrival_s for it in det])
    assert np.allclose(gaps, 0.25)
    bur = _gen(arrival="burst", rate_rps=4.0, burst_size=3).generate(9)
    ts = [it.arrival_s for it in bur]
    assert ts[0] == ts[1] == ts[2] and ts[3] == ts[4] == ts[5]
    assert ts[3] - ts[0] == pytest.approx(3 / 4.0)
    poi = _gen(arrival="poisson", rate_rps=4.0).generate(400)
    mean_gap = poi[-1].arrival_s / (len(poi) - 1)
    assert 0.15 < mean_gap < 0.40        # ~1/4 s, seeded so stable
    # heavy-tailed lengths stay inside their clip bounds
    lens = [len(it.prompt) for it in poi]
    assert min(lens) >= 2 and max(lens) <= 12
    # with_rate changes ONLY the arrival spacing
    fast = _gen(arrival="poisson", rate_rps=4.0).with_rate(8.0)
    fast_items = fast.generate(400)
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(poi, fast_items))
    assert fast_items[-1].arrival_s == pytest.approx(
        poi[-1].arrival_s / 2.0)


def test_workload_mixes_and_validation():
    g = _gen(shared_prefix_len=4, shared_prefix_frac=0.5,
             priority_mix={0: 0.5, 1: 0.5})
    items = g.generate(80)
    shared = [it for it in items if it.shared_prefix]
    assert 10 < len(shared) < 70
    prefix = shared[0].prompt[:4]
    assert all(np.array_equal(it.prompt[:4], prefix) for it in shared)
    assert {it.priority for it in items} == {0, 1}
    assert g.describe()["shared_prefix_frac"] == 0.5
    for bad in (dict(arrival="nope"), dict(rate_rps=0.0),
                dict(length_dist="uniform"),
                dict(shared_prefix_frac=0.5),     # no prefix len
                dict(priority_mix={}), dict(priority_mix={0: -1.0})):
        with pytest.raises(ValueError):
            _gen(**bad)
    with pytest.raises(ValueError):
        _gen().generate(0)


# -- metric ring -----------------------------------------------------------
def test_metric_ring_bounds_evicts_and_exports(tmp_path):
    ring = MetricRing(4)
    for i in range(7):
        ring.record({"step": i, "queue_depth": i * 2})
    assert len(ring.rows) == 4 and ring.evicted == 3
    assert ring.total_rows == 7
    assert ring.series("step") == [3, 4, 5, 6]
    assert ring.last()["queue_depth"] == 12
    agg = ring.aggregates()
    assert agg["evicted"] == 3 and agg["queue_depth_mean"] == 9.0
    path = ring.to_jsonl(str(tmp_path / "ring.jsonl"))
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 5 and lines[-1]["_meta"] is True
    assert lines[-1]["_evicted"] == 3 and lines[0]["step"] == 3
    # the whole export sweeps through the schema gate unmodified: the
    # meta row's keys are all underscore-prefixed (exempt)
    assert schema.unregistered_fields(
        [k for ln in lines for k in ln if k not in ("queue_depth",)],
        "timeline") == []
    text = ring.prometheus_text("dstpu_test")
    assert "dstpu_test_queue_depth 12" in text
    assert "dstpu_test_ring_evicted 3" in text
    with pytest.raises(ValueError, match="capacity"):
        MetricRing(0)
    # StepTimeline rides the SAME ring implementation (one seam)
    assert issubclass(StepTimeline, MetricRing)


def test_metrics_ring_config_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"tracing": {"metrics_ring": 128}}})
    assert cfg.serving.tracing.metrics_ring == 128
    assert not cfg.serving.tracing.enabled
    with pytest.raises(ConfigError):
        TracingConfig.from_dict({"metrics_ring": -1})


# -- sampler parity + schema gate ------------------------------------------
def _serve_stream(cfg):
    clock = FakeClock()
    loop = ServeLoop(FakeEngine(max_seqs=4, budget=8), cfg, clock=clock)
    prompts = [np.asarray([3, 7], np.int32),
               np.asarray([5, 1, 2], np.int32),
               np.asarray([11], np.int32)]
    reqs = [loop.submit(p, max_new_tokens=4) for p in prompts]
    steps = 0
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
        steps += 1
    return loop, reqs, steps


def test_sampler_off_is_bit_for_bit_both_directions():
    """Direction 1: the default and an explicit metrics_ring=0 behave
    identically and build NO sampler.  Direction 2: the sampler ON
    changes nothing observable — same tokens, same counters, same step
    count — it only ADDS the ring."""
    base_loop, base_reqs, base_steps = _serve_stream(ServingConfig())
    off_loop, off_reqs, off_steps = _serve_stream(
        ServingConfig(tracing=TracingConfig(metrics_ring=0)))
    on_loop, on_reqs, on_steps = _serve_stream(
        ServingConfig(tracing=TracingConfig(metrics_ring=64)))
    assert base_loop.metrics is None and off_loop.metrics is None
    assert on_loop.metrics is not None
    assert base_steps == off_steps == on_steps
    for a, b in ((base_reqs, off_reqs), (base_reqs, on_reqs)):
        for x, y in zip(a, b):
            assert list(x.output_tokens) == list(y.output_tokens)
    assert (base_loop.telemetry.counters == off_loop.telemetry.counters
            == on_loop.telemetry.counters)
    ring = on_loop.metrics.ring
    assert len(ring.rows) == on_steps
    # queue drains to zero by the end; completions accumulate
    assert ring.last()["queue_depth"] == 0
    assert ring.last()["completed_total"] == 3


def test_every_sampled_field_is_registered_in_the_schema():
    """The tier-1 silent-typo gate, extended to the JSONL time series:
    drive a sampled loop (prefix cache + speculation-free), a sampled
    DISAGG fleet, the step timeline, and the recompile recorder, then
    sweep every emitted row key against the registry."""
    clock = FakeClock()
    cfg = ServingConfig(
        prefix_cache_blocks=16, audit_blocks=True,
        tracing=TracingConfig(enabled=False, step_timeline=16,
                              metrics_ring=64),
        fleet=FleetConfig(replicas=3, snapshot_interval_steps=1,
                          disagg=DisaggConfig(prefill_replicas=1,
                                              decode_replicas=2)))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(3)]
    fleet = FleetRouter(loops, cfg)
    assert fleet.metrics is not None
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(3)]
    fleet.run_until_idle(max_steps=300)
    assert all(r.state is RequestState.DONE for r in reqs)
    loop_fields = [k for rep in fleet.replicas
                   for row in rep.loop.metrics.ring.rows for k in row]
    assert schema.unregistered_fields(loop_fields, "loop") == []
    fleet_fields = [k for row in fleet.metrics.ring.rows for k in row]
    assert schema.unregistered_fields(fleet_fields, "fleet") == []
    # disagg pools actually showed up in the fleet series
    assert any("pool_prefill_load" in row
               for row in fleet.metrics.ring.rows)
    assert any(row["parked_total"] > 0 or row["handoffs_total"] > 0
               for row in fleet.metrics.ring.rows)
    tl_fields = [k for rep in fleet.replicas
                 for row in rep.loop.telemetry.timeline.rows for k in row]
    assert schema.unregistered_fields(tl_fields, "timeline") == []
    rec = RecompileFlightRecorder(clock=clock)
    rec.start()
    rec._on_compile("/jax/core/compile/backend_compile_duration", 0.5)
    rec.stop()
    rec_fields = [k for row in rec.ring.rows for k in row]
    assert schema.unregistered_fields(rec_fields, "recompile") == []
    # and the gate actually bites
    assert schema.unregistered_fields(["queue_dpeth"], "loop") \
        == ["queue_dpeth"]
    with pytest.raises(ValueError, match="queue_dpeth"):
        schema.check_timeseries_fields(["queue_dpeth"], "loop")
    with pytest.raises(ValueError, match="kind"):
        schema.unregistered_fields(["t"], "nope")


def test_prometheus_text_surfaces_dropped_counters():
    """ISSUE 13 satellite: trace `dropped` + monitor `dropped_events`
    are scrape-visible, so a truncated observation is a number, not a
    silent gap."""
    sink = InMemoryMonitor(max_events=4)
    clock = FakeClock()
    # budget=1: a 30-token prompt takes 30 prefill steps, each adding a
    # prefill_chunk span — far past the 16-entry trace cap
    loop = ServeLoop(
        FakeEngine(max_seqs=4, budget=1),
        ServingConfig(monitor_interval_steps=1,
                      tracing=TracingConfig(enabled=True,
                                            max_spans_per_request=16)),
        clock=clock, monitor=sink)
    req = loop.submit(np.arange(1, 31, dtype=np.int32),
                      max_new_tokens=12)
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
    assert req.trace.dropped > 0          # 16-entry cap overflowed
    assert loop.telemetry.trace_dropped_entries == req.trace.dropped
    assert sink.dropped_events > 0        # 4-event sink overflowed
    text = loop.telemetry.prometheus_text()
    assert (f"dstpu_serving_trace_dropped_entries_total "
            f"{req.trace.dropped}") in text
    assert (f"dstpu_serving_monitor_dropped_events_total "
            f"{sink.dropped_events}") in text


# -- recompile flight recorder ---------------------------------------------
def test_recompile_recorder_positive_and_negative_control():
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    clock = FakeClock()
    clock.advance(5.0)
    f = jax.jit(lambda x: x * 3 + 1)
    engine = SimpleNamespace(_programs=SimpleNamespace(myprog=f))
    rec = RecompileFlightRecorder(clock=clock, capacity=8, engine=engine)
    assert "engine.myprog" in program_cache_census(engine)
    with rec:
        f(jnp.ones(4))                    # cold: compiles
        n_cold = rec.total_events
        f(jnp.ones(4))                    # warm: cache hit
        n_warm = rec.total_events - n_cold
        f(jnp.ones(8))                    # new shape: recompiles
        n_reshape = rec.total_events - n_cold - n_warm
    assert n_cold >= 1 and n_reshape >= 1
    assert n_warm == 0                    # negative control
    assert rec.total_compile_s > 0
    row = rec.ring.rows[0]
    assert row["t"] == 5.0 and row["duration_s"] > 0
    assert row["event"] in rec.__class__.__module__ or row["event"]
    # census attribution: myprog grew by the two compiled shapes
    assert rec.scan().get("engine.myprog", 0) >= 2
    # stopped recorder records nothing (second negative control)
    n = rec.total_events
    f(jnp.ones(16))
    assert rec.total_events == n
    # recompiles are trace-visible: instants on their own process row
    doc = chrome_trace([], recompiles=rec)
    names = [e for e in doc["traceEvents"] if e.get("name") == "recompile"]
    assert len(names) == rec.total_events
    procs = [e for e in doc["traceEvents"]
             if e.get("name") == "process_name"]
    assert any(p["args"]["name"] == "recompiles" for p in procs)


# -- open-loop driver ------------------------------------------------------
def _make_fake_loop(max_seqs=2, budget=4, queue_len=64, **cfg_kw):
    clock = FakeClock()
    cfg_kw.setdefault("tracing", TracingConfig(metrics_ring=4096))
    loop = ServeLoop(FakeEngine(max_seqs=max_seqs, budget=budget),
                     ServingConfig(max_queue_len=queue_len, **cfg_kw),
                     clock=clock)
    return loop, clock


def test_open_loop_submits_on_schedule_not_on_completion():
    """The defining open-loop property: arrivals land while earlier
    requests are still in flight, so the queue grows past the batch
    width — a closed loop can never produce queue_depth > 0 here."""
    gen = _gen(arrival="deterministic", rate_rps=2.0,
               length_dist="fixed", prompt_len_mean=6,
               output_len_mean=6)
    items = gen.generate(12)
    loop, clock = _make_fake_loop(max_seqs=2, budget=4)
    drv = OpenLoopDriver(loop, clock, items, step_dt=1.0)
    res = drv.run()
    assert res.lost == 0 and res.rejected == 0
    assert len(res.finished) == 12 and res.elapsed_s > 0
    depths = loop.metrics.ring.series("queue_depth")
    assert max(depths) > 0                # backlog actually formed
    assert depths[-1] == 0                # ...and drained
    # every request completed DONE with real tokens
    assert all(len(r.output_tokens) == 6 for r in res.requests)


def test_open_loop_counts_queue_full_as_rejected_not_a_crash():
    gen = _gen(arrival="burst", rate_rps=8.0, burst_size=12,
               length_dist="fixed", prompt_len_mean=6,
               output_len_mean=6)
    items = gen.generate(12)
    loop, clock = _make_fake_loop(max_seqs=2, budget=4, queue_len=4)
    res = OpenLoopDriver(loop, clock, items, step_dt=1.0).run()
    assert res.rejected > 0               # admission-gate saturation
    assert res.lost == 0                  # accepted ones all finished
    assert loop.telemetry.counters["rejected_queue_full"] == res.rejected
    assert len(res.requests) + res.rejected == 12


def test_open_loop_sla_violation_onset_is_counted():
    gen = _gen(arrival="burst", rate_rps=16.0, burst_size=16,
               length_dist="fixed", prompt_len_mean=6,
               output_len_mean=6)
    items = gen.generate(16)
    loop, clock = _make_fake_loop(max_seqs=2, budget=4, queue_len=32)
    drv = OpenLoopDriver(loop, clock, items, step_dt=1.0,
                         sla_ttft_s=2.0)
    res = drv.run()
    assert res.lost == 0
    # the backlogged burst makes late admittees wait >> 2 virtual s
    assert drv.sla_violations()["ttft"] > 0
    # light load control: same SLA, arrivals spread out -> no violations
    gen2 = _gen(arrival="deterministic", rate_rps=0.1,
                length_dist="fixed", prompt_len_mean=6,
                output_len_mean=6)
    loop2, clock2 = _make_fake_loop(max_seqs=2, budget=4)
    drv2 = OpenLoopDriver(loop2, clock2, gen2.generate(4), step_dt=1.0,
                          sla_ttft_s=2.0)
    drv2.run()
    assert drv2.sla_violations()["ttft"] == 0


def test_open_loop_drives_a_fleet_and_disagg_pools():
    """The driver's target contract covers the router: an open-loop
    stream against a 3-replica DISAGG fleet (1 prefill + 2 decode,
    real allocator fakes) completes with zero loss and the fleet
    sampler records per-pool series."""
    clock = FakeClock()
    cfg = ServingConfig(
        max_queue_len=64, prefix_cache_blocks=16, audit_blocks=True,
        tracing=TracingConfig(metrics_ring=1024),
        fleet=FleetConfig(replicas=3, snapshot_interval_steps=1,
                          disagg=DisaggConfig(prefill_replicas=1,
                                              decode_replicas=2)))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(3)]
    fleet = FleetRouter(loops, cfg)
    gen = _gen(arrival="poisson", rate_rps=1.0, vocab_size=64,
               prompt_len_mean=8.0, prompt_len_min=5,
               prompt_len_max=14, output_len_mean=3.0,
               output_len_min=2, output_len_max=4)
    res = OpenLoopDriver(fleet, clock, gen.generate(10),
                         step_dt=1.0).run()
    assert res.lost == 0 and res.rejected == 0
    fleet.audit()
    rows = list(fleet.metrics.ring.rows)
    assert rows and rows[-1]["completed_total"] == 10
    assert any("pool_decode_load" in r for r in rows)


def test_calibrate_service_rate_is_deterministic():
    gen = _gen(arrival="poisson", rate_rps=1.0, length_dist="fixed",
               prompt_len_mean=6, output_len_mean=6)
    items = gen.generate(8)

    def make_loop():
        return _make_fake_loop(max_seqs=2, budget=4)

    mu1 = calibrate_service_rate(make_loop, items, step_dt=1.0)
    mu2 = calibrate_service_rate(make_loop, items, step_dt=1.0)
    assert mu1 == mu2 > 0


# -- the ramp, on a tiny real engine ---------------------------------------
def test_open_loop_ramp_detects_collapse_knee_on_real_engine(monkeypatch):
    """Integration (ISSUE 13 acceptance): the bench sweep row's driver
    — calibration, ρ ramp, bit-stability across arms + replay,
    monotone utilization/queue series, SLA-violation onset at the
    overloaded arm, zero loss / zero leaked blocks — end-to-end on a
    tiny REAL engine under the fake clock."""
    import jax
    import jax.numpy as jnp

    import bench_serve
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    def tiny_engine(ctx_budget, max_seqs=4, decode_burst=8, **kw):
        cfg = TransformerConfig(vocab_size=96, hidden_size=32,
                                num_layers=2, num_heads=2,
                                max_seq_len=512, dtype=jnp.float32)
        model = Transformer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ecfg = RaggedInferenceEngineConfig(
            num_blocks=96, block_size=16, max_blocks_per_seq=24,
            max_seqs=max_seqs, prefill_chunk_size=64)
        return InferenceEngineV2(model, params=params, config=ecfg), cfg

    monkeypatch.setattr(bench_serve, "_engine", tiny_engine)
    value, extras = bench_serve.bench_serving_openloop_sweep(
        n_requests=16, seed=3, rhos=(0.3, 1.0, 5.0), max_seqs=2,
        decode_burst=8)
    arms = extras["arms"]
    assert value > 0 and len(arms) == 3
    assert extras["lost_requests"] == 0 and extras["rejected"] == 0
    # the knee: the overloaded arm queues where the light arm idles
    assert arms[-1]["queue_depth_peak"] > arms[0]["queue_depth_peak"]
    assert arms[-1]["ttft_p95_vs"] > arms[0]["ttft_p95_vs"]
    assert arms[0]["sla_ttft_violations"] == 0
    assert arms[-1]["sla_ttft_violations"] > 0
    assert extras["sla_onset_rho"] == arms[-1]["rho"]


# -- perf-regression ledger ------------------------------------------------
def _train_artifact(n: int, value: float) -> dict:
    """What the driver's bench.py capture looks like (synthetic: the
    committed training rounds described an installation that is gone)."""
    parsed = {"metric": "tokens/sec/chip (GPT-2-large 774M, ZeRO bf16, "
                        "seq 1024)",
              "value": value, "unit": "tokens/s/chip", "vs_baseline": 1.0}
    return {"n": n, "cmd": "python bench.py", "rc": 0,
            "tail": json.dumps(parsed) + "\n", "parsed": parsed}


def test_ledger_ingests_the_committed_artifacts(tmp_path):
    """The committed BENCH_SERVE_r* artifacts plus a training series
    (synthetic BENCH_r01–r05 beside copies of them) all validate and
    build one trajectory with the expected series."""
    import glob
    import shutil
    for path in glob.glob(os.path.join(REPO_ROOT, "BENCH_SERVE_r*.json")):
        shutil.copy(path, tmp_path)
    for n, value in enumerate((16764.0, 17435.0, 17560.3, 17429.0,
                               17610.0), start=1):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(
            json.dumps(_train_artifact(n, value)))
    doc = bench_history.build_trajectory(str(tmp_path))
    rows = doc["rows"]
    for key in ("serve_spec_c8", "serve_disagg_c8x3",
                "serve_smallctx_c8", "serve_closed_c8",
                "serve_fleet_chaos_c8x3", "serve_tp_c2"):
        assert key in rows, f"serve row {key} missing from trajectory"
        assert rows[key]["unit"] == "tokens/s"
        assert all(e["backend"] == "cpu" for e in rows[key]["series"])
    # the 774M train metric repeated across rounds -> a real series
    train = [k for k in rows if k.startswith("tokens/sec/chip")]
    assert train and any(len(rows[k]["series"]) >= 3 for k in train)
    assert len(doc["sources"]["serve"]) >= 5
    assert len(doc["sources"]["train"]) >= 5


def test_committed_trajectory_is_current_and_valid():
    """Tier-1 ledger-schema gate: BENCH_TRAJECTORY.json is committed,
    schema-valid, and exactly what a rebuild from the committed
    artifacts produces — a hand-added or malformed BENCH_*.json fails
    HERE, at commit time, instead of silently dropping out of the
    trajectory."""
    committed = bench_history.load_trajectory(REPO_ROOT)
    rebuilt = bench_history.build_trajectory(REPO_ROOT)
    assert committed == rebuilt, (
        "BENCH_TRAJECTORY.json is stale: rebuild it with "
        "`dstpu_bench --history --rebuild` (bench_serve.py does this "
        "automatically unless --no-history)")
    # and the committed trajectory passes its own gate
    report, rc = bench_history.check_latest(REPO_ROOT)
    assert rc == 0, f"committed trajectory fails its own gate: {report}"


def test_ledger_rejects_malformed_artifacts(tmp_path):
    p = tmp_path / "BENCH_SERVE_r01.json"
    p.write_text("{not json")
    with pytest.raises(bench_history.LedgerError, match="r01"):
        bench_history.build_trajectory(str(tmp_path))
    p.write_text(json.dumps({"round": 1, "date": "d", "backend": "cpu",
                             "rows": [{"key": "x", "unit": "tokens/s"}]}))
    with pytest.raises(bench_history.LedgerError, match="value"):
        bench_history.build_trajectory(str(tmp_path))
    q = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps({"round": 1, "date": "d", "backend": "cpu",
                             "rows": []}))
    q.write_text(json.dumps({"n": 1}))
    with pytest.raises(bench_history.LedgerError, match="parsed"):
        bench_history.build_trajectory(str(tmp_path))


def _write_round(tmp_path, n, value, backend="cpu", key="row_a",
                 unit="tokens/s"):
    doc = {"round": n, "date": f"2026-08-{n:02d}", "backend": backend,
           "note": "", "rows": [{"key": key, "value": value,
                                 "unit": unit, "backend": backend}]}
    (tmp_path / f"BENCH_SERVE_r{n:02d}.json").write_text(
        json.dumps(doc))


def test_regression_gate_classification_table(tmp_path):
    """The classification table: ok / improved / regressed / new /
    unit_mismatch, lower-better units inverted, backends never
    pooled."""
    for n, v in ((1, 100.0), (2, 110.0), (3, 95.0)):
        _write_round(tmp_path, n, v)
    traj = bench_history.build_trajectory(str(tmp_path))
    rows = [
        {"key": "row_a", "value": 100.0, "unit": "tokens/s"},   # in band
        {"key": "row_a", "value": 50.0, "unit": "tokens/s"},    # regress
        {"key": "row_a", "value": 200.0, "unit": "tokens/s"},   # improve
        {"key": "row_b", "value": 1.0, "unit": "tokens/s"},     # new
        {"key": "row_a", "value": 100.0, "unit": "ms/token"},   # unit
    ]
    out = bench_history.classify(traj, rows, backend="cpu",
                                 rel_tol=0.2)
    assert [r["verdict"] for r in out] == [
        "ok", "regressed", "improved", "new", "unit_mismatch"]
    assert out[0]["prior_points"] == 3 and not out[0]["thin_history"]
    # lower-is-better inversion: a LOWER ms/token is an improvement
    for n in (1, 2, 3):
        os.remove(tmp_path / f"BENCH_SERVE_r{n:02d}.json")
    _write_round(tmp_path, 1, 10.0, key="lat", unit="ms/token")
    traj = bench_history.build_trajectory(str(tmp_path))
    out = bench_history.classify(
        traj, [{"key": "lat", "value": 50.0, "unit": "ms/token"},
               {"key": "lat", "value": 2.0, "unit": "ms/token"}],
        backend="cpu", rel_tol=0.2)
    assert [r["verdict"] for r in out] == ["regressed", "improved"]
    assert out[0]["thin_history"] is True
    # cross-backend history never pools: a tpu row against cpu-only
    # history is NEW, not compared against the wrong band
    out = bench_history.classify(
        traj, [{"key": "lat", "value": 50.0, "unit": "ms/token"}],
        backend="tpu")
    assert out[0]["verdict"] == "new"


def test_regression_gate_exits_nonzero_on_injected_regression(tmp_path):
    """End-to-end gate contract (ISSUE 13 acceptance): a synthetic
    regressed round exits nonzero via `dstpu_bench --history --check`;
    the healthy trajectory passes."""
    from deepspeed_tpu.benchmarks.comms_bench import main as bench_main

    for n, v in ((1, 100.0), (2, 108.0)):
        _write_round(tmp_path, n, v)
    bench_history.rebuild(str(tmp_path))
    assert bench_main(["--history", "--root", str(tmp_path),
                       "--check"]) == 0
    # inject the regression as the latest round and re-gate
    _write_round(tmp_path, 3, 40.0)
    bench_history.rebuild(str(tmp_path))
    assert bench_main(["--history", "--root", str(tmp_path),
                       "--check"]) == 1
    report, rc = bench_history.check_latest(str(tmp_path))
    assert rc == 1
    assert report[0]["verdict"] == "regressed"
    # the check excludes the checked round from its own band: round 3's
    # own 40.0 must not have widened the band it is judged against
    assert report[0]["prior_points"] == 2
    # a unit rename is a gate FAILURE too (the row was never compared;
    # exit 0 would let a regression hide behind the rename).  No
    # rebuild here: the --check-only flow gates the renamed round
    # against the trajectory on disk (a rebuild would itself refuse
    # the mid-trajectory unit change, the other loud path)
    _write_round(tmp_path, 4, 100.0, unit="tok/s")
    report, rc = bench_history.check_latest(str(tmp_path))
    assert rc == 1 and report[0]["verdict"] == "unit_mismatch"
    with pytest.raises(bench_history.LedgerError, match="unit"):
        bench_history.rebuild(str(tmp_path))
    os.remove(tmp_path / "BENCH_SERVE_r04.json")
    # ...and a row carrying its OWN backend stamp classifies against
    # THAT backend's band, not the document's (a tpu row over cpu-only
    # history is new, never a false cpu-band verdict)
    doc = {"round": 4, "date": "2026-08-04", "backend": "cpu",
           "note": "", "rows": [{"key": "row_a", "value": 1.0,
                                 "unit": "tokens/s", "backend": "tpu"}]}
    (tmp_path / "BENCH_SERVE_r04.json").write_text(json.dumps(doc))
    bench_history.rebuild(str(tmp_path))
    report, rc = bench_history.check_latest(str(tmp_path))
    assert rc == 0
    assert report[0]["verdict"] == "new"
    assert report[0]["backend"] == "tpu"


def test_gate_failed_rounds_never_self_heal_into_the_band(tmp_path):
    """A round that failed the gate is stamped `gate_failed`
    (persist_rows does this before raising) and its values are
    excluded from every future noise band — an unfixed regression
    keeps failing on re-runs instead of becoming its own precedent."""
    for n, v in ((1, 100.0), (2, 108.0)):
        _write_round(tmp_path, n, v)
    _write_round(tmp_path, 3, 40.0)                 # the regression
    bench_history.rebuild(str(tmp_path))
    report, rc = bench_history.check_latest(str(tmp_path))
    assert rc == 1
    # the stamp (what bench_serve's auto-gate applies on failure)
    bench_history.mark_gate_failed(
        str(tmp_path / "BENCH_SERVE_r03.json"))
    bench_history.rebuild(str(tmp_path))
    # the unfixed re-run at the same regressed value STILL fails: round
    # 3's 40.0 did not widen the band it is judged against
    _write_round(tmp_path, 4, 40.0)
    bench_history.rebuild(str(tmp_path))
    report, rc = bench_history.check_latest(str(tmp_path))
    assert rc == 1 and report[0]["verdict"] == "regressed"
    assert report[0]["prior_points"] == 2           # r01 + r02 only
    # the failed re-run gets stamped too; a genuinely recovered round
    # then passes against the healthy band
    bench_history.mark_gate_failed(
        str(tmp_path / "BENCH_SERVE_r04.json"))
    _write_round(tmp_path, 5, 104.0)
    bench_history.rebuild(str(tmp_path))
    report, rc = bench_history.check_latest(str(tmp_path))
    assert rc == 0 and report[0]["verdict"] == "ok"
    assert report[0]["prior_points"] == 2
