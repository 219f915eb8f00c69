"""Tests: the serving observatory (ISSUE 13) — seeded open-loop
workload generation, the open-loop driver against bare loops / fleets /
disagg pools, the bounded metric time series + its schema gate, and the
recompile flight recorder (positive AND negative control).

Determinism discipline matches the rest of the serving tier: fake
engines where blocks don't matter, a real DSStateManager fake where
they do, one tiny REAL engine for the ramp integration test, shared
FakeClocks, zero sleeps.
"""
import json

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

pytestmark = pytest.mark.serving


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
def test_open_loop_ramp_detects_collapse_knee_on_real_engine():
    """The queueing-collapse knee on a tiny REAL engine under the fake
    clock (1 serve step = 1 virtual second): one seeded heavy-tailed
    workload, its arrival rate set to rho x the calibrated service rate,
    at rho 0.3 / 1.0 / 5.0.  Arrival timing changes scheduling and never
    results (the same greedy tokens in every arm, and the overloaded arm
    replays bit-identically); nothing is lost, rejected or leaked;
    occupancy and the queue's peak never fall as rho rises; the
    overloaded arm queues and waits where the light arm idles; and
    against a TTFT target anchored to the light arm, violations are 0
    there and > 0 past the knee."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=96, hidden_size=32, num_layers=2,
                            num_heads=2, max_seq_len=512,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    eng = InferenceEngineV2(
        model, params=model.init_params(jax.random.PRNGKey(0)),
        config=RaggedInferenceEngineConfig(
            num_blocks=96, block_size=16, max_blocks_per_seq=24,
            max_seqs=2, prefill_chunk_size=64))

    def make_loop():
        # one engine under every arm; a fresh loop and clock for each
        clock = FakeClock()
        return ServeLoop(eng, ServingConfig(
            max_queue_len=512, decode_burst=8, audit_blocks=True,
            tracing=TracingConfig(enabled=False, metrics_ring=8192)),
            clock=clock), clock

    n = 16
    gen = WorkloadGenerator(
        vocab_size=cfg.vocab_size, seed=3, arrival="poisson",
        rate_rps=1.0, prompt_len_mean=48.0, prompt_len_sigma=0.9,
        prompt_len_min=8, prompt_len_max=320, output_len_mean=12.0,
        output_len_sigma=0.6, output_len_min=2, output_len_max=48)
    mu = calibrate_service_rate(make_loop, gen.generate(n), step_dt=1.0)
    assert mu > 0

    def arm(rho):
        loop, clock = make_loop()
        res = OpenLoopDriver(loop, clock,
                             gen.with_rate(rho * mu).generate(n),
                             step_dt=1.0).run()
        assert res.lost == res.rejected == res.rejected_invalid == 0
        assert len(res.requests) == n
        eng.audit_blocks()
        s = loop.telemetry.summary(elapsed_s=res.elapsed_s)
        return {"tokens": [list(r.output_tokens) for r in res.requests],
                "ttft": list(loop.telemetry.ttft),
                "ttft_p95": s["ttft_p95_s"],
                "occupancy": s["batch_occupancy_mean"],
                "queue_peak": max(loop.metrics.ring.series("queue_depth"))}

    light, at_capacity, overloaded = arms = [arm(r) for r in (0.3, 1.0, 5.0)]
    assert at_capacity["tokens"] == overloaded["tokens"] == light["tokens"]
    replay = arm(5.0)
    assert (replay["tokens"], replay["ttft"]) == (overloaded["tokens"],
                                                  overloaded["ttft"])
    for series in ("occupancy", "queue_peak"):
        xs = [a[series] for a in arms]
        assert all(b >= a - 1e-9 for a, b in zip(xs, xs[1:])), (series, xs)
    assert overloaded["queue_peak"] > light["queue_peak"]
    assert overloaded["ttft_p95"] > light["ttft_p95"]
    # virtual time counts whole steps, so an uncontended TTFT can be 0:
    # anchor the target one step above the light arm's p95
    target = 3.0 * (light["ttft_p95"] + 1.0)
    assert sum(t > target for t in light["ttft"]) == 0
    assert sum(t > target for t in overloaded["ttft"]) > 0
