"""Tests: fleet control plane (deepspeed_tpu.serving.fleet) — the
deterministic fault-injection harness, the heartbeat supervisor's
HEALTHY/SUSPECT/DRAINED state machine, automatic zero-loss failover,
migration transport atomicity under injected failure, crash containment
(FAILED terminal state), and the watermark/cooldown autoscaler.

Determinism discipline matches test_fleet.py: replicas are ServeLoops
over the DSStateManager-backed fake engine (real allocator refcounts,
real radix prefix cache), one shared fault-harness FakeClock advanced
manually, the fleet driven lock-step by `FleetRouter.step()` — faults
are step-indexed and clock-timed, so every scenario replays exactly.
"""
import numpy as np
import pytest

from deepspeed_tpu.config.config import (AutoscaleConfig, ConfigError,
                                         DeepSpeedTPUConfig, FleetConfig,
                                         ServingConfig, SupervisorConfig)
from deepspeed_tpu.serving import (FleetRouter, ReplicaHealth, RequestErrored,
                                   RequestState, ServeLoop, ThreadedServer)
from deepspeed_tpu.serving.fleet.faults import (FOREVER, FakeClock, Fault,
                                                FaultInjected, FaultInjector,
                                                FaultPlan, FaultyTransport,
                                                TransportFault)
from deepspeed_tpu.serving.fleet.migration import NullBlockTransport

from test_fleet import BS, SHARED, PrefixFakeEngine, _prompt, _replica_of

pytestmark = pytest.mark.serving


def _sup(**kw):
    kw.setdefault("heartbeat_timeout_s", 3.0)
    kw.setdefault("error_burst", 2)
    kw.setdefault("error_window_s", 100.0)
    kw.setdefault("failover_after_s", 6.0)
    kw.setdefault("recovery_ticks", 3)
    kw.setdefault("flap_window_s", 50.0)
    return SupervisorConfig(**kw)


def _fleet(n=2, pcb=16, fleet_cfg=None, clock=None, transport=None,
           loop_factory_engine_kw=None, **engine_kw):
    clock = clock or FakeClock()
    cfg = ServingConfig(
        prefix_cache_blocks=pcb, audit_blocks=True,
        fleet=fleet_cfg or FleetConfig(replicas=n,
                                       snapshot_interval_steps=1,
                                       supervisor=_sup()))
    loops = [ServeLoop(PrefixFakeEngine(**engine_kw), cfg, clock=clock)
             for _ in range(n)]

    def loop_factory():
        return ServeLoop(
            PrefixFakeEngine(**(loop_factory_engine_kw or engine_kw)),
            cfg, clock=clock)

    return (FleetRouter(loops, cfg, transport=transport,
                        loop_factory=loop_factory), clock)


def _tick(fleet, clock, n=1, dt=1.0):
    """One (or n) lock-step fleet steps with the serve clock advancing
    `dt` seconds per step — the deterministic stand-in for wall time."""
    for _ in range(n):
        fleet.step()
        clock.advance(dt)


# -- fault plan / injector -------------------------------------------------
def test_fault_plan_validation_and_determinism():
    with pytest.raises(ValueError, match="kind"):
        Fault("melt", 0)
    with pytest.raises(ValueError, match="steps"):
        Fault("error", 0, steps=0)
    with pytest.raises(ValueError, match="slow_s"):
        Fault("slow", 0)
    a = FaultPlan.random(seed=7, horizon=64)
    b = FaultPlan.random(seed=7, horizon=64)
    assert [(f.kind, f.start, f.steps, f.slow_s) for f in a.faults] == \
           [(f.kind, f.start, f.steps, f.slow_s) for f in b.faults]
    c = FaultPlan.random(seed=8, horizon=64)
    assert [(f.kind, f.start) for f in a.faults] != \
           [(f.kind, f.start) for f in c.faults]
    death = FaultPlan.replica_death(5)
    assert death.active("error", 4) is None
    assert death.active("error", 5) is not None
    assert death.active("error", 10 ** 12) is not None


def test_fault_injector_error_freezes_progress_and_counts_errors():
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(), ServingConfig(audit_blocks=True),
                     clock=clock)
    inj = FaultInjector(loop, FaultPlan([Fault("error", 1, steps=2)]))
    req = loop.submit(_prompt(0), max_new_tokens=3)
    loop.step()                                  # call 0: normal
    p = loop.progress
    assert p == 1
    for _ in range(2):                           # calls 1-2: injected
        with pytest.raises(FaultInjected):
            loop.step()
    assert loop.progress == p                    # heartbeat frozen
    assert loop.step_errors == 2
    assert isinstance(loop.last_step_error, FaultInjected)
    while loop.has_work:                         # recovers after the fault
        loop.step()
    assert req.state is RequestState.DONE
    inj.uninstall()
    assert loop.step.__func__ is ServeLoop.step  # surface restored
    loop.engine.audit_blocks()


def test_fault_injector_stall_is_silent_and_slow_burns_clock():
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(), ServingConfig(), clock=clock)
    FaultInjector(loop, FaultPlan([Fault("stall", 0, steps=3),
                                   Fault("slow", 3, steps=2, slow_s=5.0)]))
    loop.submit(_prompt(1), max_new_tokens=2)
    for _ in range(3):
        assert loop.step() == []                 # stalled: no completions
    assert loop.progress == 0 and loop.step_errors == 0
    t0 = clock()
    loop.step()                                  # slow: works, but late
    assert clock() - t0 == 5.0
    assert loop.progress == 1


def test_drop_snapshot_fault_starves_the_router_view():
    fleet, clock = _fleet()
    inj = FaultInjector(fleet.replicas[0].loop,
                        FaultPlan([Fault("drop_snapshot", 0,
                                         steps=FOREVER)]))
    primer = fleet.submit(_prompt(0), max_new_tokens=2)
    _tick(fleet, clock, n=40)
    assert primer.state is RequestState.DONE
    # replica 0 finished and cached the prefix, but its digest is frozen:
    # the router never saw a snapshot, so the index claims nothing
    assert fleet.index.lookup(_prompt(1)).get(0, 0) == 0
    inj.uninstall()
    assert fleet.publish_snapshots() == 1        # view catches up
    assert fleet.index.lookup(_prompt(1))[0] == 4 * BS


# -- supervisor state machine ----------------------------------------------
def test_demote_on_missed_heartbeat():
    fleet, clock = _fleet()
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("stall", 0, steps=FOREVER)]))
    fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=2)
    _tick(fleet, clock, n=2)
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY  # < timeout
    _tick(fleet, clock, n=2)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    assert fleet.telemetry.health_events["demoted_heartbeat"] == 1
    # new work routes to the healthy survivor only
    req = fleet.submit(_prompt(5), max_new_tokens=2)
    assert _replica_of(fleet, req) == 1


def test_idle_replica_never_misses_heartbeats():
    fleet, clock = _fleet()
    _tick(fleet, clock, n=20, dt=10.0)           # long idle stretch
    assert all(r.health is ReplicaHealth.HEALTHY for r in fleet.replicas)
    assert all(v == 0 for v in fleet.telemetry.health_events.values())


def test_demote_on_error_burst():
    fleet, clock = _fleet()
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=2)
    _tick(fleet, clock, n=1)
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY   # 1 < burst
    _tick(fleet, clock, n=1)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    assert fleet.telemetry.health_events["demoted_error_burst"] == 1


def test_recovery_promotes_with_hysteresis():
    fleet, clock = _fleet()
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("stall", 0, steps=6)]))
    fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=20)
    _tick(fleet, clock, n=6)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    # the fault cleared at call 6; recovery needs recovery_ticks=3 CLEAN
    # ticks — one or two are not enough (hysteresis)
    _tick(fleet, clock, n=2)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    _tick(fleet, clock, n=1)
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY
    assert fleet.telemetry.health_events["promoted"] == 1


def test_flapping_replica_escalates_required_streak():
    fleet, clock = _fleet()
    # stall windows with just-long-enough clean gaps to re-promote, so
    # the replica flaps: each relapse inside flap_window_s doubles the
    # streak the next promotion requires
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("stall", 0, steps=5),
                             Fault("stall", 9, steps=5)]))
    fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=40)
    sup = fleet.supervisor
    _tick(fleet, clock, n=5)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    assert sup.required_streak(0) == 3           # first incident: base
    _tick(fleet, clock, n=4)                     # clean calls 5-8: promote
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY
    _tick(fleet, clock, n=5)                     # relapse (calls 9-13)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    assert sup.required_streak(0) == 6           # flap: doubled
    _tick(fleet, clock, n=4)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT  # 3 no longer enough
    _tick(fleet, clock, n=3)
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY


def test_promotion_forgives_the_demoting_error_burst():
    # error_window_s=100 keeps the demoting burst's timestamps "in
    # window" long after recovery: promotion must clear them, or the
    # very next tick re-demotes (and flap-escalates) a replica that
    # produced ZERO new errors
    fleet, clock = _fleet()
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=2)]))
    fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=20)
    _tick(fleet, clock, n=2)
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    _tick(fleet, clock, n=3)                     # clean streak: promote
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY
    _tick(fleet, clock, n=10)                    # still inside the window
    assert fleet.replicas[0].health is ReplicaHealth.HEALTHY
    assert fleet.telemetry.health_events["demoted_error_burst"] == 1
    assert fleet.telemetry.health_events["promoted"] == 1


def test_mid_step_crash_cannot_drop_finalized_requests():
    # a request finalized early in a step (deadline expiry) whose step
    # then RAISES must still come back from fleet.step() — via the
    # crash-safe backlog the router drains on a step error — even if
    # the replica never completes another step (it is about to die)
    fleet, clock = _fleet(max_seqs=1)
    rep = fleet.replicas[0]
    rep.loop.submit(_prompt(0), max_new_tokens=30)       # holds the slot
    doomed = rep.loop.submit(_prompt(1), max_new_tokens=2, timeout_s=2.0)
    _tick(fleet, clock, n=1)
    clock.advance(5.0)                   # deadline passes while QUEUED
    assert doomed.state is RequestState.QUEUED

    def boom(*a, **kw):
        raise RuntimeError("engine died")
    rep.loop.engine.step = boom          # next _step: expire, THEN raise
    rep.loop.engine.put = boom
    finished = fleet.step()
    assert doomed in finished
    assert doomed.state is RequestState.TIMED_OUT
    assert rep.loop.step_errors == 1     # the crash was still recorded


def test_failover_on_sustained_silence_is_zero_loss_and_automatic():
    """The tentpole acceptance path in miniature: a replica dies
    mid-stream, NOBODY calls drain, and every accepted request still
    resolves — queued work re-routed, in-flight work re-queued and
    regenerated on the survivor, waiters never stranded."""
    fleet, clock = _fleet(max_seqs=1)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=4) for i in range(6)]
    _tick(fleet, clock, n=2)                     # both replicas mid-work
    on_r0 = [r for r in reqs if _replica_of(fleet, r) == 0]
    in_flight_r0 = [r for r in on_r0 if r.state is not RequestState.QUEUED]
    assert on_r0 and in_flight_r0                # something to kill
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    _tick(fleet, clock, n=12)
    assert fleet.replicas[0].health is ReplicaHealth.DRAINED
    assert fleet.supervisor.failovers == 1
    assert fleet.telemetry.health_events["failovers"] == 1
    assert fleet.telemetry.failover_requeued >= len(in_flight_r0)
    # drive to completion on the survivor (dead replica holds nothing)
    assert not fleet.replicas[0].loop.has_work
    _tick(fleet, clock, n=200)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(r.finished for r in reqs)
    # retried requests regenerated the right tokens from scratch
    for r in reqs:
        assert list(r.output_tokens) == [
            (int(r.prompt[-1]) + 1 + k) % 64 for k in range(4)]
    fleet.replicas[1].loop.engine.audit_blocks()  # survivor leak-free
    s = fleet.summary()
    assert s["health"][0] == "drained" and s["failovers"] == 1


def test_failover_respects_retry_budget_and_fails_loudly():
    fleet, clock = _fleet(max_seqs=1, fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1,
        supervisor=_sup(max_request_retries=0)))
    reqs = [fleet.submit(_prompt(i), max_new_tokens=4) for i in range(2)]
    _tick(fleet, clock, n=2)
    victim = [r for r in reqs if _replica_of(fleet, r) == 0
              and r.state is not RequestState.QUEUED]
    assert victim
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    _tick(fleet, clock, n=12)
    assert fleet.replicas[0].health is ReplicaHealth.DRAINED
    # retry budget 0: the in-flight request FAILED with the error
    # attached — its waiter raises instead of hanging
    assert victim[0].state is RequestState.FAILED
    assert fleet.telemetry.failover_failed == len(victim)
    with pytest.raises(RequestErrored, match="failed over"):
        victim[0].result(timeout=0)
    assert victim[0].error is not None
    assert isinstance(victim[0].error.__cause__, FaultInjected)
    _tick(fleet, clock, n=100)
    assert all(r.finished for r in reqs)


def test_failover_finalized_requests_surface_in_step_returns():
    """Failover finalizations (FAILED past the retry budget) happen
    inside the supervisor tick, not a replica step: step() must still
    return them, or a closed-loop driver keyed on step() completions
    (the chaos bench) never observes those terminal states."""
    fleet, clock = _fleet(max_seqs=1, fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1,
        supervisor=_sup(max_request_retries=0)))
    reqs = [fleet.submit(_prompt(i), max_new_tokens=4) for i in range(2)]
    _tick(fleet, clock, n=2)
    victim = [r for r in reqs if _replica_of(fleet, r) == 0
              and r.state is not RequestState.QUEUED]
    assert victim
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    seen = []
    for _ in range(12):
        seen.extend(fleet.step())
        clock.advance(1.0)
    assert victim[0].state is RequestState.FAILED
    assert any(r is victim[0] for r in seen)


def test_drop_snapshot_fault_requires_a_prefix_cache():
    """Installing a drop_snapshot fault on a cacheless loop must be a
    loud error, not a silent no-op that lets a chaos test pass while
    exercising nothing."""
    loop = ServeLoop(PrefixFakeEngine(), ServingConfig(),
                     clock=FakeClock())
    with pytest.raises(ValueError, match="prefix cache"):
        FaultInjector(loop, FaultPlan([Fault("drop_snapshot", 0)]))
    assert loop.step.__func__ is ServeLoop.step  # surface untouched


def test_drained_replica_wedged_mid_retirement_fails_over():
    """An operator drains a replica holding in-flight work, then its
    engine dies: the supervisor must keep watching the DRAINED replica
    (router.step swallows its errors as health signals) and fail its
    work over instead of hanging the waiters forever."""
    fleet, clock = _fleet(max_seqs=1)
    req = fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=4)
    _tick(fleet, clock)                       # in-flight on replica 0
    assert req.state is not RequestState.QUEUED
    assert fleet.drain(0) == []               # nothing queued to re-route
    assert fleet.replicas[0].health is ReplicaHealth.DRAINED
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    _tick(fleet, clock, n=15)        # heartbeat + failover deadline
    assert fleet.supervisor.failovers == 1
    assert fleet.telemetry.failover_requeued == 1
    assert not fleet.replicas[0].loop.has_work
    _tick(fleet, clock, n=100)
    assert req.state is RequestState.DONE     # regenerated on replica 1
    fleet.replicas[1].loop.engine.audit_blocks()


def test_operator_mark_suspect_reaches_automatic_failover():
    """mark_suspect sets no suspect_since — the supervisor must latch
    the failover deadline at its first observation, or `now - since`
    reads 0 every tick and automatic failover can never fire."""
    fleet, clock = _fleet(max_seqs=1)
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("stall", 0, steps=FOREVER)]))
    req = fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=2)
    fleet.mark_suspect(0)
    _tick(fleet, clock, n=5)                  # inside failover_after_s
    assert fleet.replicas[0].health is ReplicaHealth.SUSPECT
    assert fleet.supervisor.failovers == 0
    _tick(fleet, clock, n=5)                  # past the latched deadline
    assert fleet.replicas[0].health is ReplicaHealth.DRAINED
    assert fleet.supervisor.failovers == 1
    _tick(fleet, clock, n=60)
    assert req.state is RequestState.DONE     # re-homed on replica 1


def test_supervised_fleet_without_faults_is_bit_for_bit_unsupervised():
    prompts = [_prompt(i, tail_len=3 + i) for i in range(5)]

    def run(supervised):
        sup = _sup() if supervised else None
        fleet, clock = _fleet(fleet_cfg=FleetConfig(
            replicas=2, snapshot_interval_steps=1, supervisor=sup))
        reqs = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        _tick(fleet, clock, n=120, dt=0.5)
        assert not fleet.has_work
        fleet.audit()
        return ([list(r.output_tokens) for r in reqs],
                {rid: dict(rep.loop.telemetry.counters)
                 for rid, rep in enumerate(fleet.replicas)},
                fleet.telemetry.routed)

    outs_on, counters_on, routed_on = run(True)
    outs_off, counters_off, routed_off = run(False)
    assert outs_on == outs_off
    assert counters_on == counters_off
    assert routed_on == routed_off


def test_unsupervised_fleet_propagates_step_errors_unchanged():
    fleet, clock = _fleet(fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1))     # PR-5 default
    assert fleet.supervisor is None and fleet.autoscaler is None
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    fleet.replicas[0].loop.submit(_prompt(0), max_new_tokens=2)
    with pytest.raises(FaultInjected):
        fleet.step()


# -- crash containment (satellite 1) ---------------------------------------
def test_serve_loop_fail_all_releases_every_waiter():
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(max_seqs=1),
                     ServingConfig(audit_blocks=True), clock=clock)
    reqs = [loop.submit(_prompt(i), max_new_tokens=4) for i in range(3)]
    loop.step()                                  # req 0 in flight
    boom = RuntimeError("boom")
    failed = loop.fail_all(boom)
    assert {id(r) for r in failed} == {id(r) for r in reqs}
    assert all(r.state is RequestState.FAILED for r in reqs)
    assert all(r.error is boom for r in reqs)
    assert loop.telemetry.counters["failed"] == 3
    assert loop.telemetry.counters["evicted_in_flight"] == 1
    for r in reqs:
        with pytest.raises(RequestErrored):
            r.result(timeout=0)
    assert not loop.has_work
    loop.engine.audit_blocks()                   # in-flight KV released


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_threaded_server_crash_fails_waiters_instead_of_stranding():
    """The satellite regression: an exception escaping a replica's
    step() finalizes its requests FAILED (error attached) — result()
    raises, never hangs.  (The loop thread re-raising after containment
    is by design; the filter silences pytest's report of it.)"""
    server = ThreadedServer(PrefixFakeEngine(max_seqs=1),
                            ServingConfig())
    # hold the server lock while queueing + installing the fault so the
    # loop thread cannot step (and crash) between the submits —
    # deterministic, no sleeps
    with server._cond:
        reqs = [server.loop.submit(_prompt(i), max_new_tokens=4)
                for i in range(3)]
        FaultInjector(server.loop, FaultPlan([Fault("error", 0,
                                                    steps=FOREVER)]))
        server._cond.notify_all()
    for r in reqs:
        with pytest.raises(RequestErrored, match="injected step error"):
            server.result(r, timeout=30.0)
    assert all(r.state is RequestState.FAILED for r in reqs)
    with pytest.raises(RuntimeError, match="shut down"):
        server.submit(_prompt(9))


def test_put_crash_rolls_back_admission_and_releases_leases():
    """A step that raises between scheduler.admit and a successful
    engine.put must roll the admissions back to the queue: otherwise a
    replica that keeps serving (supervised recovery) holds requests the
    engine never heard of — decode_ready never sees them, their waiters
    hang forever — and their admission-time prefix leases stay pinned
    in the cache."""
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(),
                     ServingConfig(prefix_cache_blocks=16,
                                   audit_blocks=True), clock=clock)
    primer = loop.submit(_prompt(0), max_new_tokens=2)
    while loop.has_work:                  # heat the cache
        loop.step()
    assert primer.state is RequestState.DONE
    real_put = loop.engine.put

    def boom(*a, **kw):
        raise RuntimeError("put died")
    loop.engine.put = boom
    req = loop.submit(_prompt(1), max_new_tokens=2)
    with pytest.raises(RuntimeError, match="put died"):
        loop.step()
    # rolled back: queued again, unknown to scheduler.active/ledger,
    # the lease acquired at admission returned to the cache
    assert req.state is RequestState.QUEUED
    assert req.uid not in loop.scheduler.active
    assert req.uid not in loop._reserved
    assert loop._prefix_pending == {}
    loop.engine.audit_blocks()            # no pinned lease refs leaked
    loop.engine.put = real_put
    while loop.has_work:                  # engine recovers: served clean
        loop.step()
    assert req.state is RequestState.DONE
    loop.engine.audit_blocks()


def test_expiry_flush_crash_keeps_finalizations_and_ledger():
    """Deadline expiry finalizes requests and drops them from the
    scheduler BEFORE the engine flush runs: a flush that raises must
    not hide those terminal states from step()'s view (crash-safe
    backlog) or leak their reservation-ledger debit on a replica that
    later recovers."""
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(max_seqs=2),
                     ServingConfig(audit_blocks=True), clock=clock)
    reqs = [loop.submit(_prompt(i), max_new_tokens=30, timeout_s=5.0)
            for i in range(2)]
    loop.step()                              # both admitted, in flight
    assert all(r.state is not RequestState.QUEUED for r in reqs)
    clock.advance(10.0)                      # both deadlines pass

    def boom(uid):
        raise RuntimeError("flush died")
    loop.engine.flush = boom
    with pytest.raises(RuntimeError, match="flush died"):
        loop.step()
    assert all(r.state is RequestState.TIMED_OUT for r in reqs)
    backlog = loop.take_finished_backlog()
    assert {id(r) for r in backlog} == {id(r) for r in reqs}
    assert loop._reserved == {}              # ledger debited regardless


def test_unsupervised_backlog_counts_as_work_and_drains_via_step():
    """Without a supervisor nothing calls take_finished_backlog(): when
    the crashing step also emptied the scheduler, `has_work` must keep
    counting the undrained backlog so a driver keyed on step() returns
    (run_until_idle, a closed-loop bench) calls step() once more and
    observes the terminal states — instead of them vanishing forever
    behind `has_work == False`."""
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(max_seqs=2),
                     ServingConfig(audit_blocks=True), clock=clock)
    reqs = [loop.submit(_prompt(i), max_new_tokens=30, timeout_s=5.0)
            for i in range(2)]
    loop.step()                              # both admitted, in flight
    clock.advance(10.0)                      # both deadlines pass
    loop.engine.flush = lambda uid: (_ for _ in ()).throw(
        RuntimeError("flush died"))
    with pytest.raises(RuntimeError, match="flush died"):
        loop.step()
    assert all(r.state is RequestState.TIMED_OUT for r in reqs)
    assert not loop.scheduler.has_work       # the scheduler is empty...
    assert loop.has_work                     # ...but the backlog counts
    out = loop.step()                        # an ordinary next step
    assert {id(r) for r in out} == {id(r) for r in reqs}
    assert not loop.has_work                 # drained exactly once


def test_rollback_requeue_keeps_queue_position():
    """A head-of-queue request rolled back by a failed put() must
    re-enter at its ORIGINAL FIFO place, not behind same-priority
    requests that arrived after it — repeated transient put errors
    must not leapfrog (starve) the same request."""
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(max_seqs=1), ServingConfig(),
                     clock=clock)
    first = loop.submit(_prompt(0), max_new_tokens=2)
    second = loop.submit(_prompt(1), max_new_tokens=2)
    real_put = loop.engine.put
    loop.engine.put = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("put died"))
    with pytest.raises(RuntimeError, match="put died"):
        loop.step()                          # head admitted, rolled back
    assert first.state is RequestState.QUEUED
    order = [e[2].uid for e in sorted(loop.scheduler._queue)]
    assert order == [first.uid, second.uid]  # FIFO place preserved
    loop.engine.put = real_put
    while loop.has_work:
        loop.step()
    assert first.finish_time <= second.finish_time


def test_rollback_defers_admission_side_effects():
    """Admission side effects — the `admitted` counter and the routing
    hook — must fire only after put() returns: a rolled-back admission
    would otherwise be double-counted on its retry, and the fleet
    router's coverage expectation (popped by the hook) would be
    consumed by an admission that never stuck, silencing the
    stale-snapshot correction for the retry."""
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(), ServingConfig(), clock=clock)
    hooked = []
    loop.admit_hook = lambda req, covered: hooked.append(req.uid)
    real_put = loop.engine.put
    loop.engine.put = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("put died"))
    req = loop.submit(_prompt(0), max_new_tokens=2)
    with pytest.raises(RuntimeError, match="put died"):
        loop.step()
    assert loop.telemetry.counters.get("admitted", 0) == 0
    assert hooked == []                      # expectation NOT consumed
    loop.engine.put = real_put
    while loop.has_work:
        loop.step()
    assert req.state is RequestState.DONE
    assert loop.telemetry.counters["admitted"] == 1   # counted ONCE
    assert hooked == [req.uid]               # hook fired exactly once


def test_take_active_releases_pending_prefix_leases():
    """Defense in depth on the failover path: a lease still pinned in
    _prefix_pending when the supervisor pulls the replica's in-flight
    work (a crash window the step rollback normally clears) must be
    abandoned by take_active, or the dead replica's cache leaks live
    refs."""
    clock = FakeClock()
    loop = ServeLoop(PrefixFakeEngine(),
                     ServingConfig(prefix_cache_blocks=16,
                                   audit_blocks=True), clock=clock)
    primer = loop.submit(_prompt(0), max_new_tokens=2)
    while loop.has_work:
        loop.step()
    req = loop.submit(_prompt(1), max_new_tokens=2)
    # hand-build the crash window: admitted, lease pinned, put never ran
    admitted = loop.scheduler.admit(clock(), 1, lambda r: True)
    assert [id(r) for r in admitted] == [id(req)]
    lease = loop._cache.acquire(req.prompt)
    assert lease is not None
    loop._prefix_pending[req.uid] = lease
    assert [id(r) for r in loop.take_active()] == [id(req)]
    assert loop._prefix_pending == {}
    loop.engine.audit_blocks()            # lease refs returned


def test_wedged_engine_that_returns_without_working_is_demoted():
    """A wedge that RETURNS — engine.step coming back empty-handed
    every tick while a request sits in DECODE — must freeze the
    progress heartbeat just like a raise or a hang: `progress` counts
    steps that did real work, not steps that merely completed.  The
    supervisor then demotes on the missed heartbeat and fails the work
    over automatically."""
    fleet, clock = _fleet(max_seqs=1)
    req = fleet.submit(_prompt(0), max_new_tokens=4)
    _tick(fleet, clock, n=2)                     # mid-decode on replica 0
    assert req.state is RequestState.DECODE
    fleet.replicas[0].loop.engine.step = lambda decode=True: {}
    _tick(fleet, clock, n=15)
    assert fleet.telemetry.health_events["demoted_heartbeat"] == 1
    assert fleet.replicas[0].health is ReplicaHealth.DRAINED
    assert fleet.supervisor.failovers == 1
    _tick(fleet, clock, n=60)
    assert req.state is RequestState.DONE        # re-homed on replica 1
    fleet.replicas[1].loop.engine.audit_blocks()


def test_failover_does_not_double_count_drained_unserved():
    """Evicted in-flight requests are counted evicted_in_flight; their
    re-homing must not ALSO bounce them through the dead replica's
    scheduler and count them drained_unserved — a counter documented as
    queued UNSERVED work."""
    fleet, clock = _fleet(max_seqs=1)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=4) for i in range(6)]
    _tick(fleet, clock, n=2)
    rep0 = fleet.replicas[0]
    in_flight = [r for r in reqs if _replica_of(fleet, r) == 0
                 and r.state is not RequestState.QUEUED]
    queued0 = [r for r in reqs if _replica_of(fleet, r) == 0
               and r.state is RequestState.QUEUED]
    assert in_flight
    FaultInjector(rep0.loop, FaultPlan([Fault("error", 0,
                                              steps=FOREVER)]))
    _tick(fleet, clock, n=12)
    assert fleet.supervisor.failovers == 1
    c = rep0.loop.telemetry.counters
    assert c["evicted_in_flight"] == len(in_flight)
    assert c.get("drained_unserved", 0) == len(queued0)
    _tick(fleet, clock, n=200)
    assert all(r.state is RequestState.DONE for r in reqs)
    fleet.replicas[1].loop.engine.audit_blocks()


# -- migration fault atomicity (satellite 2) -------------------------------
def test_migration_transport_fault_leaves_both_arenas_green():
    """Inject a transport failure after the read, before the insert:
    both replicas must audit clean (no leaked blocks, no stuck pins),
    the routed request must still complete via cold prefill, and the
    pair must back off before retrying."""
    fleet, clock = _fleet(
        fleet_cfg=FleetConfig(replicas=2, snapshot_interval_steps=1,
                              migration=True, migration_backoff_steps=8,
                              supervisor=_sup()),
        transport=FaultyTransport(NullBlockTransport(),
                                  fail_transfers=(0,)))
    primer = fleet.submit(_prompt(0), max_new_tokens=3)
    assert _replica_of(fleet, primer) == 0
    _tick(fleet, clock, n=40)
    assert primer.state is RequestState.DONE
    # overload replica 0 so the scorer steers the next shared-prefix
    # request at replica 1 — triggering a migration whose wire breaks
    fillers = [fleet.replicas[0].loop.submit(_prompt(100 + i),
                                             max_new_tokens=3)
               for i in range(5)]
    req = fleet.submit(_prompt(7), max_new_tokens=3)
    assert _replica_of(fleet, req) == 1
    assert fleet.telemetry.migration_failures == 1
    assert fleet.telemetry.migrations == 0       # nothing migrated
    # the atomicity contract: zero leaked blocks/pins on BOTH replicas,
    # target tree untouched by the failed stream
    fleet.audit()
    assert fleet.replicas[1].loop._cache.match(_prompt(8))[1] == 0
    # immediate retry is suppressed by the pair backoff
    req2 = fleet.submit(_prompt(9), max_new_tokens=3)
    assert fleet.telemetry.migration_backoff_skips >= 1
    assert fleet.telemetry.migration_failures == 1
    _tick(fleet, clock, n=200)
    # the routed requests completed through cold prefill
    assert req.state is RequestState.DONE
    assert req2.state is RequestState.DONE
    assert all(f.state is RequestState.DONE for f in fillers)
    fleet.audit()
    # after the backoff window the next attempt goes through (the
    # faulty transport only breaks transfer 0).  Clear replica 1's tree
    # first: completing req/req2 there inserted the shared prefix, and a
    # target that already covers it would (correctly) skip migration.
    fleet.replicas[1].loop._cache.invalidate()
    fillers2 = [fleet.replicas[0].loop.submit(_prompt(200 + i),
                                              max_new_tokens=3)
                for i in range(5)]
    req3 = fleet.submit(_prompt(11), max_new_tokens=3)
    assert fleet.telemetry.migrations == 1
    _tick(fleet, clock, n=300)
    assert req3.state is RequestState.DONE
    assert all(f.state is RequestState.DONE for f in fillers2)
    fleet.audit()


def test_real_engine_migration_fault_atomicity_and_cold_prefill():
    """Same contract on real engines and a real arena transport: the
    wire breaks mid-stream, audit stays green on both replicas, and the
    routed request serves bit-for-bit via cold prefill."""
    from deepspeed_tpu.serving.fleet.migration import ArenaBlockTransport
    from test_fleet import _real_prompts, _tiny_engine

    pa, pb = _real_prompts()
    ref_loop = ServeLoop(_tiny_engine(), ServingConfig(),
                         clock=FakeClock())
    ref = [ref_loop.submit(p, max_new_tokens=5) for p in (pa, pb)]
    ref_loop.run_until_idle(max_steps=300)

    clock = FakeClock()
    cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                        fleet=FleetConfig(replicas=2,
                                          snapshot_interval_steps=1,
                                          migration=True))
    loops = [ServeLoop(_tiny_engine(), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(
        loops, cfg,
        transport=FaultyTransport(ArenaBlockTransport(),
                                  fail_transfers=(0,),
                                  fail_after_blocks=2))
    primer = fleet.submit(pa, max_new_tokens=5)
    fleet.run_until_idle(max_steps=300)
    assert primer.state is RequestState.DONE
    fleet.mark_suspect(0)                        # force routing to rep 1
    req = fleet.submit(pb, max_new_tokens=5)
    assert _replica_of(fleet, req) == 1
    assert fleet.telemetry.migration_failures == 1
    assert fleet.telemetry.migrations == 0
    fleet.audit()                                # both arenas green
    fleet.run_until_idle(max_steps=300)
    assert req.state is RequestState.DONE
    # cold prefill produced the exact from-scratch reference tokens
    assert list(req.output_tokens) == list(ref[1].output_tokens)
    assert loops[1].telemetry.counters["prefix_hits"] == 0
    fleet.audit()


# -- autoscaler ------------------------------------------------------------
def test_autoscaler_watermark_cooldown_table():
    """Drive the autoscaler tick-by-tick against a scripted occupancy
    trace and check the decision at every tick: patience debounces,
    cooldown separates events, bounds clamp."""
    fleet, clock = _fleet(n=1, fleet_cfg=FleetConfig(
        replicas=1, snapshot_interval_steps=1, supervisor=_sup(),
        autoscale=AutoscaleConfig(min_replicas=1, max_replicas=3,
                                  high_watermark=0.8, low_watermark=0.2,
                                  patience_ticks=2, cooldown_s=10.0)))
    scaler = fleet.autoscaler
    occ = [0.0]
    scaler.occupancy = lambda: occ[0]
    # ticks run 3 serve-clock seconds apart (cooldown_s = 10 spans >3
    # ticks); expected (scale_ups, scale_downs) AFTER each tick
    table = [
        (0.9, 0, 0),    # t=0  above, patience 1/2
        (0.9, 1, 0),    # t=3  above, patience 2/2 -> UP (1 -> 2 live)
        (0.9, 1, 0),    # t=6  above again, but inside cooldown
        (0.5, 1, 0),    # t=9  in band: patience counters reset
        (0.9, 1, 0),    # t=12 above, patience 1/2 (was reset)
        (0.9, 2, 0),    # t=15 patience 2/2, cooldown over -> UP (3 live)
        (0.9, 2, 0),    # t=18 above, but at max_replicas: clamped
        (0.9, 2, 0),    # t=21 still clamped (counters keep running)
        (0.05, 2, 0),   # t=24 below, patience 1/2
        (0.05, 2, 1),   # t=27 patience 2/2 -> DOWN (3 -> 2 live)
        (0.05, 2, 1),   # t=30 inside cooldown
        (0.05, 2, 1),   # t=33 inside cooldown
        (0.05, 2, 1),   # t=36 inside cooldown (36-27 = 9 < 10)
        (0.05, 2, 2),   # t=39 cooldown over, patience held -> DOWN (1)
        (0.05, 2, 2),   # t=42 at min_replicas: clamped
        (0.05, 2, 2),   # t=45 still clamped
    ]
    for i, (o, ups, downs) in enumerate(table):
        occ[0] = o
        scaler.tick()
        assert (scaler.scale_ups, scaler.scale_downs) == (ups, downs), \
            f"tick {i} (t={clock()}): occ={o}"
        clock.advance(3.0)
    assert len(scaler.live_replicas()) == 1
    # retired replicas were idle: removed from the router entirely
    scaler.tick()
    assert len(fleet.replicas) == 1


def test_autoscaler_sla_pressure_table():
    """SLA-driven pool scaling (`AutoscaleConfig.sla_pressure`): new
    TTFT/TPOT violations since the last tick count as above-watermark
    pressure — patience debounces them, cooldown separates events, and
    violations landing inside a cooldown are consumed, not replayed.
    Flag off (the default) is bit-for-bit the occupancy-only scaler:
    the same violation stream moves nothing."""
    import types

    def build(sla_pressure):
        fleet, clock = _fleet(n=1, fleet_cfg=FleetConfig(
            replicas=1, snapshot_interval_steps=1, supervisor=_sup(),
            autoscale=AutoscaleConfig(min_replicas=1, max_replicas=3,
                                      high_watermark=0.8,
                                      low_watermark=0.2,
                                      patience_ticks=2, cooldown_s=10.0,
                                      sla_pressure=sla_pressure)))
        fleet.telemetry.sla_ttft_target_s = 1.0
        fleet.telemetry.sla_tpot_target_s = 0.1
        for rep in fleet.replicas:                # what disagg init does
            fleet._propagate_sla_targets(rep)
        fleet.autoscaler.occupancy = lambda: 0.5   # mid-band: occupancy
        return fleet, clock, fleet.autoscaler     # never votes either way

    def violate(rep):
        # a finished request blowing the 1.0 s TTFT target, through the
        # REAL record path (bumps the incremental violation counter)
        rep.loop.telemetry.record_finish(types.SimpleNamespace(
            state=RequestState.DONE, ttft=2.0, tpot=None,
            e2e_latency=None, generated=[]))

    # (violating TTFT samples appended BEFORE the tick, expected
    # scale_ups AFTER it); ticks 3 serve-clock seconds apart
    table = [
        (1, 0),   # t=0  violation -> pressure, patience 1/2
        (1, 1),   # t=3  violation -> patience 2/2 -> UP (1 -> 2 live)
        (1, 1),   # t=6  violation inside cooldown: consumed, no event
        (0, 1),   # t=9  quiet tick: patience counters reset
        (1, 1),   # t=12 violation -> patience 1/2 (was reset)
        (1, 2),   # t=15 patience 2/2, cooldown over -> UP (3 live)
        (0, 2),   # t=18 quiet
        (0, 2),   # t=21 quiet: nothing oscillates back down (mid-band)
    ]
    fleet, clock, scaler = build(True)
    rep = fleet.replicas[0]
    for i, (nviol, ups) in enumerate(table):
        for _ in range(nviol):
            violate(rep)
        scaler.tick()
        assert (scaler.scale_ups, scaler.scale_downs) == (ups, 0), \
            f"tick {i} (t={clock()})"
        clock.advance(3.0)
    # a replica retiring with consumed violations must not mask NEW
    # ones: rep0 leaves carrying its 6 consumed violations while a
    # survivor lands 1 fresh one — a pool-level total would read
    # 1 - 6 < 0 and register nothing; per-replica deltas keep it
    survivor = fleet.replicas[-1]
    fleet.replicas.remove(fleet.replicas[0])
    violate(survivor)
    scaler.tick()
    assert scaler._sla_last_delta["fleet"] == 1

    # flag OFF (default): same violation stream, zero scale events
    fleet, clock, scaler = build(False)
    rep = fleet.replicas[0]
    for _ in range(6):
        violate(rep)
        scaler.tick()
        clock.advance(3.0)
    assert (scaler.scale_ups, scaler.scale_downs) == (0, 0)
    # ...and with the flag ON but no SLA target configured, the signal
    # is inert (no targets -> no counters): occupancy-only again
    fleet, clock, scaler = build(True)
    fleet.telemetry.sla_ttft_target_s = None
    fleet.telemetry.sla_tpot_target_s = None
    rep = fleet.replicas[0]
    for _ in range(6):
        rep.loop.telemetry.ttft.append(2.0)
        scaler.tick()
        clock.advance(3.0)
    assert (scaler.scale_ups, scaler.scale_downs) == (0, 0)


def test_autoscaler_scale_up_spawns_routable_replica():
    fleet, clock = _fleet(n=1, max_seqs=1, fleet_cfg=FleetConfig(
        replicas=1, snapshot_interval_steps=1, supervisor=_sup(),
        autoscale=AutoscaleConfig(max_replicas=2, high_watermark=0.5,
                                  low_watermark=0.1, patience_ticks=2,
                                  cooldown_s=5.0)))
    assert len(fleet.replicas) == 1
    # pile queued work on the single replica: measured load > watermark
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(6)]
    _tick(fleet, clock, n=3)
    assert len(fleet.replicas) == 2
    assert fleet.autoscaler.scale_ups == 1
    assert fleet.telemetry.health_events["scale_ups"] == 1
    # the fresh replica takes new routes (least-loaded wins)
    extra = fleet.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)
    assert _replica_of(fleet, extra) == 1
    _tick(fleet, clock, n=200)
    assert all(r.state is RequestState.DONE for r in reqs + [extra])
    fleet.audit()


def test_autoscaler_scale_down_drains_zero_loss_and_retires():
    fleet, clock = _fleet(max_seqs=1, fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1, supervisor=_sup(),
        autoscale=AutoscaleConfig(min_replicas=1, max_replicas=2,
                                  high_watermark=5.0, low_watermark=0.4,
                                  patience_ticks=2, cooldown_s=1.0)))
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(4)]
    # serve until load drops below the (generous) low watermark, then
    # the scaler drains the least-loaded replica; its queued work moves,
    # in-flight finishes, and the replica is removed once idle
    _tick(fleet, clock, n=300)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert fleet.autoscaler.scale_downs == 1
    assert fleet.telemetry.health_events["scale_downs"] == 1
    assert len(fleet.replicas) == 1              # retired and removed
    for rep in fleet.replicas:
        rep.loop.engine.audit_blocks()
    # the survivor still serves
    extra = fleet.submit(_prompt(50), max_new_tokens=2)
    _tick(fleet, clock, n=60)
    assert extra.state is RequestState.DONE


def test_autoscaler_restores_fleet_below_min_replicas():
    """Supervisor failovers must not leave the fleet under its floor:
    the autoscaler spawns a replacement immediately, bypassing the
    watermark patience and the cooldown (both set prohibitively high
    here so only the floor-restore path can act)."""
    fleet, clock = _fleet(n=2, max_seqs=1, fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1, supervisor=_sup(),
        autoscale=AutoscaleConfig(min_replicas=2, max_replicas=4,
                                  high_watermark=5.0, low_watermark=0.0,
                                  patience_ticks=10 ** 6,
                                  cooldown_s=10 ** 6)))
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(2)]
    _tick(fleet, clock, n=2)
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    _tick(fleet, clock, n=12)                 # burst -> failover
    assert fleet.supervisor.failovers == 1
    assert fleet.autoscaler.scale_ups == 1
    assert len(fleet.autoscaler.live_replicas()) == 2
    _tick(fleet, clock, n=200)
    assert all(r.state is RequestState.DONE for r in reqs)
    # the dead replica was reaped once idle — not just scale-down
    # victims: repeated failures must not accumulate retired arenas
    assert len(fleet.replicas) == 2
    assert all(r.health is ReplicaHealth.HEALTHY for r in fleet.replicas)
    fleet.audit()


def test_autoscaler_recovers_from_total_fleet_death():
    """Every replica dead used to be terminal (`if not live: return`):
    the floor-restore path must spawn from zero so the fleet can serve
    again.  And the request caught in the total death must NOT be
    cancelled: the supervisor spawns the floor-restore replacement
    BEFORE the failover re-route (the min_replicas floor would produce
    it one tick later anyway), so the dying replica's work is adopted
    onto it — total fleet death is an ordinary zero-loss handoff."""
    fleet, clock = _fleet(n=1, max_seqs=1, fleet_cfg=FleetConfig(
        replicas=1, snapshot_interval_steps=1, supervisor=_sup(),
        autoscale=AutoscaleConfig(min_replicas=1, max_replicas=2,
                                  high_watermark=5.0, low_watermark=0.0,
                                  patience_ticks=10 ** 6,
                                  cooldown_s=10 ** 6)))
    doomed = fleet.submit(_prompt(0), max_new_tokens=2)
    _tick(fleet, clock)
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    seen = []
    for _ in range(60):
        seen.extend(fleet.step())
        clock.advance(1.0)
    assert fleet.supervisor.failovers == 1
    assert fleet.autoscaler.scale_ups == 1    # respawned from zero
    assert fleet.telemetry.failover_cancelled == 0
    assert fleet.telemetry.failover_requeued == 1
    assert doomed.state is RequestState.DONE  # adopted, not cancelled
    assert any(r is doomed for r in seen)     # surfaced by step() too
    live = fleet.autoscaler.live_replicas()
    assert len(live) == 1
    assert len(fleet.replicas) == 1           # dead replica reaped
    extra = fleet.submit(_prompt(5), max_new_tokens=2)
    assert _replica_of(fleet, extra) == live[0].id
    _tick(fleet, clock, n=60)
    assert extra.state is RequestState.DONE
    fleet.audit()


def test_total_death_without_autoscaler_cancels_once_not_twice():
    """Supervisor-only fleet, last replica dies holding work: with no
    loop_factory there is nothing to adopt onto, so the retryable is
    finalized CANCELLED loudly — and counted ONCE.  failover_requeued
    counts successful adoptions, not re-queue attempts: a stranded
    retryable must not read as requeued AND cancelled, or
    requeued+failed+cancelled over-counts the evicted in-flight set."""
    fleet, clock = _fleet(n=1, max_seqs=1, fleet_cfg=FleetConfig(
        replicas=1, snapshot_interval_steps=1, supervisor=_sup()))
    doomed = fleet.submit(_prompt(0), max_new_tokens=2)
    _tick(fleet, clock)
    FaultInjector(fleet.replicas[0].loop,
                  FaultPlan([Fault("error", 0, steps=FOREVER)]))
    _tick(fleet, clock, n=12)                 # burst -> failover
    assert fleet.supervisor.failovers == 1
    assert doomed.finished                    # waiter released, loudly
    assert doomed.state is RequestState.CANCELLED
    assert fleet.telemetry.failover_cancelled == 1
    assert fleet.telemetry.failover_requeued == 0
    assert fleet.telemetry.failover_failed == 0


def test_supervised_fleet_refuses_mismatched_clocks():
    """Heartbeat deadlines and scale cooldowns ride ONE serve clock; a
    replica stepping on a private clock would be demoted (or never
    failed over) by deadlines it cannot see — refused at construction
    and at add_replica, like the block-size comparability check."""
    cfg = ServingConfig(
        prefix_cache_blocks=16,
        fleet=FleetConfig(replicas=2, supervisor=_sup()))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=FakeClock())
             for _ in range(2)]
    with pytest.raises(ValueError, match="shared serve clock"):
        FleetRouter(loops, cfg)
    fleet, clock = _fleet()
    with pytest.raises(ValueError, match="fleet clock"):
        fleet.add_replica(ServeLoop(PrefixFakeEngine(),
                                    ServingConfig(prefix_cache_blocks=16),
                                    clock=FakeClock()))


def test_add_remove_replica_guards():
    fleet, clock = _fleet()
    with pytest.raises(ValueError, match="block size"):
        fleet.add_replica(ServeLoop(PrefixFakeEngine(block_size=8),
                                    ServingConfig(prefix_cache_blocks=16),
                                    clock=clock))
    with pytest.raises(ValueError, match="drained"):
        fleet.remove_replica(0)                  # healthy: refuse
    rep = fleet.add_replica(ServeLoop(PrefixFakeEngine(),
                                      ServingConfig(
                                          prefix_cache_blocks=16),
                                      clock=clock))
    assert rep.id == 2
    fleet.drain(rep.id)
    fleet.remove_replica(rep.id)
    assert [r.id for r in fleet.replicas] == [0, 1]
    # ids are never reused: the next add gets a fresh id
    rep2 = fleet.add_replica(ServeLoop(PrefixFakeEngine(),
                                       ServingConfig(
                                           prefix_cache_blocks=16),
                                       clock=clock))
    assert rep2.id == 3


# -- config ----------------------------------------------------------------
def test_supervisor_autoscale_config_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"prefix_cache_blocks": 32,
                     "fleet": {"replicas": 3,
                               "migration_backoff_steps": 64,
                               "supervisor": {"heartbeat_timeout_s": 2.5,
                                              "error_burst": 4,
                                              "failover_after_s": 9.0,
                                              "recovery_ticks": 5,
                                              "max_request_retries": 2},
                               "autoscale": {"min_replicas": 2,
                                             "max_replicas": 6,
                                             "high_watermark": 0.7,
                                             "low_watermark": 0.1,
                                             "patience_ticks": 3,
                                             "cooldown_s": 20.0}}}})
    f = cfg.serving.fleet
    assert f.migration_backoff_steps == 64
    assert (f.supervisor.heartbeat_timeout_s,
            f.supervisor.error_burst) == (2.5, 4)
    assert f.supervisor.max_request_retries == 2
    assert (f.autoscale.min_replicas, f.autoscale.max_replicas) == (2, 6)
    # defaults: both OFF — bit-for-bit the PR-5 fleet
    base = DeepSpeedTPUConfig.from_json(
        {"serving": {"fleet": {"replicas": 2}}})
    assert base.serving.fleet.supervisor is None
    assert base.serving.fleet.autoscale is None
    with pytest.raises(ConfigError, match="heartbeat_timeout_s"):
        SupervisorConfig(heartbeat_timeout_s=0).validate()
    with pytest.raises(ConfigError, match="error_burst"):
        SupervisorConfig(error_burst=0).validate()
    with pytest.raises(ConfigError, match="recovery_ticks"):
        SupervisorConfig(recovery_ticks=0).validate()
    with pytest.raises(ConfigError, match="watermarks"):
        AutoscaleConfig(low_watermark=0.8, high_watermark=0.3).validate()
    with pytest.raises(ConfigError, match="max_replicas"):
        AutoscaleConfig(min_replicas=4, max_replicas=2).validate()
    # an elastic fleet without failure detection is refused
    with pytest.raises(ConfigError, match="supervisor"):
        FleetConfig(replicas=2, autoscale=AutoscaleConfig()).validate()
    with pytest.raises(ConfigError, match="min_replicas"):
        FleetConfig(replicas=1, supervisor=SupervisorConfig(),
                    autoscale=AutoscaleConfig(min_replicas=2)).validate()
    # starting above the autoscaler's ceiling would make max_replicas a
    # bound that silently never holds (scale-down only fires on low
    # occupancy) — refused symmetrically with the min_replicas check
    with pytest.raises(ConfigError, match="max_replicas"):
        FleetConfig(replicas=8, supervisor=SupervisorConfig(),
                    autoscale=AutoscaleConfig(max_replicas=4)).validate()
    with pytest.raises(ConfigError, match="migration_backoff_steps"):
        FleetConfig(migration_backoff_steps=-1).validate()


def test_chaos_bench_row_driver_on_tiny_engine():
    """One replica of three real tiny engines dies mid-stream, holding
    work, under a closed loop of 3 clients x 2 requests (a shared-
    system-prompt request, then a stranger), once per routing policy.
    Nobody calls drain: the supervisor fails the dead replica over
    exactly once, all 6 requests finish DONE with every waiter
    released, the survivors leak no block, death and retries are
    invisible in the tokens, and cache-aware routing keeps its hit rate
    above round robin's through the death."""
    from test_fleet import _tiny_engine
    from test_serving import _closed_loop

    shared_len, unique_len, new_tokens = 64, 16, 6
    rng = np.random.RandomState(17)
    shared = rng.randint(0, 128, shared_len).astype(np.int32)

    def prompt(stranger=False):
        head = (rng.randint(0, 128, shared_len).astype(np.int32)
                if stranger else shared)
        return np.concatenate(
            [head, rng.randint(0, 128, unique_len).astype(np.int32)])

    primer_prompt = prompt()
    prompts = {(client, k): prompt(stranger=bool(k % 2))
               for client in range(3) for k in range(2)}
    results = {}
    for routing in ("round_robin", "cache_aware"):
        clock = FakeClock()
        cfg = ServingConfig(
            max_queue_len=8, prefix_cache_blocks=8, decode_burst=2,
            audit_blocks=True,
            fleet=FleetConfig(replicas=3, snapshot_interval_steps=1,
                              routing=routing, prefix_weight=4.0,
                              load_weight=0.25,
                              supervisor=_sup(max_request_retries=2)))
        fleet = FleetRouter(
            [ServeLoop(_tiny_engine(num_blocks=64, block_size=16,
                                    max_seqs=1), cfg, clock=clock)
             for _ in range(3)], cfg)
        primer = fleet.submit(primer_prompt, max_new_tokens=new_tokens)
        while fleet.has_work:
            _tick(fleet, clock)
        assert primer.state is RequestState.DONE
        # the primer heated the prefix on replica 0 (ties go to the
        # lowest id), so replica 1 serves strangers under cache-aware
        # routing and a third of everything under round robin: it dies
        # holding work either way.  The death is armed by the first
        # victim step that RETURNS with admitted work still in flight,
        # so the next one raises over a request stranded mid-decode
        # however fast the model steps.
        victim = fleet.replicas[1]
        inner_step = victim.loop.step

        def step_then_arm():
            out = inner_step()
            if victim.loop.scheduler.active:
                victim.loop.step = inner_step
                FaultInjector(victim.loop, FaultPlan.replica_death(0))
            return out

        victim.loop.step = step_then_arm
        outputs, reqs = _closed_loop(fleet, prompts, new_tokens,
                                     after_step=lambda: clock.advance(1.0))
        s = fleet.summary()
        assert s["health"][victim.id] == "drained"
        assert s["health_events"]["failovers"] == 1
        assert s["failover_requeued"] >= 1 and s["failover_failed"] == 0
        assert all(r.finished for r in reqs)
        for rep in fleet.replicas:
            if rep.id != victim.id:
                rep.loop.engine.audit_blocks()
        results[routing] = (outputs, s["fleet_prefix_hit_rate"])

    outs_rr, hit_rr = results["round_robin"]
    outs_ca, hit_ca = results["cache_aware"]
    assert len(outs_ca) == 6 and outs_ca == outs_rr
    assert hit_ca > hit_rr
