"""Tests: disaggregated prefill/decode serving
(deepspeed_tpu.serving.fleet.disagg) — pool roles, the prefill-role
serve loop, the cross-pool KV handoff, batched multi-block migration,
pool-aware failover/floor restore, chaos mid-handoff, telemetry
splits, and config wiring.

Determinism discipline matches test_fleet.py: replicas are ServeLoops
over the DSStateManager-backed PrefixFakeEngine (real allocator
refcounts, real radix prefix cache, real block-conservation audit; the
forward is faked as next-token = (input + 1) % vocab so outputs are
independent of WHERE a request is served — exactly the property the
handoff must preserve), one shared fake clock, lock-step
`FleetRouter.step()`.  Real-engine tests prove the handoff serves
bit-for-bit through a real KV arena and that the batched transport
moves the same bytes in 2 device round trips instead of 2N.
"""
import numpy as np
import pytest

from test_fleet import (BS, PrefixFakeEngine, _FakeClock, _prompt,
                        _real_prompts, _replica_of, _tiny_engine)
from test_serving import _closed_loop

from deepspeed_tpu.config.config import (ConfigError, DeepSpeedTPUConfig,
                                         DisaggConfig, FleetConfig,
                                         ServingConfig, SupervisorConfig,
                                         AutoscaleConfig)
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.serving import (AdmissionError, FleetRouter, PoolRole,
                                   RequestCancelled, RequestState,
                                   ServeLoop)
from deepspeed_tpu.serving.fleet.faults import (FaultInjector, FaultPlan,
                                                FaultyTransport,
                                                TransportFault,
                                                kill_on_fault)
from deepspeed_tpu.serving.fleet.migration import (ArenaBlockTransport,
                                                   NullBlockTransport)

pytestmark = pytest.mark.serving


def _disagg_cfg(n_prefill=1, n_decode=2, extra=0, pcb=16,
                supervisor=None, autoscale=None, **disagg_kw):
    return ServingConfig(
        prefix_cache_blocks=pcb, audit_blocks=True,
        fleet=FleetConfig(
            replicas=n_prefill + n_decode + extra,
            snapshot_interval_steps=1,
            supervisor=supervisor, autoscale=autoscale,
            disagg=DisaggConfig(prefill_replicas=n_prefill,
                                decode_replicas=n_decode, **disagg_kw)))


def _disagg_fleet(n_prefill=1, n_decode=2, clock=None, cfg=None,
                  transport=None, loop_factory=None, **engine_kw):
    clock = clock or _FakeClock()
    cfg = cfg or _disagg_cfg(n_prefill, n_decode)
    loops = [ServeLoop(PrefixFakeEngine(**engine_kw), cfg, clock=clock)
             for _ in range(cfg.fleet.replicas)]
    return FleetRouter(loops, cfg, transport=transport,
                       loop_factory=loop_factory), clock


# -- roles -----------------------------------------------------------------
def test_roles_assigned_by_position():
    fleet, _ = _disagg_fleet(n_prefill=1, n_decode=2)
    s = fleet.summary()
    assert s["roles"] == {0: "prefill", 1: "decode", 2: "decode"}
    assert fleet.replicas[0].loop.role == "prefill"
    assert fleet.replicas[1].loop.role == "decode"
    # per-replica telemetry rows carry the role
    assert s["per_replica"]["0"]["role"] == "prefill"


def test_unassigned_remainder_stays_unified():
    cfg = _disagg_cfg(n_prefill=1, n_decode=1, extra=1)
    fleet, _ = _disagg_fleet(cfg=cfg)
    assert fleet.summary()["roles"] == {0: "prefill", 1: "decode",
                                        2: "unified"}


def test_prefill_role_requires_prefix_cache():
    loop = ServeLoop(PrefixFakeEngine(), ServingConfig())  # cache off
    with pytest.raises(ValueError, match="prefix cache"):
        loop.set_role("prefill")
    with pytest.raises(ValueError, match="role"):
        loop.set_role("oracle")


def test_prefill_role_refuses_a_loop_with_live_work():
    """Switching a live replica into the prefill role would wedge its
    DECODE-state requests forever (the role suppresses decode): the
    reassignment must be refused until the loop drains."""
    loop = ServeLoop(PrefixFakeEngine(),
                     ServingConfig(prefix_cache_blocks=16),
                     clock=_FakeClock())
    req = loop.submit(_prompt(0), max_new_tokens=8)
    loop.step()
    loop.step()
    assert req.state is RequestState.DECODE
    with pytest.raises(ValueError, match="drain"):
        loop.set_role("prefill")
    loop.run_until_idle(max_steps=100)
    assert req.state is RequestState.DONE
    loop.set_role("prefill")                 # idle loop: fine now
    assert loop.role == "prefill"


# -- the prefill-role serve loop -------------------------------------------
def test_prefill_role_parks_completions_without_first_token():
    clock = _FakeClock()
    loop = ServeLoop(PrefixFakeEngine(),
                     ServingConfig(prefix_cache_blocks=16,
                                   audit_blocks=True), clock=clock)
    loop.set_role("prefill")
    req = loop.submit(_prompt(0), max_new_tokens=4)
    while loop.has_work:
        loop.step()
    # the prompt finished prefilling but NO token was sampled: the
    # request parked for handoff, still PREFILL, out of the scheduler
    assert req.state is RequestState.PREFILL
    assert req.generated == [] and req.first_token_time is None
    assert not loop.scheduler.has_work
    assert loop.telemetry.counters["handoff_parked"] == 1
    parked = loop.take_handoff_ready()
    assert parked == [req]
    assert loop.take_handoff_ready() == []          # drained exactly once
    # releasing the sequence caches the prompt KV (insert-on-completion)
    loop.finish_handoff(req.uid)
    assert loop._cache.match(_prompt(0))[1] == 4 * BS
    assert loop._reserved == {}
    loop.engine.audit_blocks()


def test_prefill_role_reserves_prompt_only_blocks():
    """The 'large admission batches' lever: a prefill-role replica
    reserves only ceil(prompt/bs) blocks (decode runs on another
    arena), so two requests whose unified-lifetime need exceeds the
    arena still prefill CONCURRENTLY here."""
    def mk(role):
        loop = ServeLoop(PrefixFakeEngine(num_blocks=10, max_seqs=2,
                                          max_blocks_per_seq=10),
                         ServingConfig(prefix_cache_blocks=4,
                                       audit_blocks=True),
                         clock=_FakeClock())
        if role:
            loop.set_role(role)
        return loop

    prompts = [np.arange(100 + 16 * i, 116 + 16 * i, dtype=np.int32) % 64
               for i in range(2)]               # 16 tokens = 4 blocks each
    # unified: each request's lifetime needs 4 + ceil(17/4) = 9 of 10
    # blocks -> strictly one at a time
    uni = mk(None)
    for p in prompts:
        uni.submit(p, max_new_tokens=17)
    uni.step()
    assert len(uni.scheduler.active) == 1
    # prefill role: 4 blocks each -> both admit in ONE step
    pre = mk("prefill")
    for p in prompts:
        pre.submit(p, max_new_tokens=17)
    pre.step()
    assert (len(pre.scheduler.active)
            + pre.telemetry.counters["handoff_parked"]) == 2


# -- the handoff end-to-end ------------------------------------------------
def test_disagg_serves_bit_for_bit_with_migrated_kv_on_fakes():
    prompts = [_prompt(i) for i in range(4)]

    def run_bare():
        loop = ServeLoop(PrefixFakeEngine(),
                         ServingConfig(prefix_cache_blocks=16,
                                       audit_blocks=True),
                         clock=_FakeClock())
        reqs = [loop.submit(p, max_new_tokens=4) for p in prompts]
        loop.run_until_idle(max_steps=200)
        return [list(r.output_tokens) for r in reqs]

    fleet, _ = _disagg_fleet(n_prefill=1, n_decode=2)
    reqs = [fleet.submit(p, max_new_tokens=4) for p in prompts]
    # every long prompt routes to the prefill pool first
    assert all(_replica_of(fleet, r) == 0 for r in reqs)
    fleet.run_until_idle(max_steps=300)
    assert [r.state for r in reqs] == [RequestState.DONE] * 4
    # same Request objects finished on the DECODE pool: waiters survive
    assert [list(r.result(timeout=0)) for r in reqs] == run_bare()
    s = fleet.summary()
    assert s["handoffs"] == 4
    assert s["routed"]["handoff"] == 4
    assert s["handoff_cold_fallbacks"] == 0
    # the shared prefix migrated once per decode replica; later
    # handoffs found it already covered (the cache seam working)
    assert s["handoff_blocks"] == 8
    # prefill pool completed nothing (it never owns a token stream);
    # the decode pool completed everything THROUGH migrated-prefix hits
    assert s["pools"]["prefill"]["completed"] == 0
    assert s["pools"]["decode"]["completed"] == 4
    hits = sum(fleet.replicas[i].loop.telemetry.counters["prefix_hits"]
               for i in (1, 2))
    assert hits == 4
    fleet.audit()


def test_short_prompts_route_straight_to_decode_pool():
    fleet, _ = _disagg_fleet(n_prefill=1, n_decode=2)
    short = np.arange(3, dtype=np.int32)     # 0 whole usable blocks
    req = fleet.submit(short, max_new_tokens=3)
    assert _replica_of(fleet, req) in (1, 2)
    fleet.run_until_idle(max_steps=100)
    assert req.state is RequestState.DONE
    s = fleet.summary()
    assert s["handoffs"] == 0
    assert fleet.replicas[0].loop.telemetry.counters["submitted"] == 0


def test_handoff_adopts_in_fleet_arrival_order():
    """Cross-pool no-skip-ahead: two prefill replicas finish in the
    same fleet step but the collect sweep visits them in replica-id
    order — the coordinator must still adopt in fleet-ARRIVAL order, so
    the earlier submit queues first on the decode replica."""
    fleet, _ = _disagg_fleet(n_prefill=2, n_decode=1)
    # bypass routing: the EARLIER arrival lands on the LATER-collected
    # replica (id 1), the later arrival on replica 0
    req_a = fleet.replicas[1].loop.submit(_prompt(0), max_new_tokens=2)
    req_a._fleet_seq = 0
    req_b = fleet.replicas[0].loop.submit(_prompt(1), max_new_tokens=2)
    req_b._fleet_seq = 1
    # equal prompt lengths: both prefills complete in the same step and
    # the same router tick collects + adopts both
    fleet.step()   # admit + prefill (budget 16 < 19 tokens)
    fleet.step()   # prefill completes, park, collect, adopt
    dec = fleet.replicas[2].loop
    seqs = {r.uid: r._arrival_seq
            for r in ([e[2] for e in dec.scheduler._queue]
                      + list(dec.scheduler.active.values()))}
    assert len(seqs) == 2
    assert seqs[req_a.uid] < seqs[req_b.uid]
    fleet.run_until_idle(max_steps=200)
    assert req_a.state is RequestState.DONE
    assert req_b.state is RequestState.DONE
    fleet.audit()


def test_parked_cancel_and_deadline_finalize_via_coordinator():
    """No scheduler watches a parked request: the coordinator applies
    cancellation (and deadlines) at handoff time — waiters release,
    nothing leaks, the terminal state is reported through step()."""
    fleet, clock = _disagg_fleet(n_prefill=1, n_decode=1)
    req = fleet.submit(_prompt(0), max_new_tokens=4)
    pre = fleet.replicas[0].loop
    # drive the prefill replica DIRECTLY so the request parks without
    # the coordinator seeing it yet
    while not pre._handoff_ready:
        pre.step()
    req.cancel()
    finished = fleet.step()                  # collect -> finalize
    assert req in finished
    assert req.state is RequestState.CANCELLED
    with pytest.raises(RequestCancelled):
        req.result(timeout=0)
    assert fleet.summary()["handoff_expired"] == 1
    assert fleet.summary()["handoffs"] == 0
    fleet.audit()


def test_decode_pool_backpressure_retries_until_adopted():
    """A full decode queue is transient backpressure, not loss: the
    coordinator holds the handoff pending (fleet.has_work stays true)
    and adopts as the pool drains — every request completes."""
    clock = _FakeClock()
    cfg = ServingConfig(
        max_queue_len=1, prefix_cache_blocks=16, audit_blocks=True,
        fleet=FleetConfig(replicas=2, snapshot_interval_steps=1,
                          disagg=DisaggConfig(prefill_replicas=1,
                                              decode_replicas=1)))
    loops = [ServeLoop(PrefixFakeEngine(max_seqs=1), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg)
    reqs = []
    for i in range(3):
        # the 1-deep queues force the whole pipeline through
        # backpressure: submit one, let the prefill replica drain it
        reqs.append(fleet.submit(_prompt(i), max_new_tokens=6))
        fleet.step()
        fleet.step()
    fleet.run_until_idle(max_steps=400)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert fleet.summary()["handoffs"] == 3
    fleet.audit()


# -- faults: transport + mid-handoff death ---------------------------------
def test_handoff_transport_fault_cold_fallback_and_backoff():
    clock = _FakeClock()
    cfg = _disagg_cfg(1, 1)
    cfg.fleet.migration_backoff_steps = 3
    transport = FaultyTransport(NullBlockTransport(), fail_transfers=(0,))
    fleet, _ = _disagg_fleet(cfg=cfg, clock=clock, transport=transport)
    rng = np.random.RandomState(2)
    # strangers (no shared prefix), so every handoff must move its OWN
    # blocks — a shared prefix would already sit in the decode cache
    # after the first adoption's insert-on-completion
    stranger = lambda: rng.randint(0, 64, 19).astype(np.int32)
    req = fleet.submit(stranger(), max_new_tokens=3)
    fleet.run_until_idle(max_steps=200)
    # the faulted transfer fell back to COLD prefill on the decode pool
    assert req.state is RequestState.DONE
    s = fleet.summary()
    assert s["handoffs"] == 1
    assert s["handoff_failures"] == 1
    assert s["handoff_cold_fallbacks"] == 1
    assert fleet.replicas[1].loop.telemetry.counters["prefix_hits"] == 0
    assert transport.faults_injected == 1
    # the (source, target) pair latched a backoff deadline (it expired
    # during the drain above — 3 router steps); the next handoff
    # migrates cleanly again
    assert (0, 1) in fleet._migration_backoff
    req2 = fleet.submit(stranger(), max_new_tokens=3)
    fleet.run_until_idle(max_steps=200)
    assert req2.state is RequestState.DONE
    s = fleet.summary()
    assert s["handoffs"] == 2
    assert s["handoff_blocks"] == 4          # req2's whole usable prefix
    assert s["handoff_cold_fallbacks"] == 1  # req2 was NOT cold
    assert fleet.replicas[1].loop.telemetry.counters["prefix_hits"] == 1
    fleet.audit()


def test_prefill_replica_death_mid_handoff_survives_cold():
    """The chaos satellite: the prefill replica dies in the post-read,
    pre-insert window of its handoff transfer.  The request must
    complete via cold prefill on the decode pool, with zero leaked
    blocks on BOTH arenas, and the supervisor must fail the dead
    replica over once it next shows work."""
    clock = _FakeClock()
    cfg = _disagg_cfg(1, 2, supervisor=SupervisorConfig(
        heartbeat_timeout_s=5.0, error_burst=2, error_window_s=100.0,
        failover_after_s=5.0, recovery_ticks=4, max_request_retries=2))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(3)]
    victim = loops[0]
    transport = FaultyTransport(NullBlockTransport(), fail_transfers=(0,),
                                on_fault=kill_on_fault(victim))
    fleet = FleetRouter(loops, cfg, transport=transport)
    req = fleet.submit(_prompt(0), max_new_tokens=4)
    assert _replica_of(fleet, req) == 0
    fleet.run_until_idle(max_steps=400)
    # the half-shipped request completed via cold prefill on the
    # decode pool — zero loss through the exact atomicity window
    assert req.state is RequestState.DONE
    assert transport.faults_injected == 1
    s = fleet.summary()
    assert s["handoffs"] == 1 and s["handoff_cold_fallbacks"] == 1
    # both arenas conserve every block (migrate_prefix rolled back)
    for lp in loops:
        lp.engine.audit_blocks()
    # the dead prefill replica errors on its NEXT work: the supervisor
    # demotes on the burst and fails it over; the stranded request
    # still completes (prefill pool empty -> decode pool serves it
    # end-to-end, the documented degradation)
    req2 = fleet.submit(_prompt(5), max_new_tokens=3)
    assert _replica_of(fleet, req2) == 0
    for _ in range(80):
        fleet.step()
        clock.t += 1.0
        if req2.state is RequestState.DONE:
            break
    assert req2.state is RequestState.DONE
    assert fleet.replicas[0].health.value == "drained"
    assert fleet.summary()["health_events"]["failovers"] == 1
    for lp in loops[1:]:
        lp.engine.audit_blocks()


def test_decode_replica_death_rehomes_inside_its_pool():
    clock = _FakeClock()
    cfg = _disagg_cfg(1, 2, supervisor=SupervisorConfig(
        heartbeat_timeout_s=5.0, error_burst=2, error_window_s=100.0,
        failover_after_s=5.0, recovery_ticks=4, max_request_retries=2))
    loops = [ServeLoop(PrefixFakeEngine(max_seqs=1), cfg, clock=clock)
             for _ in range(3)]
    fleet = FleetRouter(loops, cfg)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=8) for i in range(3)]
    # let handoffs land on the decode pool, then kill decode replica 1
    for _ in range(6):
        fleet.step()
    victims = [r for r in reqs
               if fleet.replicas[1].loop.scheduler.find(r.uid) is r]
    assert victims                         # someone is on the victim
    FaultInjector(fleet.replicas[1].loop, FaultPlan.replica_death(0))
    for _ in range(120):
        fleet.step()
        clock.t += 1.0
        if all(r.state is RequestState.DONE for r in reqs):
            break
    assert all(r.state is RequestState.DONE for r in reqs)
    assert fleet.replicas[1].health.value == "drained"
    # the victim's work re-homed INSIDE the decode pool: the prefill
    # replica never adopted a decode-phase request (its submit counter
    # only saw the original prefill-pool routes)
    assert (fleet.replicas[0].loop.telemetry.counters["submitted"]
            == len(reqs))
    for lp in (loops[0], loops[2]):
        lp.engine.audit_blocks()


# -- pool floors + autoscaler ----------------------------------------------
def test_pool_floor_restore_without_autoscaler():
    clock = _FakeClock()
    cfg = _disagg_cfg(1, 1, supervisor=SupervisorConfig(
        heartbeat_timeout_s=2.0, error_burst=2, error_window_s=100.0,
        failover_after_s=2.0, recovery_ticks=4, max_request_retries=2))

    def factory():
        return ServeLoop(PrefixFakeEngine(), cfg, clock=clock)

    loops = [factory() for _ in range(2)]
    fleet = FleetRouter(loops, cfg, loop_factory=factory)
    # kill the prefill replica while it holds work
    req = fleet.submit(_prompt(0), max_new_tokens=3)
    FaultInjector(fleet.replicas[0].loop, FaultPlan.replica_death(0))
    for _ in range(60):
        fleet.step()
        clock.t += 1.0
        if req.state is RequestState.DONE and any(
                r.role is PoolRole.PREFILL and r.health.value == "healthy"
                for r in fleet.replicas):
            break
    assert req.state is RequestState.DONE
    # the pool manager restored the prefill floor with a fresh replica
    roles = fleet.summary()["roles"]
    live_prefill = [rid for rid, role in roles.items()
                    if role == "prefill"
                    and fleet._replica(rid).health.value != "drained"]
    assert len(live_prefill) == 1 and live_prefill != [0]
    # and the restored pool serves the handoff path again
    req2 = fleet.submit(_prompt(9), max_new_tokens=3)
    assert _replica_of(fleet, req2) == live_prefill[0]
    fleet.run_until_idle(max_steps=300)
    assert req2.state is RequestState.DONE
    fleet.audit()


def test_autoscaler_scale_groups_and_pool_floor_restore():
    clock = _FakeClock()
    cfg = _disagg_cfg(
        1, 2,
        supervisor=SupervisorConfig(
            heartbeat_timeout_s=2.0, error_burst=2, error_window_s=100.0,
            failover_after_s=2.0, recovery_ticks=4,
            max_request_retries=2),
        autoscale=AutoscaleConfig(min_replicas=1, max_replicas=6,
                                  patience_ticks=2, cooldown_s=5.0))

    def factory():
        return ServeLoop(PrefixFakeEngine(max_seqs=1), cfg, clock=clock)

    loops = [factory() for _ in range(3)]
    fleet = FleetRouter(loops, cfg, loop_factory=factory)
    groups = fleet.scale_groups()
    assert [(g["label"], g["min"], len(g["members"])) for g in groups] \
        == [("prefill", 1, 1), ("decode", 2, 2)]
    # kill a DECODE replica: the autoscaler restores the decode floor
    # with a replica that joins the decode pool (not prefill)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=6) for i in range(3)]
    for _ in range(4):
        fleet.step()
    FaultInjector(fleet.replicas[1].loop, FaultPlan.replica_death(0))
    for _ in range(120):
        fleet.step()
        clock.t += 1.0
        decode_live = [r for r in fleet.replicas
                       if r.role is PoolRole.DECODE
                       and r.health.value != "drained"]
        if (all(r.state is RequestState.DONE for r in reqs)
                and len(decode_live) >= 2):
            break
    assert all(r.state is RequestState.DONE for r in reqs)
    decode_live = [r for r in fleet.replicas
                   if r.role is PoolRole.DECODE
                   and r.health.value != "drained"]
    assert len(decode_live) >= 2
    assert fleet.autoscaler.scale_ups >= 1
    prefill_live = [r for r in fleet.replicas
                    if r.role is PoolRole.PREFILL
                    and r.health.value != "drained"]
    assert len(prefill_live) == 1           # the other pool untouched


def test_autoscaler_max_replicas_is_a_fleet_wide_ceiling():
    """Two hot pools must not EACH grow to max_replicas: watermark
    scale-ups respect the fleet-wide total (floor restores still
    bypass it — redundancy beats the cap)."""
    clock = _FakeClock()
    cfg = _disagg_cfg(
        1, 1,
        supervisor=SupervisorConfig(heartbeat_timeout_s=100.0,
                                    error_burst=3, error_window_s=10.0,
                                    failover_after_s=100.0,
                                    recovery_ticks=2),
        autoscale=AutoscaleConfig(min_replicas=1, max_replicas=3,
                                  patience_ticks=1, cooldown_s=0.0))

    def factory():
        return ServeLoop(PrefixFakeEngine(), cfg, clock=clock)

    loops = [factory() for _ in range(2)]
    fleet = FleetRouter(loops, cfg, loop_factory=factory)
    # every pool reads as saturated: without the fleet-wide check each
    # pool would grow to 3 (6 total)
    fleet.autoscaler._occ = lambda g, live: 10.0
    for _ in range(10):
        fleet.autoscaler.tick()
        clock.t += 1.0
    live = [r for r in fleet.replicas if r.health.value != "drained"]
    assert len(live) == 3
    assert fleet.autoscaler.scale_ups == 1


# -- parity locks ----------------------------------------------------------
def test_disagg_unset_keeps_unified_fleet_inert():
    """The parity lock's counter half: a fleet without `disagg` takes
    ZERO new branches — no roles, no pool manager, no handoff state,
    no new summary keys beyond all-zero counters and the single
    'unified' pool row, and unchanged per-replica event tags."""
    sink = InMemoryMonitor()
    clock = _FakeClock()
    cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                        fleet=FleetConfig(replicas=2,
                                          snapshot_interval_steps=1))
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg, monitor=sink)
    assert fleet.disagg is None and fleet.pools is None \
        and fleet.handoff is None
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(3)]
    fleet.run_until_idle(max_steps=200)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(r._fleet_seq is None for r in reqs)
    assert all(rep.role is PoolRole.UNIFIED for rep in fleet.replicas)
    assert all(lp._handoff_ready == [] and lp.role == "unified"
               for lp in loops)
    s = fleet.summary()
    assert "roles" not in s
    assert s["handoffs"] == s["handoff_blocks"] == 0
    assert set(s["pools"]) == {"unified"}
    fleet.publish()
    tags = {t for t, _, _ in sink.events}
    assert "fleet/replica_0/queue_depth" in tags       # pre-disagg tag
    assert not any("pool_" in t for t in tags)


def test_disagg_with_only_short_prompts_matches_unified_decode_fleet():
    """The parity lock's behavioral half: a disagg fleet whose traffic
    never qualifies for handoff (every prompt below
    min_handoff_blocks) serves bit-for-bit like a unified fleet made of
    just its decode replicas."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, 4).astype(np.int32) for _ in range(6)]

    def run_unified():
        clock = _FakeClock()
        cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                            fleet=FleetConfig(replicas=2,
                                              snapshot_interval_steps=1))
        loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
                 for _ in range(2)]
        fleet = FleetRouter(loops, cfg)
        reqs = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        fleet.run_until_idle(max_steps=300)
        return [list(r.output_tokens) for r in reqs]

    fleet, _ = _disagg_fleet(n_prefill=1, n_decode=2,
                             cfg=_disagg_cfg(1, 2, min_handoff_blocks=8))
    reqs = [fleet.submit(p, max_new_tokens=4) for p in prompts]
    assert all(_replica_of(fleet, r) in (1, 2) for r in reqs)
    fleet.run_until_idle(max_steps=300)
    assert [list(r.output_tokens) for r in reqs] == run_unified()
    assert fleet.summary()["handoffs"] == 0
    fleet.audit()


# -- telemetry -------------------------------------------------------------
def test_pool_events_tagged_and_sla_attributed():
    sink = InMemoryMonitor()
    clock = _FakeClock()
    cfg = _disagg_cfg(1, 1, prefill_ttft_target_s=1e-9,
                      decode_tpot_target_s=100.0)
    loops = [ServeLoop(PrefixFakeEngine(), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg, monitor=sink)
    # freeze arrival at t=0, finish at t=1: TTFT == 1 s, violating the
    # absurd 1e-9 target exactly once; TPOT == 0 s under the 100 s one
    req = fleet.submit(_prompt(0), max_new_tokens=4)
    clock.t = 1.0
    fleet.run_until_idle(max_steps=200)
    assert req.state is RequestState.DONE
    s = fleet.summary()
    pools = s["pools"]
    assert set(pools) == {"prefill", "decode"}
    assert pools["decode"]["ttft_p95_s"] is not None
    # TTFT is measured end-to-end where the request finishes (decode
    # pool) against the PREFILL pool's responsibility target
    assert pools["decode"]["ttft_sla_target_s"] == 1e-9
    assert pools["decode"]["ttft_sla_violations"] == 1
    assert pools["decode"]["tpot_sla_violations"] == 0
    fleet.publish()
    tags = {t for t, _, _ in sink.events}
    assert "fleet/pool_decode/ttft_p95_s" in tags
    assert "fleet/pool_prefill/handoff_parked" in tags
    assert "fleet/handoffs" in tags
    # per-replica events are role-tagged under disagg
    assert "fleet/replica_0/prefill/queue_depth" in tags
    assert "fleet/replica_1/decode/queue_depth" in tags


# -- batched migration transport -------------------------------------------
def test_batched_transfer_matches_per_block_and_halves_round_trips():
    """Satellite: the batched multi-block path moves the SAME bytes
    (identical wire accounting, identical arrived pages — the int8
    scale grain stays per (layer, block)) in 2 device round trips
    instead of 2 per block."""
    eng_a = _tiny_engine()
    eng_b = _tiny_engine()
    eng_c = _tiny_engine()
    rng = np.random.RandomState(0)
    L = eng_a.arena["k"].shape[0]
    minor = tuple(eng_a.arena["k"].shape[2:])
    blocks = [2, 5, 7, 11]
    for b in blocks:
        eng_a.write_kv_block(b, rng.randn(*(L,) + minor).astype(np.float32),
                             rng.randn(*(L,) + minor).astype(np.float32))
    for quant in ("none", "int8"):
        batched = ArenaBlockTransport(quant)
        wire_b = batched.transfer(eng_a, eng_b, blocks, blocks)
        assert batched.round_trips == 2
        per_block = ArenaBlockTransport(quant)
        # force the per-block path by hiding the span contract
        class OneByOne:
            def __init__(self, eng):
                self.eng = eng

            def __getattr__(self, name):
                if name in ("read_kv_blocks", "write_kv_blocks"):
                    raise AttributeError(name)
                return getattr(self.eng, name)
        wire_p = per_block.transfer(OneByOne(eng_a), OneByOne(eng_c),
                                    blocks, blocks)
        assert per_block.round_trips == 2 * len(blocks)
        assert wire_b == wire_p
        for b in blocks:
            kb, vb = eng_b.read_kv_block(b)
            kc, vc = eng_c.read_kv_block(b)
            np.testing.assert_array_equal(kb, kc)
            np.testing.assert_array_equal(vb, vc)


def test_write_kv_blocks_rejects_bad_spans():
    eng = _tiny_engine()
    L = eng.arena["k"].shape[0]
    minor = tuple(eng.arena["k"].shape[2:])
    good = np.zeros((L, 2) + minor, np.float32)
    with pytest.raises(ValueError, match="duplicate"):
        eng.write_kv_blocks([3, 3], good, good)
    with pytest.raises(ValueError, match="shape"):
        eng.write_kv_blocks([3, 4], good[:, :1], good)
    with pytest.raises(ValueError, match="bad block"):
        eng.read_kv_blocks([10_000])


def test_real_engine_migrate_prefix_is_batched():
    """The handoff-path accounting: a multi-block prefix migration on
    real engines rides the span contract — 2 round trips total."""
    pa, pb = _real_prompts()
    clock = _FakeClock()
    cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                        fleet=FleetConfig(replicas=2,
                                          snapshot_interval_steps=1,
                                          migration=True))
    loops = [ServeLoop(_tiny_engine(), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg)
    assert isinstance(fleet.transport, ArenaBlockTransport)
    primer = fleet.submit(pa, max_new_tokens=3)
    fleet.run_until_idle(max_steps=300)
    assert primer.state is RequestState.DONE
    fleet.mark_suspect(0)
    req = fleet.submit(pb, max_new_tokens=3)
    fleet.run_until_idle(max_steps=300)
    assert req.state is RequestState.DONE
    assert fleet.telemetry.migrated_blocks == 4
    assert fleet.transport.round_trips == 2          # one span, not 8
    fleet.audit()


# -- real engines: the handoff serves bit-for-bit --------------------------
def test_real_engine_disagg_handoff_serves_bit_for_bit():
    """The whole point: a decode replica that never prefilled the
    prompt serves its migrated KV (plus a sub-block tail re-prefill)
    and produces EXACTLY the tokens an end-to-end replica would."""
    pa, pb = _real_prompts()
    ref_loop = ServeLoop(_tiny_engine(), ServingConfig(),
                         clock=_FakeClock())
    ref = [ref_loop.submit(p, max_new_tokens=5) for p in (pa, pb)]
    ref_loop.run_until_idle(max_steps=300)
    assert all(r.state is RequestState.DONE for r in ref)

    clock = _FakeClock()
    cfg = _disagg_cfg(1, 1)
    loops = [ServeLoop(_tiny_engine(), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg)
    reqs = [fleet.submit(p, max_new_tokens=5) for p in (pa, pb)]
    assert all(_replica_of(fleet, r) == 0 for r in reqs)
    fleet.run_until_idle(max_steps=400)
    assert all(r.state is RequestState.DONE for r in reqs)
    s = fleet.summary()
    assert s["handoffs"] == 2
    # pa's usable prefix is 5 whole blocks ((43-1)//8 — capped one
    # token short); pb's handoff finds its 4 shared blocks already
    # covered on the decode side and streams only its unique 5th
    assert s["handoff_blocks"] == 6
    assert s["handoff_bytes"] > 0           # real arena payload moved
    assert s["handoff_cold_fallbacks"] == 0
    # the decode replica admitted both THROUGH the migrated prefix
    assert loops[1].telemetry.counters["prefix_hits"] == 2
    for got, want in zip(reqs, ref):
        assert list(got.output_tokens) == list(want.output_tokens)
    fleet.audit()


# -- config ----------------------------------------------------------------
def test_disagg_config_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"prefix_cache_blocks": 32,
                     "fleet": {"replicas": 4,
                               "disagg": {"prefill_replicas": 1,
                                          "decode_replicas": 2,
                                          "handoff_quant": "int8",
                                          "min_handoff_blocks": 2,
                                          "prefill_ttft_target_s": 2.5,
                                          "decode_tpot_target_s": 0.1}}}})
    d = cfg.serving.fleet.disagg
    assert (d.prefill_replicas, d.decode_replicas) == (1, 2)
    assert d.handoff_quant == "int8" and d.min_handoff_blocks == 2
    assert (d.prefill_ttft_target_s, d.decode_tpot_target_s) == (2.5, 0.1)
    assert FleetConfig().disagg is None            # off by default
    with pytest.raises(ConfigError, match="prefill_replicas"):
        DisaggConfig(prefill_replicas=0).validate()
    with pytest.raises(ConfigError, match="handoff_quant"):
        DisaggConfig(handoff_quant="fp4").validate()
    with pytest.raises(ConfigError, match="min_handoff_blocks"):
        DisaggConfig(min_handoff_blocks=0).validate()
    with pytest.raises(ConfigError, match="decode_tpot_target_s"):
        DisaggConfig(decode_tpot_target_s=0.0).validate()
    # pools cannot exceed the fleet
    with pytest.raises(ConfigError, match="pooled"):
        FleetConfig(replicas=2,
                    disagg=DisaggConfig(prefill_replicas=2,
                                        decode_replicas=1)).validate()
    # the handoff rides each replica's prefix cache
    with pytest.raises(ConfigError, match="prefix_cache_blocks"):
        ServingConfig(prefix_cache_blocks=0,
                      fleet=FleetConfig(replicas=2,
                                        disagg=DisaggConfig())).validate()
    # migration and handoff share ONE transport: quant must agree
    cfg2 = ServingConfig(
        prefix_cache_blocks=8,
        fleet=FleetConfig(replicas=2, migration=True,
                          migration_quant="int8",
                          disagg=DisaggConfig(handoff_quant="none")))
    loops = [ServeLoop(PrefixFakeEngine(), cfg2, clock=_FakeClock())
             for _ in range(2)]
    with pytest.raises(ValueError, match="handoff_quant"):
        FleetRouter(loops, cfg2)


# -- unified against disaggregated, one stream --------------------------------
def test_bench_disagg_row_driver_on_tiny_engine():
    """One mixed stream (long and short prompts, each decoding 6 tokens,
    bursts of 16) on three real tiny replicas, unified and then as 1
    prefill + 2 decode replicas.  Prompt lengths are a multiple of the
    prefill chunk plus one, so the handoff boundary (the last whole KV
    block) is chunk-aligned and the decode side's tail re-prefill
    computes the same logits bit for bit: the handoff is invisible in
    the tokens.  Every request finishes, the disaggregated fleet hands
    requests off (the unified one never), and no replica of either
    fleet leaks a block.  (That decode TPOT falls is a claim about a
    chip, and is not made at this size.)"""
    rng = np.random.RandomState(29)
    prompts = {(client, 0): rng.randint(0, 128, 65 if client % 2 == 0
                                        else 33).astype(np.int32)
               for client in range(3)}
    results = {}
    for label, disagg in (("unified", None),
                          ("disagg", DisaggConfig(prefill_replicas=1,
                                                  decode_replicas=2))):
        cfg = ServingConfig(
            max_queue_len=8, prefix_cache_blocks=12, decode_burst=16,
            audit_blocks=True,
            fleet=FleetConfig(replicas=3, snapshot_interval_steps=1,
                              disagg=disagg))
        clock = _FakeClock()
        fleet = FleetRouter(
            [ServeLoop(_tiny_engine(num_blocks=96, block_size=16,
                                    max_seqs=2), cfg, clock=clock)
             for _ in range(3)], cfg)
        outputs, reqs = _closed_loop(fleet, prompts, new_tokens=6)
        assert not fleet.has_work and all(r.finished for r in reqs)
        fleet.audit()
        results[label] = (outputs, fleet.summary())

    outs_u, s_u = results["unified"]
    outs_d, s_d = results["disagg"]
    assert len(outs_d) == 3 and outs_d == outs_u
    assert all(len(toks) == 6 for toks in outs_d.values())
    assert s_u["handoffs"] == 0
    assert s_d["handoffs"] > 0 and s_d["handoff_blocks"] > 0
    assert s_d["handoff_failures"] == s_d["handoff_expired"] == 0
