"""Tests: cache-aware fleet router (deepspeed_tpu.serving.fleet) —
prefix-index snapshots, routing, the stale-view correction protocol,
drain/failover, and prefix KV-block migration.

Determinism discipline matches test_serving.py: replicas are plain
`ServeLoop`s over a DSStateManager-backed fake engine (real allocator
refcounts and a real radix prefix cache — only the model forward is
faked as next-token = (input + 1) % vocab), all sharing one manually
advanced fake clock, driven lock-step by `FleetRouter.step()` — no
sleeps, no sockets.  Two integration tests drive real tiny engines on
CPU to prove migrated KV blocks serve bit-for-bit outputs.
"""
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.config.config import (ConfigError, DeepSpeedTPUConfig,
                                         FleetConfig, ServingConfig)
from deepspeed_tpu.inference.v2 import DSStateManager
from deepspeed_tpu.serving import (AdmissionError, FleetRouter,
                                   GlobalPrefixIndex, ReplicaHealth,
                                   RequestState, ServeLoop, ThreadedServer)
from deepspeed_tpu.serving.fleet.migration import (NullBlockTransport,
                                                   _quant_roundtrip_int8)

from test_serving import _closed_loop

pytestmark = pytest.mark.serving

BS = 4          # KV block size of the fake replicas


# -- deterministic prefix-capable fake engine ------------------------------
class PrefixFakeEngine:
    """ServeLoop's engine contract over a REAL DSStateManager (real
    BlockedAllocator refcounts, real radix PrefixCache, real
    block-conservation audit) with a fake forward: next token is
    (input + 1) % vocab, so outputs are predictable and independent of
    where — or through which cached prefix — a request is served."""

    def __init__(self, max_seqs=2, budget=16, vocab=64, num_blocks=32,
                 block_size=BS, max_blocks_per_seq=16):
        self.config = SimpleNamespace(max_seqs=max_seqs,
                                      num_blocks=num_blocks,
                                      block_size=block_size)
        self.budget = budget
        self.vocab = vocab
        self.state = DSStateManager(num_blocks, block_size,
                                    max_blocks_per_seq, max_seqs)
        self.max_tokens_per_seq = max_blocks_per_seq * block_size
        self.prefix_cache = None
        self._prefix_leases = {}

    @property
    def free_blocks(self):
        return self.state.allocator.free_blocks

    @property
    def free_slots(self):
        return self.config.max_seqs - len(self.state.seqs)

    def enable_prefix_cache(self, n):
        from deepspeed_tpu.serving import PrefixCache
        self.prefix_cache = PrefixCache(self.state.allocator,
                                        self.config.block_size, n)
        return self.prefix_cache

    def audit_blocks(self):
        cache_blocks = (list(self.prefix_cache.block_ids())
                        if self.prefix_cache is not None else ())
        return self.state.audit(cache_blocks=cache_blocks)

    def _logits(self, tok):
        out = np.zeros(self.vocab, np.float32)
        out[(tok + 1) % self.vocab] = 1.0
        return out

    def put(self, uids, prompts, decode=True, prefixes=None):
        for uid, toks in zip(uids, prompts):
            toks = np.asarray(toks, np.int32)
            if prefixes is not None and uid in prefixes:
                lease = prefixes[uid]
            elif self.prefix_cache is not None:
                lease = self.prefix_cache.acquire(toks)
            else:
                lease = None
            if lease is None:
                self.state.create(uid, toks)
            else:
                self.state.create(uid, toks,
                                  prefix=(lease.blocks, lease.covered))
                self._prefix_leases[uid] = lease
        return self.step(decode=decode)

    def step(self, decode=True):
        out = {}
        budget = self.budget
        for d in self.state.seqs.values():          # FIFO prefill
            if d.in_prefill and budget > 0:
                adv = min(budget, len(d.prompt) - d.seen_tokens)
                self.state.ensure_capacity(d, d.seen_tokens + adv)
                d.seen_tokens += adv
                budget -= adv
                if not d.in_prefill:
                    out[d.uid] = self._logits(int(d.prompt[-1]))
        for d in self.state.seqs.values() if decode else ():
            if d.in_prefill:
                continue
            pending = d.seen_tokens - len(d.prompt)
            if pending < len(d.generated):
                tok = d.generated[pending]
                self.state.ensure_capacity(d, d.seen_tokens + 1)
                d.seen_tokens += 1
                out[d.uid] = self._logits(tok)
        return out

    def flush(self, uid):
        d = self.state.seqs.get(uid)
        if d is not None and self.prefix_cache is not None:
            # insert-on-completion BEFORE the flush decrefs (the
            # engine_v2 ownership handoff)
            self.prefix_cache.insert(
                d.prompt, d.blocks,
                upto_tokens=min(d.seen_tokens, len(d.prompt)))
        lease = self._prefix_leases.pop(uid, None)
        self.state.flush(uid)
        if lease is not None:
            self.prefix_cache.release(lease)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


SHARED = np.arange(10, 10 + 4 * BS, dtype=np.int32)   # 4 whole blocks


def _prompt(tail_seed, tail_len=3):
    rng = np.random.RandomState(tail_seed)
    return np.concatenate([
        SHARED, rng.randint(0, 64, tail_len).astype(np.int32)])


def _fleet(n=2, pcb=16, fleet_cfg=None, clock=None, **engine_kw):
    clock = clock or _FakeClock()
    cfg = ServingConfig(
        prefix_cache_blocks=pcb, audit_blocks=True,
        fleet=fleet_cfg or FleetConfig(replicas=n,
                                       snapshot_interval_steps=1))
    loops = [ServeLoop(PrefixFakeEngine(**engine_kw), cfg, clock=clock)
             for _ in range(n)]
    return FleetRouter(loops, cfg), clock


def _replica_of(fleet, req):
    """Which replica currently tracks `req` (queued or active)."""
    owners = [rep.id for rep in fleet.replicas
              if rep.loop.scheduler.find(req.uid) is req]
    assert len(owners) == 1
    return owners[0]


# -- routing ---------------------------------------------------------------
def test_routing_picks_longest_prefix_replica():
    fleet, _ = _fleet()
    # prime: empty index -> least-loaded, tie-breaks to replica 0
    primer = fleet.submit(_prompt(0), max_new_tokens=3)
    assert _replica_of(fleet, primer) == 0
    fleet.run_until_idle(max_steps=60)
    assert primer.state is RequestState.DONE
    # the flush inserted the prompt's whole blocks into replica 0's
    # cache and the step published a snapshot
    assert fleet.index.lookup(_prompt(1))[0] == 4 * BS
    req = fleet.submit(_prompt(1), max_new_tokens=3)
    assert _replica_of(fleet, req) == 0
    assert fleet.telemetry.routed["prefix"] == 1
    fleet.run_until_idle(max_steps=60)
    assert req.state is RequestState.DONE
    # the routed request actually HIT replica 0's cache
    assert fleet.replicas[0].loop.telemetry.counters["prefix_hits"] == 1
    s = fleet.summary()
    assert s["fleet_prefix_hit_rate"] == 0.5      # 1 primer miss, 1 hit
    assert s["stale_view_corrections"] == 0
    fleet.audit()


def test_routing_falls_back_to_least_loaded_without_a_match():
    fleet, _ = _fleet()
    # load replica 0 with queued work (max_seqs=2 -> third request queues)
    for i in range(3):
        fleet.replicas[0].loop.submit(_prompt(100 + i), max_new_tokens=3)
    rng = np.random.RandomState(5)
    stranger = rng.randint(0, 64, 9).astype(np.int32)
    req = fleet.submit(stranger, max_new_tokens=3)
    assert _replica_of(fleet, req) == 1
    assert fleet.telemetry.routed["least_loaded"] == 1
    fleet.run_until_idle(max_steps=120)
    assert req.state is RequestState.DONE
    fleet.audit()


def test_round_robin_policy_ignores_the_index():
    fleet, _ = _fleet(fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1, routing="round_robin"))
    reqs = [fleet.submit(_prompt(i), max_new_tokens=2) for i in range(4)]
    assert [_replica_of(fleet, r) for r in reqs] == [0, 1, 0, 1]
    assert fleet.telemetry.routed["round_robin"] == 4
    fleet.run_until_idle(max_steps=120)
    assert all(r.state is RequestState.DONE for r in reqs)


def test_snapshot_publication_is_digest_gated():
    fleet, _ = _fleet()
    primer = fleet.submit(_prompt(0), max_new_tokens=2)
    fleet.run_until_idle(max_steps=60)
    assert primer.state is RequestState.DONE
    before = fleet.telemetry.snapshots_published
    # nothing changed since the last publication: a manual sweep is free
    assert fleet.publish_snapshots() == 0
    assert fleet.telemetry.snapshots_published == before


# -- staleness protocol ----------------------------------------------------
def test_stale_view_miss_falls_back_and_corrects_the_index():
    fleet, _ = _fleet()
    primer = fleet.submit(_prompt(0), max_new_tokens=3)
    fleet.run_until_idle(max_steps=60)
    assert primer.state is RequestState.DONE
    assert fleet.index.lookup(_prompt(2))[0] == 4 * BS
    # evict replica 0's cache BEHIND the router's back (pressure would
    # do the same): the published snapshot is now a stale over-promise
    fleet.replicas[0].loop._cache.invalidate()
    req = fleet.submit(_prompt(2), max_new_tokens=3)
    assert _replica_of(fleet, req) == 0           # routed on stale view
    assert fleet.telemetry.routed["prefix"] == 1
    fleet.run_until_idle(max_steps=60)
    # the miss fell back to normal admission — the request completed —
    # and the correction demoted the stale entries
    assert req.state is RequestState.DONE
    assert fleet.telemetry.stale_view_corrections == 1
    assert fleet.index.stats()["stale_demotions"] >= 4
    fleet.audit()


def test_eviction_under_pressure_does_not_wedge_the_router():
    """One replica's cache churns out under arena pressure while the
    router keeps routing to it on (increasingly stale) views: every
    request still completes, corrections accrue instead of errors, and
    block conservation holds throughout."""
    # tight arena: 20 blocks, per-request need 5-6 blocks, cache cap 8
    fleet, _ = _fleet(pcb=8, num_blocks=20, max_seqs=1,
                      max_blocks_per_seq=20)
    primer = fleet.submit(_prompt(0), max_new_tokens=3)
    fleet.run_until_idle(max_steps=80)
    assert primer.state is RequestState.DONE
    rng = np.random.RandomState(11)
    reqs = []
    for i in range(6):
        if i % 2:
            # strangers need blocks the cache holds -> reclaim pressure
            reqs.append(fleet.submit(
                rng.randint(0, 64, 60).astype(np.int32),
                max_new_tokens=3))
        else:
            reqs.append(fleet.submit(_prompt(20 + i), max_new_tokens=3))
        fleet.step()
    fleet.run_until_idle(max_steps=400)
    assert all(r.state is RequestState.DONE for r in reqs)
    fleet.audit()


# -- drain + failover ------------------------------------------------------
def test_serve_loop_drain_mid_decode_loses_zero_accepted_requests():
    """The satellite regression: drain() while a request is mid-decode
    hands back every queued request unserved and the in-flight one
    finishes — 4 accepted, 1 DONE + 3 handed back, nothing lost."""
    clock = _FakeClock()
    loop = ServeLoop(PrefixFakeEngine(max_seqs=1),
                     ServingConfig(audit_blocks=True), clock=clock)
    reqs = [loop.submit(_prompt(i), max_new_tokens=4) for i in range(4)]
    loop.step()          # admit + prefill req 0
    loop.step()          # first decode step: req 0 is mid-decode
    assert reqs[0].state is RequestState.DECODE
    handed_back = loop.drain()
    assert handed_back == reqs[1:]
    assert all(r.state is RequestState.QUEUED for r in handed_back)
    assert loop.telemetry.counters["drained_unserved"] == 3
    with pytest.raises(AdmissionError, match="draining"):
        loop.submit(_prompt(9), max_new_tokens=2)
    while loop.has_work:
        loop.step()
    assert reqs[0].state is RequestState.DONE
    assert list(reqs[0].output_tokens) == [
        (int(_prompt(0)[-1]) + 1 + k) % 64 for k in range(4)]
    loop.engine.audit_blocks()


def test_threaded_server_drain_clean_handoff():
    server = ThreadedServer(PrefixFakeEngine(max_seqs=1, budget=4),
                            ServingConfig())
    reqs = [server.submit(_prompt(i), max_new_tokens=3) for i in range(5)]
    queued = server.drain(timeout=30.0)
    # zero loss: every accepted request either finished or was handed
    # back unserved (still QUEUED, ready for adoption elsewhere)
    assert all(r.state is RequestState.DONE or r in queued for r in reqs)
    assert all(r.state is RequestState.QUEUED for r in queued)
    with pytest.raises(AdmissionError, match="draining"):
        server.submit(_prompt(9))
    server.shutdown(drain=False)


def test_drained_replica_failover_reroutes_queued_work():
    fleet, _ = _fleet(max_seqs=1)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=3) for i in range(6)]
    fleet.step()                    # one admission on each replica
    on_r0 = [r for r in reqs if _replica_of(fleet, r) == 0]
    queued_r0 = [r for r in on_r0 if r.state is RequestState.QUEUED]
    assert queued_r0                # something to fail over
    rerouted = fleet.drain(0)
    assert rerouted == queued_r0
    assert all(_replica_of(fleet, r) == 1 for r in rerouted)
    assert fleet.telemetry.routed["failover"] == len(rerouted)
    assert fleet.replicas[0].health is ReplicaHealth.DRAINED
    # new work only routes to the survivor
    extra = fleet.submit(_prompt(50), max_new_tokens=2)
    assert _replica_of(fleet, extra) == 1
    # the drained replica finishes its in-flight request as the fleet
    # keeps stepping; nothing is lost anywhere
    fleet.run_until_idle(max_steps=400)
    assert all(r.state is RequestState.DONE for r in reqs + [extra])
    assert not fleet.replicas[0].loop.has_work
    fleet.audit()
    # drained replicas do not rejoin
    with pytest.raises(ValueError, match="drained"):
        fleet.mark_healthy(0)
    fleet.drain(1)
    with pytest.raises(AdmissionError, match="no live replicas"):
        fleet.submit(_prompt(60))


def test_drain_failover_overflow_cancels_loudly_never_strands():
    """When the survivors cannot hold the drained replica's queue, the
    overflow requests are finalized CANCELLED (waiters unblock) and the
    drain raises naming them — never a silently stranded QUEUED request
    that no scheduler owns."""
    clock = _FakeClock()
    cfg = ServingConfig(max_queue_len=3, prefix_cache_blocks=16,
                        audit_blocks=True,
                        fleet=FleetConfig(replicas=2,
                                          snapshot_interval_steps=1))
    loops = [ServeLoop(PrefixFakeEngine(max_seqs=1), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg)
    # 6 requests spread 3/3; after one step each replica runs 1 with 2
    # queued (queue cap 3)
    reqs = [fleet.submit(_prompt(i), max_new_tokens=2) for i in range(6)]
    fleet.step()
    # draining r0 hands 2 queued to r1, whose queue (2 deep, cap 3)
    # holds only one more: the second adopt overflows
    with pytest.raises(RuntimeError, match="CANCELLED"):
        fleet.drain(0)
    fleet.run_until_idle(max_steps=200)
    # every accepted request is accounted for: DONE or loudly CANCELLED
    states = {r.state for r in reqs}
    assert states <= {RequestState.DONE, RequestState.CANCELLED}
    assert sum(r.state is RequestState.CANCELLED for r in reqs) == 1
    assert all(r.finished for r in reqs)     # no waiter ever hangs
    fleet.audit()


def test_suspect_replica_deprioritized_until_recovered():
    fleet, _ = _fleet()
    fleet.mark_suspect(0)
    req = fleet.submit(_prompt(0), max_new_tokens=2)
    assert _replica_of(fleet, req) == 1      # healthy beats suspect
    fleet.mark_suspect(1)                    # no healthy left: suspects
    req2 = fleet.submit(_prompt(1), max_new_tokens=2)
    assert _replica_of(fleet, req2) in (0, 1)
    fleet.mark_healthy(0)
    req3 = fleet.submit(_prompt(2), max_new_tokens=2)
    assert _replica_of(fleet, req3) == 0
    fleet.run_until_idle(max_steps=200)
    assert all(r.state is RequestState.DONE for r in (req, req2, req3))


# -- migration -------------------------------------------------------------
def test_migration_hands_blocks_over_with_refcounts_conserved():
    fleet, _ = _fleet(fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1, migration=True))
    assert isinstance(fleet.transport, NullBlockTransport)  # fakes
    primer = fleet.submit(_prompt(0), max_new_tokens=3)
    assert _replica_of(fleet, primer) == 0
    fleet.run_until_idle(max_steps=60)
    # overload replica 0 so the scorer sends the next shared-prefix
    # request to replica 1 — which holds none of the prefix locally
    fillers = [fleet.replicas[0].loop.submit(_prompt(100 + i),
                                             max_new_tokens=3)
               for i in range(5)]
    req = fleet.submit(_prompt(7), max_new_tokens=3)
    assert _replica_of(fleet, req) == 1
    # the hot prefix was streamed replica 0 -> replica 1 at routing time
    assert fleet.telemetry.migrations == 1
    assert fleet.telemetry.migrated_blocks == 4
    assert fleet.replicas[1].loop._cache.match(_prompt(8))[1] == 4 * BS
    # both trees hold the prefix now; refcounts stay conserved on both
    fleet.audit()
    fleet.run_until_idle(max_steps=400)
    assert req.state is RequestState.DONE
    assert all(f.state is RequestState.DONE for f in fillers)
    # the migrated prefix produced a real local hit on replica 1
    assert fleet.replicas[1].loop.telemetry.counters["prefix_hits"] == 1
    fleet.audit()


def test_migration_skips_when_target_covers_as_much():
    fleet, _ = _fleet(fleet_cfg=FleetConfig(
        replicas=2, snapshot_interval_steps=1, migration=True))
    a = fleet.submit(_prompt(0), max_new_tokens=2)
    fleet.run_until_idle(max_steps=60)
    b = fleet.submit(_prompt(1), max_new_tokens=2)   # hits replica 0
    fleet.run_until_idle(max_steps=60)
    assert all(r.state is RequestState.DONE for r in (a, b))
    assert fleet.telemetry.migrations == 0           # nothing to move


def test_int8_quant_roundtrip_bounds_error_and_halves_wire_bytes():
    rng = np.random.RandomState(3)
    page = rng.randn(2, BS, 6).astype(np.float32)    # [layers, bs, minor]
    out, wire = _quant_roundtrip_int8(page)
    assert out.shape == page.shape and out.dtype == page.dtype
    # symmetric int8: error bounded by half a quantization step per layer
    step = np.abs(page.reshape(2, -1)).max(axis=1) / 127.0
    assert np.all(np.abs(out - page) <= step[:, None, None] * 0.5 + 1e-7)
    # wire carries int8 codes + one fp32 scale per layer, not fp32 pages
    assert wire == page.size + 2 * 4
    assert wire < page.nbytes / 2


# -- parity ----------------------------------------------------------------
def test_single_replica_fleet_is_bit_for_bit_a_bare_serve_loop():
    prompts = [_prompt(i, tail_len=3 + i) for i in range(5)]

    def run_bare():
        loop = ServeLoop(PrefixFakeEngine(),
                         ServingConfig(prefix_cache_blocks=16,
                                       audit_blocks=True),
                         clock=_FakeClock())
        reqs = [loop.submit(p, max_new_tokens=4) for p in prompts]
        loop.run_until_idle(max_steps=200)
        return [list(r.output_tokens) for r in reqs], loop.telemetry

    def run_fleet():
        fleet, _ = _fleet(n=1)
        reqs = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        fleet.run_until_idle(max_steps=200)
        fleet.audit()
        return ([list(r.output_tokens) for r in reqs],
                fleet.replicas[0].loop.telemetry)

    outs_bare, t_bare = run_bare()
    outs_fleet, t_fleet = run_fleet()
    assert outs_fleet == outs_bare
    for key in ("completed", "admitted", "prefix_hits", "prefix_misses"):
        assert t_fleet.counters[key] == t_bare.counters[key]


# -- real engines: migrated KV serves bit-for-bit --------------------------
def _tiny_engine(num_blocks=48, block_size=8, max_seqs=2,
                 max_blocks_per_seq=16):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=256,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    if not hasattr(_tiny_engine, "_params"):
        _tiny_engine._params = model.init_params(jax.random.PRNGKey(0))
    ecfg = RaggedInferenceEngineConfig(
        num_blocks=num_blocks, block_size=block_size,
        max_blocks_per_seq=max_blocks_per_seq, max_seqs=max_seqs,
        prefill_chunk_size=32, full_prompt_prefill=False)
    return InferenceEngineV2(model, params=_tiny_engine._params,
                             config=ecfg)


def _real_prompts():
    rng = np.random.RandomState(7)
    shared = rng.randint(0, 128, 32).astype(np.int32)   # 4 real blocks
    tails = [rng.randint(0, 128, 11).astype(np.int32) for _ in range(2)]
    return [np.concatenate([shared, t]) for t in tails]


def test_real_engine_migration_serves_bit_for_bit():
    """The whole point of migration: a replica that never prefilled the
    shared prefix serves a migrated copy of its KV and produces EXACTLY
    the tokens a from-scratch prefill would."""
    pa, pb = _real_prompts()
    # reference: cache-off, single engine
    ref_loop = ServeLoop(_tiny_engine(), ServingConfig(),
                         clock=_FakeClock())
    ref = [ref_loop.submit(p, max_new_tokens=5) for p in (pa, pb)]
    ref_loop.run_until_idle(max_steps=300)
    assert all(r.state is RequestState.DONE for r in ref)

    clock = _FakeClock()
    cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                        fleet=FleetConfig(replicas=2,
                                          snapshot_interval_steps=1,
                                          migration=True))
    loops = [ServeLoop(_tiny_engine(), cfg, clock=clock)
             for _ in range(2)]
    fleet = FleetRouter(loops, cfg)
    primer = fleet.submit(pa, max_new_tokens=5)
    assert _replica_of(fleet, primer) == 0
    fleet.run_until_idle(max_steps=300)
    # force the next shared-prefix request onto replica 1: the prefix
    # must arrive by MIGRATION, not recompute
    fleet.mark_suspect(0)
    req = fleet.submit(pb, max_new_tokens=5)
    assert _replica_of(fleet, req) == 1
    assert fleet.telemetry.migrations == 1
    assert fleet.telemetry.migrated_blocks == 4
    assert fleet.telemetry.migrated_bytes > 0     # real arena transport
    fleet.run_until_idle(max_steps=300)
    assert req.state is RequestState.DONE
    # replica 1 admitted it THROUGH the migrated prefix...
    assert loops[1].telemetry.counters["prefix_hits"] == 1
    assert loops[1].telemetry.prefill_tokens_saved == 32
    # ...and the output is bit-for-bit the from-scratch reference
    assert list(req.output_tokens) == list(ref[1].output_tokens)
    assert list(primer.output_tokens) == list(ref[0].output_tokens)
    fleet.audit()


def test_real_engine_migration_int8_quant_completes_and_accounts_bytes():
    """int8-on-the-wire migration: ~half the bytes of the raw transfer,
    outputs still produced through the quantized KV (bit-for-bit NOT
    guaranteed — documented), conservation clean."""
    pa, pb = _real_prompts()
    clock = _FakeClock()

    def build(quant):
        cfg = ServingConfig(prefix_cache_blocks=16, audit_blocks=True,
                            fleet=FleetConfig(replicas=2,
                                              snapshot_interval_steps=1,
                                              migration=True,
                                              migration_quant=quant))
        loops = [ServeLoop(_tiny_engine(), cfg, clock=clock)
                 for _ in range(2)]
        return FleetRouter(loops, cfg)

    raw_bytes = {}
    for quant in ("none", "int8"):
        fleet = build(quant)
        primer = fleet.submit(pa, max_new_tokens=3)
        fleet.run_until_idle(max_steps=300)
        assert primer.state is RequestState.DONE
        fleet.mark_suspect(0)
        req = fleet.submit(pb, max_new_tokens=3)
        fleet.run_until_idle(max_steps=300)
        assert req.state is RequestState.DONE
        assert fleet.telemetry.migrated_blocks == 4
        raw_bytes[quant] = fleet.telemetry.migrated_bytes
        fleet.audit()
    assert raw_bytes["int8"] < raw_bytes["none"] * 0.6


def test_bench_fleet_row_driver_on_tiny_engine():
    """Cache-aware against round-robin routing over one shared-system-
    prompt stream on two real tiny replicas (one sequence at a time
    each), the shared prefix heated by one primer request: round robin
    pays a cold shared-prefix prefill on every replica the stream
    touches, cache-aware routing steers the stream to the replica that
    holds the prefix.  Placement is invisible (the same tokens), every
    request finishes, the cache-aware fleet's hit rate is strictly
    higher and its prefill tokens strictly fewer, and no replica leaks
    a block."""
    shared_len, unique_len, new_tokens = 64, 16, 3
    rng = np.random.RandomState(13)
    shared = rng.randint(0, 128, shared_len).astype(np.int32)

    def prompt():
        return np.concatenate(
            [shared, rng.randint(0, 128, unique_len).astype(np.int32)])

    primer_prompt = prompt()
    prompts = {(client, 0): prompt() for client in range(3)}
    results = {}
    for routing in ("round_robin", "cache_aware"):
        cfg = ServingConfig(
            max_queue_len=8, prefix_cache_blocks=8, decode_burst=16,
            audit_blocks=True,
            fleet=FleetConfig(replicas=2, snapshot_interval_steps=1,
                              routing=routing, prefix_weight=4.0,
                              load_weight=0.25))
        clock = _FakeClock()
        fleet = FleetRouter(
            [ServeLoop(_tiny_engine(num_blocks=64, block_size=16,
                                    max_seqs=1), cfg, clock=clock)
             for _ in range(2)], cfg)
        primer = fleet.submit(primer_prompt, max_new_tokens=new_tokens)
        fleet.run_until_idle(max_steps=300)
        assert primer.state is RequestState.DONE
        outputs, _ = _closed_loop(fleet, prompts, new_tokens)
        fleet.audit()
        s = fleet.summary()
        # every prompt token was either prefilled or covered by shared KV
        prefill = ((len(prompts) + 1) * (shared_len + unique_len)
                   - s["fleet_prefill_tokens_saved"])
        results[routing] = (outputs, s["fleet_prefix_hit_rate"], prefill)

    outs_rr, hit_rr, prefill_rr = results["round_robin"]
    outs_ca, hit_ca, prefill_ca = results["cache_aware"]
    assert outs_ca == outs_rr
    assert hit_ca > hit_rr > 0
    assert prefill_ca < prefill_rr


# -- config ----------------------------------------------------------------
def test_fleet_config_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"prefix_cache_blocks": 32,
                     "fleet": {"replicas": 3, "snapshot_interval_steps": 8,
                               "prefix_weight": 2.0, "load_weight": 0.25,
                               "routing": "cache_aware",
                               "migration": True,
                               "migration_quant": "int8"}}})
    f = cfg.serving.fleet
    assert (f.replicas, f.snapshot_interval_steps) == (3, 8)
    assert (f.prefix_weight, f.load_weight) == (2.0, 0.25)
    assert f.migration is True and f.migration_quant == "int8"
    assert ServingConfig().fleet is None              # off by default
    with pytest.raises(ConfigError, match="replicas"):
        FleetConfig(replicas=0).validate()
    with pytest.raises(ConfigError, match="snapshot_interval_steps"):
        FleetConfig(snapshot_interval_steps=0).validate()
    with pytest.raises(ConfigError, match="weights"):
        FleetConfig(load_weight=-0.1).validate()
    with pytest.raises(ConfigError, match="routing"):
        FleetConfig(routing="random").validate()
    with pytest.raises(ConfigError, match="migration_quant"):
        FleetConfig(migration_quant="fp4").validate()
    # migration streams PREFIX blocks: it needs the per-replica cache
    with pytest.raises(ConfigError, match="prefix_cache_blocks"):
        ServingConfig(prefix_cache_blocks=0,
                      fleet=FleetConfig(migration=True)).validate()
    # ...and happens AT the routing decision: cache-blind round-robin
    # would silently never migrate, so the combination is refused
    with pytest.raises(ConfigError, match="cache_aware"):
        FleetConfig(migration=True, routing="round_robin").validate()


def test_global_index_rejects_mismatched_block_size():
    idx = GlobalPrefixIndex(8)
    with pytest.raises(ValueError, match="block_size"):
        idx.publish("r0", {"epoch": 1, "block_size": 4,
                           "cached_blocks": 0, "entries": {}})


def test_global_index_ignores_stale_republication():
    idx = GlobalPrefixIndex(BS)
    toks = np.arange(3 * BS + 1, dtype=np.int32)
    from deepspeed_tpu.serving import block_hashes
    entries = {h: (k + 1) * BS
               for k, h in enumerate(block_hashes(toks[:3 * BS], BS))}
    assert idx.publish("r0", {"epoch": 5, "block_size": BS,
                              "cached_blocks": 3, "entries": entries})
    # an older (reordered) snapshot must not roll the view back
    assert not idx.publish("r0", {"epoch": 4, "block_size": BS,
                                  "cached_blocks": 0, "entries": {}})
    assert idx.lookup(toks)["r0"] == 3 * BS
