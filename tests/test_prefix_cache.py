"""Tests: prefix KV-cache reuse — the refcounted block allocator, the
radix prefix tree (deepspeed_tpu.serving.prefix_cache), the state
manager's shared-prefix attach + block-conservation audit, and the serve
loop integration (ledger accounting, parity, telemetry).

Allocator and tree tests are pure host bookkeeping (no engine, no jax
compiles).  The integration tests drive the real tiny engine on CPU,
following test_serving.py's determinism discipline: greedy sampling,
fake clock, no sleeps.
"""
import numpy as np
import pytest

from deepspeed_tpu.config.config import (ConfigError, DeepSpeedTPUConfig,
                                         ServingConfig)
from deepspeed_tpu.inference.v2 import BlockedAllocator, DSStateManager
from deepspeed_tpu.serving import PrefixCache, RequestState, ServeLoop

pytestmark = pytest.mark.serving


# -- allocator: refcounts + conservation ----------------------------------
def test_allocator_refcount_property_random_interleavings():
    """Random allocate/incref/decref interleavings conserve blocks: at
    every point, free list + blocks with refcount > 0 == num_blocks, and
    once every owner releases, everything is free again."""
    rng = np.random.RandomState(3)
    alloc = BlockedAllocator(24)
    owners = []                      # one entry per outstanding reference
    for _ in range(600):
        op = rng.randint(3)
        if op == 0 and alloc.free_blocks:
            n = rng.randint(1, alloc.free_blocks + 1)
            owners.extend(alloc.allocate(n))
        elif op == 1 and owners:
            b = owners[rng.randint(len(owners))]
            alloc.incref(b)
            owners.append(b)
        elif op == 2 and owners:
            b = owners.pop(rng.randint(len(owners)))
            alloc.decref(b)
        refs = alloc.refcounts()
        held = sum(1 for r in refs if r > 0)
        assert alloc.free_blocks + held == alloc.num_blocks
        # the refcounts name exactly the outstanding references
        assert sum(refs) == len(owners)
        assert all(refs[b] == owners.count(b) for b in set(owners))
    for b in list(owners):
        alloc.decref(b)
    assert alloc.free_blocks == alloc.num_blocks
    assert all(r == 0 for r in alloc.refcounts())


def test_allocator_errors_double_free_decref_below_zero_bad_id():
    alloc = BlockedAllocator(4)
    blocks = alloc.allocate(2)
    alloc.free(blocks)
    with pytest.raises(ValueError, match="double free"):
        alloc.free([blocks[0]])
    with pytest.raises(ValueError, match="below zero|double free"):
        alloc.decref(blocks[0])
    with pytest.raises(ValueError, match="bad block id"):
        alloc.free([99])
    with pytest.raises(ValueError, match="bad block id"):
        alloc.incref(-1)
    # incref only applies to allocated blocks
    with pytest.raises(ValueError, match="incref of free block"):
        alloc.incref(blocks[0])
    # a lease listing one block more often than its refcount fails
    # atomically, before any mutation
    b = alloc.allocate(1)[0]
    with pytest.raises(ValueError, match="double free"):
        alloc.free([b, b])
    assert alloc.refcount(b) == 1   # untouched by the failed free
    alloc.free([b])


def test_allocator_shared_block_survives_first_owner():
    alloc = BlockedAllocator(4)
    (b,) = alloc.allocate(1)
    alloc.incref(b)                  # second owner (e.g. the cache)
    alloc.decref(b)
    assert alloc.refcount(b) == 1 and alloc.free_blocks == 3
    alloc.decref(b)                  # last owner: back to the free list
    assert alloc.free_blocks == 4


# -- radix tree -----------------------------------------------------------
BS = 4


def _cache(num_blocks=64, max_blocks=32):
    alloc = BlockedAllocator(num_blocks)
    return PrefixCache(alloc, BS, max_blocks), alloc


def _toks(*vals):
    return np.asarray(vals, np.int32)


def _seq(base, n):
    """n*BS distinct tokens starting at base."""
    return np.arange(base, base + n * BS, dtype=np.int32)


def _insert(cache, alloc, tokens, n_blocks):
    """Allocate, insert, then drop the 'sequence's' own references — the
    engine-flush handover: whatever the cache granted it now owns alone."""
    blocks = alloc.allocate(n_blocks)
    cache.insert(tokens, blocks)
    for b in blocks:
        alloc.decref(b)
    return blocks


def test_radix_match_is_block_granular_and_caps_below_full_prompt():
    cache, alloc = _cache()
    t = _seq(0, 3)                          # 12 tokens, 3 blocks
    blocks = alloc.allocate(3)
    assert cache.insert(t, blocks) == 3
    # identical prompt: full-block match, but capped so the last token
    # stays uncovered (the sequence must produce first-token logits)
    got, covered = cache.match(t)
    assert covered == 2 * BS and got == blocks[:2]
    # longer prompt sharing the full 3 blocks uses all of them
    got, covered = cache.match(np.concatenate([t, _toks(99)]))
    assert covered == 3 * BS and got == blocks
    # divergence inside block 2 truncates the match to whole blocks 0-1
    t2 = t.copy()
    t2[2 * BS + 1] = 77
    got, covered = cache.match(np.concatenate([t2, _toks(99)]))
    assert covered == 2 * BS and got == blocks[:2]
    # divergence inside block 0: nothing shareable
    t3 = t.copy()
    t3[1] = 77
    got, covered = cache.match(np.concatenate([t3, _toks(99)]))
    assert covered == 0 and got == []
    # sub-block prompts can never match
    assert cache.match(t[:BS - 1]) == ([], 0)


def test_radix_split_on_partial_match():
    cache, alloc = _cache()
    t1 = _seq(0, 4)
    b1 = alloc.allocate(4)
    cache.insert(t1, b1)
    assert len(cache._root.children) == 1     # one 4-block edge
    # second prompt shares exactly 2 blocks then diverges
    t2 = np.concatenate([t1[:2 * BS], _seq(100, 2)])
    b2 = alloc.allocate(4)
    assert cache.insert(t2, b2) == 2          # only its unique suffix
    # the edge split at the divergence block boundary: shared head with
    # two child branches
    (head,) = cache._root.children.values()
    assert head.blocks == b1[:2] and len(head.children) == 2
    tails = sorted(tuple(n.blocks) for n in head.children.values())
    assert tails == sorted([tuple(b1[2:]), tuple(b2[2:])])
    # both full prompts still match end-to-end (plus sentinel)
    for t, b in ((t1, b1), (t2, b1[:2] + b2[2:])):
        got, covered = cache.match(np.concatenate([t, _toks(5)]))
        assert covered == 4 * BS and got == b
    assert cache.cached_blocks == 6


def test_radix_lru_eviction_never_evicts_referenced_node():
    cache, alloc = _cache(max_blocks=4)
    t1, t2 = _seq(0, 2), _seq(100, 2)
    _insert(cache, alloc, t1, 2)
    lease = cache.acquire(np.concatenate([t1, _toks(7)]))
    assert lease is not None and lease.covered == 2 * BS
    _insert(cache, alloc, t2, 2)          # fills the 4-block budget
    # budget pressure: t2 (unreferenced, least recently used) is
    # evicted; t1 is pinned by the live lease and survives
    _insert(cache, alloc, _seq(200, 2), 2)
    assert cache.match(np.concatenate([t1, _toks(7)]))[1] == 2 * BS
    assert cache.match(np.concatenate([t2, _toks(7)]))[1] == 0
    # the lease's blocks stayed alive through it all
    assert all(alloc.refcount(b) >= 1 for b in lease.blocks)
    # release (+ the sequence's flush decref) makes t1 evictable
    cache.release(lease)
    for b in lease.blocks:
        alloc.decref(b)
    _insert(cache, alloc, _seq(300, 2), 2)
    assert cache.match(np.concatenate([t1, _toks(7)]))[1] == 0
    assert cache.cached_blocks <= 4
    # every evicted block really went back: free + cached == total
    assert alloc.free_blocks == alloc.num_blocks - cache.cached_blocks


def test_radix_invalidate_and_reclaim():
    cache, alloc = _cache()
    t1, t2 = _seq(0, 3), _seq(100, 2)
    _insert(cache, alloc, t1, 3)
    _insert(cache, alloc, t2, 2)
    assert alloc.free_blocks == alloc.num_blocks - 5
    lease = cache.acquire(np.concatenate([t2, _toks(7)]))
    # reclaim frees only unreferenced prefixes, LRU first
    assert cache.reclaim(2) >= 2
    assert cache.match(np.concatenate([t1, _toks(7)]))[1] == 0
    assert cache.match(np.concatenate([t2, _toks(7)]))[1] == 2 * BS
    # invalidate drops everything unpinned; the leased path survives
    cache.invalidate()
    assert cache.match(np.concatenate([t2, _toks(7)]))[1] == 2 * BS
    cache.release(lease)
    for b in lease.blocks:
        alloc.decref(b)               # the sequence's own flush
    assert cache.invalidate() == 2
    assert cache.cached_blocks == 0
    assert alloc.free_blocks == alloc.num_blocks


def test_radix_insert_respects_budget_with_partial_grant():
    cache, alloc = _cache(max_blocks=2)
    b = alloc.allocate(4)
    t = _seq(0, 4)
    assert cache.insert(t, b) == 2            # budget-truncated prefix
    assert cache.cached_blocks == 2
    got, covered = cache.match(np.concatenate([t, _toks(9)]))
    assert covered == 2 * BS and got == b[:2]
    # the uncached tail blocks kept only the sequence's reference
    assert alloc.refcount(b[2]) == 1 and alloc.refcount(b[0]) == 2


def test_lease_abandon_restores_everything():
    cache, alloc = _cache()
    t = _seq(0, 2)
    cache.insert(t, alloc.allocate(2))
    stats0 = cache.stats()
    refs0 = alloc.refcounts()
    lease = cache.acquire(np.concatenate([t, _toks(7)]))
    cache.abandon(lease)
    assert alloc.refcounts() == refs0
    assert cache.stats() == stats0
    with pytest.raises(ValueError, match="released twice"):
        cache.release(lease)


# -- state manager: prefix attach + audit ---------------------------------
def test_state_manager_prefix_create_validation_and_flush():
    sm = DSStateManager(num_blocks=16, block_size=4, max_blocks_per_seq=8,
                        max_seqs=4)
    shared = sm.allocator.allocate(2)
    for b in shared:
        sm.allocator.incref(b)        # the "cache" reference
    d = sm.create(0, np.arange(12, dtype=np.int32),
                  prefix=(shared, 8))
    assert d.seen_tokens == 8 and d.prefix_covered == 8
    assert d.blocks == shared and d.in_prefill
    sm.audit(cache_blocks=shared)
    sm.flush(0)
    # shared blocks survive the flush (cache still owns them)
    assert all(sm.allocator.refcount(b) == 1 for b in shared)
    report = sm.audit(cache_blocks=shared)
    assert report["cached"] == 2 and report["live"] == 0
    # validation: misaligned / over-covering prefixes are loud
    with pytest.raises(ValueError, match="block-aligned"):
        sm.create(1, np.arange(12, dtype=np.int32), prefix=(shared, 7))
    with pytest.raises(ValueError, match="blocks for covered"):
        sm.create(1, np.arange(12, dtype=np.int32), prefix=(shared, 4))
    with pytest.raises(ValueError, match="last prompt token"):
        sm.create(1, np.arange(8, dtype=np.int32), prefix=(shared, 8))


def test_state_manager_audit_detects_leaks():
    sm = DSStateManager(num_blocks=8, block_size=4, max_blocks_per_seq=4,
                        max_seqs=2)
    d = sm.create(0, np.arange(6, dtype=np.int32))
    sm.ensure_capacity(d, 6)
    sm.audit()
    # a reference nobody can name is a leak
    sm.allocator.incref(d.blocks[0])
    with pytest.raises(RuntimeError, match="leaked"):
        sm.audit()
    sm.allocator.decref(d.blocks[0])
    sm.flush(0)
    assert sm.audit() == {"free": 8, "live": 0, "shared": 0, "cached": 0,
                          "total": 8}


# -- serve loop integration (real tiny engine, CPU) -----------------------
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


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _shared_prompt_stream(n, shared_len=32, unique_len=11, seed=7):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, 128, shared_len).astype(np.int32)
    return [np.concatenate([shared,
                            rng.randint(0, 128, unique_len).astype(np.int32)])
            for _ in range(n)]


def _serve_stream(prompts, pcb, new_tokens=5, decode_burst=1, **engine_kw):
    """Serve `prompts` (all submitted at once) on a fresh tiny engine
    with a `pcb`-block prefix cache (0: off), counting the prefill
    tokens of every step.  -> (tokens per request, prefill tokens,
    telemetry summary, engine)."""
    eng = _tiny_engine(**engine_kw)
    loop = ServeLoop(eng, ServingConfig(prefix_cache_blocks=pcb,
                                        decode_burst=decode_burst,
                                        audit_blocks=True),
                     clock=_FakeClock())
    reqs = [loop.submit(p, max_new_tokens=new_tokens) for p in prompts]
    prefill_total = 0
    steps = 0
    while loop.has_work:
        loop.step()
        prefill_total += loop.telemetry.prefill_tokens_step
        steps += 1
        assert steps < 300
    assert all(r.state is RequestState.DONE for r in reqs)
    return ([list(r.output_tokens) for r in reqs], prefill_total,
            loop.telemetry.summary(), eng)


def test_serve_loop_prefix_parity_and_savings():
    """The serve-loop parity contract: `prefix_cache_blocks=0` is today's
    behavior, cache-on produces IDENTICAL tokens with measurably fewer
    prefill tokens, hits recorded, audit clean after drain."""
    prompts = _shared_prompt_stream(4)

    def run(pcb):
        return _serve_stream(prompts, pcb)

    outs_off, prefill_off, s_off, eng_off = run(0)
    outs_on, prefill_on, s_on, eng_on = run(24)
    # bit-for-bit outputs, strictly less prefill work
    assert outs_on == outs_off
    assert prefill_on < prefill_off
    assert prefill_off - prefill_on == s_on["prefill_tokens_saved"] > 0
    # max_seqs=2: the first admission wave (2 requests) misses, the
    # rest hit the 4-block (32-token) shared prefix
    assert s_on["prefix_hit_rate"] == 0.5
    assert s_on["prefill_tokens_saved"] == 2 * 32
    assert s_on["prefix_cached_blocks"] > 0
    # cache-off is bit-for-bit today's loop: no cache artifacts at all
    assert eng_off.prefix_cache is None
    assert s_off["prefix_hit_rate"] is None
    assert s_off["prefill_tokens_saved"] == 0
    # conservation after drain: only the cache holds blocks
    report = eng_on.audit_blocks()
    assert report["live"] == 0 and report["cached"] > 0
    assert eng_on.free_blocks == 48 - report["cached"]


def test_serve_loop_ledger_counts_cached_prefix_as_held():
    """Admission packs more concurrency out of the same arena: a request
    whose whole-lifetime block need exceeds free blocks is still
    admitted when the cached prefix covers the difference — and the
    run completes without an allocator error (the ledger stayed
    honest)."""
    prompts = _shared_prompt_stream(3, shared_len=64, unique_len=9)
    # per request: ceil((73 + 7)/8) = 10 blocks, 8 of them the shared
    # prefix.  num_blocks=20: after the primer caches 8 blocks +
    # request B holds 10, only 2 are free — C (10 blocks) can admit
    # ONLY because 8 of its 10 are the cached prefix.
    eng = _tiny_engine(num_blocks=20, max_seqs=1, max_blocks_per_seq=10)
    loop = ServeLoop(eng, ServingConfig(prefix_cache_blocks=8,
                                        audit_blocks=True),
                     clock=_FakeClock())
    primer = loop.submit(prompts[0], max_new_tokens=7)
    loop.run_until_idle(max_steps=200)
    assert primer.state is RequestState.DONE
    assert eng.prefix_cache.cached_blocks == 8
    b = loop.submit(prompts[1], max_new_tokens=7)
    c = loop.submit(prompts[2], max_new_tokens=7)
    loop.run_until_idle(max_steps=400)
    assert b.state is RequestState.DONE
    assert c.state is RequestState.DONE
    assert loop.telemetry.counters["prefix_hits"] == 2
    eng.audit_blocks()


def test_serve_loop_reclaims_cache_for_non_matching_request():
    """Blocks parked in the cache are headroom, not spent capacity: a
    request with NO shared prefix that needs them gets them back via
    LRU reclaim instead of queueing forever."""
    prompts = _shared_prompt_stream(1, shared_len=64, unique_len=9)
    eng = _tiny_engine(num_blocks=12, max_seqs=1, max_blocks_per_seq=12)
    loop = ServeLoop(eng, ServingConfig(prefix_cache_blocks=9,
                                        audit_blocks=True),
                     clock=_FakeClock())
    primer = loop.submit(prompts[0], max_new_tokens=7)
    loop.run_until_idle(max_steps=200)
    assert primer.state is RequestState.DONE
    assert eng.prefix_cache.cached_blocks == 9     # 12 - 9 = 3 free
    rng = np.random.RandomState(99)
    stranger = loop.submit(rng.randint(0, 128, 70).astype(np.int32),
                           max_new_tokens=7)       # needs 10 blocks
    loop.run_until_idle(max_steps=200)
    assert stranger.state is RequestState.DONE
    assert eng.prefix_cache.evicted_blocks >= 7
    eng.audit_blocks()


def test_prefix_attached_sequence_not_starved_by_fresh_stream():
    """A prefix-attached fresh sequence can never ride the full-prompt
    fast path, so the chunk-budget fairness reservation must cover it:
    a sustained stream of fresh cache-miss prompts that would otherwise
    drain the whole per-step budget through prefill_full cannot defer
    its suffix prefill indefinitely."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig
    import jax

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=256,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                num_blocks=64, block_size=8,
                                max_blocks_per_seq=16, max_seqs=8,
                                prefill_chunk_size=32,
                                max_prefill_tokens_per_step=64,
                                full_prompt_prefill=True))
    assert eng._use_prefill_full
    eng.enable_prefix_cache(16)
    rng = np.random.RandomState(5)
    shared = rng.randint(0, 128, 32).astype(np.int32)
    primer = np.concatenate([shared,
                             rng.randint(0, 128, 11).astype(np.int32)])
    eng.generate(primer, max_new_tokens=2, uid=1)      # populates the tree
    victim = np.concatenate([shared,
                             rng.randint(0, 128, 11).astype(np.int32)])

    def fresh():
        return rng.randint(0, 128, 32).astype(np.int32)

    # the victim ARRIVES WITH two fresh 32-token prompts — exactly the
    # whole 64-token budget if nothing is reserved for the chunked loop
    # — and two more arrive every subsequent step
    out = eng.put([100, 200, 201], [victim, fresh(), fresh()],
                  decode=False)
    d = eng.state.seqs[100]
    assert d.prefix_covered == 32
    for uid in (200, 201):
        if uid in out:
            eng.flush(uid)
    for i in range(1, 5):
        if not d.in_prefill:
            break
        uids = [200 + 2 * i, 201 + 2 * i]
        out = eng.put(uids, [fresh(), fresh()], decode=False)
        for uid in uids:
            if uid in out:
                eng.flush(uid)
    assert not d.in_prefill, (
        "prefix-attached sequence starved by the fresh-prompt stream")
    eng.flush(100)
    # stragglers (fresh prompts bumped to the chunked path) drain clean
    for uid in list(eng.state.seqs):
        while eng.state.seqs[uid].in_prefill:
            eng.step(decode=False)
        eng.flush(uid)
    eng.audit_blocks()


def test_reclaim_gate_does_not_wipe_cache_for_hopeless_request():
    """A queued request that cannot fit even with the cache emptied must
    not evict the hot prefixes on its way to being deferred; once
    eviction CAN close the gap, reclaim runs and the request admits."""
    prompts = _shared_prompt_stream(1, shared_len=64, unique_len=9)
    eng = _tiny_engine(num_blocks=12, max_seqs=2, max_blocks_per_seq=12)
    loop = ServeLoop(eng, ServingConfig(prefix_cache_blocks=9,
                                        audit_blocks=True),
                     clock=_FakeClock())
    primer = loop.submit(prompts[0], max_new_tokens=7)
    loop.run_until_idle(max_steps=200)
    assert primer.state is RequestState.DONE
    assert eng.prefix_cache.cached_blocks == 9      # 3 blocks stay free
    rng = np.random.RandomState(42)
    # A: 10 + 6 = 16 tokens = 2 blocks — admits into the free headroom
    a = loop.submit(rng.randint(0, 128, 10).astype(np.int32),
                    max_new_tokens=6)
    loop.step()
    assert a.state is not RequestState.QUEUED
    # B: 89 + 7 = 96 tokens = 12 blocks.  While A holds its 2 blocks,
    # even evicting all 9 cached blocks leaves only 10 — hopeless, so
    # the gate must defer B WITHOUT wiping the cache
    b = loop.submit(rng.randint(0, 128, 89).astype(np.int32),
                    max_new_tokens=7)
    loop.step()
    assert b.state is RequestState.QUEUED
    assert eng.prefix_cache.cached_blocks == 9      # nothing wiped
    assert eng.prefix_cache.evicted_blocks == 0
    # A finishes -> eviction can now close B's gap: reclaim runs, B
    # admits and completes
    loop.run_until_idle(max_steps=100)
    assert a.state is RequestState.DONE
    assert b.state is RequestState.DONE
    assert eng.prefix_cache.evicted_blocks >= 9
    eng.audit_blocks()


def test_serve_loop_does_not_double_count_cache_misses():
    """Admission already walked the tree; put() must not re-walk for
    known misses — the cache's own counters then agree with the
    admitted-request telemetry."""
    prompts = _shared_prompt_stream(3)
    eng = _tiny_engine(max_seqs=1)
    loop = ServeLoop(eng, ServingConfig(prefix_cache_blocks=24),
                     clock=_FakeClock())
    for p in prompts:
        loop.submit(p, max_new_tokens=3)
    loop.run_until_idle(max_steps=300)
    t = loop.telemetry.counters
    stats = eng.prefix_cache.stats()
    assert t["prefix_hits"] == stats["hits"] == 2
    assert t["prefix_misses"] == stats["misses"] == 1


def test_engine_direct_generate_reuses_prefix():
    """Direct engine use (no serve loop): enable_prefix_cache makes
    generate() reuse the prompt KV of earlier generate() calls, with
    identical outputs."""
    eng = _tiny_engine()
    prompt = _shared_prompt_stream(1)[0]
    want = eng.generate(prompt, max_new_tokens=5, uid=1)
    cache = eng.enable_prefix_cache(16)
    got_miss = eng.generate(prompt, max_new_tokens=5, uid=2)
    got_hit = eng.generate(prompt, max_new_tokens=5, uid=3)
    np.testing.assert_array_equal(want, got_miss)
    np.testing.assert_array_equal(want, got_hit)
    assert cache.hits == 1 and cache.tokens_saved > 0
    eng.audit_blocks()


def test_enable_prefix_cache_rejects_live_sequences_and_fake_engines():
    eng = _tiny_engine()
    eng.put([0], [np.arange(4, dtype=np.int32)], decode=False)
    with pytest.raises(RuntimeError, match="live sequences"):
        eng.enable_prefix_cache(8)
    eng.flush(0)
    eng.enable_prefix_cache(8)
    # the serve loop is loud about engines without the capability
    from types import SimpleNamespace
    with pytest.raises(ValueError, match="prefix_cache_blocks"):
        ServeLoop(SimpleNamespace(), ServingConfig(prefix_cache_blocks=8))


def test_longrope_models_refuse_prefix_cache():
    """phi3-style longrope picks short/long rope factors from the FULL
    prompt length, so cached KV is not a pure function of (tokens,
    positions, weights) — token-matched reuse across request lengths
    would be silently wrong.  enable_prefix_cache must refuse loudly."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    half = 8                                    # head_dim 16 -> half 8
    cfg = TransformerConfig(vocab_size=64, hidden_size=64, num_layers=1,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32, pos_emb="rope",
                            rope_scaling=("longrope", 1.0, 64,
                                          [1.0] * half, [2.0] * half))
    eng = InferenceEngineV2(Transformer(cfg),
                            config=RaggedInferenceEngineConfig(
                                num_blocks=16, block_size=8,
                                max_blocks_per_seq=8, max_seqs=2))
    with pytest.raises(ValueError, match="longrope"):
        eng.enable_prefix_cache(8)


def test_deep_chain_tree_operations_are_iterative():
    """Incrementally extended prompts (growing chat transcripts) build a
    chain-shaped tree one node per block; every traversal must survive
    depths past the Python recursion limit (no recursive walks on the
    serve loop's admission path)."""
    import sys
    depth = sys.getrecursionlimit() + 100
    cache, alloc = _cache(num_blocks=depth + 4, max_blocks=depth + 4)
    tokens = np.arange(depth * BS, dtype=np.int32)
    for i in range(1, depth + 1):
        (b,) = alloc.allocate(1)
        # only the new tail block is consumed (earlier entries matched)
        cache.insert(tokens[:i * BS], [-1] * (i - 1) + [b])
        alloc.decref(b)                         # hand over to the cache
    assert cache.cached_blocks == depth
    assert cache.evictable_blocks() == depth
    lease = cache.acquire(tokens)
    assert lease.covered == (depth - 1) * BS    # capped below full prompt
    # the pinned chain leaves only the unmatched deepest node evictable
    assert cache.evictable_blocks() == 1
    cache.release(lease)
    for b in lease.blocks:
        alloc.decref(b)
    assert cache.reclaim(depth) == depth
    assert cache.cached_blocks == 0
    assert alloc.free_blocks == alloc.num_blocks


def test_epoch_bumps_on_every_content_change_and_stats_exposes_it():
    """The fleet staleness protocol's cheap change detector: epoch moves
    on insert/evict/invalidate (anything that changes WHICH prefixes are
    cached) and stays put on reads, acquires, and no-op inserts."""
    cache, alloc = _cache()
    assert cache.stats()["epoch"] == 0
    _insert(cache, alloc, _seq(0, 2), 2)
    assert cache.epoch == 1                       # insert cached blocks
    lease = cache.acquire(_seq(0, 2))
    assert cache.epoch == 1                       # reads don't bump
    blocks = alloc.allocate(2)
    assert cache.insert(_seq(0, 2), blocks) == 0  # fully covered: no-op
    alloc.free(blocks)
    assert cache.epoch == 1
    cache.release(lease)
    for b in lease.blocks:
        alloc.decref(b)
    assert cache.reclaim(1) >= 1                  # eviction bumps
    assert cache.epoch == 2
    _insert(cache, alloc, _seq(100, 2), 2)
    assert cache.epoch == 3
    assert cache.invalidate() > 0                 # invalidate bumps
    assert cache.epoch == 4
    assert cache.invalidate() == 0                # empty: nothing moved
    assert cache.epoch == 4
    assert cache.digest() == (4, cache.cached_blocks)
    assert cache.stats()["epoch"] == 4


def test_snapshot_entries_cover_every_cached_whole_block_prefix():
    """snapshot() publishes one rolling-hash entry per cached
    whole-block prefix, consistent with block_hashes — the contract the
    fleet's GlobalPrefixIndex lookups rely on."""
    from deepspeed_tpu.serving import block_hashes
    cache, alloc = _cache()
    a = _seq(0, 3)                       # 3 blocks
    b = np.concatenate([_seq(0, 1), _seq(500, 2)])   # diverges after 1
    _insert(cache, alloc, a, 3)
    _insert(cache, alloc, b, 3)
    snap = cache.snapshot()
    assert snap["epoch"] == cache.epoch
    assert snap["block_size"] == BS
    assert snap["cached_blocks"] == 5    # 3 + 2 (first block shared)
    entries = snap["entries"]
    # every whole-block prefix of both prompts appears, exactly once
    want = {}
    for toks in (a, b):
        for k, h in enumerate(block_hashes(toks, BS)):
            want[h] = (k + 1) * BS
    assert entries == want
    assert len(entries) == 5             # shared first block: one entry


def test_serving_config_prefix_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"prefix_cache_blocks": 96, "audit_blocks": True}})
    assert cfg.serving.prefix_cache_blocks == 96
    assert cfg.serving.audit_blocks is True
    assert ServingConfig().prefix_cache_blocks == 0      # off by default
    with pytest.raises(ConfigError, match="prefix_cache_blocks"):
        ServingConfig(prefix_cache_blocks=-1).validate()


def test_bench_prefix_row_driver_on_tiny_engine():
    """A shared-system-prompt stream served one sequence at a time, its
    decode in bursts, behind a cache smaller than the stream (8 blocks:
    the unique tails churn out, the shared prefix stays): the same
    tokens as with the cache off, every request after the first a hit,
    at least half of all prompt tokens never prefilled, and no block
    leaked (the audit runs after every finishing step and once more
    after the drain)."""
    shared_len, unique_len = 64, 16
    prompts = _shared_prompt_stream(3, shared_len, unique_len, seed=9)
    geometry = dict(new_tokens=3, decode_burst=16, num_blocks=64,
                    block_size=16, max_seqs=1)
    outs_off, prefill_off, s_off, _ = _serve_stream(prompts, 0, **geometry)
    outs_on, prefill_on, s_on, eng = _serve_stream(prompts, 8, **geometry)
    assert outs_on == outs_off
    assert s_off["prefix_hit_rate"] is None
    assert s_on["prefix_hit_rate"] == pytest.approx(2 / 3)
    assert s_on["prefill_tokens_saved"] == prefill_off - prefill_on
    assert (s_on["prefill_tokens_saved"]
            >= 0.5 * len(prompts) * (shared_len + unique_len))
    report = eng.audit_blocks()
    assert report["live"] == 0 and 0 < report["cached"] <= 8
