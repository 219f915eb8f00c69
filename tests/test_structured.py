"""Tests: ISSUE 18 — structured generation (grammar-constrained
decoding with on-device FSM masks, serving/structured).

Locks the subsystem from both ends: the grammar compiler (regex and
JSON-schema front ends lowered to one token automaton), the compiled-
automaton LRU cache's radix-cache discipline (epoch stamps, stats,
leak audit), the device contract (k constrained steps = ONE compiled
multi-step dispatch, zero added d2h, transfer-guard clean, seeded
replay bit-exact, k-partition invariant), composition with speculative
verify (grammar pre-filtered drafts, forced-accept uplift), BOTH
off-parity directions (`structured=None` config and unconstrained
rows under an enabled config are bit-for-bit PR 17), the per-tenant
KV-arena quota satellite, the workload generator's structured
dimension (off = byte-identical schedule), and the CPU rider of the
constrained-multi-step HLO structure check."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.config.config import (ConfigError, ServingConfig,
                                         SpeculativeConfig,
                                         StructuredConfig, TenancyConfig)
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models import Transformer, TransformerConfig
from deepspeed_tpu.serving import RequestState, ServeLoop
from deepspeed_tpu.serving.server import AdmissionError
from deepspeed_tpu.serving.speculative import filter_draft
from deepspeed_tpu.serving.structured import (AutomatonCache,
                                              GrammarError,
                                              ResponseFormat,
                                              TokenVocabulary, byte_vocab,
                                              compile_regex,
                                              schema_to_regex)

pytestmark = pytest.mark.serving

EOS = 0


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _engine(model, params, **kw):
    base = dict(num_blocks=32, block_size=8, max_blocks_per_seq=8,
                max_seqs=4, prefill_chunk_size=16)
    base.update(kw)
    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**base))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _auto(pattern, vocab_size=128):
    return AutomatonCache(byte_vocab(vocab_size)).get(
        ResponseFormat.regex(pattern))


def _toks(s):
    return [ord(c) for c in s]


# -- grammar compiler -------------------------------------------------------

def test_regex_compiler_token_automaton():
    """Brzozowski-derivative regex -> DFA -> token automaton: emitted
    chains are accepted exactly when the source regex matches (EOS is
    not a grammar symbol; it is admitted in accept states only)."""
    auto = _auto(r"(ab)+c")
    for good in ("abc", "ababc", "abababc"):
        assert auto.accepts(_toks(good) + [EOS], eos_id=EOS), good
    for bad in ("", "ab", "ac", "abcc", "ba", "abca"):
        assert not auto.accepts(_toks(bad) + [EOS], eos_id=EOS), bad
    # prefix-closed navigation: every state reached by a good prefix
    # allows some continuation toward acceptance
    st = 0
    for t in _toks("abab"):
        assert auto.allows(st, t)
        st = int(auto.trans[st, t])
    assert not bool(auto.accept[st])          # "abab" needs the c
    assert auto.allows(st, ord("a")) and auto.allows(st, ord("c"))
    assert not auto.allows(st, ord("b"))


def test_automaton_table_shapes_and_mask_packing():
    """Device tables carry the documented layout: trans s32[S, V] with
    -1 = disallowed, mask u32[S, ceil(V/32)] with bit b of word w =
    token w*32+b, accept bool[S] — and host_mask unpacks to exactly
    the per-state allowed set."""
    auto = _auto(r"[ab]x")
    S, V = auto.trans.shape
    assert V == 128 and auto.mask.shape == (S, (V + 31) // 32)
    assert auto.mask.dtype == np.uint32 and auto.trans.dtype == np.int32
    for s in range(S):
        unpacked = np.zeros(V, bool)
        for t in range(V):
            unpacked[t] = bool(
                (auto.mask[s, t // 32] >> np.uint32(t % 32)) & 1)
        want = auto.trans[s] >= 0
        assert (unpacked == want).all()
    hm = auto.host_mask(0, eos_id=EOS)
    assert hm[ord("a")] and hm[ord("b")] and not hm[ord("x")]
    assert not hm[EOS]                         # start state not accepting


def test_walk_clamps_like_device_and_dead_state_escape():
    """`walk` pins the state on an undefined transition — the SAME
    clamp the device scan applies (tr < 0 keeps st), so host and
    device trackers can never diverge — and a state with an empty
    allowed set escapes to the all-True mask (never a -inf-everywhere
    row)."""
    auto = _auto(r"ab")
    st = auto.walk(0, _toks("a"))
    assert st == int(auto.trans[0, ord("a")])
    # undefined transition: state pins, subsequent walk continues
    assert auto.walk(0, _toks("ax")) == st
    assert auto.walk(0, _toks("axb")) == auto.walk(st, _toks("b"))
    # dead-state escape on the host mirror: after the full match the
    # only legal continuation is EOS; the raw token mask is empty but
    # host_mask must never return all-False
    done = auto.walk(0, _toks("ab"))
    assert bool(auto.accept[done])
    hm = auto.host_mask(done, eos_id=EOS)
    assert hm[EOS]
    hm_no_eos = auto.host_mask(done, eos_id=None)
    assert hm_no_eos.all()                     # escape, not a dead end


def test_schema_to_regex_canonical_json():
    """JSON mode lowers to a regex over the canonical compact
    serialization; conforming canonical values are accepted and
    near-misses rejected."""
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "n": {"type": "integer"}},
              "required": ["ok", "n"]}
    auto = _auto(schema_to_regex(schema))
    good = '{"n":42,"ok":true}'                # sorted keys, compact
    assert auto.accepts(_toks(good) + [EOS], eos_id=EOS)
    for bad in ('{"ok":true,"n":42}',          # unsorted keys
                '{"n": 42,"ok":true}',         # whitespace
                '{"n":42}',                    # missing property
                '{"n":42,"ok":maybe}'):
        assert not auto.accepts(_toks(bad) + [EOS], eos_id=EOS), bad
    # enum / const / array forms
    a2 = _auto(schema_to_regex(
        {"type": "array", "items": {"enum": ["x", 7]},
         "minItems": 1, "maxItems": 2}))
    for good in ('["x"]', '[7,"x"]'):
        assert a2.accepts(_toks(good) + [EOS], eos_id=EOS), good
    for bad in ("[]", '[7,7,7]', '["y"]'):
        assert not a2.accepts(_toks(bad) + [EOS], eos_id=EOS), bad


def test_grammar_error_paths():
    with pytest.raises(GrammarError):
        compile_regex("(ab")                   # unbalanced
    with pytest.raises(GrammarError):
        compile_regex("a" * 200, max_states=8)  # state-budget blowup
    with pytest.raises(GrammarError):
        schema_to_regex({"type": "object"})    # no properties
    with pytest.raises(GrammarError):
        schema_to_regex({"type": "string", "minLength": 3})  # unsupported
    with pytest.raises(GrammarError):
        ResponseFormat.json_schema("{not json")
    with pytest.raises(GrammarError):
        ResponseFormat.regex("")


# -- automaton cache --------------------------------------------------------

def test_cache_lru_discipline_and_audit():
    """LRU keyed by grammar digest: hit/miss/compile/evict counters,
    epoch-stamped digest() for change detection, audit() clean through
    churn, peek() non-mutating."""
    cache = AutomatonCache(byte_vocab(64), capacity=2)
    f1 = ResponseFormat.regex("a+")
    f2 = ResponseFormat.regex("b+")
    f3 = ResponseFormat.regex("c+")
    a1 = cache.get(f1)
    assert cache.get(f1) is a1                 # hit returns the object
    d0 = cache.digest()
    cache.get(f2)
    assert cache.digest() != d0                # any content change
    cache.get(f1)                              # refresh f1's recency
    cache.get(f3)                              # evicts f2 (LRU)
    st = cache.stats()
    assert st["size"] == 2 and st["capacity"] == 2
    assert st["evictions"] == 1 and st["compiles"] == 3
    assert st["hits"] == 2 and st["misses"] == 3
    assert cache.peek(f2.digest(cache.vocab)) is None
    assert cache.peek(f1.digest(cache.vocab)) is a1
    assert cache.stats()["hits"] == 2          # peek mutates nothing
    assert cache.audit() == []
    # two spellings of one schema share an entry (canonicalization)
    cs = cache.compiles if hasattr(cache, "compiles") else None
    g1 = cache.get(ResponseFormat.json_schema({"type": "integer"}))
    g2 = cache.get(ResponseFormat.json_schema('{"type": "integer"}'))
    assert g1 is g2


def test_structured_config_validation():
    StructuredConfig().validate()
    with pytest.raises(ConfigError):
        StructuredConfig(cache_size=0).validate()
    with pytest.raises(ConfigError):
        StructuredConfig(max_states=0).validate()
    with pytest.raises(ConfigError):
        StructuredConfig(vocab="words").validate()
    cfg = ServingConfig.from_dict(
        {"structured": {"cache_size": 4, "max_states": 256}})
    assert cfg.structured.cache_size == 4
    assert ServingConfig.from_dict({}).structured is None
    with pytest.raises(ConfigError):
        TenancyConfig(enabled=True,
                      kv_block_quota={"t0": 0}).validate()


# -- serve-loop integration -------------------------------------------------

def _serve(tiny, reqs_kw, cfg_kw=None, engine_kw=None, steps=300):
    model, params = tiny
    eng = _engine(model, params, **(engine_kw or {}))
    loop = ServeLoop(eng, ServingConfig(audit_blocks=True,
                                        **(cfg_kw or {})),
                     clock=FakeClock())
    reqs = [loop.submit(p, **kw) for p, kw in reqs_kw]
    loop.run_until_idle(max_steps=steps)
    return loop, eng, reqs


FMT = ResponseFormat.regex(r"(ab)+c")
# every sentence ends inside a 16-token budget whatever the (random) model
# prefers: under (ab)+c a model that never ranks "c" first just runs out
# of budget mid-sentence, which no grammar accepts
BOUNDED = r"ab(ab)?c"
FMT_BOUNDED = ResponseFormat.regex(BOUNDED)


def test_constrained_multistep_property_over_seeds(tiny):
    """The acceptance property: EVERY emitted chain of a constrained
    stochastic request is accepted by the source grammar — across
    seeds, mixed into a batch with an unconstrained row (whose output
    the mask must not touch)."""
    auto = _auto(r"(ab)+c")
    rng = np.random.RandomState(50)
    base_p = rng.randint(1, 128, 11).astype(np.int32)
    ref = None
    for seed in (1, 7, 123):
        p = rng.randint(1, 128, 9).astype(np.int32)
        loop, eng, (rc, rb) = _serve(
            tiny,
            [(p, dict(max_new_tokens=24, eos_token_id=EOS,
                      response_format=FMT, temperature=0.9, top_k=0,
                      seed=seed)),
             (base_p, dict(max_new_tokens=12))],
            cfg_kw=dict(multi_step=4,
                        structured=StructuredConfig()))
        assert rc.state is RequestState.DONE
        assert auto.accepts(rc.generated, eos_id=EOS), rc.generated
        assert int(rc.generated[-1]) == EOS
        assert eng.state.seqs == {} and eng.free_blocks == 32
        # the unconstrained row is identical across arms (the mask is
        # identity for has_fsm=False rows)
        if ref is None:
            ref = list(map(int, rb.generated))
        else:
            assert list(map(int, rb.generated)) == ref
    assert loop.telemetry.counters["grammar_requests"] == 1


def test_constrained_seeded_replay_bit_exact(tiny):
    """Per-request seeded streams make constrained stochastic
    generations replay bit-for-bit — the failover-regeneration
    contract extends to grammars."""
    rng = np.random.RandomState(51)
    p = rng.randint(1, 128, 9).astype(np.int32)
    kw = dict(max_new_tokens=24, eos_token_id=EOS, response_format=FMT,
              temperature=0.8, top_k=0, seed=99)
    cfg = dict(multi_step=4, structured=StructuredConfig())
    _, _, (r1,) = _serve(tiny, [(p, kw)], cfg_kw=cfg)
    _, _, (r2,) = _serve(tiny, [(p, kw)], cfg_kw=cfg)
    assert list(r1.generated) == list(r2.generated)


def test_structured_off_parity_both_directions(tiny):
    """Both parity locks: (a) `structured=None` serves bit-for-bit
    like a config that never heard of grammars; (b) under an ENABLED
    structured config, requests without response_format are
    bit-for-bit the (a) outputs — the automaton operands are absent
    from their dispatches, not masked to identity."""
    rng = np.random.RandomState(52)
    reqs_kw = [
        (rng.randint(1, 128, 9).astype(np.int32),
         dict(max_new_tokens=10, eos_token_id=EOS)),
        (rng.randint(1, 128, 13).astype(np.int32),
         dict(max_new_tokens=10, temperature=0.7, top_k=8, seed=5)),
    ]
    outs = {}
    for name, cfg_kw in (
            ("off", dict(multi_step=4)),
            ("on", dict(multi_step=4, structured=StructuredConfig()))):
        _, _, reqs = _serve(tiny, reqs_kw, cfg_kw=cfg_kw)
        outs[name] = [list(map(int, r.generated)) for r in reqs]
    assert outs["off"] == outs["on"]


def test_constrained_k_partition_bit_exact(tiny):
    """One k=8 constrained group == eight k=1 groups token-for-token
    (greedy + seeded rows): the in-scan FSM advance carries exactly
    the state the host walk re-derives between dispatches, so group
    size is a pure throughput knob under grammars too."""
    rng = np.random.RandomState(53)
    reqs_kw = [
        (rng.randint(1, 128, 9).astype(np.int32),
         dict(max_new_tokens=16, eos_token_id=EOS,
              response_format=FMT_BOUNDED)),                     # greedy
        (rng.randint(1, 128, 7).astype(np.int32),
         dict(max_new_tokens=16, eos_token_id=EOS,
              response_format=FMT_BOUNDED, temperature=0.9, top_k=0, seed=7)),
    ]
    st = StructuredConfig()
    _, _, r1 = _serve(tiny, reqs_kw,
                      cfg_kw=dict(multi_step=1, structured=st))
    _, _, r8 = _serve(tiny, reqs_kw,
                      cfg_kw=dict(multi_step=8, structured=st))
    auto = _auto(BOUNDED)
    for a, b in zip(r1, r8):
        assert list(a.generated) == list(b.generated)
        assert auto.accepts(a.generated, eos_id=EOS)


def test_constrained_d2h_ledger_identical_and_guard_clean(tiny):
    """Zero added host round trips: a constrained multi-step serve
    makes EXACTLY as many explicit d2h fetches as the same traffic
    unconstrained (the FSM state rides the scan carry, the host walks
    its mirror), and the whole constrained loop runs clean under the
    jax transfer guard at 'disallow'."""
    rng = np.random.RandomState(54)
    p1 = rng.randint(1, 128, 9).astype(np.int32)
    p2 = rng.randint(1, 128, 12).astype(np.int32)
    guarded = dict(multi_step=4, structured=StructuredConfig(),
                   transfer_guard="disallow")
    fsm_kw = dict(max_new_tokens=12, eos_token_id=EOS,
                  response_format=FMT_BOUNDED)
    fetches = {}
    for name, kw in (
            ("plain", dict(max_new_tokens=12, eos_token_id=None)),
            ("fsm", fsm_kw)):
        _, eng, _ = _serve(tiny, [(p1, dict(kw))], cfg_kw=guarded)
        fetches[name] = eng.profile["d2h_fetches"]
    # the constrained row finishes EARLIER (the bounded grammar ends
    # inside two groups) so fewer groups run; per-dispatch cost must not
    # grow
    assert fetches["fsm"] <= fetches["plain"], fetches
    # a mixed batch dispatches its constrained and unconstrained rows as
    # separate groups (_burst_groups); that loop runs guard-clean too
    _serve(tiny, [(p1, dict(fsm_kw)), (p2, dict(max_new_tokens=12))],
           cfg_kw=guarded)


def test_spec_compose_prefiltered_drafts_and_uplift(tiny):
    """Composition with speculative verify: `filter_draft` truncates a
    draft at its first out-of-grammar token, and a grammar-valid draft
    through a single-allowed-token state is FORCE-accepted by the
    constrained greedy target (the masked argmax has one choice) —
    the acceptance-uplift mechanism on templated traffic."""
    auto = _auto(r"(ab)+c")
    st_a = auto.walk(0, _toks("a"))            # after 'a': only 'b'
    kept = filter_draft(_toks("bab"), auto, st_a)
    assert list(kept) == _toks("bab")
    kept = filter_draft(_toks("bxb"), auto, st_a)
    assert list(kept) == _toks("b")            # truncated at 'x'
    assert list(filter_draft([], auto, st_a)) == []

    model, params = tiny
    eng = _engine(model, params)
    rng = np.random.RandomState(55)
    p = rng.randint(1, 128, 9).astype(np.int32)
    out = eng.put([0], [p], decode=False)
    while 0 not in out:
        out.update(eng.step(decode=False))
    eng.state.seqs[0].generated.append(ord("a"))
    res = eng.decode_burst_step(
        uids=[0], mode="per_row", temperature={0: 0.0}, top_k={0: 0},
        drafts={0: _toks("b")}, draft_span=2,
        max_tokens={0: 40},
        fsm=auto, fsm_states={0: st_a}, fsm_eos={0: EOS})
    toks, n_drafted, n_accepted = res[0]
    assert n_drafted == 1 and n_accepted == 1  # forced accept
    assert int(toks[0]) == ord("b")


def test_spec_constrained_serve_end_to_end(tiny):
    """A speculative + structured serve emits only grammar-valid
    chains and counts filtered draft tokens (grammar_drafts_filtered)
    when the lookup proposes out-of-grammar continuations."""
    auto = _auto(r"(ab)+c")
    rng = np.random.RandomState(56)
    p = rng.randint(1, 128, 16).astype(np.int32)
    loop, eng, (rc, rb) = _serve(
        tiny,
        [(p, dict(max_new_tokens=24, eos_token_id=EOS,
                  response_format=FMT)),
         (rng.randint(1, 128, 10).astype(np.int32),
          dict(max_new_tokens=10))],
        cfg_kw=dict(decode_burst=4, structured=StructuredConfig(),
                    speculative=SpeculativeConfig()))
    assert rc.state is RequestState.DONE
    assert auto.accepts(rc.generated, eos_id=EOS), rc.generated
    assert eng.state.seqs == {} and eng.free_blocks == 32


def test_submit_validation(tiny):
    model, params = tiny
    eng = _engine(model, params)
    p = np.arange(1, 9, dtype=np.int32)
    loop_off = ServeLoop(eng, ServingConfig(), clock=FakeClock())
    with pytest.raises(AdmissionError, match="structured"):
        loop_off.submit(p, max_new_tokens=4, eos_token_id=EOS,
                        response_format=FMT)
    eng2 = _engine(model, params)
    loop_on = ServeLoop(eng2,
                        ServingConfig(structured=StructuredConfig()),
                        clock=FakeClock())
    with pytest.raises(AdmissionError, match="eos"):
        loop_on.submit(p, max_new_tokens=4, response_format=FMT)
    with pytest.raises(AdmissionError):
        loop_on.submit(p, max_new_tokens=4, eos_token_id=EOS,
                       response_format="(ab)+c")   # not a ResponseFormat
    with pytest.raises(AdmissionError):
        loop_on.submit(p, max_new_tokens=4, eos_token_id=EOS,
                       response_format=ResponseFormat.regex("(unbal"))
    assert loop_on.telemetry.counters["rejected_invalid"] >= 3


def test_grammar_cache_stats_in_telemetry(tiny):
    """grammar/* monitoring: summary() carries the cache stats,
    prometheus_text() the counters, the monitor schema registers every
    grammar/ tag publish() emits, and the structured-off loop
    publishes a byte-identical tag set."""
    from deepspeed_tpu.monitor.schema import unregistered
    from deepspeed_tpu.serving.telemetry import ServingTelemetry

    class _Sink:
        def __init__(self):
            self.tags = []

        def write_events(self, events):
            self.tags.extend(t for t, _, _ in events)

    model, params = tiny
    rng = np.random.RandomState(57)
    p = rng.randint(1, 128, 8).astype(np.int32)
    sink = _Sink()
    eng = _engine(model, params)
    loop = ServeLoop(eng, ServingConfig(structured=StructuredConfig(),
                                        multi_step=4),
                     clock=FakeClock(), monitor=sink)
    loop.submit(p, max_new_tokens=8, eos_token_id=EOS,
                response_format=FMT)
    loop.run_until_idle(max_steps=100)
    loop.telemetry.publish()
    assert unregistered(sink.tags) == []
    assert any(t.startswith("grammar/") for t in sink.tags)
    assert "grammar_cache" in loop.telemetry.summary()
    assert "grammar_hits_total" in loop.telemetry.prometheus_text()
    # off path: no grammar/* tags, summary key-set parity
    off = ServingTelemetry()
    assert "grammar_cache" not in off.summary()


# -- per-tenant KV-arena quota satellite ------------------------------------

def test_kv_block_quota_defers_without_starving(tiny):
    """`TenancyConfig.kv_block_quota`: tenant a's second request waits
    while its first holds the quota'd blocks — but tenant b admits
    right past it (quota refusals must not trip the fair scheduler's
    no-skip-ahead stop) — and the deferred request completes once the
    blocks free.  quota_deferred counts both globally and per
    tenant."""
    model, params = tiny
    rng = np.random.RandomState(58)
    eng = _engine(model, params)
    loop = ServeLoop(
        eng,
        ServingConfig(audit_blocks=True,
                      tenancy=TenancyConfig(enabled=True,
                                            kv_block_quota={"a": 3})),
        clock=FakeClock())
    # each request: ceil((8 + 8)/8) = 2 blocks -> a's second must wait
    mk = lambda: rng.randint(1, 128, 8).astype(np.int32)
    ra1 = loop.submit(mk(), max_new_tokens=8, tenant="a")
    ra2 = loop.submit(mk(), max_new_tokens=8, tenant="a")
    rb = loop.submit(mk(), max_new_tokens=8, tenant="b")
    loop.step()
    assert ra1.state is not RequestState.QUEUED
    assert ra2.state is RequestState.QUEUED          # over quota
    assert rb.state is not RequestState.QUEUED       # NOT starved
    assert loop.telemetry.counters["quota_deferred"] >= 1
    assert loop.telemetry.tenants["a"]["quota_deferred"] >= 1
    assert "b" not in loop.telemetry.tenants \
        or loop.telemetry.tenants["b"].get("quota_deferred", 0) == 0
    loop.run_until_idle(max_steps=200)
    for r in (ra1, ra2, rb):
        assert r.state is RequestState.DONE
    assert eng.state.seqs == {} and eng.free_blocks == 32


def test_kv_block_quota_off_is_inert(tiny):
    """No quota map = the pre-quota admission path: identical outputs
    and zero quota_deferred."""
    model, params = tiny
    rng = np.random.RandomState(59)
    reqs_kw = [(rng.randint(1, 128, 8).astype(np.int32),
                dict(max_new_tokens=6, tenant=t))
               for t in ("a", "a", "b")]
    outs = {}
    for name, ten in (("off", TenancyConfig(enabled=True)),
                      ("quota", TenancyConfig(enabled=True,
                                              kv_block_quota={"c": 1}))):
        eng = _engine(model, params)
        loop = ServeLoop(eng, ServingConfig(tenancy=ten),
                         clock=FakeClock())
        reqs = [loop.submit(p, **kw) for p, kw in reqs_kw]
        loop.run_until_idle(max_steps=100)
        outs[name] = [list(map(int, r.generated)) for r in reqs]
        assert loop.telemetry.counters["quota_deferred"] == 0
    assert outs["off"] == outs["quota"]


# -- workload generator structured dimension --------------------------------

def test_workload_structured_dimension_and_off_parity():
    from deepspeed_tpu.serving.observatory.workload import \
        WorkloadGenerator

    base = dict(vocab_size=128, seed=3, num_tenants=2, adapter_frac=0.3)
    g_off = WorkloadGenerator(**base)
    g_zero = WorkloadGenerator(structured_frac=0.0, **base)
    for x, y in zip(g_off.generate(24), g_zero.generate(24)):
        assert x.arrival_s == y.arrival_s
        assert (x.prompt == y.prompt).all()
        assert x.tenant == y.tenant and x.adapter_id == y.adapter_id
        assert x.response_format is None and y.response_format is None

    fmts = [ResponseFormat.regex("(ab)+c"), ResponseFormat.regex("x+")]
    g_on = WorkloadGenerator(structured_frac=0.5,
                             structured_formats=fmts, **base)
    items = g_on.generate(40)
    n_con = sum(1 for it in items if it.response_format is not None)
    assert 0 < n_con < 40
    assert {it.response_format for it in items
            if it.response_format is not None} <= set(fmts)
    # the structured dimension leaves every base draw untouched
    for x, y in zip(g_off.generate(24), items[:24]):
        assert x.arrival_s == y.arrival_s
        assert (x.prompt == y.prompt).all()
    # prefix-stable like every other stream
    for x, y in zip(items[:15], g_on.generate(15)):
        assert x.response_format == y.response_format
    assert g_on.describe()["structured_frac"] == 0.5
    with pytest.raises(ValueError, match="structured_formats"):
        WorkloadGenerator(structured_frac=0.2, **base)
    with pytest.raises(ValueError, match="structured_frac"):
        WorkloadGenerator(structured_frac=1.5, structured_formats=fmts,
                          **base)


# -- HLO structure rider ----------------------------------------------------

def test_hlo_check_constrained_multistep_cpu():
    """The constrained-multi-step structural lock rides tier-1 on the
    CPU compiler: while census unchanged vs the unconstrained program
    and k-invariant, single packed d2h root, donated-arena aliasing,
    no host callback."""
    from deepspeed_tpu.benchmarks.tpu_hlo_check import (
        check_constrained_multistep)
    out = check_constrained_multistep(platform="cpu")
    assert out["whiles_k8"] == out["whiles_k16"] == out["whiles_plain"]
    assert out["root_elems"] == 1 + out["aliased_outputs"]
