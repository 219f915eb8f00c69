"""The static-kind stack (window layers with rope, global layers without a
position encoding, a router on the layer's input, ReLU-gated experts) on the
normal serving path (`build_engine("smallthinker", ...)`), at a small size
on the CPU, against the benchmark's plain float32 reference
(`benchmark/references/smallthinker.py`, which imports nothing of the
program): hidden 64, 4/2 heads of 32, 8 layers (G W W W) x 2, 8 experts of
32, top-2, a window of 20 tokens over blocks of 8 (a window row holds at most
ceil(20 / 8) + 1 = 4 window-kind blocks).

Tolerance of every comparison with the reference: both sides are float32
and differ in the order of their reductions only (paged against dense
attention, grouped against per-expert matmuls); readings are 6e-6 on logits
that reach 4.6, the limit is 1e-4, and the three broken references below
(and the int8 control) move a 180-token row's logits by 2.6 to 5.5.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import harness
from deepspeed_tpu.config.config import PreemptionConfig, ServingConfig
from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                        build_engine, hybrid_ops, ragged_ops)
from deepspeed_tpu.inference.v2.blocked_allocator import KindCounts
from deepspeed_tpu.inference.v2.ragged_manager import (KIND_NAMES,
                                                       DSStateManager)
from deepspeed_tpu.models import Transformer, get_model_config
from deepspeed_tpu.serving import RequestState, ServeLoop
from deepspeed_tpu.serving.scheduler import AdmissionError

from test_grouped_matmul import moe_through_the_kernel
from test_serving import FakeClock

pytestmark = pytest.mark.serving

REF = harness.load_module(harness.BENCH_DIR, "references", "smallthinker")
CFG = harness.load_json(harness.ROOT, "tests", "benchmark", "data",
                        "configs", "smallthinker-tiny.json")
S = REF.sizes(CFG)
SEED, TOL = 5, 1e-4
F32 = jnp.float32
BS, W = CFG["program"]["engine"]["block_size"], S.window
PER_ROW = -(-W // BS) + 1                  # window-kind blocks a row holds


def engine(engine_kw=None, **cfg_kw):
    prog = CFG["program"]
    return build_engine(
        prog["arch"], prog["size"], dtype=F32,
        params=REF.make_params(SEED, S, F32),
        engine_config=RaggedInferenceEngineConfig(
            **dict(prog["engine"], **(engine_kw or {}))),
        **dict(prog["overrides"], **cfg_kw))


def tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, S.vocab, n).astype(np.int32)


def ref_logits(toks, control=None):
    """[len(toks), V]: the reference's full forward over the whole row
    (padded at the end to one of a few widths: causal attention keeps the
    padding out of every real position, and a width is a compile)."""
    row = np.zeros((1, -(-len(toks) // 64) * 64), np.int32)
    row[0, :len(toks)] = toks
    return np.asarray(REF.logits(SEED, row, S, F32,
                                 precision=control))[0, :len(toks)]


def serve(eng, toks, n, uid=1):
    """Prefill `toks[:n]`, then feed `toks[n:]` one decode step each:
    [len(toks) - n + 1, V] program logits; the window kind's lease is
    checked at every step."""
    out = eng.put([uid], [toks[:n]])
    while uid not in out:
        out.update(eng.step())
    rows = [np.asarray(out[uid])]
    for t in toks[n:]:
        rows.append(np.asarray(eng.put([uid], [np.array([t])])[uid]))
        held = eng.state.seqs[uid].window_blocks
        assert len(held) <= PER_ROW
        assert min(held) >= max(0, eng.state.seqs[uid].seen_tokens - W) // BS
    return np.stack(rows)


# one chunk (a fresh prompt is a chunk at position 0), three chunks of 64,
# and a prompt longer than a chunk AND several windows: 150 tokens are 7.5
# windows, 180 with the decode steps 9
@pytest.mark.parametrize("engine_kw", [
    pytest.param(dict(prefill_chunk_size=256, max_blocks_per_seq=32,
                      max_prefill_tokens_per_step=256, num_blocks=64),
                 id="fresh"),
    pytest.param({}, id="chunked"),
])
def test_prefill_then_decode_matches_the_reference(engine_kw):
    """Prefill writes both kinds of cache, decode reads the global kind
    whole and the window kind from the window's first block on; every
    step's logits are the reference's full forward over the whole row."""
    n, new = 150, 30
    toks = tokens(n + new)
    eng = engine(engine_kw)
    got = serve(eng, toks, n)
    want = ref_logits(toks)[n - 1:]
    assert np.abs(got - want).max() < TOL and want.std() > 0.5
    Lg, Lw = hybrid_ops.kind_layers(eng.cfg)
    assert (Lg, Lw) == (2, 6)
    assert set(eng.arena) == {"gk", "gv", "wk", "wv", "moe_counts"}
    assert eng.arena["gk"].shape[0] == Lg and eng.arena["wk"].shape[0] == Lw
    # the row holds every global-kind block and the window's of the other
    d = eng.state.seqs[1]
    assert len(d.blocks) == -(-(n + new) // BS) == 23
    assert len(d.window_blocks) <= PER_ROW == 4
    audit = eng.audit_blocks()
    assert audit["live"] == 23 and audit["window_live"] == len(d.window_blocks)
    eng.flush(1)
    audit = eng.audit_blocks()
    assert audit["free"] == audit["total"]
    assert audit["window_free"] == audit["window_total"]


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_broken_reference_fails_the_comparison(control):
    """A reference with one mistake (window layers attending to every key,
    rope on the global layers, the router reading the post-attention norm)
    or computed on 8-bit grids lies far outside the tolerance the program
    is held to, once the row is longer than the window."""
    toks = tokens(60, seed=2)
    got = np.asarray(engine().put([1], [toks])[1])
    assert np.abs(got - ref_logits(toks)[-1]).max() < TOL
    assert np.abs(got - ref_logits(toks, control)[-1]).max() > 1000 * TOL


def test_chunks_of_one_row_in_one_step_keep_what_the_earlier_ones_read():
    """A budget of four chunks: a 150-token prompt is three chunks of ONE
    program, and the blocks its third chunk no longer needs are what its
    second still reads there, so they are not handed to the prompt planned
    after it (which would write its own keys over them in that program)."""
    eng = engine(dict(max_prefill_tokens_per_step=256))
    a, b = tokens(150, seed=12), tokens(60, seed=13)
    out = eng.put([1, 2], [a, b])
    while not (1 in out and 2 in out):
        out.update(eng.step())
    assert np.abs(np.asarray(out[1]) - ref_logits(a)[-1]).max() < TOL
    assert np.abs(np.asarray(out[2]) - ref_logits(b)[-1]).max() < TOL
    eng.audit_blocks()


def test_inside_the_window_a_window_layer_is_a_full_one():
    """What the controls cannot see: a row shorter than the window."""
    toks = tokens(W - 2, seed=3)
    assert np.abs(ref_logits(toks) - ref_logits(toks, "window_as_full")
                  ).max() < TOL


def test_generate_and_the_compiled_bursts_give_the_reference_chain():
    """`generate` (compiled bursts through `decode_tokens`) and
    `decode_multi_step` lease both kinds ahead of the burst and give the
    per-step greedy chain."""
    eng = engine()
    p = tokens(45, seed=5)
    chain = []
    for _ in range(12):
        chain.append(int(ref_logits(np.concatenate([p, chain]).astype(
            np.int32))[-1].argmax()))
    assert list(eng.generate(p, max_new_tokens=12)) == chain
    eng.put([7], [p])
    eng.state.seqs[7].generated.append(chain[0])     # the pending token
    assert list(eng.decode_multi_step([7], k=6)[7]) == chain[1:7]
    eng.flush(7)
    audit = eng.audit_blocks()
    assert audit["free"] == audit["total"]
    assert audit["window_free"] == audit["window_total"]


def test_padded_chunk_slots_cost_passes_only_for_their_real_tokens(
        monkeypatch):
    """More rows than `ROW_TILE` (every program of the cell: 12,288-slot
    chunks against 4,096): the real tokens go in front and the token-wise
    work takes them a tile at a time; the result is the untiled program's,
    and the reference's; padded slots route nothing."""
    monkeypatch.setattr(hybrid_ops, "ROW_TILE", 32)
    # (its own cfg: a jitted program is cached by its static cfg)
    eng = engine(max_seq_len=504)
    n, new = 90, 2
    toks = tokens(n + new, seed=4)
    got = serve(eng, toks, n)
    assert np.abs(got - ref_logits(toks)[n - 1:]).max() < TOL
    counts = eng.drain_moe_counts()
    # 90 prompt tokens (chunks of 64 and 26 real tokens in 64 slots) and 2
    # decode steps, 8 layers, top-2
    assert counts["picks"] == counts["local_rows"] == (n + new) * 8 * 2
    assert counts["zero_picks"] == 0


@pytest.mark.kernels
@pytest.mark.parametrize("drawn", [0.0, 4.0],
                         ids=["as_routed", "one_expert_takes_most"])
def test_the_experts_through_the_kernel_are_the_ragged_dots(monkeypatch,
                                                            drawn):
    """`_moe` with the grouped-matmul kernel (the chip's path, interpreted)
    on a later layer of this stack: ReLU gates, the router on the layer's
    input, every expert held here (no pass but the first); as it routes,
    and with the router drawn to one expert, whose rows then span tiles."""
    from deepspeed_tpu.inference.v2 import expert_ffn
    eng = engine()
    cfg, li, T = eng.cfg, 5, 96
    lp = jax.tree.map(lambda a: a[li], eng.params["layers"])
    lp = dict(lp, moe_gate=lp["moe_gate"].at[:, 2].add(drawn))
    experts = {n: w.reshape((-1,) + w.shape[2:])
               for n, w in eng.params["experts"].items()}
    h, x = jax.random.normal(jax.random.PRNGKey(1), (2, T, S.hidden))
    counts, passes = moe_through_the_kernel(
        monkeypatch, cfg, lp, experts, li, h, jnp.arange(T) < 90, TOL,
        router_in=jnp.abs(x) if drawn else x)
    assert passes == 1 and counts["zero_picks"] == 0
    assert counts["local_rows"] == counts["picks"] == 90 * cfg.moe_top_k
    assert expert_ffn.local_rows_cap(T * cfg.moe_top_k, cfg.local_experts,
                                     cfg.moe_experts) == T * cfg.moe_top_k
    if drawn:
        assert counts["busiest_rows"] > 80


def greedy_chain_ok(req):
    """Every generated token is the reference's best given what came
    before it."""
    seq = np.concatenate([req.prompt, req.generated[:-1]]).astype(np.int32)
    want = ref_logits(seq)[len(req.prompt) - 1:].argmax(-1)
    return list(want) == list(req.generated)


def test_the_serve_loop_serves_rows_side_by_side_and_counts_both_kinds():
    eng = engine()
    loop = ServeLoop(eng, ServingConfig(audit_blocks=True))
    assert eng.kind_names == KIND_NAMES == ("global", "window")
    reqs = [loop.submit(tokens(n, seed=n), max_new_tokens=m)
            for n, m in ((90, 12), (33, 20), (140, 8), (70, 16), (20, 30))]
    loop.run_until_idle()
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(greedy_chain_ok(r) for r in reqs)
    c = loop.telemetry.counters
    # block x layer units: 2 global layers hold every block of a row, 6
    # window layers at most 4; one kind over 8 layers would hold all
    assert 0 < c["kv_blocks_held"] < c["kv_blocks_full_cache"]
    assert c["kv_window_released"] > 0
    assert c["moe_router_calls"] > 0 and c["moe_zero_picks"] == 0
    assert c["moe_picks"] == c["moe_local_rows"]      # every expert is held
    free = eng.free_blocks
    assert isinstance(free, KindCounts)
    assert tuple(free) == (eng.state.allocator.num_blocks,
                           eng.state.window_allocator.num_blocks)


def test_a_row_preempted_and_resumed_decodes_the_same_tokens():
    """The victim's lifetime needs 20 of the 24 global-kind blocks, so the
    urgent request cannot be admitted beside it: it ages, the victim is
    flushed (both kinds freed) and comes back as a prompt of its prompt
    and its tokens so far, prefilled through chunks into a fresh lease."""
    eng = engine(dict(num_blocks=12, max_seqs=2))
    assert tuple(eng.free_blocks) == (24, 8)
    clock = FakeClock()
    loop = ServeLoop(eng, ServingConfig(
        audit_blocks=True, preemption=PreemptionConfig(
            enabled=True, ttft_slo_s=2.0, urgency_fraction=0.5)),
        clock=clock)
    low = loop.submit(tokens(60, seed=8), max_new_tokens=100, priority=1)
    for _ in range(6):
        loop.step()
        clock.advance(1.0)
    assert low.state is RequestState.DECODE
    high = loop.submit(tokens(40, seed=9), max_new_tokens=8, priority=0)
    steps = 0
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
        steps += 1
        assert steps < 400
    c = loop.telemetry.counters
    assert c["preemptions"] >= 1 and low.preemptions >= 1
    assert c["admit_blocked_by_kind_global"] >= 1
    assert c["admit_blocked_by_kind_window"] == 0
    assert low.state is high.state is RequestState.DONE
    assert len(low.generated) == 100 and greedy_chain_ok(low)
    assert greedy_chain_ok(high)
    audit = eng.audit_blocks()
    assert audit["free"] == 24 and audit["window_free"] == 8


def test_admission_waits_for_the_window_kind_when_that_is_short():
    """A pool of 4 window-kind blocks is one row's: a second request
    waits for them though the global kind has room, and is served once
    the first has finished."""
    eng = engine(dict(num_blocks=6, max_seqs=3))
    assert tuple(eng.free_blocks) == (12, 4)
    loop = ServeLoop(eng, ServingConfig(audit_blocks=True))
    a = loop.submit(tokens(26, seed=1), max_new_tokens=6)
    b = loop.submit(tokens(26, seed=2), max_new_tokens=6)
    loop.step()
    assert a.state is not RequestState.QUEUED
    assert b.state is RequestState.QUEUED
    c = loop.telemetry.counters
    assert c["admit_blocked_by_kind_window"] >= 1
    assert c["admit_blocked_by_kind_global"] == 0
    loop.run_until_idle()
    assert a.state is b.state is RequestState.DONE
    assert greedy_chain_ok(a) and greedy_chain_ok(b)
    # the per-sequence ceiling is the table's (24 entries of 8 tokens),
    # whichever kind's
    assert eng.max_tokens_per_seq == 192
    with pytest.raises(AdmissionError, match="192"):
        loop.submit(tokens(190, seed=3), max_new_tokens=8)


# ----------------------------------------------------------------------
# the ledger alone
# ----------------------------------------------------------------------
def manager(window_blocks=8, **kw):
    return DSStateManager(**dict(dict(num_blocks=30, block_size=8,
                                      max_blocks_per_seq=20, max_seqs=3),
                                 **kw), window=(20, window_blocks))


def test_a_window_row_holds_the_windows_blocks_and_hands_the_rest_back():
    m = manager()
    d = m.create(1, np.zeros(70, np.int32))
    # a 32-token chunk at position 0: its queries need keys 0..31
    m.ensure_capacity(d, 32, first_query=0)
    assert sorted(d.window_blocks) == [1, 2, 3] and len(d.blocks) == 4
    # (entry 0 lies behind the window of the next query, at 32: never leased)
    d.seen_tokens = 32
    m.release_behind(d, 32)
    # the next chunk, 32..63: its first query (32) still sees key 13
    m.ensure_capacity(d, 64, first_query=32)
    assert sorted(d.window_blocks) == [1, 2, 3, 5, 6, 7]
    d.seen_tokens = 64
    m.release_behind(d, 64)
    assert sorted(d.window_blocks) == [5, 6, 7]
    released = m.window_released
    assert released == 3
    # decode steps: one block in, one out as the window passes a boundary
    most = 0
    for pos in range(64, 120):
        m.ensure_capacity(d, pos + 1)
        most = max(most, len(d.window_blocks))
        assert min(d.window_blocks) == max(0, pos - 20 + 1) // 8
        assert max(d.window_blocks) == pos // 8
        d.seen_tokens = pos + 1
    assert most == 4 == -(-20 // 8) + 1
    assert len(d.blocks) == 15 and m.window_released > released
    table = m.block_table(d)
    assert table.shape == m.table_shape == (2, 20)
    assert (table[0, :15] >= 0).all() and (table[0, 15:] == -1).all()
    live = sorted(d.window_blocks)
    assert (table[1, live] >= 0).all()
    assert (np.delete(table[1], live) == -1).all()
    audit = m.audit()
    assert audit["live"] == 15 and audit["window_live"] == len(live)
    assert audit["window_free"] + audit["window_live"] == audit["window_total"]
    m.flush(1)
    audit = m.audit()
    assert audit["free"] == 30 and audit["window_free"] == 8


def test_released_window_blocks_are_leased_again():
    """Two rows over a pool of 8: each walks 200 positions, which takes 25
    window-kind blocks a row, out of the 8."""
    m = manager(num_blocks=60, max_blocks_per_seq=30)
    rows = [m.create(u, np.zeros(8, np.int32)) for u in (1, 2)]
    seen = set()
    for pos in range(200):
        for d in rows:
            m.ensure_capacity(d, pos + 1)
            seen.update(d.window_blocks.values())
        held = [b for d in rows for b in d.window_blocks.values()]
        assert len(held) == len(set(held)) <= 8
        m.audit()
    assert seen == set(range(8)) and m.window_released >= 2 * (25 - 4)


def test_a_chunk_is_cut_to_what_the_pool_spares_beyond_the_rows_shares():
    """A pool of exactly two rows' steady shares (2 x 4), one row decoding
    at its share: the other's prompt chunks (32 tokens against a window of
    20: a chunk holds the 3 blocks it reads beside the 4 it writes) are cut
    so that the decoding row always finds its next block, and the prompt
    still gets through."""
    m = manager(window_blocks=8, num_blocks=80, max_blocks_per_seq=40)
    assert m.window_row_blocks == 4
    dec, pre = m.create(1, np.zeros(8, np.int32)), m.create(
        2, np.zeros(200, np.int32))
    m.ensure_capacity(dec, 60, first_query=0)
    dec.seen_tokens = 60
    steps, cut = 0, 0
    while pre.seen_tokens < 200:
        start = pre.seen_tokens
        n = m.chunk_room(pre, start, min(32, 200 - start))
        assert 0 < n <= 32                 # never stuck
        cut += n < min(32, 200 - start)
        m.ensure_capacity(pre, start + n, first_query=start)
        # the decoding row's step, after it in the same engine step
        m.ensure_capacity(dec, dec.seen_tokens + 1)
        dec.seen_tokens += 1
        pre.seen_tokens = start + n
        m.release_behind(pre, pre.seen_tokens)
        assert len(dec.window_blocks) <= 4
        m.audit()
        steps += 1
    assert cut > 0 and steps > -(-200 // 32)
    # alone in the pool the same prompt takes whole chunks
    m.flush(1)
    m.flush(2)
    pre = m.create(3, np.zeros(200, np.int32))
    for start in range(0, 200, 32):
        assert m.chunk_room(pre, start, min(32, 200 - start)) \
            == min(32, 200 - start)
        m.ensure_capacity(pre, min(start + 32, 200), first_query=start)
        pre.seen_tokens = min(start + 32, 200)
        m.release_behind(pre, pre.seen_tokens)
    one = DSStateManager(num_blocks=10, block_size=8, max_blocks_per_seq=6,
                         max_seqs=2)
    assert one.chunk_room(one.create(1, np.zeros(8, np.int32)), 0, 40) == 40


def test_a_long_prompt_gets_through_a_window_pool_of_one_rows_share():
    """4 window-kind blocks in all, and a 150-token prompt in chunks of 64
    (whole, a chunk would hold 3 + 4): the engine cuts the chunks, leases
    never fail, and prefill and decode give the reference's logits."""
    eng = engine(dict(num_blocks=6, max_seqs=3, max_blocks_per_seq=24))
    assert tuple(eng.free_blocks) == (12, 4)
    n, new = 80, 10
    toks = tokens(n + new, seed=11)
    got = serve(eng, toks, n)
    assert np.abs(got - ref_logits(toks)[n - 1:]).max() < TOL
    eng.flush(1)
    audit = eng.audit_blocks()
    assert audit["free"] == 12 and audit["window_free"] == 4


def test_a_lease_that_cannot_be_met_takes_nothing_of_either_kind():
    m = manager(window_blocks=2)
    d = m.create(1, np.zeros(8, np.int32))
    with pytest.raises(RuntimeError):
        m.ensure_capacity(d, 30)              # 4 window-kind blocks of 2
    assert m.free_blocks == KindCounts((30, 2)) and not d.blocks
    m = manager(num_blocks=2)
    d = m.create(1, np.zeros(8, np.int32))
    with pytest.raises(RuntimeError):
        m.ensure_capacity(d, 30)              # 4 global-kind blocks of 2
    assert m.free_blocks == KindCounts((2, 8)) and not d.window_blocks
    m.audit()


def test_the_audit_names_a_leaked_window_block():
    m = manager()
    d = m.create(1, np.zeros(8, np.int32))
    m.ensure_capacity(d, 30)
    d.window_blocks.pop(min(d.window_blocks))    # forgotten, not freed
    with pytest.raises(RuntimeError, match="window-kind block conservation"):
        m.audit()


def test_a_one_kind_manager_counts_in_ints():
    m = DSStateManager(num_blocks=10, block_size=8, max_blocks_per_seq=6,
                       max_seqs=2)
    d = m.create(1, np.zeros(8, np.int32))
    m.ensure_capacity(d, 20)
    assert m.free_blocks == 7 and m.blocks_needed(20) == 3
    assert m.blocks_leased(d) == 3 and m.table_shape == (6,)
    assert m.block_table(d).shape == (6,) and "window_free" not in m.audit()


def test_kind_counts_compare_kind_by_kind():
    need, have = KindCounts((5, 4)), KindCounts((9, 3))
    assert need.short_of(have) and have.short_of(need)   # a partial order
    assert need.short_kind(have, KIND_NAMES) == "window"
    assert KindCounts((10, 3)).short_kind(have, KIND_NAMES) == "global"
    assert not KindCounts((9, 3)).short_of(have)
    assert have - need == (4, -1) and (have - need).floor0() == (4, 0)
    assert need + have == have + need == (14, 7)
    assert sum([need, have]) == (14, 7) and need + 1 == (6, 5)   # 0 + ...
    assert need.short_of(4) and not need.short_of(5)    # every kind has 5
    assert isinstance(need - 1, KindCounts)
    assert int(need) == 4 and int(have) == 3      # a gauge: the scarcest


@pytest.mark.parametrize("order", [
    lambda a, b: a > b, lambda a, b: a <= b, lambda a, b: a < 2,
    lambda a, b: max(a, b), lambda a, b: sorted([a, b])],
    ids=["gt", "le", "lt_int", "max", "sorted"])
def test_kind_counts_refuse_a_total_order(order):
    """`>` on counts that are each short of the other has no answer, and
    a tuple's own would compare the first kind alone."""
    with pytest.raises(TypeError, match="short_of"):
        order(KindCounts((5, 4)), KindCounts((9, 3)))


def test_the_pools_follow_the_byte_budget_the_kinds_and_the_rows():
    cfg = get_model_config("smallthinker", "21b-a3b", num_layers=8)
    # the cell: 3281 one-kind blocks of 8 layers; 33 rows' windows of 65
    assert hybrid_ops.window_blocks(cfg, 64) == 65
    nb_g, nb_w = hybrid_ops.kind_pools(cfg, 3281, 64, 32)
    assert (nb_g, nb_w) == (6689, 2145)
    assert nb_g * 2 + nb_w * 6 <= 3281 * 8 < (nb_g + 1) * 2 + nb_w * 6
    assert nb_g >= 32 * 209            # 32 rows of 13,376 tokens
    # a small budget: the window kind never takes more than half
    assert hybrid_ops.kind_pools(cfg, 100, 64, 32) == (202, 66)
    assert cfg.layer_period == ((0, 0), (4096, 1), (4096, 1), (4096, 1))
    assert cfg.window == 4096 and cfg.head_dim == 128


# ----------------------------------------------------------------------
# what is refused, and what stays as it was
# ----------------------------------------------------------------------
def _loop(eng, **kw):
    return ServeLoop(eng, ds.ServingConfig.from_dict(kw))


REFUSED = {
    "tensor_parallel": (ValueError, "tensor parallelism", lambda: engine(
        dict(tensor_parallel_size=2))),
    "fused_tp": (ValueError, "tensor parallelism", lambda: engine(
        dict(tensor_parallel_size=2, tp_collectives="fused"))),
    "expert_paging": (ValueError, "expert paging", lambda: build_engine(
        "smallthinker", "tiny", dtype=F32,
        serving_config=ds.ServingConfig.from_dict(
            {"moe": {"enabled": True}}))),
    "expert_paging_engine": (RuntimeError, "expert paging", lambda:
                             engine().enable_expert_paging(4)),
    "prefix_cache": (NotImplementedError, "two-kind cache", lambda: _loop(
        engine(), prefix_cache_blocks=4)),
    "kv_tiering": (NotImplementedError, "host KV tier", lambda: _loop(
        engine(), prefix_cache_blocks=4, host_cache_blocks=4)),
    "page_export": (NotImplementedError, "page export/import", lambda:
                    engine().read_kv_blocks([0])),
    "page_import": (NotImplementedError, "page export/import", lambda:
                    engine().write_kv_block(0, None, None)),
    "lora": (NotImplementedError, "LoRA adapters", lambda:
             engine().attach_lora({"a": None, "b": None})),
    "speculative": (ValueError, "draft-verify support", lambda: _loop(
        engine(), decode_burst=4,
        speculative={"mode": "prompt_lookup"})),
    "verify_span": (NotImplementedError, "speculative verify", lambda:
                    ragged_ops._span_core(engine().cfg, *[None] * 7)),
    "census_arena": (ValueError, "census rider", lambda: ragged_ops.init_arena(
        engine().cfg, 4, 16, moe_census=True)),
    "prefill_full": (NotImplementedError, "prefill_chunks alone", lambda:
                     ragged_ops.prefill_full(engine().cfg, *[None] * 6)),
    "loss_fn": (NotImplementedError, "no per-layer rope flag", lambda:
                Transformer(engine().cfg).loss_fn(None, None)),
    "forward_with_cache": (NotImplementedError, "forward_with_cache", lambda:
                           Transformer(engine().cfg).forward_with_cache(
                               None, None, None)),
    "initialize": (NotImplementedError, "initialize.*backward pass",
                   lambda: ds.initialize(model=Transformer(engine().cfg),
                                         config={"train_batch_size": 8})),
    "dense_reglu": (ValueError, "only in the static-kind stack", lambda:
                    get_model_config("llama", "tiny", activation="reglu")),
    "dense_head_dim": (ValueError, "only in the static-kind stack",
                       lambda: get_model_config("llama", "tiny",
                                                attn_head_dim=32)),
    "windows_only": (ValueError, "at least one full-attention layer", lambda:
                     get_model_config("smallthinker", "tiny",
                                      sliding_window_layers=(16,) * 8)),
    "flags_short": (ValueError, "one flag a layer", lambda: get_model_config(
        "smallthinker", "tiny", rope_layers=(0, 1, 1, 1))),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_a_path_that_cannot_serve_two_kinds_refuses(path):
    error, message, build = REFUSED[path]
    with pytest.raises(error, match=message):
        build()


def test_a_one_kind_model_keeps_its_arena_its_ledger_and_its_counts():
    """The static-kind branches are taken on `cfg.static_kinds`: a dense
    model's arena, tables, capability flags and block counts are what
    they were."""
    eng = build_engine("qwen2", "tiny", dtype=F32,
                       engine_config=RaggedInferenceEngineConfig(
                           num_blocks=8, block_size=16, max_seqs=2))
    assert set(eng.arena) == {"k", "v"} and not eng.cfg.static_kinds
    assert eng.kind_names is None and eng.free_blocks == 8
    assert eng.blocks_needed(33) == 3
    assert eng.state.window_allocator is None
    assert eng.state.table_shape == (eng.config.max_blocks_per_seq,)
    assert eng.supports_lora and eng.supports_draft_verify
    loop = ServeLoop(eng, ServingConfig())
    req = loop.submit(tokens(20), max_new_tokens=4)
    assert loop._blocks_needed(req) == 2
    loop.run_until_idle()
    c = loop.telemetry.counters
    assert c["kv_blocks_held"] == c["kv_blocks_full_cache"] == 0
    hyb = engine()
    assert not hyb.supports_lora and not hyb.supports_draft_verify
    assert not hyb.supports_moe and hyb.supports_moe_counts


def test_a_uniform_window_reaches_the_kernels_as_a_static_argument():
    """`mistral`'s one window for every layer: the paged decode kernel and
    the paged prefill kernel take it; a per-layer window that rides the
    layer scan as a traced scalar (no `rope_layers`) still does not."""
    from deepspeed_tpu.utils import device
    import dataclasses
    cfg = get_model_config("mistral", "tiny", dtype=F32)
    assert cfg.sliding_window and cfg.sliding_window_layers is None
    traced = dataclasses.replace(
        get_model_config("qwen2", "tiny", dtype=F32),
        sliding_window_layers=(0, 16) * (cfg.num_layers // 2))
    was = device.platform
    device.platform = lambda: "tpu"
    try:
        assert ragged_ops._use_paged_kernel(cfg, 64, 16, 1)
        assert ragged_ops._kernel_capable(engine().cfg, 64, 8, 1,
                                          static_windows=True)
        assert not ragged_ops._kernel_capable(traced, 64, 16, 1)
        assert not ragged_ops.prefill_full_supported(engine().cfg)
    finally:
        device.platform = was
