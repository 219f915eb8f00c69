"""The state-space parallel block (a Mamba-2 mixer beside grouped-query
attention in every layer, muP multipliers) on the normal serving path
(`build_engine("falcon_h1", ...)`), at a small size on the CPU, against
the benchmark's plain float32 reference (`benchmark/references/
falcon_h1.py`: the recurrence a token at a time, no chunks, no cache;
imports nothing of the program): hidden 64, 4/2 heads of 16, 2 layers, a
mixer of 4 heads of 16 in 2 groups, a state of 16, a convolution over 4
positions, scan chunks of 8 positions, blocks of 8 tokens.

Tolerance of every comparison with the reference: both sides are float32
and differ in the order of their reductions only (the chunked matmul form
against the recurrence, paged against dense attention); readings are 5e-6
on logits that spread by 1.0, the limit is 1e-4, and the four broken
references below (and the int8 control) move a row's logits by 0.02 to
1.5.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import harness
from deepspeed_tpu.config.config import ServingConfig
from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                        build_engine, ragged_ops, ssm_ops)
from deepspeed_tpu.inference.v2.families import family_of
from deepspeed_tpu.inference.v2.ragged_manager import DSStateManager
from deepspeed_tpu.models import Transformer, get_model_config
from deepspeed_tpu.ops import ssm as kernels
from deepspeed_tpu.serving import RequestState, ServeLoop

pytestmark = pytest.mark.serving

REF = harness.load_module(harness.BENCH_DIR, "references", "falcon_h1")
CFG = harness.load_json(harness.ROOT, "tests", "benchmark", "data",
                        "configs", "falcon-h1-tiny.json")
S = REF.sizes(CFG)
SEED, TOL = 5, 1e-4
F32 = jnp.float32


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


def ref_logits(toks, control=None, every=0):
    """[len(toks), V]: the reference's full forward over the whole row
    (padded at the end to one of a few widths: causality keeps the padding
    out of every real position, and a width is a compile)."""
    row = np.zeros((1, -(-len(toks) // 32) * 32), np.int32)
    row[0, :len(toks)] = toks
    return np.asarray(REF.logits(SEED, row, S, F32, precision=control,
                                 every=every))[0, :len(toks)]


def serve(eng, prompt, steps, uid=7):
    """Prefill `prompt`, then `steps` greedy decode steps through put():
    (logits [1 + steps, V] of the last prompt position and each decoded
    one, the whole token row)."""
    rows = eng.put([uid], [prompt])
    while uid not in rows:
        rows.update(eng.step())
    got, toks = [np.asarray(rows[uid])], list(prompt)
    for _ in range(steps):
        toks.append(int(np.argmax(got[-1])))
        got.append(np.asarray(eng.put([uid], [np.array(toks[-1:],
                                                       np.int32)])[uid]))
    return np.stack(got), np.array(toks, np.int32)


def state_of(eng, slot):
    return (np.asarray(eng.arena["ssm"][:, slot]),
            np.asarray(eng.arena["conv"][:, slot]))


# ----------------------------------------------------------------------
# against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n, engine_kw", [
    (21, {}),                                           # one fresh prefill
    (32, dict(full_prompt_prefill=False)),              # one whole chunk slot
    (37, dict(full_prompt_prefill=False)),              # two chunks, 32 + 5
    (48, dict(full_prompt_prefill=False,                # three, at the edges
              prefill_chunk_size=16, max_prefill_tokens_per_step=16)),
    (43, dict(full_prompt_prefill=False,                # three, off them
              prefill_chunk_size=16, max_prefill_tokens_per_step=16)),
    (29, dict(full_prompt_prefill=False,                # off the scan's 8
              prefill_chunk_size=12, max_prefill_tokens_per_step=12)),
], ids=["full", "one_chunk", "two_chunks", "three_at_edges",
        "three_off_edges", "off_scan_chunks"])
def test_prefill_then_decode_matches_the_reference(n, engine_kw):
    """Prefill (in one pass, or over chunk slots that hand the state and
    the convolution's tail on through the slot) and six decode steps
    through K/V blocks and the slot: the logits of the reference's full
    forward."""
    eng = engine(engine_kw)
    got, toks = serve(eng, tokens(n, seed=n), steps=6)
    want = ref_logits(toks)[n - 1:]
    assert want.std() > 0.5
    assert np.abs(got - want[:7]).max() < TOL
    audit = eng.audit_blocks()
    assert (audit["state_slots_live"], audit["state_slots_free"]) == (1, 3)


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_broken_reference_fails_the_comparison(control):
    """Each multiplier left out, the state not carried across a chunk's
    edge, the convolution's tail dropped there, the gate after the norm,
    and the int8 grid: each moves the logits by far more than the
    tolerance (the edges every 16 positions: the chunk slots of the
    engine above)."""
    toks = serve(engine(), tokens(40, seed=1), steps=4)[1]
    sound = ref_logits(toks)
    broken = ref_logits(toks, control, every=16)
    assert np.abs(broken[20:] - sound[20:]).max() > 200 * TOL


def test_a_split_prompt_equals_one_pass():
    """The same prompt through one fresh prefill and through three chunk
    slots: the slot ends up holding the same state and tail, and the first
    token's logits agree."""
    n = 43
    a, b = engine(), engine(dict(full_prompt_prefill=False,
                                 prefill_chunk_size=16,
                                 max_prefill_tokens_per_step=16))
    la, _ = serve(a, tokens(n, seed=2), steps=0)
    lb, _ = serve(b, tokens(n, seed=2), steps=0)
    assert np.abs(la - lb).max() < TOL
    sa, sb = state_of(a, a.state.seqs[7].state_slot), \
        state_of(b, b.state.seqs[7].state_slot)
    assert np.abs(sa[0]).max() > 1e-3
    np.testing.assert_allclose(sa[0], sb[0], atol=1e-5)
    np.testing.assert_allclose(sa[1], sb[1], atol=1e-5)


def test_a_program_plans_one_chunk_of_a_sequence():
    """A chunk starts from what the chunk before it left in the slot, so
    a budget of three chunks still advances a long prompt one chunk a
    step (another sequence's chunk rides along)."""
    eng = engine(dict(full_prompt_prefill=False, prefill_chunk_size=8,
                      max_prefill_tokens_per_step=24))
    eng.put([1, 2], [tokens(30, seed=3), tokens(20, seed=4)])
    assert (eng.state.seqs[1].seen_tokens, eng.state.seqs[2].seen_tokens) \
        == (8, 8)
    eng.step()
    assert (eng.state.seqs[1].seen_tokens, eng.state.seqs[2].seen_tokens) \
        == (16, 16)


# ----------------------------------------------------------------------
# slots
# ----------------------------------------------------------------------
def test_padded_rows_and_other_slots_stay_bit_for_bit():
    """A prefill and decode steps of one sequence touch its slot and
    nothing else: the other slots' state and tails (and the scratch
    slot's, on this dense path) are bit for bit what they were, padded
    rows of the programs included."""
    eng = engine()
    mark = jax.random.normal(jax.random.PRNGKey(1), eng.arena["ssm"].shape)
    eng.arena["ssm"] = mark.astype(F32)
    eng.arena["conv"] = jnp.ones_like(eng.arena["conv"]) * 0.25
    before = [state_of(eng, s) for s in range(5)]
    serve(eng, tokens(19, seed=5), steps=3)
    mine = eng.state.seqs[7].state_slot
    for s in range(5):
        after = state_of(eng, s)
        same = all(np.array_equal(x, y) for x, y in zip(before[s], after))
        assert same == (s != mine), s


def test_a_slot_leased_again_serves_as_a_fresh_engine_does():
    """No clearing pass: the next sequence's scan starts from zeros and
    overwrites whatever the slot held."""
    used = engine(dict(max_seqs=1))
    serve(used, tokens(33, seed=6), steps=5, uid=1)
    slot = used.state.seqs[1].state_slot
    used.flush(1)
    assert used.audit_blocks()["state_slots_free"] == 1
    assert np.abs(state_of(used, slot)[0]).max() > 1e-3   # not cleared
    again, _ = serve(used, tokens(27, seed=7), steps=4, uid=2)
    assert used.state.seqs[2].state_slot == slot
    fresh, _ = serve(engine(dict(max_seqs=1)), tokens(27, seed=7), steps=4,
                     uid=2)
    assert np.array_equal(again, fresh)


def test_rows_that_change_places_keep_their_state():
    """Three sequences decode together; the first finishes and is flushed,
    so the others move up a row in the decode batch (and a newcomer takes
    the freed slot): each still decodes its own reference chain."""
    eng = engine()
    prompts = {u: tokens(n, seed=u) for u, n in ((1, 12), (2, 23), (3, 17))}
    rows = eng.put(list(prompts), list(prompts.values()))
    while len(rows) < 3:               # 52 tokens under a budget of 32
        rows.update(eng.step())
    toks = {u: list(p) for u, p in prompts.items()}
    got = {u: [] for u in prompts}

    def feed(rows, live):
        for u in live:
            got[u].append(np.asarray(rows[u]))
            toks[u].append(int(np.argmax(got[u][-1])))
        return eng.put(live, [np.array(toks[u][-1:], np.int32)
                              for u in live])

    for _ in range(3):
        rows = feed(rows, [1, 2, 3])
    slots = {u: eng.state.seqs[u].state_slot for u in (1, 2, 3)}
    eng.flush(1)
    assert [d.uid for d in eng.state.decode_batch()] == [2, 3]
    prompts[4] = tokens(9, seed=4)
    toks[4], got[4] = list(prompts[4]), []
    new = eng.put([4], [prompts[4]], decode=False)
    assert eng.state.seqs[4].state_slot == slots[1]
    rows.update(new)
    for _ in range(3):
        rows = feed(rows, [2, 3, 4])
    for u in (2, 3, 4):
        want = ref_logits(np.array(toks[u], np.int32))
        n = len(prompts[u])
        assert np.abs(np.stack(got[u])
                      - want[n - 1:n - 1 + len(got[u])]).max() < TOL, u


def manager(**kw):
    return DSStateManager(**dict(dict(num_blocks=30, block_size=8,
                                      max_blocks_per_seq=20, max_seqs=3,
                                      state_slots=2), **kw))


def test_the_manager_leases_frees_and_audits_slots():
    m = manager()
    a, b = m.create(1, np.arange(5)), m.create(2, np.arange(5))
    assert {a.state_slot, b.state_slot} == {0, 1} and m.free_state_slots == 0
    with pytest.raises(RuntimeError, match="no free recurrent-state slot"):
        m.create(3, np.arange(5))
    assert 3 not in m.seqs
    assert m.audit()["state_slots_live"] == 2
    freed = a.state_slot
    m.flush(1)
    assert a.state_slot == -1
    assert m.audit() == dict(m.audit(), state_slots_free=1,
                             state_slots_live=1, state_slots_total=2)
    assert m.create(3, np.arange(5)).state_slot == freed


def test_the_audit_names_a_leaked_slot():
    m = manager()
    m.create(1, np.arange(5))
    m._free_state_slots.pop()
    with pytest.raises(RuntimeError, match="slot conservation"):
        m.audit()
    # a model without recurrent state has no slots to count
    plain = manager(state_slots=0)
    assert plain.create(1, np.arange(5)).state_slot == -1
    assert "state_slots_total" not in plain.audit()


def greedy_chain_ok(r):
    toks = np.concatenate([r.prompt, r.generated]).astype(np.int32)
    want = np.argmax(ref_logits(toks)[len(r.prompt) - 1:-1], -1)
    return np.array_equal(want, np.asarray(r.generated))


def test_the_serve_loop_serves_rows_side_by_side_and_gives_the_states_account():
    """Five requests through four rows and four slots under the default
    ServingConfig (one step in flight): every request's tokens are its
    reference chain, the step spans carry the state's account, nothing
    leaks."""
    from deepspeed_tpu.utils import spans
    seen = []
    orig = spans._Span.set_metadata

    def recording(self, **attrs):
        if self.name == "serve.step" and "state_slots" in attrs:
            seen.append(attrs)
        return orig(self, **attrs)
    eng = engine()
    loop = ServeLoop(eng, ServingConfig(audit_blocks=True))
    assert eng.recurrent_state and eng.kind_names is None
    reqs = [loop.submit(tokens(n, seed=n), max_new_tokens=m)
            for n, m in ((30, 12), (33, 20), (11, 8), (38, 16), (20, 30))]
    spans._Span.set_metadata = recording
    try:
        loop.run_until_idle()
    finally:
        spans._Span.set_metadata = orig
    assert all(r.state is RequestState.DONE for r in reqs)
    assert all(greedy_chain_ok(r) for r in reqs)
    decode = [a for a in seen if a["state_bytes_step"]]
    assert decode and all(a["state_slots"] == 4 for a in decode)
    assert max(a["state_slots_live"] for a in decode) == 4
    per_slot = ssm_ops.state_bytes_per_slot(eng.cfg)
    assert per_slot == 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    for a in decode:
        assert a["state_bytes_step"] % (2 * per_slot) == 0
        assert a["state_bytes_step"] < a["cache_bytes_step"]
    audit = eng.audit_blocks()
    assert (audit["state_slots_free"], audit["free"]) == (4, audit["total"])


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------
def recurrence(x, dt, a, b, c, h0):
    """The scan a token at a time, in float64."""
    R, S_, NH, P = x.shape
    rep = NH // b.shape[2]
    x, dt, a, h = (np.asarray(t, np.float64) for t in (x, dt, a, h0))
    b = np.repeat(np.asarray(b, np.float64), rep, axis=2)
    c = np.repeat(np.asarray(c, np.float64), rep, axis=2)
    ys = []
    for t in range(S_):
        h = np.exp(dt[:, t] * a)[..., None, None] * h + (
            dt[:, t][..., None, None] * b[:, t][..., None]
            * x[:, t][:, :, None, :])
        ys.append(np.einsum("rhnp,rhn->rhp", h, c[:, t]))
    return np.stack(ys, 1), h


def scan_operands(S_, seed=0, R=2, NH=4, P=16, G=2, N=16):
    rng = np.random.RandomState(seed)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (R, S_, NH))) * 10
    dt[1, S_ - 6:] = 0                     # a row padded at the end
    return tuple(jnp.asarray(t, F32) for t in (
        rng.randn(R, S_, NH, P), dt, -rng.uniform(1, 16, NH),
        rng.randn(R, S_, G, N), rng.randn(R, S_, G, N),
        rng.randn(R, NH, N, P)))


def kernel_scan(x, dt, a, b, c, h0, chunk):
    """`ssd_scan` (interpreted) with the initial states laid into an arena's
    slots in another order than the rows', a scratch slot behind them."""
    R = x.shape[0]
    slots = jnp.asarray(np.arange(R)[::-1].copy(), jnp.int32)
    state = jnp.zeros((2, R + 1) + h0.shape[1:], F32).at[1, slots].set(h0)
    y, state = kernels.ssd_scan(x, dt, a, b, c, state, 1, slots,
                                jnp.ones((R,), bool), chunk, interpret=True)
    assert not np.asarray(state[0]).any() and not np.asarray(state[1, R]).any()
    return y, state[1, slots]


@pytest.mark.parametrize("form", ["dense", "kernel"])
@pytest.mark.parametrize("length", [21, 32, 5])
def test_the_chunked_scan_is_the_recurrence(form, length):
    """Random steps and decay rates, an initial state, a length that is no
    multiple of the chunk of 8 (and one shorter than a chunk), one row
    padded: the chunked form, dense and as the Pallas kernel (interpreted,
    the states in place on an arena), gives the recurrence's outputs and
    final state; the padded positions leave the state as it was."""
    ops = scan_operands(length)
    scan = kernels.ssd_scan_reference if form == "dense" else kernel_scan
    y, h = scan(*ops, 8)
    want_y, want_h = recurrence(*ops)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), want_h, atol=2e-5)
    # row 1's last 6 positions are padding: its state after them is its
    # state before them
    _, h_cut = scan(*(t[:, :length - 6] if t.ndim > 1 and i in (0, 1, 3, 4)
                      else t for i, t in enumerate(ops)), 8)
    np.testing.assert_allclose(np.asarray(h)[1], np.asarray(h_cut)[1],
                               atol=1e-6)


def test_the_scan_kernel_starts_a_fresh_row_from_zeros_whatever_its_slot_holds():
    ops = scan_operands(19)
    x, dt, a, b, c, h0 = ops
    state = jnp.zeros((1, 3) + h0.shape[1:], F32).at[0, :2].set(h0)
    y, state = kernels.ssd_scan(x, dt, a, b, c, state, 0, jnp.arange(2),
                                jnp.asarray([True, False]), 8, interpret=True)
    want_y, want_h = recurrence(x, dt, a, b, c, h0.at[1].set(0.0))
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[0, :2]), want_h, atol=2e-5)


def test_the_update_kernel_is_the_dense_update_in_place():
    rng = np.random.RandomState(0)
    L, NS, B, NH, P, G, N = 2, 5, 3, 4, 16, 2, 16
    state = jnp.asarray(rng.randn(L, NS, NH, N, P), F32)
    slots = jnp.asarray([3, 0, 4], jnp.int32)        # 4: the scratch slot
    args = tuple(jnp.asarray(t, F32) for t in (
        rng.randn(B, NH, P),
        np.broadcast_to(rng.uniform(.5, 1, (B, NH, 1)), (B, NH, P)),
        rng.randn(B, G, N), rng.randn(B, G, N)))
    want_y, want = kernels.ssm_update_reference(state, 1, slots, *args)
    y, got = kernels.ssm_update(state, 1, slots, *args, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    untouched = np.asarray(got) == np.asarray(state)
    assert untouched[0].all() and untouched[1, [1, 2]].all()
    assert not untouched[1, [0, 3, 4]].any(axis=(1, 2, 3)).all()
    # a row past the last slot is dropped by the dense form
    _, kept = kernels.ssm_update_reference(state, 1, jnp.asarray(
        [3, NS, NS], jnp.int32), *args)
    assert (np.asarray(kept)[1, [0, 1, 2, 4]]
            == np.asarray(state)[1, [0, 1, 2, 4]]).all()


def test_the_programs_through_the_kernels_are_the_dense_programs(
        monkeypatch):
    """`ssm_ops` with both kernels (interpreted) in the dense forms'
    place: prefill over chunk slots and decode give the same logits, and
    inactive rows leave every real slot alone (they name the scratch
    slot)."""
    from jax.experimental import pallas as pl
    dense, _ = serve(engine(dict(full_prompt_prefill=False)),
                     tokens(37, seed=8), steps=3)
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))
    monkeypatch.setattr(ssm_ops, "_use_ssm_kernels", lambda cfg: True)
    # (another static config: the jitted programs cache by it)
    eng = engine(dict(full_prompt_prefill=False), attn_impl="auto",
                 max_seq_len=500)
    before = state_of(eng, 3)
    fused, _ = serve(eng, tokens(37, seed=8), steps=3)
    assert np.abs(fused - dense).max() < TOL
    assert eng.state.seqs[7].state_slot == 0
    after = state_of(eng, 3)
    assert all(np.array_equal(x, y) for x, y in zip(before, after))


# ----------------------------------------------------------------------
# what refuses
# ----------------------------------------------------------------------
def _loop(eng, **kw):
    return ServeLoop(eng, ds.ServingConfig.from_dict(kw))


RECURRENT = "recurrent state"
REFUSED = {
    "tensor_parallel": (NotImplementedError, RECURRENT, lambda: engine(
        dict(tensor_parallel_size=2))),
    "prefix_cache": (NotImplementedError, "prefix cache.*" + RECURRENT,
                     lambda: _loop(engine(), prefix_cache_blocks=4)),
    "kv_tiering": (NotImplementedError, "host KV tier.*" + RECURRENT,
                   lambda: _loop(engine(), prefix_cache_blocks=4,
                                 host_cache_blocks=4)),
    "page_export": (NotImplementedError, "page export/import.*" + RECURRENT,
                    lambda: engine().read_kv_blocks([0])),
    "page_import": (NotImplementedError, "page export/import.*" + RECURRENT,
                    lambda: engine().write_kv_block(0, None, None)),
    "lora": (NotImplementedError, "LoRA adapters.*" + RECURRENT, lambda:
             engine().attach_lora({"a": None, "b": None})),
    "lora_operands": (NotImplementedError, "LoRA adapters", lambda:
                      family_of(engine().cfg).refuse_lora({})),
    "preemption": (NotImplementedError, "preemption.*" + RECURRENT,
                   lambda: _loop(engine(), preemption={"enabled": True})),
    "speculative": (NotImplementedError, "speculative.*" + RECURRENT,
                    lambda: _loop(engine(), decode_burst=4,
                                  speculative={"mode": "prompt_lookup"})),
    "burst": (NotImplementedError, "burst decode.*" + RECURRENT,
              lambda: _loop(engine(), decode_burst=4)),
    "burst_engine": (NotImplementedError, "burst decode.*" + RECURRENT,
                     lambda: engine().generate(tokens(9), 4)),
    "multi_step": (NotImplementedError, "multi-step.*" + RECURRENT,
                   lambda: _loop(engine(), multi_step=4)),
    "multi_step_engine": (NotImplementedError, "multi-step.*" + RECURRENT,
                          lambda: engine().decode_multi_step(k=4)),
    "structured": (NotImplementedError, "grammar.*" + RECURRENT,
                   lambda: _loop(engine(), structured={"enabled": True})),
    "expert_paging": (NotImplementedError, "expert paging.*" + RECURRENT,
                      lambda: _loop(engine(), moe={"enabled": True})),
    "verify_span": (NotImplementedError, "speculative verify.*recurrent",
                    lambda: ragged_ops._span_core(engine().cfg, *[None] * 7)),
    "decode_without_slots": (NotImplementedError, "row -> slot vector",
                             lambda: ragged_ops._decode_core(
                                 engine().cfg, *[None] * 6)),
    "census_arena": (ValueError, "census rider", lambda:
                     ragged_ops.init_arena(engine().cfg, 4, 16,
                                           moe_census=True)),
    "loss_fn": (NotImplementedError, "no state-space mixer", lambda:
                Transformer(engine().cfg).loss_fn(None, None)),
    "forward_with_cache": (NotImplementedError, "forward_with_cache", lambda:
                           Transformer(engine().cfg).forward_with_cache(
                               None, None, None)),
    "initialize": (NotImplementedError, "initialize", lambda: ds.initialize(
        model=Transformer(engine().cfg), config={"train_batch_size": 8}
    ).train_batch({"input_ids": np.zeros((8, 16), np.int32)})),
    "one_group_too_many": (ValueError, "state-space parallel block",
                           lambda: get_model_config("falcon_h1", "tiny",
                                                    ssm_groups=3)),
    "head_dim_elsewhere": (ValueError, "attn_head_dim exists only",
                           lambda: get_model_config("llama", "tiny",
                                                    attn_head_dim=32)),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_a_path_that_cannot_serve_recurrent_state_refuses(path):
    error, message, build = REFUSED[path]
    with pytest.raises(error, match=message):
        build()


def test_a_dense_model_keeps_its_arena_and_programs():
    """The branches are taken on `cfg.ssm` at trace time: a dense model's
    arena, capability flags, step account and slots are what they were."""
    eng = build_engine("qwen2", "tiny", dtype=F32,
                       engine_config=RaggedInferenceEngineConfig(
                           num_blocks=16, block_size=8, max_seqs=2))
    assert set(eng.arena) == {"k", "v"} and not eng.recurrent_state
    assert eng.supports_lora and eng.supports_draft_verify
    assert eng.supports_multi_step and eng.state.state_slots == 0
    rows = eng.put([1], [tokens(9)])
    assert rows.state_account == {} and eng.state.seqs[1].state_slot == -1
    assert "state_slots_total" not in eng.audit_blocks()
    assert eng.free_slots == 1
