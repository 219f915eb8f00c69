"""Layers of ONE kind each (state-space mixers and one attention layer
without a position encoding a period), every one behind routed experts and
a shared expert, on the normal serving path (`build_engine(
"granite_moe_hybrid", ...)`), at a small size on the CPU, against the
benchmark's plain float32 reference (`benchmark/references/
granite_moe_hybrid.py`: the recurrence a token at a time, every held expert
over every token, no chunks, no cache; imports nothing of the program):
hidden 128, a period `m m a m` of 4 layers, 4/2 attention heads of 32, a
mixer of 4 heads of 64 (two a 128-lane row of the stored state) in one
group, a state of 16, a convolution over 4 positions, scan chunks of 8
positions, 8 experts of width 32 of which 4 are held, 3 a token, a shared
expert of 64, blocks of 8 tokens.

Tolerance of every comparison with the reference: both sides are float32
and differ in the order of their reductions only (the chunked matmul form
against the recurrence, paged against dense attention, the sorted grouped
matmuls against every expert over every token); readings are 4e-8 on
logits that spread by 0.005 (the published `logits_scaling` 16 over a tied
head of std 0.08/12: embeddings small beside what the layers add, or the
head would score the input token's own row far above the rest and greedy
decoding would repeat it), the limit is 5e-7, and the five broken
references below (and the int8 control) move a row's logits by 5e-3 to
2.5e-2.  The residual stream itself has RMS ~1-2: `TOL_X`.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import harness
from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                        build_engine, check_serving_moe,
                                        ragged_ops, ssm_ops)
from deepspeed_tpu.models import Transformer, get_model_config
from deepspeed_tpu.ops import ssm as kernels
from deepspeed_tpu.serving import RequestState, ServeLoop

pytestmark = pytest.mark.serving

REF = harness.load_module(harness.BENCH_DIR, "references",
                          "granite_moe_hybrid")
CFG = harness.load_json(harness.ROOT, "tests", "benchmark", "data",
                        "configs", "granite-4.0-h-tiny.json")
S = REF.sizes(CFG)
SEED, TOL, TOL_X = 5, 5e-7, 1e-4
F32 = jnp.float32


def engine(engine_kw=None, params=None, **cfg_kw):
    prog = CFG["program"]
    return build_engine(
        prog["arch"], prog["size"], dtype=F32,
        params=REF.make_params(SEED, S, F32) if params is None else params,
        engine_config=RaggedInferenceEngineConfig(
            **dict(prog["engine"], **(engine_kw or {}))),
        **dict(prog["overrides"], **cfg_kw))


def tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, S.vocab, n).astype(np.int32)


def ref_logits(toks, control=None, every=0, s=S):
    """[len(toks), V]: the reference's full forward over the whole row
    (padded at the end to one of a few widths: causality keeps the padding
    out of every real position, and a width is a compile)."""
    row = np.zeros((1, -(-len(toks) // 32) * 32), np.int32)
    row[0, :len(toks)] = toks
    return np.asarray(REF.logits(SEED, row, s, F32, precision=control,
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
], ids=["full", "one_chunk", "two_chunks", "three_at_edges",
        "three_off_edges"])
def test_prefill_then_decode_matches_the_reference(n, engine_kw):
    """Prefill (in one pass, or over chunk slots that hand the state and
    the convolution's tail on through the slot) and six decode steps
    through the per-kind arenas (three rows of state, one of keys): the
    logits of the reference's full forward."""
    eng = engine(engine_kw)
    got, toks = serve(eng, tokens(n, seed=n), steps=6)
    want = ref_logits(toks)[n - 1:]
    assert want.std() > 0.003
    assert len(set(toks[n:].tolist())) > 3          # no token repeated on
    assert np.abs(got - want[:7]).max() < TOL
    audit = eng.audit_blocks()
    assert (audit["state_slots_live"], audit["state_slots_free"]) == (1, 3)
    assert (audit["state_layers"], audit["kv_layers"]) == (3, 1)


def test_two_periods_scan_the_period_and_index_the_arenas_by_kind():
    """Eight layers, `m m a m` twice: the periods are scanned, the second
    period's layers land on state rows 3-5 and key row 1, and chunked
    prefill then decode still gives the reference's logits."""
    s8 = REF.sizes(dict(CFG, num_hidden_layers=8))
    assert s8.kinds == S.kinds * 2
    eng = engine(dict(full_prompt_prefill=False),
                 params=REF.make_params(SEED, s8, F32), num_layers=8)
    assert eng.arena["ssm"].shape[0] == 6 and eng.arena["k"].shape[0] == 2
    got, toks = serve(eng, tokens(37, seed=3), steps=4)
    want = ref_logits(toks, s=s8)[36:]
    assert np.abs(got - want[:5]).max() < TOL
    assert all(np.abs(np.asarray(eng.arena[n][row])).max() > 0
               for n, row in (("ssm", 5), ("conv", 3), ("k", 1)))


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_broken_reference_fails_the_comparison(control):
    """`residual_multiplier` left out, rope applied in the attention
    layer, the router's weights not renormalised over the picks, the
    shared expert dropped, the state not carried across a chunk's edge,
    and the int8 grid: each moves the logits by far more than the
    tolerance (the edges every 16 positions: chunk slots of 16)."""
    toks = serve(engine(), tokens(40, seed=1), steps=4)[1]
    sound = ref_logits(toks)
    broken = ref_logits(toks, control, every=16)
    assert np.abs(broken[20:] - sound[20:]).max() > 200 * TOL


def test_an_unknown_control_is_an_error():
    with pytest.raises(ValueError, match="unknown control"):
        REF._how("fp4")
    assert REF._how("no_shared_expert") == (None, ("no_shared_expert",))


@pytest.mark.parametrize("chunk", [32, 16], ids=["two_chunks", "three"])
def test_a_split_prompt_equals_one_pass(chunk):
    """The same prompt through one fresh prefill and through two or three
    chunk programs: the slot ends up holding the same state and tail, the
    attention layer's blocks the same keys, and the first token's logits
    agree."""
    n = 43
    a, b = engine(), engine(dict(full_prompt_prefill=False,
                                 prefill_chunk_size=chunk,
                                 max_prefill_tokens_per_step=chunk))
    la, _ = serve(a, tokens(n, seed=2), steps=0)
    lb, _ = serve(b, tokens(n, seed=2), steps=0)
    assert np.abs(la - lb).max() < TOL
    slot = a.state.seqs[7].state_slot
    assert slot == b.state.seqs[7].state_slot
    assert np.abs(np.asarray(a.arena["ssm"][:, slot])).max() > 1e-3
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(a.arena[name][:, slot]),
                                   np.asarray(b.arena[name][:, slot]),
                                   atol=1e-5)
    blocks = a.state.seqs[7].blocks
    assert blocks == b.state.seqs[7].blocks
    np.testing.assert_allclose(
        np.asarray(a.arena["k"][0, np.asarray(blocks)]).reshape(
            -1, 2, 32)[:n],
        np.asarray(b.arena["k"][0, np.asarray(blocks)]).reshape(
            -1, 2, 32)[:n], atol=1e-5)


# ----------------------------------------------------------------------
# arenas sized by kind
# ----------------------------------------------------------------------
def test_the_arenas_have_a_row_a_layer_of_their_kind():
    """Three state-space layers and one attention layer: three rows of
    state and tails (two 64-wide heads a 128-lane row), ONE row of keys and
    values; the router's counters ride along; the bytes a slot and a token
    stand for follow the kind counts."""
    eng = engine()
    cfg = eng.cfg
    assert cfg.ssm_period == ("ssm", "ssm", "attn", "ssm")
    assert (cfg.ssm_state_layers, cfg.ssm_attn_layers) == (3, 1)
    assert [(r.kind, r.first, r.count, r.state_row, r.attn_row)
            for r in ssm_ops.layer_runs(cfg)] == [
        ("ssm", 0, 2, 0, 0), ("attn", 2, 1, 2, 0), ("ssm", 3, 1, 2, 1)]
    shapes = {k: v.shape for k, v in eng.arena.items()}
    assert shapes == {"k": (1, 48, 8, 2, 32), "v": (1, 48, 8, 2, 32),
                      "ssm": (3, 5, 2, 16, 128), "conv": (3, 5, 3 * 288),
                      "moe_counts": (5,)}
    assert kernels.lane_heads(4, 1, 64) == 2
    assert kernels.state_shape(128, 1, 128, 64) == (64, 128, 128)
    assert kernels.state_shape(32, 2, 256, 128) == (32, 256, 128)
    assert ssm_ops.state_bytes_per_slot(cfg) == 3 * (4 * 64 * 16 * 4
                                                     + 3 * 288 * 4)
    assert ssm_ops.kv_bytes_per_token(cfg) == 2 * 1 * 2 * 32 * 4
    # every layer of both kinds is the other family of this module
    falcon = get_model_config("falcon_h1", "tiny")
    assert falcon.ssm_period == ("both",)
    assert (falcon.ssm_state_layers, falcon.ssm_attn_layers) == (2, 2)
    assert [tuple(r) for r in ssm_ops.layer_runs(falcon)] \
        == [("both", 0, 1, 0, 0)]


def test_a_layer_writes_its_own_kinds_rows_only():
    """What the attention layer computes reaches no slot of the
    state-space layers before it, and what the last state-space layer
    computes reaches no block: with the one kind's weights replaced, the
    other kind's arena is bit for bit what it was (and the replaced kind's
    is not)."""
    base = REF.make_params(SEED, S, F32)

    def served(change):
        layers = dict(base["layers"])
        layers.update({n: change(layers[n]) for n in layers
                       if ssm_ops._stacked_over(n) == change.kind})
        eng = engine(params={**base, "layers": layers})
        serve(eng, tokens(19, seed=5), steps=0)
        for tok in (5, 9, 11):       # the same tokens whatever is served
            eng.put([7], [np.array([tok], np.int32)])
        return {k: np.asarray(v) for k, v in eng.arena.items()}

    def other_attention(a):
        return a * 0.5
    other_attention.kind = "attn"

    def other_last_mixer(a):
        return a.at[2].multiply(0.5)
    other_last_mixer.kind = "ssm"

    def same(a):
        return a
    same.kind = "all"
    sound, attn, last = served(same), served(other_attention), \
        served(other_last_mixer)
    # the state rows of layers 0 and 1 lie before the attention layer
    for name in ("ssm", "conv"):
        assert np.array_equal(sound[name][:2], attn[name][:2])
        assert not np.array_equal(sound[name][2], attn[name][2])
        assert not np.array_equal(sound[name][2], last[name][2])
    assert not np.array_equal(sound["k"], attn["k"])
    assert np.array_equal(sound["k"], last["k"])
    assert np.array_equal(sound["v"], last["v"])


def test_padded_rows_and_other_slots_stay_bit_for_bit():
    """A prefill and decode steps of one sequence touch its slot (in the
    three state rows) and its blocks (in the one row of keys) and nothing
    else."""
    eng = engine()
    mark = jax.random.normal(jax.random.PRNGKey(1), eng.arena["ssm"].shape)
    eng.arena["ssm"] = mark.astype(F32)
    eng.arena["conv"] = jnp.ones_like(eng.arena["conv"]) * 0.25
    eng.arena["k"] = jnp.ones_like(eng.arena["k"]) * 0.5
    before = {k: np.asarray(v) for k, v in eng.arena.items()}
    serve(eng, tokens(19, seed=5), steps=3)
    mine = eng.state.seqs[7].state_slot
    blocks = set(eng.state.seqs[7].blocks)
    for s in range(5):
        same = all(np.array_equal(before[n][:, s], np.asarray(
            eng.arena[n][:, s])) for n in ("ssm", "conv"))
        assert same == (s != mine), s
    for b in range(48):
        assert np.array_equal(before["k"][:, b], np.asarray(
            eng.arena["k"][:, b])) == (b not in blocks), b


# ----------------------------------------------------------------------
# the experts: a share, the shared expert, the counters
# ----------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """8 routed experts over 2 shares of 4: each share's program gives the
    mixer, the shared expert and ITS experts' part of a state-space layer;
    with the first two counted once, the two add up to the reference layer
    that holds all 8."""
    kind = REF.MIXER
    one = dataclasses.replace(S, kinds=(kind,))
    whole = dataclasses.replace(one, local_first=0, local_count=S.experts)
    p = tokens(24, seed=3)
    pos = jnp.arange(len(p), dtype=jnp.int32)[None]
    key = REF.seed_key(REF.seed_arg(SEED))
    x0 = S.embedding_multiplier * jnp.take(
        REF.top_param(key, "tok_embed", whole, F32), jnp.asarray(p)[None], 0)
    lp = REF.layer_params(key, np.uint32(0), whole, F32, kind)
    uncut = np.asarray(REF.block(x0, lp, pos, whole, kind))
    # the layer with no routed expert's part: what every share computes alike
    none = dataclasses.replace(one, local_count=0)
    once = np.asarray(REF.block(
        x0, {**lp, "experts": jax.tree.map(lambda a: a[:0], lp["experts"])},
        pos, none, kind))
    parts = []
    for first in (0, 4):
        share = dataclasses.replace(one, local_first=first, local_count=4)
        cfg = get_model_config(
            "granite_moe_hybrid", "tiny", dtype=F32, num_layers=1,
            ssm_layout=("ssm",), moe_expert_first=first, moe_expert_count=4)
        x, _ = ssm_ops._prefill_rows(
            cfg, REF.make_params(SEED, share, F32),
            ragged_ops.init_arena(cfg, 4, 8, max_seqs=1),
            jnp.asarray(p)[None], jnp.zeros((1,), jnp.int32),
            jnp.asarray([len(p)], jnp.int32),
            jnp.arange(4, dtype=jnp.int32)[None], jnp.ones((1,), bool),
            jnp.zeros((1,), jnp.int32), fresh=True)
        parts.append(np.asarray(x))
        # and the share alone is the reference with that share
        lp_i = REF.layer_params(key, np.uint32(0), share, F32, kind)
        assert np.abs(parts[-1] - np.asarray(
            REF.block(x0, lp_i, pos, share, kind))).max() < TOL_X
    assert np.abs(parts[0] - parts[1]).max() > 1e-2        # shares differ
    assert np.abs(sum(parts) - once - uncut).max() < 2 * TOL_X
    assert np.abs(uncut - once).max() > 1e-2


def test_the_routers_counters_ride_the_arena_and_the_serve_loop_drains_them():
    eng = engine()
    assert eng.supports_moe_counts and not eng.supports_moe
    serve(eng, tokens(20, seed=9), steps=5)
    counts = eng.drain_moe_counts()
    # a prefill of 20 tokens and 5 decode steps, 4 layers, 3 picks a token
    assert counts["picks"] == (20 + 5) * 4 * 3
    assert counts["router_calls"] == 6 * 4
    assert 0 < counts["local_rows"] < counts["picks"]
    assert counts["zero_picks"] == 0
    assert eng.drain_moe_counts()["picks"] == 0


def test_the_serve_loop_serves_rows_side_by_side_and_gives_the_account():
    """Through `ServeLoop` with the default `ServingConfig`: requests of
    unlike lengths side by side finish with the tokens the reference puts
    first, every `serve.step` says how many layers hold what, and a census
    span carries the router's counters."""
    eng = engine()
    loop = ServeLoop(eng, ds.ServingConfig())
    reqs = [loop.submit(tokens(n, seed=n), max_new_tokens=m)
            for n, m in ((9, 6), (33, 4), (20, 5))]
    accounts = []
    orig = eng.step

    def step(*a, **kw):
        out = orig(*a, **kw)
        accounts.append(dict(out.state_account))
        return out
    eng.step = step
    for _ in range(40):
        loop.step()
        if all(r.state is RequestState.DONE for r in reqs):
            break
    assert all(r.state is RequestState.DONE for r in reqs)
    for r in reqs:
        row = np.concatenate([r.prompt, r.generated])
        want = ref_logits(row)[len(r.prompt) - 1:-1]
        assert list(np.argmax(want, -1)) == list(r.generated)
    assert accounts and all(
        (a["layers"], a["state_layers"], a["kv_layers"]) == (4, 3, 1)
        for a in accounts)
    busy = [a for a in accounts if a["state_bytes_step"]]
    assert busy and all(
        a["state_bytes_step"] % (2 * ssm_ops.state_bytes_per_slot(eng.cfg))
        == 0 and a["cache_bytes_step"] > a["state_bytes_step"]
        for a in busy)
    assert eng.audit_blocks()["state_slots_live"] == 0


# ----------------------------------------------------------------------
# the kernels at 64-wide heads in one group
# ----------------------------------------------------------------------
def recurrence(x, dt, a, b, c, h0):
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


def scan_operands(S_, NH, P, G, N, seed=0, R=2):
    rng = np.random.RandomState(seed)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (R, S_, NH))) * 10
    dt[1, S_ - 6:] = 0                     # a row padded at the end
    return tuple(jnp.asarray(t, F32) for t in (
        rng.randn(R, S_, NH, P), dt, -rng.uniform(1, 16, NH),
        rng.randn(R, S_, G, N), rng.randn(R, S_, G, N),
        rng.randn(R, NH, N, P)))


SHAPES = {"two_a_row_one_group": (4, 64, 1, 16),
          "two_a_row_two_groups": (8, 64, 2, 16),
          "four_a_row": (8, 32, 1, 8),
          "whole_rows": (2, 128, 1, 16)}


@pytest.mark.kernels
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("length", [21, 5])
def test_the_scan_kernel_with_heads_side_by_side_is_the_recurrence(shape,
                                                                   length):
    """Heads of 64 (and 32) channels packed two (four) a 128-lane row of
    the stored state, one group or two, against the recurrence a token at
    a time: outputs, the final state in place on the arena at the rows'
    slots, a fresh row from zeros, padding left alone; the dense form
    through `pack_state` / `unpack_state` gives the same."""
    NH, P, G, N = SHAPES[shape]
    pack = kernels.lane_heads(NH, G, P)
    assert pack == 128 // P
    x, dt, a, b, c, h0 = scan_operands(length, NH, P, G, N)
    want_y, want_h = recurrence(x, dt, a, b, c, h0.at[1].set(0.0))
    slots = jnp.asarray([1, 0], jnp.int32)
    state = jnp.zeros((2, 3) + kernels.state_shape(NH, G, N, P), F32) \
        .at[1, slots].set(kernels.pack_state(h0, pack))
    y, state = kernels.ssd_scan(x, dt, a, b, c, state, 1, slots,
                                jnp.asarray([True, False]), 8,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(kernels.unpack_state(state[1, slots], pack)), want_h,
        atol=3e-5)
    assert not np.asarray(state[0]).any() and not np.asarray(state[1, 2]).any()
    dense_y, dense_h = kernels.ssd_scan_reference(
        x, dt, a, b, c, h0.at[1].set(0.0), 8)
    np.testing.assert_allclose(np.asarray(dense_y), want_y, atol=3e-5)
    np.testing.assert_allclose(np.asarray(dense_h), want_h, atol=3e-5)
    assert np.array_equal(np.asarray(kernels.unpack_state(
        kernels.pack_state(h0, pack), pack)), np.asarray(h0))


@pytest.mark.kernels
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_update_kernel_with_heads_side_by_side_is_the_dense_update(
        shape):
    NH, P, G, N = SHAPES[shape]
    pack = kernels.lane_heads(NH, G, P)
    rng = np.random.RandomState(0)
    L, NS, B = 2, 5, 3
    h = jnp.asarray(rng.randn(L, NS, NH, N, P), F32)
    state = kernels.pack_state(h, pack)
    assert state.shape == (L, NS) + kernels.state_shape(NH, G, N, P)
    slots = jnp.asarray([3, 0, 4], jnp.int32)        # 4: the scratch slot
    x_dt, decay, b, c = (jnp.asarray(t, F32) for t in (
        rng.randn(B, NH, P),
        np.broadcast_to(rng.uniform(.5, 1, (B, NH, 1)), (B, NH, P)),
        rng.randn(B, G, N), rng.randn(B, G, N)))
    # a head at a time, in numpy
    rep = lambda t: np.repeat(np.asarray(t), NH // G, axis=1)  # noqa: E731
    h1 = np.asarray(h)[1, np.asarray(slots)] * np.asarray(decay)[:, :, None] \
        + rep(b)[..., None] * np.asarray(x_dt)[:, :, None]
    want_y = (h1 * rep(c)[..., None]).sum(2)
    for fn, kw in ((kernels.ssm_update_reference, {}),
                   (kernels.ssm_update, {"interpret": True})):
        y, got = fn(state, 1, slots, x_dt, decay, b, c, **kw)
        np.testing.assert_allclose(np.asarray(y), want_y, atol=1e-5)
        got = np.asarray(kernels.unpack_state(got, pack))
        np.testing.assert_allclose(got[1, np.asarray(slots)], h1, atol=1e-6)
        assert np.array_equal(got[0], np.asarray(h)[0])
        assert np.array_equal(got[1, [1, 2]], np.asarray(h)[1, [1, 2]])


def test_the_programs_through_the_kernels_are_the_dense_programs(
        monkeypatch):
    """`ssm_ops` with both kernels (interpreted) in the dense forms' place
    at two heads a lane row: prefill over chunk slots and decode give the
    same logits."""
    from jax.experimental import pallas as pl
    dense, _ = serve(engine(dict(full_prompt_prefill=False)),
                     tokens(37, seed=8), steps=3)
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))
    monkeypatch.setattr(ssm_ops, "_use_ssm_kernels", lambda cfg: True)
    # (another static config: the jitted programs cache by it)
    eng = engine(dict(full_prompt_prefill=False), attn_impl="auto",
                 max_seq_len=500)
    fused, _ = serve(eng, tokens(37, seed=8), steps=3)
    assert np.abs(fused - dense).max() < TOL


def test_more_slots_than_a_pass_takes_puts_the_real_rows_in_front(
        monkeypatch):
    """A chunk program of more token slots than `ROW_TILE`: the experts
    run over the real rows, in front, a tile at a time, and the logits are
    those of the program that takes every slot at once."""
    kw = dict(full_prompt_prefill=False, prefill_chunk_size=16,
              max_prefill_tokens_per_step=32)
    whole, _ = serve(engine(kw), tokens(29, seed=4), steps=2)
    monkeypatch.setattr(ssm_ops, "ROW_TILE", 8)
    tiled, _ = serve(engine(kw, max_seq_len=496), tokens(29, seed=4),
                     steps=2)
    assert np.abs(tiled - whole).max() < TOL


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
    "preemption": (NotImplementedError, "preemption.*" + RECURRENT,
                   lambda: _loop(engine(), preemption={"enabled": True})),
    "burst": (NotImplementedError, "burst decode.*" + RECURRENT,
              lambda: _loop(engine(), decode_burst=4)),
    "multi_step": (NotImplementedError, "multi-step.*" + RECURRENT,
                   lambda: _loop(engine(), multi_step=4)),
    "expert_paging": (NotImplementedError, "expert paging.*" + RECURRENT,
                      lambda: _loop(engine(), moe={"enabled": True})),
    "expert_paging_at_the_factory": (
        ValueError, "behind a state-space mixer", lambda: check_serving_moe(
            engine().cfg, ds.ServingConfig.from_dict(
                {"moe": {"enabled": True}}))),
    "loss_fn": (NotImplementedError, "experts behind a mixer", lambda:
                Transformer(engine().cfg).loss_fn(None, None)),
    "a_period_without_a_mixer": (
        ValueError, "has a mixer", lambda: get_model_config(
            "granite_moe_hybrid", "tiny", ssm_layout=("attn",),
            num_layers=1)),
    "a_period_that_does_not_divide": (
        ValueError, "divides num_layers", lambda: get_model_config(
            "granite_moe_hybrid", "tiny", num_layers=6)),
    "an_unknown_kind": (
        ValueError, "'ssm' | 'attn' | 'both'", lambda: get_model_config(
            "granite_moe_hybrid", "tiny", ssm_layout=("ssm", "mlp"),
            num_layers=2)),
    "a_share_past_the_experts": (
        ValueError, "moe_expert_first", lambda: get_model_config(
            "granite_moe_hybrid", "tiny", moe_expert_first=6,
            moe_expert_count=4)),
    "the_layout_elsewhere": (
        ValueError, "only in the state-space family",
        lambda: get_model_config("llama", "tiny", residual_multiplier=0.5)),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_a_path_that_cannot_serve_this_family_refuses(path):
    error, match, call = REFUSED[path]
    with pytest.raises(error, match=match):
        call()
