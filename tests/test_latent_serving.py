"""The latent-attention (MLA) shortcut-MoE block on the normal serving path
(`build_engine("longcat_flash", ...)`), at a small size on the CPU, against
the benchmark's plain float32 reference (`benchmark/references/
longcat_flash.py`, which imports nothing of the program): hidden 64, 4
heads, ranks 32/16, 32 routed + 16 identity experts, top-4, nonzero router
bias, 8 of the 32 experts held.

Tolerance of every comparison with the reference: both sides are float32
and differ in the order of their reductions only (decompressed against
absorbed attention, grouped against per-expert matmuls); readings are
2e-7 on logits that spread by 0.16, the limit is 2e-5, and the six mistakes
below move the last logits of a 20-token prompt by 9 times that (the MoE
joined a sub-block early: at this size the second attention barely sees it)
to 9,000 times (identity experts zeroed).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark import harness
from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                        build_engine, expert_ffn, latent_ops,
                                        ragged_ops)
from deepspeed_tpu.models import Transformer, get_model_config
from deepspeed_tpu.ops import mla_paged
from test_grouped_matmul import arena_copy, moe_through_the_kernel

pytestmark = pytest.mark.serving

REF = harness.load_module(harness.BENCH_DIR, "references", "longcat_flash")
CFG = harness.load_json(harness.ROOT, "tests", "benchmark", "data",
                        "configs", "longcat-flash-tiny.json")
S = REF.sizes(CFG)
SEED, TOL = 11, 2e-5
F32 = jnp.float32


def engine(seed=SEED, sizes=S, engine_kw=None, **cfg_kw):
    prog = CFG["program"]
    kw = dict(prog["overrides"], moe_expert_first=sizes.local_first,
              moe_expert_count=sizes.local_count, **cfg_kw)
    return build_engine(
        prog["arch"], prog["size"], dtype=F32,
        params=REF.make_params(seed, sizes, F32),
        engine_config=RaggedInferenceEngineConfig(
            **dict(prog["engine"], **(engine_kw or {}))), **kw)


def ref_logits(tokens, broken=(), sizes=S, seed=SEED):
    """[len(tokens), V] reference logits, the layers walked here so that a
    test can break the block (`broken`: `REF.block`'s flags)."""
    key = REF.seed_key(REF.seed_arg(seed))
    top = lambda n: REF.top_param(key, n, sizes, F32)  # noqa: E731
    x = jnp.take(top("tok_embed"), jnp.asarray(tokens)[None], 0)
    pos = jnp.arange(len(tokens))[None]
    for l in range(sizes.layers):
        lp = REF.layer_params(key, np.uint32(l), sizes, F32)
        x = REF.block(x, lp, pos, sizes, None, broken)
    x = REF._rms(x, top("final_norm_scale"), sizes.eps)
    return np.asarray(jnp.matmul(x, top("lm_head"), precision=REF.HI))[0]


def serve(eng, prompt, steps=4, uid=1):
    """Prefill `prompt`, then decode the reference's own greedy tokens:
    ([steps + 1, V] program logits, the tokens fed)."""
    eng.put([uid], [prompt])
    while eng.query(uid) is None:
        eng.step()
    rows, toks = [np.asarray(eng.query(uid))], list(prompt)
    for _ in range(steps):
        toks.append(int(ref_logits(toks)[-1].argmax()))
        rows.append(np.asarray(
            eng.put([uid], [np.array(toks[-1:], np.int32)])[uid]))
    eng.flush(uid)
    return np.stack(rows), np.array(toks)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, S.vocab, n).astype(np.int32)


@pytest.mark.parametrize("n,engine_kw,programs", [
    pytest.param(20, None, "prefill_full", id="full"),
    pytest.param(50, dict(full_prompt_prefill=False), "prefill_chunks",
                 id="chunked"),
    pytest.param(150, None, "prefill_chunks", id="over_budget"),
])
def test_prefill_then_decode_matches_the_reference(n, engine_kw, programs):
    """Full and chunked prefill write the latent cache; decode reads it in
    the absorbed form; every step's logits are the reference's full
    forward over the whole sequence."""
    eng = engine(engine_kw=engine_kw)
    got, toks = serve(eng, prompt(n))
    want = ref_logits(toks)[n - 1:]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1
    assert set(eng.arena) == {"c", "moe_counts"}       # no "k", no "v"
    assert eng.arena["c"].shape == (2 * S.layers, 40, 16, 128)


def test_two_sequences_share_the_arena_and_the_bursts_agree():
    """Two prompts decode side by side; `generate` (compiled bursts through
    `decode_tokens`) and `decode_multi_step` give the per-step chain."""
    eng = engine()
    p = prompt(23, seed=5)
    chain = [int(ref_logits(p)[-1].argmax())]
    for _ in range(7):
        chain.append(int(ref_logits(np.concatenate([p, chain]))[-1].argmax()))
    burst = eng.generate(p, max_new_tokens=8)
    assert list(burst) == chain
    eng.put([7, 8], [p, prompt(30, seed=6)])
    eng.state.seqs[7].generated.append(chain[0])     # the pending token
    group = eng.decode_multi_step([7], k=4)
    assert list(group[7]) == chain[1:5]


@pytest.mark.parametrize("broken", ["no_s_q", "no_s_kv", "bias_in_weight",
                                    "renormalised", "zero_as_zero",
                                    "early_join"])
def test_each_broken_path_fails_the_comparison(broken):
    """A reference with one mistake in it (a scale factor left out, the
    bias used in the weight, weights renormalised, identity experts
    zeroed, the MoE joined after the first sub-block) lies far outside
    the tolerance the program is held to."""
    p = prompt(20)
    got = np.asarray(engine().put([1], [p])[1])
    assert np.abs(got - ref_logits(p)[-1]).max() < TOL
    assert np.abs(got - ref_logits(p, (broken,))[-1]).max() > 5 * TOL


def test_the_shares_add_up_to_the_uncut_layer():
    """32 routed experts over 4 shares of 8: each share's program gives the
    dense path, the identity experts and ITS experts' part; with the first
    two counted once, the four add up to the reference layer that holds
    all 32."""
    one = dataclasses.replace(S, layers=1)
    whole = dataclasses.replace(one, local_first=0, local_count=S.experts)
    p = prompt(24, seed=3)
    pos = jnp.arange(len(p), dtype=jnp.int32)[None]
    key = REF.seed_key(REF.seed_arg(SEED))
    x0 = jnp.take(REF.top_param(key, "tok_embed", whole, F32),
                  jnp.asarray(p)[None], 0)
    lp = REF.layer_params(key, np.uint32(0), whole, F32)
    uncut = np.asarray(REF.block(x0, lp, pos, whole))
    once = np.asarray(REF.block(x0, lp, pos, whole, None,
                                ("identity_only",)))
    parts = []
    for first in range(0, S.experts, 8):
        share = dataclasses.replace(one, local_first=first, local_count=8)
        cfg = get_model_config(
            "longcat_flash", "tiny", dtype=F32, num_layers=1,
            moe_expert_first=first, moe_expert_count=8)
        params = REF.make_params(SEED, share, F32)
        x, _ = latent_ops._forward(
            cfg, params, ragged_ops.init_arena(cfg, 4, 16),
            jnp.asarray(p)[None], pos, jnp.ones((1, len(p)), bool),
            jnp.arange(4, dtype=jnp.int32)[None], "fresh")
        parts.append(np.asarray(x))
        # and the share alone is the reference with that share
        lp_i = REF.layer_params(key, np.uint32(0), share, F32)
        assert np.abs(parts[-1] - np.asarray(
            REF.block(x0, lp_i, pos, share))).max() < TOL
    assert np.abs(parts[0] - parts[1]).max() > 100 * TOL   # shares differ
    assert np.abs(sum(parts) - 3 * once - uncut).max() < 4 * TOL
    assert np.abs(uncut - once).max() > 100 * TOL


def test_assignments_past_the_buffer_run_it_again():
    """A router that piles its picks on the experts held here passes the
    compact buffer (`local_rows_cap`): the grouped matmuls run it again
    for the rest, and the result is still the reference's."""
    cfg = get_model_config("longcat_flash", "tiny", dtype=F32,
                           moe_expert_count=8)
    key = REF.seed_key(REF.seed_arg(SEED))
    lp = REF.layer_params(key, np.uint32(0), S, F32)
    lp["moe_router_bias"] = lp["moe_router_bias"].at[:8].add(1.0)
    h = jax.random.normal(jax.random.PRNGKey(0), (64, S.hidden))
    valid = jnp.arange(64) < 60
    # the program's view: the stacks of TWO layers, this one the second
    experts = {n: jnp.concatenate([jnp.ones_like(w), w])
               for n, w in lp["experts"].items()}
    got, counts = expert_ffn.moe(cfg, lp, experts, 1, h, valid)
    counts = dict(zip(expert_ffn.COUNT_NAMES, np.asarray(counts)))
    cap = expert_ffn.local_rows_cap(64 * 4, 8, 48)
    assert cap == 176 and counts["local_rows"] > cap
    assert counts["picks"] == 60 * 4 and counts["router_calls"] == 1
    routed, identity = REF.moe_parts(h[None], lp, S, functools.partial(
        REF._mm, precision=None))
    want = np.asarray(routed + identity)[0]
    assert np.abs(np.asarray(got) - want)[:60].max() < TOL
    # a padded token's picks cost no row and count nowhere
    assert counts["local_rows"] <= 60 * 4


@pytest.mark.kernels
@pytest.mark.parametrize("piled", [0.0, 1.0],
                         ids=["one_pass", "past_the_buffer"])
def test_the_experts_through_the_kernel_are_the_ragged_dots(monkeypatch,
                                                            piled):
    """`_moe` with the grouped-matmul kernel (the chip's path, interpreted)
    on the layer of the test above: as the router routes, and with its
    picks piled on the experts held here so that the compact buffer (176
    rows: a whole tile and a partial one) runs a second time."""
    cfg = get_model_config("longcat_flash", "tiny", dtype=F32,
                           moe_expert_count=8)
    lp = REF.layer_params(REF.seed_key(REF.seed_arg(SEED)), np.uint32(0), S,
                          F32)
    lp["moe_router_bias"] = lp["moe_router_bias"].at[:8].add(piled)
    h = jax.random.normal(jax.random.PRNGKey(0), (64, S.hidden))
    experts = {n: jnp.concatenate([jnp.ones_like(w), w])
               for n, w in lp["experts"].items()}
    counts, passes = moe_through_the_kernel(
        monkeypatch, cfg, lp, experts, 1, h, jnp.arange(64) < 60, TOL)
    assert passes == (2 if piled else 1)
    assert (counts["local_rows"] > 176) == bool(piled)


def _latents(rng, dtype=F32, MB=5):
    """A small arena and tables of MB blocks with garbage past the live
    ones."""
    arena = jnp.asarray(rng.randn(3, 12, 8, 128), dtype)
    tables = jnp.asarray(rng.randint(0, 12, (4, MB)), jnp.int32)
    return arena, tables.at[1, 3:].set(10 ** 6)


def test_absorbed_attention_equals_the_decompressed_form():
    """Queries against cached latents, absorbed (what the chip runs for
    decode and chunks; here the CPU's gather of the same mathematics),
    equal the reference's decompressed causal attention (`REF.mla`) of the
    same sub-block at the same positions."""
    import math
    cfg = get_model_config("longcat_flash", "tiny", dtype=F32)
    rng = np.random.RandomState(1)
    n_tok, H, NH, dn, rank = 30, S.hidden, S.heads, S.d_nope, S.kv_rank
    sp = REF.layer_params(REF.seed_key(REF.seed_arg(SEED)), np.uint32(0), S,
                          F32)["sub"][0]
    mm = functools.partial(REF._mm, precision=None)
    n = jnp.asarray(rng.randn(1, n_tok, H), F32)
    pos = jnp.arange(n_tok)[None]
    want = REF.mla(n, sp, pos, S, mm)                          # [1, n, H]
    # the queries and cache rows as `REF.mla` makes them
    cq = REF._rms(mm(n, sp["wq_a"]), sp["q_a_norm_scale"], S.eps)
    q = (math.sqrt(H / S.q_rank) * mm(cq, sp["wq_b"])).reshape(
        1, n_tok, NH, dn + S.d_rope)
    q = jnp.concatenate([q[..., :dn],
                         REF._rope(q[..., dn:], pos, S.rope_theta)], -1)
    ckv = mm(n, sp["wkv_a"])
    rows = jnp.concatenate([
        math.sqrt(H / rank) * REF._rms(ckv[..., :rank],
                                       sp["kv_a_norm_scale"], S.eps),
        REF._rope(ckv[..., rank:], pos, S.rope_theta)], -1)[0]
    # scattered through an arena by a table with garbage past its end
    table = np.concatenate([rng.permutation(12)[:4], [10 ** 6]])
    arena = np.asarray(rng.randn(3, 12, 8, 128), np.float32)
    arena[1, table[np.arange(n_tok) // 8], np.arange(n_tok) % 8,
          :rows.shape[1]] = np.asarray(rows)
    tables = jnp.asarray(np.stack([table, table]), jnp.int32)
    # the last 6 tokens as one chunk row, and a row with no query
    absorbed = latent_ops._attend_absorbed(
        cfg, jnp.concatenate([q[:, -6:]] * 2), jnp.asarray(arena), 1, tables,
        jnp.asarray([n_tok - 6, 3]), jnp.asarray([6, 0]), sp["wkv_b"])
    got = mm(absorbed[0].reshape(6, NH * S.d_v), sp["wo"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0, -6:]),
                               rtol=2e-5, atol=2e-6)
    assert not np.asarray(absorbed[1]).any()         # a row with no query


def written(got, n_valid):
    """The kernel's output with the rows nothing wrote set to zero: those
    of a query tile that holds no real query (the caller's to zero)."""
    got = np.asarray(got, np.float32)
    tq = min(mla_paged.queries_per_step(got.shape[2]), got.shape[1])
    tile_first = np.arange(got.shape[1]) // tq * tq
    return np.where((tile_first[None] < np.asarray(n_valid)[:, None])
                    [:, :, None, None], got, 0.0)


@pytest.fixture
def interpret(monkeypatch):
    import jax.experimental.pallas as pl
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))


@pytest.mark.kernels
@pytest.mark.parametrize("dtype,tol,Q,MB", [
    (jnp.float32, 2e-5, 1, 5), (jnp.float32, 2e-5, 16, 5),
    (jnp.float32, 2e-5, 16, 19), (jnp.bfloat16, 3e-2, 8, 9)])
def test_the_paged_kernel_matches_the_gather(interpret, dtype, tol, Q, MB):
    """Interpret mode: one query a row (decode) and tiles of queries (a
    chunk); rows at position 0, mid-block, deep in their table, and with
    no query at all; padded queries; garbage table entries; tables of one
    grid step, of two and of three (dead blocks past the live ones)."""
    rng = np.random.RandomState(0)
    arena, tables = _latents(rng, dtype, MB)
    qa = jnp.asarray(rng.randn(4, Q, 4, 16), dtype)
    qr = jnp.asarray(rng.randn(4, Q, 4, 8), dtype)
    pos0 = jnp.asarray([0, 17, 8 * MB - Q, 5], jnp.int32)
    n_valid = jnp.asarray([Q, max(Q - 3, 1), Q, 0], jnp.int32)
    want = mla_paged.mla_paged_reference(qa, qr, arena, tables, pos0,
                                         n_valid, 2, 0.3)
    got = mla_paged.mla_paged_attention(
        qa, qr, arena, tables, pos0, n_valid, jnp.asarray(2), 0.3)
    assert got.dtype == dtype
    np.testing.assert_allclose(written(got, n_valid),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.kernels
def test_rows_the_kernel_does_not_write_come_out_zero(interpret, monkeypatch):
    """A query tile without a real query is no item of the kernel's grid,
    so whatever lay in the output's memory stays there (here: NaN, planted
    behind the kernel); `_attend_absorbed` zeroes those rows behind the
    value up-projection and passes the rest on."""
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    cfg = get_model_config("longcat_flash", "tiny", dtype=F32)
    rng = np.random.RandomState(4)
    arena, tables = _latents(rng, MB=12)
    arena = jnp.pad(arena, ((0, 0), (0, 0), (0, 0), (0, 128)))[
        ..., :-(-(S.kv_rank + S.d_rope) // 128) * 128]
    Q, pos0, n_valid = 16, jnp.asarray([0, 17, 70, 5]), \
        jnp.asarray([16, 3, 9, 0])
    q = jnp.asarray(rng.randn(4, Q, S.heads, S.d_nope + S.d_rope), F32)
    w_kvb = jnp.asarray(rng.randn(S.kv_rank, S.heads * (S.d_nope + S.d_v)),
                        F32)
    positions = pos0[:, None] + jnp.arange(Q)[None]
    valid = jnp.arange(Q)[None] < n_valid[:, None]
    tiles = latent_ops._live_tiles(cfg, arena, tables, positions, valid)
    assert tiles is not None
    attend = mla_paged.mla_paged_attention
    unwritten = ~np.asarray(written(np.ones((4, Q, S.heads, 1)), n_valid),
                            bool)
    monkeypatch.setattr(
        mla_paged, "mla_paged_attention", lambda *a, **kw: jnp.where(
            unwritten, jnp.nan, attend(*a, **kw)))
    got = latent_ops._attend_absorbed(cfg, q, arena, 1, tables, pos0,
                                      n_valid, w_kvb, tiles)
    want = latent_ops._attend_absorbed(cfg, q, arena, 1, tables, pos0,
                                       n_valid, w_kvb)
    assert unwritten.sum() == (8 + 0 + 16) * S.heads
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    assert not np.asarray(got)[~np.asarray(valid)].any()


def _walked(rng, heads, Q, MB, pos0, n_valid, garbage=10 ** 6, nb=40):
    """A case on the walk's edges: block 8, so a key tile is 64 keys (8
    table entries); every table entry past a row's live blocks is
    `garbage`.  Returns the kernel's and the gather's arguments."""
    arena = jnp.asarray(rng.randn(3, nb, 8, 128), F32)
    tables = rng.randint(0, nb, (len(pos0), MB))
    for b, (p, n) in enumerate(zip(pos0, n_valid)):
        tables[b, (p + max(n, 1) - 1) // 8 + 1:] = garbage
    qa = jnp.asarray(rng.randn(len(pos0), Q, heads, 16), F32)
    qr = jnp.asarray(rng.randn(len(pos0), Q, heads, 8), F32)
    return (qa, qr, arena, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos0, jnp.int32), jnp.asarray(n_valid, jnp.int32))


# (heads, queries a row, table width, pos0, n_valid, garbage)
WALKS = {
    # a row's last key is a block's last, a block's first, a tile's last,
    # a tile's first, the table's last
    "block-and-tile-edges": (4, 1, 20, [7, 8, 63, 64, 127, 128, 159],
                             [1] * 7, 10 ** 6),
    "a-table-of-one": (4, 1, 1, [0, 3, 7], [1, 1, 1], 10 ** 6),
    "no-whole-tiles": (4, 1, 10, [79, 70, 64, 63, 5], [1] * 5, 10 ** 6),
    "inactive-first": (4, 1, 10, [0, 30, 70], [0, 1, 1], 10 ** 6),
    "inactive-last": (4, 1, 10, [30, 70, 0], [1, 1, 0], 10 ** 6),
    "inactive-between": (4, 1, 10, [30, 0, 0, 70], [1, 0, 0, 1], 10 ** 6),
    "all-inactive": (4, 1, 10, [30, 0, 70], [0, 0, 0], 10 ** 6),
    "negative-garbage": (4, 1, 10, [79, 9, 64], [1, 1, 1], -7),
    # chunk rows: query tiles of 8; a row of whole tiles, one whose last
    # three tiles hold no real query, one with a single real query in its
    # second tile, one with none at all, one that ends on the table's end
    "chunk-rows": (4, 32, 10, [0, 17, 40, 5, 48], [32, 5, 9, 0, 32],
                   10 ** 6),
    "chunk-rows-negative-garbage": (4, 16, 19, [64, 100, 3], [16, 1, 7],
                                    -1),
    "64-heads-decode": (64, 1, 10, [79, 8, 0, 64], [1, 1, 0, 1], 10 ** 6),
    "64-heads-chunk": (64, 16, 10, [0, 60], [16, 11], 10 ** 6),
}


@pytest.mark.kernels
@pytest.mark.parametrize("case", sorted(WALKS))
def test_the_kernel_on_the_walks_edges(interpret, case):
    """Interpret mode against the gather where the list of live key tiles
    has its edges; the index rides in traced."""
    heads, Q, MB, pos0, n_valid, garbage = WALKS[case]
    args = _walked(np.random.RandomState(1), heads, Q, MB, pos0, n_valid,
                   garbage)
    want = mla_paged.mla_paged_reference(*args, 1, 0.3)
    got = jax.jit(lambda index: mla_paged.mla_paged_attention(
        *args, index, 0.3))(jnp.asarray(1))
    # (a padded query beside real ones is zero; the gather zeroes all)
    np.testing.assert_allclose(written(got, n_valid), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("Q", [1, 16])
def test_tables_of_10_and_33_give_the_same_bits(interpret, Q):
    """The key tile follows from the static shapes alone (8 table entries,
    fewer only under a narrower table), so the same rows under a wider
    table walk the same tiles: equal outputs, bit for bit."""
    pos0, n_valid = [79 - Q + 1, 30, 0, 64 - Q], [Q, max(Q - 3, 1), 0, Q]
    qa, qr, arena, tables, *rest = _walked(
        np.random.RandomState(2), 4, Q, 10, pos0, n_valid)
    wide = jnp.pad(tables, ((0, 0), (0, 23)), constant_values=-3)
    narrow = mla_paged.mla_paged_attention(qa, qr, arena, tables, *rest,
                                           2, 0.3)
    assert np.array_equal(
        written(narrow, n_valid),
        written(mla_paged.mla_paged_attention(qa, qr, arena, wide, *rest,
                                               2, 0.3), n_valid))


@pytest.mark.parametrize("Q,heads", [(1, 4), (32, 4), (8, 128)])
def test_the_list_holds_the_live_items_and_copies_the_live_blocks(Q, heads):
    """`live_tiles`: one item a (row, query tile, key tile) that holds a
    key some real query of the tile sees, in that order; a slot past the
    tile's last such block holds what it held an item earlier, so the
    pipeline copies a block exactly where a live table entry stands."""
    bs, P, B, MB, nb = 8, mla_paged.BLOCKS_PER_STEP, 6, 20, 1000
    tq = min(mla_paged.queries_per_step(heads), Q)
    pos0 = np.asarray([159 - Q + 1, 0, 70, 63, 0, 17])
    n_valid = np.asarray([Q, 1, 0, max(Q - 3, 1), 0, Q])
    tables = np.random.RandomState(0).permutation(B * MB).reshape(B, MB)
    count, groups, tiles, blocks, first, real = (
        np.asarray(x) for x in mla_paged.live_tiles(
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos0, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), Q, heads, nb, bs))
    N = B * (Q // tq) * -(-MB // P)
    assert groups.shape == tiles.shape == (N,) and blocks.shape == (P * N,)
    # (the places behind the live items too: a pipeline may look ahead)
    assert 0 <= groups.min() and groups.max() < B * (Q // tq)
    assert 0 <= tiles.min() and tiles.max() < -(-MB // P)
    assert 0 <= blocks.min() and blocks.max() < nb
    slots, want, copies, live_blocks = blocks.reshape(P, N), [], 0, 0
    for b in range(B):
        for t in range(Q // tq):
            g = b * (Q // tq) + t
            n_real = min(max(n_valid[b] - t * tq, 0), tq)
            assert (first[g], real[g]) == (pos0[b] + t * tq, n_real)
            if n_real:
                n_blocks = (pos0[b] + t * tq + n_real - 1) // bs + 1
                want += [(g, j, n_blocks) for j in range(-(-n_blocks // P))]
    assert count == len(want)
    assert list(zip(groups[:count], tiles[:count])) == [w[:2] for w in want]
    for i, (g, j, n_blocks) in enumerate(want):
        for s in range(P):
            before = slots[s, i - 1] if i else 0
            if j * P + s < n_blocks:
                assert slots[s, i] == tables[g // (Q // tq), j * P + s]
                copies += slots[s, i] != before
                live_blocks += 1
            else:
                assert slots[s, i] == before
    # one query tile a row: every live block is copied once; later query
    # tiles of a row find its first blocks where the tile before left them
    assert copies == live_blocks if Q == tq else copies < live_blocks


@pytest.mark.kernels
def test_decode_and_chunks_through_the_kernel_match_the_gather(
        interpret, monkeypatch):
    """The decode and chunk programs on an arena a prefill has filled,
    once on the CPU's path (the absorbed gather) and once through the
    kernel (platform gate flipped)."""
    import deepspeed_tpu.utils.device as device_mod
    eng = engine()
    p = prompt(40, seed=2)
    first = int(np.asarray(eng.put([1], [p])[1]).argmax())
    table = eng.state.block_table(eng.state.seqs[1])
    tables = jnp.asarray(np.stack([table] + [np.zeros(32, np.int32)] * 3))
    # (the counters' number follows the platform's gate, flipped below)
    arena = functools.partial(arena_copy, eng)
    decode = (jnp.asarray([first, 0, 0, 0]), jnp.asarray([40, 0, 0, 0]),
              tables, jnp.asarray([True, False, False, False]))
    chunk = (jnp.asarray(np.stack([prompt(32, seed=s) for s in range(4)])),
             jnp.asarray([40, 0, 0, 0]), jnp.asarray([19, 0, 0, 0]), tables,
             jnp.asarray([True, False, False, False]))
    fused_cfg = dataclasses.replace(eng.cfg, attn_impl="pallas")
    dense, _ = latent_ops.decode_core(eng.cfg, eng.params, arena(), *decode)
    dense_c = latent_ops.prefill_chunks(eng.cfg, eng.params, arena(), *chunk)
    with pytest.raises(ValueError, match="paged latent attention kernel"):
        latent_ops.decode_core(fused_cfg, eng.params, arena(), *decode)
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    fused, _ = latent_ops.decode_core(fused_cfg, eng.params, arena(),
                                      *decode)
    fused_c = latent_ops.prefill_chunks(fused_cfg, eng.params, arena(),
                                        *chunk)
    assert np.abs(np.asarray(fused - dense))[0].max() < TOL
    assert np.abs(np.asarray(fused_c[0] - dense_c[0]))[0].max() < TOL


def test_the_list_refuses_an_arena_it_cannot_pack():
    """The running maximum packs (grid step, block) into one int32: more
    grid steps times blocks than that holds is refused while tracing."""
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="do not pack"):
        mla_paged.live_tiles(jnp.zeros((1, 8), jnp.int32), one, one + 1, 1,
                             4, 2 ** 31, 8)


@pytest.mark.kernels
@pytest.mark.parametrize("program", ["decode", "chunks"])
def test_a_programs_attentions_walk_one_list(interpret, monkeypatch, program):
    """The list of live key tiles is made once a program, outside the
    layer scan, and every attention of the scan's body is handed it."""
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    eng = engine(max_seq_len=480)                  # (a cfg of its own)
    lists, handed = [], []
    make, attend = mla_paged.live_tiles, mla_paged.mla_paged_attention
    monkeypatch.setattr(mla_paged, "live_tiles", lambda *a, **kw: (
        lists.append(make(*a, **kw)) or lists[-1]))
    monkeypatch.setattr(mla_paged, "mla_paged_attention", lambda *a, **kw: (
        handed.append(kw["tiles"]) or attend(*a, **kw)))
    tables = jnp.zeros((4, 32), jnp.int32)
    active = jnp.asarray([True, False, False, False])
    if program == "decode":
        fn = functools.partial(latent_ops.decode_core, eng.cfg)
        args = (jnp.zeros(4, jnp.int32), jnp.asarray([9, 0, 0, 0]), tables,
                active)
    else:
        fn = functools.partial(latent_ops.prefill_chunks, eng.cfg)
        args = (jnp.zeros((4, 32), jnp.int32), jnp.asarray([8, 0, 0, 0]),
                jnp.asarray([19, 0, 0, 0]), tables, active)
    jax.make_jaxpr(fn)(eng.params, eng.arena, *args)
    assert len(lists) == 1 and len(handed) == 2     # a double block's two
    assert all(h is lists[0] for h in handed)


@pytest.mark.kernels
def test_an_engine_on_the_chips_path_serves_the_reference_and_counts(
        interpret, monkeypatch):
    """An engine built where the platform says "tpu" (both kernels, the
    latent attention's and the experts', interpreted): chunked prefill and
    decode give the reference's logits, the counters' rider carries the
    kernel's three entries through `drain_moe_counts`, fetches over reached
    is 1 (this grid reads a reached expert's weights once a matmul) and an
    expert of a few rows is one item."""
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    # (its own cfg: a jitted program is cached by its static cfg)
    eng = engine(engine_kw=dict(full_prompt_prefill=False), max_seq_len=488)
    assert eng.arena["moe_counts"].shape == (8,)
    n = 40
    got, toks = serve(eng, prompt(n, seed=6), steps=2)
    assert np.abs(got - ref_logits(toks)[n - 1:]).max() < TOL
    counts = eng.drain_moe_counts()
    assert tuple(counts) == expert_ffn.COUNT_NAMES \
        + expert_ffn.KERNEL_COUNT_NAMES
    assert counts["picks"] == (n + 2) * 2 * 4 and counts["local_rows"] > 0
    # at most every held expert of both layers, each program
    assert 0 < counts["experts_reached"] <= counts["router_calls"] * 8
    assert counts["expert_weight_fetches"] == counts["experts_reached"] \
        <= counts["expert_items"]
    assert not any(eng.drain_moe_counts().values())


def test_padded_chunk_slots_cost_passes_only_for_their_real_tokens(
        monkeypatch):
    """More rows than `ROW_TILE`: the real tokens go in front and the
    token-wise work takes them a tile at a time; the result is the
    untiled program's, and the reference's."""
    monkeypatch.setattr(latent_ops, "ROW_TILE", 32)
    # (its own cfg: a jitted program is cached by its static cfg)
    eng = engine(engine_kw=dict(full_prompt_prefill=False), max_seq_len=496)
    n = 90
    got, toks = serve(eng, prompt(n, seed=4), steps=2)
    assert np.abs(got - ref_logits(toks)[n - 1:]).max() < TOL
    counts = eng.drain_moe_counts()
    # 90 prompt tokens and 2 decode steps, 2 layers, top-4: padded slots
    # of the [NC, 32] chunk programs route nothing
    assert counts["picks"] == (n + 2) * 2 * 4


def _loop(eng, **kw):
    from deepspeed_tpu.serving import ServeLoop
    return ServeLoop(eng, ds.ServingConfig.from_dict(kw))


REFUSED = {
    "tensor_parallel": (ValueError, "tensor parallelism", lambda: engine(
        engine_kw=dict(tensor_parallel_size=2))),
    "fused_tp": (ValueError, "tensor parallelism", lambda: engine(
        engine_kw=dict(tensor_parallel_size=2, tp_collectives="fused"))),
    "expert_paging": (ValueError, "expert paging", lambda: build_engine(
        "longcat_flash", "tiny", dtype=F32,
        serving_config=ds.ServingConfig.from_dict(
            {"moe": {"enabled": True}}))),
    "expert_paging_engine": (RuntimeError, "expert paging", lambda:
                             engine().enable_expert_paging(4)),
    "prefix_cache": (NotImplementedError, "prefix cache", lambda: _loop(
        engine(), prefix_cache_blocks=4)),
    "kv_tiering": (NotImplementedError, "prefix cache", lambda: _loop(
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
    "loss_fn": (NotImplementedError, "loss_fn", lambda: Transformer(
        engine().cfg).loss_fn(None, None)),
    "forward_with_cache": (NotImplementedError, "forward_with_cache", lambda:
                           Transformer(engine().cfg).forward_with_cache(
                               None, None, None)),
    "initialize": (NotImplementedError, "initialize", lambda: ds.initialize(
        model=Transformer(engine().cfg), config={"train_batch_size": 8}
    ).train_batch({"input_ids": np.zeros((8, 16), np.int32)})),
    "share_outside": (ValueError, "not among", lambda: get_model_config(
        "longcat_flash", "tiny", moe_expert_first=30, moe_expert_count=8)),
    "dense_with_share": (ValueError, "latent-attention double block",
                         lambda: get_model_config("llama", "tiny",
                                                  moe_expert_count=8)),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_a_path_that_cannot_serve_the_block_refuses(path):
    error, message, build = REFUSED[path]
    with pytest.raises(error, match=message):
        build()


def test_a_dense_model_keeps_its_arena_and_programs():
    """The latent branches are taken on `cfg.latent` at trace time: a dense
    model's arena, capability flags and counters are what they were."""
    eng = build_engine("qwen2", "tiny", dtype=F32,
                       engine_config=RaggedInferenceEngineConfig(
                           num_blocks=8, block_size=16, max_seqs=2))
    assert set(eng.arena) == {"k", "v"} and not eng.cfg.latent
    assert eng.supports_lora and eng.supports_draft_verify
    assert not eng.supports_moe_counts
    assert engine().supports_moe_counts and not engine().supports_moe
