"""The single-attention latent layer (DeepSeek-V3's form) on the normal
serving path (`build_engine("deepseek_v3", ...)`), at a small size on the
CPU, against the benchmark's plain float32 reference (`benchmark/references/
deepseek_v3.py`, which imports nothing of the program): hidden 64, 4 heads,
ranks 32/16, 1 leading dense layer + 3 expert layers, 16 routed experts in 4
groups of which 2 are kept, top-4, a shared expert, a nonzero selection
bias, YaRN (factor 8) over an original length of 64, 4 of the 16 experts
held.

Tolerance of every comparison with the reference: both sides are float32
and differ in the order of their reductions only (decompressed against
absorbed attention, grouped against per-expert matmuls); readings are
1e-7 on logits that spread by 0.16, the limit is 2e-5, and the mistakes
below move the last logits of a 90-token prompt by 20 times that (the two
rope mistakes: attention is a small part of this tiny model's output), 70
times (8-bit grids) and 100 to 4,000 times (the router's and the shared
expert's).
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
from test_latent_serving import written

pytestmark = pytest.mark.serving

REF = harness.load_module(harness.BENCH_DIR, "references", "deepseek_v3")
CFG = harness.load_json(harness.ROOT, "tests", "benchmark", "data",
                        "configs", "deepseek-v3-tiny.json")
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


def ref_logits(tokens, broken=(), sizes=S, seed=SEED, precision=None):
    """[len(tokens), V] reference logits, the layers walked here so that a
    test can break the block (`broken`: `REF.block`'s flags)."""
    key = REF.seed_key(REF.seed_arg(seed))
    top = lambda n: REF.top_param(key, n, sizes, F32)  # noqa: E731
    x = jnp.take(top("tok_embed"), jnp.asarray(tokens)[None], 0)
    pos = jnp.arange(len(tokens))[None]
    for l in range(sizes.layers):
        dense = l < sizes.dense_layers
        lp = REF.layer_params(key, np.uint32(l), sizes, F32, dense)
        x = REF.block(x, lp, pos, sizes, dense, precision, broken)
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
    pytest.param(150, None, "prefill_chunks", id="past_the_original_length"),
])
def test_prefill_then_decode_matches_the_reference(n, engine_kw, programs):
    """Full and chunked prefill write the latent cache (one row a token and
    LAYER); decode reads it in the absorbed form; every step's logits are
    the reference's full forward over the whole sequence, at contexts
    inside and past YaRN's original length (64)."""
    eng = engine(engine_kw=engine_kw)
    got, toks = serve(eng, prompt(n))
    want = ref_logits(toks)[n - 1:]
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1
    assert set(eng.arena) == {"c", "moe_counts"}
    assert eng.arena["c"].shape == (S.layers, 40, 16, 128)
    assert eng.arena["moe_counts"].shape == (7,)
    assert set(eng.params) == {"tok_embed", "final_norm_scale", "lm_head",
                               "dense_layers", "layers", "experts"}


def test_a_fresh_full_prompt_past_the_original_length():
    """`prefill_full` (the decompressed flash path) carries the blended
    frequencies and the squared attention factor too."""
    eng = engine(engine_kw=dict(max_prefill_tokens_per_step=256,
                                prefill_chunk_size=256))
    n = 130
    got, toks = serve(eng, prompt(n, seed=8), steps=1)
    assert np.abs(got - ref_logits(toks)[n - 1:]).max() < TOL


def test_a_row_preempted_and_resumed_gives_the_same_logits():
    """A row flushed mid-decode and put back (its prompt and the tokens it
    had, as `ServeLoop` resumes a preempted request by recomputation) goes
    on where it was, beside a row that stayed."""
    eng = engine()
    a, b = prompt(70, seed=1), prompt(33, seed=2)
    eng.put([1, 2], [a, b])
    while eng.query(1) is None or eng.query(2) is None:
        eng.step()
    toks = list(a)
    for _ in range(3):
        toks.append(int(ref_logits(toks)[-1].argmax()))
        eng.put([1], [np.array(toks[-1:], np.int32)])
    eng.flush(1)                                       # preempted
    eng.put([1], [np.array(toks, np.int32)])           # resumed
    while eng.query(1) is None:
        eng.step()
    got = [np.asarray(eng.query(1))]
    toks.append(int(ref_logits(toks)[-1].argmax()))
    got.append(np.asarray(eng.put([1], [np.array(toks[-1:], np.int32)])[1]))
    want = ref_logits(toks)[-2:]
    assert np.abs(np.stack(got) - want).max() < TOL
    eng.flush(1), eng.flush(2)
    eng.audit_blocks()


def test_two_sequences_share_the_arena_and_the_bursts_agree():
    eng = engine()
    p = prompt(23, seed=5)
    chain = [int(ref_logits(p)[-1].argmax())]
    for _ in range(5):
        chain.append(int(ref_logits(np.concatenate([p, chain]))[-1].argmax()))
    assert list(eng.generate(p, max_new_tokens=6)) == chain
    eng.put([7, 8], [p, prompt(30, seed=6)])
    eng.state.seqs[7].generated.append(chain[0])     # the pending token
    group = eng.decode_multi_step([7], k=4)
    assert list(group[7]) == chain[1:5]


BROKEN = ["softmax_router", "ungrouped_router", "no_shared_expert",
          "plain_rope", "bias_in_weight", "not_renormalised", "rope_halves",
          "shared_only"]


@pytest.mark.parametrize("broken", BROKEN + ["int8"])
def test_each_broken_path_fails_the_comparison(broken):
    """A reference with one mistake in it (the four controls: softmax
    scores, no group limit, no shared expert, rope without YaRN; the bias
    used in the weight, weights not renormalised, the other rope pairing,
    the routed share dropped) or computed on 8-bit grids lies far outside
    the tolerance the program is held to."""
    p = prompt(90)                  # past the original length: YaRN counts
    eng = engine()
    eng.put([1], [p])
    while eng.query(1) is None:
        eng.step()
    got = np.asarray(eng.query(1))
    assert np.abs(got - ref_logits(p)[-1]).max() < TOL
    wrong = ref_logits(p, precision="int8") if broken == "int8" \
        else ref_logits(p, (broken,))
    assert np.abs(got - wrong[-1]).max() > 10 * TOL


# ----------------------------------------------------------------------
# the router alone, against a numpy transcription of the published rule
# ----------------------------------------------------------------------
def numpy_route(logits, bias, groups, kept, k, scale):
    """sigmoid scores; bias in the selection only; a group scores the sum of
    its 2 largest biased scores; the `kept` best groups; among their experts
    the k largest biased scores (ties: the lower index); weights
    renormalised over the picks and scaled."""
    T, E = logits.shape
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    s = s.astype(np.float32)
    b = s + bias
    per = E // groups
    picks, weights, keeps = [], [], []
    for t in range(T):
        g = b[t].reshape(groups, per)
        gscore = np.sort(g, axis=1)[:, -2:].sum(1)
        keep = np.argsort(-gscore, kind="stable")[:kept]
        allowed = np.full(E, -np.inf, np.float32)
        for j in keep:
            allowed[j * per:(j + 1) * per] = b[t, j * per:(j + 1) * per]
        top = np.argsort(-allowed, kind="stable")[:k]
        w = s[t, top]
        picks.append(top)
        weights.append(scale * w / (w.sum() + 1e-20))
        keeps.append(sorted(keep))
    return np.array(picks), np.array(weights, np.float32), keeps


def test_the_router_is_the_numpy_transcription():
    """Random logits; a tie inside a group and between two groups' scores;
    a bias that flips a pick; every token's kept groups."""
    r = expert_ffn.Router("sigmoid", True, 4, 2, True, 2.5, 0)
    rng = np.random.RandomState(0)
    logits = rng.randn(40, 16).astype(np.float32) * 1.5
    logits[0, :] = 0.0                           # every score a tie
    logits[1, 4:8] = logits[1, 0:4]              # groups 0 and 1 tie
    logits[2, 8] = logits[2, 9]                  # a tie inside a group
    bias = (rng.randn(16) * 0.02).astype(np.float32)
    want_p, want_w, want_keep = numpy_route(logits, bias, 4, 2, 4, 2.5)
    picks, weight, kept = expert_ffn.route(r, jnp.asarray(logits),
                                            jnp.asarray(bias), 4)
    assert np.array_equal(np.asarray(picks), want_p)
    np.testing.assert_allclose(np.asarray(weight), want_w, rtol=2e-6)
    assert [list(np.flatnonzero(row)) for row in np.asarray(kept)] \
        == want_keep
    assert np.allclose(np.asarray(weight).sum(1), 2.5, rtol=1e-5)
    # the bias picks and does not weigh: a large one on expert 13 pulls it
    # (and its group) in, and its weight is still its unbiased score's
    flip = bias.copy()
    flip[13] = 1.0
    p2, w2, _ = expert_ffn.route(r, jnp.asarray(logits), jnp.asarray(flip),
                                  4)
    p2, w2 = np.asarray(p2), np.asarray(w2)
    assert (p2 == 13).any(1).all() and not (want_p == 13).any(1).all()
    want_p2, want_w2, _ = numpy_route(logits, flip, 4, 2, 4, 2.5)
    assert np.array_equal(p2, want_p2)
    np.testing.assert_allclose(w2, want_w2, rtol=2e-6)
    # and the other family's router is a value of the same description
    soft = expert_ffn.Router("softmax", True, 0, 0, False, 6.0, 8)
    p3, w3, none = expert_ffn.route(soft, jnp.asarray(logits),
                                     jnp.asarray(bias), 4)
    sm = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    assert none is None and np.array_equal(
        np.asarray(p3), np.argsort(-(sm + bias), 1, kind="stable")[:, :4])
    np.testing.assert_allclose(
        np.asarray(w3), 6.0 * np.take_along_axis(sm, np.asarray(p3), 1),
        rtol=2e-6)


def test_a_token_none_of_whose_groups_is_local_costs_no_row():
    """This chip holds group 0's experts.  A bias that keeps every token
    out of group 0 leaves the grouped matmuls no row, counts no group hit,
    and the layer's experts give the shared expert's part alone."""
    cfg = get_model_config("deepseek_v3", "tiny", dtype=F32,
                           moe_expert_count=4)
    key = REF.seed_key(REF.seed_arg(SEED))
    lp = REF.layer_params(key, np.uint32(1), S, F32, False)
    h = jax.random.normal(jax.random.PRNGKey(0), (24, S.hidden))
    valid = jnp.arange(24) < 20
    experts = {n: jnp.concatenate([w, jnp.ones_like(w)])
               for n, w in lp["experts"].items()}
    names = expert_ffn.count_names(cfg)
    got, counts = expert_ffn.moe(cfg, lp, experts, 0, h, valid)
    counts = dict(zip(names, np.asarray(counts)))
    mm = functools.partial(REF._mm, precision=None)
    routed, _ = REF.moe_parts(h[None], lp, S, mm)
    assert np.abs(np.asarray(got) - np.asarray(routed)[0])[:20].max() < TOL
    assert counts["router_tokens"] == 20 and counts["picks"] == 80
    assert 0 < counts["group_hit_tokens"] < 20
    assert 0 < counts["local_rows"] <= 4 * counts["group_hit_tokens"]
    away = dict(lp, moe_router_bias=lp["moe_router_bias"].at[:4].add(-5.0))
    got, counts = expert_ffn.moe(cfg, away, experts, 0, h, valid)
    counts = dict(zip(names, np.asarray(counts)))
    assert counts["group_hit_tokens"] == 0 and counts["local_rows"] == 0
    assert counts["router_tokens"] == 20
    assert not np.asarray(got).any()


@pytest.mark.kernels
@pytest.mark.parametrize("drawn", [0.0, 5.0],
                         ids=["as_routed", "every_pick_local"])
def test_the_experts_through_the_kernel_are_the_ragged_dots(monkeypatch,
                                                            drawn):
    """`_moe` with the grouped-matmul kernel (the chip's path, interpreted)
    on the layer of the test above, a later layer of a two-layer stack:
    under the grouped sigmoid router as it routes, and with every pick
    drawn to the four experts held here (nine counters then)."""
    cfg = get_model_config("deepseek_v3", "tiny", dtype=F32,
                           moe_expert_count=4)
    lp = REF.layer_params(REF.seed_key(REF.seed_arg(SEED)), np.uint32(1), S,
                          F32, False)
    lp = dict(lp, moe_router_bias=lp["moe_router_bias"].at[:4].add(drawn))
    h = jax.random.normal(jax.random.PRNGKey(0), (24, S.hidden))
    experts = {n: jnp.concatenate([jnp.ones_like(w), w])
               for n, w in lp["experts"].items()}
    counts, passes = moe_through_the_kernel(
        monkeypatch, cfg, lp, experts, 1, h, jnp.arange(24) < 20, TOL)
    assert passes == 1 and len(counts) == 10
    if drawn:
        assert counts["local_rows"] == 20 * 4
        assert counts["experts_reached"] == 4


def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts over 4 shares of 4 (a group each): each share's
    program gives attention, the shared expert and ITS experts' part; with
    the first two counted once, the four add up to the reference layer that
    holds all 16."""
    one = dataclasses.replace(S, layers=2)       # the dense layer, then one
    whole = dataclasses.replace(one, local_first=0, local_count=S.experts)
    p = prompt(24, seed=3)
    pos = jnp.arange(len(p), dtype=jnp.int32)[None]
    key = REF.seed_key(REF.seed_arg(SEED))
    x0 = jnp.take(REF.top_param(key, "tok_embed", whole, F32),
                  jnp.asarray(p)[None], 0)
    x0 = REF.block(x0, REF.layer_params(key, np.uint32(0), whole, F32, True),
                   pos, whole, True)
    lp = REF.layer_params(key, np.uint32(1), whole, F32, False)
    uncut = np.asarray(REF.block(x0, lp, pos, whole, False))
    once = np.asarray(REF.block(x0, lp, pos, whole, False, None,
                                ("shared_only",)))
    parts = []
    for first in range(0, S.experts, 4):
        share = dataclasses.replace(one, local_first=first, local_count=4)
        cfg = get_model_config(
            "deepseek_v3", "tiny", dtype=F32, num_layers=2,
            moe_expert_first=first, moe_expert_count=4)
        params = REF.make_params(SEED, share, F32)
        x, _ = latent_ops._forward(
            cfg, params, ragged_ops.init_arena(cfg, 4, 16),
            jnp.asarray(p)[None], pos, jnp.ones((1, len(p)), bool),
            jnp.arange(4, dtype=jnp.int32)[None], "fresh")
        parts.append(np.asarray(x))
        # and the share alone is the reference with that share
        lp_i = REF.layer_params(key, np.uint32(1), share, F32, False)
        assert np.abs(parts[-1] - np.asarray(
            REF.block(x0, lp_i, pos, share, False))).max() < TOL
    assert np.abs(parts[0] - parts[1]).max() > 100 * TOL   # shares differ
    assert np.abs(sum(parts) - 3 * once - uncut).max() < 4 * TOL
    assert np.abs(uncut - once).max() > 100 * TOL


# ----------------------------------------------------------------------
# the kernel at 128 heads, and YaRN's scale through it
# ----------------------------------------------------------------------
@pytest.fixture
def interpret(monkeypatch):
    import jax.experimental.pallas as pl
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))


def test_the_query_tile_follows_the_head_count():
    assert [mla_paged.queries_per_step(h) for h in (2, 4, 64, 128, 256,
                                                    1024)] \
        == [8, 8, 8, 4, 2, 1]


@pytest.mark.kernels
@pytest.mark.parametrize("Q", [1, 8])
def test_the_paged_kernel_at_128_heads_with_the_scaled_softmax(interpret, Q):
    """Interpret mode at a head count of 128 (a chunk's tiles are 4 queries
    x 128 heads, two of them a row at Q = 8) with the score scale that
    carries YaRN's m^2."""
    rng = np.random.RandomState(0)
    arena = jnp.asarray(rng.randn(2, 12, 8, 128), F32)
    tables = jnp.asarray(rng.randint(0, 12, (3, 9)), jnp.int32)
    qa = jnp.asarray(rng.randn(3, Q, 128, 16), F32)
    qr = jnp.asarray(rng.randn(3, Q, 128, 8), F32)
    pos0 = jnp.asarray([0, 8 * 9 - Q, 21], jnp.int32)
    n_valid = jnp.asarray([Q, Q, max(Q - 3, 0)], jnp.int32)
    scale = S.softmax_scale
    assert scale == pytest.approx((0.1 * np.log(8.0) + 1) ** 2 / 24 ** 0.5)
    want = mla_paged.mla_paged_reference(qa, qr, arena, tables, pos0,
                                         n_valid, 1, scale)
    got = mla_paged.mla_paged_attention(qa, qr, arena, tables, pos0, n_valid,
                                        jnp.asarray(1), scale)
    np.testing.assert_allclose(written(got, n_valid), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.kernels
@pytest.mark.parametrize("Q,pos0,n_valid", [
    # decode: a row that ends a key tile (8 entries of 8 keys), one that
    # opens the next, an inactive row between, one at the table's end
    pytest.param(1, [63, 0, 64, 71], [1, 0, 1, 1], id="decode"),
    # tiles of 4 queries x 128 heads: whole tiles, a second tile with no
    # real query, a row with none, a second tile with one real query
    pytest.param(8, [64, 0, 9, 56], [8, 3, 0, 5], id="chunk-rows"),
])
def test_the_walk_at_128_heads(interpret, Q, pos0, n_valid):
    """The list of live (row, query tile, key tile) items at 128 heads,
    against the gather; garbage past the live blocks."""
    rng = np.random.RandomState(3)
    arena = jnp.asarray(rng.randn(2, 12, 8, 128), F32)
    tables = rng.randint(0, 12, (4, 9))
    for b, (p, n) in enumerate(zip(pos0, n_valid)):
        tables[b, (p + max(n, 1) - 1) // 8 + 1:] = -5 if b % 2 else 10 ** 6
    args = (jnp.asarray(rng.randn(4, Q, 128, 16), F32),
            jnp.asarray(rng.randn(4, Q, 128, 8), F32), arena,
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos0, jnp.int32),
            jnp.asarray(n_valid, jnp.int32))
    want = mla_paged.mla_paged_reference(*args, 1, S.softmax_scale)
    got = mla_paged.mla_paged_attention(*args, jnp.asarray(1),
                                        S.softmax_scale)
    np.testing.assert_allclose(written(got, n_valid), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.kernels
def test_decode_and_chunks_through_the_kernel_match_the_gather(
        interpret, monkeypatch):
    """The decode and chunk programs of the single form on an arena a
    prefill has filled, once on the CPU's path (the absorbed gather) and
    once through the kernel (platform gate flipped)."""
    import deepspeed_tpu.utils.device as device_mod
    eng = engine()
    p = prompt(90, seed=2)
    eng.put([1], [p])
    while eng.query(1) is None:
        eng.step()
    first = int(np.asarray(eng.query(1)).argmax())
    table = eng.state.block_table(eng.state.seqs[1])
    tables = jnp.asarray(np.stack([table] + [np.zeros(32, np.int32)] * 3))
    # (the counters' number follows the platform's gate, flipped below)
    arena = functools.partial(arena_copy, eng)
    on = jnp.asarray([True, False, False, False])
    decode = (jnp.asarray([first, 0, 0, 0]), jnp.asarray([90, 0, 0, 0]),
              tables, on)
    chunk = (jnp.asarray(np.stack([prompt(32, seed=s) for s in range(4)])),
             jnp.asarray([90, 0, 0, 0]), jnp.asarray([19, 0, 0, 0]), tables,
             on)
    fused_cfg = dataclasses.replace(eng.cfg, attn_impl="pallas")
    dense, _ = latent_ops.decode_core(eng.cfg, eng.params, arena(), *decode)
    dense_c = latent_ops.prefill_chunks(eng.cfg, eng.params, arena(), *chunk)
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    fused, _ = latent_ops.decode_core(fused_cfg, eng.params, arena(),
                                      *decode)
    fused_c = latent_ops.prefill_chunks(fused_cfg, eng.params, arena(),
                                        *chunk)
    assert np.abs(np.asarray(fused - dense))[0].max() < TOL
    assert np.abs(np.asarray(fused_c[0] - dense_c[0]))[0].max() < TOL


def test_a_latent_model_without_rope_scaling_rotates_as_before():
    """`_rope_pairs` without a scaling is the plain rotation; with YaRN it
    is the reference's blended frequencies."""
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 3, 8))
    pos = jnp.arange(100, 112)
    cfg = get_model_config("deepseek_v3", "tiny")
    plain = latent_ops._rope_pairs(x, pos, 1e4)
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(REF._rope(
            x[None], pos[None], REF.yarn_inv_freq(S, plain=True)))[0],
        atol=1e-5)
    m2 = latent_ops._yarn_score_factor(cfg)
    blended = latent_ops._rope_pairs(x, pos, 1e4, cfg.rope_scaling)
    np.testing.assert_allclose(
        np.asarray(blended), np.asarray(REF._rope(
            x[None], pos[None], REF.yarn_inv_freq(S)))[0], atol=1e-5)
    assert np.abs(np.asarray(blended - plain)).max() > 0.1
    assert m2 / 24 ** 0.5 == pytest.approx(S.softmax_scale)
    assert latent_ops._yarn_score_factor(
        get_model_config("longcat_flash", "tiny")) == 1.0
    # the frequencies of the published model: ramp from pair 10 to pair 23
    real = REF.sizes(harness.load_json(harness.BENCH_DIR, "configs",
                                       "deepseek-v3.json"))
    f = 1e4 ** (-np.arange(32) / 32.0)
    inv = REF.yarn_inv_freq(real)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    assert inv[16] == pytest.approx(
        f[16] * (1 - 6 / 13) + f[16] / 40 * (6 / 13), rel=1e-6)
    assert real.softmax_scale == pytest.approx(1.8738 / 192 ** 0.5, rel=1e-4)


# ----------------------------------------------------------------------
# what cannot serve it refuses, with a reason
# ----------------------------------------------------------------------
def _loop(eng, **kw):
    from deepspeed_tpu.serving import ServeLoop
    return ServeLoop(eng, ds.ServingConfig.from_dict(kw))


REFUSED = {
    "tensor_parallel": (ValueError, "tensor parallelism", lambda: engine(
        engine_kw=dict(tensor_parallel_size=2))),
    "expert_paging": (ValueError, "expert paging", lambda: build_engine(
        "deepseek_v3", "tiny", dtype=F32,
        serving_config=ds.ServingConfig.from_dict(
            {"moe": {"enabled": True}}))),
    "prefix_cache": (NotImplementedError, "prefix cache", lambda: _loop(
        engine(), prefix_cache_blocks=4)),
    "page_export": (NotImplementedError, "page export/import", lambda:
                    engine().read_kv_blocks([0])),
    "lora": (NotImplementedError, "LoRA adapters", lambda:
             engine().attach_lora({"a": None, "b": None})),
    "speculative": (ValueError, "draft-verify support", lambda: _loop(
        engine(), decode_burst=4,
        speculative={"mode": "prompt_lookup"})),
    "verify_span": (NotImplementedError, "speculative verify", lambda:
                    ragged_ops._span_core(engine().cfg, *[None] * 7)),
    "loss_fn": (NotImplementedError, "no latent-attention", lambda:
                Transformer(engine().cfg).loss_fn(None, None)),
    "initialize": (NotImplementedError, "initialize", lambda: ds.initialize(
        model=Transformer(engine().cfg), config={"train_batch_size": 8}
    ).train_batch({"input_ids": np.zeros((8, 16), np.int32)})),
    "dense_prefix_of_a_double_block": (
        ValueError, "latent_form", lambda: get_model_config(
            "longcat_flash", "tiny", latent_dense_layers=1)),
    "shared_expert_in_a_double_block": (
        ValueError, "no shared", lambda: get_model_config(
            "longcat_flash", "tiny", moe_shared_expert_ffn=32)),
    "groups_that_do_not_divide": (
        ValueError, "moe_router_groups", lambda: get_model_config(
            "deepseek_v3", "tiny", moe_router_groups=3)),
    "top_k_past_the_kept_groups": (
        ValueError, "moe_router_groups", lambda: get_model_config(
            "deepseek_v3", "tiny", moe_router_groups_kept=1, moe_top_k=6)),
    "all_layers_dense": (ValueError, "latent_form", lambda: get_model_config(
        "deepseek_v3", "tiny", latent_dense_layers=4)),
    "llama3_scaling": (ValueError, "rope \\(plain or yarn\\)", lambda:
                       get_model_config("deepseek_v3", "tiny", rope_scaling=(
                           "linear", 2.0))),
    "sigmoid_router_on_a_dense_model": (
        ValueError, "latent-attention double block", lambda:
        get_model_config("llama", "tiny", moe_router_scores="sigmoid")),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_a_path_that_cannot_serve_the_layer_refuses(path):
    error, message, build = REFUSED[path]
    with pytest.raises(error, match=message):
        build()


def test_the_loop_drains_the_group_counters():
    """`ServeLoop` over the tiny engine: the grouped router's two counters
    reach the telemetry beside the five every latent stack has, about half
    the scored tokens keep the local group (2 of 4 kept, one held)."""
    from deepspeed_tpu.serving import ServeLoop
    loop = ServeLoop(engine(), ds.ServingConfig())
    rng = np.random.RandomState(0)
    for n in (9, 30, 41):
        loop.submit(rng.randint(0, 512, n).astype(np.int32),
                    max_new_tokens=2 * expert_ffn.COUNT_DRAIN_STEPS + 3)
    while loop.has_work:
        loop.step()
    tel = loop.telemetry.counters
    assert tel["moe_router_tokens"] > 0
    assert tel["moe_picks"] == 4 * tel["moe_router_tokens"]
    assert 0.25 < tel["moe_group_hit_tokens"] / tel["moe_router_tokens"] < 0.75
    assert tel["moe_zero_picks"] == 0
