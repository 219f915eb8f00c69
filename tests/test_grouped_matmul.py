"""`ops/grouped_matmul.py` in Pallas interpret mode on the CPU, at tiny
widths, against `lax.ragged_dot` over the same stack (what the kernel
replaced on the chip and what every other platform still runs); the item
list's properties; and the per-layer metrics that read its trace names."""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.readers import op_share
from deepspeed_tpu.ops import grouped_matmul as gm

pytestmark = pytest.mark.kernels

K, N, STACK = 64, 256, 12
# name: (rows of the buffer, row tile, rows of each of the layer's 6
# experts, the layer's first expert among the stack of 12)
CASES = {
    "empty_experts": (256, 128, [0, 50, 0, 100, 30, 0], 0),
    "one_expert_takes_every_row": (256, 128, [0, 0, 256, 0, 0, 0], 0),
    "a_tile_shared_by_three": (256, 128, [40, 40, 40, 8, 0, 100], 0),
    "zero_live_rows": (256, 128, [0, 0, 0, 0, 0, 0], 0),
    "rows_not_whole_tiles": (176, 128, [20, 20, 100, 10, 0, 26], 0),
    "a_later_layer_of_the_stack": (384, 128, [10, 20, 30, 40, 50, 200], 6),
    "the_buffer_one_tile": (96, 96, [1, 2, 3, 4, 5, 6], 6),
    "small_tiles": (256, 64, [3, 130, 5, 60, 1, 1], 0),
}
# the same, each expert's segment from a row-tile edge in the longer buffer
# (`aligned_rows`)
ALIGNED = {
    "aligned_empty_experts": (256, 128, [0, 50, 0, 100, 30, 0], 0),
    "aligned_one_expert_takes_every_row": (256, 128, [0, 0, 256, 0, 0, 0], 0),
    "aligned_more_than_a_tiles_rows": (384, 128, [10, 200, 0, 130, 1, 43], 6),
    "aligned_zero_live_rows": (256, 128, [0, 0, 0, 0, 0, 0], 0),
    "aligned_small_tiles": (256, 64, [3, 130, 5, 60, 1, 1], 0),
    # the worst routing: every expert a row past whole tiles fills the
    # buffer's `rows // tile + experts` tiles to the last
    "aligned_every_expert_a_row_past_a_tile": (390, 64, [65] * 6, 0),
    "aligned_every_expert_a_row_past_two": (774, 64, [129] * 6, 6),
}
MODES = {"gate_up_silu": jax.nn.silu, "gate_up_relu": jax.nn.relu,
         "down": None}


def keys_of(sizes, rows):
    """The end-to-end buffer's keys: a segment's number; E for a row of
    none."""
    sizes = np.asarray(sizes)
    keys = np.full(rows, len(sizes))
    keys[:sizes.sum()] = np.repeat(np.arange(len(sizes)), sizes)
    return jnp.asarray(keys, jnp.int32)


def case_of(name):
    """(rows, tile, sizes, first, aligned, where each live row of the
    end-to-end buffer lies in the buffer the kernel runs over)."""
    aligned = name in ALIGNED
    rows, tile, sizes, first = (ALIGNED if aligned else CASES)[name]
    # `sort_rows`' inverse: the place of each row of the keys' order
    at = np.argsort(np.asarray(gm.sort_rows(
        keys_of(sizes, rows), len(sizes), tile, aligned)))[:sum(sizes)]
    return rows, tile, sizes, first, aligned, at


@pytest.fixture
def interpret(monkeypatch):
    import jax.experimental.pallas as pl
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))


def operands(rows, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    return f(rows, K), f(STACK, K, N), f(STACK, K, N)


def ragged(x, weights, sizes, first, gate_act):
    """The three calls `expert_ffn.moe` makes off the chip."""
    groups = jnp.zeros((STACK,), jnp.int32).at[
        first:first + len(sizes)].set(jnp.asarray(sizes, jnp.int32))
    out = [jax.lax.ragged_dot(x, w, groups,
                              preferred_element_type=jnp.float32)
           for w in weights]
    return out[0] if gate_act is None else gate_act(out[0]) * out[1]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", [*CASES, *ALIGNED])
def test_the_kernel_is_ragged_dot_on_the_live_rows(interpret, case, mode):
    rows, tile, sizes, first, aligned, at = case_of(case)
    gate_act = MODES[mode]
    x, wg, wu = operands(rows)
    weights = (wg,) if gate_act is None else (wg, wu)
    size = jnp.asarray(sizes, jnp.int32)
    items = gm.list_items(size, rows, tile, first, aligned=aligned)
    # the aligned buffer: the same rows behind its segments, others between
    xk = x[np.minimum(np.asarray(gm.sort_rows(
        keys_of(sizes, rows), len(sizes), tile, True)), rows - 1)] \
        if aligned else x
    got = gm.grouped_matmul(xk, weights, items, tile=tile, gate_act=gate_act,
                            cols=128)
    assert got.shape == (gm.aligned_rows(rows, tile, len(sizes))
                         if aligned else rows, N)
    assert got.dtype == jnp.float32
    n = sum(sizes)
    want = np.asarray(ragged(x, weights, sizes, first, gate_act))[:n]
    assert np.abs(np.asarray(got)[at] - want).max(initial=0.0) < 1e-3
    # and with the output in one column block
    whole = gm.grouped_matmul(xk, weights, items, tile=tile,
                              gate_act=gate_act)
    assert np.array_equal(np.asarray(whole)[at], np.asarray(got)[at])


def test_the_fused_pass_casts_once_to_the_models_dtype(interpret):
    """bf16 operands, float32 products and activation, one cast: what
    `_moe` computed with `(gate_act(g) * u).astype(dt)`."""
    rows, tile, sizes, first = CASES["a_tile_shared_by_three"]
    x, wg, wu = (a.astype(jnp.bfloat16) for a in operands(rows, seed=1))
    items = gm.list_items(jnp.asarray(sizes, jnp.int32), rows, tile, first)
    got = gm.grouped_matmul(x, (wg, wu), items, tile=tile,
                            gate_act=jax.nn.silu, out_dtype=jnp.bfloat16)
    want = ragged(x, (wg, wu), sizes, first, jax.nn.silu).astype(
        jnp.bfloat16)
    n = sum(sizes)
    assert got.dtype == jnp.bfloat16
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    # a bf16 ulp at the products' size, where the two sums round apart
    assert gap[:n].max() <= 2 ** -7 * np.abs(np.asarray(want, np.float32)
                                             ).max()


def test_one_weight_with_a_gate_or_two_without_is_refused():
    x, wg, wu = operands(128)
    items = gm.list_items(jnp.zeros((6,), jnp.int32), 128, 128)
    with pytest.raises(ValueError, match="gate"):
        gm.grouped_matmul(x, (wg,), items, tile=128, gate_act=jax.nn.silu)
    with pytest.raises(ValueError, match="gate"):
        gm.grouped_matmul(x, (wg, wu), items, tile=128)


@pytest.mark.parametrize("case", [*CASES, *ALIGNED])
def test_the_items_cover_every_live_row_once_in_expert_order(case):
    rows, tile, sizes, first, aligned, at = case_of(case)
    size = jnp.asarray(sizes, jnp.int32)
    items = gm.list_items(size, rows, tile, first, aligned=aligned)
    expert, t, lo, hi, count = (np.asarray(a) for a in items)
    slots = gm.item_slots(rows, tile, len(sizes))
    assert expert.shape == t.shape == lo.shape == hi.shape == (slots,)
    n = int(count[0])
    assert n <= slots
    # every live row is written once, by the expert that owns it
    buffer = gm.aligned_rows(rows, tile, len(sizes)) if aligned else rows
    owner = np.full(buffer, -1)
    for e, ti, a, b in zip(expert[:n], t[:n], lo[:n], hi[:n]):
        assert 0 <= a < b <= tile
        span = slice(ti * tile + a, ti * tile + b)
        assert span.stop <= buffer and (owner[span] == -1).all()
        owner[span] = e
    want = np.repeat(first + np.arange(len(sizes)), sizes)
    assert np.array_equal(owner[at], want)
    assert (owner == -1).sum() == buffer - len(want)
    if aligned:
        # a tile is never shared: an item an expert's tile, from its first
        # row; an empty expert costs none, one of more than a tile's rows
        # spans whole tiles
        assert (lo[:n] == 0).all() and len(set(t[:n])) == n
        assert n == sum(-(-s // tile) for s in sizes) <= buffer // tile
        live = expert[:n]
        assert (hi[:n][:-1][live[1:] == live[:-1]] == tile).all()
        # `sort_rows` builds that buffer: a segment's rows in order from a
        # tile edge, then its padding (indices past the rows'), the rows of
        # no segment behind them all
        source = np.asarray(gm.sort_rows(keys_of(sizes, rows), len(sizes),
                                         tile, True))
        assert sorted(source) == list(range(buffer))
        assert np.array_equal(source[at], np.arange(len(want)))
        assert (source[owner == -1] >= len(want)).all()
        first_rows = (np.cumsum(sizes) - sizes)[np.asarray(sizes) > 0]
        assert (at[first_rows] % tile == 0).all()
        assert (np.diff(at)[np.diff(want) == 0] == 1).all()
    # expert-major, an expert's tiles ascending: no tile is left and met again
    assert (np.diff(expert[:n]) >= 0).all() and (np.diff(t[:n]) >= 0).all()
    # dead entries repeat the last live one: no index moves, nothing is copied
    for a in (expert, t, lo, hi):
        assert (a[n:] == a[max(n - 1, 0)]).all()
    assert first <= expert.min() and expert.max() < first + len(sizes)
    # this grid's order reads each reached expert's weights once
    assert int(gm.weight_fetches(items)) == sum(s > 0 for s in sizes)


@pytest.mark.parametrize("rows,tile", [
    (16, 16), (96, 96), (128, 128), (192, 96), (176, 128), (1024, 128),
    (2048, 128), (24576, 128), (320, 80)])
def test_the_row_tile_follows_the_buffer(rows, tile):
    assert gm.row_tile(rows) == tile
    assert gm.item_slots(rows, tile, 16) == -(-rows // tile) + 16


@pytest.mark.parametrize("name,rows,groups,whole,tile,aligned,buffer", [
    # granite's prefill program: 512 slots x 10 picks, 36 experts held
    ("granite_prefill", 5120, 36, True, 128, True, 9728),
    # smallthinker's 4096-row pass x 6 picks over 64 experts
    ("smallthinker_pass", 24576, 64, True, 128, True, 32768),
    # their decode programs: a few rows an expert, an item either way
    ("granite_decode", 960, 36, True, 96, False, 960),
    ("smallthinker_decode", 192, 64, True, 96, False, 192),
    # a share's buffer (deepseek's prefill: 2,048 of 8,192 picks) keeps its
    # pieces end to end, a tile's rows an expert or not
    ("deepseek_share", 2048, 16, False, 128, False, 2048),
    ("longcat_share", 1024, 16, False, 128, False, 1024),
])
def test_the_segments_are_aligned_where_the_shape_says(
        name, rows, groups, whole, tile, aligned, buffer):
    assert gm.row_tile(rows) == tile
    assert gm.aligns(rows, tile, groups, whole) is aligned
    if aligned:
        # no more tiles than the item list has slots: the grid is as long
        assert gm.aligned_rows(rows, tile, groups) == buffer \
            == tile * gm.item_slots(rows, tile, groups) <= 2 * rows + tile


@pytest.mark.parametrize("K_,N_,weights,cols", [
    (7168, 2048, 2, 256),       # deepseek's gate and up: 7.3 MB a step
    (2048, 7168, 1, 1792),      # ... and down
    (6144, 2048, 2, 256),       # longcat
    (2560, 768, 2, 768),        # smallthinker: an expert's weights whole
    (768, 2560, 1, 2560),
    (64, 32, 2, 32),            # no 128-lane tiles: the width itself
])
def test_the_column_block_follows_the_weights_shape(K_, N_, weights, cols):
    assert gm._column_block(K_, N_, weights, 2) == cols
    assert weights * K_ * cols * 2 <= gm.WEIGHT_BLOCK_BYTES


# ----------------------------------------------------------------------
# `expert_ffn.moe` through the kernel: the families' own test files call
# these with their layer and engine (`test_latent_serving.py`,
# `test_latent_single_serving.py`, `test_hybrid_serving.py`,
# `test_window_kernels.py`)
# ----------------------------------------------------------------------
def arena_copy(eng):
    """A copy of the engine's arena for a program traced AFTER a test has
    flipped the platform's gate: the counters' number follows the gate
    (`expert_ffn.count_names`)."""
    from deepspeed_tpu.inference.v2 import expert_ffn
    names = expert_ffn.count_names(eng.cfg)
    return {**jax.tree.map(jnp.copy, eng.arena),
            "moe_counts": jnp.zeros((len(names),), jnp.int32)}


def moe_through_the_kernel(monkeypatch, cfg, lp, experts, li, h, valid, tol,
                           router_in=None):
    """`_moe` as the CPU runs it (three `ragged_dot` calls) and with the
    platform's gate flipped (the kernel, interpreted) on the same inputs:
    the same output and router counts, and the kernel's three counts behind
    them.  Returns (the counts by name, the passes the step took)."""
    import jax.experimental.pallas as pl

    import deepspeed_tpu.utils.device as device_mod
    from deepspeed_tpu.inference.v2 import expert_ffn
    want, counts = expert_ffn.moe(cfg, lp, experts, li, h, valid,
                                   router_in=router_in)
    names = expert_ffn.count_names(cfg)
    assert not set(expert_ffn.KERNEL_COUNT_NAMES) & set(names)
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=True))
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    got, kernel_counts = expert_ffn.moe(cfg, lp, experts, li, h, valid,
                                         router_in=router_in)
    assert expert_ffn.count_names(cfg) \
        == names + expert_ffn.KERNEL_COUNT_NAMES
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < tol
    assert np.array_equal(np.asarray(kernel_counts)[:len(names)],
                          np.asarray(counts))
    c = dict(zip(expert_ffn.count_names(cfg),
                 np.asarray(kernel_counts).tolist()))
    # the experts each pass reaches, from the router's picks
    logits = (h if router_in is None else router_in).astype(
        jnp.float32) @ lp["moe_gate"].astype(jnp.float32)
    topi, _, _ = expert_ffn.route(expert_ffn.router_of(cfg), logits,
                                   lp.get("moe_router_bias"), cfg.moe_top_k)
    ids = np.asarray(topi)[np.asarray(valid)].reshape(-1) \
        - cfg.moe_expert_first
    sizes = np.bincount(ids[(ids >= 0) & (ids < cfg.local_experts)],
                        minlength=cfg.local_experts)
    cap = expert_ffn.local_rows_cap(
        h.shape[0] * cfg.moe_top_k, cfg.local_experts,
        cfg.moe_experts + cfg.moe_zero_experts)
    ends = np.cumsum(sizes)
    passes = range(0, int(sizes.sum()), cap)
    reached = sum(int(((np.clip(ends, lo, lo + cap)
                        - np.clip(ends - sizes, lo, lo + cap)) > 0).sum())
                  for lo in passes)
    assert c["local_rows"] == sizes.sum()
    # this grid reads every reached expert's weights once a matmul
    assert c["experts_reached"] == reached == c["expert_weight_fetches"]
    # and its live items are the list's, in the layout the shape asks for
    tile = gm.row_tile(cap)
    aligned = gm.aligns(cap, tile, cfg.local_experts,
                        cap == h.shape[0] * cfg.moe_top_k)
    assert c["expert_items"] == sum(int(gm.list_items(
        jnp.asarray(np.clip(ends, lo, lo + cap)
                    - np.clip(ends - sizes, lo, lo + cap)), cap, tile,
        aligned=aligned).count[0]) for lo in passes) >= reached
    return c, len(passes)


# ----------------------------------------------------------------------
# the per-layer metrics that read the kernel by its trace name
# ----------------------------------------------------------------------
SHARES = ["expert_matmul_device_share.closed",
          "expert_matmul_prefill_share.closed",
          "expert_matmul_device_share.ktok.closed",
          "expert_matmul_prefill_share.ktok.closed"]


def metric(name):
    return harness.load_json(harness.BENCH_DIR, "metrics", name + ".json")


@pytest.mark.parametrize("name", SHARES)
def test_the_share_counts_ragged_dot_and_the_kernel_alike(name):
    """On a hand-made view: a parent's programs (XLA's `ragged-dot-none`
    custom calls) and this tree's (`grouped_matmul`) read the same share,
    and the programs the metric does not name are left out."""
    spec = metric(name)
    assert spec["reader"] == "op_share"
    prefill = "prefill" in name
    mine, other = (("jit_prefill_full", "jit_decode_step") if prefill
                   else ("jit_decode_step", "jit_prefill_full"))
    parent = {
        "ragged-dot-none.1_custom-call_f32_128_2048__tpu_custom_call": 0.2,
        "ragged-dot-none_custom-call_f32_128_2048__tpu_custom_call": 0.1,
        "ragged-dot-none.2_custom-call_f32_128_7168__tpu_custom_call": 0.1,
        "fusion.229_fusion_f32_64_129280_": 0.6}
    change = {
        "grouped_matmul.8_custom-call_bf16_128_2048__tpu_custom_call": 0.3,
        "grouped_matmul.9_custom-call_f32_128_7168__tpu_custom_call": 0.1,
        "fusion.229_fusion_f32_64_129280_": 0.6}

    def view(ops):
        return {"trace": {"programs": {
            mine: {"device_s": 1.0, "runs": 4, "ops": ops},
            other: {"device_s": 5.0, "runs": 1,
                    "ops": {"grouped_matmul.3_custom-call": 5.0}}}}}

    assert op_share.read(view(parent), **spec["params"]) \
        == pytest.approx(40.0)
    assert op_share.read(view(change), **spec["params"]) \
        == pytest.approx(40.0)
    assert op_share.read(view({"fusion.1": 1.0}), **spec["params"]) is None
    if prefill:      # the chunk programs too
        chunks = {"trace": {"programs": {"jit_prefill_chunks": {
            "device_s": 2.0, "runs": 1, "ops": change}}}}
        assert op_share.read(chunks, **spec["params"]) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["expert_weight_passes.closed",
                                  "expert_weight_passes.ktok.closed"])
def test_the_passes_are_the_kernels_two_counters(name):
    from deepspeed_tpu.inference.v2 import expert_ffn
    spec = metric(name)
    assert spec["reader"] == "span_attr_ratio"
    assert spec["params"]["span"] == "serve.moe_census"
    assert (spec["params"]["num"], spec["params"]["den"]) \
        == expert_ffn.KERNEL_COUNT_NAMES[:2]
    entry = {m["name"]: m for m in json.load(open(os.path.join(
        harness.ROOT, "BENCHMARK.json")))["per_layer"]}[name]
    assert entry["unit"] == "passes" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
