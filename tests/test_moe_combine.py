"""How `expert_ffn.moe` brings the experts' outputs back to their tokens.

Where the compact buffer holds every assignment (`cap == T * k`: every
expert the router scores is held here, or the program is tiny) the k outputs
of a token are GATHERED from the sorted buffer through `order`'s inverse and
summed in float32 under the scope `experts/combine`: no loop over pieces, no
scatter-add.  Where the buffer holds a share (`cap < T * k`) the pieces and
their scatter-add stay.  Both against a dense reference that applies every
expert to every token in float32; and the per-layer metric that reads the
experts outside their matmuls from either tree's op names.

Tolerance: both sides are float32 and differ in the order of their sums
only; readings are 1e-7 on outputs of ~0.1, the limit is 2e-6.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.readers import scope_share
from deepspeed_tpu.inference.v2 import expert_ffn
from deepspeed_tpu.models import get_model_config

pytestmark = pytest.mark.serving

F32, TOL = jnp.float32, 2e-6
T, REAL = 24, 20            # rows of a pass, and the real ones among them
# the three routers, at the tiny sizes where every expert is held
WHOLE = {
    "softmax_topk_relu_gates": ("smallthinker", {}),
    "identity_outputs": ("longcat_flash", {}),
    "sigmoid_over_kept_groups": ("deepseek_v3", {}),
}
# 8 of 32 experts (+ 16 identity outputs): the buffer is 64 of 96 picks
SHARE = ("longcat_flash", dict(moe_expert_count=8, moe_expert_first=8))


def layer(family, kw):
    """(cfg, one layer's router leaves, the experts' stacks of two layers,
    rows h [T, H] and what the router reads, the real rows)."""
    cfg = get_model_config(family, "tiny", dtype=F32, **kw)
    H, F, El = cfg.hidden_size, cfg.moe_expert_ffn, cfg.local_experts
    outputs = cfg.moe_experts + cfg.moe_zero_experts
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    lp = {"moe_gate": jax.random.normal(ks[0], (H, outputs), F32)}
    if expert_ffn.router_of(cfg).bias:
        lp["moe_router_bias"] = 0.1 * jax.random.normal(ks[1], (outputs,))
    experts = {
        "w_gate_proj": jax.random.normal(ks[2], (2 * El, H, F)) / 8,
        "w_up": jax.random.normal(ks[3], (2 * El, H, F)) / 8,
        "w_down": jax.random.normal(ks[4], (2 * El, F, H)) / 8}
    h = jax.random.normal(ks[5], (T, H), F32)
    x = jax.random.normal(ks[6], (T, H), F32)
    return cfg, lp, experts, h, x, jnp.arange(T) < REAL


def dense(cfg, lp, experts, li, h, valid, router_in):
    """(Every expert of layer `li` held here applied to every real token,
    weighted by the router's picks: a padding token gets nothing; the
    identity picks' part, the token itself by their weights, which `_moe`
    gives every row: in float32 as `_moe` forms it, so a row that gets
    nothing else equals it bit for bit).  The first in float64."""
    E, first, El = cfg.moe_experts, cfg.moe_expert_first, cfg.local_experts
    gate_act = jax.nn.relu if cfg.activation == "reglu" else jax.nn.silu
    logits = (h if router_in is None else router_in) @ lp["moe_gate"]
    topi, weight, _ = expert_ffn.route(
        expert_ffn.router_of(cfg), logits, lp.get("moe_router_bias"),
        cfg.moe_top_k)
    w = {n: np.asarray(a[li * El:(li + 1) * El], np.float64)
         for n, a in experts.items()}
    h64 = np.asarray(h, np.float64)
    g = np.einsum("th,ehf->etf", h64, w["w_gate_proj"])
    u = np.einsum("th,ehf->etf", h64, w["w_up"])
    act = np.asarray(gate_act(jnp.asarray(g, F32)), np.float64) * u
    every = np.einsum("etf,efh->eth", act, w["w_down"])        # [El, T, H]
    routed = np.zeros_like(h64)
    for t in np.flatnonzero(np.asarray(valid)):
        for e, wt in zip(np.asarray(topi)[t], np.asarray(weight)[t]):
            if first <= e < first + El:
                routed[t] += wt * every[e - first, t]
    identity = jnp.sum(jnp.where(topi >= E, weight, 0.0), axis=1,
                       keepdims=True) * h
    return routed, np.asarray(identity)


@pytest.fixture
def undefined_rows(monkeypatch):
    """Rows no group covers come out of the grouped matmuls as NaN: the
    kernel never writes them (`ops/grouped_matmul.py`), and `ragged_dot`'s
    zeros there are its own kindness."""
    real = jax.lax.ragged_dot

    def planted(x, w, groups, **kw):
        covered = jnp.arange(x.shape[0]) < jnp.sum(groups)
        return jnp.where(covered[:, None], real(x, w, groups, **kw), jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", planted)


def primitives(jaxpr, found=None):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            primitives(sub, found)
    return found


def row_scatter_adds(eqns):
    """The scatter-adds of float rows onto a [T, H] accumulator (`bincount`
    is a scatter-add of ones onto a vector of counts, and stays)."""
    return [e for e in eqns if e.primitive.name == "scatter-add"
            and e.outvars[0].aval.ndim == 2]


@pytest.mark.parametrize("router", WHOLE)
def test_gathered_outputs_are_the_dense_reference(router, undefined_rows):
    family, kw = WHOLE[router]
    cfg, lp, experts, h, x, valid = layer(family, kw)
    k = cfg.moe_top_k
    assert expert_ffn.local_rows_cap(
        T * k, cfg.local_experts, cfg.moe_experts + cfg.moe_zero_experts) \
        == T * k
    # smallthinker's router reads the layer's input
    router_in = x if family == "smallthinker" else None
    got, counts = expert_ffn.moe(cfg, lp, experts, 1, h, valid,
                                  router_in=router_in)
    routed, identity = dense(cfg, lp, experts, 1, h, valid, router_in)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    assert np.abs(routed).max() > 0.05
    assert np.abs(got - (routed + identity)).max() < TOL
    # a padding token gets nothing from the experts
    assert np.array_equal(got[REAL:], identity[REAL:])
    assert identity.any() == bool(cfg.moe_zero_experts)
    c = dict(zip(expert_ffn.count_names(cfg), np.asarray(counts)))
    assert c["picks"] == REAL * k and c["router_calls"] == 1
    assert c["local_rows"] + c["zero_picks"] == REAL * k


@pytest.mark.parametrize("router", WHOLE)
def test_the_gathered_path_has_no_loop_and_no_row_scatter(router):
    family, kw = WHOLE[router]
    cfg, lp, experts, h, x, valid = layer(family, kw)
    fn = functools.partial(expert_ffn.moe, cfg)
    eqns = primitives(jax.make_jaxpr(fn)(lp, experts, 1, h, valid).jaxpr)
    names = {e.primitive.name for e in eqns}
    assert "while" not in names and "sort" in names and "gather" in names
    assert not row_scatter_adds(eqns)
    text = jax.jit(fn).lower(lp, experts, 1, h, valid).as_text(
        debug_info=True)
    assert "experts/combine" in text
    # a fixed order of the k terms: the same bits every time
    one, _ = jax.jit(fn)(lp, experts, 1, h, valid)
    two, _ = jax.jit(fn)(lp, experts, 1, h, valid)
    assert np.array_equal(np.asarray(one), np.asarray(two))


@pytest.mark.kernels
def test_the_gathered_path_through_the_kernel(monkeypatch):
    """The chip's path, interpreted: the kernel leaves the rows no item
    covers unwritten, and the gather reads only rows an item wrote."""
    from test_grouped_matmul import moe_through_the_kernel
    cfg, lp, experts, h, x, valid = layer("smallthinker", {})
    counts, passes = moe_through_the_kernel(
        monkeypatch, cfg, lp, experts, 1, h, valid, TOL, router_in=x)
    assert passes == 1
    assert counts["local_rows"] == REAL * cfg.moe_top_k


@pytest.fixture
def unwritten_rows(monkeypatch):
    """... and out of the kernel too: the rows no live item covers (the
    padding between aligned segments, the rows past the last) come out NaN,
    where the interpreter leaves them as it made them."""
    from deepspeed_tpu.ops import grouped_matmul as gm
    real = gm.grouped_matmul

    def planted(x, weights, items, *, tile, **kw):
        out = real(x, weights, items, tile=tile, **kw)
        live = jnp.arange(items.expert.shape[0]) < items.count[0]
        row = jnp.arange(x.shape[0])[None]
        first = (items.tile * tile)[:, None]
        covered = jnp.any(live[:, None] & (row >= first + items.lo[:, None])
                          & (row < first + items.hi[:, None]), axis=0)
        return jnp.where(covered[:, None], out, jnp.nan)
    monkeypatch.setattr(gm, "grouped_matmul", planted)


@pytest.mark.kernels
def test_the_gathered_path_through_the_kernel_on_aligned_segments(
        monkeypatch, unwritten_rows):
    """A row tile of 4 makes the tiny pass what a prompt's pass is at 128
    (a tile's rows an expert or more): each expert's rows from a tile edge
    in the longer buffer, the picks found again behind the padding."""
    from test_grouped_matmul import moe_through_the_kernel

    from deepspeed_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "ROW_TILE", 4)
    cfg, lp, experts, h, x, valid = layer("smallthinker", {})
    k, El = cfg.moe_top_k, cfg.local_experts
    assert gm.row_tile(T * k) == 4 and gm.aligns(T * k, 4, El, True)
    counts, passes = moe_through_the_kernel(
        monkeypatch, cfg, lp, experts, 1, h, valid, TOL, router_in=x)
    assert passes == 1 and counts["local_rows"] == REAL * k
    topi, _, _ = expert_ffn.route(expert_ffn.router_of(cfg),
                                   x @ lp["moe_gate"], None, k)
    sizes = np.bincount(np.asarray(topi)[:REAL].reshape(-1), minlength=El)
    assert sizes.max() > 4          # an expert of more than a tile's rows
    assert counts["expert_items"] == sum(-(-s // 4) for s in sizes) \
        == int(gm.list_items(jnp.asarray(sizes), T * k, 4,
                             aligned=True).count[0])
    # and against every expert applied to every token, the padding poisoned
    got, _ = expert_ffn.moe(cfg, lp, experts, 1, h, valid, router_in=x)
    routed, identity = dense(cfg, lp, experts, 1, h, valid, x)
    got = np.asarray(got)
    assert np.isfinite(got).all()
    assert np.abs(got - (routed + identity)).max() < TOL
    assert np.array_equal(got[REAL:], identity[REAL:])


@pytest.mark.parametrize("drawn", [0.0, 8.0], ids=["as_routed", "overflow"])
def test_a_share_keeps_its_pieces_and_their_scatter_add(drawn,
                                                        undefined_rows):
    """8 of 32 experts held: the buffer is 64 rows for 96 picks.  As routed
    one piece does; with every pick drawn to the held experts the 80 local
    rows overflow it into a second piece, and the sum stays exact."""
    family, kw = SHARE
    cfg, lp, experts, h, x, valid = layer(family, kw)
    k, first, El = cfg.moe_top_k, cfg.moe_expert_first, cfg.local_experts
    cap = expert_ffn.local_rows_cap(
        T * k, El, cfg.moe_experts + cfg.moe_zero_experts)
    assert cap == 64 < T * k
    lp = dict(lp, moe_router_bias=lp["moe_router_bias"].at[
        first:first + El].add(drawn))
    fn = functools.partial(expert_ffn.moe, cfg)
    eqns = primitives(jax.make_jaxpr(fn)(lp, experts, 1, h, valid).jaxpr)
    assert "while" in {e.primitive.name for e in eqns}
    assert len(row_scatter_adds(eqns)) == 1
    assert "experts/combine" not in jax.jit(fn).lower(
        lp, experts, 1, h, valid).as_text(debug_info=True)
    got, counts = fn(lp, experts, 1, h, valid)
    c = dict(zip(expert_ffn.count_names(cfg), np.asarray(counts)))
    if drawn:
        assert c["local_rows"] == REAL * k > cap
    else:
        assert 0 < c["local_rows"] <= cap
    routed, identity = dense(cfg, lp, experts, 1, h, valid, None)
    got = np.asarray(got)
    assert np.isfinite(got).all() and routed.any()
    assert np.abs(got - (routed + identity)).max() < TOL
    assert np.array_equal(got[REAL:], identity[REAL:])


# ----------------------------------------------------------------------
# the per-layer metric that reads the experts outside their matmuls
# ----------------------------------------------------------------------
NAME = "experts_outside_matmul_prefill_share.ktok.closed"
PATH = "jit(prefill_chunks)/jit(main)/while/body/closed_call/"
ELSEWHERE = {
    PATH + "attn_window/chunk_attention chunk_attention.3": 0.20,
    PATH + "router/dot_general fusion.12": 0.05,
    PATH + "zero_experts/mul fusion.13": 0.05,
    PATH + "shared_expert/dot_general fusion.14": 0.05}
# a tree before this PR: the pieces' loop, and XLA's custom calls where the
# matmuls were not yet the kernel
PARENT = {
    **ELSEWHERE,
    PATH + "experts/sort sort.2": 0.02,
    PATH + "experts/while/body/scatter-add fusion.40": 0.25,
    PATH + "experts/while/body/jit(_where)/select_n fusion.41": 0.05,
    PATH + "experts/while/body/jit(_take)/gather fusion.42": 0.03,
    PATH + "experts/while/body/grouped_matmul grouped_matmul.8": 0.10,
    PATH + "experts/while/body/grouped_matmul grouped_matmul.9": 0.05,
    PATH + "experts/while/body/ragged_dot ragged-dot-none.1": 0.15}
CHANGE = {
    **ELSEWHERE,
    PATH + "experts/sort sort.2": 0.02,
    PATH + "experts/jit(_take)/gather fusion.42": 0.03,
    PATH + "experts/combine/sort sort.3": 0.02,
    PATH + "experts/combine/gather fusion.50": 0.08,
    PATH + "experts/combine/add fusion.51": 0.05,
    PATH + "experts/grouped_matmul grouped_matmul.8": 0.10,
    PATH + "experts/grouped_matmul grouped_matmul.9": 0.05}


def chunk_programs(ops):
    return {"programs": {
        "jit_prefill_chunks": {"device_s": 1.0, "runs": 4, "ops": ops},
        "jit_decode_step": {"device_s": 5.0, "runs": 9, "ops": {
            "jit(decode_step)/experts/combine/add fusion.7": 5.0}}}}


def test_the_share_reads_the_experts_outside_their_matmuls(monkeypatch):
    from benchmark import span_reduce
    spec = harness.load_json(harness.BENCH_DIR, "metrics", NAME + ".json")
    assert spec["reader"] == "scope_share"
    assert spec["params"]["program"] == "prefill_chunks"

    def read(ops):
        monkeypatch.setattr(span_reduce, "of_view",
                            lambda view: chunk_programs(ops))
        return scope_share.read({"trace": True}, **spec["params"])
    # the sort, the loop's scatter-add, its where and its take: not the
    # kernel's two calls, not XLA's custom call, nothing outside `experts`
    assert read(PARENT) == pytest.approx(35.0)
    # the sort, the take, and the combine's sort, gather and sum
    assert read(CHANGE) == pytest.approx(20.0)
    # no op under `experts` (a model without them; a made-up trace)
    assert read(ELSEWHERE) is None
    entry = {m["name"]: m for m in json.load(open(os.path.join(
        harness.ROOT, "BENCHMARK.json")))["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace",
        "layer": "serve engine (inference/v2/engine_v2.py, ragged_ops.py)",
        "moves": "ttft_ms_per_ktok_p50",
        "workloads": ["smallthinker-21b-a3b.decode_closed_long"]}
