"""The DeepSeek-V3 cell at a tiny size on the CPU: the reference against the
program through the harness, its six controls, its counts by hand, and the
readers of the three metrics the cell adds."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

from bench_testlib import DATA

CELL = "deepseek-v3-tiny.closed"
REAL = "deepseek-v3.decode_closed_chat"
REF = harness.load_module(harness.BENCH_DIR, "references", "deepseek_v3")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")["TPU v5 lite"]
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")


def real_config():
    return harness.load_json(harness.BENCH_DIR, "configs", "deepseek-v3.json")


def real():
    return REF.sizes(real_config())


def metric(name):
    return harness.load_json(harness.BENCH_DIR, "metrics",
                             name + ".json")["params"]


@pytest.fixture
def run_deepseek(bench_root, run_tiny):
    """The tiny cell added to the temporary root as entries (its files are
    in tests/benchmark/data): every metric the real cell lists."""
    path = os.path.join(bench_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": CELL, "config": "deepseek-v3-tiny",
                               "traffic": "closed_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    json.dump(bench, open(path, "w"))
    return lambda **kw: run_tiny(CELL, **kw)


def test_program_agrees_with_the_reference(run_deepseek):
    """A whole closed-loop run: prompts through `prefill_full` and chunk
    slots, then decode through the latent cache; every served token is the
    float32 reference's best."""
    res = run_deepseek()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert res["metrics"]["ttft_p50_ms"]["value"] > 0
    assert "out_tok_s" not in res["metrics"]


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_control_comes_out_incorrect(run_deepseek, control):
    """Through the harness, on the tiny cell's traffic.  `plain_rope` is the
    exception at THIS size: with rope parts of 8 and ranks of 16-32 the tiny
    model's attention scores spread by 0.01, its attention is uniform
    whatever the rotation, and contexts stay under 52 tokens, so served
    tokens cannot tell; the control still runs end to end here, moves the
    logits by 20 times the tolerance in `tests/test_latent_single_serving.py`
    and is refused on the chip at the published widths (PERF.md section 6),
    where the rope part carries most of a score."""
    res = run_deepseek(seconds=2.0, control=control)
    assert res["control"] == control
    c = res["compared"]["greedy_gap"]
    if control == "plain_rope":
        assert np.isfinite(c["value"]) and res["notes"]["check_tokens"] > 0
        return
    assert res["correct"] is False
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault, decides", [
    ("none", None), ("every_position", "mean"),
    ("one_position_in_64", "p99"), ("one_position", "widest")])
def test_each_reading_of_the_gaps_sees_its_kind_of_fault(fault, decides):
    """`greedy_gap` on gaps shaped like the chip's (one position in nine
    above 0, mean 0.015, 99th percentile 0.4, widest 1.8): sound under the
    cell's limit; a lift of every position, garbage at one position in 64
    (a block's edge) and one position served at random are each refused,
    each by the reading meant for it."""
    limit = harness.load_json(harness.BENCH_DIR, "traffic",
                              "decode_closed_chat.json")["greedy_gap_limit"]
    rng = np.random.RandomState(0)
    gaps = np.where(rng.rand(3200) < 0.11, rng.exponential(0.13, 3200), 0.0)
    gaps[:3] = 1.4, 1.6, 1.79
    sound, read = REF.greedy_gap(gaps)
    assert sound < 0.7 * limit
    assert 0.3 < read["p99"] < 0.45 and 0.012 < read["mean"] < 0.02
    if fault == "every_position":
        gaps = gaps + 0.03
    elif fault == "one_position_in_64":
        gaps[::64] = 0.9
    elif fault == "one_position":
        gaps[100] = 3.0
    value, read = REF.greedy_gap(gaps)
    weighted = {"widest": REF.WIDEST_WEIGHT * read["widest"],
                "p99": REF.P99_AS_WIDEST * read["p99"],
                "mean": REF.MEAN_AS_WIDEST * read["mean"]}
    assert value == max(weighted.values())
    if decides is None:
        assert value == sound
        return
    assert value > limit
    assert [k for k, v in weighted.items() if v > limit] == [decides]


def test_an_unknown_control_is_an_error():
    with pytest.raises(ValueError, match="unknown control"):
        REF._how("fp4")


def test_weights_one_call_equals_layer_by_layer():
    """The program's stacked trees (the dense layer's, the expert layers',
    the experts apart) and the reference's layer-by-layer leaves are the
    same numbers."""
    s = REF.sizes(harness.load_json(DATA, "configs",
                                    "deepseek-v3-tiny.json"))
    whole = REF.make_params(2**31 + 9, s, jnp.float32)
    key = REF.seed_key(REF.seed_arg(2**31 + 9))
    assert set(whole) == {"tok_embed", "final_norm_scale", "lm_head",
                          "dense_layers", "layers", "experts"}
    # (two compiled forms of one formula: a unit in the last place)
    close = lambda at: lambda a, b: np.testing.assert_allclose(  # noqa: E731
        a[at], b, rtol=1e-6, atol=1e-8)
    for l in range(s.layers):
        dense = l < s.dense_layers
        lp = REF.layer_params(key, np.uint32(l), s, jnp.float32, dense)
        if dense:
            jax.tree.map(close(l), whole["dense_layers"], lp)
            continue
        ex = lp.pop("experts")
        jax.tree.map(close(l - s.dense_layers), whole["layers"], lp)
        jax.tree.map(close(l - s.dense_layers), whole["experts"], ex)
    assert whole["experts"]["w_up"].shape == (3, 4, 64, 32)
    assert "w_gate" in whole["dense_layers"]["sub"][0]
    assert "w_gate" not in whole["layers"]["sub"][0]
    assert whole["layers"]["shared"]["w_down"].shape == (3, 32, 64)
    assert float(jnp.std(whole["layers"]["moe_router_bias"])) > 0.01
    # the down-projections of the experts and the shared one carry the gain
    ratio = float(jnp.std(whole["layers"]["shared"]["w_down"])
                  / jnp.std(whole["dense_layers"]["sub"][0]["w_down"]))
    assert ratio == pytest.approx(s.expert_out_gain, rel=0.1)


def test_the_file_holds_the_published_numbers():
    """The published `config.json`'s numbers under their own keys; what
    differs is listed in `reduced` and stated under `published`; no width
    is among them."""
    cfg = real_config()
    published = dict(
        first_k_dense_replace=3, hidden_size=7168, intermediate_size=18432,
        kv_lora_rank=512, max_position_embeddings=163840,
        moe_intermediate_size=2048, moe_layer_freq=1, n_group=8,
        n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=128, num_experts_per_tok=8, num_hidden_layers=61,
        num_key_value_heads=128, num_nextn_predict_layers=1,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-6, rope_theta=10000, routed_scaling_factor=2.5,
        scoring_func="sigmoid", tie_word_embeddings=False, topk_group=4,
        topk_method="noaux_tc", v_head_dim=128, vocab_size=129280,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096,
                      "type": "yarn"})
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"])
    assert {k: published[k] for k in cfg["reduced"]} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"]) == (5, 1, 16)
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k
                   for k in cfg["reduced"])
    for key in ("seeded_weights.router_logit_std",
                "seeded_weights.router_bias_std",
                "seeded_weights.expert_out_gain",
                "seeded_weights.attention_out_gain",
                "num_nextn_predict_layers", "rope", "router", "yarn"):
        assert key in cfg["assumed"], key
    assert "16 v5e chips share each layer" in cfg["deployment"]


def test_the_cut_weighs_12_37_gb_and_a_token_caches_5760_bytes():
    s = real()
    mla = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 \
        + 128 * 128 * 7168
    assert mla == 187_105_280
    norms = 2 * 7168 + 1536 + 512
    dense = mla + norms + 3 * 7168 * 18432
    expert = 3 * 7168 * 2048
    outside = mla + norms + 7168 * 256 + 256 + expert       # + shared
    assert expert / 1e6 == pytest.approx(44.04, abs=0.005)
    assert dense / 1e6 == pytest.approx(583.5, abs=0.05)
    assert outside / 1e6 == pytest.approx(233.0, abs=0.05)
    assert REF.dense_layer_params(s) == dense
    assert REF.expert_layer_params_outside(s) == outside
    total = dense + 4 * (outside + 16 * expert) + 2 * 129280 * 7168 + 7168
    assert REF.weight_bytes(s, "bfloat16") == 2 * total
    assert REF.weight_bytes(s, "bfloat16") / 1e9 == pytest.approx(12.37,
                                                                  abs=0.01)
    assert REF.latent_bytes_per_token(s, "bfloat16") == 5 * 576 * 2 == 5760
    # the arena the configuration states: 64 rows of 41 blocks and a tenth
    eng = real_config()["program"]["engine"]
    assert eng["num_blocks"] == int(64 * 41 * 1.1 + 0.999) + 3 == 2890
    assert 5 * 2890 * 64 * 640 * 2 / 1e9 == pytest.approx(1.18, abs=0.005)


def test_decode_step_bytes_by_hand():
    s = real()
    rows, ctx = 64, 86_000
    # a token picks a given one of the 256 experts with probability 8/256:
    # of the 16 local experts 16 (1 - (31/32)^64) = 13.9 get a row
    reached = 16 * (1 - (31 / 32) ** 64)
    assert REF.experts_with_a_row(s, rows) == pytest.approx(reached)
    assert reached == pytest.approx(13.90, abs=0.005)
    weights = REF.weight_bytes(s, "bfloat16") - 129280 * 7168 * 2 \
        - 4 * (16 - reached) * 3 * 7168 * 2048 * 2
    want = weights + rows * 7168 * 2 + (ctx + rows) * 5760 \
        + rows * 129280 * 4
    assert REF.decode_step_bytes(s, "bfloat16", rows, ctx) \
        == pytest.approx(want, rel=1e-12)
    # 10.31 GB: 12.6 ms at 819 GB/s, the floor of a decode step
    assert want / 1e9 == pytest.approx(10.31, abs=0.01)
    assert 1e3 * want / PEAKS["hbm_bytes_per_s"] == pytest.approx(12.59,
                                                                  abs=0.02)


def test_mla_decode_counts_by_hand():
    s = real()
    rows, ctx = 64, 86_000
    per_row = 128 * (512 + 64 + 512) * 2          # q_abs, q_rope in; out
    assert REF.mla_decode_bytes(s, "bfloat16", rows, ctx) \
        == 5 * ((ctx + rows) * 576 * 2 + rows * per_row)
    assert REF.mla_decode_flops(s, rows, ctx) \
        == 5 * (ctx + rows) * 2 * 128 * (576 + 512)
    # a live key and layer: 1,152 bytes of data (1,280 with the arena's
    # padding to 640 lanes) and 2 x 128 x (576 + 512) FLOPs: at 128 heads
    # the two bounds meet on this chip (0.71 against 0.61 ms)
    t_bytes = REF.mla_decode_bytes(s, "bfloat16", rows, ctx) / 819e9
    t_flops = REF.mla_decode_flops(s, rows, ctx) / 197e12
    assert t_bytes == pytest.approx(0.71e-3, rel=0.02)
    assert t_flops == pytest.approx(0.61e-3, rel=0.02)


def test_the_new_metrics_are_entries_over_existing_readers():
    names = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("shared_expert_device_share.closed",
                 "router_device_share.closed",
                 "local_group_hit_share.closed"):
        assert REAL in names[name]["workloads"]
        assert names[name]["moves"] == "ttft_p50_ms"
        spec = harness.load_json(harness.BENCH_DIR, "metrics", name + ".json")
        assert os.path.isfile(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py"))
    lists = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
             if REAL in m.get("workloads", [])]
    assert "ttft_p50_ms" in lists and "out_tok_s" not in lists
    assert {"mla_decode_roofline.closed", "mla_decode_kernel_share.closed",
            "moe_device_share.closed", "dense_ffn_device_share.closed",
            "local_rows_per_expert.closed", "ttft_p95_ms.closed",
            "queue_wait_p95_ms.closed",
            "prefill_device_ms_per_ktok.closed"} <= set(lists)


def test_the_cell_before_this_one_is_found_by_name():
    """`test_smallthinker.py` looked its cell and metrics up at the END of
    the lists this cell's entries were appended to, and this test ran its
    body on the lists cut there.  Since PR 40 it looks them up by name, so
    its body holds on the lists whole, with this cell's entries after its
    own (`tests/conftest.py` still marks it `xfail`, not strictly: a
    benchmark PR may not edit that file, so it passes unexpectedly)."""
    import test_smallthinker as theirs
    cells = [c["name"] for c in BENCH["workloads"]]
    assert cells.index(theirs.REAL_CELL) < cells.index(REAL)
    metrics = [m["name"] for m in BENCH["per_layer"]]
    assert metrics.index("chunk_attn_device_share.closed") \
        < metrics.index("router_device_share.closed")
    theirs.test_the_cell_and_its_metrics_are_entered_as_the_issue_names_them()


def test_the_scope_readers_find_the_new_scopes(monkeypatch):
    """`shared_expert` and `router` (with `router_groups` inside it) as the
    trace names them; the accepted MoE share reads the router too, the
    shared expert stays its own."""
    from benchmark import span_reduce
    from benchmark.readers import scope_share
    body = "jit(decode_step)/while/body/closed_call/"
    reduced = {"programs": {"jit_decode_step": {"device_s": 0.2, "ops": {
        body + "shared_expert/sh,hd->sd/dot_general fusion.7": 0.03,
        body + "router/router_groups/top_k fusion.9": 0.004,
        body + "router/dot_general fusion.3": 0.006,
        body + "experts/ragged_dot ragged-dot.1": 0.05,
        body + "dense_ffn/sh,hd->sd/dot_general fusion.1": 0.02}}}}
    monkeypatch.setattr(span_reduce, "of_view", lambda view: reduced)
    read = lambda name: scope_share.read({}, **metric(name))  # noqa: E731
    assert read("shared_expert_device_share.closed") == pytest.approx(15.0)
    assert read("router_device_share.closed") == pytest.approx(5.0)
    assert read("moe_device_share.closed") == pytest.approx(30.0)
    assert read("dense_ffn_device_share.closed") == pytest.approx(10.0)
    reduced["programs"]["jit_decode_step"]["ops"] = {
        body + "dense_ffn/sh,hd->sd/dot_general fusion.1": 0.02}
    # a program without the scopes (the parent's): nothing to read
    assert read("shared_expert_device_share.closed") is None
    assert read("router_device_share.closed") is None


def test_the_group_hit_share_on_a_real_trace(tmp_path):
    """A tiny engine served for a few steps under the profiler: the
    `serve.moe_census` spans carry the grouped router's two counters and the
    metric file reads their ratio, which no run can read above 100; beside
    it the accepted reader of rows per expert."""
    from deepspeed_tpu import ServingConfig
    from deepspeed_tpu.inference.v2.latent_ops import COUNT_DRAIN_STEPS
    from deepspeed_tpu.serving import ServeLoop
    from benchmark import systems
    from benchmark.readers import span_attr_ratio
    cfg = harness.load_json(DATA, "configs", "deepseek-v3-tiny.json")
    engine, _ = systems.build_serving(cfg, 3, REF)
    loop = ServeLoop(engine, ServingConfig())
    rng = np.random.RandomState(0)
    for n in (9, 30):
        loop.submit(rng.randint(0, 512, n).astype(np.int32),
                    max_new_tokens=2 * COUNT_DRAIN_STEPS + 3)
    trace_dir = tmp_path / ".cache" / "bench_trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=options):
        while loop.has_work:
            loop.step()
    view = {"trace": {"programs": {}}, "config": cfg,
            "bench_dir": str(tmp_path / "benchmark")}
    share = span_attr_ratio.read(view,
                                 **metric("local_group_hit_share.closed"))
    per_expert = span_attr_ratio.read(
        view, **metric("local_rows_per_expert.closed"))
    tel = loop.telemetry.counters
    assert tel["moe_router_tokens"] > 0
    # 2 of 4 groups kept, one of them held here: half by symmetry
    assert 25 < share < 75
    assert share == pytest.approx(
        100 * tel["moe_group_hit_tokens"] / tel["moe_router_tokens"],
        rel=1e-6)
    assert per_expert == pytest.approx(
        tel["moe_local_rows"] / (4 * tel["moe_router_calls"]), rel=1e-6)
    # the same reader on a latent program without groups (its count
    # vector has no such entries): nothing to read, no error
    lc = harness.load_json(DATA, "configs", "longcat-flash-tiny.json")
    lref = harness.load_module(harness.BENCH_DIR, "references",
                               "longcat_flash")
    engine, _ = systems.build_serving(lc, 3, lref)
    assert engine.arena["moe_counts"].shape == (5,)
    assert set(engine.drain_moe_counts()) == {
        "picks", "zero_picks", "local_rows", "busiest_rows", "router_calls"}
