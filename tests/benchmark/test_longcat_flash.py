"""The LongCat-Flash cell at a tiny size on the CPU: the reference against
the program through the harness, its three controls, its counts by hand,
and the reader of the router counters' span."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

from bench_testlib import DATA

CELL = "longcat-flash-tiny.closed"
REF = harness.load_module(harness.BENCH_DIR, "references", "longcat_flash")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")["TPU v5 lite"]


def real():
    return REF.sizes(harness.load_json(harness.BENCH_DIR, "configs",
                                       "longcat-flash.json"))


@pytest.fixture
def run_longcat(bench_root, run_tiny):
    """The tiny cell added to the temporary root as entries (its files are
    in tests/benchmark/data): every `.closed` metric the real cell lists."""
    path = os.path.join(bench_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": CELL, "config": "longcat-flash-tiny",
                               "traffic": "closed_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "longcat-flash.decode_closed_2k" in m.get("workloads", []):
            m["workloads"].append(CELL)
    json.dump(bench, open(path, "w"))
    return lambda **kw: run_tiny(CELL, **kw)


def test_program_agrees_with_the_reference(run_longcat):
    """A whole closed-loop run: prompts through `prefill_full` and chunk
    slots, then decode through the latent cache; every served token is the
    float32 reference's best."""
    res = run_longcat()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert res["metrics"]["ttft_p50_ms"]["value"] > 0


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_control_comes_out_incorrect(run_longcat, control):
    res = run_longcat(seconds=2.0, control=control)
    assert res["correct"] is False and res["control"] == control
    c = res["compared"]["greedy_gap"]
    assert c["value"] > c["limit"]


def test_an_unknown_control_is_an_error():
    with pytest.raises(ValueError, match="unknown control"):
        REF._how("fp4")


def test_weights_one_call_equals_layer_by_layer():
    """The program's stacked tree (experts apart) and the reference's
    layer-by-layer leaves are the same numbers."""
    s = REF.sizes(harness.load_json(DATA, "configs",
                                    "longcat-flash-tiny.json"))
    whole = REF.make_params(2**31 + 9, s, jnp.float32)
    key = REF.seed_key(REF.seed_arg(2**31 + 9))
    assert set(whole) == {"tok_embed", "final_norm_scale", "lm_head",
                          "layers", "experts"}
    for l in range(s.layers):
        lp = REF.layer_params(key, np.uint32(l), s, jnp.float32)
        ex = lp.pop("experts")
        # (two compiled forms of one formula: a unit in the last place)
        close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
            a[l], b, rtol=1e-6, atol=1e-8)
        jax.tree.map(close, whole["layers"], lp)
        jax.tree.map(close, whole["experts"], ex)
    assert whole["experts"]["w_up"].shape == (2, 8, 64, 32)
    assert float(jnp.std(whole["layers"]["moe_router_bias"])) > 0.01


def test_the_cut_weighs_10_68_gb_and_a_token_caches_6912_bytes():
    s = real()
    mla = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 \
        + 64 * 128 * 6144
    assert mla == 90_570_752
    outside = 2 * mla + 2 * 3 * 6144 * 12288 + 6144 * 768 + 768 \
        + 2 * (2 * 6144 + 1536 + 512)                       # norms
    expert = 3 * 6144 * 2048
    assert outside / 1e6 == pytest.approx(638.9, abs=0.05)
    assert expert / 1e6 == pytest.approx(37.75, abs=0.005)
    assert REF.layer_params_held(s) == outside + 16 * expert
    total = 3 * (outside + 16 * expert) + 2 * 131072 * 6144 + 6144
    assert REF.weight_bytes(s, "bfloat16") == 2 * total
    assert REF.weight_bytes(s, "bfloat16") / 1e9 == pytest.approx(10.68,
                                                                  abs=0.01)
    assert REF.latent_bytes_per_token(s, "bfloat16") == 6 * 576 * 2 == 6912


def test_decode_step_bytes_by_hand():
    s = real()
    rows, ctx = 96, 110_000
    # a token picks a given one of the 768 outputs with probability 12/768:
    # of the 16 local experts 16 (1 - (63/64)^96) = 12.47 get a row, and
    # the other 3.53 (3 matrices of 6144 x 2048 each) are not read
    reached = 16 * (1 - (63 / 64) ** 96)
    assert REF.experts_with_a_row(s, rows) == pytest.approx(reached)
    assert reached == pytest.approx(12.47, abs=0.005)
    weights = REF.weight_bytes(s, "bfloat16") - 131072 * 6144 * 2 \
        - 3 * (16 - reached) * 3 * 6144 * 2048 * 2
    want = weights + rows * 6144 * 2 + (ctx + rows) * 6912 \
        + rows * 131072 * 4
    assert REF.decode_step_bytes(s, "bfloat16", rows, ctx) \
        == pytest.approx(want, rel=1e-12)
    # 9.08 GB: 11.1 ms at 819 GB/s, the floor of a decode step
    assert want / 1e9 == pytest.approx(9.08, abs=0.01)
    assert 1e3 * want / PEAKS["hbm_bytes_per_s"] == pytest.approx(11.09,
                                                                  abs=0.02)


def test_mla_decode_counts_by_hand():
    s = real()
    rows, ctx = 96, 110_000
    per_row = 64 * (512 + 64 + 512) * 2          # q_abs, q_rope in; out
    assert REF.mla_decode_bytes(s, "bfloat16", rows, ctx) \
        == 6 * ((ctx + rows) * 576 * 2 + rows * per_row)
    assert REF.mla_decode_flops(s, rows, ctx) \
        == 6 * (ctx + rows) * 2 * 64 * (576 + 512)
    # bandwidth bounds the kernel on this chip: 1.03 ms against 0.47 ms
    t_bytes = REF.mla_decode_bytes(s, "bfloat16", rows, ctx) / 819e9
    t_flops = REF.mla_decode_flops(s, rows, ctx) / 197e12
    assert t_bytes == pytest.approx(1.03e-3, rel=0.01)
    assert t_flops == pytest.approx(0.47e-3, rel=0.01)


def test_the_kernel_roofline_reader(monkeypatch):
    """Kernel seconds per run of the program against the larger of the two
    floors; no kernel in the trace: nothing to read."""
    from benchmark import span_reduce
    from benchmark.readers import kernel_roofline
    cfg = harness.load_json(harness.BENCH_DIR, "configs",
                            "longcat-flash.json")
    reduced = {"programs": {"jit_decode_step": {"device_s": 0.1, "ops": {
        "jit(decode_step)/while/body/mla_attention mla_paged_attention.3": 0.012,
        "jit(decode_step)/while/body/dense_ffn fusion.1": 0.05}}}}
    monkeypatch.setattr(span_reduce, "of_view", lambda view: reduced)
    view = {"trace": {"programs": {"jit_decode_step": {
                "runs": 4, "device_s": 0.1, "ops": {}}}},
            "stats": {"counters": {"steps": 10, "rows": 960,
                                   "context_tokens": 1_100_000}},
            "config": cfg, "model": REF, "bench_dir": harness.BENCH_DIR,
            "device_kind": "TPU v5 lite"}
    params = harness.load_json(harness.BENCH_DIR, "metrics",
                               "mla_decode_roofline.closed.json")["params"]
    got = kernel_roofline.read(view, **params)
    floor = REF.mla_decode_bytes(real(), "bfloat16", 96, 110_000) / 819e9
    assert got == pytest.approx(100 * floor / 0.003, rel=1e-9) and got < 100
    reduced["programs"]["jit_decode_step"]["ops"].pop(
        "jit(decode_step)/while/body/mla_attention mla_paged_attention.3")
    assert kernel_roofline.read(view, **params) is None


def test_the_span_attribute_reader_on_a_real_trace(tmp_path):
    """A tiny latent engine served for a few steps under the profiler: the
    `serve.moe_census` spans carry the drained counters, and the two
    metric files read a share and rows per expert out of them."""
    from deepspeed_tpu import ServingConfig
    from deepspeed_tpu.serving import ServeLoop
    from benchmark import systems
    from benchmark.readers import span_attr_ratio
    cfg = harness.load_json(DATA, "configs", "longcat-flash-tiny.json")
    engine, _ = systems.build_serving(cfg, 3, REF)
    from deepspeed_tpu.inference.v2.latent_ops import COUNT_DRAIN_STEPS
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
    metric = lambda name: harness.load_json(  # noqa: E731
        harness.BENCH_DIR, "metrics", name + ".json")["params"]
    share = span_attr_ratio.read(view, **metric("zero_expert_pick_share.closed"))
    per_expert = span_attr_ratio.read(
        view, **metric("local_rows_per_expert.closed"))
    tel = loop.telemetry.counters
    assert tel["moe_picks"] > 0 and tel["moe_router_calls"] > 0
    # steps the spans cover
    drained = loop.telemetry.steps // COUNT_DRAIN_STEPS * COUNT_DRAIN_STEPS
    assert drained and 0 < share < 100 and per_expert > 0
    assert share == pytest.approx(
        100 * tel["moe_zero_picks"] / tel["moe_picks"], rel=1e-6)
    assert per_expert == pytest.approx(
        tel["moe_local_rows"] / (8 * tel["moe_router_calls"]), rel=1e-6)
    # a program without the span (an older commit): nothing to read
    assert span_attr_ratio.read(view, span="serve.no_such", num="a",
                                den="b") is None
    assert span_attr_ratio.read(dict(view, trace=None), span="x", num="a",
                                den="b") is None
