"""The Falcon-H1 cell at a tiny size on the CPU: the reference against the
program through the harness (K/V blocks, state slots and convolution tails
under a closed loop), its five controls, its counts by hand at the
published sizes, and the readers of what the cell adds."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

from bench_testlib import DATA, REPO

CELL = "falcon-h1-tiny.closed"
REAL_CELL = "falcon-h1-34b.decode_closed_short"
REF = harness.load_module(harness.BENCH_DIR, "references", "falcon_h1")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")["TPU v5 lite"]
REAL_CFG = harness.load_json(harness.BENCH_DIR, "configs",
                             "falcon-h1-34b.json")
NEW_METRICS = {
    "ssm_device_share.closed", "ssm_update_roofline.closed",
    "ssd_scan_prefill_share.closed", "ssd_scan_roofline.closed",
    "attn_branch_device_share.closed", "state_bytes_share.closed",
    "prefill_mfu.ttft.closed", "decode_hbm_roofline.ttft.closed",
    "decode_step_device_ms.ttft.closed"}
JOINED = {"ttft_p95_ms.closed", "queue_wait_p95_ms.closed",
          "prefill_device_ms_per_ktok.closed",
          "dense_ffn_device_share.closed", "step_ms_max.closed",
          "step_host_ms_max.closed", "gc_ms_per_s.closed"}


# attention, the mixer (in, out, convolution, its bias, dt_bias + A_log + D,
# the gated norm), the MLP, the two norms
LAYER_PARAMS = (31_457_280
                + 47_349_760 + 20_971_520 + 4 * 5120 + 5120 + 3 * 32 + 4096
                + 330_301_440 + 2 * 5120)


def real():
    return REF.sizes(REAL_CFG)


@pytest.fixture
def run_falcon(bench_root, run_tiny):
    """The tiny cell added to the temporary root as entries (its files are
    in tests/benchmark/data): every metric the real cell lists."""
    path = os.path.join(bench_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": CELL, "config": "falcon-h1-tiny",
                               "traffic": "closed_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    json.dump(bench, open(path, "w"))
    return lambda **kw: run_tiny(CELL, **kw)


def test_program_agrees_with_the_reference(run_falcon):
    """A whole closed-loop run: prompts of 8-40 tokens through
    `prefill_full` and, past the 32-token budget, through chunk slots that
    carry the state and the convolution's tail across the chunk's edge,
    decode through slots that change hands as requests finish; every
    served token is the float32 reference's best."""
    res = run_falcon()
    assert res["correct"], res["compared"]
    assert res["compared"]["greedy_gap"]["value"] == 0.0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "setup_s"}
    warmed = res["notes"]["warmed"]
    assert "decode_step" in warmed and "prefill_chunks[1]" in warmed
    assert res["notes"]["check_tokens"] > 0


def test_a_traced_run_reads_every_metric_the_cell_reports(run_falcon,
                                                          monkeypatch):
    """A traced run of the tiny cell on the CPU (the device's side of the
    trace is made up: the CPU has no device plane): the result line holds
    the nine metrics this cell brought and the seven it joined, the state's
    account read from the program's own `serve.step` spans."""
    from benchmark import span_reduce, trace_reduce
    full = {"runs": 4.0, "device_s": 0.4, "run_s": [0.1] * 4, "ops": {
        "jit(prefill_full)/while/body/ssm/scan/ssd_scan ssd_scan.3": 0.02,
        "jit(prefill_full)/while/body/ssm/scan/jit(cumsum)/add fusion.6":
            0.006,
        "jit(prefill_full)/while/body/ssm/scan/transpose fusion.8": 0.004,
        "jit(prefill_full)/while/body/ssm/mul fusion.5": 0.01,
        "jit(prefill_full)/while/body/dense_ffn/sh,hd->sd fusion.2": 0.2}}
    decode = {"runs": 50.0, "device_s": 0.5, "run_s": [0.01] * 50, "ops": {
        "jit(decode_step)/while/body/ssm/update/ssm_update ssm_update.5":
            0.15,
        "jit(decode_step)/while/body/ssm/conv/add fusion.7": 0.05,
        "jit(decode_step)/while/body/attn/kv_write fusion.4": 0.03,
        "jit(decode_step)/while/body/dense_ffn/sh,hd->sd fusion.9": 0.2}}
    programs = {"jit_prefill_full": full, "jit_decode_step": decode}
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda trace_dir: {
        "programs": {k: dict(v, ops={
            key.split(" ", 1)[1]: t for key, t in v["ops"].items()})
            for k, v in programs.items()},
        "busy_s": 0.9, "window_s": 3.0, "top_ops": [], "idle_gaps": []})
    monkeypatch.setattr(span_reduce, "of_view",
                        lambda view: {"programs": programs})
    # the CPU has no published peaks; the made-up device is a v5e
    monkeypatch.setattr(harness, "peaks_of", lambda view: PEAKS)
    res = run_falcon(seconds=2.0, trace=True)
    assert res["correct"] and res["failed"] == 0, res["compared"]
    got = res["metrics"]
    assert set(got) == NEW_METRICS | JOINED
    assert got["ssm_device_share.closed"]["value"] == pytest.approx(40.0)
    assert got["attn_branch_device_share.closed"]["value"] \
        == pytest.approx(6.0)
    assert got["dense_ffn_device_share.closed"]["value"] \
        == pytest.approx(40.0)
    # the scope `ssm/scan` whole: the kernel, the cumulative sum and the
    # relayouts around it, not the mixer's other ops
    assert got["ssd_scan_prefill_share.closed"]["value"] \
        == pytest.approx(7.5)
    # the whole decode program a first token waits for before its prefill
    assert got["decode_step_device_ms.ttft.closed"]["value"] \
        == pytest.approx(10.0)
    for name in ("ssm_update_roofline.closed", "ssd_scan_roofline.closed",
                 "prefill_mfu.ttft.closed",
                 "decode_hbm_roofline.ttft.closed"):
        assert 0 < got[name]["value"] < 1, name       # a tiny model
    # a row's state is 2 layers x 4 heads x 16 x 16 float32 and a tail;
    # its keys a few dozen tokens of 2 x 2 x 16 float32
    assert 50 < got["state_bytes_share.closed"]["value"] < 100


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_control_comes_out_incorrect(run_falcon, control):
    res = run_falcon(seconds=1.0, control=control)
    assert res["correct"] is False and res["control"] == control
    c = res["compared"]["greedy_gap"]
    assert c["value"] > c["limit"]


def test_an_unknown_control_is_an_error():
    with pytest.raises(ValueError, match="unknown control"):
        REF._how("fp4")
    assert REF._how(None) == (None, ()) and REF._how("int8") == ("int8", ())
    assert REF._how("gate_after_norm") == (None, ("gate_after_norm",))


def test_weights_one_call_equals_layer_by_layer():
    """The program's stacked tree and the reference's layer-by-layer
    leaves are the same numbers, at a seed past 2**31."""
    s = REF.sizes(harness.load_json(DATA, "configs", "falcon-h1-tiny.json"))
    whole = REF.make_params(2**31 + 9, s, jnp.float32)
    key = REF.seed_key(REF.seed_arg(2**31 + 9))
    assert set(whole) == {"tok_embed", "final_norm_scale", "lm_head",
                          "layers"}
    for l in range(s.layers):
        lp = REF.layer_params(key, np.uint32(l), s, jnp.float32)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a[l], b, rtol=1e-6, atol=1e-8), whole["layers"], lp)
    assert whole["layers"]["ssm_in"].shape == (2, 64, 64 + 128 + 4)
    assert whole["layers"]["ssm_conv_w"].shape == (2, 4, 128)
    # Mamba-2's initialisation: steps in [1e-3, 1e-1], rates in [1, 16]
    step = jax.nn.softplus(whole["layers"]["ssm_dt_bias"])
    assert 1e-3 * 0.99 <= float(step.min()) and float(step.max()) <= 0.101
    rate = jnp.exp(whole["layers"]["ssm_a_log"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0


def test_a_leaf_of_a_quarter_million_rows_is_drawn_in_eighths(monkeypatch):
    """The embedding and the head at the published vocabulary are drawn an
    eighth at a time (no 5 GB float32 temporary); at a lowered threshold:
    still of the stated spread, a function of the seed alone, the eighths
    unlike each other."""
    s = real()
    assert 261120 * 5120 > REF.DRAW_WHOLE_UP_TO > 5120 * 21504
    shape = jax.eval_shape(lambda: REF.top_param(
        REF.seed_key(np.uint32(1)), "tok_embed", s, jnp.bfloat16))
    assert shape.shape == (261120, 5120) and shape.dtype == jnp.bfloat16
    import dataclasses
    monkeypatch.setattr(REF, "DRAW_WHOLE_UP_TO", 1 << 16)
    small = dataclasses.replace(s, vocab=1 << 10, hidden=264)
    a, b = (REF.top_param(REF.seed_key(np.uint32(1)), "tok_embed", small,
                          jnp.float32) for _ in range(2))
    assert a.shape == (1 << 10, 264) and bool(jnp.all(a == b))
    assert float(jnp.std(a)) * s.embedding_multiplier \
        == pytest.approx(1.0, rel=0.01)
    assert not bool(jnp.all(a[:128] == a[128:256]))


def test_the_seeded_spreads_are_what_the_configuration_states():
    """`seeded_weights` (numpy, at the published widths, projections made
    here as `_leaf` makes them): q.k / sqrt(D) of a normed input spreads by
    `qk_logit_std` AFTER `key_multiplier`, and each range of the mixer's
    in-projection has unit variance after `ssm_in_multiplier` and its own
    `ssm_multipliers` entry."""
    s = real()
    rng = np.random.RandomState(0)
    H, D = s.hidden, s.head_dim
    h = rng.randn(64, H).astype(np.float32)
    h /= np.sqrt((h * h).mean(-1, keepdims=True))
    unit = 1 / np.sqrt(H)
    wq = rng.randn(H, D).astype(np.float32) * np.sqrt(s.qk_logit_std) * unit
    wk = rng.randn(H, D).astype(np.float32) * np.sqrt(s.qk_logit_std) \
        * unit / s.key_multiplier
    logits = (h @ wq) @ (s.key_multiplier * (h @ wk)).T / np.sqrt(D)
    assert logits.std() == pytest.approx(s.qk_logit_std, rel=0.1)
    for m in s.ssm_multipliers:
        w = rng.randn(H, 64).astype(np.float32) * unit \
            / s.ssm_in_multiplier / m
        assert (((s.ssm_in_multiplier * h) @ w) * m).std() \
            == pytest.approx(1.0, rel=0.1)
    assert (s.qk_logit_std, s.branch_out_rms) == (2.5, 0.5)


def test_the_cut_weighs_10_51_gb_and_a_slot_25_mb():
    s = real()
    attention = 2 * 5120 * 2560 + 2 * 5120 * 512
    assert attention == 31_457_280
    w_in, w_out = 5120 * 9248, 4096 * 5120
    assert (s.in_width, w_in, w_out) == (9248, 47_349_760, 20_971_520)
    mixer = w_in + w_out + 4 * 5120 + 5120 + 3 * 32 + 4096
    mlp = 3 * 5120 * 21504
    assert mlp == 330_301_440
    layer = attention + mixer + mlp + 2 * 5120
    assert layer == LAYER_PARAMS
    assert layer / 1e6 == pytest.approx(430.1, abs=0.05)
    top = 2 * 261120 * 5120 + 5120
    assert REF.weight_bytes(s, "bfloat16") == 2 * (6 * layer + top)
    assert REF.weight_bytes(s, "bfloat16") / 1e9 == pytest.approx(10.51,
                                                                  abs=0.01)
    assert REF.state_bytes_per_row_layer(s) == 32 * 128 * 256 * 4 == 4 << 20
    per_slot = 6 * ((4 << 20) + 3 * 5120 * 2)
    assert per_slot / 1e6 == pytest.approx(25.35, abs=0.01)
    assert REF.kv_bytes_per_token_layer(s, "bfloat16") * 6 == 12_288


def test_a_decode_step_moves_13_gb_of_which_the_state_is_a_third():
    """96 rows at a mean context of 550 tokens: the issue's count."""
    s = real()
    rows, context = 96, 96 * 550
    total = REF.decode_step_bytes(s, "bfloat16", rows, context)
    state = 96 * 6 * 2 * (4 << 20)
    assert state / 1e9 == pytest.approx(4.83, abs=0.01)
    weights = 2 * (6 * LAYER_PARAMS + 261120 * 5120 + 5120)
    tails = 96 * 6 * 2 * 3 * 5120 * 2
    kv = (context + 96) * 12_288 + 96 * 12_288
    assert total == pytest.approx(
        weights + 96 * 5120 * 2 + state + tails + kv + 96 * 261120 * 4)
    assert total / 1e9 == pytest.approx(13.4, abs=0.1)
    assert 0.35 < state / total < 0.37
    assert total / PEAKS["hbm_bytes_per_s"] * 1e3 \
        == pytest.approx(16.4, abs=0.2)                 # ms
    # the update kernel's own floor: the state both ways and little else
    upd = REF.ssm_update_bytes(s, "bfloat16", rows)
    assert state < upd < 1.01 * state
    assert upd / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(5.9,
                                                                 abs=0.05)
    assert REF.ssm_update_flops(s, rows) \
        == 96 * 6 * 5 * 32 * 128 * 256
    assert REF.ssm_update_flops(s, rows) / PEAKS["bf16_flops"] \
        < 0.01 * upd / PEAKS["hbm_bytes_per_s"]         # bytes-bound


def test_a_512_token_prefill_is_2_7_tflop_of_which_the_scan_is_0_6_percent():
    s = real()
    n = 512
    scan = REF.ssd_scan_flops(s, n)
    per_token_layer = scan / (6 * n)
    # C B^T a group, then per head the chunk's own part, the carried
    # state's and the state handed on: about 5.4 MFLOP a token and layer
    assert per_token_layer == pytest.approx(
        2 * (2 * 128 * 256 + 32 * (128 * 128 + 2 * 256 * 128)), rel=1e-9)
    assert per_token_layer / 1e6 == pytest.approx(5.4, abs=0.05)
    total = REF.prefill_flops(s, n)
    assert total / 1e12 == pytest.approx(2.65, abs=0.05)
    assert 0.005 < scan / total < 0.007
    # a prompt that ends inside a chunk: the cut chunk costs less
    assert REF.ssd_scan_flops(s, 300) < REF.ssd_scan_flops(s, 384)
    assert REF.ssd_scan_flops(s, 256) == pytest.approx(scan / 2)
    # the scan is compute-bound: its bytes' time is below its FLOPs'
    by = REF.ssd_scan_bytes(s, "bfloat16", n)
    assert by / PEAKS["hbm_bytes_per_s"] > 0
    assert by == 6 * (n * (4096 * 6 + 2 * 512 * 2 + 2 * 32 * 4)
                      + 2 * (4 << 20))


def test_the_new_reader_sums_a_floor_a_prompt(monkeypatch):
    """`prefill_kernel_roofline`: the kernel's seconds in the matching
    programs against the larger floor of each traced prompt; nothing to
    read without the kernel, the prompts or the program's spans."""
    from benchmark import span_reduce
    reader = harness.load_module(harness.BENCH_DIR, "readers",
                                 "prefill_kernel_roofline")
    s = real()
    lengths = [512, 300]
    floor = sum(max(REF.ssd_scan_flops(s, n) / PEAKS["bf16_flops"],
                    REF.ssd_scan_bytes(s, "bfloat16", n)
                    / PEAKS["hbm_bytes_per_s"]) for n in lengths)
    programs = {"jit_prefill_full": {"ops": {
        "jit(prefill_full)/while/body/ssm/scan/ssd_scan ssd_scan.3":
            4 * floor, "jit(prefill_full)/x fusion.1": 1.0}},
        "jit_decode_step": {"ops": {"a/ssd_scan ssd_scan.9": 7.0}}}
    view = {"stats": {"traced": {"prompt_lengths": lengths}},
            "config": REAL_CFG, "model": REF, "chips": 1,
            "device_kind": "TPU v5 lite", "bench_dir": harness.BENCH_DIR}
    params = harness.load_json(harness.BENCH_DIR, "metrics",
                               "ssd_scan_roofline.closed.json")["params"]
    monkeypatch.setattr(span_reduce, "of_view",
                        lambda view: {"programs": programs})
    assert reader.read(view, **params) == pytest.approx(25.0)
    assert reader.read(dict(view, stats={}), **params) is None
    assert reader.read(view, **dict(params, kernel="no_such")) is None
    monkeypatch.setattr(span_reduce, "of_view", lambda view: None)
    assert reader.read(view, **params) is None


def test_the_configuration_is_the_catalogs_with_the_depth_cut():
    """Every key of the published `config.json` as the configuration file
    holds it; `num_hidden_layers` alone differs and is listed."""
    published = {
        "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_size": 5120, "intermediate_size": 21504,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_d_conv": 4, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
        "mamba_n_heads": 32, "max_position_embeddings": 262144,
        "mlp_expansion_factor": 8, "num_attention_heads": 20,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-05,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.08838834764831845, "vocab_size": 261120,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738]}
    for key, value in published.items():
        assert REAL_CFG[key] == value, key
    assert REAL_CFG["num_hidden_layers"] == 6
    assert REAL_CFG["published"] == {"num_hidden_layers": 72}
    assert REAL_CFG["reduced"] == ["num_hidden_layers"]
    bench = harness.load_json(REPO, "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == "falcon-h1-34b"][0]
    assert entry["reduced"] == REAL_CFG["reduced"]
    assert entry["source"] == REAL_CFG["source"]
    cell = [w for w in bench["workloads"] if w["name"] == REAL_CELL][0]
    assert (cell["chips"], cell["traffic"]) == (1, "decode_closed_short")
    listed = {m["name"] for m in bench["per_layer"]
              if REAL_CELL in m.get("workloads", [])}
    assert listed == NEW_METRICS | JOINED
    # the program the file asks for has the published widths
    from deepspeed_tpu.inference.v2.model_registry import arch_config
    prog = REAL_CFG["program"]
    cfg = arch_config(prog["arch"], prog["size"], **prog["overrides"])
    s = real()
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (
        s.hidden, s.heads, s.kv_heads, s.head_dim, s.ffn, s.vocab)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        s.ssm_heads, s.ssm_head_dim, s.ssm_state, s.ssm_groups, s.ssm_conv,
        s.ssm_chunk)
    for name in ("embedding_multiplier", "lm_head_multiplier",
                 "attention_in_multiplier", "attention_out_multiplier",
                 "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
                 "mlp_multipliers", "ssm_multipliers"):
        assert getattr(cfg, name) == getattr(s, name), name
    assert (cfg.norm_eps, cfg.rope_theta) == (s.eps, s.rope_theta)
