"""The Granite-4.0-H cell at a tiny size on the CPU: the reference against
the program through the harness (per-kind arenas, an expert share and a
shared expert under a closed loop), its six controls, its counts by hand at
the published sizes, and what the cell lists."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

from bench_testlib import DATA, REPO

CELL = "granite-4.0-h-tiny.closed"
REAL_CELL = "granite-4.0-h-small.decode_closed_support"
REF = harness.load_module(harness.BENCH_DIR, "references",
                          "granite_moe_hybrid")
PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")["TPU v5 lite"]
REAL_CFG = harness.load_json(harness.BENCH_DIR, "configs",
                             "granite-4.0-h-small.json")
NEW_METRICS = {"rows_per_held_expert.closed", "expert_matmul_roofline.closed",
               "state_layer_share.closed"}
FALCONS = {
    "ssm_device_share.closed", "ssm_update_roofline.closed",
    "ssd_scan_prefill_share.closed", "ssd_scan_roofline.closed",
    "attn_branch_device_share.closed", "state_bytes_share.closed",
    "prefill_mfu.ttft.closed", "decode_hbm_roofline.ttft.closed",
    "decode_step_device_ms.ttft.closed"}
EXPERTS = {"moe_device_share.closed", "expert_matmul_device_share.closed",
           "expert_matmul_prefill_share.closed",
           "expert_weight_passes.closed", "shared_expert_device_share.closed",
           "router_device_share.closed"}
JOINED = {"ttft_p95_ms.closed", "queue_wait_p95_ms.closed",
          "prefill_device_ms_per_ktok.closed", "step_ms_max.closed",
          "step_host_ms_max.closed", "gc_ms_per_s.closed"}

# the mixer (in, out, convolution, its bias, dt_bias + A_log + D, the gated
# norm), attention, the shared expert, the router, an expert, the two norms
MIXER = 68_681_728 + 33_554_432 + 4 * 8448 + 8448 + 3 * 128 + 8192
ATTENTION = 2 * 4096 * 4096 + 2 * 4096 * 1024
SHARED, ROUTER, EXPERT = 3 * 4096 * 1536, 4096 * 72, 3 * 4096 * 768
OUTSIDE = {"mamba": MIXER + SHARED + ROUTER + 2 * 4096,
           "attention": ATTENTION + SHARED + ROUTER + 2 * 4096}


def real():
    return REF.sizes(REAL_CFG)


@pytest.fixture
def run_granite(bench_root, run_tiny):
    """The tiny cell added to the temporary root as entries (its files are
    in tests/benchmark/data): every metric the real cell lists."""
    path = os.path.join(bench_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": CELL, "config": "granite-4.0-h-tiny",
                               "traffic": "closed_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    json.dump(bench, open(path, "w"))
    return lambda **kw: run_tiny(CELL, **kw)


def test_program_agrees_with_the_reference(run_granite):
    """A whole closed-loop run: prompts of 8-40 tokens through
    `prefill_full` and, past the 32-token budget, through chunk slots that
    carry the state and the convolution's tail across the chunk's edge,
    decode through slots and blocks that change hands as requests finish,
    4 of 8 experts held: every served token is the float32 reference's
    best."""
    res = run_granite()
    assert res["correct"], res["compared"]
    assert res["compared"]["greedy_gap"]["value"] == 0.0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "setup_s"}
    warmed = res["notes"]["warmed"]
    assert "decode_step" in warmed and "prefill_chunks[1]" in warmed
    assert res["notes"]["check_tokens"] > 0


def test_a_traced_run_reads_every_metric_the_cell_lists(run_granite,
                                                        monkeypatch):
    """A traced run of the tiny cell on the CPU (the device's side of the
    trace is made up: the CPU has no device plane): the result line holds
    the three metrics this cell brought and the 21 it joined, the layer
    kinds' account read from the program's own `serve.step` spans and the
    router's from `serve.moe_census`."""
    from benchmark import span_reduce, trace_reduce
    full = {"runs": 4.0, "device_s": 0.4, "run_s": [0.1] * 4, "ops": {
        "jit(prefill_full)/while/body/ssm/scan/ssd_scan ssd_scan.3": 0.02,
        "jit(prefill_full)/while/body/ssm/scan/transpose fusion.8": 0.01,
        "jit(prefill_full)/while/body/experts/grouped_matmul "
        "grouped_matmul.5": 0.1,
        "jit(prefill_full)/attn/sh,hd->sd fusion.2": 0.2}}
    decode = {"runs": 50.0, "device_s": 0.5, "run_s": [0.01] * 50, "ops": {
        "jit(decode_step)/while/body/ssm/update/ssm_update ssm_update.5":
            0.15,
        "jit(decode_step)/while/body/ssm/conv/add fusion.7": 0.05,
        "jit(decode_step)/attn/kv_write fusion.4": 0.03,
        "jit(decode_step)/while/body/router/dot fusion.11": 0.02,
        "jit(decode_step)/while/body/experts/grouped_matmul "
        "grouped_matmul.9": 0.15,
        "jit(decode_step)/while/body/experts/combine/gather fusion.12":
            0.03,
        "jit(decode_step)/while/body/shared_expert/dot fusion.13": 0.07}}
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
    res = run_granite(seconds=2.0, trace=True)
    assert res["correct"] and res["failed"] == 0, res["compared"]
    got = res["metrics"]
    # (on the CPU the experts' matmuls are not the kernel: `_moe` writes
    # neither of `expert_weight_passes.closed`'s attributes)
    assert set(got) == (NEW_METRICS | FALCONS | EXPERTS | JOINED) \
        - {"expert_weight_passes.closed"}
    assert got["ssm_device_share.closed"]["value"] == pytest.approx(40.0)
    assert got["attn_branch_device_share.closed"]["value"] \
        == pytest.approx(6.0)
    assert got["moe_device_share.closed"]["value"] == pytest.approx(40.0)
    assert got["router_device_share.closed"]["value"] == pytest.approx(4.0)
    assert got["shared_expert_device_share.closed"]["value"] \
        == pytest.approx(14.0)
    assert got["expert_matmul_device_share.closed"]["value"] \
        == pytest.approx(30.0)
    assert got["expert_matmul_prefill_share.closed"]["value"] \
        == pytest.approx(25.0)
    assert got["ssd_scan_prefill_share.closed"]["value"] \
        == pytest.approx(7.5)
    assert got["decode_step_device_ms.ttft.closed"]["value"] \
        == pytest.approx(10.0)
    for name in ("ssm_update_roofline.closed", "ssd_scan_roofline.closed",
                 "expert_matmul_roofline.closed", "prefill_mfu.ttft.closed",
                 "decode_hbm_roofline.ttft.closed"):
        assert 0 < got[name]["value"] < 1, name       # a tiny model
    # three of the tiny stack's four layers hold a state
    assert got["state_layer_share.closed"]["value"] == pytest.approx(75.0)
    # a row's state is 3 layers x 4 heads x 64 x 16 float32 and a tail; its
    # keys a few dozen tokens of ONE layer's 2 x 2 x 32 float32
    assert 80 < got["state_bytes_share.closed"]["value"] < 100
    # 4 of 8 experts held, 3 picks a token: half the picks stay, so an
    # expert sees 3/8 of a call's rows (1-4 decode rows, 8-32 prefill rows)
    assert 0.3 < got["rows_per_held_expert.closed"]["value"] < 12


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_each_control_comes_out_incorrect(run_granite, control):
    res = run_granite(seconds=1.0, control=control)
    assert res["correct"] is False and res["control"] == control
    c = res["compared"]["greedy_gap"]
    assert c["value"] > c["limit"]


def test_weights_one_call_equals_layer_by_layer():
    """The program's tree (leaves stacked by what has them: the mixer's
    over the three state-space layers, attention's over the one attention
    layer) and the reference's layer-by-layer leaves are the same numbers,
    at a seed past 2**31; the tied head has no leaf of its own."""
    s = REF.sizes(harness.load_json(DATA, "configs",
                                    "granite-4.0-h-tiny.json"))
    whole = REF.make_params(2**31 + 9, s, jnp.float32)
    key = REF.seed_key(REF.seed_arg(2**31 + 9))
    assert set(whole) == {"tok_embed", "final_norm_scale", "layers",
                          "experts"}
    assert s.kinds == ("mamba", "mamba", "attention", "mamba")
    rows = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(s.kinds):
        lp = REF.layer_params(key, np.uint32(l), s, jnp.float32, kind)
        experts = lp.pop("experts")
        for name, leaf in lp.items():
            row = l if name in ("attn_norm_scale", "mlp_norm_scale",
                                "moe_gate", "shared") else rows[kind]
            jax.tree.map(lambda a, b: np.testing.assert_allclose(
                a[row], b, rtol=1e-6, atol=1e-8), whole["layers"][name],
                leaf)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a[l], b, rtol=1e-6, atol=1e-8), whole["experts"], experts)
        rows[kind] += 1
    assert whole["layers"]["ssm_in"].shape == (3, 128, 256 + 288 + 4)
    assert whole["layers"]["wq"].shape == (1, 128, 128)
    assert whole["experts"]["w_up"].shape == (4, 4, 128, 32)
    # another share of the same seed cuts the same whole model
    other = REF.make_params(2**31 + 9, REF.dataclasses.replace(
        s, local_first=2, local_count=4), jnp.float32)
    np.testing.assert_array_equal(np.asarray(other["experts"]["w_up"][:, :2]),
                                  np.asarray(whole["experts"]["w_up"][:, 2:]))
    np.testing.assert_array_equal(np.asarray(other["layers"]["moe_gate"]),
                                  np.asarray(whole["layers"]["moe_gate"]))


def test_the_seeded_spreads_are_what_the_configuration_states():
    """`seeded_weights` (numpy, at the published widths, projections made
    here as `_leaf` makes them): q.k * attention_multiplier of a normed
    input spreads by `qk_logit_std` (1 / sqrt(128) in the multiplier's
    place would spread it by 11 times that), the router's logits by
    `router_logit_std`, and a branch's out-projection of a unit-RMS input
    adds `branch_out_rms` AFTER `residual_multiplier`."""
    s = real()
    rng = np.random.RandomState(0)
    H, D = s.hidden, s.head_dim
    h = rng.randn(64, H).astype(np.float32)
    h /= np.sqrt((h * h).mean(-1, keepdims=True))
    unit = 1 / np.sqrt(H)
    std = unit * np.sqrt(s.qk_logit_std / (s.attention_multiplier
                                           * np.sqrt(D)))
    wq, wk = (rng.randn(H, D).astype(np.float32) * std for _ in range(2))
    logits = (h @ wq) @ (h @ wk).T * s.attention_multiplier
    assert logits.std() == pytest.approx(s.qk_logit_std, rel=0.1)
    assert s.attention_multiplier * np.sqrt(D) == pytest.approx(1 / 11.31,
                                                                rel=1e-3)
    router = rng.randn(H, s.experts).astype(np.float32) \
        * s.router_logit_std * unit
    assert (h @ router).std() == pytest.approx(s.router_logit_std, rel=0.1)
    y = rng.randn(64, s.ssm_width).astype(np.float32)
    w_out = rng.randn(s.ssm_width, 256).astype(np.float32) \
        * s.branch_out_rms / np.sqrt(s.ssm_width) / s.residual_multiplier
    assert (s.residual_multiplier * (y @ w_out)).std() \
        == pytest.approx(s.branch_out_rms, rel=0.1)
    assert (s.embed_rms, s.qk_logit_std, s.branch_out_rms,
            s.router_logit_std, s.expert_out_gain) == (0.08, 2.5, 0.5, 2.0,
                                                       1.5)
    # the tied head scores the input token's own row 12 sigma sqrt(H) /
    # rms(x_final) deviations above the rest: 1.7-1.9 with the stream at
    # 2.8-3 after ten layers, one candidate among 100,352 (22 at unit RMS)
    assert s.embed_rms * np.sqrt(H) / 3.0 == pytest.approx(1.7, abs=0.05)


def test_the_cut_weighs_9_93_gb_a_slot_38_mb_and_a_token_4_kb():
    s = real()
    assert s.kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (s.state_layers, s.attn_layers, s.layers) == (9, 1, 10)
    assert (s.in_width, s.conv_width) == (16768, 8448)
    assert MIXER / 1e6 == pytest.approx(102.29, abs=0.01)
    assert ATTENTION / 1e6 == pytest.approx(41.94, abs=0.01)
    assert (SHARED, ROUTER, EXPERT) == (18_874_368, 294_912, 9_437_184)
    for kind in OUTSIDE:
        assert REF.layer_params_outside(s, kind) == OUTSIDE[kind]
    state_layer = OUTSIDE["mamba"] + 36 * EXPERT
    attn_layer = OUTSIDE["attention"] + 36 * EXPERT
    assert state_layer / 1e6 == pytest.approx(461.2, abs=0.05)
    assert attn_layer / 1e6 == pytest.approx(400.9, abs=0.05)
    top = 100352 * 4096 + 4096
    total = 9 * state_layer + attn_layer + top
    assert REF.weight_bytes(s, "bfloat16") == 2 * total
    assert 2 * total / 1e9 == pytest.approx(9.93, abs=0.01)
    assert REF.state_bytes_per_row_layer(s) == 128 * 64 * 128 * 4 == 4 << 20
    per_slot = 9 * ((4 << 20) + 3 * 8448 * 2)
    assert per_slot / 1e6 == pytest.approx(38.2, abs=0.01)
    assert 97 * per_slot / 1e9 == pytest.approx(3.71, abs=0.01)
    assert REF.kv_bytes_per_token_layer(s, "bfloat16") == 4096
    assert 1664 * 64 * 4096 / 1e9 == pytest.approx(0.44, abs=0.01)
    # the whole model: 40 layers, 72 experts (the deployment's 64.4 GB)
    whole = 36 * (OUTSIDE["mamba"] + 72 * EXPERT) \
        + 4 * (OUTSIDE["attention"] + 72 * EXPERT) + top
    assert 2 * whole / 1e9 == pytest.approx(64.4, abs=0.1)


def test_a_decode_step_moves_17_5_gb_two_fifths_state_two_fifths_experts():
    """96 rows at a mean context of 550 tokens: the issue's count."""
    s = real()
    rows, context = 96, 96 * 550
    total = REF.decode_step_bytes(s, "bfloat16", rows, context)
    state = 96 * 9 * 2 * (4 << 20)
    assert state / 1e9 == pytest.approx(7.25, abs=0.01)
    # 960 picks over 72 experts: every one of the 36 held is reached
    assert REF.experts_with_a_row(s, rows) == pytest.approx(36, abs=1e-3)
    assert REF.experts_with_a_row(s, 4) == pytest.approx(
        36 * (1 - (62 / 72) ** 4))
    experts = 2 * 10 * 36 * EXPERT
    assert experts / 1e9 == pytest.approx(6.79, abs=0.01)
    outside = 2 * (9 * OUTSIDE["mamba"] + OUTSIDE["attention"])
    assert outside / 1e9 == pytest.approx(2.31, abs=0.01)
    head = 2 * (100352 * 4096 + 4096)
    assert head / 1e9 == pytest.approx(0.82, abs=0.01)
    tails = 96 * 9 * 2 * 3 * 8448 * 2
    assert tails / 1e9 == pytest.approx(0.09, abs=0.005)
    kv = (context + 2 * 96) * 4096
    assert total == pytest.approx(
        outside + experts + head + 96 * 4096 * 2 + state + tails + kv
        + 96 * 100352 * 4, rel=1e-6)
    assert total / 1e9 == pytest.approx(17.5, abs=0.1)
    assert 0.40 < state / total < 0.42 and 0.38 < experts / total < 0.40
    assert total / PEAKS["hbm_bytes_per_s"] * 1e3 \
        == pytest.approx(21.4, abs=0.2)                 # ms
    # the update kernel's own floor: the state both ways and little else
    upd = REF.ssm_update_bytes(s, "bfloat16", rows)
    assert state < upd < 1.02 * state
    assert upd / PEAKS["hbm_bytes_per_s"] * 1e3 == pytest.approx(8.95,
                                                                 abs=0.05)
    assert REF.ssm_update_flops(s, rows) == 96 * 9 * 5 * 128 * 64 * 128
    # the grouped matmuls' own floor: the 36 experts of each layer once
    em = REF.expert_matmul_bytes(s, "bfloat16", rows)
    assert experts < em < 1.03 * experts
    assert REF.local_picks(s) == 5.0
    assert REF.expert_matmul_flops(s, rows) == 10 * 96 * 5 * 2 * EXPERT
    assert REF.expert_matmul_flops(s, rows) / PEAKS["bf16_flops"] \
        < 0.06 * em / PEAKS["hbm_bytes_per_s"]          # bytes-bound


def test_a_512_token_prefill_is_3_34_gflop_a_token():
    s = real()
    n = 512
    total = REF.prefill_flops(s, n)
    assert total / n / 1e9 == pytest.approx(3.34, abs=0.02)
    assert total / 1e12 == pytest.approx(1.71, abs=0.01)
    # a state-space layer: the mixer's projections 205 M, 5 local picks
    # 94 M, the shared expert 38 M, the scan ~10 M a token
    assert 2 * (68_681_728 + 33_554_432) / 1e6 == pytest.approx(204.5,
                                                                abs=0.1)
    assert 2 * 5 * EXPERT / 1e6 == pytest.approx(94.4, abs=0.1)
    assert 2 * SHARED / 1e6 == pytest.approx(37.7, abs=0.1)
    scan = REF.ssd_scan_flops(s, n)
    per_token_layer = scan / (9 * n)
    # C B^T the one group, then per head the chunk's own part, the carried
    # state's and the state handed on, chunks of 256
    assert per_token_layer == pytest.approx(
        2 * (256 * 128 + 128 * (256 * 64 + 2 * 128 * 64)), rel=1e-9)
    assert per_token_layer / 1e6 == pytest.approx(8.5, abs=0.1)
    assert 0.02 < scan / total < 0.03
    assert total / PEAKS["bf16_flops"] * 1e3 == pytest.approx(8.7, abs=0.1)
    # ... but the program reads its 9.9 GB of weights: bound by bytes
    assert REF.weight_bytes(s, "bfloat16") / PEAKS["hbm_bytes_per_s"] * 1e3 \
        == pytest.approx(12.1, abs=0.1)
    by = REF.ssd_scan_bytes(s, "bfloat16", n)
    assert by == 9 * (n * (8192 * 6 + 2 * 128 * 2 + 2 * 128 * 4)
                      + 2 * (4 << 20))
    assert REF.ssd_scan_flops(s, 300) < REF.ssd_scan_flops(s, 512)


def test_the_configuration_is_the_catalogs_with_depth_and_experts_cut():
    """Every key of the published `config.json` as the configuration file
    holds it (`layer_types` whole); `num_hidden_layers` and
    `num_local_experts` alone differ and are listed."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    for key, value in published.items():
        assert REAL_CFG[key] == value, key
    kinds = REAL_CFG["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds)
                                 if k == "attention"] == [5, 15, 25, 35]
    assert (REAL_CFG["num_hidden_layers"], REAL_CFG["num_local_experts"]) \
        == (10, 36)
    assert REAL_CFG["published"] == {"num_hidden_layers": 40,
                                     "num_local_experts": 72}
    assert REAL_CFG["reduced"] == ["num_hidden_layers", "num_local_experts"]
    bench = harness.load_json(REPO, "BENCHMARK.json")
    entry = [c for c in bench["configs"]
             if c["name"] == "granite-4.0-h-small"][0]
    assert entry["reduced"] == REAL_CFG["reduced"]
    assert entry["source"] == REAL_CFG["source"]
    cell = [w for w in bench["workloads"] if w["name"] == REAL_CELL][0]
    assert (cell["chips"], cell["traffic"]) == (1, "decode_closed_support")
    listed = {m["name"] for m in bench["per_layer"]
              if REAL_CELL in m.get("workloads", [])}
    assert listed == NEW_METRICS | FALCONS | EXPERTS | JOINED
    assert all(m["moves"] == "ttft_p50_ms" for m in bench["per_layer"]
               if REAL_CELL in m.get("workloads", []))
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                "decode_closed_support.json")
    falcon = harness.load_json(harness.BENCH_DIR, "traffic",
                               "decode_closed_short.json")
    mix = ("kind", "clients", "prompt_len", "output_len", "size_pool",
           "settle_s", "check_requests")
    assert [traffic[k] for k in mix] == [falcon[k] for k in mix] \
        == ["closed_loop", 96, [256, 512], [128, 512], 256, 6.0, 4]
    # the program the file asks for has the published widths and the cut
    from deepspeed_tpu.inference.v2.model_registry import arch_config
    prog = REAL_CFG["program"]
    cfg = arch_config(prog["arch"], prog["size"], **prog["overrides"])
    s = real()
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.vocab_size, cfg.tie_embeddings, cfg.pos_emb) == (
        s.hidden, s.heads, s.kv_heads, s.head_dim, s.vocab, True, "none")
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (
        s.ssm_heads, s.ssm_head_dim, s.ssm_state, s.ssm_groups, s.ssm_conv,
        s.ssm_chunk)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.moe_expert_ffn,
            cfg.moe_shared_expert_ffn, cfg.moe_expert_first,
            cfg.local_experts) == (s.experts, s.top_k, s.expert_ffn,
                                   s.shared_ffn, s.local_first,
                                   s.local_count)
    assert tuple({"ssm": "mamba", "attn": "attention"}[k]
                 for k in cfg.ssm_period) == s.kinds
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, 1 / cfg.lm_head_multiplier,
            cfg.norm_eps) == (
        s.embedding_multiplier, s.residual_multiplier,
        s.attention_multiplier, s.logits_scaling, s.eps)
