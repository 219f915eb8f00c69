"""Bytes and FLOPs against hand numbers, so that no share can pass 100%."""

import pytest

from benchmark import counts, harness
from benchmark import weights as W


def sizes(name):
    return W.sizes_from_config(
        harness.load_json(harness.BENCH_DIR, "configs", name + ".json"))


PEAKS = harness.load_json(harness.BENCH_DIR, "peaks.json")["TPU v5 lite"]


def test_qwen2_7b_at_16_layers_weighs_9_64_gb():
    s = sizes("qwen2-7b")
    # by hand: a layer is q 3584x3584, k and v 3584x512, o 3584x3584, three
    # 3584x18944 MLP matrices, 4608 bias and 7168 norm values
    layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944 \
        + 3584 + 2 * 512 + 2 * 3584
    assert counts.layer_params(s) == layer
    total = 16 * layer + 2 * 152064 * 3584 + 3584
    assert counts.weight_bytes(s, "bfloat16") == 2 * total
    assert counts.weight_bytes(s, "bfloat16") / 1e9 == pytest.approx(
        9.64, abs=0.005)
    assert W.param_count(s) == total
    assert counts.kv_bytes_per_token(s, "bfloat16") == 32768


def test_decode_step_bytes_by_hand():
    s = sizes("qwen2-7b")
    rows, ctx = 64, 64 * 350
    weights = 9.637846016e9 - 152064 * 3584 * 2      # all but the embedding
    want = weights + rows * 3584 * 2 + (ctx + rows) * 32768 \
        + rows * 152064 * 4
    assert counts.decode_step_bytes(s, "bfloat16", rows, ctx) \
        == pytest.approx(want, rel=1e-9)
    # more rows or longer contexts never need fewer bytes
    assert counts.decode_step_bytes(s, "bfloat16", 64, 2 * ctx) > want


def test_opt_1_3b_needs_8_5_gflop_a_token():
    s = sizes("opt-1.3b")
    matmul = 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 50272 * 2048
    attention = 24 * 2 * 2048 * 2048
    assert counts.train_flops_per_token(s, 2048) \
        == 6 * matmul + 3 * attention
    assert counts.train_flops_per_token(s, 2048) / 1e9 == pytest.approx(
        8.47, abs=0.01)
    assert W.param_count(s) == pytest.approx(1.316e9, rel=1e-3)


@pytest.mark.parametrize("device_ms,rows,ctx", [
    (20.109, 63.8, 63.8 * 350),     # the driver's reading of PR 25's tree
    (12.2, 64, 64 * 640),           # every row at its longest
])
def test_a_decode_roofline_share_cannot_pass_100(device_ms, rows, ctx):
    """At the device times this chip can reach, the bytes counted give a
    share under 100%: the floor at the longest contexts is 12.1 ms."""
    s = sizes("qwen2-7b")
    floor_ms = 1e3 * counts.decode_step_bytes(s, "bfloat16", rows, ctx) \
        / PEAKS["hbm_bytes_per_s"]
    assert floor_ms <= device_ms
    assert 100 * floor_ms / device_ms <= 100.0


def test_mfu_at_the_measured_rate_is_about_40():
    s = sizes("opt-1.3b")
    mfu = 100 * counts.train_flops_per_token(s, 2048) * 9310 \
        / PEAKS["bf16_flops"]
    assert 39 < mfu < 41
    # the rate at which MFU would read 100% is what the chip cannot pass
    assert PEAKS["bf16_flops"] / counts.train_flops_per_token(s, 2048) \
        == pytest.approx(23260, rel=1e-3)


def test_the_mfu_reader_takes_the_step_programs_device_time():
    """FLOPs of a step's tokens over the LONGEST traced run of the step
    program (the profiler cuts the first and the last short), the chips
    and the peak; the host's clock is not in it; no trace, no number."""
    from benchmark.readers import mfu
    cfg = harness.load_json(harness.BENCH_DIR, "configs", "opt-1.3b.json")
    view = {"trace": {"programs": {
        "jit_train_step": {"runs": 3, "device_s": 4.0, "ops": {},
                           "run_s": [1.7157, 1.7586, 0.01]},
        "jit_other": {"runs": 1, "device_s": 9.0, "ops": {},
                      "run_s": [9.0]}}},
        "stats": {"counters": {}}, "config": cfg,
        "traffic": {"rows": 8, "seq_len": 2048}, "chips": 1,
        "model": harness.load_module(harness.BENCH_DIR, "references",
                                     cfg["reference"]),
        "bench_dir": harness.BENCH_DIR, "device_kind": "TPU v5 lite"}
    params = harness.load_json(harness.BENCH_DIR, "metrics",
                               "mfu.train.json")["params"]
    got = mfu.read(view, **params)
    want = 100 * counts.train_flops_per_token(sizes("opt-1.3b"), 2048) \
        * 16384 / 1.7586 / PEAKS["bf16_flops"]
    assert got == pytest.approx(want, rel=1e-9) and 39 < got < 41
    assert mfu.read(dict(view, trace={"programs": {}}), **params) is None


def test_peaks_are_keyed_by_kind_and_unknown_kinds_fail():
    from benchmark.readers import roofline
    view = {"trace": {"programs": {"jit_decode_step": {
        "runs": 2, "device_s": 0.04, "ops": {}}}},
        "stats": {"counters": {"steps": 10, "rows": 640,
                               "context_tokens": 10 * 64 * 350}},
        "config": harness.load_json(harness.BENCH_DIR, "configs",
                                    "qwen2-7b.json"),
        "model": harness.load_module(harness.BENCH_DIR, "references",
                                     "transformer"),
        "bench_dir": harness.BENCH_DIR, "device_kind": "TPU v5 lite"}
    share = roofline.read(view, "decode_step", "decode_step_bytes")
    assert share == pytest.approx(100 * 9.3233e9 / 819e9 / 0.020, rel=1e-3)
    with pytest.raises(KeyError):
        roofline.read(dict(view, device_kind="TPU v9"), "decode_step",
                      "decode_step_bytes")
    assert roofline.read(dict(view, trace={"programs": {}}), "decode_step",
                         "decode_step_bytes") is None
