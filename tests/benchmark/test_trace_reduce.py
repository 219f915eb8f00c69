"""The trace -> metrics reduction on a small synthetic trace."""
import types

import pytest

from benchmark import trace_reduce as T


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n.replace("_", " "), events=evs)
        for n, evs in lines.items()])


MS = 1_000_000


def synthetic():
    """10 ms window.  The device runs program A in 1-4 ms (a loop op holding
    two body ops) and program B in 6-8 ms; the host's spans cover 0-5 and
    5-10 ms."""
    ops = [ev("%while.1 = (s32[]) while(%t), body=%b", 1 * MS, 3 * MS),
           ev("%fusion.1 = bf16[8,8]{1,0} fusion(%x), kind=kLoop", 1 * MS,
              1 * MS),
           ev('%k.2 = bf16[8]{0} custom-call(%y), custom_call_target='
              '"tpu_custom_call"', 2 * MS, 2 * MS),
           ev("%fusion.9 = f32[4]{0} fusion(%z), kind=kLoop", 6 * MS, 2 * MS)]
    device = plane("/device:TPU:0",
                   XLA_Modules=[ev("jit_a(123)", 1 * MS, 3 * MS),
                                ev("jit_b(77)", 6 * MS, 2 * MS)],
                   XLA_Ops=ops)
    host = plane("/host:CPU", main=[ev("bench.x.step", 0, 5 * MS),
                                    ev("bench.x.client", 5 * MS, 5 * MS),
                                    ev("other", 0, 10 * MS)])
    return [device, host]


def test_busy_idle_and_window():
    r = T.reduce_planes(synthetic())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    r = T.reduce_planes(synthetic())
    gaps = dict(r["idle_gaps"])
    # 0-1 and 4-5 ms under step; 5-6 and 8-10 ms under client
    assert gaps["bench.x.step"] == pytest.approx(0.002)
    assert gaps["bench.x.client"] == pytest.approx(0.003)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_per_program_time_and_self_time_of_ops():
    r = T.reduce_planes(synthetic())
    a, b = r["programs"]["jit_a"], r["programs"]["jit_b"]
    assert (a["runs"], b["runs"]) == (1, 1)
    assert a["device_s"] == pytest.approx(0.003)
    assert b["device_s"] == pytest.approx(0.002)
    assert a["run_s"] == pytest.approx([0.003])      # one entry per run
    # the loop's own time is what its body does not cover: nothing
    assert a["ops"]["while.1 while s32[]"] == pytest.approx(0.0)
    assert a["ops"]["fusion.1 fusion bf16[8,8]"] == pytest.approx(0.001)
    assert a["ops"]["k.2 custom-call bf16[8] tpu_custom_call"] \
        == pytest.approx(0.002)
    assert r["top_ops"][0][0].startswith("k.2")
    assert sum(v for _, v in r["top_ops"]) == pytest.approx(r["busy_s"])


@pytest.mark.parametrize("intervals,want", [
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)]),
    ([], []),
])
def test_union(intervals, want):
    assert T.union(intervals) == want


@pytest.mark.parametrize("text,want", [
    ("jit_decode_step(9494706179036258331)", "jit_decode_step"),
    ("jit_train_step", "jit_train_step"),
])
def test_program_name(text, want):
    assert T.program_name(text) == want


@pytest.mark.parametrize("text,want", [
    ('%closed_call.10 = bf16[64,28,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
     's32[1]{0:T(128)} %d), custom_call_target="tpu_custom_call", x={}',
     "closed_call.10 custom-call bf16[64,28,128] tpu_custom_call"),
    ("%m.2 = (f32[64,64]{1,0:T(8,128)S(1)}, f32[64,64]{1,0}) fusion(%f)",
     "m.2 fusion f32[64,64]"),
    ("%iota.10 = s32[64,1,1]{0,2,1:T(1,128)S(1)} iota(), iota_dimension=0",
     "iota.10 iota s32[64,1,1]"),
    ("no hlo here", "no hlo here"),
])
def test_op_name(text, want):
    assert T.op_name(text) == want


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_planes([plane("/host:CPU", main=[])])


def test_readers_on_the_reduction():
    """The trace readers: per-run program time, an op's share of a
    program, the idle share; nothing to read gives None, never 0."""
    from benchmark.readers import idle_share, op_share, program_time
    view = {"trace": T.reduce_planes(synthetic()), "stats": {
        "traced": {"prompt_tokens": 500}}}
    assert program_time.read(view, "jit_a", scale=1e3) == pytest.approx(3.0)
    assert program_time.read(view, "jit_(a|b)", per="prompt_tokens",
                             scale=1e6) == pytest.approx(10.0)
    assert program_time.read(view, "nothing") is None
    assert op_share.read(view, "jit_a", "custom-call") \
        == pytest.approx(100 * 2 / 3)
    assert op_share.read(view, "jit_b", "custom-call") is None
    assert idle_share.read(view) == pytest.approx(50.0)
    assert idle_share.read({"trace": {"window_s": 0, "busy_s": 0}}) is None
