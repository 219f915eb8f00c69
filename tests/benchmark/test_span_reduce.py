"""The program-span and scope reduction on a small synthetic trace (the
style of test_trace_reduce.py): idle time by innermost program span, the
step count, device self time by scope path and by kernel name, and the two
readers."""
import types

import pytest

from benchmark import span_reduce as S
from benchmark import trace_reduce as T

MS = 1_000_000


def ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n.replace("_", " "), events=evs)
        for n, evs in lines.items()])


def synthetic():
    """A 20 ms window (the benchmark's marks) over two serve steps.

    host:    client 0-2 | step A 2-10 | client 10-12 | step B 12-19 | 19-20
    step A:  admission 2-3, engine 3-8 (plan 3-4, dispatch 4-5, fetch
             5-8), sample 8-9.5, bookkeep 9.5-10
    step B:  engine 12-17 (fetch 13-17), nothing else named
    device:  busy 4.5-7 (decode_step), 13.5-15 (decode_step)
    idle:    0-4.5 (client 2, admission 1, plan 1, dispatch 0.5), 7-13.5
             (fetch 1, sample 1.5, bookkeep 0.5, client 2, engine self 1,
             fetch 0.5), 15-20 (fetch 2, step self 2, client 1)
    """
    def hlo(name, shape="bf16[8,8]{1,0}", op="fusion"):
        return f"%{name} = {shape} {op}(%x), kind=kLoop"

    pre = "jit(decode_step)/jit(main)/while/body/"
    ops = [
        ev(hlo("while.3", "(s32[])", "while"), int(4.5 * MS), int(2.5 * MS)),
        ev(hlo("paged_attention_decode.1", op="custom-call"),
           int(4.5 * MS), 1 * MS,
           tf_op=pre + "attention/paged_attention_decode/pallas_call"),
        ev(hlo("fusion.7"), int(5.5 * MS), int(0.5 * MS),
           tf_op=pre + "attention/kv_write/scatter"),
        ev(hlo("fusion.8"), 6 * MS, 1 * MS,
           tf_op="jit(decode_step)/jit(main)/lm_head/dot_general"),
        # the second run: the kernel alone, no stat on its event
        ev(hlo("paged_attention_decode.1", op="custom-call"),
           int(13.5 * MS), int(1.5 * MS)),
    ]
    device = plane(
        "/device:TPU:0",
        XLA_Modules=[ev("jit_decode_step(11)", int(4.5 * MS), int(2.5 * MS)),
                     ev("jit_decode_step(11)", int(13.5 * MS),
                        int(1.5 * MS))],
        XLA_Ops=ops)
    main = [
        ev("bench.serve.client", 0, 2 * MS),
        ev("bench.serve.step", 2 * MS, 8 * MS),
        ev("serve.step", 2 * MS, 8 * MS, step=1),
        ev("serve.admission", 2 * MS, 1 * MS, admitted=2),
        ev("serve.engine", 3 * MS, 5 * MS),
        ev("engine.plan", 3 * MS, 1 * MS, rows=2),
        # attributes may ride the name instead of the stats
        ev("engine.dispatch#program=decode_step#", 4 * MS, 1 * MS),
        ev("engine.fetch", 5 * MS, 3 * MS, program="decode_step"),
        ev("serve.sample", 8 * MS, int(1.5 * MS)),
        ev("serve.bookkeep", int(9.5 * MS), int(0.5 * MS)),
        ev("bench.serve.client", 10 * MS, 2 * MS),
        ev("bench.serve.step", 12 * MS, 7 * MS),
        ev("serve.step", 12 * MS, 7 * MS, step=2),
        ev("serve.engine", 12 * MS, 5 * MS),
        ev("engine.fetch", 13 * MS, 4 * MS),
        ev("bench.serve.client", 19 * MS, 1 * MS),
        ev("PjitFunction(decode_step)", 4 * MS, 1 * MS),
    ]
    # another thread's program spans (a second loop) are not this one's
    other = [ev("engine.fetch", 0, 20 * MS)]
    return [device, plane("/host:CPU", main=main, worker=other)]


def test_idle_is_charged_to_the_innermost_span_and_partitions():
    r = S.reduce_planes(synthetic())
    idle = {k: round(v * 1e3, 6) for k, v in r["idle_s"].items()}
    assert idle == {
        "_outside_": 5.0,            # the client, three times
        "serve.admission": 1.0,
        "engine.plan": 1.0,
        "engine.dispatch": 0.5,      # the gap straddles plan and dispatch
        "engine.fetch": 3.5,         # 7-8, 13-13.5, 15-17
        "serve.sample": 1.5,
        "serve.bookkeep": 0.5,
        "serve.engine": 1.0,         # 12-13: engine's own, under no child
        "serve.step": 2.0,           # 17-19: the step's own
    }
    # the charges partition the idle time of trace_reduce's reduction
    t = T.reduce_planes(synthetic())
    assert r["window_s"] == pytest.approx(t["window_s"]) \
        == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(t["busy_s"]) == pytest.approx(0.004)
    assert sum(r["idle_s"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"])


def test_steps_and_the_step_spans_own_numbers():
    r = S.reduce_planes(synthetic())
    assert r["steps"] == 2
    assert r["step_span_ms_p50"] == pytest.approx(7.5)
    assert r["step_period_ms_p50"] == pytest.approx(10.0)
    assert "engine.dispatch" in r["seen"] and "bench.serve.step" \
        not in r["seen"]


def test_a_step_cut_by_the_window_is_not_counted():
    planes = synthetic()
    host = planes[1].lines[0].events
    # the last client mark goes: the window now ends with step B's mark,
    # and a third step span reaches past it
    host[:] = [e for e in host if not (e.name == "bench.serve.client"
                                       and e.start_ns == 19 * MS)]
    host.append(ev("serve.step", int(18.5 * MS), 3 * MS))
    assert S.reduce_planes(planes)["steps"] == 2


def test_scopes_by_stat_and_by_instruction_name():
    r = S.reduce_planes(synthetic())
    prog = r["programs"]["jit_decode_step"]
    assert prog["device_s"] == pytest.approx(0.004)
    table = S.scope_table(r)["jit_decode_step"]
    assert table["while/body/attention/paged_attention_decode"] \
        == pytest.approx(0.001)
    assert table["while/body/attention/kv_write"] == pytest.approx(0.0005)
    assert table["lm_head"] == pytest.approx(0.001)
    # no stat: the kernel goes by its instruction name
    assert table["paged_attention_decode"] == pytest.approx(0.0015)
    assert sum(table.values()) == pytest.approx(prog["device_s"])


@pytest.mark.parametrize("text,want", [
    ("engine.fetch#program=decode_step,bytes=4#", "engine.fetch"),
    ("serve.step", "serve.step"),
])
def test_base_name(text, want):
    assert S.base_name(text) == want


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/jit(main)/forward_backward/while/body/"
     "transpose(jvp(attention))/checkpoint/rematted_computation/mul",
     "forward_backward/while/body/transpose(jvp(attention))/checkpoint/"
     "rematted_computation"),
    ("jit(f)/add", ""),
    ("", ""),
])
def test_scope_label(path, want):
    assert S.scope_label(path) == want


def _view_on(monkeypatch, reduced):
    monkeypatch.setattr(S, "of_view", lambda view: reduced)
    return {"trace": {"window_s": 1.0}}


def test_readers(monkeypatch):
    from benchmark.readers import scope_share, span_idle
    view = _view_on(monkeypatch, S.reduce_planes(synthetic()))
    assert span_idle.read(view, r"^engine\.fetch$") == pytest.approx(1.75)
    assert span_idle.read(
        view, r"^(engine\.plan|engine\.dispatch|serve\.engine)$") \
        == pytest.approx(1.25)
    assert span_idle.read(view, r"^(serve\.step|_outside_)$") \
        == pytest.approx(3.5)
    # a span the program never opened: nothing to read, not 0
    assert span_idle.read(view, r"^serve\.finalize$") is None
    # by stat and by name together: (1 + 1.5) of 4 ms
    assert scope_share.read(view, "decode_step", "paged_attention") \
        == pytest.approx(62.5)
    assert scope_share.read(view, "decode_step", r"\blm_head\b") \
        == pytest.approx(25.0)
    assert scope_share.read(view, "decode_step", r"\boptimizer\b") is None
    assert scope_share.read(view, "train_step", "paged_attention") is None


def test_a_program_without_spans_gives_the_readers_nothing(monkeypatch):
    """The parent commit's trace: the benchmark's marks, device ops, no
    program span."""
    from benchmark.readers import span_idle
    planes = synthetic()
    for line in planes[1].lines:
        line.events[:] = [e for e in line.events
                          if not S.PROGRAM_SPAN.match(e.name)]
    r = S.reduce_planes(planes)
    assert r["steps"] == 0 and r["idle_s"] == {"_outside_": 0.016}
    view = _view_on(monkeypatch, r)
    assert span_idle.read(view, r"^(serve\.step|_outside_)$") is None
    assert span_idle.read(view, r"^engine\.fetch$") is None


def test_readers_return_none_on_an_empty_view(tmp_path):
    """No traced run, or no file where the harness writes it."""
    from benchmark.readers import scope_share, span_idle
    bench_dir = str(tmp_path / "benchmark")
    for view in ({"trace": {}, "bench_dir": bench_dir},
                 {"trace": {"window_s": 1.0}, "bench_dir": bench_dir}):
        assert S.of_view(view) is None
        assert span_idle.read(view, "engine") is None
        assert scope_share.read(view, "decode_step", "lm_head") is None


# -- the scope path lives in the event METADATA of the xplane file ----------
def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _msg(*fields):
    """(field number, int | bytes | str) -> protobuf wire bytes."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            raw = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(raw)) + raw
    return out


def _xplane(name, events):
    """One XPlane with stat names 1=tf_op, 2=program_id, 3=flops and one
    event metadata per (hlo text, tf_op, program id)."""
    stat_names = [_msg((1, i), (2, _msg((1, i), (2, n))))
                  for i, n in ((1, "tf_op"), (2, "program_id"),
                               (3, "flops"))]
    metas = []
    for i, (text, tf_op, prog) in enumerate(events, start=1):
        stats = [(5, _msg((1, 3), (3, 99)))]
        if tf_op is not None:
            stats.append((5, _msg((1, 1), (5, tf_op))))
        if prog is not None:
            stats.append((5, _msg((1, 2), (3, prog))))
        metas.append(_msg((1, i), (2, _msg((1, i), (2, text), *stats))))
    return _msg((2, name), *[(4, m) for m in metas],
                *[(5, s) for s in stat_names])


def test_event_scopes_reads_the_metadata_tables(tmp_path):
    big = 13344203572343057511              # above 2**63, as on the chip
    space = _msg(
        (1, _xplane("/device:TPU:0", [
            ("%fusion.175 = bf16[8]{0} fusion(%a)",
             "jit(decode_step)/while/body/attention/kv_write/scatter:", 7),
            ("%fusion.175 = bf16[8]{0} fusion(%a)",
             "jit(prefill_full)/lm_head/dot_general:", big),
            ("%iota.1 = s32[4]{0} iota()", None, 7)])),
        (1, _xplane("/host:CPU", [("serve.step", "not/a/device", 7)])))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    scopes = S.event_scopes(str(path))
    text = "%fusion.175 = bf16[8]{0} fusion(%a)"
    assert scopes[(7, text)] \
        == "jit(decode_step)/while/body/attention/kv_write/scatter"
    assert scopes[(big, text)] == "jit(prefill_full)/lm_head/dot_general"
    assert scopes[(None, text)] == scopes[(7, text)]       # the first met
    assert not any("iota" in k[1] or "serve" in k[1] for k in scopes)
    # the same text in two programs: the enclosing run's id decides
    op = ev(text, 0, 1)
    assert S.scope_path(op, S.program_id(f"jit_prefill_full({big})"),
                        scopes).endswith("lm_head/dot_general")
    assert S.scope_path(op, 7, scopes).endswith("kv_write/scatter")
    assert S.scope_path(op, 1234, scopes).endswith("kv_write/scatter")
    assert S.scope_path(ev("%other = f32[] add()", 0, 1), 7, scopes) == ""


def test_metadata_scopes_key_the_ops_of_each_program_run():
    planes = synthetic()
    for e in planes[0].lines[1].events:
        e.stats = []                       # as on the chip: nothing here
    kernel = planes[0].lines[1].events[1].name
    r = S.reduce_planes(planes, scopes={
        (11, kernel): "jit(decode_step)/while/body/attention/"
                      "paged_attention_decode/pallas_call"})
    table = S.scope_table(r)["jit_decode_step"]
    assert table["while/body/attention/paged_attention_decode"] \
        == pytest.approx(0.0025)           # both runs of the kernel
    assert table["fusion"] == pytest.approx(0.0015)   # by instruction name
