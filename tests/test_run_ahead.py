"""Tests: one serve step kept in flight (ISSUE 30).

A serve step is a dispatch and a collect (`InferenceEngineV2.dispatch` /
`collect`), and on the per-step path the serve loop collects step n after
it has dispatched step n+1: a decode row whose input token is still on
the device takes it there.  Locked here, on the CPU: every request's
tokens and final state are those of the collect-at-once order (the same
engine stepped through `put`/`step`: `AtOnce`), a finish being reported
one call later; what the host learns late (EOS, cancel, deadline,
preemption) costs one dropped row and no block; a row sampled on the
host keeps the loop collecting at once; a first token is stamped before
the same call waits for decode tokens; nothing is left uncollected by
`run_until_idle`, `drain` or a `ThreadedServer` shutdown; a loop warmed
through `engine.put` alone compiles nothing when it runs ahead; and
`engine.step()` is `dispatch` + `collect` row for row."""
import numpy as np
import pytest

import jax

from deepspeed_tpu.config.config import (PreemptionConfig, ServingConfig,
                                         StreamingConfig, StructuredConfig)
from deepspeed_tpu.serving import RequestState, ServeLoop, ThreadedServer
from deepspeed_tpu.serving.structured import ResponseFormat
from deepspeed_tpu.utils.device import CompileCounter

from test_device_sampling import EOS, VOCAB, _engine, _prompts, tiny  # noqa: F401
from test_serving import FakeClock

pytestmark = pytest.mark.serving


class AtOnce:
    """The engine without the `collect` the serve loop probes: the loop
    then calls plain `put` and `step`, which collect what they dispatched
    before they return.  The collect-at-once order of events, on the same
    weights and through the same programs."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        if name == "collect":
            raise AttributeError(name)
        return getattr(self._engine, name)


def _counters(loop):
    return {k: loop.telemetry.counters[k] for k in
            ("steps_run_ahead", "steps_collected_at_once", "rows_overrun")}


def _drive(loop, clock, max_steps=400):
    steps = 0
    while loop.has_work:
        loop.step()
        clock.advance(1.0)
        steps += 1
        assert steps < max_steps
    return steps


def _both(tiny, script, engine_kw=None, **cfg):
    """Run `script(loop, clock) -> requests` on the collect-at-once order
    and on the loop that runs ahead: ((loop, requests) of each)."""
    runs = []
    for wrap in (AtOnce, lambda e: e):
        clock = FakeClock()
        loop = ServeLoop(wrap(_engine(tiny, **(engine_kw or {}))),
                         ServingConfig(audit_blocks=True, **cfg),
                         clock=clock)
        reqs = script(loop, clock)
        assert not loop._in_flight and not loop.has_work
        assert loop.engine.state.seqs == {}
        loop.engine.audit_blocks()
        runs.append((loop, reqs))
    return runs


def _outcome(reqs):
    return [(r.state, list(map(int, r.output_tokens))) for r in reqs]


# -- the tokens and the final states are the collect-at-once order's --------
def _greedy_mix(loop, clock):
    reqs = [loop.submit(p, max_new_tokens=n) for p, n in
            zip(_prompts(3, (9, 21, 5, 14)), (1, 2, 17, 9))]
    _drive(loop, clock)
    return reqs


def _queued(loop, clock):
    # more requests than rows: a freed row is seen one call later
    reqs = [loop.submit(p, max_new_tokens=n) for p, n in
            zip(_prompts(4, (3, 4, 5, 6, 7, 8, 9)), (3, 1, 6, 2, 5, 4, 7))]
    _drive(loop, clock)
    return reqs


def _two_waves(loop, clock):
    # the second wave's prompts share the first's leading blocks
    first = _prompts(6, (24, 24, 17))
    reqs = [loop.submit(p, max_new_tokens=5) for p in first]
    _drive(loop, clock)
    tails = _prompts(7, (6, 9, 4))
    reqs += [loop.submit(np.concatenate([p[:16], t]), max_new_tokens=6)
             for p, t in zip(first, tails)]
    _drive(loop, clock)
    return reqs


@pytest.mark.parametrize("script, engine_kw, cfg", [
    (_greedy_mix, {}, {}),
    (_greedy_mix, {"full_prompt_prefill": False}, {}),
    (_greedy_mix, {"max_prefill_tokens_per_step": 16}, {}),
    (_greedy_mix, {}, {"transfer_guard": "disallow"}),
    (_queued, {}, {}),
    (_two_waves, {"full_prompt_prefill": False},
     {"prefix_cache_blocks": 16}),
], ids=["mix", "chunks", "prompt_over_steps", "guarded", "queued",
        "prefix_cache"])
def test_tokens_and_states_are_the_collect_at_once_orders(
        tiny, script, engine_kw, cfg):
    (ref_loop, ref), (loop, got) = _both(tiny, script, engine_kw, **cfg)
    assert all(r.state is RequestState.DONE for r in ref)
    assert _outcome(got) == _outcome(ref)
    # every step that launched engine work stayed uncollected (greedy
    # rows only), and a stream with no stop token computes no row for
    # nothing: the request whose pending token is its last is left out
    assert _counters(ref_loop)["steps_run_ahead"] == 0
    c = _counters(loop)
    assert c["steps_run_ahead"] > 0
    assert c["steps_collected_at_once"] == c["rows_overrun"] == 0
    assert loop.telemetry.counters["sampled_on_device"] \
        == sum(len(r.output_tokens) for r in got)
    if "prefix_cache_blocks" in cfg:
        assert loop.telemetry.counters["prefix_hits"] \
            == ref_loop.telemetry.counters["prefix_hits"] > 0


def test_a_finish_is_reported_one_call_later_not_lost(tiny):
    def script(loop, clock):
        req = loop.submit(_prompts(8, (7,))[0], max_new_tokens=3)
        calls = []
        while loop.has_work:
            calls.append(list(loop.step()))
        return [req], calls

    runs = []
    for wrap in (AtOnce, lambda e: e):
        loop = ServeLoop(wrap(_engine(tiny)), ServingConfig(),
                         clock=FakeClock())
        runs.append(script(loop, None))
    (ref,), ref_calls = runs[0]
    (got,), calls = runs[1]
    assert list(got.output_tokens) == list(ref.output_tokens)
    assert [len(c) for c in ref_calls] == [0, 0, 1]
    assert [len(c) for c in calls] == [0, 0, 0, 1]
    assert calls[-1] == [got]


# -- what the host learns late costs one row, dropped at collect ------------
def test_eos_mid_batch_drops_one_row_and_leaks_no_block(tiny):
    prompts = _prompts(3, (9, 21, 5))
    (_, free), _ = _both(
        tiny, lambda loop, clock: (
            [loop.submit(p, max_new_tokens=12) for p in prompts],
            _drive(loop, clock))[0])
    want = [list(map(int, r.output_tokens)) for r in free]
    # stop one request on a token it emits mid-stream for the first time
    who, cut = next((i, j + 1) for i, toks in enumerate(want)
                    for j in range(2, 11) if toks[j] not in toks[:j])
    stop = want[who][cut - 1]

    def script(loop, clock):
        reqs = [loop.submit(p, max_new_tokens=12,
                            eos_token_id=stop if i == who else None)
                for i, p in enumerate(prompts)]
        _drive(loop, clock)
        return reqs

    (ref_loop, ref), (loop, got) = _both(tiny, script)
    assert _outcome(got) == _outcome(ref)
    assert [list(map(int, r.output_tokens)) for r in got] \
        == [w[:cut] if i == who else w for i, w in enumerate(want)]
    # the row computed behind the EOS nobody had seen yet
    assert _counters(loop)["rows_overrun"] == 1
    assert _counters(ref_loop)["rows_overrun"] == 0


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_with_a_step_in_flight(tiny, how):
    prompts = _prompts(5, (9, 13, 6))

    def script(loop, clock):
        kw = {"timeout_s": 5.5} if how == "deadline" else {}
        reqs = [loop.submit(prompts[0], max_new_tokens=14),
                loop.submit(prompts[1], max_new_tokens=14, **kw),
                loop.submit(prompts[2], max_new_tokens=14)]
        for _ in range(5):
            loop.step()
            clock.advance(1.0)
        assert reqs[1].state is RequestState.DECODE
        if how == "cancel":
            assert loop.cancel(reqs[1].uid)
        _drive(loop, clock)
        return reqs

    (ref_loop, ref), (loop, got) = _both(tiny, script)
    ended = (RequestState.CANCELLED if how == "cancel"
             else RequestState.TIMED_OUT)
    assert [r.state for r in got] == [r.state for r in ref] \
        == [RequestState.DONE, ended, RequestState.DONE]
    for i in (0, 2):
        assert list(got[i].output_tokens) == list(ref[i].output_tokens)
    # the one that ended early holds the same stream, one token shorter:
    # the token in flight when the host ended it was dropped
    a, b = list(got[1].output_tokens), list(ref[1].output_tokens)
    assert 0 < len(a) == len(b) - 1 and a == b[:len(a)]
    assert _counters(loop)["rows_overrun"] == 1


def test_preemption_and_resume_under_the_same_uid(tiny):
    rng = np.random.RandomState(47)
    low_p = rng.randint(1, VOCAB, 12).astype(np.int32)
    high_p = rng.randint(1, VOCAB, 8).astype(np.int32)

    def script(loop, clock):
        # low's lifetime needs 7 of the 8 blocks, so high cannot be
        # admitted while low decodes: it ages, then preempts low
        low = loop.submit(low_p, max_new_tokens=40, priority=1)
        for _ in range(4):
            loop.step()
            clock.advance(1.0)
        assert low.state is RequestState.DECODE
        high = loop.submit(high_p, max_new_tokens=8, priority=0)
        _drive(loop, clock)
        assert loop.telemetry.counters["preemptions"] >= 1
        assert low.preemptions >= 1
        return [low, high]

    (_, ref), (loop, got) = _both(
        tiny, script, {"num_blocks": 8, "max_seqs": 2},
        preemption=PreemptionConfig(enabled=True, ttft_slo_s=2.0,
                                    urgency_fraction=0.5))
    assert all(r.state is RequestState.DONE for r in got)
    assert _outcome(got) == _outcome(ref)
    # the victim's token in flight was dropped; it came back as another
    # sequence under its uid and made that token again
    assert _counters(loop)["rows_overrun"] >= 1
    assert len(got[0].output_tokens) == 40


# -- a row sampled on the host keeps the loop collecting at once ------------
@pytest.mark.parametrize("odd", [
    dict(temperature=0.9, seed=31337),
    dict(eos_token_id=EOS, response_format=ResponseFormat.regex(r"ab(ab)?c")),
], ids=["stochastic", "grammar"])
def test_a_host_sampled_row_keeps_every_step_collected_at_once(tiny, odd):
    prompts = _prompts(5, (6, 9, 12))

    def script(loop, clock):
        reqs = [loop.submit(prompts[0], max_new_tokens=8),
                loop.submit(prompts[1], max_new_tokens=8, **odd),
                loop.submit(prompts[2], max_new_tokens=8)]
        _drive(loop, clock)
        # once the odd row is gone the loop runs ahead again
        tail = loop.submit(prompts[0], max_new_tokens=4)
        _drive(loop, clock)
        return reqs + [tail]

    (_, ref), (loop, got) = _both(tiny, script,
                                  structured=StructuredConfig())
    assert _outcome(got) == _outcome(ref)
    c = _counters(loop)
    on_host = len(got[1].output_tokens)
    assert loop.telemetry.counters["sampled_on_host"] == on_host > 0
    # one serve step per token of the odd request was collected at once
    assert c["steps_collected_at_once"] == on_host
    assert c["steps_run_ahead"] > 0 and c["rows_overrun"] == 0


# -- a first token does not wait for the decode tokens of its call ----------
def test_first_token_is_stamped_before_the_calls_decode_collect(tiny):
    clock = FakeClock()
    eng = _engine(tiny)
    loop = ServeLoop(eng, ServingConfig(
        streaming=StreamingConfig(enabled=True)), clock=clock)
    old = loop.submit(_prompts(9, (7,))[0], max_new_tokens=20)
    for _ in range(3):
        loop.step()
    assert old.state is RequestState.DECODE and loop._in_flight
    new = loop.submit(_prompts(9, (11,))[0], max_new_tokens=5)
    seen = []
    collect = eng.collect

    def waited(pending, part=None):
        # waiting for a program's tokens takes a second of serve clock
        if part == "decode":
            seen.append((new.first_token_time, new.stream.emitted,
                         len(old.generated)))
        clock.advance(1.0)
        return collect(pending, part)

    eng.collect = waited
    loop.step()                      # admits `new`, dispatches its prefill
    assert new.first_token_time is None
    t0, n_old = clock(), len(old.generated)
    loop.step()                      # collects it, then old's decode token
    # the prefill collect took a second, the decode collect another
    assert new.first_token_time == t0 + 1.0
    assert seen[-1] == (t0 + 1.0, 1, n_old)
    assert len(old.generated) == n_old + 1 and clock() == t0 + 2.0
    eng.collect = collect
    loop.run_until_idle(max_steps=100)
    assert new.state is old.state is RequestState.DONE


# -- nothing stays uncollected -------------------------------------------
def test_run_until_idle_and_drain_leave_nothing_uncollected(tiny):
    eng = _engine(tiny)
    loop = ServeLoop(eng, ServingConfig(audit_blocks=True),
                     clock=FakeClock())
    reqs = [loop.submit(p, max_new_tokens=6) for p in _prompts(2, (5, 9))]
    done = loop.run_until_idle(max_steps=50)
    assert sorted(r.uid for r in done) == sorted(r.uid for r in reqs)
    assert not loop._in_flight and not loop.has_work

    busy = [loop.submit(p, max_new_tokens=9) for p in _prompts(3, (5, 9))]
    for _ in range(3):
        loop.step()
    assert loop._in_flight and loop.has_work
    assert loop._in_flight[0].decode_rows == 2
    late = loop.submit(_prompts(4, (4,))[0], max_new_tokens=3)
    assert loop.drain() == [late]    # unserved; the two in flight stay
    done = loop.run_until_idle(max_steps=50)
    assert {r.uid for r in done} == {r.uid for r in busy}
    assert all(r.state is RequestState.DONE for r in busy)
    assert not loop._in_flight and eng.state.seqs == {}
    eng.audit_blocks()


def test_take_active_and_fail_all_drop_the_step_in_flight(tiny):
    for end in ("take_active", "fail_all"):
        eng = _engine(tiny)
        loop = ServeLoop(eng, ServingConfig(), clock=FakeClock())
        reqs = [loop.submit(p, max_new_tokens=9)
                for p in _prompts(3, (5, 9))]
        for _ in range(3):
            loop.step()
        assert loop._in_flight
        n = [len(r.generated) for r in reqs]
        out = (loop.take_active() if end == "take_active"
               else loop.fail_all(RuntimeError("gone")))
        assert {r.uid for r in out} == {r.uid for r in reqs}
        assert not loop._in_flight and not loop.has_work
        # the uncollected tokens went with it
        assert [len(r.generated) for r in reqs] == n
        assert _counters(loop)["rows_overrun"] == 2
        assert eng.state.seqs == {}
        eng.audit_blocks()


@pytest.mark.parametrize("drain", [True, False])
def test_threaded_server_shutdown_leaves_nothing_uncollected(tiny, drain):
    eng = _engine(tiny)
    server = ThreadedServer(eng, ServingConfig())
    want = ServeLoop(AtOnce(_engine(tiny)), ServingConfig())
    prompts = _prompts(6, (5, 9, 12))
    ref = [want.submit(p, max_new_tokens=7) for p in prompts]
    want.run_until_idle(max_steps=100)
    reqs = [server.submit(p, max_new_tokens=7) for p in prompts]
    if drain:
        for r, w in zip(reqs, ref):
            assert list(server.result(r, timeout=120)) \
                == list(w.output_tokens)
    server.shutdown(drain=drain, timeout=120)
    assert not server._thread.is_alive()
    assert not server.loop._in_flight
    if drain:
        assert eng.state.seqs == {}
        assert _counters(server.loop)["rows_overrun"] == 0


# -- a raise with a step in flight loses no step ------------------------------
@pytest.mark.parametrize("where", ["put", "step", "collect_prefill",
                                   "collect_decode"])
def test_a_raise_with_a_step_in_flight_loses_no_token(tiny, where):
    """A replica that survives a transient error goes on: the engine call
    named raises once while a step is in flight, the call raises, and
    the loop then finishes every request with the collect-at-once
    order's tokens (on the parent the same error left the active
    requests decoding)."""
    prompts = _prompts(8, (7, 12, 5))

    def script(loop, clock):
        reqs = [loop.submit(p, max_new_tokens=n)
                for p, n in zip(prompts[:2], (14, 9))]
        for _ in range(3):
            loop.step()
            clock.advance(1.0)
        assert all(r.state is RequestState.DECODE for r in reqs)
        eng = loop.engine
        if hasattr(eng, "collect"):
            assert loop._in_flight
            name = where.split("_")[0]
            real = getattr(eng, name)

            def once(*a, **k):
                if where.startswith("collect") \
                        and (a[1:] or (k.get("part"),))[0] \
                        != where.split("_")[1]:
                    return real(*a, **k)
                setattr(eng, name, real)
                raise RuntimeError("transient")

            setattr(eng, name, once)
            if where != "step":    # a new request: `put`, and a prefill
                reqs.append(loop.submit(prompts[2], max_new_tokens=6))
                if where == "collect_prefill":
                    loop.step()    # its prefill is dispatched, awaited
                    clock.advance(1.0)
            with pytest.raises(RuntimeError, match="transient"):
                loop.step()
            assert getattr(eng, name) == real and loop.has_work
            assert len(loop._in_flight) == (2 if where == "collect_decode"
                                            else 1)
            if where == "put":     # the admission was rolled back
                assert reqs[2].state is RequestState.QUEUED
        elif where != "step":
            reqs.append(loop.submit(prompts[2], max_new_tokens=6))
        _drive(loop, clock)
        return reqs

    (_, ref), (loop, got) = _both(tiny, script)
    assert all(r.state is RequestState.DONE for r in got)
    assert _outcome(got) == _outcome(ref)
    assert _counters(loop)["rows_overrun"] == 0


# -- no compile once `engine.put` has warmed the shapes ----------------------
@pytest.mark.parametrize("committed", [False, True],
                         ids=["as_made", "weights_committed"])
def test_a_loop_warmed_through_put_alone_compiles_nothing(tiny, committed):
    from benchmark.systems import warm_serving
    model, params = tiny
    if committed:      # e.g. weights somebody device_put where they serve
        params = jax.device_put(params, jax.devices()[0])
    eng = _engine((model, params), num_blocks=128, max_blocks_per_seq=16,
                  max_seqs=8, prefill_chunk_size=32,
                  max_prefill_tokens_per_step=64)
    counter = CompileCounter()
    warm_serving(eng, (8, 40), VOCAB)
    warm = (counter.requests, counter.compile_s)
    assert warm[0] > 0 or warm[1] > 0.0     # the counter counts here
    loop = ServeLoop(eng, ServingConfig(), clock=FakeClock())
    rng = np.random.RandomState(0)
    reqs = [loop.submit(rng.randint(1, VOCAB, rng.randint(8, 41))
                        .astype(np.int32),
                        max_new_tokens=int(rng.randint(2, 12)))
            for _ in range(20)]
    loop.run_until_idle(max_steps=400)
    assert all(r.state is RequestState.DONE for r in reqs)
    c = _counters(loop)
    assert c["steps_run_ahead"] > 20 and c["steps_collected_at_once"] == 0
    assert (counter.requests, counter.compile_s) == warm


# -- the engine: step() is dispatch + collect, row for row -------------------
def _dense(tiny):
    return _engine(tiny), _engine(tiny)


def _latent(tiny):
    from test_latent_serving import engine
    return engine(), engine()


@pytest.mark.parametrize("make, vocab", [(_dense, VOCAB), (_latent, None)],
                         ids=["dense", "latent"])
def test_engine_step_equals_dispatch_plus_collect(tiny, make, vocab):
    a, b = make(tiny)
    vocab = vocab or a.cfg.vocab_size
    rng = np.random.RandomState(12)
    uids = [1, 2, 3]
    prompts = [rng.randint(1, vocab, n).astype(np.int32)
               for n in (9, 14, 5)]

    def same(x, y):
        assert sorted(x) == sorted(y) == uids
        for u in uids:
            assert x.greedy(u) == y.greedy(u)
            np.testing.assert_array_equal(x[u], y[u])

    out_a = a.put(uids, prompts)
    step = b.put(uids, prompts, collect=False)
    assert step.pending and len(step.prefill) == 1 and step.decode is None
    assert sorted(d.uid for d, _ in step.prefill[0].rows) == uids
    out_b = b.collect(step)
    assert not step.pending and len(b.collect(step)) == 0   # once
    same(out_a, out_b)
    same(out_a, step)             # the step's own rows are filled in
    for eng, out in ((a, out_a), (b, out_b)):
        for u in uids:
            eng.state.seqs[u].generated.append(out.greedy(u))
    # `a` stages every token on the host and collects at once; `b` keeps
    # one step in flight and feeds its tokens on the device: its k-th
    # decode step, collected after the next is dispatched, is `a`'s k-th
    step = b.step(collect=False)
    assert (step.decode_rows, step.fed_rows) == (3, 0)
    held_back = None
    for k in range(6):
        # one row is left out of one dispatch (k == 2), as the serve loop
        # leaves out a request whose token in flight is its last; `a`
        # mirrors it by staging that row's token a step late
        hold = [2] if k == 2 else []
        ahead = b.step(ahead=step, hold=hold, collect=False)
        want_rows = [1, 3] if k == 2 else uids
        assert ahead.decode_rows == len(want_rows)
        assert ahead.fed_rows == (2 if k in (2, 3) else 3)
        out_a = a.step()
        out_b = b.collect(step, "decode")
        rows = [1, 3] if k == 3 else uids
        assert sorted(out_a) == sorted(out_b) == rows
        for u in rows:
            assert out_a.greedy(u) == out_b.greedy(u)
            np.testing.assert_array_equal(out_a[u], out_b[u])
        for u in rows:
            b.state.seqs[u].generated.append(out_b.greedy(u))
            if k == 2 and u == 2:
                held_back = out_a.greedy(u)
            else:
                a.state.seqs[u].generated.append(out_a.greedy(u))
        if k == 3:
            a.state.seqs[2].generated.append(held_back)
        step = ahead
    # a row of a flushed sequence is left out at collect, and one that
    # came back under its uid is another sequence
    b.flush(2)
    again = b.put([2], [prompts[1]], decode=False)
    assert sorted(again) == [2]
    # nothing is read before it is collected; `items()` alone collects
    # (the route of a caller that wraps `step` and reads every row)
    assert step.pending and step.awaited == 3 and len(step) == 0
    assert 1 not in step and step.greedy(1) is None
    assert sorted(u for u, _ in step.items()) == [1, 3]
    assert not step.pending and step.awaited == 0
    for eng in (a, b):
        for u in list(eng.state.seqs):
            eng.flush(u)
        eng.audit_blocks()


# -- the span attributes and the metric that reads them ----------------------
def test_serve_step_spans_carry_the_rows_fed_on_the_device(tiny, tmp_path):
    import json
    import os
    from benchmark import harness
    from benchmark.readers import span_attr_ratio
    from test_tracing import _program_spans, _traced
    loop = ServeLoop(_engine(tiny), ServingConfig(), clock=FakeClock())
    reqs = [loop.submit(p, max_new_tokens=n) for p, n in
            zip(_prompts(3, (9, 21, 5)), (6, 9, 12))]
    trace_dir = tmp_path / ".cache" / "bench_trace"
    with _traced(trace_dir):
        loop.run_until_idle(max_steps=100)
    assert all(r.state is RequestState.DONE for r in reqs)
    steps = [e[3] for line in _program_spans(str(trace_dir))
             for e in line if e[2] == "serve.step"]
    assert steps and all({"decode_rows", "fed_on_device_rows"} <= set(s)
                         for s in steps)
    rows = sum(int(s["decode_rows"]) for s in steps)
    fed = sum(int(s["fed_on_device_rows"]) for s in steps)
    # every decode row but each request's first (its input came from a
    # prefill, through the host); a request's last token needs no row
    assert rows == sum(len(r.output_tokens) - 1 for r in reqs)
    assert fed == rows - len(reqs)
    spec = harness.load_json(harness.BENCH_DIR, "metrics",
                             "decode_fed_on_device_share.closed.json")
    entry = [m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")
             ["per_layer"] if m["name"] == "decode_fed_on_device_share.closed"]
    assert len(entry) == 1 and entry[0]["moves"] == "out_tok_s"
    assert spec["reader"] == "span_attr_ratio"
    span_attr_ratio.attributes.cache_clear()
    value = span_attr_ratio.read(
        {"trace": True, "bench_dir": str(tmp_path / "benchmark")},
        **spec["params"])
    assert value == pytest.approx(100.0 * fed / rows)
    assert json.dumps(spec)       # plain data
    assert os.path.isfile(os.path.join(
        harness.BENCH_DIR, "metrics", "decode_fed_on_device_share.closed.json"))


def test_serve_step_spans_carry_the_live_share_of_the_block_table(
        tiny, tmp_path):
    """`kv_live_blocks` / `kv_table_blocks`: of the decode program's block
    table (max_seqs x max_blocks_per_seq a step), the entries that hold
    keys a row attends to (`pos // block_size + 1` a decode row)."""
    from benchmark import harness
    from benchmark.readers import span_attr_ratio
    from test_tracing import _program_spans, _traced
    eng = _engine(tiny)
    bs = eng.config.block_size
    width = eng.config.max_seqs * eng.config.max_blocks_per_seq
    loop = ServeLoop(eng, ServingConfig(), clock=FakeClock())
    lengths, new = (9, 21, 5), (6, 9, 12)
    reqs = [loop.submit(p, max_new_tokens=n)
            for p, n in zip(_prompts(3, lengths), new)]
    trace_dir = tmp_path / ".cache" / "bench_trace"
    with _traced(trace_dir):
        loop.run_until_idle(max_steps=100)
    assert all(r.state is RequestState.DONE for r in reqs)
    steps = [e[3] for line in _program_spans(str(trace_dir))
             for e in line if e[2] == "serve.step"]
    assert steps and all({"kv_live_blocks", "kv_table_blocks"} <= set(s)
                         for s in steps)
    for s in steps:
        rows, live = int(s["decode_rows"]), int(s["kv_live_blocks"])
        # a row holds at least one block and at most its table's width
        assert rows <= live <= int(s["kv_table_blocks"])
        assert int(s["kv_table_blocks"]) == (width if rows else 0)
    # a request of p prompt tokens decodes at positions p .. p + n - 2
    want = sum((p + k) // bs + 1 for p, r in zip(lengths, reqs)
               for k in range(len(r.output_tokens) - 1))
    live = sum(int(s["kv_live_blocks"]) for s in steps)
    table = sum(int(s["kv_table_blocks"]) for s in steps)
    assert live == want
    spec = harness.load_json(harness.BENCH_DIR, "metrics",
                             "paged_live_block_share.closed.json")
    entry = [m for m in harness.load_json(harness.ROOT, "BENCHMARK.json")
             ["per_layer"] if m["name"] == "paged_live_block_share.closed"]
    assert len(entry) == 1 and entry[0]["moves"] == "out_tok_s"
    assert entry[0]["workloads"] == ["qwen2-7b.decode_closed"]
    assert spec == {"reader": "span_attr_ratio", "params": {
        "span": "serve.step", "num": "kv_live_blocks",
        "den": "kv_table_blocks", "scale": 100.0}}
    span_attr_ratio.attributes.cache_clear()
    value = span_attr_ratio.read(
        {"trace": True, "bench_dir": str(tmp_path / "benchmark")},
        **spec["params"])
    assert value == pytest.approx(100.0 * live / table)
