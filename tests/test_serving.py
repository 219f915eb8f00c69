"""Tests: the serving layer (deepspeed_tpu.serving) — request lifecycle,
bounded-queue admission control, cancellation, deadlines, fairness, and
telemetry.  Reference behaviors: DeepSpeed-MII's ragged batching serve
loop + the FastGen SLA methodology.

Everything here is deterministic on CPU: scheduler-core tests drive a
fake engine (same put/step/flush contract as InferenceEngineV2, next
token = (input + 1) % vocab) with a manually-advanced fake clock — no
real-time sleeps anywhere in the test path.  One integration test runs
the real tiny engine end-to-end through ServeLoop.
"""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from deepspeed_tpu.config.config import (ConfigError, DeepSpeedTPUConfig,
                                         PreemptionConfig, ServingConfig)
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.serving import (AdmissionError, QueueFullError, Request,
                                   RequestCancelled, RequestState,
                                   RequestTimedOut, ServeLoop,
                                   ThreadedServer)

pytestmark = pytest.mark.serving


# -- deterministic fake engine (ServeLoop's engine contract) --------------
class _FakeSeq:
    def __init__(self, uid, prompt):
        self.uid = uid
        self.prompt = np.asarray(prompt, np.int32)
        self.seen_tokens = 0
        self.generated = []
        self.blocks = []

    @property
    def in_prefill(self):
        return self.seen_tokens < len(self.prompt)


class FakeEngine:
    """Prefills `budget` tokens per step FIFO; decode emits one-hot
    logits at (input_token + 1) % vocab — generation is predictable:
    prompt[-1]+1, prompt[-1]+2, ... (mod vocab)."""

    def __init__(self, max_seqs=4, budget=8, vocab=32,
                 max_tokens_per_seq=64, num_blocks=1000, block_size=8):
        self.config = SimpleNamespace(max_seqs=max_seqs)
        self.budget = budget
        self.vocab = vocab
        self.max_tokens_per_seq = max_tokens_per_seq
        self.state = SimpleNamespace(
            seqs={}, block_size=block_size,
            allocator=SimpleNamespace(free_blocks=num_blocks))

    @property
    def free_blocks(self):
        return self.state.allocator.free_blocks

    @property
    def free_slots(self):
        return self.config.max_seqs - len(self.state.seqs)

    def _lease(self, d, upto):
        need = -(-upto // self.state.block_size) - len(d.blocks)
        if need > 0:
            if need > self.free_blocks:
                raise RuntimeError("fake allocator exhausted")
            self.state.allocator.free_blocks -= need
            d.blocks.extend([0] * need)

    def _logits(self, tok):
        out = np.zeros(self.vocab, np.float32)
        out[(tok + 1) % self.vocab] = 1.0
        return out

    def put(self, uids, prompts, decode=True):
        for uid, p in zip(uids, prompts):
            assert uid not in self.state.seqs
            assert len(self.state.seqs) < self.config.max_seqs
            self.state.seqs[uid] = _FakeSeq(uid, p)
        return self.step(decode=decode)

    def step(self, decode=True):
        out = {}
        budget = self.budget
        for d in self.state.seqs.values():          # FIFO prefill
            if d.in_prefill and budget > 0:
                adv = min(budget, len(d.prompt) - d.seen_tokens)
                self._lease(d, d.seen_tokens + adv)
                d.seen_tokens += adv
                budget -= adv
                if not d.in_prefill:
                    out[d.uid] = self._logits(int(d.prompt[-1]))
        for d in self.state.seqs.values() if decode else ():   # decode
            if d.in_prefill:
                continue
            pending = d.seen_tokens - len(d.prompt)
            if pending < len(d.generated):
                tok = d.generated[pending]
                self._lease(d, d.seen_tokens + 1)
                d.seen_tokens += 1
                out[d.uid] = self._logits(tok)
        return out

    def flush(self, uid):
        d = self.state.seqs.pop(uid)
        self.state.allocator.free_blocks += len(d.blocks)


class FakeBurstEngine(FakeEngine):
    """FakeEngine + the burst-mode engine contract (decode_burst_step /
    per-row sampling / per-uid lease caps), mirroring the semantics of
    InferenceEngineV2.decode_burst_step: full `n_steps` token vectors
    returned, engine-side state extended only up to the lease cap, last
    token left pending so bursts chain.  Logits are PEAKED one-hot
    (`peak`), so stochastic sampling is deterministic too — softmax of a
    1000-margin logit is a delta — and burst output can be compared
    bit-for-bit against the host-sampling reference path."""

    supports_per_row_sampling = True

    def __init__(self, *args, peak=1000.0, **kw):
        super().__init__(*args, **kw)
        self.peak = peak
        self._np_rng = np.random.RandomState(0)
        self.burst_calls = []        # (mode, uids, n_steps) audit trail

    def _logits(self, tok):
        out = np.zeros(self.vocab, np.float32)
        out[(tok + 1) % self.vocab] = self.peak
        return out

    def _draw(self, cur, temp, top_k):
        if temp <= 0.0:
            return (cur + 1) % self.vocab
        z = self._logits(cur).astype(np.float64) / temp
        if top_k and top_k > 0:
            kth = np.sort(z)[-top_k]
            z = np.where(z < kth, -np.inf, z)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._np_rng.choice(len(p), p=p))

    def decode_burst_step(self, uids=None, n_steps=8, mode="greedy",
                          temperature=1.0, top_k=0, rng=None,
                          max_tokens=None):
        batch = [d for d in self.state.seqs.values()
                 if not d.in_prefill and d.generated
                 and d.seen_tokens < len(d.prompt) + len(d.generated)]
        if uids is not None:
            sel = set(uids)
            batch = [d for d in batch if d.uid in sel]
        self.burst_calls.append((mode, [d.uid for d in batch], n_steps))
        out = {}
        for d in batch:
            pending = d.seen_tokens - len(d.prompt)
            assert pending == len(d.generated) - 1, "needs exactly 1 pending"
            cap = self.max_tokens_per_seq
            if max_tokens is not None and d.uid in max_tokens:
                cap = min(cap, int(max_tokens[d.uid]))
            capped = max(min(d.seen_tokens + n_steps, cap), d.seen_tokens)
            self._lease(d, capped)
            cur = d.generated[pending]
            toks = []
            for _ in range(n_steps):
                if mode == "greedy":
                    cur = (cur + 1) % self.vocab
                elif mode == "per_row":
                    cur = self._draw(cur, float(temperature.get(d.uid, 0.0)),
                                     int(top_k.get(d.uid, 0)))
                else:
                    cur = self._draw(cur, float(temperature), int(top_k))
                toks.append(cur)
            real = capped - d.seen_tokens
            d.generated.extend(toks[:real])
            d.seen_tokens = capped
            out[d.uid] = np.asarray(toks, np.int32)
        return out


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _loop(engine=None, clock=None, **cfg):
    return ServeLoop(engine or FakeEngine(), ServingConfig(**cfg),
                     clock=clock or FakeClock())


def _expected_tokens(prompt, n, vocab=32):
    return [(int(prompt[-1]) + 1 + i) % vocab for i in range(n)]


# -- lifecycle ------------------------------------------------------------
def test_request_lifecycle_transitions_enforced():
    req = Request(uid=0, prompt=np.arange(3, dtype=np.int32),
                  max_new_tokens=4, arrival_time=0.0)
    assert req.state is RequestState.QUEUED and not req.finished
    req.advance(RequestState.PREFILL, 1.0)
    req.advance(RequestState.DECODE, 2.0)
    req.mark_first_token(2.0)
    req.advance(RequestState.DONE, 5.0)
    assert req.finished and req.admit_time == 1.0
    assert req.ttft == 2.0 and req.e2e_latency == 5.0
    with pytest.raises(RuntimeError, match="illegal transition"):
        req.advance(RequestState.PREFILL, 6.0)
    # QUEUED cannot jump straight to DECODE either
    fresh = Request(uid=1, prompt=np.arange(3, dtype=np.int32),
                    max_new_tokens=4, arrival_time=0.0)
    with pytest.raises(RuntimeError, match="illegal transition"):
        fresh.advance(RequestState.DECODE, 1.0)


def test_serve_loop_completes_requests_end_to_end():
    eng = FakeEngine(max_seqs=4, budget=16)
    loop = _loop(eng)
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(9, 12, dtype=np.int32)]
    reqs = [loop.submit(p, max_new_tokens=4) for p in prompts]
    loop.run_until_idle(max_steps=50)
    for req, p in zip(reqs, prompts):
        assert req.state is RequestState.DONE
        assert list(req.output_tokens) == _expected_tokens(p, 4)
        assert req.ttft is not None and req.e2e_latency is not None
    assert eng.state.seqs == {}            # all flushed
    assert eng.free_blocks == 1000         # KV fully returned
    t = loop.telemetry
    assert t.counters["submitted"] == 2
    assert t.counters["completed"] == 2
    assert len(t.ttft) == 2 and len(t.e2e) == 2


def test_eos_stops_generation_early():
    eng = FakeEngine()
    loop = _loop(eng)
    # next tokens are 8, 9, 10, ...: eos 10 stops after 3 tokens
    req = loop.submit(np.asarray([3, 7], np.int32), max_new_tokens=16,
                      eos_token_id=10)
    loop.run_until_idle(max_steps=50)
    assert req.state is RequestState.DONE
    assert list(req.output_tokens) == [8, 9, 10]


# -- admission control ----------------------------------------------------
def test_admission_rejects_on_full_queue_with_clear_error():
    loop = _loop(max_queue_len=2)
    loop.submit(np.asarray([1], np.int32), max_new_tokens=4)
    loop.submit(np.asarray([2], np.int32), max_new_tokens=4)
    with pytest.raises(QueueFullError, match="full"):
        loop.submit(np.asarray([3], np.int32), max_new_tokens=4)
    assert loop.telemetry.counters["rejected_queue_full"] == 1
    assert loop.telemetry.counters["submitted"] == 2  # nothing silently kept


def test_admission_rejects_unservable_requests():
    loop = _loop(FakeEngine(max_tokens_per_seq=16))
    with pytest.raises(AdmissionError, match="empty prompt"):
        loop.submit(np.asarray([], np.int32))
    with pytest.raises(AdmissionError, match="exceeds"):
        loop.submit(np.arange(10, dtype=np.int32), max_new_tokens=10)
    with pytest.raises(AdmissionError, match="max_new_tokens"):
        loop.submit(np.asarray([1], np.int32), max_new_tokens=0)
    assert loop.telemetry.counters["rejected_invalid"] == 3


def test_admission_gates_on_kv_blocks_without_skipping_head():
    """The head of the queue must keep its place: when it does not fit
    in free KV blocks, later (smaller) requests wait behind it instead
    of jumping ahead — a stream of small requests cannot starve a big
    one."""
    eng = FakeEngine(max_seqs=4, num_blocks=3, block_size=8)
    loop = _loop(eng)
    big = loop.submit(np.arange(24, dtype=np.int32), max_new_tokens=8)
    small = loop.submit(np.asarray([1], np.int32), max_new_tokens=1)
    loop.step()
    # big needs 4 blocks > 3 free: neither admitted (no skip-ahead)
    assert big.state is RequestState.QUEUED
    assert small.state is RequestState.QUEUED
    assert loop.scheduler.queue_depth == 2


def test_admission_reserves_unleased_kv_across_steps():
    """The KV gate must account for blocks an earlier admittee has
    reserved but not LEASED yet (the engine leases lazily as sequences
    grow): request A (prompt 8 + 24 new = 4 blocks) holds only 1 block
    after prefill, but admitting B (2 blocks) into that apparent
    headroom would exhaust the allocator mid-decode."""
    eng = FakeEngine(max_seqs=2, budget=32, num_blocks=4, block_size=8)
    loop = _loop(eng)
    a = loop.submit(np.arange(8, dtype=np.int32), max_new_tokens=24)
    b = loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=8)
    loop.step()
    assert a.state is not RequestState.QUEUED
    # after A's prefill the allocator shows 3 free blocks, but they are
    # all promised to A's decode — B must keep waiting
    assert eng.free_blocks == 3
    assert b.state is RequestState.QUEUED
    loop.run_until_idle(max_steps=200)      # would crash the allocator
    assert a.state is RequestState.DONE     # without the reservation
    assert b.state is RequestState.DONE
    assert eng.free_blocks == 4


def test_priority_admits_before_fifo():
    clock = FakeClock()
    eng = FakeEngine(max_seqs=1, budget=32)
    loop = _loop(eng, clock=clock)
    filler = loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
    low = loop.submit(np.asarray([3], np.int32), max_new_tokens=1,
                      priority=5)
    high = loop.submit(np.asarray([4], np.int32), max_new_tokens=1,
                       priority=0)
    for _ in range(50):
        if not loop.has_work:
            break
        loop.step()
        clock.advance(1.0)          # distinct admit times per step
    assert all(r.state is RequestState.DONE for r in (filler, low, high))
    # with one slot, the higher-priority request admitted first
    assert high.admit_time < low.admit_time


# -- crash-window regressions (locked by the DST006/DST007 analyzer) -----
def test_admit_rollback_when_fits_raises_mid_scan():
    """Regression (DST006, crash-safe admission): a fits() callback that
    raises mid-scan must not strand already-moved requests in the active
    set — the caller never receives the admitted list, so its rollback
    cannot reach them and their result() waiters would hang.  admit()
    restores them to their FIFO place with states reverted, then
    re-raises; the retry admits cleanly."""
    from deepspeed_tpu.serving.scheduler import ContinuousBatchingScheduler
    sched = ContinuousBatchingScheduler()
    reqs = [Request(uid=i, prompt=np.asarray([1], np.int32),
                    max_new_tokens=2, arrival_time=float(i))
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    seen = []

    def fits(req):
        seen.append(req.uid)
        if len(seen) == 2:
            raise RuntimeError("allocator scan died")
        return True

    with pytest.raises(RuntimeError, match="allocator scan died"):
        sched.admit(1.0, 4, fits)
    assert sched.active == {}
    assert [r.uid for r in sched.queued_requests()] == [0, 1, 2]
    assert all(r.state is RequestState.QUEUED and r.admit_time is None
               for r in reqs)
    admitted = sched.admit(2.0, 4, lambda r: True)
    assert [r.uid for r in admitted] == [0, 1, 2]


def test_preempt_pass_failure_rolls_back_base_admissions():
    """Regression (DST006): the SLO-preemption pass runs OUTSIDE the
    crash-atomic admit->put try, so a raise inside it needs its own
    rollback — this step's base admissions must return to the queue
    (states reverted, engine never bound), and the retry serves them."""
    clock = FakeClock()
    eng = FakeEngine(max_seqs=1, budget=16)
    loop = _loop(eng, clock=clock,
                 preemption=PreemptionConfig(enabled=True))
    r0 = loop.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
    r1 = loop.submit(np.asarray([4, 5], np.int32), max_new_tokens=2)

    def boom(*a, **kw):
        raise RuntimeError("preempt scan died")

    loop._preempt_for_admission = boom
    with pytest.raises(RuntimeError, match="preempt scan died"):
        loop.step()
    assert loop.scheduler.active == {}
    assert r0.state is RequestState.QUEUED and r0.admit_time is None
    assert eng.state.seqs == {}          # the engine never heard of it
    del loop._preempt_for_admission      # restore the real pass
    loop.run_until_idle(max_steps=50)
    assert r0.state is RequestState.DONE and r1.state is RequestState.DONE
    assert list(r0.output_tokens) == _expected_tokens([1, 2, 3], 2)
    assert eng.state.seqs == {} and eng.free_blocks == 1000


def test_finish_records_before_flush_crash_safe_backlog():
    """Regression (DST007, crash-safe backlog): a terminal request is
    RECORDED (telemetry + backlog) before the engine flush, so a flush
    that raises propagates loudly but cannot hide the finished request
    from its waiter — it survives in the backlog for the next report."""
    clock = FakeClock()
    eng = FakeEngine(max_seqs=2, budget=16)
    loop = _loop(eng, clock=clock)
    req = loop.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
    real_flush, dead = eng.flush, [True]

    def flush(uid):
        if dead[0]:
            raise RuntimeError("flush died")
        real_flush(uid)

    eng.flush = flush
    with pytest.raises(RuntimeError, match="flush died"):
        for _ in range(50):
            loop.step()
            clock.advance(1.0)
    assert req.state is RequestState.DONE
    assert loop.telemetry.counters["completed"] == 1
    assert loop.has_work                 # the backlog holds it
    dead[0] = False
    eng.flush(req.uid)                   # operator retry of the flush
    assert loop.take_finished_backlog() == [req]
    assert not loop.has_work
    assert eng.free_blocks == 1000


# -- cancellation ---------------------------------------------------------
def test_cancellation_mid_decode_flushes_engine():
    eng = FakeEngine(budget=32)
    loop = _loop(eng)
    req = loop.submit(np.asarray([5, 6, 7], np.int32), max_new_tokens=50)
    loop.step()                      # prefill + first token
    loop.step()                      # decoding now
    assert req.state is RequestState.DECODE
    produced = len(req.generated)
    assert produced >= 1
    assert loop.cancel(req.uid)
    finished = loop.step()
    assert req in finished and req.state is RequestState.CANCELLED
    assert req.uid not in eng.state.seqs       # engine sequence flushed
    assert eng.free_blocks == 1000             # KV blocks returned
    with pytest.raises(RequestCancelled):
        req.result(timeout=0)
    assert loop.telemetry.counters["cancelled"] == 1
    assert not loop.has_work
    # cancelling again (or an unknown uid) reports False, no crash
    assert not loop.cancel(req.uid)
    assert not loop.cancel(12345)


def test_cancel_queued_request_never_touches_engine():
    eng = FakeEngine(max_seqs=1, budget=32)
    loop = _loop(eng)
    running = loop.submit(np.asarray([1], np.int32), max_new_tokens=8)
    queued = loop.submit(np.asarray([2], np.int32), max_new_tokens=8)
    loop.step()
    assert queued.state is RequestState.QUEUED
    assert loop.cancel(queued.uid)
    loop.step()
    assert queued.state is RequestState.CANCELLED
    assert queued.admit_time is None           # never reached the engine
    loop.run_until_idle(max_steps=50)
    assert running.state is RequestState.DONE


# -- deadlines ------------------------------------------------------------
def test_deadline_timeout_mid_decode():
    clock = FakeClock()
    eng = FakeEngine(budget=32, max_tokens_per_seq=256)
    loop = _loop(eng, clock=clock)
    req = loop.submit(np.asarray([4, 5], np.int32), max_new_tokens=100,
                      timeout_s=5.0)
    loop.step()
    clock.advance(1.0)
    loop.step()
    assert req.state is RequestState.DECODE
    clock.advance(10.0)                        # past the deadline
    finished = loop.step()
    assert req in finished and req.state is RequestState.TIMED_OUT
    assert req.uid not in eng.state.seqs
    with pytest.raises(RequestTimedOut):
        req.result(timeout=0)
    assert loop.telemetry.counters["timed_out"] == 1


def test_deadline_timeout_in_queue_and_default_timeout():
    clock = FakeClock()
    eng = FakeEngine(max_seqs=1, budget=32)
    loop = ServeLoop(eng, ServingConfig(default_timeout_s=3.0,
                                        default_max_new_tokens=8),
                     clock=clock)
    running = loop.submit(np.asarray([1], np.int32), max_new_tokens=50)
    queued = loop.submit(np.asarray([2], np.int32))   # default deadline
    assert queued.deadline == 3.0
    loop.step()
    clock.advance(4.0)
    loop.step()
    assert queued.state is RequestState.TIMED_OUT     # expired in queue
    assert running.state is RequestState.TIMED_OUT    # expired mid-flight


# -- fairness -------------------------------------------------------------
def test_mixed_prefill_decode_fairness_no_starvation():
    """Long-prompt and short-prompt requests over an engine with a small
    per-step prefill budget and fewer slots than requests: every request
    completes within a bounded number of steps, none starved, none
    silently dropped."""
    eng = FakeEngine(max_seqs=2, budget=4, max_tokens_per_seq=64)
    loop = _loop(eng)
    prompts = ([np.arange(12, dtype=np.int32) % 32 for _ in range(2)]
               + [np.asarray([3, 4], np.int32) for _ in range(4)])
    reqs = [loop.submit(p, max_new_tokens=3) for p in prompts]
    loop.run_until_idle(max_steps=120)        # raises if anything starves
    assert all(r.state is RequestState.DONE for r in reqs)
    assert loop.telemetry.counters["completed"] == len(reqs)
    assert loop.telemetry.counters["timed_out"] == 0
    for r, p in zip(reqs, prompts):
        assert list(r.output_tokens) == _expected_tokens(p, 3)


# -- telemetry ------------------------------------------------------------
def test_per_step_budget_accounting_measured_not_inferred():
    clock = FakeClock()
    eng = FakeEngine(max_seqs=4, budget=4)
    loop = _loop(eng, clock=clock)
    loop.submit(np.arange(6, dtype=np.int32), max_new_tokens=3)
    loop.step()                                # 4 of 6 prompt tokens
    assert loop.telemetry.prefill_tokens_step == 4
    assert loop.telemetry.decode_tokens_step == 0
    loop.step()                                # finishes prefill
    assert loop.telemetry.prefill_tokens_step == 2
    loop.step()                                # pure decode
    assert loop.telemetry.prefill_tokens_step == 0
    assert loop.telemetry.decode_tokens_step == 1
    assert loop.telemetry.batch_occupancy == 0.25


def test_telemetry_fans_out_through_monitor_sinks():
    sink = InMemoryMonitor()
    eng = FakeEngine()
    loop = ServeLoop(eng, ServingConfig(monitor_interval_steps=1),
                     clock=FakeClock(), monitor=sink)
    req = loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=2)
    loop.run_until_idle(max_steps=20)
    assert req.state is RequestState.DONE
    tags = {tag for tag, _, _ in sink.events}
    for expected in ("serving/queue_depth", "serving/batch_occupancy",
                     "serving/completed", "serving/ttft_p50_s",
                     "serving/prefill_tokens_step"):
        assert expected in tags, expected
    # summary aggregates with goodput
    s = loop.telemetry.summary(elapsed_s=2.0)
    assert s["completed"] == 1 and s["goodput_tok_s"] == 1.0
    assert s["ttft_p50_s"] is not None and s["e2e_p95_s"] is not None


# -- config ---------------------------------------------------------------
def test_serving_config_validation_and_json_wiring():
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"enabled": True, "max_queue_len": 7,
                     "default_max_new_tokens": 9,
                     "default_timeout_s": 1.5}})
    assert cfg.serving.enabled and cfg.serving.max_queue_len == 7
    assert cfg.serving.default_max_new_tokens == 9
    assert cfg.serving.default_timeout_s == 1.5
    for bad in ({"max_queue_len": 0}, {"default_max_new_tokens": 0},
                {"default_timeout_s": -1.0}, {"monitor_interval_steps": -2}):
        with pytest.raises(ConfigError):
            ServingConfig.from_dict(bad)


# -- threaded frontend ----------------------------------------------------
class _GatedEngine(FakeEngine):
    """From step `hold_after` on, every step first waits on the server's
    own condition (which releases its lock) until the test lets go.  A
    free-running fake engine emits 200 tokens in microseconds and the
    serving thread re-takes the lock between steps, so under load the long
    request could FINISH before the test thread got to cancel it — the
    test then failed on scheduling, not on the server."""
    hold_after = 8
    cond = None
    released = False
    steps = 0

    def step(self, decode=True):
        self.steps += 1
        if self.steps > self.hold_after:
            self.cond.wait_for(lambda: self.released, timeout=10.0)
        return super().step(decode=decode)


def test_threaded_server_submit_result_cancel():
    eng = _GatedEngine(max_seqs=4, budget=32, max_tokens_per_seq=512)
    server = ThreadedServer(eng)
    eng.cond = server._cond
    try:
        p1 = np.asarray([2, 3], np.int32)
        r1 = server.submit(p1, max_new_tokens=3)
        r2 = server.submit(np.asarray([9], np.int32), max_new_tokens=200)
        assert list(r1.result(timeout=10.0)) == _expected_tokens(p1, 3)
        assert server.cancel(r2.uid)          # r2 is held mid-generation
        with server._cond:
            eng.released = True
            server._cond.notify_all()
        with pytest.raises(RequestCancelled):
            r2.result(timeout=10.0)
        assert server.telemetry.counters["completed"] == 1
        assert server.telemetry.counters["cancelled"] == 1
    finally:
        eng.released = True
        server.shutdown(drain=True, timeout=10.0)
    with pytest.raises(RuntimeError, match="shut down"):
        server.submit(np.asarray([1], np.int32))


def test_threaded_server_concurrent_submitters():
    eng = FakeEngine(max_seqs=4, budget=64, vocab=32)
    server = ThreadedServer(eng)
    results = {}

    def client(i):
        p = np.asarray([i, i + 1], np.int32)
        req = server.submit(p, max_new_tokens=2)
        results[i] = (p, req.result(timeout=10.0))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(6)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert len(results) == 6
        for i, (p, toks) in results.items():
            assert list(toks) == _expected_tokens(p, 2)
    finally:
        server.shutdown(drain=True, timeout=10.0)


# -- closed loop -----------------------------------------------------------
def _closed_loop(target, prompts, new_tokens, after_step=None,
                 max_steps=2000):
    """Serve `prompts` ({(client, k): tokens}) through a `ServeLoop` or a
    `FleetRouter` as a closed loop: every client's request 0 at once, its
    request k+1 the moment its request k finishes.  Every request must
    end DONE.  -> ({(client, k): output tokens}, the requests in
    submission order)."""
    owner, outputs, reqs = {}, {}, []

    def submit(key):
        req = target.submit(prompts[key], max_new_tokens=new_tokens)
        owner[id(req)] = key
        reqs.append(req)

    for client in sorted({c for c, _ in prompts}):
        submit((client, 0))
    steps = 0
    while len(outputs) < len(prompts):
        steps += 1
        assert steps < max_steps, "closed loop wedged"
        for req in target.step():
            key = owner.pop(id(req), None)
            if key is None:                  # not of this stream
                continue
            assert req.state is RequestState.DONE, (key, req.state)
            outputs[key] = list(req.output_tokens)
            if (key[0], key[1] + 1) in prompts:
                submit((key[0], key[1] + 1))
        if after_step is not None:
            after_step()
    return outputs, reqs


def test_bench_closed_loop_driver_runs_on_tiny_engine():
    """A closed loop over `ServeLoop` on the tiny CPU engine: two clients,
    each submitting its next request the moment its previous one
    completes (one short-prompt client, one long, so prefill and decode
    interleave in the ragged batch) — per step, then with
    `decode_burst=2`.  Every request finishes DONE with all its tokens,
    none is lost, timed out or cancelled, and the telemetry's TTFT / e2e
    percentiles are ordered."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=1024,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ecfg = RaggedInferenceEngineConfig(
        num_blocks=128, block_size=16, max_blocks_per_seq=40, max_seqs=2,
        prefill_chunk_size=128)
    rng = np.random.RandomState(5)
    prompts = {(client, k): rng.randint(0, 128, 512 if client else 128)
               .astype(np.int32) for client in range(2) for k in range(2)}

    for decode_burst in (1, 2):
        eng = InferenceEngineV2(model, params=params, config=ecfg)
        loop = ServeLoop(eng, ServingConfig(max_queue_len=len(prompts) + 1,
                                            decode_burst=decode_burst))
        outputs, _ = _closed_loop(loop, prompts, new_tokens=3)
        assert all(len(toks) == 3 for toks in outputs.values())
        assert not loop.has_work and eng.state.seqs == {}
        s = loop.telemetry.summary()
        assert s["completed"] == s["admitted"] == len(prompts)
        assert s["timed_out"] == 0 and s["cancelled"] == 0
        assert s["ttft_p95_s"] >= s["ttft_p50_s"] >= 0
        assert s["e2e_p95_s"] >= s["e2e_p50_s"] > 0
        # a burst's tokens come in one host observation: the burst
        # percentiles exist exactly on the burst loop
        assert (s["tpot_burst_p50_s"] is not None) == (decode_burst > 1)


# -- real-engine integration ---------------------------------------------
def test_serve_loop_real_engine_matches_generate():
    """ServeLoop over the real InferenceEngineV2 (tiny model, CPU):
    greedy serving produces exactly what the engine's own generate()
    produces, and the engine is left clean."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ecfg = RaggedInferenceEngineConfig(
        num_blocks=32, block_size=8, max_blocks_per_seq=8, max_seqs=4,
        prefill_chunk_size=16)

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 21)]

    ref = InferenceEngineV2(model, params=params, config=ecfg)
    want = [ref.generate(p, max_new_tokens=5, uid=50 + i)
            for i, p in enumerate(prompts)]

    eng = InferenceEngineV2(model, params=params, config=ecfg)
    # audit_blocks: the block-conservation assertion hook runs after
    # every serve step that finishes a request (leak detection wired
    # into the serving tests; see test_prefix_cache.py for the cache-on
    # variants)
    loop = ServeLoop(eng, ServingConfig(max_queue_len=8,
                                        audit_blocks=True),
                     clock=FakeClock())
    reqs = [loop.submit(p, max_new_tokens=5) for p in prompts]
    loop.run_until_idle(max_steps=100)
    for req, w in zip(reqs, want):
        assert req.state is RequestState.DONE
        np.testing.assert_array_equal(req.output_tokens, w)
    assert eng.state.seqs == {} and eng.free_blocks == 32
    assert eng.audit_blocks() == {"free": 32, "live": 0, "shared": 0,
                                  "cached": 0, "total": 32}


# -- burst serving (PR 2): fused on-device decode under the lifecycle ----
def test_burst_matches_host_sampling_reference_greedy_and_stochastic():
    """Output parity, burst vs. per-step host sampling: with peaked fake
    logits both samplers are deterministic, so greedy AND stochastic
    requests must produce identical tokens through decode_burst=4 and
    through the decode_burst=1 reference path."""
    kwargs = [
        (np.asarray([3, 7], np.int32), dict(max_new_tokens=6)),
        (np.asarray([5], np.int32), dict(max_new_tokens=5,
                                         temperature=0.7, top_k=3)),
        (np.asarray([11, 2, 4], np.int32), dict(max_new_tokens=4,
                                                temperature=1.1)),
    ]

    def run(decode_burst):
        loop = ServeLoop(FakeBurstEngine(),
                         ServingConfig(decode_burst=decode_burst),
                         clock=FakeClock())
        reqs = [loop.submit(p, **kw) for p, kw in kwargs]
        loop.run_until_idle(max_steps=100)
        return loop, reqs

    loop_b, reqs_b = run(4)
    loop_r, reqs_r = run(1)
    for rb, rr, (p, kw) in zip(reqs_b, reqs_r, kwargs):
        assert rb.state is RequestState.DONE
        assert list(rb.output_tokens) == list(rr.output_tokens)
        assert list(rb.output_tokens) == _expected_tokens(
            p, kw["max_new_tokens"])
    # the burst loop really burst — ONE per_row call served all three
    # sampling signatures while they were live (pure-greedy steps after
    # the stochastic requests finished use the cheaper greedy program);
    # the reference loop never burst at all
    modes = {m for m, _, _ in loop_b.engine.burst_calls}
    assert "per_row" in modes and "sample" not in modes
    assert ("per_row", [r.uid for r in reqs_b], 4) in \
        loop_b.engine.burst_calls
    assert loop_r.engine.burst_calls == []
    assert loop_b.telemetry.counters["completed"] == 3


def test_burst_one_reproduces_per_step_path_bit_for_bit():
    """decode_burst=1 must BE today's per-step path: identical tokens,
    identical measured lifecycle stamps (ttft/tpot/e2e on the fake
    clock), burst machinery never engaged."""
    def run(engine):
        clock = FakeClock()
        loop = ServeLoop(engine, ServingConfig(decode_burst=1), clock=clock)
        reqs = [loop.submit(np.arange(1, 13, dtype=np.int32),
                            max_new_tokens=4),
                loop.submit(np.asarray([9], np.int32), max_new_tokens=3)]
        while loop.has_work:
            loop.step()
            clock.advance(1.0)
        return reqs

    got = run(FakeBurstEngine())      # burst-capable engine, burst off
    want = run(FakeEngine())          # today's engine contract
    for g, w in zip(got, want):
        assert list(g.output_tokens) == list(w.output_tokens)
        assert (g.ttft, g.tpot, g.e2e_latency) == (w.ttft, w.tpot,
                                                   w.e2e_latency)
        assert g.finish_time == w.finish_time


def test_eos_mid_burst_truncates_flushes_and_refunds_ledger():
    """EOS lands mid-burst: the request keeps tokens through EOS only,
    the over-generated engine tokens/KV die with the flush, and the
    reservation ledger returns the WHOLE reservation — no admission
    capacity leaks from truncation."""
    eng = FakeBurstEngine()
    loop = ServeLoop(eng, ServingConfig(decode_burst=8), clock=FakeClock())
    # tokens run 8, 9, 10, ...: eos 10 stops after 3 of 16 mid-burst
    req = loop.submit(np.asarray([3, 7], np.int32), max_new_tokens=16,
                      eos_token_id=10)
    loop.run_until_idle(max_steps=20)
    assert req.state is RequestState.DONE
    assert list(req.output_tokens) == [8, 9, 10]
    # the engine DID overshoot (full-size burst) before truncation
    assert ("greedy", [req.uid], 8) in eng.burst_calls
    assert eng.state.seqs == {}                 # flushed
    assert eng.free_blocks == 1000              # over-generated KV returned
    assert loop._reserved == {}                 # ledger debited
    assert loop.telemetry.counters["completed"] == 1


def test_cancellation_lands_at_burst_boundary():
    eng = FakeBurstEngine(max_tokens_per_seq=256)
    loop = ServeLoop(eng, ServingConfig(decode_burst=4), clock=FakeClock())
    req = loop.submit(np.asarray([5, 6, 7], np.int32), max_new_tokens=100)
    loop.step()                  # prefill + first token + one burst
    assert req.state is RequestState.DECODE
    assert len(req.generated) == 1 + 4
    assert loop.cancel(req.uid)
    finished = loop.step()       # takes effect at the burst boundary
    assert req in finished and req.state is RequestState.CANCELLED
    assert len(req.generated) == 5              # no extra burst ran
    assert req.uid not in eng.state.seqs
    assert eng.free_blocks == 1000
    assert loop._reserved == {}
    with pytest.raises(RequestCancelled):
        req.result(timeout=0)


def test_deadline_expiry_mid_burst_times_out_at_boundary():
    """The deadline passes DURING a burst (fake clock advanced across the
    step): the request times out at the next burst boundary with the
    already-delivered tokens retained on the request."""
    clock = FakeClock()
    eng = FakeBurstEngine(max_tokens_per_seq=256)
    loop = ServeLoop(eng, ServingConfig(decode_burst=4), clock=clock)
    req = loop.submit(np.asarray([4, 5], np.int32), max_new_tokens=100,
                      timeout_s=5.0)
    loop.step()
    produced = len(req.generated)
    assert produced == 5 and req.state is RequestState.DECODE
    clock.advance(10.0)                         # burst outlived the deadline
    finished = loop.step()
    assert req in finished and req.state is RequestState.TIMED_OUT
    assert len(req.generated) == produced       # boundary, not mid-burst
    assert req.uid not in eng.state.seqs
    assert loop.telemetry.counters["timed_out"] == 1
    with pytest.raises(RequestTimedOut):
        req.result(timeout=0)


def test_burst_lease_capped_at_admission_reservation():
    """A full-size tail burst must not lease KV past the request's
    admission reservation: block_size 4, reservation ceil(28/4) = 7 =
    every block in the arena — an uncapped overshoot to 32 tokens would
    demand an 8th block and crash the allocator mid-decode."""
    eng = FakeBurstEngine(max_seqs=2, budget=32, num_blocks=7, block_size=4)
    loop = ServeLoop(eng, ServingConfig(decode_burst=8), clock=FakeClock())
    req = loop.submit(np.arange(8, dtype=np.int32), max_new_tokens=20)
    loop.run_until_idle(max_steps=20)
    assert req.state is RequestState.DONE
    assert len(req.generated) == 20
    assert eng.free_blocks == 7
    assert loop._reserved == {}


def test_per_group_fallback_without_per_row_support():
    """Engines without per-row sampling vectors fall back to one burst
    per sampling-signature group (greedy pool + each distinct
    (temperature, top_k)) — same outputs, more dispatches."""
    eng = FakeBurstEngine(max_seqs=4, budget=16)
    eng.supports_per_row_sampling = False
    loop = ServeLoop(eng, ServingConfig(decode_burst=4), clock=FakeClock())
    kwargs = [
        (np.asarray([3, 7], np.int32), dict(max_new_tokens=6)),
        (np.asarray([5], np.int32), dict(max_new_tokens=6,
                                         temperature=0.7, top_k=3)),
        (np.asarray([9, 1], np.int32), dict(max_new_tokens=6,
                                            temperature=1.3)),
    ]
    reqs = [loop.submit(p, **kw) for p, kw in kwargs]
    loop.run_until_idle(max_steps=100)
    for req, (p, kw) in zip(reqs, kwargs):
        assert req.state is RequestState.DONE
        assert list(req.output_tokens) == _expected_tokens(p, 6)
    modes = {m for m, _, _ in eng.burst_calls}
    assert modes == {"greedy", "sample"}       # never per_row
    # the three signatures were served as separate group bursts: one
    # greedy group plus one per distinct (temperature, top_k)
    sample_groups = {(tuple(uids))
                     for m, uids, _ in eng.burst_calls if m == "sample"}
    assert len(sample_groups) == 2


def test_burst_needs_capable_engine_and_config_validation():
    with pytest.raises(ValueError, match="decode_burst"):
        ServeLoop(FakeEngine(), ServingConfig(decode_burst=4))
    with pytest.raises(ConfigError, match="decode_burst"):
        ServingConfig(decode_burst=0).validate()
    cfg = DeepSpeedTPUConfig.from_json(
        {"serving": {"decode_burst": 8}})
    assert cfg.serving.decode_burst == 8


def test_burst_telemetry_token_weighted_percentiles():
    """One host observation covers N tokens: percentiles must weight by
    the tokens covered — a lone slow 1-token tail burst is 1/11 of the
    tokens, not 1/2 of the samples."""
    from deepspeed_tpu.serving.telemetry import ServingTelemetry
    t = ServingTelemetry()
    t.record_burst(1.0, 10)        # 0.1 s/token over 10 tokens
    t.record_burst(2.0, 1)         # 2.0 s/token over 1 token
    t.record_burst(0.0, 0)         # empty observation is dropped
    assert len(t.burst_obs) == 2
    s = t.summary()
    assert s["tpot_burst_p50_s"] == pytest.approx(0.1)
    assert s["tpot_burst_p95_s"] == pytest.approx(2.0)
    assert s["burst_tokens_mean"] == pytest.approx(5.5)
    # loop-level: burst serving actually records observations
    eng = FakeBurstEngine()
    loop = ServeLoop(eng, ServingConfig(decode_burst=4), clock=FakeClock())
    loop.submit(np.asarray([1, 2], np.int32), max_new_tokens=9)
    loop.run_until_idle(max_steps=20)
    assert len(loop.telemetry.burst_obs) == 2           # 9 = 1 + 4 + 4
    assert [n for _, n in loop.telemetry.burst_obs] == [4, 4]
    assert loop.telemetry.summary()["tpot_burst_p50_s"] is not None


def test_burst_real_engine_matches_generate_and_keeps_logits_on_device():
    """Burst ServeLoop over the real InferenceEngineV2 (tiny, CPU):
    greedy serving equals the engine's own burst generate(); full-vocab
    logits reach the host ONLY at prefill completion (the batched
    first-token sample) — never for a decoding sequence (asserted via
    the engine's _last_logits bookkeeping and a put/step spy)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ecfg = RaggedInferenceEngineConfig(
        num_blocks=32, block_size=8, max_blocks_per_seq=8, max_seqs=4,
        prefill_chunk_size=16)

    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 21)]
    ref = InferenceEngineV2(model, params=params, config=ecfg)
    want = [ref.generate(p, max_new_tokens=6, uid=70 + i)
            for i, p in enumerate(prompts)]

    eng = InferenceEngineV2(model, params=params, config=ecfg)
    logit_audit = []
    orig_put, orig_step = eng.put, eng.step

    def spy_put(uids, toks, decode=True):
        pre = {u for u, d in eng.state.seqs.items() if d.in_prefill}
        out = orig_put(uids, toks, decode=decode)
        logit_audit.append((set(out), pre | set(uids), decode))
        return out

    def spy_step(decode=True):
        pre = {u for u, d in eng.state.seqs.items() if d.in_prefill}
        out = orig_step(decode=decode)
        logit_audit.append((set(out), pre, decode))
        return out

    eng.put, eng.step = spy_put, spy_step
    loop = ServeLoop(eng, ServingConfig(decode_burst=3, max_queue_len=8,
                                        audit_blocks=True),
                     clock=FakeClock())
    reqs = [loop.submit(p, max_new_tokens=6) for p in prompts]
    steps = 0
    while loop.has_work:
        loop.step()
        steps += 1
        assert steps < 100
        # burst invariant: a decoding sequence never holds host logits
        for uid, r in loop.scheduler.active.items():
            if r.state is RequestState.DECODE:
                assert eng.query(uid) is None
    for req, w in zip(reqs, want):
        assert req.state is RequestState.DONE
        np.testing.assert_array_equal(req.output_tokens, w)
    for got_uids, prefill_uids, decode in logit_audit:
        assert decode is False                  # burst mode: prefill only
        assert got_uids <= prefill_uids         # logits = prefill finishers
    assert eng._last_logits == {} and eng.state.seqs == {}
    assert eng.free_blocks == 32
    assert loop.telemetry.burst_obs             # bursts actually ran
    s = loop.telemetry.summary(elapsed_s=1.0)
    assert s["tpot_burst_p50_s"] is not None


def test_threaded_server_serves_burst_mode():
    eng = FakeBurstEngine(max_seqs=4, budget=32, max_tokens_per_seq=512)
    server = ThreadedServer(eng, ServingConfig(decode_burst=4))
    try:
        p = np.asarray([2, 3], np.int32)
        r1 = server.submit(p, max_new_tokens=7)
        r2 = server.submit(np.asarray([8], np.int32), max_new_tokens=5,
                           temperature=0.6, top_k=2)
        assert list(r1.result(timeout=10.0)) == _expected_tokens(p, 7)
        assert list(r2.result(timeout=10.0)) == _expected_tokens(
            np.asarray([8]), 5)
        assert server.telemetry.counters["completed"] == 2
    finally:
        server.shutdown(drain=True, timeout=10.0)


def test_serve_loop_transfer_guard_disallow_real_engine():
    """`ServingConfig.transfer_guard="disallow"` (the dynamic DST001
    sanitizer, analysis/transfer_guard.py): a real-engine burst serve
    runs every step under jax's device->host transfer guard and still
    produces exactly the unguarded outputs — possible only because every
    intended fetch in the hot path is an explicit jax.device_get.  Also
    checks the JSON wiring and the validation error."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.config.config import ConfigError
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ecfg = RaggedInferenceEngineConfig(
        num_blocks=32, block_size=8, max_blocks_per_seq=8, max_seqs=4,
        prefill_chunk_size=16)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (7, 15)]

    outs = {}
    for guard in ("off", "disallow"):
        eng = InferenceEngineV2(model, params=params, config=ecfg)
        loop = ServeLoop(eng, ServingConfig(decode_burst=4,
                                            transfer_guard=guard),
                         clock=FakeClock())
        reqs = [loop.submit(p, max_new_tokens=6) for p in prompts]
        loop.run_until_idle(max_steps=200)
        assert all(r.state is RequestState.DONE for r in reqs)
        outs[guard] = [r.output_tokens for r in reqs]
    for a, b in zip(outs["off"], outs["disallow"]):
        np.testing.assert_array_equal(a, b)

    # JSON wiring + validation
    assert ServingConfig.from_dict(
        {"transfer_guard": "log"}).transfer_guard == "log"
    with pytest.raises(ConfigError, match="transfer_guard"):
        ServingConfig(transfer_guard="everything").validate()


# ----------------------------------------------------------------------
# admission over per-sequence recurrent state (a state-space mixer's slots
# beside the K/V blocks): tests/test_ssm_serving.py has the rest
# ----------------------------------------------------------------------
@pytest.mark.parametrize("short, engine_kw, prompts, first_free_slots", [
    ("slots", dict(max_seqs=2), (10, 10, 10), 0),
    ("blocks", dict(num_blocks=5), (20, 20), 3),
])
def test_admission_waits_for_what_is_short_slots_or_blocks(
        short, engine_kw, prompts, first_free_slots):
    """Two decode rows, so two state slots, and ample blocks: the third
    request waits for a slot though blocks are free.  Four slots and blocks
    for one request's lifetime: the second waits for blocks though slots
    are free.  Either way it is served once the first has finished, and
    nothing leaks."""
    import jax.numpy as jnp
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine)
    eng = build_engine(
        "falcon_h1", "tiny", dtype=jnp.float32,
        engine_config=RaggedInferenceEngineConfig(**dict(dict(
            num_blocks=48, block_size=8, max_blocks_per_seq=12, max_seqs=4,
            prefill_chunk_size=32, max_prefill_tokens_per_step=32),
            **engine_kw)))
    loop = ServeLoop(eng, ServingConfig(audit_blocks=True))
    reqs = [loop.submit(np.arange(n, dtype=np.int32) + i, max_new_tokens=8)
            for i, n in enumerate(prompts)]
    loop.step()
    assert [r.state is RequestState.QUEUED for r in reqs] \
        == [False] * (len(reqs) - 1) + [True]
    assert eng.free_slots == first_free_slots
    assert (eng.free_blocks > 40) == (short == "slots")
    loop.run_until_idle()
    assert all(r.state is RequestState.DONE for r in reqs)
    audit = eng.audit_blocks()
    assert audit["state_slots_free"] == audit["state_slots_total"]
    assert audit["free"] == audit["total"]
