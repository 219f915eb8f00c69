"""The closed-loop client on a fake loop and a fake clock."""
import types

import numpy as np
import pytest

from benchmark import draws, harness

K = harness.load_module(harness.BENCH_DIR, "traffic_kinds", "closed_loop")


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeLoop:
    """Every step takes `dt`; a request gets its first token in the step
    after it was submitted and one token a step from then on."""

    def __init__(self, clock, dt=0.01, fail_every=0):
        self.clock, self.dt, self.fail_every = clock, dt, fail_every
        self.active, self.count = [], 0

    def submit(self, prompt, max_new_tokens):
        self.count += 1
        req = types.SimpleNamespace(
            prompt=np.asarray(prompt), max_new_tokens=max_new_tokens,
            generated=[], admit_time=None, first_token_time=None,
            finish_time=None, state=types.SimpleNamespace(value="queued"),
            doomed=self.fail_every and self.count % self.fail_every == 0)
        self.active.append(req)
        return req

    def step(self):
        self.clock.t += self.dt
        now = self.clock.t
        for req in list(self.active):
            if req.admit_time is None:
                req.admit_time = now - self.dt
                req.first_token_time = now
            req.generated.append(len(req.generated))
            req.state.value = "decode"
            if req.doomed and len(req.generated) == 2:
                req.state.value, req.finish_time = "failed", now
                self.active.remove(req)
            elif len(req.generated) == req.max_new_tokens:
                req.state.value, req.finish_time = "done", now
                self.active.remove(req)


def client(clients=4, n_out=5, **kw):
    clock = Clock()
    loop = FakeLoop(clock, **kw)

    def requests():
        while True:
            yield np.arange(3, dtype=np.int32), n_out
    return K.ClosedLoopClient(loop, clock, requests(), clients), clock


def test_requests_are_timed_from_due_not_from_submit():
    c, clock = client(clients=1, n_out=3)
    t_open = c.run_until(clock() + 0.2)
    stats = K.window_stats(c, t_open - 0.2, t_open)
    # due = the previous finish; the first token comes one step later
    assert stats["samples"]["ttft_ms"][1:] == pytest.approx(
        [10.0] * (len(stats["samples"]["ttft_ms"]) - 1))
    d = c.done[1]
    assert d.due == c.done[0].finished
    # 3 tokens over 2 gaps of one step
    assert stats["samples"]["tpot_ms"] == pytest.approx(
        [10.0] * len(stats["samples"]["tpot_ms"]))


def test_only_tokens_and_requests_inside_the_window_count():
    c, clock = client(clients=4, n_out=5)
    t0 = c.run_until(clock() + 0.1)
    t1 = c.run_until(t0 + 0.5)
    c.run_until(t1 + 0.1)
    stats = K.window_stats(c, t0, t1)
    steps = round((t1 - t0) / 0.01)
    assert stats["counters"]["steps"] == steps
    assert stats["output_tokens"] == 4 * steps
    assert stats["out_tok_s"] == pytest.approx(400.0)
    assert stats["attempted"] == len(
        [d for d in c.done if t0 < d.finished <= t1])
    assert all(t0 < d.finished <= t1 for d in stats["finished_ok"])
    assert stats["counters"]["rows"] == 4 * steps


def test_first_requests_are_cut_so_phases_spread():
    clock = Clock()
    loop = FakeLoop(clock)

    def requests():
        while True:
            yield np.arange(3, dtype=np.int32), 100
    c = K.ClosedLoopClient(loop, clock, requests(), 4,
                           first_fraction=np.array([0.1, 0.35, 0.6, 0.85]))
    c.run_until(clock() + 0.005)
    firsts = [cur[0].max_new_tokens for cur in c.inflight]
    assert firsts == [10, 35, 60, 85]
    c.run_until(clock() + 2.5)
    assert {len(d.tokens) for d in c.done[4:]} == {100}


def test_a_failed_request_counts_as_failed_and_frees_its_client():
    c, clock = client(clients=2, n_out=4, fail_every=3)
    t0 = clock()
    t1 = c.run_until(t0 + 0.5)
    stats = K.window_stats(c, t0, t1)
    assert stats["failed"] > 0
    assert stats["failed"] == len([d for d in c.done if not d.ok])
    assert stats["attempted"] == len(c.done)
    assert all(d.ok for d in stats["finished_ok"])


def test_traced_runs_keep_host_samples_to_the_untraced_part():
    c, clock = client()
    t0 = clock()
    t1 = c.run_until(t0 + 0.4)
    whole = K.window_stats(c, t0, t1)
    part = K.window_stats(c, t0, t1, until=t0 + 0.2)
    assert len(part["samples"]["serve_step_ms"]) \
        < len(whole["samples"]["serve_step_ms"])
    assert part["output_tokens"] == whole["output_tokens"]


def test_the_check_sample_is_seeded_and_holds_the_longest():
    done = [K.Done(0, 0, 0, 1, True, np.zeros(p, np.int32),
                   np.zeros(t, np.int32))
            for p, t in [(5, 5), (50, 9), (7, 7), (6, 30), (8, 8), (9, 9)]]
    a = K.pick_for_check(done, seed=5, n=3)
    assert len(a) == 3 and len(a[0].prompt) == 50
    b = K.pick_for_check(done, seed=5, n=3)
    assert [id(x) for x in a] == [id(x) for x in b]
    assert K.pick_for_check([], 1, 3) == []


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**32 + 5])
def test_every_seed_sends_the_same_sizes_in_another_order(seed):
    pool = draws.size_pool([64, 256], [128, 384], 256)
    it = draws.sized_requests(seed, pool, vocab=1000)
    got = [next(it) for _ in range(256)]
    assert sorted((len(p), n) for p, n in got) == sorted(pool)
    assert min(len(p) for p, _ in got) >= 64
    assert max(len(p) for p, _ in got) <= 256
    assert all(0 <= p.min() and p.max() < 1000 for p, _ in got)
    again = draws.sized_requests(seed, pool, vocab=1000)
    assert all(np.array_equal(a[0], next(again)[0]) for a in got[:5])
    mean_out = np.mean([n for _, n in pool])
    assert mean_out == pytest.approx(256, abs=0.5)
