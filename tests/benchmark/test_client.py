"""The closed-loop client on a fake loop and a fake clock."""
import hashlib
import itertools
import json
import os
import types

import numpy as np
import pytest

from benchmark import draws, harness

from bench_testlib import DATA

K = harness.load_module(harness.BENCH_DIR, "traffic_kinds", "closed_loop")


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeLoop:
    """Every step takes `dt`; a request gets its first token in the step
    after it was submitted and one token a step from then on."""

    def __init__(self, clock, dt=0.01, fail_every=0, prompts_a_step=None):
        self.clock, self.dt, self.fail_every = clock, dt, fail_every
        self.active, self.count = [], 0
        # None: every waiting prompt is prefilled in the next step; a
        # number: that many a step, first come first served (long prompts)
        self.prompts_a_step = prompts_a_step

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
        room = self.prompts_a_step
        for req in list(self.active):
            if req.first_token_time is None:
                if room == 0:
                    continue
                room = None if room is None else room - 1
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


# -- what a WINDOW sees of the pool (PR 40) ---------------------------------

LONG = harness.load_json(harness.BENCH_DIR, "traffic",
                         "decode_closed_long.json")
GOLDEN = harness.load_json(DATA, "golden", "request_streams.json")


def _pool(traffic):
    return draws.size_pool(traffic["prompt_len"], traffic["output_len"],
                           traffic["size_pool"])


def _worst_stray(order, seeds=200, horizon=1024, run=32):
    """Over `seeds` seeds and every place in the first `horizon` requests:
    how far the mean prompt and output length of `run` consecutive requests
    stray from the pool's, as a share of the pool's."""
    pool = _pool(LONG)
    want = np.mean(pool, axis=0)
    worst = np.zeros(2)
    for seed in range(seeds):
        sizes = np.array(list(itertools.islice(
            draws.request_sizes(2**31 + 7919 * seed, pool, order), horizon)))
        sums = np.cumsum(np.vstack([np.zeros(2), sizes]), axis=0)
        means = (sums[run:] - sums[:-run]) / run
        worst = np.maximum(worst, np.abs(means / want - 1).max(axis=0))
    return worst


def test_spread_any_32_consecutive_requests_carry_the_pools_mix():
    """The cell's own pool (prompts 4096-12288, outputs 512-1024), 200
    seeds: wherever a window falls, its requests' mean prompt and output
    lengths lie within 4% of the pool's."""
    assert LONG["order"] == "spread"
    prompts, outputs = _worst_stray("spread")
    assert prompts < 0.04 and outputs < 0.04


def test_shuffled_32_consecutive_requests_are_a_sample_and_stray():
    """The property is the order's, not the pool's: the shuffled pool, a
    seventh of it at a time, strays by a fifth."""
    prompts, outputs = _worst_stray("shuffled")
    assert prompts > 0.15 and outputs > 0.10


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**32 + 5])
def test_spread_visits_every_quantile_and_is_seeded(seed):
    pool = _pool(LONG)
    first = list(itertools.islice(
        draws.request_sizes(seed, pool, "spread"), 512))
    assert {p for p, _ in first} == {p for p, _ in pool}
    assert {n for _, n in first} == {n for _, n in pool}
    # one endless walk, no seam: the second 256 are not the first again
    assert first[:256] != first[256:]
    assert first == list(itertools.islice(
        draws.request_sizes(seed, pool, "spread"), 512))
    assert first != list(itertools.islice(
        draws.request_sizes(seed + 1, pool, "spread"), 512))
    # the ids are the seed's too, and the lengths the walk's
    it = draws.sized_requests(seed, pool, 1000, order="spread")
    got = [next(it) for _ in range(4)]
    assert [(len(p), n) for p, n in got] == first[:4]
    assert all(0 <= p.min() and p.max() < 1000 for p, _ in got)


def test_an_unknown_order_is_an_error():
    with pytest.raises(ValueError, match="order"):
        draws.request_sizes(1, _pool(LONG), "sorted")


def _stream(traffic, seed, order):
    it = draws.sized_requests(seed, _pool(traffic), GOLDEN["vocab"],
                              order=order)
    sizes, ids = [], hashlib.sha1()
    for prompt, n_out in itertools.islice(it, GOLDEN["requests"]):
        sizes.append([len(prompt), n_out])
        ids.update(prompt.tobytes())
    return {"sizes_sha1": hashlib.sha1(
        json.dumps(sizes).encode()).hexdigest(),
        "first8": sizes[:8], "ids_sha1": ids.hexdigest()}


@pytest.mark.parametrize("name", sorted(GOLDEN["streams"]))
def test_shuffled_streams_are_the_parents_byte_for_byte(name):
    """The first 512 requests (sizes and ids) of three seeds, as the parent
    commit sent them: `order` left the default's draws alone.  Pinned are
    the closed-loop traffic files the parent had, and each of them but the
    long cell's states no order, so its cell's requests are the parent's.
    A file a later PR adds has no parent stream to pin, and needs none."""
    traffic = harness.load_json(harness.BENCH_DIR, "traffic", name + ".json")
    assert traffic["kind"] == "closed_loop"
    if name != "decode_closed_long":
        assert "order" not in traffic
    for seed, want in GOLDEN["streams"][name].items():
        assert _stream(traffic, int(seed), "shuffled") == want


# -- the first token per 1000 prompt tokens (PR 40) -------------------------

def _waits(lengths):
    """A first token's wait as the long cell's chunk program makes it: whole
    passes of 4096 rows (PERF.md section 6, PR 33: 289 ms at 4,112 tokens,
    346 at 8,112, 432 at 8,272, 501 at 12,272)."""
    n = np.asarray(lengths, float)
    return np.where(n <= 8192, 230.4 + 0.01425 * n, 289.3 + 0.01725 * n)


def _stats(ttft_ms, prompt_tokens):
    return {"out_tok_s": 1.0, "samples": {
        "ttft_ms": list(ttft_ms), "ttft_prompt_tokens": list(prompt_tokens),
        "ttft_ms_per_ktok": K.per_ktok(ttft_ms, prompt_tokens)}}


def test_per_ktok_two_and_three_pass_prompts_interleave():
    """In ms the waits are two piles, one a side of the 8192-token step,
    with nothing between: the median sits in the gap and reads either
    pile.  Per 1000 prompt tokens the piles overlap and the samples next
    to the median lie close."""
    lengths = draws.uniform_quantiles(4096, 12288, 41)
    ms = _waits(lengths)
    got = K.end_to_end(_stats(ms, lengths))
    per_ktok = 1e3 * ms / lengths
    assert got["ttft_ms_per_ktok_p50"] == pytest.approx(np.median(per_ktok))
    assert got["ttft_p50_ms"] == pytest.approx(np.median(ms))
    two, three = ms[lengths <= 8192], ms[lengths > 8192]
    assert two.max() + 80 < three.min()             # the step, in ms
    order = np.argsort(per_ktok)
    near = order[len(order) // 2 - 5: len(order) // 2 + 6]
    assert (lengths[near] <= 8192).any() and (lengths[near] > 8192).any()
    steps = np.diff(per_ktok[near]) / got["ttft_ms_per_ktok_p50"]
    assert steps.max() < 0.02
    # one sample more on either side of the step: the median in ms jumps
    # by a fifth, the median per 1000 tokens by one sample's distance
    def medians(n):
        m = K.end_to_end(_stats(_waits(n), n))
        return m["ttft_p50_ms"], m["ttft_ms_per_ktok_p50"]
    lo = medians(np.append(lengths, [8000, 8100]))
    hi = medians(np.append(lengths, [8300, 8400]))
    assert hi[0] / lo[0] > 1.15
    assert abs(hi[1] / lo[1] - 1) < 0.025


def test_per_ktok_a_4_s_straggler_moves_one_sample_not_the_median():
    lengths = draws.uniform_quantiles(4096, 12288, 41)
    ms = _waits(lengths)
    calm = K.end_to_end(_stats(ms, lengths))
    slowest = int(np.argmax(ms / lengths))
    ms[slowest] += 4000.0                       # a host pause of seconds
    stalled = K.end_to_end(_stats(ms, lengths))
    assert stalled["ttft_ms_per_ktok_p50"] == calm["ttft_ms_per_ktok_p50"]
    assert np.mean(ms / lengths) > 1.04 * np.mean(_waits(lengths) / lengths)


def test_the_tail_per_ktok_reads_what_the_median_is_deaf_to():
    """`ttft_ms_per_ktok_p95.closed`: the same sample at q 95.  Three of 41
    requests wait for another's chunk program before their own (400 ms
    more): the median stays where it was, the tail moves."""
    lengths = draws.uniform_quantiles(4096, 12288, 41)
    calm = _waits(lengths)
    ms = calm.copy()
    ms[np.argsort(calm / lengths)[-3:]] += 400.0
    assert K.end_to_end(_stats(ms, lengths))["ttft_ms_per_ktok_p50"] \
        == K.end_to_end(_stats(calm, lengths))["ttft_ms_per_ktok_p50"]
    percentile = harness.load_module(harness.BENCH_DIR, "readers",
                                     "percentile")
    spec = harness.load_json(harness.BENCH_DIR, "metrics",
                             "ttft_ms_per_ktok_p95.closed.json")
    assert spec["reader"] == "percentile"
    tail = [percentile.read({"stats": _stats(w, lengths)}, **spec["params"])
            for w in (calm, ms)]
    assert tail[0] == pytest.approx(np.percentile(1e3 * calm / lengths, 95))
    assert tail[1] > 1.5 * tail[0]


def test_no_first_token_in_the_window_is_no_number():
    got = K.end_to_end(_stats([], []))
    assert got["ttft_p50_ms"] is None
    assert got["ttft_ms_per_ktok_p50"] is None


def test_window_stats_keeps_each_first_tokens_prompt_length():
    """Finished requests and those still decoding at the close alike, in
    the order of their waits."""
    clock = Clock()
    loop = FakeLoop(clock)
    lengths = itertools.cycle([3, 30, 300])

    def requests():
        while True:
            yield np.arange(next(lengths), dtype=np.int32), 7
    c = K.ClosedLoopClient(loop, clock, requests(), 3)
    t0 = c.run_until(clock() + 0.1)
    t1 = c.run_until(t0 + 0.255)
    stats = K.window_stats(c, t0, t1)
    s = stats["samples"]
    assert len(s["ttft_prompt_tokens"]) == len(s["ttft_ms"]) > 6
    assert any(cur[0].first_token_time is not None
               and t0 < cur[0].first_token_time for cur in c.inflight)
    assert sorted(set(s["ttft_prompt_tokens"])) == [3, 30, 300]
    # every first token comes one 10 ms step after its request was due
    assert s["ttft_ms"] == pytest.approx([10.0] * len(s["ttft_ms"]))
    assert s["ttft_ms_per_ktok"] == pytest.approx(
        [1e4 / n for n in s["ttft_prompt_tokens"]])
    want = np.median([1e4 / n for n in s["ttft_prompt_tokens"]])
    assert K.end_to_end(stats)["ttft_ms_per_ktok_p50"] == pytest.approx(want)
    assert len(c.first_fill) == 3


# -- the start's backlog stays out of the window (PR 40) ---------------------

def _long_prompt_client(clients, n_out):
    clock = Clock()
    loop = FakeLoop(clock, prompts_a_step=1)

    def requests():
        while True:
            yield np.arange(5, dtype=np.int32), n_out
    return K.ClosedLoopClient(
        loop, clock, requests(), clients,
        first_fraction=np.linspace(0.1, 1.0, clients)), clock


def test_the_window_waits_for_the_starts_backlog():
    """8 clients arrive at once and one prompt is prefilled a step: after a
    settling time of 3 steps five first requests still wait, the last for
    8 steps.  `drain_start` runs on until each has its first token, and so
    has whatever queued behind them; no wait of the start is a sample."""
    c, clock = _long_prompt_client(clients=8, n_out=20)
    t0 = clock()
    t_settled = c.run_until(t0 + 0.03)
    t_open = c.drain_start(t_settled)
    assert t_open > t_settled
    assert t_open >= max(r.first_token_time for r in c.first_fill)
    # the short first requests finished and their clients' second ones
    # queued behind the start's: those were waited for as well
    assert len(c.done) > 0 and c.submitted > 8
    t_close = c.run_until(t_open + 0.5)
    waits = K.window_stats(c, t_open, t_close)["samples"]["ttft_ms"]
    assert waits and max(waits) < 45.0          # the start's were 10-80
    late = K.window_stats(c, t_settled, t_close)["samples"]["ttft_ms"]
    assert max(late) >= 75.0                    # what opening early lets in


def test_a_start_that_is_over_by_the_settling_time_changes_nothing():
    """Short prompts (every other cell): the first fill is over within a
    step or two; no step more is run, whoever waits for a first token just
    then, and the window opens where it always did."""
    c, clock = client(clients=4, n_out=5)
    t_settled = c.run_until(clock() + 0.2)
    steps = len(c.steps)
    assert c.drain_start(t_settled) == t_settled and len(c.steps) == steps


def test_a_first_request_that_fails_does_not_hold_the_window():
    c, clock = client(clients=2, n_out=4, fail_every=1, prompts_a_step=1)
    c.run_until(clock() + 0.03)
    c.first_fill[1].first_token_time = None     # failed before any token
    c.first_fill[1].state.value = "failed"
    steps = len(c.steps)
    assert c.drain_start(clock()) == clock() and len(c.steps) == steps


# -- a traced stretch holds whole prefill programs (PR 40) -------------------

def test_a_traced_stretch_begins_and_ends_where_no_prompt_is_prefilled():
    """One prompt is prefilled a step and four requests of one length wait:
    `settle_prefill` runs on until none waits for its first token, so the
    prompts whose first token falls in a stretch between two such moments
    and the prefill programs run in it are the same work."""
    c, clock = _long_prompt_client(clients=4, n_out=6)
    c.iterate()
    assert c._awaited(cur[0] for cur in c.inflight)
    t0 = c.settle_prefill(clock(), at_most_s=1.0)
    assert not c._awaited(cur[0] for cur in c.inflight if cur is not None)
    submitted = c.submitted
    t1 = c.settle_prefill(c.run_until(t0 + 0.095), at_most_s=1.0)
    assert not c._awaited(cur[0] for cur in c.inflight if cur is not None)
    first_tokens = [r for r in
                    [d for d in c.done] + [cur[0] for cur in c.inflight
                                           if cur is not None]
                    if getattr(r, "first_token", None) is not None
                    and t0 < r.first_token <= t1
                    or getattr(r, "first_token_time", None) is not None
                    and t0 < r.first_token_time <= t1]
    # every request submitted in the stretch had its prefill inside it
    assert len(first_tokens) == c.submitted - submitted > 0
    # nothing waits: not a step is run
    steps = len(c.steps)
    assert c.settle_prefill(t1, at_most_s=1.0) == t1 and len(c.steps) == steps


def test_settling_gives_up_where_prompts_never_stop_waiting():
    """A loop that is always behind (more arrivals than it prefills): the
    stretch begins after `at_most_s` all the same."""
    c, clock = _long_prompt_client(clients=8, n_out=2)
    t0 = c.run_until(clock() + 0.05)
    assert c._awaited(cur[0] for cur in c.inflight if cur is not None)
    t1 = c.settle_prefill(t0, at_most_s=0.1)
    assert 0.1 <= t1 - t0 < 0.12
