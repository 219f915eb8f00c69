"""Closed-loop serving traffic: a fixed number of clients, each sending its
next request the moment its last one finishes (no think time).

Parameters (the traffic file): `clients`, `prompt_len` [lo, hi],
`output_len` [lo, hi], `size_pool` (how many evenly spread sizes the seed
orders), `order` (`benchmark/draws.py`: "shuffled", the default, or
"spread", where a window sees a fraction of the pool and a request's cost
follows its size), `settle_s` (the loop runs this long, already full,
before the window opens; longer where the start's backlog of first
requests is not gone by then), `window_x` (the window lasts this many
times `--seconds`, 1 unless stated: for a cell whose requests are so long
that `--seconds` holds too few first tokens to judge by), `check_requests`
(how many finished requests the plain reference re-computes),
`greedy_gap_limit`.

A request is timed from when it was DUE: the moment its client's previous
request finished (the loop's own stamp, on the shared clock) — not from
`submit`, so a stall shows in the wait it causes.  Each client's first
request is cut to a random fraction of its output length, so the loop is in
steady state (phases spread) when the window opens.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmark import draws


@dataclasses.dataclass
class Done:
    """One finished request, on the client's clock (seconds)."""
    due: float
    admitted: Optional[float]
    first_token: Optional[float]
    finished: float
    ok: bool
    prompt: np.ndarray
    tokens: np.ndarray


@dataclasses.dataclass
class Step:
    start: float
    end: float
    new_tokens: int      # output tokens that appeared during this step
    rows: int            # requests in flight during it
    context: int         # prompt + generated tokens they held after it


class ClosedLoopClient:
    """Drives anything with `submit(prompt, max_new_tokens=) -> request` and
    `step()`; a request exposes `generated`, `admit_time`,
    `first_token_time`, `finish_time` and `state.value` (terminal:
    "done" is the good one)."""

    TERMINAL = ("done", "cancelled", "timed_out", "failed")

    def __init__(self, loop, clock: Callable[[], float],
                 requests: Iterator[Tuple[np.ndarray, int]], clients: int,
                 first_fraction: Optional[np.ndarray] = None,
                 annotate=lambda name: contextlib.nullcontext()):
        self.loop, self.clock, self.requests = loop, clock, requests
        self.annotate = annotate
        # slot -> [request, due, tokens seen so far] or None
        self.inflight: List[Optional[list]] = [None] * clients
        self.free_at: List[float] = [clock()] * clients
        self.first_fraction = first_fraction
        self.done: List[Done] = []
        self.steps: List[Step] = []
        self.submitted = 0
        self.first_fill: list = []     # each client's first request

    def _fill(self) -> None:
        for slot, cur in enumerate(self.inflight):
            if cur is not None:
                continue
            prompt, n_out = next(self.requests)
            if self.first_fraction is not None \
                    and self.submitted < len(self.inflight):
                n_out = max(2, int(np.ceil(
                    n_out * self.first_fraction[self.submitted])))
            req = self.loop.submit(prompt, max_new_tokens=n_out)
            if self.submitted < len(self.inflight):
                self.first_fill.append(req)
            self.inflight[slot] = [req, self.free_at[slot], 0]
            self.submitted += 1

    def _collect(self, start: float, end: float) -> None:
        new = rows = context = 0
        for slot, cur in enumerate(self.inflight):
            if cur is None:
                continue
            req, due, seen = cur
            n = len(req.generated)
            new += n - seen
            cur[2] = n
            rows += 1
            state = req.state.value
            if state in self.TERMINAL:
                finished = req.finish_time if req.finish_time is not None \
                    else end
                self.done.append(Done(
                    due, req.admit_time, req.first_token_time, finished,
                    state == "done" and n == req.max_new_tokens,
                    np.asarray(req.prompt, np.int32),
                    np.asarray(req.generated, np.int32)))
                self.inflight[slot] = None
                self.free_at[slot] = finished
            else:
                context += len(req.prompt) + n
        self.steps.append(Step(start, end, new, rows, context))

    def iterate(self) -> float:
        """One iteration (fill, one `step`, collect); returns the time the
        step ended."""
        with self.annotate("bench.serve.client"):
            self._fill()
        start = self.clock()
        with self.annotate("bench.serve.step"):
            self.loop.step()
        now = self.clock()
        with self.annotate("bench.serve.client"):
            self._collect(start, now)
        return now

    def run_until(self, t_end: float) -> float:
        """Whole iterations until the clock passes `t_end`; returns the
        time the last one ended."""
        now = self.clock()
        while now < t_end:
            now = self.iterate()
        return now

    def _awaited(self, requests) -> bool:
        return any(r.first_token_time is None
                   and r.state.value not in self.TERMINAL for r in requests)

    def drain_start(self, now: float) -> float:
        """The start has every client arrive at once; where prompts are
        long that backlog can outlast the settling time, and its waits are
        the start's, not the loop's.  Iterations until it is gone: every
        client's first request has had its first token, and so has every
        request that queued behind those.  Returns the time the last one
        ended: `now`, with nothing run, where no first request waits."""
        if not self._awaited(self.first_fill):
            return now
        while self._awaited(self.first_fill):
            now = self.iterate()
        queued = [cur[0] for cur in self.inflight if cur is not None]
        while self._awaited(queued):
            now = self.iterate()
        return now

    def settle_prefill(self, now: float, at_most_s: float) -> float:
        """Iterations until no request in flight waits for its first token
        (no prefill program runs or is queued then), `at_most_s` at the
        longest.  A traced stretch that begins and ends so holds whole
        prefill programs only: the prompts whose first token falls in it
        and the prefill programs of its trace are the same work."""
        t_give_up = now + at_most_s
        while now < t_give_up and self._awaited(
                cur[0] for cur in self.inflight if cur is not None):
            now = self.iterate()
        return now


def per_ktok(waits_ms, prompt_tokens) -> List[float]:
    """Each first token's wait per 1000 tokens of its own prompt."""
    return [1e3 * ms / n for ms, n in zip(waits_ms, prompt_tokens)]


def window_stats(client: ClosedLoopClient, t_open: float, t_close: float,
                 until: Optional[float] = None) -> Dict[str, object]:
    """What happened inside [t_open, t_close]: both are ends of steps, so
    every step counted lies wholly inside.  `until` (a traced run) keeps
    the host-clock samples to the part before the profiler started."""
    until = t_close if until is None else until
    steps = [s for s in client.steps if t_open < s.end <= t_close]
    tokens = sum(s.new_tokens for s in steps)
    first = [d for d in client.done
             if d.first_token is not None and t_open < d.first_token <= until]
    # a request still decoding at the close has had its first token too
    first_open = [(cur[0].first_token_time, cur[1], len(cur[0].prompt))
                  for cur in client.inflight if cur is not None
                  and cur[0].first_token_time is not None
                  and t_open < cur[0].first_token_time <= until]
    ttft = [1e3 * (d.first_token - d.due) for d in first] \
        + [1e3 * (ft - due) for ft, due, _ in first_open]
    ttft_prompt = [len(d.prompt) for d in first] \
        + [n for _, _, n in first_open]
    ended = [d for d in client.done if t_open < d.finished <= until]
    good = [d for d in ended if d.ok]
    tpot = [1e3 * (d.finished - d.first_token) / (len(d.tokens) - 1)
            for d in good if len(d.tokens) > 1]
    waits = [1e3 * (d.admitted - d.due) for d in ended
             if d.admitted is not None]
    host = [s for s in steps if s.end <= until]
    return {
        "window_s": t_close - t_open,
        "output_tokens": tokens,
        "out_tok_s": tokens / (t_close - t_open),
        "attempted": len([d for d in client.done
                          if t_open < d.finished <= t_close]),
        "failed": len([d for d in client.done
                       if t_open < d.finished <= t_close and not d.ok]),
        "samples": {"ttft_ms": ttft, "ttft_prompt_tokens": ttft_prompt,
                    "ttft_ms_per_ktok": per_ktok(ttft, ttft_prompt),
                    "tpot_ms": tpot, "queue_wait_ms": waits,
                    "serve_step_ms": [1e3 * (s.end - s.start) for s in host]},
        "counters": {"output_tokens": tokens, "steps": len(steps),
                     "rows": sum(s.rows for s in steps),
                     "context_tokens": sum(s.context for s in steps)},
        "finished_ok": [d for d in client.done
                        if t_open < d.finished <= t_close and d.ok],
    }


def pick_for_check(finished: List[Done], seed: int, n: int) -> List[Done]:
    """A seeded sample of the window's finished requests, the longest in."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i].prompt)
                                   + len(finished[i].tokens)))
    rest = order[1:]
    rng = draws.rng_of(seed, 3)
    picked = [order[0]] + [rest[i] for i in
                           rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [finished[i] for i in picked]


def drive(ctx):
    """Build the system, warm it, fill the loop, run the window.  Returns
    (window stats, the window's good finished requests); the system's
    device state is freed by then."""
    from benchmark import systems
    tr, cfg = ctx.traffic, ctx.config
    model = ctx.reference()
    sizes = model.sizes(cfg)
    engine, loop = systems.build_serving(cfg, ctx.seed, model)
    built_s = time.time() - ctx.t_start
    warmed = systems.warm_serving(engine, tr["prompt_len"], sizes.vocab)
    # where `setup_s` goes: seconds from the process's start to the engine
    # built (imports, seeded weights) and to the shapes warmed; the rest
    # is the settling time and the wait for the start's backlog
    ctx.note(warmed=warmed, free_blocks=engine.free_blocks,
             built_s=round(built_s, 1),
             warmed_s=round(time.time() - ctx.t_start, 1))
    pool = draws.size_pool(tr["prompt_len"], tr["output_len"],
                           tr["size_pool"])
    client = ClosedLoopClient(
        loop, time.perf_counter,
        draws.sized_requests(ctx.seed, pool, sizes.vocab,
                             order=tr.get("order", "shuffled")),
        tr["clients"],
        first_fraction=draws.rng_of(ctx.seed, 4).uniform(
            0.0, 1.0, tr["clients"]),
        annotate=ctx.annotate)
    t_open = client.drain_start(
        client.run_until(time.perf_counter() + tr["settle_s"]))
    ctx.window_opens()
    seconds = ctx.seconds * tr.get("window_x", 1)
    traced = None
    if ctx.trace:
        lead = max(seconds - ctx.trace_seconds - 1.0, 0.5 * seconds)
        # begun and ended where no prompt is being prefilled, so that no
        # prefill program is cut by either end (`settle_prefill`)
        t0 = client.settle_prefill(client.run_until(t_open + lead),
                                   ctx.trace_seconds)
        ctx.start_trace()
        t1 = client.settle_prefill(client.run_until(t0 + ctx.trace_seconds),
                                   ctx.trace_seconds)
        ctx.stop_trace()
        traced = (t0, t1)
    t_close = client.run_until(t_open + seconds)
    ctx.window_closes()
    stats = window_stats(client, t_open, t_close,
                         until=traced[0] if traced else None)
    if traced:
        # the prompts prefilled while the profiler ran: the requests whose
        # first token (the end of their prefill) fell in there, each with
        # the wait it had, so the host's and the device's account of the
        # same prompts can be laid side by side
        firsts = [(d.first_token, d.due, len(d.prompt))
                  for d in client.done] + [
            (cur[0].first_token_time, cur[1], len(cur[0].prompt))
            for cur in client.inflight if cur is not None]
        firsts = [(ft, due, n) for ft, due, n in firsts
                  if ft is not None and traced[0] < ft <= traced[1]]
        lengths = [n for _, _, n in firsts]
        stats["traced"] = {
            "prompt_tokens": sum(lengths), "prompt_lengths": lengths,
            "ttft_ms": [1e3 * (ft - due) for ft, due, _ in firsts]}
        ctx.note(traced_first_tokens=stats["traced"])
    finished = stats.pop("finished_ok")
    # a stall shows in the rate; these say where it was.  The first fill
    # (every client's first prompt) ends before the window opens: negative
    # seconds, about 0 where the window waited for it
    filled = [r.first_token_time for r in client.first_fill]
    ctx.note(slowest_steps_ms=sorted(
        (round(ms, 1), i) for i, ms in
        enumerate(stats["samples"]["serve_step_ms"]))[-3:],
        first_fill_ends_s=None if None in filled
        else round(max(filled) - t_open, 3),
        first_tokens=len(stats["samples"]["ttft_ms"]),
        window_s=round(stats["window_s"], 3))
    ctx.read_memory_peak()
    systems.free(engine.params, engine.arena)
    return stats, finished


def check(ctx, finished: List[Done], precision=None):
    """The widest gap of the served tokens of a seeded sample of the
    window's requests (or, with `precision`, of the tokens that lower
    precision would have put first: the control)."""
    from benchmark import systems
    model = ctx.reference()
    picked = pick_for_check(finished, ctx.seed,
                            ctx.traffic["check_requests"])
    if not picked:
        return float("inf"), 0
    return model.served_token_gap(
        ctx.seed, [(d.prompt, d.tokens) for d in picked],
        model.sizes(ctx.config), systems.stored_dtype(ctx.config),
        precision=precision)


def end_to_end(stats) -> Dict[str, Optional[float]]:
    """What every closed-loop cell can report (`BENCHMARK.json` says which
    cell reports which).  The two first-token numbers are medians over ALL
    requests whose first token fell in the window: of the wait in ms, and
    of the wait per 1000 prompt tokens (for prompts that differ severalfold
    in length: `benchmark/SMALLTHINKER.md`)."""
    samples = stats["samples"]

    def median(values):
        return float(np.percentile(values, 50)) if values else None
    return {"out_tok_s": stats["out_tok_s"],
            "ttft_p50_ms": median(samples["ttft_ms"]),
            "ttft_ms_per_ktok_p50": median(samples["ttft_ms_per_ktok"])}


def run(ctx) -> dict:
    """One run of a closed-loop cell (see `benchmark.harness.Context`).
    Under `ctx.control` the window is driven all the same, and what is
    compared at each position of its requests is the token that the
    reference in that lower precision puts first, in the served one's
    place."""
    stats, finished = drive(ctx)
    gap, scored = check(ctx, finished, precision=ctx.control)
    ctx.note(check_tokens=scored)
    return {
        "attempted": stats["attempted"], "failed": stats["failed"],
        "end_to_end": end_to_end(stats),
        "compared": {
            "greedy_gap": (gap, ctx.traffic["greedy_gap_limit"]),
            "failed_requests": (stats["failed"], 0)},
        "stats": stats,
    }
