"""Training traffic: optimizer steps of a fixed token count, fed with one
step kept queued.

Parameters (the traffic file): `rows` and `seq_len` (a step is `rows`
sequences of `seq_len` tokens, every row different, ids uniform over the
vocabulary from the seed), `check_steps` (how many first steps the plain
reference follows), `limits` (loss_first_step, loss_later_steps, grad_norm,
update_norm).

Set-up builds ONE engine, drives it from the seed through its first
`check_steps` steps by the window's own call and feed, reads what the
comparison needs (each loss; the first gradient as the optimizer got it,
worked out from the first moment after one step; the weights' change after
the last of them, both by the worst leaf), and hands that same engine to
the window.  The loop
dispatches step k+1 before it waits for step k's loss, so a host stall does
not idle the device.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import draws


def batches(seed: int, rows: int, seq_len: int, vocab: int):
    """Endless {"input_ids": [rows, seq_len + 1]}: inputs and shifted
    labels of `rows` different sequences."""
    rng = draws.rng_of(seed, 11)
    while True:
        yield {"input_ids": rng.randint(
            0, vocab, (rows, seq_len + 1)).astype(np.int32)}


def _norms(tree):
    """Norm of every leaf outside `layers`; of a leaf stacked under
    `layers`, per layer."""
    sq = lambda x, axes: jnp.sqrt(jnp.sum(  # noqa: E731
        x.astype(jnp.float32) ** 2, axis=axes))
    return {"top": jax.tree.map(lambda a: sq(a, None),
                                {k: a for k, a in tree.items()
                                 if k != "layers"}),
            "layers": jax.tree.map(lambda a: sq(a, tuple(range(1, a.ndim))),
                                   tree["layers"])}


def first_moment_gradient_norms(engine, b1: float):
    """Per-leaf norms of the clipped gradient of the step just taken, from
    the optimizer's first moment: after one step m = (1 - b1) * g.  The
    8-bit moments are decoded with the optimizer's own decoder, inside the
    call that reduces them (a float32 copy of them would not fit)."""
    from deepspeed_tpu.runtime.optimizers import _dq8_sq_signed
    opt = engine.state.opt_state

    @jax.jit
    def norms(m, scale):
        if scale is not None:
            m = jax.tree.map(_dq8_sq_signed, m, scale)
        return _norms(jax.tree.map(
            lambda a: a.astype(jnp.float32) / (1.0 - b1), m))
    return jax.device_get(norms(opt["m"], opt.get("m_scale")))


@jax.jit
def _change_norms(now, start):
    return _norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
        now, start))


def flat_norms(norms: dict):
    """(names, values): every leaf of a `_norms` tree, a stacked one once
    per layer, in one fixed order."""
    names, values = [], []
    for path, a in jax.tree_util.tree_leaves_with_path(norms):
        a = np.atleast_1d(np.asarray(a, np.float64))
        names += [jax.tree_util.keystr(path)] * len(a)
        values.append(a)
    return names, np.concatenate(values)


def leaf_gaps(got: dict, want: dict) -> np.ndarray:
    """|got - want| of every leaf (and layer), each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    g, w = flat_norms(got)[1], flat_norms(want)[1]
    return np.abs(g - w) / np.maximum(w, np.median(w))


# a leaf whose reference gradient is under this share of the median leaf's
# has no gradient (the key bias: softmax ignores a shift of every score of
# a row, so the model does not depend on it at all)
NO_GRADIENT = 1e-3


def compare(got: dict, want: dict, limits: dict):
    """The numbers `correct` rests on, each beside its limit, and notes.
    `got` and `want` hold each step's `loss` and the per-leaf `grad_norm`
    (first step) and `update_norm` (after the last).  Both norms go by the
    worst leaf.  The weights' change leaves out the leaves that have no
    gradient: Adam divides a gradient by its own size, so there the
    program's rounding noise alone makes a full-size step and the float32
    reference's exact zero makes none, in every sound run."""
    names, want_grad = flat_norms(want["grad_norm"])
    has_gradient = want_grad >= NO_GRADIENT * np.median(want_grad)
    change = leaf_gaps(got["update_norm"], want["update_norm"])
    grad = leaf_gaps(got["grad_norm"], want["grad_norm"])
    worst = int(np.argmax(np.where(has_gradient, change, -1.0)))
    loss = [abs(g - w) / abs(w) for g, w in zip(got["loss"], want["loss"])]
    compared = {
        # the first step's loss, at the seeded weights, is held against a
        # part of the batch left out; a later step's also carries the
        # rounding of the update before it, and swings ten times as far
        "loss_first_step": (loss[0], limits["loss_first_step"]),
        "grad_norm": (float(np.max(grad)), limits["grad_norm"]),
        "update_norm": (float(change[worst]), limits["update_norm"]),
    }
    if loss[1:]:
        compared["loss_later_steps"] = (max(loss[1:]),
                                        limits["loss_later_steps"])
    notes = {
        "losses": list(got["loss"]), "reference_losses": list(want["loss"]),
        "grad_norm_worst_leaf": names[int(np.argmax(grad))],
        "update_norm_worst_leaf": names[worst],
        "update_norm_median_leaf": float(np.median(change)),
        "leaves_without_gradient": sorted(
            {n for n, h in zip(names, has_gradient) if not h}),
        "update_norm_without_gradient": float(np.max(
            change[~has_gradient], initial=0.0)),
    }
    return compared, notes


def hyper_of(cfg: dict) -> dict:
    opt = cfg["program"]["training"]["optimizer"]["params"]
    return {"lr": opt["lr"], "betas": opt["betas"], "eps": opt["eps"],
            "weight_decay": opt["weight_decay"],
            "clip": cfg["program"]["training"]["gradient_clipping"]}


def first_steps(ctx, engine, feed, model, sizes, dtype):
    """The engine's first `check_steps` steps through the window's own call
    and feed: (what the comparison reads of them, the batches they saw)."""
    seen: List[np.ndarray] = []
    got: Dict[str, object] = {"loss": []}
    for k in range(ctx.traffic["check_steps"]):
        batch = next(feed)
        seen.append(batch["input_ids"])
        metrics = engine.train_batch(batch)
        got["loss"].append(float(metrics["loss"]))
        if k == 0:
            got["grad_norm"] = first_moment_gradient_norms(
                engine, hyper_of(ctx.config)["betas"][0])
    start = model.make_params(ctx.seed, sizes, dtype)
    master = engine.state.master if engine.state.master is not None \
        else engine.state.params
    got["update_norm"] = jax.device_get(_change_norms(master, start))
    from benchmark import systems
    systems.free(start)
    return got, seen


def step_program_bytes(ctx, engine, sizes) -> int:
    """The compiler's own peak for the step that runs (arguments, updated
    in place, and the temporaries live beside them); arguments + temps
    counts the aliased state twice and reads past the chip's memory."""
    tr = ctx.traffic
    mem = engine._train_step.lower(
        engine.state, engine._shard_batch(next(batches(
            0, tr["rows"], tr["seq_len"], sizes.vocab))),
        jax.random.PRNGKey(0), {}).compile().memory_analysis()
    peak = getattr(mem, "peak_memory_in_bytes", 0) or None
    ctx.note(step_memory={k: getattr(mem, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes")},
        peak_memory_in_bytes=peak)
    return peak


def window(ctx, engine, feed, tokens_per_step: int):
    """The measured window: step k+1 is dispatched before step k's loss is
    read.  Returns (end-to-end numbers, stats, steps completed)."""
    step_ends: List[float] = []
    feed_ms: List[float] = []

    def dispatch():
        t0 = time.perf_counter()
        with ctx.annotate("bench.train.feed"):
            batch = next(feed)
        feed_ms.append(1e3 * (time.perf_counter() - t0))
        with ctx.annotate("bench.train.step"):
            return engine.train_batch(batch)["loss"]

    def complete_one(queued):
        nxt = dispatch()
        jax.block_until_ready(queued)
        now = time.perf_counter()
        step_ends.append(now)
        return now, nxt

    def run_until(t_end: float, queued):
        """Whole steps until the clock passes `t_end`; returns the time the
        last one ended and the loss handle still queued."""
        now, queued = complete_one(queued)
        while now < t_end:
            now, queued = complete_one(queued)
        return now, queued

    t_open, queued = complete_one(dispatch())
    ctx.window_opens()
    traced = None
    if ctx.trace:
        lead = max(ctx.seconds - ctx.trace_seconds - 2.0, 0.5 * ctx.seconds)
        t0, queued = run_until(t_open + lead, queued)
        ctx.start_trace()
        t1, queued = run_until(t0 + ctx.trace_seconds, queued)
        ctx.stop_trace()
        traced = (t0, t1)
    t_close, queued = run_until(t_open + ctx.seconds, queued)
    jax.block_until_ready(queued)          # the queued step is not counted
    ctx.window_closes()

    inside = [t for t in step_ends if t_open <= t <= t_close]
    steps = len(inside) - 1
    # a stall shows in the rate; these say where it was
    ctx.note(slowest_steps_ms=sorted(
        (round(1e3 * (b - a), 1), i) for i, (a, b) in
        enumerate(zip(inside, inside[1:])))[-3:])
    until = traced[0] if traced else t_close
    host = np.array([t for t in step_ends if t_open <= t <= until])
    stats = {
        "samples": {"step_ms": (1e3 * np.diff(host)).tolist()},
        "counters": {"steps": steps,
                     "data_wait_ms": float(np.sum(feed_ms[-steps:]))}}
    rate = steps * tokens_per_step / (t_close - t_open) / len(ctx.devices)
    return {"train_tok_s_chip": rate}, stats, steps


def run(ctx) -> dict:
    """One run of a training cell (see `benchmark.harness.Context`).  Under
    `ctx.control` no engine is built: the reference, computed in that lower
    precision, takes the same first steps in the program's place and is
    held to the same comparison."""
    from benchmark import systems
    tr, cfg = ctx.traffic, ctx.config
    model = ctx.reference()
    sizes, dtype = model.sizes(cfg), systems.stored_dtype(cfg)
    feed = batches(ctx.seed, tr["rows"], tr["seq_len"], sizes.vocab)
    end_to_end, stats, steps = {}, {}, 0
    if ctx.control is None:
        engine = systems.build_training(cfg, ctx.seed, ctx.devices, model)
        got, seen = first_steps(ctx, engine, feed, model, sizes, dtype)
        program_bytes = step_program_bytes(ctx, engine, sizes) \
            if ctx.trace else None
        end_to_end, stats, steps = window(ctx, engine, feed,
                                          tr["rows"] * tr["seq_len"])
        if program_bytes:
            stats["counters"]["program_bytes"] = program_bytes
        ctx.read_memory_peak()
        systems.free(engine.state)
    else:
        seen = [next(feed)["input_ids"] for _ in range(tr["check_steps"])]
        ctx.window_opens()
        ctx.window_closes()
        got = model.train_steps(ctx.seed, seen, sizes, dtype, hyper_of(cfg),
                                precision=ctx.control)
        ctx.read_memory_peak()
    # the plain reference follows the first steps
    want = model.train_steps(ctx.seed, seen, sizes, dtype, hyper_of(cfg))
    compared, notes = compare(got, want, tr["limits"])
    ctx.note(steps=steps, **notes)
    return {"attempted": steps, "failed": 0, "end_to_end": end_to_end,
            "compared": compared, "stats": stats}
