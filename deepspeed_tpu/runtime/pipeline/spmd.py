"""SPMD pipeline parallelism.

Reference: runtime/pipe/ — `PipelineModule`/`LayerSpec` (module.py),
1F1B `TrainSchedule` (schedule.py:189), the instruction-interpreter engine
(`_exec_schedule` engine.py:1354) and P2P send/recv (p2p.py:46).

TPU-native inversion: DeepSpeed runs an eager per-rank instruction loop with
NCCL P2P between stage processes.  Here the WHOLE pipeline — all stages, all
microbatches — is a single jitted program: layer parameters carry a leading
layer dim sharded over the `pp` mesh axis (each device holds L/P layers =
its stage), and a `lax.scan` streams microbatch activations between stages
with `jax.lax.ppermute` (XLA CollectivePermute -> one-hop ICI DMA, exactly
the P2P topology of the reference but scheduled by the compiler).

Two schedules (see pipeline_layers): T = M + P - 1 steps, step t has
stage d processing microbatch m = t - d; bubble fraction (P-1)/T either
way.  "fill_drain" lets JAX autodiff reverse the scan (stashes every
step's stage internals — all M microbatches live at the fwd/bwd boundary);
"1f1b" is a custom-vjp reverse pipeline with the reference TrainSchedule's
memory profile: only [M] stage-boundary inputs are stashed and the
backward recomputes one in-flight microbatch's stage per step.

The streamed state is a (activations, positions, aux) tuple so rotary
positions and MoE aux losses ride along with the activations.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...parallel.context import require_topology
from ...parallel.mesh import AXIS_PP
from jax import shard_map

__all__ = ["pipeline_layers"]


def pipeline_layers(
    stage_fn: Callable,       # (local_layer_params, x, pos) -> (x, aux)
    layer_params: Any,        # pytree, leaves [L, ...] sharded over pp on dim 0
    x: jax.Array,             # [B, S, H]
    positions: jax.Array,     # [B, S]
    axis_name: str = AXIS_PP,
    num_microbatches: int = 0,
    schedule: str = "fill_drain",
) -> Tuple[jax.Array, jax.Array]:
    """Run the stacked layers as a pipeline over `axis_name`.

    Returns (y [B,S,H], aux_sum scalar).  Requires B % num_microbatches == 0.

    schedule="fill_drain": XLA autodiff reverses the scan — simple, but the
    backward stashes every step's stage INTERNALS, so all M microbatches'
    per-layer activations are live at the fwd/bwd boundary (the memory
    profile 1F1B exists to avoid; reference: runtime/pipe/schedule.py:189).

    schedule="1f1b": the memory profile of the reference's TrainSchedule,
    TPU-native — a custom-vjp reverse pipeline.  The forward stashes only
    each microbatch's stage-boundary INPUT ([M, B/M, S, H]); the backward
    runs
    the mirrored schedule, recomputing one in-flight microbatch's stage vjp
    per step and streaming cotangents to the previous stage with the
    reversed ppermute ring.  Per-layer activation memory is therefore
    bounded by the in-flight recompute (O(1) microbatches per stage) rather
    than O(M) — the same bound 1F1B's interleaving buys, obtained here by
    recompute + bounded stash instead of eager interleave (under a single
    jitted SPMD program the compiler owns instruction order, so the
    schedule is expressed through what is *saved*, not when ops run).
    """
    if schedule not in ("fill_drain", "1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         f"(fill_drain | 1f1b)")
    topo = require_topology()
    pp = topo.size(axis_name)
    if pp == 1:
        return stage_fn(layer_params, x, positions)

    B = x.shape[0]
    M = num_microbatches or pp
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")

    in_dtype = x.dtype
    T = M + pp - 1
    perm = [(i, i + 1) for i in range(pp - 1)]
    rperm = [(i + 1, i) for i in range(pp - 1)]

    def _split(x, positions):
        # local views: x crosses the shard_map boundary in fp32 (the AD
        # transpose of a pp-replicated input is a psum of its cotangent,
        # and bf16 psum under partial-auto shard_map trips an XLA-CPU
        # CHECK failure); microbatch-major [M, B/M, ...] views
        x = x.astype(in_dtype)
        xs = x.reshape((M, B // M) + x.shape[1:])
        ps = positions.reshape((M, B // M) + positions.shape[1:])
        return x, xs, ps

    def _bcast_last(val, d):
        # broadcast a last-stage-owned value to every stage; psum in fp32
        # (bf16 AllReduce under partial-auto shard_map trips an XLA-CPU
        # CHECK "Invalid binary instruction opcode copy", and fp32 is the
        # right accumulation dtype anyway).  Traffic note: this AllReduce
        # moves ~|y| per link — the same as any broadcast of y — and every
        # stage DOES need y, because the loss/final-norm epilogue runs
        # replicated across pp under SPMD.  The buffer is [M, B/M, ...] =
        # exactly one global batch, not M x it.
        is_last = (d == pp - 1).astype(jnp.float32)
        return jax.lax.psum(val.astype(jnp.float32) * is_last, axis_name)

    def local_1f1b(layer_params, x, positions):
        x, xs, ps = _split(x, positions)

        @jax.custom_vjp
        def pipe(layer_params, xs, ps):
            outs, _ = _pipe_fwd_scan(layer_params, xs, ps)
            return outs

        def pipe_fwd(layer_params, xs, ps):
            outs, stash = _pipe_fwd_scan(layer_params, xs, ps)
            return outs, (layer_params, ps, stash)

        def _pipe_fwd_scan(layer_params, xs, ps):
            # axis_index must be taken inside each traced region: closing
            # over one tracer from the outer trace leaks it into the
            # custom_vjp's separately-traced fwd/bwd
            d = jax.lax.axis_index(axis_name)
            recv0 = jnp.zeros_like(xs[0])
            outs0 = jnp.zeros_like(xs)
            aux0 = jnp.zeros((M,), jnp.float32)
            stash0 = jnp.zeros_like(xs)

            def step(carry, t):
                recv, outs, auxs, stash = carry
                m = jnp.clip(t - d, 0, M - 1)
                valid = jnp.logical_and(t - d >= 0, t - d < M)
                first = d == 0
                inp = jnp.where(first, jax.lax.dynamic_index_in_dim(
                    xs, jnp.clip(t, 0, M - 1), 0, keepdims=False), recv)
                pos = jax.lax.dynamic_index_in_dim(ps, m, 0, keepdims=False)
                out, aux = stage_fn(layer_params, inp, pos)

                def upd(buf, val):
                    cur = jax.lax.dynamic_index_in_dim(buf, m, 0,
                                                       keepdims=False)
                    return jax.lax.dynamic_update_index_in_dim(
                        buf, jnp.where(valid, val, cur), m, 0)

                # [M]-row buffers indexed by microbatch: bubble steps write
                # nothing, so the stash carries no (pp-1)/M garbage rows
                outs = upd(outs, out)
                auxs = upd(auxs, aux)
                stash = upd(stash, inp)
                recv_n = jax.lax.ppermute(out, axis_name, perm)
                return (recv_n, outs, auxs, stash), None

            (_, outs, auxs, stash), _ = jax.lax.scan(
                step, (recv0, outs0, aux0, stash0), jnp.arange(T))
            return (outs, auxs), stash

        def pipe_bwd(res, g):
            layer_params, ps, stash = res
            d = jax.lax.axis_index(axis_name)
            g_outs, g_auxs = g                  # [M, B/M, S, H], [M]
            gz0 = jnp.zeros_like(g_outs[0])
            # int leaves (per-layer windows / dense flags riding the stack)
            # take float0 cotangents: carry a scalar placeholder through
            # the scan (float0 has no XLA representation) and emit the real
            # float0 zeros only at the end
            inexact = jax.tree.map(
                lambda p: jnp.issubdtype(p.dtype, jnp.inexact),
                layer_params)
            grads0 = jax.tree.map(
                lambda p, fl: (jnp.zeros_like(p) if fl
                               else jnp.zeros((), jnp.float32)),
                layer_params, inexact)
            dxs0 = jnp.zeros_like(g_outs)

            def step(carry, sigma):
                recv_g, grads, dxs = carry
                t = T - 1 - sigma               # mirrored fwd step
                m = jnp.clip(t - d, 0, M - 1)
                valid = jnp.logical_and(t - d >= 0, t - d < M)
                last = d == pp - 1
                # incoming output-cotangent: the last stage reads the
                # pipeline output's rows; others receive from stage d+1
                g_in = jnp.where(
                    last,
                    jax.lax.dynamic_index_in_dim(g_outs, m, 0,
                                                 keepdims=False),
                    recv_g)
                g_aux = jax.lax.dynamic_index_in_dim(g_auxs, m, 0,
                                                     keepdims=False)
                inp = jax.lax.dynamic_index_in_dim(stash, m, 0,
                                                   keepdims=False)
                pos = jax.lax.dynamic_index_in_dim(ps, m, 0, keepdims=False)
                # recompute THIS microbatch's stage and transpose it — the
                # only per-layer activations live at any step
                _, vjp_fn = jax.vjp(
                    lambda p, i: stage_fn(p, i, pos), layer_params, inp)
                dp, dinp = vjp_fn((g_in, g_aux))
                # jnp.where masking (not *0): a non-finite value from a
                # bubble-step recompute on garbage ring inputs must not
                # poison the accumulators via inf*0 = NaN.  float0
                # cotangents (int leaves) skip accumulation entirely.
                grads = jax.tree.map(
                    lambda a, b, fl: (
                        a + jnp.where(valid, b,
                                      jnp.zeros_like(b)).astype(a.dtype)
                        if fl else a),
                    grads, dp, inexact)
                # stream the input-cotangent to the previous stage; stage 0
                # owns the batch cotangent
                dinp = jnp.where(valid, dinp, jnp.zeros_like(dinp))
                cur = jax.lax.dynamic_index_in_dim(dxs, m, 0, keepdims=False)
                dxs = jax.lax.dynamic_update_index_in_dim(
                    dxs, jnp.where(jnp.logical_and(valid, d == 0),
                                   dinp.astype(dxs.dtype), cur), m, 0)
                recv_gn = jax.lax.ppermute(dinp, axis_name, rperm)
                return (recv_gn, grads, dxs), None

            (_, grads, dxs), _ = jax.lax.scan(
                step, (gz0, grads0, dxs0), jnp.arange(T))
            # int primals take float0 cotangents (a zero-sized numpy array
            # is the canonical symbolic zero) — returning jnp.zeros_like(ps)
            # happens to typecheck on some JAX versions but is fragile
            grads = jax.tree.map(
                lambda p, g_, fl: (g_ if fl else
                                   np.zeros(p.shape,
                                            dtype=jax.dtypes.float0)),
                layer_params, grads, inexact)
            return grads, dxs, np.zeros(ps.shape, dtype=jax.dtypes.float0)

        pipe.defvjp(pipe_fwd, pipe_bwd)

        outs, auxs = pipe(layer_params, xs, ps)
        d = jax.lax.axis_index(axis_name)
        # only the last stage's rows are the pipeline's real outputs; aux is
        # per-stage-owned here (not streamed through the pipe), so it sums
        # across ALL stages
        y = _bcast_last(outs, d)
        aux_sum = jax.lax.psum(jnp.sum(auxs), axis_name)
        return y.astype(x.dtype).reshape(x.shape), aux_sum

    def local(layer_params, x, positions):
        x, xs, ps = _split(x, positions)
        d = jax.lax.axis_index(axis_name)

        recv0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        aux0 = jnp.zeros((M,), jnp.float32)
        recv_aux0 = jnp.zeros((), jnp.float32)

        def step(carry, t):
            recv, recv_aux, outs, auxs = carry
            m = jnp.clip(t - d, 0, M - 1)
            valid = jnp.logical_and(t - d >= 0, t - d < M)
            first = d == 0
            inp = jnp.where(first, jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), 0, keepdims=False), recv)
            pos = jax.lax.dynamic_index_in_dim(ps, m, 0, keepdims=False)
            aux_in = jnp.where(first, 0.0, recv_aux)
            out, aux = stage_fn(layer_params, inp, pos)
            aux = aux_in + aux
            # collect on (what will be masked to) the last stage
            cur = jax.lax.dynamic_index_in_dim(outs, m, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, out, cur), m, 0)
            auxs = jax.lax.dynamic_update_index_in_dim(
                auxs, jnp.where(valid, aux, auxs[m]), m, 0)
            # stream to next stage
            recv_n = jax.lax.ppermute(out, axis_name, perm)
            recv_aux_n = jax.lax.ppermute(aux, axis_name, perm)
            return (recv_n, recv_aux_n, outs, auxs), None

        (_, _, outs, auxs), _ = jax.lax.scan(
            step, (recv0, recv_aux0, outs0, aux0), jnp.arange(T))

        # only the last stage's buffers are the real outputs
        y = _bcast_last(outs, d)
        aux_sum = _bcast_last(jnp.sum(auxs), d)
        return y.astype(x.dtype).reshape(x.shape), aux_sum

    pspec = jax.tree.map(
        lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))), layer_params)
    # manual only over pp; the batch dim keeps its dp sharding (auto axes)
    fn = local_1f1b if schedule == "1f1b" else local
    y, aux = shard_map(
        fn, mesh=topo.mesh, axis_names={axis_name},
        in_specs=(pspec, P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )(layer_params, x.astype(jnp.float32), positions)
    return y.astype(in_dtype), aux
