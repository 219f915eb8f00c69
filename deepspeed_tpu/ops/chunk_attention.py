"""Pallas TPU attention of prompt chunks over their rows' keys, with a
static sliding window: the prefill kernel of the static-kind stack
(`inference/v2/hybrid_ops.py`).

A row hands over `C` queries standing at positions `pos0 .. pos0 + C - 1`
(`n_valid` of them real) and a buffer of its keys and values indexed BY
POSITION: what the cache holds of the row's past, gathered through its
block table, with the chunk's own keys laid in at `pos0`.  A fresh prompt
is the case `pos0 = 0`, where the buffer is the chunk itself.  Query `i`
sees the keys at positions `p <= pos0 + i`, and with a window `W` only
those with `p > pos0 + i - W`.

The grid is (row, kv head, query tile, key step).  A query tile is `bq`
queries with all the `G` query heads of one kv head as the rows of one
matmul (`G * bq` rows against `bk` keys, operands in the cache's dtype,
float32 accumulation and online softmax).  The key steps of a query tile
run from the tile that holds its first visible key to the one that holds
its last; both follow from `pos0`, `n_valid` and the window, which ride
the grid as scalar-prefetch operands, so the index map of K and V reads
them: a step past the tile's last visible key keeps the index of that
tile, the pipeline sees no change and copies nothing, and the step
computes nothing.  With a window the key steps are a constant few
(`(W + bq) / bk + 1`) however long the row; without one they are the
buffer's tiles, of which a causal tile uses those up to its diagonal.
A query tile past `n_valid` costs its steps' fixed overhead only.

A live step is one of two kinds (`step_kind`, the one rule the kernel and
the host's `count_steps` share).  INTERIOR: every key of the step is
visible to every query of the tile, padded ones included: the step's last
key is at or before the tile's FIRST query and, with a window, its first
key is after the tile's LAST query less the window.  Of a tile's steps
only the one on its diagonal and the one or two on the window's lower edge
are not.  EDGE: every other live step.  The edge body masks the scores;
the interior body has no iota, compare or select.  A select whose
condition is all true is the identity, so both bodies give the same bits.

The running max and sum of the online softmax are kept LANE-REPLICATED
(`[rows, 128]` scratch, every lane of a row the same number) and used
that way: `[rows, 1]` columns cut out of them had to be broadcast back
over the lanes at every use (four cross-lane permutes a row group a step,
each a round trip through the XLU in the middle of the step's dependency
chain: they, not the mask, were two thirds of a live step on a v5e).  The
same scalar arithmetic on every lane: the bits do not move.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunk_attention", "chunk_attention_reference", "key_tile"]

NEG_INF = -1e30
# keys a grid step takes; the buffer's length is a multiple of it
KEY_TILE = 512
# rows (queries x the query heads of a kv head) of a step's matmul
ROWS_PER_STEP = 1024


def key_tile(n_keys: int) -> int:
    """The key tile of a buffer that has to hold `n_keys` positions: the
    caller pads its buffer to a multiple of it."""
    return min(KEY_TILE, -(-n_keys // 8) * 8)


def chunk_attention_reference(q, k, v, pos0, n_valid,
                              window: Optional[int] = None):
    """The same mathematics, dense (the CPU path and the tests' yardstick).

    q [R, C, NH, D]; k, v [R, T, NKV, D] by position; pos0, n_valid [R].
    Returns [R, C, NH, D] in q.dtype, zeros at padded queries."""
    R, C, NH, D = q.shape
    T, NKV = k.shape[1], k.shape[2]
    if NKV != NH:
        k = jnp.repeat(k, NH // NKV, axis=2)
        v = jnp.repeat(v, NH // NKV, axis=2)
    s = jnp.einsum("rcnd,rtnd->rnct", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    q_pos = pos0[:, None] + jnp.arange(C)[None]                  # [R, C]
    key_pos = jnp.arange(T)[None, None, :]
    seen = key_pos <= q_pos[:, :, None]
    if window is not None:
        seen &= key_pos > q_pos[:, :, None] - window
    p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
    out = jnp.einsum("rnct,rtnd->rcnd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    real = jnp.arange(C)[None] < n_valid[:, None]
    return jnp.where(real[:, :, None, None], out, 0.0).astype(q.dtype)


def _tiles(pos0, n_valid, t, bq: int, bk: int, window, xp=jnp):
    """(first key tile, last key tile, whether any query is real) of query
    tile `t` of a row whose chunk stands at `pos0` with `n_valid` real
    queries."""
    lo = pos0 + t * bq
    hi = pos0 + xp.minimum((t + 1) * bq, n_valid) - 1
    real = t * bq < n_valid
    first = 0 if window is None else xp.maximum(lo - window + 1, 0) // bk
    last = xp.where(real, xp.maximum(hi, 0) // bk, first)
    return first, last, real


def step_kind(pos0, n_valid, t, j, *, bq: int, bk: int, window, xp=jnp):
    """(live, interior) of key step `j` of query tile `t` of a row: the one
    rule the kernel's two bodies and the host's count (`count_steps`)
    follow.  Integers in (traced scalars with `xp=jnp`, numpy arrays that
    broadcast with `xp=np`), booleans out.

    live: the tile has a real query and the step's key tile lies between
    the tile's first and last (`_tiles`).  interior: live, and every key of
    the step is visible to EVERY query of the tile, padded ones too: the
    step's last key is at or before the tile's first query, and with a
    window its first key is after the tile's last query less the window."""
    first, last, real = _tiles(pos0, n_valid, t, bq, bk, window, xp)
    live = real & (first + j <= last)
    key_lo = (first + j) * bk
    interior = key_lo + bk - 1 <= pos0 + t * bq
    if window is not None:
        interior &= key_lo > pos0 + (t + 1) * bq - 1 - window
    return live, live & interior


def count_steps(pos0, n_valid, C: int, groups: int, bk: int, window):
    """(live, masked) key steps of one kv head of `chunk_attention` over
    chunk slots at `pos0` with `n_valid` real queries of `C` (numpy, on
    the host): the steps that compute, and of those the ones an edge
    crosses, which pay for the mask.  Their ratio says how often the
    mask-free body runs."""
    bq = _query_tile(C, groups)
    pos0 = np.asarray(pos0, np.int64).reshape(-1, 1, 1)  # dstpu: noqa[DST001] the planner's host array of chunk starts
    n_valid = np.asarray(n_valid, np.int64).reshape(-1, 1, 1)  # dstpu: noqa[DST001] the planner's host array of chunk lengths
    t = np.arange(C // bq).reshape(1, -1, 1)
    # (a tile's steps end at its diagonal: no row needs more than these)
    last = int(pos0.max(initial=0)) + C  # dstpu: noqa[DST001] numpy on the host
    j = np.arange(last // bk + 1).reshape(1, 1, -1)
    live, interior = step_kind(pos0, n_valid, t, j, bq=bq, bk=bk,
                               window=window, xp=np)
    live, interior = live.sum(), interior.sum()
    return int(live), int(live - interior)  # dstpu: noqa[DST001] numpy on the host


def _lanes(x, n: int):
    """A lane-replicated `[rows, 128]` array at `n` lanes: whole vregs
    again, no broadcast of a column."""
    reps = -(-n // 128)
    x = pltpu.repeat(x, reps, axis=1) if reps > 1 else x
    return x[:, :n] if n % 128 else x


def _span(meta_ref, r, t, bq: int, bk: int, window):
    """`_tiles` of query tile `t` of row `r`."""
    return _tiles(meta_ref[r, 0], meta_ref[r, 1], t, bq, bk, window)


def _kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            bq: int, bk: int, groups: int, sm_scale: float, window):
    # q_ref/o_ref [1, 1, G, bq, D]; k_ref/v_ref [1, 1, bk, D]; scratch m/l
    # [G * bq, 128], acc [G * bq, D] float32
    r, t, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first, _, _ = _span(meta_ref, r, t, bq, bk, window)
    live, interior = step_kind(meta_ref[r, 0], meta_ref[r, 1], t, j, bq=bq,
                               bk=bk, window=window)
    D = q_ref.shape[-1]
    rows = groups * bq

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def step(masked: bool):
        q = q_ref[0, 0].reshape(rows, D)
        k, v = k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if masked:
            # row g * bq + i is query i of the tile, whatever its head
            q_pos = (meta_ref[r, 0] + t * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (groups, bq, bk), 1)
                     ).reshape(rows, bk)
            key_pos = (first + j) * bk + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bk), 1)
            seen = key_pos <= q_pos
            if window is not None:
                seen &= key_pos > q_pos - window
            s = jnp.where(seen, s, NEG_INF)
        # the running max and sum live lane-replicated ([rows, 128]): whole
        # vregs at every use, no lane broadcast of a [rows, 1] column
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        if masked:
            # a row with no key seen yet keeps m at NEG_INF: exp(0) must
            # not count
            p = jnp.where(seen, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * _lanes(alpha, D) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    # a select whose condition is all true is the identity: the interior
    # body leaves the mask's iotas, compares and selects out and gives the
    # bits the masked one would
    pl.when(interior)(functools.partial(step, False))
    pl.when(live & ~interior)(functools.partial(step, True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-30)        # a padded query: zeros
        o_ref[0, 0] = (acc_s[...] / _lanes(l, D)).astype(
            o_ref.dtype).reshape(groups, bq, D)


def _query_tile(C: int, groups: int) -> int:
    """Queries of a grid step: the largest power of two dividing C that
    keeps the step's matmul within `ROWS_PER_STEP` rows (at least 8)."""
    bq = 8
    while bq * 2 * groups <= ROWS_PER_STEP and C % (bq * 2) == 0:
        bq *= 2
    return bq


def chunk_attention(q, k, v, pos0, n_valid, window: Optional[int] = None,
                    interpret: bool = False):
    """Fused chunk attention (see the module docstring); shapes as in
    `chunk_attention_reference`, with C a multiple of 8 and the buffer's
    length a multiple of `key_tile` of it."""
    R, C, NH, D = q.shape
    T, NKV = k.shape[1], k.shape[2]
    G = NH // NKV
    bq, bk = _query_tile(C, G), key_tile(T)
    if C % 8 or T % bk:
        raise ValueError(
            f"chunk_attention needs whole tiles: {C} queries in tiles of 8, "
            f"{T} keys in tiles of {bk}")
    steps = T // bk if window is None \
        else min(T // bk, (window + bq - 2) // bk + 2)
    meta = jnp.stack([jnp.asarray(pos0, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)], axis=1)    # [R, 2]
    # [R, NKV, G, C, D] and [R, NKV, T, D]: a kv head's rows together
    qh = q.reshape(R, C, NKV, G, D).transpose(0, 2, 3, 1, 4)
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def kv_map(r, h, t, j, meta):
        first, last, _ = _span(meta, r, t, bq, bk, window)
        return (r, h, jnp.minimum(first + j, last), 0)

    q_map = lambda r, h, t, j, meta: (r, h, 0, t, 0)        # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, NKV, C // bq, steps),
        in_specs=[pl.BlockSpec((1, 1, G, bq, D), q_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map),
                  pl.BlockSpec((1, 1, bk, D), kv_map)],
        out_specs=pl.BlockSpec((1, 1, G, bq, D), q_map),
        scratch_shapes=[pltpu.VMEM((G * bq, 128), jnp.float32),
                        pltpu.VMEM((G * bq, 128), jnp.float32),
                        pltpu.VMEM((G * bq, D), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, groups=G,
                          sm_scale=1.0 / math.sqrt(D), window=window),
        name="chunk_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(meta, qh, kh, vh)
    return out.transpose(0, 3, 1, 2, 4).reshape(R, C, NH, D)
