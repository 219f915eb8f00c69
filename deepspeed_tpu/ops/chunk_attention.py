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
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
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


def _span(meta_ref, r, t, bq: int, bk: int, window):
    """(first key tile, last key tile, whether any query is real) of query
    tile `t` of row `r`."""
    pos0, n_valid = meta_ref[r, 0], meta_ref[r, 1]
    lo = pos0 + t * bq
    hi = pos0 + jnp.minimum((t + 1) * bq, n_valid) - 1
    real = t * bq < n_valid
    first = 0 if window is None else jnp.maximum(lo - window + 1, 0) // bk
    last = jnp.where(real, jnp.maximum(hi, 0) // bk, first)
    return first, last, real


def _kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            bq: int, bk: int, groups: int, sm_scale: float, window):
    # q_ref/o_ref [1, 1, G, bq, D]; k_ref/v_ref [1, 1, bk, D]; scratch m/l
    # [G * bq, 128], acc [G * bq, D] float32
    r, t, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first, last, real = _span(meta_ref, r, t, bq, bk, window)
    D = q_ref.shape[-1]
    rows = groups * bq

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(jnp.logical_and(real, first + j <= last))
    def _compute():
        q = q_ref[0, 0].reshape(rows, D)
        k, v = k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
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
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no key seen yet keeps m at NEG_INF: exp(0) must not count
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = jnp.broadcast_to(
            alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_s.shape)
        acc_s[...] = acc_s[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-30)       # a padded query: zeros
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype).reshape(
            groups, bq, D)


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
