"""Pallas TPU paged kernel for latent (MLA) attention, absorbed form.

A row's queries attend that row's cached latents, which lie in blocks
scattered through the shared arena `[A, nb, bs, W]` (A = attention index; a
row of it is `[c | rope(kr) | unused]`, W = kv_lora_rank + rope width
rounded up to whole 128-lane tiles; it is shared by every head, and `c` is
the value as well as the larger part of the key).  With the up-projection
absorbed into the query (`q_abs[h] = W_kvb,K,h^T q_nope[h]`) the kernel
computes, per query and head,

    s = (q_abs . c + q_rope . kr) * sm_scale ;  out = softmax(s) @ c

so a cached row is read and never decompressed; the caller applies
`W_kvb,V` to `out`.  Query i of row b stands at position `pos0[b] + i` and
sees the keys at positions up to its own; `n_valid[b]` of the row's Q
queries are real (decode: Q = 1; a prefill chunk: Q = its width).

As in `ops/paged_attention.py` the block table rides the grid as a
scalar-prefetch operand and the arena's BlockSpec index maps read it.
What the shape forces (64 or 128 heads against ONE 576-wide key, thousands
of tokens of context):

- a grid step takes a tile of queries with all their heads as the rows of
  one matmul (decode: one query's 64 or 128 rows; a chunk: as many queries
  as give 512 rows, 8 x 64 heads or 4 x 128: `queries_per_step`), against
  several arena blocks at once, each its own in_spec, joined in VMEM into
  one tile of keys: a (row, block) grid of 96 x 33 steps with 64 x 64
  products costs ten times what the bytes take to read;
- blocks past a tile's last visible key map to its last live block, so the
  pipeline sees an unchanged index and issues no copy: dead table entries
  cost neither bytes nor compute;
- matmul operands stay in the cache's dtype (bf16 on the chip), with
  float32 accumulation and a float32 online softmax.

A padded query (and every query of a row with `n_valid` 0) gives zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_paged_attention", "mla_paged_reference",
           "queries_per_step"]

NEG_INF = -1e30
# a grid step's tile: arena blocks joined into one tile of keys (a table
# of MB blocks takes ceil(MB / 8) steps of equal size: 33 blocks, 5 steps
# of 7), and queries whose heads are one matmul's rows
BLOCKS_PER_STEP = 8
ROWS_PER_STEP = 512


def queries_per_step(heads: int) -> int:
    """Queries of a chunk a grid step takes: with all their heads they are
    the rows of its matmuls and of its float32 scores and accumulator in
    VMEM, `ROWS_PER_STEP` at most (8 at up to 64 heads, 4 at 128), never
    more than 8."""
    return max(1, min(8, ROWS_PER_STEP // heads))


def mla_paged_reference(q_abs, q_rope, arena, block_tables, pos0, n_valid,
                        index, sm_scale: float):
    """Dense-gather form of the same mathematics (the CPU tests' path).

    q_abs [B, Q, NH, R], q_rope [B, Q, NH, Dr]; arena [A, nb, bs, W >= R +
    Dr]; block_tables [B, MB]; pos0, n_valid [B]; index: which attention of
    the arena.  Returns [B, Q, NH, R] in q_abs.dtype."""
    B, Q, NH, R = q_abs.shape
    _, nb, bs, W = arena.shape
    MB = block_tables.shape[1]
    rows = jnp.take(arena[index], jnp.clip(block_tables, 0, nb - 1),
                    axis=0).reshape(B, MB * bs, W)
    c, kr = rows[..., :R], rows[..., R:R + q_rope.shape[-1]]
    s = (jnp.einsum("bqnr,bmr->bqnm", q_abs, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqnd,bmd->bqnm", q_rope, kr,
                      preferred_element_type=jnp.float32)) * sm_scale
    q_pos = pos0[:, None] + jnp.arange(Q)[None]                     # [B, Q]
    seen = jnp.arange(MB * bs)[None, None, :] <= q_pos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG_INF), axis=-1)
    out = jnp.einsum("bqnm,bmr->bqnr", p.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)
    real = jnp.arange(Q)[None] < n_valid[:, None]
    return jnp.where(real[:, :, None, None], out, 0.0).astype(q_abs.dtype)


def _tile_last(pos0, n_valid, t, tq):
    """Position of the last real query of query tile `t` (tiles of `tq`),
    or -1 when the tile holds none."""
    real = jnp.clip(n_valid - t * tq, 0, tq)
    return jnp.where(real > 0, pos0 + t * tq + real - 1, -1)


def _kernel(idx_ref, tables_ref, pos0_ref, nv_ref, qa_ref, qr_ref, *refs,
            bs: int, rank: int, heads: int, tq: int, per_step: int,
            sm_scale: float):
    # qa_ref [1, tq*heads, R], qr_ref [1, tq*heads, Dr]; per_step cache
    # refs [1, 1, bs, W]; o_ref like qa_ref; scratch m/l [tq*heads, 128],
    # acc [tq*heads, R]
    cache_refs, (o_ref, m_s, l_s, acc_s) = refs[:per_step], refs[per_step:]
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pos0, n_valid = pos0_ref[b], nv_ref[b]
    first = j * per_step * bs                      # this step's first key

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(first <= _tile_last(pos0, n_valid, t, tq))
    def _keys():
        rows = jnp.concatenate([ref[0, 0] for ref in cache_refs], axis=0)
        c, kr = rows[:, :rank], rows[:, rank:rank + qr_ref.shape[2]]
        nt = (((1,), (1,)), ((), ()))               # contract minor dims
        s = (jax.lax.dot_general(qa_ref[0], c, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr, nt,
                                   preferred_element_type=jnp.float32)
             ) * sm_scale                           # [tq*heads, keys]
        query = t * tq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // heads
        key_pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = (key_pos <= pos0 + query) & (query < n_valid)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_s.shape)
        acc_s[:] = acc_s[:] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-9)       # a padded query: zeros
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)


def mla_paged_attention(q_abs, q_rope, arena, block_tables, pos0, n_valid,
                        index, sm_scale: float):
    """Fused paged latent attention (module docstring); shapes as
    `mla_paged_reference`.  `index` may be traced (the layer scan's)."""
    B, Q, NH, R = q_abs.shape
    Dr = q_rope.shape[-1]
    _, nb, bs, W = arena.shape
    MB = block_tables.shape[1]
    steps = -(-MB // BLOCKS_PER_STEP)
    per_step = -(-MB // steps)
    tq = min(queries_per_step(NH), Q)
    if Q % tq:
        raise ValueError(f"{Q} queries a row are not whole tiles of {tq}")
    tables = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    scalars = (jnp.asarray(index, jnp.int32).reshape(1), tables,
               pos0.astype(jnp.int32), n_valid.astype(jnp.int32))

    def cache_map(i):
        def index_map(b, t, j, idx, tb, p0, nv):
            last = jnp.clip(_tile_last(p0[b], nv[b], t, tq) // bs, 0, MB - 1)
            return (idx[0], tb[b, jnp.minimum(j * per_step + i, last)], 0, 0)
        return index_map

    q_map = lambda b, t, j, idx, tb, p0, nv: (b, t, 0)      # noqa: E731
    rows = tq * NH
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Q // tq, steps),
        in_specs=[pl.BlockSpec((1, rows, R), q_map),
                  pl.BlockSpec((1, rows, Dr), q_map)]
        + [pl.BlockSpec((1, 1, bs, W), cache_map(i))
           for i in range(per_step)],
        out_specs=pl.BlockSpec((1, rows, R), q_map),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, R), jnp.float32)])
    kernel = functools.partial(_kernel, bs=bs, rank=R, heads=NH, tq=tq,
                               per_step=per_step, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel, name="mla_paged_attention", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q * NH, R), q_abs.dtype),
    )(*scalars, q_abs.reshape(B, Q * NH, R), q_rope.reshape(B, Q * NH, Dr),
      *([arena] * per_step))
    return out.reshape(B, Q, NH, R)
