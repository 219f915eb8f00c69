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

The kernel's time follows the batch's LIVE key tiles, as
`ops/paged_attention.py`'s does (what the shape forces: 64 or 128 heads
against ONE 576-wide key, thousands of tokens of context):

- a grid step takes a tile of queries with all their heads as the rows of
  one matmul (decode: one query's 64 or 128 rows; a chunk: as many queries
  as give 512 rows, 8 x 64 heads or 4 x 128: `queries_per_step`), against
  a tile of keys: `BLOCKS_PER_STEP` consecutive table entries of the row,
  each its own in_spec, joined in VMEM.  The tile follows from the static
  shapes alone (a narrower table only caps it), so tables of 10 and of 33
  entries walk a row alike and give the same bits; a (row, block) grid of
  96 x 33 steps with 64 x 64 products costs ten times what the bytes take
  to read;
- the grid is ONE dimension over the live (row, query tile, key tile)
  items: a key tile is an item only where it holds a key that some real
  query of the query tile sees.  `live_tiles` lists them in row order (a
  handful of integer ops, one running maximum and one sort, under the
  scope `mla_paged_walk`); the list depends on the step's tables and
  positions only, so a program makes it once, outside its layer scan, for
  all its attentions.  It rides the grid as scalar-prefetch operands and
  its length is the grid's (dynamic) size: a table entry past a query
  tile's last visible key, a row without a query and a query tile without
  a real one are no grid step at all, whatever the table's width;
- the index maps READ the list: `blocks[slot * N + i]` for the arena,
  `groups[i]` for the queries and the output.  A slot of a row's last key
  tile past its last live block keeps the index it had an item earlier,
  so the pipeline sees no change and issues no copy: a dead table entry
  costs neither a step, nor index arithmetic, nor bytes, nor products
  that count (its keys are masked);
- matmul operands stay in the cache's dtype (bf16 on the chip), with
  float32 accumulation and a float32 online softmax.

A padded query beside real ones gives zeros (nothing is summed for it).  A
query tile without a real query is no item, so NOTHING writes its rows of
the output: the caller zeroes them where its next op carries the select
for nothing (`latent_ops._attend_absorbed`: in the value up-projection's
epilogue; a select pass of its own over a decode step's 6 MB of output is
45,000 cycles an attention by the compiler's estimate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_paged_attention", "mla_paged_reference", "live_tiles",
           "queries_per_step"]

NEG_INF = -1e30
# a grid step's tile: arena blocks joined into one tile of keys (fewer only
# under a narrower table), and queries whose heads are one matmul's rows
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


@jax.named_scope("mla_paged_walk")
def live_tiles(block_tables, pos0, n_valid, queries: int, heads: int,
               nb: int, bs: int):
    """The list a call's grid walks: a function of the step's tables and
    positions alone, so one list serves every attention of a program.

    A group is a query tile of a row (G = B * queries // tq of them, row
    major); an item is a key tile of a group that holds a key some real
    query of the group sees: the static (group, key tile) grid's live
    steps, in its order, the dead ones dropped.  Returns (count, groups
    [N], tiles [N], blocks [per_step * N], first [G], real [G]) int32: item
    i < count is key tile `tiles[i]` of group `groups[i]`, whose slot s
    reads arena block `blocks[s * N + i]`, the table's entry where the
    group sees a key of it, else the block the slot held an item earlier
    (an unchanged index: no copy); a group's first query stands at
    `first[g]` and `real[g]` of its queries are real."""
    B, MB = block_tables.shape
    tq = min(queries_per_step(heads), queries)
    if queries % tq:
        raise ValueError(
            f"{queries} queries a row are not whole tiles of {tq}")
    QT, per_step = queries // tq, min(MB, BLOCKS_PER_STEP)
    T = -(-MB // per_step)
    N = B * QT * T
    at = jnp.arange(QT, dtype=jnp.int32)[None] * tq
    first = (pos0.astype(jnp.int32)[:, None] + at).reshape(-1)
    real = jnp.clip(n_valid.astype(jnp.int32)[:, None] - at, 0,
                    tq).reshape(-1)
    n_blocks = jnp.where(
        real > 0, (jnp.minimum(first + real, MB * bs) - 1) // bs + 1, 0)
    entry = jnp.arange(T * per_step, dtype=jnp.int32).reshape(T, per_step)
    live = (entry[None] < n_blocks[:, None, None]).reshape(N, per_step)
    table = jnp.pad(jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32),
                    ((0, 0), (0, T * per_step - MB)))
    table = jnp.broadcast_to(table.reshape(B, 1, T, per_step),
                             (B, QT, T, per_step)).reshape(N, per_step)
    # a dead slot keeps what the last live one before it held: a running
    # maximum over (item, block) packed into one word
    item = jnp.arange(N, dtype=jnp.int32)
    shift = (nb - 1).bit_length()
    if N << shift >= 1 << 31:
        raise ValueError(f"{N} grid steps over {nb} blocks do not pack")
    held = jax.lax.cummax(
        jnp.where(live, (item[:, None] << shift) | table, 0), axis=0)
    # the live items (their first slot is) in front, in the grid's order;
    # the places behind them, which no step reads, name the last grid step
    order, *held = jax.lax.sort(
        (jnp.where(live[:, 0], item, N),
         *(held[:, slot] & ((1 << shift) - 1) for slot in range(per_step))),
        num_keys=1)
    order = jnp.minimum(order, N - 1)
    return (jnp.sum(live[:, 0], dtype=jnp.int32), order // T, order % T,
            jnp.concatenate(held), first, real)


def _kernel(idx_ref, groups_ref, tiles_ref, blocks_ref, first_ref, real_ref,
            qa_ref, qr_ref, *refs, bs: int, rank: int, heads: int,
            per_step: int, table_keys: int, sm_scale: float):
    # qa_ref [1, tq*heads, R], qr_ref [1, tq*heads, Dr]; per_step cache
    # refs [1, 1, bs, W]; o_ref like qa_ref; scratch m/l [tq*heads, 128],
    # acc [tq*heads, R]
    cache_refs, (o_ref, m_s, l_s, acc_s) = refs[:per_step], refs[per_step:]
    i = pl.program_id(0)
    group, tile = groups_ref[i], tiles_ref[i]
    pos0, n_valid = first_ref[group], real_ref[group]   # of the query tile
    first = tile * per_step * bs                   # this item's first key

    @pl.when(tile == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    rows = jnp.concatenate([ref[0, 0] for ref in cache_refs], axis=0)
    c, kr = rows[:, :rank], rows[:, rank:rank + qr_ref.shape[2]]
    nt = (((1,), (1,)), ((), ()))                   # contract minor dims
    s = (jax.lax.dot_general(qa_ref[0], c, nt,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr_ref[0], kr, nt,
                               preferred_element_type=jnp.float32)
         ) * sm_scale                               # [tq*heads, keys]
    query = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads
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

    # the group's last item: the one that holds its last real query's key
    @pl.when(first + per_step * bs
             > jnp.minimum(pos0 + n_valid, table_keys) - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-9)       # a padded query: zeros
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)


def mla_paged_attention(q_abs, q_rope, arena, block_tables, pos0, n_valid,
                        index, sm_scale: float, tiles=None):
    """Fused paged latent attention (module docstring); shapes as
    `mla_paged_reference`, but the rows of a query tile without a real
    query are not written.  `index` may be traced (the layer scan's).
    `tiles`: `live_tiles` of the same tables, positions and shapes, where
    a program's attentions share one list."""
    B, Q, NH, R = q_abs.shape
    Dr = q_rope.shape[-1]
    _, nb, bs, W = arena.shape
    MB = block_tables.shape[1]
    if tiles is None:
        tiles = live_tiles(block_tables, pos0, n_valid, Q, NH, nb, bs)
    count, *lists = tiles
    per_step = min(MB, BLOCKS_PER_STEP)
    N, G = lists[0].shape[0], lists[-1].shape[0]    # items, query tiles
    rows = B * Q * NH // G                  # a query tile's, heads and all

    def cache_map(slot):
        def index_map(i, idx, groups, tiles, blocks, *_):
            return (idx[0], blocks[slot * N + i], 0, 0)
        return index_map

    q_map = lambda i, idx, groups, *_: (groups[i], 0, 0)     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(count,),
        in_specs=[pl.BlockSpec((1, rows, R), q_map),
                  pl.BlockSpec((1, rows, Dr), q_map)]
        + [pl.BlockSpec((1, 1, bs, W), cache_map(slot))
           for slot in range(per_step)],
        out_specs=pl.BlockSpec((1, rows, R), q_map),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, R), jnp.float32)])
    kernel = functools.partial(_kernel, bs=bs, rank=R, heads=NH,
                               per_step=per_step, table_keys=MB * bs,
                               sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel, name="mla_paged_attention", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, rows, R), q_abs.dtype),
    )(jnp.asarray(index, jnp.int32).reshape(1), *lists,
      q_abs.reshape(G, rows, R), q_rope.reshape(G, rows, Dr),
      *([arena] * per_step))
    return out.reshape(B, Q, NH, R)
