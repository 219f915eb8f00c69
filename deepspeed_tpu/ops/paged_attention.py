"""Pallas TPU paged-attention decode kernel.

Replaces the reference's blocked-flash decode kernels over a paged KV cache
(inference/v2/kernels/ragged_ops/blocked_flash/ — flash attention walking a
block table; also the fused softmax_context decode path of
csrc/transformer/inference/pt_binding.cpp).

One query token per sequence attends to that sequence's KV blocks scattered
through the shared arena.  The kernel's time follows the LIVE blocks of the
batch, not rows x table width:

- the grid is ONE dimension over the batch's live tiles.  A tile is
  `per_step` consecutive table entries of one row (about 512 keys: 8
  blocks of 64; `_blocks_per_step` derives it from the static shapes).  A
  row of `n` live blocks (`len // bs + 1`) is `ceil(n / per_step)` tiles,
  an inactive row none.  The wrapper lists the tiles in row order
  (`_walk`: a handful of integer ops on [B] and [B * steps] vectors, the
  same for every layer) and the list rides the grid as scalar-prefetch
  operands; its length is the grid's (dynamic) size, so a table entry
  past a row's last live block is no grid step at all, whatever the
  table's width;
- each of a tile's `per_step` blocks is its own in_spec, whose index map
  reads the list: the pipeline has the next tile's blocks in flight,
  across rows, while this tile computes.  A slot of a row's last tile
  past its last live block keeps the index it had a tile earlier, so the
  pipeline sees no change and issues no copy: a dead table entry costs
  neither a step, nor bytes, nor compute (its keys are masked);
- inside a tile there is no float32 copy of K or V and no transpose.  The
  blocks are read as `[bs * NKV, D]` rows (key-major, kv head minor) and
  joined into one `[keys * NKV, D]` operand in the cache's dtype; every
  query head is scored against every (key, kv head) row in one matmul
  with float32 accumulation, and the columns of another kv head's rows
  are masked with the keys past the row's length.  That is NKV times the
  products the group structure needs, on an MXU with nothing else to do:
  decode is bound by the bytes.  Where the arena's `[bs, NKV, D]` block
  and `[bs * NKV, D]` are the same bytes in HBM (`_rows_view_is_free`)
  the wrapper hands the arena over in that shape, a bitcast; elsewhere
  the kernel reshapes the block in VMEM.  The online softmax (`m`, `l`,
  `acc`) is float32; `p` goes to the cache's dtype for PV as in
  `paged_decode_reference`.

Masking: block j of a table holds key positions [j*bs, (j+1)*bs); keys with
position > lens[b] contribute exp(-inf) = 0.  lens[b] < 0 marks an inactive
(padded) row — output zeros.

A sliding window (`window`, static) moves the START of a row's walk: its
tiles are listed from table entry `max(0, lens - window + 1) // bs` on, so
a row past the window costs the window's blocks whatever its length, and
the table entries before them are never read (a cache that keeps only the
window's blocks leaves them dead).  Keys at or before `lens - window` in
the walk's first block are masked like the keys past the row's length.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_reference"]

NEG_INF = -1e30
# what a tile may take of the 16 MiB of VMEM a kernel gets on a v5e: its K
# and V blocks, double-buffered by the pipeline
_TILE_VMEM_BYTES = 8 << 20


def paged_decode_reference(q, arena_k, arena_v, block_tables, lens,
                           window=None):
    """Dense-gather reference (the ragged engine's fallback math).

    q: [B, NH, D]; arena_k/v: [nb, bs, NKV, D]; block_tables: [B, MB];
    lens: [B] current token position (inclusive key bound; <0 = inactive);
    `window`: keys at or before `lens - window` are masked.
    Returns [B, NH, D] in q.dtype.
    """
    B, NH, D = q.shape
    nb, bs, NKV, _ = arena_k.shape
    MB = block_tables.shape[1]
    kk = jnp.take(arena_k, block_tables, axis=0,
                  mode="clip").reshape(B, MB * bs, NKV, D)
    vv = jnp.take(arena_v, block_tables, axis=0,
                  mode="clip").reshape(B, MB * bs, NKV, D)
    if NKV != NH:
        kk = jnp.repeat(kk, NH // NKV, axis=2)
        vv = jnp.repeat(vv, NH // NKV, axis=2)
    s = jnp.einsum("bnd,bmnd->bnm", q, kk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    key_pos = jnp.arange(MB * bs)[None, None, :]
    seen = key_pos <= lens[:, None, None]
    if window is not None:
        seen &= key_pos > lens[:, None, None] - window
    s = jnp.where(seen, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnm,bmnd->bnd", p.astype(vv.dtype), vv)
    zero = (lens < 0)[:, None, None]
    return jnp.where(zero, 0.0, out).astype(q.dtype)


def _tile_rows(NKV: int, itemsize: int) -> int:
    """Rows of the TPU's HBM tile over a block's (NKV, D) minor dims: the
    smallest power of two that holds NKV, from one 32-bit sublane's worth
    of rows up to 8 (bfloat16: T(2,128)(2,1) to T(8,128)(2,1))."""
    rows = max(1, 4 // itemsize)
    while rows < min(NKV, 8):
        rows *= 2
    return rows


def _rows_view_is_free(NKV: int, D: int, itemsize: int) -> bool:
    """Whether an arena block `[bs, NKV, D]` and its rows `[bs * NKV, D]`
    are the same bytes in HBM, so that XLA's reshape is a bitcast: the
    head dim is one lane tile (a wider one is split into tiles that the
    two shapes order differently) and the kv heads fill their tile with
    no padding row.  `tests/test_tpu_compile.py` holds the compiler to it:
    a reshape that is not free would copy the arena."""
    return D <= 128 and NKV % _tile_rows(NKV, itemsize) == 0


def _block_vmem_bytes(bs: int, NKV: int, D: int, itemsize: int) -> int:
    """An arena block in VMEM, with the padding its (NKV, D) tile gives."""
    tile = _tile_rows(NKV, itemsize)
    return bs * (-(-NKV // tile) * tile) * (-(-D // 128) * 128) * itemsize


def _blocks_per_step(bs: int, NKV: int, D: int, itemsize: int,
                     MB: int) -> int:
    """Arena blocks joined into one grid step's tile, from the static
    shapes alone: about 512 keys, at most 8 blocks (each is an in_spec,
    and compile time follows their number), at most 4096 (key, kv head)
    rows of scores, K and V double-buffered inside `_TILE_VMEM_BYTES`.
    The table's width only caps it: tables of 10 and of 32 entries walk
    a row alike."""
    return max(1, min(MB, 8, 512 // bs, 4096 // (bs * NKV),
                      _TILE_VMEM_BYTES
                      // (4 * _block_vmem_bytes(bs, NKV, D, itemsize))))


@jax.named_scope("paged_attention_walk")
def _walk(tables, lens, bs: int, per_step: int, first=None):
    """The batch's live tiles in row order, for the grid to walk.

    tables [B, MB] (clipped), lens [B]; `first` [B] (a window's walk): the
    table entry a row's tiles start at.  Returns (count, rows [N], tiles
    [N], blocks [per_step * N]) int32, N = B * ceil(MB / per_step): item
    i < count is tile `tiles[i]` of row `rows[i]`, and slot s of it
    reads arena block `blocks[s * N + i]` — the table's entry where that
    is live, else the block the slot held an item earlier (no copy)."""
    B, MB = tables.shape
    N = B * -(-MB // per_step)
    n_blocks = jnp.where(lens >= 0, lens // bs + 1, 0)
    if first is not None:
        n_blocks = n_blocks - jnp.where(lens >= 0, first, 0)
    n_tiles = -(-n_blocks // per_step)
    ends = jnp.cumsum(n_tiles)
    item = jnp.arange(N, dtype=jnp.int32)
    rows = jnp.minimum(
        jnp.sum(ends[None, :] <= item[:, None], axis=1), B - 1)
    tiles = item - (ends - n_tiles)[rows]
    entry = tiles[:, None] * per_step + jnp.arange(per_step)[None]  # [N, P]
    live = (entry < n_blocks[rows][:, None]) & (item < ends[-1])[:, None]
    if first is not None:
        entry = entry + first[rows][:, None]
    held = jax.lax.cummax(jnp.where(live, item[:, None], -1), axis=0)
    blocks = jnp.take_along_axis(
        tables[rows[:, None], jnp.minimum(entry, MB - 1)],
        jnp.maximum(held, 0), axis=0)
    blocks = jnp.where(held >= 0, blocks, 0)
    i32 = lambda x: x.astype(jnp.int32)                      # noqa: E731
    return (i32(ends[-1]), i32(rows), i32(tiles),
            i32(blocks.T.reshape(-1)))


def _kernel(*refs, bs: int, per_step: int, kv_heads: int, groups: int,
            sm_scale: float, rows_view: bool, window=None):
    # scalars: rows [N], tiles [N], blocks [P*N], lens [B] (with a window
    # the walks' first table entries [B]; the layer index [1], if any).
    # q_ref/o_ref [1, NH, D]; col_key/col_head [1,
    # keys*NKV] int32 (the key within the tile and the kv head of a score
    # column); per_step K then V blocks, each [1(, 1), bs*NKV, D]
    # (`rows_view`) or [1(, 1), bs, NKV, D]; scratch m/l [NH, 128], acc
    # [NH, D] float32
    rows_ref, tiles_ref, _, lens_ref = refs[:4]
    first_ref = refs[4] if window is not None else None
    refs = refs[-(2 * per_step + 7):]
    q_ref, col_key_ref, col_head_ref = refs[:3]
    k_refs, v_refs = refs[3:3 + per_step], refs[3 + per_step:-4]
    o_ref, m_s, l_s, acc_s = refs[-4:]
    i = pl.program_id(0)
    tile = tiles_ref[i]
    length = lens_ref[rows_ref[i]]
    NH, D = q_ref.shape[1], q_ref.shape[2]
    keys = per_step * bs

    def position(n):
        """The position of the first key of tile `n` of this row's walk
        (computed where it is used: without a window the kernel's ops are
        the ones they were before it took one)."""
        if window is None:
            return n * keys
        return n * keys + first_ref[rows_ref[i]] * bs

    def joined(block_refs):
        """The tile's blocks as one [keys * NKV, D] operand."""
        if rows_view:
            blocks = [r[(0,) * (len(r.shape) - 2)] for r in block_refs]
        else:
            blocks = [r.reshape(bs * kv_heads, D)[...] for r in block_refs]
        return jnp.concatenate(blocks, axis=0)

    @pl.when(tile == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    k, v = joined(k_refs), joined(v_refs)
    nt = (((1,), (1,)), ((), ()))                   # contract minor dims
    s = jax.lax.dot_general(q_ref[0].astype(k.dtype), k, nt,
                            preferred_element_type=jnp.float32) * sm_scale
    head = jax.lax.broadcasted_iota(jnp.int32, (NH, 1), 0)
    first = col_head_ref[...] * groups           # a kv head's first q head
    live = ((position(tile) + col_key_ref[...] <= length)
            & (first <= head) & (head < first + groups))
    if window is not None:
        live &= position(tile) + col_key_ref[...] > length - window
    s = jnp.where(live, s, NEG_INF)                  # [NH, keys * NKV]
    m_prev = m_s[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = jnp.broadcast_to(
        alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_s.shape)
    acc_s[...] = acc_s[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(position(tile + 1) > length)            # the row's last tile
    def _finish():
        o_ref[0] = (acc_s[...] / l_s[:, :1]).astype(o_ref.dtype)


def paged_decode_attention(q, arena_k, arena_v, block_tables, lens,
                           layer_idx=None, window=None):
    """Fused paged decode attention (see module docstring).

    Shapes as in `paged_decode_reference`; block_tables entries may be
    garbage past a sequence's live blocks and, with a `window` (static),
    before the window's first block (never read).

    `layer_idx`: when given, arena_k/v keep their FULL [L, nb, bs, NKV, D]
    shape and the (traced) scalar layer index rides the grid as a scalar-
    prefetch operand consumed by the K/V index maps — no [nb, ...] layer
    slice is ever materialized in HBM (the copy that made the serving
    layer scan double-buffer the whole arena).  Merged [L, nb, bs, NKV*D]
    arenas are served by the packed-q variant in ops/paged_merged.py."""
    B, NH, D = q.shape
    layered = layer_idx is not None
    nb, bs, NKV, _ = arena_k.shape[-4:]
    MB = block_tables.shape[1]
    itemsize = jnp.dtype(arena_k.dtype).itemsize
    per_step = _blocks_per_step(bs, NKV, D, itemsize, MB)
    N = B * -(-MB // per_step)
    keys = per_step * bs

    lens = jnp.minimum(lens.astype(jnp.int32), MB * bs - 1)
    tables = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    if window is None:
        count, rows, tiles, blocks = _walk(tables, lens, bs, per_step)
        scalars = (rows, tiles, blocks, lens)
    else:
        first = (jnp.maximum(lens - window + 1, 0) // bs).astype(jnp.int32)
        count, rows, tiles, blocks = _walk(tables, lens, bs, per_step, first)
        scalars = (rows, tiles, blocks, lens, first)
    if layered:
        scalars += (jnp.asarray(layer_idx, jnp.int32).reshape(1),)

    rows_view = _rows_view_is_free(NKV, D, itemsize)
    block = (bs * NKV, D) if rows_view else (bs, NKV, D)
    if rows_view:
        arena_k = arena_k.reshape(arena_k.shape[:-3] + block)
        arena_v = arena_v.reshape(arena_v.shape[:-3] + block)

    def kv_map(slot):
        def index_map(i, rows, tiles, blocks, *rest):
            # rest: lens (a window's first entries) and the layer index
            return (tuple(ref[0] for ref in rest[-1:] if layered)
                    + (blocks[slot * N + i],) + (0,) * len(block))
        return index_map

    q_map = lambda i, rows, *_: (rows[i], 0, 0)              # noqa: E731
    col_map = lambda i, *_: (0, 0)                           # noqa: E731
    kv_spec = [pl.BlockSpec((1,) * (1 + layered) + block, kv_map(slot))
               for slot in range(per_step)]
    col = np.arange(keys * NKV, dtype=np.int32)[None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(count,),
        in_specs=[pl.BlockSpec((1, NH, D), q_map),
                  pl.BlockSpec((1, keys * NKV), col_map),
                  pl.BlockSpec((1, keys * NKV), col_map)] + kv_spec * 2,
        out_specs=pl.BlockSpec((1, NH, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((NH, 128), jnp.float32),
            pltpu.VMEM((NH, 128), jnp.float32),
            pltpu.VMEM((NH, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, bs=bs, per_step=per_step, kv_heads=NKV, groups=NH // NKV,
        sm_scale=1.0 / math.sqrt(D), rows_view=rows_view, window=window)
    out = pl.pallas_call(
        kernel,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, D), q.dtype),
    )(*scalars, q, col // NKV, col % NKV,
      *([arena_k] * per_step), *([arena_v] * per_step))
    # an inactive row is no tile of the walk: nothing wrote its output
    return jnp.where((lens < 0)[:, None, None], 0, out).astype(q.dtype)
