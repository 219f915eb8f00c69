"""Pallas TPU grouped matmul over the rows a step really has: what the
experts' share of a latent or static-kind stack multiplies with
(`inference/v2/expert_ffn.moe`).

The rows `x [rows, K]` lie sorted by expert, the experts' rows first and
dead rows behind them; `sizes` says how many rows each of one layer's
experts has, and the weights are the WHOLE stack `[layers * experts, K,
N]` (a per-layer slice handed to a custom call would first be copied).
`lax.ragged_dot` pays for the buffer and for every group of the stack;
this kernel pays for the live rows: the wrapper lists the live (expert,
row tile) ITEMS in expert-major order (`list_items`), the list rides the
grid as scalar-prefetch operands, and the index maps of the rows, the
weights and the output read it.

An expert's segment lies where the wrapper's LAYOUT puts it: end to end,
the experts' rows first and dead rows behind them, or each from a row-tile
edge in a longer buffer (`aligns` says when, `sort_rows` builds either).
End to end a tile is shared by the experts that meet in it, each an item
that multiplies the whole tile and keeps its own rows; aligned a tile is
one expert's, and an expert of s rows is `ceil(s / tile)` items where end
to end it is about one more.

The grid is (column block of the output, item).  Within a column block
the items run in order, so

- an expert's weight block `[K, cols]` is fetched when the item's expert
  differs from the item before and stays in VMEM over that expert's row
  tiles: every reached expert's weights are read ONCE a matmul, whatever
  the rows (`weight_fetches` counts it from the list and this order);
- a row tile is fetched when the item's tile differs from the item
  before (a tile shared by several experts once), once per column block;
- an output tile is visited by a run of consecutive items (the tiles of
  an expert-major list never go back), so it stays in VMEM from its first
  item to its last: each item writes the rows of its own live range
  `[lo, hi)` and leaves the others as they are.  Rows no item covers are
  never written: the caller masks the rows past the live ones;
- the list's length is static (`item_slots`); entries past the live count
  repeat the last live item, so a dead grid step changes no index, copies
  nothing and computes nothing.

Operands stay in the model's dtype (bf16 on the chip) with float32
accumulation.  With two weights and `gate_act` one pass reads the rows
once and writes `gate_act(x @ w_gate) * (x @ w_up)`, the activation in
float32 and one cast to `out_dtype`; with one weight it writes `x @ w` in
float32: what the three `ragged_dot` calls it replaces compute.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["Items", "row_tile", "aligns", "item_slots", "sort_rows",
           "list_items", "weight_fetches", "grouped_matmul"]

# rows of a grid step's matmul
ROW_TILE = 128
# bytes of the weight blocks a grid step holds (two of them in flight).  A
# block is `cols` columns of every row of the weight.  Alone, 16 MB read 2-4%
# faster than 2, 4 and 8 MB at the latent cells' shapes; inside deepseek's
# programs 16 MB read 5% SLOWER than 8 (a decode step's matmuls 7.33 against
# 6.97 ms); 2 MB read 20% slower at smallthinker's 4096-row pass
WEIGHT_BLOCK_BYTES = 8 << 20


class Items(NamedTuple):
    """The live (expert, row tile) items of one layer's pass, `item_slots`
    entries each; `count[0]` of them are live, the rest repeat the last."""
    expert: jax.Array     # [I] int32: the expert among the WHOLE stack
    tile: jax.Array       # [I] int32: the row tile
    lo: jax.Array         # [I] int32: the item's first live row in its tile
    hi: jax.Array         # [I] int32: one past its last
    count: jax.Array      # [1] int32


def row_tile(rows: int) -> int:
    """The kernel's row tile for a buffer of `rows` rows: the buffer whole
    up to `ROW_TILE` rows, else the largest of 128, 112, .. 64 that
    divides it (192 -> 96), else 128 with a partial last tile."""
    if rows <= ROW_TILE:
        return rows
    return next((t for t in range(ROW_TILE, 63, -16) if rows % t == 0),
                ROW_TILE)


def aligns(rows: int, tile: int, groups: int, whole: bool) -> bool:
    """Whether a pass over `rows` sorted assignments to `groups` experts
    lays each expert's segment on a row-tile edge (`sort_rows`) instead of
    end to end: where the buffer holds every assignment (`whole`: one
    piece, gathered once) and an even routing gives each expert a tile's
    rows or more, so the padding cannot double the buffer.  Where an expert
    has a few rows (a decode step) either layout is an item an expert and
    alignment would only multiply the rows gathered.

    What it buys was measured, not derived (`PERF.md` §6, PR 47).  The
    kernels alone read 2-3% faster: a segment end to end touches a tile
    more than its rows need, but a tile of one's own moves a whole tile's
    bytes for its live rows.  The pass around them gains more (an expert
    layer of a 512-row prompt 12%, of a 4096-row pass 5-12%): the longer
    buffer's row gather is made of indices the code makes
    (`promise_in_bounds`: no fill pass), and XLA no longer copies the
    kernel's float32 output between memory spaces ahead of the combine's
    gathers, which it does to the 84 MB an end-to-end 512-row pass
    writes."""
    return whole and rows >= groups * tile


def aligned_rows(rows: int, tile: int, groups: int) -> int:
    """Rows of the buffer that holds `rows` rows, those of `groups` segments
    each from a tile edge and the others behind them: a segment's padding
    is under a tile, so `item_slots` tiles hold them whatever the sizes."""
    return tile * item_slots(rows, tile, groups)


def item_slots(rows: int, tile: int, groups: int) -> int:
    """The static length of a pass's item list: every row tile once, and
    once more for each expert that begins inside one."""
    return -(-rows // tile) + groups


def sort_rows(key, groups: int, tile: int, aligned: bool = False):
    """`key` [n] int32, the segment of each row (`groups` for a row of
    none) -> `order` [buffer rows] int32, the row that lies at each place
    of the buffer `list_items` describes: the segments in order, a
    segment's rows in theirs, the rows of none behind them.  End to end
    the buffer is the n rows and `order` a permutation.  `aligned` it is
    `aligned_rows(n, tile, groups)` long and `order` a permutation of its
    places: an index of n or more is a padding row (as many behind each
    segment as fill its last tile, the rest last), which a gather clamps
    into the rows and nobody reads back.  Either way
    `jnp.argsort(order)[:n]` is each row's place.

    The padding is SORTED in, as keys behind the rows' own under a stable
    sort: no row is looked up one by one, which is what the chip does
    worst (placing them by index gathers cost what the layout won)."""
    if aligned:
        n = key.shape[0]
        sizes = jnp.bincount(key, length=groups + 1).astype(jnp.int32)[:groups]
        filled = jnp.cumsum(-sizes % tile)
        row = jnp.arange(aligned_rows(n, tile, groups) - n, dtype=jnp.int32)
        # the segments filled before this padding row: a compare, no lookup
        e = jnp.sum(row[:, None] >= filled[None], axis=1).astype(jnp.int32)
        key = jnp.concatenate([key, jnp.where(e < groups, e, groups + 1)])
    return jnp.argsort(key, stable=True)


def list_items(sizes, rows: int, tile: int, first_group=0,
               aligned: bool = False) -> Items:
    """`sizes` [E] int32, the rows of each of one layer's experts in buffer
    order (sum <= `rows`) -> the pass's items; `first_group`: the layer's
    first expert among the whole stack.  `aligned`: the segments lie in a
    buffer of `aligned_rows(rows, tile, E)` rows, each from a tile edge
    (`sort_rows` builds it): a tile has one expert's rows, `lo` is 0 and
    the live items are `sum(ceil(size / tile))`."""
    sizes = sizes.astype(jnp.int32)
    E = sizes.shape[0]
    # a segment's room: its rows, or (`aligned`) whole tiles
    room = -(-sizes // tile) * tile if aligned else sizes
    starts = jnp.cumsum(room) - room
    ends = starts + sizes
    first_tile = starts // tile
    spans = jnp.where(sizes > 0, (ends - 1) // tile - first_tile + 1, 0)
    item_ends = jnp.cumsum(spans)
    count = item_ends[-1]
    # a dead entry repeats the last live one (the first, of no row, if none)
    i = jnp.minimum(jnp.arange(item_slots(rows, tile, E), dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    e = jnp.minimum(jnp.searchsorted(item_ends, i, side="right"),
                    E - 1).astype(jnp.int32)
    t = first_tile[e] + i - (item_ends - spans)[e]
    return Items(first_group + e, t,
                 jnp.clip(starts[e] - t * tile, 0, tile),
                 jnp.clip(ends[e] - t * tile, 0, tile),
                 count.reshape(1))


def weight_fetches(items: Items):
    """Expert-weight fetches `grouped_matmul`'s grid makes over `items`, in
    units of one expert's whole weight: within a column block a live item
    fetches its expert's block unless the item before left it in VMEM, and
    the column blocks of an expert add up to its weight."""
    live = jnp.arange(items.expert.shape[0]) < items.count[0]
    moved = jnp.concatenate([jnp.ones((1,), bool),
                             items.expert[1:] != items.expert[:-1]])
    return jnp.sum(live & moved).astype(jnp.int32)


def _kernel(expert_ref, tile_ref, lo_ref, hi_ref, count_ref, x_ref, *refs,
            gate_act):
    # x_ref [tile, K]; weights [K, cols] each; o_ref [tile, cols]
    *w_refs, o_ref = refs
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _item():
        x = x_ref[...]
        out = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
               for w in w_refs]
        if gate_act is not None:
            out = [gate_act(out[0]) * out[1]]
        row = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        mine = jnp.logical_and(row >= lo_ref[i], row < hi_ref[i])
        # the rows of the tile's other experts stay as they are
        o_ref[...] = jnp.where(mine, out[0].astype(o_ref.dtype), o_ref[...])


def _column_block(K: int, N: int, n_weights: int, itemsize: int) -> int:
    """Columns of the output a grid step takes: the largest divisor of N
    in whole 128-lane tiles whose weight blocks fit `WEIGHT_BLOCK_BYTES`
    (N itself where it has no such tiles)."""
    if N % 128:
        return N
    fit = [c for c in range(128, N + 1, 128) if N % c == 0
           and n_weights * K * c * itemsize <= WEIGHT_BLOCK_BYTES]
    return max(fit, default=128)


def grouped_matmul(x, weights, items: Items, *, tile: int, gate_act=None,
                   out_dtype=jnp.float32, cols: int = 0):
    """x [rows, K] @ the experts' weights by `items` (`list_items` at this
    `tile`) -> [rows, N] in `out_dtype`.  `weights`: `(w,)`, or `(w_gate,
    w_up)` with `gate_act` for `gate_act(x @ w_gate) * (x @ w_up)`; each
    `[layers * experts, K, N]`.  Rows no item covers come out undefined.
    `cols`: the column block (0: `_column_block`)."""
    rows, K = x.shape
    N = weights[0].shape[2]
    if (gate_act is None) != (len(weights) == 1):
        raise ValueError("one weight, or a gate and an up weight with "
                         "`gate_act`")
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    cols = cols or _column_block(K, N, len(weights), itemsize)
    slots = items.expert.shape[0]
    x_map = lambda j, i, e, t, lo, hi, n: (t[i], 0)         # noqa: E731
    w_map = lambda j, i, e, t, lo, hi, n: (e[i], 0, j)      # noqa: E731
    o_map = lambda j, i, e, t, lo, hi, n: (t[i], j)         # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // cols, slots),
        in_specs=[pl.BlockSpec((tile, K), x_map)]
        + [pl.BlockSpec((None, K, cols), w_map)] * len(weights),
        out_specs=pl.BlockSpec((tile, cols), o_map))
    # two of every block in flight, and the step's float32 products
    need = 2 * (tile * K * jnp.dtype(x.dtype).itemsize
                + len(weights) * K * cols * itemsize
                + tile * cols * jnp.dtype(out_dtype).itemsize) \
        + (len(weights) + 2) * tile * cols * 4
    return pl.pallas_call(
        functools.partial(_kernel, gate_act=gate_act),
        name="grouped_matmul",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(need * 1.25) + (4 << 20)),
    )(*items, x, *weights)
