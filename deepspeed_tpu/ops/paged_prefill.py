"""Pallas TPU blocked-flash prefill kernel over a paged KV cache.

Replaces the reference's prefill-side blocked flash attention
(inference/v2/kernels/ragged_ops/blocked_flash/blocked_flash.py — flash
attention whose KV walk follows the sequence's block table) for the ragged
serving engine's chunked prefill.

The dense fallback in `inference/v2/ragged_ops.py` gathers the table's
blocks into a contiguous [max_kv, NKV, D] copy and materializes
[NH, C, max_kv] f32 scores per layer — O(C*max_kv) HBM at long context.
Here the block table rides the grid as a scalar-prefetch operand (same
trick as `paged_attention.py`): grid step (t, j) DMAs arena block
`table[j]` straight into VMEM and accumulates chunk-tile t's online
softmax, so neither the gathered copy nor the score matrix ever exists.

Layouts are head-major [NH, ct, X] so every vector's tiled trailing dims
are well-shaped ((ct, D), (ct, bs), (ct, 128)); the kv-head-batched
[NKV, ct, G, X] alternative puts G (often 1) in the sublane dim and pads
8x, blowing the VMEM budget.  GQA therefore repeats K/V to NH in-VMEM per
block — a [bs, D]-sized copy vs the [ct, bs, D]-sized dots, noise.

Masking: block j of the table holds absolute key positions
[j*bs, (j+1)*bs); causal = key_pos <= query_pos, with query c of tile t at
absolute position pos0 + t*ct + c.  Sliding-window attention additionally
masks key_pos <= query_pos - window.  Key blocks entirely past the last
valid query are skipped (their compute; the DMA is prefetched).  Padded
queries (c >= n_valid) renormalize to zeros via the l >= eps guard.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_prefill_attention", "paged_prefill_reference",
           "prefill_plan"]

NEG_INF = -1e30


def paged_prefill_reference(q, arena_k, arena_v, block_table, pos0, n_valid,
                            sliding_window: Optional[int] = None):
    """Dense-gather reference (the ragged engine's fallback math).

    q: [C, NH, D] chunk queries at absolute positions [pos0, pos0+C);
    arena_k/v: [nb, bs, NKV, D]; block_table: [MB].  Returns [C, NH, D].
    """
    C, NH, D = q.shape
    nb, bs, NKV, _ = arena_k.shape
    MB = block_table.shape[0]
    max_kv = MB * bs
    kk = jnp.take(arena_k, block_table, axis=0,
                  mode="clip").reshape(max_kv, NKV, D)
    vv = jnp.take(arena_v, block_table, axis=0,
                  mode="clip").reshape(max_kv, NKV, D)
    if NKV != NH:
        kk = jnp.repeat(kk, NH // NKV, axis=1)
        vv = jnp.repeat(vv, NH // NKV, axis=1)
    s = jnp.einsum("cnd,mnd->ncm", q, kk,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    key_pos = jnp.arange(max_kv)[None, None, :]
    q_pos = (pos0 + jnp.arange(C))[None, :, None]
    mask = key_pos <= q_pos
    if sliding_window is not None:
        mask &= key_pos > q_pos - sliding_window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("ncm,mnd->cnd", p.astype(vv.dtype), vv)
    return out.astype(q.dtype)


def _compute_block(meta_ref, q_s, k, v, m_s, l_s, acc_s, t, j, *,
                   ct, bs, groups, window):
    # k/v: [bs, NKV, D] arrays already read from their (possibly layered)
    # blocks — Mosaic rejects sub-ref views with a sub-128 minor dim
    NKV = k.shape[1]
    D = k.shape[2]
    k = k.astype(jnp.float32)                             # [bs, NKV, D]
    v = v.astype(jnp.float32)
    kt = jnp.swapaxes(k, 0, 1)                            # [NKV, bs, D]
    vt = jnp.swapaxes(v, 0, 1)
    if groups > 1:
        kt = jnp.repeat(kt, groups, axis=0)               # [NH, bs, D]
        vt = jnp.repeat(vt, groups, axis=0)

    # scores, head-batched (batch dims at position 0 for Mosaic matmul):
    # [NH, ct, D] x [NH, bs, D] -> [NH, ct, bs]
    s = jax.lax.dot_general(q_s[:], kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    q_pos = (meta_ref[0] + t * ct
             + jax.lax.broadcasted_iota(jnp.int32, (1, ct, 1), 1))
    key_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    mask = key_pos <= q_pos
    if window is not None:
        mask &= key_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_s[..., :1]                                 # [NH, ct, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    # re-mask: rows with every key masked have m_new == NEG_INF and
    # exp(s - m) would be exp(0) = 1 for the masked entries
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_s[..., :1] + jnp.sum(p, axis=2, keepdims=True)

    # weighted values: [NH, ct, bs] x [NH, bs, D] -> [NH, ct, D]
    pv = jax.lax.dot_general(p, vt, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_s[:] = acc_s[:] * alpha + pv
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)


def _kernel(tables_ref, meta_ref, q_ref, k_ref, v_ref, o_ref,
            q_s, m_s, l_s, acc_s, *, ct: int, bs: int, groups: int,
            sm_scale: float, window, layered: bool = False):
    # q_ref/o_ref: [ct, NH, D]; k_ref/v_ref: [1, bs, NKV, D] (or
    # [1, 1, bs, NKV, D] when `layered`)
    # scratch: q_s [NH, ct, D] f32 (tile's queries staged head-major once
    # per tile), m_s/l_s [NH, ct, 128] f32, acc_s [NH, ct, D] f32
    t = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        q_s[:] = (jnp.swapaxes(q_ref[:].astype(jnp.float32), 0, 1)
                  * sm_scale)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # causal + validity skip: block j holds keys from position j*bs; no
    # query of this tile (last abs position pos0 + (t+1)*ct - 1, bounded by
    # the last valid query pos0 + n_valid - 1) can see it if it starts later
    last_q = meta_ref[0] + jnp.minimum((t + 1) * ct, meta_ref[1]) - 1
    compute = j * bs <= last_q
    if window is not None:
        # sliding-window lower skip: key_pos visible to SOME query of the
        # tile iff key_pos > first_q - window (widest window start is the
        # tile's FIRST query); a block whose last key (j+1)*bs - 1 is at or
        # below that bound is all-masked — skip its MXU work entirely
        first_q = meta_ref[0] + t * ct
        compute = jnp.logical_and(compute, (j + 1) * bs - 1 > first_q - window)

    @pl.when(compute)
    def _compute():
        k = k_ref[0, 0] if layered else k_ref[0]
        v = v_ref[0, 0] if layered else v_ref[0]
        _compute_block(meta_ref, q_s, k, v, m_s, l_s, acc_s, t, j,
                       ct=ct, bs=bs, groups=groups, window=window)

    @pl.when(j == num_j - 1)
    def _finish():
        l = jnp.maximum(l_s[..., :1], 1e-9)   # fully-masked rows -> zeros
        out = (acc_s[:] / l).astype(o_ref.dtype)       # [NH, ct, D]
        o_ref[:] = jnp.swapaxes(out, 0, 1)             # [ct, NH, D]


def _query_tile(C: int, NH: int, D: int, bs: int):
    """Largest power-of-2 query tile in [8, 128] dividing C whose f32 VMEM
    working set (q_s + m/l + acc + s/p transients) stays well under the
    ~16 MB scoped budget; None when no tile satisfies both (caller pads
    the chunk via `prefill_plan` or raises)."""
    ct = 128
    while ct >= 8:
        if C % ct == 0:
            # scratch + s/p transients; the q/o blocks, K/V blocks and GQA
            # repeat copies ride on top, so keep headroom under the 16 MB
            # scoped limit (measured: formula 10 MB -> actual 16.75 MB)
            working = 4 * NH * ct * (2 * D + 2 * 128 + 2 * bs)
            if working <= 6 * 2**20:
                return ct
        ct //= 2
    return None


def pad_to_sublane_tile(C: int):
    """(padded_C, ct) for the sublane-padding contract SHARED by this
    kernel and the merged-arena variants (paged_merged): the largest
    power-of-2 query tile in [8, 128] dividing C, padding C up to the
    next multiple of 8 (the f32 sublane minimum) when none divides —
    speculative verify spans of 2-4 and odd chunk tails land on the pad
    path, and the pad rows are sliced off outside the kernel.  Ignores
    VMEM budgets (the merged kernels' stripes are fixed 128-lane);
    `prefill_plan` layers the 5-D kernel's VMEM fit on top."""
    def tile(c):
        ct = 128
        while ct >= 8:
            if c % ct == 0:
                return ct
            ct //= 2
        return None

    ct = tile(C)
    if ct is not None:
        return C, ct
    Cp = -(-C // 8) * 8
    return Cp, tile(Cp)


def prefill_plan(C: int, NH: int, D: int, bs: int):
    """(padded_C, ct) serving a C-row chunk through this kernel: the
    shared sublane pad contract (`pad_to_sublane_tile`) plus this
    kernel's VMEM working-set fit.  None only when even the minimal
    8-row tile's VMEM working set cannot fit (geometry, not chunk size:
    every C >= 1 is otherwise servable — the full-range contract)."""
    Cp, _ = pad_to_sublane_tile(C)
    ct = _query_tile(Cp, NH, D, bs)
    if ct is None:
        return None
    return Cp, ct


def paged_prefill_attention(q, arena_k, arena_v, block_table, pos0, n_valid,
                            sliding_window: Optional[int] = None,
                            layer_idx=None):
    """Fused blocked-flash prefill (see module docstring).

    q: [C, NH, D]; arena_k/v: [nb, bs, NKV, D]; block_table: [MB] (entries
    may be garbage past the sequence's live blocks — clamped, and causality
    masks their keys); pos0/n_valid: scalars.  Returns [C, NH, D].

    `layer_idx`: when given, arena_k/v keep their FULL [L, nb, bs, NKV, D]
    shape and the (traced) layer index rides the grid as a scalar-prefetch
    operand consumed by the K/V index maps — no per-layer arena slice is
    materialized in HBM.  Merged [L, nb, bs, NKV*D] arenas are served by
    the stripe-grid variant in ops/paged_merged.py.
    """
    C, NH, D = q.shape
    layered = layer_idx is not None
    if layered:
        _, nb, bs, NKV, _ = arena_k.shape
    else:
        nb, bs, NKV, _ = arena_k.shape
    MB = block_table.shape[0]
    groups = NH // NKV
    sm_scale = 1.0 / math.sqrt(D)
    plan = prefill_plan(C, NH, D, bs)
    if plan is None:
        raise ValueError(
            f"no query tile fits: the minimal 8-row tile's VMEM working "
            f"set overflows for this geometry (C={C}, NH={NH}, D={D}, "
            f"bs={bs})")
    C0 = C
    Cp, ct = plan
    if Cp != C:
        # pad queries to the sublane tile; n_valid <= C bounds the
        # kernel's compute skip, so pad rows never accumulate (l = 0 ->
        # zeros) and are sliced off below
        q = jnp.pad(q, ((0, Cp - C), (0, 0), (0, 0)))
        C = Cp

    tables = jnp.clip(block_table, 0, nb - 1).astype(jnp.int32)
    meta = jnp.stack([jnp.asarray(pos0, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])

    if layered:
        li = jnp.asarray(layer_idx, jnp.int32).reshape(1)
        in_specs = [
            pl.BlockSpec((ct, NH, D), lambda t, j, li_, tb, mt: (t, 0, 0)),
            pl.BlockSpec((1, 1, bs, NKV, D),
                         lambda t, j, li_, tb, mt:
                         (li_[0], tb[j], 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, NKV, D),
                         lambda t, j, li_, tb, mt:
                         (li_[0], tb[j], 0, 0, 0)),
        ]
        out_specs = pl.BlockSpec((ct, NH, D),
                                 lambda t, j, li_, tb, mt: (t, 0, 0))
        num_prefetch = 3
        operands = (li, tables, meta, q, arena_k, arena_v)
    else:
        in_specs = [
            pl.BlockSpec((ct, NH, D), lambda t, j, tb, mt: (t, 0, 0)),
            pl.BlockSpec((1, bs, NKV, D),
                         lambda t, j, tb, mt: (tb[j], 0, 0, 0)),
            pl.BlockSpec((1, bs, NKV, D),
                         lambda t, j, tb, mt: (tb[j], 0, 0, 0)),
        ]
        out_specs = pl.BlockSpec((ct, NH, D), lambda t, j, tb, mt: (t, 0, 0))
        num_prefetch = 2
        operands = (tables, meta, q, arena_k, arena_v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(C // ct, MB),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((NH, ct, D), jnp.float32),
            pltpu.VMEM((NH, ct, 128), jnp.float32),
            pltpu.VMEM((NH, ct, 128), jnp.float32),
            pltpu.VMEM((NH, ct, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, ct=ct, bs=bs, groups=groups,
                               sm_scale=sm_scale, window=sliding_window,
                               layered=layered)
    if layered:
        kernel_fn = lambda li_ref, *rest: kernel(*rest)
    else:
        kernel_fn = kernel
    out = pl.pallas_call(
        kernel_fn,
        name="paged_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, NH, D), q.dtype),
    )(*operands)
    return out if C == C0 else out[:C0]
