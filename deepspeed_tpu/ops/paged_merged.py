"""Pallas TPU paged-attention kernels for the MERGED KV-arena layout.

The serving arena stores K/V blocks as [L, nb, bs, NKV*D] with the
(kv_heads, head_dim) pair packed into ONE unpadded minor dim
(inference/v2/ragged_ops.init_arena merged=True) — at D=64 the separate
5-D minor would lane-pad to 128 and physically double the arena HBM.
Round 3 served merged arenas through the dense gather path because
Mosaic cannot re-split a packed lane dim in-kernel; these kernels remove
that fallback with two layout tricks that never split lanes:

- decode (`merged_decode_attention`): queries are packed OUTSIDE the
  kernel into a block-diagonal [NH, NKV*D] operand — head n's D values
  sit in its kv-head's lane stripe, zeros elsewhere.  One dot_general
  against the whole packed key block [bs, NKV*D] then contracts the full
  minor dim: the zero stripes annihilate cross-head products, so the
  [NH, bs] scores are exact.  The weighted-value accumulator keeps the
  packed [NH, NKV*D] form; each head's stripe is extracted outside.
  MXU cost is NKV x the 5-D kernel's — irrelevant at decode, where the
  kernel is DMA-bound — and the arena block DMA is one contiguous
  unpadded [bs, NKV*D] row read (better than the 5-D kernel's padded
  reads at D=64).

- prefill (`merged_prefill_attention`): a third grid dimension walks
  128-lane STRIPES of the minor dim (one head at D=128, a head PAIR at
  D=64 — 128/D heads per stripe).  The K/V BlockSpec reads (bs, 128)
  stripes (minor block divisible by 128: allowed), and the stripe's
  queries ride pre-packed block-diagonally as [hpb*G*ct, 128].  MXU
  overhead is only hpb x (2x at D=64), which matters at prefill where
  the attention FLOPs are real.

Reference: inference/v2/kernels/ragged_ops/blocked_flash/ — the
reference's blocked flash serves every arena shape; these kernels close
the same gap for the TPU layouts.

Assumes the arena holds finite values everywhere (init_arena zeros it;
clamped table entries read other sequences' real blocks) — garbage lanes
would otherwise poison the zero-stripe products.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["merged_decode_attention", "merged_prefill_attention",
           "merged_kernels_supported"]

NEG_INF = -1e30


def merged_kernels_supported(NH: int, NKV: int, D: int,
                             op: str = "decode") -> bool:
    """Merged-kernel eligibility.

    decode packs the WHOLE minor dim into one contraction, so any
    128-aligned packing works.  prefill walks 128-lane stripes and each
    stripe's flash accumulation must see a head's FULL D dims — D > 128
    would softmax partial logits per sub-stripe (wrong math), so prefill
    requires D <= 128 exactly."""
    if D >= 128:
        if op == "prefill":
            return D == 128
        return D % 128 == 0
    hpb = 128 // D
    return 128 % D == 0 and NKV % hpb == 0


def _head_onehot(NH: int, NKV: int, dtype):
    """[NH, NKV] assignment matrix: q head n -> kv head n // (NH//NKV)."""
    g = NH // NKV
    return (jnp.arange(NKV)[None, :] == (jnp.arange(NH) // g)[:, None]
            ).astype(dtype)


def _pack_q(q, NKV: int):
    """[..., NH, D] -> block-diagonal [..., NH, NKV*D] (zeros off-stripe)."""
    NH, D = q.shape[-2], q.shape[-1]
    oh = _head_onehot(NH, NKV, q.dtype)
    packed = jnp.einsum("...nd,nk->...nkd", q, oh)
    return packed.reshape(q.shape[:-2] + (NH, NKV * D))


def _extract_heads(out, NKV: int, D: int):
    """Inverse of _pack_q on the output: [..., NH, NKV*D] -> [..., NH, D]."""
    NH = out.shape[-2]
    oh = _head_onehot(NH, NKV, out.dtype)
    out = out.reshape(out.shape[:-1] + (NKV, D))
    return jnp.einsum("...nkd,nk->...nd", out, oh)


# ----------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------
def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_s, l_s, acc_s, *, bs: int, sm_scale: float,
                   layered: bool):
    # q_ref: [1, NH, M] packed block-diagonal; k_ref/v_ref: [1(,1), bs, M]
    # o_ref: [1, NH, M] packed; scratch m/l [NH, 128], acc [NH, M] f32
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(j * bs <= lens_ref[b])
    def _compute():
        k = (k_ref[0, 0] if layered else k_ref[0]).astype(jnp.float32)
        v = (v_ref[0, 0] if layered else v_ref[0]).astype(jnp.float32)
        qg = q_ref[0].astype(jnp.float32) * sm_scale        # [NH, M]
        # zero off-stripe lanes annihilate cross-head terms: exact
        # per-head scores from ONE full-minor contraction
        s = jax.lax.dot_general(qg, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [NH, bs]
        key_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(key_pos <= lens_ref[b], s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [NH, M]
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == num_j - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-9)   # all-masked (inactive) -> zeros
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)


def merged_decode_attention(q, arena_k, arena_v, block_tables, lens,
                            layer_idx=None, interpret: bool = False):
    """Fused decode over a MERGED arena.

    q: [B, NH, D]; arena_k/v: [nb, bs, NKV*D] (or [L, nb, bs, NKV*D] with
    `layer_idx`); block_tables: [B, MB]; lens: [B] (<0 = inactive row).
    Returns [B, NH, D] in q.dtype.
    """
    B, NH, D = q.shape
    layered = layer_idx is not None
    if layered:
        _, nb, bs, M = arena_k.shape
    else:
        nb, bs, M = arena_k.shape
    NKV = M // D
    MB = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(D)

    q_pack = _pack_q(q, NKV)                             # [B, NH, M]
    tables = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    if layered:
        li = jnp.asarray(layer_idx, jnp.int32).reshape(1)
        in_specs = [
            pl.BlockSpec((1, NH, M), lambda b, j, li_, tb, ln: (b, 0, 0)),
            pl.BlockSpec((1, 1, bs, M),
                         lambda b, j, li_, tb, ln: (li_[0], tb[b, j], 0, 0)),
            pl.BlockSpec((1, 1, bs, M),
                         lambda b, j, li_, tb, ln: (li_[0], tb[b, j], 0, 0)),
        ]
        num_prefetch = 3
        operands = (li, tables, lens, q_pack, arena_k, arena_v)
    else:
        in_specs = [
            pl.BlockSpec((1, NH, M), lambda b, j, tb, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, M), lambda b, j, tb, ln: (tb[b, j], 0, 0)),
            pl.BlockSpec((1, bs, M), lambda b, j, tb, ln: (tb[b, j], 0, 0)),
        ]
        num_prefetch = 2
        operands = (tables, lens, q_pack, arena_k, arena_v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, NH, M),
                               (lambda b, j, li_, tb, ln: (b, 0, 0))
                               if layered else
                               (lambda b, j, tb, ln: (b, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((NH, 128), jnp.float32),
            pltpu.VMEM((NH, 128), jnp.float32),
            pltpu.VMEM((NH, M), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, bs=bs, sm_scale=sm_scale,
                               layered=layered)
    kernel_fn = (lambda li_ref, *rest: kernel(*rest)) if layered else kernel
    out = pl.pallas_call(
        kernel_fn,
        name="paged_merged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, M), q.dtype),
        interpret=interpret,
    )(*operands)
    return _extract_heads(out, NKV, D)


# ----------------------------------------------------------------------
# prefill
# ----------------------------------------------------------------------
def _prefill_kernel(tables_ref, meta_ref, q_ref, k_ref, v_ref, o_ref,
                    m_s, l_s, acc_s, *, ct: int, bs: int, sm_scale: float,
                    window, layered: bool):
    # grid: (stripe p, q tile t, kv block j)
    # q_ref: [1, R, 128] stripe queries, pre-packed block-diagonal with
    #   R = hpb*G*ct rows (head-major: heads of the stripe, then tiles'
    #   queries); k_ref/v_ref: [1(,1), bs, 128] stripe of the kv block
    # o_ref: [1, R, 128]; scratch m/l [R, 128], acc [R, 128] f32
    t = pl.program_id(1)
    j = pl.program_id(2)
    num_j = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    R = m_s.shape[0]
    heads_rows = R // ct  # hpb * G query heads stacked per stripe

    last_q = meta_ref[0] + jnp.minimum((t + 1) * ct, meta_ref[1]) - 1
    compute = j * bs <= last_q
    if window is not None:
        first_q = meta_ref[0] + t * ct
        compute = jnp.logical_and(compute,
                                  (j + 1) * bs - 1 > first_q - window)

    @pl.when(compute)
    def _compute():
        k = (k_ref[0, 0] if layered else k_ref[0]).astype(jnp.float32)
        v = (v_ref[0, 0] if layered else v_ref[0]).astype(jnp.float32)
        qg = q_ref[0].astype(jnp.float32) * sm_scale        # [R, 128]
        s = jax.lax.dot_general(qg, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [R, bs]
        # row r is query c = r % ct of head r // ct
        q_pos = (meta_ref[0] + t * ct
                 + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) % ct)
        key_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        mask = key_pos <= q_pos
        if window is not None:
            mask = jnp.logical_and(mask, key_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [R, 128]
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == num_j - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-9)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)


def merged_prefill_attention(q, arena_k, arena_v, block_table, pos0, n_valid,
                             sliding_window: Optional[int] = None,
                             layer_idx=None, interpret: bool = False):
    """Fused blocked-flash prefill over a MERGED arena.

    q: [C, NH, D]; arena_k/v: [nb, bs, NKV*D] (or [L, ...] with
    `layer_idx`); block_table: [MB]; pos0/n_valid scalars.
    Returns [C, NH, D] in q.dtype.
    """
    C, NH, D = q.shape
    layered = layer_idx is not None
    if layered:
        _, nb, bs, M = arena_k.shape
    else:
        nb, bs, M = arena_k.shape
    NKV = M // D
    MB = block_table.shape[0]
    G = NH // NKV
    hpb = max(1, 128 // D)          # kv heads per 128-lane stripe
    if D > 128:
        # a stripe would see only 128 of a head's D dims — softmax over
        # partial logits is WRONG math, not just unsupported layout
        raise ValueError(
            f"merged prefill requires head_dim <= 128 (got {D}); gate "
            f"with merged_kernels_supported(..., op='prefill')")
    # q stripes: for D < 128 one stripe serves hpb kv heads (and their
    # hpb*G q heads); at D == 128 one stripe per Q head (kv stripe
    # resolved by kv_stripe below)
    n_stripes = M // 128 if D < 128 else NH
    if D >= 128:
        hpb = 1
    sm_scale = 1.0 / math.sqrt(D)

    # the sublane pad contract shared with paged_prefill: sub-8 / odd C
    # (verify spans of 2-4, odd chunk tails) pads to the 8-row tile.
    # n_valid <= C bounds the compute skip, so pad rows never
    # accumulate and are sliced off at the end.
    from .paged_prefill import pad_to_sublane_tile
    C0 = C
    C, ct = pad_to_sublane_tile(C)
    if C != C0:
        q = jnp.pad(q, ((0, C - C0), (0, 0), (0, 0)))
    R = hpb * G * ct if D <= 128 else ct * G  # rows per stripe tile

    n_t = C // ct
    # stripe-major packed queries, TILE-major rows: the q BlockSpec slices
    # rows [t*R, (t+1)*R), which must be exactly (all stripe heads) x
    # (tile t's ct queries) — in-block row r = head*ct + c, the layout
    # _prefill_kernel's q_pos iota assumes
    if D < 128:
        # [C, NH, D] -> [n_stripes, n_t * hpb*G * ct, 128]
        q4 = q.reshape(n_t, ct, NKV // hpb, hpb * G, D)
        q4 = jnp.moveaxis(q4, 2, 0)              # [ns, n_t, ct, hpb*G, D]
        oh = (jnp.arange(hpb)[None, :] ==
              (jnp.arange(hpb * G) // G)[:, None]).astype(q.dtype)  # [hpb*G, hpb]
        q5 = jnp.einsum("stcnd,nh->stnchd", q4, oh)
        q_pack = q5.reshape(n_stripes, n_t * hpb * G * ct, 128)
    else:
        # [C, NH, D] -> [NH*(D//128), C, 128] == [ns*G? ...]
        sub = D // 128
        qs = q.reshape(C, NH, sub, 128)
        q_pack = jnp.moveaxis(qs, (1, 2), (0, 1)).reshape(
            NH * sub, C, 128)
        # rows per tile are just ct (each stripe serves ONE head sub-range)
        R = ct

    tables = jnp.clip(block_table, 0, nb - 1).astype(jnp.int32)
    meta = jnp.stack([jnp.asarray(pos0, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])

    q_block = (1, R, 128)
    grid = (n_stripes, n_t, MB)
    out_rows = (n_t * hpb * G * ct) if D < 128 else C

    sub = D // 128 if D >= 128 else 1

    def kv_stripe(p):
        """q-stripe -> kv-stripe of the merged minor dim.  D<128: stripes
        align 1:1 (q_pack groups each stripe's q heads).  D>=128: q
        stripe p = (q head, sub-stripe); the kv head is q_head // G."""
        if D < 128:
            return p
        return (p // sub // G) * sub + p % sub

    if layered:
        li = jnp.asarray(layer_idx, jnp.int32).reshape(1)

        def kv_index(p, t, j, li_, tb, mt):
            return (li_[0], tb[j], 0, kv_stripe(p))
        in_specs = [
            pl.BlockSpec(q_block, lambda p, t, j, li_, tb, mt: (p, t, 0)),
            pl.BlockSpec((1, 1, bs, 128), kv_index),
            pl.BlockSpec((1, 1, bs, 128), kv_index),
        ]
        out_specs = pl.BlockSpec((1, R, 128),
                                 lambda p, t, j, li_, tb, mt: (p, t, 0))
        num_prefetch = 3
        operands = (li, tables, meta, q_pack, arena_k, arena_v)
    else:
        def kv_index(p, t, j, tb, mt):
            return (tb[j], 0, kv_stripe(p))
        in_specs = [
            pl.BlockSpec(q_block, lambda p, t, j, tb, mt: (p, t, 0)),
            pl.BlockSpec((1, bs, 128), kv_index),
            pl.BlockSpec((1, bs, 128), kv_index),
        ]
        out_specs = pl.BlockSpec((1, R, 128),
                                 lambda p, t, j, tb, mt: (p, t, 0))
        num_prefetch = 2
        operands = (tables, meta, q_pack, arena_k, arena_v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, 128), jnp.float32),
            pltpu.VMEM((R, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, ct=ct, bs=bs,
                               sm_scale=sm_scale, window=sliding_window,
                               layered=layered)
    kernel_fn = (lambda li_ref, *rest: kernel(*rest)) if layered else kernel
    out = pl.pallas_call(
        kernel_fn,
        name="paged_merged_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_stripes, out_rows, 128), q.dtype),
        interpret=interpret,
    )(*operands)

    # un-pack: stripe/tile-major rows back to [C, NH, D] (pad rows off)
    if D < 128:
        o = out.reshape(n_stripes, n_t, hpb * G, ct, hpb, D)
        oh = (jnp.arange(hpb)[None, :] ==
              (jnp.arange(hpb * G) // G)[:, None]).astype(out.dtype)
        o = jnp.einsum("stnchd,nh->stncd", o, oh)  # [ns, n_t, hpb*G, ct, D]
        # stripe s serves q heads [s*hpb*G, (s+1)*hpb*G): head-contiguous
        o = jnp.transpose(o, (1, 3, 0, 2, 4))      # [n_t, ct, ns, hpb*G, D]
        return o.reshape(C, NH, D)[:C0].astype(q.dtype)
    sub = D // 128
    o = out.reshape(NH, sub, C, 128)
    return jnp.moveaxis(o, (0, 1),
                        (1, 2)).reshape(C, NH, D)[:C0].astype(q.dtype)
