"""Pallas TPU paged-attention decode kernel.

Replaces the reference's blocked-flash decode kernels over a paged KV cache
(inference/v2/kernels/ragged_ops/blocked_flash/ — flash attention walking a
block table; also the fused softmax_context decode path of
csrc/transformer/inference/pt_binding.cpp).

One query token per sequence attends to that sequence's KV blocks scattered
through the shared arena.  The TPU-native trick: the block table rides the
grid as a *scalar-prefetch* operand, and the K/V BlockSpec index maps read
it — grid step (b, j) DMAs arena block `table[b, j]` straight into VMEM.
The gathered [B, max_kv, ...] K/V copy the dense path materializes in HBM
never exists; online softmax accumulates across table blocks in VMEM
scratch (flash-attention style), so per-step HBM traffic is exactly one
visit of the live KV blocks.

GQA runs without a KV repeat: scores are computed per kv-head with the
grouped q heads batched ([NKV, G, D] x [NKV, bs, D]).

Masking: block j of a table holds key positions [j*bs, (j+1)*bs); keys with
position > lens[b] (and whole blocks past the sequence) contribute exp(-inf)
= 0.  lens[b] < 0 marks an inactive (padded) row — output zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_reference"]

NEG_INF = -1e30


def paged_decode_reference(q, arena_k, arena_v, block_tables, lens):
    """Dense-gather reference (the ragged engine's fallback math).

    q: [B, NH, D]; arena_k/v: [nb, bs, NKV, D]; block_tables: [B, MB];
    lens: [B] current token position (inclusive key bound; <0 = inactive).
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
    s = jnp.where(key_pos <= lens[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnm,bmnd->bnd", p.astype(vv.dtype), vv)
    zero = (lens < 0)[:, None, None]
    return jnp.where(zero, 0.0, out).astype(q.dtype)


def _compute_block(tables_ref, lens_ref, q_ref, k, v,
                   m_s, l_s, acc_s, b, j, *, bs, groups, sm_scale):
    # k/v: [bs, NKV, D] arrays already read from their (possibly layered)
    # blocks — Mosaic rejects sub-ref views whose minor dim is narrower
    # than the 128 tiling, so the kernel reads with leading indices
    NH, D = q_ref.shape[1], q_ref.shape[2]
    NKV = k.shape[1]
    qg = q_ref[0].astype(jnp.float32).reshape(NKV, groups, D) * sm_scale
    k = k.astype(jnp.float32)                           # [bs, NKV, D]
    v = v.astype(jnp.float32)
    kt = jnp.swapaxes(k, 0, 1)                          # [NKV, bs, D]
    vt = jnp.swapaxes(v, 0, 1)

    # scores per kv head, grouped q heads batched: [NKV, G, bs]
    s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    key_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    s = jnp.where(key_pos <= lens_ref[b], s, NEG_INF)
    s2 = s.reshape(NH, bs)

    m_prev = m_s[:, :1]                                 # [NH, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
    # explicit re-mask: when every key is masked m_new == NEG_INF and
    # exp(s - m) would be exp(0) = 1 for the masked entries
    p2 = jnp.where(s2 > NEG_INF * 0.5, jnp.exp(s2 - m_new), 0.0)  # [NH, bs]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_s[:, :1] + jnp.sum(p2, axis=1, keepdims=True)

    # weighted values: [NKV, G, bs] x [NKV, bs, D] -> [NKV, G, D]
    pv = jax.lax.dot_general(p2.reshape(NKV, groups, bs), vt,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_s[:] = acc_s[:] * alpha + pv.reshape(NH, D)
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_s, l_s, acc_s, *, bs: int, groups: int, sm_scale: float,
            layered: bool = False):
    # q_ref: [1, NH, D]; k_ref/v_ref: [1, bs, NKV, D] (or [1, 1, bs, NKV,
    # D] when `layered` — the arena keeps its leading layer dim and the
    # BlockSpec index map picks the layer); o_ref: [1, NH, D]
    # scratch: m_s/l_s [NH, 128] f32, acc_s [NH, D] f32
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # skip whole blocks past the sequence end (their DMA is already paid;
    # the compute is not)
    @pl.when(j * bs <= lens_ref[b])
    def _compute():
        k = k_ref[0, 0] if layered else k_ref[0]
        v = v_ref[0, 0] if layered else v_ref[0]
        _compute_block(tables_ref, lens_ref, q_ref, k, v,
                       m_s, l_s, acc_s, b, j, bs=bs, groups=groups,
                       sm_scale=sm_scale)

    @pl.when(j == num_j - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-9)   # all-masked (inactive) -> zeros
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q, arena_k, arena_v, block_tables, lens,
                           layer_idx=None):
    """Fused paged decode attention (see module docstring).

    Shapes as in `paged_decode_reference`; block_tables entries may be
    garbage past a sequence's live blocks (clamped + masked).

    `layer_idx`: when given, arena_k/v keep their FULL [L, nb, bs, NKV, D]
    shape and the (traced) scalar layer index rides the grid as a scalar-
    prefetch operand consumed by the K/V index maps — no [nb, ...] layer
    slice is ever materialized in HBM (the copy that made the serving
    layer scan double-buffer the whole arena).  Merged [L, nb, bs, NKV*D]
    arenas are served by the packed-q variant in ops/paged_merged.py."""
    B, NH, D = q.shape
    layered = layer_idx is not None
    if layered:
        _, nb, bs, NKV, _ = arena_k.shape
    else:
        nb, bs, NKV, _ = arena_k.shape
    MB = block_tables.shape[1]
    groups = NH // NKV
    sm_scale = 1.0 / math.sqrt(D)

    tables = jnp.clip(block_tables, 0, nb - 1).astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    if layered:
        li = jnp.asarray(layer_idx, jnp.int32).reshape(1)
        in_specs = [
            pl.BlockSpec((1, NH, D), lambda b, j, li_, tb, ln: (b, 0, 0)),
            pl.BlockSpec((1, 1, bs, NKV, D),
                         lambda b, j, li_, tb, ln:
                         (li_[0], tb[b, j], 0, 0, 0)),
            pl.BlockSpec((1, 1, bs, NKV, D),
                         lambda b, j, li_, tb, ln:
                         (li_[0], tb[b, j], 0, 0, 0)),
        ]
        out_specs = pl.BlockSpec((1, NH, D),
                                 lambda b, j, li_, tb, ln: (b, 0, 0))
        num_prefetch = 3
        operands = (li, tables, lens, q, arena_k, arena_v)
    else:
        in_specs = [
            pl.BlockSpec((1, NH, D), lambda b, j, tb, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, NKV, D),
                         lambda b, j, tb, ln: (tb[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, NKV, D),
                         lambda b, j, tb, ln: (tb[b, j], 0, 0, 0)),
        ]
        out_specs = pl.BlockSpec((1, NH, D), lambda b, j, tb, ln: (b, 0, 0))
        num_prefetch = 2
        operands = (tables, lens, q, arena_k, arena_v)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((NH, 128), jnp.float32),
            pltpu.VMEM((NH, 128), jnp.float32),
            pltpu.VMEM((NH, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, groups=groups,
                               sm_scale=sm_scale, layered=layered)
    if layered:
        # kernel positional refs: (li, tables, lens, q, k, v, o, scratch);
        # adapt to the shared (tables, lens, ...) signature
        kernel_fn = lambda li_ref, *rest: kernel(*rest)
    else:
        kernel_fn = kernel
    return pl.pallas_call(
        kernel_fn,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NH, D), q.dtype),
    )(*operands)
