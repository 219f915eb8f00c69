"""Pallas TPU block-sparse flash attention (splash-attention analog).

Replaces the reference's Triton block-sparse SDD/DSD matmul + masked
softmax kernels (deepspeed/ops/sparse_attention/{matmul,softmax}.py over
csrc/sparse_attention) for the layout family in `ops/sparse_attention.py`.

The jnp fallback gathers every (head, q-block)'s active K/V blocks into a
[B, H, nqb, A, block, D] HBM copy and materializes [block, A*block] f32
scores.  Here the padded gather index `kb_idx[h, qb, a]` rides the grid as
a scalar-prefetch operand and the K/V BlockSpec index maps read it — grid
step (b, h, i, a) DMAs exactly the visited arena block into VMEM and
accumulates an online softmax, so neither the gathered copy nor the score
strip ever exists.  Padding entries (kb_idx < 0) skip compute (their DMA
is clamped to block 0 and ignored); fully-masked rows renormalize to
zeros, matching the fallback's NaN->0 convention.

Same grid-owns-the-sparsity design as splash attention in JAX: the layout
is static, the visitation is data-driven through scalar prefetch, every
matmul is a dense MXU tile.

Measured (v5e-1, 2026-07-30, BigBird layout, H=8, D=64, bf16, chained
device timing): 2.0x vs the jnp gather at S=4096/block=64, 3.0x at
S=8192 (block 64 and 128), bf16-tolerance parity throughout.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_sparse_flash_attention", "block_sparse_flash_backward",
           "reverse_gather"]

NEG_INF = -1e30


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, *rest, block: int,
            causal: bool, sm_scale: float, with_lse: bool = False):
    # q_ref/o_ref: [1, 1, 1, block, D]; k_ref/v_ref: [1, 1, 1, block, D]
    # scratch: m_s/l_s [block, 128] f32, acc_s [block, D] f32
    if with_lse:
        lse_ref, m_s, l_s, acc_s = rest
    else:
        m_s, l_s, acc_s = rest
        lse_ref = None
    i = pl.program_id(2)
    a = pl.program_id(3)
    num_a = pl.num_programs(3)
    h = pl.program_id(1)
    kb = idx_ref[h, i, a]

    @pl.when(a == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(kb >= 0)
    def _compute():
        q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale   # [block, D]
        k = k_ref[0, 0, 0].astype(jnp.float32)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = (i * block
                    + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0))
            kpos = (kb * block
                    + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # re-mask: rows with every key masked have m_new == NEG_INF and
        # exp(s - m) would be exp(0) = 1 for the masked entries
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(a == num_a - 1)
    def _finish():
        l = jnp.maximum(l_s[:, :1], 1e-30)   # fully-masked rows -> zeros
        o_ref[0, 0, 0] = (acc_s[:] / l).astype(o_ref.dtype)
        if with_lse:
            lse = m_s[:, :1] + jnp.log(l)    # [block, 1]
            lse_ref[0, 0, 0] = lse[:, 0]


def block_sparse_flash_attention(q, k, v, kb_idx, block: int,
                                 causal: bool = True,
                                 scale: Optional[float] = None,
                                 return_lse: bool = False):
    """Fused block-sparse attention (see module docstring).

    q/k/v: [B, S, H, D]; kb_idx: [H, nqb, A] int32, -1 padding.
    Returns [B, S, H, D] in q.dtype (with return_lse: also the logsumexp
    [B, H, nqb, block] f32 the backward kernels consume).
    """
    B, S, H, D = q.shape
    nb = S // block
    nqb, A = kb_idx.shape[1], kb_idx.shape[2]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qb = q.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    kb = k.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    vb = v.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    idx = jnp.asarray(kb_idx, jnp.int32)

    out_specs = pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, i, a, idx: (b, h, i, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, H, nb, block, D), q.dtype)
    if return_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, 1, block),
                                  lambda b, h, i, a, idx: (b, h, i, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, H, nqb, block), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nqb, A),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block, D),
                         lambda b, h, i, a, idx: (b, h, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block, D),
                         lambda b, h, i, a, idx: (
                             b, h, jnp.maximum(idx[h, i, a], 0), 0, 0)),
            pl.BlockSpec((1, 1, 1, block, D),
                         lambda b, h, i, a, idx: (
                             b, h, jnp.maximum(idx[h, i, a], 0), 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, block=block, causal=causal,
                               sm_scale=sm_scale, with_lse=return_lse)
    out = pl.pallas_call(
        kernel,
        name="sparse_flash_fwd",
        grid_spec=grid_spec,
        out_shape=out_shape,
    )(idx, qb, kb, vb)
    if return_lse:
        out, lse = out
        return out.reshape(B, H, S, D).transpose(0, 2, 1, 3), lse
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ----------------------------------------------------------------------
# backward kernels (reference: the Triton block-sparse matmul backward,
# deepspeed/ops/sparse_attention/matmul.py)
# ----------------------------------------------------------------------
def reverse_gather(kb_idx: "np.ndarray") -> "np.ndarray":
    """Invert the [H, nqb, A] gather table: rev[h, kb, r] lists the
    q-blocks whose row visits key block kb (-1 padded).  Host-side numpy;
    the result rides the dk/dv grid as scalar prefetch."""
    import numpy as np
    kb_idx = np.asarray(kb_idx)
    H, nqb, A = kb_idx.shape
    nkb = nqb  # square layouts
    lists = [[[] for _ in range(nkb)] for _ in range(H)]
    for h in range(H):
        for i in range(nqb):
            for a in range(A):
                kb = int(kb_idx[h, i, a])
                if kb >= 0:
                    lists[h][kb].append(i)
    R = max(1, max(len(l) for hl in lists for l in hl))
    rev = -np.ones((H, nkb, R), np.int32)
    for h in range(H):
        for kb in range(nkb):
            rev[h, kb, :len(lists[h][kb])] = lists[h][kb]
    return rev


def _bwd_dq_kernel(idx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_s, *, block: int, causal: bool,
                   sm_scale: float):
    i = pl.program_id(2)
    a = pl.program_id(3)
    num_a = pl.num_programs(3)
    h = pl.program_id(1)
    kb = idx_ref[h, i, a]

    @pl.when(a == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(kb >= 0)
    def _compute():
        q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale   # [block, D]
        k = k_ref[0, 0, 0].astype(jnp.float32)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        do = do_ref[0, 0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]                     # [block, 1]
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = (i * block
                    + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0))
            kpos = (kb * block
                    + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_s[:] = acc_s[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(a == num_a - 1)
    def _finish():
        dq_ref[0, 0, 0] = (acc_s[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(rev_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, block: int,
                    causal: bool, sm_scale: float):
    kbi = pl.program_id(2)
    r = pl.program_id(3)
    num_r = pl.num_programs(3)
    h = pl.program_id(1)
    qb = rev_ref[h, kbi, r]

    @pl.when(r == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(qb >= 0)
    def _compute():
        q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale   # [block, D]
        k = k_ref[0, 0, 0].astype(jnp.float32)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        do = do_ref[0, 0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = (qb * block
                    + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0))
            kpos = (kbi * block
                    + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [block, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(r == num_r - 1)
    def _finish():
        dk_ref[0, 0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0, 0] = dv_s[:].astype(dv_ref.dtype)


def block_sparse_flash_backward(q, k, v, kb_idx, rev_idx, out, do, lse,
                                block: int, causal: bool = True,
                                scale: Optional[float] = None):
    """Fused backward for `block_sparse_flash_attention`.

    q/k/v/out/do: [B, S, H, D]; kb_idx: [H, nqb, A]; rev_idx: [H, nkb, R]
    from `reverse_gather(kb_idx)`; lse: [B, H, nqb, block] f32 (forward's
    return_lse output).  Returns (dq, dk, dv) in q.dtype.
    """
    B, S, H, D = q.shape
    nb = S // block
    nqb, A = kb_idx.shape[1], kb_idx.shape[2]
    R = rev_idx.shape[2]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)

    tr = lambda x: x.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    qb_, kb_, vb_, dob, ob = tr(q), tr(k), tr(v), tr(do), tr(out)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)                         # [B, H, nb, block]
    idx = jnp.asarray(kb_idx, jnp.int32)
    rev = jnp.asarray(rev_idx, jnp.int32)

    # ---- dq: same visitation as the forward ------------------------
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block=block, causal=causal,
                          sm_scale=sm_scale),
        name="sparse_flash_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nqb, A),
            in_specs=[
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, i, a, idx: (b, h, i, 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, i, a, idx: (
                                 b, h, jnp.maximum(idx[h, i, a], 0), 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, i, a, idx: (
                                 b, h, jnp.maximum(idx[h, i, a], 0), 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, i, a, idx: (b, h, i, 0, 0)),
                pl.BlockSpec((1, 1, 1, block),
                             lambda b, h, i, a, idx: (b, h, i, 0)),
                pl.BlockSpec((1, 1, 1, block),
                             lambda b, h, i, a, idx: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, block, D),
                                   lambda b, h, i, a, idx: (b, h, i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, nb, block, D), q.dtype),
    )(idx, qb_, kb_, vb_, dob, lse, delta)

    # ---- dk/dv: reverse visitation ---------------------------------
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block=block, causal=causal,
                          sm_scale=sm_scale),
        name="sparse_flash_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nb, R),
            in_specs=[
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, kb, r, rv: (
                                 b, h, jnp.maximum(rv[h, kb, r], 0), 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, kb, r, rv: (b, h, kb, 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, kb, r, rv: (b, h, kb, 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, kb, r, rv: (
                                 b, h, jnp.maximum(rv[h, kb, r], 0), 0, 0)),
                pl.BlockSpec((1, 1, 1, block),
                             lambda b, h, kb, r, rv: (
                                 b, h, jnp.maximum(rv[h, kb, r], 0), 0)),
                pl.BlockSpec((1, 1, 1, block),
                             lambda b, h, kb, r, rv: (
                                 b, h, jnp.maximum(rv[h, kb, r], 0), 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, kb, r, rv: (b, h, kb, 0, 0)),
                pl.BlockSpec((1, 1, 1, block, D),
                             lambda b, h, kb, r, rv: (b, h, kb, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nb, block, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, nb, block, D), q.dtype),
        ],
    )(rev, qb_, kb_, vb_, dob, lse, delta)

    back = lambda x: x.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    return back(dq), back(dk), back(dv)
