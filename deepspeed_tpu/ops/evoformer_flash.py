"""Pallas TPU Evoformer attention kernels (MSA/triangle attention with pair
biases) — forward AND backward.

Replaces the reference's CUTLASS fMHA-with-bias kernels
(csrc/deepspeed4science/evoformer_attn/kernel_forward.h:986 and
kernel_backward.h:1965) behind `DS4Sci_EvoformerAttention`: flash-style
online softmax over key blocks with up to two additive biases — the
per-row key mask bias [B, N, 1, 1, L] and the pair-representation bias
[B, 1, H, L, L] — added to each score tile in VMEM.  The [B, N, H, L, L]
score tensor never materializes; neither do broadcast copies of the
biases.

Backward is the standard flash three-way split, with the pair-bias
gradient getting its own reduction kernel (the reference accumulates dB
with atomics; on TPU the N-reduction rides the grid instead):
- dq kernel: grid (BN, iq), fori over key blocks.
- dk/dv kernel: grid (BN, jk, iq) with iq minormost — dk/dv accumulate in
  VMEM scratch across the consecutive iq steps and write once.
- db2 kernel: grid (B, iq, jk, n) with n minormost — ds accumulates into
  the [H, bq, bk] pair-bias tile across the consecutive n steps (the
  sum over MSA rows the bias broadcast implies).
- db1 kernel: grid (BN, jk, iq) with iq minormost — ds summed over heads
  and query rows into the [bk] mask-bias row (the reference exposes this
  behind its bias1-grad flag; here it is computed whenever b1 is given).
All four recompute p = exp(s - lse) from the saved q/k/v and the
forward's logsumexp (emitted slim as [BN, H, L]).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["evoformer_flash_forward", "evoformer_flash_forward_dmajor",
           "evoformer_flash_backward"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, *rest, bq: int, bk: int, sm_scale: float,
            has_b1: bool, has_b2: bool, with_lse: bool = False):
    # one grid step handles ALL H heads of one (b, n) row — batched dots
    # keep the MXU busy where per-head [bq, D] tiles (D is 32 in
    # AlphaFold-class models) would leave it mostly idle
    refs = list(rest)
    b1_ref = refs.pop(0) if has_b1 else None
    b2_ref = refs.pop(0) if has_b2 else None
    lse_ref = refs.pop(1) if with_lse else None
    o_ref, m_s, l_s, acc_s = refs
    jk = pl.program_id(2)
    num_jk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32) * sm_scale         # [H, bq, D]
    k = k_ref[0].astype(jnp.float32)                    # [H, bk, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # [H,bq,bk]
    if has_b1:
        # [bq, bk] tile; broadcast only over the leading (head) dim — a
        # lane-dim vector broadcast over tiled dims crashes the backend
        s = s + b1_ref[0, 0].astype(jnp.float32)[None]
    if has_b2:
        s = s + b2_ref[0].astype(jnp.float32)           # [H, bq, bk]

    m_prev = m_s[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    # re-mask: a tile whose biases are all -inf-like must contribute zeros
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_s[..., :1] + jnp.sum(p, axis=2, keepdims=True)
    acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(jk == num_jk - 1)
    def _finish():
        l = jnp.maximum(l_s[..., :1], 1e-9)
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)
        if with_lse:
            # slim [H, bq] logsumexp (lanes = bq): the backward kernels
            # re-expand per tile, so no [BN,H,L,128] padded copy ever
            # lands in HBM
            lse = m_s[..., :1] + jnp.log(l)            # [H, bq, 1]
            lse_ref[0] = lse[..., 0]


def evoformer_flash_forward(q, k, v, b1=None, b2=None,
                            block_q: int = 128, block_k: int = 128,
                            scale: Optional[float] = None,
                            return_lse: bool = False):
    """q/k/v: [B, N, L, H, D]; b1: [B, N, 1, 1, L] mask bias or None;
    b2: [B, 1, H, L, L] pair bias or None.  Returns [B, N, L, H, D]
    (with return_lse: also the logsumexp [B*N, H, L] f32 the backward
    kernels consume)."""
    B, N, L, H, D = q.shape
    bq = min(block_q, L)
    bk = min(block_k, L)
    if L % bq or L % bk:
        raise ValueError(f"L={L} must divide block_q={bq} / block_k={bk}")
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    BN = B * N

    qh = q.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    kh = k.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    vh = v.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)

    grid = (BN, L // bq, L // bk)
    in_specs = [
        pl.BlockSpec((1, H, bq, D), lambda bn, iq, jk: (bn, 0, iq, 0)),
        pl.BlockSpec((1, H, bk, D), lambda bn, iq, jk: (bn, 0, jk, 0)),
        pl.BlockSpec((1, H, bk, D), lambda bn, iq, jk: (bn, 0, jk, 0)),
    ]
    args = [qh, kh, vh]
    if b1 is not None:
        # replicate each key row to a full [bq, bk] tile: 1-row tiles (in
        # any dtype) and in-kernel lane-vector broadcasts both trip the
        # backend's tiling checks; bq rows of f32 is ~bq x a [BN, L]
        # vector — small next to K/V, and the [L, L]-sized copy the jnp
        # path broadcasts never exists
        rows = jnp.broadcast_to(
            b1.astype(jnp.float32).reshape(BN, L // bk, 1, bk),
            (BN, L // bk, bq, bk))
        args.append(rows)
        in_specs.append(
            pl.BlockSpec((1, 1, bq, bk), lambda bn, iq, jk: (bn, jk, 0, 0)))
    if b2 is not None:
        # squeeze the broadcast dim; index batch as bn // N
        args.append(b2.reshape(B, H, L, L))
        in_specs.append(
            pl.BlockSpec((1, H, bq, bk),
                         lambda bn, iq, jk: (bn // N, 0, iq, jk)))

    kernel = functools.partial(_kernel, bq=bq, bk=bk, sm_scale=sm_scale,
                               has_b1=b1 is not None, has_b2=b2 is not None,
                               with_lse=return_lse)
    out_specs = pl.BlockSpec((1, H, bq, D), lambda bn, iq, jk: (bn, 0, iq, 0))
    out_shape = jax.ShapeDtypeStruct((BN, H, L, D), q.dtype)
    if return_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, H, bq), lambda bn, iq, jk: (bn, 0, iq))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((BN, H, L), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        name="evoformer_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((H, bq, 128), jnp.float32),
            pltpu.VMEM((H, bq, 128), jnp.float32),
            pltpu.VMEM((H, bq, D), jnp.float32),
        ],
    )(*args)
    if return_lse:
        out, lse = out
        return (out.reshape(B, N, H, L, D).transpose(0, 1, 3, 2, 4)
                .astype(q.dtype), lse)
    return (out.reshape(B, N, H, L, D).transpose(0, 1, 3, 2, 4)
            .astype(q.dtype))


# ----------------------------------------------------------------------
# backward kernels (reference: kernel_backward.h:1965)
# ----------------------------------------------------------------------
def _p_tile(q, k, b1_tile, b2_tile, lse_col):
    """Recompute the probability tile: q [H,bq,D] (pre-scaled) f32,
    k [H,bk,D] f32, lse_col [H,bq,1] f32 -> (s, p) [H,bq,bk] f32."""
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    if b1_tile is not None:
        s = s + b1_tile
    if b2_tile is not None:
        s = s + b2_tile
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse_col), 0.0)
    return p


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   bq: int, bk: int, sm_scale: float, has_b1: bool,
                   has_b2: bool, num_jk: int):
    refs = list(rest)
    b1_ref = refs.pop(0) if has_b1 else None
    b2_ref = refs.pop(0) if has_b2 else None
    (dq_ref,) = refs

    q = q_ref[0].astype(jnp.float32) * sm_scale        # [H, bq, D]
    do = do_ref[0].astype(jnp.float32)
    lse_col = lse_ref[0][..., None]                    # [H, bq, 1]
    delta_col = delta_ref[0][..., None]
    H, _, D = q.shape

    def body(jk, acc):
        k = k_ref[0, :, pl.ds(jk * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, :, pl.ds(jk * bk, bk), :].astype(jnp.float32)
        b1_t = (b1_ref[0, jk][None].astype(jnp.float32)
                if has_b1 else None)
        b2_t = (b2_ref[0, :, :, pl.ds(jk * bk, bk)].astype(jnp.float32)
                if has_b2 else None)
        p = _p_tile(q, k, b1_t, b2_t, lse_col)
        dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_col)
        return acc + jax.lax.dot_general(
            ds, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, num_jk, body,
                            jnp.zeros((H, bq, D), jnp.float32))
    dq_ref[0] = (acc * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    bq: int, bk: int, sm_scale: float, has_b1: bool,
                    has_b2: bool):
    refs = list(rest)
    b1_ref = refs.pop(0) if has_b1 else None
    b2_ref = refs.pop(0) if has_b2 else None
    dk_ref, dv_ref, dk_s, dv_s = refs
    iq = pl.program_id(2)
    num_iq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q = q_ref[0].astype(jnp.float32) * sm_scale        # [H, bq, D]
    k = k_ref[0].astype(jnp.float32)                   # [H, bk, D]
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse_col = lse_ref[0][..., None]
    delta_col = delta_ref[0][..., None]
    b1_t = b1_ref[0, 0][None].astype(jnp.float32) if has_b1 else None
    b2_t = b2_ref[0].astype(jnp.float32) if has_b2 else None
    p = _p_tile(q, k, b1_t, b2_t, lse_col)             # [H, bq, bk]
    dv_s[:] = dv_s[:] + jax.lax.dot_general(
        p, do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # [H, bk, D]
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col)
    dk_s[:] = dk_s[:] + jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)            # [H, bk, D]

    @pl.when(iq == num_iq - 1)
    def _finish():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_db2_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    bq: int, bk: int, sm_scale: float, has_b1: bool,
                    has_b2: bool):
    refs = list(rest)
    b1_ref = refs.pop(0) if has_b1 else None
    b2_ref = refs.pop(0) if has_b2 else None
    db2_ref, acc_s = refs
    n = pl.program_id(3)
    num_n = pl.num_programs(3)

    @pl.when(n == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32) * sm_scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse_col = lse_ref[0][..., None]
    delta_col = delta_ref[0][..., None]
    b1_t = b1_ref[0, 0][None].astype(jnp.float32) if has_b1 else None
    b2_t = b2_ref[0].astype(jnp.float32) if has_b2 else None
    p = _p_tile(q, k, b1_t, b2_t, lse_col)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_s[:] = acc_s[:] + p * (dp - delta_col)

    @pl.when(n == num_n - 1)
    def _finish():
        db2_ref[0] = acc_s[:].astype(db2_ref.dtype)


def _bwd_db1_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    bq: int, bk: int, sm_scale: float, has_b1: bool,
                    has_b2: bool):
    refs = list(rest)
    b1_ref = refs.pop(0) if has_b1 else None
    b2_ref = refs.pop(0) if has_b2 else None
    db1_ref, acc_s = refs
    iq = pl.program_id(2)
    num_iq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32) * sm_scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse_col = lse_ref[0][..., None]
    delta_col = delta_ref[0][..., None]
    b1_t = b1_ref[0, 0][None].astype(jnp.float32) if has_b1 else None
    b2_t = b2_ref[0].astype(jnp.float32) if has_b2 else None
    p = _p_tile(q, k, b1_t, b2_t, lse_col)
    dp = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col)                          # [H, bq, bk]
    # the mask bias broadcasts over heads and query rows -> sum both
    acc_s[:] = acc_s[:] + jnp.sum(ds, axis=(0, 1))[None, :]

    @pl.when(iq == num_iq - 1)
    def _finish():
        db1_ref[0] = acc_s[0]


def evoformer_flash_backward(q, k, v, b1, b2, out, do, lse,
                             block_q: int = 128, block_k: int = 128,
                             scale: Optional[float] = None,
                             need_db1: bool = True, need_db2: bool = True):
    """Flash backward for `evoformer_flash_forward`.

    q/k/v/out/do: [B, N, L, H, D]; lse: [B*N, H, L] f32 (forward's
    return_lse output); b1: [B, N, 1, 1, L] or None; b2: [B, 1, H, L, L]
    or None.  Returns (dq, dk, dv, db1, db2); db1/db2 are None when the
    corresponding bias is absent or not requested.
    """
    B, N, L, H, D = q.shape
    bq = min(block_q, L)
    bk = min(block_k, L)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    BN = B * N

    qh = q.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    kh = k.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    vh = v.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    doh = do.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    oh = out.transpose(0, 1, 3, 2, 4).reshape(BN, H, L, D)
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32),
                    axis=-1)                            # [BN, H, L]

    b1rows = None
    if b1 is not None:
        b1rows = jnp.broadcast_to(
            b1.astype(jnp.float32).reshape(BN, L // bk, 1, bk),
            (BN, L // bk, bq, bk))
    b2h = b2.reshape(B, H, L, L) if b2 is not None else None
    has_b1, has_b2 = b1 is not None, b2 is not None

    def bias_specs_dq():
        specs, args = [], []
        if has_b1:
            specs.append(pl.BlockSpec(
                (1, L // bk, bq, bk), lambda bn, iq: (bn, 0, 0, 0)))
            args.append(b1rows)
        if has_b2:
            specs.append(pl.BlockSpec(
                (1, H, bq, L), lambda bn, iq: (bn // N, 0, iq, 0)))
            args.append(b2h)
        return specs, args

    # ---- dq: grid (BN, iq), fori over key blocks --------------------
    bspecs, bargs = bias_specs_dq()
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, sm_scale=sm_scale,
                          has_b1=has_b1, has_b2=has_b2, num_jk=L // bk),
        name="evoformer_dq",
        grid=(BN, L // bq),
        in_specs=[
            pl.BlockSpec((1, H, bq, D), lambda bn, iq: (bn, 0, iq, 0)),
            pl.BlockSpec((1, H, L, D), lambda bn, iq: (bn, 0, 0, 0)),
            pl.BlockSpec((1, H, L, D), lambda bn, iq: (bn, 0, 0, 0)),
            pl.BlockSpec((1, H, bq, D), lambda bn, iq: (bn, 0, iq, 0)),
            pl.BlockSpec((1, H, bq), lambda bn, iq: (bn, 0, iq)),
            pl.BlockSpec((1, H, bq), lambda bn, iq: (bn, 0, iq)),
        ] + bspecs,
        out_specs=pl.BlockSpec((1, H, bq, D), lambda bn, iq: (bn, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BN, H, L, D), q.dtype),
    )(qh, kh, vh, doh, lse, delta, *bargs)

    # ---- dk/dv: grid (BN, jk, iq), iq minormost ----------------------
    bspecs, bargs = [], []
    if has_b1:
        bspecs.append(pl.BlockSpec(
            (1, 1, bq, bk), lambda bn, jk, iq: (bn, jk, 0, 0)))
        bargs.append(b1rows)
    if has_b2:
        bspecs.append(pl.BlockSpec(
            (1, H, bq, bk), lambda bn, jk, iq: (bn // N, 0, iq, jk)))
        bargs.append(b2h)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, sm_scale=sm_scale,
                          has_b1=has_b1, has_b2=has_b2),
        name="evoformer_dkv",
        grid=(BN, L // bk, L // bq),
        in_specs=[
            pl.BlockSpec((1, H, bq, D), lambda bn, jk, iq: (bn, 0, iq, 0)),
            pl.BlockSpec((1, H, bk, D), lambda bn, jk, iq: (bn, 0, jk, 0)),
            pl.BlockSpec((1, H, bk, D), lambda bn, jk, iq: (bn, 0, jk, 0)),
            pl.BlockSpec((1, H, bq, D), lambda bn, jk, iq: (bn, 0, iq, 0)),
            pl.BlockSpec((1, H, bq), lambda bn, jk, iq: (bn, 0, iq)),
            pl.BlockSpec((1, H, bq), lambda bn, jk, iq: (bn, 0, iq)),
        ] + bspecs,
        out_specs=[
            pl.BlockSpec((1, H, bk, D), lambda bn, jk, iq: (bn, 0, jk, 0)),
            pl.BlockSpec((1, H, bk, D), lambda bn, jk, iq: (bn, 0, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((BN, H, L, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, bk, D), jnp.float32),
            pltpu.VMEM((H, bk, D), jnp.float32),
        ],
    )(qh, kh, vh, doh, lse, delta, *bargs)

    # ---- db2: grid (B, iq, jk, n), n minormost ----------------------
    db2 = None
    if has_b2 and need_db2:
        bspecs, bargs = [], []
        if has_b1:
            bspecs.append(pl.BlockSpec(
                (1, 1, bq, bk), lambda b, iq, jk, n: (b * N + n, jk, 0, 0)))
            bargs.append(b1rows)
        bspecs.append(pl.BlockSpec(
            (1, H, bq, bk), lambda b, iq, jk, n: (b, 0, iq, jk)))
        bargs.append(b2h)
        db2 = pl.pallas_call(
            functools.partial(_bwd_db2_kernel, bq=bq, bk=bk,
                              sm_scale=sm_scale, has_b1=has_b1,
                              has_b2=True),
            name="evoformer_db2",
            grid=(B, L // bq, L // bk, N),
            in_specs=[
                pl.BlockSpec((1, H, bq, D),
                             lambda b, iq, jk, n: (b * N + n, 0, iq, 0)),
                pl.BlockSpec((1, H, bk, D),
                             lambda b, iq, jk, n: (b * N + n, 0, jk, 0)),
                pl.BlockSpec((1, H, bk, D),
                             lambda b, iq, jk, n: (b * N + n, 0, jk, 0)),
                pl.BlockSpec((1, H, bq, D),
                             lambda b, iq, jk, n: (b * N + n, 0, iq, 0)),
                pl.BlockSpec((1, H, bq),
                             lambda b, iq, jk, n: (b * N + n, 0, iq)),
                pl.BlockSpec((1, H, bq),
                             lambda b, iq, jk, n: (b * N + n, 0, iq)),
            ] + bspecs,
            out_specs=pl.BlockSpec((1, H, bq, bk),
                                   lambda b, iq, jk, n: (b, 0, iq, jk)),
            out_shape=jax.ShapeDtypeStruct((B, H, L, L), jnp.float32),
            scratch_shapes=[pltpu.VMEM((H, bq, bk), jnp.float32)],
        )(qh, kh, vh, doh, lse, delta, *bargs)
        db2 = db2.reshape(B, 1, H, L, L).astype(b2.dtype)

    # ---- db1: grid (BN, jk, iq), iq minormost -----------------------
    db1 = None
    if has_b1 and need_db1:
        bspecs, bargs = [], []
        bspecs.append(pl.BlockSpec(
            (1, 1, bq, bk), lambda bn, jk, iq: (bn, jk, 0, 0)))
        bargs.append(b1rows)
        if has_b2:
            bspecs.append(pl.BlockSpec(
                (1, H, bq, bk), lambda bn, jk, iq: (bn // N, 0, iq, jk)))
            bargs.append(b2h)
        db1 = pl.pallas_call(
            functools.partial(_bwd_db1_kernel, bq=bq, bk=bk,
                              sm_scale=sm_scale, has_b1=True,
                              has_b2=has_b2),
            name="evoformer_db1",
            grid=(BN, L // bk, L // bq),
            in_specs=[
                pl.BlockSpec((1, H, bq, D),
                             lambda bn, jk, iq: (bn, 0, iq, 0)),
                pl.BlockSpec((1, H, bk, D),
                             lambda bn, jk, iq: (bn, 0, jk, 0)),
                pl.BlockSpec((1, H, bk, D),
                             lambda bn, jk, iq: (bn, 0, jk, 0)),
                pl.BlockSpec((1, H, bq, D),
                             lambda bn, jk, iq: (bn, 0, iq, 0)),
                pl.BlockSpec((1, H, bq), lambda bn, jk, iq: (bn, 0, iq)),
                pl.BlockSpec((1, H, bq), lambda bn, jk, iq: (bn, 0, iq)),
            ] + bspecs,
            out_specs=pl.BlockSpec((1, bk), lambda bn, jk, iq: (bn, jk)),
            out_shape=jax.ShapeDtypeStruct((BN, L), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, bk), jnp.float32)],
        )(qh, kh, vh, doh, lse, delta, *bargs)
        db1 = db1.reshape(B, N, 1, 1, L).astype(b1.dtype)

    to_in = lambda x: (x.reshape(B, N, H, L, D)
                       .transpose(0, 1, 3, 2, 4).astype(q.dtype))
    return to_in(dq), to_in(dk), to_in(dv), db1, db2


# ----------------------------------------------------------------------
# D-major forward variant for narrow heads (AlphaFold's D=32)
# ----------------------------------------------------------------------
def _kernel_dmajor(q_ref, k_ref, v_ref, *rest, bq: int, bk: int,
                   sm_scale: float, has_b1: bool, has_b2: bool,
                   with_lse: bool = False):
    # D-major blocks: q [1, H, D, bq], k/v [1, H, D, bk], out [1, H, D, bq]
    # — the minor dim is a 128-multiple L tile, so a D=32 head is stored
    # and DMA'd UNPADDED (D-minor blocks lane-pad 32 -> 128 = 4x traffic,
    # which is why the D-minor kernel lost to XLA at D=32)
    refs = list(rest)
    b1_ref = refs.pop(0) if has_b1 else None
    b2_ref = refs.pop(0) if has_b2 else None
    lse_ref = refs.pop(1) if with_lse else None
    o_ref, m_s, l_s, acc_s = refs
    jk = pl.program_id(2)
    num_jk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32) * sm_scale         # [H, D, bq]
    k = k_ref[0].astype(jnp.float32)                    # [H, D, bk]
    v = v_ref[0].astype(jnp.float32)
    # contract the D sublane dim: [H, D, bq] x [H, D, bk] -> [H, bq, bk]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    if has_b1:
        s = s + b1_ref[0, 0].astype(jnp.float32)[None]
    if has_b2:
        s = s + b2_ref[0].astype(jnp.float32)           # [H, bq, bk]

    m_prev = m_s[..., :1]                               # [H, bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)                     # [H, bq, 1]
    l_new = alpha * l_s[..., :1] + jnp.sum(p, axis=2, keepdims=True)
    # [H, D, bk] x [H, bq, bk] contract bk -> [H, D, bq]
    pv = jax.lax.dot_general(v, p, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_s[:] = acc_s[:] * jnp.swapaxes(alpha, 1, 2) + pv
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(jk == num_jk - 1)
    def _finish():
        l = jnp.maximum(l_s[..., :1], 1e-9)             # [H, bq, 1]
        o_ref[0] = (acc_s[:] / jnp.swapaxes(l, 1, 2)).astype(o_ref.dtype)
        if with_lse:
            lse = m_s[..., :1] + jnp.log(l)
            lse_ref[0] = lse[..., 0]


def evoformer_flash_forward_dmajor(q, k, v, b1=None, b2=None,
                                   block_q: int = 128, block_k: int = 128,
                                   scale: Optional[float] = None,
                                   return_lse: bool = False):
    """D-major twin of `evoformer_flash_forward` for D < 64: operands and
    output are staged [BN, H, D, L] so narrow heads are never lane-padded.
    Same signature/results; the extra in/out transposes are XLA ops on
    unpadded data."""
    B, N, L, H, D = q.shape
    bq = min(block_q, L)
    bk = min(block_k, L)
    if L % bq or L % bk:
        raise ValueError(f"L={L} must divide block_q={bq} / block_k={bk}")
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(D)
    BN = B * N

    qh = q.transpose(0, 1, 3, 4, 2).reshape(BN, H, D, L)
    kh = k.transpose(0, 1, 3, 4, 2).reshape(BN, H, D, L)
    vh = v.transpose(0, 1, 3, 4, 2).reshape(BN, H, D, L)

    grid = (BN, L // bq, L // bk)
    in_specs = [
        pl.BlockSpec((1, H, D, bq), lambda bn, iq, jk: (bn, 0, 0, iq)),
        pl.BlockSpec((1, H, D, bk), lambda bn, iq, jk: (bn, 0, 0, jk)),
        pl.BlockSpec((1, H, D, bk), lambda bn, iq, jk: (bn, 0, 0, jk)),
    ]
    args = [qh, kh, vh]
    if b1 is not None:
        rows = jnp.broadcast_to(
            b1.astype(jnp.float32).reshape(BN, L // bk, 1, bk),
            (BN, L // bk, bq, bk))
        args.append(rows)
        in_specs.append(
            pl.BlockSpec((1, 1, bq, bk), lambda bn, iq, jk: (bn, jk, 0, 0)))
    if b2 is not None:
        args.append(b2.reshape(B, H, L, L))
        in_specs.append(
            pl.BlockSpec((1, H, bq, bk),
                         lambda bn, iq, jk: (bn // N, 0, iq, jk)))

    kernel = functools.partial(_kernel_dmajor, bq=bq, bk=bk,
                               sm_scale=sm_scale, has_b1=b1 is not None,
                               has_b2=b2 is not None, with_lse=return_lse)
    out_specs = pl.BlockSpec((1, H, D, bq), lambda bn, iq, jk: (bn, 0, 0, iq))
    out_shape = jax.ShapeDtypeStruct((BN, H, D, L), q.dtype)
    if return_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, H, bq), lambda bn, iq, jk: (bn, 0, iq))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((BN, H, L), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        name="evoformer_fwd_dmajor",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((H, bq, 128), jnp.float32),
            pltpu.VMEM((H, bq, 128), jnp.float32),
            pltpu.VMEM((H, D, bq), jnp.float32),
        ],
    )(*args)
    if return_lse:
        out, lse = out
        return (out.reshape(B, N, H, D, L).transpose(0, 1, 4, 2, 3)
                .astype(q.dtype), lse)
    return (out.reshape(B, N, H, D, L).transpose(0, 1, 4, 2, 3)
            .astype(q.dtype))
