"""Pallas TPU flash attention (forward + backward).

Replaces the reference's fused attention CUDA kernels:
- training softmax/attention (csrc/transformer/softmax_kernels.cu:701,
  general attention path of ds_transformer_cuda.cpp)
- inference fused softmax (csrc/transformer/inference/softmax.cu:562)
- the memory-efficient fMHA of DS4Science
  (csrc/deepspeed4science/evoformer_attn/kernel_forward.h:986 /
  kernel_backward.h:1965)

Algorithm: FlashAttention-2-style online softmax. One grid step per
(batch, head, q-block); an inner `fori_loop` walks k/v blocks held in VMEM,
maintaining running max/sum and a fp32 accumulator so the full [S,S] score
matrix never materializes.  Causal blocks beyond the diagonal are skipped by
bounding the loop, not masked — ~2x fewer FLOPs than a masked dense sweep.

Backward follows the standard two-kernel split:
- dq kernel: same layout as forward, loops over k-blocks.
- dk/dv kernel: grid over k-blocks, loops over q-blocks from the diagonal.
Both consume the saved logsumexp and the precomputed row dot
delta = rowsum(dO * O).

GQA is handled in the BlockSpec index maps (q-head h reads kv-head
h // group) — no materialized KV repeat.

Layout notes (guide: /opt/skills/guides/pallas_guide.md): blocks are
(block_q|k, head_dim) with head_dim padded to a multiple of 128 lanes by the
caller; accumulation always fp32 via preferred_element_type.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _scale_exact_in_dtype(sm_scale: float) -> bool:
    """True when sm_scale is a power of two — multiplying a bf16 tensor by
    it is exact (exponent shift only), so q can be pre-scaled per [bq, D]
    tile instead of post-scaling every [bq, bk] fp32 score block.  D = 64
    (GPT-2 family) and D = 256 hit this; D = 128 (2^-3.5) does not."""
    m, e = math.frexp(sm_scale)
    return m == 0.5


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                block_k: int, sm_scale: float, causal: bool, seq_len: int):
    # q_ref: [block_q, D]; k_ref/v_ref: [S, D]; o_ref: [block_q, D]
    # lse_ref: [block_q, 128] (lane-padded logsumexp, column 0 is live)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    iq = pl.program_id(2)

    # keep q in its storage dtype: the MXU multiplies bf16 inputs with fp32
    # accumulation (preferred_element_type) at full rate, while fp32 x fp32
    # matmuls run ~8x slower via multi-pass decomposition.  When sm_scale
    # is a power of two the bf16 pre-scale of the [bq, D] q tile is exact
    # and replaces a per-pair [bq, bk] fp32 multiply (VPU-bound kernel);
    # otherwise sm_scale is applied to the fp32 scores.
    prescale = _scale_exact_in_dtype(sm_scale)
    q = q_ref[:]
    if prescale:
        q = q * jnp.asarray(sm_scale, q.dtype)

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    if causal:
        # number of k blocks this q block attends to (static per-iq bound
        # computed dynamically from the grid index)
        num_k = jnp.minimum((iq + 1) * block_q + block_k - 1, seq_len) // block_k
        # blocks whose every key is visible to every query row of this tile
        # — they skip the mask (and its iotas) entirely.  The kernel is
        # VPU-bound at small head dims, so dropping those elementwise
        # passes matters more than the matmuls.
        num_full = (iq * block_q + 1) // block_k
    else:
        num_k = seq_len // block_k
        num_full = num_k

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def make_body(masked: bool):
        def body(ik, carry):
            m, l, acc = carry
            k = k_ref[pl.ds(ik * block_k, block_k), :]
            v = v_ref[pl.ds(ik * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk]
            if not prescale:
                s = s * sm_scale
            if masked:
                k_pos = ik * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    carry = jax.lax.fori_loop(0, num_full, make_body(False), (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(num_full, num_k, make_body(causal), carry)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse = (m + jnp.log(l))  # [block_q, 1]
    lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, dq_ref, *,
                   block_k: int, sm_scale: float, causal: bool, seq_len: int):
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    iq = pl.program_id(2)

    # bf16 matmul inputs, fp32 accumulation + exact power-of-two q
    # pre-scale (see _fwd_kernel dtype note)
    prescale = _scale_exact_in_dtype(sm_scale)
    q = q_ref[:]
    if prescale:
        q = q * jnp.asarray(sm_scale, q.dtype)
    do = do_ref[:]
    lse = lse_ref[:, 0:1]
    # delta = rowsum(dO * O) computed in-VMEM from the saved output tile —
    # cheaper than materializing and re-reading a lane-padded [B,N,S,128]
    # fp32 array from HBM (rowsum over D=64..128 is trivial VPU work)
    delta = jnp.sum(do_ref[:].astype(jnp.float32) *
                    out_ref[:].astype(jnp.float32), axis=1, keepdims=True)

    # NOTE a fused dq+dkv single-pass kernel (sequential-grid dq
    # accumulation, both RMW-on-output and VMEM-scratch variants) measured
    # ~30% SLOWER than this two-kernel split at the training geometry: the
    # in-loop [block_q, D] accumulator update defeats Mosaic's software
    # pipelining, while the split kernels reduce cleanly into registers.
    if causal:
        num_k = jnp.minimum((iq + 1) * block_q + block_k - 1, seq_len) // block_k
        num_full = (iq * block_q + 1) // block_k  # mask-free blocks (see fwd)
    else:
        num_k = seq_len // block_k
        num_full = num_k
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def make_body(masked: bool):
        def body(ik, dq):
            k = k_ref[pl.ds(ik * block_k, block_k), :]
            v = v_ref[pl.ds(ik * block_k, block_k), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if not prescale:
                s = s * sm_scale
            if masked:
                k_pos = ik * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(k.dtype)
            return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        return body

    dq = jax.lax.fori_loop(0, num_full, make_body(False),
                           jnp.zeros((block_q, d), jnp.float32))
    dq = jax.lax.fori_loop(num_full, num_k, make_body(causal), dq)
    dq_ref[:] = (dq * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref,
                    dk_ref, dv_ref, *,
                    block_q: int, sm_scale: float, causal: bool, seq_len: int):
    block_k = k_ref.shape[0]
    d = k_ref.shape[1]
    ik = pl.program_id(2)

    # bf16 matmul inputs, fp32 accumulation + exact power-of-two q
    # pre-scale (see _fwd_kernel dtype note)
    prescale = _scale_exact_in_dtype(sm_scale)
    k = k_ref[:]
    v = v_ref[:]

    num_q_blocks = seq_len // block_q
    if causal:
        start_q = (ik * block_k) // block_q
        # first q block whose every row sees this whole k block — from
        # there on the mask (and its iotas) is dropped (see fwd note)
        start_full = ((ik + 1) * block_k + block_q - 1) // block_q
    else:
        start_q = 0
        start_full = 0
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def make_body(masked: bool):
        def body(iq, carry):
            dk, dv = carry
            q = q_ref[pl.ds(iq * block_q, block_q), :]
            if prescale:
                q = q * jnp.asarray(sm_scale, q.dtype)
            do = do_ref[pl.ds(iq * block_q, block_q), :]
            lse = lse_ref[pl.ds(iq * block_q, block_q), 0:1]
            out = out_ref[pl.ds(iq * block_q, block_q), :]
            delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                            axis=1, keepdims=True)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if not prescale:
                s = s * sm_scale
            if masked:
                q_pos = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse)  # [block_q, block_k]
            p_b = p.astype(do.dtype)
            dv_new = dv + jax.lax.dot_general(p_b, do, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32)
            return dk_new, dv_new
        return body

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    stop_masked = jnp.minimum(start_full, num_q_blocks) if causal else start_full
    dk, dv = jax.lax.fori_loop(start_q, stop_masked, make_body(causal),
                               (dk0, dv0))
    dk, dv = jax.lax.fori_loop(stop_masked, num_q_blocks, make_body(False),
                               (dk, dv))
    # chain rule through s = sm_scale * (q @ k^T): with the exact q
    # pre-scale the factor is already baked into dk via q; on the
    # post-scale path dk accumulated unscaled q rows, so fold it in here
    if not prescale:
        dk = dk * sm_scale
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# wrappers
# ----------------------------------------------------------------------
def _heads_layout(x):
    """[B,S,N,D] -> [B,N,S,D]."""
    return jnp.transpose(x, (0, 2, 1, 3))


def _fwd(q, k, v, causal: bool, block_q: int, block_k: int):
    B, Nq, S, D = q.shape
    Nkv = k.shape[1]
    group = Nq // Nkv
    sm_scale = 1.0 / math.sqrt(D)
    grid = (B, Nq, S // block_q)

    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, sm_scale=sm_scale, causal=causal,
        seq_len=S)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n // group, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n // group, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 128), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Nq, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, Nq, S, 128), jnp.float32),
        ],
    )(q, k, v)
    return out, lse


def _index_squeeze(kernel):
    """Adapt kernels written for 2-D refs to the (1,1,...) leading block dims
    pallas delivers: refs arrive as [1,1,rows,cols]; view them as 2-D."""

    @functools.wraps(kernel)
    def wrapped(*refs, **kw):
        class _View:
            __slots__ = ("r",)

            def __init__(self, r):
                self.r = r

            @property
            def shape(self):
                return self.r.shape[2:]

            @property
            def dtype(self):
                return self.r.dtype

            def __getitem__(self, idx):
                if not isinstance(idx, tuple):
                    idx = (idx,)
                return self.r[(0, 0) + idx]

            def __setitem__(self, idx, val):
                if not isinstance(idx, tuple):
                    idx = (idx,)
                self.r[(0, 0) + idx] = val

        return kernel(*[_View(r) for r in refs], **kw)

    return wrapped


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    out, _ = _fwd_res(q, k, v, causal, block_q, block_k)
    return out


def _fwd_res(q, k, v, causal, block_q, block_k):
    out, lse = _fwd(q, k, v, causal, block_q, block_k)
    # residual slimming: the kernel emits lse lane-padded [B,N,S,128] (all
    # columns equal); keep only [B,N,S] as the residual — 128x smaller.
    # Tag out+lse for the save_attn* remat policies: with BOTH saved the
    # remat backward skips the O(S^2) forward kernel entirely (saving only
    # `out` still forces a forward re-run to regenerate lse).
    #
    # The out residual stays in the kernel's [B, N, S, D] layout even
    # though at D = 64 its trailing dim pads to 128 lanes when stacked
    # across the layer scan (2.0x memory, 720 MB at the bench geometry):
    # tagging a lane-dense flat [B, S, N*D] copy instead was MEASURED 4%
    # slower end-to-end (16.7k vs 17.5k tok/s) — the backward's per-layer
    # reshape+transpose to regenerate the kernel layout costs more than
    # the padded save/load traffic.
    from ..runtime.activation_checkpointing import (attn_checkpoint_name,
                                                    lse_checkpoint_name)
    out = attn_checkpoint_name(out)
    lse = lse_checkpoint_name(lse[..., 0])
    return out, (q, k, v, out, lse)


def _fwd_vjp(q, k, v, causal, block_q, block_k):
    out, res = _fwd_res(q, k, v, causal, block_q, block_k)
    return out, res


def _bwd_vjp(causal, block_q, block_k, res, do):
    q, k, v, out, lse = res
    B, Nq, S, D = q.shape
    Nkv = k.shape[1]
    group = Nq // Nkv
    sm_scale = 1.0 / math.sqrt(D)

    lse = jnp.broadcast_to(lse[..., None], (B, Nq, S, 128))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, sm_scale=sm_scale,
                          causal=causal, seq_len=S),
        name="flash_attention_dq",
        grid=(B, Nq, S // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n // group, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n // group, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q, 128), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, n, i: (b, n, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, Nq, S, D), q.dtype),
    )(q, k, v, do, out, lse)

    # dk/dv per q-head, then reduce over the GQA group
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, sm_scale=sm_scale,
                          causal=causal, seq_len=S),
        name="flash_attention_dkv",
        grid=(B, Nq, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D), lambda b, n, i: (b, n // group, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D), lambda b, n, i: (b, n // group, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, n, i: (b, n, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, 128), lambda b, n, i: (b, n, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D), lambda b, n, i: (b, n, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Nq, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, Nq, S, D), q.dtype),
        ],
    )(q, k, v, do, out, lse)

    if group > 1:
        dk = dk.reshape(B, Nkv, group, S, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, Nkv, group, S, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_fwd_vjp, _bwd_vjp)

# kernels view refs as 2-D; wrap them once at import
_fwd_kernel = _index_squeeze(_fwd_kernel)
_bwd_dq_kernel = _index_squeeze(_bwd_dq_kernel)
_bwd_dkv_kernel = _index_squeeze(_bwd_dkv_kernel)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 512, block_k: int = 512):
    """Flash attention over [B, S, N, D] tensors (kv may have fewer heads).

    Requires S % block and D % 128 == 0 (the dispatcher in ops/attention.py
    enforces this and falls back to the jnp reference otherwise).

    Block-size sweep (v5e, 2026-07-31, GPT-2-large geometry
    [8,1024,20,64]): ISOLATED dependent-chain timing says block_q=256
    wins big (fwd 2.87 -> 2.00 ms, fwd+bwd 3.95 -> 3.38 vs 512/512;
    (512,256), (256,256), (128,*), (1024,512) worse) — but inside the
    full 774M training step the same change measured 2.4% SLOWER
    end-to-end twice (17.45k -> 17.03-17.08k tok/s): the doubled grid
    count interacts badly with the surrounding remat program's
    scheduling.  The 512/512 default is therefore kept on END-TO-END
    evidence; treat kernel microbenches as a screen, not a verdict.
    """
    B, S, Nq, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    qh = _heads_layout(q)
    kh = _heads_layout(k)
    vh = _heads_layout(v)
    out = _flash(qh, kh, vh, causal, block_q, block_k)
    return jnp.transpose(out, (0, 2, 1, 3))
