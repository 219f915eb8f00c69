"""FPDT — Fully Pipelined Distributed Transformer (Ulysses-Offload).

Reference: sequence/fpdt_layer.py — `_FPDTGPUOffloadingAttentionImpl_` :510
runs attention over sequence chunks with online-softmax accumulation
(`update_out_and_lse` :58) while parking K/V chunks in host memory;
`FPDT_Attention` :971 is the public wrapper.  This enables ~2M-token
contexts with bounded device memory.

TPU-first redesign:
- The chunk loop is a double `lax.scan` (q chunks × kv chunks) with
  flash-style running (m, l, o) accumulators in fp32 — the same math as the
  reference's update_out_and_lse, compiled into one XLA program.
- Host offload is XLA memory-kind placement: Q/K/V/output chunk stacks are
  annotated `pinned_host` and each inner step pulls one chunk back to
  `device` (replaces CUDA pinned-buffer prefetch streams; XLA overlaps the
  host DMA with the previous chunk's compute).
- The backward is a custom_vjp flash backward with the SAME chunked
  host-fetch structure (reference: fpdt_layer.py:510 backward): residuals
  between forward and backward are the host-resident Q/K/V/output stacks
  plus a small [n, B, NH, c] log-sum-exp, and each backward step re-stages
  one chunk and recomputes its [c, c] score block.  Device-resident
  backward state is O(S) only for the cotangents themselves (dq/dk/dv must
  be returned as device arrays) — K/V never materialize on device at full
  sequence length in either pass.
- Composes with Ulysses: run the a2a head-scatter first (parallel/ulysses),
  then FPDT chunking locally — exactly the reference's composition.

Measured (v5e-1, 2026-07-30, compiled.memory_analysis):
- attention-only fwd+bwd at 32k tokens (NH=16, D=128, chunk 1024): the old
  XLA-autodiff backward of the chunk scan tried to save every fetched K/V
  chunk — a 137 GB allocation that failed to compile; the custom backward
  compiles at ~534 MiB of device temp.
- 4-layer model at 16k tokens: fpdt_offload=True parks 768 MiB of
  residual stacks in host memory and drops device temp 6850 -> 6270 MiB
  vs offload=False (the saving is exactly the per-layer Q/K/V/out
  residuals, so it scales with num_layers x S).
- offload and device-chunked backward gradients are bitwise identical on
  TPU.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.device import platform

NEG = -1e30


def _supports_host_memory() -> bool:
    return platform() in ("tpu", "cpu")


def _to_host(x):
    return jax.device_put(x, jax.memory.Space.Host)


def _to_device(x):
    return jax.device_put(x, jax.memory.Space.Device)


def _stack(x, n: int, offload: bool):
    """[B, S, N, D] -> [n, elems] chunk-major buffer, host-resident when
    offloading.

    Flattened to one row per chunk before the host put: the TPU backend
    propagates fused (tiled) layouts into host-memory buffers and then
    fails when dynamic-slicing them back; a [n, elems] buffer keeps a
    trivial row layout, so row dynamic_slice + on-device reshape is safe —
    including when an outer layer scan stacks these buffers as residuals."""
    B, S, N, D = x.shape
    c = S // n
    rows = x.reshape(B, n, c, N, D).transpose(1, 0, 2, 3, 4).reshape(n, -1)
    return _to_host(rows) if offload else rows


def _fetch_chunk(stack, i, shape):
    """One [B, c, N, D] chunk of a host (or device) chunk-major stack."""
    row = jax.lax.dynamic_index_in_dim(stack, i, axis=0, keepdims=False)
    return _to_device(row).reshape(shape)


def fpdt_attention(q, k, v, chunk_size: int, causal: bool = True,
                   offload: Optional[bool] = None, scale: Optional[float] = None):
    """Sequence-chunked causal attention with online softmax.

    q: [B,S,NH,D], k/v: [B,S,NKV,D] (GQA broadcast handled).  Peak memory is
    O(S·chunk) for scores instead of O(S²); with `offload=True` the Q/K/V
    and output stacks live in host memory between chunk visits, in both the
    forward and the custom flash backward.
    """
    if offload is None:
        offload = False
    if offload and not _supports_host_memory():
        offload = False
    scale = scale or (1.0 / math.sqrt(q.shape[-1]))
    return _fpdt_custom(q, k, v, chunk_size, causal, scale, offload)


def _fpdt_fwd_impl(q, k, v, chunk_size: int, causal: bool, scale: float,
                   offload: bool):
    """Chunked online-softmax forward.  Returns (out, lse, qs, ks, vs):
    lse is [n, B, NH, c] (log-sum-exp per query, chunk-stacked); qs/ks/vs
    are the chunk-major stacks (host-resident under offload), returned so
    the custom backward reuses them instead of re-staging."""
    B, S, NH, D = q.shape
    NKV = k.shape[2]
    n = S // chunk_size
    assert n * chunk_size == S, f"S={S} not divisible by chunk_size={chunk_size}"
    c = chunk_size

    qs = _stack(q, n, offload)
    ks = _stack(k, n, offload)
    vs = _stack(v, n, offload)
    fetch_q = lambda i: _fetch_chunk(qs, i, (B, c, NH, D))
    fetch_kv = lambda st, i: _fetch_chunk(st, i, (B, c, NKV, D))

    neg = jnp.asarray(NEG, jnp.float32)
    cpos = jnp.arange(c)
    rep = NH // NKV

    def fetch_rep(st, i):
        chunk = fetch_kv(st, i)
        return jnp.repeat(chunk, rep, axis=2) if rep > 1 else chunk

    def q_chunk_body(qi):
        """Attend q chunk `qi` to kv chunks 0..qi (causal)."""
        qc = fetch_q(qi)
        m0 = jnp.full((B, NH, c), neg, jnp.float32)
        l0 = jnp.zeros((B, NH, c), jnp.float32)
        o0 = jnp.zeros((B, NH, c, D), jnp.float32)

        # remat the chunk body: backward recomputes the [c,c] score block
        # instead of storing n^2 of them (the reference's autograd chunking
        # has the same recompute shape)
        @jax.checkpoint
        def visit(carry, ki):
            m, l, o = carry
            kc = fetch_rep(ks, ki)
            vc = fetch_rep(vs, ki)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = qi * c + cpos[:, None]
                kpos = ki * c + cpos[None, :]
                s = jnp.where(kpos <= qpos, s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            o_new = o * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        def kv_body(carry, ki):
            if not causal:
                return visit(carry, ki)
            # runtime-skip fully-future blocks (triangular visitation —
            # halves FLOPs and host DMA vs visiting all n blocks)
            return jax.lax.cond(
                ki <= qi, lambda cr: visit(cr, ki)[0], lambda cr: cr, carry
            ), None

        (m, l, o), _ = jax.lax.scan(kv_body, (m0, l0, o0), jnp.arange(n))
        l = jnp.maximum(l, 1e-30)
        out = o / l[..., None]                           # [B, NH, c, D]
        lse = m + jnp.log(l)                             # [B, NH, c]
        return out.transpose(0, 2, 1, 3), lse            # [B, c, NH, D]

    def outer(carry, qi):
        return carry, q_chunk_body(qi)

    _, (outs, lses) = jax.lax.scan(outer, None, jnp.arange(n))
    # outs: [n, B, c, NH, D] -> [B, S, NH, D]
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, NH, D).astype(q.dtype)
    return out, lses, qs, ks, vs


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fpdt_custom(q, k, v, chunk_size, causal, scale, offload):
    out, *_ = _fpdt_fwd_impl(q, k, v, chunk_size, causal, scale, offload)
    return out


def _fpdt_custom_fwd(q, k, v, chunk_size, causal, scale, offload):
    out, lse, qs, ks, vs = _fpdt_fwd_impl(q, k, v, chunk_size, causal,
                                          scale, offload)
    n = lse.shape[0]
    # residuals park EVERY S-sized tensor on host under offload; between a
    # layer's forward and its backward only the [n, B, NH, c] lse stays
    # device-resident.  The custom backward also serves offload=False: the
    # XLA autodiff of the double chunk scan saves every fetched (GQA-
    # repeated) K/V chunk — an n^2-chunk buffer that at 32k tokens is a
    # 137 GB allocation (measured: compile fails on v5e) where this
    # backward's chunked recompute needs ~534 MiB of temp
    res = (qs, ks, vs, _stack(out, n, offload), lse)
    return out, res


def _fpdt_custom_bwd(chunk_size, causal, scale, offload, res, g):
    qs, ks, vs, outs, lse = res
    n, B, NH, c = lse.shape
    S = n * c
    D = g.shape[-1]
    NKV = ks.shape[1] // (B * c * D)    # stack rows are [B*c*NKV*D] wide
    rep = NH // NKV
    dt = g.dtype

    gs = g.astype(jnp.float32).reshape(B, n, c, NH, D)
    neg = jnp.asarray(NEG, jnp.float32)
    cpos = jnp.arange(c)

    def fetch_nh(st, i):
        return _fetch_chunk(st, i, (B, c, NH, D)).astype(jnp.float32)

    def fetch_nkv(st, i):
        chunk = _fetch_chunk(st, i, (B, c, NKV, D)).astype(jnp.float32)
        return jnp.repeat(chunk, rep, axis=2) if rep > 1 else chunk

    def qi_body(carry, qi):
        dks, dvs = carry                      # [B, n, c, NKV, D] f32
        qc = fetch_nh(qs, qi)                 # [B, c, NH, D]
        oc = fetch_nh(outs, qi)
        gc = jax.lax.dynamic_index_in_dim(gs, qi, axis=1, keepdims=False)
        lse_c = lse[qi]                       # [B, NH, c]
        # delta = rowsum(dout * out) per query (flash-bwd identity)
        delta_c = jnp.einsum("bqhd,bqhd->bhq", gc, oc)     # [B, NH, c]
        dq0 = jnp.zeros((B, c, NH, D), jnp.float32)

        # remat: recompute the [c, c] probability block in this step's own
        # backward rather than storing it
        @jax.checkpoint
        def visit(carry, ki):
            dq_c, dks, dvs = carry
            kc = fetch_nkv(ks, ki)            # [B, c, NH, D] (GQA-repeated)
            vc = fetch_nkv(vs, ki)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = qi * c + cpos[:, None]
                kpos = ki * c + cpos[None, :]
                s = jnp.where(kpos <= qpos, s, neg)
            p = jnp.exp(s - lse_c[..., None])              # [B, NH, c, c]
            dv_part = jnp.einsum("bhqk,bqhd->bkhd", p, gc)
            dp = jnp.einsum("bqhd,bkhd->bhqk", gc, vc)
            ds = p * (dp - delta_c[..., None])
            dq_c = dq_c + jnp.einsum("bhqk,bkhd->bqhd", ds, kc) * scale
            dk_part = jnp.einsum("bhqk,bqhd->bkhd", ds, qc) * scale
            if rep > 1:   # GQA: fold the repeated query heads back
                dk_part = dk_part.reshape(B, c, NKV, rep, D).sum(axis=3)
                dv_part = dv_part.reshape(B, c, NKV, rep, D).sum(axis=3)
            dks = dks.at[:, ki].add(dk_part)
            dvs = dvs.at[:, ki].add(dv_part)
            return dq_c, dks, dvs

        def kv_body(carry, ki):
            if not causal:
                return visit(carry, ki), None
            return jax.lax.cond(ki <= qi, visit,
                                lambda cr, _ki: cr, carry, ki), None

        (dq_c, dks, dvs), _ = jax.lax.scan(kv_body, (dq0, dks, dvs),
                                           jnp.arange(n))
        return (dks, dvs), dq_c

    dk0 = jnp.zeros((B, n, c, NKV, D), jnp.float32)
    dv0 = jnp.zeros((B, n, c, NKV, D), jnp.float32)
    (dks, dvs), dqs = jax.lax.scan(qi_body, (dk0, dv0), jnp.arange(n))
    dq = dqs.transpose(1, 0, 2, 3, 4).reshape(B, S, NH, D).astype(dt)
    dk = dks.reshape(B, S, NKV, D).astype(dt)
    dv = dvs.reshape(B, S, NKV, D).astype(dt)
    return dq, dk, dv


_fpdt_custom.defvjp(_fpdt_custom_fwd, _fpdt_custom_bwd)


class FPDT_Attention:
    """Wrapper mirroring the reference class (fpdt_layer.py:971): optional
    Ulysses a2a around the chunked-offloaded local attention."""

    def __init__(self, chunk_size: int = 512, causal: bool = True,
                 offload: Optional[bool] = None, sp_axis: Optional[str] = None):
        self.chunk_size = chunk_size
        self.causal = causal
        self.offload = offload
        self.sp_axis = sp_axis

    def __call__(self, q, k, v):
        local = lambda q_, k_, v_: fpdt_attention(
            q_, k_, v_, self.chunk_size, causal=self.causal,
            offload=self.offload)
        if self.sp_axis is not None:
            from ..parallel.ulysses import ulysses_attention
            return ulysses_attention(q, k, v, axis_name=self.sp_axis,
                                     attn_fn=local)
        return local(q, k, v)
