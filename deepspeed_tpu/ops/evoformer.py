"""Memory-efficient Evoformer (MSA/triangle) attention with pair biases.

Reference: `deepspeed/ops/deepspeed4science/evoformer_attn.py`
`DS4Sci_EvoformerAttention(Q, K, V, biases)` backed by the CUTLASS fMHA
kernels in csrc/deepspeed4science/evoformer_attn/ (kernel_forward.h:986,
kernel_backward.h:1965).  Contract: Q/K/V are [B, N, L, H, D]; up to two
additive biases — bias1 [B, N, 1, 1, L] (per-row key mask bias) and bias2
[B, 1, H, L, L] (pair-representation bias), both broadcast against the
[B, N, H, Lq, Lk] score tensor.

TPU-first: instead of a hand-scheduled CUTLASS kernel, keys are processed in
chunks under `lax.scan` with online-softmax accumulation in fp32 — the
blockwise-attention recurrence — so the [Lq, Lk] score matrix is never
materialized beyond one [Lq, chunk] tile, XLA fuses the bias adds into the
tile matmuls, and the MXU sees dense [L, chunk] GEMMs.  Autodiff through the
scan gives the backward; `jax.checkpoint` on the chunk body keeps bwd memory
at one tile as well.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = ["evoformer_attention", "DS4Sci_EvoformerAttention"]


def _check_biases(q, biases):
    B, N, L, H, D = q.shape
    b1 = b2 = None
    biases = [b for b in (biases or []) if b is not None]
    if len(biases) > 2:
        raise ValueError("at most two biases (mask bias, pair bias)")
    for b in biases:
        if b.shape == (B, N, 1, 1, L):
            if b1 is not None:
                raise ValueError("two mask-shaped biases given; one per "
                                 "slot (mask, pair) as in the reference")
            b1 = b
        elif b.shape == (B, 1, H, L, L):
            if b2 is not None:
                raise ValueError("two pair-shaped biases given; one per "
                                 "slot (mask, pair) as in the reference")
            b2 = b
        else:
            raise ValueError(
                f"bias shape {b.shape} is neither mask-bias {(B, N, 1, 1, L)} "
                f"nor pair-bias {(B, 1, H, L, L)}")
    return b1, b2


def _use_evo_kernel(impl: str, L: int, D: int) -> bool:
    """Gate the kernel-backed custom_vjp (ops/evoformer_flash.py).

    Measured (v5e, 2026-07-31, bf16, both biases, sweeps over L=256..1024,
    D=32/64): the fused FORWARD kernel loses to XLA's batched chunked path
    at every tested geometry (0.5-0.9x; XLA pipelines the bias-add einsums
    better), but the fused BACKWARD kernels WIN — grad-path 1.11x at D=32
    and 1.18x at D=64 at L=1024.  "auto" therefore runs the HYBRID: XLA
    forward (emitting the logsumexp residual) + Pallas flash backward —
    including the AlphaFold D=32 head size.  "pallas" forces the fully-
    fused kernels both directions (benchmarking); "jnp" disables kernels
    entirely (pure autodiff)."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"unknown impl {impl!r} (auto | pallas | jnp)")
    # tiling: full-L blocks below 128 must still be sublane-aligned
    capable = ((L % 128 == 0 or (L <= 128 and L % 16 == 0))
               and D % 8 == 0)
    try:
        from ..utils.device import on_tpu
        capable = capable and on_tpu()
    except Exception:
        capable = False
    if impl == "jnp":
        return False
    if impl == "pallas":
        if not capable:
            raise ValueError(
                f"impl='pallas' requested but the Evoformer kernel cannot "
                f"run here (needs TPU, L % block == 0 [got L={L}], "
                f"head_dim % 8 == 0 [got {D}]) — a silent fallback would "
                f"benchmark/debug the wrong implementation")
        return True
    return capable


def _fwd_kernel_for(D: int):
    """D-minor kernel at MXU-native widths; the D-major variant for
    narrow heads (AlphaFold's D=32) where D-minor blocks lane-pad 4x."""
    from . import evoformer_flash as ef
    return (ef.evoformer_flash_forward if D % 64 == 0
            else ef.evoformer_flash_forward_dmajor)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _evo_kernel_diff(q, k, v, b1, b2, chunk_size):
    # hybrid fast path: XLA forward (measured faster than the fused
    # forward kernel at every tested geometry), Pallas flash backward
    return _evoformer_jnp(q, k, v, b1, b2, chunk_size)


def _evo_kernel_diff_fwd(q, k, v, b1, b2, chunk_size):
    out, lse = _evoformer_jnp(q, k, v, b1, b2, chunk_size,
                              return_lse=True)
    return out, (q, k, v, b1, b2, out, lse)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _evo_kernel_fused_diff(q, k, v, b1, b2, chunk_size):
    # fully-fused path (impl="pallas"): kernel forward too
    return _fwd_kernel_for(q.shape[-1])(q, k, v, b1, b2)


def _evo_kernel_fused_diff_fwd(q, k, v, b1, b2, chunk_size):
    out, lse = _fwd_kernel_for(q.shape[-1])(q, k, v, b1, b2,
                                            return_lse=True)
    return out, (q, k, v, b1, b2, out, lse)


def _evo_kernel_diff_bwd(chunk_size, res, g):
    q, k, v, b1, b2, out, lse = res
    # fused flash backward kernels (evoformer_flash.py) — exact gradients
    # including both bias cotangents, recomputing p tiles from the saved
    # logsumexp instead of re-running the chunked jnp forward
    from .evoformer_flash import evoformer_flash_backward
    dq, dk, dv, db1, db2 = evoformer_flash_backward(
        q, k, v, b1, b2, out, g, lse)
    return dq, dk, dv, db1, db2


_evo_kernel_diff.defvjp(_evo_kernel_diff_fwd, _evo_kernel_diff_bwd)
_evo_kernel_fused_diff.defvjp(_evo_kernel_fused_diff_fwd,
                              _evo_kernel_diff_bwd)


def evoformer_attention(q, k, v, biases: Sequence = (),
                        chunk_size: int = 128, impl: str = "auto"):
    """q,k,v: [B, N, L, H, D]; returns [B, N, L, H, D].

    biases: up to two of mask-bias [B,N,1,1,L] / pair-bias [B,1,H,L,L]
    (order-free; disambiguated by shape, reference asserts the same shapes).
    On TPU the forward runs as a fused Pallas kernel (evoformer_flash.py).
    """
    B, N, L, H, D = q.shape
    b1, b2 = _check_biases(q, biases)
    if _use_evo_kernel(impl, L, D):
        if impl == "pallas":
            return _evo_kernel_fused_diff(q, k, v, b1, b2, chunk_size)
        return _evo_kernel_diff(q, k, v, b1, b2, chunk_size)
    return _evoformer_jnp(q, k, v, b1, b2, chunk_size)


def _evoformer_jnp(q, k, v, b1, b2, chunk_size: int = 128,
                   return_lse: bool = False):
    """return_lse: also return the softmax logsumexp [B*N, H, L] f32 —
    the residual the fused flash BACKWARD kernels consume (the hybrid
    fast path: XLA forward, Pallas backward)."""
    B, N, L, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    odt = q.dtype

    # scores laid out [B, N, H, Lq, Lk]
    qh = q.transpose(0, 1, 3, 2, 4).astype(jnp.float32) * scale
    kh = k.transpose(0, 1, 3, 2, 4).astype(jnp.float32)
    vh = v.transpose(0, 1, 3, 2, 4).astype(jnp.float32)

    NEG = -1e30
    if L <= chunk_size:
        s = jnp.einsum("bnhqd,bnhkd->bnhqk", qh, kh)
        if b1 is not None:
            s = s + b1.astype(jnp.float32)          # [B,N,1,1,L] broadcasts
        if b2 is not None:
            s = s + b2.astype(jnp.float32)          # [B,1,H,L,L] broadcasts
        # masked-softmax with the kernel's fully-masked-row convention:
        # entries at/below the -1e30 mask level contribute exactly zero and
        # an all-masked row outputs zeros (softmax would give NaN/uniform)
        s = jnp.maximum(s, NEG)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(s > NEG * 0.5, jnp.exp(s - m), 0.0)
        out = jnp.einsum("bnhqk,bnhkd->bnhqd", p, vh)
        # eps large enough that eps**2 stays normal in f32: the
        # division vjp computes -acc/l^2, and 1e-30**2 underflows
        # to 0 -> 0/0 = NaN in the masked-row gradient
        l = jnp.maximum(p.sum(-1), 1e-9)
        out = out / l[..., None]
        out = out.transpose(0, 1, 3, 2, 4).astype(odt)
        if return_lse:
            lse = (m[..., 0] + jnp.log(l)).reshape(B * N, H, L)
            return out, lse
        return out

    if L % chunk_size != 0:
        raise ValueError(f"L={L} must be a multiple of chunk_size={chunk_size}")
    C = L // chunk_size

    kc = kh.reshape(B, N, H, C, chunk_size, D).transpose(3, 0, 1, 2, 4, 5)
    vc = vh.reshape(B, N, H, C, chunk_size, D).transpose(3, 0, 1, 2, 4, 5)
    b1c = (b1.astype(jnp.float32)
           .reshape(B, N, 1, 1, C, chunk_size).transpose(4, 0, 1, 2, 3, 5)
           if b1 is not None else None)
    b2c = (b2.astype(jnp.float32)
           .reshape(B, 1, H, L, C, chunk_size).transpose(4, 0, 1, 2, 3, 5)
           if b2 is not None else None)

    xs = {"k": kc, "v": vc}
    if b1c is not None:
        xs["b1"] = b1c
    if b2c is not None:
        xs["b2"] = b2c

    def chunk(carry, x):
        m, l, acc = carry
        s = jnp.einsum("bnhqd,bnhkd->bnhqk", qh, x["k"])
        if "b1" in x:
            s = s + x["b1"]
        if "b2" in x:
            s = s + x["b2"]
        s = jnp.maximum(s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(s > NEG * 0.5, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bnhqk,bnhkd->bnhqd", p, x["v"])
        return (m_new, l, acc), None

    init = (jnp.full((B, N, H, L), NEG, jnp.float32),
            jnp.zeros((B, N, H, L), jnp.float32),
            jnp.zeros((B, N, H, L, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(chunk), init, xs)
    l = jnp.maximum(l, 1e-9)  # eps**2 must stay normal (vjp)
    out = acc / l[..., None]
    out = out.transpose(0, 1, 3, 2, 4).astype(odt)
    if return_lse:
        lse = (m + jnp.log(l)).reshape(B * N, H, L)
        return out, lse
    return out


def DS4Sci_EvoformerAttention(Q, K, V, biases):
    """Drop-in name parity with the reference entry point."""
    return evoformer_attention(Q, K, V, biases)
