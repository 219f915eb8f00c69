"""Attention ops with Pallas fast path and jnp reference fallback.

Reference kernels being replaced: the fused softmax/attention CUDA kernels
(csrc/transformer/inference/softmax.cu:562, the blocked flash kernels under
inference/v2/kernels/ragged_ops/blocked_flash/, and the DS4Science evoformer
fMHA csrc/deepspeed4science/evoformer_attn/).

`causal_attention` is the single entry point used by the model family:
- impl="pallas": Pallas TPU flash attention (ops/flash_attention.py), tiled
  for the MXU with online softmax — O(S) memory.
- impl="jnp":    straight jnp einsum + softmax reference (used on CPU test
  meshes and as the numerical baseline in ops tests).
- impl="auto":   pallas on TPU when shapes permit, else jnp.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils.device import on_tpu

__all__ = ["causal_attention", "attention_reference"]


def _repeat_kv(k, num_heads: int):
    """Expand KV heads for GQA: [B,S,NKV,D] -> [B,S,NH,D]."""
    nkv = k.shape[2]
    if nkv == num_heads:
        return k
    rep = num_heads // nkv
    return jnp.repeat(k, rep, axis=2)


def attention_reference(q, k, v, causal: bool = True,
                        segment_ids: Optional[jax.Array] = None,
                        bias: Optional[jax.Array] = None,
                        sliding_window: Optional[int] = None):
    """Pure-jnp causal attention. q:[B,S,NH,D] k,v:[B,S,NKV,D] -> [B,S,NH,D].
    Softmax in fp32 (matching the reference kernels' accumulation dtype).

    bias: additive score bias broadcastable to [B,NH,Sq,Sk] (ALiBi slopes,
    evoformer pair bias).  sliding_window: keys older than `window` positions
    behind the query are masked (Mistral-style local attention)."""
    NH = q.shape[2]
    k = _repeat_kv(k, NH)
    v = _repeat_kv(v, NH)
    D = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    S_q, S_k = q.shape[1], k.shape[1]
    neg = jnp.finfo(jnp.float32).min
    if causal:
        mask = jnp.tril(jnp.ones((S_q, S_k), jnp.bool_), k=S_k - S_q)
        logits = jnp.where(mask[None, None], logits, neg)
    if sliding_window is not None:
        qpos = jnp.arange(S_q)[:, None] + (S_k - S_q)
        kpos = jnp.arange(S_k)[None, :]
        win = kpos > (qpos - sliding_window)
        logits = jnp.where(win[None, None], logits, neg)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        logits = jnp.where(seg_mask, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def _flash_per_shard(q, k, v, **blocks):
    """The flash kernel under the ambient mesh.  A pallas_call does not
    auto-partition under GSPMD (Mosaic refuses to lower it in a
    multi-device program), so on a mesh the kernel runs per shard in a
    shard_map over every axis that is still automatic (size-1 axes
    included: Mosaic wants all of them manual): batch split over the data
    axes, heads over tp, both only where they divide (an axis that does
    not divide computes replicated)."""
    import math

    from jax.sharding import PartitionSpec as P

    from ..parallel.context import get_current_topology, shard_map_mesh
    from ..parallel.mesh import AXIS_TP
    from .flash_attention import flash_attention
    kernel = functools.partial(flash_attention, causal=True, **blocks)
    topo = get_current_topology()
    if topo is None or topo.mesh.size == 1:
        return kernel(q, k, v)
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    auto = set(topo.mesh.axis_names) - manual
    if not auto:
        return kernel(q, k, v)
    batch = tuple(a for a in topo.data_axes if a in auto)
    if q.shape[0] % math.prod(topo.size(a) for a in batch):
        batch = ()
    tp = topo.size(AXIS_TP)
    heads = (AXIS_TP if AXIS_TP in auto and q.shape[2] % tp == 0
             and k.shape[2] % tp == 0 else None)
    spec = P(batch or None, None, heads, None)
    return jax.shard_map(
        kernel, mesh=shard_map_mesh(topo), axis_names=auto,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)(q, k, v)


def causal_attention(q, k, v, impl: str = "auto",
                     segment_ids: Optional[jax.Array] = None,
                     bias: Optional[jax.Array] = None,
                     sliding_window: Optional[int] = None):
    """Dispatching causal attention. Shapes: q [B,S,NH,D]; k/v [B,S,NKV,D].
    `bias`/`sliding_window` force the jnp path (the Pallas kernel has no
    score-bias input yet)."""
    from ..runtime.activation_checkpointing import attn_checkpoint_name
    if impl == "jnp" or bias is not None or sliding_window is not None:
        # tag so save_attn* remat policies skip the softmax recompute on
        # the jnp path too (the flash path tags its residuals internally)
        return attn_checkpoint_name(attention_reference(
            q, k, v, causal=True, segment_ids=segment_ids, bias=bias,
            sliding_window=sliding_window))
    if impl in ("pallas", "auto"):
        use_pallas = impl == "pallas" or on_tpu()
        D = q.shape[-1]
        S = q.shape[1]
        # Pallas kernel needs MXU-friendly tiles.  Even at D=64 (GPT-2
        # family, half the lanes idle) the flash kernel beats dense XLA once
        # the S^2 score matrix dominates HBM traffic: measured 34.5k vs
        # 24.6k tok/s/chip on GPT-2-medium seq=1024 micro=16 v5e (bench
        # sweep 2026-07-30) — switch over from S=1024.
        shapes_ok = S % 128 == 0 and (
            D % 128 == 0 or (D == 64 and (S >= 1024 or impl == "pallas")))
        import os

        # tuning knob for sweeps: "bq,bk" (512,512 measured best at seq
        # 1024; the backward kernels inherit them)
        blk = os.environ.get("DSTPU_FLASH_BLOCKS")
        blocks = {}
        if blk:
            try:
                bq, bk = (int(x) for x in blk.split(","))
            except ValueError as e:
                raise ValueError(
                    f"DSTPU_FLASH_BLOCKS={blk!r} must be 'bq,bk'") from e
            blocks = {"block_q": bq, "block_k": bk}
        if use_pallas and shapes_ok and segment_ids is None:
            # a kernel that cannot compile here is an error: impl="jnp"
            # is the only dense route
            return _flash_per_shard(q, k, v, **blocks)
        return attn_checkpoint_name(attention_reference(
            q, k, v, causal=True, segment_ids=segment_ids))
    raise ValueError(f"unknown attention impl {impl!r}")
