"""Ring attention — context parallelism over ICI neighbors.

The reference has NO ring attention (SURVEY §5.7: Ulysses a2a + FPDT
blockwise-offload fill the long-context role, sequence/fpdt_layer.py's
`update_out_and_lse`:58 is the same online-softmax math iterated locally).
On TPU a ring over the torus's nearest-neighbor ICI links is the natural
*additional* CP strategy, so it is first-class here.

Mechanism: sequence sharded over the `sp` axis.  Each device holds one Q
block permanently and circulates K/V blocks around the ring with
`jax.lax.ppermute` (XLA CollectivePermute -> ICI neighbor DMA), accumulating
flash-style online softmax per step.  P steps; comm volume O(S/P * 2) per
step, fully overlappable with the block attention compute by XLA's
latency-hiding scheduler.

Causality: Q block b attends K/V blocks 0..b.  Rotations that deliver a
future block contribute nothing; they are masked out (the classic ring
imbalance — a zig-zag block order is the known fix, left for a later round).

Differentiable by construction (ppermute has a transpose rule); memory is
O(S_local) activations per step; wrap in jax.checkpoint when sequences are
extreme.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from .context import require_topology, shard_map_mesh
from .mesh import AXIS_SP

__all__ = ["ring_attention"]

NEG_INF = -1e30


def _block_attn(q, k, v, q_start, k_start, scale):
    """One blockwise attention step with global-position causal mask.
    q: [B, Sq, N, D], k/v: [B, Sk, NKV, D]; returns (scores-exp sums).
    Returns m [B,N,Sq,1], l [B,N,Sq,1], o [B,Sq,N,D] partials."""
    nh, nkv = q.shape[2], k.shape[2]
    if nkv != nh:
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    Sq, Sk = q.shape[1], k.shape[1]
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
    s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                    # [B,N,Sq,1]
    # guard fully-masked rows (future-only block): exp(NEG_INF - NEG_INF)=1
    # would pollute l; clamp m so p underflows to 0 instead.
    p = jnp.exp(s - jnp.maximum(m, -1e20))
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bnqk,bknd->bqnd", p.astype(v.dtype), v)
    return m, l, o


def ring_attention(q, k, v, axis_name: str = AXIS_SP):
    """Causal ring attention over GLOBAL [B, S, N, D] arrays sequence-sharded
    on `axis_name`."""
    topo = require_topology()
    p_size = topo.size(axis_name)
    if p_size == 1:
        from ..ops.attention import causal_attention
        return causal_attention(q, k, v)

    scale = 1.0 / (q.shape[-1] ** 0.5)

    def local(q, k, v):
        # local views: [B, S/P, N, D]
        B, S_loc, NH, D = q.shape
        my = jax.lax.axis_index(axis_name)
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]

        m0 = jnp.full((B, NH, S_loc, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, NH, S_loc, 1), jnp.float32)
        acc0 = jnp.zeros((B, S_loc, NH, D), jnp.float32)

        def step(carry, i):
            m, l, acc, k_cur, v_cur = carry
            src = (my - i) % p_size  # which global block k_cur holds
            bm, bl, bo = _block_attn(q, k_cur, v_cur,
                                     q_start=my * S_loc,
                                     k_start=src * S_loc,
                                     scale=scale)
            m_new = jnp.maximum(m, bm)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(bm - m_new)
            l_new = alpha * l + beta * bl
            # bo was computed with softmax base bm; rescale by beta
            acc_new = (acc * jnp.transpose(alpha, (0, 2, 1, 3))
                       + bo.astype(jnp.float32)
                       * jnp.transpose(beta, (0, 2, 1, 3)))
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            return (m_new, l_new, acc_new, k_nxt, v_nxt), None

        (m, l, acc, _, _), _ = jax.lax.scan(
            step, (m0, l0, acc0, k, v), jnp.arange(p_size))
        out = acc / jnp.transpose(l, (0, 2, 1, 3))
        return out.astype(q.dtype)

    spec = P(None, axis_name, None, None)
    # manual only over the sp axis; dp/tp/... stay under automatic SPMD
    return shard_map(local, mesh=shard_map_mesh(topo), axis_names={axis_name},
                     in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)
