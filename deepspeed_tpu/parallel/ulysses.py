"""Ulysses sequence parallelism: all-to-all head-scatter / seq-gather.

Reference: sequence/layer.py — `_SeqAllToAll`:277 and
`DistributedAttention`:331.  The mechanism: shard the sequence across SP
ranks; before attention, all-to-all Q/K/V so each rank holds the FULL
sequence for 1/P of the heads; run any local attention (flash); all-to-all
back.  Comm volume O(M/P) per rank vs O(M) for an allgather — the property
the reference's long-sequence results rest on.

TPU-native: `_SeqAllToAll` becomes `jax.lax.all_to_all` over a mesh axis
inside a `shard_map` region; XLA lowers it to an ICI AllToAll and overlaps it
with surrounding compute (the reference needs a dedicated side stream for
that — sp_overlap_comm, layer.py:357-361).

Requires num_heads % sp_size == 0 (same constraint as the reference).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from .context import require_topology, shard_map_mesh
from .mesh import AXIS_SP

__all__ = ["ulysses_attention", "seq_all_to_all"]


def seq_all_to_all(x, axis_name: str, scatter: str):
    """Local-view all-to-all. x: [B, s_local, N, D] (scatter='heads') or
    [B, S, n_local, D] (scatter='seq').

    scatter='heads': seq-sharded -> head-sharded (gather seq, scatter heads)
    scatter='seq':   head-sharded -> seq-sharded (reverse)
    (reference: _SeqAllToAll scatter_idx/gather_idx, layer.py:345-346)
    """
    if scatter == "heads":
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)
    if scatter == "seq":
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)
    raise ValueError(f"scatter must be 'heads' or 'seq', got {scatter!r}")


def ulysses_attention(q, k, v, axis_name: str = AXIS_SP,
                      attn_fn: Optional[Callable] = None):
    """Distributed attention over a sequence-sharded batch.

    Args are GLOBAL arrays [B, S, N, D] logically sharded over `axis_name`
    on the sequence dim (the engine's batch sharding does this).  Internally
    opens a shard_map on the ambient mesh: a2a to head-sharding, local
    attention on the full sequence, a2a back.

    attn_fn: local attention callable (defaults to the framework dispatcher).
    """
    if attn_fn is None:
        from ..ops.attention import causal_attention
        attn_fn = causal_attention

    topo = require_topology()
    sp = topo.size(axis_name)
    if sp == 1:
        return attn_fn(q, k, v)
    n_heads = q.shape[2]
    n_kv = k.shape[2]
    if n_heads % sp or n_kv % sp:
        raise ValueError(
            f"num_heads ({n_heads}/{n_kv}) must divide sp size {sp} "
            "(reference constraint: sequence/layer.py DistributedAttention)")

    def local(q, k, v):
        # local view: [B, S/P, N, D]
        q = seq_all_to_all(q, axis_name, "heads")   # [B, S, N/P, D]
        k = seq_all_to_all(k, axis_name, "heads")
        v = seq_all_to_all(v, axis_name, "heads")
        o = attn_fn(q, k, v)
        return seq_all_to_all(o, axis_name, "seq")  # [B, S/P, N, D]

    spec = P(None, axis_name, None, None)
    # manual only over the sp axis; dp/tp/... stay under automatic SPMD
    return shard_map(
        local, mesh=shard_map_mesh(topo), axis_names={axis_name},
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
