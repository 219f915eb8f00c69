"""Mixture-of-Experts with expert parallelism.

Reference: deepspeed/moe/sharded_moe.py — `top1gating`:183, `top2gating`:290,
`topkgating`:374, `TopKGate`:452, `MOELayer`:536, `_AllToAll`:96; layer API
moe/layer.py:17 `MoE`.

TPU-native formulation, TWO dispatch forms behind one `moe_layer` API:

- "einsum" (default): the GShard form — a [tokens, experts, capacity]
  one-hot dispatch tensor contracted on the MXU — with the expert
  dimension sharded over the `ep` mesh axis.  The XLA SPMD partitioner
  lowers the two dispatch/combine einsums to the reference's AllToAll
  pair (tokens->experts, experts->tokens), scheduled and overlapped
  automatically.
- "a2a": the reference's EXPLICIT all_to_all of token buffers
  (`_AllToAll` sharded_moe.py:96) as a shard_map region manual over
  `ep`: tokens split over ep, local gating + capacity, one
  `lax.all_to_all` ships each expert's buffer to its owner rank, the
  local expert FFN runs, and a second all_to_all ships outputs back for
  the local combine.  `dispatch_bits=8/4` additionally rides the pair
  on the `comm/compressed.py` fused block-quant wire (ZeRO++-style
  int8-on-the-wire, arxiv 2306.10209) — LOSSY, so it is opt-in and
  loss-parity-gated by tests; the default (None) is bit-exact.  Both
  hops report their ACTUAL on-wire bytes to the CommsLogger.

Gating parity:
- top-1 (Switch), top-2 (GShard) and general top-k with capacity factor,
  min_capacity, token dropping, and the load-balancing auxiliary loss
  l_aux = E * sum_e(me * ce) (same formula as the reference's top1gating).
- optional gate noise (noisy_gate_policy 'RSample' / 'Jitter' analogs).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..parallel.mesh import AXIS_EP, AXIS_TP

__all__ = ["topk_gating", "moe_layer", "init_moe_params", "moe_tp_rules",
           "compute_capacity", "moe_dispatch_a2a", "moe_combine_a2a"]


def compute_capacity(num_tokens: int, num_experts: int,
                     capacity_factor: float, min_capacity: int) -> int:
    """reference: sharded_moe.py _capacity (tokens/experts * factor)."""
    cap = int(num_tokens * capacity_factor / num_experts)
    cap = max(cap, min_capacity)
    # keep the MXU dispatch einsum tiled: round up to a multiple of 8
    return ((cap + 7) // 8) * 8


def topk_gating(
    logits: jax.Array,            # [T, E] fp32
    k: int,
    capacity: int,
    rng: Optional[jax.Array] = None,
    noise_std: float = 0.0,
    drop_tokens: bool = True,
    norm_topk: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (dispatch [T,E,C] bool-ish, combine [T,E,C] float, l_aux,
    metrics)."""
    T, E = logits.shape
    C = capacity
    gates = jax.nn.softmax(logits, axis=-1)  # [T, E]

    noisy = logits
    if noise_std > 0.0 and rng is not None:
        noisy = logits + jax.random.normal(rng, logits.shape) * noise_std

    # top-k expert indices per token
    _, expert_idx = jax.lax.top_k(noisy, k)          # [T, k]
    masks = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, k, E]

    # load-balance aux loss from the top-1 assignment (reference top1gating:
    # l_aux = E * mean_e(me * ce))
    me = jnp.mean(gates, axis=0)                     # [E]
    ce = jnp.mean(masks[:, 0, :], axis=0)            # [E]
    l_aux = jnp.sum(me * ce) * E

    # position of each (token, choice) within its expert's capacity
    # process choices sequentially so the k-th choice queues behind earlier
    # choices (same ordering semantics as the reference's cumsum chain)
    dispatch = jnp.zeros((T, E, C), jnp.float32)
    combine = jnp.zeros((T, E, C), jnp.float32)
    counts = jnp.zeros((E,), jnp.float32)
    if norm_topk:
        denom = jnp.sum(jnp.sum(masks, axis=1) * gates, axis=-1, keepdims=True)
        denom = jnp.maximum(denom, 1e-9)
    else:
        # qwen2-moe convention: combine with raw softmax probabilities
        denom = jnp.ones((logits.shape[0], 1), jnp.float32)

    for j in range(k):
        mask_j = masks[:, j, :]                      # [T, E]
        pos_in_expert = jnp.cumsum(mask_j, axis=0) - mask_j + counts[None, :]
        if drop_tokens:
            keep = mask_j * (pos_in_expert < C)
        else:
            keep = mask_j
        pos = jnp.sum(pos_in_expert * keep, axis=-1)          # [T]
        pos_oh = jax.nn.one_hot(jnp.minimum(pos, C - 1).astype(jnp.int32),
                                C, dtype=jnp.float32)          # [T, C]
        disp_j = keep[:, :, None] * pos_oh[:, None, :]         # [T, E, C]
        gate_j = jnp.sum(gates * mask_j, axis=-1, keepdims=True) / denom
        dispatch = dispatch + disp_j
        combine = combine + disp_j * gate_j[:, :, None]
        counts = counts + jnp.sum(keep, axis=0)

    metrics = {
        "l_aux": l_aux,
        "expert_load": counts / jnp.maximum(T * k, 1),
        "dropped_frac": 1.0 - jnp.sum(dispatch) / (T * k),
    }
    return dispatch, combine, l_aux, metrics


# ----------------------------------------------------------------------
# Expert FFN layer
# ----------------------------------------------------------------------
def init_moe_params(key, num_experts: int, hidden: int, ffn: int,
                    activation: str = "gelu") -> Dict[str, Any]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    p = {
        "gate": jax.random.normal(k1, (hidden, num_experts), jnp.float32) * std,
        "w_up": jax.random.normal(k2, (num_experts, hidden, ffn), jnp.float32) * std,
        "w_down": jax.random.normal(k3, (num_experts, ffn, hidden), jnp.float32) * std,
    }
    if activation == "swiglu":
        p["w_gate_proj"] = jax.random.normal(
            k4, (num_experts, hidden, ffn), jnp.float32) * std
    return p


_MOE_TP_RULES = {
    # experts sharded over ep; ffn dim over tp (column/row parallel)
    "w_up": PartitionSpec(AXIS_EP, None, AXIS_TP),
    "w_gate_proj": PartitionSpec(AXIS_EP, None, AXIS_TP),
    "w_down": PartitionSpec(AXIS_EP, AXIS_TP, None),
    "gate": PartitionSpec(),
}


def moe_tp_rules(path: Tuple[str, ...], shape) -> Optional[PartitionSpec]:
    return _MOE_TP_RULES.get(path[-1])


def _expert_ffn(params: Dict[str, Any], expert_in: jax.Array,
                activation: str) -> jax.Array:
    """Batched expert FFN over [E, C, H] buffers (grouped matmul on the
    MXU).  Inside the a2a shard_map region E is the LOCAL expert count and
    C the concatenated per-rank capacity — the einsum is shape-agnostic."""
    dt = expert_in.dtype
    up = jnp.einsum("ech,ehf->ecf", expert_in, params["w_up"].astype(dt),
                    preferred_element_type=jnp.float32).astype(dt)
    if activation == "swiglu":
        g = jnp.einsum("ech,ehf->ecf", expert_in,
                       params["w_gate_proj"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * up
    else:
        from ..models.transformer import _act_fn
        act = _act_fn(activation)(up.astype(jnp.float32)).astype(dt)
    return jnp.einsum("ecf,efh->ech", act, params["w_down"].astype(dt),
                      preferred_element_type=jnp.float32).astype(dt)


# ----------------------------------------------------------------------
# explicit all_to_all dispatch/combine (reference _AllToAll) — these run
# INSIDE a shard_map region manual over the ep axis
# ----------------------------------------------------------------------
def _raw_a2a(send: jax.Array, axis_name: str, op: str) -> jax.Array:
    """Bit-exact all_to_all hop, wire bytes recorded under `op`."""
    from ..comm.comm import comms_logger
    comms_logger.record(
        op, int(np.prod(send.shape)) * send.dtype.itemsize, str(axis_name))
    return jax.lax.all_to_all(send, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)


def _quant_hop(send: jax.Array, axis_name: str, op: str, bits: int,
               block_size: int) -> jax.Array:
    from ..comm.compressed import _dequantize_wire, _quantize_wire, _record
    # meta is static (shape/pad/dtype): construct it once and vmap only
    # the array outputs (the quantized_reduce_scatter pattern)
    slice_shape = send.shape[1:]
    pad = (-int(np.prod(slice_shape))) % block_size
    meta = (slice_shape, pad, block_size, bits, True, send.dtype)
    wires = jax.vmap(
        lambda s: _quantize_wire(s, bits, block_size)[0])(send)
    nb = (int(np.prod(slice_shape)) + pad) // block_size
    n_codes = nb * block_size
    _record(op, wires, axis_name)
    wg = jax.lax.all_to_all(wires, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    return jax.vmap(lambda w: _dequantize_wire(w, nb, n_codes, meta))(wg)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _quant_a2a(send: jax.Array, axis_name: str, op: str, bits: int,
               block_size: int) -> jax.Array:
    """Quantized hop with a straight-through gradient: the forward ships
    int8/int4 block-quant codes, the backward ships the EXACT cotangent
    through a raw hop (the symmetric a2a is its own transpose).  Without
    this the int8 cast would zero every expert-weight gradient."""
    return _quant_hop(send, axis_name, op, bits, block_size)


def _quant_a2a_fwd(send, axis_name, op, bits, block_size):
    return _quant_hop(send, axis_name, op, bits, block_size), None


def _quant_a2a_bwd(axis_name, op, bits, block_size, _res, g):
    return (_raw_a2a(g, axis_name, op + "_grad"),)


_quant_a2a.defvjp(_quant_a2a_fwd, _quant_a2a_bwd)


def _wire_a2a(send: jax.Array, axis_name: str, op: str,
              bits: Optional[int], block_size: int) -> jax.Array:
    """One all_to_all hop: `send` [ep, ...] ships slice i to rank i and
    returns the [ep, ...] stack received (slice j from rank j).

    bits=None is the bit-exact raw hop; bits=8/4 quantizes each
    destination's slice independently onto the fused block-quant wire
    (`comm/compressed.py`: int8 codes + bitcast f32 scales in ONE int8
    buffer) — LOSSY, callers gate it.  Either way the ACTUAL on-wire
    bytes are recorded to the CommsLogger under `op`."""
    if not bits:
        return _raw_a2a(send, axis_name, op)
    return _quant_a2a(send, axis_name, op, bits, block_size)


def moe_dispatch_a2a(expert_in: jax.Array, axis_name: str = AXIS_EP,
                     bits: Optional[int] = None,
                     block_size: int = 256) -> jax.Array:
    """Token->expert hop: local send buffer [E, C, H] (this rank's C-slot
    buffer for EVERY global expert, owner-major expert order) ->
    [E/ep, ep*C, H] (every rank's buffers for this rank's LOCAL experts).
    Must run inside a shard_map region manual over `axis_name`."""
    from jax.lax import axis_size
    ep = axis_size(axis_name)
    E, C, H = expert_in.shape
    if E % ep:
        raise ValueError(f"num_experts {E} not divisible by ep={ep}")
    recv = _wire_a2a(expert_in.reshape(ep, E // ep, C, H), axis_name,
                     "moe_dispatch_a2a", bits, block_size)
    # recv dim0 = source rank's token chunk; group per local expert
    return jnp.transpose(recv, (1, 0, 2, 3)).reshape(E // ep, ep * C, H)


def moe_combine_a2a(expert_out: jax.Array, axis_name: str = AXIS_EP,
                    bits: Optional[int] = None,
                    block_size: int = 256) -> jax.Array:
    """Expert->token hop, inverse of `moe_dispatch_a2a`:
    [E/ep, ep*C, H] -> [E, C, H] (this rank's tokens' outputs from every
    global expert, owner-major order — ready for the local combine)."""
    from jax.lax import axis_size
    ep = axis_size(axis_name)
    E_loc, PC, H = expert_out.shape
    if PC % ep:
        raise ValueError(f"capacity dim {PC} not divisible by ep={ep}")
    C = PC // ep
    send = jnp.transpose(expert_out.reshape(E_loc, ep, C, H), (1, 0, 2, 3))
    recv = _wire_a2a(send, axis_name, "moe_combine_a2a", bits, block_size)
    return recv.reshape(ep * E_loc, C, H)


def _moe_layer_einsum(
    params: Dict[str, Any],
    x: jax.Array,                  # [B, S, H] compute dtype
    *,
    top_k: int,
    capacity_factor: float,
    min_capacity: int,
    activation: str,
    drop_tokens: bool,
    rng: Optional[jax.Array],
    noise_std: float,
    norm_topk: bool,
) -> Tuple[jax.Array, jax.Array]:
    """GShard einsum dispatch.  The two dispatch einsums below are the comm
    boundary: with `w_up/w_down` sharded over `ep`, XLA partitions `ecm`
    over ep and inserts the token->expert AllToAll (reference: _AllToAll
    sharded_moe.py:96)."""
    B, S, H = x.shape
    dt = x.dtype
    T = B * S
    E = params["w_up"].shape[0]
    xt = x.reshape(T, H)

    logits = (xt.astype(jnp.float32) @ params["gate"])    # [T, E] fp32
    C = compute_capacity(T, E, capacity_factor, min_capacity)
    dispatch, combine, l_aux, _ = topk_gating(
        logits, top_k, C, rng=rng, noise_std=noise_std,
        drop_tokens=drop_tokens, norm_topk=norm_topk)

    # token -> expert buffers: [E, C, H]
    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(dt), xt,
                           preferred_element_type=jnp.float32).astype(dt)

    expert_out = _expert_ffn(params, expert_in, activation)

    # expert -> token combine
    out = jnp.einsum("tec,ech->th", combine.astype(dt), expert_out,
                     preferred_element_type=jnp.float32).astype(dt)
    return out.reshape(B, S, H), l_aux


def _moe_layer_a2a(
    params: Dict[str, Any],
    x: jax.Array,                  # [B, S, H] compute dtype
    *,
    top_k: int,
    capacity_factor: float,
    min_capacity: int,
    activation: str,
    drop_tokens: bool,
    rng: Optional[jax.Array],
    noise_std: float,
    norm_topk: bool,
    dispatch_bits: Optional[int],
    ep_axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """Explicit all_to_all dispatch: tokens split over `ep_axis` inside a
    shard_map region, each rank gates its LOCAL tokens against the full
    gate, builds per-expert capacity buffers, and the a2a pair ships them
    to/from the owning ranks.  Capacity is computed from the LOCAL token
    count, so the per-expert slot total matches the einsum form's global
    capacity exactly when T divides evenly."""
    from ..parallel.context import require_topology, shard_map_mesh
    from jax import shard_map

    topo = require_topology()
    ep = topo.size(ep_axis)
    B, S, H = x.shape
    T = B * S
    E = params["w_up"].shape[0]
    if T % ep:
        raise ValueError(
            f"a2a dispatch needs tokens ({T}) divisible by ep={ep}")
    if E % ep:
        raise ValueError(
            f"a2a dispatch needs num_experts ({E}) divisible by ep={ep}")
    C_loc = compute_capacity(T // ep, E, capacity_factor, min_capacity)
    use_noise = noise_std > 0.0 and rng is not None
    rng_arr = rng if rng is not None else jax.random.PRNGKey(0)

    wp = {"w_up": params["w_up"], "w_down": params["w_down"]}
    wspec = {"w_up": PartitionSpec(AXIS_EP, None, None),
             "w_down": PartitionSpec(AXIS_EP, None, None)}
    if activation == "swiglu":
        wp["w_gate_proj"] = params["w_gate_proj"]
        wspec["w_gate_proj"] = PartitionSpec(AXIS_EP, None, None)

    def local(gate, wloc, xt, r):
        # xt: [T/ep, H] local tokens; wloc: [E/ep, ...] local experts
        dt = xt.dtype
        logits = xt.astype(jnp.float32) @ gate            # [T/ep, E]
        r = (jax.random.fold_in(r, jax.lax.axis_index(ep_axis))
             if use_noise else None)
        dispatch, combine, l_aux, _ = topk_gating(
            logits, top_k, C_loc, rng=r, noise_std=noise_std,
            drop_tokens=drop_tokens, norm_topk=norm_topk)
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(dt), xt,
                               preferred_element_type=jnp.float32
                               ).astype(dt)                # [E, C_loc, H]
        expert_in = moe_dispatch_a2a(expert_in, ep_axis, dispatch_bits)
        expert_out = _expert_ffn(wloc, expert_in, activation)
        expert_out = moe_combine_a2a(expert_out, ep_axis, dispatch_bits)
        out = jnp.einsum("tec,ech->th", combine.astype(dt), expert_out,
                         preferred_element_type=jnp.float32).astype(dt)
        # aux loss averages over ranks (each rank's me/ce are local means)
        return out, jax.lax.pmean(l_aux, ep_axis)

    # full-manual over every mesh axis, not partial-manual over just ep:
    # non-ep axes see replicated tokens/weights inside the region, which
    # is correct (dp replicas compute identical MoE output).
    out, l_aux = shard_map(
        local, mesh=shard_map_mesh(topo),
        in_specs=(PartitionSpec(), wspec, PartitionSpec(AXIS_EP, None),
                  PartitionSpec()),
        out_specs=(PartitionSpec(AXIS_EP, None), PartitionSpec()),
        check_vma=False)(params["gate"], wp, x.reshape(T, H), rng_arr)
    return out.reshape(B, S, H), l_aux


def moe_layer(
    params: Dict[str, Any],
    x: jax.Array,                  # [B, S, H] compute dtype
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    min_capacity: int = 4,
    activation: str = "gelu",
    drop_tokens: bool = True,
    rng: Optional[jax.Array] = None,
    noise_std: float = 0.0,
    norm_topk: bool = True,
    dispatch: str = "einsum",
    dispatch_bits: Optional[int] = None,
    ep_axis: str = AXIS_EP,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [B,S,H], l_aux scalar).

    dispatch="einsum" (default): GShard einsum form, collectives inserted
    by the SPMD partitioner.  dispatch="a2a": the reference's explicit
    all_to_all token-buffer exchange (shard_map manual over `ep_axis`),
    optionally with the pair riding the int8/int4 block-quant wire
    (`dispatch_bits` — lossy, loss-parity-gated; None = bit-exact).
    Without an ep axis in the ambient topology the a2a form degenerates
    to the identical local computation."""
    if dispatch not in ("einsum", "a2a"):
        raise ValueError(f"unknown moe dispatch {dispatch!r} "
                         f"(einsum | a2a)")
    if dispatch_bits and dispatch != "a2a":
        raise ValueError(
            "dispatch_bits requires dispatch='a2a': the einsum form's "
            "collectives are partitioner-inserted and cannot ride the "
            "quantized wire")
    if dispatch_bits and dispatch_bits not in (4, 8):
        raise ValueError(f"dispatch_bits must be 4 or 8, "
                         f"got {dispatch_bits}")
    kw = dict(top_k=top_k, capacity_factor=capacity_factor,
              min_capacity=min_capacity, activation=activation,
              drop_tokens=drop_tokens, rng=rng, noise_std=noise_std,
              norm_topk=norm_topk)
    if dispatch == "a2a":
        from ..parallel.context import get_current_topology
        topo = get_current_topology()
        if topo is not None and topo.size(ep_axis) > 1:
            return _moe_layer_a2a(params, x, dispatch_bits=dispatch_bits,
                                  ep_axis=ep_axis, **kw)
        # no ep axis: fall through — the local math is the einsum form
    return _moe_layer_einsum(params, x, **kw)
