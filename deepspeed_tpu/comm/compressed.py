"""Compressed & quantized collectives.

Reference:
- ZeRO++ qgZ: `all_to_all_quant_reduce` (runtime/comm/coalesced_collectives.py
  :31, LoCo variant :81) — quantize grads int4/int8, all-to-all, dequant,
  local reduce, requantize, second a2a (hierarchical on DGX boxes).
- ZeRO++ qwZ: quantized weight allgather (partition_parameters.py
  CUDAQuantizer:824 + all_gather_coalesced).
- EQuARX (arxiv 2506.17615): XLA-native quantized all-reduce — quantized
  reduce-scatter + quantized all-gather with payload and scales shipped in
  ONE buffer per hop (`quantized_all_reduce` below).
- 1-bit optimizers' compressed allreduce with error feedback
  (runtime/comm/nccl.py `NcclBackend`, compressed.py `CompressedBackend`).

TPU formulation: each primitive is quantize -> XLA collective -> dequantize
inside the compiled program (int8 rides ICI at 1/2-1/4 the bytes of bf16;
cf. PAPERS.md EQuARX for the same trick inside XLA itself).  Error-feedback
state threads through functionally (no in-place buffers).

Wire layout: symmetric block quantization has a zero offset of exactly 0,
so only the int8 codes and the f32 per-block scales cross the wire — and
they cross FUSED: the scales are bitcast to int8 bytes and concatenated
onto the payload, so each hop is ONE collective launch instead of the
three (codes, scales, zeros) the r3 implementation paid per leaf.  Every
primitive reports its actual on-wire payload bytes (int8/int4 codes +
scale bytes) to the CommsLogger at trace time, so telemetry shows the
quantization saving instead of logical bf16 volume.

Hierarchy (ZeRO++ 2-hop qgZ): `hierarchical_quantized_reduce_scatter`
reduces over a factored (intra, inter) mesh-axis pair — full-precision (or
int8) reduce-scatter over the ICI-like intra axis first, so only 1/intra of
the data crosses the DCN-like inter axis, quantized.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.quantization import (dequantize_blockwise, quantize_blockwise)
from jax.lax import axis_size
from .comm import comms_logger

__all__ = [
    "quantized_all_gather",
    "quantized_reduce_scatter",
    "hierarchical_quantized_reduce_scatter",
    "quantized_all_reduce",
    "compressed_all_reduce",
    "onebit_compress",
    "onebit_decompress",
]


def _pack_nibbles(q):
    """int8 4-bit codes [..., n] -> one int8 per PAIR [..., ceil(n/2)]:
    without this, int4 rides unpacked in int8 containers and the collective
    moves the same bytes as int8 (the whole point of bits=4 is the halving).
    Odd n pads one zero nibble (trimmed by `_unpack_nibbles(p, n)`)."""
    if q.shape[-1] % 2:
        q = jnp.concatenate(
            [q, jnp.zeros(q.shape[:-1] + (1,), q.dtype)], axis=-1)
    lo = q[..., 0::2] & 0xF
    hi = q[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def _unpack_nibbles(p, n: Optional[int] = None):
    """Inverse of _pack_nibbles (sign-extend each nibble).  `n` trims the
    output to the original pre-pad length when it was odd."""
    lo = ((p & 0xF) ^ 8) - 8
    hi = p >> 4                      # arithmetic shift sign-extends int8
    out = jnp.stack([lo, hi], axis=-1)
    out = out.reshape(p.shape[:-1] + (p.shape[-1] * 2,)).astype(jnp.int8)
    if n is not None and n != out.shape[-1]:
        out = out[..., :n]
    return out


# ----------------------------------------------------------------------
# fused wire buffers: one int8 launch carries codes AND scales
# ----------------------------------------------------------------------
def _fuse_wire(q, scale):
    """[..., B] int8 codes + [..., nb] f32 scales -> one int8 wire buffer
    [..., B + 4*nb].  The scales ride as raw bytes (bitcast), so a single
    collective moves everything a hop needs — EQuARX's fused payload."""
    sb = jax.lax.bitcast_convert_type(scale, jnp.int8)       # [..., nb, 4]
    sb = sb.reshape(scale.shape[:-1] + (scale.shape[-1] * 4,))
    return jnp.concatenate([q, sb], axis=-1)


def _unfuse_wire(wire, nb: int):
    """Inverse of _fuse_wire: -> (codes [..., B], scales f32 [..., nb])."""
    q = wire[..., : wire.shape[-1] - 4 * nb]
    sb = wire[..., wire.shape[-1] - 4 * nb:]
    sb = sb.reshape(sb.shape[:-1] + (nb, 4))
    return q, jax.lax.bitcast_convert_type(sb, jnp.float32)


def _quantize_wire(x, bits: int, block_size: int):
    """Quantize one tensor to a flat fused wire buffer.
    Returns (wire int8 [W], nb, n_codes, meta)."""
    q, scale, _zero, meta = quantize_blockwise(x, bits, block_size)
    nb = q.shape[0]
    flat = q.reshape(-1)
    n_codes = flat.shape[0]
    if bits == 4:
        flat = _pack_nibbles(flat)   # halve the payload for real
    return _fuse_wire(flat, scale), nb, n_codes, meta


def _dequantize_wire(wire, nb: int, n_codes: int, meta):
    """Inverse of _quantize_wire for one tensor (or a [ranks, W] batch via
    vmap at the call site)."""
    bits, block_size = meta[3], meta[2]
    flat, scale = _unfuse_wire(wire, nb)
    if bits == 4:
        flat = _unpack_nibbles(flat, n_codes)
    q = flat.reshape(nb, block_size)
    zero = jnp.zeros_like(scale)
    return dequantize_blockwise(q, scale, zero, meta)


def _record(op: str, wire, axis) -> None:
    """Trace-time CommsLogger accounting of the ACTUAL on-wire payload
    (int8 codes + scale bytes), not the logical bf16 volume."""
    comms_logger.record(op, int(np.prod(wire.shape)) * wire.dtype.itemsize,
                        str(axis))


def quantized_all_gather(x, axis_name: str, bits: int = 8,
                         block_size: int = 256, gather_axis: int = 0):
    """qwZ-style: quantize the local shard, AllGather ONE fused
    payload+scales buffer, dequantize.  Comm volume = 1/2 (int8) or 1/4
    (int4, nibble-packed) of bf16, plus 4 B/block of scales."""
    wire, nb, n_codes, meta = _quantize_wire(x, bits, block_size)
    _record("quantized_all_gather", wire, axis_name)
    wg = jax.lax.all_gather(wire, axis_name, axis=0, tiled=False)
    # one vmapped dequant over the gathered rank axis (O(1) program size)
    parts = jax.vmap(lambda w: _dequantize_wire(w, nb, n_codes, meta))(wg)
    return jnp.concatenate(list(parts), axis=gather_axis)


def quantized_reduce_scatter(x, axis_name: str, axis_size: int,
                             bits: int = 8, block_size: int = 256):
    """qgZ-style gradient reduction: quantize -> AllToAll (each rank receives
    every rank's slice of its partition) -> dequant -> local sum.
    One-hop version of coalesced_collectives.py:31; the 2-hop hierarchical
    variant is `hierarchical_quantized_reduce_scatter`.  x: [N, ...] with
    N % axis_size == 0; returns the local partition's reduced slice
    [N/axis_size, ...].  Payload and scales ride one fused int8 a2a."""
    n = x.shape[0]
    assert n % axis_size == 0
    # quantize each destination's slice independently (one vmapped quantize —
    # O(1) program size in the axis size), then a2a the fused payloads
    slices = x.reshape((axis_size, n // axis_size) + x.shape[1:])
    # meta is static (shape/pad/dtype), so construct it directly and vmap
    # only the array outputs
    slice_shape = slices.shape[1:]
    pad = (-int(np.prod(slice_shape))) % block_size
    meta = (slice_shape, pad, block_size, bits, True, x.dtype)
    wires = jax.vmap(
        lambda sl: _quantize_wire(sl, bits, block_size)[0])(slices)
    nb = (int(np.prod(slice_shape)) + pad) // block_size
    n_codes = nb * block_size
    _record("quantized_reduce_scatter", wires, axis_name)
    wg = jax.lax.all_to_all(wires, axis_name, split_axis=0, concat_axis=0,
                            tiled=False)
    deq = jax.vmap(lambda w: _dequantize_wire(w, nb, n_codes, meta))(wg)
    return jnp.sum(deq, axis=0)


def hierarchical_quantized_reduce_scatter(
        x, intra_axis: str, inter_axis: str, intra_size: int,
        inter_size: int, *, bits: int = 8, intra_bits: int = 0,
        block_size: int = 256):
    """ZeRO++ 2-hop qgZ over a factored (intra, inter) topology.

    Hop 1 rides the fast intra (ICI-like) axis: a full-precision
    reduce-scatter (``intra_bits=0``, the reference's intra-node tensor
    slicing at working precision) or a quantized one (``intra_bits=4/8``).
    Hop 2 ships the intra-reduced partial — already 1/intra_size of the
    data — over the slow inter (DCN-like) axis as a quantized all-to-all +
    local sum.  Equivalent (up to quantization) to a reduce-scatter over
    the combined group with the INTRA axis major in the partitioned dim:
    device (i, j) ends with slice ``i * inter_size + j`` of the sum,
    matching a ``PartitionSpec((intra, inter))`` layout of that dim.

    x: [N, ...] with N % (intra_size * inter_size) == 0; returns
    [N / (intra_size * inter_size), ...].
    """
    n = x.shape[0]
    group = intra_size * inter_size
    assert n % group == 0, (n, intra_size, inter_size)
    if intra_size > 1:
        if intra_bits:
            x = quantized_reduce_scatter(x, intra_axis, intra_size,
                                         bits=intra_bits,
                                         block_size=block_size)
        else:
            _record("reduce_scatter_intra", x, intra_axis)
            x = jax.lax.psum_scatter(x, intra_axis, scatter_dimension=0,
                                     tiled=True)
    if inter_size > 1:
        x = quantized_reduce_scatter(x, inter_axis, inter_size, bits=bits,
                                     block_size=block_size)
    return x


def quantized_all_reduce(x, axis_name, group_size: Optional[int] = None,
                         *, bits: int = 8, block_size: int = 256):
    """EQuARX-style quantized all-reduce: quantized reduce-scatter (fused
    payload+scales all-to-all) + re-quantize + quantized all-gather (fused
    again) — TWO int8 launches replace one bf16/f32 psum at ~1/2 (int8) or
    ~1/4 (int4) of the wire bytes.  Shape- and layout-preserving, so it
    drops in for `jax.lax.psum` of gradients (the stage<3 data-axis grad
    path).  `axis_name` may be a tuple of mesh axes (joint group).

    Lossy (block-quantization error on both hops) — gate behind a measured
    loss-parity test, as runtime/zero/quantized.py's config flags do.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if group_size is None:
        group_size = 1
        for a in axes:
            group_size *= axis_size(a)
    if group_size == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    # every rank reduces one chunk; pad so chunks are whole blocks
    chunk = -(-n // group_size)
    chunk += (-chunk) % block_size
    pad = group_size * chunk - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(group_size, chunk)
    nb = chunk // block_size
    meta = ((chunk,), 0, block_size, bits, True, jnp.float32)
    # hop 1: fused quantized reduce-scatter (a2a + local sum)
    wires = jax.vmap(
        lambda c: _quantize_wire(c, bits, block_size)[0])(chunks)
    _record("quantized_all_reduce", wires, axes)
    recv = jax.lax.all_to_all(wires, axes, split_axis=0, concat_axis=0,
                              tiled=False)
    deq = jax.vmap(lambda w: _dequantize_wire(w, nb, chunk, meta))(recv)
    reduced = jnp.sum(deq, axis=0)                       # my chunk, reduced
    # hop 2: fused quantized all-gather of the reduced chunk
    wire2, nb2, n2, meta2 = _quantize_wire(reduced, bits, block_size)
    _record("quantized_all_reduce", wire2, axes)
    allw = jax.lax.all_gather(wire2, axes, axis=0, tiled=False)
    out = jax.vmap(lambda w: _dequantize_wire(w, nb2, n2, meta2))(allw)
    out = out.reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(shape).astype(dtype)


# ----------------------------------------------------------------------
# 1-bit compression with error feedback (reference: runtime/comm/nccl.py)
# ----------------------------------------------------------------------
def onebit_compress(x, error: Optional[jax.Array] = None):
    """sign(x + error) * rms(x + error); returns (signs int8, scale,
    new_error).  The error-feedback recurrence of 1-bit Adam (adam.py:14);
    scale is the RMS norm per tensor (the reference scales each chunk by
    norm/sqrt(numel), runtime/comm/nccl.py compressed_allreduce)."""
    xf = x.astype(jnp.float32)
    if error is not None:
        xf = xf + error
    scale = jnp.linalg.norm(xf.ravel()) / jnp.sqrt(xf.size)
    signs = jnp.where(xf >= 0, 1, -1).astype(jnp.int8)
    decompressed = signs.astype(jnp.float32) * scale
    new_error = xf - decompressed
    return signs, scale, new_error


def onebit_decompress(signs, scale):
    return signs.astype(jnp.float32) * scale


def compressed_all_reduce(x, axis_name: str, error: Optional[jax.Array] = None,
                          server_error: Optional[jax.Array] = None):
    """1-bit allreduce with two-stage error feedback (reference:
    NcclBackend.compressed_allreduce — worker compression, chunked
    reduce-scatter exchange, server compression, allgather).

    Only int8 sign payloads (plus one f32 scale scalar per rank) cross the
    wire: stage 1 is an AllToAll of each rank's int8 sign chunks so rank r
    reduces chunk r; stage 2 re-compresses the reduced chunk (with its own
    error feedback) and AllGathers the int8 result.  Wire volume per rank is
    ~2 bytes/element vs ~8 for a ring fp32 allreduce.

    Returns (avg_tensor, new_error, new_server_error); `new_error` is shaped
    like `x`, `new_server_error` like this rank's flat chunk (pass both back
    in on the next call, as the 1-bit optimizers do)."""
    world = axis_size(axis_name)
    n = x.size
    signs, scale, new_error = onebit_compress(x, error)
    flat = signs.ravel()
    pad = (-n) % world
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(world, -1)
    # stage 1 wire: int8 chunks a2a + per-rank f32 scale allgather
    _record("compressed_all_reduce", chunks, axis_name)
    recv = jax.lax.all_to_all(chunks, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                    # [world, chunk]
    scales = jax.lax.all_gather(scale, axis_name)             # [world]
    server_chunk = jnp.einsum(
        "w,wc->c", scales, recv.astype(jnp.float32)) / world
    # stage 2: compress the reduced chunk with server-side error feedback
    s_signs, s_scale, new_server_error = onebit_compress(
        server_chunk, server_error)
    # stage 2 wire: int8 server signs + f32 scalar scales
    _record("compressed_all_reduce", s_signs, axis_name)
    all_signs = jax.lax.all_gather(s_signs, axis_name)        # [world, chunk]
    all_scales = jax.lax.all_gather(s_scale, axis_name)       # [world]
    out = (all_signs.astype(jnp.float32) * all_scales[:, None]).ravel()
    out = out[:n].reshape(x.shape).astype(x.dtype)
    return out, new_error, new_server_error
