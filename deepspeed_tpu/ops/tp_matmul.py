"""Fused compute-collective matmuls for tensor-parallel decode.

The Megatron-style TP block pays two collectives per transformer block
(one for attention, one for the MLP).  Stock XLA lowers each as a
standalone all-reduce that serializes with the matmul producing (or
consuming) its payload — at decode batch sizes the ICI sits idle while
the MXU runs, then the MXU sits idle while the ICI runs.  The two
retrieved papers close that gap by FUSING the collective into the GEMM:

- "Optimizing Distributed ML Communication with Fused
  Computation-Collective Operations" (arxiv 2305.06942): embed the
  all-gather / reduce-scatter steps into the GEMM's tile loop so
  communication of one tile overlaps computation of the next.
- "The Big Send-off: High Performance Collectives on GPU-based
  Supercomputers" (arxiv 2504.18658): the producer/consumer formulation —
  an all-gather whose consumer multiplies shard chunks as they stream
  in, and a partial-sum producer whose tiles ship ring-ward as they
  finish.

TPU formulation (this module): the ring schedule is expressed as
`tp` per-chunk matmuls interleaved with `jax.lax.ppermute` hops inside
a shard_map region.  The permute of step k carries no data dependency
on step k's matmul, so XLA's latency-hiding scheduler issues
collective-permute-start, runs the matmul, then waits on
collective-permute-done — the overlap is STRUCTURAL in the scheduled
executable (async start/done pairs with MXU compute between); no test
or cell reads it off a TPU executable yet.  Each per-chunk matmul runs
as a Pallas MXU kernel on TPU (`tile_matmul`), with `jnp.dot` as the
portable escape (and the CPU-test path).

Two fused primitives, mirroring the papers' pair:

- `ag_matmul`:  all-gather PRODUCER matmul.  `x_local` is this shard's
  ROW chunk of a sequence/row-sharded activation; the full-row output
  of `x @ w_local` is assembled by multiplying each chunk as it arrives
  on the ring.  Output: full rows, the caller's (column-sharded) N.
- `matmul_rs`:  matmul REDUCE-SCATTER consumer.  `x` holds full rows of
  a column-sharded activation (`ag_matmul`'s output shape), `w_local`
  the matching row shard of a row-parallel weight; partial row-chunk
  tiles are computed just in time and ring-accumulated, so each shard
  ends holding its fully-reduced row chunk.  The pair
  `matmul_rs -> (residual ops) -> ag_matmul` is comm-equivalent to one
  all-reduce per block, with every byte hidden behind a matmul tile.

The plain-XLA twins (`ag_matmul_xla` / `matmul_rs_xla`) keep the same
signatures over `jax.lax.all_gather` / `psum_scatter` — the default
escape hatch (`tp_collectives="xla"` in the engine config) and the
unfused arm of `benchmarks/comms_bench.py --tp-inference`.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "tile_matmul",
    "tile_matmul_supported",
    "ag_matmul",
    "matmul_rs",
    "ag_matmul_xla",
    "matmul_rs_xla",
]


# ----------------------------------------------------------------------
# Pallas tiled matmul (the per-chunk GEMM of the ring schedules)
# ----------------------------------------------------------------------
def _pick_block(dim: int, candidates) -> Optional[int]:
    for c in candidates:
        if dim % c == 0:
            return c
    return None


def tile_matmul_supported(M: int, K: int, N: int) -> bool:
    """Shapes the Pallas tile kernel serves: every dim must factor into
    MXU-aligned blocks (sublane multiples of 8 on M, 128-lane multiples
    on K and N).  Anything else takes the jnp escape — same math, XLA's
    own tiling."""
    return (_pick_block(M, (256, 128, 64, 32, 16, 8)) is not None
            and _pick_block(K, (512, 256, 128)) is not None
            and _pick_block(N, (512, 256, 128)) is not None)


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:], w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[:] = acc_ref[:]


def _pallas_matmul(x, w):
    """[M, K] @ [K, N] -> f32 [M, N] on the MXU, tiled over an
    (M/bm, N/bn, K/bk) grid with a VMEM f32 accumulator (K iterates
    innermost, so each output tile accumulates across its K blocks
    before the store)."""
    M, K = x.shape
    _, N = w.shape
    bm = _pick_block(M, (256, 128, 64, 32, 16, 8))
    bk = _pick_block(K, (512, 256, 128))
    bn = _pick_block(N, (512, 256, 128))
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        name="tp_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, w)


def tile_matmul(x, w, *, impl: str = "auto"):
    """2-D matmul with f32 accumulation: `x [M, K] @ w [K, N] -> f32`.

    impl="auto" runs the Pallas MXU kernel on TPU for tile-able shapes
    and `jnp.dot` everywhere else; "pallas" forces the kernel (raising
    when the platform/shape cannot run it — a silent fallback would
    benchmark the wrong implementation, the `_gate_fused` discipline);
    "jnp" is the explicit escape hatch."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"impl must be auto|pallas|jnp, got {impl!r}")
    M, K = x.shape
    N = w.shape[1]
    if impl != "jnp":
        from ..utils.device import on_tpu
        capable = on_tpu() and tile_matmul_supported(M, K, N)
        if impl == "pallas" and not capable:
            raise ValueError(
                f"impl='pallas' requested but the tile matmul cannot run "
                f"here (needs TPU and MXU-aligned dims; got "
                f"[{M},{K}]x[{K},{N}]) — a silent dense fallback would "
                f"benchmark the wrong implementation")
        if capable:
            return _pallas_matmul(x, w)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# fused ring collective-matmuls (call from INSIDE a shard_map region)
# ----------------------------------------------------------------------
def ag_matmul(x_local, axis_name: str, tp: int,
              mm: Callable[[jnp.ndarray], jnp.ndarray]):
    """All-gather-producer matmul (fused): the activation's row shards
    stream around the ring while each arriving chunk multiplies through
    this shard's weight columns.

    x_local: [s, K] — this shard's row chunk of the logically [tp*s, K]
    activation (row chunk i lives on tp-index i).  `mm` maps one
    [s, K] chunk to its [s, N] product (the per-chunk GEMM — Pallas on
    TPU via `tile_matmul`).  Returns [tp*s, N]: full rows, the caller's
    local N columns.  Step k multiplies the chunk that originated at
    shard (idx + k) while the ring forwards it onward — the permute of
    step k has no dependency on step k's matmul, which is the overlap.
    """
    idx = jax.lax.axis_index(axis_name)
    s = x_local.shape[0]
    chunk = x_local
    y = mm(chunk)
    out = jnp.zeros((tp * s,) + y.shape[1:], y.dtype)
    out = jax.lax.dynamic_update_slice(out, y, (idx * s,) + (0,) * (y.ndim - 1))
    fwd = [(i, (i - 1) % tp) for i in range(tp)]   # receive from idx+1
    for k in range(1, tp):
        chunk = jax.lax.ppermute(chunk, axis_name, fwd)
        src = (idx + k) % tp
        y = mm(chunk)
        out = jax.lax.dynamic_update_slice(
            out, y, (src * s,) + (0,) * (y.ndim - 1))
    return out


def matmul_rs(x, axis_name: str, tp: int,
              mm: Callable[[jnp.ndarray], jnp.ndarray]):
    """Matmul-reduce-scatter consumer (fused): partial row-chunk tiles
    are computed just in time and accumulated around the ring; each
    shard ends holding its own row chunk fully reduced over the
    contraction shards.

    x: [S, K_local] — FULL rows with this shard's slice of the
    contraction dim (the shape a column-parallel stage produces).  `mm`
    maps a [S/tp, K_local] row chunk to its [S/tp, N] f32 partial
    product.  Returns [S/tp, N] f32 — row chunk `axis_index`, summed
    over all tp shards (the caller casts/biases ONCE after the ring so
    accumulation stays f32).  Chunk c's accumulation starts at shard
    c+1 and visits every shard, ending at c; step k's matmul is
    independent of step k's permute, which is the overlap."""
    idx = jax.lax.axis_index(axis_name)
    S = x.shape[0]
    s = S // tp

    def part(c):
        rows = jax.lax.dynamic_slice_in_dim(x, c * s, s, 0)
        return mm(rows)

    acc = part((idx + tp - 1) % tp)
    fwd = [(i, (i + 1) % tp) for i in range(tp)]   # send toward idx+1
    for k in range(1, tp):
        acc = jax.lax.ppermute(acc, axis_name, fwd)
        acc = acc + part((idx + tp - 1 - k) % tp)
    return acc


# ----------------------------------------------------------------------
# plain-XLA twins (the unfused escape hatch / bench baseline)
# ----------------------------------------------------------------------
def ag_matmul_xla(x_local, axis_name: str, tp: int,
                  mm: Callable[[jnp.ndarray], jnp.ndarray]):
    """Same contract as `ag_matmul`, one monolithic all-gather then one
    GEMM — the collective fully serializes with the matmul."""
    del tp
    x = jax.lax.all_gather(x_local, axis_name, axis=0, tiled=True)
    return mm(x)


def matmul_rs_xla(x, axis_name: str, tp: int,
                  mm: Callable[[jnp.ndarray], jnp.ndarray]):
    """Same contract as `matmul_rs`, one monolithic GEMM then a
    psum_scatter of the full partial product."""
    del tp
    return jax.lax.psum_scatter(mm(x), axis_name, scatter_dimension=0,
                                tiled=True)
