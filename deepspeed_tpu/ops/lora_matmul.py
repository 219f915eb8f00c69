"""Gather-LoRA epilogue for multi-tenant ragged serving.

One base model serves many per-tenant LoRA adapters from a SINGLE
continuous batch: every row of the ragged batch carries an adapter slot
id, and the dense projections gain a low-rank epilogue

    y[s] += scaling * (x[s] @ A[id[s]]) @ B[id[s]]        (id[s] >= 0)
    y[s] += 0                                             (id[s] < 0)

so rows of different tenants — and base-model rows with no adapter at
all — share one compiled program instead of one batch per adapter
(the multi-LoRA serving formulation of Punica/S-LoRA: arxiv 2310.18547,
arxiv 2311.03285).  The `id < 0` branch is the PARITY LOCK: a base row's
delta is EXACTLY zero (a masked select against a 0.0 constant, never an
`0 * garbage` that could leak NaNs), which is what lets the serve loop
promise `adapter_id=None` output token-identical to single-tenant
serving.

Two implementations with one contract, the `ops/tp_matmul.tile_matmul`
discipline:

- Pallas MXU kernel (`impl="pallas"` / "auto" on TPU): rows are grouped
  by adapter with a masked SEGMENTED accumulation over a
  (row_tiles, num_slots) grid — slot j's factors are resident in VMEM
  while every row tile streams past, rows of other adapters contribute
  through the mask as exact zeros, and the per-tile f32 accumulator
  carries the sum across the slot dimension (innermost grid dim, the
  `_mm_kernel` init/store pattern).  Row counts pad to the f32 sublane
  tile via the `ops/paged_prefill.pad_to_sublane_tile` contract (pad
  rows ride with id=-1 and are sliced off outside the kernel).  The
  dense slot sweep costs `num_slots` rank-r passes per tile — the
  epilogue's r is tiny next to the base GEMM's K, so the sweep stays a
  rounding error for the slot counts a pool holds resident.
- `jnp` escape (`impl="jnp"` / non-TPU "auto"): per-row gathered
  factors through two einsums — same math, XLA's tiling, the CPU test
  path.  `interpret=True` runs the Pallas kernel in interpret mode
  instead, the parity harness for the kernel's masking/accumulation
  logic on CPU (the `ops/paged_merged` test discipline).

`impl="pallas"` on an unsupported platform/shape raises loudly — a
silent dense fallback would benchmark the wrong implementation (the
`_gate_fused` discipline).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_prefill import pad_to_sublane_tile

__all__ = ["lora_delta", "lora_delta_supported", "pad_lora_rank"]

# lane width the MXU contracts over; LoRA ranks (8-64) pad up to one
# full lane tile, zero columns contributing exact zeros
_LANES = 128
# VMEM budget for one grid step's working set (x tile + slot factors +
# out/acc tiles) — the paged_prefill headroom discipline
_VMEM_BUDGET = 6 * 2 ** 20


def pad_lora_rank(r: int) -> int:
    """Rank padded to the 128-lane tile the kernel contracts over; zero
    pad columns in A (and rows in B) contribute exactly zero."""
    if r < 1:
        raise ValueError(f"LoRA rank must be >= 1, got {r}")
    return -(-r // _LANES) * _LANES


def lora_delta_supported(S: int, K: int, N: int, num_slots: int) -> bool:
    """Shapes the Pallas kernel serves: K and N must be 128-lane
    multiples (the factor matmuls' contraction/output lanes), rows pad
    to a sublane tile, and one grid step's VMEM working set must fit.
    Anything else takes the jnp escape — same math, XLA's tiling."""
    if num_slots < 1 or S < 1:
        return False
    if K % _LANES != 0 or N % _LANES != 0:
        return False
    Sp, bm = pad_to_sublane_tile(S)
    if bm is None:
        return False
    rp = _LANES
    working = 4 * (bm * K + K * rp + rp * N + 2 * bm * N + bm)
    return working <= _VMEM_BUDGET


def _lora_kernel(x_ref, ids_ref, a_ref, b_ref, o_ref, acc_ref, *,
                 num_slots: int):
    j = pl.program_id(1)                       # adapter slot (innermost)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # slot j's low-rank pass over this row tile; rows of OTHER adapters
    # are masked to an exact 0.0 (never 0 * x — the parity lock)
    h = jnp.dot(x_ref[:], a_ref[0],
                preferred_element_type=jnp.float32)        # [bm, rp]
    y = jnp.dot(h, b_ref[0],
                preferred_element_type=jnp.float32)        # [bm, N]
    mask = ids_ref[:] == j                                 # [bm, 1]
    acc_ref[:] += jnp.where(mask, y, 0.0)

    @pl.when(j == num_slots - 1)
    def _store():
        o_ref[:] = acc_ref[:]


def _pallas_lora_delta(x, lora_a, lora_b, ids, interpret: bool):
    S, K = x.shape
    A, _, r = lora_a.shape
    N = lora_b.shape[2]
    rp = pad_lora_rank(r)
    if rp != r:
        lora_a = jnp.pad(lora_a, ((0, 0), (0, 0), (0, rp - r)))
        lora_b = jnp.pad(lora_b, ((0, 0), (0, rp - r), (0, 0)))
    Sp, bm = pad_to_sublane_tile(S)
    if Sp != S:
        x = jnp.pad(x, ((0, Sp - S), (0, 0)))
        ids = jnp.pad(ids, (0, Sp - S), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_lora_kernel, num_slots=A),
        name="lora_matmul",
        grid=(Sp // bm, A),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, K, rp), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, rp, N), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, N), jnp.float32)],
        interpret=interpret,
    )(x, ids[:, None], lora_a, lora_b)
    return out[:S]


def lora_delta(x, lora_a, lora_b, adapter_ids, *, scaling: float = 1.0,
               impl: str = "auto", interpret: bool = False):
    """Per-row low-rank delta: f32 `[S, N]` (see module docstring).

    x: [S, K] batch rows; lora_a: [num_slots, K, r]; lora_b:
    [num_slots, r, N]; adapter_ids: [S] int32 slot per row, < 0 = base
    row (delta exactly 0.0).  impl="auto" runs the Pallas kernel on TPU
    for supported shapes and the jnp gather path everywhere else;
    "pallas" forces the kernel (raising when it cannot run here);
    "jnp" is the explicit escape.  `interpret=True` runs the kernel in
    Pallas interpret mode on any backend (the CPU parity harness)."""
    if impl not in ("auto", "pallas", "jnp"):
        raise ValueError(f"impl must be auto|pallas|jnp, got {impl!r}")
    S, K = x.shape
    A, Ka, r = lora_a.shape
    Ab, rb, N = lora_b.shape
    if Ka != K or Ab != A or rb != r:
        raise ValueError(
            f"LoRA factor shapes disagree: x [{S},{K}], lora_a "
            f"[{A},{Ka},{r}], lora_b [{Ab},{rb},{N}] (need a "
            f"[slots,K,r] / [slots,r,N] stack over one slot axis)")
    ids = jnp.asarray(adapter_ids, jnp.int32)
    if impl != "jnp":
        from ..utils.device import on_tpu
        capable = ((on_tpu() or interpret)
                   and lora_delta_supported(S, K, N, A))
        if impl == "pallas" and not capable:
            raise ValueError(
                f"impl='pallas' requested but the LoRA kernel cannot run "
                f"here (needs TPU or interpret=True, 128-lane K/N and a "
                f"VMEM-fitting tile; got [{S},{K}]x[{A},{K},{r}]x"
                f"[{A},{r},{N}]) — a silent dense fallback would "
                f"benchmark the wrong implementation")
        if capable:
            out = _pallas_lora_delta(x, lora_a, lora_b, ids, interpret)
            return out * scaling if scaling != 1.0 else out
    # jnp escape: per-row gathered factors (ids clamped for the gather;
    # the mask — not the clamp — decides who contributes)
    safe = jnp.clip(ids, 0, A - 1)
    a = jnp.take(lora_a, safe, axis=0)                     # [S, K, r]
    h = jnp.einsum("sk,skr->sr", x, a,
                   preferred_element_type=jnp.float32)
    b = jnp.take(lora_b, safe, axis=0)                     # [S, r, N]
    out = jnp.einsum("sr,srn->sn", h, b.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = jnp.where(ids[:, None] >= 0, out, 0.0)
    return out * scaling if scaling != 1.0 else out
