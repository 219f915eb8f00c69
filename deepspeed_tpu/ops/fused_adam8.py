"""Fused 8-bit-Adam update kernel (Pallas TPU) — OPT-IN.

Why a kernel: the jnp int8-Adam update (runtime/optimizers.py
_make_adam_int8) requantizes the new moments with a per-row absmax, and
XLA cannot fuse a full-row reduction with its broadcast consumer — the
fp32 m_new/v_new intermediates round-trip HBM (~12 GB extra at the 774M
bench).  This kernel performs decode -> update -> row-amax -> requantize
in ONE VMEM pass per tile, cutting HBM traffic to the ~12.4 GB floor.

MEASURED OUTCOME (v5e-1, 774M, 2026-07-31, chained-dispatch timing):
jnp path 30-33 ms; this kernel 45-47 ms at both 128k- and 256k-element
tiles.  The update is VPU-COMPUTE-bound, not HBM-bound: the log-codebook
decode/encode costs ~40 VPU ops/element (exp2 + log2 + select chains)
~= 36 ms at the VPU's ~1 Tops — XLA's multi-pass overlaps that compute
under its (larger) HBM streams, while the single-pass kernel serializes
it after the tile load.  The kernel therefore stays OPT-IN
(optimizer params: {"fused_update": true}) until the codebook math is
cheapened; the engine default remains the jnp path.

Reference analog: csrc/adam/multi_tensor_adam.cu fuses the whole Adam
chain per 512-element chunk — on GPUs the same fusion wins because the
transcendental rate is far higher relative to HBM bandwidth.

Layout: each leaf is processed as [rows, R] with R = the original last
dim (the quantization row; _scale_shape in optimizers.py).  The grid
tiles rows; R rides whole so the row amax is a single in-tile
reduction.  Gating (runtime side): TPU backend + R % 128 == 0 + fp32
master; anything else falls back to the jnp path — numerics are
identical either way (parity-tested in interpret mode).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adam8_leaf", "leaf_supported"]

# mirror of optimizers.py log-codebook constants (single source would be
# a circular import; the parity test locks them together)
_V_OCTAVES = 24.0
_V_LOG_STEP = _V_OCTAVES / 254.0


def leaf_supported(shape, dtype) -> bool:
    """Kernel eligibility for one master leaf: >=1D, fp32 master, last
    dim lane-aligned, and rows either sublane-tileable (x8) or small
    enough to ride as one whole-array block."""
    if len(shape) == 0 or dtype != jnp.float32:
        return False
    r = shape[-1]
    if r % 128 != 0:
        return False
    rows = 1
    for d in shape[:-1]:
        rows *= d
    # Mosaic wants row blocks %8 or == full array; non-tileable rows ride
    # as ONE whole-array block, whose in-kernel residency is ~18 B/element
    # across the 13 row-shaped operands plus fp32 temporaries — bound the
    # element count so that stays ~1 MB, far under the 16 MB scoped VMEM
    return rows % 8 == 0 or rows * r <= (1 << 16)


def _kernel(sc_ref, g_ref, mq_ref, ms_ref, vq_ref, vs_ref, p_ref,
            po_ref, pb_ref, mqo_ref, mso_ref, vqo_ref, vso_ref, *,
            b1: float, b2: float, eps: float, wd: float, adam_w: bool,
            bias_correction: bool):
    # sc_ref (SMEM): [4] = lr, gscale, c1, c2 (bias corrections computed
    # on host-side trace: step is a traced scalar there)
    lr = sc_ref[0]
    gscale = sc_ref[1]
    c1 = sc_ref[2]
    c2 = sc_ref[3]

    g = g_ref[:].astype(jnp.float32) * gscale
    p = p_ref[:]
    if not adam_w and wd:
        g = g + wd * p

    # decode moments (per-row scales broadcast over the 128-lane tiles).
    # Mosaic has no uint8<->f32 cast: read the v codes through an int8
    # bitcast (two's-complement: code c > 127 arrives as c - 256)
    m = mq_ref[:].astype(jnp.float32) * ms_ref[:]
    vq_i8 = jax.lax.bitcast_convert_type(vq_ref[:], jnp.int8)
    qf = vq_i8.astype(jnp.float32)
    qf = jnp.where(qf < 0, qf + 256.0, qf)
    v = jnp.where(qf == 0, 0.0,
                  vs_ref[:] * jnp.exp2((qf - 255.0) * _V_LOG_STEP))

    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * (g * g)
    if bias_correction:
        upd = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    else:
        upd = m_new / (jnp.sqrt(v_new) + eps)
    if adam_w and wd:
        upd = upd + wd * p
    p_new = p - lr * upd
    po_ref[:] = p_new
    pb_ref[:] = p_new.astype(pb_ref.dtype)

    # requantize m: signed linear absmax per row
    m_amax = jnp.max(jnp.abs(m_new), axis=-1, keepdims=True)
    m_scale = jnp.where(m_amax > 0, m_amax / 127.0, 1.0)
    mqo_ref[:] = jnp.round(m_new / m_scale).astype(jnp.int8)
    mso_ref[:] = m_scale

    # requantize v: log-map uint8 per row (optimizers._q8_log); the
    # uint8 store goes through the inverse int8 bitcast
    v_amax = jnp.max(v_new, axis=-1, keepdims=True)
    r = v_new / jnp.where(v_amax > 0, v_amax, 1.0)
    code = jnp.where(
        r > 0,
        jnp.clip(jnp.round(255.0 + jnp.log2(jnp.maximum(r, 2.0 ** -30))
                           / _V_LOG_STEP), 1.0, 255.0),
        0.0)
    code_i8 = jnp.where(code > 127.0, code - 256.0, code).astype(jnp.int8)
    vqo_ref[:] = jax.lax.bitcast_convert_type(code_i8, jnp.uint8)
    vso_ref[:] = v_amax


def _pick_block_rows(rows: int, r: int) -> int:
    """Rows per tile: ~2 MB of fp32 working set; blocks must be
    sublane-tileable (x8, preferring the x32 int8 packing) or the whole
    array (Mosaic's block-shape rule)."""
    if rows % 8 != 0:
        return rows  # single whole-array block (leaf_supported bounds it)
    # ~16 B/element of tile residency across the 11 operands plus fp32
    # intermediates, double-buffered by the pipeline: 256k elements/tile
    # stays under the 16 MB scoped-vmem limit (128k and 256k measured
    # within 5% of each other — the kernel is compute-bound)
    target = max(1, (1 << 18) // max(r, 1))
    bm = 32 if rows % 32 == 0 else 8
    while bm * 2 <= target and rows % (bm * 2) == 0 and bm < 512:
        bm *= 2
    return min(bm, rows)


@functools.partial(jax.jit, static_argnames=(
    "b1", "b2", "eps", "wd", "adam_w", "bias_correction", "out_dtype",
    "interpret"))
def fused_adam8_leaf(g, m_q, m_s, v_q, v_s, p, lr, gscale, c1, c2, *,
                     b1: float, b2: float, eps: float, wd: float,
                     adam_w: bool, bias_correction: bool,
                     out_dtype=jnp.bfloat16,
                     interpret: bool = False) -> Tuple[jax.Array, ...]:
    """One leaf's fused 8-bit-Adam step.

    Returns (p_new_f32, p_new_cast, m_q', m_s', v_q', v_s').  `gscale`
    folds the engine's grad unscale (1/(loss_scale*gas)) and clip factor
    into the kernel so the pre-scaled grads never materialize.
    """
    shape = p.shape
    r = shape[-1]
    rows = max(1, p.size // r)
    g2 = g.reshape(rows, r)
    p2 = p.reshape(rows, r)
    mq2 = m_q.reshape(rows, r)
    vq2 = v_q.reshape(rows, r)
    ms2 = m_s.reshape(rows, 1)
    vs2 = v_s.reshape(rows, 1)

    bm = _pick_block_rows(rows, r)
    grid = (rows // bm,)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(gscale, jnp.float32),
                         jnp.asarray(c1, jnp.float32),
                         jnp.asarray(c2, jnp.float32)])

    # index maps receive the scalar-prefetch ref as a trailing arg
    row_spec = pl.BlockSpec((bm, r), lambda i, sc: (i, 0),
                            memory_space=pltpu.VMEM)
    scale_spec = pl.BlockSpec((bm, 1), lambda i, sc: (i, 0),
                              memory_space=pltpu.VMEM)
    kernel = functools.partial(
        _kernel, b1=b1, b2=b2, eps=eps, wd=wd, adam_w=adam_w,
        bias_correction=bias_correction)
    outs = pl.pallas_call(
        kernel,
        name="fused_adam8",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[row_spec, row_spec, scale_spec, row_spec, scale_spec,
                      row_spec],
            out_specs=[row_spec, row_spec, row_spec, scale_spec, row_spec,
                       scale_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, r), jnp.float32),
            jax.ShapeDtypeStruct((rows, r), out_dtype),
            jax.ShapeDtypeStruct((rows, r), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, r), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(scalars, g2, mq2, ms2, vq2, vs2, p2)
    p_new, p_cast, mq, ms, vq, vs = outs
    from ..runtime.optimizers import _scale_shape
    return (p_new.reshape(shape), p_cast.reshape(shape),
            mq.reshape(shape), ms.reshape(_scale_shape(p)),
            vq.reshape(shape), vs.reshape(_scale_shape(p)))
