"""Block-sparse attention — the TPU answer to DeepSpeed Sparse Attention.

Reference surface (re-designed, not translated):
- `deepspeed/ops/sparse_attention/sparsity_config.py` — the layout family
  (Dense :63, Fixed :95, Variable :239, BigBird :411, BSLongformer :546,
  LocalSlidingWindow) producing a per-head block mask.
- `deepspeed/ops/sparse_attention/{matmul,softmax}.py` + csrc Triton
  kernels — block-sparse SDD/DSD matmuls and masked softmax.
- `sparse_self_attention.py` `SparseSelfAttention` — the user module.

TPU-first mechanics: layouts are *static* (shape-only functions of the
config), so the active k-blocks of every (head, q-block) are known at trace
time.  We precompute a padded gather index `kb_idx[h, qb, A]` (A = max
active blocks across rows) and compute attention only over gathered blocks:
FLOPs and memory scale with A/nkb, the true block sparsity, while every
matmul stays a dense MXU-shaped [block, A*block] tile — the same design
point as splash attention in JAX (PAPERS.md), where the sparsity lives in a
static gather, not in dynamic control flow XLA cannot tile.
"""
from __future__ import annotations

import math
import random
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SparsityConfig",
    "DenseSparsityConfig",
    "FixedSparsityConfig",
    "VariableSparsityConfig",
    "BigBirdSparsityConfig",
    "BSLongformerSparsityConfig",
    "LocalSlidingWindowSparsityConfig",
    "block_sparse_attention",
    "SparseSelfAttention",
]


# ----------------------------------------------------------------------
# sparsity configs -> block layouts
# ----------------------------------------------------------------------
class SparsityConfig:
    """Base: produces a [num_heads, nb, nb] bool block layout for a seq_len.

    `different_layout_per_head=False` collapses all heads to head-0's
    layout (reference: check_and_propagate_first_head_layout :48)."""

    def __init__(self, num_heads: int, block: int = 16,
                 different_layout_per_head: bool = False):
        self.num_heads = num_heads
        self.block = block
        self.different_layout_per_head = different_layout_per_head

    def num_blocks(self, seq_len: int) -> int:
        if seq_len % self.block != 0:
            raise ValueError(
                f"seq_len {seq_len} must be a multiple of block {self.block}")
        return seq_len // self.block

    def make_layout(self, seq_len: int) -> np.ndarray:
        raise NotImplementedError

    def _finalize(self, layout: np.ndarray) -> np.ndarray:
        if not self.different_layout_per_head:
            layout[1:] = layout[0]
        return layout


class DenseSparsityConfig(SparsityConfig):
    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self.num_blocks(seq_len)
        return np.ones((self.num_heads, nb, nb), bool)


class FixedSparsityConfig(SparsityConfig):
    """Local windows of `num_local_blocks`, plus `num_global_blocks` global
    block-columns taken from the tail of each window; heads may rotate among
    `num_different_global_patterns` choices (reference: Fixed :95)."""

    def __init__(self, num_heads, block=16, different_layout_per_head=False,
                 num_local_blocks=4, num_global_blocks=1,
                 attention="bidirectional", horizontal_global_attention=False,
                 num_different_global_patterns=1):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_local_blocks = num_local_blocks
        self.num_global_blocks = num_global_blocks
        if attention not in ("unidirectional", "bidirectional"):
            raise ValueError(f"invalid attention mode {attention!r}")
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention
        if num_different_global_patterns > 1 and not different_layout_per_head:
            raise ValueError(
                "num_different_global_patterns > 1 requires "
                "different_layout_per_head=True")
        self.num_different_global_patterns = num_different_global_patterns

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self.num_blocks(seq_len)
        L = np.zeros((self.num_heads, nb, nb), bool)
        w = self.num_local_blocks
        for h in range(self.num_heads):
            # local windows
            for start in range(0, nb, w):
                end = min(start + w, nb)
                for q in range(start, end):
                    hi = (q + 1) if self.attention == "unidirectional" else end
                    L[h, q, start:hi] = True
            # global columns: pattern-rotated tail blocks of each window
            pat = h % self.num_different_global_patterns
            first = w - (1 + pat) * self.num_global_blocks
            for start in range(0, nb, w):
                g0 = start + max(first, 0)
                for g in range(g0, min(g0 + self.num_global_blocks, nb)):
                    L[h, :, g] = True       # every query block attends to g
                    if self.horizontal_global_attention:
                        L[h, g, :] = True   # g attends everywhere
        if self.attention == "unidirectional":
            tri = np.tril(np.ones((nb, nb), bool))
            L &= tri[None]
        return self._finalize(L)


class VariableSparsityConfig(SparsityConfig):
    """Custom local window sizes + explicit global block indices + random
    blocks (reference: Variable :239)."""

    def __init__(self, num_heads, block=16, different_layout_per_head=False,
                 num_random_blocks=0, local_window_blocks: Optional[List[int]] = None,
                 global_block_indices: Optional[Sequence[int]] = None,
                 global_block_end_indices: Optional[Sequence[int]] = None,
                 attention="bidirectional", horizontal_global_attention=False):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_random_blocks = num_random_blocks
        self.local_window_blocks = local_window_blocks or [4]
        self.global_block_indices = list(global_block_indices or [0])
        self.global_block_end_indices = (
            list(global_block_end_indices) if global_block_end_indices else None)
        self.attention = attention
        self.horizontal_global_attention = horizontal_global_attention

    def _global_cols(self, nb: int) -> List[int]:
        cols: List[int] = []
        if self.global_block_end_indices is None:
            cols = [i for i in self.global_block_indices if i < nb]
        else:
            for s, e in zip(self.global_block_indices,
                            self.global_block_end_indices):
                cols.extend(range(s, min(e, nb)))
        return cols

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self.num_blocks(seq_len)
        L = np.zeros((self.num_heads, nb, nb), bool)
        rng = random.Random(0)
        for h in range(self.num_heads):
            # variable-width local windows, then the last width repeats
            q = 0
            widths = list(self.local_window_blocks)
            widths += [widths[-1]] * nb
            for w in widths:
                if q >= nb:
                    break
                end = min(q + w, nb)
                for i in range(q, end):
                    hi = (i + 1) if self.attention == "unidirectional" else end
                    L[h, i, q:hi] = True
                q = end
            for g in self._global_cols(nb):
                L[h, :, g] = True
                if self.horizontal_global_attention:
                    L[h, g, :] = True
            for i in range(nb):
                for _ in range(self.num_random_blocks):
                    L[h, i, rng.randrange(nb)] = True
        if self.attention == "unidirectional":
            L &= np.tril(np.ones((nb, nb), bool))[None]
        return self._finalize(L)


class BigBirdSparsityConfig(SparsityConfig):
    """random + sliding-window + global (ITC) blocks (reference: :411)."""

    def __init__(self, num_heads, block=16, different_layout_per_head=False,
                 num_random_blocks=1, num_sliding_window_blocks=3,
                 num_global_blocks=1, attention="bidirectional"):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_random_blocks = num_random_blocks
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.num_global_blocks = num_global_blocks
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self.num_blocks(seq_len)
        L = np.zeros((self.num_heads, nb, nb), bool)
        rng = random.Random(0)
        half = self.num_sliding_window_blocks // 2
        g = min(self.num_global_blocks, nb)
        for h in range(self.num_heads):
            for i in range(nb):
                L[h, i, max(0, i - half):min(nb, i + half + 1)] = True
                for _ in range(self.num_random_blocks):
                    L[h, i, rng.randrange(nb)] = True
            L[h, :, :g] = True      # global columns (ITC)
            L[h, :g, :] = True      # global rows
        if self.attention == "unidirectional":
            L &= np.tril(np.ones((nb, nb), bool))[None]
        return self._finalize(L)


class BSLongformerSparsityConfig(SparsityConfig):
    """Block-sparse Longformer: sliding window + leading global blocks
    (reference: :546)."""

    def __init__(self, num_heads, block=16, different_layout_per_head=False,
                 num_sliding_window_blocks=3,
                 global_block_indices: Optional[Sequence[int]] = None,
                 global_block_end_indices: Optional[Sequence[int]] = None,
                 attention="bidirectional"):
        super().__init__(num_heads, block, different_layout_per_head)
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.global_block_indices = list(global_block_indices or [0])
        self.global_block_end_indices = (
            list(global_block_end_indices) if global_block_end_indices else None)
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self.num_blocks(seq_len)
        L = np.zeros((self.num_heads, nb, nb), bool)
        half = self.num_sliding_window_blocks // 2
        if self.global_block_end_indices is None:
            cols = [i for i in self.global_block_indices if i < nb]
        else:
            cols = []
            for s, e in zip(self.global_block_indices,
                            self.global_block_end_indices):
                cols.extend(range(s, min(e, nb)))
        for h in range(self.num_heads):
            for i in range(nb):
                L[h, i, max(0, i - half):min(nb, i + half + 1)] = True
            for c in cols:
                L[h, :, c] = True
                L[h, c, :] = True
        if self.attention == "unidirectional":
            L &= np.tril(np.ones((nb, nb), bool))[None]
        return self._finalize(L)


class LocalSlidingWindowSparsityConfig(SparsityConfig):
    """Pure sliding-window layout (reference: local_sliding_window class)."""

    def __init__(self, num_heads, block=16, num_sliding_window_blocks=3,
                 attention="unidirectional"):
        super().__init__(num_heads, block, different_layout_per_head=False)
        self.num_sliding_window_blocks = num_sliding_window_blocks
        self.attention = attention

    def make_layout(self, seq_len: int) -> np.ndarray:
        nb = self.num_blocks(seq_len)
        L = np.zeros((self.num_heads, nb, nb), bool)
        w = self.num_sliding_window_blocks
        for i in range(nb):
            if self.attention == "unidirectional":
                L[:, i, max(0, i - w + 1):i + 1] = True
            else:
                half = w // 2
                L[:, i, max(0, i - half):min(nb, i + half + 1)] = True
        return self._finalize(L)


# ----------------------------------------------------------------------
# the kernel: static-gather block-sparse attention
# ----------------------------------------------------------------------
def _layout_to_gather(layout: np.ndarray):
    """[H, nqb, nkb] bool -> (kb_idx [H, nqb, A] int32 padded with -1)."""
    H, nqb, nkb = layout.shape
    max_a = int(layout.sum(-1).max())
    if max_a == 0:
        raise ValueError("sparsity layout has an all-zero row")
    idx = np.full((H, nqb, max_a), -1, np.int32)
    for h in range(H):
        for q in range(nqb):
            cols = np.nonzero(layout[h, q])[0]
            idx[h, q, :len(cols)] = cols
    return idx


def _use_sparse_kernel(impl: str, block: int, D: int) -> bool:
    """Gate the fused Pallas block-sparse kernel (splash-attention analog).
    "auto" uses it wherever capable on TPU — it never materializes the
    [B, H, nqb, A, block, D] gathered copy the jnp path builds, so it is
    the memory-safe default; "pallas" forces (raising if incapable),
    "jnp" disables."""
    capable = block % 8 == 0 and D % 64 == 0
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
                f"impl='pallas' requested but the block-sparse kernel "
                f"cannot run here (needs TPU, block % 8 == 0 [got {block}],"
                f" head_dim % 64 == 0 [got {D}]) — a silent dense fallback "
                f"would benchmark/debug the wrong implementation")
        return True
    return capable


def block_sparse_attention(q, k, v, layout: np.ndarray, block: int,
                           causal: bool = True, scale: Optional[float] = None,
                           impl: str = "auto"):
    """q,k,v: [B, S, H, D]; layout: [H, S/block, S/block] bool (static).

    Compute/memory scale with the layout's max row population A, not with
    S/block: per (head, q-block) only its A active k/v blocks are visited.
    On TPU the visitation runs as a Pallas flash kernel whose K/V index
    maps read the gather table via scalar prefetch (ops/sparse_flash.py);
    elsewhere a static jnp gather computes [block, A·block] score strips.
    """
    B, S, H, D = q.shape
    nb = S // block
    if layout.shape != (H, nb, nb):
        raise ValueError(f"layout {layout.shape} != {(H, nb, nb)}")
    kb_idx = _layout_to_gather(layout)               # [H, nqb, A]
    if _use_sparse_kernel(impl, block, D):
        # custom_vjp: pallas_call has no autodiff rule, and the auto-on
        # kernel must not break training that worked on the jnp path — the
        # backward recomputes through the differentiable gather path (same
        # memory/speed users had before; a fused flash backward can slot in
        # here later)
        return _sparse_kernel_diff(q, k, v, kb_idx, layout, block, causal,
                                   scale)
    A = kb_idx.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qb = q.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    kb = k.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)
    vb = v.transpose(0, 2, 1, 3).reshape(B, H, nb, block, D)

    idx = jnp.asarray(np.maximum(kb_idx, 0))         # [H, nqb, A]
    h_ar = jnp.arange(H)[:, None, None]
    # gather active k/v blocks per (h, qb): [B, H, nqb, A, block, D]
    gk = kb[:, h_ar, idx]
    gv = vb[:, h_ar, idx]

    s = jnp.einsum("bhqid,bhqajd->bhqiaj", qb, gk,
                   preferred_element_type=jnp.float32) * scale

    # static mask [H, nqb, block(i), A, block(j)]
    qpos = np.arange(nb)[:, None] * block + np.arange(block)   # [nqb, i]
    kpos = kb_idx[..., None] * block + np.arange(block)        # [H, nqb, A, j]
    valid = (kb_idx >= 0)[:, :, None, :, None]                 # padding blocks
    if causal:
        valid = valid & (kpos[:, :, None, :, :] <=
                         qpos[None, :, :, None, None])
    mask = jnp.asarray(np.broadcast_to(
        valid, (H, nb, block, A, block)))[None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s.reshape(B, H, nb, block, A * block), axis=-1)
    # a fully-masked row (layout without the diagonal block) softmaxes to
    # NaN — define its output as 0 instead
    p = jnp.where(jnp.isnan(p), 0.0, p).reshape(s.shape)
    out = jnp.einsum("bhqiaj,bhqajd->bhqid", p.astype(q.dtype), gv)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sparse_kernel_diff(q, k, v, kb_idx, layout, block, causal, scale):
    from .sparse_flash import block_sparse_flash_attention
    return block_sparse_flash_attention(q, k, v, kb_idx, block,
                                        causal=causal, scale=scale)


def _sparse_kernel_diff_fwd(q, k, v, kb_idx, layout, block, causal, scale):
    from .sparse_flash import block_sparse_flash_attention
    out, lse = block_sparse_flash_attention(
        q, k, v, kb_idx, block, causal=causal, scale=scale,
        return_lse=True)
    return out, (q, k, v, out, lse, kb_idx.shape)


def _sparse_kernel_diff_bwd(layout, block, causal, scale, res, g):
    # fused Pallas backward (sparse_flash.py): dq walks the forward's
    # gather table, dk/dv walk its host-built inverse — no [.., A*block]
    # gathered HBM copy, matching the reference Triton backward
    # (ops/sparse_attention/matmul.py)
    q, k, v, out, lse, kb_shape = res
    from .sparse_flash import block_sparse_flash_backward, reverse_gather
    kb_idx = _layout_to_gather(np.asarray(layout))
    rev = reverse_gather(kb_idx)
    dq, dk, dv = block_sparse_flash_backward(
        q, k, v, kb_idx, rev, out, g, lse, block, causal=causal,
        scale=scale)
    # kb_idx is an int primal: its cotangent must be float0 (None happens
    # to pass on some JAX versions but is version-fragile)
    return dq, dk, dv, np.zeros(kb_shape, dtype=jax.dtypes.float0)


_sparse_kernel_diff.defvjp(_sparse_kernel_diff_fwd, _sparse_kernel_diff_bwd)


class SparseSelfAttention:
    """User module (reference: sparse_self_attention.py): holds a sparsity
    config, applies block-sparse attention to [B, S, H, D] q/k/v."""

    def __init__(self, sparsity_config: SparsityConfig,
                 causal: Optional[bool] = None):
        self.sparsity_config = sparsity_config
        if causal is None:
            # derive from the config: bidirectional layouts must not be
            # silently causal-masked (their upper-triangle blocks are the
            # point); configs without an attention mode default causal
            causal = getattr(sparsity_config, "attention",
                             "unidirectional") == "unidirectional"
        elif (not causal and getattr(sparsity_config, "attention", None)
              == "unidirectional"):
            causal = True
        self.causal = causal
        self._layouts = {}

    def layout(self, seq_len: int) -> np.ndarray:
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def __call__(self, q, k, v):
        return block_sparse_attention(
            q, k, v, self.layout(q.shape[1]), self.sparsity_config.block,
            causal=self.causal)
