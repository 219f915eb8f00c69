"""Bytes and operations an algorithm NEEDS, from shapes alone.

These are the numerators of every roofline and MFU share, kept with the
benchmark so that no later PR can change what "100%" means.  They count what
the mathematics requires once — weights read once a step, live K/V read
once, every matmul's multiply-adds, the causal half of attention — and
nothing a particular implementation adds (recomputation, padding, gathers,
casts).  Hand numbers: tests/benchmark/test_counts.py.
"""
from __future__ import annotations

from benchmark.weights import Sizes

BYTES = {"bfloat16": 2, "float32": 4}


def layer_matmul_params(s: Sizes) -> int:
    """Weights of one layer that a token multiplies through."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    mlp = (3 if s.act == "swiglu" else 2) * s.hidden * s.ffn
    return s.hidden * q + 2 * s.hidden * kv + q * s.hidden + mlp


def layer_params(s: Sizes) -> int:
    """All leaves of one layer: matrices, biases, norm scales."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    n = layer_matmul_params(s) + 2 * s.hidden          # two norm scales
    if s.qkv_bias:
        n += q + 2 * kv
    if s.dense_bias:
        n += 2 * s.hidden + s.hidden + s.ffn + s.hidden  # norm b, bo, b_up, b_down
    return n


def weight_bytes(s: Sizes, dtype: str) -> int:
    """The whole model as stored: layers, embedding, head, final norm,
    learned positions."""
    top = s.vocab * s.hidden * (1 if s.tied else 2) + s.hidden
    if s.norm == "ln":
        top += s.hidden
    if s.pos == "learned":
        top += s.max_pos * s.hidden
    return (s.layers * layer_params(s) + top) * BYTES[dtype]


def kv_bytes_per_token(s: Sizes, dtype: str) -> int:
    return 2 * s.kv_heads * s.head_dim * s.layers * BYTES[dtype]


def decode_step_bytes(s: Sizes, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """HBM bytes one decode step must move: every layer's weights and the
    head once (of the embedding table only the rows looked up), the live
    K/V of every row once, the new K/V written, float32 logits written."""
    w = BYTES[dtype]
    weights = (s.layers * layer_params(s) + s.vocab * s.hidden
               + s.hidden) * w
    embed_rows = rows * s.hidden * w
    kv = (context_tokens + rows) * kv_bytes_per_token(s, dtype)
    logits = rows * s.vocab * 4
    return weights + embed_rows + kv + logits


def train_flops_per_token(s: Sizes, seq: int) -> float:
    """Forward + backward FLOPs a token of a `seq`-long causal sequence
    requires: 6 per matmul weight (2 forward, 4 backward; the head counts,
    the embedding lookup does not) plus attention's two S x S products at
    their causal half (2*seq*q_width forward, twice that backward).
    Nothing recomputed is counted."""
    q = s.heads * s.head_dim
    matmul = s.layers * layer_matmul_params(s) + s.vocab * s.hidden
    attention = s.layers * 2 * seq * q     # QK^T and PV, causal half, forward
    return 6.0 * matmul + 3.0 * attention
