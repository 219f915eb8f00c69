"""Plain float32 reference of SmallThinker's layer, its seeded weights and
its counts (PowerInfer/SmallThinker-21BA3B-Instruct `config.json`).

Straight `jax.numpy`, float32, `highest` matmul precision, masked causal
attention from every key (a block of queries at a time, so that 13k
positions fit), no cache, no kernel, no batching, experts one after another
over every token, one layer's weights at a time; it imports nothing of the
program.

The layer `l` (sizes from the configuration file: H hidden, NH query heads
and NKV key/value heads of D, E experts of width F, k a token, window W,
`rope_layout[l]` and `sliding_window_layout[l]` in {0, 1}), input x [T, H]:

    r  = x W_router                     float32 [T, E]: the layer's INPUT,
                                        before the input norm and attention
    h  = rms(x, g_in);  q = h W_q [T, NH, D];  k = h W_k, v = h W_v [T, NKV, D]
    if rope_layout[l]: rotate q and k over the whole head width, pairs
                       (i, i + D/2), frequency theta^(-2i/D); else nothing
    a  = softmax(q k^T / sqrt(D) + mask) v     NH / NKV query heads a kv head;
         mask: key j <= i, and j > i - W where sliding_window_layout[l]
    x1 = x + a W_o
    h2 = rms(x1, g_post);  (s, e) = top_k(r);  p = softmax(s) over the k picked
    y  = sum_j p_j W_down[e_j] (relu(W_gate[e_j] h2) * (W_up[e_j] h2))
    out = x1 + y

and after the last layer rms(., g_final) and the untied head.  No biases, no
dense FFN, no shared expert.

What `config.json` does not say (the configuration file's `assumed` lists
each): the experts' gate is ReLU ("sparse ReGLU"); no projection has a
bias; RoPE rotates the pairs (i, i + D/2); the router reads the layer's
input ("router placed before attention") in float32;
`moe_primary_router_apply_softmax` puts the softmax over the k picked
logits, after which `norm_topk_prob` changes nothing.  Departures: seeded
random weights at the spreads `seeded_weights` states (unit-RMS residual
stream, attention logits that spread by `qk_logit_std`, router logits that
spread by `router_logit_std` per unit RMS of the stream); the
configuration's cut of layers.

`precision` selects a control, which has to come out NOT correct: "int8"
(every weight matmul's operands on an 8-bit grid), "window_as_full" (the
window layers attend to every key), "rope_on_global" (the global layers
rotate too), "router_after_attention" (the router reads h2).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.transformer import HI, _mm

BYTES = {"bfloat16": 2, "float32": 4}
CONTROLS = ("int8", "window_as_full", "rope_on_global",
            "router_after_attention")
# queries a block of the reference's attention takes
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_ffn: int
    experts: int
    top_k: int
    window: int
    rope_layout: Tuple[int, ...]
    window_layout: Tuple[int, ...]
    eps: float
    rope_theta: float
    vocab: int
    router_logit_std: float
    qk_logit_std: float
    # the dense block's names for what a shared check reads
    norm, act, pos, tied, qkv_bias = "rms", "reglu", "rope", False, False

    @property
    def global_layers(self) -> int:
        return self.layers - sum(self.window_layout)

    @property
    def ffn(self) -> int:
        """The model's FFN width: its experts' (there is no dense FFN)."""
        return self.expert_ffn


def sizes(cfg: dict) -> Sizes:
    """The configuration file's published keys -> Sizes."""
    w = cfg["seeded_weights"]
    L = cfg["num_hidden_layers"]
    assert len(cfg["rope_layout"]) == len(cfg["sliding_window_layout"]) == L
    return Sizes(
        layers=L, hidden=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], expert_ffn=cfg["moe_ffn_hidden_size"],
        experts=cfg["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        window=cfg["sliding_window_size"],
        rope_layout=tuple(cfg["rope_layout"]),
        window_layout=tuple(cfg["sliding_window_layout"]),
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        vocab=cfg["vocab_size"],
        router_logit_std=w["router_logit_std"],
        qk_logit_std=w["qk_logit_std"])


# ----------------------------------------------------------------------
# seeded weights, in the program's layout: attention, norms and router per
# layer; the experts apart.  `make_params` stacks layers on a leading axis
# ----------------------------------------------------------------------
Leaves = List[Tuple[str, Tuple[int, ...], str]]


def layer_leaves(s: Sizes) -> Leaves:
    H, NH, NKV, D = s.hidden, s.heads, s.kv_heads, s.head_dim
    return [
        ("attn_norm_scale", (H,), "scale"), ("mlp_norm_scale", (H,), "scale"),
        ("wq", (H, NH * D), "qk"), ("wk", (H, NKV * D), "qk"),
        ("wv", (H, NKV * D), "in"), ("wo", (NH * D, H), "out"),
        ("moe_gate", (H, s.experts), "router")]


def expert_leaves(s: Sizes) -> Leaves:
    """One expert."""
    return [("w_gate_proj", (s.hidden, s.expert_ffn), "in"),
            ("w_up", (s.hidden, s.expert_ffn), "in"),
            ("w_down", (s.expert_ffn, s.hidden), "down")]


def top_leaves(s: Sizes) -> Leaves:
    return [("tok_embed", (s.vocab, s.hidden), "embed"),
            ("final_norm_scale", (s.hidden,), "scale"),
            ("lm_head", (s.hidden, s.vocab), "in")]


def seed_key(seed) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(20260930),
                              jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    """The driver's seeds pass 2**31: fold into the 32 bits a key takes."""
    return np.uint32(int(seed) % (1 << 32))


def _leaf(key, slot: int, layer, shape, kind: str, s: Sizes, dtype):
    """The residual stream has about unit RMS (embeddings of unit
    variance, every branch adding a fraction of that), so a matmul of a
    normed input by an "in" leaf gives unit variance; "qk" leaves give q
    and k the variance that spreads q.k / sqrt(D) by `qk_logit_std`; the
    router's logits spread by `router_logit_std` per unit RMS of the
    (un-normed) stream."""
    fan_in = shape[0]
    mean, std = {
        "embed": (0.0, 1.0), "scale": (1.0, 0.1),
        "in": (0.0, 1.0 / math.sqrt(fan_in)),
        "qk": (0.0, math.sqrt(s.qk_logit_std / fan_in)),
        "out": (0.0, 0.5 / math.sqrt(fan_in)),
        "down": (0.0, 1.0 / math.sqrt(fan_in)),
        "router": (0.0, s.router_logit_std / math.sqrt(fan_in)),
    }[kind]
    k = jax.random.fold_in(jax.random.fold_in(key, slot), layer)
    return (mean + std * jax.random.normal(k, shape, jnp.float32)
            ).astype(dtype)


def layer_params(key, layer, s: Sizes, dtype) -> dict:
    """One layer (`layer` may be traced): its leaves and, under
    "experts", its experts' stacks."""
    leaf = lambda slot, shape, kind: _leaf(  # noqa: E731
        key, slot, layer, shape, kind, s, dtype)
    out = {n: leaf(i, shape, kind)
           for i, (n, shape, kind) in enumerate(layer_leaves(s))}
    out["experts"] = {
        n: jnp.stack([leaf(100 * (e + 1) + i, shape, kind)
                      for e in range(s.experts)])
        for i, (n, shape, kind) in enumerate(expert_leaves(s))}
    return out


def top_param(key, name: str, s: Sizes, dtype) -> jax.Array:
    for i, (n, shape, kind) in enumerate(top_leaves(s)):
        if n == name:
            return _leaf(key, 90 + i, 0, shape, kind, s, dtype)
    raise KeyError(name)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _make_params(seed, *, s: Sizes, dtype):
    key = seed_key(seed)
    params = {n: top_param(key, n, s, dtype) for n, _, _ in top_leaves(s)}
    params["layers"] = jax.lax.map(
        lambda l: layer_params(key, l, s, dtype),
        jnp.arange(s.layers, dtype=jnp.uint32))
    params["experts"] = params["layers"].pop("experts")   # outside the scan
    return params


def make_params(seed: int, s: Sizes, dtype):
    """The whole seeded tree in the program's layout, on the device, in
    one jitted call."""
    return _make_params(seed_arg(seed), s=s, dtype=dtype)


# ----------------------------------------------------------------------
# the layer
# ----------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta: float):
    """x [B, S, N, D]: rotate the pairs (i, i + D/2) by position *
    theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv  # [B,S,1,D/2]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def attention(q, k, v, window: int):
    """Masked causal attention, a block of queries against every key.
    q [B, S, NH, D]; k, v [B, S, NKV, D]; `window` 0: none."""
    B, S, NH, D = q.shape
    k = jnp.repeat(k, NH // k.shape[2], axis=2)
    v = jnp.repeat(v, NH // v.shape[2], axis=2)
    qb = min(QUERY_BLOCK, S)
    pad = -S % qb
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    key_pos = jnp.arange(S)[None, :]

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=1)
        q_pos = (i * qb + jnp.arange(qb))[:, None]
        seen = key_pos <= q_pos
        if window:
            seen &= key_pos > q_pos - window
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HI) \
            / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange((S + pad) // qb))    # [n, B, qb, ..]
    return jnp.moveaxis(out, 0, 1).reshape(B, S + pad, NH, D)[:, :S]


def moe(h2, r_in, lp, s: Sizes, mm):
    """y [B, S, H]: the k experts the router's logits on `r_in` pick,
    weighted by the softmax over the picked logits."""
    r = jnp.matmul(r_in, lp["moe_gate"], precision=HI)          # [B, S, E]
    top, picks = jax.lax.top_k(r, s.top_k)
    p = jax.nn.softmax(top, axis=-1)
    # weight of every expert at every token (0 where not picked)
    dense_w = jnp.sum(jax.nn.one_hot(picks, s.experts) * p[..., None],
                      axis=-2)

    def expert(args):
        wg, wu, wd, we = args
        act = jax.nn.relu(mm(h2, wg)) * mm(h2, wu)
        return we[..., None] * mm(act, wd)

    ex = lp["experts"]
    return jnp.sum(jax.lax.map(expert, (
        ex["w_gate_proj"], ex["w_up"], ex["w_down"],
        jnp.moveaxis(dense_w, -1, 0))), axis=0)


def block(x, lp, positions, s: Sizes, rotate: bool, window: int,
          precision=None, broken=()):
    """One layer.  x [B, S, H] float32; lp: its leaves (`layer_params`),
    float32; `rotate`, `window`: the layer's kind.  `broken`: names of
    departures (the controls')."""
    mm = functools.partial(_mm, precision=precision)
    B, S, H = x.shape
    NH, NKV, D = s.heads, s.kv_heads, s.head_dim
    h = _rms(x, lp["attn_norm_scale"], s.eps)
    q = mm(h, lp["wq"]).reshape(B, S, NH, D)
    k = mm(h, lp["wk"]).reshape(B, S, NKV, D)
    v = mm(h, lp["wv"]).reshape(B, S, NKV, D)
    if rotate or "rope_on_global" in broken:
        q = _rope(q, positions, s.rope_theta)
        k = _rope(k, positions, s.rope_theta)
    a = attention(q, k, v, 0 if "window_as_full" in broken else window)
    x1 = x + mm(a.reshape(B, S, NH * D), lp["wo"])
    h2 = _rms(x1, lp["mlp_norm_scale"], s.eps)
    r_in = h2 if "router_after_attention" in broken else x
    return x1 + moe(h2, r_in, lp, s, mm)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _how(precision):
    """A control's name -> (matmul precision, broken flags)."""
    if precision is None or precision == "int8":
        return precision, ()
    if precision in CONTROLS:
        return None, (precision,)
    raise ValueError(f"unknown control {precision!r} (have {CONTROLS})")


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _embed_call(seed, tokens, *, s, dtype):
    return jnp.take(_f32(top_param(seed_key(seed), "tok_embed", s, dtype)),
                    tokens, 0)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision",
                                             "rotate", "window"),
                   donate_argnums=(2,))
def _layer_call(seed, layer, x, *, s, dtype, precision, rotate, window):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lp = _f32(layer_params(seed_key(seed), layer, s, dtype))
    mm_precision, broken = _how(precision)
    return block(x, lp, pos, s, rotate, window, mm_precision, broken)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _final_call(seed, x, *, s, dtype):
    return _rms(x, _f32(top_param(seed_key(seed), "final_norm_scale", s,
                                  dtype)), s.eps)


def hidden_states(seed, tokens: np.ndarray, s: Sizes, dtype,
                  precision=None):
    """Final-normed hidden states [B, S, H] of padded token rows (padding
    at the end: causal attention keeps it out of every real position).
    One layer's weights are made, widened and dropped at a time."""
    seed = seed_arg(seed)
    x = _embed_call(seed, jnp.asarray(tokens), s=s, dtype=dtype)
    for layer in range(s.layers):
        x = _layer_call(
            seed, np.uint32(layer), x, s=s, dtype=dtype, precision=precision,
            rotate=bool(s.rope_layout[layer]),
            window=s.window * int(s.window_layout[layer]))
    return _final_call(seed, x, s=s, dtype=dtype)


def logits(seed, tokens: np.ndarray, s: Sizes, dtype, precision=None):
    """[B, S, V] logits of padded token rows (tests; small sizes)."""
    head = _f32(top_param(seed_key(seed_arg(seed)), "lm_head", s, dtype))
    return jnp.matmul(hidden_states(seed, tokens, s, dtype, precision),
                      head, precision=HI)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "int8"))
def _gap_call(seed, h_ref, h_other, chosen, valid, *, s, dtype, int8):
    """One row: per scored position, the gap by which the scored token's
    logit lies below the reference's best, in units of the reference
    logits' spread there.  With `h_other` the scored token is the one those
    hidden states put first (a control)."""
    head = _f32(top_param(seed_key(seed), "lm_head", s, dtype))
    ref = jnp.matmul(h_ref, head, precision=HI)
    if h_other is not None:
        chosen = jnp.argmax(_mm(h_other, head, "int8" if int8 else None), -1)
    at = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    gap = (jnp.max(ref, -1) - at) / jnp.std(ref, axis=-1)
    return jnp.where(valid, gap, 0.0)


def served_token_gaps(seed, served, s: Sizes, dtype, precision=None):
    """`served`: (prompt, tokens) int arrays of finished greedy requests.
    The reference runs once over each prompt with its served tokens, a
    row at a time.  Per request: the gap of each served token (precision
    None), or of the token a control puts first at the same positions."""
    out = []
    # (a width is a compile of the layer: long rows are padded to few)
    width = max(len(p) + len(t) - 1 for p, t in served)
    step = 256 if width <= 2048 else 2048
    width = -(-width // step) * step
    n_max = max(len(t) for _, t in served)
    for p, t in served:
        row = np.zeros((1, width), np.int32)
        seq = np.concatenate([p, t[:-1]])
        row[0, :len(seq)] = seq
        at = np.zeros(n_max, np.int32)
        at[:len(t)] = np.arange(len(p) - 1, len(p) - 1 + len(t))
        h_ref = hidden_states(seed, row, s, dtype)[0][at]
        h_low = (hidden_states(seed, row, s, dtype, precision)[0][at]
                 if precision else None)
        chosen = np.zeros(n_max, np.int32)
        chosen[:len(t)] = t
        gaps = _gap_call(
            seed_arg(seed), h_ref, h_low, jnp.asarray(chosen),
            jnp.asarray(np.arange(n_max) < len(t)), s=s, dtype=dtype,
            int8=precision == "int8")
        out.append(np.asarray(gaps)[:len(t)])
    return out


# What the traffic file's `greedy_gap_limit` holds for this family is a
# SHARE: of the scored positions, those at which the served token is not
# the reference's best, in %.  The widest gap (what the dense family
# compares) is one position's, and here it tells nothing: six of 64 experts
# are picked by float32 logits whose sixth and seventh are often a rounding
# apart, and where bfloat16 picks the other expert that one position's
# logits move, so a sound row has read a widest gap of 1.9 where the
# reference on 8-bit grids read 0.8.  Near ties flip in proportion to the
# noise in the logits at EVERY position, so a uniform loss of precision
# shows in the share (8-bit grids double it) and a wrong mask, rope flag or
# router input multiplies it (PERF.md section 6 has the readings the limit
# lies between).  `served_token_gaps` keeps the gaps for a closer look.
def served_token_gap(seed, served, s: Sizes, dtype, precision=None):
    """(the share, in %, of `served_token_gaps` that are not zero: the
    positions whose served token is not the reference's best; tokens
    scored)."""
    gaps = np.concatenate(served_token_gaps(seed, served, s, dtype, precision))
    return 100.0 * float(np.mean(gaps > 0)), len(gaps)


# ----------------------------------------------------------------------
# counts: what the mathematics needs once, from shapes alone
# ----------------------------------------------------------------------
def _count(leaves: Leaves) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaves)


def weight_bytes(s: Sizes, dtype: str) -> int:
    return (s.layers * (_count(layer_leaves(s))
                        + s.experts * _count(expert_leaves(s)))
            + _count(top_leaves(s))) * BYTES[dtype]


def experts_with_a_row(s: Sizes, rows: float) -> float:
    """How many of a layer's experts a step of `rows` tokens is expected to
    reach under even routing (a token picks a given expert with
    probability top_k / experts): the others' weights need not be read.
    64 experts at 32 rows: 61.3."""
    return s.experts * (1.0 - (1.0 - s.top_k / s.experts) ** rows)


def kv_bytes_per_token_layer(s: Sizes, dtype: str) -> int:
    """A cached token's key and value in one layer."""
    return 2 * s.kv_heads * s.head_dim * BYTES[dtype]


def keys_read(s: Sizes, rows: float, context_tokens: float) -> float:
    """(layer, key) pairs a decode step's attention must read: every live
    key on the global layers, the window's on the window layers (the mean
    live context a row, cut at the window: a row shorter than the window
    has fewer, which the cell's rows never are)."""
    per_row = context_tokens / max(rows, 1e-9) + 1
    return rows * (s.global_layers * per_row
                   + (s.layers - s.global_layers) * min(per_row, s.window))


def paged_decode_bytes(s: Sizes, dtype: str, rows: float,
                       context_tokens: float) -> float:
    """The paged decode kernel's own bytes over a step (all layers): the
    keys and values its rows can see, read once; the queries read and the
    outputs written."""
    w = BYTES[dtype]
    return (keys_read(s, rows, context_tokens)
            * kv_bytes_per_token_layer(s, dtype)
            + s.layers * rows * 2 * s.heads * s.head_dim * w)


def paged_decode_flops(s: Sizes, rows: float, context_tokens: float) -> float:
    """Its multiply-adds, twice: per visible key, every query head's score
    and weighted sum over the head's width."""
    return keys_read(s, rows, context_tokens) * 2 * 2 * s.heads * s.head_dim


def prefill_flops(s: Sizes, prompt_tokens: int) -> float:
    """Multiply-adds, twice, that the forward pass of ONE fresh prompt
    needs before its first token: every token through a layer's attention
    projections and router and through its `top_k` experts; every (query,
    key) pair the mask lets through (key <= query, on a window layer also
    key > query - window), every query head's score and weighted sum over
    the head's width; the head on the last position only."""
    n = prompt_tokens
    matmuls = _count([leaf for leaf in layer_leaves(s) if len(leaf[1]) == 2]) \
        + s.top_k * _count(expert_leaves(s))
    w = min(n, s.window)
    pairs = s.global_layers * n * (n + 1) / 2 \
        + (s.layers - s.global_layers) * (w * (w + 1) / 2 + (n - w) * w)
    return 2.0 * (s.layers * n * matmuls
                  + pairs * 2 * s.heads * s.head_dim + s.hidden * s.vocab)


def decode_step_bytes(s: Sizes, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """HBM bytes one decode step must move: attention's weights, the norms,
    the router and the head once (of the embedding only the rows looked
    up), of the experts those expected to have a row
    (`experts_with_a_row`), the keys and values the rows can see
    (`keys_read`), the new ones written, float32 logits written."""
    w = BYTES[dtype]
    weights = (s.layers * (_count(layer_leaves(s))
                           + experts_with_a_row(s, rows)
                           * _count(expert_leaves(s)))
               + s.vocab * s.hidden + s.hidden) * w
    kv = (keys_read(s, rows, context_tokens) + s.layers * rows) \
        * kv_bytes_per_token_layer(s, dtype)
    return weights + rows * s.hidden * w + kv + rows * s.vocab * 4
