"""Plain float32 reference of LongCat-Flash's layer, its seeded weights and
its counts (meituan-longcat/LongCat-Flash-Chat `config.json`).

Straight `jax.numpy`, float32, `highest` matmul precision, full-square
causal attention from DECOMPRESSED keys and values, no cache, no kernel, no
absorbed form, experts one after another over every token; it imports
nothing of the program.

The layer (sizes from the configuration file: H hidden, NH heads, ranks
rq/rkv, head dims dn | dr and dv, dense width F, expert width Fe, E routed
and Z identity experts, top-k, scaling factor):

    a0 = x + MLA0(rms(x));  h0 = rms(a0);  m = MoE(h0);  y0 = a0 + FFN0(h0)
    a1 = y0 + MLA1(rms(y0));  y1 = a1 + FFN1(rms(a1));  out = y1 + m

four norms with their own scales; FFN(h) = W_down(silu(W_gate h) * W_up h).

MLA(n): cq = rms(W_qa n); q = s_q W_qb cq -> NH x (dn | dr), s_q =
sqrt(H/rq); [ckv | kr] = W_kva n; c = s_kv rms(ckv), s_kv = sqrt(H/rkv);
[k_nope | v] = W_kvb c -> NH x (dn | dv); RoPE on q's dr part and on the
one shared kr; score (q_nope.k_nope + q_rope.kr) / sqrt(dn + dr); causal
softmax; o = W_o concat_h(P v).

MoE(h): s = softmax(W_r h) over E + Z outputs; the k largest of s + b are
picked; w_j = scaling * s_j (not renormalised); E_j(h) is a SwiGLU of width
Fe for j < E and h itself for j >= E; MoE(h) = sum_j w_j E_j(h).

What `config.json` does not say (the configuration file's `assumed` lists
each): the two scale factors are given as booleans only; RoPE rotates the
pairs (2i, 2i+1) with frequency theta^(-2i/dr); the router works in
float32; the bias b enters the selection only; both bottleneck norms carry
learned scales.  Departures: seeded random weights; THIS CHIP'S SHARE of
the routed experts (`local_first`, `local_count`): an assignment to an
expert held elsewhere contributes nothing here (the deployment adds it on
another chip), the identity experts and everything else are whole; the
configuration's cut of layers and vocabulary.

`precision` selects a control, which has to come out NOT correct: "int8"
(every matmul operand on an 8-bit grid), "no_experts" (m dropped),
"zero_as_zero" (identity experts return 0).  `block`'s other `broken`
flags are the mistakes the tests show the comparison can see.
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
CONTROLS = ("int8", "no_experts", "zero_as_zero")


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    ffn: int
    expert_ffn: int
    experts: int          # routed experts the router scores
    zero_experts: int     # identity outputs after them
    local_first: int      # the share held here: [first, first + count)
    local_count: int
    top_k: int
    scaling: float
    eps: float
    rope_theta: float
    vocab: int
    router_logit_std: float
    router_bias_std: float
    # the dense block's names for what a shared check reads
    norm, act, pos, tied, qkv_bias = "rms", "swiglu", "rope", False, False

    @property
    def kv_heads(self) -> int:
        return self.heads

    @property
    def head_dim(self) -> int:        # nominal (hidden / heads): the head
        return self.hidden // self.heads   # widths are d_nope, d_rope, d_v

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.d_rope


def sizes(cfg: dict) -> Sizes:
    """The configuration file's published keys -> Sizes.  `n_routed_experts`
    is the number HELD (a `reduced` key); the router's width is the
    published one."""
    w = cfg["seeded_weights"]
    return Sizes(
        layers=cfg["num_layers"], hidden=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], d_nope=cfg["qk_nope_head_dim"],
        d_rope=cfg["qk_rope_head_dim"], d_v=cfg["v_head_dim"],
        ffn=cfg["ffn_hidden_size"], expert_ffn=cfg["expert_ffn_hidden_size"],
        experts=cfg["published"]["n_routed_experts"],
        zero_experts=cfg["zero_expert_num"],
        local_first=cfg["first_local_expert"],
        local_count=cfg["n_routed_experts"], top_k=cfg["moe_topk"],
        scaling=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        vocab=cfg["vocab_size"],
        router_logit_std=w["router_logit_std"],
        router_bias_std=w["router_bias_std"])


# ----------------------------------------------------------------------
# seeded weights, in the program's layout: per layer `sub` (the two
# sub-blocks' leaves), the router and its bias; the share's experts apart.
# `make_params` stacks layers on a leading axis
# ----------------------------------------------------------------------
Leaves = List[Tuple[str, Tuple[int, ...], str]]


def sub_leaves(s: Sizes) -> Leaves:
    """(name, shape, kind) of one attention + dense-FFN sub-block."""
    H, NH, F = s.hidden, s.heads, s.ffn
    return [
        ("attn_norm_scale", (H,), "scale"), ("mlp_norm_scale", (H,), "scale"),
        ("q_a_norm_scale", (s.q_rank,), "scale"),
        ("kv_a_norm_scale", (s.kv_rank,), "scale"),
        ("wq_a", (H, s.q_rank), "w"),
        ("wq_b", (s.q_rank, NH * (s.d_nope + s.d_rope)), "w"),
        ("wkv_a", (H, s.latent_width), "w"),
        ("wkv_b", (s.kv_rank, NH * (s.d_nope + s.d_v)), "w"),
        ("wo", (NH * s.d_v, H), "out"),
        ("w_gate", (H, F), "w"), ("w_up", (H, F), "w"),
        ("w_down", (F, H), "out")]


def router_leaves(s: Sizes) -> Leaves:
    return [("moe_gate", (s.hidden, s.experts + s.zero_experts), "router"),
            ("moe_router_bias", (s.experts + s.zero_experts,), "rbias")]


def expert_leaves(s: Sizes) -> Leaves:
    """One routed expert."""
    return [("w_gate_proj", (s.hidden, s.expert_ffn), "w"),
            ("w_up", (s.hidden, s.expert_ffn), "w"),
            ("w_down", (s.expert_ffn, s.hidden), "out")]


def top_leaves(s: Sizes) -> Leaves:
    return [("tok_embed", (s.vocab, s.hidden), "w"),
            ("final_norm_scale", (s.hidden,), "scale"),
            ("lm_head", (s.hidden, s.vocab), "w")]


def seed_key(seed) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(20260929),
                              jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    """The driver's seeds pass 2**31: fold into the 32 bits a key takes."""
    return np.uint32(int(seed) % (1 << 32))


def _leaf(key, slot: int, layer, shape, kind: str, s: Sizes, dtype):
    mean, std = {"w": (0.0, 0.02), "scale": (1.0, 0.1),
                 "out": (0.0, 0.02 / math.sqrt(2 * s.layers)),
                 # a normed input has unit RMS: the logits' spread
                 "router": (0.0, s.router_logit_std / math.sqrt(s.hidden)),
                 "rbias": (0.0, s.router_bias_std)}[kind]
    k = jax.random.fold_in(jax.random.fold_in(key, slot), layer)
    return (mean + std * jax.random.normal(k, shape, jnp.float32)
            ).astype(dtype)


def layer_params(key, layer, s: Sizes, dtype) -> dict:
    """One layer (`layer` may be traced): {"sub": [leaves, leaves],
    "moe_gate", "moe_router_bias", "experts": the share's stacks}.  Expert
    `local_first + i` is drawn by its own number, so every share of one
    seed cuts the same 'whole' model."""
    leaf = lambda slot, shape, kind: _leaf(  # noqa: E731
        key, slot, layer, shape, kind, s, dtype)
    out = {"sub": [{n: leaf(20 * sub + i, shape, kind)
                    for i, (n, shape, kind) in enumerate(sub_leaves(s))}
                   for sub in (0, 1)]}
    for i, (n, shape, kind) in enumerate(router_leaves(s)):
        out[n] = leaf(40 + i, shape, kind)
    out["experts"] = {
        n: jnp.stack([leaf(100 * (s.local_first + e + 1) + i, shape, kind)
                      for e in range(s.local_count)])
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
    """x [B, S, ..., D]: rotate the pairs (2i, 2i+1) by position *
    theta^(-2i/D)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[..., None] * inv      # [B, S, D/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def mla(n, sp, positions, s: Sizes, mm, broken=()):
    """Latent attention of a sub-block (leaves `sp`) on normed input n
    [B, S, H]."""
    B, S, H = n.shape
    NH, dn, dr, dv = s.heads, s.d_nope, s.d_rope, s.d_v
    s_q = 1.0 if "no_s_q" in broken else math.sqrt(H / s.q_rank)
    s_kv = 1.0 if "no_s_kv" in broken else math.sqrt(H / s.kv_rank)
    cq = _rms(mm(n, sp["wq_a"]), sp["q_a_norm_scale"], s.eps)
    q = (s_q * mm(cq, sp["wq_b"])).reshape(B, S, NH, dn + dr)
    ckv = mm(n, sp["wkv_a"])
    c = s_kv * _rms(ckv[..., :s.kv_rank], sp["kv_a_norm_scale"], s.eps)
    kr = _rope(ckv[..., s.kv_rank:], positions, s.rope_theta)  # [B, S, dr]
    kv = mm(c, sp["wkv_b"]).reshape(B, S, NH, dn + dv)
    q_rope = _rope(q[..., dn:], positions, s.rope_theta)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def heads(args):                       # a block of heads at a time
        qn, qr, kn, v = args               # [B, S, g, .]
        sc = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn, precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", qr, kr, precision=HI)
              ) / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    g = math.gcd(NH, 8)
    split = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(B, S, NH // g, g, t.shape[-1]), 2, 0)
    o = jax.lax.map(heads, (split(q[..., :dn]), split(q_rope),
                            split(kv[..., :dn]), split(kv[..., dn:])))
    o = jnp.moveaxis(o, 0, 2).reshape(B, S, NH * dv)
    return mm(o, sp["wo"])


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def moe_parts(h, lp, s: Sizes, mm, broken=()):
    """(the local routed experts' part, the identity experts' part) of
    MoE(h), h [B, S, H]."""
    E = s.experts
    score = jax.nn.softmax(jnp.matmul(h, lp["moe_gate"], precision=HI), -1)
    _, picks = jax.lax.top_k(score + lp["moe_router_bias"], s.top_k)
    weigh = score + lp["moe_router_bias"] if "bias_in_weight" in broken \
        else score
    w = jnp.take_along_axis(weigh, picks, axis=-1)             # [B, S, k]
    if "renormalised" in broken:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = s.scaling * w
    # weight of every router output at every token (0 where not picked)
    dense_w = jnp.sum(jax.nn.one_hot(picks, E + s.zero_experts) * w[..., None],
                      axis=-2)
    identity = jnp.sum(dense_w[..., E:], -1, keepdims=True) * h
    if "zero_as_zero" in broken:
        identity = jnp.zeros_like(h)

    def expert(args):
        wg, wu, wd, we = args
        return we[..., None] * swiglu(h, wg, wu, wd, mm)

    local_w = jnp.moveaxis(
        dense_w[..., s.local_first:s.local_first + s.local_count], -1, 0)
    ex = lp["experts"]
    routed = jnp.sum(jax.lax.map(expert, (
        ex["w_gate_proj"], ex["w_up"], ex["w_down"], local_w)), axis=0)
    return routed, identity


def block(x, lp, positions, s: Sizes, precision=None, broken=()):
    """One (double) layer.  x [B, S, H] float32; lp: its leaves
    (`layer_params`), float32.  `broken`: names of departures (the
    controls' and the tests')."""
    mm = functools.partial(_mm, precision=precision)
    sp0, sp1 = lp["sub"]
    ffn = lambda h, sp: swiglu(  # noqa: E731
        h, sp["w_gate"], sp["w_up"], sp["w_down"], mm)
    a0 = x + mla(_rms(x, sp0["attn_norm_scale"], s.eps), sp0, positions, s,
                 mm, broken)
    h0 = _rms(a0, sp0["mlp_norm_scale"], s.eps)
    routed, identity = moe_parts(h0, lp, s, mm, broken)
    m = identity if "identity_only" in broken else routed + identity
    if "no_experts" in broken:
        m = jnp.zeros_like(m)
    y0 = a0 + ffn(h0, sp0)
    if "early_join" in broken:
        y0, m = y0 + m, jnp.zeros_like(m)
    a1 = y0 + mla(_rms(y0, sp1["attn_norm_scale"], s.eps), sp1, positions, s,
                  mm, broken)
    y1 = a1 + ffn(_rms(a1, sp1["mlp_norm_scale"], s.eps), sp1)
    return y1 + m


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


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision"),
                   donate_argnums=(2,))
def _layer_call(seed, layer, x, *, s, dtype, precision):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lp = _f32(layer_params(seed_key(seed), layer, s, dtype))
    mm_precision, broken = _how(precision)
    return block(x, lp, pos, s, mm_precision, broken)


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
        x = _layer_call(seed, np.uint32(layer), x, s=s, dtype=dtype,
                        precision=precision)
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


def served_token_gaps(seed, served, s: Sizes, dtype, precision=None,
                      rows_per_block: int = 2):
    """`served`: (prompt, tokens) int arrays of finished greedy requests.
    The reference runs once over each prompt with its served tokens, in
    blocks of `rows_per_block` rows.  Per request: the gap of each served
    token (precision None), or of the token a control puts first at the
    same positions."""
    out = []
    width = -(-max(len(p) + len(t) - 1 for p, t in served) // 128) * 128
    n_max = max(len(t) for _, t in served)
    for b in range(0, len(served), rows_per_block):
        blk = served[b:b + rows_per_block]
        rows = np.zeros((rows_per_block, width), np.int32)
        for i, (p, t) in enumerate(blk):
            seq = np.concatenate([p, t[:-1]])
            rows[i, :len(seq)] = seq
        h_ref = hidden_states(seed, rows, s, dtype)
        h_low = (hidden_states(seed, rows, s, dtype, precision)
                 if precision else None)
        for i, (p, t) in enumerate(blk):
            at = np.zeros(n_max, np.int32)
            at[:len(t)] = np.arange(len(p) - 1, len(p) - 1 + len(t))
            chosen = np.zeros(n_max, np.int32)
            chosen[:len(t)] = t
            gaps = _gap_call(
                seed_arg(seed), h_ref[i][at],
                None if h_low is None else h_low[i][at], jnp.asarray(chosen),
                jnp.asarray(np.arange(n_max) < len(t)), s=s, dtype=dtype,
                int8=precision == "int8")
            out.append(np.asarray(gaps)[:len(t)])
    return out


# One limit (the traffic file's `greedy_gap_limit`) holds two readings of
# `served_token_gaps`.  The widest gap is one position's: a fault at a
# block's edge or in one row shows there, but over the ~4,000 positions a run
# scores it is the far end of a tail, and the int8 control's lies within
# twice the sound runs'.  The mean gap is every position's: sound runs read
# 0.0030-0.0036 beside a widest of 0.19-0.41, the int8 control 0.043-0.048
# beside 0.69-0.97 (28,598 positions of 6 seeds at 3 layers, my chip run,
# PR 29), because a uniform loss of precision lifts every position and the
# far end hardly.  So the mean counts as a widest gap this many times its
# size: 0.15 in a sound run (the widest decides), 2.0 under int8
MEAN_AS_WIDEST = 45.0


def served_token_gap(seed, served, s: Sizes, dtype, precision=None):
    """(the larger of the widest of `served_token_gaps` and
    `MEAN_AS_WIDEST` times their mean, tokens scored)."""
    gaps = np.concatenate(served_token_gaps(seed, served, s, dtype, precision))
    return (max(float(gaps.max()), MEAN_AS_WIDEST * float(gaps.mean())),
            len(gaps))


# ----------------------------------------------------------------------
# counts: what the mathematics needs once, from shapes alone
# ----------------------------------------------------------------------
def _count(leaves: Leaves) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaves)


def layer_params_held(s: Sizes) -> int:
    """Leaves of one layer as held here (the share's experts only)."""
    return (2 * _count(sub_leaves(s)) + _count(router_leaves(s))
            + s.local_count * _count(expert_leaves(s)))


def weight_bytes(s: Sizes, dtype: str) -> int:
    return (s.layers * layer_params_held(s) + _count(top_leaves(s))) \
        * BYTES[dtype]


def latent_bytes_per_token(s: Sizes, dtype: str) -> int:
    """Cached per token: [c | rope(kr)] for each of a layer's two
    attentions."""
    return 2 * s.layers * s.latent_width * BYTES[dtype]


def experts_with_a_row(s: Sizes, rows: float) -> float:
    """How many of the experts held here a step of `rows` tokens is
    expected to reach under even routing (a token picks a given router
    output with probability top_k / outputs): the others' weights need
    not be read.  16 local experts at 96 rows: 12.5."""
    miss = (1.0 - s.top_k / (s.experts + s.zero_experts)) ** rows
    return s.local_count * (1.0 - miss)


def decode_step_bytes(s: Sizes, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """HBM bytes one decode step must move: the weights held here and the
    head once (of the embedding only the rows looked up; of the local
    experts those expected to have a row, `experts_with_a_row`), every
    live latent row once, the new rows written, float32 logits written."""
    w = BYTES[dtype]
    idle = (s.local_count - experts_with_a_row(s, rows)) \
        * _count(expert_leaves(s))
    weights = (s.layers * (layer_params_held(s) - idle) + s.vocab * s.hidden
               + s.hidden) * w
    return (weights + rows * s.hidden * w
            + (context_tokens + rows) * latent_bytes_per_token(s, dtype)
            + rows * s.vocab * 4)


def mla_decode_bytes(s: Sizes, dtype: str, rows: float,
                     context_tokens: float) -> float:
    """The paged latent decode kernel's own bytes over a step (all 2L
    attentions): every live latent row read once, the absorbed query and
    the rope query read, the output written."""
    w = BYTES[dtype]
    per_row = s.heads * (2 * s.kv_rank + s.d_rope) * w
    return 2 * s.layers * ((context_tokens + rows) * s.latent_width * w
                           + rows * per_row)


def mla_decode_flops(s: Sizes, rows: float, context_tokens: float) -> float:
    """Its multiply-adds, twice: per row and cached token, every head's
    score (latent_width) and weighted sum (kv_rank)."""
    return 2 * s.layers * (context_tokens + rows) \
        * 2 * s.heads * (s.latent_width + s.kv_rank)
