"""Plain float32 reference of DeepSeek-V3's layer, its seeded weights and its
counts (deepseek-ai/DeepSeek-V3 `config.json`).

Straight `jax.numpy`, float32, `highest` matmul precision, full-square
causal attention from DECOMPRESSED keys and values (no absorption), no
cache, no kernel, no batching, experts one after another over every token,
one layer's weights at a time; it imports nothing of the program.

Sizes from the configuration file: H hidden; L layers, the first Ld
(`first_k_dense_replace`) with a dense SwiGLU FFN of width F, the others
with E routed experts of width Fe (k a token) and one shared expert of
width Fe; NH heads, ranks rq / rkv, head widths dn (no position) | dr
(rope) for q and k, dv for v; untied head; no biases.  Layer l, input x
[T, H]:

1. h = rms(x, g_attn).  cq = rms(h W_qa, g_qa) [T, rq];  q = cq W_qb
   [T, NH, dn + dr] = [q_n | q_r].  ckv = h W_kva [T, rkv + dr];
   c = rms(ckv[:, :rkv], g_kva);  k_r = ckv[:, rkv:]: ONE rope key a token,
   shared by all heads.  No sqrt(hidden / rank) factors.
2. Rope on q_r and k_r, pairs (2i, 2i+1), with YaRN frequencies:
   f_i = theta^(-2i/dr), i = 0..dr/2-1;  corr(r) = dr ln(orig / (2 pi r)) /
   (2 ln theta);  low = max(floor(corr(beta_fast)), 0),  high =
   min(ceil(corr(beta_slow)), dr - 1)  (10 and 23 at the published sizes);
   ramp_i = clip((i - low) / (high - low), 0, 1);
   inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i.
   cos and sin are NOT scaled (mscale / mscale_all_dim = 1).
3. k_n[h] = c W_kvb,K[h],  v[h] = c W_kvb,V[h];
   s = (q_n . k_n + q_r . k_r) (dn + dr)^-0.5 m^2,  m = 0.1 mscale_all_dim
   ln(factor) + 1 (1.3689; m^2 = 1.874);  causal softmax;  a = softmax(s) v;
   x1 = x + concat_h(a) W_o.
4. h2 = rms(x1, g_mlp).  l < Ld:  y = W_down (silu(W_gate h2) * (W_up h2)).
   l >= Ld:  s = sigmoid(h2 W_r) in float32 [T, E];  b = s + e (the
   correction bias, selection only);  group j = experts j E/G .. (j+1) E/G - 1
   (G = `n_group`);  a group's score is the sum of its 2 largest b;  keep
   the `topk_group` best groups;  among their experts take the k largest b
   (ties: the lower index);  w = scaling * s[picked] / (sum s[picked] +
   1e-20);  y = shared(h2) + sum_j w_j expert_{e_j}(h2), every expert and
   the shared one a SwiGLU of width Fe.  out = x1 + y.
5. After the last layer: rms(., g_final), then the untied head.

What `config.json` does not say (the configuration file's `assumed` lists
each; all are the published modelling code's): the rope pairing; that the
bias enters the selection only; how a group is scored; that an expert
outside the kept groups cannot be picked (its score counts as -inf, where
the code masks to 0: the same picks whenever k experts of the kept groups
score above 0, as sigmoid scores plus a small bias do); the YaRN
arithmetic; float32 router.  Departures: seeded random weights at the
spreads `seeded_weights` states; THIS CHIP'S SHARE of the routed experts
(`local_first`, `local_count`): an assignment to an expert held elsewhere
contributes nothing here (the deployment adds it on another chip), the
shared expert and everything else are whole; the configuration's cut of
layers; the multi-token-prediction module (`num_nextn_predict_layers`) is
not part of the served stack, as in the published code's serving path.

`precision` selects a control, which has to come out NOT correct: "int8"
(every matmul operand on an 8-bit grid), "softmax_router" (softmax scores in
the sigmoid's place), "ungrouped_router" (the k best of all E, no groups),
"no_shared_expert" (the shared expert dropped), "plain_rope" (no YaRN blend,
no m^2), "block_edge" (a NARROW fault: the attention output of the one
position in `cache_block` that opens a cache block is dropped, every other
position computed soundly).  `block`'s other `broken` flags are mistakes
the tests show the comparison can see.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import sys
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.transformer import HI, _mm

BYTES = {"bfloat16": 2, "float32": 4}
CONTROLS = ("int8", "softmax_router", "ungrouped_router", "no_shared_expert",
            "plain_rope", "block_edge")


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    dense_layers: int     # the leading layers with a dense FFN
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    ffn: int              # the dense layers' FFN width
    expert_ffn: int       # a routed expert's and the shared expert's
    experts: int          # routed experts the router scores
    shared_experts: int
    local_first: int      # the share held here: [first, first + count)
    local_count: int
    top_k: int
    groups: int
    groups_kept: int
    scaling: float
    eps: float
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    vocab: int
    router_logit_std: float
    router_bias_std: float
    expert_out_gain: float
    attention_out_gain: float
    cache_block: int      # tokens a cache block holds (`block_edge` only)
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

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.yarn_mscale_all_dim * math.log(self.yarn_factor) + 1
        return m * m / math.sqrt(self.d_nope + self.d_rope)


def sizes(cfg: dict) -> Sizes:
    """The configuration file's published keys -> Sizes.  `n_routed_experts`
    is the number HELD (a `reduced` key); the router's width is the
    published one."""
    w, y = cfg["seeded_weights"], cfg["rope_scaling"]
    assert y["type"] == "yarn" and cfg["scoring_func"] == "sigmoid" \
        and cfg["norm_topk_prob"] and cfg["n_shared_experts"] == 1
    return Sizes(
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        d_nope=cfg["qk_nope_head_dim"], d_rope=cfg["qk_rope_head_dim"],
        d_v=cfg["v_head_dim"], ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        experts=cfg["published"]["n_routed_experts"],
        shared_experts=cfg["n_shared_experts"],
        local_first=cfg["first_local_expert"],
        local_count=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], groups=cfg["n_group"],
        groups_kept=cfg["topk_group"],
        scaling=float(cfg["routed_scaling_factor"]),
        eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(y["factor"]),
        yarn_original=y["original_max_position_embeddings"],
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]), yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        vocab=cfg["vocab_size"],
        router_logit_std=w["router_logit_std"],
        router_bias_std=w["router_bias_std"],
        expert_out_gain=w["expert_out_gain"],
        attention_out_gain=w["attention_out_gain"],
        cache_block=cfg["program"]["engine"].get("block_size", 64))


# ----------------------------------------------------------------------
# seeded weights, in the program's layout: `dense_layers` (per leading
# layer `sub`: one attention + dense FFN) and `layers` (per expert layer
# `sub`: one attention; the router and its bias; `shared`), each stacked
# over its own layers; the share's experts apart
# ----------------------------------------------------------------------
Leaves = List[Tuple[str, Tuple[int, ...], str]]


def attention_leaves(s: Sizes) -> Leaves:
    """(name, shape, kind) of one attention and the layer's two norms."""
    H, NH = s.hidden, s.heads
    return [
        ("attn_norm_scale", (H,), "scale"), ("mlp_norm_scale", (H,), "scale"),
        ("q_a_norm_scale", (s.q_rank,), "scale"),
        ("kv_a_norm_scale", (s.kv_rank,), "scale"),
        ("wq_a", (H, s.q_rank), "w"),
        ("wq_b", (s.q_rank, NH * (s.d_nope + s.d_rope)), "w"),
        ("wkv_a", (H, s.latent_width), "w"),
        ("wkv_b", (s.kv_rank, NH * (s.d_nope + s.d_v)), "w"),
        ("wo", (NH * s.d_v, H), "attention_out")]


def ffn_leaves(s: Sizes, width: int, out: str = "out") -> Leaves:
    return [("w_gate", (s.hidden, width), "w"),
            ("w_up", (s.hidden, width), "w"),
            ("w_down", (width, s.hidden), out)]


def router_leaves(s: Sizes) -> Leaves:
    return [("moe_gate", (s.hidden, s.experts), "router"),
            ("moe_router_bias", (s.experts,), "rbias")]


def expert_leaves(s: Sizes) -> Leaves:
    """One routed expert."""
    return [("w_gate_proj", (s.hidden, s.expert_ffn), "w"),
            ("w_up", (s.hidden, s.expert_ffn), "w"),
            ("w_down", (s.expert_ffn, s.hidden), "expert_out")]


def top_leaves(s: Sizes) -> Leaves:
    return [("tok_embed", (s.vocab, s.hidden), "w"),
            ("final_norm_scale", (s.hidden,), "scale"),
            ("lm_head", (s.hidden, s.vocab), "w")]


def seed_key(seed) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(20260930),
                              jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    """The driver's seeds pass 2**31: fold into the 32 bits a key takes."""
    return np.uint32(int(seed) % (1 << 32))


def _leaf(key, slot: int, layer, shape, kind: str, s: Sizes, dtype):
    out = 0.02 / math.sqrt(2 * s.layers)
    mean, std = {"w": (0.0, 0.02), "scale": (1.0, 0.1), "out": (0.0, out),
                 # the shared and the routed experts' down-projections: the
                 # branch as large as the residual it joins
                 "expert_out": (0.0, s.expert_out_gain * out),
                 # attention's: a third of the residual, not a tenth
                 "attention_out": (0.0, s.attention_out_gain * out),
                 # a normed input has unit RMS: the logits' spread
                 "router": (0.0, s.router_logit_std / math.sqrt(s.hidden)),
                 "rbias": (0.0, s.router_bias_std)}[kind]
    k = jax.random.fold_in(jax.random.fold_in(key, slot), layer)
    return (mean + std * jax.random.normal(k, shape, jnp.float32)
            ).astype(dtype)


def layer_params(key, layer, s: Sizes, dtype, dense: bool) -> dict:
    """One layer (`layer`, its absolute number, may be traced; `dense` is
    static).  A dense layer: {"sub": [attention + FFN leaves]}.  An expert
    layer: {"sub": [attention leaves], "moe_gate", "moe_router_bias",
    "shared", "experts": the share's stacks}.  Expert `local_first + i` is
    drawn by its own number, so every share of one seed cuts the same
    'whole' model."""
    leaf = lambda slot, shape, kind: _leaf(  # noqa: E731
        key, slot, layer, shape, kind, s, dtype)
    sub = {n: leaf(i, shape, kind)
           for i, (n, shape, kind) in enumerate(attention_leaves(s))}
    if dense:
        sub.update({n: leaf(20 + i, shape, kind) for i, (n, shape, kind)
                    in enumerate(ffn_leaves(s, s.ffn))})
        return {"sub": [sub]}
    out = {"sub": [sub]}
    for i, (n, shape, kind) in enumerate(router_leaves(s)):
        out[n] = leaf(40 + i, shape, kind)
    out["shared"] = {n: leaf(50 + i, shape, kind) for i, (n, shape, kind)
                     in enumerate(ffn_leaves(s, s.expert_ffn, "expert_out"))}
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
    if s.dense_layers:
        params["dense_layers"] = jax.lax.map(
            lambda l: layer_params(key, l, s, dtype, True),
            jnp.arange(s.dense_layers, dtype=jnp.uint32))
    params["layers"] = jax.lax.map(
        lambda l: layer_params(key, l, s, dtype, False),
        jnp.arange(s.dense_layers, s.layers, dtype=jnp.uint32))
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


def yarn_inv_freq(s: Sizes, plain: bool = False) -> np.ndarray:
    """The rope part's inverse frequencies [dr / 2] (docstring, step 2)."""
    dr = s.d_rope
    f = s.rope_theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    if plain:
        return f.astype(np.float32)

    def corr(rotations):
        return dr * math.log(s.yarn_original / (rotations * 2 * math.pi)) \
            / (2 * math.log(s.rope_theta))
    low = max(math.floor(corr(s.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(s.yarn_beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / s.yarn_factor * ramp).astype(np.float32)


def _rope(x, positions, inv_freq):
    """x [B, S, ..., D]: rotate the pairs (2i, 2i+1) by position *
    inv_freq[i]."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def mla(n, sp, positions, s: Sizes, mm, broken=()):
    """Latent attention (leaves `sp`) on normed input n [B, S, H]."""
    B, S, H = n.shape
    NH, dn, dr, dv = s.heads, s.d_nope, s.d_rope, s.d_v
    plain = "plain_rope" in broken
    inv = yarn_inv_freq(s, plain)
    scale = 1.0 / math.sqrt(dn + dr) if plain else s.softmax_scale
    cq = _rms(mm(n, sp["wq_a"]), sp["q_a_norm_scale"], s.eps)
    q = mm(cq, sp["wq_b"]).reshape(B, S, NH, dn + dr)
    ckv = mm(n, sp["wkv_a"])
    c = _rms(ckv[..., :s.kv_rank], sp["kv_a_norm_scale"], s.eps)
    kr = _rope(ckv[..., s.kv_rank:], positions, inv)          # [B, S, dr]
    kv = mm(c, sp["wkv_b"]).reshape(B, S, NH, dn + dv)
    q_rope = _rope(q[..., dn:], positions, inv)
    if "rope_halves" in broken:        # pairs (i, i + dr/2) instead
        half = lambda t: jnp.concatenate(  # noqa: E731
            [t[..., 0::2], t[..., 1::2]], -1)
        back = lambda t: jnp.stack(  # noqa: E731
            [t[..., :dr // 2], t[..., dr // 2:]], -1).reshape(t.shape)
        kr = half(_rope(back(ckv[..., s.kv_rank:]), positions, inv))
        q_rope = half(_rope(back(q[..., dn:]), positions, inv))
    causal = jnp.tril(jnp.ones((S, S), bool))

    def heads(args):                       # a block of heads at a time
        qn, qr, kn, v = args               # [B, S, g, .]
        sc = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn, precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", qr, kr, precision=HI)) * scale
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    g = math.gcd(NH, 8)
    split = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape(B, S, NH // g, g, t.shape[-1]), 2, 0)
    o = jax.lax.map(heads, (split(q[..., :dn]), split(q_rope),
                            split(kv[..., :dn]), split(kv[..., dn:])))
    o = jnp.moveaxis(o, 0, 2).reshape(B, S, NH * dv)
    if "block_edge" in broken:
        o = jnp.where((positions % s.cache_block == 0)[..., None], 0.0, o)
    return mm(o, sp["wo"])


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def route(h, lp, s: Sizes, broken=()):
    """The router on h [..., H]: (picks [..., k] int32, weights [..., k])."""
    logits = jnp.matmul(h, lp["moe_gate"], precision=HI)
    score = (jax.nn.softmax(logits, -1) if "softmax_router" in broken
             else jax.nn.sigmoid(logits))
    b = score + lp["moe_router_bias"]
    if "ungrouped_router" not in broken:
        per = s.experts // s.groups
        grouped = b.reshape(b.shape[:-1] + (s.groups, per))
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        _, keep = jax.lax.top_k(group_score, s.groups_kept)
        kept = jnp.sum(jax.nn.one_hot(keep, s.groups), -2) > 0
        b = jnp.where(jnp.repeat(kept, per, -1), b, -jnp.inf)
    _, picks = jax.lax.top_k(b, s.top_k)
    w = jnp.take_along_axis(b if "bias_in_weight" in broken else score,
                            picks, axis=-1)
    if "not_renormalised" not in broken:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return picks, s.scaling * w


def moe_parts(h, lp, s: Sizes, mm, broken=()):
    """(the local routed experts' part, the shared expert's part) of the
    expert layer's FFN on h [B, S, H]."""
    picks, w = route(h, lp, s, broken)
    # weight of every routed expert at every token (0 where not picked)
    dense_w = jnp.sum(jax.nn.one_hot(picks, s.experts) * w[..., None],
                      axis=-2)
    sh = lp["shared"]
    shared = swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"], mm)
    if "no_shared_expert" in broken:
        shared = jnp.zeros_like(h)

    def expert(args):
        wg, wu, wd, we = args
        return we[..., None] * swiglu(h, wg, wu, wd, mm)

    local_w = jnp.moveaxis(
        dense_w[..., s.local_first:s.local_first + s.local_count], -1, 0)
    ex = lp["experts"]
    routed = jnp.sum(jax.lax.map(expert, (
        ex["w_gate_proj"], ex["w_up"], ex["w_down"], local_w)), axis=0)
    return routed, shared


def block(x, lp, positions, s: Sizes, dense: bool, precision=None,
          broken=()):
    """One layer.  x [B, S, H] float32; lp: its leaves (`layer_params`),
    float32; `dense`: a leading dense layer.  `broken`: names of
    departures (the controls' and the tests')."""
    mm = functools.partial(_mm, precision=precision)
    sp = lp["sub"][0]
    x1 = x + mla(_rms(x, sp["attn_norm_scale"], s.eps), sp, positions, s, mm,
                 broken)
    h2 = _rms(x1, sp["mlp_norm_scale"], s.eps)
    if dense:
        return x1 + swiglu(h2, sp["w_gate"], sp["w_up"], sp["w_down"], mm)
    routed, shared = moe_parts(h2, lp, s, mm, broken)
    if "shared_only" in broken:
        routed = jnp.zeros_like(routed)
    return x1 + (shared + routed)


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
                                             "dense"),
                   donate_argnums=(2,))
def _layer_call(seed, layer, x, *, s, dtype, precision, dense):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lp = _f32(layer_params(seed_key(seed), layer, s, dtype, dense))
    mm_precision, broken = _how(precision)
    return block(x, lp, pos, s, dense, mm_precision, broken)


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
                        precision=precision, dense=layer < s.dense_layers)
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


# What the traffic file's `greedy_gap_limit` holds for this family: the
# largest of three readings of `served_token_gaps`, each counted at the
# weight that puts ITS limit between its sound and its faulty readings
# (PERF.md section 6; limit 1.7).
# - The MEAN gap is every position's: a uniform loss of precision or a wrong
#   rule in the router lifts it (sound 0.011-0.016, the int8 control
#   0.078-0.088, the router without its groups 0.069-0.084; its limit 0.038).
# - The 99TH PERCENTILE is the worst position in a hundred's: a fault in one
#   row, at a block's edge or in one step's rows shows there before it moves
#   the mean (sound 0.37-0.44 over ten seeds, int8 0.78-0.89, `block_edge`
#   3.7; its limit 0.68, above which lie 0.3-0.4% of a sound run's positions
#   and 1.5-2.3% of int8's).
# - The WIDEST gap is one position's, and here a sound run's own rounding
#   shows there too: 8 of 128 kept experts are picked by float32 scores
#   whose 8th and 9th lie 0.005 apart, bfloat16 picks the other one at about
#   one position in ten, and where that expert is held here the position's
#   logits move by up to 1.8 spreads.  Over 27 seeds a sound run's widest
#   gap read 0.86-1.79 (over 1.6 in six runs, never over 1.8; int8's reads
#   1.4-2.0, the same), and the tail is steep: one position in 1,700 over
#   1.2, one in 65,000 over 1.79, by which one run in 13,000 would read over
#   2.83, its limit: between 1.79 and what a position served at random
#   reads (`block_edge`'s widest on the chip: 7.7).  Below that one
#   position's gap tells no fault, so it counts `WIDEST_WEIGHT` of its size.
MEAN_AS_WIDEST = 45.0
P99_AS_WIDEST = 2.5
WIDEST_WEIGHT = 0.6


def greedy_gap(gaps: np.ndarray) -> Tuple[float, dict]:
    """(what is held to `greedy_gap_limit`, the three readings of every
    scored position's gap it is the largest of, unweighted)."""
    read = {"widest": float(gaps.max()),
            "p99": float(np.percentile(gaps, 99)),
            "mean": float(gaps.mean())}
    return max(WIDEST_WEIGHT * read["widest"], P99_AS_WIDEST * read["p99"],
               MEAN_AS_WIDEST * read["mean"]), read


def served_token_gap(seed, served, s: Sizes, dtype, precision=None):
    """(`greedy_gap` of `served_token_gaps`, tokens scored).  The three
    readings go to standard error, for whoever asks which one decided."""
    gaps = np.concatenate(served_token_gaps(seed, served, s, dtype, precision))
    value, read = greedy_gap(gaps)
    print("[reference] " + json.dumps(
        {"greedy_gap_readings": read, "control": precision,
         "positions": len(gaps)}), file=sys.stderr, flush=True)
    return value, len(gaps)


# ----------------------------------------------------------------------
# counts: what the mathematics needs once, from shapes alone
# ----------------------------------------------------------------------
def _count(leaves: Leaves) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaves)


def dense_layer_params(s: Sizes) -> int:
    return _count(attention_leaves(s)) + _count(ffn_leaves(s, s.ffn))


def expert_layer_params_outside(s: Sizes) -> int:
    """An expert layer without its routed experts: attention, norms,
    router and bias, the shared expert."""
    return (_count(attention_leaves(s)) + _count(router_leaves(s))
            + s.shared_experts * _count(ffn_leaves(s, s.expert_ffn)))


def weight_bytes(s: Sizes, dtype: str) -> int:
    """The leaves held here (the share's experts only)."""
    return (s.dense_layers * dense_layer_params(s)
            + s.expert_layers * (expert_layer_params_outside(s)
                                 + s.local_count * _count(expert_leaves(s)))
            + _count(top_leaves(s))) * BYTES[dtype]


def latent_bytes_per_token(s: Sizes, dtype: str) -> int:
    """Cached per token: [c | rope(kr)] for each layer's one attention."""
    return s.layers * s.latent_width * BYTES[dtype]


def experts_with_a_row(s: Sizes, rows: float) -> float:
    """How many of the experts held here a step of `rows` tokens is
    expected to reach under even routing (a token picks a given routed
    expert with probability top_k / experts, groups or none): the others'
    weights need not be read.  16 local experts at 64 rows: 13.9."""
    miss = (1.0 - s.top_k / s.experts) ** rows
    return s.local_count * (1.0 - miss)


def decode_step_bytes(s: Sizes, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """HBM bytes one decode step must move: the weights held here and the
    head once (of the embedding only the rows looked up; of the local
    experts those expected to have a row, `experts_with_a_row`), every
    live latent row once, the new rows written, float32 logits written."""
    w = BYTES[dtype]
    weights = (s.dense_layers * dense_layer_params(s)
               + s.expert_layers * (expert_layer_params_outside(s)
                                    + experts_with_a_row(s, rows)
                                    * _count(expert_leaves(s)))
               + s.vocab * s.hidden + s.hidden) * w
    return (weights + rows * s.hidden * w
            + (context_tokens + rows) * latent_bytes_per_token(s, dtype)
            + rows * s.vocab * 4)


def mla_decode_bytes(s: Sizes, dtype: str, rows: float,
                     context_tokens: float) -> float:
    """The paged latent decode kernel's own bytes over a step (all L
    attentions): every live latent row read once, the absorbed query and
    the rope query read, the output written."""
    w = BYTES[dtype]
    per_row = s.heads * (2 * s.kv_rank + s.d_rope) * w
    return s.layers * ((context_tokens + rows) * s.latent_width * w
                       + rows * per_row)


def mla_decode_flops(s: Sizes, rows: float, context_tokens: float) -> float:
    """Its multiply-adds, twice: per row and cached token, every head's
    score (latent_width) and weighted sum (kv_rank)."""
    return s.layers * (context_tokens + rows) \
        * 2 * s.heads * (s.latent_width + s.kv_rank)
