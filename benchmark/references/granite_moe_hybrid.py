"""Plain float32 reference of Granite-4.0-H's layers, its seeded weights and
its counts (ibm-granite/granite-4.0-h-small `config.json`, `model_type`
`granitemoehybrid`, 32B-A9B).

Straight `jax.numpy`, float32, `highest` matmul precision, the selective
state-space recurrence ONE TOKEN AT A TIME (`lax.scan` over positions: no
chunks, no state-space-dual matmuls), full causal attention from every key
(a block of queries at a time), every expert held here over every token (no
sorting, no grouped matmul), no cache, no kernel, one layer's weights at a
time; it imports nothing of the program.

Layers are of ONE kind each (`layer_types`: `attention` at 5, 15, 25, 35 of
the 40 and `mamba` elsewhere, period 10: `m m m m m a m m m m`), every one
followed by the expert FFN.  Sizes from `config.json`: hidden H 4096; 32
query / 8 key-value heads of D 128, `position_embedding_type` `nope`, no
biases; the mixer `mamba_expand` 2 -> 8192 = NHm 128 heads x `mamba_d_head`
P 64, `mamba_d_state` N 128, `mamba_n_groups` G 1, `mamba_d_conv` K 4 with
bias, `mamba_chunk_size` 256 (the program's; the reference has no chunks),
no projection bias; E 72 experts of `intermediate_size` Fe 768, k 10 a
token, `shared_intermediate_size` Fs 1536; `embedding_multiplier` 12,
`residual_multiplier` r 0.22, `attention_multiplier` 0.0078125,
`logits_scaling` 16; `rms_norm_eps` 1e-5; vocabulary 100,352, the head tied
to the embedding.  For input x [T, H]:

1. x0 = E[token] * 12.
2. Mixer branch, n = rms(x, g_in):
   - `mamba` layer: p = n W_in, W_in [H, 2 NHm P + 2 G N + NHm], split
     [z 8192 | xBC 8448 | dt 128]; xBC <- silu(causal depthwise conv over the
     last K positions + bias); split x [T, NHm, P], B, C [T, G, N] (one
     group: every head reads the same B and C); dt = softplus(dt + dt_bias)
     per head; A = -exp(A_log) per head;
     h_t = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t,
     y_t = h_t C_t + D * x_t,  h [NHm, P, N];
     y <- rms_grouped(y * silu(z), g_norm) over each of the G groups of
     channels (gate first; one group: the plain norm over all 8192);
     m = y W_out.
   - `attention` layer: q, k, v = n W_q, n W_k, n W_v; NO rotation; causal
     softmax(q.k * 0.0078125) v; m = o W_o.
   - x1 = x + r * m.
3. FFN, h = rms(x1, g_ff): router l = h W_r in float32 [T, E]; the k largest
   logits, weights softmax over THOSE k (= softmax over E, picked,
   renormalised); expert e: (silu(h W_g^e) * (h W_u^e)) W_d^e; shared: the
   same form at width Fs, every token;
   out = x1 + r * (sum_picks w_e expert_e(h) + shared(h)).
4. After the last layer: logits = (rms(x, g_final) E^T) / 16.

What `config.json` cannot say follows the published
`modeling_granitemoehybrid.py` (the configuration file's `assumed` lists
each): `time_step_limit` is (0, inf) and clamps nothing; the convolution's
tap k reads position t - (K - 1) + k and its activation is silu; D is one
scale a head; the published fused `input_linear` is [W_g | W_u], the first
half the gate (kept here as two leaves); the router runs in float32; the
gated norm is ungrouped at one group.  Departures: seeded random weights at
the spreads `seeded_weights` states; THIS CHIP'S SHARE of the routed experts
(`local_first`, `local_count`): an assignment to an expert held elsewhere
contributes nothing here (the deployment adds it on another chip), the
shared expert and everything else are whole; the configuration's cut of
layers (whole periods, in the published order).

`precision` selects a control, which has to come out NOT correct: "int8"
(every weight matmul's operands on an 8-bit grid), "no_residual_multiplier"
(r left out: every branch joins at full size), "rope_in_attention" (q and k
rotated, pairs (i, i + D/2), theta `rope_theta`), "router_not_renormalised"
(the picks' weights are their softmax over all E, not over the picks),
"no_shared_expert" (the shared expert dropped), "state_not_carried" (the
recurrent state starts from zero again at every `every`-th position, as if
it were not handed across a chunk's edge).
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
CONTROLS = ("int8", "no_residual_multiplier", "rope_in_attention",
            "router_not_renormalised", "no_shared_expert",
            "state_not_carried")
# queries a block of the reference's attention takes
QUERY_BLOCK = 256
# elements of a leaf drawn in one piece; a larger one an eighth at a time
DRAW_WHOLE_UP_TO = 1 << 28
MIXER, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class Sizes:
    kinds: Tuple[str, ...]        # a layer's kind, in order: the cut's
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    ssm_chunk: int
    expert_ffn: int
    shared_ffn: int
    experts: int                  # routed experts the router scores
    local_first: int              # the share held here: [first, first + count)
    local_count: int
    top_k: int
    eps: float
    rope_theta: float             # "rope_in_attention" only
    vocab: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    embed_rms: float
    qk_logit_std: float
    branch_out_rms: float
    router_logit_std: float
    expert_out_gain: float
    attention_out_gain: float
    # the dense block's names for what a shared check reads
    norm, act, pos, tied, qkv_bias = "rms", "swiglu", "none", True, False

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def ffn(self) -> int:         # no dense FFN: the routed experts' width
        return self.expert_ffn

    @property
    def state_layers(self) -> int:
        return self.kinds.count(MIXER)

    @property
    def attn_layers(self) -> int:
        return self.kinds.count(ATTENTION)

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        return self.ssm_width + self.conv_width + self.ssm_heads


def sizes(cfg: dict) -> Sizes:
    """The configuration file's published keys -> Sizes.  `num_local_experts`
    is the number HELD (a `reduced` key) and `num_hidden_layers` the cut's;
    the router's width is the published one and `layer_types` is whole: the
    cut keeps its first `num_hidden_layers` entries."""
    w = cfg["seeded_weights"]
    assert cfg["position_embedding_type"] == "nope" \
        and cfg["tie_word_embeddings"] and cfg["hidden_act"] == "silu" \
        and cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"] \
        and not cfg["attention_bias"]
    assert cfg["mamba_expand"] * cfg["hidden_size"] \
        == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return Sizes(
        kinds=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        expert_ffn=cfg["intermediate_size"],
        shared_ffn=cfg["shared_intermediate_size"],
        experts=cfg["published"]["num_local_experts"],
        local_first=cfg["first_local_expert"],
        local_count=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), vocab=cfg["vocab_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        embed_rms=w["embed_rms"],
        qk_logit_std=w["qk_logit_std"], branch_out_rms=w["branch_out_rms"],
        router_logit_std=w["router_logit_std"],
        expert_out_gain=w["expert_out_gain"],
        attention_out_gain=w["attention_out_gain"])


# ----------------------------------------------------------------------
# seeded weights, in the program's layout: `layers` holds the two norms, the
# router and the shared expert stacked over ALL layers, the mixer's leaves
# (`ssm_*`) over the layers with a mixer and attention's (`wq`, `wk`, `wv`,
# `wo`) over those with attention; the share's experts apart (`experts`
# [layers, held, ...]); a tied head (no `lm_head`)
# ----------------------------------------------------------------------
Leaves = List[Tuple[str, Tuple[int, ...], str]]


def common_leaves(s: Sizes) -> Leaves:
    return [("attn_norm_scale", (s.hidden,), "scale"),
            ("mlp_norm_scale", (s.hidden,), "scale"),
            ("moe_gate", (s.hidden, s.experts), "router")]


def shared_leaves(s: Sizes) -> Leaves:
    return [("w_gate", (s.hidden, s.shared_ffn), "in"),
            ("w_up", (s.hidden, s.shared_ffn), "in"),
            ("w_down", (s.shared_ffn, s.hidden), "shared_out")]


def expert_leaves(s: Sizes) -> Leaves:
    """One routed expert."""
    return [("w_gate_proj", (s.hidden, s.expert_ffn), "in"),
            ("w_up", (s.hidden, s.expert_ffn), "in"),
            ("w_down", (s.expert_ffn, s.hidden), "expert_out")]


def mixer_leaves(s: Sizes) -> Leaves:
    return [("ssm_in", (s.hidden, s.in_width), "in"),
            ("ssm_conv_w", (s.ssm_conv, s.conv_width), "conv"),
            ("ssm_conv_b", (s.conv_width,), "conv_bias"),
            ("ssm_dt_bias", (s.ssm_heads,), "dt_bias"),
            ("ssm_a_log", (s.ssm_heads,), "a_log"),
            ("ssm_d", (s.ssm_heads,), "scale"),
            ("ssm_norm_scale", (s.ssm_width,), "scale"),
            ("ssm_out", (s.ssm_width, s.hidden), "out")]


def attention_leaves(s: Sizes) -> Leaves:
    H, NH, NKV, D = s.hidden, s.heads, s.kv_heads, s.head_dim
    return [("wq", (H, NH * D), "qk"), ("wk", (H, NKV * D), "qk"),
            ("wv", (H, NKV * D), "in"), ("wo", (NH * D, H), "attn_out")]


def top_leaves(s: Sizes) -> Leaves:
    return [("tok_embed", (s.vocab, s.hidden), "embed"),
            ("final_norm_scale", (s.hidden,), "scale")]


def seed_key(seed) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(20261003),
                              jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    """The driver's seeds pass 2**31: fold into the 32 bits a key takes."""
    return np.uint32(int(seed) % (1 << 32))


def _leaf(key, slot: int, layer, shape, kind: str, s: Sizes, dtype):
    """Spreads chosen so that every branch and every multiplier moves served
    tokens with random weights AND the published multipliers in place (the
    configuration file's `assumed` has the numpy check of each): embeddings
    of std `embed_rms` / embedding_multiplier, so that the residual stream
    starts at `embed_rms`, SMALL beside what the layers add: the head is the
    embedding, so the input token's own row scores 12 sigma sqrt(H) /
    rms(x_final) standard deviations above the other logits, and at unit
    RMS (22 of them at the published widths) greedy decoding repeats its
    input for ever and every comparison reads 0; an "in" leaf gives a normed
    input unit variance; q.k *
    attention_multiplier spreads by `qk_logit_std`; the mixer's and
    attention's out-projections and the shared expert's down-projection make
    their branch about `branch_out_rms` (x 2 for the gated FFN, whose
    activation has RMS ~0.5) AFTER residual_multiplier, attention's
    `attention_out_gain` times that (softmax averages its values over
    hundreds of keys), a routed expert's `expert_out_gain` times the shared
    one's (its weight is about a tenth and half the picks are held
    elsewhere); the router's logits spread by
    `router_logit_std`.  `dt_bias` and `A_log` as Mamba-2 initialises them:
    the step log-uniform in [1e-3, 1e-1] through the inverse of softplus,
    the decay rate uniform in [1, 16]."""
    k = jax.random.fold_in(jax.random.fold_in(key, slot), layer)
    if kind == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    unit = 1.0 / math.sqrt(shape[0])
    out = s.branch_out_rms * unit / s.residual_multiplier
    mean, std = {
        "embed": (0.0, s.embed_rms / s.embedding_multiplier),
        "scale": (1.0, 0.1),
        "in": (0.0, unit),
        "qk": (0.0, unit * math.sqrt(
            s.qk_logit_std / (s.attention_multiplier
                              * math.sqrt(s.head_dim)))),
        "out": (0.0, out),
        "attn_out": (0.0, s.attention_out_gain * out),
        "shared_out": (0.0, 2.0 * out),
        "expert_out": (0.0, s.expert_out_gain * 2.0 * out),
        "router": (0.0, s.router_logit_std * unit),
        "conv": (0.0, 0.5), "conv_bias": (0.0, 0.1),
    }[kind]
    if int(np.prod(shape)) > DRAW_WHOLE_UP_TO and shape[0] % 8 == 0:
        # a hundred thousand rows: an eighth at a time, so that the float32
        # draw of the embedding is no 1.6 GB temporary
        part = (shape[0] // 8,) + tuple(shape[1:])
        return jax.lax.map(
            lambda j: (mean + std * jax.random.normal(
                jax.random.fold_in(k, j), part, jnp.float32)).astype(dtype),
            jnp.arange(8, dtype=jnp.uint32)).reshape(shape)
    return (mean + std * jax.random.normal(k, shape, jnp.float32)
            ).astype(dtype)


def _leaves(key, layer, leaves: Leaves, first_slot: int, s, dtype) -> dict:
    return {n: _leaf(key, first_slot + i, layer, shape, kind, s, dtype)
            for i, (n, shape, kind) in enumerate(leaves)}


def common_params(key, layer, s: Sizes, dtype) -> dict:
    """What every layer has, whatever its kind (`layer`, its absolute
    number, may be traced): the norms, the router, the shared expert and
    the share's experts.  Expert `local_first + i` is drawn by its own
    number, so every share of one seed cuts the same 'whole' model."""
    out = _leaves(key, layer, common_leaves(s), 0, s, dtype)
    out["shared"] = _leaves(key, layer, shared_leaves(s), 10, s, dtype)
    out["experts"] = {
        n: jnp.stack([_leaf(key, 100 * (s.local_first + e + 1) + i, layer,
                            shape, kind, s, dtype)
                      for e in range(s.local_count)])
        for i, (n, shape, kind) in enumerate(expert_leaves(s))}
    return out


def branch_params(key, layer, s: Sizes, dtype, kind: str) -> dict:
    """The leaves of a layer's one branch: the mixer's or attention's."""
    if kind == MIXER:
        return _leaves(key, layer, mixer_leaves(s), 20, s, dtype)
    return _leaves(key, layer, attention_leaves(s), 40, s, dtype)


def layer_params(key, layer, s: Sizes, dtype, kind: str) -> dict:
    """One layer whole (`kind` is static)."""
    return {**common_params(key, layer, s, dtype),
            **branch_params(key, layer, s, dtype, kind)}


def top_param(key, name: str, s: Sizes, dtype) -> jax.Array:
    for i, (n, shape, kind) in enumerate(top_leaves(s)):
        if n == name:
            return _leaf(key, 90 + i, 0, shape, kind, s, dtype)
    raise KeyError(name)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _make_params(seed, *, s: Sizes, dtype):
    key = seed_key(seed)
    params = {n: top_param(key, n, s, dtype) for n, _, _ in top_leaves(s)}
    of_kind = lambda kind: jnp.asarray(  # noqa: E731
        [l for l, k in enumerate(s.kinds) if k == kind], jnp.uint32)
    layers = jax.lax.map(lambda l: common_params(key, l, s, dtype),
                         jnp.arange(s.layers, dtype=jnp.uint32))
    params["experts"] = layers.pop("experts")         # outside the scans
    for kind in (MIXER, ATTENTION):
        if kind in s.kinds:
            layers.update(jax.lax.map(
                lambda l: branch_params(key, l, s, dtype, kind),
                of_kind(kind)))
    params["layers"] = layers
    return params


def make_params(seed: int, s: Sizes, dtype):
    """The whole seeded tree in the program's layout, on the device, in
    one jitted call."""
    return _make_params(seed_arg(seed), s=s, dtype=dtype)


# ----------------------------------------------------------------------
# the layers
# ----------------------------------------------------------------------
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta: float):
    """x [B, S, N, D]: rotate the pairs (i, i + D/2) by position *
    theta^(-2i/D) (the "rope_in_attention" control's)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def attention(q, k, v, scale: float):
    """Masked causal attention, a block of queries against every key.
    q [B, S, NH, D]; k, v [B, S, NKV, D]; scores times `scale`."""
    B, S, NH, D = q.shape
    k = jnp.repeat(k, NH // k.shape[2], axis=2)
    v = jnp.repeat(v, NH // v.shape[2], axis=2)
    qb = min(QUERY_BLOCK, S)
    pad = -S % qb
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    key_pos = jnp.arange(S)[None, :]

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=1)
        seen = key_pos <= (i * qb + jnp.arange(qb))[:, None]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HI) * scale
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange((S + pad) // qb))
    return jnp.moveaxis(out, 0, 1).reshape(B, S + pad, NH, D)[:, :S]


def attention_branch(n, lp, positions, s: Sizes, mm, broken=()):
    """The attention layer's branch on normed rows n [B, S, H]: m [B, S,
    H] before the residual multiplier."""
    B, S, _ = n.shape
    NH, NKV, D = s.heads, s.kv_heads, s.head_dim
    q = mm(n, lp["wq"]).reshape(B, S, NH, D)
    k = mm(n, lp["wk"]).reshape(B, S, NKV, D)
    v = mm(n, lp["wv"]).reshape(B, S, NKV, D)
    if "rope_in_attention" in broken:
        q, k = (_rope(t, positions, s.rope_theta) for t in (q, k))
    o = attention(q, k, v, s.attention_multiplier)
    return mm(o.reshape(B, S, NH * D), lp["wo"])


def mixer(n, lp, s: Sizes, mm, broken=(), every: int = 0):
    """The state-space layer's branch on normed rows n [B, S, H]: m [B, S,
    H] before the residual multiplier.  `every`: where a broken carry
    loses what came before (the control's)."""
    B, S, _ = n.shape
    NHm, P, N, G, K = (s.ssm_heads, s.ssm_head_dim, s.ssm_state,
                       s.ssm_groups, s.ssm_conv)
    Wm, Wc, gn = s.ssm_width, s.conv_width, s.ssm_groups * s.ssm_state
    p = mm(n, lp["ssm_in"])
    z, xbc, dt = p[..., :Wm], p[..., Wm:Wm + Wc], p[..., Wm + Wc:]
    # causal depthwise convolution over the last K positions
    conv = lp["ssm_conv_b"]
    for j in range(K):
        back = K - 1 - j                         # tap j reads position t - back
        conv = conv + lp["ssm_conv_w"][j] * jnp.pad(
            xbc, ((0, 0), (back, 0), (0, 0)))[:, :S]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :Wm].reshape(B, S, NHm, P)
    b = jnp.repeat(xbc[..., Wm:Wm + gn].reshape(B, S, G, N), NHm // G, 2)
    c = jnp.repeat(xbc[..., Wm + gn:].reshape(B, S, G, N), NHm // G, 2)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])                  # [B,S,NHm]
    a = -jnp.exp(lp["ssm_a_log"])
    keep = jnp.ones((S,), jnp.float32)
    if "state_not_carried" in broken:
        keep = (jnp.arange(S) % every != 0).astype(jnp.float32)

    def token(h, inp):
        x_t, b_t, c_t, dt_t, keep_t = inp
        h = (jnp.exp(dt_t * a)[..., None, None] * h * keep_t
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.sum(h * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(
        token, jnp.zeros((B, NHm, P, N), jnp.float32),
        (jnp.moveaxis(x, 1, 0), jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0),
         jnp.moveaxis(dt, 1, 0), keep))
    y = jnp.moveaxis(y, 0, 1) + lp["ssm_d"][:, None] * x          # [B,S,NHm,P]
    y = (y.reshape(B, S, Wm) * jax.nn.silu(z)).reshape(B, S, G, Wm // G)
    y = _rms(y, 1.0, s.eps).reshape(B, S, Wm) * lp["ssm_norm_scale"]
    return mm(y, lp["ssm_out"])


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def route(h, lp, s: Sizes, broken=()):
    """The router on h [..., H], in float32 whatever the control: (picks
    [..., k] int32, weights [..., k])."""
    logits = jnp.matmul(h, lp["moe_gate"], precision=HI)
    top, picks = jax.lax.top_k(logits, s.top_k)
    if "router_not_renormalised" in broken:
        return picks, jnp.take_along_axis(jax.nn.softmax(logits, -1), picks,
                                          axis=-1)
    return picks, jax.nn.softmax(top, -1)


def moe_parts(h, lp, s: Sizes, mm, broken=(), first=None, count=None):
    """(the routed experts' part over the experts [first, first + count):
    the share's by default, whose weights `lp["experts"]` holds; the shared
    expert's part) of the FFN on h [B, S, H]."""
    first = s.local_first if first is None else first
    count = s.local_count if count is None else count
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

    local_w = jnp.moveaxis(dense_w[..., first:first + count], -1, 0)
    ex = lp["experts"]
    routed = jnp.sum(jax.lax.map(expert, (
        ex["w_gate_proj"], ex["w_up"], ex["w_down"], local_w)), axis=0)
    return routed, shared


def block(x, lp, positions, s: Sizes, kind: str, precision=None, broken=(),
          every: int = 0):
    """One layer of `kind`.  x [B, S, H] float32; lp: its leaves
    (`layer_params`), float32.  `broken`: names of departures (the
    controls')."""
    mm = functools.partial(_mm, precision=precision)
    r = 1.0 if "no_residual_multiplier" in broken else s.residual_multiplier
    n = _rms(x, lp["attn_norm_scale"], s.eps)
    m = mixer(n, lp, s, mm, broken, every) if kind == MIXER \
        else attention_branch(n, lp, positions, s, mm, broken)
    x1 = x + r * m
    routed, shared = moe_parts(_rms(x1, lp["mlp_norm_scale"], s.eps), lp, s,
                               mm, broken)
    return x1 + r * (routed + shared)


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
    return s.embedding_multiplier * jnp.take(
        _f32(top_param(seed_key(seed), "tok_embed", s, dtype)), tokens, 0)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision",
                                             "every", "kind"),
                   donate_argnums=(2,))
def _layer_call(seed, layer, x, *, s, dtype, precision, every, kind):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lp = _f32(layer_params(seed_key(seed), layer, s, dtype, kind))
    mm_precision, broken = _how(precision)
    return block(x, lp, pos, s, kind, mm_precision, broken, every)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _final_call(seed, x, *, s, dtype):
    return _rms(x, _f32(top_param(seed_key(seed), "final_norm_scale", s,
                                  dtype)), s.eps)


def hidden_states(seed, tokens: np.ndarray, s: Sizes, dtype,
                  precision=None, every: int = 0):
    """Final-normed hidden states [B, S, H] of padded token rows (padding
    at the end: causality keeps it out of every real position).  One
    layer's weights are made, widened and dropped at a time.  `every`: the
    spacing of the edges at which "state_not_carried" loses what came
    before (default: the chunk)."""
    seed = seed_arg(seed)
    x = _embed_call(seed, jnp.asarray(tokens), s=s, dtype=dtype)
    for layer, kind in enumerate(s.kinds):
        x = _layer_call(seed, np.uint32(layer), x, s=s, dtype=dtype,
                        precision=precision, every=every or s.ssm_chunk,
                        kind=kind)
    return _final_call(seed, x, s=s, dtype=dtype)


def logits(seed, tokens: np.ndarray, s: Sizes, dtype, precision=None,
           every: int = 0):
    """[B, S, V] logits of padded token rows (tests; small sizes)."""
    head = _f32(top_param(seed_key(seed_arg(seed)), "tok_embed", s, dtype))
    return jnp.matmul(
        hidden_states(seed, tokens, s, dtype, precision, every), head.T,
        precision=HI) / s.logits_scaling


@functools.partial(jax.jit, static_argnames=("s", "dtype", "int8"))
def _gap_call(seed, h_ref, h_other, chosen, valid, *, s, dtype, int8):
    """One row: per scored position, the gap by which the scored token's
    logit lies below the reference's best, in units of the reference
    logits' spread there.  With `h_other` the scored token is the one those
    hidden states put first (a control).  (`logits_scaling` divides every
    logit alike: it moves neither the order nor this ratio.)"""
    head = _f32(top_param(seed_key(seed), "tok_embed", s, dtype)).T
    ref = jnp.matmul(h_ref, head, precision=HI)
    if h_other is not None:
        chosen = jnp.argmax(_mm(h_other, head, "int8" if int8 else None), -1)
    at = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    gap = (jnp.max(ref, -1) - at) / jnp.std(ref, axis=-1)
    return jnp.where(valid, gap, 0.0)


def served_token_gaps(seed, served, s: Sizes, dtype, precision=None):
    """`served`: (prompt, tokens) int arrays of finished greedy requests.
    The reference runs once over each prompt with its served tokens, a row
    at a time.  Per request: the gap of each served token (precision
    None), or of the token a control puts first at the same positions."""
    out = []
    # (a width is a compile of the layers: rows are padded to few)
    width = -(-max(len(p) + len(t) - 1 for p, t in served) // 256) * 256
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


def served_token_gap(seed, served, s: Sizes, dtype, precision=None):
    """(the share, in %, of the scored positions whose served token is not
    the reference's best; tokens scored).  A share and not the widest gap,
    as for the other bfloat16 families with a hundred thousand logits a
    position: near ties flip in proportion to the noise in the logits at
    EVERY position, so a uniform loss of precision and a broken mechanism
    both show in it, where the widest gap is one position's
    (`served_token_gaps` keeps the gaps for a closer look; PERF.md section
    6 has the readings the traffic file's limit lies between)."""
    gaps = np.concatenate(served_token_gaps(seed, served, s, dtype, precision))
    return 100.0 * float(np.mean(gaps > 0)), len(gaps)


# ----------------------------------------------------------------------
# counts: what the mathematics needs once, from shapes alone
# ----------------------------------------------------------------------
def _count(leaves: Leaves) -> int:
    return sum(int(np.prod(shape)) for _, shape, _ in leaves)


def layer_params_outside(s: Sizes, kind: str) -> int:
    """A layer of `kind` without its routed experts: the norms, the router,
    the shared expert, the mixer or attention."""
    return (_count(common_leaves(s)) + _count(shared_leaves(s))
            + _count(mixer_leaves(s) if kind == MIXER
                     else attention_leaves(s)))


def params_outside(s: Sizes) -> int:
    return sum(layer_params_outside(s, kind) for kind in s.kinds)


def weight_bytes(s: Sizes, dtype: str) -> int:
    """The leaves held here (the share's experts only; the head is the
    embedding)."""
    return (params_outside(s)
            + s.layers * s.local_count * _count(expert_leaves(s))
            + _count(top_leaves(s))) * BYTES[dtype]


def state_bytes_per_row_layer(s: Sizes) -> int:
    """A sequence's recurrent state in one state-space layer: float32
    whatever the stored type of the weights."""
    return s.ssm_heads * s.ssm_head_dim * s.ssm_state * 4


def kv_bytes_per_token_layer(s: Sizes, dtype: str) -> int:
    """A cached token's key and value in one attention layer."""
    return 2 * s.kv_heads * s.head_dim * BYTES[dtype]


def experts_with_a_row(s: Sizes, rows: float) -> float:
    """How many of the experts held here a step of `rows` tokens is
    expected to reach in one layer under even routing (a token picks a
    given routed expert with probability top_k / experts): the others'
    weights need not be read.  36 held at 96 rows: all of them."""
    miss = (1.0 - s.top_k / s.experts) ** rows
    return s.local_count * (1.0 - miss)


def local_picks(s: Sizes) -> float:
    """Of a token's picks, how many even routing sends to this share."""
    return s.top_k * s.local_count / s.experts


def ssm_update_bytes(s: Sizes, dtype: str, rows: float,
                     context_tokens: float = 0.0) -> float:
    """The one-token update's own bytes over a decode step (the layers
    with a mixer): every row's state read and written, its float32
    operands (dt * x and the decay over the channels, B and C of its
    groups) read and y written."""
    small = (3 * s.ssm_heads * s.ssm_head_dim
             + 2 * s.ssm_groups * s.ssm_state) * 4
    return s.state_layers * rows * (2 * state_bytes_per_row_layer(s) + small)


def ssm_update_flops(s: Sizes, rows: float,
                     context_tokens: float = 0.0) -> float:
    """Its arithmetic: per state element the decay's multiply, the input's
    multiply-add and the output's multiply-add (5 FLOP)."""
    return s.state_layers * rows * 5.0 \
        * s.ssm_heads * s.ssm_head_dim * s.ssm_state


def ssd_scan_flops(s: Sizes, prompt_tokens: int) -> float:
    """Multiply-adds, twice, of the chunked scan over ONE prompt (the
    layers with a mixer), chunks of `ssm_chunk` positions with the last one
    cut: per chunk of q positions and group `C B^T` (q q N), per head the
    chunk's own part (q q P), the carried state's part (q N P) and the
    state handed on (q N P)."""
    Q = s.ssm_chunk
    total = 0.0
    for q in [Q] * (prompt_tokens // Q) + [prompt_tokens % Q]:
        total += 2.0 * (s.ssm_groups * q * q * s.ssm_state + s.ssm_heads * (
            q * q * s.ssm_head_dim + 2 * q * s.ssm_state * s.ssm_head_dim))
    return s.state_layers * total


def ssd_scan_bytes(s: Sizes, dtype: str, prompt_tokens: int) -> float:
    """Its bytes over one prompt (the layers with a mixer): x, B and C read
    in the stored type, dt and its running sum in float32, y written in
    float32, the state read once and written once."""
    w = BYTES[dtype]
    per_token = (s.ssm_heads * s.ssm_head_dim * (w + 4)
                 + 2 * s.ssm_groups * s.ssm_state * w + 2 * s.ssm_heads * 4)
    return s.state_layers * (prompt_tokens * per_token
                             + 2 * state_bytes_per_row_layer(s))


def expert_matmul_bytes(s: Sizes, dtype: str, rows: float,
                        context_tokens: float = 0.0) -> float:
    """The routed experts' grouped matmuls' own bytes over a decode step
    (every layer): the three matrices of every expert held here that the
    step is expected to reach, once; the local picks' rows read (the
    stored type) and their down-projections written (float32), the
    activations between the two matmuls written and read."""
    w = BYTES[dtype]
    picks = rows * local_picks(s)
    return s.layers * (
        experts_with_a_row(s, rows) * _count(expert_leaves(s)) * w
        + picks * (s.hidden * (w + 4) + 2 * s.expert_ffn * w))


def expert_matmul_flops(s: Sizes, rows: float,
                        context_tokens: float = 0.0) -> float:
    """Their multiply-adds, twice: a local pick through the expert's three
    matrices."""
    return s.layers * rows * local_picks(s) * 2.0 * _count(expert_leaves(s))


def prefill_flops(s: Sizes, prompt_tokens: int) -> float:
    """Multiply-adds, twice, that the forward pass of ONE fresh prompt
    needs before its first token: every token through each layer's
    projections (the mixer's or attention's, the router, the shared
    expert, the local picks' experts under even routing), the convolution,
    in the attention layers every (query, key) pair with key <= query over
    every query head's score and weighted sum, the scan; the head on the
    last position only."""
    n = prompt_tokens
    matmul = lambda leaves: _count(  # noqa: E731
        [leaf for leaf in leaves
         if len(leaf[1]) == 2 and leaf[0] != "ssm_conv_w"])
    every = (matmul(common_leaves(s)) + matmul(shared_leaves(s))
             + local_picks(s) * _count(expert_leaves(s)))
    mixers = s.state_layers * (matmul(mixer_leaves(s))
                               + s.ssm_conv * s.conv_width)
    attns = s.attn_layers * matmul(attention_leaves(s))
    pairs = n * (n + 1) / 2
    return (2.0 * (n * (s.layers * every + mixers + attns)
                   + s.attn_layers * pairs * 2 * s.heads * s.head_dim
                   + s.hidden * s.vocab)
            + ssd_scan_flops(s, n))


def decode_step_bytes(s: Sizes, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """HBM bytes one decode step must move: every layer's weights outside
    the routed experts and the tied head once (of the embedding beyond
    that only the rows looked up), of the experts held here those the step
    is expected to reach, every row's recurrent state read and written and
    its convolution tail read and written (the layers with a mixer), the
    keys and values the rows attend to and the new ones written (the
    layers with attention), float32 logits written."""
    w = BYTES[dtype]
    weights = (params_outside(s)
               + s.layers * experts_with_a_row(s, rows)
               * _count(expert_leaves(s))
               + s.vocab * s.hidden + s.hidden) * w
    state = s.state_layers * rows * 2 * (
        state_bytes_per_row_layer(s)
        + (s.ssm_conv - 1) * s.conv_width * w)
    kv = (context_tokens + 2 * rows) * s.attn_layers \
        * kv_bytes_per_token_layer(s, dtype)
    return weights + rows * s.hidden * w + state + kv + rows * s.vocab * 4
