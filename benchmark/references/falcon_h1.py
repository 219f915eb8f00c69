"""Plain float32 reference of Falcon-H1's layer, its seeded weights and its
counts (tiiuae/Falcon-H1-34B-Instruct `config.json`, `model_type`
`falcon_h1`).

Straight `jax.numpy`, float32, `highest` matmul precision, the selective
state-space recurrence ONE TOKEN AT A TIME (`lax.scan` over positions: no
chunks, no state-space-dual matmuls), full causal attention from every key
(a block of queries at a time), no cache, no kernel, one layer's weights at
a time; it imports nothing of the program.

All 72 layers are alike.  Sizes from `config.json`: hidden H 5120; 20
query / 4 key-value heads of D 128; `intermediate_size` F 21504; the mixer
`mamba_d_ssm` 4096 = NHm 32 heads x `mamba_d_head` P 128, `mamba_d_state` N
256, `mamba_n_groups` G 2, `mamba_d_conv` K 4 with bias, `mamba_chunk_size`
128 (the program's; the reference has no chunks), `mamba_rms_norm` true,
`mamba_norm_before_gate` false; vocabulary 261,120, untied head;
`rms_norm_eps` 1e-5; `rope_theta` 1e11, no scaling; no projection biases.
For input x [T, H]:

1. x0 = E[token] * embedding_multiplier.
2. n = rms(x, g_in).  Both branches read n:
   - mixer: p = ((ssm_in_multiplier * n) W_in) * mup, W_in [H, 2 NHm P +
     2 G N + NHm], split [z | xBC | dt]; mup multiplies the column ranges
     [z | x | B | C | dt] by ssm_multipliers[0..4].
     xBC <- silu(causal depthwise conv over the last K positions + bias);
     split x [T, NHm, P], B, C [T, G, N].  dt = softplus(dt + dt_bias) per
     head; A = -exp(A_log) per head.  Head j uses group j // (NHm / G):
     h_t = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t,
     y_t = h_t C_t + D * x_t,  h [NHm, P, N].
     y <- rms_grouped(y * silu(z), g_norm): the gate first, then RMS over
     each of the G groups of NHm P / G channels.
     m = ssm_out_multiplier * (y W_out).
   - attention: q = (attention_in_multiplier * n) W_q, k = key_multiplier *
     ((attention_in_multiplier * n) W_k), v = (... n) W_v; rope on the whole
     head, pairs (i, i + D/2); causal softmax(q.k / sqrt(D)) v;
     a = attention_out_multiplier * (o W_o).
   - x1 = x + m + a.
3. h = rms(x1, g_ff); f = mlp_multipliers[1] * ((W_up h) * silu(
   mlp_multipliers[0] * (W_gate h))) W_down; out = x1 + f.
4. After the last layer: logits = lm_head_multiplier * (rms(x, g_final)
   W_head).

What `config.json` cannot say follows the published `modeling_falcon_h1.py`
(the configuration file's `assumed` lists each): the gated norm is grouped
and gates first; rope rotates the pairs (i, i + D/2); `ssm_multipliers` are
in the order [z, x, B, C, dt]; `time_step_limit` is (0, inf) and clamps
nothing; the convolution's activation is silu.  Departures: seeded random
weights at the spreads `seeded_weights` states; the configuration's cut of
layers.

`precision` selects a control, which has to come out NOT correct: "int8"
(every weight matmul's operands on an 8-bit grid), "no_multipliers" (every
muP multiplier left out), "state_not_carried" (the recurrent state starts
from zero again at every `every`-th position, as if it were not handed
across a chunk's edge), "conv_tail_dropped" (the
convolution sees zeros before such an edge), "gate_after_norm" (RMS first,
then the gate).
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
CONTROLS = ("int8", "no_multipliers", "state_not_carried",
            "conv_tail_dropped", "gate_after_norm")
# queries a block of the reference's attention takes
QUERY_BLOCK = 256
# elements of a leaf drawn in one piece; a larger one an eighth at a time
DRAW_WHOLE_UP_TO = 1 << 28


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    ssm_chunk: int
    eps: float
    rope_theta: float
    vocab: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    mlp_multipliers: Tuple[float, float]
    ssm_multipliers: Tuple[float, float, float, float, float]
    qk_logit_std: float
    branch_out_rms: float
    # the dense block's names for what a shared check reads
    norm, act, pos, tied, qkv_bias = "rms", "swiglu", "rope", False, False

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
    """The configuration file's published keys -> Sizes."""
    w = cfg["seeded_weights"]
    assert cfg["mamba_d_ssm"] == cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    assert cfg["mamba_rms_norm"] and not cfg["mamba_norm_before_gate"]
    return Sizes(
        layers=cfg["num_hidden_layers"], hidden=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn=cfg["intermediate_size"], ssm_heads=cfg["mamba_n_heads"],
        ssm_head_dim=cfg["mamba_d_head"], ssm_state=cfg["mamba_d_state"],
        ssm_groups=cfg["mamba_n_groups"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"], eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]), vocab=cfg["vocab_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        lm_head_multiplier=cfg["lm_head_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        mlp_multipliers=tuple(cfg["mlp_multipliers"]),
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        qk_logit_std=w["qk_logit_std"], branch_out_rms=w["branch_out_rms"])


# ----------------------------------------------------------------------
# seeded weights, in the program's layout.  `make_params` stacks layers on a
# leading axis
# ----------------------------------------------------------------------
Leaves = List[Tuple[str, Tuple[int, ...], str]]


def layer_leaves(s: Sizes) -> Leaves:
    H, NH, NKV, D, F = s.hidden, s.heads, s.kv_heads, s.head_dim, s.ffn
    return [
        ("attn_norm_scale", (H,), "scale"), ("mlp_norm_scale", (H,), "scale"),
        ("wq", (H, NH * D), "q"), ("wk", (H, NKV * D), "k"),
        ("wv", (H, NKV * D), "v"), ("wo", (NH * D, H), "attn_out"),
        ("ssm_in", (H, s.in_width), "ssm_in"),
        ("ssm_conv_w", (s.ssm_conv, s.conv_width), "conv"),
        ("ssm_conv_b", (s.conv_width,), "conv_bias"),
        ("ssm_dt_bias", (s.ssm_heads,), "dt_bias"),
        ("ssm_a_log", (s.ssm_heads,), "a_log"),
        ("ssm_d", (s.ssm_heads,), "scale"),
        ("ssm_norm_scale", (s.ssm_width,), "scale"),
        ("ssm_out", (s.ssm_width, H), "ssm_out"),
        ("w_gate", (H, F), "gate"), ("w_up", (H, F), "up"),
        ("w_down", (F, H), "down")]


def top_leaves(s: Sizes) -> Leaves:
    return [("tok_embed", (s.vocab, s.hidden), "embed"),
            ("final_norm_scale", (s.hidden,), "scale"),
            ("lm_head", (s.hidden, s.vocab), "head")]


def seed_key(seed) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(20261002),
                              jnp.asarray(seed, jnp.uint32))


def seed_arg(seed: int) -> np.uint32:
    """The driver's seeds pass 2**31: fold into the 32 bits a key takes."""
    return np.uint32(int(seed) % (1 << 32))


def _leaf(key, slot: int, layer, shape, kind: str, s: Sizes, dtype):
    """Spreads chosen so that every branch and every multiplier matters
    with random weights (the configuration file's `assumed` has the numpy
    check of each).  The multipliers are the published ones and shrink
    what they touch by 1 to 3 orders of magnitude, so a leaf's spread is
    its target over its multiplier: the residual stream keeps about unit
    RMS (embeddings of std 1 / embedding_multiplier), a normed input times
    an "in" leaf gives unit variance AFTER its multiplier, q.k / sqrt(D)
    spreads by `qk_logit_std` after `key_multiplier`, each branch adds about
    `branch_out_rms` to the stream after its output multiplier, the logits
    spread by about 1 after `lm_head_multiplier`.  `dt_bias` and `A_log` as
    Mamba-2 initialises them: the step log-uniform in [1e-3, 1e-1] through
    the inverse of softplus, the decay rate uniform in [1, 16]."""
    k = jax.random.fold_in(jax.random.fold_in(key, slot), layer)
    if kind == "dt_bias":
        step = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    fan_in = shape[0]
    unit = 1.0 / math.sqrt(fan_in)
    m_z, m_x, m_b, m_c, m_dt = s.ssm_multipliers
    out = s.branch_out_rms
    mean, std = {
        "embed": (0.0, 1.0 / s.embedding_multiplier),
        "scale": (1.0, 0.1),
        "q": (0.0, math.sqrt(s.qk_logit_std) * unit
              / s.attention_in_multiplier),
        "k": (0.0, math.sqrt(s.qk_logit_std) * unit
              / (s.attention_in_multiplier * s.key_multiplier)),
        "v": (0.0, unit / s.attention_in_multiplier),
        "attn_out": (0.0, out * unit / s.attention_out_multiplier),
        "conv": (0.0, 0.5), "conv_bias": (0.0, 0.1),
        "ssm_out": (0.0, out * unit / s.ssm_out_multiplier),
        "gate": (0.0, unit / s.mlp_multipliers[0]),
        "up": (0.0, unit),
        "down": (0.0, out * 2.0 * unit / s.mlp_multipliers[1]),
        "head": (0.0, unit / s.lm_head_multiplier),
        "ssm_in": (0.0, unit / s.ssm_in_multiplier),
    }[kind]
    if int(np.prod(shape)) > DRAW_WHOLE_UP_TO and shape[0] % 8 == 0:
        # a quarter of a million rows: an eighth at a time, so that the
        # float32 draw of the embedding or the head is no 5 GB temporary
        part = (shape[0] // 8,) + tuple(shape[1:])
        return jax.lax.map(
            lambda j: (mean + std * jax.random.normal(
                jax.random.fold_in(k, j), part, jnp.float32)).astype(dtype),
            jnp.arange(8, dtype=jnp.uint32)).reshape(shape)
    w = mean + std * jax.random.normal(k, shape, jnp.float32)
    if kind == "ssm_in":
        # unit variance after each column range's own multiplier
        gn = s.ssm_groups * s.ssm_state
        w = w / jnp.concatenate([
            jnp.full((n,), m, jnp.float32) for n, m in zip(
                (s.ssm_width, s.ssm_width, gn, gn, s.ssm_heads),
                (m_z, m_x, m_b, m_c, m_dt))])
    return w.astype(dtype)


def layer_params(key, layer, s: Sizes, dtype) -> dict:
    """One layer's leaves (`layer` may be traced)."""
    return {n: _leaf(key, i, layer, shape, kind, s, dtype)
            for i, (n, shape, kind) in enumerate(layer_leaves(s))}


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
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def attention(q, k, v):
    """Masked causal attention, a block of queries against every key.
    q [B, S, NH, D]; k, v [B, S, NKV, D]."""
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
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HI) \
            / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange((S + pad) // qb))
    return jnp.moveaxis(out, 0, 1).reshape(B, S + pad, NH, D)[:, :S]


def mixer(n, lp, s: Sizes, mm, mult, broken=(), every: int = 0):
    """The state-space branch on normed rows n [B, S, H]: m [B, S, H].
    `mult(name)`: a multiplier (1 under "no_multipliers"); `every`: where
    a broken carry loses what came before (the controls')."""
    B, S, _ = n.shape
    NHm, P, N, G, K = (s.ssm_heads, s.ssm_head_dim, s.ssm_state,
                       s.ssm_groups, s.ssm_conv)
    Wm, Wc, gn = s.ssm_width, s.conv_width, s.ssm_groups * s.ssm_state
    mup = jnp.concatenate([
        jnp.full((w,), mult("ssm_multipliers", i), jnp.float32)
        for i, w in enumerate((Wm, Wm, gn, gn, NHm))])
    p = mm(mult("ssm_in_multiplier") * n, lp["ssm_in"]) * mup
    z, xbc, dt = p[..., :Wm], p[..., Wm:Wm + Wc], p[..., Wm + Wc:]
    # causal depthwise convolution over the last K positions
    pos = jnp.arange(S)
    conv = lp["ssm_conv_b"]
    for j in range(K):
        back = K - 1 - j                         # tap j reads position t - back
        tap = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :S]
        if "conv_tail_dropped" in broken:
            # nothing from before the last edge at or below t
            tap = jnp.where((pos % every >= back)[None, :, None], tap, 0.0)
        conv = conv + lp["ssm_conv_w"][j] * tap
    xbc = jax.nn.silu(conv)
    x = xbc[..., :Wm].reshape(B, S, NHm, P)
    b = jnp.repeat(xbc[..., Wm:Wm + gn].reshape(B, S, G, N), NHm // G, 2)
    c = jnp.repeat(xbc[..., Wm + gn:].reshape(B, S, G, N), NHm // G, 2)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])                  # [B,S,NHm]
    a = -jnp.exp(lp["ssm_a_log"])
    keep = jnp.ones((S,), jnp.float32)
    if "state_not_carried" in broken:
        keep = (pos % every != 0).astype(jnp.float32)

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
    y = y.reshape(B, S, Wm)
    grouped = lambda t: _rms(t.reshape(B, S, G, Wm // G), 1.0,  # noqa: E731
                             s.eps).reshape(B, S, Wm)
    if "gate_after_norm" in broken:
        y = grouped(y) * lp["ssm_norm_scale"] * jax.nn.silu(z)
    else:
        y = grouped(y * jax.nn.silu(z)) * lp["ssm_norm_scale"]
    return mult("ssm_out_multiplier") * mm(y, lp["ssm_out"])


def block(x, lp, positions, s: Sizes, precision=None, broken=(),
          every: int = 0):
    """One layer.  x [B, S, H] float32; lp: its leaves (`layer_params`),
    float32.  `broken`: names of departures (the controls')."""
    mm = functools.partial(_mm, precision=precision)

    def mult(name, i=None):
        if "no_multipliers" in broken:
            return 1.0
        m = getattr(s, name)
        return m if i is None else m[i]

    B, S, H = x.shape
    NH, NKV, D = s.heads, s.kv_heads, s.head_dim
    n = _rms(x, lp["attn_norm_scale"], s.eps)
    m = mixer(n, lp, s, mm, mult, broken, every)
    na = mult("attention_in_multiplier") * n
    q = mm(na, lp["wq"]).reshape(B, S, NH, D)
    k = (mult("key_multiplier") * mm(na, lp["wk"])).reshape(B, S, NKV, D)
    v = mm(na, lp["wv"]).reshape(B, S, NKV, D)
    o = attention(_rope(q, positions, s.rope_theta),
                  _rope(k, positions, s.rope_theta), v)
    a = mult("attention_out_multiplier") * mm(o.reshape(B, S, NH * D),
                                              lp["wo"])
    x1 = x + m + a
    h = _rms(x1, lp["mlp_norm_scale"], s.eps)
    f = mm(h, lp["w_up"]) * jax.nn.silu(
        mult("mlp_multipliers", 0) * mm(h, lp["w_gate"]))
    return x1 + mult("mlp_multipliers", 1) * mm(f, lp["w_down"])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _how(precision):
    """A control's name -> (matmul precision, broken flags)."""
    if precision is None or precision == "int8":
        return precision, ()
    if precision in CONTROLS:
        return None, (precision,)
    raise ValueError(f"unknown control {precision!r} (have {CONTROLS})")


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision"))
def _embed_call(seed, tokens, *, s, dtype, precision):
    _, broken = _how(precision)
    mult = 1.0 if "no_multipliers" in broken else s.embedding_multiplier
    return mult * jnp.take(
        _f32(top_param(seed_key(seed), "tok_embed", s, dtype)), tokens, 0)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision",
                                             "every"), donate_argnums=(2,))
def _layer_call(seed, layer, x, *, s, dtype, precision, every):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lp = _f32(layer_params(seed_key(seed), layer, s, dtype))
    mm_precision, broken = _how(precision)
    return block(x, lp, pos, s, mm_precision, broken, every)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _final_call(seed, x, *, s, dtype):
    return _rms(x, _f32(top_param(seed_key(seed), "final_norm_scale", s,
                                  dtype)), s.eps)


def hidden_states(seed, tokens: np.ndarray, s: Sizes, dtype,
                  precision=None, every: int = 0):
    """Final-normed hidden states [B, S, H] of padded token rows (padding
    at the end: causality keeps it out of every real position).  One
    layer's weights are made, widened and dropped at a time.  `every`: the
    spacing of the edges at which "state_not_carried" and
    "conv_tail_dropped" lose what came before (default: the chunk)."""
    seed = seed_arg(seed)
    x = _embed_call(seed, jnp.asarray(tokens), s=s, dtype=dtype,
                    precision=precision)
    for layer in range(s.layers):
        x = _layer_call(seed, np.uint32(layer), x, s=s, dtype=dtype,
                        precision=precision, every=every or s.ssm_chunk)
    return _final_call(seed, x, s=s, dtype=dtype)


def _head_multiplier(s: Sizes, precision) -> float:
    return 1.0 if precision == "no_multipliers" else s.lm_head_multiplier


def logits(seed, tokens: np.ndarray, s: Sizes, dtype, precision=None,
           every: int = 0):
    """[B, S, V] logits of padded token rows (tests; small sizes)."""
    head = _f32(top_param(seed_key(seed_arg(seed)), "lm_head", s, dtype))
    return _head_multiplier(s, precision) * jnp.matmul(
        hidden_states(seed, tokens, s, dtype, precision, every), head,
        precision=HI)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "int8"))
def _gap_call(seed, h_ref, h_other, chosen, valid, *, s, dtype, int8):
    """One row: per scored position, the gap by which the scored token's
    logit lies below the reference's best, in units of the reference
    logits' spread there.  With `h_other` the scored token is the one those
    hidden states put first (a control).  (The head's multiplier scales
    every logit alike: it moves neither the order nor this ratio.)"""
    head = _f32(top_param(seed_key(seed), "lm_head", s, dtype))
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
    # (a width is a compile of the layer: rows are padded to few)
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
    as for the other bfloat16 families with a quarter of a million logits a
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


def weight_bytes(s: Sizes, dtype: str) -> int:
    return (s.layers * _count(layer_leaves(s)) + _count(top_leaves(s))) \
        * BYTES[dtype]


def state_bytes_per_row_layer(s: Sizes) -> int:
    """A sequence's recurrent state in one layer: float32 whatever the
    stored type of the weights."""
    return s.ssm_heads * s.ssm_head_dim * s.ssm_state * 4


def kv_bytes_per_token_layer(s: Sizes, dtype: str) -> int:
    """A cached token's key and value in one layer."""
    return 2 * s.kv_heads * s.head_dim * BYTES[dtype]


def ssm_update_bytes(s: Sizes, dtype: str, rows: float,
                     context_tokens: float = 0.0) -> float:
    """The one-token update's own bytes over a decode step (all layers):
    every row's state read and written, its float32 operands (dt * x and
    the decay over the channels, B and C of its groups) read and y
    written."""
    small = (3 * s.ssm_heads * s.ssm_head_dim
             + 2 * s.ssm_groups * s.ssm_state) * 4
    return s.layers * rows * (2 * state_bytes_per_row_layer(s) + small)


def ssm_update_flops(s: Sizes, rows: float,
                     context_tokens: float = 0.0) -> float:
    """Its arithmetic: per state element the decay's multiply, the input's
    multiply-add and the output's multiply-add (5 FLOP)."""
    return s.layers * rows * 5.0 * s.ssm_heads * s.ssm_head_dim * s.ssm_state


def ssd_scan_flops(s: Sizes, prompt_tokens: int) -> float:
    """Multiply-adds, twice, of the chunked scan over ONE prompt (all
    layers), chunks of `ssm_chunk` positions with the last one cut: per
    chunk of q positions and group `C B^T` (q q N), per head the chunk's own
    part (q q P), the carried state's part (q N P) and the state handed on
    (q N P)."""
    Q = s.ssm_chunk
    total = 0.0
    for q in [Q] * (prompt_tokens // Q) + [prompt_tokens % Q]:
        total += 2.0 * (s.ssm_groups * q * q * s.ssm_state + s.ssm_heads * (
            q * q * s.ssm_head_dim + 2 * q * s.ssm_state * s.ssm_head_dim))
    return s.layers * total


def ssd_scan_bytes(s: Sizes, dtype: str, prompt_tokens: int) -> float:
    """Its bytes over one prompt (all layers): x, B and C read in the
    stored type, dt and its running sum in float32, y written in float32,
    the state read once and written once."""
    w = BYTES[dtype]
    per_token = (s.ssm_heads * s.ssm_head_dim * (w + 4)
                 + 2 * s.ssm_groups * s.ssm_state * w + 2 * s.ssm_heads * 4)
    return s.layers * (prompt_tokens * per_token
                       + 2 * state_bytes_per_row_layer(s))


def prefill_flops(s: Sizes, prompt_tokens: int) -> float:
    """Multiply-adds, twice, that the forward pass of ONE fresh prompt
    needs before its first token: every token through a layer's
    projections (attention's, the mixer's, the MLP's), the convolution,
    every (query, key) pair with key <= query over every query head's score
    and weighted sum, the scan; the head on the last position only."""
    n = prompt_tokens
    matmuls = _count([leaf for leaf in layer_leaves(s)
                      if len(leaf[1]) == 2 and leaf[0] != "ssm_conv_w"])
    conv = s.ssm_conv * s.conv_width
    pairs = n * (n + 1) / 2
    return (2.0 * (s.layers * (n * (matmuls + conv)
                               + pairs * 2 * s.heads * s.head_dim)
                   + s.hidden * s.vocab)
            + ssd_scan_flops(s, n))


def decode_step_bytes(s: Sizes, dtype: str, rows: float,
                      context_tokens: float) -> float:
    """HBM bytes one decode step must move: every layer's weights and the
    head once (of the embedding only the rows looked up), every row's
    recurrent state read and written and its convolution tail read and
    written, the keys and values the rows attend to, the new ones written,
    float32 logits written."""
    w = BYTES[dtype]
    weights = (s.layers * _count(layer_leaves(s))
               + s.vocab * s.hidden + s.hidden) * w
    state = s.layers * rows * 2 * (
        state_bytes_per_row_layer(s)
        + (s.ssm_conv - 1) * s.conv_width * w)
    kv = (context_tokens + rows) * s.layers \
        * kv_bytes_per_token_layer(s, dtype) \
        + s.layers * rows * kv_bytes_per_token_layer(s, dtype)
    return weights + rows * s.hidden * w + state + kv + rows * s.vocab * 4
