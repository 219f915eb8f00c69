"""Plain float32 reference of a decoder-only transformer (Qwen2, OPT).

Straight `jax.numpy`, float32, `highest` matmul precision, full-square
causal attention, no cache, no kernel, no batching trick; it imports nothing
of the program.  It follows the published block equations (Qwen2: RMSNorm,
rotary halves, grouped K/V heads, QKV bias, SwiGLU, untied head; OPT:
pre-LayerNorm, learned positions, biased projections, ReLU, tied head).
Departures: OPT's learned positions index from 0 (the published checkpoint
offsets them by 2 rows, a table layout, not mathematics) and no dropout
(both as the configuration file's `assumed` says).

The model is walked layer by layer and the weights of a layer are made from
the seed inside the call that uses them (`benchmark.weights`), so the whole
model is never held.  `precision` selects the control's lower precision:
None (the reference itself), "int8" (weights on a per-output-channel int8
grid and matmul inputs on a per-token int8 grid, float32 accumulation) —
one step below a bfloat16 configuration.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W
from benchmark.counts import (decode_step_bytes,  # noqa: F401  (the
                              train_flops_per_token)  # family's counts)

HI = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------------------
# what the harness, the traffic kinds and the readers ask of a reference
# module (they find it by the configuration's `reference` and import
# nothing else of a family): `sizes`, `make_params`, `served_token_gap`,
# `train_steps`, and the counts a metric file names (`decode_step_bytes`,
# `train_flops_per_token`).  The sizes object has `.vocab`, the width of
# the token ids the traffic draws.
# ----------------------------------------------------------------------
sizes = W.sizes_from_config


def make_params(seed: int, s: W.Sizes, dtype):
    """The whole seeded tree in the program's layout, on the device, in
    one jitted call."""
    return W.make_params(W.seed_arg(seed), s=s, dtype=dtype)


# ----------------------------------------------------------------------
# precision of the control
# ----------------------------------------------------------------------
def _grid8(x, axis):
    """x rounded to a symmetric 8-bit grid scaled by max|x| along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm8(x, w):
    """x [..., K] @ w [K, N] with every matmul operand on an 8-bit grid, in
    the backward pass too (the incoming gradient per row), as an 8-bit
    training recipe would have it; accumulation stays float32."""
    return jnp.matmul(_grid8(x, -1), _grid8(w, 0), precision=HI)


def _mm8_fwd(x, w):
    xq, wq = _grid8(x, -1), _grid8(w, 0)
    return jnp.matmul(xq, wq, precision=HI), (xq, wq)


def _mm8_bwd(res, g):
    xq, wq = res
    gq = _grid8(g, -1)
    dx = jnp.matmul(gq, wq.T, precision=HI)
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    gq.reshape(-1, gq.shape[-1]), precision=HI)
    return dx, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(x, w, precision: Optional[str]):
    """x @ w with float32 accumulation; under "int8" the operands are put
    on 8-bit grids first (weights per output channel, activations and
    gradients per row)."""
    if precision == "int8":
        return _mm8(x, w)
    if precision is not None:
        raise ValueError(f"unknown control precision {precision!r}")
    return jnp.matmul(x, w, precision=HI)


# ----------------------------------------------------------------------
# the block
# ----------------------------------------------------------------------
def _norm(x, scale, bias, s: W.Sizes):
    if s.norm == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + s.eps) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + s.eps) * scale + bias


def _rope(x, positions, theta: float):
    """x [B,S,N,D]; rotate (x[:half], x[half:]) by position * theta^(-i/half)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def block(x, lp, positions, s: W.Sizes, precision=None):
    """One layer.  x [B,S,H] float32; lp: the layer's leaves, float32."""
    B, S, _ = x.shape
    mm = functools.partial(_mm, precision=precision)
    h = _norm(x, lp["attn_norm_scale"], lp.get("attn_norm_bias"), s)
    q = mm(h, lp["wq"]) + lp["bq"]
    k = mm(h, lp["wk"]) + lp["bk"]
    v = mm(h, lp["wv"]) + lp["bv"]
    q = q.reshape(B, S, s.heads, s.head_dim)
    k = k.reshape(B, S, s.kv_heads, s.head_dim)
    v = v.reshape(B, S, s.kv_heads, s.head_dim)
    if s.pos == "rope":
        q, k = _rope(q, positions, s.rope_theta), _rope(k, positions,
                                                        s.rope_theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        / np.sqrt(s.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HI)
    attn = mm(attn.reshape(B, S, s.heads * s.head_dim), lp["wo"])
    if s.dense_bias:
        attn = attn + lp["bo"]
    x = x + attn
    h = _norm(x, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"), s)
    if s.act == "swiglu":
        h = jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"])
        h = mm(h, lp["w_down"])
    else:
        h = jax.nn.relu(mm(h, lp["w_up"]) + lp["b_up"])
        h = mm(h, lp["w_down"]) + lp["b_down"]
    return x + h


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def embed(tokens, positions, key, s: W.Sizes, dtype):
    x = jnp.take(_f32(W.top_param(key, "tok_embed", s, dtype)), tokens, 0)
    if s.pos == "learned":
        x = x + jnp.take(_f32(W.top_param(key, "pos_embed", s, dtype)),
                         positions, 0)
    return x


def head_weight(key, s: W.Sizes, dtype):
    if s.tied:
        return _f32(W.top_param(key, "tok_embed", s, dtype)).T
    return _f32(W.top_param(key, "lm_head", s, dtype))


def final_norm(x, key, s: W.Sizes, dtype):
    bias = (_f32(W.top_param(key, "final_norm_bias", s, dtype))
            if s.norm == "ln" else None)
    return _norm(x, _f32(W.top_param(key, "final_norm_scale", s, dtype)),
                 bias, s)


# ----------------------------------------------------------------------
# serving: hidden states of whole sequences, then the gap of chosen tokens
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _embed_call(seed, tokens, *, s, dtype):
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return embed(tokens, pos, W.seed_key(seed), s, dtype)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision"),
                   donate_argnums=(2,))
def _layer_call(seed, layer, x, *, s, dtype, precision):
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lp = _f32(W.layer_params(W.seed_key(seed), layer, s, dtype))
    return block(x, lp, pos, s, precision)


@functools.partial(jax.jit, static_argnames=("s", "dtype"))
def _final_call(seed, x, *, s, dtype):
    return final_norm(x, W.seed_key(seed), s, dtype)


def hidden_states(seed, tokens: np.ndarray, s: W.Sizes, dtype,
                  precision=None):
    """Final-normed hidden states [B,S,H] of padded token rows (padding sits
    at the end, so causal attention keeps it out of every real position)."""
    seed = W.seed_arg(seed)
    x = _embed_call(seed, jnp.asarray(tokens), s=s, dtype=dtype)
    for layer in range(s.layers):
        x = _layer_call(seed, np.uint32(layer), x, s=s, dtype=dtype,
                        precision=precision)
    return _final_call(seed, x, s=s, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("s", "dtype", "precision"))
def _gap_call(seed, h_ref, h_other, chosen, valid, *, s, dtype, precision):
    """One row.  h_ref [N,H]: the reference's hidden states at the scored
    positions; `chosen` [N]: the tokens to score, or, with `h_other` [N,H],
    ignored in favour of the tokens the lower precision puts first there.
    Returns, per position, the gap by which the scored token lies below the
    reference's best, in units of the reference logits' spread there."""
    head = head_weight(W.seed_key(seed), s, dtype)
    logits = jnp.matmul(h_ref, head, precision=HI)
    if h_other is not None:
        chosen = jnp.argmax(_mm(h_other, head, precision), axis=-1)
    at = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    gap = (jnp.max(logits, -1) - at) / jnp.std(logits, axis=-1)
    return jnp.where(valid, gap, 0.0)


def served_token_gaps(seed, served, s: W.Sizes, dtype, precision=None,
                      rows_per_block: int = 4):
    """`served`: list of (prompt, tokens) int arrays of finished greedy
    requests.  The reference runs once over each prompt with its served
    tokens.  With `precision` None, returns per request the gap by which
    each served token's logit lies below the reference's best; with a lower
    precision, the gap of the token THAT precision puts first at each of
    the same positions (the control: it need not decode)."""
    out = []
    width = max(len(p) + len(t) - 1 for p, t in served)
    width = -(-width // 128) * 128       # few shapes, so few compiles
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
                W.seed_arg(seed), h_ref[i][at],
                None if h_low is None else h_low[i][at],
                jnp.asarray(chosen), jnp.asarray(np.arange(n_max) < len(t)),
                s=s, dtype=dtype, precision=precision)
            out.append(np.asarray(gaps)[:len(t)])
    return out


def served_token_gap(seed, served, s: W.Sizes, dtype, precision=None):
    """(the widest of `served_token_gaps`, tokens scored)."""
    gaps = served_token_gaps(seed, served, s, dtype, precision)
    return (max(float(g.max()) for g in gaps), sum(len(g) for g in gaps))


# ----------------------------------------------------------------------
# training: loss, gradients and AdamW steps, a block of rows and a layer at
# a time (float32 weights, gradients and activations of the whole model and
# batch do not fit one chip together)
# ----------------------------------------------------------------------
def init_train_params(seed, s: W.Sizes, dtype):
    """Float32 master weights: the stored `dtype` values, widened."""
    key = W.seed_key(W.seed_arg(seed))
    top = {n: _f32(W.top_param(key, n, s, dtype))
           for n, _, _ in W.top_leaves(s)}
    layers = [_f32(W.layer_params(key, np.uint32(l), s, dtype))
              for l in range(s.layers)]
    return {"top": top, "layers": layers}


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                            ids.shape)


@functools.partial(jax.jit, static_argnames=("s",))
def _train_embed(top, ids, *, s):
    x = jnp.take(top["tok_embed"], ids, 0)
    if s.pos == "learned":
        x = x + jnp.take(top["pos_embed"], _positions(ids), 0)
    return x


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _train_layer(x, lp, *, s, precision):
    return block(x, lp, _positions(x[..., 0]), s, precision)


def _head_loss(top, x, labels, s, precision):
    x = _norm(x, top["final_norm_scale"], top.get("final_norm_bias"), s)
    head = top["tok_embed"].T if s.tied else top["lm_head"]
    logp = jax.nn.log_softmax(_mm(x, head, precision), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return jnp.mean(nll, axis=-1)            # one loss per row


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _train_head(top, x, labels, *, s, precision):
    """Sum of the rows' mean losses, with its gradient to x and to the
    head-side leaves of `top`."""
    def f(top, x):
        return jnp.sum(_head_loss(top, x, labels, s, precision))
    loss, (d_top, d_x) = jax.value_and_grad(f, argnums=(0, 1))(top, x)
    return loss, d_top, d_x


@functools.partial(jax.jit, static_argnames=("s", "precision"),
                   donate_argnums=(3,))
def _train_layer_back(x, lp, d_out, acc, *, s, precision):
    _, vjp = jax.vjp(lambda x, lp: block(x, lp, _positions(x[..., 0]), s,
                                         precision), x, lp)
    d_x, d_lp = vjp(d_out)
    return d_x, jax.tree.map(jnp.add, acc, d_lp)


@functools.partial(jax.jit, static_argnames=("s",), donate_argnums=(3,))
def _train_embed_back(top, ids, d_x, acc, *, s):
    _, vjp = jax.vjp(lambda top: _train_embed(top, ids, s=s), top)
    return jax.tree.map(jnp.add, acc, vjp(d_x)[0])


def loss_and_grads(params, batch: np.ndarray, s: W.Sizes, precision=None,
                   rows_per_block: int = 2):
    """Mean over the rows of `batch` [rows, S+1] of each row's mean
    next-token cross-entropy, and its gradient in the layout of `params`."""
    rows = batch.shape[0]
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)  # noqa: E731
    g_top, g_layers = zeros(params["top"]), [zeros(lp)
                                             for lp in params["layers"]]
    loss = 0.0
    for b in range(0, rows, rows_per_block):
        ids = jnp.asarray(batch[b:b + rows_per_block, :-1])
        labels = jnp.asarray(batch[b:b + rows_per_block, 1:])
        xs = [_train_embed(params["top"], ids, s=s)]
        for lp in params["layers"]:
            xs.append(_train_layer(xs[-1], lp, s=s, precision=precision))
        l, d_top, d_x = _train_head(params["top"], xs.pop(), labels, s=s,
                                    precision=precision)
        loss += float(l)
        g_top = jax.tree.map(jnp.add, g_top, d_top)
        for i in reversed(range(s.layers)):
            d_x, g_layers[i] = _train_layer_back(
                xs.pop(), params["layers"][i], d_x, g_layers[i], s=s,
                precision=precision)
        g_top = _train_embed_back(params["top"], ids, d_x, g_top, s=s)
    scale = jax.jit(lambda t: jax.tree.map(lambda a: a / rows, t))
    return loss / rows, {"top": scale(g_top),
                         "layers": [scale(g) for g in g_layers]}


@jax.jit
def _sq_sum(tree):
    return sum(jnp.sum(a * a) for a in jax.tree.leaves(tree))


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(a * a)), tree)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("b1", "b2", "eps", "wd"))
def _adamw_leaves(p, g, m, v, lr, c1, c2, *, b1, b2, eps, wd):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    p = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), p, m, v)
    return p, m, v


def train_steps(seed, batches, s: W.Sizes, dtype, hyper: dict,
                precision=None) -> dict:
    """Follow len(batches) optimizer steps of AdamW with global-norm
    clipping from the seeded weights.  Returns each step's loss, the
    per-leaf norms of the first step's gradient as the optimizer gets it
    (clipped), and the per-leaf norms of the weights' change after the last
    step.  The moments live on the host between steps (they do not fit
    beside weights and gradients)."""
    b1, b2 = hyper["betas"]
    kw = dict(b1=b1, b2=b2, eps=hyper["eps"], wd=hyper["weight_decay"])
    params = init_train_params(seed, s, dtype)
    groups = [("top", None)] + [("layers", i) for i in range(s.layers)]
    pick = lambda t, g: t[g[0]] if g[1] is None else t[g[0]][g[1]]  # noqa
    moments = {g: None for g in groups}
    out = {"loss": []}
    for k, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, batch, s, precision)
        out["loss"].append(loss)
        gnorm = float(jnp.sqrt(_sq_sum(grads)))
        clip = min(1.0, hyper["clip"] / (gnorm + 1e-6))
        c1, c2 = 1.0 - b1 ** k, 1.0 - b2 ** k
        if k == 1:
            out["grad_global_norm"] = gnorm
        for g in groups:
            gl = jax.tree.map(lambda a: a * clip, pick(grads, g))
            if k == 1:
                n = jax.device_get(_leaf_norms(gl))
                out.setdefault("grad_norm", {})[g] = n
            mv = moments[g]
            m, v = (jax.tree.map(jnp.zeros_like, gl),) * 2 if mv is None \
                else (jax.tree.map(jnp.asarray, mv[0]),
                      jax.tree.map(jnp.asarray, mv[1]))
            p, m, v = _adamw_leaves(pick(params, g), gl, m, v,
                                    hyper["lr"], c1, c2, **kw)
            if g[1] is None:
                params["top"] = p
            else:
                params["layers"][g[1]] = p
            if k < len(batches):
                moments[g] = (jax.device_get(m), jax.device_get(v))
            del gl, m, v
        del grads
    key = W.seed_key(W.seed_arg(seed))
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)), a, b))
    out["update_norm"] = {}
    for g in groups:
        p0 = ({n: _f32(W.top_param(key, n, s, dtype))
               for n, _, _ in W.top_leaves(s)} if g[1] is None else
              _f32(W.layer_params(key, np.uint32(g[1]), s, dtype)))
        out["update_norm"][g] = jax.device_get(diff(pick(params, g), p0))
    for name in ("grad_norm", "update_norm"):
        per = out[name]
        out[name] = {"top": {k: float(v) for k, v in
                             per[("top", None)].items()},
                     "layers": {k: np.array([float(per[("layers", i)][k])
                                             for i in range(s.layers)])
                                for k in per[("layers", 0)]}}
    return out
