"""Latent-attention (MLA) MoE stacks over a paged latent arena: what
`ragged_ops`' four layer bodies run for a `TransformerConfig` with
`kv_lora_rank > 0`, in its two forms (`cfg.latent_form`).

"shortcut" (LongCat-Flash): a layer is two attention + dense-FFN sub-blocks
with the MoE branching off the first sub-block's post-attention norm and
joining after the second FFN:

    a0 = x + MLA0(rms(x));  h0 = rms(a0);  m = MoE(h0);  y0 = a0 + FFN0(h0)
    a1 = y0 + MLA1(rms(y0));  y1 = a1 + FFN1(rms(a1));  out = y1 + m

"single" (DeepSeek-V3): one attention a layer; the first
`cfg.latent_dense_layers` layers follow it with a dense FFN, the others
with the routed experts beside a shared expert:

    a = x + MLA(rms(x));  h = rms(a)
    out = a + FFN(h)                          a leading dense layer
    out = a + (Shared(h) + MoE(h))            an expert layer

The layer body is data (`_plans`): per sub-block whether a dense FFN follows
its attention, whether the MoE (and a shared expert) branches off there; a
layer's MoE part joins the residual at the layer's end.  `_forward` runs
one scan per plan over that plan's own stacked leaves (the leading dense
layers, then the expert layers), each with Python-static structure: no
layer computes a branch it throws away.  The bodies differ only in the rows
they hand it and in the attention `form`:

- "fresh"  (`prefill_full`): whole prompts from position 0; K/V are
  decompressed from the tokens' own latents and go through the flash path
  (`ops.attention.causal_attention`; head dims 192/128 zero-padded to the
  next multiple of 128, the score scale folded into q);
- "cached" (`prefill_chunks`): chunks against the arena, and "decode"
  (`_decode_core`): one token a row.  Both in the ABSORBED form through
  the paged kernel `ops/mla_paged.py` (tiles of queries x all heads, or
  one query's heads, against the row's arena blocks by block table).  On
  the CPU only: the same absorbed mathematics as a dense gather
  (`mla_paged_reference`).

Under `cfg.rope_scaling` ("yarn") every rotation (fresh, cached, decode; q's
rope part and the shared rope key) uses the blended inverse frequencies and
the score scale carries the squared attention factor
(`_yarn_score_factor`); without it the stack computes what it computed
before the option existed.

Chunk slots are padded (`[NC, C]` rows for at most the step's token budget
of real tokens), so everything that works token by token (norms,
projections, the dense FFNs, the router, the shared expert and the experts)
runs over the real tokens only, `ROW_TILE` at a time (`expert_ffn.rows`).
Attention sees them back in their rows.

Weights: `params["layers"]` holds, stacked over the (expert) layers for the
scan, `sub` (a list of the sub-blocks' leaves), `moe_gate`,
`moe_router_bias` and, in the single form, `shared`; `params["dense_layers"]`
the leading dense layers' `sub`; `params["experts"]` this chip's experts
`[layers, local, ...]` OUTSIDE the scan, for `expert_ffn.moe` (the router,
the share held here, the grouped matmuls and the combine are that module's).

The arena is ONE array `[A, blocks, block_size, W]`: row `[c | rope(kr) |
unused]` per token and attention (A = `cfg.latent_attentions`: attention
`2*layer + sub` of the double block, the layer's own number in the single
form), no V, nothing per head.  Block tables, the allocator and admission
do not know: a block is still `block_size` tokens.  W is `kv_lora_rank +
rope` rounded up to whole 128-lane tiles (576 -> 640), stated in the shape:
the TPU tiles a 576-wide minor dimension to 640 lanes anyway (as it would
two arrays of 512 and 64), and handed the unpadded shape XLA copies the
whole arena into the tiled one before every kernel call (compiled for the
v5e: 2.29 GB of temporaries per call at LongCat's cell's size, none with
640).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig, _scale_rope_freqs
from .expert_ffn import count_names, moe, rms, rows
# the one name this module hands on: tests/benchmark/test_smallthinker.py,
# test_deepseek_v3.py and test_longcat_flash.py read it HERE, and no PR but a
# `benchmark` one edits them
from .expert_ffn import COUNT_DRAIN_STEPS  # noqa: F401
from .ragged_ops import (_dense, _embed, _gate_fused, _lm_logits,
                         _plain_mlp, greedy_tokens)

__all__ = ["COUNT_DRAIN_STEPS", "ROW_TILE", "init_latent_arena",
           "prefill_full", "prefill_chunks", "decode_core"]

# rows a token-wise pass takes at once (see `expert_ffn.rows`)
ROW_TILE = 1024


def init_latent_arena(cfg: TransformerConfig, num_blocks: int,
                      block_size: int, max_seqs: int = 0):
    """(Nothing of it is sized by `max_seqs`.)"""
    width = -(-cfg.latent_width // 128) * 128
    return {"c": jnp.zeros((cfg.latent_attentions, num_blocks, block_size,
                            width), cfg.dtype),
            "moe_counts": jnp.zeros((len(count_names(cfg)),), jnp.int32)}


def _yarn_score_factor(cfg: TransformerConfig) -> float:
    """What `cfg.rope_scaling` puts on the attention scores.  YaRN here is
    the latent models' form: blended inverse frequencies
    (`_scale_rope_freqs`), cos and sin times the attention factor the
    configuration states, and m^2 on the scores, m = 0.1 mscale_all_dim
    ln(factor) + 1.  1.0 without a scaling."""
    if cfg.rope_scaling is None:
        return 1.0
    m = 0.1 * cfg.mla_yarn_mscale_all_dim * math.log(cfg.rope_scaling[1]) + 1
    return m * m


def _rope_pairs(x, positions, theta: float, scaling=None):
    """Rotate the pairs (2i, 2i+1) of x [T, ..., D] by positions [T] *
    theta^(-2i/D) (the interleaved convention); under `scaling`
    (`cfg.rope_scaling`) by the blended frequencies."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    if scaling is not None:
        freqs = _scale_rope_freqs(freqs, scaling, theta)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling[2] != 1.0:
        cos, sin = cos * scaling[2], sin * scaling[2]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def _use_latent_kernel(cfg: TransformerConfig, bs: int, queries: int) -> bool:
    from ...ops.mla_paged import queries_per_step
    from ...utils.device import on_tpu
    tq = queries_per_step(cfg.num_heads)
    return _gate_fused(
        cfg, on_tpu() and bs % 8 == 0 and queries % min(queries, tq) == 0,
        reason=f"attn_impl='pallas' requested but the paged latent "
               f"attention kernel cannot run here (needs TPU, block_size % "
               f"8 == 0 [got {bs}] and whole tiles of {tq} queries [got "
               f"{queries}])")


def _attend_fresh(cfg, q, c, kr, w_kvb):
    """Rows [R, S, ...] attend their own tokens, causally: decompressed
    K/V through the flash path.  q [R, S, NH, dn + dr]."""
    from ...ops.attention import causal_attention
    R, S, NH, dqk = q.shape
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = _dense(c.reshape(R * S, -1), w_kvb).reshape(R, S, NH, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kr[:, :, None],
                                        (R, S, NH, kr.shape[-1]))], -1)
    width = -(-dqk // 128) * 128        # the flash kernel's head widths
    pad = lambda t: jnp.pad(  # noqa: E731
        t, ((0, 0),) * 3 + ((0, width - t.shape[-1]),))
    # the attention path scales by 1/sqrt(its head width): fold the rest in
    q = (q.astype(jnp.float32)
         * (math.sqrt(width / dqk) * _yarn_score_factor(cfg))
         ).astype(q.dtype)
    out = causal_attention(pad(q), pad(k), pad(kv[..., dn:]),
                           impl=cfg.attn_impl)
    return out[..., :dv]


def _live_tiles(cfg, arena_c, block_tables, positions, valid):
    """The list the paged kernel's grid walks for rows of queries at
    `positions` [R, S] (`mla_paged.live_tiles`), or None where the kernel
    does not run: a function of the step's tables and positions, the same
    for every attention of a program."""
    if not _use_latent_kernel(cfg, arena_c.shape[2], positions.shape[1]):
        return None
    from ...ops import mla_paged
    return mla_paged.live_tiles(
        block_tables, positions[:, 0], jnp.sum(valid, axis=1),
        positions.shape[1], cfg.num_heads, *arena_c.shape[1:3])


def _attend_absorbed(cfg, q, arena_c, index, block_tables, pos0, n_valid,
                     w_kvb, tiles=None):
    """Rows of queries q [R, S, NH, dn + dr] against the arena, absorbed:
    the latents are read and never decompressed.  Query i of row r stands
    at pos0[r] + i; n_valid[r] of them are real.  `tiles`: the program's
    `_live_tiles`; None where the kernel does not run."""
    R, S, NH, _ = q.shape
    dn = cfg.qk_nope_head_dim
    w = w_kvb.astype(q.dtype).reshape(cfg.kv_lora_rank, NH, -1)
    q_abs = jnp.einsum("rsnd,cnd->rsnc", q[..., :dn], w[..., :dn],
                       preferred_element_type=jnp.float32).astype(q.dtype)
    from ...ops import mla_paged
    fn = (mla_paged.mla_paged_reference if tiles is None else
          functools.partial(mla_paged.mla_paged_attention, tiles=tiles))
    u = fn(q_abs, q[..., dn:], arena_c, block_tables, pos0, n_valid, index,
           sm_scale=_yarn_score_factor(cfg) / math.sqrt(q.shape[-1]))
    o = jnp.einsum("rsnc,cnd->rsnd", u, w[..., dn:],
                   preferred_element_type=jnp.float32).astype(u.dtype)
    if tiles is None:
        return o
    # the kernel writes no query tile without a real query: zeroed here,
    # in this product's epilogue
    real = jnp.arange(S)[None] < n_valid[:, None]
    return jnp.where(real[:, :, None, None], o, 0)


# ----------------------------------------------------------------------
# the layer, once: its body is data
# ----------------------------------------------------------------------
class SubBlock(NamedTuple):
    """One attention of a layer and what follows its output projection."""
    dense_ffn: bool      # a dense FFN on the post-attention norm
    moe: bool            # the routed experts branch off that norm; their
    #                      part joins the residual at the layer's end
    shared: bool         # ... beside a shared expert on every token
    reads: tuple         # what the token-wise stage after the attention
    #                      takes, in this order: the residual `y`, an earlier
    #                      sub-block's experts' part `m`, the attention's
    #                      output `o`, `pos` where the next sub-block's
    #                      projections follow, `real` where it routes


def _plans(cfg: TransformerConfig):
    """The stack as ((params key, first layer, layers, sub-blocks), ...):
    one layer scan each, in order."""
    L, Ld = cfg.num_layers, cfg.latent_dense_layers
    if cfg.latent_form == "shortcut":
        return (("layers", 0, L, (
            SubBlock(True, True, False, ("y", "o", "pos", "real")),
            SubBlock(True, False, False, ("y", "m", "o")))),)
    dense = SubBlock(True, False, False, ("y", "o"))
    routed = SubBlock(False, True, bool(cfg.moe_shared_expert_ffn),
                      ("y", "o", "real"))
    return ((("dense_layers", 0, Ld, (dense,)),) if Ld else ()) + (
        ("layers", Ld, L - Ld, (routed,)),)


def _forward(cfg: TransformerConfig, params, arena, tokens, positions, valid,
             block_tables, form: str):
    """tokens/positions/valid [R, S] (a row's real tokens first);
    block_tables [R, MB]; `form` as the module docstring.  Returns (hidden
    states [R, S, H], arena)."""
    R, S = tokens.shape
    H, T, NH, dt = cfg.hidden_size, R * S, cfg.num_heads, cfg.dtype
    dn, dr, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    El = cfg.local_experts
    nb, bs, Wa = arena["c"].shape[1:]
    MB = block_tables.shape[1]
    s_q = math.sqrt(H / cfg.q_lora_rank) if cfg.mla_scale_q_lora else 1.0
    s_kv = math.sqrt(H / rank) if cfg.mla_scale_kv_lora else 1.0
    yarn = cfg.rope_scaling
    experts = {n: w.reshape((w.shape[0] * El,) + w.shape[2:]).astype(dt)
               for n, w in params["experts"].items()}

    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, MB - 1), axis=1)
    blk = jnp.where(valid, blk, nb).reshape(T)       # padded slots drop
    off, pos, real = (positions % bs).reshape(T), positions.reshape(T), \
        valid.reshape(T)
    toks, n = tokens.reshape(T), jnp.sum(valid)
    # more rows than a pass takes: the real ones go in front (`rows`)
    compact = T > ROW_TILE and T % ROW_TILE == 0
    if compact:
        order = jnp.argsort(~real, stable=True)
        back = jnp.argsort(order)
        toks, pos, real, blk, off = (a[order] for a in
                                     (toks, pos, real, blk, off))
    in_rows = lambda a: (a[back] if compact else a).reshape(  # noqa: E731
        (R, S) + a.shape[1:])
    in_line = lambda a: a.reshape((T,) + a.shape[2:])[order] \
        if compact else a.reshape((T,) + a.shape[2:])  # noqa: E731
    none = jnp.zeros((), jnp.int32)
    # one list of live key tiles a program, outside the layer scan
    tiles = None if form == "fresh" else _live_tiles(
        cfg, arena["c"], block_tables, positions, valid)
    x = _embed(cfg, params, toks, pos)                            # [T, H]

    def project(sp, t, pos):
        """Normed input t [tile, H] -> (queries with their rope part
        rotated [tile, NH * (dn + dr)], cache rows [tile, Wa])."""
        with jax.named_scope("mla_proj"):
            # both scale factors ride the bottleneck norms (q = W_qb s_q cq)
            cq = rms(_dense(t, sp["wq_a"]), sp["q_a_norm_scale"],
                      cfg.norm_eps, s_q)
            q = _dense(cq, sp["wq_b"]).reshape(-1, NH, dn + dr)
            q = jnp.concatenate(
                [q[..., :dn],
                 _rope_pairs(q[..., dn:], pos, cfg.rope_theta, yarn)], -1)
            ckv = _dense(t, sp["wkv_a"])
            c = rms(ckv[:, :rank], sp["kv_a_norm_scale"], cfg.norm_eps, s_kv)
            kr = _rope_pairs(ckv[:, rank:], pos, cfg.rope_theta, yarn)
            row = jnp.pad(jnp.concatenate([c, kr], -1).astype(dt),
                          ((0, 0), (0, Wa - rank - dr)))
        return q.reshape(-1, NH * (dn + dr)), row

    def attend(sp, index, q, row, arena_c):
        """The attention proper of one sub-block on projected queries and
        rows [T, ...]: (heads' outputs [T, NH * dv], arena)."""
        with jax.named_scope("latent_write"):
            arena_c = arena_c.at[index, blk, off].set(row, mode="drop")
        with jax.named_scope("mla_attention"):
            q = in_rows(q).reshape(R, S, NH, dn + dr)
            if form == "fresh":
                row = in_rows(row)
                o = _attend_fresh(cfg, q, row[..., :rank],
                                  row[..., rank:rank + dr], sp["wkv_b"])
            else:
                o = _attend_absorbed(
                    cfg, q, arena_c, index, block_tables, positions[:, 0],
                    jnp.sum(valid, axis=1), sp["wkv_b"], tiles)
        return in_line(o.reshape(R, S, NH * cfg.v_head_dim).astype(dt)), \
            arena_c

    def ffn(sp, h):
        with jax.named_scope("dense_ffn"):
            return _plain_mlp(cfg, sp, h)

    def out_proj(sp, o):
        with jax.named_scope("mla_proj"):
            return _dense(o, sp["wo"])

    def layer_of(subs, first: int):
        """The scan body of layers made of `subs` (`SubBlock`s).  A layer
        is token-wise stages with an attention between two of them; stage
        k closes sub-block k - 1 (output projection, norm, its experts and
        dense FFN) and opens sub-block k (its projections); the last adds
        the experts' part, which rode along since its sub-block."""
        A, last = len(subs), len(subs) - 1

        def arena_index(li, k: int):
            """Attention k of this stack's layer li.  (No `+ 0`, no `* 1`:
            the double block's programs stay the ones they were,
            instruction for instruction.)"""
            at = li + first if first else li
            at = A * at if A > 1 else at
            return at + k if k else at

        def layer(carry, xs):
            x, arena_c, counts = carry
            lp, li = xs                   # li: the layer's place in ITS stack

            def before(x, pos):
                sp = lp["sub"][0]
                return project(sp, rms(x, sp["attn_norm_scale"],
                                        cfg.norm_eps), pos), none

            def closing(k: int):
                """Stage k + 1, on the operands `subs[k].reads`."""
                sub, sp = subs[k], lp["sub"][k]

                def stage(*ins):
                    v = dict(zip(sub.reads, ins))
                    a = v["y"] + out_proj(sp, v["o"])
                    h = rms(a, sp["mlp_norm_scale"], cfg.norm_eps)
                    m, c = v.get("m"), none
                    if sub.moe:
                        m, c = moe(cfg, lp, experts, li, h, v["real"])
                        if sub.shared:
                            with jax.named_scope("shared_expert"):
                                m = _plain_mlp(cfg, lp["shared"], h) + m
                    y = a + ffn(sp, h) if sub.dense_ffn else a
                    if k == last:
                        return (y if m is None else y + m,), c
                    nxt = lp["sub"][k + 1]
                    q, row = project(nxt, rms(y, nxt["attn_norm_scale"],
                                               cfg.norm_eps), v["pos"])
                    return (y,) + (() if m is None else (m,)) + (q, row), c
                return stage

            (q, row), _ = rows(before, n, (x, pos), none, ROW_TILE)
            held = {"y": x, "pos": pos, "real": real}
            for k, sub in enumerate(subs):
                held["o"], arena_c = attend(lp["sub"][k], arena_index(li, k),
                                            q, row, arena_c)
                outs, e = rows(closing(k), n,
                               tuple(held[name] for name in sub.reads),
                               counts if sub.moe else none, ROW_TILE)
                if sub.moe:
                    counts = e
                if k < last:
                    *rest, q, row = outs
                    held.update(zip(("y", "m"), rest))
            return (outs[0], arena_c, counts), None
        return layer

    carry = (x, arena["c"], arena["moe_counts"])
    for key, first, count, subs in _plans(cfg):
        carry, _ = jax.lax.scan(layer_of(subs, first), carry,
                                (params[key], jnp.arange(count)))
    x, arena_c, counts = carry
    return in_rows(x), {**arena, "c": arena_c, "moe_counts": counts}


def _last_logits(cfg, params, x, last):
    xl = x[jnp.arange(x.shape[0]), jnp.clip(last, 0, x.shape[1] - 1)]
    logits = _lm_logits(cfg, params, xl)
    return logits, greedy_tokens(logits)


def prefill_full(cfg, params, arena, tokens, lens, block_tables, active,
                 slots=None):
    """`ragged_ops.prefill_full` for a latent model (same contract)."""
    NS, S = tokens.shape
    lens = jnp.where(active, lens, 0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (NS, S))
    x, arena = _forward(cfg, params, arena, tokens, positions,
                        positions < lens[:, None], block_tables, "fresh")
    return (*_last_logits(cfg, params, x, lens - 1), arena)


def prefill_chunks(cfg, params, arena, tokens, pos0s, n_valids,
                   block_tables, active, slots=None, **uniform_only):
    """`ragged_ops.prefill_chunks` for a latent model (same contract)."""
    C = tokens.shape[1]
    pos0s = jnp.where(active, pos0s, 0)
    n_valids = jnp.where(active, n_valids, 0)
    positions = pos0s[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    valid = jnp.arange(C)[None] < n_valids[:, None]
    x, arena = _forward(cfg, params, arena, tokens, positions, valid,
                        block_tables, "cached")
    return (*_last_logits(cfg, params, x, n_valids - 1), arena)


def decode_core(cfg, params, arena, tokens, seq_lens, block_tables, active,
                slots=None, **uniform_only):
    """`ragged_ops._decode_core` for a latent model: (logits, arena)."""
    x, arena = _forward(cfg, params, arena, tokens[:, None],
                        seq_lens[:, None], active[:, None], block_tables,
                        "decode")
    return _lm_logits(cfg, params, x[:, 0]), arena
