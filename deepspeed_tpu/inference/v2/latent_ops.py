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
runs over the real tokens only: a program of more than `ROW_TILE` rows
moves the real ones to the front and takes them `ROW_TILE` at a time, for
as many passes as they need (`_rows`).  Attention sees them back in their
rows.

Weights: `params["layers"]` holds, stacked over the (expert) layers for the
scan, `sub` (a list of the sub-blocks' leaves), `moe_gate`,
`moe_router_bias` and, in the single form, `shared`; `params["dense_layers"]`
the leading dense layers' `sub`; `params["experts"]` holds this chip's
experts `[layers, local, ...]` OUTSIDE the scan: the grouped matmuls take
the whole stack (a per-layer slice handed to a custom call is first
copied, 1.2 GB a layer at LongCat's cell's size: measured 29 of a 51 ms
decode step).  On the chip they are `ops/grouped_matmul.py` over the live
(expert of the whole stack, row tile) items of the layer at hand, gate and
up in one pass; elsewhere three `lax.ragged_dot` calls with group sizes that
are zero outside the layer (`_use_expert_kernel`: the platform's choice).
Their float32 outputs come back to their tokens by one gather a pick and a
sum over the picks in a fixed order (scope `experts/combine`) where the
compact buffer holds every assignment (`cap == T * k`: a fact of the
program's shape), and by the pieces' scatter-add where it holds a share.
Where it holds them all, the kernel runs and an even routing gives an
expert a row tile's rows or more (a prompt's pass, not a decode step:
`grouped_matmul.aligns`, shapes again), each expert's rows start on a tile
edge in a longer buffer, so that no (expert, tile) item multiplies another
expert's rows, and a pick's row is found behind its expert's padding.

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

The router is a description (`Router`, `router_of(cfg)`) read by one
function (`_route`): softmax or sigmoid scores, a selection bias, the
choice limited to the best few of equal expert groups, weights renormalised
or not and scaled, identity outputs; the families' routers are values of
it.  The MoE holds a SHARE of the routed experts (`cfg.moe_expert_first`,
`cfg.local_experts`): it routes over every router output, runs grouped
matmuls over the assignments to its own experts only, adds the identity
experts' part for every token, and leaves the absent experts' part out
(another chip's work; nothing stands in for it).  Rider `moe_counts`
([len(count_names(cfg))] int32) accumulates what the router did, for
`InferenceEngineV2.drain_moe_counts`.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig, _scale_rope_freqs
from .ragged_ops import (_dense, _embed, _gate_fused, _lm_logits,
                         _plain_mlp, greedy_tokens)

__all__ = ["COUNT_NAMES", "GROUP_COUNT_NAMES", "KERNEL_COUNT_NAMES",
           "COUNT_DRAIN_STEPS", "ROW_TILE",
           "Router", "router_of", "count_names", "init_latent_arena",
           "prefill_full", "prefill_chunks", "decode_core", "local_rows_cap",
           "refuse_lora"]

# rows a token-wise pass takes at once (see `_rows`)
ROW_TILE = 1024

# what `moe_counts` holds, summed over layers and program calls: top-k
# picks of valid tokens; those that fell on identity experts; those that
# fell on the experts held here (rows of the grouped matmuls); the
# busiest local expert's rows, summed; router calls (layers x programs)
COUNT_NAMES = ("picks", "zero_picks", "local_rows", "busiest_rows",
               "router_calls")
# and, after them, where the router has groups: valid tokens scored (summed
# over layers); those whose kept groups include a group this chip holds
# experts of
GROUP_COUNT_NAMES = ("router_tokens", "group_hit_tokens")
# and, last, where the experts' grouped matmuls are the kernel
# (`_use_expert_kernel`: the chip), how it engaged, summed over layers and
# passes: the expert-weight fetches its grid made, in units of one expert's
# whole weight (`ops.grouped_matmul.weight_fetches`), the experts a pass
# reached, and the live (expert, row tile) items of its list.  Fetches over
# reached is 1 where every reached expert's weights were read once a matmul;
# items over reached is the grid steps an expert's weights stay for (1 at a
# few rows an expert; `ceil(rows / tile)` where the segments lie on tile
# edges, about one more where they lie end to end)
KERNEL_COUNT_NAMES = ("expert_weight_fetches", "experts_reached",
                      "expert_items")
# serve steps between two drains of it (`ServeLoop`: one small fetch)
COUNT_DRAIN_STEPS = 16


class Router(NamedTuple):
    """What a router does with its logits `[T, experts + identity]`."""
    scores: str              # "softmax" | "sigmoid" of the logits
    bias: bool               # a bias buffer enters the selection only
    groups: int              # the experts in this many equal groups (0: none)
    groups_kept: int         # ... of which the best few may be picked from
    renormalise: bool        # the picks' weights sum to 1 before the scale
    scale: float
    identity: int            # outputs past the experts that return their input


def router_of(cfg: TransformerConfig) -> Router:
    return Router(cfg.moe_router_scores, cfg.moe_router_bias,
                  cfg.moe_router_groups, cfg.moe_router_groups_kept,
                  cfg.moe_norm_topk_prob, cfg.moe_routed_scaling,
                  cfg.moe_zero_experts)


def count_names(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of `moe_counts`' entries: its length follows the router."""
    return (COUNT_NAMES + (GROUP_COUNT_NAMES if cfg.moe_router_groups else ())
            + (KERNEL_COUNT_NAMES if _use_expert_kernel() else ()))


def _use_expert_kernel() -> bool:
    """The experts' grouped matmuls are `ops/grouped_matmul.py` on the chip
    and three `lax.ragged_dot` calls elsewhere (the kernel's reference):
    the platform's choice, as `_use_latent_kernel`'s."""
    from ...utils.device import on_tpu
    return on_tpu()


def init_latent_arena(cfg: TransformerConfig, num_blocks: int,
                      block_size: int):
    width = -(-cfg.latent_width // 128) * 128
    return {"c": jnp.zeros((cfg.latent_attentions, num_blocks, block_size,
                            width), cfg.dtype),
            "moe_counts": jnp.zeros((len(count_names(cfg)),), jnp.int32)}


def refuse_lora(lora) -> None:
    if lora is not None:
        raise NotImplementedError(
            "LoRA adapters are not wired into the latent (MLA) block or "
            "the static-kind stack")


def _rms(x, scale, eps: float, mult: float = 1.0):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * (scale.astype(jnp.float32) * mult)).astype(x.dtype)


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
# the router and this chip's share of the experts
# ----------------------------------------------------------------------
def local_rows_cap(assignments: int, local: int, outputs: int) -> int:
    """Rows of the compact buffer the grouped matmuls run over: four
    times the share of `assignments` that even routing sends to `local`
    of `outputs` router outputs, in steps of 16, never more than all of
    them.  A step whose local assignments pass it runs the buffer again
    for the rest (exact either way; absent experts never cost a row)."""
    even = 4 * assignments * local / outputs
    return min(assignments, max(16, -(-int(even) // 16) * 16))


def _route(r: Router, logits, bias, k: int):
    """The router's one reading of its description.  logits [T, experts +
    r.identity] float32 -> (picks [T, k] int32, their weights [T, k]
    float32, kept [T, r.groups] bool: the groups a token may pick from, or
    None without groups)."""
    score = (jax.nn.sigmoid(logits) if r.scores == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    choose = score
    if r.bias:                         # the bias picks, it does not weigh
        choose = score + bias.astype(jnp.float32)
    kept = None
    if r.groups:
        with jax.named_scope("router_groups"):
            T, outputs = logits.shape
            per = (outputs - r.identity) // r.groups
            # a group counts by the sum of its 2 best biased scores
            best2, _ = jax.lax.top_k(choose.reshape(T, r.groups, per), 2)
            _, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), r.groups_kept)
            kept = jnp.any(
                keep[:, :, None] == jnp.arange(r.groups)[None, None], axis=1)
            choose = jnp.where(jnp.repeat(kept, per, axis=1), choose,
                               -jnp.inf)
    _, topi = jax.lax.top_k(choose, k)                        # [T, k]
    weight = jnp.take_along_axis(score, topi, axis=1)
    if r.renormalise:
        weight = weight / jnp.maximum(
            jnp.sum(weight, axis=1, keepdims=True), 1e-9)
    return topi, weight * r.scale, kept


def _moe(cfg: TransformerConfig, lp, experts, li, h, tok_valid,
         router_in=None):
    """h [T, H] -> (MoE(h) [T, H] over the experts held here and the
    identity experts, counts [len(count_names(cfg))] int32).  `experts`:
    the whole `[layers * local, ...]` stacks; `li`: the layer at hand
    among them; `router_in` [T, H]: what the router scores where that is
    not `h` (a router on the layer's input).  The experts' gate is ReLU
    for `reglu`, else SiLU."""
    # (imported here, as the latent kernel is: Pallas loads while the device
    # is still seeding weights, not before the process has dispatched a thing)
    from ...ops import grouped_matmul
    T, H = h.shape
    dt, k = h.dtype, cfg.moe_top_k
    E, first, El = cfg.moe_experts, cfg.moe_expert_first, cfg.local_experts
    gate_act = jax.nn.relu if cfg.activation == "reglu" else jax.nn.silu
    r = router_of(cfg)
    with jax.named_scope("router"):
        logits = (h if router_in is None else router_in).astype(
            jnp.float32) @ lp["moe_gate"].astype(jnp.float32)
        topi, weight, kept = _route(r, logits, lp.get("moe_router_bias"), k)
    if cfg.moe_zero_experts:
        with jax.named_scope("zero_experts"):
            is_zero = topi >= E
            zero_part = jnp.sum(jnp.where(is_zero, weight, 0.0), axis=1,
                                keepdims=True) * h.astype(jnp.float32)
    else:
        is_zero, zero_part = jnp.zeros_like(topi, bool), 0.0
    with jax.named_scope("experts"):
        ids, wf = topi.reshape(-1), weight.reshape(-1)        # [T * k]
        picked = jnp.repeat(tok_valid, k)
        local = (ids >= first) & (ids < first + El) & picked
        key = jnp.where(local, ids - first, El)
        cap = local_rows_cap(T * k, El, E + cfg.moe_zero_experts)
        # the buffer holds every assignment (all the router's experts are
        # held here, or the program is tiny): one piece, no overflow
        whole = cap == T * k
        kernel = _use_expert_kernel()
        tile = grouped_matmul.row_tile(cap)
        # each expert's rows from a row-tile edge, in a longer buffer: what
        # the kernel's pass over a whole prompt's assignments wants, and a
        # fact of the program's shape
        aligned = kernel and grouped_matmul.aligns(cap, tile, El, whole)
        # local rows first, by expert (`aligned`: with padding rows among
        # them, which name no assignment)
        order = grouped_matmul.sort_rows(key, El, tile, aligned)
        sizes = jnp.bincount(key, length=El + 1).astype(jnp.int32)[:El]
        n_local = jnp.sum(sizes)
        ends = jnp.cumsum(sizes)
        if not whole:
            order = jnp.pad(order, (0, cap))  # a window never slides back
        every = jnp.zeros((experts["w_up"].shape[0],), jnp.int32)

        def outputs(sel, part):
            """The experts' outputs for the sorted assignments `sel` (`cap`
            of them end to end; the aligned buffer's rows where the
            segments lie on tile edges), `part` of them each expert's:
            (their tokens, the down projections' products [rows, H]
            float32, the kernel's three counts where it runs).  Rows of no
            expert's segment belong to no expert held here and hold
            anything."""
            # `ragged_dot`'s groups of the whole stack: empty outside this
            # layer
            groups = None if kernel else jax.lax.dynamic_update_slice(
                every, part, (li * El,))
            tok = sel // k
            if aligned:
                # a padding row reads the last token; so no index leaves `h`,
                # and the default's fill is a select pass over every row
                tok = jnp.minimum(tok, T - 1)
                xs = h.at[tok].get(mode="promise_in_bounds")
            else:
                xs = jnp.take(h, tok, axis=0)
            if kernel:
                # the live (expert of the whole stack, row tile) items
                items = grouped_matmul.list_items(part, cap, tile, li * El,
                                                  aligned=aligned)
                act = grouped_matmul.grouped_matmul(
                    xs, (experts["w_gate_proj"], experts["w_up"]), items,
                    tile=tile, gate_act=gate_act, out_dtype=dt)
                down = grouped_matmul.grouped_matmul(
                    act, (experts["w_down"],), items, tile=tile)
                return tok, down, jnp.stack([
                    grouped_matmul.weight_fetches(items),
                    jnp.sum(part > 0).astype(jnp.int32), items.count[0]])
            g = jax.lax.ragged_dot(xs, experts["w_gate_proj"], groups,
                                   preferred_element_type=jnp.float32)
            u = jax.lax.ragged_dot(xs, experts["w_up"], groups,
                                   preferred_element_type=jnp.float32)
            act = (gate_act(g) * u).astype(dt)
            return tok, jax.lax.ragged_dot(
                act, experts["w_down"], groups,
                preferred_element_type=jnp.float32), ()

        if whole:
            # every (token, pick) has its row in the buffer, so the k
            # outputs of a token are gathered, not scatter-added
            _, down, engaged = outputs(order, sizes)
            with jax.named_scope("combine"):
                # the row of the sorted buffer that holds pick j of token
                # t: `order`'s inverse, a permutation (never out of bounds;
                # the padding rows' places come last and are left out)
                pos = jnp.argsort(order)
                if aligned:
                    pos = pos[:T * k]
                pos = pos.reshape(T, k)
                mine = local.reshape(T, k)
                routed = jnp.zeros((T, H), jnp.float32)
                for j in range(k):
                    rows = down.at[pos[:, j]].get(mode="promise_in_bounds")
                    # `where`, not a product with 0: a row no expert held
                    # here wrote may hold anything
                    routed = routed + jnp.where(
                        mine[:, j, None], weight[:, j, None] * rows, 0.0)
        else:
            def piece(i, carry):
                """Rows [i * cap, (i + 1) * cap) of the sorted
                assignments."""
                acc, engaged = carry   # the kernel's counts, where it runs
                lo = i * cap
                sel = jax.lax.dynamic_slice(order, (lo,), (cap,))
                part = (jnp.clip(ends, lo, lo + cap)
                        - jnp.clip(ends - sizes, lo, lo + cap))  # per expert
                tok, down, reached = outputs(sel, part)
                engaged = engaged + reached      # () + () off the chip
                # rows past the last group belong to no expert held here
                mine = (lo + jnp.arange(cap) < n_local)[:, None]
                return acc.at[tok].add(
                    jnp.where(mine, down * wf[sel][:, None], 0.0)), engaged

            # one piece unless routing piles more than `cap` rows on this
            # share
            routed, engaged = jax.lax.fori_loop(
                0, (n_local + cap - 1) // cap, piece,
                (jnp.zeros((T, H), jnp.float32),
                 jnp.zeros((len(KERNEL_COUNT_NAMES),), jnp.int32)
                 if kernel else ()))
    picked = picked.reshape(T, k)
    counts = [jnp.sum(picked), jnp.sum(picked & is_zero), n_local,
              jnp.max(sizes), jnp.ones((), jnp.int32)]
    if r.groups:
        per = E // r.groups          # the groups this share has experts of
        mine = kept[:, first // per:(first + El - 1) // per + 1]
        counts += [jnp.sum(tok_valid),
                   jnp.sum(tok_valid & jnp.any(mine, axis=1))]
    counts += list(engaged)
    counts = jnp.stack(counts).astype(jnp.int32)
    return (routed + zero_part).astype(dt), counts


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
    moe = SubBlock(False, True, bool(cfg.moe_shared_expert_ffn),
                   ("y", "o", "real"))
    return ((("dense_layers", 0, Ld, (dense,)),) if Ld else ()) + (
        ("layers", Ld, L - Ld, (moe,)),)


def _rows(fn, n, ins, extra, row_tile: int = 0):
    """Token-wise work over the first `n` rows of `ins` ([T, ...] arrays,
    real rows in front), `row_tile` (`ROW_TILE`) rows at a time for as many
    passes as `n` needs; rows no pass reached come out zero.  `fn(*tile_ins)
    -> (row outputs, a summand for `extra`)`.  A program of at most
    a tile's rows (or not whole tiles) takes them all at once."""
    T = ins[0].shape[0]
    row_tile = row_tile or ROW_TILE
    tile = row_tile if T > row_tile and T % row_tile == 0 else T
    if tile == T:
        outs, e = fn(*ins)
        return outs, extra + e
    shapes, _ = jax.eval_shape(fn, *[
        jax.ShapeDtypeStruct((tile,) + a.shape[1:], a.dtype) for a in ins])

    def one(i, carry):
        outs, extra = carry
        lo = i * tile
        part, e = fn(*[jax.lax.dynamic_slice_in_dim(a, lo, tile)
                       for a in ins])
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, p, lo, 0)
                     for o, p in zip(outs, part)), extra + e

    return jax.lax.fori_loop(
        0, (n + tile - 1) // tile, one,
        (tuple(jnp.zeros((T,) + s.shape[1:], s.dtype) for s in shapes),
         extra))


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
    # more rows than a pass takes: the real ones go in front (`_rows`)
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
            cq = _rms(_dense(t, sp["wq_a"]), sp["q_a_norm_scale"],
                      cfg.norm_eps, s_q)
            q = _dense(cq, sp["wq_b"]).reshape(-1, NH, dn + dr)
            q = jnp.concatenate(
                [q[..., :dn],
                 _rope_pairs(q[..., dn:], pos, cfg.rope_theta, yarn)], -1)
            ckv = _dense(t, sp["wkv_a"])
            c = _rms(ckv[:, :rank], sp["kv_a_norm_scale"], cfg.norm_eps, s_kv)
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
                return project(sp, _rms(x, sp["attn_norm_scale"],
                                        cfg.norm_eps), pos), none

            def closing(k: int):
                """Stage k + 1, on the operands `subs[k].reads`."""
                sub, sp = subs[k], lp["sub"][k]

                def stage(*ins):
                    v = dict(zip(sub.reads, ins))
                    a = v["y"] + out_proj(sp, v["o"])
                    h = _rms(a, sp["mlp_norm_scale"], cfg.norm_eps)
                    m, c = v.get("m"), none
                    if sub.moe:
                        m, c = _moe(cfg, lp, experts, li, h, v["real"])
                        if sub.shared:
                            with jax.named_scope("shared_expert"):
                                m = _plain_mlp(cfg, lp["shared"], h) + m
                    y = a + ffn(sp, h) if sub.dense_ffn else a
                    if k == last:
                        return (y if m is None else y + m,), c
                    nxt = lp["sub"][k + 1]
                    q, row = project(nxt, _rms(y, nxt["attn_norm_scale"],
                                               cfg.norm_eps), v["pos"])
                    return (y,) + (() if m is None else (m,)) + (q, row), c
                return stage

            (q, row), _ = _rows(before, n, (x, pos), none)
            held = {"y": x, "pos": pos, "real": real}
            for k, sub in enumerate(subs):
                held["o"], arena_c = attend(lp["sub"][k], arena_index(li, k),
                                            q, row, arena_c)
                outs, e = _rows(closing(k), n,
                                tuple(held[name] for name in sub.reads),
                                counts if sub.moe else none)
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


def prefill_full(cfg, params, arena, tokens, lens, block_tables, active):
    """`ragged_ops.prefill_full` for a latent model (same contract)."""
    NS, S = tokens.shape
    lens = jnp.where(active, lens, 0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (NS, S))
    x, arena = _forward(cfg, params, arena, tokens, positions,
                        positions < lens[:, None], block_tables, "fresh")
    return (*_last_logits(cfg, params, x, lens - 1), arena)


def prefill_chunks(cfg, params, arena, tokens, pos0s, n_valids,
                   block_tables, active):
    """`ragged_ops.prefill_chunks` for a latent model (same contract)."""
    C = tokens.shape[1]
    pos0s = jnp.where(active, pos0s, 0)
    n_valids = jnp.where(active, n_valids, 0)
    positions = pos0s[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    valid = jnp.arange(C)[None] < n_valids[:, None]
    x, arena = _forward(cfg, params, arena, tokens, positions, valid,
                        block_tables, "cached")
    return (*_last_logits(cfg, params, x, n_valids - 1), arena)


def decode_core(cfg, params, arena, tokens, seq_lens, block_tables, active):
    """`ragged_ops._decode_core` for a latent model: (logits, arena)."""
    x, arena = _forward(cfg, params, arena, tokens[:, None],
                        seq_lens[:, None], active[:, None], block_tables,
                        "decode")
    return _lm_logits(cfg, params, x[:, 0]), arena
