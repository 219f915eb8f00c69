"""The routed-expert FFN of every served family that has one: the router,
this chip's share of the experts and what brings their outputs back to their
tokens.  `latent_ops` (LongCat-Flash, DeepSeek-V3), `hybrid_ops` (SmallThinker)
and `ssm_ops` (Granite-4.0-H) call `moe` from their layer bodies and run their
token-wise stages through `rows`.

The router is a description (`Router`, `router_of(cfg)`) read by one
function (`route`): softmax or sigmoid scores, a selection bias, the
choice limited to the best few of equal expert groups, weights renormalised
or not and scaled, identity outputs; the families' routers are values of
it.  The MoE holds a SHARE of the routed experts (`cfg.moe_expert_first`,
`cfg.local_experts`): it routes over every router output, runs grouped
matmuls over the assignments to its own experts only, adds the identity
experts' part for every token, and leaves the absent experts' part out
(another chip's work; nothing stands in for it).

The caller holds this chip's experts `[layers * local, ...]` OUTSIDE its
layer scan: the grouped matmuls take the whole stack (a per-layer slice
handed to a custom call is first copied, 1.2 GB a layer at LongCat's cell's
size: measured 29 of a 51 ms decode step).  On the chip they are
`ops/grouped_matmul.py` over the live (expert of the whole stack, row tile)
items of the layer at hand, gate and up in one pass; elsewhere three
`lax.ragged_dot` calls with group sizes that are zero outside the layer
(`use_expert_kernel`: the platform's choice).  Their float32 outputs come
back to their tokens by one gather a pick and a sum over the picks in a
fixed order (scope `experts/combine`) where the compact buffer holds every
assignment (`cap == T * k`: a fact of the program's shape), and by the
pieces' scatter-add where it holds a share.  Where it holds them all, the
kernel runs and an even routing gives an expert a row tile's rows or more (a
prompt's pass, not a decode step: `grouped_matmul.aligns`, shapes again),
each expert's rows start on a tile edge in a longer buffer, so that no
(expert, tile) item multiplies another expert's rows, and a pick's row is
found behind its expert's padding.

Chunk slots are padded, so what works token by token runs over the real
tokens only: a program of more than a family's `ROW_TILE` rows moves the
real ones to the front and takes them a tile at a time, for as many passes
as they need (`rows`; 1024, 4096 and 512 rows are the three families'
measured tiles, each in its own module).

Rider `moe_counts` ([len(count_names(cfg))] int32) of the families' arenas
accumulates what the router did, for `InferenceEngineV2.drain_moe_counts`.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig

__all__ = ["COUNT_NAMES", "GROUP_COUNT_NAMES", "KERNEL_COUNT_NAMES",
           "COUNT_DRAIN_STEPS", "Router", "router_of", "route", "count_names",
           "use_expert_kernel", "local_rows_cap", "moe", "rows", "rms"]

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
# (`use_expert_kernel`: the chip), how it engaged, summed over layers and
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
            + (KERNEL_COUNT_NAMES if use_expert_kernel() else ()))


def use_expert_kernel() -> bool:
    """The experts' grouped matmuls are `ops/grouped_matmul.py` on the chip
    and three `lax.ragged_dot` calls elsewhere (the kernel's reference):
    the platform's choice, as `latent_ops._use_latent_kernel`'s."""
    from ...utils.device import on_tpu
    return on_tpu()


def rms(x, scale, eps: float, mult: float = 1.0):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (out * (scale.astype(jnp.float32) * mult)).astype(x.dtype)


def local_rows_cap(assignments: int, local: int, outputs: int) -> int:
    """Rows of the compact buffer the grouped matmuls run over: four
    times the share of `assignments` that even routing sends to `local`
    of `outputs` router outputs, in steps of 16, never more than all of
    them.  A step whose local assignments pass it runs the buffer again
    for the rest (exact either way; absent experts never cost a row)."""
    even = 4 * assignments * local / outputs
    return min(assignments, max(16, -(-int(even) // 16) * 16))


def route(r: Router, logits, bias, k: int):
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


def moe(cfg: TransformerConfig, lp, experts, li, h, tok_valid,
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
        topi, weight, kept = route(r, logits, lp.get("moe_router_bias"), k)
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
        kernel = use_expert_kernel()
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


def rows(fn, n, ins, extra, row_tile: int):
    """Token-wise work over the first `n` rows of `ins` ([T, ...] arrays,
    real rows in front), `row_tile` (the family's `ROW_TILE`) rows at a time
    for as many passes as `n` needs; rows no pass reached come out zero.
    `fn(*tile_ins) -> (row outputs, a summand for `extra`)`.  A program of at
    most a tile's rows (or not whole tiles) takes them all at once."""
    T = ins[0].shape[0]
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
