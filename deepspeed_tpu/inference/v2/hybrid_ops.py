"""The static-kind stack (window layers with rope, global layers without a
position encoding, a router on the layer's input, ReLU-gated experts) over
a TWO-KIND paged cache: what `ragged_ops`' layer bodies run for a
`TransformerConfig` with `rope_layers` (SmallThinker).

A layer, kinds static per position of the period (`cfg.layer_period`):

    r = x W_router (float32; the layer's INPUT)
    a = x + Attn(rms(x)) W_o     rope on q, k where the layer rotates;
                                 keys within the window where it has one
    out = a + sum_j p_j E_j(rms(a)),  (p, e) = softmax(top_k(r)),
    E(h) = W_down (relu(W_gate h) * W_up h)

The layer scan runs over whole periods with the period's layers unrolled
in the body, so every attention call sees its window and its rope flag as
Python values.

The cache holds the two kinds apart: `gk`/`gv` `[Lg, blocks_g, bs, NKV,
D]` over the global layers and `wk`/`wv` `[Lw, blocks_w, bs, NKV, D]` over
the window layers, each with its own block ids and its own table a row
(`block_tables` `[rows, 2, MB]`: global, window).  A window-kind table
keeps the entries of the blocks that still hold a key some later query
can see; the entries behind them are dead (-1): nothing reads them (the
decode kernel's walk starts at the window's first block) and a key whose
entry is dead is written nowhere.  `kind_pools` sizes the two.

- decode (`decode_core`): the new key goes to the arena, then
  `ops/paged_attention.py` with the kind's table and window (on the CPU:
  its dense reference);
- prompt chunks (`prefill_chunks`; a fresh prompt is a chunk at position
  0): a row's keys are laid out by position (its past gathered through
  the table, the chunk's own keys laid in) and go with the chunk's
  queries through `ops/chunk_attention.py`, which skips the key tiles
  outside the window; the chunk's keys are written to the blocks the row
  still holds.  There is no separate fresh-prompt program
  (`prefill_full_supported` is False).

Chunk slots are padded, so everything token-wise runs over the real
tokens only, `ROW_TILE` at a time (`expert_ffn.rows`).  The experts lie
outside the scan and the router's counters ride the arena (`moe_counts`):
`expert_ffn.moe`, as for every family with experts.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig, _rope
from .expert_ffn import count_names, moe, rms, rows
from .ragged_ops import (_dense, _embed, _gate_fused, _kernel_capable,
                         _lm_logits, greedy_tokens)

__all__ = ["ROW_TILE", "kind_layers", "kind_pools", "window_blocks",
           "init_kinds_arena", "manager_pools", "chunk_account",
           "step_account", "prefill_chunks", "decode_core"]

# rows a token-wise pass takes at once: every pass reads the weights of
# every expert that has a row, so a pass is as large as its float32
# temporaries allow
ROW_TILE = 4096


def kind_layers(cfg: TransformerConfig) -> Tuple[int, int]:
    """(global layers, window layers) of the stack."""
    n_window = sum(1 for w in cfg.sliding_window_layers if w)
    return cfg.num_layers - n_window, n_window


def window_blocks(cfg: TransformerConfig, block_size: int) -> int:
    """Window-kind blocks a row can hold at once: the window's, and one
    for the block being written."""
    return -(-cfg.window // block_size) + 1


def kind_pools(cfg: TransformerConfig, num_blocks: int, block_size: int,
               max_seqs: int) -> Tuple[int, int]:
    """(global-kind blocks, window-kind blocks) of an arena whose byte
    budget is that of `num_blocks` one-kind blocks (a block of every
    layer).  The window kind takes the steady share of `max_seqs` rows and
    one row's worth to spare, never more than half the budget; the global
    kind takes the rest.  (Through a step that continues its prompt a row
    holds the blocks it reads beside those it writes, up to two shares
    where a chunk is at least a window long: the spare is what lets one
    such chunk a step go through whole, and `DSStateManager.chunk_room`
    cuts what the pool cannot take.)"""
    Lg, Lw = kind_layers(cfg)
    budget = num_blocks * cfg.num_layers
    per_row = window_blocks(cfg, block_size)
    nb_w = max(1, min((max_seqs + 1) * per_row, budget // (2 * Lw)))
    return max(1, (budget - nb_w * Lw) // Lg), nb_w


def init_kinds_arena(cfg: TransformerConfig, num_blocks: int,
                     block_size: int, max_seqs: int):
    Lg, Lw = kind_layers(cfg)
    nb_g, nb_w = kind_pools(cfg, num_blocks, block_size, max_seqs)
    shape = (block_size, cfg.kv_heads, cfg.head_dim)
    zeros = lambda n, nb: jnp.zeros((n, nb) + shape, cfg.dtype)  # noqa: E731
    return {"gk": zeros(Lg, nb_g), "gv": zeros(Lg, nb_g),
            "wk": zeros(Lw, nb_w), "wv": zeros(Lw, nb_w),
            "moe_counts": jnp.zeros((len(count_names(cfg)),), jnp.int32)}


def manager_pools(cfg: TransformerConfig, arena, config):
    """(blocks, window, state slots) for `DSStateManager`: the arena has
    divided `config.num_blocks`, its byte budget, by the model's kinds and
    `max_seqs` (`kind_pools`), and the ledger counts the pools it made."""
    return (arena["gk"].shape[1], (cfg.window, arena["wk"].shape[1]), 0)


def _chunk_keys(MB: int, bs: int, S: int) -> Tuple[int, int]:
    """(length, key tile) of the buffer a chunk program lays a row's keys
    out in by position: its table's blocks, then room for a chunk that
    starts in the last of them, in whole key tiles."""
    from ...ops.chunk_attention import key_tile
    bk = key_tile(MB * bs + S)
    return -(-(MB * bs + S) // bk) * bk, bk


def chunk_account(engine, pos0s, n_valids) -> dict:
    """The live and the masked key steps of `ops/chunk_attention.py` in the
    chunk program just dispatched (slots at `pos0s` with `n_valids` real
    tokens each), summed over the layers of both kinds, a kv head (numpy on
    the host, by the kernel's own rule): the steps that compute, and of
    those the ones an edge crosses, which pay for the mask.  Attributes of
    the program's `engine.dispatch` span: how often the kernel's mask-free
    body runs."""
    from ...ops.chunk_attention import count_steps
    cfg, S = engine.cfg, engine.config.prefill_chunk_size
    G, bk = cfg.num_heads // cfg.kv_heads, _chunk_keys(
        engine.config.max_blocks_per_seq, engine.config.block_size, S)[1]
    live = masked = 0
    for layers, window in zip(kind_layers(cfg), (None, cfg.window)):
        l, m = count_steps(pos0s, n_valids, S, G, bk, window)
        live, masked = live + layers * l, masked + layers * m
    return dict(attn_steps_live=live, attn_steps_masked=masked)


def step_account(engine, pending, batch) -> None:
    """A step's account of the two-kind cache from its decode rows `batch`
    (none: zeros), in block x layer units: what the rows hold of both
    kinds, what one kind over all layers would hold for them, the
    window-kind blocks handed back since the last account; and the live
    entries of BOTH kinds' tables (`pending.kv_live_blocks` comes with the
    global kind's)."""
    pending.kv_kinds = dict.fromkeys(
        ("kv_blocks_held", "kv_blocks_full_cache", "kv_window_released"), 0)
    if not batch:
        return
    state = engine.state
    Lg, Lw = engine.arena["gk"].shape[0], engine.arena["wk"].shape[0]
    bs, W = engine.config.block_size, engine.cfg.window
    # (a row's query of this step stood at `seen_tokens - 1`)
    pending.kv_live_blocks += sum(
        (d.seen_tokens - 1) // bs - max(0, d.seen_tokens - W) // bs + 1
        for d in batch)
    pending.kv_kinds.update(
        kv_blocks_held=sum(
            Lg * len(d.blocks) + Lw * len(d.window_blocks) for d in batch),
        kv_blocks_full_cache=sum((Lg + Lw) * len(d.blocks) for d in batch),
        kv_window_released=state.window_released - state.window_reported)
    state.window_reported = state.window_released


def _use_kernels(cfg: TransformerConfig, bs: int) -> bool:
    return _gate_fused(
        cfg, _kernel_capable(cfg, cfg.head_dim, bs, 1, static_windows=True),
        reason=f"attn_impl='pallas' requested but the paged decode and "
               f"chunk attention kernels cannot run here (need TPU, "
               f"head_dim % 64 == 0 [got {cfg.head_dim}], block_size % 8 "
               f"== 0 [got {bs}])")


def _forward(cfg: TransformerConfig, params, arena, tokens, positions, valid,
             block_tables, decode: bool):
    """tokens/positions/valid [R, S] (a row's real tokens first);
    block_tables [R, 2, MB].  Returns (hidden states [R, S, H], arena)."""
    R, S = tokens.shape
    H, T, dt = cfg.hidden_size, R * S, cfg.dtype
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    period = cfg.layer_period
    P, L, E = len(period), cfg.num_layers, cfg.local_experts
    bs = arena["gk"].shape[2]
    MB = block_tables.shape[2]
    fused = _use_kernels(cfg, bs)
    experts = {n: w.reshape((L * E,) + w.shape[2:]).astype(dt)
               for n, w in params["experts"].items()}
    pos0, n_valid = positions[:, 0], jnp.sum(valid, axis=1)

    def slots(kind: int):
        """The block every token's key is written to, [T]: the kind's
        pool size (out of range: dropped) where the token is padding or
        its table entry is dead."""
        nb = arena["wk" if kind else "gk"].shape[1]
        entry = jnp.take_along_axis(
            block_tables[:, kind], jnp.clip(positions // bs, 0, MB - 1),
            axis=1)
        return jnp.where(valid & (entry >= 0), entry, nb).reshape(T)

    blk = (slots(0), slots(1))
    off, pos, real = (positions % bs).reshape(T), positions.reshape(T), \
        valid.reshape(T)
    toks, n = tokens.reshape(T), jnp.sum(valid)
    # more rows than a pass takes: the real ones go in front (`rows`)
    compact = T > ROW_TILE and T % ROW_TILE == 0
    if compact:
        order = jnp.argsort(~real, stable=True)
        back = jnp.argsort(order)
        toks, pos, real, off = (a[order] for a in (toks, pos, real, off))
        blk = tuple(b[order] for b in blk)
    in_rows = lambda a: (a[back] if compact else a).reshape(  # noqa: E731
        (R, S) + a.shape[1:])
    in_line = lambda a: a.reshape((T,) + a.shape[2:])[order] \
        if compact else a.reshape((T,) + a.shape[2:])  # noqa: E731
    none = jnp.zeros((), jnp.int32)
    x = _embed(cfg, params, toks, pos)                            # [T, H]
    # the chunk programs lay a row's keys out by position: its table's
    # blocks, then room for a chunk that starts in the last of them
    if not decode:
        room, _ = _chunk_keys(MB, bs, S)

    def attend(kind: int, index, window, q, k, v, ak, av):
        """Attention proper of a layer of kind `kind` (0 global, 1 window)
        on projected [T, ...] rows: (heads' outputs [T, NH * D], arena
        pair)."""
        with jax.named_scope("kv_write"):
            ak = ak.at[index, blk[kind], off].set(k, mode="drop")
            av = av.at[index, blk[kind], off].set(v, mode="drop")
        table = block_tables[:, kind]
        if decode:
            lens = jnp.where(valid[:, 0], pos0, -1)
            if fused:
                from ...ops.paged_attention import paged_decode_attention
                o = paged_decode_attention(q, ak, av, table, lens,
                                           layer_idx=index, window=window)
            else:
                from ...ops.paged_attention import paged_decode_reference
                o = paged_decode_reference(q, ak[index], av[index], table,
                                           lens, window=window)
            return o.reshape(T, NH * D), ak, av
        from ...ops import chunk_attention as ca
        q, k, v = (in_rows(a) for a in (q, k, v))
        idx = jnp.clip(table, 0, ak.shape[1] - 1)

        def by_position(arena_l, new, p0):
            """A row's keys by position: the table's blocks, the chunk's
            own keys laid in at its first position."""
            old = jnp.take(arena_l, idx, axis=0).reshape(R, MB * bs, NKV, D)
            old = jnp.pad(old, ((0, 0), (0, room - MB * bs), (0, 0), (0, 0)))
            return jax.vmap(lambda o, c, p: jax.lax.dynamic_update_slice(
                o, c, (p, 0, 0)))(old, new, p0)

        kk, vv = by_position(ak[index], k, pos0), \
            by_position(av[index], v, pos0)
        fn = ca.chunk_attention if fused else ca.chunk_attention_reference
        o = fn(q, kk, vv, pos0, n_valid, window=window)
        return in_line(o.reshape(R, S, NH * D)), ak, av

    def layer(x, lp, li, kind: int, index, window, rotate, ak, av, counts):
        scope = "attn_window" if kind else "attn_global"

        def before(x, pos):
            with jax.named_scope(scope):
                h = rms(x, lp["attn_norm_scale"], cfg.norm_eps)
                q = _dense(h, lp["wq"]).reshape(-1, NH, D)
                k = _dense(h, lp["wk"]).reshape(-1, NKV, D)
                v = _dense(h, lp["wv"]).reshape(-1, NKV, D)
                if rotate:
                    q = _rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
                    k = _rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            return (q, k, v), none

        def after(x, o, real):
            with jax.named_scope(scope):
                a = x + _dense(o, lp["wo"])
            h = rms(a, lp["mlp_norm_scale"], cfg.norm_eps)
            # the router reads the layer's input, not the experts' `h`
            m, c = moe(cfg, lp, experts, li, h, real, router_in=x)
            return (a + m,), c

        (q, k, v), _ = rows(before, n, (x, pos), none, ROW_TILE)
        with jax.named_scope(scope):
            o, ak, av = attend(kind, index, window, q, k, v, ak, av)
        (x,), counts = rows(after, n, (x, o, real), counts, ROW_TILE)
        return x, ak, av, counts

    # a period's layers by kind (0 global, 1 window): which of its kind's
    # layers each is, and how many of each kind a period has
    kinds = [int(bool(w)) for w, _ in period]
    nth = [kinds[:j].count(k) for j, k in enumerate(kinds)]
    per_kind = (kinds.count(0), kinds.count(1))

    def one_period(carry, xs):
        x, counts, *caches = carry            # caches: [(gk, gv), (wk, wv)]
        lps, pi = xs
        for j, (w, rotate) in enumerate(period):
            k = kinds[j]
            x, ak, av, counts = layer(
                x, jax.tree.map(lambda a: a[j], lps), pi * P + j, k,
                pi * per_kind[k] + nth[j], w or None, bool(rotate),
                *caches[k], counts)
            caches[k] = (ak, av)
        return (x, counts, *caches), None

    by_period = jax.tree.map(
        lambda a: a.reshape((L // P, P) + a.shape[1:]), params["layers"])
    (x, counts, (gk, gv), (wk, wv)), _ = jax.lax.scan(
        one_period,
        (x, arena["moe_counts"], (arena["gk"], arena["gv"]),
         (arena["wk"], arena["wv"])),
        (by_period, jnp.arange(L // P)))
    return in_rows(x), {**arena, "gk": gk, "gv": gv, "wk": wk, "wv": wv,
                        "moe_counts": counts}


def prefill_chunks(cfg, params, arena, tokens, pos0s, n_valids,
                   block_tables, active, slots=None, **uniform_only):
    """`ragged_ops.prefill_chunks` for a static-kind stack (same contract;
    `block_tables` [NC, 2, MB])."""
    C = tokens.shape[1]
    pos0s = jnp.where(active, pos0s, 0)
    n_valids = jnp.where(active, n_valids, 0)
    positions = pos0s[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    valid = jnp.arange(C)[None] < n_valids[:, None]
    x, arena = _forward(cfg, params, arena, tokens, positions, valid,
                        block_tables, decode=False)
    xl = x[jnp.arange(x.shape[0]), jnp.clip(n_valids - 1, 0, C - 1)]
    logits = _lm_logits(cfg, params, xl)
    return logits, greedy_tokens(logits), arena


def decode_core(cfg, params, arena, tokens, seq_lens, block_tables, active,
                slots=None, **uniform_only):
    """`ragged_ops._decode_core` for a static-kind stack: (logits,
    arena)."""
    x, arena = _forward(cfg, params, arena, tokens[:, None],
                        seq_lens[:, None], active[:, None], block_tables,
                        decode=True)
    return _lm_logits(cfg, params, x[:, 0]), arena
