"""The state-space family (Mamba-2 mixers and grouped-query attention in
layers of statically known KINDS, then a gated MLP or routed experts;
scalar multipliers) over paged K/V AND per-sequence recurrent state: what
`ragged_ops`' programs run for a `TransformerConfig` with `ssm_state`.

`cfg.ssm_period` is one period of layer kinds, which is the model's and
data here: "ssm" (the mixer alone), "attn" (attention alone) or "both" (the
two side by side on one input norm).  Every layer "both" is the state-space
parallel block (Falcon-H1); nine "ssm" to one "attn" is Granite-4.0-H.  A
layer, input x [T, H], `n = rms(x, g_in)`, r = `residual_multiplier`:

    mixer (kinds "ssm", "both"):
    p = (n W_in) * (ssm_in_multiplier * mup)       [z | xBC | dt], mup =
        ssm_multipliers over the columns [z | x | B | C | dt]
    xBC = silu(conv(xBC) + bias)   causal, depthwise, over the last
        `ssm_conv` positions; split x [T, NHm, P], B, C [T, G, N]
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (outer) x_t;  y_t = C_t h_t + D x_t
    y = rms_grouped(y * silu(z), g_norm)           gate first, then RMS over
        each of the G groups of channels
    m = r ssm_out_multiplier * (y W_out)
    attention (kinds "attn", "both"):
    q, k, v = n W_q, key_multiplier * (n W_k), n W_v  (n times
        attention_in_multiplier); under pos_emb "rope" rotated over the
        whole head, pairs (i, i + D/2), under "none" as projected; causal
        softmax(q k s) v, s = attention_multiplier or 1 / sqrt(D)
    a = r attention_out_multiplier * (o W_o)
    x1 = x + m + a                                 (what the kind has)
    FFN, h = rms(x1, g_ff):
    out = x1 + r mlp_multipliers[1] * ((W_up h) * silu(mlp_multipliers[0] *
        (W_gate h))) W_down                         moe_experts == 1
    out = x1 + r (sum_picks w_e E_e(h) + Shared(h))  moe_experts > 1:
        `expert_ffn.moe` over the experts held here, the shared expert
        (`moe_shared_expert_ffn`) on every token

with `embedding_multiplier` on the embedding and `lm_head_multiplier` on
the logits.  A multiplier sits on a matmul's float32 result, before the
one rounding to the stored type; the score scale rides q's projection so
(the kernels scale by 1 / sqrt(D) and q is rounded once).

Consecutive layers of one kind are one `lax.scan` (`layer_runs`), the
periods are scanned outside them; a scan of one step is its body; a layer
indexes the stacks by its place among its kind.  The
leaves of `params["layers"]` are stacked by what has them: the mixer's
(`ssm_*`) over the layers with a mixer, attention's (`wq`, `wk`, `wv`,
`wo`) over those with attention, the norms and the FFN's over all; the
routed experts lie apart (`params["experts"]` `[L, local, ...]`), outside
the scans, as `expert_ffn.moe` takes them.

The arena is sized BY KIND: attention's paged `k`/`v` `[La, blocks, bs,
NKV, D]` over the La layers with attention, and one SLOT a live sequence
over the Lm layers with a mixer: `ssm` `[Lm, slots + 1, NHm / pack, N,
pack * P]` float32 (the state transposed, channels on the lanes, heads
narrower than 128 lanes side by side: `ops/ssm.py`) and `conv` `[Lm, slots
+ 1, (ssm_conv - 1) * x|B|C]` (the convolution's tail: the inputs of the
last positions, in the stored type, which is what they were computed in;
one flat row a slot, because with a minor pair `[3, 5120]` XLA carries the
buffer through the chunk program's layer loop in a layout that pads the 3
to 128 lanes: 763 MB for 18).  A layer reads and writes its kind's row
and nothing of the other kind.  The last slot is scratch: a padded row of
the decode kernel reads and writes it.  **The state is float32**: the
recurrence rounds what it stores once a token for as many steps as a
request has, so a narrower store is a change of precision, not a layout.
With experts the router's counters ride along (`moe_counts`).

Every program takes the rows' slots beside their block tables (`slots`
[rows]; a decode batch is not in slot order).  A prompt's scan STARTS from
zeros where the row starts at position 0 and from the slot where it
continues (`prefill_chunks`; the engine plans at most one chunk of a
sequence a program, so chunk slots do not depend on each other), and ENDS
by writing the final state and tail into the slot: a leased slot needs no
clearing.  Padded positions have dt 0 (no decay, no input) and padded or
inactive rows write nothing.  `decode_core` updates the active rows' slots
in place (`ops/ssm.ssm_update`).

Scopes: `ssm` (in-projection, `ssm/conv`, `ssm/scan` or `ssm/update`,
gated norm, out-projection), `attn` (projections, rope, `kv_write`, the
attention proper, `W_o`), `dense_ffn` or `router`, `experts`,
`experts/combine`, `shared_expert`; `lm_head`.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig, _rope
from .expert_ffn import count_names, moe, rms, rows
from .ragged_ops import (_embed, _kv_write, _lm_logits, _plain_mlp,
                         _use_paged_kernel, _use_paged_prefill, greedy_tokens)

__all__ = ["ROW_TILE", "layer_runs", "init_ssm_arena", "manager_pools",
           "state_bytes_per_slot", "kv_bytes_per_token", "step_account",
           "arena_layers", "prefill_full", "prefill_chunks", "decode_core"]

# rows the experts take at once where a program has more slots than that
# (chunk slots are padded: the real rows go in front, `expert_ffn.rows`)
ROW_TILE = 512
ATTN_LEAVES = ("wq", "wk", "wv", "wo")


class Run(NamedTuple):
    """Consecutive layers of one kind within a period."""
    kind: str            # "ssm" | "attn" | "both"
    first: int           # the first one's place in the period
    count: int
    state_row: int       # ... among the period's layers with a mixer
    attn_row: int        # ... among the period's layers with attention


def layer_runs(cfg: TransformerConfig) -> Tuple[Run, ...]:
    period, runs = cfg.ssm_period, []
    for j, kind in enumerate(period):
        if runs and runs[-1].kind == kind:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(Run(kind, j, 1,
                            sum(k != "attn" for k in period[:j]),
                            sum(k != "ssm" for k in period[:j])))
    return tuple(runs)


def init_ssm_arena(cfg: TransformerConfig, num_blocks: int, block_size: int,
                   max_seqs: int):
    """Paged K/V over the layers with attention beside `max_seqs` state
    slots and one scratch slot over the layers with a mixer."""
    from ...ops.ssm import state_shape
    Lm, La = cfg.ssm_state_layers, cfg.ssm_attn_layers
    kv = (La, num_blocks, block_size, cfg.kv_heads, cfg.head_dim)
    arena = {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
             "ssm": jnp.zeros((Lm, max_seqs + 1) + state_shape(
                 cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state,
                 cfg.ssm_head_dim), jnp.float32),
             "conv": jnp.zeros((Lm, max_seqs + 1, (cfg.ssm_conv - 1)
                                * cfg.ssm_conv_width), cfg.dtype)}
    if cfg.moe_experts > 1:
        arena["moe_counts"] = jnp.zeros((len(count_names(cfg)),), jnp.int32)
    return arena


def state_bytes_per_slot(cfg: TransformerConfig) -> int:
    """Recurrent state a sequence holds over the layers with a mixer
    (state + tail)."""
    return cfg.ssm_state_layers * (
        cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4
        + (cfg.ssm_conv - 1) * cfg.ssm_conv_width
        * jnp.dtype(cfg.dtype).itemsize)


def kv_bytes_per_token(cfg: TransformerConfig) -> int:
    """K and V a token holds over the layers with attention."""
    return (2 * cfg.ssm_attn_layers * cfg.kv_heads * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)


def manager_pools(cfg: TransformerConfig, arena, config):
    """(blocks, window, state slots) for `DSStateManager`: a slot a decode
    row, so a live sequence always has one and `free_slots` counts both."""
    return config.num_blocks, None, config.max_seqs


def step_account(engine, pending, batch) -> None:
    """A step's account of per-sequence recurrent state, from its decode
    rows `batch` (none: the byte counts are 0) and the tokens they attend
    to; on every step, so that every `serve.step` span has the attributes
    (a reader sums them over the spans there are).  The slots there are and
    those live sequences hold; the bytes of state the step must read and
    write back (every row's slot, both ways) and of cache altogether (those
    and the rows' keys and values); the layers, and those whose kind holds a
    state and those whose kind holds keys, which size both."""
    cfg, slots = engine.cfg, engine.state
    state = len(batch) * 2 * state_bytes_per_slot(cfg)
    pending.state_account = dict(
        layers=cfg.num_layers, state_layers=cfg.ssm_state_layers,
        kv_layers=cfg.ssm_attn_layers, state_slots=slots.state_slots,
        state_slots_live=slots.state_slots - slots.free_state_slots,
        state_bytes_step=state,
        cache_bytes_step=state + sum(d.seen_tokens for d in batch)
        * kv_bytes_per_token(cfg))


def arena_layers(arena) -> dict:
    """What a slot and a block stand for: the arenas' rows by kind."""
    return dict(state_layers=arena["ssm"].shape[0],
                kv_layers=arena["k"].shape[0])


def _scaled(h, w, mult):
    """(h w) * mult: the multiplier (a scalar or a vector over the
    columns) on the float32 result, one rounding to h's type."""
    out = jnp.einsum("sh,hd->sd", h, w.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    return (out * mult).astype(h.dtype)


def _in_multipliers(cfg: TransformerConfig):
    """`ssm_in_multiplier * mup` over the in-projection's columns
    [z | x | B | C | dt]."""
    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.ssm_width, cfg.ssm_width, gn, gn, cfg.ssm_heads)
    return jnp.concatenate([
        jnp.full((w,), cfg.ssm_in_multiplier * m, jnp.float32)
        for w, m in zip(widths, cfg.ssm_multipliers)])


def _use_ssm_kernels(cfg: TransformerConfig) -> bool:
    """The Pallas scan and update where the chip is (their transposes
    want whole 128-lane tiles: a stored row of heads, the state's N, the
    chunk); `attn_impl='jnp'` keeps the dense forms."""
    from ...ops.ssm import state_shape
    from ...utils.device import on_tpu
    row = state_shape(cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state,
                      cfg.ssm_head_dim)[-1]
    return (on_tpu() and cfg.attn_impl != "jnp"
            and row % 128 == 0 and cfg.ssm_state % 128 == 0
            and cfg.ssm_chunk % 128 == 0)


def _conv(cfg, lp, xbc, tail=None):
    """The causal depthwise convolution and its activation over xbc [R, S,
    W], the positions before the first read as `tail` [R, K - 1, W] (None:
    zeros).  Returns (silu(conv) [R, S, W], the inputs behind K - 1 zeros
    [R, K - 1 + S, W]).  The tail enters as a correction of the first K - 1
    positions, not laid in front of `xbc`: concatenated, the layout XLA
    picks for a tail gathered from the arena reaches the in-projection and
    has it copy every layer's weights (0.77 GB of temporaries in the cell's
    chunk program)."""
    K, S = cfg.ssm_conv, xbc.shape[1]
    ext = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    w = lp["ssm_conv_w"].astype(jnp.float32)                      # [K, W]
    out = lp["ssm_conv_b"].astype(jnp.float32) + sum(
        w[j] * ext[:, j:j + S].astype(jnp.float32) for j in range(K))
    if tail is not None:
        # position t < K - 1 reads tail[t + j] through its taps j < K - 1 - t
        tail = tail.astype(jnp.float32)
        missed = jnp.stack([
            sum(w[j] * tail[:, t + j] for j in range(K - 1 - t))
            for t in range(min(K - 1, S))], axis=1)
        out = out.at[:, :missed.shape[1]].add(missed)
    return jax.nn.silu(out).astype(xbc.dtype), ext


def _next_tail(ext, tail, n_valids, k1: int):
    """The inputs of a row's last `k1` = K - 1 real positions: of `ext`
    (`_conv`'s: K - 1 zeros, then the inputs) and, where the row has fewer
    than K - 1 real positions, of the `tail` before them (None: zeros)."""
    take = lambda a, at: jax.vmap(                          # noqa: E731
        lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, k1))(a, at)
    if tail is None:
        return take(ext, n_valids)
    # (one of the two is zero at every entry: the sum is exact)
    old = jnp.pad(tail.astype(ext.dtype), ((0, 0), (0, k1), (0, 0)))
    return take(ext, n_valids) + take(old, jnp.minimum(n_valids, k1))


def _gated_out(cfg, lp, y, z):
    """rms over each group of channels of `y * silu(z)`, then W_out."""
    T, G = y.shape[0], cfg.ssm_groups
    y = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(T, G, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = (y.reshape(T, -1) * lp["ssm_norm_scale"].astype(jnp.float32)
         ).astype(z.dtype)
    return _scaled(y, lp["ssm_out"],
                   cfg.ssm_out_multiplier * cfg.residual_multiplier)


def _split_in(cfg, lp, n):
    """The in-projection of [T, H] rows: z [T, Wm], xBC [T, Wc], dt [T,
    NHm] (before its bias and softplus)."""
    p = _scaled(n, lp["ssm_in"], _in_multipliers(cfg))
    Wm, Wc = cfg.ssm_width, cfg.ssm_conv_width
    return p[:, :Wm], p[:, Wm:Wm + Wc], p[:, Wm + Wc:]


def _split_conv(cfg, xbc):
    """x [.., NHm, P], B, C [.., G, N] of the convolution's output."""
    Wm, gn = cfg.ssm_width, cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :Wm].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim)),
            xbc[..., Wm:Wm + gn].reshape(lead + (cfg.ssm_groups,
                                                 cfg.ssm_state)),
            xbc[..., Wm + gn:].reshape(lead + (cfg.ssm_groups,
                                               cfg.ssm_state)))


def _step_size(lp, dt):
    return jax.nn.softplus(dt.astype(jnp.float32)
                           + lp["ssm_dt_bias"].astype(jnp.float32))


def _qkv(cfg, lp, n, positions):
    """Projected q [R, S, NH, D], k, v [R, S, NKV, D] of n [R, S, H],
    rotated where the model rotates.  The kernels scale the scores by 1 /
    sqrt(D): a model that states another scale has the ratio on q's
    projection."""
    R, S, H = n.shape
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    n2, a_in = n.reshape(R * S, H), cfg.attention_in_multiplier
    a_q = a_in * (cfg.attention_multiplier * math.sqrt(D)
                  if cfg.attention_multiplier else 1.0)
    q = _scaled(n2, lp["wq"], a_q).reshape(R, S, NH, D)
    k = _scaled(n2, lp["wk"], a_in * cfg.key_multiplier).reshape(R, S, NKV, D)
    v = _scaled(n2, lp["wv"], a_in).reshape(R, S, NKV, D)
    if cfg.pos_emb == "none":
        return q, k, v
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _mlp(cfg, lp, x1):
    with jax.named_scope("dense_ffn"):
        h = rms(x1, lp["mlp_norm_scale"], cfg.norm_eps)
        gate = _scaled(h, lp["w_gate"], cfg.mlp_multipliers[0])
        up = _scaled(h, lp["w_up"], 1.0)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(h.dtype)
        return _scaled(act, lp["w_down"],
                       cfg.mlp_multipliers[1] * cfg.residual_multiplier)


def _experts_ffn(cfg, lp, experts, li, x1, real):
    """x1 + r (the held experts' part + the shared expert) on [T, H] rows
    (`real`: the rows that are tokens), and the router's counts."""
    h = rms(x1, lp["mlp_norm_scale"], cfg.norm_eps)
    m, counts = moe(cfg, lp, experts, li, h, real)
    m = m.astype(jnp.float32)
    if cfg.moe_shared_expert_ffn:
        with jax.named_scope("shared_expert"):
            m = m + _plain_mlp(cfg, lp["shared"], h).astype(jnp.float32)
    return x1 + (m * cfg.residual_multiplier).astype(x1.dtype), counts


def _logits(cfg, params, x):
    logits = _lm_logits(cfg, params, x)
    with jax.named_scope("lm_head"):
        return logits * cfg.lm_head_multiplier


def _stacked_over(name: str) -> str:
    """Which layers a leaf of `params["layers"]` is stacked over: those
    with a mixer ("ssm"), those with attention ("attn"), or all."""
    if name.startswith("ssm_"):
        return "ssm"
    return "attn" if name in ATTN_LEAVES else "all"


def _steps(body, carry, xs, n: int):
    """`lax.scan` of `body` over `xs`' leading `n`; one step is the body
    itself on the one slice."""
    if n == 1:
        return body(carry, jax.tree.map(lambda a: a[0], xs))[0]
    return jax.lax.scan(body, carry, xs)[0]


def _stack(cfg: TransformerConfig, params, arena, x, real, mixer, attend):
    """The layers over x [T, H]: per period its runs of one kind, each a
    scan over that run's layers (`_steps`).  `mixer(lp, row, n, ssm, conv)
    -> (m, ssm, conv)` and `attend(lp, row, n, ak, av) -> (a, ak, av)` are
    the program's two branches on the normed input, `row` the layer's place
    among its kind's arena rows; `real` [T]: the rows that are tokens.
    Returns (x, arena)."""
    period, L = cfg.ssm_period, cfg.num_layers
    P, dt = len(period), cfg.dtype
    per_state = sum(k != "attn" for k in period)
    per_attn = sum(k != "ssm" for k in period)
    T = x.shape[0]
    experts = None
    if cfg.moe_experts > 1:
        experts = {n: w.reshape((L * cfg.local_experts,) + w.shape[2:])
                   .astype(dt) for n, w in params["experts"].items()}
    # more slots than the experts take at once: the real rows go in front
    compact = experts is not None and T > ROW_TILE and T % ROW_TILE == 0
    if compact:
        order = jnp.argsort(~real, stable=True)
        back, n_real = jnp.argsort(order), jnp.sum(real)

    def ffn(lp, li, x1, counts):
        if experts is None:
            return x1 + _mlp(cfg, lp, x1), counts

        def one(x1, real):
            out, c = _experts_ffn(cfg, lp, experts, li, x1, real)
            return (out,), c

        if not compact:
            (out,), c = one(x1, real)
            return out, counts + c
        (out,), counts = rows(one, n_real, (x1[order], real[order]),
                               counts, ROW_TILE)
        return out[back], counts

    leaves = params["layers"]

    def layers_of(kind: str):
        mine = [n for n in leaves if kind == "both"
                or _stacked_over(n) in ("all", kind)]

        def layer(carry, places):
            x, ak, av, ssm, conv, counts = carry                    # [T, H]
            li, srow, arow = places
            # (a layer takes its leaves out of the whole stacks by its own
            # place among each: a run's slice of a stack handed to the scan
            # as `xs` is a copy, 1.2 GB of them in the decode program of
            # nine mixers)
            row = {"all": li, "ssm": srow, "attn": arow}
            lp = {n: jax.tree.map(lambda a: a[row[_stacked_over(n)]],
                                  leaves[n]) for n in mine}
            n = rms(x, lp["attn_norm_scale"], cfg.norm_eps)
            x1 = x
            if kind != "attn":
                with jax.named_scope("ssm"):
                    m, ssm, conv = mixer(lp, srow, n, ssm, conv)
                x1 = x1 + m
            if kind != "ssm":
                with jax.named_scope("attn"):
                    a, ak, av = attend(lp, arow, n, ak, av)
                x1 = x1 + a
            out, counts = ffn(lp, li, x1, counts)
            return (out, ak, av, ssm, conv, counts), None
        return layer

    runs = layer_runs(cfg)

    def one_period(carry, pi):
        for run in runs:
            nth = jnp.arange(run.count)
            carry = _steps(layers_of(run.kind), carry, (
                pi * P + run.first + nth,
                pi * per_state + run.state_row + nth,
                pi * per_attn + run.attn_row + nth), run.count)
        return carry, None

    x, ak, av, ssm, conv, counts = _steps(
        one_period, (x, arena["k"], arena["v"], arena["ssm"], arena["conv"],
                     arena.get("moe_counts", ())),
        jnp.arange(L // P), L // P)
    out = {**arena, "k": ak, "v": av, "ssm": ssm, "conv": conv}
    if experts is not None:
        out["moe_counts"] = counts
    return x, out


def _attend_chunks(cfg, q, ak, av, li, block_tables, positions, pos0s,
                   n_valids):
    """Chunk rows against their sequences' keys in the arena (the chunk's
    own already written): the blocked-flash kernel a row, or the dense
    gather.  q [NC, C, NH, D] -> [NC, C, NH * D]."""
    NC, C, NH, D = q.shape
    L, nb, bs, NKV, _ = ak.shape
    MB = block_tables.shape[1]
    use_kernel = _use_paged_prefill(cfg, D, bs, C)
    key_pos = jnp.arange(MB * bs)

    def one(_, inp):
        q_i, table_i, pos_i, p0_i, nv_i = inp
        if use_kernel:
            from ...ops.paged_prefill import paged_prefill_attention
            o = paged_prefill_attention(q_i, ak, av, table_i, p0_i, nv_i,
                                        layer_idx=li)
        else:
            idx = jnp.clip(table_i, 0, nb - 1)
            kk = jnp.take(ak[li], idx, axis=0).reshape(MB * bs, NKV, D)
            vv = jnp.take(av[li], idx, axis=0).reshape(MB * bs, NKV, D)
            kk = jnp.repeat(kk, NH // NKV, axis=1)
            vv = jnp.repeat(vv, NH // NKV, axis=1)
            s = jnp.einsum("cnd,mnd->ncm", q_i, kk,
                           preferred_element_type=jnp.float32) / math.sqrt(D)
            s = jnp.where(key_pos[None, None, :] <= pos_i[None, :, None],
                          s, -1e30)
            o = jnp.einsum("ncm,mnd->cnd",
                           jax.nn.softmax(s, axis=-1).astype(q_i.dtype), vv)
        return (), o.reshape(C, NH * D)

    _, o = jax.lax.scan(one, (), (q, block_tables, positions, pos0s,
                                  n_valids))
    return o


def _prefill_rows(cfg: TransformerConfig, params, arena, tokens, pos0s,
                  n_valids, block_tables, active, slots, fresh: bool):
    """Rows of prompt positions [pos0, pos0 + n_valid): tokens [R, S];
    `fresh`: every row starts at position 0 (a static promise: causal
    flash attention over the row itself, zero initial state).  Returns
    (the last layer's output [R, S, H], arena)."""
    from ...ops import ssm as kernels
    R, S = tokens.shape
    H, dt_ = cfg.hidden_size, cfg.dtype
    NH, D = cfg.num_heads, cfg.head_dim
    K = cfg.ssm_conv
    nb, bs = arena["k"].shape[1], arena["k"].shape[2]
    MB = block_tables.shape[1]
    n_slots = arena["ssm"].shape[1]
    pack = arena["ssm"].shape[-1] // cfg.ssm_head_dim
    pos0s = jnp.where(active, pos0s, 0)
    n_valids = jnp.where(active, n_valids, 0)
    positions = pos0s[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = jnp.arange(S)[None] < n_valids[:, None]
    x = (_embed(cfg, params, tokens.ravel(), positions.ravel())
         * cfg.embedding_multiplier).astype(dt_)
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, MB - 1), axis=1)
    blk = jnp.where(valid, blk, nb)                      # drop padded slots
    off = positions % bs
    # a row that writes nothing names a slot past the last
    slot_w = jnp.where(active, slots, n_slots)
    slot_r = jnp.clip(slots, 0, n_slots - 1)
    # the kernel's rows all name a slot that exists: the scratch one
    slot_k = jnp.where(active, slot_r, n_slots - 1)
    carried = (pos0s > 0)[:, None, None]
    fused = _use_ssm_kernels(cfg)

    def mixer(lp, li, n, ssm, conv):
        z, xbc, dtr = _split_in(cfg, lp, n)
        with jax.named_scope("conv"):
            tail = None if fresh else jnp.where(
                carried, conv[li, slot_r].reshape(R, K - 1, -1), 0)
            xbc, ext = _conv(cfg, lp, xbc.reshape(R, S, -1), tail)
            conv = conv.at[li, slot_w].set(
                _next_tail(ext, tail, n_valids, K - 1).reshape(R, -1),
                mode="drop")
        xs_, b, c = _split_conv(cfg, xbc)
        a_neg = -jnp.exp(lp["ssm_a_log"].astype(jnp.float32))
        step = jnp.where(valid[..., None],
                         _step_size(lp, dtr).reshape(R, S, -1), 0.0)
        with jax.named_scope("scan"):
            if fused:
                y, ssm = kernels.ssd_scan(
                    xs_, step, a_neg, b, c, ssm, li, slot_k,
                    jnp.zeros_like(slots) if fresh else pos0s > 0,
                    cfg.ssm_chunk)
            else:
                h0 = jnp.zeros((R, cfg.ssm_heads, cfg.ssm_state,
                                cfg.ssm_head_dim), jnp.float32) \
                    if fresh else jnp.where(
                        carried[..., None],
                        kernels.unpack_state(ssm[li, slot_r], pack), 0.0)
                y, h = kernels.ssd_scan_reference(
                    xs_, step, a_neg, b, c, h0, cfg.ssm_chunk)
                ssm = ssm.at[li, slot_w].set(kernels.pack_state(h, pack),
                                             mode="drop")
        y = y + lp["ssm_d"].astype(jnp.float32)[:, None] \
            * xs_.astype(jnp.float32)
        return _gated_out(cfg, lp, y.reshape(R * S, -1), z), ssm, conv

    def attend(lp, li, n, ak, av):
        q, k, v = _qkv(cfg, lp, n.reshape(R, S, H), positions)
        ak, av = _kv_write(ak, av, li, blk, off, k, v, False)
        if fresh:
            from ...ops.attention import causal_attention
            o = causal_attention(q, k, v, impl=cfg.attn_impl
                                 ).reshape(R * S, NH * D)
        else:
            o = _attend_chunks(cfg, q, ak, av, li, block_tables,
                               positions, pos0s, n_valids
                               ).reshape(R * S, NH * D)
        return _scaled(o, lp["wo"], cfg.attention_out_multiplier
                       * cfg.residual_multiplier), ak, av

    x, arena = _stack(cfg, params, arena, x, valid.reshape(R * S), mixer,
                      attend)
    return x.reshape(R, S, H), arena


def _prefill(cfg: TransformerConfig, params, arena, tokens, pos0s, n_valids,
             block_tables, active, slots, fresh: bool):
    """`_prefill_rows`, then (logits [R, V] at each row's last position,
    their argmax, arena)."""
    x, arena = _prefill_rows(cfg, params, arena, tokens, pos0s, n_valids,
                             block_tables, active, slots, fresh)
    last = jnp.clip(jnp.where(active, n_valids, 0) - 1, 0, x.shape[1] - 1)
    logits = _logits(cfg, params, x[jnp.arange(x.shape[0]), last])
    return logits, greedy_tokens(logits), arena


def prefill_full(cfg, params, arena, tokens, lens, block_tables, active,
                 slots):
    """`ragged_ops.prefill_full` for the state-space family: fresh whole
    prompts, dense causal flash attention, the scan from zeros."""
    return _prefill(cfg, params, arena, tokens, jnp.zeros_like(lens), lens,
                    block_tables, active, slots, fresh=True)


def prefill_chunks(cfg, params, arena, tokens, pos0s, n_valids,
                   block_tables, active, slots, **uniform_only):
    """`ragged_ops.prefill_chunks` for the state-space family: a chunk
    slot a SEQUENCE (the engine plans no two chunks of one sequence into a
    program), each from its slot's state where it continues."""
    return _prefill(cfg, params, arena, tokens, pos0s, n_valids,
                    block_tables, active, slots, fresh=False)


def decode_core(cfg, params, arena, tokens, seq_lens, block_tables, active,
                slots, **uniform_only):
    """`ragged_ops._decode_core` for the state-space family: (logits [B,
    V], arena), the active rows' slots updated in place."""
    from ...ops import ssm as kernels
    B = tokens.shape[0]
    NH, D = cfg.num_heads, cfg.head_dim
    nb, bs = arena["k"].shape[1], arena["k"].shape[2]
    n_slots = arena["ssm"].shape[1]
    x = (_embed(cfg, params, tokens, seq_lens)
         * cfg.embedding_multiplier).astype(cfg.dtype)
    blk = jnp.take_along_axis(block_tables, (seq_lens // bs)[:, None],
                              axis=1)[:, 0]
    blk = jnp.where(active, blk, nb)
    off = seq_lens % bs
    lens = jnp.where(active, seq_lens, -1)
    fused_ssm = _use_ssm_kernels(cfg)
    fused_attn = _use_paged_kernel(cfg, D, bs)
    slot_r = jnp.clip(slots, 0, n_slots - 1)
    slot_w = jnp.where(active, slots, n_slots)
    # the kernel's rows all name a slot that exists: the scratch one
    slot_k = jnp.where(active, slot_r, n_slots - 1)

    def mixer(lp, li, n, ssm, conv):
        z, xbc_in, dtr = _split_in(cfg, lp, n)
        with jax.named_scope("conv"):
            tail = conv[li, slot_r]                        # [B, (K - 1) W]
            Wc = xbc_in.shape[-1]
            xbc, _ = _conv(cfg, lp, xbc_in[:, None],
                           tail.reshape(B, -1, Wc))
            conv = conv.at[li, slot_w].set(jnp.concatenate(
                [tail[:, Wc:], xbc_in], axis=1), mode="drop")
        xs_, b, c = _split_conv(cfg, xbc[:, 0])
        step = _step_size(lp, dtr)                               # [B, NHm]
        xf = xs_.astype(jnp.float32)
        decay = jnp.exp(step * -jnp.exp(
            lp["ssm_a_log"].astype(jnp.float32)))
        with jax.named_scope("update"):
            args = (xf * step[..., None],
                    jnp.broadcast_to(decay[..., None], xf.shape),
                    b.astype(jnp.float32), c.astype(jnp.float32))
            if fused_ssm:
                y, ssm = kernels.ssm_update(ssm, li, slot_k, *args)
            else:
                y, ssm = kernels.ssm_update_reference(ssm, li, slot_w,
                                                      *args)
        y = y + lp["ssm_d"].astype(jnp.float32)[:, None] * xf
        return _gated_out(cfg, lp, y.reshape(B, -1), z), ssm, conv

    def attend(lp, li, n, ak, av):
        q, k, v = _qkv(cfg, lp, n[:, None], seq_lens[:, None])
        ak, av = _kv_write(ak, av, li, blk, off, k[:, 0], v[:, 0], False)
        if fused_attn:
            from ...ops.paged_attention import paged_decode_attention
            o = paged_decode_attention(q[:, 0], ak, av, block_tables,
                                       lens, layer_idx=li)
        else:
            from ...ops.paged_attention import paged_decode_reference
            o = paged_decode_reference(q[:, 0], ak[li], av[li],
                                       block_tables, lens)
        return _scaled(o.reshape(B, NH * D), lp["wo"],
                       cfg.attention_out_multiplier
                       * cfg.residual_multiplier), ak, av

    x, arena = _stack(cfg, params, arena, x, active, mixer, attend)
    return _logits(cfg, params, x), arena
