"""The state-space parallel block (a Mamba-2 mixer BESIDE grouped-query
attention in every layer, then a gated MLP; muP multipliers) over paged
K/V AND per-sequence recurrent state: what `ragged_ops`' programs run for
a `TransformerConfig` with `ssm_state` (Falcon-H1).

A layer, input x [T, H], `n = rms(x, g_in)` read by both branches:

    p = (n W_in) * (ssm_in_multiplier * mup)       [z | xBC | dt], mup =
        ssm_multipliers over the columns [z | x | B | C | dt]
    xBC = silu(conv(xBC) + bias)   causal, depthwise, over the last
        `ssm_conv` positions; split x [T, NHm, P], B, C [T, G, N]
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (outer) x_t;  y_t = C_t h_t + D x_t
    y = rms_grouped(y * silu(z), g_norm)           gate first, then RMS over
        each of the G groups of channels
    m = ssm_out_multiplier * (y W_out)
    q, k, v = n W_q, key_multiplier * (n W_k), n W_v  (n times
        attention_in_multiplier); rope over the whole head, pairs
        (i, i + D/2); causal softmax(q k / sqrt(D)) v
    a = attention_out_multiplier * (o W_o)
    x1 = x + m + a
    out = x1 + mlp_multipliers[1] * ((W_up h) * silu(mlp_multipliers[0] *
        (W_gate h))) W_down,   h = rms(x1, g_ff)

with `embedding_multiplier` on the embedding and `lm_head_multiplier` on
the logits.  A multiplier sits on a matmul's float32 result, before the
one rounding to the stored type.

The arena holds attention's paged `k`/`v` `[L, blocks, bs, NKV, D]` as the
dense family's, and beside them one SLOT a live sequence: `ssm` `[L, slots
+ 1, NHm, N, P]` float32 (the state transposed, channels on the lanes:
`ops/ssm.py`) and `conv` `[L, slots + 1, (ssm_conv - 1) * x|B|C]` (the
convolution's tail: the inputs of the last positions, in the stored type,
which is what they were computed in; one flat row a slot, because with a
minor pair `[3, 5120]` XLA carries the buffer through the chunk program's
layer loop in a layout that pads the 3 to 128 lanes: 763 MB for 18).  The last slot is scratch: a padded
row of the decode kernel reads and writes it.  **The state is float32**: the
recurrence rounds what it stores once a token for as many steps as a
request has, so a narrower store is a change of precision, not a layout.

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
attention proper, `W_o`), `dense_ffn`, `lm_head`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...models.transformer import TransformerConfig, _rope
from .latent_ops import _rms
from .ragged_ops import (_embed, _kv_write, _lm_logits, _use_paged_kernel,
                         _use_paged_prefill, greedy_tokens)

__all__ = ["init_ssm_arena", "state_bytes_per_slot", "prefill_full",
           "prefill_chunks", "decode_core", "refuse_lora"]


def init_ssm_arena(cfg: TransformerConfig, num_blocks: int, block_size: int,
                   max_seqs: int):
    """Paged K/V beside `max_seqs` state slots and one scratch slot."""
    L = cfg.num_layers
    kv = (L, num_blocks, block_size, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "ssm": jnp.zeros((L, max_seqs + 1, cfg.ssm_heads, cfg.ssm_state,
                              cfg.ssm_head_dim), jnp.float32),
            "conv": jnp.zeros((L, max_seqs + 1, (cfg.ssm_conv - 1)
                               * cfg.ssm_conv_width), cfg.dtype)}


def state_bytes_per_slot(cfg: TransformerConfig) -> int:
    """Recurrent state a sequence holds over all layers (state + tail)."""
    return cfg.num_layers * (
        cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4
        + (cfg.ssm_conv - 1) * cfg.ssm_conv_width
        * jnp.dtype(cfg.dtype).itemsize)


def refuse_lora(lora) -> None:
    if lora is not None:
        raise NotImplementedError(
            "LoRA adapters are not wired for the state-space parallel "
            "block: the gather epilogue sits on the dense block's output "
            "projection, and this block's programs take no adapter operands")


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
    want whole 128-lane tiles); `attn_impl='jnp'` keeps the dense forms."""
    from ...utils.device import on_tpu
    return (on_tpu() and cfg.attn_impl != "jnp"
            and cfg.ssm_head_dim % 128 == 0 and cfg.ssm_state % 128 == 0
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
    return _scaled(y, lp["ssm_out"], cfg.ssm_out_multiplier)


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
    """Projected and rotated q [R, S, NH, D], k, v [R, S, NKV, D] of n
    [R, S, H]."""
    R, S, H = n.shape
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    n2, a_in = n.reshape(R * S, H), cfg.attention_in_multiplier
    q = _scaled(n2, lp["wq"], a_in).reshape(R, S, NH, D)
    k = _scaled(n2, lp["wk"], a_in * cfg.key_multiplier).reshape(R, S, NKV, D)
    v = _scaled(n2, lp["wv"], a_in).reshape(R, S, NKV, D)
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _mlp(cfg, lp, x1):
    with jax.named_scope("dense_ffn"):
        h = _rms(x1, lp["mlp_norm_scale"], cfg.norm_eps)
        gate = _scaled(h, lp["w_gate"], cfg.mlp_multipliers[0])
        up = _scaled(h, lp["w_up"], 1.0)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(h.dtype)
        return _scaled(act, lp["w_down"], cfg.mlp_multipliers[1])


def _logits(cfg, params, x):
    logits = _lm_logits(cfg, params, x)
    with jax.named_scope("lm_head"):
        return logits * cfg.lm_head_multiplier


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


def _prefill(cfg: TransformerConfig, params, arena, tokens, pos0s, n_valids,
             block_tables, active, slots, fresh: bool):
    """Rows of prompt positions [pos0, pos0 + n_valid): tokens [R, S];
    `fresh`: every row starts at position 0 (a static promise: causal
    flash attention over the row itself, zero initial state).  Returns
    (logits [R, V] at each row's last position, their argmax, arena)."""
    from ...ops import ssm as kernels
    R, S = tokens.shape
    H, dt_ = cfg.hidden_size, cfg.dtype
    NH, D = cfg.num_heads, cfg.head_dim
    K = cfg.ssm_conv
    nb, bs = arena["k"].shape[1], arena["k"].shape[2]
    MB = block_tables.shape[1]
    n_slots = arena["ssm"].shape[1]
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

    def layer(carry, xs):
        x, ak, av, ssm, conv = carry                              # [R*S, H]
        lp, li = xs
        n = _rms(x, lp["attn_norm_scale"], cfg.norm_eps)
        with jax.named_scope("ssm"):
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
                    h0 = jnp.zeros((R,) + ssm.shape[2:], jnp.float32) \
                        if fresh else jnp.where(carried[..., None],
                                                ssm[li, slot_r], 0.0)
                    y, h = kernels.ssd_scan_reference(
                        xs_, step, a_neg, b, c, h0, cfg.ssm_chunk)
                    ssm = ssm.at[li, slot_w].set(h, mode="drop")
            y = y + lp["ssm_d"].astype(jnp.float32)[:, None] \
                * xs_.astype(jnp.float32)
            m = _gated_out(cfg, lp, y.reshape(R * S, -1), z)
        with jax.named_scope("attn"):
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
            a = _scaled(o, lp["wo"], cfg.attention_out_multiplier)
        x1 = x + m + a
        return (x1 + _mlp(cfg, lp, x1), ak, av, ssm, conv), None

    (x, ak, av, ssm, conv), _ = jax.lax.scan(
        layer, (x, arena["k"], arena["v"], arena["ssm"], arena["conv"]),
        (params["layers"], jnp.arange(cfg.num_layers)))
    last = jnp.clip(n_valids - 1, 0, S - 1)
    logits = _logits(cfg, params, x.reshape(R, S, H)[jnp.arange(R), last])
    return logits, greedy_tokens(logits), {
        **arena, "k": ak, "v": av, "ssm": ssm, "conv": conv}


def prefill_full(cfg, params, arena, tokens, lens, block_tables, active,
                 slots):
    """`ragged_ops.prefill_full` for the state-space parallel block: fresh
    whole prompts, dense causal flash attention, the scan from zeros."""
    return _prefill(cfg, params, arena, tokens, jnp.zeros_like(lens), lens,
                    block_tables, active, slots, fresh=True)


def prefill_chunks(cfg, params, arena, tokens, pos0s, n_valids,
                   block_tables, active, slots):
    """`ragged_ops.prefill_chunks` for the state-space parallel block: a
    chunk slot a SEQUENCE (the engine plans no two chunks of one sequence
    into a program), each from its slot's state where it continues."""
    return _prefill(cfg, params, arena, tokens, pos0s, n_valids,
                    block_tables, active, slots, fresh=False)


def decode_core(cfg, params, arena, tokens, seq_lens, block_tables, active,
                slots):
    """`ragged_ops._decode_core` for the state-space parallel block:
    (logits [B, V], arena), the active rows' slots updated in place."""
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

    def layer(carry, xs):
        x, ak, av, ssm, conv = carry                                 # [B, H]
        lp, li = xs
        n = _rms(x, lp["attn_norm_scale"], cfg.norm_eps)
        with jax.named_scope("ssm"):
            z, xbc_in, dtr = _split_in(cfg, lp, n)
            with jax.named_scope("conv"):
                tail = conv[li, slot_r]                    # [B, (K - 1) W]
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
            m = _gated_out(cfg, lp, y.reshape(B, -1), z)
        with jax.named_scope("attn"):
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
            a = _scaled(o.reshape(B, NH * D), lp["wo"],
                        cfg.attention_out_multiplier)
        x1 = x + m + a
        return (x1 + _mlp(cfg, lp, x1), ak, av, ssm, conv), None

    (x, ak, av, ssm, conv), _ = jax.lax.scan(
        layer, (x, arena["k"], arena["v"], arena["ssm"], arena["conv"]),
        (params["layers"], jnp.arange(cfg.num_layers)))
    return _logits(cfg, params, x), {
        **arena, "k": ak, "v": av, "ssm": ssm, "conv": conv}
