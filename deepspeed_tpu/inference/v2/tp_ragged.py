"""Fused-collective tensor-parallel serving programs.

The default TP path (``tp_collectives="xla"``) runs the ragged_ops
programs over GSPMD-sharded operands: weights carry the Megatron
column/row `_TP_RULES` specs, the partitioner inserts one all-reduce per
block half, and the fused attention kernels run per-shard via
`_shard_mapped_tp`.  Correct — but every collective serializes with the
matmul that feeds it.

This module is the ``tp_collectives="fused"`` path: the whole serving
program runs INSIDE one shard_map region over the tp axis, with the
residual stream kept ROW-SHARDED between blocks and every TP collective
expressed as a fused ring matmul from `ops/tp_matmul.py`:

- column-parallel stages (QKV, MLP up/gate, lm head) consume the
  row-sharded stream through the all-gather-producer matmul
  (`ag_matmul`: shard chunks stream in while local weight columns
  multiply);
- row-parallel stages (attn out, MLP down) produce the next row shard
  through the matmul-reduce-scatter consumer (`matmul_rs`: partial row
  tiles ship ring-ward as they finish, accumulated in f32).

Comm volume per block is identical to the one-reduce-per-block Megatron
layout (ring AR == RS + AG), but each hop is issued while the previous
chunk's matmul runs (the hop carries no dependency on that matmul, so
the scheduler may overlap them).  Extra collectives
outside the blocks: one [rows, H] psum at the vocab-sharded embedding,
and one vocab all-gather of the final logits.

Attention runs per-shard on local heads exactly like the xla path's
`_shard_mapped_tp` — we are already inside the manual region, so the
fused paged kernels are called directly (dense gather math with local
head counts everywhere else, e.g. the CPU parity suite).

Layout invariants (checked loudly by `tp_fused_unsupported_reason`; the
xla path stays the escape hatch for everything refused here):
pre-norm sequential-residual archs only, rope/learned positions, no
sliding windows / per-layer extras / MoE / OPT-style embed projections /
fp8 weight dicts, 5-D (unmerged) arena, and every row dimension the
stream is sharded over must divide by tp (max_seqs, prefill chunk,
vocab, heads, ffn).

Parity discipline: tp=1 never builds these programs (byte-identical
default), and the fused tp=2 greedy chain on a forced-host CPU mesh is
locked token-for-token against tp=1 by tests/test_tp_inference.py.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models.transformer import _norm, _rope
from ...ops.tp_matmul import ag_matmul, matmul_rs, tile_matmul
from ...parallel.mesh import AXIS_TP
from .ragged_ops import greedy_tokens
from jax import shard_map

PyTree = Any

__all__ = ["TPServingPrograms", "tp_fused_unsupported_reason"]


def tp_fused_unsupported_reason(cfg, config, params, arena) -> Optional[str]:
    """None when the fused-TP programs can serve this (cfg, config,
    params, arena); otherwise the reason string the engine raises with.
    The xla path serves every refused configuration."""
    tp = config.tensor_parallel_size
    if cfg.post_norm or cfg.parallel_residual:
        return ("post-norm / parallel-residual blocks are not wired "
                "through the fused-TP forward")
    if cfg.moe_experts > 1 or cfg.moe_dense_layers is not None:
        return "MoE layers are not wired through the fused-TP forward"
    if cfg.pos_emb not in ("rope", "learned"):
        return (f"pos_emb={cfg.pos_emb!r} is not wired through the "
                f"fused-TP forward (alibi slopes are global-head-indexed)")
    if cfg.sliding_window is not None or cfg.sliding_window_layers is not None:
        return "sliding windows are not wired through the fused-TP forward"
    if "embed_in_proj" in params or "embed_out_proj" in params:
        return ("OPT-style embed in/out projections are not wired "
                "through the fused-TP forward")
    paths = {".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    if any("q_codes" in p or "q_scales" in p or "q_col_scales" in p
           for p in paths):
        return ("fp8 serving-weight dicts are not TP-sharded (their "
                "leaves carry no _TP_RULES spec), so the fused path "
                "would stream full-size codes")
    if arena["k"].ndim == 4:
        return ("the merged [L, nb, bs, NKV*D] arena layout cannot "
                "shard contiguous kv-head groups for the per-shard "
                "kernels (use arena_merged=False)")
    if config.max_seqs % tp:
        return (f"max_seqs={config.max_seqs} must divide by tp={tp} "
                f"(the decode batch rows are the sharded stream)")
    if config.prefill_chunk_size % tp:
        return (f"prefill_chunk_size={config.prefill_chunk_size} must "
                f"divide by tp={tp}")
    if cfg.vocab_size % tp:
        return (f"vocab_size={cfg.vocab_size} must divide by tp={tp} "
                f"(vocab-sharded embedding / lm head)")
    ffn = params["layers"]["w_up"].shape[-1]
    if ffn % tp:
        return f"ffn width {ffn} must divide by tp={tp}"
    return None


class TPServingPrograms:
    """Per-engine compiled entry points for fused-TP serving.

    Signatures mirror the ragged_ops programs minus the (n_tp, mesh)
    statics — the mesh and tp degree are bound at construction.  The
    arena is donated on every call, exactly like the xla programs.
    """

    def __init__(self, cfg, topology, param_specs: PyTree, config):
        self.cfg = cfg
        self.mesh = topology.mesh
        self.tp = topology.tp_size
        self._pspecs = param_specs
        self._aspec = {"k": P(None, None, None, AXIS_TP, None),
                       "v": P(None, None, None, AXIS_TP, None)}
        # per-chunk GEMM dispatch: Pallas MXU tiles on TPU, jnp elsewhere
        self._mm_impl = "auto"
        from .ragged_ops import _use_paged_kernel
        # decode attention kernel gate: per-shard (we are inside the
        # manual region), so capability is judged at n_tp=1
        self._decode_kernel = _use_paged_kernel(cfg, cfg.head_dim,
                                                config.block_size, 1)
        self.prefill_chunks = jax.jit(self._prefill_chunks_impl,
                                      donate_argnums=(1,))
        self.decode_step = jax.jit(self._decode_step_impl,
                                   donate_argnums=(1,))
        self.decode_tokens = jax.jit(
            self._decode_tokens_impl, donate_argnums=(1,),
            static_argnames=("n_steps", "mode", "top_k"))
        self.verify_tokens = jax.jit(self._verify_tokens_impl,
                                     donate_argnums=(1,),
                                     static_argnames=("mode",))

    # -- fused matmul halves ---------------------------------------------
    def _col(self, h_local, w, b):
        """Column-parallel stage on the row-sharded stream: fused
        all-gather matmul.  h_local [rows, K] -> [tp*rows, N_local]."""
        dt = self.cfg.dtype
        mat = w.astype(dt)
        mm = lambda c: tile_matmul(c, mat, impl=self._mm_impl).astype(dt)
        out = ag_matmul(h_local, AXIS_TP, self.tp, mm)
        if b is not None:
            out = out + b.astype(dt)
        return out

    def _rowp(self, y_full, w, b):
        """Row-parallel stage back onto the row-sharded stream: fused
        matmul-reduce-scatter (f32 ring accumulation, ONE cast + bias
        after).  y_full [S, K_local] -> [S/tp, N]."""
        dt = self.cfg.dtype
        mat = w.astype(dt)
        mm = lambda c: tile_matmul(c, mat, impl=self._mm_impl)
        out = matmul_rs(y_full, AXIS_TP, self.tp, mm).astype(dt)
        if b is not None:
            out = out + b.astype(dt)
        return out

    # -- shared local pieces ---------------------------------------------
    def _embed_rows(self, params, tokens_flat, positions_flat):
        """Row-sharded embedding from the vocab-sharded table: every
        shard looks the FULL token vector up in its local vocab chunk
        (rows outside the chunk masked to zero), one psum assembles the
        complete embeddings — a row's table entry lives on exactly one
        shard, so slicing before the psum would sum DIFFERENT row sets —
        then this shard keeps its row chunk of the stream."""
        cfg = self.cfg
        idx = jax.lax.axis_index(AXIS_TP)
        rows = tokens_flat.shape[0] // self.tp
        emb = params["tok_embed"]                    # [V/tp, H] local
        Vl = emb.shape[0]
        loc = tokens_flat - idx * Vl
        ok = (loc >= 0) & (loc < Vl)
        x = jnp.take(emb, jnp.clip(loc, 0, Vl - 1), axis=0).astype(cfg.dtype)
        x = jnp.where(ok[:, None], x, 0)
        x = jax.lax.psum(x, AXIS_TP)                 # [B_total, H] full
        x = jax.lax.dynamic_slice_in_dim(x, idx * rows, rows, 0)
        if cfg.pos_emb == "learned":
            pos_l = jax.lax.dynamic_slice_in_dim(positions_flat,
                                                 idx * rows, rows, 0)
            pos = jnp.clip(pos_l, 0, cfg.max_seq_len - 1)
            x = x + jnp.take(params["pos_embed"], pos,
                             axis=0).astype(cfg.dtype)
        if cfg.embed_norm:
            x = _norm(x, params["embed_norm_scale"],
                      params["embed_norm_bias"], "layernorm", cfg.norm_eps)
        return x                                     # [rows, H]

    def _head_cols(self, params):
        head = params.get("lm_head")
        if head is None:
            head = params["tok_embed"].T             # [H, V/tp]
        return head

    def _logits_repl(self, params, xl):
        """Full-vocab logits for a REPLICATED row set `xl` [N, H]:
        column-parallel head matmul + one vocab all-gather."""
        cfg = self.cfg
        if cfg.final_norm:
            xl = _norm(xl, params["final_norm_scale"],
                       params.get("final_norm_bias"), cfg.norm,
                       cfg.norm_eps)
        head = self._head_cols(params).astype(xl.dtype)
        lg = jnp.einsum("sh,hv->sv", xl, head,
                        preferred_element_type=jnp.float32)
        if "lm_head_bias" in params:
            lg = lg + params["lm_head_bias"]         # local [V/tp] chunk
        return jax.lax.all_gather(lg, AXIS_TP, axis=1, tiled=True)

    def _logits_rows(self, params, x_local):
        """Full-vocab logits for EVERY row of the row-sharded stream:
        fused all-gather head matmul + one vocab all-gather."""
        cfg = self.cfg
        if cfg.final_norm:
            x_local = _norm(x_local, params["final_norm_scale"],
                            params.get("final_norm_bias"), cfg.norm,
                            cfg.norm_eps)
        head = self._head_cols(params).astype(x_local.dtype)
        mm = lambda c: tile_matmul(c, head, impl=self._mm_impl)
        lg = ag_matmul(x_local, AXIS_TP, self.tp, mm)   # [S, V/tp] f32
        if "lm_head_bias" in params:
            lg = lg + params["lm_head_bias"]
        return jax.lax.all_gather(lg, AXIS_TP, axis=1, tiled=True)

    def _mlp_rows(self, x_local, lp):
        """norm -> MLP on the row-sharded stream (pre-norm sequential
        residual only — validated), returning the row-sharded delta."""
        cfg = self.cfg
        dt = cfg.dtype
        h = _norm(x_local, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                  cfg.norm, cfg.norm_eps)
        if cfg.activation == "swiglu":
            g = self._col(h, lp["w_gate"], None)
            u = self._col(h, lp["w_up"], None)
            hh = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        else:
            from ...models.transformer import _act_fn
            hh = self._col(h, lp["w_up"], lp.get("b_up"))
            hh = _act_fn(cfg.activation)(hh.astype(jnp.float32)).astype(dt)
        return self._rowp(hh, lp["w_down"], lp.get("b_down"))

    def _gather_attn(self, q, ak_all, av_all, block_tables, positions, li):
        """Dense-gather attention fallback for ONE layer on LOCAL heads
        (the per-shard mirror of ragged_ops' gather math — shared by the
        decode, span, and prefill cores so the mask/GQA/softmax details
        live once): q [B, S, NHl, D], block_tables [B, MB],
        positions [B, S] -> [B, S, NHl, D]."""
        cfg = self.cfg
        B, S, NHl, D = q.shape
        NKVl = cfg.kv_heads // self.tp
        L = cfg.num_layers
        nb, bs = ak_all.shape[1], ak_all.shape[2]
        MB = block_tables.shape[1]
        max_kv = MB * bs
        key_pos = (jnp.arange(MB)[:, None] * bs
                   + jnp.arange(bs)[None, :]).ravel()
        idx_ = li * nb + jnp.clip(block_tables, 0, nb - 1)
        kk = jnp.take(ak_all.reshape(L * nb, bs, NKVl * D), idx_,
                      axis=0).reshape(B, max_kv, NKVl, D)
        vv = jnp.take(av_all.reshape(L * nb, bs, NKVl * D), idx_,
                      axis=0).reshape(B, max_kv, NKVl, D)
        if NKVl != NHl:
            kk = jnp.repeat(kk, NHl // NKVl, axis=2)
            vv = jnp.repeat(vv, NHl // NKVl, axis=2)
        s = jnp.einsum("bsnd,bmnd->bnsm", q, kk,
                       preferred_element_type=jnp.float32) / math.sqrt(D)
        mask = key_pos[None, None, None, :] <= positions[:, None, :, None]
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnsm,bmnd->bsnd", p.astype(cfg.dtype), vv)

    # -- decode -----------------------------------------------------------
    def _decode_core_local(self, params, ak_all, av_all, tokens, seq_lens,
                           block_tables, active):
        cfg = self.cfg
        tp = self.tp
        B = tokens.shape[0]
        NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        NHl, NKVl = NH // tp, NKV // tp
        bs = ak_all.shape[2]
        nb = ak_all.shape[1]
        L = cfg.num_layers

        positions = seq_lens
        blk = jnp.take_along_axis(block_tables, (positions // bs)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(active, blk, nb)
        off = positions % bs

        x = self._embed_rows(params, tokens, positions)       # [B/tp, H]

        def layer(carry, xs):
            x, ak_all, av_all = carry
            lp, li = xs
            h = _norm(x, lp["attn_norm_scale"], lp.get("attn_norm_bias"),
                      cfg.norm, cfg.norm_eps)
            q = self._col(h, lp["wq"], lp.get("bq")).reshape(B, NHl, D)
            k = self._col(h, lp["wk"], lp.get("bk")).reshape(B, NKVl, D)
            v = self._col(h, lp["wv"], lp.get("bv")).reshape(B, NKVl, D)
            if cfg.pos_emb == "rope":
                q = _rope(q[:, None], positions[:, None], cfg.rope_theta,
                          cfg.rope_pct, cfg.rope_scaling)[:, 0]
                k = _rope(k[:, None], positions[:, None], cfg.rope_theta,
                          cfg.rope_pct, cfg.rope_scaling)[:, 0]
            ak_all = ak_all.at[li, blk, off].set(k, mode="drop")
            av_all = av_all.at[li, blk, off].set(v, mode="drop")
            if self._decode_kernel:
                from ...ops.paged_attention import paged_decode_attention
                lens = jnp.where(active, positions, -1)
                attn = paged_decode_attention(
                    q, ak_all, av_all, block_tables, lens, layer_idx=li,
                    window=cfg.sliding_window).reshape(B, NHl * D)
            else:
                attn = self._gather_attn(
                    q[:, None], ak_all, av_all, block_tables,
                    positions[:, None], li)[:, 0].reshape(B, NHl * D)
            x = x + self._rowp(attn, lp["wo"], lp.get("bo"))
            x = x + self._mlp_rows(x, lp)
            return (x, ak_all, av_all), None

        (x, new_k, new_v), _ = jax.lax.scan(
            layer, (x, ak_all, av_all), (params["layers"], jnp.arange(L)))
        logits = self._logits_rows(params, x)                 # [B, V] f32
        return logits, new_k, new_v

    def _decode_step_impl(self, params, arena, tokens, seq_lens,
                          block_tables, active):
        def local(params, arena, tokens, seq_lens, block_tables, active):
            logits, nk, nv = self._decode_core_local(
                params, arena["k"], arena["v"], tokens, seq_lens,
                block_tables, active)
            return logits, {"k": nk, "v": nv}

        sm = shard_map(local, mesh=self.mesh,
                       in_specs=(self._pspecs, self._aspec) + (P(),) * 4,
                       out_specs=(P(), self._aspec), check_vma=False)
        logits, arena = sm(params, arena, tokens, seq_lens, block_tables,
                           active)
        # the replicated logits' argmax, as ragged_ops.decode_step returns
        return logits, greedy_tokens(logits), arena

    def _decode_tokens_impl(self, params, arena, tokens, seq_lens,
                            block_tables, active, rng, temperature,
                            max_len, top_k_vec=None, *, n_steps: int,
                            mode: str, top_k: int):
        from .ragged_ops import _sample_tokens

        def local(params, arena, tokens, seq_lens, block_tables, active,
                  rng, temperature, max_len, *rest):
            tkv = rest[0] if rest else None

            def step(carry, key):
                toks, lens, ak, av = carry
                logits, ak, av = self._decode_core_local(
                    params, ak, av, toks, lens, block_tables, active)
                nxt = _sample_tokens(logits, key, mode, temperature,
                                     tkv if mode == "per_row" else top_k)
                lens_next = jnp.minimum(lens + 1, max_len - 1)
                return (nxt, lens_next, ak, av), nxt

            keys = jax.random.split(rng, n_steps)
            (_, _, ak, av), toks = jax.lax.scan(
                step, (tokens, seq_lens, arena["k"], arena["v"]), keys)
            return jnp.swapaxes(toks, 0, 1), {"k": ak, "v": av}

        args = [params, arena, tokens, seq_lens, block_tables, active,
                rng, temperature, max_len]
        specs = [self._pspecs, self._aspec] + [P()] * 7
        if top_k_vec is not None:
            args.append(top_k_vec)
            specs.append(P())
        sm = shard_map(local, mesh=self.mesh,
                       in_specs=tuple(specs),
                       out_specs=(P(), self._aspec), check_vma=False)
        return sm(*args)

    # -- span (verify) ----------------------------------------------------
    def _span_core_local(self, params, ak_all, av_all, tokens, seq_lens,
                         n_valids, block_tables, active, max_len):
        cfg = self.cfg
        tp = self.tp
        B, S = tokens.shape
        NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        NHl, NKVl = NH // tp, NKV // tp
        bs = ak_all.shape[2]
        nb = ak_all.shape[1]
        MB = block_tables.shape[1]
        L = cfg.num_layers

        positions = seq_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        valid = (jnp.arange(S)[None] < n_valids[:, None]) & active[:, None]
        if max_len is not None:
            # lease bound: overshooting span positions DROP their writes
            # (see ragged_ops._span_core's clamp-vs-drop note)
            valid &= positions < max_len[:, None]
            positions = jnp.minimum(positions, max_len[:, None] - 1)
        blk = jnp.take_along_axis(block_tables,
                                  jnp.clip(positions // bs, 0, MB - 1),
                                  axis=1)
        blk = jnp.where(valid, blk, nb)
        off = positions % bs

        from .ragged_ops import _use_paged_prefill
        use_kernel = _use_paged_prefill(cfg, D, bs, S, 1,
                                        local_heads=NHl)

        x = self._embed_rows(params, tokens.ravel(), positions.ravel())

        def layer(carry, xs):
            x, ak_all, av_all = carry                 # x [B*S/tp, H]
            lp, li = xs
            h = _norm(x, lp["attn_norm_scale"], lp.get("attn_norm_bias"),
                      cfg.norm, cfg.norm_eps)
            q = self._col(h, lp["wq"], lp.get("bq")).reshape(B, S, NHl, D)
            k = self._col(h, lp["wk"], lp.get("bk")).reshape(B, S, NKVl, D)
            v = self._col(h, lp["wv"], lp.get("bv")).reshape(B, S, NKVl, D)
            if cfg.pos_emb == "rope":
                q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling)
                k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling)
            ak_all = ak_all.at[li, blk, off].set(k, mode="drop")
            av_all = av_all.at[li, blk, off].set(v, mode="drop")
            if use_kernel:
                from ...ops.paged_prefill import paged_prefill_attention

                def row_step(_, inp):
                    q_i, table_i, p0_i, nv_i = inp
                    return (), paged_prefill_attention(
                        q_i, ak_all, av_all, table_i, p0_i, nv_i,
                        layer_idx=li)

                _, attn = jax.lax.scan(
                    row_step, (), (q, block_tables, seq_lens, n_valids))
                attn = attn.reshape(B, S, NHl, D)
            else:
                attn = self._gather_attn(q, ak_all, av_all, block_tables,
                                         positions, li)
            x = x + self._rowp(attn.reshape(B * S, NHl * D), lp["wo"],
                               lp.get("bo"))
            x = x + self._mlp_rows(x, lp)
            return (x, ak_all, av_all), None

        (x, new_k, new_v), _ = jax.lax.scan(
            layer, (x, ak_all, av_all), (params["layers"], jnp.arange(L)))
        logits = self._logits_rows(params, x).reshape(B, S, -1)
        return logits, new_k, new_v

    def _verify_tokens_impl(self, params, arena, tokens, seq_lens,
                            n_valids, block_tables, active, rng,
                            temperature, max_len, top_k_vec=None, *,
                            mode: str):
        from .ragged_ops import _spec_accept

        def local(params, arena, tokens, seq_lens, n_valids, block_tables,
                  active, rng, temperature, max_len, *rest):
            tkv = rest[0] if rest else None
            logits, nk, nv = self._span_core_local(
                params, arena["k"], arena["v"], tokens, seq_lens,
                n_valids, block_tables, active, max_len)
            emitted, n_emitted = _spec_accept(logits, tokens, n_valids,
                                              rng, mode, temperature, tkv)
            return emitted, n_emitted, {"k": nk, "v": nv}

        args = [params, arena, tokens, seq_lens, n_valids, block_tables,
                active, rng, temperature, max_len]
        specs = [self._pspecs, self._aspec] + [P()] * 8
        if top_k_vec is not None:
            args.append(top_k_vec)
            specs.append(P())
        sm = shard_map(local, mesh=self.mesh,
                       in_specs=tuple(specs),
                       out_specs=(P(), P(), self._aspec), check_vma=False)
        return sm(*args)

    # -- prefill ----------------------------------------------------------
    def _prefill_core_local(self, params, ak_all, av_all, tokens, pos0s,
                            n_valids, block_tables, active, total_lens):
        cfg = self.cfg
        tp = self.tp
        NC, C = tokens.shape
        NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        NHl, NKVl = NH // tp, NKV // tp
        bs = ak_all.shape[2]
        nb = ak_all.shape[1]
        MB = block_tables.shape[1]
        H = cfg.hidden_size
        L = cfg.num_layers

        pos0s = jnp.where(active, pos0s, 0)
        n_valids = jnp.where(active, n_valids, 0)
        positions = pos0s[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        valid = (jnp.arange(C)[None] < n_valids[:, None]) & active[:, None]
        blk = jnp.take_along_axis(block_tables,
                                  jnp.clip(positions // bs, 0, MB - 1),
                                  axis=1)
        blk = jnp.where(valid, blk, nb)
        off = positions % bs

        from .ragged_ops import _use_paged_prefill
        use_kernel = _use_paged_prefill(cfg, D, bs, C, 1,
                                        local_heads=NHl)

        x = self._embed_rows(params, tokens.ravel(), positions.ravel())

        def layer(carry, xs):
            x, ak_all, av_all = carry                 # x [NC*C/tp, H]
            lp, li = xs
            h = _norm(x, lp["attn_norm_scale"], lp.get("attn_norm_bias"),
                      cfg.norm, cfg.norm_eps)
            q = self._col(h, lp["wq"], lp.get("bq")).reshape(NC, C, NHl, D)
            k = self._col(h, lp["wk"], lp.get("bk")).reshape(NC, C, NKVl, D)
            v = self._col(h, lp["wv"], lp.get("bv")).reshape(NC, C, NKVl, D)
            if cfg.pos_emb == "rope":
                q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling, regime_len=total_lens)
                k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling, regime_len=total_lens)
            # one batched scatter for every chunk BEFORE the chunk scan
            # (causality masks early keys — ragged_ops.prefill_chunks)
            ak_all = ak_all.at[li, blk, off].set(k, mode="drop")
            av_all = av_all.at[li, blk, off].set(v, mode="drop")

            def chunk_step(_, inp):
                q_i, table_i, pos_i, p0_i, nv_i = inp
                if use_kernel:
                    from ...ops.paged_prefill import paged_prefill_attention
                    attn = paged_prefill_attention(
                        q_i, ak_all, av_all, table_i, p0_i, nv_i,
                        layer_idx=li)
                else:
                    attn = self._gather_attn(
                        q_i[None], ak_all, av_all, table_i[None],
                        pos_i[None], li)[0]
                return (), attn.reshape(C, NHl * D)

            _, attn = jax.lax.scan(
                chunk_step, (),
                (q, block_tables, positions, pos0s, n_valids))
            x = x + self._rowp(attn.reshape(NC * C, NHl * D), lp["wo"],
                               lp.get("bo"))
            x = x + self._mlp_rows(x, lp)
            return (x, ak_all, av_all), None

        (x, new_k, new_v), _ = jax.lax.scan(
            layer, (x, ak_all, av_all), (params["layers"], jnp.arange(L)))
        # each chunk's last valid token: gather the row shards once
        # ([NC*C, H]) — cheaper than a full-row [NC*C, V/tp] head matmul
        x_full = jax.lax.all_gather(x, AXIS_TP, axis=0, tiled=True)
        last = jnp.clip(n_valids - 1, 0, C - 1)
        xl = x_full.reshape(NC, C, H)[jnp.arange(NC), last]
        logits = self._logits_repl(params, xl)        # [NC, V] f32
        return logits, new_k, new_v

    def _prefill_chunks_impl(self, params, arena, tokens, pos0s, n_valids,
                             block_tables, active, total_lens):
        def local(params, arena, tokens, pos0s, n_valids, block_tables,
                  active, total_lens):
            logits, nk, nv = self._prefill_core_local(
                params, arena["k"], arena["v"], tokens, pos0s, n_valids,
                block_tables, active, total_lens)
            return logits, {"k": nk, "v": nv}

        sm = shard_map(local, mesh=self.mesh,
                       in_specs=(self._pspecs, self._aspec) + (P(),) * 6,
                       out_specs=(P(), self._aspec), check_vma=False)
        logits, arena = sm(params, arena, tokens, pos0s, n_valids,
                           block_tables, active, total_lens)
        return logits, greedy_tokens(logits), arena
