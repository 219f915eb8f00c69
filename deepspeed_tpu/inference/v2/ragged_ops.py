"""Paged-KV transformer steps (blocked prefill + batched paged decode).

Reference: `inference/v2/kernels/ragged_ops/` — `blocked_flash/` (flash
attention over a paged KV cache), `linear_blocked_kv_rotary/` (fused
qkv+rotary writing blocked KV), `atom_builder/`, `logits_gather/`; model
forward in `inference/v2/model_implementations/*` over the
`DSStateManager`'s ragged batch.

TPU-native formulation: the KV arena is one stacked array per tensor
([L, num_blocks, block_size, KVH*D] — merged unpadded minor dim, see
init_arena); a sequence's keys are materialized
with one `take` over its block table (XLA lowers this to an efficient
dynamic-gather; the Pallas fused variant can replace the gather+dot without
changing this interface).  Scatter of new keys uses `.at[...].set` with
``mode="drop"`` so padded slots self-discard — no host-side masking.

Two jitted entry points with static shapes, so the whole serving loop runs
as a handful of compiled programs:
- `prefill_chunks`: up to NC chunks of `chunk` tokens each (padded; NC is
  bucketed to powers of two by the engine, one compile per bucket), from
  any mix of sequences — all chunks' keys land in the arena in one
  batched scatter per layer and causality masks what a query may see, so
  consecutive chunks of one prompt stay exact.
- `decode_step`:    `max_seqs` sequences (padded), one token each.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ...models.transformer import (TransformerConfig, _act_fn,
                                   _alibi_slopes, _embed_in, _head_hidden,
                                   _layer_extras, _norm, _rope,
                                   resolve_weight_scaled)
from .families import family_of

PyTree = Any

__all__ = ["init_arena", "prefill_chunks", "prefill_full",
           "prefill_full_supported", "decode_step", "decode_tokens",
           "decode_multi_step", "verify_tokens", "philox_word",
           "seeded_uniform24"]


def init_arena(cfg: TransformerConfig, num_blocks: int, block_size: int,
               topology=None, merged="auto", moe_census: bool = False,
               max_seqs: int = 0):
    """KV arena pytree (reference: ragged/kv_cache.py blocked arena).

    Under tensor parallelism the arena is sharded over tp on the kv-head
    dim, mirroring the reference's per-rank KV allocation
    (inference/v2/model_implementations/sharding/attn.py).

    Layout (`merged`): TPU tiles the last two dims to (8, 128), so a
    separate D<128 minor dim is lane-padded — at D=64 that is physically
    2x the arena bytes in HBM (measured: the 32-seq ctx-2048 arena
    reported 6.05 GiB per array for 3.25 GiB of data).  merged=True
    stores the trailing (kv_heads, head_dim) pair as ONE unpadded
    kv_heads*head_dim minor dim; "auto" merges when head_dim is narrow
    enough to pad AND the padded per-device 5-D footprint exceeds
    ~8 GiB (the serving programs need several GB of temps on top) —
    smaller arenas keep the 5-D layout the fused Pallas kernels consume
    directly.  The serving programs branch on the arena rank.

    The other families make their own (`families.family_of(cfg).
    init_arena`: `latent_ops`, `hybrid_ops` and `ssm_ops` say what each
    holds), none sharded over tp and none with the census rider; a
    static-kind stack takes `num_blocks` as the byte budget of its two kinds
    and sizes the window kind by `max_seqs`, the state-space family its
    state slots."""
    fam = family_of(cfg)
    if fam.shards:
        return fam.init_arena(cfg, num_blocks, block_size, max_seqs,
                              topology, merged, moe_census)
    if (topology is not None and topology.tp_size > 1) or moe_census:
        fam.refuse("an arena sharded over tp or with the expert-paging "
                   "census rider", ValueError)
    return fam.init_arena(cfg, num_blocks, block_size, max_seqs)


def _uniform_arena(cfg: TransformerConfig, num_blocks: int, block_size: int,
                   max_seqs: int = 0, topology=None, merged="auto",
                   moe_census: bool = False):
    """`init_arena` of the uniform family (nothing of it is sized by
    `max_seqs`)."""
    D = cfg.head_dim
    logical = (cfg.num_layers * num_blocks * block_size
               * cfg.kv_heads * D * jnp.dtype(cfg.dtype).itemsize)
    pad_factor = (-(-D // 128) * 128) / D
    if merged == "auto":
        # merge when the PADDED 5-D arena would crowd a 16 GB chip: the
        # serving programs need several GB of temps on top (the big-NC
        # prefill buckets especially — measured: a 13 GiB padded arena
        # OOMs at 21.3 GiB during prefill compile), so the 5-D fused-
        # kernel layout gets the chip only up to ~8 GiB of padded arena.
        # Under tp each device holds 1/tp — judge PER-DEVICE bytes.
        tp = topology.tp_size if topology is not None else 1
        merged = (pad_factor > 1.0
                  and 2 * logical * pad_factor / tp > 8 * 2 ** 30)
    if merged:
        shape = (cfg.num_layers, num_blocks, block_size,
                 cfg.kv_heads * D)
    else:
        shape = (cfg.num_layers, num_blocks, block_size, cfg.kv_heads, D)
    arena = {"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
    if topology is not None and topology.tp_size > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        from ...parallel.mesh import AXIS_TP
        # tp shards contiguous kv-head groups either way
        spec = (PartitionSpec(None, None, None, AXIS_TP) if merged
                else PartitionSpec(None, None, None, AXIS_TP, None))
        s = NamedSharding(topology.mesh, spec)
        arena = jax.tree.map(lambda x: jax.device_put(x, s), arena)
    if moe_census:
        if cfg.moe_experts <= 1:
            raise ValueError(
                "moe_census arena requested for a dense model "
                "(moe_experts <= 1 has no router to count)")
        # per-layer routed-assignment counts + (last col) assignments
        # rerouted off non-resident experts; decode accumulates, the
        # serving loop drains it for the ExpertPool's LRU ranking
        arena["moe_census"] = jnp.zeros(
            (cfg.num_layers, cfg.moe_experts + 1), jnp.int32)
    return arena


def _arena_out(arena, new_k, new_v, census=None):
    """Rebuild the output arena dict, passing every non-k/v rider key
    (moe_census) through unchanged — or accumulated, for the core that
    counts."""
    out = dict(arena)
    out["k"], out["v"] = new_k, new_v
    if census is not None:
        out["moe_census"] = arena["moe_census"] + census
    return out


def _dense(h, w, b=None):
    dt = h.dtype
    mat, post = resolve_weight_scaled(w, dt)
    out = jnp.einsum("sh,hd->sd", h, mat,
                     preferred_element_type=jnp.float32)
    if post is not None:
        out = out * post.astype(jnp.float32)
    out = out.astype(dt)
    if b is not None:
        out = out + b.astype(dt)
    return out


@jax.named_scope("kv_write")
def _kv_write(ak_all, av_all, li, blk, off, k, v, merged: bool):
    """Scatter layer `li`'s new keys and values into the arena at (block,
    offset); a padded slot carries block == nb and drops.  A merged arena
    keeps the NKV*D minor unpadded (init_arena)."""
    if merged:
        k = k.reshape(k.shape[:-2] + (-1,))
        v = v.reshape(v.shape[:-2] + (-1,))
    return (ak_all.at[li, blk, off].set(k, mode="drop"),
            av_all.at[li, blk, off].set(v, mode="drop"))


def _plain_mlp(cfg: TransformerConfig, lp, h):
    dt = h.dtype
    if cfg.activation == "swiglu":
        g = _dense(h, lp["w_gate"])
        u = _dense(h, lp["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    else:
        h = _dense(h, lp["w_up"], lp.get("b_up"))
        h = _act_fn(cfg.activation)(h.astype(jnp.float32)).astype(dt)
    return _dense(h, lp["w_down"], lp.get("b_down"))


@jax.named_scope("mlp")
def _mlp_delta(cfg: TransformerConfig, x, lp, pre_norm: bool = True,
               dense_flag=None):
    """norm -> MLP of `x`, WITHOUT the residual add (the caller places it:
    sequential blocks add to x_attn, parallel blocks — falcon/phi/neox — to
    the layer input alongside the attention output; post-norm blocks pass
    pre_norm=False and norm after the residual instead).  `dense_flag`:
    traced per-layer dense-vs-MoE selector (moe_dense_layers)."""
    h = x if not pre_norm else _norm(x, lp["mlp_norm_scale"],
                                     lp.get("mlp_norm_bias"), cfg.norm,
                                     cfg.norm_eps)
    if cfg.moe_experts > 1:
        # exact-routing MoE (+ shared expert) over this chunk's tokens
        # (reference: qwen_v2_moe / mixtral v2 model implementations)
        from ...models.transformer import _moe_inference
        out = _moe_inference(cfg, lp, h[None])[0]
        if dense_flag is not None:
            out = jnp.where(dense_flag > 0, _plain_mlp(cfg, lp, h), out)
        return out
    return _plain_mlp(cfg, lp, h)


@jax.named_scope("mlp")
def _mlp_delta_census(cfg: TransformerConfig, x, lp, dense_flag=None):
    """`_mlp_delta` (sequential pre-norm form) that also returns this
    layer's router census row [E+1] (see `_moe_inference`); a dense-
    interleaved layer contributes a zero row."""
    h = _norm(x, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"), cfg.norm,
              cfg.norm_eps)
    from ...models.transformer import _moe_inference
    out, census = _moe_inference(cfg, lp, h[None], with_census=True)
    out = out[0]
    if dense_flag is not None:
        df = dense_flag > 0
        out = jnp.where(df, _plain_mlp(cfg, lp, h), out)
        census = jnp.where(df, 0, census)
    return out, census


def _use_paged_kernel(cfg: TransformerConfig, D: int, bs: int,
                      n_tp: int = 1) -> bool:
    """Gate the fused Pallas decode kernel: capability only.

    What the kernel costs on the chip, alone and inside the decode
    program, and how it compares with the dense gather, is in `PERF.md`
    (sections 5 and 6: the qwen2-7b cell and the kernel-alone sweep).
    The kernel serves the FULL key range and every table width: it walks
    a row's live blocks (`ops/paged_attention.py`), so a small arena is a
    short walk (a single block included), which is strictly cheaper than
    materializing the gathered copy.  attn_impl="pallas" forces it
    (raising if the shapes or platform cannot run it — no silent
    fallback), "jnp" is the explicit dense escape hatch.

    No kv-head-count gate is needed: odd counts, one local head of a
    tensor-parallel shard, GQA and MHA at D 64 and 128 all compile under
    Mosaic (`tests/test_tpu_compile.py` holds the real TPU compiler to
    it, the cell's own shape and a 32k table among them) and match the
    dense reference (`tests/test_paged_attention.py`).  A uniform
    `sliding_window` is the kernel's static `window` (its walk starts at
    the window's first block); a window that rides the layer scan as a
    traced scalar (`sliding_window_layers` here) is not."""
    return _gate_fused(
        cfg, _kernel_capable(cfg, D, bs, n_tp),
        reason=f"attn_impl='pallas' requested but the paged decode kernel "
               f"cannot run here (needs TPU, a mesh when tp > 1, "
               f"head_dim % 64 == 0 [got {D}], block_size % 8 == 0 "
               f"[got {bs}], no alibi, no traced per-layer window "
               f"(sliding_window_layers without rope_layers))")


def _kernel_capable(cfg: TransformerConfig, D: int, bs: int,
                    n_tp: int, static_windows: bool = False) -> bool:
    """Capability conditions shared by both fused paged kernels.
    `static_windows`: the caller hands the kernels each layer's window as a
    Python value (a stack whose layer kinds are static).

    n_tp > 1 without a mesh: operands are GSPMD-sharded and a pallas_call
    does not auto-partition, so the dense gather path serves.  WITH a mesh
    the serving programs wrap the kernels in shard_map over tp
    (_shard_mapped_tp) and the kernels run per-shard — callers substitute
    n_tp=1 here in that case."""
    from ...utils.device import on_tpu
    return (on_tpu() and n_tp == 1 and D % 64 == 0 and bs % 8 == 0
            and cfg.pos_emb != "alibi"
            # a window the kernels can take is a Python value
            and (cfg.sliding_window_layers is None or static_windows))


def _shard_mapped_tp(fn, mesh, n_in_specs_headed, layered=False):
    """Run a fused kernel per-tp-shard: q/attention tensors split on the
    head dim, the KV arena on the kv-head dim, small operands replicated.
    Inside each shard the kernel sees local head counts (GQA group size is
    unchanged: NH/tp over NKV/tp).  This is how the fused kernels serve
    tp > 1 — a pallas_call does not auto-partition under GSPMD.
    `layered`: the arena keeps its leading [L] layer dim (the layer index
    is threaded to the kernel as a trailing replicated operand)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import AXIS_TP
    q_spec = P(None, AXIS_TP, None)            # [B or C, NH, D]
    if layered:
        arena_spec = P(None, None, None, AXIS_TP)  # [L, nb, bs, NKV*D]
    else:
        arena_spec = P(None, None, AXIS_TP, None)  # [nb, bs, NKV, D]
    in_specs = (q_spec, arena_spec, arena_spec) + (P(),) * n_in_specs_headed
    # manual over EVERY mesh axis (the default), not just tp: Mosaic
    # refuses to lower a kernel inside a partially-manual region
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=q_spec,
                     check_vma=False)


def _gate_fused(cfg: TransformerConfig, supported: bool,
                reason: str) -> bool:
    """Shared auto/forced dispatch: "jnp" disables (the explicit dense
    escape hatch), "pallas" forces (raising when not capable — a silent
    dense fallback would benchmark/debug the wrong implementation),
    auto serves the kernel wherever it is capable.  The 2048-key
    auto-threshold (and its once-per-kind slow-path warning + 774M
    crash guard) was retired in r7: the full-range kernels serve every
    budget, so "capable" is the whole question."""
    if cfg.attn_impl == "jnp":
        return False
    if cfg.attn_impl == "pallas":
        if not supported:
            raise ValueError(reason + " — a silent dense fallback would "
                             "benchmark/debug the wrong implementation")
        return True
    return supported


def _gate_merged(cfg: TransformerConfig, use_kernel: bool, D: int,
                 n_tp: int, mesh, kernel: str) -> bool:
    """`use_kernel` for a merged arena, which feeds kernels of its own
    (ops/paged_merged: the stripe grid for a chunk or a verify span, packed
    q for decode, which has no window) where the layout qualifies and the
    dense gather elsewhere, under `_gate_fused`'s no-silent-fallback
    contract.  `kernel`: "prefill" | "verify" | "decode"."""
    from ...ops.paged_merged import merged_kernels_supported
    loc = n_tp if mesh is not None else 1
    NH, NKV, decode = cfg.num_heads // loc, cfg.kv_heads // loc, \
        kernel == "decode"
    ok = merged_kernels_supported(
        NH, NKV, D, op="decode" if decode else "prefill") and not (
            decode and cfg.sliding_window is not None)
    if use_kernel and not ok and cfg.attn_impl == "pallas":
        raise ValueError(
            f"attn_impl='pallas' requested but the merged-arena {kernel} "
            f"kernel cannot serve this layout (local heads {NH}/{NKV}, "
            f"head_dim {D}: needs " + (
                "128-aligned packed stripes and no sliding_window)" if decode
                else "head_dim <= 128 and whole 128-lane kv stripes)"))
    return use_kernel and ok


def _use_paged_prefill(cfg: TransformerConfig, D: int, bs: int, C: int,
                       n_tp: int = 1, local_heads: int = 0) -> bool:
    """Gate the fused Pallas blocked-flash prefill kernel: capability
    only.

    Measurements (v5e, 2026-07-30, C=256, bs=64, bf16, direct chained
    timing, two geometries NH16/D64-MHA and NH32/NKV8/D128-GQA):
    - ctx 2048-4096: kernel within noise of the dense gather (0.9-1.1x).
    - ctx 8192: the dense path hits a reproducible XLA-gather cliff —
      kernel 4.9-9.6x faster.
    - ctx 16384: par again (0.9-1.1x), but the kernel never materializes
      the [max_kv, NKV, D] gathered copy or [NH, C, max_kv] f32 scores, so
      its HBM headroom (and thus the context ceiling) is strictly better.
    History: auto-on from 4096 keys (r3) -> 2048 (r4: the dense-GATHER
    prefill program crashes the remote-compile helper at GPT-2-large
    scale, so sub-2048 774M-class prefill was force-routed + guarded) ->
    FULL RANGE (r7: small chunks and verify spans pad to the 8-row query
    tile inside `paged_prefill.prefill_plan`, so the gather program class
    is unreachable under auto and the guard is gone).  attn_impl="pallas"
    forces it wherever *capable* (raising otherwise — no silent
    fallback), "jnp" is the explicit dense escape hatch.
    A uniform sliding window is masked in the kernel, which skips the
    key blocks wholly outside it; alibi is not supported."""
    from ...ops.paged_prefill import prefill_plan
    # under a tp mesh the kernel runs per-shard, so the VMEM-fit check must
    # size the LOCAL head count
    nh = local_heads or cfg.num_heads
    supported = (_kernel_capable(cfg, D, bs, n_tp)
                 and prefill_plan(C, nh, D, bs) is not None)
    return _gate_fused(
        cfg, supported,
        reason=f"attn_impl='pallas' requested but the blocked-flash "
               f"prefill kernel cannot run here (needs TPU, a mesh when "
               f"tp > 1, head_dim % 64 == 0 [got {D}], block_size "
               f"% 8 == 0 [got {bs}], no alibi, no traced per-layer "
               f"window, and a VMEM-fitting query tile "
               f"[got chunk {C}, heads {nh}])")


@jax.named_scope("embed")
def _embed(cfg: TransformerConfig, params, tokens, positions):
    x = _embed_in(cfg, params, tokens, cfg.dtype)
    if cfg.pos_emb == "learned":
        # explicit clip: prefill_full's padded bucket can exceed
        # max_seq_len, and relying on XLA's implicit out-of-bounds
        # gather clamping would make that invariant silent (the engine
        # rejects REAL tokens past max_seq_len before they get here)
        pos = jnp.clip(positions, 0, cfg.max_seq_len - 1)
        x = x + jnp.take(params["pos_embed"], pos, axis=0).astype(cfg.dtype)
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm_scale"], params["embed_norm_bias"],
                  "layernorm", cfg.norm_eps)
    return x


@jax.named_scope("lm_head")
def _lm_logits(cfg: TransformerConfig, params, x):
    if cfg.final_norm:
        x = _norm(x, params["final_norm_scale"],
                  params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)
    x = _head_hidden(params, x, x.dtype)
    head = params.get("lm_head")
    if head is None:
        head = params["tok_embed"].T
    logits = jnp.einsum("sh,hv->sv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"]
    return logits


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
         static_argnames=("n_tp", "mesh"))
def prefill_chunks(cfg: TransformerConfig, params, arena, tokens, pos0s,
                   n_valids, block_tables, active, total_lens=None,
                   n_tp: int = 1, mesh=None, adapter_ids=None, lora=None,
                   slots=None):
    """Advance up to NC prompt chunks in ONE compiled program (the ragged
    composition of Dynamic SplitFuse: reference ragged/ragged_wrapper.py +
    kernels/ragged_ops/atom_builder/ build one batch from many sequences'
    prefill chunks).

    tokens: [NC, C] int32 (padded); pos0s/n_valids: [NC]; block_tables:
    [NC, MB]; active: [NC] bool; total_lens: [NC] full prompt length of
    each chunk's sequence (drives the longrope short/long regime choice so
    every chunk of a long prompt embeds with the factors HF's one-shot
    forward would use); adapter_ids: [NC] int32 LoRA pool slot per chunk
    (< 0 = base model) paired with `lora` = {"a": [L, A, NH*D, r],
    "b": [L, A, r, H]} stacked per-layer factors — the attention output
    projection gains the gather-LoRA epilogue (ops/lora_matmul), and
    `lora=None` traces the exact single-tenant program (the parity
    lock).  Chunks may come from different sequences
    or be consecutive chunks of one long prompt — in scheduling order:
    within each layer the chunks scan sequentially over the shared arena,
    so a later chunk attends keys a former chunk just wrote, while QKV
    projections, MLP and logits batch over all NC*C tokens (better MXU
    shapes than NC separate calls, and NC fewer host dispatches).
    `slots` [NC]: each chunk's state slot, for a family with a row ->
    slot vector alone (`ssm_ops`: the scan starts from the slot where the
    chunk continues a prompt and ends by writing it).
    Returns (logits [NC, V] — last valid token each, their argmax
    [NC] int32 (`greedy_tokens`), arena)."""
    fam = family_of(cfg)
    fam.refuse_lora(lora)
    if fam.prefill_chunks is not _uniform_prefill_chunks:
        return fam.prefill_chunks(
            cfg, params, arena, tokens, pos0s, n_valids, block_tables,
            active, total_lens=total_lens, n_tp=n_tp, mesh=mesh,
            adapter_ids=adapter_ids, lora=lora, slots=slots)
    # the uniform family's body, in this frame (`_decode_core` says why)
    NC, C = tokens.shape
    bs = arena["k"].shape[2]
    nb = arena["k"].shape[1]
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype
    MB = block_tables.shape[1]
    max_kv = MB * bs
    H = cfg.hidden_size

    merged = arena["k"].ndim == 4     # unpadded NKV*D minor (init_arena)
    pos0s = jnp.where(active, pos0s, 0)
    n_valids = jnp.where(active, n_valids, 0)
    positions = pos0s[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # [NC,C]
    valid = (jnp.arange(C)[None] < n_valids[:, None]) & active[:, None]
    x = _embed(cfg, params, tokens.ravel(),
               positions.ravel()).reshape(NC, C, H)

    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, MB - 1), axis=1)
    blk = jnp.where(valid, blk, nb)                       # drop padded slots
    off = positions % bs
    key_pos = (jnp.arange(MB)[:, None] * bs
               + jnp.arange(bs)[None, :]).ravel()         # [max_kv]
    use_kernel = _use_paged_prefill(
        cfg, D, bs, C, 1 if mesh is not None else n_tp,
        local_heads=NH // (n_tp if mesh is not None else 1))
    if merged:
        use_kernel = _gate_merged(cfg, use_kernel, D, n_tp, mesh, "prefill")

    extras = _layer_extras(cfg)
    has_ex = bool(extras)
    has_lora = lora is not None
    if has_lora:
        row_ids = jnp.repeat(jnp.asarray(adapter_ids, jnp.int32), C)

    L = cfg.num_layers

    # arena as scan CARRY with in-place [li, ...] updates — see the
    # matching note in _decode_core: the xs/ys form double-buffers the
    # whole arena per call (the 32-seq serving OOM) and copies per-layer
    # slices for the kernel operands
    def layer(carry, xs):
        x, ak_all, av_all = carry                          # [NC, C, H]
        lp, li = xs[0], xs[1]
        ex = xs[2] if has_ex else {}
        la = xs[-1] if has_lora else None
        win = ex.get("window")
        dflag = ex.get("dense")
        with jax.named_scope("attention"):
            h = (x.reshape(NC * C, H) if cfg.post_norm
                 else _norm(x.reshape(NC * C, H), lp["attn_norm_scale"],
                            lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps))
            q = _dense(h, lp["wq"], lp.get("bq")).reshape(NC, C, NH, D)
            k = _dense(h, lp["wk"], lp.get("bk")).reshape(NC, C, NKV, D)
            v = _dense(h, lp["wv"], lp.get("bv")).reshape(NC, C, NKV, D)
            if cfg.pos_emb == "rope":
                q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling, regime_len=total_lens)
                k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling, regime_len=total_lens)

            # ONE batched scatter for every chunk of this layer, BEFORE the
            # chunk scan: a chunk's keys can sit in the arena early because
            # causality masks any key at a position a query cannot see (later
            # chunks of the same prompt hold strictly higher positions, other
            # sequences' blocks are not in this chunk's table).  Keeping the
            # arena OUT of the inner scan's carry also stops XLA from holding
            # a second full arena buffer for the nested loop — the 2x-arena
            # peak that OOMed 32-seq serving.
            ak_all, av_all = _kv_write(ak_all, av_all, li, blk, off, k, v,
                                       merged)

            def chunk_step(_, inp):
                q_i, table_i, pos_i, p0_i, nv_i = inp
                if use_kernel:
                    if merged:
                        from ...ops.paged_merged import (
                            merged_prefill_attention as _prefill_fn)
                    else:
                        from ...ops.paged_prefill import (
                            paged_prefill_attention as _prefill_fn)
                    if mesh is not None and n_tp > 1:
                        kfn = _shard_mapped_tp(
                            lambda q_, k_, v_, tb_, p0_, nv_, li_:
                            _prefill_fn(
                                q_, k_, v_, tb_, p0_, nv_,
                                sliding_window=cfg.sliding_window,
                                layer_idx=li_),
                            mesh, 4, layered=True)
                        attn = kfn(q_i, ak_all, av_all, table_i, p0_i, nv_i,
                                   jnp.asarray(li))
                    else:
                        attn = _prefill_fn(
                            q_i, ak_all, av_all, table_i, p0_i, nv_i,
                            sliding_window=cfg.sliding_window, layer_idx=li)
                else:
                    idx = li * nb + jnp.clip(table_i, 0, nb - 1)
                    kk = jnp.take(ak_all.reshape(L * nb, bs, NKV * D), idx,
                                  axis=0).reshape(max_kv, NKV, D)
                    vv = jnp.take(av_all.reshape(L * nb, bs, NKV * D), idx,
                                  axis=0).reshape(max_kv, NKV, D)
                    # (the L*nb flatten works for BOTH arena ranks)
                    if NKV != NH:
                        kk = jnp.repeat(kk, NH // NKV, axis=1)
                        vv = jnp.repeat(vv, NH // NKV, axis=1)
                    s = jnp.einsum(
                        "cnd,mnd->ncm", q_i, kk,
                        preferred_element_type=jnp.float32) / math.sqrt(D)
                    if cfg.pos_emb == "alibi":
                        dist = (pos_i[None, :, None]
                                - key_pos[None, None, :]).astype(jnp.float32)
                        slopes = _alibi_slopes(NH)
                        if cfg.alibi_scaled:   # falcon: (qk+alibi)*inv_norm
                            slopes = slopes / math.sqrt(D)
                        s = s - slopes[:, None, None] * jnp.maximum(
                            dist, 0.0)
                    mask = key_pos[None, None, :] <= pos_i[None, :, None]
                    if win is not None:
                        w_eff = jnp.where(win > 0, win, max_kv)
                        mask &= (key_pos[None, None, :]
                                 > pos_i[None, :, None] - w_eff)
                    elif cfg.sliding_window is not None:
                        mask &= (key_pos[None, None, :]
                                 > pos_i[None, :, None] - cfg.sliding_window)
                    s = jnp.where(mask, s, -1e30)
                    p = jax.nn.softmax(s, axis=-1)
                    attn = jnp.einsum("ncm,mnd->cnd", p.astype(dt), vv)
                return (), attn.reshape(C, NH * D)

            # Chunk attentions are data-independent (the scatter above
            # already wrote EVERY chunk's keys; position masking provides
            # causality even between chunks of one prompt), so a parallel
            # vmap is semantically legal here — but MEASURED SLOWER (r5,
            # v5e, 8k prompt, C=256): vmapping the scalar-prefetch pallas
            # kernel halves prefill throughput (13.5k -> 7.5k tok/s; the
            # batching rule's lowering serializes with per-instance arena
            # handling), so the scan stays.  Prefill's distance from the
            # training-forward bound (~9x at medium/8k) is the per-chunk
            # kernel geometry, not the scan ordering; bigger chunks help
            # modestly (C 256 -> 2048 measured +26%).
            _, attn = jax.lax.scan(
                chunk_step, (),
                (q, block_tables, positions, pos0s, n_valids))
            attn_out = _dense(attn.reshape(NC * C, NH * D), lp["wo"],
                              lp.get("bo"))
            if has_lora:
                from ...ops.lora_matmul import lora_delta
                attn_out = attn_out + lora_delta(
                    attn.reshape(NC * C, NH * D), la["a"], la["b"],
                    row_ids).astype(dt)
        x2 = x.reshape(NC * C, H)
        if cfg.parallel_residual:
            x2 = x2 + attn_out + _mlp_delta(cfg, x2, lp)
        elif cfg.post_norm:
            x2 = _norm(x2 + attn_out, lp["attn_norm_scale"],
                       lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
            x2 = _norm(x2 + _mlp_delta(cfg, x2, lp, pre_norm=False),
                       lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                       cfg.norm, cfg.norm_eps)
        else:
            x2 = x2 + attn_out
            x2 = x2 + _mlp_delta(cfg, x2, lp, dense_flag=dflag)
        return (x2.reshape(NC, C, H), ak_all, av_all), None

    scan_xs = ((params["layers"], jnp.arange(L), extras)
               if has_ex else (params["layers"], jnp.arange(L)))
    if has_lora:
        scan_xs = scan_xs + (lora,)
    (x, new_k, new_v), _ = jax.lax.scan(
        layer, (x, arena["k"], arena["v"]), scan_xs)
    last = jnp.clip(n_valids - 1, 0, C - 1)
    xl = x[jnp.arange(NC), last]                           # [NC, H]
    logits = _lm_logits(cfg, params, xl)                   # [NC, V]
    return logits, greedy_tokens(logits), _arena_out(arena, new_k, new_v)


# what the uniform family's record points at (`families.family_of`): the
# functions themselves, whatever a test puts in the modules' names
_uniform_prefill_chunks = prefill_chunks.__wrapped__


def prefill_full_supported(cfg: TransformerConfig) -> bool:
    """Gate for the fresh-full-prompt fast path: the dense causal flash
    path handles the mainstream archs; alibi / sliding windows /
    per-layer window extras keep the chunked path (their masks live in
    the chunk kernels).  Under attn_impl='pallas' the head_dim must be
    flash-capable too — otherwise causal_attention would SILENTLY serve
    the jnp reference here while the chunked path raises, violating the
    no-silent-fallback contract (_gate_fused); such configs stay chunked
    (and get that loud error).  Another family's own program serves every
    configuration of it: a latent stack pads its own head widths for the
    flash path (latent_ops._attend_fresh), and a state-space parallel
    block's attention is plain causal attention at any head width the flash
    path takes or pads (`ssm_ops.prefill_full`).  A static-kind stack has
    none, and one prefill program: a fresh prompt is a chunk at position 0
    of `ops/chunk_attention.py`, which has the window the flash kernel
    lacks and holds no whole sequence of keys in VMEM."""
    own = family_of(cfg).prefill_full
    if own is not _uniform_prefill_full:
        return own is not None
    D = cfg.head_dim
    flash_ok = D % 128 == 0 or D == 64
    return (cfg.pos_emb in ("rope", "learned") and cfg.sliding_window is None
            and cfg.sliding_window_layers is None and not cfg.post_norm
            and not cfg.parallel_residual
            and (cfg.attn_impl != "pallas" or flash_ok))


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def prefill_full(cfg: TransformerConfig, params, arena, tokens, lens,
                 block_tables, active, slots=None):
    """Prefill FRESH full prompts with dense causal flash attention.

    The chunked path (`prefill_chunks`) serializes a per-chunk blocked
    kernel per layer — measured ~9x under the training-forward bound on
    an 8k prompt (r5).  For prompts starting at position 0 whose whole
    length fits this call, chunking buys nothing: attention over the
    prompt IS plain causal self-attention, so this path runs the same
    flash kernel training uses ([NS, S] batched; padded tail positions
    are never attended by valid queries, and their K/V writes drop via
    the position-masked scatter), then scatters each layer's K/V into
    the paged arena for the decode phase.  Measured 5.1x over the
    chunked path at medium/8k (13.0k -> 66.9k tok/s device-side).

    tokens: [NS, S] int32 (zero-padded); lens: [NS]; block_tables:
    [NS, MB]; active: [NS].  Returns (logits [NS, V] at each prompt's
    last token, their argmax [NS] int32 (`greedy_tokens`), arena).

    Invariant: the padded bucket S may EXCEED cfg.max_seq_len (a
    513-token prompt with max_seq_len 768 pads to S=1024), so padded
    tail positions can index past model tables.  This is safe by
    construction, not by XLA's out-of-bounds gather clamping:
    `_embed` explicitly clips learned-position lookups to
    max_seq_len - 1, causality keeps valid queries from attending any
    padded-tail key, the position-masked scatter (`mode="drop"` +
    `blk -> nb` for invalid slots) discards padded K/V writes, and the
    logits slice reads only each prompt's LAST VALID token.
    """
    own = family_of(cfg).prefill_full
    if own is None:
        raise NotImplementedError(
            "this family prefills through prefill_chunks alone "
            "(prefill_full_supported is False)")
    if own is not _uniform_prefill_full:
        return own(cfg, params, arena, tokens, lens, block_tables, active,
                   slots=slots)
    # the uniform family's body, in this frame (`_decode_core` says why)
    from ...ops.attention import causal_attention
    NS, S = tokens.shape
    bs = arena["k"].shape[2]
    nb = arena["k"].shape[1]
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype
    MB = block_tables.shape[1]
    H = cfg.hidden_size
    merged = arena["k"].ndim == 4

    lens = jnp.where(active, lens, 0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (NS, S))
    valid = positions < lens[:, None]
    x = _embed(cfg, params, tokens.ravel(),
               positions.ravel()).reshape(NS, S, H)

    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, MB - 1), axis=1)
    blk = jnp.where(valid, blk, nb)                       # drop padded slots
    off = positions % bs

    extras = _layer_extras(cfg)
    has_ex = bool(extras)
    total_lens = lens

    def layer(carry, xs):
        x, ak_all, av_all = carry                          # [NS, S, H]
        if has_ex:
            lp, li, ex = xs
        else:
            lp, li = xs
            ex = {}
        with jax.named_scope("attention"):
            h = _norm(x.reshape(NS * S, H), lp["attn_norm_scale"],
                      lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
            q = _dense(h, lp["wq"], lp.get("bq")).reshape(NS, S, NH, D)
            k = _dense(h, lp["wk"], lp.get("bk")).reshape(NS, S, NKV, D)
            v = _dense(h, lp["wv"], lp.get("bv")).reshape(NS, S, NKV, D)
            if cfg.pos_emb == "rope":
                q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling, regime_len=total_lens)
                k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling, regime_len=total_lens)
            ak_all, av_all = _kv_write(ak_all, av_all, li, blk, off, k, v,
                                       merged)
            # dense causal self-attention over the prompts — the training
            # flash kernel (GQA handled inside); padded tails are masked by
            # causality + the logits slice below
            attn = causal_attention(q.astype(dt), k.astype(dt), v.astype(dt),
                                    impl=cfg.attn_impl)
            attn_out = _dense(attn.reshape(NS * S, NH * D), lp["wo"],
                              lp.get("bo"))
        x2 = x.reshape(NS * S, H) + attn_out
        x2 = x2 + _mlp_delta(cfg, x2, lp, dense_flag=ex.get("dense"))
        return (x2.reshape(NS, S, H), ak_all, av_all), None

    L = cfg.num_layers
    scan_xs = ((params["layers"], jnp.arange(L), extras)
               if has_ex else (params["layers"], jnp.arange(L)))
    (x, new_k, new_v), _ = jax.lax.scan(
        layer, (x, arena["k"], arena["v"]), scan_xs)
    last = jnp.clip(lens - 1, 0, S - 1)
    xl = x[jnp.arange(NS), last]                           # [NS, H]
    logits = _lm_logits(cfg, params, xl)                   # [NS, V]
    return logits, greedy_tokens(logits), _arena_out(arena, new_k, new_v)


_uniform_prefill_full = prefill_full.__wrapped__


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
         static_argnames=("n_tp", "mesh"))
def decode_step(cfg: TransformerConfig, params, arena, tokens, seq_lens,
                block_tables, active, n_tp: int = 1, mesh=None,
                adapter_ids=None, lora=None, slots=None):
    """One generated token for up to B sequences.

    tokens: [B] int32 (this step's input token per sequence);
    seq_lens: [B] current lengths (new token position); block_tables:
    [B, MB]; active: [B] bool (padded rows inert); n_tp: static tensor-
    parallel degree (only gates the fused kernel — sharding itself flows
    from the operands' NamedShardings); adapter_ids [B] + `lora` stacked
    factors: the per-row gather-LoRA epilogue (see `prefill_chunks`),
    `lora=None` = the exact single-tenant program; `slots` [B]: the rows'
    state slots, for a state-space parallel block alone.  Returns
    (logits [B, V], their argmax [B] int32 (`greedy_tokens`), arena).
    """
    logits, arena = _decode_core(cfg, params, arena, tokens, seq_lens,
                                 block_tables, active, n_tp, mesh,
                                 adapter_ids, lora, slots)
    return logits, greedy_tokens(logits), arena


@jax.named_scope("sample")
def _sample_tokens(logits, key, mode: str, temperature, top_k):
    """On-device sampling (reference: the host-side sampler the v2 engine
    leaves to the client — moving it on-device removes the per-token
    host round-trip entirely).  mode: "greedy" | "sample" | "per_row";
    top_k=0 means no truncation.

    "per_row": `temperature` [B] and `top_k` [B] int32 are traced per-row
    vectors, so ONE burst serves a heterogeneous batch (the serving layer
    mixes greedy and stochastic requests in one compiled program instead
    of one burst per sampling-signature group).  Rows with
    temperature <= 0 take the argmax — bit-identical to mode="greedy"
    for those rows."""
    if mode == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if mode == "per_row":
        from ..sampling import scale_topk_per_row
        t = jnp.asarray(temperature, jnp.float32)
        sampled = jax.random.categorical(
            key, scale_topk_per_row(logits, t, top_k), axis=-1)
        return jnp.where(t <= 0.0, jnp.argmax(logits, axis=-1),
                         sampled).astype(jnp.int32)
    if mode != "sample":
        raise ValueError(
            f"unknown sampling mode {mode!r} (greedy | sample | per_row)")
    from ..sampling import scale_topk
    return jax.random.categorical(
        key, scale_topk(logits, temperature, top_k),
        axis=-1).astype(jnp.int32)


def greedy_tokens(logits):
    """The greedy token of every logits row, chosen by the program that
    made the logits (the per-step programs return it beside them, so a
    greedy row's token crosses to the host as 4 bytes and its logits
    row stays on the device).  The same f32 row, and `jnp.argmax` like
    `np.argmax` takes the first maximum: bit-for-bit the host
    sampler's token for temperature <= 0 (`ServeLoop._sample`)."""
    return _sample_tokens(logits, None, "greedy", None, 0)


@jax.jit
def logits_row(logits, i):
    """Row `i` of a program's [N, V] logits, for a caller that reads one
    (`engine_v2.LogitsRows`).  `i` is traced: one program per logits
    shape, not per row."""
    return logits[i]


# -- counter-based sampling streams (Philox4x64-10 in uint32 lanes) --------
# The serving layer's replayable stochastic decode draws token `position`
# of a seeded request from numpy's Philox bit generator keyed by
# (seed, position) — serving/streaming.seeded_uniform.  To sample on
# device WITHOUT a per-token host round-trip, the same block cipher runs
# here in pure uint32 arithmetic (tier-1 disables x64): every 64-bit
# word is an (hi, lo) uint32 pair and the 64x64 multiplies go through
# 16-bit limbs.  numpy's Generator increments the counter BEFORE the
# first draw, so the word behind seeded_uniform(seed, position) is
# output word 0 of the block at counter (1, 0, 0, 0) — verified
# bit-for-bit against numpy in tests/test_multistep.py.

_PHILOX_M0 = (0xD2E7470E, 0xE14C6C93)   # round multipliers (hi, lo)
_PHILOX_M1 = (0xCA5A8263, 0x95121157)
_PHILOX_W0 = (0x9E3779B9, 0x7F4A7C15)   # key-schedule Weyl constants
_PHILOX_W1 = (0xBB67AE85, 0x84CAA73B)


def _umul32(x, y):
    """Unsigned 32x32 -> 64 multiply as (hi, lo) uint32 via 16-bit
    limbs — every intermediate stays below 2**32, so plain wrapping
    uint32 ops are exact."""
    M = jnp.uint32(0xFFFF)
    xl, xh = x & M, x >> 16
    yl, yh = y & M, y >> 16
    ll, lh, hl, hh = xl * yl, xl * yh, xh * yl, xh * yh
    t = (ll >> 16) + (lh & M) + (hl & M)
    lo = (ll & M) | ((t & M) << 16)
    hi = hh + (lh >> 16) + (hl >> 16) + (t >> 16)
    return hi, lo


def _add64(ah, al, bh, bl):
    """(ah,al) + (bh,bl) mod 2**64 in uint32 lanes."""
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _mul64(ah, al, bh, bl):
    """64x64 -> 128 multiply: four uint32 words, most significant
    first.  Philox only keeps the hi and lo 64-bit halves."""
    p0h, p0l = _umul32(al, bl)
    p1h, p1l = _umul32(al, bh)
    p2h, p2l = _umul32(ah, bl)
    p3h, p3l = _umul32(ah, bh)
    w1 = p0h + p1l
    c = (w1 < p1l).astype(jnp.uint32)
    w1b = w1 + p2l
    c = c + (w1b < p2l).astype(jnp.uint32)
    w2 = p1h + p2h
    d = (w2 < p2h).astype(jnp.uint32)
    w2b = w2 + p3l
    d = d + (w2b < p3l).astype(jnp.uint32)
    w2c = w2b + c
    d = d + (w2c < c).astype(jnp.uint32)
    w3 = p3h + d
    return w3, w2c, w1b, p0l


def philox_word(seed_hi, seed_lo, pos_hi, pos_lo):
    """Output word 0 of the Philox4x64-10 block at counter (1,0,0,0)
    keyed by (seed, position), as an (hi, lo) uint32 pair — the exact
    u64 numpy's Generator(Philox(key=[seed, position])).random() turns
    into a double.  Inputs are uint32 arrays (any matching shape); the
    ten rounds unroll at trace time."""
    z = jnp.zeros_like(seed_hi)
    c0h, c0l = z, jnp.ones_like(seed_hi)      # counter bumped pre-draw
    c1h, c1l, c2h, c2l, c3h, c3l = z, z, z, z, z, z
    k0h, k0l = seed_hi, seed_lo
    k1h, k1l = pos_hi, pos_lo
    m0h, m0l = jnp.uint32(_PHILOX_M0[0]), jnp.uint32(_PHILOX_M0[1])
    m1h, m1l = jnp.uint32(_PHILOX_M1[0]), jnp.uint32(_PHILOX_M1[1])
    w0h, w0l = jnp.uint32(_PHILOX_W0[0]), jnp.uint32(_PHILOX_W0[1])
    w1h, w1l = jnp.uint32(_PHILOX_W1[0]), jnp.uint32(_PHILOX_W1[1])
    for r in range(10):
        if r:
            k0h, k0l = _add64(k0h, k0l, w0h, w0l)
            k1h, k1l = _add64(k1h, k1l, w1h, w1l)
        a3, a2, a1, a0 = _mul64(m0h, m0l, c0h, c0l)
        b3, b2, b1, b0 = _mul64(m1h, m1l, c2h, c2l)
        c0h, c0l = b3 ^ c1h ^ k0h, b2 ^ c1l ^ k0l
        c1h, c1l = b1, b0
        c2h, c2l = a3 ^ c3h ^ k1h, a2 ^ c3l ^ k1l
        c3h, c3l = a1, a0
    return c0h, c0l


def seeded_uniform24(seed_hi, seed_lo, position):
    """f32 uniform in [0, 1) from the TOP 24 bits of the (seed,
    position) Philox word.  The host (serving/streaming.seeded_uniform)
    keeps 53 bits; f32 holds 24 exactly, so this is the host draw
    truncated — never rounded — and the two agree to strictly less than
    2**-24.  `position` is int32/uint32 (token index in the generated
    stream); seed words are uint32."""
    pos = jnp.asarray(position).astype(jnp.uint32)
    hi, _ = philox_word(jnp.asarray(seed_hi).astype(jnp.uint32),
                        jnp.asarray(seed_lo).astype(jnp.uint32),
                        jnp.zeros_like(pos), pos)
    return (hi >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _seeded_pick(scaled_logits, u):
    """Inverse-CDF draw matching serving/streaming.seeded_sample:
    `searchsorted(cumsum(p), u * sum(p), side="right")`, clipped to the
    last bin.  `scaled_logits` [B, V] are the masked/temperature-scaled
    logits (top-k holes at -inf -> probability exactly 0, flat CDF);
    `u` [B] the per-row uniform.  f32 throughout — the host reference
    runs the same formula in f64, so a draw landing within f32 rounding
    of a bin edge can differ; the replay tests pin seeds on the shipped
    configs (docs/serving.md records the caveat)."""
    p = jax.nn.softmax(scaled_logits.astype(jnp.float32), axis=-1)
    cdf = jnp.cumsum(p, axis=-1)
    t = u * cdf[:, -1]
    idx = jnp.sum((cdf <= t[:, None]).astype(jnp.int32), axis=-1)
    return jnp.minimum(idx, cdf.shape[-1] - 1).astype(jnp.int32)


@jax.named_scope("sample")
def _sample_per_row(logits, key, temperature, top_k_vec, seed_hi=None,
                    seed_lo=None, seed_pos=None, has_seed=None,
                    mask=None):
    """mode="per_row" sampling with optional per-row counter-based
    streams: rows flagged by `has_seed` draw token `seed_pos` of their
    (seed) Philox stream via inverse-CDF — replay-deterministic,
    engine-RNG-independent — while unflagged stochastic rows draw from
    `key` and temperature <= 0 rows take the argmax, bit-identical to
    the unseeded per-row program for those rows.
    `mask` [B, V] bool (optional): grammar allowed-token mask
    (serving/structured) — disallowed tokens are -inf for every draw
    path INCLUDING the greedy argmax (an unmasked greedy row would
    walk straight out of the grammar); all-True rows stay
    bit-identical to mask=None."""
    from ..sampling import scale_topk_per_row
    t = jnp.asarray(temperature, jnp.float32)
    scaled = scale_topk_per_row(logits, t, top_k_vec, mask)
    drawn = jax.random.categorical(key, scaled, axis=-1)
    if seed_hi is not None:
        u = seeded_uniform24(seed_hi, seed_lo, seed_pos)
        drawn = jnp.where(has_seed, _seeded_pick(scaled, u), drawn)
    greedy_src = (logits if mask is None
                  else jnp.where(mask, logits, -jnp.inf))
    return jnp.where(t <= 0.0, jnp.argmax(greedy_src, axis=-1),
                     drawn).astype(jnp.int32)


def _fsm_allowed(fsm_mask, fsm_accept, fsm_state, has_fsm, eos_ids, V):
    """[B, V] bool allowed-token mask from the grammar automaton
    tables (serving/structured/automaton.py), ONE gather per row:

    - `fsm_mask` u32[S, W] per-state packed bitmask, `fsm_accept`
      bool[S], gathered by `fsm_state` [B];
    - EOS composition: accept states additionally allow the row's own
      `eos_ids` token (EOS is not a grammar symbol, so one compiled
      table serves requests with different EOS ids; -1 = disabled
      matches no token);
    - dead-state escape: a state with NO emittable token (grammar
      character no vocabulary token covers) falls back to all-True
      rather than a NaN softmax / degenerate argmax — mirrored on
      host by TokenAutomaton.host_mask;
    - rows with `has_fsm` False get all-True, which downstream
      `jnp.where(mask, ...)` turns into the identity — unconstrained
      rows in a constrained dispatch are bit-exact with the
      mask-free program."""
    words = fsm_mask[fsm_state]                             # [B, W] u32
    bits = ((words[:, :, None]
             >> jnp.arange(32, dtype=jnp.uint32)[None, None, :])
            & jnp.uint32(1))
    allowed = bits.reshape(words.shape[0], -1)[:, :V].astype(bool)
    acc = fsm_accept[fsm_state]                             # [B] bool
    iota = jnp.arange(V, dtype=jnp.int32)[None, :]
    allowed = allowed | (acc[:, None] & (iota == eos_ids[:, None]))
    allowed = allowed | ~jnp.any(allowed, axis=-1, keepdims=True)
    return allowed | ~has_fsm[:, None]


@partial(jax.jit, static_argnames=("mode", "top_k"))
def sample_tokens_compiled(logits, key, temperature, top_k_vec=None,
                           seed_hi=None, seed_lo=None, seed_pos=None,
                           has_seed=None, *,
                           mode: str = "greedy", top_k: int = 0):
    """Compiled `_sample_tokens` for EAGER callers (the engine's batched
    first-token sampler).  Two reasons over calling `_sample_tokens`
    directly: the eager op chain re-transfers its python-scalar
    constants (the temperature-clamp epsilon and friends) implicitly on
    every call — which the transfer-guard sanitizer rightly rejects —
    while a compiled program embeds them once at trace time; and the
    scale/top-k/draw chain fuses into one dispatch instead of five.
    mode="per_row" reads the traced `top_k_vec`; scalar modes use the
    static `top_k`.  Optional seed operands (uint32 seed words, [B]
    positions, [B] bool flag) route flagged rows through their
    counter-based Philox streams; passing them changes the pytree
    structure, so the seedless trace stays byte-identical."""
    if mode == "per_row":
        return _sample_per_row(logits, key, temperature, top_k_vec,
                               seed_hi, seed_lo, seed_pos, has_seed)
    if seed_hi is not None:
        raise ValueError(
            "seeded sampling operands need mode='per_row' (the flag "
            "vector decides per row; scalar modes have no row axis)")
    return _sample_tokens(logits, key, mode, temperature, top_k)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
         static_argnames=("n_steps", "mode", "top_k", "n_tp", "mesh"))
def decode_tokens(cfg: TransformerConfig, params, arena, tokens, seq_lens,
                  block_tables, active, rng, temperature=1.0, max_len=None,
                  top_k_vec=None, adapter_ids=None, lora=None,
                  seed_hi=None, seed_lo=None, seed_pos=None,
                  has_seed=None, *,
                  n_steps: int = 8, mode: str = "greedy",
                  top_k: int = 0, n_tp: int = 1, mesh=None):
    """`n_steps` decode iterations in ONE compiled program with on-device
    sampling: sample -> append KV -> feed back, as a `lax.scan`.

    The single-token `decode_step` returns logits and leaves sampling to
    the host — one host round-trip per generated token, which caps decode
    throughput far below the HBM-bandwidth bound.  Here the whole burst
    runs on device; the host only sees `n_steps` sampled tokens per call.
    EOS is handled by the caller (truncate the returned burst) — a frozen
    row would save no time in a lockstep batch.

    tokens/seq_lens/block_tables/active: as `decode_step`; rng: PRNG key
    (ignored under mode="greedy"); temperature: traced scalar — or, under
    mode="per_row", a traced [B] vector paired with `top_k_vec` [B] int32
    (the static `top_k` is ignored then), so one program serves a batch
    of heterogeneous sampling signatures (greedy rows: temperature <= 0).
    `max_len` [B]: per-sequence KV-lease bound — positions clamp to
    max_len-1 so an overshooting tail burst (the engine always runs
    full-size bursts for one compiled shape) re-writes the LAST leased
    slot instead of scribbling into unleased arena blocks; the host trims
    the overshot tokens.
    Optional seed operands (`seed_hi`/`seed_lo` [B] uint32, `seed_pos`
    [B] int32 — the stream index of the FIRST token this burst draws,
    advanced per step on device — `has_seed` [B] bool) route flagged
    rows through their counter-based Philox streams (mode="per_row"
    only); leaving them None keeps the legacy trace byte-identical.
    Returns (tokens [B, n_steps] int32, arena).
    """
    seeded = seed_hi is not None
    if seeded and mode != "per_row":
        raise ValueError(
            "seeded burst decode needs mode='per_row' (per-row seed "
            "flags have no meaning for scalar sampling signatures)")

    def step(carry, xs):
        toks, lens, arena = carry
        key, j = xs if seeded else (xs, None)
        logits, arena = _decode_core(cfg, params, arena, toks, lens,
                                     block_tables, active, n_tp, mesh,
                                     adapter_ids, lora)
        if seeded:
            nxt = _sample_per_row(logits, key, temperature, top_k_vec,
                                  seed_hi, seed_lo, seed_pos + j,
                                  has_seed)
        else:
            nxt = _sample_tokens(logits, key, mode, temperature,
                                 top_k_vec if mode == "per_row" else top_k)
        lens_next = lens + 1
        if max_len is not None:
            lens_next = jnp.minimum(lens_next, max_len - 1)
        return (nxt, lens_next, arena), nxt

    keys = jax.random.split(rng, n_steps)
    xs = (keys, jnp.arange(n_steps, dtype=jnp.int32)) if seeded else keys
    (_, _, arena), toks = jax.lax.scan(
        step, (tokens, seq_lens, arena), xs)
    return jnp.swapaxes(toks, 0, 1), arena


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
         static_argnames=("k", "n_tp", "mesh"))
def decode_multi_step(cfg: TransformerConfig, params, arena, tokens,
                      seq_lens, block_tables, active, rng, temperature,
                      max_len, top_k_vec, eos_ids, budget, seed_hi,
                      seed_lo, seed_pos, has_seed, adapter_ids=None,
                      lora=None, fsm_trans=None, fsm_mask=None,
                      fsm_accept=None, fsm_state=None, has_fsm=None,
                      *, k: int = 8, n_tp: int = 1, mesh=None):
    """Host-free steady-state decode: `k` decode steps in ONE compiled
    dispatch with on-device per-row sampling AND on-device termination.

    Extends `decode_tokens` (whose lockstep burst keeps every row
    decoding all n_steps and leaves EOS to the host) with the step-group
    contract the multi-step serve loop needs:

    - per-row termination masks: a row stops when it samples its
      `eos_ids` token (>= 0; -1 disables EOS) or exhausts `budget` (its
      remaining new-token allowance, <= k).  A stopped row pins its
      length, stops writing KV (it leaves the `_decode_core` active set,
      so its block index masks to the drop slot), and its remaining
      steps emit the -1 pad sentinel;
    - per-row counter-based sampling: rows flagged by `has_seed` draw
      token `seed_pos + emitted` of their (seed) Philox stream
      (`_sample_per_row`), so stochastic streams replay bit-exactly
      without any host round-trip; unflagged stochastic rows use `rng`,
      temperature <= 0 rows take the argmax;
    - one device->host transfer per GROUP: the emissions ride a single
      packed [B, k+1] int32 buffer — k (possibly pad-masked) tokens plus
      the per-row emitted count in the last column — which the engine
      fetches with ONE explicit `jax.device_get`.

    Sampling is always per-row here (`temperature` [B] f32 + `top_k_vec`
    [B] int32): the step-group loop serves heterogeneous batches, and a
    uniform-greedy batch is just temperature == 0 everywhere — those
    rows are bit-identical to `decode_tokens` mode="greedy".
    `max_len` clamps KV positions exactly like `decode_tokens` (defense
    in depth: `budget` already stops rows at the lease bound).

    Optional grammar constraint (serving/structured): `fsm_trans`
    s32[S, V] + `fsm_mask` u32[S, W] + `fsm_accept` bool[S] are ONE
    automaton's device tables, `fsm_state` [B] int32 the per-row FSM
    state ids, `has_fsm` [B] bool the participation flags.  Each step
    gathers the state's allowed-token mask (`_fsm_allowed`) into the
    per-row sampler and advances `state = fsm_trans[state, sampled]`
    INSIDE the scan body — k constrained steps stay this ONE dispatch
    with the same packed fetch (the final states are recomputed on
    host from the emitted tokens, not returned), so the d2h ledger is
    identical to the unconstrained program.  Leaving the five operands
    None keeps the legacy trace byte-identical, exactly like the seed
    and LoRA operands.

    Returns (packed [B, k+1] int32, arena).
    """
    constrained = fsm_trans is not None
    def step(carry, xs):
        if constrained:
            toks, lens, alive, e, st, arena = carry
        else:
            toks, lens, alive, e, arena = carry
        key, j = xs
        live = active & alive
        logits, arena = _decode_core(cfg, params, arena, toks, lens,
                                     block_tables, live, n_tp, mesh,
                                     adapter_ids, lora)
        allowed = (_fsm_allowed(fsm_mask, fsm_accept, st, has_fsm,
                                eos_ids, logits.shape[-1])
                   if constrained else None)
        nxt = _sample_per_row(logits, key, temperature, top_k_vec,
                              seed_hi, seed_lo, seed_pos + e, has_seed,
                              mask=allowed)
        e_next = jnp.where(live, e + 1, e)
        eos_hit = (eos_ids >= 0) & (nxt == eos_ids)
        stop = eos_hit | (e_next >= budget)
        alive_next = alive & ~stop
        lens_next = jnp.where(live, jnp.minimum(lens + 1, max_len - 1),
                              lens)
        toks_next = jnp.where(live, nxt, toks)
        emit = jnp.where(live, nxt, -1)
        if constrained:
            # advance only live constrained rows; an undefined
            # transition (the EOS close, or a dead-state-escape draw)
            # pins the state — TokenAutomaton.walk mirrors this clamp
            # on host so the two trackers can never diverge
            tr = fsm_trans[st,
                           jnp.clip(nxt, 0, fsm_trans.shape[1] - 1)]
            st_next = jnp.where(live & has_fsm & (tr >= 0), tr, st)
            return (toks_next, lens_next, alive_next, e_next, st_next,
                    arena), emit
        return (toks_next, lens_next, alive_next, e_next, arena), emit

    keys = jax.random.split(rng, k)
    xs = (keys, jnp.arange(k, dtype=jnp.int32))
    alive0 = jnp.ones_like(active)
    e0 = jnp.zeros_like(seq_lens)
    if constrained:
        carry0 = (tokens, seq_lens, alive0, e0,
                  jnp.asarray(fsm_state, jnp.int32), arena)
        (_, _, _, e, _, arena), emitted = jax.lax.scan(
            step, carry0, xs)
    else:
        (_, _, _, e, arena), emitted = jax.lax.scan(
            step, (tokens, seq_lens, alive0, e0, arena), xs)
    packed = jnp.concatenate(
        [jnp.swapaxes(emitted, 0, 1), e[:, None]], axis=1)
    return packed, arena


@jax.named_scope("sample")
def _spec_accept(logits, tokens, n_valids, key, mode: str, temperature,
                 top_k_vec, fsm_mask=None, fsm_accept=None,
                 span_states=None, has_fsm=None, fsm_eos=None):
    """On-device accept/reject for a verified draft span.

    logits: [B, S, V] fp32 — position i of row b is the model's
    distribution AFTER consuming tokens[b, :i+1] (the span forward
    conditions each position on the draft prefix before it, which is
    exactly the distribution speculative verification needs: it is only
    read when that prefix was accepted).  tokens: [B, S] — column 0 the
    pending input token, columns 1.. the draft; n_valids: [B] =
    1 + draft length.

    Greedy rows accept draft token i+1 iff it equals argmax(logits_i) —
    the emitted prefix is then BIT-IDENTICAL to the sequential greedy
    chain (the span logits are bitwise the decode_step logits; locked
    by test).  Stochastic rows use standard rejection sampling against
    the point-mass draft: accept d with probability p(d); on reject,
    sample the replacement from p with d masked out (the exact residual
    distribution for a deterministic drafter), so the emitted stream is
    distributed exactly as spec-off sampling — the accepted/bonus
    mixture preserves the target distribution, not the random stream.
    Returns (emitted [B, S] int32, n_emitted [B] int32): row b's tokens
    this dispatch are emitted[b, :n_emitted[b]] — its accepted draft
    prefix plus one replacement/bonus token, so every dispatch emits at
    least 1 and at most n_valids[b] tokens.

    Optional grammar constraint (serving/structured): `span_states`
    [B, S] int32 carries the automaton state BEFORE each span position
    (the host walks the draft prefix — it proposed the draft, so the
    states are known pre-dispatch), and one `_fsm_allowed` gather masks
    the logits at entry.  That single mask constrains every downstream
    read: the greedy target, the acceptance probability, and the
    residual/bonus sample, so a constrained row can only ever emit
    grammar-valid tokens.  Drafts are pre-filtered host-side
    (serving/speculative.filter_draft), so draft tokens are always
    allowed at their position and the rejection math is unchanged."""
    B, S, V = logits.shape
    if fsm_mask is not None:
        allowed = _fsm_allowed(
            fsm_mask, fsm_accept, span_states.reshape(B * S),
            jnp.repeat(has_fsm, S), jnp.repeat(fsm_eos, S),
            V).reshape(B, S, V)
        logits = jnp.where(allowed, logits, -jnp.inf)
    draft_len = n_valids - 1                                      # [B]
    idx = jnp.arange(S, dtype=jnp.int32)[None]                    # [1, S]
    in_draft = idx < draft_len[:, None]                           # [B, S]
    # draft token CHECKED at position i is tokens[:, i+1] (the wrap-in
    # of column 0 only lands where in_draft is False)
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    greedy_tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B, S]
    if mode == "greedy":
        m = (nxt == greedy_tgt) & in_draft
        n_acc = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)
        return greedy_tgt, n_acc + 1
    if mode != "per_row":
        raise ValueError(
            f"unknown verify mode {mode!r} (greedy | per_row)")
    from ..sampling import scale_topk_per_row
    t = jnp.asarray(temperature, jnp.float32)                     # [B]
    k = jnp.asarray(top_k_vec, jnp.int32)                         # [B]
    scaled = scale_topk_per_row(
        logits.reshape(B * S, V),
        jnp.repeat(t, S), jnp.repeat(k, S)).reshape(B, S, V)
    logp = jax.nn.log_softmax(scaled, axis=-1)
    p_d = jnp.exp(jnp.take_along_axis(logp, nxt[..., None],
                                      axis=-1)[..., 0])           # [B, S]
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (B, S))
    stoch_m = u < p_d
    greedy_m = nxt == greedy_tgt
    m = jnp.where((t <= 0.0)[:, None], greedy_m, stoch_m) & in_draft
    n_acc = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)
    # replacement token per position: at a REJECT boundary (inside the
    # draft) sample the residual — target with the rejected draft token
    # masked out; at the full-accept boundary (i == draft_len) sample
    # the bonus from the unmasked target.  Computed at every position,
    # read only at the boundary each row actually reached.
    masked = jnp.where(
        (jax.nn.one_hot(nxt, V, dtype=bool)) & in_draft[..., None],
        -jnp.inf, scaled)
    samp = jax.random.categorical(kr, masked, axis=-1).astype(jnp.int32)
    tail = jnp.where((t <= 0.0)[:, None], greedy_tgt, samp)
    emitted = jnp.where(idx < n_acc[:, None], nxt, tail)
    return emitted.astype(jnp.int32), n_acc + 1


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
         static_argnames=("mode", "n_tp", "mesh"))
def verify_tokens(cfg: TransformerConfig, params, arena, tokens, seq_lens,
                  n_valids, block_tables, active, rng, temperature=0.0,
                  max_len=None, top_k_vec=None, fsm_mask=None,
                  fsm_accept=None, span_states=None, has_fsm=None,
                  fsm_eos=None, *, mode: str = "greedy",
                  n_tp: int = 1, mesh=None):
    """Draft-and-verify: advance up to B sequences by a whole DRAFT SPAN
    in ONE compiled program — forward over [pending token, draft...]
    with the span's KV scattered into the arena, target sampling and
    accept/reject on device (`_spec_accept`).  The host sees only the
    emitted tokens and counts, never the logits.

    The economics vs the sequential burst: one span forward moves every
    weight ONCE for up to S tokens of progress (decode is weight-
    bandwidth-bound, so S sequential decode steps move them S times),
    and its matmuls batch [B*S, H] instead of S skinny [B, H] calls —
    acceptance rate converts that into delivered tokens.

    tokens: [B, S] int32 — column 0 each row's pending input token
    (the decode chaining invariant, as `decode_tokens`), columns 1..
    the drafted continuation, zero-padded; n_valids: [B] = 1 + actual
    draft length (padded columns are never scattered, checked, or
    emitted); seq_lens: [B] the pending token's position; rng ignored
    under mode="greedy"; temperature/top_k_vec: traced [B] vectors
    under mode="per_row" (rows with temperature <= 0 verify greedily).
    `max_len` [B]: per-row KV-lease bound — overshooting span positions
    drop their KV writes (so in-lease positions' KV stays clean within
    the one forward) and the host trims emitted tokens past the cap,
    the span-safe analog of `decode_tokens`' between-step position
    clamp.  S is STATIC: callers
    bucket it to a fixed power of two per config
    (serving.speculative.span_bucket), so every dispatch reuses one
    compiled program regardless of per-row draft lengths.
    Returns (emitted [B, S] int32, n_emitted [B] int32, arena).

    Optional grammar constraint: `fsm_mask`/`fsm_accept` are one
    automaton's device tables, `span_states` [B, S] the per-position
    FSM states (host-walked along the pre-filtered draft), `has_fsm`
    [B] the participation flags, `fsm_eos` [B] the per-row EOS ids
    accept states admit — see `_spec_accept`.  None keeps the
    unconstrained trace byte-identical.

    Stage-2 note: this interface verifies ANY drafted tokens against
    the target model — a small draft model sharing the KV arena plugs
    in by producing `tokens[:, 1:]` and reusing this exact program.
    """
    logits, arena = _span_core(cfg, params, arena, tokens, seq_lens,
                               n_valids, block_tables, active, max_len,
                               n_tp, mesh)
    emitted, n_emitted = _spec_accept(logits, tokens, n_valids, rng,
                                      mode, temperature, top_k_vec,
                                      fsm_mask, fsm_accept, span_states,
                                      has_fsm, fsm_eos)
    return emitted, n_emitted, arena


def _span_core(cfg: TransformerConfig, params, arena, tokens, seq_lens,
               n_valids, block_tables, active, max_len=None,
               n_tp: int = 1, mesh=None):
    """Forward over a [B, S] token span per sequence (the verify step's
    body): `_decode_core` generalized from one token to S consecutive
    positions per row.  Each row's span keys land in the arena BEFORE
    attention (position-masked scatter) and causality masks what a
    query may see, so position i attends its own draft prefix — the
    conditioning speculative verification needs.  Returns
    (logits [B, S, V] at every span position, arena)."""
    fam = family_of(cfg)
    if fam.span_core is None:
        fam.refuse("speculative verify spans (the engine reports "
                   "supports_draft_verify = False)")
    if fam.span_core is not _span_core:
        return fam.span_core(cfg, params, arena, tokens, seq_lens, n_valids,
                             block_tables, active, max_len, n_tp, mesh)
    # the uniform family's body, in this frame (`_decode_core` says why)
    B, S = tokens.shape
    bs = arena["k"].shape[2]
    nb = arena["k"].shape[1]
    MB = block_tables.shape[1]
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype
    max_kv = MB * bs
    H = cfg.hidden_size
    L = cfg.num_layers
    merged = arena["k"].ndim == 4     # unpadded NKV*D minor (init_arena)

    positions = seq_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = (jnp.arange(S)[None] < n_valids[:, None]) & active[:, None]
    if max_len is not None:
        # lease bound: overshooting span positions DROP their KV writes
        # entirely (valid mask) rather than clamp-overwriting the last
        # leased slot mid-forward — a clamp here would clobber an
        # IN-LEASE position's freshly written KV before attention reads
        # it and corrupt the in-lease tokens the host keeps (the
        # sequential decode_tokens can clamp safely only because its
        # clamp lands between steps).  The overshot positions' own
        # logits are garbage and their tokens are trimmed on host.
        valid &= positions < max_len[:, None]
        positions = jnp.minimum(positions, max_len[:, None] - 1)
    x = _embed(cfg, params, tokens.ravel(),
               positions.ravel()).reshape(B, S, H)

    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, MB - 1), axis=1)
    blk = jnp.where(valid, blk, nb)                       # drop padded slots
    off = positions % bs
    key_pos = (jnp.arange(MB)[:, None] * bs
               + jnp.arange(bs)[None, :]).ravel()         # [max_kv]

    # fused-kernel gate: the span is a C=S prefill chunk per row, so the
    # BLOCKED-PREFILL kernel (pos0/n_valid masking) serves it on TPU —
    # the decode kernel is single-query.  Span buckets below the 8-wide
    # minimum query tile (S = 2, 4 — small by construction) pad up to it
    # inside the kernel wrapper (prefill_plan), so EVERY verify span
    # rides the fused path; "jnp" stays the explicit dense escape.
    use_kernel = _use_paged_prefill(
        cfg, D, bs, S, 1 if mesh is not None else n_tp,
        local_heads=NH // (n_tp if mesh is not None else 1))
    if merged:
        use_kernel = _gate_merged(cfg, use_kernel, D, n_tp, mesh, "verify")

    extras = _layer_extras(cfg)
    has_ex = bool(extras)

    # arena as scan CARRY with in-place [li, ...] updates — same
    # rationale as _decode_core (the xs/ys form double-buffers the
    # whole arena per call)
    def layer(carry, xs):
        x, ak_all, av_all = carry                          # [B, S, H]
        if has_ex:
            lp, li, ex = xs
        else:
            lp, li = xs
            ex = {}
        win = ex.get("window")
        dflag = ex.get("dense")
        with jax.named_scope("attention"):
            h = (x.reshape(B * S, H) if cfg.post_norm
                 else _norm(x.reshape(B * S, H), lp["attn_norm_scale"],
                            lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps))
            q = _dense(h, lp["wq"], lp.get("bq")).reshape(B, S, NH, D)
            k = _dense(h, lp["wk"], lp.get("bk")).reshape(B, S, NKV, D)
            v = _dense(h, lp["wv"], lp.get("bv")).reshape(B, S, NKV, D)
            if cfg.pos_emb == "rope":
                q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling)
                k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct,
                          cfg.rope_scaling)
            ak_all, av_all = _kv_write(ak_all, av_all, li, blk, off, k, v,
                                       merged)

            if use_kernel:
                # per-row spans ride the blocked-prefill kernel (pos0 =
                # seq_lens, nv = n_valids), scanned over rows exactly like
                # prefill_chunks' chunk scan
                if merged:
                    from ...ops.paged_merged import (
                        merged_prefill_attention as _prefill_fn)
                else:
                    from ...ops.paged_prefill import (
                        paged_prefill_attention as _prefill_fn)

                def row_step(_, inp):
                    q_i, table_i, p0_i, nv_i = inp
                    if mesh is not None and n_tp > 1:
                        kfn = _shard_mapped_tp(
                            lambda q_, k_, v_, tb_, p0_, nv_, li_:
                            _prefill_fn(
                                q_, k_, v_, tb_, p0_, nv_,
                                sliding_window=cfg.sliding_window,
                                layer_idx=li_),
                            mesh, 4, layered=True)
                        attn = kfn(q_i, ak_all, av_all, table_i, p0_i, nv_i,
                                   jnp.asarray(li))
                    else:
                        attn = _prefill_fn(
                            q_i, ak_all, av_all, table_i, p0_i, nv_i,
                            sliding_window=cfg.sliding_window, layer_idx=li)
                    return (), attn

                _, attn = jax.lax.scan(
                    row_step, (),
                    (q, block_tables, seq_lens, n_valids))
                attn = attn.reshape(B, S, NH, D)
            else:
                idx = li * nb + jnp.clip(block_tables, 0, nb - 1)
                kk = jnp.take(ak_all.reshape(L * nb, bs, NKV * D), idx,
                              axis=0).reshape(B, max_kv, NKV, D)
                vv = jnp.take(av_all.reshape(L * nb, bs, NKV * D), idx,
                              axis=0).reshape(B, max_kv, NKV, D)
                if NKV != NH:
                    kk = jnp.repeat(kk, NH // NKV, axis=2)
                    vv = jnp.repeat(vv, NH // NKV, axis=2)
                # ONE gather serves all S queries of a row — S sequential
                # decode steps would materialize this [B, max_kv] copy S
                # times, the bandwidth the span forward amortizes
                s = jnp.einsum("bsnd,bmnd->bnsm", q, kk,
                               preferred_element_type=jnp.float32
                               ) / math.sqrt(D)
                if cfg.pos_emb == "alibi":
                    dist = (positions[:, None, :, None]
                            - key_pos[None, None, None, :]).astype(jnp.float32)
                    slopes = _alibi_slopes(NH)
                    if cfg.alibi_scaled:   # falcon: (qk+alibi)*inv_norm
                        slopes = slopes / math.sqrt(D)
                    s = s - slopes[None, :, None, None] * jnp.maximum(
                        dist, 0.0)
                mask = key_pos[None, None, None, :] <= positions[:, None, :,
                                                                None]
                if win is not None:
                    w_eff = jnp.where(win > 0, win, max_kv)
                    mask &= (key_pos[None, None, None, :]
                             > positions[:, None, :, None] - w_eff)
                elif cfg.sliding_window is not None:
                    mask &= (key_pos[None, None, None, :]
                             > positions[:, None, :, None]
                             - cfg.sliding_window)
                s = jnp.where(mask, s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                attn = jnp.einsum("bnsm,bmnd->bsnd", p.astype(dt), vv)
            attn_out = _dense(attn.reshape(B * S, NH * D), lp["wo"],
                              lp.get("bo"))
        x2 = x.reshape(B * S, H)
        if cfg.parallel_residual:
            x2 = x2 + attn_out + _mlp_delta(cfg, x2, lp)
        elif cfg.post_norm:
            x2 = _norm(x2 + attn_out, lp["attn_norm_scale"],
                       lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
            x2 = _norm(x2 + _mlp_delta(cfg, x2, lp, pre_norm=False),
                       lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                       cfg.norm, cfg.norm_eps)
        else:
            x2 = x2 + attn_out
            x2 = x2 + _mlp_delta(cfg, x2, lp, dense_flag=dflag)
        return (x2.reshape(B, S, H), ak_all, av_all), None

    scan_xs = ((params["layers"], jnp.arange(L), extras)
               if has_ex else (params["layers"], jnp.arange(L)))
    (x, new_k, new_v), _ = jax.lax.scan(
        layer, (x, arena["k"], arena["v"]), scan_xs)
    logits = _lm_logits(cfg, params, x.reshape(B * S, H))
    return logits.reshape(B, S, -1), _arena_out(arena, new_k, new_v)


def _decode_core(cfg: TransformerConfig, params, arena, tokens, seq_lens,
                 block_tables, active, n_tp: int = 1, mesh=None,
                 adapter_ids=None, lora=None, slots=None):
    """One decode step of the family's stack: (logits [B, V], arena).

    The uniform family's record points at THIS function, and its body runs
    in this frame, as the other three dispatch points' do in theirs: a
    program's trace is mostly its Pallas kernels' bodies (two thirds of a
    `prefill_full` shape's), and one Python frame more between the jitted
    function and the kernel cost each of them 40% (145 -> 200 ms a shape on
    the chip's host, PR 50: `PERF.md` section 6), half a second of a
    set-up."""
    fam = family_of(cfg)
    fam.refuse_lora(lora)
    if fam.row_slots and slots is None:
        fam.refuse("a decode program that hands no row -> slot vector "
                   "(burst, multi-step and draft-verify decode; "
                   "decode_step hands one)")
    if fam.decode_core is not _decode_core:
        return fam.decode_core(cfg, params, arena, tokens, seq_lens,
                               block_tables, active, n_tp=n_tp, mesh=mesh,
                               adapter_ids=adapter_ids, lora=lora,
                               slots=slots)
    B = tokens.shape[0]
    bs = arena["k"].shape[2]
    nb = arena["k"].shape[1]
    MB = block_tables.shape[1]
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dt = cfg.dtype
    max_kv = MB * bs

    merged = arena["k"].ndim == 4     # unpadded NKV*D minor (init_arena)
    positions = seq_lens                                          # [B]
    x = _embed(cfg, params, tokens, positions)                    # [B, H]

    blk = jnp.take_along_axis(block_tables, (positions // bs)[:, None],
                              axis=1)[:, 0]                       # [B]
    blk = jnp.where(active, blk, nb)                              # drop pads
    off = positions % bs
    key_pos = (jnp.arange(MB)[:, None] * bs
               + jnp.arange(bs)[None, :]).ravel()                 # [max_kv]

    extras = _layer_extras(cfg)
    has_ex = bool(extras)
    has_lora = lora is not None
    L = cfg.num_layers
    # census rider: count router assignments per layer (decode steps only
    # — prefill cores pass the buffer through untouched).  MoE excludes
    # parallel_residual/post_norm at config time, so the counting branch
    # below is always the one taken when the arena carries the buffer.
    want_census = "moe_census" in arena

    # The arena rides the layer scan as CARRY (whole [L, nb, bs, NKV, D]
    # buffers updated in place at [li, ...]), NOT as per-layer xs/ys: the
    # xs/ys form makes XLA materialize a per-layer slice for the kernel
    # operand and write back a second full arena — double the arena's HBM
    # footprint and ~2x its bytes in traffic per serving step.  With the
    # carry form the kernels read blocks straight out of the full buffer
    # (layer_idx rides their scalar-prefetch index maps) and the updates
    # are in-place scatters.
    def layer(carry, xs):
        x, ak_all, av_all = carry                                 # [B, H]
        lp, li = xs[0], xs[1]
        ex = xs[2] if has_ex else {}
        la = xs[-1] if has_lora else None
        win = ex.get("window")
        dflag = ex.get("dense")
        with jax.named_scope("attention"):
            h = x if cfg.post_norm else _norm(x, lp["attn_norm_scale"],
                                              lp.get("attn_norm_bias"),
                                              cfg.norm, cfg.norm_eps)
            q = _dense(h, lp["wq"], lp.get("bq")).reshape(B, NH, D)
            k = _dense(h, lp["wk"], lp.get("bk")).reshape(B, NKV, D)
            v = _dense(h, lp["wv"], lp.get("bv")).reshape(B, NKV, D)
            if cfg.pos_emb == "rope":
                q = _rope(q[:, None], positions[:, None], cfg.rope_theta,
                          cfg.rope_pct, cfg.rope_scaling)[:, 0]
                k = _rope(k[:, None], positions[:, None], cfg.rope_theta,
                          cfg.rope_pct, cfg.rope_scaling)[:, 0]
            ak_all, av_all = _kv_write(ak_all, av_all, li, blk, off, k, v,
                                       merged)

            use_kernel = _use_paged_kernel(
                cfg, D, bs, 1 if mesh is not None else n_tp)
            if merged:
                use_kernel = _gate_merged(cfg, use_kernel, D, n_tp, mesh,
                                          "decode")
            if use_kernel:
                # fused Pallas paged attention: the block table is a
                # scalar-prefetch operand whose index map DMAs arena blocks
                # directly — the [B, max_kv] gathered K/V copy below never
                # materializes (measured 1.2-2.9x vs the dense gather on
                # v5e, 2026-07-30)
                if merged:
                    from ...ops.paged_merged import (
                        merged_decode_attention as _decode_fn)
                else:
                    from ...ops.paged_attention import paged_decode_attention
                    _decode_fn = paged_decode_attention
                    if cfg.sliding_window is not None:
                        _decode_fn = partial(paged_decode_attention,
                                             window=cfg.sliding_window)
                lens = jnp.where(active, positions, -1)
                if mesh is not None and n_tp > 1:
                    kfn = _shard_mapped_tp(
                        lambda q_, k_, v_, tb_, ln_, li_:
                        _decode_fn(q_, k_, v_, tb_, ln_, layer_idx=li_),
                        mesh, 3, layered=True)
                    attn = kfn(q, ak_all, av_all, block_tables, lens,
                               jnp.asarray(li)).reshape(B, NH * D)
                else:
                    attn = _decode_fn(
                        q, ak_all, av_all, block_tables, lens,
                        layer_idx=li).reshape(B, NH * D)
            else:
                idx = li * nb + jnp.clip(block_tables, 0, nb - 1)
                kk = jnp.take(ak_all.reshape(L * nb, bs, NKV * D), idx,
                              axis=0).reshape(B, max_kv, NKV, D)
                vv = jnp.take(av_all.reshape(L * nb, bs, NKV * D), idx,
                              axis=0).reshape(B, max_kv, NKV, D)
                if NKV != NH:
                    kk = jnp.repeat(kk, NH // NKV, axis=2)
                    vv = jnp.repeat(vv, NH // NKV, axis=2)
                s = jnp.einsum(
                    "bnd,bmnd->bnm", q, kk,
                    preferred_element_type=jnp.float32) / math.sqrt(D)
                if cfg.pos_emb == "alibi":
                    dist = (positions[:, None, None]
                            - key_pos[None, None, :]).astype(jnp.float32)
                    slopes = _alibi_slopes(NH)
                    if cfg.alibi_scaled:   # falcon: (qk+alibi)*inv_norm
                        slopes = slopes / math.sqrt(D)
                    s = s - slopes[None, :, None] * jnp.maximum(
                        dist, 0.0)
                mask = key_pos[None, None, :] <= positions[:, None, None]
                if win is not None:
                    w_eff = jnp.where(win > 0, win, max_kv)
                    mask &= (key_pos[None, None, :]
                             > positions[:, None, None] - w_eff)
                elif cfg.sliding_window is not None:
                    mask &= (key_pos[None, None, :]
                             > positions[:, None, None] - cfg.sliding_window)
                s = jnp.where(mask, s, -1e30)
                p = jax.nn.softmax(s, axis=-1)
                attn = jnp.einsum("bnm,bmnd->bnd", p.astype(dt),
                                  vv).reshape(B, NH * D)
            attn_out = _dense(attn, lp["wo"], lp.get("bo"))
            if has_lora:
                from ...ops.lora_matmul import lora_delta
                attn_out = attn_out + lora_delta(
                    attn, la["a"], la["b"],
                    jnp.asarray(adapter_ids, jnp.int32)).astype(dt)
        if cfg.parallel_residual:
            x = x + attn_out + _mlp_delta(cfg, x, lp)
        elif cfg.post_norm:
            x = _norm(x + attn_out, lp["attn_norm_scale"],
                      lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
            x = _norm(x + _mlp_delta(cfg, x, lp, pre_norm=False),
                      lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                      cfg.norm, cfg.norm_eps)
        else:
            x = x + attn_out
            if want_census:
                delta, crow = _mlp_delta_census(cfg, x, lp, dense_flag=dflag)
                x = x + delta
                return (x, ak_all, av_all), crow
            x = x + _mlp_delta(cfg, x, lp, dense_flag=dflag)
        return (x, ak_all, av_all), None

    scan_xs = ((params["layers"], jnp.arange(L), extras)
               if has_ex else (params["layers"], jnp.arange(L)))
    if has_lora:
        scan_xs = scan_xs + (lora,)
    (x, new_k, new_v), census = jax.lax.scan(
        layer, (x, arena["k"], arena["v"]), scan_xs)
    # the sh,hv->sv einsum in _lm_logits handles the [B,H] decode batch too
    logits = _lm_logits(cfg, params, x)
    return logits, _arena_out(arena, new_k, new_v,
                              census if want_census else None)
