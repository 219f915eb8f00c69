"""Pipelined inference: stage-sharded layers + collective-permute token
relay (reference `InferenceSchedule`, runtime/pipe/schedule.py:135).

Why this exists: TP serving covers one slice, but a model whose weights
exceed a slice's HBM must also split LAYERS across devices.  The
reference pipelines generation with an InferenceSchedule of micro-batch
commands; the TPU-native formulation is a single compiled program under
`shard_map` manual over the `pp` axis:

- the stacked layer leaves ([L, ...]) are sharded over pp on the layer
  dim — each stage holds L/pp layers and the KV cache for exactly those
  layers (HBM per device drops ~1/pp for weights AND cache);
- micro-batches ROTATE through the stages (B is split into pp groups;
  at tick t stage s runs micro-batch (t - s) mod pp), so after a
  pp-tick warmup every stage computes every tick — the 1/pp idle of
  naive layer-split decoding is gone;
- the relay is one cyclic `ppermute` per tick carrying (activations ->
  next stage, sampled token ids last -> first).  The last stage samples
  (greedy) and the first stage embeds the relayed token — the token
  stream literally travels the ring.

Steady-state throughput: one token per tick aggregate (pp micro-batches
x one token per pp ticks), with each tick costing L/pp layers — the
same FLOPs per token as single-device decode, at 1/pp the per-device
memory.  Latency per token is pp ticks, the standard pipeline tradeoff.

Scope: dense models (no MoE routing or per-layer window extras),
equal-length (padded) prompts, B and L divisible by pp.  Sampling:
greedy by default; `temperature`/`top_k` + `rng` run gumbel-argmax with
a per-(row, step) key discipline (`sample_tokens`) so pipelined and
single-device generation sample IDENTICAL tokens from the same key.
TP composes: on a pp×tp mesh the stage weights are sharded over tp
inside each stage (Megatron column/row rules via sharding constraints
on the auto tp axis; GSPMD inserts the per-layer tp collectives), so a
stage larger than one chip's HBM splits further.  The ragged paged-KV
engine remains the mixed-length serving path.  Attention uses the dense
cache math of models.transformer._layer_decode (reused directly).
"""
from __future__ import annotations

import jax
from jax import shard_map
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.transformer import (TransformerConfig, _embed_in,
                                  _layer_decode, _lm_head, _norm,
                                  tp_rules as _tp_rules)
from ..parallel.mesh import AXIS_PP, MeshTopology
from .sampling import scale_topk

__all__ = ["pp_generate", "sample_tokens"]


def sample_tokens(logits, base_key, step_index, rows, temperature=0.0,
                  top_k=0):
    """Token sampling with a stateless per-(row, step) key discipline.

    logits: [N, V] (any float dtype); rows: [N] GLOBAL row indices;
    step_index: scalar int32, 0-based index of the new token being
    sampled.  temperature <= 0 -> greedy.  Determinism contract: the
    sampled token for (row r, step s) depends only on (base_key, r, s,
    logits row) — the pipelined ring and a single-device loop produce
    identical streams from the same key (tested in test_pp_inference).
    """
    if temperature is None or temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = scale_topk(logits, temperature, top_k)
    step_key = jax.random.fold_in(base_key, step_index)
    keys = jax.vmap(lambda r: jax.random.fold_in(step_key, r))(rows)
    g = jax.vmap(lambda k: jax.random.gumbel(k, l.shape[-1:], jnp.float32))(keys)
    return jnp.argmax(l + g, axis=-1).astype(jnp.int32)


def _stage_layers(cfg: TransformerConfig, params_layers, x, cache_k,
                  cache_v, positions, lens, valid):
    """Run this stage's local layer stack; cache writes masked by
    `valid` (pipeline warmup ticks process placeholder payloads)."""
    def body(carry, layer_in):
        x = carry
        lp, ck, cv = layer_in
        x2, ck2, cv2 = _layer_decode(cfg, x, lp, ck, cv, positions, lens)
        keep = valid  # scalar bool
        ck2 = jnp.where(keep, ck2, ck)
        cv2 = jnp.where(keep, cv2, cv)
        return x2, (ck2, cv2)

    x, (ck, cv) = jax.lax.scan(body, x, (params_layers, cache_k, cache_v))
    return x, ck, cv


def pp_generate(cfg: TransformerConfig, params, topo: MeshTopology,
                prompt_ids, max_new_tokens: int, temperature: float = 0.0,
                top_k: int = 0, rng=None):
    """Pipelined generation (greedy, or sampled when temperature > 0).

    prompt_ids: [B, Sp] int32 — EQUAL-length prompts (the cache is
    written densely for all Sp positions, so ragged rows would attend
    their pad keys; batch same-length requests, the ragged engine
    handles mixed lengths).  Returns [B, max_new_tokens] int32.
    """
    pp = topo.pp_size
    if pp <= 1:
        raise ValueError("pp_generate needs a pp axis > 1 (use the ragged "
                         "engine for single-stage serving)")
    if temperature and temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng=jax.random.PRNGKey")
    if rng is None:
        rng = jax.random.PRNGKey(0)  # unused under greedy
    if cfg.moe_experts > 1 or cfg.sliding_window_layers is not None:
        raise NotImplementedError(
            "pp_generate is the minimal dense pipeline (no MoE / "
            "per-layer windows)")
    if cfg.embed_proj_dim:
        raise NotImplementedError(
            "pp_generate does not thread the embed_out_proj projection "
            "(OPT-350m style embed_proj_dim)")
    B, Sp = prompt_ids.shape
    L = cfg.num_layers
    if B % pp or L % pp:
        raise ValueError(f"B={B} and num_layers={L} must divide pp={pp}")
    Bm = B // pp
    Ls = L // pp
    T = max_new_tokens
    max_len = Sp + T
    dt = cfg.dtype
    NKV, D = cfg.kv_heads, cfg.head_dim
    H = cfg.hidden_size

    def embed(params, ids, positions):
        x = _embed_in(cfg, params, ids, dt)
        if cfg.pos_emb == "learned":
            x = x + jnp.take(params["pos_embed"],
                             jnp.clip(positions, 0, cfg.max_seq_len - 1),
                             axis=0).astype(dt)
        if cfg.embed_norm:
            x = _norm(x, params["embed_norm_scale"],
                      params["embed_norm_bias"], "layernorm", cfg.norm_eps)
        return x

    def head(params, x):
        if cfg.final_norm:
            x = _norm(x, params["final_norm_scale"],
                      params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)
        logits = jnp.einsum("bsh,hv->bsv", x, _lm_head(params).astype(dt),
                            preferred_element_type=jnp.float32)
        if "lm_head_bias" in params:
            logits = logits + params["lm_head_bias"]
        return logits

    fwd_perm = [(s, (s + 1) % pp) for s in range(pp)]

    tp_on = topo.tp_size > 1

    def run(layers_local, rest, prompts, key):
        """shard_map body: manual over pp (tp stays auto; GSPMD shards
        the per-stage math over it); `layers_local` [Ls, ...]."""
        stage = jax.lax.axis_index(AXIS_PP)
        if tp_on:
            # Megatron column/row layout for the stage weights on the
            # AUTO tp axis — GSPMD partitions the matmuls and inserts
            # the per-layer tp collectives (reference: module_inject
            # AutoTP splits, auto_tp.py:193)
            def _tp_constrain(path, leaf):
                spec = _tp_rules(tuple(str(getattr(p, "key", p))
                                       for p in path), leaf.shape)
                if spec is None:
                    return leaf
                return jax.lax.with_sharding_constraint(
                    leaf, NamedSharding(mesh, spec))
            layers_local = jax.tree_util.tree_map_with_path(
                _tp_constrain, layers_local)
        p_local = dict(rest)
        p_local["layers"] = layers_local

        ck0 = jnp.zeros((Ls, B, max_len, NKV, D), dt)
        cv0 = jnp.zeros((Ls, B, max_len, NKV, D), dt)
        lens0 = jnp.zeros((B,), jnp.int32)

        def mb_rows(mb):
            return mb * Bm  # dynamic_slice start of the micro-batch rows

        # ---- phase 1: pipelined prefill (2*pp - 1 ticks) --------------
        def prefill_tick(t, carry):
            x_pay, ck, cv, lens, first = carry
            mb = jnp.mod(t - stage, pp)
            valid = jnp.logical_and(t >= stage, t - stage < pp)
            r0 = mb_rows(mb)
            # stage 0 embeds micro-batch t's prompt; later stages use the
            # relayed payload
            ids = jax.lax.dynamic_slice(prompts, (r0, 0), (Bm, Sp))
            pos = jnp.broadcast_to(
                jnp.arange(Sp, dtype=jnp.int32)[None], (Bm, Sp))
            x_in = jnp.where(stage == 0, embed(p_local, ids, pos), x_pay)
            mb_lens = jnp.zeros((Bm,), jnp.int32)
            ckm = jax.lax.dynamic_slice(
                ck, (0, r0, 0, 0, 0), (Ls, Bm, max_len, NKV, D))
            cvm = jax.lax.dynamic_slice(
                cv, (0, r0, 0, 0, 0), (Ls, Bm, max_len, NKV, D))
            y, ckm, cvm = _stage_layers(cfg, layers_local, x_in, ckm, cvm,
                                        pos, mb_lens, valid)
            ck = jax.lax.dynamic_update_slice(ck, ckm, (0, r0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, cvm, (0, r0, 0, 0, 0))
            lens = jnp.where(valid,
                             jax.lax.dynamic_update_slice(
                                 lens, jnp.full((Bm,), Sp, jnp.int32),
                                 (r0,)),
                             lens)
            # last stage: sample each row's FIRST new token (step 0) —
            # head applied only to the last position's hidden state
            # (the full [Bm, Sp, V] logits tensor would be Sp x the work)
            last = head(p_local, y[:, Sp - 1:Sp])[:, 0]     # [Bm, V]
            tok = sample_tokens(last, key, jnp.zeros((), jnp.int32),
                                r0 + jnp.arange(Bm, dtype=jnp.int32),
                                temperature, top_k)
            is_last = stage == pp - 1
            first = jnp.where(jnp.logical_and(is_last, valid),
                              jax.lax.dynamic_update_slice(first, tok, (r0,)),
                              first)
            x_pay = jax.lax.ppermute(y, AXIS_PP, fwd_perm)
            return x_pay, ck, cv, lens, first

        first0 = jnp.zeros((B,), jnp.int32)
        xp0 = jnp.zeros((Bm, Sp, H), dt)
        _, ck, cv, lens, first = jax.lax.fori_loop(
            0, 2 * pp - 1, prefill_tick, (xp0, ck0, cv0, lens0, first0))
        # every stage needs the first tokens (stage 0 injects them):
        # they live on the last stage — one max-reduce replicates them
        first = jax.lax.pmax(first, AXIS_PP)

        # ---- phase 2: rotating decode (T * pp ticks) ------------------
        # relay payload: (activation [Bm,1,H] s->s+1, token ids [Bm]
        # last->0); records collect (tick, token) at the last stage
        def decode_tick(carry, t):
            x_pay, tok_pay, ck, cv, lens = carry
            mb = jnp.mod(t - stage, pp)
            r0 = mb_rows(mb)
            # stage 0: embed the micro-batch's latest token — relayed
            # from the last stage (or the prefill-sampled first token
            # during the first pp ticks)
            tok_first = jax.lax.dynamic_slice(first, (r0,), (Bm,))
            tok_in = jnp.where(t < pp, tok_first, tok_pay)
            mb_lens = jax.lax.dynamic_slice(lens, (r0,), (Bm,))
            x0 = embed(p_local, tok_in[:, None], mb_lens[:, None])
            x_in = jnp.where(stage == 0, x0, x_pay)
            ckm = jax.lax.dynamic_slice(
                ck, (0, r0, 0, 0, 0), (Ls, Bm, max_len, NKV, D))
            cvm = jax.lax.dynamic_slice(
                cv, (0, r0, 0, 0, 0), (Ls, Bm, max_len, NKV, D))
            # pipeline refill: stage s's first valid decode payload
            # arrives at tick s — placeholder ticks must not touch the
            # cache or advance lens
            valid = t >= stage
            y, ckm, cvm = _stage_layers(cfg, layers_local, x_in, ckm, cvm,
                                        mb_lens[:, None], mb_lens, valid)
            ck = jax.lax.dynamic_update_slice(ck, ckm, (0, r0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, cvm, (0, r0, 0, 0, 0))
            lens = jnp.where(
                valid,
                jax.lax.dynamic_update_slice(lens, mb_lens + 1, (r0,)),
                lens)
            logits = head(p_local, y)[:, 0]                 # [Bm, V]
            # this tick samples the micro-batch's (lens-Sp+1)-th new
            # token (equal-length prompts: every row shares the index)
            s_idx = mb_lens[0] - Sp + 1
            tok_out = sample_tokens(logits, key, s_idx,
                                    r0 + jnp.arange(Bm, dtype=jnp.int32),
                                    temperature, top_k)
            is_last = stage == pp - 1
            rec = jnp.where(is_last, tok_out, 0)
            x_next = jax.lax.ppermute(y, AXIS_PP, fwd_perm)
            tok_next = jax.lax.ppermute(tok_out, AXIS_PP, fwd_perm)
            return (x_next, tok_next, ck, cv, lens), rec

        xd0 = jnp.zeros((Bm, 1, H), dt)
        td0 = jnp.zeros((Bm,), jnp.int32)
        (_, _, _, _, _), recs = jax.lax.scan(
            decode_tick, (xd0, td0, ck, cv, lens),
            jnp.arange(T * pp, dtype=jnp.int32))
        # records live on the last stage; replicate
        recs = jax.lax.pmax(recs, AXIS_PP)                  # [T*pp, Bm]
        return recs, first  # first already replicated after phase 1

    mesh = topo.mesh
    layer_spec = jax.tree.map(lambda _: P(AXIS_PP), params["layers"])
    rest = {k: v for k, v in params.items() if k != "layers"}
    run_sm = shard_map(
        run, mesh=mesh,
        in_specs=(layer_spec, P(), P(), P()),
        out_specs=(P(), P()),
        axis_names=frozenset({AXIS_PP}), check_vma=False)
    recs, first = jax.jit(run_sm)(params["layers"], rest, prompt_ids, rng)

    # de-interleave: decode tick t emits micro-batch (t-(pp-1)) mod pp's
    # token; its k-th NEW token (k >= 1) lands at tick mb + k*pp - 1.
    recs = np.asarray(recs)                                 # [T*pp, Bm]
    first = np.asarray(first)                               # [B]
    out = np.zeros((B, T), np.int32)
    out[:, 0] = first
    for mb in range(pp):
        rows = slice(mb * Bm, (mb + 1) * Bm)
        for k in range(1, T):
            out[rows, k] = recs[mb + k * pp - 1]
    return jnp.asarray(out)
