"""Decoder-only transformer model family (GPT-2 / LLaMA / Mistral-class).

Replaces the reference's model-integration layer: DeepSpeed wraps external HF
torch models (module_inject/ policies per arch — bert, llama, bloom, opt…,
reference: module_inject/replace_policy.py) while here the framework ships
TPU-first implementations directly (the same move the reference's inference
v2 makes with `inference/v2/model_implementations/`).

TPU-first choices:
- **Stacked layers + `lax.scan`**: all L layers' params carry a leading
  layer dim; the forward scans over it.  One compiled layer body instead of L
  inlined copies → O(1) compile time, natural pipeline-stage splitting, and
  XLA double-buffers the per-layer weight allgathers under ZeRO-3.
- **bf16 matmuls on the MXU**, fp32 for softmax/norm accumulation.
- Attention dispatches to the Pallas flash-attention kernel on TPU
  (ops/flash_attention.py) with a pure-jnp fallback elsewhere.
- `jax.checkpoint` (remat) around each layer when activation checkpointing is
  on (reference: runtime/activation_checkpointing/checkpointing.py:488).
- Sequence parallelism: pass ``sp_axis`` to shard attention Ulysses-style
  (parallel/ulysses.py) or ring-style (parallel/ring_attention.py).

Covers both families via config:
  GPT-2:  learned positions, LayerNorm, gelu MLP, tied embeddings
  LLaMA:  rotary, RMSNorm, SwiGLU, untied head, GQA (n_kv_heads)
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..parallel.mesh import AXIS_EP, AXIS_TP

PyTree = Any

__all__ = [
    "TransformerConfig", "Transformer", "gpt2_config", "llama_config",
    "mistral_config", "mixtral_config", "qwen2_config", "qwen2_moe_config",
    "smallthinker_config",
    "falcon_h1_config",
    "phi_config", "phi3_config", "falcon_config", "opt_config",
    "bloom_config", "gptneox_config", "longcat_flash_config",
    "deepseek_v3_config",
]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None          # GQA; None -> num_heads
    intermediate_size: Optional[int] = None     # None -> 4*hidden (gelu) / 8/3*hidden (swiglu)
    max_seq_len: int = 1024
    pos_emb: str = "learned"                    # learned | rope | alibi | none
    # falcon adds alibi BEFORE the 1/sqrt(D) score scaling ((qk+alibi)*inv,
    # modeling_falcon.py eager path), bloom after (baddbmm beta=1) — the
    # 0.1-logit falcon divergence round 2 measured and refused on
    alibi_scaled: bool = False
    norm: str = "layernorm"                     # layernorm | rmsnorm
    activation: str = "gelu"                    # gelu (tanh) | gelu_exact | swiglu | relu
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    rope_pct: float = 1.0                       # partial rotary (phi/neox)
    # scaled RoPE as a hashable tuple (config is a static jit arg):
    #   ("linear", factor)  — position-interpolation (original "linear" HF
    #                         rope_scaling: all inverse freqs / factor)
    #   ("llama3", factor, low_freq_factor, high_freq_factor,
    #    original_max_position_embeddings)
    rope_scaling: Optional[Tuple] = None
    qkv_bias: bool = False                      # qkv biases w/ rmsnorm (qwen2)
    embed_norm: bool = False                    # layernorm after tok embed (bloom)
    head_bias: bool = False                     # bias on the lm head (phi-2)
    # OPT-350m block shape: norms applied AFTER the residual add
    # (do_layer_norm_before=False), embeddings in a narrower space projected
    # in/out of the hidden width, and no final layer norm
    post_norm: bool = False
    embed_proj_dim: Optional[int] = None        # word_embed_proj_dim != H
    final_norm: bool = True
    parallel_residual: bool = False             # attn+mlp from same x (falcon/neox/phi)
    sliding_window: Optional[int] = None        # local attention (mistral)
    # heterogeneous stacks: per-layer window sizes (0 = full attention),
    # length num_layers.  Alone (qwen2's use_sliding_window) the window
    # rides the layer scan as a traced scalar and attention takes the masked
    # jnp path.  With `rope_layers` the layer kinds are STATIC: serving
    # unrolls one period of the pattern inside the layer scan, every kernel
    # sees its window as a Python value, and the cache holds the two kinds
    # apart (inference/v2/hybrid_ops.py)
    sliding_window_layers: Optional[Tuple[int, ...]] = None
    # per-layer rotary flags (1 = rotate q and k, 0 = no position encoding
    # at all), length num_layers, pos_emb "rope": stacks that rotate their
    # window layers and leave their global layers position-free (NoPE)
    rope_layers: Optional[Tuple[int, ...]] = None
    # attention head width where it is not hidden_size / num_heads (0: it
    # is); read through `head_dim`.  The static-kind stack only
    attn_head_dim: int = 0
    norm_eps: float = 1e-5
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16                   # compute dtype for activations
    remat: bool = False                         # activation checkpointing per layer
    attn_impl: str = "auto"                     # auto | pallas | jnp
    # sequence parallel: name of mesh axis to run Ulysses a2a over (None = off)
    sp_axis: Optional[str] = None
    sp_mode: str = "ulysses"                    # ulysses | ring
    # pipeline parallel: mesh axis for SPMD layer pipelining (None = off);
    # requires num_layers % pp == 0 and batch % pp_microbatches == 0
    pp_axis: Optional[str] = None
    pp_microbatches: int = 0                    # 0 -> pp size
    pp_schedule: str = "fill_drain"             # fill_drain | 1f1b
                                                # (runtime/pipeline/spmd.py)
    # mixture-of-experts (reference: moe/layer.py MoE args); >1 turns every
    # layer's MLP into a top-k gated expert layer (Mixtral-style)
    moe_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_weight: float = 0.01
    moe_drop_tokens: bool = True
    # qwen2-moe style shared expert: a dense MLP of this intermediate size
    # runs on every token alongside the routed experts, its output scaled by
    # a learned per-token sigmoid gate (reference:
    # inference/v2/model_implementations/qwen_v2_moe/model.py shared expert)
    moe_shared_expert_ffn: int = 0
    # normalize the selected top-k gate probs to sum to 1 (mixtral: True,
    # HF qwen2-moe default: False — raw softmax probs are used)
    moe_norm_topk_prob: bool = True
    # dispatch form: "einsum" (GShard one-hot contraction, collectives
    # partitioner-inserted) or "a2a" (explicit all_to_all token-buffer
    # exchange manual over the ep axis — reference _AllToAll).  Only the
    # a2a form can ride the quantized wire: moe_dispatch_bits=8/4 block-
    # quantizes the dispatch/combine payloads (ZeRO++-style, LOSSY —
    # opt-in and loss-parity-gated; None = bit-exact)
    moe_dispatch: str = "einsum"
    moe_dispatch_bits: Optional[int] = None
    # qwen2-moe dense-interleaved stacks (mlp_only_layers /
    # decoder_sparse_step): per-layer flags (1 = plain dense MLP instead of
    # the expert layer), length num_layers.  Both MLPs are computed and
    # where-selected per layer — collective-safe under EP sharding, at the
    # cost of the unused branch's FLOPs on mixed stacks
    moe_dense_layers: Optional[Tuple[int, ...]] = None
    dense_intermediate_size: Optional[int] = None   # dense layers' FFN dim
    # ALST/FPDT long-sequence memory knobs (reference: ulysses_sp.py tiled
    # compute :614-:898; fpdt_layer.py chunked attention :510)
    tiled_mlp_shards: int = 1       # >1: chunk seq through the MLP
    tiled_loss_shards: int = 1      # >1: fused logits+loss, no [B,S,V] tensor
    attn_chunk_size: int = 0        # >0: FPDT chunked online-softmax attention
    fpdt_offload: bool = False      # park K/V chunks in host memory (TPU)
    scan_unroll: int = 1            # lax.scan unroll factor over layers
                                    # (larger: XLA schedules across layer
                                    # boundaries; costs compile time)
    # latent (MLA) attention, on when kv_lora_rank > 0: queries go through a
    # q_lora_rank bottleneck, keys and values are up-projections of ONE
    # kv_lora_rank-wide normed latent per token, and a qk_rope_head_dim-wide
    # rotary key is shared by all heads.  The serving cache holds
    # [latent | rotary key] per token and attention, nothing per head
    # (inference/v2/latent_ops.py).  mla_scale_*: multiply q by
    # sqrt(hidden/q_lora_rank) and the latent by sqrt(hidden/kv_lora_rank).
    # A latent layer rotates the pairs (2i, 2i+1).  Under `rope_scaling`
    # ("yarn", factor, attention_factor, beta_fast, beta_slow, original
    # length) it rotates by the blended frequencies, scales cos/sin by
    # attention_factor and its scores by m^2, m = 0.1 mla_yarn_mscale_all_dim
    # ln(factor) + 1 (the softmax temperature of the long context)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    mla_yarn_mscale_all_dim: float = 0.0
    # the latent stack's form.  "shortcut": the shortcut-connected double
    # block: TWO attention + dense-FFN sub-blocks a layer; the MoE reads
    # the first sub-block's post-attention norm and its output joins the
    # residual after the second FFN.  "single": ONE attention a layer, then
    # a dense FFN on the first `latent_dense_layers` layers (a static
    # prefix of the stack with its own stacked weights, not
    # `moe_dense_layers`' both-branch select) and, on the others, the
    # routed experts beside a shared expert of `moe_shared_expert_ffn`
    # (plain, on every token, no gate of its own)
    latent_form: str = "shortcut"               # shortcut | single
    latent_dense_layers: int = 0
    moe_expert_ffn: int = 0         # routed experts' width (intermediate_size
                                    # is the dense FFNs')
    # the latent stack's router, as data (inference/v2/latent_ops.py
    # `router_of`): scores softmax or sigmoid of the logits; router outputs
    # past moe_experts that return their input ("identity" zero-compute
    # experts); selection by score + a bias buffer; the choice limited to
    # the experts of the `moe_router_groups_kept` best of
    # `moe_router_groups` equal groups, a group scored by the sum of its 2
    # largest biased scores (0: no groups); weights from the unbiased
    # score (renormalised over the picks under moe_norm_topk_prob) times
    # moe_routed_scaling
    moe_router_scores: str = "softmax"          # softmax | sigmoid
    moe_zero_experts: int = 0
    moe_router_bias: bool = False
    moe_router_groups: int = 0
    moe_router_groups_kept: int = 0
    moe_routed_scaling: float = 1.0
    # the share of the routed experts THIS program holds: experts
    # [first, first + count) (count 0: all).  The router still scores
    # every expert; assignments to absent ones are another chip's work
    moe_expert_first: int = 0
    moe_expert_count: int = 0
    # a state-space (Mamba-2) mixer, on when ssm_state > 0: served over
    # per-sequence state slots beside the paged K/V
    # (inference/v2/ssm_ops.py).  `ssm_layout` is ONE PERIOD of layer
    # kinds, repeated over the stack: "ssm" (the mixer alone), "attn"
    # (grouped-query attention alone) or "both" (the two side by side on
    # one input norm, both added to the residual); None: every layer
    # "both" (Falcon-H1); nine "ssm" to one "attn" is Granite-4.0-H.  A
    # layer holds only its kind's state: a slot's rows count the layers
    # with a mixer, a block's the layers with attention.  After the mixer
    # branch comes the FFN: the gated MLP, or (moe_experts > 1) the routed
    # experts of moe_expert_ffn held here (moe_expert_first/count) beside
    # a shared expert of moe_shared_expert_ffn.  ssm_heads heads of
    # ssm_head_dim channels, a state of ssm_state values a channel, B and
    # C shared by the heads of a group, a causal depthwise convolution
    # over the last ssm_conv positions of [x | B | C], prompts scanned in
    # chunks of ssm_chunk positions.  pos_emb "rope" rotates q and k,
    # "none" leaves them as projected.  The state is per SEQUENCE and of
    # fixed size.
    # The multipliers are the published scalars, applied where the
    # published modelling code applies them: ssm_multipliers on the column
    # ranges [z | x | B | C | dt] of the mixer's in-projection,
    # mlp_multipliers on (the gate's pre-activation, the FFN's output),
    # residual_multiplier on every branch as it joins the residual stream,
    # attention_multiplier on the scores in 1/sqrt(head_dim)'s place (0:
    # that), lm_head_multiplier on the logits (a published
    # `logits_scaling` is its inverse)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_layout: Optional[Tuple[str, ...]] = None
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)

    def __post_init__(self):
        # static feature-compat checks: fail at config time, not with silently
        # wrong attention output (or a trace-time broadcast crash) later
        if self.attn_chunk_size and (self.pos_emb == "alibi"
                                     or self.sliding_window
                                     or self.sliding_window_layers):
            raise ValueError(
                "attn_chunk_size (FPDT chunked attention) does not support "
                "alibi bias or sliding-window masking yet")
        if self.sliding_window_layers is not None:
            if len(self.sliding_window_layers) != self.num_layers:
                raise ValueError(
                    f"sliding_window_layers has "
                    f"{len(self.sliding_window_layers)} entries for "
                    f"{self.num_layers} layers")
            if self.sliding_window is not None:
                raise ValueError(
                    "set either sliding_window (homogeneous) or "
                    "sliding_window_layers (per-layer), not both")
            if self.sp_axis is not None and self.sp_mode == "ring":
                raise ValueError(
                    "sliding_window_layers is not supported with RING "
                    "sequence parallelism (per-chunk window masking is not "
                    "wired into the ring loop; use sp_mode='ulysses')")
        if self.sp_axis is not None:
            if self.sp_mode == "ring" and (self.pos_emb == "alibi"
                                           or self.sliding_window):
                raise ValueError(
                    "ring sequence parallelism does not support alibi or "
                    "sliding_window")
            if self.sp_mode != "ring" and self.pos_emb == "alibi":
                raise ValueError(
                    "Ulysses SP shards heads; the global-head alibi bias is "
                    "not head-shard-aware yet")
        if self.parallel_residual and self.moe_experts > 1:
            raise ValueError(
                "parallel_residual (falcon/neox/phi block) with MoE is not "
                "supported")
        if self.moe_dense_layers is not None:
            if self.moe_experts <= 1:
                raise ValueError(
                    "moe_dense_layers requires moe_experts > 1 (it marks "
                    "which layers of an MoE stack are dense)")
            if len(self.moe_dense_layers) != self.num_layers:
                raise ValueError(
                    f"moe_dense_layers has {len(self.moe_dense_layers)} "
                    f"entries for {self.num_layers} layers")
            # sliding_window_layers composes: both ride the _layer_extras
            # dict through every forward path (a qwen2-moe with
            # heterogeneous windows and dense-interleave uses both)
            if self.dense_intermediate_size is None:
                raise ValueError(
                    "moe_dense_layers needs dense_intermediate_size (the "
                    "dense layers' FFN width — usually different from the "
                    "per-expert moe width)")
        if self.moe_dispatch not in ("einsum", "a2a"):
            raise ValueError(
                f"moe_dispatch must be 'einsum' or 'a2a', "
                f"got {self.moe_dispatch!r}")
        if self.moe_dispatch_bits is not None:
            if self.moe_dispatch != "a2a":
                raise ValueError(
                    "moe_dispatch_bits requires moe_dispatch='a2a' (the "
                    "einsum form's collectives are partitioner-inserted "
                    "and cannot ride the quantized wire)")
            if self.moe_dispatch_bits not in (4, 8):
                raise ValueError(
                    f"moe_dispatch_bits must be 4 or 8, "
                    f"got {self.moe_dispatch_bits}")
        if self.moe_shared_expert_ffn and self.moe_experts <= 1:
            raise ValueError(
                "moe_shared_expert_ffn requires moe_experts > 1 (the shared "
                "expert runs alongside routed experts; a dense model would "
                "silently ignore it)")
        if self.post_norm and (self.parallel_residual
                               or self.moe_experts > 1):
            raise ValueError(
                "post_norm (OPT-350m block) supports only the sequential "
                "dense block")
        if self.latent:
            if not (self.q_lora_rank and self.qk_nope_head_dim
                    and self.qk_rope_head_dim and self.v_head_dim):
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
            if not (self.moe_experts > 1
                    and self.moe_expert_ffn and self.pos_emb == "rope"
                    and self.norm == "rmsnorm"
                    and self.activation == "swiglu"
                    and self.sliding_window is None
                    and self.sliding_window_layers is None
                    and self.moe_dense_layers is None
                    and not self.qkv_bias and self.tie_embeddings is False
                    and (self.rope_scaling is None
                         or self.rope_scaling[0] == "yarn")):
                raise ValueError(
                    "latent attention is served in two forms of MoE stack, "
                    "the shortcut-connected double block and the "
                    "single-attention layer with leading dense layers "
                    "(latent_form), both with moe_experts > 1, "
                    "moe_expert_ffn, rope (plain or yarn), rmsnorm, swiglu, "
                    "no window, no qkv bias, no moe_dense_layers, untied "
                    "head")
            single = self.latent_form == "single"
            if self.latent_form not in ("shortcut", "single") or not (
                    0 <= self.latent_dense_layers
                    < (self.num_layers if single else 1)):
                raise ValueError(
                    f"latent_form {self.latent_form!r} with "
                    f"{self.latent_dense_layers} leading dense of "
                    f"{self.num_layers} layers: the form is 'shortcut' "
                    f"(every layer a double block, no dense prefix) or "
                    f"'single' (a dense prefix shorter than the stack)")
            if self.moe_shared_expert_ffn and not single:
                raise ValueError(
                    "the shortcut-connected double block has no shared "
                    "expert (its dense FFNs run on every token); "
                    "moe_shared_expert_ffn belongs to latent_form='single'")
            if self.moe_router_scores not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"moe_router_scores must be 'softmax' or 'sigmoid', "
                    f"got {self.moe_router_scores!r}")
            if self.moe_router_groups and not (
                    self.moe_experts % self.moe_router_groups == 0
                    and 0 < self.moe_router_groups_kept
                    <= self.moe_router_groups
                    and self.moe_experts // self.moe_router_groups >= 2
                    and not self.moe_zero_experts
                    and self.moe_top_k <= self.moe_router_groups_kept
                    * (self.moe_experts // self.moe_router_groups)):
                raise ValueError(
                    f"moe_router_groups={self.moe_router_groups} (kept "
                    f"{self.moe_router_groups_kept}) must divide the "
                    f"{self.moe_experts} routed experts into groups of at "
                    f"least 2, keep between 1 and all of them, leave "
                    f"top_k={self.moe_top_k} experts to pick among the "
                    f"kept, and stand without identity experts")
            if not (0 <= self.moe_expert_first
                    and self.moe_expert_first + self.local_experts
                    <= self.moe_experts):
                raise ValueError(
                    f"experts [{self.moe_expert_first}, "
                    f"{self.moe_expert_first + self.local_experts}) are not "
                    f"among the {self.moe_experts} routed experts")
        elif (self.moe_zero_experts or self.moe_router_bias
              or self.moe_router_groups or self.latent_dense_layers
              or self.moe_router_scores != "softmax"
              or ((self.moe_expert_count or self.moe_expert_first)
                  and not self.ssm)):
            raise ValueError(
                "moe_zero_experts, moe_router_bias, moe_router_scores, "
                "moe_router_groups and latent_dense_layers exist only in "
                "the latent-attention double block and the single-attention "
                "latent layer (kv_lora_rank > 0), the expert share "
                "(moe_expert_first/count) there and behind a state-space "
                "mixer (ssm_state > 0)")
        if self.rope_layers is not None:
            wins = set(self.sliding_window_layers or ())
            if not (len(self.rope_layers) == self.num_layers
                    and self.sliding_window_layers is not None
                    and 0 in wins and len(wins) == 2
                    and self.pos_emb == "rope" and self.norm == "rmsnorm"
                    and self.activation in ("swiglu", "reglu")
                    and self.moe_experts > 1 and self.moe_expert_ffn
                    and not self.latent and not self.qkv_bias
                    and self.tie_embeddings is False
                    and self.moe_dense_layers is None
                    and not self.moe_shared_expert_ffn
                    and self.rope_scaling is None and self.rope_pct == 1.0):
                raise ValueError(
                    "rope_layers (static layer kinds) is served as the "
                    "window + global MoE stack only: one flag a layer, "
                    "sliding_window_layers with one window size and at "
                    "least one full-attention layer, rope, rmsnorm, a gated "
                    "activation, moe_experts > 1 with moe_expert_ffn, no "
                    "qkv bias, untied head, no dense or shared-expert "
                    "layers, no rope scaling")
        elif (self.attn_head_dim and not self.ssm) \
                or self.activation == "reglu":
            raise ValueError(
                "attn_head_dim exists only in the static-kind stack "
                "(rope_layers) and the state-space parallel block "
                "(ssm_state), the 'reglu' experts only in the former")
        if self.ssm:
            period = self.ssm_period
            experts = self.moe_experts > 1
            if not (self.ssm_heads and self.ssm_head_dim
                    and self.ssm_groups >= 1
                    and self.ssm_heads % self.ssm_groups == 0
                    and self.ssm_conv >= 2 and self.ssm_chunk >= 1
                    and len(self.ssm_multipliers) == 5
                    and len(self.mlp_multipliers) == 2
                    and period and self.num_layers % len(period) == 0
                    and all(k in ("ssm", "attn", "both") for k in period)
                    and any(k != "attn" for k in period)
                    and self.pos_emb in ("rope", "none")
                    and self.norm == "rmsnorm"
                    and self.activation == "swiglu"
                    and not self.latent
                    and self.rope_layers is None
                    and self.sliding_window is None
                    and self.sliding_window_layers is None
                    and self.moe_dense_layers is None
                    and not self.qkv_bias
                    and self.rope_scaling is None and self.rope_pct == 1.0
                    and not self.post_norm and not self.embed_proj_dim
                    and (bool(self.moe_expert_ffn) if experts else not (
                        self.moe_expert_ffn or self.moe_expert_count
                        or self.moe_expert_first))
                    and 0 <= self.moe_expert_first
                    and self.moe_expert_first + self.local_experts
                    <= self.moe_experts):
                raise ValueError(
                    "the state-space family (ssm_state > 0: a mixer alone, "
                    "attention alone, or the state-space parallel block of "
                    "both) is served in one form: ssm_heads heads of "
                    "ssm_head_dim in ssm_groups equal groups, a convolution "
                    "of at least 2 positions, five ssm_multipliers and two "
                    "mlp_multipliers; ssm_layout one period of 'ssm' | "
                    "'attn' | 'both' that divides num_layers and has a "
                    "mixer; full causal attention with plain rope or no "
                    "position encoding (pos_emb 'none'), rmsnorm, no "
                    "biases on the projections; then a dense swiglu FFN, "
                    "or moe_experts > 1 of moe_expert_ffn with the share "
                    "[moe_expert_first, + moe_expert_count) among them and "
                    "an optional plain shared expert")
        elif (self.ssm_layout is not None or self.residual_multiplier != 1.0
              or self.attention_multiplier):
            raise ValueError(
                "ssm_layout, residual_multiplier and attention_multiplier "
                "exist only in the state-space family (ssm_state > 0)")
        if self.embed_proj_dim and self.tiled_loss_shards > 1:
            raise ValueError(
                "tiled_loss_shards with embed_proj_dim is not supported: "
                "the fused tiled loss consumes hidden states directly and "
                "would skip the embed-out projection")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_attentions(self) -> int:
        """Attentions of a latent stack (rows of its arena a token)."""
        return self.num_layers * (2 if self.latent_form == "shortcut" else 1)

    @property
    def latent_width(self) -> int:
        """Values cached per token and attention: [latent | rotary key]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def local_experts(self) -> int:
        return self.moe_expert_count or self.moe_experts

    @property
    def ssm(self) -> bool:
        """State-space mixers in the stack (in every layer, or in the
        layers `ssm_layout` gives them): served over per-sequence state
        slots beside the paged K/V."""
        return self.ssm_state > 0

    @property
    def ssm_period(self) -> Tuple[str, ...]:
        """One period of layer kinds: "ssm" | "attn" | "both"."""
        return tuple(self.ssm_layout) if self.ssm_layout else ("both",)

    @property
    def ssm_state_layers(self) -> int:
        """Layers with a mixer: the rows of a state slot."""
        period = self.ssm_period
        return self.num_layers // len(period) \
            * sum(k != "attn" for k in period)

    @property
    def ssm_attn_layers(self) -> int:
        """Layers with attention: the rows of a K/V block."""
        period = self.ssm_period
        return self.num_layers // len(period) \
            * sum(k != "ssm" for k in period)

    @property
    def ssm_width(self) -> int:
        """The mixer's channels (heads x their width)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """What the convolution runs over: [x | B | C]."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    @property
    def static_kinds(self) -> bool:
        """Layers of statically known kinds (window or global, rotary or
        not), each with a router that scores the layer's INPUT (before the
        input norm and attention): served over a two-kind paged cache."""
        return self.rope_layers is not None

    @property
    def layer_period(self) -> Tuple[Tuple[int, int], ...]:
        """(window, rope flag) of the layers of ONE period of a static-kind
        stack: the shortest prefix of the pattern that, repeated, gives the
        whole stack."""
        kinds = tuple(zip(self.sliding_window_layers, self.rope_layers))
        for p in range(1, self.num_layers + 1):
            if self.num_layers % p == 0 \
                    and kinds == kinds[:p] * (self.num_layers // p):
                return kinds[:p]
        return kinds

    @property
    def window(self) -> int:
        """The window layers' size of a static-kind stack (0: none)."""
        return max(self.sliding_window_layers or (0,))

    @property
    def ffn_dim(self) -> int:
        if self.intermediate_size:
            return self.intermediate_size
        if self.activation == "swiglu":
            # llama convention: 2/3 * 4h rounded to 256
            d = int(8 * self.hidden_size / 3)
            return 256 * ((d + 255) // 256)
        return 4 * self.hidden_size


def gpt2_config(size: str = "small", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     max_seq_len=512, vocab_size=1024),
        "small": dict(hidden_size=768, num_layers=12, num_heads=12),
        "medium": dict(hidden_size=1024, num_layers=24, num_heads=16),
        "large": dict(hidden_size=1280, num_layers=36, num_heads=20),
        "xl": dict(hidden_size=1600, num_layers=48, num_heads=25),
        # a 1.3B-class shape of this repository's own (16 heads of 128: no
        # published GPT-2 has it); tests size memory and kernels with it
        "1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16, max_seq_len=2048),
    }
    base = dict(vocab_size=50304, pos_emb="learned", norm="layernorm",
                activation="gelu", tie_embeddings=True, max_seq_len=1024)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def llama_config(size: str = "7b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=4,
                     max_seq_len=512, vocab_size=32000),
        "1b": dict(hidden_size=2048, num_layers=22, num_heads=32, num_kv_heads=4,
                   max_seq_len=2048, vocab_size=32000),
        "7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                   max_seq_len=4096, vocab_size=32000),
        "13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                    max_seq_len=4096, vocab_size=32000),
        "70b": dict(hidden_size=8192, num_layers=80, num_heads=64, num_kv_heads=8,
                    intermediate_size=28672, max_seq_len=4096, vocab_size=32000),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


# Per-arch configs mirroring the reference's supported model families
# (module_inject/replace_policy.py policies; inference/v2/model_implementations
# llama_v2 / mistral / mixtral / falcon / opt / phi / qwen_v2{,_moe}).
def mistral_config(size: str = "7b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=2,
                     max_seq_len=512, sliding_window=256),
        "7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                   num_kv_heads=8, intermediate_size=14336, max_seq_len=8192,
                   sliding_window=4096),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, vocab_size=32000)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def mixtral_config(size: str = "8x7b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=2,
                     max_seq_len=512, moe_experts=4, moe_top_k=2),
        "8x7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                     num_kv_heads=8, intermediate_size=14336, max_seq_len=8192,
                     moe_experts=8, moe_top_k=2),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, vocab_size=32000)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def qwen2_config(size: str = "7b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=2,
                     max_seq_len=512),
        "7b": dict(hidden_size=3584, num_layers=28, num_heads=28,
                   num_kv_heads=4, intermediate_size=18944, max_seq_len=8192),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, vocab_size=151936, qkv_bias=True,
                rope_theta=1000000.0)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def qwen2_moe_config(size: str = "a2.7b", **kw) -> TransformerConfig:
    """Qwen2-MoE (reference: inference/v2/model_implementations/qwen_v2_moe):
    routed experts with a small per-expert FFN plus an always-on shared
    expert behind a sigmoid gate."""
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     num_kv_heads=4, max_seq_len=512, vocab_size=1024,
                     intermediate_size=128, moe_experts=4, moe_top_k=2,
                     moe_shared_expert_ffn=256),
        # Qwen1.5-MoE-A2.7B geometry
        "a2.7b": dict(hidden_size=2048, num_layers=24, num_heads=16,
                      num_kv_heads=16, intermediate_size=1408,
                      max_seq_len=8192, vocab_size=151936, moe_experts=60,
                      moe_top_k=4, moe_shared_expert_ffn=5632),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, qkv_bias=True, rope_theta=1000000.0,
                moe_norm_topk_prob=False)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def phi3_config(size: str = "mini", **kw) -> TransformerConfig:
    """Phi-3 (reference: inference/v2/model_implementations/phi3) — unlike
    phi-2 it is llama-style: RMSNorm, SwiGLU, full rotary, sequential
    residual."""
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     num_kv_heads=8, max_seq_len=512, vocab_size=1024),
        "mini": dict(hidden_size=3072, num_layers=32, num_heads=32,
                     num_kv_heads=32, intermediate_size=8192,
                     max_seq_len=4096, vocab_size=32064),
        "medium": dict(hidden_size=5120, num_layers=40, num_heads=40,
                       num_kv_heads=10, intermediate_size=17920,
                       max_seq_len=4096, vocab_size=32064),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def phi_config(size: str = "2", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     max_seq_len=512, vocab_size=1024),
        "2": dict(hidden_size=2560, num_layers=32, num_heads=32,
                  max_seq_len=2048, vocab_size=51200),
    }
    base = dict(pos_emb="rope", rope_pct=0.4, norm="layernorm",
                activation="gelu", tie_embeddings=False,
                parallel_residual=True, head_bias=True)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def falcon_config(size: str = "7b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     num_kv_heads=1, max_seq_len=512, vocab_size=1024),
        "7b": dict(hidden_size=4544, num_layers=32, num_heads=71,
                   num_kv_heads=1, max_seq_len=2048, vocab_size=65024),
    }
    base = dict(pos_emb="rope", norm="layernorm", activation="gelu",
                tie_embeddings=True, parallel_residual=True)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def opt_config(size: str = "1.3b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     max_seq_len=512, vocab_size=1024),
        "1.3b": dict(hidden_size=2048, num_layers=24, num_heads=32,
                     max_seq_len=2048, vocab_size=50272),
        "13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                    max_seq_len=2048, vocab_size=50272),
    }
    base = dict(pos_emb="learned", norm="layernorm", activation="relu",
                tie_embeddings=True)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def bloom_config(size: str = "7b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     max_seq_len=512, vocab_size=1024),
        "7b": dict(hidden_size=4096, num_layers=30, num_heads=32,
                   max_seq_len=2048, vocab_size=250880),
    }
    base = dict(pos_emb="alibi", norm="layernorm", activation="gelu",
                tie_embeddings=True, embed_norm=True)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def gptneox_config(size: str = "20b", **kw) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8,
                     max_seq_len=512, vocab_size=1024),
        "20b": dict(hidden_size=6144, num_layers=44, num_heads=64,
                    max_seq_len=2048, vocab_size=50432),
    }
    base = dict(pos_emb="rope", rope_pct=0.25, norm="layernorm",
                activation="gelu", tie_embeddings=False,
                parallel_residual=True)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def longcat_flash_config(size: str = "chat", **kw) -> TransformerConfig:
    """LongCat-Flash (meituan-longcat/LongCat-Flash-Chat config.json):
    latent attention, shortcut-connected double layers, a router over
    512 routed + 256 identity experts.  Serving only
    (inference/v2/latent_ops.py)."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=2, num_heads=4,
                     max_seq_len=512, vocab_size=512, intermediate_size=128,
                     q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, moe_experts=32,
                     moe_zero_experts=16, moe_top_k=4, moe_expert_ffn=32),
        "chat": dict(hidden_size=6144, num_layers=28, num_heads=64,
                     max_seq_len=131072, vocab_size=131072,
                     intermediate_size=12288, q_lora_rank=1536,
                     kv_lora_rank=512, qk_nope_head_dim=128,
                     qk_rope_head_dim=64, v_head_dim=128, moe_experts=512,
                     moe_zero_experts=256, moe_top_k=12,
                     moe_expert_ffn=2048),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, rope_theta=1e7, norm_eps=1e-5,
                mla_scale_q_lora=True, mla_scale_kv_lora=True,
                moe_router_bias=True, moe_routed_scaling=6.0,
                moe_norm_topk_prob=False)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def deepseek_v3_config(size: str = "v3", **kw) -> TransformerConfig:
    """DeepSeek-V3 (deepseek-ai/DeepSeek-V3 config.json): latent attention
    in single-attention layers, the first 3 with a dense FFN, the others
    with 256 routed experts (8 a token, a sigmoid router limited to 4 of 8
    expert groups) beside a shared expert; YaRN over 4096 positions.
    Serving only (inference/v2/latent_ops.py); the multi-token-prediction
    module is not part of the served stack."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=4, num_heads=4,
                     max_seq_len=512, vocab_size=512, intermediate_size=128,
                     q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16, moe_experts=16,
                     moe_top_k=4, moe_expert_ffn=32,
                     moe_shared_expert_ffn=32, latent_dense_layers=1,
                     moe_router_groups=4, moe_router_groups_kept=2,
                     rope_scaling=("yarn", 8.0, 1.0, 32.0, 1.0, 64)),
        "v3": dict(hidden_size=7168, num_layers=61, num_heads=128,
                   max_seq_len=163840, vocab_size=129280,
                   intermediate_size=18432, q_lora_rank=1536,
                   kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128, moe_experts=256,
                   moe_top_k=8, moe_expert_ffn=2048,
                   moe_shared_expert_ffn=2048, latent_dense_layers=3,
                   moe_router_groups=8, moe_router_groups_kept=4,
                   rope_scaling=("yarn", 40.0, 1.0, 32.0, 1.0, 4096)),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, rope_theta=1e4, norm_eps=1e-6,
                latent_form="single", moe_router_scores="sigmoid",
                moe_router_bias=True, moe_routed_scaling=2.5,
                moe_norm_topk_prob=True, mla_yarn_mscale_all_dim=1.0)
    base.update(presets[size])
    base.update(kw)
    return TransformerConfig(**base)


def smallthinker_config(size: str = "21b-a3b", **kw) -> TransformerConfig:
    """SmallThinker (PowerInfer/SmallThinker-21BA3B-Instruct config.json):
    window layers with rope and global layers with no position encoding
    (one global in every four), a router that reads the layer's input,
    ReLU-gated experts and no dense FFN.  Serving only
    (inference/v2/hybrid_ops.py).  `num_layers` cuts whole periods: the
    two layouts follow it."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=8, num_heads=4,
                     num_kv_heads=2, max_seq_len=512, vocab_size=512,
                     attn_head_dim=32, moe_experts=8, moe_top_k=2,
                     moe_expert_ffn=32, sliding_window=16),
        "21b-a3b": dict(hidden_size=2560, num_layers=52, num_heads=28,
                        num_kv_heads=4, attn_head_dim=128,
                        max_seq_len=16384, vocab_size=151936, moe_experts=64, moe_top_k=6,
                        moe_expert_ffn=768, sliding_window=4096),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="reglu",
                tie_embeddings=False, rope_theta=1.5e6, norm_eps=1e-6,
                moe_norm_topk_prob=True)
    base.update(presets[size])
    base.update(kw)
    # the published layouts: layer 4n global without rope, the others
    # window with rope (`sliding_window` names the window's size here)
    L, W = base["num_layers"], base.pop("sliding_window")
    # (no dense FFN anywhere: the experts' width is the model's FFN width)
    base.setdefault("intermediate_size", base["moe_expert_ffn"])
    base.setdefault("rope_layers", tuple(int(l % 4 != 0) for l in range(L)))
    base.setdefault("sliding_window_layers",
                    tuple(W * (l % 4 != 0) for l in range(L)))
    return TransformerConfig(**base)


def falcon_h1_config(size: str = "34b", **kw) -> TransformerConfig:
    """Falcon-H1 (tiiuae/Falcon-H1-34B-Instruct config.json): a Mamba-2
    mixer and grouped-query attention side by side in every layer, both on
    the input norm, then a gated MLP; muP multipliers on the embedding,
    the projections and the head.  Serving only
    (inference/v2/ssm_ops.py)."""
    presets = {
        # the published ratios at a small size: 2 groups of heads, a
        # convolution over 4 positions, a scan chunk shorter than a prompt
        "tiny": dict(hidden_size=64, num_layers=2, num_heads=4,
                     num_kv_heads=2, attn_head_dim=16, intermediate_size=128,
                     max_seq_len=512, vocab_size=512, ssm_state=16,
                     ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_chunk=8),
        "34b": dict(hidden_size=5120, num_layers=72, num_heads=20,
                    num_kv_heads=4, attn_head_dim=128,
                    intermediate_size=21504, max_seq_len=262144,
                    vocab_size=261120, ssm_state=256, ssm_heads=32,
                    ssm_head_dim=128, ssm_groups=2, ssm_chunk=128),
    }
    base = dict(pos_emb="rope", norm="rmsnorm", activation="swiglu",
                tie_embeddings=False, rope_theta=1e11, norm_eps=1e-5,
                ssm_conv=4,
                embedding_multiplier=5.656854249492381,
                lm_head_multiplier=0.0078125,
                attention_in_multiplier=1.0,
                attention_out_multiplier=0.0375,
                key_multiplier=0.011048543456039804,
                ssm_in_multiplier=0.25,
                ssm_out_multiplier=0.08838834764831845,
                mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
                ssm_multipliers=(0.3535533905932738, 0.25,
                                 0.1767766952966369, 0.5,
                                 0.3535533905932738))
    base.update(presets[size])
    base.update(kw)
    for name in ("mlp_multipliers", "ssm_multipliers"):
        base[name] = tuple(float(m) for m in base[name])
    return TransformerConfig(**base)


def granite_moe_hybrid_config(size: str = "h-small",
                              **kw) -> TransformerConfig:
    """Granite-4.0-H (ibm-granite/granite-4.0-h-small config.json,
    `model_type` `granitemoehybrid`): layers of ONE kind each, nine
    Mamba-2 mixers to one grouped-query attention layer without a position
    encoding (period `m m m m m a m m m m`), every layer followed by 72
    routed experts (10 a token, softmax over the picks) beside a shared
    expert; scalar multipliers on the embedding, every residual branch,
    the attention scores and the logits; a tied head.  Serving only
    (inference/v2/ssm_ops.py).  `num_layers` cuts whole periods."""
    period = ("ssm",) * 5 + ("attn",) + ("ssm",) * 4
    presets = {
        # the published ratios at a small size: a period of four kinds with
        # one attention layer, 64-wide mixer heads (two a 128-lane row) in
        # one group, a scan chunk shorter than a prompt, 3 of 8 experts a
        # token
        "tiny": dict(hidden_size=128, num_layers=4, num_heads=4,
                     num_kv_heads=2, attn_head_dim=32, max_seq_len=512,
                     vocab_size=512, ssm_state=16, ssm_heads=4,
                     ssm_head_dim=64, ssm_groups=1, ssm_chunk=8,
                     moe_experts=8, moe_top_k=3, moe_expert_ffn=32,
                     moe_shared_expert_ffn=64,
                     attention_multiplier=0.03125,   # 1 / head_dim, as 1/128
                     ssm_layout=("ssm", "ssm", "attn", "ssm")),
        "h-small": dict(hidden_size=4096, num_layers=40, num_heads=32,
                        num_kv_heads=8, attn_head_dim=128,
                        max_seq_len=131072, vocab_size=100352,
                        ssm_state=128, ssm_heads=128, ssm_head_dim=64,
                        ssm_groups=1, ssm_chunk=256, moe_experts=72,
                        moe_top_k=10, moe_expert_ffn=768,
                        moe_shared_expert_ffn=1536, ssm_layout=period),
    }
    base = dict(pos_emb="none", norm="rmsnorm", activation="swiglu",
                tie_embeddings=True, norm_eps=1e-5, ssm_conv=4,
                moe_norm_topk_prob=True,
                embedding_multiplier=12.0, residual_multiplier=0.22,
                attention_multiplier=0.0078125,
                lm_head_multiplier=1.0 / 16)       # logits_scaling 16
    base.update(presets[size])
    base.update(kw)
    # (no dense FFN anywhere: the experts' width is the model's FFN width)
    base.setdefault("intermediate_size", base["moe_expert_ffn"])
    return TransformerConfig(**base)


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------
def _init_ssm_params(key, cfg: TransformerConfig) -> PyTree:
    """Random weights in the state-space family's layout (the leaves
    `inference/v2/ssm_ops.py` reads): per layer the two norms and the FFN
    (the gated MLP, or the router and the shared expert: the routed experts
    this chip holds lie apart, outside the layer scan); the mixer's leaves
    stacked over the layers that have one, attention's over those that
    have it (`cfg.ssm_layout`; every layer has both where it is None).
    `dt_bias` and `A_log` as Mamba-2 initialises them: a step log-uniform
    in [1e-3, 1e-1] through the inverse of softplus, a decay rate uniform
    in [1, 16]."""
    H, L, NH, NKV, D = (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
                        cfg.kv_heads, cfg.head_dim)
    F, Wm, Wc, NHm = (cfg.ffn_dim, cfg.ssm_width, cfg.ssm_conv_width,
                      cfg.ssm_heads)
    Lm, La = cfg.ssm_state_layers, cfg.ssm_attn_layers
    out_std = 0.02 / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 20))

    def rnd(shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    step = jnp.exp(jax.random.uniform(
        next(keys), (Lm, NHm), jnp.float32, math.log(1e-3), math.log(1e-1)))
    params = {
        "tok_embed": rnd((cfg.vocab_size, H)),
        "lm_head": rnd((H, cfg.vocab_size)),
        "final_norm_scale": jnp.ones((H,), jnp.float32),
        "layers": {
            "attn_norm_scale": jnp.ones((L, H), jnp.float32),
            "mlp_norm_scale": jnp.ones((L, H), jnp.float32),
            "wq": rnd((La, H, NH * D)), "wk": rnd((La, H, NKV * D)),
            "wv": rnd((La, H, NKV * D)), "wo": rnd((La, NH * D, H), out_std),
            "ssm_in": rnd((Lm, H, Wm + Wc + NHm)),
            "ssm_conv_w": rnd((Lm, cfg.ssm_conv, Wc), 0.2),
            "ssm_conv_b": jnp.zeros((Lm, Wc), jnp.float32),
            "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssm_a_log": jnp.log(jax.random.uniform(
                next(keys), (Lm, NHm), jnp.float32, 1.0, 16.0)),
            "ssm_d": jnp.ones((Lm, NHm), jnp.float32),
            "ssm_norm_scale": jnp.ones((Lm, Wm), jnp.float32),
            "ssm_out": rnd((Lm, Wm, H), out_std)}}
    if cfg.tie_embeddings:
        del params["lm_head"]
    ffn = lambda n, width: {  # noqa: E731
        "w_gate": rnd((n, H, width)), "w_up": rnd((n, H, width)),
        "w_down": rnd((n, width, H), out_std)}
    if cfg.moe_experts == 1:
        params["layers"].update(ffn(L, F))
        return params
    El, Fe = cfg.local_experts, cfg.moe_expert_ffn
    params["layers"]["moe_gate"] = rnd((L, H, cfg.moe_experts))
    if cfg.moe_shared_expert_ffn:
        params["layers"]["shared"] = ffn(L, cfg.moe_shared_expert_ffn)
    params["experts"] = {"w_gate_proj": rnd((L, El, H, Fe)),
                         "w_up": rnd((L, El, H, Fe)),
                         "w_down": rnd((L, El, Fe, H), out_std)}
    return params


def _init_kinds_params(key, cfg: TransformerConfig) -> PyTree:
    """Random weights in the static-kind stack's layout (the leaves
    `inference/v2/hybrid_ops.py` reads): attention, the two norms and the
    router per layer; the experts apart, outside the layer scan."""
    H, L, NH, NKV, D = (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
                        cfg.kv_heads, cfg.head_dim)
    E, Fe = cfg.moe_experts, cfg.moe_expert_ffn
    out_std = 0.02 / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 16))

    def rnd(shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    return {
        "tok_embed": rnd((cfg.vocab_size, H)),
        "lm_head": rnd((H, cfg.vocab_size)),
        "final_norm_scale": jnp.ones((H,), jnp.float32),
        "layers": {"attn_norm_scale": jnp.ones((L, H), jnp.float32),
                   "mlp_norm_scale": jnp.ones((L, H), jnp.float32),
                   "wq": rnd((L, H, NH * D)), "wk": rnd((L, H, NKV * D)),
                   "wv": rnd((L, H, NKV * D)),
                   "wo": rnd((L, NH * D, H), out_std),
                   "moe_gate": rnd((L, H, E))},
        "experts": {"w_gate_proj": rnd((L, E, H, Fe)),
                    "w_up": rnd((L, E, H, Fe)),
                    "w_down": rnd((L, E, Fe, H), out_std)}}


def _init_latent_params(key, cfg: TransformerConfig) -> PyTree:
    """Random weights in a latent stack's layout (the leaves
    `inference/v2/latent_ops.py` reads).  The double block: per layer the
    two sub-blocks' leaves (`sub`), the router and its bias; this chip's
    experts apart.  The single-attention form: `dense_layers` (the leading
    layers: `sub` of one attention + dense FFN) and `layers` (the expert
    layers: `sub` of one attention, the router and its bias, the shared
    expert), each stacked over its own layers; the experts apart."""
    H, L, NH, F = (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
                   cfg.ffn_dim)
    rq, rkv, Fe = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.moe_expert_ffn
    dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    dkv = cfg.qk_nope_head_dim + cfg.v_head_dim
    E, El = cfg.moe_experts + cfg.moe_zero_experts, cfg.local_experts
    out_std = 0.02 / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 32))

    def rnd(shape, std=0.02):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    def ffn(n, width):
        return {"w_gate": rnd((n, H, width)), "w_up": rnd((n, H, width)),
                "w_down": rnd((n, width, H), out_std)}

    def sub_block(n, dense_ffn=True):
        ones = lambda w: jnp.ones((n, w), jnp.float32)  # noqa: E731
        return {"attn_norm_scale": ones(H), "mlp_norm_scale": ones(H),
                "q_a_norm_scale": ones(rq), "kv_a_norm_scale": ones(rkv),
                "wq_a": rnd((n, H, rq)), "wq_b": rnd((n, rq, NH * dqk)),
                "wkv_a": rnd((n, H, cfg.latent_width)),
                "wkv_b": rnd((n, rkv, NH * dkv)),
                "wo": rnd((n, NH * cfg.v_head_dim, H), out_std),
                **(ffn(n, F) if dense_ffn else {})}

    Ld = cfg.latent_dense_layers
    Le = L - Ld
    top = {"tok_embed": rnd((cfg.vocab_size, H)),
           "lm_head": rnd((H, cfg.vocab_size)),
           "final_norm_scale": jnp.ones((H,), jnp.float32)}
    if cfg.latent_form == "shortcut":
        layers = {"sub": [sub_block(L), sub_block(L)]}
    else:
        if Ld:
            top["dense_layers"] = {"sub": [sub_block(Ld)]}
        layers = {"sub": [sub_block(Le, dense_ffn=False)]}
    layers["moe_gate"] = rnd((Le, H, E))
    layers["moe_router_bias"] = jnp.zeros((Le, E), jnp.float32)
    if cfg.moe_shared_expert_ffn:
        layers["shared"] = ffn(Le, cfg.moe_shared_expert_ffn)
    return {**top, "layers": layers,
            "experts": {"w_gate_proj": rnd((Le, El, H, Fe)),
                        "w_up": rnd((Le, El, H, Fe)),
                        "w_down": rnd((Le, El, Fe, H), out_std)}}


def _init_params(key, cfg: TransformerConfig) -> PyTree:
    if cfg.latent:
        return _init_latent_params(key, cfg)
    if cfg.static_kinds:
        return _init_kinds_params(key, cfg)
    if cfg.ssm:
        return _init_ssm_params(key, cfg)
    H, L = cfg.hidden_size, cfg.num_layers
    D, NH, NKV = cfg.head_dim, cfg.num_heads, cfg.kv_heads
    F, V = cfg.ffn_dim, cfg.vocab_size
    std = 0.02
    keys = jax.random.split(key, 20)

    def rnd(k, shape, scale=std):
        return (jax.random.normal(k, shape, jnp.float32) * scale)

    layers: Dict[str, Any] = {
        "attn_norm_scale": jnp.ones((L, H), jnp.float32),
        "mlp_norm_scale": jnp.ones((L, H), jnp.float32),
        "wq": rnd(keys[0], (L, H, NH * D)),
        "wk": rnd(keys[1], (L, H, NKV * D)),
        "wv": rnd(keys[2], (L, H, NKV * D)),
        "wo": rnd(keys[3], (L, NH * D, H), scale=std / math.sqrt(2 * L)),
    }
    if cfg.norm == "layernorm":
        layers["attn_norm_bias"] = jnp.zeros((L, H), jnp.float32)
        layers["mlp_norm_bias"] = jnp.zeros((L, H), jnp.float32)
        layers["bo"] = jnp.zeros((L, H), jnp.float32)
    if cfg.norm == "layernorm" or cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, NH * D), jnp.float32)
        layers["bk"] = jnp.zeros((L, NKV * D), jnp.float32)
        layers["bv"] = jnp.zeros((L, NKV * D), jnp.float32)
    if cfg.moe_experts > 1:
        E = cfg.moe_experts
        layers["moe_gate"] = rnd(keys[10], (L, H, E))
        layers["moe_w_up"] = rnd(keys[11], (L, E, H, F))
        layers["moe_w_down"] = rnd(keys[12], (L, E, F, H),
                                   scale=std / math.sqrt(2 * L))
        if cfg.activation == "swiglu":
            layers["moe_w_gate_proj"] = rnd(keys[13], (L, E, H, F))
        if cfg.moe_dense_layers is not None:
            Fd = cfg.dense_intermediate_size or F
            layers["w_up"] = rnd(keys[4], (L, H, Fd))
            layers["w_down"] = rnd(keys[6], (L, Fd, H),
                                   scale=std / math.sqrt(2 * L))
            if cfg.activation == "swiglu":
                layers["w_gate"] = rnd(keys[5], (L, H, Fd))
            else:
                layers["b_up"] = jnp.zeros((L, Fd), jnp.float32)
                layers["b_down"] = jnp.zeros((L, H), jnp.float32)
        if cfg.moe_shared_expert_ffn:
            Fs = cfg.moe_shared_expert_ffn
            layers["moe_shared_w_up"] = rnd(keys[16], (L, H, Fs))
            layers["moe_shared_w_down"] = rnd(keys[17], (L, Fs, H),
                                              scale=std / math.sqrt(2 * L))
            if cfg.activation == "swiglu":
                layers["moe_shared_w_gate_proj"] = rnd(keys[18], (L, H, Fs))
            layers["moe_shared_gate"] = rnd(keys[19], (L, H))
    elif cfg.activation == "swiglu":
        layers["w_gate"] = rnd(keys[4], (L, H, F))
        layers["w_up"] = rnd(keys[5], (L, H, F))
        layers["w_down"] = rnd(keys[6], (L, F, H), scale=std / math.sqrt(2 * L))
    else:
        layers["w_up"] = rnd(keys[5], (L, H, F))
        layers["w_down"] = rnd(keys[6], (L, F, H), scale=std / math.sqrt(2 * L))
        layers["b_up"] = jnp.zeros((L, F), jnp.float32)
        layers["b_down"] = jnp.zeros((L, H), jnp.float32)

    E = cfg.embed_proj_dim or H
    params: Dict[str, Any] = {
        "tok_embed": rnd(keys[7], (V, E)),
        "layers": layers,
    }
    if cfg.final_norm:
        params["final_norm_scale"] = jnp.ones((H,), jnp.float32)
        if cfg.norm == "layernorm":
            params["final_norm_bias"] = jnp.zeros((H,), jnp.float32)
    if cfg.embed_proj_dim:
        # OPT-350m project_in/project_out around the narrow embedding space
        params["embed_in_proj"] = rnd(keys[14], (E, H))
        params["embed_out_proj"] = rnd(keys[15], (H, E))
    if cfg.pos_emb == "learned":
        params["pos_embed"] = rnd(keys[8], (cfg.max_seq_len, H), scale=0.01)
    if cfg.embed_norm:
        # bloom: word_embeddings_layernorm (always LN w/ bias)
        params["embed_norm_scale"] = jnp.ones((H,), jnp.float32)
        params["embed_norm_bias"] = jnp.zeros((H,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = rnd(keys[9], (E, V))
        if cfg.head_bias:
            params["lm_head_bias"] = jnp.zeros((V,), jnp.float32)
    return params


# ----------------------------------------------------------------------
# ops
# ----------------------------------------------------------------------
def _norm(x, scale, bias, kind: str, eps: float):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        # reference kernel analog: csrc/transformer/inference/rms_norm.cu:263
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * scale
    else:
        # csrc/transformer/inference/layer_norm.cu:503 analog
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * scale
        if bias is not None:
            out = out + bias
    return out.astype(x.dtype)


def _alibi_slopes(num_heads: int):
    """ALiBi per-head slopes (bloom; reference: the alibi tensor built in
    module_inject bloom policy / ops/transformer/inference)."""
    import numpy as _np
    p = 2 ** _np.floor(_np.log2(num_heads))
    slopes = 2.0 ** (-8.0 * (_np.arange(1, p + 1) / p))
    if p < num_heads:
        extra = 2.0 ** (-4.0 * (_np.arange(1, 2 * (num_heads - p) + 1, 2) / p))
        slopes = _np.concatenate([slopes, extra])
    return jnp.asarray(slopes[:num_heads], jnp.float32)


def _alibi_bias(num_heads: int, s_q: int, s_k: int):
    """[NH, Sq, Sk] additive bias: -slope * distance."""
    slopes = _alibi_slopes(num_heads)
    qpos = jnp.arange(s_q)[:, None] + (s_k - s_q)
    kpos = jnp.arange(s_k)[None, :]
    dist = (qpos - kpos).astype(jnp.float32)
    return -slopes[:, None, None] * dist[None]


def _scale_rope_freqs(freqs, scaling, theta):
    """Apply an HF-style rope_scaling spec to the inverse frequencies.

    ("linear", factor): position interpolation — every freq / factor.
    ("llama3", factor, low, high, orig_max): frequency-dependent — high-freq
    (short-wavelength) components unscaled, low-freq fully scaled, smooth
    ramp between (HF modeling_rope_utils._compute_llama3_parameters).
    ("yarn", factor, attention_factor, beta_fast, beta_slow, orig_max):
    NTK-by-parts interpolation with a linear correction ramp between the
    beta_fast/beta_slow rotation counts (_compute_yarn_parameters); the
    attention_factor (precomputed at conversion, incl. mscale variants)
    scales cos/sin in _rope.
    """
    kind = scaling[0]
    if kind == "linear":
        return freqs / scaling[1]
    if kind == "llama3":
        _, factor, low_f, high_f, orig = scaling
        wavelen = 2.0 * math.pi / freqs
        low_wl = orig / low_f
        high_wl = orig / high_f
        smooth = (orig / wavelen - low_f) / (high_f - low_f)
        mid = (1.0 - smooth) * freqs / factor + smooth * freqs
        out = jnp.where(wavelen > low_wl, freqs / factor,
                        jnp.where(wavelen < high_wl, freqs, mid))
        return out
    if kind == "yarn":
        _, factor, _af, beta_fast, beta_slow, orig = scaling
        half = freqs.shape[0]
        dim = 2 * half

        def corr(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(corr(beta_fast)), 0)
        high = min(math.ceil(corr(beta_slow)), dim - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        # interpolated (freq/factor) where ramp=1, extrapolated where 0
        return (freqs / factor) * ramp + freqs * (1.0 - ramp)
    raise ValueError(f"unknown rope_scaling kind {kind!r} "
                     f"(supported: linear, llama3, yarn)")


def _rope(x, positions, theta: float, pct: float = 1.0, scaling=None,
          regime_len=None):
    """Rotary embedding (reference kernel: apply_rotary_pos_emb.cu:199).
    x: [B, S, N, D]; pct<1 rotates only the leading rotary_dim (phi/neox);
    `scaling` is a TransformerConfig.rope_scaling tuple.  `regime_len`:
    optional [B] per-row sequence length used for the longrope short/long
    band choice — chunked serving prefill passes the FULL prompt length so
    early chunks of a long prompt embed with the same (long) factors HF's
    one-shot forward uses; defaults to max(positions)+1 (correct for full
    forwards)."""
    if pct < 1.0:
        rd = (int(x.shape[-1] * pct) // 2) * 2
        x_rot, x_pass = x[..., :rd], x[..., rd:]
        return jnp.concatenate(
            [_rope(x_rot, positions, theta, scaling=scaling,
                   regime_len=regime_len), x_pass],
            axis=-1)
    B, S, N, D = x.shape
    half = D // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    attn_factor = None
    if scaling is not None and scaling[0] == "longrope":
        # phi3-style longrope (HF _compute_longrope_parameters): per-band
        # divisors, short_factor inside the original context window and
        # long_factor beyond it.  The choice is made from the positions
        # actually being embedded — per batch row, so a ragged serving
        # batch mixes regimes correctly (HF's per-forward choice is the
        # single-sequence special case of this).
        _, attn_factor, orig, short_f, long_f = scaling
        eff_len = (regime_len if regime_len is not None
                   else jnp.max(positions, axis=-1) + 1)           # [B]
        use_long = eff_len > orig                                  # [B]
        ext = jnp.where(use_long[:, None],
                        jnp.asarray(long_f, jnp.float32)[None],
                        jnp.asarray(short_f, jnp.float32)[None])   # [B,half]
        freqs = freqs[None] / ext                                  # [B,half]
        angles = (positions[:, :, None].astype(jnp.float32)
                  * freqs[:, None, :])                             # [B,S,half]
    else:
        if scaling is not None:
            freqs = _scale_rope_freqs(freqs, scaling, theta)
        angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None and scaling[0] == "yarn":
        # yarn attention temperature: HF scales cos/sin by attention_factor
        attn_factor = scaling[2]
    if attn_factor is not None:
        cos = cos * attn_factor
        sin = sin * attn_factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: TransformerConfig, window=None):
    """Causal attention dispatch.  q: [B,S,NH,D], k/v: [B,S,NKV,D].
    `window`: traced per-layer window scalar (0 = full) — forces the
    masked jnp path."""
    if cfg.attn_chunk_size and q.shape[1] > cfg.attn_chunk_size:
        if q.shape[1] % cfg.attn_chunk_size != 0:
            raise ValueError(
                f"attn_chunk_size={cfg.attn_chunk_size} configured but seq "
                f"len {q.shape[1]} is not a multiple — a silent fallback to "
                f"dense O(S^2) attention would defeat FPDT; pad the batch or "
                f"choose a divisor")
        from ..runtime.activation_checkpointing import attn_checkpoint_name
        from ..sequence.fpdt import fpdt_attention
        # tag the output so save_attn* policies save it (fpdt's custom-vjp
        # residuals are host-parked by its own offload machinery)
        return attn_checkpoint_name(fpdt_attention(
            q, k, v, cfg.attn_chunk_size, offload=cfg.fpdt_offload))
    from ..ops.attention import causal_attention
    bias = None
    if cfg.pos_emb == "alibi":
        bias = _alibi_bias(cfg.num_heads, q.shape[1], k.shape[1])[None]
        if cfg.alibi_scaled:
            bias = bias / math.sqrt(cfg.head_dim)
    if window is not None:
        # 0 -> effectively unwindowed (S covers the whole causal range)
        w_eff = jnp.where(window > 0, window, q.shape[1])
        return causal_attention(q, k, v, impl=cfg.attn_impl, bias=bias,
                                sliding_window=w_eff)
    return causal_attention(q, k, v, impl=cfg.attn_impl, bias=bias,
                            sliding_window=cfg.sliding_window)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _act_fn(name: str):
    """Non-gated activation in fp32 (reference kernels: gelu.cu, relu.cu —
    "gelu" is the tanh approximation HF calls gelu_new; "gelu_exact" the erf
    form plain HF "gelu")."""
    if name == "relu":
        return jax.nn.relu
    if name == "gelu_exact":
        return partial(jax.nn.gelu, approximate=False)
    return partial(jax.nn.gelu, approximate=True)


def resolve_weight(w, dt):
    """Weight leaf -> compute-dtype matrix.

    Plain arrays cast; {"q_codes", "q_scales"} dicts (quantize_serving_
    weights) dequantize group-wise on use — the fp8 codes are what HBM
    moves, halving the weight-read bytes that dominate decode (reference:
    inference fp-quantize path, linear/quantization.py fp_quantize).
    The group count rides the scales' trailing dim, so sliced per-layer
    leaves (the layer scan) resolve without static shape metadata.

    Column-granular dicts ({"q_codes", "q_col_scales"}) should NOT be
    resolved here — consumers apply the scale after the matmul
    (resolve_weight_scaled), which is what lets XLA feed the fp8 codes
    to the dot without materializing a dequantized copy."""
    if isinstance(w, dict):
        if "q_col_scales" in w:
            codes, scales = w["q_codes"], w["q_col_scales"]
            return (codes.astype(jnp.float32)
                    * scales[..., None, :]).astype(dt)
        codes, scales = w["q_codes"], w["q_scales"]
        g = codes.shape[-1] // scales.shape[-1]
        cf = codes.astype(jnp.float32).reshape(
            codes.shape[:-1] + (scales.shape[-1], g))
        return (cf * scales[..., None]).reshape(codes.shape).astype(dt)
    return w.astype(dt)


def resolve_weight_scaled(w, dt):
    """(matrix, post_scale_or_None): column-granular fp8 weights return
    the raw codes plus their per-output-column scale, to be applied to
    the matmul OUTPUT — dequant commutes with the contraction when the
    scale is constant per column, so the fp8 codes feed the dot directly
    (one bf16 convert fused into the operand read) and no dequantized
    matrix materializes in HBM.  Everything else resolves as usual with
    no post-scale."""
    if isinstance(w, dict) and "q_col_scales" in w:
        return w["q_codes"].astype(dt), w["q_col_scales"]
    return resolve_weight(w, dt), None


def quantize_serving_weights(params: PyTree, q_bits: int = 8,
                             group_size: int = 128,
                             granularity: str = "column",
                             keys=("wq", "wk", "wv", "wo", "w_up",
                                   "w_down", "w_gate")) -> PyTree:
    """Replace the named layer-stack matmul weights with fp8 code/scale
    dicts consumed by resolve_weight.  Serving-side weight quantization
    (reference: MoQ / inference quantization, quantization_setting in
    replace_with_policy) — embeddings/norms/biases stay bf16 (the layer
    matmuls are ~90% of GPT-2-large's bytes).  Training through quantized
    dicts is unsupported; this is an inference transform.

    granularity:
      "column" (default) — one absmax per output COLUMN (the last dim):
                 the scale commutes with the contraction and applies to
                 the matmul OUTPUT instead (resolve_weight_scaled), so
                 the fp8 codes feed the dot directly and the weight-read
                 bytes actually halve.  Measured (v5e, 774M ctx2048
                 decode): 1030.3 tok/s vs bf16's 995.1 and group-fp8's
                 955.3; parity equal to group at GPT-2-small geometry
                 (max logit diff 0.233 vs 0.243, argmax preserved).
      "group"  — absmax per `group_size` run of the LAST dim; dequant
                 must materialize before the matmul (XLA does not fuse
                 it into the dot — measured throughput-neutral vs bf16).
                 Tighter error bound for outlier-heavy weights."""
    if q_bits != 8:
        raise NotImplementedError("serving weight quantization ships fp8 "
                                  "(e4m3) — fp6/fp12 codecs exist in "
                                  "linear/quantization.py but are not "
                                  "wired to the zoo")
    if granularity not in ("group", "column"):
        raise ValueError(f"granularity must be group|column, got "
                         f"{granularity!r}")
    layers = dict(params["layers"])
    for k in keys:
        if k not in layers:
            continue
        w = layers[k]
        wf = w.astype(jnp.float32)
        if granularity == "column":
            # per-output-column absmax over the contraction dim (-2)
            amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) + 1e-12
            scale = amax / 448.0                  # e4m3 max
            codes = (wf / scale).astype(jnp.float8_e4m3fn)
            layers[k] = {"q_codes": codes,
                         "q_col_scales": scale[..., 0, :]}
            continue
        r = w.shape[-1]
        g = group_size if r % group_size == 0 else r
        grouped = wf.reshape(w.shape[:-1] + (r // g, g))
        amax = jnp.max(jnp.abs(grouped), axis=-1, keepdims=True) + 1e-12
        scale = amax / 448.0                      # e4m3 max
        codes = (grouped / scale).astype(jnp.float8_e4m3fn)
        layers[k] = {"q_codes": codes.reshape(w.shape),
                     "q_scales": scale[..., 0]}
    out = dict(params)
    out["layers"] = layers
    return out


def _dense(h, w, b=None):
    """[B,S,H] @ [H,D] in the activation dtype, fp32 MXU accumulation
    (single definition so the matmul precision policy lives in one place).
    Column-granular fp8 weights apply their scale to the matmul OUTPUT
    (resolve_weight_scaled) so the codes feed the dot directly."""
    dt = h.dtype
    mat, post = resolve_weight_scaled(w, dt)
    out = jnp.einsum("bsh,hd->bsd", h, mat,
                     preferred_element_type=jnp.float32)
    if post is not None:
        out = out * post.astype(jnp.float32)
    out = out.astype(dt)
    if b is not None:
        out = out + b.astype(dt)
    return out


def _layer(cfg: TransformerConfig, x, lp, positions, window=None,
           dense_flag=None):
    """One transformer block. x: [B,S,H] compute dtype; `window`: traced
    per-layer sliding-window scalar (sliding_window_layers); `dense_flag`:
    traced per-layer dense-vs-MoE selector (moe_dense_layers)."""
    B, S, H = x.shape
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dense = _dense

    # -- attention --
    x_in = x
    with jax.named_scope("attention"):
        # post_norm (OPT-350m): no norm before the sublayer; the block norms
        # move to after each residual add below
        h = x if cfg.post_norm else _norm(x, lp["attn_norm_scale"],
                                          lp.get("attn_norm_bias"), cfg.norm,
                                          cfg.norm_eps)
        # proj tags: residuals for the save_attn_proj* remat policies (identity
        # under every other policy) — the remat backward then recomputes only
        # norm/rope, not the q/k/v matmuls
        from ..runtime.activation_checkpointing import proj_checkpoint_name
        def heads(name, n):
            y = proj_checkpoint_name(dense(h, lp["w" + name],
                                           lp.get("b" + name)))
            if D % 128:
                # a head narrower than a tile's 128 lanes: XLA folds the
                # head-major transpose the attention kernel asks for into
                # the matmul, as a convolution over `n` positions of D
                # output features, and at D = 64 that is half the MXU's
                # width (200-224 us a projection at OPT-1.3B where the
                # output projection, the same FLOPs, takes 102; PERF.md
                # sections 5-6, PR 34).  Behind the barrier the matmul
                # stays [S, H] x [H, n * D] (94-103 us) and the transpose
                # is a copy of 14-27 us; at D = 128 the fold costs nothing
                # and stays
                y = jax.lax.optimization_barrier(y)
            return y.reshape(B, S, n, D)
        q, k, v = heads("q", NH), heads("k", NKV), heads("v", NKV)
        if cfg.pos_emb == "rope":
            q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct, cfg.rope_scaling)
            k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct, cfg.rope_scaling)

        if cfg.sp_axis is not None:
            if cfg.sp_mode == "ring":
                from ..parallel.ring_attention import ring_attention
                attn = ring_attention(q, k, v, axis_name=cfg.sp_axis)
            else:
                # Ulysses all-to-all leaves each device with the FULL sequence
                # for a head subset, so position-based masks (incl. the traced
                # per-layer window) apply unchanged inside the wrapper
                from ..parallel.ulysses import ulysses_attention
                attn = ulysses_attention(q, k, v, axis_name=cfg.sp_axis,
                                         attn_fn=partial(_attention, cfg=cfg,
                                                         window=window))
            # ring/ulysses run under shard_map where the flash custom_vjp's
            # internal tags are not visible to the outer remat policy — tag
            # the gathered output here so save_attn* at least saves it (their
            # custom-vjp residuals still recompute; the single-path flash
            # kernel is the fully-saved case)
            from ..runtime.activation_checkpointing import attn_checkpoint_name
            attn = attn_checkpoint_name(attn)
        else:
            attn = _attention(q, k, v, cfg, window=window)
        attn = attn.reshape(B, S, NH * D)
        # single-path attention tags its own residuals (ops/flash_attention.py
        # _fwd_res tags out+lse; ops/attention.py tags the jnp output) — a
        # second tag on the reshaped copy would double-save under save_attn*
        attn_out = proj_checkpoint_name(dense(attn, lp["wo"], lp.get("bo")))

    # layer-boundary residual: the save/offload/partition remat policies key
    # off this tag (runtime/activation_checkpointing — maybe identity)
    from ..runtime.activation_checkpointing import maybe_checkpoint_name

    if cfg.parallel_residual:
        # falcon/gpt-neox/phi block: attn and mlp both read the layer input;
        # one residual add at the end (reference: falcon/neox policies in
        # module_inject/containers)
        with jax.named_scope("mlp"):
            h2 = _norm(x_in, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                       cfg.norm, cfg.norm_eps)
            mlp_out = _mlp_block(cfg, lp, h2, S)
        x = x_in + attn_out + mlp_out
        return maybe_checkpoint_name(x), jnp.zeros((), jnp.float32)

    x = x_in + attn_out
    if cfg.post_norm:
        x = _norm(x, lp["attn_norm_scale"], lp.get("attn_norm_bias"),
                  cfg.norm, cfg.norm_eps)
    x = maybe_checkpoint_name(x)

    # -- mlp --
    with jax.named_scope("mlp"):
        h = x if cfg.post_norm else _norm(x, lp["mlp_norm_scale"],
                                          lp.get("mlp_norm_bias"), cfg.norm,
                                          cfg.norm_eps)
        if cfg.moe_experts > 1:
            from ..moe.sharded import moe_layer
            moe_params = {"gate": lp["moe_gate"], "w_up": lp["moe_w_up"],
                          "w_down": lp["moe_w_down"]}
            if cfg.activation == "swiglu":
                moe_params["w_gate_proj"] = lp["moe_w_gate_proj"]
            mlp_out, l_aux = moe_layer(
                moe_params, h, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                min_capacity=cfg.moe_min_capacity, activation=cfg.activation,
                drop_tokens=cfg.moe_drop_tokens,
                norm_topk=cfg.moe_norm_topk_prob,
                dispatch=cfg.moe_dispatch,
                dispatch_bits=cfg.moe_dispatch_bits)
            if cfg.moe_shared_expert_ffn:
                mlp_out = mlp_out + _shared_expert(cfg, lp, h)
            if dense_flag is not None:
                # dense-interleaved layer: both branches computed (collective-
                # safe under EP sharding), the flag selects; a dense layer
                # contributes no router aux
                df = (dense_flag > 0)
                mlp_out = jnp.where(df, _mlp_block(cfg, lp, h, S), mlp_out)
                l_aux = jnp.where(df, 0.0, l_aux)
            return x + mlp_out, l_aux
        x = x + _mlp_block(cfg, lp, h, S)
        if cfg.post_norm:
            x = _norm(x, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                      cfg.norm, cfg.norm_eps)
        return x, jnp.zeros((), jnp.float32)


def _shared_expert(cfg: TransformerConfig, lp, h):
    """Always-on shared expert scaled by a per-token sigmoid gate
    (qwen2-moe; reference: qwen_v2_moe model implementation)."""
    dt = h.dtype
    dense = _dense
    u = dense(h, lp["moe_shared_w_up"])
    if cfg.activation == "swiglu":
        g = dense(h, lp["moe_shared_w_gate_proj"])
        act = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    else:
        act = _act_fn(cfg.activation)(u.astype(jnp.float32)).astype(dt)
    out = dense(act, lp["moe_shared_w_down"])
    gate = jnp.einsum("bsh,h->bs", h.astype(jnp.float32),
                      lp["moe_shared_gate"].astype(jnp.float32))
    return out * jax.nn.sigmoid(gate)[..., None].astype(dt)


def _moe_inference(cfg: TransformerConfig, lp, h, with_census: bool = False):
    """Exact top-k MoE for decode/serving paths: no capacity, no dropping,
    so each token's output depends only on its own routing (batch-shape
    independent — required for prefill/decode consistency).

    Tokens are sorted by assigned expert and pushed through grouped matmuls
    (`lax.ragged_dot`), so cost is O(top_k * T) FLOPs regardless of
    num_experts — the TPU-native replacement for the reference's CUTLASS
    grouped GEMM (inference/v2/kernels/cutlass_ops/moe_gemm/).  Training
    uses the capacity-limited einsum dispatch in moe_layer instead; the
    combine-weight formula (softmax over all experts; normalized over the
    selected k when moe_norm_topk_prob) matches topk_gating's exactly.
    h: [B,S,H] post-norm hidden.

    EXPERT-PAGED layers (serving/experts.ExpertPool): when `lp` carries
    `moe_slot_map` the FFN weights live in slot stacks `moe_*_slots`
    [S, ...] holding only the RESIDENT experts; `moe_slot_map` [E] int32
    maps expert -> slot (-1 when demoted to host) and `moe_resident_mask`
    [E] marks residency.  Gate logits of non-resident experts are masked
    to -inf BEFORE the softmax, so their tokens reroute to the best
    resident expert (counted as "rerouted" in the census).  With every
    expert resident in its home slot (slot_map == identity) the mask is
    all-true and the slot gather is the identity — bit-for-bit the
    unpaged math.  Tokens are then grouped by SLOT for the ragged_dot,
    so compute runs directly over the slot stacks without materializing
    a full [E, ...] weight tensor.

    with_census=True additionally returns a [E+1] int32 census row:
    per-expert routed-assignment counts plus (last column) the number of
    assignments rerouted away from non-resident experts — the decode loop
    accumulates these for the pool's LRU ranking and the
    serving/expert/* gauges."""
    dt = h.dtype
    B, S, H = h.shape
    T, k, E = B * S, cfg.moe_top_k, cfg.moe_experts
    xt = h.reshape(T, H)
    paged = "moe_slot_map" in lp

    logits = xt.astype(jnp.float32) @ lp["moe_gate"]            # [T, E]
    if paged:
        raw_logits = logits
        logits = jnp.where(lp["moe_resident_mask"][None, :], logits, -1e30)
    gates = jax.nn.softmax(logits, axis=-1)
    _, topi = jax.lax.top_k(logits, k)                          # [T, k]
    sel = jnp.take_along_axis(gates, topi, axis=1)              # [T, k]
    if cfg.moe_norm_topk_prob:
        weight = sel / jnp.maximum(jnp.sum(sel, axis=1, keepdims=True), 1e-9)
    else:
        weight = sel

    ids = topi.reshape(-1)                                      # [T*k]
    if paged:
        # group by SLOT: ragged_dot runs over the slot stacks directly.
        # Masked routing guarantees resident targets; the max(...,0) only
        # covers the no-resident-expert corner (engine refuses it anyway)
        gids = jnp.maximum(lp["moe_slot_map"][ids], 0)
        n_groups = lp["moe_w_up_slots"].shape[0]
        w_up, w_down = lp["moe_w_up_slots"], lp["moe_w_down_slots"]
        w_gp = lp.get("moe_w_gate_proj_slots")
    else:
        gids = ids
        n_groups = E
        w_up, w_down = lp["moe_w_up"], lp["moe_w_down"]
        w_gp = lp.get("moe_w_gate_proj")
    order = jnp.argsort(gids, stable=True)
    token_of = (jnp.arange(T * k) // k)[order]                  # [T*k]
    group_sizes = jnp.bincount(gids, length=n_groups).astype(jnp.int32)
    xs = jnp.take(xt, token_of, axis=0)                         # [T*k, H]

    up = jax.lax.ragged_dot(xs, w_up.astype(dt), group_sizes,
                            preferred_element_type=jnp.float32).astype(dt)
    if cfg.activation == "swiglu":
        g = jax.lax.ragged_dot(xs, w_gp.astype(dt),
                               group_sizes,
                               preferred_element_type=jnp.float32)
        act = jax.nn.silu(g).astype(dt) * up
    else:
        act = _act_fn(cfg.activation)(up.astype(jnp.float32)).astype(dt)
    down = jax.lax.ragged_dot(act, w_down.astype(dt), group_sizes,
                              preferred_element_type=jnp.float32)  # [T*k, H]

    w_flat = weight.reshape(-1)[order]                          # [T*k]
    out = jnp.zeros((T, H), jnp.float32)
    out = out.at[token_of].add(down * w_flat[:, None])
    out = out.astype(dt).reshape(B, S, H)
    if cfg.moe_shared_expert_ffn:
        out = out + _shared_expert(cfg, lp, h)
    if not with_census:
        return out
    if paged:
        # count what the router WANTED (unmasked top-k): cold demoted
        # experts keep accruing demand, which is exactly the signal the
        # pool's LRU promote/demote ranking needs; col E counts the
        # assignments that had to reroute because their expert was out
        _, topi_u = jax.lax.top_k(raw_logits, k)
        ids_u = topi_u.reshape(-1)
        rerouted = jnp.sum(
            ~lp["moe_resident_mask"][ids_u]).astype(jnp.int32)
    else:
        ids_u = ids
        rerouted = jnp.zeros((), jnp.int32)
    census = jnp.bincount(ids_u, length=E).astype(jnp.int32)    # [E]
    return out, jnp.concatenate([census, rerouted[None]])


def _mlp_block(cfg: TransformerConfig, lp, h, S, tiled=True):
    """Dense MLP (swiglu / gelu / relu), seq-tiled when configured."""
    dt = h.dtype
    dense = _dense

    from ..runtime.activation_checkpointing import mlp_up_checkpoint_name

    def mlp(hc):
        if cfg.activation == "swiglu":
            # fused gated activation (reference: csrc .../gated_activations)
            g = mlp_up_checkpoint_name(dense(hc, lp["w_gate"]))
            u = mlp_up_checkpoint_name(dense(hc, lp["w_up"]))
            hc = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        else:
            hc = mlp_up_checkpoint_name(dense(hc, lp["w_up"], lp.get("b_up")))
            hc = _act_fn(cfg.activation)(hc.astype(jnp.float32)).astype(dt)
        return dense(hc, lp["w_down"], lp.get("b_down"))

    if tiled and cfg.tiled_mlp_shards > 1:
        if S % cfg.tiled_mlp_shards != 0:
            raise ValueError(
                f"tiled_mlp_shards={cfg.tiled_mlp_shards} configured but seq "
                f"len {S} is not a multiple — a silent dense fallback would "
                f"restore the full activation-memory peak; pad the batch or "
                f"choose a divisor")
        from ..sequence.tiled import tiled_mlp
        return tiled_mlp(mlp, h, cfg.tiled_mlp_shards)
    return mlp(h)


def _layer_extras(cfg: TransformerConfig):
    """Per-layer scan extras derived from static config: traced scalars
    that ride the layer scan next to the weights.  One construction shared
    by every forward path (training, KV-cache, ragged serving) so a new
    extra cannot be threaded through some paths and silently dropped in
    others."""
    extras = {}
    if cfg.sliding_window_layers is not None:
        extras["window"] = jnp.asarray(cfg.sliding_window_layers, jnp.int32)
    if cfg.moe_dense_layers is not None:
        extras["dense"] = jnp.asarray(cfg.moe_dense_layers, jnp.int32)
    return extras


def _lm_head(params: PyTree):
    """Output projection: explicit lm_head or tied token embedding."""
    head = params.get("lm_head")
    return params["tok_embed"].T if head is None else head


def _embed_in(cfg: TransformerConfig, params, input_ids, dt):
    """Token embedding, projected up to hidden width when the model embeds
    in a narrower space (OPT-350m project_in)."""
    x = jnp.take(params["tok_embed"], input_ids, axis=0).astype(dt)
    if "embed_in_proj" in params:
        x = jnp.einsum("...e,eh->...h", x,
                       params["embed_in_proj"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    return x


def _head_hidden(params, x, dt):
    """Final hidden states projected back to the embedding width before the
    lm head (OPT-350m project_out)."""
    if "embed_out_proj" in params:
        x = jnp.einsum("...h,he->...e", x,
                       params["embed_out_proj"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    return x


@jax.custom_vjp
def _grads_into(lp: PyTree, sink: PyTree, i):
    """(`lp`, `sink`) unchanged.  Backward, `lp` takes no cotangent: it is
    added to layer `i` of the cotangent of `sink` (a tree of `[L, ...]`
    stacks shaped like the stacked `lp`), in place.  `sink` rides the layer
    scan as a carry, so its cotangent is a carry of the reverse scan, and
    the cotangent the caller hands it where it leaves the scan (a gradient
    accumulator) is what each layer's gradient is added to where the
    backward pass produces it: no second stacked tree, no pass to add it."""
    return lp, sink


def _grads_into_fwd(lp, sink, i):
    return (lp, sink), i


def _grads_into_bwd(i, cts):
    g, acc = cts
    at = jax.lax.dynamic_index_in_dim
    return None, jax.tree.map(
        lambda a, g: jax.lax.dynamic_update_index_in_dim(
            a, at(a, i, 0, keepdims=False) + g.astype(a.dtype), i, 0),
        acc, g), None


_grads_into.defvjp(_grads_into_fwd, _grads_into_bwd)


def _forward(cfg: TransformerConfig, params: PyTree, input_ids, positions=None,
             return_hidden=False, grad_sink=None):
    """Logits for [B,S] token ids (final hidden states when return_hidden).
    With `grad_sink` (a tree like `params["layers"]`, any dtype, its values
    unread) the layers' weights take no gradient: it goes to the sink's
    cotangent (`_grads_into`), and the sink as it left the layer scan is
    returned as a third value."""
    B, S = input_ids.shape
    dt = cfg.dtype
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    # the scopes (embed, attention and mlp in `_layer`, lm_head, loss)
    # name the layers' device work in a profiler trace: metadata only
    with jax.named_scope("embed"):
        x = _embed_in(cfg, params, input_ids, dt)
        if cfg.pos_emb == "learned":
            x = x + jnp.take(params["pos_embed"], positions,
                             axis=0).astype(dt)
        if cfg.embed_norm:
            x = _norm(x, params["embed_norm_scale"],
                      params["embed_norm_bias"], "layernorm", cfg.norm_eps)

    layer_fn = partial(_layer, cfg)
    if cfg.remat:
        from ..runtime.activation_checkpointing import checkpoint_wrapper
        layer_fn = checkpoint_wrapper(layer_fn)

    # per-layer extras ride the layer scan (and, under pp, the stage
    # sharding) next to the weights
    extras = _layer_extras(cfg)
    has_ex = bool(extras)
    layers = params["layers"]
    if grad_sink is not None:
        if cfg.pp_axis is not None:
            raise NotImplementedError(
                "grad_sink under pipeline parallelism: the stages' scans "
                "run inside pipeline_layers")
        layers = jax.lax.stop_gradient(layers)
    stack = (layers, extras) if has_ex else layers

    def stage(layer_params, x, pos):
        def body(carry, item):
            x, aux, *sink = carry       # sink: (the stacks, this layer's index)
            lp, ex = item if has_ex else (item, {})
            if sink:
                lp, into = _grads_into(lp, *sink)
                sink = (into, sink[1] + 1)
            # ZeRO++ qwZ per-layer fetch: when the quantized path left the
            # stacked leaves sharded, gather THIS layer's slice only
            # (runtime/zero/layer_gather.py) — stage-3 residency with
            # int8-wire gathers; identity outside that context
            from ..runtime.zero.layer_gather import apply_layer_gathers
            lp = apply_layer_gathers(lp)
            x, l_aux = layer_fn(x, lp, pos, ex.get("window"),
                                ex.get("dense"))
            return (x, aux + l_aux, *sink), None
        sink = () if grad_sink is None else (grad_sink,
                                             jnp.zeros((), jnp.int32))
        (x, aux, *sink), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32), *sink), layer_params,
            unroll=cfg.scan_unroll)
        return (x, aux, *sink[:1])

    if cfg.pp_axis is not None:
        from ..runtime.pipeline.spmd import pipeline_layers
        x, moe_aux = pipeline_layers(
            stage, stack, x, positions, axis_name=cfg.pp_axis,
            num_microbatches=cfg.pp_microbatches,
            schedule=cfg.pp_schedule)
        sunk = []
    else:
        x, moe_aux, *sunk = stage(stack, x, positions)
    with jax.named_scope("lm_head"):
        if cfg.final_norm:
            x = _norm(x, params["final_norm_scale"],
                      params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)
        if return_hidden:
            return (x, moe_aux, *sunk)
        x = _head_hidden(params, x, dt)
        head = _lm_head(params)
        logits = jnp.einsum("bsh,hv->bsv", x, head.astype(dt),
                            preferred_element_type=jnp.float32)
        if "lm_head_bias" in params:
            logits = logits + params["lm_head_bias"]
    return (logits, moe_aux, *sunk)


def _lm_loss(cfg: TransformerConfig, params, batch, rng=None, grad_sink=None):
    """Next-token cross-entropy.  batch: {"input_ids": [B,S]} (labels default
    to shifted inputs) or explicit {"input_ids", "labels", "mask"?}.
    `grad_sink`: see `_forward`; returned third, after (loss, aux)."""
    ids = batch["input_ids"]
    labels = batch.get("labels")
    mask = batch.get("mask")
    if (labels is None and ids.shape[1] <= cfg.max_seq_len
            and (mask is None or mask.shape[1] == ids.shape[1])):
        # keep the full S sequence (so S-divisibility features — FPDT
        # chunking, tiled MLP/loss, SP sharding — stay active) and mask the
        # final position instead of slicing to S-1; the masked mean equals
        # the sliced mean exactly
        inputs = ids
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
        last_off = jnp.concatenate(
            [jnp.ones_like(ids[:, 1:]), jnp.zeros_like(ids[:, :1])], axis=1)
        mask = last_off if mask is None else mask * last_off
    elif labels is None:
        # S = max_seq_len + 1 shift-by-one idiom: slice, as positions beyond
        # max_seq_len have no embedding / mask rows
        labels = ids[:, 1:]
        inputs = ids[:, :-1]
    else:
        inputs = ids
    if cfg.tiled_loss_shards > 1:
        # ALST fused logits+loss: the [B,S,V] tensor is never materialized
        # (reference: TiledFusedLogitsLoss ulysses_sp.py:898)
        from ..sequence.tiled import tiled_fused_logits_loss
        hidden, moe_aux, *sink = _forward(cfg, params, inputs,
                                          return_hidden=True,
                                          grad_sink=grad_sink)
        with jax.named_scope("lm_head"):    # head and loss fused, by tile
            loss = tiled_fused_logits_loss(
                hidden, _lm_head(params), labels,
                shards=cfg.tiled_loss_shards, mask=mask,
                bias=params.get("lm_head_bias"))
    else:
        logits, moe_aux, *sink = _forward(cfg, params, inputs,
                                          grad_sink=grad_sink)
        with jax.named_scope("loss"):
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None],
                                       axis=-1)[..., 0]
            if mask is not None:
                maskf = mask.astype(jnp.float32)
                loss = jnp.sum(nll * maskf) / jnp.maximum(jnp.sum(maskf),
                                                          1.0)
            else:
                loss = jnp.mean(nll)
    aux = {"ppl_log": loss}
    if cfg.moe_experts > 1:
        aux["moe_aux"] = moe_aux
        loss = loss + cfg.moe_aux_weight * moe_aux
    return (loss, aux, *sink)


# ----------------------------------------------------------------------
# KV-cache decode path (inference)
# Replaces the reference's static KV-cache arena + fused decode kernels
# (csrc/transformer/inference/inference_context.h:292 workspace;
#  pt_binding.cpp qkv_gemm/softmax_context ops).
# ----------------------------------------------------------------------
def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """[L, B, max_len, NKV, D] k/v arenas in the compute dtype."""
    shape = (cfg.num_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


def _layer_decode(cfg: TransformerConfig, x, lp, cache_k, cache_v, positions,
                  cache_len, window=None, dense_flag=None):
    """One block over new tokens [B, T, H] with an existing cache.
    cache_k/v: [B, max_len, NKV, D]; returns (x, new_k, new_v)."""
    B, T, H = x.shape
    NH, NKV, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    dt = x.dtype
    dense = _dense

    x_in = x
    h = x if cfg.post_norm else _norm(x, lp["attn_norm_scale"],
                                      lp.get("attn_norm_bias"), cfg.norm,
                                      cfg.norm_eps)
    q = dense(h, lp["wq"], lp.get("bq")).reshape(B, T, NH, D)
    k = dense(h, lp["wk"], lp.get("bk")).reshape(B, T, NKV, D)
    v = dense(h, lp["wv"], lp.get("bv")).reshape(B, T, NKV, D)
    if cfg.pos_emb == "rope":
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_pct, cfg.rope_scaling)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_pct, cfg.rope_scaling)

    # write new k/v at positions [cache_len, cache_len+T)
    idx = cache_len[:, None] + jnp.arange(T)[None, :]          # [B, T]
    oh = jax.nn.one_hot(idx, cache_k.shape[1], dtype=dt)        # [B, T, M]
    cache_k = cache_k + jnp.einsum("btm,btnd->bmnd", oh, k)
    cache_v = cache_v + jnp.einsum("btm,btnd->bmnd", oh, v)

    # attention of new tokens against the whole cache, masked to valid keys
    kk = jnp.repeat(cache_k, NH // NKV, axis=2) if NKV != NH else cache_k
    vv = jnp.repeat(cache_v, NH // NKV, axis=2) if NKV != NH else cache_v
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("btnd,bmnd->bntm", q, kk,
                   preferred_element_type=jnp.float32) * scale
    key_pos = jnp.arange(cache_k.shape[1])[None, None, None, :]
    q_pos = idx[:, None, :, None]
    s = jnp.where(key_pos <= q_pos, s, -1e30)
    if window is not None:
        w_eff = jnp.where(window > 0, window, cache_k.shape[1])
        s = jnp.where(key_pos > q_pos - w_eff, s, -1e30)
    elif cfg.sliding_window is not None:
        s = jnp.where(key_pos > q_pos - cfg.sliding_window, s, -1e30)
    if cfg.pos_emb == "alibi":
        slopes = _alibi_slopes(NH)
        if cfg.alibi_scaled:
            slopes = slopes / math.sqrt(D)
        dist = (q_pos - key_pos).astype(jnp.float32)
        s = s - slopes[None, :, None, None] * jnp.maximum(dist, 0.0)
    p = jax.nn.softmax(s, axis=-1)
    attn = jnp.einsum("bntm,bmnd->btnd", p.astype(dt), vv).reshape(B, T, NH * D)
    attn_out = dense(attn, lp["wo"], lp.get("bo"))

    if cfg.parallel_residual:
        h2 = _norm(x_in, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                   cfg.norm, cfg.norm_eps)
        x = x_in + attn_out + _mlp_block(cfg, lp, h2, T, tiled=False)
    elif cfg.post_norm:
        x = _norm(x_in + attn_out, lp["attn_norm_scale"],
                  lp.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
        x = _norm(x + _mlp_block(cfg, lp, x, T, tiled=False),
                  lp["mlp_norm_scale"], lp.get("mlp_norm_bias"), cfg.norm,
                  cfg.norm_eps)
    else:
        x = x_in + attn_out
        h2 = _norm(x, lp["mlp_norm_scale"], lp.get("mlp_norm_bias"),
                   cfg.norm, cfg.norm_eps)
        if cfg.moe_experts > 1:
            mlp_out = _moe_inference(cfg, lp, h2)
            if dense_flag is not None:
                mlp_out = jnp.where(dense_flag > 0,
                                    _mlp_block(cfg, lp, h2, T, tiled=False),
                                    mlp_out)
            x = x + mlp_out
        else:
            x = x + _mlp_block(cfg, lp, h2, T, tiled=False)
    return x, cache_k, cache_v


def forward_with_cache(cfg: TransformerConfig, params, input_ids, cache):
    """Prefill or decode step: consumes [B, T] new tokens, returns
    (logits [B, T, V], updated cache)."""
    B, T = input_ids.shape
    dt = cfg.dtype
    positions = cache["len"][:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x = _embed_in(cfg, params, input_ids, dt)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(dt)
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm_scale"], params["embed_norm_bias"],
                  "layernorm", cfg.norm_eps)

    extras = _layer_extras(cfg)
    has_ex = bool(extras)

    def body(carry, layer_in):
        x = carry
        if has_ex:
            lp, ck, cv, ex = layer_in
        else:
            lp, ck, cv = layer_in
            ex = {}
        x, ck, cv = _layer_decode(cfg, x, lp, ck, cv, positions,
                                  cache["len"], window=ex.get("window"),
                                  dense_flag=ex.get("dense"))
        return x, (ck, cv)

    xs = ((params["layers"], cache["k"], cache["v"], extras) if has_ex
          else (params["layers"], cache["k"], cache["v"]))
    x, (new_k, new_v) = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll)
    if cfg.final_norm:
        x = _norm(x, params["final_norm_scale"],
                  params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)
    x = _head_hidden(params, x, dt)
    head = _lm_head(params)
    logits = jnp.einsum("bsh,hv->bsv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"]
    new_cache = {"k": new_k, "v": new_v, "len": cache["len"] + T}
    return logits, new_cache


# ----------------------------------------------------------------------
# tensor-parallel partition rules
# (reference: module_inject AutoTP column/row split of Linears, auto_tp.py:193)
# ----------------------------------------------------------------------
_TP_RULES = {
    # column-parallel (shard output dim): qkv, mlp up/gate
    "wq": PartitionSpec(None, None, AXIS_TP),
    "wk": PartitionSpec(None, None, AXIS_TP),
    "wv": PartitionSpec(None, None, AXIS_TP),
    "bq": PartitionSpec(None, AXIS_TP),
    "bk": PartitionSpec(None, AXIS_TP),
    "bv": PartitionSpec(None, AXIS_TP),
    "w_up": PartitionSpec(None, None, AXIS_TP),
    "w_gate": PartitionSpec(None, None, AXIS_TP),
    "b_up": PartitionSpec(None, AXIS_TP),
    # row-parallel (shard input dim): attn out, mlp down
    "wo": PartitionSpec(None, AXIS_TP, None),
    "w_down": PartitionSpec(None, AXIS_TP, None),
    # vocab-parallel embeddings
    "tok_embed": PartitionSpec(AXIS_TP, None),
    "lm_head": PartitionSpec(None, AXIS_TP),
    "lm_head_bias": PartitionSpec(AXIS_TP),
    # MoE expert weights: experts over ep, ffn dim over tp
    # (reference: expert parallel groups, utils/groups.py:240)
    "moe_w_up": PartitionSpec(None, AXIS_EP, None, AXIS_TP),
    "moe_w_gate_proj": PartitionSpec(None, AXIS_EP, None, AXIS_TP),
    "moe_w_down": PartitionSpec(None, AXIS_EP, AXIS_TP, None),
    # shared expert: plain column/row-parallel dense MLP (runs on all tokens)
    "moe_shared_w_up": PartitionSpec(None, None, AXIS_TP),
    "moe_shared_w_gate_proj": PartitionSpec(None, None, AXIS_TP),
    "moe_shared_w_down": PartitionSpec(None, AXIS_TP, None),
}


def tp_rules(path: Tuple[str, ...], shape: Tuple[int, ...]) -> Optional[PartitionSpec]:
    name = path[-1]
    return _TP_RULES.get(name)


# ----------------------------------------------------------------------
# Model bundle (what deepspeed_tpu.initialize(model=...) consumes)
# ----------------------------------------------------------------------
class Transformer:
    """Bundle of init/loss/forward/tp-rules for the engine."""

    # the layer scan calls layer_gather.apply_layer_gathers, so the ZeRO++
    # quantized path may leave stacked layer leaves sharded (per-layer
    # qwZ fetch); initialize() forwards this marker onto the loss fn
    supports_layer_gather = True

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    @property
    def grad_sink(self) -> Optional[str]:
        """The key of the stacked subtree of the parameters whose gradient
        `loss_fn(..., grad_sink=)` adds into a sink, inside the backward
        layer scan (`_forward`); the engine's accumulation loop asks.
        None under pipeline parallelism: the stages run their own scans."""
        return None if self.cfg.pp_axis is not None else "layers"

    def init_params(self, key) -> PyTree:
        return _init_params(key, self.cfg)

    def refuse_serving_only(self, what: str) -> None:
        """The serving-only blocks: refused with what is missing."""
        if self.cfg.latent:
            raise NotImplementedError(
                f"{what} has no latent-attention (MLA) layer in either "
                f"form (the shortcut-connected double block, the "
                f"single-attention layer over a static dense prefix): "
                f"`_layer` has no latent projections, no absorbed or "
                f"decompressed latent attention and no backward pass for "
                f"them, and the training MoE (moe/sharded.py) no sigmoid "
                f"or group-limited router, identity experts or expert "
                f"share; this configuration is served through "
                f"inference.v2 (build_engine -> ServeLoop) only")
        if self.cfg.static_kinds:
            raise NotImplementedError(
                f"{what} has no static-kind stack: `_layer` has no "
                f"per-layer rope flag, no router on the layer's input and "
                f"no ReLU-gated experts, the training MoE (moe/sharded.py) "
                f"no such expert either, and nothing gives the window "
                f"layers' attention a backward pass; this configuration "
                f"is served through inference.v2 (build_engine -> "
                f"ServeLoop) only")

        if self.cfg.ssm:
            raise NotImplementedError(
                f"{what} has no state-space mixer: `_layer` has no "
                f"convolution, no selective scan (and nothing gives "
                f"`ops/ssm.py`'s chunked scan a backward pass), no gated "
                f"norm, no per-layer kinds (a mixer alone, attention "
                f"alone, both) and no scalar multipliers, and the training "
                f"MoE (moe/sharded.py) no experts behind a mixer, no "
                f"expert share and no plain shared expert; this "
                f"configuration is served through inference.v2 "
                f"(build_engine -> ServeLoop) only")

    def loss_fn(self, params, batch, rng=None, grad_sink=None):
        self.refuse_serving_only("Transformer.loss_fn (training, initialize())")
        return _lm_loss(self.cfg, params, batch, rng, grad_sink)

    def init_cache(self, batch: int, max_len: int):
        self.refuse_serving_only("Transformer.init_cache (the dense K/V cache)")
        return init_kv_cache(self.cfg, batch, max_len)

    def forward_with_cache(self, params, input_ids, cache):
        self.refuse_serving_only("Transformer.forward_with_cache")
        return forward_with_cache(self.cfg, params, input_ids, cache)

    def tp_rules(self, path, shape):
        """Partition rules for the engine: TP column/row specs plus, under
        pipeline parallelism, the layer dim sharded over the pp axis (each
        device stores only its stage's layers — the reference's
        PipelineModule partitioning, runtime/pipe/module.py)."""
        spec = _TP_RULES.get(path[-1])
        if self.cfg.pp_axis and path and path[0] == "layers":
            base = list(spec) if spec is not None else []
            base += [None] * (len(shape) - len(base))
            base[0] = self.cfg.pp_axis
            return PartitionSpec(*base)
        return spec

    def forward(self, params, input_ids, positions=None):
        self.refuse_serving_only("Transformer.forward")
        logits, _ = _forward(self.cfg, params, input_ids, positions)
        return logits


    def num_params(self, params=None) -> int:
        if params is None:
            shapes = jax.eval_shape(self.init_params, jax.random.PRNGKey(0))
            return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        return sum(x.size for x in jax.tree.leaves(params))
