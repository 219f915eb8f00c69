"""A model family, as the serving stack sees one, is a value, and
`family_of(cfg)` is the ONE reader of `cfg.ssm` / `cfg.latent` /
`cfg.static_kinds`: `ragged_ops`' jitted entry points call the family's
programs at trace time, `InferenceEngineV2` sizes its ledger by the family's
pools and takes its step accounts from the family's hooks, and whatever
cannot serve a family refuses through `Family.refuse`, with the family's own
reason in the message.

Four families: `uniform` (one kind of per-head K/V block pair a layer:
`ragged_ops`' own bodies, which run in the dispatching function's frame),
`latent` (`latent_ops`), `kinds` (the static-kind stack over a two-kind
cache, `hybrid_ops`) and `ssm` (per-sequence recurrent state beside paged
K/V, `ssm_ops`).  A family's module is imported where its
record is made: the package's import and another family's traces gain
nothing from it.  One more family is a `*_ops.py`, a record here, and its
fields and preset in `models/transformer.py`.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

__all__ = ["Family", "family_of"]


def _no_account(*_) -> dict:
    return {}


class Family(NamedTuple):
    name: str
    # why mechanisms written for ONE kind of per-head K/V block pair a layer
    # cannot serve it: what every refusal ends in (None: they can)
    reason: Optional[str]
    # -- the programs, each under one signature whatever the family
    init_arena: Callable       # (cfg, num_blocks, block_size, max_seqs)
    # (cfg, params, arena, tokens, lens, block_tables, active, slots=);
    # None: fresh prompts go through `prefill_chunks` alone
    prefill_full: Optional[Callable]
    # (cfg, params, arena, tokens, pos0s, n_valids, block_tables, active,
    #  total_lens=, n_tp=, mesh=, adapter_ids=, lora=, slots=); a family
    # whose arena does not shard and that takes no LoRA operands lets the
    # uniform operands (1 and None by then: `refuse`, `refuse_lora`) and
    # longrope's `total_lens` fall into `**uniform_only`
    prefill_chunks: Callable
    # (cfg, params, arena, tokens, seq_lens, block_tables, active, n_tp=,
    #  mesh=, adapter_ids=, lora=, slots=) -> (logits, arena)
    decode_core: Callable
    # `ragged_ops._span_core`'s body (draft-verify); None: it has none
    span_core: Optional[Callable]
    # -- what its arena and programs take
    # the arena shards over tp (`topology`, `merged`) and carries the
    # expert-paging census rider (`moe_census`)
    shards: bool
    lora: bool                 # LoRA operands (`adapter_ids`, `lora`)
    # a row -> slot vector (`slots`: a sequence holds a slot of state beside
    # its blocks, which every token rewrites): a decode program that hands
    # none cannot serve it, and a chunk program holds one chunk a sequence
    row_slots: bool
    # what tensor parallelism is refused as (the types callers catch)
    tp_error: type = ValueError
    # -- what the engine asks, each once
    # (cfg, arena, engine config) -> (blocks, window, state_slots) of the
    # arena it made, for `DSStateManager`
    pools: Callable = lambda cfg, arena, config: (config.num_blocks, None, 0)
    # (engine, pos0s, n_valids) -> attributes of the chunk program's
    # `engine.dispatch` span
    chunk_account: Callable = _no_account
    # (engine, the step's LogitsRows, its decode batch): the account of the
    # family's cache, on every step (a step without decode rows: zeros)
    step_account: Callable = _no_account
    audit: Callable = _no_account      # (arena) -> what `audit_blocks` adds

    def refuse(self, what: str, error: type = NotImplementedError) -> None:
        """Raise where `what` cannot serve this family (the uniform one
        refuses nothing)."""
        if self.reason is not None:
            raise error(f"{what}: not wired for {self.reason}")

    def refuse_lora(self, lora) -> None:
        if lora is not None and not self.lora:
            self.refuse("LoRA adapters (these programs take no adapter "
                        "operands)")


def family_of(cfg) -> Family:
    if getattr(cfg, "ssm", False):
        from . import ssm_ops as ops
        return Family(
            "ssm",
            "per-sequence recurrent state: a sequence here is its K/V blocks "
            "(of the layers with attention) AND a slot of state-space state "
            "and convolution tail (of the layers with a mixer) that every "
            "token rewrites in place. A block holds no snapshot of the state "
            "at its edge (a cached, migrated or preempted prefix could not "
            "be continued, a rejected draft not rolled back: a slot holds "
            "one state); the programs take a row -> slot vector and no "
            "LoRA, draft-span, burst or multi-step operands; the slots, the "
            "mixer's heads and its kernels are not split over a mesh; and "
            "the experts behind a state-space mixer lie apart from "
            "`params['layers']`, a fixed share (moe_expert_first/count) "
            "with no exchange and no census rider",
            ops.init_ssm_arena, ops.prefill_full, ops.prefill_chunks,
            ops.decode_core, None, shards=False, lora=False, row_slots=True,
            tp_error=NotImplementedError, pools=ops.manager_pools,
            step_account=ops.step_account, audit=ops.arena_layers)
    if getattr(cfg, "static_kinds", False):
        from . import hybrid_ops as ops
        return Family(
            "kinds",
            "the two-kind cache of the static-kind stack: a block id names "
            "a block of ONE kind of layer (global or window), and a "
            "sequence's window-kind blocks are handed back as it advances "
            "(a cached prefix could not be re-attached under them, and a "
            "page is no [layers, block] K/V pair); the stack's programs "
            "take no LoRA or draft-span operands; its arena, its chunk "
            "attention kernel and its experts are not wrapped for a mesh; "
            "and it keeps its experts, all of them, apart from the slot "
            "stacks of `params['layers']`, with no census rider",
            ops.init_kinds_arena, None, ops.prefill_chunks, ops.decode_core,
            None, shards=False, lora=False, row_slots=False,
            pools=ops.manager_pools, chunk_account=ops.chunk_account,
            step_account=ops.step_account)
    if getattr(cfg, "latent", False):
        from . import latent_ops as ops
        return Family(
            "latent",
            "the latent (MLA) arena and block: the arena holds one [latent "
            "| rope key] row per token and attention and no K/V pages, "
            "with no head dimension to shard over tp; the block's programs "
            "take no LoRA or draft-span operands, and its kernel and "
            "expert share are not wrapped for a mesh; and a "
            "latent-attention MoE stack (either form) holds a fixed share "
            "of its experts (moe_expert_first/count), the rest being other "
            "chips' work: nothing to page, and no census rider",
            ops.init_latent_arena, ops.prefill_full, ops.prefill_chunks,
            ops.decode_core, None, shards=False, lora=False, row_slots=False)
    from . import ragged_ops as ops
    return Family(
        "uniform", None, ops._uniform_arena, ops._uniform_prefill_full,
        ops._uniform_prefill_chunks, ops._decode_core, ops._span_core,
        shards=True, lora=True, row_slots=False)
