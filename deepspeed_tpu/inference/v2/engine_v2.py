"""FastGen-style continuous-batching inference engine.

Reference: `inference/v2/engine_v2.py` `InferenceEngineV2` (:30, `put` :107)
+ `engine_factory.py` — ragged batches of live sequences are advanced by a
scheduler implementing Dynamic SplitFuse (blogs/deepspeed-fastgen): each
`put` call does a bounded amount of prefill work (long prompts split into
fixed chunks) while every decode-ready sequence generates a token.

TPU-first: the per-call shapes are static — prefill runs in `chunk_size`
token tiles batched over power-of-two chunk-count buckets, decode in a
`max_seqs`-wide batch — so the whole serving loop executes as a handful of
compiled XLA programs over a donated paged-KV arena (ragged_ops.py);
scheduling is host-side bookkeeping in DSStateManager.
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass
from typing import Collection, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ...utils.spans import span
from .families import family_of
from .ragged_manager import DSStateManager, SequenceDescriptor
from .ragged_ops import (init_arena, prefill_chunks, decode_step,
                         decode_tokens, decode_multi_step, verify_tokens,
                         logits_row)

__all__ = ["RaggedInferenceEngineConfig", "InferenceEngineV2", "LogitsRows"]


# what the four block readers/writers refuse for an arena without K/V pages
_PAGE_IO = ("arena page export/import (KV tiering, fleet migration, "
            "disaggregated handoff)")


class _Row:
    """One sequence's last-token logits: row `i` of a program's [N, V]
    output still on the device (`logits`), the row on the host once it
    was read (`host`), and its argmax as the program took it (`token`).
    The cell is shared by the step's result and the engine's own table,
    so a row crosses once whoever reads it."""
    __slots__ = ("logits", "i", "token", "host")

    def __init__(self, logits, i: int, token: Optional[int], host=None):
        self.logits, self.i, self.token, self.host = logits, i, token, host


class _Program:
    """One dispatched per-step program: its outputs still on the device
    and the (sequence, row) pairs it computed a token for.  The row is
    bound to the sequence object, not the uid: a preempted request comes
    back under its uid as another sequence."""
    __slots__ = ("name", "logits", "toks", "rows")

    def __init__(self, name: str, logits, toks, rows: List[tuple]):
        self.name, self.logits, self.toks, self.rows = (name, logits, toks,
                                                        rows)


class LogitsRows(Mapping):
    """{uid: last-token logits row [V]} over logits left on the device:
    what `put`/`step` return and `query` reads.

    The per-step programs hand back each row's greedy token beside the
    logits (`ragged_ops.greedy_tokens`), and only those [N] int32 cross
    when the step is collected: `greedy(uid)` reads them.  A logits row
    crosses when it is read, through the engine's explicit fetch (an
    `engine.fetch` span, one count in `profile["d2h_fetches"]`):
    `rows[uid]` brings that row alone, `items()` brings each program's
    whole output once (the burst loops' batched first-token sampler
    reads every row of a prefill, with the fetches it always made).
    Keys, `in` and `len` fetch nothing.  `rows[uid] = row` puts a host
    row in a row's place: it has no token of the program's, so
    `greedy(uid)` is None and whoever wants its token samples that
    row.

    A step dispatched with `collect=False` returns its rows `pending`:
    the programs that will fill them are listed (`prefill`, `decode`),
    their tokens still on the device, and the mapping is empty until
    `engine.collect` fetches them.  `items()` alone collects what is
    pending before it reads: a compatibility route for a caller that
    wraps `step` and reads every row of whatever it returned (the
    benchmark's altered-token test, `HostRowsOnly`), which must see
    rows at once; the serve loop never reads rows it has not
    collected."""

    def __init__(self, fetch, collect=None):
        self._fetch = fetch       # (device logits, row or None) -> host
        self._collect = collect   # (these rows) -> their tokens fetched
        self._rows: Dict[int, _Row] = {}
        self.prefill: List[_Program] = []
        self.decode: Optional[_Program] = None
        # of the step's decode rows, how many took their input token on
        # the device from the decode program of the step before
        self.decode_rows = self.fed_rows = 0
        # of the decode program's block table (rows x width), the entries
        # that hold keys a decode row attends to: what the paged decode
        # kernel's time follows
        self.kv_live_blocks = self.kv_table_blocks = 0
        # a two-kind cache's account of the decode rows, by counter name
        # (`hybrid_ops.step_account`); one kind: nothing
        self.kv_kinds: Dict[str, int] = {}
        # a decode step's account of per-sequence recurrent state
        # (`ssm_ops.step_account`); a model without: nothing
        self.state_account: Dict[str, int] = {}
        # what `engine.collect` returns: the rows it left out because
        # their sequence had been flushed (or replaced under its uid)
        self.overrun = 0

    @property
    def pending(self) -> bool:
        return bool(self.prefill) or self.decode is not None

    @property
    def awaited(self) -> int:
        """The rows of the programs not collected yet."""
        return sum(len(prog.rows) for prog in self.prefill) + (
            0 if self.decode is None else len(self.decode.rows))

    def _set(self, uid: int, logits, i: int, token: int) -> None:
        self._rows[uid] = _Row(logits, i, token)

    def __setitem__(self, uid: int, row: np.ndarray) -> None:
        self._rows[uid] = _Row(None, 0, None, host=row)

    def __getitem__(self, uid: int) -> np.ndarray:
        row = self._rows[uid]
        if row.host is None:
            row.host, row.logits = self._fetch(row.logits, row.i), None
        return row.host

    def __iter__(self):
        return iter(self._rows)

    def __len__(self) -> int:
        return len(self._rows)

    def __contains__(self, uid) -> bool:
        return uid in self._rows

    def greedy(self, uid: int) -> Optional[int]:
        """The argmax of `rows[uid]` as the program that made the
        logits took it: already on the host, no row fetched.  The serve
        loop probes this for the rows whose sampler is the plain
        argmax."""
        row = self._rows.get(uid)
        return None if row is None else row.token

    def items(self):
        if self.pending:
            self._collect(self)
        pending: Dict[int, List[_Row]] = {}
        for row in self._rows.values():
            if row.host is None:
                pending.setdefault(id(row.logits), []).append(row)
        for rows in pending.values():
            host = self._fetch(rows[0].logits, None)
            for row in rows:
                row.host, row.logits = host[row.i], None
        return super().items()

    def update(self, other: "LogitsRows") -> None:
        self._rows.update(other._rows)

    def discard(self, uid: int) -> None:
        self._rows.pop(uid, None)


def _feed_tokens(staged, fed, source):
    """A decode step's input tokens: row i takes row `source[i]` of the
    step before's tokens, still on the device (`fed`), or, where
    `source[i]` < 0, the token the host staged."""
    return jnp.where(source >= 0, fed[jnp.maximum(source, 0)], staged)


@dataclass
class RaggedInferenceEngineConfig:
    """Reference: RaggedInferenceEngineConfig (state manager + allocator
    sizing knobs)."""
    num_blocks: int = 256
    block_size: int = 64
    max_blocks_per_seq: int = 32
    # decode-batch width.  32 (vs the reference's conservative defaults):
    # decode is HBM-bandwidth-bound, so widening the batch multiplies
    # aggregate tok/s nearly for free until KV reads dominate weight reads
    max_seqs: int = 32
    prefill_chunk_size: int = 256
    # Dynamic SplitFuse budget: max new prefill tokens scheduled per put()
    max_prefill_tokens_per_step: int = 512
    # tokens sampled per compiled decode-burst call (generate paths):
    # on-device sampling + feedback, so the host loop runs once per burst
    # instead of once per token
    decode_burst: int = 8
    # arena layout: "auto" merges the (kv_heads, head_dim) pair into one
    # unpadded minor dim when the padded 5-D arena would crowd the chip
    # (see ragged_ops.init_arena) — merged arenas serve via the gather
    # path, 5-D arenas via the fused Pallas kernels
    arena_merged: object = "auto"
    # shard weights + KV arena over the first N devices (reference:
    # inference/v2/model_implementations/sharding/{attn,mlp}.py)
    tensor_parallel_size: int = 1
    # how the per-block TP collectives run (only read at tp > 1):
    # "xla"   — GSPMD inserts the block all-reduces; fused attention
    #           kernels run per-shard via _shard_mapped_tp (the default
    #           escape hatch — serves every arch/layout tp=1 serves)
    # "fused" — the whole serving program runs in one shard_map region
    #           with ring compute-collective matmuls (ops/tp_matmul.py:
    #           all-gather-producer + matmul-reduce-scatter, each hop
    #           issued while the previous chunk's matmul runs);
    #           refuses unsupported layouts loudly (inference/v2/
    #           tp_ragged.tp_fused_unsupported_reason)
    tp_collectives: str = "xla"
    # fresh full prompts within budget run ONE dense-causal-flash forward
    # (ragged_ops.prefill_full, measured 5.1x the chunked path) instead
    # of the per-chunk blocked kernel; False forces chunked everywhere
    full_prompt_prefill: bool = True


class InferenceEngineV2:
    """put()/flush() continuous-batching engine over a paged KV arena."""

    def __init__(self, model, params=None,
                 config: Optional[RaggedInferenceEngineConfig] = None,
                 topology=None):
        # set-up's `engine.build` span: weights cast and placed, the arena
        # made, the first small programs run
        with span("engine.build") as built:
            self._build(model, params, config, topology)
            built.set_metadata(
                params_bytes=sum(x.nbytes
                                 for x in jax.tree.leaves(self.params)),
                arena_bytes=sum(x.nbytes
                                for x in jax.tree.leaves(self.arena)))

    def _build(self, model, params, config, topology) -> None:
        self.cfg = model.cfg if hasattr(model, "cfg") else model
        self.config = config or RaggedInferenceEngineConfig()
        if params is None:
            if not hasattr(model, "init_params"):
                raise ValueError("need params= or a model with init_params")
            params = model.init_params(jax.random.PRNGKey(0))
        def _to_compute_dtype(x):
            x = jnp.asarray(x)
            # fp8 serving-weight codes (quantize_serving_weights) must
            # keep their 1-byte storage — float8 IS a jnp.floating
            # subtype, so a blanket cast would silently un-quantize them
            if x.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
                return x
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.cfg.dtype)
            return x

        def _map_leaf(path, x):
            # quantization scale keys keep fp32 (the dequant/post-scale
            # multiplies in fp32)
            if path and getattr(path[-1], "key", None) in ("q_scales",
                                                           "q_col_scales"):
                return jnp.asarray(x)
            return _to_compute_dtype(x)

        self.params = jax.tree_util.tree_map_with_path(_map_leaf, params)

        # -- tensor parallelism: shard weights (column/row per _TP_RULES)
        # and the KV arena (kv-head dim) over the tp mesh axis; GSPMD then
        # inserts the per-layer allreduce at the row-parallel matmuls, the
        # same cut points as the reference's sharding/attn.py + mlp.py.
        self.topology = topology
        if (topology is not None and self.config.tensor_parallel_size > 1
                and topology.tp_size != self.config.tensor_parallel_size):
            raise ValueError(
                f"topology has tp_size={topology.tp_size} but config asks "
                f"tensor_parallel_size={self.config.tensor_parallel_size}; "
                f"pass one or make them agree")
        if self.topology is None and self.config.tensor_parallel_size > 1:
            from ...parallel.mesh import make_tp_mesh
            self.topology = make_tp_mesh(self.config.tensor_parallel_size)
        self.tp = self.topology.tp_size if self.topology is not None else 1
        if self.config.tp_collectives not in ("xla", "fused"):
            raise ValueError(
                f"tp_collectives must be 'xla' or 'fused', got "
                f"{self.config.tp_collectives!r}")
        if self.config.tp_collectives == "fused" and self.tp <= 1:
            raise ValueError(
                "tp_collectives='fused' requires tensor_parallel_size > 1 "
                "(there is no collective to fuse at tp=1; the default "
                "'xla' keeps tp=1 byte-identical)")
        # the model's family: its programs and arena, what they take, and
        # the step accounts (`families.py`)
        self.family = family_of(self.cfg)
        if not self.family.shards and (
                self.tp > 1 or self.config.tp_collectives != "xla"):
            self.family.refuse(
                "tensor parallelism (tensor_parallel_size > 1, "
                "tp_collectives='fused')", self.family.tp_error)
        if self.tp > 1:
            if self.cfg.num_heads % self.tp or self.cfg.kv_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide num_heads="
                    f"{self.cfg.num_heads} and kv_heads={self.cfg.kv_heads}")
            from jax.sharding import NamedSharding
            from ...runtime.zero.sharding import (ZeroShardingRules,
                                                  param_specs)
            rules = ZeroShardingRules(0, self.topology,
                                      tp_rules=getattr(model, "tp_rules",
                                                       None))
            specs = param_specs(rules, self.params)
            mesh = self.topology.mesh
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                self.params, specs)
            from jax.sharding import PartitionSpec
            self._replicated = NamedSharding(mesh, PartitionSpec())
            self._param_specs = specs
        else:
            # weights somebody committed to a device make every program's
            # outputs committed; host inputs are then staged the same
            # way, so a program sees one kind of operand whether it came
            # from the host or from the program before (`dispatch`)
            held = [x.sharding for x in jax.tree.leaves(self.params)
                    if x.committed and len(x.sharding.device_set) == 1]
            self._replicated = held[0] if held else None
            self._param_specs = None

        self.arena = init_arena(self.cfg, self.config.num_blocks,
                                self.config.block_size, self.topology,
                                merged=self.config.arena_merged,
                                max_seqs=self.config.max_seqs)
        # the ledger counts the pools of the arena the family made
        nb, window, state_slots = self.family.pools(self.cfg, self.arena,
                                                    self.config)
        self.state = DSStateManager(
            nb, self.config.block_size, self.config.max_blocks_per_seq,
            self.config.max_seqs, window=window, state_slots=state_slots)
        # per-sequence token ceiling: arena lease AND model context — learned
        # position embeddings clip silently past max_seq_len, so enforce it
        # here with a loud error instead
        self.max_tokens_per_seq = min(
            self.config.max_blocks_per_seq * self.config.block_size,
            self.cfg.max_seq_len)
        # fused kernels under tp run per-shard via shard_map; the mesh is a
        # static arg of the serving programs (hashable)
        self._kernel_mesh = (self.topology.mesh if self.tp > 1 else None)
        # fused compute-collective TP programs (tp_collectives="fused"):
        # the serving programs run in one shard_map region with ring
        # collective-matmuls; unsupported layouts refuse loudly here —
        # a silent GSPMD fallback would benchmark the wrong path
        self._tpp = None
        if self.tp > 1 and self.config.tp_collectives == "fused":
            from .tp_ragged import (TPServingPrograms,
                                    tp_fused_unsupported_reason)
            reason = tp_fused_unsupported_reason(
                self.cfg, self.config, self.params, self.arena)
            if reason is not None:
                raise ValueError(
                    f"tp_collectives='fused' cannot serve this "
                    f"configuration: {reason} — tp_collectives='xla' "
                    f"(the GSPMD path) serves it")
            self._tpp = TPServingPrograms(self.cfg, self.topology,
                                          self._param_specs, self.config)
        # one program namespace for every serving call site: the fused
        # TP programs, or the ragged_ops programs with their (cfg, n_tp,
        # mesh) statics bound — TPServingPrograms' signatures are the
        # ragged ones minus exactly those statics, so the call sites
        # never branch
        if self._tpp is not None:
            self._programs = self._tpp
        else:
            from functools import partial
            from types import SimpleNamespace
            bind = dict(n_tp=self.tp, mesh=self._kernel_mesh)
            self._programs = SimpleNamespace(
                prefill_chunks=partial(prefill_chunks, self.cfg, **bind),
                decode_step=partial(decode_step, self.cfg, **bind),
                decode_tokens=partial(decode_tokens, self.cfg, **bind),
                decode_multi_step=partial(decode_multi_step, self.cfg,
                                          **bind),
                verify_tokens=partial(verify_tokens, self.cfg, **bind))
        # device-resident zero temperature for greedy verify dispatches
        # (mode="greedy" ignores it; a fresh per-dispatch staging would
        # put one needless h2d transfer on the hot path)
        self._greedy_temp = self._host_in(np.zeros((), np.float32))
        # the select in front of every decode_step (`dispatch`): built
        # and run once here, so no later step compiles it; `_no_tokens`
        # stands in for the step before's tokens where no row is fed
        self._feed_tokens = jax.jit(
            _feed_tokens, **({} if self._replicated is None
                             else dict(out_shardings=self._replicated)))
        self._no_tokens = self._host_in(
            np.zeros(self.config.max_seqs, np.int32))
        self._feed_tokens(self._no_tokens, self._no_tokens,
                          self._host_in(np.full(self.config.max_seqs, -1,
                                                np.int32)))
        # fresh-full-prompt fast path (ragged_ops.prefill_full): dense
        # causal flash for whole prompts — gated off under tp (no
        # shard_map wiring) and for archs whose masks live in the chunk
        # kernels; config.full_prompt_prefill=False forces chunked
        from .ragged_ops import prefill_full_supported
        self._use_prefill_full = (self.config.full_prompt_prefill
                                  and self.tp == 1
                                  and prefill_full_supported(self.cfg))
        self._last_logits = LogitsRows(self._fetch_logits)
        self._rng = jax.random.PRNGKey(0)
        # host-sync ledger: every EXPLICIT device->host fetch the engine
        # performs bumps d2h_fetches (the implicit ones are what the
        # transfer guard + DST001 forbid, so this IS the engine's total).
        # Tests divide deltas by tokens generated to read
        # host syncs per token — the number multi-step decode amortizes.
        self.profile: Dict[str, int] = {"d2h_fetches": 0}
        # radix prefix KV cache (serving/prefix_cache.py), off until
        # enable_prefix_cache(): put() then attaches matched shared
        # blocks to fresh sequences and flush() caches completed prompts
        self.prefix_cache = None
        self._prefix_leases: Dict[int, object] = {}
        # multi-LoRA serving (serving/tenancy): stacked adapter factors
        # attached by the adapter pool (attach_lora) + per-sequence pool
        # slot bindings (set_adapter).  Batches with NO adapter rows —
        # including everything before attach_lora — trace the exact
        # single-tenant programs (the parity lock): the LoRA operands
        # only enter a program when some row needs them.
        self._lora = None
        self._adapter_slots: Dict[int, int] = {}
        # expert-paged MoE serving (serving/experts.ExpertPool), off
        # until enable_expert_paging(); None keeps every program and
        # params pytree bit-for-bit the unpaged model
        self._expert_pool = None

    def enable_prefix_cache(self, max_blocks: int, host_blocks: int = 0,
                            host_quant: str = "none"):
        """Turn on prefix KV reuse: completed prompts' full KV blocks are
        kept in a radix tree (up to `max_blocks`) and later prompts
        sharing a token prefix attach them read-only, prefilling only
        the uncovered suffix.  `host_blocks` > 0 additionally attaches a
        host-memory spill tier (serving/kv_tier.HostKVTier, up to that
        many blocks, optionally int8-quantized via `host_quant`) behind
        the cache's eviction seam: evicted spans demote arena -> host
        through this engine's batched span IO and promote back on a
        later hit — the effective prefix cache grows to host-RAM scale.
        0 = bit-for-bit the HBM-only cache.  Returns the PrefixCache
        (telemetry / invalidation handle)."""
        from ...serving.kv_tier import HostKVTier
        from ...serving.prefix_cache import PrefixCache
        self.family.refuse("the prefix cache and its host KV tier "
                           "(shared blocks are attached and spilled as "
                           "K/V pages)")
        scaling = getattr(self.cfg, "rope_scaling", None)
        if scaling and scaling[0] == "longrope":
            # phi3-style longrope picks short/long rope factors from the
            # sequence's FULL prompt length (regime_len), so cached KV is
            # NOT a pure function of (tokens, positions, weights): a
            # prefix written under the short band would silently corrupt
            # a longer prompt served from the long band
            raise ValueError(
                "prefix KV reuse is unsupported for longrope models: the "
                "cached KV depends on the writer's total prompt length "
                "(short/long rope band), so token-matched reuse across "
                "requests of different lengths would be silently wrong — "
                "use prefix_cache_blocks=0 for this model")
        if self.state.seqs:
            raise RuntimeError(
                "enable_prefix_cache with live sequences: drain or flush "
                "them first (their blocks predate the cache's refcounts "
                "bookkeeping window)")
        if self.prefix_cache is not None:
            # a replaced cache must return its blocks (no live sequences
            # means nothing is pinned, so this always fully drains) —
            # host-tier spans included
            self.prefix_cache.invalidate()
            if self.prefix_cache.cached_blocks \
                    or self.prefix_cache.host_cached_blocks:
                raise RuntimeError(
                    "old prefix cache failed to drain (refcount bug)")
        tier = (HostKVTier(self, host_blocks, quant=host_quant)
                if host_blocks > 0 else None)
        self.prefix_cache = PrefixCache(
            self.state.allocator, self.config.block_size, max_blocks,
            tier=tier)
        return self.prefix_cache

    # -- multi-LoRA adapter serving (serving/tenancy) ---------------------
    # the serving layer probes this before enabling an adapter pool
    @property
    def supports_lora(self) -> bool:
        return self.family.lora

    # per-sequence recurrent state beside the blocks: the serving layer
    # refuses what assumes "a sequence's state is its blocks"
    @property
    def recurrent_state(self) -> bool:
        return self.family.row_slots

    def attach_lora(self, lora) -> None:
        """Attach (None = detach) the stacked multi-LoRA factors the
        serving programs' gather-LoRA epilogue reads:
        {"a": [L, slots, NH*D, r], "b": [L, slots, r, H]} device arrays
        over the attention output projection (ops/lora_matmul).  The
        adapter pool (serving/tenancy/adapter_pool.py) owns the slot
        tensors and re-attaches after every slot mutation; the engine
        just holds the current view.  Batches without adapter rows never
        see these operands — their programs stay bit-for-bit
        single-tenant."""
        if lora is not None:
            self.family.refuse("LoRA adapters (the gather epilogue sits "
                               "on the dense block's output projection)")
            a, b = lora["a"], lora["b"]
            if (a.ndim != 4 or b.ndim != 4 or a.shape[0] != b.shape[0]
                    or a.shape[1] != b.shape[1] or a.shape[3] != b.shape[2]):
                raise ValueError(
                    f"attach_lora needs a [L,slots,K,r] / [L,slots,r,H] "
                    f"stack, got a {tuple(a.shape)}, b {tuple(b.shape)}")
            if a.shape[0] != self.cfg.num_layers:
                raise ValueError(
                    f"attach_lora stack covers {a.shape[0]} layers, "
                    f"model has {self.cfg.num_layers}")
        self._lora = lora

    def set_adapter(self, uid: int, slot: int) -> None:
        """Bind sequence `uid`'s batch rows to LoRA pool slot `slot`
        (< 0 = base model).  The binding must land before the
        sequence's first prefill token and holds until flush — mid-
        stream slot moves would change the math a request was admitted
        under."""
        if self._lora is None and slot >= 0:
            raise RuntimeError(
                f"set_adapter({uid}, {slot}) with no LoRA stack "
                f"attached — attach_lora first (the adapter pool owns "
                f"this ordering)")
        if slot >= 0 and uid in self.state.seqs \
                and self.state.seqs[uid].seen_tokens > 0:
            raise RuntimeError(
                f"set_adapter({uid}, {slot}) after the sequence began "
                f"prefill — the binding must cover every token")
        if slot < 0:
            self._adapter_slots.pop(uid, None)
        else:
            self._adapter_slots[uid] = int(slot)

    def _batch_adapter_ids(self, descs, n: int):
        """[n] int32 pool slots for a staged batch (row i = descs[i],
        -1 = base row), or None when NO row carries an adapter — the
        None keeps adapter-free batches on the exact single-tenant
        compiled programs (the parity lock)."""
        if self._lora is None or not self._adapter_slots:
            return None
        aids = np.full(n, -1, np.int32)
        any_adapter = False
        for i, d in enumerate(descs):
            s = self._adapter_slots.get(d.uid, -1)
            aids[i] = s
            any_adapter = any_adapter or s >= 0
        return aids if any_adapter else None

    # -- arena block IO (serving/fleet migration transport) ---------------
    def read_kv_block(self, block: int) -> tuple:
        """Host copy of one arena block's K/V pages, shape
        [num_layers, block_size, ...] each — the unit the fleet
        migration transport streams replica-to-replica.  Explicit fetch
        (jax.device_get): migration runs outside the serve step's
        transfer guard, but the same no-implicit-sync discipline
        applies."""
        self.family.refuse(_PAGE_IO)
        if not 0 <= block < self.config.num_blocks:
            raise ValueError(f"bad block id {block}")
        k = jax.device_get(self.arena["k"][:, block])
        v = jax.device_get(self.arena["v"][:, block])
        self.profile["d2h_fetches"] += 2
        return k, v

    def write_kv_block(self, block: int, k, v) -> None:
        """Adopt one migrated block's K/V pages into this engine's
        arena.  The caller must own the block (a fresh allocator lease —
        see fleet/migration.py's insert-before-decref handoff); writing
        a block a live sequence reads would corrupt its KV."""
        self.family.refuse(_PAGE_IO)
        if not 0 <= block < self.config.num_blocks:
            raise ValueError(f"bad block id {block}")
        shape = self.arena["k"].shape         # [L, blocks, bs, ...minor]
        want = (shape[0], self.config.block_size) + tuple(shape[3:])
        for name, page in (("k", k), ("v", v)):
            got = tuple(np.asarray(page).shape)  # dstpu: noqa[DST001] migrated pages arrive as host arrays from the transport
            if got != want:
                # both pages checked: a wrong-shaped page would silently
                # BROADCAST into the arena slot and corrupt the KV
                raise ValueError(
                    f"migrated {name.upper()} page shape {got} does not "
                    f"fit this arena (expected {want}): replicas must "
                    f"share the model and arena layout")
        dt = self.arena["k"].dtype
        self.arena["k"] = self._keep_arena_sharding(
            "k", self.arena["k"].at[:, block].set(
                jnp.asarray(np.asarray(k), dt)))  # dstpu: noqa[DST001] explicit h2d staging of the migrated page
        self.arena["v"] = self._keep_arena_sharding(
            "v", self.arena["v"].at[:, block].set(
                jnp.asarray(np.asarray(v), dt)))  # dstpu: noqa[DST001] explicit h2d staging of the migrated page

    def read_kv_blocks(self, blocks) -> tuple:
        """Batched twin of `read_kv_block`: host copies of a whole block
        span's K/V pages, shape [num_layers, n_blocks, block_size, ...]
        each, in ONE gather fetch per page tensor — the multi-block
        transfer unit of the disagg handoff path (one device round trip
        for the span instead of one per block)."""
        self.family.refuse(_PAGE_IO)
        blocks = [int(b) for b in blocks]
        for b in blocks:
            if not 0 <= b < self.config.num_blocks:
                raise ValueError(f"bad block id {b}")
        idx = jnp.asarray(np.asarray(blocks, np.int32))  # dstpu: noqa[DST001] block ids are host ints from the allocator
        k = jax.device_get(self.arena["k"][:, idx])
        v = jax.device_get(self.arena["v"][:, idx])
        self.profile["d2h_fetches"] += 2
        return k, v

    def write_kv_blocks(self, blocks, k, v) -> None:
        """Batched twin of `write_kv_block`: adopt a whole migrated
        span's K/V pages ([num_layers, n_blocks, block_size, ...]) in
        ONE scatter launch per page tensor.  Same ownership contract:
        the caller holds a fresh allocator lease on every target block,
        and the span's block ids must be distinct (a duplicated scatter
        index would silently keep only one page)."""
        self.family.refuse(_PAGE_IO)
        blocks = [int(b) for b in blocks]
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate block ids in span {blocks}")
        for b in blocks:
            if not 0 <= b < self.config.num_blocks:
                raise ValueError(f"bad block id {b}")
        shape = self.arena["k"].shape         # [L, blocks, bs, ...minor]
        want = (shape[0], len(blocks),
                self.config.block_size) + tuple(shape[3:])
        for name, pages in (("k", k), ("v", v)):
            got = tuple(np.asarray(pages).shape)  # dstpu: noqa[DST001] migrated pages arrive as host arrays from the transport
            if got != want:
                raise ValueError(
                    f"migrated {name.upper()} span shape {got} does not "
                    f"fit this arena (expected {want}): replicas must "
                    f"share the model and arena layout")
        idx = jnp.asarray(np.asarray(blocks, np.int32))  # dstpu: noqa[DST001] block ids are host ints from the allocator
        dt = self.arena["k"].dtype
        self.arena["k"] = self._keep_arena_sharding(
            "k", self.arena["k"].at[:, idx].set(
                jnp.asarray(np.asarray(k), dt)))  # dstpu: noqa[DST001] explicit h2d staging of the migrated span
        self.arena["v"] = self._keep_arena_sharding(
            "v", self.arena["v"].at[:, idx].set(
                jnp.asarray(np.asarray(v), dt)))  # dstpu: noqa[DST001] explicit h2d staging of the migrated span

    def _keep_arena_sharding(self, name: str, updated):
        """Adopted pages arrive as REPLICATED host arrays, and the eager
        scatter's output sharding follows propagation, not the arena's
        NamedSharding — under tp a migration/handoff write could silently
        leave the arena replicated (tp^2 the HBM) until the next donated
        program re-shards it.  Pin the write back onto the arena's own
        sharding (no-op copy when it already matches); `read_kv_blocks`'
        `jax.device_get` reassembles the kv-head shards into the global
        page layout, so cross-tp-degree handoffs exchange full pages."""
        old = self.arena[name].sharding
        if self.tp > 1 and updated.sharding != old:
            updated = jax.device_put(updated, old)
        return updated

    def audit_blocks(self) -> Dict[str, int]:
        """Block-conservation audit: free + live + cache-held blocks must
        account for every block and every refcount (DSStateManager.audit)
        — and, with a host KV tier attached, every demoted span must be
        reachable from exactly one radix-tree node with balanced
        block/byte gauges (PrefixCache.audit_host), so a demoted-but-
        leaked span is as loud as an arena leak.  Raises RuntimeError on
        a leak; returns the merged summary when clean."""
        cache_blocks = (list(self.prefix_cache.block_ids())
                        if self.prefix_cache is not None else ())
        out = self.state.audit(cache_blocks=cache_blocks)
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.audit_host())
        out.update(self.family.audit(self.arena))
        return out

    def _host_in(self, x):
        """Stage a host array as a replicated device array under tp (so jit
        sees consistent NamedShardings), or beside weights committed to
        one device; pass through otherwise."""
        x = jnp.asarray(x)
        if self._replicated is not None:
            x = jax.device_put(x, self._replicated)
        return x

    # -- scheduling ------------------------------------------------------
    def put(self, uids: Sequence[int], tokens_list: Sequence[np.ndarray],
            decode: bool = True, prefixes=None, **step_kw) -> LogitsRows:
        """Admit new sequences and advance the ragged batch one step
        (reference `put` :107).  Returns {uid: last-token logits} for every
        sequence that produced fresh logits this call (`LogitsRows`: a
        row crosses to the host when it is read).  `decode=False`
        runs only the prefill phase — the burst serve loop owns decode via
        `decode_burst_step` and must not have pending burst-chain tokens
        consumed by the per-step decode path here.

        Further keywords (`ahead`, `hold`, `collect`) go to `step` as
        given: a caller that gives none reaches `step(decode=...)` as
        ever (tests and tools replace `step` under that signature).

        `prefixes` maps a fresh uid to a PrefixLease the caller already
        acquired — or to None recording a known miss (the serve loop
        looks up at admission so its KV ledger and the attached prefix
        agree; put must not re-walk the tree either way).  Fresh uids
        WITHOUT an entry look the radix tree up here when the cache is
        enabled, so direct engine use (generate/generate_batch) reuses
        prefixes too.  A matched sequence attaches the shared blocks
        read-only and prefills only the uncovered suffix."""
        # admitting the sequences is part of planning the step that follows
        with span("engine.plan", rows=len(uids)):
            # validate EVERY uid before mutating ANY sequence — a mid-loop
            # raise after partial mutation would double-append tokens on
            # retry
            for uid, toks in zip(uids, tokens_list):
                new_tokens = len(np.asarray(toks).ravel())  # dstpu: noqa[DST001] caller-provided prompt tokens are host arrays per the put() contract
                cur = (self.state.seqs[uid].seen_tokens
                       if uid in self.state.seqs else 0)
                if cur + new_tokens > self.max_tokens_per_seq:
                    raise RuntimeError(
                        f"sequence {uid} would reach {cur + new_tokens} "
                        f"tokens, over the {self.max_tokens_per_seq} limit "
                        f"(min of KV lease capacity and model max_seq_len "
                        f"{self.cfg.max_seq_len})")
                if uid in self.state.seqs and self.state.seqs[uid].in_prefill:
                    raise RuntimeError(
                        f"sequence {uid} is still prefilling "
                        f"({self.state.seqs[uid].seen_tokens}/"
                        f"{len(self.state.seqs[uid].prompt)} prompt tokens); "
                        f"drive step() until query({uid}) returns logits "
                        f"before feeding continuation tokens")
            for uid, toks in zip(uids, tokens_list):
                if uid in self.state.seqs:
                    # continuation: append pre-sampled token(s) to an existing
                    # sequence (the reference's next-token put path)
                    self.state.seqs[uid].generated.extend(
                        int(t) for t in np.asarray(toks).ravel())  # dstpu: noqa[DST001] continuation tokens are host ints the caller sampled
                else:
                    toks = np.asarray(toks, np.int32)  # dstpu: noqa[DST001] caller-provided prompt tokens are host arrays per the put() contract
                    if prefixes is not None and uid in prefixes:
                        # the caller already looked this uid up (an entry of
                        # None records a known miss — no second tree walk,
                        # no double-counted miss)
                        lease = prefixes[uid]
                    elif self.prefix_cache is not None:
                        lease = self.prefix_cache.acquire(toks)
                    else:
                        lease = None
                    if lease is None:
                        self.state.create(uid, toks)
                    else:
                        try:
                            self.state.create(
                                uid, toks,
                                prefix=(lease.blocks, lease.covered))
                        except Exception:
                            self.prefix_cache.abandon(lease)
                            raise
                        self._prefix_leases[uid] = lease
        return self.step(decode=decode, **step_kw)

    def _fetch_tokens(self, program: str, toks) -> List[int]:
        """What a per-step program's end costs the host: its [N] int32
        greedy tokens (waiting for them is waiting for the program)."""
        with span("engine.fetch", program=program, bytes=toks.nbytes):
            toks = jax.device_get(toks)  # dstpu: noqa[DST001] intended: one [N]-token fetch per per-step program — the step's sync point; the [N, V] logits stay on the device (LogitsRows); explicit so the transfer guard admits it
        self.profile["d2h_fetches"] += 1
        return toks.tolist()

    def _fetch_logits(self, logits, row: Optional[int]) -> np.ndarray:
        """Row `row` of a program's [N, V] logits (None: all of it) on
        the host — `LogitsRows`' reader.  The span's `bytes` tell it from
        a step's token fetch."""
        if row is not None:
            logits = logits_row(
                logits, self._host_in(np.asarray(row, np.int32)))
        with span("engine.fetch", program="logits_rows",
                  bytes=logits.nbytes):
            logits = jax.device_get(logits)  # dstpu: noqa[DST001] intended: logits a caller reads (a host-sampled row, query(), the burst loops' first-token batch); explicit so the transfer guard admits it
        self.profile["d2h_fetches"] += 1
        return logits

    def step(self, decode: bool = True, ahead: Optional[LogitsRows] = None,
             hold: Collection[int] = (), collect: bool = True
             ) -> LogitsRows:
        """One ragged step: dispatch its programs, then collect their
        tokens.  `collect=False` leaves that to the caller, who goes on
        while the programs run and calls `collect(rows)` later (the
        serve loop dispatches its next step first); `ahead` and `hold`
        are `dispatch`'s."""
        rows = self.dispatch(decode=decode, ahead=ahead, hold=hold)
        if collect:
            self.collect(rows)
        return rows

    def collect(self, rows: LogitsRows,
                part: Optional[str] = None) -> LogitsRows:
        """Fetch the tokens of a dispatched step's programs (`part`:
        "prefill" or "decode" alone; None = all that is left) and fill
        in their rows — waiting for the tokens is waiting for the
        programs.  Returns the rows this call filled in.  A row whose
        sequence was flushed since the dispatch (or replaced under its
        uid: a preempted request comes back as another sequence) is left
        out and counted in the result's `overrun`: it was computed for
        nothing.  The programs leave `rows` only once their tokens are
        here: after a fetch that raised they can be collected again."""
        programs = [] if part == "decode" else list(rows.prefill)
        if part != "prefill" and rows.decode is not None:
            programs.append(rows.decode)
        fetched = [self._fetch_tokens(prog.name, prog.toks)
                   for prog in programs]
        out = LogitsRows(self._fetch_logits)
        for prog, toks in zip(programs, fetched):
            if prog is rows.decode:
                rows.decode = None
            else:
                rows.prefill.remove(prog)
            for d, i in prog.rows:
                if self.state.seqs.get(d.uid) is d:
                    out._set(d.uid, prog.logits, i, toks[i])
                else:
                    out.overrun += 1
        rows.update(out)
        self._last_logits.update(out)
        return out

    def dispatch(self, decode: bool = True,
                 ahead: Optional[LogitsRows] = None,
                 hold: Collection[int] = ()) -> LogitsRows:
        """`step`'s first half: plan the step and launch its programs
        (`prefill_full`, `prefill_chunks`, `decode_step`), fetching
        nothing.  `seen_tokens`, block leases and lengths advance here;
        the tokens stay on the device, and the rows returned `pending`,
        until `collect`.

        `ahead` is an earlier step whose decode tokens are still
        uncollected: a sequence with a row there (and its uid not in
        `hold`) decodes again, its input token taken from that row on
        the device (`_feed_tokens`); the host's `generated` list catches
        up when `ahead` is collected.  Every other decode row takes the
        pending token the host staged, as ever."""
        pending = LogitsRows(self._fetch_logits, self.collect)
        C = self.config.prefill_chunk_size
        # a zero/negative budget must still make 1 token of progress per
        # step, or in_prefill sequences (and generate()) would spin forever
        budget = max(self.config.max_prefill_tokens_per_step, 1)

        # 0) fresh-full-prompt fast path: a prompt starting at position 0
        #    whose whole length fits this step's budget needs no chunking —
        #    prefill_full runs the dense causal flash kernel training uses
        #    (measured 2.3x the chunked row at medium/8k, r5) and scatters
        #    the KV for decode.  Scheduling guards:
        #    - any MID-PREFILL sequence suspends the fast path this step
        #      (FIFO fairness: the fresh-arrival stream must not starve a
        #      chunked continuation by draining the budget every step);
        #    - a FRESH prompt longer than the whole step budget can never
        #      ride the fast path, and the suspension guard above only
        #      protects mid-prefill sequences — so when one exists, one
        #      chunk of budget is RESERVED for the chunked loop below,
        #      which (FIFO) starts the earliest pending prompt; once that
        #      prompt is mid-prefill the suspension guard takes over.
        #      Without the reservation, a sustained stream of short fresh
        #      arrivals totalling >= budget/step could defer a long fresh
        #      prompt indefinitely;
        #    - one batch holds only prompts from ONE power-of-2 length
        #      bucket, and its PADDED slot count is capped at
        #      max(2x the budget's bucket, max_seqs * 128) — a lone long
        #      prompt cannot drag 31 short ones up to its padding (memory)
        #      and the (NS, S) program bucket count stays small (compiles);
        #    over-budget prompts fall through to the chunked path below.
        #    (a fresh prefix-attached sequence starts at seen_tokens ==
        #    prefix_covered — that is arrival state, not mid-prefill
        #    progress, so it must not suspend the fast path for others;
        #    with the cache off prefix_covered is 0 and the guard is
        #    bit-for-bit the old `seen_tokens > 0`)
        if self._use_prefill_full and not any(
                d.seen_tokens > d.prefix_covered and d.in_prefill
                and not d.done
                for d in self.state.seqs.values()):
            with span("engine.plan") as plan:
                pad_cap = 128
                while pad_cap < 2 * budget:
                    pad_cap *= 2
                # floor: a full batch of minimum-bucket (128-slot) prompts is
                # always affordable — without this, a small budget would
                # de-batch short prompts (the real-token budget still
                # governs).  NOTE this floor makes the effective padded-slot
                # cap max(2 * budget_bucket, max_seqs * 128): for small
                # budgets the batch-width floor wins over the budget bucket.
                pad_cap = max(pad_cap, self.config.max_seqs * 128)
                full_budget = budget
                if any(d.seen_tokens == d.prefix_covered and not d.done
                       and d.in_prefill
                       and (len(d.prompt) > budget or d.prefix_covered > 0)
                       for d in self.state.seqs.values()):
                    # fairness reservation for a pending prompt that can
                    # ONLY prefill through the chunked loop: an over-budget
                    # fresh prompt, or a prefix-attached one (seen ==
                    # prefix_covered > 0 — ineligible for the fast path at
                    # any length, and not yet protected by the mid-prefill
                    # suspension above).  Without it, a sustained stream of
                    # fresh arrivals totalling >= budget/step could defer
                    # either indefinitely.
                    full_budget = max(budget - C, 0)
                fresh: List = []
                S = 128
                for d in self.state.seqs.values():
                    if not (d.seen_tokens == 0 and not d.done
                            and 0 < len(d.prompt) <= full_budget - sum(
                                len(f.prompt) for f in fresh)
                            and len(fresh) < self.config.max_seqs
                            # adapter rows need the chunked path's gather-
                            # LoRA epilogue (prefill_full has none)
                            and self._adapter_slots.get(d.uid, -1) < 0):
                        continue
                    bucket = 128
                    while bucket < len(d.prompt):
                        bucket *= 2
                    if fresh and bucket != S:
                        continue          # one length bucket per batch
                    ns_next = 1
                    while ns_next < len(fresh) + 1:
                        ns_next *= 2
                    if ns_next * bucket > pad_cap:
                        continue          # padded-slot budget guard
                    S = bucket
                    fresh.append(d)
                if fresh:
                    from .ragged_ops import prefill_full
                    NS = 1
                    while NS < len(fresh):
                        NS *= 2
                    ftokens = np.zeros((NS, S), np.int32)
                    flens = np.zeros(NS, np.int32)
                    ftables = np.zeros((NS,) + self.state.table_shape, np.int32)
                    factive = np.zeros(NS, bool)
                    for i, d in enumerate(fresh):
                        n = len(d.prompt)
                        self.state.ensure_capacity(d, n)
                        ftokens[i, :n] = d.prompt
                        flens[i] = n
                        ftables[i] = self.state.block_table(d)
                        factive[i] = True
                plan.set_metadata(rows=len(fresh))
            if fresh:
                with span("engine.dispatch", program="prefill_full"):
                    logits, toks, self.arena = prefill_full(
                        self.cfg, self.params, self.arena,
                        self._host_in(ftokens), self._host_in(flens),
                        self._host_in(ftables), self._host_in(factive),
                        **self._slots_kw(fresh, NS))
                for d in fresh:
                    d.seen_tokens = len(d.prompt)
                pending.prefill.append(_Program(
                    "prefill_full", logits, toks,
                    [(d, i) for i, d in enumerate(fresh)]))
                budget -= sum(len(d.prompt) for d in fresh)
                budget = max(budget, 0)
        # slot bound: every full chunk consumes C budget and each sequence
        # contributes at most one partial (tail) chunk, so this cap never
        # throttles below what the budget itself allows; staging arrays are
        # allocated at the next power of two so NC below never clips
        with span("engine.plan") as plan:
            cap = budget // C + self.config.max_seqs
            cap_alloc = 1
            while cap_alloc < cap:
                cap_alloc *= 2
            # 1) prefill: plan the step's chunks (FIFO over pending prompts,
            #    possibly several chunks of one long prompt, budget-bounded),
            #    then advance them all in ONE compiled call — the
            #    ragged-batch composition of Dynamic SplitFuse (reference:
            #    ragged_wrapper + atom_builder build one forward from many
            #    sequences' chunks).
            #    The chunk-slot count is padded to a power of two so the
            #    program compiles once per bucket, and a lone small chunk pays
            #    the 1-slot program, not the worst case.
            planned: List[tuple] = []          # (d, start, n)
            pseen = {d.uid: d.seen_tokens for d in self.state.seqs.values()}
            tokens = np.zeros((cap_alloc, C), np.int32)
            pos0s = np.zeros(cap_alloc, np.int32)
            nvalids = np.zeros(cap_alloc, np.int32)
            tlens = np.zeros(cap_alloc, np.int32)
            tables = np.zeros((cap_alloc,) + self.state.table_shape, np.int32)
            active = np.zeros(cap_alloc, bool)
            # (recurrent state: a chunk starts from what the chunk before
            # it left in the slot, so a program holds one chunk a sequence)
            chunked = set()
            while budget > 0 and len(planned) < cap:
                d = next((s for s in self.state.seqs.values()
                          if pseen[s.uid] < len(s.prompt) and not s.done
                          and s.uid not in chunked), None)
                if d is None:
                    break
                start = pseen[d.uid]
                n = self.state.chunk_room(
                    d, start, min(C, len(d.prompt) - start, budget))
                if n == 0:     # a two-kind cache short of the window kind
                    break
                # (the row's first query of this STEP: an earlier chunk of
                # it in this program still reads what lies behind `start`)
                self.state.ensure_capacity(d, start + n,
                                           first_query=d.seen_tokens)
                i = len(planned)
                tokens[i, :n] = d.prompt[start:start + n]
                pos0s[i] = start
                nvalids[i] = n
                # full prompt length, so longrope chooses the short/long band
                # the way HF's one-shot prompt forward does, for every chunk
                tlens[i] = len(d.prompt)
                tables[i] = self.state.block_table(d)
                active[i] = True
                if self.family.row_slots:
                    chunked.add(d.uid)
                planned.append((d, start, n))
                pseen[d.uid] = start + n
                budget -= n
            plan.set_metadata(rows=len(planned))
        if planned:
            with span("engine.dispatch", program="prefill_chunks") as sent:
                NC = 1
                while NC < len(planned):
                    NC *= 2
                descs = [d for d, _, _ in planned]
                aids = self._batch_adapter_ids(descs, NC)
                lkw = ({} if aids is None else
                       dict(adapter_ids=self._host_in(aids), lora=self._lora))
                logits, toks, self.arena = self._programs.prefill_chunks(
                    self.params, self.arena, self._host_in(tokens[:NC]),
                    self._host_in(pos0s[:NC]), self._host_in(nvalids[:NC]),
                    self._host_in(tables[:NC]), self._host_in(active[:NC]),
                    self._host_in(tlens[:NC]), **lkw,
                    **self._slots_kw(descs, NC))
                sent.set_metadata(**self.family.chunk_account(
                    self, pos0s[:NC], nvalids[:NC]))
            for d, start, n in planned:
                d.seen_tokens = start + n
                # the window-kind blocks the chunk read and no later query
                # will (a one-kind cache holds none)
                self.state.release_behind(d, d.seen_tokens)
            rows = [(d, i) for i, (d, _, _) in enumerate(planned)
                    if not d.in_prefill]
            if rows:      # chunks that end no prompt leave nothing to fetch
                pending.prefill.append(
                    _Program("prefill_chunks", logits, toks, rows))
        # 2) decode: one token for every sequence with a pending input token
        #    (suppressed under decode=False: the burst serve path keeps one
        #    pending token per chained sequence, which must wait for the
        #    next decode_burst_step, not be host-decoded here)
        with span("engine.plan") as plan:
            # id(sequence) -> its row of `ahead`'s decode program
            fed = {id(d): i for d, i in ahead.decode.rows
                   if d.uid not in hold and self.state.seqs.get(d.uid) is d
                   } if ahead is not None and ahead.decode is not None \
                else {}
            batch = [d for d in self.state.decode_batch() if id(d) in fed
                     or (d.generated and d.seen_tokens
                         < len(d.prompt) + len(d.generated))
                     ] if decode else []
            if batch:
                B = self.config.max_seqs
                tokens = np.zeros(B, np.int32)
                source = np.full(B, -1, np.int32)
                lens = np.zeros(B, np.int32)
                tables = np.zeros((B,) + self.state.table_shape, np.int32)
                active = np.zeros(B, bool)
                for i, d in enumerate(batch):
                    if id(d) in fed:
                        source[i] = fed[id(d)]
                    else:
                        tokens[i] = d.generated[d.seen_tokens
                                                - len(d.prompt)]
                    lens[i] = d.seen_tokens
                    self.state.ensure_capacity(d, d.seen_tokens + 1)
                    tables[i] = self.state.block_table(d)
                    active[i] = True
            plan.set_metadata(rows=len(batch))
        if batch:
            with span("engine.dispatch", program="decode_step"):
                aids = self._batch_adapter_ids(batch, B)
                lkw = ({} if aids is None else
                       dict(adapter_ids=self._host_in(aids), lora=self._lora))
                logits, toks, self.arena = self._programs.decode_step(
                    self.params, self.arena,
                    self._feed_tokens(
                        self._host_in(tokens),
                        ahead.decode.toks if fed else self._no_tokens,
                        self._host_in(source)),
                    self._host_in(lens), self._host_in(tables),
                    self._host_in(active), **lkw, **self._slots_kw(batch, B))
            pending.kv_live_blocks = sum(
                d.seen_tokens // self.config.block_size + 1 for d in batch)
            pending.kv_table_blocks = tables.size
            for d in batch:
                d.seen_tokens += 1
            pending.decode = _Program("decode_step", logits, toks,
                                      list(zip(batch, range(len(batch)))))
            pending.decode_rows = len(batch)
            pending.fed_rows = sum(id(d) in fed for d in batch)
        # the family's account of its cache, on every step
        self.family.step_account(self, pending, batch)
        return pending

    def _slots_kw(self, rows, n: int) -> dict:
        """The state slots of a program's `n` rows (sequences `rows`, then
        padding) as its `slots=`, where the family's programs take a row ->
        slot vector; the others are handed the operands they always were."""
        if not self.family.row_slots:
            return {}
        slots = np.zeros(n, np.int32)
        slots[:len(rows)] = [d.state_slot for d in rows]
        return {"slots": self._host_in(slots)}

    # -- burst decode: on-device sampling, one host dispatch per K tokens
    # the serving layer probes this before merging heterogeneous sampling
    # signatures into one per-row burst (vs per-signature-group bursts)
    supports_per_row_sampling = True
    # the serving layer probes this before enabling speculative decoding
    # (decode_burst_step drafts= runs the compiled verify program)
    @property
    def supports_draft_verify(self) -> bool:
        return self.family.span_core is not None
    # per-request counter-based sampling streams (serving/streaming.
    # seeded_sample — the streaming layer's replayable stochastic
    # decode): the compiled burst and multi-step programs run the SAME
    # Philox4x64-10 draw on device (ragged_ops.philox_word, bit-exact
    # against numpy's generator), so seeded rows replay
    # deterministically without a host round-trip.  decode_burst_step
    # takes seeds=/seed_positions= dicts; decode_multi_step threads the
    # per-row stream positions through its on-device termination masks.
    # Properties, not constants: the fused-TP program set
    # (tp_ragged.TPServingPrograms) carries neither the seed operands
    # nor a multi-step program yet, and a silent fallback there would
    # defeat the serve loop's loud capability checks (xla TP serves
    # both).
    @property
    def supports_seeded_sampling(self) -> bool:
        return self._tpp is None

    # K decode steps per compiled dispatch with on-device sampling,
    # termination, and ONE packed device->host fetch (decode_multi_step)
    @property
    def supports_multi_step(self) -> bool:
        return self._tpp is None and not self.family.row_slots

    # grammar-constrained decoding (serving/structured): fsm= operands
    # on decode_multi_step and the draft-verify path — the fused-TP
    # program set carries neither
    @property
    def supports_structured(self) -> bool:
        return self._tpp is None and not self.family.row_slots

    # expert-paged MoE decode (serving/experts.ExpertPool): the slot
    # stacks/maps ride params["layers"] through every layer scan, which
    # the fused-TP program set does not thread (and its weights are
    # pre-sharded per rank — a host-side slot splice would corrupt them)
    @property
    def supports_moe(self) -> bool:
        # (the slot stacks ride `params["layers"]`, and the census rider
        # the arena: `Family.shards`)
        return (self.cfg.moe_experts > 1 and self._tpp is None
                and self.family.shards)

    # the router counters of `expert_ffn.moe`'s callers
    # (expert_ffn.count_names), a rider of their arena that every program
    # accumulates
    @property
    def supports_moe_counts(self) -> bool:
        return "moe_counts" in self.arena

    def drain_moe_counts(self) -> Dict[str, int]:
        """Fetch-and-reset the router counters: ONE explicit d2h of a few
        int32 per drain (the serve loop's interval), ledgered like every
        other fetch."""
        from .expert_ffn import count_names
        counts = self.arena["moe_counts"]
        with span("engine.fetch", program="moe_counts", bytes=counts.nbytes):
            out = jax.device_get(counts)  # dstpu: noqa[DST001] intended: the periodic counter drain (a few int32 per interval), explicit so the transfer guard admits it
        self.profile["d2h_fetches"] += 1
        self.arena["moe_counts"] = jnp.zeros_like(counts)
        return dict(zip(count_names(self.cfg), out.tolist()))

    def enable_expert_paging(self, slots_per_layer: int,
                             spill: str = "none"):
        """Page this MoE model's expert FFN weights: only
        `slots_per_layer` experts per layer stay HBM-resident in slot
        stacks, the rest live on host (optionally int8 via `spill`) and
        promote back on demand; demoted experts' tokens REROUTE to the
        best resident expert (masked router) instead of faulting.  The
        original [L, E, ...] stacks are deleted from params — the HBM
        saving is real.  Rebuilds the KV arena with the router-census
        rider, so it refuses while sequences are live.  Returns the
        ExpertPool (policy / telemetry handle).

        slots_per_layer == E keeps every expert in its home slot —
        bit-for-bit the unpaged model (spill='none')."""
        if not self.supports_moe:
            raise RuntimeError(
                f"expert paging needs an MoE model served without "
                f"fused-TP collectives (moe_experts="
                f"{self.cfg.moe_experts}, fused_tp={self._tpp is not None})"
            )
        if self.tp > 1:
            raise RuntimeError(
                "expert paging under tensor parallelism is not wired: "
                "the slot stacks would need per-rank resharding on every "
                "promote (serve MoE with tp=1, or keep experts unpaged)")
        if self._expert_pool is not None:
            raise RuntimeError(
                "expert paging already enabled (one pool owns the slot "
                "tensors; reconstruct the engine to resize it)")
        if self.state.seqs:
            raise RuntimeError(
                "enable_expert_paging with live sequences: drain or "
                "flush them first (the arena is rebuilt with the census "
                "rider)")
        from ...serving.experts import ExpertPool
        self.arena = init_arena(self.cfg, self.config.num_blocks,
                                self.config.block_size, self.topology,
                                merged=self.config.arena_merged,
                                moe_census=True)
        self._expert_pool = ExpertPool(self, slots_per_layer, spill=spill)
        return self._expert_pool

    def _install_expert_pages(self, pages: Dict[str, object]) -> None:
        """ExpertPool publish hook: splice the slot stacks + slot map +
        resident mask into params['layers'], deleting the dense [L, E,
        ...] expert stacks on first install (paged serving must not hold
        both copies — that would be a 1 + S/E footprint, not S/E)."""
        layers = self.params["layers"]
        for key in ("moe_w_up", "moe_w_down", "moe_w_gate_proj"):
            layers.pop(key, None)
        layers.update(pages)

    def drain_moe_census(self) -> np.ndarray:
        """Fetch-and-reset the router census the decode programs
        accumulate (arena 'moe_census' [L, E+1]; see _moe_inference) —
        ONE explicit d2h per drain, ledgered like every other fetch."""
        census = self.arena.get("moe_census")
        if census is None:
            raise RuntimeError(
                "no census rider in the arena — enable_expert_paging "
                "first")
        with span("engine.fetch", program="moe_census", bytes=census.nbytes):
            out = np.asarray(jax.device_get(census))  # dstpu: noqa[DST001] intended: the census drain IS the explicit periodic fetch (one [L, E+1] int32 buffer per drain interval)
        self.profile["d2h_fetches"] += 1
        self.arena["moe_census"] = jnp.zeros_like(census)
        return out

    def decode_burst_step(self, uids: Optional[Sequence[int]] = None,
                          n_steps: Optional[int] = None,
                          mode: str = "greedy", temperature=1.0,
                          top_k=0, rng=None,
                          max_tokens: Optional[Dict[int, int]] = None,
                          drafts: Optional[Dict[int, Sequence[int]]] = None,
                          draft_span: Optional[int] = None,
                          seeds: Optional[Dict[int, int]] = None,
                          seed_positions: Optional[Dict[int, int]] = None,
                          fsm=None,
                          fsm_states: Optional[Dict[int, int]] = None,
                          fsm_eos: Optional[Dict[int, int]] = None
                          ) -> Dict[int, np.ndarray]:
        """Advance decode-ready sequences `n_steps` tokens in ONE compiled
        program (ragged_ops.decode_tokens): sample -> append KV -> feed
        back, all on device.  Each selected sequence must hold exactly one
        pending input token (the state after prefill + a host-sampled
        first token, or after a previous burst).  Returns
        {uid: [n_steps] int32 sampled tokens}; the last returned token is
        left pending so bursts chain.

        mode="per_row" serves a heterogeneous batch in one program:
        `temperature` and `top_k` are then {uid: value} dicts (missing
        uids sample greedily — temperature 0).  `max_tokens`
        ({uid: absolute token cap}) tightens each row's KV-lease bound
        below the engine-wide `max_tokens_per_seq` — the serving layer
        passes prompt+max_new_tokens so a full-size tail burst can never
        lease blocks past what admission reserved for the request.

        `drafts` switches the call to DRAFT-AND-VERIFY (speculative
        decoding, ragged_ops.verify_tokens): {uid: proposed continuation
        tokens} — one span forward verifies each row's pending token
        plus its draft with on-device accept/reject, instead of
        `n_steps` sequential decode iterations.  The return type changes
        to {uid: (emitted_tokens [n] int32, n_drafted, n_accepted)}
        where n = n_accepted + 1 (accepted prefix + one replacement or
        bonus token); the last emitted token is left pending so
        dispatches chain exactly like bursts.  `draft_span` fixes the
        compiled span width (1 + max draft, bucketed by the caller to a
        power of two) so heterogeneous per-row draft lengths share ONE
        program; it must be given with `drafts`.  Greedy rows emit the
        bit-identical sequential chain; mode="sample"/"per_row" rows use
        rejection sampling (distribution-exact, stream-divergent).  The
        draft source is the caller's: prompt-lookup today, a draft model
        sharing this arena later — the verify interface is the same.

        `seeds` ({uid: stream seed}) + `seed_positions` ({uid: index of
        the row's FIRST token of this burst in its generated stream})
        switch the flagged rows to their counter-based Philox streams:
        token j of the burst is drawn from seeded_sample(seed,
        position + j) ON DEVICE (ragged_ops._sample_per_row), replay-
        deterministic across failover and independent of the engine
        RNG.  Unflagged rows are untouched; greedy rows never consume a
        stream.  Requires a stochastic mode ("sample" rides the per-row
        program so the seed flags get a row axis)."""
        if self.family.row_slots:
            self.family.refuse("burst decode (decode_burst_step, "
                               "generate) and its draft-verify path")
        if seeds and drafts is not None:
            raise RuntimeError(
                "draft-and-verify cannot serve seeded sampling streams: "
                "rejection sampling consumes a DATA-dependent number of "
                "uniforms per emitted token, so the (seed, position) "
                "stream contract — one draw per generated index — "
                "cannot hold; serve seeded requests through plain "
                "bursts or multi-step groups")
        if fsm is not None and drafts is None:
            raise RuntimeError(
                "fsm= on decode_burst_step serves only the "
                "draft-and-verify path (the sequential burst has no "
                "in-scan state carry) — constrained non-speculative "
                "groups go through decode_multi_step")
        if drafts is not None:
            if self._lora is not None and any(
                    self._adapter_slots.get(u, -1) >= 0 for u in drafts):
                raise RuntimeError(
                    "draft-and-verify does not serve LoRA adapter rows: "
                    "the verify program has no gather-LoRA epilogue, so "
                    "accepting drafts against base-model logits would "
                    "silently decode the wrong model — serve adapter "
                    "requests through plain bursts (the serving layer "
                    "refuses the speculative+tenancy combination at "
                    "config validation)")
            return self._verify_draft_step(
                uids, mode=mode, temperature=temperature, top_k=top_k,
                rng=rng, max_tokens=max_tokens, drafts=drafts,
                draft_span=draft_span, fsm=fsm, fsm_states=fsm_states,
                fsm_eos=fsm_eos)
        with span("engine.plan") as plan:
            n_steps = n_steps or self.config.decode_burst
            batch = [d for d in self.state.decode_batch() if d.generated
                     and d.seen_tokens < len(d.prompt) + len(d.generated)]
            if uids is not None:
                sel = set(uids)
                batch = [d for d in batch if d.uid in sel]
            if not batch:
                return {}
            B = self.config.max_seqs
            tokens = np.zeros(B, np.int32)
            lens = np.zeros(B, np.int32)
            max_lens = np.ones(B, np.int32)
            tables = np.zeros((B,) + self.state.table_shape, np.int32)
            active = np.zeros(B, bool)
            for i, d in enumerate(batch):
                pending = d.seen_tokens - len(d.prompt)
                if pending != len(d.generated) - 1:
                    raise RuntimeError(
                        f"sequence {d.uid} has {len(d.generated) - pending} "
                        f"pending tokens; burst decode needs exactly 1 (drive "
                        f"step() to drain extras first)")
                tokens[i] = d.generated[pending]
                lens[i] = d.seen_tokens
                # cap the lease at the sequence's KV budget: a tail burst that
                # overshoots must not demand blocks past the lease (or any
                # blocks the overshoot alone would waste); the compiled
                # program clamps positions to max_lens-1 so overshot steps
                # re-write the last leased slot (their tokens are trimmed)
                capped = min(d.seen_tokens + n_steps, self.max_tokens_per_seq)
                if max_tokens is not None and d.uid in max_tokens:
                    capped = min(capped, int(max_tokens[d.uid]))  # dstpu: noqa[DST001] max_tokens is a host dict of python ints per the method contract
                capped = max(capped, d.seen_tokens)
                max_lens[i] = capped
                self.state.ensure_capacity(d, capped,
                                           first_query=d.seen_tokens)
                tables[i] = self.state.block_table(d)
                active[i] = True
            plan.set_metadata(rows=len(batch))
        with span("engine.dispatch", program="decode_tokens"):
            if rng is None:
                self._rng, rng = jax.random.split(self._rng)
            aids = self._batch_adapter_ids(batch, B)
            lkw = ({} if aids is None else
                   dict(adapter_ids=self._host_in(aids), lora=self._lora))
            if seeds and mode == "greedy":
                raise ValueError(
                    "seeds= with mode='greedy': greedy rows never consume "
                    "their sampling stream — drop the seeds or pick a "
                    "stochastic mode")
            if mode == "per_row" or (seeds and mode == "sample"):
                temp_vec = np.zeros(B, np.float32)
                topk_vec = np.zeros(B, np.int32)
                if mode == "per_row":
                    temperature = dict(temperature or {})
                    top_k = dict(top_k or {})
                    for i, d in enumerate(batch):
                        temp_vec[i] = float(temperature.get(d.uid, 0.0))
                        topk_vec[i] = int(top_k.get(d.uid, 0))
                else:
                    # a uniform stochastic group with seeded rows rides the
                    # per-row program: the seed flags need a row axis
                    temp_vec[:len(batch)] = float(temperature)  # dstpu: noqa[DST001] scalar-mode temperature is a host python/np scalar per the method contract
                    topk_vec[:len(batch)] = int(top_k)  # dstpu: noqa[DST001] scalar-mode top_k is a host python int per the method contract
                skw = {}
                if seeds:
                    skw = self._seed_operands(batch, B, seeds, seed_positions)
                toks, self.arena = self._programs.decode_tokens(
                    self.params, self.arena, self._host_in(tokens),
                    self._host_in(lens), self._host_in(tables),
                    self._host_in(active), rng, self._host_in(temp_vec),
                    self._host_in(max_lens), self._host_in(topk_vec),
                    n_steps=n_steps, mode="per_row", top_k=0, **skw, **lkw)
            else:
                # stage the sampling scalar explicitly as a 0-d ndarray: a
                # python/np scalar would ride into the compiled program as an
                # IMPLICIT host->device transfer every burst, which the
                # transfer-guard sanitizer (analysis/transfer_guard.py)
                # rightly rejects
                temp_in = self._host_in(np.asarray(temperature, np.float32))  # dstpu: noqa[DST001] host scalar staged as 0-d array so the h2d transfer is explicit
                toks, self.arena = self._programs.decode_tokens(
                    self.params, self.arena, self._host_in(tokens),
                    self._host_in(lens), self._host_in(tables),
                    self._host_in(active), rng, temp_in,
                    self._host_in(max_lens), n_steps=n_steps, mode=mode,
                    top_k=top_k, **lkw)
        with span("engine.fetch", program="decode_tokens",
                  bytes=toks.nbytes):
            toks = jax.device_get(toks)  # dstpu: noqa[DST001] intended: THE once-per-burst fetch — n_steps sampled tokens per sequence, the only device->host traffic of burst decode
        self.profile["d2h_fetches"] += 1
        out: Dict[int, np.ndarray] = {}
        for i, d in enumerate(batch):
            real = max(0, int(max_lens[i]) - int(lens[i]))
            d.generated.extend(int(t) for t in toks[i][:real])
            d.seen_tokens = min(d.seen_tokens + n_steps, int(max_lens[i]))
            out[d.uid] = toks[i]
            # burst path produces tokens, not logits — drop stale logits
            self._last_logits.discard(d.uid)
        return out

    def _seed_operands(self, batch, B: int,
                       seeds: Optional[Dict[int, int]],
                       seed_positions: Optional[Dict[int, int]]) -> Dict:
        """Stage the per-row counter-based stream operands: the 64-bit
        seed split into uint32 words (device x64 stays disabled), the
        stream index of the row's first drawn token, and the
        participation flag.  Empty seeds -> all-False flags (the
        multi-step program takes the operands unconditionally)."""
        seeds = dict(seeds or {})
        if seeds and seed_positions is None:
            raise ValueError(
                "seeds= needs seed_positions= (the stream index of "
                "each row's first drawn token)")
        seed_positions = dict(seed_positions or {})
        sh = np.zeros(B, np.uint32)
        sl = np.zeros(B, np.uint32)
        sp = np.zeros(B, np.int32)
        hs = np.zeros(B, bool)
        for i, d in enumerate(batch):
            if d.uid in seeds:
                s = int(seeds[d.uid]) & 0xFFFFFFFFFFFFFFFF
                sh[i], sl[i] = s >> 32, s & 0xFFFFFFFF
                sp[i] = int(seed_positions[d.uid])
                hs[i] = True
        return dict(seed_hi=self._host_in(sh), seed_lo=self._host_in(sl),
                    seed_pos=self._host_in(sp),
                    has_seed=self._host_in(hs))

    def decode_multi_step(self, uids: Optional[Sequence[int]] = None,
                          k: int = 8, temperature=None, top_k=None,
                          rng=None,
                          max_tokens: Optional[Dict[int, int]] = None,
                          eos_ids: Optional[Dict[int, int]] = None,
                          seeds: Optional[Dict[int, int]] = None,
                          seed_positions: Optional[Dict[int, int]] = None,
                          fsm=None,
                          fsm_states: Optional[Dict[int, int]] = None
                          ) -> Dict[int, np.ndarray]:
        """Advance decode-ready sequences up to `k` tokens in ONE
        compiled dispatch with ON-DEVICE sampling AND termination
        (ragged_ops.decode_multi_step): a row stops the moment it
        samples its EOS token or exhausts its new-token budget — it
        pins its length and stops writing KV — and the host sees ONE
        packed [B, k+1] fetch per group (k pad-masked tokens plus the
        per-row emitted count), not one transfer per token.

        Sampling is always per-row: `temperature`/`top_k` are
        {uid: value} dicts (missing uids sample greedily);
        `seeds`/`seed_positions` exactly as `decode_burst_step`.
        `max_tokens` ({uid: absolute token cap}) bounds both the row's
        KV lease and its on-device budget; `eos_ids` ({uid: token id})
        arms per-row EOS termination (missing = never).  KV leases are
        reserved for the full k upfront (one compiled shape); a row
        that terminates mid-group carries its residue only to the
        group boundary — the serve loop finishes EOS/budget-stopped
        requests right after the fetch, and that flush frees the whole
        lease (the refund).

        `fsm` (a serving.structured.TokenAutomaton) + `fsm_states`
        ({uid: current automaton state id}) constrain the flagged rows
        to the grammar ON DEVICE: the automaton's cached device tables
        ride the dispatch, each step masks the per-row sampler by one
        state-indexed gather and advances the state inside the scan —
        same packed fetch, zero added device->host traffic (the serve
        loop re-derives states by host-walking the emitted tokens).
        One automaton per dispatch; rows absent from `fsm_states` run
        unconstrained (all-True mask, bit-identical to fsm=None).
        Constrained rows should carry `eos_ids` — accept states admit
        the row's EOS, which is how a constrained row terminates.

        Returns {uid: [n_e] int32} — exactly the tokens the row
        emitted, EOS included, nothing past termination; the last
        emitted token stays pending so groups chain like bursts."""
        if self.family.row_slots:
            self.family.refuse("multi-step decode groups")
        if k < 1:
            raise ValueError(f"decode_multi_step needs k >= 1, got {k}")
        if not self.supports_multi_step:
            raise RuntimeError(
                "decode_multi_step is not served by the fused-TP "
                "program set (tp_ragged.TPServingPrograms has no "
                "multi-step program) — use tp_collectives='xla' for "
                "multi-step serving")
        with span("engine.plan") as plan:
            batch = [d for d in self.state.decode_batch() if d.generated
                     and d.seen_tokens < len(d.prompt) + len(d.generated)]
            if uids is not None:
                sel = set(uids)
                batch = [d for d in batch if d.uid in sel]
            if not batch:
                return {}
            temperature = dict(temperature or {})
            top_k = dict(top_k or {})
            eos_ids = dict(eos_ids or {})
            max_tokens = dict(max_tokens or {})
            B = self.config.max_seqs
            tokens = np.zeros(B, np.int32)
            lens = np.zeros(B, np.int32)
            max_lens = np.ones(B, np.int32)
            budget = np.zeros(B, np.int32)
            eos_vec = np.full(B, -1, np.int32)
            temp_vec = np.zeros(B, np.float32)
            topk_vec = np.zeros(B, np.int32)
            tables = np.zeros((B,) + self.state.table_shape, np.int32)
            active = np.zeros(B, bool)
            for i, d in enumerate(batch):
                pending = d.seen_tokens - len(d.prompt)
                if pending != len(d.generated) - 1:
                    raise RuntimeError(
                        f"sequence {d.uid} has {len(d.generated) - pending} "
                        f"pending tokens; multi-step decode needs exactly 1 "
                        f"(drive step() to drain extras first)")
                tokens[i] = d.generated[pending]
                lens[i] = d.seen_tokens
                # full-k lease upfront, bounded by the row's token cap —
                # identical discipline to decode_burst_step, except the
                # budget ALSO terminates the row on device, so the program
                # never even re-writes the last leased slot
                capped = min(d.seen_tokens + k, self.max_tokens_per_seq)
                capped = min(capped, int(max_tokens.get(d.uid, capped)))
                capped = max(capped, d.seen_tokens)
                max_lens[i] = capped
                budget[i] = capped - d.seen_tokens
                self.state.ensure_capacity(d, capped,
                                           first_query=d.seen_tokens)
                tables[i] = self.state.block_table(d)
                active[i] = budget[i] > 0
                eos_vec[i] = int(eos_ids.get(d.uid, -1))
                temp_vec[i] = float(temperature.get(d.uid, 0.0))
                topk_vec[i] = int(top_k.get(d.uid, 0))
            plan.set_metadata(rows=len(batch))
        with span("engine.dispatch", program="decode_multi_step"):
            if rng is None:
                self._rng, rng = jax.random.split(self._rng)
            aids = self._batch_adapter_ids(batch, B)
            lkw = ({} if aids is None else
                   dict(adapter_ids=self._host_in(aids), lora=self._lora))
            skw = self._seed_operands(batch, B, seeds, seed_positions)
            fkw = {}
            if fsm is not None:
                fsm_states = dict(fsm_states or {})
                st = np.zeros(B, np.int32)
                hf = np.zeros(B, bool)
                for i, d in enumerate(batch):
                    if d.uid in fsm_states:
                        st[i] = int(fsm_states[d.uid])
                        hf[i] = True
                dt = fsm.device_tables()
                fkw = dict(fsm_trans=dt["trans"], fsm_mask=dt["mask"],
                           fsm_accept=dt["accept"],
                           fsm_state=self._host_in(st),
                           has_fsm=self._host_in(hf))
            packed, self.arena = self._programs.decode_multi_step(
                self.params, self.arena, self._host_in(tokens),
                self._host_in(lens), self._host_in(tables),
                self._host_in(active), rng, self._host_in(temp_vec),
                self._host_in(max_lens), self._host_in(topk_vec),
                self._host_in(eos_vec), self._host_in(budget),
                skw["seed_hi"], skw["seed_lo"], skw["seed_pos"],
                skw["has_seed"], k=k, **fkw, **lkw)
        with span("engine.fetch", program="decode_multi_step",
                  bytes=packed.nbytes):
            packed = jax.device_get(packed)  # dstpu: noqa[DST001] intended: THE once-per-group fetch — k pad-masked tokens + per-row emitted counts, the only device->host traffic of a step group
        self.profile["d2h_fetches"] += 1
        out: Dict[int, np.ndarray] = {}
        for i, d in enumerate(batch):
            n_e = int(packed[i, k])
            toks = np.asarray(packed[i, :n_e], np.int32)
            d.generated.extend(int(t) for t in toks)
            d.seen_tokens += n_e
            out[d.uid] = toks
            # multi-step produces tokens, not logits — drop stale logits
            self._last_logits.discard(d.uid)
        return out

    def _verify_draft_step(self, uids: Optional[Sequence[int]], *,
                           mode: str, temperature, top_k, rng,
                           max_tokens: Optional[Dict[int, int]],
                           drafts: Dict[int, Sequence[int]],
                           draft_span: Optional[int],
                           fsm=None,
                           fsm_states: Optional[Dict[int, int]] = None,
                           fsm_eos: Optional[Dict[int, int]] = None
                           ) -> Dict[int, tuple]:
        """Speculative dispatch body (decode_burst_step drafts= path):
        stage each row's [pending, draft...] span, run the compiled
        verify program, adopt the accepted tokens.  See
        decode_burst_step's docstring for the contract.

        `fsm`/`fsm_states`/`fsm_eos` constrain flagged rows to the
        grammar (serving/structured): the host walks each row's draft
        from its current automaton state to the per-position
        `span_states` operand — it can, because the host proposed the
        draft — and the verify program masks its logits once at entry,
        so the greedy target, the acceptance test, and the
        residual/bonus draw are all grammar-confined.  Callers
        pre-filter drafts (serving/speculative.filter_draft), so every
        staged draft token is allowed at its position."""
        if draft_span is None or draft_span < 1:
            raise ValueError(
                "drafts= needs draft_span >= 1 (the bucketed compiled "
                "span width, 1 + max draft length)")
        if self._expert_pool is not None:
            raise RuntimeError(
                "speculative verify with expert paging enabled is "
                "refused: a rejected draft rolls KV back, but the census "
                "the verify span accumulated (and any reroutes a demoted "
                "expert caused inside the speculated span) cannot be "
                "rolled back with it — serve MoE speculation unpaged")
        with span("engine.plan") as plan:
            batch = [d for d in self.state.decode_batch() if d.generated
                     and d.seen_tokens < len(d.prompt) + len(d.generated)]
            if uids is not None:
                sel = set(uids)
                batch = [d for d in batch if d.uid in sel]
            if not batch:
                return {}
            B = self.config.max_seqs
            S = int(draft_span)
            fsm_states = dict(fsm_states or {})
            fsm_eos = dict(fsm_eos or {})
            tokens = np.zeros((B, S), np.int32)
            lens = np.zeros(B, np.int32)
            nval = np.ones(B, np.int32)
            max_lens = np.ones(B, np.int32)
            span_sts = np.zeros((B, S), np.int32)
            hfv = np.zeros(B, bool)
            eosv = np.full(B, -1, np.int32)
            tables = np.zeros((B,) + self.state.table_shape, np.int32)
            active = np.zeros(B, bool)
            for i, d in enumerate(batch):
                pending = d.seen_tokens - len(d.prompt)
                if pending != len(d.generated) - 1:
                    raise RuntimeError(
                        f"sequence {d.uid} has {len(d.generated) - pending} "
                        f"pending tokens; draft verify needs exactly 1 (drive "
                        f"step() to drain extras first)")
                tokens[i, 0] = d.generated[pending]
                dr = np.asarray(drafts.get(d.uid, ()),  # dstpu: noqa[DST001] drafts are host token arrays per the method contract
                                np.int32).ravel()[:S - 1]
                tokens[i, 1:1 + len(dr)] = dr
                nval[i] = 1 + len(dr)
                lens[i] = d.seen_tokens
                if fsm is not None and d.uid in fsm_states:
                    hfv[i] = True
                    eosv[i] = int(fsm_eos.get(d.uid, -1))
                    # state BEFORE each span position: walk the draft from
                    # the row's current state (same clamp as the device
                    # scan and TokenAutomaton.walk); the tail past the
                    # draft pins, masking the bonus position correctly
                    stw = int(fsm_states[d.uid])
                    for j in range(S):
                        span_sts[i, j] = stw
                        if j < len(dr):
                            nt = int(fsm.trans[stw, int(dr[j])])  # dstpu: noqa[DST001] automaton tables + drafts are host numpy (TokenAutomaton contract) — no device sync
                            if nt >= 0:
                                stw = nt
                # lease cap exactly as the sequential burst: span positions
                # clamp to max_lens-1 in the program, overshot tokens are
                # trimmed below, and capacity never exceeds what admission
                # reserved
                capped = min(d.seen_tokens + S, self.max_tokens_per_seq)
                if max_tokens is not None and d.uid in max_tokens:
                    capped = min(capped, int(max_tokens[d.uid]))  # dstpu: noqa[DST001] max_tokens is a host dict of python ints per the method contract
                capped = max(capped, d.seen_tokens)
                max_lens[i] = capped
                self.state.ensure_capacity(d, capped,
                                           first_query=d.seen_tokens)
                tables[i] = self.state.block_table(d)
                active[i] = True
            plan.set_metadata(rows=len(batch))
        with span("engine.dispatch", program="verify_tokens"):
            if rng is None:
                self._rng, rng = jax.random.split(self._rng)
            fkw = {}
            if fsm is not None:
                dt = fsm.device_tables()
                fkw = dict(fsm_mask=dt["mask"], fsm_accept=dt["accept"],
                           span_states=self._host_in(span_sts),
                           has_fsm=self._host_in(hfv),
                           fsm_eos=self._host_in(eosv))
            if mode == "greedy":
                emitted, n_emitted, self.arena = self._programs.verify_tokens(
                    self.params, self.arena, self._host_in(tokens),
                    self._host_in(lens), self._host_in(nval),
                    self._host_in(tables), self._host_in(active), rng,
                    self._greedy_temp, self._host_in(max_lens),
                    mode="greedy", **fkw)
            else:
                # heterogeneous rows ("per_row" dicts) and uniform stochastic
                # rows ("sample" scalars) share the per-row verify program —
                # unlike the sequential burst there is no scalar "sample"
                # variant to save a compile on: verification is one program
                # per span width either way
                temp_vec = np.zeros(B, np.float32)
                topk_vec = np.zeros(B, np.int32)
                if mode == "per_row":
                    temperature = dict(temperature or {})
                    top_k = dict(top_k or {})
                    for i, d in enumerate(batch):
                        temp_vec[i] = float(temperature.get(d.uid, 0.0))
                        topk_vec[i] = int(top_k.get(d.uid, 0))
                elif mode == "sample":
                    temp_vec[:len(batch)] = float(temperature)
                    topk_vec[:len(batch)] = int(top_k)
                else:
                    raise ValueError(
                        f"unknown sampling mode {mode!r} "
                        f"(greedy | sample | per_row)")
                emitted, n_emitted, self.arena = self._programs.verify_tokens(
                    self.params, self.arena, self._host_in(tokens),
                    self._host_in(lens), self._host_in(nval),
                    self._host_in(tables), self._host_in(active), rng,
                    self._host_in(temp_vec), self._host_in(max_lens),
                    self._host_in(topk_vec), mode="per_row", **fkw)
        with span("engine.fetch", program="verify_tokens",
                  bytes=emitted.nbytes + n_emitted.nbytes):
            emitted, n_emitted = jax.device_get((emitted, n_emitted))  # dstpu: noqa[DST001] intended: THE once-per-dispatch fetch — emitted tokens + counts, the only device->host traffic of draft verify
        self.profile["d2h_fetches"] += 1
        out: Dict[int, tuple] = {}
        for i, d in enumerate(batch):
            n = int(n_emitted[i])
            real = max(0, int(max_lens[i]) - int(lens[i]))
            take = min(n, real)
            toks = np.asarray(emitted[i][:take], np.int32)  # dstpu: noqa[DST001] emitted was fetched by the explicit device_get above; this slices a host array
            d.generated.extend(int(t) for t in toks)
            d.seen_tokens = min(d.seen_tokens + n, int(max_lens[i]))
            # verify path produces tokens, not logits — drop stale logits
            self._last_logits.discard(d.uid)
            out[d.uid] = (toks, int(nval[i]) - 1, max(take - 1, 0))
        return out

    def sample_tokens_batch(self, logits_rows, mode: str = "greedy",
                            temperature=1.0, top_k=0) -> np.ndarray:
        """Sample one token per row of `logits_rows` [N, V] in ONE device
        call (the generate_batch first-token pattern — per-row host
        sampling would pay one host dispatch each).  Scalar
        temperature/top_k with mode "greedy"/"sample", or per-row vectors
        (length N) with mode="per_row" (rows with temperature <= 0 take
        the argmax).  Returns [N] int32 on host."""
        from .ragged_ops import sample_tokens_compiled
        with span("engine.dispatch", program="sample_tokens"):
            self._rng, key = jax.random.split(self._rng)
            stacked = jnp.asarray(np.asarray(logits_rows))  # dstpu: noqa[DST001] rows are host np logits the engine already fetched; this is h2d staging, not a sync
            if mode == "per_row":
                temperature = jnp.asarray(np.asarray(temperature, np.float32))  # dstpu: noqa[DST001] caller-provided host vector; explicit h2d staging
                topk_vec = jnp.asarray(np.asarray(top_k, np.int32))  # dstpu: noqa[DST001] caller-provided host vector; explicit h2d staging
                toks = sample_tokens_compiled(stacked, key, temperature,
                                              topk_vec, mode="per_row")
            else:
                # 0-d ndarray staging, not a bare np scalar: scalar avals
                # transfer implicitly, which the transfer guard rejects
                temperature = jnp.asarray(np.asarray(temperature, np.float32))  # dstpu: noqa[DST001] host scalar staged as 0-d array so the h2d transfer is explicit
                toks = sample_tokens_compiled(stacked, key, temperature,
                                              mode=mode, top_k=int(top_k))
        with span("engine.fetch", program="sample_tokens", bytes=toks.nbytes):
            toks = jax.device_get(toks)  # dstpu: noqa[DST001] intended: one [N]-token fetch per batched first-token sample
        self.profile["d2h_fetches"] += 1
        return toks

    # -- lifecycle -------------------------------------------------------
    def flush(self, uid: int) -> None:
        # insert-on-completion BEFORE the flush decrefs the sequence's
        # blocks: the cache increfs the newly cached prompt blocks while
        # the sequence still owns them, so ownership hands over without
        # the blocks ever touching the free list.  Only fully WRITTEN
        # whole prompt blocks qualify (a cancelled mid-prefill sequence
        # caches just the prefix it completed).
        d = self.state.seqs.get(uid)
        if d is not None and self.prefix_cache is not None:
            self.prefix_cache.insert(
                d.prompt, d.blocks,
                upto_tokens=min(d.seen_tokens, len(d.prompt)))
        lease = self._prefix_leases.pop(uid, None)
        self.state.flush(uid)
        if lease is not None:
            self.prefix_cache.release(lease)
        self._last_logits.discard(uid)
        self._adapter_slots.pop(uid, None)

    def query(self, uid: int) -> Optional[np.ndarray]:
        """`uid`'s latest last-token logits row on the host (fetched
        when first read), None until its prompt is complete."""
        return self._last_logits.get(uid)

    @property
    def free_blocks(self):
        """Free blocks: an int, or one count a kind of a two-kind cache
        (`blocked_allocator.KindCounts`, in `kind_names`' order)."""
        return self.state.free_blocks

    # what the serving layer books a request's blocks with: ints, or one
    # count a kind (`DSStateManager.blocks_needed` / `blocks_leased`)
    @property
    def kind_names(self):
        from .ragged_manager import KIND_NAMES
        return KIND_NAMES if self.state.window else None

    def blocks_needed(self, tokens: int):
        return self.state.blocks_needed(tokens)

    def blocks_leased(self, d):
        return self.state.blocks_leased(d)

    @property
    def free_slots(self) -> int:
        """Ragged-batch slots not held by a live sequence — the serving
        layer's admission headroom (deepspeed_tpu.serving)."""
        return self.config.max_seqs - len(self.state.seqs)

    # -- convenience: generation driving prefill + burst decode ----------
    def generate(self, prompt_tokens, max_new_tokens: int = 16,
                 uid: int = 0, mode: str = "greedy",
                 temperature: float = 1.0, top_k: int = 0,
                 eos_token_id: Optional[int] = None) -> np.ndarray:
        """Generate up to max_new_tokens (stops early at eos_token_id).
        Prefill runs through put()/step(); decode runs in compiled bursts
        of `config.decode_burst` tokens with on-device sampling."""
        out = self.generate_batch([np.asarray(prompt_tokens, np.int32)],  # dstpu: noqa[DST001] caller-provided prompt is a host array per contract
                                  max_new_tokens=max_new_tokens,
                                  mode=mode, temperature=temperature,
                                  top_k=top_k, eos_token_id=eos_token_id,
                                  first_uid=uid)
        return out[0]

    def generate_batch(self, prompts: Sequence[np.ndarray],
                       max_new_tokens: int = 16, mode: str = "greedy",
                       temperature: float = 1.0, top_k: int = 0,
                       eos_token_id: Optional[int] = None,
                       first_uid: int = 0) -> List[np.ndarray]:
        """Batched generation: admit prompts in waves of max_seqs, prefill
        via the chunked program, then burst-decode every live sequence in
        lockstep — one compiled call per `decode_burst` tokens for the
        whole wave.  Sequences that hit EOS drop out of later bursts."""
        results: List[np.ndarray] = [None] * len(prompts)
        W = self.config.max_seqs
        burst = max(1, self.config.decode_burst)
        for w0 in range(0, len(prompts), W):
            wave = list(range(w0, min(w0 + W, len(prompts))))
            uids = {i: first_uid + i for i in wave}
            self.put([uids[i] for i in wave],
                     [np.asarray(prompts[i], np.int32) for i in wave])  # dstpu: noqa[DST001] caller-provided prompts are host arrays per contract
            while any(self.query(uids[i]) is None for i in wave):
                self.step()
            # sample every first token in ONE device call (per-request
            # host sampling cost one host dispatch each)
            firsts = self.sample_tokens_batch(
                np.stack([self.query(uids[i]) for i in wave]),
                mode=mode, temperature=temperature, top_k=top_k)
            toks: Dict[int, List[int]] = {}
            live: List[int] = []
            for i, first in zip(wave, (int(t) for t in firsts)):
                toks[i] = [first]
                if not (eos_token_id is not None and first == eos_token_id
                        ) and max_new_tokens > 1:
                    # stage as the pending input of the first burst
                    self.state.seqs[uids[i]].generated.append(first)
                    live.append(i)
            while live:
                # ALWAYS decode a full burst: n_steps is a static arg of
                # the compiled program, so a tail-sized burst would compile
                # a fresh program per distinct remainder (a multi-second
                # compile inside a serving loop).  Overshoot
                # past max_new_tokens is trimmed on host; the stale KV the
                # extra steps wrote dies with the flush below.
                got = self.decode_burst_step(
                    uids=[uids[i] for i in live], n_steps=burst, mode=mode,
                    temperature=temperature, top_k=top_k)
                nxt_live = []
                for i in live:
                    new = got[uids[i]]
                    done = False
                    for t in new:
                        toks[i].append(int(t))
                        if ((eos_token_id is not None
                             and int(t) == eos_token_id)
                                or len(toks[i]) >= max_new_tokens):
                            done = True
                            break
                    if not done:
                        nxt_live.append(i)
                live = nxt_live
            for i in wave:
                results[i] = np.asarray(toks[i], np.int32)
                self.flush(uids[i])
        return results
