"""The serve loop: a synchronous continuous-batching core plus a thin
threaded frontend.

Reference: DeepSpeed-MII's async serving layer (mii/batching) flattened
into an explicitly-driveable core: `ServeLoop.step()` advances admission
-> one ragged engine step -> sampling -> completion bookkeeping, with no
hidden threads or sleeps, so tests drive it deterministically on CPU
with a fake clock.  `ThreadedServer` wraps the same core behind
`submit()/cancel()/result()` for callers that want a background loop.

Two hot paths, selected by `ServingConfig.decode_burst`:

- **decode_burst == 1** (the default): one ServeLoop step == one engine
  step == one token per decoding request, with admission every step.
  The step's programs return each row's argmax beside its logits and
  the engine fetches only those [max_seqs] int32; a request with
  temperature <= 0 and no response_format takes that token (it IS the
  host sampler's result for such a row), and only the other rows —
  stochastic, seeded, top-k, grammar-masked — have their own logits
  row fetched and sampled on HOST (`_sample`), as every row of an
  engine whose step returns plain host rows is (test fakes).
- **decode_burst > 1** (burst serving): decode rides the engine's fused
  `decode_burst_step` — sample -> append-KV -> feed-back run as ONE
  compiled program per `decode_burst` tokens and logits never leave the
  device; the host loop runs once per BURST.  Prefill still advances one
  engine step per serve step (`put(..., decode=False)` keeps the host-
  logits decode path out of it) and FIRST tokens are still sampled from
  the prefill logits by the engine's batched sampler, so TTFT semantics
  are unchanged.  Requests with heterogeneous sampling parameters share
  one burst via per-row temperature/top_k vectors
  (`ragged_ops._sample_tokens` mode="per_row"); engines without that
  capability fall back to one burst per (temperature, top_k) signature
  group.  Mid-burst EOS / max_new_tokens are truncated on host, the
  flush releases the over-generated KV, and the reservation ledger is
  debited for the truncated request so admission capacity never leaks.
  Cancellations and deadlines are checked at burst boundaries — the
  burst size is a throughput vs. responsiveness knob, not a correctness
  one.

Every completion/cancel/timeout flushes the engine sequence so KV blocks
return to the arena, on both paths.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from ..config.config import ServingConfig
from ..inference.v2.blocked_allocator import KindCounts
from ..utils.logging import logger
from ..utils.spans import span
from .request import Request, RequestState
from .scheduler import (AdmissionError, ContinuousBatchingScheduler)
from .telemetry import ServingTelemetry

__all__ = ["ServeLoop", "ThreadedServer"]


# Block counts are ints, or one count a kind where the engine's cache holds
# two kinds of block (`inference/v2/blocked_allocator.KindCounts`): both add
# and subtract, and these two are every comparison the ledger makes
def _short(need, have) -> bool:
    """`have` blocks do not cover `need` (two kinds: of ANY kind)."""
    return need.short_of(have) if isinstance(need, KindCounts) \
        else need > have


def _floor0(blocks):
    """max(blocks, 0), kind by kind."""
    return blocks.floor0() if isinstance(blocks, KindCounts) \
        else max(blocks, 0)


class _StepPhases:
    """The phases of one serve step follow one another on one thread, so
    each boundary is written once, as one `enter(name)`: it ends the open
    phase's profiler span and begins the next (always; utils/spans.py),
    and with the step timeline on (`clock` given) it reads the serve
    clock there too and adds the seconds to the phase that ended
    (`spent[name]`: a step that collects one engine step and dispatches
    the next enters `serve.engine` and `serve.sample` twice), which is
    all `StepTimeline` is fed from.  `now` hands over a read the step
    makes anyway, so with the timeline off the serve clock is touched
    exactly as without this.
    Leaving the `with` ends the open phase, also when the step raises."""

    __slots__ = ("_clock", "_open", "_name", "_since", "spent")

    def __init__(self, clock: Optional[Callable[[], float]]):
        self._clock = clock
        self._open = None
        self._name, self._since = None, 0.0
        self.spent: Dict[str, float] = {}

    def enter(self, name: str, now: Optional[float] = None, **attrs):
        if self._open is not None:
            self._open.__exit__(None, None, None)
        if self._clock is not None:
            now = self._clock() if now is None else now
            if self._name is not None:
                self.spent[self._name] = (self.spent.get(self._name, 0.0)
                                          + now - self._since)
            self._name, self._since = name, now
        self._open = span(name, **attrs)
        self._open.__enter__()
        return self._open

    def __enter__(self) -> "_StepPhases":
        return self

    def __exit__(self, *exc) -> bool:
        if self._open is not None:
            self._open.__exit__(*exc)
            self._open = None
        return False


def _refuse_recurrent_state(config: ServingConfig, family) -> None:
    """What assumes "a sequence's state is its K/V blocks" cannot serve an
    engine that keeps per-sequence recurrent state beside them
    (`engine.recurrent_state`): refused with the reason of the engine's
    `family`.  The prefix cache and its host tier, page export and import,
    LoRA and tensor parallelism are refused by the engine where they are
    switched on."""
    on = lambda c: c is not None and getattr(c, "enabled", True)  # noqa: E731
    asked = {
        "speculative decoding (draft and verify)":
            config.speculative is not None
            and config.speculative.mode != "off",
        "burst decode (decode_burst > 1)": config.decode_burst > 1,
        "multi-step decode groups (multi_step > 1)": config.multi_step > 1,
        "preemption by KV swap or recompute": on(config.preemption),
        "grammar-constrained decoding (it rides the multi-step and "
        "verify programs)": on(config.structured),
        "expert paging": on(config.moe),
    }
    for what, wanted in asked.items():
        if wanted:
            family.refuse(what)


class ServeLoop:
    """Synchronous serving core over an `InferenceEngineV2`-shaped engine.

    The engine contract (satisfied by `InferenceEngineV2` and by test
    fakes): `config.max_seqs`, `max_tokens_per_seq`, `free_slots`,
    `free_blocks`, `state.seqs` (uid -> descriptor with `.seen_tokens/
    .prompt/.generated`), `state.block_size`, `put(uids, prompts) ->
    {uid: logits}`, `step() -> {uid: logits}`, `flush(uid)`.

    Burst mode (`ServingConfig.decode_burst > 1`) extends the contract:
    `put`/`step` take `decode=False` (prefill only), and
    `decode_burst_step(uids, n_steps, mode, temperature, top_k,
    max_tokens) -> {uid: [n_steps] tokens}` runs fused on-device
    sampling.  Optional capabilities: `sample_tokens_batch` (batched
    first-token sampling) and `supports_per_row_sampling` (one burst for
    heterogeneous sampling signatures).

    Prefix reuse (`ServingConfig.prefix_cache_blocks > 0`) requires
    `enable_prefix_cache(n) -> PrefixCache`, `put(..., prefixes=...)`
    accepting admission-time leases, and `audit_blocks()` for the debug
    conservation hook (`audit_blocks=True` runs without the cache too,
    on any engine that has the method).

    KV tiering (`ServingConfig.host_cache_blocks > 0`) additionally
    requires `enable_prefix_cache(n, host_blocks=, host_quant=)` and
    the batched span-IO contract (`read_kv_blocks`/`write_kv_blocks`):
    cache evictions demote cold prefix KV to host memory and admission
    promotes host-resident hits back, with the promoted blocks counted
    against this step's arena headroom (`PrefixLease.promoted`).
    """

    # speculative drafting backoff cadence (see __init__'s _spec_idle)
    _SPEC_BACKOFF_AFTER = 8
    _SPEC_PROBE_EVERY = 4

    def __init__(self, engine, config: Optional[ServingConfig] = None,
                 clock: Optional[Callable[[], float]] = None,
                 monitor=None, rng_seed: int = 0):
        self.engine = engine
        self.config = config or ServingConfig()
        self.config.validate()
        # tensor-parallel serving: the config's TP fields describe the
        # engine this loop expects (engine factories fold them in via
        # model_registry.apply_serving_tp) — a mismatch means the
        # operator asked for TP the engine does not run, which would
        # silently serve single-device; loud here instead.
        tp_cfg = self.config.tensor_parallel_size
        if tp_cfg > 1:
            eng_tp = getattr(engine, "tp", 1)
            if eng_tp != tp_cfg:
                raise ValueError(
                    f"ServingConfig.tensor_parallel_size={tp_cfg} but the "
                    f"engine serves tp={eng_tp}: build the engine from "
                    f"this config (model_registry.apply_serving_tp / "
                    f"build_engine(serving_config=...)) or make them "
                    f"agree")
            eng_coll = getattr(getattr(engine, "config", None),
                               "tp_collectives", "xla")
            # only the silent-degradation direction is an error: the
            # operator asked for fused collectives and the engine runs
            # the xla path.  The reverse (serving keeps the "xla"
            # default, engine configured fused directly) is a stronger
            # engine serving the same contract — apply_serving_tp
            # deliberately lets engine-side values survive the fold.
            if self.config.tp_collectives == "fused" \
                    and eng_coll != "fused":
                raise ValueError(
                    f"ServingConfig.tp_collectives='fused' but the "
                    f"engine runs {eng_coll!r}: build the engine from "
                    f"this config (model_registry.apply_serving_tp) or "
                    f"make them agree")
        if getattr(engine, "recurrent_state", False):
            _refuse_recurrent_state(self.config, engine.family)
        # burst serving needs the extended engine contract: decode_burst_
        # step(uids, n_steps, mode, temperature, top_k, max_tokens) and
        # the decode= kwarg on put()/step().  Loud here, not a silent
        # slow path mid-serve.
        self._burst_n = self.config.decode_burst
        if self._burst_n > 1 and not hasattr(engine, "decode_burst_step"):
            raise ValueError(
                f"ServingConfig.decode_burst={self._burst_n} needs an "
                f"engine with decode_burst_step (on-device burst "
                f"sampling); {type(engine).__name__} has none — use "
                f"decode_burst=1 for the per-step path")
        # multi-step step groups (host-free steady-state decode): K
        # decode steps per compiled dispatch with ON-DEVICE sampling and
        # termination (engine decode_multi_step).  Everything host-side
        # — admission, streaming flush, deadline/cancel, preemption,
        # ledger accounting — moves to group boundaries.  Loud
        # capability check here: an engine without the program (or a
        # fused-TP engine, whose program set lacks it) must not silently
        # serve per-token.
        self._group_k = self.config.multi_step
        if self._group_k > 1:
            if not hasattr(engine, "decode_multi_step") or not getattr(
                    engine, "supports_multi_step", False):
                raise ValueError(
                    f"ServingConfig.multi_step={self._group_k} needs an "
                    f"engine with decode_multi_step (on-device sampling "
                    f"+ termination; xla-TP program set); "
                    f"{type(engine).__name__} does not serve it — use "
                    f"multi_step=1, or tp_collectives='xla' if this is "
                    f"the fused-TP engine")
        # speculative decoding (serving/speculative.py): model-free
        # prompt-lookup drafts verified on device through the engine's
        # decode_burst_step(drafts=...) path.  Engines without the
        # verify capability fail loudly here; config.validate() already
        # guarantees decode_burst > 1 when the mode is on.  Each verify
        # dispatch's span buckets into the fixed shape set
        # {2, 4, ..., span_bucket(1 + max_draft)} (see _decode_bursts).
        self._spec = None
        spec = self.config.speculative
        if spec is not None and spec.mode != "off":
            if not getattr(engine, "supports_draft_verify", False):
                raise ValueError(
                    f"ServingConfig.speculative.mode={spec.mode!r} needs "
                    f"an engine with draft-verify support "
                    f"(decode_burst_step drafts=); "
                    f"{type(engine).__name__} has none — use "
                    f"speculative.mode='off' for the sequential burst "
                    f"path")
            from .speculative import PromptLookupDrafter
            self._spec = PromptLookupDrafter(ngram=spec.ngram,
                                             max_draft=spec.max_draft)
            # the per-dispatch draft cap comes from CONFIG, not from
            # the drafter: any DraftSource (a stage-2 draft model
            # included) only has to implement draft()/observe()
            self._spec_max_draft = spec.max_draft
        # drafting backoff: after _SPEC_BACKOFF_AFTER consecutive
        # decode rounds without ACCEPTED draft tokens (no match, gate
        # failure, or verified-but-all-rejected), only PROBE for drafts
        # every _SPEC_PROBE_EVERY rounds — traffic speculation cannot
        # help then skips the per-row context scans and the 1-token
        # verify dispatches instead of paying them every step; one
        # accepting dispatch resets the cadence
        self._spec_idle = 0
        # grammar-constrained decoding (serving/structured): requests
        # carrying a response_format decode under an on-device token
        # automaton — the mask is one table gather inside the compiled
        # dispatch, states advance in the scan body, so constraint adds
        # ZERO per-step host round-trips.  None = constrained submits
        # refused loudly; unconstrained requests are bit-for-bit the
        # pre-structured loop either way (locked both ways by test).
        self._structured = None
        self._grammar_cache = None
        st_cfg = self.config.structured
        if st_cfg is not None and st_cfg.enabled:
            if not getattr(engine, "supports_structured", False):
                raise ValueError(
                    f"ServingConfig.structured needs an engine serving "
                    f"the constrained decode operands (decode_multi_step "
                    f"fsm= / verify fsm=; xla-TP program set); "
                    f"{type(engine).__name__} does not — drop "
                    f"structured, or tp_collectives='xla' if this is "
                    f"the fused-TP engine")
            from .structured import (AutomatonCache, TokenVocabulary,
                                     byte_vocab)
            vsz = int(engine.cfg.vocab_size)
            if isinstance(st_cfg.vocab, str):
                gvocab = byte_vocab(vsz)
            else:
                if len(st_cfg.vocab) != vsz:
                    raise ValueError(
                        f"ServingConfig.structured.vocab lists "
                        f"{len(st_cfg.vocab)} token strings but the "
                        f"engine's vocabulary is {vsz} — the automaton "
                        f"must cover every token id exactly once")
                gvocab = TokenVocabulary(list(st_cfg.vocab))
            self._grammar_cache = AutomatonCache(
                gvocab, capacity=st_cfg.cache_size,
                max_states=st_cfg.max_states)
            self._structured = st_cfg
        # prefix KV reuse (serving/prefix_cache.py): the loop enables the
        # radix cache ON the engine (lookups happen at admission so the
        # KV ledger and the attached prefix agree); engines without the
        # capability fail loudly here, not silently slower mid-serve
        self._cache = None
        self._tier = None
        if self.config.prefix_cache_blocks > 0:
            if not hasattr(engine, "enable_prefix_cache"):
                raise ValueError(
                    f"ServingConfig.prefix_cache_blocks="
                    f"{self.config.prefix_cache_blocks} needs an engine "
                    f"with enable_prefix_cache (radix prefix KV reuse); "
                    f"{type(engine).__name__} has none — use "
                    f"prefix_cache_blocks=0 for the no-reuse path")
            if self.config.host_cache_blocks > 0:
                # host KV spill tier (serving/kv_tier.py): eviction
                # demotes, hits promote; needs the engine's batched
                # span-IO contract — loud here, never a silent HBM-only
                # downgrade.  Signature-probed rather than try/except
                # TypeError: a genuine TypeError raised INSIDE a capable
                # engine's enable path must surface as itself, not as a
                # misleading capability complaint
                import inspect
                try:
                    params = inspect.signature(
                        engine.enable_prefix_cache).parameters
                    capable = ("host_blocks" in params or any(
                        p.kind is p.VAR_KEYWORD for p in params.values()))
                except (TypeError, ValueError):
                    capable = True       # uninspectable: attempt the call
                if not capable:
                    raise ValueError(
                        f"ServingConfig.host_cache_blocks="
                        f"{self.config.host_cache_blocks} needs an "
                        f"engine whose enable_prefix_cache takes "
                        f"host_blocks/host_quant (the HBM -> host KV "
                        f"spill tier); {type(engine).__name__} does not "
                        f"— use host_cache_blocks=0 for the HBM-only "
                        f"cache")
                self._cache = engine.enable_prefix_cache(
                    self.config.prefix_cache_blocks,
                    host_blocks=self.config.host_cache_blocks,
                    host_quant=self.config.host_cache_quant)
                self._tier = getattr(self._cache, "tier", None)
            else:
                self._cache = engine.enable_prefix_cache(
                    self.config.prefix_cache_blocks)
        self._audit = self.config.audit_blocks
        # dynamic host-sync sanitizer: every step runs under jax's
        # device->host transfer guard at the configured level.  The hot
        # paths fetch explicitly (jax.device_get), so "disallow" makes an
        # accidental implicit materialization raise at the offending call
        # (analysis/transfer_guard.py; the static twin is lint DST001)
        from ..analysis.transfer_guard import serve_guard
        self._guard = serve_guard(self.config.transfer_guard)
        # leases acquired at admission, consumed by the same step's put()
        self._prefix_pending: Dict[int, object] = {}
        # routing hook (serving/fleet): called once per ADMITTED request
        # as admit_hook(request, covered_tokens) with the prefix coverage
        # the request actually got (0 on a miss or with the cache off) —
        # the fleet router's stale-view protocol compares this against
        # what its snapshot of the replica promised
        self.admit_hook: Optional[Callable] = None
        # drain(): stop admitting, finish in-flight (failover handoff)
        self._draining = False
        # pool role (serving/fleet/disagg): "unified" (default — zero
        # behavior change, the parity lock) serves end-to-end;
        # "decode" is routing/telemetry attribution only (same loop);
        # "prefill" runs prompts to completion and PARKS them for the
        # fleet handoff coordinator instead of sampling a first token —
        # see set_role()
        self._role = "unified"
        # prefill-role only: requests whose prompt finished prefilling
        # this replica, awaiting cross-pool handoff (the coordinator
        # drains this via take_handoff_ready every fleet step)
        self._handoff_ready: List[Request] = []
        # step-progress heartbeat (serving/fleet/supervisor.py):
        # `progress` advances once per step that COMPLETED having done
        # REAL work (admission, prefill/decode tokens, or a
        # finalization) — a wedged replica leaves it frozen whether the
        # wedge raises, hangs, or returns instantly while the engine
        # advances nothing, which is exactly what the supervisor's
        # deadline clocks watch.  `step_errors` counts exceptions that
        # escaped step() (the error-burst signal).
        self.progress = 0
        self._step_worked = False
        self.step_errors = 0
        self.last_step_error: Optional[BaseException] = None
        # requests finalized during a step that later RAISED: they are
        # terminal (waiters already resolved) but were never returned to
        # the step() caller — the next successful step (or the fleet
        # router's error handler) reports them, so a mid-step engine
        # failure can never drop a terminal-state notification
        self._finished_backlog: List[Request] = []
        # the per-step path keeps at most one dispatched engine step
        # uncollected (its `engine_v2.LogitsRows`, still `pending`): its
        # programs run on the device while the next call admits and
        # dispatches, and its tokens are fetched a call late
        # (`_step_phases` 3).  The capability probed is the engine's
        # `collect`; an engine without it (the test fakes) collects
        # inside its own `put`/`step`, as ever.  Oldest first; a second
        # entry only after a call raised while it waited for the first
        self._in_flight: List = []
        self._splits_step = hasattr(engine, "collect")
        self.clock = clock or time.monotonic
        self.scheduler = ContinuousBatchingScheduler(
            max_queue_len=self.config.max_queue_len)
        self.telemetry = ServingTelemetry(
            monitor=monitor,
            monitor_interval_steps=self.config.monitor_interval_steps)
        # publish() reads the automaton cache's stats() live (grammar/*
        # tags); None with structured off keeps the published tag set
        # byte-identical
        self.telemetry.grammar_cache = self._grammar_cache
        # multi-tenant serving (serving/tenancy): per-tenant WFQ + rate
        # limits on the admission path, and a paged LoRA adapter pool
        # the admission contract reserves residency in.  None/disabled =
        # bit-for-bit the single-tenant loop above (locked by test both
        # directions): the scheduler stays the base class, no bucket is
        # consulted, no pool exists, and record_step publishes nothing
        # new.
        ten = self.config.tenancy
        self._tenancy = ten if (ten is not None and ten.enabled) else None
        self._pool = None
        self._buckets: Dict[str, object] = {}
        # adapter reservations held by admitted requests: uid ->
        # adapter_id (the pin `AdapterPool.reserve` took at admission;
        # every path that debits `_reserved` releases this too)
        self._adapter_held: Dict[int, str] = {}
        if self._tenancy is not None:
            from .tenancy import TenantFairScheduler, TokenBucket
            self.scheduler = TenantFairScheduler(
                max_queue_len=self.config.max_queue_len,
                weights=self._tenancy.weights,
                default_weight=self._tenancy.default_weight)
            self._buckets = {
                t: TokenBucket(rate, self._tenancy.burst_s)
                for t, rate in self._tenancy.rate_limits.items()}
            if self._tenancy.adapter_pool_blocks > 0:
                # serving adapters needs the engine's multi-LoRA
                # contract (gather epilogue + per-row slot binding) —
                # loud here, never a silent base-model decode
                if not getattr(engine, "supports_lora", False):
                    raise ValueError(
                        f"ServingConfig.tenancy.adapter_pool_blocks="
                        f"{self._tenancy.adapter_pool_blocks} needs an "
                        f"engine with multi-LoRA support "
                        f"(supports_lora/attach_lora/set_adapter); "
                        f"{type(engine).__name__} has none — set "
                        f"adapter_pool_blocks=0 for QoS-only tenancy")
                from .tenancy import AdapterPool
                self._pool = AdapterPool(
                    engine, self._tenancy.adapter_pool_blocks,
                    block_elems=self._tenancy.adapter_block_elems,
                    host_blocks=self._tenancy.host_spill_blocks,
                    quant=self._tenancy.host_spill_quant)
            self.telemetry.track_tenants = True
        # expert-paged MoE decode (serving/experts.py): the model's own
        # expert FFN weights under the adapter-pool residency discipline
        # — slotted HBM pages, demotion to host, census-driven
        # promotion.  None/disabled = bit-for-bit the unpaged loop
        # (locked by test both directions): no census rider in the
        # arena, no pool, record_step publishes nothing new.
        moe = self.config.moe
        self._moe = moe if (moe is not None and moe.enabled) else None
        self._expert_pool = None
        if self._moe is not None:
            # paging the experts needs the engine's MoE contract
            # (census arena + slot-grouped _moe_inference) — loud here,
            # never a silent dense decode
            if not getattr(engine, "supports_moe", False):
                raise ValueError(
                    f"ServingConfig.moe needs an engine with expert "
                    f"paging support (supports_moe — an MoE model "
                    f"config, no fused-TP program); "
                    f"{type(engine).__name__} does not qualify — drop "
                    f"serving.moe (or set enabled=false) to serve the "
                    f"unpaged model")
            slots = (self._moe.slots_per_layer
                     or engine.cfg.moe_experts)  # 0 = full residency
            self._expert_pool = engine.enable_expert_paging(
                slots, spill=self._moe.spill)
        # the latent MoE stacks' router counters (an engine with
        # supports_moe_counts): drained every COUNT_DRAIN_STEPS steps
        # into the telemetry counters and a `serve.moe_census` span;
        # 0 = an engine without them, never asked
        self._moe_counts_every = 0
        if getattr(engine, "supports_moe_counts", False):
            from ..inference.v2.expert_ffn import COUNT_DRAIN_STEPS
            self._moe_counts_every = COUNT_DRAIN_STEPS
        # observability (serving/tracing.py): per-request span traces +
        # the per-step timeline profiler.  Both default off (tracing is
        # None) and every hook below guards on None — the untraced loop
        # is bit-for-bit PR-10 behavior, locked by test.  `trace_label`
        # is the replica identity spans carry; the fleet router renames
        # it to "replica<N>" when this loop joins a fleet.
        self.trace_label = "loop"
        self._tracer = None
        self._timeline = None
        # per-tick metric time series (serving/observatory): None = off
        # = the unsampled loop, bit-for-bit (locked by test) — the off
        # path below never reads the clock for it
        self._metrics = None
        tracing = self.config.tracing
        if tracing is not None and (tracing.enabled
                                    or tracing.step_timeline > 0
                                    or tracing.metrics_ring > 0):
            from .tracing import RequestTracer, StepTimeline
            if tracing.enabled:
                self._tracer = RequestTracer(tracing.max_spans_per_request)
            if tracing.step_timeline > 0:
                self._timeline = StepTimeline(tracing.step_timeline)
                self.telemetry.timeline = self._timeline
            if tracing.metrics_ring > 0:
                from .observatory.metrics import MetricsSampler
                self._metrics = MetricsSampler(tracing.metrics_ring)
        # token streaming (serving/streaming.py): when on, every submit
        # attaches a TokenStream and the loop emits at first-token and
        # burst/verify-span boundaries.  Off (None) = bit-for-bit the
        # unstreamed loop — every emission seam guards on req.stream.
        stream_cfg = self.config.streaming
        self._streaming = stream_cfg is not None and stream_cfg.enabled
        self._auto_seed = self._streaming and stream_cfg.auto_seed
        # seed assignment draws from its OWN RandomState so auto-seeded
        # stochastic requests do not perturb the loop's sampling stream
        self._seed_rng = (np.random.RandomState(
            (rng_seed ^ 0x5EED) & 0x7FFFFFFF) if self._auto_seed
            else None)
        # SLO-aware preemption by KV swap-or-recompute: when on, an
        # urgent queued request that cannot admit preempts the lowest-
        # priority DECODE-state request (see _preempt_for_admission).
        # Off (None) = bit-for-bit the no-preemption scheduler.
        pre = self.config.preemption
        self._preempt_cfg = pre if (pre is not None and pre.enabled) \
            else None
        self._preempted_this_step = 0
        self._rng = np.random.RandomState(rng_seed)
        self._next_uid = 0
        self._block_size = getattr(engine.state, "block_size", 1)
        # how the engine counts a sequence's blocks (`DSStateManager.
        # blocks_needed` / `blocks_leased`: ints, or one count a kind); an
        # engine that states no count holds whole blocks of `block_size`
        self._count_needed = getattr(
            engine, "blocks_needed",
            lambda tokens: -(-tokens // self._block_size))
        self._count_leased = getattr(engine, "blocks_leased",
                                     lambda d: len(d.blocks))
        # KV reservation ledger: uid -> total blocks the request's WHOLE
        # lifetime needs.  The engine leases blocks lazily as sequences
        # grow, so "free_blocks" alone over-reports headroom: blocks an
        # earlier admittee has not leased YET must not be handed to a
        # later one (that would be an allocator error mid-decode, steps
        # after admission claimed to guarantee capacity).
        self._reserved: Dict[int, int] = {}

    # -- client surface ---------------------------------------------------
    def submit(self, prompt_tokens, max_new_tokens: Optional[int] = None,
               timeout_s: Optional[float] = None, priority: int = 0,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               seed: Optional[int] = None, tenant: str = "default",
               adapter_id: Optional[str] = None,
               response_format=None,
               due: Optional[float] = None) -> Request:
        """Queue one request.  Raises `AdmissionError` for a request the
        engine can never serve and `QueueFullError` when the bounded queue
        is full (backpressure — nothing is silently dropped).

        `due` (serve-clock seconds) is when the caller says the request
        arrived, where that is earlier than this call — a load generator
        that was itself held up, a client whose previous request just
        finished: `arrival_time`, and with it the `queued` span, the
        queue wait, TTFT and the deadline, count from there.  None = now.

        `seed` pins the request's stochastic sampling to the counter-
        based stream (serving/streaming.seeded_sample) — required for
        verifiable replay of temperature > 0 requests under streaming
        failover; with `StreamingConfig.auto_seed` one is assigned
        automatically.

        `tenant` bills the request to a tenancy account (rate limits /
        WFQ weight / per-tenant telemetry; inert with tenancy off) and
        `adapter_id` decodes it through a registered LoRA adapter —
        `RateLimitedError` when the tenant's token bucket is empty,
        `AdmissionError` for an adapter this replica does not hold.

        `response_format` (serving/structured.ResponseFormat: a regex
        or JSON-schema output grammar) constrains the generation ON
        DEVICE via the compiled token automaton.  The grammar compiles
        (or cache-hits) HERE — a spec the compiler rejects raises
        `AdmissionError` at submit, never a mid-decode surprise — and
        `eos_token_id` is required with it (accept states terminate by
        emitting the row's EOS).  None = unconstrained, bit-for-bit
        the pre-structured loop."""
        now = self.clock()
        if self._draining:
            # transient failover backpressure, NOT a malformed request —
            # its own counter so dashboards don't conflate the two
            self.telemetry.count("rejected_draining")
            raise AdmissionError(
                "serve loop is draining: no new requests are admitted "
                "(in-flight work finishes; queued work was handed back "
                "by drain())")
        prompt = np.asarray(prompt_tokens, np.int32).ravel()
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens
        if timeout_s is None:
            timeout_s = self.config.default_timeout_s
        if len(prompt) == 0:
            self.telemetry.count("rejected_invalid")
            raise AdmissionError("empty prompt")
        if max_new_tokens < 1:
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if top_k < 0:
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(f"top_k must be >= 0, got {top_k}")
        if ((self._streaming or seed is not None) and temperature > 0.0
                and (self._burst_n > 1 or self._group_k > 1)
                and not getattr(self.engine, "supports_seeded_sampling",
                                False)):
            # burst/multi-step decode samples ON DEVICE: without the
            # engine's counter-based (seed, position) streams a
            # stochastic streamed row's failover replay would diverge
            # from the delivered log, and an explicit seed would be
            # only half-honored (seeded first token, engine-RNG
            # bursts).  Loud at submit, never a silent determinism/
            # delivery downgrade.  Greedy streams work on every engine;
            # InferenceEngineV2 under xla TP serves seeded streams
            # on-device (ragged_ops Philox, bit-exact with
            # streaming.seeded_sample).
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(
                f"a stochastic request (temperature={temperature}) "
                f"that is streamed or seeded cannot serve under burst "
                f"or multi-step decode without an engine with seeded "
                f"per-request sampling (supports_seeded_sampling); "
                f"{type(self.engine).__name__} has none — use "
                f"temperature=0, decode_burst=1/multi_step=1, or a "
                f"capable engine")
        total = len(prompt) + max_new_tokens
        cap = self.engine.max_tokens_per_seq
        if total > cap:
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) = {total} tokens exceeds the engine's "
                f"per-sequence capacity {cap} (min of KV lease and model "
                f"max_seq_len)")
        if response_format is not None:
            if self._grammar_cache is None:
                self.telemetry.count("rejected_invalid")
                raise AdmissionError(
                    "request carries a response_format but this loop "
                    "serves no grammar subsystem "
                    "(ServingConfig.structured is None/disabled) — "
                    "queueing it would silently emit unconstrained "
                    "output")
            if eos_token_id is None:
                self.telemetry.count("rejected_invalid")
                raise AdmissionError(
                    "a constrained request needs eos_token_id: the "
                    "automaton finishes a completed generation by "
                    "emitting EOS from an accept state — without one "
                    "the row would be forced past the grammar's end")
            from .structured import GrammarError, ResponseFormat
            if not isinstance(response_format, ResponseFormat):
                self.telemetry.count("rejected_invalid")
                raise AdmissionError(
                    f"response_format must be a "
                    f"serving.structured.ResponseFormat (build one via "
                    f"ResponseFormat.regex / .json_schema), got "
                    f"{type(response_format).__name__}")
            try:
                # compile (or cache-hit) NOW: admission-time cost,
                # submit-time rejection — a grammar the compiler
                # refuses must never strand a queued request
                self._grammar_cache.get(response_format)
            except GrammarError as e:
                self.telemetry.count("rejected_invalid")
                raise AdmissionError(
                    f"response_format rejected by the grammar "
                    f"compiler: {e}")
            self.telemetry.count("grammar_requests")
        if adapter_id is not None:
            if self._pool is None:
                self.telemetry.count("rejected_invalid")
                raise AdmissionError(
                    f"request names adapter {adapter_id!r} but this loop "
                    f"serves no adapter pool "
                    f"(ServingConfig.tenancy.adapter_pool_blocks=0) — "
                    f"serving it would silently decode the base model")
            if not self._pool.is_registered(adapter_id):
                self.telemetry.count("rejected_invalid")
                raise AdmissionError(
                    f"adapter {adapter_id!r} is not registered on this "
                    f"replica (register_adapter first) — queueing the "
                    f"request would strand it at admission forever")
        if self._tenancy is not None:
            bucket = self._buckets.get(tenant)
            if bucket is not None and not bucket.try_take(now):
                # per-tenant admission metering: the configured tenant
                # is over its rate — shed HERE, loudly, before the
                # request touches the queue (the QueueFullError
                # backpressure discipline, priced per tenant)
                self.telemetry.count("rejected_rate_limited")
                self.telemetry.count_tenant(tenant,
                                            "rejected_rate_limited")
                from .tenancy import RateLimitedError
                raise RateLimitedError(
                    f"tenant {tenant!r} is over its "
                    f"{bucket.rate:g} req/s rate limit (burst "
                    f"{bucket.burst:g}); retry after backoff")
        if seed is None and self._auto_seed and temperature > 0.0:
            # deterministic given submission order (the parity/chaos
            # comparisons re-run identical schedules), stable across
            # failover because the seed rides the Request
            seed = int(self._seed_rng.randint(1 << 31))
        if self._streaming and temperature > 0.0 and seed is None:
            # an UNSEEDED stochastic stream cannot honor exactly-once:
            # failover regeneration resamples from the loop RNG, the
            # replay check diverges from the delivered log, and the
            # resulting StreamReplayError escapes the serve step —
            # whose crash containment fails the whole replica, not one
            # request.  Loud at submit instead (auto_seed, the
            # default, never reaches here).
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(
                f"streaming a stochastic request (temperature="
                f"{temperature}) needs a sampling seed for verifiable "
                f"exactly-once replay: pass seed= or leave "
                f"StreamingConfig.auto_seed on")
        arrival = now if due is None else due
        req = Request(
            uid=self._next_uid, prompt=prompt,
            max_new_tokens=max_new_tokens, arrival_time=arrival,
            deadline=(arrival + timeout_s) if timeout_s is not None
            else None,
            priority=priority, eos_token_id=eos_token_id,
            temperature=temperature, top_k=top_k, seed=seed,
            tenant=tenant, adapter_id=adapter_id,
            response_format=response_format)
        self._next_uid += 1
        try:
            self.scheduler.submit(req)
        except Exception:
            self.telemetry.count("rejected_queue_full")
            raise
        self.telemetry.count("submitted")
        if self._tenancy is not None:
            self.telemetry.count_tenant(tenant, "submitted")
        if self._tracer is not None:
            self._tracer.attach(req, self.trace_label)
        if self._streaming:
            from .streaming import TokenStream
            req.stream = TokenStream()
        return req

    # -- pool roles (serving/fleet/disagg) --------------------------------
    @property
    def role(self) -> str:
        return self._role

    def set_role(self, role: str) -> None:
        """Assign this replica's pool role (disaggregated serving).

        "prefill": the loop suppresses decode entirely — admission
        reserves only the PROMPT's KV blocks (decode happens on another
        replica's arena, so reserving the decode budget here would just
        shrink the admission batch), put/step run prefill-only, and a
        request whose prompt completes is parked for the handoff
        coordinator instead of sampling a first token.  Requires the
        prefix cache: the handoff streams the finished prompt KV through
        the flush -> insert-on-completion -> migrate seam.

        "decode"/"unified": no loop behavior change (a decode replica is
        a normal serve loop — the role is routing and telemetry
        attribution); "unified" is the default and the disagg-off
        parity state."""
        if role not in ("prefill", "decode", "unified"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'unified', got "
                f"{role!r}")
        if role == "prefill" and self._cache is None:
            raise ValueError(
                "the prefill role needs a prefix cache "
                "(ServingConfig.prefix_cache_blocks > 0): the handoff "
                "streams finished prompt KV out of it")
        if (role == "prefill" and role != self._role
                and self.scheduler.has_work):
            # a DECODE-state request on a loop that stops running the
            # decode phase would never advance again: its waiters hang
            # while has_work stays true forever.  Roles are assigned to
            # idle loops (fleet construction / fresh spawns); draining
            # first is the live-reassignment path.
            raise ValueError(
                f"cannot assign the prefill role to a loop with "
                f"{self.scheduler.queue_depth} queued and "
                f"{len(self.scheduler.active)} in-flight request(s): "
                f"the prefill role suppresses decode, so existing work "
                f"would wedge — drain the loop first")
        if role != "prefill" and self._handoff_ready:
            raise ValueError(
                f"cannot leave the prefill role with "
                f"{len(self._handoff_ready)} request(s) parked for "
                f"handoff")
        self._role = role

    @property
    def has_parked(self) -> bool:
        """True while prefill-finished requests are parked on this loop
        awaiting the handoff coordinator.  Deliberately NOT part of
        `has_work`: the loop itself cannot advance them (stepping a loop
        with only parked requests would spin), but the fleet must treat
        them as live work — the router's has_work, replica removal, and
        autoscaler retirement all check this seam."""
        return bool(self._handoff_ready)

    def take_handoff_ready(self) -> List[Request]:
        """Drain the requests whose prompt finished prefilling on this
        (prefill-role) replica.  Each is still in PREFILL state, still
        owns its engine sequence (the prompt KV), and is no longer in
        the scheduler — the handoff coordinator owns it from here:
        `finish_handoff(uid)` flushes the sequence (prompt KV lands in
        this replica's prefix cache via insert-on-completion), the KV
        migrates pool-ward, and the request is adopted on a decode
        replica."""
        out, self._handoff_ready = self._handoff_ready, []
        return out

    def finish_handoff(self, uid: int) -> None:
        """Release a parked request's engine sequence: the flush runs
        insert-on-completion (prompt KV -> this replica's prefix cache,
        whole blocks, before the decref) and the admission ledger
        returns the prompt-only reservation."""
        self._reserved.pop(uid, None)
        self._release_adapter(uid)
        self.engine.flush(uid)

    def cancel(self, uid: int) -> bool:
        """Flag a request for cancellation; it is finalized (and its
        engine sequence flushed) at the next `step()`.  Returns False for
        an unknown/already-finished uid."""
        req = self.scheduler.find(uid)
        if req is None or req.finished:
            return False
        req.cancel()
        return True

    def drain(self) -> List[Request]:
        """Begin a clean handoff: stop admitting (submit/adopt raise
        AdmissionError from now on), pop every QUEUED request off the
        scheduler, and return them UNSERVED — still in QUEUED state, so
        a fleet router can re-route them to another replica (`adopt`)
        instead of losing them to an abrupt shutdown.  In-flight
        (PREFILL/DECODE) requests are untouched: keep stepping until
        `has_work` clears and they finish normally."""
        self._draining = True
        queued = self.scheduler.take_queued()
        if queued:
            self.telemetry.count("drained_unserved", len(queued))
        return queued

    def adopt(self, req: Request) -> Request:
        """Take over a QUEUED request another replica handed back from
        `drain()`: re-validate against THIS engine's capacity, move it
        to this loop's uid space, and queue it.  The caller keeps the
        same Request object, so `result()` waiters survive failover."""
        if self._draining:
            self.telemetry.count("rejected_draining")
            raise AdmissionError("serve loop is draining")
        if req.state is not RequestState.QUEUED:
            raise ValueError(
                f"adopt needs a QUEUED request, got {req.uid} in "
                f"{req.state.value} (only unserved queued work fails "
                f"over; in-flight requests finish on their replica)")
        total = len(req.prompt) + req.max_new_tokens
        cap = self.engine.max_tokens_per_seq
        if total > cap:
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(
                f"adopted request needs {total} tokens, over this "
                f"engine's per-sequence capacity {cap}")
        if req.adapter_id is not None and (
                self._pool is None
                or not self._pool.is_registered(req.adapter_id)):
            # without this refusal the request would queue, then block
            # admission forever: fits()'s can_reserve pre-check can
            # never pass for an adapter this pool has never seen
            self.telemetry.count("rejected_invalid")
            raise AdmissionError(
                f"adopted request needs adapter {req.adapter_id!r}, "
                f"which this replica's pool does not hold — register "
                f"it here (or route tenant traffic by adapter "
                f"residency) before failing it over")
        req.uid = self._next_uid
        self._next_uid += 1
        try:
            self.scheduler.submit(req)
        except Exception:
            self.telemetry.count("rejected_queue_full")
            raise
        self.telemetry.count("submitted")
        if req.trace is not None:
            # the trace rides the Request across the re-homing: from
            # here on its entries attribute to THIS replica under the
            # uid this loop just assigned
            req.trace.on_adopt(self.clock(), self.trace_label, req.uid)
        return req

    def take_active(self) -> List[Request]:
        """Pull every in-flight request out of this loop WITHOUT
        finalizing it (engine sequences flushed best-effort, reservation
        ledger cleared): the fleet supervisor's failover hook for a
        replica whose engine can no longer be trusted to finish them.
        The requests stay in their in-flight state — the caller decides
        retry (`Request.reset_for_retry` + adoption elsewhere) vs
        `Request.fail`."""
        taken = list(self.scheduler.active.values())
        # a step still in flight is theirs: its uncollected tokens go
        # (the resumed replica re-prefills prompt + `generated`)
        self.drop_pending()
        # parked handoff-ready requests (prefill role) are in-flight too:
        # they hold engine sequences and PREFILL state, so a failover off
        # this replica must evict and re-home them like any active request
        taken += self.take_handoff_ready()
        now = self.clock() if any(r.trace is not None for r in taken) \
            else None
        for req in taken:
            if req.trace is not None:
                # the failover story starts here: this replica can no
                # longer be trusted with the request's in-flight work
                req.trace.event("demote", now)
            try:
                self.engine.flush(req.uid)
            except Exception:        # the engine may be the dead party
                pass
            self._reserved.pop(req.uid, None)
            self._release_adapter(req.uid)
            lease = self._prefix_pending.pop(req.uid, None)
            if lease is not None:
                # a crash between admission (lease acquired) and the
                # put() that would consume it left the lease held here:
                # return its pins or the cache leaks live refs forever
                try:
                    self._cache.abandon(lease)
                except Exception:    # cache may have died with the engine
                    pass
            self.scheduler.active.pop(req.uid, None)
        if taken:
            self.telemetry.count("evicted_in_flight", len(taken))
        return taken

    def fail_all(self, error: Optional[BaseException]) -> List[Request]:
        """Crash containment: finalize every queued AND in-flight
        request FAILED with `error` attached, so `result()` waiters
        raise `RequestErrored` instead of hanging on work no loop will
        ever finish.  Returns the failed requests."""
        failed: List[Request] = list(self.scheduler.take_queued())
        failed.extend(self.take_active())
        # clock read AFTER take_active: its demote trace events carry a
        # fresh read, so the finish stamps must not precede them on a
        # real clock (same ordering fix as the supervisor failover)
        now = self.clock()
        for req in failed:
            req.fail(error, now)
            self.telemetry.record_finish(req)
        return failed

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def metrics(self):
        """The per-tick `MetricsSampler` (None unless
        `ServingConfig.tracing.metrics_ring` > 0) — its `.ring` holds
        the loop's metric time series, exportable via `to_jsonl()` /
        `prometheus_text()`."""
        return self._metrics

    @property
    def has_work(self) -> bool:
        # an undrained finished backlog is reportable work: requests a
        # crashed step already finalized but never returned to step()'s
        # caller.  Counting it here keeps drivers keyed on step()
        # returns (run_until_idle, a closed-loop bench) calling step()
        # one more time to collect them even when the crash emptied the
        # scheduler — without a supervisor around to call
        # take_finished_backlog(), they would otherwise vanish
        # so is a dispatched step whose tokens are not collected yet
        return (self.scheduler.has_work or bool(self._finished_backlog)
                or bool(self._in_flight))

    # -- the serve step ---------------------------------------------------
    def step(self) -> List[Request]:
        """Advance the serve loop by one engine step — plus, in burst
        mode, one compiled decode burst per sampling group.  Returns the
        requests that reached a terminal state during this step.

        Runs under the configured transfer guard
        (`ServingConfig.transfer_guard`): with "disallow", any host sync
        the hot path did not declare via an explicit `jax.device_get`
        raises here instead of silently capping throughput."""
        try:
            with self._guard():
                out = self._step()
        except Exception as e:
            self.step_errors += 1
            self.last_step_error = e
            raise
        if self._step_worked:
            self.progress += 1
        return out

    def _step(self) -> List[Request]:
        # `step` is the number the timeline row of this step carries
        # (`telemetry.steps` after its `record_step`): the join between
        # the profiler's clock and the serve clock
        phases = _StepPhases(
            self.clock if self._timeline is not None else None)
        with span("serve.step", step=self.telemetry.steps + 1) as whole, \
                phases:
            out = self._step_phases(phases, whole)
        # the step as the host-clock log has it (utils/spans.py): its
        # length, its wait for the device, its garbage collections
        self.telemetry.record_host_step(whole.record)
        return out

    def _step_phases(self, phases: _StepPhases, whole) -> List[Request]:
        now = self.clock()
        # step timeline (observe-only): the phase boundaries read the
        # serve clock only with the timeline on (`_StepPhases`), so the
        # off path touches the clock exactly as before
        timeline = self._timeline
        phases.enter("serve.finalize", now=now)
        # promote-wall attribution (host KV tier): promotions run inside
        # the admission phase, so the timeline carries their wall as its
        # own sub-phase — real profiler seconds from the tier's
        # perf_counter accumulator, deliberately not the (possibly
        # fake/virtual) serve clock
        promote_w0 = (self._tier.promote_wall_s
                      if timeline is not None and self._tier is not None
                      else 0.0)
        # accumulate into the crash-safe backlog: if any phase below
        # raises after a finalization (deadline expiry, then engine.put
        # fails), the finalized requests survive for the next report
        finished = self._finished_backlog
        # multi-step groups share the burst path's serve-loop shape:
        # pending tokens stay staged for the next compiled dispatch
        # (decode=False below), first tokens batch from prefill logits,
        # and _decode_bursts picks the k>1 group program per group
        burst = self._burst_n > 1 or self._group_k > 1
        prefill_only = self._role == "prefill"
        # a prefill-role loop must never run the engine's decode phase
        # (its requests hand off at prompt completion); the burst path's
        # decode=False suppression is exactly that switch
        no_decode = burst or prefill_only

        # 1) cancellations + deadline timeouts (queued AND active).  In
        #    burst mode this runs once per BURST, not per token — the
        #    documented responsiveness cost of the decode_burst knob.
        fin_q, fin_a = self.scheduler.expire(now)
        # finalizations enter the crash-safe backlog BEFORE any engine
        # call: expire() already made them terminal and dropped them
        # from the scheduler, so a flush that raises must not be able
        # to hide them from step()'s view (or leak their ledger debit)
        for req in fin_q + fin_a:
            self.telemetry.record_finish(req)
            finished.append(req)
        flush_err: Optional[BaseException] = None
        for req in fin_a:
            self._reserved.pop(req.uid, None)
            self._release_adapter(req.uid)
            try:
                self.engine.flush(req.uid)
            except Exception as e:   # the engine may be the dead party
                flush_err = flush_err or e
        if flush_err is not None:
            # every expiry was still flushed (attempted) and reported;
            # the failure itself surfaces as this step's health signal
            raise flush_err
        admission = phases.enter("serve.admission")

        # 2) admission: fold queued requests into free engine slots,
        #    gated on the KV blocks their WHOLE lifetime needs (minus
        #    what active requests have reserved but not leased yet) so
        #    an admitted request can never hit an allocator error
        #    mid-decode
        free_slots = self.engine.free_slots
        headroom = [self.engine.free_blocks - self._unleased_reserve()]

        def fits(req: Request) -> bool:
            # per-tenant KV-arena quota (tenancy.kv_block_quota): the
            # tenant's ACTIVE requests may hold at most `quota` reserved
            # blocks concurrently.  Checked FIRST — before any lease /
            # promotion / ledger side effect — so a quota-deferred head
            # costs nothing and retries cleanly.  `fits.blocked_tenant`
            # tells the fair scheduler this refusal is a per-tenant cap,
            # not arena pressure: other tenants' heads may still admit
            # (capacity refusals keep the strict no-skip-ahead stop).
            fits.blocked_tenant = None
            if self._tenancy is not None and self._tenancy.kv_block_quota:
                quota = self._tenancy.kv_block_quota.get(req.tenant)
                if quota is not None:
                    held = sum(self._reserved.get(uid, 0)
                               for uid, r in self.scheduler.active.items()
                               if r.tenant == req.tenant)
                    if _short(held + self._blocks_needed(req), quota):
                        self.telemetry.count("quota_deferred")
                        self.telemetry.count_tenant(req.tenant,
                                                    "quota_deferred")
                        fits.blocked_tenant = req.tenant
                        return False
            if req.adapter_id is not None \
                    and not self._pool.can_reserve(req.adapter_id):
                # adapter residency is admission capacity exactly like
                # KV blocks: every slot pinned by admitted requests =
                # the head waits (no-skip-ahead holds — a later
                # base-model request does not jump it).  Checked FIRST,
                # before any lease/ledger side effect below.
                return False
            total = self._blocks_needed(req)
            # the token sequence admission places: the prompt, plus any
            # already-generated tokens a preemption resume re-prefills
            # (or re-attaches from the cache — the swap-in path)
            toks = self._effective_tokens(req)
            # prefix reuse: acquire the match NOW (references pin it) so
            # the blocks a cached prefix provides are accounted as
            # already-held — the request only needs NEW blocks for its
            # uncovered suffix + decode budget, and admission can pack
            # more concurrent requests into the same arena
            if self._tier is not None and total > headroom[0]:
                # affordability pre-check BEFORE any promotion: the
                # residency-blind peek bounds what a lease could attach,
                # so a request that cannot fit even with full coverage
                # AND the whole evictable cache reclaimed is rejected
                # without paying promote round trips it would abandon —
                # retries of a hopeless queue head must not churn spans
                # host -> arena -> host every step.  (Skipped entirely
                # when the request fits current headroom uncovered, so
                # the unpressured hot path pays ONE radix walk, not two;
                # the O(tree) evictable scan runs only on an actual
                # shortfall, like the reclaim branch below.)
                best_cov = (self._cache.covered_tokens(toks)
                            // self._block_size)
                short = total - best_cov - headroom[0]
                if short > 0 and short > self._cache.evictable_blocks():
                    return False
                # host-resident spans on the match path promote back
                # into the arena here, bounded by the step's headroom —
                # promotion consumes real free blocks, so the promoted
                # count debits the ledger mirror below exactly like a
                # lease the request will hold
                lease = self._cache.acquire(
                    toks, max_promote_blocks=max(headroom[0], 0))
                if lease is not None and lease.promoted:
                    headroom[0] -= lease.promoted
            elif self._cache is not None:
                lease = self._cache.acquire(toks)
            else:
                lease = None
            # crash-window guard: everything between the acquire above
            # and the pending-map park below can raise (the evictable
            # scan, reclaim, the adapter promotion, the engine row
            # bind), and a raise here unwinds out of scheduler.admit —
            # the lease, the ledger entry, and the adapter pin must not
            # outlive it, or a recovering replica leaks admission
            # capacity for a request that was never admitted.
            try:
                need = total - (len(lease.blocks)
                                if lease is not None else 0)
                if _short(need, headroom[0]) and self._cache is not None:
                    # cached-but-unreferenced blocks are reclaimable
                    # headroom, not spent capacity: evict LRU prefixes
                    # to fit the head of the queue (never skipped —
                    # anti-starvation holds).  Only when eviction can
                    # actually close the gap, though — a request that
                    # cannot fit even with the cache emptied must not
                    # wipe the hot prefixes for nothing
                    short = need - headroom[0]
                    if self._cache.evictable_blocks() >= short:
                        headroom[0] += self._cache.reclaim(short)
                if _short(need, headroom[0]):
                    if isinstance(need, KindCounts):
                        self.telemetry.count(
                            "admit_blocked_by_kind_" + need.short_kind(
                                headroom[0], self.engine.kind_names))
                    if lease is not None:
                        self._cache.abandon(lease)
                    elif self._cache is not None:
                        # keep the standalone counters retry-neutral,
                        # like abandon() does for hits
                        self._cache.retract_miss()
                    return False
                headroom[0] -= need
                # the ledger stores the WHOLE lifetime need: shared
                # blocks attach at create, so need-minus-leased stays
                # correct
                self._reserved[req.uid] = total
                if req.adapter_id is not None:
                    # pin the adapter HBM-resident for this request's
                    # whole lifetime (promoting it from the host tier
                    # if it spilled) and bind the engine row to its
                    # slot — the never-fault-mid-decode half of the
                    # admission contract.  The pin gets its own
                    # rollback: a bind that raises must return the
                    # slot before the outer guard unwinds the rest.
                    slot = self._pool.reserve(req.adapter_id)
                    try:
                        self._adapter_held[req.uid] = req.adapter_id
                        self.engine.set_adapter(req.uid, slot)
                    except BaseException:
                        self._adapter_held.pop(req.uid, None)
                        try:
                            self._pool.release(req.adapter_id)
                        except Exception:
                            pass
                        raise
                if lease is not None:
                    self._prefix_pending[req.uid] = lease
                elif self._cache is not None:
                    # None records a known miss, so put() skips
                    # re-walking the tree (and double-counting the
                    # miss) for this uid
                    self._prefix_pending[req.uid] = None
                return True
            except BaseException:
                # mirror _rollback_admission for a request that never
                # admitted: ledger and lease — best-effort, never
                # shadowing the original error (the adapter pin
                # already rolled itself back above)
                self._reserved.pop(req.uid, None)
                self._prefix_pending.pop(req.uid, None)
                if lease is not None:
                    try:
                        self._cache.abandon(lease)
                    except Exception:
                        pass
                raise

        admitted = self.scheduler.admit(now, free_slots, fits)
        if (self._preempt_cfg is not None and not prefill_only
                and self.scheduler.queue_depth > 0):
            # SLO-aware preemption: an urgent head-of-queue request the
            # ordinary admission could not fit may evict a lower-
            # priority decode by KV swap-or-recompute, then admit in
            # THIS step (the preempted capacity is free immediately).
            # It runs OUTSIDE the crash-atomic admit->put try below, so
            # a raise in the preempt pass needs its own rollback or the
            # base admissions above stay stranded in the active set.
            try:
                admitted += self._preempt_for_admission(
                    now, len(admitted), fits, headroom)
            except BaseException:
                self._rollback_admission(admitted)
                raise
        # 3) one ragged engine step (admissions ride the same put() call).
        #    Burst mode suppresses the engine's per-step decode phase:
        #    burst-chained sequences each hold one pending token that
        #    belongs to the NEXT decode burst, which must not be decoded
        #    one step at a time while bursts own decode.
        #    On the per-step path a serve step is a dispatch and a
        #    collect (`engine.step(collect=False)` / `engine.collect`),
        #    and the collect of the step before comes after this step's
        #    dispatch, so the device holds its next programs while the
        #    host waits for tokens: first that step's PREFILL tokens
        #    (its prefill programs ended before the decode program
        #    queued behind them; a first token is stamped when it is
        #    here, not at the end of the call), then this step's
        #    dispatch, then that step's DECODE tokens.  A decode row
        #    whose input is among those takes it on the device.  This
        #    step in turn stays uncollected while every active request
        #    takes the program's own token (temperature <= 0, no grammar
        #    mask, no adapter row); a token sampled on the host has to
        #    be here before the next dispatch, so then the same call
        #    collects what it dispatched before it returns (depth 0).
        #    A step stays in `_in_flight` until its tokens are here
        #    (`engine.collect` takes a program off it only with its
        #    tokens), so a raise loses none: after one in the dispatch
        #    the next call feeds from and collects the step before as
        #    if this call had not been; after one in the wait for the
        #    step before's decode tokens, two steps are left, and the
        #    next call settles the older before anything else.
        #    The whole admit->put window is crash-atomic: a raise before
        #    put() returns rolls the admissions back to the queue —
        #    without that, a supervised replica that recovers after the
        #    error would hold requests the engine never heard of (hung
        #    waiters) plus their still-pinned prefix leases.  The try
        #    opens directly after admission, so even the timing/tracing
        #    bookkeeping below cannot strand an admitted request.
        #    Admission side effects (the `admitted` counter, the
        #    routing hook) fire only AFTER put() returns, so a
        #    rolled-back admission is neither double-counted on its
        #    retry nor allowed to consume the fleet router's coverage
        #    expectation for an admission that never stuck.
        split = self._splits_step and not no_decode
        in_flight = self._in_flight
        taken = 0
        try:
            admission.set_metadata(admitted=len(admitted))
            # the engine calls, and the bookkeeping of what they
            # admitted: the timeline's "prefill"
            phases.enter("serve.engine")
            while len(in_flight) > 1:
                taken += self._collect(in_flight[0], None, phases, finished)
                del in_flight[0]
                phases.enter("serve.engine")
            earlier = in_flight[0] if in_flight else None
            if earlier is not None and earlier.prefill:
                taken += self._collect(earlier, "prefill", phases, finished)
                phases.enter("serve.engine")
            # prefill-chunk span attribution reads the clock only when
            # some live request is actually traced (admitted ones
            # already joined the active set above)
            tracing_step = (self._tracer is not None
                            and any(r.trace is not None
                                    for r in self.scheduler.active.values()))
            t_engine0 = self.clock() if tracing_step else 0.0
            seen_before = {uid: d.seen_tokens
                           for uid, d in self.engine.state.seqs.items()}
            prefill_before = {uid for uid, d
                              in self.engine.state.seqs.items()
                              if d.seen_tokens < len(d.prompt)}
            # how the engine is to step: dispatch only, a row whose
            # input token is among `earlier`'s taking it on the device
            step_kw = (dict(ahead=earlier,
                            hold=self._ending_by_count(earlier),
                            collect=False)
                       if split else dict(decode=False) if no_decode
                       else {})
            if admitted:
                if self._cache is not None:
                    # hand the admission-time lookups to the engine —
                    # hits AND known misses (None), so put() never
                    # re-walks the tree.  Leases stay in _prefix_pending
                    # until put() RETURNS, so a put that raises leaves
                    # them findable for the rollback (and take_active)
                    # instead of orphaned in a dead local
                    step_kw["prefixes"] = {
                        r.uid: self._prefix_pending.get(r.uid)
                        for r in admitted}
                out = self.engine.put(
                    [r.uid for r in admitted],
                    [self._effective_tokens(r) for r in admitted],
                    **step_kw)
            elif self.scheduler.active and (not no_decode
                                            or prefill_before):
                out = self.engine.step(**step_kw)
            else:
                out = {}
        except BaseException:
            self._rollback_admission(admitted)
            raise
        # a two-kind cache's account of this step's decode rows (block x
        # layer units: `hybrid_ops.step_account`); else nothing
        kinds = getattr(out, "kv_kinds", {})
        whole.set_metadata(
            decode_rows=getattr(out, "decode_rows", 0),
            fed_on_device_rows=getattr(out, "fed_rows", 0),
            kv_live_blocks=getattr(out, "kv_live_blocks", 0),
            kv_table_blocks=getattr(out, "kv_table_blocks", 0), **kinds,
            # per-sequence recurrent state's account of the step's decode
            # rows (`ssm_ops.step_account`); else nothing
            **getattr(out, "state_account", {}))
        for name, n in kinds.items():
            self.telemetry.count(name, n)
        self.telemetry.count("admitted", len(admitted))
        if self._tenancy is not None:
            for r in admitted:
                self.telemetry.count_tenant(r.tenant, "admitted")
        covered_by_uid: Dict[int, int] = {}
        for r in admitted:
            lease = self._prefix_pending.pop(r.uid, None)
            covered_by_uid[r.uid] = (lease.covered if lease is not None
                                     else 0)
            if self._cache is not None:
                # hit/miss telemetry counts ADMITTED requests that the
                # engine actually accepted, not queue retries
                self.telemetry.record_prefix(covered_by_uid[r.uid])
            if r.trace is not None and covered_by_uid[r.uid] > 0:
                r.trace.event("prefix_hit", now,
                              covered_tokens=covered_by_uid[r.uid])
            if r.preemptions > 0 and lease is not None and lease.promoted:
                # blocks the resume just streamed host -> arena: the
                # swap-in half of swap-or-recompute, ledger-debited by
                # the fits() promotion accounting above
                self.telemetry.count("kv_swapped_in", lease.promoted)
            if (r.stream is not None and r.stream.emitted > 0
                    and (r.preemptions > 0 or r.retries > 0)):
                # a re-admission behind a non-empty delivered log:
                # the stream resumes (preemption continues it; failover
                # replays + suppresses) instead of starting over
                self.telemetry.count("streams_resumed")
        if self.admit_hook is not None:
            # routing hook: report the coverage each admitted request
            # ACTUALLY got (put() above consumed the leases)
            for r in admitted:
                self.admit_hook(r, covered_by_uid[r.uid])

        # 4) measured per-step budget accounting: attribute each live
        #    sequence's progress to prefill or decode work (the engine
        #    advances `seen_tokens` as it dispatches).  (Burst-mode
        #    decode tokens are counted in _decode_bursts below — the
        #    engine state read here predates the bursts.)
        t_engine1 = self.clock() if tracing_step else 0.0
        prefill_toks = decode_toks = 0
        for uid, d in self.engine.state.seqs.items():
            # a fresh prefix-attached sequence starts at seen_tokens ==
            # prefix_covered without computing anything — only the
            # uncovered suffix is real prefill work
            base = seen_before.get(uid, getattr(d, "prefix_covered", 0))
            delta = d.seen_tokens - base
            if delta <= 0:
                continue
            if uid not in seen_before or uid in prefill_before:
                prefill_toks += delta
                if tracing_step:
                    req = self.scheduler.active.get(uid)
                    if req is not None and req.trace is not None:
                        # one span per serve step the prompt advanced:
                        # the chunked-prefill progress a TTFT debug needs
                        req.trace.span("prefill_chunk", t_engine0,
                                       t_engine1, tokens=delta)
            else:
                decode_toks += delta

        pending = getattr(out, "pending", False)
        if pending:
            in_flight.append(out)
        if earlier is not None:
            # the step before's decode tokens: the device already holds
            # this step's programs.  A row whose request ended
            # meanwhile (EOS seen late, cancel, deadline, preemption)
            # was computed for nothing and its token is dropped
            if earlier.decode is not None:
                taken += self._collect(earlier, "decode", phases,
                                       finished)
            in_flight.remove(earlier)
        if not no_decode:
            # 5) per-step path: this step's tokens wait on the device
            #    for the next call while every active request takes the
            #    program's own token; a row sampled here has to be on
            #    the host before the next dispatch, so then they are
            #    collected now.  (An engine that collects inside its own
            #    put()/step() hands back rows that are never `pending`.)
            if pending and all(
                    r.temperature <= 0.0 and r.response_format is None
                    and r.adapter_id is None
                    for r in self.scheduler.active.values()):
                self.telemetry.count("steps_run_ahead")
            else:
                self.telemetry.count("steps_collected_at_once",
                                     1 if pending or out else 0)
                if pending:
                    taken += self._collect(out, None, phases, finished)
                    in_flight.remove(out)
                else:
                    taken += self._take_tokens(out, phases, finished)
        elif prefill_only:
            # 5) prefill pool (disagg): a request whose prompt just
            #    finished is PARKED for the cross-pool handoff — no
            #    first token here (it is sampled on the decode replica
            #    after the KV migrates, so the token stream has exactly
            #    one author), no decode phase ever
            phases.enter("serve.sample", rows=len(out))
            self._park_handoffs(out)
        elif burst:
            # 5) burst path: batched first tokens from the prefill logits
            #    (TTFT semantics unchanged), then one compiled burst per
            #    sampling group with on-device sampling.  The clock is
            #    re-read: the engine call above is where the step's time
            #    went, and first-token / finish stamps must charge it to
            #    THIS step's requests
            now = self.clock()
            phases.enter("serve.sample", now=now, rows=len(out))
            self._first_tokens_batch(out, now, finished)
            decode_toks = self._decode_bursts(finished)

        phases.enter("serve.bookkeep")
        # census-driven expert rebalance: every Nth step, drain the
        # router census the decode programs accumulated (one tiny d2h),
        # fold it into the pool's LRU/demand ranking, and promote the
        # hottest demoted experts — BEFORE record_step so this step's
        # gauges reflect this step's routing
        if (self._expert_pool is not None
                and self._moe.census_interval_steps > 0
                and (self.telemetry.steps + 1)
                % self._moe.census_interval_steps == 0):
            self._expert_pool.ingest_census(self.engine.drain_moe_census())
            self._expert_pool.rebalance(self._moe.max_promotes_per_step)
        # the latent MoE stacks' router counters: drained on the same
        # kind of interval, into the counters and a span a trace carries
        if (self._moe_counts_every and (self.telemetry.steps + 1)
                % self._moe_counts_every == 0):
            with span("serve.moe_census") as census:
                counts = self.engine.drain_moe_counts()
                census.set_metadata(**counts)
            for name, n in counts.items():
                self.telemetry.count("moe_" + name, n)

        self.telemetry.record_step(
            queue_depth=self.scheduler.queue_depth,
            live_seqs=len(self.engine.state.seqs),
            max_seqs=self.engine.config.max_seqs,
            prefill_tokens=prefill_toks, decode_tokens=decode_toks,
            prefix_cached_blocks=(self._cache.cached_blocks
                                  if self._cache is not None else None),
            host_tier=(self._tier.stats()
                       if self._tier is not None else None),
            adapter_pool=(self._pool.stats()
                          if self._pool is not None else None),
            expert_pool=(self._expert_pool.stats()
                         if self._expert_pool is not None else None))
        if timeline is not None:
            spent = phases.spent
            timeline.record(
                self.telemetry.steps,
                {"finalize": spent.get("serve.finalize", 0.0),
                 "admission": spent.get("serve.admission", 0.0),
                 # host-tier promotions ran INSIDE the admission window
                 # above; this is their share of it (tier perf-counter
                 # wall — 0.0 without a tier)
                 "promote": (self._tier.promote_wall_s - promote_w0
                             if self._tier is not None else 0.0),
                 # the engine's calls dominate this window (on the
                 # per-step path that is staging, the launches, and the
                 # waits for the tokens of the step before); the cheap
                 # host bookkeeping of what was admitted rides along
                 "prefill": spent.get("serve.engine", 0.0),
                 # bookkeeping and host sampling of the rows that need
                 # it (per-step path), or the compiled bursts
                 "decode": spent.get("serve.sample", 0.0)},
                admitted=len(admitted), finished=len(finished),
                prefill_tokens=prefill_toks, decode_tokens=decode_toks,
                queue_depth=self.scheduler.queue_depth,
                free_blocks=int(self.engine.free_blocks))  # dstpu: noqa[DST001] a host count: an int, or a two-kind cache's KindCounts (its scarcest kind)
        if self._metrics is not None:
            # one time-series row per tick (serving/observatory): pure
            # host reads on state this step already computed
            self._metrics.sample_loop(self, self.clock())

        # debug-mode block-conservation check: every time requests drain,
        # free + live + cache-held blocks must account for every block
        # and refcount — a leak here is a serving bug, caught loudly at
        # the step that introduced it, not as a slow arena exhaustion
        if self._audit and finished and hasattr(self.engine,
                                                "audit_blocks"):
            self.engine.audit_blocks()
        if self._audit and finished and self._pool is not None:
            # same cadence for the adapter pool: slot/host-page/pin
            # conservation, loud at the step that broke it
            self._pool.audit()
        if self._audit and finished and self._expert_pool is not None:
            # and for the expert pool: slot conservation + published
            # slot_map/resident_mask vs the host bookkeeping
            self._expert_pool.audit()
        # the heartbeat signal: did this step DO anything?  A step that
        # completes with work queued/active but no admission, no token
        # advanced, and no finalization is a wedge that RETURNS (engine
        # silently dropping its sequences) — it must read exactly like a
        # stall to the supervisor, so step() only advances `progress`
        # when this is set
        self._step_worked = (bool(finished) or bool(admitted)
                             or prefill_toks > 0 or decode_toks > 0
                             or taken > 0
                             or self._preempted_this_step > 0)
        self._preempted_this_step = 0
        self._finished_backlog = []
        return finished

    def _collect(self, step, part: Optional[str], phases: _StepPhases,
                 finished: List[Request]) -> int:
        """Wait for a dispatched step's tokens (`engine.collect`) and
        take them; a row whose sequence is gone was an overrun."""
        got = self.engine.collect(step, part)
        self.telemetry.count("rows_overrun", got.overrun)
        return self._take_tokens(got, phases, finished)

    def _take_tokens(self, out, phases: _StepPhases,
                     finished: List[Request]) -> int:
        """Per-step path: a token for every sequence of ours in `out`
        (the rows an engine step produced), stamped with the clock read
        now; finish the request or stage the token as its next decode
        input.  Returns how many tokens were taken.

        A row whose sampler is the plain argmax (temperature <= 0, no
        grammar mask: `_sample`) takes the token the engine's program
        chose beside the logits — the same f32 row, the same first
        maximum — and its logits never leave the device.  Every other
        row is sampled here from its own logits row: so is a row
        somebody put a host row in the place of (no token), and every
        row of an engine whose step returns plain host rows
        (`engine_v2.LogitsRows.greedy` is the capability probed; test
        fakes return dicts)."""
        # the clock is read after the wait for these rows: first-token
        # and finish stamps charge it to the requests that waited
        now = self.clock()
        # host work on what the engine returned: the timeline's "decode"
        phases.enter("serve.sample", now=now, rows=len(out))
        greedy = getattr(out, "greedy", None)
        taken = 0
        for uid in out:
            req = self.scheduler.active.get(uid)
            if req is None:
                continue   # not ours (engine shared with other callers)
            tok = None
            if (greedy is not None and req.temperature <= 0.0
                    and req.response_format is None):
                tok = greedy(uid)
            if tok is None:
                tok = self._sample(req, np.asarray(out[uid]))  # dstpu: noqa[DST001] a host np row: the engine fetches it explicitly (device_get) when it is read
                self.telemetry.count("sampled_on_host")
            else:
                self.telemetry.count("sampled_on_device")
            taken += 1
            if req.state is RequestState.PREFILL:
                req.advance(RequestState.DECODE, now)
                req.mark_first_token(now)
            req.generated.append(tok)
            self._emit_stream(req, now)
            hit_eos = (req.eos_token_id is not None
                       and tok == req.eos_token_id)
            if hit_eos or len(req.generated) >= req.max_new_tokens:
                self._finish(req, now, finished)
            else:
                # pending input of the next decode step (the same
                # staging generate_batch uses); where that step is
                # dispatched already and took the token on the device,
                # this is the host's list catching up
                self.engine.state.seqs[uid].generated.append(tok)
        return taken

    def _ending_by_count(self, earlier) -> List[int]:
        """The uids whose uncollected decode token in `earlier` will be
        their last by count: they are left out of the next dispatch, so
        a greedy stream with no stop token computes no row for nothing.
        (What the host learns late — EOS, cancel, deadline, preemption —
        costs one row, dropped when it is collected.)"""
        if earlier is None or earlier.decode is None:
            return []
        active = self.scheduler.active
        return [d.uid for d, _ in earlier.decode.rows
                if d.uid not in active
                or len(active[d.uid].generated) + 1
                >= active[d.uid].max_new_tokens]

    def drop_pending(self) -> None:
        """Forget the dispatched steps whose tokens were never collected
        (failover, crash containment, shutdown): their rows were
        computed for nothing."""
        for step in self._in_flight:
            self.telemetry.count("rows_overrun", step.awaited)
        self._in_flight.clear()

    def _rollback_admission(self, admitted: List[Request]) -> None:
        """Undo admission for requests whose engine put() never
        completed.  Without this, a step that raises between
        `scheduler.admit` and a successful put() leaves them in the
        scheduler's active set but unknown to the engine — decode_ready
        never sees them, so on a replica that keeps serving (supervised
        fleet, SUSPECT -> HEALTHY recovery; ThreadedServer crash
        containment with a caller-owned engine) they would hang their
        `result()` waiters forever while their admission-time prefix
        leases stay pinned.  Rolled-back requests return to the queue
        (requeue bypasses the admission bound — they were accepted long
        ago) and the next successful step re-admits them cleanly."""
        for req in admitted:
            in_engine = req.uid in self.engine.state.seqs
            if in_engine:
                # put() got far enough to create this sequence (and
                # hand it any lease): flush releases both
                try:
                    self.engine.flush(req.uid)
                except Exception:
                    pass
            lease = self._prefix_pending.pop(req.uid, None)
            if lease is not None and not in_engine:
                try:
                    self._cache.abandon(lease)
                except Exception:
                    # a partially-failed put may have abandoned it
                    # already (engine-side create failure)
                    pass
            if req.uid in self._adapter_held and not in_engine:
                # put() never created the sequence, so flush above never
                # ran: clear the slot binding fits() set, or the next
                # request under this uid would decode through a stale
                # adapter
                try:
                    self.engine.set_adapter(req.uid, -1)
                except Exception:
                    pass
            self._reserved.pop(req.uid, None)
            self._release_adapter(req.uid)
            self.scheduler.active.pop(req.uid, None)
            if not req.finished:
                # PREFILL -> QUEUED, same direct reset reset_for_retry
                # uses (no retry count: the request never left this loop)
                req.state = RequestState.QUEUED
                req.admit_time = None
                self.scheduler.requeue(req)
                if req.trace is not None:
                    req.trace.on_rollback(self.clock())

    # -- burst path -------------------------------------------------------
    def _finish(self, req: Request, now: float,
                finished: List[Request]) -> None:
        """Terminal bookkeeping shared by both hot paths: the flush
        releases the engine sequence (including any KV a burst over-
        generated past EOS) and the ledger debit returns the request's
        whole reservation, so truncation can never leak admission
        capacity."""
        self.scheduler.finish(req, now)
        # crash-safe backlog: the request is terminal the moment the
        # scheduler finishes it, so it must be RECORDED before the
        # engine flush — a flush that raises after this point loses KV
        # bookkeeping (and propagates loudly), but it can no longer
        # hide a finished request from its result() waiter
        self._reserved.pop(req.uid, None)
        self._release_adapter(req.uid)
        self.telemetry.record_finish(req)
        finished.append(req)
        self.engine.flush(req.uid)

    def _park_handoffs(self, out) -> None:
        """Prefill-role completion path: every logits row is a request
        whose prompt just finished (the decode phase is suppressed, so
        nothing else produces logits here).  The request leaves the
        scheduler — still PREFILL state, engine sequence (the prompt KV)
        and ledger reservation intact — and waits for the fleet handoff
        coordinator, which flushes the KV into this replica's prefix
        cache, streams it to a decode replica, and adopts the request
        there.  The logits themselves are dropped: the first token is
        sampled once, on the decode replica, after the handoff."""
        for uid in out:
            req = self.scheduler.active.get(uid)
            if req is None:
                continue   # not ours (engine shared with other callers)
            del self.scheduler.active[uid]
            self._handoff_ready.append(req)
            self.telemetry.count("handoff_parked")
            if req.trace is not None:
                req.trace.on_park(self.clock())

    def _first_tokens_batch(self, out, now: float,
                            finished: List[Request]) -> None:
        """Sample the first token of every request whose prefill just
        finished, in ONE device call when the engine offers its batched
        sampler (`sample_tokens_batch`, the generate_batch first-token
        pattern), host-side otherwise (test fakes).  Tokens are staged as
        the pending input of the next burst; finishes append to the
        caller's (crash-safe) `finished` list."""
        rows = [(uid, logits) for uid, logits in out.items()
                if self.scheduler.active.get(uid) is not None]
        if not rows:
            return
        reqs = [self.scheduler.active[uid] for uid, _ in rows]
        sampler = getattr(self.engine, "sample_tokens_batch", None)
        # seeded stochastic rows must draw from the request's counter-
        # based stream (replay-deterministic), not the engine's batched
        # sampler RNG: the host reference sampler handles them — greedy-
        # only batches (the parity-locked common case) keep the batched
        # device dispatch
        if any(r.seed is not None and r.temperature > 0.0 for r in reqs):
            sampler = None
        # constrained rows must mask their FIRST token too: the host
        # reference sampler applies the automaton's start-state mask
        # (_sample), which the engine's batched prefill sampler has no
        # operand for — one host pass here, the compiled multi-step /
        # verify dispatches take over from the second token on
        if any(r.response_format is not None for r in reqs):
            sampler = None
        if sampler is not None:
            # pad to max_seqs rows so the sampler dispatch keeps ONE
            # compiled shape regardless of how many prefills finished
            # this step (each distinct row count would otherwise compile
            # its own program — multi-second compiles inside the serve
            # loop)
            n = len(rows)
            width = max(getattr(self.engine.config, "max_seqs", n), n)
            stacked = np.zeros((width,) + np.asarray(rows[0][1]).shape,
                               np.float32)
            for i, (_, logits) in enumerate(rows):
                stacked[i] = np.asarray(logits)  # dstpu: noqa[DST001] host-side restaging of logits the engine fetched explicitly once
            if all(r.temperature <= 0.0 for r in reqs):
                # all-greedy: one argmax dispatch, no per-row sort
                toks = sampler(stacked, mode="greedy")
            else:
                temp = np.zeros(width, np.float32)
                topk = np.zeros(width, np.int32)
                temp[:n] = [r.temperature for r in reqs]
                topk[:n] = [r.top_k for r in reqs]
                toks = sampler(stacked, mode="per_row", temperature=temp,
                               top_k=topk)
            toks = [int(t) for t in toks[:n]]
        else:
            toks = [self._sample(r, np.asarray(l))  # dstpu: noqa[DST001] fake-engine fallback; rows are host np logits
                    for r, (_, l) in zip(reqs, rows)]
        for req, tok in zip(reqs, toks):
            req.advance(RequestState.DECODE, now)
            req.mark_first_token(now)
            req.generated.append(tok)
            self._emit_stream(req, now)
            hit_eos = (req.eos_token_id is not None
                       and tok == req.eos_token_id)
            if hit_eos or len(req.generated) >= req.max_new_tokens:
                self._finish(req, now, finished)
            else:
                self.engine.state.seqs[req.uid].generated.append(tok)

    def _burst_groups(self, ready: List[Request]):
        """Partition burst-ready requests into dispatch groups, yielding
        (mode, temperature, top_k, requests, response_format) tuples.

        Unconstrained requests group by sampling signature: one per-row
        burst serves them ALL when the engine vectorizes
        temperature/top_k (greedy rows ride along at temperature 0);
        otherwise greedy requests share one burst and each distinct
        (temperature, top_k) gets its own — the documented fallback,
        costing one compiled dispatch per group.

        Constrained requests (response_format set) additionally group
        per GRAMMAR: a compiled dispatch carries exactly one automaton
        table set (trans/mask/accept operands), so rows sharing a
        grammar share a dispatch and distinct grammars each pay one.
        Constrained groups always sample per-row (their dispatch paths
        — multi-step scan or draft-verify — vectorize temperature /
        top_k natively, so no signature sub-split is needed); sort by
        the grammar's (kind, spec) keeps group order deterministic
        across steps."""
        base = [r for r in ready if r.response_format is None]
        cons = [r for r in ready if r.response_format is not None]
        out = []
        if base:
            greedy = [r for r in base if r.temperature <= 0.0]
            stoch = [r for r in base if r.temperature > 0.0]
            if not stoch:
                out.append(("greedy", 0.0, 0, base, None))
            else:
                sigs = {(r.temperature, r.top_k) for r in stoch}
                if not greedy and len(sigs) == 1:
                    # uniform stochastic batch: the scalar "sample"
                    # program skips the per-row path's O(V log V) sort
                    # per decode token (its kth threshold needs a full
                    # sort because lax.top_k wants a static k) — per_row
                    # is only worth its cost for genuinely mixed
                    # signatures
                    (t, k), = sigs
                    out.append(("sample", t, k, base, None))
                elif getattr(self.engine,
                             "supports_per_row_sampling", False):
                    out.append(("per_row", None, None, base, None))
                else:
                    groups: Dict = {}
                    for r in stoch:
                        groups.setdefault((r.temperature, r.top_k),
                                          []).append(r)
                    if greedy:
                        out.append(("greedy", 0.0, 0, greedy, None))
                    for (t, k), reqs in sorted(groups.items()):
                        out.append(("sample", t, k, reqs, None))
        gmap: Dict = {}
        for r in cons:
            fmt = r.response_format
            gmap.setdefault((fmt.kind, fmt.spec), []).append(r)
        for key in sorted(gmap):
            reqs = gmap[key]
            out.append(("per_row", None, None, reqs,
                        reqs[0].response_format))
        return out

    def _decode_bursts(self, finished: List[Request]) -> int:
        """Advance every DECODE-state request by one compiled burst —
        or, under speculative serving, by one draft-and-verify dispatch.
        Returns the decode tokens delivered; finishes append to the
        caller's (crash-safe) `finished` list.  EOS and
        max_new_tokens are truncated on host mid-burst; `max_tokens`
        bounds each row's KV lease at the request's admission reservation
        (prompt + max_new_tokens), so a full-size tail burst cannot lease
        past what the ledger promised.

        Speculative mode (`ServingConfig.speculative`): prompt-lookup
        drafts are built per request (against its own prompt + generated
        context, capped so a draft can never run past max_new_tokens)
        and a draft-coverage gate picks the group's dispatch — when
        enough live rows hold a draft (>= ~1/5, the measured span-vs-
        burst cost crossover), one verify-span dispatch serves everyone
        (the engine emits each row's accepted prefix + one bonus token;
        draftless rows advance one verified token); otherwise the group
        bursts as usual and the few drafts are discarded, so
        non-templated traffic serves exactly like spec-off — and after
        `_SPEC_BACKOFF_AFTER` consecutive rounds without ACCEPTED draft
        tokens the per-row context scans themselves back off to a
        probe every `_SPEC_PROBE_EVERY` rounds.  The verify
        span buckets per dispatch to the fixed shape set {2, 4, ...,
        span_bucket(1 + max_draft)}.  EOS inside an accepted span,
        max_new truncation, and the ledger refund on finish are handled
        by the SAME host code path as sequential bursts — a rejected
        draft changes only how many tokens arrived, never the lifecycle
        bookkeeping."""
        ready = [r for r in self.scheduler.decode_ready()
                 if r.uid in self.engine.state.seqs]
        if not ready:
            return 0
        delivered = 0
        # fresh read, NOT the post-prefill `now`: first-token sampling
        # (and its one-time compiles) ran in between, and that wall must
        # not be attributed to the first burst's tpot_burst observation
        t_prev = self.clock()
        # backoff accounting is per decode ROUND (one _decode_bursts
        # call), not per signature group: a round "succeeds" only when
        # some verify dispatch ACCEPTED tokens — a drafter that matches
        # but is always rejected must back off too, or it would replace
        # the n_steps burst with ~1-token dispatches forever
        spec_probe = (self._spec is not None
                      and (self._spec_idle < self._SPEC_BACKOFF_AFTER
                           or self._spec_idle % self._SPEC_PROBE_EVERY
                           == 0))
        spec_round_accepted = False
        for mode, temp, top_k, reqs, fmt in self._burst_groups(ready):
            if mode == "per_row":
                temp = {r.uid: r.temperature for r in reqs}
                top_k = {r.uid: r.top_k for r in reqs}
            max_toks = {r.uid: len(r.prompt) + r.max_new_tokens
                        for r in reqs}
            got = {}
            spec_stats: Dict[int, tuple] = {}
            # constrained group: resolve the shared automaton once and
            # derive each row's current FSM state by the host walk
            # (_fsm_state) — the device carries the SAME states through
            # its scan, so no state ever needs fetching back
            auto = None
            fsm_states: Optional[Dict[int, int]] = None
            if fmt is not None:
                auto = self._grammar_cache.get(fmt)
                fsm_states = {r.uid: self._fsm_state(r) for r in reqs}
            # a constrained group under speculative serving ALWAYS takes
            # the verify dispatch (probe backoff and the coverage gate
            # are bypassed): the verify program is the one that carries
            # the grammar mask, and even a draftless verify advances
            # every row one grammar-valid token for a span-2 forward
            if spec_probe or (fmt is not None and self._spec is not None):
                drafts = {
                    r.uid: self._spec.draft(
                        np.concatenate([r.prompt,
                                        np.asarray(r.generated,  # dstpu: noqa[DST001] prompt and generated are host request state (python ints / np arrays), never device values
                                                   np.int32)]),
                        # a dispatch always emits >= 1 token, so drafting
                        # past max_new_tokens - 1 remaining can only
                        # produce trimmed work
                        min(self._spec_max_draft,
                            max(r.max_new_tokens - len(r.generated) - 1,
                                0)))
                    for r in reqs}
                if auto is not None:
                    # grammar pre-filter: truncate each draft at its
                    # first out-of-grammar token (speculative.
                    # filter_draft) — one invalid draft token would
                    # forfeit the whole accepted suffix behind it, and
                    # the verify precondition (every staged draft token
                    # allowed at its span position) is what lets the
                    # host walk span states without a device fetch
                    from .speculative import filter_draft
                    for r in reqs:
                        raw = drafts[r.uid]
                        kept = filter_draft(raw, auto,
                                            fsm_states[r.uid])
                        if len(kept) < len(raw):
                            self.telemetry.count(
                                "grammar_drafts_filtered",
                                len(raw) - len(kept))
                        drafts[r.uid] = kept
                # draft-coverage gate: the group takes ONE dispatch per
                # step either way (compiled programs cost their padded
                # width, so splitting a step into burst + verify would
                # pay two full programs to advance fragments of the
                # batch).  A span dispatch costs a single forward over
                # S tokens — measured ~5x cheaper than the n_steps
                # sequential burst it replaces (and more on bandwidth-
                # bound backends, where the burst re-reads every weight
                # per token) — so verifying pays as soon as roughly
                # 1/5 of the live rows hold a draft: expected tokens
                # ~(accept * drafted_rows + draftless_rows) per ~1/5th
                # the burst's wall.  Below that, everyone keeps the
                # burst's full amortization and the few drafts are
                # discarded — non-templated traffic serves exactly like
                # spec-off.
                n_drafted_rows = sum(1 for r in reqs
                                     if len(drafts[r.uid]))
                spec_step = fmt is not None \
                    or (5 * n_drafted_rows >= len(reqs)
                        and n_drafted_rows > 0)
            else:
                spec_step = False
            if spec_step:
                # per-dispatch span bucket: the FIXED shape set
                # {2, 4, ..., span_bucket(1 + max_draft)} — a batch of
                # short drafts compiles/pays the small span, not the
                # configured maximum (ISSUE 8's draft-length bucketing)
                from .speculative import span_bucket
                span = span_bucket(1 + max(len(drafts[r.uid])
                                           for r in reqs))
                fsm_kw = {}
                if auto is not None:
                    # grammar mask rides the verify program: the host-
                    # walked span states + per-row EOS ids let the
                    # device constrain the greedy target, acceptance
                    # test, and residual/bonus draw in the SAME fused
                    # dispatch (submit() guarantees eos_token_id)
                    fsm_kw = dict(fsm=auto, fsm_states=fsm_states,
                                  fsm_eos={r.uid: r.eos_token_id
                                           for r in reqs})
                verified = self.engine.decode_burst_step(
                    uids=[r.uid for r in reqs], mode=mode,
                    temperature=temp, top_k=top_k, max_tokens=max_toks,
                    drafts=drafts, draft_span=span, **fsm_kw)
                for uid, (toks, n_drafted, n_accepted) in \
                        verified.items():
                    got[uid] = toks
                    spec_stats[uid] = (n_drafted, n_accepted)
                # adaptive-drafter feedback (DraftSource.observe): the
                # dispatch's aggregate drafted vs accepted counts
                n_acc_total = sum(a for _, a in spec_stats.values())
                self._spec.observe(
                    sum(d for d, _ in spec_stats.values()),
                    n_acc_total)
                spec_round_accepted = spec_round_accepted \
                    or n_acc_total > 0
            else:
                burst_kw = {}
                if mode != "greedy" and getattr(
                        self.engine, "supports_seeded_sampling", False):
                    # per-request counter-based sampling streams: the
                    # engine draws row uid's token at generated index
                    # seed_positions[uid] + j from seeded_sample(seed,
                    # position) — replay-deterministic across failover
                    seeds = {r.uid: int(r.seed) for r in reqs  # dstpu: noqa[DST001] Request.seed is a host python int
                             if r.seed is not None and r.temperature > 0}
                    if seeds:
                        burst_kw["seeds"] = seeds
                        burst_kw["seed_positions"] = {
                            r.uid: len(r.generated) for r in reqs
                            if r.uid in seeds}
                if self._group_k > 1 or auto is not None:
                    # step-group path: k decode steps in ONE compiled
                    # dispatch with on-device sampling AND termination
                    # (EOS / budget rows stop inside the scan) — the
                    # host sees exactly one packed fetch per group.
                    # Sampling is always per-row on this path, so the
                    # signature grouping collapses to row dicts (greedy
                    # rows ride as temperature 0 = argmax); EOS lands
                    # on device so the host loop below only re-confirms.
                    # Constrained groups take this path even at
                    # group_k == 1 (k = the burst width): the scan body
                    # is where the FSM mask and in-scan state advance
                    # live, so k constrained steps stay ONE dispatch
                    # with zero added host round trips
                    mkw = dict(burst_kw)
                    if auto is not None:
                        mkw.update(fsm=auto, fsm_states=fsm_states)
                    got.update(self.engine.decode_multi_step(
                        uids=[r.uid for r in reqs],
                        k=(self._group_k if self._group_k > 1
                           else self._burst_n),
                        temperature={r.uid: r.temperature for r in reqs},
                        top_k={r.uid: r.top_k for r in reqs},
                        max_tokens=max_toks,
                        eos_ids={r.uid: r.eos_token_id for r in reqs
                                 if r.eos_token_id is not None},
                        **mkw))
                else:
                    got.update(self.engine.decode_burst_step(
                        uids=[r.uid for r in reqs],
                        n_steps=self._burst_n,
                        mode=mode, temperature=temp, top_k=top_k,
                        max_tokens=max_toks, **burst_kw))
            now = self.clock()
            burst_toks = 0
            for req in reqs:
                toks = got.get(req.uid)
                if toks is None:
                    continue
                if req.uid in spec_stats:
                    n_drafted, n_accepted = spec_stats[req.uid]
                    req.drafted_tokens += n_drafted
                    req.accepted_tokens += n_accepted
                    self.telemetry.record_spec(n_drafted, n_accepted,
                                               len(toks))
                    if req.trace is not None:
                        req.trace.span("spec_verify", t_prev, now,
                                       tokens=len(toks),
                                       drafted=n_drafted,
                                       accepted=n_accepted)
                elif req.trace is not None:
                    req.trace.span("decode_burst", t_prev, now,
                                   tokens=len(toks))
                done = False
                for tok in toks:
                    tok = int(tok)
                    req.generated.append(tok)
                    burst_toks += 1
                    if ((req.eos_token_id is not None
                         and tok == req.eos_token_id)
                            or len(req.generated) >= req.max_new_tokens):
                        done = True
                        break
                # one stream emission per burst/verify-span boundary —
                # BEFORE the finish below closes the stream, so the
                # final tokens are delivered, then the close wakes
                # consumers with the terminal state
                self._emit_stream(req, now)
                if done:
                    # mid-burst truncation: over-generated tokens were
                    # dropped above; _finish flushes their KV and
                    # debits the ledger
                    self._finish(req, now, finished)
            self.telemetry.record_burst(now - t_prev, burst_toks)
            delivered += burst_toks
            t_prev = now
        if self._spec is not None:
            # a round with accepted draft tokens resets the backoff; a
            # round that matched nothing, failed the gate, was skipped,
            # or verified-and-rejected everything extends it
            self._spec_idle = (0 if spec_round_accepted
                               else self._spec_idle + 1)
        return delivered

    def take_finished_backlog(self) -> List[Request]:
        """Requests finalized by a step that later RAISED: terminal
        states are set and waiters resolved, but they were never
        returned to the step() caller.  The fleet router drains this
        after catching a step error — the replica may never step
        successfully again (automatic failover), and a closed-loop
        driver keyed on step() completions must still see them."""
        out, self._finished_backlog = self._finished_backlog, []
        return out

    def run_until_idle(self, max_steps: Optional[int] = None
                       ) -> List[Request]:
        """Step until no queued or active work remains.  `max_steps` is a
        liveness bound: exceeding it raises (a starved/stuck request is a
        bug, not a hang)."""
        finished: List[Request] = []
        steps = 0
        while self.has_work:
            if max_steps is not None and steps >= max_steps:
                stuck = ([r.uid for r in self.scheduler.active.values()]
                         + [r.uid for r in
                            self.scheduler.queued_requests()])
                raise RuntimeError(
                    f"serve loop still has work after {max_steps} steps "
                    f"(requests {stuck}): starvation or scheduling bug")
            finished.extend(self.step())
            steps += 1
        return finished

    # -- adapter pool (serving/tenancy) ------------------------------------
    @property
    def adapter_pool(self):
        """The loop's `AdapterPool` (None unless
        `ServingConfig.tenancy.adapter_pool_blocks` > 0) — residency
        snapshots for fleet routing ride `adapter_pool.snapshot()`."""
        return self._pool

    def register_adapter(self, adapter_id: str, a, b,
                         scaling: float = 1.0) -> None:
        """Install a LoRA adapter into this replica's pool (a: [L, K, r]
        down factors, b: [L, r, H] up factors; `scaling` folds alpha/r
        into b).  Requests then decode through it via
        `submit(..., adapter_id=...)`."""
        if self._pool is None:
            raise ValueError(
                "this loop serves no adapter pool: set "
                "ServingConfig.tenancy.adapter_pool_blocks > 0 (and "
                "tenancy.enabled) to serve LoRA adapters")
        self._pool.register(adapter_id, a, b, scaling=scaling)

    def _release_adapter(self, uid: int) -> None:
        """Drop the adapter reservation admission took for `uid` (no-op
        for base-model requests).  Paired with every `_reserved` debit."""
        aid = self._adapter_held.pop(uid, None)
        if aid is not None:
            self._pool.release(aid)

    # -- expert pool (serving/experts) -------------------------------------
    @property
    def expert_pool(self):
        """The loop's `ExpertPool` (None unless `ServingConfig.moe` is
        enabled) — residency control + the serving/expert/* gauge
        source."""
        return self._expert_pool

    # -- KV reservation ---------------------------------------------------
    def _blocks_needed(self, req: Request):
        """Blocks the request's whole lifetime holds at most, as the
        engine counts them (an int, or one count a kind)."""
        if self._role == "prefill":
            # disagg prefill pool: decode runs on ANOTHER replica's
            # arena after the handoff, so only the prompt's blocks are
            # ever leased here — reserving the decode budget too would
            # just shrink the admission batch (the "large prefill
            # batches" lever of disaggregated serving)
            return self._count_needed(len(req.prompt))
        return self._count_needed(len(req.prompt) + req.max_new_tokens)

    def _unleased_reserve(self):
        """Blocks promised to active requests but not leased yet."""
        out = 0
        for uid, need in self._reserved.items():
            d = self.engine.state.seqs.get(uid)
            out = out + _floor0(
                need - (self._count_leased(d) if d is not None else 0))
        return out

    def _effective_tokens(self, req: Request) -> np.ndarray:
        """The token sequence admission must place for `req`: the
        prompt, plus any already-generated tokens a preemption resume
        carries (KV is a pure function of tokens and positions, so
        re-prefilling the generated prefix reproduces it bit-for-bit —
        or the swap-out stashed it in the prefix cache and admission
        re-attaches/promotes it).  Plain requests (generated empty in
        QUEUED — the only producer of a non-empty one is `preempt`;
        failover resets clear it) return the prompt unchanged."""
        if req.generated:
            return np.concatenate([req.prompt,
                                   np.asarray(req.generated, np.int32)])  # dstpu: noqa[DST001] prompt and generated are host request state (np array + python ints)
        return req.prompt

    # -- streaming --------------------------------------------------------
    def _emit_stream(self, req: Request, now: float) -> None:
        """Reconcile `req`'s token stream with its generated list: new
        tokens past the log tail are delivered (sequence number = index
        — gap-free, duplicate-free by construction), regenerated
        overlap after a failover is verified against the log and
        suppressed.  No-op with streaming off (req.stream is None) —
        the bit-for-bit parity seam."""
        stream = req.stream
        if stream is None:
            return
        before = stream.replayed_tokens
        n_new = stream.sync(req.generated)
        replayed = stream.replayed_tokens - before
        if replayed:
            self.telemetry.count("tokens_replayed", replayed)
        if n_new:
            self.telemetry.count("tokens_streamed", n_new)
            if stream.last_emit_t is not None:
                self.telemetry.record_itl(now - stream.last_emit_t,
                                          n_new)
            stream.last_emit_t = now

    # -- SLO-aware preemption ---------------------------------------------
    def _preempt_for_admission(self, now: float, n_pending: int,
                               fits, headroom) -> List[Request]:
        """Admit an URGENT head-of-queue request by preempting lower-
        priority decodes (KV swap-or-recompute).  Runs after the
        ordinary admission pass left the head queued: while the head
        (a) has produced no first token, (b) has aged past
        `urgency_fraction * ttft_slo_s`, and (c) a DECODE-state victim
        with priority >= head.priority + min_priority_gap exists, the
        victim is preempted (`_preempt_victim`) and admission retries —
        bounded by `max_victims_per_step` and an affordability guard
        (victim reservations + current headroom + the evictable cache
        must cover the head's whole-lifetime need, so a hopeless head
        cannot churn swaps for nothing).  Returns the extra requests
        admitted.  `n_pending` counts this step's already-admitted
        requests, which hold no engine slot yet."""
        cfg = self._preempt_cfg
        out: List[Request] = []
        victims = 0
        while victims < cfg.max_victims_per_step:
            head = self.scheduler.peek_head()
            if head is None:
                break
            if head.first_token_time is not None:
                break      # a resumed victim: its TTFT already happened
            if now - head.arrival_time \
                    < cfg.ttft_slo_s * cfg.urgency_fraction:
                break
            cands = [r for r in self.scheduler.active.values()
                     if r.state is RequestState.DECODE
                     and r.priority >= head.priority
                     + cfg.min_priority_gap]
            if not cands:
                break
            # victim order: lowest priority first, youngest within the
            # class (the least-progressed obligation goes first).  The
            # affordability guard below sums reservations in THIS
            # order — the victims that would actually be preempted —
            # so it can never green-light a swap whose freed blocks
            # cannot admit the head (the churn it exists to prevent)
            if self._tenancy is not None:
                # priced preemption: within a priority class, a
                # low-weight tenant's decodes are the cheap victims
                # (1/weight ranks heavier tenants later), so paying
                # for share also buys preemption shelter — same
                # youngest-first tiebreak inside a (priority, weight)
                # class
                cands.sort(
                    key=lambda r: (r.priority,
                                   1.0 / self.scheduler.weight_of(r.tenant),
                                   r._arrival_seq or 0),
                    reverse=True)
            else:
                cands.sort(key=lambda r: (r.priority,
                                          r._arrival_seq or 0),
                           reverse=True)
            need = self._blocks_needed(head)
            avail = (_floor0(headroom[0])
                     + sum(self._reserved.get(r.uid, 0) for r in
                           cands[:cfg.max_victims_per_step - victims]))
            if self._cache is not None:
                # credit what the head would NOT draw from the free
                # list: a covered prefix (shared/pinned blocks are in
                # neither headroom nor evictable_blocks, exactly like
                # fits()'s own pre-check) plus the evictable cache —
                # the residency-blind peek, optimistic like fits()'s
                avail += (self._cache.covered_tokens(
                    self._effective_tokens(head)) // self._block_size)
                avail += self._cache.evictable_blocks()
            if _short(need, avail):
                break      # preemption cannot make the head fit
            victim = cands[0]
            self._preempt_victim(victim, now)
            victims += 1
            # rebuild the admission mirror from live reads: the flush
            # returned the victim's leased blocks and its reservation
            # left the ledger (pending admits still count in full —
            # conservative, they have leased nothing yet)
            headroom[0] = (self.engine.free_blocks
                           - self._unleased_reserve())
            slots = self.engine.free_slots - n_pending - len(out)
            out.extend(self.scheduler.admit(now, slots, fits))
        return out

    def _preempt_victim(self, victim: Request, now: float) -> None:
        """Evict one DECODE-state request mid-stream, keeping its work:
        the live KV of every WRITTEN whole block (prompt + generated so
        far) is inserted into the radix prefix cache before the flush
        decrefs it (the insert-on-completion ownership seam, applied
        mid-decode) and immediately demoted through the host tier when
        one is attached (`PrefixCache.demote_prefix` — batched span IO,
        the swap-out).  Without a tier the span stays arena-resident
        (reclaimable under pressure); without a cache nothing is
        stashed and the resume recomputes via re-prefill — the
        documented recompute fallback.  The victim returns to QUEUED
        with `generated` intact (`Request.preempt`) at its original
        arrival seq, so it resumes at its old FIFO place once capacity
        returns."""
        d = self.engine.state.seqs.get(victim.uid)
        swapped = 0
        if d is not None and self._cache is not None:
            eff = self._effective_tokens(victim)
            written = min(int(getattr(d, "seen_tokens", 0)), len(eff))  # dstpu: noqa[DST001] seen_tokens is host descriptor bookkeeping (python int)
            blocks = list(getattr(d, "blocks", ()))
            if written > 0 and blocks:
                kept = self._cache.insert(eff, blocks,
                                          upto_tokens=written)
                if kept and self._tier is not None:
                    swapped = self._cache.demote_prefix(eff[:written])
        if d is not None:
            self.engine.flush(victim.uid)
        self._reserved.pop(victim.uid, None)
        # the adapter pin returns with the KV reservation: a queued
        # victim must not hold a slot hostage — its re-admission
        # re-reserves (promoting from the host tier if it spilled
        # while waiting; the never-fault contract is per-admission)
        self._release_adapter(victim.uid)
        self.scheduler.active.pop(victim.uid, None)
        victim.preempt(now)
        self.scheduler.requeue(victim)
        self._preempted_this_step += 1
        self.telemetry.count("preemptions")
        if self._tenancy is not None:
            self.telemetry.count_tenant(victim.tenant, "preempted")
        if swapped:
            self.telemetry.count("kv_swapped_out", swapped)

    # -- sampling ---------------------------------------------------------
    def _fsm_state(self, req: Request) -> int:
        """The request's current automaton state — the HOST mirror of
        the device scan carry, derived by walking the emitted tokens
        with the SAME clamp semantics (`TokenAutomaton.walk`), so the
        two trackers can never diverge and constrained decode needs no
        extra device->host fetch.  Memoized as (walked_count, state) on
        the request; a failover/preemption reset that rewinds
        `generated` invalidates the memo and the walk restarts from the
        start state (state is a pure function of the token list)."""
        auto = self._grammar_cache.get(req.response_format)
        memo = getattr(req, "_fsm_memo", None)
        toks = req.generated
        if memo is not None and memo[0] <= len(toks):
            pos, st = memo
            st = auto.walk(st, toks[pos:])
        else:
            st = auto.walk(0, toks)
        req._fsm_memo = (len(toks), st)
        return st

    def _sample(self, req: Request, logits: np.ndarray) -> int:
        """Host-side reference sampler: on the decode_burst == 1 path,
        every row whose token is not the plain argmax of its logits (a
        greedy unconstrained row takes the engine's on-device argmax —
        the same token, `LogitsRows.greedy`), and every row of an
        engine without that capability.
        Same truncation semantics as the on-device samplers: temperature
        scale, entries below the top_k-th value dropped (ties at the kth
        value survive).  A seeded request draws from its counter-based
        stream (seed, token position) instead of the loop RNG, so
        regeneration after failover reproduces the token bit-for-bit.
        A constrained request (response_format) masks to its automaton
        state's allowed tokens first — the host mirror of the device
        gather (`TokenAutomaton.host_mask`: EOS admitted in accept
        states, all-True dead-state escape), so per-step and compiled
        serving obey one grammar rule."""
        if req.response_format is not None \
                and self._grammar_cache is not None:
            auto = self._grammar_cache.get(req.response_format)
            m = auto.host_mask(self._fsm_state(req),
                               eos_id=req.eos_token_id)
            logits = np.where(m, logits, -np.inf)
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        z = logits.astype(np.float64) / req.temperature
        if req.top_k and req.top_k > 0:
            kth = np.sort(z)[-min(req.top_k, len(z))]
            z = np.where(z < kth, -np.inf, z)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        if req.seed is not None:
            from .streaming import seeded_sample
            return seeded_sample(req.seed, len(req.generated), p)
        return int(self._rng.choice(len(p), p=p))  # dstpu: noqa[DST001] numpy RandomState draw on host probabilities — no device value involved


class ThreadedServer:
    """Thin threaded frontend over `ServeLoop`: a background thread steps
    the loop while work exists and parks on a condition variable when
    idle (no polling, no sleeps).  `submit`/`cancel` are thread-safe;
    `Request.result()` blocks on the request's completion event.

    The loop thread holds the server lock for the duration of each engine
    step, so submits during a long step wait for it to finish — the
    frontend is a convenience wrapper, not a high-concurrency RPC server.
    """

    def __init__(self, engine, config: Optional[ServingConfig] = None,
                 **loop_kwargs):
        self.loop = ServeLoop(engine, config, **loop_kwargs)
        self._cond = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="deepspeed-tpu-serve")
        self._thread.start()

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._stop and not self.loop.has_work:
                    self._cond.wait()
                if self._stop:
                    self.loop.drop_pending()
                    return
                try:
                    self.loop.step()
                except Exception as e:
                    # a crashed loop must not strand blocked result()
                    # callers: finalize every queued + in-flight request
                    # FAILED with the error attached (engine state
                    # released best-effort), then surface the error
                    logger.exception("serve loop step failed; failing "
                                     "all in-flight requests")
                    self.loop.fail_all(e)
                    self._stop = True
                    raise
                finally:
                    self._cond.notify_all()

    def submit(self, prompt_tokens, **kwargs) -> Request:
        with self._cond:
            if self._stop:
                raise RuntimeError("server is shut down")
            req = self.loop.submit(prompt_tokens, **kwargs)
            self._cond.notify_all()
            return req

    def cancel(self, uid: int) -> bool:
        with self._cond:
            ok = self.loop.cancel(uid)
            self._cond.notify_all()
            return ok

    def register_adapter(self, adapter_id: str, a, b,
                         scaling: float = 1.0) -> None:
        """Thread-safe adapter registration (the loop thread touches the
        pool every step; registration must not race an install)."""
        with self._cond:
            if self._stop:
                raise RuntimeError("server is shut down")
            self.loop.register_adapter(adapter_id, a, b, scaling=scaling)
            self._cond.notify_all()

    def result(self, req: Request,
               timeout: Optional[float] = None) -> np.ndarray:
        """Block (on the request's completion event — no polling) until
        terminal and return the generated tokens; see
        `Request.result`."""
        return req.result(timeout)

    def stream(self, req: Request, start: int = 0,
               timeout: Optional[float] = None):
        """Iterate `req`'s tokens as they are emitted (exactly-once:
        gap-free, duplicate-free, survives failover/preemption).  The
        iterator blocks event-driven on the stream's condition variable
        — signaled at every emission and at finalization, the same
        no-polling discipline as `result()` — and, like `result()`,
        raises the matching RequestFailed subclass after draining a
        stream that closed non-DONE.  `start` resumes a consumer from a
        known sequence number (e.g. after a client reconnect — the log
        replays from there); `timeout` bounds each individual wait.
        Requires `ServingConfig.streaming`."""
        if req.stream is None:
            raise ValueError(
                f"request {req.uid} has no token stream: enable "
                f"ServingConfig.streaming (default-off keeps the "
                f"unstreamed loop bit-for-bit)")
        return req.stream.tokens(start, timeout=timeout)

    @property
    def telemetry(self) -> ServingTelemetry:
        return self.loop.telemetry

    def drain(self, timeout: Optional[float] = None) -> List[Request]:
        """Clean handoff (fleet failover): stop admitting, hand back the
        unserved queued requests immediately, then wait for the in-flight
        requests to finish.  Unlike `shutdown(drain=True)` — which waits
        for the QUEUE too and then kills the thread — this returns the
        queued work for the caller to re-route, keeps the loop thread
        alive to finish PREFILL/DECODE requests, and guarantees no
        accepted request is silently lost.  Returns the unserved queued
        requests (still QUEUED; re-route them via another replica's
        `adopt`)."""
        with self._cond:
            queued = self.loop.drain()
            self._cond.notify_all()
            self._cond.wait_for(lambda: not self.loop.has_work,
                                timeout=timeout)
        return queued

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the loop thread.  `drain=True` waits for queued + active
        requests to finish first; `drain=False` stops after the current
        step (in-flight requests stay unfinished)."""
        with self._cond:
            if drain:
                self._cond.wait_for(lambda: not self.loop.has_work,
                                    timeout=timeout)
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)
