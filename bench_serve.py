"""Serving benchmark: decode + prefill throughput of the ragged (paged-KV)
inference engine on the available TPU chip.

Prints one JSON line per measurement:
  {"metric", "value", "unit", "vs_recorded", ...extras}

`vs_recorded` compares against the numbers recorded when each row first
ran on v5e-1 so later rounds — and kernel-gate changes — have a stable
reference (FastGen methodology: throughput at fixed load,
blogs/deepspeed-fastgen/README.md:139).

Rows:
- decode_single_ctx2048: the round-2 measurement (8 seqs, one compiled
  decode_step per token, host loop between tokens) — kept for continuity.
- decode_burst_b8_ctx2048: the round-3 headline — `decode_tokens`
  bursts of 64 (sample -> append -> feed back on device, one host
  dispatch per 64 tokens), 8 seqs on the 5-D fused-kernel arena.
- decode_burst32_ctx2048 / _ctx8192: bursts of 32 on the MERGED arena —
  32 concurrent seqs at ctx 2048, 8 at ctx 8192, the configurations
  whose padded 5-D arenas cannot fit the chip.  Round 3 served these on
  the XLA gather path; round 4's packed-q merged kernels
  (ops/paged_merged.py) lifted both rows 6.9x (267.5 -> 1849.1 and
  67.3 -> 461.4 tok/s, hbm_util 0.19 -> 0.51).  Each decode row reports
  `hbm_util` = est. bytes-moved/s over the v5e ~819 GB/s HBM peak
  (weights once per step + live KV read per token), the number that says
  how far decode sits from its bandwidth bound.
- decode_774m_{bf16,fp8}: north-star scale (GPT-2-large) decode at
  ctx 2048, 16 seqs, full engine path (chunked blocked-flash prefill +
  fused decode); the fp8 row serves layer weights as e4m3 codes
  dequantized on use (models.transformer.quantize_serving_weights).
- prefill_ctx8192: engine-path chunked prefill; reports `mfu` vs the
  197 TFLOP/s bf16 peak.
- load_c{N}: latency-vs-load curve à la FastGen — N concurrent requests
  (prompt 512, 64 new tokens each) through generate_batch; reports
  aggregate generated tok/s and mean per-token latency.
- serve_closed_c8: closed-loop load through the serving layer
  (deepspeed_tpu.serving.ServeLoop — bounded-queue admission, request
  lifecycle, per-request SLA telemetry): 8 clients x 2 requests, mixed
  128/512-token prompts, fixed staggered first arrivals; reports
  goodput + p50/p95 TTFT and e2e latency, and FAILS if any request is
  starved, timed out, or dropped.

Full run is ~15 min on v5e-1 (compiles dominate); individual rows can be
driven via the bench_* functions directly (each builds its own engine).

Timing method: direct chained device calls, synced once per timed region;
per-call host dispatch is real serving overhead and is exactly what the
burst path amortizes.  Kernel-level comparisons should use the chained
rows.

Runs on a TPU only (`utils.tpu_claim.require_tpu`); the row functions that
tests drive directly with a tiny engine work on any backend.
"""
from __future__ import annotations

import json
import time

import numpy as np

# v5e-1 recorded baselines (date each value first produced)
RECORDED = {
    "decode_single_ctx2048": 159.6,     # 2026-07-30 (8 seqs, host loop)
    "decode_burst_b8_ctx2048": 978.4,   # 2026-07-31 (burst-64 probe)
    "decode_burst32_ctx2048": 1849.1,   # 2026-07-31 r4 (merged kernel;
                                        #   gather path was 267.5)
    "decode_burst32_ctx8192": 461.4,    # 2026-07-31 r4 (merged kernel;
                                        #   gather path was 67.3)
    "decode_774m_bf16": 995.1,          # 2026-07-31 r4 (hbm_util 0.586;
                                        #   full engine path — prefill
                                        #   kernel threshold fix)
    "decode_774m_fp8": 1030.3,          # 2026-07-31 r4b — COLUMN-granular
                                        #   fp8 (default): the per-column
                                        #   scale commutes past the matmul
                                        #   so the codes feed the dots
                                        #   directly; +3.5% over bf16.
                                        #   GROUP-granular fp8 measured
                                        #   955.3 (throughput-neutral: XLA
                                        #   materializes the dequantized
                                        #   matrices, the byte saving
                                        #   never reaches HBM)
    "prefill_ctx8192": 30816.5,         # 2026-08-01 r5b — prefill_full:
                                        #   fresh full prompts run ONE
                                        #   dense-causal-flash forward
                                        #   (the training kernel) + arena
                                        #   scatter instead of the
                                        #   per-chunk blocked kernel.
                                        #   History: 6900 (r2, chunk 256)
                                        #   -> 11600 (r4) -> 13003 (r5
                                        #   chunk 2048) -> 30817 (4.5x
                                        #   r2; mfu 0.10 -> 0.25).  A
                                        #   vmap over chunks measured
                                        #   SLOWER first (ragged_ops
                                        #   note) — the win needed the
                                        #   dense kernel, not parallel
                                        #   chunk scheduling
    # load rows run the full engine loop (one host round trip per prefill
    # step / burst); recorded for regression tracking only
    "load_c8": 63.5,                    # 2026-08-01 r5b (prefill_full
                                        #   batches all fresh prompts in
                                        #   one dense forward; was 49.4)
    "load_c32": 66.1,                   # 2026-08-01 r5b (was 38.4 —
                                        #   +72%: 32 concurrent 512-token
                                        #   prompts prefill in a couple
                                        #   of dense batched forwards)
    # p95 ms/token (fused decode, ctx 2048, burst 16; these four were
    # taken with a per-dispatch constant subtracted that bench_latency no
    # longer subtracts) — note B=16 ~= B=32: decode is in the
    # bandwidth-bound plateau, the FastGen load-curve shape
    "latency_c4": 4.745,                # 2026-08-01 r5
    "latency_c8": 8.138,                # 2026-08-01 r5
    "latency_c16": 15.486,              # 2026-08-01 r5
    "latency_c32": 16.576,              # 2026-08-01 r5
    # north-star-1.3B decode, 8 seqs ctx 2048.  Roofline note (VERDICT r4
    # Weak #6): hbm_util rises 0.586 (774M, B=16) -> 0.711 (1.3B, B=8) as
    # weight bytes grow relative to everything else, so the residual is
    # NOT proportional byte inflation (arena padding / scales) but
    # per-step fixed work — sampling + block-table/bookkeeping ops and
    # inter-step gaps inside the burst — which amortizes with model
    # scale.  fp8 pays +14.4% here vs +3.5% at 774M for the same reason:
    # at B=8 the weight stream dominates the bytes fp8 halves.
    "decode_1p3b_bf16": 770.0,          # 2026-08-01 r5
    "decode_1p3b_fp8": 881.2,           # 2026-08-01 r5
    # long-context decode: 2 seqs at ctx 16k on the merged arena (6.4 GB
    # of KV).  hbm_util 0.31 — two streams can't fill the bandwidth;
    # the row documents the regime works and what it costs per stream
    "decode_burst_ctx16k": 124.6,       # 2026-08-01 r5
    # closed-loop goodput THROUGH the serving layer (request lifecycle,
    # admission, host sampling) — 8 clients x 2 requests, 128/512
    # prompts, 16 new tokens; ttft_p50 24.2s, e2e_p50 139.7s.  Low by
    # construction: per-step full-logit host materialization + one host
    # dispatch per token (see bench_serving_closed_loop docstring); the
    # baseline the burst-integrated serve loop must beat
    "serve_closed_c8": 0.9,             # 2026-08-03 r6
    # burst-integrated serve loop (decode_burst=16, fused on-device
    # sampling under the full lifecycle).  Every row from here down was
    # recorded on the CPU BACKEND of a container with no chip (serve_closed
    # remeasured 0.89 there, so the 0.9 above was a CPU number too): raw
    # model compute dominates and the burst's host-dispatch amortization
    # cannot show.  Counts and correctness carry over; the rates do not.
    "serve_burst_c8": 0.68,             # 2026-08-03 (CPU backend)
    # radix prefix KV reuse over a shared-system-prompt stream (PR 3):
    # 16 requests (256-token shared prefix + unique 128-token tails)
    # through max_seqs=2, burst decode (decode_burst=16, comparable with
    # serve_burst_c8), identical stream cache-off vs cache-on.
    # Measured (CPU backend, same caveat as above): hit_rate 0.875 (only
    # the 2-request first admission wave can miss), prefill tokens saved
    # 3584/6144 = 58.3%, outputs bit-for-bit identical, zero leaked
    # blocks; vs the same driver cache-off: goodput 0.48 vs 0.42 and
    # ttft_p50 148.0 s -> 121.5 s — the skipped shared-prefix prefill
    # lands directly on TTFT and completion time.  Hit rate and prefill
    # reduction are backend-independent; absolute times are not.
    # v5e-1 number pending.
    "serve_prefix_c8": 0.48,            # 2026-08-03 (CPU backend)
    # cache-aware fleet routing (PR 5, serving/fleet): the shared-
    # system-prompt closed loop on TWO replicas, identical stream
    # cache-aware vs round-robin.  Measured (CPU backend, same caveat):
    # fleet hit rate 16/17 = 0.941 vs round-robin's 14/17 = 0.824
    # (round-robin pays a cold prefill per replica — and its second
    # concurrent admission on the cold replica misses too, since the
    # cache inserts at flush), prefill tokens 2432 vs 2944, outputs
    # bit-for-bit, zero lost, audit clean per replica.  Goodput 0.45 vs
    # round-robin 0.46: cache affinity concentrates the stream on the
    # owning replica, and on this compute-bound CPU backend the idle
    # second replica costs about what the saved prefill buys —
    # hit-rate/prefill wins are backend-independent, the goodput win
    # needs the prefill-bound regime of a chip; not measured there.
    "serve_fleet_c8x2": 0.45,           # 2026-08-03 (CPU backend)
    # speculative decoding (ISSUE 8, serving/speculative.py): templated
    # greedy stream (shared 192-token template + 16-token unique slots)
    # served spec-off vs spec-on over the IDENTICAL stream,
    # decode_burst=16 both ways, tiny-GPT-2 f32 (see the function
    # docstring for why this row runs tiny/f32 on this CPU backend).
    # Measured 2026-08-03, two runs: decode 1.93x / 2.01x spec-off's
    # decode tok/s (1136 vs 589; the verify span moves the weights once
    # and gathers each row's paged KV once per layer for up to 16
    # tokens, where the sequential burst pays per token), acceptance
    # 0.675, 9.16 effective tokens per request-dispatch, goodput 903 vs
    # 525 (1.72x), outputs bit-for-bit, zero lost, zero leaked blocks
    # (all three asserted in-row).  ABSOLUTE tok/s on this shared-host
    # container swings +-30% run to run (a third run: 606 goodput,
    # in-row decode ratio 2.28x) — the within-run off/on ratio is the
    # stable number (1.93 / 2.01 / 2.28 across three runs), which is
    # why the row measures both arms in one process back-to-back.  GPT-2-small at the same stream
    # measured 1.10-1.14x only: its 50k-vocab chains keep breaking
    # their repetition (acceptance 0.85 -> 0.66 as new_tokens grows),
    # so less of the stream is draftable — the speedup tracks traffic
    # draftability, which is the designed behavior (the coverage gate
    # keeps undraftable stretches on the plain burst).  Value = spec-on
    # goodput; v5e-1 pending.
    "serve_spec_c8": 903.1,             # 2026-08-03 (CPU backend)
    # fleet chaos (ISSUE 7, serving/fleet supervisor): the mixed
    # shared-prefix + stranger closed loop on THREE replicas with
    # replica 1 killed mid-stream by injected step faults.  Measured
    # (CPU backend, same caveat): exactly 1 AUTOMATIC failover per run
    # (heartbeat demotion -> drain/adopt, no operator call), 16/16
    # requests DONE, zero waiters stranded, zero leaked blocks on the
    # survivors, outputs bit-for-bit across routing policies, fleet hit
    # rate 0.471 vs round-robin 0.235 (prefill tokens 4480 vs 5504) —
    # cache affinity survives the death because the victim carries
    # stranger traffic while the prefix owner keeps serving.  Goodput
    # 0.38 vs 0.40 round-robin: the chaos run measures robustness, not
    # speed, on this compute-bound backend; v5e-1 number pending.
    "serve_fleet_chaos_c8x3": 0.38,     # 2026-08-03 (CPU backend)
    # disaggregated prefill/decode (ISSUE 9, serving/fleet/disagg): a
    # mixed long-prompt/long-decode closed loop (8 clients x 2, 513/129
    # prompts alternating, 48 new tokens each, tiny f32 — the
    # serve_spec_c8 CPU-measurability + bitwise-stability choices) on
    # THREE replicas, unified vs 1-prefill + 2-decode disaggregated
    # over the IDENTICAL stream.  Measured (CPU backend, same caveat):
    # decode TPOT p95 31.6 ms vs unified 41.8 ms (p50 27.8 vs 35.2) —
    # the interference win, directly: unified decode absorbs other
    # requests' 256-token prefill chunks between bursts, disagg decode
    # replicas only ever prefill sub-block handoff tails; outputs
    # bit-for-bit between the arms, 16/16 DONE, zero leaked blocks on
    # all six engines, 16 handoffs (80 blocks, 41.9 MB raw wire, 0
    # cold fallbacks).  The trade is visible too: ttft_p95 1915 ms vs
    # 1240 ms (one prefill replica serializes admission waves) and
    # goodput 135.3 vs 147.5 on this COMPUTE-bound backend, where
    # devoting 1/3 of the fleet's compute to prefill-only costs more
    # than the interference it removes — the regime disagg exists for
    # is prefill-bound/bandwidth-bound serving (DistServe's setting),
    # where TPOT p95 is the SLA that pays.
    # Value = disagg goodput; v5e-1 re-measure pending (ROADMAP).
    "serve_disagg_c8x3": 135.3,         # 2026-08-03 (CPU backend)
    # sub-2048-key arena through the full-range fused kernels (the
    # budget the retired 2048-key auto-gate served via the dense XLA
    # gather).  CPU backend: both arms run the same dense path (the
    # platform gate keeps kernels off), so the number documents
    # bit-for-bit parity + zero loss/leaks; dense arm measured 190.3
    # in the same run (within this container's +-30% noise — same
    # program).  The kernel-vs-gather delta is a v5e re-measure.
    "serve_smallctx_c8": 225.3,         # 2026-08-04 r7 (CPU backend)
    # tensor-parallel serving (ISSUE 12, ops/tp_matmul.py +
    # inference/v2/tp_ragged.py): the greedy closed loop served tp=1 vs
    # tp=2 stock-XLA collectives vs tp=2 fused ring compute-collective
    # matmuls, on a forced 2-virtual-device CPU host mesh (this
    # container has no TPU; the row re-execs itself onto the mesh).
    # Measured 2026-08-04: outputs BIT-FOR-BIT identical across all
    # three arms (tiny f32), zero lost, zero leaked; goodput 192.3
    # fused vs 250.6 xla vs 145.2 tp1.  On this 1-hop virtual mesh the
    # ring decomposition only adds launch overhead vs the monolithic
    # collective (wire bytes are IDENTICAL — comms_bench --tp-inference
    # measures both) and collectives cost ~nothing, so fused-vs-xla
    # wall time here documents parity, not the win: the overlap the
    # fused schedule exists for (permute hops hidden behind matmul
    # tiles) only shows on real ICI, where tpu_hlo_check.
    # check_tp_fused_overlap asserts it structurally.  Value = fused
    # arm goodput; v5e multi-chip re-measure in the ROADMAP ledger.
    "serve_tp_c2": 192.3,               # 2026-08-04 (CPU backend, 2-dev
                                        #   forced host mesh)
    # open-loop observatory rows (ISSUE 13, serving/observatory):
    # VIRTUAL-time tok/s — the serve FakeClock advances 1 s per serve
    # step, so these are deterministic queueing measurements (seeded
    # workload, bit-stable outputs asserted across arms + replay), not
    # wall-time throughput.  serve_openloop_c8: one rho=0.85 Poisson
    # arm with shared-prefix (hit rate 0.344) + priority mixes, metric
    # time series + recompile flight recorder armed (7 cold compiles
    # counted + census-attributed on a cold process, 0 warm).
    # serve_openloop_sweep: the rho ramp {0.3..3.5} over the measured
    # service rate (2.29 req/vs) — goodput ramps to a 24.6 plateau at
    # capacity, queue-depth peak monotone, TTFT SLA onset at rho 2.2: the
    # queueing-collapse knee closed loops cannot show.  Values are
    # backend-dependent only through the admission/batching mechanics
    # (tiny f32 model); re-measure on v5e in measured-wall mode
    # (OpenLoopDriver(step_dt=None)) for real-time SLAs.
    "serve_openloop_c8": 15.5,          # 2026-08-04 (CPU backend,
                                        #   virtual time)
    "serve_openloop_sweep": 24.6,       # 2026-08-04 (CPU backend,
                                        #   virtual time)
    # KV-cache tiering (ISSUE 14, serving/kv_tier.py): the HBM -> host
    # spill tier behind the radix prefix cache.  serve_tier_c8:
    # rotating 4-group shared prefixes through a 6-block HBM cache —
    # the HBM-only arm's LRU churns every group out before reuse (hit
    # rate 0.0), the tiered arm demotes those evictions and promotes
    # on the next group hit: hit rate 0.75, prefill tokens 1536 vs
    # 3072, outputs bit-for-bit across cache-off/HBM/tiered arms
    # (quant="none" spill is raw bytes), zero leaked blocks in both
    # tiers.  Goodput on this COMPUTE-bound CPU backend is ~NEUTRAL vs
    # HBM-only (57.8 vs 71.9 here, inside the container's +-30% wall
    # noise band across runs) because a CPU "promotion" is a memcpy
    # and prefill compute is nearly free per token — the hit-rate /
    # prefill-token wins are the backend-independent measurement, and
    # the regime the tier exists for is prefill-bound serving where
    # each saved prefill token is real accelerator time.  The
    # serve_openloop_tier sweep shows exactly that on deterministic
    # virtual time with a 128-token/step prefill cap: identical
    # arrival schedules, HBM-only collapses at rho 2.4 (32 TTFT SLA
    # violations, queue peak 24, p95 19 vs) while the tiered arm
    # serves the same schedule violation-FREE (p95 8 vs, queue peak
    # 14, goodput 11.2 vs 7.9) — the SLA knee moved right past the
    # measured ramp.  v5e-1 numbers pending.
    "serve_tier_c8": 57.8,              # 2026-08-04 (CPU backend)
    "serve_openloop_tier": 11.2,        # 2026-08-04 (CPU backend,
                                        #   virtual time)
    # ISSUE 15 rows (r08, tiny f32).  serve_stream_c8: the measurement
    # is the delivery contract, not the wall — bit-for-bit outputs
    # streaming on vs off, every consumer's sequence exactly its
    # request's output; ITL p50 9.3 ms is the consumer-experienced
    # burst gap on this CPU backend, and the reported wall overhead is
    # within this container's +-30% shared-host swing (trust the
    # contract asserts, not the walls).  serve_preempt_openloop
    # (virtual time, rho 2 burst mix): preemption ON turned 3
    # high-priority TTFT SLA violations into 0 on the identical
    # schedule (p95 3.0 -> 1.55 vs) with 3 preemptions, 2 live KV
    # blocks swapped out AND back in through the host tier, zero lost
    # requests, zero leaked blocks, outputs bit-identical across arms
    # — goodput unchanged (27.6 vs): preemption moves WHEN work runs,
    # never how much.  v5e-1 numbers pending.
    "serve_stream_c8": 143.8,           # 2026-08-04 (CPU backend)
    "serve_preempt_openloop": 27.6,     # 2026-08-04 (CPU backend,
                                        #   virtual time)
    # ISSUE 16 rows (multi-tenant serving, tiny f32).  serve_tenants_c8
    # (closed loop): 3 tenants' LoRA adapters through a 2-slot paged
    # pool + host spill tier — 4 demotes / 3 promotes exercised, zero
    # drops, adapter_id=None rows bit-for-bit the plain loop, adapter
    # rows diverge, pool audit + zero pinned reservations after drain;
    # goodput 16.2 vs plain 20.8 on this COMPUTE-bound CPU backend
    # (each resident-set change recompiles nothing but re-binds the
    # slot stacks; the gather epilogue's cost is the measurement on a
    # chip, the contract asserts are the measurement here).
    # serve_tenants_openloop (virtual time, rho 2.5, 3-tenant Zipf mix,
    # 25% LoRA traffic): t2 rate-limited to mu/4 shed 8/13 offered with
    # its 5 admissions inside the token-bucket bound and every shed
    # accounted; WFQ weight 4 on t0 turned 4 t0 TTFT SLA violations
    # into 0 on the identical schedule (p95 4.0 -> 1.0 vs) with
    # BIT-IDENTICAL outputs across arms — fairness moves WHEN a request
    # admits, never the math — and goodput unchanged (23.6 both arms:
    # work-conserving).  v5e-1 numbers pending.
    "serve_tenants_c8": 16.2,           # 2026-08-06 (CPU backend)
    "serve_tenants_openloop": 23.6,     # 2026-08-06 (CPU backend,
                                        #   virtual time)
    # ISSUE 17 row (r10, tiny f32).  serve_multistep_c8: K decode steps
    # per compiled dispatch with on-device sampling + termination — the
    # measurement is the TRANSFER ledger, which is backend-independent:
    # explicit d2h fetches per generated token 0.25 (k=1 per-token
    # loop) -> 0.047 (k=8 step groups), a 5.3x drop (>= 4x asserted
    # in-row), outputs bit-for-bit across k in {1, 8, 16}, zero
    # loss/leaks per arm.  Goodput moved 54.7 -> 58.6 tok/s on this
    # COMPUTE-bound CPU container (each fetch here is cheap shared
    # memory); on a real TPU each counted fetch is a dispatch-pipeline
    # stall, which is where the ledger's 5.3x pays.  v5e-1 numbers
    # pending.
    "serve_multistep_c8": 58.6,         # 2026-08-07 (CPU backend)
    # ISSUE 18 row (r11, tiny f32).  serve_grammar_c8: grammar-
    # constrained decode through multi-step groups — per-row FSM state
    # rides the scan carry, masks applied on device, so the measurement
    # is again the backend-independent TRANSFER ledger: explicit d2h
    # fetches per generated token IDENTICAL constrained vs plain on
    # the same dispatch schedule (zero added host round trips — the
    # grammar costs dispatches nothing), every constrained chain
    # machine-checked against its source automaton, unconstrained rows
    # bit-for-bit the grammar-off arm, zero loss/leaks per arm.
    # Measured d2h per multi-step dispatch: [1] on BOTH arms.
    # Constrained-arm goodput carries the usual CPU-backend caveat;
    # the masked rows EOS at ~18 chars of canonical JSON (the grammar
    # forces short valid objects from random prompts), so 33.4 vs the
    # plain arm's 48.4 is early termination shrinking the batch, not
    # mask overhead — per-dispatch transfer cost is the invariant this
    # row locks.  v5e-1 numbers pending.
    "serve_grammar_c8": 33.4,           # 2026-08-07 (CPU backend)
    # ISSUE 20 row (r12, qwen_v2_moe tiny f32).  serve_moe_c8:
    # expert-paged decode — expert FFN weights live in slotted HBM
    # pages (serving/experts.py ExpertPool, the AdapterPool residency
    # discipline applied to experts), demoted to canonical host copies
    # and promoted back on demand, with the router census rider
    # feeding rebalance.  The measured contract is bit-exactness, not
    # wall time: paged tokens bit-for-bit the moe=None arm across a
    # full demote+promote cycle of every demotable expert in every
    # layer (8 demotes + 8 promotes on this 4-expert/top-2/4-layer
    # model), zero router drops, conservation audit green in every
    # phase, zero pins after drain, zero loss/leaks both arms.
    # Goodput 29.0 vs 29.7 moe-off on this CPU container — residency
    # bookkeeping costs ~2% here; on a real TPU the pool is what lets
    # an over-provisioned expert set serve from bounded HBM at all.
    # v5e-1 numbers pending.
    "serve_moe_c8": 29.0,               # 2026-08-07 (CPU backend)
}


def _peak(name: str) -> float:
    """Peak rate of the ATTACHED device (utils.device: one table keyed by
    device_kind; an unknown kind raises)."""
    from deepspeed_tpu.utils.device import device_peaks
    return device_peaks()[name]


def _engine(ctx_budget: int, max_seqs: int = 8, decode_burst: int = 32,
            size: str = "medium", weights: str = "bf16",
            prefill_chunk: int = 256, full_prompt_prefill: bool = True,
            dtype=None, attn_impl: str = "auto",
            tensor_parallel_size: int = 1, tp_collectives: str = "xla"):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import Transformer, gpt2_config
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    dtype = dtype or jnp.bfloat16
    cfg = gpt2_config(size, max_seq_len=max(ctx_budget, 1024),
                      dtype=dtype, attn_impl=attn_impl)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(dtype), params)
    if weights == "fp8":
        from deepspeed_tpu.models.transformer import quantize_serving_weights
        params = quantize_serving_weights(params)
    blocks_per_seq = ctx_budget // 64
    ecfg = RaggedInferenceEngineConfig(
        num_blocks=max_seqs * blocks_per_seq + 8, block_size=64,
        max_blocks_per_seq=blocks_per_seq, max_seqs=max_seqs,
        prefill_chunk_size=prefill_chunk, max_prefill_tokens_per_step=8192,
        decode_burst=decode_burst,
        full_prompt_prefill=full_prompt_prefill,
        tensor_parallel_size=tensor_parallel_size,
        tp_collectives=tp_collectives)
    return InferenceEngineV2(model, params=params, config=ecfg), cfg


def _decode_bytes_per_step(cfg, B: int, ctx: int,
                           weights: str = "bf16") -> float:
    """Estimated HBM bytes one decode step must move: every weight once
    (batch reuses them) + each sequence's live K/V pages once."""
    layer_param = cfg.num_layers * 12 * cfg.hidden_size ** 2
    embed_param = 2 * cfg.vocab_size * cfg.hidden_size
    # column-granular fp8 (the default): codes feed the dots directly,
    # so layer weights move 1 byte/param (+ negligible per-column scales)
    if weights == "fp8":
        param_bytes = layer_param * 1 + 2 * embed_param
    else:
        param_bytes = 2 * (layer_param + embed_param)
    kv_bytes = B * ctx * cfg.num_layers * 2 * (
        cfg.kv_heads * cfg.head_dim) * 2
    return param_bytes + kv_bytes


def _fill(eng, cfg, B, ctx, seed=0):
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, ctx - 80).astype(np.int32)
               for _ in range(B)]
    out = eng.put(list(range(B)), prompts)
    while len(out) < B:
        out.update(eng.step())
    import jax.numpy as jnp
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, B), jnp.int32)
    lens = jnp.asarray([ctx - 80] * B, jnp.int32)
    tables = jnp.asarray(np.stack(
        [eng.state.block_table(eng.state.seqs[u]) for u in range(B)]))
    active = jnp.ones(B, bool)
    return tokens, lens, tables, active


def bench_decode_single(ctx: int, B: int = 8, steps: int = 50):
    from deepspeed_tpu.inference.v2.ragged_ops import decode_step
    eng, cfg = _engine(ctx, max_seqs=B)
    tokens, lens, tables, active = _fill(eng, cfg, B, ctx)
    arena = eng.arena
    logits, _, arena = decode_step(eng.cfg, eng.params, arena, tokens, lens,
                                   tables, active)
    float(logits.sum())
    t0 = time.perf_counter()
    for _ in range(steps):
        logits, _, arena = decode_step(eng.cfg, eng.params, arena, tokens,
                                       lens, tables, active)
    float(logits.sum())
    dt = time.perf_counter() - t0
    tok_s = B * steps / dt
    util = _decode_bytes_per_step(cfg, B, ctx) * (steps / dt) / _peak("hbm_bytes_per_s")
    return tok_s, {"hbm_util": round(util, 3)}


def bench_decode_burst(ctx: int, B: int = 32, burst: int = 32,
                       rounds: int = 4, size: str = "medium",
                       weights: str = "bf16"):
    import jax
    from deepspeed_tpu.inference.v2.ragged_ops import decode_tokens
    eng, cfg = _engine(ctx, max_seqs=B, size=size, weights=weights)
    tokens, lens, tables, active = _fill(eng, cfg, B, ctx)
    arena = eng.arena
    key = jax.random.PRNGKey(0)
    toks, arena = decode_tokens(eng.cfg, eng.params, arena, tokens, lens,
                                tables, active, key, n_steps=burst)
    int(np.asarray(toks)[0, -1])
    t0 = time.perf_counter()
    for _ in range(rounds):
        toks, arena = decode_tokens(eng.cfg, eng.params, arena, tokens,
                                    lens, tables, active, key,
                                    n_steps=burst)
    int(np.asarray(toks)[0, -1])
    dt = time.perf_counter() - t0
    tok_s = B * burst * rounds / dt
    util = (_decode_bytes_per_step(cfg, B, ctx, weights)
            * (burst * rounds / dt) / _peak("hbm_bytes_per_s"))
    return tok_s, {"hbm_util": round(util, 3), "burst": burst, "seqs": B}


def bench_decode_774m(ctx: int = 2048, B: int = 16, weights: str = "bf16",
                      burst: int = 32, rounds: int = 4):
    """North-star-scale decode row (VERDICT r3 weak #3), fully through
    the engine path: real chunked prefill (the blocked-flash kernel —
    the DENSE 774M prefill program crashes this environment's remote-
    compile helper, which is why the prefill auto-threshold moved to
    2048 keys in r4) then timed on-device burst decode.  Delegates to
    bench_decode_burst so the timing methodology stays in ONE place."""
    tok_s, ex = bench_decode_burst(ctx, B=B, burst=burst, rounds=rounds,
                                   size="large", weights=weights)
    ex = dict(ex)
    ex.pop("burst", None)
    ex["weights"] = weights
    return tok_s, ex


def bench_prefill(ctx: int, rounds: int = 3):
    # one-sequence arena; the fresh full prompt rides prefill_full (the
    # dense-causal-flash fast path, default-on) — this row measures THAT
    # path; set full_prompt_prefill=False here to measure the chunked
    # SplitFuse kernel instead (recorded 13.0k at chunk 2048 / 11.6k at
    # the 256 serving default, r5)
    eng, cfg = _engine(ctx, max_seqs=1)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab_size, ctx - 8).astype(np.int32)
    out = eng.put([0], [prompt])           # warm every chunk bucket
    while 0 not in out:
        out.update(eng.step())
    eng.flush(0)
    best = 0.0
    for it in range(1, rounds + 1):
        t0 = time.perf_counter()
        out = eng.put([it], [prompt])
        while it not in out:
            out.update(eng.step())
        float(np.asarray(out[it]).sum())
        best = max(best, len(prompt) / (time.perf_counter() - t0))
        eng.flush(it)
    n_params = (cfg.num_layers * 12 * cfg.hidden_size ** 2
                + 2 * cfg.vocab_size * cfg.hidden_size)
    flops_tok = 2 * n_params + 4 * cfg.num_layers * cfg.hidden_size * ctx
    return best, {"mfu": round(best * flops_tok / _peak("bf16_flops"), 3)}


def bench_latency(B: int, burst: int = 16, reps: int = 24):
    """Token-latency percentiles at load level B (VERDICT r4 Weak #5 /
    FastGen SLA methodology, blogs/deepspeed-fastgen/README.md:139).

    Times `reps` individually-synced decode bursts; nothing is subtracted.
    A user's stream advances one token per decode step, so ms/token =
    burst wall / burst — NOT divided by B."""
    import jax
    from deepspeed_tpu.inference.v2.ragged_ops import decode_tokens
    eng, cfg = _engine(2048, max_seqs=B, decode_burst=burst)
    tokens, lens, tables, active = _fill(eng, cfg, B, 2048)
    arena = eng.arena
    key = jax.random.PRNGKey(0)
    toks, arena = decode_tokens(eng.cfg, eng.params, arena, tokens, lens,
                                tables, active, key, n_steps=burst)
    int(np.asarray(toks)[0, -1])
    per_tok = []
    for _ in range(reps):
        t0 = time.perf_counter()
        toks, arena = decode_tokens(eng.cfg, eng.params, arena, tokens,
                                    lens, tables, active, key,
                                    n_steps=burst)
        int(np.asarray(toks)[0, -1])
        per_tok.append((time.perf_counter() - t0) * 1e3 / burst)
    p50, p95 = np.percentile(per_tok, [50, 95])
    return float(p95), {"p50_ms": round(float(p50), 3),
                        "concurrency": B, "burst": burst}


# per-token p95 device latency an interactive service would budget at
# this model scale (40 tok/s per user stream); the SLA row reports the
# largest tested load still inside it — the FastGen headline shape
# (their 70B/4xA100 SLA was 4 tok/s/stream; GPT-2-medium on one v5e
# chip budgets far tighter)
SLA_MS_PER_TOK = 25.0


def bench_load(concurrency: int, prompt_len: int = 512,
               new_tokens: int = 64):
    """FastGen-style load point: `concurrency` clients each submit one
    request; report aggregate generated tok/s + mean per-token latency."""
    eng, cfg = _engine(1024, max_seqs=min(concurrency, 32),
                       decode_burst=16)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(concurrency)]
    # warm at FULL concurrency: the chunked prefill compiles one program
    # per power-of-two chunk-count bucket and the burst per decode width —
    # a single-request warm-up would leave the big buckets compiling
    # inside the timed region
    eng.generate_batch(prompts, max_new_tokens=new_tokens,
                       first_uid=10_000)
    t0 = time.perf_counter()
    outs = eng.generate_batch(prompts, max_new_tokens=new_tokens)
    dt = time.perf_counter() - t0
    gen = sum(len(o) for o in outs)
    return gen / dt, {"latency_ms_per_tok": round(dt / new_tokens * 1e3, 1),
                      "concurrency": concurrency}


def bench_serving_closed_loop(clients: int = 8, requests_per_client: int = 2,
                              new_tokens: int = 16, stagger_s: float = 0.05,
                              decode_burst: int = 1,
                              trace_overhead: bool = False,
                              observatory_overhead: bool = False,
                              size: str = "medium"):
    """Closed-loop load generator through the serving layer
    (deepspeed_tpu.serving.ServeLoop): `clients` logical clients each
    issue `requests_per_client` requests back-to-back — a client's next
    request arrives the moment its previous one completes (closed loop),
    with first arrivals on a fixed staggered schedule.  Prompts alternate
    short/long (128/512 tokens) per client so prefill and decode phases
    interleave in the ragged batch.

    Reports goodput (generated tokens of COMPLETED requests per second)
    plus p50/p95 TTFT and p50/p95 e2e latency measured by the serving
    telemetry — the FastGen SLA surface, now measured through the real
    request lifecycle (queue wait included) instead of inferred from
    kernel timings.  Raises if any request is starved, timed out, or
    dropped: the serving layer's no-silent-loss contract is part of the
    measurement.

    With `decode_burst=1` (the recorded `serve_closed_c8` baseline) the
    absolute goodput is LOW by design of what it measures: the per-step
    loop samples on host, so every serve step materializes the full
    [max_seqs, vocab] logits on the host (~3 MB/step here) and
    pays one dispatch per token — the quantified cost of per-token host
    scheduling.  `decode_burst>1` (the `serve_burst_c8` row) runs the
    SAME driver, lifecycle, and zero-loss assert through the burst serve
    loop: decode rides the engine's fused on-device-sampling program,
    one host observation per burst — closing the gap to the `load_c*`
    engine rows wherever per-token dispatch is the bound (see the
    RECORDED caveat: the CPU-backend numbers are compute-bound, so the
    two rows measure near-parity there).

    `trace_overhead=True` re-runs the identical driver with request
    tracing + the step timeline ON (serving/tracing.py) over the same
    warmed engine and records the goodput cost — asserted < 5%, the
    observe-only contract made a measured number.
    `observatory_overhead=True` does the same for the ISSUE 13 per-tick
    metric time series (`tracing.metrics_ring` — one MetricRing row per
    serve step): its goodput cost is measured against the off-run mean
    and asserted < 5% too."""
    from deepspeed_tpu.config.config import ServingConfig, TracingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    eng, cfg = _engine(1024, max_seqs=min(clients, 16),
                       decode_burst=max(decode_burst, 16), size=size)
    total = clients * requests_per_client

    def prompt_maker():
        rng = np.random.RandomState(5)

        def prompt_for(client):
            n = 512 if client % 2 else 128
            return rng.randint(0, cfg.vocab_size, n).astype(np.int32)

        return prompt_for

    prompt_for = prompt_maker()

    # warm EVERY program the timed region can hit (compiles would
    # otherwise dominate TTFT — measured ~100 s serve steps when the
    # load's batched arrivals hit cold prefill buckets).  Arrivals queue
    # behind slow steps, so prefill can run the fresh-full-prompt
    # program at any power-of-two batch bucket (NS per prompt length)
    # or the chunked program (when a same-step batch already claimed the
    # full-prompt bucket, NC buckets); the burst/decode programs and the
    # fixed-width first-token sampler warm on any wave.
    warm = ServeLoop(eng, ServingConfig(max_queue_len=4 * clients + 4,
                                        decode_burst=decode_burst))

    def warm_wave(prompts):
        for p in prompts:
            warm.submit(p, max_new_tokens=2)
        warm.run_until_idle(max_steps=4000)

    half = max(min(clients, 16) // 2, 1)
    for k in sorted({half, 2, 1}, reverse=True):
        # short prompts claim the full-prompt bucket, longs go chunked
        warm_wave([prompt_for(0) for _ in range(k)]
                  + [prompt_for(1) for _ in range(k)])
    for k in sorted({half, 2, 1}, reverse=True):
        warm_wave([prompt_for(1) for _ in range(k)])   # long-only buckets
    warm_wave([prompt_for(1), prompt_for(0)])          # short rides chunked

    def run_once(tracing):
        loop = ServeLoop(eng, ServingConfig(max_queue_len=total + 1,
                                            decode_burst=decode_burst,
                                            tracing=tracing))
        prompt_for = prompt_maker()     # identical stream every run
        remaining = {c: requests_per_client for c in range(clients)}
        owner = {}                      # uid -> client
        first_arrival = [(stagger_s * c, c) for c in range(clients)]
        t0 = time.perf_counter()

        def now():
            return time.perf_counter() - t0

        done = 0
        while done < total:
            while first_arrival and first_arrival[0][0] <= now():
                _, c = first_arrival.pop(0)
                req = loop.submit(prompt_for(c), max_new_tokens=new_tokens)
                owner[req.uid] = c
                remaining[c] -= 1
            for req in loop.step():
                done += 1
                if req.state is not RequestState.DONE:
                    raise RuntimeError(
                        f"request {req.uid} ended {req.state.value} — the "
                        f"closed loop must complete every request")
                c = owner[req.uid]
                if remaining[c] > 0:  # closed loop: next = completion
                    nxt = loop.submit(prompt_for(c),
                                      max_new_tokens=new_tokens)
                    owner[nxt.uid] = c
                    remaining[c] -= 1
            if not loop.has_work and first_arrival:
                # idle window between staggered first arrivals
                time.sleep(max(0.0, first_arrival[0][0] - now()))
        elapsed = now()
        s = loop.telemetry.summary(elapsed_s=elapsed)
        if s["completed"] != total or s["timed_out"] or s["cancelled"]:
            raise RuntimeError(f"closed loop lost requests: {s}")
        return s

    s = run_once(None)
    extras = {
        "ttft_p50_ms": round(s["ttft_p50_s"] * 1e3, 1),
        "ttft_p95_ms": round(s["ttft_p95_s"] * 1e3, 1),
        "e2e_p50_ms": round(s["e2e_p50_s"] * 1e3, 1),
        "e2e_p95_ms": round(s["e2e_p95_s"] * 1e3, 1),
        "requests": total, "clients": clients,
        "batch_occupancy_mean": round(s["batch_occupancy_mean"], 3),
        "decode_burst": decode_burst, "model": size,
    }
    if s.get("tpot_burst_p50_s") is not None:
        # burst-mode inter-token percentiles (token-weighted; one host
        # observation covers a whole burst)
        extras["tpot_burst_p50_ms"] = round(s["tpot_burst_p50_s"] * 1e3, 1)
        extras["tpot_burst_p95_ms"] = round(s["tpot_burst_p95_s"] * 1e3, 1)
    s_off2 = None
    if trace_overhead:
        # identical driver + warmed engine, tracing + step timeline ON;
        # a second tracing-off run bounds this container's run-to-run
        # noise so the overhead number compares against the off-mean
        tcfg = TracingConfig(enabled=True, step_timeline=1024)
        s_on = run_once(tcfg)
        s_off2 = run_once(None)
        off_mean = (s["goodput_tok_s"] + s_off2["goodput_tok_s"]) / 2
        overhead = 1.0 - s_on["goodput_tok_s"] / off_mean
        extras["goodput_traced"] = round(s_on["goodput_tok_s"], 2)
        extras["goodput_off_rerun"] = round(s_off2["goodput_tok_s"], 2)
        extras["trace_overhead"] = round(overhead, 4)
        if overhead >= 0.05:
            raise RuntimeError(
                f"tracing overhead {overhead:.1%} >= 5% on the closed "
                f"loop (off {off_mean:.2f} vs on "
                f"{s_on['goodput_tok_s']:.2f} tok/s): tracing must stay "
                f"observe-only cheap")
    if observatory_overhead:
        # same discipline for the per-tick metric time series: sampler
        # ON (tracing/timeline off, isolating ITS cost) vs the off-mean
        if s_off2 is None:
            s_off2 = run_once(None)
        s_obs = run_once(TracingConfig(enabled=False,
                                       metrics_ring=4096))
        off_mean = (s["goodput_tok_s"] + s_off2["goodput_tok_s"]) / 2
        overhead = 1.0 - s_obs["goodput_tok_s"] / off_mean
        extras["goodput_sampled"] = round(s_obs["goodput_tok_s"], 2)
        extras.setdefault("goodput_off_rerun",
                          round(s_off2["goodput_tok_s"], 2))
        extras["observatory_overhead"] = round(overhead, 4)
        if overhead >= 0.05:
            raise RuntimeError(
                f"observatory sampling overhead {overhead:.1%} >= 5% "
                f"on the closed loop (off {off_mean:.2f} vs sampled "
                f"{s_obs['goodput_tok_s']:.2f} tok/s): the per-tick "
                f"series must stay observe-only cheap")
    return s["goodput_tok_s"], extras


def bench_serving_prefix(clients: int = 8, requests_per_client: int = 2,
                         new_tokens: int = 8, shared_len: int = 256,
                         unique_len: int = 128, max_seqs: int = 2,
                         prefix_cache_blocks: int = 16,
                         decode_burst: int = 16):
    """Prefix KV reuse row (`serve_prefix_c8`): a shared-system-prompt
    workload — every request's prompt is one fixed `shared_len`-token
    system prefix plus a unique `unique_len`-token tail — served twice
    over the IDENTICAL request stream: once with the radix prefix cache
    off (`prefix_cache_blocks=0`) and once with it on.

    Both runs use the chunked prefill path (`full_prompt_prefill=False`)
    so the comparison is apples-to-apples: with the cache on, a matched
    request attaches the shared prefix's KV blocks read-only and chunk-
    prefills only its tail from the covered offset; with it off, every
    request chunk-prefills from position 0.  `shared_len` is a multiple
    of the 256-token chunk and the 64-token block, so suffix chunk
    boundaries line up and greedy outputs are bit-for-bit comparable.
    `max_seqs` bounds concurrency so only the first admission wave can
    miss (nothing is cached yet); every later request hits.  The small
    `prefix_cache_blocks` budget additionally exercises LRU eviction:
    unique tails churn out, the constantly re-used system prefix stays.

    Asserts the row's contract — hit rate > 0, prefill tokens reduced
    >= 50% vs cache-off, outputs bit-for-bit identical, and the block-
    conservation audit clean after the loop drains — and reports
    cache-on goodput with hit rate, saved-token fraction, and TTFT
    p50/p95 for both runs (same CPU-backend caveat as the serve rows:
    hit rate and prefill reduction are backend-independent, absolute
    times are not)."""
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    total = clients * requests_per_client
    rng = np.random.RandomState(9)
    vocab = None

    def build_prompts(cfg):
        shared = rng.randint(0, cfg.vocab_size,
                             shared_len).astype(np.int32)
        return [np.concatenate([
            shared,
            rng.randint(0, cfg.vocab_size, unique_len).astype(np.int32)])
            for _ in range(total)]

    prompts = None
    results = {}
    for label, pcb in (("off", 0), ("on", prefix_cache_blocks)):
        eng, cfg = _engine(1024, max_seqs=max_seqs,
                           decode_burst=max(decode_burst, 16),
                           full_prompt_prefill=False)
        if prompts is None:
            vocab = cfg.vocab_size
            prompts = build_prompts(cfg)
        # decode rides the fused burst path (greedy bursts are
        # deterministic, so the bit-for-bit assert still holds) — the
        # row stays comparable with serve_burst_c8
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=total + 1, prefix_cache_blocks=pcb,
            decode_burst=decode_burst, audit_blocks=True))
        t0 = time.perf_counter()
        reqs = [loop.submit(p, max_new_tokens=new_tokens) for p in prompts]
        loop.run_until_idle(max_steps=100_000)
        elapsed = time.perf_counter() - t0
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError("prefix row lost requests")
        eng.audit_blocks()            # zero leaked blocks after drain
        s = loop.telemetry.summary(elapsed_s=elapsed)
        results[label] = ([list(r.output_tokens) for r in reqs], s)

    outs_off, s_off = results["off"]
    outs_on, s_on = results["on"]
    if outs_off != outs_on:
        bad = [i for i, (a, b) in enumerate(zip(outs_off, outs_on))
               if a != b]
        raise RuntimeError(
            f"prefix cache changed outputs for requests {bad}: reuse "
            f"must be bit-for-bit (vocab {vocab})")
    hit_rate = s_on["prefix_hit_rate"] or 0.0
    if hit_rate <= 0:
        raise RuntimeError("shared-prefix workload produced no cache hits")
    total_prompt_tokens = total * (shared_len + unique_len)
    saved_frac = s_on["prefill_tokens_saved"] / total_prompt_tokens
    if saved_frac < 0.5:
        raise RuntimeError(
            f"prefill tokens reduced only {saved_frac:.0%} (< 50%) on the "
            f"shared-prefix stream")
    extras = {
        "hit_rate": round(hit_rate, 3),
        "prefill_tokens_saved": s_on["prefill_tokens_saved"],
        "prefill_saved_frac": round(saved_frac, 3),
        "prefix_cached_blocks": s_on["prefix_cached_blocks"],
        "ttft_p50_ms": round(s_on["ttft_p50_s"] * 1e3, 1),
        "ttft_p95_ms": round(s_on["ttft_p95_s"] * 1e3, 1),
        "ttft_p50_ms_cache_off": round(s_off["ttft_p50_s"] * 1e3, 1),
        "ttft_p95_ms_cache_off": round(s_off["ttft_p95_s"] * 1e3, 1),
        "goodput_cache_off": round(s_off["goodput_tok_s"], 2),
        "requests": total, "shared_len": shared_len,
        "max_seqs": max_seqs,
    }
    return s_on["goodput_tok_s"], extras


def bench_serving_tier(groups: int = 4, requests_per_group: int = 4,
                       new_tokens: int = 8, group_prefix_len: int = 128,
                       tail_len: int = 64, max_seqs: int = 2,
                       prefix_cache_blocks: int = 6,
                       host_cache_blocks: int = 64,
                       decode_burst: int = 16):
    """KV-cache tiering row (`serve_tier_c8`, ISSUE 14): a rotating
    shared-prefix workload — `groups` distinct 2-block system prompts,
    requests round-robin across them with unique 1-block tails — served
    THREE times over the IDENTICAL stream: cache off, HBM-only radix
    cache, and the cache + host spill tier (serving/kv_tier.py).

    The workload is built so the HBM budget (`prefix_cache_blocks=6`,
    vs 12 blocks of live group prefixes) cannot hold every group: by
    the time a group's prefix is reused (4 requests later), LRU churn
    has evicted it.  HBM-only evicts *to nothing* and mostly re-
    prefills; the tiered arm demotes the same evictions to host memory
    and promotes them back on the next group hit — the ZeRO-Offload
    hierarchy applied to the prefix cache, measured head-to-head.

    `prefill_chunk=64` == the block size, so a covered-offset suffix
    prefill chunks exactly like the tail of the from-zero prefill (the
    serve_prefix_c8 alignment trick) and tiny-f32 greedy outputs are
    bit-for-bit comparable across all three arms.

    Asserts the ISSUE 14 acceptance contract in-row: the tiered arm's
    prefix hit rate strictly above the HBM-only arm's, strictly fewer
    prefill tokens computed (strictly more saved), outputs bit-for-bit
    identical across ALL arms (host_cache_quant="none"), demotions AND
    promotions actually exercised, and zero leaked blocks in both
    tiers (engine.audit_blocks covers the arena and the host-span
    residency).  Value = tiered-arm goodput (CPU-backend caveat as the
    sibling rows: hit rates and token counts are backend-independent,
    absolute tok/s is not)."""
    import jax.numpy as jnp
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    total = groups * requests_per_group
    rng = np.random.RandomState(33)
    prompts = None
    results = {}
    arms = (("off", 0, 0), ("hbm", prefix_cache_blocks, 0),
            ("tiered", prefix_cache_blocks, host_cache_blocks))
    for label, pcb, hcb in arms:
        eng, cfg = _engine(1024, max_seqs=max_seqs,
                           decode_burst=max(decode_burst, 16),
                           size="tiny", dtype=jnp.float32,
                           prefill_chunk=64, full_prompt_prefill=False)
        if prompts is None:
            gp = [rng.randint(0, cfg.vocab_size,
                              group_prefix_len).astype(np.int32)
                  for _ in range(groups)]
            prompts = [np.concatenate([
                gp[i % groups],
                rng.randint(0, cfg.vocab_size,
                            tail_len).astype(np.int32)])
                for i in range(total)]
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=total + 1, prefix_cache_blocks=pcb,
            host_cache_blocks=hcb, host_cache_quant="none",
            decode_burst=decode_burst, audit_blocks=True))
        t0 = time.perf_counter()
        reqs = [loop.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        loop.run_until_idle(max_steps=100_000)
        elapsed = time.perf_counter() - t0
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError("tier row lost requests")
        eng.audit_blocks()   # zero leaks — arena AND host residency
        s = loop.telemetry.summary(elapsed_s=elapsed)
        results[label] = ([list(r.output_tokens) for r in reqs], s)

    outs_off, s_off = results["off"]
    outs_hbm, s_hbm = results["hbm"]
    outs_tier, s_tier = results["tiered"]
    for label, outs in (("hbm", outs_hbm), ("tiered", outs_tier)):
        if outs != outs_off:
            bad = [i for i, (a, b) in enumerate(zip(outs_off, outs))
                   if a != b]
            raise RuntimeError(
                f"{label} arm changed outputs for requests {bad}: "
                f"prefix reuse (and the quant='none' spill round trip) "
                f"must be bit-for-bit")
    hits_hbm = s_hbm["prefix_hits"]
    hits_tier = s_tier["prefix_hits"]
    if hits_tier <= hits_hbm:
        raise RuntimeError(
            f"tiered hit count {hits_tier} not strictly above HBM-only "
            f"{hits_hbm}: the spill tier failed to widen the cache")
    total_prompt = sum(len(p) for p in prompts)
    prefill_hbm = total_prompt - s_hbm["prefill_tokens_saved"]
    prefill_tier = total_prompt - s_tier["prefill_tokens_saved"]
    if prefill_tier >= prefill_hbm:
        raise RuntimeError(
            f"tiered arm prefilled {prefill_tier} tokens vs HBM-only "
            f"{prefill_hbm}: must be strictly fewer")
    if not (s_tier["kv_demoted_blocks"] > 0
            and s_tier["kv_promoted_blocks"] > 0):
        raise RuntimeError(
            f"tier cycle not exercised: demoted="
            f"{s_tier['kv_demoted_blocks']} promoted="
            f"{s_tier['kv_promoted_blocks']}")
    denom_h = s_hbm["prefix_hits"] + s_hbm["prefix_misses"]
    denom_t = s_tier["prefix_hits"] + s_tier["prefix_misses"]
    extras = {
        "hit_rate": round(hits_tier / denom_t, 3),
        "hit_rate_hbm_only": round(hits_hbm / denom_h, 3),
        "prefill_tokens": prefill_tier,
        "prefill_tokens_hbm_only": prefill_hbm,
        "prefill_tokens_cache_off": total_prompt,
        "kv_demoted_blocks": s_tier["kv_demoted_blocks"],
        "kv_promoted_blocks": s_tier["kv_promoted_blocks"],
        "kv_demoted_bytes": s_tier["kv_demoted_bytes"],
        "host_cached_blocks": s_tier["host_cached_blocks"],
        "goodput_hbm_only": round(s_hbm["goodput_tok_s"], 2),
        "goodput_cache_off": round(s_off["goodput_tok_s"], 2),
        "ttft_p50_ms": round(s_tier["ttft_p50_s"] * 1e3, 1),
        "ttft_p50_ms_hbm_only": round(s_hbm["ttft_p50_s"] * 1e3, 1),
        "requests": total, "groups": groups,
        "prefix_cache_blocks": prefix_cache_blocks,
        "host_cache_blocks": host_cache_blocks,
        "lost_requests": 0, "model": "tiny",
    }
    return s_tier["goodput_tok_s"], extras


def bench_serving_spec(clients: int = 8, requests_per_client: int = 2,
                       new_tokens: int = 64, template_len: int = 192,
                       slot_len: int = 16, max_seqs: int = 16,
                       decode_burst: int = 16, max_draft: int = 15,
                       ngram: int = 3, size: str = "tiny"):
    """Speculative decoding row (`serve_spec_c8`): a TEMPLATED greedy
    stream — every prompt is one fixed `template_len`-token template
    with a small unique `slot_len`-token slot (form letters, retrieval
    wrappers, few-shot scaffolds: the traffic class prompt-lookup
    drafting exists for) — served twice over the IDENTICAL request
    stream: once spec-off (the PR 2 sequential burst loop) and once with
    `ServingConfig.speculative` prompt-lookup drafts + on-device verify.
    Both runs use decode_burst=16 and the same engine geometry, so the
    only variable is the speculation itself.

    Two numeric choices keep the bit-for-bit assert testing exactly the
    verify path's contract (and nothing else):
    - `max_seqs` covers the whole stream so BOTH runs admit every
      request in ONE wave: admission timing is the one thing
      speculation moves (staggered finishes), and a second wave
      admitted at different times would prefill under different
      power-of-two batch buckets, whose bf16 logits differ by ulps (a
      measured engine-wide property of bucketed prefill, nothing
      speculative: two spec-OFF runs with different arrival timing
      diverge the same way on near-tie argmaxes).
    - the row runs **f32** weights/activations: on this CPU backend f32
      logits are measured BITWISE identical between the single-token
      decode program and the multi-token verify span, while bf16's
      per-layer rounding lets a 50k-vocab near-tie argmax flip between
      the two program shapes (~1 token in 500 on this stream — the
      same ulp class as the prefill buckets, and CPU matmuls are
      f32-native anyway).  On TPU, run the row in the serving dtype and
      expect the greedy contract to hold per compiled-shape class.

    Asserts the row's contract — greedy outputs BIT-FOR-BIT identical
    between the runs, zero lost requests, zero leaked blocks (block-
    conservation audit after drain) — and reports spec-on goodput with
    the headline comparison: decode tok/s (generated tokens over the
    decode dispatches' wall, prefill excluded) spec-on vs spec-off,
    acceptance rate, and effective tokens per verify dispatch.  The
    default tiny model keeps the two-run row CPU-measurable (the serve
    rows' medium model needs ~6 s per decode step here) AND behaves
    like genuinely templated traffic: its low-vocab greedy chains lock
    into stable repetition that prompt-lookup drafts near-perfectly,
    which is what this traffic class looks like to the drafter.
    GPT-2-small (size="small") is the harder regime — its 50k-vocab
    chains keep breaking their repetition, acceptance drops to
    ~0.66-0.85 and the speedup to ~1.1x, with the coverage gate keeping
    the undraftable stretches on the plain burst (the designed
    degradation).  The speedup mechanism — one span forward moves every
    weight once for up to max_draft+1 tokens while the sequential burst
    moves them per token — is the same at every scale, and larger
    models amortize better on bandwidth-bound backends."""
    from deepspeed_tpu.config.config import ServingConfig, SpeculativeConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    total = clients * requests_per_client
    rng = np.random.RandomState(21)
    prompts = None
    results = {}
    for label, spec in (
            ("off", None),
            ("on", SpeculativeConfig(mode="prompt_lookup", ngram=ngram,
                                     max_draft=max_draft))):
        import jax.numpy as jnp
        eng, cfg = _engine(1024, max_seqs=max_seqs,
                           decode_burst=max(decode_burst, 16), size=size,
                           dtype=jnp.float32)
        if prompts is None:
            template = rng.randint(0, cfg.vocab_size,
                                   template_len).astype(np.int32)
            prompts = [np.concatenate([
                template,
                rng.randint(0, cfg.vocab_size, slot_len).astype(np.int32)])
                for _ in range(total)]
        def stream():
            loop = ServeLoop(eng, ServingConfig(
                max_queue_len=total + 1, decode_burst=decode_burst,
                audit_blocks=True, speculative=spec))
            t0 = time.perf_counter()
            reqs = [loop.submit(p, max_new_tokens=new_tokens)
                    for p in prompts]
            loop.run_until_idle(max_steps=100_000)
            return loop, reqs, time.perf_counter() - t0

        # warm pass: greedy replay is deterministic, so running the
        # IDENTICAL stream once compiles every program the timed pass
        # will hit (prefill bucket, burst, first-token sampler, and —
        # spec-on only — each verify span bucket the stream reaches);
        # without it the spec-on run pays its extra span compiles
        # inside the measurement while spec-off does not
        stream()
        loop, reqs, elapsed = stream()
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError("speculative row lost requests")
        eng.audit_blocks()            # zero leaked blocks after drain
        s = loop.telemetry.summary(elapsed_s=elapsed)
        # decode tok/s from the burst observations: every decode/verify
        # dispatch records (wall, tokens), so this isolates the decode
        # phase both rows contend on from prefill + admission
        wall = sum(w for w, _ in loop.telemetry.burst_obs)
        toks = sum(n for _, n in loop.telemetry.burst_obs)
        decode_tok_s = toks / wall if wall > 0 else 0.0
        results[label] = ([list(r.output_tokens) for r in reqs], s,
                          decode_tok_s)

    outs_off, s_off, dec_off = results["off"]
    outs_on, s_on, dec_on = results["on"]
    if outs_off != outs_on:
        bad = [i for i, (a, b) in enumerate(zip(outs_off, outs_on))
               if a != b]
        raise RuntimeError(
            f"speculation changed greedy outputs for requests {bad}: "
            f"draft acceptance must be bit-for-bit")
    extras = {
        "decode_tok_s": round(dec_on, 2),
        "decode_tok_s_spec_off": round(dec_off, 2),
        "decode_speedup": round(dec_on / dec_off, 3) if dec_off else None,
        "acceptance_rate": (round(s_on["spec_acceptance_rate"], 3)
                            if s_on["spec_acceptance_rate"] is not None
                            else None),
        "tokens_per_dispatch": (
            round(s_on["spec_tokens_per_dispatch"], 2)
            if s_on["spec_tokens_per_dispatch"] is not None else None),
        "drafted": s_on["spec_drafted"], "accepted": s_on["spec_accepted"],
        "goodput_spec_off": round(s_off["goodput_tok_s"], 2),
        "ttft_p50_ms": round(s_on["ttft_p50_s"] * 1e3, 1),
        "e2e_p50_ms": round(s_on["e2e_p50_s"] * 1e3, 1),
        "requests": total, "new_tokens": new_tokens,
        "max_draft": max_draft, "ngram": ngram, "model": size,
    }
    return s_on["goodput_tok_s"], extras


def bench_serving_fleet(clients: int = 8, requests_per_client: int = 2,
                        new_tokens: int = 8, shared_len: int = 256,
                        unique_len: int = 128, max_seqs: int = 2,
                        prefix_cache_blocks: int = 16,
                        decode_burst: int = 16, replicas: int = 2):
    """Fleet routing row (`serve_fleet_c8x2`): the serve_prefix_c8
    shared-system-prompt workload served by a `replicas`-wide fleet
    twice over the IDENTICAL request stream — once with round-robin
    routing (the cache-blind baseline), once with cache-aware routing
    (deepspeed_tpu.serving.fleet: prefix-index snapshots + scored
    routing).

    One primer request heats the shared prefix fleet-wide, then a
    closed loop runs: each client's next request arrives when its
    previous one completes.  Round-robin pays one cold shared-prefix
    prefill PER REPLICA the stream touches; cache-aware routing steers
    every later request to the replica that already holds the prefix,
    so the fleet pays exactly ONE cold prefill total.  The flip side is
    measured too: cache affinity concentrates load on the owning
    replica (`FleetConfig.load_weight` is the knob that trades hit rate
    back toward balance).

    Asserts the acceptance contract — cache-aware fleet prefix-hit rate
    STRICTLY higher than round-robin's, total prefill tokens strictly
    lower, outputs bit-for-bit identical between the runs (greedy
    decode, same weights on every replica), zero lost requests, and a
    clean block-conservation audit on every replica after drain."""
    from deepspeed_tpu.config.config import FleetConfig, ServingConfig
    from deepspeed_tpu.serving import FleetRouter, RequestState, ServeLoop

    total = clients * requests_per_client
    rng = np.random.RandomState(13)
    prompts = None        # {(client, k): tokens}, one fixed stream
    primer_prompt = None
    results = {}
    for routing in ("round_robin", "cache_aware"):
        engines = []
        for _ in range(replicas):
            eng, cfg = _engine(1024, max_seqs=max_seqs,
                               decode_burst=max(decode_burst, 16),
                               full_prompt_prefill=False)
            engines.append(eng)
        if prompts is None:
            shared = rng.randint(0, cfg.vocab_size,
                                 shared_len).astype(np.int32)
            mk = lambda: np.concatenate([
                shared, rng.randint(0, cfg.vocab_size,
                                    unique_len).astype(np.int32)])
            primer_prompt = mk()
            prompts = {(c, k): mk() for c in range(clients)
                       for k in range(requests_per_client)}
        scfg = ServingConfig(
            max_queue_len=total + 2, prefix_cache_blocks=prefix_cache_blocks,
            decode_burst=decode_burst, audit_blocks=True,
            fleet=FleetConfig(replicas=replicas, snapshot_interval_steps=1,
                              routing=routing, prefix_weight=4.0,
                              load_weight=0.25))
        fleet = FleetRouter([ServeLoop(e, scfg) for e in engines], scfg)
        # primer: heat the shared prefix somewhere in the fleet (the
        # production steady state this row measures)
        primer = fleet.submit(primer_prompt, max_new_tokens=new_tokens)
        fleet.run_until_idle(max_steps=100_000)
        if primer.state is not RequestState.DONE:
            raise RuntimeError("fleet primer did not complete")
        t0 = time.perf_counter()
        owner = {}
        remaining = {}
        for c in range(clients):
            req = fleet.submit(prompts[(c, 0)], max_new_tokens=new_tokens)
            owner[id(req)] = (c, 0)
            remaining[c] = requests_per_client - 1
        outputs = {}
        steps = 0
        while len(outputs) < total:
            steps += 1
            if steps > 200_000:
                raise RuntimeError("fleet closed loop wedged")
            for req in fleet.step():
                key = owner.pop(id(req), None)
                if key is None:
                    continue
                if req.state is not RequestState.DONE:
                    raise RuntimeError(
                        f"fleet request {key} ended {req.state.value} — "
                        f"the closed loop must complete every request")
                outputs[key] = list(req.output_tokens)
                c = key[0]
                if remaining[c] > 0:
                    k = requests_per_client - remaining[c]
                    nxt = fleet.submit(prompts[(c, k)],
                                       max_new_tokens=new_tokens)
                    owner[id(nxt)] = (c, k)
                    remaining[c] -= 1
        elapsed = time.perf_counter() - t0
        fleet.audit()             # zero leaked blocks on every replica
        s = fleet.summary()
        # exact fleet-wide prefill accounting: every prompt token was
        # either prefilled or covered by shared prefix KV
        prompt_tokens = (total + 1) * (shared_len + unique_len)
        prefill_tokens = prompt_tokens - s["fleet_prefill_tokens_saved"]
        goodput = sum(len(o) for o in outputs.values()) / elapsed
        results[routing] = (outputs, s, prefill_tokens, goodput)

    outs_rr, s_rr, prefill_rr, _ = results["round_robin"]
    outs_ca, s_ca, prefill_ca, goodput = results["cache_aware"]
    if outs_ca != outs_rr:
        bad = [k for k in outs_rr if outs_ca.get(k) != outs_rr[k]]
        raise RuntimeError(
            f"routing changed outputs for requests {bad}: placement "
            f"must be invisible (same weights on every replica)")
    hit_ca = s_ca["fleet_prefix_hit_rate"] or 0.0
    hit_rr = s_rr["fleet_prefix_hit_rate"] or 0.0
    if not hit_ca > hit_rr:
        raise RuntimeError(
            f"cache-aware fleet hit rate {hit_ca:.3f} not above "
            f"round-robin's {hit_rr:.3f}")
    if not prefill_ca < prefill_rr:
        raise RuntimeError(
            f"cache-aware prefill tokens {prefill_ca} not below "
            f"round-robin's {prefill_rr}")
    extras = {
        "replicas": replicas, "requests": total,
        "hit_rate": round(hit_ca, 3),
        "hit_rate_round_robin": round(hit_rr, 3),
        "prefill_tokens": prefill_ca,
        "prefill_tokens_round_robin": prefill_rr,
        "routed": s_ca["routed"],
        "stale_view_corrections": s_ca["stale_view_corrections"],
        "goodput_round_robin": round(results["round_robin"][3], 2),
    }
    return goodput, extras


def bench_serving_fleet_chaos(clients: int = 8,
                              requests_per_client: int = 2,
                              new_tokens: int = 8, shared_len: int = 256,
                              unique_len: int = 128, max_seqs: int = 2,
                              prefix_cache_blocks: int = 16,
                              decode_burst: int = 4, replicas: int = 3,
                              kill_after_steps: int = 1,
                              heartbeat_timeout_s: float = 0.5,
                              failover_after_s: float = 0.5,
                              trace_out=None, size: str = "medium"):
    """Chaos row (`serve_fleet_chaos_c8x3`): the shared-system-prompt
    closed loop on THREE replicas with one replica KILLED mid-stream
    (deterministic fault injection: every step on the victim raises
    after its `kill_after_steps`-th post-primer step), served twice over
    the identical stream — cache-aware vs round-robin routing, both
    under the fleet supervisor.

    The stream is mixed, the production shape: each client alternates a
    shared-system-prompt request with a unique "stranger" request.
    Cache-aware routing concentrates the prefix stream on its owning
    replica and spreads strangers by load — so the victim (replica 1, a
    NON-owner that serves stranger traffic under both policies) dies
    holding real work while the prefix affinity survives it.

    The acceptance contract this row asserts, per ISSUE 7:
    - the supervisor detects the death and fails over AUTOMATICALLY —
      no operator `drain` call anywhere in the driver;
    - zero accepted requests are lost: every request in the closed
      stream completes DONE (in-flight work on the dead replica is
      re-queued and regenerated on the survivors);
    - every `result()` waiter resolves (`Request.finished` fleet-wide);
    - zero leaked blocks on all SURVIVING replicas (`audit_blocks`);
    - outputs are bit-for-bit identical between the two routing runs
      (greedy decode: placement, death, and retries must be invisible);
    - the cache-aware fleet's prefix-hit rate stays strictly above
      round-robin's THROUGH the replica death.

    Supervisor thresholds are tuned to the real clock this row runs on
    (steps take real seconds on CPU/TPU): error_burst=2 demotes on the
    second consecutive step error, failover fires half a second later.

    `trace_out=<path>` runs BOTH arms with request tracing on
    (serving/tracing.py — observe-only, outputs still bit-for-bit
    between arms), asserts the failed-over request's span tree crosses
    two replicas with route -> demote -> requeue -> adopt in order, and
    persists the cache-aware arm's traces as a perfetto-loadable
    Chrome-trace artifact."""
    from deepspeed_tpu.config.config import (FleetConfig, ServingConfig,
                                             SupervisorConfig,
                                             TracingConfig)
    from deepspeed_tpu.serving import (FleetRouter, RequestState,
                                       ServeLoop, write_chrome_trace)
    from deepspeed_tpu.serving.fleet.faults import (FaultInjector,
                                                    FaultPlan)

    total = clients * requests_per_client
    rng = np.random.RandomState(17)
    prompts = None
    primer_prompt = None
    results = {}
    for routing in ("round_robin", "cache_aware"):
        engines = []
        for _ in range(replicas):
            eng, cfg = _engine(1024, max_seqs=max_seqs,
                               decode_burst=max(decode_burst, 16),
                               full_prompt_prefill=False, size=size)
            engines.append(eng)
        if prompts is None:
            shared = rng.randint(0, cfg.vocab_size,
                                 shared_len).astype(np.int32)
            mk = lambda: np.concatenate([
                shared, rng.randint(0, cfg.vocab_size,
                                    unique_len).astype(np.int32)])
            stranger = lambda: rng.randint(
                0, cfg.vocab_size,
                shared_len + unique_len).astype(np.int32)
            primer_prompt = mk()
            # mixed stream: even requests share the system prompt, odd
            # ones are strangers (spread by load under cache-aware
            # routing — the victim's traffic)
            prompts = {(c, k): (mk() if k % 2 == 0 else stranger())
                       for c in range(clients)
                       for k in range(requests_per_client)}
        scfg = ServingConfig(
            max_queue_len=total + 2,
            prefix_cache_blocks=prefix_cache_blocks,
            decode_burst=decode_burst, audit_blocks=True,
            tracing=(TracingConfig(enabled=True, step_timeline=256)
                     if trace_out else None),
            fleet=FleetConfig(
                replicas=replicas, snapshot_interval_steps=1,
                routing=routing, prefix_weight=4.0, load_weight=0.25,
                supervisor=SupervisorConfig(
                    heartbeat_timeout_s=heartbeat_timeout_s,
                    error_burst=2, error_window_s=60.0,
                    failover_after_s=failover_after_s,
                    recovery_ticks=4, max_request_retries=2)))
        loops = [ServeLoop(e, scfg) for e in engines]
        fleet = FleetRouter(loops, scfg)
        primer = fleet.submit(primer_prompt, max_new_tokens=new_tokens)
        fleet.run_until_idle(max_steps=100_000)
        if primer.state is not RequestState.DONE:
            raise RuntimeError("chaos fleet primer did not complete")
        # the victim is replica 1: the primer heated the shared prefix
        # on replica 0 (deterministic tie-break), so replica 1 serves
        # stranger traffic under cache-aware routing and a 1/replicas
        # slice under round-robin — it dies HOLDING WORK either way,
        # while the prefix affinity the row measures survives.  The
        # death plan installs the moment a victim step RETURNS with
        # admitted work still in flight (fixed call indexing raced the
        # model's step speed: a fast model could finish the victim's
        # work before the scheduled kill), so the death
        # deterministically strands in-flight requests MID-DECODE and
        # exercises the re-queue/regenerate failover path, not just
        # queue re-routing; `kill_after_steps` then indexes the
        # victim's step calls from that observation.  The row's
        # decode_burst (4, vs the serve default 16) keeps decode
        # spanning several bursts per request so that mid-decode window
        # exists at every model size.
        victim = fleet.replicas[1]
        # arm the death on the victim's own step seam: the first step
        # that RETURNS with admitted work still in flight installs the
        # permanent kill, so the next call raises over stranded
        # in-flight requests no matter how fast the model steps
        _inner_step = victim.loop.step
        armed = {"killed": False}

        def _step_then_arm():
            out = _inner_step()
            if not armed["killed"] and victim.loop.scheduler.active:
                victim.loop.step = _inner_step
                FaultInjector(victim.loop, FaultPlan.replica_death(
                    max(kill_after_steps - 1, 0)))
                armed["killed"] = True
            return out

        victim.loop.step = _step_then_arm
        t0 = time.perf_counter()
        owner = {}
        remaining = {}
        arm_reqs = [primer]
        for c in range(clients):
            req = fleet.submit(prompts[(c, 0)], max_new_tokens=new_tokens)
            owner[id(req)] = (c, 0)
            remaining[c] = requests_per_client - 1
            arm_reqs.append(req)
        outputs = {}
        steps = 0
        while len(outputs) < total:
            steps += 1
            # generous guard: while the whole stream sits on the dying
            # replica, the loop spins cheap error-steps in real time
            # until the failover deadline elapses
            if steps > 2_000_000:
                raise RuntimeError("chaos closed loop wedged")
            for req in fleet.step():
                key = owner.pop(id(req), None)
                if key is None:
                    continue
                if req.state is not RequestState.DONE:
                    raise RuntimeError(
                        f"chaos request {key} ended {req.state.value} "
                        f"(uid {req.uid}) — replica death must not lose "
                        f"accepted requests")
                outputs[key] = list(req.output_tokens)
                c = key[0]
                if remaining[c] > 0:
                    k = requests_per_client - remaining[c]
                    nxt = fleet.submit(prompts[(c, k)],
                                       max_new_tokens=new_tokens)
                    owner[id(nxt)] = (c, k)
                    remaining[c] -= 1
                    arm_reqs.append(nxt)
        elapsed = time.perf_counter() - t0
        s = fleet.summary()
        if s["health"][victim.id] != "drained":
            raise RuntimeError(
                f"the supervisor never failed the dead replica over: "
                f"health={s['health']}")
        if s["health_events"]["failovers"] != 1:
            raise RuntimeError(
                f"expected exactly 1 automatic failover, got "
                f"{s['health_events']}")
        # every waiter resolved; zero leaked blocks on the survivors
        for rep in fleet.replicas:
            if rep.id != victim.id and hasattr(rep.loop.engine,
                                               "audit_blocks"):
                rep.loop.engine.audit_blocks()
        prompt_tokens = (total + 1) * (shared_len + unique_len)
        prefill_tokens = prompt_tokens - s["fleet_prefill_tokens_saved"]
        goodput = sum(len(o) for o in outputs.values()) / elapsed
        results[routing] = (outputs, s, prefill_tokens, goodput,
                            arm_reqs)

    outs_rr, s_rr, prefill_rr, _, _ = results["round_robin"]
    outs_ca, s_ca, prefill_ca, goodput, reqs_ca = results["cache_aware"]
    if outs_ca != outs_rr:
        bad = [k for k in outs_rr if outs_ca.get(k) != outs_rr[k]]
        raise RuntimeError(
            f"chaos changed outputs for requests {bad}: failover and "
            f"retries must be invisible under greedy decode")
    hit_ca = s_ca["fleet_prefix_hit_rate"] or 0.0
    hit_rr = s_rr["fleet_prefix_hit_rate"] or 0.0
    if not hit_ca > hit_rr:
        raise RuntimeError(
            f"cache-aware chaos hit rate {hit_ca:.3f} not above "
            f"round-robin's {hit_rr:.3f}")
    extras = {
        "replicas": replicas, "requests": total,
        "failovers": s_ca["health_events"]["failovers"],
        "failover_requeued": s_ca["failover_requeued"],
        "failover_failed": s_ca["failover_failed"],
        "hit_rate": round(hit_ca, 3),
        "hit_rate_round_robin": round(hit_rr, 3),
        "prefill_tokens": prefill_ca,
        "prefill_tokens_round_robin": prefill_rr,
        "goodput_round_robin": round(results["round_robin"][3], 2),
        "model": size,
    }
    if trace_out:
        # the tentpole acceptance artifact: the failed-over request's
        # span tree must cross two replicas with route -> demote ->
        # requeue -> adopt in timestamp order, and the whole arm's
        # traces load in perfetto
        failed_over = [r for r in reqs_ca
                       if r.trace is not None and r.trace.events("requeue")]
        if not failed_over:
            raise RuntimeError(
                "chaos trace: no request recorded a failover re-queue — "
                "the victim died holding no traced in-flight work")
        for r in failed_over:
            tr = r.trace
            if len(tr.replicas()) < 2:
                raise RuntimeError(
                    f"chaos trace: failed-over request {r.uid} stayed on "
                    f"{tr.replicas()} — the span tree must cross "
                    f"replicas")
            order = [e["name"] for e in tr.events()
                     if e["name"] in ("route", "demote", "requeue",
                                      "adopt")]
            want = ["route", "demote", "requeue", "adopt"]
            if order[:len(want)] != want:
                raise RuntimeError(
                    f"chaos trace: request {r.uid} failover events out "
                    f"of order: {order}")
            ts = [e["t"] for e in tr.events()]
            if ts != sorted(ts):
                raise RuntimeError(
                    f"chaos trace: request {r.uid} timestamps not "
                    f"monotone on the serve clock")
        write_chrome_trace(reqs_ca, trace_out)
        extras["trace_out"] = trace_out
        extras["traced_requests"] = sum(
            1 for r in reqs_ca if r.trace is not None)
        extras["failover_traced"] = len(failed_over)
    return goodput, extras


def bench_serving_disagg(clients: int = 8, requests_per_client: int = 2,
                         new_tokens: int = 48, long_prompt_len: int = 513,
                         short_prompt_len: int = 129, max_seqs: int = 4,
                         prefix_cache_blocks: int = 48,
                         decode_burst: int = 16, replicas: int = 3,
                         size: str = "tiny",
                         require_tpot_win: bool = True):
    """Disaggregated prefill/decode row (`serve_disagg_c8x3`): a MIXED
    long-prompt/long-decode closed-loop stream — each client alternates
    a long (`long_prompt_len`) and a short (`short_prompt_len`) prompt,
    every request decoding `new_tokens` tokens — served twice over the
    IDENTICAL stream on a `replicas`-wide fleet: once UNIFIED (every
    replica prefills and decodes) and once DISAGGREGATED (1 prefill
    replica runs prompts to completion and streams the finished KV to
    2 decode replicas through the batched migration transport;
    serving/fleet/disagg).

    The number this row exists for is decode-side interference: in the
    unified fleet a decoding request's inter-token time absorbs the
    256-token prefill chunks of whoever else is being admitted on its
    replica, while a disagg decode replica's only prefill work is the
    sub-block handoff tail (<= 1 block of tokens).  Both arms run f32
    (the serve_spec_c8 bitwise-stability choice: bf16 near-tie argmaxes
    flip between program shapes) and chunked prefill, with prompt
    lengths chosen so the handoff boundary (the last whole KV block)
    is also a chunk-aligned position — tail re-prefill then computes
    bit-identical logits and greedy outputs are comparable.

    Asserts the acceptance contract — outputs BIT-FOR-BIT identical
    between the arms, zero lost requests, zero leaked blocks on every
    replica of both fleets, and (require_tpot_win) strictly lower
    decode-pool request TPOT p95 than the unified fleet — and reports
    disagg goodput with the per-pool percentile splits, handoff
    counters, and wire accounting.  Each arm runs a warm pass over the
    identical stream first (compiles out of the timed region; the warm
    pass's cached prefixes are dropped when the timed loops re-enable
    each engine's cache)."""
    from deepspeed_tpu.config.config import (DisaggConfig, FleetConfig,
                                             ServingConfig)
    from deepspeed_tpu.serving import FleetRouter, RequestState, ServeLoop

    import jax.numpy as jnp

    total = clients * requests_per_client
    rng = np.random.RandomState(29)
    prompts = None
    results = {}
    for label in ("unified", "disagg"):
        engines = []
        for _ in range(replicas):
            eng, cfg = _engine(1024, max_seqs=max_seqs,
                               decode_burst=max(decode_burst, 16),
                               size=size, dtype=jnp.float32,
                               full_prompt_prefill=False)
            engines.append(eng)
        if prompts is None:
            mk = lambda n: rng.randint(0, cfg.vocab_size,
                                       n).astype(np.int32)
            # mixed stream: alternating long/short prompts per client,
            # every request decoding long
            prompts = {(c, k): mk(long_prompt_len if (c + k) % 2 == 0
                                  else short_prompt_len)
                       for c in range(clients)
                       for k in range(requests_per_client)}
        disagg = (DisaggConfig(prefill_replicas=1,
                               decode_replicas=replicas - 1)
                  if label == "disagg" else None)
        scfg = ServingConfig(
            max_queue_len=total + 2,
            prefix_cache_blocks=prefix_cache_blocks,
            decode_burst=decode_burst, audit_blocks=True,
            fleet=FleetConfig(replicas=replicas,
                              snapshot_interval_steps=1,
                              disagg=disagg))

        def stream():
            # fresh loops per pass: ServeLoop re-enables each engine's
            # prefix cache, which drops the previous pass's cached
            # prefixes — the timed pass starts cold like the warm one
            fleet = FleetRouter([ServeLoop(e, scfg) for e in engines],
                                scfg)
            t0 = time.perf_counter()
            owner = {}
            remaining = {}
            for c in range(clients):
                req = fleet.submit(prompts[(c, 0)],
                                   max_new_tokens=new_tokens)
                owner[id(req)] = (c, 0)
                remaining[c] = requests_per_client - 1
            outputs = {}
            steps = 0
            while len(outputs) < total:
                steps += 1
                if steps > 200_000:
                    raise RuntimeError("disagg closed loop wedged")
                for req in fleet.step():
                    key = owner.pop(id(req), None)
                    if key is None:
                        continue
                    if req.state is not RequestState.DONE:
                        raise RuntimeError(
                            f"disagg request {key} ended "
                            f"{req.state.value} — the closed loop must "
                            f"complete every request")
                    outputs[key] = list(req.output_tokens)
                    c = key[0]
                    if remaining[c] > 0:
                        k = requests_per_client - remaining[c]
                        nxt = fleet.submit(prompts[(c, k)],
                                           max_new_tokens=new_tokens)
                        owner[id(nxt)] = (c, k)
                        remaining[c] -= 1
            return fleet, outputs, time.perf_counter() - t0

        stream()                               # warm pass (compiles)
        fleet, outputs, elapsed = stream()
        fleet.audit()             # zero leaked blocks on every replica
        s = fleet.summary()
        goodput = sum(len(o) for o in outputs.values()) / elapsed
        results[label] = (outputs, s, goodput)

    outs_u, s_u, goodput_u = results["unified"]
    outs_d, s_d, goodput = results["disagg"]
    if outs_d != outs_u:
        bad = [k for k in outs_u if outs_d.get(k) != outs_u[k]]
        raise RuntimeError(
            f"disaggregation changed outputs for requests {bad}: the "
            f"handoff must be invisible under greedy decode")
    tpot_u = s_u["pools"]["unified"]["tpot_p95_s"]
    tpot_d = s_d["pools"]["decode"]["tpot_p95_s"]
    if require_tpot_win and not tpot_d < tpot_u:
        raise RuntimeError(
            f"disagg decode TPOT p95 {tpot_d:.3f}s not below the "
            f"unified fleet's {tpot_u:.3f}s: the interference win is "
            f"the row's contract")
    lost = total - sum(1 for o in outs_d.values() if o is not None)
    extras = {
        "replicas": replicas, "requests": total,
        "tpot_p95_ms": round(tpot_d * 1e3, 1),
        "tpot_p95_ms_unified": round(tpot_u * 1e3, 1),
        "tpot_p50_ms": round(
            s_d["pools"]["decode"]["tpot_p50_s"] * 1e3, 1),
        "tpot_p50_ms_unified": round(
            s_u["pools"]["unified"]["tpot_p50_s"] * 1e3, 1),
        "ttft_p95_ms": round(
            s_d["pools"]["decode"]["ttft_p95_s"] * 1e3, 1),
        "ttft_p95_ms_unified": round(
            s_u["pools"]["unified"]["ttft_p95_s"] * 1e3, 1),
        "handoffs": s_d["handoffs"],
        "handoff_blocks": s_d["handoff_blocks"],
        "handoff_bytes": s_d["handoff_bytes"],
        "handoff_cold_fallbacks": s_d["handoff_cold_fallbacks"],
        "goodput_unified": round(goodput_u, 2),
        "lost_requests": lost,
        "model": size, "new_tokens": new_tokens,
    }
    return goodput, extras


def bench_serving_smallctx(clients: int = 8, requests_per_client: int = 2,
                           new_tokens: int = 16, max_seqs: int = 4,
                           decode_burst: int = 16, size: str = "tiny"):
    """Small-context full-range-kernel row (`serve_smallctx_c8`,
    ISSUE 10): a closed-loop stream over a SUB-2048-KEY arena (1024
    keys/seq — the budget the retired auto-gate used to route onto the
    ~25x-slower dense XLA gather, and the 774M-class corner PR 2 could
    only crash-guard), served twice over the IDENTICAL stream: once on
    the default gate (the full-range fused kernels on TPU) and once on
    the explicit dense escape hatch (attn_impl="jnp").

    Asserts the acceptance contract — outputs BIT-FOR-BIT identical
    between the arms (both run f32 chunked prefill so program shapes
    align; the serve_spec_c8 bitwise-stability choice), zero lost
    requests, zero leaked blocks on both engines — and reports the
    kernel arm's goodput with the dense arm's alongside.  On a CPU
    backend both arms execute the same dense path (the platform gate,
    not the budget, keeps the kernel off), so the CPU number documents
    parity + zero-loss only; the kernel-vs-gather delta is a v5e
    re-measure (ROADMAP).  Each arm runs a warm pass first (compiles
    out of the timed region)."""
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    import jax
    import jax.numpy as jnp

    total = clients * requests_per_client
    rng = np.random.RandomState(31)
    prompts = None
    results = {}
    for label, impl in (("kernel", "auto"), ("dense", "jnp")):
        eng, cfg = _engine(1024, max_seqs=max_seqs,
                           decode_burst=max(decode_burst, 16),
                           size=size, dtype=jnp.float32,
                           full_prompt_prefill=False, attn_impl=impl)
        if prompts is None:
            # alternating 129/65-token prompts per client (well inside
            # the 1024-key lease), chunk-unaligned tails included
            mk = lambda n: rng.randint(0, cfg.vocab_size,
                                       n).astype(np.int32)
            prompts = {(c, k): mk(129 if (c + k) % 2 == 0 else 65)
                       for c in range(clients)
                       for k in range(requests_per_client)}
        scfg = ServingConfig(max_queue_len=total + 2,
                             decode_burst=decode_burst,
                             audit_blocks=True)

        def stream():
            loop = ServeLoop(eng, scfg)
            t0 = time.perf_counter()
            owner = {}
            remaining = {c: requests_per_client - 1
                         for c in range(clients)}
            for c in range(clients):
                req = loop.submit(prompts[(c, 0)],
                                  max_new_tokens=new_tokens)
                owner[id(req)] = (c, 0)
            outputs = {}
            steps = 0
            while len(outputs) < total:
                steps += 1
                if steps > 100_000:
                    raise RuntimeError("smallctx closed loop wedged")
                for req in loop.step():
                    key = owner.pop(id(req), None)
                    if key is None:
                        continue
                    if req.state is not RequestState.DONE:
                        raise RuntimeError(
                            f"smallctx request {key} ended "
                            f"{req.state.value} — the closed loop must "
                            f"complete every request")
                    outputs[key] = list(req.output_tokens)
                    c = key[0]
                    if remaining[c] > 0:
                        k = requests_per_client - remaining[c]
                        nxt = loop.submit(prompts[(c, k)],
                                          max_new_tokens=new_tokens)
                        owner[id(nxt)] = (c, k)
                        remaining[c] -= 1
            return outputs, time.perf_counter() - t0

        stream()                               # warm pass (compiles)
        outputs, elapsed = stream()
        eng.audit_blocks()                     # zero leaked blocks
        goodput = sum(len(o) for o in outputs.values()) / elapsed
        results[label] = (outputs, goodput)

    outs_k, goodput = results["kernel"]
    outs_d, goodput_d = results["dense"]
    if outs_k != outs_d:
        bad = [k for k in outs_d if outs_k.get(k) != outs_d[k]]
        raise RuntimeError(
            f"kernel arm changed outputs for requests {bad}: the "
            f"full-range kernel must be invisible under greedy decode")
    extras = {
        "requests": total, "clients": clients,
        "kv_budget_keys": 1024,
        "goodput_dense": round(goodput_d, 2),
        "lost_requests": 0,
        "backend": jax.default_backend(),
        "model": size, "new_tokens": new_tokens,
    }
    return goodput, extras


def bench_serving_tp(clients: int = 4, requests_per_client: int = 2,
                     new_tokens: int = 16, max_seqs: int = 2,
                     decode_burst: int = 16, size: str = "tiny"):
    """Tensor-parallel serving row (`serve_tp_c2`, ISSUE 12): a greedy
    closed-loop stream served THREE times over the IDENTICAL prompts —
    tp=1 (the single-device reference), tp=2 with the stock-XLA
    collectives (GSPMD all-reduce per block half), and tp=2 with the
    fused ring compute-collective matmuls (ops/tp_matmul.py through
    inference/v2/tp_ragged.py) — on a 2-device mesh.

    Asserts the acceptance contract: outputs BIT-FOR-BIT identical
    across all three arms (tiny GPT-2 in f32, the serve_spec_c8
    bitwise-stability choice), zero lost requests, zero leaked blocks
    on every engine.  Value = the fused arm's goodput; extras carry all
    three arms.  Needs two devices in THIS process (main() leaves the
    row out where there is one); on the tests' virtual CPU mesh the
    numbers document correctness + relative cost only — the overlap win
    needs real ICI (tpu_hlo_check asserts it structurally)."""
    import jax

    if len(jax.devices()) < 2:
        raise RuntimeError("serve_tp_c2 needs >= 2 devices in this process")

    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    import jax.numpy as jnp

    total = clients * requests_per_client
    rng = np.random.RandomState(37)
    prompts = None
    results = {}
    arms = (("tp1", 1, "xla"), ("tp2_xla", 2, "xla"),
            ("tp2_fused", 2, "fused"))
    for label, tp, coll in arms:
        eng, cfg = _engine(1024, max_seqs=max_seqs,
                           decode_burst=max(decode_burst, 16), size=size,
                           dtype=jnp.float32, full_prompt_prefill=False,
                           tensor_parallel_size=tp, tp_collectives=coll)
        if prompts is None:
            mk = lambda n: rng.randint(0, cfg.vocab_size,
                                       n).astype(np.int32)
            prompts = {(c, k): mk(33 if (c + k) % 2 == 0 else 17)
                       for c in range(clients)
                       for k in range(requests_per_client)}
        scfg = ServingConfig(
            max_queue_len=total + 2, decode_burst=decode_burst,
            audit_blocks=True,
            tensor_parallel_size=tp, tp_collectives=coll)

        def stream():
            loop = ServeLoop(eng, scfg)
            t0 = time.perf_counter()
            owner = {}
            remaining = {c: requests_per_client - 1
                         for c in range(clients)}
            for c in range(clients):
                req = loop.submit(prompts[(c, 0)],
                                  max_new_tokens=new_tokens)
                owner[id(req)] = (c, 0)
            outputs = {}
            steps = 0
            while len(outputs) < total:
                steps += 1
                if steps > 100_000:
                    raise RuntimeError("tp closed loop wedged")
                for req in loop.step():
                    key = owner.pop(id(req), None)
                    if key is None:
                        continue
                    if req.state is not RequestState.DONE:
                        raise RuntimeError(
                            f"tp request {key} ended {req.state.value} — "
                            f"the closed loop must complete every request")
                    outputs[key] = list(req.output_tokens)
                    c = key[0]
                    if remaining[c] > 0:
                        k = requests_per_client - remaining[c]
                        nxt = loop.submit(prompts[(c, k)],
                                          max_new_tokens=new_tokens)
                        owner[id(nxt)] = (c, k)
                        remaining[c] -= 1
            return outputs, time.perf_counter() - t0

        stream()                               # warm pass (compiles)
        outputs, elapsed = stream()
        eng.audit_blocks()                     # zero leaked blocks
        goodput = sum(len(o) for o in outputs.values()) / elapsed
        results[label] = (outputs, goodput)

    outs_ref, goodput_tp1 = results["tp1"]
    for label in ("tp2_xla", "tp2_fused"):
        outs, _ = results[label]
        if outs != outs_ref:
            bad = [k for k in outs_ref if outs.get(k) != outs_ref[k]]
            raise RuntimeError(
                f"{label} changed outputs for requests {bad}: tensor "
                f"parallelism must be invisible under greedy decode")
    goodput = results["tp2_fused"][1]
    extras = {
        "requests": total, "clients": clients,
        "goodput_tp1": round(goodput_tp1, 2),
        "goodput_tp2_xla": round(results["tp2_xla"][1], 2),
        "lost_requests": 0,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "model": size, "new_tokens": new_tokens,
    }
    return goodput, extras


def _openloop_setup(max_seqs: int, decode_burst: int,
                    prefix_cache_blocks: int = 0):
    """One tiny-f32 engine shared by every open-loop arm (module-level
    program caches stay warm across arms; virtual time never charges
    compiles anyway) plus a loop factory producing fresh
    (ServeLoop, clock) pairs on it."""
    from deepspeed_tpu.config.config import ServingConfig, TracingConfig
    from deepspeed_tpu.serving import ServeLoop, VirtualClock

    import jax.numpy as jnp

    eng, cfg = _engine(1024, max_seqs=max_seqs,
                       decode_burst=max(decode_burst, 16), size="tiny",
                       dtype=jnp.float32, full_prompt_prefill=False)

    def make_loop(queue_len: int = 512):
        clock = VirtualClock()
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=queue_len, decode_burst=decode_burst,
            prefix_cache_blocks=prefix_cache_blocks, audit_blocks=True,
            tracing=TracingConfig(enabled=False, metrics_ring=8192)),
            clock=clock)
        return loop, clock

    return eng, cfg, make_loop


def _run_openloop_arm(make_loop, items, step_dt: float = 1.0):
    """One open-loop arm on a fresh loop: returns (driver result,
    per-request outputs keyed by workload index, telemetry summary,
    metric-ring series)."""
    from deepspeed_tpu.serving.observatory import OpenLoopDriver

    loop, clock = make_loop()
    drv = OpenLoopDriver(loop, clock, items, step_dt=step_dt)
    res = drv.run()
    if res.lost or res.rejected or res.rejected_invalid:
        raise RuntimeError(
            f"open-loop arm lost work: lost={res.lost} "
            f"rejected={res.rejected} invalid={res.rejected_invalid} — "
            f"the bench arms are sized for zero loss")
    loop.engine.audit_blocks()          # zero leaked blocks
    pool = getattr(loop, "adapter_pool", None)
    if pool is not None:                # tenancy arms: pool conservation
        pool.audit()
        if pool._pins:
            raise RuntimeError(
                f"adapter reservations leaked past drain: {pool._pins}")
    # requests submit in schedule order, so outputs key by that order
    # (res.lost above already guaranteed every one of them is DONE)
    outputs = [list(r.output_tokens) for r in res.requests]
    ring = loop.metrics.ring
    series = {
        "queue_depth": ring.series("queue_depth"),
        "batch_occupancy": ring.series("batch_occupancy"),
        # raw per-request TTFT samples (virtual seconds) for post-hoc
        # SLA-onset classification
        "ttft": list(loop.telemetry.ttft),
    }
    s = loop.telemetry.summary(elapsed_s=res.elapsed_s)
    return res, outputs, s, series


def bench_serving_openloop(n_requests: int = 32, seed: int = 0,
                           rho: float = 0.85, max_seqs: int = 4,
                           decode_burst: int = 8):
    """Open-loop serving row (`serve_openloop_c8`, ISSUE 13): a seeded
    Poisson arrival stream with heavy-tailed prompt/output lengths, a
    shared-prefix mix (prefix cache on) and a priority mix, submitted
    on schedule — NOT on completion — at offered load `rho` against
    the engine's measured service rate, on the serve FakeClock
    (deterministic virtual time: one virtual second per serve step,
    real serving mechanics, real greedy tokens).

    The observatory rides along the way production would run it: the
    per-tick metric time series samples every step and the recompile
    flight recorder is armed across the run (this row's first arm IS
    where the serving programs compile, so the recorder's event count
    and program-cache census attribution are exercised on real
    compiles — on a warmed second run it reads zero, the negative
    control the tests lock).

    Asserts zero lost/rejected requests and zero leaked blocks.
    Virtual-time caveat: goodput/TTFT are in virtual seconds (ratios
    and queueing behavior are the measurement; wall numbers live on
    the closed-loop rows)."""
    from deepspeed_tpu.serving.observatory import (
        RecompileFlightRecorder, WorkloadGenerator,
        calibrate_service_rate)

    eng, cfg, make_loop = _openloop_setup(max_seqs, decode_burst,
                                          prefix_cache_blocks=24)
    gen = WorkloadGenerator(
        vocab_size=cfg.vocab_size, seed=seed, arrival="poisson",
        rate_rps=1.0, prompt_len_mean=48.0, prompt_len_sigma=0.9,
        prompt_len_min=8, prompt_len_max=320, output_len_mean=12.0,
        output_len_sigma=0.6, output_len_min=2, output_len_max=48,
        shared_prefix_len=64, shared_prefix_frac=0.4,
        priority_mix={0: 0.8, 1: 0.2})
    # the recorder arms across the WHOLE row (calibration included):
    # on a cold process the serving programs compile inside this
    # window, so the row's artifact carries real counted/attributed
    # compile events; in a warmed process it reads 0 — both are the
    # truth, and the negative control the tests lock
    rec = RecompileFlightRecorder(engine=eng)
    with rec:
        items = gen.generate(n_requests)
        mu = calibrate_service_rate(make_loop, items, step_dt=1.0)
        gen = gen.with_rate(rho * mu)   # the generator the arm RAN
        items = gen.generate(n_requests)
        res, outputs, s, series = _run_openloop_arm(make_loop, items)
    grew = rec.scan()
    goodput = s["goodput_tok_s"]
    extras = {
        "requests": n_requests, "rho": rho,
        "service_rate_rps": round(mu, 4),
        "arrival_rate_rps": round(rho * mu, 4),
        "ttft_p50_vs": round(s["ttft_p50_s"], 2),
        "ttft_p95_vs": round(s["ttft_p95_s"], 2),
        "tpot_p50_vs": (round(s["tpot_p50_s"], 3)
                        if s["tpot_p50_s"] is not None else None),
        "queue_depth_peak": max(series["queue_depth"]),
        "batch_occupancy_mean": round(s["batch_occupancy_mean"], 3),
        "prefix_hit_rate": (round(s["prefix_hit_rate"], 3)
                            if s["prefix_hit_rate"] is not None
                            else None),
        "recompiles": rec.total_events,
        "recompile_wall_s": round(rec.total_compile_s, 2),
        "recompiled_programs": sorted(grew),
        "rejected": 0, "lost_requests": 0,
        "workload": gen.describe(),
        "time_base": "virtual (1 serve step = 1 s; see docstring)",
        "model": "tiny",
    }
    return goodput, extras


def bench_serving_openloop_sweep(n_requests: int = 32, seed: int = 0,
                                 rhos=(0.3, 0.6, 0.9, 1.4, 2.2, 3.5),
                                 max_seqs: int = 4,
                                 decode_burst: int = 8,
                                 sla_ttft_factor: float = 3.0):
    """Open-loop offered-load sweep (`serve_openloop_sweep`, ISSUE 13):
    the SAME seeded heavy-tailed workload (identical prompts across
    arms — only the arrival spacing changes) swept over offered load
    ρ = arrival rate / measured service rate, on deterministic virtual
    time.  This is the queueing-collapse measurement a closed loop
    cannot produce: under capacity the queue stays shallow and TTFT
    tracks service time; past ρ = 1 the queue and TTFT grow with the
    backlog while goodput pins at capacity — the knee.

    In-row acceptance contract (ISSUE 13):
    - fully deterministic: the overloaded arm re-runs bit-identically,
      and greedy token outputs are bit-identical ACROSS arms (tiny f32,
      the serve_spec_c8 bitwise-stability choice) — arrival timing must
      change scheduling, never results;
    - zero lost requests, zero rejections, zero leaked blocks on every
      arm;
    - utilization (mean batch occupancy) and queue-depth peak are
      monotone non-decreasing through the ramp;
    - SLA-violation onset: with the TTFT target set to
      `sla_ttft_factor` x the lightest arm's p95, the lightest arm
      shows ZERO violations and the most overloaded arm shows them —
      the onset ρ is reported.

    Value = peak goodput across the arms (the measured capacity, in
    virtual tok/s)."""
    from deepspeed_tpu.serving.observatory import (
        WorkloadGenerator, calibrate_service_rate)

    eng, cfg, make_loop = _openloop_setup(max_seqs, decode_burst)
    gen = WorkloadGenerator(
        vocab_size=cfg.vocab_size, seed=seed, arrival="poisson",
        rate_rps=1.0, prompt_len_mean=48.0, prompt_len_sigma=0.9,
        prompt_len_min=8, prompt_len_max=320, output_len_mean=12.0,
        output_len_sigma=0.6, output_len_min=2, output_len_max=48)
    base_items = gen.generate(n_requests)
    mu = calibrate_service_rate(make_loop, base_items, step_dt=1.0)

    arms = []
    ttft_by_arm = []
    ref_outputs = None
    for rho in rhos:
        items = gen.with_rate(rho * mu).generate(n_requests)
        res, outputs, s, series = _run_openloop_arm(make_loop, items)
        if ref_outputs is None:
            ref_outputs = outputs
        elif outputs != ref_outputs:
            bad = [i for i, (a, b) in
                   enumerate(zip(ref_outputs, outputs)) if a != b]
            raise RuntimeError(
                f"rho={rho} arm changed greedy outputs for requests "
                f"{bad}: arrival timing must be invisible to results")
        ttft_by_arm.append(series["ttft"])
        arms.append({
            "rho": rho,
            "goodput_tok_vs": round(s["goodput_tok_s"], 3),
            "ttft_p50_vs": round(s["ttft_p50_s"], 2),
            "ttft_p95_vs": round(s["ttft_p95_s"], 2),
            "tpot_p95_vs": (round(s["tpot_p95_s"], 3)
                            if s["tpot_p95_s"] is not None else None),
            "batch_occupancy_mean": round(s["batch_occupancy_mean"], 4),
            "queue_depth_peak": max(series["queue_depth"]),
            "elapsed_vs": round(res.elapsed_s, 1),
        })

    # determinism: the most overloaded arm replays bit-identically
    items = gen.with_rate(rhos[-1] * mu).generate(n_requests)
    _, outputs2, _, series2 = _run_openloop_arm(make_loop, items)
    if outputs2 != ref_outputs or series2["ttft"] != ttft_by_arm[-1]:
        raise RuntimeError(
            "overloaded arm replay diverged (tokens or TTFT series): "
            "the sweep must be deterministic under its seed")

    # monotone ramp: utilization and queue depth through increasing rho
    occ = [a["batch_occupancy_mean"] for a in arms]
    peaks = [a["queue_depth_peak"] for a in arms]
    for name, xs in (("batch occupancy", occ), ("queue-depth peak",
                                                peaks)):
        if any(b < a - 1e-9 for a, b in zip(xs, xs[1:])):
            raise RuntimeError(
                f"{name} not monotone through the ramp: {xs} — the "
                f"open-loop knee should only sharpen with rho")

    # SLA-violation onset: target anchored to the lightest arm's p95
    # PLUS one serve step (virtual time quantizes to whole steps, so an
    # uncontended TTFT is 0 and a bare multiple would set a 0 target),
    # violations counted from the raw per-request samples
    target = sla_ttft_factor * (arms[0]["ttft_p95_vs"] + 1.0)
    onset_rho = None
    for a, samples in zip(arms, ttft_by_arm):
        a["sla_ttft_violations"] = sum(1 for x in samples if x > target)
        if onset_rho is None and a["sla_ttft_violations"] > 0:
            onset_rho = a["rho"]
    if arms[0]["sla_ttft_violations"] != 0:
        raise RuntimeError(
            f"lightest arm (rho={rhos[0]}) already violates the TTFT "
            f"target {target:.1f} vs — the SLA anchor is broken")
    if arms[-1]["sla_ttft_violations"] == 0:
        raise RuntimeError(
            f"overloaded arm (rho={rhos[-1]}) shows no TTFT SLA "
            f"violations against target {target:.1f} vs: the sweep "
            f"failed to reach queueing collapse")
    goodput = max(a["goodput_tok_vs"] for a in arms)
    extras = {
        "requests": n_requests, "seed": seed,
        "service_rate_rps": round(mu, 4),
        "sla_ttft_target_vs": round(target, 2),
        "sla_onset_rho": onset_rho,
        "arms": arms,
        "rejected": 0, "lost_requests": 0,
        # the workload parameterization each arm actually RAN: base
        # draws at the recorded spec, arrival rate = rho * mu per arm
        # (replaying an arm = with_rate(rho * service_rate_rps))
        "workload": dict(gen.describe(), rate_rps={
            str(rho): round(rho * mu, 4) for rho in rhos}),
        "time_base": "virtual (1 serve step = 1 s; deterministic "
                     "queueing measurement, not wall time)",
        "model": "tiny",
    }
    return goodput, extras


def bench_serving_openloop_tier(n_requests: int = 48, seed: int = 0,
                                rhos=(0.6, 1.0, 1.6, 2.4),
                                max_seqs: int = 4,
                                decode_burst: int = 8,
                                prefix_cache_blocks: int = 4,
                                host_cache_blocks: int = 128,
                                groups: int = 3,
                                sla_ttft_factor: float = 3.0):
    """Open-loop tiering sweep (`serve_openloop_tier`, ISSUE 14): the
    SAME seeded heavy-tailed shared-prefix workload — identical
    prompts, identical arrival schedules per rho — served by two cache
    configurations, HBM-only vs HBM + host spill tier, across an
    offered-load ramp on deterministic virtual time.

    The engine caps prefill at 128 tokens/step, so a long stranger
    prompt costs several virtual-time steps while a shared-prefix hit
    prefills its tail in one: prefix retention is literally service
    rate here.  The generator's shared-prefix arrivals are rotated
    across `groups` distinct 2-block system prompts (deterministic by
    arrival index, identical across rhos and arms), so with the small
    HBM budget (4 blocks, < one resident group + churn) every group is
    COLD again by the time it recurs — an LRU cannot save a working
    set bigger than its arena, which is exactly the regime the spill
    tier exists for.  The tiered arm demotes those evictions to host
    and promotes on the next group hit.  The claim
    under test is the ISSUE 14 one: with more of the stream hitting,
    the SLA-violation knee MOVES RIGHT — at the same offered load the
    tiered arm violates the (HBM-anchored) TTFT target strictly less,
    and its violation onset never comes at a lower rho.

    In-row acceptance: greedy outputs bit-identical across BOTH arms
    and every rho (tiny f32, chunk == block alignment,
    host_cache_quant="none" — arrival timing and spill residency must
    be invisible to results), zero lost/rejected requests and zero
    leaked blocks (arena + host residency audit) on every arm, tiered
    hit rate strictly above HBM-only's, strictly fewer total TTFT SLA
    violations, and onset_rho(tiered) >= onset_rho(hbm).  Value = the
    tiered arm's peak goodput (virtual tok/s)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.config.config import ServingConfig, TracingConfig
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, gpt2_config
    from deepspeed_tpu.serving import ServeLoop, VirtualClock
    from deepspeed_tpu.serving.observatory import (
        OpenLoopDriver, WorkloadGenerator, calibrate_service_rate)

    cfg = gpt2_config("tiny", max_seq_len=1024, dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                num_blocks=8 * 16 + 8, block_size=64,
                                max_blocks_per_seq=16, max_seqs=max_seqs,
                                prefill_chunk_size=64,
                                max_prefill_tokens_per_step=128,
                                decode_burst=max(decode_burst, 8),
                                full_prompt_prefill=False))

    def make_loop_factory(hcb):
        def make_loop(queue_len: int = 512):
            clock = VirtualClock()
            loop = ServeLoop(eng, ServingConfig(
                max_queue_len=queue_len, decode_burst=decode_burst,
                prefix_cache_blocks=prefix_cache_blocks,
                host_cache_blocks=hcb, host_cache_quant="none",
                audit_blocks=True,
                tracing=TracingConfig(enabled=False, metrics_ring=8192)),
                clock=clock)
            return loop, clock
        return make_loop

    gen = WorkloadGenerator(
        vocab_size=cfg.vocab_size, seed=seed, arrival="poisson",
        rate_rps=1.0, prompt_len_mean=96.0, prompt_len_sigma=0.8,
        prompt_len_min=16, prompt_len_max=448, output_len_mean=8.0,
        output_len_sigma=0.5, output_len_min=2, output_len_max=24,
        shared_prefix_len=128, shared_prefix_frac=0.5)

    # rotate the generator's single shared prefix across `groups`
    # distinct system prompts, by arrival index: the prompt draws are
    # rate-independent (the sweep's cross-rho bit-stability contract),
    # so the rotation is identical for every rho and both arms
    gp_rng = np.random.RandomState(seed + 4321)
    group_prefixes = [gp_rng.randint(0, cfg.vocab_size,
                                     128).astype(np.int32)
                      for _ in range(groups)]

    def rotate(items):
        g = 0
        for it in items:
            if it.shared_prefix:
                it.prompt[:128] = group_prefixes[g % groups]
                g += 1
        return items

    base_items = rotate(gen.generate(n_requests))
    # ONE service-rate anchor (the HBM arm's), so both arms see the
    # IDENTICAL arrival schedule at each rho — the knee comparison is
    # between serving configurations, not between workloads
    mu = calibrate_service_rate(make_loop_factory(0), base_items,
                                step_dt=1.0)

    arms = {"hbm": [], "tiered": []}
    ttft = {"hbm": [], "tiered": []}
    hits = {"hbm": [0, 0], "tiered": [0, 0]}
    ref_outputs = {}
    for rho in rhos:
        items = rotate(gen.with_rate(rho * mu).generate(n_requests))
        for label, hcb in (("hbm", 0),
                           ("tiered", host_cache_blocks)):
            res, outputs, s, series = _run_openloop_arm(
                make_loop_factory(hcb), items)
            if rho not in ref_outputs:
                ref_outputs[rho] = outputs
            elif outputs != ref_outputs[rho]:
                bad = [i for i, (a, b) in
                       enumerate(zip(ref_outputs[rho], outputs))
                       if a != b]
                raise RuntimeError(
                    f"{label} arm at rho={rho} changed greedy outputs "
                    f"for requests {bad}: spill residency must be "
                    f"invisible to results")
            hits[label][0] += s["prefix_hits"]
            hits[label][1] += s["prefix_hits"] + s["prefix_misses"]
            ttft[label].append(series["ttft"])
            arms[label].append({
                "rho": rho,
                "goodput_tok_vs": round(s["goodput_tok_s"], 3),
                "ttft_p95_vs": round(s["ttft_p95_s"], 2),
                "queue_depth_peak": max(series["queue_depth"]),
                "prefix_hit_rate": (round(s["prefix_hit_rate"], 3)
                                    if s["prefix_hit_rate"] is not None
                                    else None),
                "kv_promoted_blocks": s["kv_promoted_blocks"],
            })
    hit_rate = {k: v[0] / v[1] for k, v in hits.items()}
    if hit_rate["tiered"] <= hit_rate["hbm"]:
        raise RuntimeError(
            f"tiered sweep hit rate {hit_rate['tiered']:.3f} not "
            f"strictly above HBM-only {hit_rate['hbm']:.3f}")
    # SLA target anchored on the HBM arm's lightest rho (+1 virtual
    # step, the serve_openloop_sweep quantization guard)
    target = sla_ttft_factor * (arms["hbm"][0]["ttft_p95_vs"] + 1.0)
    onset = {}
    viol_total = {}
    for label in ("hbm", "tiered"):
        onset[label] = None
        viol_total[label] = 0
        for a, samples in zip(arms[label], ttft[label]):
            a["sla_ttft_violations"] = sum(
                1 for x in samples if x > target)
            viol_total[label] += a["sla_ttft_violations"]
            if onset[label] is None and a["sla_ttft_violations"] > 0:
                onset[label] = a["rho"]
    if arms["hbm"][0]["sla_ttft_violations"] != 0:
        raise RuntimeError(
            f"lightest HBM arm already violates its own anchored "
            f"target {target:.1f} vs — the SLA anchor is broken")
    if viol_total["hbm"] == 0:
        raise RuntimeError(
            "HBM-only sweep never reached SLA violations: the ramp is "
            "too light to show a knee at all")
    if viol_total["tiered"] >= viol_total["hbm"]:
        raise RuntimeError(
            f"tiered sweep violated the TTFT target {target:.1f} vs "
            f"{viol_total['tiered']} times vs HBM-only's "
            f"{viol_total['hbm']}: the knee did not move")
    if onset["tiered"] is not None and onset["hbm"] is not None \
            and onset["tiered"] < onset["hbm"]:
        raise RuntimeError(
            f"tiered SLA onset rho {onset['tiered']} EARLIER than "
            f"HBM-only's {onset['hbm']}")
    goodput = max(a["goodput_tok_vs"] for a in arms["tiered"])
    extras = {
        "requests": n_requests, "seed": seed,
        "service_rate_rps": round(mu, 4),
        "sla_ttft_target_vs": round(target, 2),
        "sla_onset_rho_hbm": onset["hbm"],
        "sla_onset_rho_tiered": onset["tiered"],
        "sla_violations_hbm": viol_total["hbm"],
        "sla_violations_tiered": viol_total["tiered"],
        "hit_rate_hbm": round(hit_rate["hbm"], 3),
        "hit_rate_tiered": round(hit_rate["tiered"], 3),
        "arms_hbm": arms["hbm"],
        "arms_tiered": arms["tiered"],
        "prefix_cache_blocks": prefix_cache_blocks,
        "host_cache_blocks": host_cache_blocks,
        "shared_prefix_groups": groups,
        "rejected": 0, "lost_requests": 0,
        "workload": dict(gen.describe(), rate_rps={
            str(rho): round(rho * mu, 4) for rho in rhos}),
        "time_base": "virtual (1 serve step = 1 s; deterministic "
                     "queueing measurement, not wall time)",
        "model": "tiny",
    }
    return goodput, extras


def bench_serving_stream(clients: int = 8, requests_per_client: int = 2,
                         new_tokens: int = 16, max_seqs: int = 4,
                         decode_burst: int = 16):
    """Token-streaming row (`serve_stream_c8`, ISSUE 15): the same
    greedy closed-loop request stream served twice — streaming off
    (the PR 14 loop) and streaming on with one event-driven consumer
    thread per request collecting its `TokenStream`.

    Asserts the row's contract: outputs bit-for-bit identical between
    the arms (streaming is delivery, never decoding), every consumer's
    collected sequence exactly equals its request's output (gap-free,
    duplicate-free), zero lost requests, zero leaked blocks.  Extras
    carry TTFT p50/p95 and the NEW inter-token-latency p50/p95 —
    the consumer-experienced gap between emissions, which under burst
    serving is the burst wall, the number tpot percentiles hide —
    plus the measured streaming wall overhead (reported, not gated:
    CPU-backend wall noise; the bit-for-bit and exactly-once asserts
    are the contract)."""
    import threading

    import jax.numpy as jnp

    from deepspeed_tpu.config.config import ServingConfig, StreamingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    total = clients * requests_per_client
    rng = np.random.RandomState(15)
    prompts = None
    results = {}
    for label, streaming in (("warm", None), ("off", None),
                             ("on", StreamingConfig(enabled=True))):
        # tiny f32, like the sibling open-loop rows: the measurement
        # is the delivery contract (bit-for-bit, exactly-once), not
        # model-scale throughput — and the "model" extra must name the
        # engine the row actually ran
        eng, cfg = _engine(1024, max_seqs=max_seqs,
                           decode_burst=max(decode_burst, 16),
                           size="tiny", dtype=jnp.float32,
                           full_prompt_prefill=False)
        if prompts is None:
            prompts = [rng.randint(
                0, cfg.vocab_size,
                128 if i % 2 else 512).astype(np.int32)
                for i in range(total)]
        if label == "warm":
            # compile wave: both measured arms then run on warmed
            # program caches, so the off/on wall comparison is
            # apples-to-apples (first-compile wall would otherwise
            # land entirely in the off arm)
            wl = ServeLoop(eng, ServingConfig(
                max_queue_len=4, decode_burst=decode_burst))
            for p in prompts[:2]:
                wl.submit(p, max_new_tokens=new_tokens)
            wl.run_until_idle(max_steps=100_000)
            continue
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=total + 1, decode_burst=decode_burst,
            audit_blocks=True, streaming=streaming))
        t0 = time.perf_counter()
        reqs = [loop.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        consumed = [[] for _ in reqs]
        threads = []
        if label == "on":
            def consume(stream, out):
                for tok in stream.tokens():
                    out.append(tok)

            for r, out in zip(reqs, consumed):
                th = threading.Thread(target=consume,
                                      args=(r.stream, out))
                th.start()
                threads.append(th)
        loop.run_until_idle(max_steps=100_000)
        elapsed = time.perf_counter() - t0
        for th in threads:
            th.join(30.0)
            if th.is_alive():
                raise RuntimeError("stream consumer hung after drain")
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError("streaming row lost requests")
        eng.audit_blocks()
        outs = [list(map(int, r.output_tokens)) for r in reqs]
        if label == "on" and consumed != outs:
            bad = [i for i, (a, b) in enumerate(zip(consumed, outs))
                   if a != b]
            raise RuntimeError(
                f"stream consumers diverged from outputs for requests "
                f"{bad}: delivery must be gap-free and duplicate-free")
        results[label] = (outs, loop.telemetry.summary(elapsed_s=elapsed),
                          elapsed)
    outs_off, s_off, t_off = results["off"]
    outs_on, s_on, t_on = results["on"]
    if outs_off != outs_on:
        bad = [i for i, (a, b) in enumerate(zip(outs_off, outs_on))
               if a != b]
        raise RuntimeError(
            f"streaming changed outputs for requests {bad}: delivery "
            f"must be bit-for-bit")
    extras = {
        "requests": total, "new_tokens": new_tokens,
        "decode_burst": decode_burst,
        "tokens_streamed": s_on["tokens_streamed"],
        "ttft_p50_ms": round(s_on["ttft_p50_s"] * 1e3, 1),
        "ttft_p95_ms": round(s_on["ttft_p95_s"] * 1e3, 1),
        "itl_p50_ms": round(s_on["itl_p50_s"] * 1e3, 2),
        "itl_p95_ms": round(s_on["itl_p95_s"] * 1e3, 2),
        "goodput_stream_off": round(s_off["goodput_tok_s"], 2),
        "stream_overhead_frac": round(t_on / t_off - 1.0, 4),
        "model": "tiny",
    }
    return s_on["goodput_tok_s"], extras


def bench_serving_multistep(clients: int = 8, requests_per_client: int = 2,
                            new_tokens: int = 32, max_seqs: int = 4,
                            ks=(1, 8, 16)):
    """Multi-step decode row (`serve_multistep_c8`, ISSUE 17): the same
    greedy request stream served once per `multi_step` k in `ks` —
    k=1 is the legacy per-token host loop, k>1 runs K decode steps in
    ONE compiled dispatch with on-device sampling + termination and a
    single packed device->host fetch per step group.

    In-row acceptance contract (ISSUE 17): outputs bit-for-bit across
    every k (multi_step=1 IS the pre-PR loop; groups change WHEN the
    host observes, never what the model computes), zero lost requests
    and zero leaked blocks per arm, and explicit d2h fetches PER
    GENERATED TOKEN (the engine's `profile["d2h_fetches"]` ledger —
    every intended `jax.device_get` in the serve path bumps it) drop
    >= 4x at k=8 vs k=1.  The transfer counters are backend-
    independent — they count dispatch-pipeline stalls a TPU serve
    would pay, measured exactly, even on this CPU container; the
    goodput walls carry the usual CPU-backend caveat."""
    import jax.numpy as jnp

    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop

    total = clients * requests_per_client
    rng = np.random.RandomState(17)
    prompts = None
    results = {}
    for k in ks:
        eng, cfg = _engine(1024, max_seqs=max_seqs, decode_burst=16,
                           size="tiny", dtype=jnp.float32,
                           full_prompt_prefill=False)
        if prompts is None:
            prompts = [rng.randint(
                0, cfg.vocab_size,
                128 if i % 2 else 512).astype(np.int32)
                for i in range(total)]
        # per-arm compile wave, then zero the transfer ledger so the
        # counters cover exactly the measured serve
        warm = ServeLoop(eng, ServingConfig(max_queue_len=4,
                                            multi_step=k))
        for p in prompts[:2]:
            warm.submit(p, max_new_tokens=2)
        warm.run_until_idle(max_steps=100_000)
        eng.profile["d2h_fetches"] = 0
        loop = ServeLoop(eng, ServingConfig(max_queue_len=total + 1,
                                            multi_step=k,
                                            audit_blocks=True))
        t0 = time.perf_counter()
        reqs = [loop.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        loop.run_until_idle(max_steps=100_000)
        elapsed = time.perf_counter() - t0
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError(f"multi-step row k={k} lost requests")
        eng.audit_blocks()            # zero leaked blocks after drain
        outs = [list(map(int, r.output_tokens)) for r in reqs]
        n_tok = sum(len(o) for o in outs)
        results[k] = (outs, n_tok / elapsed,
                      eng.profile["d2h_fetches"] / n_tok)
    base = results[ks[0]][0]
    for k in ks[1:]:
        if results[k][0] != base:
            bad = [i for i, (a, b) in enumerate(zip(base, results[k][0]))
                   if a != b]
            raise RuntimeError(
                f"multi_step={k} changed outputs for requests {bad}: "
                f"step groups must be bit-for-bit with the legacy loop")
    ratio = results[1][2] / results[8][2]
    if ratio < 4.0:
        raise RuntimeError(
            f"d2h per generated token dropped only {ratio:.1f}x at k=8 "
            f"vs k=1 (need >= 4x): "
            f"{results[1][2]:.3f} -> {results[8][2]:.3f}")
    extras = {
        "requests": total, "new_tokens": new_tokens,
        "multi_step": 8, "model": "tiny",
        "d2h_ratio_k8_vs_k1": round(ratio, 1),
    }
    for k in ks:
        extras[f"goodput_k{k}"] = round(results[k][1], 2)
        extras[f"d2h_per_token_k{k}"] = round(results[k][2], 4)
    return results[8][1], extras


def bench_serving_grammar(clients: int = 8, requests_per_client: int = 2,
                          new_tokens: int = 32, max_seqs: int = 4,
                          k: int = 8):
    """Grammar-constrained decode row (`serve_grammar_c8`, ISSUE 18):
    the serve_multistep_c8 stream with every EVEN request constrained
    to a JSON-schema grammar (serving/structured: token automaton
    compiled once, masks applied INSIDE the k-step scan, per-row FSM
    state riding the carry), odd requests untouched — served once
    plain (structured config armed, zero constrained traffic) and once
    with the grammar on.

    In-row acceptance contract (ISSUE 18): every constrained chain is
    machine-accepted by the source automaton and ends at EOS; the
    UNCONSTRAINED rows are bit-for-bit the plain arm (has_fsm=False is
    identity, not an all-ones mask detour); explicit d2h fetches PER
    MULTI-STEP DISPATCH — measured per call against the engine's
    transfer ledger — are IDENTICAL across arms (the grammar adds zero
    host round trips; the ledger is backend-independent, counting the
    dispatch-pipeline stalls a TPU serve would pay); zero lost
    requests and zero leaked blocks per arm.  Value = the constrained
    arm's goodput; the masked rows EOS early by construction so the
    wall is not comparable to the unconstrained rows' rows."""
    import jax.numpy as jnp

    from deepspeed_tpu.config.config import ServingConfig, StructuredConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop
    from deepspeed_tpu.serving.structured import (AutomatonCache,
                                                  ResponseFormat,
                                                  byte_vocab)

    eos = 0
    # bounded grammar: every path reaches an accept state well inside
    # the token budget (an unbounded {"type": "integer"} would let
    # greedy ride digits past max_new_tokens and die mid-prefix)
    fmt = ResponseFormat.json_schema(
        {"type": "object",
         "properties": {"done": {"type": "boolean"},
                        "n": {"enum": [1, 2, 3]}},
         "required": ["done", "n"]})
    total = clients * requests_per_client
    rng = np.random.RandomState(18)
    prompts = None
    results = {}
    for arm in ("plain", "fsm"):
        eng, cfg = _engine(1024, max_seqs=max_seqs, decode_burst=16,
                           size="tiny", dtype=jnp.float32,
                           full_prompt_prefill=False)
        if prompts is None:
            prompts = [rng.randint(
                1, cfg.vocab_size,
                128 if i % 2 else 512).astype(np.int32)
                for i in range(total)]
        scfg = dict(max_queue_len=total + 1, multi_step=k,
                    audit_blocks=True, structured=StructuredConfig())
        warm = ServeLoop(eng, ServingConfig(**{**scfg,
                                               "max_queue_len": 4}))
        for i, p in enumerate(prompts[:2]):
            warm.submit(p, max_new_tokens=2, eos_token_id=eos,
                        response_format=fmt if arm == "fsm" and i == 0
                        else None)
        warm.run_until_idle(max_steps=100_000)
        eng.profile["d2h_fetches"] = 0
        # count explicit d2h fetches PER multi-step dispatch: the
        # grammar must not add any (the FSM state lives in the scan
        # carry; the host mirrors it by pure re-derivation)
        orig_ms = eng.decode_multi_step
        deltas = []

        def counted(*a, _o=orig_ms, _d=deltas, **kw):
            before = eng.profile["d2h_fetches"]
            out = _o(*a, **kw)
            _d.append(eng.profile["d2h_fetches"] - before)
            return out

        eng.decode_multi_step = counted
        loop = ServeLoop(eng, ServingConfig(**scfg))
        t0 = time.perf_counter()
        reqs = [loop.submit(p, max_new_tokens=new_tokens,
                            eos_token_id=eos if arm == "fsm"
                            and i % 2 == 0 else None,
                            response_format=fmt if arm == "fsm"
                            and i % 2 == 0 else None)
                for i, p in enumerate(prompts)]
        loop.run_until_idle(max_steps=100_000)
        elapsed = time.perf_counter() - t0
        eng.decode_multi_step = orig_ms
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError(f"grammar row arm={arm} lost requests")
        eng.audit_blocks()
        outs = [list(map(int, r.output_tokens)) for r in reqs]
        n_tok = sum(len(o) for o in outs)
        results[arm] = (outs, n_tok / elapsed, sorted(set(deltas)),
                        loop.telemetry.counters["grammar_requests"])
    if results["fsm"][2] != results["plain"][2]:
        raise RuntimeError(
            "grammar added d2h fetches to the multi-step dispatch: "
            f"per-dispatch deltas {results['plain'][2]} (plain) vs "
            f"{results['fsm'][2]} (constrained)")
    n_con = results["fsm"][3]
    if n_con != (total + 1) // 2:
        raise RuntimeError(f"expected {(total + 1) // 2} constrained "
                           f"requests, telemetry saw {n_con}")
    auto = AutomatonCache(byte_vocab(cfg.vocab_size)).get(fmt)
    for i in range(total):
        if i % 2 == 0:
            toks = results["fsm"][0][i]
            if toks[-1] != eos or not auto.accepts(toks, eos_id=eos):
                raise RuntimeError(
                    f"constrained request {i} emitted an out-of-grammar "
                    f"chain: {bytes(t for t in toks if t != eos)!r}")
        elif results["fsm"][0][i] != results["plain"][0][i]:
            raise RuntimeError(
                f"unconstrained request {i} diverged from the plain "
                f"arm: the has_fsm=False row must be identity")
    extras = {
        "requests": total, "new_tokens": new_tokens, "multi_step": k,
        "model": "tiny", "constrained_requests": n_con,
        "goodput_plain": round(results["plain"][1], 2),
        "d2h_per_dispatch": results["fsm"][2],
        "grammar": "json_schema{done:bool,n:enum123}",
    }
    return results["fsm"][1], extras


def bench_serving_moe(n_requests: int = 8, max_seqs: int = 4,
                      new_tokens: int = 8, seed: int = 0):
    """Expert-paged MoE decode row (`serve_moe_c8`, ISSUE 20): a tiny
    real MoE engine (qwen_v2_moe tiny f32 — 4 experts, top-2 router,
    4 layers) served twice on the same stream: once with
    `ServingConfig.moe=None` (the config shape every pre-MoE round ran,
    so this arm IS the locked off-path — no pool, no census, no expert
    gauges) and once with expert paging on at full residency, the
    demote/promote lifecycle choreographed between drains exactly the
    way serve_tenants_c8 exercises the adapter pool.

    In-row acceptance contract (ISSUE 20): the paged arm's token
    streams are BIT-FOR-BIT the moe-off arm's (residency bookkeeping
    must never touch the math), at least one demote AND one promote
    fired per layer with ZERO router drops (expert_rerouted == 0,
    drop_rate == 0.0 — every demoted expert is promoted back before
    traffic resumes), pool conservation audit green in every phase,
    zero reservations still pinned after drain, zero lost requests and
    zero leaked KV blocks in both arms.  Value = the paged arm's
    goodput (same CPU-backend wall-time caveat as the other
    closed-loop rows)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.config.config import (MoeServingConfig,
                                             ServingConfig)
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig,
                                            arch_config)
    from deepspeed_tpu.models import Transformer
    from deepspeed_tpu.serving import RequestState, ServeLoop

    cfg = arch_config("qwen_v2_moe", "tiny", dtype=jnp.float32,
                      max_seq_len=128)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))

    def make_engine():
        return InferenceEngineV2(model, params=params,
                                 config=RaggedInferenceEngineConfig(
                                     num_blocks=64, block_size=8,
                                     max_blocks_per_seq=16,
                                     max_seqs=max_seqs,
                                     prefill_chunk_size=16))

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size,
                           32 if i % 2 else 16).astype(np.int32)
               for i in range(n_requests)]
    half = n_requests // 2

    def serve(loop, batch):
        reqs = [loop.submit(p, max_new_tokens=new_tokens) for p in batch]
        while loop.has_work:
            loop.step()
        if any(r.state is not RequestState.DONE for r in reqs):
            raise RuntimeError("serve_moe_c8 lost requests")
        return [list(map(int, r.output_tokens)) for r in reqs]

    # ---- moe-off arm: the pre-MoE serve loop, unchanged config shape
    off_loop = ServeLoop(make_engine(), ServingConfig(
        max_queue_len=2 * n_requests, audit_blocks=True))
    if off_loop.expert_pool is not None:
        raise RuntimeError("moe=None built an expert pool: the off-path "
                           "lock is broken")
    t0 = time.perf_counter()
    outs_off = serve(off_loop, prompts)
    dt_off = time.perf_counter() - t0
    off_loop.engine.audit_blocks()

    # ---- paged arm: full residency + census rider, with an explicit
    # demote/promote storm between the two half-drains
    loop = ServeLoop(make_engine(), ServingConfig(
        max_queue_len=2 * n_requests, audit_blocks=True,
        moe=MoeServingConfig(census_interval_steps=2)))
    pool = loop.expert_pool
    t0 = time.perf_counter()
    outs = serve(loop, prompts[:half])
    pool.audit()
    # page every demotable expert out and back: demote() keeps top_k
    # resident per layer, promote() restores full residency, so the
    # second half decodes with zero reroutes — bit-exactness holds
    cycled = [(layer, e) for layer in range(cfg.num_layers)
              for e in range(cfg.moe_top_k, cfg.moe_experts)]
    for layer, e in cycled:
        pool.demote(layer, e)
    pool.audit()
    if pool.spilled_count() != len(cycled):
        raise RuntimeError(
            f"expected {len(cycled)} spilled experts mid-cycle, pool "
            f"says {pool.spilled_count()}")
    for layer, e in cycled:
        pool.promote(layer, e)
    pool.audit()
    outs += serve(loop, prompts[half:])
    dt = time.perf_counter() - t0
    loop.engine.audit_blocks()
    pool.ingest_census(loop.engine.drain_moe_census())
    pool.audit()
    st = pool.stats()
    if outs != outs_off:
        bad = [i for i, (a, b) in enumerate(zip(outs, outs_off))
               if a != b]
        raise RuntimeError(
            f"paged arm diverged from the moe-off arm on requests "
            f"{bad}: expert paging must be bit-for-bit at full "
            f"residency")
    if st["expert_demotes"] < len(cycled) or st["expert_promotes"] < len(cycled):
        raise RuntimeError(
            f"the demote/promote cycle did not fire ({st}): the row "
            f"must exercise the residency lifecycle")
    if st["expert_rerouted"] or st["expert_drop_rate"]:
        raise RuntimeError(
            f"router dropped assignments ({st}): zero drops is the "
            f"row's contract — every expert was resident during traffic")
    if st["expert_routed"] <= 0:
        raise RuntimeError("census counted no routed assignments: the "
                           "rider never ran")
    if pool.pinned_count():
        raise RuntimeError(
            f"{pool.pinned_count()} reservations still pinned after "
            f"drain")
    goodput = n_requests * new_tokens / dt
    extras = {
        "requests": n_requests, "new_tokens": new_tokens,
        "model": "qwen_v2_moe-tiny",
        "experts": cfg.moe_experts, "top_k": cfg.moe_top_k,
        "goodput_off": round(n_requests * new_tokens / dt_off, 2),
        "expert_demotes": int(st["expert_demotes"]),
        "expert_promotes": int(st["expert_promotes"]),
        "expert_routed": int(st["expert_routed"]),
        "expert_rerouted": int(st["expert_rerouted"]),
        "expert_resident": int(st["expert_resident"]),
        "expert_spilled": int(st["expert_spilled"]),
    }
    return goodput, extras


def bench_serving_preempt_openloop(n_requests: int = 40, seed: int = 0,
                                   rho: float = 2.0, max_seqs: int = 4,
                                   decode_burst: int = 8,
                                   high_frac: float = 0.2):
    """SLO-aware preemption row (`serve_preempt_openloop`, ISSUE 15):
    an open-loop BURST-arrival mix (heavy-tailed lengths, `high_frac`
    of requests at priority 0, the rest at priority 1) offered at
    rho > 1 on deterministic virtual time, served twice on identical
    schedules — preemption off vs on (KV swap through the host tier,
    recompute fallback).

    In-row acceptance contract (ISSUE 15): zero lost requests and zero
    leaked blocks on both arms, greedy token outputs bit-identical
    across arms (preemption moves WHEN work runs, never what it
    computes), at least one preemption actually fired with live KV
    swapped out, and high-priority TTFT SLA violations strictly fewer
    than the no-preemption arm against the same target on the
    identical schedule.  Value = the preemption arm's virtual goodput
    (same virtual-time caveat as the other open-loop rows)."""
    from deepspeed_tpu.config.config import (PreemptionConfig,
                                             ServingConfig)
    from deepspeed_tpu.serving import ServeLoop, VirtualClock
    from deepspeed_tpu.serving.observatory import (
        WorkloadGenerator, calibrate_service_rate)

    import jax.numpy as jnp

    eng, cfg = _engine(1024, max_seqs=max_seqs,
                       decode_burst=max(decode_burst, 16), size="tiny",
                       dtype=jnp.float32, full_prompt_prefill=False)

    def make_loop_factory(pre):
        from deepspeed_tpu.config.config import TracingConfig

        def make_loop(queue_len: int = 512):
            clock = VirtualClock()
            loop = ServeLoop(eng, ServingConfig(
                max_queue_len=queue_len, decode_burst=decode_burst,
                prefix_cache_blocks=24, host_cache_blocks=64,
                audit_blocks=True, preemption=pre,
                tracing=TracingConfig(enabled=False,
                                      metrics_ring=8192)), clock=clock)
            return loop, clock
        return make_loop

    # long heavy-tailed decodes are what preemption exists for: a
    # priority-1 request mid-way through a 100+-token decode holds its
    # slot and blocks for tens of virtual seconds, which is the wait a
    # bursty priority-0 arrival cannot absorb
    gen = WorkloadGenerator(
        vocab_size=cfg.vocab_size, seed=seed, arrival="burst",
        burst_size=8, rate_rps=1.0, prompt_len_mean=48.0,
        prompt_len_sigma=0.9, prompt_len_min=8, prompt_len_max=320,
        output_len_mean=40.0, output_len_sigma=0.6, output_len_min=4,
        output_len_max=128,
        priority_mix={0: high_frac, 1: 1.0 - high_frac})
    items = gen.generate(n_requests)
    mu = calibrate_service_rate(make_loop_factory(None), items,
                                step_dt=1.0)
    gen = gen.with_rate(rho * mu)
    items = gen.generate(n_requests)

    def run(pre):
        res, outputs, s, series = _run_openloop_arm(
            make_loop_factory(pre), items)
        high = [r for r in res.requests if r.priority == 0]
        return res, outputs, s, [r.ttft for r in high]

    res_off, outs_off, s_off, high_off = run(None)
    # the TTFT SLA target both arms are judged against: anchored to
    # the no-preemption arm's high-priority median (+1 virtual step —
    # virtual time quantizes to whole steps), so the off arm has
    # violations to beat and the target is meaningful per seed/backend
    target = float(np.median(high_off)) + 1.0
    pre = PreemptionConfig(enabled=True, ttft_slo_s=target,
                           urgency_fraction=0.5)
    res_on, outs_on, s_on, high_on = run(pre)

    if outs_on != outs_off:
        bad = [i for i, (a, b) in enumerate(zip(outs_off, outs_on))
               if a != b]
        raise RuntimeError(
            f"preemption changed outputs for requests {bad}: "
            f"swap-or-recompute resume must be bit-for-bit")
    if s_on["preemptions"] < 1:
        raise RuntimeError(
            "preemption arm never preempted: the burst mix failed to "
            "create an urgent high-priority admission")
    if s_on["kv_swapped_out"] < 1:
        raise RuntimeError(
            "no live KV was swapped out: the preemption served only "
            "the recompute path — the row must exercise the host-tier "
            "swap")
    viol_off = sum(1 for x in high_off if x > target)
    viol_on = sum(1 for x in high_on if x > target)
    if viol_off == 0:
        raise RuntimeError(
            f"no-preemption arm shows no high-priority TTFT violations "
            f"against target {target:.1f} vs: the offered load is too "
            f"light to measure preemption")
    if viol_on >= viol_off:
        raise RuntimeError(
            f"preemption did not reduce high-priority TTFT SLA "
            f"violations ({viol_on} vs {viol_off} at target "
            f"{target:.1f} vs on the identical schedule)")
    goodput = s_on["goodput_tok_s"]
    extras = {
        "requests": n_requests, "rho": rho, "seed": seed,
        "service_rate_rps": round(mu, 4),
        "high_priority_frac": high_frac,
        "sla_ttft_target_vs": round(target, 2),
        "high_ttft_violations_off": viol_off,
        "high_ttft_violations_on": viol_on,
        "high_ttft_p95_off_vs": round(float(np.percentile(
            high_off, 95)), 2),
        "high_ttft_p95_on_vs": round(float(np.percentile(
            high_on, 95)), 2),
        "preemptions": s_on["preemptions"],
        "kv_swapped_out_blocks": s_on["kv_swapped_out"],
        "kv_swapped_in_blocks": s_on["kv_swapped_in"],
        "goodput_preempt_off_vs": round(s_off["goodput_tok_s"], 3),
        "rejected": 0, "lost_requests": 0,
        "workload": gen.describe(),
        "time_base": "virtual (1 serve step = 1 s; see docstring)",
        "model": "tiny",
    }
    return goodput, extras


def _lora_factors(cfg, n_adapters: int, rank: int = 4, seed: int = 1):
    """Deterministic tiny LoRA factor sets for the tenancy rows:
    a [L, K, r] down / b [L, r, H] up per adapter, scaled small enough
    that adapter outputs stay finite but visibly diverge from base."""
    rng = np.random.RandomState(seed)
    L, H = cfg.num_layers, cfg.hidden_size
    out = []
    for _ in range(n_adapters):
        a = (0.05 * rng.randn(L, H, rank)).astype(np.float32)
        b = rng.randn(L, rank, H).astype(np.float32)
        out.append((a, b))
    return out


def bench_serving_tenants_closed(n_requests: int = 16, max_seqs: int = 4,
                                 decode_burst: int = 8,
                                 new_tokens: int = 8, seed: int = 0):
    """Multi-tenant serving row (`serve_tenants_c8`, ISSUE 16): one
    tiny-f32 base model serving three tenants' LoRA adapters from a
    single continuous batch, closed loop, vs the SAME stream through a
    plain single-tenant loop on the same engine.

    The adapter pool is sized for TWO resident adapters (8 blocks at 4
    blocks/adapter) and THREE are registered, so the pool's LRU demotes
    the coldest to the host spill tier at register time and admission's
    `reserve()` promotes it back when its tenant's request arrives —
    the paged-residency lifecycle under the real serve loop.

    In-row acceptance contract (ISSUE 16): requests with
    `adapter_id=None` under the enabled pool decode BIT-FOR-BIT the
    plain loop's tokens (the LoRA epilogue contributes exactly zero for
    base rows), adapter rows diverge from base (the epilogue actually
    ran), at least one demote AND one promote fired with zero adapters
    dropped, zero lost requests, zero leaked KV blocks, pool
    conservation audit clean, zero adapter reservations still pinned
    after drain, and the per-tenant telemetry accounts every request.
    Value = the tenancy arm's goodput (same CPU-backend wall-time
    caveat as the other closed-loop rows)."""
    from deepspeed_tpu.config.config import (ServingConfig, TenancyConfig,
                                             TracingConfig)
    from deepspeed_tpu.serving import ServeLoop

    import jax.numpy as jnp

    eng, cfg = _engine(1024, max_seqs=max_seqs,
                       decode_burst=max(decode_burst, 16), size="tiny",
                       dtype=jnp.float32, full_prompt_prefill=False)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size,
                           64 if i % 2 else 32).astype(np.int32)
               for i in range(n_requests)]
    adapters = _lora_factors(cfg, 3, seed=seed + 1)
    adapter_ids = ["lora_a", "lora_b", "lora_c"]
    # every 4th request is a base-model row (the parity probe); the
    # rest cycle all three adapters so the spilled one gets promoted
    plan = [None if i % 4 == 0 else adapter_ids[i % 3]
            for i in range(n_requests)]

    def run_plain():
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=2 * n_requests, decode_burst=decode_burst,
            audit_blocks=True,
            tracing=TracingConfig(enabled=False, metrics_ring=8192)))
        reqs = [loop.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        t0 = time.perf_counter()
        while loop.has_work:
            loop.step()
        dt = time.perf_counter() - t0
        loop.engine.audit_blocks()
        return [list(r.output_tokens) for r in reqs], dt

    def run_tenancy():
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=2 * n_requests, decode_burst=decode_burst,
            audit_blocks=True,
            tenancy=TenancyConfig(
                enabled=True, adapter_pool_blocks=8,
                host_spill_blocks=16, weights={"t0": 2.0}),
            tracing=TracingConfig(enabled=False, metrics_ring=8192)))
        for aid, (a, b) in zip(adapter_ids, adapters):
            loop.register_adapter(aid, a, b)
        pool = loop.adapter_pool
        if pool.demotes < 1:
            raise RuntimeError(
                f"registering {len(adapter_ids)} adapters into a "
                f"2-slot pool demoted nothing (demotes="
                f"{pool.demotes}): the row must exercise the spill "
                f"tier")
        reqs = [loop.submit(p, max_new_tokens=new_tokens,
                            tenant=f"t{i % 3}", adapter_id=plan[i])
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        while loop.has_work:
            loop.step()
        dt = time.perf_counter() - t0
        loop.engine.audit_blocks()
        pool.audit()
        if pool._pins:
            raise RuntimeError(
                f"adapter reservations leaked past drain: {pool._pins}")
        return ([list(r.output_tokens) for r in reqs], dt, pool.stats(),
                loop.telemetry.summary())

    outs_plain, dt_plain = run_plain()
    outs_ten, dt_ten, pstats, s = run_tenancy()

    base_rows = [i for i, aid in enumerate(plan) if aid is None]
    bad = [i for i in base_rows if outs_ten[i] != outs_plain[i]]
    if bad:
        raise RuntimeError(
            f"adapter_id=None rows {bad} diverged from the plain loop: "
            f"the enabled pool must be bit-for-bit base for base rows")
    lora_rows = [i for i, aid in enumerate(plan) if aid is not None]
    if all(outs_ten[i] == outs_plain[i] for i in lora_rows):
        raise RuntimeError(
            "no adapter row diverged from the base model: the LoRA "
            "epilogue never contributed — the row is not serving "
            "adapters at all")
    if pstats["adapter_promotes"] < 1:
        raise RuntimeError(
            f"no promote fired (stats {pstats}): a spilled adapter's "
            f"tenant was served without its weights returning to HBM")
    if pstats["adapter_dropped"]:
        raise RuntimeError(
            f"{pstats['adapter_dropped']} adapter(s) dropped: the host "
            f"tier is sized to hold every eviction in this row")
    tstats = s["tenants"]
    done_by_tenant = {t: v["completed"] for t, v in tstats.items()}
    if sum(done_by_tenant.values()) != n_requests:
        raise RuntimeError(
            f"per-tenant telemetry lost requests: {done_by_tenant} "
            f"!= {n_requests} submitted")
    goodput = n_requests * new_tokens / dt_ten
    extras = {
        "requests": n_requests, "tenants": len(done_by_tenant),
        "adapters": len(adapter_ids),
        "goodput_plain": round(n_requests * new_tokens / dt_plain, 2),
        "base_parity_rows": len(base_rows),
        "adapter_rows": len(lora_rows),
        "adapter_demotes": pstats["adapter_demotes"],
        "adapter_promotes": pstats["adapter_promotes"],
        "adapter_resident": pstats["adapter_resident"],
        "adapter_spilled": pstats["adapter_spilled"],
        "completed_by_tenant": done_by_tenant,
        "lost_requests": 0,
        "new_tokens": new_tokens, "model": "tiny",
    }
    return goodput, extras


def bench_serving_tenants_openloop(n_requests: int = 48, seed: int = 0,
                                   rho: float = 2.5, max_seqs: int = 4,
                                   decode_burst: int = 8):
    """Tenant-QoS overload row (`serve_tenants_openloop`, ISSUE 16): a
    seeded 3-tenant Poisson mix (mild Zipf skew, 25% of requests
    through per-tenant LoRA adapters) offered at rho > 1 on
    deterministic virtual time, served twice on IDENTICAL schedules —
    flat weights vs tenant t0 at WFQ weight 4 — with tenant t2
    rate-limited to a quarter of the measured service rate in BOTH
    arms.

    In-row acceptance contract (ISSUE 16): greedy outputs bit-identical
    across arms (WFQ moves WHEN a request is admitted, never what it
    computes), the same arrivals shed in both arms (the bucket meters
    arrival times, which the arms share), t2's sheds > 0 with its
    admitted count inside the token-bucket bound (burst + rate *
    elapsed), every shed accounted (admitted + shed = offered), zero
    lost accepted requests, zero leaked KV blocks, zero pinned adapter
    reservations after drain, and the weighted tenant's TTFT SLA
    violations STRICTLY FEWER than the flat arm's against the same
    target on the identical schedule.  Value = the weighted arm's
    virtual goodput (same virtual-time caveat as the other open-loop
    rows)."""
    from deepspeed_tpu.config.config import (ServingConfig, TenancyConfig,
                                             TracingConfig)
    from deepspeed_tpu.serving import ServeLoop, VirtualClock
    from deepspeed_tpu.serving.observatory import (
        WorkloadGenerator, calibrate_service_rate)

    import jax.numpy as jnp

    eng, cfg = _engine(1024, max_seqs=max_seqs,
                       decode_burst=max(decode_burst, 16), size="tiny",
                       dtype=jnp.float32, full_prompt_prefill=False)
    adapters = _lora_factors(cfg, 3, seed=seed + 1)

    def make_plain(queue_len: int = 512):
        clock = VirtualClock()
        loop = ServeLoop(eng, ServingConfig(
            max_queue_len=queue_len, decode_burst=decode_burst,
            audit_blocks=True,
            tracing=TracingConfig(enabled=False, metrics_ring=8192)),
            clock=clock)
        return loop, clock

    def make_tenancy_factory(weights, limit_rps):
        def make_loop(queue_len: int = 512):
            clock = VirtualClock()
            loop = ServeLoop(eng, ServingConfig(
                max_queue_len=queue_len, decode_burst=decode_burst,
                audit_blocks=True,
                tenancy=TenancyConfig(
                    enabled=True, adapter_pool_blocks=16,
                    rate_limits={"t2": limit_rps}, burst_s=2.0,
                    weights=weights),
                tracing=TracingConfig(enabled=False,
                                      metrics_ring=8192)), clock=clock)
            for t, (a, b) in enumerate(adapters):
                loop.register_adapter(f"lora_t{t}", a, b)
            return loop, clock
        return make_loop

    gen = WorkloadGenerator(
        vocab_size=cfg.vocab_size, seed=seed, arrival="poisson",
        rate_rps=1.0, prompt_len_mean=48.0, prompt_len_sigma=0.9,
        prompt_len_min=8, prompt_len_max=320, output_len_mean=12.0,
        output_len_sigma=0.6, output_len_min=2, output_len_max=48,
        num_tenants=3, tenant_zipf_a=0.3, adapter_frac=0.25)
    items = gen.generate(n_requests)
    mu = calibrate_service_rate(make_plain, items, step_dt=1.0)
    gen = gen.with_rate(rho * mu)
    items = gen.generate(n_requests)
    limit_rps = 0.25 * mu
    burst = max(1.0, 2.0 * limit_rps)
    offered = {"t0": 0, "t1": 0, "t2": 0}
    for it in items:
        offered[it.tenant] += 1

    def run(weights):
        res, outputs, s, series = _run_openloop_arm(
            make_tenancy_factory(weights, limit_rps), items)
        t0_ttft = [r.ttft for r in res.requests if r.tenant == "t0"]
        return res, outputs, s, t0_ttft

    res_flat, outs_flat, s_flat, t0_flat = run({})
    res_w, outs_w, s_w, t0_w = run({"t0": 4.0})

    if outs_w != outs_flat:
        bad = [i for i, (a, b) in enumerate(zip(outs_flat, outs_w))
               if a != b]
        raise RuntimeError(
            f"tenant weighting changed outputs for requests {bad}: WFQ "
            f"must reorder admission, never the math")
    shed = res_flat.rejected_rate_limited
    if shed != res_w.rejected_rate_limited:
        raise RuntimeError(
            f"arms shed differently ({shed} vs "
            f"{res_w.rejected_rate_limited}): the bucket meters the "
            f"shared arrival schedule, so sheds must match")
    if shed < 1:
        raise RuntimeError(
            f"tenant t2 never shed at limit {limit_rps:.3f} rps "
            f"against {offered['t2']} offered requests: the row must "
            f"exercise the rate limiter")
    for res, s, name in ((res_flat, s_flat, "flat"),
                        (res_w, s_w, "weighted")):
        adm = s["tenants"]["t2"]["admitted"]
        bound = burst + limit_rps * res.elapsed_s + 1.0
        if adm > bound:
            raise RuntimeError(
                f"{name} arm admitted {adm} t2 requests, above the "
                f"token-bucket bound {bound:.1f} (burst {burst:.1f} + "
                f"{limit_rps:.3f}/s over {res.elapsed_s:.0f} vs)")
        if adm + shed != offered["t2"]:
            raise RuntimeError(
                f"{name} arm lost t2 accounting: {adm} admitted + "
                f"{shed} shed != {offered['t2']} offered")
    # the TTFT SLA target both arms are judged against: anchored to
    # the flat arm's t0 median (+1 virtual step — virtual time
    # quantizes to whole steps), the preempt row's anchoring discipline
    target = float(np.median(t0_flat)) + 1.0
    viol_flat = sum(1 for x in t0_flat if x > target)
    viol_w = sum(1 for x in t0_w if x > target)
    if viol_flat == 0:
        raise RuntimeError(
            f"flat arm shows no t0 TTFT violations against target "
            f"{target:.1f} vs: the offered load is too light to "
            f"measure WFQ")
    if viol_w >= viol_flat:
        raise RuntimeError(
            f"weight 4 did not reduce t0's TTFT SLA violations "
            f"({viol_w} vs {viol_flat} at target {target:.1f} vs on "
            f"the identical schedule)")
    goodput = s_w["goodput_tok_s"]
    extras = {
        "requests": n_requests, "rho": rho, "seed": seed,
        "service_rate_rps": round(mu, 4),
        "t2_limit_rps": round(limit_rps, 4),
        "offered_by_tenant": offered,
        "rate_limited_shed": shed,
        "t2_admitted": s_w["tenants"]["t2"]["admitted"],
        "sla_ttft_target_vs": round(target, 2),
        "t0_ttft_violations_flat": viol_flat,
        "t0_ttft_violations_weighted": viol_w,
        "t0_ttft_p95_flat_vs": round(float(np.percentile(
            t0_flat, 95)), 2),
        "t0_ttft_p95_weighted_vs": round(float(np.percentile(
            t0_w, 95)), 2),
        "goodput_flat_vs": round(s_flat["goodput_tok_s"], 3),
        "adapter_frac": gen.adapter_frac,
        "rejected": 0, "lost_requests": 0,
        "workload": gen.describe(),
        "time_base": "virtual (1 serve step = 1 s; see docstring)",
        "model": "tiny",
    }
    return goodput, extras


def main():
    import argparse
    import jax
    from deepspeed_tpu.utils.device import place_compile_cache
    from deepspeed_tpu.utils.tpu_claim import require_tpu

    ap = argparse.ArgumentParser(
        description="serving benchmark (one JSON line per row)")
    ap.add_argument("--rows", default=None,
                    help="comma-separated row keys to run (default: all; "
                         "latency_c* rows run only with no filter)")
    ap.add_argument("--trace-out", default=None,
                    help="persist the chaos row's request traces as a "
                         "perfetto-loadable Chrome-trace JSON artifact "
                         "at this path (runs the row with tracing on)")
    ap.add_argument("--note", default="",
                    help="free-text note recorded in BENCH_SERVE_r0N.json")
    ap.add_argument("--size", default=None,
                    help="model preset override for the serve_closed_c8 "
                         "and serve_fleet_chaos_c8x3 rows (e.g. 'tiny' "
                         "for a CPU-backend partial round; default: each "
                         "row's recorded configuration)")
    ap.add_argument("--emit-only", action="store_true",
                    help="print row JSON but skip BENCH_SERVE_r0N "
                         "persistence")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload-generator seed for the open-loop "
                         "rows (serve_openloop_*): same seed = "
                         "bit-identical arrival schedule and prompts")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the BENCH_TRAJECTORY.json auto-append "
                         "after persisting this round's rows "
                         "(benchmarks/bench_history.py)")
    args = ap.parse_args()
    size_kw = {} if args.size is None else {"size": args.size}
    require_tpu()
    place_compile_cache()

    rows = [
        ("decode_single_ctx2048", "decode tokens/sec (GPT-2-medium, 8 seqs,"
         " ctx 2048, 1 host dispatch/token)",
         lambda: bench_decode_single(2048)),
        ("decode_burst_b8_ctx2048", "decode tokens/sec (GPT-2-medium, "
         "8 seqs, ctx 2048, on-device sampled burst, fused kernel)",
         lambda: bench_decode_burst(2048, B=8, burst=64)),
        ("decode_burst32_ctx2048", "decode tokens/sec (GPT-2-medium, "
         "32 seqs, ctx 2048, on-device sampled burst, merged arena)",
         lambda: bench_decode_burst(2048)),
        ("decode_burst32_ctx8192", "decode tokens/sec (GPT-2-medium, "
         "8 seqs, ctx 8192, on-device sampled burst, merged arena)",
         lambda: bench_decode_burst(8192, B=8)),
        ("decode_774m_bf16", "decode tokens/sec (GPT-2-large 774M, "
         "16 seqs, ctx 2048, bf16 weights, on-device burst)",
         lambda: bench_decode_774m()),
        ("decode_774m_fp8", "decode tokens/sec (GPT-2-large 774M, "
         "16 seqs, ctx 2048, fp8 layer weights, on-device burst)",
         lambda: bench_decode_774m(weights="fp8")),
        ("decode_burst_ctx16k", "decode tokens/sec (GPT-2-medium, 2 seqs, "
         "ctx 16384, on-device sampled burst, merged arena)",
         lambda: bench_decode_burst(16384, B=2, burst=32, rounds=2)),
        ("decode_1p3b_bf16", "decode tokens/sec (GPT-2-1.3B north-star, "
         "8 seqs, ctx 2048, bf16 weights, on-device burst)",
         lambda: bench_decode_burst(2048, B=8, burst=32, size="1.3b")),
        ("decode_1p3b_fp8", "decode tokens/sec (GPT-2-1.3B north-star, "
         "8 seqs, ctx 2048, fp8 layer weights, on-device burst)",
         lambda: bench_decode_burst(2048, B=8, burst=32, size="1.3b",
                                    weights="fp8")),
        ("prefill_ctx8192", "prefill tokens/sec (GPT-2-medium, 8k prompt, "
         "blocked-flash)", lambda: bench_prefill(8192)),
        ("load_c8", "generated tokens/sec at load (8 concurrent requests, "
         "512+64)", lambda: bench_load(8)),
        ("load_c32", "generated tokens/sec at load (32 concurrent "
         "requests, 512+64)", lambda: bench_load(32)),
        ("serve_closed_c8", "goodput tokens/sec through the serving layer "
         "(closed loop, 8 clients x 2 requests, mixed 128/512 prompts, "
         "16 new tokens; extras carry p50/p95 TTFT + e2e and the "
         "measured request-tracing + observatory-sampling overheads, "
         "each asserted < 5%)",
         lambda: bench_serving_closed_loop(trace_overhead=True,
                                           observatory_overhead=True,
                                           **size_kw)),
        ("serve_burst_c8", "goodput tokens/sec through the serving layer "
         "with fused on-device burst decode (same closed loop + zero-loss "
         "assert, decode_burst 16 — logits never leave the device during "
         "decode)",
         lambda: bench_serving_closed_loop(decode_burst=16)),
        ("serve_prefix_c8", "goodput tokens/sec through the serving layer "
         "with radix prefix KV reuse (shared 256-token system prompt + "
         "unique 128-token tails, identical stream vs cache-off; asserts "
         "hit rate > 0, >= 50% prefill-token reduction, bit-for-bit "
         "outputs, zero leaked blocks)",
         lambda: bench_serving_prefix()),
        ("serve_tier_c8", "goodput tokens/sec through the serving layer "
         "with the HBM -> host KV spill tier (rotating 4-group shared "
         "prefixes churning a 6-block HBM cache, identical stream: "
         "cache-off vs HBM-only vs tiered; asserts strictly higher hit "
         "rate and strictly fewer prefill tokens than HBM-only, "
         "bit-for-bit outputs across all arms under "
         "host_cache_quant='none', demote+promote exercised, zero "
         "leaked blocks in both tiers)",
         lambda: bench_serving_tier()),
        ("serve_spec_c8", "goodput tokens/sec through the serving layer "
         "with speculative decoding (prompt-lookup drafts + on-device "
         "verify, templated 192+16 prompts, identical stream vs "
         "spec-off; asserts bit-for-bit greedy outputs, zero lost "
         "requests, zero leaked blocks; extras carry decode tok/s both "
         "ways, acceptance rate, tokens/dispatch)",
         lambda: bench_serving_spec()),
        ("serve_fleet_c8x2", "goodput tokens/sec through a 2-replica "
         "cache-aware fleet (serving.fleet: prefix-index routing, same "
         "closed shared-system-prompt loop vs round-robin; asserts fleet "
         "hit rate > round-robin's, fewer prefill tokens, bit-for-bit "
         "outputs, zero lost requests, zero leaked blocks per replica)",
         lambda: bench_serving_fleet()),
        ("serve_fleet_chaos_c8x3", "goodput tokens/sec through a "
         "3-replica SUPERVISED fleet with replica 1 killed mid-stream "
         "(serving.fleet supervisor: heartbeat health + automatic "
         "drain/adopt failover, no operator call; asserts zero lost "
         "accepted requests, every waiter resolved, zero leaked blocks "
         "on survivors, bit-for-bit outputs vs round-robin, hit rate "
         "still above round-robin's; --trace-out additionally runs it "
         "traced and persists the perfetto failover-span artifact)",
         lambda: bench_serving_fleet_chaos(trace_out=args.trace_out,
                                           **size_kw)),
        ("serve_smallctx_c8", "goodput tokens/sec through the serving "
         "layer on a SUB-2048-key arena (1024 keys/seq — the budget the "
         "retired auto-gate served via the dense XLA gather; closed "
         "loop, 8 clients x 2 requests, mixed 129/65 prompts, full-range "
         "kernel arm vs attn_impl='jnp' dense arm; asserts bit-for-bit "
         "outputs, zero lost requests, zero leaked blocks)",
         lambda: bench_serving_smallctx()),
        ("serve_disagg_c8x3", "goodput tokens/sec through a "
         "disaggregated 1-prefill + 2-decode fleet "
         "(serving.fleet.disagg: prompts run to completion on the "
         "prefill pool, finished KV streams to the decode pool via "
         "batched block migration, same Request adopted across pools; "
         "mixed long-prompt/long-decode stream vs the unified "
         "3-replica fleet — asserts bit-for-bit outputs, zero lost "
         "requests, zero leaked blocks everywhere, and strictly lower "
         "decode TPOT p95 than unified)",
         lambda: bench_serving_disagg()),
        ("serve_tp_c2", "goodput tokens/sec through tensor-parallel "
         "serving on a 2-device mesh (tp=2 fused ring "
         "compute-collective matmuls vs tp=2 stock-XLA collectives vs "
         "tp=1, identical greedy closed loop; asserts bit-for-bit "
         "outputs across all three arms, zero lost requests, zero "
         "leaked blocks per engine)",
         lambda: bench_serving_tp()),
        ("serve_stream_c8", "goodput tokens/sec through the serving "
         "layer with token streaming (identical greedy closed loop "
         "streaming-off vs -on, one event-driven consumer thread per "
         "request; asserts bit-for-bit outputs across arms, every "
         "consumer's sequence exactly the request's output — gap-free, "
         "duplicate-free — zero lost requests, zero leaked blocks; "
         "extras carry TTFT + the new inter-token-latency p50/p95 and "
         "the measured streaming overhead)",
         lambda: bench_serving_stream()),
        ("serve_multistep_c8", "goodput tokens/sec through multi-step "
         "decode groups (identical greedy stream at multi_step 1 vs 8 "
         "vs 16 — K decode steps per compiled dispatch, on-device "
         "sampling + EOS/budget termination, ONE packed d2h fetch per "
         "group; asserts bit-for-bit outputs across all k, zero lost "
         "requests, zero leaked blocks, and >= 4x fewer explicit d2h "
         "transfers per generated token at k=8 vs the per-token loop)",
         lambda: bench_serving_multistep()),
        ("serve_grammar_c8", "goodput tokens/sec through grammar-"
         "constrained multi-step decode (even requests locked to a "
         "JSON-schema token automaton, masks applied inside the k=8 "
         "scan with per-row FSM state in the carry; asserts every "
         "constrained chain machine-accepted + EOS-terminated, "
         "unconstrained rows bit-for-bit the grammar-off arm, "
         "IDENTICAL d2h fetches per multi-step dispatch across arms — "
         "the grammar adds zero host round trips — zero lost "
         "requests, zero leaked blocks)",
         lambda: bench_serving_grammar()),
        ("serve_moe_c8", "goodput tokens/sec through expert-paged MoE "
         "decode (qwen_v2_moe tiny: 4 experts, top-2 router, slotted "
         "HBM expert pages with host demotion + census-driven "
         "promotion; asserts paged arm bit-for-bit the moe=None arm, "
         "demote+promote exercised per layer with zero router drops, "
         "pool conservation audit green in every phase, zero pinned "
         "reservations after drain, zero lost requests, zero leaked "
         "blocks)",
         lambda: bench_serving_moe()),
        ("serve_preempt_openloop","virtual-time goodput with "
         "SLO-aware preemption under OPEN-loop burst load at rho=2 "
         "(identical seeded schedules preemption-off vs -on; asserts "
         "strictly fewer high-priority TTFT SLA violations, at least "
         "one live-KV swap through the host tier, bit-identical "
         "outputs across arms, zero lost requests, zero leaked "
         "blocks)",
         lambda: bench_serving_preempt_openloop(seed=args.seed)),
        ("serve_tenants_c8", "goodput tokens/sec through multi-tenant "
         "serving (serving/tenancy: 3 tenants' LoRA adapters from one "
         "continuous batch, 2-slot paged adapter pool + host spill "
         "tier, closed loop vs the plain loop on the same stream; "
         "asserts adapter_id=None rows bit-for-bit base, adapter rows "
         "diverge, demote+promote exercised with zero drops, zero "
         "lost requests, zero leaked KV blocks, pool audit clean, "
         "zero pinned reservations after drain, per-tenant telemetry "
         "accounts every request)",
         lambda: bench_serving_tenants_closed()),
        ("serve_tenants_openloop", "virtual-time goodput under tenant "
         "QoS at OPEN-loop rho=2.5 (3-tenant Zipf mix, 25% LoRA "
         "traffic, identical seeded schedules flat vs t0 at WFQ "
         "weight 4, t2 rate-limited in both arms; asserts bit-identical "
         "outputs across arms, t2 sheds > 0 inside the token-bucket "
         "bound with every shed accounted, strictly fewer t0 TTFT SLA "
         "violations under weight 4, zero lost accepted requests, "
         "zero leaked blocks, zero pinned adapter reservations)",
         lambda: bench_serving_tenants_openloop(seed=args.seed)),
        ("serve_openloop_c8", "virtual-time goodput under OPEN-loop "
         "Poisson load at rho=0.85 (serving.observatory: seeded "
         "heavy-tailed workload with shared-prefix + priority mixes "
         "submitted on schedule regardless of completions; metric "
         "time series + recompile flight recorder armed; asserts zero "
         "lost/rejected requests, zero leaked blocks)",
         lambda: bench_serving_openloop(seed=args.seed)),
        ("serve_openloop_sweep", "virtual-time capacity from the "
         "open-loop offered-load sweep (rho ramp over the measured "
         "service rate; asserts bit-stable outputs across arms + "
         "replay, zero loss/leaks per arm, monotone utilization and "
         "queue depth through the ramp, and TTFT SLA-violation onset "
         "at the overloaded arm — the queueing-collapse knee closed "
         "loops cannot show)",
         lambda: bench_serving_openloop_sweep(seed=args.seed)),
        ("serve_openloop_tier", "virtual-time capacity with the host "
         "KV tier under OPEN-loop shared-prefix load (identical seeded "
         "arrival schedules per rho, HBM-only vs tiered arms on a "
         "prefill-step-capped engine; asserts bit-stable outputs "
         "across arms and rhos, zero loss/leaks both tiers, strictly "
         "higher tiered hit rate, strictly fewer TTFT SLA violations "
         "and a no-earlier violation onset — the knee moves right)",
         lambda: bench_serving_openloop_tier(seed=args.seed)),
    ]
    if len(jax.devices()) < 2:
        # the TP row needs two devices in one process; no child stands in
        rows = [r for r in rows if r[0] != "serve_tp_c2"]
    wanted = (None if args.rows is None
              else {k.strip() for k in args.rows.split(",") if k.strip()})
    if wanted is not None:
        unknown = wanted - {key for key, _, _ in rows}
        if unknown:
            raise SystemExit(f"--rows: unknown row key(s) {sorted(unknown)}")
        rows = [r for r in rows if r[0] in wanted]
    if args.trace_out and not any(key == "serve_fleet_chaos_c8x3"
                                  for key, _, _ in rows):
        raise SystemExit(
            "--trace-out produces the chaos row's trace artifact, but "
            "serve_fleet_chaos_c8x3 is filtered out by --rows — nothing "
            "would be written")
    persisted = []
    for key, metric, fn in rows:
        value, extras = fn()
        rec = RECORDED.get(key)
        row = {"metric": metric, "value": round(value, 1),
               "unit": "tokens/s",
               "vs_recorded": round(value / rec, 3) if rec else None}
        row.update(extras)
        row["key"] = key
        print(json.dumps(row), flush=True)
        persisted.append(row)

    if wanted is not None:
        # filtered partial round: skip the latency sweep + SLA row
        if not args.emit_only:
            persist_rows(persisted, note=args.note,
                         history=not args.no_history)
        return
    # latency percentiles per load level + the SLA row
    sla_best = None
    for B in (4, 8, 16, 32):
        p95, extras = bench_latency(B)
        k = f"latency_c{B}"
        rec = RECORDED.get(k)
        row = {"metric": f"p95 ms/token ({B} concurrent seqs, "
               f"ctx 2048, burst 16)", "value": round(p95, 3),
               "unit": "ms/token",
               "vs_recorded": round(p95 / rec, 3) if rec else None}
        row.update(extras)
        row["key"] = k
        print(json.dumps(row), flush=True)
        persisted.append(row)
        if p95 <= SLA_MS_PER_TOK:
            sla_best = B
    print(json.dumps({
        "metric": f"max tested load with p95 <= {SLA_MS_PER_TOK} ms/token "
        f"(FastGen throughput-at-SLA shape)",
        "value": sla_best or 0, "unit": "concurrent seqs",
        "vs_recorded": None}), flush=True)
    if not args.emit_only:
        persist_rows(persisted, note=args.note,
                     history=not args.no_history)


def persist_rows(rows, note: str = "", history: bool = True) -> str:
    """Write this round's measured rows to the next free
    `BENCH_SERVE_r0N.json` beside this script, so the serving perf
    trajectory is machine-readable across rounds (the BENCH_r0N.json
    discipline, extended to the serving benchmark), then fold the new
    round into `BENCH_TRAJECTORY.json` (the ISSUE 13 perf-regression
    ledger; `history=False` / `--no-history` opts out).  The backend
    caveat is stamped PER ROW — a partial round re-measured on
    different hardware must not inherit the document-level backend.
    Returns the artifact path."""
    import datetime
    import os
    backend = __import__("jax").default_backend()
    for row in rows:
        row.setdefault("backend", backend)
    here = os.path.dirname(os.path.abspath(__file__))
    n = 1
    while os.path.exists(os.path.join(here,
                                      f"BENCH_SERVE_r{n:02d}.json")):
        n += 1
    path = os.path.join(here, f"BENCH_SERVE_r{n:02d}.json")
    doc = {
        "round": n,
        "date": datetime.date.today().isoformat(),
        "backend": backend,
        "note": note,
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({"persisted": path}), flush=True)
    if history:
        from deepspeed_tpu.benchmarks import bench_history
        traj = bench_history.rebuild(here)
        report, rc = bench_history.check_latest(here)
        print(json.dumps({"trajectory": traj,
                          "regression_gate": "FAIL" if rc else "ok",
                          "verdicts": {r["row"]: r["verdict"]
                                       for r in report}}), flush=True)
        if rc:
            # the round IS persisted (the measurement happened and the
            # trajectory records it) but the process must exit loudly —
            # a swallowed gate is exactly the unread-JSON failure mode
            # the ledger exists to end.  Stamp the artifact gate_failed
            # FIRST (and fold the stamp into the trajectory), so this
            # round's regressed values never become part of the noise
            # band an unfixed re-run would be judged against.
            bench_history.mark_gate_failed(path)
            bench_history.rebuild(here)
            raise RuntimeError(
                f"perf-regression gate failed for {path}: "
                f"{[r['row'] for r in report if r['verdict'] in ('regressed', 'unit_mismatch')]} "
                f"outside the trajectory noise band (dstpu_bench "
                f"--history --check; --no-history to bypass)")
    return path


if __name__ == "__main__":
    main()
