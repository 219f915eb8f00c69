"""The serving layer end-to-end (the reference's MII serve quick-start).

Run:  python examples/serve_requests.py [--shared-system-prompt]
Submits a mixed stream of requests — different lengths, priorities, a
deadline, and a cancellation — through `deepspeed_tpu.serving.ServeLoop`
and prints the per-request SLAs the telemetry measured.

`--shared-system-prompt` prepends one fixed 128-token system prompt to
every request and turns on the radix prefix KV cache
(`prefix_cache_blocks`): the first request prefills and caches the
shared KV, every later one attaches it read-only and prefills only its
own tail — the summary then shows the hit rate and prefill tokens
saved.
"""
import argparse
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deepspeed_tpu import ServingConfig
from deepspeed_tpu.inference.v2 import (build_engine,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.serving import ServeLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shared-system-prompt", action="store_true",
                    help="prepend a shared 128-token system prompt to "
                         "every request and enable prefix KV reuse")
    ap.add_argument("--host-cache-blocks", type=int, default=0,
                    help="with --shared-system-prompt: attach a "
                         "host-memory KV spill tier of this many blocks "
                         "behind the prefix cache (and shrink the HBM "
                         "cache budget so eviction actually demotes) — "
                         "the summary then shows demotions/promotions "
                         "and host occupancy (docs/serving.md "
                         "\"KV-cache tiering\")")
    ap.add_argument("--transfer-guard", default="off",
                    choices=("off", "log", "disallow"),
                    help="run every serve step under jax's device->host "
                         "transfer guard: an accidental host sync in the "
                         "hot path logs or raises at the offending call "
                         "(docs/ANALYSIS.md)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative decoding: prompt-lookup drafts "
                         "verified on device (greedy outputs bit-identical "
                         "to spec-off); the summary then shows the "
                         "acceptance rate and tokens per verify dispatch")
    ap.add_argument("--multi-step", action="store_true",
                    help="multi-step decode groups: k=8 decode steps "
                         "per compiled dispatch with on-device sampling "
                         "AND on-device EOS/budget termination — the "
                         "host sees one packed fetch per group (one "
                         "request rides a seeded stochastic stream to "
                         "show the device-side Philox draws); the "
                         "summary then shows d2h fetches per generated "
                         "token (docs/serving.md \"Multi-step decode "
                         "groups\")")
    ap.add_argument("--stream", action="store_true",
                    help="token streaming: attach a TokenStream to "
                         "every request and print tokens as they are "
                         "delivered (exactly-once, event-driven — "
                         "docs/serving.md \"Token streaming & "
                         "preemption\"); the summary then shows the "
                         "inter-token-latency percentiles")
    ap.add_argument("--tenants", action="store_true",
                    help="multi-tenant serving: three tenants share the "
                         "base model through one continuous batch, two "
                         "decode through their own paged LoRA adapters "
                         "(3 adapters into a 2-slot HBM pool, the cold "
                         "one spilled to host pages), 'gold' carries a "
                         "4x weighted-fair share and 'free' is "
                         "rate-limited; the summary shows per-tenant "
                         "counters and the adapter pool's demote/"
                         "promote traffic (docs/serving.md "
                         "\"Multi-tenant serving\")")
    ap.add_argument("--moe", action="store_true",
                    help="expert-paged MoE decode: a tiny qwen2-moe "
                         "model (4 experts, top-2 router) serves with "
                         "fewer HBM expert slots than experts — the "
                         "router census drains every 2 steps and "
                         "rebalances residency (LRU demote to host, "
                         "bounded promote), non-resident demand "
                         "degrades to rerouting; the summary shows the "
                         "serving/expert/* gauges and the pool's "
                         "conservation audit (docs/serving.md "
                         "\"Expert-paged decode\")")
    ap.add_argument("--json-schema", action="store_true",
                    help="structured generation: constrain requests to "
                         "a JSON schema and a regex (serving/structured "
                         "— the grammar compiles once to a token "
                         "automaton whose mask rides INSIDE the k=8 "
                         "multi-step scan: constrained decode stays one "
                         "compiled dispatch with zero added host round "
                         "trips); prints the grammar-valid outputs and "
                         "the automaton cache stats (docs/serving.md "
                         "\"Structured generation\")")
    ap.add_argument("--open-loop", action="store_true",
                    help="serve a seeded OPEN-loop Poisson workload on "
                         "deterministic virtual time instead of the fixed "
                         "request set: arrivals land on schedule whether "
                         "or not earlier requests finished, the per-tick "
                         "metric time series samples every step, and the "
                         "summary shows the queue/occupancy series "
                         "(docs/OBSERVABILITY.md)")
    args = ap.parse_args()
    if args.moe:
        return moe_demo()
    if args.tenants:
        return tenants_demo()
    if args.open_loop:
        return open_loop_demo()
    if args.json_schema:
        return structured_demo()
    if args.host_cache_blocks and not args.shared_system_prompt:
        ap.error("--host-cache-blocks is the spill tier behind the "
                 "prefix cache; pass --shared-system-prompt too")
    if args.multi_step and args.speculative:
        ap.error("--multi-step and --speculative are two spellings of "
                 "'k tokens per dispatch' — the config refuses the "
                 "combination (docs/serving.md)")

    eng = build_engine(
        "gpt2", "tiny",
        engine_config=RaggedInferenceEngineConfig(
            num_blocks=128, block_size=32, max_blocks_per_seq=24,
            max_seqs=4, prefill_chunk_size=128))
    # decode_burst=8: decode runs as fused on-device bursts (sampling
    # included — logits never leave the device); set 1 for the per-step
    # path (one token and one admission pass per step; greedy rows take
    # the program's own argmax).  prefix_cache_blocks: KV blocks the radix
    # prefix cache may keep for reuse across requests (0 = off)
    from deepspeed_tpu import SpeculativeConfig
    # with the host tier on, a deliberately small HBM budget (the shared
    # prefix is 4 blocks at block_size 32) makes eviction demote —
    # otherwise nothing would ever spill in a demo this small
    pcb = 0 if not args.shared_system_prompt else (
        8 if args.host_cache_blocks else 32)
    from deepspeed_tpu.config.config import StreamingConfig
    # multi_step and decode_burst are exclusive (two spellings of
    # "k tokens per dispatch"): the step-group path adds on-device
    # termination + the single packed per-group fetch on top of the
    # burst path's on-device sampling
    dispatch_kw = (dict(multi_step=8) if args.multi_step
                   else dict(decode_burst=8))
    loop = ServeLoop(eng, ServingConfig(
        max_queue_len=16, **dispatch_kw,
        prefix_cache_blocks=pcb,
        host_cache_blocks=args.host_cache_blocks,
        transfer_guard=args.transfer_guard,
        streaming=(StreamingConfig(enabled=True) if args.stream
                   else None),
        speculative=(SpeculativeConfig(mode="prompt_lookup")
                     if args.speculative else None)))
    rng = np.random.RandomState(0)
    system = rng.randint(0, 1024, 128).astype(np.int32)

    def prompt(n):
        p = rng.randint(0, 1024, n).astype(np.int32)
        return np.concatenate([system, p]) if args.shared_system_prompt \
            else p

    # six requests for four engine slots: the scheduler queues the rest
    # and admits them (priority first, FIFO within) as slots free up.
    # (With the shared system prompt the longest body shrinks so
    # 128 + body + 12 stays inside the tiny model's 512-token context.)
    lengths = ((37, 200, 80, 300, 64, 120) if args.shared_system_prompt
               else (37, 200, 80, 411, 64, 120))
    reqs = []
    for i, n in enumerate(lengths):
        reqs.append(loop.submit(
            prompt(n), max_new_tokens=12, priority=0 if i == 4 else 1))
    if args.multi_step:
        # a seeded stochastic row: its draws come from the device-side
        # counter-based Philox stream keyed by (seed, position) — the
        # same stream the host replay verifier would regenerate
        reqs.append(loop.submit(prompt(60), max_new_tokens=12,
                                temperature=0.8, top_k=40, seed=1234))
    victim = loop.submit(prompt(50), max_new_tokens=64)
    victim.cancel()
    fetches0 = eng.profile["d2h_fetches"] if args.multi_step else 0

    if args.stream:
        # incremental delivery: print each token the moment its burst
        # lands (a per-token callback; `loop.step()` below drives the
        # emissions — with ThreadedServer, `server.stream(req)` is the
        # blocking-iterator equivalent)
        for req in reqs:
            req.stream.add_callback(
                lambda seq, tok, uid=req.uid: print(
                    f"  request {uid} token[{seq}] = {tok}"))

    loop.run_until_idle(max_steps=500)
    for req in reqs:
        print(f"request {req.uid}: {req.state.value:9s} "
              f"prio={req.priority} "
              f"ttft={req.ttft * 1e3:7.1f}ms "
              f"e2e={req.e2e_latency * 1e3:7.1f}ms "
              f"tokens={len(req.generated)}")
    print(f"request {victim.uid}: {victim.state.value} (client cancelled)")

    s = loop.telemetry.summary()
    print(f"completed={s['completed']} cancelled={s['cancelled']} "
          f"ttft_p95={s['ttft_p95_s'] * 1e3:.1f}ms "
          f"mean_batch_occupancy={s['batch_occupancy_mean']:.2f}")
    if args.shared_system_prompt:
        print(f"prefix cache: hit_rate={s['prefix_hit_rate']:.2f} "
              f"prefill_tokens_saved={s['prefill_tokens_saved']} "
              f"cached_blocks={s['prefix_cached_blocks']}")
    if args.host_cache_blocks:
        print(f"host KV tier: host_cached_blocks="
              f"{s['host_cached_blocks']} "
              f"demoted={s['kv_demoted_blocks']} "
              f"promoted={s['kv_promoted_blocks']} "
              f"spill_bytes={s['kv_demoted_bytes']}")
    if args.stream:
        print(f"streaming: tokens_streamed={s['tokens_streamed']} "
              f"itl_p50={s['itl_p50_s'] * 1e3:.1f}ms "
              f"itl_p95={s['itl_p95_s'] * 1e3:.1f}ms")
    if args.multi_step:
        toks = sum(len(r.generated) for r in reqs)
        fetches = eng.profile["d2h_fetches"] - fetches0
        print(f"multi-step groups (k=8): d2h_fetches={fetches} for "
              f"{toks} tokens = {fetches / max(toks, 1):.2f} "
              f"fetches/token (legacy loop: >= 1.0)")
    if args.speculative:
        rate = s["spec_acceptance_rate"]
        tpd = s["spec_tokens_per_dispatch"]
        print(f"speculative: drafted={s['spec_drafted']} "
              f"accepted={s['spec_accepted']} "
              f"acceptance={rate if rate is None else round(rate, 2)} "
              f"tokens_per_dispatch="
              f"{tpd if tpd is None else round(tpd, 2)}")


def moe_demo():
    """`--moe`: the ISSUE 20 expert-paging subsystem in ~40 lines — a
    real (tiny) MoE model serving with fewer HBM expert slots than
    experts.  The router census rides the decode kernel on device, the
    serve loop drains it every 2 steps, and the pool rebalances
    residency toward the measured demand (LRU demote is pure
    bookkeeping — canonical copies live on host — promote uploads one
    expert per budget step).  A wanted-but-demoted expert reroutes the
    token to its next-best resident expert; it never faults."""
    import jax.numpy as jnp

    from deepspeed_tpu.config.config import MoeServingConfig

    eng = build_engine(
        "qwen_v2_moe", "tiny", dtype=jnp.float32, max_seq_len=256,
        engine_config=RaggedInferenceEngineConfig(
            num_blocks=64, block_size=8, max_blocks_per_seq=16,
            max_seqs=4, prefill_chunk_size=16))
    E = eng.cfg.moe_experts
    top_k = eng.cfg.moe_top_k
    # slots = top_k + 1 of E: under-provisioned on purpose, so the
    # census-driven rebalance (and the reroute gauge) have work to do
    scfg = ServingConfig(
        max_queue_len=16, audit_blocks=True,
        moe=MoeServingConfig(slots_per_layer=top_k + 1,
                             census_interval_steps=2,
                             max_promotes_per_step=1))
    loop = ServeLoop(eng, scfg)
    pool = loop.expert_pool
    print(f"experts={E} top_k={top_k} slots/layer={top_k + 1} "
          f"(resident={pool.resident_count()} "
          f"spilled={pool.spilled_count()})")

    rng = np.random.RandomState(0)
    reqs = [loop.submit(rng.randint(0, 1024, 24 + 8 * i).astype(np.int32),
                        max_new_tokens=12) for i in range(6)]
    loop.run_until_idle(max_steps=800)
    assert all(len(r.output_tokens) == 12 for r in reqs)

    st = loop.telemetry.summary()["expert_pool"]
    print(f"routed={st['expert_routed']:.0f} "
          f"rerouted={st['expert_rerouted']:.0f} "
          f"(drop rate {st['expert_drop_rate']:.1%})")
    print(f"demotes={st['expert_demotes']:.0f} "
          f"promotes={st['expert_promotes']:.0f} "
          f"load imbalance={st['expert_load_imbalance']:.2f}")
    pool.audit()
    print("pool conservation audit: clean; pinned after drain:",
          pool.pinned_count())


def tenants_demo():
    """`--tenants`: the ISSUE 16 tenancy subsystem in ~50 lines — one
    base model serving three tenants from a single continuous batch,
    per-tenant LoRA adapters paged through a slotted HBM pool with a
    host spill tier, start-time-fair queueing weights, and a token-
    bucket rate limit that sheds (never queues) over-limit traffic."""
    from deepspeed_tpu.config.config import TenancyConfig
    from deepspeed_tpu.serving.tenancy import RateLimitedError

    eng = build_engine(
        "gpt2", "tiny",
        engine_config=RaggedInferenceEngineConfig(
            num_blocks=128, block_size=32, max_blocks_per_seq=24,
            max_seqs=4, prefill_chunk_size=128))
    # the tiny model is hidden=256 x 4 layers: a rank-4 adapter is
    # 4 * (256*4 + 4*256) = 8192 elems = 4 blocks at the default
    # 4096-elem page, so adapter_pool_blocks=8 holds TWO resident
    # adapters — registering a third spills the coldest to host pages,
    # and the first request that names it pages it back in (LRU)
    loop = ServeLoop(eng, ServingConfig(
        max_queue_len=16, decode_burst=8,
        tenancy=TenancyConfig(
            enabled=True, adapter_pool_blocks=8, host_spill_blocks=16,
            weights={"gold": 4.0}, rate_limits={"free": 0.5},
            burst_s=2.0)))
    rng = np.random.RandomState(0)
    for i, aid in enumerate(("lora_gold", "lora_std", "lora_free")):
        a = (0.05 * rng.randn(4, 256, 4)).astype(np.float32)
        b = rng.randn(4, 4, 256).astype(np.float32)
        loop.register_adapter(aid, a, b)
    pool = loop.adapter_pool
    print(f"adapter pool: resident={pool.resident} "
          f"spilled={pool.spilled}")

    def prompt(n):
        return rng.randint(0, 1024, n).astype(np.int32)

    reqs, shed = [], 0
    for i in range(9):
        tenant = ("gold", "std", "free")[i % 3]
        try:
            reqs.append(loop.submit(
                prompt(40 + 8 * i), max_new_tokens=12, tenant=tenant,
                adapter_id=None if i < 3 else f"lora_{tenant}"))
        except RateLimitedError:
            # the bucket holds 1 token for "free" (0.5 rps * 2 s
            # burst): over-limit submits shed LOUDLY at admission —
            # they never occupy queue slots the paying tenants bought
            shed += 1
    loop.run_until_idle(max_steps=800)

    s = loop.telemetry.summary()
    for tenant, row in sorted(s["tenants"].items()):
        print(f"tenant {tenant:5s}: submitted={row['submitted']} "
              f"completed={row['completed']} tokens={row['tokens']} "
              f"rate_limited={row['rejected_rate_limited']}")
    ap_ = s["adapter_pool"]
    print(f"adapter pool: resident={ap_['adapter_resident']} "
          f"spilled={ap_['adapter_spilled']} "
          f"demotes={ap_['adapter_demotes']} "
          f"promotes={ap_['adapter_promotes']}")
    print(f"rate-limited sheds (client saw RateLimitedError): {shed}")


def structured_demo():
    """`--json-schema`: the ISSUE 18 structured subsystem in ~40 lines
    — a JSON-schema request and a regex request decode through the
    k=8 multi-step scan with the grammar's FSM mask applied ON DEVICE
    (per-row automaton state rides the scan carry; zero added d2h
    fetches), next to an unconstrained request the masks never touch.
    The model is an untrained tiny GPT-2 babbling random logits — the
    grammar alone is why the outputs parse."""
    import json

    from deepspeed_tpu.config.config import StructuredConfig
    from deepspeed_tpu.serving.structured import ResponseFormat

    eng = build_engine(
        "gpt2", "tiny",
        engine_config=RaggedInferenceEngineConfig(
            num_blocks=128, block_size=32, max_blocks_per_seq=24,
            max_seqs=4, prefill_chunk_size=128))
    loop = ServeLoop(eng, ServingConfig(
        max_queue_len=16, multi_step=8,
        structured=StructuredConfig()))
    rng = np.random.RandomState(0)

    def prompt(n):
        # byte-range prompt tokens so the decoded output reads as text
        return rng.randint(32, 127, n).astype(np.int32)

    # bounded grammars: every path reaches an accept state inside the
    # token budget (an open-ended {"type": "integer"} would let the
    # model ride digits forever).  EOS is NOT part of the grammar —
    # the device admits each request's own eos_token_id in accept
    # states, so constrained submits must name one.
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "severity": {"enum": ["low", "high"]}},
              "required": ["ok", "severity"]}
    eos = 0
    r_schema = loop.submit(
        prompt(40), max_new_tokens=32, eos_token_id=eos,
        response_format=ResponseFormat.json_schema(schema))
    r_regex = loop.submit(
        prompt(40), max_new_tokens=32, eos_token_id=eos,
        # seeded stochastic: the mask renormalizes the device Philox
        # draw over the grammar-legal tokens only
        temperature=0.9, top_k=0, seed=7,
        response_format=ResponseFormat.regex(r"(GET|PUT) /[a-z]{1,8}"))
    r_free = loop.submit(prompt(40), max_new_tokens=12)
    loop.run_until_idle(max_steps=500)

    def text(req):
        return bytes(t for t in req.generated if t != eos and t < 256
                     ).decode("latin-1")

    parsed = json.loads(text(r_schema))     # the point: it parses
    print(f"json-schema constrained: {text(r_schema)!r} -> "
          f"json.loads OK, keys={sorted(parsed)}")
    print(f"regex constrained (seeded): {text(r_regex)!r}")
    print(f"unconstrained: {len(r_free.generated)} free tokens "
          f"(automaton operands absent from its dispatch — bit-for-bit "
          f"the structured=None loop)")
    s = loop.telemetry.summary()
    gc = s["grammar_cache"]
    print(f"automaton cache: compiles={gc['compiles']} "
          f"states={gc['states']} bytes={gc['bytes']} "
          f"hits={gc['hits']} (grammars compile ONCE at submit; "
          f"repeat formats hit the LRU)")


def open_loop_demo():
    """`--open-loop`: the ISSUE 13 observatory in ~30 lines — a seeded
    Poisson workload with heavy-tailed lengths submitted on schedule
    against the tiny engine on a virtual serve clock, with the metric
    time series and the recompile flight recorder riding along."""
    from deepspeed_tpu.config.config import TracingConfig
    from deepspeed_tpu.serving import (OpenLoopDriver,
                                       RecompileFlightRecorder,
                                       VirtualClock, WorkloadGenerator)

    eng = build_engine(
        "gpt2", "tiny",
        engine_config=RaggedInferenceEngineConfig(
            num_blocks=128, block_size=32, max_blocks_per_seq=24,
            max_seqs=4, prefill_chunk_size=128))
    clock = VirtualClock()
    loop = ServeLoop(eng, ServingConfig(
        max_queue_len=64, decode_burst=8,
        tracing=TracingConfig(metrics_ring=4096)), clock=clock)
    gen = WorkloadGenerator(
        vocab_size=1024, seed=0, arrival="poisson", rate_rps=1.2,
        prompt_len_mean=48.0, prompt_len_max=256,
        output_len_mean=12.0, output_len_max=32)
    rec = RecompileFlightRecorder(clock=clock, engine=eng)
    with rec:
        res = OpenLoopDriver(loop, clock, gen.generate(16),
                             step_dt=1.0).run()
    s = loop.telemetry.summary(elapsed_s=res.elapsed_s)
    ring = loop.metrics.ring
    print(f"open loop: {len(res.finished)} finished, {res.rejected} "
          f"rejected, {res.steps} steps, {res.elapsed_s:.0f} virtual s")
    print(f"goodput={s['goodput_tok_s']:.1f} tok/vs "
          f"ttft_p95={s['ttft_p95_s']:.1f} vs "
          f"occupancy_mean={s['batch_occupancy_mean']:.2f}")
    print(f"queue depth series (per tick): "
          f"{ring.series('queue_depth')}")
    print(f"recompiles: {rec.total_events} "
          f"({rec.total_compile_s:.1f}s wall) in programs "
          f"{sorted(rec.scan())}")


if __name__ == "__main__":
    main()
