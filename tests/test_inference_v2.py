"""Tests: FastGen-analog continuous batching engine (reference:
tests/unit/inference/v2/ — ragged batching, KV block management, engine
put/flush correctness vs a dense forward)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig, build_engine, arch_config)
from deepspeed_tpu.models import Transformer, TransformerConfig


pytestmark = pytest.mark.serving


def _model():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128, dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _engine(model, params, **kw):
    base = dict(num_blocks=32, block_size=8, max_blocks_per_seq=8, max_seqs=4,
                prefill_chunk_size=16)
    base.update(kw)
    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**base))


def test_prefill_logits_match_dense_forward():
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 128, 24).astype(np.int32)
    out = eng.put([7], [prompt])
    assert 7 in out
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(model.cfg, params, jnp.asarray(prompt)[None])
    np.testing.assert_allclose(out[7], np.asarray(dense[0, -1]), atol=2e-3)


def test_decode_matches_dense_forward():
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 128, 10).astype(np.int32)
    eng.put([1], [prompt])
    nxt = 42
    out = eng.put([1], [np.asarray([nxt])])
    full = np.concatenate([prompt, [nxt]])
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(model.cfg, params, jnp.asarray(full)[None])
    np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]), atol=2e-3)


def test_split_fuse_chunked_prefill():
    """Prompt longer than chunk size: correct logits after chunked prefill."""
    model, params = _model()
    eng = _engine(model, params, prefill_chunk_size=8)
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 128, 30).astype(np.int32)   # 4 chunks of 8
    out = eng.put([3], [prompt])
    assert 3 in out                # budget 512 covers all chunks in one call
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(model.cfg, params, jnp.asarray(prompt)[None])
    np.testing.assert_allclose(out[3], np.asarray(dense[0, -1]), atol=2e-3)


def test_prefill_budget_bounds_work_per_step():
    model, params = _model()
    eng = _engine(model, params, prefill_chunk_size=8,
                  max_prefill_tokens_per_step=8)
    prompt = np.arange(24, dtype=np.int32) % 128
    out = eng.put([5], [prompt])
    assert out == {}               # only 8 of 24 tokens prefilled
    assert eng.state.seqs[5].seen_tokens == 8
    out = eng.step()
    out.update(eng.step())
    assert 5 in out                # finished by the third step


def test_concurrent_sequences_and_flush():
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(3)
    p1 = rng.randint(0, 128, 12).astype(np.int32)
    p2 = rng.randint(0, 128, 20).astype(np.int32)
    out = eng.put([1, 2], [p1, p2])
    assert set(out) == {1, 2}
    # decode both concurrently in one batched step
    out = eng.put([1, 2], [np.asarray([5]), np.asarray([9])])
    assert set(out) == {1, 2}
    free_before = eng.free_blocks
    eng.flush(1)
    assert eng.free_blocks > free_before
    assert 1 not in eng.state.seqs
    # per-sequence isolation: seq 2 decode still correct after flush of 1
    out = eng.put([2], [np.asarray([11])])
    full = np.concatenate([p2, [9, 11]])
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(model.cfg, params, jnp.asarray(full)[None])
    np.testing.assert_allclose(out[2], np.asarray(dense[0, -1]), atol=2e-3)


def test_generate_greedy_matches_dense_greedy():
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, 128, 9).astype(np.int32)
    got = eng.generate(prompt, max_new_tokens=5)

    from deepspeed_tpu.models.transformer import _forward
    cur = list(prompt)
    want = []
    for _ in range(5):
        dense, _ = _forward(model.cfg, params, jnp.asarray(cur)[None])
        t = int(jnp.argmax(dense[0, -1]))
        want.append(t)
        cur.append(t)
    assert got.tolist() == want


def test_generate_batch_matches_sequential_generate():
    """Lockstep burst decode over a ragged batch must produce exactly what
    per-prompt greedy generation produces (cross-sequence batching and the
    on-device sample->feedback loop change scheduling, not math)."""
    model, params = _model()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 21, 5)]

    eng = _engine(model, params, decode_burst=3)
    batch_out = eng.generate_batch(prompts, max_new_tokens=7)

    for p, got in zip(prompts, batch_out):
        ref_eng = _engine(model, params)
        want = ref_eng.generate(p, max_new_tokens=7)
        assert got.tolist() == want.tolist()


def test_generate_eos_stops_early():
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, 128, 9).astype(np.int32)
    full = eng.generate(prompt, max_new_tokens=6, uid=50)
    eos = int(full[2])
    first = full.tolist().index(eos)         # tiny models repeat tokens
    eng2 = _engine(model, params)
    out = eng2.generate(prompt, max_new_tokens=6, eos_token_id=eos)
    assert out.tolist() == full[:first + 1].tolist()
    assert out[-1] == eos


def test_generate_sampling_reproducible_and_in_vocab():
    model, params = _model()
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, 128, 9).astype(np.int32)

    def run(seed):
        import jax as _jax
        eng = _engine(model, params, decode_burst=4)
        eng._rng = _jax.random.PRNGKey(seed)
        return eng.generate(prompt, max_new_tokens=12, mode="sample",
                            temperature=0.9, top_k=8)

    a, b, c = run(0), run(0), run(123)
    assert a.tolist() == b.tolist()          # same key -> same draw
    assert ((0 <= a) & (a < 128)).all()
    assert a.shape == (12,)
    assert c.shape == (12,)                  # different key still valid


def test_generate_exact_fit_request_completes():
    """A request whose prompt+new tokens exactly fill the per-sequence KV
    lease must complete: the tail burst overshoots the lease (bursts are
    full-size for one compiled shape) and the program clamps positions to
    the last leased slot instead of demanding blocks past it (regression:
    ensure_capacity raised mid-generation)."""
    model, params = _model()
    eng = _engine(model, params, decode_burst=8)
    # capacity = max_blocks_per_seq(8) * block_size(8) = 64 tokens
    prompt = np.random.RandomState(17).randint(0, 128, 57).astype(np.int32)
    out = eng.generate(prompt, max_new_tokens=7)
    assert out.shape == (7,)
    # parity with single-token-sized bursts (no overshoot -> no clamping)
    eng2 = _engine(model, params, decode_burst=1)
    want = eng2.generate(prompt, max_new_tokens=7)
    assert out.tolist() == want.tolist()


def test_decode_burst_requires_single_pending_token():
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(10)
    out = eng.put([0], [rng.randint(0, 128, 9).astype(np.int32)])
    while 0 not in out:
        out.update(eng.step())
    d = eng.state.seqs[0]
    d.generated.extend([3, 4])               # two unconsumed tokens
    with pytest.raises(RuntimeError, match="pending"):
        eng.decode_burst_step(uids=[0], n_steps=2)


def test_registry_and_factory():
    cfg = arch_config("mistral", "tiny")
    assert cfg.sliding_window is not None
    with pytest.raises(ValueError):
        arch_config("not_an_arch")
    eng = build_engine("gpt2", "tiny",
                       engine_config=RaggedInferenceEngineConfig(
                           num_blocks=16, block_size=8, max_blocks_per_seq=4,
                           max_seqs=2, prefill_chunk_size=8))
    out = eng.put([0], [np.arange(6, dtype=np.int32)])
    assert 0 in out and out[0].shape[-1] == eng.cfg.vocab_size


def test_capacity_errors():
    model, params = _model()
    eng = _engine(model, params, num_blocks=4, max_blocks_per_seq=2,
                  block_size=8)
    with pytest.raises(RuntimeError):
        eng.put([1], [np.zeros(100, np.int32)])   # needs >2 blocks


def test_max_seq_len_guard():
    """KV lease capacity above the model context must not silently clip
    learned position embeddings — loud error instead."""
    model, params = _model()     # max_seq_len=128
    eng = _engine(model, params, num_blocks=64, max_blocks_per_seq=32,
                  block_size=8)  # lease capacity 256 > context 128
    assert eng.max_tokens_per_seq == 128
    with pytest.raises(RuntimeError, match="max_seq_len"):
        eng.put([1], [np.zeros(129, np.int32)])
    # incremental path: admit 127, then two more tokens crosses the limit
    eng.put([2], [np.zeros(127, np.int32)])
    with pytest.raises(RuntimeError, match="max_seq_len"):
        eng.put([2], [np.asarray([1, 2], np.int32)])


def test_moe_arch_serves_and_matches_dense_prefill():
    """MoE archs (mixtral/qwen2-moe) run through the ragged engine; prefill
    logits match the dense cache-forward (exact no-drop routing both sides)."""
    cfg = arch_config("qwen_v2_moe", "tiny", dtype=jnp.float32,
                      max_seq_len=128)
    assert cfg.moe_experts > 1 and cfg.moe_shared_expert_ffn > 0
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, prefill_chunk_size=16)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab_size, 11).astype(np.int32)
    out = eng.put([1], [prompt])
    cache = model.init_cache(batch=1, max_len=32)
    dense_logits, _ = model.forward_with_cache(params, prompt[None], cache)
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray(dense_logits[0, -1]),
                               rtol=2e-3, atol=2e-3)
    # decode one token through the paged decode_step MoE branch and compare
    # against the dense cache path
    nxt = int(np.argmax(out[1]))
    out2 = eng.put([1], [np.asarray([nxt], np.int32)])
    dense2, _ = model.forward_with_cache(
        params, np.asarray([[nxt]], np.int32),
        model.forward_with_cache(params, prompt[None],
                                 model.init_cache(1, 32))[1])
    np.testing.assert_allclose(np.asarray(out2[1]),
                               np.asarray(dense2[0, -1]),
                               rtol=2e-3, atol=2e-3)


def test_alibi_arch_ragged_matches_dense():
    """bloom-style alibi + embedding layernorm through the ragged engine:
    prefill and one decode step match the dense cache path (alibi bias was
    previously ignored by the paged attention)."""
    from deepspeed_tpu.models import get_model_config
    cfg = get_model_config("bloom", "tiny", dtype=jnp.float32, max_seq_len=128)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, prefill_chunk_size=16)
    prompt = np.random.RandomState(5).randint(0, cfg.vocab_size,
                                              13).astype(np.int32)
    out = eng.put([1], [prompt])
    cache = model.init_cache(1, 32)
    dense, cache = model.forward_with_cache(params, prompt[None], cache)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(dense[0, -1]),
                               rtol=2e-3, atol=2e-3)
    nxt = int(np.argmax(out[1]))
    out2 = eng.put([1], [np.asarray([nxt], np.int32)])
    dense2, _ = model.forward_with_cache(params, np.asarray([[nxt]], np.int32),
                                         cache)
    np.testing.assert_allclose(np.asarray(out2[1]), np.asarray(dense2[0, -1]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("family", ["falcon", "phi", "gptneox"])
def test_parallel_residual_archs_ragged_match_dense(family):
    """falcon/phi/neox through the ragged engine: parallel residual blocks
    and partial rotary must match the dense cache path (both were previously
    unimplemented in prefill_chunk/decode_step)."""
    from deepspeed_tpu.models import get_model_config
    cfg = get_model_config(family, "tiny", dtype=jnp.float32, max_seq_len=128)
    assert cfg.parallel_residual
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, prefill_chunk_size=16)
    prompt = np.random.RandomState(6).randint(0, cfg.vocab_size,
                                              9).astype(np.int32)
    out = eng.put([1], [prompt])
    cache = model.init_cache(1, 32)
    dense, cache = model.forward_with_cache(params, prompt[None], cache)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(dense[0, -1]),
                               rtol=2e-3, atol=2e-3)
    nxt = int(np.argmax(out[1]))
    out2 = eng.put([1], [np.asarray([nxt], np.int32)])
    dense2, _ = model.forward_with_cache(params, np.asarray([[nxt]], np.int32),
                                         cache)
    np.testing.assert_allclose(np.asarray(out2[1]), np.asarray(dense2[0, -1]),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_ragged_matches_dense():
    """mistral-style local attention through the ragged engine: once context
    exceeds the window, old keys must be masked exactly like the dense cache
    path (previously the ragged paths ignored sliding_window)."""
    from deepspeed_tpu.models import get_model_config
    cfg = get_model_config("mistral", "tiny", dtype=jnp.float32,
                           max_seq_len=128, sliding_window=8)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, prefill_chunk_size=16)
    prompt = np.random.RandomState(7).randint(0, cfg.vocab_size,
                                              21).astype(np.int32)
    out = eng.put([1], [prompt])
    cache = model.init_cache(1, 64)
    dense, cache = model.forward_with_cache(params, prompt[None], cache)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(dense[0, -1]),
                               rtol=2e-3, atol=2e-3)
    nxt = int(np.argmax(out[1]))
    out2 = eng.put([1], [np.asarray([nxt], np.int32)])
    dense2, _ = model.forward_with_cache(params, np.asarray([[nxt]], np.int32),
                                         cache)
    np.testing.assert_allclose(np.asarray(out2[1]), np.asarray(dense2[0, -1]),
                               rtol=2e-3, atol=2e-3)


def test_merged_arena_serving_matches_5d():
    """The merged [L, nb, bs, NKV*D] arena layout (the large-arena memory
    form, init_arena merged=True) must produce exactly what the 5-D
    kernel-friendly layout produces through prefill, decode and burst."""
    model, params = _model()
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (19, 7)]

    outs = {}
    for merged in (False, True):
        eng = _engine(model, params, arena_merged=merged, decode_burst=3)
        assert eng.arena["k"].ndim == (4 if merged else 5)
        outs[merged] = eng.generate_batch(prompts, max_new_tokens=6)
    for a, b in zip(outs[False], outs[True]):
        assert a.tolist() == b.tolist()


def test_longrope_chunked_prefill_matches_dense_forward():
    """longrope picks short vs long factors from the sequence length.  A
    long prompt through CHUNKED prefill must use the same (long) factors
    for every chunk that HF's one-shot forward uses — early chunks must
    not embed with short_factor just because their own positions are small
    (the engine passes the full prompt length as the regime hint)."""
    half = 8  # head_dim 16
    short = tuple(1.0 + 0.1 * i for i in range(half))
    long_ = tuple(1.0 + 1.5 * i for i in range(half))
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128,
                            dtype=jnp.float32, pos_emb="rope",
                            rope_scaling=("longrope", 1.2, 16.0,
                                          short, long_))
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, prefill_chunk_size=16, num_blocks=64,
                  max_blocks_per_seq=16)
    prompt = np.random.RandomState(11).randint(
        0, cfg.vocab_size, 41).astype(np.int32)   # 41 > orig=16
    out = eng.put([1], [prompt])
    while 1 not in out:
        out.update(eng.step())
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(cfg, params, jnp.asarray(prompt[None]))
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray(dense[0, -1]),
                               rtol=2e-3, atol=2e-3)


# ----------------------------------------------------------------------
# tensor parallelism (reference: inference/v2/model_implementations/
# sharding/{attn,mlp}.py — v2 engines shard every model across ranks)
# ----------------------------------------------------------------------
def _gqa_model():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=128,
                            pos_emb="rope", norm="rmsnorm",
                            activation="swiglu", dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    return model, params


def test_tp2_serving_matches_tp1_gqa():
    """Same GQA model served at tp=2 and tp=1: identical logits through
    chunked prefill AND batched decode (weights column/row-sharded, KV arena
    sharded on the kv-head dim, allreduce inserted by the partitioner)."""
    model, params = _gqa_model()
    eng1 = _engine(model, params)
    eng2 = _engine(model, params, tensor_parallel_size=2)
    assert eng2.tp == 2
    # sanity: weights and arena are actually sharded over 2 devices
    assert len(eng2.params["layers"]["wq"].sharding.device_set) == 2
    assert len(eng2.arena["k"].sharding.device_set) == 2

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (25, 7)]
    out1 = eng1.put([0, 1], list(prompts))
    out2 = eng2.put([0, 1], list(prompts))
    assert set(out1) == set(out2) == {0, 1}
    for uid in (0, 1):
        np.testing.assert_allclose(out1[uid], out2[uid],
                                   rtol=2e-4, atol=2e-4)
    # a few decode steps, feeding each engine its own greedy token (they
    # must agree, so the streams stay comparable)
    for _ in range(3):
        toks = {u: np.asarray([int(np.argmax(out1[u]))], np.int32)
                for u in (0, 1)}
        assert all(int(np.argmax(out2[u])) == int(toks[u][0]) for u in (0, 1))
        out1 = eng1.put([0, 1], [toks[0], toks[1]])
        out2 = eng2.put([0, 1], [toks[0], toks[1]])
        for uid in (0, 1):
            np.testing.assert_allclose(out1[uid], out2[uid],
                                       rtol=2e-4, atol=2e-4)


def test_tp_requires_divisible_heads():
    model, params = _gqa_model()
    with pytest.raises(ValueError, match="kv_heads"):
        _engine(model, params, tensor_parallel_size=4)  # kv_heads=2 % 4 != 0


def test_tp_pallas_kernel_gate(monkeypatch):
    """The fused decode kernel does not auto-partition under GSPMD, so the
    gate must turn it off at tp>1 even where it would otherwise run — and
    attn_impl='pallas' must refuse loudly rather than silently fall back.
    the platform is patched True so the n_tp condition itself is what's tested
    (on the CPU suite the platform check alone would mask a regression)."""
    import deepspeed_tpu.utils.device as device_mod
    from deepspeed_tpu.inference.v2.ragged_ops import _use_paged_kernel
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    auto = TransformerConfig(vocab_size=128, hidden_size=256, num_layers=1,
                             num_heads=4, max_seq_len=4096,
                             dtype=jnp.float32)
    assert _use_paged_kernel(auto, 64, 64, n_tp=1) is True
    assert _use_paged_kernel(auto, 64, 64, n_tp=2) is False
    forced = TransformerConfig(vocab_size=128, hidden_size=256, num_layers=1,
                               num_heads=4, max_seq_len=4096,
                               attn_impl="pallas", dtype=jnp.float32)
    with pytest.raises(ValueError, match="mesh when tp > 1"):
        _use_paged_kernel(forced, 64, 64, n_tp=2)


def test_prefill_pallas_kernel_gate(monkeypatch):
    """Auto/forced/jnp dispatch of the blocked-flash prefill gate, with
    the platform patched to tpu so the conditions themselves are exercised.
    Full range (r7): the gate is capability-only — no KV-budget
    threshold, and non-divisible / sub-8 chunks pad to the query tile
    instead of disqualifying the kernel."""
    import deepspeed_tpu.utils.device as device_mod
    from deepspeed_tpu.inference.v2.ragged_ops import _use_paged_prefill
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    auto = TransformerConfig(vocab_size=128, hidden_size=256, num_layers=1,
                             num_heads=4, max_seq_len=16384,
                             dtype=jnp.float32)
    assert _use_paged_prefill(auto, 64, 64, 256) is True
    # odd chunks and sub-8 verify spans pad into the kernel now
    assert _use_paged_prefill(auto, 64, 64, 100) is True
    assert _use_paged_prefill(auto, 64, 64, 2) is True
    # tp>1 without a mesh turns it off (no GSPMD auto-partition)
    assert _use_paged_prefill(auto, 64, 64, 256, n_tp=2) is False
    # jnp stays the explicit dense escape hatch
    off = TransformerConfig(vocab_size=128, hidden_size=256, num_layers=1,
                            num_heads=4, max_seq_len=16384,
                            attn_impl="jnp", dtype=jnp.float32)
    assert _use_paged_prefill(off, 64, 64, 256) is False
    # forced: raises on a genuinely incapable layout (block_size % 8)
    forced = TransformerConfig(vocab_size=128, hidden_size=256, num_layers=1,
                               num_heads=4, max_seq_len=16384,
                               attn_impl="pallas", dtype=jnp.float32)
    assert _use_paged_prefill(forced, 64, 64, 100) is True
    with pytest.raises(ValueError, match="block_size"):
        _use_paged_prefill(forced, 64, 60, 256)


def test_gate_machinery_fully_retired():
    """The 2048-key auto-gate's support machinery must stay deleted:
    the slow-path warning set, its reset hook, and the 774M crash
    guard/class all existed only because small budgets rode the dense
    gather — full-range kernels make them dead weight, and a
    reintroduction would mean the gather path is reachable again."""
    import deepspeed_tpu.inference.v2.ragged_ops as ro
    for name in ("guard_gather_prefill", "gather_prefill_crash_class",
                 "_warned_gather_fallback", "_warn_gather_fallback",
                 "_reset_fallback_warnings", "GATHER_PREFILL_CRASH_PARAMS"):
        assert not hasattr(ro, name), name


def test_prefill_full_matches_chunked():
    """The fresh-full-prompt fast path (prefill_full, dense causal flash
    + arena scatter) must produce the SAME logits and generation as the
    chunked SplitFuse path — including the decode phase reading the KV
    the fast path scattered."""
    model, params = _model()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (23, 9, 16)]
    outs = {}
    for full in (True, False):
        eng = _engine(model, params, full_prompt_prefill=full,
                      max_prefill_tokens_per_step=64)
        assert eng._use_prefill_full is full
        outs[full] = eng.generate_batch(prompts, max_new_tokens=6)
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_prefill_full_over_budget_falls_back_chunked(monkeypatch):
    """A prompt longer than the step budget must keep the chunked path
    (prefill_full only serves whole prompts within budget)."""
    import deepspeed_tpu.inference.v2.ragged_ops as rops
    model, params = _model()
    called = {"full": 0}
    real_full = rops.prefill_full

    def count_full(*a, **k):
        called["full"] += 1
        return real_full(*a, **k)

    monkeypatch.setattr(rops, "prefill_full", count_full)
    eng = _engine(model, params, max_prefill_tokens_per_step=16,
                  prefill_chunk_size=16)
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, 128, 40).astype(np.int32)  # > 16 budget
    out = eng.put([0], [prompt])
    while 0 not in out:
        out.update(eng.step())
    assert called["full"] == 0  # chunked served the long prompt
    # and the result still matches a fast-path engine with enough budget
    eng2 = _engine(model, params, max_prefill_tokens_per_step=64)
    out2 = eng2.put([1], [prompt])
    np.testing.assert_allclose(out[0], out2[1], rtol=2e-4, atol=2e-5)


def test_prefill_full_does_not_starve_chunked_continuation():
    """A mid-prefill (chunked) sequence must keep progressing even when a
    fresh prompt arrives every step — the fast path suspends itself
    rather than draining the budget (review r5 finding)."""
    model, params = _model()
    eng = _engine(model, params, max_prefill_tokens_per_step=16,
                  prefill_chunk_size=16, max_seqs=4, num_blocks=64,
                  max_blocks_per_seq=16)
    rng = np.random.RandomState(9)
    long_prompt = rng.randint(0, 128, 64).astype(np.int32)  # 4 chunks
    out = eng.put([0], [long_prompt])
    steps = 0
    uid = 100
    while 0 not in out:
        # adversarial arrival stream: one fresh short prompt per step
        out.update(eng.put([uid], [rng.randint(0, 128, 8).astype(np.int32)]))
        eng.flush(uid) if uid in out else None
        uid += 1
        steps += 1
        assert steps < 32, "mid-prefill sequence starved by fresh arrivals"
    assert 0 in out


def test_prefill_full_does_not_starve_fresh_long_prompt():
    """A FRESH prompt longer than the whole step budget must still start:
    the fast path reserves it one chunk of budget (it can never ride
    prefill_full itself, and the suspension guard only protects
    mid-prefill sequences), so a sustained stream of short fresh
    arrivals must not defer it indefinitely."""
    model, params = _model()
    eng = _engine(model, params, max_prefill_tokens_per_step=16,
                  prefill_chunk_size=8, max_seqs=4, num_blocks=64,
                  max_blocks_per_seq=16)
    rng = np.random.RandomState(21)
    long_prompt = rng.randint(0, 128, 24).astype(np.int32)  # > 16 budget
    out = eng.put([0], [long_prompt])
    steps = 0
    uid = 100
    while 0 not in out:
        # adversarial arrival stream: one budget-sized fresh short prompt
        # per step — without the reservation, prefill_full drains the
        # whole budget every step and uid 0 never starts
        out.update(eng.put([uid],
                           [rng.randint(0, 128, 16).astype(np.int32)]))
        if uid in out:
            eng.flush(uid)
        uid += 1
        steps += 1
        assert steps < 32, "fresh long prompt starved by short arrivals"
    assert 0 in out
    # and the logits are the ones the chunked path computes
    eng2 = _engine(model, params, max_prefill_tokens_per_step=64)
    out2 = eng2.put([1], [long_prompt])
    np.testing.assert_allclose(out[0], out2[1], rtol=2e-4, atol=2e-4)


def test_prefill_full_padding_bounded_by_bucket():
    """One long + many short fresh prompts must NOT pad into one
    rectangular batch (memory guard): batches hold a single power-of-2
    length bucket and everyone still completes correctly."""
    model, params = _model()
    eng = _engine(model, params, max_prefill_tokens_per_step=128,
                  max_seqs=4, num_blocks=64, max_blocks_per_seq=16)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, 128, n).astype(np.int32)
               for n in (100, 5, 6, 7)]
    outs = eng.generate_batch(prompts, max_new_tokens=4)
    ref_eng = _engine(model, params, full_prompt_prefill=False,
                      max_prefill_tokens_per_step=128, max_seqs=4,
                      num_blocks=64, max_blocks_per_seq=16)
    refs = ref_eng.generate_batch(prompts, max_new_tokens=4)
    for a, b in zip(outs, refs):
        np.testing.assert_array_equal(a, b)


def test_batched_prefill_one_dispatch_for_concurrent_prompts(monkeypatch):
    """4 concurrent prompts advance with ONE prefill dispatch + ONE decode
    dispatch per step (reference: ragged_wrapper composes one batch from
    all sequences' chunks), with logits identical to serial serving."""
    import deepspeed_tpu.inference.v2.engine_v2 as ev2
    import deepspeed_tpu.inference.v2.ragged_ops as rops
    model, params = _model()
    calls = {"prefill": 0, "decode": 0}
    real_prefill, real_decode = ev2.prefill_chunks, ev2.decode_step
    real_full = rops.prefill_full

    def count_prefill(*a, **k):
        calls["prefill"] += 1
        return real_prefill(*a, **k)

    def count_full(*a, **k):
        # fresh full prompts ride prefill_full now — still ONE dispatch
        calls["prefill"] += 1
        return real_full(*a, **k)

    def count_decode(*a, **k):
        calls["decode"] += 1
        return real_decode(*a, **k)

    monkeypatch.setattr(ev2, "prefill_chunks", count_prefill)
    monkeypatch.setattr(rops, "prefill_full", count_full)
    monkeypatch.setattr(ev2, "decode_step", count_decode)
    eng = _engine(model, params, prefill_chunk_size=16,
                  max_prefill_tokens_per_step=64)
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, 128, n).astype(np.int32)
               for n in (15, 9, 16, 4)]
    out = eng.put([0, 1, 2, 3], list(prompts))
    assert set(out) == {0, 1, 2, 3}
    assert calls == {"prefill": 1, "decode": 0}   # 4 prompts, one dispatch
    # one decode step for all four
    toks = {u: np.asarray([int(np.argmax(out[u]))], np.int32)
            for u in range(4)}
    out2 = eng.put([0, 1, 2, 3], [toks[u] for u in range(4)])
    # no pending prompts -> the empty plan short-circuits: zero prefill
    # dispatches, one decode dispatch for all four sequences
    assert calls == {"prefill": 1, "decode": 1}
    assert set(out2) == {0, 1, 2, 3}
    # logits match serial engines
    for u in range(4):
        solo = _engine(model, params, prefill_chunk_size=16)
        so = solo.put([9], [prompts[u]])
        np.testing.assert_allclose(out[u], so[9], rtol=2e-4, atol=2e-4)


def test_batched_prefill_long_prompt_chunks_stay_causal():
    """Consecutive chunks of ONE long prompt in the same batched program:
    a later chunk must attend keys the earlier chunk wrote this call."""
    model, params = _model()
    eng = _engine(model, params, prefill_chunk_size=8,
                  max_prefill_tokens_per_step=64)   # NC=8 slots
    rng = np.random.RandomState(14)
    prompt = rng.randint(0, 128, 61).astype(np.int32)  # 8 chunks, one call
    out = eng.put([5], [prompt])
    assert 5 in out
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(model.cfg, params, jnp.asarray(prompt)[None])
    np.testing.assert_allclose(out[5], np.asarray(dense[0, -1]), atol=2e-3)


def test_tp2_serving_with_fused_kernels(monkeypatch):
    """tp=2 with attn_impl='pallas': both paged kernels run PER-SHARD via
    shard_map (a pallas_call does not auto-partition under GSPMD) and the
    logits match the tp=1 jnp engine.  Interpreter mode stands in for the
    TPU compile; the platform is patched so the gates exercise the tp branch."""
    import functools
    import jax.experimental.pallas as pl
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    cfg_kw = dict(vocab_size=128, hidden_size=256, num_layers=2,
                  num_heads=4, num_kv_heads=2, max_seq_len=256,
                  pos_emb="rope", norm="rmsnorm", activation="swiglu",
                  dtype=jnp.float32)
    model_k = Transformer(TransformerConfig(attn_impl="pallas", **cfg_kw))
    model_j = Transformer(TransformerConfig(attn_impl="jnp", **cfg_kw))
    params = model_k.init_params(jax.random.PRNGKey(5))
    base = dict(num_blocks=24, block_size=8, max_blocks_per_seq=16,
                max_seqs=2, prefill_chunk_size=16)
    eng_k = InferenceEngineV2(model_k, params=params,
                              config=RaggedInferenceEngineConfig(
                                  tensor_parallel_size=2, **base))
    eng_j = InferenceEngineV2(model_j, params=params,
                              config=RaggedInferenceEngineConfig(**base))
    prompt = np.random.RandomState(21).randint(0, 128, 23).astype(np.int32)
    out_k = eng_k.put([0], [prompt])
    out_j = eng_j.put([0], [prompt])
    np.testing.assert_allclose(out_k[0], out_j[0], rtol=2e-4, atol=2e-4)
    nxt = int(np.argmax(out_j[0]))
    out_k2 = eng_k.put([0], [np.asarray([nxt], np.int32)])
    out_j2 = eng_j.put([0], [np.asarray([nxt], np.int32)])
    np.testing.assert_allclose(out_k2[0], out_j2[0], rtol=2e-4, atol=2e-4)


# -- burst serving primitives (PR 2): per-row sampling, lease caps, ------
# -- prefill-only steps, gather-regime guards ----------------------------
def _prefill_and_stage_first(eng, prompt, uid=0):
    """Prefill + greedy first token staged as the pending burst input —
    the state the burst serve loop hands to decode_burst_step.  Prefill
    runs decode=False so an earlier sequence's pending burst token is not
    consumed by the host-logits decode path (the exact interference the
    flag exists to prevent)."""
    out = eng.put([uid], [prompt], decode=False)
    while uid not in out:
        out.update(eng.step(decode=False))
    tok = int(np.argmax(out[uid]))
    eng.state.seqs[uid].generated.append(tok)
    return tok


def test_decode_burst_per_row_all_greedy_matches_greedy_mode():
    """mode='per_row' with temperature 0 rows must be bit-identical to
    mode='greedy' — the serving layer relies on this to merge greedy and
    stochastic requests into one compiled burst."""
    model, params = _model()
    rng = np.random.RandomState(30)
    prompt = rng.randint(0, 128, 11).astype(np.int32)

    eng_a = _engine(model, params)
    _prefill_and_stage_first(eng_a, prompt)
    got_a = eng_a.decode_burst_step(uids=[0], n_steps=5, mode="greedy")

    eng_b = _engine(model, params)
    _prefill_and_stage_first(eng_b, prompt)
    got_b = eng_b.decode_burst_step(uids=[0], n_steps=5, mode="per_row",
                                    temperature={0: 0.0}, top_k={0: 0})
    assert got_a[0].tolist() == got_b[0].tolist()


def test_decode_burst_per_row_mixed_reproducible_and_valid():
    """One per-row burst over a heterogeneous batch: the greedy row
    matches a pure-greedy burst, the stochastic row is reproducible under
    the same key and stays in-vocab."""
    model, params = _model()
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 13)]

    def run(seed):
        eng = _engine(model, params)
        for uid, p in enumerate(prompts):
            _prefill_and_stage_first(eng, p, uid=uid)
        return eng.decode_burst_step(
            uids=[0, 1], n_steps=6, mode="per_row",
            temperature={0: 0.0, 1: 0.8}, top_k={0: 0, 1: 5},
            rng=jax.random.PRNGKey(seed))

    a, b, c = run(0), run(0), run(7)
    assert a[0].tolist() == b[0].tolist() and a[1].tolist() == b[1].tolist()
    assert ((0 <= a[1]) & (a[1] < 128)).all()
    assert a[1].shape == (6,) and c[1].shape == (6,)

    eng_g = _engine(model, params)
    _prefill_and_stage_first(eng_g, prompts[0])
    want = eng_g.decode_burst_step(uids=[0], n_steps=6, mode="greedy")
    assert a[0].tolist() == want[0].tolist()


def test_decode_burst_max_tokens_caps_kv_lease():
    """The per-uid `max_tokens` cap must bound the KV lease below the
    engine-wide limit: a full-size burst past the cap re-writes the last
    leased slot (overshoot trimmed) instead of leasing blocks admission
    never reserved — the serve loop's ledger-honesty contract."""
    model, params = _model()
    eng = _engine(model, params)        # block_size 8, 8 blocks/seq
    rng = np.random.RandomState(32)
    prompt = rng.randint(0, 128, 10).astype(np.int32)
    free0 = eng.free_blocks
    _prefill_and_stage_first(eng, prompt)
    got = eng.decode_burst_step(uids=[0], n_steps=8,
                                max_tokens={0: 14})
    d = eng.state.seqs[0]
    assert got[0].shape == (8,)          # full compiled shape returned
    assert d.seen_tokens == 14           # capped, not 10 + 8
    assert len(d.generated) == 1 + 4     # first + real (capped) tokens
    assert len(d.blocks) == 2            # ceil(14 / 8), not ceil(18 / 8)
    assert free0 - eng.free_blocks == 2


def test_put_step_decode_false_is_prefill_only():
    """decode=False advances prefill but must not consume the pending
    burst-chain token nor ship decode logits to host (the burst serve
    loop's no-host-logits invariant rides on this)."""
    model, params = _model()
    eng = _engine(model, params, prefill_chunk_size=8,
                  max_prefill_tokens_per_step=8)
    rng = np.random.RandomState(33)
    p0 = rng.randint(0, 128, 9).astype(np.int32)
    _prefill_and_stage_first(eng, p0)
    pend_before = list(eng.state.seqs[0].generated)
    seen_before = eng.state.seqs[0].seen_tokens
    # admit a second prompt prefill-only: seq 0's pending token survives
    long = rng.randint(0, 128, 20).astype(np.int32)
    out = eng.put([1], [long], decode=False)
    assert 0 not in out                          # no decode logits shipped
    assert eng.state.seqs[0].generated == pend_before
    assert eng.state.seqs[0].seen_tokens == seen_before
    while eng.state.seqs[1].in_prefill:
        out = eng.step(decode=False)
        assert 0 not in out
    assert 1 in out                              # prefill completion logits
    # the pending token is still exactly one burst input
    got = eng.decode_burst_step(uids=[0], n_steps=2)
    assert got[0].shape == (2,)


def test_sample_tokens_batch_per_row_greedy_matches_argmax():
    model, params = _model()
    eng = _engine(model, params)
    rows = np.random.RandomState(34).randn(3, 128).astype(np.float32)
    toks = eng.sample_tokens_batch(rows, mode="per_row",
                                   temperature=np.zeros(3, np.float32),
                                   top_k=np.zeros(3, np.int32))
    assert toks.tolist() == rows.argmax(-1).tolist()


def test_scale_topk_per_row_matches_scalar_variant():
    """Uniform per-row vectors must reproduce the scalar scale_topk
    (same truncation semantics, ties at the kth value survive)."""
    from deepspeed_tpu.inference.sampling import scale_topk, scale_topk_per_row
    logits = jnp.asarray(np.random.RandomState(35).randn(4, 64),
                         jnp.float32)
    want = np.asarray(scale_topk(logits, 0.7, 5))
    got = np.asarray(scale_topk_per_row(
        logits, jnp.full((4,), 0.7), jnp.full((4,), 5, jnp.int32)))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # top_k <= 0 rows keep every entry
    open_row = np.asarray(scale_topk_per_row(
        logits, jnp.full((4,), 0.7), jnp.zeros((4,), jnp.int32)))
    assert np.isfinite(open_row).all()


def test_small_budget_engine_serves_kernel_class(monkeypatch):
    """The 774M-class sub-2048-key engine — the exact corner PR 2 could
    only *guard* — now constructs and gates onto the full-range kernels:
    the chunked-prefill and decode gates both say kernel for the
    sub-2048 budget (on TPU), so the gather-dense program class the old
    ConfigError protected against is simply unreachable under auto."""
    import deepspeed_tpu.utils.device as device_mod
    import deepspeed_tpu.inference.v2.ragged_ops as ro
    from deepspeed_tpu.models import gpt2_config
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    large = gpt2_config("large", max_seq_len=1024, dtype=jnp.float32)
    # 1024-key budget (16 blocks x 64), chunk 256: kernel on, both gates
    assert ro._use_paged_prefill(large, large.head_dim, 64, 256) is True
    assert ro._use_paged_kernel(large, large.head_dim, 64) is True
    # the explicit dense escape hatch still exists and still disables
    large_jnp = gpt2_config("large", max_seq_len=1024, dtype=jnp.float32,
                            attn_impl="jnp")
    assert ro._use_paged_prefill(large_jnp, large.head_dim, 64, 256) \
        is False


def test_prefill_full_learned_pos_513_prompt_past_bucket(monkeypatch):
    """Regression: a 513-token prompt pads prefill_full's bucket
    to S=1024 > max_seq_len=768, so padded TAIL positions index past the
    learned pos_embed table.  `_embed` clips them explicitly
    (ragged_ops.py) — this drives the exact corner end-to-end and checks
    the REAL tokens' logits against the dense forward, proving the
    padded tail neither crashes nor perturbs the valid rows."""
    import deepspeed_tpu.inference.v2.ragged_ops as ro
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=768,
                            pos_emb="learned", dtype=jnp.float32)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(
        model, params=params,
        config=RaggedInferenceEngineConfig(
            num_blocks=16, block_size=64, max_blocks_per_seq=12,
            max_seqs=2, prefill_chunk_size=128,
            max_prefill_tokens_per_step=1024))
    calls = []
    orig = ro.prefill_full
    monkeypatch.setattr(ro, "prefill_full",
                        lambda *a, **k: (calls.append(1),
                                         orig(*a, **k))[1])
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, 128, 513).astype(np.int32)
    out = eng.put([1], [prompt])
    assert calls, "513-token prompt must ride the prefill_full fast path"
    from deepspeed_tpu.models.transformer import _forward
    dense, _ = _forward(cfg, params, jnp.asarray(prompt)[None])
    np.testing.assert_allclose(out[1], np.asarray(dense[0, -1]), atol=2e-3)
    # and the clip invariant directly: an out-of-table position embeds
    # exactly like the last valid one (explicit clip, not XLA clamp luck)
    e_hi = ro._embed(cfg, params, jnp.asarray([5]), jnp.asarray([1023]))
    e_last = ro._embed(cfg, params, jnp.asarray([5]), jnp.asarray([767]))
    np.testing.assert_array_equal(np.asarray(e_hi), np.asarray(e_last))


def test_decode_burst_under_transfer_guard_clean():
    """Dynamic DST001 enforcement (analysis/transfer_guard.py): after a
    warm-up generation compiles the programs, a full prefill + burst-
    decode generation runs under jax's transfer guard with BOTH
    directions on "disallow".  Every intended fetch in the hot path is
    explicit (jax.device_get), every staging explicit (jnp.asarray /
    device_put), so nothing trips.  On this CPU backend the d2h guard is
    zero-copy-blind, but the h2d direction has full teeth: an accidental
    python-scalar operand or a mid-burst RECOMPILE (fresh trace-time
    constants) raises immediately — which also makes this a dynamic
    recompile detector for the decode loop."""
    from deepspeed_tpu.analysis.transfer_guard import no_host_transfers
    model, params = _model()
    eng = _engine(model, params)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 128, 12).astype(np.int32)
    want = eng.generate(prompt, max_new_tokens=9, uid=1)   # warm-up
    with no_host_transfers(device_to_host="disallow",
                           host_to_device="disallow"):
        got = eng.generate(prompt, max_new_tokens=9, uid=2)
    np.testing.assert_array_equal(got, want)
    # stochastic per-row path too (temperature staging must be explicit)
    eng.decode_burst_step  # touch: same engine drives the serve loop
    eng2 = _engine(model, params)
    w2 = eng2.generate(prompt, max_new_tokens=6, uid=3, mode="sample",
                       temperature=0.8, top_k=8)
    with no_host_transfers(device_to_host="disallow",
                           host_to_device="disallow"):
        eng2.generate(prompt, max_new_tokens=6, uid=4, mode="sample",
                      temperature=0.8, top_k=8)
    assert len(w2) == 6


def test_transfer_guard_negative_control():
    """The guard actually bites on this backend: an IMPLICIT
    host->device transfer (python scalar operand) raises under
    "disallow", and the same expression passes outside the guard —
    proving the clean-burst test above is not vacuous."""
    from deepspeed_tpu.analysis.transfer_guard import no_host_transfers
    x = jnp.asarray(np.ones(4, np.float32))
    _ = x + 1.0                                  # fine outside the guard
    with no_host_transfers(device_to_host="disallow",
                           host_to_device="disallow"):
        with pytest.raises(Exception, match="[Tt]ransfer"):
            _ = x + np.float32(1.0)              # implicit scalar h2d


def test_audit_blocks_counts_leased_and_free_state_slots():
    """A model with per-sequence recurrent state: a slot a decode row
    (`max_seqs`) and a scratch one; `audit_blocks` counts them beside the
    blocks, `flush` hands one back (tests/test_ssm_serving.py has the
    programs, and the manager's own refusal when slots run out)."""
    eng = build_engine("falcon_h1", "tiny", dtype=jnp.float32,
                       engine_config=RaggedInferenceEngineConfig(
                           num_blocks=24, block_size=8, max_blocks_per_seq=6,
                           max_seqs=2))
    assert eng.arena["ssm"].shape[1] == 3           # two slots and scratch
    assert eng.free_slots == 2
    eng.put([1, 2], [np.arange(9, dtype=np.int32)] * 2)
    audit = eng.audit_blocks()
    assert (audit["state_slots_live"], audit["state_slots_free"],
            audit["state_slots_total"]) == (2, 0, 2)
    assert eng.free_slots == 0 and audit["live"] == 4
    with pytest.raises(RuntimeError, match="too many concurrent sequences"):
        eng.put([3], [np.arange(9, dtype=np.int32)])
    assert 3 not in eng.state.seqs
    eng.flush(1)
    audit = eng.audit_blocks()
    assert (audit["state_slots_live"], audit["state_slots_free"]) == (1, 1)
    assert eng.free_slots == 1
