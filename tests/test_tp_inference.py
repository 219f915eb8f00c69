"""Tensor-parallel inference serving: fused compute-collective kernels
(ISSUE 12).

Locks, on the 8-virtual-device CPU mesh:

- interpret-mode tile parity for the fused Pallas matmul and ring-vs-XLA
  parity for the ag_matmul / matmul_rs collective-matmuls;
- tp=2 vs tp=1 bit-parity of the GREEDY TOKEN streams (and tight logits
  agreement) through put/step, decode_burst_step, and the speculative
  verify compose — for BOTH tp_collectives modes;
- sharded-arena KV block IO: reassembled round trips (including across
  tp degrees — the prefix-migration / disagg-handoff wire) and the
  arena's NamedSharding surviving adoption writes;
- config validation + JSON wiring of the ServingConfig TP fields, the
  engine-factory fold (apply_serving_tp), and the ServeLoop parity lock
  both directions (tp config off = bit-for-bit; tp=2 loop = same
  outputs as tp=1).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models import Transformer
from deepspeed_tpu.models.transformer import TransformerConfig

pytestmark = pytest.mark.serving


def _model(**kw):
    cfg_kw = dict(vocab_size=128, hidden_size=64, num_layers=2,
                  num_heads=4, num_kv_heads=2, max_seq_len=128,
                  pos_emb="rope", norm="rmsnorm", activation="swiglu",
                  dtype=jnp.float32)
    cfg_kw.update(kw)
    cfg = TransformerConfig(**cfg_kw)
    model = Transformer(cfg)
    return model, model.init_params(jax.random.PRNGKey(3))


def _engine(model, params, **kw):
    base = dict(num_blocks=64, block_size=8, max_blocks_per_seq=16,
                max_seqs=4, prefill_chunk_size=16,
                max_prefill_tokens_per_step=64, full_prompt_prefill=False)
    base.update(kw)
    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**base))


# ----------------------------------------------------------------------
# ops/tp_matmul.py: kernel parity
# ----------------------------------------------------------------------
def test_tile_matmul_interpret_parity(monkeypatch):
    """The Pallas MXU tile kernel must match jnp.dot (f32 accumulation)
    in interpret mode, including multi-block K accumulation."""
    import jax.experimental.pallas as pl
    import deepspeed_tpu.utils.device as device_mod
    import deepspeed_tpu.ops.tp_matmul as tpm
    monkeypatch.setattr(tpm.pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    rng = np.random.RandomState(0)
    for (M, K, N) in ((16, 256, 128), (8, 512, 384), (64, 128, 128)):
        x = jnp.asarray(rng.randn(M, K), jnp.float32)
        w = jnp.asarray(rng.randn(K, N), jnp.float32)
        got = tpm.tile_matmul(x, w, impl="pallas")
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(
            got, jnp.dot(x, w, preferred_element_type=jnp.float32),
            rtol=1e-5, atol=1e-4)
    # forced pallas refuses loudly off-tile / off-TPU (no silent fallback)
    with pytest.raises(ValueError, match="pallas"):
        tpm.tile_matmul(jnp.zeros((5, 100)), jnp.zeros((100, 60)),
                        impl="pallas")
    monkeypatch.setattr(device_mod, "platform", lambda: "cpu")
    with pytest.raises(ValueError, match="pallas"):
        tpm.tile_matmul(jnp.zeros((16, 256)), jnp.zeros((256, 128)),
                        impl="pallas")


def test_ring_collective_matmuls_match_xla(devices8):
    """ag_matmul / matmul_rs (ring schedules) vs their monolithic XLA
    twins and a plain replicated matmul — the fused kernels are a
    schedule change, not a math change."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.ops.tp_matmul import (ag_matmul, ag_matmul_xla,
                                             matmul_rs, matmul_rs_xla,
                                             tile_matmul)
    from deepspeed_tpu.parallel.mesh import AXIS_TP, make_mesh
    from jax import shard_map
    tp = 4
    topo = make_mesh(dp=1, tp=tp, devices=devices8[:tp])
    rng = np.random.RandomState(0)
    S, H, F = 16, 32, 64
    x = jnp.asarray(rng.randn(S, H), jnp.float32)
    w1 = jnp.asarray(rng.randn(H, F), jnp.float32)
    w2 = jnp.asarray(rng.randn(F, H), jnp.float32)
    ref = jnp.tanh(x @ w1) @ w2

    def block(ag, rs):
        def f(x, w1, w2):
            y = ag(x, AXIS_TP, tp, lambda c: tile_matmul(
                c, w1, impl="jnp").astype(x.dtype))
            return rs(jnp.tanh(y), AXIS_TP, tp,
                      lambda c: tile_matmul(c, w2, impl="jnp"))
        return jax.jit(shard_map(
            f, mesh=topo.mesh, axis_names={AXIS_TP},
            in_specs=(P(AXIS_TP, None), P(None, AXIS_TP), P(AXIS_TP, None)),
            out_specs=P(AXIS_TP, None), check_vma=False))

    fused = block(ag_matmul, matmul_rs)(x, w1, w2)
    xla = block(ag_matmul_xla, matmul_rs_xla)(x, w1, w2)
    np.testing.assert_allclose(fused, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(xla, ref, rtol=1e-5, atol=1e-5)
    # and the fused program's collectives are ring hops, not monoliths
    txt = block(ag_matmul, matmul_rs).lower(x, w1, w2).compile().as_text()
    assert "collective-permute" in txt


# ----------------------------------------------------------------------
# engine parity: tp=2 vs tp=1, both collective modes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("collectives", ["xla", "fused"])
def test_tp2_greedy_serving_bit_parity(collectives):
    """The acceptance lock: tp=2 greedy decode on the forced-host
    2-device mesh is TOKEN-BIT-IDENTICAL to tp=1 (f32) through
    put/step (prefill logits feed first-token argmax), the burst
    decode path, and the speculative verify compose; logits agree to
    float-noise tolerance."""
    model, params = _model()
    e1 = _engine(model, params)
    e2 = _engine(model, params, tensor_parallel_size=2,
                 tp_collectives=collectives)
    assert e2.tp == 2
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (25, 7)]
    o1 = e1.put([0, 1], list(prompts))
    o2 = e2.put([0, 1], list(prompts))
    assert set(o1) == set(o2) == {0, 1}
    for u in (0, 1):
        np.testing.assert_allclose(o1[u], o2[u], rtol=2e-4, atol=2e-4)
        assert int(np.argmax(o1[u])) == int(np.argmax(o2[u]))
    # stage first greedy token, then compiled bursts must chain
    # bit-identically
    for e, o in ((e1, o1), (e2, o2)):
        for u in (0, 1):
            e.state.seqs[u].generated.append(int(np.argmax(o[u])))
    b1 = e1.decode_burst_step(n_steps=8, mode="greedy")
    b2 = e2.decode_burst_step(n_steps=8, mode="greedy")
    for u in (0, 1):
        np.testing.assert_array_equal(b1[u], b2[u])
    # speculative verify compose: same drafts in, same emissions out
    drafts = {0: [int(t) for t in b1[0][-3:]], 1: [int(b1[1][-1])]}
    d1 = e1.decode_burst_step(drafts=drafts, draft_span=4, mode="greedy")
    d2 = e2.decode_burst_step(drafts=drafts, draft_span=4, mode="greedy")
    for u in (0, 1):
        np.testing.assert_array_equal(d1[u][0], d2[u][0])
        assert d1[u][1:] == d2[u][1:]
    # host-logits decode path (put continuation) agrees too
    n1 = e1.put([1], [np.asarray([5], np.int32)])
    n2 = e2.put([1], [np.asarray([5], np.int32)])
    np.testing.assert_allclose(n1[1], n2[1], rtol=2e-4, atol=2e-4)
    e1.audit_blocks()
    e2.audit_blocks()


def test_tp2_fused_with_paged_kernels_interpret(monkeypatch):
    """The fused-TP programs' PER-SHARD paged-kernel branch (taken on
    TPU): interpret mode stands in for the Mosaic compile, the platform is
    patched so the gates take the kernel path, and the logits must
    match a tp=1 attn_impl='jnp' engine — the kernel wiring inside the
    shard_map region, not just the CPU dense fallback."""
    import functools
    import jax.experimental.pallas as pl
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(pl.pallas_call, interpret=True))
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    kw = dict(vocab_size=128, hidden_size=256, num_layers=2, num_heads=4,
              num_kv_heads=2, max_seq_len=256, pos_emb="rope",
              norm="rmsnorm", activation="swiglu", dtype=jnp.float32)
    model_k, params = _model(attn_impl="pallas", **kw)
    model_j, _ = _model(attn_impl="jnp", **kw)
    base = dict(num_blocks=24, block_size=8, max_blocks_per_seq=16,
                max_seqs=2, prefill_chunk_size=16,
                max_prefill_tokens_per_step=64, full_prompt_prefill=False)
    eng_k = _engine(model_k, params, tensor_parallel_size=2,
                    tp_collectives="fused", **base)
    assert eng_k._tpp._decode_kernel       # the gate took the kernel path
    eng_j = _engine(model_j, params, **base)
    prompt = np.random.RandomState(21).randint(0, 128, 23).astype(np.int32)
    out_k = eng_k.put([0], [prompt])
    out_j = eng_j.put([0], [prompt])
    np.testing.assert_allclose(out_k[0], out_j[0], rtol=2e-4, atol=2e-4)
    nxt = np.asarray([int(np.argmax(out_j[0]))], np.int32)
    out_k2 = eng_k.put([0], [nxt])
    out_j2 = eng_j.put([0], [nxt])
    np.testing.assert_allclose(out_k2[0], out_j2[0], rtol=2e-4, atol=2e-4)


def test_tp_fused_refuses_unsupported_layouts():
    """tp_collectives='fused' must refuse loudly — never silently serve
    the GSPMD path — for layouts the fused forward is not wired for;
    and 'fused' at tp=1 is a config error (nothing to fuse)."""
    model, params = _model()
    with pytest.raises(ValueError, match="tensor_parallel_size > 1"):
        _engine(model, params, tp_collectives="fused")
    with pytest.raises(ValueError, match="tp_collectives"):
        _engine(model, params, tensor_parallel_size=2,
                tp_collectives="ring")
    # post-norm arch: refused with the reason + escape hatch named
    model_pn, params_pn = _model(post_norm=True, pos_emb="learned",
                                 norm="layernorm", activation="gelu")
    with pytest.raises(ValueError, match="tp_collectives='xla'"):
        _engine(model_pn, params_pn, tensor_parallel_size=2,
                tp_collectives="fused")
    # fp8 weight dicts: not TP-sharded, refused
    from deepspeed_tpu.models.transformer import quantize_serving_weights
    qparams = quantize_serving_weights(
        jax.tree.map(lambda x: x, params))
    with pytest.raises(ValueError, match="fp8"):
        _engine(model, qparams, tensor_parallel_size=2,
                tp_collectives="fused")
    # stream rows must divide by tp
    with pytest.raises(ValueError, match="max_seqs"):
        _engine(model, params, tensor_parallel_size=2,
                tp_collectives="fused", max_seqs=3)
    # the xla escape hatch serves all of these
    eng = _engine(model_pn, params_pn, tensor_parallel_size=2)
    assert eng.tp == 2 and eng._tpp is None


def test_tp1_default_engine_untouched():
    """tp=1 must never build TP programs or touch the new code paths —
    the byte-identical-default discipline."""
    model, params = _model()
    eng = _engine(model, params)
    assert eng.tp == 1 and eng._tpp is None and eng.topology is None
    assert eng.config.tp_collectives == "xla"


# ----------------------------------------------------------------------
# sharded-arena KV block IO (prefix migration / disagg handoff wire)
# ----------------------------------------------------------------------
def test_sharded_arena_block_io_roundtrip_and_cross_tp():
    """read/write_kv_blocks on a tp=2 engine: pages reassemble to the
    GLOBAL layout on read, adopt correctly on write, the arena keeps
    its NamedSharding across adoption writes, and pages exchange
    cleanly with a tp=1 engine (the cross-degree handoff case)."""
    model, params = _model()
    e1 = _engine(model, params)
    e2 = _engine(model, params, tensor_parallel_size=2)
    assert len(e2.arena["k"].sharding.device_set) == 2
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 128, 17).astype(np.int32)
    o1 = e1.put([0], [prompt])
    o2 = e2.put([0], [prompt])
    np.testing.assert_allclose(o1[0], o2[0], rtol=2e-4, atol=2e-4)
    blocks2 = list(e2.state.seqs[0].blocks)[:2]
    k2, v2 = e2.read_kv_blocks(blocks2)
    # global page shape: [L, n_blocks, block_size, NKV, D]
    assert k2.shape == (2, 2, 8, 2, 16)
    blocks1 = list(e1.state.seqs[0].blocks)[:2]
    k1, v1 = e1.read_kv_blocks(blocks1)
    np.testing.assert_allclose(k1, k2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(v1, v2, rtol=2e-4, atol=2e-4)
    # adopt tp=1 pages into the tp=2 arena at fresh blocks: values land
    # bit-for-bit and the arena stays sharded
    fresh = e2.state.allocator.allocate(2)
    try:
        e2.write_kv_blocks(fresh, k1, v1)
        assert len(e2.arena["k"].sharding.device_set) == 2, (
            "adoption write dropped the arena's tp sharding")
        k_back, v_back = e2.read_kv_blocks(fresh)
        np.testing.assert_array_equal(k_back, k1)
        np.testing.assert_array_equal(v_back, v1)
    finally:
        e2.state.allocator.free(fresh)
    # wrong-shaped pages still refuse loudly
    with pytest.raises(ValueError, match="does not fit"):
        e2.write_kv_blocks(blocks2, k1[:, :1], v1[:, :1])
    e1.flush(0)
    e2.flush(0)
    e1.audit_blocks()
    e2.audit_blocks()


# ----------------------------------------------------------------------
# ServingConfig wiring + ServeLoop parity lock
# ----------------------------------------------------------------------
def test_serving_config_tp_fields_validation_and_json():
    from deepspeed_tpu.config.config import ConfigError, ServingConfig
    cfg = ServingConfig.from_dict({"tensor_parallel_size": 2,
                                   "tp_collectives": "fused"})
    assert cfg.tensor_parallel_size == 2
    assert cfg.tp_collectives == "fused"
    assert ServingConfig.from_dict({}).tensor_parallel_size == 1
    with pytest.raises(ConfigError, match="tensor_parallel_size"):
        ServingConfig.from_dict({"tensor_parallel_size": 0})
    with pytest.raises(ConfigError, match="tp_collectives"):
        ServingConfig.from_dict({"tp_collectives": "ring"})
    with pytest.raises(ConfigError, match="fused"):
        ServingConfig.from_dict({"tp_collectives": "fused"})


def test_apply_serving_tp_engine_factory_fold():
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.inference.v2.model_registry import apply_serving_tp
    scfg = ServingConfig(tensor_parallel_size=2, tp_collectives="fused")
    out = apply_serving_tp(None, scfg)
    assert out.tensor_parallel_size == 2
    assert out.tp_collectives == "fused"
    base = RaggedInferenceEngineConfig(num_blocks=8)
    out = apply_serving_tp(base, scfg)
    assert out.num_blocks == 8 and out.tensor_parallel_size == 2
    with pytest.raises(ValueError, match="conflicts"):
        apply_serving_tp(
            RaggedInferenceEngineConfig(tensor_parallel_size=4), scfg)
    # defaults pass through untouched
    out = apply_serving_tp(base, ServingConfig())
    assert out.tensor_parallel_size == 1
    assert out.tp_collectives == "xla"


def test_serve_loop_tp_mismatch_refused():
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import ServeLoop
    model, params = _model()
    eng = _engine(model, params)           # tp=1 engine
    with pytest.raises(ValueError, match="tensor_parallel_size"):
        ServeLoop(eng, ServingConfig(tensor_parallel_size=2))
    # the silent-degradation direction is refused: serving asked for
    # fused collectives, the engine runs the xla path
    eng_xla = _engine(model, params, tensor_parallel_size=2)
    with pytest.raises(ValueError, match="fused"):
        ServeLoop(eng_xla, ServingConfig(tensor_parallel_size=2,
                                         tp_collectives="fused"))
    # the reverse is legal: an engine configured fused directly serves
    # a loop whose serving config keeps the "xla" default — no forced
    # knob duplication (apply_serving_tp lets engine values survive)
    eng_fused = _engine(model, params, tensor_parallel_size=2,
                        tp_collectives="fused")
    ServeLoop(eng_fused, ServingConfig(tensor_parallel_size=2))


@pytest.mark.parametrize("collectives", ["xla", "fused"])
def test_serve_loop_tp2_outputs_match_tp1(collectives):
    """The ServeLoop parity lock, both directions: a tp=2 loop (either
    collectives mode, the ServingConfig TP fields set) serves the
    identical stream with BIT-FOR-BIT the tp=1 default-config loop's
    outputs, zero lost requests, zero leaked blocks."""
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.serving import RequestState, ServeLoop
    model, params = _model()
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, 128, n).astype(np.int32)
               for n in (25, 7, 13, 9)]
    outs = {}
    for tp in (1, 2):
        eng = (_engine(model, params) if tp == 1 else
               _engine(model, params, tensor_parallel_size=2,
                       tp_collectives=collectives))
        scfg = (ServingConfig(decode_burst=8, audit_blocks=True)
                if tp == 1 else
                ServingConfig(decode_burst=8, audit_blocks=True,
                              tensor_parallel_size=2,
                              tp_collectives=collectives))
        loop = ServeLoop(eng, scfg)
        reqs = [loop.submit(p, max_new_tokens=6) for p in prompts]
        done = loop.run_until_idle(max_steps=200)
        assert len(done) == len(reqs)
        assert all(r.state is RequestState.DONE for r in reqs)
        outs[tp] = [list(r.output_tokens) for r in reqs]
        eng.audit_blocks()
    assert outs[1] == outs[2]
