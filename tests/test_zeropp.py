"""ZeRO++ qwZ/qgZ: quantized param-allgather and grad-reduction wired
into the compiled train step (reference: partition_parameters.py:824
CUDAQuantizer allgather, coalesced_collectives.py:31
all_to_all_quant_reduce).  The flags must change the wire dtype (int8
payloads in the lowered collectives) while training stays on the fp32
trajectory within quantization tolerance.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu


pytestmark = pytest.mark.slow


def _params():
    k = jax.random.PRNGKey(0)
    return {f"w{i}": jax.random.normal(jax.random.fold_in(k, i),
                                       (64, 64)) * 0.1
            for i in range(4)}


def _loss_fn(p, batch, rng=None):
    x = batch["x"]
    for i in range(4):
        x = jnp.tanh(x @ p[f"w{i}"])
    return jnp.mean((x - batch["y"]) ** 2)


def _engine(zero_extra, stage=3):
    zo = {"stage": stage}
    zo.update(zero_extra)
    return dstpu.initialize(loss_fn=_loss_fn, params=_params(), config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": zo, "steps_per_print": 0})


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(16, 64).astype(np.float32),
            "y": rng.randn(16, 64).astype(np.float32)}


def _losses(eng, n=8):
    b = _batch()
    return [float(eng.train_batch(b)["loss"]) for _ in range(n)]


def test_qwz_qgz_loss_parity(devices8):
    """int8 wire quantization must track the exact trajectory."""
    base = _losses(_engine({}))
    quant = _losses(_engine({"zero_quantized_weights": True,
                             "zero_quantized_gradients": True}))
    assert quant[-1] < quant[0] * 0.7, quant  # it actually trains
    # within block-quantization tolerance of the exact path
    np.testing.assert_allclose(quant[-1], base[-1], rtol=0.15)


def test_qwz_only_and_qgz_only_train(devices8):
    for flags in ({"zero_quantized_weights": True},
                  {"zero_quantized_gradients": True}):
        losses = _losses(_engine(flags), n=6)
        assert losses[-1] < losses[0] * 0.8, (flags, losses)


def test_qgz_stage2(devices8):
    losses = _losses(_engine({"zero_quantized_gradients": True}, stage=2),
                     n=6)
    assert losses[-1] < losses[0] * 0.8, losses


def test_qgz_int4_wire(devices8):
    """zero_quantized_gradients_bits=4 — the reference's qgZ wire width
    (quant_reduce.cu ships int4).  Coarser codes, looser parity."""
    base = _losses(_engine({}), n=6)
    q4 = _losses(_engine({"zero_quantized_gradients": True,
                          "zero_quantized_gradients_bits": 4}), n=6)
    assert q4[-1] < q4[0] * 0.8, q4
    np.testing.assert_allclose(q4[-1], base[-1], rtol=0.3)


def test_int4_nibble_packing_roundtrip():
    """bits=4 must HALVE the collective payload (nibble packing), not
    ship 4-bit codes in int8 containers."""
    from deepspeed_tpu.comm.compressed import (_pack_nibbles,
                                               _unpack_nibbles)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-8, 8, (3, 256)), jnp.int8)
    p = _pack_nibbles(q)
    assert p.shape == (3, 128)       # half the bytes on the wire
    np.testing.assert_array_equal(np.asarray(_unpack_nibbles(p)),
                                  np.asarray(q))


def test_qgz_bits_validated():
    from deepspeed_tpu.config.config import ConfigError
    with pytest.raises(ConfigError, match="bits"):
        _engine({"zero_quantized_gradients": True,
                 "zero_quantized_gradients_bits": 6})


def test_flags_change_wire_dtype(devices8):
    """The collectives the step lowers to must carry int8 payloads when
    the flags are on (flags that parse but drive nothing would fail
    this)."""
    def collect_lines(eng):
        b = eng._shard_batch(_batch())
        txt = eng._train_step.lower(
            eng.state, b, jax.random.PRNGKey(0), {}).compile().as_text()
        return [l for l in txt.splitlines()
                if re.search(r"\b(all-gather|all-to-all)\b", l)
                and "= " in l]

    base_lines = collect_lines(_engine({}))
    qz_lines = collect_lines(_engine({"zero_quantized_weights": True,
                                      "zero_quantized_gradients": True}))
    base_int8 = [l for l in base_lines if re.search(r"\bs8\[", l)]
    qz_int8 = [l for l in qz_lines if re.search(r"\bs8\[", l)]
    assert not base_int8, "unquantized path unexpectedly ships int8"
    assert qz_int8, "qwZ/qgZ path ships no int8 collectives"
    # the gathers of the four 64x64 params must ride int8, i.e. an s8
    # all-gather whose payload is a param shard (64*64/8 = 512 elems)
    assert any("all-gather" in l for l in qz_int8), qz_int8
    assert any("all-to-all" in l for l in qz_int8), qz_int8


def _tfm_engine(qwz, hidden=512, layers=6, micro=1, seq=32):
    import jax.numpy as jnp
    from deepspeed_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=hidden, num_layers=layers, num_heads=4,
        max_seq_len=seq, pos_emb="rope", norm="rmsnorm",
        activation="swiglu", dtype=jnp.float32, attn_impl="jnp")
    zo = {"stage": 3}
    if qwz:
        zo.update({"zero_quantized_weights": True,
                   "zero_quantized_gradients": True})
    return dstpu.initialize(model=Transformer(cfg), config={
        "train_micro_batch_size_per_gpu": micro,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": zo, "steps_per_print": 0}), cfg


def _temp_bytes(eng, cfg, seq=32):
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (eng.config.train_batch_size, seq)).astype(np.int32)
    b = eng._shard_batch({"input_ids": ids})
    comp = eng._train_step.lower(eng.state, b, jax.random.PRNGKey(0),
                                 {}).compile()
    return int(comp.memory_analysis().temp_size_in_bytes)


def test_qwz_per_layer_gather_composes_with_stage3_memory(devices8):
    """qwZ used to gather EVERY sharded leaf at the
    top of the loss, so its peak memory was ZeRO-1/2-like.  With the
    per-layer gather (layer_gather.py + the model scan hook) the compiled
    step's temp memory must sit near plain stage 3, far below the eager
    whole-model gather.  Geometry chosen weight-heavy (hidden 512 x 6
    layers, micro 1, seq 32) so residency differences dominate."""
    import deepspeed_tpu.runtime.zero.quantized as qz

    eng3, cfg = _tfm_engine(qwz=False)
    stage3 = _temp_bytes(eng3, cfg)
    engq, _ = _tfm_engine(qwz=True)
    per_layer = _temp_bytes(engq, cfg)
    old = qz.PER_LAYER_GATHER
    try:
        qz.PER_LAYER_GATHER = False
        enge, _ = _tfm_engine(qwz=True)
        eager = _temp_bytes(enge, cfg)
    finally:
        qz.PER_LAYER_GATHER = old
    # per-layer ~ stage-3 class; eager holds the whole gathered model
    assert per_layer < eager * 0.75, (per_layer, eager, stage3)
    assert per_layer < stage3 * 1.6, (per_layer, eager, stage3)

    # and it still trains on the exact trajectory class (parity vs eager)
    ids = np.random.RandomState(1).randint(
        0, cfg.vocab_size, (engq.config.train_batch_size, 32)).astype(np.int32)
    losses = [float(engq.train_batch({"input_ids": ids})["loss"])
              for _ in range(6)]
    assert losses[-1] < losses[0], losses


_DTYPE_BYTES = {"s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "f64": 8, "pred": 1}


def _collective_wire_bytes(eng, batch, n=8):
    """Per-device wire-byte estimate from the compiled step's collective
    ops: all-gather/all-to-all cost (n-1)/n of the payload, all-reduce
    2x that (reduce+broadcast phases), collective-permute the payload.
    Absolute numbers are estimates; RATIOS between engines compiled from
    the same model/mesh are exact comparisons."""
    b = eng._shard_batch(batch)
    txt = eng._train_step.lower(
        eng.state, b, jax.random.PRNGKey(0), {}).compile().as_text()
    # the sync-op regex below cannot see async pairs; fail loudly if the
    # backend ever asyncifies collectives rather than undercount silently
    assert "-start" not in txt, "async collectives: census regex blind"
    total = 0.0
    for m in re.finditer(
            r"%(all-gather|all-to-all|all-reduce|reduce-scatter|"
            r"collective-permute)[.\d]* = (.*?) \1", txt):
        op, result_ty = m.groups()
        size = 0
        # result type may be a tuple — sum every dtype[shape] element
        for dt, shape in re.findall(r"([a-z0-9]+)\[([\d,]*)\]", result_ty):
            if dt not in _DTYPE_BYTES:
                continue
            elems = 1
            for d in shape.split(","):
                if d:
                    elems *= int(d)
            size += elems * _DTYPE_BYTES[dt]
        if op == "all-reduce":
            total += 2.0 * size * (n - 1) / n
        elif op in ("all-gather", "all-to-all", "reduce-scatter"):
            total += size * (n - 1) / n
        else:
            total += size
    return total


def test_zeropp_wire_bytes_measured(devices8):
    """The qwZ/qgZ byte saving must be MEASURED, not
    asserted by dtype alone.  Census the compiled step's collectives:
    int8 wire must at least halve stage-3 param+grad traffic; int4 qgZ
    must cut strictly deeper.  (Reference quantifies 4x for the full
    qwZ+hpZ+qgZ triple, docs/_tutorials/zeropp.md:13-17.)"""
    batch = _batch()
    base = _collective_wire_bytes(_engine({}), batch)
    q8 = _collective_wire_bytes(_engine({"zero_quantized_weights": True,
                                         "zero_quantized_gradients": True}),
                                batch)
    q4 = _collective_wire_bytes(_engine({"zero_quantized_weights": True,
                                         "zero_quantized_gradients": True,
                                         "zero_quantized_gradients_bits": 4}),
                                batch)
    # re-measured 2026-08-03 on the 8-device mesh: base 90.5 KB, q8
    # 29.2 KB (3.1x), q4 22.0 KB (4.1x) — fp32 baseline.  (The 2026-08-01
    # numbers, 6.2x/12.1x, predate the census catching the backward
    # all-to-all tuples; the test had started failing on main before this
    # re-anchor.)  A bf16 baseline would halve the ratios; the reference's
    # 4x headline is for the full qwZ+hpZ+qgZ triple at int4.
    assert q8 <= base / 2.5, (base, q8, q4)
    assert q4 <= base / 4.0, (base, q8, q4)


def test_qwz_requires_stage3():
    from deepspeed_tpu.config.config import ConfigError
    with pytest.raises(ConfigError, match="stage 3"):
        _engine({"zero_quantized_weights": True}, stage=2)


def test_qgz_requires_stage2():
    from deepspeed_tpu.config.config import ConfigError
    with pytest.raises(ConfigError, match="stage >= 2"):
        _engine({"zero_quantized_gradients": True}, stage=1)
