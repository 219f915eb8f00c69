"""AutoTP classification + optimized linear / LoRA / fp-quant tests
(reference: tests/unit/model_parallelism, tests/unit/linear/)."""
import jax
from jax import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import deepspeed_tpu as dstpu
from deepspeed_tpu.module_inject import (
    AutoTP, build_tp_rules, classify_param, column_parallel_linear,
    row_parallel_linear, vocab_parallel_embedding,
)
from deepspeed_tpu.linear import (
    LoRAConfig, QuantizationConfig, OptimizedLinear, LoRAOptimizedLinear,
    QuantizedLinear, QuantizedParameter, fp_quantize, fp_dequantize,
)


class TestAutoTP:
    def test_classify_hf_llama_names(self):
        assert classify_param("model.layers.0.self_attn.q_proj.kernel", (64, 64)) == "column"
        assert classify_param("model.layers.0.self_attn.o_proj.kernel", (64, 64)) == "row"
        assert classify_param("model.layers.0.mlp.down_proj.kernel", (256, 64)) == "row"
        assert classify_param("model.layers.0.mlp.gate_proj.kernel", (64, 256)) == "column"
        assert classify_param("model.embed_tokens.embedding", (32000, 64)) == "vocab"
        assert classify_param("model.norm.weight", (64,)) == "replicated"

    def test_classify_gpt2_bloom_names(self):
        assert classify_param("h.0.attn.c_attn.kernel", (64, 192)) == "column"
        assert classify_param("h.0.attn.c_proj.kernel", (64, 64)) == "row"
        assert classify_param("h.0.mlp.dense_4h_to_h.kernel", (256, 64)) == "row"
        assert classify_param("h.0.self_attention.query_key_value.kernel",
                              (64, 192)) == "column"

    def test_rules_specs(self):
        params = {
            "layers": {
                "0": {"q_proj": {"kernel": jnp.zeros((8, 8))},
                      "o_proj": {"kernel": jnp.zeros((8, 8))}},
            },
            "ln": {"weight": jnp.zeros((8,))},
        }
        rules = build_tp_rules(params)
        assert rules(("layers", "0", "q_proj", "kernel"), (8, 8)) == \
            PartitionSpec(None, "tp")
        assert rules(("layers", "0", "o_proj", "kernel"), (8, 8)) == \
            PartitionSpec("tp", None)
        assert rules(("ln", "weight"), (8,)) is None

    def test_torch_layout(self):
        rules = build_tp_rules({"q_proj": {"weight": jnp.zeros((24, 8))}},
                               kernel_in_first=False)
        assert rules(("q_proj", "weight"), (24, 8)) == PartitionSpec("tp", None)

    def test_own_model_rules_agree(self):
        from deepspeed_tpu.models import Transformer, llama_config
        model = Transformer(llama_config("tiny"))
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        auto = AutoTP().rules(shapes)
        # stacked [L, H, O] qkv weights: column-parallel on the out dim
        assert auto(("layers", "wq"), (4, 256, 256)) == \
            PartitionSpec(None, None, "tp")
        assert auto(("layers", "wo"), (4, 256, 256)) == \
            PartitionSpec(None, "tp", None)

    def test_tp_model_init(self):
        mgr = dstpu.tp_model_init(params={"fc1": {"kernel": jnp.zeros((8, 32))}},
                                  tp_size=2)
        assert mgr.tp_size == 2
        assert mgr.tp_rules(("fc1", "kernel"), (8, 32)) == PartitionSpec(None, "tp")

    def test_shardmap_tp_linears_match_dense(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
        H, O = 16, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (2, H))
        w1 = jax.random.normal(jax.random.PRNGKey(1), (H, O))
        w2 = jax.random.normal(jax.random.PRNGKey(2), (O, H))

        def f(x, w1_local, w2_local):
            h = column_parallel_linear(x, w1_local)
            return row_parallel_linear(h, w2_local, axis_name="tp")

        P = PartitionSpec
        out = shard_map(f, mesh=mesh,
                            in_specs=(P(), P(None, "tp"), P("tp", None)),
                            out_specs=P())(x, w1, w2)
        ref = (x @ w1) @ w2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_vocab_parallel_embedding(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
        table = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
        ids = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0, 64)
        P = PartitionSpec
        out = shard_map(
            lambda i, t: vocab_parallel_embedding(i, t, "tp"),
            mesh=mesh, in_specs=(P(), P("tp", None)), out_specs=P())(ids, table)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.take(table, ids, axis=0)),
                                   rtol=1e-5, atol=1e-5)


class TestFpQuant:
    @pytest.mark.parametrize("q_bits,tol", [(8, 0.08), (6, 0.3), (12, 0.012)])
    def test_roundtrip_error(self, q_bits, tol):
        w = jax.random.normal(jax.random.PRNGKey(0), (4, 512))
        codes, scales = fp_quantize(w, q_bits=q_bits, group_size=512)
        deq = fp_dequantize(codes, scales, q_bits=q_bits, shape=w.shape,
                            dtype=jnp.float32)
        rel = float(jnp.max(jnp.abs(deq - w)) / jnp.max(jnp.abs(w)))
        assert rel < tol, rel

    def test_fp8_native_dtype(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (512,))
        codes, _ = fp_quantize(w, q_bits=8)
        assert codes.dtype == jnp.float8_e4m3fn

    def test_quantized_parameter_pytree(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        qp = QuantizedParameter.quantize(w, QuantizationConfig(group_size=128))
        leaves = jax.tree.leaves(qp)
        assert len(leaves) == 2
        out = jax.jit(lambda q: q.dequantized())(qp)
        assert out.shape == (16, 32)
        assert qp.nbytes < w.size * 2  # smaller than bf16

    def test_quantized_linear(self):
        lin = QuantizedLinear(32, 16, quantization_config=QuantizationConfig(
            q_bits=8, group_size=128))
        p = lin.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32), jnp.bfloat16)
        y = lin(p, x)
        assert y.shape == (4, 16)
        ref = x.astype(jnp.float32) @ p["weight"].dequantized().astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref),
                                   rtol=0.1, atol=0.1)


class TestOptimizedLinear:
    def test_factory_dispatch(self):
        assert type(OptimizedLinear(8, 8)).__name__ == "_PlainLinear"
        assert isinstance(OptimizedLinear(8, 8, lora_config=LoRAConfig(lora_r=4)),
                          LoRAOptimizedLinear)
        assert isinstance(
            OptimizedLinear(8, 8, quantization_config=QuantizationConfig()),
            QuantizedLinear)

    def test_lora_forward_and_frozen_base(self):
        lin = OptimizedLinear(16, 8, lora_config=LoRAConfig(lora_r=4,
                                                            lora_alpha=8.0))
        p = lin.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        # lora_b starts at zero -> output equals base matmul
        y0 = lin(p, x)
        ref = x @ np.asarray(p["base"], np.float32)
        np.testing.assert_allclose(np.asarray(y0, np.float32), ref,
                                   rtol=2e-2, atol=2e-2)
        # gradients: base frozen (zero), adapters live
        g = jax.grad(lambda pp: jnp.sum(lin(pp, x) ** 2))(p)
        assert float(jnp.max(jnp.abs(g["base"]))) == 0.0
        # at init lora_b==0, so dL/dlora_a==0 but dL/dlora_b is live
        assert float(jnp.max(jnp.abs(g["lora_b"]))) > 0.0

    def test_lora_quantized_base(self):
        lin = OptimizedLinear(
            16, 8, lora_config=LoRAConfig(lora_r=4),
            quantization_config=QuantizationConfig(q_bits=8, group_size=128))
        p = lin.init_params(jax.random.PRNGKey(0))
        assert isinstance(p["base"], QuantizedParameter)
        y = lin(p, jnp.ones((2, 16), jnp.bfloat16))
        assert y.shape == (2, 8)

    def test_lora_trains_under_engine(self):
        lin = OptimizedLinear(8, 8, lora_config=LoRAConfig(lora_r=2))
        params = lin.init_params(jax.random.PRNGKey(0))

        def loss_fn(p, batch, rng=None):
            return jnp.mean((lin(p, batch["x"]) - batch["y"]) ** 2)

        engine = dstpu.initialize(loss_fn=loss_fn, params=params, config={
            "train_micro_batch_size_per_gpu": 4,
            "optimizer": {"type": "adam", "params": {"lr": 1e-2}},
        })
        x = np.random.RandomState(0).randn(32, 8).astype(np.float32)
        y = -x
        base0 = np.asarray(engine.state.params["base"], np.float32).copy()
        losses = [float(engine.train_batch({"x": x, "y": y})["loss"])
                  for _ in range(8)]
        assert losses[-1] < losses[0]
        base1 = np.asarray(engine.state.params["base"], np.float32)
        np.testing.assert_allclose(base0, base1)  # base never moves
