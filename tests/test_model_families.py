"""Model family coverage (reference: per-arch policies in
module_inject/replace_policy.py + inference/v2/model_implementations)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import (
    Transformer, get_model_config, MODEL_FAMILIES,
)

FAMILIES = sorted(MODEL_FAMILIES)


pytestmark = pytest.mark.serving


def _tiny(family):
    kw = {"dtype": jnp.float32, "max_seq_len": 128}
    return get_model_config(family, "tiny", **kw)


class TestFamilies:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_train_forward_backward(self, family):
        cfg = _tiny(family)
        model = Transformer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                 cfg.vocab_size)
        if cfg.latent or cfg.static_kinds or cfg.ssm:   # inference.v2 only
            with pytest.raises(NotImplementedError, match="loss_fn"):
                model.loss_fn(params, {"input_ids": ids})
            return
        loss, aux = model.loss_fn(params, {"input_ids": ids})
        assert np.isfinite(float(loss))
        grads = jax.grad(lambda p: model.loss_fn(p, {"input_ids": ids})[0])(params)
        flat = jax.tree.leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat)
        # something should be learning in every family
        assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_decode_matches_forward(self, family):
        """Prefill-via-cache logits == full forward logits (the decode path
        shares weights but not code with the train path)."""
        cfg = _tiny(family)
        if cfg.latent or cfg.static_kinds or cfg.ssm:   # its own arena only
            with pytest.raises(NotImplementedError, match="init_cache"):
                Transformer(cfg).init_cache(batch=1, max_len=32)
            return
        if cfg.moe_experts > 1:
            # decode routes exactly (no capacity drops); lift the training
            # forward's capacity so its routing is drop-free and comparable
            import dataclasses
            cfg = dataclasses.replace(
                cfg, moe_capacity_factor=float(cfg.moe_experts),
                moe_min_capacity=64)
        model = Transformer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                 cfg.vocab_size)
        full = model.forward(params, ids)
        cache = model.init_cache(batch=1, max_len=32)
        prefill, cache = model.forward_with_cache(params, ids, cache)
        np.testing.assert_allclose(np.asarray(prefill), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("family", ["mistral", "bloom", "phi",
                                        "mixtral", "qwen2_moe"])
    def test_decode_step_consistency(self, family):
        """Token-by-token decode == one-shot prefill (exercises sliding
        window, alibi, partial rotary in the cache path)."""
        cfg = _tiny(family)
        model = Transformer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                 cfg.vocab_size)
        full, _ = model.forward_with_cache(params, ids,
                                           model.init_cache(1, 16))
        cache = model.init_cache(1, 16)
        outs = []
        for t in range(8):
            lg, cache = model.forward_with_cache(params, ids[:, t:t + 1], cache)
            outs.append(lg)
        step = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                                   rtol=2e-3, atol=2e-3)


class TestArchFeatures:
    def test_sliding_window_masks_old_keys(self):
        from deepspeed_tpu.ops.attention import attention_reference
        B, S, N, D = 1, 32, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, N, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, N, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, N, D))
        out_w = attention_reference(q, k, v, sliding_window=8)
        out_full = attention_reference(q, k, v)
        # early positions (< window) identical, late positions differ
        np.testing.assert_allclose(np.asarray(out_w[:, :8]),
                                   np.asarray(out_full[:, :8]), rtol=1e-5)
        assert float(jnp.max(jnp.abs(out_w[:, 16:] - out_full[:, 16:]))) > 1e-4

    def test_alibi_bias_monotone(self):
        from deepspeed_tpu.models.transformer import _alibi_bias, _alibi_slopes
        bias = _alibi_bias(4, 8, 8)
        assert bias.shape == (4, 8, 8)
        # distance-0 diagonal is zero, further back is more negative
        assert float(bias[0, 5, 5]) == 0.0
        assert float(bias[0, 5, 2]) < float(bias[0, 5, 4]) < 0.0
        s = _alibi_slopes(8)
        assert np.all(np.diff(np.asarray(s)) < 0)

    def test_partial_rope_passthrough(self):
        from deepspeed_tpu.models.transformer import _rope
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 16))
        pos = jnp.arange(4)[None, :]
        out = _rope(x, pos, 10000.0, pct=0.5)
        # the non-rotated tail is untouched
        np.testing.assert_allclose(np.asarray(out[..., 8:]),
                                   np.asarray(x[..., 8:]))
        assert float(jnp.max(jnp.abs(out[..., :8] - x[..., :8]))) > 1e-4

    def test_registry_errors(self):
        with pytest.raises(ValueError, match="unknown model family"):
            get_model_config("nope")


class TestSharedExpert:
    def test_shared_expert_params_and_gate(self):
        """qwen2-moe shared expert: weights exist per layer and contribute to
        the output (zeroing them changes logits)."""
        from deepspeed_tpu.models import qwen2_moe_config
        cfg = qwen2_moe_config("tiny", dtype=jnp.float32, max_seq_len=128)
        model = Transformer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        for k in ("moe_shared_w_up", "moe_shared_w_down",
                  "moe_shared_w_gate_proj", "moe_shared_gate"):
            assert k in params["layers"], k
        ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                 cfg.vocab_size)
        base = model.forward(params, ids)
        params["layers"]["moe_shared_w_down"] = jnp.zeros_like(
            params["layers"]["moe_shared_w_down"])
        ablated = model.forward(params, ids)
        assert float(jnp.max(jnp.abs(base - ablated))) > 1e-5

    def test_shared_expert_requires_moe(self):
        from deepspeed_tpu.models import TransformerConfig
        with pytest.raises(ValueError, match="moe_shared_expert_ffn"):
            TransformerConfig(moe_shared_expert_ffn=256)
