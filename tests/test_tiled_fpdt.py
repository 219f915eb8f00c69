"""ALST tiled compute + TiledLinear + FPDT tests (reference:
tests/unit/ulysses_alst/test_tiled_compute.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.sequence import (
    sequence_tiled_compute, tiled_mlp, tiled_fused_logits_loss, fpdt_attention,
)
from deepspeed_tpu.runtime.zero.tiling import TiledLinear


pytestmark = pytest.mark.slow


class TestSequenceTiled:
    def test_matches_untiled(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        fn = lambda h: jnp.tanh(h @ w)
        np.testing.assert_allclose(
            np.asarray(sequence_tiled_compute(fn, x, shards=4)),
            np.asarray(fn(x)), rtol=2e-5, atol=2e-5)

    def test_gradients_match(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 16))

        def loss_tiled(w):
            return jnp.sum(sequence_tiled_compute(
                lambda h: jax.nn.gelu(h @ w), x, shards=8))

        def loss_ref(w):
            return jnp.sum(jax.nn.gelu(x @ w))

        g1, g2 = jax.grad(loss_tiled)(w), jax.grad(loss_ref)(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-4, atol=2e-4)

    def test_tiled_mlp_wrapper(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 8))
        out = tiled_mlp(lambda h: h * 2.0, x, shards=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0)


class TestTiledLoss:
    def test_matches_full_softmax(self):
        B, S, H, V = 2, 32, 16, 64
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H))
        head = jax.random.normal(jax.random.PRNGKey(1), (H, V))
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)

        logits = (x @ head).astype(jnp.float32)
        ref = jnp.mean(jax.nn.logsumexp(logits, -1) -
                       jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])
        out = tiled_fused_logits_loss(x, head, labels, shards=8)
        np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)

    def test_masked(self):
        B, S, H, V = 1, 16, 8, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H))
        head = jax.random.normal(jax.random.PRNGKey(1), (H, V))
        labels = jnp.zeros((B, S), jnp.int32)
        mask = jnp.concatenate([jnp.ones((B, 8)), jnp.zeros((B, 8))], axis=1)
        out = tiled_fused_logits_loss(x, head, labels, shards=4, mask=mask)
        logits = (x @ head).astype(jnp.float32)[:, :8]
        ref = jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])
        np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)

    def test_grad_wrt_head(self):
        B, S, H, V = 1, 16, 8, 32
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H))
        head = jax.random.normal(jax.random.PRNGKey(1), (H, V))
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)

        def ref_loss(h):
            logits = (x @ h).astype(jnp.float32)
            return jnp.mean(jax.nn.logsumexp(logits, -1) -
                            jnp.take_along_axis(logits, labels[..., None], -1)[..., 0])

        g1 = jax.grad(lambda h: tiled_fused_logits_loss(x, h, labels, 4))(head)
        g2 = jax.grad(ref_loss)(head)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-5)


class TestFPDT:
    def _ref_causal(self, q, k, v):
        B, S, N, D = q.shape
        s = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", p, v.astype(jnp.float32)).astype(q.dtype)

    def test_matches_dense_causal(self):
        B, S, N, D = 2, 64, 4, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, N, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, N, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, N, D))
        out = fpdt_attention(q, k, v, chunk_size=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(self._ref_causal(q, k, v)),
                                   rtol=2e-4, atol=2e-4)

    def test_gqa(self):
        B, S, N, NKV, D = 1, 32, 8, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, N, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, NKV, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, NKV, D))
        out = fpdt_attention(q, k, v, chunk_size=8)
        kk = jnp.repeat(k, N // NKV, axis=2)
        vv = jnp.repeat(v, N // NKV, axis=2)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref_causal(q, kk, vv)),
                                   rtol=2e-4, atol=2e-4)

    def test_differentiable(self):
        B, S, N, D = 1, 32, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, N, D))
        g = jax.grad(lambda q_: jnp.sum(
            fpdt_attention(q_, q_, q_, chunk_size=8)))(q)
        assert np.isfinite(np.asarray(g)).all()

    def test_model_fpdt_config(self):
        from deepspeed_tpu.models import Transformer, TransformerConfig
        cfg = TransformerConfig(vocab_size=128, hidden_size=32, num_layers=2,
                                num_heads=4, max_seq_len=64, attn_chunk_size=16,
                                tiled_mlp_shards=2, tiled_loss_shards=4,
                                dtype=jnp.float32)
        model = Transformer(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
        labels = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 128)
        batch = {"input_ids": ids, "labels": labels}
        loss, _ = model.loss_fn(params, batch)
        assert np.isfinite(float(loss))
        # equals the untiled config's loss
        cfg0 = TransformerConfig(vocab_size=128, hidden_size=32, num_layers=2,
                                 num_heads=4, max_seq_len=64, dtype=jnp.float32)
        loss0, _ = Transformer(cfg0).loss_fn(params, batch)
        np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-4)


class TestTiledLinear:
    def test_matches_dense(self):
        lin = TiledLinear(32, 48, in_splits=4, out_splits=3)
        p = lin.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
        w = lin.to_dense(p)
        np.testing.assert_allclose(
            np.asarray(lin(p, x)), np.asarray(x @ w + p["bias"]),
            rtol=2e-5, atol=2e-5)

    def test_from_dense_roundtrip(self):
        lin = TiledLinear(16, 24, in_splits=2, out_splits=2, bias=False)
        w = jax.random.normal(jax.random.PRNGKey(0), (16, 24))
        p = lin.from_dense(w)
        np.testing.assert_allclose(np.asarray(lin.to_dense(p)), np.asarray(w))
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
        np.testing.assert_allclose(np.asarray(lin(p, x)), np.asarray(x @ w),
                                   rtol=2e-5, atol=2e-5)


class TestVocabParallelCE:
    def test_matches_full(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from deepspeed_tpu.sequence import vocab_parallel_cross_entropy
        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("tp",))
        B, S, V = 2, 8, 64
        logits = jax.random.normal(jax.random.PRNGKey(0), (B, S, V))
        labels = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)

        f = shard_map(
            lambda lg, lb: vocab_parallel_cross_entropy(lg, lb, "tp"),
            mesh=mesh, in_specs=(P(None, None, "tp"), P()), out_specs=P())
        out = f(logits, labels)
        ref = jax.nn.logsumexp(logits.astype(jnp.float32), -1) - \
            jnp.take_along_axis(logits.astype(jnp.float32), labels[..., None], -1)[..., 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestFPDTOffloadBackward:
    """The offloaded path's custom flash backward (reference:
    fpdt_layer.py:510 — chunked backward over host-parked K/V) must produce
    the same gradients as plain attention.  On the CPU suite the host
    placements are no-ops, so the chunked math itself is what's tested."""

    def _grads(self, fn, q, k, v):
        def loss(q_, k_, v_):
            out = fn(q_, k_, v_)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("nkv", [4, 2])
    def test_offload_grads_match_dense(self, nkv):
        from deepspeed_tpu.sequence.fpdt import _fpdt_custom
        rng = np.random.RandomState(0)
        B, S, NH, D = 2, 64, 4, 16
        q = jnp.asarray(rng.randn(B, S, NH, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, nkv, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, nkv, D), jnp.float32)

        def dense(q_, k_, v_):
            kk = jnp.repeat(k_, NH // nkv, axis=2) if nkv != NH else k_
            vv = jnp.repeat(v_, NH // nkv, axis=2) if nkv != NH else v_
            s = jnp.einsum("bqhd,bkhd->bhqk", q_, kk) / np.sqrt(D)
            mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            s = jnp.where(mask[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

        off = lambda q_, k_, v_: _fpdt_custom(q_, k_, v_, 16, True,
                                               1.0 / np.sqrt(D), True)
        want = self._grads(dense, q, k, v)
        got = self._grads(off, q, k, v)
        for g_w, g_g, name in zip(want, got, "qkv"):
            np.testing.assert_allclose(np.asarray(g_g), np.asarray(g_w),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=f"d{name}")

    def test_custom_bwd_matches_xla_autodiff_of_fwd(self):
        """The hand-written flash backward agrees with XLA autodiff of the
        same chunked forward (the pre-custom-vjp reference semantics)."""
        from deepspeed_tpu.sequence.fpdt import _fpdt_fwd_impl, _fpdt_custom
        rng = np.random.RandomState(1)
        B, S, NH, D = 1, 48, 2, 8
        q = jnp.asarray(rng.randn(B, S, NH, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, NH, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, NH, D), jnp.float32)
        plain = lambda q_, k_, v_: _fpdt_fwd_impl(q_, k_, v_, 8, True,
                                                  1.0 / np.sqrt(D),
                                                  False)[0]
        off = lambda q_, k_, v_: _fpdt_custom(q_, k_, v_, 8, True,
                                               1.0 / np.sqrt(D), True)
        want = self._grads(plain, q, k, v)
        got = self._grads(off, q, k, v)
        for g_w, g_g in zip(want, got):
            np.testing.assert_allclose(np.asarray(g_g), np.asarray(g_w),
                                       rtol=1e-4, atol=1e-4)

    def test_offload_train_step_through_model(self):
        """A model configured with attn_chunk_size + fpdt_offload trains
        (fwd+bwd+update) and matches the non-offload loss."""
        import deepspeed_tpu as dstpu
        from deepspeed_tpu.models import Transformer, TransformerConfig

        def build(offload):
            cfg = TransformerConfig(
                vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64, pos_emb="rope", norm="rmsnorm",
                activation="swiglu", dtype=jnp.float32, attn_impl="jnp",
                attn_chunk_size=16, fpdt_offload=offload)
            model = Transformer(cfg)
            return dstpu.initialize(model=model, config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 0})

        eng_off = build(True)
        gbs = eng_off.config.train_batch_size
        ids = np.random.RandomState(2).randint(0, 128,
                                               (gbs, 65)).astype(np.int32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        # monkeypatch-free: _supports_host_memory is True on cpu now
        l_off = float(eng_off.train_batch(batch)["loss"])
        l_plain = float(build(False).train_batch(batch)["loss"])
        assert abs(l_off - l_plain) < 1e-4, (l_off, l_plain)
