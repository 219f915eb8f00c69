"""Tests: Evoformer pair-bias attention (reference:
tests/unit/ops/deepspeed4science/test_DS4Sci_EvoformerAttention.py —
numeric match vs a plain torch attention with broadcast biases, fwd+bwd)."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.evoformer import (
    evoformer_attention, DS4Sci_EvoformerAttention)

B, N, L, H, D = 2, 3, 32, 4, 8


pytestmark = pytest.mark.kernels


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.5, jnp.float32)
    q, k, v = mk(B, N, L, H, D), mk(B, N, L, H, D), mk(B, N, L, H, D)
    bias1 = mk(B, N, 1, 1, L)     # key mask bias
    bias2 = mk(B, 1, H, L, L)     # pair bias
    return q, k, v, bias1, bias2


def _reference(q, k, v, b1=None, b2=None):
    s = np.einsum("bnqhd,bnkhd->bnhqk", np.array(q, np.float64),
                  np.array(k, np.float64)) / math.sqrt(D)
    if b1 is not None:
        s = s + np.array(b1, np.float64)
    if b2 is not None:
        s = s + np.array(b2, np.float64)
    e = np.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return np.einsum("bnhqk,bnkhd->bnqhd", p, np.array(v, np.float64))


@pytest.mark.parametrize("use_b1,use_b2", [(False, False), (True, False),
                                           (False, True), (True, True)])
def test_matches_reference(use_b1, use_b2):
    q, k, v, b1, b2 = _inputs()
    biases = []
    if use_b1:
        biases.append(b1)
    if use_b2:
        biases.append(b2)
    got = DS4Sci_EvoformerAttention(q, k, v, biases)
    want = _reference(q, k, v, b1 if use_b1 else None, b2 if use_b2 else None)
    np.testing.assert_allclose(np.array(got), want, atol=1e-5)


def test_chunked_matches_unchunked():
    q, k, v, b1, b2 = _inputs(1)
    full = evoformer_attention(q, k, v, [b1, b2], chunk_size=L)
    chunked = evoformer_attention(q, k, v, [b1, b2], chunk_size=8)
    np.testing.assert_allclose(np.array(full), np.array(chunked), atol=1e-5)


def test_bias_order_free():
    q, k, v, b1, b2 = _inputs(2)
    a = evoformer_attention(q, k, v, [b1, b2])
    b = evoformer_attention(q, k, v, [b2, b1])
    np.testing.assert_allclose(np.array(a), np.array(b))


def test_bad_bias_shape_raises():
    q, k, v, b1, b2 = _inputs()
    with pytest.raises(ValueError):
        evoformer_attention(q, k, v, [jnp.zeros((B, N, L))])


def test_gradients_including_biases():
    q, k, v, b1, b2 = _inputs(3)

    def loss(q, b1, b2, chunk):
        return jnp.sum(evoformer_attention(q, k, v, [b1, b2],
                                           chunk_size=chunk) ** 2)

    g_full = jax.grad(loss, argnums=(0, 1, 2))(q, b1, b2, L)
    g_chun = jax.grad(loss, argnums=(0, 1, 2))(q, b1, b2, 8)
    for gf, gc in zip(g_full, g_chun):
        assert bool(jnp.isfinite(gf).all())
        np.testing.assert_allclose(np.array(gf), np.array(gc), atol=2e-4)
    # pair-bias grad nonzero (the reference exposes is_b2_grad path)
    assert float(jnp.abs(g_full[2]).max()) > 0


class TestEvoformerFlashKernel:
    """Pallas forward kernel vs the chunked-jnp path (interpreter mode; the
    same code path the TPU compiles)."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        import functools
        import jax.experimental.pallas as pl
        import deepspeed_tpu.utils.device as device_mod
        monkeypatch.setattr(pl, "pallas_call",
                            functools.partial(pl.pallas_call,
                                              interpret=True))
        monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
        yield

    def _qkv(self, B=1, N=3, L=256, H=2, D=64, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
        return (mk(B, N, L, H, D), mk(B, N, L, H, D), mk(B, N, L, H, D),
                jnp.asarray(rng.randn(B, N, 1, 1, L) * 2, jnp.float32),
                mk(B, 1, H, L, L))

    @pytest.mark.parametrize("which", ["none", "b1", "b2", "both"])
    def test_matches_jnp_path(self, which):
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        q, k, v, b1, b2 = self._qkv()
        biases = {"none": (), "b1": (b1,), "b2": (b2,),
                  "both": (b1, b2)}[which]
        got = evoformer_attention(q, k, v, biases)        # kernel (auto)
        ref = evoformer_attention(q, k, v, biases, impl="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_flow_through_kernel_path(self):
        """custom_vjp: bias gradients (the learned pair bias!) must match
        the jnp path's."""
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        q, k, v, b1, b2 = self._qkv(L=128)

        def loss(impl, q_, b2_):
            return jnp.sum(
                evoformer_attention(q_, k, v, (b1, b2_), impl=impl) ** 2)
        ga = jax.grad(lambda q_, b_: loss("auto", q_, b_),
                      argnums=(0, 1))(q, b2)
        gj = jax.grad(lambda q_, b_: loss("jnp", q_, b_),
                      argnums=(0, 1))(q, b2)
        for a, b in zip(ga, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("which", ["none", "b1", "b2", "both"])
    def test_fused_backward_kernels_match_jnp(self, which):
        """The flash backward kernels (dq/dkv/db1/db2, evoformer_flash.py)
        vs the chunked-jnp autodiff — every cotangent including both
        biases, with a partially masked b1."""
        import deepspeed_tpu.ops.evoformer as evo
        B, N, L, H, D = 1, 3, 64, 2, 32
        rng = np.random.RandomState(5)
        mk = lambda *s: jnp.asarray(rng.randn(*s) * 0.3, jnp.float32)
        q, k, v = mk(B, N, L, H, D), mk(B, N, L, H, D), mk(B, N, L, H, D)
        b1 = jnp.asarray(
            np.where(rng.rand(B, N, 1, 1, L) > 0.2, 0.0, -1e9), jnp.float32)
        b2 = mk(B, 1, H, L, L)
        bb1 = b1 if which in ("b1", "both") else None
        bb2 = b2 if which in ("b2", "both") else None
        an = tuple(i for i, t in enumerate(
            (q, k, v, bb1, bb2)) if t is not None)

        gk = jax.grad(lambda *a: jnp.sum(
            evo._evo_kernel_diff(*a, 128) ** 2), argnums=an)(q, k, v,
                                                             bb1, bb2)
        gj = jax.grad(lambda *a: jnp.sum(
            evo._evoformer_jnp(*a, 128) ** 2), argnums=an)(q, k, v,
                                                           bb1, bb2)
        for a, b in zip(gk, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_auto_gate_covers_d32(self):
        """Measured r3: the HYBRID (XLA fwd + Pallas bwd) wins at both
        D=32 and D=64 — auto enables it everywhere capable, including the
        AlphaFold head size the round-2 gate excluded."""
        from deepspeed_tpu.ops.evoformer import _use_evo_kernel
        assert _use_evo_kernel("auto", 256, 64) is True
        assert _use_evo_kernel("auto", 256, 32) is True
        assert _use_evo_kernel("pallas", 256, 32) is True  # forced: capable
        assert _use_evo_kernel("jnp", 256, 64) is False

    def test_fully_masked_row_zero_output_finite_grads(self):
        """A -1e30 mask bias over every key of one MSA row: both paths
        output zeros there and gradients stay finite (regression: the
        division vjp underflowed eps**2 to 0 -> NaN; and the kernel/jnp
        paths used different fully-masked conventions)."""
        from deepspeed_tpu.ops.evoformer import evoformer_attention
        q, k, v, _, _ = self._qkv(N=2)
        b1 = jnp.zeros((1, 2, 1, 1, 256), jnp.float32).at[0, 0].set(-1e30)
        for impl in ("auto", "jnp"):
            out = evoformer_attention(q, k, v, (b1,), impl=impl)
            assert float(jnp.max(jnp.abs(out[0, 0]))) == 0.0
            g = jax.grad(lambda q_: jnp.sum(
                evoformer_attention(q_, k, v, (b1,), impl=impl) ** 2))(q)
            assert np.isfinite(np.asarray(g)).all()
