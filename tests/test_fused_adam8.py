"""Fused 8-bit-Adam Pallas kernel parity vs the jnp int8 path
(runtime/optimizers._make_adam_int8).  Runs in interpret mode on the CPU
mesh; tests/test_tpu_compile.py compiles the kernel for the described
chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.fused_adam8 import fused_adam8_leaf, leaf_supported
from deepspeed_tpu.runtime.optimizers import (_dq8, _dq8_log, _q8_log,
                                              _q8_signed)

B1, B2, EPS, WD = 0.9, 0.999, 1e-8, 0.1


pytestmark = pytest.mark.kernels


def _jnp_leaf(g, m_q, m_s, v_q, v_s, p, lr, c1, c2):
    g = g.astype(jnp.float32)
    m_new = B1 * _dq8(m_q, m_s) + (1.0 - B1) * g
    v_new = B2 * _dq8_log(v_q, v_s) + (1.0 - B2) * (g * g)
    upd = (m_new / c1) / (jnp.sqrt(v_new / c2) + EPS) + WD * p
    p_new = p - lr * upd
    mq, ms = _q8_signed(m_new)
    vq, vs = _q8_log(v_new)
    return p_new, mq, ms, vq, vs


@pytest.mark.parametrize("shape", [(256, 256), (8, 32, 128), (384,), (3, 128)])
def test_fused_matches_jnp(shape):
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 3)
    p = jax.random.normal(ks[0], shape, jnp.float32) * 0.1
    g = (jax.random.normal(ks[1], shape, jnp.float32) * 1e-3).astype(jnp.bfloat16)
    # moments after one real quantized step (not all-zero state)
    m0 = jax.random.normal(ks[2], shape, jnp.float32) * 1e-3
    m_q, m_s = _q8_signed(m0)
    v_q, v_s = _q8_log(m0 * m0)
    c1, c2 = 1.0 - B1 ** 2, 1.0 - B2 ** 2

    assert leaf_supported(shape, jnp.float32)
    got = fused_adam8_leaf(g, m_q, m_s, v_q, v_s, p, 1e-3, 1.0, c1, c2,
                           b1=B1, b2=B2, eps=EPS, wd=WD, adam_w=True,
                           bias_correction=True, interpret=True)
    p_new, p_cast, mq, ms, vq, vs = got
    ref = _jnp_leaf(g, m_q, m_s, v_q, v_s, p, 1e-3, c1, c2)
    np.testing.assert_allclose(np.asarray(p_new), np.asarray(ref[0]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(p_cast),
                                  np.asarray(ref[0].astype(jnp.bfloat16)))
    # fp32 rounding ties may flip a code by 1 (observed ~1e-5 of elements)
    assert int(np.abs(np.asarray(mq, np.int32)
                      - np.asarray(ref[1], np.int32)).max()) <= 1
    np.testing.assert_allclose(np.asarray(ms).ravel(),
                               np.asarray(ref[2]).ravel(), rtol=1e-6)
    # log-codebook rounding at the clip boundary may differ by 1 code
    assert int(np.abs(np.asarray(vq, np.int32)
                      - np.asarray(ref[3], np.int32)).max()) <= 1
    np.testing.assert_allclose(np.asarray(vs).ravel(),
                               np.asarray(ref[4]).ravel(), rtol=1e-6)


def test_leaf_supported_gates():
    assert not leaf_supported((), jnp.float32)       # 0-d
    assert not leaf_supported((64, 100), jnp.float32)  # lanes
    assert not leaf_supported((64, 128), jnp.bfloat16)  # master dtype
    assert leaf_supported((64, 128), jnp.float32)


def test_gscale_folds_grad_scaling():
    shape = (16, 128)
    k = jax.random.PRNGKey(1)
    p = jax.random.normal(k, shape, jnp.float32) * 0.1
    g = jax.random.normal(jax.random.fold_in(k, 1), shape, jnp.float32)
    m_q, m_s = _q8_signed(jnp.zeros(shape))
    v_q, v_s = _q8_log(jnp.zeros(shape))
    a = fused_adam8_leaf(g * 0.25, m_q, m_s, v_q, v_s, p, 1e-3, 1.0,
                         1 - B1, 1 - B2, b1=B1, b2=B2, eps=EPS, wd=0.0,
                         adam_w=True, bias_correction=True, interpret=True)
    b = fused_adam8_leaf(g, m_q, m_s, v_q, v_s, p, 1e-3, 0.25,
                         1 - B1, 1 - B2, b1=B1, b2=B2, eps=EPS, wd=0.0,
                         adam_w=True, bias_correction=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               rtol=1e-6, atol=1e-7)
