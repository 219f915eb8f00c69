"""Merged-arena fused kernels vs the dense reference (merged
[nb, bs, NKV*D] arenas previously fell back to the XLA gather path).
Interpret mode on the CPU mesh; tests/test_tpu_compile.py compiles the
decode kernel for the described chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.paged_attention import paged_decode_reference
from deepspeed_tpu.ops.paged_merged import (merged_decode_attention,
                                            merged_kernels_supported,
                                            merged_prefill_attention)
from deepspeed_tpu.ops.paged_prefill import paged_prefill_reference



pytestmark = pytest.mark.kernels


def _arena(key, L, nb, bs, NKV, D, dtype=jnp.float32, layered=True):
    shape = (L, nb, bs, NKV * D) if layered else (nb, bs, NKV * D)
    return jax.random.normal(key, shape, dtype) * 0.3


def _as5d(merged, NKV, D):
    return merged.reshape(merged.shape[:-1] + (NKV, D))


@pytest.mark.parametrize("NH,NKV,D", [(4, 4, 64), (4, 2, 64), (2, 2, 128),
                                      (4, 2, 256)])
def test_merged_decode_parity(NH, NKV, D):
    assert merged_kernels_supported(NH, NKV, D)
    B, nb, bs, MB = 3, 16, 8, 4
    k = jax.random.PRNGKey(0)
    ak = _arena(k, 1, nb, bs, NKV, D, layered=False)
    av = _arena(jax.random.fold_in(k, 1), 1, nb, bs, NKV, D, layered=False)
    q = jax.random.normal(jax.random.fold_in(k, 2), (B, NH, D), jnp.float32)
    tables = jax.random.randint(jax.random.fold_in(k, 3), (B, MB), 0, nb)
    lens = jnp.asarray([5, 17, -1], jnp.int32)  # incl. inactive row

    got = merged_decode_attention(q, ak, av, tables, lens,
                                  interpret=True)
    ref = paged_decode_reference(q, _as5d(ak, NKV, D), _as5d(av, NKV, D),
                                 tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_merged_decode_layered():
    NH, NKV, D = 4, 2, 64
    B, L, nb, bs, MB = 2, 3, 16, 8, 4
    k = jax.random.PRNGKey(1)
    ak = _arena(k, L, nb, bs, NKV, D)
    av = _arena(jax.random.fold_in(k, 1), L, nb, bs, NKV, D)
    q = jax.random.normal(jax.random.fold_in(k, 2), (B, NH, D), jnp.float32)
    tables = jax.random.randint(jax.random.fold_in(k, 3), (B, MB), 0, nb)
    lens = jnp.asarray([9, 30], jnp.int32)
    for li in (0, 2):
        got = merged_decode_attention(q, ak, av, tables, lens,
                                      layer_idx=li, interpret=True)
        ref = paged_decode_reference(q, _as5d(ak[li], NKV, D),
                                     _as5d(av[li], NKV, D), tables, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("NH,NKV,D", [(4, 4, 64), (4, 2, 64), (2, 2, 128),
                                      (4, 2, 128)])
@pytest.mark.parametrize("window", [None, 12])
def test_merged_prefill_parity(NH, NKV, D, window):
    C, nb, bs, MB = 16, 16, 8, 6
    k = jax.random.PRNGKey(2)
    ak = _arena(k, 1, nb, bs, NKV, D, layered=False)
    av = _arena(jax.random.fold_in(k, 1), 1, nb, bs, NKV, D, layered=False)
    q = jax.random.normal(jax.random.fold_in(k, 2), (C, NH, D), jnp.float32)
    table = jax.random.randint(jax.random.fold_in(k, 3), (MB,), 0, nb)
    pos0, n_valid = 21, 11

    got = merged_prefill_attention(q, ak, av, table, pos0, n_valid,
                                   sliding_window=window, interpret=True)
    ref = paged_prefill_reference(q, _as5d(ak, NKV, D), _as5d(av, NKV, D),
                                  table, pos0, n_valid,
                                  sliding_window=window)
    # padded queries (c >= n_valid) are don't-care: engine discards them
    np.testing.assert_allclose(np.asarray(got)[:n_valid],
                               np.asarray(ref)[:n_valid],
                               rtol=2e-5, atol=2e-5)


def test_merged_prefill_layered():
    NH, NKV, D = 4, 2, 64
    C, L, nb, bs, MB = 16, 3, 16, 8, 6
    k = jax.random.PRNGKey(3)
    ak = _arena(k, L, nb, bs, NKV, D)
    av = _arena(jax.random.fold_in(k, 1), L, nb, bs, NKV, D)
    q = jax.random.normal(jax.random.fold_in(k, 2), (C, NH, D), jnp.float32)
    table = jax.random.randint(jax.random.fold_in(k, 3), (MB,), 0, nb)
    got = merged_prefill_attention(q, ak, av, table, 5, 16, layer_idx=1,
                                   interpret=True)
    ref = paged_prefill_reference(q, _as5d(ak[1], NKV, D),
                                  _as5d(av[1], NKV, D), table, 5, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_supported_gates():
    assert merged_kernels_supported(4, 2, 64)
    assert merged_kernels_supported(8, 8, 128)
    assert merged_kernels_supported(4, 4, 256)      # decode packs whole minor
    assert not merged_kernels_supported(4, 3, 64)   # NKV % hpb
    assert not merged_kernels_supported(4, 4, 96)   # lanes
    # prefill stripes must see a head's FULL D dims: D > 128 would
    # softmax partial logits per sub-stripe
    assert merged_kernels_supported(4, 2, 128, op="prefill")
    assert not merged_kernels_supported(4, 4, 256, op="prefill")


def test_prefill_rejects_d_over_128():
    NH, NKV, D = 4, 4, 256
    k = jax.random.PRNGKey(4)
    ak = _arena(k, 1, 8, 8, NKV, D, layered=False)
    q = jax.random.normal(k, (16, NH, D), jnp.float32)
    with pytest.raises(ValueError, match="head_dim <= 128"):
        merged_prefill_attention(q, ak, ak, jnp.zeros(4, jnp.int32), 0, 8,
                                 interpret=True)
