"""Full-range fused paged attention: small-KV-budget parity (ISSUE 10).

The 2048-key auto-gate is gone — every budget rides the fused kernels —
so this module locks the newly-covered corner of the shape space in
interpreter mode (the same code path the TPU compiles):

- decode over tiny arenas: degenerate single-k-block tables (MB=1),
  two-block walks, the minimal bs=8 block, GQA + MHA + odd NKV, f32
  and bf16;
- blocked-flash prefill for sub-8 and non-tile-divisible chunks (the
  speculative verify-span shapes S=2/4 and odd chunk tails), which pad
  up to the 8-row query tile via `prefill_plan` and slice the pad off;
- the merged-arena variants of both;
- end-to-end kernel-vs-dense agreement on a tiny engine: the greedy
  decode chain's token ids are identical between the fused path and the
  attn_impl="jnp" dense escape hatch (f32), and a sub-8 verify span
  emits identical tokens/counts through `verify_tokens` on both arms.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import paged_merged as pm
from deepspeed_tpu.ops import paged_prefill as pp


pytestmark = pytest.mark.kernels


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    import jax.experimental.pallas as pl
    orig = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(orig, interpret=True))
    yield


@pytest.fixture
def _fake_tpu(monkeypatch):
    """Flip the platform gate so the serving programs trace the fused
    kernels (which then run in interpreter mode on this CPU suite)."""
    import deepspeed_tpu.utils.device as device_mod
    monkeypatch.setattr(device_mod, "platform", lambda: "tpu")
    yield


# -- decode: tiny arenas ---------------------------------------------------

@pytest.mark.parametrize("B,MB,bs,NH,NKV,dtype,tol", [
    (3, 1, 8, 8, 2, jnp.float32, 2e-5),     # single-k-block, GQA
    (2, 1, 16, 4, 4, jnp.float32, 2e-5),    # single-k-block, MHA
    (3, 2, 8, 6, 3, jnp.float32, 2e-5),     # two-block walk, odd NKV
    (4, 2, 8, 8, 2, jnp.bfloat16, 3e-2),    # bf16 tolerance
])
def test_decode_tiny_arena_matches_reference(B, MB, bs, NH, NKV, dtype, tol):
    rng = np.random.RandomState(7)
    nb, D = 4, 64
    q = jnp.asarray(rng.randn(B, NH, D), dtype)
    ak = jnp.asarray(rng.randn(nb, bs, NKV, D), dtype)
    av = jnp.asarray(rng.randn(nb, bs, NKV, D), dtype)
    tables = jnp.asarray(rng.randint(0, nb, (B, MB)), jnp.int32)
    lens = jnp.asarray(rng.randint(0, MB * bs, B), jnp.int32)
    ref = pa.paged_decode_reference(q, ak, av, tables, lens)
    got = pa.paged_decode_attention(q, ak, av, tables, lens)
    np.testing.assert_allclose(np.asarray(got).astype(np.float32),
                               np.asarray(ref).astype(np.float32),
                               rtol=tol, atol=tol)
    # merged-arena packed-q variant over the same tiny table
    gotm = pm.merged_decode_attention(
        q, ak.reshape(nb, bs, NKV * D), av.reshape(nb, bs, NKV * D),
        tables, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(gotm).astype(np.float32),
                               np.asarray(ref).astype(np.float32),
                               rtol=tol, atol=tol)


def test_decode_single_block_len_boundaries():
    """MB=1: len=0 (one key), len=bs-1 (full block) and len<0 (inactive
    row -> zeros) all hit init/compute/finish in the SAME grid step."""
    rng = np.random.RandomState(8)
    nb, bs, NH, NKV, D = 3, 8, 4, 2, 64
    q = jnp.asarray(rng.randn(3, NH, D), jnp.float32)
    ak = jnp.asarray(rng.randn(nb, bs, NKV, D), jnp.float32)
    av = jnp.asarray(rng.randn(nb, bs, NKV, D), jnp.float32)
    tables = jnp.asarray(rng.randint(0, nb, (3, 1)), jnp.int32)
    lens = jnp.asarray([0, -1, bs - 1], jnp.int32)
    ref = pa.paged_decode_reference(q, ak, av, tables, lens)
    got = pa.paged_decode_attention(q, ak, av, tables, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert np.allclose(np.asarray(got[1]), 0.0)


# -- prefill: sub-8 and odd chunks (the pad path) --------------------------

def _prefill_case(C, NH=8, NKV=2, D=64, nb=16, bs=8, MB=8, seed=0,
                  dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(C, NH, D), dtype)
    ak = jnp.asarray(rng.randn(nb, bs, NKV, D), dtype)
    av = jnp.asarray(rng.randn(nb, bs, NKV, D), dtype)
    table = jnp.asarray(rng.permutation(nb)[:MB], jnp.int32)
    return q, ak, av, table


@pytest.mark.parametrize("C,nv,pos0", [
    (2, 2, 16),      # minimal verify span mid-sequence
    (4, 4, 0),       # spec span bucket, fresh sequence
    (12, 11, 24),    # odd chunk with a padded query row
    (20, 20, 3),     # non-power-of-2, unaligned pos0
])
def test_prefill_padded_chunk_matches_reference(C, nv, pos0):
    q, ak, av, table = _prefill_case(C)
    ref = pp.paged_prefill_reference(q, ak, av, table, pos0, nv)
    got = pp.paged_prefill_attention(q, ak, av, table, pos0, nv)
    assert got.shape == (C, q.shape[1], q.shape[2])
    np.testing.assert_allclose(np.asarray(got[:nv]), np.asarray(ref[:nv]),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # merged-arena stripe-grid variant, same pad path
    nb, bs, NKV, D = ak.shape
    gotm = pm.merged_prefill_attention(
        q, ak.reshape(nb, bs, NKV * D), av.reshape(nb, bs, NKV * D),
        table, pos0, nv, interpret=True)
    assert gotm.shape == got.shape
    np.testing.assert_allclose(np.asarray(gotm[:nv]), np.asarray(ref[:nv]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("NH,NKV", [(4, 4), (6, 3)])
def test_prefill_small_chunk_mha_and_odd_nkv(NH, NKV):
    q, ak, av, table = _prefill_case(4, NH=NH, NKV=NKV, seed=3)
    ref = pp.paged_prefill_reference(q, ak, av, table, 10, 4)
    got = pp.paged_prefill_attention(q, ak, av, table, 10, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_prefill_small_chunk_bf16_tolerance():
    q, ak, av, table = _prefill_case(4, seed=4, dtype=jnp.bfloat16)
    ref = pp.paged_prefill_reference(q, ak, av, table, 12, 4)
    got = pp.paged_prefill_attention(q, ak, av, table, 12, 4)
    np.testing.assert_allclose(np.asarray(got).astype(np.float32),
                               np.asarray(ref).astype(np.float32),
                               rtol=3e-2, atol=3e-2)


def test_prefill_small_chunk_sliding_window():
    q, ak, av, table = _prefill_case(4, seed=5)
    ref = pp.paged_prefill_reference(q, ak, av, table, 30, 4,
                                     sliding_window=8)
    got = pp.paged_prefill_attention(q, ak, av, table, 30, 4,
                                     sliding_window=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_prefill_plan_pads_to_sublane_tile():
    """The plan serves EVERY chunk size: exact tiles stay exact, the
    rest pad to the next multiple of 8; only a VMEM-overflow geometry
    returns None."""
    assert pp.prefill_plan(256, 8, 64, 8) == (256, 128)
    assert pp.prefill_plan(8, 8, 64, 8) == (8, 8)
    for C, Cp in [(1, 8), (2, 8), (4, 8), (12, 16), (100, 104)]:
        got = pp.prefill_plan(C, 8, 64, 8)
        assert got is not None and got[0] == Cp and got[0] % got[1] == 0
    # a head count whose minimal 8-row tile overflows the VMEM budget
    assert pp.prefill_plan(8, 4096, 128, 256) is None


# -- end-to-end: kernel arm vs the dense escape hatch ----------------------

def _twin(attn_impl):
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(vocab_size=131, hidden_size=256, num_layers=2,
                            num_heads=4, max_seq_len=192,
                            dtype=jnp.float32, attn_impl=attn_impl)
    model = Transformer(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                num_blocks=16, block_size=8,
                                max_blocks_per_seq=8, max_seqs=2,
                                prefill_chunk_size=16, decode_burst=4,
                                full_prompt_prefill=False))
    return eng, cfg


def test_greedy_decode_chain_kernel_matches_dense(_fake_tpu):
    """A 64-key budget (16 blocks x 8 x 2 seqs) through chunked prefill
    + greedy bursts: the fused-kernel arm's token ids must equal the
    attn_impl='jnp' dense arm's, end to end (f32)."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 131, n).astype(np.int32) for n in (21, 13)]
    outs = {}
    for impl in ("auto", "jnp"):
        eng, _ = _twin(impl)
        outs[impl] = eng.generate_batch(prompts, max_new_tokens=8)
        eng.audit_blocks()
    assert [list(o) for o in outs["auto"]] == \
        [list(o) for o in outs["jnp"]]


def test_verify_span_kernel_matches_dense(_fake_tpu):
    """A sub-8 verify span (S=4 — always the gather path before this
    PR) through `verify_tokens`: the padded blocked-prefill kernel arm
    emits the same tokens and counts as the dense arm."""
    from deepspeed_tpu.inference.v2.ragged_ops import verify_tokens
    results = {}
    for impl in ("auto", "jnp"):
        rng = np.random.RandomState(12)           # identical per arm
        prompts = [rng.randint(0, 131, n).astype(np.int32) for n in (17, 9)]
        tokens = jnp.asarray(rng.randint(0, 131, (2, 4)), jnp.int32)
        eng, cfg = _twin(impl)
        out = eng.put([0, 1], prompts)
        while len(out) < 2:
            out.update(eng.step())
        tables = jnp.asarray(np.stack(
            [eng.state.block_table(eng.state.seqs[u]) for u in (0, 1)]))
        emitted, n_emitted, _ = verify_tokens(
            cfg, eng.params, eng.arena, tokens,
            jnp.asarray([len(p) for p in prompts], jnp.int32),
            jnp.asarray([4, 3], jnp.int32), tables,
            jnp.ones(2, bool), jax.random.PRNGKey(0), mode="greedy")
        results[impl] = (np.asarray(emitted), np.asarray(n_emitted))
    np.testing.assert_array_equal(results["auto"][0], results["jnp"][0])
    np.testing.assert_array_equal(results["auto"][1], results["jnp"][1])
