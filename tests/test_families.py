"""`families.family_of(cfg)`: every architecture the registry lists is exactly
one of four families with a whole record, and what cannot serve a family
refuses with THAT family's reason, the same list for all three but for what
one of them serves (burst and multi-step decode hand no row -> slot vector:
the latent block and the static-kind stack need none).

The families' own files (`tests/test_{latent,latent_single,hybrid,ssm,
granite}_serving.py`) hold their refusals one by one, by a word of the
message; here the matrix is whole and the reason is the family's.
"""
import functools

import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig,
                                        build_engine, check_serving_moe,
                                        ragged_ops)
from deepspeed_tpu.inference.v2.families import family_of
from deepspeed_tpu.inference.v2.model_registry import (ARCH_REGISTRY,
                                                       arch_config)
from deepspeed_tpu.models import Transformer

pytestmark = pytest.mark.serving

# one served architecture a family (granite: the state-space one with experts)
ARCH = {"latent": "longcat_flash", "kinds": "smallthinker",
        "ssm": "granite_moe_hybrid"}


@pytest.mark.parametrize("arch", sorted(ARCH_REGISTRY))
def test_an_architecture_is_one_family_with_a_whole_record(arch):
    cfg = arch_config(arch, "tiny")
    fam = family_of(cfg)
    flags = {"ssm": cfg.ssm, "kinds": cfg.static_kinds, "latent": cfg.latent}
    assert sum(flags.values()) <= 1
    assert fam.name == next((n for n, on in flags.items() if on), "uniform")
    for fn in (fam.init_arena, fam.prefill_chunks, fam.decode_core,
               fam.pools, fam.chunk_account, fam.step_account, fam.audit):
        assert callable(fn)
    # fresh prompts: the static-kind stack alone has no program of its own
    assert (fam.prefill_full is None) == (fam.name == "kinds")
    assert ragged_ops.prefill_full_supported(cfg) or fam.name in (
        "kinds", "uniform")
    uniform = fam.name == "uniform"
    assert (fam.reason is None) == uniform == fam.shards == fam.lora \
        == (fam.span_core is not None)
    assert fam.row_slots == (fam.name == "ssm")
    assert fam.refuse("anything") is None if uniform else fam.reason


def test_the_families_reasons_are_their_own():
    reasons = {family_of(arch_config(a, "tiny")).reason
               for a in ARCH.values()}
    assert len(reasons) == 3 and None not in reasons


@functools.lru_cache(maxsize=None)
def engine(family: str) -> InferenceEngineV2:
    return build_engine(ARCH[family], "tiny", dtype=jnp.float32,
                        engine_config=RaggedInferenceEngineConfig(
                            num_blocks=16, block_size=8, max_seqs=2))


def _tensor_parallel(eng):
    return InferenceEngineV2(
        Transformer(eng.cfg), params=eng.params,
        config=RaggedInferenceEngineConfig(tensor_parallel_size=2))


# what -> (the families that serve it, the error, the call on a tiny engine)
REFUSED = {
    "prefix_cache": ((), NotImplementedError,
                     lambda eng: eng.enable_prefix_cache(4)),
    "page_export": ((), NotImplementedError,
                    lambda eng: eng.read_kv_block(0)),
    "page_import": ((), NotImplementedError,
                    lambda eng: eng.write_kv_blocks([0], None, None)),
    "lora": ((), NotImplementedError,
             lambda eng: eng.attach_lora({"a": None, "b": None})),
    "lora_operands": ((), NotImplementedError,
                      lambda eng: ragged_ops._decode_core(
                          eng.cfg, *[None] * 6, lora={})),
    "burst": (("latent", "kinds"), NotImplementedError,
              lambda eng: eng.decode_burst_step()),
    "multi_step": (("latent", "kinds"), NotImplementedError,
                   lambda eng: eng.decode_multi_step(k=4)),
    "draft_verify": ((), NotImplementedError,
                     lambda eng: ragged_ops._span_core(eng.cfg, *[None] * 7)),
    "expert_paging": ((), ValueError, lambda eng: check_serving_moe(
        eng.cfg, ds.ServingConfig.from_dict({"moe": {"enabled": True}}))),
    "census_arena": ((), ValueError, lambda eng: ragged_ops.init_arena(
        eng.cfg, 4, 8, moe_census=True)),
    "tensor_parallel": ((), None, _tensor_parallel),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
@pytest.mark.parametrize("family", sorted(ARCH))
def test_a_family_refuses_the_list_with_its_own_reason(family, what):
    serves, error, call = REFUSED[what]
    eng = engine(family)
    if family in serves:
        assert call(eng) == {}     # no decode row yet: nothing to advance
        return
    with pytest.raises(error or eng.family.tp_error) as refused:
        call(eng)
    assert eng.family.reason in str(refused.value)
    assert not (eng.supports_lora or eng.supports_draft_verify
                or eng.supports_moe)
