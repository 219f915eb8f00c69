"""The serving programs of the families the benchmark already runs lower to
the StableHLO they lowered to at PR 34 (commit 9e7457a), hash for hash: a
change made for one family's layer (PR 38: a second form of the latent
layer, a router read from a description, YaRN in the latent path, counters
whose number follows the configuration) must leave the others' programs as
they were.

`EXPECTED` was printed by `python tests/test_serving_programs_lowering.py`
run against the parent's tree.  A PR that changes one of these programs on
purpose prints them again and says so: PR 43 did for `longcat_flash` and
`smallthinker` (their tiny configurations hold every expert, so
`expert_ffn.moe` gathers the experts' outputs where it used to scatter-add
them); `qwen2`'s are PR 34's; `deepseek_v3`'s were printed at PR 46 from its
parent (73ee70b), when the state-space family learned layer kinds and
experts through `expert_ffn.moe`; `falcon_h1`'s and `granite_moe_hybrid`'s
were printed at PR 50 from its parent (e33567f), before the expert layer
moved to `expert_ffn` and the families behind `families.family_of`: that PR
printed none again.
"""
import hashlib

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.serving

B, MB, NB, BS = 4, 8, 16, 16

EXPECTED = {
    "longcat_flash": {"decode_step": "57c6d98cfdacca22",
                      "decode_tokens": "839f788a0baa8ced",
                      "prefill_chunks": "ea23da315e1910bb",
                      "prefill_chunks_tiled": "6523a172c34951db",
                      "prefill_full": "5873ff02dc4722cd"},
    "qwen2": {"decode_step": "5387aafd2fa5f207",
              "decode_tokens": "8a4f4792919bdae8",
              "prefill_chunks": "1fe435c08a0e8fe1",
              "prefill_full": "93898cc383c0a6ac"},
    "deepseek_v3": {"decode_step": "46a77386e9a7828f",
                    "decode_tokens": "965ab8993892b659",
                    "prefill_chunks": "c771819f6b89a8dc",
                    "prefill_chunks_tiled": "caf9a454d4ce164c",
                    "prefill_full": "86236bf825e860f2"},
    "smallthinker": {"decode_step": "bc5c37f8dcbb06aa",
                     "decode_tokens": "0b37c9ef7ff20e91",
                     "prefill_chunks": "63f68572ffa79331"},
    "falcon_h1": {"decode_step": "50d0df3c47db1bd4",
                  "prefill_chunks": "760f80f8a92b991e",
                  "prefill_full": "5219eb873856dea0"},
    "granite_moe_hybrid": {"decode_step": "9735574c647120f2",
                           "prefill_chunks": "63c173dcde8fb604",
                           "prefill_chunks_tiled": "30d108a1f408458a",
                           "prefill_full": "75789631d5811eb3"},
}


def program_hashes(family: str) -> dict:
    from deepspeed_tpu.inference.v2 import ragged_ops
    from deepspeed_tpu.models import Transformer, get_model_config
    cfg = get_model_config(family, "tiny", dtype=jnp.float32)
    params = jax.eval_shape(Transformer(cfg).init_params,
                            jax.random.PRNGKey(0))
    arena = jax.eval_shape(
        lambda: ragged_ops.init_arena(cfg, NB, BS, max_seqs=B))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    flags = jax.ShapeDtypeStruct((B,), jnp.bool_)
    # a static-kind stack's tables are [rows, 2, MB]
    tables = i32(B, 2, MB) if cfg.static_kinds else i32(B, MB)
    # per-sequence recurrent state: every program takes the rows' slots, and
    # the burst program, which hands none, does not serve the family
    slots = dict(slots=i32(B)) if cfg.ssm else {}
    calls = {
        "decode_step": (ragged_ops.decode_step,
                        (i32(B), i32(B), tables, flags), slots),
        "decode_tokens": (ragged_ops.decode_tokens,
                          (i32(B), i32(B), tables, flags,
                           jax.eval_shape(lambda: jax.random.PRNGKey(0))),
                          dict(n_steps=4)),
        "prefill_chunks": (ragged_ops.prefill_chunks,
                           (i32(B, 32), i32(B), i32(B), tables, flags),
                           slots),
    }
    if cfg.ssm:
        del calls["decode_tokens"]
    # more rows than a token-wise pass takes (`expert_ffn.rows`)
    if cfg.latent or (cfg.ssm and cfg.moe_experts > 1):
        calls["prefill_chunks_tiled"] = (
            ragged_ops.prefill_chunks,
            (i32(B, 512), i32(B), i32(B), tables, flags), slots)
    if ragged_ops.prefill_full_supported(cfg):
        calls["prefill_full"] = (ragged_ops.prefill_full,
                                 (i32(B, 128), i32(B), tables, flags), slots)
    # (the tests' own matmul precision, `tests/conftest.py`: it is part of
    # the text)
    with jax.default_matmul_precision("highest"):
        return {name: hashlib.sha256(
            fn.lower(cfg, params, arena, *args, **kw).as_text().encode()
        ).hexdigest()[:16] for name, (fn, args, kw) in calls.items()}


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_a_family_the_benchmark_runs_lowers_to_the_parents_programs(family):
    assert program_hashes(family) == EXPECTED[family]


if __name__ == "__main__":
    import pprint
    pprint.pprint({f: program_hashes(f) for f in sorted(EXPECTED)})
