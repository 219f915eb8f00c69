"""Tests: zero.Init / GatheredParameters / OnDevice / z3 leaf modules /
sparse row gradients (reference: tests/unit/runtime/zero/test_zero.py
TestZero3ParamPartitioningBase, tests for GatheredParameters and
init_on_device, tests/unit/runtime/sparse_tensor)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import Transformer, TransformerConfig
from deepspeed_tpu.parallel.mesh import make_mesh
from deepspeed_tpu.runtime import zero
from deepspeed_tpu.runtime.sparse_tensor import (
    SparseRows, sparse_lookup_vjp, allgather_sparse, to_dense, apply_rows)


def _cfg(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=64, dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


def test_zero_init_params_born_sharded(devices8):
    topo = make_mesh(fsdp=8, devices=devices8)
    model = Transformer(_cfg())
    with zero.Init(topo=topo, stage=3):
        params = model.init_params(jax.random.PRNGKey(0))
    # large 2D leaves must be fsdp-sharded at birth
    wq = params["layers"]["wq"]
    assert not wq.sharding.is_fully_replicated
    # and values must match the unsharded init exactly
    ref = Transformer(_cfg()).init_params(jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.array(wq), np.array(ref["layers"]["wq"]),
                               rtol=1e-6)
    # context exit restores the class method
    post = Transformer(_cfg()).init_params(jax.random.PRNGKey(0))
    assert post["layers"]["wq"].sharding.is_fully_replicated


def test_on_device_meta():
    model = Transformer(_cfg())
    with zero.OnDevice(dtype=jnp.bfloat16, device="meta"):
        shapes = model.init_params(jax.random.PRNGKey(0))
    leaf = shapes["layers"]["wq"]
    assert isinstance(leaf, jax.ShapeDtypeStruct)
    assert leaf.dtype == jnp.bfloat16
    # real init works again after exit
    real = model.init_params(jax.random.PRNGKey(0))
    assert isinstance(real["layers"]["wq"], jax.Array)


def test_gathered_parameters_roundtrip_engine():
    engine = dstpu.initialize(
        model=Transformer(_cfg()),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "steps_per_print": 0})
    with zero.GatheredParameters(engine) as full:
        assert isinstance(full["final_norm_scale"], np.ndarray)
        full["final_norm_scale"][...] = 7.0
    got = np.array(jax.device_get(engine.state.params["final_norm_scale"]))
    np.testing.assert_allclose(got, 7.0)
    if engine.state.master is not None:
        gm = np.array(jax.device_get(engine.state.master["final_norm_scale"]))
        np.testing.assert_allclose(gm, 7.0)


def test_z3_leaf_modules_stay_unsharded(devices8):
    model = Transformer(_cfg(moe_experts=2))
    zero.set_z3_leaf_modules(model, ["layers/moe_w_up", ("layers", "moe_w_down")])
    assert zero.get_z3_leaf_modules(model) == [
        ("layers", "moe_w_up"), ("layers", "moe_w_down")]
    engine = dstpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}, "steps_per_print": 0})
    def flat_axes(spec):
        out = set()
        for s in spec:
            if s is None:
                continue
            out.update(s if isinstance(s, tuple) else (s,))
        return out

    # leaf subtree: TP/EP sharding may remain, data axes must not appear
    spec = engine.rules.param_spec(("layers", "moe_w_up"), (2, 4, 64, 128))
    assert not flat_axes(spec) & {"dp", "fsdp"}
    # non-leaf large params still sharded
    spec2 = engine.rules.param_spec(("layers", "wq"), (2, 64, 64))
    assert any(s is not None for s in spec2)
    zero.unset_z3_leaf_modules(model)
    assert zero.get_z3_leaf_modules(model) == []


def test_sparse_rows_exactness():
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(32, 8), jnp.float32)
    ids = jnp.asarray(rng.randint(0, 32, (4, 6)), jnp.int32)
    out, pull = sparse_lookup_vjp(table, ids)
    np.testing.assert_allclose(np.array(out), np.array(table)[np.array(ids)])
    g = jnp.asarray(rng.randn(4, 6, 8), jnp.float32)
    rows = pull(g)
    assert rows.sparse_size() < rows.dense_size()
    # exactness vs autodiff dense gradient
    dense_ref = jax.grad(
        lambda t: jnp.vdot(jnp.take(t, ids, axis=0), g))(table)
    np.testing.assert_allclose(np.array(to_dense(rows)), np.array(dense_ref),
                               rtol=1e-6)
    # row-wise apply == dense apply
    upd = apply_rows(table, rows, -0.1)
    np.testing.assert_allclose(np.array(upd),
                               np.array(table) - 0.1 * np.array(dense_ref),
                               rtol=1e-6)


def test_sparse_allgather_matches_dense_allreduce(devices8):
    """Sparse DP reduction (gather rows, deferred sum) == dense psum."""
    from jax import shard_map
    mesh = Mesh(np.array(devices8), ("dp",))
    rng = np.random.RandomState(1)
    vocab, hidden = 16, 4
    ids = jnp.asarray(rng.randint(0, vocab, (8, 3)), jnp.int32)     # per-rank rows
    vals = jnp.asarray(rng.randn(8, 3, hidden), jnp.float32)

    def f(ids_l, vals_l):
        rows = SparseRows(ids_l.reshape(-1), vals_l.reshape(-1, hidden),
                          (vocab, hidden))
        return to_dense(allgather_sparse(rows, "dp"))

    sparse_sum = shard_map(
        f, mesh=mesh,
        in_specs=(PartitionSpec("dp"), PartitionSpec("dp")),
        out_specs=PartitionSpec(), check_vma=False)(ids, vals)
    dense_sum = np.zeros((vocab, hidden), np.float32)
    np.add.at(dense_sum, np.array(ids).reshape(-1),
              np.array(vals).reshape(-1, hidden))
    np.testing.assert_allclose(np.array(sparse_sum), dense_sum, rtol=1e-5)
