"""hpZ / MiCS: the config knobs must DRIVE the dp×fsdp mesh split.

Reference semantics being tested:
- ZeRO++ hpZ (`zero_hpz_partition_size=k`, utils/groups.py:702
  _create_zero_param_parallel_group, zero/config.py:298): optimizer state
  (primary partition) spans the full world; the bf16 params (secondary
  partition) are sharded over only the fsdp sub-group of size k, so the
  per-use backward allgather stays intra-group.
- MiCS (`mics_shard_size=k`, runtime/zero/mics.py:64,362): params AND
  optimizer state shard within the size-k sub-group, replicate across
  groups; grads still sum over the replica (dp) axis.

These flags once parsed and silently no-oped.  These tests fail if that
regresses.
"""
import jax
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.config.config import ConfigError
from deepspeed_tpu.parallel.mesh import AXIS_DP, AXIS_FSDP

import jax.numpy as jnp


def _params():
    k = jax.random.PRNGKey(0)
    return {f"w{i}": jax.random.normal(jax.random.fold_in(k, i),
                                       (64, 64)) * 0.1
            for i in range(4)}


def _loss_fn(p, batch, rng=None):
    x = batch["x"]
    for i in range(4):
        x = jnp.tanh(x @ p[f"w{i}"])
    return jnp.mean((x - batch["y"]) ** 2)


def _engine(zero_extra, stage=3, bf16=False):
    zo = {"stage": stage}
    zo.update(zero_extra)
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": zo, "steps_per_print": 0}
    if bf16:
        cfg["bf16"] = {"enabled": True}
    return dstpu.initialize(loss_fn=_loss_fn, params=_params(), config=cfg)


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(16, 64).astype(np.float32),
            "y": rng.randn(16, 64).astype(np.float32)}


def _losses(eng, n=6):
    b = _batch()
    return [float(eng.train_batch(b)["loss"]) for _ in range(n)]


def _axes_of(arr):
    """Flat set of mesh axes appearing in an array's PartitionSpec."""
    spec = arr.sharding.spec
    out = set()
    for e in spec:
        if e is None:
            continue
        if isinstance(e, (tuple, list)):
            out.update(e)
        else:
            out.add(e)
    return out


# ---------------------------------------------------------------- hpZ ----
def test_hpz_builds_dp_by_fsdp_mesh(devices8):
    eng = _engine({"zero_hpz_partition_size": 2})
    assert eng.topology.fsdp_size == 2
    assert eng.topology.size(AXIS_DP) == 4
    assert eng.topology.dp_size == 8  # full data parallel preserved


def test_hpz_param_gather_domain_is_fsdp_opt_is_world(devices8):
    """Secondary partition: params sharded over fsdp ONLY (intra-group
    gathers); primary partition: master/opt state over dp×fsdp (1/world,
    stage-3 memory for the optimizer)."""
    eng = _engine({"zero_hpz_partition_size": 2}, bf16=True)
    for name, p in eng.state.params.items():
        assert _axes_of(p) == {AXIS_FSDP}, (name, p.sharding)
    for name, m in eng.state.master.items():
        assert _axes_of(m) == {AXIS_FSDP, AXIS_DP}, (name, m.sharding)
    for moment, tree in eng.state.opt_state.items():
        for name, leaf in tree.items():
            got = _axes_of(leaf)
            # quantized-moment scale leaves are replicated by design
            if not got:
                assert leaf.size <= 64 * 2, (moment, name, leaf.shape)
                continue
            assert got == {AXIS_FSDP, AXIS_DP}, (moment, name, leaf.sharding)


def test_hpz_param_layout_survives_steps(devices8):
    """Regression: in fp32 (no-master) mode the optimizer writes params
    directly; the updated params must keep the fsdp-only resident layout,
    not inherit the opt-state's dp×fsdp layout (which would silently widen
    every later gather to the full world)."""
    for bf16 in (False, True):
        eng = _engine({"zero_hpz_partition_size": 2}, bf16=bf16)
        eng.train_batch(_batch())
        eng.train_batch(_batch())
        for name, p in eng.state.params.items():
            assert _axes_of(p) == {AXIS_FSDP}, (bf16, name, p.sharding)


def test_hpz_loss_parity_with_plain_stage3(devices8):
    base = _losses(_engine({}))
    hpz = _losses(_engine({"zero_hpz_partition_size": 2}))
    np.testing.assert_allclose(hpz, base, rtol=2e-3, atol=1e-5)


def test_hpz_full_zeropp_triple_on_scan_model(devices8):
    """The complete ZeRO++ stack on a scan-over-layers Transformer:
    hpZ mesh split + qwZ/qgZ quantized collectives + the per-layer
    gather (layer_gather hook).  Must train; params stay fsdp-resident."""
    from deepspeed_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
        max_seq_len=32, pos_emb="rope", norm="rmsnorm",
        activation="swiglu", dtype=jnp.float32, attn_impl="jnp")
    eng = dstpu.initialize(model=Transformer(cfg), config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3, "zero_hpz_partition_size": 2,
                              "zero_quantized_weights": True,
                              "zero_quantized_gradients": True},
        "steps_per_print": 0})
    assert eng.topology.fsdp_size == 2 and eng.topology.size(AXIS_DP) == 4
    ids = np.random.RandomState(5).randint(
        0, 128, (eng.config.train_batch_size, 32)).astype(np.int32)
    losses = [float(eng.train_batch({"input_ids": ids})["loss"])
              for _ in range(8)]
    assert losses[-1] < losses[0] * 0.9, losses
    for name, p in eng.state.params.items():
        if name == "layers":
            for k, leaf in p.items():
                got = _axes_of(leaf)
                assert got <= {AXIS_FSDP}, (k, leaf.sharding)


def test_hpz_composes_with_qwz_qgz(devices8):
    """The full ZeRO++ triple: quantized gathers over the fsdp sub-group,
    quantized grad reduce-scatter refining to the dp×fsdp world."""
    base = _losses(_engine({}))
    triple = _losses(_engine({"zero_hpz_partition_size": 2,
                              "zero_quantized_weights": True,
                              "zero_quantized_gradients": True}))
    assert triple[-1] < triple[0] * 0.7, triple
    np.testing.assert_allclose(triple[-1], base[-1], rtol=0.15)


def test_hpz_composes_with_tensor_parallel(devices8):
    """hpZ's dp×fsdp split must coexist with a tp axis: mesh (2,2,..,2),
    TP rules win their dims, hpZ shards a remaining dim; trains."""
    from deepspeed_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=32, pos_emb="rope", norm="rmsnorm",
        activation="swiglu", dtype=jnp.float32, attn_impl="jnp")
    eng = dstpu.initialize(model=Transformer(cfg), config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3, "zero_hpz_partition_size": 2},
        "tensor_parallel": {"tp_size": 2},
        "steps_per_print": 0})
    assert eng.topology.fsdp_size == 2 and eng.topology.tp_size == 2
    ids = np.random.RandomState(0).randint(
        0, 128, (eng.config.train_batch_size, 32)).astype(np.int32)
    losses = [float(eng.train_batch({"input_ids": ids})["loss"])
              for _ in range(6)]
    assert losses[-1] < losses[0], losses


# --------------------------------------------------------------- MiCS ----
def test_mics_builds_dp_by_fsdp_mesh(devices8):
    eng = _engine({"mics_shard_size": 4})
    assert eng.topology.fsdp_size == 4
    assert eng.topology.size(AXIS_DP) == 2
    assert eng.topology.dp_size == 8


def test_mics_shards_within_subgroup_only(devices8):
    """Shard within the group, replicate across: every stateful leaf lives
    on the fsdp axis only — no dp-axis partitioning anywhere."""
    eng = _engine({"mics_shard_size": 4}, bf16=True)
    for tree in (eng.state.params, eng.state.master):
        for name, leaf in tree.items():
            assert _axes_of(leaf) == {AXIS_FSDP}, (name, leaf.sharding)
    for moment, tree in eng.state.opt_state.items():
        for name, leaf in tree.items():
            got = _axes_of(leaf)
            if not got:
                assert leaf.size <= 64 * 2, (moment, name, leaf.shape)
                continue
            assert got == {AXIS_FSDP}, (moment, name, leaf.sharding)


def test_mics_loss_parity_with_plain_stage3(devices8):
    base = _losses(_engine({}))
    mics = _losses(_engine({"mics_shard_size": 2}))
    np.testing.assert_allclose(mics, base, rtol=2e-3, atol=1e-5)


# ------------------------------------------------------- validation ----
def test_hpz_requires_stage3():
    with pytest.raises(ConfigError, match="stage 3"):
        _engine({"zero_hpz_partition_size": 2}, stage=2)


def test_mics_requires_stage3():
    with pytest.raises(ConfigError, match="stage 3"):
        _engine({"mics_shard_size": 2}, stage=1)


def test_hpz_invalid_partition_size(devices8):
    with pytest.raises(ConfigError, match="zero_hpz_partition_size"):
        _engine({"zero_hpz_partition_size": 3})  # 8 % 3 != 0


def test_mics_invalid_shard_size(devices8):
    with pytest.raises(ConfigError, match="mics_shard_size"):
        _engine({"mics_shard_size": 5})


def test_mics_shard_size_one_rejected():
    """k=1 is full replication (DDP), not MiCS — must error with the
    actionable alternative, not silently run world-wide stage 3."""
    with pytest.raises(ConfigError, match="stage 0"):
        _engine({"mics_shard_size": 1})


def test_hpz_and_mics_conflict():
    with pytest.raises(ConfigError, match="at most one"):
        _engine({"zero_hpz_partition_size": 2, "mics_shard_size": 2})


def test_explicit_topology_conflict(devices8):
    """A hand-built mesh that contradicts the knob must error, not
    silently win."""
    from deepspeed_tpu.parallel.mesh import make_mesh
    from deepspeed_tpu.config.config import DeepSpeedTPUConfig
    from deepspeed_tpu.runtime.engine import TrainEngine
    topo = make_mesh(fsdp=1)
    cfg = DeepSpeedTPUConfig.from_json({
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3, "zero_hpz_partition_size": 2}})
    with pytest.raises(ConfigError, match="fsdp"):
        TrainEngine(_loss_fn, _params(), cfg, topology=topo)
