"""Mesh topology + collective facade tests (reference analog:
tests/unit/comm/test_dist.py over the spawned process group)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec
from jax import shard_map

import deepspeed_tpu.comm as dist
from deepspeed_tpu.parallel.mesh import (
    AXIS_DP, AXIS_TP, AXIS_SP, make_mesh,
)


def test_make_mesh_infers_dp(devices8):
    topo = make_mesh(tp=2)
    assert topo.dp_size == 4
    assert topo.tp_size == 2
    assert topo.world_size == 8


def test_make_mesh_bad_sizes(devices8):
    with pytest.raises(ValueError):
        make_mesh(tp=3)


def test_sharding_helpers(devices8):
    topo = make_mesh(tp=2)
    s = topo.sharding(AXIS_DP, None)
    assert s.spec == PartitionSpec(AXIS_DP, None)
    x = jnp.arange(16.0).reshape(8, 2)
    xs = jax.device_put(x, topo.sharding(AXIS_DP))
    np.testing.assert_allclose(np.asarray(xs), np.asarray(x))


def _shmap(topo, fn, in_specs, out_specs):
    return shard_map(fn, mesh=topo.mesh, in_specs=in_specs, out_specs=out_specs)


def test_all_reduce_sum(devices8):
    topo = make_mesh()
    x = jnp.arange(8.0)

    f = _shmap(topo, lambda x: dist.all_reduce(x, AXIS_DP),
               (PartitionSpec(AXIS_DP),), PartitionSpec(AXIS_DP))
    out = f(x)
    # each shard becomes the global sum of its slice position -> all equal sum
    np.testing.assert_allclose(np.asarray(out), np.full((8,), x.sum()))


def test_all_reduce_avg_max_min(devices8):
    topo = make_mesh()
    x = jnp.arange(8.0)
    for op, expect in [(dist.ReduceOp.AVG, x.mean()),
                       (dist.ReduceOp.MAX, x.max()),
                       (dist.ReduceOp.MIN, x.min())]:
        f = _shmap(topo, lambda x, op=op: dist.all_reduce(x, AXIS_DP, op=op),
                   (PartitionSpec(AXIS_DP),), PartitionSpec(AXIS_DP))
        np.testing.assert_allclose(np.asarray(f(x)), np.full((8,), expect))


def test_all_gather(devices8):
    topo = make_mesh()
    x = jnp.arange(8.0)
    # every shard gathers the full vector; with out_spec P(dp) the global
    # result is the vector tiled once per rank
    f = _shmap(topo, lambda x: dist.all_gather(x, AXIS_DP),
               (PartitionSpec(AXIS_DP),), PartitionSpec(AXIS_DP))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.tile(np.arange(8.0), 8))


def test_reduce_scatter(devices8):
    topo = make_mesh()
    # each rank holds the full vector; psum_scatter returns 8x its shard
    x = jnp.tile(jnp.arange(8.0), (8, 1))  # [8 ranks, 8]

    f = _shmap(topo, lambda x: dist.reduce_scatter(x[0], AXIS_DP),
               (PartitionSpec(AXIS_DP, None),), PartitionSpec(AXIS_DP))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 8)


def test_all_to_all(devices8):
    topo = make_mesh()
    x = jnp.arange(64.0).reshape(8, 8)  # rank r holds row r ([1, 8] locally)

    # split the local free dim across ranks, concat on the sharded dim:
    # rank r ends with column r ([8, 1] locally) -> global [64, 1] = x.T flat
    f = _shmap(topo, lambda x: dist.all_to_all(x, AXIS_DP, split_axis=1, concat_axis=0),
               (PartitionSpec(AXIS_DP, None),), PartitionSpec(AXIS_DP, None))
    out = np.asarray(f(x))
    ref = np.arange(64.0).reshape(8, 8).T.reshape(64, 1)
    np.testing.assert_allclose(out, ref)


def test_broadcast(devices8):
    topo = make_mesh()
    x = jnp.arange(8.0)
    f = _shmap(topo, lambda x: dist.broadcast(x, AXIS_DP, src=3),
               (PartitionSpec(AXIS_DP),), PartitionSpec(AXIS_DP))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.full((8,), 3.0))


def test_ppermute_ring(devices8):
    topo = make_mesh()
    x = jnp.arange(8.0)
    f = _shmap(topo, lambda x: dist.send_recv_next(x, AXIS_DP, 8),
               (PartitionSpec(AXIS_DP),), PartitionSpec(AXIS_DP))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_comms_logger_records(devices8):
    topo = make_mesh()
    dist.configure(enabled=True, verbose=False)
    try:
        x = jnp.arange(8.0)
        f = _shmap(topo, lambda x: dist.all_reduce(x, AXIS_DP),
                   (PartitionSpec(AXIS_DP),), PartitionSpec(AXIS_DP))
        f(x)
        assert "all_reduce" in dist.comms_logger.comms_dict
        summary = dist.log_summary()
        assert "all_reduce" in summary
    finally:
        dist.configure(enabled=False)
