"""Comm API tests (reference: tests/unit/comm/test_dist.py exercises
deepspeed.comm directly)."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.parallel.topology import ProcessTopology, build_mesh


@pytest.fixture
def mesh_dp4_tp2():
    mesh = build_mesh(axis_dims={"pipe": 1, "data": 4, "expert": 1, "seq": 1, "tensor": 2})
    dist.init_distributed(mesh=mesh, verbose=False)
    return mesh


def test_all_reduce_eager(mesh_dp4_tp2):
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    out = np.asarray(dist.all_reduce(x, group="data"))
    np.testing.assert_allclose(out[0], x.sum(0))
    np.testing.assert_allclose(out[3], x.sum(0))


def test_all_reduce_ops(mesh_dp4_tp2):
    x = np.array([[1.0], [5.0], [3.0], [2.0]], np.float32)
    assert np.asarray(dist.all_reduce(x, op=dist.ReduceOp.MAX, group="data"))[0] == 5.0
    assert np.asarray(dist.all_reduce(x, op=dist.ReduceOp.MIN, group="data"))[0] == 1.0
    np.testing.assert_allclose(np.asarray(dist.all_reduce(x, op=dist.ReduceOp.AVG, group="data"))[0], 2.75)


def test_all_gather_eager(mesh_dp4_tp2):
    x = np.arange(4, dtype=np.float32).reshape(4, 1)
    out = np.asarray(dist.all_gather(x, group="data"))
    assert out.shape == (4, 4, 1)
    np.testing.assert_allclose(out[0][:, 0], [0, 1, 2, 3])


def test_reduce_scatter_eager(mesh_dp4_tp2):
    x = np.ones((4, 8), np.float32)
    out = np.asarray(dist.reduce_scatter(x, group="data"))
    assert out.shape == (4, 2)
    np.testing.assert_allclose(out, 4.0)


def test_all_to_all_eager(mesh_dp4_tp2):
    x = np.arange(16, dtype=np.float32).reshape(4, 4)
    out = np.asarray(dist.all_to_all_single(x, group="data"))
    np.testing.assert_allclose(out, x.T)


def test_broadcast_eager(mesh_dp4_tp2):
    x = np.arange(8, dtype=np.float32).reshape(4, 2)
    out = np.asarray(dist.broadcast(x, src=1, group="data"))
    for i in range(4):
        np.testing.assert_allclose(out[i], x[1])


def test_traced_collectives_inside_shard_map(mesh_dp4_tp2):
    mesh = mesh_dp4_tp2

    def f(x):
        s = dist.all_reduce(x, group=("data", "tensor"))
        g = dist.all_gather(x, group="data")
        return s, g

    x = np.ones(8, np.float32)
    s, g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("data", "tensor")),
                                 out_specs=(P(), P(("data", "tensor")))))(x)
    np.testing.assert_allclose(np.asarray(s), 8.0)


def test_world_size_accessors(mesh_dp4_tp2):
    assert dist.get_world_size() == 8
    assert dist.get_world_size(group="data") == 4
    assert dist.get_world_size(group="tensor") == 2
    assert dist.get_rank() == 0


def test_process_topology_math():
    topo = ProcessTopology(["pipe", "data"], [2, 4])
    assert topo.get_rank(pipe=0, data=0) == 0
    assert topo.get_rank(pipe=1, data=0) == 4
    assert topo.get_coord(6).pipe == 1 and topo.get_coord(6).data == 2
    assert topo.get_axis_comm_lists("data") == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert topo.get_axis_comm_lists("pipe") == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert topo.filter_match(pipe=1) == [4, 5, 6, 7]
    assert topo.world_size() == 8
