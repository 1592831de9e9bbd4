"""Device-mesh topology: the TPU-native successor of rank-grid bookkeeping.

Counterpart of the reference's ``deepspeed/runtime/pipe/topology.py``
(ProcessTopology:12, PipeDataParallelTopology:232, PipeModelDataParallelTopology
:244, PipelineParallelGrid:251). The reference maps flat NCCL ranks onto a
cartesian grid and builds a process group per axis-slice. On TPU the mesh IS
the first-class object: we build one ``jax.sharding.Mesh`` whose named axes
(pipe, data, expert, seq, tensor) subsume the reference's ('pipe','data',
'model') axes plus the expert/sequence axes DeepSpeed keeps in
``utils/groups.py``. Rank⇄coordinate math is retained as pure Python because
the pipeline engine and checkpoint naming still need it.

Axis order is outermost→innermost placement over the chip slice:
pipe and data ride DCN/outer ICI; seq and tensor sit innermost so their
collectives (which fire per-layer) ride the fastest ICI links.
"""

from __future__ import annotations

import itertools
from collections import namedtuple
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order for the global mesh.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
# MiCS sub-group axis (reference zero/mics.py:31): when mics_shard_size is
# set, the data-parallel world is factored into (DATA_AXIS = replica groups,
# MICS_AXIS = in-group shard). ZeRO state shards over MICS_AXIS only, so
# GSPMD's allgather-on-use is confined to the small group; placing 'mics'
# immediately inside 'data' puts each shard group on contiguous ICI
# neighbors — the hierarchical intra-node gather MiCS hand-codes.
MICS_AXIS = "mics"
# Intra-host sub-axis of the data-parallel world: (DATA_AXIS = inter-host
# groups, ICI_AXIS = devices within a host), placed immediately inside
# 'data' (like 'mics') so each host group lands on contiguous ICI
# neighbors. Size 1 unless ``tpu.ici`` sets it by hand: the hpZ secondary
# partition that factored it went at PR 44, and the axis itself is
# ROADMAP D15 (removing it changes every mesh's axis names).
ICI_AXIS = "ici"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"
ALL_AXES = (PIPE_AXIS, DATA_AXIS, MICS_AXIS, ICI_AXIS, EXPERT_AXIS, SEQ_AXIS,
            TENSOR_AXIS)

# Axes over which dense parameters are replicated (ZeRO shards over these).
DP_AXES = (DATA_AXIS, MICS_AXIS, ICI_AXIS, EXPERT_AXIS)


class ProcessTopology:
    """Pure-python cartesian rank↔coordinate mapping over named axes.

    API-parity with reference topology.py:12 (get_rank:49, get_coord,
    get_axis_comm_lists:127, filter_match) but implemented over numpy index
    arithmetic instead of itertools scans.
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        assert len(axes) == len(dims)
        self.axes = list(axes)
        self.dims = list(int(d) for d in dims)
        self.ProcessCoord = namedtuple("ProcessCoord", self.axes)
        self._strides = np.cumprod([1] + self.dims[::-1][:-1])[::-1]

    def world_size(self) -> int:
        return int(np.prod(self.dims))

    def get_rank(self, **coord_kwargs) -> int:
        if sorted(coord_kwargs) != sorted(self.axes):
            raise ValueError(f"get_rank() needs all axes {self.axes}, got {list(coord_kwargs)}")
        rank = 0
        for axis, stride in zip(self.axes, self._strides):
            c = coord_kwargs[axis]
            assert 0 <= c < self.dims[self.axes.index(axis)]
            rank += int(stride) * c
        return rank

    def get_coord(self, rank: int):
        coords = []
        for stride, dim in zip(self._strides, self.dims):
            coords.append((rank // int(stride)) % dim)
        return self.ProcessCoord(*coords)

    def get_axis_names(self) -> List[str]:
        return list(self.axes)

    def get_dim(self, axis: str) -> int:
        if axis not in self.axes:
            return 1
        return self.dims[self.axes.index(axis)]

    def get_rank_repr(self, rank: int, omit_axes=("data",), inner_sep="_", outer_sep="-") -> str:
        omit_axes = list(omit_axes)
        axes = [a for a in self.axes if a not in omit_axes]
        names = []
        coord = self.get_coord(rank)
        for ax in axes:
            names.append(f"{ax}{inner_sep}{getattr(coord, ax):02d}")
        return outer_sep.join(names)

    def filter_match(self, **filter_kwargs) -> List[int]:
        """All ranks whose coords match the given axis=value constraints."""
        out = []
        for rank in range(self.world_size()):
            coord = self.get_coord(rank)
            if all(getattr(coord, ax) == v for ax, v in filter_kwargs.items()):
                out.append(rank)
        return out

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Groups of ranks that differ only along ``axis`` (reference :127)."""
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        ranges = [range(self.get_dim(a)) for a in other_axes]
        for combo in itertools.product(*ranges):
            fixed = dict(zip(other_axes, combo))
            group = [self.get_rank(**{**fixed, axis: i}) for i in range(self.get_dim(axis))]
            lists.append(group)
        return lists

    def __str__(self):
        return f"ProcessTopology(axes={self.axes}, dims={self.dims})"


def _resolve_mesh_dims(mesh_config, n_devices: int) -> Dict[str, int]:
    """Fill in data=-1 and validate the product against the device count."""
    dims = {
        PIPE_AXIS: mesh_config.pipe,
        DATA_AXIS: mesh_config.data,
        MICS_AXIS: getattr(mesh_config, "mics", 1),
        ICI_AXIS: getattr(mesh_config, "ici", 1),
        EXPERT_AXIS: mesh_config.expert,
        SEQ_AXIS: mesh_config.seq,
        TENSOR_AXIS: mesh_config.tensor,
    }
    fixed = int(np.prod([v for v in dims.values() if v != -1]))
    if dims[DATA_AXIS] == -1:
        if n_devices % fixed != 0:
            raise ValueError(f"device count {n_devices} not divisible by pipe*mics*ici*expert*seq*tensor={fixed}")
        dims[DATA_AXIS] = n_devices // fixed
    total = int(np.prod(list(dims.values())))
    if total != n_devices:
        raise ValueError(f"mesh {dims} needs {total} devices but {n_devices} are present")
    return dims


def build_mesh(mesh_config=None, devices=None, axis_dims: Optional[Dict[str, int]] = None) -> Mesh:
    """Build the global Mesh from a TPUMeshConfig (or explicit axis dims).

    Uses mesh_utils.create_device_mesh so the logical axes land contiguously on
    the physical ICI torus (innermost axes on nearest neighbors).
    """
    devices = devices if devices is not None else jax.devices()
    if axis_dims is None:
        from deepspeed_tpu.runtime.config import TPUMeshConfig

        mesh_config = mesh_config or TPUMeshConfig()
        axis_dims = _resolve_mesh_dims(mesh_config, len(devices))
    names = [a for a in ALL_AXES if a in axis_dims]
    shape = [axis_dims[a] for a in names]
    from jax.experimental import mesh_utils

    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, NotImplementedError, AssertionError) as e:
        # Warn, not raise: the enumeration-order mesh below is still a
        # CORRECT mesh — what is lost is placement (an inner axis may no
        # longer sit on neighbouring chips, so its per-layer collectives
        # take more hops), and legitimate callers land here: a survivor
        # mesh over 6 of 8 chips has no torus assignment at all. Whoever
        # reads a slow collective in a trace must be able to find this.
        from deepspeed_tpu.utils.logging import logger

        logger.warning(
            f"build_mesh: no topology-aware device assignment for mesh "
            f"{dict(zip(names, shape))} over {len(devices)} "
            f"{devices[0].device_kind} device(s) ({type(e).__name__}: {e}); "
            "using enumeration order — logical axes may not be "
            "ICI-contiguous")
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names=tuple(names))


def topology_from_mesh(mesh: Mesh) -> ProcessTopology:
    return ProcessTopology(axes=list(mesh.axis_names), dims=[mesh.shape[a] for a in mesh.axis_names])


def spec_axes(spec, ndim: int) -> Tuple[str, ...]:
    """All mesh axis names a PartitionSpec actually uses, normalized over
    the array rank (None / missing trailing entries use no axis)."""
    if spec is None:
        return ()
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    axes = []
    for e in entries:
        if e is None:
            continue
        for a in (e if isinstance(e, (tuple, list)) else (e,)):
            axes.append(a)
    return tuple(axes)


def unused_mesh_axes(spec, ndim: int, mesh: Mesh) -> Tuple[str, ...]:
    """The replication set of a placement: mesh axes of size > 1 that a
    PartitionSpec leaves unused. An array placed with ``spec`` is fully
    materialized once per coordinate of every returned axis — the
    ds_doctor sharding lint flags large arrays whose replication set
    still covers the data-parallel axes a ZeRO stage promised to shard
    over."""
    used = set(spec_axes(spec, ndim))
    return tuple(a for a in mesh.axis_names
                 if mesh.shape[a] > 1 and a not in used)


class ParallelGrid:
    """Axis-size/rank accessors bound to a Mesh + this process's position.

    Counterpart of PipelineParallelGrid (topology.py:251): exposes
    get_data_parallel_rank/world_size etc. On TPU a "rank" is a device index in
    the mesh; the per-process notion (jax.process_index) matters only for IO.
    """

    def __init__(self, mesh: Mesh, topology: Optional[ProcessTopology] = None):
        self.mesh = mesh
        self.topo = topology or topology_from_mesh(mesh)
        self.global_rank = jax.process_index()

    def _axis_size(self, axis: str) -> int:
        return self.mesh.shape.get(axis, 1)

    def get_pipe_parallel_world_size(self) -> int:
        return self._axis_size(PIPE_AXIS)

    def get_data_parallel_world_size(self) -> int:
        return (self._axis_size(DATA_AXIS) * self._axis_size(MICS_AXIS)
                * self._axis_size(EXPERT_AXIS))

    def get_model_parallel_world_size(self) -> int:
        return self._axis_size(TENSOR_AXIS)

    def get_tensor_parallel_world_size(self) -> int:
        return self._axis_size(TENSOR_AXIS)

    def get_sequence_parallel_world_size(self) -> int:
        return self._axis_size(SEQ_AXIS)

    def get_expert_parallel_world_size(self) -> int:
        return self._axis_size(EXPERT_AXIS)

    def get_slice_parallel_world_size(self) -> int:
        return self.get_model_parallel_world_size()

    # Device-level coords of the first local device — used for checkpoint
    # shard naming on multi-host.
    def _my_coord(self):
        dev = jax.local_devices()[0]
        idx = np.argwhere(np.asarray(self.mesh.devices) == dev)
        if idx.size == 0:
            return self.topo.get_coord(0)
        flat_rank = int(np.ravel_multi_index(tuple(idx[0]), np.asarray(self.mesh.devices).shape))
        return self.topo.get_coord(flat_rank)

    def get_stage_id(self) -> int:
        return getattr(self._my_coord(), PIPE_AXIS, 0)

    def get_data_parallel_rank(self) -> int:
        c = self._my_coord()
        return ((getattr(c, DATA_AXIS, 0) * self._axis_size(MICS_AXIS)
                 + getattr(c, MICS_AXIS, 0)) * self._axis_size(EXPERT_AXIS)
                + getattr(c, EXPERT_AXIS, 0))

    def get_model_parallel_rank(self) -> int:
        return getattr(self._my_coord(), TENSOR_AXIS, 0)
