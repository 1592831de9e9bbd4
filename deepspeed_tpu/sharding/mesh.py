"""The process-global named mesh.

One ``jax.sharding.Mesh`` per process, constructed from the ``tpu`` config
block through :func:`~deepspeed_tpu.parallel.topology.build_mesh` and CACHED:
asking for the same axis dims returns the SAME object, so the train engine,
the inference engine, the hybrid engine and the serving front-end compile
their programs against one device order. A request for different dims
rebuilds (a new "generation") — legitimate for sequential jobs in one
process (the multichip dryrun runs five topologies back to back), logged so
an accidental topology flap is visible.

Why object identity matters: two meshes built from the same dims have equal
device order (``mesh_utils.create_device_mesh`` is deterministic), but every
independently-built mesh is another chance for a subsystem to pass
``devices=`` or ``axis_dims=`` that differ subtly — and a program compiled
over a mesh whose device order disagrees with the train step's deadlocks
the collective rendezvous (the MULTICHIP_r05 failure class). One cached
object turns "the same mesh" from a convention into a fact.
"""

from __future__ import annotations

from typing import Dict, Optional

from jax.sharding import Mesh

from deepspeed_tpu.utils.logging import logger

_GLOBAL_MESH: Optional[Mesh] = None
_GENERATION: int = 0
_RNG_PINNED = False


def _enable_sharding_invariant_rng() -> None:
    """Force partitionable threefry ON (one-time, with the first mesh).

    Non-partitionable threefry is NOT sharding-invariant: the same
    ``jax.random.normal`` compiled with dp/pipe-sharded ``out_shardings``
    yields DIFFERENT values than the unsharded draw (measured: 0.09 abs
    diff on a 0.02-std init), which makes a model's initialization depend
    on its topology. The installed jax defaults the flag to True; the pin
    covers a process that turned it off (``JAX_THREEFRY_PARTITIONABLE=0``).
    One mesh, one RNG semantics: every placement decision flows through
    this package, so the invariance knob lives here too.
    """
    global _RNG_PINNED
    if _RNG_PINNED:
        return
    import jax

    if not jax.config.jax_threefry_partitionable:
        jax.config.update("jax_threefry_partitionable", True)
        logger.info("jax_threefry_partitionable enabled: random inits "
                    "are now sharding-invariant (a sharded draw equals "
                    "the unsharded draw for the same key)")
    _RNG_PINNED = True


def global_mesh() -> Optional[Mesh]:
    """The current process-global mesh, or None before the first build."""
    return _GLOBAL_MESH


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` block, or None outside one.

    Every engine traces its programs inside ``with self.mesh:``, so at
    trace time this is the mesh the program is being compiled FOR — which
    the process-global mesh is not when a caller handed an engine its own
    (``init_inference(mesh=...)``, AOT lowering against a compile-only
    topology). Model code asks here which devices a kernel will run on.
    The one read of jax's thread-local mesh context in the package."""
    from jax._src.mesh import thread_resources

    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def mesh_generation() -> int:
    """How many times the global mesh has been (re)built this process."""
    return _GENERATION


def _dims_of(mesh: Mesh) -> Dict[str, int]:
    return {a: int(mesh.shape[a]) for a in mesh.axis_names}


def ensure_global_mesh(mesh_config=None, devices=None,
                       axis_dims: Optional[Dict[str, int]] = None) -> Mesh:
    """Return THE process mesh for the requested topology.

    Same resolved axis dims as the current global mesh → the cached object.
    Different dims → a fresh build replaces it (logged). Accepts the same
    arguments as :func:`~deepspeed_tpu.parallel.topology.build_mesh`; with
    none given, the dims resolve from a default ``TPUMeshConfig`` (data =
    all devices).
    """
    global _GLOBAL_MESH, _GENERATION
    from deepspeed_tpu.parallel.topology import _resolve_mesh_dims, build_mesh

    _enable_sharding_invariant_rng()
    if axis_dims is None:
        import jax

        from deepspeed_tpu.runtime.config import TPUMeshConfig

        n = len(devices) if devices is not None else len(jax.devices())
        axis_dims = _resolve_mesh_dims(mesh_config or TPUMeshConfig(), n)
    # normalize against the canonical axis set (missing axes = size 1):
    # "data=8" and "data=8 with mics/seq elided" are the SAME topology and
    # must hit the same cache entry — a spurious rebuild would hand two
    # subsystems two distinct Mesh objects for one topology
    from deepspeed_tpu.parallel.topology import ALL_AXES

    want = {a: int(axis_dims.get(a, 1)) for a in ALL_AXES}
    for a, v in axis_dims.items():
        want[a] = int(v)
    cur = _GLOBAL_MESH
    if cur is not None and _dims_of(cur) == want and devices is None:
        return cur
    mesh = build_mesh(devices=devices, axis_dims=want)
    if cur is not None and _dims_of(cur) != want:
        logger.info(
            f"global mesh rebuilt: {_nontrivial(_dims_of(cur))} -> "
            f"{_nontrivial(want)} (generation {_GENERATION + 1}); programs "
            "compiled on the previous mesh keep running on it — sequential "
            "jobs are fine, interleaving them is not")
    _GLOBAL_MESH = mesh
    _GENERATION += 1
    return mesh


def adopt_global_mesh(mesh: Mesh) -> Mesh:
    """Install a caller-built mesh (mpu=, resize survivor meshes) as the
    process-global one, so later same-dims requests reuse it."""
    global _GLOBAL_MESH, _GENERATION
    _enable_sharding_invariant_rng()
    if mesh is not _GLOBAL_MESH:
        _GLOBAL_MESH = mesh
        _GENERATION += 1
    return mesh


def reset_global_mesh() -> None:
    """Drop the cached mesh (tests; a fresh comm backend does this)."""
    global _GLOBAL_MESH
    _GLOBAL_MESH = None


def _nontrivial(dims: Dict[str, int]) -> Dict[str, int]:
    return {a: v for a, v in dims.items() if v > 1} or dict(list(dims.items())[:1])


def host_device_groups(mesh: Optional[Mesh]):
    """Device-id groups per *host* — the boundary the xray comm model
    splits a collective's bytes on (``all-gather`` vs
    ``all-gather/intra``). Three sources, in order:

    * a real multi-process run: group by ``device.process_index`` — the
      actual host boundary;
    * a single-process mesh carrying the ``ici`` sub-axis (``tpu.ici``
      > 1): the DCN-ish axes (pipe, data, mics) index the host groups and
      everything inside (ici, expert, seq, tensor) is one host — the
      simulated-fleet host model the 8-dev drills run on;
    * neither: ``None`` — the mesh encodes no host structure, and the
      comm model keeps its flat (un-split) accounting.
    """
    if mesh is None:
        return None
    import jax
    import numpy as np

    from deepspeed_tpu.parallel.topology import (DATA_AXIS, ICI_AXIS,
                                                 MICS_AXIS, PIPE_AXIS)

    if jax.process_count() > 1:
        by_proc = {}
        for d in mesh.devices.flat:
            by_proc.setdefault(int(d.process_index), set()).add(int(d.id))
        return tuple(frozenset(g) for _, g in sorted(by_proc.items()))
    if int(mesh.shape.get(ICI_AXIS, 1)) <= 1:
        return None
    inter = [i for i, a in enumerate(mesh.axis_names)
             if a in (PIPE_AXIS, DATA_AXIS, MICS_AXIS)]
    groups = {}
    for coords, dev in np.ndenumerate(mesh.devices):
        key = tuple(coords[i] for i in inter)
        groups.setdefault(key, set()).add(int(dev.id))
    return tuple(frozenset(g) for _, g in sorted(groups.items()))


def mesh_axes_string(mesh: Optional[Mesh]) -> str:
    """Compact ``data=4×tensor=2`` identity of a mesh — the string ds_perf
    ledger entries carry so a benchmark line is mesh-attributable, and the
    header ``ds_report mesh`` prints. Size-1 axes are elided; a fully
    trivial mesh renders as ``single-device``."""
    if mesh is None:
        return "unmeshed"
    parts = [f"{a}={int(mesh.shape[a])}" for a in mesh.axis_names
             if int(mesh.shape[a]) > 1]
    return "×".join(parts) if parts else "single-device"
