"""ZeRO partitioning — sharding-spec planner.

The TPU-native re-expression of the reference's three ZeRO optimizers
(zero/stage_1_and_2.py:90, zero/stage3.py:65, zero/partition_parameters.py:603).
Where the reference installs gradient hooks, flattens parameter groups, and
hand-schedules bucketed reduce/allgather on side streams, the TPU build states
the *placement* declaratively and lets XLA generate the collectives:

  stage 0  params replicated, grads all-reduced (psum), optimizer replicated
  stage 1  + optimizer state (and fp32 master weights) sharded over the DP axes
  stage 2  + gradients sharded over the DP axes (psum → reduce_scatter)
  stage 3  + parameters themselves sharded over the DP axes, gathered on use

**Stage 3: who gathers, and where.** A placement at rest says nothing of
where a sharded weight becomes whole, and the partitioner, left alone, picks
a different form in each pass (gpt2-xl over data=4: the backward's re-run
gathered the weight, the forward gathered the ACTIVATIONS of every chip and
sent the product back through an all-to-all, or ran the matmul windowed over
the shards: two to three times the re-run's time). So the program states it,
in two places, and the partitioner keeps the rest (embeddings, final norm):

* **the layer stack** (:class:`LayerGathers`): every leaf of a layer's slice
  that ZeRO sharded is constrained, where the block uses it, to its spec
  WITHOUT the DP axes (tensor / expert axes kept: :func:`drop_dp_axes`)
  under a ``custom_vjp`` whose backward constrains the cotangent to the
  SHARDED spec (:func:`gather_on_use`): one async all-gather a leaf a pass,
  the weight gradient leaves as a reduce-scatter. The seat is
  ``models/common.py::remat_wrap``, INSIDE the block's ``jax.checkpoint``,
  so what a layer keeps for its backward is the sharded slice and the
  backward gathers again (a gather outside the checkpoint makes the scan
  stack L gathered layers). The engine installs the rule around the trace
  of the loss's gradient when the plan is stage 3 over more than one chip
  (not under ``overlap.schedule: "serial"``, whose gather phase hands the
  step the whole tree gathered). One chip, stage 0-2, a leaf without a DP
  axis, a region that is already manual: nothing is traced.
* **the loss head**: ``models/common.py::chunked_lm_loss`` (a ``shard_map``
  over the batch axes that the head enters whole, once a step).

``param_persistence_threshold`` keeps small params replicated in stage 3 just
like the reference's "persistent parameters" (stage3.py persistence threshold),
avoiding per-tiny-tensor allgathers. As in the reference it judges ONE
layer's parameter: a leaf of a layer-stacked subtree (``stacked_keys``:
``model.stacked_params_key``, ``"blocks"`` by default) by the elements one
layer holds, any other leaf whole. Masters, moments and gradients of a
persistent leaf stay sharded. MiCS-style scoped sharding
(zero/mics.py:31) falls out of restricting ``shard_axes`` to a sub-axis of the
mesh: params replicate across the remaining DP axes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import (DATA_AXIS, DP_AXES, EXPERT_AXIS,
                                             ICI_AXIS, MICS_AXIS, SEQ_AXIS,
                                             TENSOR_AXIS)
from deepspeed_tpu.utils.logging import logger


def _spec_tuple(spec: Optional[P], ndim: int) -> Tuple:
    """Normalize a PartitionSpec to a length-ndim tuple of entries."""
    if spec is None:
        return (None,) * ndim
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    return entries[:ndim]


def _axes_of(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def drop_dp_axes(spec: Optional[P], ndim: int, dp_axes: Sequence[str],
                 own: Optional[P] = None) -> P:
    """The GATHERED twin of a ZeRO-sharded spec: same tp placement, dp
    axes removed (the all-gather GSPMD inserts to honor the change).
    ``own``: the leaf's spec before ZeRO touched it; a dp axis it carries
    there is the model's (an expert axis) and stays."""
    out = []
    for entry, mine in zip(_spec_tuple(spec, ndim), _spec_tuple(own, ndim)):
        axes = tuple(a for a in _axes_of(entry)
                     if a not in dp_axes or a in _axes_of(mine))
        out.append(axes[0] if len(axes) == 1 else (axes if axes else None))
    return P(*out)


def gather_on_use(x, gathered: NamedSharding, sharded: NamedSharding):
    """``x`` constrained to its GATHERED placement under a ``custom_vjp``
    whose backward constrains the cotangent straight back to the SHARDED
    placement, so a weight's gradient leaves the pass that made it as a
    reduce-scatter. A plain constraint's transpose pins the cotangent
    GATHERED instead: inside a loop that is an all-reduce of the whole
    gradient an iteration (measured at the loss head, PR 28)."""

    @jax.custom_vjp
    def gather(v):
        return jax.lax.with_sharding_constraint(v, gathered)

    def fwd(v):
        return gather(v), None

    def bwd(_, ct):
        return (jax.lax.with_sharding_constraint(ct, sharded),)

    gather.defvjp(fwd, bwd)
    return gather(x)


# checkpoint name of a gathered leaf: a remat policy that saves by name
# never lists it, so a layer keeps its sharded slice and gathers again
GATHERED_NAME = "zero3_gathered"


def stacked_param_keys(model) -> Tuple[str, ...]:
    """The layer-stacked subtrees of a model's params: ``"blocks"`` unless
    the model names its own (``stacked_params_key``: one key or several)."""
    key = getattr(model, "stacked_params_key", "blocks")
    return (key,) if isinstance(key, str) else tuple(key)


def _stacked_leaves(tree: Any, stacked_keys: Sequence[str]):
    """The (path from the root, leaf) pairs of every subtree of ``tree``
    under a ``stacked_keys`` entry whose leaves all lead with one length."""
    found = []
    for key in stacked_keys if isinstance(tree, dict) else ():
        flat = jax.tree_util.tree_flatten_with_path({key: tree.get(key)})[0]
        if flat and len({l.shape[:1] for _, l in flat}) == 1 \
                and flat[0][1].shape:
            found += flat
    return found


@dataclasses.dataclass(frozen=True)
class LayerGathers:
    """Stage 3's gather-on-use for the layer stack, as a rule the models'
    layer walk applies to what a block is handed (``models/common.py::
    remat_wrap``, inside the block's checkpoint). ``leaves``: {leaf name:
    [(one layer's shape, gathered spec, sharded spec)]} of every leaf of a
    stacked subtree that ZeRO sharded; a leaf is recognised by the dict key
    it is held under and its trailing shape (leading axes — a pair of
    layers — stay unsharded), anything else passes through."""
    mesh: Mesh
    dp_axes: Tuple[str, ...]
    leaves: dict

    def __call__(self, tree: Any) -> Any:
        from deepspeed_tpu.comm import comm as _comm

        if jax.sharding.get_abstract_mesh().manual_axes:
            return tree         # a manual region places its own operands

        def on_use(path, x):
            name = next((k.key for k in reversed(path)
                         if isinstance(k, jax.tree_util.DictKey)), None)
            shape = tuple(getattr(x, "shape", ()))
            for layer, gathered, sharded in self.leaves.get(name, ()):
                lead = len(shape) - len(layer)
                if lead < 0 or shape[lead:] != layer:
                    continue
                _comm.record_engine_collective("zero3_gather", layer,
                                               x.dtype, self.dp_axes)
                pad = (None,) * lead
                return checkpoint_name(gather_on_use(
                    x, NamedSharding(self.mesh, P(*pad, *gathered)),
                    NamedSharding(self.mesh, P(*pad, *sharded))),
                    GATHERED_NAME)
            return x

        return jax.tree_util.tree_map_with_path(on_use, tree)


def _layer_gathers(stacked, param_specs, tp_specs, mesh,
                   dp_axes) -> Optional[LayerGathers]:
    """The rule over ``stacked`` (:func:`_stacked_leaves` of the shapes)."""
    at = lambda tree, path: functools.reduce(
        lambda t, k: t[k.key if hasattr(k, "key") else k.idx], path, tree)
    leaves = {}
    for path, sh in stacked:
        spec, own = at(param_specs, path), at(tp_specs, path)
        rank = len(sh.shape)
        sharded = P(*_spec_tuple(spec, rank)[1:])
        gathered = P(*tuple(drop_dp_axes(spec, rank, dp_axes, own))[1:])
        name = getattr(path[-1], "key", None)   # held in a dict
        if name is not None and tuple(gathered) != tuple(sharded):
            leaves.setdefault(name, []).append(
                (tuple(sh.shape[1:]), gathered, sharded))
    return LayerGathers(mesh, tuple(dp_axes), leaves) if leaves else None


def _shard_over_dp(shape: Tuple[int, ...], base_spec: Optional[P], dp_axes: Sequence[str],
                   mesh: Mesh, min_size: int = 0) -> P:
    """Add DP axes to the best available dim of ``base_spec``.

    Picks the largest dim whose size (divided by what tp already shards it by)
    is divisible by the DP world; returns base_spec unchanged if none fits or
    the tensor is smaller than ``min_size`` elements.
    """
    dp_axes = [a for a in dp_axes if mesh.shape.get(a, 1) > 1]
    if not dp_axes:
        return base_spec if base_spec is not None else P()
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    entries = list(_spec_tuple(base_spec, len(shape)))
    if int(np.prod(shape)) < max(1, min_size):
        return P(*entries)

    used = set()
    for e in entries:
        used.update(_axes_of(e))
    if any(a in used for a in dp_axes):
        return P(*entries)  # already dp-sharded (e.g. expert-stacked weights)

    # Dim choice: (1) prefer a dim already tp-sharded — dp extends the same
    # dim (fsdp-over-tp, the layout the forward pass already uses), then
    # (2) prefer LATER dims — leading dims are layer-stack/position dims that
    # lax.scan and wpe[:T]-style slices cut through, and slicing a dp-sharded
    # dim forces SPMD "involuntary full rematerialization" (observed on the
    # (n_positions, d) table when n_positions tied n_embd).
    best_dim, best_key = -1, (-1, -1)
    for d, size in enumerate(shape):
        tp_factor = int(np.prod([mesh.shape[a] for a in _axes_of(entries[d])])) or 1
        local = size // tp_factor
        if local % dp_size == 0 and local // dp_size > 0:
            key = (1 if tp_factor > 1 else 0, d)
            if key > best_key:
                best_dim, best_key = d, key
    if best_dim < 0:
        return P(*entries)
    entries[best_dim] = tuple(_axes_of(entries[best_dim])) + tuple(dp_axes)
    if len(entries[best_dim]) == 1:
        entries[best_dim] = entries[best_dim][0]
    return P(*entries)


class ShardingPlan:
    """Per-pytree NamedShardings for every piece of training state — a VIEW
    over the :class:`~deepspeed_tpu.sharding.registry.ShardingRegistry`.

    The plan used to OWN the spec trees; now the registry does (one source
    for params / master / grads / batch / optimizer state / KV cache), and
    the plan keeps its historical attribute surface (``param_specs``,
    ``master_shardings()``, …) as reads of the registry, so ZeRO consumers
    and the serial schedule (``runtime/overlap.py``) did not have to move."""

    def __init__(self, mesh: Optional[Mesh] = None, param_specs: Any = None,
                 master_specs: Any = None, grad_specs: Any = None,
                 batch_spec: Optional[P] = None, zero_stage: int = 0,
                 dp_axes: Tuple[str, ...] = (), registry=None):
        from deepspeed_tpu.sharding.registry import ShardingRegistry

        if registry is None:
            assert mesh is not None, "ShardingPlan needs a mesh or a registry"
            registry = ShardingRegistry(mesh)
            registry.register("params", param_specs)
            registry.register("master", master_specs)
            registry.register("grads", grad_specs)
            registry.register("batch", batch_spec)
        self.registry = registry
        self.zero_stage = int(zero_stage)
        self.dp_axes = tuple(dp_axes)
        self._master_shapes = None
        # stage 3 over more than one chip: the gather-on-use rule of the
        # layer stack (plan_sharding fills it; None: nothing to gather)
        self.layer_gathers: Optional[LayerGathers] = None

    # ------------------------------------------------------- registry views
    @property
    def mesh(self) -> Mesh:
        return self.registry.mesh

    @property
    def param_specs(self) -> Any:
        return self.registry.spec("params")

    @property
    def master_specs(self) -> Any:
        return self.registry.spec("master")

    @property
    def grad_specs(self) -> Any:
        return self.registry.spec("grads")

    @property
    def batch_spec(self) -> P:
        return self.registry.spec("batch")

    def named(self, spec: P, memory_kind: Optional[str] = None) -> NamedSharding:
        return self.registry.named(spec, memory_kind)

    def param_shardings(self):
        return jax.tree.map(self.named, self.param_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def master_shardings(self, memory_kind: Optional[str] = None):
        return jax.tree.map(lambda s: self.named(s, memory_kind), self.master_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def grad_shardings(self):
        return jax.tree.map(self.named, self.grad_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def batch_sharding(self) -> NamedSharding:
        return self.named(self.batch_spec)

    def map_opt_state_specs(self, opt_state_shapes: Any, master_shapes: Any):
        """Build specs for the optimizer state given abstract shapes.

        optax states embed copies of the param tree inside NamedTuples (e.g.
        ScaleByAdamState.mu/.nu), so an optimizer-state leaf's key path ends
        with the key path of the param it shadows. Matching by that PATH
        SUFFIX (plus a shape check) — not by shape alone — keeps two
        same-shaped but differently-sharded params (a tp-sharded and a
        replicated square matrix, say) from silently swapping their moment
        placements. Leaves that shadow no param (step counters, EmptyState)
        replicate; a shape-only fallback remains for exotic states but
        refuses to guess when two candidate specs conflict.
        """
        def key_of(path):
            return tuple(str(p) for p in path)

        spec_by_path = {}
        shape_by_path = {}
        # BOTH flattens must keep None leaves (None is an empty pytree node a
        # default flatten drops) or the zip below shifts from the first None
        # onward and every spec pairs with the wrong shape
        keep_none = lambda x: x is None
        spec_flat = jax.tree_util.tree_flatten_with_path(
            self.master_specs, is_leaf=lambda x: isinstance(x, P) or x is None)[0]
        shapes_flat = jax.tree_util.tree_flatten_with_path(
            master_shapes, is_leaf=keep_none)[0]
        for (p_sp, sp), (p_sh, sh) in zip(spec_flat, shapes_flat):
            if sh is None:
                continue
            spec_by_path[key_of(p_sp)] = sp
            shape_by_path[key_of(p_sp)] = tuple(sh.shape)

        # shape fallback: only unambiguous (all same-shaped masters agree)
        shape_index = {}
        for k, shape in shape_by_path.items():
            shape_index.setdefault(shape, set()).add(
                tuple(spec_by_path[k]) if spec_by_path[k] is not None else None)

        def leaf_spec(path, leaf):
            k = key_of(path)
            shape = tuple(leaf.shape)
            # longest path suffix that names a master param of the same shape
            for i in range(len(k)):
                sp = spec_by_path.get(k[i:])
                if sp is not None and shape_by_path[k[i:]] == shape:
                    return sp
            cands = shape_index.get(shape)
            if cands is not None and len(cands) == 1:
                only = next(iter(cands))
                return P(*only) if only is not None else P()
            if cands is not None and len(cands) > 1:
                logger.warning(
                    f"optimizer-state leaf at {'/'.join(k)} (shape {shape}) "
                    f"matches no master param by path and {len(cands)} "
                    "conflicting specs by shape — replicating it. If this "
                    "leaf shadows a sharded param, its memory savings are "
                    "lost; wire an explicit spec.")
            return P()

        flat = jax.tree_util.tree_flatten_with_path(opt_state_shapes)
        leaves = [leaf_spec(path, leaf) for path, leaf in flat[0]]
        specs = jax.tree_util.tree_unflatten(flat[1], leaves)
        # the optimizer state is an engine pytree like any other: its specs
        # live in the registry too (ds_report mesh renders them from there)
        self.registry.register("opt_state", specs)
        return specs


def plan_sharding(param_shapes: Any,
                  mesh: Mesh,
                  zero_config=None,
                  tp_specs: Any = None,
                  dp_axes: Sequence[str] = DP_AXES,
                  batch_spec: Optional[P] = None,
                  stacked_keys: Sequence[str] = ("blocks",)) -> ShardingPlan:
    """Compute the ZeRO placement plan.

    Args:
      param_shapes: pytree of ShapeDtypeStruct (from jax.eval_shape of init).
      tp_specs: optional pytree of PartitionSpec with tensor/seq-parallel axes
        already assigned (the AutoTP analogue fills this; None = pure DP).
      zero_config: DeepSpeedZeroConfig; stage and thresholds read from it.
      stacked_keys: the top-level keys of ``param_shapes`` whose leaves are
        stacked over the layers (:func:`stacked_param_keys`): the persistence
        threshold judges such a leaf by ONE layer's elements, and at stage 3
        the plan carries the gather-on-use rule for them (``layer_gathers``).
    """
    from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig

    zc = zero_config or DeepSpeedZeroConfig()
    stage = int(zc.stage)
    if zc.shard_axes:
        dp_axes = tuple(zc.shard_axes)
    elif zc.mics_shard_size and zc.mics_shard_size > 0:
        # MiCS (ref zero/mics.py:31): shard state within groups of
        # mics_shard_size, replicate across groups. The engine factors the
        # data-parallel world into (DATA_AXIS = replica groups, MICS_AXIS =
        # in-group shard) at mesh build; sharding over MICS_AXIS only then
        # confines GSPMD's allgather-on-use to the small contiguous group
        # (the hierarchical intra-node gather the reference hand-codes in
        # MiCS_AllGatherCoalescedHandle), while grads still psum over the
        # full (data, mics) product for correctness — the inter-group
        # allreduce riding the outer links.
        want = int(zc.mics_shard_size)
        mics_size = mesh.shape.get(MICS_AXIS, 1)
        data_size = mesh.shape.get(DATA_AXIS, 1)
        if mics_size == want:
            dp_axes = (MICS_AXIS,)
        elif mics_size == 1 and data_size == want:
            # group == the whole data axis: MiCS degenerates to plain ZeRO
            dp_axes = (DATA_AXIS,)
        else:
            raise ValueError(
                f"mics_shard_size={want} does not match the mesh: mics axis "
                f"is {mics_size}, data axis is {data_size}. Pass "
                "mics_shard_size through ds_config zero_optimization so "
                "initialize() factors the mesh, or build the mesh with "
                "tpu={'mics': <shard_size>, ...} explicitly")
    dp_axes = tuple(a for a in dp_axes if mesh.shape.get(a, 1) > 1)

    if tp_specs is None:
        tp_specs = jax.tree.map(lambda s: P(), param_shapes)

    # the layers a leaf is stacked over, by its path: the persistence
    # threshold is a count of ONE layer's elements (module docstring)
    stacked = _stacked_leaves(param_shapes, stacked_keys)
    layers_of = {jax.tree_util.keystr(path): leaf.shape[0]
                 for path, leaf in stacked}

    def persistent_under(path):
        return int(zc.param_persistence_threshold) * \
            layers_of.get(jax.tree_util.keystr(path), 1)

    def param_spec(path, shape_struct, tp_spec):
        if stage >= 3:
            return _shard_over_dp(shape_struct.shape, tp_spec, dp_axes, mesh,
                                  min_size=persistent_under(path))
        return tp_spec if tp_spec is not None else P()

    def master_spec(shape_struct, tp_spec):
        if stage >= 1:
            return _shard_over_dp(shape_struct.shape, tp_spec, dp_axes, mesh, min_size=0)
        return tp_spec if tp_spec is not None else P()

    def grad_spec(shape_struct, tp_spec):
        if stage >= 2:
            return _shard_over_dp(shape_struct.shape, tp_spec, dp_axes, mesh, min_size=0)
        return tp_spec if tp_spec is not None else P()

    is_p = lambda x: isinstance(x, P) or x is None
    param_specs = jax.tree_util.tree_map_with_path(param_spec, param_shapes,
                                                   tp_specs)
    master_specs = jax.tree.map(master_spec, param_shapes, tp_specs)
    grad_specs = jax.tree.map(grad_spec, param_shapes, tp_specs)

    # Surface silent sharding failures: _shard_over_dp degrades to replicated
    # when no dim is divisible by the dp world — correct, but a LARGE leaf
    # that fails is exactly how a model quietly loses its ZeRO memory
    # savings (e.g. a vocab padded to a size coprime with dp). One warning
    # per offending leaf, threshold = the stage-3 persistence threshold
    # (smaller leaves are intentionally kept whole).
    if dp_axes and stage >= 1:
        # keep None leaves on both sides so the zip can't shift (see
        # map_opt_state_specs)
        shapes_flat = jax.tree_util.tree_flatten_with_path(
            param_shapes, is_leaf=lambda x: x is None)[0]
        check = param_specs if stage >= 3 else master_specs
        what = "params+optimizer" if stage >= 3 else "optimizer state"
        specs_flat = jax.tree_util.tree_flatten_with_path(check, is_leaf=is_p)[0]
        for (path, sh), (_, sp) in zip(shapes_flat, specs_flat):
            if sh is None:
                continue
            n = int(np.prod(sh.shape))
            if n < max(persistent_under(path), 1):
                continue
            axes = set()
            for e in _spec_tuple(sp, len(sh.shape)):
                axes.update(_axes_of(e))
            if not any(a in dp_axes for a in axes):
                name = "/".join(str(p) for p in path)
                placement = (f"keeps only its tp sharding {sp}" if axes
                             else "stays fully REPLICATED")
                logger.warning(
                    f"ZeRO stage {stage}: {what} for param {name} "
                    f"(shape {tuple(sh.shape)}, {n/1e6:.1f}M elements) "
                    f"{placement} — no dim is divisible by the dp world "
                    f"{[f'{a}={mesh.shape[a]}' for a in dp_axes]}. Pad the "
                    "offending dim to a multiple of the dp world to recover "
                    "the ZeRO sharding memory savings.")

    if batch_spec is None:
        batch_axes = tuple(a for a in (DATA_AXIS, MICS_AXIS, ICI_AXIS, EXPERT_AXIS)
                           if mesh.shape.get(a, 1) > 1)
        if mesh.shape.get(SEQ_AXIS, 1) > 1:
            # sequence parallelism: tokens dim sharded over 'seq' too
            batch_spec = P(batch_axes if batch_axes else None, SEQ_AXIS)
        else:
            batch_spec = P(batch_axes if batch_axes else None)

    from deepspeed_tpu.sharding.registry import ShardingRegistry

    registry = ShardingRegistry(mesh)
    registry.register("params", param_specs)
    registry.register("master", master_specs)
    registry.register("grads", grad_specs)
    registry.register("batch", batch_spec)
    plan = ShardingPlan(registry=registry, zero_stage=stage, dp_axes=dp_axes)
    plan._master_shapes = param_shapes
    if stage >= 3 and dp_axes:
        plan.layer_gathers = _layer_gathers(stacked, param_specs, tp_specs,
                                            mesh, dp_axes)
    return plan


def partition_report(plan: ShardingPlan, param_shapes: Any) -> str:
    """Human-readable table of how much of the model each stage shards."""
    n_total = 0
    n_sharded = 0
    for leaf, spec in zip(jax.tree.leaves(param_shapes),
                          jax.tree.leaves(plan.param_specs, is_leaf=lambda x: isinstance(x, P))):
        n = int(np.prod(leaf.shape))
        n_total += n
        axes = set()
        for e in _spec_tuple(spec, len(leaf.shape)):
            axes.update(_axes_of(e))
        if any(a in plan.dp_axes for a in axes):
            n_sharded += n
    if not plan.dp_axes:
        # one-chip / no-dp mesh: "0.0% dp-sharded over axes ()" reads like
        # a sharding bug when it is just a world of one — say WHY instead
        dp_world = int(np.prod([plan.mesh.shape.get(a, 1)
                                for a in DP_AXES] or [1]))
        why = ("world size 1 — nothing to shard across"
               if dp_world <= 1 else
               "the configured shard axes have size 1 on this mesh")
        from deepspeed_tpu.sharding.mesh import mesh_axes_string

        return (f"ZeRO stage {plan.zero_stage}: {n_total/1e6:.1f}M params, "
                f"dp sharding inactive ({why}) "
                f"[mesh {mesh_axes_string(plan.mesh)}]; params/optimizer "
                "state stay whole on each chip (expected on this topology, "
                "not a sharding bug — the ZeRO placement activates when a "
                "data-parallel mesh axis has size > 1)")
    from deepspeed_tpu.sharding.mesh import mesh_axes_string

    pct = 100.0 * n_sharded / max(1, n_total)
    msg = (f"ZeRO stage {plan.zero_stage}: {n_total/1e6:.1f}M params, "
           f"{pct:.1f}% dp-sharded over axes {plan.dp_axes} "
           f"[mesh {mesh_axes_string(plan.mesh)}]")
    if plan.dp_axes == (MICS_AXIS,):
        n_groups = plan.mesh.shape.get(DATA_AXIS, 1)
        msg += (f" (MiCS: {n_groups} replica groups × "
                f"{plan.mesh.shape.get(MICS_AXIS, 1)}-way shard)")
    return msg
