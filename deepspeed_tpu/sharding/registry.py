"""The spec registry: every engine pytree's placement, derived in ONE place.

Before this module, five subsystems each decided placement for themselves:
the ZeRO planner computed param/master/grad specs, the engine hand-rolled
batch specs in ``_shard_batch``, the inference engine re-derived param
specs through AutoTP, every generation program re-read the model's KV-cache
specs, and the pipeline/SP paths carried their own. The registry holds all
of them, keyed by name — ``params`` / ``master`` / ``grads`` / ``opt_state``
/ ``batch`` / ``kv_cache`` — as :class:`~jax.sharding.PartitionSpec` trees
over THE mesh, and hands out :class:`~jax.sharding.NamedSharding` trees on
demand. The ZeRO :class:`~deepspeed_tpu.runtime.zero.partition.ShardingPlan`
is a view over an instance of this class; ``sharded_jit`` call sites read
their in/out shardings from here.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import (DATA_AXIS, EXPERT_AXIS,
                                             ICI_AXIS, MICS_AXIS, SEQ_AXIS)

__all__ = ["ShardingRegistry"]

_is_spec = lambda x: isinstance(x, P) or x is None


class ShardingRegistry:
    """Named PartitionSpec trees over one mesh.

    ``register(name, specs)`` stores a spec pytree; ``spec(name)`` returns
    it; ``shardings(name)`` maps it to NamedShardings. Batch helpers clamp
    the registered ``batch`` spec to each leaf's rank (the one behavior
    that used to live, duplicated, in ``engine._shard_batch`` and
    ``engine.aot_memory_analysis``).
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._specs: Dict[str, Any] = {}

    # ------------------------------------------------------------- storage
    def register(self, name: str, specs: Any) -> None:
        self._specs[name] = specs

    def has(self, name: str) -> bool:
        return name in self._specs

    def spec(self, name: str) -> Any:
        if name not in self._specs:
            raise KeyError(
                f"sharding registry has no '{name}' specs (registered: "
                f"{sorted(self._specs)})")
        return self._specs[name]

    def names(self):
        return sorted(self._specs)

    # ----------------------------------------------------------- shardings
    def named(self, spec: Optional[P],
              memory_kind: Optional[str] = None) -> NamedSharding:
        spec = spec if spec is not None else P()
        if memory_kind:
            return NamedSharding(self.mesh, spec, memory_kind=memory_kind)
        return NamedSharding(self.mesh, spec)

    def shardings(self, name: str, memory_kind: Optional[str] = None) -> Any:
        return jax.tree.map(lambda s: self.named(s, memory_kind),
                            self.spec(name), is_leaf=_is_spec)

    def replicated(self) -> NamedSharding:
        return self.named(P())

    def fit(self, specs: Any, shapes: Any) -> Any:
        """``specs`` with every dim entry the mesh does not divide evenly
        set to None (jax refuses uneven shardings): GPT-2's published
        vocab of 50257 over ``tensor=4`` stays whole on each chip instead
        of failing the placement. Each dropped entry is logged."""
        from deepspeed_tpu.parallel.topology import spec_axes
        from deepspeed_tpu.utils.logging import logger

        def leaf(path, spec, shape):
            if spec is None:
                return spec
            entries = list(tuple(spec)[:len(shape.shape)])
            for d, e in enumerate(entries):
                ways = math.prod(self.mesh.shape[a]
                                 for a in spec_axes(P(e), 1))
                if shape.shape[d] % ways:
                    logger.warning(
                        f"sharding: {jax.tree_util.keystr(path)} dim {d} "
                        f"({shape.shape[d]}) is not divisible by {e}="
                        f"{ways}; that dim stays unsharded")
                    entries[d] = None
            return P(*entries)

        return jax.tree_util.tree_map_with_path(leaf, specs, shapes,
                                                is_leaf=_is_spec)

    # -------------------------------------------------------------- batches
    def batch_axes(self) -> Tuple[str, ...]:
        """Mesh axes the batch (leading) dim shards over."""
        spec = self._specs.get("batch")
        if spec is not None:
            first = tuple(spec)[0] if tuple(spec) else None
            if first is None:
                return ()
            return tuple(first) if isinstance(first, (tuple, list)) else (first,)
        return tuple(a for a in (DATA_AXIS, MICS_AXIS, ICI_AXIS, EXPERT_AXIS)
                     if self.mesh.shape.get(a, 1) > 1)

    def batch_spec(self, ndim: int) -> P:
        """The registered batch spec clamped to an ``ndim``-rank leaf."""
        base = self._specs.get("batch")
        if base is None:
            axes = self.batch_axes()
            base = P(axes if axes else None)
        entries = tuple(base)[:ndim]
        return P(*(entries + (None,) * (ndim - len(entries))))

    def batch_sharding(self, ndim: int) -> NamedSharding:
        return self.named(self.batch_spec(ndim))

    def batch_shardings(self, batch: Any) -> Any:
        """Per-leaf NamedShardings for a host/device batch pytree."""
        return jax.tree.map(lambda x: self.batch_sharding(np.ndim(x)), batch)

    def ids_sharding(self, batch_size: Optional[int] = None) -> NamedSharding:
        """Token-id arrays of generation programs — (B, T) with B over the
        dp batch axes, T NEVER sequence-sharded (decode appends one token
        at a time; a seq-sharded T dim would reshard every step). A batch
        the dp world does not divide falls back to replicated — this jax
        refuses uneven device_put shardings — which stays EXPLICIT: the
        program still compiles with stated in/out placements."""
        axes = self.batch_axes()
        if not axes:
            return self.named(P())
        if batch_size is not None:
            world = int(np.prod([self.mesh.shape[a] for a in axes]))
            if batch_size % world != 0:
                return self.named(P())
        return self.named(P(axes))

    # ------------------------------------------------------------- KV cache
    def cache_shardings(self, module) -> Optional[Any]:
        """The module's KV-cache specs as NamedShardings over THE mesh —
        one derivation shared by the fused generate, the split
        prefill/decode pair, the serving tick programs and the hybrid
        engine (registered under ``kv_cache`` on first use)."""
        specs = self._specs.get("kv_cache")
        if specs is None:
            if not hasattr(module, "cache_partition_specs"):
                return None
            specs = module.cache_partition_specs()
            self._specs["kv_cache"] = specs
        return jax.tree.map(self.named, specs, is_leaf=_is_spec)

    # ------------------------------------------------------------ reporting
    def describe(self) -> str:
        from deepspeed_tpu.sharding.jit import describe_shardings
        from deepspeed_tpu.sharding.mesh import mesh_axes_string

        lines = [f"mesh: {mesh_axes_string(self.mesh)}"]
        for name in self.names():
            tree = jax.tree.map(lambda s: self.named(s), self._specs[name],
                                is_leaf=_is_spec)
            lines.append(f"  {name}: {describe_shardings(tree)}")
        return "\n".join(lines)
