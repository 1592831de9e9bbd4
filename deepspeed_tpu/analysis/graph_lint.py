"""Pass 1 — graph lint: jaxpr-level TPU-burning-bug detection.

In JAX the training computation is literally inspectable before any
compilation: ``jax.make_jaxpr`` abstract-traces the step (seconds, no
XLA) and the jaxpr carries every op, dtype, shape and source line. The
rules here flag the classes of bug that otherwise surface as a melted
TPU bill:

* ``graph/weak-scalar-input`` — a Python scalar passed as a step
  argument traces as a weak-typed 0-d aval. Weak avals are UNSTABLE:
  call sites that alternate a Python number with an array (or an
  explicitly-dtyped scalar) flip the aval and retrace+recompile the
  whole step, and the scalar's dtype follows promotion rules instead of
  the config. (The engine's own batch path is immune — ``_shard_batch``
  materializes every leaf as a strong-typed array — so this fires on
  user-built steps, where the alternation bug actually lives.)
* ``graph/dtype-promotion`` — a large ``dot_general``/conv running on
  fp32/f64 operands while the config says bf16/fp16: one stray fp32
  constant or ``astype`` upstream silently halves (or worse) MXU
  throughput. f64 anywhere under a low-precision config is flagged too.
* ``graph/missing-donation`` — a large input buffer (optimizer state,
  params) not donated to the step doubles peak HBM: XLA must keep the
  old tree alive next to the new one.
* ``sharding/replicated-large-array`` — the ZeRO stage promises
  partitioned state but the sharding plan leaves a large leaf fully
  replicated (e.g. a vocab dim coprime with the dp world): the memory
  savings silently evaporate. Linted against the mesh/topology layer.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from deepspeed_tpu.analysis.findings import Finding

RULE_WEAK_INPUT = "graph/weak-scalar-input"
RULE_DTYPE_PROMOTION = "graph/dtype-promotion"
RULE_DONATION = "graph/missing-donation"
RULE_REPLICATED = "sharding/replicated-large-array"
RULE_SHAPE_RETRACE = "graph/shape-varying-input"

# ops whose operand precision decides MXU throughput
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")
_LOW_PRECISION = (jnp.bfloat16, jnp.float16)
_WIDE = (jnp.float32, jnp.float64)


def _site(eqn) -> str:
    """file:line of the eqn's user-level call site (best effort)."""
    try:
        from jax._src import source_info_util

        return str(source_info_util.summarize(eqn.source_info))
    except Exception:
        return ""


def _sub_jaxprs(eqn):
    """Sub-jaxprs buried in an eqn's params (scan/while/cond/pjit/remat)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def _aval(var):
    return getattr(var, "aval", None)


def _elements(aval) -> int:
    try:
        return int(np.prod(aval.shape)) if aval.shape else 1
    except Exception:
        return 0


def lint_jaxpr(closed_jaxpr, *, train_dtype,
               min_promote_elements: int = 65536,
               what: str = "train step") -> List[Finding]:
    """Dtype-promotion + weak-input lint over a traced step.

    ``train_dtype`` is the config's compute dtype; promotion findings
    fire only under bf16/fp16 (an fp32 config is allowed fp32 math).
    """
    findings: List[Finding] = []
    seen: set = set()
    low_precision = any(jnp.dtype(train_dtype) == jnp.dtype(d)
                       for d in _LOW_PRECISION)
    cfg_name = jnp.dtype(train_dtype).name

    for i, aval in enumerate(closed_jaxpr.in_avals):
        if getattr(aval, "weak_type", False) and getattr(aval, "ndim", 1) == 0:
            findings.append(Finding(
                rule=RULE_WEAK_INPUT, severity="warning",
                message=(f"{what} argument {i} is a weak-typed Python scalar "
                         f"({aval.dtype}); its abstract value is unstable — "
                         "call sites that alternate a Python number with an "
                         "array retrace and recompile the whole step, and its"
                         " dtype follows promotion instead of the config — "
                         "pass an explicitly-dtyped jnp array (or bake the "
                         "constant into the function)"),
                citation=f"arg[{i}]", pass_name="graph"))

    if not low_precision:
        return findings

    for eqn in _walk_eqns(closed_jaxpr.jaxpr):
        prim = eqn.primitive.name
        # f64 under a low-precision config is always a bug on TPU
        for var in list(eqn.outvars):
            aval = _aval(var)
            if aval is not None and getattr(aval, "dtype", None) == jnp.float64:
                key = (RULE_DTYPE_PROMOTION, "f64", _site(eqn))
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        rule=RULE_DTYPE_PROMOTION, severity="error",
                        message=(f"op {prim} produces float64 under a "
                                 f"{cfg_name} config — f64 is emulated on "
                                 "TPU (double-digit slowdown); drop the f64 "
                                 "input or disable jax_enable_x64"),
                        citation=f"{prim} @ {_site(eqn)}", pass_name="graph"))
        if prim not in _MATMUL_PRIMS:
            continue
        operands = [_aval(v) for v in eqn.invars]
        wide = [a for a in operands
                if a is not None and getattr(a, "dtype", None) in
                tuple(jnp.dtype(d) for d in _WIDE)]
        if not wide:
            continue
        big = max((_elements(a) for a in operands if a is not None), default=0)
        if big < min_promote_elements:
            continue        # scalar/loss-path fp32 math is fine
        wdt = jnp.dtype(wide[0].dtype).name
        key = (RULE_DTYPE_PROMOTION, prim, _site(eqn))
        if key in seen:
            continue
        seen.add(key)
        shapes = [tuple(a.shape) for a in operands if a is not None]
        findings.append(Finding(
            rule=RULE_DTYPE_PROMOTION, severity="error",
            message=(f"{prim} runs on {wdt} operands {shapes} while the "
                     f"config compute dtype is {cfg_name} — a silent upcast "
                     "upstream (fp32 constant, .astype, numpy input) is "
                     "burning MXU throughput; cast the operand back to "
                     f"{cfg_name} or move the fp32 math off the hot path"),
            citation=f"{prim} @ {_site(eqn)}", pass_name="graph"))
    return findings


def lint_donation(args: Sequence[Any], donate_argnums: Sequence[int],
                  min_bytes: int = 64 << 20,
                  what: str = "train step") -> List[Finding]:
    """Peak-memory lint: large positional args not donated to the jitted
    step keep their old buffers alive next to the new ones."""
    findings: List[Finding] = []
    donated = set(donate_argnums)
    for i, arg in enumerate(args):
        if i in donated:
            continue
        nbytes = 0
        for leaf in jax.tree.leaves(arg):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            nbytes += int(np.prod(shape)) * jnp.dtype(dtype).itemsize
        if nbytes >= min_bytes:
            findings.append(Finding(
                rule=RULE_DONATION, severity="warning",
                message=(f"{what} argument {i} ({nbytes / 2**20:.0f} MiB) is "
                         "not donated — XLA keeps the old state tree alive "
                         "next to the updated one, doubling its peak HBM; "
                         f"add donate_argnums=({i},) if the caller never "
                         "reuses it"),
                citation=f"arg[{i}]", pass_name="graph"))
    return findings


def lint_sharding_plan(plan, param_shapes,
                       min_elements: Optional[int] = None) -> List[Finding]:
    """Sharding lint against the mesh/topology layer: a ZeRO stage >= 1
    promises dp-partitioned optimizer state (stage >= 3: params too); any
    large leaf whose spec touches no data-parallel axis quietly keeps its
    full replicated footprint on every chip."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.topology import unused_mesh_axes

    findings: List[Finding] = []
    stage = plan.zero_stage
    if stage < 1 or not plan.dp_axes:
        return findings
    if min_elements is None:
        min_elements = 100_000      # the stage-3 persistence default
    check = plan.param_specs if stage >= 3 else plan.master_specs
    what = "params+optimizer state" if stage >= 3 else "optimizer state"
    is_p = lambda x: isinstance(x, P) or x is None
    shapes_flat = jax.tree_util.tree_flatten_with_path(
        param_shapes, is_leaf=lambda x: x is None)[0]
    specs_flat = jax.tree_util.tree_flatten_with_path(check, is_leaf=is_p)[0]
    for (path, sh), (_, sp) in zip(shapes_flat, specs_flat):
        if sh is None:
            continue
        n = int(np.prod(sh.shape))
        if n < min_elements:
            continue
        # the replication set of this placement: mesh axes (size > 1) the
        # spec leaves unused — partitioned state must use SOME dp axis
        free = unused_mesh_axes(sp, len(sh.shape), plan.mesh)
        if not all(a in free for a in plan.dp_axes):
            continue
        name = "/".join(str(p) for p in path)
        findings.append(Finding(
            rule=RULE_REPLICATED, severity="warning",
            message=(f"ZeRO stage {stage}: {what} for param {name} "
                     f"(shape {tuple(sh.shape)}, {n / 1e6:.1f}M elements) "
                     f"stays replicated over dp axes "
                     f"{[f'{a}={plan.mesh.shape[a]}' for a in plan.dp_axes]}"
                     " — no dim is divisible by the dp world; pad the "
                     "offending dim to recover the ZeRO memory savings"),
            citation=f"param {name}", pass_name="sharding"))
    return findings


def diff_batch_shapes(first: Dict[str, Tuple], batch) -> List[Finding]:
    """Recompilation hazard: a batch whose leaf shapes differ from the
    first-seen batch recompiles the whole step program. ``first`` is the
    {leaf-path: shape} map captured at the first step."""
    findings: List[Finding] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(batch)[0]:
        name = "/".join(str(p) for p in path)
        shape = tuple(getattr(leaf, "shape", ()))
        prev = first.get(name)
        if prev is not None and prev != shape:
            findings.append(Finding(
                rule=RULE_SHAPE_RETRACE, severity="warning",
                message=(f"batch leaf {name} changed shape {prev} -> {shape} "
                         "— every distinct shape compiles a NEW step program "
                         "(pad or bucket your batches to a fixed set of "
                         "shapes)"),
                citation=f"batch {name}", pass_name="graph"))
    return findings


def batch_shape_map(batch) -> Dict[str, Tuple]:
    return {"/".join(str(p) for p in path): tuple(getattr(leaf, "shape", ()))
            for path, leaf in jax.tree_util.tree_flatten_with_path(batch)[0]}
