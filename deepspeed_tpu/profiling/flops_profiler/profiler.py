"""FLOPs profiler — XLA-native model profiling.

Counterpart of the reference's ``profiling/flops_profiler/profiler.py``
(FlopsProfiler :23, ~1.2k LoC). The torch profiler monkey-patches
``torch.nn.functional`` to count MACs as ops execute; on TPU the compiler
already knows: we read exact flop/byte counts from XLA's cost analysis
(``jax.jit(fn).lower(...).compile().cost_analysis()``) and complement it with
a jaxpr walk that attributes matmul/conv flops to user ``jax.named_scope`` /
module names — the analogue of the reference's per-module tree printout.

No runtime overhead when disabled; profiling a step never perturbs it (the
analysis runs on the lowered program, not the execution).
"""

from __future__ import annotations

import sys
from collections import defaultdict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist, logger


# ----------------------------------------------------------------- formatting
def number_to_string(num, units=None, precision=2):
    if units is None:
        if num >= 1e12:
            return f"{num / 1e12:.{precision}f} T"
        if num >= 1e9:
            return f"{num / 1e9:.{precision}f} G"
        if num >= 1e6:
            return f"{num / 1e6:.{precision}f} M"
        if num >= 1e3:
            return f"{num / 1e3:.{precision}f} K"
        return f"{num:.{precision}f} "
    scale = {"T": 1e12, "G": 1e9, "M": 1e6, "K": 1e3, "": 1.0}[units]
    return f"{num / scale:.{precision}f} {units}"


def flops_to_string(flops, units=None, precision=2):
    return number_to_string(flops, units, precision) + "FLOPS"


def macs_to_string(macs, units=None, precision=2):
    return number_to_string(macs, units, precision) + "MACs"


def params_to_string(params_num, units=None, precision=2):
    return number_to_string(params_num, units, precision).rstrip() or "0"


def duration_to_string(duration, units=None, precision=2):
    if duration >= 1:
        return f"{duration:.{precision}f} s"
    if duration >= 1e-3:
        return f"{duration * 1e3:.{precision}f} ms"
    return f"{duration * 1e6:.{precision}f} us"


# ------------------------------------------------------------- jaxpr walking
_DOT_PRIMS = {"dot_general"}
_CONV_PRIMS = {"conv_general_dilated"}


def _dot_flops(eqn) -> int:
    """2*M*N*K for a dot_general, accounting for batch dims."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    dnums = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dnums
    batch = int(np.prod([lhs.shape[i] for i in lb], dtype=np.int64)) if lb else 1
    contract = int(np.prod([lhs.shape[i] for i in lc], dtype=np.int64)) if lc else 1
    m = int(np.prod([lhs.shape[i] for i in range(len(lhs.shape)) if i not in lc and i not in lb],
                    dtype=np.int64))
    n = int(np.prod([rhs.shape[i] for i in range(len(rhs.shape)) if i not in rc and i not in rb],
                    dtype=np.int64))
    return 2 * batch * m * n * contract


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    out_elems = int(np.prod(out.shape, dtype=np.int64))
    # per output element: 2 * (kernel spatial * in_channels / feature_group_count)
    kernel_elems = int(np.prod(rhs.shape, dtype=np.int64)) // max(1, rhs.shape[
        eqn.params["dimension_numbers"].rhs_spec[0]])
    return 2 * out_elems * kernel_elems


def _walk_jaxpr(jaxpr, scope: str, acc: Dict[str, int], totals: Dict[str, int],
                mult: int = 1):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        # a jax.named_scope (telemetry/scopes.py: attn, mlp, head ...) is in
        # the equation's name stack, relative to the jaxpr that holds it; a
        # pjit's own name in its `name` param
        name = "/".join(filter(None, (
            scope, str(eqn.source_info.name_stack))))
        if prim in ("pjit", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
                    "remat", "remat2", "checkpoint", "scan", "while", "cond", "closed_call",
                    "shard_map", "custom_partitioning"):
            sub_name = eqn.params.get("name", "")
            inner_scope = f"{name}/{sub_name}" if sub_name else name
            inner_mult = mult * int(eqn.params.get("length", 1)) if prim == "scan" else mult
            for key in ("jaxpr", "call_jaxpr", "branches", "cond_jaxpr", "body_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is None:
                    continue
                subs = sub if isinstance(sub, (tuple, list)) else [sub]
                for s in subs:
                    inner = getattr(s, "jaxpr", s)
                    _walk_jaxpr(inner, inner_scope, acc, totals, inner_mult)
            continue
        if prim in _DOT_PRIMS:
            f = _dot_flops(eqn) * mult
            acc[name] = acc.get(name, 0) + f
            totals["dot"] = totals.get("dot", 0) + f
        elif prim in _CONV_PRIMS:
            f = _conv_flops(eqn) * mult
            acc[name] = acc.get(name, 0) + f
            totals["conv"] = totals.get("conv", 0) + f


def count_jaxpr_flops(fn: Callable, *args, **kwargs) -> Tuple[int, Dict[str, int]]:
    """Matmul/conv flops of ``fn`` by jaxpr traversal (scan-aware).

    Returns (total_flops, per_scope dict). This is the *model math* count
    (the reference counts the same way — MACs of linears/convs/attention);
    XLA cost analysis additionally counts elementwise flops.
    """
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    acc: Dict[str, int] = {}
    totals: Dict[str, int] = {}
    _walk_jaxpr(jaxpr.jaxpr, "", acc, totals)
    return sum(totals.values()), acc


def extract_compiled_cost(compiled) -> Dict[str, float]:
    """flops / bytes_accessed of an already-compiled executable, from
    ``compiled.cost_analysis()`` — THE single extraction point shared by
    :func:`compiled_cost_analysis` (the ThroughputTimer's EstTFLOPs
    path) and ``analysis/roofline``'s live cross-check, so the two can
    never disagree on the same program. Degrades to zeros when the
    backend exposes no cost analysis."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
    except Exception as e:  # pragma: no cover - backend-dependent
        logger.warning(f"cost_analysis unavailable: {e}")
        ca = {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


def compiled_cost_analysis(fn: Callable, *args, static_argnums=(), **kwargs) -> Dict[str, float]:
    """Exact compiler-side counts: flops, bytes accessed, peak memory.

    The TPU answer to the reference's hand-maintained MODULE_HOOK_MAPPING —
    XLA already computed this for the real program it will run.
    """
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(*args, **kwargs)
    compiled = lowered.compile()
    out = extract_compiled_cost(compiled)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["peak_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0) or 0) + \
                float(getattr(mem, "argument_size_in_bytes", 0) or 0)
    except Exception:
        pass
    return out


def _count_params(params) -> int:
    return int(sum(np.prod(x.shape, dtype=np.int64) for x in jax.tree.leaves(params)
                   if hasattr(x, "shape")))


# ------------------------------------------------------------------ profiler
class FlopsProfiler:
    """Profile a jitted step function (reference FlopsProfiler profiler.py:23).

    Usage mirrors the reference: ``start_profile()`` before the step to
    profile, ``stop_profile()`` after, then ``print_model_profile()`` /
    accessors. The engine drives this automatically at
    ``flops_profiler.profile_step`` when enabled.
    """

    def __init__(self, model=None, ds_engine=None, recompute_fwd_factor: float = 0.0):
        self.model = model
        self.ds_engine = ds_engine
        self.recompute_fwd_factor = recompute_fwd_factor
        self.started = False
        self.flops = 0.0          # compiler flops of the profiled program
        self.macs = 0             # matmul/conv MACs (jaxpr count / 2)
        self.params = 0
        self.bytes_accessed = 0.0
        self.per_scope: Dict[str, int] = {}
        self.duration = 0.0

    def start_profile(self, ignore_list=None):
        self.started = True

    def profile_fn(self, fn: Callable, *args, params=None, duration: float = 0.0, **kwargs):
        math_flops, per_scope = count_jaxpr_flops(fn, *args, **kwargs)
        cost = compiled_cost_analysis(fn, *args, **kwargs)
        self.flops = cost.get("flops") or float(math_flops)
        self.macs = math_flops // 2
        self.bytes_accessed = cost.get("bytes_accessed", 0.0)
        self.per_scope = per_scope
        self.duration = duration
        if params is not None:
            self.params = _count_params(params)
        return self

    def stop_profile(self):
        self.started = False

    def reset_profile(self):
        self.flops = 0.0
        self.macs = 0
        self.params = 0
        self.per_scope = {}

    def end_profile(self):
        self.stop_profile()
        self.reset_profile()

    def get_total_flops(self, as_string=False):
        return flops_to_string(self.flops) if as_string else self.flops

    def get_total_macs(self, as_string=False):
        return macs_to_string(self.macs) if as_string else self.macs

    def get_total_params(self, as_string=False):
        return params_to_string(self.params) if as_string else self.params

    def get_total_duration(self, as_string=False):
        return duration_to_string(self.duration) if as_string else self.duration

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=1,
                            detailed=True, output_file=None):
        out = open(output_file, "w") if output_file else sys.stdout
        try:
            print("\n-------------------------- DeepSpeed-TPU Flops Profiler "
                  "--------------------------", file=out)
            print(f"Profile step:                   {profile_step}", file=out)
            print(f"Params:                         {params_to_string(self.params)}", file=out)
            print(f"MACs (matmul/conv):             {macs_to_string(self.macs)}", file=out)
            print(f"Compiled FLOPs (XLA):           {flops_to_string(self.flops)}", file=out)
            if self.bytes_accessed:
                print(f"Bytes accessed:                 {number_to_string(self.bytes_accessed)}B",
                      file=out)
                ai = self.flops / max(self.bytes_accessed, 1.0)
                print(f"Arithmetic intensity:           {ai:.1f} flops/byte", file=out)
            if self.duration > 0:
                print(f"Step latency:                   {duration_to_string(self.duration)}", file=out)
                print(f"Achieved:                       "
                      f"{flops_to_string(self.flops / self.duration)}", file=out)
            if detailed and self.per_scope:
                print("Per-scope matmul/conv flops:", file=out)
                ranked = sorted(self.per_scope.items(), key=lambda kv: -kv[1])
                for name, f in ranked[:max(top_modules, 1)]:
                    print(f"  {name or '<toplevel>':48s} {flops_to_string(f)}", file=out)
            print("--------------------------------------------------------------"
                  "-----------------\n", file=out)
        finally:
            if output_file:
                out.close()


def get_model_profile(model=None,
                      fn: Callable = None,
                      args=(),
                      kwargs=None,
                      params=None,
                      print_profile=True,
                      detailed=True,
                      module_depth=-1,
                      top_modules=1,
                      warm_up=1,
                      as_string=True,
                      output_file=None,
                      ignore_modules=None):
    """One-shot profiling (reference get_model_profile profiler.py:1100).

    ``fn(*args, **kwargs)`` is the forward; if ``model`` is given and has
    ``.apply``, fn defaults to it. Returns (flops, macs, params).
    """
    kwargs = kwargs or {}
    if fn is None:
        assert model is not None and hasattr(model, "apply"), \
            "pass fn= or a model with .apply"
        fn = model.apply
    prof = FlopsProfiler(model)
    prof.profile_fn(fn, *args, params=params, **kwargs)
    if print_profile:
        prof.print_model_profile(detailed=detailed, module_depth=module_depth,
                                 top_modules=top_modules, output_file=output_file)
    if as_string:
        return (prof.get_total_flops(True), prof.get_total_macs(True),
                prof.get_total_params(True))
    return prof.flops, prof.macs, prof.params
