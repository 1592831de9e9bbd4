"""ds_xray — post-GSPMD static analysis of every compiled engine program.

Every hard multichip bug so far lived BELOW the jaxpr the ds_doctor graph
pass lints: the RLHF ``generate()`` deadlock was XLA choosing a collective
device order the train step disagreed with, replicated-large-array leaks
and dropped donations are decisions GSPMD makes AFTER tracing. The
``sharded_jit`` program table (PR 12) names every compiled program with
its promise — mesh, in/out specs, donation — and keeps enough captured
abstract arguments to AOT lower+compile each one again (no execution,
the same ``memory_analysis``/compile-cache path ``aot_memory_analysis``
uses). This module compiles each table entry, parses the compiled HLO
into the :mod:`~deepspeed_tpu.analysis.hlo_model` structures, and runs
four passes over the result:

* ``xray/collective-order`` — cross-program compatibility: two programs
  over the same devices whose collective device orders (or same-size
  replica-group partitions, for programs GSPMD had placement freedom
  over) can interleave into a rendezvous mismatch — the rc=134 class,
  now a permanent lint instead of a fixed bug;
* ``xray/promise-vs-actual`` — GSPMD's actual per-buffer shardings
  diffed against the recorded promise, plus the ZeRO-stage semantic
  check (a stage that promises dp-partitioned state whose compiled
  buffers are replicated is a silent memory-savings leak the jaxpr
  pass structurally cannot see);
* ``xray/donation-dropped`` — declared donations that produced NO
  input-output alias in the executable: silent 2× HBM;
* ``xray/static-comm`` — per-program wire bytes per collective kind
  (ring model) + a bus-seconds estimate; the number perf-ledger
  entries carry as ``static_comm_bytes`` and
  ``ds_perf gate --metric static_comm_bytes`` regresses on.

Cost: one AOT compile per analyzed program (seconds each on the CPU
mesh) — which is why the engine runs this pass only when ``"xray"`` is
EXPLICITLY listed in ``analysis.passes``, after the first train_batch
(the table must hold compiled programs first).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.analysis.findings import Finding
from deepspeed_tpu.analysis.hlo_model import (HloModel, estimate_bus_seconds,
                                              parse_hlo_module)

RULE_COLLECTIVE_ORDER = "xray/collective-order"
RULE_PROMISE = "xray/promise-vs-actual"
RULE_DONATION_DROPPED = "xray/donation-dropped"
RULE_STATIC_COMM = "xray/static-comm"

# default per-link bus bandwidth for the bus-seconds estimate: one v5e
# ICI link direction (~4.5e10 B/s). An ESTIMATE for ranking/regression
# only — the gate compares bytes, which are exact.
DEFAULT_BUS_BYTES_PER_S = 4.5e10


# --------------------------------------------------------------- per program
@dataclasses.dataclass
class ProgramXray:
    """One program's compiled truth, next to its recorded promise."""

    label: str
    record: Any                               # sharding.jit.ProgramRecord
    model: HloModel
    device_order: Tuple[int, ...]             # physical ids, assignment order
    in_leaves: List[Tuple[str, Any, Any, Any]]   # (path, aval, promise, actual)
    out_leaves: List[Tuple[str, Any, Any, Any]]
    arg_leaf_ranges: List[Tuple[int, int]]    # flat param range per argnum
    comm_by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)
    total_comm_bytes: int = 0

    def resolved_groups(self):
        """Replica groups of every collective, resolved from partition
        ids to PHYSICAL device ids through the program's assignment —
        the identity two programs must agree on to rendezvous."""
        n = len(self.device_order)
        for op in self.model.collectives:
            for g in op.replica_groups:
                if all(0 <= p < n for p in g):
                    yield op, tuple(self.device_order[p] for p in g)

    def state_families(self):
        """(family, path, aval, promise, actual) rows of the state
        argument's leaves — family names resolved through the call
        site's ``meta={"state_argnum": i, "state_fields": [...]}`` tags
        (TrainState is a NamedTuple: tree paths are INDICES, the meta
        carries the field names)."""
        meta = self.record.meta or {}
        argnum = meta.get("state_argnum")
        if argnum is None or argnum >= len(self.arg_leaf_ranges):
            return
        fields = list(meta.get("state_fields") or ())
        lo, hi = self.arg_leaf_ranges[argnum]
        prefix = f"arg{argnum}."
        for path, aval, prom, actual in self.in_leaves[lo:hi]:
            rel = path[len(prefix):] if path.startswith(prefix) else path
            head = rel.split("/", 1)[0]
            family = head
            if fields:
                try:
                    family = fields[int(head)]
                except (ValueError, IndexError):
                    pass
            yield family, rel, aval, prom, actual

    def family_sharding(self) -> Dict[str, Dict[str, Any]]:
        """Per-family actual-sharding summary for the state argument:
        leaf count, how many leaves are actually partitioned, and the
        smallest shard factor among non-tiny leaves (1 = a replicated
        buffer is present)."""
        out: Dict[str, Dict[str, Any]] = {}
        for family, _rel, aval, _prom, actual in self.state_families():
            fam = out.setdefault(family, {"leaves": 0, "sharded_leaves": 0,
                                          "min_factor": None})
            fam["leaves"] += 1
            factor = _shard_factor(aval, actual) if actual is not None else 1
            if factor > 1:
                fam["sharded_leaves"] += 1
            if _num_elements(aval) >= 4096:   # step counters don't vote
                fam["min_factor"] = (factor if fam["min_factor"] is None
                                     else min(fam["min_factor"], factor))
        return out


def _num_elements(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()) or ():
        n *= int(d)
    return n


def _shard_factor(aval, sharding) -> int:
    """global elements / per-shard elements under ``sharding`` (1 =
    replicated)."""
    try:
        shape = tuple(aval.shape)
        shard = sharding.shard_shape(shape)
        num, den = 1, 1
        for g, s in zip(shape, shard):
            num *= int(g)
            den *= int(s)
        return max(1, num // max(1, den))
    except Exception:
        return 1


def _leaf_bytes(aval) -> int:
    try:
        import numpy as np

        return _num_elements(aval) * int(np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0


def _spec_axes(sharding) -> Tuple[str, ...]:
    """Mesh axis names a NamedSharding's spec actually uses."""
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return ()
    axes: List[str] = []
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(str(a) for a in entry)
        else:
            axes.append(str(entry))
    return tuple(axes)


def _device_order_of(shardings_leaves) -> Tuple[int, ...]:
    """Physical device ids in assignment order, read off the compiled
    shardings (a NamedSharding carries its mesh; a GSPMD sharding its
    ``_device_assignment``)."""
    for leaf in shardings_leaves:
        mesh = getattr(leaf, "mesh", None)
        if mesh is not None:
            try:
                return tuple(int(d.id) for d in mesh.devices.flat)
            except Exception:
                pass
        da = getattr(leaf, "_device_assignment", None)
        if da:
            try:
                return tuple(int(d.id) for d in da)
            except Exception:
                pass
    return ()


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "name",
                                                   getattr(p, "idx", p)))))
    return "/".join(parts)


def _flatten_with_promise(arg_aval, promise):
    """Flatten one argument's aval tree next to its promise (prefix)
    tree: a promise that is a single sharding broadcasts to every leaf;
    a promise tree flattens alongside. A ``None`` inside the promise is
    ambiguous — an empty subtree (``TrainState.scaler=None``, which the
    AVAL flatten also drops) or an explicit per-leaf "inherit" — so
    alignment is tried with Nones kept first, then with them dropped
    (the empty-subtree case), and falls back to no-promises on a
    residual mismatch rather than mispairing."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(arg_aval)[0]
    is_sh = lambda x: x is None or hasattr(x, "spec") or hasattr(x, "devices")
    if promise is None:
        proms = [None] * len(leaves)
    elif is_sh(promise) and not isinstance(promise, (dict, list, tuple)):
        proms = [promise] * len(leaves)
    else:
        flat = jax.tree_util.tree_flatten(promise, is_leaf=is_sh)[0]
        if len(flat) == len(leaves):
            proms = list(flat)
        else:
            nonone = [x for x in flat if x is not None]
            proms = (nonone if len(nonone) == len(leaves)
                     else [None] * len(leaves))
    return [(_path_str(p), a, pr) for (p, a), pr in zip(leaves, proms)]


# ------------------------------------------------------------------ compile
def xray_program(record) -> Tuple[Optional[ProgramXray], List[Finding]]:
    """AOT lower+compile one program record and build its xray. Returns
    ``(None, findings)`` when the record cannot be analyzed (never
    dispatched, or lowering failed) — an info finding says why."""
    import jax

    label = record.label
    if not record.can_lower():
        why = ("was registered but never dispatched — nothing captured"
               if record.abstract_args is None else
               "has been garbage-collected (one-shot program whose "
               "handle was dropped; the table holds only a weak "
               "reference so dead engines are not pinned)")
        return None, [Finding(
            rule=RULE_STATIC_COMM, severity="info",
            message=f"program {label!r} {why} — skipped",
            citation=record.call_site, pass_name="xray")]
    out_tree = None
    try:
        import contextlib

        compiled = record.compiled()        # the door's one lower-and-compile
        with record.mesh if record.mesh is not None \
                else contextlib.nullcontext():
            try:
                out_tree = jax.eval_shape(record.jitted,
                                          *record.abstract_args,
                                          **(record.abstract_kwargs or {}))
            except Exception:
                out_tree = None
        text = compiled.as_text()
    except Exception as e:
        return None, [Finding(
            rule=RULE_STATIC_COMM, severity="info",
            message=(f"program {label!r} could not be AOT re-lowered for "
                     f"x-ray ({type(e).__name__}: {e})"),
            citation=record.call_site, pass_name="xray")]
    model = parse_hlo_module(text)

    try:
        in_sh, kw_sh = compiled.input_shardings
    except Exception:
        in_sh, kw_sh = None, None
    try:
        out_sh = compiled.output_shardings
    except Exception:
        out_sh = None

    in_leaves: List[Tuple[str, Any, Any, Any]] = []
    ranges: List[Tuple[int, int]] = []
    args = record.abstract_args or ()
    promises = record.in_shardings
    for i, arg in enumerate(args):
        start = len(in_leaves)
        promise_i = None
        if isinstance(promises, (tuple, list)) and i < len(promises):
            promise_i = promises[i]
        rows = _flatten_with_promise(arg, promise_i)
        actual_i = None
        if isinstance(in_sh, (tuple, list)) and i < len(in_sh):
            actual_i = in_sh[i]
        actual_flat = (jax.tree_util.tree_flatten(actual_i)[0]
                       if actual_i is not None else [])
        if len(actual_flat) != len(rows):
            actual_flat = [None] * len(rows)
        for (path, aval, prom), act in zip(rows, actual_flat):
            in_leaves.append((f"arg{i}.{path}" if path else f"arg{i}",
                              aval, prom, act))
        ranges.append((start, len(in_leaves)))

    out_leaves: List[Tuple[str, Any, Any, Any]] = []
    out_avals = (jax.tree_util.tree_flatten_with_path(out_tree)[0]
                 if out_tree is not None else [])
    out_flat = (jax.tree_util.tree_flatten(out_sh)[0]
                if out_sh is not None else [])
    prom_out = (jax.tree_util.tree_flatten(
        record.out_shardings,
        is_leaf=lambda x: x is None or hasattr(x, "spec"))[0]
        if record.out_shardings is not None else [])
    for k, (path, aval) in enumerate(out_avals):
        act = out_flat[k] if k < len(out_flat) else None
        prom = prom_out[k] if len(prom_out) == len(out_avals) else None
        out_leaves.append((_path_str(path), aval, prom, act))

    order = _device_order_of(
        [a for *_x, a in in_leaves if a is not None]
        + [a for *_x, a in out_leaves if a is not None])
    if not order:
        try:
            n = model.num_partitions
            order = tuple(range(n))
        except Exception:
            order = ()

    xr = ProgramXray(label=label, record=record, model=model,
                     device_order=order, in_leaves=in_leaves,
                     out_leaves=out_leaves, arg_leaf_ranges=ranges)
    xr.comm_by_kind = comm_by_kind_hostaware(xr)
    xr.total_comm_bytes = sum(xr.comm_by_kind.values())
    return xr, []


def _op_intra_host(op, device_order, host_groups) -> bool:
    """Does this collective stay inside ONE host group? Replica groups are
    spelled in partition ids; the program's device assignment maps them to
    physical ids, which the host sets classify. Anything unmappable (or a
    group/pair crossing hosts) counts as inter-host."""
    n = len(device_order)

    def within(ids) -> bool:
        ids = set(ids)
        return any(ids <= hs for hs in host_groups)

    saw = False
    for g in op.replica_groups:
        if not all(0 <= p < n for p in g):
            return False
        if not within(device_order[p] for p in g):
            return False
        saw = True
    for a, b in op.source_target_pairs:
        if not (0 <= a < n and 0 <= b < n):
            return False
        if not within((device_order[a], device_order[b])):
            return False
        saw = True
    return saw


def comm_by_kind_hostaware(xr: "ProgramXray") -> Dict[str, int]:
    """Per-kind wire bytes split by host: on a mesh that encodes host
    structure (the ``ici`` sub-axis, or a real multi-process run —
    :func:`~deepspeed_tpu.sharding.mesh.host_device_groups`), collectives
    confined to one host group land under ``<kind>/intra`` while
    everything crossing hosts keeps the plain kind — so "all-gather +
    reduce-scatter" reads as INTER-host wire bytes, and meshes without
    host structure keep the flat accounting."""
    from deepspeed_tpu.analysis.hlo_model import collective_wire_bytes
    from deepspeed_tpu.sharding.mesh import host_device_groups

    try:
        hg = host_device_groups(getattr(xr.record, "mesh", None))
    except Exception:
        hg = None
    if not hg or len(hg) < 2:
        return xr.model.comm_bytes_by_kind()
    out: Dict[str, int] = {}
    for op in xr.model.collectives:
        b = collective_wire_bytes(op)
        if not b:
            continue
        kind = op.kind
        if _op_intra_host(op, xr.device_order, hg):
            kind = f"{kind}/intra"
        out[kind] = out.get(kind, 0) + b
    return out


def inter_host_bytes(by_kind: Dict[str, int],
                     kinds=("all-gather", "reduce-scatter")) -> int:
    """Sum of the named kinds' INTER-host wire bytes (the ``/intra``
    entries excluded)."""
    return sum(v for k, v in by_kind.items() if k in kinds)


# ------------------------------------------------------- pass 1: order lint
def lint_collective_order(xrays: Sequence[ProgramXray]) -> List[Finding]:
    """Cross-program rendezvous compatibility.

    (a) Two programs over the SAME device set whose device assignments
    ORDER those devices differently — and both actually launch
    collectives — can interleave into a rendezvous mismatch: each
    program's replica groups are spelled in partition ids, so the same
    group text means different physical cliques. This is the compiled
    signature of the RLHF ``generate()`` deadlock (a program that
    inherited placement from operands committed to a differently-
    ordered mesh).

    (b) A program GSPMD had placement freedom over (inherited in/out)
    whose resolved replica groups conflict with the groups the fully-
    specified programs established on those devices: same members in a
    different order, or a same-size group that CROSSES an established
    one (overlapping, neither nested — two different partitions at one
    granularity cannot both be the mesh's axis structure)."""
    findings: List[Finding] = []
    with_colls = [x for x in xrays
                  if x.model.collectives and len(x.device_order) > 1]
    # ---- (a) device-assignment order conflicts, pairwise per device set.
    # Programs of DIFFERENT mesh generations never compare: sequential
    # jobs on rebuilt meshes (the multichip dryrun runs five topologies
    # back to back) are legitimate — only programs that can actually
    # interleave (one generation, one device set) must agree.
    by_set: Dict[tuple, List[ProgramXray]] = {}
    for x in with_colls:
        by_set.setdefault((x.record.generation,
                           frozenset(x.device_order)), []).append(x)
    for (_gen, devset), group in by_set.items():
        if len(devset) < 2:
            continue
        baseline = group[0]
        for other in group[1:]:
            if other.device_order != baseline.device_order:
                bop = baseline.model.collectives[0]
                oop = other.model.collectives[0]
                findings.append(Finding(
                    rule=RULE_COLLECTIVE_ORDER, severity="error",
                    message=(
                        f"programs {baseline.label!r} and {other.label!r} "
                        f"run collectives over the same {len(devset)} "
                        "device(s) with DIFFERENT device-assignment orders "
                        f"({list(baseline.device_order)} vs "
                        f"{list(other.device_order)}); their replica groups "
                        f"({baseline.label}: {bop.kind} "
                        f"{bop.describe_groups()}; {other.label}: {oop.kind} "
                        f"{oop.describe_groups()}) rendezvous as different "
                        "physical cliques — interleaved dispatch deadlocks "
                        "(the MULTICHIP_r05 rc=134 class); compile both "
                        "against THE global mesh with explicit shardings"),
                    citation=other.record.call_site, pass_name="xray"))
    # ---- (b) freedom-program partitions vs the established contract
    for (_gen, devset), group in by_set.items():
        if len(devset) < 2:
            continue
        established: Dict[Tuple[int, ...], str] = {}
        for x in group:
            rec = x.record
            if rec.inherited_in or rec.inherited_out:
                continue
            for _op, g in x.resolved_groups():
                established.setdefault(g, x.label)
        if not established:
            continue
        est_sets = {frozenset(g): (g, label)
                    for g, label in established.items()}
        for x in group:
            rec = x.record
            if not (rec.inherited_in or rec.inherited_out):
                continue
            flagged = set()
            for op, g in x.resolved_groups():
                if g in established or len(g) < 2:
                    continue
                gset = frozenset(g)
                key = (op.kind, gset)
                if key in flagged:
                    continue
                if gset in est_sets:
                    eg, elabel = est_sets[gset]
                    flagged.add(key)
                    findings.append(Finding(
                        rule=RULE_COLLECTIVE_ORDER, severity="error",
                        message=(
                            f"program {x.label!r} (GSPMD-chosen placement) "
                            f"launches {op.kind} over devices {list(g)} "
                            f"while {elabel!r} established the same group "
                            f"as {list(eg)} — same clique, different "
                            "rendezvous order (rc=134 class); state "
                            "explicit in/out shardings on the global mesh"),
                        citation=rec.call_site, pass_name="xray"))
                    continue
                for eset, (eg, elabel) in est_sets.items():
                    if len(eset) != len(gset):
                        continue
                    if gset & eset and gset != eset \
                            and not (gset < eset or eset < gset):
                        flagged.add(key)
                        findings.append(Finding(
                            rule=RULE_COLLECTIVE_ORDER, severity="error",
                            message=(
                                f"program {x.label!r} (GSPMD-chosen "
                                f"placement) partitions devices as "
                                f"{op.kind} {op.describe_groups()} "
                                f"-> {list(g)}, CROSSING the group "
                                f"{list(eg)} program {elabel!r} "
                                "established at the same size — two "
                                "conflicting partitions of one device set "
                                "cannot both follow the mesh axes; "
                                "interleaved dispatch can rendezvous-"
                                "mismatch (rc=134 class)"),
                            citation=rec.call_site, pass_name="xray"))
                        break
    return findings


# --------------------------------------------- pass 2: promise vs actual
def lint_promise_vs_actual(xrays: Sequence[ProgramXray],
                           plan=None,
                           min_elements: int = 100_000) -> List[Finding]:
    """Recorded promise vs compiled actual, per buffer — plus the ZeRO
    semantic check when a sharding ``plan`` is given: families the stage
    promises dp-partitioned (stage>=1: master/opt_state; stage>=3:
    params too) whose compiled buffers stay replicated."""
    findings: List[Finding] = []
    for x in xrays:
        if len(x.device_order) <= 1:
            continue
        for where, leaves in (("in", x.in_leaves), ("out", x.out_leaves)):
            for path, aval, prom, act in leaves:
                if prom is None or act is None:
                    continue
                if _num_elements(aval) < min_elements:
                    continue
                try:
                    shape = tuple(aval.shape)
                    if prom.shard_shape(shape) == act.shard_shape(shape):
                        continue
                except Exception:
                    continue
                findings.append(Finding(
                    rule=RULE_PROMISE, severity="error",
                    message=(
                        f"program {x.label!r} {where}put {path} "
                        f"(shape {tuple(aval.shape)}): the recorded promise "
                        f"{getattr(prom, 'spec', prom)} compiled to actual "
                        f"{getattr(act, 'spec', act)} — GSPMD did not "
                        "honor the registry spec this call site stated"),
                    citation=x.record.call_site, pass_name="xray"))
        # ---- ZeRO family semantics on the state argument
        meta = x.record.meta or {}
        if plan is None or meta.get("state_argnum") is None:
            continue
        stage = getattr(plan, "zero_stage", 0)
        dp_axes = tuple(getattr(plan, "dp_axes", ()) or ())
        if stage < 1 or not dp_axes:
            continue
        want = {"master", "opt_state"} | ({"params"} if stage >= 3 else set())
        for family, path, aval, _prom, act in x.state_families():
            if family not in want or act is None:
                continue
            if _num_elements(aval) < min_elements:
                continue
            axes = _spec_axes(act)
            if any(a in axes for a in dp_axes):
                continue
            findings.append(Finding(
                rule=RULE_PROMISE, severity="error",
                message=(
                    f"ZeRO stage {stage} promises {family} dp-partitioned "
                    f"over {list(dp_axes)}, but program {x.label!r} "
                    f"compiled {path} (shape {tuple(aval.shape)}, "
                    f"{_leaf_bytes(aval) / 2**20:.1f} MiB global) with "
                    f"actual sharding {getattr(act, 'spec', act)} — the "
                    "buffer is fully replicated in the executable; the "
                    "ZeRO memory savings silently evaporated (registry "
                    "spec regression or call-site override)"),
                citation=x.record.call_site, pass_name="xray"))
    return findings


# ------------------------------------------------- pass 3: donation audit
def lint_donation_compiled(xrays: Sequence[ProgramXray],
                           min_bytes: int = 1 << 20) -> List[Finding]:
    """Declared donations that produced no alias in the executable.

    This is the compiled-alias-table rebase of the donation story: the
    jaxpr-level ``graph/missing-donation`` heuristic stays the
    no-compile fallback (run_doctor uses it only when no compiled table
    is in reach), while here the executable itself says which donated
    buffers actually alias. A donated argument whose large leaves all
    miss the alias table is paying 2× HBM silently — usually a dtype/
    layout change between the donated input and every output."""
    findings: List[Finding] = []
    for x in xrays:
        donated = set(x.record.donate or ())
        if not donated:
            continue
        aliased = x.model.aliased_parameters()
        pbytes = x.model.parameter_bytes
        for argnum in sorted(donated):
            if argnum >= len(x.arg_leaf_ranges):
                continue
            lo, hi = x.arg_leaf_ranges[argnum]
            if len(pbytes) < hi:
                continue   # parameter count disagrees — don't guess
            dropped = [(i, pbytes[i]) for i in range(lo, hi)
                       if i not in aliased and pbytes[i] >= min_bytes]
            if not dropped:
                continue
            total = sum(b for _, b in dropped)
            names = []
            for i, b in dropped[:3]:
                path = x.in_leaves[i][0] if i < len(x.in_leaves) else f"p{i}"
                names.append(f"{path} ({b / 2**20:.1f} MiB)")
            findings.append(Finding(
                rule=RULE_DONATION_DROPPED, severity="warning",
                message=(
                    f"program {x.label!r} declares donate_argnums="
                    f"({argnum},) but {len(dropped)} donated buffer(s) "
                    f"totalling {total / 2**20:.1f} MiB/device produced NO "
                    f"input-output alias in the executable ({', '.join(names)}"
                    + (", …" if len(dropped) > 3 else "")
                    + ") — XLA keeps old and new alive together (silent 2× "
                    "HBM); usually a dtype or layout change between the "
                    "donated input and every output of matching shape"),
                citation=x.record.call_site, pass_name="xray"))
    return findings


# -------------------------------------------------- pass 4: static comm
def static_comm_table(xrays: Sequence[ProgramXray],
                      bus_bytes_per_s: float = DEFAULT_BUS_BYTES_PER_S
                      ) -> Dict[str, Dict[str, Any]]:
    """{label: {total_bytes, by_kind, collectives, est_bus_us}} — the
    hardware-free comm bill per program."""
    out: Dict[str, Dict[str, Any]] = {}
    for x in xrays:
        out[x.label] = {
            "total_bytes": x.total_comm_bytes,
            "by_kind": dict(x.comm_by_kind),
            "collectives": len(x.model.collectives),
            "est_bus_us": round(1e6 * estimate_bus_seconds(
                x.total_comm_bytes, bus_bytes_per_s), 1),
        }
    return out


# ------------------------------------------------------------------ driver
@dataclasses.dataclass
class XrayResult:
    xrays: List[ProgramXray]
    findings: List[Finding]
    comm: Dict[str, Dict[str, Any]]

    def program(self, label_prefix: str) -> Optional[ProgramXray]:
        for x in self.xrays:
            if x.label.startswith(label_prefix):
                return x
        return None

    def render(self) -> str:
        lines = [f"ds_xray: {len(self.xrays)} program(s) analyzed, "
                 f"{len(self.findings)} finding(s)"]
        for x in sorted(self.xrays, key=lambda x: x.label):
            c = self.comm.get(x.label, {})
            lines.append(
                f"  {x.label}  [{x.record.mesh_axes}]  "
                f"collectives={c.get('collectives', 0)}  "
                f"comm={c.get('total_bytes', 0) / 2**20:.2f} MiB/dev/step  "
                f"est_bus={c.get('est_bus_us', 0.0):.0f} µs")
            for kind, b in sorted((c.get("by_kind") or {}).items()):
                lines.append(f"      {kind:<20} {b / 2**20:9.2f} MiB")
            fams = x.family_sharding()
            for fam in sorted(fams):
                f = fams[fam]
                lines.append(
                    f"      {fam}: {f['sharded_leaves']}/{f['leaves']} "
                    "leaves partitioned"
                    + (f", min shard factor 1/{f['min_factor']}"
                       if f.get("min_factor") else ""))
        return "\n".join(lines)


def run_xray(records=None, plan=None, *,
             min_replicated_elements: int = 100_000,
             min_donate_bytes: int = 1 << 20,
             bus_bytes_per_s: float = DEFAULT_BUS_BYTES_PER_S) -> XrayResult:
    """X-ray every analyzable program of the process-global table (or an
    explicit record list). Pure analysis: no execution, one AOT compile
    per program."""
    if records is None:
        from deepspeed_tpu.sharding import program_table

        records = list(program_table().values())
    findings: List[Finding] = []
    xrays: List[ProgramXray] = []
    for rec in sorted(records, key=lambda r: r.label):
        xr, fs = xray_program(rec)
        findings.extend(fs)
        if xr is not None:
            xrays.append(xr)
    findings.extend(lint_collective_order(xrays))
    findings.extend(lint_promise_vs_actual(
        xrays, plan=plan, min_elements=min_replicated_elements))
    findings.extend(lint_donation_compiled(xrays,
                                           min_bytes=min_donate_bytes))
    return XrayResult(xrays=xrays, findings=findings,
                      comm=static_comm_table(xrays, bus_bytes_per_s))


def static_comm_for_engine(engine) -> Optional[Dict[str, Any]]:
    """THIS engine's train program's static comm bill, for perf-ledger
    attribution — {static_comm_bytes, by_kind, collectives, est_bus_us}
    or None.

    The program is matched to the engine (its configured gas and its
    mesh object), newest registration first — the table is process-
    global and may hold train programs of other engines or earlier gas
    configurations. Single-device meshes short-circuit to zero bytes
    WITHOUT paying the AOT compile (no partitions ⇒ no collectives by
    construction), so a one-device entry still carries the key. The
    bill is deterministic per compiled program, so it is memoized on the
    record: a loop recording N perf entries pays the AOT compile once,
    not N times."""
    from deepspeed_tpu.sharding import program_table
    from deepspeed_tpu.sharding.mesh import mesh_axes_string

    mesh = getattr(engine, "mesh", None)
    gas = getattr(getattr(engine, "_config", None),
                  "gradient_accumulation_steps", None)
    candidates = [rec for rec in program_table().values()
                  if rec.label.startswith("engine/train_batch")
                  and rec.can_lower()]
    # newest registration last in dict order; require this engine's mesh
    # object, prefer its configured gas
    train = None
    for rec in reversed(candidates):
        if rec.mesh is not mesh:
            continue
        if gas is not None and f"[gas={gas}]" not in rec.label:
            train = train or rec
            continue
        train = rec
        break
    if train is None:
        # no train program of THIS engine's mesh: report a missing
        # measurement (gate exit 3) instead of stamping another engine's
        # or topology's bill into this entry
        return None
    if mesh_axes_string(mesh) == "single-device":
        return {"static_comm_bytes": 0, "by_kind": {},
                "inter_gather_scatter_bytes": 0, "collectives": 0,
                "est_bus_us": 0.0, "program": train.label}
    cached = getattr(train, "_static_comm_cache", None)
    if cached is not None:
        return dict(cached)
    xr, _ = xray_program(train)
    if xr is None:
        return None
    bill = {"static_comm_bytes": xr.total_comm_bytes,
            "by_kind": dict(xr.comm_by_kind),
            "inter_gather_scatter_bytes": inter_host_bytes(xr.comm_by_kind),
            "collectives": len(xr.model.collectives),
            "est_bus_us": round(1e6 * estimate_bus_seconds(
                xr.total_comm_bytes, DEFAULT_BUS_BYTES_PER_S), 1),
            "program": train.label}
    train._static_comm_cache = dict(bill)
    return bill


# ----------------------------------------------------------------- fixtures
def xray_for_config(config, model: str = "gpt2", *, batch_size=None,
                    seq_len: int = 32) -> XrayResult:
    """Build a family-fixture engine from a ds_config, run ONE
    train_batch to populate the program table, and x-ray it — the
    ``bin/ds_doctor xray`` / ``ds_report xray`` path. The config must be
    a complete ds_config (train_batch_size, optimizer); the model is a
    registry family or preset name."""
    import json as _json

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.analysis.doctor import _family_tiny
    from deepspeed_tpu.models.registry import resolve_family

    if isinstance(config, str):
        with open(config) as f:
            config = _json.load(f)
    preset = _family_tiny(model)
    model_cls, make_batch, presets = resolve_family(preset)
    if preset not in presets:
        preset = sorted(presets)[0]
    mcfg = presets[preset]
    engine, _, _, _ = deepspeed_tpu.initialize(model=model_cls(mcfg),
                                               config=dict(config))
    bs = batch_size or engine.train_batch_size()
    seq_len = min(seq_len, mcfg.n_positions)
    batch = make_batch(bs, seq_len, mcfg.vocab_size)
    engine.train_batch(batch)
    acfg = engine._config.analysis
    present = engine._config.analysis_present
    return run_xray(plan=getattr(engine, "plan", None),
                    min_replicated_elements=(
                        acfg.min_replicated_elements if present else 100_000),
                    min_donate_bytes=(
                        acfg.min_donate_bytes if present else 1 << 20))


def multichip_precheck(n_devices: int = 8) -> int:
    """Static precursor to the multichip gate: compile the historically
    deadlock-prone program PAIR — dp×tp ZeRO-3 train step + RLHF hybrid
    ``generate()`` — on the simulated mesh and x-ray the table. A
    collective-order (or any error-severity) finding fails in seconds,
    before the full 8-device dryrun spends minutes reaching its rc=134.
    Run in a fresh process with the device count forced (ds_multichip
    sets XLA_FLAGS before this import)."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model,
                                           synthetic_lm_batch)

    tp = 2 if n_devices % 2 == 0 else 1
    dp = n_devices // tp
    cfg = GPT2Config(vocab_size=256, n_positions=96, n_embd=64, n_layer=2,
                     n_head=4, remat=False, use_flash_attention=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2Model(cfg),
        config={"train_batch_size": dp * 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3,
                                      "stage3_param_persistence_threshold": 0},
                "tpu": {"data": dp, "tensor": tp},
                "hybrid_engine": {"enabled": True, "max_out_tokens": 48},
                "steps_per_print": 0})
    prompts = np.random.RandomState(7).randint(
        0, cfg.vocab_size, size=(dp * 2, 16)).astype(np.int32)
    engine.generate(prompts, max_new_tokens=8)
    batch = synthetic_lm_batch(dp * 2, 32, cfg.vocab_size, seed=0)
    engine.train_batch(batch)
    result = run_xray(plan=engine.plan)
    print(result.render())
    errors = [f for f in result.findings if f.severity == "error"]
    for f in errors:
        print(f"  {f}")
    if errors:
        print(f"[xray precheck] {len(errors)} error(s) — the gate would "
              "deadlock; not running the dryrun")
        return 2
    print("[xray precheck] clean: train/generate collective schedules agree")
    return 0


# ------------------------------------------------------------- engine hook
def engine_xray_analysis(engine):
    """The ``xray`` ds_doctor pass, run after the FIRST train_batch (the
    program table must hold compiled programs). Opt-in: only when
    ``"xray"`` is explicitly listed in ``analysis.passes`` — each
    analyzed program costs an AOT compile. Honors ``fail_on``."""
    from deepspeed_tpu.analysis.findings import AnalysisReport
    from deepspeed_tpu.utils.logging import log_dist

    acfg = engine._config.analysis
    result = run_xray(plan=getattr(engine, "plan", None),
                      min_replicated_elements=acfg.min_replicated_elements,
                      min_donate_bytes=acfg.min_donate_bytes)
    report = AnalysisReport().extend(result.findings, "xray")
    report.count_into_registry()
    if report.findings:
        log_dist(report.render("ds_doctor xray report"), ranks=[0])
    engine._xray_result = result
    report.raise_if(acfg.fail_on)
    return report
