"""Structured model of a compiled (post-GSPMD) HLO module.

The jaxpr passes lint what the USER wrote; everything the partitioner
decides afterwards — which collectives exist, over which device groups,
in what schedule order, which buffers actually alias — is only visible in
the compiled executable. ``compiled.as_text()`` prints the scheduled,
partitioned module; this parser turns the three slices the xray passes
need into data:

* the **collective schedule**: every collective instruction in program
  (schedule) order — kind, result bytes, decoded replica groups (both the
  explicit ``{{0,1},{2,3}}`` and the iota-v2 ``[G,S]<=[dims]T(perm)``
  spellings), channel id, source metadata;
* the **input-output alias table** from the module header — which flat
  output index aliases which flat parameter (the compiled truth behind
  every ``donate_argnums`` promise);
* the **entry layout** — flat parameter/result shapes, so alias and
  donation findings can talk in bytes;
* the **compute regions** (ds_roofline): every dot / convolution /
  fusion / costed instruction in every non-fused computation, with
  analytic FLOPs and HBM bytes-accessed. The counting conventions
  deliberately MATCH XLA's ``HloCostAnalysis`` (what
  ``compiled.cost_analysis()`` reports) so the regex model and the live
  compiler agree on the same program: dot = 2·result_elems·contract;
  elementwise = 1 flop/element; transcendentals (tanh/exp/…) counted
  separately, NOT as flops; reduce = in_elems − out_elems; while bodies
  counted ONCE (trip counts are invisible to both sides — ratios like
  MFU ceilings are invariant to that shared undercount); a fusion's
  flops are its called computation's, its bytes are its EXTERNAL
  operands + results (fusion internals never touch HBM).

Everything here is regex-over-text on purpose: the HLO text format is the
one stable cross-version surface (jax's python bindings for these
structures churn), and parsing it keeps the model buildable from a saved
``.hlo`` dump with no jax at all. A line the parser does not understand
is skipped, never fatal — the model reports what it could see.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["CollectiveOp", "ComputeOp", "HloModel", "parse_hlo_module",
           "parse_replica_groups", "shape_bytes", "shape_elements",
           "collective_wire_bytes"]

# HLO primitive bytes per element (pred is byte-packed in practice)
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "u1": 1, "s1": 1,
}

# collective opcodes, longest-first so ``all-gather-start`` wins over
# ``all-gather`` (async pairs: the -start carries the semantics, the
# -done is bookkeeping and is skipped)
COLLECTIVE_KINDS = (
    "all-gather-start", "all-reduce-start", "all-to-all-start",
    "reduce-scatter-start", "collective-permute-start",
    "all-gather-done", "all-reduce-done", "all-to-all-done",
    "reduce-scatter-done", "collective-permute-done",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)
_SKIP_SUFFIX = "-done"


@dataclasses.dataclass
class CollectiveOp:
    """One collective instruction of the scheduled entry computation."""

    kind: str                         # canonical (-start folded away)
    name: str                         # %instruction name
    index: int                        # schedule order within the entry
    bytes: int                        # result bytes (local/per-partition)
    channel_id: Optional[int]
    replica_groups: Tuple[Tuple[int, ...], ...]   # partition-id groups
    source_target_pairs: Tuple[Tuple[int, int], ...] = ()
    metadata_op: str = ""             # op_name= from metadata
    source_line: str = ""             # source_file:source_line

    def group_size(self) -> int:
        if self.replica_groups:
            return max(len(g) for g in self.replica_groups)
        if self.source_target_pairs:
            return 2
        return 1

    def describe_groups(self) -> str:
        if self.replica_groups:
            shown = ["{" + ",".join(map(str, g)) + "}"
                     for g in self.replica_groups[:4]]
            if len(self.replica_groups) > 4:
                shown.append(f"(+{len(self.replica_groups) - 4} more)")
            return "{" + ",".join(shown) + "}"
        if self.source_target_pairs:
            return "pairs{" + ",".join(f"{s}->{t}" for s, t
                                       in self.source_target_pairs[:6]) + "}"
        return "{}"


# --------------------------------------------------------------- cost model
# Elementwise opcodes that cost 1 flop per result element in
# HloCostAnalysis (the probe-calibrated set; add/maximum/multiply/divide
# and convert verified numerically against compiled.cost_analysis() on
# cpu jax). convert matters a LOT: a mixed-precision ZeRO-3 step carries
# millions of bf16<->f32 cast elements, and omitting it put the regex
# model ~16% under XLA's count on the gpt2 fixture.
_FLOP1_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "compare", "negate", "abs", "sign", "floor", "ceil", "remainder",
    "round-nearest-afz", "round-nearest-even", "clamp", "select",
    "and", "or", "xor", "not", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "is-finite", "clz", "popcnt",
    "convert", "bitcast-convert", "reduce-precision",
    "stochastic-convert",
})
# Counted as TRANSCENDENTALS per element, never flops (verified:
# tanh/exp contribute to cost_analysis()['transcendentals'] only).
_TRANSCENDENTAL_OPS = frozenset({
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "sqrt", "rsqrt", "cbrt", "sine", "cosine",
    "tan", "power", "atan2", "erf", "exp", "expm1",
})
# Free on both axes: no arithmetic, no HBM traffic of their own (XLA
# zeroes these in HloCostAnalysis — buffer bookkeeping, or control flow
# whose bodies are counted as separate computations).
_ZERO_COST_OPS = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "while", "conditional",
    "call", "opt-barrier", "domain",
})

_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([0-9a-zA-Z?]+)_([0-9a-zA-Z?]+)->")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_COMP_HEADER_RE = re.compile(
    r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*\S.*\{\s*$")


@dataclasses.dataclass
class ComputeOp:
    """One costed instruction of a non-fused computation (roofline
    region): a dot, convolution, fusion, collective, or any other op
    with nonzero analytic flops / transcendentals / HBM bytes."""

    name: str                 # %instruction name
    opcode: str               # dot | convolution | fusion | ...
    computation: str          # enclosing computation (ENTRY, while body…)
    flops: int = 0            # fusion: its called computation's flops
    transcendentals: int = 0  # per-element transcendental count
    bytes: int = 0            # HBM model: operand bytes + result bytes
    result_bytes: int = 0
    metadata_op: str = ""     # op_name= from metadata
    source_line: str = ""     # source_file:source_line


@dataclasses.dataclass
class HloModel:
    """The xray-relevant slices of one compiled HLO module."""

    name: str = ""
    num_partitions: int = 1
    collectives: List[CollectiveOp] = dataclasses.field(default_factory=list)
    # flat output index -> flat parameter index (may-alias entries included:
    # the point is "did the donation survive", not its kind)
    aliases: Dict[int, int] = dataclasses.field(default_factory=dict)
    parameter_bytes: List[int] = dataclasses.field(default_factory=list)
    result_bytes: List[int] = dataclasses.field(default_factory=list)
    # costed instructions of every NON-fused computation, textual order
    # (fused computations are rolled into their fusion instruction)
    compute_ops: List[ComputeOp] = dataclasses.field(default_factory=list)

    def aliased_parameters(self) -> set:
        return set(self.aliases.values())

    def total_flops(self) -> int:
        """HloCostAnalysis-convention module flops (while bodies once,
        transcendentals excluded) — the number the live
        ``compiled.cost_analysis()['flops']`` cross-check compares to."""
        return sum(op.flops for op in self.compute_ops)

    def total_transcendentals(self) -> int:
        return sum(op.transcendentals for op in self.compute_ops)

    def total_bytes_accessed(self) -> int:
        """Σ per-instruction (operand + result) bytes — the HBM-traffic
        model the roofline's memory axis prices."""
        return sum(op.bytes for op in self.compute_ops)

    def comm_bytes_by_kind(self) -> Dict[str, int]:
        """Per-kind WIRE bytes (per participating device, ring model)."""
        out: Dict[str, int] = {}
        for op in self.collectives:
            b = collective_wire_bytes(op)
            if b:
                out[op.kind] = out.get(op.kind, 0) + b
        return out

    def total_comm_bytes(self) -> int:
        return sum(self.comm_bytes_by_kind().values())


# ------------------------------------------------------------------ shapes
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def shape_bytes(shape_text: str) -> int:
    """Total bytes of an HLO shape string — ``f32[4,256]{1,0}``, or a
    tuple ``(f32[8], bf16[2,2])`` (summed). Layout braces are ignored."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_text):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def shape_elements(shape_text: str) -> int:
    """Total element count of an HLO shape string (tuples summed)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_text):
        if m.group(1) not in _DTYPE_BYTES:
            continue
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _shape_dims(text: str) -> List[List[int]]:
    """Dims of every shape literal in ``text``, in order (``f32[8,64]``
    -> ``[8, 64]``; scalars -> ``[]``)."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        if m.group(1) not in _DTYPE_BYTES:
            continue
        out.append([int(d) for d in m.group(2).split(",") if d])
    return out


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


# ---------------------------------------------------------- replica groups
_IOTA_RE = re.compile(
    r"\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _decode_iota(m: "re.Match") -> Tuple[Tuple[int, ...], ...]:
    """Decode the iota-v2 spelling: reshape arange(prod(dims)) to dims,
    transpose by perm, flatten, then chop into G groups of S."""
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(d) for d in m.group(3).split(",") if d]
    n = 1
    for d in dims:
        n *= d
    if n != g * s or n == 0:
        return ()
    ids = list(range(n))
    if m.group(4):
        perm = [int(p) for p in m.group(4).split(",") if p]
        # index math without numpy: value at flat position i of the
        # transposed array = ids[original flat index]
        strides = [0] * len(dims)
        acc = 1
        for i in range(len(dims) - 1, -1, -1):
            strides[i] = acc
            acc *= dims[i]
        tdims = [dims[p] for p in perm]
        tstrides = [strides[p] for p in perm]
        flat = []
        idx = [0] * len(tdims)
        for _ in range(n):
            flat.append(sum(i * st for i, st in zip(idx, tstrides)))
            for ax in range(len(tdims) - 1, -1, -1):
                idx[ax] += 1
                if idx[ax] < tdims[ax]:
                    break
                idx[ax] = 0
        ids = flat
    return tuple(tuple(ids[i * s:(i + 1) * s]) for i in range(g))


def parse_replica_groups(text: str) -> Tuple[Tuple[int, ...], ...]:
    """Decode a ``replica_groups=`` value: explicit ``{{0,1},{2,3}}`` or
    iota ``[G,S]<=[dims]`` / ``[G,S]<=[dims]T(perm)``."""
    text = text.strip()
    m = _IOTA_RE.match(text)
    if m:
        return _decode_iota(m)
    if text.startswith("{"):
        groups = []
        for grp in re.finditer(r"\{([0-9, ]*)\}", text):
            members = tuple(int(x) for x in grp.group(1).replace(" ", "")
                            .split(",") if x)
            if members:
                groups.append(members)
        return tuple(groups)
    return ()


# ------------------------------------------------------------------ parsing
_ALIAS_ENTRY_RE = re.compile(
    r"\{([0-9,\s]*)\}:\s*\((\d+),\s*\{[0-9,\s]*\}")


def _balanced_value(text: str, key: str) -> str:
    """The ``{...}`` value of ``key={...}`` in a header line, brace-
    balanced (the value itself contains braces); "" when absent."""
    i = text.find(key + "={")
    if i < 0:
        return ""
    start = i + len(key) + 1
    depth = 0
    for j in range(start, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1:j]
    return ""
_NUM_PART_RE = re.compile(r"num_partitions=(\d+)")
_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^=]*?\}\}|\{\}|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([^}]*)\}")
_META_OP_RE = re.compile(r'op_name="([^"]*)"')
_META_SRC_RE = re.compile(r'source_file="([^"]*)".*?source_line=(\d+)')
# shape alternatives: a tuple (may contain one paren-nesting level — TPU
# tiled layouts print as f32[128]{0:T(256)} inside tuples) or a bare token
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"(\((?:[^()]+|\([^()]*\))*\)|\S+)\s+([\w\-]+)\(")


def _tuple_elements(shape_text: str):
    """Top-level comma split of a tuple shape (nested parens/braces from
    tiled layouts are kept inside their element)."""
    if not (shape_text.startswith("(") and shape_text.endswith(")")):
        return [shape_text]
    parts, depth, cur = [], 0, []
    for ch in shape_text[1:-1]:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def _split_shapes(sig: str) -> List[str]:
    """Split an entry-layout side into per-leaf shape strings (flat —
    the tuple result is one level deep in practice)."""
    return [m.group(0) for m in _SHAPE_RE.finditer(sig)]


def _alias_output_index(idx_text: str, result_arity: int) -> Optional[int]:
    """``{2}`` -> 2; ``{}`` -> 0 (single-output module)."""
    idx = [int(x) for x in idx_text.replace(" ", "").split(",") if x]
    if not idx:
        return 0 if result_arity <= 1 else None
    return idx[0]


def _args_segment(line: str, open_pos: int) -> str:
    """The operand list between the opcode's ``(`` at ``open_pos`` and
    its balanced ``)`` — attributes/metadata after it are excluded, so
    shape-looking text inside ``op_name="…"`` never pollutes operand
    byte counts."""
    depth = 0
    for j in range(open_pos, len(line)):
        if line[j] == "(":
            depth += 1
        elif line[j] == ")":
            depth -= 1
            if depth == 0:
                return line[open_pos + 1:j]
    return line[open_pos + 1:]


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _typed_operands(args: str, shape_of: Dict[str, str]) -> str:
    """``args`` with every operand's shape in front of its name. Older
    printers write ``dot(f32[32,64]{1,0} %a, ...)``; jax 0.9's writes
    ``dot(%a, %b)`` and leaves the shapes to the operands' own lines, so
    a dot's contraction, a reduce's input and every operand's bytes come
    from ``shape_of`` (name -> the shape its defining line printed)."""
    if _SHAPE_RE.search(args):
        return args
    return _OPERAND_NAME_RE.sub(
        lambda m: f"{shape_of.get(m.group(1), '')} %{m.group(1)}", args)


def _instr_cost(opcode: str, shape_text: str, args: str, attrs: str):
    """(flops, transcendentals, bytes, result_bytes) of one instruction
    under the HloCostAnalysis conventions (module docstring). Fusions
    return 0 flops here — their called computation is resolved by the
    caller. Unknown opcodes cost 0 flops but still move their bytes."""
    if opcode in _ZERO_COST_OPS:
        return 0, 0, 0, 0
    if opcode.endswith(_SKIP_SUFFIX):       # async -done: bookkeeping
        return 0, 0, 0, 0
    if opcode.endswith("-start"):
        # async tuple carries operand AND result — count the result only
        shape_text = _tuple_elements(shape_text)[-1]
        result_bytes = shape_bytes(shape_text)
        return 0, 0, result_bytes + shape_bytes(args), result_bytes
    result_bytes = shape_bytes(shape_text)
    nbytes = result_bytes + shape_bytes(args)
    elems = shape_elements(shape_text)
    if opcode == "dot":
        contract = 1
        cm = _LHS_CONTRACT_RE.search(attrs)
        operand_dims = _shape_dims(args)
        lhs = operand_dims[0] if operand_dims else []
        if cm and lhs:
            for d in (int(x) for x in cm.group(1).split(",") if x):
                if d < len(lhs):
                    contract *= lhs[d]
        elif lhs:                            # unannotated: last dim
            contract = lhs[-1] if lhs else 1
        return 2 * elems * contract, 0, nbytes, result_bytes
    if opcode == "convolution":
        operand_dims = _shape_dims(args)
        kernel = operand_dims[1] if len(operand_dims) > 1 else []
        macs_per_out = _prod(kernel)
        dm = _DIM_LABELS_RE.search(attrs)
        if dm and kernel:
            o_pos = dm.group(2).find("o")
            if 0 <= o_pos < len(kernel) and kernel[o_pos]:
                macs_per_out //= kernel[o_pos]
        return 2 * elems * max(1, macs_per_out), 0, nbytes, result_bytes
    if opcode in ("reduce", "reduce-window"):
        in_elems = 0
        od = _shape_dims(args)
        if od:
            in_elems = _prod(od[0])
        return max(0, in_elems - elems), 0, nbytes, result_bytes
    if opcode in _TRANSCENDENTAL_OPS:
        return 0, elems, nbytes, result_bytes
    if opcode in _FLOP1_OPS:
        return elems, 0, nbytes, result_bytes
    return 0, 0, nbytes, result_bytes


def parse_hlo_module(text: str) -> HloModel:
    """Parse one compiled HLO module's text into an :class:`HloModel`.

    Only the ENTRY computation's collectives are scheduled program order;
    collectives inside fusions/called computations (rare post-scheduling)
    are still counted, in textual order.

    Compute regions: instructions are grouped by enclosing computation;
    fused computations (targets of a fusion's ``calls=``) contribute
    their flops to the fusion instruction and NOTHING to bytes — every
    other computation (ENTRY, while bodies, branches) contributes its
    instructions as regions directly, counted once."""
    model = HloModel()
    lines = text.splitlines()
    if lines:
        m = _MODULE_RE.match(lines[0])
        if m:
            model.name = m.group(1)
        mp = _NUM_PART_RE.search(lines[0])
        if mp:
            model.num_partitions = int(mp.group(1))
        lay = _balanced_value(lines[0], "entry_computation_layout")
        if lay and "->" in lay:
            params_sig, result_sig = lay.split("->", 1)
            model.parameter_bytes = [shape_bytes(s)
                                     for s in _split_shapes(params_sig)]
            model.result_bytes = [shape_bytes(s)
                                  for s in _split_shapes(result_sig)]
        al = _balanced_value(lines[0], "input_output_alias")
        if al:
            arity = max(1, len(model.result_bytes))
            for entry in _ALIAS_ENTRY_RE.finditer(al):
                out_idx = _alias_output_index(entry.group(1), arity)
                if out_idx is not None:
                    model.aliases[out_idx] = int(entry.group(2))

    order = 0
    current_comp = ""
    comp_order: List[str] = []
    # per computation: [(ComputeOp, calls_target_or_None), ...]
    comp_records: Dict[str, list] = {}
    shape_of: Dict[str, str] = {}      # instruction name -> its result shape
    for line in lines[1:]:
        im = _INSTR_RE.match(line)
        if im is None:
            hm = _COMP_HEADER_RE.match(line)
            if hm and " = " not in line:
                current_comp = hm.group(2)
                if current_comp not in comp_records:
                    comp_order.append(current_comp)
                    comp_records[current_comp] = []
            continue
        name, shape_text, opcode = im.group(1), im.group(2), im.group(3)
        shape_of[name] = shape_text

        # ---- compute region (roofline) --------------------------------
        args = _args_segment(line, im.end() - 1)
        attrs = line[im.end() - 1 + len(args) + 2:]
        args = _typed_operands(args, shape_of)
        flops, trans, nbytes, rbytes = _instr_cost(
            opcode, shape_text, args, attrs)
        calls = None
        if opcode == "fusion":
            cm2 = _CALLS_RE.search(attrs)
            calls = cm2.group(1) if cm2 else None
        if flops or trans or nbytes or calls:
            mo2 = _META_OP_RE.search(line)
            ms2 = _META_SRC_RE.search(line)
            if current_comp not in comp_records:
                comp_order.append(current_comp)
                comp_records[current_comp] = []
            comp_records[current_comp].append((ComputeOp(
                name=name, opcode=opcode, computation=current_comp,
                flops=flops, transcendentals=trans, bytes=nbytes,
                result_bytes=rbytes,
                metadata_op=mo2.group(1) if mo2 else "",
                source_line=(f"{ms2.group(1)}:{ms2.group(2)}"
                             if ms2 else "")), calls))

        # ---- collectives ----------------------------------------------
        kind = None
        for k in COLLECTIVE_KINDS:
            if opcode == k:
                kind = k
                break
        if kind is None or kind.endswith(_SKIP_SUFFIX):
            continue
        canonical = kind[:-len("-start")] if kind.endswith("-start") else kind
        if kind.endswith("-start"):
            # async spelling: the result is a tuple carrying BOTH the
            # operand and the result buffer — count only the LAST element
            # (the result), or the sync/async flip of one collective would
            # read as a ~2x static-comm change
            shape_text = _tuple_elements(shape_text)[-1]
        groups: Tuple[Tuple[int, ...], ...] = ()
        gm = _GROUPS_RE.search(line)
        if gm:
            groups = parse_replica_groups(gm.group(1))
        pairs: Tuple[Tuple[int, int], ...] = ()
        pm = _PAIRS_RE.search(line)
        if pm:
            pairs = tuple(
                (int(a), int(b))
                for a, b in re.findall(r"\{(\d+),\s*(\d+)\}", pm.group(0)))
        cm = _CHANNEL_RE.search(line)
        mo = _META_OP_RE.search(line)
        ms = _META_SRC_RE.search(line)
        model.collectives.append(CollectiveOp(
            kind=canonical, name=name, index=order,
            bytes=shape_bytes(shape_text),
            channel_id=int(cm.group(1)) if cm else None,
            replica_groups=groups,
            source_target_pairs=pairs,
            metadata_op=mo.group(1) if mo else "",
            source_line=(f"{ms.group(1)}:{ms.group(2)}" if ms else "")))
        order += 1

    # ---- resolve fusions, assemble regions --------------------------------
    # Callee computations print before their callers, so one in-order pass
    # resolves fusion flops; an unresolvable calls= costs 0, never raises.
    fusion_targets = set()
    comp_flops: Dict[str, int] = {}
    comp_trans: Dict[str, int] = {}
    for comp in comp_order:
        f = t = 0
        for op, calls in comp_records[comp]:
            if calls:
                fusion_targets.add(calls)
                op.flops = comp_flops.get(calls, 0)
                op.transcendentals = comp_trans.get(calls, 0)
            f += op.flops
            t += op.transcendentals
        comp_flops[comp] = f
        comp_trans[comp] = t
    for comp in comp_order:
        if comp in fusion_targets:
            continue  # rolled into its fusion instruction
        for op, _calls in comp_records[comp]:
            if op.flops or op.transcendentals or op.bytes:
                model.compute_ops.append(op)
    return model


# ------------------------------------------------------------- comm model
def collective_wire_bytes(op: CollectiveOp) -> int:
    """Per-device wire bytes of one collective under the standard ring
    model — the hardware-free cost the static-comm gate tracks:

    * all-gather:       result is the gathered buffer; each device
                        RECEIVES (g-1)/g of it.
    * reduce-scatter:   result is the scattered shard; each device sends/
                        receives (g-1) shards ≈ result × (g-1).
    * all-reduce:       reduce-scatter + all-gather over the same bytes:
                        2 × result × (g-1)/g.
    * all-to-all:       result bytes × (g-1)/g cross the wire.
    * collective-permute / -broadcast: the buffer crosses once.
    """
    g = op.group_size()
    b = op.bytes
    if op.kind == "all-gather":
        return int(b * (g - 1) / g) if g > 1 else 0
    if op.kind == "reduce-scatter":
        return int(b * (g - 1))
    if op.kind == "all-reduce":
        return int(2 * b * (g - 1) / g) if g > 1 else 0
    if op.kind == "all-to-all":
        return int(b * (g - 1) / g) if g > 1 else 0
    if op.kind in ("collective-permute", "collective-broadcast"):
        return b if (g > 1 or op.source_target_pairs) else 0
    return 0


def estimate_bus_seconds(total_bytes: int, bus_bytes_per_s: float) -> float:
    """Lower-bound seconds on the wire for ``total_bytes`` at the given
    per-link bus bandwidth (0 bandwidth -> inf guard)."""
    if bus_bytes_per_s <= 0:
        return math.inf if total_bytes else 0.0
    return total_bytes / bus_bytes_per_s
