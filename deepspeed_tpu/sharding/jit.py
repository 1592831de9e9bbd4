"""``sharded_jit`` — the one door engine programs walk through to compile.

Wraps ``jax.jit`` with three obligations the bare call lets you skip:

* ``in_shardings`` / ``out_shardings`` are REQUIRED keyword arguments.
  A program compiled without them on a multi-device mesh leaves XLA free
  to invent shardings — including a device-group order that disagrees
  with the train step's, which is the RLHF ``generate()`` deadlock class
  (MULTICHIP_r05.json: collective rendezvous timeout, rc=134). Writing
  :data:`INHERIT` is allowed — it states, explicitly, "this operand is
  already committed to the right placement" — but it must be WRITTEN.
* ``donate_argnums`` is required (pass ``()`` to donate nothing): every
  program states its buffer-reuse contract where the reviewer can see it.
* every compiled program is recorded in a process-global table —
  ``(label, call site, mesh axes, in/out spec summary, donation)`` —
  which ``ds_report mesh`` renders and the ds_doctor
  ``sharding/unspecified-jit`` lint audits.
* every call that makes a program specialize (a new shape, dtype,
  placement or committed-ness of an operand: a trace and, unless a cache
  has it, a compile) is counted at the door: :func:`door_events` says
  WHICH label went through and when.

The wrapper is intentionally thin: it resolves :data:`INHERIT` to the
``None`` jax.jit spells inference with, registers the record, and returns
the jitted callable unchanged (lower/compile/AOT all still work).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import inspect
import os
import re
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax

__all__ = ["INHERIT", "ProgramRecord", "program_table", "sharded_jit",
           "render_program_table", "reset_program_table",
           "describe_shardings", "door_events"]


class _Inherit:
    """Sentinel: 'inherit the committed operand's sharding' — the explicit
    spelling of what a bare ``jax.jit`` does implicitly. Resolves to None
    at the jax level; the program table records that it was chosen."""

    def __repr__(self):
        return "INHERIT"


INHERIT = _Inherit()


@dataclasses.dataclass
class ProgramRecord:
    """One engine-compiled program's sharding contract.

    Beyond the human-readable table row, the record keeps what the
    post-GSPMD analyzer (``deepspeed_tpu.analysis.xray``) needs to AOT
    re-lower the program WITHOUT an engine in hand: the jitted callable,
    the resolved promise trees, and — captured at the first real
    dispatch — abstract argument shapes carrying each COMMITTED
    operand's sharding (so an INHERIT program re-lowers against the
    same placements it actually compiled with).

    That is also enough to say what the program IS, on demand and never on
    the call path: :meth:`compiled` is the one lower-and-compile of a
    record, :meth:`instruction_scopes` and :meth:`memory` read its result."""

    label: str
    call_site: str
    mesh_axes: str
    in_desc: str
    out_desc: str
    donate: Tuple[int, ...]
    inherited_in: bool          # whole-argument INHERIT appeared in inputs
    inherited_out: bool
    generation: int = 0         # global-mesh generation at compile wrap time
    # --- post-GSPMD analysis hooks (xray) -------------------------------
    mesh: Any = None            # the Mesh object programs lower under
    in_shardings: Any = None    # resolved promise tree (INHERIT -> None)
    out_shardings: Any = None
    meta: Optional[Dict[str, Any]] = None   # call-site tags (state_argnum …)
    # WEAK reference to the jax.jit callable (the engine's _ShardedProgram
    # proxy holds the strong one): the process-global table must not pin a
    # dead engine — the jitted step closes over the engine and its whole
    # TrainState, and value-parameterized labels (generate[new=N]) would
    # otherwise accumulate one pinned engine per N for process lifetime
    jitted_ref: Any = None      # callable -> jitted | None
    abstract_args: Optional[Tuple] = None   # captured at first dispatch
    abstract_kwargs: Optional[Dict[str, Any]] = None
    _compiled: Any = dataclasses.field(default=None, repr=False,
                                       compare=False)

    @property
    def jitted(self):
        """The underlying jitted callable, or None once its program (and
        engine) have been garbage-collected."""
        return self.jitted_ref() if self.jitted_ref is not None else None

    def can_lower(self) -> bool:
        """True while a dispatch-captured, re-lowerable program is alive."""
        return self.jitted is not None and self.abstract_args is not None

    def compiled(self):
        """The program lowered from ``abstract_args`` under ``mesh`` and
        compiled again: instruction for instruction what the dispatch runs,
        with THIS tree's metadata; kept while the program lives. None for a
        record that was never dispatched or whose program was collected;
        what the lowering or the compiler raises is the caller's."""
        if not self.can_lower():
            self._compiled = None
            return None
        if self._compiled is None:
            # Not the dispatch's executable: jax's persistent cache keys a
            # program WITHOUT its metadata (op_name, source lines), so a hit
            # may have handed the dispatch what another tree compiled, under
            # that tree's names, and ``lower()`` hands back that very
            # lowering with its executable. A compiler option (this one at
            # XLA's default) makes it compile anew, and this compile keys
            # on the metadata too: a later process of this tree hits it.
            flag = "jax_compilation_cache_include_metadata_in_key"
            keep = getattr(jax.config, flag)
            jax.config.update(flag, True)
            try:
                # traces that constrain with bare PartitionSpecs need the
                # mesh context at lower time, exactly like the dispatch
                with self.mesh if self.mesh is not None \
                        else contextlib.nullcontext():
                    self._compiled = self.jitted.lower(
                        *self.abstract_args,
                        **(self.abstract_kwargs or {})).compile(
                            compiler_options={
                                "xla_embed_ir_in_executable": False})
            finally:
                jax.config.update(flag, keep)
        return self._compiled

    def instruction_scopes(self) -> Optional[Dict[str, str]]:
        """``{HLO instruction name: op_name}`` over every computation of
        the optimized module (``""`` where there is none):
        ``telemetry.scopes.classify`` reads a scope and a pass from an
        ``op_name``. None as :meth:`compiled`."""
        compiled = self.compiled()
        return None if compiled is None \
            else _instruction_scopes(compiled.as_text())

    def memory(self) -> Optional[Dict[str, int]]:
        """Bytes a device holds for this program, as the compiler counts
        them (``memory_analysis()``): ``argument``, ``output``, ``alias``
        (outputs written into donated arguments), ``temp``,
        ``generated_code``, and ``total`` = argument + output - alias +
        temp + generated_code. None as :meth:`compiled`."""
        compiled = self.compiled()
        if compiled is None:
            return None
        m = compiled.memory_analysis()
        out = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
               ("argument", "output", "alias", "temp", "generated_code")}
        out["total"] = sum(out.values()) - 2 * out["alias"]
        return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"[( ]%([\w.\-]+)")


def _instruction_scopes(text: str) -> Dict[str, str]:
    """One pass over a compiled module's text, which lists a computation
    before its callers and an operand before its users. An instruction with
    no metadata of its own takes, if it calls a computation (some fusions),
    the ``op_name`` of that computation's ROOT, else the commonest among its
    instructions; if it calls none, or one that holds no name either (what
    the compiler made itself: a combined collective, the done of a start, a
    layout copy, a bitcast fusion), that of its first operand that has one.
    So does an instruction whose ``op_name`` is no path but a bare name of
    the compiler's own (``ragged-dot-none``: a grouped matmul XLA:TPU
    rewrote into its Mosaic kernel, which keeps none of the traced op's)."""
    from deepspeed_tpu.telemetry.scopes import classify

    names: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    seen: Dict[str, collections.Counter] = {}
    comp = ""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                comp = head.group(1)
            continue
        is_root, name = m.groups()
        op = _OP_NAME.search(line)
        # every op the program traced carries a PATH (``jit(step_fn)/...``);
        # a bare name is the compiler's for a call it rewrote itself
        # (XLA:TPU's ``ragged-dot-none``): named by its operands, as below
        if op and "/" not in op.group(1):
            # of its operands' names the one furthest along the step: a
            # backward product reads a cotangent, a re-run a re-run's rows
            given = [n for n in (names.get(o) for o in
                                 _OPERAND.findall(line, m.end())) if n]
            block = [n for n in given
                     if classify(n)[0] not in ("", "layers")] or given
            rerun = [n for n in block if "rematted_computation" in n]
            names[name] = next(
                (n for n in block if "transpose(" in n and n not in rerun),
                (rerun or block or [""])[0])
            continue
        if op:
            names[name] = op.group(1)
            seen.setdefault(comp, collections.Counter())[op.group(1)] += 1
            if is_root:
                roots[comp] = op.group(1)
            continue
        callee = _CALLS.search(line)
        inner = seen.get(callee.group(1)) if callee else None
        names[name] = callee and (roots.get(callee.group(1)) or (
            inner.most_common(1)[0][0] if inner else "")) or next(filter(
                None, (names.get(o)
                       for o in _OPERAND.findall(line, m.end()))), "")
    return names


from deepspeed_tpu.utils import locks as _locks

_LOCK = _locks.make_lock("sharding.programs")
_PROGRAMS: Dict[str, ProgramRecord] = {}


def program_table() -> Dict[str, ProgramRecord]:
    """Snapshot of every program registered this process (label-keyed;
    re-registering a label — engines recompiling — overwrites)."""
    with _LOCK:
        return dict(_PROGRAMS)


def reset_program_table() -> None:
    with _LOCK:
        _PROGRAMS.clear()


# (time.monotonic(), label, specializations of that program so far), one
# per call that added a specialization; appends are atomic, nothing is
# removed (the count is bounded by programs x shapes)
_DOOR_EVENTS: List[Tuple[float, str, int]] = []


def door_events() -> List[Tuple[float, str, int]]:
    """Every specialization this process's programs went through, in
    order: ``(time.monotonic(), label, specializations so far)``."""
    return list(_DOOR_EVENTS)


_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def _place_compile_cache() -> None:
    """Give jax's persistent compilation cache a home unless it has one.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it into
    its config and nothing is set here — the operator (or the chip tool)
    placed the cache. Otherwise it goes to ``<checkout>/.jax_cache``: a
    FIXED path, because the directory is where a later process looks, so
    one built from a pid, the time or ``tempfile`` never hits. This is the
    only site in the package that names a cache directory."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", _REPO_CACHE_DIR)


def _resolve(tree):
    """INHERIT → None (jax.jit's 'infer from operand'), recursively.
    Returns (resolved, saw_inherit)."""
    saw = False

    def leaf(x):
        nonlocal saw
        if isinstance(x, _Inherit):
            saw = True
            return None
        return x

    resolved = jax.tree.map(leaf, tree,
                            is_leaf=lambda x: isinstance(x, _Inherit) or x is None)
    return resolved, saw


def describe_shardings(tree, limit: int = 4) -> str:
    """Compact multiset of the distinct PartitionSpecs in a shardings
    pytree — ``P('data',)×12 P()×3`` — for the program table."""
    if isinstance(tree, _Inherit):
        return "inherit"
    if tree is None:
        return "infer"
    counts: Dict[str, int] = {}
    for leaf in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, _Inherit) or x is None):
        if isinstance(leaf, _Inherit):
            key = "inherit"
        elif hasattr(leaf, "spec"):   # NamedSharding
            key = f"P{tuple(leaf.spec)!r}"
        else:
            key = repr(leaf)
        counts[key] = counts.get(key, 0) + 1
    if not counts:
        # a zero-argument program (in_shardings=()) has nothing to inherit
        return "no-args"
    items = sorted(counts.items(), key=lambda kv: -kv[1])
    shown = [f"{k}×{v}" if v > 1 else k for k, v in items[:limit]]
    if len(items) > limit:
        shown.append(f"(+{len(items) - limit} more)")
    return " ".join(shown)


def _caller_site() -> str:
    frame = inspect.currentframe()
    try:
        f = frame.f_back.f_back      # skip _caller_site and sharded_jit
        while f is not None and f.f_code.co_filename.endswith(
                os.path.join("sharding", "jit.py")):
            f = f.f_back
        if f is None:
            return "<unknown>"
        path = f.f_code.co_filename
        marker = os.sep + "deepspeed_tpu" + os.sep
        i = path.rfind(marker)
        rel = path[i + len(os.sep):] if i >= 0 else os.path.basename(path)
        return f"{rel.replace(os.sep, '/')}:{f.f_lineno}"
    finally:
        del frame


def _abstract_leaf(x):
    """A leaf's re-lowerable stand-in: array-likes become
    ShapeDtypeStructs (keeping a COMMITTED jax.Array's sharding — the
    placement jit actually inherited), everything else (static values,
    Python scalars) passes through unchanged."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x
    sharding = None
    if getattr(x, "_committed", False):
        sharding = getattr(x, "sharding", None)
    try:
        if sharding is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    except Exception:
        return jax.ShapeDtypeStruct(tuple(shape), dtype)


class _ShardedProgram:
    """Thin dispatch proxy around the jitted callable: forwards every
    call/attribute untouched, and on the FIRST call snapshots the
    arguments' abstract shapes (+ committed shardings) into the program
    record — that snapshot is what lets ``ds_doctor xray`` AOT
    lower+compile the exact program later, with no engine in hand.
    Snapshot cost is paid once; afterwards ``__call__`` is one flag
    check and one read of the callable's specialization count
    (``_cache_size()``, ~0.06 µs) on top of the pjit fast path. A call
    after which that count has grown went through trace and compile (or
    a cache load): it is noted as a door event and as an instant
    ``door_compile`` in the tracer."""

    __slots__ = ("_jitted", "program_record", "_captured", "_cache_size",
                 "_specializations")

    def __init__(self, jitted, record: ProgramRecord):
        self._jitted = jitted
        self.program_record = record
        self._captured = False
        self._cache_size = jitted._cache_size
        self._specializations = 0

    def _capture(self, args, kwargs):
        self._captured = True
        rec = self.program_record
        try:
            rec.abstract_args = tuple(
                jax.tree.map(_abstract_leaf, a) for a in args)
            rec.abstract_kwargs = {k: jax.tree.map(_abstract_leaf, v)
                                   for k, v in kwargs.items()}
        except Exception:
            rec.abstract_args = rec.abstract_kwargs = None

    def __call__(self, *args, **kwargs):
        if not self._captured:
            self._capture(args, kwargs)
        out = self._jitted(*args, **kwargs)
        n = self._cache_size()
        if n != self._specializations:
            self._note_specialization(n)
        return out

    def _note_specialization(self, n: int) -> None:
        grew = n > self._specializations    # not: jax dropped its caches
        self._specializations = n
        if not grew:
            return
        from deepspeed_tpu import telemetry

        label = self.program_record.label
        _DOOR_EVENTS.append((time.monotonic(), label, n))
        # not named "compile": goodput books that as compile badput
        telemetry.get_tracer().instant("door_compile", cat="door",
                                       label=label, specializations=n)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def __repr__(self):
        return f"<sharded_jit {self.program_record.label!r}>"


def sharded_jit(fn, *, label: str, in_shardings, out_shardings,
                donate_argnums: Tuple[int, ...],
                static_argnums=None, static_argnames=None,
                mesh=None, meta: Optional[Dict[str, Any]] = None):
    """``jax.jit`` with the sharding contract stated and recorded.

    Args:
      label: stable program name (``"engine/train_batch"``) — the table
        key, what the lint and ``ds_report mesh`` print.
      in_shardings / out_shardings: pytree (prefix) of
        :class:`~jax.sharding.NamedSharding` (or :data:`INHERIT` /
        per-leaf ``None`` for explicitly-inherited operands). REQUIRED.
      donate_argnums: REQUIRED — ``()`` means "nothing donated", written
        down rather than defaulted.
      mesh: records the mesh identity in the table (defaults to the
        process-global mesh at wrap time).
      meta: optional call-site tags for the post-GSPMD analyzer (e.g.
        ``{"state_argnum": 0}`` marks which argument is the TrainState
        whose families the xray promise-vs-actual pass audits).
    """
    if not label:
        raise ValueError("sharded_jit: a non-empty program label is required")
    _place_compile_cache()
    if in_shardings is None or out_shardings is None:
        raise TypeError(
            f"sharded_jit({label!r}): in_shardings/out_shardings must be "
            "explicit — pass registry specs or sharding.INHERIT. A bare "
            "None means 'let XLA decide', which is the unspecified-jit "
            "deadlock class this wrapper exists to forbid")
    from deepspeed_tpu.sharding.mesh import (global_mesh, mesh_axes_string,
                                             mesh_generation)

    in_resolved, in_inh = _resolve(in_shardings)
    out_resolved, out_inh = _resolve(out_shardings)
    record = ProgramRecord(
        label=label,
        call_site=_caller_site(),
        mesh_axes=mesh_axes_string(mesh if mesh is not None else global_mesh()),
        in_desc=describe_shardings(in_shardings),
        out_desc=describe_shardings(out_shardings),
        donate=tuple(donate_argnums),
        inherited_in=in_inh or isinstance(in_shardings, _Inherit),
        inherited_out=out_inh or isinstance(out_shardings, _Inherit),
        generation=mesh_generation(),
        mesh=mesh if mesh is not None else global_mesh(),
        in_shardings=in_resolved, out_shardings=out_resolved,
        meta=dict(meta) if meta else None)
    with _LOCK:
        _PROGRAMS[label] = record

    kwargs: Dict[str, Any] = dict(donate_argnums=tuple(donate_argnums))
    if static_argnums is not None:
        kwargs["static_argnums"] = static_argnums
    if static_argnames is not None:
        kwargs["static_argnames"] = static_argnames
    if in_resolved is not None:
        kwargs["in_shardings"] = in_resolved
    if out_resolved is not None:
        kwargs["out_shardings"] = out_resolved
    jitted = jax.jit(fn, **kwargs)
    record.jitted_ref = weakref.ref(jitted)
    return _ShardedProgram(jitted, record)


def render_program_table(mesh: Optional[Any] = None) -> str:
    """The per-program in/out spec table ``ds_report mesh`` prints."""
    from deepspeed_tpu.sharding.mesh import global_mesh, mesh_axes_string

    mesh = mesh if mesh is not None else global_mesh()
    rows = sorted(program_table().values(), key=lambda r: r.label)
    lines = [f"mesh: {mesh_axes_string(mesh)}"
             + (f" ({len(rows)} compiled program(s))" if rows else
                " (no programs compiled yet)")]
    for r in rows:
        donate = f"donate={list(r.donate)}" if r.donate else "donate=()"
        lines.append(f"  {r.label}  [{r.mesh_axes}]  {donate}  @ {r.call_site}")
        lines.append(f"    in:  {r.in_desc}")
        lines.append(f"    out: {r.out_desc}")
    return "\n".join(lines)
