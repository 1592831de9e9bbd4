"""ds_doctor sharding pass — the ``sharding/unspecified-jit`` lint.

Two layers, one rule: no engine program may enter ``jax.jit`` without an
explicit sharding contract.

* **AST layer** (:func:`lint_unspecified_jit`) — walks the package for bare
  ``jax.jit(...)`` calls. Every engine-compiled program must route through
  :func:`deepspeed_tpu.sharding.sharded_jit`, whose ``in_shardings`` /
  ``out_shardings`` / ``donate_argnums`` are REQUIRED keyword arguments; a
  bare ``jax.jit`` in the engine tree is exactly how the RLHF hybrid
  ``generate()`` shipped with no ``in_shardings`` and deadlocked the
  8-device dp×tp mesh (MULTICHIP_r05.json rc=134). The finding names the
  enclosing function (the program) and the call site.
* **Runtime layer** (:func:`lint_program_table`) — audits the process-global
  program table ``sharded_jit`` maintains: a program registered on a
  multi-axis mesh whose inputs AND outputs are both wholly inherited gets a
  warning (legitimate for single-device utility programs; on a real mesh it
  means the contract was stated as "whatever the operands say" twice over).

Allowlisted files (bare jax.jit permitted):
* ``sharding/jit.py`` — the wrapper itself;
* ``env_report.py`` — a lower-only capability probe, never dispatched on a
  training mesh;
* ``profiling/flops_profiler/profiler.py`` — AOT ``lower()`` for jaxpr
  walks; nothing is executed;
* ``analysis/doctor.py`` — the compiled donation lint AOT-compiles a
  user-supplied graph to read its alias table; nothing is dispatched.

Zero findings on the migrated tree is a tier-1 assertion
(tests/unit/test_sharding.py), so a bare jit cannot merge back in.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Sequence

from deepspeed_tpu.analysis.findings import Finding

RULE_UNSPECIFIED_JIT = "sharding/unspecified-jit"

# bare jax.jit is allowed here (see module docstring)
BARE_JIT_ALLOWED = (
    "sharding/jit.py",
    "env_report.py",
    "profiling/flops_profiler/profiler.py",
    # AOT lower().compile() of a USER-supplied graph purely to read its
    # alias table (the compiled donation lint) — nothing is dispatched
    "analysis/doctor.py",
)


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _enclosing_function(tree: ast.AST, lineno: int) -> str:
    """Name of the innermost def/class containing ``lineno`` — the
    "program" the finding names."""
    best = "<module>"
    best_span = None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            end = getattr(node, "end_lineno", None)
            if end is None or not (node.lineno <= lineno <= end):
                continue
            span = end - node.lineno
            if best_span is None or span < best_span:
                best, best_span = node.name, span
    return best


def lint_jit_source(src: str, relpath: str) -> List[Finding]:
    """Lint one module's source for bare jax.jit calls."""
    relpath = relpath.replace("\\", "/")
    if any(relpath.endswith(p) for p in BARE_JIT_ALLOWED):
        return []
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return []    # the selflint pass reports syntax errors
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func)
        if name not in ("jax.jit", "jit"):
            continue
        if name == "jit" and "import jax" not in src and \
                "from jax" not in src:
            continue
        program = _enclosing_function(tree, node.lineno)
        findings.append(Finding(
            rule=RULE_UNSPECIFIED_JIT, severity="error",
            message=(f"bare jax.jit in engine program {program!r} — on a "
                     "multi-axis mesh an unspecified program lets XLA "
                     "invent in/out shardings AND a collective device-group "
                     "order (the RLHF generate() deadlock class, "
                     "MULTICHIP_r05 rc=134); route it through "
                     "deepspeed_tpu.sharding.sharded_jit, which makes "
                     "in_shardings/out_shardings/donate_argnums mandatory"),
            citation=f"{relpath}:{node.lineno}", pass_name="sharding"))
    return findings


_AST_CACHE = {}


def repo_script_paths(root: str) -> List[str]:
    """The repo-level entry scripts the lint also covers: ``bin/*``
    (extensionless python launchers). These dispatch real programs, so
    a bare ``jax.jit`` there is exactly as deadlock-capable as one in
    the package; package-only coverage left them a blind spot."""
    repo = os.path.dirname(root)
    out: List[str] = []
    bindir = os.path.join(repo, "bin")
    if os.path.isdir(bindir):
        for fn in sorted(os.listdir(bindir)):
            path = os.path.join(bindir, fn)
            if not os.path.isfile(path):
                continue
            try:
                with open(path, encoding="utf-8") as f:
                    head = f.read(128)
            except (OSError, UnicodeDecodeError):
                continue
            first = head.splitlines()[0] if head else ""
            if "python" in first:
                out.append(path)
    return out


def lint_unspecified_jit(root: Optional[str] = None,
                         skip_dirs: Sequence[str] = ("__pycache__",),
                         include_scripts: bool = True) -> List[Finding]:
    """AST lint of every .py file of the deepspeed_tpu package, plus the
    repo's entry scripts (``bin/*``) when they sit next to it. Memoized
    per root: the source tree does not change mid-process, and the engine
    runs this at every init."""
    if root is None:
        import deepspeed_tpu

        root = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    key = (root, include_scripts)
    if key in _AST_CACHE:
        return list(_AST_CACHE[key])
    findings: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in skip_dirs]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as f:
                    src = f.read()
            except OSError:
                continue     # the selflint pass reports unreadable files
            findings.extend(lint_jit_source(src, rel))
    if include_scripts:
        repo = os.path.dirname(root)
        for path in repo_script_paths(root):
            rel = os.path.relpath(path, repo).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as f:
                    src = f.read()
            except OSError:
                continue
            findings.extend(lint_jit_source(src, rel))
    _AST_CACHE[key] = list(findings)
    return findings


def lint_program_table() -> List[Finding]:
    """Runtime audit of the sharded_jit program table: on a multi-axis
    mesh, a program whose in or out shardings were left UNSPECIFIED (raw
    ``None`` rather than registry specs or an explicit :data:`INHERIT`) is
    an error naming the program and call site. ``sharded_jit`` refuses
    top-level ``None`` at wrap time, so this is the tripwire for any
    future escape hatch — green by construction on the migrated tree."""
    from deepspeed_tpu.sharding import program_table

    findings: List[Finding] = []
    for rec in sorted(program_table().values(), key=lambda r: r.label):
        # multi-DEVICE, not multi-axis: a pure-dp "data=8" mesh (no '×'
        # separator) is exactly the ZeRO topology the gate protects —
        # any nontrivial axis in the identity string means >1 device
        if rec.mesh_axes in ("single-device", "unmeshed"):
            continue
        if rec.in_desc == "infer" or rec.out_desc == "infer":
            which = "in" if rec.in_desc == "infer" else "out"
            findings.append(Finding(
                rule=RULE_UNSPECIFIED_JIT, severity="error",
                message=(f"program {rec.label!r} compiled on mesh "
                         f"[{rec.mesh_axes}] with UNSPECIFIED "
                         f"{which}_shardings — XLA is free to invent a "
                         "placement and a collective device-group order; "
                         "pass registry specs or the explicit INHERIT"),
                citation=rec.call_site, pass_name="sharding"))
    return findings
