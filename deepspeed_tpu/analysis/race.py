"""ds_doctor ``race`` pass — host-side concurrency analysis.

The compiled device program has ds_xray; the PYTHON HOST PROGRAM that keeps
a fleet alive (watchdog deadline threads, async checkpoint snapshots, the
serving worker + breaker, telemetry, gray microprobes) had nothing — and
every concurrency bug so far (the PR 7 submit-vs-record ABBA deadlock, the
half_open probe wedge, self-join-unsafe ``wait_for_pending_saves``) was
caught by human review after it shipped. Three static rules over the
package AST, plus the offline witness pass over the runtime order graph
recorded by the instrumented lock factory (utils/locks.py):

* ``race/lock-order`` — every lock acquisition (``with lock:``,
  ``.acquire()``) is extracted per module/class into the static
  lock-acquisition graph (analysis/lockgraph.py); interprocedural closure
  over resolvable calls; cycles are reported citing BOTH call sites. Lock
  identity is the order CLASS: factory locks carry their literal name,
  hand-rolled locks get ``module::Class.attr`` ids, and constructor
  injection (``CircuitBreaker(..., lock=rlock)``) / re-binding
  (``threading.Condition(rlock)``) union identities — the fixed
  frontend/breaker shared-RLock pattern is ONE node, not a false cycle.
  A non-reentrant class acquired under itself is a single-edge cycle.
* ``race/blocking-under-lock`` — ``time.sleep``, thread ``.join``,
  ``open``/subprocess, host collectives (``monitored_barrier``,
  ``allgather_host``), device syncs (``block_until_ready``/``device_get``)
  and engine dispatch (``train_batch``/``eval_batch``,
  ``wait_for_pending_saves``) inside a held framework lock — the exact
  class behind the breaker deadlock and the half_open wedge.
* ``race/signal-unsafe`` — a Python ``signal.signal`` handler may only set
  flags, log, poke os-level primitives, or call a function pre-registered
  via ``@signal_safe("justification")`` (utils/locks.py) — no lock
  acquisition, no arbitrary calls.

Deliberate exceptions are suppressed in code with a justified comment::

    # race-allow: blocking-under-lock — one in-flight snapshot by design
    with self._lock: ...

The lint verifies the justification is non-empty (``race/allow``
otherwise) and the rule name is real. Config-side, ``analysis.race_allowlist``
entries (``"race/<rule>[:substr]"``) filter findings whose citation or
message match.

Zero findings on the current tree is a tier-1 assertion
(tests/unit/test_race.py), exactly like ``sharding/unspecified-jit``.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deepspeed_tpu.analysis.findings import Finding
from deepspeed_tpu.analysis.jit_lint import _dotted, repo_script_paths
from deepspeed_tpu.analysis.lockgraph import Aliases, LockGraph

RULE_ORDER = "race/lock-order"
RULE_BLOCKING = "race/blocking-under-lock"
RULE_SIGNAL = "race/signal-unsafe"
RULE_WITNESS = "race/witness-inversion"
RULE_ALLOW = "race/allow"

RACE_RULES = (RULE_ORDER, RULE_BLOCKING, RULE_SIGNAL, RULE_WITNESS,
              RULE_ALLOW)

_ALLOW_RE = re.compile(
    r"#\s*race-allow:\s*([a-z-]+)\s*(?:[—–-]+\s*(.*?))?\s*$")

# blocking primitives flagged under a held lock: exact dotted names
_BLOCKING_EXACT = {
    "time.sleep", "open", "subprocess.run", "subprocess.call",
    "subprocess.check_call", "subprocess.check_output", "subprocess.Popen",
}
# ... and dotted suffixes (`.join` means thread/process join — constant-
# string `" ".join` has no dotted base and never matches; `os.path.join`
# is excluded explicitly)
_BLOCKING_SUFFIX = (
    ".join", ".monitored_barrier", ".allgather_host", ".block_until_ready",
    ".device_get", ".wait_for_pending_saves", ".train_batch", ".eval_batch",
)
_BLOCKING_BARE = {
    "monitored_barrier", "allgather_host", "wait_for_pending_saves",
}
_JOIN_EXCLUDED = (".path.join",)

# calls a signal handler may make without pre-registration: logging, os
# signal forwarding, interpreter/process exits, faulthandler
_SIGNAL_OK_PREFIX = ("logger.", "logging.", "log.", "faulthandler.",
                     "signal.", "os.", "sys.")
_SIGNAL_OK_EXACT = {"print", "log_dist", "repr", "str", "int", "format"}
_SIGNAL_OK_SUFFIX = (".send_signal", ".terminate", ".kill", ".set",
                     ".warning", ".info", ".error", ".debug", ".critical",
                     ".exception", ".write", ".flush")

_LOCK_FACTORIES = {
    "make_lock": "lock", "make_rlock": "rlock", "make_condition": "rlock",
}


# --------------------------------------------------------------- extraction
class _FnInfo:
    __slots__ = ("key", "relpath", "name", "cls", "node", "acquires",
                 "calls", "blocking", "signal_safe_just", "pushes")

    def __init__(self, key, relpath, name, cls, node):
        self.key = key
        self.relpath = relpath
        self.name = name
        self.cls = cls                  # simple class name or None
        self.node = node
        self.acquires: Dict[str, int] = {}      # lock id -> lineno
        # (callee_key_or_None, dotted, lineno, held snapshot tuple)
        self.calls: List[Tuple[Optional[str], str, int, tuple]] = []
        # (dotted, lineno, innermost held (id, lineno))
        self.blocking: List[Tuple[str, int, Tuple[str, int]]] = []
        self.signal_safe_just: Optional[str] = None
        # direct nested acquisitions: (held_id, held_line, got_id, got_line)
        self.pushes: List[Tuple[str, int, str, int]] = []


class _ClassInfo:
    __slots__ = ("name", "relpath", "attr_locks", "attr_types", "injectable",
                 "callback_params", "methods")

    def __init__(self, name, relpath):
        self.name = name
        self.relpath = relpath
        self.attr_locks: Dict[str, str] = {}        # attr -> lock id
        self.attr_types: Dict[str, str] = {}        # attr -> class simple name
        self.injectable: Dict[str, str] = {}        # __init__ param -> attr
        self.callback_params: Dict[str, str] = {}   # ctor param -> attr it lands on
        self.methods: Dict[str, str] = {}           # method name -> fn key


class _Module:
    __slots__ = ("relpath", "tree", "lines", "imports", "globals_locks",
                 "allow")

    def __init__(self, relpath, tree, lines):
        self.relpath = relpath
        self.tree = tree
        self.lines = lines
        self.imports: Dict[str, str] = {}       # alias -> dotted full name
        self.globals_locks: Dict[str, str] = {}  # module global -> lock id
        self.allow: Dict[int, Tuple[str, str]] = {}  # lineno -> (rule, just)


class _Tree:
    """Everything extracted from one package walk."""

    def __init__(self):
        self.modules: Dict[str, _Module] = {}
        self.classes: Dict[str, _ClassInfo] = {}        # simple name -> info
        self.fns: Dict[str, _FnInfo] = {}
        self.module_fns: Dict[Tuple[str, str], str] = {}  # (relpath, name) -> key
        # (class simple name, attr) -> fn keys wired in via ctor kwargs —
        # the historical ABBA entered through exactly such a callback
        # (CircuitBreaker(on_transition=frontend._on_breaker))
        self.callback_bindings: Dict[Tuple[str, str], set] = {}
        self.aliases = Aliases()
        self.handlers: List[Tuple[str, str, int]] = []  # (fn key, relpath, line)
        self.findings: List[Finding] = []


def _scan_allow_comments(mod: _Module) -> List[Finding]:
    out = []
    shorts = {r.split("/", 1)[1] for r in RACE_RULES}
    for i, line in enumerate(mod.lines, 1):
        m = _ALLOW_RE.search(line)
        if not m:
            continue
        rule, just = m.group(1), (m.group(2) or "").strip()
        if rule not in shorts:
            out.append(Finding(
                rule=RULE_ALLOW, severity="error",
                message=(f"race-allow comment names unknown rule {rule!r}; "
                         f"known: {sorted(shorts - {'allow'})}"),
                citation=f"{mod.relpath}:{i}", pass_name="race"))
            continue
        if not just:
            out.append(Finding(
                rule=RULE_ALLOW, severity="error",
                message=("race-allow comment has no justification — the "
                         "suppression contract is '# race-allow: <rule> — "
                         "why this is safe'"),
                citation=f"{mod.relpath}:{i}", pass_name="race"))
            continue
        mod.allow[i] = (rule, just)
    return out


def _allowed(mod: _Module, rule_short: str, *linenos: int) -> bool:
    """A finding is suppressed by a justified race-allow comment on the
    flagged line, up to two lines above it, or on the acquisition line of
    the held lock."""
    for ln in linenos:
        for probe in (ln, ln - 1, ln - 2):
            got = mod.allow.get(probe)
            if got and got[0] == rule_short:
                return True
    return False


def _lock_ctor(call: ast.Call, mod: _Module,
               fallback_id: str) -> Optional[Tuple[str, str, Optional[str]]]:
    """Classify a call as a lock constructor. Returns ``(lock_id, kind,
    alias_of)`` — kind in {lock, rlock}; ``alias_of`` is the *expression
    source* to union with (a Name fed to ``threading.Condition``)."""
    d = _dotted(call.func)
    if not d:
        return None
    leaf = d.rsplit(".", 1)[-1]
    if d in ("threading.Lock",) or (leaf == "Lock" and "threading" in d):
        return fallback_id, "lock", None
    if d in ("threading.RLock",) or (leaf == "RLock" and "threading" in d):
        return fallback_id, "rlock", None
    if leaf == "Condition":
        src = None
        if call.args and isinstance(call.args[0], ast.Name):
            src = call.args[0].id
        return fallback_id, "rlock", src
    if leaf in _LOCK_FACTORIES:
        full = mod.imports.get(d.split(".", 1)[0], "")
        known = (d in _LOCK_FACTORIES
                 or "locks" in d
                 or full.startswith("deepspeed_tpu"))
        if known and call.args and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, str):
            return call.args[0].value, _LOCK_FACTORIES[leaf], None
    return None


def _collect_imports(mod: _Module) -> None:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                mod.imports[a.asname or a.name.split(".", 1)[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                mod.imports[a.asname or a.name] = f"{node.module}.{a.name}"


def _signal_safe_just(node) -> Optional[str]:
    for dec in node.decorator_list:
        if isinstance(dec, ast.Call) and \
                _dotted(dec.func).rsplit(".", 1)[-1] == "signal_safe":
            if dec.args and isinstance(dec.args[0], ast.Constant) and \
                    isinstance(dec.args[0].value, str):
                return dec.args[0].value
            return ""       # decorated but unjustified -> race/allow
        if _dotted(dec).rsplit(".", 1)[-1] == "signal_safe":
            return ""
    return None


def _parse_tree(root: str, include_scripts: bool,
                skip_dirs=("__pycache__",)) -> _Tree:
    tree = _Tree()
    paths: List[Tuple[str, str]] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in skip_dirs]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, root).replace(os.sep, "/")
                paths.append((path, rel))
    if include_scripts:
        repo = os.path.dirname(root)
        for path in repo_script_paths(root):
            rel = os.path.relpath(path, repo).replace(os.sep, "/")
            paths.append((path, rel))

    for path, rel in paths:
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        try:
            node = ast.parse(src)
        except SyntaxError:
            continue        # the selflint pass reports syntax errors
        mod = _Module(rel, node, src.splitlines())
        _collect_imports(mod)
        tree.findings.extend(_scan_allow_comments(mod))
        tree.modules[rel] = mod
        _collect_defs(tree, mod)
    for mod in tree.modules.values():
        _analyze_module(tree, mod)
    _close_and_edges(tree)
    _signal_pass(tree)
    return tree


def _collect_defs(tree: _Tree, mod: _Module) -> None:
    """First pass over one module: classes, lock attributes/globals,
    function keys, injectable ctor params."""

    def fn_key(name: str, cls: Optional[str]) -> str:
        return f"{mod.relpath}::{cls + '.' if cls else ''}{name}"

    def visit_fns(body, cls: Optional[str], prefix: str = ""):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = f"{prefix}{node.name}"
                key = fn_key(name, cls)
                info = _FnInfo(key, mod.relpath, name, cls, node)
                info.signal_safe_just = _signal_safe_just(node)
                tree.fns[key] = info
                if cls:
                    tree.classes[cls].methods.setdefault(node.name, key)
                else:
                    tree.module_fns[(mod.relpath, name)] = key
                    if "." not in name:
                        tree.module_fns.setdefault((mod.relpath, node.name),
                                                   key)
                visit_fns(node.body, cls, prefix=f"{name}.")
            elif isinstance(node, ast.ClassDef) and cls is None:
                ci = tree.classes.setdefault(node.name,
                                             _ClassInfo(node.name,
                                                        mod.relpath))
                visit_fns(node.body, node.name)

    visit_fns(mod.tree.body, None)

    # module-global locks
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Call):
            got = _lock_ctor(node.value, mod,
                             f"{mod.relpath}::{node.targets[0].id}")
            if got:
                lock_id, kind, _ = got
                mod.globals_locks[node.targets[0].id] = lock_id
                tree.aliases.mark_reentrant(lock_id, kind == "rlock")

    # class attribute locks + injectable params + attr types
    for cls_node in [n for n in mod.tree.body if isinstance(n, ast.ClassDef)]:
        ci = tree.classes.get(cls_node.name)
        if ci is None or ci.relpath != mod.relpath:
            continue
        for meth in [n for n in cls_node.body
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))]:
            params = {a.arg for a in meth.args.args} if \
                meth.name == "__init__" else set()
            local_locks: Dict[str, str] = {}
            for st in ast.walk(meth):
                if not isinstance(st, ast.Assign) or len(st.targets) != 1:
                    continue
                tgt = st.targets[0]
                # local lock: rlock = make_rlock("...")
                if isinstance(tgt, ast.Name) and isinstance(st.value,
                                                            ast.Call):
                    got = _lock_ctor(
                        st.value, mod,
                        f"{mod.relpath}::{cls_node.name}.{meth.name}."
                        f"{tgt.id}")
                    if got:
                        local_locks[tgt.id] = got[0]
                        tree.aliases.mark_reentrant(got[0],
                                                    got[1] == "rlock")
                    continue
                if not (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    continue
                attr = tgt.attr
                attr_id = f"{mod.relpath}::{cls_node.name}.{attr}"
                val = st.value
                # self.X = P / self.X = P if ... else ctor / P or ctor
                # where P is an __init__ param: injectable identity
                inj_param, fallback = None, None
                if isinstance(val, ast.Name) and val.id in params:
                    inj_param = val.id
                elif isinstance(val, ast.IfExp) and \
                        isinstance(val.body, ast.Name) and \
                        val.body.id in params:
                    inj_param, fallback = val.body.id, val.orelse
                elif isinstance(val, ast.BoolOp) and \
                        isinstance(val.op, ast.Or) and \
                        isinstance(val.values[0], ast.Name) and \
                        val.values[0].id in params:
                    inj_param = val.values[0].id
                    fallback = val.values[-1]
                if inj_param is not None:
                    lock_id = attr_id
                    lockish = _param_is_lockish(meth, inj_param)
                    if isinstance(fallback, ast.Call):
                        got = _lock_ctor(fallback, mod, attr_id)
                        if got:
                            lock_id = got[0]
                            tree.aliases.mark_reentrant(lock_id,
                                                        got[1] == "rlock")
                            lockish = True
                    if lockish:
                        ci.attr_locks[attr] = lock_id
                        ci.injectable[inj_param] = attr
                        tree.aliases.union(attr_id, lock_id)
                    else:
                        # a ctor param stored on self: a callback slot —
                        # call sites wiring self.method into it make
                        # `self.<attr>()` resolvable (the ABBA entry path)
                        ci.callback_params[inj_param] = attr
                    continue
                if isinstance(val, ast.Call):
                    got = _lock_ctor(val, mod, attr_id)
                    if got:
                        lock_id, kind, alias_src = got
                        ci.attr_locks[attr] = lock_id
                        tree.aliases.union(attr_id, lock_id)
                        tree.aliases.mark_reentrant(lock_id, kind == "rlock")
                        if alias_src and alias_src in local_locks:
                            tree.aliases.union(lock_id,
                                               local_locks[alias_src])
                        continue
                    # self.X = ClassName(...): attr type for call resolution
                    t = _dotted(val.func).rsplit(".", 1)[-1]
                    if t and t[:1].isupper():
                        ci.attr_types[attr] = t
                elif isinstance(val, ast.Name) and val.id in local_locks:
                    ci.attr_locks[attr] = local_locks[val.id]
                    tree.aliases.union(attr_id, local_locks[val.id])


def _param_is_lockish(meth, param: str) -> bool:
    """A bare ``self.X = P`` is injectable only when the annotation or
    name says lock — plain data params must not become lock nodes."""
    for a in meth.args.args:
        if a.arg != param:
            continue
        ann = _dotted(a.annotation) if a.annotation is not None else ""
        if isinstance(a.annotation, ast.Subscript):
            ann = ast.dump(a.annotation)
        return "ock" in ann or "lock" in param.lower()
    return False


# ------------------------------------------------------- per-function walk
class _Ctx:
    __slots__ = ("tree", "mod", "fn", "cls", "local_locks")

    def __init__(self, tree, mod, fn, cls):
        self.tree = tree
        self.mod = mod
        self.fn = fn
        self.cls = cls
        self.local_locks: Dict[str, str] = {}


def _resolve_lock(expr, ctx: _Ctx) -> Optional[str]:
    if isinstance(expr, ast.Name):
        if expr.id in ctx.local_locks:
            return ctx.local_locks[expr.id]
        return ctx.mod.globals_locks.get(expr.id)
    if isinstance(expr, ast.Attribute) and \
            isinstance(expr.value, ast.Name) and expr.value.id == "self" \
            and ctx.cls is not None:
        return ctx.cls.attr_locks.get(expr.attr)
    if isinstance(expr, ast.Attribute):
        # module-qualified global: othermod._LOCK
        base = _dotted(expr.value)
        full = ctx.mod.imports.get(base)
        if full and full.startswith("deepspeed_tpu"):
            rel = full.replace("deepspeed_tpu.", "").replace(".", "/") + ".py"
            other = ctx.tree.modules.get(rel)
            if other:
                return other.globals_locks.get(expr.attr)
    return None


def _resolve_callee(call: ast.Call, dotted: str,
                    ctx: _Ctx) -> Optional[str]:
    tree, mod = ctx.tree, ctx.mod
    parts = dotted.split(".")
    if parts[0] == "self" and ctx.cls is not None:
        if len(parts) == 2:
            return ctx.cls.methods.get(parts[1])
        if len(parts) == 3:
            t = ctx.cls.attr_types.get(parts[1])
            ci = tree.classes.get(t) if t else None
            return ci.methods.get(parts[2]) if ci else None
        return None
    if len(parts) == 1:
        name = parts[0]
        key = tree.module_fns.get((mod.relpath, name))
        if key:
            return key
        ci = tree.classes.get(name)
        if ci:
            return ci.methods.get("__init__")
        full = mod.imports.get(name)
        if full and full.startswith("deepspeed_tpu."):
            modpath, _, leaf = full.rpartition(".")
            rel = modpath.replace("deepspeed_tpu.", "").replace(".", "/") \
                + ".py"
            key = tree.module_fns.get((rel, leaf))
            if key:
                return key
            ci = tree.classes.get(leaf)
            if ci and ci.relpath == rel:
                return ci.methods.get("__init__")
        return None
    full = mod.imports.get(parts[0])
    if full and full.startswith("deepspeed_tpu"):
        rel = full.replace("deepspeed_tpu.", "").replace(".", "/") + ".py"
        leaf = parts[-1]
        key = tree.module_fns.get((rel, leaf))
        if key:
            return key
        ci = tree.classes.get(leaf)
        if ci and ci.relpath == rel:
            return ci.methods.get("__init__")
    return None


def _is_blocking(dotted: str) -> bool:
    if dotted in _BLOCKING_EXACT or dotted in _BLOCKING_BARE:
        return True
    for ex in _JOIN_EXCLUDED:
        if dotted.endswith(ex):
            return False
    return any(dotted.endswith(s) for s in _BLOCKING_SUFFIX)


def _calls_in(node) -> List[ast.Call]:
    """Call nodes within an expression/statement, NOT descending into
    nested function/class definitions (they are their own scopes)."""
    out: List[ast.Call] = []
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef, ast.Lambda)):
            continue
        if isinstance(n, ast.Call):
            out.append(n)
        stack.extend(ast.iter_child_nodes(n))
    return out


def _on_calls(node, held: List[Tuple[str, int]], ctx: _Ctx) -> None:
    fn, tree = ctx.fn, ctx.tree
    for call in _calls_in(node):
        d = _dotted(call.func)
        if not d:
            continue
        leaf = d.rsplit(".", 1)[-1]
        if leaf in ("acquire", "release"):
            continue        # handled by the statement walker
        if held and _is_blocking(d):
            fn.blocking.append((d, call.lineno, held[-1]))
        key = _resolve_callee(call, d, ctx)
        fn.calls.append((key, d, call.lineno, tuple(held)))
        # constructor injection: Class(..., lock=<id>) unions the callee's
        # injectable attr with the passed identity; Class(..., on_x=
        # self.method) binds the callback slot so `self.<attr>()` in the
        # callee resolves back to the wired method
        ci = tree.classes.get(leaf)
        if ci is not None and (ci.injectable or ci.callback_params):
            for kw in call.keywords:
                if kw.arg is None:
                    continue
                if kw.arg in ci.injectable:
                    lock_id = _resolve_lock(kw.value, ctx)
                    if lock_id:
                        attr = ci.injectable[kw.arg]
                        tree.aliases.union(
                            f"{ci.relpath}::{ci.name}.{attr}", lock_id)
                if kw.arg in ci.callback_params:
                    kd = _dotted(kw.value)
                    mkey = None
                    if kd.startswith("self.") and kd.count(".") == 1 \
                            and ctx.cls is not None:
                        mkey = ctx.cls.methods.get(kd.split(".")[1])
                    elif kd and "." not in kd:
                        mkey = tree.module_fns.get((ctx.mod.relpath, kd))
                    if mkey:
                        tree.callback_bindings.setdefault(
                            (ci.name, ci.callback_params[kw.arg]),
                            set()).add(mkey)


def _walk_fn(tree: _Tree, mod: _Module, fn: _FnInfo,
             cls: Optional[_ClassInfo]) -> None:
    ctx = _Ctx(tree, mod, fn, cls)
    held: List[Tuple[str, int]] = []

    def push(lock_id: str, lineno: int) -> None:
        for h_id, h_line in held:
            fn.pushes.append((h_id, h_line, lock_id, lineno))
        if lock_id not in fn.acquires:
            fn.acquires[lock_id] = lineno
        held.append((lock_id, lineno))

    def walk(stmts: list) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, ast.Assign) and isinstance(st.value, ast.Call) \
                    and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Name):
                got = _lock_ctor(
                    st.value, mod,
                    f"{mod.relpath}::{fn.cls + '.' if fn.cls else ''}"
                    f"{fn.name}.{st.targets[0].id}")
                if got:
                    ctx.local_locks[st.targets[0].id] = got[0]
                    tree.aliases.mark_reentrant(got[0], got[1] == "rlock")
            if isinstance(st, (ast.With, ast.AsyncWith)):
                pushed = 0
                for item in st.items:
                    lock_id = _resolve_lock(item.context_expr, ctx)
                    if lock_id:
                        push(lock_id, item.context_expr.lineno)
                        pushed += 1
                    else:
                        _on_calls(item.context_expr, held, ctx)
                walk(st.body)
                for _ in range(pushed):
                    held.pop()
                continue
            if isinstance(st, ast.Expr) and isinstance(st.value, ast.Call):
                d = _dotted(st.value.func)
                if d.endswith(".acquire"):
                    lock_id = _resolve_lock(st.value.func.value, ctx)
                    if lock_id:
                        push(lock_id, st.lineno)
                        continue
                elif d.endswith(".release"):
                    lock_id = _resolve_lock(st.value.func.value, ctx)
                    if lock_id:
                        for i in range(len(held) - 1, -1, -1):
                            if held[i][0] == lock_id:
                                del held[i]
                                break
                        continue
            _on_calls(_headers_of(st), held, ctx)
            for body in _bodies_of(st):
                walk(body)

    walk(fn.node.body)


def _headers_of(st) -> ast.AST:
    """The statement's own expressions (test/iter/value/...) as a scannable
    node, excluding nested block bodies (walked with their own held
    state)."""
    if isinstance(st, ast.If) or isinstance(st, ast.While):
        return st.test
    if isinstance(st, ast.For):
        return st.iter
    if isinstance(st, (ast.Try,)):
        return ast.Pass()
    return st


def _bodies_of(st) -> List[list]:
    out = []
    for field in ("body", "orelse", "finalbody"):
        body = getattr(st, field, None)
        if isinstance(body, list) and body and isinstance(body[0], ast.stmt):
            out.append(body)
    for h in getattr(st, "handlers", ()) or ():
        out.append(h.body)
    return out


def _analyze_module(tree: _Tree, mod: _Module) -> None:
    for fn in [f for f in tree.fns.values() if f.relpath == mod.relpath]:
        cls = tree.classes.get(fn.cls) if fn.cls else None
        _walk_fn(tree, mod, fn, cls)
    # signal handler registrations (any scope)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or \
                _dotted(node.func) != "signal.signal":
            continue
        if len(node.args) < 2:
            continue
        h = node.args[1]
        hd = _dotted(h)
        key = None
        if hd.startswith("self.") and hd.count(".") == 1:
            cls_name = _class_at(mod.tree, node.lineno)
            ci = tree.classes.get(cls_name) if cls_name else None
            key = ci.methods.get(hd.split(".")[1]) if ci else None
        elif hd and "." not in hd:
            key = _nested_fn_at(tree, mod, node.lineno, hd) or \
                tree.module_fns.get((mod.relpath, hd))
        if key:
            tree.handlers.append((key, mod.relpath, node.lineno))


def _class_at(tree_node: ast.AST, lineno: int) -> Optional[str]:
    for node in ast.walk(tree_node):
        if isinstance(node, ast.ClassDef):
            end = getattr(node, "end_lineno", None)
            if end is not None and node.lineno <= lineno <= end:
                return node.name
    return None


def _nested_fn_at(tree: _Tree, mod: _Module, lineno: int,
                  name: str) -> Optional[str]:
    """A handler defined in the registering function's scope — the common
    ``def _on_signal(...)`` nested in ``install_signal_handlers``."""
    best = None
    for fn in tree.fns.values():
        if fn.relpath != mod.relpath:
            continue
        if fn.name.rsplit(".", 1)[-1] != name:
            continue
        end = getattr(fn.node, "end_lineno", 0)
        node = fn.node
        # prefer the def lexically closest above the registration
        if node.lineno <= lineno and (best is None
                                      or node.lineno > best.node.lineno):
            best = fn
    return best.key if best else None


# ------------------------------------------------------ closure + findings
def _targets(tree: _Tree, fn: _FnInfo, callee: Optional[str],
             dotted: str) -> List[str]:
    """Resolved callees of one recorded call: the directly-resolved key,
    or — for a ``self.<attr>()`` callback slot — every method wired into
    that slot at a constructor call site."""
    if callee is not None:
        return [callee]
    parts = dotted.split(".")
    if parts[0] == "self" and len(parts) == 2 and fn.cls:
        return sorted(tree.callback_bindings.get((fn.cls, parts[1]), ()))
    return []


def _close_and_edges(tree: _Tree) -> None:
    """Interprocedural may-acquire closure, then the global order graph."""
    closure: Dict[str, Dict[str, Tuple[str, int]]] = {}
    for key, fn in tree.fns.items():
        closure[key] = {lid: (fn.relpath, line)
                        for lid, line in fn.acquires.items()}
    changed = True
    rounds = 0
    while changed and rounds < 50:
        changed = False
        rounds += 1
        for key, fn in tree.fns.items():
            mine = closure[key]
            for callee, dotted, _, _ in fn.calls:
                for target in _targets(tree, fn, callee, dotted):
                    if target == key:
                        continue
                    for lid, site in closure.get(target, {}).items():
                        if lid not in mine:
                            mine[lid] = site
                            changed = True
    tree.closure = closure      # type: ignore[attr-defined]

    graph = LockGraph()
    canon = tree.aliases.find

    def add(src, src_rel, src_line, dst, dst_rel, dst_line):
        cs, cd = canon(src), canon(dst)
        if cs == cd:
            if tree.aliases.is_reentrant(src) or tree.aliases.is_reentrant(dst):
                return      # reentrant same-class nesting is legal
        graph.add_edge(cs, cd, f"{src_rel}:{src_line}",
                       f"{dst_rel}:{dst_line}")

    for fn in tree.fns.values():
        for h_id, h_line, g_id, g_line in fn.pushes:
            add(h_id, fn.relpath, h_line, g_id, fn.relpath, g_line)
        for callee, dotted, line, held in fn.calls:
            if not held:
                continue
            for target in _targets(tree, fn, callee, dotted):
                for lid, (rel, acq_line) in closure.get(target, {}).items():
                    for h_id, h_line in held:
                        add(h_id, fn.relpath, h_line, lid, rel, acq_line)

    tree.graph = graph      # type: ignore[attr-defined]
    for cyc in graph.cycles():
        nodes = [e[0] for e in cyc]
        chain = "; ".join(
            f"{src} -> {dst} (holding {_short(src)} at {s_site}, "
            f"acquires {_short(dst)} at {d_site})"
            for src, dst, s_site, d_site in cyc)
        first = cyc[0]
        mod = tree.modules.get(first[2].rsplit(":", 1)[0])
        lines = [int(e[2].rsplit(":", 1)[1]) for e in cyc
                 if e[2].rsplit(":", 1)[0] == (mod.relpath if mod else "")]
        if mod is not None and _allowed(mod, "lock-order", *lines):
            continue
        tree.findings.append(Finding(
            rule=RULE_ORDER, severity="error",
            message=(f"lock-order cycle over {{{', '.join(_short(n) for n in nodes)}}} "
                     f"— the ABBA deadlock shape: {chain}. Two threads "
                     "entering from different edges wedge forever; impose "
                     "one global order or share one lock "
                     "(utils/locks.py factory names make the order "
                     "auditable)"),
            citation=first[2], pass_name="race"))

    # blocking-under-lock
    for fn in tree.fns.values():
        mod = tree.modules[fn.relpath]
        for d, line, (h_id, h_line) in fn.blocking:
            if _allowed(mod, "blocking-under-lock", line, h_line):
                continue
            tree.findings.append(Finding(
                rule=RULE_BLOCKING, severity="error",
                message=(f"blocking call {d}() at {fn.relpath}:{line} runs "
                         f"inside held lock {_short(tree.aliases.find(h_id))!r} "
                         f"(acquired {fn.relpath}:{h_line}) — every other "
                         "thread needing the lock stalls for the full "
                         "duration (the breaker-deadlock / half_open-wedge "
                         "class); move the blocking work outside the "
                         "critical section or justify with "
                         "'# race-allow: blocking-under-lock — why'"),
                citation=f"{fn.relpath}:{line}", pass_name="race"))


def _short(lock_id: str) -> str:
    return lock_id.rsplit("::", 1)[-1]


def _signal_pass(tree: _Tree) -> None:
    for key, reg_rel, reg_line in sorted(set(tree.handlers)):
        fn = tree.fns.get(key)
        if fn is None:
            continue
        mod = tree.modules[fn.relpath]
        # lock acquisition inside the handler body
        for lid, line in fn.acquires.items():
            if _allowed(mod, "signal-unsafe", line):
                continue
            tree.findings.append(Finding(
                rule=RULE_SIGNAL, severity="error",
                message=(f"signal handler {fn.name!r} (registered at "
                         f"{reg_rel}:{reg_line}) acquires lock "
                         f"{_short(tree.aliases.find(lid))!r} — a handler "
                         "interrupting the holder thread deadlocks on a "
                         "non-reentrant lock; handlers may only set flags "
                         "or call @signal_safe paths"),
                citation=f"{fn.relpath}:{line}", pass_name="race"))
        for callee, d, line, _held in fn.calls:
            if _signal_call_ok(tree, d, callee):
                continue
            if _allowed(mod, "signal-unsafe", line):
                continue
            tree.findings.append(Finding(
                rule=RULE_SIGNAL, severity="error",
                message=(f"signal handler {fn.name!r} (registered at "
                         f"{reg_rel}:{reg_line}) calls {d}() — not a flag "
                         "set, a logger, an os-level signal primitive, or a "
                         "function pre-registered with "
                         "@signal_safe('why'); handlers run between "
                         "bytecodes of ANY main-thread code and must not "
                         "do open-ended work"),
                citation=f"{fn.relpath}:{line}", pass_name="race"))
    # signal_safe decorators must carry a justification
    for fn in tree.fns.values():
        if fn.signal_safe_just == "":
            tree.findings.append(Finding(
                rule=RULE_ALLOW, severity="error",
                message=(f"@signal_safe on {fn.name!r} has no justification "
                         "— the pre-registration contract is "
                         "@signal_safe('why this path is async-safe')"),
                citation=f"{fn.relpath}:{fn.node.lineno}", pass_name="race"))


def _signal_call_ok(tree: _Tree, dotted: str,
                    callee: Optional[str]) -> bool:
    if callee is not None:
        target = tree.fns.get(callee)
        if target is not None and target.signal_safe_just:
            return True
    if dotted in _SIGNAL_OK_EXACT:
        return True
    if dotted.startswith(_SIGNAL_OK_PREFIX):
        return True
    leaf_ok = any(dotted.endswith(s) for s in _SIGNAL_OK_SUFFIX)
    return leaf_ok


# ------------------------------------------------------------- public API
_LINT_CACHE: Dict[Tuple[str, bool], List[Finding]] = {}


def lint_race(root: Optional[str] = None, include_scripts: bool = True,
              allowlist: Sequence[str] = ()) -> List[Finding]:
    """The three static rules over the package (and, by default, the repo
    entry scripts ``bin/*``). Memoized per root like the
    unspecified-jit lint — the source tree does not change mid-process.
    ``allowlist`` entries (``analysis.race_allowlist``) are
    ``"race/<rule>[:substr]"``; matching findings are filtered, unknown
    rules get a warning."""
    if root is None:
        import deepspeed_tpu

        root = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    key = (root, include_scripts)
    if key not in _LINT_CACHE:
        _LINT_CACHE[key] = list(_parse_tree(root, include_scripts).findings)
    return _apply_allowlist(list(_LINT_CACHE[key]), allowlist)


def analyze_tree(root: Optional[str] = None,
                 include_scripts: bool = True) -> _Tree:
    """The full extraction (lock graph + closure), for tooling/tests."""
    if root is None:
        import deepspeed_tpu

        root = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    return _parse_tree(root, include_scripts)


def _apply_allowlist(findings: List[Finding],
                     allowlist: Sequence[str]) -> List[Finding]:
    if not allowlist:
        return findings
    keep: List[Finding] = []
    rules_short = {r.split("/", 1)[1]: r for r in RACE_RULES}
    parsed = []
    for entry in allowlist:
        rule, _, substr = str(entry).partition(":")
        rule = rule.strip()
        if rule.startswith("race/"):
            rule = rule.split("/", 1)[1]
        if rule not in rules_short:
            findings.append(Finding(
                rule=RULE_ALLOW, severity="warning",
                message=(f"analysis.race_allowlist entry {entry!r} names "
                         f"unknown rule {rule!r}; known: "
                         f"{sorted(rules_short)}"),
                citation="analysis.race_allowlist", pass_name="race"))
            continue
        parsed.append((rules_short[rule], substr))
    for f in findings:
        suppressed = any(
            f.rule == rule and (not substr or substr in (f.citation or "")
                                or substr in f.message)
            for rule, substr in parsed)
        if not suppressed:
            keep.append(f)
    return keep


def witness_findings(edges: Optional[List[Dict[str, Any]]] = None
                     ) -> List[Finding]:
    """The offline witness pass: union the observed per-thread acquisition
    order graph (utils/locks.py, or a saved ``--witness`` JSON) and flag
    inversions — the ABBA that has not deadlocked YET. Both first-seen
    sites are named."""
    if edges is None:
        from deepspeed_tpu.utils.locks import witness_edges

        edges = witness_edges()
    graph = LockGraph()
    for e in edges:
        if e["src"] == e["dst"]:
            continue        # reentrant same-class nesting
        graph.add_edge(e["src"], e["dst"], e["src_site"], e["dst_site"])
    findings: List[Finding] = []
    for cyc in graph.cycles():
        chain = "; ".join(
            f"{src} -> {dst} (held at {s_site}, acquired at {d_site})"
            for src, dst, s_site, d_site in cyc)
        findings.append(Finding(
            rule=RULE_WITNESS, severity="error",
            message=("runtime lock witness observed BOTH orders over "
                     f"{{{', '.join(e[0] for e in cyc)}}}: {chain}. No "
                     "deadlock manifested this run — two threads entering "
                     "concurrently from different edges WILL wedge; impose "
                     "one global order"),
            citation=cyc[0][3], pass_name="race"))
    return findings


def load_witness(path: str) -> List[Dict[str, Any]]:
    import json

    with open(path) as f:
        data = json.load(f)
    return list(data.get("edges", []))
