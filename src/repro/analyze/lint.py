"""Runtime-invariant static lint — repo-specific AST rules for src/repro.

Each rule guards an invariant this codebase has already been burned by (the
rule catalog with the incident history lives in docs/SANITIZER.md):

``waitfree-blocking``
    No blocking or spinning calls (``sleep``/``wait``/``acquire``/``join``/
    ``notify``/``spin``) inside the wait-free ASM sections of
    ``core/asm.py`` (MailBox delivery, transition rules, register/
    unregister). The wait-freedom proof of paper §2.3 is void the moment a
    delivery can block. MailBoxPool is exempt: the pool is locked by
    design and sits outside the delivery path.

``lock-try-finally``
    Every ``X.lock()`` statement must be immediately followed by a
    ``try:`` whose ``finally:`` calls ``X.unlock()`` — a raising body
    between the two leaks the lock and deadlocks every worker (the exact
    PR-2 bug class in the scheduler).

``event-catalog``
    ``tracer.event(name, ...)`` names must be string literals present in
    the ``EVENTS`` catalog of ``core/instrument.py`` (or registered via
    ``register_event``), and so must the begin and end events of every
    ``tracer.span(name, ...)``. Ad-hoc names serialize as event id 0 and
    make the binary trace unparseable.

``shared-random``
    No module-level ``random.*`` calls in ``core/`` worker code: the
    shared global RNG is a cross-thread contention point and makes victim
    sequences depend on interleaving. Construct a per-worker
    ``random.Random(seed)`` instead.

``task-retention``
    A ``spawn(...)`` result stored anywhere that outlives the local frame
    (attribute, subscript, container ``append``/``add``/``put``, or a
    ``@dataclass`` constructor field — the instance carries the task out
    of the frame) must be spawned with ``retain=True`` or ``handle=True``
    — a bare pooled Task held across its completion silently becomes a
    different logical task.

Suppression: append ``# lint: ok(rule-id)`` to the flagged line (or the
line above) with a short justification after it.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Iterable, Optional

from repro.core.instrument import span_events

RULES = {
    "waitfree-blocking": "blocking/spinning call inside a wait-free ASM "
                         "section",
    "lock-try-finally": "lock() not paired with try/finally unlock()",
    "event-catalog": "trace event name not in the EVENTS catalog",
    "shared-random": "module-level shared random.* call in worker code",
    "task-retention": "spawn() result retained beyond the local frame "
                      "without retain=True/handle=True",
}

_SUPPRESS_RE = re.compile(r"#.*?lint:\s*ok\(([a-z-]+)\)")

# waitfree-blocking scope: these classes in core/asm.py ARE the wait-free
# sections; MailBoxPool (locked by design, off the delivery path) is not
_WAITFREE_CLASSES = {"MailBox", "WaitFreeDependencySystem", "DataAccess",
                     "DataAccessMessage"}
_BLOCKING_ATTRS = {"sleep", "wait", "acquire", "join", "notify",
                   "notify_all"}
_BLOCKING_NAMES = {"sleep", "spin"}

_ESCAPE_METHODS = {"append", "add", "put"}


class Finding:
    __slots__ = ("file", "line", "rule", "message")

    def __init__(self, file: str, line: int, rule: str, message: str):
        self.file = file
        self.line = line
        self.rule = rule
        self.message = message

    def __repr__(self):
        return f"{self.file}:{self.line}: [{self.rule}] {self.message}"


def _iter_py(paths: Iterable[str]):
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            yield p
        elif os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def _suppressions(src: str) -> dict:
    """line -> set(rule ids) suppressed there (the marked line and the
    one below it, so the comment can sit above a long statement)."""
    out: dict = {}
    for i, line in enumerate(src.splitlines(), 1):
        for m in _SUPPRESS_RE.finditer(line):
            out.setdefault(i, set()).add(m.group(1))
            out.setdefault(i + 1, set()).add(m.group(1))
    return out


def _catalog_from_instrument(tree: ast.Module) -> Optional[set]:
    """Literal keys of the EVENTS dict in core/instrument.py."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
            if "EVENTS" in targets and isinstance(node.value, ast.Dict):
                keys = set()
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) and isinstance(k.value,
                                                                  str):
                        keys.add(k.value)
                return keys
    return None


def _recv_src(node: ast.expr) -> str:
    """Stable textual key for a lock receiver expression."""
    return ast.dump(node)


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: str, tree: ast.Module, catalog: set,
                 registered: set, findings: list):
        self.path = path
        self.tree = tree
        self.catalog = catalog
        self.registered = registered
        self.findings = findings
        self.norm = path.replace(os.sep, "/")
        self.in_core = "/core/" in self.norm or \
            self.norm.endswith(("core/asm.py",))
        self.is_asm = self.norm.endswith("core/asm.py")
        self._class_stack: list = []
        self._dataclasses = self._collect_dataclasses(tree)

    @staticmethod
    def _collect_dataclasses(tree: ast.Module) -> set:
        """Names of @dataclass-decorated classes in this module: their
        constructors store every argument in a field, so passing a task
        into one is a frame escape."""
        out: set = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for dec in node.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                name = d.attr if isinstance(d, ast.Attribute) else \
                    getattr(d, "id", None)
                if name == "dataclass":
                    out.add(node.name)
        return out

    def emit(self, node: ast.AST, rule: str, message: str):
        self.findings.append(
            Finding(self.path, getattr(node, "lineno", 0), rule, message))

    # -------------------------------------------------- class scope
    def visit_ClassDef(self, node: ast.ClassDef):
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()

    def _in_waitfree_section(self) -> bool:
        return self.is_asm and bool(self._class_stack) and \
            self._class_stack[-1] in _WAITFREE_CLASSES

    # -------------------------------------------------- calls
    def visit_Call(self, node: ast.Call):
        fn = node.func
        # waitfree-blocking
        if self._in_waitfree_section():
            if isinstance(fn, ast.Attribute) and \
                    fn.attr in _BLOCKING_ATTRS:
                self.emit(node, "waitfree-blocking",
                          f".{fn.attr}() may block inside a wait-free "
                          "ASM section — deliveries must stay "
                          "non-blocking (paper §2.3)")
            elif isinstance(fn, ast.Name) and fn.id in _BLOCKING_NAMES:
                self.emit(node, "waitfree-blocking",
                          f"{fn.id}() inside a wait-free ASM section")
        # shared-random
        if self.in_core and isinstance(fn, ast.Attribute) and \
                isinstance(fn.value, ast.Name) and \
                fn.value.id == "random" and \
                fn.attr not in ("Random", "SystemRandom"):
            self.emit(node, "shared-random",
                      f"random.{fn.attr}() uses the shared global RNG "
                      "from worker code; use a per-worker "
                      "random.Random(seed)")
        # event-catalog
        if isinstance(fn, ast.Attribute) and fn.attr in ("event", "span") \
                and node.args:
            name = node.args[0]
            if isinstance(name, ast.Constant) and isinstance(name.value,
                                                             str):
                names = span_events(name.value) if fn.attr == "span" \
                    else (name.value,)
                for n in names:
                    if n not in self.catalog and n not in self.registered:
                        self.emit(node, "event-catalog",
                                  f"event name {n!r} is not in "
                                  "core/instrument.py EVENTS (id 0 in the "
                                  "binary stream)")
            else:
                self.emit(node, "event-catalog",
                          f"non-literal trace {fn.attr} name cannot be "
                          "checked against the catalog")
        self.generic_visit(node)

    # -------------------------------------------------- statement lists
    def _check_body(self, body: list):
        for i, stmt in enumerate(body):
            if isinstance(stmt, ast.Expr) and \
                    isinstance(stmt.value, ast.Call) and \
                    isinstance(stmt.value.func, ast.Attribute) and \
                    stmt.value.func.attr == "lock":
                recv = _recv_src(stmt.value.func.value)
                nxt = body[i + 1] if i + 1 < len(body) else None
                if not self._releases_in_finally(nxt, recv):
                    self.emit(stmt, "lock-try-finally",
                              "lock() must be immediately followed by "
                              "try/finally unlock() on the same lock — "
                              "a raising body leaks the lock and "
                              "deadlocks every worker")

    @staticmethod
    def _releases_in_finally(stmt, recv: str) -> bool:
        if not isinstance(stmt, ast.Try) or not stmt.finalbody:
            return False
        for fin in ast.walk(ast.Module(body=stmt.finalbody,
                                       type_ignores=[])):
            if isinstance(fin, ast.Call) and \
                    isinstance(fin.func, ast.Attribute) and \
                    fin.func.attr == "unlock" and \
                    _recv_src(fin.func.value) == recv:
                return True
        return False

    def _walk_bodies(self, node):
        for child in ast.walk(node):
            for field in ("body", "orelse", "finalbody"):
                body = getattr(child, field, None)
                if isinstance(body, list) and body and \
                        isinstance(body[0], ast.stmt):
                    self._check_body(body)
            for handler in getattr(child, "handlers", []) or []:
                self._check_body(handler.body)

    # -------------------------------------------------- task retention
    def _check_retention(self, fn_node):
        tainted: set = set()
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and \
                    self._is_unretained_spawn(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tainted.add(tgt.id)
                    else:
                        self.emit(node, "task-retention",
                                  "spawn() result stored beyond the "
                                  "local frame without retain=True/"
                                  "handle=True — the pooled Task may be "
                                  "recycled into a different logical "
                                  "task")
        # no early-out on empty taint: an unretained spawn() passed inline
        # into a dataclass constructor escapes without ever naming a local
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id in tainted:
                for tgt in node.targets:
                    if not isinstance(tgt, ast.Name):
                        self.emit(node, "task-retention",
                                  f"local {node.value.id!r} holds an "
                                  "unretained spawn() result; storing "
                                  "it beyond the frame needs "
                                  "retain=True/handle=True")
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _ESCAPE_METHODS:
                for arg in node.args:
                    if isinstance(arg, ast.Name) and arg.id in tainted:
                        self.emit(node, "task-retention",
                                  f"unretained spawn() result "
                                  f"{arg.id!r} escapes via "
                                  f".{node.func.attr}(); spawn with "
                                  "retain=True/handle=True")
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name) and \
                    node.func.id in self._dataclasses:
                for arg in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    if (isinstance(arg, ast.Name) and arg.id in tainted) \
                            or self._is_unretained_spawn(arg):
                        held = arg.id if isinstance(arg, ast.Name) \
                            else "spawn() result"
                        self.emit(node, "task-retention",
                                  f"unretained {held!s} escapes into "
                                  f"dataclass {node.func.id} field — the "
                                  "instance outlives the frame; spawn "
                                  "with retain=True/handle=True")

    @staticmethod
    def _is_unretained_spawn(value) -> bool:
        if not (isinstance(value, ast.Call) and
                isinstance(value.func, ast.Attribute) and
                value.func.attr == "spawn"):
            return False
        for kw in value.keywords:
            if kw.arg in ("retain", "handle") and \
                    isinstance(kw.value, ast.Constant) and \
                    kw.value.value is True:
                return False
        return True

    # -------------------------------------------------- entry
    def run(self):
        self.visit(self.tree)
        self._walk_bodies(self.tree)
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_retention(node)


def run_lint(paths: Iterable[str],
             instrument_path: Optional[str] = None) -> list:
    """Lint the given files/directories; returns a list of Finding."""
    files = list(_iter_py(paths))
    trees: dict = {}
    sources: dict = {}
    for path in files:
        with open(path) as f:
            src = f.read()
        sources[path] = src
        trees[path] = ast.parse(src, filename=path)

    # event catalog: the EVENTS literal in core/instrument.py (from the
    # linted set, or the explicit instrument_path) + register_event calls
    catalog: set = set()
    for path, tree in trees.items():
        if path.replace(os.sep, "/").endswith("core/instrument.py"):
            catalog = _catalog_from_instrument(tree) or set()
    if not catalog and instrument_path:
        with open(instrument_path) as f:
            catalog = _catalog_from_instrument(
                ast.parse(f.read())) or set()
    registered: set = set()
    for tree in trees.values():
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (
                    (isinstance(node.func, ast.Name) and
                     node.func.id == "register_event") or
                    (isinstance(node.func, ast.Attribute) and
                     node.func.attr == "register_event")):
                if node.args and isinstance(node.args[0], ast.Constant):
                    registered.add(node.args[0].value)

    findings: list = []
    for path in files:
        raw: list = []
        _FileLinter(path, trees[path], catalog, registered, raw).run()
        supp = _suppressions(sources[path])
        for f in raw:
            if f.rule in supp.get(f.line, ()):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings
