"""lock-discipline: cross-thread access to lock-guarded attributes.

The loom-shaped pass: for every class that owns a lock, infer which
attributes the lock guards (attributes WRITTEN inside a `with self._lock:`
region), infer the class's thread roots (`threading.Thread(target=...)`
call sites — methods and nested closures alike — plus the implicit
"external caller" root entered through public methods), and flag any
access of guarded state that happens outside the lock while the attribute
is touched from more than one root. `__init__` is exempt (construction
happens-before every thread start).

Per-class, lexical, one parse: this deliberately does NOT chase guard
state through helper calls. A helper whose caller holds the lock has
three ways to say so, in order of preference: take the lock itself
(RLocks make that free), carry the `_locked` name suffix (the
CPython/Chromium convention — the suffix asserts "caller holds the class
lock" and the method body is scanned as guarded), or put the field on
the allowlist below with its justification.
"""

from __future__ import annotations

import ast

from ..astutil import (
    MUTATORS,
    base_self_attr_of_target,
    class_lock_attrs,
    self_attr,
    terminal_name,
    with_lock_names,
    write_targets,
)
from ..core import Finding, Project, Rule, SourceFile

#: (class name, attribute) pairs that are intentionally lock-free. Every
#: entry carries its justification; "*" matches any class.
ALLOW_LOCK_FREE = {
    # the session cancel token: setting/checking a threading.Event is atomic
    # by design, so a CancelRequest never queues behind the statement it is
    # trying to stop (adapter/dyncfg.py SessionConfigs docstring)
    ("*", "cancelled"),
    # advisory degradation flag: all WRITES happen under _cmd_lock; reads
    # poll it lock-free on purpose — a stale read only delays one heal poll
    # and never corrupts state (cluster/controller.py)
    ("ShardedComputeController", "degraded"),
    # the attribute is assigned exactly once in __init__ and never rebound;
    # _Inbox carries its OWN Condition internally, and delivery/collection
    # are epoch-keyed so stale traffic lands in dead slots (cluster/mesh.py)
    ("WorkerMesh", "inbox"),
}

SCOPE_DIRS = (
    "materialize_tpu/adapter/",
    "materialize_tpu/egress/",
    "materialize_tpu/cluster/",
    "materialize_tpu/frontend/",
    "materialize_tpu/persist/",
    "materialize_tpu/storage/",
    "materialize_tpu/obs/",
    "materialize_tpu/orchestrator/",
)


class _Access:
    __slots__ = ("attr", "line", "write", "guarded", "func")

    def __init__(self, attr, line, write, guarded, func):
        self.attr = attr
        self.line = line
        self.write = write
        self.guarded = guarded
        self.func = func  # key of the enclosing function


class _FuncScan(ast.NodeVisitor):
    """Walk ONE function body (not descending into nested defs) recording
    self-attribute accesses, self-method calls, and thread spawns."""

    def __init__(self, cls_scan, key, guard_depth=0):
        self.cls = cls_scan
        self.key = key
        self.guard_depth = guard_depth
        self.accesses: list[_Access] = []
        self.calls: set = set()
        self.thread_targets: list = []  # keys of spawned roots

    # -- helpers -------------------------------------------------------------

    def _record(self, attr, line, write):
        if attr in self.cls.lock_attrs:
            return
        self.accesses.append(
            _Access(attr, line, write, self.guard_depth > 0, self.key)
        )

    def _scan_expr(self, node):
        """Record loads (and property-call edges) in an expression tree."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and isinstance(sub.ctx, ast.Load):
                attr = self_attr(sub)
                if attr:
                    self._record(attr, sub.lineno, write=False)
                    if attr in self.cls.properties:
                        self.calls.add((attr, None))

    # -- statements ----------------------------------------------------------

    def visit_With(self, node: ast.With):
        locks = with_lock_names(node)
        for item in node.items:
            self.generic_visit(item)
        if locks:
            self.guard_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        if locks:
            self.guard_depth -= 1

    def visit_Assign(self, node):
        self._handle_store(node)

    def visit_AugAssign(self, node):
        self._handle_store(node)

    def visit_AnnAssign(self, node):
        self._handle_store(node)

    def visit_Delete(self, node):
        self._handle_store(node)

    def _handle_store(self, node):
        for tgt in write_targets(node):
            attr = base_self_attr_of_target(tgt)
            if attr:
                self._record(attr, node.lineno, write=True)
            # subscript stores also READ the container expression
            self._scan_expr(tgt)
        value = getattr(node, "value", None)
        if value is not None:
            self.visit(value)

    def visit_Call(self, node: ast.Call):
        fn = node.func
        # thread spawn: threading.Thread(target=self.m) / Thread(target=f)
        if terminal_name(fn) == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    attr = self_attr(kw.value)
                    if attr:
                        self.thread_targets.append((attr, None))
                    elif isinstance(kw.value, ast.Name):
                        self.thread_targets.append((self.key[0], kw.value.id))
        # self.m(...) call edge; mutator calls are writes of the attribute
        if isinstance(fn, ast.Attribute):
            recv_attr = self_attr(fn.value)
            owner = self_attr(fn)
            if owner:  # self.m(...)
                self.calls.add((owner, None))
            if recv_attr and fn.attr in MUTATORS:
                self._record(recv_attr, node.lineno, write=True)
        elif isinstance(fn, ast.Name):
            self.calls.add((self.key[0], fn.id))  # maybe a nested def
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        attr = self_attr(node)
        if attr and isinstance(node.ctx, ast.Load):
            self._record(attr, node.lineno, write=False)
            if attr in self.cls.properties:
                self.calls.add((attr, None))
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef):
        # nested def: runs later (thread target / callback), NOT under the
        # current guard
        self.cls.scan_function((self.key[0], node.name), node, guard_depth=0)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda):
        # lambdas (cv.wait_for predicates etc.) run where they're used:
        # inherit the definition-site guard state
        self._scan_expr(node.body)


class _ClassScan:
    def __init__(self, cls: ast.ClassDef):
        self.name = cls.name
        self.lock_attrs = class_lock_attrs(cls)
        self.properties = {
            n.name
            for n in cls.body
            if isinstance(n, ast.FunctionDef)
            and any(terminal_name(d) == "property" for d in n.decorator_list)
        }
        self.funcs: dict = {}  # key -> _FuncScan
        for n in cls.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.scan_function((n.name, None), n)

    def scan_function(self, key, node, guard_depth=0):
        # `_locked` suffix = contract that the caller holds the class lock
        if (key[1] or key[0]).endswith("_locked"):
            guard_depth = 1
        scan = _FuncScan(self, key, guard_depth)
        self.funcs[key] = scan
        for stmt in node.body:
            scan.visit(stmt)

    def roots(self) -> dict:
        """root id -> set of reachable function keys."""
        roots: dict = {}
        thread_targets = []
        for scan in self.funcs.values():
            thread_targets.extend(scan.thread_targets)
        for tgt in thread_targets:
            if tgt in self.funcs:
                roots[f"thread:{tgt[0]}" + (f".{tgt[1]}" if tgt[1] else "")] = (
                    self._reach({tgt})
                )
        external_entries = {
            key
            for key in self.funcs
            if key[1] is None
            and (not key[0].startswith("_") or key[0] in self.properties)
            and key[0] != "__init__"
        }
        if external_entries:
            roots["external"] = self._reach(external_entries)
        return roots

    def _reach(self, entries: set) -> set:
        seen = set()
        work = [k for k in entries if k in self.funcs]
        while work:
            key = work.pop()
            if key in seen:
                continue
            seen.add(key)
            for callee in self.funcs[key].calls:
                if callee in self.funcs and callee not in seen:
                    work.append(callee)
        return seen


class LockDiscipline(Rule):
    id = "lock-discipline"
    description = (
        "guarded attributes must not be read/written outside their lock "
        "when reachable from a second thread root"
    )

    def scope(self, rel: str) -> bool:
        return rel.startswith(SCOPE_DIRS)

    def check_file(self, sf: SourceFile, project: Project):
        for node in sf.tree.body:
            if not isinstance(node, ast.ClassDef):
                continue
            scan = _ClassScan(node)
            if not scan.lock_attrs:
                continue
            yield from self._check_class(sf, scan)

    def _check_class(self, sf: SourceFile, scan: _ClassScan):
        lockname = sorted(scan.lock_attrs)[0]
        roots = scan.roots()
        if len(roots) < 2:
            return
        accesses: list[_Access] = []
        for fscan in scan.funcs.values():
            accesses.extend(fscan.accesses)
        guarded_attrs = {a.attr for a in accesses if a.write and a.guarded}
        # which roots touch each guarded attribute?
        roots_of_attr: dict = {}
        for a in accesses:
            if a.attr not in guarded_attrs:
                continue
            for rid, reach in roots.items():
                if a.func in reach:
                    roots_of_attr.setdefault(a.attr, set()).add(rid)
        for a in accesses:
            if (
                a.attr not in guarded_attrs
                or a.guarded
                or a.func == ("__init__", None)
            ):
                continue
            if ("*", a.attr) in ALLOW_LOCK_FREE or (
                scan.name,
                a.attr,
            ) in ALLOW_LOCK_FREE:
                continue
            touching = roots_of_attr.get(a.attr, set())
            thread_roots = {r for r in touching if r.startswith("thread:")}
            if len(touching) < 2 or not thread_roots:
                continue
            if not any(a.func in reach for reach in roots.values()):
                continue
            kind = "write" if a.write else "read"
            yield Finding(
                self.id,
                sf.rel,
                a.line,
                f"'{scan.name}.{a.attr}' is written under "
                f"'{scan.name}.{lockname}' but {kind} here without it "
                f"(attribute is shared by roots: {', '.join(sorted(touching))})",
            )
