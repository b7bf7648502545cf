"""blocking-under-lock: no sleeps, subprocesses, or socket/CTP frame I/O
while a shared lock is held.

A blocking call under the coordinator or mesh lock turns one slow peer
into a whole-process stall (every frontend serializes through the
coordinator lock; every shard command serializes through the mesh/command
locks). The check is lexical: a call to a known blocking primitive inside
a `with <lock>:` region. Locks that exist PRECISELY to serialize a socket
(ReplicaClient's per-connection request lock) are allowlisted with their
justification below.
"""

from __future__ import annotations

import ast

from ..astutil import dotted, terminal_name, with_lock_names
from ..core import Finding, Project, Rule, SourceFile

#: fully-dotted callables that block
BLOCKING_DOTTED_PREFIXES = ("subprocess.",)
BLOCKING_DOTTED = {"time.sleep", "socket.create_connection"}
#: method names that block on a socket regardless of receiver spelling
BLOCKING_METHODS = {"accept", "recv", "recv_into", "sendall", "connect"}
#: CTP framing (cluster/protocol.py): one frame is one blocking socket op
BLOCKING_TERMINAL = {"send_frame", "recv_frame"}

#: (class name or function name, lock name) pairs where holding the lock
#: across blocking calls is the documented design; "*" matches any scope.
ALLOW_BLOCKING = {
    # ReplicaClient.lock serializes request/response pairs on ONE socket —
    # the lock's whole purpose is to span the send+recv; timeouts bound it
    ("ReplicaClient", "lock"),
    # the heal gate intentionally spans reform backoff sleeps so concurrent
    # healers collapse into one; commands only contend on _cmd_lock, which
    # is NOT held across the sleeps (cluster/controller.py)
    ("ShardedComputeController", "_heal_lock"),
    # WorkerMesh's per-peer send locks exist to serialize whole frames onto
    # one peer socket during exchange fan-out; they are never held while
    # taking the mesh lock, so they cannot stall the command path
    ("WorkerMesh", "slock"),
}

SCOPE_DIRS = (
    "materialize_tpu/adapter/",
    "materialize_tpu/egress/",
    "materialize_tpu/cluster/",
    "materialize_tpu/frontend/",
    "materialize_tpu/persist/",
    "materialize_tpu/storage/",
    "materialize_tpu/obs/",
)


def _is_blocking(call: ast.Call) -> str | None:
    d = dotted(call.func)
    if d is not None:
        if d in BLOCKING_DOTTED or d.startswith(BLOCKING_DOTTED_PREFIXES):
            return d
    term = terminal_name(call.func)
    if term in BLOCKING_TERMINAL:
        return term
    if isinstance(call.func, ast.Attribute) and call.func.attr in BLOCKING_METHODS:
        return term
    return None


class _Scan(ast.NodeVisitor):
    def __init__(self, rule_id, rel, owner):
        self.rule_id = rule_id
        self.rel = rel
        self.owner = owner  # enclosing class name or "<module>"
        self.held: list[str] = []
        self.findings: list[Finding] = []

    def visit_With(self, node: ast.With):
        locks = with_lock_names(node)
        for item in node.items:
            self.generic_visit(item)
        self.held.extend(locks)
        for stmt in node.body:
            self.visit(stmt)
        if locks:
            del self.held[-len(locks) :]

    def visit_Call(self, node: ast.Call):
        if self.held:
            what = _is_blocking(node)
            if what is not None:
                held = [
                    lk
                    for lk in self.held
                    if (self.owner, lk) not in ALLOW_BLOCKING
                    and ("*", lk) not in ALLOW_BLOCKING
                ]
                if held:
                    self.findings.append(
                        Finding(
                            self.rule_id,
                            self.rel,
                            node.lineno,
                            f"blocking call '{what}' while holding "
                            f"'{held[-1]}' — decide under the lock, "
                            "perform I/O outside it",
                        )
                    )
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        # nested defs run later, not under the current lock
        saved, self.held = self.held, []
        for stmt in node.body:
            self.visit(stmt)
        self.held = saved

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        pass  # deferred; and wait_for predicates must stay cheap anyway


class BlockingUnderLock(Rule):
    id = "blocking-under-lock"
    description = (
        "no time.sleep/subprocess/socket/CTP-frame calls while a shared "
        "lock is held"
    )

    def scope(self, rel: str) -> bool:
        return rel.startswith(SCOPE_DIRS)

    def check_file(self, sf: SourceFile, project: Project):
        for node in sf.tree.body:
            if isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        scan = _Scan(self.id, sf.rel, node.name)
                        for stmt in sub.body:
                            scan.visit(stmt)
                        yield from scan.findings
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan = _Scan(self.id, sf.rel, "<module>")
                for stmt in node.body:
                    scan.visit(stmt)
                yield from scan.findings
