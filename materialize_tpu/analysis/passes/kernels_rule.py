"""kernel-dispatch-coherence: the ops/kernels registry contracts.

The kernel registry (materialize_tpu/ops/kernels/registry.py) only keeps its
bit-identity guarantee if three lexical invariants hold across the tree:

  1. every ``register_kernel(name, ...)`` carries BOTH ``xla=`` and
     ``pallas=`` implementations and a string-literal name — a single-backend
     registration silently turns a forced ``SET kernel_backend = pallas``
     into a KeyError (or worse, an untested fallback) at tick time;
  2. ``pallas_call`` is confined to ``materialize_tpu/ops/kernels/`` and
     every call sets ``interpret=`` to a ``pallas_interpret()`` CALL — a
     bare ``interpret=False`` asks the chip's compiler off a chip (where the
     tier-1 byte-identity suites could no longer run the kernel), a bare
     ``interpret=True`` emulates on the chip a forced ``kernel_backend =
     pallas`` paid for, and a pallas_call outside the registry escapes the
     dispatch counter, the XLA oracle and the differential suite. ``auto``
     never reaches a pallas_call (it resolves to xla on every platform);
     whether a program compiles for the chip is tests/test_chip_compile.py's
     question, asked with interpret forced off in the test;
  3. every ``dispatch("name", ...)`` literal names a registered kernel and
     every registered kernel is dispatched somewhere — a typo'd name fails
     at lint time, not as a KeyError in a compiled tick.
"""

from __future__ import annotations

import ast

from ..astutil import terminal_name
from ..core import Finding, Project, Rule

_KERNELS_DIR = "materialize_tpu/ops/kernels/"


def _str_arg0(call: ast.Call) -> str | None:
    if call.args and isinstance(call.args[0], ast.Constant):
        v = call.args[0].value
        if isinstance(v, str):
            return v
    return None


class KernelDispatchCoherence(Rule):
    id = "kernel-dispatch-coherence"
    description = (
        "register_kernel must carry both backends; pallas_call stays inside "
        "ops/kernels/ with interpret=pallas_interpret(); dispatch names must "
        "match registrations"
    )

    def check_project(self, project: Project):
        registered: dict = {}  # name -> (rel, line)
        dispatched: dict = {}  # name -> (rel, line) of first dispatch

        for sf in project.files:
            if not sf.rel.startswith("materialize_tpu/"):
                continue
            in_kernels = sf.rel.startswith(_KERNELS_DIR)
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = terminal_name(node.func)

                if fn == "register_kernel":
                    name = _str_arg0(node)
                    if name is None:
                        yield Finding(
                            self.id,
                            sf.rel,
                            node.lineno,
                            "register_kernel needs a string-literal kernel "
                            "name — dispatch sites are matched lexically",
                        )
                        continue
                    registered[name] = (sf.rel, node.lineno)
                    kw = {k.arg for k in node.keywords}
                    for backend in ("xla", "pallas"):
                        if backend not in kw:
                            yield Finding(
                                self.id,
                                sf.rel,
                                node.lineno,
                                f"register_kernel({name!r}, ...) is missing "
                                f"the {backend}= implementation — every "
                                "kernel must carry both backends so forced "
                                "modes always resolve",
                            )

                elif fn == "dispatch":
                    name = _str_arg0(node)
                    if name is not None:
                        dispatched.setdefault(name, (sf.rel, node.lineno))

                elif fn == "pallas_call":
                    if not in_kernels:
                        yield Finding(
                            self.id,
                            sf.rel,
                            node.lineno,
                            "pallas_call outside materialize_tpu/ops/kernels/ "
                            "— Pallas kernels must live behind the registry "
                            "(XLA oracle + dispatch counter + differential "
                            "suite)",
                        )
                        continue
                    interp = next(
                        (k.value for k in node.keywords if k.arg == "interpret"),
                        None,
                    )
                    if interp is None or not (
                        isinstance(interp, ast.Call)
                        and terminal_name(interp.func) == "pallas_interpret"
                    ):
                        yield Finding(
                            self.id,
                            sf.rel,
                            node.lineno,
                            "pallas_call must pass "
                            "interpret=registry.pallas_interpret() — the one "
                            "place the interpret-off-TPU policy is decided "
                            "(a forced kernel_backend = pallas compiles for "
                            "the chip on a TPU and interprets elsewhere)",
                        )

        for name, (rel, line) in sorted(dispatched.items()):
            if name not in registered:
                yield Finding(
                    self.id,
                    rel,
                    line,
                    f"dispatch({name!r}, ...) names a kernel that is never "
                    "registered — a typo here is a KeyError inside a "
                    "compiled tick",
                )
        for name, (rel, line) in sorted(registered.items()):
            if name not in dispatched:
                yield Finding(
                    self.id,
                    rel,
                    line,
                    f"kernel {name!r} is registered but never dispatched by "
                    "string literal — either wire it up or delete the "
                    "registration",
                )
