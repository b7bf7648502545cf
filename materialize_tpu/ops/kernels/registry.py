"""Kernel registry: pluggable XLA / Pallas backends for the tick hot path.

Every hot-path primitive that has a hand-written Pallas TPU kernel is
registered here under a short name with BOTH implementations — the existing
XLA lowering (the reference oracle) and the Pallas kernel. Call sites route
through :func:`dispatch`, which resolves the active backend and bumps the
``mzt_kernel_dispatch_total{kernel,backend}`` counter, so ``/metrics`` shows
which backend actually served each trace.

**Backend selection.** The ``kernel_backend`` dyncfg has three modes:
``auto`` (XLA on every platform — see :func:`resolve_backend` for the rule
and its reason) and the ``xla`` / ``pallas`` force modes for bisection. A
forced ``pallas`` on a TPU compiles the Pallas programs for the chip and
raises whatever the chip's compiler raises; nothing catches it. The mode is
a process-global set by :func:`set_kernel_backend` (ALTER SYSTEM SET on the
coordinator; CreateInstance config on clusterd).

**jit-boundary discipline.** Dispatch happens at TRACE time — a module-global
read inside an already-compiled function re-executes nothing. Public ops
entry points therefore resolve :func:`active_backend` OUTSIDE their jitted
inner function and pass it through a static ``backend`` argname, opening a
:func:`using_backend` scope for the trace; a mode flip changes the static
argument, which retriggers tracing naturally. The fused renderer captures the
resolved backend at ``_build()`` time and rebuilds its tick program when the
mode flips (dataflow/fused.py).

**Bit-identity contract.** A Pallas backend must produce BYTE-identical
output to its XLA reference on every input — padding sentinels, empty
batches, deep hash-collision buckets included (doc/KERNELS.md). Kernels are
therefore restricted to exact (integer / bitwise) arithmetic; anything that
would reassociate floating-point falls back to the XLA implementation.

**Interpret mode.** Off-TPU, Pallas kernels run under ``interpret=True``
(pure XLA emulation of the kernel program) — that is what lets tier-1 prove
bit-identity on CPU. It proves nothing about whether the chip's compiler
accepts a program; tests/test_chip_compile.py asks that. The flag is decided in ONE place, :func:`pallas_interpret`,
and the kernel-dispatch-coherence lint pass enforces that every
``pallas_call`` site takes ``interpret=pallas_interpret()`` (never a bare
constant) and lives inside ``ops/kernels/``.

Counter caveat: the dispatch counter is a host-side effect, so it counts
TRACES, not executions — a compiled tick replayed from cache bumps nothing.
That is the honest signal for "which backend is this program built from".
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable

import jax

from ...obs import metrics as obs_metrics

KERNEL_BACKENDS = ("xla", "pallas")
KERNEL_MODES = ("auto", "xla", "pallas")

_DISPATCH = obs_metrics.REGISTRY.counter(
    "mzt_kernel_dispatch_total",
    "hot-path kernel dispatches by registered kernel and serving backend "
    "(counted at trace time: one bump per compiled program, not per tick)",
    ("kernel", "backend"),
)

_mode = "auto"
_mode_lock = threading.Lock()
_tls = threading.local()

_KERNELS: dict[str, dict[str, Callable]] = {}


def set_kernel_backend(mode: str) -> None:
    """Set the process-global kernel backend mode (the `kernel_backend` dyncfg)."""
    global _mode
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"kernel_backend must be one of {KERNEL_MODES}, got {mode!r}"
        )
    with _mode_lock:
        _mode = mode


def kernel_backend_mode() -> str:
    """The configured mode as set (may be 'auto'; see active_backend)."""
    return _mode


def resolve_backend(mode: str | None = None) -> str:
    """Resolve a mode ('auto' included) to a concrete backend name."""
    m = _mode if mode is None else mode
    if m == "auto":
        # Rule: `auto` is the XLA lowering on every platform. None of the six
        # registered Pallas programs compiles for a TPU v5e (Mosaic refuses
        # run_sum / bucket_rank's unaligned lane shifts and i64 operands,
        # probe / probe2's 1-D gather, multi_take's take shapes, route_dest's
        # `%`), and none has a grid, so a whole column would have to sit in
        # fast memory. tests/test_chip_compile.py holds one strict xfail per
        # program; the PR that makes one compile flips its case and may then
        # widen this rule by what it can observe (kernel, dtype, capacity).
        return "xla"
    return m


def active_backend() -> str:
    """The backend the NEXT dispatched kernel will use.

    A thread-local `using_backend` scope (opened by jitted entry-point
    wrappers for the duration of a trace) wins over the process-global mode.
    """
    override = getattr(_tls, "backend", None)
    if override is not None:
        return override
    return resolve_backend()


@contextmanager
def using_backend(backend: str):
    """Pin the dispatch backend for the enclosed (trace-time) region.

    Thread-local, reentrant; used by ops entry points to thread their static
    `backend` argument down to nested kernel dispatches without changing
    every helper signature.
    """
    if backend not in KERNEL_BACKENDS:
        raise ValueError(
            f"backend must be one of {KERNEL_BACKENDS}, got {backend!r}"
        )
    prev = getattr(_tls, "backend", None)
    _tls.backend = backend
    try:
        yield
    finally:
        _tls.backend = prev


def pallas_interpret() -> bool:
    """Whether pallas_call sites must run in interpret mode (no TPU present).

    The ONE place this decision lives: interpret mode is pure-XLA emulation
    of the kernel program, which is how tier-1 proves bit-identity on CPU.
    """
    return jax.default_backend() != "tpu"


def register_kernel(name: str, *, xla: Callable, pallas: Callable) -> None:
    """Register both backends of a kernel. Both are mandatory — a kernel
    without its XLA reference oracle has no bit-identity contract to test."""
    _KERNELS[name] = {"xla": xla, "pallas": pallas}


def registered_kernels() -> list[str]:
    return sorted(_KERNELS)


def dispatch(name: str, *args, **kwargs):
    """Route one kernel invocation to the active backend's implementation."""
    backend = active_backend()
    impl = _KERNELS[name][backend]
    _DISPATCH.inc(kernel=name, backend=backend)
    return impl(*args, **kwargs)


def dispatch_counts() -> dict[tuple[str, str], int]:
    """Snapshot of the dispatch counter for introspection: (kernel, backend)
    -> traces served. Kernels that never dispatched don't appear."""
    out: dict[tuple[str, str], int] = {}
    for labels, v in _DISPATCH._snapshot_samples():
        d = dict(labels)
        out[(d["kernel"], d["backend"])] = int(v)
    return out
