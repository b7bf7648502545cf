"""Bytes a kernel call has to move, from the shapes of what goes in and out.

For each call of a counted kernel the work is its logical input and output:
every array of the batches (and state) that go in read once, every array of
what comes out written once, at their capacities (`size x itemsize`). That is
the least any implementation of the same call could move, whatever it does
inside, so a share of the HBM roofline made from it cannot pass 100 %.

`Recorder.wrap_all` puts a thin wrapper round the public functions named in
the metric files, in the traced run only; it reads shapes, never values, so
it does not wait for the device.
"""

from __future__ import annotations

import functools
import importlib
import sys


def tree_bytes(tree) -> int:
    """Sum of size x itemsize over every array in a pytree (shapes only)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape, dtype = getattr(leaf, "shape", None), getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * dtype.itemsize
    return total


def call_bytes(args, kwargs, result) -> int:
    return tree_bytes((args, kwargs)) + tree_bytes(result)


class Recorder:
    """Per counted kernel: calls made outside any jit trace, and their bytes."""

    def __init__(self):
        self.calls: dict = {}
        self.bytes: dict = {}
        self._undo: list = []

    def wrap_all(self, kernels: dict, package: str) -> None:
        """`kernels`: {kernel name: "module:function"}. Every module of
        `package` that holds the function under any name gets the wrapper."""
        import jax

        for name, target in kernels.items():
            mod_name, fn_name = target.split(":")
            original = getattr(importlib.import_module(mod_name), fn_name)

            def wrapper(*args, __orig=original, __name=name, **kwargs):
                out = __orig(*args, **kwargs)
                leaves = jax.tree_util.tree_leaves((args, kwargs))
                if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                    self.calls[__name] = self.calls.get(__name, 0) + 1
                    self.bytes[__name] = self.bytes.get(__name, 0) + call_bytes(args, kwargs, out)
                return out

            functools.update_wrapper(wrapper, original)
            for mod in list(sys.modules.values()):
                if mod is None or not getattr(mod, "__name__", "").startswith(package):
                    continue
                for attr, value in list(vars(mod).items()):
                    if value is original:
                        setattr(mod, attr, wrapper)
                        self._undo.append((mod, attr, original))

    def unwrap(self) -> None:
        for mod, attr, original in self._undo:
            setattr(mod, attr, original)
        self._undo.clear()
