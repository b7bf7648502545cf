"""The benchmark's TPC-H load generator for the Q17 configuration.

`chipbench/traffic/tpch.py::Generator` draws `part` in `snapshot()` and hands
it to the program, but keeps no host mirror of it, so its `live()` has no
`part` for a reference to read. This subclass keeps the three `part` columns
it drew (part never changes: the refresh stream is RF1 + RF2 over orders and
lineitem) and returns them from `live()`. Every draw, the snapshot's and the
refreshes', is the parent class's, from the same two streams of `--seed`.
"""

from __future__ import annotations

from .tpch import Generator as _TpchGenerator


class Generator(_TpchGenerator):
    """`tpch.Generator` whose `live()` also holds `part` (partkey, brand, container)."""

    _part = None

    def snapshot(self) -> dict:
        tables = super().snapshot()
        self._part = tuple(tables["part"])
        return tables

    def live(self) -> dict:
        return {**super().live(), "part": self._part}
