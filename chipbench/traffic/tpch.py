"""The benchmark's own TPC-H load generator: snapshot and refresh stream from `--seed`.

A copy of `materialize_tpu/storage/generator.py::TpchGenerator` (same schema,
same column subset and distributions, RF1+RF2 at SF x 1,500 orders per
refresh) with three changes. Every draw comes from the seed it is built with
(the program's copy draws the snapshot from a fixed `default_rng(12345)` and
the stream from seed 0). It keeps a log of how many source updates each
refresh carried. And it keeps the live rows in a FIFO, where the program's
generator, inside the timed `advance()`, runs `np.isin` over all 6 M
lineitems and concatenates both whole tables on every refresh: so the
benchmark's `advance()` is cheaper than what a user of the program's own
`LOAD GENERATOR TPCH` pays, by that much (PERF.md gives the reading beside
`tick_outside_render_ms`). The data is the program generator's, not dbgen's:
the configuration file lists where they differ. `chipbench/run.py` puts it where the coordinator constructs
its generator (the one seam; see the configuration file), so the program gets
only generated inputs through its own ingest path. The host mirrors of the
live rows are what the plain reference reads (`live()`).
"""

from __future__ import annotations

import numpy as np


SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
TABLES = ("customer", "orders", "lineitem", "part")


class _Fifo:
    """Live rows of one table as columns: appended at the back (RF1), dropped
    from the front (RF2, the oldest orders), without copying the table on
    every refresh."""

    def __init__(self, cols: tuple):
        n = len(cols[0])
        self.lo, self.hi = 0, n
        self.buf = [np.concatenate([c, np.empty(n // 4 + 1024, dtype=c.dtype)]) for c in cols]

    def cols(self) -> tuple:
        return tuple(b[self.lo : self.hi] for b in self.buf)

    def append(self, cols: tuple) -> None:
        n = len(cols[0])
        if self.hi + n > len(self.buf[0]):
            live = self.hi - self.lo
            self.buf = [np.concatenate([b[self.lo : self.hi], np.empty(live // 4 + n + 1024, dtype=b.dtype)])
                        for b in self.buf]
            self.lo, self.hi = 0, live
        for b, c in zip(self.buf, cols):
            b[self.hi : self.hi + n] = c
        self.hi += n

    def pop_front(self, n: int) -> tuple:
        out = tuple(b[self.lo : self.lo + n].copy() for b in self.buf)
        self.lo += n
        return out


class Generator:
    """TPC-H rows as (column tuples, i64) with RF1/RF2 refreshes.

    customer(custkey, mktsegment, nationkey); orders(orderkey, custkey,
    orderdate, shippriority); lineitem(orderkey, extendedprice in cents,
    discount in percent, shipdate, quantity, partkey); part(partkey, brand,
    container). Dates are day numbers since 1992-01-01.
    """

    def __init__(self, sf: float = 0.01, seed: int = 0, segment_codes=None):
        self.sf = sf
        self.seed = int(seed)
        # two independent streams of one seed: [seed, 0] the snapshot, [seed, 1] the refreshes
        self._snapshot_rng = np.random.default_rng([self.seed, 0])
        self.rng = np.random.default_rng([self.seed, 1])
        self.segment_codes = (
            np.asarray(segment_codes, dtype=np.int64)
            if segment_codes is not None
            else np.arange(5, dtype=np.int64)
        )
        self.n_customer = max(int(150_000 * sf), 10)
        self.n_orders = max(int(1_500_000 * sf), 20)
        self.n_part = max(int(200_000 * sf), 10)
        self.next_orderkey = self.n_orders
        self._customer = None  # (custkey, segment index into SEGMENTS, nationkey)
        self._orders: _Fifo | None = None  # live rows, oldest first (orderkeys only grow)
        self._lineitem: _Fifo | None = None
        self.updates_by_ts: dict = {}  # refresh timestamp -> source updates it carried

    # -- snapshot ------------------------------------------------------------
    def _draw_orders(self, rng, orderkey: np.ndarray) -> tuple:
        n = len(orderkey)
        return (
            orderkey,
            rng.integers(0, self.n_customer, n).astype(np.int64),
            rng.integers(0, 2406, n).astype(np.int64),  # 1992-1998
            np.zeros(n, dtype=np.int64),
        )

    def _draw_lineitems(self, rng, orderkey: np.ndarray) -> tuple:
        lk = np.repeat(orderkey, rng.integers(1, 8, len(orderkey)))
        n = len(lk)
        return (
            lk,
            rng.integers(100_00, 100_000_00, n).astype(np.int64),
            rng.integers(0, 11, n).astype(np.int64),  # percent
            rng.integers(0, 2557, n).astype(np.int64),
            rng.integers(1, 51, n).astype(np.int64),
            rng.integers(0, self.n_part, n).astype(np.int64),
        )

    def snapshot(self) -> dict:
        """Draws the snapshot on the host: table -> columns as the program ingests them."""
        rng = self._snapshot_rng
        custkey = np.arange(self.n_customer, dtype=np.int64)
        segment = rng.integers(0, 5, self.n_customer)
        nationkey = rng.integers(0, 25, self.n_customer).astype(np.int64)
        orderkey = np.arange(self.n_orders, dtype=np.int64)
        orders = self._draw_orders(rng, orderkey)
        lineitem = self._draw_lineitems(rng, orderkey)
        partkey = np.arange(self.n_part, dtype=np.int64)
        part = (
            partkey,
            rng.integers(0, 25, self.n_part).astype(np.int64),
            rng.integers(0, 40, self.n_part).astype(np.int64),
        )
        self._customer = (custkey, segment.astype(np.int64), nationkey)
        self._orders = _Fifo(orders)
        self._lineitem = _Fifo(lineitem)
        return {
            "customer": (custkey, self.segment_codes[segment], nationkey),
            "orders": orders,
            "lineitem": lineitem,
            "part": part,
        }

    def initial_batches(self, tick: int = 0) -> dict:
        from materialize_tpu.repr.batch import UpdateBatch  # the program's ingest currency

        tables = self.snapshot()
        out = {}
        for name in TABLES:
            cols = tables[name]
            n = len(cols[0])
            out[name] = UpdateBatch.build((), cols, np.full(n, tick), np.ones(n, dtype=np.int64))
        return out

    # -- refresh stream ------------------------------------------------------
    def refresh_rows(self, frac: float = 0.001) -> dict:
        """RF1 (new orders with their lineitems) + RF2 (the oldest live orders
        and their lineitems retracted), SF x 1,500 orders each, on the host:
        table -> (columns, diffs). Moves the live rows."""
        if self._orders is None:
            raise RuntimeError("refresh before the snapshot")
        n_new = max(int(self.n_orders * frac), 1)
        new_ok = np.arange(self.next_orderkey, self.next_orderkey + n_new, dtype=np.int64)
        self.next_orderkey += n_new
        o_new = self._draw_orders(self.rng, new_ok)
        l_new = self._draw_lineitems(self.rng, new_ok)

        # both tables are kept in orderkey order, so the oldest orders and
        # their lineitems are the front of each
        o_del = self._orders.pop_front(n_new)
        n_l_del = int(np.searchsorted(self._lineitem.cols()[0], o_del[0][-1], side="right"))
        l_del = self._lineitem.pop_front(n_l_del)
        self._orders.append(o_new)
        self._lineitem.append(l_new)

        o_all = tuple(np.concatenate(p) for p in zip(o_new, o_del))
        l_all = tuple(np.concatenate(p) for p in zip(l_new, l_del))
        od = np.concatenate([np.ones(n_new, dtype=np.int64), -np.ones(len(o_del[0]), dtype=np.int64)])
        ld = np.concatenate([np.ones(len(l_new[0]), dtype=np.int64), -np.ones(len(l_del[0]), dtype=np.int64)])
        return {"orders": (o_all, od), "lineitem": (l_all, ld)}

    def refresh(self, tick: int) -> dict:
        """One refresh as the coordinator's `advance()` asks for it."""
        from materialize_tpu.repr.batch import UpdateBatch

        rows = self.refresh_rows()
        self.updates_by_ts[int(tick)] = sum(len(d) for _cols, d in rows.values())
        return {
            name: UpdateBatch.build((), cols, np.full(len(d), tick), d)
            for name, (cols, d) in rows.items()
        }

    # -- what the plain reference reads ---------------------------------------
    def live(self) -> dict:
        """The live rows on the host: table -> tuple of NumPy columns.
        `customer`'s second column is an index into SEGMENTS."""
        return {
            "segments": list(SEGMENTS),
            "customer": tuple(self._customer),
            "orders": self._orders.cols(),
            "lineitem": self._lineitem.cols(),
        }
