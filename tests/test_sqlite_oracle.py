"""Differential testing: random queries vs a sqlite3 oracle (VERDICT r4 #4).

The reference methodology is output-consistency testing against alternative
evaluation modes (/root/reference/test/output-consistency/,
doc/developer/guide-testing.md:121-196). Here the oracle is Python's stdlib
sqlite3: every generated query runs against both engines over identical data
and must produce the same multiset of rows — not just "doesn't crash".

The generated dialect is the overlap where both engines agree semantically:
INT and TEXT columns, +,-,* arithmetic (no division: div-by-zero is an error
here, NULL in sqlite), comparisons, 3VL AND/OR/NOT, IS NULL, LIKE (with
sqlite's case_sensitive_like ON to match pg), upper/lower/length/substr/||,
inner equi-joins, GROUP BY with sum/count/min/max, HAVING, DISTINCT,
ORDER BY+LIMIT (compared as sorted prefix-free multisets by re-sorting).
Booleans normalize to 0/1 (sqlite has no bool type).
"""

from __future__ import annotations

import sqlite3

import numpy as np
import pytest

from materialize_tpu.adapter import Coordinator


def _norm(rows):
    out = []
    for r in rows:
        out.append(
            tuple(
                int(v) if isinstance(v, (bool, np.bool_)) else v
                for v in r
            )
        )
    return sorted(
        out, key=lambda r: tuple((v is not None, str(type(v)), str(v)) for v in r)
    )


class Oracle:
    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        self.mz = Coordinator()
        self.db = sqlite3.connect(":memory:")
        self.db.execute("PRAGMA case_sensitive_like = ON")
        self.tables: dict[str, list[tuple[str, str]]] = {}
        self.mismatches: list[str] = []
        self.checked = 0

    def pick(self, xs):
        return xs[int(self.rng.integers(0, len(xs)))]

    # -- schema/data (applied to both engines) ----------------------------
    def make_table(self, name: str, nrows: int):
        ncols = int(self.rng.integers(2, 5))
        cols = [("c0", "int")]
        for i in range(1, ncols):
            cols.append((f"c{i}", self.pick(["int", "int", "text"])))
        self.tables[name] = cols
        ddl = ", ".join(f"{c} {t}" for c, t in cols)
        self.mz.execute(f"CREATE TABLE {name} ({ddl})")
        self.db.execute(f"CREATE TABLE {name} ({ddl})")
        rows = []
        for _ in range(nrows):
            vals = []
            for _c, t in cols:
                if self.rng.random() < 0.15:
                    vals.append("NULL")
                elif t == "int":
                    vals.append(str(int(self.rng.integers(-9, 50))))
                else:
                    s = self.pick(["ab", "Abc", "x", "yz", "aa", "", "b%c"])
                    vals.append(f"'{s}'")
            rows.append(f"({', '.join(vals)})")
        # one statement, one batch: a table loaded row by row is a spine of
        # many small batches, whose summed capacity gives every query over it
        # shapes (and programs) of its own
        stmt = f"INSERT INTO {name} VALUES {', '.join(rows)}"
        self.mz.execute(stmt)
        self.db.execute(stmt)

    def churn(self):
        name = self.pick(list(self.tables))
        cols = self.tables[name]
        if self.rng.random() < 0.5:
            vals = []
            for _c, t in cols:
                if t == "int":
                    vals.append(str(int(self.rng.integers(-9, 50))))
                else:
                    vals.append(f"'{self.pick(['ab', 'new', 'zz'])}'")
            stmt = f"INSERT INTO {name} VALUES ({', '.join(vals)})"
        else:
            intcols = [c for c, t in cols if t == "int"]
            c = self.pick(intcols)
            stmt = f"DELETE FROM {name} WHERE {c} = {int(self.rng.integers(-9, 50))}"
        self.mz.execute(stmt)
        self.db.execute(stmt)

    # -- expression generation -------------------------------------------
    def int_expr(self, cols, depth=0):
        intcols = [c for c, t in cols if t == "int"]
        r = self.rng.random()
        if depth >= 2 or r < 0.35:
            if intcols and r < 0.25:
                return self.pick(intcols)
            return str(int(self.rng.integers(-9, 50)))
        if r < 0.45:
            txt = [c for c, t in cols if t == "text"]
            if txt:
                return f"length({self.pick(txt)})"
        op = self.pick(["+", "-", "*"])
        return f"({self.int_expr(cols, depth + 1)} {op} {self.int_expr(cols, depth + 1)})"

    def text_expr(self, cols, depth=0):
        txt = [c for c, t in cols if t == "text"]
        r = self.rng.random()
        if not txt or r < 0.3:
            return f"'{self.pick(['ab', 'x', 'Q'])}'"
        if depth >= 2 or r < 0.6:
            return self.pick(txt)
        if r < 0.75:
            return f"upper({self.text_expr(cols, depth + 1)})"
        if r < 0.85:
            return f"lower({self.text_expr(cols, depth + 1)})"
        return f"({self.text_expr(cols, depth + 1)} || {self.text_expr(cols, depth + 1)})"

    def pred(self, cols, depth=0):
        r = self.rng.random()
        if depth < 2 and r < 0.25:
            op = self.pick(["AND", "OR"])
            return f"({self.pred(cols, depth + 1)} {op} {self.pred(cols, depth + 1)})"
        if depth < 2 and r < 0.3:
            return f"(NOT {self.pred(cols, depth + 1)})"
        if r < 0.4:
            anycol = self.pick([c for c, _t in cols])
            neg = " NOT" if self.rng.random() < 0.5 else ""
            return f"({anycol} IS{neg} NULL)"
        if r < 0.55:
            txt = [c for c, t in cols if t == "text"]
            if txt:
                pat = self.pick(["a%", "%b%", "_b%", "x", "%c", "A%"])
                return f"({self.pick(txt)} LIKE '{pat}')"
        cmp_ = self.pick(["=", "<>", "<", "<=", ">", ">="])
        if self.rng.random() < 0.3:
            return f"({self.text_expr(cols)} {cmp_} {self.text_expr(cols)})"
        return f"({self.int_expr(cols)} {cmp_} {self.int_expr(cols)})"

    # -- query generation --------------------------------------------------
    def query(self) -> str:
        r = self.rng.random()
        name = self.pick(list(self.tables))
        cols = self.tables[name]
        if r < 0.3:
            # grouped aggregate
            intcols = [c for c, t in cols if t == "int"]
            gb = self.pick([c for c, _t in cols])
            aggs = []
            for _ in range(int(self.rng.integers(1, 3))):
                f = self.pick(["sum", "count", "min", "max"])
                arg = self.pick(intcols) if intcols else "c0"
                aggs.append(f"{f}({arg})" if f != "count" else
                            self.pick([f"count({arg})", "count(*)"]))
            q = f"SELECT {gb}, {', '.join(aggs)} FROM {name}"
            if self.rng.random() < 0.5:
                q += f" WHERE {self.pred(cols)}"
            q += f" GROUP BY {gb}"
            if self.rng.random() < 0.3:
                q += " HAVING count(*) >= 1"
            return q
        if r < 0.45 and len(self.tables) >= 2:
            # inner equi-join on int columns
            n2 = self.pick([t for t in self.tables if t != name])
            c1 = [c for c, t in self.tables[name] if t == "int"]
            c2 = [c for c, t in self.tables[n2] if t == "int"]
            if c1 and c2:
                a, b = self.pick(c1), self.pick(c2)
                sel = f"{name}.c0, {n2}.c0"
                q = (
                    f"SELECT {sel} FROM {name}, {n2} "
                    f"WHERE {name}.{a} = {n2}.{b}"
                )
                return q
        if r < 0.6:
            # ORDER BY all selected columns + LIMIT: ordering by the FULL
            # row makes the limited prefix a well-defined multiset (ties are
            # identical rows), so both engines must return the same rows.
            # Explicit NULLS FIRST/LAST pins the engines' differing defaults.
            sel_cols = [c for c, _t in cols][: int(self.rng.integers(1, 4))]
            order = []
            for sc in sel_cols:
                if self.rng.random() < 0.5:
                    order.append(f"{sc} ASC NULLS FIRST")
                else:
                    order.append(f"{sc} DESC NULLS LAST")
            k = int(self.rng.integers(1, 8))
            q = f"SELECT {', '.join(sel_cols)} FROM {name}"
            if self.rng.random() < 0.5:
                q += f" WHERE {self.pred(cols)}"
            q += f" ORDER BY {', '.join(order)} LIMIT {k}"
            return q
        # plain select
        items = []
        for _ in range(int(self.rng.integers(1, 4))):
            if self.rng.random() < 0.6:
                items.append(self.int_expr(cols))
            else:
                items.append(self.text_expr(cols))
        distinct = "DISTINCT " if self.rng.random() < 0.2 else ""
        q = f"SELECT {distinct}{', '.join(items)} FROM {name}"
        if self.rng.random() < 0.6:
            q += f" WHERE {self.pred(cols)}"
        return q

    def check(self, q: str):
        got = _norm(self.mz.execute(q).rows)
        want = _norm(self.db.execute(q).fetchall())
        self.checked += 1
        if got != want:
            self.mismatches.append(f"{q}\n  engine: {got[:6]}\n  sqlite: {want[:6]}")

    def run(self, n_queries: int):
        self.make_table("ta", 14)
        self.make_table("tb", 10)
        self.make_table("tc", 7)
        for i in range(n_queries):
            if i % 10 == 9:
                self.churn()
            self.check(self.query())
        return self


def test_oracle_quick():
    o = Oracle(1).run(70)
    assert not o.mismatches, "\n\n".join(o.mismatches[:8])
    assert o.checked >= 70


@pytest.mark.slow
@pytest.mark.parametrize("seed", [11, 12, 13, 14, 15])
def test_oracle_deep(seed):
    # 5 seeds × 200 queries ≥ the 1,000-query differential bar (VERDICT #4)
    o = Oracle(seed).run(200)
    assert not o.mismatches, "\n\n".join(o.mismatches[:8])
    assert o.checked >= 200
