"""SQL planning: AST → MIR with name resolution and typing.

The analogue of the reference's `mz-sql` plan pipeline (name resolution in
names.rs, HIR construction in plan/query.rs, HIR→MIR decorrelation in
plan/lowering.rs). This build plans directly to MIR; uncorrelated EXISTS/IN
become semijoins, NOT IN/NOT EXISTS threshold antijoins, and equality-
correlated scalar subqueries decorrelate into grouped joins (_decorrelate_
scalar — the Q17 pattern). General correlated decorrelation is future work.

NUMERIC is fixed-point i64 with a tracked decimal scale: literals like 0.05
plan as Literal(5)@scale2, multiplication adds scales, addition aligns them —
exact arithmetic on device, mirroring the reference's libdecnumber NUMERIC
without an f64 dependency (TPUs have no f64 ALU).

NUMERIC division (one rule, `_numeric_div`): `l / r` with either side NUMERIC
returns NUMERIC at scale max(scale(l), scale(r), NUMERIC_DIV_SCALE = 6), the
exact quotient TRUNCATED toward zero at that digit (the i64 `div` kernel's
rounding; no half-up step, so no second multiply that could overflow):
`1850 / 7.0` is 264.285714, `sum(cents) / 7.0` keeps six digits. The
dividend is scaled up by 10^(target + scale(r) - scale(l)) first (`mul_exact`):
past 2^63 / 10^that (a scale-2 sum over 7.0: 9.2e13 in cents; an integer
`avg`: a sum of 9.2e12) the row is a `numeric overflow` error in the
errs stream, never a wrapped value.
int / int stays SQL integer division. `avg` over an integer or NUMERIC
column is that same division of the group's exact i64 sum by its non-null
count (scale max(scale(x), 6), NULL for an empty group); only `avg` over a
float column is a float. A NUMERIC operand that meets a FLOAT one in `*` or
`/` is descaled to its value first (`0.2 * f` is a fifth of `f`).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Optional

import numpy as np

from ..expr import relation as mir
from ..expr.scalar import CallBinary, CallUnary, CallVariadic, Column, Literal, expr_columns
from ..repr.types import ColType, ColumnDesc, RelationDesc
from . import ast


class PlanError(ValueError):
    pass


@dataclass(frozen=True)
class PType:
    """Planned column type: engine ColType plus NUMERIC scale."""

    col: ColType
    scale: int = 0

    @property
    def dtype(self) -> np.dtype:
        return self.col.dtype


INT = PType(ColType.INT64)
BOOL = PType(ColType.BOOL)
STRING = PType(ColType.STRING)
FLOAT = PType(ColType.FLOAT64)
DATE = PType(ColType.TIMESTAMP)
JSONB = PType(ColType.JSONB)


@dataclass(frozen=True)
class ScopeCol:
    qualifier: Optional[str]
    name: Optional[str]
    typ: PType


@dataclass
class Scope:
    cols: list

    def resolve(self, name: str, qualifier: Optional[str]) -> int:
        matches = [
            i
            for i, c in enumerate(self.cols)
            if c.name == name and (qualifier is None or c.qualifier == qualifier)
        ]
        if not matches:
            raise PlanError(f"unknown column: {qualifier + '.' if qualifier else ''}{name}")
        if len(matches) > 1:
            raise PlanError(f"ambiguous column: {name}")
        return matches[0]

    def __add__(self, other: "Scope") -> "Scope":
        return Scope(self.cols + other.cols)


@dataclass
class RowSetFinishing:
    """Host-side ordering/limit applied to peek results (the reference's
    RowSetFinishing applied in the adapter, not the dataflow)."""

    order_by: tuple = ()  # ((col_idx, desc), ...)
    limit: Optional[int] = None
    offset: int = 0
    nulls_last: tuple = ()  # per order col; aligned with order_by


@dataclass
class PlannedQuery:
    mir: Any
    scope: Scope  # output columns with names/types
    finishing: RowSetFinishing

    @property
    def desc(self) -> RelationDesc:
        return RelationDesc(
            tuple(
                ColumnDesc(c.name or f"column{i+1}", c.typ.col, scale=c.typ.scale)
                for i, c in enumerate(self.scope.cols)
            )
        )

    @property
    def dtypes(self) -> tuple:
        return tuple(c.typ.dtype for c in self.scope.cols)


_AGG_FUNCS = {
    "sum", "count", "min", "max", "avg",
    "stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop",
    "bool_and", "bool_or",
    "string_agg", "array_agg", "list_agg", "jsonb_agg",
}
_BASIC_AGGS = {"string_agg", "array_agg", "list_agg", "jsonb_agg"}


@dataclass(frozen=True)
class _AggRef:
    """Internal AST placeholder for an extracted aggregate call."""

    index: int


# functions that only exist as window functions (aggregates become window
# functions when called with OVER)
_WINDOW_FUNCS = {
    "row_number", "rank", "dense_rank", "ntile",
    "lag", "lead", "first_value", "last_value",
}


@dataclass(frozen=True)
class _WinRef:
    """Internal AST placeholder for an extracted window function call."""

    index: int


def _map_window_spec(spec, fn):
    """Apply `fn` to every expression inside an OVER spec (None-safe)."""
    if spec is None:
        return None
    return ast.WindowSpec(
        tuple(fn(p) for p in spec.partition_by),
        tuple(replace(o, expr=fn(o.expr)) for o in spec.order_by),
    )


def _parse_interval(text: str) -> tuple[int, int]:
    """'1 year 2 months 3 days' → (months, days). Weeks fold into days;
    sub-day fields are rejected (the engine's calendar unit is days).
    The WHOLE string must tokenize — '1.5 months' or '- 3 days' error
    instead of silently dropping characters."""
    import re as _re

    if not _re.fullmatch(r"\s*([+-]?\d+\s*[a-zA-Z]+\s*)+", text):
        raise PlanError(f"cannot parse interval {text!r}")
    months = days = 0
    matched = False
    for num, unit in _re.findall(r"([+-]?\d+)\s*([a-zA-Z]+)", text):
        n = int(num)
        u = unit.lower().rstrip("s")
        matched = True
        if u in ("year", "yr", "y"):
            months += 12 * n
        elif u in ("month", "mon"):
            months += n
        elif u in ("week", "w"):
            days += 7 * n
        elif u in ("day", "d"):
            days += n
        else:
            raise PlanError(
                f"interval unit {unit!r} unsupported (DATE granularity: "
                "year/month/week/day)"
            )
    if not matched:
        raise PlanError(f"cannot parse interval {text!r}")
    return months, days


def _argtype(t: PType):
    """Decode tag for host-side multi-arg string evaluation (expr/strings.py)."""
    if t.col == ColType.STRING:
        return "str"
    if t.col == ColType.JSONB:
        return "jsonb"
    if t.col == ColType.NUMERIC:
        return ("numeric", t.scale)
    if t.col == ColType.FLOAT64:
        return "float"
    if t.col == ColType.BOOL:
        return "bool"
    return "int"


def _literal_int(e, what: str) -> int:
    if isinstance(e, ast.NumberLit) and "." not in e.value:
        return int(e.value)
    raise PlanError(f"{what} must be an integer literal")


def _rescale(e, from_scale: int, to_scale: int):
    if from_scale == to_scale:
        return e
    if to_scale > from_scale:
        return CallBinary("mul", e, Literal(10 ** (to_scale - from_scale)))
    return CallBinary("floordiv", e, Literal(10 ** (from_scale - to_scale)))


NUMERIC_DIV_SCALE = 6  # least number of fractional digits a NUMERIC quotient keeps


def _numeric_div(l, lt: "PType", r, rt: "PType"):
    """`l / r` under the module note's one NUMERIC division rule."""
    target = max(lt.scale, rt.scale, NUMERIC_DIV_SCALE)  # a PType that is not NUMERIC has scale 0
    num = CallBinary("mul_exact", l, Literal(10 ** (target + rt.scale - lt.scale)))
    return CallBinary("div", num, r), PType(ColType.NUMERIC, target)


def _avg_type(vt: "PType") -> "PType":
    """What `avg` over a column of type `vt` returns."""
    if vt.col == ColType.FLOAT64:
        return FLOAT
    return PType(ColType.NUMERIC, max(vt.scale, NUMERIC_DIV_SCALE))


class Planner:
    def __init__(self, catalog):
        self.catalog = catalog
        self._cte_frames: list[dict] = []  # name -> ("cte", PlannedQuery) | ("rec", gid, Scope)
        self._rec_counter = 0
        # extended-protocol parameter values for the statement being planned
        # (text-format Python values: str | None), set via set_params()
        self._params: tuple | None = None

    def set_params(self, params) -> None:
        """Bind $n parameter values (tuple of str|None) for subsequent plans."""
        self._params = tuple(params) if params is not None else None

    def _lookup_cte(self, name: str):
        for frame in reversed(self._cte_frames):
            if name in frame:
                return frame[name]
        return None

    # -- expression planning -------------------------------------------------
    def plan_scalar(self, e, scope: Scope):
        """AST expr → (ScalarExpr, PType)."""
        if isinstance(e, _AggRef):
            raise PlanError("aggregate not allowed here")
        if isinstance(e, _WinRef):
            raise PlanError("window functions are only allowed in SELECT items")
        if isinstance(e, _PostCol):
            return Column(e.index), scope.cols[e.index].typ
        if isinstance(e, _PostSum):
            # sum over an all-NULL (or empty) group is NULL, not 0
            guard = CallBinary("gt", Column(e.cnt_col), Literal(0))
            null = Literal(None, e.vt.dtype.name)
            return CallVariadic("if", (guard, Column(e.sum_col), null)), e.vt
        if isinstance(e, _PostAvg):
            # nullif guard: a group whose inputs are all NULL has non-null
            # count 0 and must yield NULL, not divide by zero
            if e.vt.col != ColType.FLOAT64:
                den = CallVariadic("nullif", (Column(e.cnt_col), Literal(0)))
                return _numeric_div(Column(e.sum_col), e.vt, den, INT)
            den = CallVariadic(
                "nullif", (CallUnary("cast_float", Column(e.cnt_col)), Literal(0.0, "float32"))
            )
            return CallBinary("div", _to_float(Column(e.sum_col), e.vt), den), FLOAT
        if isinstance(e, _PostStat):
            # var = (sum_sq - sum^2/n) / (n - ddof); stddev = sqrt(var)
            s_ = _to_float(Column(e.sum_col), e.vt)
            sq_t = PType(ColType.NUMERIC, e.vt.scale * 2) if e.vt.col == ColType.NUMERIC else e.vt
            q = _to_float(Column(e.sq_col), sq_t)
            n = CallUnary("cast_float", Column(e.cnt_col))
            mean_sq = CallBinary("div", CallBinary("mul", s_, s_), n)
            ddof = Literal(0.0 if e.pop else 1.0, "float32")
            denom = CallBinary("sub", n, ddof)
            safe = CallVariadic("if", (CallBinary("gt", denom, Literal(0.0, "float32")), denom, Literal(1.0, "float32")))
            var = CallBinary("div", CallBinary("sub", q, mean_sq), safe)
            var = CallVariadic("if", (CallBinary("gt", denom, Literal(0.0, "float32")), var, Literal(0.0, "float32")))
            if e.sqrt:
                return CallUnary("sqrt", var), FLOAT
            return var, FLOAT
        if isinstance(e, ast.Param):
            if self._params is None or not (1 <= e.index <= len(self._params)):
                raise PlanError(f"parameter ${e.index} not bound")
            v = self._params[e.index - 1]
            # text-protocol values are typed structurally, never spliced back
            # into SQL text (the round-1 re-literalizing shim is gone).
            # Known limitation: a digits-only value bound against a TEXT
            # column types as INT (pg infers parameter types from context;
            # this planner does not yet)
            if v is None:
                return Literal(None), INT
            if not isinstance(v, str):
                # programmatic callers may bind Python values directly; the
                # wire path always delivers text-format strings
                v = str(v)
            import re as _re

            if _re.fullmatch(r"\d{4}-\d{2}-\d{2}", v):
                from ..storage.generator import date_num

                y, mo, d = (int(x) for x in v.split("-"))
                return Literal(int(date_num(y, mo, d))), DATE
            s = v.lstrip("+")
            if _re.fullmatch(r"-?\d+", s):
                return Literal(int(s)), INT
            m = _re.fullmatch(r"-?(\d*)\.(\d+)", s)
            if m:
                scale = len(m.group(2))
                neg = s.startswith("-")
                iv = int(m.group(1) or "0") * 10**scale + int(m.group(2))
                return Literal(-iv if neg else iv), PType(ColType.NUMERIC, scale)
            if v.lower() in ("t", "true", "f", "false"):
                return Literal(v.lower() in ("t", "true"), "bool"), BOOL
            return Literal(self.catalog.dict.encode(v)), STRING
        if isinstance(e, ast.Ident):
            i = scope.resolve(e.name, e.qualifier)
            return Column(i), scope.cols[i].typ
        if isinstance(e, ast.NumberLit):
            if "e" in e.value or "E" in e.value:
                # scientific notation is always a float literal (f32, the
                # device float precision — repr/types.py FLOAT64 rule)
                import numpy as _np

                return Literal(float(_np.float32(e.value)), "float32"), FLOAT
            if "." in e.value:
                intpart, frac = e.value.split(".")
                scale = len(frac)
                v = int(intpart or "0") * 10**scale + int(frac)
                return Literal(v), PType(ColType.NUMERIC, scale)
            return Literal(int(e.value)), INT
        if isinstance(e, ast.StringLit):
            return Literal(self.catalog.dict.encode(e.value)), STRING
        if isinstance(e, ast.BoolLit):
            return Literal(e.value, "bool"), BOOL
        if isinstance(e, ast.NullLit):
            # untyped NULL: int64 carrier; 3VL makes the dtype inert
            return Literal(None), INT
        if isinstance(e, ast.DateLit):
            from ..storage.generator import date_num

            y, m, d = (int(x) for x in e.value.split("-"))
            return Literal(int(date_num(y, m, d))), DATE
        if isinstance(e, ast.UnaryOp):
            v, t = self.plan_scalar(e.expr, scope)
            if e.op == "-":
                return CallUnary("neg", v), t
            if e.op == "not":
                return CallUnary("not", v), BOOL
            raise PlanError(f"unary {e.op}")
        if isinstance(e, ast.BinaryOp):
            return self._plan_binary(e, scope)
        if isinstance(e, ast.Between):
            lo = ast.BinaryOp(">=", e.expr, e.low)
            hi = ast.BinaryOp("<=", e.expr, e.high)
            both = ast.BinaryOp("and", lo, hi)
            if e.negated:
                both = ast.UnaryOp("not", both)
            return self.plan_scalar(both, scope)
        if isinstance(e, ast.InList):
            if any(isinstance(i, ast.Subquery) for i in e.items):
                raise PlanError("IN (SELECT …) must be planned at relation level")
            ors = None
            for item in e.items:
                eq = ast.BinaryOp("=", e.expr, item)
                ors = eq if ors is None else ast.BinaryOp("or", ors, eq)
            if e.negated:
                ors = ast.UnaryOp("not", ors)
            return self.plan_scalar(ors, scope)
        if isinstance(e, ast.IsNull):
            v, _t = self.plan_scalar(e.expr, scope)
            return CallUnary("is_not_null" if e.negated else "is_null", v), BOOL
        if isinstance(e, ast.Case):
            return self._plan_case(e, scope)
        if isinstance(e, ast.Cast):
            return self._plan_cast(e, scope)
        if isinstance(e, ast.FuncCall):
            return self._plan_func(e, scope)
        if isinstance(e, ast.Subquery):
            raise PlanError("scalar subqueries not supported yet")
        raise PlanError(f"unsupported expression: {e!r}")

    def _plan_binary(self, e: ast.BinaryOp, scope: Scope):
        op = e.op
        # DATE ± INTERVAL (and INTERVAL + DATE): calendar arithmetic planned
        # structurally — months via the clamping add_months kernel, days as
        # plain addition (mz-repr Interval, DATE-granularity slice)
        if op in ("+", "-") and (
            isinstance(e.right, ast.IntervalLit) or isinstance(e.left, ast.IntervalLit)
        ):
            if isinstance(e.left, ast.IntervalLit):
                if op == "-":
                    raise PlanError("cannot subtract a date from an interval")
                date_ast, iv = e.right, e.left
            else:
                date_ast, iv = e.left, e.right
            months, days = _parse_interval(iv.value)
            if op == "-":
                months, days = -months, -days
            v, vt = self.plan_scalar(date_ast, scope)
            if vt.col != ColType.TIMESTAMP:
                raise PlanError("interval arithmetic requires a date operand")
            # pg/Materialize order: months FIRST (with end-of-month clamp),
            # then days — '1995-03-31' - '1 month 1 day' is Feb 27, not the
            # day-first Feb 28
            if months:
                v = CallBinary("add_months", v, Literal(months))
            if days:
                v = CallBinary("add", v, Literal(days))
            return v, DATE
        if isinstance(e.left, ast.IntervalLit) or isinstance(e.right, ast.IntervalLit):
            raise PlanError(f"INTERVAL unsupported with operator {op}")
        if op in ("and", "or"):
            l, _ = self.plan_scalar(e.left, scope)
            r, _ = self.plan_scalar(e.right, scope)
            return CallBinary(op, l, r), BOOL
        l, lt = self.plan_scalar(e.left, scope)
        r, rt = self.plan_scalar(e.right, scope)
        if op in ("=", "<>", "<", "<=", ">", ">="):
            if op not in ("=", "<>") and ColType.JSONB in (lt.col, rt.col):
                raise PlanError(
                    "jsonb ordering comparisons are not supported "
                    "(equality and grouping are)"
                )
            if op in ("=", "<>") and {lt.col, rt.col} == {
                ColType.JSONB, ColType.STRING
            }:
                # jsonb equality is CANONICAL-text equality: a verbatim text
                # literal with different spacing/key order must re-encode
                # canonically, or the code comparison is silently false
                def canon(expr, t):
                    if t.col != ColType.STRING:
                        return expr
                    if isinstance(expr, Literal) and expr.value is not None:
                        from ..expr.strings import json_canonical

                        try:
                            txt = json_canonical(self.catalog.dict.decode(expr.value))
                        except ValueError as exc:
                            raise PlanError(
                                f"invalid input syntax for type jsonb: {exc}"
                            ) from exc
                        return Literal(self.catalog.dict.encode(txt))
                    return self._dictfunc(("jsonb_parse",), (expr,), ("str",), "string")

                l, r = canon(l, lt), canon(r, rt)
                fn = "eq" if op == "=" else "ne"
                return CallBinary(fn, l, r), BOOL
            if (
                op not in ("=", "<>")
                and ColType.STRING in (lt.col, rt.col)
            ):
                # dictionary codes are insertion-ordered: inequality must
                # compare DECODED strings (host path; fused falls back).
                # Equality on codes stays exact and device-native.
                if isinstance(l, Literal) and l.value is None:
                    return Literal(None, "int8"), BOOL  # NULL cmp is NULL
                if isinstance(r, Literal) and r.value is None:
                    return Literal(None, "int8"), BOOL
                if lt.col != rt.col:
                    raise PlanError("cannot compare string with non-string")
                fn = {"<": "str_lt", "<=": "str_lte", ">": "str_gt", ">=": "str_gte"}[op]
                return (
                    self._dictfunc((fn,), (l, r), ("str", "str"), "bool"),
                    BOOL,
                )
            l, r, _t = self._align(l, lt, r, rt)
            fn = {"=": "eq", "<>": "ne", "<": "lt", "<=": "lte", ">": "gt", ">=": "gte"}[op]
            return CallBinary(fn, l, r), BOOL
        if op in ("+", "-"):
            l, r, t = self._align(l, lt, r, rt)
            return CallBinary("add" if op == "+" else "sub", l, r), t
        if op == "*":
            t = self._arith_type(lt, rt)
            if t.col == ColType.NUMERIC:
                return CallBinary("mul", l, r), PType(ColType.NUMERIC, lt.scale + rt.scale)
            if t.col == ColType.FLOAT64:
                return CallBinary("mul", _descaled(l, lt), _descaled(r, rt)), FLOAT
            return CallBinary("mul", l, r), t
        if op == "/":
            t = self._arith_type(lt, rt)
            if t.col == ColType.FLOAT64:
                return CallBinary("div", _descaled(l, lt), _descaled(r, rt)), FLOAT
            if t.col == ColType.NUMERIC:
                return _numeric_div(l, lt, r, rt)
            return CallBinary("div", l, r), INT
        if op == "%":
            return CallBinary("mod", l, r), INT
        if op in ("->", "->>"):
            if lt.col != ColType.JSONB:
                raise PlanError(f"{op} requires a jsonb left operand")
            as_text = op == "->>"
            out_t = STRING if as_text else JSONB
            fname = "json_get_text" if as_text else "json_get"
            if (
                isinstance(r, CallUnary)
                and r.func == "neg"
                and isinstance(r.expr, Literal)
            ):
                r = Literal(-r.expr.value, r.expr.dtype)  # j -> -1 (from end)
            if isinstance(r, Literal) and r.value is not None:
                key = (
                    self.catalog.dict.decode(r.value)
                    if rt.col == ColType.STRING
                    else int(r.value)
                )
                return (
                    self._dictfunc((fname, key), (l,), ("str",), "string"),
                    out_t,
                )
            raise PlanError(f"{op} key must be a literal string or integer")
        if op in ("like", "not_like", "ilike", "not_ilike"):
            if lt.col != ColType.STRING:
                raise PlanError("LIKE requires a string operand")
            ci = "ilike" in op
            if isinstance(r, Literal) and rt.col == ColType.STRING and r.value is not None:
                pat = self.catalog.dict.decode(r.value)
                d = self._dictfunc(("like", pat, ci), (l,), ("str",), "bool")
            elif rt.col == ColType.STRING:
                d = self._dictfunc(("like_dyn", ci), (l, r), ("str", "str"), "bool")
            else:
                raise PlanError("LIKE pattern must be a string")
            if op.startswith("not_"):
                d = CallUnary("not", d)
            return d, BOOL
        if op == "||":
            if ColType.STRING not in (lt.col, rt.col):
                raise PlanError("|| requires at least one string operand")
            if isinstance(l, Literal) and lt.col == ColType.STRING and l.value is not None:
                lit = self.catalog.dict.decode(l.value)
                if rt.col == ColType.STRING:
                    return self._dictfunc(("concat_l", lit), (r,), ("str",), "string"), STRING
            if isinstance(r, Literal) and rt.col == ColType.STRING and r.value is not None:
                lit = self.catalog.dict.decode(r.value)
                if lt.col == ColType.STRING:
                    return self._dictfunc(("concat_r", lit), (l,), ("str",), "string"), STRING
            return (
                self._dictfunc(
                    ("concat",), (l, r), (_argtype(lt), _argtype(rt)), "string"
                ),
                STRING,
            )
        raise PlanError(f"binary op {op}")

    def _dictfunc(self, spec, args, argtypes, out):
        from ..expr.scalar import DictFunc

        return DictFunc(tuple(spec), tuple(args), tuple(argtypes), out, self.catalog.str_tables)

    def _arith_type(self, lt: PType, rt: PType) -> PType:
        if ColType.FLOAT64 in (lt.col, rt.col):
            return FLOAT
        if ColType.NUMERIC in (lt.col, rt.col):
            return PType(ColType.NUMERIC, max(lt.scale, rt.scale))
        return INT

    def _common_type(self, lt: PType, rt: PType) -> PType:
        t = self._arith_type(lt, rt)
        if t.col == ColType.NUMERIC:
            return PType(ColType.NUMERIC, max(lt.scale, rt.scale))
        return t

    def _align_to(self, e, t: PType, target: PType):
        """Rescale/cast one planned expr to `target` (for n-ary alignment)."""
        if target.col == ColType.NUMERIC:
            from_scale = t.scale if t.col == ColType.NUMERIC else 0
            return _rescale(e, from_scale, target.scale)
        if target.col == ColType.FLOAT64 and t.col != ColType.FLOAT64:
            return _to_float(e, t)
        return e

    def _align(self, l, lt: PType, r, rt: PType):
        """Align numeric scales for add/sub/compare."""
        t = self._arith_type(lt, rt)
        if t.col == ColType.NUMERIC:
            target = max(lt.scale, rt.scale)
            l = _rescale(l, lt.scale, target)
            r = _rescale(r, rt.scale, target)
            return l, r, PType(ColType.NUMERIC, target)
        if t.col == ColType.FLOAT64:
            return _to_float(l, lt), _to_float(r, rt), FLOAT
        return l, r, t

    def _plan_case(self, e: ast.Case, scope: Scope):
        whens = e.whens
        if e.operand is not None:
            whens = tuple(
                (ast.BinaryOp("=", e.operand, cond), res) for cond, res in whens
            )
        else_, et = (
            self.plan_scalar(e.else_, scope) if e.else_ is not None else (Literal(0), INT)
        )
        result = else_
        rt = et
        for cond, res in reversed(whens):
            c, _ = self.plan_scalar(cond, scope)
            v, vt = self.plan_scalar(res, scope)
            v, result, rt = self._align(v, vt, result, rt)
            result = CallVariadic("if", (c, v, result))
        return result, rt

    def _plan_cast(self, e: ast.Cast, scope: Scope):
        from ..adapter.catalog import coltype_of

        v, vt = self.plan_scalar(e.expr, scope)
        target = coltype_of(e.typ)
        if target == ColType.JSONB:
            if vt.col == ColType.JSONB:
                return v, JSONB
            if vt.col == ColType.STRING:
                # text → jsonb: parse + canonicalize (invalid JSON → NULL,
                # documented divergence from pg's error)
                return (
                    self._dictfunc(("jsonb_parse",), (v,), ("str",), "string"),
                    JSONB,
                )
            raise PlanError("cast to jsonb supports text input")
        if vt.col == ColType.JSONB and target == ColType.STRING:
            return v, STRING  # canonical text IS the value
        if target == ColType.NUMERIC:
            scale = 2
            if vt.col == ColType.NUMERIC:
                return _rescale(v, vt.scale, scale), PType(ColType.NUMERIC, scale)
            return CallBinary("mul", CallUnary("cast_int64", v), Literal(10**scale)), PType(
                ColType.NUMERIC, scale
            )
        if target in (ColType.INT64, ColType.INT32):
            if vt.col == ColType.NUMERIC:
                return _rescale(v, vt.scale, 0), INT
            return CallUnary("cast_int64", v), INT
        if target == ColType.FLOAT64:
            return CallUnary("cast_float", _descale(v, vt)), FLOAT
        if target == ColType.BOOL:
            return CallUnary("is_true", v), BOOL
        raise PlanError(f"unsupported cast to {e.typ}")

    def _plan_func(self, e: ast.FuncCall, scope: Scope):
        name = e.name
        if e.over is not None:
            raise PlanError("window functions are only allowed in SELECT items")
        if name in _WINDOW_FUNCS:
            raise PlanError(f"window function {name} requires an OVER clause")
        if name in _AGG_FUNCS:
            raise PlanError(f"aggregate {name} not allowed in this context")
        if name == "abs":
            v, t = self.plan_scalar(e.args[0], scope)
            return CallUnary("abs", v), t
        if name in ("greatest", "least"):
            planned = [self.plan_scalar(a, scope) for a in e.args]
            t = planned[0][1]
            return CallVariadic(name, tuple(p for p, _ in planned)), t
        if name in ("extract_year", "extract_month", "extract_day"):
            v, _t = self.plan_scalar(e.args[0], scope)
            return CallUnary(name, v), INT
        if name == "sqrt":
            v, vt = self.plan_scalar(e.args[0], scope)
            return CallUnary("sqrt", _to_float(v, vt)), FLOAT
        if name == "coalesce":
            if not e.args:
                raise PlanError("coalesce needs at least one argument")
            planned = [self.plan_scalar(a, scope) for a in e.args]
            # common result type, then align every operand to it once
            common = planned[0][1]
            for _v, t in planned[1:]:
                common = self._common_type(common, t)
            aligned = tuple(
                self._align_to(v, t, common) for v, t in planned
            )
            return CallVariadic("coalesce", aligned), common
        if name == "nullif":
            if len(e.args) != 2:
                raise PlanError("nullif takes exactly two arguments")
            l, lt = self.plan_scalar(e.args[0], scope)
            r, rt = self.plan_scalar(e.args[1], scope)
            # aligned values compare; the aligned type is what decodes them
            l2, r2, t = self._align(l, lt, r, rt)
            return CallVariadic("nullif", (l2, r2)), t
        return self._plan_scalar_func_lib(e, scope)

    def _plan_scalar_func_lib(self, e: ast.FuncCall, scope: Scope):
        """The string/math/date scalar function library.

        Mirrors the accessible core of the reference's Unary/Binary/Variadic
        function registry (src/expr/src/scalar/func/macros.rs:153; string
        impls in func/impls/string.rs). String functions evaluate over
        dictionary codes via host-built tables (expr/strings.py)."""
        name = e.name
        args = e.args

        def plan(i):
            return self.plan_scalar(args[i], scope)

        def need(n_, *alts):
            if len(args) not in (n_, *alts):
                raise PlanError(f"{name} argument count")

        def str_arg(i):
            v, t = plan(i)
            if t.col != ColType.STRING:
                raise PlanError(f"{name} requires a string argument")
            return v

        def lit_str(i):
            a = args[i]
            if isinstance(a, ast.StringLit):
                return a.value
            v, t = plan(i)
            if isinstance(v, Literal) and t.col == ColType.STRING and v.value is not None:
                return self.catalog.dict.decode(v.value)
            raise PlanError(f"{name}: argument {i + 1} must be a string literal")

        def lit_int(i):
            v, t = plan(i)
            if isinstance(v, CallUnary) and v.func == "neg" and isinstance(v.expr, Literal):
                v = Literal(-v.expr.value, v.expr.dtype)
            if isinstance(v, Literal) and v.value is not None and t.col != ColType.STRING:
                return int(v.value)
            raise PlanError(f"{name}: argument {i + 1} must be an integer literal")

        # -- string → string / int / bool (dictionary-table) ----------------
        if name in ("upper", "lower", "initcap", "reverse", "md5"):
            need(1)
            return self._dictfunc((name,), (str_arg(0),), ("str",), "string"), STRING
        if name in ("trim", "btrim", "ltrim", "rtrim"):
            need(1, 2)
            f = "trim" if name == "btrim" else name
            spec = (f,) if len(args) == 1 else (f, lit_str(1))
            return self._dictfunc(spec, (str_arg(0),), ("str",), "string"), STRING
        if name in ("substr", "substring"):
            need(2, 3)
            ln = lit_int(2) if len(args) == 3 else None
            spec = ("substr", lit_int(1), ln)
            return self._dictfunc(spec, (str_arg(0),), ("str",), "string"), STRING
        if name in ("left", "right"):
            need(2)
            return self._dictfunc((name, lit_int(1)), (str_arg(0),), ("str",), "string"), STRING
        if name == "repeat":
            need(2)
            return self._dictfunc((name, lit_int(1)), (str_arg(0),), ("str",), "string"), STRING
        if name in ("lpad", "rpad"):
            need(2, 3)
            spec = (name, lit_int(1)) if len(args) == 2 else (name, lit_int(1), lit_str(2))
            return self._dictfunc(spec, (str_arg(0),), ("str",), "string"), STRING
        if name == "replace":
            need(3)
            return (
                self._dictfunc(
                    ("replace", lit_str(1), lit_str(2)), (str_arg(0),), ("str",), "string"
                ),
                STRING,
            )
        if name == "split_part":
            need(3)
            return (
                self._dictfunc(
                    ("split_part", lit_str(1), lit_int(2)), (str_arg(0),), ("str",), "string"
                ),
                STRING,
            )
        if name in ("length", "char_length", "character_length"):
            need(1)
            return self._dictfunc(("length",), (str_arg(0),), ("str",), "int64"), INT
        if name in ("bit_length", "octet_length", "ascii"):
            need(1)
            return self._dictfunc((name,), (str_arg(0),), ("str",), "int64"), INT
        if name in ("strpos", "position"):
            need(2)
            s = str_arg(0)
            try:
                sub = lit_str(1)
                return self._dictfunc(("strpos", sub), (s,), ("str",), "int64"), INT
            except PlanError:
                return (
                    self._dictfunc(("strpos",), (s, str_arg(1)), ("str", "str"), "int64"),
                    INT,
                )
        if name in ("starts_with", "ends_with"):
            need(2)
            s = str_arg(0)
            try:
                lit = lit_str(1)
                return self._dictfunc((name, lit), (s,), ("str",), "bool"), BOOL
            except PlanError:
                return (
                    self._dictfunc((name,), (s, str_arg(1)), ("str", "str"), "bool"),
                    BOOL,
                )
        if name in ("concat", "concat_ws"):
            if name == "concat_ws" and len(args) < 2:
                raise PlanError("concat_ws needs a separator and arguments")
            if not args:  # concat() is ''
                return Literal(self.catalog.dict.encode("")), STRING
            planned = [self.plan_scalar(a, scope) for a in args]
            # pg concat treats NULL string args as ''; coalesce them so the
            # NULL-propagating DictFunc matches (non-string NULLs still
            # propagate — documented divergence). concat_ws must NOT
            # coalesce: NULL args are skipped at eval time (no phantom
            # separators) and a NULL separator yields NULL — the eval layer
            # handles both (expr/scalar.py concat_ws null semantics).
            empty = Literal(self.catalog.dict.encode(""))
            vals, ats = [], []
            for v, t in planned:
                if t.col == ColType.STRING and name == "concat":
                    v = CallVariadic("coalesce", (v, empty))
                vals.append(v)
                ats.append(_argtype(t))
            return (
                self._dictfunc((name,), tuple(vals), tuple(ats), "string"),
                STRING,
            )

        # -- math -------------------------------------------------------------
        if name in ("floor", "ceil", "ceiling", "trunc") and len(args) == 1:
            v, t = plan(0)
            f = "ceil" if name == "ceiling" else name
            if t.col == ColType.NUMERIC and t.scale > 0:
                unit = Literal(10**t.scale)
                if f == "trunc":
                    q = CallBinary("div", v, unit)  # truncates toward zero
                else:
                    q = CallBinary("fdiv" if f == "floor" else "div", v, unit)
                    if f == "ceil":
                        # ceil = -floor(-v)
                        q = CallUnary("neg", CallBinary("fdiv", CallUnary("neg", v), unit))
                return CallBinary("mul", q, unit), t
            if t.col in (ColType.INT64, ColType.INT32) or (
                t.col == ColType.NUMERIC and t.scale == 0
            ):
                return v, t
            return CallUnary(f, _to_float(v, t)), FLOAT
        if name == "round" and len(args) in (1, 2):
            v, t = plan(0)
            if t.col == ColType.NUMERIC:
                digits = lit_int(1) if len(args) == 2 else 0
                if digits >= t.scale:
                    return v, t
                # half-away-from-zero at the target digit, keep the scale
                unit = Literal(10 ** (t.scale - digits))
                half = Literal(10 ** (t.scale - digits) // 2)
                pos = CallBinary("mul", CallBinary("div", CallBinary("add", v, half), unit), unit)
                neg = CallBinary("mul", CallBinary("div", CallBinary("sub", v, half), unit), unit)
                return (
                    CallVariadic("if", (CallBinary("gte", v, Literal(0)), pos, neg)),
                    t,
                )
            if len(args) == 2:
                digits = lit_int(1)
                m = Literal(float(np.float32(10.0**digits)), "float32")
                scaled = CallBinary("mul", _to_float(v, t), m)
                return CallBinary("div", CallUnary("round_half_away", scaled), m), FLOAT
            if t.col in (ColType.INT64, ColType.INT32):
                return v, t
            return CallUnary("round_half_away", _to_float(v, t)), FLOAT
        if name == "sign":
            need(1)
            v, t = plan(0)
            return CallUnary("sign", v), (FLOAT if t.col == ColType.FLOAT64 else INT)
        if name in ("exp", "ln", "log10", "log2", "sin", "cos", "tan", "cot",
                    "asin", "acos", "atan", "sinh", "cosh", "tanh", "cbrt",
                    "degrees", "radians"):
            need(1)
            v, t = plan(0)
            return CallUnary(name, _to_float(v, t)), FLOAT
        if name == "log":
            need(1, 2)
            if len(args) == 1:
                v, t = plan(0)
                return CallUnary("log10", _to_float(v, t)), FLOAT
            b, bt = plan(0)
            v, t = plan(1)
            return (
                CallBinary(
                    "div",
                    CallUnary("ln", _to_float(v, t)),
                    CallUnary("ln", _to_float(b, bt)),
                ),
                FLOAT,
            )
        if name in ("power", "pow"):
            need(2)
            l, lt = plan(0)
            r, rt = plan(1)
            return CallBinary("pow", _to_float(l, lt), _to_float(r, rt)), FLOAT
        if name == "atan2":
            need(2)
            l, lt = plan(0)
            r, rt = plan(1)
            return CallBinary("atan2", _to_float(l, lt), _to_float(r, rt)), FLOAT
        if name == "pi":
            need(0)
            return Literal(float(np.float32(np.pi)), "float32"), FLOAT
        if name == "mod":
            need(2)
            l, lt = plan(0)
            r, rt = plan(1)
            return CallBinary("mod", l, r), INT

        # -- date -------------------------------------------------------------
        if name in ("date_trunc", "date_part"):
            need(2)
            fld = lit_str(0).lower()
            v, t = plan(1)
            if name == "date_part":
                return self.plan_scalar(
                    ast.FuncCall(f"extract_{fld}", (args[1],)), scope
                )
            if fld not in ("year", "quarter", "month", "week", "day"):
                raise PlanError(f"date_trunc field {fld!r} unsupported for DATE")
            return CallUnary(f"date_trunc_{fld}", v), DATE
        if name in ("extract_dow", "extract_isodow", "extract_doy",
                    "extract_quarter", "extract_week", "extract_century",
                    "extract_decade", "extract_millennium"):
            need(1)
            v, _t = plan(0)
            return CallUnary(name, v), INT
        if name == "extract_epoch":
            need(1)
            v, _t = plan(0)
            return CallUnary("extract_epoch_date", v), INT

        # -- jsonb ------------------------------------------------------------
        if name == "jsonb_typeof":
            need(1)
            v, t = plan(0)
            if t.col != ColType.JSONB:
                raise PlanError("jsonb_typeof requires a jsonb argument")
            return self._dictfunc(("jsonb_typeof",), (v,), ("str",), "string"), STRING
        if name == "jsonb_array_length":
            need(1)
            v, t = plan(0)
            if t.col != ColType.JSONB:
                raise PlanError("jsonb_array_length requires a jsonb argument")
            return (
                self._dictfunc(("jsonb_array_length",), (v,), ("str",), "int64"),
                INT,
            )
        if name == "to_jsonb":
            need(1)
            v, t = plan(0)
            if t.col == ColType.JSONB:
                return v, JSONB
            if t.col == ColType.STRING:
                # a string becomes a JSON string value (quoted/escaped)
                return (
                    self._dictfunc(("jsonb_quote",), (v,), ("str",), "string"),
                    JSONB,
                )
            raise PlanError("to_jsonb supports jsonb/text arguments")
        raise PlanError(f"unsupported function: {name}")

    # -- relation planning ---------------------------------------------------
    def plan_query(self, q: ast.Query) -> PlannedQuery:
        frame: dict = {}
        rec_bindings: list = []
        if q.ctes:
            self._cte_frames.append(frame)
            if q.recursive:
                # declare every binding up front (bodies may reference any)
                from ..adapter.catalog import coltype_of

                for b in q.ctes:
                    if not b.columns:
                        raise PlanError(
                            f"WITH MUTUALLY RECURSIVE binding {b.name} needs "
                            "explicit column types (name type, …)"
                        )
                    gid = f"rec{self._rec_counter}_{b.name}"
                    self._rec_counter += 1
                    cols = [
                        ScopeCol(b.name, cname, PType(coltype_of(ctyp),
                                 2 if coltype_of(ctyp) == ColType.NUMERIC else 0))
                        for cname, ctyp in b.columns
                    ]
                    frame[b.name] = ("rec", gid, Scope(cols))
                for b in q.ctes:
                    pq = self.plan_query(b.query)
                    if len(pq.scope.cols) != len(b.columns):
                        raise PlanError(
                            f"binding {b.name}: body arity {len(pq.scope.cols)} "
                            f"!= declared {len(b.columns)}"
                        )
                    _k, gid, scope = frame[b.name]
                    brel = pq.mir
                    if pq.finishing.limit is not None:
                        brel = _apply_finishing_as_topk(pq)
                    rec_bindings.append(
                        (gid, tuple(c.typ.dtype for c in scope.cols), brel)
                    )
            else:
                for b in q.ctes:
                    frame[b.name] = ("cte", self.plan_query(b.query))
        try:
            rel, scope = self.plan_set_expr(q.body)
        finally:
            if q.ctes:
                self._cte_frames.pop()
        if rec_bindings:
            rel = mir.MirLetRec(tuple(rec_bindings), rel)
        order, limit, offset = q.order_by, q.limit, q.offset
        order_idx = []
        nulls_last = []
        for ob in order:
            idx = self._resolve_output_col(ob.expr, q.body, scope)
            order_idx.append((idx, ob.desc))
            nl = ob.nulls_last
            nulls_last.append(not ob.desc if nl is None else nl)
        finishing = RowSetFinishing(
            tuple(order_idx), limit, offset, tuple(nulls_last)
        )
        return PlannedQuery(rel, scope, finishing)

    def _resolve_output_col(self, e, body, scope: Scope) -> int:
        if isinstance(e, ast.NumberLit) and "." not in e.value:
            n = int(e.value)
            if not (1 <= n <= len(scope.cols)):
                raise PlanError(f"ORDER BY position {n} out of range")
            return n - 1
        if isinstance(e, ast.Ident) and e.qualifier is None:
            for i, c in enumerate(scope.cols):
                if c.name == e.name:
                    return i
        raise PlanError(f"cannot resolve ORDER BY expression {e!r}")

    def plan_set_expr(self, body):
        if isinstance(body, ast.Select):
            return self.plan_select(body)
        if isinstance(body, ast.Values):
            return self.plan_values(body)
        if isinstance(body, ast.SetOp):
            lrel, lscope = self.plan_set_expr(body.left)
            rrel, rscope = self.plan_set_expr(body.right)
            if len(lscope.cols) != len(rscope.cols):
                raise PlanError("set operands have different arities")
            op = body.op
            if op == "union_all":
                return mir.MirUnion((lrel, rrel)), lscope
            if op == "union":
                return mir.MirDistinct(mir.MirUnion((lrel, rrel))), lscope
            if op in ("except", "except_all"):
                if op == "except":
                    lrel, rrel = mir.MirDistinct(lrel), mir.MirDistinct(rrel)
                return (
                    mir.MirThreshold(mir.MirUnion((lrel, mir.MirNegate(rrel)))),
                    lscope,
                )
            if op in ("intersect", "intersect_all"):
                if op == "intersect":
                    lrel, rrel = mir.MirDistinct(lrel), mir.MirDistinct(rrel)
                # min(a,b) = a - (a - b)^+
                diff = mir.MirThreshold(mir.MirUnion((lrel, mir.MirNegate(rrel))))
                return (
                    mir.MirThreshold(mir.MirUnion((lrel, mir.MirNegate(diff)))),
                    lscope,
                )
            raise PlanError(f"set op {op}")
        if isinstance(body, ast.Query):
            pq = self.plan_query(body)
            if pq.finishing.limit is not None or pq.finishing.order_by:
                rel = _apply_finishing_as_topk(pq)
            else:
                rel = pq.mir
            return rel, pq.scope
        raise PlanError(f"unsupported query body {type(body).__name__}")

    def plan_values(self, v: ast.Values):
        if not v.rows:
            raise PlanError("VALUES needs at least one row")
        arity = len(v.rows[0])
        planned_rows = []
        types: list = [None] * arity
        for row in v.rows:
            if len(row) != arity:
                raise PlanError("VALUES rows must have equal arity")
            vals = []
            for i, e in enumerate(row):
                p, t = self.plan_scalar(e, Scope([]))
                if not isinstance(p, Literal):
                    raise PlanError("VALUES entries must be literals")
                if types[i] is None:
                    types[i] = t
                elif types[i].col != t.col:
                    # align int/numeric mixes by rescaling to the wider scale
                    if {types[i].col, t.col} == {ColType.INT64, ColType.NUMERIC}:
                        types[i] = t if t.col == ColType.NUMERIC else types[i]
                    else:
                        raise PlanError("VALUES column types must match")
                vals.append((p.value, t))
            planned_rows.append(vals)
        rows = []
        for vals in planned_rows:
            data = []
            for i, (raw, t) in enumerate(vals):
                target = types[i]
                if target.col == ColType.NUMERIC and t.scale != target.scale:
                    raw = raw * 10 ** (target.scale - t.scale)
                data.append(raw)
            rows.append((tuple(data), 1))
        rel = mir.MirConstant(
            rows=tuple(rows), dtypes=tuple(t.dtype for t in types)
        )
        scope = Scope(
            [ScopeCol(None, f"column{i+1}", t) for i, t in enumerate(types)]
        )
        return rel, scope

    def plan_select(self, sel: ast.Select):
        # 1. FROM: flatten factors + inner joins into one MirJoin
        factors: list = []
        scopes: list[Scope] = []
        on_preds: list = []
        outer_fm = getattr(self, "_pending_fm", None)
        self._pending_fm = []
        if not sel.from_:
            factors.append(mir.MirConstant(rows=(((), 1),), dtypes=()))
            scopes.append(Scope([]))
        for f in sel.from_:
            self._flatten_from(f, factors, scopes, on_preds)
        pending_fm = self._pending_fm
        self._pending_fm = outer_fm
        if pending_fm:
            # their scope slots must be the trailing ones: the FlatMap output
            # column is appended after all factor columns
            want = list(range(len(scopes) - len(pending_fm), len(scopes)))
            if [i for _n, _a, _al, i in pending_fm] != want:
                raise PlanError(
                    "correlated generate_series must come after all plain "
                    "FROM items"
                )
        # 1b. lift uncorrelated subqueries (IN / EXISTS / scalar) into join
        # factors — the decorrelation-lite path (reference: HIR→MIR lowering
        # in src/sql/src/plan/lowering.rs; correlated forms are future work)
        n_factors_pre_lift = len(factors)
        lifter = _SubqueryLifter(self, factors, scopes)
        # WHERE/ON conjuncts may register antijoins (top level only); other
        # contexts reject NOT IN/NOT EXISTS instead of silently misplanning
        new_where = None
        if sel.where is not None:
            parts = [lifter.rewrite_conjunct(c) for c in _split_and(sel.where)]
            for part in parts:
                new_where = part if new_where is None else ast.BinaryOp("and", new_where, part)
        on_preds[:] = [
            _join_and([lifter.rewrite_conjunct(c) for c in _split_and(p_)])
            for p_ in on_preds
        ]
        sel = replace(
            sel,
            where=new_where,
            items=tuple(
                ast.SelectItem(lifter.rewrite(it.expr), it.alias) for it in sel.items
            ),
            having=lifter.rewrite(sel.having) if sel.having is not None else None,
        )

        full_scope = Scope([c for s in scopes for c in s.cols])
        offsets = []
        off = 0
        for s in scopes:
            offsets.append(off)
            off += len(s.cols)

        # 2. conjuncts from ON + WHERE; split equijoin equivalences vs filters
        conjuncts = []
        for p in on_preds:
            conjuncts.extend(_split_and(p))
        if sel.where is not None:
            conjuncts.extend(_split_and(sel.where))
        conjuncts.extend(lifter.extra_conjuncts)
        temporal = [c for c in conjuncts if _contains_mz_now(c)]
        conjuncts = [c for c in conjuncts if not _contains_mz_now(c)]
        if not factors:
            # every FROM item was a correlated table function: fan out of the
            # unit relation
            factors.append(mir.MirConstant(rows=(((), 1),), dtypes=()))
        if pending_fm and len(factors) > n_factors_pre_lift:
            # a lifted subquery factor would sit AFTER the FlatMap's scope
            # slot, misaligning every post-join column index
            raise PlanError(
                "correlated generate_series cannot be combined with "
                "IN/EXISTS/scalar subqueries yet"
            )
        flat_start = len(full_scope.cols) - len(pending_fm)
        equivs: list[set] = []
        residual = []
        for c in conjuncts:
            pair = self._as_column_equality(c, full_scope, scopes, offsets)
            # equalities touching a FlatMap output column can't join factors
            # (the column doesn't exist until after the join) — filter instead
            if pair is not None and all(i < flat_start for i in pair):
                merged = False
                for cls in equivs:
                    if pair[0] in cls or pair[1] in cls:
                        cls.update(pair)
                        merged = True
                        break
                if not merged:
                    equivs.append(set(pair))
            else:
                residual.append(c)
        scope = full_scope
        filters = [self.plan_scalar(c, scope)[0] for c in residual]
        if len(factors) == 1:
            rel = factors[0]
        elif lifter.extra_conjuncts and not pending_fm:
            # a decorrelated subquery is a per-key aggregate joined back on
            # its keys: what it joins is the outer relation, the FROM items
            # under the WHERE's own predicates (the dependent join's left
            # side), so those are joined and filtered first. One flat join
            # with every filter above it would stream each changed aggregate
            # through all of the outer rows of its key before any filter.
            n_outer = offsets[n_factors_pre_lift]
            outer = factors[0]
            if n_factors_pre_lift > 1:
                outer = mir.MirJoin(
                    inputs=tuple(factors[:n_factors_pre_lift]),
                    equivalences=tuple(
                        o for o in (tuple(sorted(i for i in c if i < n_outer)) for c in equivs)
                        if len(o) > 1
                    ),
                )
            own = [p for p in filters if all(i < n_outer for i in expr_columns(p))]
            filters = [p for p in filters if p not in own]
            for p in own:
                outer = mir.MirFilter(outer, (p,))
            # the outer columns of a class are equal already: one stands for them
            rel = mir.MirJoin(
                inputs=(outer, *factors[n_factors_pre_lift:]),
                equivalences=tuple(
                    x for x in (
                        tuple(sorted([i for i in c if i < n_outer][:1] + [i for i in c if i >= n_outer]))
                        for c in equivs
                    )
                    if len(x) > 1
                ),
            )
        else:
            rel = mir.MirJoin(
                inputs=tuple(factors),
                equivalences=tuple(tuple(sorted(c)) for c in equivs),
            )
        # correlated table functions fan out on top of the joined factors
        for k, (fname, fargs, _alias, _si) in enumerate(pending_fm):
            prefix = Scope(list(full_scope.cols[: flat_start + k]))
            planned_args = [self.plan_scalar(a, prefix)[0] for a in fargs]
            if len(planned_args) == 2:
                planned_args.append(Literal(1))
            rel = mir.MirFlatMap(rel, fname, tuple(planned_args))
        for p in filters:
            rel = mir.MirFilter(rel, (p,))
        if temporal:
            rel = self._plan_temporal(rel, temporal, scope)

        # NOT IN / NOT EXISTS antijoins: rel − (rel ⋉ sub), thresholded
        for key_ast, sub_pq, is_exists in lifter.antijoins:
            n = len(scope.cols)

            def anti(rel_in, key_expr, sub_rel):
                rel_k = mir.MirMap(rel_in, (key_expr,))
                matched = mir.MirProject(
                    mir.MirJoin(
                        inputs=(rel_k, sub_rel),
                        equivalences=((n, n + 1),),
                    ),
                    tuple(range(n)),
                )
                return mir.MirThreshold(
                    mir.MirUnion((rel_in, mir.MirNegate(matched)))
                )

            if is_exists:
                sub_rel = mir.MirDistinct(
                    mir.MirProject(
                        mir.MirMap(sub_pq.mir, (Literal(1),)),
                        (len(sub_pq.scope.cols),),
                    )
                )
                rel = anti(rel, Literal(1), sub_rel)
                continue
            # NOT IN, three-valued (pg semantics): a NULL key row passes only
            # when the subquery is EMPTY; if the subquery produces any NULL,
            # no row passes (x NOT IN S is then NULL or FALSE for every x)
            key_expr, _t = self.plan_scalar(key_ast, scope)
            sub = sub_pq.mir  # arity 1
            res0 = anti(
                mir.MirFilter(rel, (CallUnary("is_not_null", key_expr),)),
                key_expr,
                mir.MirDistinct(sub),
            )
            s_nonempty = mir.MirDistinct(
                mir.MirProject(mir.MirMap(sub, (Literal(1),)), (1,))
            )
            keep_null = anti(
                mir.MirFilter(rel, (CallUnary("is_null", key_expr),)),
                Literal(1),
                s_nonempty,
            )
            s_null = mir.MirDistinct(
                mir.MirProject(
                    mir.MirMap(
                        mir.MirFilter(sub, (CallUnary("is_null", Column(0)),)),
                        (Literal(1),),
                    ),
                    (1,),
                )
            )
            rel = anti(mir.MirUnion((res0, keep_null)), Literal(1), s_null)

        # 3. aggregates?
        has_group = bool(sel.group_by)
        aggs: list[ast.FuncCall] = []
        items = [
            ast.SelectItem(self._extract_aggs(it.expr, aggs), it.alias)
            for it in sel.items
        ]
        having = self._extract_aggs(sel.having, aggs) if sel.having is not None else None
        if has_group or aggs:
            rel, scope, items, having = self._plan_reduce(
                rel, scope, sel, items, aggs, having
            )
        if having is not None:
            p, _ = self.plan_scalar(having, scope)
            rel = mir.MirFilter(rel, (p,))

        # 3.5 window functions (evaluated after grouping/HAVING, pg order)
        wins: list[ast.FuncCall] = []
        items = [
            ast.SelectItem(self._extract_windows(it.expr, wins), it.alias)
            for it in items
        ]
        if wins:
            rel, scope = self._plan_windows(rel, scope, wins)
            items = [
                ast.SelectItem(self._rewrite_wins(it.expr), it.alias)
                for it in items
            ]

        # 4. projection (names come from the pre-rewrite select items)
        out_exprs = []
        out_cols = []
        for it, orig in zip(items, sel.items):
            if isinstance(it.expr, ast.Star):
                for i, c in enumerate(scope.cols):
                    if it.expr.qualifier is None or c.qualifier == it.expr.qualifier:
                        out_exprs.append((Column(i), c.typ))
                        out_cols.append(ScopeCol(c.qualifier, c.name, c.typ))
            else:
                p, t = self.plan_scalar(it.expr, scope)
                out_exprs.append((p, t))
                name = orig.alias or _default_name(orig.expr)
                out_cols.append(ScopeCol(None, name, t))
        arity_in = len(scope.cols)
        rel = mir.MirMap(rel, tuple(p for p, _ in out_exprs))
        rel = mir.MirProject(rel, tuple(range(arity_in, arity_in + len(out_exprs))))
        out_scope = Scope(out_cols)
        if sel.distinct:
            rel = mir.MirDistinct(rel)
        return rel, out_scope

    def _plan_temporal(self, rel, temporal, scope: Scope):
        """mz_now() comparisons → validity windows (MirTemporalFilter).

        mz_now() <= e  →  valid until e+1     mz_now() >= e  →  valid from e
        mz_now() <  e  →  valid until e       mz_now() >  e  →  valid from e+1
        (mirrored when mz_now() is on the right side).
        """
        lowers, uppers = [], []
        for c in temporal:
            if isinstance(c, ast.Between) and _is_mz_now(c.expr) and not c.negated:
                lo, _ = self.plan_scalar(c.low, scope)
                hi, _ = self.plan_scalar(c.high, scope)
                lowers.append(lo)
                uppers.append(CallBinary("add", hi, Literal(1)))
                continue
            if not isinstance(c, ast.BinaryOp):
                raise PlanError("mz_now() only supported in comparison predicates")
            lhs_now = _is_mz_now(c.left)
            rhs_now = _is_mz_now(c.right)
            if lhs_now == rhs_now:
                raise PlanError("mz_now() must appear alone on one side of a comparison")
            other = c.right if lhs_now else c.left
            if _contains_mz_now(other):
                raise PlanError("mz_now() must appear alone on one side of a comparison")
            e, _t = self.plan_scalar(other, scope)
            op = c.op
            if rhs_now:  # e OP mz_now() → mz_now() flip(OP) e
                op = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}[op]
            plus1 = CallBinary("add", e, Literal(1))
            if op == "<=":
                uppers.append(plus1)
            elif op == "<":
                uppers.append(e)
            elif op == ">=":
                lowers.append(e)
            elif op == ">":
                lowers.append(plus1)
            elif op == "=":
                lowers.append(e)
                uppers.append(plus1)
            else:
                raise PlanError(f"mz_now() unsupported with operator {op}")
        return mir.MirTemporalFilter(rel, tuple(lowers), tuple(uppers))

    def _flatten_from(self, f, factors, scopes, on_preds):
        if isinstance(f, ast.TableRef):
            cte = self._lookup_cte(f.name)
            if cte is not None:
                alias = f.alias or f.name
                if cte[0] == "rec":
                    _k, gid, rscope = cte
                    factors.append(mir.MirGet(gid, len(rscope.cols)))
                    scopes.append(
                        Scope([ScopeCol(alias, c.name, c.typ) for c in rscope.cols])
                    )
                    return
                pq = cte[1]
                rel = pq.mir
                if pq.finishing.limit is not None:
                    rel = _apply_finishing_as_topk(pq)
                factors.append(rel)
                scopes.append(
                    Scope([ScopeCol(alias, c.name, c.typ) for c in pq.scope.cols])
                )
                return
            item = self.catalog.get(f.name)
            if item.desc is None:
                raise PlanError(f"{f.name} has no relation description")
            alias = f.alias or f.name
            if item.kind == "view":
                # inline the stored view MIR (the reference inlines view
                # definitions during name resolution too)
                pq = item.mir
                rel = pq.mir
                if pq.finishing.limit is not None:
                    rel = _apply_finishing_as_topk(pq)
                factors.append(rel)
                scopes.append(
                    Scope([ScopeCol(alias, c.name, c.typ) for c in pq.scope.cols])
                )
                return
            factors.append(mir.MirGet(item.global_id, item.desc.arity))
            scopes.append(
                Scope(
                    [
                        ScopeCol(alias, c.name, PType(c.typ, c.scale if c.typ == ColType.NUMERIC else 0))
                        for c in item.desc.columns
                    ]
                )
            )
            return
        if isinstance(f, ast.TableFuncRef):
            if f.name == "generate_series":
                if len(f.args) not in (2, 3):
                    raise PlanError("generate_series takes 2 or 3 arguments")
                alias = f.alias or "generate_series"
                try:
                    vals = []
                    for a in f.args:
                        p, _t = self.plan_scalar(a, Scope([]))
                        if (
                            isinstance(p, CallUnary)
                            and p.func == "neg"
                            and isinstance(p.expr, Literal)
                        ):
                            p = Literal(-p.expr.value, p.expr.dtype)
                        if not isinstance(p, Literal):
                            raise PlanError("non-literal")
                        vals.append(int(p.value))
                except PlanError:
                    # CORRELATED series (args reference other FROM columns):
                    # becomes a FlatMap applied on top of the joined factors
                    # (reference MirRelationExpr::FlatMap, rendered at
                    # compute/src/render/flat_map.rs). Must trail the plain
                    # factors so its output column is the last one.
                    if getattr(self, "_no_flatmaps", False):
                        raise PlanError(
                            "correlated generate_series is only supported as "
                            "a top-level FROM item"
                        )
                    self._pending_fm.append(
                        (f.name, tuple(f.args), alias, len(scopes))
                    )
                    scopes.append(Scope([ScopeCol(alias, alias, INT)]))
                    return
                lo, hi = vals[0], vals[1]
                step = vals[2] if len(vals) == 3 else 1
                if step == 0:
                    raise PlanError("generate_series step must be nonzero")
                rows = tuple(((v,), 1) for v in range(lo, hi + (1 if step > 0 else -1), step))
                factors.append(
                    mir.MirConstant(rows=rows, dtypes=(np.dtype(np.int64),))
                )
                scopes.append(Scope([ScopeCol(alias, alias, INT)]))
                return
            raise PlanError(f"unsupported table function {f.name}")
        if isinstance(f, ast.SubqueryRef):
            pq = self.plan_query(f.query)
            rel = pq.mir
            if pq.finishing.limit is not None:
                rel = _apply_finishing_as_topk(pq)
            factors.append(rel)
            scopes.append(
                Scope([ScopeCol(f.alias, c.name, c.typ) for c in pq.scope.cols])
            )
            return
        if isinstance(f, ast.JoinClause):
            if f.kind == "cross":
                self._flatten_from(f.left, factors, scopes, on_preds)
                self._flatten_from(f.right, factors, scopes, on_preds)
                return
            if f.kind != "inner":
                rel, scope = self._plan_outer_join(f)
                factors.append(rel)
                scopes.append(scope)
                return
            self._flatten_from(f.left, factors, scopes, on_preds)
            self._flatten_from(f.right, factors, scopes, on_preds)
            if f.on is not None:
                on_preds.append(f.on)
            return
        raise PlanError(f"unsupported FROM clause {type(f).__name__}")

    def _plan_factor_rel(self, f):
        """Plan one table factor (incl. nested joins) to a (rel, scope).

        Correlated table functions are not supported inside nested factor
        trees (outer joins etc.) — `_no_flatmaps` makes them error cleanly.
        """
        prev_guard = getattr(self, "_no_flatmaps", False)
        self._no_flatmaps = True
        try:
            return self._plan_factor_rel_inner(f)
        finally:
            self._no_flatmaps = prev_guard

    def _plan_factor_rel_inner(self, f):
        factors: list = []
        scopes: list[Scope] = []
        on_preds: list = []
        self._flatten_from(f, factors, scopes, on_preds)
        scope = Scope([c for s in scopes for c in s.cols])
        if len(factors) == 1:
            rel = factors[0]
        else:
            offsets = []
            off = 0
            for s in scopes:
                offsets.append(off)
                off += len(s.cols)
            equivs, residual = self._split_equalities(on_preds, scope, scopes, offsets)
            rel = mir.MirJoin(
                inputs=tuple(factors),
                equivalences=tuple(tuple(sorted(c)) for c in equivs),
            )
            for c in residual:
                p, _t = self.plan_scalar(c, scope)
                rel = mir.MirFilter(rel, (p,))
            on_preds = []
        for c in on_preds:
            p, _t = self.plan_scalar(c, scope)
            rel = mir.MirFilter(rel, (p,))
        return rel, scope

    def _split_equalities(self, preds, full_scope, scopes, offsets):
        """Partition conjuncts into join equivalence classes and residuals."""
        conjuncts = []
        for p in preds:
            conjuncts.extend(_split_and(p))
        equivs: list[set] = []
        residual = []
        for c in conjuncts:
            pair = self._as_column_equality(c, full_scope, scopes, offsets)
            if pair is not None:
                merged = False
                for cls in equivs:
                    if pair[0] in cls or pair[1] in cls:
                        cls.update(pair)
                        merged = True
                        break
                if not merged:
                    equivs.append(set(pair))
            else:
                residual.append(c)
        return equivs, residual

    def _plan_outer_join(self, f: ast.JoinClause):
        """LEFT/RIGHT/FULL OUTER JOIN via the union/compensation lowering
        (reference: HIR→MIR outer-join lowering, plan/lowering.rs:1581):

            inner ∪ (unmatched preserved rows × NULL row for the other side)

        where unmatched = preserved − (preserved ⋉ distinct matched rows),
        the semijoin taken with null-safe (IS NOT DISTINCT FROM) equality so
        preserved rows containing NULLs still count as matched.
        """
        lrel, lscope = self._plan_factor_rel(f.left)
        rrel, rscope = self._plan_factor_rel(f.right)
        n_l, n_r = len(lscope.cols), len(rscope.cols)
        full_scope = Scope(list(lscope.cols) + list(rscope.cols))
        if f.on is None:
            raise PlanError("outer joins require an ON clause")
        equivs, residual = self._split_equalities(
            [f.on], full_scope, [lscope, rscope], [0, n_l]
        )
        inner = mir.MirJoin(
            inputs=(lrel, rrel),
            equivalences=tuple(tuple(sorted(c)) for c in equivs),
        )
        for c in residual:
            p, _t = self.plan_scalar(c, full_scope)
            inner = mir.MirFilter(inner, (p,))

        def nulls_for(scope_cols):
            return tuple(
                Literal(None, t.col.dtype.name)
                for t in (c.typ for c in scope_cols)
            )

        def compensation(side_rel, side_cols_range, other_scope_cols, reorder):
            matched = mir.MirDistinct(mir.MirProject(inner, tuple(side_cols_range)))
            n = len(side_cols_range)
            semi = mir.MirJoin(
                inputs=(side_rel, matched),
                equivalences=tuple((i, n + i) for i in range(n)),
                null_safe=True,
            )
            semi_kept = mir.MirProject(semi, tuple(range(n)))
            unmatched = mir.MirUnion((side_rel, mir.MirNegate(semi_kept)))
            padded = mir.MirMap(unmatched, nulls_for(other_scope_cols))
            if reorder is not None:
                padded = mir.MirProject(padded, reorder)
            return padded

        parts = [inner]
        if f.kind in ("left", "full"):
            parts.append(
                compensation(lrel, range(n_l), rscope.cols, None)
            )
        if f.kind in ("right", "full"):
            # Map appends NULL left-cols after the right row; reorder to
            # (left NULLs, right cols)
            reorder = tuple(range(n_r, n_r + n_l)) + tuple(range(n_r))
            parts.append(
                compensation(rrel, range(n_l, n_l + n_r), lscope.cols, reorder)
            )
        rel = mir.MirUnion(tuple(parts)) if len(parts) > 1 else parts[0]
        return rel, full_scope

    def _as_column_equality(self, c, full_scope, scopes, offsets):
        """col = col crossing two inputs → (global_col_a, global_col_b)."""
        if not (isinstance(c, ast.BinaryOp) and c.op == "="):
            return None
        l, r = c.left, c.right
        if not (isinstance(l, ast.Ident) and isinstance(r, ast.Ident)):
            return None
        try:
            li = full_scope.resolve(l.name, l.qualifier)
            ri = full_scope.resolve(r.name, r.qualifier)
        except PlanError:
            return None
        # find owning inputs
        def owner(i):
            for k in range(len(offsets) - 1, -1, -1):
                if i >= offsets[k]:
                    return k
            return 0

        if owner(li) == owner(ri):
            return None
        return (li, ri)

    def _extract_aggs(self, e, aggs: list):
        """Replace aggregate FuncCalls with _AggRef placeholders."""
        if e is None or isinstance(e, (ast.NumberLit, ast.StringLit, ast.BoolLit, ast.NullLit, ast.DateLit, ast.Ident, ast.Star)):
            return e
        if isinstance(e, ast.FuncCall) and e.name in _AGG_FUNCS and e.over is None:
            for i, a in enumerate(aggs):
                if a == e:
                    return _AggRef(i)
            aggs.append(e)
            return _AggRef(len(aggs) - 1)
        if isinstance(e, ast.UnaryOp):
            return replace(e, expr=self._extract_aggs(e.expr, aggs))
        if isinstance(e, ast.BinaryOp):
            return replace(
                e,
                left=self._extract_aggs(e.left, aggs),
                right=self._extract_aggs(e.right, aggs),
            )
        if isinstance(e, ast.FuncCall):
            # window calls: aggregates may appear in args AND in the OVER
            # spec's partition/order expressions of a grouped query
            return replace(
                e,
                args=tuple(self._extract_aggs(a, aggs) for a in e.args),
                over=_map_window_spec(e.over, lambda a: self._extract_aggs(a, aggs)),
            )
        if isinstance(e, ast.Cast):
            return replace(e, expr=self._extract_aggs(e.expr, aggs))
        if isinstance(e, ast.Case):
            return ast.Case(
                self._extract_aggs(e.operand, aggs) if e.operand else None,
                tuple(
                    (self._extract_aggs(c, aggs), self._extract_aggs(r, aggs))
                    for c, r in e.whens
                ),
                self._extract_aggs(e.else_, aggs) if e.else_ else None,
            )
        if isinstance(e, ast.Between):
            return replace(
                e,
                expr=self._extract_aggs(e.expr, aggs),
                low=self._extract_aggs(e.low, aggs),
                high=self._extract_aggs(e.high, aggs),
            )
        if isinstance(e, ast.InList):
            return replace(
                e,
                expr=self._extract_aggs(e.expr, aggs),
                items=tuple(self._extract_aggs(i, aggs) for i in e.items),
            )
        if isinstance(e, ast.IsNull):
            return replace(e, expr=self._extract_aggs(e.expr, aggs))
        return e

    def _extract_windows(self, e, wins: list):
        """Replace window FuncCalls (over != None) with _WinRef placeholders."""
        if e is None or isinstance(
            e,
            (
                ast.NumberLit, ast.StringLit, ast.BoolLit, ast.NullLit,
                ast.DateLit, ast.Ident, ast.Star,
                _PostCol, _PostAvg, _PostSum, _PostStat,
            ),
        ):
            return e
        if isinstance(e, ast.FuncCall) and e.over is not None:
            for i, w in enumerate(wins):
                if w == e:
                    return _WinRef(i)
            wins.append(e)
            return _WinRef(len(wins) - 1)
        if isinstance(e, ast.UnaryOp):
            return replace(e, expr=self._extract_windows(e.expr, wins))
        if isinstance(e, ast.BinaryOp):
            return replace(
                e,
                left=self._extract_windows(e.left, wins),
                right=self._extract_windows(e.right, wins),
            )
        if isinstance(e, ast.FuncCall):
            return replace(
                e, args=tuple(self._extract_windows(a, wins) for a in e.args)
            )
        if isinstance(e, ast.Cast):
            return replace(e, expr=self._extract_windows(e.expr, wins))
        if isinstance(e, ast.Case):
            return ast.Case(
                self._extract_windows(e.operand, wins) if e.operand else None,
                tuple(
                    (self._extract_windows(c, wins), self._extract_windows(r, wins))
                    for c, r in e.whens
                ),
                self._extract_windows(e.else_, wins) if e.else_ else None,
            )
        if isinstance(e, ast.Between):
            return replace(
                e,
                expr=self._extract_windows(e.expr, wins),
                low=self._extract_windows(e.low, wins),
                high=self._extract_windows(e.high, wins),
            )
        if isinstance(e, ast.InList):
            return replace(
                e,
                expr=self._extract_windows(e.expr, wins),
                items=tuple(self._extract_windows(i, wins) for i in e.items),
            )
        if isinstance(e, ast.IsNull):
            return replace(e, expr=self._extract_windows(e.expr, wins))
        return e

    def _plan_windows(self, rel, scope, wins: list):
        """Plan extracted window calls: per distinct OVER spec, map the
        partition/order/argument expressions onto the relation and add one
        MirWindow; finally project away the helper columns, keeping the
        original scope plus one output column per call.

        The reference plans window functions into whole-group-recompute
        reduces during HIR lowering (src/sql/src/plan/query.rs window
        planning, src/sql/src/plan/lowering.rs:1581); the net SQL surface
        here is the same, the physical plan is the batched Window operator.
        """
        n0 = len(scope.cols)
        groups: list[tuple] = []  # (WindowSpec, [win index, ...])
        for i, w in enumerate(wins):
            for spec, idxs in groups:
                if spec == w.over:
                    idxs.append(i)
                    break
            else:
                groups.append((w.over, [i]))

        cur = n0
        func_abs: list[int] = []  # absolute column position per emitted func
        func_types: list = []
        self._win_repl = {}
        pending: list[tuple] = []  # (win_i, kind, payload into func index space)

        for spec, idxs in groups:
            map_exprs: list = []
            if spec.partition_by:
                for p in spec.partition_by:
                    pe, _pt = self.plan_scalar(p, scope)
                    map_exprs.append(pe)
            else:
                map_exprs.append(Literal(1))
            npart = len(map_exprs)
            part_cols = tuple(range(cur, cur + npart))
            for o in spec.order_by:
                oe, ot = self.plan_scalar(o.expr, scope)
                if ot.col in (ColType.STRING, ColType.JSONB):
                    # the window kernel ranks on device by dictionary code
                    # (insertion order) — reject rather than mis-order
                    raise PlanError(
                        "window ORDER BY on a string column is not supported "
                        "(device ordering is by dictionary code)"
                    )
                map_exprs.append(oe)
            ord_cols = tuple(range(cur + npart, cur + npart + len(spec.order_by)))
            order_by = tuple(
                (c, o.desc) for c, o in zip(ord_cols, spec.order_by)
            )
            nulls_last = (
                tuple(
                    (not o.desc) if o.nulls_last is None else o.nulls_last
                    for o in spec.order_by
                )
                or None
            )

            funcs: list = []
            k0 = len(func_abs)
            for wi in idxs:
                call = wins[wi]
                name = call.name
                if call.distinct:
                    raise PlanError("DISTINCT is not supported in window functions")

                def arg_col(a):
                    v, vt = self.plan_scalar(a, scope)
                    map_exprs.append(v)
                    return cur + len(map_exprs) - 1, vt

                if name in ("row_number", "rank", "dense_rank"):
                    funcs.append(mir.MirWindowFunc(name))
                    pending.append((wi, "col", (k0 + len(funcs) - 1, INT)))
                elif name == "ntile":
                    nt = _literal_int(call.args[0], "ntile bucket count")
                    funcs.append(mir.MirWindowFunc("ntile", None, nt))
                    pending.append((wi, "col", (k0 + len(funcs) - 1, INT)))
                elif name == "count" and (call.is_star or not call.args):
                    funcs.append(mir.MirWindowFunc("count"))
                    pending.append((wi, "col", (k0 + len(funcs) - 1, INT)))
                elif name == "avg":
                    acol, vt = arg_col(call.args[0])
                    funcs.append(mir.MirWindowFunc("sum", acol))
                    s_k = k0 + len(funcs) - 1
                    funcs.append(mir.MirWindowFunc("count", acol))
                    c_k = k0 + len(funcs) - 1
                    pending.append((wi, "avg", (s_k, c_k, vt)))
                elif name in ("lag", "lead"):
                    if len(call.args) >= 3:
                        raise PlanError(f"{name} default argument not supported")
                    acol, vt = arg_col(call.args[0])
                    off = (
                        _literal_int(call.args[1], f"{name} offset")
                        if len(call.args) >= 2
                        else 1
                    )
                    funcs.append(mir.MirWindowFunc(name, acol, off))
                    pending.append((wi, "col", (k0 + len(funcs) - 1, vt)))
                elif name in ("first_value", "last_value", "sum", "min", "max", "count"):
                    acol, vt = arg_col(call.args[0])
                    if name in ("min", "max") and vt.col in (
                        ColType.STRING, ColType.JSONB
                    ):
                        raise PlanError(
                            f"window {name} over a string/jsonb column is not "
                            "supported (device ordering is by dictionary code)"
                        )
                    out_t = INT if name == "count" else vt
                    funcs.append(mir.MirWindowFunc(name, acol))
                    pending.append((wi, "col", (k0 + len(funcs) - 1, out_t)))
                else:
                    raise PlanError(f"window function {name} not supported")

            rel = mir.MirMap(rel, tuple(map_exprs))
            base = cur + len(map_exprs)
            rel = mir.MirWindow(
                rel, part_cols, order_by, tuple(funcs), nulls_last
            )
            for fi in range(len(funcs)):
                func_abs.append(base + fi)
            cur = base + len(funcs)

        # project: original columns ++ every window output, in emission order
        rel = mir.MirProject(rel, tuple(range(n0)) + tuple(func_abs))

        # record types + placeholder replacements in projected positions
        func_types = [None] * len(func_abs)
        for wi, kind, payload in pending:
            if kind == "col":
                k, t = payload
                func_types[k] = t
                self._win_repl[wi] = _PostCol(n0 + k)
            else:
                s_k, c_k, vt = payload
                func_types[s_k] = vt
                func_types[c_k] = INT
                self._win_repl[wi] = _PostAvg(n0 + s_k, n0 + c_k, vt)

        out_cols = list(scope.cols) + [
            ScopeCol(None, None, t) for t in func_types
        ]
        return rel, Scope(out_cols)

    def _rewrite_wins(self, e):
        """Replace _WinRef placeholders with their post-window column refs."""
        if e is None:
            return None
        if isinstance(e, _WinRef):
            return self._win_repl[e.index]
        if isinstance(e, ast.UnaryOp):
            return replace(e, expr=self._rewrite_wins(e.expr))
        if isinstance(e, ast.BinaryOp):
            return replace(
                e, left=self._rewrite_wins(e.left), right=self._rewrite_wins(e.right)
            )
        if isinstance(e, ast.FuncCall):
            return replace(e, args=tuple(self._rewrite_wins(a) for a in e.args))
        if isinstance(e, ast.Cast):
            return replace(e, expr=self._rewrite_wins(e.expr))
        if isinstance(e, ast.Case):
            return ast.Case(
                self._rewrite_wins(e.operand) if e.operand else None,
                tuple(
                    (self._rewrite_wins(c), self._rewrite_wins(r))
                    for c, r in e.whens
                ),
                self._rewrite_wins(e.else_) if e.else_ else None,
            )
        if isinstance(e, ast.Between):
            return replace(
                e,
                expr=self._rewrite_wins(e.expr),
                low=self._rewrite_wins(e.low),
                high=self._rewrite_wins(e.high),
            )
        if isinstance(e, ast.InList):
            return replace(
                e,
                expr=self._rewrite_wins(e.expr),
                items=tuple(self._rewrite_wins(i) for i in e.items),
            )
        if isinstance(e, ast.IsNull):
            return replace(e, expr=self._rewrite_wins(e.expr))
        return e

    def _plan_reduce(self, rel, scope, sel, items, aggs, having):
        """GROUP BY planning: Map(keys+agg args) → Reduce → post scope."""
        # resolve group-by items (ordinals refer to select items pre-extraction)
        group_asts = []
        for g in sel.group_by:
            if isinstance(g, ast.NumberLit) and "." not in g.value:
                n = int(g.value)
                if not (1 <= n <= len(sel.items)):
                    raise PlanError(f"GROUP BY position {n} out of range")
                group_asts.append(sel.items[n - 1].expr)
            else:
                group_asts.append(g)
        key_planned = [self.plan_scalar(g, scope) for g in group_asts]

        # plan aggregate argument expressions + build MirAggregates.
        # DISTINCT aggregates get their own reduce branch over
        # DISTINCT(keys, arg) — the reference plans them the same way
        # (a distinct collection feeding the aggregation); branches join
        # back on the group key below.
        mir_aggs = []
        agg_types = []
        agg_branch: list = []  # parallel to mir_aggs: 0 = main, >0 = distinct
        distinct_branches: list = []  # (branch_id, arg ast)
        post_agg_exprs: list = []  # how each _AggRef is reconstructed post-reduce

        nk = len(group_asts)

        def branch_for(a, v):
            """(branch id, aggregate input expr). min/max/bool_and/bool_or
            over DISTINCT inputs equal their plain forms, so they stay in the
            main branch; other DISTINCT aggs get a dedicated branch whose
            reduce reads the distinct relation's arg column."""
            if not a.distinct or a.name in ("min", "max", "bool_and", "bool_or"):
                return 0, v
            if a.name in ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop"):
                raise PlanError(f"DISTINCT {a.name} not supported")
            bid = len(distinct_branches) + 1
            distinct_branches.append((bid, v))
            return bid, Column(nk)

        def emit(bid: int, agg) -> int:
            mir_aggs.append(agg)
            agg_branch.append(bid)
            return len(mir_aggs) - 1

        for a in aggs:
            fname = a.name
            if fname == "count":
                # count(*) counts rows; count(x) counts non-null x
                if a.args and not isinstance(a.args[0], ast.Star):
                    arg, _at = self.plan_scalar(a.args[0], scope)
                    bid, arg = branch_for(a, arg)
                else:
                    arg, bid = Literal(1), 0
                i = emit(bid, mir.MirAggregate("count", arg))
                post_agg_exprs.append(("col", i, INT))
                agg_types.append(INT)
            elif fname == "avg":
                v, vt = self.plan_scalar(a.args[0], scope)
                bid, v = branch_for(a, v)
                sum_i = emit(bid, mir.MirAggregate("sum", v))
                # avg divides by the NON-NULL input count
                cnt_i = emit(bid, mir.MirAggregate("count", v))
                post_agg_exprs.append(("avg", (sum_i, cnt_i, vt), _avg_type(vt)))
                agg_types.extend([vt, INT])
            elif fname in ("stddev", "stddev_samp", "stddev_pop", "variance", "var_samp", "var_pop"):
                if a.distinct:
                    raise PlanError(f"DISTINCT {fname} not supported")
                v, vt = self.plan_scalar(a.args[0], scope)
                sum_i = emit(0, mir.MirAggregate("sum", v))
                sq_i = emit(0, mir.MirAggregate("sum", CallBinary("mul", v, v)))
                cnt_i = emit(0, mir.MirAggregate("count", Literal(1)))
                sq_t = PType(ColType.NUMERIC, vt.scale * 2) if vt.col == ColType.NUMERIC else vt
                post_agg_exprs.append((fname, (sum_i, sq_i, cnt_i, vt), FLOAT))
                agg_types.extend([vt, sq_t, INT])
            elif fname == "sum":
                v, vt = self.plan_scalar(a.args[0], scope)
                bid, v = branch_for(a, v)
                sum_i = emit(bid, mir.MirAggregate("sum", v))
                # paired non-null count: sum over only-NULL inputs is NULL
                cnt_i = emit(bid, mir.MirAggregate("count", v))
                post_agg_exprs.append(("sumn", (sum_i, cnt_i, vt), vt))
                agg_types.extend([vt, INT])
            elif fname in _BASIC_AGGS:
                # Basic reduces (reference ReducePlan::Basic): the group's
                # input multiset renders to one value at emission. Output is
                # always STRING (string_agg text; array/list aggs render
                # their pg text form — the engine has no array ADT).
                if a.distinct:
                    raise PlanError(f"DISTINCT {fname} not supported")
                if fname != "string_agg" and len(a.args) != 1:
                    raise PlanError(f"{fname} takes exactly one argument")
                if not a.args:
                    raise PlanError(f"{fname} needs an argument")
                v, vt = self.plan_scalar(a.args[0], scope)
                delim = None
                if fname == "string_agg":
                    if len(a.args) != 2:
                        raise PlanError("string_agg takes (value, delimiter)")
                    if vt.col != ColType.STRING:
                        raise PlanError("string_agg requires a string value")
                    d, dt_ = self.plan_scalar(a.args[1], scope)
                    if not (isinstance(d, Literal) and dt_.col == ColType.STRING):
                        raise PlanError("string_agg delimiter must be a string literal")
                    delim = self.catalog.dict.decode(d.value)
                extra = (delim, _argtype(vt), self.catalog.dict)
                out_t = JSONB if fname == "jsonb_agg" else STRING
                i = emit(0, mir.MirAggregate(fname, v, extra=extra))
                post_agg_exprs.append(("col", i, out_t))
                agg_types.append(out_t)
            elif fname in ("bool_and", "bool_or"):
                # all/any over non-NULL inputs = min/max over the stored
                # int8 truth values (func.rs All/Any accumulation)
                v, _vt = self.plan_scalar(a.args[0], scope)
                i = emit(0, mir.MirAggregate("min" if fname == "bool_and" else "max", v))
                post_agg_exprs.append(("col", i, BOOL))
                agg_types.append(BOOL)
            else:
                v, vt = self.plan_scalar(a.args[0], scope)
                out_t = vt if fname != "count" else INT
                if fname in ("min", "max") and vt.col == ColType.JSONB:
                    raise PlanError(
                        f"{fname} over jsonb is not supported (jsonb has no "
                        "device ordering)"
                    )
                if fname in ("min", "max") and vt.col == ColType.STRING:
                    # device top-1 would rank by dictionary code; route
                    # through the Basic class, which compares decoded strings
                    extra = (None, "str", self.catalog.dict)
                    i = emit(0, mir.MirAggregate(f"{fname}_str", v, extra=extra))
                else:
                    i = emit(0, mir.MirAggregate(fname, v))
                post_agg_exprs.append(("col", i, out_t))
                agg_types.append(out_t)

        # keys become mapped columns so the Reduce's group_key is plain columns
        arity_in = len(scope.cols)
        key_exprs = tuple(p for p, _ in key_planned)
        # aggregate inputs holding string functions (DictFunc) are lifted into
        # mapped columns too: the reduce kernels run under jit, where string
        # tables cannot be evaluated — the eager Mfp stage computes them first
        from ..expr.scalar import expr_has_dictfunc

        lifted: list = []
        for i, ag in enumerate(mir_aggs):
            if expr_has_dictfunc(ag.expr):
                if agg_branch[i] != 0:
                    raise PlanError(
                        "DISTINCT aggregates over string functions not supported"
                    )
                mir_aggs[i] = mir.MirAggregate(
                    ag.func,
                    Column(arity_in + len(key_exprs) + len(lifted)),
                    ag.distinct,
                    ag.extra,
                )
                lifted.append(ag.expr)
        if not distinct_branches:
            inner = mir.MirMap(rel, key_exprs + tuple(lifted))
            rel = mir.MirReduce(
                inner,
                group_key=tuple(range(arity_in, arity_in + len(key_exprs))),
                aggregates=tuple(mir_aggs),
            )
        else:
            if lifted:
                raise PlanError(
                    "string-function aggregates cannot mix with DISTINCT aggregates"
                )
            rel = self._reduce_with_distinct_branches(
                rel, arity_in, key_exprs, mir_aggs, agg_branch, distinct_branches
            )

        # post-reduce scope: keys then aggregate outputs
        post_cols = []
        for gast, (_, t) in zip(group_asts, key_planned):
            name = gast.name if isinstance(gast, ast.Ident) else _default_name(gast)
            qual = gast.qualifier if isinstance(gast, ast.Ident) else None
            post_cols.append(ScopeCol(qual, name, t))
        nkeys = len(post_cols)
        for ag, t in zip(mir_aggs, agg_types):
            post_cols.append(ScopeCol(None, None, t))
        post_scope = Scope(post_cols)

        # rewrite items/having: _AggRef(i) → column ref; group asts → key cols
        self._group_asts = group_asts
        self._post_nkeys = nkeys
        self._post_agg_exprs = post_agg_exprs

        items = [
            ast.SelectItem(self._rewrite_post(it.expr), it.alias) for it in items
        ]
        having = self._rewrite_post(having) if having is not None else None
        return rel, post_scope, items, having

    def _reduce_with_distinct_branches(
        self, rel, arity_in, key_exprs, mir_aggs, agg_branch, distinct_branches
    ):
        """DISTINCT aggregates: one reduce per distinct argument over
        DISTINCT(keys, arg), joined back with the main reduce on the group
        key (NULL-safe: NULL group keys are one group). Output layout is the
        canonical (keys ++ aggregates in declaration order) so the post-agg
        rewrite indices stay valid. Mirrors the reference's distinct-agg
        planning (a distinct collection feeding each such aggregate)."""
        nk = len(key_exprs)
        order: list[int] = []
        per_branch: dict[int, list[int]] = {}
        for i, b in enumerate(agg_branch):
            per_branch.setdefault(b, []).append(i)
        branches = []
        if per_branch.get(0):
            inner = mir.MirMap(rel, key_exprs)
            branches.append(
                mir.MirReduce(
                    inner,
                    group_key=tuple(range(arity_in, arity_in + nk)),
                    aggregates=tuple(mir_aggs[i] for i in per_branch[0]),
                )
            )
            order.append(0)
        for bid, v in distinct_branches:
            inner = mir.MirMap(rel, key_exprs + (v,))
            proj = mir.MirProject(
                inner, tuple(range(arity_in, arity_in + nk + 1))
            )
            branches.append(
                mir.MirReduce(
                    mir.MirDistinct(proj),
                    group_key=tuple(range(nk)),
                    aggregates=tuple(mir_aggs[i] for i in per_branch[bid]),
                )
            )
            order.append(bid)
        if len(branches) == 1:
            return branches[0]
        arities = [nk + len(per_branch[b]) for b in order]
        offsets = [sum(arities[:i]) for i in range(len(arities))]
        equivs = tuple(
            tuple(offsets[j] + k for j in range(len(order)))
            for k in range(nk)
        )
        join = mir.MirJoin(
            inputs=tuple(branches), equivalences=equivs, null_safe=True
        )
        pos: dict[int, int] = {}
        for j, b in enumerate(order):
            for local, i in enumerate(per_branch[b]):
                pos[i] = offsets[j] + nk + local
        out = tuple(range(nk)) + tuple(pos[i] for i in range(len(mir_aggs)))
        return mir.MirProject(join, out)

    def _rewrite_post(self, e):
        """Rewrite a post-aggregation AST: group exprs → _PostCol, aggs → _PostCol/avg."""
        if e is None:
            return None
        for k, g in enumerate(self._group_asts):
            if e == g:
                return _PostCol(k)
        if isinstance(e, _AggRef):
            kind, payload, t = self._post_agg_exprs[e.index]
            if kind == "col":
                return _PostCol(self._post_nkeys + payload)
            if kind == "avg":
                sum_i, cnt_i, vt = payload
                return _PostAvg(self._post_nkeys + sum_i, self._post_nkeys + cnt_i, vt)
            if kind == "sumn":
                sum_i, cnt_i, vt = payload
                return _PostSum(self._post_nkeys + sum_i, self._post_nkeys + cnt_i, vt)
            sum_i, sq_i, cnt_i, vt = payload
            return _PostStat(
                self._post_nkeys + sum_i,
                self._post_nkeys + sq_i,
                self._post_nkeys + cnt_i,
                vt,
                pop=kind in ("stddev_pop", "var_pop"),
                sqrt=kind.startswith("stddev"),
            )
        if isinstance(e, ast.UnaryOp):
            return replace(e, expr=self._rewrite_post(e.expr))
        if isinstance(e, ast.BinaryOp):
            return replace(e, left=self._rewrite_post(e.left), right=self._rewrite_post(e.right))
        if isinstance(e, ast.FuncCall):
            return replace(
                e,
                args=tuple(self._rewrite_post(a) for a in e.args),
                over=_map_window_spec(e.over, self._rewrite_post),
            )
        if isinstance(e, ast.Cast):
            return replace(e, expr=self._rewrite_post(e.expr))
        if isinstance(e, ast.Ident):
            raise PlanError(
                f"column {e.name} must appear in GROUP BY or be used in an aggregate"
            )
        return e


@dataclass(frozen=True)
class _PostCol:
    index: int


@dataclass(frozen=True)
class _PostAvg:
    sum_col: int
    cnt_col: int
    vt: PType


@dataclass(frozen=True)
class _PostSum:
    sum_col: int
    cnt_col: int
    vt: PType


@dataclass(frozen=True)
class _PostStat:
    sum_col: int
    sq_col: int
    cnt_col: int
    vt: PType
    pop: bool
    sqrt: bool


def _to_float(e, t: PType):
    """Cast to float, descaling NUMERIC fixed-point by its scale factor."""
    f = CallUnary("cast_float", e)
    if t.col == ColType.NUMERIC and t.scale:
        f = CallBinary("div", f, Literal(float(10**t.scale), "float32"))
    return f


def _descaled(e, t: PType):
    """A scaled NUMERIC operand as its float value, where it meets a FLOAT in
    `*` or `/`; anything else as it is (the kernels promote int to float)."""
    return _to_float(e, t) if t.col == ColType.NUMERIC and t.scale else e


class _SubqueryLifter:
    """Rewrite uncorrelated subqueries into extra join factors.

    IN (SELECT …)   → join factor Distinct(sub), predicate expr = hidden col
    EXISTS (…)      → cross-join factor Distinct(Map(sub → [1])), predicate TRUE
    scalar (SELECT) → cross-join factor sub (must be single-row), hidden col
    """

    def __init__(self, planner, factors, scopes):
        self.planner = planner
        self.factors = factors
        self.scopes = scopes
        self.n = 0
        # (key_ast | None, PlannedQuery, is_exists) — applied as antijoins
        # after the join is built (NOT IN / NOT EXISTS)
        self.antijoins: list = []
        # equality conjuncts added by decorrelation (joined on in the WHERE)
        self.extra_conjuncts: list = []

    def _add_factor(self, rel, typ: PType) -> ast.Ident:
        name = f"__sub{self.n}"
        self.n += 1
        self.factors.append(rel)
        self.scopes.append(Scope([ScopeCol("__sub", name, typ)]))
        return ast.Ident(name, qualifier="__sub")

    def _add_multi_factor(self, rel, cols: list) -> str:
        """Add a factor with several named columns; returns its qualifier."""
        qual = f"__subq{self.n}"
        self.n += 1
        self.factors.append(rel)
        self.scopes.append(Scope([ScopeCol(qual, n, t) for n, t in cols]))
        return qual

    def _decorrelate_scalar(self, q: ast.Query):
        """Decorrelate `(SELECT agg-expr FROM … WHERE inner = outer AND …)`.

        The classic equality pattern (reference: HIR→MIR decorrelation,
        src/sql/src/plan/lowering.rs): rewrite to a grouped subquery over the
        correlation keys and join it on them. Missing groups drop the outer
        row (consistent with WHERE-context NULL comparisons; this engine has
        no NULLs).
        """
        if q.ctes or q.order_by or q.limit is not None:
            raise PlanError("unsupported correlated subquery shape")
        sel = q.body
        if not isinstance(sel, ast.Select) or sel.group_by or sel.having or len(sel.items) != 1:
            raise PlanError("unsupported correlated subquery shape")
        # the subquery's own FROM: its aliases, and its column names, because an
        # unqualified name binds there first (SQL scoping), so Q17's published
        # text (`l_partkey = p_partkey`, no aliases) finds its correlation the
        # same way the aliased form does
        inner_scopes: list[Scope] = []
        outer_fm, self.planner._pending_fm = getattr(self.planner, "_pending_fm", None), []
        for f in sel.from_:
            self.planner._flatten_from(f, [], inner_scopes, [])
        self.planner._pending_fm = outer_fm
        inner_names = {c.qualifier for s in inner_scopes for c in s.cols}
        inner_cols = {c.name for s in inner_scopes for c in s.cols}

        def is_inner(i: ast.Ident) -> bool:
            if i.qualifier is not None:
                return i.qualifier in inner_names
            return i.name in inner_cols

        corr: list[tuple[ast.Ident, ast.Ident]] = []  # (inner, outer)
        residual: list = []
        for c in _split_and(sel.where) if sel.where is not None else []:
            if (
                isinstance(c, ast.BinaryOp) and c.op == "="
                and isinstance(c.left, ast.Ident) and isinstance(c.right, ast.Ident)
                and is_inner(c.left) != is_inner(c.right)
            ):
                inner, outer = (c.left, c.right) if is_inner(c.left) else (c.right, c.left)
                corr.append((inner, outer))
                continue
            residual.append(c)
        if not corr:
            raise PlanError("correlated subquery: no equality correlation found")
        res_where = None
        for c in residual:
            res_where = c if res_where is None else ast.BinaryOp("and", res_where, c)
        items = tuple(
            ast.SelectItem(inner, alias=f"__ck{i}") for i, (inner, _o) in enumerate(corr)
        ) + (ast.SelectItem(sel.items[0].expr, alias="__agg"),)
        dq = ast.Query(
            ast.Select(
                items=items,
                from_=sel.from_,
                where=res_where,
                group_by=tuple(inner for inner, _o in corr),
            )
        )
        pq = self.planner.plan_query(dq)
        qual = self._add_multi_factor(
            pq.mir, [(c.name, c.typ) for c in pq.scope.cols]
        )
        names = [c.name for c in pq.scope.cols]
        for i, (_inner, outer) in enumerate(corr):
            self.extra_conjuncts.append(
                ast.BinaryOp("=", outer, ast.Ident(names[i], qualifier=qual))
            )
        return ast.Ident(names[-1], qualifier=qual)

    def rewrite_conjunct(self, e):
        """Rewrite a top-level WHERE/ON conjunct; antijoins allowed here."""
        return self.rewrite(e, _allow_anti=True)

    def rewrite(self, e, _allow_anti: bool = False):
        if e is None or isinstance(
            e,
            (ast.NumberLit, ast.StringLit, ast.BoolLit, ast.NullLit, ast.DateLit,
             ast.Ident, ast.Star),
        ):
            return e
        if isinstance(e, ast.Subquery):
            try:
                pq = self.planner.plan_query(e.query)
            except PlanError as err:
                if not e.exists and "unknown column" in str(err):
                    # correlated scalar subquery: try equality decorrelation
                    return self._decorrelate_scalar(e.query)
                raise
            if e.exists:
                one = mir.MirProject(
                    mir.MirMap(pq.mir, (Literal(1),)),
                    (len(pq.scope.cols),),
                )
                ident = self._add_factor(mir.MirDistinct(one), INT)
                return ast.BoolLit(True)  # presence enforced by the join itself
            if len(pq.scope.cols) != 1:
                raise PlanError("scalar subquery must return one column")
            return self._add_factor(pq.mir, pq.scope.cols[0].typ)
        if isinstance(e, ast.InList):
            subs = [i for i in e.items if isinstance(i, ast.Subquery)]
            if subs:
                if len(e.items) != 1:
                    raise PlanError("IN mixing subquery and literals unsupported")
                pq = self.planner.plan_query(subs[0].query)
                if len(pq.scope.cols) != 1:
                    raise PlanError("IN subquery must return one column")
                if e.negated:
                    if not _allow_anti:
                        raise PlanError(
                            "NOT IN (SELECT …) only supported as a top-level "
                            "WHERE/ON conjunct"
                        )
                    # antijoin: handled at relation level after the join builds
                    self.antijoins.append((self.rewrite(e.expr), pq, False))
                    return ast.BoolLit(True)
                ident = self._add_factor(
                    mir.MirDistinct(pq.mir), pq.scope.cols[0].typ
                )
                return ast.BinaryOp("=", self.rewrite(e.expr), ident)
            return replace(e, expr=self.rewrite(e.expr),
                           items=tuple(self.rewrite(i) for i in e.items))
        if isinstance(e, ast.UnaryOp):
            if (
                e.op == "not"
                and isinstance(e.expr, ast.Subquery)
                and e.expr.exists
            ):
                if not _allow_anti:
                    raise PlanError(
                        "NOT EXISTS only supported as a top-level WHERE/ON conjunct"
                    )
                pq = self.planner.plan_query(e.expr.query)
                self.antijoins.append((None, pq, True))
                return ast.BoolLit(True)
            return replace(e, expr=self.rewrite(e.expr))
        if isinstance(e, ast.BinaryOp):
            return replace(e, left=self.rewrite(e.left), right=self.rewrite(e.right))
        if isinstance(e, ast.FuncCall):
            return replace(e, args=tuple(self.rewrite(a) for a in e.args))
        if isinstance(e, ast.Cast):
            return replace(e, expr=self.rewrite(e.expr))
        if isinstance(e, ast.Between):
            return replace(
                e, expr=self.rewrite(e.expr), low=self.rewrite(e.low),
                high=self.rewrite(e.high),
            )
        if isinstance(e, ast.IsNull):
            return replace(e, expr=self.rewrite(e.expr))
        if isinstance(e, ast.Case):
            return ast.Case(
                self.rewrite(e.operand) if e.operand else None,
                tuple((self.rewrite(c), self.rewrite(r)) for c, r in e.whens),
                self.rewrite(e.else_) if e.else_ else None,
            )
        return e


def _join_and(parts):
    out = None
    for p_ in parts:
        out = p_ if out is None else ast.BinaryOp("and", out, p_)
    return out


def _split_and(e):
    if isinstance(e, ast.BinaryOp) and e.op == "and":
        return _split_and(e.left) + _split_and(e.right)
    return [e]


def _is_mz_now(e) -> bool:
    return isinstance(e, ast.FuncCall) and e.name == "mz_now"


def _contains_mz_now(e) -> bool:
    if _is_mz_now(e):
        return True
    if isinstance(e, ast.BinaryOp):
        return _contains_mz_now(e.left) or _contains_mz_now(e.right)
    if isinstance(e, ast.UnaryOp):
        return _contains_mz_now(e.expr)
    if isinstance(e, ast.FuncCall):
        return any(_contains_mz_now(a) for a in e.args)
    if isinstance(e, ast.Cast):
        return _contains_mz_now(e.expr)
    if isinstance(e, (ast.Between,)):
        return _contains_mz_now(e.expr) or _contains_mz_now(e.low) or _contains_mz_now(e.high)
    return False


def _default_name(e) -> str:
    if isinstance(e, ast.Ident):
        return e.name
    if isinstance(e, ast.FuncCall):
        return e.name
    if isinstance(e, _AggRef):
        return "agg"
    return "column"


def _apply_finishing_as_topk(pq: PlannedQuery):
    """LIMIT inside a view body becomes a TopK (global group).

    Rejected for STRING order columns when rows are actually dropped
    (LIMIT/OFFSET): a maintained TopK ranks rows on device by dictionary
    code (insertion order, not collation), which would silently mis-order.
    Without LIMIT/OFFSET the TopK keeps every row, so ordering is
    semantically inert (relations are unordered) and stays allowed. One-shot
    peeks are unaffected — their finishing sorts decoded strings host-side
    (coordinator._finish)."""
    if pq.finishing.limit is not None or pq.finishing.offset:
        for col, _desc in pq.finishing.order_by:
            if pq.scope.cols[col].typ.col in (ColType.STRING, ColType.JSONB):
                raise PlanError(
                    "ORDER BY on a string column with LIMIT is not supported "
                    "in maintained views (device ordering is by dictionary "
                    "code)"
                )
    return mir.MirTopK(
        pq.mir,
        group_key=(),
        order_by=tuple(pq.finishing.order_by),
        limit=pq.finishing.limit,
        offset=pq.finishing.offset,
        nulls_last=tuple(pq.finishing.nulls_last) or None,
    )
