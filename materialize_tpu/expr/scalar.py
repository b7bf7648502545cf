"""Scalar expressions evaluated columnwise on device, with SQL NULLs.

The TPU analogue of the reference's `MirScalarExpr`
(src/expr/src/scalar.rs:69) and its Unary/Binary/Variadic function enums
(src/expr/src/scalar/func/macros.rs): an expression tree compiles to a pure
JAX computation over column arrays, vectorized across the batch. Runtime
errors (division by zero, …) do not trap: they produce a per-row error code
that the MFP routes into the dataflow's error stream, mirroring the
reference's oks/errs twin collections (src/compute/src/render.rs:30-101).

**NULL representation** (the `Datum::Null` analogue, src/repr/src/row.rs:1071,
re-designed columnar): NULL is IN-BAND — a per-dtype sentinel value stored in
the column itself (INT64_MIN for 64-bit ints, INT32_MIN / -128 for narrower,
NaN for floats). Evaluation derives a boolean null mask from the stored
values at each Column reference, threads three-valued logic through the tree
as (value, null, err) triples, and re-materializes the sentinel at operator
output boundaries. Because the sentinel IS the stored value, hashing,
sorting, consolidation, grouping and DISTINCT treat NULL as an ordinary
value (SQL's NULLs-group-together semantics) with zero kernel changes; only
equality JOINs need planner-inserted IS NOT NULL guards (SQL's
NULL-never-matches semantics). Trade-off: the sentinel value itself cannot
be stored (INT64_MIN as data reads back as NULL) — documented, like the
engine's other fixed-width compromises.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp
import numpy as np

NULL_I64 = np.int64(np.iinfo(np.int64).min)
NULL_I32 = np.int32(np.iinfo(np.int32).min)
NULL_I8 = np.int8(-128)


def null_sentinel(dtype) -> Any:
    """The in-band NULL value for a storage dtype."""
    dt = np.dtype(dtype)
    if dt == np.int64 or dt == np.uint64:
        return NULL_I64
    if dt == np.int32:
        return NULL_I32
    if dt == np.int8 or dt == np.bool_:
        return NULL_I8
    if np.issubdtype(dt, np.floating):
        return dt.type(np.nan)
    raise TypeError(f"no null sentinel for {dt}")


def derived_null(col: jnp.ndarray) -> jnp.ndarray:
    """Null mask derived from a stored column's sentinel values."""
    if jnp.issubdtype(col.dtype, jnp.floating):
        return jnp.isnan(col)
    if col.dtype == jnp.bool_:
        return jnp.zeros(col.shape, dtype=jnp.bool_)
    return col == jnp.asarray(null_sentinel(col.dtype), col.dtype)


def is_null_value(v, coltype=None) -> bool:
    """Host-side: is a decoded storage scalar the NULL sentinel?

    `coltype` (a repr.types.ColType) picks the right sentinel width — -128 is
    NULL only for BOOL columns, INT32_MIN only for INT32, etc. Without it,
    only the unambiguous sentinels (None, NaN, INT64_MIN) are recognized.
    """
    if v is None:
        return True
    if isinstance(v, float) and v != v:  # NaN
        return True
    if isinstance(v, (int, np.integer)):
        iv = int(v)
        if coltype is None:
            return iv == int(NULL_I64)
        name = getattr(coltype, "name", str(coltype))
        if name == "BOOL":
            return iv == int(NULL_I8)
        if name == "INT32":
            return iv == int(NULL_I32)
        return iv == int(NULL_I64)
    return False


def force_sentinel(col: jnp.ndarray, null: jnp.ndarray) -> jnp.ndarray:
    """Write the dtype sentinel wherever `null` — the output-boundary
    materialization that keeps NULL canonical in storage."""
    if col.dtype == jnp.bool_:
        # bool arrays cannot carry a sentinel; nullable booleans are stored
        # as int8 by the planner (ColType.BOOL), so a bool array here means
        # an eval-internal predicate that is about to be consumed, not stored
        return col
    return jnp.where(null, jnp.asarray(null_sentinel(col.dtype), col.dtype), col)


class EvalErr(enum.IntEnum):
    """Per-row evaluation error codes (0 = no error)."""

    NONE = 0
    DIVISION_BY_ZERO = 1
    NUMERIC_OVERFLOW = 2
    # reduce lookup scanned _MAX_HASH_COLLISIONS slots of one hash bucket
    # without resolving the probe: the answer would be unsound, so the tick
    # reports an error instead of silently dropping the group (needs >4
    # distinct live keys sharing one 32-bit hash — rare but plausible at
    # tens of millions of keys; detected, never silent); also a reduce
    # step's table merge that met two keys under one packed (hash, mix)
    # pair (a 2^-64 double collision, ops/reduce.py::_accum_pack)
    HASH_COLLISION_EXHAUSTED = 3
    # a string column held a code outside the dictionary (corrupt data);
    # string-function tables cannot resolve it
    STRING_CODE_OOB = 4
    NEGATIVE_FUNC_ARG = 5
    STEP_ZERO = 6  # generate_series step size cannot equal zero


@dataclass(frozen=True)
class Column:
    """Reference to input column `index` (after maps: index into input+maps)."""

    index: int


@dataclass(frozen=True)
class Literal:
    value: Any
    dtype: str = "int64"  # numpy dtype name


@dataclass(frozen=True)
class CallUnary:
    func: str  # neg | not | abs | is_true | cast_int64 | cast_float
    expr: Any


@dataclass(frozen=True)
class CallBinary:
    func: str  # add sub mul mul_exact div floordiv mod eq ne lt lte gt gte and or min max
    left: Any
    right: Any


@dataclass(frozen=True)
class CallVariadic:
    func: str  # and | or | greatest | least
    exprs: tuple


@dataclass(frozen=True, eq=False)
class DictFunc:
    """A string function over dictionary codes (expr/strings.py).

    `spec` = (name, *literal_args); `args` are ScalarExprs; `argtypes` tags
    how each arg decodes for multi-arg host evaluation ("str" args are codes).
    `out` is the result kind: "string" (i64 code), "int64", or "bool" (i8).
    `tables` is the engine's StringFuncTables registry — a mutable reference
    shared with the catalog's dictionary, deliberately outside eq/hash.

    Single-string-arg specs evaluate on device as one table gather; multi-arg
    specs decode host-side (eager host path only). The fused renderer rejects
    plans containing DictFunc (tables would bake stale into the compiled
    program) and falls back to the host-orchestrated path.
    """

    spec: tuple
    args: tuple
    argtypes: tuple
    out: str
    tables: Any


ScalarExpr = Any  # Column | Literal | CallUnary | CallBinary | CallVariadic | DictFunc


def eval_expr(expr: ScalarExpr, cols: list[jnp.ndarray], n: int):
    """Evaluate to (value[n], err_code[n] int32) — the storage-facing surface.

    NULL rows come back with the dtype sentinel already materialized (and no
    error), so callers that write columns need no extra handling; callers
    that need the mask itself use `eval_expr3`.
    """
    v, null, err = eval_expr3(expr, cols, n)
    return force_sentinel(v, null), err


def _truth(v: jnp.ndarray) -> jnp.ndarray:
    """Boolean view of a stored truth value (int8 {0,1} or bool)."""
    return v.astype(jnp.bool_) if v.dtype != jnp.bool_ else v


def _as_bool_i8(b: jnp.ndarray) -> jnp.ndarray:
    return b.astype(jnp.int8)


def eval_expr3(expr: ScalarExpr, cols: list[jnp.ndarray], n: int):
    """Three-valued evaluation: (value[n], null[n] bool, err[n] int32).

    Values under a set null bit are unspecified until `force_sentinel`;
    errors never fire on NULL rows (SQL: NULL/0 is NULL, not an error).
    Boolean results are int8 {0,1} — ColType.BOOL's storage dtype.
    """
    zero_err = jnp.zeros((n,), dtype=jnp.int32)
    no_null = jnp.zeros((n,), dtype=jnp.bool_)
    if isinstance(expr, Column):
        v = cols[expr.index]
        return v, derived_null(v), zero_err
    if isinstance(expr, Literal):
        dt = np.dtype(expr.dtype)
        if expr.value is None:
            return (
                jnp.full((n,), null_sentinel(dt), dtype=dt),
                jnp.ones((n,), dtype=jnp.bool_),
                zero_err,
            )
        if dt == np.bool_:  # legacy spelling: booleans store as int8
            return jnp.full((n,), int(bool(expr.value)), dtype=np.int8), no_null, zero_err
        return jnp.full((n,), expr.value, dtype=dt), no_null, zero_err
    if isinstance(expr, CallUnary):
        f = expr.func
        v, null, e = eval_expr3(expr.expr, cols, n)
        if f == "is_null":
            return _as_bool_i8(null), no_null, zero_err
        if f == "is_not_null":
            return _as_bool_i8(~null), no_null, zero_err
        e = jnp.where(null, 0, e)
        if f == "neg":
            return -v, null, e
        if f == "not":
            return _as_bool_i8(~_truth(v)), null, e
        if f == "abs":
            return jnp.abs(v), null, e
        if f == "is_true":
            # NULL is not true (WHERE-clause semantics handled by MFP's keep)
            return _truth(v) & ~null, no_null, e
        if f == "cast_int64":
            return v.astype(jnp.int64), null, e
        if f == "cast_int32":
            return v.astype(jnp.int32), null, e
        if f == "cast_float":
            return v.astype(jnp.float32), null, e
        if f == "sqrt":
            return jnp.sqrt(v.astype(jnp.float32)), null, e
        if f in _FLOAT_UNARY:
            return _FLOAT_UNARY[f](v.astype(jnp.float32)), null, e
        if f == "round_half_away":
            fv = v.astype(jnp.float32)
            return jnp.sign(fv) * jnp.floor(jnp.abs(fv) + jnp.float32(0.5)), null, e
        if f == "sign":
            return jnp.sign(v), null, e
        if f in ("extract_year", "extract_month", "extract_day"):
            y, m, d = _civil_from_days(v)
            return {"extract_year": y, "extract_month": m, "extract_day": d}[f], null, e
        if f in _DATE_UNARY:
            return _DATE_UNARY[f](v), null, e
        raise NotImplementedError(f"unary func {f}")
    if isinstance(expr, CallBinary):
        f = expr.func
        lv, ln, le = eval_expr3(expr.left, cols, n)
        rv, rn, re_ = eval_expr3(expr.right, cols, n)
        null = ln | rn
        err = jnp.where(null, 0, jnp.maximum(le, re_))
        if f == "and":
            lt, rt = _truth(lv) & ~ln, _truth(rv) & ~rn
            lf, rf = ~_truth(lv) & ~ln, ~_truth(rv) & ~rn
            is_false = lf | rf  # Kleene: FALSE dominates NULL
            return _as_bool_i8(lt & rt), null & ~is_false, err
        if f == "or":
            lt, rt = _truth(lv) & ~ln, _truth(rv) & ~rn
            is_true = lt | rt  # Kleene: TRUE dominates NULL
            return _as_bool_i8(is_true), null & ~is_true, err
        if f == "add":
            return lv + rv, null, err
        if f == "sub":
            return lv - rv, null, err
        if f == "mul":
            return lv * rv, null, err
        if f == "mul_exact":
            # an i64 product that would wrap is an error, not a value (the
            # NUMERIC pre-scale of a dividend, sql/plan.py::_numeric_div)
            lv, rv = lv.astype(jnp.int64), rv.astype(jnp.int64)
            room = jnp.iinfo(jnp.int64).max // jnp.maximum(jnp.abs(rv), 1)
            over = (jnp.abs(lv) > room) & ~null
            return lv * rv, null, jnp.where(over, jnp.int32(EvalErr.NUMERIC_OVERFLOW), err)
        if f in ("div", "floordiv"):
            zero = (rv == 0) & ~null
            safe = jnp.where(rv == 0, jnp.ones_like(rv), rv)
            if jnp.issubdtype(jnp.result_type(lv, rv), jnp.floating):
                out = lv / safe
            else:
                # SQL integer division truncates toward zero; lax floordiv
                # floors, so compute on magnitudes and restore sign.
                q = jnp.abs(lv) // jnp.abs(safe)
                out = jnp.where((lv < 0) ^ (safe < 0), -q, q)
            err = jnp.where(zero, jnp.int32(EvalErr.DIVISION_BY_ZERO), err)
            return out, null, err
        if f == "mod":
            zero = (rv == 0) & ~null
            safe = jnp.where(rv == 0, jnp.ones_like(rv), rv)
            out = lv - safe * (
                jnp.where((lv < 0) ^ (safe < 0), -(jnp.abs(lv) // jnp.abs(safe)), jnp.abs(lv) // jnp.abs(safe))
            )
            err = jnp.where(zero, jnp.int32(EvalErr.DIVISION_BY_ZERO), err)
            return out, null, err
        if f == "eq":
            return _as_bool_i8(lv == rv), null, err
        if f == "ne":
            return _as_bool_i8(lv != rv), null, err
        if f == "lt":
            return _as_bool_i8(lv < rv), null, err
        if f == "lte":
            return _as_bool_i8(lv <= rv), null, err
        if f == "gt":
            return _as_bool_i8(lv > rv), null, err
        if f == "gte":
            return _as_bool_i8(lv >= rv), null, err
        if f == "min":
            return jnp.minimum(lv, rv), null, err
        if f == "max":
            return jnp.maximum(lv, rv), null, err
        if f == "pow":
            return jnp.power(lv.astype(jnp.float32), rv.astype(jnp.float32)), null, err
        if f == "atan2":
            return jnp.arctan2(lv.astype(jnp.float32), rv.astype(jnp.float32)), null, err
        if f == "add_months":
            # calendar month addition with pg's end-of-month clamp:
            # Jan 31 + 1 month = Feb 28/29 (reference interval.rs semantics)
            y, m, d = _civil_from_days(lv)
            t = y * 12 + (m - 1) + rv.astype(jnp.int64)
            y2 = t // 12
            m2 = t % 12 + 1
            d2 = jnp.minimum(d, _days_in_month(y2, m2))
            return _days_from_civil(y2, m2, d2), null, err
        if f in ("fdiv", "fmod"):
            # FLOOR division/modulo (internal: date_trunc/extract arithmetic;
            # SQL-visible div/mod truncate toward zero instead)
            zero = (rv == 0) & ~null
            safe = jnp.where(rv == 0, jnp.ones_like(rv), rv)
            err = jnp.where(zero, jnp.int32(EvalErr.DIVISION_BY_ZERO), err)
            if f == "fdiv":
                return lv // safe, null, err
            return lv - safe * (lv // safe), null, err
        raise NotImplementedError(f"binary func {f}")
    if isinstance(expr, CallVariadic):
        f = expr.func
        parts = [eval_expr3(e, cols, n) for e in expr.exprs]
        vals = [p[0] for p in parts]
        nulls = [p[1] for p in parts]
        errs = [p[2] for p in parts]
        any_null = nulls[0]
        for m in nulls[1:]:
            any_null = any_null | m
        err = errs[0]
        for e in errs[1:]:
            err = jnp.maximum(err, e)
        if f == "and":
            is_false = no_null
            all_true = ~no_null
            for v, m in zip(vals, nulls):
                is_false = is_false | (~_truth(v) & ~m)
                all_true = all_true & (_truth(v) & ~m)
            err = jnp.where(any_null & ~is_false, 0, err)
            return _as_bool_i8(all_true), any_null & ~is_false, err
        if f == "or":
            is_true = no_null
            for v, m in zip(vals, nulls):
                is_true = is_true | (_truth(v) & ~m)
            err = jnp.where(any_null & ~is_true, 0, err)
            return _as_bool_i8(is_true), any_null & ~is_true, err
        if f == "if":
            (cv, cn, _), (tv, tn, _), (ev, en, _) = parts
            take = _truth(cv) & ~cn  # NULL condition selects ELSE
            out = jnp.where(take, tv, ev)
            return out, jnp.where(take, tn, en), err
        if f == "coalesce":
            out, null = vals[0], nulls[0]
            for v, m in zip(vals[1:], nulls[1:]):
                out = jnp.where(null, v.astype(out.dtype), out)
                null = null & m
            return out, null, err
        if f == "nullif":
            a, an = vals[0], nulls[0]
            b, bn = vals[1], nulls[1]
            eq = (a == b.astype(a.dtype)) & ~an & ~bn
            return a, an | eq, err
        if f == "greatest":
            out, null = vals[0], nulls[0]
            for v, m in zip(vals[1:], nulls[1:]):
                # SQL greatest/least ignore NULLs; all-NULL stays NULL
                out = jnp.where(null, v, jnp.where(m, out, jnp.maximum(out, v)))
                null = null & m
            return out, null, err
        if f == "least":
            out, null = vals[0], nulls[0]
            for v, m in zip(vals[1:], nulls[1:]):
                out = jnp.where(null, v, jnp.where(m, out, jnp.minimum(out, v)))
                null = null & m
            return out, null, err
        raise NotImplementedError(f"variadic func {f}")
    if isinstance(expr, DictFunc):
        parts = [eval_expr3(a, cols, n) for a in expr.args]
        vals = [p[0] for p in parts]
        # concat_ws skips NULL arguments instead of propagating them (pg
        # semantics: no phantom separators); only a NULL separator (arg 0)
        # nulls the result. Everything else is strictly NULL-propagating.
        skips_null_args = expr.spec[0] == "concat_ws"
        null = parts[0][1]
        err = parts[0][2]
        for _, nv, ev in parts[1:]:
            if not skips_null_args:
                null = null | nv
            err = jnp.maximum(err, ev)
        err = jnp.where(null, 0, err)
        import jax.core as _core

        if any(isinstance(v, _core.Tracer) for v in vals) or isinstance(
            null, _core.Tracer
        ):
            # tables are host state; baking them into a compiled program
            # would go stale as the dictionary grows (fused path rejects
            # DictFunc upfront — this guard catches any other jit use)
            raise NotImplementedError("string functions evaluate host-side only")
        if len(vals) == 1:
            tbl = jnp.asarray(expr.tables.table(expr.spec))
            m = int(tbl.shape[0])
            code = vals[0].astype(jnp.int64)
            oob = (~null) & ((code < 0) | (code >= m))
            if m:
                out = tbl[jnp.clip(code, 0, m - 1)]
            else:
                out = jnp.zeros((n,), dtype=tbl.dtype)
            err = jnp.where(oob, jnp.int32(EvalErr.STRING_CODE_OOB), err)
        else:
            res, oob = expr.tables.eval_multi(
                expr.spec,
                expr.argtypes,
                [np.asarray(v) for v in vals],
                np.asarray(null),
                arg_nulls=(
                    [np.asarray(p[1]) for p in parts] if skips_null_args else None
                ),
            )
            out = jnp.asarray(res)
            err = jnp.where(
                jnp.asarray(oob), jnp.int32(EvalErr.STRING_CODE_OOB), err
            )
        if expr.out == "bool":
            out = out.astype(jnp.int8)
        else:
            # table entries can hold the NULL sentinel (json key misses,
            # bad casts): fold them into the null mask so 3VL holds for
            # direct consumers of this expression
            null = null | (out == NULL_I64)
        return out, null, err
    raise TypeError(f"not a ScalarExpr: {expr!r}")


# days between 1970-01-01 and the engine's date epoch 1992-01-01
_D1992 = 8035

# float32 elementwise math (device VPU transcendentals; host mirror uses the
# same f32 width so fast-path peeks agree bit-for-bit)
_FLOAT_UNARY = {
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "trunc": jnp.trunc,
    "exp": jnp.exp,
    "ln": jnp.log,
    "log10": lambda v: jnp.log10(v),
    "log2": lambda v: jnp.log2(v),
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "cot": lambda v: jnp.float32(1.0) / jnp.tan(v),
    "cbrt": jnp.cbrt,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
}

# host numpy mirror of _FLOAT_UNARY (same names, same f32 width) — kept
# adjacent so the two tables cannot silently diverge; the fast-path row
# interpreter uses this to agree bit-for-bit with device kernels
_FLOAT_UNARY_NP = {
    "floor": np.floor,
    "ceil": np.ceil,
    "trunc": np.trunc,
    "exp": np.exp,
    "ln": np.log,
    "log10": np.log10,
    "log2": np.log2,
    "sin": np.sin,
    "cos": np.cos,
    "tan": np.tan,
    "asin": np.arcsin,
    "acos": np.arccos,
    "atan": np.arctan,
    "sinh": np.sinh,
    "cosh": np.cosh,
    "tanh": np.tanh,
    "cot": lambda v: np.float32(1.0) / np.tan(v),
    "cbrt": np.cbrt,
    "degrees": np.degrees,
    "radians": np.radians,
}
assert set(_FLOAT_UNARY_NP) == set(_FLOAT_UNARY)


_MONTH_DAYS = np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])


def _days_in_month(y, m):
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    base = jnp.asarray(_MONTH_DAYS)[jnp.clip(m - 1, 0, 11)]
    return base + (leap & (m == 2))


def add_months_int(v: int, n: int) -> int:
    """Host mirror of the device add_months kernel (same clamp rule)."""
    y, m, d = civil_from_days_int(int(v))
    t = y * 12 + (m - 1) + int(n)
    y2, m2 = t // 12, t % 12 + 1
    leap = (y2 % 4 == 0 and y2 % 100 != 0) or y2 % 400 == 0
    dim = [31, 29 if leap else 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31][m2 - 1]
    return days_from_civil_int(y2, m2, min(d, dim))


def _days_from_civil(y, m, d):
    """Inverse of _civil_from_days: (y, m, d) → day number since 1992-01-01."""
    y = y - (m <= 2)
    era = y // 400  # jnp // floors, as the algorithm requires for y < 0
    yoe = y - era * 400
    doy = (153 * (m + jnp.where(m > 2, -3, 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468 - _D1992


def _date_dow(v):
    """Day of week, Sunday = 0 (pg extract(dow)). 1970-01-01 was Thursday."""
    return jnp.remainder(v.astype(jnp.int64) + _D1992 + 4, 7)


def _date_isodow(v):
    """ISO day of week, Monday = 1 … Sunday = 7."""
    return jnp.remainder(v.astype(jnp.int64) + _D1992 + 3, 7) + 1


def _date_doy(v):
    y, _m, _d = _civil_from_days(v)
    ones = jnp.ones_like(y)
    return v.astype(jnp.int64) - _days_from_civil(y, ones, ones) + 1


def _iso_long_year(y):
    """53-week ISO years: Jan 1 is Thursday, or leap year with Jan 1 Wednesday."""
    ones = jnp.ones_like(y)
    jan1 = _days_from_civil(y, ones, ones)
    dow = _date_isodow(jan1)
    leap = ((y % 4 == 0) & (y % 100 != 0)) | (y % 400 == 0)
    return (dow == 4) | (leap & (dow == 3))


def _date_isoweek(v):
    y, _m, _d = _civil_from_days(v)
    w = (_date_doy(v) - _date_isodow(v) + 10) // 7
    weeks_prev = jnp.where(_iso_long_year(y - 1), 53, 52)
    weeks_cur = jnp.where(_iso_long_year(y), 53, 52)
    # the two rollovers are exclusive: w<1 borrows the previous year's last
    # week; only an ORIGINAL w past this year's count wraps to week 1
    return jnp.where(w < 1, weeks_prev, jnp.where(w > weeks_cur, 1, w))


def _trunc_year(v):
    y, _m, _d = _civil_from_days(v)
    ones = jnp.ones_like(y)
    return _days_from_civil(y, ones, ones)


def _trunc_quarter(v):
    y, m, _d = _civil_from_days(v)
    qm = ((m - 1) // 3) * 3 + 1
    return _days_from_civil(y, qm, jnp.ones_like(y))


def _trunc_month(v):
    y, m, _d = _civil_from_days(v)
    return _days_from_civil(y, m, jnp.ones_like(y))


def _trunc_week(v):
    """Monday of v's ISO week."""
    return v.astype(jnp.int64) - (_date_isodow(v) - 1)


_DATE_UNARY = {
    "extract_dow": _date_dow,
    "extract_isodow": _date_isodow,
    "extract_doy": _date_doy,
    "extract_quarter": lambda v: (_civil_from_days(v)[1] + 2) // 3,
    "extract_week": _date_isoweek,
    "extract_epoch_date": lambda v: (v.astype(jnp.int64) + _D1992) * 86400,
    "extract_century": lambda v: (_civil_from_days(v)[0] + 99) // 100,
    "extract_decade": lambda v: _civil_from_days(v)[0] // 10,
    "extract_millennium": lambda v: (_civil_from_days(v)[0] + 999) // 1000,
    "date_trunc_year": _trunc_year,
    "date_trunc_quarter": _trunc_quarter,
    "date_trunc_month": _trunc_month,
    "date_trunc_week": _trunc_week,
    "date_trunc_day": lambda v: v,
}


def civil_from_days_int(days: int) -> tuple:
    """Pure-int (y, m, d) from a day number since 1992-01-01 — the single
    definition both the device kernel and host fast-path interpreter use."""
    z = days + _D1992 + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + (3 if mp < 10 else -9)
    return y + (1 if m <= 2 else 0), m, d


def days_from_civil_int(y: int, m: int, d: int) -> int:
    """Pure-int inverse of civil_from_days_int (host mirror of _days_from_civil)."""
    y = y - (1 if m <= 2 else 0)
    era = y // 400
    yoe = y - era * 400
    doy = (153 * (m + (-3 if m > 2 else 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468 - _D1992


def date_unary_int(f: str, v: int) -> int:
    """Host mirror of _DATE_UNARY for the fast-path row interpreter —
    bit-identical to the device kernels (both are pure integer Hinnant
    calendar arithmetic)."""
    v = int(v)
    if f == "extract_dow":
        return (v + _D1992 + 4) % 7
    if f == "extract_isodow":
        return (v + _D1992 + 3) % 7 + 1
    y, m, d = civil_from_days_int(v)
    if f == "extract_doy":
        return v - days_from_civil_int(y, 1, 1) + 1
    if f == "extract_quarter":
        return (m + 2) // 3
    if f == "extract_week":
        doy = v - days_from_civil_int(y, 1, 1) + 1
        isodow = (v + _D1992 + 3) % 7 + 1
        w = (doy - isodow + 10) // 7

        def long_year(yy):
            jan1 = days_from_civil_int(yy, 1, 1)
            dw = (jan1 + _D1992 + 3) % 7 + 1
            leap = (yy % 4 == 0 and yy % 100 != 0) or yy % 400 == 0
            return dw == 4 or (leap and dw == 3)

        if w < 1:
            return 53 if long_year(y - 1) else 52
        if w > (53 if long_year(y) else 52):
            return 1
        return w
    if f == "extract_epoch_date":
        return (v + _D1992) * 86400
    if f == "extract_century":
        return (y + 99) // 100
    if f == "extract_decade":
        return y // 10
    if f == "extract_millennium":
        return (y + 999) // 1000
    if f == "date_trunc_year":
        return days_from_civil_int(y, 1, 1)
    if f == "date_trunc_quarter":
        return days_from_civil_int(y, ((m - 1) // 3) * 3 + 1, 1)
    if f == "date_trunc_month":
        return days_from_civil_int(y, m, 1)
    if f == "date_trunc_week":
        return v - ((v + _D1992 + 3) % 7)
    if f == "date_trunc_day":
        return v
    raise NotImplementedError(f"date func {f}")


def _civil_from_days(days):
    """Exact (y, m, d) from day numbers since 1992-01-01 (Hinnant's
    civil_from_days, pure integer ops — vectorizes on the VPU)."""
    z = days.astype(jnp.int64) + _D1992 + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def expr_columns(expr: ScalarExpr) -> set[int]:
    """Set of input column indices an expression references (for demand analysis)."""
    if isinstance(expr, Column):
        return {expr.index}
    if isinstance(expr, Literal):
        return set()
    if isinstance(expr, CallUnary):
        return expr_columns(expr.expr)
    if isinstance(expr, CallBinary):
        return expr_columns(expr.left) | expr_columns(expr.right)
    if isinstance(expr, CallVariadic):
        out: set[int] = set()
        for e in expr.exprs:
            out |= expr_columns(e)
        return out
    if isinstance(expr, DictFunc):
        out2: set[int] = set()
        for e in expr.args:
            out2 |= expr_columns(e)
        return out2
    raise TypeError(f"not a ScalarExpr: {expr!r}")


def expr_has_dictfunc(expr: ScalarExpr) -> bool:
    """True if the expression tree contains a DictFunc (host-path only)."""
    if isinstance(expr, DictFunc):
        return True
    if isinstance(expr, CallUnary):
        return expr_has_dictfunc(expr.expr)
    if isinstance(expr, CallBinary):
        return expr_has_dictfunc(expr.left) or expr_has_dictfunc(expr.right)
    if isinstance(expr, CallVariadic):
        return any(expr_has_dictfunc(e) for e in expr.exprs)
    return False
