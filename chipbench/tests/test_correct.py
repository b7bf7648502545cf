"""Whole rehearsal runs of the committed cell (at SF0.01, CPU) with the timed path
broken underneath: `correct` has to come out false for each fault a cell can
have, and true with no fault. The harness's look for a chip is skipped
(`rehearse=True`); everything else is the run a cell makes."""

import copy

import numpy as np
import pytest

from chipbench import run as bench_run
from chipbench.traffic import tpch as traffic


class WithheldFromReference(traffic.Generator):
    """The program gets every refresh; the reference's live rows miss the
    changes of the third (its mirrors are put back)."""

    def refresh_rows(self, frac: float = 0.001):
        n = getattr(self, "_n", 0) + 1
        self._n = n
        if n != 3:
            return super().refresh_rows(frac)
        orders, lineitem = copy.deepcopy(self._orders), copy.deepcopy(self._lineitem)
        rows = super().refresh_rows(frac)
        self._orders, self._lineitem = orders, lineitem
        return rows


class StateLeftUnchanged(traffic.Generator):
    """A step that leaves the view's state as it was: the third refresh moves
    the live rows (and so the reference) but nothing of it reaches the program."""

    def refresh_rows(self, frac: float = 0.001):
        rows = super().refresh_rows(frac)
        n = getattr(self, "_n", 0) + 1
        self._n = n
        if n == 3:
            rows = {t: (tuple(c[:0] for c in cols), d[:0]) for t, (cols, d) in rows.items()}
        return rows


class HalfTheBatchLeftOut(traffic.Generator):
    """Half of one refresh's lineitem changes never reach the program."""

    def refresh_rows(self, frac: float = 0.001):
        rows = super().refresh_rows(frac)
        n = getattr(self, "_n", 0) + 1
        self._n = n
        if n == 3:
            cols, d = rows["lineitem"]
            keep = np.arange(len(d)) % 2 == 0
            rows["lineitem"] = (tuple(c[keep] for c in cols), d[keep])
        return rows


def _rehearse(generator_cls=None):
    """The committed cell's own configuration and traffic mix, found as the
    command finds them, at a size the CPU holds."""
    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench["workloads"][0]
    config = bench_run.load_json(bench_run.ROOT / bench_run.one(bench["configs"], cell["config"])["file"])
    spec = bench_run.load_json(bench_run.HERE / "workloads" / f"{cell['traffic']}.json")
    # SF0.01: at the rehearsal's SF0.001 a refresh is one order, which Q3 may well not select
    config["rehearse_scale_factor"] = 0.01
    if generator_cls is not None:
        config["generator"]["class"] = f"{__name__}:{generator_cls.__name__}"
    return bench_run.run_cell(bench, cell, config, spec, seed=4242, seconds=600.0, trace=False, rehearse=True)


def _failing(result) -> set:
    return {k for k, c in result["checks"].items() if not bench_run.holds(c)}


@pytest.mark.parametrize("fault", [None, WithheldFromReference, StateLeftUnchanged, HalfTheBatchLeftOut],
                         ids=lambda f: "sound" if f is None else f.__name__)
def test_run_is_correct_only_when_sound(fault):
    result = _rehearse(fault)
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] == 5 and result["failed"] == 0
    assert list(result)[-1] == "checks"  # the numbers compared come last in the line
    assert result["correct"] is (fault is None), result["checks"]
    if fault is not None:
        assert {"subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ"} <= _failing(result)


def test_answer_altered_where_it_is_produced(monkeypatch):
    """An answer altered where it is produced: the coordinator's SELECT hands
    back one revenue that is 1 off. Only the reads can see it; the
    subscriber's stream stays right."""
    from materialize_tpu.adapter import coordinator

    real = coordinator.Coordinator.execute_stmt

    def altered(self, stmt, *a, **kw):
        res = real(self, stmt, *a, **kw)
        if res.kind == "rows" and res.rows and "revenue" in tuple(res.columns):
            i = tuple(res.columns).index("revenue")
            row = list(res.rows[0])
            row[i] = row[i] + type(row[i])(1)
            res.rows[0] = tuple(row)
        return res

    monkeypatch.setattr(coordinator.Coordinator, "execute_stmt", altered)
    result = _rehearse()
    assert result["correct"] is False
    assert _failing(result) == {"pgwire_rows_differ", "http_rows_differ"}
