"""Whole rehearsal runs of the Q18 cell (its own configuration and traffic mix,
at the configuration's rehearsal scale, CPU) with the timed path broken
underneath: `correct` has to come out false for each fault, and true with none.

At SF0.01 Q18's answer is an order or two of 15,000 and moves in few
refreshes of a run, so a fault in an arbitrary refresh may well not reach
it. The seed and the refresh are pinned where it does: at seed 3000003602
refresh 4 inserts an order whose lines sum past 300, which the withheld
refresh keeps from the reference and the halved batch keeps from the program
(host arithmetic over the faulted streams, `_bites` below, asserted before
the long run is made)."""

import copy

import numpy as np
import pytest

from chipbench import run as bench_run
from chipbench.reference import tpch_q18 as ref
from chipbench.traffic import tpch_full as traffic
from materialize_tpu.storage.generator import TPCH_TABLES

CELL, SEED, AT = "loadgen_q18_sf1_refresh", 3000003602, 4


class WithheldFromReference(traffic.Generator):
    """The program gets every refresh; the reference's live rows miss the
    changes of refresh AT (its mirrors are put back)."""

    def refresh_rows(self, frac: float = 0.001, deletes: bool = True):
        n = getattr(self, "_n", 0) + 1
        self._n = n
        if n != AT:
            return super().refresh_rows(frac, deletes)
        orders, lineitem = copy.deepcopy(self._orders), copy.deepcopy(self._lineitem)
        rows = super().refresh_rows(frac, deletes)
        self._orders, self._lineitem = orders, lineitem
        return rows


class HalfTheBatchLeftOut(traffic.Generator):
    """Half of refresh AT's lineitem changes never reach the program."""

    def refresh_rows(self, frac: float = 0.001, deletes: bool = True):
        rows = super().refresh_rows(frac, deletes)
        n = getattr(self, "_n", 0) + 1
        self._n = n
        if n == AT:
            cols, d = rows["lineitem"]
            keep = np.arange(len(d)) % 2 == 0
            rows["lineitem"] = (tuple(c[keep] for c in cols), d[keep])
        return rows


def _files():
    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = bench_run.one(bench["workloads"], CELL)
    config = bench_run.load_json(bench_run.ROOT / bench_run.one(bench["configs"], cell["config"])["file"])
    spec = bench_run.load_json(bench_run.HERE / "workloads" / f"{cell['traffic']}.json")
    return bench, cell, config, spec


def _bites(generator_cls) -> bool:
    """Host only: Q18 over the rows the PROGRAM was sent (snapshot plus every
    refresh as `generator_cls` hands it over, with their diffs summed per
    row) against the reference over the generator's own live rows, after the
    cell's refreshes."""
    _bench, _cell, config, spec = _files()
    g = generator_cls(sf=config["rehearse_scale_factor"], seed=SEED)
    snap = g.snapshot()
    sent = {t: [(snap[t], np.ones(len(snap[t][0]), dtype=np.int64))] for t in ("customer", "orders", "lineitem")}
    for _ in range(spec["warmups"] + spec["refreshes"]):
        for t, part in g.refresh_rows().items():
            sent[t].append(part)
    program = {}
    for t, parts in sent.items():
        rows = np.stack([np.concatenate(c) for c in zip(*(cols for cols, _d in parts))], axis=1)
        uniq, inv = np.unique(rows, axis=0, return_inverse=True)
        net = np.bincount(inv.ravel(), weights=np.concatenate([d for _c, d in parts]))
        program[t] = dict(zip(TPCH_TABLES[t].names, uniq[net > 0].T))  # a withheld refresh retracts twice
    return ref.q18(program) != ref.q18(g.live())


def _rehearse(generator_cls=None):
    bench, cell, config, spec = _files()
    if generator_cls is not None:
        config["generator"]["class"] = f"{__name__}:{generator_cls.__name__}"
    return bench_run.run_cell(bench, cell, config, spec, seed=SEED, seconds=3600.0, trace=False, rehearse=True)


def _failing(result) -> set:
    return {k for k, c in result["checks"].items() if not bench_run.holds(c)}


@pytest.mark.parametrize("fault", [None, WithheldFromReference, HalfTheBatchLeftOut],
                         ids=lambda f: "sound" if f is None else f.__name__)
def test_run_is_correct_only_when_sound(fault):
    if fault is not None:
        assert _bites(fault), "the pinned seed and refresh no longer carry the fault to the answer"
    result = _rehearse(fault)
    assert result["device"]["platform"] == "cpu" and result["metrics"] == {}
    assert result["attempted"] == 10 and result["failed"] == 0
    if fault is not WithheldFromReference:  # the order that entered in refresh AT is in the answer
        assert result["counts"]["reference_rows"] >= 1
    assert list(result)[-1] == "checks"
    assert result["correct"] is (fault is None), result["checks"]
    if fault is not None:
        assert {"subscribe_rows_differ", "pgwire_rows_differ", "http_rows_differ"} <= _failing(result)


def test_answer_altered_where_it_is_produced(monkeypatch):
    """The coordinator's SELECT hands back a first row whose o_totalprice is
    one cent off. Only the reads can see it; the subscriber's stream stays right."""
    from materialize_tpu.adapter import coordinator

    real = coordinator.Coordinator.execute_stmt

    def altered(self, stmt, *a, **kw):
        res = real(self, stmt, *a, **kw)
        if res.kind == "rows" and res.rows and tuple(res.columns)[:5] == (
                "c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"):
            row = list(res.rows[0])
            row[4] = round(row[4] + 0.01, 2)
            res.rows[0] = tuple(row)
        return res

    monkeypatch.setattr(coordinator.Coordinator, "execute_stmt", altered)
    result = _rehearse()
    assert result["correct"] is False
    assert _failing(result) == {"pgwire_rows_differ", "http_rows_differ"}
