"""TPC-H Q18 as the benchmark's fourth deployment serves it: the published
text (a per-order sum over lineitem, its HAVING, an IN semijoin into a
four-input join, ORDER BY ... LIMIT 100) through `Coordinator` over
`LOAD GENERATOR TPCH`, with the cell's seeded generator at the one seam,
compared with the benchmark's plain NumPy reference after hydration and
after every refresh, by `SELECT` and by a SUBSCRIBE's consolidated diffs.
And what the query forced: the join planner takes an input with a known
unique key (the semijoin) first in every path that can reach it, and leaves
the other cells' plans as they were; a refresh that first moves the answer
after the warm-ups asks for no program; the join and TopK operators count
what they do without a read of their own."""

import collections
import functools
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.reference import tpch_q18 as ref
from chipbench.traffic.tpch_full import Generator
from materialize_tpu.adapter import Coordinator, coordinator
from materialize_tpu.adapter.coordinator import Lowerer, _collect_gets, optimize
from materialize_tpu.dataflow import plan as lir
from materialize_tpu.dataflow import runtime
from materialize_tpu.obs.metrics import REGISTRY
from materialize_tpu.obs.spans import TRACER
from materialize_tpu.sql.parser import parse_statement

CONFIGS = Path(ref.__file__).parents[1] / "configs"
SOURCE, Q18 = json.loads((CONFIGS / "loadgen_tpch_sf1_q18.json").read_text())["setup_sql"]
# At SF0.01 the published threshold keeps about one order of 15,000: a lower
# one and a LIMIT that binds make the TopK drop rows and apply its tie rule.
BINDING = Q18.replace("> 300", "> 200").replace("LIMIT 100", "LIMIT 5")
SEEDS = [11, 3000003601]


@pytest.fixture(autouse=True)
def _clear_jax_caches_between_tests():
    """Free compiled executables after each test, not only after the module:
    each test here serves TPC-H at SF0.01 or builds a join, and one process
    that runs the whole module holds more compiled code than XLA:CPU
    compiles beside without crashing (doc/ROADMAP.md "Known flake": the
    module's last test segfaulted in `backend_compile_and_load` when run
    after all the others in one process). What a later test needs comes back
    from the run's persistent cache."""
    yield
    import jax

    jax.clear_caches()


def _served(monkeypatch, seed: int, view: str, sf: str = "0.01", generator=Generator) -> Coordinator:
    monkeypatch.setattr(coordinator, "TpchGenerator", functools.partial(generator, seed=seed))
    c = Coordinator()
    c.execute(SOURCE.format(scale_factor=sf))
    c.execute(view)
    return c


def _parse(rows) -> dict:
    return ref.VIEWS["q18"][1](rows)


class _Subscribed:
    """A SUBSCRIBE's diffs, consolidated; every live row at multiplicity 1."""

    def __init__(self, c: Coordinator):
        self.sub = c.execute("SUBSCRIBE q18 WITH (PROGRESS)").subscription
        self.rows: dict = {}

    def live(self) -> dict:
        for _ts, progress, diff, row in self.sub.drain():
            if not progress:
                self.rows[row] = self.rows.get(row, 0) + diff
        live = {r: n for r, n in self.rows.items() if n}
        assert set(live.values()) <= {1}
        return _parse(live)


@pytest.mark.parametrize("seed", SEEDS)
def test_q18_equals_the_reference_after_every_refresh(monkeypatch, seed):
    c = _served(monkeypatch, seed, Q18)
    gen, sub = c.generators[0][0], _Subscribed(c)
    for refresh in range(9):  # hydration, then 8 refreshes
        if refresh:
            c.advance()
        want = ref.q18(gen.live())
        assert _parse(c.execute("SELECT * FROM q18").rows) == want
        assert sub.live() == want


class _Placed(Generator):
    """The cell's draw, with an order past the threshold (seven lines at
    quantity 50) placed among the new orders of each refresh in `enters`,
    and in the snapshot among the orders each refresh in `leaves` retracts."""

    def __init__(self, *a, enters=(), leaves=(), **kw):
        super().__init__(*a, **kw)
        self.enters, self.leaves, self.drawn = set(enters), set(leaves), 0

    def _core(self, rng, n_customer, n_part, orderkey):
        core = Generator._core(rng, n_customer, n_part, orderkey)
        drawn, self.drawn = self.drawn, self.drawn + 1
        per_refresh = max(int(self.n_orders * 0.001), 1)  # `refresh_rows`' default frac
        if drawn == 0:  # the snapshot, oldest first: refresh k retracts the k-th block of keys
            blocks = [(orderkey >= (k - 1) * per_refresh) & (orderkey < k * per_refresh) for k in self.leaves]
        else:
            blocks = [np.ones(len(orderkey), dtype=bool)] if drawn in self.enters else []
        _custkey, _date, lines_per_order, lines = core
        for block in blocks:
            seven = np.flatnonzero(block & (lines_per_order == 7))
            first = int(np.cumsum(lines_per_order)[seven[0]]) - 7
            lines[3][first : first + 7] = 50  # l_quantity, drawn in place
        return core


def test_an_answer_that_first_moves_after_the_warmups_asks_for_no_program(monkeypatch, programs_built):
    """The cell's four warm-ups, in which the answer does not move, then an
    order entering it (refresh 5), one leaving it (6), and both at once (7):
    each is the first refresh of its kind in the process, and none asks for
    a program. The semijoin's path, the stages past it, the outer reduce,
    the TopK and the view's arrangements run in every refresh, at the
    shapes a handful of rows gives them; the answer is the reference's. At
    SF0.03 a refresh's deltas sit inside their buckets, as at SF1 (at
    SF0.01 lineitem's, some 120 rows, crosses 128 by chance)."""
    placed = functools.partial(_Placed, enters=(5, 7), leaves=(6, 7))
    c = _served(monkeypatch, 11, Q18, sf="0.03", generator=placed)
    gen, sub = c.generators[0][0], _Subscribed(c)
    answers, programs = [], []
    for refresh in range(1, 8):
        before = programs_built()
        c.advance()
        programs.append(programs_built() - before)
        want = ref.q18(gen.live())
        assert _parse(c.execute("SELECT * FROM q18").rows) == want
        assert sub.live() == want
        answers.append(want)
    assert answers[0] == answers[1] == answers[2] == answers[3] != answers[4] != answers[5] != answers[6]
    assert programs[4:] == [0, 0, 0], programs


def _family(name: str, dataflow: str) -> dict:
    """The view's own samples of one registry family (the registry is the process's)."""
    for fam in REGISTRY.families():
        if fam.name == name:
            return {labels: (v[2] if fam.kind == "histogram" else v) for labels, v in fam.samples
                    if dict(labels)["dataflow"] == dataflow}
    return {}


def _semijoin_kept(live: dict, gone: set) -> int:
    """Rows of a refresh's orders and lineitem deltas the semijoin keeps, as
    its arrangement stood before the refresh: the retracted orders (and their
    lines) that were past the threshold. New orders have new keys."""
    li = live["lineitem"]
    keys, inv = np.unique(li["l_orderkey"], return_inverse=True)
    big = set(keys[np.bincount(inv, weights=li["l_quantity"]) > 200].tolist()) & gone
    return len(big) + int(np.isin(li["l_orderkey"], list(big)).sum())


class _Reads:
    """Device-to-host reads inside the join's and the TopK's steps, beside
    the count passes they run: an array's first conversion to NumPy is its
    read. An arrangement's insert is counted apart (it reads for its own
    merges, before and after this instrumentation alike)."""

    def __init__(self, monkeypatch):
        from jax._src import array as jarray

        from materialize_tpu.arrangement import spine
        from materialize_tpu.ops import join, topk

        self.reads, self.passes, self.where = collections.Counter(), collections.Counter(), ["other"]
        value = jarray.ArrayImpl._value

        def counted(arr):
            if arr._npy_value is None:
                self.reads[self.where[0]] += 1
            return value.fget(arr)

        monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counted))
        for cls, tag in ((runtime.DeltaJoinNode, "join"), (runtime.TopKNode, "topk"),
                         (spine.Arrangement, "insert")):
            name = "insert" if tag == "insert" else "step"
            monkeypatch.setattr(cls, name, self._in(getattr(cls, name), tag))
        for mod, name, tag in ((join, "_join_total", "join"), (topk, "_gather_total_jit", "topk")):
            monkeypatch.setattr(mod, name, self._counted(getattr(mod, name), tag))

    def _in(self, fn, tag):
        def wrapped(*args, **kw):
            prev = self.where[0]
            self.where[0] = "insert" if tag == "insert" else tag
            try:
                return fn(*args, **kw)
            finally:
                self.where[0] = prev

        return wrapped

    def _counted(self, fn, tag):
        def wrapped(*args, **kw):
            self.passes[tag] += 1
            return fn(*args, **kw)

        return wrapped


def test_a_binding_limit_equals_the_reference_and_the_operators_count(monkeypatch):
    """The same text at a threshold of 200 and LIMIT 5: the reference at the
    same threshold and limit, after hydration and 5 refreshes (each of the
    first few compiles the paths' programs on the CPU: the IN's keys change in
    most refreshes here, and at the published threshold in almost none). And
    per refresh: the semijoin stage's `out` rows (paths 1 and 2, stage 0:
    orders and lineitem meet it first) are the rows the reference's semijoin
    keeps of the deltas;
    `mzt_topk_step_duration_ns` counts one per TopK step that had input; the
    join's and the TopK's steps read nothing but their count passes' totals;
    each path that had input is a `join.path` span, each TopK step a
    `topk.step` span, under a `dataflow.tick` span."""
    c = _served(monkeypatch, 11, BINDING)
    gen, sub = c.generators[0][0], _Subscribed(c)
    gid = c.catalog.get("q18").global_id
    stepped = []
    real_step = runtime.TopKNode.step

    def topk_step(node, tick, ins):
        stepped.append(ins[0] is not None and ins[0][0] is not None)
        return real_step(node, tick, ins)

    monkeypatch.setattr(runtime.TopKNode, "step", topk_step)
    reads = _Reads(monkeypatch)
    kept_total = dropped = 0
    for refresh in range(6):
        before = {n: _family(n, gid) for n in ("mzt_join_stage_rows_total", "mzt_topk_step_duration_ns")}
        live = gen.live()
        orders_before = set(live["orders"]["o_orderkey"].tolist())
        if refresh:
            stepped.clear()
            reads.reads.clear()
            reads.passes.clear()
            TRACER.spans.clear()
            c.advance()
        want = ref.q18(gen.live(), quantity=200, limit=5)
        assert len(want) == 5
        dropped += len(ref.q18(gen.live(), quantity=200, limit=10**9)) - 5
        assert _parse(c.execute("SELECT * FROM q18").rows) == want
        assert sub.live() == want
        if not refresh:
            continue
        gone = orders_before - set(gen.live()["orders"]["o_orderkey"].tolist())
        rows = _family("mzt_join_stage_rows_total", gid)
        semijoin_out = sum(n - before["mzt_join_stage_rows_total"].get(k, 0) for k, n in rows.items()
                           if dict(k)["stage"] == "0" and dict(k)["side"] == "out" and dict(k)["path"] in ("1", "2"))
        kept = _semijoin_kept(live, gone)
        assert semijoin_out == kept
        kept_total += kept
        steps = _family("mzt_topk_step_duration_ns", gid)
        assert sum(steps.values()) - sum(before["mzt_topk_step_duration_ns"].values()) == sum(stepped)
        assert reads.reads["join"] == reads.passes["join"] > 0
        assert reads.reads["topk"] == reads.passes["topk"]
        names = {s.id: s.name for s in TRACER.spans}
        paths = [s for s in TRACER.spans if s.name == "join.path"]
        assert paths and all(names.get(s.parent) == "dataflow.tick" for s in paths)
        assert sum(1 for s in TRACER.spans if s.name == "topk.step") == sum(stepped)
    assert kept_total > 0 and dropped > 0


def _explain(c: Coordinator, select: str) -> list:
    return [r[0] for r in c.execute(f"EXPLAIN PHYSICAL PLAN FOR {select}").rows]


def test_q18_paths_take_the_semijoin_first(monkeypatch):
    """Inputs: 0 customer, 1 orders, 2 lineitem, 3 the IN subquery's
    distinct keys. The lineitem and orders paths meet the semijoin first,
    the customer path as soon as it can (orders first: the semijoin does not
    touch customer); the semijoin's own path keeps input order."""
    c = _served(monkeypatch, 11, "SELECT 1", sf="0.001")
    (join,) = [line.strip() for line in _explain(c, Q18.split(" AS ", 1)[1]) if line.strip().startswith("Join")]
    assert join == "Join type=delta paths=0>1>3>2 1>3>0>2 2>3>1>0 3>1>0>2"


def _lowered(c: Coordinator, select: str):
    rel = optimize(c.planner.plan_query(parse_statement(select).query).mir, c.configs)
    env = {g: c.storage[g].dtypes for g in _collect_gets(rel)}
    return Lowerer(env, c._mono_ids()).lower(rel)


@pytest.mark.parametrize("config", ["loadgen_tpch_sf1_q3", "loadgen_tpch_sf1_q17", "loadgen_tpch_sf1_q6"])
def test_the_other_cells_plans_are_unchanged(monkeypatch, config):
    """The lowered plans of the benchmark's other three views, as they were
    before the semijoin rule (tests/data/q18_unchanged_lir.json): Q3's three
    inputs have no known key, Q17's joins are binary, Q6 has no join."""
    from chipbench.run import resolve

    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    monkeypatch.setattr(coordinator, "TpchGenerator", functools.partial(resolve(cfg["generator"]["class"]), seed=11))
    c = Coordinator()
    c.execute(cfg["setup_sql"][0].format(scale_factor="0.001"))
    pinned = json.loads((Path(__file__).parent / "data" / "q18_unchanged_lir.json").read_text())
    assert repr(_lowered(c, cfg["setup_sql"][1].split(" AS ", 1)[1])) == pinned[config]


@pytest.fixture
def chain():
    c = Coordinator()
    for t in ("r0 (a int, b int)", "r1 (b int, c int)", "r2 (c int, d int)"):
        c.execute(f"CREATE TABLE {t}")
    c.execute("INSERT INTO r0 VALUES (1, 10), (2, 20), (3, 30)")
    c.execute("INSERT INTO r1 VALUES (10, 100), (20, 200), (20, 201), (30, 300)")
    c.execute("INSERT INTO r2 VALUES (100, 7), (200, 8), (201, 9), (300, 9)")
    return c


def _paths(c: Coordinator, select: str) -> list:
    (join,) = [line.strip() for line in _explain(c, select) if line.strip().startswith("Join")]
    return join.split("paths=")[1].split()


def test_a_three_way_join_without_a_keyed_input_keeps_its_order(chain):
    """Each path takes the lowest-numbered input connected to what it has
    joined, as before the semijoin rule."""
    assert _paths(chain, "SELECT * FROM r2, r0, r1 WHERE r0.b = r1.b AND r1.c = r2.c") == ["0>2>1", "1>2>0", "2>0>1"]
    assert _paths(chain, "SELECT * FROM r0, r1, r2 WHERE r0.b = r1.b AND r1.c = r2.c") == ["0>1>2", "1>0>2", "2>1>0"]


IN_QUERY = ("SELECT r0.a, r1.c, r2.d FROM r0, r1, r2 WHERE r0.b = r1.b AND r1.c = r2.c "
            "AND r1.c IN (SELECT c FROM r2 GROUP BY c HAVING sum(d) > 7)")
IN_ANSWER = [(2, 200, 8), (2, 201, 9), (3, 300, 9)]


def test_a_keyed_input_joins_first_in_a_delta_join(chain):
    """Input 3, the IN's distinct keys, is looked up by its whole key: each
    path that can reach it takes it next."""
    assert _paths(chain, IN_QUERY) == ["0>1>3>2", "1>3>0>2", "2>3>1>0", "3>1>0>2"]
    assert sorted(chain.execute(IN_QUERY).rows) == IN_ANSWER


def test_a_linear_chain_takes_the_keyed_input_first_and_answers_the_same(chain):
    """With delta joins off, the chain from input 0 joins input 1, then the
    keyed input 3 before input 2, and the closure projects the columns back
    into the inputs' order; the answer is the delta join's."""
    chain.execute("ALTER SYSTEM SET enable_delta_join = false")
    joins = []

    def walk(e):
        if isinstance(e, lir.Join):
            joins.append(e)
        for child in getattr(e, "inputs", None) or (getattr(e, "input", None),):
            if child is not None:
                walk(child)

    walk(_lowered(chain, IN_QUERY))
    (join,) = joins
    assert isinstance(join.plan, lir.LinearJoinPlan)
    assert [isinstance(i, lir.Get) for i in join.inputs] == [True, True, False, True]
    assert join.closure.projection == (0, 1, 2, 3, 5, 6, 4)
    assert sorted(chain.execute(IN_QUERY).rows) == IN_ANSWER


def _nodes(c: Coordinator, cls) -> list:
    return [node for _gid, df, _src in c.dataflows for _obj, ops, _out in df.builds
            for node, _refs in ops if isinstance(node, cls)]


def test_a_delta_joins_output_does_not_follow_which_paths_matched(monkeypatch):
    """A tick whose rows come from one path and one whose rows come from two
    (b's insert is the delta of inputs 1 and 2) hand the operators downstream
    one capacity: where the paths' matches fit the join's floor, their
    outputs are folded into one batch of that size, not concatenated."""
    caps = []
    real = runtime.DeltaJoinNode.step

    def step(node, tick, ins):
        out = real(node, tick, ins)
        if out is not None and out[0] is not None:
            caps.append((sum(d is not None and d[0] is not None for d in ins), out[0].cap))
        return out

    monkeypatch.setattr(runtime.DeltaJoinNode, "step", step)
    c = Coordinator()
    c.execute("CREATE TABLE a (k int, v int)")
    c.execute("CREATE TABLE b (k int, w int)")
    c.execute("INSERT INTO a VALUES (1, 10)")
    c.execute("INSERT INTO b VALUES (1, 100)")
    c.execute("CREATE MATERIALIZED VIEW j AS SELECT a.v, x.w AS w1, y.w AS w2 FROM a, b x, b y WHERE a.k = x.k AND x.k = y.k")
    caps.clear()
    c.execute("INSERT INTO a VALUES (1, 11)")  # input 0's path alone
    c.execute("INSERT INTO b VALUES (1, 101)")  # the paths of inputs 1 and 2
    assert [n for n, _ in caps] == [1, 2]
    assert {cap for _, cap in caps} == {8}
    assert sorted(c.execute("SELECT * FROM j").rows) == sorted(
        (v, w1, w2) for v in (10, 11) for w1 in (100, 101) for w2 in (100, 101))


def test_gather_groups_at_a_floor_follows_neither_the_group_nor_its_batches():
    """`ops/topk.py::gather_groups` with a floor: a group's rows come back at
    the floor's capacity whichever batches of the arrangement hold them and
    however many they are (up to the floor), the same rows as without it."""
    from materialize_tpu.arrangement import Arrangement, arrange_batch
    from materialize_tpu.ops.topk import distinct_keys, gather_groups
    from materialize_tpu.repr import UpdateBatch

    def keyed(rows, t):
        cols = tuple(np.array([r[i] for r in rows], dtype=np.int64) for i in range(2))
        return arrange_batch(UpdateBatch.build((), cols, [t] * len(rows), [1] * len(rows)), (0,))

    arr, caps = Arrangement(key_cols=(0,)), []
    for t, n in enumerate([5, 3, 20, 1]):
        arr.insert(keyed([(1, 100 * t + i) for i in range(n)] + [(2, t)], t), already_keyed=True)
        probes = distinct_keys(keyed([(1, 0)], t))
        got = gather_groups(probes, arr.batches, t, (np.dtype(np.int64),) * 2, floor=64)
        want = gather_groups(probes, arr.batches, t, (np.dtype(np.int64),) * 2)
        assert sorted(got.to_rows()) == sorted(want.to_rows())
        assert len(got.to_rows()) == sum([5, 3, 20, 1][: t + 1])
        caps.append(got.cap)
    assert caps == [64] * 4


def test_the_distinct_keys_table_only_grows():
    """A DISTINCT's table keeps the largest capacity it has held, and never
    less than MIN_TABLE: a count that moves back and forth across a power of
    two (Q18's IN holds some 57 keys at SF1, near 64) would otherwise give
    its step a new shape each time."""
    c = Coordinator()
    c.execute("CREATE TABLE t (x int)")
    c.execute("CREATE MATERIALIZED VIEW d AS SELECT DISTINCT x FROM t")
    c.execute("INSERT INTO t VALUES " + ",".join(f"({i})" for i in range(9)))
    (node,) = _nodes(c, runtime.DistinctNode)
    assert node.state.cap == runtime.MIN_TABLE == 128
    c.execute("INSERT INTO t VALUES " + ",".join(f"({i})" for i in range(9, 129)))
    assert node.state.cap == 256
    c.execute("DELETE FROM t WHERE x < 3")
    assert node.state.cap == 256
    assert sorted(c.execute("SELECT * FROM d").rows) == [(i,) for i in range(3, 129)]
