"""Shared arrangements (PR 9): cross-dataflow trace reuse with reader-held
compaction.

The contract under test (arrangement/trace_manager.py): N dataflows over the
same collection share ONE arrangement per (collection id, key columns); each
reader registers a since hold; compaction only advances to the minimum live
hold; DROP releases holds (re-arming compaction) and deletes reader-less
traces; a failed CREATE rolls its exports/holds back exactly. The canonical
differential check renders the same multi-MV workload with the TraceManager
force-disabled vs enabled (`enable_arrangement_sharing`) and demands
byte-identical peeks AND byte-identical durable MV shards.
"""

import pytest

from materialize_tpu.adapter import Coordinator
from materialize_tpu.arrangement import Arrangement, TraceManager


# -- unit: hold ledger on the spine ------------------------------------------


def test_hold_ledger_min_over_live_holds():
    arr = Arrangement(key_cols=(0,))
    arr.hold("a", 5)
    arr.hold("b", 10)
    arr.allow_compaction(20)
    assert arr.since == 5  # pinned by the slowest reader
    arr.release_hold("a")
    assert arr.since == 10  # re-armed to the next-slowest hold
    # releasing a reader that holds nothing must not move since
    arr.release_hold("ghost")
    assert arr.since == 10
    arr.downgrade_hold("b", 15)
    arr.allow_compaction(99)
    assert arr.since == 15
    arr.release_hold("b")
    assert not arr.holds and arr.since == 15


def test_trace_manager_export_import_release():
    tm = TraceManager()
    tr, imported = tm.get_arrangement("u1", (0,), reader="mv_a", as_of=3)
    assert tr is not None and not imported
    tr2, imported2 = tm.get_arrangement("u1", (0,), reader="mv_b", as_of=7)
    assert tr2 is tr and imported2
    assert tm.stats == {"exports": 1, "imports": 1, "peek_since_misses": 0}
    assert tr.holds == {"mv_a": 3, "mv_b": 7}
    # a peek whose as_of predates the shared since is refused (partial read)
    tr.arr.compact(5)
    got, _ = tm.get_arrangement("u1", (0,), reader="peek", as_of=4, export=False)
    assert got is None and tm.stats["peek_since_misses"] == 1
    # export=False never creates
    got, _ = tm.get_arrangement("u2", (0,), reader="peek", as_of=4, export=False)
    assert got is None and tm.trace_count() == 1
    # DROP of the last reader deletes the trace (nobody would step it)
    tm.release("mv_a")
    assert tm.trace_count() == 1
    tm.release("mv_b")
    assert tm.trace_count() == 0


def test_rollback_install_is_exact_undo():
    tm = TraceManager()
    tm.get_arrangement("u1", (0,), reader="mv_a", as_of=2)

    def snap():
        return (
            {k: (t.exporter, dict(t.holds), t.since) for k, t in tm.traces.items()},
            dict(tm.stats),
        )

    before = snap()
    # a failed install that imported u1 and exported u2
    tm.get_arrangement("u1", (0,), reader="mv_b", as_of=9)
    tm.get_arrangement("u2", (1,), reader="mv_b", as_of=9)
    tm.rollback_install("mv_b")
    assert snap() == before


# -- per-level join output caps ---------------------------------------------


def test_join_caps_taper_and_provable_bound():
    from materialize_tpu.dataflow.fused import FusedCaps

    caps = FusedCaps(join_out=1 << 12, levels=3, cap_ratio=4)
    jc = caps.join_caps(64, (256, 1024, 16384))
    # tapered small→large, never above join_out, never below the probe width
    assert jc[-1] == 1 << 12
    assert list(jc) == sorted(jc)
    assert all(64 <= c <= 1 << 12 for c in jc)
    # cap_ratio=1 restores the uniform pre-PR-9 caps
    uni = FusedCaps(join_out=1 << 12, levels=3, cap_ratio=1)
    assert uni.join_caps(1 << 12, (256, 1024, 16384)) == (1 << 12,) * 3
    # the provable pair bound probe.cap × level.cap wins where tighter
    tiny = caps.join_caps(8, (4, 8, 16384))
    assert tiny[0] <= 8 * 4


# -- the canonical multi-MV workload, shared vs private -----------------------


_MVS = [
    ("mv_join", "SELECT t1.k AS k, a, b FROM t1, t2 WHERE t1.k = t2.k"),
    ("mv_sum", "SELECT sum(a + b) AS s FROM t1, t2 WHERE t1.k = t2.k"),
    ("mv_grp", "SELECT t1.k AS k, sum(b) AS sb FROM t1, t2 WHERE t1.k = t2.k GROUP BY t1.k"),
]


def _run_workload(data_dir: str, sharing: bool):
    """2 sources, 3 MVs sharing a join input, insert+delete churn, one DROP
    mid-run. Returns (peek rows per query, net durable shard contents per
    surviving MV, the coordinator)."""
    c = Coordinator(data_dir=data_dir)
    if not sharing:
        c.execute("ALTER SYSTEM SET enable_arrangement_sharing = false")
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    c.execute("INSERT INTO t1 VALUES (1, 10), (2, 20), (3, 30)")
    c.execute("INSERT INTO t2 VALUES (1, 100), (2, 200), (2, 201)")
    for name, q in _MVS:
        c.execute(f"CREATE MATERIALIZED VIEW {name} AS {q}")
    mv_gids = {name: c.catalog.get(name).global_id for name, _q in _MVS}
    # churn: inserts, deletes, a k that annihilates, and post-DROP ticks
    c.execute("INSERT INTO t1 VALUES (4, 40)")
    c.execute("INSERT INTO t2 VALUES (4, 400), (3, 300)")
    c.execute("DELETE FROM t2 WHERE b = 201")
    c.execute("INSERT INTO t1 VALUES (5, 50)")
    c.execute("DROP MATERIALIZED VIEW mv_sum")
    c.execute("DELETE FROM t1 WHERE k = 2")
    c.execute("INSERT INTO t2 VALUES (5, 500), (1, 101)")
    c.execute("INSERT INTO t1 VALUES (1, 11)")
    peeks = {
        "mv_join": sorted(c.execute("SELECT * FROM mv_join").rows),
        "mv_grp": sorted(c.execute("SELECT * FROM mv_grp").rows),
        # ephemeral peek dataflow over the same shared join input
        "adhoc": sorted(
            c.execute("SELECT a, b FROM t1, t2 WHERE t1.k = t2.k").rows
        ),
    }
    shards = {}
    for name in ("mv_join", "mv_grp"):
        gid = c.catalog.get(name).global_id
        m = c._shard(gid)
        _seq, state = m.fetch_state()
        net: dict = {}
        for cols in m.snapshot(state.upper - 1):
            ncols = len([k for k in cols if k.startswith("c")])
            for row in zip(*([cols[f"c{i}"] for i in range(ncols)] + [cols["diffs"]])):
                key = tuple(int(v) for v in row[:-1])
                net[key] = net.get(key, 0) + int(row[-1])
        shards[name] = {k: v for k, v in net.items() if v != 0}
    return peeks, shards, c, mv_gids


def test_shared_vs_private_differential(tmp_path):
    peeks_off, shards_off, c_off, _g = _run_workload(
        str(tmp_path / "off"), sharing=False
    )
    assert c_off.trace_manager.stats["exports"] == 0  # force-disable really disables
    peeks_on, shards_on, c_on, gids_on = _run_workload(
        str(tmp_path / "on"), sharing=True
    )
    assert peeks_on == peeks_off
    assert shards_on == shards_off
    # sharing actually happened: later MVs (and the ad-hoc peek) imported
    tm = c_on.trace_manager
    assert tm.stats["exports"] > 0 and tm.stats["imports"] > 0
    # the DROP released mv_sum's holds everywhere
    for _key, tr in tm.traces.items():
        assert gids_on["mv_sum"] not in tr.holds


def test_drop_releases_holds_and_deletes_readerless_traces():
    c = Coordinator()
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    c.execute("INSERT INTO t1 VALUES (1, 10)")
    c.execute("INSERT INTO t2 VALUES (1, 100)")
    c.execute(
        "CREATE MATERIALIZED VIEW m1 AS SELECT a, b FROM t1, t2 WHERE t1.k = t2.k"
    )
    c.execute(
        "CREATE MATERIALIZED VIEW m2 AS SELECT a + b AS ab FROM t1, t2 WHERE t1.k = t2.k"
    )
    tm = c.trace_manager
    g1 = c.catalog.get("m1").global_id
    g2 = c.catalog.get("m2").global_id
    assert tm.trace_count() > 0
    shared = [tr for tr in tm.traces.values() if {g1, g2} <= set(tr.holds)]
    assert shared, "both MVs should hold the same join-input traces"
    c.execute("DROP MATERIALIZED VIEW m2")
    assert all(g2 not in tr.holds for tr in tm.traces.values())
    assert any(g1 in tr.holds for tr in tm.traces.values())
    c.execute("DROP MATERIALIZED VIEW m1")
    assert tm.trace_count() == 0
    # and the engine still serves fresh dataflows afterwards
    c.execute(
        "CREATE MATERIALIZED VIEW m3 AS SELECT b FROM t1, t2 WHERE t1.k = t2.k"
    )
    assert c.execute("SELECT * FROM m3").rows == [(100,)]


def test_failed_create_rolls_back_trace_exports(tmp_path):
    c = Coordinator(data_dir=str(tmp_path / "d"))
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    c.execute("INSERT INTO t1 VALUES (1, 10), (2, 20)")
    c.execute("INSERT INTO t2 VALUES (1, 100)")
    c.execute(
        "CREATE MATERIALIZED VIEW m1 AS SELECT a, b FROM t1, t2 WHERE t1.k = t2.k"
    )
    tm = c.trace_manager

    def snap():
        return (
            {k: (t.exporter, dict(t.holds)) for k, t in tm.traces.items()},
            dict(tm.stats),
        )

    before = snap()
    real = c._persist_batches

    def boom(*a, **kw):
        raise RuntimeError("injected: MV hydration persist failed")

    c._persist_batches = boom
    with pytest.raises(RuntimeError, match="injected"):
        c.execute(
            "CREATE MATERIALIZED VIEW m2 AS "
            "SELECT sum(b) AS s FROM t1, t2 WHERE t1.k = t2.k"
        )
    c._persist_batches = real
    assert snap() == before, "failed CREATE must leave the TraceManager untouched"
    assert "m2" not in c.catalog.items
    # the retry succeeds and reads correctly — no stale export shadowed it
    c.execute(
        "CREATE MATERIALIZED VIEW m2 AS "
        "SELECT sum(b) AS s FROM t1, t2 WHERE t1.k = t2.k"
    )
    assert c.execute("SELECT * FROM m2").rows == [(100,)]
    c.execute("INSERT INTO t2 VALUES (2, 200)")
    assert c.execute("SELECT * FROM m2").rows == [(300,)]


def test_fused_render_yields_to_host_import():
    """A fused dataflow cannot import a host spine: when a shared trace it
    would read exists, FusedDataflow declares FusedUnsupported and the host
    renderer takes the sharing win — without breaking the fused fallback."""
    c = Coordinator()
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    c.execute("INSERT INTO t1 VALUES (1, 10)")
    c.execute("INSERT INTO t2 VALUES (1, 100)")
    c.execute(
        "CREATE MATERIALIZED VIEW m1 AS SELECT a, b FROM t1, t2 WHERE t1.k = t2.k"
    )
    assert c.trace_manager.trace_count() > 0
    c.execute("ALTER SYSTEM SET enable_fused_render = true")
    imports_before = c.trace_manager.stats["imports"]
    c.execute(
        "CREATE MATERIALIZED VIEW m2 AS SELECT b, a FROM t1, t2 WHERE t1.k = t2.k"
    )
    from materialize_tpu.dataflow.runtime import Dataflow

    df2 = next(df for gid, df, _s in c.dataflows if gid == c.catalog.get("m2").global_id)
    assert isinstance(df2, Dataflow), "fused render must yield to the host import"
    assert c.trace_manager.stats["imports"] > imports_before
    c.execute("INSERT INTO t2 VALUES (1, 101)")
    assert sorted(c.execute("SELECT * FROM m2").rows) == [(100, 10), (101, 10)]


def test_introspection_and_metrics_surface_sharing():
    c = Coordinator()
    c.execute("CREATE TABLE t1 (k int, a int)")
    c.execute("CREATE TABLE t2 (k int, b int)")
    c.execute("INSERT INTO t1 VALUES (1, 10)")
    c.execute("INSERT INTO t2 VALUES (1, 100)")
    c.execute(
        "CREATE MATERIALIZED VIEW m1 AS SELECT a, b FROM t1, t2 WHERE t1.k = t2.k"
    )
    c.execute(
        "CREATE MATERIALIZED VIEW m2 AS SELECT b FROM t1, t2 WHERE t1.k = t2.k"
    )
    rows = c.execute(
        "SELECT trace_key, exporter, readers FROM mz_arrangement_sharing"
    ).rows
    assert rows and any(r[2] >= 2 for r in rows), rows
    g1 = c.catalog.get("m1").global_id
    assert any(r[1] == g1 for r in rows)  # m1 exported the traces
    assert 0.0 < c.trace_manager.import_hit_rate() <= 1.0


# -- scaling: the K-MV sharing win -------------------------------------------


@pytest.mark.smoke
def test_shared_mv_scaling_smoke():
    """Installing 8 identical-source MVs on the shared path must cost
    ~O(sources), not O(8 × sources): the 8 views hold the 1-MV run's traces
    and no more (each is maintained once per tick, by the first reader to
    step it — SharedTrace.offer), and arrangement bytes stay near the 1-MV
    footprint."""
    from benchmarks.bench_shared_mvs import run_scenario

    rows, ticks = 1000, 3
    r1 = run_scenario(1, True, rows=rows, ticks=ticks)
    r8 = run_scenario(8, True, rows=rows, ticks=ticks)
    # inputs are arranged ONCE: views 2-8 import every trace view 1 exported
    assert r8["exports"] == r1["exports"] > 0, (r1, r8)
    assert r8["imports"] == 7 * r1["exports"], (r1, r8)
    assert r8["arrangement_bytes"] < 2.0 * r1["arrangement_bytes"], (
        r1["arrangement_bytes"],
        r8["arrangement_bytes"],
    )
