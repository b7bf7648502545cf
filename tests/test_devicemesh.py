"""Device-collective exchange plane (parallel/devicemesh/, PR 16).

Runs on the 8-device virtual CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8``), the stand-in for real
multi-chip ICI. Fast tier: the routing invariant (device destinations ==
host destinations for every dtype mix), exchange-mode resolution, dyncfg
validation, and the host force-disable.
Slow tier: the Q3 SQL differential across {host, single fused, 8-device
device mesh} with durable MV shard comparison, the mid-run
``exchange_backend`` flip, the zero-host-transfer guard, and the
device-mesh-under-host-mesh composition (2 proc x 4 devices).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from materialize_tpu.parallel import make_mesh
from materialize_tpu.parallel.devicemesh import (
    EXCHANGE_MODES,
    device_mesh_rows,
    exchange,
    form_device_mesh,
    local_device_count,
    mesh_jit,
    resolve_exchange_mesh,
)


def _counter(name, **labels):
    """Current value of one labelled sample in the process metrics registry."""
    from materialize_tpu.obs import metrics as obs_metrics

    want = tuple(sorted(labels.items()))
    for fam, _kind, _help, samples in obs_metrics.REGISTRY.snapshot():
        if fam != name:
            continue
        for lbls, v in samples:
            if tuple(sorted(lbls)) == want:
                return v
    return 0


# -- the routing invariant: device == host, every dtype mix -------------------

DTYPE_MIXES = [
    ("int64",),
    ("int32",),
    ("float32",),
    ("bool",),
    ("int64", "float32"),
    ("int32", "bool", "float32"),
    ("int64", "int64", "float32"),
]


@pytest.mark.parametrize("n_workers", [1, 2, 3, 8])
@pytest.mark.parametrize("mix", DTYPE_MIXES, ids=["_".join(m) for m in DTYPE_MIXES])
def test_route_dests_device_matches_host(mix, n_workers):
    """The ONE routing rule (parallel/routing.route_mod): destinations the
    device plane computes through `route_dest` are identical to the
    host plane's netexchange.route_dests for every supported dtype mix —
    including the float canonicalizations (NaN = the float NULL sentinel,
    -0.0 == 0.0) that make an insert and its retraction co-locate even when
    one is routed by each plane."""
    from materialize_tpu.parallel.devicemesh.exchange import route_dest
    from materialize_tpu.parallel.netexchange import route_dests
    from materialize_tpu.repr.hashing import hash_columns

    rng = np.random.default_rng(abs(hash((mix, n_workers))) % (2**32))
    n = 257
    cols = []
    for dt in mix:
        if dt == "bool":
            cols.append(rng.integers(0, 2, n).astype(np.bool_))
        elif dt == "float32":
            f = rng.normal(size=n).astype(np.float32)
            f[:4] = [np.nan, -0.0, np.inf, -np.inf]
            cols.append(f)
        else:
            cols.append(rng.integers(-(2**40), 2**40, n).astype(dt))
    host_cols = {f"c{i}": c for i, c in enumerate(cols)}
    host_cols["times"] = np.zeros(n, dtype=np.uint64)
    host_cols["diffs"] = np.ones(n, dtype=np.int64)

    # whole-row routing, all-columns-by-index, and single-column routing
    for key_cols in (None, tuple(range(len(mix))), (0,)):
        host = route_dests(host_cols, key_cols, n_workers)
        picked = cols if key_cols is None else [cols[i] for i in key_cols]
        hashes = hash_columns(tuple(jnp.asarray(c) for c in picked))
        dev = route_dest(hashes, n_workers)
        assert (np.asarray(dev) == host).all(), (mix, n_workers, key_cols)
        assert (host >= 0).all() and (host < n_workers).all()
    # keyless groups co-locate on worker 0 in both planes
    assert (route_dests(host_cols, (), n_workers) == 0).all()


# -- mode resolution + introspection rows -------------------------------------


def test_resolve_exchange_mesh_modes():
    assert EXCHANGE_MODES == ("auto", "host", "device")
    # host: force-disable, even when a mesh is on offer
    assert resolve_exchange_mesh("host") is None
    assert resolve_exchange_mesh("host", make_mesh(4)) is None
    # device: the given mesh, or one formed over every local device
    m2 = make_mesh(4)
    assert resolve_exchange_mesh("device", m2) is m2
    m = resolve_exchange_mesh("device")
    assert m is not None and int(m.shape["workers"]) == local_device_count()
    # auto: an explicit mesh opts in; bare forced-CPU devices do not — the
    # virtual mesh is a test harness, not a performance win (decision table
    # in doc/DEVICE_MESH.md)
    assert resolve_exchange_mesh("auto", m2) is m2
    assert resolve_exchange_mesh("auto") is None
    with pytest.raises(ValueError, match="exchange_backend"):
        resolve_exchange_mesh("chip")


def test_device_mesh_rows():
    mesh = form_device_mesh(4)
    rows = device_mesh_rows(mesh, "device")
    assert len(rows) == local_device_count() == 8
    assert [r[0] for r in rows] == list(range(8))  # position per local device
    member = [r for r in rows if r[5]]
    assert len(member) == 4
    for _pos, dev, plat, axis, axis_size, _in, backend in member:
        assert axis == "workers" and axis_size == 4
        assert plat in dev and backend == "device"
    # non-members still report the mesh axis (the table answers "what could
    # a mesh use here"), distinguished by the membership flag alone
    assert all(r[3] == "workers" and r[4] == 4 for r in rows if not r[5])
    assert len(rows) - len(member) == 4


@pytest.mark.smoke
def test_mesh_jit_exchange_roundtrip_and_metrics():
    """mesh_jit is the one program-build entry point: the exchange delivers
    every live row to its hash-owning device and stamps the
    mzt_device_exchange_* program metrics."""
    from jax.sharding import PartitionSpec as P

    from materialize_tpu.arrangement import arrange_batch
    from materialize_tpu.repr import PAD_HASH, UpdateBatch

    mesh = form_device_mesh(2)
    k = np.arange(32, dtype=np.int64)
    batch = UpdateBatch.build(
        (), (k, k * 3), np.zeros(32), np.ones(32, dtype=np.int64)
    )
    keyed = arrange_batch(batch, (0,))

    def go(b):
        out, over = exchange(b, "workers", 2, 32)
        return out, over.reshape((1,))

    programs0 = _counter("mzt_device_exchange_programs_total", axis="workers")
    f = mesh_jit(go, mesh, in_specs=(P("workers"),), out_specs=(P("workers"), P("workers")))
    assert _counter("mzt_device_exchange_programs_total", axis="workers") == programs0 + 1
    assert _counter("mzt_device_exchange_mesh_devices", axis="workers") == 2

    out, over = f(keyed)
    assert not bool(np.asarray(over).any())
    hashes = np.asarray(out.hashes)
    live = (hashes != np.uint64(PAD_HASH)) & (np.asarray(out.diffs) != 0)
    assert int(live.sum()) == 32  # nothing lost
    per_dev = hashes.reshape(2, -1)
    live_dev = live.reshape(2, -1)
    for d in range(2):
        assert (per_dev[d][live_dev[d]] % 2 == d).all()


# -- adapter surface: dyncfg validation + host force-disable ------------------


def test_exchange_backend_dyncfg_validated():
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.sql.plan import PlanError

    c = Coordinator()
    assert c.execute("SHOW exchange_backend").rows == [("auto",)]
    for mode in EXCHANGE_MODES:
        c.execute(f"ALTER SYSTEM SET exchange_backend = {mode}")
        assert c.execute("SHOW exchange_backend").rows == [(mode,)]
    with pytest.raises(PlanError, match="exchange_backend"):
        c.execute("ALTER SYSTEM SET exchange_backend = chip")
    # the rejected value never landed
    assert c.execute("SHOW exchange_backend").rows == [("device",)]


def test_exchange_backend_host_is_inert_with_mesh():
    """The force-disable escape hatch: a coordinator HOLDING a device mesh
    still renders single-shard fused dataflows under exchange_backend=host,
    and the results match a plain host coordinator."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.dataflow.fused import FusedDataflow

    host = Coordinator()
    c = Coordinator(mesh=make_mesh(4))
    c.execute("ALTER SYSTEM SET enable_fused_render = true")
    c.execute("ALTER SYSTEM SET exchange_backend = host")
    cs = (host, c)
    for cc in cs:
        cc.execute("CREATE TABLE t (a int, b int)")
        cc.execute("INSERT INTO t VALUES (1, 2), (3, 4), (1, 6)")
        cc.execute(
            "CREATE MATERIALIZED VIEW mv AS SELECT a, sum(b) FROM t GROUP BY a"
        )
    dfs = [df for _g, df, _s in c.dataflows]
    assert dfs and isinstance(dfs[0], FusedDataflow)
    assert dfs[0].n_shards == 1  # the mesh was NOT used
    for cc in cs:
        cc.execute("DELETE FROM t WHERE a = 3")
    a, b = (sorted(cc.execute("SELECT * FROM mv").rows) for cc in cs)
    assert a == b == [(1, 8)]


# -- slow tier: whole-engine differentials on the 8-device mesh ---------------


def _mv_shard_rows(c, name):
    """Consolidated durable contents of an MV's persist shard."""
    gid = c.catalog.items[name].global_id
    m = c.shards[gid]
    _seq, st = m.fetch_state()
    acc: dict = {}
    for cols in m.snapshot(st.upper - 1):
        ncols = len([k for k in cols if k.startswith("c")])
        vals = [cols[f"c{i}"] for i in range(ncols)]
        for j in range(len(cols["times"])):
            row = tuple(v[j].item() for v in vals)
            acc[row] = acc.get(row, 0) + int(cols["diffs"][j])
    return {k: v for k, v in acc.items() if v != 0}


@pytest.mark.smoke
@pytest.mark.slow
def test_device_mesh_sql_differential(tmp_path):
    """Q3-shape MV, byte-identical across {host runtime, single-device
    fused, 8-device device mesh}: seeded hydration + 8 insert/delete churn
    ticks, checked after every tick, INCLUDING the durable MV shard
    contents. Also pins the introspection surface: mz_device_mesh rows and
    the mzt_device_exchange_* metric families."""
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.dataflow.fused import FusedDataflow

    host = Coordinator(data_dir=str(tmp_path / "host"))
    single = Coordinator(data_dir=str(tmp_path / "single"))
    single.execute("ALTER SYSTEM SET enable_fused_render = true")
    dev = Coordinator(data_dir=str(tmp_path / "dev"))
    dev.execute("ALTER SYSTEM SET enable_fused_render = true")
    dev.execute("ALTER SYSTEM SET exchange_backend = device")
    cs = (host, single, dev)

    def both(sql):
        return [c.execute(sql) for c in cs]

    def check(sql):
        r = both(sql)
        assert sorted(r[0].rows) == sorted(r[1].rows) == sorted(r[2].rows), (
            sql, r[0].rows, r[1].rows, r[2].rows,
        )
        return r[0].rows

    both("CREATE TABLE c (ck int, seg int)")
    both("CREATE TABLE o (ok int, ck int, od int)")
    both("CREATE TABLE l (lk int, price int)")
    # seeded hydration BEFORE the MV: the device plane must survive a
    # snapshot-sized first tick, not just trickle inserts
    import random

    rng = random.Random(16)
    for i in range(6):
        both(f"INSERT INTO c VALUES ({i}, {rng.randrange(2)})")
        both(f"INSERT INTO o VALUES ({i * 10}, {rng.randrange(6)}, {rng.randrange(100)})")
        both(f"INSERT INTO l VALUES ({rng.randrange(6) * 10}, {rng.randrange(500)})")
    both(
        "CREATE MATERIALIZED VIEW q3 AS SELECT o.ok, sum(l.price), count(*) "
        "FROM c, o, l WHERE c.ck = o.ck AND o.ok = l.lk AND c.seg = 1 "
        "AND o.od < 50 GROUP BY o.ok"
    )
    # the device coordinator must actually be running an 8-shard mesh tick
    dfs = [df for _g, df, _s in dev.dataflows]
    assert dfs and isinstance(dfs[0], FusedDataflow) and dfs[0].n_shards == 8
    check("SELECT * FROM q3")

    # introspection: every local device is listed, mesh members flagged
    rows = dev.execute("SELECT * FROM mz_device_mesh").rows
    assert len(rows) == 8
    assert all(r[3] == "workers" and r[4] == 8 and r[5] for r in rows)
    assert {r[6] for r in rows} == {"device"}
    # ...and the exchange metrics are live on the scrape surface
    import threading

    from materialize_tpu.frontend.http_server import metrics_text

    text = metrics_text(dev, threading.Lock())
    for fam in (
        "mzt_device_exchange_programs_total",
        "mzt_device_exchange_mesh_devices",
        "mzt_device_exchange_retries_total",
    ):
        assert f"# TYPE {fam} " in text, fam

    # 8 seeded churn ticks: inserts + deletes through the mesh exchange
    for i in range(8):
        both(f"INSERT INTO o VALUES ({rng.randrange(8) * 10}, {rng.randrange(6)}, {rng.randrange(100)})")
        both(f"INSERT INTO l VALUES ({rng.randrange(8) * 10}, {rng.randrange(500)})")
        if i % 2:
            both(f"DELETE FROM l WHERE lk = {rng.randrange(8) * 10}")
        else:
            both(f"DELETE FROM o WHERE ck = {rng.randrange(6)}")
        check("SELECT * FROM q3")

    # the DURABLE record agrees: all three coordinators persisted the same
    # consolidated MV shard contents
    want = _mv_shard_rows(host, "q3")
    assert want  # the churn left real rows behind
    assert _mv_shard_rows(single, "q3") == want
    assert _mv_shard_rows(dev, "q3") == want


@pytest.mark.slow
def test_exchange_backend_flip_mid_run():
    """ALTER SYSTEM SET exchange_backend applies at the NEXT render: flipping
    mid-run never disturbs running dataflows, and new MVs pick up the new
    plane — device -> host -> device, all byte-identical to a host oracle."""
    from materialize_tpu.adapter import Coordinator

    host = Coordinator()
    c = Coordinator()
    c.execute("ALTER SYSTEM SET enable_fused_render = true")
    c.execute("ALTER SYSTEM SET exchange_backend = device")
    cs = (host, c)
    for cc in cs:
        cc.execute("CREATE TABLE t (g int, v int)")
        cc.execute("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30)")
        cc.execute("CREATE MATERIALIZED VIEW m1 AS SELECT g, sum(v) FROM t GROUP BY g")
    assert [df.n_shards for _g, df, _s in c.dataflows] == [8]

    # flip to host mid-run: m1 keeps ticking on the mesh, m2 renders host
    for cc in cs:
        cc.execute("INSERT INTO t VALUES (1, 5)")
    c.execute("ALTER SYSTEM SET exchange_backend = host")
    for cc in cs:
        cc.execute("INSERT INTO t VALUES (2, -20), (4, 40)")
        cc.execute("CREATE MATERIALIZED VIEW m2 AS SELECT g, count(*) FROM t GROUP BY g")
    n_shards = [df.n_shards for _g, df, _s in c.dataflows]
    assert n_shards[0] == 8 and n_shards[-1] == 1

    # and back: the flip is symmetric
    c.execute("ALTER SYSTEM SET exchange_backend = device")
    for cc in cs:
        cc.execute("CREATE MATERIALIZED VIEW m3 AS SELECT sum(v) FROM t")
        cc.execute("DELETE FROM t WHERE g = 3")
    n_shards = [df.n_shards for _g, df, _s in c.dataflows]
    assert n_shards[-1] == 8
    for mv in ("m1", "m2", "m3"):
        a, b = (sorted(cc.execute(f"SELECT * FROM {mv}").rows) for cc in cs)
        assert a == b, (mv, a, b)


@pytest.mark.slow
def test_device_tick_makes_zero_host_transfers(device_tick_guard):
    """The jitted device-mesh tick touches the host ZERO times once warm:
    with both transfer_guard directions set to disallow around the tick,
    insert + delete churn still works and the results stay correct."""
    from materialize_tpu.adapter import Coordinator

    c = Coordinator()
    c.execute("ALTER SYSTEM SET enable_fused_render = true")
    c.execute("ALTER SYSTEM SET exchange_backend = device")
    c.execute("CREATE TABLE t (a int, b int)")
    c.execute("INSERT INTO t VALUES (1, 2), (3, 4)")
    c.execute("CREATE MATERIALIZED VIEW mv AS SELECT a, sum(b) FROM t GROUP BY a")
    df = [d for _g, d, _s in c.dataflows][0]
    assert df.n_shards == 8
    c.execute("INSERT INTO t VALUES (5, 6)")  # warm: compile transfers happen here
    device_tick_guard(df)
    c.execute("INSERT INTO t VALUES (7, 8)")
    c.execute("DELETE FROM t WHERE a = 1")
    assert sorted(c.execute("SELECT * FROM mv").rows) == [(3, 4), (5, 6), (7, 8)]


@pytest.mark.slow
def test_q3_trimodal_controller_differential(tmp_path):
    """The ISSUE's three deployment shapes, same TPC-H Q3, same writes:
    {1-device single worker, 8-device device mesh, 2-process host mesh}
    peek byte-identical through hydration + 8 insert/delete churn ticks.
    The device leg runs INSIDE a clusterd subprocess (CreateInstance config
    snapshot carries exchange_backend=device; the subprocess forms its own
    8-device mesh), the host-mesh leg is the real 2-process WorkerMesh."""
    from materialize_tpu.cluster import (
        ComputeController,
        ShardedComputeController,
    )
    from materialize_tpu.models import tpch
    from materialize_tpu.orchestrator import ProcessOrchestrator
    from materialize_tpu.persist import FileBlob, FileConsensus, ShardMachine

    from tests.test_sharded_mesh import write_rows

    orch = ProcessOrchestrator(cpu=True)
    orch_dev = ProcessOrchestrator(cpu=True, devices_per_process=8)
    blob_path, cas_path = str(tmp_path / "blob"), str(tmp_path / "cas")
    blob, cas = FileBlob(blob_path), FileConsensus(cas_path)
    ctls = []
    try:
        customer = ShardMachine(blob, cas, "customer")
        orders = ShardMachine(blob, cas, "orders")
        lineitem = ShardMachine(blob, cas, "lineitem")

        single = ComputeController(
            orch.ensure_service("q3_single", scale=1), blob_path, cas_path, epoch=1
        )
        ctls.append(single)
        dev = ComputeController(
            orch_dev.ensure_service("q3_dev", scale=1), blob_path, cas_path,
            epoch=1,
            config={"enable_fused_render": True, "exchange_backend": "device"},
        )
        ctls.append(dev)
        addrs, mesh_addrs = orch.ensure_sharded_service("q3_mesh", 2, workers_per_process=2)
        mesh = ShardedComputeController(
            addrs, mesh_addrs, 2, blob_path, cas_path, epoch=1
        )
        ctls.append(mesh)

        src = {"customer": "customer", "orders": "orders", "lineitem": "lineitem"}
        for ctl in ctls:
            ctl.create_dataflow("q3", tpch.q3(), src, as_of=0)

        B, D = tpch.BUILDING, tpch.Q3_DATE
        # tick 1: seeded hydration spread across join keys
        write_rows(customer, 0, 1,
                   [(c, B if c % 2 else 0, 0, 1) for c in range(1, 9)], 3)
        write_rows(orders, 0, 1,
                   [(100 + o, (o % 8) + 1, D - 1 - (o % 3), o % 5, 1) for o in range(12)], 4)
        write_rows(lineitem, 0, 1,
                   [(100 + (li % 12), 1000 + li, li % 10, D + 1 + (li % 4), 1, li, 1)
                    for li in range(24)], 6)

        def check(to):
            for ctl in ctls:
                ctl.process_to(to)
            want = single.peek("q3", "idx_q3")
            assert dev.peek("q3", "idx_q3") == want, "device mesh diverged"
            assert mesh.peek("q3", "idx_q3") == want, "host mesh diverged"
            return want

        assert len(check(2)) > 0

        # 8 churn ticks: inserts plus exact retractions of earlier inserts
        o_up, l_up = 1, 1
        for t in range(2, 10):
            orow = (200 + t, (t % 8) + 1, D - 1 - (t % 3), t % 5, 1)
            write_rows(orders, o_up, t,
                       [orow] + ([(200 + t - 1, (t - 1) % 8 + 1, D - 1 - ((t - 1) % 3),
                                   (t - 1) % 5, -1)] if t % 2 == 0 and t > 2 else []),
                       4)
            o_up = t
            lrow = (100 + (t % 12), 5000 + t, t % 10, D + 2, 1, t, 1)
            write_rows(lineitem, l_up, t,
                       [lrow] + ([(100 + ((t - 1) % 12), 5000 + t - 1, (t - 1) % 10,
                                   D + 2, 1, t - 1, -1)] if t % 2 == 1 else []),
                       6)
            l_up = t
            check(t + 1)
    finally:
        for ctl in ctls:
            ctl.close()
        orch.shutdown()
        orch_dev.shutdown()


@pytest.mark.slow
def test_device_mesh_composes_with_host_mesh(tmp_path):
    """The two planes compose: 2 clusterd processes, each forming a 4-device
    intra-process device mesh (ProcessOrchestrator(devices_per_process=4) +
    exchange_backend=device in the CreateInstance config), replicating one
    instance under the host control plane — peeks match a plain host
    replica, and the replicas' shipped metrics prove the device mesh
    actually built programs in the subprocesses."""
    from materialize_tpu.cluster import ComputeController
    from materialize_tpu.models import auction
    from materialize_tpu.orchestrator import ProcessOrchestrator
    from materialize_tpu.persist import FileBlob, FileConsensus, ShardMachine

    from tests.test_sharded_mesh import write_rows

    orch = ProcessOrchestrator(cpu=True, devices_per_process=4)
    blob_path, cas_path = str(tmp_path / "blob"), str(tmp_path / "cas")
    blob, cas = FileBlob(blob_path), FileConsensus(cas_path)
    ctls = []
    try:
        bids = ShardMachine(blob, cas, "bids")
        dev = ComputeController(
            orch.ensure_service("dev", scale=2), blob_path, cas_path, epoch=1,
            config={"enable_fused_render": True, "exchange_backend": "device"},
        )
        ctls.append(dev)
        plain = ComputeController(
            orch.ensure_service("plain", scale=1), blob_path, cas_path, epoch=1
        )
        ctls.append(plain)
        for ctl in ctls:
            ctl.create_dataflow(
                "df1", auction.bids_sum_count(), {"bids": "bids"}, as_of=0
            )
        write_rows(bids, 0, 1, [(1, 7, 10, 100, 0, 1), (2, 8, 10, 250, 0, 1),
                                (3, 7, 11, 40, 0, 1)], 5)
        for ctl in ctls:
            ctl.process_to(2)
        want = plain.peek("df1", "idx_bids_sum")
        assert dev.peek("df1", "idx_bids_sum") == want and want
        # churn through the composed planes
        write_rows(bids, 2, 2, [(4, 9, 11, 60, 0, 1), (1, 7, 10, 100, 0, -1)], 5)
        for ctl in ctls:
            ctl.process_to(3)
        want = plain.peek("df1", "idx_bids_sum")
        assert dev.peek("df1", "idx_bids_sum") == want

        # the subprocesses really formed device meshes: their shipped metric
        # counters include built exchange programs on the workers axis
        built = 0
        for rep in dev.fetch_stats():
            for fam, _kind, _help, samples in rep.counters:
                if fam == "mzt_device_exchange_programs_total":
                    built += sum(v for _lbls, v in samples)
        assert built >= 1, "no device exchange program was built in any replica"
    finally:
        for ctl in ctls:
            ctl.close()
        orch.shutdown()
