"""The fixed-capacity head of an arrangement spine (arrangement/spine.py).

What the head must keep: the arrangement's contents (against a plain Python
multiset), the truncation bound, the two views of a shared trace. What it
must give: inserts and probes that ask XLA for no program once the head's
(T, d) shapes are met.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from materialize_tpu.arrangement import Arrangement, arrange_batch
from materialize_tpu.arrangement.spine import HEAD_RATIO
from materialize_tpu.arrangement.trace_manager import SharedTrace
from materialize_tpu.ops.join import join_against

from test_join import collect, mkbatch, oracle_join


def keyed(rows, tick):
    """rows: [((k, v), diff)] -> a batch keyed by column 0 at time `tick`."""
    ks = [r[0][0] for r in rows]
    vs = [r[0][1] for r in rows]
    return arrange_batch(
        mkbatch([ks, vs], [tick] * len(rows), [r[1] for r in rows]), (0,)
    )


def contents(batches, since):
    """Multiset {(data, max(time, since)): diff} of a list of batches."""
    acc = {}
    for b in batches:
        for data, t, d in b.to_rows():
            k = (data, max(t, since))
            acc[k] = acc.get(k, 0) + d
    return {k: v for k, v in acc.items() if v != 0}


class Reference:
    """The arrangement as a plain list of (data, time, diff)."""

    def __init__(self):
        self.rows = []

    def insert(self, rows, tick):
        self.rows += [(data, tick, diff) for data, diff in rows]

    def contents(self, since):
        acc = {}
        for data, t, d in self.rows:
            k = (data, max(t, since))
            acc[k] = acc.get(k, 0) + d
        return {k: v for k, v in acc.items() if v != 0}

    def live(self):
        acc = {}
        for data, _t, d in self.rows:
            acc[data] = acc.get(data, 0) + d
        return [data for data, d in acc.items() for _ in range(max(d, 0))]


def head_counter(kind):
    from materialize_tpu.obs.metrics import REGISTRY

    fams = {f.name: f for f in REGISTRY.families()}
    return sum(v for _l, v in fams[f"mzt_arrangement_head_{kind}_total"].samples)


def check_head(arr):
    """The bound that makes truncation to T safe without a device read."""
    if arr.head_bound:
        assert arr.head is arr.batches[-1]
        assert arr.head_bound <= arr.head.cap
        assert int(arr.head.count()) <= arr.head_bound


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_head_holds_the_reference_multiset(seed):
    """Random inserts and retractions with `since` advancing, full heads
    spilling, deltas too large for the head and deltas smaller than the
    head's own bucket: after every insert the arrangement holds what the
    reference holds, and a join over its batches is the reference join."""
    rng = np.random.default_rng(seed)
    arr, ref = Arrangement(key_cols=(0,)), Reference()
    sizes = [300] + [6] * 18 + [100, 12, 5, 5, 40, 5, 5] + [6] * 16
    counted = {k: head_counter(k) for k in ("merges", "spills", "bypass")}
    for tick, n in enumerate(sizes):
        live = ref.live()
        n_del = min(n // 3, len(live)) if tick else 0
        picks = rng.choice(len(live), size=n_del, replace=False) if n_del else []
        rows = [(live[i], -1) for i in picks]
        rows += [
            ((int(rng.integers(0, 12)), int(rng.integers(0, 1000))), 1)
            for _ in range(n - n_del)
        ]
        arr.insert(keyed(rows, tick), already_keyed=True)
        ref.insert(rows, tick)
        check_head(arr)
        if tick % 5 == 4:
            arr.compact(tick - 2)
        assert contents(arr.batches, arr.since) == ref.contents(arr.since)
        if tick % 4 == 0:
            probe_rows = [((int(k), 7), 1) for k in rng.integers(0, 12, 5)]
            got = collect(join_against(keyed(probe_rows, tick), arr.batches))
            want = oracle_join(
                [(data, tick, d) for data, d in probe_rows],
                [(data, t, d) for (data, t), d in contents(arr.batches, 0).items()],
                (0,), (0,),
            )
            assert got == want
    # the sequence met a merge, a full head, an oversize delta and a bulk load
    assert all(head_counter(k) > n for k, n in counted.items())
    arr.compact(len(sizes))
    final = {}
    for data in ref.live():
        final[data] = final.get(data, 0) + 1
    assert {data: d for data, _t, d in arr.rows_host()} == final


def test_head_is_spilled_before_the_bound_passes_its_capacity():
    """Every row of every delta live: the head fills to exactly T rows, the
    next delta spills it whole, and no row is ever truncated away."""
    arr = Arrangement(key_cols=(0,))
    arr.insert(keyed([((k, 0), 1) for k in range(200)], 0), already_keyed=True)
    assert arr.head is None  # the first batch is the spine's
    T = HEAD_RATIO * 8
    n = 200
    for tick in range(1, 2 * HEAD_RATIO + 2):
        arr.insert(
            keyed([((1000 + tick, v), 1) for v in range(8)], tick), already_keyed=True
        )
        n += 8
        check_head(arr)
        assert arr.head.cap == T
        # delta number HEAD_RATIO + 1 (and 2 * HEAD_RATIO + 1) meets a full head
        fills = (tick - 1) % HEAD_RATIO + 1
        assert arr.head_bound == 8 * fills
        assert int(arr.head.count()) == 8 * fills
        assert arr.count() == n
    # two full heads went to the spine at their own capacity; the first met
    # the geometric rule there (256 + T -> 512), the second did not
    assert [b.cap for b in arr.batches] == [512, T, T]


def test_oversize_and_first_deltas_go_to_the_spine():
    arr = Arrangement(key_cols=(0,))
    arr.insert(keyed([((k, 0), 1) for k in range(300)], 0), already_keyed=True)
    arr.insert(keyed([((1, v), 1) for v in range(8)], 1), already_keyed=True)
    assert arr.head is not None and arr.head.cap == HEAD_RATIO * 8
    # a delta whose bucket exceeds T/2: the head is spilled first (order kept)
    arr.insert(keyed([((2, v), 1) for v in range(100)], 2), already_keyed=True)
    assert arr.head is None
    assert arr.count() == 408
    # a delta whose head would outgrow the spine it fronts is a bulk load
    arr.insert(keyed([((3, v), 1) for v in range(200)], 3), already_keyed=True)
    assert arr.head is None
    # and rebucket sees the head as one more batch
    arr.insert(keyed([((4, v), 1) for v in range(8)], 4), already_keyed=True)
    assert arr.head is not None
    arr.rebucket()
    assert arr.head is None and arr.count() == 616
    # a table filled row by row: the smallest head there is is always allowed
    small = Arrangement(key_cols=(0,))
    small.insert(keyed([((1, 1), 1)], 0), already_keyed=True)
    small.insert(keyed([((2, 1), 1)], 1), already_keyed=True)
    assert [b.cap for b in small.batches] == [8, HEAD_RATIO * 8]
    assert small.head_bound == 1 and small.count() == 2
    # a delta is sized by its rows, not by the capacity its producer left it
    # at, and a delta with no rows is not inserted
    small.insert(keyed([((3, 1), 1), ((4, 1), 1)], 2).with_capacity(1024), already_keyed=True)
    small.insert(keyed([((9, 9), 1), ((9, 9), -1)], 3), already_keyed=True)
    assert [b.cap for b in small.batches] == [8, HEAD_RATIO * 8]
    assert small.head_bound == 3 and small.count() == 4


def test_inserts_and_probes_build_no_program_once_the_head_is_met(programs_built):
    """One bulk insert and two delta inserts of one bucket meet every shape;
    the next 12 inserts and probes ask XLA for nothing. (At the parent the
    spine walked d, 2d, 4d, ... and every step built merge and join programs.)"""
    arr = Arrangement(key_cols=(0,))
    ticks = range(1, 15)
    bulk = keyed([((k, 0), 1) for k in range(200)], 0)
    # every delta: 5 rows of fresh keys; the first also 3 rows of key 0, so a
    # probe of keys 0 and 3 meets the bulk batch and the head while it fills
    # (the join's own output bucket follows its match count, which stays put)
    deltas = [
        keyed(
            [((10_000 + t, v), 1) for v in range(5)]
            + [((0, 100 + v), 1) for v in range(3 if t == 1 else 0)],
            t,
        )
        for t in ticks
    ]
    probes = [keyed([((0, 7), 1), ((3, 7), 1)], t) for t in ticks]
    arr.insert(bulk, already_keyed=True)
    for i in range(len(ticks)):
        if i == 2:
            built = programs_built()
        arr.insert(deltas[i], already_keyed=True)
        outs = join_against(probes[i], arr.batches)
        assert [int(o.count()) for o in outs] == [2, 3]
    assert programs_built() - built == 0
    assert arr.head_bound == 14 * 5 + 3 and arr.count() == 200 + 14 * 5 + 3


@pytest.mark.parametrize("wide", [False, True])
def test_a_delta_past_the_heads_bucket_goes_in_pieces_through_the_same_program(wide):
    """A head started by a delta of 5 rows (d = 8: T = 16 x 8, or 16 x 16 with
    the bucket of slack an operator's wide output gets) later meets deltas of
    17 to 33 rows, as a handful of rows does by chance (Q17's filtered
    lineitems at SF1: 12 a refresh, give or take). They go in d rows at a
    time: the contents are the reference's, and no insert after the one that
    started the head asks for a merge program (a (T, d') program of their own
    cost the chip's compiler seconds in whichever refresh met it)."""
    from materialize_tpu.ops.consolidate import _merge_consolidate

    arr, ref = Arrangement(key_cols=(0,)), Reference()
    sizes = [200, 5, 7, 17, 20, 9, 33, 12, 18]
    for tick, n in enumerate(sizes):
        rows = [((1000 * tick + k, k), 1) for k in range(n)]
        if tick > 2:
            rows[0] = ((1000 * (tick - 1), 0), -1)  # a retraction of the last delta's first row
        delta = keyed(rows, tick)
        if wide and tick:  # an operator's output: far wider than its rows
            delta = delta.with_capacity(1024)
        if tick == 2:
            built = _merge_consolidate._cache_size()
        arr.insert(delta, already_keyed=True)
        ref.insert(rows, tick)
        assert contents(arr.batches, arr.since) == ref.contents(arr.since)
        if tick:
            assert arr.head is not None and arr.head.cap == HEAD_RATIO * (16 if wide else 8)
    assert _merge_consolidate._cache_size() == built
    assert arr.head_bound == sum(sizes[1:])


def test_shared_trace_views_with_a_head_and_a_staged_delta():
    """`batches_thru` / `batches_before` read what they read before the
    head: the spine, the head, and the staged delta by its tick."""
    tr = SharedTrace("u1", (0,), "mv")
    rows = {
        0: [((k, 0), 1) for k in range(200)],
        1: [((1, v), 1) for v in range(4)],
        2: [((2, v), 1) for v in range(4)] + [((1, 0), -1)],
        3: [((3, v), 1) for v in range(4)],
    }
    ref = Reference()
    for t in range(4):
        before = ref.contents(0)
        tr.offer(t, keyed(rows[t], t))
        ref.insert(rows[t], t)
        assert contents(tr.batches_thru(t), 0) == ref.contents(0)
        assert contents(tr.batches_before(t), 0) == before
        # a later tick's view of `before` takes the staged delta in
        assert contents(tr.batches_before(t + 1), 0) == ref.contents(0)
    # ticks 1 and 2 are sealed into the head; tick 3 is staged behind it
    assert tr.arr.head is not None and tr.arr.head_bound == 4 + 5
    assert tr.batches_thru(3)[-2] is tr.arr.head
    assert tr.batches_thru(3)[-1] is tr.delta
    nb, cap, rec = tr.state_info()
    assert (nb, rec) == (3, 200 + 4 + 5 + 4)


def _canonical_batch(rng, n, cap, keys=40, ticks=4):
    """A `consolidate` output: `n` random updates over few keys (heavy ties in
    the packed key, rows equal but for their time) padded to `cap`."""
    ks = rng.integers(0, keys, n)
    vs = rng.integers(0, 3, n)
    b = mkbatch([ks, vs], rng.integers(0, ticks, n), rng.choice([-1, 1, 2], n))
    return arrange_batch(b, (0,)).with_capacity(cap)


def _same_arrays(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("out_cap", [None, "padded", "truncated"])
@pytest.mark.parametrize("since", [None, 2])
@pytest.mark.parametrize("na,nb", [(256, 16), (16, 256), (64, 64)])
def test_merge_consolidate_is_bit_identical_to_the_two_search_merge(
    rng, na, nb, since, out_cap
):
    """`merge_perm` changed how the merge order is built, not the order: the
    output equals, array for array, the program of PRs 29-32 (its position
    construction frozen in test_search32.py), and holds the multiset of
    `consolidate(concat(a, b))`."""
    from materialize_tpu.ops import consolidate
    from materialize_tpu.ops.consolidate import (
        _consolidate_sorted,
        advance_times,
        merge_consolidate,
        pack_sort_key,
    )
    from materialize_tpu.ops.permute import batch_permute
    from materialize_tpu.repr import UpdateBatch
    from materialize_tpu.repr.batch import TIME_DTYPE
    from test_search32 import two_search_merge_perm

    a = _canonical_batch(rng, na // 4, na)
    b = _canonical_batch(rng, nb // 4, nb)
    cap = {None: None, "padded": 2 * (na + nb), "truncated": max(na, nb)}[out_cap]
    frontier = None if since is None else jnp.asarray(since, TIME_DTYPE)
    got = merge_consolidate(a, b, frontier, cap)

    old = batch_permute(
        UpdateBatch.concat(a, b),
        two_search_merge_perm(*pack_sort_key(a), *pack_sort_key(b)),
    )
    if frontier is not None:
        old = advance_times(old, frontier)
    old = _consolidate_sorted(old, compact=True)
    _same_arrays(got, old if cap is None else old.with_capacity(cap))

    cat = UpdateBatch.concat(a, b)
    if frontier is not None:
        cat = advance_times(cat, frontier)
    assert contents([got], 0) == contents([consolidate(cat)], 0)
    assert got.cap == (na + nb if cap is None else cap)


@pytest.mark.parametrize("na,nb", [(256, 16), (16, 256), (64, 64)])
def test_merge_consolidate_accums_is_bit_identical_to_the_two_search_merge(rng, na, nb):
    from materialize_tpu.ops.reduce import (
        AccumState,
        _accum_pack,
        _accum_take,
        _consolidate_accums_sorted,
        consolidate_accums,
        merge_consolidate_accums,
    )
    from materialize_tpu.repr import hash_columns
    from test_search32 import two_search_merge_perm

    def table(n, cap):
        ks = jnp.asarray(rng.permutation(3 * n)[:n].astype(np.int64))
        s = AccumState(
            hash_columns((ks,)),
            (ks,),
            (jnp.asarray(rng.integers(-5, 6, n)), jnp.asarray(rng.integers(1, 4, n))),
            jnp.asarray(rng.choice([-1, 1, 2], n)),
        )
        return consolidate_accums(s.with_capacity(cap))

    # keys overlap between the sides, some sums cancel to empty groups
    a, b = table(na // 2, na), table(nb // 2, nb)
    got, dup = merge_consolidate_accums(a, b)
    old, old_dup = _consolidate_accums_sorted(
        _accum_take(
            AccumState.concat(a, b),
            two_search_merge_perm(*_accum_pack(a), *_accum_pack(b)),
        )
    )
    _same_arrays(got, old)
    assert bool(dup) == bool(old_dup) is False
    def groups(s):
        live = np.asarray(s.live)
        cols = [np.asarray(c)[live] for c in (*s.keys, *s.accums, s.nrows)]
        return sorted(zip(*(c.tolist() for c in cols)))

    want = groups(consolidate_accums(AccumState.concat(a, b)))
    assert groups(got) == want and len(want) > max(na, nb) // 2


def _gathers_and_scatters(jaxpr):
    """(rows gathered, ...) and (rows scattered, ...) of every gather and
    scatter in a jaxpr, nested programs included."""
    gathers, scatters = [], []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            gathers.append(eqn.outvars[0].aval.shape[-1])  # (n,) or stacked (k, n)
        elif name.startswith("scatter"):
            scatters.append(eqn.invars[2].aval.shape[0])  # the updates
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (tuple, list)) else (sub,):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    g, sc = _gathers_and_scatters(inner)
                    gathers += g
                    scatters += sc
    return gathers, scatters


def test_a_head_merge_searches_the_delta_only():
    """The shape of the head-merge program, counted on the CPU: at (16 d, d)
    no binary-search step gathers T rows (the program of PRs 29-32 had 30
    such gathers: every head row searched into the delta), the only gathers
    of T rows or more are the payload's, and one scatter fewer runs over
    T + d rows (the inverse of `pos` is gone; the mark scatters d)."""
    from materialize_tpu.ops.consolidate import _merge_consolidate
    from materialize_tpu.repr.batch import MIN_CAP

    d = MIN_CAP
    t = HEAD_RATIO * d
    a = _canonical_batch(np.random.default_rng(0), t // 4, t)
    b = _canonical_batch(np.random.default_rng(1), d // 2, d)
    jaxpr = jax.make_jaxpr(lambda x, y: _merge_consolidate(x, y, None, out_cap=t))(a, b)
    gathers, scatters = _gathers_and_scatters(jaxpr.jaxpr)
    steps = t.bit_length()  # ceil(log2(t)) + 1 steps of the search into the head
    assert sum(g == d for g in gathers) == 2 * steps  # (hi, lo) per step
    assert sum(g == t for g in gathers) == 0  # was 2 * (ceil(log2(d)) + 1)
    # what is left at T + d rows is `_consolidate_sorted`'s and the payload's,
    # gathered by dtype group into merged order and again compacted
    # (ops/permute.py); the position arithmetic gathers nothing at this size
    long_gathers = sum(g >= t for g in gathers)
    assert 0 < long_gathers <= 2 * len(jax.tree_util.tree_leaves(a))
    assert len(gathers) == 2 * steps + long_gathers
    assert sorted(scatters) == [d, t + d, t + d]  # mark; run ends; compaction


KERNELS = {
    "materialize_tpu.ops.consolidate": ("_merge_consolidate", "_consolidate"),
    "materialize_tpu.ops.join": ("_join_total", "_join_materialize"),
    "materialize_tpu.ops.fused_reduce": ("_fused_mfp_reduce_step",),
}


def kernel_programs() -> dict:
    """Programs each of the five kernel functions holds (its jit cache)."""
    return {
        name: getattr(importlib.import_module(mod), name)._cache_size()
        for mod, names in KERNELS.items()
        for name in names
    }


def test_served_q3_refreshes_build_no_kernel_program():
    """Q3 through SQL: after hydration and two refreshes, refreshes 3-10
    build no merge, consolidate, join or fused-reduce program, and the view
    equals the oracle after each."""
    import tpch_q3
    from materialize_tpu.adapter import Coordinator
    from materialize_tpu.models import tpch

    c = Coordinator()
    c.execute(tpch_q3.SOURCE_SQL_STEADY)
    c.execute(tpch_q3.VIEW_SQL)
    gen = c.generators[0][0]
    seg_code = c.catalog.dict.lookup("BUILDING")

    def check():
        rows = c.execute("SELECT * FROM q3").rows
        want = tpch.q3_oracle(
            *tpch.q3_inputs(gen.live()),
            building_code=seg_code,
        )
        got = {(lk, od, sp): round(rev * 10_000) for (lk, rev, od, sp) in rows}
        assert got == {k: v for k, v in want.items() if v != 0}

    for _ in range(2):
        c.advance()
    check()  # the peek's own programs are met before the count starts
    merges = head_counter("merges")
    for refresh in range(3, 11):
        before = kernel_programs()
        c.advance()
        assert kernel_programs() == before, f"refresh {refresh}"
        check()
    assert head_counter("merges") - merges >= 8 * 5  # every arrangement, every refresh
    heads = c.execute(
        "SELECT arrangement FROM mz_arrangement_sizes WHERE arrangement LIKE '%:head'"
    ).rows
    assert heads
