"""Deterministic load-generator sources (auction, TPC-H).

The TPU build's stand-in for the reference's load-generator sources
(src/storage-types/src/sources/load_generator.rs:146-240 — Auction tables
organizations/users/accounts/auctions/bids; Tpch with per-table row counts):
deterministic input without Kafka, for tests and benchmarks. Generation is
vectorized NumPy on host; batches land on device as UpdateBatch columns.

Schemas follow the reference:
  auctions(id i64, seller i64, item str, end_time ts)
  bids(id i64, buyer i64, auction_id i64, amount i32→i64, bid_time ts)
TPC-H: the specification's eight tables and every column (TPCH_TABLES), with
NUMERIC money columns as fixed-point i64 cents, dates as day numbers and
strings as dictionary codes (TPU-native choices: exact arithmetic without
f64).
"""

from __future__ import annotations

import numpy as np

from ..repr.batch import UpdateBatch
from ..repr.types import ColType, ColumnDesc, RelationDesc, StringDictionary

_ITEMS = [
    "Signed Memorabilia",
    "City Bar Crawl",
    "Best Pizza in Town",
    "Gift Basket",
    "Custom Art",
]


class AuctionGenerator:
    """Append-only auction/bids stream, deterministic per seed.

    Mirrors the reference auction generator's shape (load_generator.rs:185-240):
    static organizations/users/accounts; a stream of auctions and bids.
    """

    # per-bid footprint for ingest budgeting (5 i64 cols + time/diff)
    ROW_BYTES = 56

    def __init__(self, seed: int = 0, n_auctions_per_tick: int = 4, dict_: StringDictionary | None = None):
        self.rng = np.random.default_rng(seed)
        self.dict = dict_ or StringDictionary()
        self.item_codes = self.dict.encode_many(_ITEMS)
        self.next_auction_id = 0
        self.next_bid_id = 0
        self.n_auctions_per_tick = n_auctions_per_tick
        self.open_auctions: np.ndarray = np.array([], dtype=np.int64)

    def static_tables(self) -> dict[str, tuple]:
        orgs = np.arange(20, dtype=np.int64)
        org_names = self.dict.encode_many([f"org #{i}" for i in orgs])
        users = np.arange(1000, dtype=np.int64)
        user_org = users % 20
        user_names = self.dict.encode_many([f"user #{i}" for i in users])
        balances = np.full(1000, 10_000, dtype=np.int64)
        return {
            "organizations": (orgs, org_names),
            "users": (users, user_org, user_names),
            "accounts": (users, user_org, balances),
        }

    def next_tick(self, tick: int, n_bids: int) -> dict[str, UpdateBatch]:
        """New auctions + a batch of bids on open auctions at time `tick`."""
        na = self.n_auctions_per_tick
        a_ids = np.arange(self.next_auction_id, self.next_auction_id + na, dtype=np.int64)
        self.next_auction_id += na
        sellers = self.rng.integers(0, 1000, na).astype(np.int64)
        items = self.item_codes[self.rng.integers(0, len(self.item_codes), na)]
        end_times = np.full(na, tick + 100, dtype=np.int64)
        self.open_auctions = np.concatenate([self.open_auctions, a_ids])

        b_ids = np.arange(self.next_bid_id, self.next_bid_id + n_bids, dtype=np.int64)
        self.next_bid_id += n_bids
        buyers = self.rng.integers(0, 1000, n_bids).astype(np.int64)
        target = self.open_auctions[
            self.rng.integers(0, len(self.open_auctions), n_bids)
        ]
        amounts = self.rng.integers(1, 10_000, n_bids).astype(np.int64)
        bid_times = np.full(n_bids, tick, dtype=np.int64)

        return {
            "auctions": UpdateBatch.build(
                (), (a_ids, sellers, items, end_times), [tick] * na, [1] * na
            ),
            "bids": UpdateBatch.build(
                (),
                (b_ids, buyers, target, amounts, bid_times),
                [tick] * n_bids,
                [1] * n_bids,
            ),
        }


class CounterGenerator:
    """COUNTER load generator (load_generator.rs:150-155): emits 1, 2, 3, …;
    with max_cardinality, value v-max is retracted when v is emitted."""

    ROW_BYTES = 24  # one i64 col + time/diff

    def __init__(self, max_cardinality: int | None = None):
        self.max_cardinality = max_cardinality
        self.next = 1

    def next_tick(self, tick: int, n_rows: int = 1) -> dict[str, UpdateBatch]:
        vals = np.arange(self.next, self.next + n_rows, dtype=np.int64)
        self.next += n_rows
        diffs = np.ones(n_rows, dtype=np.int64)
        if self.max_cardinality is not None:
            dead = vals - self.max_cardinality
            keep = dead >= 1
            vals = np.concatenate([vals, dead[keep]])
            diffs = np.concatenate([diffs, -np.ones(int(keep.sum()), dtype=np.int64)])
        n = len(vals)
        return {
            "counter": UpdateBatch.build((), (vals,), np.full(n, tick), diffs)
        }


def date_num(y: int, m: int, d: int) -> int:
    """Days since 1992-01-01 (TPC-H epoch)."""
    return (np.datetime64(f"{y:04d}-{m:02d}-{d:02d}") - np.datetime64("1992-01-01")).astype(int)


# -- TPC-H -----------------------------------------------------------------------
# The specification's (v3, sections 1.4 and 4.2.3) value sets, in the order the
# generator indexes them: _BRANDS[7] is 'Brand#23', _CONTAINERS[17] 'MED BOX'.
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
_BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
_TYPES = [
    f"{a} {b} {c}"
    for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
    for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
    for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
]
_CONTAINERS = [
    f"{a} {b}"
    for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
    for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
]
_COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush brown burlywood "
    "burnished chartreuse chiffon chocolate coral cornflower cornsilk cream cyan dark deep dim "
    "dodger drab firebrick floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid pale papaya peach "
    "peru pink plum powder puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise violet wheat white yellow"
).split()
# the words of dbgen's comment grammar (nouns, verbs, adjectives, adverbs)
_WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses platelets "
    "asymptotes courts dolphins multipliers sauternes warthogs frets dinos attainments somas "
    "patterns forges braids frays warhorses dugouts notornis epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches escapades packages "
    "requests accounts deposits sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage hinder print breach "
    "eat grow impress mold poach serve run dazzle snooze doze unwind kindle play hang believe "
    "doubt furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged "
    "daring brave stealthy permanent enticing idle busy regular final ironic even bold silent "
    "special pending express unusual sometimes always never furiously slyly carefully "
    "blithely quickly fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly finally ironically "
    "evenly boldly silently"
).split()
_ALNUM = np.array(list("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789,"))
_CURRENT_DATE = date_num(1995, 6, 17)  # the specification's CURRENTDATE

_I, _S, _D = ColType.INT64, ColType.STRING, ColType.TIMESTAMP  # dates are day numbers


def _money(name: str) -> ColumnDesc:
    return ColumnDesc(name, ColType.NUMERIC, scale=2)


# TPC-H's eight tables, every column in the specification's order. DECIMAL is
# fixed-point cents (a discount or tax is a whole percent at scale 2);
# l_quantity, a whole number in the specification, is an integer.
TPCH_TABLES = {
    "region": RelationDesc.of(("r_regionkey", _I), ("r_name", _S), ("r_comment", _S), key=(0,)),
    "nation": RelationDesc.of(
        ("n_nationkey", _I), ("n_name", _S), ("n_regionkey", _I), ("n_comment", _S), key=(0,)
    ),
    "supplier": RelationDesc.of(
        ("s_suppkey", _I), ("s_name", _S), ("s_address", _S), ("s_nationkey", _I),
        ("s_phone", _S), _money("s_acctbal"), ("s_comment", _S), key=(0,),
    ),
    "customer": RelationDesc.of(
        ("c_custkey", _I), ("c_name", _S), ("c_address", _S), ("c_nationkey", _I),
        ("c_phone", _S), _money("c_acctbal"), ("c_mktsegment", _S), ("c_comment", _S), key=(0,),
    ),
    "part": RelationDesc.of(
        ("p_partkey", _I), ("p_name", _S), ("p_mfgr", _S), ("p_brand", _S), ("p_type", _S),
        ("p_size", _I), ("p_container", _S), _money("p_retailprice"), ("p_comment", _S), key=(0,),
    ),
    "partsupp": RelationDesc.of(
        ("ps_partkey", _I), ("ps_suppkey", _I), ("ps_availqty", _I), _money("ps_supplycost"),
        ("ps_comment", _S), key=(0, 1),
    ),
    "orders": RelationDesc.of(
        ("o_orderkey", _I), ("o_custkey", _I), ("o_orderstatus", _S), _money("o_totalprice"),
        ("o_orderdate", _D), ("o_orderpriority", _S), ("o_clerk", _S), ("o_shippriority", _I),
        ("o_comment", _S), key=(0,),
    ),
    "lineitem": RelationDesc.of(
        ("l_orderkey", _I), ("l_partkey", _I), ("l_suppkey", _I), ("l_linenumber", _I),
        ("l_quantity", _I), _money("l_extendedprice"), _money("l_discount"), _money("l_tax"),
        ("l_returnflag", _S), ("l_linestatus", _S), ("l_shipdate", _D), ("l_commitdate", _D),
        ("l_receiptdate", _D), ("l_shipinstruct", _S), ("l_shipmode", _S), ("l_comment", _S),
    ),
}


def _texts(rng, n: int, lo: int, hi: int) -> list[str]:
    """`n` comments of `lo` to `hi` characters in the words of dbgen's grammar."""
    words = np.array(_WORDS)[rng.integers(0, len(_WORDS), (n, hi // 3 + 1))]
    cut = rng.integers(lo, hi + 1, n)
    return [" ".join(w)[:k].rstrip() for w, k in zip(words.tolist(), cut.tolist())]


def _vstrings(rng, n: int, lo: int, hi: int) -> list[str]:
    """`n` random strings of `lo` to `hi` characters (dbgen's v-string: addresses)."""
    chars = _ALNUM[rng.integers(0, len(_ALNUM), (n, hi))]
    cut = rng.integers(lo, hi + 1, n)
    return ["".join(c[:k]) for c, k in zip(chars.tolist(), cut.tolist())]


def _phones(rng, nationkey: np.ndarray) -> list[str]:
    """dbgen's phone numbers: the country code is the nation's key plus 10."""
    parts = np.stack([nationkey + 10, rng.integers(100, 1000, len(nationkey)),
                      rng.integers(100, 1000, len(nationkey)),
                      rng.integers(1000, 10000, len(nationkey))], axis=1)
    return [f"{a}-{b}-{c}-{d}" for a, b, c, d in parts.tolist()]


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


class TpchGenerator:
    """LOAD GENERATOR TPCH: TPC-H's eight tables in the specification's schema
    (`TPCH_TABLES`), with RF1/RF2 refreshes of orders and lineitem.

    Row counts follow the reference Tpch load generator's knobs
    (load_generator.rs:157: count_customer/count_orders/...); per TPC-H spec,
    customer = 150k·SF, orders = 1.5M·SF, lineitems 1–7 per order, part =
    200k·SF with four partsupp rows each, supplier = 10k·SF. Keys count from 0;
    dates are day numbers (date_num). The columns Q3's and Q17's models read
    are drawn first, from one stream, as the generator's first version drew
    them: `l_shipdate` uniform over 1992-1998 and independent of the order's
    date, `l_extendedprice` uniform. Every other column follows dbgen's rules
    (`l_returnflag`, `l_linestatus`, `o_orderstatus` and `o_totalprice` derive
    from the drawn values as the specification says), except that comments,
    addresses and part names repeat from a pool of `POOL` texts per column.
    Strings are codes in the dictionary `tables()` binds (positions in their
    value set before that). `columns` picks the tables and columns emitted, in
    their order (default: all of them, as the catalog holds them).
    """

    POOL = 1 << 14  # distinct texts per comment, address and part-name column

    def __init__(self, sf: float = 0.01, seed: int = 0, segment_codes=None,
                 val_dtype=np.int64, columns: dict | None = None):
        self.sf = sf
        # Device-batch value dtype. The SQL path keeps i64 (table descs are
        # int64); the bench path passes int32 — every TPC-H column fits
        # (orderkey < 2^31 through SF100, cents < 10^9, dates < 2557) and the
        # TPU VPU is a 32-bit machine, so i32 halves gather/sort bandwidth.
        # Host mirrors stay i64; the cast happens at batch build.
        self.val_dtype = np.dtype(val_dtype)
        self.snapshot_rng = np.random.default_rng(12345)
        self.rng = np.random.default_rng(seed)  # refreshes: the columns drawn first
        self.extra_rng = np.random.default_rng([seed, 1])  # refreshes: every other column
        # c_mktsegment where no dictionary is bound: raw 0..4 indices into
        # _SEGMENTS by default, or the caller's codes for them
        self.segment_codes = (
            np.asarray(segment_codes, dtype=np.int64)
            if segment_codes is not None
            else np.arange(5, dtype=np.int64)
        )
        self.columns = columns or {t: d.names for t, d in TPCH_TABLES.items()}
        self.n_customer = max(int(150_000 * sf), 10)
        self.n_orders = max(int(1_500_000 * sf), 20)
        self.n_part = max(int(200_000 * sf), 10)
        self.n_supplier = max(int(10_000 * sf), 10)
        self.n_clerk = max(int(1_000 * sf), 1)
        self.next_orderkey = self.n_orders
        self._strings: StringDictionary | None = None
        self._codes: dict | None = None  # value set -> codes, made with the snapshot
        self._static: dict | None = None  # tables the refreshes never touch, by column
        self._orders: _Fifo | None = None  # live rows, oldest first (orderkeys only grow)
        self._lineitem: _Fifo | None = None

    def tables(self, strings: StringDictionary) -> dict[str, RelationDesc]:
        """The tables this generator emits, as the catalog holds them. Its
        strings are interned in `strings` from here on."""
        self._strings = strings
        out = {}
        for t, cols in self.columns.items():
            full = TPCH_TABLES[t]
            key = tuple(cols.index(full.names[k]) for k in full.key if full.names[k] in cols)
            out[t] = RelationDesc(tuple(full.columns[full.names.index(c)] for c in cols),
                                  key if len(key) == len(full.key) else ())
        return out

    def _encode(self, words: list[str]) -> np.ndarray:
        if self._strings is None:
            return np.arange(len(words), dtype=np.int64)
        return self._strings.encode_many(words)

    def _value_sets(self, rng) -> dict:
        pool = lambda n: min(n, self.POOL)  # noqa: E731
        return {
            "segment": self._encode(_SEGMENTS) if self._strings is not None else self.segment_codes,
            "region": self._encode(_REGIONS), "nation": self._encode([n for n, _ in _NATIONS]),
            "mfgr": self._encode(_MFGRS), "brand": self._encode(_BRANDS),
            "type": self._encode(_TYPES), "container": self._encode(_CONTAINERS),
            "status": self._encode(["F", "O", "P"]), "priority": self._encode(_PRIORITIES),
            "clerk": self._encode([f"Clerk#{i:09d}" for i in range(1, self.n_clerk + 1)]),
            "returnflag": self._encode(["R", "A", "N"]), "linestatus": self._encode(["F", "O"]),
            "instruct": self._encode(_INSTRUCTIONS), "mode": self._encode(_MODES),
            "p_name": self._encode([" ".join(c) for c in np.array(_COLORS)[
                np.argsort(rng.random((pool(self.n_part), len(_COLORS))), axis=1)[:, :5]].tolist()]),
            "address": self._encode(_vstrings(rng, pool(self.n_customer), 10, 40)),
            "r_comment": self._encode(_texts(rng, 5, 31, 115)),
            "n_comment": self._encode(_texts(rng, 25, 31, 114)),
            "s_comment": self._encode(_texts(rng, pool(self.n_supplier), 25, 100)),
            "c_comment": self._encode(_texts(rng, pool(self.n_customer), 29, 116)),
            "p_comment": self._encode(_texts(rng, pool(self.n_part), 5, 22)),
            "ps_comment": self._encode(_texts(rng, pool(4 * self.n_part), 49, 198)),
            "o_comment": self._encode(_texts(rng, pool(self.n_orders), 19, 78)),
            "l_comment": self._encode(_texts(rng, pool(4 * self.n_orders), 10, 43)),
        }

    def _suppliers(self, partkey: np.ndarray, i) -> np.ndarray:
        """The i-th supplier (0..3) of a part, counted from 0: dbgen's formula
        without its ⌊partkey / S⌋ in the stride, which makes two of a part's
        four suppliers one below SF 0.03."""
        s = self.n_supplier
        return (partkey + 1 + i * (s // 4)) % s

    @staticmethod
    def _core(rng, n_customer: int, n_part: int, orderkey: np.ndarray) -> tuple:
        """The columns drawn first for new orders: (o_custkey, o_orderdate,
        lines per order, lineitem (price, discount, shipdate, quantity, partkey))."""
        n = len(orderkey)
        o_custkey = rng.integers(0, n_customer, n)
        o_orderdate = rng.integers(0, 2406, n)  # 1992-1998
        nli = rng.integers(1, 8, n)
        n_l = int(nli.sum())
        lines = (
            rng.integers(100_00, 100_000_00, n_l),  # cents
            rng.integers(0, 11, n_l),  # percent
            rng.integers(0, 2557, n_l),
            rng.integers(1, 51, n_l),
            rng.integers(0, n_part, n_l),
        )
        return o_custkey, o_orderdate, nli, lines

    def _orders_lineitems(self, rng, orderkey: np.ndarray, core: tuple) -> tuple:
        """Every column of new orders and their lineitems (TPCH_TABLES order),
        the core's columns given, the others drawn from `rng`."""
        o_custkey, o_orderdate, nli, (price, disc, ship, qty, partkey) = core
        v = self._codes
        n_o, n_l = len(orderkey), len(price)
        order_of = np.repeat(np.arange(n_o), nli)
        first = np.cumsum(nli) - nli
        tax = rng.integers(0, 9, n_l)  # percent
        receipt = ship + rng.integers(1, 31, n_l)
        returned = rng.integers(0, 2, n_l)
        open_line = (ship > _CURRENT_DATE).astype(np.int64)
        lineitem = (
            np.repeat(orderkey, nli), partkey, self._suppliers(partkey, rng.integers(0, 4, n_l)),
            np.arange(n_l) - first[order_of] + 1, qty, price, disc, tax,
            v["returnflag"][np.where(receipt <= _CURRENT_DATE, returned, 2)],
            v["linestatus"][open_line], ship, o_orderdate[order_of] + rng.integers(30, 91, n_l),
            receipt, v["instruct"][rng.integers(0, 4, n_l)], v["mode"][rng.integers(0, 7, n_l)],
            v["l_comment"][rng.integers(0, len(v["l_comment"]), n_l)],
        )
        # o_totalprice: the sum of price x (1 + tax) x (1 - discount), in cents
        charged = np.add.reduceat(price * (100 + tax) * (100 - disc), first)
        n_open = np.add.reduceat(open_line, first)
        orders = (
            orderkey, o_custkey,
            v["status"][np.where(n_open == 0, 0, np.where(n_open == nli, 1, 2))],
            (charged + 5_000) // 10_000, o_orderdate, v["priority"][rng.integers(0, 5, n_o)],
            v["clerk"][rng.integers(0, len(v["clerk"]), n_o)], np.zeros(n_o, dtype=np.int64),
            v["o_comment"][rng.integers(0, len(v["o_comment"]), n_o)],
        )
        return orders, lineitem

    def initial(self) -> dict[str, tuple]:
        """Draws the snapshot: table -> its emitted columns (host, i64)."""
        rng = self.snapshot_rng
        custkey = np.arange(self.n_customer, dtype=np.int64)
        segment = rng.integers(0, 5, self.n_customer)
        c_nationkey = rng.integers(0, 25, self.n_customer)
        orderkey = np.arange(self.n_orders, dtype=np.int64)
        core = self._core(rng, self.n_customer, self.n_part, orderkey)
        partkey = np.arange(self.n_part, dtype=np.int64)
        brand = rng.integers(0, 25, self.n_part)
        container = rng.integers(0, 40, self.n_part)
        # every other column, after those, from the same stream
        v = self._codes = self._value_sets(rng)
        orders, lineitem = self._orders_lineitems(rng, orderkey, core)
        suppkey = np.arange(self.n_supplier, dtype=np.int64)
        s_nationkey = rng.integers(0, 25, self.n_supplier)
        ps_partkey = np.repeat(partkey, 4)
        cols = {
            "region": (np.arange(5, dtype=np.int64), v["region"], v["r_comment"]),
            "nation": (np.arange(25, dtype=np.int64), v["nation"],
                       np.array([r for _, r in _NATIONS], dtype=np.int64), v["n_comment"]),
            "supplier": (suppkey, self._encode([f"Supplier#{k:09d}" for k in range(1, self.n_supplier + 1)]),
                         v["address"][rng.integers(0, len(v["address"]), self.n_supplier)], s_nationkey,
                         self._encode(_phones(rng, s_nationkey)), rng.integers(-999_99, 10_000_00, self.n_supplier),
                         v["s_comment"][rng.integers(0, len(v["s_comment"]), self.n_supplier)]),
            "customer": (custkey, self._encode([f"Customer#{k:09d}" for k in range(1, self.n_customer + 1)]),
                         v["address"][rng.integers(0, len(v["address"]), self.n_customer)], c_nationkey,
                         self._encode(_phones(rng, c_nationkey)), rng.integers(-999_99, 10_000_00, self.n_customer),
                         v["segment"][segment], v["c_comment"][rng.integers(0, len(v["c_comment"]), self.n_customer)]),
            "part": (partkey, v["p_name"][rng.integers(0, len(v["p_name"]), self.n_part)], v["mfgr"][brand // 5],
                     v["brand"][brand], v["type"][rng.integers(0, len(_TYPES), self.n_part)],
                     rng.integers(1, 51, self.n_part), v["container"][container],
                     90_000 + ((partkey + 1) // 10) % 20_001 + 100 * ((partkey + 1) % 1_000),
                     v["p_comment"][rng.integers(0, len(v["p_comment"]), self.n_part)]),
            "partsupp": (ps_partkey, self._suppliers(ps_partkey, np.tile(np.arange(4), self.n_part)),
                         rng.integers(1, 10_000, 4 * self.n_part), rng.integers(1_00, 1_000_01, 4 * self.n_part),
                         v["ps_comment"][rng.integers(0, len(v["ps_comment"]), 4 * self.n_part)]),
            "orders": orders,
            "lineitem": lineitem,
        }
        self._static = {t: c for t, c in cols.items() if t not in ("orders", "lineitem")}
        self._orders, self._lineitem = _Fifo(orders), _Fifo(lineitem)
        return {t: self._emitted(t, cols[t]) for t in self.columns}

    def _emitted(self, table: str, cols: tuple) -> tuple:
        names = TPCH_TABLES[table].names
        return tuple(np.asarray(cols[names.index(c)], dtype=np.int64) for c in self.columns[table])

    def batch(self, table: str, cols: tuple, tick: int, diffs: np.ndarray) -> UpdateBatch:
        """`table`'s rows (every column, host) as the update batch it emits at `tick`."""
        vals = tuple(c.astype(self.val_dtype) for c in self._emitted(table, cols))
        return UpdateBatch.build((), vals, np.full(len(diffs), tick), diffs)

    def initial_batches(self, tick: int = 0) -> dict[str, UpdateBatch]:
        self.initial()
        live = {**self._static, "orders": self._orders.cols(), "lineitem": self._lineitem.cols()}
        return {t: self.batch(t, live[t], tick, np.ones(len(live[t][0]), dtype=np.int64))
                for t in self.columns}

    def refresh_rows(self, frac: float = 0.001, deletes: bool = True) -> dict:
        """RF1 (new orders with their lineitems) + RF2 (the oldest live orders
        and their lineitems retracted) on the host: table -> (every column,
        diffs). Moves the live rows."""
        if self._orders is None:
            raise RuntimeError("call initial()/initial_batches() first")
        n_new = max(int(self.n_orders * frac), 1)
        new_ok = np.arange(self.next_orderkey, self.next_orderkey + n_new, dtype=np.int64)
        self.next_orderkey += n_new
        core = self._core(self.rng, self.n_customer, self.n_part, new_ok)
        o_new, l_new = self._orders_lineitems(self.extra_rng, new_ok, core)
        o_parts, l_parts = [o_new], [l_new]
        if deletes:
            # both tables are kept in orderkey order, so the oldest orders and
            # their lineitems are the front of each
            o_parts.append(self._orders.pop_front(n_new))
            last = o_parts[-1][0][-1]
            l_parts.append(self._lineitem.pop_front(
                int(np.searchsorted(self._lineitem.cols()[0], last, side="right"))))
        self._orders.append(o_new)
        self._lineitem.append(l_new)
        out = {}
        for t, parts in (("orders", o_parts), ("lineitem", l_parts)):
            cols = tuple(np.concatenate(c) for c in zip(*parts))
            diffs = np.concatenate([np.full(len(p[0]), 1 - 2 * i, dtype=np.int64) for i, p in enumerate(parts)])
            out[t] = (cols, diffs)
        return out

    def refresh(self, tick: int, frac: float = 0.001, deletes: bool = True) -> dict[str, UpdateBatch]:
        """RF1 + RF2 (`refresh_rows`) as update batches at `tick`: the
        canonical IVM update stream."""
        rows = self.refresh_rows(frac, deletes)
        return {t: self.batch(t, cols, tick, d) for t, (cols, d) in rows.items() if t in self.columns}

    def live(self) -> dict[str, dict[str, np.ndarray]]:
        """The live rows on the host: table -> column name -> values, every
        column of every table."""
        live = {**self._static, "orders": self._orders.cols(), "lineitem": self._lineitem.cols()}
        return {t: dict(zip(TPCH_TABLES[t].names, cols)) for t, cols in live.items()}
