"""Deterministic TPC-H data generator connector.

Reference analog: ``presto-tpch`` (io.airlift.tpch based generator
connector, `presto-tpch/src/main/java/com/facebook/presto/tpch/`),
which is the basis of most engine tests and benchmarks in the
reference. This is a from-scratch implementation of the TPC-H spec's
data distributions — NOT a port of airlift/tpch — built around two
TPU-driven requirements:

* **Stateless chunked generation.** Every value is a pure function of
  (table, column, row index) via a splitmix64-style counter hash, so any
  split [row0, row1) generates independently — SF100 streams split by
  split without materializing 600M rows, and workers generate their own
  splits without coordination (the reference achieves this with
  per-split generator offsets in TpchRecordSet).

* **Dictionary-first strings.** Low-cardinality columns (shipmode,
  priority, types...) use small vocab dictionaries; per-row unique
  strings (names, phones, comments) use :class:`PatternDictionary`
  which formats values lazily from the code, so devices only ever see
  int32 codes.

Distributions follow TPC-H spec v2 section 4.2 closely enough that the
standard 22 queries exercise the same paths (selectivities, key
sparsity, date ranges); exact dbgen byte-parity is a non-goal since
correctness is checked against an oracle fed the same data.
"""

from __future__ import annotations

import datetime
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from presto_tpu.page import Dictionary, Page
from presto_tpu.types import BIGINT, DATE, DOUBLE, INTEGER, VARCHAR, DecimalType, Type

# ---------------------------------------------------------------------------
# counter-based RNG: value = f(seed, index), vectorized over index
# ---------------------------------------------------------------------------

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (public-domain algorithm), vectorized."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x


def _hash_u64(seed: int, idx: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        return _mix(np.asarray(idx, dtype=np.uint64) + np.uint64(seed) * _GOLDEN)


def _uniform_int(seed: int, idx: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Uniform integer in [lo, hi] inclusive (like spec's random(lo,hi))."""
    span = np.uint64(hi - lo + 1)
    return (lo + (_hash_u64(seed, idx) % span).astype(np.int64)).astype(np.int64)


def _uniform_unit(seed: int, idx: np.ndarray) -> np.ndarray:
    return (_hash_u64(seed, idx) >> np.uint64(11)).astype(np.float64) / float(1 << 53)


def _seed(table: str, column: str) -> int:
    h = 1469598103934665603
    for c in f"{table}.{column}":
        h = ((h ^ ord(c)) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h


def _date(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


MIN_ORDER_DATE = _date(1992, 1, 1)
MAX_ORDER_DATE = _date(1998, 8, 2)
CURRENT_DATE = _date(1995, 6, 17)

# ---------------------------------------------------------------------------
# vocabularies (TPC-H spec 4.2.2.13 / appendix; fixed text domains)
# ---------------------------------------------------------------------------

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# (name, regionkey) in nationkey order, spec table A-1
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("RUSSIA", 3), ("SAUDI ARABIA", 4), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1), ("VIETNAM", 2),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
TYPE_SYL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_SYL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_SYL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_SYL1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_SYL2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush brown "
    "burlywood burnished chartreuse chiffon chocolate coral cornflower cornsilk cream "
    "cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost "
    "goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon "
    "light lime linen magenta maroon medium metallic midnight mint misty moccasin "
    "navajo navy olive orange orchid pale papaya peach peru pink plum powder puff "
    "purple red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke "
    "snow spring steel tan thistle tomato turquoise violet wheat white yellow"
).split()
_NOUNS = (
    "packages requests accounts deposits foxes ideas theodolites pinto beans "
    "instructions dependencies excuses platelets asymptotes courts dolphins "
    "multipliers sauternes warthogs frets dinos attainments somas braids "
    "hockey players frays warhorses dugouts notornis epitaphs pearls tithes "
    "waters orbits gifts sheaves depths sentiments decoys realms pains grouches "
    "escapades"
).split()
_VERBS = (
    "sleep wake are cajole haggle nag use boost affix detect integrate maintain "
    "nod was lose sublate solve thrash promise engage hinder print x-ray breach "
    "eat grow impress mold poach serve run dazzle snooze doze unwind kindle play "
    "hang believe doubt"
).split()
_ADJECTIVES = (
    "furious sly careful blithe quick fluffy slow quiet ruthless thin close dogged "
    "daring brave stealthy permanent enticing idle busy regular final ironic even "
    "bold silent special pending unusual express"
).split()
_ADVERBS = (
    "sometimes always never furiously slyly carefully blithely quickly fluffily "
    "slowly quietly ruthlessly thinly closely doggedly daringly bravely stealthily "
    "permanently enticingly idly busily regularly finally ironically evenly boldly "
    "silently"
).split()


def _make_comment_vocab(n: int, seed: int) -> List[str]:
    """Fixed-size sentence vocabulary for comment columns. A slice of
    entries embeds 'special … requests' / 'pending … deposits' style
    phrases so Q13-like LIKE predicates have real selectivity."""
    idx = np.arange(n)
    adv = _hash_u64(seed + 1, idx) % len(_ADVERBS)
    adj = _hash_u64(seed + 2, idx) % len(_ADJECTIVES)
    noun = _hash_u64(seed + 3, idx) % len(_NOUNS)
    verb = _hash_u64(seed + 4, idx) % len(_VERBS)
    adj2 = _hash_u64(seed + 5, idx) % len(_ADJECTIVES)
    noun2 = _hash_u64(seed + 6, idx) % len(_NOUNS)
    out = []
    for i in range(n):
        out.append(
            f"{_ADVERBS[adv[i]]} {_ADJECTIVES[adj[i]]} {_NOUNS[noun[i]]} "
            f"{_VERBS[verb[i]]} the {_ADJECTIVES[adj2[i]]} {_NOUNS[noun2[i]]}"
        )
    return out


class PatternDictionary(Dictionary):
    """Dictionary whose values are computed lazily from the code by a
    formatting function (e.g. ``Customer#%09d``). Avoids materializing
    millions of per-row-unique strings; devices see only the code."""

    __slots__ = ("fmt", "size")

    def __init__(self, fmt, size: int):
        self.fmt = fmt  # callable code -> str
        self.size = size
        self.values = _LazyValues(fmt, size)  # type: ignore[assignment]
        self._index = None

    def code_of(self, s: str) -> int:  # pragma: no cover - rarely used
        for i in range(self.size):
            if self.fmt(i) == s:
                return i
        return -1

    def decode(self, codes: np.ndarray) -> np.ndarray:
        flat = codes.ravel()
        out = np.empty(flat.shape, dtype=object)
        for j, c in enumerate(flat):
            out[j] = self.fmt(int(c)) if 0 <= c < self.size else None
        return out.reshape(codes.shape)

    def lut(self, predicate) -> np.ndarray:
        return np.asarray(
            [bool(predicate(self.fmt(i))) for i in range(self.size)], dtype=np.bool_
        )

    def __len__(self) -> int:
        return self.size

    def __repr__(self) -> str:
        return f"PatternDictionary({self.size} values)"


class _LazyValues:
    def __init__(self, fmt, size):
        self._fmt, self._size = fmt, size

    def __getitem__(self, i):
        return self._fmt(i)

    def __len__(self):
        return self._size

    def __iter__(self):
        return (self._fmt(i) for i in range(self._size))


def _phone_fmt(nation_of_code):
    def fmt(code: int) -> str:
        nk = nation_of_code(code)
        h = int(_hash_u64(77, np.asarray([code]))[0])
        return (
            f"{10 + nk}-{100 + h % 900}-{100 + (h >> 10) % 900}-{1000 + (h >> 20) % 9000}"
        )

    return fmt


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

_MONEY = DecimalType(12, 2)
_PCT = DecimalType(12, 2)  # discount/tax stored scale-2 (0.05 -> 5)

SCHEMAS: Dict[str, List[Tuple[str, Type]]] = {
    "region": [("r_regionkey", BIGINT), ("r_name", VARCHAR), ("r_comment", VARCHAR)],
    "nation": [
        ("n_nationkey", BIGINT), ("n_name", VARCHAR),
        ("n_regionkey", BIGINT), ("n_comment", VARCHAR),
    ],
    "supplier": [
        ("s_suppkey", BIGINT), ("s_name", VARCHAR), ("s_address", VARCHAR),
        ("s_nationkey", BIGINT), ("s_phone", VARCHAR), ("s_acctbal", _MONEY),
        ("s_comment", VARCHAR),
    ],
    "customer": [
        ("c_custkey", BIGINT), ("c_name", VARCHAR), ("c_address", VARCHAR),
        ("c_nationkey", BIGINT), ("c_phone", VARCHAR), ("c_acctbal", _MONEY),
        ("c_mktsegment", VARCHAR), ("c_comment", VARCHAR),
    ],
    "part": [
        ("p_partkey", BIGINT), ("p_name", VARCHAR), ("p_mfgr", VARCHAR),
        ("p_brand", VARCHAR), ("p_type", VARCHAR), ("p_size", BIGINT),
        ("p_container", VARCHAR), ("p_retailprice", _MONEY), ("p_comment", VARCHAR),
    ],
    "partsupp": [
        ("ps_partkey", BIGINT), ("ps_suppkey", BIGINT), ("ps_availqty", BIGINT),
        ("ps_supplycost", _MONEY), ("ps_comment", VARCHAR),
    ],
    "orders": [
        ("o_orderkey", BIGINT), ("o_custkey", BIGINT), ("o_orderstatus", VARCHAR),
        ("o_totalprice", _MONEY), ("o_orderdate", DATE), ("o_orderpriority", VARCHAR),
        ("o_clerk", VARCHAR), ("o_shippriority", BIGINT), ("o_comment", VARCHAR),
    ],
    "lineitem": [
        ("l_orderkey", BIGINT), ("l_partkey", BIGINT), ("l_suppkey", BIGINT),
        ("l_linenumber", BIGINT), ("l_quantity", DecimalType(12, 2)),
        ("l_extendedprice", _MONEY), ("l_discount", _PCT), ("l_tax", _PCT),
        ("l_returnflag", VARCHAR), ("l_linestatus", VARCHAR),
        ("l_shipdate", DATE), ("l_commitdate", DATE), ("l_receiptdate", DATE),
        ("l_shipinstruct", VARCHAR), ("l_shipmode", VARCHAR), ("l_comment", VARCHAR),
    ],
}


class Tpch:
    """TPC-H generator: tables at scale factor ``sf``, split-chunked.

    Orders/lineitem splits are aligned on order ranges so each split is
    self-consistent (o_totalprice/o_orderstatus derive from that order's
    line items, as the spec requires)."""

    COMMENT_VOCAB = 4096

    def __init__(self, sf: float = 1.0, split_rows: int = 1 << 20,
                 aligned_buckets: bool = False,
                 orderless_third: bool = False):
        self.sf = float(sf)
        self.split_rows = int(split_rows)
        # orderless_third: O_CUSTKEY as the spec draws it (clause
        # 4.2.3): never a key divisible by 3, so a third of the
        # customers hold no orders (Q13's c_count = 0 bucket).  The
        # default keeps the uniform draw over every customer, which
        # the stored answers of the benchmark's first configurations
        # were made from; the argument and that draw go when those
        # answers are made again, so no new caller sets it (PERF.md
        # section 7, PR 27)
        self.orderless_third = bool(orderless_third)
        # aligned_buckets: orders and lineitem use the SAME order-range
        # granularity per split, making split index a shared bucket id
        # (ConnectorNodePartitioningProvider analog — enables colocated
        # joins; lineitem splits are ~4x the rows of orders splits)
        self.aligned_buckets = bool(aligned_buckets)
        self.n_orders = int(round(1_500_000 * self.sf))
        self.n_customers = int(round(150_000 * self.sf))
        self.n_parts = int(round(200_000 * self.sf))
        self.n_suppliers = int(round(10_000 * self.sf))
        self._dicts: Dict[str, Dictionary] = {}
        self._comment_vocab = Dictionary(
            _make_comment_vocab(self.COMMENT_VOCAB, seed=99)
        )

    # -- dictionaries -------------------------------------------------------
    def _dict(self, key: str) -> Dictionary:
        if key in self._dicts:
            return self._dicts[key]
        d: Dictionary
        if key == "r_name":
            d = Dictionary(REGIONS)
        elif key == "n_name":
            d = Dictionary([n for n, _ in NATIONS])
        elif key == "c_mktsegment":
            d = Dictionary(SEGMENTS)
        elif key == "o_orderpriority":
            d = Dictionary(PRIORITIES)
        elif key == "o_orderstatus":
            d = Dictionary(["F", "O", "P"])
        elif key == "l_returnflag":
            d = Dictionary(["A", "N", "R"])
        elif key == "l_linestatus":
            d = Dictionary(["F", "O"])
        elif key == "l_shipinstruct":
            d = Dictionary(INSTRUCTS)
        elif key == "l_shipmode":
            d = Dictionary(MODES)
        elif key == "p_type":
            d = Dictionary(
                [f"{a} {b} {c}" for a in TYPE_SYL1 for b in TYPE_SYL2 for c in TYPE_SYL3]
            )
        elif key == "p_container":
            d = Dictionary([f"{a} {b}" for a in CONTAINER_SYL1 for b in CONTAINER_SYL2])
        elif key == "p_brand":
            d = Dictionary([f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)])
        elif key == "p_mfgr":
            d = Dictionary([f"Manufacturer#{m}" for m in range(1, 6)])
        elif key == "p_name":
            d = self._part_name_dict()
        elif key == "c_name":
            d = PatternDictionary(lambda i: f"Customer#{i + 1:09d}", self.n_customers)
        elif key == "s_name":
            d = PatternDictionary(lambda i: f"Supplier#{i + 1:09d}", self.n_suppliers)
        elif key == "o_clerk":
            n_clerks = max(int(1000 * self.sf), 1)
            d = PatternDictionary(lambda i: f"Clerk#{i + 1:09d}", n_clerks)
        elif key == "c_phone":
            d = PatternDictionary(
                _phone_fmt(lambda c: int(_uniform_int(_seed("customer", "c_nationkey"), np.asarray([c]), 0, 24)[0])),
                self.n_customers,
            )
        elif key == "s_phone":
            d = PatternDictionary(
                _phone_fmt(lambda c: int(_uniform_int(_seed("supplier", "s_nationkey"), np.asarray([c]), 0, 24)[0])),
                self.n_suppliers,
            )
        elif key == "c_address":
            d = PatternDictionary(lambda i: _address(i, 101), self.n_customers)
        elif key == "s_address":
            d = PatternDictionary(lambda i: _address(i, 102), self.n_suppliers)
        elif key.endswith("_comment"):
            d = self._comment_vocab
        else:
            raise KeyError(key)
        self._dicts[key] = d
        return d

    def _part_name_dict(self) -> Dictionary:
        # 5 color words per part name (spec: P_NAME from 92-word list);
        # lazy: at SF100 there are 20M parts.
        def fmt(i: int) -> str:
            ia = np.asarray([i])
            return " ".join(
                COLORS[int(_hash_u64(300 + j, ia)[0] % len(COLORS))] for j in range(5)
            )

        return PatternDictionary(fmt, self.n_parts)

    # -- split layout -------------------------------------------------------
    def row_count(self, table: str) -> int:
        if table == "lineitem":
            return self._lineitem_count()
        return {
            "region": 5,
            "nation": 25,
            "supplier": self.n_suppliers,
            "customer": self.n_customers,
            "part": self.n_parts,
            "partsupp": self.n_parts * 4,
            "orders": self.n_orders,
        }[table]

    def _lines_per_order(self, order_idx: np.ndarray) -> np.ndarray:
        return _uniform_int(_seed("lineitem", "count"), order_idx, 1, 7)

    def _lineitem_count(self) -> int:
        # exact total: sum of per-order line counts, computed chunked
        if not hasattr(self, "_li_count"):
            total = 0
            for lo in range(0, self.n_orders, 4_000_000):
                hi = min(lo + 4_000_000, self.n_orders)
                total += int(self._lines_per_order(np.arange(lo, hi)).sum())
            self._li_count = total
        return self._li_count

    def max_split_rows(self, table: str) -> int:
        """Static upper bound on rows in any split (static-shape wave
        capacity for distributed scans)."""
        if table == "lineitem":
            per = self._per("lineitem")
            return min(per * 7, max(self.row_count("lineitem"), 1))
        return min(self.split_rows, max(self.row_count(table), 1))

    def num_splits(self, table: str) -> int:
        if table in ("orders", "lineitem"):
            per = self._per(table)
            return max(1, -(-self.n_orders // per))
        return max(1, -(-self.row_count(table) // self.split_rows))

    def table_version(self, table: str) -> int:
        """Generated data is immutable: a constant version marks every
        table cacheable forever (serving-tier result/subplan caches)."""
        return 0

    def _per(self, table: str) -> int:
        """Orders per split for the order-range-partitioned tables."""
        if table == "lineitem" and not self.aligned_buckets:
            return max(self.split_rows // 4, 1)
        return self.split_rows

    def _order_range(self, table: str, split: int) -> Tuple[int, int]:
        per = self._per(table)
        lo = split * per
        return lo, min(lo + per, self.n_orders)

    # -- generators ---------------------------------------------------------
    def generate_split(self, table: str, split: int) -> Dict[str, np.ndarray]:
        """Columns for one split as host numpy arrays (dictionary codes
        for VARCHAR); deterministic in (sf, table, split)."""
        if table in ("orders", "lineitem"):
            o0, o1 = self._order_range(table, split)
            return self._orders(o0, o1) if table == "orders" else self._lineitem(o0, o1)
        n = self.row_count(table)
        lo = split * self.split_rows
        hi = min(lo + self.split_rows, n)
        idx = np.arange(lo, hi)
        return getattr(self, f"_{table}")(idx)

    # each generator returns {column: np.ndarray}
    def _region(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        return {
            "r_regionkey": idx.astype(np.int64),
            "r_name": idx.astype(np.int32),
            "r_comment": (_hash_u64(1, idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    def _nation(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        region = np.asarray([r for _, r in NATIONS], dtype=np.int64)
        return {
            "n_nationkey": idx.astype(np.int64),
            "n_name": idx.astype(np.int32),
            "n_regionkey": region[idx],
            "n_comment": (_hash_u64(2, idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    def _supplier(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        s = lambda c: _seed("supplier", c)
        return {
            "s_suppkey": idx.astype(np.int64) + 1,
            "s_name": idx.astype(np.int32),
            "s_address": idx.astype(np.int32),
            "s_nationkey": _uniform_int(s("s_nationkey"), idx, 0, 24),
            "s_phone": idx.astype(np.int32),
            "s_acctbal": _uniform_int(s("s_acctbal"), idx, -99999, 999999),
            "s_comment": (_hash_u64(s("s_comment"), idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    def _customer(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        s = lambda c: _seed("customer", c)
        return {
            "c_custkey": idx.astype(np.int64) + 1,
            "c_name": idx.astype(np.int32),
            "c_address": idx.astype(np.int32),
            "c_nationkey": _uniform_int(s("c_nationkey"), idx, 0, 24),
            "c_phone": idx.astype(np.int32),
            "c_acctbal": _uniform_int(s("c_acctbal"), idx, -99999, 999999),
            "c_mktsegment": (_hash_u64(s("c_mktsegment"), idx) % 5).astype(np.int32),
            "c_comment": (_hash_u64(s("c_comment"), idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    def _part(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        s = lambda c: _seed("part", c)
        partkey = idx.astype(np.int64) + 1
        retail = self._retail_price(partkey)
        return {
            "p_partkey": partkey,
            "p_name": idx.astype(np.int32),
            "p_mfgr": (_hash_u64(s("p_mfgr"), idx) % 5).astype(np.int32),
            "p_brand": (_hash_u64(s("p_brand"), idx) % 25).astype(np.int32),
            "p_type": (_hash_u64(s("p_type"), idx) % 150).astype(np.int32),
            "p_size": _uniform_int(s("p_size"), idx, 1, 50),
            "p_container": (_hash_u64(s("p_container"), idx) % 40).astype(np.int32),
            "p_retailprice": retail,
            "p_comment": (_hash_u64(s("p_comment"), idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    def _suppkey_for(self, partkey: np.ndarray, j: np.ndarray) -> np.ndarray:
        # spec: PS_SUPPKEY = (ps_partkey + i*(S/4 + (ps_partkey-1)/S)) % S + 1
        # shared by partsupp and lineitem so l_suppkey always matches one
        # of the part's 4 suppliers.
        S = max(self.n_suppliers, 1)
        return ((partkey + j * (S // 4 + (partkey - 1) // S)) % S + 1).astype(np.int64)

    @staticmethod
    def _retail_price(partkey: np.ndarray) -> np.ndarray:
        # spec 4.2.3 (scale-2 money); shared by part and lineitem.
        return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)

    def _partsupp(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        s = lambda c: _seed("partsupp", c)
        partkey = (idx // 4).astype(np.int64) + 1
        j = idx % 4
        return {
            "ps_partkey": partkey,
            "ps_suppkey": self._suppkey_for(partkey, j),
            "ps_availqty": _uniform_int(s("ps_availqty"), idx, 1, 9999),
            "ps_supplycost": _uniform_int(s("ps_supplycost"), idx, 100, 100000),
            "ps_comment": (_hash_u64(s("ps_comment"), idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    def _orderkey(self, order_idx: np.ndarray) -> np.ndarray:
        # dbgen-style sparse keys: 8 live keys per 32-key block
        return ((order_idx >> 3) << 5 | (order_idx & 7)).astype(np.int64) + 1

    def _order_dates(self, order_idx: np.ndarray) -> np.ndarray:
        return _uniform_int(
            _seed("orders", "o_orderdate"), order_idx, MIN_ORDER_DATE, MAX_ORDER_DATE - 121
        )

    def _order_custkeys(self, order_idx: np.ndarray) -> np.ndarray:
        n = max(self.n_customers, 1)
        seed = _seed("orders", "o_custkey")
        if not self.orderless_third:
            return _uniform_int(seed, order_idx, 1, n)
        # the j-th of the n - n // 3 keys in [1, n] not divisible by 3
        j = _uniform_int(seed, order_idx, 0, n - n // 3 - 1)
        return 3 * (j // 2) + j % 2 + 1

    def _lineitem_raw(self, o0: int, o1: int):
        """Line-level arrays for orders [o0, o1) plus per-order offsets."""
        order_idx = np.arange(o0, o1)
        counts = self._lines_per_order(order_idx)
        total = int(counts.sum())
        oi = np.repeat(order_idx, counts)  # order index per line
        starts = np.cumsum(counts) - counts
        linenum = np.arange(total) - np.repeat(starts, counts) + 1
        s = lambda c: _seed("lineitem", c)
        gidx = oi * np.int64(8) + linenum  # globally unique line id
        odate_l = np.repeat(self._order_dates(order_idx), counts)

        qty = _uniform_int(s("l_quantity"), gidx, 1, 50)
        partkey = _uniform_int(s("l_partkey"), gidx, 1, max(self.n_parts, 1))
        # supplier chosen among the 4 for the part (spec 4.2.3)
        j = _uniform_int(s("l_suppj"), gidx, 0, 3)
        suppkey = self._suppkey_for(partkey, j)
        # qty is unscaled units, retail is scale-2 -> product is scale-2 money
        extprice = qty * self._retail_price(partkey)
        discount = _uniform_int(s("l_discount"), gidx, 0, 10)  # scale-2 (0.00-0.10)
        tax = _uniform_int(s("l_tax"), gidx, 0, 8)
        shipdate = odate_l + _uniform_int(s("l_shipdate"), gidx, 1, 121)
        commitdate = odate_l + _uniform_int(s("l_commitdate"), gidx, 30, 90)
        receiptdate = shipdate + _uniform_int(s("l_receiptdate"), gidx, 1, 30)
        linestatus = (shipdate > CURRENT_DATE).astype(np.int32)  # 0=F,1=O
        returned = receiptdate <= CURRENT_DATE
        rflag_rand = (_hash_u64(s("l_returnflag"), gidx) % 2).astype(np.int32)  # A or R
        returnflag = np.where(returned, np.where(rflag_rand == 0, 0, 2), 1).astype(np.int32)
        cols = {
            "l_orderkey": np.repeat(self._orderkey(order_idx), counts),
            "l_partkey": partkey,
            "l_suppkey": suppkey.astype(np.int64),
            "l_linenumber": linenum.astype(np.int64),
            "l_quantity": qty * 100,  # scale-2
            "l_extendedprice": extprice,
            "l_discount": discount,
            "l_tax": tax,
            "l_returnflag": returnflag,
            "l_linestatus": linestatus,
            "l_shipdate": shipdate.astype(np.int32),
            "l_commitdate": commitdate.astype(np.int32),
            "l_receiptdate": receiptdate.astype(np.int32),
            "l_shipinstruct": (_hash_u64(s("l_shipinstruct"), gidx) % 4).astype(np.int32),
            "l_shipmode": (_hash_u64(s("l_shipmode"), gidx) % 7).astype(np.int32),
            "l_comment": (_hash_u64(s("l_comment"), gidx) % self.COMMENT_VOCAB).astype(np.int32),
        }
        return cols, counts

    def _lineitem(self, o0: int, o1: int) -> Dict[str, np.ndarray]:
        cols, _ = self._lineitem_raw(o0, o1)
        return cols

    def _orders(self, o0: int, o1: int) -> Dict[str, np.ndarray]:
        order_idx = np.arange(o0, o1)
        s = lambda c: _seed("orders", c)
        li, counts = self._lineitem_raw(o0, o1)
        # o_totalprice = sum(extprice * (1+tax) * (1-disc)) over the order's lines
        charge = (
            li["l_extendedprice"] * (100 + li["l_tax"]) * (100 - li["l_discount"])
        ) // 10000
        ends = np.cumsum(counts)
        starts = np.concatenate([[0], ends[:-1]])
        csum = np.concatenate([[0], np.cumsum(charge)])
        totalprice = csum[ends] - csum[starts]
        # o_orderstatus: F if all lines F, O if all O, else P
        ls = li["l_linestatus"]
        lsum = np.concatenate([[0], np.cumsum(ls)])
        o_sum = lsum[ends] - lsum[starts]
        status = np.where(o_sum == 0, 0, np.where(o_sum == counts, 1, 2)).astype(np.int32)
        return {
            "o_orderkey": self._orderkey(order_idx),
            "o_custkey": self._order_custkeys(order_idx),
            "o_orderstatus": status,
            "o_totalprice": totalprice.astype(np.int64),
            "o_orderdate": self._order_dates(order_idx).astype(np.int32),
            "o_orderpriority": (_hash_u64(s("o_orderpriority"), order_idx) % 5).astype(np.int32),
            "o_clerk": (_hash_u64(s("o_clerk"), order_idx) % max(int(1000 * self.sf), 1)).astype(np.int32),
            "o_shippriority": np.zeros(len(order_idx), dtype=np.int64),
            "o_comment": (_hash_u64(s("o_comment"), order_idx) % self.COMMENT_VOCAB).astype(np.int32),
        }

    # -- Page production ----------------------------------------------------
    def schema(self, table: str) -> List[Tuple[str, Type]]:
        return SCHEMAS[table]

    def dictionary_for(self, table: str, column: str) -> Optional[Dictionary]:
        t = dict(SCHEMAS[table])[column]
        return self._dict(column) if t.is_string else None

    def page_for_split(self, table: str, split: int, capacity: Optional[int] = None) -> Page:
        cols = self.generate_split(table, split)
        schema = SCHEMAS[table]
        arrays = [cols[name] for name, _ in schema]
        types = [t for _, t in schema]
        dicts = [self.dictionary_for(table, name) for name, _ in schema]
        return Page.from_arrays(arrays, types, dictionaries=dicts, capacity=capacity)

    def pages(self, table: str, capacity: Optional[int] = None) -> Iterator[Page]:
        for i in range(self.num_splits(table)):
            yield self.page_for_split(table, i, capacity=capacity)

    def column_names(self, table: str) -> List[str]:
        return [n for n, _ in SCHEMAS[table]]

    def table_names(self) -> List[str]:
        return list(SCHEMAS.keys())

    def primary_key(self, table: str) -> Optional[List[str]]:
        return {
            "region": ["r_regionkey"],
            "nation": ["n_nationkey"],
            "supplier": ["s_suppkey"],
            "customer": ["c_custkey"],
            "part": ["p_partkey"],
            "partsupp": ["ps_partkey", "ps_suppkey"],
            "orders": ["o_orderkey"],
            "lineitem": ["l_orderkey", "l_linenumber"],
        }.get(table)

    def bucketing(self, table: str) -> Optional[Tuple[List[str], tuple, int]]:
        """(bucket_columns, alignment_token, bucket_count) — split index
        IS the bucket id; orders/lineitem share order-range buckets when
        ``aligned_buckets`` (ConnectorNodePartitioningProvider analog,
        presto-tpch TpchNodePartitioningProvider)."""
        if table in ("orders", "lineitem") and self._per("orders") == self._per(table):
            col = "o_orderkey" if table == "orders" else "l_orderkey"
            token = ("tpch-order-range", self.sf, self._per(table))
            return ([col], token, self.num_splits(table))
        return None

    def sort_order(self, table: str) -> Optional[List[str]]:
        """The generator emits rows in primary-key order (sequential
        keys per split), so the physical ordering IS the primary key —
        the streaming-aggregation trigger (ConnectorMetadata
        local-properties analog)."""
        return self.primary_key(table)

    def column_ndv(self, table: str, column: str) -> Optional[int]:
        """Distinct-value counts where the domain width overstates them
        (sparse keys: orderkeys skip 8-of-32 slots). Reference analog:
        presto-tpch/.../statistics/ ColumnStatisticsData distinctValues."""
        ndvs: Dict[str, int] = {
            "o_orderkey": self.n_orders,
            "l_orderkey": self.n_orders,
            # spec: 1/3 hold no orders (true of the data under
            # orderless_third; the default draw reaches nearly all)
            "o_custkey": int(self.n_customers * 2 / 3),
            "l_partkey": self.n_parts,
            "l_suppkey": self.n_suppliers,
            "ps_partkey": self.n_parts,
            "ps_suppkey": self.n_suppliers,
        }
        return ndvs.get(column)

    def column_domain(self, table: str, column: str) -> Optional[Tuple[int, int]]:
        """Known (lo, hi) of a column in its device representation —
        the stats feed for exact key packing (planner/exact joins).
        Reference analog: presto-tpch/.../statistics/ column stats."""
        t = dict(SCHEMAS[table])[column]
        if t.is_string:
            return (0, len(self.dictionary_for(table, column)) - 1)
        max_orderkey = int(((self.n_orders - 1) >> 3) << 5 | ((self.n_orders - 1) & 7)) + 1
        # Money, in cents (scale 2), each from the formula that
        # generates it.  A declared domain is a licence: the executor
        # drops an arithmetic guard and sizes a sum's accumulator by it
        # (docs/static-analysis.md), so a bound here must hold for every
        # row the generator can emit at any scale factor.
        # _retail_price: 90000 + (k // 10) % 20001 + 100 * (k % 1000)
        retail = (90000, 90000 + 20000 + 100 * 999)  # 209900
        # l_extendedprice = qty * retail, qty uniform in [1, 50]
        extprice = (retail[0], 50 * retail[1])  # 10,495,000
        # o_totalprice = sum over an order's 1..7 lines
        # (_lines_per_order) of ext * (100 + tax) * (100 - disc) // 10000,
        # tax in [0, 8], disc in [0, 10]
        totalprice = (extprice[0] * 100 * 90 // 10000,
                      7 * (extprice[1] * 108 * 100 // 10000))
        doms: Dict[str, Tuple[int, int]] = {
            "s_acctbal": (-99999, 999999),  # _uniform_int(-99999, 999999)
            "c_acctbal": (-99999, 999999),  # the same draw
            "p_retailprice": retail,
            "ps_supplycost": (100, 100000),  # _uniform_int(100, 100000)
            "o_totalprice": totalprice,
            "l_extendedprice": extprice,
            "r_regionkey": (0, 4),
            "n_nationkey": (0, 24),
            "n_regionkey": (0, 4),
            "s_suppkey": (1, self.n_suppliers),
            "s_nationkey": (0, 24),
            "c_custkey": (1, self.n_customers),
            "c_nationkey": (0, 24),
            "p_partkey": (1, self.n_parts),
            "p_size": (1, 50),
            "ps_partkey": (1, self.n_parts),
            "ps_suppkey": (1, self.n_suppliers),
            "ps_availqty": (1, 9999),
            "o_orderkey": (1, max_orderkey),
            "o_custkey": (1, self.n_customers),
            "o_orderdate": (MIN_ORDER_DATE, MAX_ORDER_DATE),
            "o_shippriority": (0, 0),
            "l_orderkey": (1, max_orderkey),
            "l_partkey": (1, self.n_parts),
            "l_suppkey": (1, self.n_suppliers),
            "l_linenumber": (1, 7),
            "l_quantity": (100, 5000),
            "l_discount": (0, 10),
            "l_tax": (0, 8),
            "l_shipdate": (MIN_ORDER_DATE + 1, MAX_ORDER_DATE + 121),
            "l_commitdate": (MIN_ORDER_DATE + 30, MAX_ORDER_DATE + 90),
            "l_receiptdate": (MIN_ORDER_DATE + 2, MAX_ORDER_DATE + 151),
        }
        return doms.get(column)


def _address(i: int, salt: int) -> str:
    h = int(_hash_u64(salt, np.asarray([i]))[0])
    chars = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ,"
    n = 10 + h % 25
    out = []
    x = h
    for _ in range(n):
        x = (x * 6364136223846793005 + 1442695040888963407) & 0xFFFFFFFFFFFFFFFF
        out.append(chars[(x >> 33) % len(chars)])
    return "".join(out)
