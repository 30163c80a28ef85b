"""The TPC-H population, made from `--seed` with numpy alone: the
benchmark's own generator, shaped as the specification's clause 4.2.3
shapes it (dbgen), so that the statements select what they select on a
published TPC-H database. It imports nothing of the program; the loader
hands these arrays to the program, the references read them.

Kept from clause 4.2.3: the cardinalities (SF x 10,000 suppliers,
200,000 parts, 4 partsupp rows a part, 150,000 customers, 10 orders a
customer, 1 to 7 lineitems an order); sparse order keys (the first 8
of every 32); no order for a customer key divisible by 3; the
part-supplier formula; p_retailprice and l_extendedprice from the part
key; o_totalprice and o_orderstatus from the order's lines; ship,
commit and receipt dates from the order date; l_returnflag and
l_linestatus from those dates against 1995-06-17; every value range.

Not dbgen: the random streams are numpy's, so no row equals dbgen's;
the number of lineitem rows is brought to a target (the configuration's
published count) by one line more or less on a few orders in a
thousand; comments come from small vocabularies (no statement of the
benchmark reads them, and the program codes strings by dictionary);
addresses are 25 random characters.

A table is {column: Column}; a Column is (kind, data[, dictionary]):
kind `int` (int64), `dec2` (int64, hundredths), `date` (int32, days
since 1970-01-01) or `str` (int32 codes into a sorted dictionary of
str objects)."""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class Column(NamedTuple):
    kind: str
    data: np.ndarray
    dictionary: Optional[np.ndarray] = None


NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")]
COLORS = sorted(
    "almond antique aquamarine azure beige bisque black blanched blue blush brown burlywood "
    "burnished chartreuse chiffon chocolate coral cornflower cornsilk cream cyan dark deep dim "
    "dodger drab firebrick floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid pale papaya peach "
    "peru pink plum powder puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise violet wheat white "
    "yellow".split())
COMMENTS = [
    "carefully ironic deposits wake furiously",
    "quickly bold accounts nag blithely",
    "special packages among the requests detect slyly",
    "express special pending requests are final deposits",
    "silent foxes boost across the ironic accounts",
    "pending theodolites haggle quickly",
    "special deposits cajole; even requests sleep",
    "regular ideas use slyly after the furious dependencies",
    "ironic pinto beans integrate carefully",
    "asymptotes above the slow requests sleep finally",
]
SUPPLIER_COMPLAINT = "blithely Customer accounts sleep; furious Complaints nag"
SUPPLIER_RECOMMEND = "carefully Customer deposits wake; final Recommends haggle"


def _days(date: str) -> int:
    return int(np.datetime64(date, "D").astype(np.int64))


START, END, CURRENT = _days("1992-01-01"), _days("1998-12-31"), _days("1995-06-17")
LINEITEM_ROWS_PER_SF = 6_000_000  # the expected count: 1.5 M orders x 4 lines


def _draw(rng, lo: int, hi: int, n: int, dtype=np.int64) -> np.ndarray:
    """Uniform whole numbers in [lo, hi]."""
    small = np.int32 if max(abs(lo), abs(hi)) < 2**31 - 1 else np.int64
    return rng.integers(lo, hi + 1, n, dtype=small).astype(dtype, copy=False)


def _coded(codes: np.ndarray, universe) -> Column:
    """Codes into `universe` (in its own order) as a sorted dictionary's codes."""
    order = np.argsort(np.array(universe, dtype=object), kind="stable")
    remap = np.empty(len(universe), dtype=np.int32)
    remap[order] = np.arange(len(universe), dtype=np.int32)
    return Column("str", remap[codes], np.array(universe, dtype=object)[order])


def _numbered(prefix: str, keys: np.ndarray) -> Column:
    """`<prefix>#000000001`-style names of ascending keys: zero-padded,
    so the names sort as the keys do."""
    names = np.array([f"{prefix}#{k:09d}" for k in keys.tolist()], dtype=object)
    return Column("str", np.arange(len(keys), dtype=np.int32), names)


def _by_key(keys: np.ndarray, text) -> Column:
    """Strings that sort as their integer keys do: the dictionary is the
    text of each distinct key."""
    distinct, codes = np.unique(keys, return_inverse=True)
    words = np.array([text(k) for k in distinct.tolist()], dtype=object)
    return Column("str", codes.astype(np.int32), words)


def _colour_names(keys: np.ndarray) -> Column:
    """Five colours a part, from the key's five digits to the base of
    the colour count, first colour first. No colour is the start of
    another, so the names sort as the keys do."""
    distinct, codes = np.unique(keys, return_inverse=True)
    palette, words, rest = np.array(COLORS, dtype=object), [], distinct
    for _ in range(5):
        rest, digit = np.divmod(rest, len(COLORS))
        words.append(palette[digit])
    names = np.array([" ".join(t) for t in zip(*reversed(words))], dtype=object)
    return Column("str", codes.astype(np.int32), names)


def _phones(rng, nation: np.ndarray) -> Column:
    """CC-LLL-LLL-LLLL with the country code nation + 10 (clause 4.2.2.9)."""
    n = len(nation)
    key = (((nation + 10) * 1000 + _draw(rng, 100, 999, n)) * 1000
           + _draw(rng, 100, 999, n)) * 10000 + _draw(rng, 1000, 9999, n)

    def text(k):
        return f"{k // 10**10}-{k // 10**7 % 1000}-{k // 10**4 % 1000}-{k % 10**4}"

    return _by_key(key, text)


def _addresses(rng, n: int) -> Column:
    alphabet = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ ,",
                             dtype=np.uint8)
    raw = alphabet[rng.integers(0, len(alphabet), (n, 25), dtype=np.uint8)]
    distinct, codes = np.unique(raw.view("S25").ravel(), return_inverse=True)
    return Column("str", codes.astype(np.int32), distinct.astype("U25").astype(object))


def _comments(rng, n: int) -> Column:
    return _coded(_draw(rng, 0, len(COMMENTS) - 1, n, np.int32), COMMENTS)


def _retail_price(partkey: np.ndarray) -> np.ndarray:
    """Clause 4.2.3: 90000 + ((key / 10) mod 20001) + 100 * (key mod 1000), in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _supplier_of(partkey: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    """Clause 4.2.3: the i-th (0..3) of a part's four suppliers."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1


def _lines_per_order(rng, n_orders: int, target: Optional[int]) -> np.ndarray:
    """1 to 7 lines an order; with a target, a few orders get one line
    more or fewer so that the table has exactly that many rows."""
    counts = _draw(rng, 1, 7, n_orders)
    if target is not None:
        diff = int(target - counts.sum())
        room = np.nonzero(counts < 7 if diff > 0 else counts > 1)[0]
        counts[rng.choice(room, abs(diff), replace=False)] += 1 if diff > 0 else -1
    return counts


def generate(scale: float, seed: int, lineitem_rows: Optional[int] = None) -> dict:
    """{table: {column: Column}} at scale factor `scale` from `seed`."""
    rng = np.random.default_rng(seed)
    n_supp = max(int(10_000 * scale), 100)
    n_part = max(int(200_000 * scale), 1000)
    n_cust = max(int(150_000 * scale), 150)
    n_orders = 10 * n_cust
    tables = {}

    tables["region"] = {
        "r_regionkey": Column("int", np.arange(5, dtype=np.int64)),
        "r_name": _coded(np.arange(5), REGIONS),
        "r_comment": _comments(rng, 5),
    }
    tables["nation"] = {
        "n_nationkey": Column("int", np.arange(25, dtype=np.int64)),
        "n_name": _coded(np.arange(25), [name for name, _ in NATIONS]),
        "n_regionkey": Column("int", np.array([r for _, r in NATIONS], dtype=np.int64)),
        "n_comment": _comments(rng, 25),
    }

    suppkey = np.arange(1, n_supp + 1, dtype=np.int64)
    s_nation = _draw(rng, 0, 24, n_supp)
    # clause 4.2.3: 5 x SF suppliers complain, 5 x SF recommend
    s_words = COMMENTS + [SUPPLIER_COMPLAINT, SUPPLIER_RECOMMEND]
    s_comment = _draw(rng, 0, len(COMMENTS) - 1, n_supp, np.int32)
    marked = rng.choice(n_supp, 2 * max(int(5 * scale), 1), replace=False)
    s_comment[marked[: len(marked) // 2]] = len(COMMENTS)
    s_comment[marked[len(marked) // 2:]] = len(COMMENTS) + 1
    tables["supplier"] = {
        "s_suppkey": Column("int", suppkey),
        "s_name": _numbered("Supplier", suppkey),
        "s_address": _addresses(rng, n_supp),
        "s_nationkey": Column("int", s_nation),
        "s_phone": _phones(rng, s_nation),
        "s_acctbal": Column("dec2", _draw(rng, -99_999, 999_999, n_supp)),
        "s_comment": _coded(s_comment, s_words),
    }

    partkey = np.arange(1, n_part + 1, dtype=np.int64)
    retail = _retail_price(partkey)
    name_key = np.zeros(n_part, dtype=np.int64)
    for _ in range(5):
        name_key = name_key * len(COLORS) + _draw(rng, 0, len(COLORS) - 1, n_part)
    mfgr = _draw(rng, 1, 5, n_part, np.int32)
    brand = (mfgr - 1) * 5 + _draw(rng, 1, 5, n_part, np.int32) - 1

    tables["part"] = {
        "p_partkey": Column("int", partkey),
        "p_name": _colour_names(name_key),
        "p_mfgr": _coded(mfgr - 1, [f"Manufacturer#{m}" for m in range(1, 6)]),
        "p_brand": _coded(brand, [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]),
        "p_type": _coded(_draw(rng, 0, len(TYPES) - 1, n_part, np.int32), TYPES),
        "p_size": Column("int", _draw(rng, 1, 50, n_part)),
        "p_container": _coded(_draw(rng, 0, len(CONTAINERS) - 1, n_part, np.int32), CONTAINERS),
        "p_retailprice": Column("dec2", retail),
        "p_comment": _comments(rng, n_part),
    }

    ps_part = np.repeat(partkey, 4)
    tables["partsupp"] = {
        "ps_partkey": Column("int", ps_part),
        "ps_suppkey": Column("int", _supplier_of(ps_part, np.tile(np.arange(4), n_part), n_supp)),
        "ps_availqty": Column("int", _draw(rng, 1, 9999, 4 * n_part)),
        "ps_supplycost": Column("dec2", _draw(rng, 100, 100_000, 4 * n_part)),
        "ps_comment": _comments(rng, 4 * n_part),
    }

    custkey = np.arange(1, n_cust + 1, dtype=np.int64)
    c_nation = _draw(rng, 0, 24, n_cust)
    tables["customer"] = {
        "c_custkey": Column("int", custkey),
        "c_name": _numbered("Customer", custkey),
        "c_address": _addresses(rng, n_cust),
        "c_nationkey": Column("int", c_nation),
        "c_phone": _phones(rng, c_nation),
        "c_acctbal": Column("dec2", _draw(rng, -99_999, 999_999, n_cust)),
        "c_mktsegment": _coded(_draw(rng, 0, 4, n_cust, np.int32), SEGMENTS),
        "c_comment": _comments(rng, n_cust),
    }

    # orders and their lines
    index = np.arange(1, n_orders + 1, dtype=np.int64)
    orderkey = ((index >> 3) << 5) | (index & 7)  # the first 8 of every 32 keys
    buyers = n_cust - n_cust // 3  # customer keys not divisible by 3
    j = _draw(rng, 0, buyers - 1, n_orders)
    o_cust = 3 * (j // 2) + j % 2 + 1
    o_date = _draw(rng, START, END - 151, n_orders, np.int32)
    counts = _lines_per_order(rng, n_orders, lineitem_rows)
    n = int(counts.sum())
    first = np.cumsum(counts) - counts
    of_order = np.repeat(np.arange(n_orders, dtype=np.int64), counts)

    l_part = _draw(rng, 1, n_part, n)
    quantity = _draw(rng, 1, 50, n)
    price = quantity * retail[l_part - 1]
    discount = _draw(rng, 0, 10, n)
    tax = _draw(rng, 0, 8, n)
    ship = o_date[of_order] + _draw(rng, 1, 121, n, np.int32)
    commit = o_date[of_order] + _draw(rng, 30, 90, n, np.int32)
    receipt = ship + _draw(rng, 1, 30, n, np.int32)
    returned = np.where(receipt <= CURRENT, _draw(rng, 0, 1, n, np.int32), 2)  # R, A | N
    open_line = ship > CURRENT
    tables["lineitem"] = {
        "l_orderkey": Column("int", orderkey[of_order]),
        "l_partkey": Column("int", l_part),
        "l_suppkey": Column("int", _supplier_of(l_part, _draw(rng, 0, 3, n), n_supp)),
        "l_linenumber": Column("int", np.arange(n, dtype=np.int64) - first[of_order] + 1),
        "l_quantity": Column("dec2", quantity * 100),
        "l_extendedprice": Column("dec2", price),
        "l_discount": Column("dec2", discount),
        "l_tax": Column("dec2", tax),
        "l_returnflag": _coded(returned, ["R", "A", "N"]),
        "l_linestatus": _coded(open_line.astype(np.int32), ["F", "O"]),
        "l_shipdate": Column("date", ship),
        "l_commitdate": Column("date", commit),
        "l_receiptdate": Column("date", receipt),
        "l_shipinstruct": _coded(_draw(rng, 0, 3, n, np.int32), INSTRUCTIONS),
        "l_shipmode": _coded(_draw(rng, 0, 6, n, np.int32), MODES),
        "l_comment": _comments(rng, n),
    }

    # clause 4.2.3: the order's total and status follow from its lines
    charged = price * (100 - discount) // 100 * (100 + tax) // 100
    open_lines = np.add.reduceat(open_line.astype(np.int64), first)
    status = np.where(open_lines == 0, 0, np.where(open_lines == counts, 1, 2))
    n_clerk = max(int(1000 * scale), 1)
    clerk = _draw(rng, 1, n_clerk, n_orders)
    tables["orders"] = {
        "o_orderkey": Column("int", orderkey),
        "o_custkey": Column("int", o_cust),
        "o_orderstatus": _coded(status, ["F", "O", "P"]),
        "o_totalprice": Column("dec2", np.add.reduceat(charged, first)),
        "o_orderdate": Column("date", o_date),
        "o_orderpriority": _coded(_draw(rng, 0, 4, n_orders, np.int32), PRIORITIES),
        "o_clerk": _by_key(clerk, lambda k: f"Clerk#{k:09d}"),
        "o_shippriority": Column("int", np.zeros(n_orders, dtype=np.int64)),
        "o_comment": _comments(rng, n_orders),
    }
    return tables
