"""The TPC-DS web channel's population, made from `--seed` with numpy
alone: the five tables the specification's Query 95 reads, every column
of each at its published type (specification v3.2.0, clauses 2.3-2.4),
shaped as dsdgen shapes them. It imports nothing of the program; the
loader hands these arrays to the program, the references read them.

Kept from the specification and from dsdgen: the row counts of Table
3-2 (the five tables' at SF=1 are in `ROWS_SF1`); 60,000 orders a scale
factor of 8 to 16 items each, uniform, an item at most once an order;
the sold date, the customers and the ship-to address drawn per order,
the ship date (1 to 120 days after the sold date), the item, the web
site, the ship mode, the warehouse (5 at SF=1) and the promotion per
item; sold dates over the five sales years 1998-2002; `d_date_sk` the
Julian day number, 2415022 for 1900-01-02, over 73,049 days; one item
in ten returned, the return naming its (order, item); the pricing
chain of dsdgen's `set_pricing` in whole cents; NULLs as dsdgen's
`nullSet` makes them (a row in `NULL_ROW_SHARE` may lack values, and
then lacks each nullable column with probability one half), never in a
primary key: `ws_item_sk`, `ws_order_number`, `wr_item_sk`,
`wr_order_number`, the dimensions' surrogate and business keys and the
whole of `date_dim`; `web_company_name` the syllable name of
`web_company_id` 1..6 (the rows take the ids in turn, a sixth of
them each as dsdgen's uniform draw expects, so that every name is
some row's on every seed); an address's state drawn by its
share of the nation's counties.

Not dsdgen: the random streams are numpy's, so no row equals dsdgen's;
the `web_sales` count, which dsdgen's seeds happen to give, is reached
by one item more or fewer on a few orders in a hundred; a site is drawn
uniformly and not by the revision that was current on the sold date;
street names, cities, counties, managers and descriptions come from
small vocabularies (no statement of the benchmark reads them, and the
program codes strings by dictionary).

A table is {column: Column}; a Column is (kind, data, dictionary,
valid): kind `int` (int64), `dec2` (int64, hundredths), `date` (int32,
days since 1970-01-01) or `str` (int32 codes into a sorted dictionary
of str objects); `valid` is False where the value is NULL (the data
there is 0)."""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class Column(NamedTuple):
    kind: str
    data: np.ndarray
    dictionary: Optional[np.ndarray]
    valid: np.ndarray


ROWS_SF1 = {  # specification v3.2.0, Table 3-2, SF=1
    "web_sales": 719_384, "web_returns": 71_763, "date_dim": 73_049,
    "customer_address": 50_000, "web_site": 30,
}
ORDERS_PER_SF = 60_000  # dsdgen's web orders; 8-16 items each: 720,000 expected
ITEMS_MIN, ITEMS_MAX = 8, 16
RETURN_SHARE = ROWS_SF1["web_returns"] / ROWS_SF1["web_sales"]  # dsdgen: one item in ten
NULL_ROW_SHARE = 0.09  # x 1/2 a column: about 4.5 % of a nullable column is NULL
# rows of the dimensions the fact tables point into, at SF=1 (Table 3-2)
DIMENSION_ROWS_SF1 = {
    "customer": 100_000, "customer_demographics": 1_920_800, "household_demographics": 7_200,
    "item": 18_000, "time_dim": 86_400, "web_page": 60, "ship_mode": 20, "warehouse": 5,
    "promotion": 300, "reason": 35,
}
JULIAN_OF_EPOCH = 2_440_588  # the Julian day number of 1970-01-01
FIRST_DATE_SK = 2_415_022  # 1900-01-02
SYLLABLES = ["ought", "able", "pri", "ese", "anti", "cally"]  # web_company_id 1..6

# counties a state (the FIPS list dsdgen draws an address's county from)
STATE_COUNTIES = {
    "AL": 67, "AK": 27, "AZ": 15, "AR": 75, "CA": 58, "CO": 64, "CT": 8, "DE": 3, "DC": 1,
    "FL": 67, "GA": 159, "HI": 5, "ID": 44, "IL": 102, "IN": 92, "IA": 99, "KS": 105,
    "KY": 120, "LA": 64, "ME": 16, "MD": 24, "MA": 14, "MI": 83, "MN": 87, "MS": 82,
    "MO": 115, "MT": 56, "NE": 93, "NV": 17, "NH": 10, "NJ": 21, "NM": 33, "NY": 62,
    "NC": 100, "ND": 53, "OH": 88, "OK": 77, "OR": 36, "PA": 67, "RI": 5, "SC": 46,
    "SD": 66, "TN": 95, "TX": 254, "UT": 29, "VT": 14, "VA": 134, "WA": 39, "WV": 55,
    "WI": 72, "WY": 23,
}
STREET_NAMES = ["Main", "Oak", "Park", "Elm", "Lake", "Hill", "Maple", "Cedar", "View", "Pine",
                "Washington", "Second", "Third", "Fourth", "Fifth", "Sixth", "Ridge", "Church",
                "Walnut", "Spring", "River", "Sunset", "Railroad", "Jackson", "Lincoln", "Mill",
                "Forest", "Highland", "Center", "North", "South", "East", "West", "Green",
                "Franklin", "Johnson", "Williams", "Smith", "Davis", "Wilson", "Adams", "Dogwood",
                "Hickory", "Locust", "Poplar", "Chestnut", "Birch", "Ash", "Valley", "Meadow",
                "Woodland", "College", "First", "Broadway", "Cherry", "Laurel", "Sycamore", "Spruce",
                "Willow", "Lakeview", "Lee", "Madison", "Jefferson", "Elevnth", "Tenth", "Ninth",
                "Eigth", "Seventh", "Twelfth", "Thirteenth", "Fourteenth", "Fifteenth", "Hillcrest",
                "Pine Oak", "Oak Elm", "Park Main", "Lake Hill", "Cedar View", "Maple Ridge"]
STREET_TYPES = ["Street", "ST", "Avenue", "Ave", "Boulevard", "Blvd", "Road", "RD", "Parkway",
                "Pkwy", "Way", "Wy", "Drive", "Dr.", "Circle", "Cir.", "Lane", "Ln", "Court", "Ct."]
CITIES = ["Fairview", "Midway", "Oak Grove", "Five Points", "Riverside", "Pleasant Hill",
          "Centerville", "Mount Pleasant", "Oakland", "Liberty", "Union", "Salem", "Greenville",
          "Franklin", "Springfield", "Clinton", "Georgetown", "Bethel", "Marion", "Shiloh",
          "Oak Hill", "Pleasant Grove", "Glendale", "Lakeside", "Spring Hill", "Concord",
          "Antioch", "Kingston", "Newport", "Arlington", "Hopewell", "Jamestown", "Lebanon",
          "Highland Park", "Woodville", "Friendship", "Enterprise", "Hamilton", "Farmington",
          "Macedonia", "Sunnyside", "Mount Zion", "Walnut Grove", "Bridgeport", "Harmony",
          "Mount Olive", "New Hope", "Wildwood", "Edgewood", "Lakeview", "Glenwood", "Unionville",
          "Waterloo", "Plainview", "Summit", "Riverdale", "Ashland", "Buena Vista", "Deerfield",
          "Clifton", "Stringtown", "Red Hill", "White Oak", "Providence", "Woodlawn", "Belmont"]
COUNTY_WORDS = ["Washington", "Jefferson", "Franklin", "Jackson", "Lincoln", "Madison", "Clay",
                "Montgomery", "Union", "Marion", "Monroe", "Wayne", "Grant", "Greene", "Warren",
                "Carroll", "Adams", "Douglas", "Clark", "Lake", "Lee", "Marshall", "Polk", "Crawford",
                "Fayette", "Johnson", "Morgan", "Scott", "Calhoun", "Lawrence", "Logan", "Perry",
                "Pike", "Hamilton", "Hancock", "Henry", "Benton", "Shelby", "Knox", "Putnam"]
LOCATION_TYPES = ["apartment", "condo", "single family"]
FIRST_NAMES = ["James", "John", "Robert", "Michael", "William", "David", "Richard", "Charles",
               "Joseph", "Thomas", "Mary", "Patricia", "Linda", "Barbara", "Elizabeth", "Jennifer"]
LAST_NAMES = ["Smith", "Johnson", "Williams", "Jones", "Brown", "Davis", "Miller", "Wilson",
              "Moore", "Taylor", "Anderson", "Thomas", "Jackson", "White", "Harris", "Martin"]
MARKET_CLASSES = [
    "Completely excellent things ought to",
    "Mammals take at all. Profound weeks must know parts",
    "Wide, final representat",
    "Lucky passengers know. Red details will not hang alive, international s",
    "Well similar decisions used to keep hardly democratic, personal priorities",
    "Grey lines ought to result indeed centres. Tod",
    "About rural reasons shall no",
    "Rich, deep types go. Safe premises ought to",
]
MARKET_DESCRIPTIONS = [
    "Subjects may think on a times. New, back services will keep along a runs; trees engage financial models",
    "As existing others matter today. Defensive, new offices used to",
    "Facilities mean now. Pregnant tests shall not try in a effects. Also rare funds assist",
    "Simple, unknown measures must not give slowly new, wrong policies. New children go bad",
    "Also other women know now. Different, annual issues tell",
    "Quick sisters stay regularly for example",
    "Only likely practices could not expect only important, dangerous",
    "Dead, great states let together practices",
]
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday", "Saturday"]
SALES_START = "1998-01-01"  # the five sales years
SALES_END = "2002-12-31"
TODAY = "2003-01-08"  # dsdgen's CURRENT_DAY, for date_dim's d_current_* flags


def _days(date: str) -> int:
    return int(np.datetime64(date, "D").astype(np.int64))


def _draw(rng, lo: int, hi: int, n: int, dtype=np.int64) -> np.ndarray:
    """Uniform whole numbers in [lo, hi]."""
    return rng.integers(lo, hi + 1, n, dtype=np.int64).astype(dtype, copy=False)


def _all_valid(n: int) -> np.ndarray:
    return np.ones(n, dtype=bool)


def _col(kind: str, data, valid=None, dictionary=None) -> Column:
    data = np.asarray(data)
    valid = _all_valid(len(data)) if valid is None else valid
    if not valid.all():
        data = np.where(valid, data, 0).astype(data.dtype, copy=False)
    return Column(kind, data, dictionary, valid)


def _ints(data, valid=None) -> Column:
    return _col("int", np.asarray(data, dtype=np.int64), valid)


def _cents(data, valid=None) -> Column:
    return _col("dec2", np.asarray(data, dtype=np.int64), valid)


def _dates(data, valid=None) -> Column:
    return _col("date", np.asarray(data, dtype=np.int32), valid)


def _coded(codes, universe, valid=None) -> Column:
    """Codes into `universe` (in its own order) as a sorted dictionary's codes."""
    words = np.array(universe, dtype=object)
    order = np.argsort(words, kind="stable")
    remap = np.empty(len(universe), dtype=np.int32)
    remap[order] = np.arange(len(universe), dtype=np.int32)
    return _col("str", remap[np.asarray(codes)], valid, words[order])


def _by_key(keys, text, valid=None) -> Column:
    """Strings that sort as their integer keys do: the dictionary is the
    text of each distinct key."""
    distinct, codes = np.unique(np.asarray(keys), return_inverse=True)
    words = np.array([text(k) for k in distinct.tolist()], dtype=object)
    return _col("str", codes.astype(np.int32), valid, words)


def _business_ids(keys) -> Column:
    """dsdgen's 16-character business keys: the key's base-26 digits as
    letters, least digit first, padded with `A`."""
    def text(k: int) -> str:
        out = []
        for _ in range(8):
            out.append(chr(ord("A") + k % 26))
            k //= 26
        return "AAAAAAAA" + "".join(out)

    keys = np.asarray(keys)
    words = np.array([text(k) for k in keys.tolist()], dtype=object)
    order = np.argsort(words, kind="stable")
    codes = np.empty(len(keys), dtype=np.int32)
    codes[order] = np.arange(len(keys), dtype=np.int32)
    return _col("str", codes, None, words[order])


class _Nulls:
    """dsdgen's `nullSet`: a row in NULL_ROW_SHARE may lack values, and
    then lacks each nullable column with probability one half."""

    def __init__(self, rng, n: int):
        self.rng, self.row = rng, rng.random(n) < NULL_ROW_SHARE

    def valid(self) -> np.ndarray:
        return ~(self.row & (self.rng.random(len(self.row)) < 0.5))


def items_per_order(rng, n_orders: int, target: Optional[int]) -> np.ndarray:
    """8 to 16 items an order, uniform; with a target, a few orders get
    one item more or fewer so that the table has exactly that many rows."""
    counts = _draw(rng, ITEMS_MIN, ITEMS_MAX, n_orders)
    if target is not None:
        diff = int(target - counts.sum())
        room = np.nonzero(counts < ITEMS_MAX if diff > 0 else counts > ITEMS_MIN)[0]
        counts[rng.choice(room, abs(diff), replace=False)] += 1 if diff > 0 else -1
    return counts


def sizes(scale: float) -> dict:
    """Row counts of the tables and of the dimensions pointed into, at
    scale factor `scale`: Table 3-2's at 1, in proportion (and at least
    a handful) below it. date_dim and web_site do not scale down."""
    def part(n, least):
        return max(int(round(n * scale)), least)

    out = {
        "orders": part(ORDERS_PER_SF, 50),
        "customer_address": part(ROWS_SF1["customer_address"], 500),
        "date_dim": ROWS_SF1["date_dim"], "web_site": ROWS_SF1["web_site"],
    }
    for name, n in DIMENSION_ROWS_SF1.items():
        out[name] = n if n <= 300 or name == "time_dim" else part(n, 100)
    out["web_sales"] = ROWS_SF1["web_sales"] if scale == 1 else None  # None: what comes
    return out


def _date_dim() -> dict:
    n = ROWS_SF1["date_dim"]
    sk = FIRST_DATE_SK + np.arange(n, dtype=np.int64)
    days = (sk - JULIAN_OF_EPOCH).astype(np.int32)
    d = days.astype("datetime64[D]")
    year = d.astype("datetime64[Y]").astype(np.int64) + 1970
    month_index = d.astype("datetime64[M]").astype(np.int64)  # months since 1970-01
    moy = month_index % 12 + 1
    first_dom = d.astype("datetime64[M]").astype("datetime64[D]")
    last_dom = (d.astype("datetime64[M]") + 1).astype("datetime64[D]") - 1
    dom = (d - first_dom).astype(np.int64) + 1
    dow = (days.astype(np.int64) + 4) % 7  # 1970-01-01 was a Thursday; 0 is Sunday
    qoy = (moy - 1) // 3 + 1
    month_seq = (year - 1900) * 12 + moy - 1
    quarter_seq = (year - 1900) * 4 + qoy
    week_seq = (sk - FIRST_DATE_SK + 1) // 7 + 1  # 1900-01-01 was a Monday
    holiday = ((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4)) | ((moy == 12) & (dom == 25))
    today = _days(TODAY)
    same = lambda a, b: a == b[np.searchsorted(days, today)]
    yn = lambda flags, valid=None: _coded(flags.astype(np.int32), ["N", "Y"], valid)
    return {
        "d_date_sk": _ints(sk),
        "d_date_id": _business_ids(sk),
        "d_date": _dates(days),
        "d_month_seq": _ints(month_seq),
        "d_week_seq": _ints(week_seq),
        "d_quarter_seq": _ints(quarter_seq),
        "d_year": _ints(year),
        "d_dow": _ints(dow),
        "d_moy": _ints(moy),
        "d_dom": _ints(dom),
        "d_qoy": _ints(qoy),
        "d_fy_year": _ints(year),
        "d_fy_quarter_seq": _ints(quarter_seq),
        "d_fy_week_seq": _ints(week_seq),
        "d_day_name": _coded(dow, DAY_NAMES),
        "d_quarter_name": _by_key(year * 10 + qoy, lambda k: f"{k // 10}Q{k % 10}"),
        "d_holiday": yn(holiday),
        "d_weekend": yn((dow == 0) | (dow == 6)),
        "d_following_holiday": yn(np.concatenate([[False], holiday[:-1]])),
        "d_first_dom": _ints(first_dom.astype(np.int64) + JULIAN_OF_EPOCH),
        "d_last_dom": _ints(last_dom.astype(np.int64) + JULIAN_OF_EPOCH),
        "d_same_day_ly": _ints(sk - 365),
        "d_same_day_lq": _ints(sk - 91),
        "d_current_day": yn(days == today),
        "d_current_week": yn(same(week_seq, week_seq)),
        "d_current_month": yn(same(month_seq, month_seq)),
        "d_current_quarter": yn(same(quarter_seq, quarter_seq)),
        "d_current_year": yn(same(year, year)),
    }


def _street(rng, n: int, nulls, prefix: str) -> dict:
    suite = _draw(rng, 0, 99, n)
    return {
        prefix + "street_number": _by_key(_draw(rng, 1, 1000, n), str, nulls.valid()),
        prefix + "street_name": _coded(_draw(rng, 0, len(STREET_NAMES) - 1, n), STREET_NAMES,
                                       nulls.valid()),
        prefix + "street_type": _coded(_draw(rng, 0, len(STREET_TYPES) - 1, n), STREET_TYPES,
                                       nulls.valid()),
        prefix + "suite_number": _by_key(
            suite, lambda k: f"Suite {k // 2 * 10}" if k % 2 else f"Suite {chr(65 + k // 2 % 26)}",
            nulls.valid()),
    }


def _place(rng, n: int, nulls, prefix: str) -> dict:
    """City, county, state, zip, country and GMT offset of n addresses;
    the state by its share of the nation's counties."""
    states = sorted(STATE_COUNTIES)
    share = np.array([STATE_COUNTIES[s] for s in states], dtype=np.float64)
    state = rng.choice(len(states), n, p=share / share.sum())
    county = _draw(rng, 0, len(COUNTY_WORDS) - 1, n)
    offset = np.array([-5, -6, -7, -8])[(state * 7 + 3) % 4]  # fixed by the state
    return {
        prefix + "city": _coded(_draw(rng, 0, len(CITIES) - 1, n), CITIES, nulls.valid()),
        prefix + "county": _coded(county, [w + " County" for w in COUNTY_WORDS], nulls.valid()),
        prefix + "state": _coded(state, states, nulls.valid()),
        prefix + "zip": _by_key(_draw(rng, 601, 99_950, n), lambda k: f"{k:05d}", nulls.valid()),
        prefix + "country": _coded(np.zeros(n, dtype=np.int64), ["United States"], nulls.valid()),
        prefix + "gmt_offset": _cents(offset * 100, nulls.valid()),
    }


def _customer_address(rng, n: int) -> dict:
    nulls = _Nulls(rng, n)
    sk = np.arange(1, n + 1, dtype=np.int64)
    return {
        "ca_address_sk": _ints(sk),
        "ca_address_id": _business_ids(sk),
        **_street(rng, n, nulls, "ca_"),
        **_place(rng, n, nulls, "ca_"),
        "ca_location_type": _coded(_draw(rng, 0, 2, n), LOCATION_TYPES, nulls.valid()),
    }


def _web_site(rng, n: int) -> dict:
    """A slowly changing dimension: a site's business key recurs, one
    row a revision; the last revision's end date is NULL."""
    nulls = _Nulls(rng, n)
    sk = np.arange(1, n + 1, dtype=np.int64)
    site = (sk - 1) // 3 * 2 + np.minimum((sk - 1) % 3, 1) + 1  # revisions 1, 2, 2 a pair of sites
    last = np.concatenate([site[1:] != site[:-1], [True]])
    revision = np.where(np.concatenate([[True], site[1:] != site[:-1]]), 0, 1)
    start = np.where(revision == 0, _days("1997-08-16"), _days("2000-08-16")).astype(np.int32)
    end = np.where(last, 0, _days("2000-08-15")).astype(np.int32)
    # dsdgen draws the id uniformly, a revision's anew: here the rows take
    # the ids in turn, a sixth of them each, so that every name is some
    # row's whatever the seed
    company = (sk - 1) % len(SYLLABLES) + 1
    person = lambda: _by_key(
        _draw(rng, 0, len(FIRST_NAMES) * len(LAST_NAMES) - 1, n),
        lambda k: f"{FIRST_NAMES[k // len(LAST_NAMES)]} {LAST_NAMES[k % len(LAST_NAMES)]}",
        nulls.valid())
    return {
        "web_site_sk": _ints(sk),
        "web_site_id": _business_ids(site),
        "web_rec_start_date": _dates(start, nulls.valid()),
        "web_rec_end_date": _dates(end, ~last),
        "web_name": _by_key(site, lambda k: f"site_{k - 1}", nulls.valid()),
        "web_open_date_sk": _ints(_draw(rng, 2_450_000, 2_450_800, n), nulls.valid()),
        "web_close_date_sk": _ints(_draw(rng, 2_440_000, 2_448_000, n), rng.random(n) < 0.2),
        "web_class": _coded(np.zeros(n, dtype=np.int64), ["Unknown"], nulls.valid()),
        "web_manager": person(),
        "web_mkt_id": _ints(_draw(rng, 1, 6, n), nulls.valid()),
        "web_mkt_class": _coded(_draw(rng, 0, len(MARKET_CLASSES) - 1, n), MARKET_CLASSES,
                                nulls.valid()),
        "web_mkt_desc": _coded(_draw(rng, 0, len(MARKET_DESCRIPTIONS) - 1, n),
                               MARKET_DESCRIPTIONS, nulls.valid()),
        "web_market_manager": person(),
        "web_company_id": _ints(company, nulls.valid()),
        # dsdgen writes the name from the id, so the two are NULL apart
        "web_company_name": _coded(company - 1, SYLLABLES, nulls.valid()),
        **_street(rng, n, nulls, "web_"),
        **_place(rng, n, nulls, "web_"),
        "web_tax_percentage": _cents(_draw(rng, 0, 12, n), nulls.valid()),
    }


def _pricing(rng, n: int) -> dict:
    """dsdgen's `set_pricing` for a sale, in whole cents."""
    quantity = _draw(rng, 1, 100, n)
    wholesale = _draw(rng, 100, 100_00, n)
    list_price = wholesale * (100 + _draw(rng, 0, 200, n)) // 100
    sales_price = list_price * (100 - _draw(rng, 0, 100, n)) // 100
    ext_sales = sales_price * quantity
    ext_list = list_price * quantity
    ext_wholesale = wholesale * quantity
    coupon = np.where(_draw(rng, 1, 100, n) <= 20, ext_sales * _draw(rng, 0, 100, n) // 100, 0)
    net_paid = ext_sales - coupon
    ship = list_price * _draw(rng, 0, 100, n) // 100 * quantity
    tax = net_paid * _draw(rng, 0, 9, n) // 100
    return {
        "quantity": quantity, "wholesale_cost": wholesale, "list_price": list_price,
        "sales_price": sales_price, "ext_discount_amt": ext_list - ext_sales,
        "ext_sales_price": ext_sales, "ext_wholesale_cost": ext_wholesale,
        "ext_list_price": ext_list, "ext_tax": tax, "coupon_amt": coupon, "ext_ship_cost": ship,
        "net_paid": net_paid, "net_paid_inc_tax": net_paid + tax,
        "net_paid_inc_ship": net_paid + ship, "net_paid_inc_ship_tax": net_paid + ship + tax,
        "net_profit": net_paid - ext_wholesale,
    }


def _distinct_items(rng, counts: np.ndarray, n_items: int) -> np.ndarray:
    """An item key for every row, no item twice in an order."""
    order = np.repeat(np.arange(len(counts), dtype=np.int64), counts)
    item = _draw(rng, 1, n_items, len(order))
    while True:
        by = np.lexsort((item, order))
        again = np.zeros(len(order), dtype=bool)
        again[by[1:]] = (order[by][1:] == order[by][:-1]) & (item[by][1:] == item[by][:-1])
        if not again.any():
            return item
        item[again] = _draw(rng, 1, n_items, int(again.sum()))


def _web_sales(rng, size: dict) -> dict:
    counts = items_per_order(rng, size["orders"], size["web_sales"])
    n = int(counts.sum())
    of_order = np.repeat(np.arange(size["orders"], dtype=np.int64), counts)
    nulls = _Nulls(rng, n)
    key = lambda per_order, hi: _ints(  # noqa: E731
        (_draw(rng, 1, hi, size["orders"])[of_order] if per_order else _draw(rng, 1, hi, n)),
        nulls.valid())
    sold = _draw(rng, _days(SALES_START), _days(SALES_END), size["orders"])[of_order]
    ship = sold + _draw(rng, 1, 120, n)
    bill_customer = _draw(rng, 1, size["customer"], size["orders"])
    # dsdgen: the goods go to the buyer in 85 orders of 100, else to another customer
    gift = rng.random(size["orders"]) >= 0.85
    ship_customer = np.where(gift, _draw(rng, 1, size["customer"], size["orders"]), bill_customer)
    bill_addr = _draw(rng, 1, size["customer_address"], size["orders"])
    ship_addr = np.where(gift, _draw(rng, 1, size["customer_address"], size["orders"]), bill_addr)
    price = _pricing(rng, n)
    money = lambda name: _cents(price[name], nulls.valid())  # noqa: E731
    return {
        "ws_sold_date_sk": _ints(sold + JULIAN_OF_EPOCH, nulls.valid()),
        "ws_sold_time_sk": key(True, size["time_dim"]),
        "ws_ship_date_sk": _ints(ship + JULIAN_OF_EPOCH, nulls.valid()),
        "ws_item_sk": _ints(_distinct_items(rng, counts, size["item"])),
        "ws_bill_customer_sk": _ints(bill_customer[of_order], nulls.valid()),
        "ws_bill_cdemo_sk": key(True, size["customer_demographics"]),
        "ws_bill_hdemo_sk": key(True, size["household_demographics"]),
        "ws_bill_addr_sk": _ints(bill_addr[of_order], nulls.valid()),
        "ws_ship_customer_sk": _ints(ship_customer[of_order], nulls.valid()),
        "ws_ship_cdemo_sk": key(True, size["customer_demographics"]),
        "ws_ship_hdemo_sk": key(True, size["household_demographics"]),
        "ws_ship_addr_sk": _ints(ship_addr[of_order], nulls.valid()),
        "ws_web_page_sk": key(True, size["web_page"]),
        "ws_web_site_sk": key(False, size["web_site"]),
        "ws_ship_mode_sk": key(False, size["ship_mode"]),
        "ws_warehouse_sk": key(False, size["warehouse"]),
        "ws_promo_sk": key(False, size["promotion"]),
        "ws_order_number": _ints(of_order + 1),
        "ws_quantity": _ints(price["quantity"], nulls.valid()),
        **{"ws_" + name: money(name) for name in price if name != "quantity"},
    }


def _web_returns(rng, size: dict, sales: dict) -> dict:
    """One item in ten comes back: a return names its sale's order and
    item, 1 to 120 days after the ship date."""
    n_sales = len(sales["ws_order_number"].data)
    n = ROWS_SF1["web_returns"] if size["web_sales"] is not None else int(round(RETURN_SHARE * n_sales))
    sale = np.sort(rng.choice(n_sales, n, replace=False))
    nulls = _Nulls(rng, n)
    key = lambda hi: _ints(_draw(rng, 1, hi, n), nulls.valid())  # noqa: E731
    shipped = sales["ws_ship_date_sk"].data[sale]
    shipped = np.where(sales["ws_ship_date_sk"].valid[sale], shipped,
                       _days(SALES_START) + JULIAN_OF_EPOCH)
    # dsdgen: the refund goes to the buyer's record in 80 returns of 100
    own = rng.random(n) < 0.8
    quantity = _draw(rng, 1, np.maximum(sales["ws_quantity"].data[sale], 1), n)
    amount = quantity * sales["ws_sales_price"].data[sale]
    tax = amount * _draw(rng, 0, 9, n) // 100
    fee = _draw(rng, 50, 100_00, n)
    ship_cost = quantity * (sales["ws_list_price"].data[sale] * _draw(rng, 0, 100, n) // 100)
    cash = amount * _draw(rng, 0, 100, n) // 100
    charge = (amount - cash) * _draw(rng, 0, 100, n) // 100
    money = lambda data: _cents(data, nulls.valid())  # noqa: E731

    def party(column, hi):
        mine = np.where(sales[column].valid[sale], sales[column].data[sale], 1)
        return np.where(own, mine, _draw(rng, 1, hi, n))

    refunded = party("ws_bill_customer_sk", size["customer"])
    refunded_addr = party("ws_bill_addr_sk", size["customer_address"])
    return {
        "wr_returned_date_sk": _ints(shipped + _draw(rng, 1, 120, n), nulls.valid()),
        "wr_returned_time_sk": key(size["time_dim"]),
        "wr_item_sk": _ints(sales["ws_item_sk"].data[sale]),
        "wr_refunded_customer_sk": _ints(refunded, nulls.valid()),
        "wr_refunded_cdemo_sk": key(size["customer_demographics"]),
        "wr_refunded_hdemo_sk": key(size["household_demographics"]),
        "wr_refunded_addr_sk": _ints(refunded_addr, nulls.valid()),
        "wr_returning_customer_sk": _ints(refunded, nulls.valid()),
        "wr_returning_cdemo_sk": key(size["customer_demographics"]),
        "wr_returning_hdemo_sk": key(size["household_demographics"]),
        "wr_returning_addr_sk": _ints(refunded_addr, nulls.valid()),
        "wr_web_page_sk": key(size["web_page"]),
        "wr_reason_sk": key(size["reason"]),
        "wr_order_number": _ints(sales["ws_order_number"].data[sale]),
        "wr_return_quantity": _ints(quantity, nulls.valid()),
        "wr_return_amt": money(amount),
        "wr_return_tax": money(tax),
        "wr_return_amt_inc_tax": money(amount + tax),
        "wr_fee": money(fee),
        "wr_return_ship_cost": money(ship_cost),
        "wr_refunded_cash": money(cash),
        "wr_reversed_charge": money(charge),
        "wr_account_credit": money(amount - cash - charge),
        "wr_net_loss": money(tax + fee + ship_cost),
    }


def generate(scale: float, seed: int) -> dict:
    """{table: {column: Column}} at scale factor `scale` from `seed`."""
    rng = np.random.default_rng(seed)
    size = sizes(scale)
    tables = {
        "date_dim": _date_dim(),
        "customer_address": _customer_address(rng, size["customer_address"]),
        "web_site": _web_site(rng, size["web_site"]),
    }
    tables["web_sales"] = _web_sales(rng, size)
    tables["web_returns"] = _web_returns(rng, size, tables["web_sales"])
    return tables
