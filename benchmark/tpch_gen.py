"""The benchmark's own TPC-H data generator.

A copy of the engine's dbgen-style generator (``cylon_tpu/tpch/dbgen.py``),
kept here so that no change to the program can change the benchmark's
data. Same tables, same value distributions, same random stream: for a
given scale factor and seed every column equals the engine generator's
(``benchmark/tests/test_copies.py`` holds the two equal). Every table
is generated at full width, the comment columns included.

Dates are int32 days since 1970-01-01. Row counts per scale factor
follow TPC-H: customer 150k, supplier 10k, part 200k, partsupp 800k,
orders 1.5M, lineitem ~6M (1-7 lines per order), nation 25, region 5.
"""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1).toordinal()

REGIONS = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"],
                   dtype=object)
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                     "MACHINERY"], dtype=object)
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                       "5-LOW"], dtype=object)
SHIPMODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                      "FOB"], dtype=object)
SHIPINSTRUCT = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                         "TAKE BACK RETURN"], dtype=object)
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "chartreuse", "chiffon", "chocolate", "coral", "cornflower",
          "cream", "cyan", "dark", "deep", "dim", "dodger", "drab",
          "firebrick", "floral", "forest", "frosted", "gainsboro",
          "ghost", "goldenrod", "green", "grey", "honeydew", "hot",
          "indian", "ivory", "khaki", "lace", "lavender", "lawn",
          "lemon", "light", "lime", "linen", "magenta", "maroon",
          "medium", "midnight", "mint", "misty", "moccasin", "navajo",
          "navy", "olive", "orange", "orchid", "pale", "papaya", "peach",
          "peru", "pink", "plum", "powder", "puff", "purple", "red",
          "rose", "rosy", "royal", "saddle", "salmon", "sandy",
          "seashell", "sienna", "sky", "slate", "smoke", "snow",
          "spring", "steel", "tan", "thistle", "tomato", "turquoise",
          "violet", "wheat", "white", "yellow"]
_VOCAB = np.array(
    ["packages", "requests", "accounts", "deposits", "foxes", "ideas",
     "theodolites", "instructions", "dependencies", "excuses", "platelets",
     "asymptotes", "courts", "dolphins", "multipliers", "warhorses",
     "sheaves", "decoys", "realms", "pearls", "sleep", "wake", "haggle",
     "nag", "cajole", "boost", "detect", "integrate", "engage", "doze",
     "snooze", "affix", "solve", "breach", "dazzle", "use", "play",
     "lose", "wade", "sublate", "regular", "final", "ironic", "even",
     "special", "express", "bold", "silent", "pending", "busy", "careful",
     "close", "dogged", "quick", "ruthless", "stealthy", "unusual",
     "quickly", "carefully", "furiously", "slyly", "blithely", "fluffily",
     "daringly", "evenly", "finally", "silently", "above", "against",
     "among", "beneath", "the"], dtype="U16")


def date_int(year: int, month: int, day: int) -> int:
    """Calendar date -> int32 days since 1970-01-01."""
    return datetime.date(year, month, day).toordinal() - _EPOCH


_START = date_int(1992, 1, 1)
_END = date_int(1998, 8, 2)


def _phrases(rng, n, k, max_chars=None):
    """n random k-word phrases, optionally cut to a varchar bound."""
    idx = rng.integers(0, len(_VOCAB), (n, k))
    out = _VOCAB[idx[:, 0]]
    for j in range(1, k):
        out = np.char.add(np.char.add(out, " "), _VOCAB[idx[:, j]])
    if max_chars is not None:
        out = out.astype(f"U{max_chars}")
    return out.astype(object)


def _inject_seq(rng, comments, frac, w1, w2):
    """Overwrite a ``frac`` of comments with '<w> w1 <w> w2 <w>' (the
    Q13/Q16 LIKE predicates)."""
    sel = rng.random(len(comments)) < frac
    k = int(sel.sum())
    if k:
        fill = _VOCAB[rng.integers(0, len(_VOCAB), (k, 3))]
        comments[sel] = np.char.add(np.char.add(np.char.add(np.char.add(
            fill[:, 0], f" {w1} "), fill[:, 1]), f" {w2} "), fill[:, 2]
        ).astype(object)
    return comments


def date_int(year: int, month: int, day: int) -> int:
    """Calendar date -> int32 days since 1970-01-01."""
    return datetime.date(year, month, day).toordinal() - _EPOCH


_START = date_int(1992, 1, 1)
_END = date_int(1998, 8, 2)


def generate(sf, seed):
    """All eight tables at full width, as ``{table: {column: np.ndarray}}``."""
    rng = np.random.default_rng(seed)
    n_cust = max(int(150_000 * sf), 10)
    n_supp = max(int(10_000 * sf), 5)
    n_ord = max(int(1_500_000 * sf), 20)
    n_part = max(int(200_000 * sf), 8)

    region = {"r_regionkey": np.arange(5, dtype=np.int64),
              "r_name": REGIONS.copy()}
    nation = {
        "n_nationkey": np.arange(len(NATIONS), dtype=np.int64),
        "n_name": np.array([n for n, _ in NATIONS], dtype=object),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
    }

    c_nationkey = rng.integers(0, len(NATIONS), n_cust).astype(np.int64)
    phone_tail = rng.integers(0, 10_000_000, n_cust)
    customer = {"c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
                "c_nationkey": c_nationkey}
    customer["c_mktsegment"] = SEGMENTS[
        rng.integers(0, len(SEGMENTS), n_cust)]
    customer["c_acctbal"] = np.round(rng.uniform(-999.99, 9999.99, n_cust), 2)
    customer["c_phone"] = np.array(
        [f"{nk + 10}-{t % 1000:03d}-{(t // 1000) % 1000:03d}-"
         f"{t // 1_000_000:04d}"
         for nk, t in zip(c_nationkey, phone_tail)], dtype=object)

    supplier = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
        "s_name": np.array([f"Supplier#{i:09d}" for i in range(1, n_supp + 1)],
                           dtype=object),
    }
    supplier["s_nationkey"] = rng.integers(
        0, len(NATIONS), n_supp).astype(np.int64)
    supplier["s_acctbal"] = np.round(rng.uniform(-999.99, 9999.99, n_supp), 2)
    supplier["s_comment"] = _inject_seq(
        rng, _phrases(rng, n_supp, 6), 0.01, "Customer", "Complaints")

    p_type = np.array(
        [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2 for c in TYPE_S3],
        dtype=object)
    p_container = np.array(
        [f"{a} {b}" for a in CONTAINER_S1 for b in CONTAINER_S2],
        dtype=object)
    brands = np.array([f"Brand#{m}{n}" for m in range(1, 6)
                       for n in range(1, 6)], dtype=object)
    colors = np.array(COLORS, dtype=object)
    name_a = colors[rng.integers(0, len(colors), n_part)]
    name_b = colors[rng.integers(0, len(colors), n_part)]
    part = {"p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
            "p_name": np.array([f"{a} {b}" for a, b in zip(name_a, name_b)],
                               dtype=object)}
    part["p_mfgr"] = np.array(
        [f"Manufacturer#{m}" for m in rng.integers(1, 6, n_part)],
        dtype=object)
    part["p_brand"] = brands[rng.integers(0, len(brands), n_part)]
    part["p_type"] = p_type[rng.integers(0, len(p_type), n_part)]
    part["p_size"] = rng.integers(1, 51, n_part).astype(np.int64)
    part["p_container"] = p_container[
        rng.integers(0, len(p_container), n_part)]
    part["p_retailprice"] = np.round(rng.uniform(900.0, 2000.0, n_part), 2)

    # 4 distinct suppliers per part: base + i*step mod S, 0 < step <= (S-1)/3
    ps_partkey = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
    n_ps = len(ps_partkey)
    base = rng.integers(0, n_supp, n_part)
    step = rng.integers(1, max((n_supp - 1) // 3, 1) + 1, n_part)
    partsupp = {
        "ps_partkey": ps_partkey,
        "ps_suppkey": ((base[:, None] + np.arange(4)[None, :] * step[:, None])
                       % n_supp + 1).reshape(-1).astype(np.int64),
    }
    partsupp["ps_availqty"] = rng.integers(1, 10_000, n_ps).astype(np.int64)
    partsupp["ps_supplycost"] = np.round(rng.uniform(1.0, 1000.0, n_ps), 2)

    o_orderdate = rng.integers(_START, _END + 1, n_ord).astype(np.int32)
    cut_f = date_int(1995, 6, 1)
    cut_o = date_int(1995, 6, 30)
    orders = {"o_orderkey": np.arange(1, n_ord + 1, dtype=np.int64)}
    orders["o_custkey"] = rng.integers(1, n_cust + 1, n_ord).astype(np.int64)
    orders["o_orderstatus"] = np.where(
        o_orderdate < cut_f, "F",
        np.where(o_orderdate > cut_o, "O", "P")).astype(object)
    orders["o_orderdate"] = o_orderdate
    orders["o_orderpriority"] = PRIORITIES[
        rng.integers(0, len(PRIORITIES), n_ord)]
    orders["o_shippriority"] = np.zeros(n_ord, dtype=np.int64)
    orders["o_totalprice"] = np.round(rng.uniform(800.0, 500_000.0, n_ord), 2)
    orders["o_comment"] = _inject_seq(
        rng, _phrases(rng, n_ord, 5), 0.02, "special", "requests")

    per_order = rng.integers(1, 8, n_ord)
    l_orderkey = np.repeat(np.arange(1, n_ord + 1, dtype=np.int64),
                           per_order)
    n_li = len(l_orderkey)
    l_orderdate = np.repeat(o_orderdate, per_order)
    l_shipdate = (l_orderdate + rng.integers(1, 122, n_li)).astype(np.int32)
    # the supplier is one of the part's four in partsupp
    l_partkey = rng.integers(1, n_part + 1, n_li).astype(np.int64)
    l_suppkey = ((base[l_partkey - 1]
                  + rng.integers(0, 4, n_li) * step[l_partkey - 1])
                 % n_supp + 1).astype(np.int64)
    lineitem = {"l_orderkey": l_orderkey, "l_partkey": l_partkey,
                "l_suppkey": l_suppkey}
    lineitem["l_quantity"] = rng.integers(1, 51, n_li).astype(np.int64)
    lineitem["l_extendedprice"] = np.round(
        rng.uniform(900.0, 105_000.0, n_li), 2)
    lineitem["l_discount"] = np.round(rng.integers(0, 11, n_li) / 100.0, 2)
    lineitem["l_tax"] = np.round(rng.integers(0, 9, n_li) / 100.0, 2)
    lineitem["l_returnflag"] = np.array(["R", "A", "N"])[
        rng.integers(0, 3, n_li)]
    lineitem["l_linestatus"] = np.array(["O", "F"])[rng.integers(0, 2, n_li)]
    lineitem["l_shipdate"] = l_shipdate
    lineitem["l_commitdate"] = (
        l_orderdate + rng.integers(30, 91, n_li)).astype(np.int32)
    lineitem["l_receiptdate"] = (
        l_shipdate + rng.integers(1, 31, n_li)).astype(np.int32)
    lineitem["l_shipmode"] = SHIPMODES[rng.integers(0, len(SHIPMODES), n_li)]
    lineitem["l_shipinstruct"] = SHIPINSTRUCT[
        rng.integers(0, len(SHIPINSTRUCT), n_li)]
    lineitem["l_comment"] = _phrases(rng, n_li, 4, max_chars=44)
    return {"region": region, "nation": nation, "customer": customer,
            "supplier": supplier, "part": part, "partsupp": partsupp,
            "orders": orders, "lineitem": lineitem}


def to_pandas(data):
    """A :func:`generate` mapping as pandas DataFrames."""
    import pandas as pd

    return {name: pd.DataFrame(cols) for name, cols in data.items()}
