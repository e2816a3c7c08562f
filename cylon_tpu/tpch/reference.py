"""Plain pandas implementations of TPC-H queries: the reference the
engine's answers are compared with (tests, ``chip_smoke.py``).

Straight transcriptions of the SQL over :func:`dbgen.generate_pandas`
frames, sharing no code with the engine.
"""

from cylon_tpu.tpch.dbgen import date_int


def q1_pandas(pdfs, cutoff=None):
    if cutoff is None:
        cutoff = date_int(1998, 9, 2)
    li = pdfs["lineitem"]
    li = li[li["l_shipdate"] <= cutoff].copy()
    li["disc_price"] = li["l_extendedprice"] * (1 - li["l_discount"])
    li["charge"] = li["disc_price"] * (1 + li["l_tax"])
    want = li.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "count"),
    )
    return (want.reset_index()
            .sort_values(["l_returnflag", "l_linestatus"])
            .reset_index(drop=True))


def q3_pandas(pdfs, segment="BUILDING", cutoff=None, limit=10):
    if cutoff is None:
        cutoff = date_int(1995, 3, 15)
    c = pdfs["customer"]
    o = pdfs["orders"]
    l = pdfs["lineitem"]
    c = c[c.c_mktsegment == segment]
    o = o[o.o_orderdate < cutoff]
    l = l[l.l_shipdate > cutoff].copy()
    l["revenue"] = l.l_extendedprice * (1 - l.l_discount)
    j = l.merge(o.merge(c, left_on="o_custkey", right_on="c_custkey"),
                left_on="l_orderkey", right_on="o_orderkey")
    g = (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                   as_index=False)["revenue"].sum())
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(limit)
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]


def q5_pandas(pdfs, region="ASIA", date_from=None, date_to=None):
    if date_from is None:
        date_from = date_int(1994, 1, 1)
    if date_to is None:
        date_to = date_int(1995, 1, 1)
    r = pdfs["region"]
    n = pdfs["nation"]
    s = pdfs["supplier"]
    c = pdfs["customer"]
    o = pdfs["orders"]
    l = pdfs["lineitem"].copy()
    l["revenue"] = l.l_extendedprice * (1 - l.l_discount)
    r = r[r.r_name == region]
    nat = n.merge(r, left_on="n_regionkey", right_on="r_regionkey")
    sup = s.merge(nat, left_on="s_nationkey", right_on="n_nationkey")
    o = o[(o.o_orderdate >= date_from) & (o.o_orderdate < date_to)]
    j = (l.merge(o.merge(c, left_on="o_custkey", right_on="c_custkey"),
                 left_on="l_orderkey", right_on="o_orderkey")
          .merge(sup, left_on="l_suppkey", right_on="s_suppkey"))
    j = j[j.c_nationkey == j.s_nationkey]
    g = j.groupby("n_name", as_index=False)["revenue"].sum()
    return g.sort_values("revenue", ascending=False)[["n_name", "revenue"]]


def q6_pandas(pdfs, date_from=None, date_to=None):
    if date_from is None:
        date_from = date_int(1994, 1, 1)
    if date_to is None:
        date_to = date_int(1995, 1, 1)
    li = pdfs["lineitem"]
    m = ((li["l_shipdate"] >= date_from) & (li["l_shipdate"] < date_to)
         & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
         & (li["l_quantity"] < 24))
    return float((li[m]["l_extendedprice"] * li[m]["l_discount"]).sum())
