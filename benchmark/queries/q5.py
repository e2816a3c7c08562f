"""TPC-H Q5, local supplier volume: a six-table join restricted to one
region and one year, summed per nation.

``READS`` is what the query reads, table by table. ``reference`` is a
plain pandas transcription of the SQL at the validation parameters.
"""

from tpch_gen import date_int

READS = {
    "customer": ["c_custkey", "c_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                 "l_discount"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}


def reference(pdfs):
    region = "ASIA"
    date_from, date_to = date_int(1994, 1, 1), date_int(1995, 1, 1)
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
