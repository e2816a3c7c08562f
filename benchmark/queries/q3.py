"""TPC-H Q3, shipping priority: customer -> orders -> lineitem joins,
a groupby per order and the top 10 by revenue.

``READS`` is what the query reads, table by table. ``reference`` is a
plain pandas transcription of the SQL at the validation parameters.
"""

from tpch_gen import date_int

READS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"],
}


def reference(pdfs):
    segment, cutoff, limit = "BUILDING", date_int(1995, 3, 15), 10
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
