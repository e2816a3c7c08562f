"""TPC-H Q1, pricing summary report: a scan of lineitem and a groupby
into four (returnflag, linestatus) groups.

``READS`` is what the query reads, table by table. ``reference`` is a
plain pandas transcription of the SQL at the validation parameters.
"""

from tpch_gen import date_int

READS = {
    "lineitem": ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate"],
}


def reference(pdfs):
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
