"""TPC-H Q6, forecasting revenue change: a filtered scan of lineitem
summed to one number.

``READS`` is what the query reads, table by table. ``reference`` is a
plain pandas transcription of the SQL at the validation parameters.
"""

from tpch_gen import date_int

READS = {
    "lineitem": ["l_quantity", "l_extendedprice", "l_discount",
                 "l_shipdate"],
}


def reference(pdfs):
    date_from, date_to = date_int(1994, 1, 1), date_int(1995, 1, 1)
    li = pdfs["lineitem"]
    m = ((li["l_shipdate"] >= date_from) & (li["l_shipdate"] < date_to)
         & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
         & (li["l_quantity"] < 24))
    return float((li[m]["l_extendedprice"] * li[m]["l_discount"]).sum())
